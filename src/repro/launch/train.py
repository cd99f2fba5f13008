"""Training launcher: runs a (reduced or custom) architecture on the
locally available devices with the production sharding rules.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 100 [--no-reduced] [--batch 8] [--seq 128] [--model-parallel 1]

On a real TPU slice the same entry point picks up all devices; on CPU it
demonstrates the full path (mesh, sharded params, jitted step, data
pipeline, checkpointing) at reduced scale.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from ..configs import ARCHS, get_config
from ..distributed.sharding import batch_shardings, params_shardings
from ..models import init_params
from ..training import (
    AdamWConfig,
    DataConfig,
    SyntheticTokenStream,
    TrainConfig,
    init_adamw,
    make_train_step,
)
from .compile_cache import CompileCounter, enable_compile_cache
from .mesh import make_host_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the 2-layer variant (default); --no-reduced "
                         "trains the published widths")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--moe-ep", action="store_true")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, dtype=jnp.float32, moe_ep=args.moe_ep)
    mesh = make_host_mesh(model=args.model_parallel)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}  "
          f"arch: {cfg.name}")

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_adamw(params)
    data = SyntheticTokenStream(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch)
    )
    tc = TrainConfig(
        steps=args.steps, remat=True,
        opt=AdamWConfig(lr=args.lr, warmup_steps=10,
                        total_steps=args.steps),
    )
    step = make_train_step(cfg, tc)
    with jax.set_mesh(mesh):
        p_sh = params_shardings(params, mesh)
        o_sh = type(opt)(
            step=None,
            mu=params_shardings(opt.mu, mesh),
            nu=params_shardings(opt.nu, mesh),
        )
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, None),
                         out_shardings=(p_sh, o_sh, None))
        for i in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
            params, opt, metrics = jitted(params, opt, batch)
            if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
                print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.2f}")
    print(compiles.summary(cache_dir))
    print("done")


if __name__ == "__main__":
    main()
