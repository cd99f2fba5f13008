import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and extract memory / cost / collective stats.

The two lines above MUST stay the first statements in this module — jax
locks the device count at first init, and the dry-run (and ONLY the
dry-run) needs 512 placeholder CPU devices for the 2x16x16 mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k [--multi-pod] [--all] [--out results.json]
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCHS, INPUT_SHAPES, get_config
from ..distributed.sharding import (
    batch_shardings,
    cache_shardings,
    data_pspec,
    params_shardings,
    replicated,
)
from ..launch.mesh import make_production_mesh
from ..launch.roofline import (
    CollectiveStats,
    collective_stats,
    model_flops_estimate,
    roofline_terms,
)
from ..launch.specs import abstract_state, input_specs, make_step
from ..models.init import abstract_params
from jax.sharding import NamedSharding, PartitionSpec as P


def _cost_get(cost, key: str) -> float:
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost.get(key, 0.0))


def _body_cost(cfg, shape, mesh, kind, specs, params) -> Optional[Dict]:
    """Compile ONE standalone super-block (the scan body) under the same
    mesh/shardings and return its (flops, bytes, collective) cost.

    XLA's cost model counts a while-loop body once, so the scanned module
    understates per-step cost by ~n_periods; the dry-run reports
    corrected = module + (n_periods - 1) x body. Validated against fully
    unrolled lowering (see EXPERIMENTS.md §Dry-run).
    """
    import jax.numpy as jnp

    from ..launch.specs import effective_window, sds
    from ..models.transformer import super_block

    W = effective_window(cfg, INPUT_SHAPES[shape.name])
    strip = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), tree
    )
    pp = strip(params["blocks"])
    pp_sh = params_shardings(pp, mesh)
    B = shape.global_batch
    S = 1 if kind == "decode" else shape.seq_len
    x = sds((B, S, cfg.d_model), cfg.dtype)
    x_sh = batch_shardings(x, mesh)
    frontend = specs.get("frontend") if isinstance(specs, dict) else None
    if kind == "train" and "batch" in specs:
        frontend = specs["batch"].get("frontend")
    f_args = [frontend] if frontend is not None else []
    f_sh = [batch_shardings(frontend, mesh)] if frontend is not None else []

    if kind == "train":
        def body(pp, x, *fa):
            fr = fa[0] if fa else None

            def f(pp_, x_):
                out, _, aux = super_block(
                    pp_, x_, cfg, mode="train", frontend=fr,
                    caches=None, cache_len=None, window=0,
                )
                return jnp.sum(out.astype(jnp.float32)) + aux

            # value_and_grad keeps the primal forward alive (grad alone
            # lets XLA DCE it, undercounting remat fwd+fwd+bwd ~ 4x fwd)
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if cfg.remat_policy == "dots" else None
            )
            return jax.value_and_grad(
                jax.checkpoint(f, policy=policy), argnums=(0, 1)
            )(pp, x)

        jitted = jax.jit(body, in_shardings=(pp_sh, x_sh, *f_sh))
        lowered = jitted.lower(pp, x, *f_args)
    else:
        if "caches" in specs:
            caches_p = specs["caches"]
        else:  # prefill creates its caches internally; rebuild abstractly
            from ..models.transformer import init_caches

            caches_p = jax.eval_shape(
                lambda: init_caches(cfg, B, shape.seq_len, W)
            )
        if cfg.first_k_dense:      # one scanned period: not the lead layers
            caches_p = caches_p["blocks"]
        caches_p = strip(caches_p)
        c_sh = cache_shardings(caches_p, mesh)
        if kind == "prefill":
            def body(pp, x, caches, *fa):
                return super_block(
                    pp, x, cfg, mode="prefill",
                    frontend=fa[0] if fa else None,
                    caches=caches, cache_len=None, window=W,
                )
            jitted = jax.jit(body, in_shardings=(pp_sh, x_sh, c_sh, *f_sh))
            lowered = jitted.lower(pp, x, caches_p, *f_args)
        else:
            clen = sds((), jnp.int32)
            def body(pp, x, caches, cache_len, *fa):
                return super_block(
                    pp, x, cfg, mode="decode",
                    frontend=fa[0] if fa else None,
                    caches=caches, cache_len=cache_len, window=W,
                )
            jitted = jax.jit(
                body,
                in_shardings=(pp_sh, x_sh, c_sh, replicated(mesh), *f_sh),
            )
            lowered = jitted.lower(pp, x, caches_p, clen, *f_args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_stats(compiled.as_text())
    return {
        "flops": _cost_get(cost, "flops"),
        "bytes": _cost_get(cost, "bytes accessed"),
        "coll": coll,
    }


def dryrun_one(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    verbose: bool = True,
    scan_layers: bool = True,
    correct_scan: bool = True,
) -> Dict[str, Any]:
    """Lower+compile one combo. ``scan_layers=True`` keeps compile time
    bounded (layers as a lax.scan); ``correct_scan`` then compiles one
    standalone super-block and reports module + (n_periods-1) x body so
    the roofline terms match the fully-unrolled ground truth (validated:
    tinyllama train_4k unrolled vs corrected agree within a few %)."""
    import dataclasses

    from ..distributed.sharding import OPT as _OPT0

    cfg = get_config(arch, shape=shape_name)
    repl = dict(
        scan_layers=scan_layers,
        remat_policy="dots" if _OPT0["remat_dots"] else "full",
        moe_ep=_OPT0["moe_ep"],
    )
    if _OPT0.get("ssm_chunk"):
        repl["ssm_chunk"] = int(_OPT0["ssm_chunk"])
    cfg = dataclasses.replace(cfg, **repl)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    step_fn, kind = make_step(cfg, shape)
    specs = input_specs(cfg, shape)
    t0 = time.monotonic()

    with jax.set_mesh(mesh):
        params = abstract_params(cfg)
        p_sh = params_shardings(params, mesh)
        rep = replicated(mesh)
        if kind == "train":
            from ..training.optimizer import init_adamw

            from ..distributed.sharding import OPT as _OPTz, zero1_shardings

            opt = jax.eval_shape(lambda: init_adamw(params))
            shard_fn = (
                zero1_shardings if _OPTz["zero1"] else params_shardings
            )
            o_sh = shard_fn({"mu": opt.mu, "nu": opt.nu}, mesh)
            opt_sh = type(opt)(step=rep, mu=o_sh["mu"], nu=o_sh["nu"])
            b_sh = batch_shardings(specs["batch"], mesh)
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_sh, opt_sh, b_sh),
                out_shardings=(p_sh, opt_sh, None),
            )
            lowered = jitted.lower(params, opt, specs["batch"])
        elif kind == "prefill":
            in_sh = [p_sh] + [
                batch_shardings(specs[k], mesh)
                for k in ("tokens", "frontend", "inputs_embeds")
                if k in specs
            ]
            args = [params] + [
                specs[k]
                for k in ("tokens", "frontend", "inputs_embeds")
                if k in specs
            ]
            jitted = jax.jit(
                step_fn, in_shardings=tuple(in_sh), out_shardings=None
            )
            lowered = jitted.lower(*args)
        else:  # decode
            c_sh = cache_shardings(specs["caches"], mesh)
            in_sh = [p_sh, batch_shardings(specs["token"], mesh), c_sh, rep]
            args = [params, specs["token"], specs["caches"],
                    specs["cache_len"]]
            if "frontend" in specs:
                in_sh.append(batch_shardings(specs["frontend"], mesh))
                args.append(specs["frontend"])
            from ..distributed.sharding import OPT as _OPT

            jitted = jax.jit(
                step_fn,
                in_shardings=tuple(in_sh),
                out_shardings=(
                    NamedSharding(mesh, data_pspec(
                        (shape.global_batch, cfg.vocab), mesh)),
                    c_sh,
                ),
                donate_argnums=(2,) if _OPT["donate_caches"] else (),
            )
            lowered = jitted.lower(*args)

        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    flops = _cost_get(cost, "flops")
    hbm_bytes = _cost_get(cost, "bytes accessed")
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    if scan_layers and correct_scan and cfg.n_periods > 1:
        with jax.set_mesh(mesh):
            body = _body_cost(cfg, shape, mesh, kind, specs, params)
        k = cfg.n_periods - 1
        flops += k * body["flops"]
        hbm_bytes += k * body["bytes"]
        bc: CollectiveStats = body["coll"]
        for kk in coll.bytes_by_kind:
            coll.bytes_by_kind[kk] += k * bc.bytes_by_kind[kk]
            coll.count_by_kind[kk] += k * bc.count_by_kind[kk]
    mf = model_flops_estimate(cfg, shape)
    rf = roofline_terms(flops, hbm_bytes, coll, model_flops=mf,
                        n_chips=n_chips)

    mem_fields = {}
    for f in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
    ):
        v = getattr(mem, f, None)
        if v is not None:
            mem_fields[f] = int(v)

    from ..distributed.sharding import OPT

    result = {
        "arch": arch,
        "shape": shape_name,
        "kind": kind,
        "opt": ",".join(sorted(k for k, v in OPT.items() if v)),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": flops,
        "hbm_bytes_per_device": hbm_bytes,
        "collective_bytes_per_device": coll.total_bytes,
        "collective_counts": coll.count_by_kind,
        "compute_s": rf.compute_s,
        "memory_s": rf.memory_s,
        "collective_s": rf.collective_s,
        "dominant": rf.dominant,
        "model_flops": mf,
        "flops_ratio": rf.flops_ratio,
        "memory_analysis": mem_fields,
    }
    if verbose:
        print(
            f"[{result['mesh']}] {arch} x {shape_name} ({kind}): "
            f"compile {t_compile:.1f}s  "
            f"flops/dev {flops:.3g}  hbm/dev {hbm_bytes:.3g}B  "
            f"coll/dev {coll.total_bytes:.3g}B  dominant={rf.dominant}  "
            f"useful-flops-ratio {rf.flops_ratio:.2f}"
        )
        print(f"  memory_analysis: {mem_fields}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all 10 archs x 4 shapes")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--opt", default="",
                    help="comma-separated perf options (kv_seq_shard, "
                         "zero1, donate_caches, remat_dots, moe_ep) — "
                         "see §Perf")
    ap.add_argument("--ssm-chunk", type=int, default=0,
                    help="override cfg.ssm_chunk (§Perf hillclimb C)")
    args = ap.parse_args()

    from ..distributed.sharding import OPT

    for o in filter(None, args.opt.split(",")):
        assert o in OPT, f"unknown opt {o}"
        OPT[o] = True
    if args.ssm_chunk:
        OPT["ssm_chunk"] = args.ssm_chunk

    combos = []
    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = (
        list(INPUT_SHAPES) if (args.all or args.shape is None)
        else [args.shape]
    )
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    results = []
    failures = 0
    for a, s, mp in combos:
        try:
            r = dryrun_one(a, s, multi_pod=mp)
        except Exception as e:
            failures += 1
            r = {
                "arch": a, "shape": s,
                "mesh": "2x16x16" if mp else "16x16",
                "ok": False, "error": f"{type(e).__name__}: {e}",
            }
            print(f"FAIL {a} x {s} ({r['mesh']}): {r['error']}")
            traceback.print_exc()
        results.append(r)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(r) + "\n")
    print(f"\n{len(results) - failures}/{len(results)} combos compiled OK")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
