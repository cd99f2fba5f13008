"""Serving launcher: the functional server, with prefix-cache accounting,
for any registered model (the assigned ARCHS and the paper's
PAPER_MODELS) at its published widths, or reduced for a CPU run.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
      --requests 6 [--max-new 8]                       # on a TPU
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve \
      --arch tinyllama-1.1b --reduced                  # on the CPU
"""
from __future__ import annotations

import argparse

import numpy as np

from ..configs import ARCHS, PAPER_MODELS, get_config
from ..serving import FunctionalServer
from .compile_cache import CompileCounter, enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=sorted({**ARCHS, **PAPER_MODELS}))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the 2-layer variant of the family (CPU runs)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--repeat-every", type=int, default=3,
                    help="every Nth request reuses a prompt (prefix hits)")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    srv = FunctionalServer(cfg, max_running=2,
                           device_budget_tokens=2 * args.max_len,
                           max_len=args.max_len, page_size=16)
    rng = np.random.default_rng(0)
    base_prompt = rng.integers(0, cfg.vocab, size=args.prompt_len)
    for i in range(args.requests):
        if args.repeat_every and i % args.repeat_every == 0:
            p = base_prompt
        else:
            p = rng.integers(0, cfg.vocab, size=args.prompt_len)
        srv.submit(p, max_new_tokens=args.max_new)
    done = srv.run_until_done()
    for r in done:
        print(f"req {r.req_id}: hit {r.hit_tokens:3d} tokens  "
              f"generated {r.generated}")
    hits = sum(1 for r in done if r.hit_tokens)
    print(f"{len(done)} served, {hits} prefix hits; transfers: "
          f"{srv.transfer_log}")
    print(compiles.summary(cache_dir))


if __name__ == "__main__":
    main()
