"""Bring-up smoke: the serving path and the multipath data plane on a TPU.

  python3 chip_smoke.py                 # one chip
  python3 chip_smoke.py --four-chips    # a four-chip host

On one chip it serves qwen3-4b at its published widths (36 layers,
d_model 2560, 32/8 heads of 128, d_ff 9728, vocab 151,936, bf16, seeded
random weights) through ``FunctionalServer``, then:

  (a) serves 4 requests of 2048 prompt tokens, two sharing a 1536-token
      prefix, 16 new tokens each, after one warm-up request of the same
      shape; no executable may be built after the warm-up;
  (b) checks prefill + 8 cached decode steps against one ``forward`` over
      the whole sequence, logits against logits;
  (c) sleeps the served weights D2H and wakes them H2D through
      ``WeightManager`` on the functional multipath engine, checks them
      bit for bit, and serves one more request on them;
  (d) sends that request's KV caches D2H and back H2D through
      ``multipath_device_get`` / ``multipath_device_put``, bit for bit.

With ``--four-chips`` it runs only the relay path: host->chip 0 copies of
64 MiB and 4 GiB relayed over chips 1-3, and the D2H direction, each
against a plain single-path copy.

Times are host-clock seconds around work that ends in a blocking read.
Prefix hits are counts: the server still times its KV transfers on a
simulator, and no simulated time is printed here. One process holds the
chip(s) and starts no other. The last line of stdout is
``{"ok": true, "device": {...}}``; the script exits non-zero without it
when JAX finds no TPU, when a check fails or when a phase raises.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import weakref

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import PAPER_MODELS  # noqa: E402
from repro.core import (  # noqa: E402
    MMAConfig,
    make_functional_engine,
    multipath_device_get,
    multipath_device_put,
)
from repro.launch.compile_cache import (  # noqa: E402
    CompileCounter,
    enable_compile_cache,
)
from repro.models import forward  # noqa: E402
from repro.serving import FunctionalServer, WeightManager  # noqa: E402
from repro.serving.engine import jit_decode_step, jit_prefill  # noqa: E402

MODEL = "qwen3-4b"
MAX_LEN = 4096
PROMPT = 2048
SHARED = 1536
NEW_TOKENS = 16
CHECK_STEPS = 8
# Chunks of tens of MiB keep a multi-GB leaf to a few dozen parts:
# ChunkAssembler concatenates every part, once per leaf shape.
CHUNK_BYTES = 64 << 20
# Cached path vs whole-sequence forward, as ||a - b|| / ||b|| per position.
# Both run in bf16 with f32 accumulation, but the decode step contracts one
# query against the cache where forward contracts the whole sequence, so
# sums are ordered differently and every layer rounds its bf16 output at
# 2**-8 relative. Over 36 layers such differences grow like a random walk
# (sqrt(72) * 2**-8 ~ 0.03). A cache bug (wrong slot, position or mask)
# gives unrelated logits, a relative error near 1.
LOGIT_RTOL = 0.05
# A sleep must free the weights' HBM, less what the sleep itself leaves
# allocated (1,352,704 B on a v5e, not attributed). 1% of qwen3-4b is
# 88 MB: below its smallest stacked weight (w_k, 189 MB), so an engine
# that keeps any large leaf or a copy of one alive still fails.
FREED_SLACK = 0.01


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def memory(dev) -> str:
    stats = dev.memory_stats()
    return (f"device memory: in use {stats['bytes_in_use']} B, "
            f"peak {stats['peak_bytes_in_use']} B")


def rate(nbytes: int, seconds: float) -> str:
    return f"{nbytes} B in {seconds} s = {nbytes / seconds / 1e9} GB/s"


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)))


def trees_same_bits(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        same_bits(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------
def phase_serve(srv: FunctionalServer, rng, compiles: CompileCounter):
    """(a) Warm up on one request of the measured shape, then serve four;
    returns the prompts and the finished requests."""
    vocab = srv.cfg.vocab
    t0 = time.perf_counter()
    srv.submit(rng.integers(0, vocab, PROMPT), max_new_tokens=NEW_TOKENS)
    srv.run_until_done()
    print(f"(a) warm-up request (compiles prefill and decode): "
          f"{time.perf_counter() - t0} s; {compiles.built} executables "
          f"built so far")

    built = compiles.built
    prefix = rng.integers(0, vocab, SHARED)
    tail = lambda: rng.integers(0, vocab, PROMPT - SHARED)
    prompts = [np.concatenate([prefix, tail()]), rng.integers(0, vocab, PROMPT),
               np.concatenate([prefix, tail()]), rng.integers(0, vocab, PROMPT)]
    t0 = time.perf_counter()
    submitted = time.monotonic()        # the server's clock
    reqs = [srv.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    srv.run_until_done()
    print(f"(a) served {len(reqs)} requests of {PROMPT} prompt tokens, "
          f"{NEW_TOKENS} new tokens each, two running at once: "
          f"{time.perf_counter() - t0} s")
    for i, r in enumerate(reqs):
        print(f"(a) request {i}: TTFT {r.ttft} s from prefill start, "
              f"{r.first_token_at - submitted} s from submission; "
              f"prefix-hit tokens (count): {r.hit_tokens}; "
              f"generated {len(r.generated)} tokens")
    after = compiles.built - built
    print(f"(a) executables built after warm-up: {after}")
    check(after == 0, f"{after} executables built after warm-up")
    check(all(len(r.generated) == NEW_TOKENS for r in reqs),
          "a request did not generate all its tokens")
    check(all(0 <= t < vocab for r in reqs for t in r.generated),
          "a generated token is outside the vocabulary")
    hits = [r.hit_tokens for r in reqs]
    check(hits == [0, 0, SHARED, 0],
          f"prefix-hit tokens {hits}, expected [0, 0, {SHARED}, 0]")
    return prompts, reqs


def phase_cached_path(srv: FunctionalServer, rng) -> None:
    """(b) Prefill + ``CHECK_STEPS`` cached decode steps vs one forward."""
    cfg, prompt, steps = srv.cfg, PROMPT, CHECK_STEPS
    seq = rng.integers(0, cfg.vocab, prompt + steps).astype(np.int32)
    t0 = time.perf_counter()
    logits, caches, clen = jit_prefill(
        srv.params, jnp.asarray(seq[None, :prompt]), cfg, max_len=srv.max_len
    )
    cached = [logits[0]]
    for i in range(steps):
        logits, caches = jit_decode_step(
            srv.params, jnp.asarray(seq[prompt + i:prompt + i + 1]), caches,
            clen + i, cfg,
        )
        cached.append(logits[0])
    cached = np.asarray(jnp.stack(cached), np.float32)
    whole = jax.jit(lambda p, t: forward(p, t, cfg)[0][0, prompt - 1:])
    ref = np.asarray(whole(srv.params, jnp.asarray(seq[None])), np.float32)
    err = np.linalg.norm(cached - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    print(f"(b) prefill + {steps} cached decode steps vs forward over "
          f"{prompt + steps} tokens: {time.perf_counter() - t0} s "
          f"(compiles forward); relative logit error per position "
          f"{err.tolist()}, max |diff| {float(np.abs(cached - ref).max())}, "
          f"max |logit| {float(np.abs(ref).max())}, tolerance {LOGIT_RTOL}")
    check(bool(np.isfinite(cached).all()), "non-finite logits")
    check(cached.shape == (steps + 1, cfg.vocab), f"shape {cached.shape}")
    check(float(err.max()) <= LOGIT_RTOL,
          f"cached-path logits off by {float(err.max())} > {LOGIT_RTOL}")


def phase_switch(srv: FunctionalServer, engine, dev, prompt, served,
                 compiles: CompileCounter):
    """(c) Sleep and wake the served weights through the multipath engine;
    returns the KV caches of one request served on the woken weights."""
    t0 = time.perf_counter()
    reference = jax.device_get(srv.params)
    d2h_s = time.perf_counter() - t0
    params = srv.release_params()
    wm = WeightManager(engine, params=params)
    leaves = [weakref.ref(leaf) for leaf in jax.tree.leaves(params)]
    del params
    print(f"(c) reference copy, plain D2H: {rate(wm.nbytes, d2h_s)}")
    before = dev.memory_stats()["bytes_in_use"]
    slept = wm.sleep()
    freed = before - dev.memory_stats()["bytes_in_use"]
    alive = sum(ref() is not None for ref in leaves)
    print(f"(c) sleep, multipath D2H on {dev.device_kind}: "
          f"{rate(slept.nbytes, slept.seconds)}; HBM freed {freed} B; "
          f"weight arrays still alive: {alive}")
    check(alive == 0, f"{alive} weight arrays outlived the sleep")
    check(freed >= (1 - FREED_SLACK) * wm.nbytes,
          f"sleep freed {freed} B of {wm.nbytes} B")
    woke = wm.wake()
    print(f"(c) wake, multipath H2D on {dev.device_kind}: "
          f"{rate(woke.nbytes, woke.seconds)}")
    check(trees_same_bits(reference, wm.params),
          "weights differ after the sleep/wake round trip")
    del reference
    print("(c) weights bit-exact after the round trip")

    srv.params = wm.params
    built = compiles.built
    again = srv.submit(prompt, max_new_tokens=NEW_TOKENS)
    caches = None
    t0 = time.perf_counter()
    while srv.scheduler.has_work():
        srv.step()
        if again.context is not None and again.finished():
            caches = again.context["caches"]   # before finishing drops them
    print(f"(c) one more request on the woken weights: "
          f"{time.perf_counter() - t0} s, TTFT {again.ttft} s, "
          f"prefix-hit tokens (count): {again.hit_tokens}; executables "
          f"built: {compiles.built - built}")
    check(compiles.built == built,
          "serving on the woken weights compiled the model again")
    check(again.generated == served.generated,
          "the woken weights generate other tokens for the same prompt")
    check(caches is not None, "no KV caches captured")
    return caches


def phase_kv_roundtrip(caches, engine) -> None:
    """(d) A request's KV caches D2H and back H2D, multipath both ways."""
    nbytes = sum(l.nbytes for l in jax.tree.leaves(caches))
    t0 = time.perf_counter()
    host = jax.tree.map(lambda l: multipath_device_get(l, engine=engine),
                        caches)
    d2h_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    back = jax.block_until_ready(jax.tree.map(
        lambda h: multipath_device_put(h, target=0, engine=engine), host
    ))
    h2d_s = time.perf_counter() - t0
    print(f"(d) KV caches D2H: {rate(nbytes, d2h_s)}; "
          f"H2D: {rate(nbytes, h2d_s)}")
    check(trees_same_bits(caches, host), "KV caches differ after D2H")
    check(trees_same_bits(caches, back), "KV caches differ after the round trip")
    print("(d) KV caches bit-exact after the round trip")


def one_chip(dev, seed: int, compiles: CompileCounter) -> None:
    rng = np.random.default_rng(seed)
    cfg = PAPER_MODELS[MODEL]
    t0 = time.perf_counter()
    srv = FunctionalServer(cfg, max_running=2,
                           device_budget_tokens=2 * MAX_LEN,
                           page_size=16, seed=seed, max_len=MAX_LEN,
                           now_fn=time.monotonic)
    jax.block_until_ready(srv.params)
    print(f"setup: {MODEL}, {cfg.param_count()} parameters "
          f"({sum(l.nbytes for l in jax.tree.leaves(srv.params))} B), "
          f"max_len {MAX_LEN}, seeded weights: "
          f"{time.perf_counter() - t0} s; {memory(dev)}")

    t0 = time.perf_counter()
    prompts, reqs = phase_serve(srv, rng, compiles)
    print(f"(a) phase: {time.perf_counter() - t0} s; {memory(dev)}")

    t0 = time.perf_counter()
    phase_cached_path(srv, rng)
    print(f"(b) phase: {time.perf_counter() - t0} s; {memory(dev)}")

    engine = make_functional_engine(
        config=MMAConfig(chunk_bytes=CHUNK_BYTES, fallback_bytes=0)
    )
    print(f"(c) multipath chunk size: {CHUNK_BYTES} B")
    t0 = time.perf_counter()
    caches = phase_switch(srv, engine, dev, prompts[1], reqs[1], compiles)
    print(f"(c) phase: {time.perf_counter() - t0} s; {memory(dev)}")

    t0 = time.perf_counter()
    phase_kv_roundtrip(caches, engine)
    print(f"(d) phase: {time.perf_counter() - t0} s; {memory(dev)}")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------
def link_chunks(engine) -> dict:
    return {d: {"direct": w.chunks_direct, "relay": w.chunks_relay}
            for d, w in sorted(engine.workers.items())}


def relay_copy(devs, label: str, nbytes: int, chunk_bytes: int, rng) -> None:
    """Host->chip 0 and back, multipath over chips 1-3 vs single path."""
    x = rng.integers(0, 2 ** 32, nbytes // 4, dtype=np.uint32)
    equal = jax.jit(lambda a, b: jnp.all(a == b))
    config = lambda: MMAConfig(chunk_bytes=chunk_bytes, fallback_bytes=0)
    print(f"{label}: {nbytes} B, multipath chunk size {chunk_bytes} B")
    # One untimed round trip first: it compiles the chunk slices and the
    # concatenation of this many parts, which the timed copies reuse.
    warm = multipath_device_put(x, target=0, engine=make_functional_engine(
        config=config()))
    multipath_device_get(warm, target=0, engine=make_functional_engine(
        config=config()))
    del warm

    t0 = time.perf_counter()
    plain = jax.block_until_ready(jax.device_put(x, devs[0]))
    plain_s = time.perf_counter() - t0
    h2d = make_functional_engine(config=config())
    t0 = time.perf_counter()
    multi = jax.block_until_ready(
        multipath_device_put(x, target=0, engine=h2d)
    )
    multi_s = time.perf_counter() - t0
    print(f"{label} H2D single path: {rate(nbytes, plain_s)}; multipath: "
          f"{rate(nbytes, multi_s)}; chunks per link {link_chunks(h2d)}")
    check(multi.devices() == {devs[0]}, f"landed on {multi.devices()}")
    check(bool(equal(multi, plain)), f"{label}: H2D differs from device_put")
    check(all(h2d.workers[d].chunks_relay > 0 for d in (1, 2, 3)),
          f"{label}: a relay link carried no H2D chunk")
    del plain

    t0 = time.perf_counter()
    ref = np.asarray(multi)
    plain_s = time.perf_counter() - t0
    d2h = make_functional_engine(config=config())
    t0 = time.perf_counter()
    back = multipath_device_get(multi, target=0, engine=d2h)
    multi_s = time.perf_counter() - t0
    print(f"{label} D2H single path: {rate(nbytes, plain_s)}; multipath: "
          f"{rate(nbytes, multi_s)}; chunks per link {link_chunks(d2h)}")
    check(same_bits(back, ref) and same_bits(ref, x),
          f"{label}: D2H differs from np.asarray")
    check(all(d2h.workers[d].chunks_relay > 0 for d in (1, 2, 3)),
          f"{label}: a relay link carried no D2H chunk")
    print(f"{label}: both directions bit-exact against the single path")


def four_chips(devs, seed: int) -> None:
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    rng = np.random.default_rng(seed)
    for label, nbytes, chunk in (("prefix-fetch size", 64 << 20, 4 << 20),
                                 ("wake size", 4 << 30, 64 << 20)):
        t0 = time.perf_counter()
        relay_copy(devs, label, nbytes, chunk, rng)
        print(f"{label} phase: {time.perf_counter() - t0} s; "
              + "; ".join(f"chip {i} {memory(d)}" for i, d in enumerate(devs)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the relay path on a four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    print(f"device: {devs[0].device_kind} x {len(devs)}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(devs, args.seed)
    else:
        one_chip(devs[0], args.seed, compiles)
    print(f"total: {time.perf_counter() - t0} s")
    print(compiles.summary(cache_dir))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
