"""Prefix fetches, closed loop: restore one cached prefix's KV from host
memory into the serving chip's HBM, then the next.

Chip 0 holds the configuration's weights, as the replica it serves would.
A host store holds the KV of ``prefix.groups`` documents, token-major
(token, layer, k/v, kv head, head dim), so that any prefix of a document
is one contiguous host array. Each fetch is a ``multipath_device_put`` of
one prefix to chip 0 on the functional engine over every chip of the cell,
with the program's default configuration; it is timed until the array is
ready, then a position-weighted checksum of it is taken on the chip
(outside the timed span) and it is dropped.
"""
from __future__ import annotations

import gc
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from harness import generator, model
from harness.checksum import checksum, host_checksums


def make_store(run, shapes: model.Shapes) -> list:
    """The documents' KV on the host, made fast from the seed: one random
    block of ``block_tokens`` tokens whose sign and mantissa bits are
    flipped by a different key in each block of each document. Every value
    is a finite bfloat16 of magnitude in [2**-8, 2**-7), and no two blocks
    are alike."""
    p = run.cell.traffic["prefix"]
    n_docs, n_tok, blk = p["groups"], p["document_tokens"], p["block_tokens"]
    per_token = (shapes.n_layers, 2, shapes.n_kv_heads, shapes.head_dim)
    rng = generator.rng_for(run.seed, 5)
    base = rng.integers(0, 1 << 16, (blk,) + per_token, dtype=np.uint16)
    base = (base & np.uint16(0x807F)) | np.uint16(0x3B80)
    keys = rng.permutation(np.arange(1, 256, dtype=np.uint16))
    docs = []
    for d in range(n_docs):
        doc = np.empty((n_tok,) + per_token, np.uint16)
        for b in range(n_tok // blk):
            k = keys[(d * (n_tok // blk) + b) % len(keys)]
            k = np.uint16(((int(k) & 0x80) << 8) | (int(k) & 0x7F))
            np.bitwise_xor(base, k, out=doc[b * blk:(b + 1) * blk])
        docs.append(doc.view(jnp.bfloat16))
    return docs


def _fetch(state, group, tokens):
    from repro.core import multipath_device_put

    view = state["docs"][group][:tokens]
    with jax.profiler.TraceAnnotation("multipath_device_put"):
        t0 = time.monotonic()
        arr = multipath_device_put(view, target=0, engine=state["engine"])
        arr.block_until_ready()
        seconds = time.monotonic() - t0
    with jax.profiler.TraceAnnotation("checksum"):
        cs = checksum(arr)
    return seconds, cs, view.nbytes


def _chunks(engine):
    direct = sum(w.chunks_direct for w in engine.workers.values())
    relay = sum(w.chunks_relay for w in engine.workers.values())
    return direct, relay


def setup(run):
    from repro.core import MMAConfig, make_functional_engine

    shapes = model.Shapes.of(run.cell.config)
    state = {
        "weights": model.make_weights(run.cell.config, run.seed,
                                      run.devices[0]),
        "docs": make_store(run, shapes),
        "items": generator.generate(run.cell.traffic, run.seed, run.seconds),
        "engine": make_functional_engine(devices=run.devices,
                                         config=MMAConfig()),
    }
    for tokens in sorted({it["prefix_tokens"] for it in state["items"]}):
        int(_fetch(state, 0, tokens)[1])
    return state


def window(state, run):
    items, fetches = state["items"], []
    before = _chunks(state["engine"])
    start = time.monotonic()
    while time.monotonic() - start < run.seconds:
        it = items[len(fetches) % len(items)]
        seconds, cs, nbytes = _fetch(state, it["group"], it["prefix_tokens"])
        fetches.append({"group": it["group"], "tokens": it["prefix_tokens"],
                        "seconds": seconds, "bytes": nbytes, "checksum": cs})
    for f in fetches:
        f["checksum"] = int(f["checksum"])
    after = _chunks(state["engine"])
    run.records.update(
        fetches=fetches, attempted=len(fetches), failed=0,
        chunks_direct=after[0] - before[0], chunks_relay=after[1] - before[1])


def release(state):
    state.pop("weights")
    state.pop("engine")
    gc.collect()


def _control(state, run, want, blk) -> float:
    """The control: prefixes moved in float8 (e4m3) instead of bfloat16 and
    widened again on the chip, by a plain copy; a sample of eight."""
    fetches = run.records["fetches"]
    pick = generator.rng_for(run.seed, 6).permutation(len(fetches))[:8]
    wrong = 0
    for k in pick:
        f = fetches[k]
        low = state["docs"][f["group"]][:f["tokens"]].astype(jnp.float8_e4m3fn)
        arr = jax.device_put(low, run.devices[0]).astype(jnp.bfloat16)
        wrong += int(checksum(arr)) != int(want[f["group"]][f["tokens"] // blk - 1])
        del arr
    return float(wrong)


def check(state, run):
    """Every fetched prefix against the checksum of its host source."""
    blk = run.cell.traffic["prefix"]["block_tokens"]
    fetches = run.records["fetches"]
    blocks = [max([f["tokens"] // blk for f in fetches if f["group"] == g],
                  default=0) for g in range(len(state["docs"]))]
    with ThreadPoolExecutor(len(blocks)) as pool:   # numpy frees the GIL
        want = list(pool.map(host_checksums, state["docs"],
                             [blk] * len(blocks), blocks))
    wrong = sum(f["checksum"] != int(want[f["group"]][f["tokens"] // blk - 1])
                for f in run.records["fetches"])
    if run.control:
        run.records["control"] = {"fetches_wrong": _control(state, run, want,
                                                            blk)}
    state.pop("docs")
    return [("fetches_wrong", float(wrong),
             float(run.cell.settings["limits"]["fetches_wrong"]))]
