"""What the serving drivers share: a ``FunctionalServer`` built for a cell,
host spans and token times around its calls, and the served tokens'
comparison with the reference.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import numpy as np

from . import generator, model, reference


def build_server(run, weights):
    """The program's server for the cell, with each prefill and decode call
    wrapped in a host span that also stamps the request's token times."""
    from repro.serving import FunctionalServer

    s = run.cell.settings
    cfg = model.program_config(run.cell.config)
    srv = FunctionalServer(
        cfg, params=weights, max_running=s["max_running"],
        # admission is bounded by max_running; the byte budget never binds
        device_budget_tokens=4 * s["max_running"] * s["max_len"],
        page_size=16, max_len=s["max_len"],
        now_fn=time.monotonic,
    )
    stamps: Dict[int, Dict[str, Any]] = {}

    def wrap(call, span, first):
        def timed(req):
            start = time.monotonic()
            with jax.profiler.TraceAnnotation(span):
                call(req)
            rec = stamps.setdefault(req.req_id, {"tokens": []})
            if first:
                rec["prefill_start"] = start
            rec["tokens"].append(time.monotonic())
        return timed

    srv._prefill = wrap(srv._prefill, "prefill", True)
    srv._decode_one = wrap(srv._decode_one, "decode", False)
    return srv, stamps


def prompt_of(seed: int, item: Dict[str, Any], index: int,
              vocab: int) -> np.ndarray:
    parts = []
    if item["prefix_tokens"]:
        parts.append(generator.token_ids(seed, item["group"],
                                         item["prefix_tokens"], vocab))
    parts.append(generator.token_ids(seed, -1, item["suffix_tokens"], vocab,
                                     salt=index))
    return np.concatenate(parts)


def warm_up(srv, lengths, vocab: int) -> None:
    """One request of each prompt length: compiles the prefill of each
    shape and the decode step. Their tokens share no prefix with traffic."""
    rng = np.random.default_rng(0xBE7C)
    for n in sorted(set(lengths)):
        srv.submit(rng.integers(0, vocab, n), max_new_tokens=2)
        srv.run_until_done()
    srv.scheduler.done.clear()


def served_gaps(run, served: List[Dict[str, Any]]) -> List:
    """Widest gap, over all served tokens, by which a served token's logit
    lies below the reference's best at its position (float32 reference,
    weights made again from the seed). With ``run.control`` also the
    control's reading: at the same positions, the gap of the token that
    the float8 reference puts first."""
    shapes = model.Shapes.of(run.cell.config)
    weights = model.flat_layout(model.make_weights(run.cell.config, run.seed,
                                                   run.devices[0]))
    seqs, positions, tokens = [], [], []
    for r in served:
        gen = np.asarray(r["generated"], np.int32)
        seqs.append(np.concatenate([r["prompt"], gen[:-1]]))
        positions.append(len(r["prompt"]) - 1 + np.arange(len(gen)))
        tokens.append(gen)
    with jax.default_matmul_precision("highest"):
        ref = reference.logits_at(weights, shapes, seqs, positions)
        ctl = (reference.logits_at(weights, shapes, seqs, positions, "fp8")
               if run.control else None)
    del weights
    gap = lambda rows, toks: float(np.max(
        rows.max(-1) - rows[np.arange(len(toks)), toks]))
    if ctl is not None:
        run.records["control"] = {"served_logit_gap": max(
            gap(r, c.argmax(-1)) for r, c in zip(ref, ctl))}
    value = max(gap(r, t) for r, t in zip(ref, tokens))
    return [("served_logit_gap", value,
             run.cell.settings["limits"]["served_logit_gap"])]
