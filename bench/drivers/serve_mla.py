"""Open-loop serving of a latent-attention MoE model (the DeepSeek-V2
family): the window of ``serve.py`` on a server built from ``mla_model``,
and its served tokens compared with ``mla_reference``.

Set-up builds the program's ``ModelConfig`` before anything else, so a
program without latent attention fails at once, before weights are made
or anything compiles.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import jax
import numpy as np

from drivers.serve import release, window  # noqa: F401  (the driver's calls)
from harness import generator, mla_model, mla_reference, serving


def build_server(run, cfg, weights):
    """The program's server for the cell, with each prefill and decode call
    wrapped in a host span that also stamps the request's token times (as
    ``serving.build_server`` does for the dense configurations)."""
    from repro.serving import FunctionalServer

    s = run.cell.settings
    srv = FunctionalServer(
        cfg, params=weights, max_running=s["max_running"],
        # admission is bounded by max_running; the byte budget never binds
        device_budget_tokens=4 * s["max_running"] * s["max_len"],
        page_size=16, max_len=s["max_len"], now_fn=time.monotonic,
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


def setup(run):
    cfg = mla_model.program_config(run.cell.config)
    vocab = cfg.vocab
    srv, stamps = build_server(
        run, cfg, mla_model.make_weights(run.cell.config, run.seed,
                                         run.devices[0]))
    items = generator.generate(run.cell.traffic, run.seed, run.seconds,
                               rate_per_s=run.rate_per_s)
    prompts = [serving.prompt_of(run.seed, it, i, vocab)
               for i, it in enumerate(items)]
    # Every length a document and a question of this mix can make, the
    # same set for every seed, so no seed compiles in a later run.
    serving.warm_up(srv, {d + q for d in {it["prefix_tokens"] for it in items}
                          for q in {it["suffix_tokens"] for it in items}},
                    vocab)
    return {"srv": srv, "stamps": stamps, "items": items, "prompts": prompts}


def served_gaps(run, served):
    """Widest gap, over all served tokens, by which a served token's logit
    lies below the reference's best at its position (float32 reference,
    weights made again from the seed). With ``run.control`` also the
    control's reading: at the same positions, the gap of the token that
    the float8 reference puts first."""
    shapes = mla_model.Shapes.of(run.cell.config)
    weights = mla_model.make_flat(run.cell.config, run.seed, run.devices[0])
    seqs, positions, tokens = [], [], []
    for r in served:
        gen = np.asarray(r["generated"], np.int32)
        seqs.append(np.concatenate([r["prompt"], gen[:-1]]))
        positions.append(len(r["prompt"]) - 1 + np.arange(len(gen)))
        tokens.append(gen)
    with jax.default_matmul_precision("highest"):
        ref = mla_reference.logits_at(weights, shapes, seqs, positions)
        ctl = (mla_reference.logits_at(weights, shapes, seqs, positions, "fp8")
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


def check(state, run):
    """Served tokens of a sample of the finished requests, drawn from the
    seed, with the longest in it, until ``check_tokens`` tokens (the
    sample of ``serve.check``)."""
    limit = run.cell.settings["limits"]["served_logit_gap"]
    done = [r for r in run.records["requests"] if r["done"]]
    if not done:
        return [("served_logit_gap", float("inf"), limit)]
    order = generator.rng_for(run.seed, 4).permutation(len(done)).tolist()
    longest = max(range(len(done)), key=lambda k: len(done[k]["generated"]))
    order.remove(longest)
    sample, n = [], 0
    for k in [longest] + order:
        if n >= run.cell.settings["check_tokens"]:
            break
        sample.append(done[k])
        n += len(done[k]["generated"])
    run.records["checked_tokens"] = n
    return served_gaps(run, sample)
