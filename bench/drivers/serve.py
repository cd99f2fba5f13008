"""Open-loop serving: requests are submitted when due, whatever the server
is doing, and timed from their due time.

The loop submits every request that is due, runs one server iteration
(admit, prefill the admitted, decode one token of each running request)
and sleeps only when the server is idle until the next is due. After the
window's last arrival it serves what is left; late answers count with
their lateness.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from harness import generator, model, serving


def setup(run):
    cfg = run.cell.config
    vocab = cfg["vocab_size"]
    srv, stamps = serving.build_server(
        run, model.make_weights(cfg, run.seed, run.devices[0]))
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


def window(state, run):
    srv, items, prompts = state["srv"], state["items"], state["prompts"]
    submitted, i, backlog = [], 0, None
    start = time.monotonic()
    while True:
        now = time.monotonic() - start
        if i == len(items) and backlog is None:
            backlog = (len(srv.scheduler.waiting), now)
        while i < len(items) and items[i]["due_s"] <= now:
            with jax.profiler.TraceAnnotation("submit"):
                req = srv.submit(prompts[i],
                                 max_new_tokens=items[i]["new_tokens"])
            submitted.append((i, req, time.monotonic() - start))
            i += 1
        if srv.scheduler.has_work():
            with jax.profiler.TraceAnnotation("step"):
                srv.step()
        elif i < len(items):
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(max(0.0, items[i]["due_s"] - now))
        else:
            break
    stamps = state["stamps"]
    requests = []
    for k, req, sent in submitted:
        st = stamps.get(req.req_id, {"tokens": []})
        due = start + items[k]["due_s"]
        requests.append({
            "due": due, "late_s": sent - items[k]["due_s"],
            "prefill_start": st.get("prefill_start"), "tokens": st["tokens"],
            "hit_tokens": req.hit_tokens, "prompt": prompts[k],
            "generated": list(req.generated), "done": req.state == "done",
            "group": items[k]["group"],
        })
    end = time.monotonic() - start
    lates = sorted(r["late_s"] for r in requests)
    ttfts = [r["tokens"][0] - r["due"] for r in requests if r["tokens"]]
    run.records["info"] = {
        "ttft_p50_s": float(np.median(ttfts)) if ttfts else None,
        "offered_per_s": len(requests) / run.seconds,
        "waiting_at_last_arrival": backlog[0] if backlog else 0,
        "drain_s": end - backlog[1] if backlog else 0.0,
        "submit_late_max_s": lates[-1] if lates else 0.0,
    }
    run.records.update(
        requests=requests, attempted=len(requests),
        failed=sum(not r["done"] for r in requests),
        prefill_tokens=[len(r["prompt"]) for r in requests])


def release(state):
    state.pop("srv").params = None
    gc.collect()


def check(state, run):
    """Served tokens of a sample of the finished requests, drawn from the
    seed, with the longest in it, until ``check_tokens`` tokens."""
    done = [r for r in run.records["requests"] if r["done"]]
    if not done:
        return [("served_logit_gap", float("inf"),
                 run.cell.settings["limits"]["served_logit_gap"])]
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
    return serving.served_gaps(run, sample)
