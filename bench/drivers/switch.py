"""Model switches, back to back: a request arrives for weights that sleep.

Each switch hands the served weights to a ``WeightManager``, sleeps them
to host memory (multipath D2H, freeing their HBM), wakes them (multipath
H2D) on the functional engine with the program's default configuration,
and serves the waiting request on the woken weights. Its time runs from
the request's arrival to its first token; the request is then decoded to
its end, and a checksum of each woken leaf is dispatched on the chip (read
after the window). A switch starts while window time is left, and every
switch started is finished and counted.
"""
from __future__ import annotations

import gc
import time

import jax

from harness import generator, model, serving
from harness.checksum import checksum


def _switch(state, prompt, new_tokens):
    from repro.serving import WeightManager

    srv, engine = state["srv"], state["engine"]
    arrival = time.monotonic()
    wm = WeightManager(engine, params=srv.release_params())
    with jax.profiler.TraceAnnotation("sleep"):
        t0 = time.monotonic()
        wm.sleep()
        slept = time.monotonic()
    with jax.profiler.TraceAnnotation("wake"):
        wm.wake()
        woke = time.monotonic()
    srv.params = wm.params
    req = srv.submit(prompt, max_new_tokens=new_tokens)
    with jax.profiler.TraceAnnotation("step"):
        srv.run_until_done()
    srv.scheduler.done.clear()
    first = state["stamps"][req.req_id]["tokens"][0]
    with jax.profiler.TraceAnnotation("checksum"):
        sums = {k: checksum(v) for k, v in model.flat_layout(srv.params).items()}
    return {"arrival": arrival, "first_token": first,
            "sleep_s": slept - t0, "wake_s": woke - slept, "bytes": wm.nbytes,
            "prompt": prompt, "generated": list(req.generated),
            "checksums": sums}


def setup(run):
    from repro.core import MMAConfig, make_functional_engine

    cfg = run.cell.config
    vocab = cfg["vocab_size"]
    srv, stamps = serving.build_server(
        run, model.make_weights(cfg, run.seed, run.devices[0]))
    items = generator.generate(run.cell.traffic, run.seed, run.seconds)
    state = {"srv": srv, "stamps": stamps, "items": items,
             "engine": make_functional_engine(config=MMAConfig())}
    serving.warm_up(srv, [it["suffix_tokens"] for it in items], vocab)
    # One untimed switch builds every slice and concatenation a switch runs.
    warm = generator.rng_for(run.seed, 3).integers(
        0, vocab, items[0]["suffix_tokens"])
    _switch(state, warm, items[0]["new_tokens"])
    return state


def window(state, run):
    vocab = run.cell.config["vocab_size"]
    items, switches = state["items"], []
    start = time.monotonic()
    while time.monotonic() - start < run.seconds:
        i = len(switches)
        item = items[i % len(items)]
        switches.append(_switch(state, serving.prompt_of(
            run.seed, item, i, vocab), item["new_tokens"]))
    for sw in switches:
        sw["checksums"] = {k: int(v) for k, v in sw["checksums"].items()}
    run.records.update(switches=switches, attempted=len(switches), failed=0)


def release(state):
    state.pop("srv").params = None
    state.pop("engine")
    gc.collect()


def check(state, run):
    """Every switch's woken weights, leaf by leaf, against the checksums of
    the weights made again from the seed (exact), and every served token
    against the reference."""
    ref = model.flat_layout(model.make_weights(run.cell.config, run.seed,
                                               run.devices[0]))
    want = {k: int(checksum(v)) for k, v in ref.items()}
    del ref
    switches = run.records["switches"]
    wrong = sum(len(sw["checksums"].keys() - want.keys())
                + sum(sw["checksums"].get(k) != v for k, v in want.items())
                for sw in switches)
    limit = run.cell.settings["limits"]["weights_wrong"]
    return [("weights_wrong", float(wrong), float(limit))] \
        + serving.served_gaps(run, switches)
