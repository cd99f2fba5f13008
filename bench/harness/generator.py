"""The one traffic generator: turns a mix's parameters into a list of items.

An item is a request (or a switch, or a fetch) with the keys

    due_s          when it is due, seconds after the window opens
                   (open loop; a closed loop sends the next on completion)
    group          the shared prefix it reads (-1: none)
    prefix_tokens  tokens of that shared prefix
    suffix_tokens  tokens of its own after the prefix
    new_tokens     tokens to generate

Every seed gets the same work in another order. Each size and gap is
drawn as evenly spaced quantiles of its distribution, so every seed has
the same multiset of them, and the seed permutes them. In an open loop
the documents (a length and a number of uses each), the questions, the
answers and the gaps between arrivals are such multisets; the seed orders
the arrivals and the sessions. The seed also picks the token ids. So
two seeds differ in order alone.

An open loop's ``strata`` (k, default 1) bounds how far the seed may bunch
its draws: each run of k consecutive gaps, session gaps, questions and
answers holds one value from each k-th of their sorted multiset, and the
seed orders the values within each run and picks which value of a k-th
goes to which run. A burst of short gaps, or of long prompts, is then as
long and as frequent on every seed, so the tail of a window is not decided
by one seed's coincidences.

Distributions (a JSON object with one of these forms):

    {"values": [a, b, ...]}                         cycled over the items
    {"grid": [lo, hi, step]}                        lo, lo+step, ... hi, cycled
    {"exponential": mean}
    {"lognormal": {"median": m, "sigma": s}, "min": lo, "max": hi,
     "multiple": k}                                  rounded to k, clipped
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

NONE = {"values": [0]}

def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one use of the run's seed. Any whole number is
    a seed, negative or above 64 bits included."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` values of ``dist``, in a fixed order that depends on nothing
    but ``dist`` and ``n``."""
    if n <= 0:
        return np.zeros(0)
    if "values" in dist:
        vals = np.asarray(dist["values"], float)
        return vals[np.arange(n) % len(vals)]
    if "grid" in dist:
        lo, hi, step = dist["grid"]
        vals = np.arange(lo, hi + 1, step, dtype=float)
        return vals[np.arange(n) % len(vals)]
    p = (np.arange(n) + 0.5) / n
    if "exponential" in dist:
        return -float(dist["exponential"]) * np.log1p(-p)
    if "lognormal" in dist:
        ln = dist["lognormal"]
        z = np.array([NormalDist().inv_cdf(q) for q in p])
        out = float(ln["median"]) * np.exp(float(ln["sigma"]) * z)
        k = dist.get("multiple", 1)
        out = np.round(out / k) * k
        return np.clip(out, dist.get("min", -np.inf), dist.get("max", np.inf))
    raise ValueError(f"unknown distribution {dist!r}")


def _draw(dist: Dict[str, Any], n: int, rng: np.random.Generator,
          strata: int = 1) -> np.ndarray:
    """The ``n`` quantiles of ``dist`` in the seed's order; with ``strata``
    k > 1, stratified in runs of k (see the module's note)."""
    if strata <= 1 or n <= 1:
        return rng.permutation(quantiles(dist, n))
    vals = np.sort(quantiles(dist, n))
    runs = -(-n // strata)
    members: List[List[float]] = [[] for _ in range(runs)]
    for i in range(strata):
        part = vals[i * runs:(i + 1) * runs]
        for r, v in zip(rng.permutation(runs), part):
            members[r].append(v)
    return np.concatenate([rng.permutation(m) for m in members if m])


def generate(traffic: Dict[str, Any], seed: int, seconds: float,
             rate_per_s: float = 0.0) -> List[Dict[str, Any]]:
    """The items of one run. An open loop (``rate_per_s`` in the mix, or the
    argument where it is given) holds ``round(rate * seconds)`` items due
    within ``seconds``; a closed loop holds ``items`` of them, sent back to
    back."""
    rate = rate_per_s or traffic.get("rate_per_s", 0.0)
    prefix = traffic.get("prefix")
    rng = rng_for(seed, 0)
    if rate:
        return _open_loop(traffic, prefix, max(1, round(rate * seconds)),
                          rate, rng, int(traffic.get("strata", 1)))
    n = int(traffic["items"])
    groups = int(prefix["groups"]) if prefix else 0
    plens = _draw(prefix["tokens"], n, rng) if prefix else np.zeros(n)
    return [
        {"due_s": 0.0, "group": (i % groups) if groups else -1,
         "prefix_tokens": int(plens[i]),
         "suffix_tokens": int(s), "new_tokens": int(t)}
        for i, (s, t) in enumerate(zip(
            _draw(traffic.get("suffix_tokens", NONE), n, rng),
            _draw(traffic.get("new_tokens", NONE), n, rng)))
    ]


def _documents(prefix: Dict[str, Any], n: int) -> List[tuple]:
    """The shared documents of ``n`` requests, the same for every seed:
    (tokens, uses) pairs whose uses add up to ``n``. Lengths are the
    quantiles of ``prefix.tokens`` and uses cycle through ``prefix.uses``,
    paired in one fixed shuffled order."""
    cycle = quantiles(prefix["uses"], n).astype(int)
    uses = cycle[:int(np.searchsorted(np.cumsum(cycle), n)) + 1].tolist()
    uses[-1] -= sum(uses) - n
    lengths = quantiles(prefix["tokens"], len(uses))
    lengths = lengths[rng_for(0, 7).permutation(len(uses))]
    return [(int(t), u) for t, u in zip(lengths, uses)]


def _open_loop(traffic, prefix, n, rate, rng, strata):
    """``n`` requests arriving as a Poisson process at ``rate``: the gaps
    between arrivals are the quantiles of the exponential, in the seed's
    order. Which request takes which arrival follows sessions: documents
    open as a Poisson process of their own, each is asked again after a
    ``reuse_gap_s``, and the asks take the arrivals in the order of those
    instants."""
    gaps = _draw({"exponential": 1.0 / rate}, n - 1, rng, strata)
    due = np.concatenate([[0.0], np.cumsum(gaps)])
    if prefix:
        docs = _documents(prefix, n)
        order = rng.permutation(len(docs))
        opens = np.cumsum(_draw({"exponential": len(docs) / n / rate},
                                len(docs), rng, strata))
        again = iter(_draw(prefix["reuse_gap_s"], n - len(docs), rng,
                           strata))
        asks = []
        for j, t in zip(order, opens):
            for u in range(docs[j][1]):
                t += next(again) if u else 0.0
                asks.append((t, int(j)))
        asks.sort()
    else:
        docs, asks = [], [(0.0, -1)] * n
    suffix = _draw(traffic["suffix_tokens"], n, rng, strata)
    new = _draw(traffic["new_tokens"], n, rng, strata)
    return [{"due_s": float(due[k]), "group": g,
             "prefix_tokens": docs[g][0] if g >= 0 else 0,
             "suffix_tokens": int(suffix[k]), "new_tokens": int(new[k])}
            for k, (_, g) in enumerate(asks)]


def token_ids(seed: int, group: int, n: int, vocab: int,
              salt: int = 0) -> np.ndarray:
    """Token ids of a prefix (``group`` >= 0, the same for every use of it)
    or of one item's own suffix (``salt`` tells the items apart)."""
    rng = rng_for(seed, 1, group + 1, salt)
    return rng.integers(0, vocab, n, dtype=np.int32)
