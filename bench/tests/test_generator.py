"""The traffic generator: deterministic per seed, the same sizes for every
seed, the length grid, and the stated rates and medians."""
import json

import numpy as np
import pytest

from conftest import BENCH
from harness import generator


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,seconds", [("switch", 45), ("docqa", 45),
                                          ("fetch.relay4", 45)])
def test_deterministic_and_same_sizes_for_every_seed(name, seconds):
    a = generator.generate(_mix(name), 5, seconds)
    assert a == generator.generate(_mix(name), 5, seconds)
    b = generator.generate(_mix(name), 2 ** 33 + 1, seconds)
    sizes = lambda items, k: sorted(i[k] for i in items)
    gaps = lambda items: sorted(np.diff([i["due_s"] for i in items]))
    for k in ("prefix_tokens", "suffix_tokens", "new_tokens"):
        assert sizes(a, k) == sizes(b, k)
    np.testing.assert_allclose(gaps(a), gaps(b), atol=1e-9)
    if len({(i["prefix_tokens"], i["new_tokens"]) for i in a}) > 1:
        assert a != b                    # the seed orders the work


def test_docqa_grid_rate_and_lengths():
    mix = _mix("docqa")
    items = generator.generate(mix, 3, 51)
    prompts = [i["prefix_tokens"] + i["suffix_tokens"] for i in items]
    assert all(p % 256 == 0 and 768 <= p <= 3840 for p in prompts)
    assert len(set(prompts)) >= 10                  # lengths have a tail
    assert np.median(prompts) == 1536
    assert all(4 <= i["new_tokens"] <= 64 for i in items)
    assert np.median([i["new_tokens"] for i in items]) == 13
    assert len(items) == round(mix["rate_per_s"] * 51)
    uses = {}
    for i in items:
        uses[i["group"]] = uses.get(i["group"], 0) + 1
    assert max(uses.values()) <= 5 and min(uses.values()) >= 1
    assert sum(n > 1 for n in uses.values()) >= len(uses) - 1
    due = [i["due_s"] for i in items]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 51
    # a document's later asks come after its first
    first = {}
    for i in items:
        assert i["prefix_tokens"] == first.setdefault(i["group"],
                                                      i["prefix_tokens"])


def test_fetch_sizes():
    items = generator.generate(_mix("fetch.relay4"), 9, 45)
    sizes = [i["prefix_tokens"] for i in items]
    assert all(s % 256 == 0 and 1024 <= s <= 8192 for s in sizes)
    assert 1792 <= np.median(sizes) <= 2304
    assert {i["group"] for i in items} == {0, 1, 2, 3}


def test_quantiles():
    q = generator.quantiles({"exponential": 4.0}, 1000)
    assert abs(q.mean() - 4.0) < 0.05
    q = generator.quantiles({"lognormal": {"median": 16, "sigma": 0.6}}, 1001)
    assert np.median(q) == 16.0


@pytest.mark.parametrize("seed", [0, 2 ** 40 + 3])
def test_strata_bound_bunching(seed):
    """With strata k, no k consecutive draws hold more than two values of
    any one k-th of the sorted multiset, and the multiset is unchanged."""
    dist, n, k = {"exponential": 1.0}, 61, 4
    out = generator._draw(dist, n, generator.rng_for(seed, 0), k)
    vals = np.sort(generator.quantiles(dist, n))
    np.testing.assert_array_equal(np.sort(out), vals)
    stratum = np.searchsorted(vals, out) // -(-n // k)
    for i in range(n - k + 1):
        assert np.bincount(stratum[i:i + k], minlength=k).max() <= 2
    assert not (out == generator._draw(dist, n, generator.rng_for(seed + 1, 0),
                                       k)).all()


def test_token_ids_shared_prefix():
    a = generator.token_ids(7, 3, 100, 1000)
    b = generator.token_ids(7, 3, 50, 1000)
    assert (a[:50] == b).all()
    assert not (generator.token_ids(7, -1, 50, 1000, salt=1)
                == generator.token_ids(7, -1, 50, 1000, salt=2)).all()
