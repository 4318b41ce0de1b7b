"""The generator is deterministic in the seed, hits its stated medians and
clips, and gives every seed the same work in another order."""
import json
import os

import numpy as np
import pytest

from benchmark import loadgen

from bh_tiny import REPO


def traffic(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def lens(reqs, prefix=0):
    return sorted(len(r["prompt"]) - prefix for r in reqs), \
        sorted(r["max_new_tokens"] for r in reqs)


def test_same_seed_same_requests_other_seed_same_schedule_other_tokens():
    t = traffic("chat-r80")
    a, _ = loadgen.requests(t, 32768, 2**31 + 7, 50)
    b, _ = loadgen.requests(t, 32768, 2**31 + 7, 50)
    c, _ = loadgen.requests(t, 32768, 5, 50)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and x["due"] == y["due"]
               for x, y in zip(a, b))
    shape = lambda r: [(len(x["prompt"]), x["max_new_tokens"], x["due"]) for x in r]  # noqa: E731
    assert shape(a) == shape(c)                       # the same work, the same order
    assert not any(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, c))
    assert len(a) == len(c) == round(t["rate_rps"] * 50)
    d, _ = loadgen.requests(dict(t, schedule_seed=t["schedule_seed"] + 1), 32768, 5, 50)
    assert lens(d) == lens(c) and shape(d) != shape(c)   # another schedule of the same set


def test_open_loop_lengths_hit_medians_and_clips():
    t = traffic("chat-r80")
    reqs, prefixes = loadgen.requests(t, 32768, 3, 50)
    own, out = lens(reqs)
    assert not prefixes
    assert abs(np.median(own) - t["prompt"]["median"]) <= 0.03 * t["prompt"]["median"]
    assert abs(np.median(out) - t["output"]["median"]) <= 0.03 * t["output"]["median"]
    assert own[0] >= t["prompt"]["min"] and own[-1] == t["prompt"]["max"]
    assert out[0] >= t["output"]["min"] and out[-1] <= t["output"]["max"]
    due = [r["due"] for r in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 50
    gaps = np.diff(due)
    assert abs(gaps.mean() - 1 / t["rate_rps"]) < 0.01           # the offered rate
    assert 0.8 < gaps.std() / gaps.mean() < 1.2                  # exponential-like


def test_closed_loop_shares_prefixes_and_repeats_its_multiset():
    t = traffic("fewshot-batch")
    reqs, prefixes = loadgen.requests(t, 32768, 9, 50)
    p = t["prefixes"]
    assert len(prefixes) == p["count"] and len(reqs) == t["pool"]
    assert all(r["due"] is None for r in reqs)
    for r in reqs[:64]:
        assert np.array_equal(r["prompt"][:p["tokens"]], prefixes[r["prefix"]])
    own = [len(r["prompt"]) - p["tokens"] for r in reqs]
    assert min(own) >= t["prompt"]["min"] and max(own) <= t["prompt"]["max"]
    b = t["block"]
    assert sorted(own[:b]) == sorted(own[b:2 * b])
    assert sorted(r["prefix"] for r in reqs[:8]) == list(range(8))
    # unique questions: no two requests share more than the prefix
    tails = {r["prompt"][p["tokens"]:].tobytes() for r in reqs[:256]}
    assert len(tails) == 256


@pytest.mark.parametrize("dist,q,want", [
    ({"dist": "uniform", "min": 64, "max": 256}, 0.5, 160),
    ({"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 32, "max": 3072}, 0.5, 512),
    ({"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 32, "max": 3072}, 0.999, 3072),
    ({"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 32, "max": 3072}, 0.0005, 32),
])
def test_quantiles_by_hand(dist, q, want):
    assert loadgen.quantile(dist, q) == want


def test_train_batches_differ_by_row_step_and_seed():
    t = {"batch": 4, "seq": 16}
    ids, labels = loadgen.train_batch(t, 1000, 2**31 + 1, 0)
    assert ids.shape == labels.shape == (4, 16) and ids.dtype == np.int32
    assert np.array_equal(ids[:, 1:], labels[:, :-1])            # next token
    assert len({r.tobytes() for r in ids}) == 4
    again, _ = loadgen.train_batch(t, 1000, 2**31 + 1, 0)
    other, _ = loadgen.train_batch(t, 1000, 2**31 + 1, 1)
    assert np.array_equal(ids, again) and not np.array_equal(ids, other)
