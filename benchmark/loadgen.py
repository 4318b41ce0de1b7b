"""The one traffic generator: reads benchmark/traffic/<name>.json.

Every seed gets the SAME schedule: lengths and arrival gaps are the
distributions' quantiles on a fixed grid, shuffled ONCE by the traffic file's
own `schedule_seed`; the run's seed draws the token ids (and the weights).  So
two seeds differ in content, never in the amount of work nor in which request
follows which.  A tail latency is made by coincidences (three arrivals behind
a long prompt): on the chip a reshuffle by the seed, and even a rotation of
one fixed cycle, moved the 95th percentile of time to first token by 10-17%
between seeds while two runs of one seed agreed to ~2% (PERF.md, PR 24).
Serving parameters:

  loop      "open": requests are due on a schedule whether or not earlier
            ones finished (rate_rps, Poisson gaps); "closed": `clients`
            callers each send their next request when the last one returned
  prompt    {"dist": "lognormal", "median", "sigma", "min", "max"} or
            {"dist": "uniform", "min", "max"}: tokens of each request's OWN part
  output    the same, for max_new_tokens
  schedule_seed  fixes the schedule (see above)
  prefixes  optional {"count", "tokens"}: each request opens with one of
            `count` shared prefixes (round robin by a shuffled order)
  pool      closed loop only: how many requests to prepare
  block     closed loop only: lengths repeat as the same multiset every
            `block` requests (an open loop's grid spans the whole run)

Training parameters: {"batch", "seq"}; `train_batch` draws a fresh batch a step.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantile(dist, q):
    if dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif dist["dist"] == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * NormalDist().inv_cdf(q))
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def _lengths(dist, n, block, rng):
    """n lengths: the quantile grid of `block` points, reshuffled per block."""
    grid = np.array([quantile(dist, (i + 0.5) / block) for i in range(block)])
    out = [rng.permutation(grid) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


def requests(traffic, vocab, seed, seconds):
    """[{id, due, prompt, max_new_tokens, prefix}] for one run.  Open loop:
    round(rate * seconds) requests whose gaps are the exponential's quantiles,
    shuffled, scaled to sum to `seconds`.  Closed loop: `pool` requests, due
    None.  Also returns the shared prefixes (for set-up to send once)."""
    sched = np.random.default_rng(int(traffic["schedule_seed"]))
    rng = np.random.default_rng(seed)
    if traffic["loop"] == "open":
        n = max(1, round(traffic["rate_rps"] * seconds))
        q = (np.arange(n) + 0.5) / n
        gaps = sched.permutation(-np.log1p(-q)) * (seconds / -np.log1p(-q).sum())
        block = n  # one grid for the whole run: every seed, the same multiset
    elif traffic["loop"] == "closed":
        n, due = int(traffic["pool"]), None
        block = int(traffic["block"])
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    own = _lengths(traffic["prompt"], n, block, sched)
    out = _lengths(traffic["output"], n, block, sched)
    if traffic["loop"] == "open":
        due = np.cumsum(gaps) - gaps[0] / 2  # the first falls inside its gap
    prefixes = []
    if "prefixes" in traffic:
        p = traffic["prefixes"]
        prefixes = [rng.integers(0, vocab, p["tokens"], dtype=np.int32)
                    for _ in range(p["count"])]
    reqs = []
    for i in range(n):
        body = rng.integers(0, vocab, int(own[i]), dtype=np.int32)
        k = None
        if prefixes:
            if i % len(prefixes) == 0:
                order = rng.permutation(len(prefixes))
            k = int(order[i % len(prefixes)])
            body = np.concatenate([prefixes[k], body])
        reqs.append({"id": i, "due": None if due is None else float(due[i]),
                     "prompt": body, "max_new_tokens": int(out[i]), "prefix": k})
    return reqs, prefixes


def train_batch(traffic, vocab, seed, step):
    """(ids, labels) int32 [batch, seq] of step `step`: every row differs,
    labels are the next token."""
    rng = np.random.default_rng([int(seed), int(step)])
    toks = rng.integers(0, vocab, (traffic["batch"], traffic["seq"] + 1),
                        dtype=np.int32)
    return toks[:, :-1].copy(), toks[:, 1:].copy()
