"""The one general traffic generator.  A mix is a data file of parameters
(``benchmarks/traffic/<mix>.json``); this module turns it, a seed and a
window length into requests or batches.  The program under test receives
only what is generated here.

Every seed gets the SAME set of lengths and inter-arrival gaps: they are
the quantiles of their distributions on a fixed grid, permuted.  The
permutation comes from the mix's ``schedule_seed`` where it has one (the
seed then changes every token and weight, and no length or arrival), or
else from the seed (another order, the same amount of work).
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def length_grid(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole-number lengths: the quantiles (i + 1/2) / n of the
    distribution, clipped to [min, max]."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        q = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
        x = spec["median"] * np.exp(spec["sigma"] * q)
    elif spec["dist"] == "uniform":
        x = spec["min"] + (spec["max"] - spec["min"]) * (np.arange(n) + 0.5) / n
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(spec: Dict[str, Any], n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times of ``n`` requests inside ``[0, seconds)``.

    ``poisson``: exponential gaps (their quantile grid, permuted).
    ``onoff``: the same gaps squeezed into bursts of ``on_s`` seconds
    separated by ``off_s`` seconds of silence, at the same mean rate."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    t = np.cumsum(rng.permutation(gaps))
    t *= seconds * (n - 0.5) / n / t[-1]
    if spec["process"] == "poisson":
        return t
    if spec["process"] == "onoff":
        on, off = float(spec["on_s"]), float(spec["off_s"])
        t_on = t * on / (on + off)          # time spent inside bursts
        return np.floor(t_on / on) * (on + off) + np.mod(t_on, on)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def serve_requests(mix: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> Dict[str, Any]:
    """``{"prefixes": [tokens, ...], "requests": [{"due", "prompt",
    "max_new", "prefix"}, ...]}``, requests in due order.  A closed-loop
    mix (``arrivals.process == "closed"``) gives ``due`` None and a pool
    of ``arrivals.pool`` requests that the clients cycle through."""
    arr = mix["arrivals"]
    closed = arr["process"] == "closed"
    n = int(arr["pool"]) if closed else max(1, round(arr["rate_per_s"] * seconds))
    # the schedule (which length comes when, behind which prefix) is drawn
    # from the mix's own ``schedule_seed`` where it has one, so that every
    # run of the cell times the same work; every token is from ``seed``
    sched = _rng(mix.get("schedule_seed", seed), 1)
    rng = _rng(seed, 4)
    user = sched.permutation(length_grid(mix["user_tokens"], n))
    new = sched.permutation(length_grid(mix["max_new_tokens"], n))
    due = [None] * n if closed else arrival_times(arr, n, seconds, sched)
    pre = mix.get("prefix", {"pool": 0, "tokens": 0})
    pool, plen = int(pre["pool"]), int(pre["tokens"])
    prefixes = [rng.integers(0, vocab, plen).astype(np.int32)
                for _ in range(pool)]
    # each shared prefix heads the same number of requests (+-1)
    which = sched.permutation(np.arange(n) % pool) if pool else [-1] * n
    repeats = int(mix.get("repeats", 1))
    reqs: List[Dict[str, Any]] = []
    for i in range(n):
        if repeats > 1 and i % repeats:
            body = reqs[i - i % repeats]["prompt"]       # asked again
        else:
            head = prefixes[which[i]] if pool else \
                rng.integers(0, vocab, plen).astype(np.int32)
            body = np.concatenate(
                [head, rng.integers(0, vocab, int(user[i])).astype(np.int32)])
        reqs.append({"due": None if closed else float(due[i]),
                     "prompt": body, "max_new": int(new[i]),
                     "prefix": int(which[i])})
    return {"prefixes": prefixes, "requests": reqs}


def longest_request_tokens(mix: Dict[str, Any]) -> int:
    """Prompt plus answer of the longest request the mix can make."""
    return int(mix.get("prefix", {}).get("tokens", 0)
               + mix["user_tokens"].get("max", mix["user_tokens"].get("value", 0))
               + mix["max_new_tokens"].get("max", mix["max_new_tokens"].get("value", 0)))


def train_batch(mix: Dict[str, Any], seed: int, step: int, vocab: int):
    """``(input_ids, labels)`` of one optimizer step: ``[accum, micro,
    seq]`` uniform-random tokens (``[micro, seq]`` where accum is 1), a
    new draw every step; labels are the inputs shifted by one."""
    shape = (mix["micro_batch"], mix["seq_len"])
    if mix["accum"] > 1:
        shape = (mix["accum"], *shape)
    ids = _rng(seed, 2, step).integers(0, vocab, shape).astype(np.int32)
    return ids, np.roll(ids, -1, axis=-1)


def tokens_per_step(mix: Dict[str, Any]) -> int:
    """``micro_batch`` is the whole micro-step's batch, over all chips."""
    return mix["micro_batch"] * mix["seq_len"] * mix["accum"]
