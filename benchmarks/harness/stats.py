"""Arithmetic from timestamps to the serving and training metrics.

All times are seconds on one host clock.  A request is the record the
serve runner keeps: ``due`` (when it was due to be sent), ``sent``,
``emit`` (the time each output token reached the host, in order) and
``want`` (the tokens it asked for).
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile by linear interpolation between closest
    ranks (numpy's default), on a copy sorted here."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def ttft_s(req: Dict, limit_s: float) -> float:
    """First token's time minus the time the request was DUE; a request
    with no token misses every limit and enters as ``limit_s``."""
    if not req["emit"]:
        return limit_s
    return req["emit"][0] - req["due"]


def token_gaps_s(req: Dict, limit_s: float) -> List[float]:
    """Gaps between successive tokens of one request.  An unfinished
    request adds one gap of ``limit_s`` for the token that never came."""
    e = req["emit"]
    gaps = [b - a for a, b in zip(e, e[1:])]
    if len(e) < req["want"]:
        gaps.append(limit_s)
    return gaps


def serving_summary(reqs: Sequence[Dict], t0: float, seconds: float,
                    limit_s: float) -> Dict[str, float]:
    """End-to-end serving numbers over the requests due in the window
    ``[t0, t0 + seconds)``: all of them, finished or not."""
    ttfts = [ttft_s(r, limit_s) for r in reqs]
    gaps = [g for r in reqs for g in token_gaps_s(r, limit_s)]
    in_window = sum(1 for r in reqs for t in r["emit"]
                    if t0 <= t < t0 + seconds)
    late = [r["sent"] - r["due"] for r in reqs if r.get("sent") is not None]
    out = {
        "requests": len(reqs),
        "failed": sum(1 for r in reqs if len(r["emit"]) < r["want"]),
        "ttft_p95_ms": percentile(ttfts, 95) * 1e3,
        "ttft_p50_ms": median(ttfts) * 1e3,
        "serve_tokens_per_s": in_window / seconds,
        "n_gaps": len(gaps),
        "sent_late_p50_ms": median(late) * 1e3 if late else 0.0,
        "sent_late_max_ms": max(late) * 1e3 if late else 0.0,
    }
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
        out["itl_p50_ms"] = median(gaps) * 1e3
    return out


def training_summary(step_ends: Sequence[float], t_start: float,
                     tokens_per_step: int) -> Dict[str, float]:
    """Tokens of the optimizer steps completed in the window over the
    time those steps took (``t_start`` is when the first was issued)."""
    if not step_ends:
        raise ValueError("no step completed in the window")
    took = step_ends[-1] - t_start
    ends = [t_start, *step_ends]
    steps = [b - a for a, b in zip(ends, ends[1:])]
    p50 = median(steps)
    return {
        "steps": len(step_ends),
        "train_tokens_per_s": len(step_ends) * tokens_per_step / took,
        "step_p50_ms": p50 * 1e3,
        # where a run reads slow: one long stall, or many slower steps
        "step_max_ms": max(steps) * 1e3,
        "slow_steps": [(i, round(s * 1e3, 1)) for i, s in enumerate(steps)
                       if s > 1.2 * p50][:12],
    }

