"""Sum of the count ``num`` over sum of the count ``den``, in %, over
the ``serving.step_counts`` markers of the steps that lie wholly inside
the traced window (``rows`` over ``rows_cap``: how full the static step
is)."""

from __future__ import annotations

from benchmarks.readers import program_trace


def read(obs, num: str, den: str):
    pt = program_trace.of(obs)
    counts = pt.step_counts() if pt else []
    total = sum(c[den] for c in counts)
    return 100.0 * sum(c[num] for c in counts) / total if total else None
