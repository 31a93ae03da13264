"""Median duration, in ms, of the program's span ``span`` over the
traced window (the spans that lie wholly inside it), on the profiler's
clock."""

from __future__ import annotations

from benchmarks.harness import stats
from benchmarks.readers import program_trace


def read(obs, span: str):
    pt = program_trace.of(obs)
    durs = [sp[2] for sp in pt.named(span)] if pt else []
    return stats.median(durs) / 1e6 if durs else None
