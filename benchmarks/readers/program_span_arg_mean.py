"""Mean of the argument ``arg`` of the program's markers named ``span``
over the traced window, times ``scale``.  For ``serving.step_counts``
the steps that lie wholly inside the window; the window's largest value
is printed beside it (a tail, not a mean, is what a latency tail is
made of)."""

from __future__ import annotations

from benchmarks.readers import program_trace


def read(obs, span: str, arg: str, scale: float = 1.0):
    pt = program_trace.of(obs)
    if pt is None:
        return None
    stats = pt.step_counts() if span == program_trace.COUNTS_SPAN \
        else [sp[3] for sp in pt.named(span)]
    values = [s[arg] * scale for s in stats if arg in s]
    if not values:
        return None
    mean = sum(values) / len(values)
    print(f"# {span} {arg} x {scale:g} over the window: mean {mean:.6g}, "
          f"max {max(values):.6g}, n {len(values)}", flush=True)
    return mean
