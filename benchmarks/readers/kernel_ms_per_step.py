"""Device time of the Pallas kernel named ``kernel`` (its
``pallas_call``'s ``name=``) per engine step, in ms: over the steps
that lie wholly inside the traced window."""

from __future__ import annotations

from benchmarks.readers import program_trace


def read(obs, kernel: str):
    pt = program_trace.of(obs)
    if pt is None:
        return None
    steps = len(pt.named(program_trace.STEP_SPAN))
    ns = pt.kernel_ns_in_steps(kernel)
    return ns / 1e6 / steps if steps and ns else None
