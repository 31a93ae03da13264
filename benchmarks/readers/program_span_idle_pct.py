"""The device's idle time that lay under the program's span ``span``,
as a share of the traced window, in %.

The idle gaps of the window are shared out among ``serving.step`` and
its phases, each gap to the innermost span that covered it
(``trace_reduce.idle_gaps``), so what no phase covers falls to the step
itself and what no step covers to ``(no span)``.  The host runs ahead of
the device: idle time under a span means the device had nothing queued
while the host did that.  Under ``serving.launch`` it waited for the
uploads; under ``serving.fetch_logits`` for nothing the host could have
given it sooner.  The whole split is printed once a run.
"""

from __future__ import annotations

from benchmarks.harness import trace_reduce
from benchmarks.readers import program_trace

SPLIT_KEY = "program_trace.idle_split"


def idle_split(obs, pt):
    """``{span: seconds}`` of the window's idle time, kept in ``obs``."""
    if SPLIT_KEY not in obs:
        # every name: the phases, the step itself and "(no span)"
        obs[SPLIT_KEY] = dict(trace_reduce.idle_gaps(
            pt.device, pt.phases, pt.window,
            n=len(program_trace.PHASE_SPANS) + 2))
        shares = ", ".join(f"{k} {100e9 * v / pt.window_ns:.3f}%"
                           for k, v in obs[SPLIT_KEY].items())
        print(f"# idle time of the device by program span, of the window: "
              f"{shares}", flush=True)
    return obs[SPLIT_KEY]


def read(obs, span: str):
    pt = program_trace.of(obs)
    if pt is None or not pt.device \
            or not any(e[0] == span for e in pt.phases):
        return None
    return 100e9 * idle_split(obs, pt).get(span, 0.0) / pt.window_ns
