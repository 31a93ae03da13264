"""What a runner is handed, and how it reports: the cell, the seed, the
window, the device expectations, the clocks, and the list of numbers
compared with their limits that decides ``correct``."""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

from .clocks import CompileClock, SpanLog
from .manifest import Cell


@dataclasses.dataclass(frozen=True)
class Target:
    """``run.py`` builds the chip's; the CPU rehearsal test injects its
    own (as ``tests/test_chip_smoke.py`` does for the smoke)."""
    platform: str = "tpu"
    trace_device: bool = True       # reduce the device planes of a trace


class Checks:
    """Every number compared, beside its limit; printed in every run."""

    def __init__(self, say):
        self.rows: List[Dict[str, Any]] = []
        self._say = say

    def at_most(self, name: str, value: float, limit: float) -> bool:
        ok = bool(math.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": ok})
        self._say(f"check {name} = {value:.6g} (limit {limit:.6g}) "
                  f"{'ok' if ok else 'NOT CORRECT'}")
        return ok

    def within(self, name: str, value: float, lo: float, hi: float) -> bool:
        ok = bool(math.isfinite(value) and lo <= value <= hi)
        self.rows.append({"name": name, "value": value, "limit": [lo, hi],
                          "ok": ok})
        self._say(f"check {name} = {value:.6g} (limits {lo:.6g} .. {hi:.6g}) "
                  f"{'ok' if ok else 'NOT CORRECT'}")
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    target: Target
    clock: CompileClock
    spans: SpanLog
    t_process: float                      # perf_counter at process start
    trace_dir: str
    devices: list
    # what a control or a rehearsal changes from outside the program:
    # keyword arguments for the engine, a wrapper around the step
    overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    checks: Optional[Checks] = None
    # what a tool wants to read beyond the last line (control readings)
    report: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.checks is None:
            self.checks = Checks(self.say)

    def say(self, msg: str) -> None:
        print(f"# [{time.perf_counter() - self.t_process:7.1f}s] {msg}",
              flush=True)


class TraceWindow:
    """A few seconds of the steady window inside ``jax.profiler.trace``,
    opened 30% into the window; the host span ``name`` marks it in the
    trace, and while it is open the run's spans are annotations too."""

    def __init__(self, ctx: "RunContext", name: str, trace_s: float):
        self.ctx, self.name = ctx, name
        self.at = ctx.seconds * 0.3
        self.length = min(float(trace_s), ctx.seconds * 0.5)
        self.state = "no" if ctx.trace else "done"
        self.interval = (0.0, 0.0)        # perf_counter at open and close
        self._span = None

    def poll(self, since_t0: float) -> None:
        """Open or close the trace when due; call once per loop turn."""
        if self.state == "no" and since_t0 >= self.at:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.ctx.trace_dir, profiler_options=opts)
            self.ctx.spans.annotate = True
            self._span = jax.profiler.TraceAnnotation(self.name)
            self._span.__enter__()
            self.state, self.interval = "on", (time.perf_counter(), 0.0)
        elif self.state == "on" and \
                time.perf_counter() - self.interval[0] >= self.length:
            self.close()

    def close(self) -> None:
        if self.state != "on":
            return
        import jax

        self._span.__exit__(None, None, None)
        self.interval = (self.interval[0], time.perf_counter())
        self.ctx.spans.annotate = False
        jax.profiler.stop_trace()
        self.state = "done"


def device_report(devices) -> Dict[str, Any]:
    """The device as JAX reports it; the peak on the fullest chip."""
    peak = 0
    for d in devices:
        peak = max(peak, int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def traced(ctx: RunContext, span_names, window_span: str) -> Optional[Dict[str, Any]]:
    """Reduce the trace this run wrote under ``ctx.trace_dir``."""
    from . import trace_reduce

    trace = trace_reduce.load_xplane(
        trace_reduce.find_xplane(ctx.trace_dir), [*span_names, window_span])
    if not ctx.target.trace_device:
        # a rehearsal off the chip: the host spans are there, no TPU plane
        if not any(e[0] == window_span for e in trace["host"]):
            raise ValueError(f"no host span {window_span!r} in the trace")
        return None
    return trace_reduce.reduce_trace(trace, window_span)
