"""paddle_tpu.profiler (analog of python/paddle/profiler/profiler.py:358).

``RecordEvent`` is the program's one span.  Entering it opens a
``jax.profiler.TraceAnnotation``, so the span is in any profiler trace
that runs, whoever started it, on the same clock as the device's
operations and with its keyword arguments as the event's stats; while a
``Profiler`` of this module records, the span is also kept in memory
for ``summary()`` and the chrome trace.  With no trace running a span
is a no-op.

On a TPU ``Profiler.start()`` also writes the device's trace into
``profiler.trace_dir``, and ``summary()`` appends the DEVICE's time by
the serving step's own scopes (``device_trace.DEVICE_SCOPES``: the
``jax.named_scope``s of the step, read from the trace's operation
metadata): a row a scope and kind of operation (``pallas`` kernel or
``xla``), with the launches, ms a launch, share of the device's busy
time, and XLA's own GFLOP and MB a launch.  ``device_trace`` reads any
``.xplane.pb`` the same way (``load_xplane``, ``device_time_by_scope``,
``scope_table``); the benchmark's per-layer metrics are its other reader.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Any, Dict, List

import jax

from ..core.device import is_tpu as _is_tpu

_host_events: List[Dict[str, Any]] = []
_recording = [False]
_open_spans = threading.local()     # .stack: names of this thread's open spans


class RecordEvent:
    """A span (analog of paddle/fluid/platform/profiler/event_tracing.h
    RecordEvent).  ``args`` are Python numbers or strings; they ride on
    the trace event as its stats and on the recorded host event as
    ``args``, beside the enclosing span's name (``parent``)."""

    def __init__(self, name: str, event_type: str = "UserDefined", **args):
        self.name = name
        self.event_type = event_type
        self.args = args
        self._ann = None
        self._begin = None          # set only while a Profiler records
        self._parent = None

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        if _recording[0]:
            stack = getattr(_open_spans, "stack", None)
            if stack is None:
                stack = _open_spans.stack = []
            self._parent = stack[-1] if stack else None
            stack.append(self.name)
            self._begin = time.perf_counter_ns()

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._begin is None:
            return
        begin, self._begin = self._begin, None
        _open_spans.stack.pop()
        if not _recording[0]:
            return
        event = {
            "name": self.name, "cat": self.event_type, "ph": "X",
            "ts": begin / 1000.0,
            "dur": (time.perf_counter_ns() - begin) / 1000.0,
            # full ident: masking could collide two threads into one
            # (pid, tid) sweep lane and corrupt the per-thread self-time
            # subtraction in summarize_events
            "pid": os.getpid(), "tid": threading.get_ident(),
        }
        args = dict(self.args)
        if self._parent:
            args["parent"] = self._parent
        if args:
            event["args"] = args
        _host_events.append(event)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    def __init__(self, timer_only=False):
        self.timer_only = timer_only
        self.trace_dir = None       # where the device's trace was written
        self._tracing = False
        self._running = False

    def start(self):
        _recording[0] = True
        _host_events.clear()
        self._running = True
        if not self.timer_only and _is_tpu():
            # the device's trace, with every RecordEvent in it
            self.trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_profile_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True

    def stop(self):
        _recording[0] = False
        self._running = False
        if self._tracing:
            self._tracing = False
            jax.profiler.stop_trace()

    def step(self):
        from ..common import flags as _flags

        if not self._running:
            return
        if _flags.get_flag("FLAGS_log_memory_stats"):
            from .. import device as _device

            _host_events.append({
                "name": "memory_stats", "ph": "C", "dur": 0,
                "ts": time.perf_counter() * 1e6,
                "args": {"allocated": _device.memory_allocated(),
                         "max_allocated": _device.max_memory_allocated()},
            })

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def export_chrome_tracing(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": _host_events}, f)

    export = export_chrome_tracing

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", top_n: int = 30):
        """Aggregated statistics table (the profiler_statistic.py analog:
        python/paddle/profiler/profiler_statistic.py) — per-event-name
        calls / total / avg / max / min and share of the profiled span,
        sorted by total self time.  After a recording on a TPU, the
        device's time by the serving step's scopes follows (the module
        docstring; nothing where the trace holds no launch of a step)."""
        table = summarize_events(_host_events, time_unit=time_unit,
                                 top_n=top_n)
        if self.trace_dir is None or self._tracing:
            return table
        from . import device_trace

        path = device_trace.find_xplane(self.trace_dir)
        if path is None:
            return table
        trace = device_trace.load_xplane(path)
        for plane in sorted(trace.ops):
            times = device_trace.device_time_by_scope(
                trace.ops[plane], trace.modules.get(plane, ()),
                spans=trace.spans)
            if times.launches:
                table += f"\n{plane}\n{device_trace.scope_table(times)}"
        return table


def summarize_events(events, time_unit="ms", top_n: int = 30) -> str:
    """Build the top-N-by-SELF-time table from chrome-trace-style event
    dicts (ph == 'X'): nested span durations are subtracted from their
    parent (a RecordEvent wrapping ten op spans reports only its own
    overhead), so per-name ratios sum to <= 100% of the profiled wall
    span.  A row is one name under one enclosing span (the last column;
    as ``RecordEvent`` recorded it, else as the intervals nest).  Also
    works on an EXPORTED trace: ``summarize_chrome_trace``."""
    div = {"s": 1e6, "ms": 1e3, "us": 1.0}[time_unit]
    # interval sweep PER (pid, tid): nesting only holds within one
    # thread — mixing threads would subtract unrelated concurrent spans
    # from each other's self time
    by_thread: Dict[tuple, list] = {}
    for e in events:
        if e.get("ph") == "X":
            by_thread.setdefault((e.get("pid", 0), e.get("tid", 0)),
                                 []).append(e)
    stats: Dict[tuple, list] = {}
    lo, hi = float("inf"), 0.0
    for spans in by_thread.values():
        spans.sort(key=lambda e: (e["ts"], -e["dur"]))
        # a span starting inside the currently-open span is its child —
        # subtract the child's (inclusive) duration from the parent's
        # self time (direct children only; grandchildren already
        # reduced the child)
        self_time = [e["dur"] for e in spans]
        parent = [None] * len(spans)
        open_stack: list = []
        for i, e in enumerate(spans):
            ts, dur = e["ts"], e["dur"]
            while open_stack and ts >= spans[open_stack[-1]]["ts"] \
                    + spans[open_stack[-1]]["dur"] - 1e-9:
                open_stack.pop()
            if open_stack:
                self_time[open_stack[-1]] -= dur
                parent[i] = spans[open_stack[-1]]["name"]
            parent[i] = (e.get("args") or {}).get("parent", parent[i])
            open_stack.append(i)
            lo = min(lo, ts)
            hi = max(hi, ts + dur)
        for i, e in enumerate(spans):
            st = max(self_time[i], 0.0)
            s = stats.setdefault((e["name"], parent[i]),
                                 [0, 0.0, 0.0, float("inf")])
            s[0] += 1
            s[1] += st
            s[2] = max(s[2], st)
            s[3] = min(s[3], st)
    wall = max(hi - lo, 1e-9)
    header = (f"{'Name':<36}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
              f"{'Avg(' + time_unit + ')':>12}{'Max(' + time_unit + ')':>12}"
              f"{'Min(' + time_unit + ')':>12}{'Ratio(%)':>10}  Under")
    lines = ["-" * len(header), header, "-" * len(header)]
    rows = sorted(stats.items(), key=lambda kv: -kv[1][1])[:top_n]
    for (name, under), (calls, total, mx, mn) in rows:
        lines.append(f"{name[:35]:<36}{calls:>8}{total / div:>14.3f}"
                     f"{total / calls / div:>12.3f}{mx / div:>12.3f}"
                     f"{mn / div:>12.3f}{100.0 * total / wall:>10.2f}"
                     f"  {under or '-'}")
    lines.append("-" * len(header))
    return "\n".join(lines)


def summarize_chrome_trace(path: str, time_unit="ms", top_n: int = 30) -> str:
    """Summary table from an exported chrome trace file."""
    with open(path) as f:
        data = json.load(f)
    events = data.get("traceEvents", data if isinstance(data, list) else [])
    return summarize_events(events, time_unit=time_unit, top_n=top_n)
