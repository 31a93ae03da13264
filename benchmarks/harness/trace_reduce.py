"""From a profiler trace to numbers: device busy and idle time, time by
operation, idle gaps named after the host span that covered them, and
the collectives' share.  Works on plain lists of events, so it is
tested on synthetic lists as well as on a recorded ``.xplane.pb``.

An event is ``(name, start_ns, duration_ns)``.
"""

from __future__ import annotations

import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]
Interval = Tuple[float, float]

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# HLO opcodes that move data between chips (also as -start/-done pairs)
COLLECTIVE_RE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)(-start|-done)?(\.\d+)?$")


def op_name(text: str) -> str:
    """The trace prints an operation as its whole HLO line, ``%name.N =
    type opcode(...)``.  Keep the instruction's name without the number,
    so that the layers' copies of one operation add up, and mark a
    Pallas kernel (a ``tpu_custom_call``)."""
    m = re.match(r"%?([^\s=]+?)(\.\d+)? = ", text)
    if not m:
        return text[:80]
    return m.group(1) + (" (pallas)" if "tpu_custom_call" in text else "")


def merged(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(events: Sequence[Event], window: Interval) -> List[Event]:
    """Events cut to the window; those outside it are dropped."""
    w0, w1 = window
    out = []
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_ns(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in merged((s, s + d) for _, s, d in events))


def idle_share(events: Sequence[Event], window: Interval) -> float:
    """1 minus the union of device-operation intervals over the window."""
    span = window[1] - window[0]
    if span <= 0:
        raise ValueError("empty window")
    return 1.0 - busy_ns(clip(events, window)) / span


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds by operation name, each event counted for the part of
    it that no event nested inside it covers (a ``while`` holds its
    body's operations on the same line)."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [name, end, self_ns]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    close(float("inf"))
    return out


def top_ops(events: Sequence[Event], n: int = 10) -> List[List]:
    """``[[name, seconds], ...]``: the operations that took most time."""
    by = sorted(self_times(events).items(), key=lambda kv: -kv[1])
    return [[name, ns / 1e9] for name, ns in by[:n]]


def collective_share(events: Sequence[Event]) -> Optional[float]:
    """Device time in collective operations over device busy time."""
    busy = busy_ns(events)
    if busy <= 0:
        return None
    coll = [e for e in events if COLLECTIVE_RE.match(e[0])]
    return busy_ns(coll) / busy


def innermost(host_spans: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """The spans as a disjoint timeline ``(start, end, name)``: where
    spans nest or overlap, the one that started last holds the time."""
    spans = sorted((s, s + d, name) for name, s, d in host_spans if d > 0)
    points = sorted({x for sp in spans for x in sp[:2]})
    out, active, j = [], [], 0
    for a, b in zip(points, points[1:]):
        while j < len(spans) and spans[j][0] <= a:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            out.append((a, b, max(active)[2]))
    return out


def idle_gaps(events: Sequence[Event], host_spans: Sequence[Event],
              window: Interval, n: int = 10) -> List[List]:
    """``[[span name, seconds], ...]``: the device's idle time inside the
    window, shared out among the host spans that covered it (the
    innermost where they nest), longest first.  Idle time under no span
    goes to ``"(no span)"``."""
    busy = merged((s, s + d) for _, s, d in clip(events, window))
    gaps: List[Interval] = []
    at = window[0]
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = b
    if window[1] > at:
        gaps.append((at, window[1]))
    line = innermost(host_spans)
    out: Dict[str, float] = {}
    i = 0
    for ga, gb in gaps:
        while i < len(line) and line[i][1] <= ga:
            i += 1
        covered, j = 0.0, i
        while j < len(line) and line[j][0] < gb:
            ov = min(gb, line[j][1]) - max(ga, line[j][0])
            if ov > 0:
                out[line[j][2]] = out.get(line[j][2], 0.0) + ov
                covered += ov
            j += 1
        if gb - ga > covered:
            out["(no span)"] = out.get("(no span)", 0.0) + (gb - ga - covered)
    by = sorted(out.items(), key=lambda kv: -kv[1])
    return [[name, ns / 1e9] for name, ns in by[:n]]


# --------------------------------------------------------------------------
# reading the profiler's file
# --------------------------------------------------------------------------

def find_xplane(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path, span_names: Iterable[str]) -> Dict[str, object]:
    """``{"devices": {plane: [Event]}, "host": [Event]}`` from an ``.xplane.pb``: each TPU plane's operation line, and the host
    events whose names are in ``span_names``."""
    import jax

    want = set(span_names)
    data = jax.profiler.ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE_RE.match(plane.name):
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in ln.events]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in ln.events if e.name in want)
    return {"devices": devices, "host": host}


def reduce_trace(trace: Dict[str, object], window_span: str) -> Dict[str, object]:
    """The traced window is the host span named ``window_span`` (the
    runner opens it around the steady part of the trace).  Busy time is
    averaged over the device planes; the breakdown is the fullest
    plane's."""
    win = [e for e in trace["host"] if e[0] == window_span]
    if not win:
        raise ValueError(f"no host span {window_span!r} in the trace")
    _, w0, wd = max(win, key=lambda e: e[2])
    window = (w0, w0 + wd)
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU operation line")
    per_dev = {p: clip(ev, window) for p, ev in devices.items()}
    busy = {p: busy_ns(ev) for p, ev in per_dev.items()}
    fullest = max(busy, key=busy.get)
    events = per_dev[fullest]
    spans = [e for e in clip(trace["host"], window) if e[0] != window_span]
    return {
        "window_s": wd / 1e9,
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "idle_share": 1.0 - sum(busy.values()) / len(busy) / wd,
        "collective_share": collective_share(events),
        "device_ops": top_ops(events),
        "idle_gaps": idle_gaps(events, spans, window),
        "n_device_events": sum(len(e) for e in per_dev.values()),
    }
