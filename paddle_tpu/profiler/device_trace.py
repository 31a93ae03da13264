"""Device time by the program's own scopes, from a profiler trace file.

A device trace (``.xplane.pb``, what ``jax.profiler`` writes) holds, for
every operation the chip ran, the HLO instruction's metadata: ``tf_op``
is the instruction's ``op_name``, the path of ``jax.named_scope``s it
was traced under (``jit(step)/attn_qkv/dot_general``), beside XLA's own
``hlo_category``, ``flops`` and ``bytes_accessed``.  They sit in the
file's ``XEventMetadata``, which ``jax.profiler.ProfileData`` does not
hand out, so this module reads the file itself: a decoder of the seven
messages' wire format, no proto library (tensorflow's costs 10 s of
import).

``load_xplane`` reads the file; ``device_time_by_scope`` reduces one
chip's operations to SELF time by ``(scope, "pallas" | "xla")`` over the
LAUNCHES of the serving step (an ``XLA Modules`` event each, so the
boundaries are the device's own), joined in order to the
``serving.launch`` span that enqueued each and the
``serving.step_counts`` marker that committed it; ``scope_table``
prints it.  ``DEVICE_SCOPES`` is the closed set of scope names the
serving steps open: an operation belongs to the INNERMOST component of
its path that is in the set, to ``unscoped`` where it has a path and no
such component, and to ``compiler`` where it has none or an argument's
name (layout copies, ``copy-done``, ``slice-done``: what XLA made that
no line of the program asked for).  Nothing here is imported by the serving path.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import re
import struct
from typing import (Any, Dict, Iterable, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple)

# every scope a serving step opens (``inference/paged_layout.py``,
# ``models/generation.py``, ``models/llama_paged.py``,
# ``models/deepseek_v32.py``, ``models/nemotron_h.py``,
# ``models/minicpm_sala.py``, ``models/kimi_linear.py``);
# tests/test_device_scopes.py holds each
# compiled step's working instructions to it
DEVICE_SCOPES = (
    "embed", "attn_qkv", "kv_scatter", "paged_attn", "attn_out", "mlp",
    "moe_route", "moe_experts", "shared_expert", "moe_latent_down",
    "moe_latent_up", "mla_qkv", "index_select", "sparse_attn", "mamba_in_proj",
    "mamba_conv", "ssd_scan", "state_snapshot", "mamba_out", "lm_head",
    "sample", "lightning_qkv", "lightning_out", "block_select", "ckey_write",
    "kda_qkv", "kda_conv", "kda_scan", "kda_out", "latent_attn")
UNSCOPED = "unscoped"       # a path, and no component of it in the set
COMPILER = "compiler"       # no path: XLA's own operation

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
LAUNCH_SPAN, COUNTS_SPAN = "serving.launch", "serving.step_counts"
# the device's clock and the host's agree to some tens of microseconds:
# on the chip a launch of an idle device read up to 25 us BEFORE the span
# that enqueued it opened (tests/data/tiny_engine_v5e.xplane.pb), while
# the next launch's span opens 0.4 ms and more after a launch starts
CLOCK_SLACK_NS = 100e3


class DeviceOp(NamedTuple):
    """One event of a chip's ``XLA Ops`` line."""
    name: str               # the instruction, as the benchmark prints it
    start_ns: float
    duration_ns: float
    tf_op: str              # "<scope path>:<type>", the type empty under
                            # JAX; "" for XLA's own operations
    hlo_category: str
    flops: int
    bytes_accessed: int
    program_id: int


class Module(NamedTuple):
    """One event of a chip's ``XLA Modules`` line: a launch of a program."""
    name: str
    start_ns: float
    duration_ns: float
    program_id: int


Span = Tuple[str, float, float, Dict[str, Any]]      # a host event


@dataclasses.dataclass
class XplaneTrace:
    ops: Dict[str, List[DeviceOp]]          # by TPU plane
    modules: Dict[str, List[Module]]
    spans: List[Span]                       # the host's, by name prefix


# --------------------------------------------------------------------------
# the file: protobuf wire format of XSpace / XPlane / XLine / XEvent /
# XEventMetadata / XStat / XStatMetadata (tsl/profiler/protobuf/xplane.proto)
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    """The varint at ``buf[i]`` and the index after it."""
    v = buf[i]
    i += 1
    if v >= 0x80:
        shift, v = 7, v & 0x7f
        while True:
            c = buf[i]
            i += 1
            v |= (c & 0x7f) << shift
            if c < 0x80:
                break
            shift += 7
    return v, i


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """An ``XStat`` as ``(name, value)``; a ``ref_value`` is resolved
    through the plane's ``stat_metadata``."""
    name, value = "", None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            (value,) = struct.unpack("<d", v)
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif f == 5:
            value = _text(v)
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf) -> Tuple[int, Any]:
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_name(text: str) -> str:
    """``benchmarks/harness/trace_reduce.op_name``: the trace prints an
    operation as its whole HLO line; keep the instruction's name without
    its number and mark a Pallas kernel."""
    m = re.match(r"%?([^\s=]+?)(\.\d+)? = ", text)
    if not m:
        return text[:80]
    return m.group(1) + (" (pallas)" if "tpu_custom_call" in text else "")


def _plane(buf):
    """``(name, [line bytes], {id: event metadata bytes}, {id: stat
    name})`` of one ``XPlane``."""
    name, lines, events, stats = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, m = _map_entry(v)
            events[k] = m
        elif f == 5:
            k, m = _map_entry(v)
            stats[k] = next((_text(x) for g, x in _fields(m) if g == 2), "")
    return name, lines, events, stats


def _event(buf):
    """``(metadata id, offset_ps, duration_ps, [stat bytes])``."""
    mid = off = dur = 0
    stats = []
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            off = v
        elif f == 3:
            dur = v
        elif f == 4:
            stats.append(v)
    return mid, off, dur, stats


def _event_metadata(buf, stat_names):
    """``(name, {stat: value})`` of one ``XEventMetadata``."""
    name, stats = "", {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 5:
            k, val = _stat(v, stat_names)
            stats[k] = val
    return name, stats


def _line(buf):
    """``(name, timestamp_ns, [event bytes])`` of one ``XLine``."""
    name, t0, events = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    return name, t0, events


def _events(raw, t0, emeta, stat_names, make):
    """For each event of a line: ``(make(name, stats) of the event's
    metadata, start_ns, duration_ns, [stat bytes])``; each metadata is
    made once."""
    made: Dict[int, Any] = {}
    for ev in raw:
        mid, off, dur, stats = _event(ev)
        if mid not in made:
            made[mid] = make(*_event_metadata(emeta.get(mid, b""),
                                              stat_names))
        yield made[mid], t0 + off / 1e3, dur / 1e3, stats


def _op_metadata(text: str, st: Dict[str, Any]) -> tuple:
    return (op_name(text), str(st.get("tf_op") or ""),
            str(st.get("hlo_category") or ""), int(st.get("flops") or 0),
            int(st.get("bytes_accessed") or 0),
            int(st.get("program_id") or 0))


def _module_metadata(text: str, st: Dict[str, Any]) -> tuple:
    m = re.search(r"\((\d+)\)$", text)       # jit_step(<program id>)
    return text, int(m.group(1)) if m else 0


def load_xplane(path, host_prefixes: Sequence[str] = ("serving.",)
                ) -> XplaneTrace:
    """Every TPU plane's operations and launches, and the host events
    whose name starts with one of ``host_prefixes``, their stats as a
    dict.  Times are nanoseconds on the trace's one clock, as
    ``jax.profiler.ProfileData`` gives them."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    trace = XplaneTrace(ops={}, modules={}, spans=[])
    prefixes = tuple(host_prefixes)

    def wanted(text, st):
        return text if text.startswith(prefixes) else None

    for f, plane in _fields(space):
        if f != 1:
            continue
        pname, lines, emeta, stat_names = _plane(plane)
        if DEVICE_PLANE_RE.match(pname):
            for raw in lines:
                lname, t0, events = _line(raw)
                if lname == OPS_LINE:
                    trace.ops[pname] = [
                        DeviceOp(m[0], start, dur, *m[1:])
                        for m, start, dur, _ in _events(
                            events, t0, emeta, stat_names, _op_metadata)]
                elif lname == MODULES_LINE:
                    trace.modules[pname] = [
                        Module(m[0], start, dur, m[1])
                        for m, start, dur, _ in _events(
                            events, t0, emeta, stat_names, _module_metadata)]
        elif pname.startswith("/host:CPU") and prefixes:
            for raw in lines:
                _, t0, events = _line(raw)
                trace.spans.extend(
                    (name, start, dur,
                     dict(_stat(x, stat_names) for x in stats))
                    for name, start, dur, stats in _events(
                        events, t0, emeta, stat_names, wanted) if name)
    trace.spans.sort(key=lambda sp: (sp[1], -sp[2]))
    return trace


def find_xplane(trace_dir) -> Optional[str]:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``."""
    files = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)       # a trace has a few thousand paths
def scope_of(tf_op: str) -> str:
    """The innermost component of the path that is in ``DEVICE_SCOPES``
    (``mlp/moe_experts/...`` is ``moe_experts``)."""
    # a path without "/" is an ARGUMENT's name, which XLA hands to the
    # layout copy it makes of it: the compiler's, like no path
    path = tf_op.rsplit(":", 1)[0]
    if "/" not in path:
        return COMPILER
    return next((part for part in reversed(path.split("/"))
                 if part in DEVICE_SCOPES), UNSCOPED)


def self_ns(events: Sequence[Tuple[float, float]]) -> List[float]:
    """For ``(start, duration)`` events sorted by ``(start, -duration)``:
    each one's duration less what the events nested inside it cover (a
    ``while`` or ``conditional`` holds its body's operations on the same
    line), so that the parts add up to the line's busy time."""
    out = [d for _, d in events]
    stack: List[Tuple[int, float]] = []         # (index, end)
    for i, (s, d) in enumerate(events):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= min(d, stack[-1][1] - s)
        stack.append((i, s + d))
    return [max(x, 0.0) for x in out]


def _union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


@dataclasses.dataclass
class Launch:
    """One launch of a step program, as the device ran it."""
    start_ns: float
    duration_ns: float
    program_id: int
    serial: Optional[int] = None            # the engine's, where joined
    counts: Optional[Dict[str, Any]] = None  # its serving.step_counts
    busy_ns: float = 0.0                    # union of its operations
    by_scope: Dict[Tuple[str, str], float] = dataclasses.field(
        default_factory=dict)               # (scope, kind): self ns


@dataclasses.dataclass
class ScopeTimes:
    """``device_time_by_scope``'s result: the WHOLE launches inside the
    window and their sums by ``(scope, "pallas" | "xla")``."""
    launches: List[Launch]
    ns: Dict[Tuple[str, str], float]
    flops: Dict[Tuple[str, str], int]
    bytes_accessed: Dict[Tuple[str, str], int]
    busy_ns: float
    cut: int                                # launches the window cut

    def scope_ns(self, scopes: Iterable[str], ops: str = "all") -> float:
        """Self time of ``scopes``; ``ops``: ``all``, ``xla``, ``pallas``."""
        want = set(scopes)
        return sum(ns for (scope, kind), ns in self.ns.items()
                   if scope in want and ops in ("all", kind))


def join_launches(launches: Sequence[Launch], spans: Sequence[Span]) -> None:
    """Give each launch its serial and its marker's counts.  The device
    runs the step's launches in the order the engine enqueued them, so
    their serials run on by one; a launch cannot start before the
    ``serving.launch`` span that enqueued it opened (by more than the
    clocks' slack), and it does start before the next one opens (the engine enqueues launch n+1 only after
    it has read launch n-1, which ends as n starts), so the first
    launch's serial is the largest that no launch contradicts."""
    opened = sorted((sp[1], sp[3]["launch"]) for sp in spans
                    if sp[0] == LAUNCH_SPAN and "launch" in sp[3])
    if not launches or not opened:
        return
    starts = [t for t, _ in opened]
    first = None
    for k, launch in enumerate(launches):
        at = bisect.bisect_right(starts, launch.start_ns + CLOCK_SLACK_NS) - 1
        if at >= 0:
            cap = opened[at][1] - k
            first = cap if first is None else min(first, cap)
    if first is None:
        return
    marks = {sp[3]["launch"]: sp[3] for sp in spans
             if sp[0] == COUNTS_SPAN and sp[3].get("launch") is not None}
    for k, launch in enumerate(launches):
        launch.serial = first + k
        launch.counts = marks.get(launch.serial)


def device_time_by_scope(ops: Sequence[DeviceOp], modules: Sequence[Module],
                         window: Optional[Tuple[float, float]] = None,
                         spans: Sequence[Span] = ()) -> ScopeTimes:
    """One chip's operations reduced to self time by scope over the
    launches of the step's programs: the programs some operation of
    which carries a scope of ``DEVICE_SCOPES``.  A launch the window
    cuts is dropped (``cut`` counts them), with its operations."""
    step_programs = {op.program_id for op in ops
                     if scope_of(op.tf_op) not in (UNSCOPED, COMPILER)}
    launches = [Launch(m.start_ns, m.duration_ns, m.program_id)
                for m in sorted(modules, key=lambda m: m.start_ns)
                if m.program_id in step_programs]
    join_launches(launches, spans)
    w0, w1 = window or (float("-inf"), float("inf"))
    inside = [la for la in launches
              if la.start_ns < w1 and la.start_ns + la.duration_ns > w0]
    whole = [la for la in inside
             if la.start_ns >= w0 and la.start_ns + la.duration_ns <= w1]
    out = ScopeTimes(launches=whole, ns={}, flops={}, bytes_accessed={},
                     busy_ns=0.0, cut=len(inside) - len(whole))
    if not whole:
        return out
    ordered = sorted(ops, key=lambda op: (op.start_ns, -op.duration_ns))
    own = self_ns([(op.start_ns, op.duration_ns) for op in ordered])
    starts = [la.start_ns for la in whole]
    ran: Dict[int, List[Tuple[float, float]]] = {}
    for op, ns in zip(ordered, own):
        k = bisect.bisect_right(starts, op.start_ns) - 1
        if k < 0:
            continue
        la = whole[k]
        if op.start_ns + op.duration_ns > la.start_ns + la.duration_ns \
                or op.program_id != la.program_id:
            continue
        key = (scope_of(op.tf_op),
               "pallas" if op.name.endswith(" (pallas)") else "xla")
        la.by_scope[key] = la.by_scope.get(key, 0.0) + ns
        out.ns[key] = out.ns.get(key, 0.0) + ns
        out.flops[key] = out.flops.get(key, 0) + op.flops
        out.bytes_accessed[key] = out.bytes_accessed.get(key, 0) \
            + op.bytes_accessed
        ran.setdefault(k, []).append(
            (op.start_ns, op.start_ns + op.duration_ns))
    for k, la in enumerate(whole):
        la.busy_ns = _union_ns(ran.get(k, ()))
    out.busy_ns = sum(la.busy_ns for la in whole)
    return out


def scope_table(times: ScopeTimes) -> str:
    """The operator's view: a row a ``(scope, kind)``, longest first."""
    n = len(times.launches)
    head = (f"{'Scope':<18}{'Ops':<8}{'Launches':>9}{'ms/launch':>11}"
            f"{'Busy(%)':>9}{'GFLOP/launch':>14}{'MB/launch':>11}")
    rule = "-" * len(head)
    lines = [rule, head, rule]
    for key, ns in sorted(times.ns.items(), key=lambda kv: -kv[1]):
        lines.append(
            f"{key[0]:<18}{key[1]:<8}{n:>9}{ns / n / 1e6:>11.4f}"
            f"{100.0 * ns / max(times.busy_ns, 1e-9):>9.2f}"
            f"{times.flops[key] / n / 1e9:>14.3f}"
            f"{times.bytes_accessed[key] / n / 1e6:>11.3f}")
    lines.append(rule)
    if n:
        lines.append(f"{n} launches, {times.busy_ns / n / 1e6:.4f} ms busy "
                     f"each; {times.cut} cut by the window")
    return "\n".join(lines)
