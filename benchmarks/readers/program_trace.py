"""What the program itself wrote into the traced run's profile: its
``serving.*`` spans with their arguments, beside the device's
operations, cut to the traced window.  The readers of this directory
share it.

The runner hands a reader neither the trace's path nor the cell's name,
and its own reduction keeps only the runner's spans, without their
stats.  So the first reader that asks opens the newest ``.xplane.pb``
under ``<checkout>/.bench_out/trace-*/`` (a run wipes its own directory
before it traces) and keeps what it found in ``obs`` for the others:
one load a process.  Off the chip (``obs["trace"]`` is ``None``) there
is nothing to read: a host time from a CPU is not written under a
metric's name.

A span is ``(name, start_ns, duration_ns, stats)``; an event of the
device, as in ``harness/trace_reduce.py``, ``(name, start_ns,
duration_ns)``.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.harness import trace_reduce

Span = Tuple[str, float, float, Dict[str, Any]]

ROOT = pathlib.Path(__file__).resolve().parents[2]      # the checkout

WINDOW_SPAN = "traced_window"            # the runner's, around the traced part
PROGRAM_PREFIX = "serving."
STEP_SPAN = "serving.step"
COUNTS_SPAN = "serving.step_counts"
# what a step is made of; the device's idle time is shared out among
# these and the step itself, so what no phase covers is seen too
PHASE_SPANS = ("serving.admit", "serving.propose", "serving.pack",
               "serving.launch", "serving.fetch_logits", "serving.commit")
KEY = "program_trace"                    # where ``of`` keeps it in ``obs``


@dataclasses.dataclass(frozen=True)
class ProgramTrace:
    window: trace_reduce.Interval
    device: List[trace_reduce.Event]     # the fullest chip's operations, clipped
    spans: List[Span]                    # program spans wholly inside the window
    # steps and their phases that reach into the window, NOT clipped:
    # a step that the window cuts and its open phase would then start
    # together, and ``innermost`` gives a tie to the longer, the step
    phases: List[trace_reduce.Event]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def named(self, name: str) -> List[Span]:
        return [sp for sp in self.spans if sp[0] == name]

    def step_counts(self) -> List[Dict[str, Any]]:
        """The counts of the steps that lie wholly inside the window
        (a marker alone inside it belongs to a step that is cut)."""
        whole = {sp[3].get("step") for sp in self.named(STEP_SPAN)}
        return [sp[3] for sp in self.named(COUNTS_SPAN)
                if sp[3].get("step") in whole]

    def kernel_ns_in_steps(self, kernel: str) -> float:
        """Device time of the Pallas kernel ``kernel`` inside the whole
        steps.  The host blocks in ``serving.fetch_logits`` until the
        device has run the step, so a step's operations lie inside its
        ``serving.step``; counts and kernel time then cover the same
        steps."""
        mine = [e for e in self.device if is_kernel(e[0], kernel)]
        return sum(trace_reduce.busy_ns(trace_reduce.clip(mine, (s, s + d)))
                   for _, s, d, _ in self.named(STEP_SPAN))


def is_kernel(op: str, kernel: str) -> bool:
    """Whether the device operation ``op`` (as ``trace_reduce.op_name``
    prints it) is the Pallas kernel ``kernel``: the instruction is named
    after the ``pallas_call``'s ``name=``, with the autodiff scope around
    it where there is one (``jvp_<name>_``)."""
    return bool(re.fullmatch(rf"(\w+_)?{re.escape(kernel)}_* \(pallas\)", op))


def newest_xplane(root) -> Optional[pathlib.Path]:
    files = list((pathlib.Path(root) / ".bench_out").glob(
        "trace-*/plugins/profile/*/*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime) if files else None


def load_xplane(path) -> Dict[str, Any]:
    """``{"devices": {plane: [Event]}, "window": [Event], "program":
    [Span]}``: as ``trace_reduce.load_xplane``, and the program's spans
    with their stats."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    devices: Dict[str, List[trace_reduce.Event]] = {}
    window: List[trace_reduce.Event] = []
    program: List[Span] = []
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE_RE.match(plane.name):
            for ln in plane.lines:
                if ln.name == trace_reduce.OPS_LINE:
                    devices[plane.name] = [
                        (trace_reduce.op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in ln.events]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for e in ln.events:
                    at = (float(e.start_ns), float(e.duration_ns))
                    if e.name == WINDOW_SPAN:
                        window.append((e.name, *at))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append((e.name, *at, dict(e.stats)))
    return {"devices": devices, "window": window, "program": program}


def reduce(trace: Dict[str, Any]) -> Optional[ProgramTrace]:
    """Cut to the traced window (the longest ``traced_window`` span, as
    ``trace_reduce.reduce_trace`` takes it).  ``None`` where the program
    wrote no span into it: the parent of the PR that added them."""
    if not trace["window"] or not trace["program"]:
        return None
    _, w0, wd = max(trace["window"], key=lambda e: e[2])
    window = (w0, w0 + wd)
    per_dev = [trace_reduce.clip(ev, window)
               for ev in trace["devices"].values()]
    device = max(per_dev, key=trace_reduce.busy_ns, default=[])
    spans = [sp for sp in trace["program"]
             if sp[1] >= w0 and sp[1] + sp[2] <= w0 + wd]
    phases = [sp[:3] for sp in trace["program"]
              if (sp[0] == STEP_SPAN or sp[0] in PHASE_SPANS)
              and sp[1] < w0 + wd and sp[1] + sp[2] > w0]
    return ProgramTrace(window=window, device=device, spans=spans,
                        phases=phases)


def of(obs: Dict[str, Any], root=ROOT) -> Optional[ProgramTrace]:
    """The traced run's ``ProgramTrace``, or ``None`` off the chip and
    where there is no trace file or no program span in it.  ``root`` is
    the checkout the run wrote its trace into: this file's own."""
    if not obs.get("trace"):
        return None
    if KEY not in obs:
        path = newest_xplane(root)
        obs[KEY] = reduce(load_xplane(path)) if path else None
    return obs[KEY]
