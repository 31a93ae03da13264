"""Median device busy time of a launch of the step, in ms, over the
traced window's whole launches of one ``kind``: ``decode`` (the
launch's ``serving.step_counts`` marker has ``prefill_rows`` 0) or
``chunk`` (it carries prompt rows).  A launch is joined to its marker
by the marker's ``launch`` serial; a program whose markers carry none
(the parent of the PR that added it) gives ``None``."""

from __future__ import annotations

import statistics

from benchmarks.readers import device_scopes


def read(obs, kind: str):
    if kind not in ("decode", "chunk"):
        raise ValueError(f"kind {kind!r}: decode or chunk")
    times = device_scopes.of(obs)
    if times is None:
        return None
    busy = [la.busy_ns for la in times.launches if la.counts is not None
            and (la.counts["prefill_rows"] > 0) == (kind == "chunk")]
    return statistics.median(busy) / 1e6 if busy else None
