"""Device self time of the operations under the scopes ``scopes`` over
the device's busy time, in %, over the traced window's whole launches
(``scope_ms_per_launch`` says what a scope and ``ops`` are)."""

from __future__ import annotations

from benchmarks.readers import device_scopes


def read(obs, scopes, ops: str = "all"):
    times = device_scopes.of(obs)
    if times is None or not times.busy_ns:
        return None
    return 100.0 * times.scope_ns(scopes, ops) / times.busy_ns
