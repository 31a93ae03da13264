"""Device self time of the operations under the program's scopes
``scopes`` (``paddle_tpu.profiler.device_trace.DEVICE_SCOPES``, or
``compiler`` / ``unscoped``) per launch of the step, in ms: over the
launches that lie wholly inside the traced window, by the device's own
launch boundaries.  ``ops``: ``all``, or only the ``xla`` operations or
the ``pallas`` kernels under those scopes."""

from __future__ import annotations

from benchmarks.readers import device_scopes


def read(obs, scopes, ops: str = "all"):
    times = device_scopes.of(obs)
    if times is None:
        return None
    return times.scope_ns(scopes, ops) / len(times.launches) / 1e6
