"""The device's time by the program's own scopes in the traced window:
what ``paddle_tpu.profiler.device_trace`` (the program's reduction,
which ``Profiler.summary()`` prints for an operator) makes of the
traced run's ``.xplane.pb``.  The readers ``scope_ms_per_launch``,
``scope_busy_share_pct`` and ``launch_device_ms`` share it.

Found as ``program_trace`` finds its trace (the newest file under
``<checkout>/.bench_out/trace-*/``), reduced once a process and kept in
``obs``.  ``None``, never an error: off the chip, without a trace file,
where the program has no such module (the parent of the PR that added
it) or wrote no ``serving.*`` span, and where the window holds no whole
launch of the step (the DeepSeek cell's can be empty of device work).
"""

from __future__ import annotations

import time

from benchmarks.readers import program_trace

KEY = "device_scopes"                    # where ``of`` keeps it in ``obs``


def reduce(path):
    """``ScopeTimes`` of the fullest chip over the traced window."""
    try:
        from paddle_tpu.profiler import device_trace
    except ImportError:
        return None
    try:
        return _reduce(device_trace, path)
    except Exception as e:      # noqa: BLE001
        # a file the decoder cannot walk, or anything else: a traced run
        # does not fail on its trace's reduction; say so, leave the
        # metrics out
        print(f"# device time by scope: {path} not reduced: {e!r}", flush=True)
        return None


def _reduce(device_trace, path):
    t0 = time.perf_counter()
    trace = device_trace.load_xplane(
        path, ("serving.", program_trace.WINDOW_SPAN))
    windows = [sp for sp in trace.spans if sp[0] == program_trace.WINDOW_SPAN]
    spans = [sp for sp in trace.spans if sp[0] != program_trace.WINDOW_SPAN]
    if not windows or not spans or not trace.ops:
        return None
    _, w0, wd, _ = max(windows, key=lambda sp: sp[2])
    plane = max(trace.ops, key=lambda p: sum(op.duration_ns
                                             for op in trace.ops[p]))
    times = device_trace.device_time_by_scope(
        trace.ops[plane], trace.modules.get(plane, ()), (w0, w0 + wd), spans)
    if not times.launches:
        return None
    parts = sum(times.ns.values())
    print("# device time by scope, the window's whole launches "
          f"(parts {parts / 1e6:.3f} ms of {times.busy_ns / 1e6:.3f} busy):\n# "
          + device_trace.scope_table(times).replace("\n", "\n# ")
          + f"\n# read and reduced in {time.perf_counter() - t0:.2f} s",
          flush=True)
    return times


def of(obs):
    """The traced run's ``ScopeTimes``, or ``None`` (the module's
    docstring says where)."""
    if not obs.get("trace"):
        return None
    if KEY not in obs:
        path = program_trace.newest_xplane(program_trace.ROOT)
        obs[KEY] = reduce(path) if path else None
    return obs[KEY]
