#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It finds the cell's configuration, traffic mix, runner and
per-layer readers by the names in ``BENCHMARK.json``, fails without
running a model where JAX finds no TPU or another count of chips than
the cell's, warms up, measures for ``--seconds``, decides ``correct``
against the plain reference, and prints one JSON object as the last
line of its standard output.  ``benchmarks/README.md`` says how a later
PR adds a cell, a configuration, a mix, a per-layer metric or a runner.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse                                       # noqa: E402
import json                                           # noqa: E402
import pathlib                                        # noqa: E402
import sys                                            # noqa: E402
import traceback                                      # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ".bench_out"            # inside the checkout; in .gitignore


class NoChip(RuntimeError):
    """JAX found no TPU, or another number of chips than the cell asks."""


def require_chips(target, n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != target.platform:
        raise NoChip(f"the benchmark needs a {target.platform.upper()}; JAX "
                     f"found {devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) != n_chips:
        raise NoChip(f"the cell asks for {n_chips} chip(s); JAX found "
                     f"{len(devs)}")
    return devs


def make_context(root, workload: str, seed: int, seconds: float, trace: bool,
                 target=None, devices=None, overrides=None, t_process=None,
                 report=None):
    """The cell's files, its runner and the context a runner is handed;
    fails before any model exists where the chips are not the cell's."""
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.harness import clocks, context, manifest

    cell = manifest.load_cell(root, workload)
    runner = manifest.load_runner(root, cell.config["runner"])
    target = target or context.Target()
    if devices is None:
        devices = require_chips(target, cell.chips)
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    trace_dir = pathlib.Path(root) / TRACE_DIR / f"trace-{workload}"
    if trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
    ctx = context.RunContext(
        cell=cell, seed=seed, seconds=seconds, trace=trace, target=target,
        clock=clocks.CompileClock(), spans=clocks.SpanLog(),
        t_process=T_PROCESS if t_process is None else t_process,
        trace_dir=str(trace_dir), devices=list(devices),
        overrides=dict(overrides or {}),
        report=report if report is not None else {})
    ctx.say(f"cell {workload}: {len(devices)} x {devices[0].device_kind}; "
            f"compile cache {cache}")
    return ctx, runner


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             **injected):
    """Run one cell and return the result object (the last line).  The
    rehearsal test injects ``target`` and ``devices``; a control injects
    ``overrides`` and reads the numbers compared from ``report``."""
    ctx, runner = make_context(root, workload, seed, seconds, trace, **injected)
    from benchmarks.harness import readers      # root is on sys.path now

    cell, devices = ctx.cell, ctx.devices
    res = runner.run(ctx)
    ctx.report.update(checks=ctx.checks.rows, summary=res.get("summary"))

    line = {"correct": ctx.checks.correct, "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": None,
            "device": res["device"]}
    if trace:
        obs = {**res["obs"], "config": cell.config, "traffic": cell.traffic,
               "chips": len(devices), "device_kind": devices[0].device_kind}
        line["metrics"] = readers.read_all(root, cell.per_layer, obs)
        tr = obs.get("trace")
        if tr:
            line["device"] = {**res["device"], "busy_s": tr["busy_s"],
                              "window_s": tr["window_s"]}
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        missing = sorted(set(units) - set(res["metrics"]))
        if missing:
            raise RuntimeError(f"the runner reported no {missing}")
        line["metrics"] = {k: {"value": float(res["metrics"][k]), "unit": units[k]}
                           for k in units}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except Exception:  # noqa: BLE001 - no result line; the exit code says so
        traceback.print_exc()
        sys.stderr.flush()
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
