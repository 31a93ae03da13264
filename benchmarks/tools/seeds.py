#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds in ONE
process (set-up is long), and the controls that must come out as not
correct, at the cell's own size on the chip:

    python3 benchmarks/tools/seeds.py --workload <cell> --seeds 11,12,13 --seconds 12
    python3 benchmarks/tools/seeds.py --workload <serving cell> --seeds 21,22,23 --seconds 12 --control int8kv
    python3 benchmarks/tools/seeds.py --workload <cell> --seeds 21,22,23 --seconds 12 --control fp8

``--control int8kv`` (serving): the program's own lower-precision path,
the engine with an int8 KV cache, serves and is judged like a sound run.
``--control fp8``: the plain reference, in fp8, in the program's place:
for serving beside a sound run, on the same prompts and served tokens;
for training alone, against the float32 reference.  The limits in
``benchmarks/runners/*.py`` were set from these readings (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def train_control(cell, seed: int, lowp: str):
    from benchmarks.harness import manifest

    tr = manifest.load_runner(ROOT, "train")
    cfg, mix = cell.config, cell.traffic
    ref = tr.follow_reference(seed, cfg, mix)
    low = tr.follow_reference(seed, cfg, mix, lowp=lowp)
    return {
        "loss_gap_worst_step": max(abs(a - b) for a, b in
                                   zip(low["losses"], ref["losses"])),
        "first_grad_norm_gap_worst_leaf":
            tr.worst_leaf_gap(low["grad_norms"], ref["grad_norms"]),
        "first_grad_sample_gap_worst_leaf":
            tr.worst_sample_gap(low["grad_samples"], ref["grad_samples"]),
        "param_change_norm_gap_worst_leaf":
            tr.worst_leaf_gap(low["change_norms"], ref["change_norms"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control", choices=("none", "int8kv", "fp8"), default="none")
    args = ap.parse_args(argv)

    from benchmarks import run as bench_run
    from benchmarks.harness import manifest

    cell = manifest.load_cell(ROOT, args.workload)
    kind = cell.config["runner"]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        if kind == "train" and args.control == "fp8":
            from paddle_tpu.utils.compile_cache import enable_compile_cache

            enable_compile_cache()
            row = {"seed": seed, "control": "fp8", **train_control(cell, seed, "fp8")}
        else:
            overrides = {}
            if args.control == "int8kv":
                import jax.numpy as jnp

                overrides["engine"] = {"cache_dtype": jnp.int8}
            elif args.control == "fp8":
                overrides["control_lowp"] = "fp8"
            report = {}
            line = bench_run.run_cell(ROOT, args.workload, seed, args.seconds,
                                      False, overrides=overrides,
                                      t_process=time.perf_counter(), report=report)
            row = {"seed": seed, "control": args.control,
                   "correct": line["correct"], "failed": line["failed"],
                   "attempted": line["attempted"],
                   **{c["name"]: c["value"] for c in report["checks"]},
                   "metrics": {k: v["value"] for k, v in line["metrics"].items()},
                   "memory_peak_bytes": line["device"]["memory_peak_bytes"]}
            if "control" in report:
                row["reference_control"] = report["control"]
        rows.append(row)
        print("# seeds " + json.dumps(row), flush=True)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    name = f"seeds-{args.workload}-{args.control}-{rows[0]['seed']}.json"
    (out / name).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
