#!/usr/bin/env python3
"""Sound runs and the three controls of a ``serve_deepseek_v32`` cell in
ONE process, at the cell's own size on the chip (``tools/seeds.py`` knows
its own two controls only):

    python3 benchmarks/tools/controls_deepseek_v32.py --workload <cell> \
        --seeds 31,32,33 --seconds 20 --controls none,fp8,no_selection,gates_held

Each (seed, control) is one run of the cell through the runner's
``overrides``.  ``none`` is a sound run, judged on its served tokens'
gaps.  Under a control the run is a sound one too, and the CONTROL's
greedy choices are held to the limits in the served tokens' place
(``runners/serve_deepseek_v32.py``): ``fp8`` the reference with every
matmul operand in fp8, ``no_selection`` every row attending its whole
context, ``gates_held`` the gates normalised over the held experts.
A control has to come out as not correct; its row also carries the
sound run's gaps (``sound_*``), so one run gives both readings.  The
limits in the configuration's ``"check"`` were set from these rows
(PERF.md section 2).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CONTROLS = {"none": {}, "fp8": {"control_lowp": "fp8"},
            "no_selection": {"control_no_selection": True},
            "gates_held": {"control_gates": "held"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--controls", default="none,fp8,no_selection,gates_held")
    args = ap.parse_args(argv)

    from benchmarks import run as bench_run

    rows = []
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = args.controls.split(",")
    for n, seed in enumerate(seeds):
        # each seed runs once; the controls take turns over the seeds
        control = controls[n % len(controls)]
        report = {}
        line = bench_run.run_cell(ROOT, args.workload, seed, args.seconds,
                                  False, overrides=dict(CONTROLS[control]),
                                  t_process=time.perf_counter(), report=report)
        row = {"seed": seed, "control": control, "correct": line["correct"],
               "failed": line["failed"], "attempted": line["attempted"],
               **{c["name"]: c["value"] for c in report["checks"]},
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "memory_peak_bytes": line["device"]["memory_peak_bytes"]}
        if "control" in report:
            row["reference_control"] = report["control"]
        rows.append(row)
        print("# controls " + json.dumps(row), flush=True)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"controls-{args.workload}-{seeds[0]}.json").write_text(
        json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
