#!/usr/bin/env python3
"""Sound runs and the four controls of a ``serve_mellum2`` cell in ONE
process, at the cell's own size on the chip (``tools/seeds.py`` knows its
own two controls only):

    python3 benchmarks/tools/controls_mellum2.py --workload <cell> \
        --seeds 31,32,33,34,35 --seconds 20 \
        --controls none,fp8,no_window,no_yarn,gates_softmax

Each (seed, control) is one run of the cell through the runner's
``overrides``.  ``none`` is a sound run, judged on its served tokens'
gaps.  Under a control the run is a sound one too, and the CONTROL's
greedy choices are held to the limits in the served tokens' place
(``runners/serve_mellum2.py``): ``fp8`` the reference with every matmul
operand in fp8, ``no_window`` every layer attending its whole context,
``no_yarn`` the full layers on the plain rotary table, ``gates_softmax``
the gates not renormalised over the 8 chosen.
A control has to come out as not correct; its row also carries the
sound run's gaps and logit error (``sound_*``), so one run gives both
readings, and every row the numbers of each position (``positions``).
The limits in the configuration's ``"check"`` were set from these rows
(PERF.md section 2).  ``--pad 8192`` lets the reference run each sampled
request at the next multiple of that length and not at the engine's
longest (many seeds in one process: a shape compiles once).
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
            "no_window": {"control_no_window": True},
            "no_yarn": {"control_no_yarn": True},
            "gates_softmax": {"control_gates": "softmax"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--controls", default="none,fp8,no_window,no_yarn,gates_softmax")
    ap.add_argument("--pad", type=int, default=0)
    args = ap.parse_args(argv)

    from benchmarks import run as bench_run

    rows = []
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = args.controls.split(",")
    for n, seed in enumerate(seeds):
        # each seed runs once; the controls take turns over the seeds
        control = controls[n % len(controls)]
        report = {}
        overrides = dict(CONTROLS[control])
        if args.pad:
            overrides["reference_pad"] = args.pad
        line = bench_run.run_cell(ROOT, args.workload, seed, args.seconds,
                                  False, overrides=overrides,
                                  t_process=time.perf_counter(), report=report)
        row = {"seed": seed, "control": control, "correct": line["correct"],
               "failed": line["failed"], "attempted": line["attempted"],
               **{c["name"]: c["value"] for c in report["checks"]},
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "memory_peak_bytes": line["device"]["memory_peak_bytes"]}
        if "control" in report:
            row["reference_control"] = report["control"]
        print("# controls " + json.dumps(row), flush=True)
        rows.append({**row, "positions": report.get("positions")})
        out = ROOT / "chiprun_out"          # after every run: a call may be cut
        out.mkdir(exist_ok=True)
        (out / f"controls-{args.workload}-{seeds[0]}.json").write_text(
            json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
