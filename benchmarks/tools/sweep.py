#!/usr/bin/env python3
"""Find a serving cell's knee, once, by a sweep on the chip: one process,
one set-up, a window at each rate.  The knee is the highest of the rates
tried at which every request due in the window finishes inside the drain
limit and the backlog is no longer at the window's end than at its
middle; the cell's rate is then fixed at about four fifths of it, as a
number in ``benchmarks/traffic/<mix>.json``.

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 3,4,5,6,7,8 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from benchmarks import run as bench_run
    from benchmarks.harness import stats, traffic as gen

    ctx, runner = bench_run.make_context(ROOT, args.workload, args.seed,
                                         args.seconds, False)
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    params, eng = runner.set_up(ctx)
    first = gen.serve_requests(mix, args.seed, args.seconds, cfg["vocab_size"])
    runner.warm_up(ctx, eng, first, cfg["vocab_size"])
    rows = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = {**mix, "arrivals": {**mix["arrivals"], "rate_per_s": rate}}
        reqs = gen.serve_requests(m, args.seed, args.seconds, cfg["vocab_size"])
        # the same seed: the sweep's windows share their system prompts
        drv, t0, _, compiled = runner.measure(ctx, eng, m, reqs["requests"])
        sample = [{**r, "due": t0 + r["due"], "sent": t0 + r["sent"]}
                  for r in drv.recs.values()]
        s = stats.serving_summary(sample, t0, args.seconds, float(m["drain_s"]))
        steps = ctx.spans.durations("engine.step", t0, t0 + args.seconds)
        row = {"rate_per_s": rate, "requests": s["requests"],
               "finished_share": 1 - s["failed"] / s["requests"],
               "backlog_mid": drv.open_at(t0 + args.seconds / 2),
               "backlog_end": drv.open_at(t0 + args.seconds),
               "ttft_p50_ms": s["ttft_p50_ms"], "ttft_p95_ms": s["ttft_p95_ms"],
               "itl_p50_ms": s.get("itl_p50_ms"), "itl_p95_ms": s.get("itl_p95_ms"),
               "serve_tokens_per_s": s["serve_tokens_per_s"],
               "engine_step_p50_ms": stats.median(steps) * 1e3 if steps else None,
               "compiled_in_window": compiled}
        rows.append(row)
        print("# sweep " + json.dumps(row), flush=True)
        eng.finished.clear()
        eng.prefill_stats.clear()
        time.sleep(1.0)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"sweep-{args.workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
