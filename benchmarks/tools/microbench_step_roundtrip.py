#!/usr/bin/env python3
"""Microbenchmark of what lies between two engine steps on the host's
side of the chip, at a serving cell's shapes (measured before the
engine's order of work was changed, PR 29):

    python3 benchmarks/tools/microbench_step_roundtrip.py \
        --workload mistral7b-serve-l16.chat [--root <another checkout>]

Four costs, each a median of ``--iters`` (7), in ms:

  (a) ``fetch_logits``: ``np.asarray`` of a READY fp32 ``[gather_cap,
      vocab]`` array (what the engine copied back a step before PR 29);
  (b) ``fetch_tokens``: the same of a ready int32 ``[gather_cap]``;
  (c) ``uploads``: ``rows`` ``[rows_cap, 5]``, ``tables`` ``[slots,
      pages_per_seq]`` and ``gather`` ``[gather_cap]`` from numpy to the
      device, until the host has them back (``uploads_call``) and until
      all three are ready on the device (``uploads_ready``);
  (d) ``dispatch``: the call of the engine's own step program
      (``analysis_entry()``: every weight, the 2 x layers pools, the
      three arrays of (c) already on the device) with nothing queued on
      the device, until the call returns; ``step_ready`` is the same
      until its results are ready (a step of padding rows alone).

The engine is built as the cell's runner builds it (weights from
``--seed``).  Prints a table and writes
``chiprun_out/microbench-step-roundtrip[-<tag>].json``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[2]


def _ms(fn, iters: int, before=None):
    """Median wall of ``fn(i)`` in ms; ``before(i)`` runs untimed."""
    out = []
    for i in range(iters):
        arg = before(i) if before else i
        t = time.perf_counter()
        fn(arg)
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out)


def measure(eng, iters: int = 7):
    """The four costs for ``eng`` (a ``ContinuousBatchingEngine`` whose
    step is compiled or will compile here): ``{name: ms}``.  The
    engine's pools are donated through the timed steps: it serves no
    request afterwards."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    vocab = eng.cfg.vocab_size
    out = {"gather_cap": eng.gather_cap, "rows_cap": eng.rows_cap,
           "vocab": vocab, "iters": iters}

    # (a), (b): a fresh array a call, or JAX hands back the host copy
    # it kept from the call before
    def ready(shape, dtype):
        base = jnp.zeros(shape, dtype)
        return lambda i: jax.block_until_ready(base + jnp.asarray(i, dtype))

    np.asarray(ready((eng.gather_cap, vocab), jnp.float32)(0))
    out["fetch_logits_ms"] = _ms(np.asarray, iters,
                                 ready((eng.gather_cap, vocab), jnp.float32))
    out["fetch_tokens_ms"] = _ms(np.asarray, iters,
                                 ready((eng.gather_cap,), jnp.int32))

    # (c)
    fn, args, kwargs, _ = eng.analysis_entry()
    rows = np.asarray(args[3])
    tables = np.asarray(args[4])
    gather = np.zeros(eng.gather_cap, np.int32)

    def upload(i):
        return (jnp.asarray(rows + i), jnp.asarray(tables + i),
                jnp.asarray(gather + i))

    def upload_ready(i):
        jax.block_until_ready(upload(i))

    upload_ready(0)
    out["uploads_call_ms"] = _ms(upload, iters)
    out["uploads_ready_ms"] = _ms(upload_ready, iters)

    # (d): pools are donated, so each call's pools are the last one's
    state = {"args": list(args), "res": None}

    def dispatch(_):
        res = fn(*state["args"], **kwargs)
        state["args"][1], state["args"][2], state["res"] = res

    def settle(i):
        if state["res"] is not None:
            jax.block_until_ready(state["res"])
        jax.block_until_ready(state["args"][1])
        return i

    dispatch(0)                     # compiles where nothing has yet
    out["dispatch_ms"] = _ms(dispatch, iters, settle)

    def step_ready(i):
        dispatch(i)
        settle(i)

    settle(0)
    out["step_ready_ms"] = _ms(step_ready, iters)
    return out


def table(res) -> str:
    rows = [("fetch_logits", f"np.asarray of a ready fp32 [{res['gather_cap']}, "
             f"{res['vocab']}]", res["fetch_logits_ms"]),
            ("fetch_tokens", f"np.asarray of a ready int32 [{res['gather_cap']}]",
             res["fetch_tokens_ms"]),
            ("uploads_call", "rows, tables, gather: until the host is free",
             res["uploads_call_ms"]),
            ("uploads_ready", "... until ready on the device",
             res["uploads_ready_ms"]),
            ("dispatch", "the step's call, device idle, until it returns",
             res["dispatch_ms"]),
            ("step_ready", "... until its results are ready (padding rows)",
             res["step_ready_ms"])]
    head = "| cost | what | ms (median of %d) |\n| --- | --- | --- |\n" \
        % res["iters"]
    return head + "\n".join(f"| `{n}` | {w} | {ms:.4f} |" for n, w, ms in rows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--root", default=str(HERE),
                    help="the checkout whose engine is timed")
    ap.add_argument("--seed", type=int, default=2900000011)
    ap.add_argument("--iters", type=int, default=7)
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, args.root)

    from benchmarks import run as bench_run

    ctx, runner = bench_run.make_context(pathlib.Path(args.root),
                                         args.workload, args.seed, 1.0, False)
    _, eng = runner.set_up(ctx)
    res = measure(eng, args.iters)
    dev = ctx.devices[0]
    res.update(workload=args.workload, root=args.root,
               device={"platform": dev.platform, "kind": dev.device_kind})
    print(table(res), flush=True)
    print("# microbench " + json.dumps(res), flush=True)
    out = HERE / "chiprun_out"
    out.mkdir(exist_ok=True)
    tag = f"-{args.tag}" if args.tag else ""
    (out / f"microbench-step-roundtrip{tag}.json").write_text(
        json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
