#!/usr/bin/env python3
"""Microbenchmark of the ragged paged attention kernel at ONE layer's
shapes of a Llama-family serving cell, on the chip (decided before any
end-to-end run, as PRs 25 and 26 did for their kernels):

    python3 benchmarks/tools/microbench_ragged_attention.py \
        --workload mistral7b-serve-l16.chat [--root <another checkout>]

Three steps of the cell's static row count (slots + prefill budget):

  (a) ``decode12``: 12 decode rows from 12 slots at context 1,200;
  (b) ``decode12+chunk256``: those and a 256-row prefill chunk that ends
      at context 1,500 in a 13th slot;
  (c) ``decode32``: 32 decode rows at context 2,000;

every other row padding, in the engine's packing order (live rows
first).  Each is timed for every ``--tile-rows`` x ``--pages-per-step``
where the kernel of the checkout takes a tile (``tile_rows``; 4 rows is
the nearest it comes to a walk a row), else for the pages alone: run it
with ``--root`` on the parent's checkout for the parent's kernel.  A
timed call is ``--layers`` launches in one jitted program (a step's
sixteen), so the dispatch of a call is spread over them; times are
medians of ``--iters`` calls a launch, less the loop's own cost, in ms.
Also prints the bytes a launch has to read at least and what the walk
fetches (``ragged_kv_tokens_read``).  Writes
``chiprun_out/microbench-ragged-attention[-<tag>].json``.
"""

from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import statistics
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[2]


def timed(fn, *args, iters: int):
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out)


def steps(slots: int, rows: int, chunk: int):
    """name -> (row_lens, row_slot), the engine's packing order."""
    import numpy as np

    def pack(decode_ctx, n_decode, chunk_end=None):
        lens = np.zeros(rows, np.int32)
        slot = np.full(rows, -1, np.int32)
        lens[:n_decode], slot[:n_decode] = decode_ctx, np.arange(n_decode)
        if chunk_end is not None:
            lens[n_decode:n_decode + chunk] = \
                chunk_end - chunk + 1 + np.arange(chunk)
            slot[n_decode:n_decode + chunk] = n_decode
        return lens, slot

    return {"decode12": pack(1200, 12),
            f"decode12+chunk{chunk}": pack(1200, 12, 1500),
            "decode32": pack(2000, min(32, slots))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--root", default=str(HERE),
                    help="the checkout whose kernel is timed")
    ap.add_argument("--tag", default="")
    ap.add_argument("--tile-rows", default="4,16,32,64")
    ap.add_argument("--pages-per-step", default="2,4,8")
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--iters", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import manifest
    from paddle_tpu.inference import serving
    from paddle_tpu.ops.pallas import decode_attention as da

    cell = manifest.load_cell(pathlib.Path(args.root), args.workload)
    mc, eng = cell.config, cell.config["engine"]
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs the chip; JAX found {dev.platform}")
    P, page, slots = eng["num_pages"], eng["page_size"], eng["max_slots"]
    chunk, maxp = eng["prefill_token_budget"], -(-eng["max_seq_len"] // page)
    T, h, kvh = slots + chunk, mc["num_attention_heads"], \
        mc["num_key_value_heads"]
    d = mc["head_dim"]
    dt = jnp.dtype(eng["cache_dtype"])
    tiled = "tile_rows" in inspect.signature(
        da.ragged_paged_decode_raw).parameters
    key = jax.random.PRNGKey(0)
    nrm = lambda i, shape: jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32).astype(jnp.bfloat16)
    kc, vc = nrm(1, (P, kvh, page, d)).astype(dt), \
        nrm(2, (P, kvh, page, d)).astype(dt)
    q = nrm(3, (T, h, d))
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.permutation(P - 1)[:slots * maxp]
                         .reshape(slots, maxp).astype(np.int32))
    rows_out = []

    def say(row):
        rows_out.append(row)
        print("# microbench " + json.dumps(row), flush=True)

    def loop(attend, q):
        """``--layers`` launches in one program: another q a launch (or
        XLA keeps one of them), the outputs summed."""
        def body(i, acc):
            return acc + attend(q * (1 + i).astype(q.dtype))
        return jax.lax.fori_loop(0, args.layers, body,
                                 jnp.zeros(q.shape, jnp.float32))

    # what the loop costs a launch with no kernel in it
    around = timed(jax.jit(lambda q: loop(lambda qi: qi, q)), q,
                   iters=args.iters) / args.layers
    say({"loop_alone_ms_a_launch": around})
    tiles = [int(t) for t in args.tile_rows.split(",")] if tiled else [None]
    for name, (lens, slot) in steps(slots, T, chunk).items():
        jl, js = jnp.asarray(lens), jnp.asarray(slot)
        live = slot >= 0
        ctx_once = sum(int(lens[slot == s].max()) for s in set(slot[live]))
        base = {"step": name, "rows": int(live.sum()),
                "kv_ctx_tokens": ctx_once,
                "least_ms": ctx_once * kvh * d * 2 * dt.itemsize / 819e9 * 1e3}
        for tq in tiles:
            for pp in (int(p) for p in args.pages_per_step.split(",")):
                kw = {"pages_per_step": pp}
                row = dict(base, pages_per_step=pp)
                if tiled:
                    kw["tile_rows"] = tq
                    row.update(tile_rows=tq, kv_tokens_read=int(
                        serving.ragged_kv_tokens_read(slot, lens, tq, page,
                                                      maxp)))

                def layers(q, kc, vc, l, s, t, kw=kw):
                    return loop(lambda qi: da.ragged_paged_decode_raw(
                        qi, kc, vc, l, s, t, **kw), q)

                ms = timed(jax.jit(layers), q, kc, vc, jl, js, tables,
                           iters=args.iters) / args.layers - around
                say(dict(row, ms_a_launch=ms,
                         roofline_pct=100 * base["least_ms"] / ms))
    out = pathlib.Path(HERE) / "chiprun_out"
    out.mkdir(exist_ok=True)
    tag = f"-{args.tag}" if args.tag else ""
    (out / f"microbench-ragged-attention{tag}.json").write_text(
        json.dumps({"device": {"platform": dev.platform,
                               "kind": dev.device_kind},
                    "workload": args.workload, "root": args.root,
                    "layers": args.layers, "rows": rows_out}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
