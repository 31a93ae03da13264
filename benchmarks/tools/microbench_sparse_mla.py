#!/usr/bin/env python3
"""Microbenchmark of the two device-heavy parts a DeepSeek-V3.2 layer
adds to the unified step, at ONE layer's shapes of the cell, on the chip
(decided before any end-to-end run, as PR 25 did for the K/V write):

  (a) index scores of the step's rows over their slots' paged index keys
      (``lightning_index_scores``), then the selection: ``select_top_k``
      (counting passes) against ``lax.top_k``;
  (b) absorbed MLA over the selected latent rows, two spellings of the
      same mathematics: ``sparse_mla_attention`` over the whole context
      under the mask, or a gather of the selected rows and dense
      attention over them (plain XLA);
  (c) the held experts' part (``generation._moe_ffn``) at the step's rows.

    python3 benchmarks/tools/microbench_sparse_mla.py --workload <cell> --ctx 8192,24576

Rows: the cell's static step (slots + prefill budget): one decode row a
slot at ``ctx`` and a full prefill chunk ending at ``ctx`` in slot 0; and
the same step with the chunk's rows padded out (a decode-only step).
Times are medians of ``--iters`` calls that end in ``block_until_ready``.
Writes ``chiprun_out/microbench-sparse-mla.json``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def timed(fn, *args, iters: int):
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ctx", default="8192,24576")
    ap.add_argument("--pages-per-step", default="4,8,16")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--skip-gather", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import manifest
    from paddle_tpu.models import generation
    from paddle_tpu.ops.pallas import sparse_mla

    cell = manifest.load_cell(ROOT, args.workload)
    runner = manifest.load_runner(ROOT, cell.config["runner"])
    cfg, eng = runner.model_config(cell.config), cell.config["engine"]
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs the chip; JAX found {dev.platform}")
    P, page, slots = eng["num_pages"], eng["page_size"], eng["max_slots"]
    budget, maxp = eng["prefill_token_budget"], -(-eng["max_seq_len"] // page)
    T, H, Hi = slots + budget, cfg.num_attention_heads, cfg.index_n_heads
    dl, di, dc, k = (cfg.latent_row, cfg.index_head_dim, cfg.kv_lora_rank,
                     cfg.index_topk)
    bf = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    nrm = lambda i, shape, dt=bf: jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32).astype(dt)
    lat_pool, idx_pool = nrm(1, (P, page, dl)), nrm(2, (P, page, di))
    qi, wi = nrm(3, (T, Hi, di)), nrm(4, (T, Hi), jnp.float32)
    qf = nrm(5, (T, H, dl)) * 0.05
    rng = np.random.default_rng(0)
    tables = rng.permutation(P - 1)[:slots * maxp].reshape(slots, maxp) \
        if slots * maxp <= P - 1 else rng.integers(0, P - 1, (slots, maxp))
    tables = jnp.asarray(tables.astype(np.int32))
    rows_out = []

    def say(row):
        rows_out.append(row)
        print("# microbench " + json.dumps(row), flush=True)

    for ctx in (int(c) for c in args.ctx.split(",")):
        for what in ("chunk", "decode_only"):
            lens = np.zeros(T, np.int32)
            slot = np.full(T, -1, np.int32)
            lens[:slots], slot[:slots] = ctx, np.arange(slots)
            if what == "chunk":
                lens[slots:] = ctx - budget + 1 + np.arange(budget)
                slot[slots:] = 0
            jl, js = jnp.asarray(lens), jnp.asarray(slot)
            for pp in (int(p) for p in args.pages_per_step.split(",")):
                f_idx = jax.jit(lambda q, w, pool, l, s, t, pp=pp:
                                sparse_mla.lightning_index_scores_raw(
                                    q, w, pool, l, s, t, pages_per_step=pp))
                scores = f_idx(qi, wi, idx_pool, jl, js, tables)
                f_sel = jax.jit(lambda sc: sparse_mla.select_top_k(sc, k))
                sel = f_sel(scores)
                f_att = jax.jit(lambda q, pool, sc, se, l, s, t, pp=pp:
                                sparse_mla.sparse_mla_attention_raw(
                                    q, pool, sc, se, l, s, t, dv=dc,
                                    pages_per_step=pp))
                say({"ctx": ctx, "rows": what, "pages_per_step": pp,
                     "index_scores_ms": timed(f_idx, qi, wi, idx_pool, jl, js,
                                              tables, iters=args.iters),
                     "select_top_k_ms": timed(f_sel, scores, iters=args.iters),
                     "masked_attention_ms": timed(
                         f_att, qf, lat_pool, scores, sel, jl, js, tables,
                         iters=args.iters)})
            if what != "chunk":
                continue
            f_topk = jax.jit(lambda sc: jax.lax.top_k(sc, k))
            row = {"ctx": ctx, "rows": what,
                   "lax_top_k_ms": timed(f_topk, scores, iters=args.iters)}
            if not args.skip_gather:
                def gathered(q, pool, sc, l, s, t):
                    vals, idx = jax.lax.top_k(sc, k)          # [T, k]
                    pg = jnp.take_along_axis(t[jnp.maximum(s, 0)],
                                             idx // page, axis=1)
                    flat = jnp.maximum(pg, 0) * page + idx % page
                    g = jnp.take(pool.reshape(P * page, dl), flat, axis=0)
                    sc2 = jnp.einsum("thd,tkd->thk", q, g,
                                     preferred_element_type=jnp.float32)
                    sc2 = jnp.where((vals > -jnp.inf)[:, None, :], sc2, -1e30)
                    p = jax.nn.softmax(sc2, axis=-1).astype(q.dtype)
                    return jnp.einsum("thk,tkc->thc", p, g[..., :dc])

                row["gather_attention_ms"] = timed(
                    jax.jit(gathered), qf, lat_pool, scores, jl, js, tables,
                    iters=max(2, args.iters // 2))
                # against the masked kernel on the same rows
                o_m = f_att(qf, lat_pool, scores, sel, jl, js, tables)
                o_g = jax.jit(gathered)(qf, lat_pool, scores, jl, js, tables)
                row["masked_vs_gather_max_abs"] = float(jnp.max(jnp.abs(
                    o_m.astype(jnp.float32) - o_g.astype(jnp.float32))))
            say(row)

    # (c) the held experts' part of one layer at the step's rows
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    e = cfg.experts_held[1] - cfg.experts_held[0]
    pre = "model.layers.1.mlp."
    p = {pre + "router.weight": nrm(10, (h, cfg.n_routed_experts)) * 0.02,
         pre + "router.bias": nrm(11, (cfg.n_routed_experts,)) * 0.02,
         pre + "experts.gate_proj.weight": nrm(12, (e, h, f)) * 0.02,
         pre + "experts.up_proj.weight": nrm(13, (e, h, f)) * 0.02,
         pre + "experts.down_proj.weight": nrm(14, (e, f, h)) * 0.02,
         pre + "shared_expert.gate_proj.weight": nrm(15, (h, f)) * 0.02,
         pre + "shared_expert.up_proj.weight": nrm(16, (h, f)) * 0.02,
         pre + "shared_expert.down_proj.weight": nrm(17, (f, h)) * 0.02}
    x = nrm(18, (T, h))
    f_moe = jax.jit(lambda p, x: generation._moe_ffn(
        generation._Weights(cfg, p), 1, x))
    say({"moe_layer_ms": timed(f_moe, p, x, iters=args.iters), "rows": T})
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "microbench-sparse-mla.json").write_text(json.dumps(rows_out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
