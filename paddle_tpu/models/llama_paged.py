"""The Llama family on the serving path: what ``LlamaConfig.paged_layout()``
tells the engine (``kv_layout``: K rows and V rows, one kind of page or,
where ``layer_types`` mixes window and full attention, two) and the
family's part of the engine's one ragged step (``unified_step_jit``), under
the contract of ``inference/paged_layout.PagedLayout``.  ``Mellum2Config``
inherits both.  The grouped-query attention over paged K/V
(``gqa_paged_attention``) is Nemotron-H's ``*`` layers' too.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from ..inference.paged_layout import (MOE_DEVICE_COUNTS, WINDOW_PAGE_COUNTS,
                                      WINDOW_ROW_COUNTS, PagedLayout,
                                      PageKind, _write_kv_rows,
                                      gathered_logits, ragged_kv_tokens_read,
                                      resolve_row_tokens, sample_greedy)
from ..ops.pallas.decode_attention import (default_pages_per_step,
                                           ragged_paged_decode_raw,
                                           ragged_tile_rows)
from .generation import (_CFGS, _Weights, _apply_rope, _block, _ffn,
                         _moe_device_counts, _rms_norm)


def page_kinds(cfg) -> tuple:
    """The kinds of page of a Llama-shaped config, from its
    ``layer_types``: the ``full_attention`` layers first, then the
    ``sliding_attention`` layers with ``cfg.sliding_window``.  Empty
    where every layer retains its whole context (no ``layer_types``, or
    none of them sliding): ONE kind, as ever."""
    types = tuple(getattr(cfg, "layer_types", None) or ())
    sliding = tuple(i for i, t in enumerate(types)
                    if t == "sliding_attention")
    if not sliding:
        return ()
    full = tuple(i for i, t in enumerate(types) if t != "sliding_attention")
    if not full:
        raise ValueError("every layer slides: the first kind of page "
                         "retains every position (PagedLayout)")
    return (PageKind("full", full),
            PageKind("window", sliding, int(cfg.sliding_window)))


def _counts_experts(cfg) -> bool:
    """Whether the step takes the expert layers' counts on the device
    (``MOE_DEVICE_COUNTS``): a config that states its experts.
    ``kv_layout`` and the step ask the same question."""
    return int(getattr(cfg, "num_experts", 0) or 0) > 0


def kv_layout(cfg) -> PagedLayout:
    """The Llama family's layout: K rows and V rows, ``unified_step_jit``
    and what its ragged kernel's walk reads; two kinds of page where
    ``cfg.layer_types`` mixes window and full layers (``page_kinds``)."""
    kvh, d = cfg.num_key_value_heads, cfg.head_dim
    # rows of a query tile of the ragged kernel: the K/V its walk reads
    # are counted by the kernel's own units of work
    tile_rows = ragged_tile_rows(cfg.num_attention_heads, kvh, d)
    kinds = page_kinds(cfg)
    windows = [k.window for k in kinds if k.window is not None]

    def row_counts(rows, ctx_tokens, page_size, pages_per_seq):
        # what the walk fetches in one layer (whole pages, a slot once
        # for each of its units of work): over kv_ctx_tokens, the
        # re-read factor
        out = {"attn_kv_tokens_read": ragged_kv_tokens_read(
            rows[:, 4], rows[:, 3], tile_rows, page_size, pages_per_seq)}
        if windows:
            # a window layer's least work: a row's arithmetic over
            # min(visibility, W) keys, a slot's bytes over min(context,
            # W) positions (a slot's context is its rows' largest
            # visibility)
            w, vis = windows[0], rows[:, 3]
            ctx = np.zeros(int(rows[:, 4].max(initial=-1)) + 1, np.int64)
            np.maximum.at(ctx, rows[:, 4], vis)
            out.update(zip(WINDOW_ROW_COUNTS,
                           (int(np.minimum(vis, w).sum()),
                            int(np.minimum(ctx, w).sum()))))
        return out

    def pages_per_step(page_size, pages_per_seq, itemsize):
        return default_pages_per_step(page_size, kvh, d, pages_per_seq,
                                      itemsize)

    device_counts = MOE_DEVICE_COUNTS if _counts_experts(cfg) else ()
    return PagedLayout(
        name="kv", rows=((kvh, d), (kvh, d)), step=unified_step_jit,
        row_counts=row_counts, device_counts=device_counts,
        count_names=("kv_ctx_tokens", "attn_kv_tokens_read",
                     *((*WINDOW_ROW_COUNTS, *WINDOW_PAGE_COUNTS)
                       if windows else ()), *device_counts),
        pages_per_step=pages_per_step, tile_rows=tile_rows, kinds=kinds)


def _round_int8(x):
    """Round-half-away-from-zero to int8 range (the reference's
    quant_round_type=1)."""
    y = jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5)
    return jnp.clip(y, -127, 127).astype(jnp.int8)


def gqa_paged_attention(cfg, w, i, x, k_pool, v_pool, phys, off, lens, slot,
                        table, pages_per_step: int, *, norm: str, eps: float,
                        rope=None, kv_scales=None, window=None):
    """Layer ``i``'s grouped-query attention on the packed rows ``x``
    ``[T, hidden]``, with its residual: the norm (weight ``norm``), the
    q/k/v projections, the rotary embedding where ``rope`` gives the
    rows' ``(cos, sin)``, K and V rows written at (``phys``, ``off``),
    then the ragged paged kernel over ``table`` (the last ``window``
    positions where one is given) and the output projection.  Into an
    int8 pool the rows are quantized by ``kv_scales`` (the engine's
    frozen per-(layer, kv head) scales, ``[L, kvh]`` each), the
    dequantization folded into the query and the context.  Returns ``(x
    + attention, k pool, v pool)``."""
    T = x.shape[0]
    h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    at = "self_attn."
    with jax.named_scope("attn_qkv"):
        xin = _rms_norm(x, w.layer(i, norm), eps)
        q = (xin @ w.layer(i, at + "q_proj.weight")).reshape(T, h, d)
        k = (xin @ w.layer(i, at + "k_proj.weight")).reshape(T, kvh, d)
        v = (xin @ w.layer(i, at + "v_proj.weight")).reshape(T, kvh, d)
        if rope is not None:
            q, k = _apply_rope(q, k, *rope)
    with jax.named_scope("kv_scatter"):
        kw_, vw_, qd = k, v, q
        if k_pool.dtype == jnp.int8:
            kw_ = _round_int8(kw_.astype(jnp.float32)
                              * kv_scales["kq"][i][None, :, None])
            vw_ = _round_int8(vw_.astype(jnp.float32)
                              * kv_scales["vq"][i][None, :, None])
            kdq = jnp.repeat(kv_scales["kdq"][i], h // kvh)
            qd = (qd.astype(jnp.float32)
                  * kdq[None, :, None]).astype(q.dtype)
        # scatter ALL rows' K/V first (a chunk row must see its
        # in-chunk predecessors), then one ragged kernel launch
        k_pool = _write_kv_rows(k_pool, phys, off, kw_)
        v_pool = _write_kv_rows(v_pool, phys, off, vw_)
    with jax.named_scope("paged_attn"):
        ctx = ragged_paged_decode_raw(
            qd, k_pool, v_pool, lens, slot, table, scale=d ** -0.5,
            pages_per_step=pages_per_step, window=window)
        if k_pool.dtype == jnp.int8:
            vdq = jnp.repeat(kv_scales["vdq"][i], h // kvh)
            ctx = ctx.astype(jnp.float32) * vdq[None, :, None]
    with jax.named_scope("attn_out"):
        x = x + (ctx.reshape(T, h * d).astype(x.dtype)
                 @ w.layer(i, at + "o_proj.weight"))
    return x, k_pool, v_pool


@partial(jax.jit, static_argnames=("self_cfg_id", "pages_per_step",
                                   "with_head"),
         donate_argnums=(1, 2))
def unified_step_jit(params, k_pages, v_pages, rows, tables,
                     cos_tab, sin_tab, self_cfg_id, pages_per_step,
                     kv_scales=None, with_head=True, gather=None,
                     prev_tokens=None):
    """The Llama family's part of the engine's ONE ragged step, under
    ``PagedLayout.step``'s contract (``inference/paged_layout.py``):
    attention served by the ragged paged kernel (per-row page-table
    indirection + causal visibility), the unified prefill/decode
    formulation of the Ragged Paged Attention paper, so that decode
    latency is bounded by the launch, not by any co-scheduled prompt's
    length.  Its own: where window and full layers mix (``page_kinds``)
    a layer takes its kind's table and page column, a window layer's
    kernel attends the last ``window`` positions, and ``cos_tab`` /
    ``sin_tab`` are dicts by ``layer_types`` entry from which a layer
    takes its own; ``kv_scales`` quantize the rows of an int8 cache; a
    config that states its experts returns ``MOE_DEVICE_COUNTS`` after
    the tokens.  (It resolves its tokens before it slices the other
    columns and leaves a padding row's visibility as packed, 0, where
    the other steps zero it: ROADMAP.md D1.)"""
    cfg, _, _ = _CFGS[self_cfg_id]
    w = _Weights(cfg, params)
    # a layer's kind of page: its table, the column of the page a
    # row writes, its window (one kind: the table, column 1, none)
    kinds = page_kinds(cfg)
    kind_of = {i: k for k, kind in enumerate(kinds) for i in kind.layers}
    with jax.named_scope("embed"):
        tok = rows[:, 0]
        if prev_tokens is not None:
            tok = resolve_row_tokens(tok, prev_tokens)
        phys = rows[:, 1]
        off = rows[:, 2]
        lens = rows[:, 3]
        slot = rows[:, 4]
        phys_of = [phys] + [rows[:, 4 + k] for k in range(1, len(tables))]
        x = w.embed(tok)                          # [T, hidden]
        pos = jnp.maximum(lens - 1, 0)

        def rope_rows(tab):
            return jnp.take(tab, pos, axis=0)[:, None, :].astype(x.dtype)

        # rope tables by kind of layer where the config has them
        cos, sin = jax.tree.map(rope_rows, (cos_tab, sin_tab))
        stats = None
        if _counts_experts(cfg):
            stats = {"valid": slot >= 0,
                     **{c: [] for c in MOE_DEVICE_COUNTS[:4]}}
    new_k, new_v = list(k_pages), list(v_pages)
    for i in range(cfg.num_hidden_layers):
        ki = kind_of.get(i, 0)
        rope = (cos, sin)
        if isinstance(cos, dict):
            rope = (cos[cfg.layer_types[i]], sin[cfg.layer_types[i]])
        x, new_k[i], new_v[i] = gqa_paged_attention(
            cfg, w, i, x, new_k[i], new_v[i], phys_of[ki], off, lens, slot,
            tables[ki], pages_per_step, norm="input_layernorm.weight",
            eps=cfg.rms_norm_eps, rope=rope, kv_scales=kv_scales,
            window=kinds[ki].window if kinds else None)
        with jax.named_scope("mlp"):
            xm = _rms_norm(x, w.layer(i, "post_attention_layernorm"
                                         ".weight"), cfg.rms_norm_eps)
            # the shared FFN entry routes MoE layers through top-k
            # expert gather-then-dequant (the int8 _Weights expert
            # view), dense layers through SwiGLU
            x = x + _ffn(w, i, xm, stats)
    if not with_head:
        # draft cache-mirror launches only need the K/V scatter side
        # effect: no head matmul, no fp32 logits
        return tuple(new_k), tuple(new_v), None
    logits = gathered_logits(
        x, gather, lambda y: _rms_norm(y, w["model.norm.weight"],
                                       cfg.rms_norm_eps), w.head)
    with jax.named_scope("sample"):
        out = (logits, sample_greedy(logits))
        if stats is not None:
            out = (*out, _moe_device_counts(stats, int(cfg.num_experts)))
    return tuple(new_k), tuple(new_v), out


@partial(jax.jit, static_argnames=("self_cfg_id", "bucket"))
def calibration_prefill_jit(params, ids, cos_tab, sin_tab, self_cfg_id,
                            bucket):
    """Dense causal forward of ONE prompt padded to ``bucket``, for
    the int8 cache's scale calibration alone (the engine's
    ``_calibrate_int8_unified``, through
    ``LlamaConfig.calibration_prefill``): returns the per-layer K and V
    ``[L, bucket, kvh, d]`` as the model computes them, unquantized.
    Nothing is written to the pools."""
    cfg, _, _ = _CFGS[self_cfg_id]
    w = _Weights(cfg, params)
    x = w.embed(ids[None])
    pos = jnp.arange(bucket)
    cos = jnp.take(cos_tab, pos, axis=0)[None, :, None, :].astype(x.dtype)
    sin = jnp.take(sin_tab, pos, axis=0)[None, :, None, :].astype(x.dtype)
    # causal, so the padding behind the prompt changes no real row
    causal = jnp.where(jnp.tril(jnp.ones((bucket, bucket), bool)),
                       0.0, -jnp.inf)
    ks, vs = [], []
    for i in range(cfg.num_hidden_layers):
        x, k, v = _block(w, i, x, cos, sin, causal)
        ks.append(k[0])
        vs.append(v[0])
    return jnp.stack(ks), jnp.stack(vs)
