"""Autoregressive generation with a KV cache for the Llama flagship.

Reference analogs: the fused decode path
(python/paddle/incubate/nn/functional/masked_multihead_attention.py and
fused_multi_transformer.py — one-token-per-step attention against a
preallocated cache) plus the generation loops PaddleNLP layers over it.

TPU-native design: the whole decode is TWO compiled programs —
- prefill: one forward over the prompt that also returns the per-layer
  K/V tensors (written into a [L, B, kvh, max_len, d] cache — head-major,
  the Pallas flash-decoding kernel's layout), and
- a ``lax.scan`` over decode steps: each step embeds one token, runs every
  layer against the cache through the Pallas flash-decoding kernel
  (ops/pallas/decode_attention.py — online softmax, HBM traffic bounded
  by the CURRENT position rather than max_len), appends its K/V via
  ``dynamic_update_slice``, samples (greedy / temperature / top-k /
  top-p) and carries the PRNG key chain.
No per-token python dispatch, no cache reallocation, static shapes
throughout — the XLA-friendly formulation of the reference's CUDA decode
kernels.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["generate"]


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rotate_half(x):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-b, a], axis=-1)


def _apply_rope(q, k, cos, sin):
    """q: [..., h, d]; cos/sin broadcastable [..., 1, d] (neox style, the
    layout _rope_tables builds)."""
    return (q * cos + _rotate_half(q) * sin,
            k * cos + _rotate_half(k) * sin)


class _Weights:
    """Name-indexed view over functional_state (paddle Linear weights are
    [in, out]: y = x @ W).

    Weight-only int8 support: a weight named ``N`` may ride with a
    sibling ``N._scale`` (per-output-channel fp scales from
    quantize_params_int8).  Accessors dequantize ``int8 -> compute
    dtype`` right at the consumer, so under jit XLA fuses the convert +
    scale into the dot's operand stream and int8 is what leaves HBM —
    the reference's weight_only_linear capability (python/paddle/nn/
    quant/quantized_linear.py) realized as an XLA fusion instead of a
    custom kernel.  Embedding lookups gather int8 ROWS first and
    dequantize after (never materialising the full fp matrix)."""

    def __init__(self, cfg, params):
        self.cfg = cfg
        self.p = params
        self._dt = None
        for k, v in params.items():
            if k.endswith("._scale"):
                continue
            if jnp.issubdtype(v.dtype, jnp.floating):
                self._dt = v.dtype
                break
        if self._dt is None:
            self._dt = jnp.bfloat16

    def _deq(self, name):
        w = self.p[name]
        sc = self.p.get(name + "._scale")
        if sc is None:
            return w
        # per-out-channel (last axis) scales; convert+multiply fuse into
        # the consuming dot — int8 streams from HBM, fp stays in VMEM
        return w.astype(self._dt) * sc.astype(self._dt)[None, :]

    def layer(self, i, name):
        return self._deq(f"model.layers.{i}.{name}")

    def is_moe_layer(self, i) -> bool:
        """A layer is MoE iff the checkpoint carries its stacked expert
        weights (sparse checkpoints may mix dense and MoE layers)."""
        return f"model.layers.{i}.mlp.experts.up_proj.weight" in self.p

    def expert(self, i, proj, idx):
        """Gather-then-dequant expert slices from the stacked
        ``[E, in, out]`` weight: int8 expert ROWS are gathered by
        ``idx`` (expert ids) FIRST and dequantized after with their
        per-(expert, out-channel) scales, so the full fp bank is never
        materialized — ``_moe_ffn`` passes one expert id at a time,
        bounding live memory to a single dequantized slice."""
        name = f"model.layers.{i}.mlp.experts.{proj}.weight"
        w = self.p[name]
        rows = jnp.take(w, idx, axis=0)              # [T, in, out]
        sc = self.p.get(name + "._scale")
        if sc is None:
            return rows
        return rows.astype(self._dt) * jnp.take(
            sc.astype(self._dt), idx, axis=0)[:, None, :]

    def embed(self, ids):
        """Token embedding lookup: gather rows, then dequantize the
        gathered rows only (per-row scales for the [vocab, hidden]
        matrix)."""
        w = self.p["model.embed_tokens.weight"]
        rows = jnp.take(w, ids, axis=0)
        sc = self.p.get("model.embed_tokens.weight._scale")
        if sc is None:
            return rows
        return rows.astype(self._dt) * jnp.take(
            sc.astype(self._dt), ids, axis=0)[..., None]

    def head(self, x):
        if "lm_head.weight" in self.p:
            w = self.p["lm_head.weight"]
            sc = self.p.get("lm_head.weight._scale")
            if sc is None:
                return x @ w
            return (x @ w.astype(self._dt)) * sc.astype(self._dt)[None, :]
        # tied embeddings: reuse the embedding matrix transposed (the
        # per-row embed scales become per-out-channel head scales)
        w = self.p["model.embed_tokens.weight"]
        sc = self.p.get("model.embed_tokens.weight._scale")
        if sc is None:
            return x @ w.T
        return (x @ w.T.astype(self._dt)) * sc.astype(self._dt)[None, :]

    def __getitem__(self, k):
        return self._deq(k)


def quantize_params_int8(params, keep=("norm", "layernorm", "router")):
    """Weight-only int8 quantization of a functional_state dict:
    2D floating weights become int8 with a per-output-channel
    (symmetric absmax) fp32 ``<name>._scale`` sibling; 1D weights
    (norm gains) and anything matching ``keep`` stay in fp (the MoE
    router is tiny and its logits gate everything — it stays fp like
    the norms).  The embedding matrix is quantized per ROW (its rows
    are gathered, its transpose is the tied head's [hidden, vocab]).
    Stacked ``[E, in, out]`` expert banks quantize per (expert,
    out-channel) — the ``_Weights.expert`` gather-then-dequant view
    reads exactly this layout."""
    out = {}
    for name, w in params.items():
        is_embed = name.endswith("embed_tokens.weight")
        is_expert = ".mlp.experts." in name and w.ndim == 3
        if ((w.ndim != 2 and not is_expert)
                or not jnp.issubdtype(w.dtype, jnp.floating)
                or any(s in name for s in keep)):
            out[name] = w
            continue
        if is_expert:
            absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=1)
            scale = jnp.maximum(absmax, 1e-8) / 127.0    # [E, out]
            den = scale[:, None, :]
        else:
            axis = 1 if is_embed else 0      # reduce over the in-dim
            absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis)
            scale = jnp.maximum(absmax, 1e-8) / 127.0
            den = scale[:, None] if is_embed else scale[None, :]
        q = jnp.round(w.astype(jnp.float32) / den)
        out[name] = jnp.clip(q, -127, 127).astype(jnp.int8)
        out[name + "._scale"] = scale
    return out


def self_draft_params(cfg, params, num_layers: int):
    """Layer-truncated self-speculative draft: reuse the target's first
    ``num_layers`` decoder layers plus its embeddings / final norm /
    head as the proposer model (no separate distilled checkpoint
    needed — the early layers of the same network are a classic cheap
    drafter).  Returns ``(draft_cfg, draft_params)`` ready for
    ``ContinuousBatchingEngine(draft_params=..., draft_cfg=...)``.

    Weight-only int8 dicts pass through unchanged: the ``._scale``
    siblings of kept layers ride along, so an int8 target drafts with
    int8 weights too (compose with ``quantize_params_int8`` in either
    order)."""
    import dataclasses

    n = int(num_layers)
    if not 0 < n <= cfg.num_hidden_layers:
        raise ValueError(
            f"draft depth {n} outside (0, {cfg.num_hidden_layers}]")
    dcfg = dataclasses.replace(cfg, num_hidden_layers=n)
    dparams = {}
    for k, v in params.items():
        if k.startswith("model.layers."):
            if int(k.split(".")[2]) >= n:
                continue
        dparams[k] = v
    return dcfg, dparams


#: row-block quantum of the serving grouped-matmul launches (segment
#: alignment; serving batches are small, so a fine block keeps padding
#: slack low while staying sublane-aligned)
_MOE_FFN_BLOCK_ROWS = 8


def _route_softmax_topk(cfg, logits, bias=None):
    """Top-k of the softmax over all experts, weights normalised over
    the k chosen (the reference ``fused_moe`` semantics)."""
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = lax.top_k(probs, int(cfg.moe_top_k))
    return top_ids, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def _route_sigmoid_groups(cfg, logits, bias=None):
    """Group-limited top-k over sigmoid scores (DeepSeek-V3's
    ``noaux_tc``): selection runs on ``score + bias`` (the bias steers
    load and never reaches a gate): the experts form ``n_group``
    groups of consecutive ids, a group scores the sum of its 2 best,
    the ``topk_group`` best groups stay, and the k best experts among
    them are chosen.  Gates are the UNBIASED scores, normalised over
    all k chosen and scaled by ``routed_scaling_factor``."""
    k, e = int(cfg.moe_top_k), logits.shape[-1]
    scores = jax.nn.sigmoid(logits)
    pick = scores if bias is None else scores + bias.astype(jnp.float32)
    groups = pick.reshape(-1, cfg.n_group, e // cfg.n_group)
    gscore = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)
    _, gkeep = lax.top_k(gscore, int(cfg.topk_group))
    gmask = jnp.zeros_like(gscore, bool).at[
        jnp.arange(gscore.shape[0])[:, None], gkeep].set(True)
    pick = jnp.where(jnp.repeat(gmask, e // cfg.n_group, axis=-1), pick,
                     -jnp.inf)
    _, top_ids = lax.top_k(pick, k)
    gates = jnp.take_along_axis(scores, top_ids, axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return top_ids, gates * cfg.routed_scaling_factor


_MOE_ROUTES = {"softmax": _route_softmax_topk,
               "sigmoid_groups": _route_sigmoid_groups}


#: the most cells of a ``[rows, T]`` matrix the dispatch's product forms
#: may lay out (32 MB in bf16): they are quadratic in the tokens, so a
#: step past this, a long prompt prefilled whole, moves its copies by
#: row gathers instead.  On a v5e the product is the faster up to a
#: step's 544 tokens x 8,512 rows and level with the gather there
_MOE_PRODUCT_CELLS = 1 << 24


@jax.named_scope("moe_experts")
def _moe_experts(w: _Weights, i, x2, top_ids, top_p, lo, hi, e_all, stats):
    """The held experts' part of ``_moe_ffn``: the sorted ragged
    dispatch of the token copies, the experts' grouped matmuls, the
    weighted combine.  ``x2`` [T, width]; ``top_ids`` / ``top_p`` [T, k]
    over the router's ``e_all`` experts (a token's k are distinct, as
    ``top_k``'s are), of which [lo, hi) are in the bank.  The expert's
    FORM is the config's: gated SwiGLU (gate, up, down: three launches)
    unless it states ``mlp_hidden_act = "relu2"`` (up, squared relu,
    down: two; the bank has no ``gate_proj``).

    The copies travel by READS alone.  Each row of the experts' buffer
    knows the sorted copy it holds, so the buffer is the product of a
    one-hot ``[rows, T]`` with ``x2`` on the MXU (exact) or, past
    ``_MOE_PRODUCT_CELLS``, one gather of ``x2``'s rows; it is allocated
    with the grouped matmul's park block as its last and handed through
    the launches as it stands.  A token's output is the gate-weighted
    sum of its copies' rows in float32: where the bank holds every
    expert each token has k of them and they are gathered token-major;
    where it holds a share, few of a token's copies are live and the sum
    is the product of the gates laid out ``[T, rows]`` with the live
    rows (token-major again past ``_MOE_PRODUCT_CELLS``)."""
    from ..ops.pallas.grouped_matmul import (align_rows,
                                             grouped_matmul_raw,
                                             segment_starts)

    cfg = w.cfg
    pre = f"model.layers.{i}.mlp."
    e, k = hi - lo, top_ids.shape[-1]
    t, dt = x2.shape[0], x2.dtype
    gated = _gated(cfg)
    # ---- sorted ragged dispatch: copies sorted by expert tile the
    # block-aligned segment windows the kernel contract wants.  A copy
    # of an absent expert sorts behind every segment (id ``e``), has no
    # row and weighs 0
    bm = int(getattr(cfg, "moe_block_rows", _MOE_FFN_BLOCK_ROWS))
    tk = t * k
    held = (top_ids >= lo) & (top_ids < hi)
    if stats is not None:
        held = held & stats["valid"][:, None]
    absent = e < e_all or stats is not None   # may a copy have no segment?
    ids = (jnp.where(held, top_ids - lo, e) if absent
           else top_ids).astype(jnp.int32)                      # [T, k]
    gates = jnp.where(held, top_p, 0.0).astype(jnp.float32)
    chose = ids[:, :, None] == jnp.arange(e + 1, dtype=jnp.int32)
    chosen = jnp.sum(chose, axis=1, dtype=jnp.int32)  # [T, e + 1] copies
    counts = jnp.sum(chosen, axis=0)[:e]
    seg_st = segment_starts(counts, bm)
    run_st = jnp.cumsum(counts) - counts              # unaligned starts
    if stats is not None:
        stats["moe_rows_routed"].append(jnp.sum(stats["valid"]) * k)
        stats["moe_rows_held"].append(jnp.sum(held))
        stats["moe_expert_rows_max"].append(jnp.max(counts))
        if "moe_experts_hit" in stats:      # experts with a row at least
            stats["moe_experts_hit"].append(jnp.sum(counts > 0))
    # the copies' tokens and gates in expert order (stable: absent last)
    _, tok_sorted, gate_sorted = lax.sort(
        (ids.reshape(-1), jnp.arange(tk, dtype=jnp.int32) // k,
         gates.reshape(-1)), num_keys=1, is_stable=True)

    def bank(proj):
        name = pre + f"experts.{proj}.weight"
        wq = w.p[name]
        sc = w.p.get(name + "._scale")
        if sc is None:
            return wq.astype(dt), None
        return wq, sc                                 # int8 + [E, out]

    wids = jnp.arange(e, dtype=jnp.int32)
    tokens = jnp.arange(t, dtype=jnp.int32)
    # a product that moves rows must not round them: float32 rows take
    # the MXU's exact passes, 16-bit rows are exact in one
    exact = lax.Precision.HIGHEST if dt == jnp.float32 else None

    def gmm(xin, proj):
        wq, sc = bank(proj)
        # the sorted dispatch's segments tile the rows densely: the row
        # blocks alone are the grid, however many experts the bank has,
        # and the buffer's last block is the launch's park block
        return grouped_matmul_raw(xin, wq, seg_st, counts, wids,
                                  block_rows=bm, w_scale=sc, dense=True)

    def experts_of(n):
        """The layer's routed part from a buffer sized for ``n`` copies
        (they must be all that have a segment)."""
        # static worst case, and the park block
        nblk = int(align_rows(n, bm)) // bm + e + 1
        # ---- in: a buffer row reads the token of the sorted copy it
        # holds; a block lies in one segment, so blocks find theirs
        blk = jnp.arange(nblk, dtype=jnp.int32)
        ends = (seg_st + align_rows(counts, bm)) // bm
        seg = jnp.minimum(jnp.searchsorted(ends, blk, side="right",
                                           method="compare_all"), e - 1)
        first = run_st[seg] + blk * bm - seg_st[seg]   # sorted copy of row 0
        stop = run_st[seg] + counts[seg]
        src = first[:, None] + jnp.arange(bm, dtype=jnp.int32)[None, :]
        live = (src < stop[:, None]).reshape(-1)      # slack, unused, park
        src = jnp.minimum(src, tk - 1).reshape(-1)
        tok_row = tok_sorted[src]
        small = t * nblk * bm <= _MOE_PRODUCT_CELLS
        if small:
            into = ((tok_row[:, None] == tokens) & live[:, None]).astype(dt)
            xr = jnp.dot(into, x2, precision=exact,
                         preferred_element_type=jnp.float32).astype(dt)
        else:
            xr = x2[tok_row]       # a row that is not live: some token's
        if gated:
            gate = gmm(xr, "gate_proj")
            up = gmm(xr, "up_proj")
            eo = gmm(jax.nn.silu(gate) * up, "down_proj")     # [rows, h]
        else:
            eo = gmm(_relu2(gmm(xr, "up_proj")), "down_proj")
        # ---- out: rows of ``eo`` that no step wrote (slack aside: past
        # the last used block, the park block) hold anything, NaN
        # included, so what is not live is selected away, never weighed
        if e == e_all or not small:
            # copies of earlier tokens, by expert: a copy's rank in its
            # segment, as the stable sort ranks it
            before = (jnp.cumsum(chosen, axis=0) - chosen)[:, :e]
            dest = jnp.sum(jnp.where(chose[:, :, :e],
                                     (seg_st + before)[:, None, :], 0),
                           axis=-1)                            # [T, k]
            ys = eo[jnp.where(held, dest, 0)].astype(jnp.float32)
            return jnp.sum(jnp.where(held[:, :, None],
                                     ys * gates[:, :, None], 0),
                           axis=1).astype(dt)
        gate_row = jnp.where(live, gate_sorted[src], 0.0)
        back = jnp.where(tok_row[None, :] == tokens[:, None],
                         gate_row[None, :], 0.0).astype(dt)    # [T, rows]
        return jnp.dot(back, jnp.where(live[:, None], eo, 0),
                       precision=exact,
                       preferred_element_type=jnp.float32).astype(dt)

    # a chip that holds e of e_all experts sees about tk * e / e_all of
    # the copies: the dispatch is sized to twice that, and to all tk
    # (dropless: nothing is ever left out) only in a step where more
    # copies than that chose a held expert
    few = int(align_rows(2 * tk * e // e_all, bm))
    if not absent or few >= tk:
        return experts_of(tk)
    return lax.cond(jnp.sum(counts) <= few, lambda: experts_of(few),
                    lambda: experts_of(tk))


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _gated(cfg) -> bool:
    """Whether the config's experts are gated SwiGLU (gate, up, down) or,
    where it states ``mlp_hidden_act = "relu2"``, up, squared relu, down."""
    return getattr(cfg, "mlp_hidden_act", None) != "relu2"


@jax.named_scope("shared_expert")
def _shared_expert(w: _Weights, i, x2):
    """The always-on expert, in the routed experts' form and at its own
    width (its leaves' shapes say which)."""
    if not _gated(w.cfg):
        su = x2 @ w.layer(i, "mlp.shared_expert.up_proj.weight")
        return _relu2(su) @ w.layer(i, "mlp.shared_expert.down_proj.weight")
    sg = x2 @ w.layer(i, "mlp.shared_expert.gate_proj.weight")
    su = x2 @ w.layer(i, "mlp.shared_expert.up_proj.weight")
    return (jax.nn.silu(sg) * su) @ w.layer(
        i, "mlp.shared_expert.down_proj.weight")


def _moe_ffn(w: _Weights, i, xm, stats=None):
    """Top-k expert routing for one MoE layer on the ``_Weights`` view
    (round-20 dropless serving): fp32 router logits -> the chosen
    experts and their gates (``cfg.moe_scoring``: ``softmax``, the
    default, or ``sigmoid_groups``) -> token copies argsorted by expert
    into block-aligned ragged segments -> ONE grouped-matmul launch per
    projection (ops/pallas/grouped_matmul) applying each expert's
    ``[in, out]`` slice to its row window, SwiGLU, then each token's
    gate-weighted sum of its copies' rows (``_moe_experts``: the copies
    travel both ways by reads, no scatter).

    This replaces the round-18 masked-dense expert loop (every token
    through every expert, flops scaling E/k-fold): compute is now the
    ragged T*k rows — the same unified-ragged-step shape the training
    dropless path uses — while the expert bank is still read exactly
    once per call.  int8 banks stay int8 all the way into the kernel:
    the raw stacked ``[E, in, out]`` bank plus its per-(expert,
    out-channel) ``._scale`` ride as the kernel's ``w``/``w_scale``,
    which widens one VMEM block at a time and folds the scale into the
    fp32 accumulator — the gather-then-dequant view moved in-kernel, no
    dequantized slice ever materialized in HBM.  ``xm`` is any
    [..., hidden] batch (the unified step's packed [T, h] rows, a
    decode chunk's [slots, 1, h], prefill's [b, s, h]); routing is per
    token row.

    THE expert-share layer: the router keeps its full width, and
    ``cfg.experts_held = (lo, hi)`` says which of its experts the bank
    holds (expert parallelism's share of one chip; absent: all).  A
    copy routed to an absent expert takes part in the gates'
    normalisation and adds nothing: what the other chips would add is
    left out, and nothing stands in for them.  A checkpoint with
    ``mlp.shared_expert.*`` leaves adds that always-on expert once, and
    one with ``mlp.latent_down`` / ``mlp.latent_up`` runs the routed
    experts in that latent (Nemotron-3's LatentMoE); the experts' form
    is the config's (``_moe_experts``);
    ``mlp.router.bias`` is the selection bias of ``sigmoid_groups``.
    ``stats`` (a dict with the rows' ``valid`` mask) takes the layer's
    counts: copies routed, copies of held experts, the fullest expert;
    rows that are not valid count nowhere and reach no expert."""
    cfg = w.cfg
    shape = xm.shape
    x2 = xm.reshape(-1, shape[-1])
    pre = f"model.layers.{i}.mlp."
    router = w.layer(i, "mlp.router.weight")          # [h, E], fp
    # E comes from the CHECKPOINT (MoE-ness is checkpoint-driven, via
    # is_moe_layer) — a cfg.num_experts desync must be loud, not a
    # silently zeroed expert output
    e_all = int(router.shape[-1])
    lo, hi = getattr(cfg, "experts_held", None) or (0, e_all)
    e = hi - lo
    bank_e = int(w.p[pre + "experts.up_proj.weight"].shape[0])
    if bank_e != e or not 0 <= lo < hi <= e_all:
        raise ValueError(
            f"layer {i}: router routes {e_all} experts, experts "
            f"[{lo}, {hi}) are held, but the stacked bank holds {bank_e}")
    k = int(cfg.moe_top_k)
    if not 1 <= k <= e_all:
        raise ValueError(
            f"layer {i}: moe_top_k={k} outside [1, {e_all}] — set "
            f"LlamaConfig.moe_top_k for this sparse checkpoint")
    with jax.named_scope("moe_route"):
        logits = x2.astype(jnp.float32) @ router.astype(jnp.float32)
        top_ids, top_p = _MOE_ROUTES[getattr(cfg, "moe_scoring", "softmax")](
            cfg, logits, w.p.get(pre + "router.bias"))    # [T, k] each

    if pre + "latent_down.weight" in w.p:
        # LatentMoE: the routed experts live in a narrower latent,
        # between two projections that every chip computes alike
        with jax.named_scope("moe_latent_down"):
            lat = x2 @ w.layer(i, "mlp.latent_down.weight")
        y = _moe_experts(w, i, lat, top_ids, top_p, lo, hi, e_all, stats)
        with jax.named_scope("moe_latent_up"):
            y = y @ w.layer(i, "mlp.latent_up.weight")
    else:
        y = _moe_experts(w, i, x2, top_ids, top_p, lo, hi, e_all, stats)
    if pre + "shared_expert.up_proj.weight" in w.p:
        y = y + _shared_expert(w, i, x2)
    return y.reshape(shape)


def _moe_device_counts(stats, experts: int):
    """int32 ``[5]``: ``inference.paged_layout.MOE_DEVICE_COUNTS`` over
    a step's expert layers, from the ``stats`` they filled
    (``_moe_experts``): sums over the layers, the fullest expert's rows
    their maximum, ``experts`` a layer the experts there are."""
    zero = jnp.zeros((), jnp.int32)
    hit = stats["moe_experts_hit"]
    return jnp.stack([
        sum(stats["moe_rows_routed"], zero),
        sum(stats["moe_rows_held"], zero),
        jnp.max(jnp.stack(stats["moe_expert_rows_max"] or [zero])),
        sum(hit, zero), zero + len(hit) * experts]).astype(jnp.int32)


def _ffn(w: _Weights, i, xm, stats=None):
    """Layer ``i``'s FFN on the ``_Weights`` view: dense SwiGLU, or —
    when the checkpoint carries this layer's stacked expert weights —
    top-k expert routing (``_moe_ffn``, which ``stats`` is for).  The ONE implementation the
    prefill/decode ``_block``, the serving decode chunk and the
    unified ragged step all share, so a sparse checkpoint serves
    through every path that serves a dense one."""
    if w.is_moe_layer(i):
        return _moe_ffn(w, i, xm, stats)
    gate = xm @ w.layer(i, "mlp.gate_proj.weight")
    up = xm @ w.layer(i, "mlp.up_proj.weight")
    return (jax.nn.silu(gate) * up) @ w.layer(i, "mlp.down_proj.weight")


def _block(w: _Weights, i, x, cos, sin, mask, k_all=None, v_all=None,
           cache_pos=None):
    """One decoder layer. x [b, s, hdim]; without a cache (prefill) it
    attends x's own K/V causally; with k_all/v_all ([b, kvh, M, d] layer
    cache) and ``cache_pos``, x's K/V are first written at that position,
    then attention runs over the cache through the Pallas flash-decoding
    kernel (HBM traffic bounded by cache_pos+s, not M). Returns
    (y, k_attended, v_attended) — the prompt's K/V ([b, s, kvh, d]) in
    prefill, the updated layer cache in decode."""
    cfg = w.cfg
    b, s, _ = x.shape
    h, kvh, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    xin = _rms_norm(x, w.layer(i, "input_layernorm.weight"), eps)
    q = (xin @ w.layer(i, "self_attn.q_proj.weight")).reshape(b, s, h, d)
    k = (xin @ w.layer(i, "self_attn.k_proj.weight")).reshape(b, s, kvh, d)
    v = (xin @ w.layer(i, "self_attn.v_proj.weight")).reshape(b, s, kvh, d)
    q, k = _apply_rope(q, k, cos, sin)
    g = h // kvh
    if k_all is None:
        # prefill: attend x's own K/V with the causal mask (one big
        # MXU-friendly batched matmul over [S, S])
        k_all, v_all = k, v
        qg = q.reshape(b, s, kvh, g, d).astype(jnp.float32)
        scores = jnp.einsum("bskgd,bSkd->bskgS", qg,
                            k_all.astype(jnp.float32)) * (d ** -0.5)
        if mask is not None:
            scores = scores + mask[None, :, None, None, :]
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bskgS,bSkd->bskgd", probs,
                         v_all.astype(jnp.float32))
        ctx = ctx.reshape(b, s, h * d).astype(x.dtype)
    else:
        # write the new K/V at cache_pos ([b, kvh, M, d] cache layout)
        kt = jnp.moveaxis(k, 1, 2).astype(k_all.dtype)   # [b, kvh, s, d]
        vt = jnp.moveaxis(v, 1, 2).astype(v_all.dtype)
        k_all = lax.dynamic_update_slice(k_all, kt, (0, 0, cache_pos, 0))
        v_all = lax.dynamic_update_slice(v_all, vt, (0, 0, cache_pos, 0))
        if s == 1 and mask is None:
            # single-token decode: Pallas flash-decoding kernel (HBM
            # traffic bounded by cache_pos+1, not M)
            from ..ops.pallas.decode_attention import flash_decode_raw

            lens = jnp.broadcast_to(cache_pos + 1, (b,)).astype(jnp.int32)
            ctx = flash_decode_raw(q.reshape(b, h, d), k_all, v_all,
                                   lens, scale=d ** -0.5)
            ctx = ctx.reshape(b, s, h * d).astype(x.dtype)
        else:
            # chunked prefill against an existing cache (s > 1, or an
            # explicit mask): general grouped attention over the cache
            qg = q.reshape(b, s, kvh, g, d).astype(jnp.float32)
            scores = jnp.einsum("bskgd,bkSd->bskgS", qg,
                                k_all.astype(jnp.float32)) * (d ** -0.5)
            if mask is not None:
                scores = scores + mask[None, :, None, None, :]
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bskgS,bkSd->bskgd", probs,
                             v_all.astype(jnp.float32))
            ctx = ctx.reshape(b, s, h * d).astype(x.dtype)
    x = x + ctx @ w.layer(i, "self_attn.o_proj.weight")
    xm = _rms_norm(x, w.layer(i, "post_attention_layernorm.weight"), eps)
    x = x + _ffn(w, i, xm)
    return x, k_all, v_all


def _decode_step(w: _Weights, cos_tab, sin_tab, token, pos, k_cache, v_cache):
    """One-token step. token [b], pos scalar; caches [L, b, kvh, M, d].
    Each layer goes through the same _block as prefill, writing its K/V at
    ``pos`` before attending. Returns (logits [b, V], k_cache, v_cache)."""
    cfg = w.cfg
    x = w.embed(token[:, None])
    cos = lax.dynamic_slice_in_dim(cos_tab, pos, 1)[None, :, None, :]
    sin = lax.dynamic_slice_in_dim(sin_tab, pos, 1)[None, :, None, :]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    for i in range(cfg.num_hidden_layers):
        x, kl, vl = _block(w, i, x, cos, sin, None, k_cache[i], v_cache[i],
                           pos)
        k_cache = k_cache.at[i].set(kl)
        v_cache = v_cache.at[i].set(vl)
    x = _rms_norm(x, w["model.norm.weight"], cfg.rms_norm_eps)
    return w.head(x[:, 0]), k_cache, v_cache


def _sample(logits, key, do_sample, temperature, top_k, top_p):
    logits = logits.astype(jnp.float32)
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with mass >= top_p
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cfg_id", "max_new_tokens", "do_sample",
                                   "temperature", "top_k", "top_p", "eos_id"))
def _generate_jit(params, ids, key, cfg_id, max_new_tokens,
                  do_sample, temperature, top_k, top_p, eos_id):
    cfg, cos_tab, sin_tab = _CFGS[cfg_id]
    w = _Weights(cfg, params)
    b, S = ids.shape
    M = S + max_new_tokens
    h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    L = cfg.num_hidden_layers

    # ---- prefill: full causal forward, capture per-layer K/V ----
    positions = jnp.broadcast_to(jnp.arange(S), (b, S))
    x = w.embed(ids)
    cos = jnp.take(cos_tab, positions, axis=0)[:, :, None, :].astype(x.dtype)
    sin = jnp.take(sin_tab, positions, axis=0)[:, :, None, :].astype(x.dtype)
    causal = jnp.where(jnp.tril(jnp.ones((S, S), bool)), 0.0, -jnp.inf)
    k_cache = jnp.zeros((L, b, kvh, M, d), x.dtype)
    v_cache = jnp.zeros((L, b, kvh, M, d), x.dtype)
    for i in range(L):
        x, k, v = _block(w, i, x, cos, sin, causal)
        k_cache = k_cache.at[i, :, :, :S].set(jnp.moveaxis(k, 1, 2))
        v_cache = v_cache.at[i, :, :, :S].set(jnp.moveaxis(v, 1, 2))
    x = _rms_norm(x, w["model.norm.weight"], cfg.rms_norm_eps)
    last_logits = w.head(x[:, -1])

    key, sub = jax.random.split(key)
    tok = _sample(last_logits, sub, do_sample, temperature, top_k, top_p)
    done = jnp.zeros((b,), bool) | (tok == eos_id)

    # ---- decode scan ----
    def step(carry, _):
        tok, pos, k_cache, v_cache, key, done = carry
        logits, k_cache, v_cache = _decode_step(w, cos_tab, sin_tab, tok,
                                                pos, k_cache, v_cache)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sub, do_sample, temperature, top_k, top_p)
        nxt = jnp.where(done, eos_id, nxt)
        done = done | (nxt == eos_id)
        return (nxt, pos + 1, k_cache, v_cache, key, done), tok

    carry = (tok, jnp.asarray(S, jnp.int32), k_cache, v_cache, key, done)
    (last, _, _, _, _, _), toks = lax.scan(step, carry, None,
                                           length=max_new_tokens - 1)
    out = jnp.concatenate([jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
    return out


@partial(jax.jit, static_argnames=("cfg_id", "max_new_tokens", "num_beams",
                                   "length_penalty", "eos_id"))
def _beam_search_jit(params, ids, cfg_id, max_new_tokens, num_beams,
                     length_penalty, eos_id):
    """Compiled beam search: prefill once per prompt, then a ``lax.scan``
    over decode steps carrying B beams per sequence.  Finished (EOS) beams
    are frozen — their candidate row collapses to a single "emit EOS again
    at +0 logp" entry, so they keep competing on their final score.  The
    analog of the reference's beam-search decode (the legacy
    paddle beam_search op + PaddleNLP's loop), formulated as two XLA
    programs with static shapes."""
    cfg, cos_tab, sin_tab = _CFGS[cfg_id]
    w = _Weights(cfg, params)
    b, S = ids.shape
    B = num_beams
    M = S + max_new_tokens
    h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    L = cfg.num_hidden_layers

    # ---- prefill (per prompt, beams share it) ----
    positions = jnp.broadcast_to(jnp.arange(S), (b, S))
    x = w.embed(ids)
    cos = jnp.take(cos_tab, positions, axis=0)[:, :, None, :].astype(x.dtype)
    sin = jnp.take(sin_tab, positions, axis=0)[:, :, None, :].astype(x.dtype)
    causal = jnp.where(jnp.tril(jnp.ones((S, S), bool)), 0.0, -jnp.inf)
    k_cache = jnp.zeros((L, b, kvh, M, d), x.dtype)
    v_cache = jnp.zeros((L, b, kvh, M, d), x.dtype)
    for i in range(L):
        x, k, v = _block(w, i, x, cos, sin, causal)
        k_cache = k_cache.at[i, :, :, :S].set(jnp.moveaxis(k, 1, 2))
        v_cache = v_cache.at[i, :, :, :S].set(jnp.moveaxis(v, 1, 2))
    x = _rms_norm(x, w["model.norm.weight"], cfg.rms_norm_eps)
    logp0 = jax.nn.log_softmax(w.head(x[:, -1]).astype(jnp.float32), axis=-1)
    V = logp0.shape[-1]

    alive_logp, tok = lax.top_k(logp0, B)            # [b, B]
    tok = tok.astype(jnp.int32)
    done = tok == eos_id
    gen_len = jnp.ones((b, B), jnp.int32)
    toks_buf = jnp.zeros((b, B, max_new_tokens), jnp.int32)
    toks_buf = toks_buf.at[:, :, 0].set(tok)
    # beams share the prompt cache: tile to [L, b*B, kvh, M, d]
    k_cache = jnp.repeat(k_cache, B, axis=1)
    v_cache = jnp.repeat(v_cache, B, axis=1)

    def gather_cache(c, parent):
        # c: [L, b*B, kvh, M, d] -> reorder the beam sub-axis by parent
        cv = c.reshape(L, b, B, kvh, M, d)
        idx = parent[None, :, :, None, None, None]
        cv = jnp.take_along_axis(cv, idx, axis=2)
        return cv.reshape(L, b * B, kvh, M, d)

    def step(carry, t):
        alive_logp, tok, toks_buf, gen_len, done, k_cache, v_cache = carry
        pos = S + t
        logits, k_cache, v_cache = _decode_step(
            w, cos_tab, sin_tab, tok.reshape(b * B), pos, k_cache, v_cache)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                axis=-1).reshape(b, B, V)
        # frozen EOS beams: single continuation (EOS again) at +0 logp
        eos_row = jnp.full((V,), -jnp.inf).at[eos_id if eos_id >= 0 else 0
                                              ].set(0.0)
        lp = jnp.where(done[:, :, None], eos_row[None, None, :], lp)
        cand = alive_logp[:, :, None] + lp           # [b, B, V]
        top, idx = lax.top_k(cand.reshape(b, B * V), B)
        parent = (idx // V).astype(jnp.int32)
        ntok = (idx % V).astype(jnp.int32)
        # reorder all beam state by parent
        toks_buf = jnp.take_along_axis(toks_buf, parent[:, :, None], axis=1)
        gen_len = jnp.take_along_axis(gen_len, parent, axis=1)
        done = jnp.take_along_axis(done, parent, axis=1)
        k_cache = gather_cache(k_cache, parent)
        v_cache = gather_cache(v_cache, parent)
        gen_len = gen_len + jnp.where(done, 0, 1)
        toks_buf = lax.dynamic_update_slice_in_dim(
            toks_buf, ntok[:, :, None], t + 1, axis=2)
        done = done | (ntok == eos_id)
        return (top, ntok, toks_buf, gen_len, done, k_cache, v_cache), None

    carry = (alive_logp, tok, toks_buf, gen_len, done, k_cache, v_cache)
    carry, _ = lax.scan(step, carry, jnp.arange(max_new_tokens - 1))
    alive_logp, _, toks_buf, gen_len, done, _, _ = carry
    # GNMT-free simple normalization: score = logp / len^alpha
    scores = alive_logp / jnp.power(gen_len.astype(jnp.float32),
                                    length_penalty)
    best = jnp.argmax(scores, axis=1)                # [b]
    out = jnp.take_along_axis(toks_buf, best[:, None, None], axis=1)[:, 0]
    best_score = jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0]
    return out, best_score


_CFGS = {}


def register_config(cfg):
    """Key the compiled decode programs + rope tables on the config
    VALUES, so equal configs across model instances (and external
    callers like bench.py driving ``_generate_jit`` with their own
    param dict) share one compilation.  Returns the hashable cfg id."""
    import dataclasses

    cfg_key = tuple(sorted(dataclasses.asdict(cfg).items()))
    if cfg_key not in _CFGS:
        from .llama import _rope_tables

        if hasattr(cfg, "rope_tables"):     # its own (YaRN, partial)
            cos_tab, sin_tab = cfg.rope_tables()
        else:
            cos_tab, sin_tab = _rope_tables(cfg.head_dim,
                                            cfg.max_position_embeddings,
                                            cfg.rope_theta)
        _CFGS[cfg_key] = (cfg, cos_tab, sin_tab)
    return cfg_key


def generate(model, input_ids, max_new_tokens: int = 32,
             do_sample: bool = False, temperature: float = 1.0,
             top_k: int = 0, top_p: float = 1.0, seed: int = 0,
             eos_token_id: Optional[int] = None, num_beams: int = 1,
             length_penalty: float = 1.0):
    """Generate continuations for ``input_ids`` ([b, S] int) with a KV
    cache; returns [b, S + max_new_tokens] including the prompt. Greedy by
    default; ``do_sample`` enables temperature / top-k / top-p;
    ``num_beams > 1`` selects compiled beam search (returns each prompt's
    best beam, scored as logp / len**length_penalty). After an EOS is
    produced, a sequence keeps emitting ``eos_token_id``."""
    from ..core.tensor import Tensor

    ids = input_ids._value if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    cfg = model.cfg if hasattr(model, "cfg") else model.model.cfg
    if "sliding_attention" in (getattr(cfg, "layer_types", None) or ()):
        raise NotImplementedError(
            "generate() attends every layer's whole context and takes one "
            "rope table a config: a model that mixes window and full "
            "layers is served by inference.ContinuousBatchingEngine")
    if cfg.paged_layout().state:
        raise NotImplementedError(
            "generate() carries a K/V cache and nothing else from token to "
            "token: a model whose layers keep a recurrent state is served "
            "by inference.ContinuousBatchingEngine")
    max_new_tokens = int(max_new_tokens)
    if max_new_tokens <= 0:
        return Tensor(ids)
    total = ids.shape[1] + max_new_tokens
    if total > cfg.max_position_embeddings:
        raise ValueError(
            f"generate: prompt ({ids.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) = {total} exceeds max_position_embeddings "
            f"({cfg.max_position_embeddings}); rope phases past the table "
            f"would silently repeat")
    params = {k: v for k, v in model.functional_state().items()}
    cfg_key = register_config(cfg)
    eos = -1 if eos_token_id is None else int(eos_token_id)
    if num_beams > 1:
        if do_sample:
            raise ValueError("beam search is deterministic: num_beams > 1 "
                             "is incompatible with do_sample=True")
        new, _ = _beam_search_jit(params, ids, cfg_key, max_new_tokens,
                                  int(num_beams), float(length_penalty), eos)
    else:
        key = jax.random.PRNGKey(seed)
        new = _generate_jit(params, ids, key, cfg_key, max_new_tokens,
                            bool(do_sample), float(temperature), int(top_k),
                            float(top_p), eos)
    return Tensor(jnp.concatenate([ids, new], axis=1))
