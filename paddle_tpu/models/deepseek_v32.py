"""DeepSeek-V3.2 on the serving path: multi-head latent attention in
its absorbed form over a paged LATENT cache, the lightning indexer's
top-k selection inside the unified ragged step, and the DeepSeek expert
layer holding one chip's share of the experts.

Source: https://huggingface.co/deepseek-ai/DeepSeek-V3.2 (``config.json``
and ``inference/model.py``).  A layer, with ``h = RMSNorm(x)``:

- MLA: ``cq = RMSNorm(h W_qa)``; per head ``q = cq W_qb`` (``nope`` 128
  + ``rope`` 64, the rope part rotated at the token's position);
  ``[ckv ; kr] = h W_kva`` (512 + 64), ``ckv`` RMS-normed, ``kr``
  rotated, shared by all heads.  The cache holds ONE row a token,
  ``[ckv ; kr]``.  ``W_kvb`` splits per head into ``W_uk`` and ``W_uv``
  (512 x 128 each); absorbed, the query meets the latent directly:
  ``qa = [W_uk q_nope ; q_rope]``, scores ``c * qa . [ckv ; kr]``, the
  value of a row is its ``ckv``, and the head's output is ``W_uv^T``
  of the weighted sum.  ``c = (128 + 64)^-0.5 * m^2`` with YaRN's
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
- Lightning indexer: ``qI = cq W_iq`` (64 heads of 128), ``kI =
  LayerNorm(h W_ik)``, the first 64 dimensions of both rotated;
  ``w = h W_iw * 64^-0.5 * 128^-0.5``; ``I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])``; a token attends the ``index_topk``
  positions at or before it of largest ``I`` (all while there are
  fewer), ties to the lower position.  The cache holds ``kI`` beside
  the latent row, under the same page id.  Departures from the
  published indexer: no Hadamard rotation of ``qI``/``kI`` (orthogonal:
  it changes no dot product) and keys in the cache's dtype, not FP8.
- Experts: ``generation._moe_ffn`` with ``moe_scoring =
  "sigmoid_groups"``, a shared expert, and ``experts_held``.

The rotated pairs are the two HALVES of the rotary dimensions
(``generation._rotate_half``'s layout); the published code interleaves
them, which permutes the columns of weights that are random here.

The engine (``ContinuousBatchingEngine``) serves this through its one
``step()``: ``DeepseekV32Config.paged_layout()`` gives it the two pools'
row shapes and ``unified_step_jit``, this model's part of the unified
step, under the contract of ``inference/paged_layout.PagedLayout``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..inference.paged_layout import (PagedLayout, gathered_logits,
                                      ragged_kv_tokens_read, row_columns,
                                      sample_greedy)
from .generation import _CFGS, _Weights, _ffn, _rms_norm, _rotate_half

__all__ = ["DeepseekV32Config", "unified_step_jit"]

_YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 40), ("mscale", 1),
         ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096),
         ("type", "yarn"))


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config:
    """The published keys (defaults: the published values) plus what one
    chip holds: ``experts_held = (lo, hi)``, the routed experts of its
    expert-parallel rank (None: all), with ``n_routed_experts`` the
    router's full width.  ``vocab_size`` is the rows of the embedding
    and the head that live here."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Tuple[Tuple[str, Any], ...] = _YARN
    max_position_embeddings: int = 163840
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"
    #: row block of the held experts' grouped matmuls (bf16 packs 16
    #: rows a tile; an expert here sees some 16 rows of a 512-row chunk)
    moe_block_rows: int = 16

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(v) for v in self.experts_held))
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what this layer adds")

    # what generation._moe_ffn reads of a config
    moe_scoring = "sigmoid_groups"

    @property
    def moe_top_k(self) -> int:
        return self.num_experts_per_tok

    @classmethod
    def from_published(cls, published: Dict[str, Any], **changed):
        """From a ``config.json``'s keys; those this model has no use for
        (``model_type``, ``ep_size``, ...) are passed over."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in published.items() if k in names}
        if "torch_dtype" in published:
            kw["dtype"] = published["torch_dtype"]
        kw.update(changed)
        return cls(**kw)

    @classmethod
    def debug(cls, **changed):
        """The CPU tests' size: every mechanism, nothing wide."""
        kw = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_hidden_layers=3,
                  first_k_dense_replace=1, num_attention_heads=4,
                  q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=16, v_head_dim=16, n_routed_experts=16,
                  num_experts_per_tok=2, n_group=4, topk_group=2,
                  index_n_heads=2, index_head_dim=16, index_topk=8,
                  max_position_embeddings=256, dtype="float32",
                  moe_block_rows=8,
                  rope_scaling=dict(_YARN, factor=4,
                                    original_max_position_embeddings=64))
        kw.update(changed)
        return cls(**kw)

    @property
    def latent_row(self) -> int:
        """Numbers a token's cached latent row takes: ``kv_lora_rank +
        qk_rope_head_dim`` (576) padded to whole 128-lane tiles (640).
        The rope part PADS; it does not live beside the index key, which
        would leave that pool at 192, no multiple of 128 either."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        rs = dict(self.rope_scaling)
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def rope_tables(self):
        """YaRN's cos/sin ``[max_position_embeddings, qk_rope_head_dim]``
        (halves layout).  With ``mscale == mscale_all_dim`` the tables
        carry no magnitude correction; it is in ``softmax_scale``."""
        from .llama import yarn_rope_tables

        rs = dict(self.rope_scaling)
        return yarn_rope_tables(
            self.qk_rope_head_dim, self.max_position_embeddings,
            self.rope_theta, factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"])

    def paged_layout(self):
        return PagedLayout(
            name="latent", rows=((self.latent_row,), (self.index_head_dim,)),
            head_major=False, step=unified_step_jit,
            row_counts=partial(_row_counts, self),
            device_counts=DEVICE_COUNTS,
            count_names=(*ROW_COUNTS, *DEVICE_COUNTS),
            pages_per_step=_pages_per_step)

    @property
    def walk_tile_rows(self) -> int:
        """Packed rows a tile of the layer's two kernels (the attention's
        shapes bound it; the index kernel takes the same, so a step's
        units of work are the same in both)."""
        from ..ops.pallas.sparse_mla import sparse_tile_rows

        return sparse_tile_rows(self.num_attention_heads, self.latent_row,
                                self.kv_lora_rank)

    def leaf_shapes(self) -> Dict[str, tuple]:
        """Every leaf of the functional state this model reads, by name
        (Linear weights ``[in, out]``; expert banks stacked over the
        experts HELD)."""
        c = self
        h, H = c.hidden_size, c.num_attention_heads
        lo, hi = c.experts_held or (0, c.n_routed_experts)
        out = {"model.embed_tokens.weight": (c.vocab_size, h),
               "model.norm.weight": (h,), "lm_head.weight": (h, c.vocab_size)}
        for i in range(c.num_hidden_layers):
            p = f"model.layers.{i}."
            a = p + "self_attn."
            out.update({
                p + "input_layernorm.weight": (h,),
                a + "q_a_proj.weight": (h, c.q_lora_rank),
                a + "q_a_layernorm.weight": (c.q_lora_rank,),
                a + "q_b_proj.weight": (
                    c.q_lora_rank,
                    H * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
                a + "kv_a_proj_with_mqa.weight": (
                    h, c.kv_lora_rank + c.qk_rope_head_dim),
                a + "kv_a_layernorm.weight": (c.kv_lora_rank,),
                a + "kv_b_proj.weight": (
                    c.kv_lora_rank, H * (c.qk_nope_head_dim + c.v_head_dim)),
                a + "o_proj.weight": (H * c.v_head_dim, h),
                a + "indexer.wq_b.weight": (
                    c.q_lora_rank, c.index_n_heads * c.index_head_dim),
                a + "indexer.wk.weight": (h, c.index_head_dim),
                a + "indexer.k_norm.weight": (c.index_head_dim,),
                a + "indexer.k_norm.bias": (c.index_head_dim,),
                a + "indexer.weights_proj.weight": (h, c.index_n_heads),
                p + "post_attention_layernorm.weight": (h,),
            })
            m = p + "mlp."
            if i < c.first_k_dense_replace:
                f = c.intermediate_size
                out.update({m + "gate_proj.weight": (h, f),
                            m + "up_proj.weight": (h, f),
                            m + "down_proj.weight": (f, h)})
                continue
            f, e = c.moe_intermediate_size, hi - lo
            out.update({
                m + "router.weight": (h, c.n_routed_experts),
                m + "router.bias": (c.n_routed_experts,),
                m + "shared_expert.gate_proj.weight": (h, f),
                m + "shared_expert.up_proj.weight": (h, f),
                m + "shared_expert.down_proj.weight": (f, h),
                m + "experts.gate_proj.weight": (e, h, f),
                m + "experts.up_proj.weight": (e, h, f),
                m + "experts.down_proj.weight": (e, f, h),
            })
        return out


#: what the step counts on the device, in the order it returns them
DEVICE_COUNTS = ("moe_rows_held", "moe_rows_routed", "moe_expert_rows_max")
#: what the packed rows give (``_row_counts``)
ROW_COUNTS = ("index_row_ctx", "sel_row_tokens", "latent_ctx_tokens",
              "attn_kv_tokens_read")


def _pages_per_step(page: int, pages_per_seq: int, itemsize: int) -> int:
    """2048 keys a turn of the kernels' page walk: the best of 512, 1024
    and 2048 on the chip (PERF.md section 6, PR 26)."""
    return max(1, 2048 // page)


def _row_counts(cfg, rows: np.ndarray, ctx_tokens: int, page_size: int,
                pages_per_seq: int) -> Dict[str, int]:
    """A step's counts that the packed rows give (host side):
    positions the indexer scores, positions attended after selection,
    the context each scheduled slot holds, once each, and the positions
    the kernels' walk FETCHES in one layer (``attn_kv_tokens_read``,
    under the name and meaning it has on the Llama family's engines:
    whole turns, a slot once for each of its units of work; over
    ``latent_ctx_tokens`` it is the re-read factor.  At the layout's own
    pages a turn: an engine given another counts as if it had not).
    One function counts for both families: a turn of the walk here is
    what a page is to the ragged kernel's."""
    from ..ops.pallas.sparse_mla import walk_geometry

    vis = rows[:, 3]
    _, keys, turns = walk_geometry(
        page_size, pages_per_seq, _pages_per_step(page_size, pages_per_seq, 0))
    read = ragged_kv_tokens_read(rows[:, 4], vis, cfg.walk_tile_rows, keys,
                                 turns)
    return dict(zip(ROW_COUNTS, (int(vis.sum()),
                                 int(np.minimum(vis, cfg.index_topk).sum()),
                                 int(ctx_tokens), read)))


def _rope(x, cos, sin):
    return x * cos + _rotate_half(x) * sin


def _rope_head(x, cos, sin, n: int):
    """Rotate the first ``n`` of the last axis; the rest passes."""
    return jnp.concatenate([_rope(x[..., :n], cos, sin), x[..., n:]], axis=-1)


def _layer_norm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def attention_part(cfg, w, i, x, lat_pool, idx_pool, phys, off, lens, slot,
                   tables, cos, sin, pages_per_step: int):
    """Layer ``i``'s attention on the packed rows ``x`` ``[T, hidden]``:
    writes each row's latent and index key at (``phys``, ``off``) of the
    layer's pools, selects, attends.  Returns ``(x + attention, latent
    pool, index pool, selection)`` with ``selection = (index scores,
    select_top_k's numbers)``."""
    from ..ops.pallas.sparse_mla import (lightning_index_scores_raw,
                                         select_top_k,
                                         sparse_mla_attention_raw)

    T = x.shape[0]
    H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    dc, dl, eps = cfg.kv_lora_rank, cfg.latent_row, cfg.rms_norm_eps
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    walk = dict(pages_per_step=pages_per_step, tile_rows=cfg.walk_tile_rows)
    at = "self_attn."
    with jax.named_scope("mla_qkv"):
        xin = _rms_norm(x, w.layer(i, "input_layernorm.weight"), eps)
        cq = _rms_norm(xin @ w.layer(i, at + "q_a_proj.weight"),
                       w.layer(i, at + "q_a_layernorm.weight"), eps)
        q = (cq @ w.layer(i, at + "q_b_proj.weight")).reshape(T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cos[:, None],
                                            sin[:, None])
        kva = xin @ w.layer(i, at + "kv_a_proj_with_mqa.weight")
        ckv = _rms_norm(kva[:, :dc], w.layer(i, at + "kv_a_layernorm.weight"),
                        eps)
        kr = _rope(kva[:, dc:], cos, sin)
        row = jnp.concatenate(
            [ckv, kr, jnp.zeros((T, dl - dc - dr), x.dtype)], axis=-1)
        # the in-page offset indexes the pool's second axis, so the
        # update window is one whole row: written in place
        lat_pool = lat_pool.at[phys, off].set(row.astype(lat_pool.dtype))
    with jax.named_scope("index_select"):
        qi = _rope_head((cq @ w.layer(i, at + "indexer.wq_b.weight")
                         ).reshape(T, Hi, di), cos[:, None], sin[:, None], dr)
        ki = _rope_head(_layer_norm(
            xin @ w.layer(i, at + "indexer.wk.weight"),
            w.layer(i, at + "indexer.k_norm.weight"),
            w.layer(i, at + "indexer.k_norm.bias"), 1e-6), cos, sin, dr)
        wi = (xin @ w.layer(i, at + "indexer.weights_proj.weight")
              ).astype(jnp.float32) * (Hi ** -0.5 * di ** -0.5)
        idx_pool = idx_pool.at[phys, off].set(ki.astype(idx_pool.dtype))
        scores = lightning_index_scores_raw(
            qi, wi, idx_pool, lens, slot, tables, **walk)
        sel = select_top_k(scores, cfg.index_topk)
    with jax.named_scope("sparse_attn"):
        wkvb = w.layer(i, at + "kv_b_proj.weight").reshape(dc, H, dn + dv)
        qa = jnp.einsum("thd,chd->thc", q_nope, wkvb[..., :dn],
                        preferred_element_type=jnp.float32)
        qf = jnp.concatenate(
            [qa, q_rope.astype(jnp.float32),
             jnp.zeros((T, H, dl - dc - dr), jnp.float32)], axis=-1)
        qf = (qf * cfg.softmax_scale).astype(x.dtype)
        o_lat = sparse_mla_attention_raw(
            qf, lat_pool, scores, sel, lens, slot, tables, dv=dc, **walk)
    with jax.named_scope("attn_out"):
        o = jnp.einsum("thc,chd->thd", o_lat.astype(x.dtype), wkvb[..., dn:])
        x = x + o.reshape(T, H * dv) @ w.layer(i, at + "o_proj.weight")
    return x, lat_pool, idx_pool, (scores, sel)


@partial(jax.jit, static_argnames=("self_cfg_id", "pages_per_step",
                                   "with_head", "debug_select"),
         donate_argnums=(1, 2))
def unified_step_jit(params, lat_pages, idx_pages, rows, tables, cos_tab,
                     sin_tab, self_cfg_id, pages_per_step, kv_scales=None,
                     with_head=True, gather=None, prev_tokens=None,
                     debug_select=False):
    """This model's part of the engine's ONE ragged step, under
    ``PagedLayout.step``'s contract (``inference/paged_layout.py``).
    Its own: ``lat_pages`` / ``idx_pages`` are the per-layer latent and
    index-key pools ``[pages, page, numbers]`` in the engine's
    ``k_pages`` / ``v_pages`` places; the counts after the tokens are
    the int32 ``DEVICE_COUNTS`` of this step's expert layers (sums over
    layers; the fullest expert's rows, the maximum).  With
    ``debug_select`` (tests) the third result is ``(logits, tokens,
    counts, [the selection as a boolean [T, W], a layer])``."""
    from ..ops.pallas.sparse_mla import selected_mask

    cfg, _, _ = _CFGS[self_cfg_id]
    w = _Weights(cfg, params)
    (tables,) = tables                  # a table a kind of page: it has one
    # the scopes are ``profiler.device_trace.DEVICE_SCOPES``
    with jax.named_scope("embed"):
        tok, phys, off, lens, slot = row_columns(rows, prev_tokens)
        lens = jnp.where(slot < 0, 0, lens)
        x = w.embed(tok)
        pos = jnp.maximum(lens - 1, 0)
        cos = jnp.take(cos_tab, pos, axis=0).astype(x.dtype)
        sin = jnp.take(sin_tab, pos, axis=0).astype(x.dtype)
        stats = {"valid": slot >= 0, **{k: [] for k in DEVICE_COUNTS}}
    new_lat, new_idx = list(lat_pages), list(idx_pages)
    masks = []
    for i in range(cfg.num_hidden_layers):
        x, new_lat[i], new_idx[i], (scores, sel) = attention_part(
            cfg, w, i, x, new_lat[i], new_idx[i], phys, off, lens, slot,
            tables, cos, sin, pages_per_step)
        if debug_select:
            masks.append(selected_mask(scores, sel, lens))
        with jax.named_scope("mlp"):
            xm = _rms_norm(x, w.layer(i, "post_attention_layernorm.weight"),
                           cfg.rms_norm_eps)
            x = x + _ffn(w, i, xm, stats)
    if not with_head:
        return tuple(new_lat), tuple(new_idx), None
    logits = gathered_logits(
        x, gather, lambda y: _rms_norm(y, w["model.norm.weight"],
                                       cfg.rms_norm_eps), w.head)
    with jax.named_scope("sample"):
        # (its own three counts, in the order its runner reads them)
        zero = jnp.zeros((), jnp.int32)
        counts = jnp.stack([
            sum(stats["moe_rows_held"], zero),
            sum(stats["moe_rows_routed"], zero),
            jnp.max(jnp.stack(stats["moe_expert_rows_max"] or [zero]))]
        ).astype(jnp.int32)
        out = (logits, sample_greedy(logits), counts)
    if debug_select:
        out = (*out, masks)
    return tuple(new_lat), tuple(new_idx), out
