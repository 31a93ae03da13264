"""Kimi-Linear (``model_type`` ``kimi_linear``) on the serving path: Kimi
Delta Attention, a gated delta-rule state with a decay a key CHANNEL,
in three layers of four, NoPE multi-head latent attention over a paged
latent cache in the fourth, and sigmoid-routed experts of which one
chip holds its share.

Source: https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct
(``config.json``; the indices of ``linear_attn_config`` are 1-BASED).
Layer ``n``: ``h = x + Mixer_n(RMSNorm(x))``, ``y = h + FFN_n(RMSNorm(h))``.

- KDA (``n`` in ``kda_layers``; ``H`` heads of ``d`` for keys and values),
  on the normed ``u``: ``q^, k^, v = silu(conv(u W_q | u W_k | u W_v))``,
  one causal depthwise convolution over the last
  ``short_conv_kernel_size`` inputs of the ``3 H d`` channels, no bias
  (``ops/pallas/causal_conv.py`` over the packed rows); a head: ``q = q^
  / |q^| * d^-1/2``, ``k = k^ / |k^|``; the decay a key channel ``g =
  -exp(A_log[h]) * softplus((u W_fa W_fb)[h, :] + dt_bias[h, :])`` and
  ``beta = sigmoid((u W_b)[h])`` in float32; the scan of
  ``ops/pallas/kda_scan.py`` (``S' = Diag(e^g) S``, ``S = S' + beta k (v
  - S'^T k)^T``, ``o = S^T q``); ``o~ = RMSNorm_d(o) * sigmoid((u W_ga
  W_gb)[h, :])``; ``out = concat_h(o~) W_o``.  What a sequence carries
  from token to token is the state ``S [H, d, d]`` (float32) and the
  convolution's last inputs ``[short_conv_kernel_size - 1, 3 H d]``: one
  ENTRY of each a sequence, whatever its length.
- MLA (``n`` in ``full_attn_layers``), NoPE: ``q = u W_q`` (a head:
  ``nope`` 128 + ``rope`` 64, the second part NOT rotated: the KDA
  layers carry position); ``[c | k_p] = u W_kva`` (512 + 64), ``c~ =
  RMSNorm(c)``, ``k_p`` shared by the heads.  Served absorbed, as
  ``models/deepseek_v32.py`` serves it, without an indexer, a selection
  or a rotation: a token's cache row is ``c~`` in the first of the
  layer's two pools and ``k_p``, padded to a lane tile, in the second;
  ``qa = [W_uk q_nope ; q_p]``, scores ``(128 + 64)^-1/2 qa . [c~ ;
  k_p]`` over the whole context (``ops/pallas/dense_mla.py``), the head's
  output ``W_uv^T`` of the weighted sum of ``c~``.
- FFN: ``generation._ffn``: dense SwiGLU in the first
  ``first_k_dense_replace`` layers, then ``_moe_ffn`` with ``moe_scoring
  = "sigmoid_groups"`` at one group, a shared expert and ``experts_held``.

The engine (``ContinuousBatchingEngine``) serves this through its one
``step()``: ``paged_layout()`` says which layers have pages (the MLA
layers, one kind, whose page is a latent row), what a slot's recurrent
state is a KDA layer (``PagedLayout.state``), how many packed rows are
whole tiles of every kernel of the step (``PagedLayout.tile_rows``) and
gives ``unified_step_jit``, this model's part of the unified step, under
the contract of ``inference/paged_layout.PagedLayout``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core.device import pallas_interpret
from ..inference.paged_layout import (MOE_DEVICE_COUNTS, SNAPSHOTS_A_STEP,
                                      PagedLayout, PageKind, copy_snapshots,
                                      gathered_logits, ragged_kv_tokens_read,
                                      row_columns, sample_greedy,
                                      snapshot_plan)
from ..ops.pallas.causal_conv import (packed_causal_conv,
                                      packed_causal_conv_reference)
from ..ops.pallas.dense_mla import dense_mla_attention_raw, dense_tile_rows
from ..ops.pallas.kda_scan import kda_delta_scan, kda_scan_reference
from ..ops.pallas.sparse_mla import walk_geometry
from ..ops.pallas.ssd_scan import ssd_max_units
from .generation import (_CFGS, _Weights, _ffn, _moe_device_counts,
                         _rms_norm)

__all__ = ["KimiLinearConfig", "unified_step_jit"]

_KDA = tuple(n for n in range(1, 27) if n % 4)
_FULL = (4, 8, 12, 16, 20, 24, 27)

#: packed rows a tile of the scan and of the convolution before it
KDA_TILE_ROWS = 128
#: the L2 norm's epsilon on ``q^`` and ``k^`` (the family's kernels')
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published keys (defaults: the published values;
    ``linear_attn_config``'s under ``kda_*``, ``full_attn_layers`` and
    ``short_conv_kernel_size``) plus what one chip holds: ``experts_held
    = (lo, hi)``, the routed experts of its expert-parallel rank (None:
    all), with ``num_experts`` the router's full width; ``vocab_size``
    the rows of the embedding and the head that live here;
    ``num_hidden_layers`` the layers that run, the first of the model
    under their published indices."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    kda_layers: Tuple[int, ...] = _KDA
    full_attn_layers: Tuple[int, ...] = _FULL
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    num_expert_group: int = 1
    topk_group: int = 1
    moe_router_activation_func: str = "sigmoid"
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    model_max_length: int = 1048576
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"
    #: row block of the held experts' grouped matmuls (bf16 packs 16
    #: rows a tile)
    moe_block_rows: int = 16

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers", "experts_held"):
            v = getattr(self, name)
            if v is not None:
                object.__setattr__(self, name, tuple(int(n) for n in v))
        run = set(range(1, self.num_hidden_layers + 1))
        if set(self.kda_layers) & set(self.full_attn_layers) \
                or not run <= set(self.kda_layers) | set(self.full_attn_layers):
            raise ValueError("kda_layers and full_attn_layers (1-based) name "
                             "each layer that runs once")
        if self.q_lora_rank is not None or not self.mla_use_nope \
                or self.num_shared_experts != 1 or self.moe_layer_freq != 1 \
                or self.moe_router_activation_func != "sigmoid":
            raise ValueError("this model's attention projects q whole and "
                             "rotates nothing; every layer past the dense "
                             "ones has sigmoid-routed experts and one shared")

    # what generation._moe_ffn and _route_sigmoid_groups read of a config
    moe_scoring = "sigmoid_groups"
    moe_top_k = property(lambda self: self.num_experts_per_token)
    n_group = property(lambda self: self.num_expert_group)
    norm_topk_prob = property(lambda self: self.moe_renormalize)
    # what the engine asks every config
    max_position_embeddings = property(lambda self: self.model_max_length)
    num_key_value_heads = property(lambda self: self.num_attention_heads)

    def is_kda(self, i: int) -> bool:
        """Whether layer ``i`` (0-based, as the leaves count) is a KDA
        layer: published index ``i + 1``."""
        return i + 1 in self.kda_layers

    def layers_of(self, kda: bool) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.is_kda(i) == kda)

    @property
    def pos_row(self) -> int:
        """Numbers a token's row takes in the second pool: ``k_p``
        (``qk_rope_head_dim``) padded to whole 128-lane tiles."""
        return -(-self.qk_rope_head_dim // 128) * 128

    @property
    def walk_tile_rows(self) -> int:
        return dense_tile_rows(self.num_attention_heads, self.kv_lora_rank,
                               self.pos_row)

    @classmethod
    def from_published(cls, published: Dict[str, Any], **changed):
        """From a ``config.json``'s keys; those this model has no use for
        (``model_type``, ``rope_theta``: nothing rotates, ``head_dim``,
        ...) are passed over."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in published.items() if k in names}
        lin = published.get("linear_attn_config", {})
        kw.update({"kda_" + k: lin[k] for k in ("num_heads", "head_dim")
                   if k in lin})
        kw.update({k: lin[k] for k in ("kda_layers", "full_attn_layers",
                                       "short_conv_kernel_size") if k in lin})
        if "torch_dtype" in published:
            kw["dtype"] = published["torch_dtype"]
        kw.update(changed)
        return cls(**kw)

    @classmethod
    def debug(cls, **changed):
        """The CPU tests' size: the dense layer and a whole period (K K K
        M K), nothing wide."""
        kw = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                  moe_intermediate_size=24, num_hidden_layers=5,
                  num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=8, v_head_dim=8, kda_num_heads=4,
                  kda_head_dim=8, num_experts=16, num_experts_per_token=3,
                  model_max_length=256, dtype="float32", moe_block_rows=8)
        kw.update(changed)
        return cls(**kw)

    def rope_tables(self):
        """None to speak of: no layer rotates anything."""
        z = jnp.zeros((1, 1), jnp.float32)
        return z, z

    def paged_layout(self):
        c = self
        H, d = c.kda_num_heads, c.kda_head_dim
        walk_tile = c.walk_tile_rows
        # whole tiles of every kernel of the step: the scan's and the
        # convolution's, and the latent walk's
        tile_rows = math.lcm(KDA_TILE_ROWS, walk_tile)

        def row_counts(rows, ctx_tokens, page_size, pages_per_seq):
            # a row of a slot reads and writes its slot's state once a
            # KDA layer; the latent rows the walk fetches in one MLA
            # layer (whole turns, a slot once for each of its units)
            _, keys, turns = walk_geometry(
                page_size, pages_per_seq,
                _pages_per_step(page_size, pages_per_seq, 0))
            return {"attn_kv_tokens_read": ragged_kv_tokens_read(
                        rows[:, 4], rows[:, 3], walk_tile, keys, turns),
                    "state_rows": len(rows),
                    "state_slots": len(np.unique(rows[:, 4]))}

        return PagedLayout(
            name="latent", rows=((c.kv_lora_rank,), (c.pos_row,)),
            head_major=False, step=unified_step_jit, row_counts=row_counts,
            device_counts=MOE_DEVICE_COUNTS,
            count_names=("kv_ctx_tokens", "attn_kv_tokens_read", "state_rows",
                         "state_slots", *MOE_DEVICE_COUNTS),
            pages_per_step=_pages_per_step, tile_rows=tile_rows,
            kinds=(PageKind("latent", c.layers_of(False)),),
            state=(((H, d, d), "float32"),
                   ((c.short_conv_kernel_size - 1, 3 * H * d), None)),
            state_layers=len(c.layers_of(True)),
            state_snapshots_a_step=SNAPSHOTS_A_STEP)

    def leaf_shapes(self) -> Dict[str, tuple]:
        """Every leaf of the functional state this model reads, by name
        (Linear weights ``[in, out]``; expert banks stacked over the
        experts HELD; a convolution ``[taps, channels]``), the layers
        0-based: ``model.layers.<n - 1>`` is published layer ``n``."""
        c = self
        h, H = c.hidden_size, c.num_attention_heads
        lo, hi = c.experts_held or (0, c.num_experts)
        out = {"model.embed_tokens.weight": (c.vocab_size, h),
               "model.norm.weight": (h,), "lm_head.weight": (h, c.vocab_size)}
        for i in range(c.num_hidden_layers):
            p = f"model.layers.{i}."
            a = p + "self_attn."
            out[p + "input_layernorm.weight"] = (h,)
            out[p + "post_attention_layernorm.weight"] = (h,)
            if c.is_kda(i):
                Hk, d, K = (c.kda_num_heads, c.kda_head_dim,
                            c.short_conv_kernel_size)
                hd = Hk * d
                out.update({
                    **{a + f"{n}_proj.weight": (h, hd) for n in "qkv"},
                    **{a + f"{n}_conv1d.weight": (K, hd) for n in "qkv"},
                    a + "A_log": (Hk,), a + "dt_bias": (hd,),
                    a + "f_a_proj.weight": (h, d),
                    a + "f_b_proj.weight": (d, hd),
                    a + "b_proj.weight": (h, Hk),
                    a + "g_a_proj.weight": (h, d),
                    a + "g_b_proj.weight": (d, hd),
                    a + "o_norm.weight": (d,),
                    a + "o_proj.weight": (hd, h)})
            else:
                out.update({
                    a + "q_proj.weight": (
                        h, H * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
                    a + "kv_a_proj_with_mqa.weight": (
                        h, c.kv_lora_rank + c.qk_rope_head_dim),
                    a + "kv_a_layernorm.weight": (c.kv_lora_rank,),
                    a + "kv_b_proj.weight": (
                        c.kv_lora_rank,
                        H * (c.qk_nope_head_dim + c.v_head_dim)),
                    a + "o_proj.weight": (H * c.v_head_dim, h)})
            m = p + "mlp."
            if i < c.first_k_dense_replace:
                f = c.intermediate_size
                out.update({m + "gate_proj.weight": (h, f),
                            m + "up_proj.weight": (h, f),
                            m + "down_proj.weight": (f, h)})
                continue
            f, e = c.moe_intermediate_size, hi - lo
            out.update({
                m + "router.weight": (h, c.num_experts),
                m + "router.bias": (c.num_experts,),
                m + "shared_expert.gate_proj.weight": (h, f),
                m + "shared_expert.up_proj.weight": (h, f),
                m + "shared_expert.down_proj.weight": (f, h),
                m + "experts.gate_proj.weight": (e, h, f),
                m + "experts.up_proj.weight": (e, h, f),
                m + "experts.down_proj.weight": (e, f, h)})
        return out


def _pages_per_step(page: int, pages_per_seq: int, itemsize: int) -> int:
    """2048 keys a turn of the latent walk, as DeepSeek's layout has it
    (PERF.md section 6, PR 26)."""
    return max(1, 2048 // page)


def _l2_norm(x):
    """Float32 ``x`` over its last axis to unit length."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def kda_part(cfg, w, i, x, s_pool, conv_pool, slot, lens, src, dst,
             max_slots: int):
    """Layer ``i``'s KDA mixer on the packed rows ``x`` ``[T, hidden]``:
    each slot's rows start from state entry ``src`` (below zero: zeros)
    and leave the state in entry ``dst``, in both pools; ``max_slots``
    bounds the slots the rows can name (the scan's units of work).
    Returns ``(x + mixer, state pool, conv pool)``."""
    T = x.shape[0]
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    hd, f32 = H * d, jnp.float32
    at = "self_attn."

    def leaf(name):
        return w.layer(i, at + name)

    with jax.named_scope("kda_qkv"):
        u = _rms_norm(x, w.layer(i, "input_layernorm.weight"),
                      cfg.rms_norm_eps)
        qkv = jnp.concatenate([u @ leaf(f"{n}_proj.weight") for n in "qkv"],
                              axis=-1)
        f = (u @ leaf("f_a_proj.weight")) @ leaf("f_b_proj.weight")
        z = (u @ leaf("g_a_proj.weight")) @ leaf("g_b_proj.weight")
        b = u @ leaf("b_proj.weight")
    with jax.named_scope("kda_conv"):
        taps = jnp.concatenate([leaf(f"{n}_conv1d.weight") for n in "qkv"],
                               axis=-1)
        conv = packed_causal_conv_reference if pallas_interpret() \
            else partial(packed_causal_conv, tile_rows=KDA_TILE_ROWS)
        qkv, conv_pool = conv(qkv, taps, jnp.zeros((3 * hd,), taps.dtype),
                              conv_pool, slot, src, dst)
    with jax.named_scope("kda_scan"):
        q, k, v = (qkv[:, n * hd:(n + 1) * hd].reshape(T, H, d)
                   for n in range(3))
        q = (_l2_norm(q.astype(f32)) * d ** -0.5).astype(x.dtype)
        k = _l2_norm(k.astype(f32)).astype(x.dtype)
        g = -jnp.exp(leaf("A_log").astype(f32))[None, :, None] \
            * jax.nn.softplus(f.astype(f32).reshape(T, H, d)
                              + leaf("dt_bias").astype(f32).reshape(H, d))
        beta = jax.nn.sigmoid(b.astype(f32))
        if pallas_interpret():
            o, s_pool = kda_scan_reference(q, k, v, g, beta, s_pool, slot,
                                           src, dst)
        else:
            o, s_pool = kda_delta_scan(
                q, k, v, g, beta, s_pool, slot, lens, src, dst,
                tile_rows=KDA_TILE_ROWS,
                max_units=ssd_max_units(T, KDA_TILE_ROWS, max_slots))
    with jax.named_scope("kda_out"):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + cfg.rms_norm_eps)
        o = o * leaf("o_norm.weight").astype(f32) \
            * jax.nn.sigmoid(z.astype(f32).reshape(T, H, d))
        x = x + o.reshape(T, hd).astype(x.dtype) @ leaf("o_proj.weight")
    return x, s_pool, conv_pool


def mla_part(cfg, w, i, x, lat_pool, pos_pool, phys, off, lens, slot, table,
             pages_per_step: int):
    """Layer ``i``'s latent attention on the packed rows ``x`` ``[T,
    hidden]``: writes each row's ``c~`` and ``k_p`` at (``phys``,
    ``off``) of the layer's two pools and attends the whole context.
    Returns ``(x + attention, latent pool, positional pool)``."""
    T = x.shape[0]
    H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    dc, dp, eps = cfg.kv_lora_rank, cfg.pos_row, cfg.rms_norm_eps
    at = "self_attn."
    with jax.named_scope("mla_qkv"):
        u = _rms_norm(x, w.layer(i, "input_layernorm.weight"), eps)
        q = (u @ w.layer(i, at + "q_proj.weight")).reshape(T, H, dn + dr)
        kva = u @ w.layer(i, at + "kv_a_proj_with_mqa.weight")
        c = _rms_norm(kva[:, :dc], w.layer(i, at + "kv_a_layernorm.weight"),
                      eps)
        pad = [(0, 0), (0, dp - dr)]
        # the in-page offset indexes the pool's second axis, so the
        # update window is one whole row: written in place
        lat_pool = lat_pool.at[phys, off].set(c.astype(lat_pool.dtype))
        pos_pool = pos_pool.at[phys, off].set(
            jnp.pad(kva[:, dc:], pad).astype(pos_pool.dtype))
    with jax.named_scope("latent_attn"):
        scale = (dn + dr) ** -0.5
        wkvb = w.layer(i, at + "kv_b_proj.weight").reshape(dc, H, dn + dv)
        qa = jnp.einsum("thd,chd->thc", q[..., :dn], wkvb[..., :dn],
                        preferred_element_type=jnp.float32)
        q_p = jnp.pad(q[..., dn:].astype(jnp.float32), [(0, 0), *pad])
        o_lat = dense_mla_attention_raw(
            (qa * scale).astype(x.dtype), (q_p * scale).astype(x.dtype),
            lat_pool, pos_pool, lens, slot, table,
            pages_per_step=pages_per_step, tile_rows=cfg.walk_tile_rows)
    with jax.named_scope("attn_out"):
        o = jnp.einsum("thc,chd->thd", o_lat.astype(x.dtype), wkvb[..., dn:])
        x = x + o.reshape(T, H * dv) @ w.layer(i, at + "o_proj.weight")
    return x, lat_pool, pos_pool


@partial(jax.jit, static_argnames=("self_cfg_id", "pages_per_step",
                                   "with_head"),
         donate_argnames=("k_pages", "v_pages", "state"))
def unified_step_jit(params, k_pages, v_pages, rows, tables, cos_tab,
                     sin_tab, self_cfg_id, pages_per_step, kv_scales=None,
                     with_head=True, gather=None, prev_tokens=None,
                     state=None):
    """This model's part of the engine's ONE ragged step, under
    ``PagedLayout.step``'s contract (``inference/paged_layout.py``).
    Its own: ``k_pages`` / ``v_pages`` are the latent and the positional
    pools of the MLA layers alone, in their order; ``state`` is ``(state
    pools, conv pools)``, one ``[entries, ...]`` pool a KDA layer each;
    ``rows`` ``[T, 8]``.  Returns ``(latent pools, positional pools,
    (logits, tokens, MOE_DEVICE_COUNTS), state)``."""
    cfg, _, _ = _CFGS[self_cfg_id]
    w = _Weights(cfg, params)
    (table,) = tables
    new_lat, new_pos = list(k_pages), list(v_pages)
    kda, conv = (list(p) for p in state)
    # the scopes are ``profiler.device_trace.DEVICE_SCOPES``
    with jax.named_scope("embed"):
        tok, phys, off, lens, slot, src, dst, snap = row_columns(
            rows, prev_tokens)
        lens = jnp.where(slot < 0, 0, lens)
        x = w.embed(tok)
        stats = {"valid": slot >= 0,
                 **{c: [] for c in MOE_DEVICE_COUNTS[:4]}}
    snaps = snapshot_plan(snap, dst, kda[0].shape[0] - 1)
    n_attn = n_state = 0
    for i in range(cfg.num_hidden_layers):
        if cfg.is_kda(i):
            x, kda[n_state], conv[n_state] = kda_part(
                cfg, w, i, x, kda[n_state], conv[n_state], slot, lens, src,
                dst, table.shape[0])
            kda[n_state] = copy_snapshots(kda[n_state], snaps)
            conv[n_state] = copy_snapshots(conv[n_state], snaps)
            n_state += 1
        else:
            x, new_lat[n_attn], new_pos[n_attn] = mla_part(
                cfg, w, i, x, new_lat[n_attn], new_pos[n_attn], phys, off,
                lens, slot, table, pages_per_step)
            n_attn += 1
        with jax.named_scope("mlp"):
            xm = _rms_norm(x, w.layer(i, "post_attention_layernorm.weight"),
                           cfg.rms_norm_eps)
            x = x + _ffn(w, i, xm, stats)
    state = (tuple(kda), tuple(conv))
    if not with_head:
        return tuple(new_lat), tuple(new_pos), None, state
    logits = gathered_logits(
        x, gather, lambda y: _rms_norm(y, w["model.norm.weight"],
                                       cfg.rms_norm_eps), w.head)
    with jax.named_scope("sample"):
        lo, hi = cfg.experts_held or (0, cfg.num_experts)
        counts = _moe_device_counts(stats, hi - lo)
        out = (logits, sample_greedy(logits), counts)
    return tuple(new_lat), tuple(new_pos), out, state
