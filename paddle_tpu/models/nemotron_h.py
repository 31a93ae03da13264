"""Nemotron-H (NVIDIA Nemotron-3, ``model_type`` ``nemotron_h``) on the
serving path: a hybrid whose every layer is ONE part, by the letters of
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*`` grouped-query
attention, ``E`` a bank of experts.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
(``config.json``).  Layer ``i``: ``x <- x + part_i(RMSNorm(x))``.

- ``M``: ``[z | xBC | dt] = u W_in`` (widths ``d_in``, ``d_in + 2 G N``,
  ``H``; ``d_in = H P``); ``xBC <- silu(conv)``, a causal depthwise
  convolution over the last ``conv_kernel`` positions plus a bias: the
  kernel of ``ops/pallas/causal_conv.py`` over the packed rows (on the
  CPU its reference, the same in XLA's terms); ``xBC`` splits into ``x
  [H, P]``, ``B [G, N]``, ``C [G, N]`` (head h reads group ``h // (H /
  G)``); ``dt <- softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the scan of ``ops/pallas/ssd_scan.py``; ``y <- y + D
  x``; ``y <- RMSNorm over each of the G groups of (y * silu(z))`` with a
  gain; ``out = y W_out``.  What a sequence carries from token to token
  is the state ``S [H, P, N]`` (float32) and the convolution's last
  ``conv_kernel - 1`` inputs ``[conv_kernel - 1, d_in + 2 G N]``: not
  pages, one ENTRY of each a sequence, whatever its length.
- ``*``: GQA over the whole context through the engine's ragged paged
  kernel.  No rotary embedding (the family's convention: the Mamba
  layers carry position).
- ``E``: ``generation._moe_ffn`` with ``moe_scoring = "sigmoid_groups"``
  (``n_group`` 1: the plain k largest), the routed experts not gated
  (``relu(.)^2``) and in a latent of ``moe_latent_size`` between two
  projections, a shared expert at the full hidden width, and
  ``experts_held``.

The engine (``ContinuousBatchingEngine``) serves this through its one
``step()``: ``paged_layout()`` says which layers have pages (the ``*``
layers, one kind), what a slot's recurrent state is a state layer
(``PagedLayout.state``), how many packed rows are whole tiles of every
kernel of the step (``PagedLayout.tile_rows``) and gives
``unified_step_jit``, this model's part of the unified step, under the
contract of ``inference/paged_layout.PagedLayout`` (the rows' state
columns too).  The multi-token-prediction module is not loaded.
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
from ..ops.pallas.decode_attention import (default_pages_per_step,
                                           ragged_tile_rows)
from ..ops.pallas.ssd_scan import (mamba2_ssd_scan, ssd_max_units,
                                   ssd_scan_reference)
from .generation import (_CFGS, _Weights, _ffn, _moe_device_counts,
                         _rms_norm)
from .llama_paged import gqa_paged_attention

__all__ = ["NemotronHConfig", "unified_step_jit"]

_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")

#: whole tiles of the step's kernels in the least rows the step is
#: compiled at.  At ONE tile (128 rows at the published widths) the
#: compiled step does not end on the chip: every rung compiles and its
#: padding launch ends, and the first launch of a few decode rows at
#: that rung never comes back.  It takes the expert layer's Mosaic
#: grouped matmul INSIDE the step compiled at that size (PERF.md
#: section 6, PR 43: the expert layer alone at 128 rows, the same step
#: at two and at three tiles, and the step with the grouped matmul in
#: XLA's terms all end; the shared expert computed first, 64 MiB of
#: scoped VMEM for the kernel, its operands pinned to HBM, its other
#: form, wider blocks, a plain route and no ``lax.cond`` do not mend
#: it).  The cause is not found, so the layout's tile is two of the
#: kernels': a finding of the chip beside its reason, not an option
STEP_TILES = 2


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published keys (defaults: the published values) plus what one
    chip holds: ``experts_held = (lo, hi)``, the routed experts of its
    expert-parallel rank (None: all), with ``n_routed_experts`` the
    router's full width; ``vocab_size`` the rows of the embedding and
    the head that live here; ``num_hidden_layers`` the layers that run,
    the first of the pattern."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = _PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    n_routed_experts: int = 512
    n_shared_experts: int = 1
    num_experts_per_tok: int = 22
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    mlp_hidden_act: str = "relu2"
    norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"
    #: row block of the held experts' grouped matmuls (bf16 packs 16
    #: rows a tile; a decode step gives an expert some 44 rows)
    moe_block_rows: int = 16

    def __post_init__(self):
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(v) for v in self.experts_held))
        odd = set(self.pattern) - set("M*E")
        if odd or len(self.pattern) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern gives {len(self.pattern)} layers "
                f"of M, * and E ({sorted(odd)} besides) for "
                f"{self.num_hidden_layers}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("the heads of a group share B and C: "
                             "mamba_num_heads is a multiple of n_groups")
        if self.mlp_hidden_act != "relu2" or self.n_shared_experts != 1 \
                or self.mamba_proj_bias or not self.use_conv_bias:
            raise ValueError("this model's experts are relu2 with one shared "
                             "expert; its mixer has a conv bias and no "
                             "projection bias")

    # what generation._moe_ffn reads of a config
    moe_scoring = "sigmoid_groups"

    @property
    def moe_top_k(self) -> int:
        return self.num_experts_per_tok

    @property
    def pattern(self) -> str:
        """A letter a layer that runs."""
        return self.hybrid_override_pattern[:self.num_hidden_layers]

    def layers_of(self, letter: str) -> Tuple[int, ...]:
        return tuple(i for i, c in enumerate(self.pattern) if c == letter)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @classmethod
    def from_published(cls, published: Dict[str, Any], **changed):
        """From a ``config.json``'s keys; those this model has no use for
        (``model_type``, ``rope_theta``: no rotary, ...) are passed over."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in published.items() if k in names}
        if "torch_dtype" in published:
            kw["dtype"] = published["torch_dtype"]
        kw.update(changed)
        return cls(**kw)

    @classmethod
    def debug(cls, **changed):
        """The CPU tests' size: every letter, nothing wide."""
        kw = dict(vocab_size=96, hidden_size=32, num_hidden_layers=5,
                  hybrid_override_pattern="MEM*E", num_attention_heads=4,
                  num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
                  mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                  chunk_size=8, n_routed_experts=16, num_experts_per_tok=3,
                  moe_intermediate_size=24, moe_latent_size=16,
                  moe_shared_expert_intermediate_size=40,
                  max_position_embeddings=256, dtype="float32",
                  moe_block_rows=8)
        kw.update(changed)
        return cls(**kw)

    def rope_tables(self):
        """None to speak of: the attention layers rotate nothing."""
        z = jnp.zeros((1, 1), jnp.float32)
        return z, z

    def paged_layout(self):
        c = self
        kvh, d = c.num_key_value_heads, c.head_dim
        attn_tile = ragged_tile_rows(c.num_attention_heads, kvh, d)
        # whole tiles of every kernel of the step: the ragged walk's,
        # and the scan's and the convolution's, which is ``chunk_size``
        # (``mamba_part``; 128 at the published widths); the experts'
        # grouped matmul pads its own copies to whole blocks, whatever
        # the rows.  ``STEP_TILES`` of them: 256
        tile_rows = STEP_TILES * math.lcm(attn_tile, c.chunk_size)
        n_state = len(c.layers_of("M"))

        def row_counts(rows, ctx_tokens, page_size, pages_per_seq):
            # a row of a slot reads and writes its slot's state once a
            # state layer; the scan's rows are the step's live rows
            slots = len(np.unique(rows[:, 4]))
            decode = int((np.bincount(rows[:, 4], minlength=1) == 1).sum()) \
                if len(rows) else 0
            return {"attn_kv_tokens_read": ragged_kv_tokens_read(
                        rows[:, 4], rows[:, 3], attn_tile, page_size,
                        pages_per_seq),
                    "ssm_state_slots": slots, "ssm_rows": len(rows),
                    "ssm_prefill_rows": len(rows) - decode}

        return PagedLayout(
            name="kv", rows=((kvh, d), (kvh, d)), step=unified_step_jit,
            row_counts=row_counts, device_counts=MOE_DEVICE_COUNTS,
            count_names=("kv_ctx_tokens", "attn_kv_tokens_read",
                         "ssm_state_slots", "ssm_rows", "ssm_prefill_rows",
                         *MOE_DEVICE_COUNTS),
            pages_per_step=lambda page, pps, itemsize: default_pages_per_step(
                page, kvh, d, pps, itemsize),
            tile_rows=tile_rows,
            kinds=(PageKind("pages", c.layers_of("*")),),
            state=(((c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size),
                    "float32"),
                   ((c.conv_kernel - 1, c.conv_dim), None)),
            state_layers=n_state, state_snapshots_a_step=SNAPSHOTS_A_STEP)

    def leaf_shapes(self) -> Dict[str, tuple]:
        """Every leaf of the functional state this model reads, by name
        (Linear weights ``[in, out]``; expert banks stacked over the
        experts HELD; the convolution ``[taps, channels]``)."""
        c = self
        h, H = c.hidden_size, c.mamba_num_heads
        lo, hi = c.experts_held or (0, c.n_routed_experts)
        out = {"model.embed_tokens.weight": (c.vocab_size, h),
               "model.norm.weight": (h,), "lm_head.weight": (h, c.vocab_size)}
        for i, letter in enumerate(c.pattern):
            p = f"model.layers.{i}."
            out[p + "norm.weight"] = (h,)
            if letter == "M":
                m = p + "mixer."
                out.update({
                    m + "in_proj.weight": (h, c.d_inner + c.conv_dim + H),
                    m + "conv1d.weight": (c.conv_kernel, c.conv_dim),
                    m + "conv1d.bias": (c.conv_dim,),
                    m + "dt_bias": (H,), m + "A_log": (H,), m + "D": (H,),
                    m + "norm.weight": (c.d_inner,),
                    m + "out_proj.weight": (c.d_inner, h)})
            elif letter == "*":
                a = p + "self_attn."
                q = c.num_attention_heads * c.head_dim
                kv = c.num_key_value_heads * c.head_dim
                out.update({a + "q_proj.weight": (h, q),
                            a + "k_proj.weight": (h, kv),
                            a + "v_proj.weight": (h, kv),
                            a + "o_proj.weight": (q, h)})
            else:
                m = p + "mlp."
                f, l, e = c.moe_intermediate_size, c.moe_latent_size, hi - lo
                fs = c.moe_shared_expert_intermediate_size
                out.update({
                    m + "router.weight": (h, c.n_routed_experts),
                    m + "router.bias": (c.n_routed_experts,),
                    m + "latent_down.weight": (h, l),
                    m + "latent_up.weight": (l, h),
                    m + "experts.up_proj.weight": (e, l, f),
                    m + "experts.down_proj.weight": (e, f, l),
                    m + "shared_expert.up_proj.weight": (h, fs),
                    m + "shared_expert.down_proj.weight": (fs, h)})
        return out


def mamba_part(cfg, w, i, x, ssm_pool, conv_pool, slot, lens, src, dst,
               max_slots: int):
    """Layer ``i``'s Mamba-2 mixer on the packed rows ``x`` ``[T,
    hidden]``: each slot's rows start from state entry ``src`` (below
    zero: zeros) and leave the state in entry ``dst``, in both pools;
    ``max_slots`` bounds the slots the rows can name (the scan's units
    of work).  Returns ``(x + mixer, ssm pool, conv pool)``."""
    T = x.shape[0]
    H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.ssm_state_size)
    d_in, cd = cfg.d_inner, cfg.conv_dim
    mx = "mixer."
    with jax.named_scope("mamba_in_proj"):
        u = _rms_norm(x, w.layer(i, "norm.weight"), cfg.norm_eps)
        zxd = u @ w.layer(i, mx + "in_proj.weight")
        z, xbc, dt = (zxd[:, :d_in], zxd[:, d_in:d_in + cd],
                      zxd[:, d_in + cd:])
    with jax.named_scope("mamba_conv"):
        conv = packed_causal_conv_reference if pallas_interpret() \
            else partial(packed_causal_conv, tile_rows=cfg.chunk_size)
        xbc, conv_pool = conv(xbc, w.layer(i, mx + "conv1d.weight"),
                              w.layer(i, mx + "conv1d.bias"), conv_pool,
                              slot, src, dst)
    with jax.named_scope("ssd_scan"):
        xs = xbc[:, :d_in].reshape(T, H, P)
        B = xbc[:, d_in:d_in + G * N].reshape(T, G, N)
        C = xbc[:, d_in + G * N:].reshape(T, G, N)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + w.layer(i, mx + "dt_bias").astype(jnp.float32))
        a = dt * -jnp.exp(w.layer(i, mx + "A_log").astype(jnp.float32))
        if pallas_interpret():
            y, ssm_pool = ssd_scan_reference(xs, dt, a, B, C, ssm_pool, slot,
                                             src, dst)
        else:
            y, ssm_pool = mamba2_ssd_scan(
                xs, dt, a, B, C, ssm_pool, slot, lens, src, dst,
                tile_rows=cfg.chunk_size,
                max_units=ssd_max_units(T, cfg.chunk_size, max_slots))
        y = y + xs.astype(jnp.float32) \
            * w.layer(i, mx + "D").astype(jnp.float32)[None, :, None]
    with jax.named_scope("mamba_out"):
        y = y.reshape(T, d_in) * jax.nn.silu(z.astype(jnp.float32))
        yg = y.reshape(T, G, d_in // G)
        yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                                + cfg.norm_eps)
        y = (yg.reshape(T, d_in).astype(x.dtype)
             * w.layer(i, mx + "norm.weight"))
        x = x + y @ w.layer(i, mx + "out_proj.weight")
    return x, ssm_pool, conv_pool


@partial(jax.jit, static_argnames=("self_cfg_id", "pages_per_step",
                                   "with_head"),
         donate_argnames=("k_pages", "v_pages", "state"))
def unified_step_jit(params, k_pages, v_pages, rows, tables, cos_tab,
                     sin_tab, self_cfg_id, pages_per_step, kv_scales=None,
                     with_head=True, gather=None, prev_tokens=None,
                     state=None):
    """This model's part of the engine's ONE ragged step, under
    ``PagedLayout.step``'s contract (``inference/paged_layout.py``).
    Its own: ``k_pages`` / ``v_pages`` are the pools of the ``*`` layers
    alone, in their order (their attention is the Llama family's,
    ``llama_paged.gqa_paged_attention``, with no rotary embedding: the
    Mamba layers carry position); ``state`` is ``(ssm pools, conv
    pools)``, one ``[entries, ...]`` pool a ``M`` layer each; ``rows``
    ``[T, 8]``.  Returns ``(k pools, v pools, (logits, tokens,
    MOE_DEVICE_COUNTS), state)``."""
    cfg, _, _ = _CFGS[self_cfg_id]
    w = _Weights(cfg, params)
    (table,) = tables
    new_k, new_v = list(k_pages), list(v_pages)
    ssm, conv = (list(p) for p in state)
    # the scopes are ``profiler.device_trace.DEVICE_SCOPES``
    with jax.named_scope("embed"):
        tok, phys, off, lens, slot, src, dst, snap = row_columns(
            rows, prev_tokens)
        lens = jnp.where(slot < 0, 0, lens)
        x = w.embed(tok)
        stats = {"valid": slot >= 0,
                 **{c: [] for c in MOE_DEVICE_COUNTS[:4]}}
    snaps = snapshot_plan(snap, dst, ssm[0].shape[0] - 1)
    n_attn = n_state = 0
    for i, letter in enumerate(cfg.pattern):
        if letter == "M":
            x, ssm[n_state], conv[n_state] = mamba_part(
                cfg, w, i, x, ssm[n_state], conv[n_state], slot, lens, src,
                dst, table.shape[0])
            ssm[n_state] = copy_snapshots(ssm[n_state], snaps)
            conv[n_state] = copy_snapshots(conv[n_state], snaps)
            n_state += 1
        elif letter == "*":
            x, new_k[n_attn], new_v[n_attn] = gqa_paged_attention(
                cfg, w, i, x, new_k[n_attn], new_v[n_attn], phys, off, lens,
                slot, table, pages_per_step, norm="norm.weight",
                eps=cfg.norm_eps)
            n_attn += 1
        else:
            with jax.named_scope("mlp"):
                xm = _rms_norm(x, w.layer(i, "norm.weight"), cfg.norm_eps)
                x = x + _ffn(w, i, xm, stats)
    state = (tuple(ssm), tuple(conv))
    if not with_head:
        return tuple(new_k), tuple(new_v), None, state
    logits = gathered_logits(
        x, gather, lambda y: _rms_norm(y, w["model.norm.weight"],
                                       cfg.norm_eps), w.head)
    with jax.named_scope("sample"):
        lo, hi = cfg.experts_held or (0, cfg.n_routed_experts)
        counts = _moe_device_counts(stats, hi - lo)
        out = (logits, sample_greedy(logits), counts)
    return tuple(new_k), tuple(new_v), out, state
