"""MiniCPM-SALA (``openbmb``, ``model_type`` ``minicpm_sala``) on the
serving path: a hybrid whose layers take their mixer from a list,
``mixer_types``: ``minicpm4`` (InfLLM-V2 block-sparse attention) or
``lightning-attn`` (Lightning linear attention), each followed by a
SwiGLU MLP, under MiniCPM's width and depth scalings.

Source: https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json.
With ``L = num_hidden_layers`` (the PUBLISHED depth, whatever part of it
runs here) and ``l`` a layer's published index: ``h_0 = scale_emb
E[token]``; ``h += (scale_depth / sqrt(L)) Mixer_l(RMSNorm(h))``; ``h +=
(scale_depth / sqrt(L)) W_d(silu(W_g u) * W_u u)``, ``u = RMSNorm(h)``;
``logits = W_head (RMSNorm(h) / (hidden_size / dim_model_base))``.

- ``lightning-attn``: ``q, k, v`` of ``lightning_nh`` heads; RMSNorm
  with a gain over each head of ``q`` and of ``k``; rotary on both (the
  whole head, computed from the row's position: no table); ``S_t =
  lambda S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(d)`` with
  ``lambda = exp(-s_h (1 - l / (L - 1) + 1e-5))``, ``s_h = 2^(-8 (h + 1)
  / H)``: the scan of ``ops/pallas/ssd_scan.py`` with ``x = v``, ``B =
  k``, ``C = q``, ``dt = 1``, ``a = log lambda`` and a ``B`` / ``C`` a
  head; ``W_o (sigmoid(W_gate u) * RMSNorm(concat o))``.  What a
  sequence carries from token to token is the state ``S`` ``[H, d, d]``
  (float32; stored ``[H, value, key]``, the scan's ``[H, P, N]``): one
  ENTRY a sequence, whatever its length.
- ``minicpm4``: ``q`` of ``num_attention_heads``, ``k``, ``v`` of
  ``num_key_value_heads`` heads, no rotary, no q/k norm.  A row whose
  context is at most ``dense_len`` attends all of it through the
  engine's ragged paged kernel; a longer one selects ``topk`` blocks of
  ``block_size`` tokens a K/V group from its sequence's COMPRESSED keys
  and attends those alone (``ops/pallas/block_sparse_attention.py``);
  ``W_o (sigmoid(W_gate u) * concat o)``.

The engine (``ContinuousBatchingEngine``) serves this through its one
``step()``: ``paged_layout()`` says which layers have pages (the
``minicpm4`` layers, one kind), that a page has a THIRD pool there
beside K and V (the compressed keys, ``PagedLayout.more_pools``), what
a slot's recurrent state is a ``lightning-attn`` layer
(``PagedLayout.state``), how many packed rows are whole tiles of every
kernel of the step (``PagedLayout.tile_rows``) and gives
``unified_step_jit``, this model's part of the unified step, under the
contract of ``inference/paged_layout.PagedLayout`` (the rows' state
columns too).
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
from ..inference.paged_layout import (SNAPSHOTS_A_STEP, PagedLayout, PageKind,
                                      _write_kv_rows, copy_snapshots,
                                      gathered_logits, row_columns,
                                      sample_greedy, snapshot_plan)
from ..ops.pallas import block_sparse_attention as bsa
from ..ops.pallas.decode_attention import (default_pages_per_step,
                                           ragged_paged_decode_raw,
                                           ragged_tile_rows)
from ..ops.pallas.ssd_scan import (mamba2_ssd_scan, ssd_max_units,
                                   ssd_scan_reference)
from .generation import _CFGS, _Weights, _ffn, _rms_norm, _rotate_half

__all__ = ["MiniCPMSALAConfig", "unified_step_jit", "lightning_part",
           "sparse_attention_part", "lightning_decay"]

_S, _L = "minicpm4", "lightning-attn"
_MIXERS = ((_S,) + (_L,) * 8 + (_S,) + (_L,) * 6 + (_S, _S) + (_L,) * 4
           + (_S,) + (_L,) * 6 + (_S,) * 3)

#: packed rows a tile of the scan (the rows are padded to whole tiles)
SCAN_TILE_ROWS = 128
#: heads a grid step of the scan holds, with a key and a query each
SCAN_HEADS_A_STEP = 16


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    """The published keys (defaults: the published values), the sizes
    the published file does not carry (the family's ``sparse_config``)
    and what one chip runs: ``layers_run = (first, one past the last)``
    of the published layers (None: all), each under its published
    index."""
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: Tuple[str, ...] = _MIXERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    qk_norm: bool = True
    use_output_norm: bool = True
    use_output_gate: bool = True
    attn_use_output_gate: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    tie_word_embeddings: bool = False
    # the family's sparse_config (MiniCPM4)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    layers_run: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        if self.layers_run is not None:
            object.__setattr__(self, "layers_run",
                               tuple(int(v) for v in self.layers_run))
        odd = set(self.mixer_types) - {_S, _L}
        if odd or len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError(
                f"mixer_types gives {len(self.mixer_types)} mixers "
                f"({sorted(odd)} besides {_S} and {_L}) for "
                f"{self.num_hidden_layers} layers")
        lo, hi = self.layers_run or (0, self.num_hidden_layers)
        if not 0 <= lo < hi <= self.num_hidden_layers:
            raise ValueError(f"layers_run {self.layers_run} of "
                             f"{self.num_hidden_layers} layers")
        if self.kernel_size != 2 * self.kernel_stride \
                or self.block_size % self.kernel_stride:
            raise ValueError("a compressed key averages two strides of "
                             "keys and a block is whole strides")
        if self.dense_len < self.topk * self.block_size:
            raise ValueError("a row that selects has topk whole blocks: "
                             "dense_len >= topk x block_size")
        if self.lightning_nkv != self.lightning_nh \
                or self.num_attention_heads % self.num_key_value_heads \
                or self.attn_use_rope or not (
                    self.lightning_use_rope and self.qk_norm
                    and self.use_output_norm and self.use_output_gate
                    and self.attn_use_output_gate):
            raise ValueError("this model's lightning layers have a key a "
                             "head, rotary, q/k norm, output norm and gate; "
                             "its attention layers a gate and no rotary")

    @property
    def layers(self) -> Tuple[int, ...]:
        """The published indices of the layers that run."""
        return tuple(range(*(self.layers_run
                             or (0, self.num_hidden_layers))))

    def layers_of(self, mixer: str) -> Tuple[int, ...]:
        return tuple(l for l in self.layers if self.mixer_types[l] == mixer)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.num_hidden_layers)

    @classmethod
    def from_published(cls, published: Dict[str, Any], **changed):
        """From a ``config.json``'s keys; those this model has no use for
        (``model_type``, ``mup_denominator``: an initialisation, ...) are
        passed over."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in published.items() if k in names}
        if "torch_dtype" in published:
            kw["dtype"] = published["torch_dtype"]
        kw.update(changed)
        return cls(**kw)

    @classmethod
    def debug(cls, **changed):
        """The CPU tests' size: both mixers, nothing wide; rows select
        once their context passes 32 tokens."""
        kw = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                  num_hidden_layers=4, mixer_types=(_S, _L, _L, _S),
                  num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                  lightning_nh=4, lightning_nkv=4, lightning_head_dim=8,
                  dim_model_base=16, max_position_embeddings=256,
                  kernel_size=4, kernel_stride=2, block_size=4, topk=4,
                  init_blocks=1, window_size=6, dense_len=32,
                  dtype="float32")
        kw.update(changed)
        return cls(**kw)

    def rope_tables(self):
        """None to speak of: the lightning layers rotate by the row's
        position (``_rotary``), the attention layers not at all."""
        z = jnp.zeros((1, 1), jnp.float32)
        return z, z

    def paged_layout(self):
        c = self
        kvh, d = c.num_key_value_heads, c.head_dim
        H, dl = c.lightning_nh, c.lightning_head_dim
        # the least row count that is whole tiles of every kernel of the
        # step: the scores', the block-sparse kernel's, the dense walk's
        # and the scan's (128 at the published widths: the scan's)
        tile_rows = math.lcm(
            bsa.SCORES_TILE_ROWS, bsa.SPARSE_TILE_ROWS,
            ragged_tile_rows(c.num_attention_heads, kvh, d), SCAN_TILE_ROWS)

        def row_counts(rows, ctx_tokens, page_size, pages_per_seq):
            # a layer's: the attention rows by path, what the selecting
            # rows score (summed over rows, and once a slot) and select,
            # the tokens selected and those the kernel's copies fetch
            # (the selected blocks, each once a row and group); the
            # scan's rows and the states it reads and writes
            n, slot = rows[:, 3], rows[:, 4]
            picks = n > c.dense_len
            sparse = int(picks.sum())
            blocks = sparse * kvh * c.topk
            nck = np.where(picks, np.maximum(n // c.kernel_stride - 1, 0), 0)
            # the compressed keys a slot's rows score, once a slot
            per_slot = np.zeros(int(slot.max(initial=-1)) + 1, np.int64)
            np.maximum.at(per_slot, slot, nck)
            return {"sparse_rows": sparse, "dense_rows": len(rows) - sparse,
                    "ckey_ctx": int(nck.sum()),
                    "ckey_slot_ctx": int(per_slot.sum()),
                    "sel_blocks": blocks,
                    "sel_kv_tokens": blocks * c.block_size,
                    "sel_kv_tokens_read": blocks * c.block_size,
                    "state_rows": len(rows),
                    "state_slots": len(np.unique(rows[:, 4]))}

        return PagedLayout(
            name="kv", rows=((kvh, d), (kvh, d)), step=unified_step_jit,
            row_counts=row_counts,
            count_names=("kv_ctx_tokens", "sparse_rows", "dense_rows",
                         "ckey_ctx", "ckey_slot_ctx", "sel_blocks",
                         "sel_kv_tokens", "sel_kv_tokens_read",
                         "state_rows", "state_slots"),
            pages_per_step=lambda page, pps, itemsize: default_pages_per_step(
                page, kvh, d, pps, itemsize),
            tile_rows=tile_rows,
            kinds=(PageKind("pages", c.layers_of(_S)),),
            more_pools=(lambda page: (page // c.kernel_stride, kvh * d),),
            state=(((H, dl, dl), "float32"),),
            state_layers=len(c.layers_of(_L)),
            state_snapshots_a_step=SNAPSHOTS_A_STEP)

    def leaf_shapes(self) -> Dict[str, tuple]:
        """Every leaf of the functional state this model reads, by name
        (Linear weights ``[in, out]``), the layers under their published
        indices."""
        c = self
        h, f = c.hidden_size, c.intermediate_size
        out = {"model.embed_tokens.weight": (c.vocab_size, h),
               "model.norm.weight": (h,), "lm_head.weight": (h, c.vocab_size)}
        for l in c.layers:
            p = f"model.layers.{l}."
            a = p + "self_attn."
            out.update({p + "input_layernorm.weight": (h,),
                        p + "post_attention_layernorm.weight": (h,),
                        p + "mlp.gate_proj.weight": (h, f),
                        p + "mlp.up_proj.weight": (h, f),
                        p + "mlp.down_proj.weight": (f, h)})
            if c.mixer_types[l] == _L:
                q, d = c.lightning_nh * c.lightning_head_dim, \
                    c.lightning_head_dim
                out.update({a + "q_proj.weight": (h, q),
                            a + "k_proj.weight": (h, q),
                            a + "v_proj.weight": (h, q),
                            a + "q_norm.weight": (d,),
                            a + "k_norm.weight": (d,),
                            a + "o_norm.weight": (q,),
                            a + "o_gate.weight": (h, q),
                            a + "o_proj.weight": (q, h)})
            else:
                q = c.num_attention_heads * c.head_dim
                kv = c.num_key_value_heads * c.head_dim
                out.update({a + "q_proj.weight": (h, q),
                            a + "k_proj.weight": (h, kv),
                            a + "v_proj.weight": (h, kv),
                            a + "o_gate.weight": (h, q),
                            a + "o_proj.weight": (q, h)})
        return out


def lightning_decay(cfg, l: int):
    """``log lambda`` of each head of published layer ``l``, float32
    ``[H]``: Lightning Attention's slopes, scaled down the depth."""
    H = cfg.lightning_nh
    slope = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    depth = 1.0 - l / (cfg.num_hidden_layers - 1) + 1e-5
    return jnp.asarray(-slope * depth, jnp.float32)


def _rotary(x, pos, theta: float):
    """Float32 ``x`` ``[T, H, d]`` rotated by its row's position (the
    whole head, halves paired: ``generation._apply_rope``'s layout, the
    angles computed here)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    return x * cos + _rotate_half(x) * sin


def _mlp(cfg, w, l, x):
    with jax.named_scope("mlp"):
        u = _rms_norm(x, w.layer(l, "post_attention_layernorm.weight"),
                      cfg.rms_norm_eps)
        return x + (cfg.residual_scale * _ffn(w, l, u)).astype(x.dtype)


def lightning_part(cfg, w, l, x, pool, slot, lens, src, dst, max_slots: int):
    """Published layer ``l``'s Lightning mixer on the packed rows ``x``
    ``[T, hidden]``: each slot's rows start from state entry ``src``
    (below zero: zeros) and leave the state in entry ``dst``.  Returns
    ``(x + mixer, pool)``."""
    T = x.shape[0]
    H, d = cfg.lightning_nh, cfg.lightning_head_dim
    at = "self_attn."
    with jax.named_scope("lightning_qkv"):
        u = _rms_norm(x, w.layer(l, "input_layernorm.weight"),
                      cfg.rms_norm_eps)
        q = (u @ w.layer(l, at + "q_proj.weight")).reshape(T, H, d)
        k = (u @ w.layer(l, at + "k_proj.weight")).reshape(T, H, d)
        v = (u @ w.layer(l, at + "v_proj.weight")).reshape(T, H, d)
        pos = jnp.maximum(lens - 1, 0)
        q = _rotary(_rms_norm(q.astype(jnp.float32),
                              w.layer(l, at + "q_norm.weight"),
                              cfg.rms_norm_eps), pos, cfg.rope_theta)
        k = _rotary(_rms_norm(k.astype(jnp.float32),
                              w.layer(l, at + "k_norm.weight"),
                              cfg.rms_norm_eps), pos, cfg.rope_theta)
        q, k = q.astype(x.dtype), k.astype(x.dtype)
    with jax.named_scope("ssd_scan"):
        ones = jnp.ones((T, H), jnp.float32)
        a = jnp.broadcast_to(lightning_decay(cfg, l)[None, :], (T, H))
        if pallas_interpret():
            y, pool = ssd_scan_reference(v, ones, a, k, q, pool, slot, src,
                                         dst)
        else:
            tile = SCAN_TILE_ROWS
            pad = -T % tile

            def rows(z, fill=0):
                """Whole tiles: the rows past the end belong to no slot."""
                return jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1),
                               constant_values=fill)

            trash = pool.shape[0] - 1
            y, pool = mamba2_ssd_scan(
                rows(v), rows(ones), rows(a), rows(k), rows(q), pool,
                rows(slot, -1), rows(lens), rows(src, trash),
                rows(dst, trash), tile_rows=tile,
                max_units=ssd_max_units(T + pad, tile, max_slots),
                heads_per_step=min(SCAN_HEADS_A_STEP, H))
            y = y[:T]
        y = y * d ** -0.5
    with jax.named_scope("lightning_out"):
        o = _rms_norm(y.reshape(T, H * d), w.layer(l, at + "o_norm.weight"),
                      cfg.rms_norm_eps)
        gate = jax.nn.sigmoid((u @ w.layer(l, at + "o_gate.weight")
                               ).astype(jnp.float32))
        out = (gate * o).astype(x.dtype) @ w.layer(l, at + "o_proj.weight")
        x = x + (cfg.residual_scale * out).astype(x.dtype)
    return x, pool


def sparse_attention_part(cfg, w, l, x, k_pool, v_pool, c_pool, phys, off,
                          lens, slot, table, pages_per_step: int):
    """Published layer ``l``'s InfLLM-V2 attention on the packed rows: K
    and V rows written at (``phys``, ``off``), the compressed keys they
    finish written to ``c_pool``, then dense attention for the rows whose
    context is at most ``dense_len`` and the selection and block-sparse
    attention for the others.  Returns ``(x + mixer, k pool, v pool,
    compressed keys' pool, the rows' selections [T, kvh, topk])``."""
    T = x.shape[0]
    h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    at = "self_attn."
    interpret = pallas_interpret()
    with jax.named_scope("attn_qkv"):
        u = _rms_norm(x, w.layer(l, "input_layernorm.weight"),
                      cfg.rms_norm_eps)
        q = (u @ w.layer(l, at + "q_proj.weight")).reshape(T, h, d)
        k = (u @ w.layer(l, at + "k_proj.weight")).reshape(T, kvh, d)
        v = (u @ w.layer(l, at + "v_proj.weight")).reshape(T, kvh, d)
    with jax.named_scope("kv_scatter"):
        k_pool = _write_kv_rows(k_pool, phys, off, k)
        v_pool = _write_kv_rows(v_pool, phys, off, v)
    with jax.named_scope("ckey_write"):
        c_pool = bsa.write_compressed_keys(
            c_pool, k_pool, lens, slot, table, stride=cfg.kernel_stride,
            max_final=min(T, table.shape[0] + -(-T // cfg.kernel_stride)))
    sparse = (slot >= 0) & (lens > cfg.dense_len)
    with jax.named_scope("paged_attn"):
        dense = ragged_paged_decode_raw(
            q, k_pool, v_pool, jnp.where(sparse, 0, lens),
            jnp.where(sparse, -1, slot), table, scale=d ** -0.5,
            pages_per_step=pages_per_step)
    with jax.named_scope("block_select"):
        qs = (q.astype(jnp.float32) * d ** -0.5).astype(c_pool.dtype)
        ck = bsa.gather_compressed(c_pool, table)
        nck = jnp.where(sparse, bsa.compressed_count(lens, cfg.kernel_stride),
                        0)
        if interpret:
            scores = bsa.block_scores_reference(qs, ck, slot, nck)
        else:
            scores = bsa.infllm_block_scores(
                qs, ck, slot, nck,
                max_units=min(T, table.shape[0]
                              + -(-T // bsa.SCORES_TILE_ROWS)))
        sel = bsa.select_blocks(
            scores, lens, stride=cfg.kernel_stride, block=cfg.block_size,
            topk=cfg.topk, init_blocks=cfg.init_blocks,
            window=cfg.window_size)
    with jax.named_scope("sparse_attn"):
        attend = bsa.block_sparse_attention_reference if interpret \
            else bsa.block_sparse_paged_attention
        picked = attend(qs, k_pool, v_pool, sel, lens, slot, table, sparse,
                        block=cfg.block_size)
        ctx = jnp.where(sparse[:, None, None], picked.astype(dense.dtype),
                        dense)
    with jax.named_scope("attn_out"):
        gate = jax.nn.sigmoid((u @ w.layer(l, at + "o_gate.weight")
                               ).astype(jnp.float32))
        out = (gate * ctx.reshape(T, h * d).astype(jnp.float32)
               ).astype(x.dtype) @ w.layer(l, at + "o_proj.weight")
        x = x + (cfg.residual_scale * out).astype(x.dtype)
    return x, k_pool, v_pool, c_pool, sel


@partial(jax.jit, static_argnames=("self_cfg_id", "pages_per_step",
                                   "with_head"),
         donate_argnames=("k_pages", "v_pages", "state", "pools"))
def unified_step_jit(params, k_pages, v_pages, rows, tables, cos_tab,
                     sin_tab, self_cfg_id, pages_per_step, kv_scales=None,
                     with_head=True, gather=None, prev_tokens=None,
                     state=None, pools=None):
    """This model's part of the engine's ONE ragged step, under
    ``PagedLayout.step``'s contract (``inference/paged_layout.py``).
    Its own: ``k_pages`` / ``v_pages`` are the pools of the ``minicpm4``
    layers alone, in their order, ``pools`` ``(the compressed keys'
    pools,)`` of the same layers; ``state`` is ``(S pools,)``, one
    ``[entries, H, d, d]`` pool a ``lightning-attn`` layer; ``rows``
    ``[T, 8]``.  Returns ``(k pools, v pools, (logits, tokens,
    selections), state, pools)``; ``selections`` ``[gathered rows,
    minicpm4 layers, kvh, topk]``: the blocks each gathered row selected
    (``engine.last_extras``; a check holds the attention to them where
    bf16 and float32 order near-tied blocks differently)."""
    cfg, _, _ = _CFGS[self_cfg_id]
    w = _Weights(cfg, params)
    (table,) = tables
    new_k, new_v = list(k_pages), list(v_pages)
    new_c, pool = list(pools[0]), list(state[0])
    # the scopes are ``profiler.device_trace.DEVICE_SCOPES``
    with jax.named_scope("embed"):
        tok, phys, off, lens, slot, src, dst, snap = row_columns(
            rows, prev_tokens)
        lens = jnp.where(slot < 0, 0, lens)
        x = w.embed(tok)
        x = (x.astype(jnp.float32) * cfg.scale_emb).astype(x.dtype)
    snaps = snapshot_plan(snap, dst, pool[0].shape[0] - 1)
    n_attn = n_state = 0
    sels = []
    for l in cfg.layers:
        if cfg.mixer_types[l] == _L:
            x, pool[n_state] = lightning_part(
                cfg, w, l, x, pool[n_state], slot, lens, src, dst,
                table.shape[0])
            pool[n_state] = copy_snapshots(pool[n_state], snaps)
            n_state += 1
        else:
            x, new_k[n_attn], new_v[n_attn], new_c[n_attn], sel = \
                sparse_attention_part(
                    cfg, w, l, x, new_k[n_attn], new_v[n_attn],
                    new_c[n_attn], phys, off, lens, slot, table,
                    pages_per_step)
            sels.append(sel)
            n_attn += 1
        x = _mlp(cfg, w, l, x)
    state, pools = (tuple(pool),), (tuple(new_c),)
    if not with_head:
        return tuple(new_k), tuple(new_v), None, state, pools
    def final_norm(y):
        y = _rms_norm(y, w["model.norm.weight"], cfg.rms_norm_eps)
        return (y.astype(jnp.float32)
                / (cfg.hidden_size / cfg.dim_model_base)).astype(y.dtype)

    with jax.named_scope("lm_head"):
        sels = jnp.stack(sels, axis=1)
    logits, sels = gathered_logits(x, gather, final_norm, w.head, (sels,))
    with jax.named_scope("sample"):
        out = (logits, sample_greedy(logits), sels)
    return tuple(new_k), tuple(new_v), out, state, pools
