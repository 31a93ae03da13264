"""Flash-decoding (Pallas TPU kernel): single-token decode attention
against a dense KV cache.

TPU-native replacement for the reference's LLM-serving decode kernels
(paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu,
block_multi_head_attention; Python entry
python/paddle/incubate/nn/functional/masked_multihead_attention.py).
The GPU kernel's job is bandwidth: stream the whole KV cache once per
step.  The TPU design mirrors that:

- layout: the GQA group's ``rep = h // kvh`` query heads are stacked on
  the sublane axis (padded to 8) and ALL kv heads of a sequence ride in
  one grid step as a batched dot_general — grid (b, k_blocks) rather
  than (b*kvh, k_blocks).  Decode tiles are tiny, so per-grid-step
  overhead dominates; batching the head axis into the block cut measured
  step count 8x (v5e: 257us -> ~70us at 12% fill);
- k innermost ("arbitrary") with online softmax in fp32 VMEM scratch,
  exactly like the training flash kernel;
- per-sequence length drives BOTH the compute gate (@pl.when skips the
  MXU work of blocks past ``seq_len``) AND the DMA: the k/v BlockSpec
  index maps read ``seq_lens`` via scalar prefetch and CLAMP the block
  index to the last valid block, so consecutive grid steps revisit the
  same block and Mosaic elides the copy.  HBM traffic scales with the
  *actual* sequence length, not the cache capacity — the flash-decoding
  property that makes a 1k-token decode against an 8k cache ~8x cheaper;
- forward-only (decode is inference; the reference kernel has no grad).

Shapes: q [b, h, d]; k_cache/v_cache [b, kvh, t_max, d]; seq_lens [b]
int32 = number of valid cache rows (attend positions < seq_lens).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.device import pallas_interpret

from .flash_attention import NEG_INF, _sds

# The kernels' names in a compiled program and a device trace (the
# benchmark's readers find a kernel's events by them): one place.
FLASH_DECODE_KERNEL = "flash_decode_attention"
PAGED_DECODE_KERNEL = "paged_decode_attention"
RAGGED_PAGED_KERNEL = "ragged_paged_attention"


def _decode_kernel(*refs, block_k: int, scale: float):
    """Online-softmax decode body for the DENSE cache layout (the paged
    variant lives in _paged_decode_kernel, which iterates several
    physical pages per grid step)."""
    seq_ref = refs[0]
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs[-7:]
    bi = pl.program_id(0)                   # batch
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    slen = seq_ref[bi]

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0]                        # [kvh, rp, d]
        k = k_ref[0]                        # [kvh, block_k, d]
        if k.dtype == jnp.int8:
            # int8 KV cache: HALF the HBM traffic of bf16 on this
            # bandwidth-bound kernel; the per-head dequant scales are
            # folded into q (k side) and the output (v side) by the
            # callers, so the kernel only widens the streamed block
            # (reference: block_multi_head_attention_kernel.cu
            # cachekv_quant path)
            k = k.astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [kvh, rp, BK]
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        s = jnp.where(kpos < slen, s, NEG_INF)
        m_prev = m_scr[:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)              # [kvh, rp, BK]
        l_new = l_scr[:, :, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0]                        # [kvh, BK, d]
        if v.dtype == jnp.int8:
            v = v.astype(q.dtype)
        # rows past slen carry whatever the cache holds (p there is 0,
        # but 0 * inf/nan would poison acc) — zero them
        rpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        v = jnp.where(rpos < slen, v, jnp.zeros_like(v))
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # blocks entirely past the sequence end skip the MXU work (their DMA
    # was already elided by the clamped index map)
    pl.when(ki * block_k < slen)(compute)

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        valid = m_scr[:, :, :1] > NEG_INF * 0.5
        o_ref[0] = jnp.where(valid, acc_scr[:] / l, 0.0).astype(o_ref.dtype)


def flash_decode_raw(q, k_cache, v_cache, seq_lens, scale=None,
                     block_k: int = 512, interpret=None):
    """One decode step of attention.  q [b, h, d]; k_cache/v_cache
    [b, kvh, t_max, d] (kvh divides h, heads group-major as in the
    training flash kernel's _kv_index); seq_lens [b] int32.  Returns
    out [b, h, d].  The new token's k/v must already be written into the
    cache (slot seq_lens-1) — cache update is a host-side scatter, the
    kernel only streams."""
    b, h, d = q.shape
    kvh, t_max = k_cache.shape[1], k_cache.shape[2]
    if h % kvh != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = pallas_interpret()
    rep = h // kvh
    rp = -(-rep // 8) * 8                   # sublane-pad the head group
    # the whole head axis rides in one block, so the k/v block footprint
    # is kvh * block_k * d — scale block_k down for wide-head (MHA)
    # caches to keep the double-buffered k+v pipeline inside VMEM
    # (~2MB per block -> <=8MB resident)
    budget = 2 * 1024 * 1024
    fit = budget // max(1, kvh * d * jnp.dtype(k_cache.dtype).itemsize)
    block_k = max(128, min(block_k, (fit // 128) * 128))
    block_k = min(block_k, -(-t_max // 128) * 128)
    nk = pl.cdiv(t_max, block_k)

    qg = q.reshape(b, kvh, rep, d)
    if rp != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rp - rep), (0, 0)))
    seq = seq_lens.astype(jnp.int32)

    def kv_map(bi, ki, seq_ref):
        # clamp to the last block holding valid rows: out-of-range grid
        # steps revisit it, Mosaic elides the repeated DMA
        last = jnp.maximum((seq_ref[bi] + block_k - 1) // block_k - 1, 0)
        return (bi, 0, jnp.minimum(ki, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, kvh, rp, d), lambda bi, ki, s: (bi, 0, 0, 0)),
            pl.BlockSpec((1, kvh, block_k, d), kv_map),
            pl.BlockSpec((1, kvh, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, rp, d),
                               lambda bi, ki, s: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, rp, 128), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((kvh, rp, 128), jnp.float32),  # l
            pltpu.VMEM((kvh, rp, d), jnp.float32),    # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=_sds((b, kvh, rp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=FLASH_DECODE_KERNEL,
        interpret=interpret,
    )(seq, qg, k_cache, v_cache)
    return out[:, :, :rep].reshape(b, h, d)


def _paged_decode_kernel(*refs, page: int, pp: int, scale: float,
                         nsp: int = 2):
    """Paged online-softmax decode body iterating ``pp`` physical pages
    per grid step.  The per-page k/v refs were DMA'd independently by
    ``pp`` scalar-prefetch index maps (ragged page iteration fused into
    the block pipeline); the kernel walks them in order, updating the
    same fp32 VMEM online-softmax state the dense kernel uses.  Decode
    blocks are tiny, so per-grid-step overhead dominates — folding pp
    pages into one step recovers the dense kernel's ~512-token window
    (measured r4/r5: 64-128 token pages paid ~3x the dense kernel's
    grid overhead).

    ``nsp`` is the number of scalar-prefetch operands ahead of q: 2 for
    the per-sequence layout (seq_lens, tables), 3 for the ragged
    per-row layout (row_lens, row_slot, tables) — the body itself only
    ever reads refs[0] (the per-grid-row visibility length), so both
    layouts share it."""
    seq_ref = refs[0]
    q_ref = refs[nsp]
    k_refs = refs[nsp + 1:nsp + 1 + pp]
    v_refs = refs[nsp + 1 + pp:nsp + 1 + 2 * pp]
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    bi = pl.program_id(0)
    gi = pl.program_id(1)
    ng = pl.num_programs(1)
    slen = seq_ref[bi]

    @pl.when(gi == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                            # [kvh, rp, d]
    for j in range(pp):
        start = (gi * pp + j) * page

        def compute(j=j, start=start):
            k = k_refs[j][0]                # [kvh, page, d]
            if k.dtype == jnp.int8:
                # int8 KV: half the HBM stream; dequant scales are folded
                # into q / the output by the callers
                k = k.astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(kpos < slen, s, NEG_INF)
            m_prev = m_scr[:, :, :1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = (l_scr[:, :, :1] * alpha
                     + jnp.sum(p, axis=-1, keepdims=True))
            v = v_refs[j][0]
            if v.dtype == jnp.int8:
                v = v.astype(q.dtype)
            rpos = start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            v = jnp.where(rpos < slen, v, jnp.zeros_like(v))
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

        # sub-blocks entirely past the live length skip the MXU work
        # (their DMA was already elided by the clamped index maps)
        pl.when(start < slen)(compute)

    @pl.when(gi == ng - 1)
    def _():
        l = l_scr[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        valid = m_scr[:, :, :1] > NEG_INF * 0.5
        o_ref[0] = jnp.where(valid, acc_scr[:] / l, 0.0).astype(o_ref.dtype)


# VMEM budget for the resident paged k+v blocks (double-buffered by the
# pipeline): bounds pages_per_step for large page x head configs
_PAGED_VMEM_BUDGET = 8 * 1024 * 1024
# token window one grid step should cover — the dense kernel's default
# block_k, where per-step overhead stops dominating (v5e measured)
_PAGED_TARGET_WINDOW = 512


def default_pages_per_step(page: int, kvh: int, d: int, max_pages: int,
                           itemsize: int = 2) -> int:
    """Heuristic pp: cover ~_PAGED_TARGET_WINDOW tokens per grid step,
    capped by the page count and the double-buffered VMEM budget."""
    pp = max(1, _PAGED_TARGET_WINDOW // max(page, 1))
    pp = min(pp, max_pages)
    blk = 2 * 2 * page * kvh * d * itemsize      # k+v, double-buffered
    while pp > 1 and pp * blk > _PAGED_VMEM_BUDGET:
        pp //= 2
    return max(1, pp)


def tune_pages_per_step(b, kvh, page, d, max_pages, dtype=jnp.bfloat16):
    """Measure paged_decode_raw across pages-per-step candidates for this
    serving shape (cached per signature; ops/autotune.py pattern).
    Returns the heuristic default when autotune is off or on CPU."""
    from .. import autotune as _at

    default = default_pages_per_step(page, kvh, d, max_pages,
                                     jnp.dtype(dtype).itemsize)
    key = ("paged_pages_per_step", b, kvh, page, d, max_pages, str(dtype))
    cached = _at.AutoTuneCache.instance().lookup(key)
    if cached is not None:
        return cached
    if not _at.enabled() or pallas_interpret():
        return default

    npages = b * max_pages
    kc = jnp.zeros((npages, kvh, page, d), dtype)
    vc = jnp.zeros((npages, kvh, page, d), dtype)
    tables = jnp.arange(npages, dtype=jnp.int32).reshape(b, max_pages)
    qx = jnp.ones((b, kvh, d), dtype)
    lens = jnp.full((b,), (max_pages * page) // 2, jnp.int32)

    def measure(pp):
        return _at.time_fn(lambda: jax.block_until_ready(
            paged_decode_raw(qx, kc, vc, lens, tables, pages_per_step=pp)))

    cands = sorted({p for p in (1, 2, 4, 8)
                    if p <= max_pages} | {default})
    return _at.AutoTuneCache.instance().tune(key, cands, measure)


def paged_decode_raw(q, key_cache, value_cache, seq_lens, block_tables,
                     scale=None, interpret=None, pages_per_step="auto"):
    """Paged (vLLM-layout) flash decode: q [b, h, d]; key/value_cache
    [n_blocks, kvh, page, d]; seq_lens [b] (valid tokens, INCLUDING the
    current one — the caller writes the new token's K/V into its page
    slot first); block_tables [b, max_pages] int32 physical page ids
    (-1 for unused slots).

    The page indirection lives in the BlockSpec index maps: each grid
    step DMAs ``pages_per_step`` physical pages straight from HBM via
    independent scalar-prefetch-driven index maps — ragged page
    iteration fused into the kernel's block pipeline; no gathered
    [b, pages, ...] copy of the cache is ever materialised (the XLA
    fallback's cost).  Pages past seq_len clamp to the last valid page
    (DMA elided) and their compute is skipped, so both HBM traffic AND
    grid-step count are bounded by the live lengths, not capacity.

    ``pages_per_step``: physical pages per grid step ("auto" targets a
    ~512-token window per step — the dense kernel's block size — under
    a VMEM budget; serving pre-tunes it via tune_pages_per_step)."""
    b, h, d = q.shape
    kvh, page = key_cache.shape[1], key_cache.shape[2]
    if h % kvh != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = pallas_interpret()
    rep = h // kvh
    rp = -(-rep // 8) * 8
    max_pages = block_tables.shape[1]
    if pages_per_step == "auto":
        pages_per_step = default_pages_per_step(
            page, kvh, d, max_pages, jnp.dtype(key_cache.dtype).itemsize)
    pp = max(1, min(int(pages_per_step), max_pages))
    ng = -(-max_pages // pp)

    qg = q.reshape(b, kvh, rep, d)
    if rp != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rp - rep), (0, 0)))
    seq = seq_lens.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)

    def kv_map(j):
        def _map(bi, gi, seq_ref, tab_ref):
            # clamp to the last page holding valid rows (and to the table
            # width — lookahead scheduling may run a slot past capacity):
            # out-of-range steps revisit it and Mosaic elides the DMA
            last = jnp.maximum((seq_ref[bi] + page - 1) // page - 1, 0)
            last = jnp.minimum(last, max_pages - 1)
            phys = tab_ref[bi, jnp.minimum(gi * pp + j, last)]
            return (jnp.maximum(phys, 0), 0, 0, 0)
        return _map

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, ng),
        in_specs=(
            [pl.BlockSpec((1, kvh, rp, d), lambda bi, gi, s, t: (bi, 0, 0, 0))]
            + [pl.BlockSpec((1, kvh, page, d), kv_map(j)) for j in range(pp)]
            + [pl.BlockSpec((1, kvh, page, d), kv_map(j)) for j in range(pp)]
        ),
        out_specs=pl.BlockSpec((1, kvh, rp, d),
                               lambda bi, gi, s, t: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, rp, 128), jnp.float32),
            pltpu.VMEM((kvh, rp, 128), jnp.float32),
            pltpu.VMEM((kvh, rp, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page, pp=pp,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=_sds((b, kvh, rp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=PAGED_DECODE_KERNEL,
        interpret=interpret,
    )(seq, tables, qg, *([key_cache] * pp), *([value_cache] * pp))
    return out[:, :, :rep].reshape(b, h, d)


def ragged_paged_decode_raw(q, key_cache, value_cache, row_lens, row_slot,
                            block_tables, scale=None, interpret=None,
                            pages_per_step="auto"):
    """Ragged paged flash attention: the serving plane's unified
    prefill+decode step (the Ragged Paged Attention kernel shape,
    PAPERS.md 2604.15464), built as a per-ROW generalization of
    ``paged_decode_raw``'s scalar-prefetch index maps.

    q [T, h, d] is a PACKED array of query tokens from MANY sequences in
    one launch: decode slots contribute one row each (q_len=1), prefill
    chunks contribute a row per prompt token (q_len=chunk), speculative
    verify contributes q_len=k+1 rows.  Per row:

    - ``row_slot`` [T] int32 — which sequence (page-table row) the token
      belongs to (<0 = padding row, output forced to zero);
    - ``row_lens`` [T] int32 — causal visibility: row r attends cache
      positions < row_lens[r] of its sequence (for a token at absolute
      position p this is p+1, so a prefill chunk's rows each see the
      shared prefix plus the chunk tokens at or before themselves —
      their K/V must already be scattered into the pages, exactly like
      the decode contract);
    - ``block_tables`` [slots, max_pages] int32 physical page ids.

    The page indirection happens in the index maps: grid step (r, g)
    DMAs ``pages_per_step`` physical pages of row r's sequence via
    ``tab_ref[row_slot[r], ...]`` — the same clamp-to-last-valid-page
    trick bounds both HBM traffic and compute by each ROW's visibility,
    so a decode row costs one tiny step regardless of how many prefill
    rows share the launch (the property that makes mixing chunked
    prefill into the decode batch latency-safe).  Per-row grid steps
    keep the decode rows' cost identical to ``paged_decode_raw``;
    prefill rows pay one grid trip per row (the RPA paper's fused
    multi-row q tiles are the TPU follow-on once chunk shapes are
    pinned)."""
    T, h, d = q.shape
    kvh, page = key_cache.shape[1], key_cache.shape[2]
    if h % kvh != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = pallas_interpret()
    rep = h // kvh
    rp = -(-rep // 8) * 8
    max_pages = block_tables.shape[1]
    if pages_per_step == "auto":
        pages_per_step = default_pages_per_step(
            page, kvh, d, max_pages, jnp.dtype(key_cache.dtype).itemsize)
    pp = max(1, min(int(pages_per_step), max_pages))
    ng = -(-max_pages // pp)

    qg = q.reshape(T, kvh, rep, d)
    if rp != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rp - rep), (0, 0)))
    lens = row_lens.astype(jnp.int32)
    # padding rows (slot < 0) clamp to table row 0 with visibility 0:
    # their DMA still lands somewhere valid, their output is forced to 0
    lens = jnp.where(row_slot < 0, 0, lens)
    slots = jnp.maximum(row_slot.astype(jnp.int32), 0)
    tables = block_tables.astype(jnp.int32)

    def kv_map(j):
        def _map(ri, gi, lens_ref, slot_ref, tab_ref):
            # clamp to the row's last VISIBLE page: grid steps past it
            # revisit the same page and Mosaic elides the DMA, so a
            # decode row never streams a prefill row's page span
            last = jnp.maximum((lens_ref[ri] + page - 1) // page - 1, 0)
            last = jnp.minimum(last, max_pages - 1)
            phys = tab_ref[slot_ref[ri], jnp.minimum(gi * pp + j, last)]
            return (jnp.maximum(phys, 0), 0, 0, 0)
        return _map

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T, ng),
        in_specs=(
            [pl.BlockSpec((1, kvh, rp, d),
                          lambda ri, gi, l, s, t: (ri, 0, 0, 0))]
            + [pl.BlockSpec((1, kvh, page, d), kv_map(j)) for j in range(pp)]
            + [pl.BlockSpec((1, kvh, page, d), kv_map(j)) for j in range(pp)]
        ),
        out_specs=pl.BlockSpec((1, kvh, rp, d),
                               lambda ri, gi, l, s, t: (ri, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, rp, 128), jnp.float32),
            pltpu.VMEM((kvh, rp, 128), jnp.float32),
            pltpu.VMEM((kvh, rp, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page, pp=pp,
                          scale=float(scale), nsp=3),
        grid_spec=grid_spec,
        out_shape=_sds((T, kvh, rp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=RAGGED_PAGED_KERNEL,
        interpret=interpret,
    )(lens, slots, tables, qg, *([key_cache] * pp), *([value_cache] * pp))
    return out[:, :, :rep].reshape(T, h, d)


# framework op registration (forward-only inference ops)
from ..registry import register  # noqa: E402


@register("flash_decoding", amp="white")
def flash_decoding_op(q, k_cache, v_cache, seq_lens, scale=None):
    return flash_decode_raw(q, k_cache, v_cache, seq_lens, scale=scale)


@register("paged_flash_decoding", amp="white")
def paged_flash_decoding_op(q, key_cache, value_cache, seq_lens,
                            block_tables, scale=None,
                            pages_per_step="auto"):
    return paged_decode_raw(q, key_cache, value_cache, seq_lens,
                            block_tables, scale=scale,
                            pages_per_step=pages_per_step)


@register("ragged_paged_flash_decoding", amp="white")
def ragged_paged_flash_decoding_op(q, key_cache, value_cache, row_lens,
                                   row_slot, block_tables, scale=None,
                                   pages_per_step="auto"):
    return ragged_paged_decode_raw(q, key_cache, value_cache, row_lens,
                                   row_slot, block_tables, scale=scale,
                                   pages_per_step=pages_per_step)
