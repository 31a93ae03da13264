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

Three kernels share that online softmax.  ``flash_decode_raw`` (dense
cache) and ``paged_decode_raw`` (paged cache, one query row a sequence:
the legacy chunked serving path) have the grid described above, the
page indirection of the second in its index maps.
``ragged_paged_decode_raw`` (the unified serving step: decode rows,
verify windows and prefill chunks of many sequences packed into one
launch) has NOT: a grid of (rows, page blocks) costs a grid trip for
every block a row COULD have, whatever the step carries, and re-reads a
sequence's pages for every row of its chunk.  Its grid is the query
tiles alone; inside a tile each run of one sequence's rows walks that
sequence's pages once, in a loop of dynamic length with manual
double-buffered copies, so its cost follows the live rows and the pages
they can see (PERF.md section 6, PR 27).
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
# the same kernel over a layer that attends the last ``window`` positions
RAGGED_PAGED_WINDOW_KERNEL = "ragged_paged_attention_window"


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


def _paged_decode_kernel(*refs, page: int, pp: int, scale: float):
    """Paged online-softmax decode body iterating ``pp`` physical pages
    per grid step.  The per-page k/v refs were DMA'd independently by
    ``pp`` scalar-prefetch index maps (ragged page iteration fused into
    the block pipeline); the kernel walks them in order, updating the
    same fp32 VMEM online-softmax state the dense kernel uses.  Decode
    blocks are tiny, so per-grid-step overhead dominates — folding pp
    pages into one step recovers the dense kernel's ~512-token window
    (measured r4/r5: 64-128 token pages paid ~3x the dense kernel's
    grid overhead).  Two scalar-prefetch operands (seq_lens, tables)
    ride ahead of q; the body reads only the first."""
    seq_ref = refs[0]
    q_ref = refs[2]
    k_refs = refs[3:3 + pp]
    v_refs = refs[3 + pp:3 + 2 * pp]
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


def paged_decode_raw(q, key_cache, value_cache, seq_lens, block_tables,
                     scale=None, interpret=None, pages_per_step=None,
                     name=PAGED_DECODE_KERNEL):
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

    ``pages_per_step``: physical pages per grid step (left unset,
    ``default_pages_per_step``: a ~512-token window per step, the dense
    kernel's block size, under a VMEM budget).
    ``name`` is the kernel's name in the compiled program."""
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
    if pages_per_step is None:
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
        name=name,
        interpret=interpret,
    )(seq, tables, qg, *([key_cache] * pp), *([value_cache] * pp))
    return out[:, :, :rep].reshape(b, h, d)


# sublanes of a query tile: the MXU's rows.  A tile of ``tile_rows``
# packed rows stacks ``tile_rows * rep`` (row, query head) pairs of one
# KV head on the sublane axis
_RAGGED_TILE_SUBLANES = 128
# the narrow window a short run (a decode row, a verify window) computes
# on instead of the whole tile, so that a step of decode rows from many
# slots is bound by their K/V bytes and not by a tile's softmax each
_RAGGED_SHORT_SUBLANES = 32
# sublane alignment of a window's start: bf16's (16, 128) tile
_RAGGED_ALIGN = 16
# VMEM the tile's own blocks may take (q, out, softmax state) beside the
# double-buffered K/V pages (_PAGED_VMEM_BUDGET)
_RAGGED_TILE_VMEM = 4 * 1024 * 1024


def _padded_rep(rep: int) -> int:
    """Query heads a KV head, padded so that a row's heads never
    straddle a window's alignment: a power of two up to the alignment,
    whole alignments above."""
    if rep >= _RAGGED_ALIGN:
        return -(-rep // _RAGGED_ALIGN) * _RAGGED_ALIGN
    return 1 << (rep - 1).bit_length()


def ragged_tile_rows(h: int, kvh: int, d: int) -> int:
    """Packed rows a query tile of the ragged kernel: as many as fill
    the MXU's 128 rows with the ``rep`` query heads of a KV head,
    halved while the tile's q and output (double-buffered, reckoned at
    four bytes) and fp32 softmax state (all KV heads ride in the tile)
    pass their VMEM budget."""
    rp = _padded_rep(h // kvh)
    per_sublane = kvh * (2 * 128 * 4 + d * 4 + 2 * 2 * d * 4)
    m = _RAGGED_TILE_SUBLANES
    while m > max(rp, _RAGGED_ALIGN) and m * per_sublane > _RAGGED_TILE_VMEM:
        m //= 2
    return max(1, m // rp)


def ragged_units(row_slot, row_lens, tile_rows: int, xp, low: bool = False):
    """The ragged kernel's units of work, from the packed rows alone.

    A unit is a maximal run of consecutive live rows (slot >= 0) of ONE
    slot inside ONE query tile of ``tile_rows`` rows: the kernel walks
    that slot's pages once for all of the unit's rows, as far as the
    largest visibility among them.  Returns ``(count, reach)``, int32
    ``[T]`` each: at a unit's FIRST row the unit's row count and that
    largest visibility, 0 at every other row; with ``low`` also the
    unit's SMALLEST visibility (a window layer's walk starts at the page
    that holds the lowest position any of its rows attends).  ``xp`` is
    ``numpy`` (the engine's packing counts what a step's walk reads) or
    ``jax.numpy`` (the kernel's wrapper): one definition for both."""
    T = row_slot.shape[0]
    idx = xp.arange(T)
    live = row_slot >= 0
    prev = xp.concatenate([xp.full((1,), -1, row_slot.dtype), row_slot[:-1]])
    first = live & ((row_slot != prev) | (idx % tile_rows == 0))
    uid = xp.where(live, xp.cumsum(first), 0)     # 1.. a unit, 0 not live
    lens = xp.where(live, row_lens, 0)
    tail = xp.zeros((tile_rows - 1,), uid.dtype)
    # a unit never leaves its tile, so the tile_rows rows from its first
    # hold all of it
    win = xp.arange(tile_rows)[:, None] + idx[None, :]
    same = xp.concatenate([uid, tail])[win] == uid[None]
    reach = xp.where(same, xp.concatenate([lens, tail.astype(lens.dtype)]
                                          )[win], 0).max(0)
    count = same.sum(0)
    out = (xp.where(first, count, 0).astype(xp.int32),
           xp.where(first, reach, 0).astype(xp.int32))
    if not low:
        return out
    lens_win = xp.concatenate([lens, tail.astype(lens.dtype)])[win]
    lowest = xp.where(same, lens_win, xp.iinfo(xp.int32).max).min(0)
    return (*out, xp.where(first, lowest, 0).astype(xp.int32))


def _ragged_paged_kernel(*refs, page: int, pp: int, rp: int, tile_rows: int,
                         short: int, scale: float, window=None):
    """One query tile of the ragged kernel: every unit of work of the
    tile (``ragged_units``) walks its slot's pages in a loop of dynamic
    length, ``pp`` pages a turn, copying the next turn's pages (or the
    next unit's first) while it computes on this turn's.  The online
    softmax of a (row, page) is ``_paged_decode_kernel``'s.

    With ``window`` (a layer that attends the last ``window`` positions)
    one more prefetched scalar a unit, ``first_ref``, is the page its
    walk STARTS at: the one holding the lowest position any of its rows
    attends.  No table entry under it is read and no page under it
    copied (the engine has given those pages back), and a row masks
    below ``visibility - window``."""
    if window is None:
        (slot_ref, cnt_ref, reach_ref, tab_ref, live_ref, q_ref, vis_ref,
         k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr) = refs
        first_ref = None
    else:
        (slot_ref, cnt_ref, reach_ref, first_ref, tab_ref, live_ref, q_ref,
         vis_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_scr, l_scr,
         acc_scr) = refs
    M = tile_rows * rp
    row0 = pl.program_id(0) * tile_rows
    # one past the launch's last live row: tiles past it have no work
    end = jnp.minimum(row0 + tile_rows, live_ref[0])
    n_rows = cnt_ref.shape[0]

    def page_at(p0, blk, j):
        """Index into the slot's table of page ``j`` of turn ``blk`` of
        a walk that starts at page ``p0`` (None: at the table's first)."""
        return blk * pp + j if p0 is None else p0 + blk * pp + j

    def first_of(row):
        return None if first_ref is None else first_ref[row]

    def copies(slot, reach, p0, blk, half):
        """The turn's page copies, for ``start`` and ``wait`` alike;
        only the pages that hold a position under the unit's reach."""
        out = []
        for j in range(pp):
            idx = page_at(p0, blk, j)
            need = idx * page < reach
            phys = jnp.maximum(tab_ref[slot, idx], 0)
            out.append((need, [
                pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[half, j],
                                      sem.at[half, 0, j]),
                pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[half, j],
                                      sem.at[half, 1, j])]))
        return out

    def start(slot, reach, p0, blk, half):
        for need, pair in copies(slot, reach, p0, blk, half):
            @pl.when(need)
            def _(pair=pair):
                for c in pair:
                    c.start()

    def wait(slot, reach, p0, blk, half):
        for need, pair in copies(slot, reach, p0, blk, half):
            @pl.when(need)
            def _(pair=pair):
                for c in pair:
                    c.wait()

    def walk(lo, n, slot, reach, p0, nblk, nxt, has_next, half, w0, W):
        """All turns of one unit on the window of ``W`` sublanes at
        ``w0``; returns the buffer half the next unit starts in."""
        win = pl.ds(w0, W)
        q = q_ref[:, win, :]                              # [kvh, W, d]
        # a row of the window attends below its own visibility; rows of
        # other units in the window attend nothing here
        r = row0 + (w0 + jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)) // rp
        vis = jnp.where((r >= lo) & (r < lo + n), vis_ref[win, :], 0)[None]

        def turn(blk, half):
            wait(slot, reach, p0, blk, half)

            @pl.when(blk + 1 < nblk)
            def _():
                start(slot, reach, p0, blk + 1, 1 - half)

            @pl.when((blk + 1 == nblk) & has_next)
            def _():
                start(slot_ref[nxt], reach_ref[nxt], first_of(nxt), 0,
                      1 - half)

            for j in range(pp):
                first = page_at(p0, blk, j) * page

                def compute(j=j, first=first):
                    k = kbuf[half, j]                     # [kvh, page, d]
                    if k.dtype == jnp.int8:
                        # int8 KV: half the HBM stream; dequant scales
                        # are folded into q / the output by the callers
                        k = k.astype(q.dtype)
                    s = jax.lax.dot_general(
                        q, k, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32) * scale
                    kpos = first + jax.lax.broadcasted_iota(
                        jnp.int32, (1, 1, page), 2)
                    seen = kpos < vis                     # [1, W, page]
                    if window is not None:
                        seen = seen & (kpos >= vis - window)
                    s = jnp.where(seen, s, NEG_INF)
                    m_prev = m_scr[:, win, :1]
                    m_new = jnp.maximum(
                        m_prev, jnp.max(s, axis=-1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    # a row that sees nothing of this page (another
                    # unit's, or an earlier row of a chunk) may still be
                    # at NEG_INF, where exp(s - m) is 1: mask p as well
                    p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
                    l_new = (l_scr[:, win, :1] * alpha
                             + jnp.sum(p, axis=-1, keepdims=True))
                    v = vbuf[half, j]
                    if v.dtype == jnp.int8:
                        v = v.astype(q.dtype)
                    # positions past the reach carry whatever the pool
                    # holds (p there is 0, but 0 * inf/nan would poison
                    # acc): zero them
                    rpos = first + jax.lax.broadcasted_iota(
                        jnp.int32, v.shape, 1)
                    v = jnp.where(rpos < reach, v, jnp.zeros_like(v))
                    acc_scr[:, win, :] = (
                        acc_scr[:, win, :] * alpha + jax.lax.dot_general(
                            p.astype(v.dtype), v,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32))
                    m_scr[:, win, :] = jnp.broadcast_to(
                        m_new, (m_new.shape[0], W, m_scr.shape[2]))
                    l_scr[:, win, :] = jnp.broadcast_to(
                        l_new, (l_new.shape[0], W, l_scr.shape[2]))

                # pages wholly past the reach were not copied
                pl.when(first < reach)(compute)
            return 1 - half

        return jax.lax.fori_loop(0, nblk, turn, half)

    def unit(lo, n, half, started):
        slot, reach, p0 = slot_ref[lo], reach_ref[lo], first_of(lo)
        if p0 is None:
            nblk = (reach + pp * page - 1) // (pp * page)
        else:
            nblk = (reach - p0 * page + pp * page - 1) // (pp * page)
        nxt = jnp.minimum(lo + n, n_rows - 1)
        has_next = (lo + n < end) & (cnt_ref[nxt] > 0)

        @pl.when(started == 0)
        def _():
            start(slot, reach, p0, 0, half)

        args = (lo, n, slot, reach, p0, nblk, nxt, has_next, half)
        if short:
            # a short run computes on a narrow aligned window
            w0 = ((lo - row0) * rp // _RAGGED_ALIGN) * _RAGGED_ALIGN
            w0 = jnp.minimum(w0, M - short)
            fits = (lo - row0 + n) * rp - w0 <= short
            half = jax.lax.cond(
                fits,
                lambda: walk(*args, pl.multiple_of(w0, _RAGGED_ALIGN),
                             short),
                lambda: walk(*args, 0, M))
        else:
            half = walk(*args, 0, M)
        return half, (has_next & (nblk > 0)).astype(jnp.int32)

    @pl.when(row0 >= end)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(row0 < end)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def body(c):
            lo, half, started = c
            n = cnt_ref[lo]
            half, started = jax.lax.cond(
                n > 0, lambda: unit(lo, n, half, started),
                lambda: (half, jnp.int32(0)))       # a padding row
            return lo + jnp.maximum(n, 1), half, started

        jax.lax.while_loop(lambda c: c[0] < end, body,
                           (row0, jnp.int32(0), jnp.int32(0)))
        l = l_scr[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        valid = m_scr[:, :, :1] > NEG_INF * 0.5
        o_ref[...] = jnp.where(valid, acc_scr[...] / l, 0.0
                               ).astype(o_ref.dtype)


def ragged_paged_decode_raw(q, key_cache, value_cache, row_lens, row_slot,
                            block_tables, scale=None, interpret=None,
                            pages_per_step=None, tile_rows=None,
                            window=None):
    """Ragged paged flash attention: the serving plane's unified
    prefill+decode step (the Ragged Paged Attention kernel shape,
    PAPERS.md 2604.15464).

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
      the decode contract); positions past the table's width do not
      exist (lookahead scheduling may run a slot past capacity);
    - ``block_tables`` [slots, max_pages] int32 physical page ids.

    The cost follows the live rows.  The grid is the query TILES alone
    (``ragged_tile_rows`` packed rows each, the ``rep`` query heads of a
    KV head stacked on the sublanes: 32 rows x 4 heads are the MXU's 128
    rows).  Inside a tile every UNIT of work (``ragged_units``: a run of
    consecutive rows of one slot) walks that slot's pages ONCE for all
    of its rows, in a loop of dynamic length as far as the unit's
    largest visibility, ``pages_per_step`` pages a turn with manual
    double-buffered copies (``sparse_mla._walk_tile``'s idea, for two
    pools); each row masks by its own visibility.  So a prefill chunk
    of 256 rows reads its context 8 times and not 256, a tile with no
    live row returns at once without a copy, and the engine's packing
    (live rows first, a slot's rows contiguous) puts every padding row
    in such tiles.  A short run (a decode row, a verify window) computes
    on a narrow window of the tile, so a step of decode rows from many
    slots is bound by their bytes.  Rows may come in any order and with
    any visibilities: the units are found from the ``slot`` column on
    the device, once a step (every layer's launch shares them).

    ``window=W`` is a layer that attends the last ``W`` positions: row r
    attends ``row_lens[r] - W <= position < row_lens[r]``.  A unit's
    walk then starts at the page that holds the lowest position any of
    its rows attends; no table entry under that page is read and no page
    under it copied, so those entries may name pages that have been
    given back.  The launch is named ``ragged_paged_attention_window``
    in a compiled program and a device trace."""
    T, h, d = q.shape
    kvh, page = key_cache.shape[1], key_cache.shape[2]
    if h % kvh != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = pallas_interpret()
    if not interpret and d % 128:
        # Mosaic copies by hand only whole lanes of the minor dimension
        # (a pool of head dim 64 is padded to 128 lanes in HBM and a
        # slice of 64 is refused), so such pools cannot be walked inside
        # the kernel: a row goes as a sequence of its own through the
        # grid kernel, pages by its index maps.  Interpret mode takes
        # the walk at every head dim
        if window is not None:
            raise ValueError(
                f"a window layer of head dim {d} cannot be walked on the "
                f"chip: the walk copies whole 128-lane rows")
        live = row_slot >= 0
        return paged_decode_raw(
            q, key_cache, value_cache, jnp.where(live, row_lens, 0),
            block_tables[jnp.maximum(row_slot, 0)], scale=scale,
            interpret=interpret, pages_per_step=pages_per_step,
            name=RAGGED_PAGED_KERNEL)
    max_pages = block_tables.shape[1]
    if pages_per_step is None:
        pages_per_step = default_pages_per_step(
            page, kvh, d, max_pages, jnp.dtype(key_cache.dtype).itemsize)
    pp = max(1, min(int(pages_per_step), max_pages))
    if tile_rows is None:
        tile_rows = ragged_tile_rows(h, kvh, d)
    # a launch smaller than a tile is one tile of its own size (whole
    # alignments of sublanes): the same units of work, less padding
    whole = max(1, _RAGGED_ALIGN // _padded_rep(h // kvh))
    tile_rows = min(int(tile_rows), -(-T // whole) * whole)
    if window is not None and int(window) < 1:
        raise ValueError(f"window {window!r}: a layer attends the last "
                         f"window >= 1 positions")
    return _ragged_walk(q, key_cache, value_cache, row_lens, row_slot,
                        block_tables, scale=float(scale),
                        interpret=bool(interpret), pp=pp, tq=int(tile_rows),
                        window=None if window is None else int(window))


# jitted on its own: a step's launches (one a layer) are then ONE traced
# and lowered function called sixteen times, not sixteen kernels traced
# and lowered one by one (5.7 s of a 16-layer step's lowering otherwise)
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "pp", "tq",
                                             "window"))
def _ragged_walk(q, key_cache, value_cache, row_lens, row_slot, block_tables,
                 *, scale, interpret, pp, tq, window=None):
    T, h, d = q.shape
    kvh, page = key_cache.shape[1], key_cache.shape[2]
    rep = h // kvh
    rp = _padded_rep(rep)
    itemsize = jnp.dtype(key_cache.dtype).itemsize
    max_pages = block_tables.shape[1]
    M = tq * rp
    short = _RAGGED_SHORT_SUBLANES \
        if rp <= _RAGGED_ALIGN and M >= 2 * _RAGGED_SHORT_SUBLANES else 0
    n_tiles = -(-T // tq)
    Tp = n_tiles * tq

    slots = jnp.pad(row_slot.astype(jnp.int32), (0, Tp - T),
                    constant_values=-1)
    # a padding row sees nothing; a position past the table's width is
    # none
    lens = jnp.minimum(jnp.pad(row_lens.astype(jnp.int32), (0, Tp - T)),
                       max_pages * page)
    lens = jnp.where(slots < 0, 0, lens)
    if window is None:
        count, reach = ragged_units(slots, lens, tq, jnp)
        units = (slots, count, reach)
        tab_pad = -max_pages % pp
    else:
        count, reach, lowest = ragged_units(slots, lens, tq, jnp, low=True)
        # the page a unit's walk starts at; its turns may run up to pp - 1
        # entries past the table's padded width (never copied: past reach)
        units = (slots, count, reach, jnp.maximum(lowest - window, 0) // page)
        tab_pad = -max_pages % pp + pp
    live_end = jnp.max(jnp.where(slots >= 0, jnp.arange(Tp) + 1, 0),
                       keepdims=True).astype(jnp.int32)
    # the table's width padded to whole turns, so that a turn's page
    # index never leaves it (the padding is never copied: past the reach)
    tables = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, tab_pad)))
    # [kvh, rows x heads, d]: a tile's rows of one KV head are one
    # matmul operand
    qg = jnp.pad(q.reshape(T, kvh, rep, d),
                 ((0, Tp - T), (0, 0), (0, rp - rep), (0, 0)))
    qg = qg.transpose(1, 0, 2, 3).reshape(kvh, Tp * rp, d)
    vis = jnp.repeat(lens, rp)[:, None]                   # [Tp * rp, 1]

    def tile(i, *_):
        return (0, i, 0)

    # K/V pages of two turns, the tile's q/out (double-buffered by the
    # pipeline) and softmax state, a page's fp32 scores and weights
    resident = (2 * 2 * pp * kvh * page * d * itemsize
                + kvh * M * (2 * 128 * 4 + d * 4
                             + 2 * 2 * d * jnp.dtype(q.dtype).itemsize)
                + 3 * kvh * M * page * 4)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(units) + 2,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((kvh, M, d), tile),
            pl.BlockSpec((M, 1), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((kvh, M, d), tile),
        scratch_shapes=[
            pltpu.VMEM((2, pp, kvh, page, d), key_cache.dtype),
            pltpu.VMEM((2, pp, kvh, page, d), value_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2, pp)),
            pltpu.VMEM((kvh, M, 128), jnp.float32),   # m (lane-replicated)
            pltpu.VMEM((kvh, M, 128), jnp.float32),   # l
            pltpu.VMEM((kvh, M, d), jnp.float32),     # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_paged_kernel, page=page, pp=pp, rp=rp,
                          tile_rows=tq, short=short, scale=scale,
                          window=window),
        grid_spec=grid_spec,
        out_shape=_sds((kvh, Tp * rp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 << 20, 2 * resident)),
        name=(RAGGED_PAGED_KERNEL if window is None
              else RAGGED_PAGED_WINDOW_KERNEL),
        interpret=interpret,
    )(*units, tables, live_end, qg, vis, key_cache, value_cache)
    out = out.reshape(kvh, Tp, rp, d)[:, :T, :rep]
    return out.transpose(1, 0, 2, 3).reshape(T, h, d)


# framework op registration (forward-only inference ops)
from ..registry import register  # noqa: E402


@register("flash_decoding", amp="white")
def flash_decoding_op(q, k_cache, v_cache, seq_lens, scale=None):
    return flash_decode_raw(q, k_cache, v_cache, seq_lens, scale=scale)


@register("paged_flash_decoding", amp="white")
def paged_flash_decoding_op(q, key_cache, value_cache, seq_lens,
                            block_tables, scale=None,
                            pages_per_step=None):
    return paged_decode_raw(q, key_cache, value_cache, seq_lens,
                            block_tables, scale=scale,
                            pages_per_step=pages_per_step)


@register("ragged_paged_flash_decoding", amp="white")
def ragged_paged_flash_decoding_op(q, key_cache, value_cache, row_lens,
                                   row_slot, block_tables, scale=None,
                                   pages_per_step=None, window=None):
    return ragged_paged_decode_raw(q, key_cache, value_cache, row_lens,
                                   row_slot, block_tables, scale=scale,
                                   pages_per_step=pages_per_step,
                                   window=window)
