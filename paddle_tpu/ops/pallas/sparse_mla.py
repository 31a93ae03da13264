"""Learned sparse attention over a paged LATENT cache (Pallas TPU
kernels): the two device-heavy parts of a DeepSeek-V3.2 layer inside the
serving engine's unified ragged step.

- ``lightning_index_scores_raw``: the lightning indexer's scores of
  every packed query row over its sequence's paged index keys,
  ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``.  The per-head
  products live only in VMEM (one ``[heads, keys]`` tile at a time);
  what reaches HBM is one fp32 score a (row, position).
- ``select_top_k``: the row's k-th largest score, found by 32 counting
  passes over the scores' bits (plain XLA; a sort of 25k scores a row
  costs far more).  ``score >= kth`` IS the selection of the top k
  (with a cut position for ties at the k-th score).
- ``sparse_mla_attention_raw``: absorbed multi-head latent attention
  (128 query heads against ONE shared latent row a token, whose value
  is the first ``dv`` lanes of its key) over the row's whole paged
  context under that mask: the "masked" spelling of
  selected attention, which walks pages in order and never gathers the
  selected rows (PERF.md section 6, PR 26 has the microbenchmark
  against the gather).

Both kernels share one shape: the grid is the packed ROWS alone and each
row walks its own pages in a loop of DYNAMIC length with double-buffered
manual copies, ``pages_per_step`` pages a turn.  A grid of (rows, page
blocks) costs a grid step for every block a row COULD have (528 rows x
49 blocks of a 24k context is 9 ms of empty steps a kernel a layer);
here a padding row costs one step and a decode row at position 300 one
turn.  The heads are the matmul's rows (64 or 128 against one page of
keys), which makes the walk a row efficient for latent attention;
``decode_attention.ragged_paged_decode_raw`` (4 query heads a KV head)
walks the same way since PR 27, a TILE of one slot's rows a walk.

Layouts: index keys ``[pages, page, di]``, latents ``[pages, page,
dl]`` with ``dl`` a multiple of 128 (the 512 latent + 64 rotary numbers
of a token padded to 640: 576 is not a multiple of the 128-lane tile,
and a device array of minor size 576 is padded to 640 in memory
anyway).  One page id names a page in both pools.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.device import pallas_interpret

from .flash_attention import NEG_INF, _sds

INDEX_SCORES_KERNEL = "lightning_index_scores"
SPARSE_MLA_KERNEL = "sparse_mla_attention"


def _page_walk(tab_ref, slot, pool_hbm, buf, sem, pp: int, max_pages: int):
    """``start(block, half)`` / ``wait(half)`` of the double-buffered
    copies of one block of ``pp`` pages of ``slot``'s sequence.  A page
    past the table's width repeats the last one (its rows are masked by
    the caller's visibility test)."""
    def start(blk, half):
        for j in range(pp):
            page = tab_ref[slot, jnp.minimum(blk * pp + j, max_pages - 1)]
            pltpu.make_async_copy(pool_hbm.at[jnp.maximum(page, 0)],
                                  buf.at[half, j], sem.at[half, j]).start()

    def wait(half):
        for j in range(pp):
            pltpu.make_async_copy(pool_hbm.at[0], buf.at[half, j],
                                  sem.at[half, j]).wait()

    return start, wait


def _index_scores_kernel(lens_ref, slot_ref, tab_ref, q_ref, w_ref,
                         pool_hbm, o_ref, buf, sem, *, page: int, pp: int,
                         max_pages: int):
    r = pl.program_id(0)
    n = lens_ref[r]
    nk = pp * page
    nblk = (n + nk - 1) // nk
    start, wait = _page_walk(tab_ref, slot_ref[r], pool_hbm, buf, sem, pp,
                             max_pages)
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(nblk > 0)
    def _():
        start(0, 0)

    q = q_ref[0]                                  # [heads, di]
    w = w_ref[0]                                  # [heads, 1] fp32

    def body(blk, carry):
        half = blk % 2
        wait(half)

        @pl.when(blk + 1 < nblk)
        def _():
            start(blk + 1, 1 - half)

        k = buf[half].reshape(nk, q.shape[-1])    # [keys, di]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        row = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
        pos = blk * nk + lax.broadcasted_iota(jnp.int32, row.shape, 1)
        o_ref[0, pl.ds(blk, 1), :] = jnp.where(pos < n, row, -jnp.inf)
        return carry

    lax.fori_loop(0, nblk, body, 0)


def lightning_index_scores_raw(q, w, key_pool, row_lens, row_slot,
                               block_tables, pages_per_step: int = 8,
                               interpret=None):
    """Index scores of packed query rows over their sequences' paged
    index keys.  q ``[T, heads, di]`` (the pool's dtype), w ``[T,
    heads]`` fp32 head weights, key_pool ``[pages, page, di]``,
    row_lens ``[T]`` visibility (position + 1; 0 for a padding row),
    row_slot ``[T]`` page-table row, block_tables ``[slots,
    max_pages]``.  Returns fp32 ``[T, W]``, ``W`` = the table's width
    in tokens rounded up to whole blocks; ``-inf`` at and past the
    row's visibility.  Products accumulate in fp32."""
    T, h, di = q.shape
    page = key_pool.shape[1]
    max_pages = block_tables.shape[1]
    pp = max(1, min(int(pages_per_step), max_pages))
    nblocks = -(-max_pages // pp)
    nk = pp * page
    if interpret is None:
        interpret = pallas_interpret()
    lens = jnp.where(row_slot < 0, 0, row_lens).astype(jnp.int32)
    slots = jnp.maximum(row_slot.astype(jnp.int32), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, h, di), lambda r, l, s, t: (r, 0, 0)),
            pl.BlockSpec((1, h, 1), lambda r, l, s, t: (r, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, nblocks, nk), lambda r, l, s, t: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pp, page, di), key_pool.dtype),
            pltpu.SemaphoreType.DMA((2, pp)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, page=page, pp=pp,
                          max_pages=max_pages),
        grid_spec=grid_spec,
        out_shape=_sds((T, nblocks, nk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=INDEX_SCORES_KERNEL,
        interpret=interpret,
    )(lens, slots, block_tables.astype(jnp.int32), q.astype(key_pool.dtype),
      w.astype(jnp.float32)[:, :, None], key_pool)
    return out.reshape(T, nblocks * nk)


def _sortable_bits(x):
    """fp32 -> uint32 whose unsigned order is the floats' order."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest(scores, k: int):
    """The k-th largest of each row of fp32 ``scores`` ``[T, W]``
    (``-inf`` where fewer than k are finite, and always where
    ``W <= k``).  Built bit by bit from the top: 32 counting passes, no
    sort."""
    T, W = scores.shape
    if W <= k:
        return jnp.full((T,), -jnp.inf, jnp.float32)
    u = _sortable_bits(scores)

    def body(i, ans):
        cand = ans | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        cnt = jnp.sum((u >= cand[:, None]).astype(jnp.int32), axis=1)
        return jnp.where(cnt >= k, cand, ans)

    ans = lax.fori_loop(0, 32, body, jnp.zeros((T,), jnp.uint32))
    b = lax.bitcast_convert_type(ans ^ jnp.uint32(0x80000000), jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return lax.bitcast_convert_type(b, jnp.float32)


def select_top_k(scores, k: int):
    """The selection of each row's k largest scores as two numbers a
    row, fp32 ``[T, 2]``: position ``s`` is selected iff ``score[s] >
    thr``, or ``score[s] == thr`` and ``s <= cut``.  ``thr`` is the k-th
    largest score; ``cut`` settles ties at ``thr`` in favour of the
    LOWER position (``lax.top_k``'s rule), and is the row's width where
    no tie has to be cut, which is every row of real 64-head scores:
    the cumulative count that finds it runs only if some row needs it
    (few-head test sizes, where a score is exactly 0 whenever every
    head's product is negative)."""
    T, W = scores.shape
    thr = kth_largest(scores, k)
    no_cut = jnp.full((T,), float(W), jnp.float32)
    if W <= k:
        return jnp.stack([thr, no_cut], axis=1)
    live = thr > -jnp.inf
    at_least = jnp.sum((scores >= thr[:, None]).astype(jnp.int32), axis=1)

    def cut_ties():
        above = jnp.sum((scores > thr[:, None]).astype(jnp.int32), axis=1)
        tie = scores == thr[:, None]
        seen = jnp.cumsum(tie.astype(jnp.int32), axis=1)
        pos = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        last = jnp.max(jnp.where(tie & (seen <= (k - above)[:, None]), pos,
                                 -1), axis=1)
        return jnp.where(live, last.astype(jnp.float32), no_cut)

    cut = lax.cond(jnp.any(live & (at_least > k)), cut_ties, lambda: no_cut)
    return jnp.stack([thr, cut], axis=1)


def selected_mask(scores, sel, row_lens):
    """The selection as a boolean ``[T, W]`` (what the attention kernel
    applies block by block); for tests and the reference's comparison."""
    pos = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    thr, cut = sel[:, :1], sel[:, 1:2]
    keep = (scores > thr) | ((scores == thr) & (pos.astype(jnp.float32) <= cut))
    return keep & (pos < row_lens[:, None])


def _sparse_mla_kernel(lens_ref, slot_ref, tab_ref, q_ref, sc_ref, sel_ref,
                       pool_hbm, o_ref, buf, sem, m_scr, l_scr, acc_scr, *,
                       page: int, pp: int, max_pages: int, dv: int):
    r = pl.program_id(0)
    n = lens_ref[r]
    nk = pp * page
    nblk = (n + nk - 1) // nk
    start, wait = _page_walk(tab_ref, slot_ref[r], pool_hbm, buf, sem, pp,
                             max_pages)
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
    l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
    acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    @pl.when(nblk > 0)
    def _():
        start(0, 0)

    q = q_ref[0]                                  # [heads, dl]
    thr = sel_ref[0][:, :1]                       # [1, 1] fp32
    cut = sel_ref[0][:, 1:2]

    def body(blk, carry):
        half = blk % 2
        wait(half)

        @pl.when(blk + 1 < nblk)
        def _():
            start(blk + 1, 1 - half)

        kv = buf[half].reshape(nk, q.shape[-1])   # [keys, dl]
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        idx = sc_ref[0, pl.ds(blk, 1), :]         # [1, keys] index scores
        pos = blk * nk + lax.broadcasted_iota(jnp.int32, idx.shape, 1)
        keep = ((idx > thr) | ((idx == thr) & (pos.astype(jnp.float32) <= cut))
                ) & (pos < n)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a block with no kept key leaves m at NEG_INF: exp(0) rows of
        # ones would count masked keys, so the mask is applied to p too
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        # p is exactly 0 on a masked key, and a pool holds only finite
        # numbers (zeros until written), so stale rows add nothing
        v = kv[:, :dv]
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        return carry

    lax.fori_loop(0, nblk, body, 0)
    l = l_scr[:, :1]
    o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def sparse_mla_attention_raw(q, latent_pool, index_scores, selection,
                             row_lens, row_slot, block_tables, dv: int,
                             pages_per_step: int = 8, interpret=None):
    """Absorbed latent attention of packed query rows over their
    sequences' paged latents, each row attending the positions below
    its visibility that ``selection`` (``select_top_k``'s two numbers a
    row) selects by their index scores.

    q ``[T, heads, dl]`` (softmax scale folded in; the pool's dtype),
    latent_pool ``[pages, page, dl]``, index_scores ``[T, W]`` as
    ``lightning_index_scores_raw`` returns them (the same
    ``pages_per_step``), selection ``[T, 2]`` fp32.  Returns ``[T, heads,
    dv]``: the softmax-weighted sum of the first ``dv`` lanes of the
    attended latent rows (zeros for a padding row)."""
    T, h, dl = q.shape
    page = latent_pool.shape[1]
    max_pages = block_tables.shape[1]
    pp = max(1, min(int(pages_per_step), max_pages))
    nblocks = -(-max_pages // pp)
    nk = pp * page
    if index_scores.shape != (T, nblocks * nk):
        raise ValueError(f"index scores {index_scores.shape}, expected "
                         f"{(T, nblocks * nk)}: another pages_per_step?")
    if interpret is None:
        interpret = pallas_interpret()
    lens = jnp.where(row_slot < 0, 0, row_lens).astype(jnp.int32)
    slots = jnp.maximum(row_slot.astype(jnp.int32), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, h, dl), lambda r, l, s, t: (r, 0, 0)),
            pl.BlockSpec((1, nblocks, nk), lambda r, l, s, t: (r, 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda r, l, s, t: (r, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, dv), lambda r, l, s, t: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pp, page, dl), latent_pool.dtype),
            pltpu.SemaphoreType.DMA((2, pp)),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_sparse_mla_kernel, page=page, pp=pp,
                          max_pages=max_pages, dv=int(dv)),
        grid_spec=grid_spec,
        out_shape=_sds((T, h, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=SPARSE_MLA_KERNEL,
        interpret=interpret,
    )(lens, slots, block_tables.astype(jnp.int32),
      q.astype(latent_pool.dtype),
      index_scores.reshape(T, nblocks, nk),
      selection.astype(jnp.float32).reshape(T, 1, 2), latent_pool)
