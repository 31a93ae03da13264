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

Both kernels share one shape and one walk (``_walk_tile``): the grid
is TILES of packed rows (``sparse_tile_rows``: 8 at DeepSeek's widths),
and inside a tile every UNIT of work, a run of consecutive live rows of
one slot (``decode_attention.ragged_units``, the definition that
kernel's wrapper and the engine's counts share since PR 27), walks that
slot's pages ONCE for all its rows, in a loop of DYNAMIC length as far
as the largest visibility among them, ``pages_per_step`` pages a turn
with double-buffered manual copies.  The unit's rows x heads are the
matmul's rows against the turn's keys, held in VMEM once; each row
keeps its own visibility, index scores and cut, so the mask is a row's
and every position below a row's visibility is scored as when a row
walked alone.  A 512-row prefill chunk fetches its document 64 times a
layer, not 512; a decode row is a unit of one and computes on its row
alone, so a step of decode rows from many slots pays a row's arithmetic
a row; a tile with no live row returns at once.  A grid of (rows, page
blocks) would cost a grid step for every block a row COULD have (528
rows x 49 blocks of a 24k context is 9 ms of empty steps a kernel a
layer).  The model's layout counts what a step's walk fetches
(``attn_kv_tokens_read``) from the same units and ``walk_geometry``.

Layouts: index keys ``[pages, page, di]``, latents ``[pages, page,
dl]`` with ``dl`` a multiple of 128 (the 512 latent + 64 rotary numbers
of a token padded to 640: 576 is not a multiple of the 128-lane tile,
and a device array of minor size 576 is padded to 640 in memory
anyway).  One page id names a page in both pools.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.device import pallas_interpret

from .decode_attention import ragged_units
from .flash_attention import NEG_INF, _sds

INDEX_SCORES_KERNEL = "lightning_index_scores"
SPARSE_MLA_KERNEL = "sparse_mla_attention"

# the scope the kernels ask for: a tile's blocks and temporaries
# (``_TILE_VMEM``) beside two turns of pages, with room for the compiler's own
_VMEM_LIMIT = 64 * 1024 * 1024
# VMEM a tile's compute may take: the fp32 scores and weights of one
# block of keys (``rows x heads x keys`` each, and a bf16 copy of the
# weights for the second product), the tile's q and output blocks
# (double-buffered by the pipeline), its fp32 accumulator and softmax
# state and its rows' index scores
_TILE_VMEM = 24 * 1024 * 1024
# most rows a tile: past the MXU's appetite more rows only cost VMEM
_TILE_ROWS_MAX = 8
# keys of one block of a turn's arithmetic (a turn COPIES ``pages_per_step``
# pages; its scores are taken a block at a time so that their fp32 tile
# stays inside ``_TILE_VMEM``)
_BLOCK_KEYS = 1024


def sparse_tile_rows(heads: int, d_in: int, d_out: int = 0,
                     max_rows: int = _TILE_ROWS_MAX) -> int:
    """Packed rows a tile of a kernel whose row brings ``heads`` query
    heads of ``d_in`` numbers and takes ``d_out`` a head away: as many
    as ``max_rows``, halved while a row's share of the VMEM (its
    heads are the matmul's rows) passes ``_TILE_VMEM``.  A layer gives
    both its kernels the attention's tile (128 heads of 640 in, 512
    out), so a step's units of work are the same in both and one count
    says what either walk fetched."""
    per_row = heads * (_BLOCK_KEYS * (4 + 4 + 2)      # scores, weights
                       + 2 * 2 * d_in + 2 * 2 * d_out  # q and out blocks
                       + 4 * d_out + 2 * 128 * 4)     # acc, m and l
    rows = max_rows
    while rows > 1 and rows * per_row > _TILE_VMEM:
        rows //= 2
    return rows


def walk_geometry(page: int, max_pages: int, pages_per_step: int):
    """``(pages a turn, keys a turn, turns the table's width takes)`` of
    the page walk both kernels share."""
    pp = max(1, min(int(pages_per_step), max_pages))
    return pp, pp * page, -(-max_pages // pp)


def _block_pages(pp: int, page: int) -> int:
    """Pages of one block of a turn's arithmetic: the most that divide
    the turn's ``pp`` and hold no more than ``_BLOCK_KEYS`` keys."""
    return max(d for d in range(1, pp + 1)
               if pp % d == 0 and (d == 1 or d * page <= _BLOCK_KEYS))


def _walk_tile(row0, slot_ref, cnt_ref, reach_ref, tab_ref, live_ref,
               pool_hbm, buf, sem, turn, *, tile_rows: int, page: int,
               pp: int, max_pages: int):
    """The tile of packed rows from ``row0``: every unit of work of the tile
    (``ragged_units``: a run of consecutive live rows of one slot) walks
    that slot's pages ONCE, ``pp`` pages a turn as far as the unit's
    reach, copying the next turn's pages (or the next unit's first)
    while ``turn(i0, rows, lo, n, blk, half)`` computes on this turn's:
    on the ``rows`` rows of the tile from its row ``i0``, of which those
    from ``lo`` to ``lo + n`` (tile-relative) are the unit's.  A unit of
    ONE row computes on that row alone (``rows`` 1), any other on the
    whole tile: a step of decode rows from many slots pays a row's
    arithmetic a row, as when the grid was the rows.  A page past the
    table's width repeats the last one (its positions are masked by the
    rows' visibility)."""
    nk = pp * page
    # one past the launch's last live row: tiles past it have no work
    end = jnp.minimum(row0 + tile_rows, live_ref[0])
    n_rows = cnt_ref.shape[0]
    # pools that share the page id (``dense_mla.py`` walks two), each
    # with its own buffer and semaphores
    if not isinstance(pool_hbm, tuple):
        pool_hbm, buf, sem = (pool_hbm,), (buf,), (sem,)

    def start(slot, blk, half):
        for j in range(pp):
            phys = tab_ref[slot, jnp.minimum(blk * pp + j, max_pages - 1)]
            for pool, b, s in zip(pool_hbm, buf, sem):
                pltpu.make_async_copy(pool.at[jnp.maximum(phys, 0)],
                                      b.at[half, j], s.at[half, j]).start()

    def wait(half):
        for j in range(pp):
            for pool, b, s in zip(pool_hbm, buf, sem):
                pltpu.make_async_copy(pool.at[0], b.at[half, j],
                                      s.at[half, j]).wait()

    def walk(lo, n, slot, nblk, nxt, has_next, half, i0, rows):
        def body(blk, half):
            wait(half)
            more = blk + 1 < nblk

            # this unit's next turn, or the next unit's first: ONE set
            # of copies to issue a turn, whichever it is
            @pl.when(more | has_next)
            def _():
                start(jnp.where(more, slot, slot_ref[nxt]),
                      jnp.where(more, blk + 1, 0), 1 - half)

            turn(i0, rows, lo - row0, n, blk, half)
            return 1 - half

        return lax.fori_loop(0, nblk, body, half)

    def unit(lo, n, half, started):
        slot = slot_ref[lo]
        nblk = (reach_ref[lo] + nk - 1) // nk
        nxt = jnp.minimum(lo + n, n_rows - 1)
        has_next = (lo + n < end) & (cnt_ref[nxt] > 0) & (reach_ref[nxt] > 0)

        @pl.when((started == 0) & (nblk > 0))
        def _():
            start(slot, 0, half)

        args = (lo, n, slot, nblk, nxt, has_next, half)
        if tile_rows > 1:
            half = lax.cond(n == 1, lambda: walk(*args, lo - row0, 1),
                            lambda: walk(*args, 0, tile_rows))
        else:
            half = walk(*args, 0, 1)
        return half, (has_next & (nblk > 0)).astype(jnp.int32)

    def body(c):
        lo, half, started = c
        n = cnt_ref[lo]
        half, started = lax.cond(
            n > 0, lambda: unit(lo, n, half, started),
            lambda: (half, jnp.int32(0)))             # a padding row
        return lo + jnp.maximum(n, 1), half, started

    lax.while_loop(lambda c: c[0] < end, body,
                   (row0, jnp.int32(0), jnp.int32(0)))


def _own(ref, i0, rows: int, lo, n):
    """Visibilities ``[rows, 1, 1]`` of the tile's rows from ``i0``, 0
    for a row that is not the unit's (it sees nothing of this walk), and
    which rows are the unit's."""
    vis = ref[pl.ds(i0, rows)]
    r = i0 + lax.broadcasted_iota(jnp.int32, vis.shape, 0)
    mine = (r >= lo) & (r < lo + n)
    return jnp.where(mine, vis, 0), mine


def _index_scores_kernel(slot_ref, cnt_ref, reach_ref, tab_ref, live_ref,
                         q_ref, w_ref, vis_ref, pool_hbm, o_ref, buf, sem, *,
                         tile_rows: int, page: int, pp: int, max_pages: int):
    nk, bp = pp * page, _block_pages(pp, page)
    kb = bp * page
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def turn(i0, rows, lo, n, blk, half):
        q = q_ref[pl.ds(i0, rows)]                # [rows, heads, di]
        h, di = q.shape[1:]
        q = q.reshape(rows * h, di)
        w = w_ref[pl.ds(i0, rows)]                # [rows, heads, 1] fp32
        vis, mine = _own(vis_ref, i0, rows, lo, n)
        for c in range(pp // bp):
            k = buf[half, pl.ds(c * bp, bp)].reshape(kb, di)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.maximum(s, 0.0).reshape(rows, h, kb) * w
            row = jnp.sum(s, axis=1, keepdims=True)           # [rows, 1, kb]
            pos = blk * nk + c * kb + lax.broadcasted_iota(
                jnp.int32, row.shape, 2)
            at = (pl.ds(i0, rows), pl.ds(blk, 1), pl.ds(c * kb, kb))
            row = jnp.where(pos < vis, row, -jnp.inf)
            # a row of another unit keeps what its own walk wrote
            o_ref[at] = row if rows == 1 else jnp.where(mine, row, o_ref[at])

    _walk_tile(pl.program_id(0) * tile_rows, slot_ref, cnt_ref, reach_ref,
               tab_ref, live_ref, pool_hbm, buf, sem, turn,
               tile_rows=tile_rows, page=page, pp=pp, max_pages=max_pages)


def _tiles(row_lens, row_slot, block_tables, page: int, tile_rows):
    """What both kernels' launches take from the packed rows: the tile,
    the rows padded to whole tiles (``Tp``), the prefetched scalars
    (slots, units' counts and reaches, the table, one past the last live
    row) and the rows' visibilities ``[Tp, 1, 1]``."""
    T = row_slot.shape[0]
    tq = max(1, min(int(tile_rows), T))
    Tp = -(-T // tq) * tq
    slots = jnp.pad(row_slot.astype(jnp.int32), (0, Tp - T),
                    constant_values=-1)
    # a padding row sees nothing; a position past the table's width is
    # none
    lens = jnp.minimum(jnp.pad(row_lens.astype(jnp.int32), (0, Tp - T)),
                       block_tables.shape[1] * page)
    lens = jnp.where(slots < 0, 0, lens)
    count, reach = ragged_units(slots, lens, tq, jnp)
    live_end = jnp.max(jnp.where(slots >= 0, jnp.arange(Tp) + 1, 0),
                       keepdims=True).astype(jnp.int32)
    scalars = (jnp.maximum(slots, 0), count, reach,
               block_tables.astype(jnp.int32), live_end)
    return tq, Tp, scalars, lens[:, None, None]


def _tile_maps(tq: int):
    """Index maps of a tile's blocks: its own, and for what a tile only
    READS its own as far as the last tile with a live row.  The engine
    packs live rows first, so a decode-only step is a tile or two of
    live rows and then padding: those tiles name the block before them
    again and the pipeline copies nothing in for them."""
    def tile(i, *_):
        return (i, 0, 0)

    def live_tile(i, slot, cnt, reach, tab, live_end):
        return (jnp.minimum(i, jnp.maximum(live_end[0] - 1, 0) // tq), 0, 0)

    return tile, live_tile


def _pad_rows(x, Tp: int):
    return x if x.shape[0] == Tp else jnp.pad(
        x, ((0, Tp - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def lightning_index_scores_raw(q, w, key_pool, row_lens, row_slot,
                               block_tables, pages_per_step: int = 8,
                               interpret=None, tile_rows=None):
    """Index scores of packed query rows over their sequences' paged
    index keys.  q ``[T, heads, di]`` (the pool's dtype), w ``[T,
    heads]`` fp32 head weights, key_pool ``[pages, page, di]``,
    row_lens ``[T]`` visibility (position + 1; 0 for a padding row),
    row_slot ``[T]`` page-table row, block_tables ``[slots,
    max_pages]``.  Returns fp32 ``[T, W]``, ``W`` = the table's width
    in tokens rounded up to whole turns; ``-inf`` at and past the
    row's visibility.  Products accumulate in fp32.  ``tile_rows`` left
    unset is ``sparse_tile_rows`` of these shapes."""
    if interpret is None:
        interpret = pallas_interpret()
    if tile_rows is None:
        tile_rows = sparse_tile_rows(q.shape[1], q.shape[2])
    return _index_scores(q, w, key_pool, row_lens, row_slot, block_tables,
                         pp=int(pages_per_step), tq=int(tile_rows),
                         interpret=bool(interpret))


# jitted on their own, as ``decode_attention._ragged_walk`` is: a step's
# launches (one a layer) are ONE traced and lowered function each
@functools.partial(jax.jit, static_argnames=("pp", "tq", "interpret"))
def _index_scores(q, w, key_pool, row_lens, row_slot, block_tables, *, pp,
                  tq, interpret):
    T, h, di = q.shape
    page = key_pool.shape[1]
    max_pages = block_tables.shape[1]
    pp, nk, nblocks = walk_geometry(page, max_pages, pp)
    tq, Tp, scalars, vis = _tiles(row_lens, row_slot, block_tables, page, tq)

    tile, live_tile = _tile_maps(tq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(Tp // tq,),
        in_specs=[
            pl.BlockSpec((tq, h, di), live_tile),
            pl.BlockSpec((tq, h, 1), live_tile),
            pl.BlockSpec((tq, 1, 1), live_tile),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((tq, nblocks, nk), tile),
        scratch_shapes=[
            pltpu.VMEM((2, pp, page, di), key_pool.dtype),
            pltpu.SemaphoreType.DMA((2, pp)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, tile_rows=tq, page=page,
                          pp=pp, max_pages=max_pages),
        grid_spec=grid_spec,
        out_shape=_sds((Tp, nblocks, nk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=INDEX_SCORES_KERNEL,
        interpret=interpret,
    )(*scalars, _pad_rows(q.astype(key_pool.dtype), Tp),
      _pad_rows(w.astype(jnp.float32)[:, :, None], Tp), vis, key_pool)
    return out[:T].reshape(T, nblocks * nk)


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


def _sparse_mla_kernel(slot_ref, cnt_ref, reach_ref, tab_ref, live_ref,
                       q_ref, sc_ref, sel_ref, vis_ref, pool_hbm, o_ref, buf,
                       sem, m_scr, l_scr, acc_scr, *, tile_rows: int,
                       page: int, pp: int, max_pages: int, dv: int):
    nk, bp = pp * page, _block_pages(pp, page)
    kb = bp * page
    row0 = pl.program_id(0) * tile_rows
    live = row0 < live_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def turn(i0, rows, lo, n, blk, half):
        at = pl.ds(i0, rows)
        q = q_ref[at]                             # [rows, heads, dl]
        h, dl = q.shape[1:]
        q = q.reshape(rows * h, dl)
        vis, _ = _own(vis_ref, i0, rows, lo, n)
        sel = sel_ref[at]                         # [rows, 1, 2] fp32
        thr, cut = sel[:, :, :1], sel[:, :, 1:2]
        for c in range(pp // bp):
            kv = buf[half, pl.ds(c * bp, bp)].reshape(kb, dl)
            s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = s.reshape(rows, h, kb)
            idx = sc_ref[at, pl.ds(blk, 1), pl.ds(c * kb, kb)]  # [rows, 1, kb]
            pos = blk * nk + c * kb + lax.broadcasted_iota(
                jnp.int32, idx.shape, 2)
            keep = ((idx > thr) | ((idx == thr)
                                   & (pos.astype(jnp.float32) <= cut))
                    ) & (pos < vis)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[at][:, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row with no kept key so far (another unit's, or a block
            # its selection skips) is still at NEG_INF, where exp(s - m)
            # is 1 on a masked key: the mask is applied to p too
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            l_new = l_scr[at][:, :, :1] * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            # p is exactly 0 on a masked key, and a pool holds only
            # finite numbers (zeros until written), so stale rows add
            # nothing
            v = kv[:, :dv]
            pv = lax.dot_general(
                p.astype(v.dtype).reshape(rows * h, kb), v,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            acc_scr[at] = acc_scr[at] * alpha + pv.reshape(rows, h, dv)
            m_scr[at] = jnp.broadcast_to(m_new, (rows, h, m_scr.shape[2]))
            l_scr[at] = jnp.broadcast_to(l_new, (rows, h, l_scr.shape[2]))

    @pl.when(live)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)
        _walk_tile(row0, slot_ref, cnt_ref, reach_ref, tab_ref, live_ref,
                   pool_hbm, buf, sem, turn, tile_rows=tile_rows, page=page,
                   pp=pp, max_pages=max_pages)
        l = l_scr[:, :, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def sparse_mla_attention_raw(q, latent_pool, index_scores, selection,
                             row_lens, row_slot, block_tables, dv: int,
                             pages_per_step: int = 8, interpret=None,
                             tile_rows=None):
    """Absorbed latent attention of packed query rows over their
    sequences' paged latents, each row attending the positions below
    its visibility that ``selection`` (``select_top_k``'s two numbers a
    row) selects by their index scores.

    q ``[T, heads, dl]`` (softmax scale folded in; the pool's dtype),
    latent_pool ``[pages, page, dl]``, index_scores ``[T, W]`` as
    ``lightning_index_scores_raw`` returns them (the same
    ``pages_per_step``), selection ``[T, 2]`` fp32.  Returns ``[T, heads,
    dv]``: the softmax-weighted sum of the first ``dv`` lanes of the
    attended latent rows (zeros for a padding row).  ``tile_rows`` left
    unset is ``sparse_tile_rows`` of these shapes."""
    T, h, dl = q.shape
    _, nk, nblocks = walk_geometry(latent_pool.shape[1],
                                   block_tables.shape[1], pages_per_step)
    if index_scores.shape != (T, nblocks * nk):
        raise ValueError(f"index scores {index_scores.shape}, expected "
                         f"{(T, nblocks * nk)}: another pages_per_step?")
    if interpret is None:
        interpret = pallas_interpret()
    if tile_rows is None:
        tile_rows = sparse_tile_rows(h, dl, dv)
    return _sparse_mla(q, latent_pool, index_scores, selection, row_lens,
                       row_slot, block_tables, dv=int(dv),
                       pp=int(pages_per_step), tq=int(tile_rows),
                       interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("dv", "pp", "tq", "interpret"))
def _sparse_mla(q, latent_pool, index_scores, selection, row_lens, row_slot,
                block_tables, *, dv, pp, tq, interpret):
    T, h, dl = q.shape
    page = latent_pool.shape[1]
    max_pages = block_tables.shape[1]
    pp, nk, nblocks = walk_geometry(page, max_pages, pp)
    tq, Tp, scalars, vis = _tiles(row_lens, row_slot, block_tables, page, tq)

    tile, live_tile = _tile_maps(tq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(Tp // tq,),
        in_specs=[
            pl.BlockSpec((tq, h, dl), live_tile),
            pl.BlockSpec((tq, nblocks, nk), live_tile),
            pl.BlockSpec((tq, 1, 2), live_tile),
            pl.BlockSpec((tq, 1, 1), live_tile),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((tq, h, dv), tile),
        scratch_shapes=[
            pltpu.VMEM((2, pp, page, dl), latent_pool.dtype),
            pltpu.SemaphoreType.DMA((2, pp)),
            pltpu.VMEM((tq, h, 128), jnp.float32),    # m (lane-replicated)
            pltpu.VMEM((tq, h, 128), jnp.float32),    # l
            pltpu.VMEM((tq, h, dv), jnp.float32),     # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_sparse_mla_kernel, tile_rows=tq, page=page, pp=pp,
                          max_pages=max_pages, dv=dv),
        grid_spec=grid_spec,
        out_shape=_sds((Tp, h, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=SPARSE_MLA_KERNEL,
        interpret=interpret,
    )(*scalars, _pad_rows(q.astype(latent_pool.dtype), Tp),
      _pad_rows(index_scores.reshape(T, nblocks, nk), Tp),
      _pad_rows(selection.astype(jnp.float32).reshape(T, 1, 2), Tp), vis,
      latent_pool)
    return out[:T]
