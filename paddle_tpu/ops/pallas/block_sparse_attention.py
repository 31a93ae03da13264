"""Block-sparse attention over a paged K/V with a cache of COMPRESSED
keys (InfLLM-V2, the ``minicpm4`` mixer of MiniCPM-SALA): the two
device-heavy parts of such a layer inside the serving engine's unified
ragged step, and the selection between them.

A compressed key is the mean of ``kernel_size`` (32) consecutive keys of
one K/V head, one every ``kernel_stride`` (16) tokens: ``c_j =
mean(k[16 j : 16 j + 32])``.  It is FINAL once token ``16 j + 31`` is
written, and it lives in the page of that token, so that a page of the
cache of compressed keys depends on nothing past its own page of tokens
and the prefix cache may share it by the same page id as the K and V
pages.  With ``f = j + 1`` the FLAT index of ``c_j``, flat index ``f``
is row ``f % (page / 16)`` of the sequence's page ``f // (page / 16)``
and flat index 0 holds nothing.  A context of ``n`` tokens has the flat
indices ``1 .. n // 16 - 1`` (``compressed_count``).

- ``infllm_block_scores`` (Pallas TPU kernel): for every packed query
  row, the softmax of each query head over its sequence's compressed
  keys, summed over the ``H / kvh`` heads of a K/V group: fp32 ``[T,
  kvh, flat indices]``.  The compressed keys of a sequence arrive
  CONTIGUOUS (``gather_compressed``: one XLA gather of every slot's
  pages a layer, 4 KiB a page), a unit of work (a run of one
  slot's rows inside a tile, ``causal_conv.conv_units``) reads
  them once, and the per-head scores live only in VMEM.
- ``select_blocks`` (plain XLA): a block of ``block_size`` (64) tokens
  scores the maximum over the five compressed keys that overlap it; the
  ``init_blocks`` first blocks and the blocks that overlap the last
  ``window_size`` tokens score infinity; the ``topk`` highest are the
  selection, one a K/V group a row, ties to the lower block, returned
  in ASCENDING order so that the row's own block, the only one that its
  visibility cuts, is the last.
- ``block_sparse_paged_attention`` (Pallas TPU kernel): causal softmax
  attention of a group's query heads over the tokens of the selected
  blocks ONLY.  A block is a contiguous part of a page a head (``[64,
  d]`` of ``[pages, kvh, page, d]``), so the kernel copies exactly the
  selected blocks' K and V by hand, ``topk`` copies each a (row,
  group), the next (row, group)'s in flight while this one's are
  computed on: the gather form of selected attention, whose bytes
  follow the selection and not the context.

The ``*_reference`` functions are the same in plain ``jax.numpy``: the
CPU path (``core/device.pallas_interpret``) and the kernels' test
oracles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .causal_conv import conv_units
from .flash_attention import NEG_INF, _sds
from .sparse_mla import kth_largest

__all__ = ["BLOCK_SCORES_KERNEL", "BLOCK_SPARSE_KERNEL", "compressed_count",
           "write_compressed_keys", "gather_compressed",
           "infllm_block_scores", "block_scores_reference", "select_blocks",
           "block_sparse_paged_attention", "block_sparse_attention_reference"]

#: the kernels' names in a device trace
BLOCK_SCORES_KERNEL = "infllm_block_scores"
BLOCK_SPARSE_KERNEL = "block_sparse_paged_attention"

_VMEM_LIMIT = 96 * 1024 * 1024
#: packed rows a tile of the scores kernel (a tile's fp32 scores are
#: ``rows x heads of a group x flat indices``: 4.6 MB at 16 x 16 x 4480)
SCORES_TILE_ROWS = 16
#: packed rows a tile of the attention kernel (its selection rides in
#: SMEM a tile: ``[8, kvh topk]`` int32)
SPARSE_TILE_ROWS = 8


def compressed_count(n, stride: int):
    """Compressed keys that are final in a context of ``n`` tokens (the
    kernel of a compressed key is two strides): flat indices ``1 ..
    compressed_count(n)``."""
    return jnp.maximum(n // stride - 1, 0)


def write_compressed_keys(c_pool, k_pool, lens, slot, table, *, stride: int,
                          max_final: int):
    """The compressed keys that this step's rows make final, written to
    ``c_pool`` ``[pages, page / stride, kvh d]``: a row at position ``t``
    with ``(t + 1) % stride == 0`` and ``t + 1 >= 2 stride`` finishes
    the key over tokens ``t - 2 stride + 1 .. t``, whose rows are in
    ``k_pool`` ``[pages, kvh, page, d]`` by now (this step's own
    included).  At most ``max_final`` rows of a step do; the others
    write the pool's last page, the trash page."""
    pages, kvh, page, d = k_pool.shape
    rpp = page // stride                         # rows of a page
    n = jnp.where(slot >= 0, lens, 0)
    final = (n % stride == 0) & (n >= 2 * stride)
    (at,) = jnp.nonzero(final, size=max_final, fill_value=0)
    ok = final[at]
    n, s = n[at], jnp.maximum(slot[at], 0)
    # the two strides of tokens the key averages, a page and a group of
    # ``stride`` rows in it each
    g0 = (n // stride - 2)[:, None] + jnp.arange(2)[None, :]  # [F, 2]
    pg = table[s[:, None], g0 // rpp]
    kp = k_pool.reshape(pages, kvh, rpp, stride, d)
    keys = kp[jnp.maximum(pg, 0)[:, :, None], jnp.arange(kvh)[None, None, :],
              (g0 % rpp)[:, :, None]]                        # [F, 2, kvh, s, d]
    ck = jnp.mean(keys.astype(jnp.float32), axis=(1, 3))     # [F, kvh, d]
    f = n // stride - 1                                      # flat index
    to = jnp.where(ok, table[s, f // rpp], pages - 1)
    return c_pool.at[jnp.where(to < 0, pages - 1, to), f % rpp].set(
        ck.reshape(-1, kvh * d).astype(c_pool.dtype))


def gather_compressed(c_pool, table, *, lanes: int = 128):
    """Every slot's compressed keys, contiguous: ``[slots, flat
    indices, kvh d]``, the flat indices padded (with the trash page) to
    whole ``lanes``.  A table entry below zero reads the trash page."""
    pages, rpp, w = c_pool.shape
    per = -(-table.shape[1] * rpp // lanes) * lanes // rpp   # pages, padded
    tab = jnp.pad(table, ((0, 0), (0, per - table.shape[1])),
                  constant_values=-1)
    tab = jnp.where(tab < 0, pages - 1, tab)
    return c_pool[tab].reshape(table.shape[0], per * rpp, w)


def _units(slot, tile: int, max_units: int):
    """The prefetched scalars of a grid over units of work
    (``causal_conv.conv_units``: a run of one slot's rows inside a
    tile): each unit's first row, rows (0: padding), whether it is its
    tile's first, slot and tile."""
    row0, cnt, live = conv_units(slot, tile, max_units)
    u_tile = (row0 // tile).astype(jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), u_tile[:-1]])
    tfirst = (live & (u_tile != prev)).astype(jnp.int32)
    return row0, cnt, tfirst, jnp.maximum(slot[row0], 0).astype(jnp.int32), \
        u_tile


def _scores_kernel(row0_ref, cnt_ref, tfirst_ref, slot_ref, tile_ref, q_ref,
                   nck_ref, ck_ref, o_ref, *, tile: int, kvh: int, hpg: int,
                   d: int, W: int):
    """One unit of work: rows ``[r, r + cnt)`` of its tile against its
    slot's compressed keys ``ck_ref`` ``[1, W, kvh d]``.  ``q_ref``
    ``[tile, H, d]``, ``nck_ref`` ``[tile, 1, 1]`` (a row's compressed
    keys; 0: none of its business), ``o_ref`` ``[tile, kvh W]``."""
    u = pl.program_id(0)
    cnt = cnt_ref[u]
    r = row0_ref[u] - tile_ref[u] * tile

    @pl.when(tfirst_ref[u] == 1)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def rows_of(i0, rows: int):
        q = q_ref[pl.ds(i0, rows)]                        # [rows, H, d]
        ridx = i0 + lax.broadcasted_iota(jnp.int32, (rows, 1, 1), 0)
        # a row that is not the unit's scores nothing here
        nck = jnp.where((ridx >= r) & (ridx < r + cnt),
                        nck_ref[pl.ds(i0, rows)], 0)          # [rows, 1, 1]
        f = lax.broadcasted_iota(jnp.int32, (rows, hpg, W), 2)
        valid = (f >= 1) & (f <= nck)
        r2 = i0 + lax.broadcasted_iota(jnp.int32, (rows, W), 0)
        mine = (r2 >= r) & (r2 < r + cnt)
        for g in range(kvh):
            qg = q[:, g * hpg:(g + 1) * hpg, :].reshape(rows * hpg, d)
            s = lax.dot_general(qg, ck_ref[0, :, g * d:(g + 1) * d],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(valid, s.reshape(rows, hpg, W), NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.where(valid, jnp.exp(s - m), 0.0)
            l = jnp.sum(p, axis=-1, keepdims=True)
            pg = jnp.sum(p / jnp.where(l == 0.0, 1.0, l), axis=1)  # [rows, W]
            at = (pl.ds(i0, rows), slice(g * W, (g + 1) * W))
            # a row of another unit keeps what its own unit wrote
            o_ref[at] = pg if rows == 1 else jnp.where(mine, pg, o_ref[at])

    @pl.when(cnt == 1)
    def _():
        rows_of(r, 1)

    if tile > 1:
        @pl.when(cnt > 1)
        def _():
            rows_of(0, tile)


@functools.partial(jax.jit, static_argnames=("tile_rows", "max_units",
                                             "interpret"))
def infllm_block_scores(q, ck, row_slot, row_nck, *,
                        tile_rows: int = SCORES_TILE_ROWS, max_units=None,
                        interpret=False):
    """Group scores of packed query rows over their slots' compressed
    keys.  ``q`` ``[T, H, d]`` (the softmax scale folded in), ``ck``
    ``[slots, W, kvh d]`` (``gather_compressed``; ``W`` whole lanes),
    ``row_slot`` ``[T]`` (below zero: padding), ``row_nck`` ``[T]`` the
    compressed keys a row scores (``compressed_count`` of its
    visibility; 0 for a row that selects nothing: zeros come back).
    Returns fp32 ``[T, kvh, W]``: at flat index ``f`` in ``1 ..
    row_nck`` the sum over the group's heads of ``softmax_f(q_h .
    c_f)``, 0 elsewhere."""
    T, H, d = q.shape
    W, kvh = ck.shape[1], ck.shape[2] // d
    tile = max(1, min(int(tile_rows), T))
    Tp = -(-T // tile) * tile
    pad = Tp - T
    slot = jnp.pad(row_slot.astype(jnp.int32), (0, pad), constant_values=-1)
    nck = jnp.where(slot >= 0, jnp.pad(row_nck.astype(jnp.int32), (0, pad)), 0)
    U = int(max_units or Tp)
    scalars = _units(slot, tile, U)
    qp = jnp.pad(q.astype(ck.dtype), ((0, pad), (0, 0), (0, 0)))

    def by_tile(u, row0, cnt, tfirst, uslot, utile):
        return utile[u]

    out = pl.pallas_call(
        functools.partial(_scores_kernel, tile=tile, kvh=kvh, hpg=H // kvh,
                          d=d, W=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(U,),
            in_specs=[
                pl.BlockSpec((tile, H, d), lambda u, *r: (by_tile(u, *r), 0, 0)),
                pl.BlockSpec((tile, 1, 1), lambda u, *r: (by_tile(u, *r), 0, 0)),
                pl.BlockSpec((1, W, kvh * d), lambda u, *r: (r[3][u], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, kvh * W),
                                   lambda u, *r: (by_tile(u, *r), 0))),
        out_shape=_sds((Tp, kvh * W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=BLOCK_SCORES_KERNEL,
        interpret=interpret,
    )(*scalars, qp, nck[:, None, None], ck)
    # a tile with no unit was never visited: its block is not written
    return jnp.where((nck > 0)[:T, None, None],
                     out[:T].reshape(T, kvh, W), 0.0)


def block_scores_reference(q, ck, row_slot, row_nck):
    """``infllm_block_scores`` in plain ``jax.numpy``."""
    T, H, d = q.shape
    W, kvh = ck.shape[1], ck.shape[2] // d
    keys = ck[jnp.maximum(row_slot, 0)].reshape(T, W, kvh, d)
    s = jnp.einsum("tghd,tfgd->tghf",
                   q.reshape(T, kvh, H // kvh, d).astype(jnp.float32),
                   keys.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    f = jnp.arange(W)[None, None, None, :]
    nck = jnp.where(row_slot >= 0, row_nck, 0)[:, None, None, None]
    valid = (f >= 1) & (f <= nck)
    p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
    return jnp.sum(jnp.where(valid, p, 0.0), axis=2)


def select_blocks(scores, row_lens, *, stride: int, block: int, topk: int,
                  init_blocks: int, window: int):
    """The selection of each (row, K/V group): int32 ``[T, kvh, topk]``
    block numbers, ascending.  ``scores`` ``[T, kvh, W]`` by flat index
    (``infllm_block_scores``), ``row_lens`` the rows' visibilities.
    Block ``b`` holds tokens ``[block b, block (b + 1))`` and scores the
    maximum of the compressed keys that overlap it: flat indices ``b per
    .. b per + per`` with ``per = block / stride``.  Forced blocks score
    infinity, blocks past the row's own minus infinity; a row with fewer
    than ``topk`` blocks selects some of those, which hold nothing a
    causal row may see (such a row's context is dense)."""
    T, kvh, W = scores.shape
    per = block // stride
    nb = W // per
    padded = jnp.pad(scores, ((0, 0), (0, 0), (0, per)))
    inner = padded[..., :nb * per].reshape(T, kvh, nb, per).max(-1)
    edge = padded[..., per:per * (nb + 1):per]      # flat index per (b + 1)
    b = jnp.arange(nb)[None, None, :]
    n = row_lens[:, None, None]
    own = (n - 1) // block
    forced = (b < init_blocks) | (b >= jnp.maximum(n - window, 0) // block)
    s = jnp.where(forced, jnp.inf, jnp.maximum(inner, edge))
    s = jnp.where(b > own, -jnp.inf, s).reshape(T * kvh, nb)
    # the topk largest WITHOUT a sort (a sort of 1,100 scores a row and
    # group took 11 ms a launch on the chip, a quarter of the step): the
    # k-th largest by counting passes, everything above it, and of the
    # ties at it the lowest blocks (``lax.top_k``'s rule)
    thr = kth_largest(s, topk)[:, None]
    above, tie = s > thr, s == thr
    need = topk - jnp.sum(above, axis=1, keepdims=True)
    # running counts along a row as a product with a triangle of ones
    # (exact: 0/1 operands, float32 sums; a cumulative sum is a window
    # reduction whose fusions lose the scope's name)
    upto = (jnp.arange(nb)[:, None] <= jnp.arange(nb)[None, :]
            ).astype(jnp.bfloat16)

    def running(mask):
        return jnp.dot(mask.astype(jnp.bfloat16), upto,
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    keep = above | (tie & (running(tie) <= need))
    # the kept blocks in ascending order: the i-th is the block whose
    # rank among the kept is i
    rank = running(keep) - 1
    slot = jnp.arange(topk)[None, :, None]
    sel = jnp.sum(jnp.where(keep[:, None, :] & (rank[:, None, :] == slot),
                            jnp.arange(nb)[None, None, :], 0), axis=-1)
    return sel.reshape(T, kvh, topk).astype(jnp.int32)


def _sparse_kernel(live_ref, nlast_ref, addr_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sem, *, tile: int, kvh: int, hpg: int,
                   topk: int, block: int, bpp: int):
    """One tile of ``tile`` packed rows: ``tile kvh`` items, one a (row,
    K/V group), each over its ``topk`` selected blocks.  ``addr_ref``
    ``[tile, kvh topk]`` (SMEM): ``physical page x bpp + part of the
    page``; ``live_ref`` / ``nlast_ref`` (prefetched) a row: whether it
    is computed at all, and the tokens of its LAST selected block that
    it sees."""
    row0 = pl.program_id(0) * tile
    n_items = tile * kvh
    nk = topk * block

    def copies(i, half, go):
        r, g = i // kvh, i % kvh

        def body(j, _):
            a = addr_ref[r, g * topk + j]
            pg, part = a // bpp, a % bpp
            for hbm, buf, k in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                cp = pltpu.make_async_copy(
                    hbm.at[pg, g, pl.ds(pl.multiple_of(part * block, block),
                                     block), :],
                    buf.at[half, pl.ds(j * block, block), :],
                    sem.at[k, half])
                cp.start() if go else cp.wait()
            return 0

        @pl.when(live_ref[row0 + r] > 0)
        def _():
            lax.fori_loop(0, topk, body, 0)

    copies(0, 0, True)

    def item(i, half):
        @pl.when(i + 1 < n_items)
        def _():
            copies(i + 1, 1 - half, True)

        r, g = i // kvh, i % kvh
        at = (r, pl.ds(pl.multiple_of(g * hpg, hpg), hpg), slice(None))
        live = live_ref[row0 + r] > 0

        @pl.when(live)
        def _():
            copies(i, half, False)
            q = q_ref[at]                                     # [hpg, d]
            s = lax.dot_general(q, kbuf[half], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = col < (topk - 1) * block + nlast_ref[row0 + r]
            s = jnp.where(seen, s, NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.where(seen, jnp.exp(s - m), 0.0)
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = jnp.dot(p.astype(vbuf.dtype), vbuf[half],
                        preferred_element_type=jnp.float32)
            o_ref[at] = (o / l).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[at] = jnp.zeros((hpg, o_ref.shape[2]), o_ref.dtype)

        return 1 - half

    lax.fori_loop(0, n_items, item, 0)


def _addresses(sel, row_lens, row_slot, table, page: int, block: int):
    """What the attention takes of a selection: each selected block's
    ``physical page x bpp + part`` ``[T, kvh topk]``, and the tokens of
    the LAST selected block a row sees (its own block's, where the
    selection holds it)."""
    T, kvh, topk = sel.shape
    bpp = page // block
    # a row's pages by a compare and a sum over its table row (a gather
    # of 78k single numbers took 2.3 ms a launch on the chip)
    rows = table[jnp.maximum(row_slot, 0)]                    # [T, pages]
    want = jnp.minimum(sel // bpp, table.shape[1] - 1).reshape(T, -1)
    pg = jnp.sum(jnp.where(
        want[:, :, None] == jnp.arange(table.shape[1])[None, None, :],
        rows[:, None, :], 0), axis=-1).reshape(T, kvh, topk)
    addr = jnp.maximum(pg, 0) * bpp + sel % bpp
    nlast = jnp.clip(row_lens - sel[:, 0, -1] * block, 0, block)
    return addr.reshape(T, kvh * topk).astype(jnp.int32), \
        nlast.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "tile_rows",
                                             "interpret"))
def block_sparse_paged_attention(q, k_pool, v_pool, sel, row_lens, row_slot,
                                 table, live, *, block: int,
                                 tile_rows: int = SPARSE_TILE_ROWS,
                                 interpret=False):
    """Attention of packed query rows over the tokens of their selected
    blocks.  ``q`` ``[T, H, d]`` (the softmax scale folded in), pools
    ``[pages, kvh, page, d]``, ``sel`` ``[T, kvh, topk]`` ascending
    block numbers (``select_blocks``: every group's last is the row's
    own block), ``row_lens`` the visibilities, ``row_slot`` the table's
    rows, ``live`` ``[T]`` the rows to compute (the others give zeros
    and copy nothing).  Returns ``[T, H, d]`` in the pools' dtype."""
    T, H, d = q.shape
    kvh, page = k_pool.shape[1], k_pool.shape[2]
    topk = sel.shape[2]
    tile = int(tile_rows)
    Tp = -(-T // tile) * tile
    pad = Tp - T
    addr, nlast = _addresses(sel, row_lens, row_slot, table, page, block)
    live = (live & (row_slot >= 0)).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, tile=tile, kvh=kvh, hpg=H // kvh,
                          topk=topk, block=block, bpp=page // block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(Tp // tile,),
            in_specs=[
                pl.BlockSpec((tile, kvh * topk), lambda i, *_: (i, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, H, d), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            ],
            out_specs=pl.BlockSpec((tile, H, d), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, topk * block, d), k_pool.dtype),
                pltpu.VMEM((2, topk * block, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=_sds((Tp, H, d), k_pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=BLOCK_SPARSE_KERNEL,
        interpret=interpret,
    )(jnp.pad(live, (0, pad)), jnp.pad(nlast, (0, pad)),
      jnp.pad(addr, ((0, pad), (0, 0))),
      jnp.pad(q.astype(k_pool.dtype), ((0, pad), (0, 0), (0, 0))),
      k_pool, v_pool)
    return out[:T]


def block_sparse_attention_reference(q, k_pool, v_pool, sel, row_lens,
                                     row_slot, table, live, *, block: int):
    """``block_sparse_paged_attention`` in plain ``jax.numpy``."""
    T, H, d = q.shape
    pages, kvh, page, _ = k_pool.shape
    topk, bpp = sel.shape[2], page // block
    addr, nlast = _addresses(sel, row_lens, row_slot, table, page, block)
    addr = addr.reshape(T, kvh, topk)
    g = jnp.arange(kvh)[None, :, None]

    def take(pool):
        x = pool.reshape(pages, kvh, bpp, block, d)[addr // bpp, g, addr % bpp]
        return x.reshape(T, kvh, topk * block, d).astype(jnp.float32)

    s = jnp.einsum("tghd,tgnd->tghn",
                   q.reshape(T, kvh, H // kvh, d).astype(jnp.float32),
                   take(k_pool), precision=lax.Precision.HIGHEST)
    col = jnp.arange(topk * block)[None, None, None, :]
    seen = col < (topk - 1) * block + nlast[:, None, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
    o = jnp.einsum("tghn,tgnd->tghd", jnp.where(seen, p, 0.0), take(v_pool),
                   precision=lax.Precision.HIGHEST)
    keep = (live & (row_slot >= 0))[:, None, None]
    return jnp.where(keep, o.reshape(T, H, d), 0.0).astype(k_pool.dtype)
