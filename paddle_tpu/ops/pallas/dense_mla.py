"""Absorbed multi-head latent attention over a paged LATENT cache with
NO selection (Pallas TPU kernel ``dense_mla_attention``): the attention
of a Kimi-Linear MLA layer inside the serving engine's unified ragged
step.

``sparse_mla.py``'s attention kernel without its index scores and its
mask, over a cache row split where the engine's two pools a page split
it: the normed latent ``c~`` (``[pages, page, dc]``: a row's key AND its
value) and the shared positional part of the key ``k_p`` (``[pages,
page, dp]``, ``dp`` a whole lane tile).  The walk is that file's
(``_walk_tile``: the grid is tiles of packed rows, a unit of work walks
its slot's pages once for all its rows, ``pages_per_step`` pages a turn
with double-buffered copies, here of both pools under the one page id),
the scores of a turn's block of keys are two products, ``q_c . c~ + q_p
. k_p``, and every position below a row's visibility is attended.

A tile holds ``dense_tile_rows`` packed rows: as many as give the
matmuls the rows DeepSeek's tile of 8 rows of 128 heads gives them
(1024: 32 rows at 32 heads), so a 512-row chunk walks its context 16
times a layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.device import pallas_interpret

from .flash_attention import NEG_INF, _sds
from .sparse_mla import (_VMEM_LIMIT, _block_pages, _own, _pad_rows,
                         _tile_maps, _tiles, _walk_tile, sparse_tile_rows,
                         walk_geometry)

__all__ = ["DENSE_MLA_KERNEL", "dense_mla_attention_raw", "dense_tile_rows"]

DENSE_MLA_KERNEL = "dense_mla_attention"

#: rows of a tile's matmuls (packed rows x heads): DeepSeek's tile's
_TILE_MATMUL_ROWS = 1024


def dense_tile_rows(heads: int, dc: int, dp: int) -> int:
    """Packed rows a tile: ``_TILE_MATMUL_ROWS`` rows of matmul, held to
    the VMEM as ``sparse_mla.sparse_tile_rows`` holds its own (a row
    brings ``dc + dp`` numbers a head and takes ``dc`` away)."""
    return sparse_tile_rows(heads, dc + dp, dc,
                            max_rows=max(1, _TILE_MATMUL_ROWS // heads))


def _dense_mla_kernel(slot_ref, cnt_ref, reach_ref, tab_ref, live_ref,
                      qc_ref, qp_ref, vis_ref, c_hbm, p_hbm, o_ref, cbuf,
                      pbuf, csem, psem, m_scr, l_scr, acc_scr, *,
                      tile_rows: int, page: int, pp: int, max_pages: int):
    nk, bp = pp * page, _block_pages(pp, page)
    kb = bp * page
    row0 = pl.program_id(0) * tile_rows
    live = row0 < live_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def turn(i0, rows, lo, n, blk, half):
        at = pl.ds(i0, rows)
        h, dc = qc_ref.shape[1:]
        qc = qc_ref[at].reshape(rows * h, dc)
        qp = qp_ref[at].reshape(rows * h, qp_ref.shape[2])
        vis, _ = _own(vis_ref, i0, rows, lo, n)
        nt = (((1,), (1,)), ((), ()))
        for c in range(pp // bp):
            kc = cbuf[half, pl.ds(c * bp, bp)].reshape(kb, dc)
            kp = pbuf[half, pl.ds(c * bp, bp)].reshape(kb, qp.shape[1])
            s = lax.dot_general(qc, kc, nt, preferred_element_type=jnp.float32) \
                + lax.dot_general(qp, kp, nt,
                                  preferred_element_type=jnp.float32)
            s = s.reshape(rows, h, kb)
            pos = blk * nk + c * kb + lax.broadcasted_iota(
                jnp.int32, (rows, 1, kb), 2)
            keep = pos < vis
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[at][:, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row of another unit is still at NEG_INF, where exp(s - m)
            # is 1 on a masked key: the mask is applied to p too
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            l_new = l_scr[at][:, :, :1] * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            # p is exactly 0 on a masked key, and a pool holds only
            # finite numbers (zeros until written)
            pv = lax.dot_general(
                p.astype(kc.dtype).reshape(rows * h, kb), kc,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            acc_scr[at] = acc_scr[at] * alpha + pv.reshape(rows, h, dc)
            m_scr[at] = jnp.broadcast_to(m_new, (rows, h, m_scr.shape[2]))
            l_scr[at] = jnp.broadcast_to(l_new, (rows, h, l_scr.shape[2]))

    @pl.when(live)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)
        _walk_tile(row0, slot_ref, cnt_ref, reach_ref, tab_ref, live_ref,
                   (c_hbm, p_hbm), (cbuf, pbuf), (csem, psem), turn,
                   tile_rows=tile_rows, page=page, pp=pp,
                   max_pages=max_pages)
        l = l_scr[:, :, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def dense_mla_attention_raw(q_c, q_p, latent_pool, pos_pool, row_lens,
                            row_slot, block_tables, pages_per_step: int = 8,
                            interpret=None, tile_rows=None):
    """Absorbed latent attention of packed query rows over their
    sequences' paged latents, each row attending every position below
    its visibility.

    q_c ``[T, heads, dc]`` (the query against the latent, softmax scale
    folded in) and q_p ``[T, heads, dp]`` (against the positional part),
    latent_pool ``[pages, page, dc]``, pos_pool ``[pages, page, dp]``,
    row_lens ``[T]`` visibility (position + 1; 0 for a padding row),
    row_slot ``[T]`` page-table row, block_tables ``[slots,
    max_pages]``.  Returns ``[T, heads, dc]``: the softmax-weighted sum
    of the attended latent rows (zeros for a padding row).
    ``tile_rows`` left unset is ``dense_tile_rows`` of these shapes."""
    if interpret is None:
        interpret = pallas_interpret()
    if tile_rows is None:
        tile_rows = dense_tile_rows(q_c.shape[1], q_c.shape[2], q_p.shape[2])
    return _dense_mla(q_c, q_p, latent_pool, pos_pool, row_lens, row_slot,
                      block_tables, pp=int(pages_per_step), tq=int(tile_rows),
                      interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("pp", "tq", "interpret"))
def _dense_mla(q_c, q_p, latent_pool, pos_pool, row_lens, row_slot,
               block_tables, *, pp, tq, interpret):
    T, h, dc = q_c.shape
    dp = q_p.shape[2]
    page = latent_pool.shape[1]
    max_pages = block_tables.shape[1]
    pp, _, _ = walk_geometry(page, max_pages, pp)
    tq, Tp, scalars, vis = _tiles(row_lens, row_slot, block_tables, page, tq)

    tile, live_tile = _tile_maps(tq)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(Tp // tq,),
        in_specs=[
            pl.BlockSpec((tq, h, dc), live_tile),
            pl.BlockSpec((tq, h, dp), live_tile),
            pl.BlockSpec((tq, 1, 1), live_tile),
            hbm, hbm,
        ],
        out_specs=pl.BlockSpec((tq, h, dc), tile),
        scratch_shapes=[
            pltpu.VMEM((2, pp, page, dc), latent_pool.dtype),
            pltpu.VMEM((2, pp, page, dp), pos_pool.dtype),
            pltpu.SemaphoreType.DMA((2, pp)),
            pltpu.SemaphoreType.DMA((2, pp)),
            pltpu.VMEM((tq, h, 128), jnp.float32),    # m (lane-replicated)
            pltpu.VMEM((tq, h, 128), jnp.float32),    # l
            pltpu.VMEM((tq, h, dc), jnp.float32),     # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_dense_mla_kernel, tile_rows=tq, page=page, pp=pp,
                          max_pages=max_pages),
        grid_spec=grid_spec,
        out_shape=_sds((Tp, h, dc), q_c.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=DENSE_MLA_KERNEL,
        interpret=interpret,
    )(*scalars, _pad_rows(q_c.astype(latent_pool.dtype), Tp),
      _pad_rows(q_p.astype(pos_pool.dtype), Tp), vis, latent_pool, pos_pool)
    return out[:T]
