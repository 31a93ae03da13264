"""Grouped / segmented matmul (Pallas TPU kernel): one ragged launch
applies a different ``[in, out]`` weight slice per variable-length row
segment.

This is the expert-compute half of the dropless MoE path (MegaBlocks'
grouped GEMM, PAPERS.md; reference kernel family:
paddle/phi/kernels/fusion/cutlass/moe kernels' grouped GEMM): tokens
arrive argsorted by destination expert, so expert e owns one contiguous
row window ``[seg_starts[e], seg_starts[e] + seg_lens[e])`` of the input
and the kernel multiplies that window by ``w[seg_wids[e]]``.  No
``[E, C, d]`` capacity buffer exists anywhere — cold experts cost their
actual rows (an empty segment costs zero grid work beyond the skipped
steps) and hot experts never drop.

The ragged iteration is the scalar-prefetch index-map idiom this repo
already ships for decode attention (decode_attention.py's
clamp-to-last-valid-page maps, the Ragged Paged Attention shape): the
grid is ``(S, nbmax)`` where ``nbmax`` is the worst case (one segment
owning every row block), and per-segment block counts read via scalar
prefetch both gate the MXU work (@pl.when) and drive the DMA (index
maps).  Because segments are variable, a segment using fewer than
``nbmax`` blocks must park its skipped steps somewhere safe: they map to
a dedicated PAD row block appended past the real rows, so no live output
block is ever flushed with stale VMEM.  The caller slices the pad block
off.

``seg_wids`` is an indirection, not an identity: several segments may
reuse one weight slice.  That is exactly the per-row LoRA adapter shape
(many small row groups, few adapters) — the backward pass scatter-adds
per-segment dW into slices with ``.at[wids].add``, so repeated ids
accumulate correctly and the same kernel serves the ROADMAP's
multi-adapter item.

Contract (callers: parallel/expert.py dropless body, models/generation.py
``_moe_experts``):
- ``x`` [R, K] with R a multiple of ``block_rows``; ``seg_starts`` are
  ``block_rows``-aligned and ascending (cumsum of block-aligned lens);
- the DIFFERENTIABLE path (``grouped_matmul``, training: the ``(S,
  nbmax)`` form and ``grouped_outer_raw``) needs the rows of x inside a
  segment's alignment slack ``[len, align(len))`` to be zero (the
  training dispatch's scatter guarantees it): they then contribute exact
  zeros to dW.  A forward launch alone does not: a slack row's product
  lands in a slack row of the output, which nobody reads, so the serving
  dispatch fills its slack with whatever finite row is at hand;
- output rows outside ``[start, start+len)`` of some segment are
  unspecified; callers only gather valid rows.
- a weight slice of up to ``_WHOLE_SLICE_BYTES`` rides in one block of
  the ``(S, nbmax)`` grid above.  A larger one (7168 x 2048 in bf16 is
  29 MB) takes the BLOCK-MAJOR form instead: the grid is (N tiles, row
  blocks), each row block reads its segment's weight id from a
  scalar-prefetched table, and with the N tile outermost a slice's tile
  is fetched once however many row blocks its segment has.
- a caller whose segments tile the rows densely (``dense=True``: the
  serving step's sorted dispatch) takes the block-major form whatever
  the slice's size: its grid is the row blocks alone, where the ``(S,
  nbmax)`` grid walks ``nbmax`` steps for every segment, nearly all of
  them parked.  Its ``x`` is taken AS IT STANDS: the LAST block of the
  caller's buffer is the park block (no segment may reach it; it is
  never read, and may hold anything), nothing is appended and nothing
  cut off, and the output has ``x``'s rows, the park block, which holds
  anything, included.  Any other caller's ``x`` gets the pad block
  appended and its output cut to ``R`` rows, as in the ``(S, nbmax)``
  form.

int8 expert banks: pass the raw quantized bank as ``w`` plus the
per-(slice, out-channel) dequant scales ``w_scale`` [E, N] — the kernel
widens in VMEM and folds the scale into the fp32 accumulator, so serving
never materialises a dequantized bank (the gather-then-dequant path of
generation._Weights.expert, moved in-kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.device import pallas_interpret

from .flash_attention import _sds

# its name in a compiled program and a device trace
GROUPED_MATMUL_BLOCKS_KERNEL = "grouped_matmul_blocks"


def align_rows(n, block_rows: int):
    """Round ``n`` up to a multiple of ``block_rows`` (works on ints and
    traced int arrays)."""
    return ((n + block_rows - 1) // block_rows) * block_rows


def segment_starts(seg_lens, block_rows: int):
    """Block-aligned exclusive cumsum of segment lengths: the
    ``seg_starts`` the kernel contract wants (segments densely tile
    ``[0, sum(align(len)))``)."""
    aligned = align_rows(seg_lens, block_rows)
    zero = jnp.zeros((1,), aligned.dtype)
    return jnp.concatenate([zero, jnp.cumsum(aligned)[:-1]])


def _gmm_kernel(*refs, block_rows: int, has_scale: bool):
    starts_ref, lens_ref, wids_ref = refs[:3]
    if has_scale:
        x_ref, w_ref, scale_ref, o_ref = refs[3:]
    else:
        x_ref, w_ref, o_ref = refs[3:]
        scale_ref = None
    si = pl.program_id(0)
    j = pl.program_id(1)
    nblk = (lens_ref[si] + block_rows - 1) // block_rows

    # steps past the segment's last block were parked on the PAD row
    # block by the index maps; skip their MXU work too
    @pl.when(j < nblk)
    def _():
        xb = x_ref[...]                     # [bm, K]
        wb = w_ref[0]                       # [K, N]
        if wb.dtype == jnp.int8:
            # int8 expert bank: widen the slice in VMEM and fold the
            # per-out-channel dequant scale into the fp32 accumulator
            wb = wb.astype(xb.dtype)
        acc = jax.lax.dot_general(
            xb, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if scale_ref is not None:
            acc = acc * scale_ref[0]
        o_ref[...] = acc.astype(o_ref.dtype)


# a [K, N] slice up to this rides whole in one block (the dropless-MoE
# path's 2048 x 1408 in bf16 is 5.8 MB, double-buffered by the pipeline);
# a larger one is tiled over N, a tile of at most _TILE_BYTES
_WHOLE_SLICE_BYTES = 6 * 1024 * 1024
_TILE_BYTES = 4 * 1024 * 1024


def _tile_n(K: int, N: int, itemsize: int) -> int:
    """N itself where the slice rides whole; else the widest multiple of
    128 that divides N with a [K, tile] block of at most ``_TILE_BYTES``."""
    if K * N * itemsize <= _WHOLE_SLICE_BYTES or N % 128:
        return N
    best = 128
    for t in range(128, N + 1, 128):
        if N % t == 0 and K * t * itemsize <= _TILE_BYTES:
            best = t
    return best


def _gmm_blocks_kernel(*refs, has_scale: bool):
    wid_ref, used_ref = refs[:2]
    if has_scale:
        x_ref, w_ref, scale_ref, o_ref = refs[2:]
    else:
        x_ref, w_ref, o_ref = refs[2:]
        scale_ref = None

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        xb = x_ref[...]                     # [bm, K]
        wb = w_ref[0]                       # [K, tn]
        if wb.dtype == jnp.int8:
            wb = wb.astype(xb.dtype)
        acc = jax.lax.dot_general(
            xb, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if scale_ref is not None:
            acc = acc * scale_ref[0]
        o_ref[...] = acc.astype(o_ref.dtype)


def _grouped_matmul_blocks(x, w, starts, lens, wids, bm: int, tn: int,
                           w_scale, interpret):
    """Block-major grouped matmul (module docstring).  Segments tile
    ``[0, sum(align(len)))`` densely, so row block ``b`` belongs to the
    segment whose aligned window holds it.  The LAST block of ``x`` is
    the park block: no segment reaches it, no step reads it, and the
    steps past the last segment's end write there and skip their work.
    The output has ``x``'s rows, the park block included."""
    R, K = x.shape
    N = w.shape[2]
    nblocks = R // bm - 1                                 # the park block apart
    ends = (starts + align_rows(lens, bm)) // bm          # in blocks
    blk = jnp.arange(nblocks, dtype=jnp.int32)
    seg_of = jnp.minimum(
        jnp.searchsorted(ends, blk, side="right", method="compare_all"),
        starts.shape[0] - 1)
    blk_wid = wids[seg_of].astype(jnp.int32)
    used = jnp.max(ends).astype(jnp.int32).reshape(1)

    def live(b, used_ref):
        # the last used block again (DMA elided) once past the end
        return jnp.minimum(b, jnp.maximum(used_ref[0] - 1, 0))

    def x_map(n, b, wid_ref, used_ref):
        return (live(b, used_ref), 0)

    def w_map(n, b, wid_ref, used_ref):
        return (wid_ref[live(b, used_ref)], 0, n)

    def o_map(n, b, wid_ref, used_ref):
        return (jnp.where(b < used_ref[0], b, nblocks), n)

    in_specs = [pl.BlockSpec((bm, K), x_map), pl.BlockSpec((1, K, tn), w_map)]
    operands = [x, w]
    if w_scale is not None:
        # [E, 1, N]: a block's last two dims are then (whole, lanes)
        in_specs.append(pl.BlockSpec(
            (1, 1, tn), lambda n, b, wid_ref, used_ref:
            (wid_ref[live(b, used_ref)], 0, n)))
        operands.append(w_scale.astype(jnp.float32)[:, None, :])
    return pl.pallas_call(
        functools.partial(_gmm_blocks_kernel, has_scale=w_scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // tn, nblocks),
            in_specs=in_specs, out_specs=pl.BlockSpec((bm, tn), o_map)),
        out_shape=_sds((R, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=GROUPED_MATMUL_BLOCKS_KERNEL,
        interpret=interpret,
    )(blk_wid, used, *operands)


def grouped_matmul_raw(x, w, seg_starts, seg_lens, seg_wids,
                       block_rows: int = 128, w_scale=None,
                       interpret=None, tile_n=None, dense: bool = False):
    """Ragged grouped matmul: ``y[start_s:start_s+len_s] =
    x[start_s:start_s+len_s] @ w[wid_s]`` for every segment ``s`` in one
    launch.  x [R, K] (R % block_rows == 0, see module contract);
    w [E, K, N]; seg_starts/seg_lens/seg_wids [S] int32; optional
    w_scale [E, N] dequant scales for an int8 ``w``.  Returns y [R, N]
    in x's dtype (rows outside valid segments unspecified).  ``tile_n``
    forces the block-major form with that N tile (tests); left None the
    slice's size decides.  ``dense=True`` says the segments tile
    ``[0, sum(align(len)))`` densely, as the block-major form needs, and
    takes it with the slice's own tile (the slice whole where it fits);
    x's last block is then the caller's park block and y has x's rows
    (module contract)."""
    R, K = x.shape
    E, Kw, N = w.shape
    if Kw != K:
        raise ValueError(f"x inner dim {K} != weight inner dim {Kw}")
    S = seg_starts.shape[0]
    bm = int(block_rows)
    if R % bm:
        raise ValueError(f"rows {R} not a multiple of block_rows {bm}")
    if interpret is None:
        interpret = pallas_interpret()
    if R == 0 or S == 0:
        return jnp.zeros((R, N), x.dtype)
    if dense and R < 2 * bm:
        raise ValueError(f"a dense launch's {R} rows hold no block before "
                         f"the park block of {bm}")
    tn = _tile_n(K, N, w.dtype.itemsize) if tile_n is None else int(tile_n)
    if tn != N or dense:
        # a dense caller's buffer ends in its own park block and goes in
        # and out as it stands; any other gets one appended and cut off
        xp = x if dense else jnp.concatenate(
            [x, jnp.zeros((bm, K), x.dtype)], axis=0)
        out = _grouped_matmul_blocks(
            xp, w, seg_starts.astype(jnp.int32), seg_lens.astype(jnp.int32),
            seg_wids.astype(jnp.int32), bm, tn, w_scale, interpret)
        return out if dense else out[:R]
    pad_blk = R // bm                       # the appended safe block
    nbmax = R // bm                         # worst case: one segment owns all

    xp = jnp.concatenate([x, jnp.zeros((bm, K), x.dtype)], axis=0)
    starts = seg_starts.astype(jnp.int32)
    lens = seg_lens.astype(jnp.int32)
    wids = seg_wids.astype(jnp.int32)

    def row_map(si, j, starts_ref, lens_ref, wids_ref):
        # blocks past the segment end park on the PAD block: the skipped
        # steps never touch a live output block, and consecutive parked
        # steps revisit the same block so Mosaic elides the DMA
        nblk = (lens_ref[si] + bm - 1) // bm
        return (jnp.where(j < nblk, starts_ref[si] // bm + j, pad_blk), 0)

    def w_map(si, j, starts_ref, lens_ref, wids_ref):
        return (wids_ref[si], 0, 0)

    in_specs = [
        pl.BlockSpec((bm, K), row_map),
        pl.BlockSpec((1, K, N), w_map),
    ]
    operands = [xp, w]
    if w_scale is not None:
        def scale_map(si, j, starts_ref, lens_ref, wids_ref):
            return (wids_ref[si], 0, 0)
        in_specs.append(pl.BlockSpec((1, 1, N), scale_map))
        operands.append(w_scale.astype(jnp.float32)[:, None, :])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, nbmax),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, N), row_map),
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, block_rows=bm,
                          has_scale=w_scale is not None),
        grid_spec=grid_spec,
        out_shape=_sds((R + bm, N), x.dtype),
        # segments share the PAD output block, so si is not parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(starts, lens, wids, *operands)
    return out[:R]


def _outer_kernel(starts_ref, lens_ref, x_ref, dy_ref, o_ref, *,
                  block_rows: int):
    si = pl.program_id(0)
    j = pl.program_id(3)
    nblk = (lens_ref[si] + block_rows - 1) // block_rows

    # the (si, kt, nt) output tile stays resident in VMEM across the
    # innermost j steps and is the fp32 accumulator itself; zeroing it
    # at j == 0 UNCONDITIONALLY makes empty segments emit exact zeros
    # and leaves no tile with stale VMEM
    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < nblk)
    def _():
        o_ref[0] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


# fp32 elements of one (bk, bn) output tile: 4 MiB, so the tile and its
# double buffer stay well inside the 16 MiB a kernel may scope in VMEM
_OUTER_TILE_ELEMS = 1 << 20


def _lane_tile(dim: int, cap: int) -> int:
    """Largest divisor of ``dim`` that is a multiple of 128 and <= cap;
    ``dim`` itself when it already fits or has no such divisor."""
    if dim <= cap:
        return dim
    for t in range(cap - cap % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def grouped_outer_raw(x, dy, seg_starts, seg_lens, block_rows: int = 128,
                      interpret=None):
    """Per-segment outer product ``out[s] = x[win_s].T @ dy[win_s]`` —
    the dW half of the grouped matmul backward.  x [R, K]; dy [R, N];
    returns [S, K, N] float32.  Alignment-slack rows of x are zero by
    the module contract, so they contribute exact zeros regardless of
    dy's content there.  ``(K, N)`` is tiled in the grid (N whole up to
    2048 columns, K cut so a tile holds _OUTER_TILE_ELEMS) — expert
    widths do not fit VMEM whole."""
    R, K = x.shape
    Rd, N = dy.shape
    if Rd != R:
        raise ValueError(f"x rows {R} != dy rows {Rd}")
    S = seg_starts.shape[0]
    bm = int(block_rows)
    if R % bm:
        raise ValueError(f"rows {R} not a multiple of block_rows {bm}")
    if interpret is None:
        interpret = pallas_interpret()
    if S == 0:
        return jnp.zeros((0, K, N), jnp.float32)
    if R == 0:
        return jnp.zeros((S, K, N), jnp.float32)
    pad_blk = R // bm
    nbmax = R // bm
    bn = _lane_tile(N, 2048)
    bk = _lane_tile(K, max(_OUTER_TILE_ELEMS // bn, 128))

    xp = jnp.concatenate([x, jnp.zeros((bm, K), x.dtype)], axis=0)
    dyp = jnp.concatenate([dy, jnp.zeros((bm, N), dy.dtype)], axis=0)
    starts = seg_starts.astype(jnp.int32)
    lens = seg_lens.astype(jnp.int32)

    def row_blk(si, j, starts_ref, lens_ref):
        nblk = (lens_ref[si] + bm - 1) // bm
        return jnp.where(j < nblk, starts_ref[si] // bm + j, pad_blk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, K // bk, N // bn, nbmax),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda si, kt, nt, j, s, l:
                         (row_blk(si, j, s, l), kt)),
            pl.BlockSpec((bm, bn), lambda si, kt, nt, j, s, l:
                         (row_blk(si, j, s, l), nt)),
        ],
        out_specs=pl.BlockSpec((1, bk, bn), lambda si, kt, nt, j, s, l:
                               (si, kt, nt)),
    )
    return pl.pallas_call(
        functools.partial(_outer_kernel, block_rows=bm),
        grid_spec=grid_spec,
        out_shape=_sds((S, K, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4),
        interpret=interpret,
    )(starts, lens, xp, dyp)


@functools.lru_cache(maxsize=None)
def _make_grouped_matmul(block_rows: int):
    @jax.custom_vjp
    def gmm(x, w, seg_starts, seg_lens, seg_wids):
        return grouped_matmul_raw(x, w, seg_starts, seg_lens, seg_wids,
                                  block_rows=block_rows)

    def fwd(x, w, seg_starts, seg_lens, seg_wids):
        y = grouped_matmul_raw(x, w, seg_starts, seg_lens, seg_wids,
                               block_rows=block_rows)
        return y, (x, w, seg_starts, seg_lens, seg_wids)

    def bwd(res, dy):
        x, w, seg_starts, seg_lens, seg_wids = res
        # dx: the same ragged launch against the transposed slices
        dx = grouped_matmul_raw(
            dy, w.swapaxes(1, 2), seg_starts, seg_lens, seg_wids,
            block_rows=block_rows).astype(x.dtype)
        # dW: per-segment outer products scatter-added into slices —
        # repeated seg_wids (the adapter shape) accumulate correctly
        dwseg = grouped_outer_raw(x, dy, seg_starts, seg_lens,
                                  block_rows=block_rows)
        dw = jnp.zeros(w.shape, jnp.float32).at[seg_wids].add(
            dwseg).astype(w.dtype)
        f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
        return (dx, dw, f0(seg_starts), f0(seg_lens), f0(seg_wids))

    gmm.defvjp(fwd, bwd)
    return gmm


def grouped_matmul(x, w, seg_starts, seg_lens, seg_wids,
                   block_rows: int = 128):
    """Differentiable grouped matmul (float weight banks, training):
    forward is ``grouped_matmul_raw``; backward runs the transposed
    ragged launch for dx and per-segment outer products scatter-added
    over ``seg_wids`` for dW."""
    return _make_grouped_matmul(int(block_rows))(
        x, w, seg_starts, seg_lens, seg_wids)


# framework op registration
from ..registry import register  # noqa: E402


@register("grouped_matmul", amp="white")
def grouped_matmul_op(x, w, seg_starts, seg_lens, seg_wids,
                      block_rows: int = 128):
    return grouped_matmul(x, w, seg_starts, seg_lens, seg_wids,
                          block_rows=block_rows)
