"""Mamba-2's causal depthwise convolution over the serving engine's
PACKED rows (Pallas TPU kernel ``mamba2_causal_conv``) and the plain form
it is tested against.

A Mamba-2 mixer convolves each channel of ``xBC`` over the last ``K``
positions of its sequence (``K = conv_kernel``, 4) and passes the sum
through ``silu``.  The engine's step carries rows of MANY sequences in
one launch (``ssd_scan.py``'s docstring): what a sequence carries from
launch to launch is its last ``K - 1`` inputs, its TAIL, one entry
``[K - 1, C]`` of a pool ``[entries, K - 1, C]``.  A packed row names
the entry its slot STARTS from (``src``; below zero: zeros) and the
entry its tail is LEFT in (``dst``); a slot's rows are consecutive.  Row
``t``, ``j`` rows into its slot's run, convolves ``[x_{t-K+1} .. x_t]``,
where the tap ``back > j`` rows behind is entry ``K - 1 - back + j`` of
the slot's tail; the slot's LAST row leaves the newest ``K - 1`` inputs
of its window in ``pool[dst]`` (a run shorter than ``K - 1`` rows leaves
a mix of the old tail and its own rows).

Units of work are the scan's (``ssd_scan.py``): a maximal run of one
slot's rows inside one tile of ``tile_rows`` rows (the model's
``chunk_size``).  The grid is the units, the rows on the sublanes and
the channels, all of them, on the lanes.  A tile of ``xbc`` is read once,
when its first unit runs, and its results are written once, after its
last; a unit's tail comes in and goes out as one block ``[1, K - 1,
C]`` of the pool, which is written in place.  A unit of one row
(a decoding slot) multiplies its window ``[K, C]``, the tail and the
row, by the taps and sums over the sublanes; a longer unit lays the
tail over the ``K - 1`` rows before its first (rows of units that are
done) and takes every tap from the tile's rows shifted down, a whole
tile at a time.  A slot's further units in the
same launch find its tail where the unit before left it, in the output
block that stays in VMEM while the entry's index does not change, so a
chunk of 512 rows reads its tail once and writes it once.  Padding units
point at the pool's LAST entry, the trash entry: no live tail is written
by them.  What moves is the rows once each way and a tail each way for
each slot in the launch; a tile with no live row is never visited.

Precision: rows and tails taken to float32, taps and bias in float32,
float32 sums, ``silu`` in float32, one cast to the rows' dtype.  The
tails written are copies of rows of ``xbc`` and of old tail entries.

``packed_causal_conv_reference`` is the same function in XLA's terms: the
CPU path (``core/device.pallas_interpret``) and the kernel's test oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssd_scan import run_first, ssd_max_units

__all__ = ["CAUSAL_CONV_KERNEL", "packed_causal_conv",
           "packed_causal_conv_reference"]

#: the kernel's name in a device trace
CAUSAL_CONV_KERNEL = "mamba2_causal_conv"

#: rows kept free before a tile's rows in VMEM (a float32 sublane tile):
#: room for a tail before the tile's first row, so that the tile shifted
#: down by up to ``K - 1`` rows is one slice
_HALO = 8


def _run_index(slot):
    """For each packed row, how many rows of its slot lie before it in
    this launch (a slot's rows are consecutive)."""
    idx = jnp.arange(slot.shape[0], dtype=jnp.int32)
    return idx - lax.cummax(jnp.where(run_first(slot), idx, 0))


def _ends_run(slot):
    """Whether each packed row is the last of a LIVE slot's run."""
    nxt = jnp.concatenate([slot[1:], jnp.full((1,), -2, slot.dtype)])
    return (slot >= 0) & (slot != nxt)


def packed_causal_conv_reference(xbc, weight, bias, conv_pool, slot, src,
                                 dst):
    """The convolution as gathers and selects.  ``xbc`` ``[T, C]``,
    ``weight`` ``[K, C]``, ``bias`` ``[C]``, ``conv_pool`` ``[entries, K
    - 1, C]`` (the LAST entry the trash entry); ``slot``, ``src``,
    ``dst`` int32 ``[T]``.  Returns ``(silu(conv) [T, C] in xbc's dtype,
    conv_pool)``; rows with ``slot < 0`` write the trash entry."""
    K, cd = weight.shape
    j = _run_index(slot)
    tail = jnp.where((src < 0)[:, None, None], 0,
                     conv_pool[jnp.maximum(src, 0)])          # [T, K-1, cd]
    taps = []
    for back in range(K - 1, 0, -1):
        shifted = jnp.concatenate(
            [jnp.zeros((back, cd), xbc.dtype), xbc[:-back]])
        # the tap `back` rows before row t: in the run if j >= back,
        # else entry K - 1 - back + j of the tail
        from_tail = jnp.take_along_axis(
            tail, jnp.clip(K - 1 - back + j, 0, K - 2)[:, None, None],
            axis=1)[:, 0].astype(xbc.dtype)
        taps.append(jnp.where((j >= back)[:, None], shifted, from_tail))
    taps.append(xbc)
    win = jnp.stack(taps, axis=1)                             # [T, K, cd]
    conv = jnp.einsum("tkc,kc->tc", win.astype(jnp.float32),
                      weight.astype(jnp.float32)) \
        + bias.astype(jnp.float32)
    out = jax.nn.silu(conv).astype(xbc.dtype)
    # a slot's last row leaves its window's newest K - 1 entries
    trash = conv_pool.shape[0] - 1
    conv_pool = conv_pool.at[jnp.where(_ends_run(slot), dst, trash)].set(
        win[:, 1:].astype(conv_pool.dtype))
    return out, conv_pool


def conv_units(slot, tile: int, max_units: int):
    """The units of work of ``ssd_scan.py`` (a maximal run of one live
    slot's rows inside one tile), ``max_units`` of them, from the packed
    slots alone: ``(first row, rows, live)`` a unit, padding units
    standing at the last live unit's row with no rows."""
    T = slot.shape[0]
    idx = jnp.arange(T, dtype=jnp.int32)
    live = slot >= 0
    nxt = jnp.concatenate([slot[1:], jnp.full((1,), -2, slot.dtype)])
    first = live & (run_first(slot) | (idx % tile == 0))
    last = live & ((slot != nxt) | (idx % tile == tile - 1))
    n_units = jnp.sum(first)
    (row0,) = jnp.nonzero(first, size=max_units, fill_value=0)
    (row1,) = jnp.nonzero(last, size=max_units, fill_value=0)
    is_unit = jnp.arange(max_units) < n_units
    row0 = row0.astype(jnp.int32)
    at_last = row0[jnp.maximum(n_units - 1, 0)]
    cnt = jnp.where(is_unit, row1.astype(jnp.int32) - row0 + 1, 0)
    return jnp.where(is_unit, row0, at_last), cnt, is_unit


def _conv_kernel(row0_ref, cnt_ref, mode_ref, tfirst_ref, tlast_ref, in_ref,
                 out_ref, tile_ref, x_ref, w_ref, b_ref, s_ref, y_ref, o_ref,
                 ext, acc, *, tile: int, K: int):
    """One unit of work (module docstring).
    Prefetched, a unit: its first packed row, its rows (0: padding),
    where its tail comes from (0 the input block, 1 zeros, 2 the unit
    before it), whether it is its tile's first and its last, the entries
    and the tile.  ``x_ref`` / ``y_ref`` ``[tile, C]``, ``w_ref`` ``[K,
    C]``, ``b_ref`` ``[1, C]``, ``s_ref`` / ``o_ref`` ``[1, K - 1, C]``.
    Scratch: ``ext`` ``[_HALO + tile, C]`` the tile's rows in float32
    after ``_HALO`` free rows (a unit at the tile's first row lays its
    tail there), ``acc`` ``[tile, C]`` the tile's sums of taps."""
    u = pl.program_id(0)
    cnt, mode = cnt_ref[u], mode_ref[u]
    r = row0_ref[u] - tile_ref[u] * tile

    def taps():
        return w_ref[...].astype(jnp.float32)                 # [K, C]

    @pl.when(tfirst_ref[u] == 1)
    def _():
        ext[_HALO:, :] = x_ref[...].astype(jnp.float32)
        acc[...] = jnp.zeros_like(acc)

    def one_row(tail):
        # its window is the tail and the row
        win = jnp.concatenate([tail, ext[pl.ds(_HALO + r, 1), :]],
                              axis=0)                         # [K, C]
        acc[pl.ds(r, 1), :] = jnp.sum(win * taps(), axis=0, keepdims=True)
        o_ref[0] = win[1:].astype(o_ref.dtype)

    # a branch a source of the tail: a decode launch is a hundred of these
    for m, tail_of in enumerate([
            lambda: s_ref[0].astype(jnp.float32),
            lambda: jnp.zeros(s_ref.shape[1:], jnp.float32),
            lambda: o_ref[0].astype(jnp.float32)]):
        @pl.when((cnt == 1) & (mode == m))
        def _(tail_of=tail_of):
            one_row(tail_of())

    @pl.when(cnt > 1)
    def _():
        # rows [r, r + cnt) of the tile.  The tail goes where the K - 1
        # rows before the unit's first were (rows of units that are done,
        # or the free rows before the tile's), so every tap is the tile
        # shifted down and what the unit leaves is K - 1 rows in a row
        tail = jnp.where(mode == 2, o_ref[0].astype(jnp.float32),
                         jnp.where(mode == 1, 0.0,
                                   s_ref[0].astype(jnp.float32)))
        for k in range(K - 1):
            ext[pl.ds(_HALO + r - (K - 1) + k, 1), :] = tail[k:k + 1]
        w = taps()
        conv = sum(ext[pl.ds(_HALO - (K - 1) + k, tile), :] * w[k:k + 1]
                   for k in range(K))
        j = lax.broadcasted_iota(jnp.int32, (tile, 1), 0) - r
        acc[...] = jnp.where((j >= 0) & (j < cnt), conv, acc[...])
        o_ref[0] = jnp.concatenate(
            [ext[pl.ds(_HALO + r + cnt - (K - 1) + k, 1), :]
             for k in range(K - 1)], axis=0).astype(o_ref.dtype)

    @pl.when(tlast_ref[u] == 1)
    def _():
        # the bias and silu once a tile, every sublane at work
        conv = acc[...] + b_ref[...].astype(jnp.float32)
        y_ref[...] = (conv * jax.nn.sigmoid(conv)).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def packed_causal_conv(xbc, weight, bias, conv_pool, slot, src, dst, *,
                       tile_rows: int, interpret=False):
    """The kernel over packed rows, under
    ``packed_causal_conv_reference``'s arguments and results.
    ``conv_pool`` is written in place; rows with ``slot < 0`` write no
    live entry and give zeros; ``tile_rows`` divides the rows."""
    T, C = xbc.shape
    K = weight.shape[0]
    tile = int(tile_rows)
    if T % tile or tile % _HALO or not 2 <= K <= _HALO + 1:
        raise ValueError(f"{T} rows in tiles of {tile} (whole tiles of "
                         f"{_HALO} rows), {K} taps")
    U = ssd_max_units(T, tile, conv_pool.shape[0])
    trash = conv_pool.shape[0] - 1
    row0, cnt, is_unit = conv_units(slot, tile, U)
    first = run_first(slot)[row0]
    u_src, u_dst = src[row0], dst[row0]
    mode = jnp.where(first, jnp.where(u_src < 0, 1, 0), 2).astype(jnp.int32)
    u_in = jnp.where(is_unit, jnp.where(u_src < 0, u_dst, u_src), trash)
    u_out = jnp.where(is_unit, u_dst, trash).astype(jnp.int32)
    u_tile = (row0 // tile).astype(jnp.int32)
    prev_tile = jnp.concatenate([jnp.full((1,), -1, jnp.int32), u_tile[:-1]])
    next_tile = jnp.concatenate([u_tile[1:], jnp.full((1,), -1, jnp.int32)])
    nxt_unit = jnp.concatenate([is_unit[1:], jnp.zeros((1,), bool)])
    tfirst = (is_unit & (u_tile != prev_tile)).astype(jnp.int32)
    tlast = (is_unit & ((u_tile != next_tile) | ~nxt_unit)).astype(jnp.int32)

    in_specs = [
        pl.BlockSpec((tile, C), lambda u, *r: (r[7][u], 0)),
        pl.BlockSpec((K, C), lambda u, *r: (0, 0)),
        pl.BlockSpec((1, C), lambda u, *r: (0, 0)),
        pl.BlockSpec((1, K - 1, C), lambda u, *r: (r[5][u], 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((tile, C), lambda u, *r: (r[7][u], 0)),
        pl.BlockSpec((1, K - 1, C), lambda u, *r: (r[6][u], 0, 0)),
    ]
    y, conv_pool = pl.pallas_call(
        functools.partial(_conv_kernel, tile=tile, K=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8, grid=(U,), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((_HALO + tile, C), jnp.float32),
                            pltpu.VMEM((tile, C), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((T, C), xbc.dtype),
                   jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)],
        # operand 11 of the call (8 prefetched + 3) is the pool
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a tile of rows in and out twice (the pipeline's) and twice
            # in float32: 21 MB at the Nemotron cell's 10240 channels
            vmem_limit_bytes=64 * 1024 * 1024),
        name=CAUSAL_CONV_KERNEL,
        interpret=interpret,
    )(row0, cnt, mode, tfirst, tlast, u_in.astype(jnp.int32), u_out, u_tile,
      xbc, weight, bias.reshape(1, C), conv_pool)
    # a tile with no unit was never visited: its block is not written
    return jnp.where((slot >= 0)[:, None], y, 0), conv_pool
