"""Kimi Delta Attention's gated delta-rule scan over the serving engine's
PACKED rows (Pallas TPU kernel ``kda_delta_scan``) and the sequential
scan it is tested against.

A KDA head keeps a state ``S`` in ``R^{d x d}`` (key x value) a sequence
and, a token, decays every KEY CHANNEL by its own factor and then
corrects the state by a rank-one term that reads it:

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(``q_t``, ``k_t``, ``v_t`` in ``R^d``, ``g_t <= 0`` in ``R^d``, ``beta_t``
a scalar a head).  Mamba-2's scan (``ssd_scan.py``) has ONE scalar decay
a head and ADDS an outer product; this update reads what it corrects, so
its chunked form needs the inverse of a unit-lower-triangular matrix a
head a unit.  What the two share is the engine's side: the states live
in ONE pool ``[entries, H, d, d]`` (float32), a packed row names the
entry its slot STARTS from (``src``; below zero: zeros) and the entry
the slot's state is LEFT in (``dst``), the units of work are
``ssd_scan.scan_units``' (a maximal run of one slot's rows inside one
tile of ``tile_rows``), the grid is (block of heads, unit), a slot's
further units in the same launch find its state in the output block that
stays in VMEM, and padding units point at the pool's LAST entry, the
trash entry.

A unit of ONE row (a decoding slot) takes the recurrent form above, all
of a block's heads at once on the VPU: the state is read, decayed, read
against ``k`` (a sum over its sublanes), given the rank-one term, read
against ``q`` and written: two passes over the state and nothing else of
its size.  The row's ``k``, ``q`` and ``g`` meet the state as COLUMNS (a
key channel is a sublane of ``S``): they come from transposed copies of
the rows (``[H, d, T]``), rolled so that the row's column stands in lane
0, and broadcast along the lanes from there.

A longer unit of ``C`` rows takes the chunked form on the MXU.  With
``G_i = sum_{s <= i} g_s`` a channel (from the unit's first row) and
``M[i, j] = sum_c a_ic b_jc exp(G_ic - G_jc)`` for ``i >= j``:

    T = (I + strict_lower(Diag(beta) M(K, K)))^-1
    W = T Diag(beta) (K * e^G),  U = T Diag(beta) V,  D = U - W S_0
    O = (Q * e^G) S_0 + lower(M(Q, K)) D
    S_C = Diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T D

``e^{-G}`` is never formed: a channel that decays hard (``exp(A_log) *
softplus`` reaches 16 and more a row) takes it past float32 within two
rows.  ``M`` and ``T`` are built by HALVING: at the level of segments of
``2 s`` rows (``s`` = 1, 2, 4, ... ``tile / 2``) the pairs with ``i`` in
a segment's upper half and ``j`` in its lower half factor through the
segment's midpoint ``m``, ``exp(G_i - G_j) = exp(sum_{m <= t <= i} g_t)
exp(sum_{j < t < m} g_t)``, both exponents sums of ``g`` over rows
BETWEEN the two and so never above zero (taken as masked sums of ``g``
itself, a matmul with a 0/1 matrix: no difference of two large
cumulative sums either); one masked ``[tile, d] x [d, tile]`` product a
level gives that level's pairs of ``M``, every pair at exactly one
level.  The same segments invert the triangle: with the two halves'
inverses on the diagonal of ``T``, ``T <- T - T A_level T`` is the
inverse of the whole segment (block forward substitution: no power of
``A`` is ever formed).  Rows of the tile that are not the unit's are
zeroed on the way in and keep what their own unit wrote on the way out.

The ORDER of a chunk unit's products is what its time hangs on: ``T``'s
updates are a chain, each waiting for the one before, and a product of
``[128, 128]`` tiles loads its weights for as long as it streams its
rows.  So (1) everything that does not read ``T`` comes first.  The
masked sums of ``g`` of ALL levels, ``G``'s triangle and the sums of the
rows AFTER a row (what is left of its term at the unit's end) are ONE
stacked product ``[(levels + 2) tile, tile] x [tile, d]`` a bf16 part of
``g`` (``_level_masks``), and one ``exp`` over the result; then every
level's ``M(K, K)`` and ``M(Q, K)``, ``k``'s and ``q``'s upper rows
stacked against the lower rows of ``k``.  (2) At the first level ``T``
is the identity, so ``T - T A T = I - A`` exactly and costs no product:
the chain is two products a level from the second level on, and from
the level whose halves are whole register tiles (8 rows) on they stream
the UPPER rows of each segment alone (``A``'s lower rows are zero and
``T``'s blocks are the halves', so the other rows' terms are exact
zeros).  (3) The unit's whole decay a channel is a sum along the lanes
of ``g``'s transposed copy, and the state's update contracts over the
rows' dimension of ``K * e^{G_C - G}``: neither is a product with the
transposed rows.  (4) The heads of a block share nothing, so a loop's
turn takes TWO: both heads' loads, then both chains in one basic block
for the compiler to run one under the other, then both stores.

Precision: the state, ``g``, ``beta``, every exponent and ``T`` in
float32 (``T``'s updates at the highest precision); the other matmuls'
operands in the rows' dtype (bf16 rows: bf16 operands, float32
accumulation, the float32 state in two bf16 parts; float32 rows:
float32 at the highest precision).

``kda_scan_reference`` is the same function as a plain ``lax.scan`` a
ROW: the CPU path (``core/device.pallas_interpret``) and the kernel's
test oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssd_scan import run_first, scan_units

__all__ = ["KDA_SCAN_KERNEL", "kda_delta_scan", "kda_scan_reference"]

#: the kernel's name in a device trace
KDA_SCAN_KERNEL = "kda_delta_scan"

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
_SUBLANES = 8           # rows of a float32 register tile


def kda_scan_reference(q, k, v, g, beta, pool, slot, src, dst):
    """The scan a row at a time.  ``q``, ``k``, ``v`` ``[T, H, d]`` (``q``
    and ``k`` as they meet the state: normalised, ``q`` scaled), ``g``
    ``[T, H, d]`` float32 log-decays (``<= 0``), ``beta`` ``[T, H]``
    float32, ``pool`` ``[entries, H, d, d]`` float32 (key x value; the
    LAST entry the trash entry); ``slot``, ``src``, ``dst`` int32
    ``[T]``.  Returns ``(o [T, H, d] float32, pool)``; rows with ``slot
    < 0`` give zeros and write the trash entry."""
    first = run_first(slot)
    trash = pool.shape[0] - 1

    def body(carry, t):
        pool, cur = carry
        fresh = jnp.where(src[t] < 0, 0.0, pool[jnp.maximum(src[t], 0)])
        s = jnp.where(first[t], fresh, cur)                   # [H, dk, dv]
        kt, qt, vt = (a[t].astype(_F32) for a in (k, q, v))
        s = s * jnp.exp(g[t])[:, :, None]
        u = jnp.sum(s * kt[:, :, None], axis=1)               # S'^T k
        s = s + (beta[t][:, None] * kt)[:, :, None] * (vt - u)[:, None, :]
        o = jnp.sum(s * qt[:, :, None], axis=1)
        live = slot[t] >= 0
        pool = pool.at[jnp.where(live, dst[t], trash)].set(s)
        return (pool, jnp.where(live, s, cur)), jnp.where(live, o, 0.0)

    (pool, _), o = lax.scan(body, (pool, jnp.zeros_like(pool[0])),
                            jnp.arange(q.shape[0]))
    return o, pool


def _dot(a, b, mm, dims=None):
    """``a @ b`` (``dims``: the contracting dimensions) in the operands'
    dtype ``mm``, float32 out: float32 at the highest precision."""
    prec = _HI if mm == _F32 else None
    a, b = a.astype(mm), b.astype(mm)
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=_F32, precision=prec)
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=_F32, precision=prec)


def _parts(x, mm, n: int):
    """Float32 ``x`` as ``n`` parts of dtype ``mm`` that add up to it (to
    ``2^-8n`` of it for bf16)."""
    out = []
    for _ in range(n):
        p = x.astype(mm)
        out.append(p)
        x = x - p.astype(_F32)
    return out


def _dot_exact(mask, x, mm):
    """``mask @ x`` for a 0/1 matrix and a float32 ``x``, to float32's
    precision: bf16 rows take ``x`` in three bf16 parts (a 0/1 matrix is
    exact in bf16), not the six passes of a float32 matmul."""
    if mm == _F32:
        return _dot(mask, x, mm)
    return sum(_dot(mask, p, mm) for p in _parts(x, mm, 3))


def _halves(x, half: int):
    """``(lower rows, upper rows)`` of ``x``: the rows in the lower and in
    the upper half of every segment of ``2 half`` rows, in order."""
    return tuple(jnp.concatenate([x[n + at:n + at + half] for n in
                                  range(0, x.shape[0], 2 * half)], axis=0)
                 for at in (0, half))


def _level_masks(tile: int, mm):
    """The 0/1 matrices of the chunked form, which depend on the tile
    alone.  ``sums`` ``[(L + 2) tile, tile]`` in ``mm``, ``L = log2(tile)``
    levels: level ``l``'s block gives an upper row of a segment of
    ``2^(l + 1)`` rows the rows from the segment's midpoint ``m`` to
    itself and a lower row the rows after itself and before ``m``; block
    ``L`` is the triangle (the rows up to a row: ``G``), block ``L + 1``
    the rows after a row (what is left of its term at the tile's end).
    ``pairs``, float32 ``[tile, tile]`` a level: the pairs (upper ``i``,
    lower ``j``) of one segment, every pair ``i > j`` at exactly one
    level."""
    i0 = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    i1 = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    sums, pairs = [], []
    s = 1
    while s < tile:
        up_0, up_1 = (i0 & (2 * s - 1)) >= s, (i1 & (2 * s - 1)) >= s
        mid = (i0 & -(2 * s)) + s             # row i's segment's midpoint
        sums.append((up_0 & (i1 >= mid) & (i1 <= i0))
                    | (~up_0 & (i1 > i0) & (i1 < mid)))
        pairs.append((((i0 & -(2 * s)) == (i1 & -(2 * s))) & up_0
                      & ~up_1).astype(_F32))
        s *= 2
    sums += [i1 <= i0, i1 > i0]
    return jnp.concatenate([m.astype(mm) for m in sums], axis=0), pairs


def _chunk(q, k, v, g, beta, gT, s0, r, cnt, *, tile: int, mm):
    """The chunked form of one head over rows ``[r, r + cnt)`` of a tile
    (module docstring).  ``q``, ``k``, ``v``, ``g`` ``[tile, d]``,
    ``beta`` ``[tile, 1]``, ``gT`` ``[d, tile]``, all float32; ``s0``
    ``[d, d]``.  Returns ``(o [tile, d], the state after the unit)``; rows
    of ``o`` outside the unit mean nothing."""
    sums, pairs = _level_masks(tile, mm)
    levels = len(pairs)
    sub = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    m_col = (sub >= r) & (sub < r + cnt)
    q, k, g = (jnp.where(m_col, a, 0.0) for a in (q, k, g))
    beta = jnp.where(m_col, beta, 0.0)
    gT = jnp.where((lane >= r) & (lane < r + cnt), gT, 0.0)
    i0 = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    i1 = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)

    # every masked sum of g in ONE stacked product (a bf16 part of g is
    # loaded once for all its blocks), and their exponentials
    e = jnp.exp(_dot_exact(sums, g, mm))           # [(levels + 2) tile, d]

    def block(n):
        return e[n * tile:(n + 1) * tile]

    # M(K, K) below the diagonal a level, M(Q, K) on and below it: they
    # read k, q and the exponentials alone, so all come before T's chain
    mqk = jnp.where(i0 == i1, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    a_levels = []
    for lv in range(levels):
        half = 1 << lv
        up_c = (sub & (2 * half - 1)) >= half                 # [tile, 1]
        ke = k * block(lv)
        kq_up = jnp.concatenate([jnp.where(up_c, ke, 0.0),
                                 jnp.where(up_c, q * block(lv), 0.0)], axis=0)
        both = _dot(kq_up, jnp.where(up_c, 0.0, ke), mm, ((1,), (1,)))
        a_levels.append(beta * (pairs[lv] * both[:tile]))
        mqk = mqk + pairs[lv] * both[tile:]

    # the triangle's inverse by halving: at level 1 T is the identity, so
    # T - T A T = I - A and costs no product; then the chain, which holds
    # nothing but its own products
    T = (i0 == i1).astype(_F32) - a_levels[0]
    for lv, a in enumerate(a_levels[1:], 1):
        half = 1 << lv
        if half < _SUBLANES:
            T = T - _dot(_dot(T, a, _F32), T, _F32)
            continue
        # A's lower rows are zero and T's blocks are the halves', so the
        # update moves each segment's UPPER rows alone: half the rows to
        # stream, once a half is whole register tiles
        lo, up = _halves(T, half)
        up = up - _dot(_dot(up, a, _F32), T, _F32)
        T = jnp.concatenate([x[n:n + half] for n in range(0, tile // 2, half)
                             for x in (lo, up)], axis=0)

    eg = block(levels)                                        # e^G
    w = _dot(T, beta * (k * eg), mm)
    u = _dot(T, beta * v, mm)
    s_parts = [s0] if mm == _F32 else _parts(s0, mm, 2)
    dlt = u - sum(_dot(w, p, mm) for p in s_parts)            # D
    o = sum(_dot(q * eg, p, mm) for p in s_parts) + _dot(mqk, dlt, mm)
    # what of each row's term is left at the unit's end, summed over the
    # rows; the whole unit's decay a channel, channels down
    left = _dot(k * block(levels + 1), dlt, mm, ((0,), (0,)))
    total = jnp.exp(jnp.sum(gT, axis=1, keepdims=True))       # [d, 1]
    return o, s0 * total + left


def _kda_kernel(row0_ref, cnt_ref, mode_ref, tfirst_ref, in_ref, out_ref,
                tile_ref, q_ref, k_ref, v_ref, g_ref, b_ref, qT_ref, kT_ref,
                gT_ref, s_ref, o_ref, so_ref, *, tile: int, hb: int, d: int,
                mm):
    """One unit of work of one block of ``hb`` heads (module docstring).
    Prefetched, a unit: ``ssd_scan.scan_units``.  ``q_ref``, ``k_ref``,
    ``v_ref``, ``g_ref``, ``b_ref`` (beta, along the lanes) and ``o_ref``
    ``[hb, tile, d]``, ``qT_ref``, ``kT_ref``, ``gT_ref`` ``[hb, d,
    tile]``, all float32; ``s_ref`` / ``so_ref`` ``[1, hb, d, d]``."""
    u = pl.program_id(1)
    cnt, mode = cnt_ref[u], mode_ref[u]
    r = row0_ref[u] - tile_ref[u] * tile

    @pl.when(tfirst_ref[u] == 1)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def state_in(at=slice(None)):
        return jnp.where(mode == 2, so_ref[0, at],
                         jnp.where(mode == 1, 0.0, s_ref[0, at]))

    @pl.when(cnt == 1)
    def _():
        # the recurrent form, the block's heads at once
        def column(ref):
            # row r of the tile as a column a head, in lane 0
            x = pltpu.roll(ref[...].reshape(hb * d, tile),
                           lax.rem(tile - r, tile), 1)
            return x[:, :1].reshape(hb, d, 1)

        at = pl.ds(r, 1)
        s = state_in() * jnp.exp(column(gT_ref))
        kc = column(kT_ref)
        got = jnp.sum(s * kc, axis=1, keepdims=True)          # [hb, 1, d]
        s = s + kc * (b_ref[:, at, :] * (v_ref[:, at, :] - got))
        so_ref[0] = s
        o_ref[:, at, :] = jnp.sum(s * column(qT_ref), axis=1, keepdims=True)

    @pl.when(cnt > 1)
    def _():
        sub = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        in_unit = (sub >= r) & (sub < r + cnt)

        def run(heads):
            # the heads share nothing: every load before the first
            # product and every store after the last, so that one head's
            # chain of products runs under the other's
            done = [_chunk(q_ref[h], k_ref[h], v_ref[h], g_ref[h],
                           b_ref[h][:, :1], gT_ref[h], state_in(h), r, cnt,
                           tile=tile, mm=mm) for h in heads]
            for h, (o, s) in zip(heads, done):
                so_ref[0, h] = s
                o_ref[h] = jnp.where(in_unit, o, o_ref[h])

        def pair(p, carry):
            run([2 * p, 2 * p + 1])
            return carry

        lax.fori_loop(0, hb // 2, pair, 0)
        if hb % 2:
            run([hb - 1])


@functools.partial(jax.jit, static_argnames=("tile_rows", "max_units",
                                             "heads_per_step", "interpret"))
def kda_delta_scan(q, k, v, g, beta, pool, slot, lens, src, dst, *,
                   tile_rows: int = 128, max_units=None,
                   heads_per_step: int = 8, interpret=False):
    """The kernel over packed rows, under ``kda_scan_reference``'s
    arguments and results plus ``lens`` (the rows' visibilities, for
    ``ragged_units``).  ``pool``'s last entry is the trash entry;
    ``tile_rows``, a power of two, divides the rows; the matmuls'
    operands take ``q``'s dtype."""
    T, H, d = q.shape
    tile, hb = int(tile_rows), min(int(heads_per_step), H)
    if T % tile or tile & (tile - 1) or H % hb:
        raise ValueError(f"{T} rows in tiles of {tile} (a power of two), "
                         f"{H} heads in blocks of {hb}")
    units = scan_units(slot, lens, src, dst, tile, int(max_units or T),
                       pool.shape[0] - 1)
    rows = [a.astype(_F32).transpose(1, 0, 2) for a in (q, k, v, g)]
    rows.append(jnp.broadcast_to(beta.astype(_F32).T[:, :, None], (H, T, d)))
    cols = [rows[i].transpose(0, 2, 1) for i in (0, 1, 3)]    # [H, d, T]

    row_spec = pl.BlockSpec((hb, tile, d), lambda b, u, *r: (b, r[6][u], 0))
    col_spec = pl.BlockSpec((hb, d, tile), lambda b, u, *r: (b, 0, r[6][u]))
    o, pool = pl.pallas_call(
        functools.partial(_kda_kernel, tile=tile, hb=hb, d=d,
                          mm=jnp.dtype(q.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(units), grid=(H // hb, units[0].shape[0]),
            in_specs=[row_spec] * 5 + [col_spec] * 3 + [
                pl.BlockSpec((1, hb, d, d),
                             lambda b, u, *r: (r[4][u], b, 0, 0))],
            out_specs=[row_spec,
                       pl.BlockSpec((1, hb, d, d),
                                    lambda b, u, *r: (r[5][u], b, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((H, T, d), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is the call's last operand
        input_output_aliases={len(units) + 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        name=KDA_SCAN_KERNEL,
        interpret=interpret,
    )(*units, *rows, *cols, pool)
    # a tile with no unit was never visited: its block is not written
    o = jnp.where((slot >= 0)[:, None, None], o.transpose(1, 0, 2), 0.0)
    return o, pool
