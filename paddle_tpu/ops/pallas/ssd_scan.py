"""Mamba-2's selective scan (SSD) over the serving engine's PACKED rows
(Pallas TPU kernel ``mamba2_ssd_scan``) and the sequential scan it is
tested against.

A Mamba-2 head keeps a state ``S`` in ``R^{P x N}`` a sequence and, a
token, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
C_t`` (``x_t`` in ``R^P``, ``B_t``, ``C_t`` in ``R^N`` shared by the
heads of a GROUP, ``dt_t > 0`` and ``A < 0`` scalars a head).  The
engine's step carries rows of MANY sequences in one launch: a decode
row of each decoding slot and a prompt chunk of a few.  The states live
in ONE pool ``[entries, H, P, N]`` (float32): a packed row names the
entry its slot STARTS from (``src``: the slot's own, a snapshot's, or
below zero for a fresh sequence: zeros) and the entry the slot's state
is LEFT in (``dst``).  A slot's rows are consecutive, and all of them
carry the same two numbers.

Units of work are ``decode_attention.ragged_units``': a maximal run of
one slot's rows inside one tile of ``tile_rows`` (the model's
``chunk_size``).  The grid is (block of heads, unit): a block of ``hb``
heads holds one or more whole GROUPS, each head reading its own group's
``B`` and ``C`` (Mamba-2: a block is one group of ``H / G`` heads that
share them; linear attention, where every head has its own key and
query, ``G = H``: a block of ``heads_per_step`` heads with a ``B`` and a
``C`` each); a unit of one row
takes the recurrent form (the state is read, decayed, given one outer
product, read out against ``C`` and written: two passes over the state
and nothing else of its size); a longer unit takes the chunked form:
inside the unit the masked ``C B^T`` products and the decays between
its rows, ``[tile, tile]`` a head, on the MXU; the state enters through
``S_0 C^T`` and leaves as ``exp(sum a) S_0 + (x dt decay)^T B``.  A
slot's further units in the same launch find its state where the unit
before left it, in the output block that stays in VMEM while the
entry's index does not change, so a chunk of 512 rows reads its state
once and writes it once.  Padding units point at the pool's LAST entry,
the trash entry: no live state is written by them.

Everything a unit needs a ROW of is laid out with the rows along the
lanes (``x^T [H P, T]``, ``dt^T``, ``a^T``), so that the one row of a
decode unit is picked by a one-hot matmul on the otherwise idle MXU
(exact: one term a sum) and arrives broadcast along the state's lanes,
and the chunked form's matmuls are all plain or transposed-rhs ones.
``y`` leaves transposed for the same reason.

Precision: states, decays and ``dt`` in float32 throughout; the chunked
form's matmul operands in the rows' dtype (bf16 rows: bf16 operands,
float32 accumulation; float32 rows: float32 at the highest precision);
the read-out of a float32 state against ``C`` in float32 always.

``ssd_scan_reference`` is the same function as a plain ``lax.scan`` a
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

from .decode_attention import ragged_units

__all__ = ["SSD_SCAN_KERNEL", "mamba2_ssd_scan", "ssd_scan_reference",
           "ssd_max_units", "run_first", "scan_units"]

#: the kernel's name in a device trace
SSD_SCAN_KERNEL = "mamba2_ssd_scan"

_HI = lax.Precision.HIGHEST


def ssd_max_units(rows: int, tile_rows: int, max_slots=None) -> int:
    """The most units of work ``rows`` packed rows can hold: a unit a
    slot and one more for each tile boundary a slot's rows may cross."""
    if max_slots is None:
        return rows
    return min(rows, int(max_slots) + -(-rows // tile_rows))


def run_first(slot):
    """Whether each packed row is the first of its slot's run of rows (a
    slot's rows are consecutive)."""
    prev = jnp.concatenate([jnp.full((1,), -2, slot.dtype), slot[:-1]])
    return slot != prev


def scan_units(slot, lens, src, dst, tile: int, U: int, trash: int):
    """What a scan kernel over packed rows prefetches, ``U`` units of
    work (``ragged_units``: a maximal run of one slot's rows inside one
    tile of ``tile`` rows): a unit's first packed row, its rows (0:
    padding), where its state comes from (0 the input block, 1 zeros, 2
    the unit before it), whether it is its tile's first, the entries its
    state is read from and left in (padding units: ``trash``) and its
    tile.  Padding units stand at the last live unit's row."""
    count, _ = ragged_units(slot, lens, tile, jnp)
    n_units = jnp.sum(count > 0)
    (row0,) = jnp.nonzero(count > 0, size=U, fill_value=0)
    row0 = row0.astype(jnp.int32)
    live = jnp.arange(U) < n_units
    last = row0[jnp.maximum(n_units - 1, 0)]
    row0 = jnp.where(live, row0, last)        # padding: the last live tile
    cnt = jnp.where(live, count[row0], 0).astype(jnp.int32)
    first = run_first(slot)[row0]
    u_src, u_dst = src[row0], dst[row0]
    mode = jnp.where(first, jnp.where(u_src < 0, 1, 0), 2).astype(jnp.int32)
    u_in = jnp.where(live, jnp.where(u_src < 0, u_dst, u_src), trash)
    u_out = jnp.where(live, u_dst, trash).astype(jnp.int32)
    u_tile = (row0 // tile).astype(jnp.int32)
    prev_tile = jnp.concatenate([jnp.full((1,), -1, jnp.int32), u_tile[:-1]])
    tfirst = (live & (u_tile != prev_tile)).astype(jnp.int32)
    return (row0, cnt, mode, tfirst, u_in.astype(jnp.int32), u_out, u_tile)


def ssd_scan_reference(x, dt, a, B, C, pool, slot, src, dst):
    """The scan a row at a time.  ``x`` ``[T, H, P]``, ``dt`` and ``a =
    dt * A`` ``[T, H]`` float32, ``B`` / ``C`` ``[T, G, N]``, ``pool``
    ``[entries, H, P, N]`` float32; ``slot``, ``src``, ``dst`` int32
    ``[T]``.  Returns ``(y [T, H, P] float32, pool)``; rows with ``slot
    < 0`` give zeros and write nothing."""
    T, H, P = x.shape
    G = B.shape[1]
    first = run_first(slot)
    rep = H // G

    def body(carry, t):
        pool, cur = carry
        fresh = jnp.where(src[t] < 0, 0.0, pool[jnp.maximum(src[t], 0)])
        s = jnp.where(first[t], fresh, cur)
        Bt = jnp.repeat(B[t].astype(jnp.float32), rep, axis=0)    # [H, N]
        Ct = jnp.repeat(C[t].astype(jnp.float32), rep, axis=0)
        xt = x[t].astype(jnp.float32) * dt[t][:, None]            # [H, P]
        s1 = s * jnp.exp(a[t])[:, None, None] \
            + xt[:, :, None] * Bt[:, None, :]
        y = jnp.sum(s1 * Ct[:, None, :], axis=-1)
        live = slot[t] >= 0
        pool = jnp.where(live, pool.at[dst[t]].set(s1), pool)
        return (pool, jnp.where(live, s1, cur)), jnp.where(live, y, 0.0)

    (pool, _), y = lax.scan(body, (pool, jnp.zeros_like(pool[0])),
                            jnp.arange(T))
    return y, pool


def _dot(a, b, dims=None):
    """A matmul on the MXU, float32 out: float32 operands at the highest
    precision, narrower ones as they are."""
    prec = _HI if a.dtype == jnp.float32 else None
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32,
                       precision=prec)
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=prec)


def _dot_f32(p, f, mm):
    """``p @ f`` for a float32 ``p`` and a 0/1 matrix ``f``: where the
    rows are bf16, two bf16 passes (``p``'s upper and lower halves; the
    error is 2^-17 of a term) instead of the six of a float32 matmul."""
    if mm == jnp.float32:
        return jnp.dot(p, f.astype(jnp.float32), precision=_HI,
                       preferred_element_type=jnp.float32)
    hi = p.astype(mm)
    lo = (p - hi.astype(jnp.float32)).astype(mm)
    f = f.astype(mm)
    return (jnp.dot(hi, f, preferred_element_type=jnp.float32)
            + jnp.dot(lo, f, preferred_element_type=jnp.float32))


def _ssd_kernel(row0_ref, cnt_ref, mode_ref, tfirst_ref, in_ref, out_ref,
                tile_ref, xT_ref, b_ref, c_ref, ar_ref, cs_ref, s_ref,
                yT_ref, o_ref, *, tile: int, hb: int, P: int, N: int,
                hpg: int):
    """One unit of work of one block of ``hb`` heads, ``hpg`` heads a
    group (module docstring).
    Prefetched, a unit: its first packed row, its rows (0: padding),
    where its state comes from (0 the input block, 1 zeros, 2 the unit
    before it), whether it is its tile's first, the entries and the
    tile.  ``xT_ref`` ``[hb P, tile]``, ``b_ref`` / ``c_ref`` ``[tile,
    N]`` (a block of one group) or ``[hb / hpg, tile, N]``, ``ar_ref`` ``[1,
    tile, hb]`` (``a``, rows down), ``cs_ref`` ``[1, 2 hb, tile]``
    (``dt`` then ``a``, rows along), ``s_ref`` / ``o_ref`` ``[1, hb, P,
    N]``, ``yT_ref`` ``[hb P, tile]``."""
    u = pl.program_id(1)
    cnt, mode = cnt_ref[u], mode_ref[u]
    r = row0_ref[u] - tile_ref[u] * tile
    mm = xT_ref.dtype

    @pl.when(tfirst_ref[u] == 1)
    def _():
        yT_ref[...] = jnp.zeros_like(yT_ref)

    def state_in():
        return jnp.where(mode == 2, o_ref[0],
                         jnp.where(mode == 1, 0.0, s_ref[0]))

    def of_group(ref, h, rows=slice(None)):
        """Head ``h``'s group's rows of ``b_ref`` / ``c_ref``: ``[tile,
        N]`` where the block is one group, else ``[groups, tile, N]``."""
        if hb == hpg:
            return ref[rows, :]
        return ref[h // hpg, rows, :]

    @pl.when(cnt == 1)
    def _():
        # the recurrent form: row r of the tile, broadcast along lanes
        s = state_in()
        pick = lax.broadcasted_iota(jnp.int32, (tile, N), 0) == r
        xb = _dot(xT_ref[...], pick.astype(mm)).reshape(hb, P, N)
        w = jnp.dot(cs_ref[0], pick.astype(jnp.float32), precision=_HI,
                    preferred_element_type=jnp.float32)       # [2 hb, N]
        dt_b, a_b = w[:hb], w[hb:]
        place = lax.broadcasted_iota(jnp.int32, (N, tile), 1) == r
        if hb == hpg:
            # ONE group: its row of B and of C serves every head
            brow = b_ref[pl.ds(r, 1), :]                      # [1, N]
            crow = c_ref[pl.ds(r, 1), :]
            s1 = s * jnp.exp(a_b)[:, None, :] \
                + (xb * dt_b[:, None, :]) * brow[None]
            o_ref[0] = s1
            yT_ref[...] += _dot_f32((s1 * crow[None]).reshape(hb * P, N),
                                    place, mm)
        else:
            for h in range(hb):
                s1 = s[h] * jnp.exp(a_b[h:h + 1]) \
                    + (xb[h] * dt_b[h:h + 1]) \
                    * of_group(b_ref, h, pl.ds(r, 1))
                o_ref[0, h] = s1
                yT_ref[h * P:(h + 1) * P, :] += _dot_f32(
                    s1 * of_group(c_ref, h, pl.ds(r, 1)), place, mm)

    @pl.when(cnt > 1)
    def _():
        # the chunked form over rows [r, r + cnt) of the tile
        s = state_in()
        lane = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        sub = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        m_row = (lane >= r) & (lane < r + cnt)                # along t
        m_col = (sub >= r) & (sub < r + cnt)                  # along r
        i0 = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
        i1 = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
        tri_f = (i0 >= i1).astype(jnp.float32)
        cs = cs_ref[0]
        dt_c = jnp.where(m_row, cs[:hb], 0.0)                 # [hb, tile]
        a_c = jnp.where(m_row, cs[hb:], 0.0)
        a_r = jnp.where(m_col, ar_ref[0], 0.0)                # [tile, hb]
        # cumulative log-decays, rows down (L) and rows along (LT)
        L = jnp.dot(tri_f, a_r, precision=_HI,
                    preferred_element_type=jnp.float32)       # [tile, hb]
        LT = lax.dot_general(a_c, tri_f, (((1,), (1,)), ((), ())),
                             precision=_HI,
                             preferred_element_type=jnp.float32)
        # a unit's whole log-decay, along the lanes of a tile and of a
        # state row (a [1, 1] value broadcasts neither way in one step)
        tot_t = jnp.dot(a_c, jnp.ones((tile, tile), jnp.float32),
                        precision=_HI, preferred_element_type=jnp.float32)
        tot_n = jnp.dot(a_c, jnp.ones((tile, N), jnp.float32),
                        precision=_HI, preferred_element_type=jnp.float32)
        valid = (i1 >= i0) & m_col & m_row                    # t >= r, live
        groups = {}
        for h in range(hb):
            if h // hpg not in groups:
                bm = of_group(b_ref, h).astype(mm)
                groups[h // hpg] = (bm, _dot(
                    bm, of_group(c_ref, h).astype(mm), ((1,), (1,))))
            bm, gT = groups[h // hpg]                         # gT [r, t]
            s0 = s[h]                                         # [P, N]
            lt = LT[h:h + 1, :]                               # [1, tile]
            dec = jnp.exp(jnp.where(valid, lt - L[:, h:h + 1], -jnp.inf))
            xdt = xT_ref[h * P:(h + 1) * P, :].astype(jnp.float32) \
                * dt_c[h:h + 1, :]                            # [P, tile]
            y = _dot(xdt.astype(mm), (gT * dec).astype(mm))   # [P, t]
            y0 = lax.dot_general(s0, of_group(c_ref, h),
                                 (((1,), (1,)), ((), ())), precision=_HI,
                                 preferred_element_type=jnp.float32)
            y = y + y0 * jnp.exp(lt)
            yT_ref[h * P:(h + 1) * P, :] += jnp.where(m_row, y, 0.0)
            xw = xdt * jnp.exp(jnp.where(m_row, tot_t[h:h + 1] - lt, -jnp.inf))
            o_ref[0, h] = s0 * jnp.exp(tot_n[h:h + 1]) \
                + _dot(xw.astype(mm), bm)


@functools.partial(jax.jit, static_argnames=("tile_rows", "max_units",
                                             "heads_per_step", "interpret"))
def mamba2_ssd_scan(x, dt, a, B, C, pool, slot, lens, src, dst, *,
                    tile_rows: int, max_units=None, heads_per_step=None,
                    interpret=False):
    """The kernel over packed rows, under ``ssd_scan_reference``'s
    arguments and results plus ``lens`` (the rows' visibilities, for
    ``ragged_units``).  ``pool``'s last entry is the trash entry;
    ``tile_rows`` divides the rows.  ``heads_per_step``: the heads of a
    grid step's block, whole groups of them (left unset: one group)."""
    T, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    tile = int(tile_rows)
    if T % tile or H % G:
        raise ValueError(f"{T} rows in tiles of {tile}, {H} heads in "
                         f"{G} groups")
    hpg = H // G
    hb = int(heads_per_step or hpg)
    if hb % hpg or H % hb:
        raise ValueError(f"a step's {hb} heads are whole groups of {hpg} "
                         f"and divide the {H} heads")
    gb, G = hb // hpg, H // hb          # groups a block; blocks
    U = int(max_units or T)
    units = scan_units(slot, lens, src, dst, tile, U, pool.shape[0] - 1)

    xT = x.reshape(T, H * P).T                                # [H P, T]
    a_r = a.reshape(T, G, hb).transpose(1, 0, 2)              # [G, T, hb]
    cs = jnp.concatenate([dt.reshape(T, G, hb), a.reshape(T, G, hb)],
                         axis=-1).transpose(1, 2, 0)          # [G, 2hb, T]
    # float32: a decode unit slices ONE row of them, which a packed
    # dtype's tiles do not give; the chunked form casts its tiles
    if gb == 1:
        Bf = B.reshape(T, G * N).astype(jnp.float32)
        Cf = C.reshape(T, G * N).astype(jnp.float32)
        bc_spec = pl.BlockSpec((tile, N), lambda g, u, *r: (r[6][u], g))
    else:
        # group-major: a group's rows are a [tile, N] slab of the block
        Bf = B.astype(jnp.float32).transpose(1, 0, 2)         # [groups, T, N]
        Cf = C.astype(jnp.float32).transpose(1, 0, 2)
        bc_spec = pl.BlockSpec((gb, tile, N),
                               lambda g, u, *r: (g, r[6][u], 0))

    in_specs = [
        pl.BlockSpec((hb * P, tile), lambda g, u, *r: (g, r[6][u])),
        bc_spec, bc_spec,
        pl.BlockSpec((1, tile, hb), lambda g, u, *r: (g, r[6][u], 0)),
        pl.BlockSpec((1, 2 * hb, tile), lambda g, u, *r: (g, 0, r[6][u])),
        pl.BlockSpec((1, hb, P, N), lambda g, u, *r: (r[4][u], g, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((hb * P, tile), lambda g, u, *r: (g, r[6][u])),
        pl.BlockSpec((1, hb, P, N), lambda g, u, *r: (r[5][u], g, 0, 0)),
    ]
    yT, pool = pl.pallas_call(
        functools.partial(_ssd_kernel, tile=tile, hb=hb, P=P, N=N, hpg=hpg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=(G, U), in_specs=in_specs,
            out_specs=out_specs),
        out_shape=[jax.ShapeDtypeStruct((H * P, T), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 12 of the call (7 prefetched + 5) is the pool
        input_output_aliases={12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name=SSD_SCAN_KERNEL,
        interpret=interpret,
    )(*units, xT, Bf, Cf, a_r, cs, pool)
    # a tile with no unit was never visited: its block is not written
    y = jnp.where((slot >= 0)[:, None], yT.T, 0.0).reshape(T, H, P)
    return y, pool
