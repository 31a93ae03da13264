"""The ragged paged attention kernel (``ops/pallas/decode_attention.
ragged_paged_decode_raw``) against a dense fp32 reference built from the
same pages, in interpret mode: the page walk inside the kernel, a tile of
one slot's rows reading that slot's pages once, nothing for padding.

The launches are small (pages of 8 positions) but cross every boundary
the kernel has: pages, turns of ``pages_per_step`` pages, query tiles,
the narrow window of a short run against the whole tile, units of work
of several slots in one tile.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.paged_layout import ragged_kv_tokens_read
from paddle_tpu.ops.pallas.decode_attention import (
    ragged_paged_decode_raw, ragged_tile_rows, ragged_units)

PAGE, KVH, SLOTS, WIDTH = 8, 2, 6, 14          # 14 pages = 112 positions


def reference(q, kc, vc, lens, slot, tables, scale, window=None):
    """Row r attends positions < lens[r] (and inside the table's width)
    of its slot's pages, with ``window`` the last ``window`` of them; a
    padding row gives zeros.  fp32, dense."""
    T, h, d = q.shape
    kvh, page = kc.shape[1], kc.shape[2]
    rep = h // kvh
    kc, vc = np.asarray(kc, np.float32), np.asarray(vc, np.float32)
    out = np.zeros((T, h, d), np.float32)
    for r in range(T):
        n = min(int(lens[r]), tables.shape[1] * page)
        if slot[r] < 0 or n <= 0:
            continue
        pages = np.maximum(tables[slot[r]], 0)
        lo = 0 if window is None else max(0, int(lens[r]) - window)
        k = kc[pages].transpose(1, 0, 2, 3).reshape(kvh, -1, d)[:, lo:n]
        v = vc[pages].transpose(1, 0, 2, 3).reshape(kvh, -1, d)[:, lo:n]
        s = np.einsum("grd,gtd->grt",
                      np.asarray(q[r], np.float32).reshape(kvh, rep, d),
                      k) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[r] = np.einsum("grt,gtd->grd", p, v).reshape(h, d)
    return out


def run(start, n, slot):
    """``n`` rows of ``slot`` at consecutive positions from ``start``."""
    return [(slot, start + j + 1) for j in range(n)]


def schedules():
    """name -> list of (slot, visibility) rows, the engine's packing
    order (decode and verify windows, then chunks, then padding)."""
    pad = [(-1, 0)]
    return {
        # one row a slot, every slot another context
        "decode": [(0, 9), (1, 48), (2, 1), (3, 112), (4, 33), (5, 8)]
        + pad * 4,
        # 70 rows from position 5: starts mid-page, crosses eight pages,
        # two turns of 4 pages and (32 rows a tile) two tile boundaries
        "chunk_midpage": run(5, 70, 2) + pad * 3,
        # three decode rows, a verify window of 4, two slots' chunks
        "mixed": [(0, 30), (1, 77), (2, 3)] + run(40, 4, 3)
        + run(6, 37, 4) + run(0, 21, 5) + pad * 7,
        "padding": pad * 40,
        # slot 0 runs past the table's width (the lookahead clamp: such
        # positions do not exist); slots 1 and 2 hold -1 past their pages
        "lookahead": [(0, 120), (1, 10), (2, 17)] + run(110, 6, 0)
        + run(3, 9, 1) + pad * 2,
    }


VARIANTS = {
    # rep, head dim, pages_per_step, cache dtype, q dtype
    "rep4_d64_pp4": (4, 64, 4, np.float32, np.float32),
    "rep4_d64_pp1": (4, 64, 1, np.float32, np.float32),
    "rep1_d64_pp4": (1, 64, 4, np.float32, np.float32),
    "rep8_d64_pp4": (8, 64, 4, np.float32, np.float32),
    "rep4_d128_pp4": (4, 128, 4, np.float32, np.float32),
    "rep4_d64_pp4_int8": (4, 64, 4, np.int8, np.float32),
    "rep4_d128_pp1_bf16": (4, 128, 1, jnp.bfloat16, jnp.bfloat16),
}

CASES = [(s, v) for s in schedules() for v in ("rep4_d64_pp4",
                                               "rep4_d64_pp1")] \
    + [("mixed", v) for v in VARIANTS if v not in ("rep4_d64_pp4",
                                                   "rep4_d64_pp1")] \
    + [("chunk_midpage", "rep8_d64_pp4"), ("lookahead", "rep1_d64_pp4"),
       ("lookahead", "rep4_d64_pp4_int8")]


def launch(schedule, variant, seed=0):
    rep, d, pp, cache_dt, q_dt = VARIANTS[variant]
    rows = schedules()[schedule]
    slot = np.array([r[0] for r in rows], np.int32)
    lens = np.array([r[1] for r in rows], np.int32)
    rng = np.random.default_rng(seed)
    n_pages = SLOTS * WIDTH + 1
    tables = rng.permutation(n_pages)[:SLOTS * WIDTH].reshape(
        SLOTS, WIDTH).astype(np.int32)
    if schedule == "lookahead":
        tables[1, 2:] = -1          # two pages hold slot 1's 12 positions
        tables[2, 3:] = -1
    shape = (n_pages, KVH, PAGE, d)
    if cache_dt == np.int8:
        # int8 pools as serving uses them: the dequant scales are folded
        # into q and the output by the caller, the kernel only widens
        kc = np.clip(np.round(rng.standard_normal(shape) * 32), -127, 127
                     ).astype(np.int8)
        vc = np.clip(np.round(rng.standard_normal(shape) * 32), -127, 127
                     ).astype(np.int8)
        q = rng.standard_normal((len(rows), KVH * rep, d)) / 32
    else:
        kc, vc = rng.standard_normal(shape), rng.standard_normal(shape)
        q = rng.standard_normal((len(rows), KVH * rep, d))
    kc, vc = jnp.asarray(kc, cache_dt), jnp.asarray(vc, cache_dt)
    q = jnp.asarray(q, q_dt)
    return q, kc, vc, lens, slot, tables, pp


@pytest.mark.parametrize("schedule,variant", CASES)
def test_ragged_kernel_matches_the_dense_reference(schedule, variant):
    q, kc, vc, lens, slot, tables, pp = launch(schedule, variant)
    d = q.shape[-1]
    got = np.asarray(ragged_paged_decode_raw(
        q, kc, vc, jnp.asarray(lens), jnp.asarray(slot),
        jnp.asarray(tables), scale=d ** -0.5, pages_per_step=pp
    ).astype(jnp.float32))
    want = reference(q.astype(jnp.float32), kc, vc, lens, slot, tables,
                     d ** -0.5)
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 2e-5
    scale = max(1.0, float(np.abs(want).max()))
    assert np.abs(got - want).max() <= tol * scale
    assert not got[slot < 0].any()      # padding rows are zeros, exactly
    if schedule == "padding":
        assert not got.any()


@pytest.mark.parametrize("tile_rows", [4, 8, 32])
def test_ragged_kernel_at_other_tiles(tile_rows):
    """The tile is the wrapper's choice; the result is not."""
    q, kc, vc, lens, slot, tables, pp = launch("mixed", "rep4_d64_pp4", 1)
    got = np.asarray(ragged_paged_decode_raw(
        q, kc, vc, jnp.asarray(lens), jnp.asarray(slot),
        jnp.asarray(tables), pages_per_step=pp, tile_rows=tile_rows))
    want = reference(q, kc, vc, lens, slot, tables, q.shape[-1] ** -0.5)
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_ragged_kernel_takes_rows_in_any_order():
    """Rows of a slot need not be adjacent or ascending: every row
    masks by its own visibility, a unit walks to its largest."""
    q, kc, vc, lens, slot, tables, pp = launch("mixed", "rep4_d64_pp4", 2)
    order = np.random.default_rng(3).permutation(len(slot))
    got = np.asarray(ragged_paged_decode_raw(
        q[order], kc, vc, jnp.asarray(lens[order]),
        jnp.asarray(slot[order]), jnp.asarray(tables), pages_per_step=pp))
    want = reference(q[order], kc, vc, lens[order], slot[order], tables,
                     q.shape[-1] ** -0.5)
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_units_of_work_by_hand():
    """2 decode rows, a verify window of 3, a chunk of 11 rows that
    crosses a tile boundary of 8, one padding row inside the tile."""
    slot = np.array([0, 1] + [2] * 3 + [-1] + [3] * 11 + [-1] * 7, np.int32)
    lens = np.array([20, 9, 5, 6, 7, 0] + list(range(30, 41)) + [0] * 7,
                    np.int32)
    count, reach = ragged_units(slot, lens, 8, np)
    first = np.flatnonzero(count)
    assert first.tolist() == [0, 1, 2, 6, 8, 16]
    assert count[first].tolist() == [1, 1, 3, 2, 8, 1]
    assert reach[first].tolist() == [20, 9, 7, 31, 39, 40]
    # the device's copy of the same definition
    jc, jr = ragged_units(jnp.asarray(slot), jnp.asarray(lens), 8, jnp)
    assert np.array_equal(np.asarray(jc), count)
    assert np.array_equal(np.asarray(jr), reach)
    # whole pages of 8 as far as each unit's reach, at most 4 pages wide
    assert ragged_kv_tokens_read(slot, lens, 8, 8, 4) \
        == 8 * (3 + 2 + 1 + 4 + 4 + 4)


@pytest.mark.parametrize("h,kvh,d,rows", [(32, 8, 128, 32), (8, 8, 128, 128),
                                          (64, 8, 128, 16), (32, 32, 128, 32),
                                          (12, 4, 64, 32)])
def test_tile_rows_follow_the_shapes(h, kvh, d, rows):
    """128 sublanes of (row, head) pairs a KV head, fewer where every
    KV head's state would not fit beside the pages."""
    assert ragged_tile_rows(h, kvh, d) == rows


# ---- a layer that attends the last ``window`` positions ------------------

WINDOW = 20                     # two and a half pages of 8


def window_schedules():
    pad = [(-1, 0)]
    return {
        # one row a slot: under the window, at it, past it by pages
        "decode": [(0, 9), (1, 48), (2, 1), (3, 112), (4, 20), (5, 21)]
        + pad * 4,
        # a chunk of 70 rows from position 5: its rows' windows start in
        # nine different pages, two tile boundaries (32 rows a tile)
        "chunk": run(5, 70, 2) + pad * 3,
        # a unit whose lowest row's window starts exactly at a page's
        # first position (visibility 36: positions 16..35), and one
        # whose walk starts at the table's first page
        "page_edge": run(35, 5, 0) + run(0, 30, 1) + [(3, 44)] + pad * 2,
        "mixed": [(0, 30), (1, 77), (2, 3)] + run(40, 4, 3)
        + run(6, 37, 4) + run(60, 21, 5) + pad * 7,
    }


def window_launch(schedule, variant, seed=0, poison=False):
    """As ``launch``; with ``poison`` every page wholly under the lowest
    position that any row of its slot attends is NaN and its table
    entry -1: what the engine has given back."""
    rep, d, pp, cache_dt, q_dt = VARIANTS[variant]
    rows = window_schedules()[schedule]
    slot = np.array([r[0] for r in rows], np.int32)
    lens = np.array([r[1] for r in rows], np.int32)
    rng = np.random.default_rng(seed)
    n_pages = SLOTS * WIDTH + 1
    # page 0 belongs to no slot: it is where a clamped -1 would read
    tables = (1 + rng.permutation(SLOTS * WIDTH)).reshape(
        SLOTS, WIDTH).astype(np.int32)
    shape = (n_pages, KVH, PAGE, d)
    kc, vc = rng.standard_normal(shape), rng.standard_normal(shape)
    if poison:
        for s_ in range(SLOTS):
            mine = lens[slot == s_]
            if not len(mine):
                continue
            gone = max(0, int(mine.min()) - WINDOW) // PAGE
            kc[tables[s_, :gone]] = np.nan
            vc[tables[s_, :gone]] = np.nan
            tables[s_, :gone] = -1
        kc[0], vc[0] = np.nan, np.nan
    q = jnp.asarray(rng.standard_normal((len(rows), KVH * rep, d)), q_dt)
    return (q, jnp.asarray(kc, cache_dt), jnp.asarray(vc, cache_dt), lens,
            slot, tables, pp)


WINDOW_CASES = [(s, v) for s in window_schedules()
                for v in ("rep4_d64_pp4", "rep4_d64_pp1")] \
    + [("mixed", "rep8_d64_pp4"), ("chunk", "rep4_d128_pp4"),
       ("mixed", "rep4_d128_pp1_bf16")]


@pytest.mark.parametrize("schedule,variant", WINDOW_CASES)
def test_window_kernel_matches_the_dense_reference(schedule, variant):
    q, kc, vc, lens, slot, tables, pp = window_launch(schedule, variant)
    d = q.shape[-1]
    got = np.asarray(ragged_paged_decode_raw(
        q, kc, vc, jnp.asarray(lens), jnp.asarray(slot),
        jnp.asarray(tables), scale=d ** -0.5, pages_per_step=pp,
        window=WINDOW).astype(jnp.float32))
    want = reference(q.astype(jnp.float32), kc, vc, lens, slot, tables,
                     d ** -0.5, window=WINDOW)
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 2e-5
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())
    assert not got[slot < 0].any()
    # and it is not the full context's answer where a row is past the window
    full = reference(q.astype(jnp.float32), kc, vc, lens, slot, tables,
                     d ** -0.5)
    past = (slot >= 0) & (lens > WINDOW)
    assert np.abs(full - want)[past].max() > 1e-2


@pytest.mark.parametrize("schedule", list(window_schedules()))
@pytest.mark.parametrize("pp", [1, 4])
def test_window_kernel_never_touches_a_page_under_its_start(schedule, pp):
    """Pages wholly under every row's window are NaN and their table
    entries -1 (the engine gave them back); so is page 0, where a
    clamped -1 would read.  Nothing of them reaches a result."""
    variant = f"rep4_d64_pp{pp}"
    q, kc, vc, lens, slot, tables, pp = window_launch(schedule, variant, 1,
                                                      poison=True)
    got = np.asarray(ragged_paged_decode_raw(
        q, kc, vc, jnp.asarray(lens), jnp.asarray(slot),
        jnp.asarray(tables), pages_per_step=pp, window=WINDOW))
    assert np.isfinite(got).all()
    clean = np.nan_to_num(np.asarray(kc)), np.nan_to_num(np.asarray(vc))
    want = reference(q, *clean, lens, slot, np.maximum(tables, 0),
                     q.shape[-1] ** -0.5, window=WINDOW)
    assert np.abs(got - want).max() <= 2e-5 * max(1.0, np.abs(want).max())


def test_window_none_is_the_kernel_as_it_was():
    """``window=None`` takes the path it took: same result, and a window
    wider than every context changes nothing either."""
    q, kc, vc, lens, slot, tables, pp = launch("mixed", "rep4_d64_pp4", 4)
    args = (q, kc, vc, jnp.asarray(lens), jnp.asarray(slot),
            jnp.asarray(tables))
    a = np.asarray(ragged_paged_decode_raw(*args, pages_per_step=pp))
    b = np.asarray(ragged_paged_decode_raw(*args, pages_per_step=pp,
                                           window=None))
    c = np.asarray(ragged_paged_decode_raw(*args, pages_per_step=pp,
                                           window=PAGE * WIDTH))
    assert np.array_equal(a, b)
    assert np.abs(a - c).max() <= 2e-5 * np.abs(a).max()
    with pytest.raises(ValueError):
        ragged_paged_decode_raw(*args, window=0)


def test_units_report_their_lowest_row():
    slot = np.array([0, 1] + [2] * 3 + [-1] + [3] * 11 + [-1] * 7, np.int32)
    lens = np.array([20, 9, 5, 6, 7, 0] + list(range(30, 41)) + [0] * 7,
                    np.int32)
    count, reach, low = ragged_units(slot, lens, 8, np, low=True)
    first = np.flatnonzero(count)
    assert low[first].tolist() == [20, 9, 5, 30, 32, 40]
    assert not low[count == 0].any()
    _, _, jl = ragged_units(jnp.asarray(slot), jnp.asarray(lens), 8, jnp,
                            low=True)
    assert np.array_equal(np.asarray(jl), low)
