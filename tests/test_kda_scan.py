"""The KDA scan kernel and the dense latent walk (interpret mode) against
the sequential scan a row and dense numpy: decode units, units that cross
tiles, a slot resumed from a snapshot, gates at the HARD end (where
``e^-G`` would pass float32 within two rows), padding into the trash
entry."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.dense_mla import (dense_mla_attention_raw,
                                             dense_tile_rows)
from paddle_tpu.ops.pallas.kda_scan import kda_delta_scan, kda_scan_reference
from paddle_tpu.ops.pallas.ssd_scan import ssd_max_units
from test_nemotron_h import row_columns     # packed rows of (slot, rows, entry)

H, D, TILE, ENTRIES = 4, 16, 8, 7


def gates(rng, rows, hard: bool, H=H, D=D):
    """Log-decays a key channel: typical ones (``A`` log-uniform in [1,
    16] a head, ``dt`` around 0.02), or the hard end, ``A_log = log 16``
    and ``dt_bias`` +2: some -34 a row."""
    z = rng.normal(size=(rows, H, D))
    if hard:
        return -16.0 * np.log1p(np.exp(z + 2.0))
    a = np.exp(rng.uniform(0, np.log(16), (1, H, 1)))
    return -a * np.log1p(np.exp(z - 4.0))


def scan_case(runs, rows, hard=False, dtype=jnp.float32, seed=0, H=H, D=D):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(rows, H, D))) * D ** -0.5
    k = unit(rng.normal(size=(rows, H, D)))
    v = rng.normal(size=(rows, H, D))
    beta = 1 / (1 + np.exp(-rng.normal(size=(rows, H))))
    pool = rng.normal(size=(ENTRIES, H, D, D))
    return ([jnp.asarray(a, dtype) for a in (q, k, v)]
            + [jnp.asarray(a, jnp.float32)
               for a in (gates(rng, rows, hard, H, D), beta, pool)],
            row_columns(runs, rows, ENTRIES))


#: packed rows as ``(runs of (slot, rows, entry to start from), rows)``,
#: in tiles of 8
ROW_LAYOUTS = {
    "decode": ([(0, 1, 0), (1, 1, -1), (2, 1, 5)], 16),
    "chunk": ([(0, 13, -1)], 16),                       # 2 tiles
    "restore": ([(2, 20, 5)], 24),                      # from a snapshot
    "mixed": ([(0, 1, 0), (1, 1, 1), (2, 11, -1), (3, 3, 4)], 24),
    "edges": ([(1, 1, 1), (0, 7, 0), (3, 9, 3), (2, 1, -1)], 24),
    "two_rows": ([(0, 2, -1)], 8),                      # level 1 alone
    "odd_start": ([(1, 3, 1), (0, 5, 0)], 16),          # 5 rows from row 3
}
#: (rows' layout, heads a grid step, rows a tile): the chunk unit takes a
#: block's heads two at a time, so 1 head (one left over, no pair), 2 (a
#: pair), 4 of 4; in tiles of 32 the halves of 8 and 16 rows are whole
#: register tiles, where T's update takes a segment's upper rows alone
CASES = {**{name: (*layout, 2, TILE) for name, layout in ROW_LAYOUTS.items()},
         "mixed-1": (*ROW_LAYOUTS["mixed"], 1, TILE),
         "mixed-4": (*ROW_LAYOUTS["mixed"], 4, TILE),
         "tile_32": ([(2, 45, 5), (0, 1, 0), (1, 9, -1)], 64, 2, 32)}


@pytest.mark.parametrize("runs, rows, heads_per_step, tile",
                         list(CASES.values()), ids=list(CASES))
@pytest.mark.parametrize("hard", [False, True], ids=["typical", "hard"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_scan_kernel_matches_the_recurrence(runs, rows, heads_per_step, tile,
                                            hard, dtype, tol):
    """float32 rows: float32 matmuls at the highest precision, the same
    sums in another order (1e-7 read).  bf16 rows: the chunked form's
    matmul operands are bf16, so a hundredth of the largest value."""
    args, (slot, lens, src, dst) = scan_case(runs, rows, hard, dtype)
    o0, p0 = kda_scan_reference(*args, slot, src, dst)
    o1, p1 = kda_delta_scan(*args, slot, lens, src, dst, tile_rows=tile,
                            max_units=ssd_max_units(rows, tile, 4),
                            heads_per_step=heads_per_step, interpret=True)
    assert bool(jnp.isfinite(o1).all()) and bool(jnp.isfinite(p1[:-1]).all())
    assert float(jnp.abs(o0 - o1).max() / jnp.abs(o0).max()) < tol
    # the trash entry (the last) is the padding units' to scribble on
    assert float(jnp.abs(p0[:-1] - p1[:-1]).max() / jnp.abs(p0).max()) < tol
    touched = {s for s, _, _ in runs}
    for e in range(ENTRIES - 1):
        if e not in touched:            # snapshots and idle slots: as found
            assert jnp.array_equal(p1[e], args[5][e])
    pad = np.asarray(slot) < 0
    assert not np.asarray(o1)[pad].any()


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_a_unit_of_a_whole_tile_of_128_rows_at_the_published_head_size(
        dtype, tol):
    """The cell's tile and head size, two heads: seven levels of halving;
    one slot's 200 rows cross a tile and a decode row rides beside."""
    args, cols = scan_case([(0, 200, -1), (1, 1, 1)], 256, hard=True,
                           dtype=dtype, seed=5, H=2, D=128)
    slot, lens, src, dst = cols
    o0, p0 = kda_scan_reference(*args, slot, src, dst)
    o1, p1 = kda_delta_scan(*args, slot, lens, src, dst, tile_rows=128,
                            heads_per_step=2, interpret=True)
    assert float(jnp.abs(o0 - o1).max() / jnp.abs(o0).max()) < tol
    assert float(jnp.abs(p0[:2] - p1[:2]).max() / jnp.abs(p0[:2]).max()) < tol


def test_dense_latent_walk_matches_dense_numpy():
    """Packed rows of three slots (a chunk, a decode row, a chunk that
    crosses a tile) over two pools under one page id, against a dense
    softmax over ``[c~ | k_p]`` in numpy; tiles of 1, 4 and the default."""
    rng = np.random.default_rng(2)
    heads, dc, dp, page, pages, T = 4, 16, 8, 4, 12, 14
    lat = rng.normal(size=(pages, page, dc)).astype(np.float32)
    pos = rng.normal(size=(pages, page, dp)).astype(np.float32)
    tables = np.array([[3, 5, 7, 0], [1, 2, 0, 0], [4, 6, 8, 9]], np.int32)
    slot = np.array([0] * 5 + [1] + [2] * 6 + [-1] * 2, np.int32)
    lens = np.array([4, 5, 6, 7, 8, 6, 9, 10, 11, 12, 13, 14, 0, 0], np.int32)
    qc = rng.normal(size=(T, heads, dc)).astype(np.float32)
    qp = rng.normal(size=(T, heads, dp)).astype(np.float32)
    want = np.zeros((T, heads, dc), np.float32)
    for t in range(T):
        if slot[t] < 0:
            continue
        c = lat[tables[slot[t]]].reshape(-1, dc)[:lens[t]]
        p = pos[tables[slot[t]]].reshape(-1, dp)[:lens[t]]
        s = qc[t] @ c.T + qp[t] @ p.T
        w = np.exp(s - s.max(-1, keepdims=True))
        want[t] = (w / w.sum(-1, keepdims=True)) @ c
    for tile in (1, 4, None):
        got = dense_mla_attention_raw(
            *(jnp.asarray(a) for a in (qc, qp, lat, pos, lens, slot, tables)),
            pages_per_step=2, interpret=True, tile_rows=tile)
        assert float(np.abs(np.asarray(got) - want).max()) < 1e-5
    assert dense_tile_rows(32, 512, 128) == 32      # the cell's: 1024 rows
