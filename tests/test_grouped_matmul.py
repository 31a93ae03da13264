"""Grouped / segmented Pallas matmul (ops/pallas/grouped_matmul.py) vs a
dense per-segment loop — the expert-compute kernel of the dropless MoE
path (and, via seg_wids indirection, the future per-row LoRA adapter
kernel).  Interpret mode on CPU runs the identical kernel logic."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas.grouped_matmul import (
    align_rows, grouped_matmul, grouped_matmul_raw, grouped_outer_raw,
    segment_starts)


def _pack(lens, bm, K, rng, dtype=np.float32):
    """Build (x, starts) for the kernel contract: segments densely tile
    block-aligned windows, alignment-slack rows are zero."""
    aligned = [int(align_rows(l, bm)) for l in lens]
    R = sum(aligned)
    x = np.zeros((max(R, bm), K), dtype)
    if R == 0:
        R = bm  # keep one (all-slack) block so R % bm == 0 and R > 0
    starts, off = [], 0
    for l, a in zip(lens, aligned):
        starts.append(off)
        x[off:off + l] = rng.standard_normal((l, K)).astype(dtype)
        off += a
    return x[:R], np.asarray(starts, np.int32), R


def _dense_reference(x, w, starts, lens, wids, scale=None):
    """Per-segment numpy loop in float64 layout (float32 math to match
    kernel accumulate exactness at these sizes)."""
    y = np.zeros((x.shape[0], w.shape[2]), np.float32)
    for s, l, e in zip(starts, lens, wids):
        wf = w[e].astype(np.float32)
        if scale is not None:
            wf = wf * scale[e][None, :]
        y[s:s + l] = x[s:s + l].astype(np.float32) @ wf
    return y


def _valid_mask(R, starts, lens):
    m = np.zeros((R,), bool)
    for s, l in zip(starts, lens):
        m[s:s + l] = True
    return m


@pytest.mark.parametrize("lens", [
    [8, 8, 8],            # exact blocks
    [3, 0, 13, 8],        # ragged + an EMPTY segment
    [0, 0, 0],            # all empty
    [25],                 # one segment, several blocks
    [1, 1, 1, 1, 1, 1],   # many tiny segments
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
# dense: the block-major form, the slice whole (the serving step's
# sorted dispatch asks for it: its segments tile the rows densely, and
# its buffer ends in the park block, which goes in and comes out)
@pytest.mark.parametrize("dense", [False, True], ids=["segments", "blocks"])
def test_grouped_matmul_matches_dense_loop(lens, dtype, dense):
    rng = np.random.default_rng(0)
    bm, K, N = 8, 16, 24
    S = len(lens)
    x, starts, R = _pack(lens, bm, K, rng)
    if dense:           # the park block: anything, NaN included
        x = np.concatenate([x, np.full((bm, K), np.nan, x.dtype)])
    w = rng.standard_normal((S + 1, K, N)).astype(np.float32)
    wids = np.arange(S, dtype=np.int32)  # slice S is deliberately unused

    xj = jnp.asarray(x, dtype)
    wj = jnp.asarray(w, dtype)
    y = np.asarray(grouped_matmul_raw(
        xj, wj, jnp.asarray(starts), jnp.asarray(lens, jnp.int32),
        jnp.asarray(wids), block_rows=bm, dense=dense), np.float32)
    ref = _dense_reference(np.asarray(xj, np.float32),
                           np.asarray(wj, np.float32), starts, lens, wids)
    assert y.shape == (x.shape[0], N)
    m = _valid_mask(x.shape[0], starts, lens)
    tol = 1e-6 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(y[m], ref[m], rtol=tol, atol=tol)


def test_grouped_matmul_segment_starts_helper():
    lens = jnp.asarray([3, 0, 13, 8], jnp.int32)
    starts = segment_starts(lens, 8)
    np.testing.assert_array_equal(np.asarray(starts), [0, 8, 8, 24])


def test_grouped_matmul_int8_dequant_view():
    """int8 expert bank + [E, N] per-out-channel scales: the kernel's
    in-VMEM widen-and-fold must match gather-then-dequant exactly."""
    rng = np.random.default_rng(1)
    bm, K, N, E = 8, 16, 24, 4
    lens = [5, 16, 0, 8]
    x, starts, R = _pack(lens, bm, K, rng)
    q = rng.integers(-127, 128, size=(E, K, N)).astype(np.int8)
    scale = (rng.random((E, N)).astype(np.float32) + 0.5) / 127.0
    wids = np.asarray([2, 0, 1, 2], np.int32)  # reuse + skip slices

    y = np.asarray(grouped_matmul_raw(
        jnp.asarray(x), jnp.asarray(q), jnp.asarray(starts),
        jnp.asarray(lens, jnp.int32), jnp.asarray(wids), block_rows=bm,
        w_scale=jnp.asarray(scale)))
    deq = q.astype(np.float32) * scale[:, None, :]
    ref = _dense_reference(x, deq, starts, lens, wids)
    m = _valid_mask(R, starts, lens)
    np.testing.assert_allclose(y[m], ref[m], rtol=1e-6, atol=1e-6)


def test_grouped_matmul_adapter_shape_reuses_slices():
    """The LoRA-adapter shape: MANY small row segments cycling over FEW
    weight slices (seg_wids is an indirection, not an identity)."""
    rng = np.random.default_rng(2)
    bm, K, N = 8, 8, 16
    lens = [4, 8, 2, 8, 7, 8, 1, 5]          # 8 segments
    x, starts, R = _pack(lens, bm, K, rng)
    w = rng.standard_normal((2, K, N)).astype(np.float32)  # 2 adapters
    wids = np.asarray([0, 1, 0, 1, 0, 1, 0, 1], np.int32)

    y = np.asarray(grouped_matmul_raw(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(starts),
        jnp.asarray(lens, jnp.int32), jnp.asarray(wids), block_rows=bm))
    ref = _dense_reference(x, w, starts, lens, wids)
    m = _valid_mask(R, starts, lens)
    np.testing.assert_allclose(y[m], ref[m], rtol=1e-6, atol=1e-6)


def test_grouped_outer_matches_dense_loop():
    rng = np.random.default_rng(3)
    bm, K, N = 8, 8, 12
    lens = [6, 0, 16, 3]
    x, starts, R = _pack(lens, bm, K, rng)
    dy = rng.standard_normal((R, N)).astype(np.float32)
    # contract: alignment-slack rows of x are zero, so slack dy content
    # is irrelevant — leave dy dense to prove it
    out = np.asarray(grouped_outer_raw(
        jnp.asarray(x), jnp.asarray(dy), jnp.asarray(starts),
        jnp.asarray(lens, jnp.int32), block_rows=bm))
    for i, (s, l) in enumerate(zip(starts, lens)):
        ref = x[s:s + l].T.astype(np.float32) @ dy[s:s + l]
        np.testing.assert_allclose(out[i], ref, rtol=1e-6, atol=1e-6)
    assert np.all(out[1] == 0.0)  # empty segment emits exact zeros


def test_grouped_matmul_grad_matches_dense_reference():
    """custom_vjp parity: jax.grad through the ragged launch vs grad
    through the per-segment dense loop — incl. REPEATED seg_wids, whose
    dW contributions must scatter-accumulate."""
    rng = np.random.default_rng(4)
    bm, K, N, E = 8, 8, 12, 2
    lens = [5, 8, 3, 7]
    x, starts, R = _pack(lens, bm, K, rng)
    w = rng.standard_normal((E, K, N)).astype(np.float32)
    wids = np.asarray([0, 1, 0, 0], np.int32)
    m = _valid_mask(R, starts, lens)
    tgt = rng.standard_normal((int(m.sum()), N)).astype(np.float32)
    starts_j = jnp.asarray(starts)
    lens_j = jnp.asarray(lens, jnp.int32)
    wids_j = jnp.asarray(wids)
    mj = jnp.asarray(m)

    def loss_kernel(xv, wv):
        y = grouped_matmul(xv, wv, starts_j, lens_j, wids_j, block_rows=bm)
        return jnp.sum((y[mj] - tgt) ** 2)

    def loss_dense(xv, wv):
        parts = []
        for s, l, e in zip(starts, lens, wids):
            parts.append(xv[s:s + l] @ wv[e])
        return jnp.sum((jnp.concatenate(parts) - tgt) ** 2)

    gx, gw = jax.grad(loss_kernel, argnums=(0, 1))(
        jnp.asarray(x), jnp.asarray(w))
    rx, rw = jax.grad(loss_dense, argnums=(0, 1))(
        jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(gx)[m], np.asarray(rx)[m],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=1e-5, atol=1e-6)


def test_grouped_matmul_registered_op():
    from paddle_tpu.ops.registry import all_ops
    assert "grouped_matmul" in all_ops()
