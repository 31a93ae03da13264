"""Pallas flash-decoding kernel (ops/pallas/decode_attention.py) vs naive
softmax reference — the TPU analog of the reference's
masked_multihead_attention CUDA kernel
(paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.decode_attention import flash_decode_raw


def _naive(q, kc, vc, lens):
    """q [B,H,D]; kc/vc [B,KVH,T,D]; lens [B] -> [B,H,D] fp64."""
    b, h, d = q.shape
    kvh = kc.shape[1]
    rep = h // kvh
    out = np.zeros((b, h, d))
    for bi in range(b):
        for hi in range(h):
            g = hi // rep
            t = int(lens[bi])
            if t == 0:
                continue
            logits = (kc[bi, g, :t].astype(np.float64)
                      @ q[bi, hi].astype(np.float64)) / np.sqrt(d)
            p = np.exp(logits - logits.max())
            p /= p.sum()
            out[bi, hi] = p @ vc[bi, g, :t].astype(np.float64)
    return out


@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (6, 1)])
def test_flash_decode_parity(h, kvh):
    rng = np.random.RandomState(0)
    b, d, t_max = 3, 32, 300            # t_max spans >1 k block of 128
    lens = np.array([1, 130, 300], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    kc = rng.randn(b, kvh, t_max, d).astype(np.float32)
    vc = rng.randn(b, kvh, t_max, d).astype(np.float32)

    out = flash_decode_raw(q, kc, vc, lens, block_k=128)
    np.testing.assert_allclose(np.asarray(out), _naive(q, kc, vc, lens),
                               rtol=2e-4, atol=2e-5)


def test_flash_decode_garbage_past_len():
    """Cache rows past seq_len hold NaN/inf garbage (unwritten slots):
    the kernel's masking must keep them out of the result — this is what
    lets the DMA-clamped index map revisit stale blocks safely."""
    rng = np.random.RandomState(1)
    b, h, d, t_max = 2, 4, 16, 256
    lens = np.array([7, 131], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    kc = np.full((b, h, t_max, d), np.nan, np.float32)
    vc = np.full((b, h, t_max, d), np.inf, np.float32)
    for bi in range(b):
        kc[bi, :, :lens[bi]] = rng.randn(h, lens[bi], d)
        vc[bi, :, :lens[bi]] = rng.randn(h, lens[bi], d)

    out = np.asarray(flash_decode_raw(q, kc, vc, lens, block_k=128))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, _naive(q, kc, vc, lens),
                               rtol=2e-4, atol=2e-5)


def test_flash_decode_zero_len_rows():
    rng = np.random.RandomState(2)
    b, h, d, t_max = 2, 2, 8, 64
    lens = np.array([0, 5], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    kc = rng.randn(b, h, t_max, d).astype(np.float32)
    vc = rng.randn(b, h, t_max, d).astype(np.float32)
    out = np.asarray(flash_decode_raw(q, kc, vc, lens))
    assert np.allclose(out[0], 0.0)
    np.testing.assert_allclose(out[1], _naive(q, kc, vc, lens)[1],
                               rtol=2e-4, atol=2e-5)


def test_flash_decode_bf16():
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    b, h, kvh, d, t_max = 2, 8, 4, 64, 256
    lens = np.array([100, 256], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    kc = rng.randn(b, kvh, t_max, d).astype(np.float32)
    vc = rng.randn(b, kvh, t_max, d).astype(np.float32)
    out = flash_decode_raw(jnp.asarray(q, jnp.bfloat16),
                           jnp.asarray(kc, jnp.bfloat16),
                           jnp.asarray(vc, jnp.bfloat16), lens)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _naive(q, kc, vc, lens), rtol=0.1, atol=0.1)


@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2)])
def test_paged_decode_parity(h, kvh):
    """Pallas paged kernel == dense attention over the logical sequence,
    with physical pages deliberately shuffled."""
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_raw

    rng = np.random.RandomState(5)
    b, d, page, nblocks, mp = 2, 32, 16, 12, 4
    lens = np.array([10, 60], np.int32)     # 60 < mp*page = 64
    tables = np.array([[7, 2, 9, 0], [1, 11, 4, 8]], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    kcache = rng.randn(nblocks, kvh, page, d).astype(np.float32)
    vcache = rng.randn(nblocks, kvh, page, d).astype(np.float32)

    out = np.asarray(paged_decode_raw(q, kcache, vcache, lens, tables))

    # build the logical dense cache from the page tables
    kc = np.zeros((b, kvh, mp * page, d), np.float32)
    vc = np.zeros((b, kvh, mp * page, d), np.float32)
    for bi in range(b):
        for pi in range(mp):
            kc[bi, :, pi * page:(pi + 1) * page] = kcache[tables[bi, pi]]
            vc[bi, :, pi * page:(pi + 1) * page] = vcache[tables[bi, pi]]
    np.testing.assert_allclose(out, _naive(q, kc, vc, lens),
                               rtol=2e-4, atol=2e-5)


def test_paged_decode_unused_slots_are_negative():
    """Unused table slots are -1 (the reference's convention): they sit
    past seq_len so they must never be dereferenced."""
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_raw

    rng = np.random.RandomState(6)
    b, h, d, page, nblocks = 1, 2, 16, 8, 4
    lens = np.array([5], np.int32)
    tables = np.array([[3, -1, -1]], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    kcache = rng.randn(nblocks, h, page, d).astype(np.float32)
    vcache = rng.randn(nblocks, h, page, d).astype(np.float32)
    out = np.asarray(paged_decode_raw(q, kcache, vcache, lens, tables))
    kc = kcache[tables[0, :1]].transpose(1, 0, 2, 3).reshape(
        1, h, page, d)
    vc = vcache[tables[0, :1]].transpose(1, 0, 2, 3).reshape(
        1, h, page, d)
    np.testing.assert_allclose(out, _naive(q, kc, vc, lens),
                               rtol=2e-4, atol=2e-5)


def test_incubate_flash_decoding_surface():
    rng = np.random.RandomState(4)
    b, h, d, t_max = 2, 4, 16, 128
    lens = np.array([3, 60], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    kc = rng.randn(b, h, t_max, d).astype(np.float32)
    vc = rng.randn(b, h, t_max, d).astype(np.float32)
    out = paddle.incubate.nn.flash_decoding(
        paddle.to_tensor(q), paddle.to_tensor(kc), paddle.to_tensor(vc),
        paddle.to_tensor(lens))
    np.testing.assert_allclose(np.asarray(out._value),
                               _naive(q, kc, vc, lens),
                               rtol=2e-4, atol=2e-5)


def test_flash_decode_tensor_parallel_shard_map():
    """Serving under TP: shard the KV heads over a mesh axis with
    shard_map — each device runs the decode kernel on its kv-head slice
    (embarrassingly parallel; outputs concatenate over heads).  The
    distributed serving analog of the reference's TP-sharded
    fused_multi_transformer decode."""
    import jax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.RandomState(7)
    b, h, kvh, d, t_max = 2, 8, 4, 16, 64
    lens = np.array([20, 64], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    kc = rng.randn(b, kvh, t_max, d).astype(np.float32)
    vc = rng.randn(b, kvh, t_max, d).astype(np.float32)

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("mp",))
    # q heads are group-major: reshaping to [b, kvh, rep, d] aligns the
    # q shard with its kv-head shard on the same axis
    rep = h // kvh
    qg = q.reshape(b, kvh, rep, d)

    def local_decode(qg_l, kc_l, vc_l, lens_l):
        bl, kvh_l, rep_l, dl = qg_l.shape
        out = flash_decode_raw(qg_l.reshape(bl, kvh_l * rep_l, dl),
                               kc_l, vc_l, lens_l)
        return out.reshape(bl, kvh_l, rep_l, dl)

    specs = dict(mesh=mesh,
                 in_specs=(P(None, "mp"), P(None, "mp"), P(None, "mp"),
                           P()),
                 out_specs=P(None, "mp"))
    try:
        got = np.asarray(jax.jit(shard_map(local_decode, **specs))(
            qg, kc, vc, lens))
    except NotImplementedError:
        # older jax: no replication rule for pallas_call (the vma
        # mechanism _sds feeds does not exist yet) — disable the check
        got = np.asarray(jax.jit(shard_map(local_decode, check_rep=False,
                                           **specs))(qg, kc, vc, lens))
    got = got.reshape(b, h, d)
    np.testing.assert_allclose(got, _naive(q, kc, vc, lens),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("pp", [1, 2, 3, 4, None],
                         ids=["1", "2", "3", "4", "default"])
def test_paged_decode_multi_page_grid_steps(pp):
    """Round-6 ragged page iteration: pages_per_step physical pages DMA'd
    per grid step must be bit-for-the-same-math as one-page-per-step
    (shuffled physical layout, ragged lens, trailing -1 table slots)."""
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_raw
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    b, h, kvh, d, page, mp = 3, 8, 2, 32, 16, 7    # mp NOT divisible by 2/4
    lens = np.array([5, 50, 112], np.int32)
    nb = b * mp
    tables = rng.permutation(nb).reshape(b, mp).astype(np.int32)
    tables[0, 1:] = -1                              # short row: unused slots
    kp = rng.randn(nb, kvh, page, d).astype(np.float32)
    vp = rng.randn(nb, kvh, page, d).astype(np.float32)
    q = rng.randn(b, h, d).astype(np.float32)
    # dense-layout reference: gather each row's live pages
    kc = np.zeros((b, kvh, mp * page, d), np.float32)
    vc = np.zeros((b, kvh, mp * page, d), np.float32)
    for bi in range(b):
        for j in range(mp):
            if tables[bi, j] >= 0:
                kc[bi, :, j * page:(j + 1) * page] = kp[tables[bi, j]]
                vc[bi, :, j * page:(j + 1) * page] = vp[tables[bi, j]]
    want = _naive(q, kc, vc, lens)
    got = np.asarray(paged_decode_raw(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(lens), jnp.asarray(tables), pages_per_step=pp))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_paged_decode_overrun_lens_safe():
    """Lookahead serving can hand the kernel seq_lens past the table
    capacity (a finished slot's stale chunk) — output for such rows is
    garbage-but-finite and other rows are untouched."""
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_raw
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    b, h, kvh, d, page, mp = 2, 4, 2, 32, 16, 4
    nb = b * mp
    tables = np.arange(nb).reshape(b, mp).astype(np.int32)
    kp = rng.randn(nb, kvh, page, d).astype(np.float32)
    vp = rng.randn(nb, kvh, page, d).astype(np.float32)
    q = rng.randn(b, h, d).astype(np.float32)
    lens_ok = np.array([40, 30], np.int32)
    lens_over = np.array([40, 999], np.int32)      # row 1 overruns capacity
    ref = np.asarray(paged_decode_raw(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(lens_ok), jnp.asarray(tables), pages_per_step=2))
    got = np.asarray(paged_decode_raw(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(lens_over), jnp.asarray(tables), pages_per_step=2))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-5)


def test_default_pages_per_step_heuristic():
    from paddle_tpu.ops.pallas.decode_attention import (
        _PAGED_TARGET_WINDOW, default_pages_per_step)

    # small pages group up to the ~512-token window
    assert default_pages_per_step(128, 4, 128, 16) == \
        _PAGED_TARGET_WINDOW // 128
    # big pages stay single; never exceeds the page count
    assert default_pages_per_step(512, 4, 128, 16) == 1
    assert default_pages_per_step(64, 4, 128, 2) == 2
    # VMEM budget caps wide-head configs
    assert default_pages_per_step(512, 32, 128, 16, itemsize=2) == 1
