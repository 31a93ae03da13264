"""Round-5 linalg long tail (reference python/paddle/linalg.py __all__):
cholesky_inverse, lu_unpack, householder_product/ormqr, low-rank
svd/pca, fp8 gemm, norms."""

import numpy as np
import scipy.linalg
import jax.numpy as jnp

import paddle_tpu as paddle


def _np(x):
    return np.asarray(getattr(x, "_value", x))


def test_linalg_namespace_complete():
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "ref_linalg_all.txt")
    with open(path) as f:
        names = [ln.strip() for ln in f
                 if ln.strip() and not ln.startswith("#")]
    assert len(names) >= 30, names
    missing = [n for n in names if not hasattr(paddle.linalg, n)]
    assert not missing, missing


def test_cholesky_inverse():
    rng = np.random.RandomState(0)
    a = rng.randn(4, 4).astype(np.float32)
    A = a @ a.T + 4 * np.eye(4, dtype=np.float32)
    L = np.linalg.cholesky(A)
    got = _np(paddle.linalg.cholesky_inverse(paddle.to_tensor(L)))
    np.testing.assert_allclose(got, np.linalg.inv(A), rtol=1e-3, atol=1e-4)
    U = L.T.copy()
    got_u = _np(paddle.linalg.cholesky_inverse(paddle.to_tensor(U),
                                               upper=True))
    np.testing.assert_allclose(got_u, np.linalg.inv(A), rtol=1e-3,
                               atol=1e-4)


def test_lu_unpack_reconstructs():
    rng = np.random.RandomState(1)
    A = rng.randn(5, 5).astype(np.float32)
    lu, piv = scipy.linalg.lu_factor(A)
    P, L, U = paddle.linalg.lu_unpack(paddle.to_tensor(lu),
                                      paddle.to_tensor(piv.astype(np.int32)
                                                       + 1))
    rec = _np(P) @ _np(L) @ _np(U)
    np.testing.assert_allclose(rec, A, rtol=1e-4, atol=1e-4)


def test_householder_product_and_ormqr():
    rng = np.random.RandomState(2)
    A = rng.randn(5, 3).astype(np.float32)
    h, tau, _, _ = scipy.linalg.lapack.sgeqrf(A)
    h = np.asarray(h, np.float32)
    t = np.asarray(tau, np.float32)
    Q = _np(paddle.linalg.householder_product(paddle.to_tensor(h),
                                              paddle.to_tensor(t)))
    Qs = scipy.linalg.qr(A, mode="economic")[0]
    # column sign freedom: compare up to reconstruction
    np.testing.assert_allclose(np.abs(Q.T @ Q), np.eye(3), atol=1e-4)
    R = np.triu(h)[:3]
    np.testing.assert_allclose(Q @ R, A, rtol=1e-3, atol=1e-3)

    # ormqr vs the explicit full Q from scipy (orgqr of ALL reflectors)
    Qfull = scipy.linalg.qr(A)[0]                      # m x m
    y = rng.randn(5, 2).astype(np.float32)
    got = _np(paddle.linalg.ormqr(paddle.to_tensor(h), paddle.to_tensor(t),
                                  paddle.to_tensor(y)))
    np.testing.assert_allclose(got, Qfull @ y, rtol=1e-3, atol=1e-3)
    gotT = _np(paddle.linalg.ormqr(paddle.to_tensor(h),
                                   paddle.to_tensor(t),
                                   paddle.to_tensor(y), transpose=True))
    np.testing.assert_allclose(gotT, Qfull.T @ y, rtol=1e-3, atol=1e-3)
    yr = rng.randn(2, 5).astype(np.float32)
    gotR = _np(paddle.linalg.ormqr(paddle.to_tensor(h),
                                   paddle.to_tensor(t),
                                   paddle.to_tensor(yr), left=False))
    np.testing.assert_allclose(gotR, yr @ Qfull, rtol=1e-3, atol=1e-3)


def test_svd_pca_lowrank_and_fp8():
    rng = np.random.RandomState(3)
    base = rng.randn(20, 4).astype(np.float32)
    A = base @ rng.randn(4, 12).astype(np.float32)   # rank 4
    u, s, v = paddle.linalg.svd_lowrank(paddle.to_tensor(A), q=6)
    rec = _np(u) @ np.diag(_np(s)) @ _np(v).T
    np.testing.assert_allclose(rec, A, rtol=1e-2, atol=1e-2)
    u2, s2, v2 = paddle.linalg.pca_lowrank(paddle.to_tensor(A), q=4)
    assert _np(s2).shape[-1] == 4

    x8 = jnp.asarray(rng.randn(4, 8), jnp.float8_e4m3fn)
    y8 = jnp.asarray(rng.randn(8, 5), jnp.float8_e4m3fn)
    out = paddle.linalg.fp8_fp8_half_gemm_fused(x8, y8)
    got = _np(out)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(x8, np.float32) @ np.asarray(y8, np.float32)
    np.testing.assert_allclose(got.astype(np.float32), want, rtol=0.1,
                               atol=0.5)


def test_norms_and_matrix_exp():
    rng = np.random.RandomState(4)
    x = rng.randn(3, 4).astype(np.float32)
    np.testing.assert_allclose(_np(paddle.linalg.vector_norm(
        paddle.to_tensor(x))), np.linalg.norm(x.reshape(-1)), rtol=1e-5)
    np.testing.assert_allclose(_np(paddle.linalg.matrix_norm(
        paddle.to_tensor(x))), np.linalg.norm(x, "fro"), rtol=1e-5)
    a = 0.3 * rng.randn(4, 4).astype(np.float32)
    np.testing.assert_allclose(_np(paddle.linalg.matrix_exp(
        paddle.to_tensor(a))), scipy.linalg.expm(a), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(_np(paddle.linalg.inv(paddle.to_tensor(
        a + 3 * np.eye(4, dtype=np.float32)))),
        np.linalg.inv(a + 3 * np.eye(4)), rtol=1e-3, atol=1e-4)
