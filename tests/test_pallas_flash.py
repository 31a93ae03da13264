"""Pallas flash attention: interpret-mode numerics vs XLA reference, grads,
framework-op integration (SURVEY.md §4 fake-backend strategy)."""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.flash_attention import (_attn_reference,
                                                   flash_attention_raw)


def _rand_qkv(b=2, s=128, h=4, d=64, kv_heads=None, seed=0):
    rng = np.random.RandomState(seed)
    kvh = kv_heads or h
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, s, kvh, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, s, kvh, d).astype(np.float32)) * 0.3
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv()
    out = flash_attention_raw(q, k, v, causal=causal, interpret=True)
    ref = _attn_reference(q, k, v, causal, 1.0 / math.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_uneven_blocks():
    # seq not a multiple of the 128 block
    q, k, v = _rand_qkv(s=192)
    out = flash_attention_raw(q, k, v, causal=True, interpret=True)
    ref = _attn_reference(q, k, v, True, 1.0 / math.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_small_seq():
    q, k, v = _rand_qkv(s=16)
    out = flash_attention_raw(q, k, v, causal=True, interpret=True)
    ref = _attn_reference(q, k, v, True, 1.0 / math.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_flash_gqa_native():
    # tier-2 (round-16 re-tier): GQA fwd twin; tier-1 home:
    # test_flash_unpadded_gqa_and_grads (GQA incl. grads)
    """Native GQA routing: kv heads != q heads, no upstream repeat."""
    q, k, v = _rand_qkv(b=2, s=128, h=8, d=32, kv_heads=2)
    out = flash_attention_raw(q, k, v, causal=True, interpret=True)
    ref = _attn_reference(q, k, v, True, 1.0 / math.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        return (flash_attention_raw(q, k, v, causal=True,
                                    interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_attn_reference(q, k, v, True,
                                1.0 / math.sqrt(q.shape[-1])) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_grads_match_reference():
    q, k, v = _rand_qkv(b=1, s=64, h=2, d=32)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        return (flash_attention_raw(q, k, v, causal=True,
                                    interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_attn_reference(q, k, v, True, scale) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_op_through_tape():
    from paddle_tpu.ops.registry import dispatch

    q, k, v = _rand_qkv(b=1, s=32, h=2, d=16)
    tq = paddle.to_tensor(np.asarray(q)); tq.stop_gradient = False
    tk = paddle.to_tensor(np.asarray(k)); tk.stop_gradient = False
    tv = paddle.to_tensor(np.asarray(v)); tv.stop_gradient = False
    out = dispatch("pallas_flash_attention", tq, tk, tv, causal=True)
    loss = (out ** 2).sum()
    loss.backward()
    assert tq.grad is not None and tk.grad is not None and tv.grad is not None
    ref = _attn_reference(q, k, v, True, 1.0 / math.sqrt(16))
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _seg_reference(q, k, v, seg, causal, scale):
    import jax.numpy as jnp

    rep = q.shape[2] // k.shape[2]
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
    mask = (seg[:, :, None] == seg[:, None, :])[:, None]
    if causal:
        s = q.shape[1]
        mask = mask & jnp.tril(jnp.ones((s, s), bool))[None, None]
    logits = jnp.where(mask, logits, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vv)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_padding_mask(causal):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    q, k, v = _rand_qkv(b=2, s=160, h=4, d=32, kv_heads=2)
    lens = np.array([130, 96])
    seg = jnp.asarray((np.arange(160)[None, :] < lens[:, None])
                      .astype(np.int32))
    scale = 1.0 / math.sqrt(32)
    out = flash_attention_raw(q, k, v, causal=causal,
                              q_segment_ids=seg, kv_segment_ids=seg,
                              interpret=True)
    want = _seg_reference(q, k, v, seg, causal, scale)
    m = np.asarray(seg, bool)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(out) * m, np.asarray(want) * m,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_flash_segment_grads_match_reference():
    # tier-2 (round-16 re-tier): segment-grad breadth; tier-1 home: the
    # segment padding-mask fwd legs + unpadded GQA grads
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    q, k, v = _rand_qkv(b=2, s=128, h=2, d=32)
    lens = np.array([100, 64])
    seg = jnp.asarray((np.arange(128)[None, :] < lens[:, None])
                      .astype(np.int32))
    m = jnp.asarray(np.asarray(seg, bool)[:, :, None, None])
    scale = 1.0 / math.sqrt(32)

    def loss_flash(q, k, v):
        o = flash_attention_raw(q, k, v, causal=False, q_segment_ids=seg,
                                kv_segment_ids=seg, interpret=True)
        return ((o * m) ** 2).sum()

    def loss_ref(q, k, v):
        return ((_seg_reference(q, k, v, seg, False, scale) * m) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_packed_sequences():
    """Two sequences packed in one row: ids [1]*64 + [2]*64 — tokens of
    one packed sequence must not attend the other."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    q, k, v = _rand_qkv(b=1, s=128, h=2, d=32)
    seg = jnp.asarray(np.r_[np.full(64, 1), np.full(64, 2)][None, :]
                      .astype(np.int32))
    out = flash_attention_raw(q, k, v, causal=False, q_segment_ids=seg,
                              kv_segment_ids=seg, interpret=True)
    # first-half output must equal attention computed over first half only
    half = flash_attention_raw(q[:, :64], k[:, :64], v[:, :64], causal=False,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, :64]), np.asarray(half),
                               rtol=2e-5, atol=2e-5)


def test_incubate_routing_padding_mask_uses_pallas(monkeypatch):
    """A [b, sk] boolean mask must ride the Pallas path (not silently fall
    back to the XLA softmax path) when Pallas is available."""
    import paddle_tpu.incubate.nn.attention as attn_mod

    monkeypatch.setattr(attn_mod, "is_tpu", lambda device=None: True)
    calls = {}
    from paddle_tpu.ops.registry import dispatch as real_dispatch

    def spy(name, *a, **kw):
        calls[name] = calls.get(name, 0) + 1
        return real_dispatch(name, *a, **kw)

    monkeypatch.setattr(attn_mod, "dispatch", spy)
    q, k, v = _rand_qkv(b=2, s=96, h=2, d=32)
    mask = paddle.to_tensor(np.arange(96)[None, :]
                            < np.array([80, 60])[:, None])  # BOOL keep-mask
    out = attn_mod.flash_attention(paddle.to_tensor(np.asarray(q)),
                                   paddle.to_tensor(np.asarray(k)),
                                   paddle.to_tensor(np.asarray(v)),
                                   causal=False, attn_mask=mask)
    assert calls.get("pallas_flash_attention", 0) == 1, calls
    assert "scaled_dot_product_attention" not in calls
    # an INT mask is additive (sdpa semantics) and must NOT be rerouted
    imask = paddle.to_tensor(np.zeros((2, 1, 1, 96), np.float32))
    attn_mod.flash_attention(paddle.to_tensor(np.asarray(q)),
                             paddle.to_tensor(np.asarray(k)),
                             paddle.to_tensor(np.asarray(v)),
                             causal=False, attn_mask=imask)
    assert calls.get("scaled_dot_product_attention", 0) == 1, calls


def test_incubate_bool_mask_same_numerics_on_fallback(monkeypatch):
    """Pallas path and XLA fallback must agree on a bool keep-mask."""
    import paddle_tpu.incubate.nn.attention as attn_mod

    q, k, v = _rand_qkv(b=2, s=64, h=2, d=32)
    mask_np = np.arange(64)[None, :] < np.array([50, 30])[:, None]
    args = [paddle.to_tensor(np.asarray(t)) for t in (q, k, v)]
    monkeypatch.setattr(attn_mod, "is_tpu", lambda device=None: True)
    a = attn_mod.flash_attention(*args, causal=False,
                                 attn_mask=paddle.to_tensor(mask_np))
    monkeypatch.setattr(attn_mod, "is_tpu", lambda device=None: False)
    b = attn_mod.flash_attention(*args, causal=False,
                                 attn_mask=paddle.to_tensor(mask_np))
    m = mask_np[:, :, None, None]
    np.testing.assert_allclose(np.asarray(a._value) * m,
                               np.asarray(b._value) * m, rtol=2e-5,
                               atol=2e-5)


def test_flash_fully_masked_row_outputs_zero():
    """A q row whose segment id appears in NO key must output exactly 0
    with zero gradients — not a uniform attend-everything (the p=exp(0)
    poisoning when every s == m == NEG_INF)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    q, k, v = _rand_qkv(b=1, s=64, h=2, d=32)
    qs = np.full((1, 64), 1, np.int32)
    qs[0, 10] = 7                      # no key carries id 7
    ks = np.full((1, 64), 1, np.int32)
    out = flash_attention_raw(q, k, v, causal=False,
                              q_segment_ids=jnp.asarray(qs),
                              kv_segment_ids=jnp.asarray(ks),
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0, 10]), 0.0)

    def loss(q, k, v):
        o = flash_attention_raw(q, k, v, causal=False,
                                q_segment_ids=jnp.asarray(qs),
                                kv_segment_ids=jnp.asarray(ks),
                                interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
            / math.sqrt(32)
        mask = (jnp.asarray(qs)[:, :, None]
                == jnp.asarray(ks)[:, None, :])[:, None]
        logits = jnp.where(mask, logits, -1e30)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
        # reference softmax of an all -1e30 row is uniform: zero it to
        # match the kernel's (correct) empty-row convention
        o = o.at[0, 10].set(0.0)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_incubate_padded_rows_agree_between_paths(monkeypatch):
    """Pallas route and XLA fallback must now agree at EVERY position,
    including padded query rows (both use segment semantics)."""
    import paddle_tpu.incubate.nn.attention as attn_mod

    q, k, v = _rand_qkv(b=2, s=64, h=2, d=32)
    mask_np = np.arange(64)[None, :] < np.array([50, 30])[:, None]
    args = [paddle.to_tensor(np.asarray(t)) for t in (q, k, v)]
    monkeypatch.setattr(attn_mod, "is_tpu", lambda device=None: True)
    a = attn_mod.flash_attention(*args, causal=False,
                                 attn_mask=paddle.to_tensor(mask_np))
    monkeypatch.setattr(attn_mod, "is_tpu", lambda device=None: False)
    b = attn_mod.flash_attention(*args, causal=False,
                                 attn_mask=paddle.to_tensor(mask_np))
    np.testing.assert_allclose(np.asarray(a._value), np.asarray(b._value),
                               rtol=2e-5, atol=2e-5)


def test_incubate_decode_shape_bool_mask():
    """sq != sk (decode): a [b, sk] bool mask must broadcast correctly on
    the fallback (regression: the equality expand was gated on sq == sk
    and left the raw 2-D mask to misbroadcast)."""
    import paddle_tpu.incubate.nn.attention as attn_mod

    rng = np.random.RandomState(0)
    q = paddle.to_tensor(rng.randn(2, 1, 2, 16).astype(np.float32))
    k = paddle.to_tensor(rng.randn(2, 8, 2, 16).astype(np.float32))
    v = paddle.to_tensor(rng.randn(2, 8, 2, 16).astype(np.float32))
    mask = np.arange(8)[None, :] < np.array([6, 4])[:, None]
    out = attn_mod.flash_attention(q, k, v, causal=False,
                                   attn_mask=paddle.to_tensor(mask))
    assert tuple(out.shape) == (2, 1, 2, 16)
    # golden: masked softmax attention over valid keys only
    qj, kj, vj = (np.asarray(t._value) for t in (q, k, v))
    logits = np.einsum("bqhd,bkhd->bhqk", qj, kj) / np.sqrt(16)
    logits = np.where(mask[:, None, None, :], logits, -1e30)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, vj)
    np.testing.assert_allclose(np.asarray(out._value), want, rtol=2e-5,
                               atol=2e-5)


def test_incubate_segment_pair_required_together():
    import paddle_tpu.incubate.nn.attention as attn_mod

    q, k, v = _rand_qkv(b=1, s=32, h=2, d=16)
    args = [paddle.to_tensor(np.asarray(t)) for t in (q, k, v)]
    seg = paddle.to_tensor(np.ones((1, 32), np.int32))
    with pytest.raises(ValueError):
        attn_mod.flash_attention(*args, kv_segment_ids=seg)
    with pytest.raises(ValueError):
        attn_mod.flash_attention(*args, q_segment_ids=seg)
    with pytest.raises(ValueError):
        attn_mod.flash_attention(*args, q_segment_ids=seg,
                                 kv_segment_ids=seg,
                                 attn_mask=paddle.to_tensor(
                                     np.ones((1, 32), bool)))


# --------------------------------------------------------------------------
# varlen / ragged (flash_attn_unpadded): round-3 addition
# --------------------------------------------------------------------------

def _pack_ref(q, k, v, seqlens, causal=True):
    """Per-sequence dense attention, concatenated — the varlen golden."""
    from paddle_tpu.ops.pallas.flash_attention import _attn_reference

    outs = []
    off = 0
    for n in seqlens:
        sl = slice(off, off + n)
        outs.append(_attn_reference(q[None, sl], k[None, sl], v[None, sl],
                                    causal, 1.0 / np.sqrt(q.shape[-1]))[0])
        off += n
    return jnp.concatenate(outs, axis=0)


def test_flash_unpadded_parity():
    from paddle_tpu.ops.pallas.flash_attention import flash_attn_unpadded_raw

    rng = np.random.RandomState(3)
    seqlens = [5, 11, 8]
    total, h, d = sum(seqlens), 4, 16
    q = jnp.asarray(rng.randn(total, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(total, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(total, h, d).astype(np.float32))
    cu = jnp.asarray(np.cumsum([0] + seqlens).astype(np.int32))

    out = flash_attn_unpadded_raw(q, k, v, cu, cu, causal=True)
    ref = _pack_ref(q, k, v, seqlens, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_unpadded_gqa_and_grads():
    from paddle_tpu.ops.pallas.flash_attention import flash_attn_unpadded_raw

    rng = np.random.RandomState(4)
    seqlens = [7, 9]
    total, hq, kvh, d = sum(seqlens), 4, 2, 8
    q = jnp.asarray(rng.randn(total, hq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(total, kvh, d).astype(np.float32))
    v = jnp.asarray(rng.randn(total, kvh, d).astype(np.float32))
    cu = jnp.asarray(np.cumsum([0] + seqlens).astype(np.int32))
    cot = jnp.asarray(rng.randn(total, hq, d).astype(np.float32))

    def loss(q, k, v):
        return (flash_attn_unpadded_raw(q, k, v, cu, cu, causal=True)
                * cot).sum()

    def ref_loss(q, k, v):
        rep = hq // kvh
        kr = jnp.repeat(k, rep, axis=1)
        vr = jnp.repeat(v, rep, axis=1)
        return (_pack_ref(q, kr, vr, seqlens, causal=True) * cot).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


def test_flash_unpadded_isolation():
    """Tokens of one sequence must be invariant to another sequence's
    content (the whole point of the segment gate)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attn_unpadded_raw

    rng = np.random.RandomState(5)
    seqlens = [6, 10]
    total, h, d = sum(seqlens), 2, 8
    q = rng.randn(total, h, d).astype(np.float32)
    k = rng.randn(total, h, d).astype(np.float32)
    v = rng.randn(total, h, d).astype(np.float32)
    cu = jnp.asarray(np.cumsum([0] + seqlens).astype(np.int32))

    o1 = flash_attn_unpadded_raw(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), cu, cu)
    k2, v2 = k.copy(), v.copy()
    k2[6:], v2[6:] = 123.0, -7.0   # clobber sequence 1
    o2 = flash_attn_unpadded_raw(jnp.asarray(q), jnp.asarray(k2),
                                 jnp.asarray(v2), cu, cu)
    np.testing.assert_allclose(np.asarray(o1[:6]), np.asarray(o2[:6]),
                               rtol=1e-6)


def test_seg_block_overlap_predicate():
    """The kernel's tile gate, evaluated directly: disjoint-segment tiles
    report no overlap (skipped), intersecting tiles report overlap."""
    from paddle_tpu.ops.pallas.flash_attention import _seg_block_overlap

    # 2 sequences of 8 tokens, block 8: tile (q=1, k=0) is cross-segment
    ids = jnp.asarray([1] * 8 + [2] * 8, jnp.int32)
    qs, ks = ids[8:], ids[:8]
    assert not bool(_seg_block_overlap(qs, ks, 1, 0, 8, 8, 16, 16))
    # same-segment tile must run
    assert bool(_seg_block_overlap(ids[:8], ids[:8], 0, 0, 8, 8, 16, 16))
    # a tile straddling the boundary overlaps both neighbours
    strad = ids[4:12]
    assert bool(_seg_block_overlap(strad, ks, 0, 0, 8, 8, 16, 16))


def test_varlen_skip_fraction_beats_dense():
    """For a B-sequence packing the ragged kernel must skip a substantial
    fraction of tiles; dense-padded-with-masks skips none of these (it
    runs masked MXU work instead) — this is the >=30%-padding win."""
    from paddle_tpu.ops.pallas.flash_attention import \
        varlen_block_skip_fraction

    frac = varlen_block_skip_fraction([700, 900, 500, 1996], block=512)
    assert frac >= 0.3, frac


def test_head_batched_default_parity(monkeypatch):
    """The head-batched GQA kernels are the DEFAULT for unmasked dense
    calls (round-7, post root-cause fix): fwd+bwd parity with the
    per-head path, plus the PADDLE_TPU_FLASH_HEAD_BATCHED=0 kill switch
    routing back to the per-head kernels."""
    import jax

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    rng = np.random.RandomState(7)
    b, s, h, kvh, d = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, s, kvh, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, s, kvh, d).astype(np.float32))

    def loss(q, k, v):
        return jnp.sum(flash_attention_raw(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    # opt-OUT: env=0 must route the per-head kernels
    monkeypatch.setenv("PADDLE_TPU_FLASH_HEAD_BATCHED", "0")
    from paddle_tpu.ops.pallas import flash_attention as FA

    calls = []
    real = FA._flash_hb

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(FA, "_flash_hb", spy)
    base = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert not calls, "kill switch ignored: HB path taken under env=0"

    # default (no env): HB path must be taken and match
    monkeypatch.delenv("PADDLE_TPU_FLASH_HEAD_BATCHED", raising=False)
    hb = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert calls, "HB path was not taken by default"
    for a, b_ in zip(hb, base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)
