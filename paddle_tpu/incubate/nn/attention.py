"""Flash attention entry point.

Analog of the reference's FlashAttention integration
(paddle/phi/kernels/gpu/flash_attn_kernel.cu +
python/paddle/nn/functional/flash_attention.py:195). On TPU the fused
attention kernel is a Pallas kernel (paddle_tpu/ops/pallas/flash_attention.py);
on CPU (tests) or when Pallas is unavailable we fall back to the XLA softmax
path, which XLA still fuses well.
"""

from __future__ import annotations

from ...core.device import is_tpu
from ...ops.registry import dispatch


def _as_padding_segments(attn_mask, query, key):
    """A BOOLEAN [b, sk] (or [b, 1, 1, sk]) keep-mask maps onto the
    kernel's segment ids (valid=1, pad=0); anything else returns None and
    takes the XLA path.  Bool-only on purpose: integer/float masks are
    ADDITIVE in the XLA path (sdpa semantics), so routing them as keep
    masks would change numerics between backends."""
    m = attn_mask._value if hasattr(attn_mask, "_value") else attn_mask
    import jax.numpy as jnp

    if m.ndim == 4 and m.shape[1] == 1 and m.shape[2] == 1:
        m = m[:, 0, 0]
    if m.ndim != 2 or m.shape != (key.shape[0], key.shape[1]):
        return None
    if not jnp.issubdtype(m.dtype, jnp.bool_):
        return None
    if query.shape[1] != key.shape[1]:
        return None
    return m.astype(jnp.int32)


def flash_attention(query, key, value, causal=False, dropout=0.0,
                    attn_mask=None, scale=None, q_segment_ids=None,
                    kv_segment_ids=None):
    """(batch, seq, heads, head_dim) attention, flash-style.  GQA (fewer
    kv heads) is accepted: the Pallas kernel routes q heads to kv groups
    natively; the XLA fallback repeats kv heads.  A [b, sk] boolean
    padding mask — or explicit int [b, s] segment ids (sequence packing)
    — rides the Pallas path splash-attention style; arbitrary additive
    masks and dropout use the XLA path."""
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("q_segment_ids and kv_segment_ids must be given "
                         "together")
    if q_segment_ids is not None:
        if attn_mask is not None:
            raise ValueError("pass either attn_mask or segment ids, "
                             "not both")
        import jax.numpy as jnp

        qsv = q_segment_ids._value if hasattr(q_segment_ids, "_value") \
            else jnp.asarray(q_segment_ids)
        ksv = kv_segment_ids._value if hasattr(kv_segment_ids, "_value") \
            else jnp.asarray(kv_segment_ids)
        seg_pair = (qsv.astype(jnp.int32), ksv.astype(jnp.int32))
    else:
        seg_pair = None
    if attn_mask is not None and dropout == 0.0:
        seg = _as_padding_segments(attn_mask, query, key)
        if seg is not None:
            # the bool keep-mask is fully expressed as segment ids from
            # here on (both backends use the same equality semantics)
            seg_pair = (seg, seg)
            attn_mask = None
    if is_tpu() and dropout == 0.0 and attn_mask is None:
        # registers the op; only a shape outside the kernel's envelope
        # (e.g. causal sq != sk decode shapes) may take the XLA path —
        # any other kernel exception propagates
        from ...ops.pallas.flash_attention import FlashUnsupportedError

        kw = {}
        if seg_pair is not None:
            from ...core.tensor import Tensor as _T

            kw = {"q_segment_ids": _T(seg_pair[0]),
                  "kv_segment_ids": _T(seg_pair[1])}
        try:
            return dispatch("pallas_flash_attention", query, key, value,
                            causal=causal, scale=scale, **kw)
        except FlashUnsupportedError:
            pass
    rep = query.shape[2] // key.shape[2]
    if rep > 1:
        from ...ops.manip import repeat_interleave

        key = repeat_interleave(key, rep, axis=2)
        value = repeat_interleave(value, rep, axis=2)
    if attn_mask is None and seg_pair is not None:
        # segment ids on the XLA path: the same equality semantics the
        # Pallas kernel applies (bool keep-masks were folded into
        # seg_pair above, so this is the single masked-fallback branch)
        from ...core.tensor import Tensor

        attn_mask = Tensor(
            (seg_pair[0][:, :, None] == seg_pair[1][:, None, :])[:, None])
    elif attn_mask is not None:
        # masks _as_padding_segments rejected: decode shapes (sq != sk)
        # with a [b, sk] bool keep-mask normalize to the broadcastable
        # form; anything else (additive float/4-D) passes through as-is
        from ...core.tensor import Tensor
        import jax.numpy as jnp

        mv = attn_mask._value if isinstance(attn_mask, Tensor) else \
            jnp.asarray(attn_mask)
        if mv.ndim == 4 and mv.shape[1] == 1 and mv.shape[2] == 1 \
                and jnp.issubdtype(mv.dtype, jnp.bool_):
            mv = mv[:, 0, 0]
        if jnp.issubdtype(mv.dtype, jnp.bool_) and mv.ndim == 2 \
                and mv.shape == (key.shape[0], key.shape[1]):
            # every decode query is a live token; only keys carry padding
            attn_mask = Tensor(mv[:, None, None, :])
    dropout_mask = None
    if dropout > 0.0:
        from ...core.tensor import Tensor
        from ...ops import random as _random
        import jax.numpy as jnp

        b, sq, h, _ = query.shape
        sk = key.shape[1]
        k_ = _random.default_generator().next_key()
        dropout_mask = Tensor(jax.random.bernoulli(k_, 1.0 - dropout, (b, h, sq, sk)))
    return dispatch("scaled_dot_product_attention", query, key, value,
                    attn_mask=attn_mask, dropout_mask=dropout_mask,
                    dropout_p=dropout, is_causal=causal, scale=scale)
