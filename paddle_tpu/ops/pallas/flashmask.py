"""FlashMask attention — the reference fork's headline long-sequence
masking capability, TPU-native.

Reference surface: ``paddle.nn.functional.flashmask_attention``
(python/paddle/nn/functional/flash_attention.py:1098; op
paddle/phi/ops/yaml/ops.yaml:1913 ``flashmask_attention``; semantics
pinned by test/legacy_test/test_flashmask.py flashmask_to_densemask).

A dense [sq, sk] mask is expressed column-wise: for key column ``j`` the
masked rows are one or two CONTIGUOUS row bands.  ``startend_row_indices``
[b, mh, sk, {1, 2, 4}] int32 encodes them:

- causal=True,  last=1: band [r1, seq_q)           (causal document mask)
- causal=True,  last=2: band [r1, r2)              (share-question mask)
- causal=False, last=2: bands [r1, seq_q) + [0, r2) (bidirectional doc)
- causal=False, last=4: bands [r1, r2) + [r3, r4)  (global + sliding
  window etc — the reference API declares this class but its kernel
  raises NotImplementedError; here it is implemented)

Internally every class is normalised to four per-column row-bound vectors
(lts, lte, uts, ute) and fed to the Pallas flash kernel
(flash_attention.py), which masks score tiles with them AND skips tiles
whose row range is fully covered by the bands of every column
(_band_block_covered) — mask-structure-driven block skipping, the
FlashMask O(s·k) memory + sparse-compute win, on the MXU.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from ...core.device import pallas_interpret
from .flash_attention import (FlashUnsupportedError, flash_attention_raw,
                              segment_ids_from_cu_seqlens)

__all__ = [
    "flashmask_attention_raw", "normalize_startend_row_indices",
    "flashmask_to_dense_bias", "sliding_window_row_indices",
    "causal_document_row_indices", "share_question_row_indices",
    "global_sliding_row_indices", "flashmask_block_skip_fraction",
    "flash_attn_varlen_qkvpacked_raw",
]


def normalize_startend_row_indices(idx, causal: bool, seq_q: int):
    """[b, mh, sk, {1,2,4}] int32 -> 4 band arrays (lts, lte, uts, ute)
    each [b, mh, sk]: column j masks rows [lts, lte) ∪ [uts, ute)."""
    if idx.ndim != 4:
        raise ValueError(
            f"startend_row_indices rank must be 4, got shape {idx.shape}")
    idx = idx.astype(jnp.int32)
    last = idx.shape[-1]
    empty_s = jnp.zeros_like(idx[..., 0])
    if causal:
        if last == 1:
            lts, lte = idx[..., 0], jnp.full_like(idx[..., 0], seq_q)
            uts = ute = empty_s
        elif last == 2:
            lts, lte = idx[..., 0], idx[..., 1]
            uts = ute = empty_s
        else:
            raise ValueError(
                "causal flashmask expects last dim 1 or 2, got "
                f"{last}")
    else:
        if last == 2:
            lts = idx[..., 0]
            lte = jnp.full_like(lts, seq_q)
            uts, ute = empty_s, idx[..., 1]
        elif last == 4:
            lts, lte = idx[..., 0], idx[..., 1]
            uts, ute = idx[..., 2], idx[..., 3]
        else:
            raise ValueError(
                "non-causal flashmask expects last dim 2 or 4, got "
                f"{last}")
    return lts, lte, uts, ute


def flashmask_to_dense_bias(idx, causal: bool, seq_q: int,
                            dtype=jnp.float32, neg=-1e30):
    """Dense [b, mh, sq, sk] additive bias (0 / neg) expansion — the
    reference's flashmask_to_densemask (test/legacy_test/
    test_flashmask.py:78), used by tests and the XLA fallback path."""
    lts, lte, uts, ute = normalize_startend_row_indices(idx, causal, seq_q)
    rows = jnp.arange(seq_q, dtype=jnp.int32)[:, None]       # [sq, 1]
    lts, lte, uts, ute = (x[:, :, None, :] for x in (lts, lte, uts, ute))
    masked = (((rows >= lts) & (rows < lte))
              | ((rows >= uts) & (rows < ute)))
    if causal:
        cols = jnp.arange(idx.shape[2], dtype=jnp.int32)[None, :]
        masked = masked | (rows < cols)
    return jnp.where(masked, jnp.asarray(neg, dtype), jnp.asarray(0, dtype))


# --------------------------------------------------------------------------
# mask-class builders (the patterns from the reference docstring figures)
# --------------------------------------------------------------------------

def causal_document_row_indices(seqlens, *, dtype=np.int32):
    """Causal document mask (figure b): tokens attend causally WITHIN
    their document.  seqlens: per-document lengths -> [1, 1, total, 1]
    (column j of document ending at row e masks rows [e, total))."""
    ends = np.cumsum(np.asarray(seqlens, dtype=np.int64))
    total = int(ends[-1])
    r1 = np.repeat(ends, np.asarray(seqlens)).astype(dtype)
    return jnp.asarray(r1.reshape(1, 1, total, 1))


def share_question_row_indices(q_len, span, total, *, dtype=np.int32):
    """Share-question mask (reference figure e): the first ``q_len``
    (question) columns are visible to everyone EXCEPT rows in ``span`` =
    (start, end) — a middle answer segment attending only itself —
    while the remaining columns are pure causal.  Causal 2-bound class."""
    r = np.full((total, 2), total, dtype=dtype)
    s, e = span
    r[:q_len, 0] = s
    r[:q_len, 1] = e
    return jnp.asarray(r.reshape(1, 1, total, 2))


def sliding_window_row_indices(seq_len, window, causal: bool,
                               *, dtype=np.int32):
    """window_size -> startend_row_indices, exactly the reference's
    expansion (flash_attention.py:1395): causal -> [.., 1] with
    r1 = clip(j + w0 + 1, max=s); bidirectional -> [.., 2] adding
    r2 = clip(j - w1, 0, s)."""
    if isinstance(window, int):
        window = (window, window)
    j = np.arange(seq_len, dtype=np.int64)
    if causal:
        r1 = np.clip(j + window[0] + 1, None, seq_len).astype(dtype)
        return jnp.asarray(r1.reshape(1, 1, seq_len, 1))
    r1 = np.clip(j + window[0] + 1, None, seq_len).astype(dtype)
    r2 = np.clip(j - window[1], 0, seq_len).astype(dtype)
    return jnp.asarray(
        np.stack([r1, r2], axis=-1).reshape(1, 1, seq_len, 2))


def global_sliding_row_indices(seq_len, window, n_global,
                               *, dtype=np.int32):
    """Global + sliding-window mask (figure g, the 4-bound class): the
    first ``n_global`` columns are globally visible; other columns are
    visible only within ``window`` rows around the diagonal."""
    j = np.arange(seq_len, dtype=np.int64)
    lts = np.clip(j + window + 1, None, seq_len)
    lte = np.full(seq_len, seq_len, dtype=np.int64)
    uts = np.zeros(seq_len, dtype=np.int64)
    ute = np.clip(j - window, 0, seq_len)
    lts[:n_global] = seq_len       # global cols: empty lower band
    ute[:n_global] = 0             # ... and empty upper band
    out = np.stack([lts, lte, uts, ute], axis=-1).astype(dtype)
    return jnp.asarray(out.reshape(1, 1, seq_len, 4))


def flashmask_block_skip_fraction(idx, causal: bool, seq_q: int,
                                  block: int = 512) -> float:
    """Host-side estimate of the fraction of (q, k) tiles the kernel
    skips for this mask (the same cover predicate _band_block_covered
    gates on, plus the causal triangle)."""
    lts, lte, uts, ute = (np.asarray(x) for x in
                          normalize_startend_row_indices(
                              jnp.asarray(idx), causal, seq_q))
    b, mh, sk = lts.shape
    nq = -(-seq_q // block)
    nk = -(-sk // block)
    run = skip = 0
    for bi in range(b):
        for hi in range(mh):
            for qi in range(nq):
                q_lo, q_hi = qi * block, min((qi + 1) * block, seq_q)
                for ki in range(nk):
                    if causal and (qi + 1) * block - 1 < ki * block:
                        skip += 1
                        continue
                    sl = slice(ki * block, min((ki + 1) * block, sk))
                    a, b_, c, d = lts[bi, hi, sl], lte[bi, hi, sl], \
                        uts[bi, hi, sl], ute[bi, hi, sl]
                    lt = (a <= q_lo) & (b_ >= q_hi)
                    ut = (c <= q_lo) & (d >= q_hi)
                    j1 = (a <= q_lo) & (c <= b_) & (d >= q_hi)
                    j2 = (c <= q_lo) & (a <= d) & (b_ >= q_hi)
                    if np.all(lt | ut | j1 | j2):
                        skip += 1
                    else:
                        run += 1
    return skip / max(run + skip, 1)


# --------------------------------------------------------------------------
# public entries
# --------------------------------------------------------------------------

def flashmask_attention_raw(q, k, v, startend_row_indices=None, *,
                            causal: bool = False, window_size=None,
                            scale=None, interpret=None, blocks=None):
    """q/k/v: [b, s, h|kvh, d].  startend_row_indices: [b, mh, sk,
    {1,2,4}] int32, mh in {1, kvh}.  Returns [b, s, h, d].

    Runs the Pallas flash kernel with per-column band masking and
    mask-structure-driven block skipping.  The 4-bound non-causal class
    (which the reference declares but leaves NotImplementedError) is
    supported."""
    if window_size is not None:
        if startend_row_indices is not None:
            raise ValueError(
                "can't use window_size with startend_row_indices")
        sri = sliding_window_row_indices(q.shape[1], window_size, causal)
        startend_row_indices = jnp.broadcast_to(
            sri, (q.shape[0],) + sri.shape[1:])
    if startend_row_indices is None:
        return flash_attention_raw(q, k, v, causal=causal, scale=scale,
                                   interpret=interpret, blocks=blocks)
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    idx = startend_row_indices
    if idx.shape[0] != b or idx.shape[2] != k.shape[1]:
        raise ValueError(
            f"startend_row_indices shape {idx.shape} does not match "
            f"batch {b} / seqlen_k {k.shape[1]}")
    if idx.shape[1] not in (1, kvh):
        raise ValueError(
            f"startend_row_indices head dim must be 1 or kv heads "
            f"({kvh}), got {idx.shape[1]}")
    bands = normalize_startend_row_indices(idx, causal, sq)
    return flash_attention_raw(q, k, v, causal=causal, scale=scale,
                               interpret=interpret, blocks=blocks,
                               mask_bands=bands)


def flash_attn_varlen_qkvpacked_raw(qkv, cu_seqlens_q, cu_seqlens_k,
                                    max_seqlen_q=None, max_seqlen_k=None,
                                    scale=None, causal: bool = False,
                                    varlen_padded: bool = True,
                                    interpret=None):
    """Reference flash_attn_varlen_qkvpacked (python/paddle/nn/functional/
    flash_attention.py:848; GPU kernel FlashAttnVarlenQKVPackedKernel).

    qkv: [total, g + 2, kvh, d] with g = h // kvh — the first g slots
    along axis 1 are q heads (flattened g-major, so reference q head
    ``hq`` maps to kv head ``hq % kvh``), then k, then v.

    varlen_padded=True means the PADDED layout (total = b * max_seqlen,
    each sequence i occupying rows [i*max_seqlen, i*max_seqlen+len_i),
    output zero-padded); False means the packed layout of
    flash_attn_unpadded.  Returns out [total, h, d]."""
    total, g2, kvh, d = qkv.shape
    g = g2 - 2
    if g < 1:
        raise FlashUnsupportedError(
            f"qkv axis 1 must be h/kvh + 2, got {g2}")
    h = g * kvh
    q = qkv[:, :g]                     # [total, g, kvh, d]
    k = qkv[:, g]                      # [total, kvh, d]
    v = qkv[:, g + 1]
    # reference head order is g-major (hq -> kv head hq % kvh); the
    # Pallas kernel's GQA map is group-major (hq -> hq // g), so present
    # q as [total, kvh*g, d] and un-permute the output back
    qg = q.transpose(0, 2, 1, 3).reshape(total, h, d)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = pallas_interpret()

    if varlen_padded:
        if max_seqlen_q is None:
            raise ValueError("varlen_padded=True requires max_seqlen_q")
        pos = jnp.arange(total, dtype=jnp.int32)
        seq_i = pos // max_seqlen_q
        off = pos % max_seqlen_q
        cu_q = cu_seqlens_q.astype(jnp.int32)
        cu_k = cu_seqlens_k.astype(jnp.int32)
        len_q = cu_q[seq_i + 1] - cu_q[seq_i]
        len_k = cu_k[seq_i + 1] - cu_k[seq_i]
        # real tokens carry their sequence id; q-side padding and k-side
        # padding get DISJOINT unique negatives so padded rows match no
        # key at all (the kernel zeroes such rows and pins their lse)
        qs = jnp.where(off < len_q, seq_i + 1, -(2 * pos + 2))
        ks = jnp.where(off < len_k, seq_i + 1, -(2 * pos + 3))
    else:
        qs = segment_ids_from_cu_seqlens(cu_seqlens_q, total)
        ks = segment_ids_from_cu_seqlens(cu_seqlens_k, total)
    blocks = (1024, 1024) if not interpret else None
    out = flash_attention_raw(
        qg[None], k[None], v[None], causal=causal, scale=scale,
        interpret=interpret, q_segment_ids=qs[None].astype(jnp.int32),
        kv_segment_ids=ks[None].astype(jnp.int32), blocks=blocks)[0]
    # back to reference g-major head order
    return out.reshape(total, kvh, g, d).transpose(0, 2, 1, 3).reshape(
        total, h, d)


# framework op registration (tape + AMP aware)
from ..registry import register  # noqa: E402


@register("flashmask_attention", amp="white")
def flashmask_attention_op(q, k, v, startend_row_indices=None,
                           dropout=0.0, causal=False, window_size=None,
                           scale=None):
    if dropout:
        raise NotImplementedError(
            "flashmask_attention: dropout is a GPU-kernel feature; apply "
            "nn.functional.dropout outside attention")
    return flashmask_attention_raw(q, k, v, startend_row_indices,
                                   causal=causal, window_size=window_size,
                                   scale=scale)


@register("flash_attn_varlen_qkvpacked", amp="white")
def flash_attn_varlen_qkvpacked_op(qkv, cu_seqlens_q, cu_seqlens_k,
                                   max_seqlen_q=None, max_seqlen_k=None,
                                   scale=None, dropout=0.0, causal=False,
                                   varlen_padded=True):
    if dropout:
        raise NotImplementedError(
            "flash_attn_varlen_qkvpacked: dropout is a GPU-kernel "
            "feature; apply nn.functional.dropout outside attention")
    return flash_attn_varlen_qkvpacked_raw(
        qkv, cu_seqlens_q, cu_seqlens_k, max_seqlen_q, max_seqlen_k,
        scale=scale, causal=causal, varlen_padded=varlen_padded)
