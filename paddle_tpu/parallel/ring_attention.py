"""Ring attention — exact long-context attention over a seq-sharded axis.

The reference snapshot has NO ring/context parallelism (SURVEY.md §2.7 "Ring
attention: not present"); its long-context story is the sep axis + SP +
FlashAttention.  This module EXCEEDS reference capability: blockwise-exact
attention for sequences sharded over a mesh axis, k/v blocks rotating the
ring via collective_permute (ICI neighbour hops) while each hop's compute
runs the Pallas flash kernel — communication hidden behind the flash tiles.

Forward algorithm (per device, inside shard_map over ``axis``):
  local q block stays; k/v blocks make P-1 ring hops.  Each hop computes
  (o_i, lse_i) for the visiting block — causal structure decided by
  (my_rank, src_rank): src < me full block, src == me causal, src > me
  skipped — then merges online:  m' = max(m, lse_i),
  acc' = acc*e^{m-m'} + o_i*l_i*e^{lse_i-m'}, l' likewise.  Final
  o = acc / l.  This is blockwise-exact (same math as flash across blocks).

Backward is a second ring pass (custom_vjp): the forward saves the fully
merged output o and GLOBAL row logsumexp.  Each hop re-runs the tiled
Pallas flash backward on (q_local, k_src, v_src) with the global lse, which
yields that hop's exact contribution to dq (accumulated locally) and to
dk/dv of the VISITING block.  dk/dv accumulators travel the ring with
their k/v blocks, so after P hops every device holds the complete gradient
for its own block — the standard ring-attention backward schedule.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import compat as _compat


from jax.lax import axis_size as _axis_size

from ..core.device import pallas_interpret as _interpret


def _varying(x, axis):
    """Pre-cast axis-invariant constants to device-varying so shard_map's
    vma typing accepts them as loop carries."""
    try:
        return lax.pcast(x, (axis,), to="varying")
    except AttributeError:
        return x


def _local_flash(q, k, v, causal, scale):
    """Per-block flash on [b, s, h, d]; returns (o, lse[b,h,s])."""
    from ..ops.pallas.flash_attention import _flash_forward, _to_bh

    b, sq, h, d = q.shape
    kvh = k.shape[2]
    of, lse = _flash_forward(_to_bh(q), _to_bh(k), _to_bh(v), causal, scale,
                             h=h, kvh=kvh, interpret=_interpret())
    o = of.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return o.astype(jnp.float32), lse[:, 0, :].reshape(b, h, sq)


def _hop_branch(src, me):
    """0 = full block (src < me), 1 = diagonal causal (src == me),
    2 = skip (src > me, all keys in the future)."""
    return (src == me).astype(jnp.int32) + (src > me).astype(jnp.int32) * 2


def _ring_forward_loop(q, k, v, axis, causal, scale):
    """Returns (o [b,s,h,d] float32, lse_global [b,h,s,1] float32)."""
    p = _axis_size(axis)
    me = lax.axis_index(axis)
    b, sl, h, d = q.shape

    m = _varying(jnp.full((b, h, sl, 1), -jnp.inf, dtype=jnp.float32), axis)
    l = _varying(jnp.zeros((b, h, sl, 1), dtype=jnp.float32), axis)
    acc = _varying(jnp.zeros((b, sl, h, d), dtype=jnp.float32), axis)
    perm = [(i, (i + 1) % p) for i in range(p)]  # send k/v to the right

    def merge(carry, block_kv, src):
        m_prev, l_prev, acc_prev = carry
        kb, vb = block_kv

        def attend(causal_flag):
            def f():
                o_i, lse_i = _local_flash(q, kb, vb, causal_flag, scale)
                return o_i, lse_i.reshape(b, h, sl, 1)
            return f

        if causal:
            def skip():
                # src > me: q tokens all precede the visiting k block
                return (jnp.zeros((b, sl, h, d), jnp.float32),
                        jnp.full((b, h, sl, 1), -jnp.inf, jnp.float32))

            # one branch executes per hop (lax.switch, not where-over-both)
            o_i, lse_i = lax.switch(_hop_branch(src, me),
                                    [attend(False), attend(True), skip])
        else:
            o_i, lse_i = attend(False)()

        m_new = jnp.maximum(m_prev, lse_i)
        # guard -inf - -inf
        safe = lambda x, mn: jnp.where(jnp.isinf(mn) & (mn < 0), 0.0,
                                       jnp.exp(x - mn))
        alpha = safe(m_prev, m_new)                     # rescale old
        beta = safe(lse_i, m_new)                       # weight of new block
        l_new = l_prev * alpha + beta
        # o_i is already softmax-normalised within its block (divided by
        # l_i = e^{lse_i - m_i} sums); re-weight by beta
        acc_new = acc_prev * alpha.transpose(0, 2, 1, 3) + \
            o_i * beta.transpose(0, 2, 1, 3)
        return m_new, l_new, acc_new

    # p is static (mesh axis size), so unroll in Python: XLA overlaps each
    # hop's ppermute with the previous hop's flash compute, and the final
    # hop skips the k/v rotation entirely (its result would be discarded)
    kb, vb = k, v
    for i in range(p):
        src = (me - i) % p  # after i hops we hold rank (me - i)'s block
        m, l, acc = merge((m, l, acc), (kb, vb), src)
        if i != p - 1:
            kb = _compat.ppermute(kb, axis, perm)
            vb = _compat.ppermute(vb, axis, perm)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = acc / l_safe.transpose(0, 2, 1, 3)
    # global logsumexp of each row (backward residual): lse = m + log(l)
    lse = jnp.where(l > 0.0, m + jnp.log(l_safe), -jnp.inf)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis, causal, scale):
    o, _ = _ring_forward_loop(q, k, v, axis, causal, scale)
    return o.astype(q.dtype)


def _ring_fwd(q, k, v, axis, causal, scale):
    o, lse = _ring_forward_loop(q, k, v, axis, causal, scale)
    return o.astype(q.dtype), (q, k, v, o.astype(q.dtype), lse)


def _ring_bwd(axis, causal, scale, res, g):
    from ..ops.pallas.flash_attention import (_flash_backward, _from_bh,
                                              _to_bh)

    q, k, v, o, lse = res
    p = _axis_size(axis)
    me = lax.axis_index(axis)
    b, sl, h, d = q.shape
    kvh = k.shape[2]
    interpret = _interpret()
    perm = [(i, (i + 1) % p) for i in range(p)]

    # the Pallas backward consumes lse as [b*h, 8, s] float32 (sublane-
    # replicated rows); broadcasting the global lse here makes each hop's
    # recomputed p_ij the TRUE global softmax prob, so per-hop dq/dk/dv
    # are exact contributions that sum to the full gradient.
    lse8 = jnp.broadcast_to(
        lse[:, :, :, 0].reshape(b * h, 1, sl), (b * h, 8, sl))
    qf, of, gf = _to_bh(q), _to_bh(o), _to_bh(g.astype(o.dtype))

    def hop_grads(kb, vb, causal_flag):
        def f():
            dq_i, dk_i, dv_i = _flash_backward(
                qf, _to_bh(kb), _to_bh(vb), of, lse8, gf,
                causal_flag, scale, h=h, kvh=kvh, interpret=interpret)
            return (_from_bh(dq_i, b, h).astype(jnp.float32),
                    _from_bh(dk_i, b, kvh).astype(jnp.float32),
                    _from_bh(dv_i, b, kvh).astype(jnp.float32))
        return f

    dq = _varying(jnp.zeros((b, sl, h, d), jnp.float32), axis)
    dkb = _varying(jnp.zeros((b, sl, kvh, d), jnp.float32), axis)
    dvb = _varying(jnp.zeros((b, sl, kvh, d), jnp.float32), axis)
    kb, vb = k, v
    for i in range(p):  # p static: unrolled, final k/v rotation skipped
        src = (me - i) % p

        def skip():
            return (jnp.zeros((b, sl, h, d), jnp.float32),
                    jnp.zeros((b, sl, kvh, d), jnp.float32),
                    jnp.zeros((b, sl, kvh, d), jnp.float32))

        if causal:
            dq_i, dk_i, dv_i = lax.switch(
                _hop_branch(src, me),
                [hop_grads(kb, vb, False), hop_grads(kb, vb, True), skip])
        else:
            dq_i, dk_i, dv_i = hop_grads(kb, vb, False)()

        dq = dq + dq_i
        dkb = dkb + dk_i
        dvb = dvb + dv_i
        # dk/dv accumulators travel WITH their k/v block: after p hops
        # (their rotation runs on the last hop too) every block is home
        # again carrying all devices' contributions; the k/v blocks
        # themselves are no longer needed after the last compute
        if i != p - 1:
            kb = _compat.ppermute(kb, axis, perm)
            vb = _compat.ppermute(vb, axis, perm)
        dkb = _compat.ppermute(dkb, axis, perm)
        dvb = _compat.ppermute(dvb, axis, perm)
    return dq.astype(q.dtype), dkb.astype(k.dtype), dvb.astype(v.dtype)


_ring_flash.defvjp(_ring_fwd, _ring_bwd)


def ring_flash_attention(q, k, v, axis: str = "sep", causal: bool = True,
                         scale: Optional[float] = None):
    """Exact (and exactly differentiable) attention for seq-sharded q,k,v
    inside a shard_map body.

    q: [b, s_local, h, d]; k,v: [b, s_local, kvh, d], all sharded on dim 1
    over ``axis``.  Returns [b, s_local, h, d] (same sharding).  Supports
    ``jax.grad`` through it — the backward runs a reverse ring schedule
    reusing the tiled Pallas flash backward per hop.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _ring_flash(q, k, v, axis, bool(causal), float(scale))
