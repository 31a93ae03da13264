"""Compiled pipeline parallelism: GPipe/1F1B inside one XLA program.

Analog of the reference's pipeline runtimes — the eager 1F1B scheduler
(fleet/meta_parallel/pipeline_parallel.py:547), the static scheduler passes
(passes/pipeline_scheduler_pass/pipeline_1f1b.py:39, pipeline_zero_bubble
.py:62), and the P2P layer (pp_utils/p2p_communication.py) — collapsed the
TPU way: ONE jitted shard_map over the ``pp`` mesh axis.  Per-stage
parameters are stacked on a leading axis and sharded over pp, so each
device holds its stage; micro-batch activations advance one stage per tick
via collective_permute (ICI neighbour hop).  XLA overlaps each tick's
ppermute with the next tick's compute — the 1F1B "steady state" falls out
of dataflow rather than an actor runtime (FleetExecutor, SURVEY §2.6).

The schedule below is the forward pass; backward through it is jax.grad
(XLA reverses the scan, recomputing per-tick state under remat) — so the
bubble count matches GPipe: (P-1) ticks each direction.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from . import compat as _compat


from jax.lax import axis_size as _axis_size

def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jnp.ndarray,
                   axis: str = "pp", num_microbatches: int | None = None,
                   squeeze_stage_dim: bool = True):
    """Run a P-stage pipeline inside a shard_map body.

    stage_fn(params_slice, activation) -> activation  — one stage's compute
    stage_params: pytree whose leaves have leading dim 1 (this device's
        stage slice of the stacked [P, ...] parameters); pass
        ``squeeze_stage_dim=False`` when the leading dim is itself
        meaningful to stage_fn (e.g. layer-major [L/P, ...] stacks that
        the stage scans over)
    x: [M, mb, ...] this call's micro-batched input — every device receives
        the same x (replicated); only stage 0 consumes it.
    Returns [M, mb, ...] outputs (valid on the LAST stage; other devices
        hold zeros — callers usually ppermute/psum or read stage P-1).
    """
    p = _axis_size(axis)
    me = lax.axis_index(axis)
    m = x.shape[0] if num_microbatches is None else num_microbatches
    ticks = m + p - 1
    perm = [(i, (i + 1) % p) for i in range(p)]

    def _varying(v):
        try:
            return lax.pcast(v, (axis,), to="varying")
        except AttributeError:
            return v

    params = jax.tree_util.tree_map(lambda a: a[0], stage_params) \
        if squeeze_stage_dim else stage_params
    state = _varying(jnp.zeros_like(x[0]))            # current activation
    outs = _varying(jnp.zeros((m,) + tuple(x.shape[1:]), x.dtype))

    def tick(t, carry):
        state, outs = carry
        # stage 0 ingests micro-batch t (while it exists); other stages use
        # what arrived from the left neighbour
        feed = lax.dynamic_index_in_dim(x, jnp.minimum(t, m - 1), axis=0,
                                        keepdims=False)
        inp = jnp.where(me == 0, feed, state)
        out = stage_fn(params, inp)
        # last stage emits micro-batch t-(p-1); masked write (a cond would
        # trip the vma type check: branches differ in axis-variance)
        emit_idx = t - (p - 1)
        valid = (me == p - 1) & (emit_idx >= 0)
        emit = (jnp.arange(m) == emit_idx) & valid
        emit = emit.reshape((m,) + (1,) * (outs.ndim - 1))
        outs = jnp.where(emit, out.astype(outs.dtype)[None], outs)
        # advance the ring: stage i's output becomes stage i+1's input
        state = _compat.ppermute(out, axis, perm)
        return state, outs

    _, outs = lax.fori_loop(0, ticks, tick, (state, outs))
    return outs


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack a list of per-stage param pytrees into [P, ...] leaves (the
    layout pipeline_apply shards over pp)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0),
                                  *per_stage_params)


def device_major_order(sched):
    """Placement-aware device-major position list for a Schedule:
    stacked position r*v + j holds global stage ``sched.stage_of(r, j)``
    (Megatron-interleaved for VPP, zigzag for ZBV).  Returns (order,
    inverse) with the same contract as vpp_device_major_order."""
    p, v = sched.p, sched.v
    order = [sched.stage_of(r, j) for r in range(p) for j in range(v)]
    inv = [0] * (p * v)
    for pos, st in enumerate(order):
        inv[st] = pos
    return order, inv


def vpp_device_major_order(p: int, v: int):
    """Megatron VPP placement as a position list: stacked position
    r*v + j holds global stage j*p + r (device-major), so sharding dim 0
    over ``pp`` hands rank r exactly its chunks in chunk order.  Returns
    (order, inverse): ``stacked[i] = stages[order[i]]`` and
    ``stages[s] = stacked[inverse[s]]``."""
    order = [j * p + r for r in range(p) for j in range(v)]
    inv = [0] * (p * v)
    for pos, st in enumerate(order):
        inv[st] = pos
    return order, inv


def stack_stage_params_interleaved(per_stage_params: list, p: int) -> Any:
    """Stack per-GLOBAL-stage params for a VPP run: with v chunks per rank,
    device r holds global stages {r, r+p, ..., r+(v-1)p} (Megatron VPP
    placement), so the stacked [p*v, ...] leading dim is ordered
    device-major: position r*v + j holds stage j*p + r.  Sharding dim 0
    over ``pp`` then gives each device exactly its chunks, in chunk order.
    """
    n = len(per_stage_params)
    assert n % p == 0, f"{n} stages not divisible by {p} ranks"
    order, _ = vpp_device_major_order(p, n // p)
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack([xs[i] for i in order], axis=0),
        *per_stage_params)


# --------------------------------------------------------------------------
# schedule-explicit compiled train step (1F1B / VPP / zero-bubble / FThenB)
# --------------------------------------------------------------------------

def pipeline_train_step(stage_fn: Callable, loss_fn: Callable, sched,
                        stage_params: Any, x: jnp.ndarray, y: jnp.ndarray,
                        axis: str = "pp", loss_params: Any = None,
                        want_x_grad: bool = False):
    """Run one forward+backward over micro-batches under an explicit
    pipeline schedule, inside a shard_map body.  Returns (mean_loss,
    param_grads) where grads match ``stage_params``' layout.

    With ``loss_params`` (a pytree closed into the loss head — final
    norm + LM head weights), loss_fn is called as ``loss_fn(loss_params,
    act, y_mb)`` and the step ALSO returns their accumulated grads; with
    ``want_x_grad=True`` it returns the per-microbatch gradient w.r.t.
    the stage-0 INPUT (``[m, ...]``, valid on rank 0) — what an
    embedding outside the pipeline needs for its backward.  Full return
    shape: (loss, param_grads[, loss_param_grads][, x_grads]).

    The TPU translation of the reference's schedule runtimes
    (fleet/meta_parallel/pipeline_parallel.py:547 1F1B, :1143 interleave,
    passes/pipeline_scheduler_pass/pipeline_zero_bubble.py:62): the
    schedule is a static table (paddle_tpu.parallel.schedules) and each
    tick dispatches one op — FWD, BWD (fused dx+dw), BWDX (dx only) or
    BWDW (dw only) — with exactly one ppermute per direction per tick.
    Backward recomputes the stage forward from the stashed input (per-op
    remat; the schedule's memory bound is its ``num_slots``).

    stage_fn(chunk_params, act) -> act             (uniform act shapes)
    loss_fn(act, y_mb) -> scalar                   (applied at last stage;
        with ``loss_params`` the signature becomes
        loss_fn(loss_params, act, y_mb))
    sched: a ``schedules.Schedule`` for (p, m, v)
    stage_params: pytree with leading dim v (this device's chunk slice —
        shard a [p*v, ...] stack over ``axis``; use
        stack_stage_params_interleaved for v > 1)
    x, y: [m, ...] micro-batched inputs/targets, replicated.
    """
    from .schedules import BWD, BWDW, BWDX, FWD

    p = _axis_size(axis)
    me = lax.axis_index(axis)
    assert p == sched.p, f"schedule built for p={sched.p}, mesh has {p}"
    m, v = sched.m, sched.v
    perm_r = [(i, (i + 1) % p) for i in range(p)]
    perm_l = [(i, (i - 1) % p) for i in range(p)]

    act_shape = x.shape[1:]
    act_dtype = x.dtype

    kind_t = jnp.asarray(sched.kind)
    mb_t = jnp.asarray(sched.mb)
    chunk_t = jnp.asarray(sched.chunk)
    slot_t = jnp.asarray(sched.slot)
    rs_t = jnp.asarray(sched.recv_slot)      # [3, p, ticks] per channel
    rm_t = jnp.asarray(sched.recv_mask)
    ri_t = jnp.asarray(sched.recv_isact)
    asend_t = jnp.asarray(sched.asend_ch)
    gsend_t = jnp.asarray(sched.gsend_ch)

    def _varying(z):
        try:
            return lax.pcast(z, (axis,), to="varying")
        except AttributeError:
            return z

    S = sched.num_slots
    stash0 = _varying(jnp.zeros((S,) + act_shape, act_dtype))
    gin0 = _varying(jnp.zeros((S,) + act_shape, act_dtype))
    # one carry per comm channel: rightward ring, leftward ring, local
    # (the V placement's same-rank stage hand-off)
    carries0 = tuple(_varying(jnp.zeros(act_shape, act_dtype))
                     for _ in range(3))
    gacc0 = jax.tree_util.tree_map(
        lambda a: _varying(jnp.zeros(a.shape, jnp.float32)), stage_params)
    # loss-head grads (final norm/LM head outside the stages) and the
    # stage-0 input grads (for an embedding outside the pipeline)
    lacc0 = jax.tree_util.tree_map(
        lambda a: _varying(jnp.zeros(jnp.shape(a), jnp.float32)),
        loss_params) if loss_params is not None else _varying(
        jnp.zeros((), jnp.float32))
    dxs0 = _varying(jnp.zeros((m,) + act_shape, act_dtype)) \
        if want_x_grad else _varying(jnp.zeros((), jnp.float32))
    loss0 = _varying(jnp.zeros((), jnp.float32))

    # placement-aware: interleaved puts the last global stage on rank
    # p-1; the ZBV zigzag turns back so rank 0 holds BOTH stage 0 and
    # the last stage (v even)
    is_last = (me == sched.rank_of_stage(p * sched.v - 1))
    is_first = (me == sched.rank_of_stage(0))

    def _chunk_params(ch):
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, ch, 0, keepdims=False),
            stage_params)

    def _upd(buf, val, idx):
        return lax.dynamic_update_index_in_dim(buf, val.astype(buf.dtype),
                                               idx, 0)

    def tick(t, carry):
        stash, gin, carries, gacc, lacc, dxs, loss_acc = carry

        # 1) store this tick's arrivals (what last tick's channels
        # delivered): per channel, an activation goes to the stash, an
        # upstream grad to the grad buffer
        for ch in range(3):
            sl_, mk, ia = rs_t[ch, me, t], rm_t[ch, me, t], ri_t[ch, me, t]
            cur = lax.dynamic_index_in_dim(stash, sl_, 0, keepdims=False)
            stash = _upd(stash, jnp.where((mk == 1) & (ia == 1),
                                          carries[ch], cur), sl_)
            curg = lax.dynamic_index_in_dim(gin, sl_, 0, keepdims=False)
            gin = _upd(gin, jnp.where((mk == 1) & (ia == 0),
                                      carries[ch], curg), sl_)

        k = kind_t[me, t]
        mb = jnp.maximum(mb_t[me, t], 0)
        ch = chunk_t[me, t]
        sl = slot_t[me, t]
        pc = _chunk_params(ch)
        xin = lax.dynamic_index_in_dim(x, mb, 0, keepdims=False)
        yin = lax.dynamic_index_in_dim(y, mb, 0, keepdims=False)
        stashed = lax.dynamic_index_in_dim(stash, sl, 0, keepdims=False)
        g_up = lax.dynamic_index_in_dim(gin, sl, 0, keepdims=False)

        zero_act = jnp.zeros(act_shape, act_dtype)
        first_here = is_first & (ch == 0)

        def _loss_grad(out, lacc):
            """Upstream grad at this op's stage: the loss gradient if this
            is the last global stage, else the stashed arrival.  Computed
            unconditionally on every rank — uniform SPMD program; the
            unused value is dead weight XLA overlaps, not a branch."""
            last_here = is_last & (ch == v - 1)
            if loss_params is not None:
                # COST NOTE: the head vjp runs on EVERY rank (uniform
                # SPMD — it cannot be lax.cond'ed away, because an
                # mp-sharded head emits collectives inside the vjp and
                # per-rank branch divergence around collectives
                # deadlocks); (p-1)/p of the head FLOPs + the fp32 lacc
                # buffer are the price.  For very large vocabs, fold the
                # head into the LAST stage's chunk params instead of
                # loss_params.
                l, lvjp = jax.vjp(
                    lambda lp, o: loss_fn(lp, o, yin), loss_params, out)
                dlp, gl = lvjp(jnp.ones((), l.dtype) / (m))
                lacc = jax.tree_util.tree_map(
                    lambda acc, d: acc + jnp.where(
                        last_here, d.astype(jnp.float32), 0.0),
                    lacc, dlp)
            else:
                l, lvjp = jax.vjp(lambda o: loss_fn(o, yin), out)
                (gl,) = lvjp(jnp.ones((), l.dtype) / (m))
            gl = gl.astype(act_dtype)
            return (jnp.where(last_here, gl, g_up),
                    jnp.where(last_here, l / m, 0.0).astype(jnp.float32),
                    lacc)

        def _stash_dx(dxs, dx):
            """Record stage-0's input grad for micro-batch ``mb``."""
            if not want_x_grad:
                return dxs
            cur = lax.dynamic_index_in_dim(dxs, mb, 0, keepdims=False)
            return _upd(dxs, jnp.where(first_here, dx, cur), mb)

        def do_noop(stash, gin, gacc, lacc, dxs, loss_acc):
            return stash, gin, gacc, lacc, dxs, loss_acc, zero_act, zero_act

        def do_fwd(stash, gin, gacc, lacc, dxs, loss_acc):
            inp = jnp.where(first_here, xin.astype(act_dtype), stashed)
            stash = _upd(stash, inp, sl)      # stage-0 path stores x[mb]
            out = stage_fn(pc, inp)
            return (stash, gin, gacc, lacc, dxs, loss_acc,
                    out.astype(act_dtype), zero_act)

        def _accum(gacc, ch, dp):
            return jax.tree_util.tree_map(
                lambda acc, d: _upd(
                    acc,
                    lax.dynamic_index_in_dim(acc, ch, 0, keepdims=False)
                    + d.astype(jnp.float32), ch),
                gacc, dp)

        def do_bwd(stash, gin, gacc, lacc, dxs, loss_acc):
            out, vjp = jax.vjp(stage_fn, pc, stashed)
            g, l, lacc = _loss_grad(out, lacc)
            dp, dx = vjp(g)
            gacc = _accum(gacc, ch, dp)
            dxs = _stash_dx(dxs, dx)
            return (stash, gin, gacc, lacc, dxs, loss_acc + l, zero_act,
                    dx.astype(act_dtype))

        def do_bwdx(stash, gin, gacc, lacc, dxs, loss_acc):
            out, vjpx = jax.vjp(lambda xx: stage_fn(pc, xx), stashed)
            g, l, lacc = _loss_grad(out, lacc)
            (dx,) = vjpx(g)
            # the loss-grad case (last stage) must persist g for BWDW
            gin = _upd(gin, g, sl)
            dxs = _stash_dx(dxs, dx)
            return (stash, gin, gacc, lacc, dxs, loss_acc + l, zero_act,
                    dx.astype(act_dtype))

        def do_bwdw(stash, gin, gacc, lacc, dxs, loss_acc):
            _, vjpw = jax.vjp(lambda pp: stage_fn(pp, stashed), pc)
            (dp,) = vjpw(g_up)
            gacc = _accum(gacc, ch, dp)
            return (stash, gin, gacc, lacc, dxs, loss_acc, zero_act,
                    zero_act)

        branches = [do_noop] * 5
        branches[FWD], branches[BWD] = do_fwd, do_bwd
        branches[BWDX], branches[BWDW] = do_bwdx, do_bwdw
        stash, gin, gacc, lacc, dxs, loss_acc, fsend, bsend = lax.switch(
            k, branches, stash, gin, gacc, lacc, dxs, loss_acc)

        # route the op's outputs onto their channels: the activation and
        # the dx each go right / left / local per the schedule tables
        # (interleaved: acts always right, grads always left; ZBV: odd
        # chunks reverse, the V turn stays local).  One op per tick
        # produces at most one act and one dx, so a channel carries at
        # most one value.
        adir, gdir = asend_t[me, t], gsend_t[me, t]
        sends = [jnp.where(adir == ch, fsend, 0).astype(act_dtype)
                 + jnp.where(gdir == ch, bsend, 0).astype(act_dtype)
                 for ch in range(3)]
        # the two directional permutes are data-INDEPENDENT (and so are
        # the fwd chains of CONSECUTIVE ticks); without explicit ordering
        # edges, per-device thunk schedulers can enter collectives in
        # different orders and deadlock the rendezvous (observed on
        # XLA:CPU with auto batch axes alongside manual pp).  Two
        # barriers pin the global order right(t) -> left(t) -> right(t+1):
        # the first sequences the pair inside the tick, the second makes
        # EVERY carry output (hence all of tick t+1) depend on left(t).
        c0 = _compat.ppermute(sends[0], axis, perm_r)
        c0, s1 = lax.optimization_barrier((c0, sends[1]))
        c1 = _compat.ppermute(s1, axis, perm_l)
        return lax.optimization_barrier(
            (stash, gin, (c0, c1, sends[2]), gacc, lacc, dxs, loss_acc))

    init = (stash0, gin0, carries0, gacc0, lacc0, dxs0, loss0)
    _, _, _, gacc, lacc, dxs, loss_acc = lax.fori_loop(
        0, sched.ticks, tick, init)
    # only the last rank accumulated real losses; share it
    loss = _compat.psum(jnp.where(is_last, loss_acc, 0.0), axis)
    out = [loss, gacc]
    if loss_params is not None:
        # real only on the last rank (masked zeros elsewhere): share
        out.append(jax.tree_util.tree_map(
            lambda a: _compat.psum(a, axis), lacc))
    if want_x_grad:
        # real only on rank 0 (first global stage)
        out.append(_compat.psum(jnp.where(is_first, dxs, 0.0), axis))
    return tuple(out)
