"""Functional collectives — the in-program (SPMD) communication layer.

Analog of the reference's collective *kernels* used inside compiled programs
(paddle/phi/kernels/gpu/all_reduce_kernel.cu:27 reading
dev_ctx.GetCommContext(); legacy c_allreduce/c_allgather ops in
paddle/fluid/operators/collective/).  TPU-native: these are thin wrappers
over ``jax.lax`` collectives, usable inside ``shard_map`` bodies where an
axis name is bound; XLA lowers them to ICI/DCN collectives.  This is the hot
path — the eager ProcessGroup layer (collective.py) is sugar over these.

Ops accept/return raw jax arrays OR paddle_tpu Tensors (unwrapped
transparently) so the same functions serve framework internals and user
shard_map code.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax


from jax.lax import axis_size as _axis_size

def _unwrap(x):
    from ..core.tensor import Tensor
    return x._value if isinstance(x, Tensor) else x


def _rewrap(ref, val):
    from ..core.tensor import Tensor
    return Tensor(val) if isinstance(ref, Tensor) else val


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    AVG = "avg"
    PROD = "prod"


def _reduce(val, op: str, axis):
    if op == ReduceOp.SUM:
        return lax.psum(val, axis)
    if op == ReduceOp.MAX:
        return lax.pmax(val, axis)
    if op == ReduceOp.MIN:
        return lax.pmin(val, axis)
    if op == ReduceOp.AVG:
        return lax.pmean(val, axis)
    if op == ReduceOp.PROD:
        # gather-then-prod: sign- and zero-safe, unlike exp(psum(log))
        gathered = lax.all_gather(val, axis, axis=0)
        return jnp.prod(gathered, axis=0)
    raise ValueError(f"unknown reduce op {op!r}")


def all_reduce(x, op: str = ReduceOp.SUM, axis: Union[str, Sequence[str]] = "dp"):
    """AllReduce over a mesh axis (reference: ProcessGroup::AllReduce,
    process_group.h:126)."""
    return _rewrap(x, _reduce(_unwrap(x), op, axis))


def all_gather(x, axis: str = "mp", concat_dim: int = 0, tiled: bool = True):
    """AllGather along ``axis``, concatenating on ``concat_dim``
    (reference: ProcessGroup::AllGather)."""
    return _rewrap(x, lax.all_gather(_unwrap(x), axis, axis=concat_dim,
                                     tiled=tiled))


def reduce_scatter(x, op: str = ReduceOp.SUM, axis: str = "sharding",
                   scatter_dim: int = 0):
    """ReduceScatter: reduce over ``axis`` then keep this rank's slice of
    ``scatter_dim`` (reference: ProcessGroup::ReduceScatter)."""
    v = _unwrap(x)
    if op != ReduceOp.SUM:
        full = _reduce(v, op, axis)
        n = lax.psum(1, axis)
        idx = lax.axis_index(axis)
        size = full.shape[scatter_dim] // n
        return _rewrap(x, lax.dynamic_slice_in_dim(full, idx * size, size,
                                                   axis=scatter_dim))
    return _rewrap(x, lax.psum_scatter(v, axis, scatter_dimension=scatter_dim,
                                       tiled=True))


def all_to_all(x, axis: str = "sep", split_dim: int = 0, concat_dim: int = 0):
    """AllToAll: split ``split_dim`` across ranks, concat received chunks on
    ``concat_dim`` (reference: ProcessGroup::AllToAll; the MoE / Ulysses
    primitive — global_scatter/global_gather analogs build on this)."""
    return _rewrap(x, lax.all_to_all(_unwrap(x), axis, split_axis=split_dim,
                                     concat_axis=concat_dim, tiled=True))


def broadcast(x, src: int = 0, axis: str = "dp"):
    """Broadcast rank ``src``'s value along ``axis``
    (reference: ProcessGroup::Broadcast).  Implemented as masked psum —
    XLA folds this into an efficient broadcast."""
    v = _unwrap(x)
    idx = lax.axis_index(axis)
    mask = (idx == src).astype(v.dtype)
    return _rewrap(x, lax.psum(v * mask, axis))


def reduce(x, dst: int = 0, op: str = ReduceOp.SUM, axis: str = "dp"):
    """Reduce to rank ``dst``; other ranks get zeros (SPMD programs keep a
    value on every rank — reference semantics leave others undefined)."""
    v = _unwrap(x)
    red = _reduce(v, op, axis)
    idx = lax.axis_index(axis)
    return _rewrap(x, jnp.where(idx == dst, red, jnp.zeros_like(red)))


def scatter(x, src: int = 0, axis: str = "dp", dim: int = 0):
    """Scatter rank ``src``'s chunks of ``dim`` across the axis."""
    v = broadcast(x, src=src, axis=axis)
    v = _unwrap(v)
    n = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    size = v.shape[dim] // n
    return _rewrap(x, lax.dynamic_slice_in_dim(v, idx * size, size, axis=dim))


def ppermute(x, perm, axis: str = "pp"):
    """Point-to-point ring permute (reference: batched isend/irecv in
    pp_utils/p2p_communication.py:335; on TPU this is collective_permute
    over ICI)."""
    return _rewrap(x, lax.ppermute(_unwrap(x), axis, perm=perm))


def shift(x, offset: int = 1, axis: str = "pp", wrap: bool = True):
    """Send to rank+offset along ``axis`` (ring if wrap)."""
    n = _axis_size_static(axis)
    perm = [(i, (i + offset) % n) for i in range(n)] if wrap else \
        [(i, i + offset) for i in range(n) if 0 <= i + offset < n]
    return ppermute(x, perm, axis)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.psum(1, axis)


def _axis_size_static(axis: str) -> int:
    return int(_axis_size(axis))


def barrier(axis: str = "dp"):
    """No-op under SPMD: XLA programs are globally scheduled; kept for API
    parity with ProcessGroup::Barrier."""
    return None
