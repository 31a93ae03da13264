"""DTensor API — shard_tensor / reshard / shard_layer / shard_optimizer.

Analog of the reference's dygraph auto-parallel API
(python/paddle/distributed/auto_parallel/api.py: shard_tensor:181,
reshard:703, shard_layer:804, shard_optimizer:1512 with
ShardingStage1/2/3:1273-:1420, dtensor_from_local:617,
unshard_dtensor:2671, shard_dataloader:3016).

TPU-native design — where the reference needs ~60 kLoC (DistTensor C++ core,
reshard engine with 13 placement-pair functions, 101 SPMD rule files, a
completion pass), we lower to GSPMD:

- a "DistTensor" is an ordinary Tensor whose jax.Array carries a
  NamedSharding; every eager op and every jit'ed program propagates
  shardings through XLA's sharding propagation (the completion pass),
- reshard = jax.device_put to the new NamedSharding — XLA emits the
  collective (the reshard engine: s_to_r = all_gather, r_to_s = slice,
  s_to_s = all_to_all/collective_permute ...); Partial→Replicate is the one
  case XLA cannot see from layout alone, handled here with a psum,
- per-op SPMD rules are only needed where propagation is suboptimal; those
  live as sharding_constraints inside the ops that need them.

The ``Partial`` placement is tracked as Tensor metadata (``_partial_axes``)
because a jax.Array cannot represent pending reductions at rest.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...core.tensor import Tensor
from ..placements import (Partial, Placement, Replicate, Shard,
                          placements_to_spec, spec_to_placements)
from ..process_mesh import ProcessMesh, get_mesh


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _as_jax_mesh(mesh: Union[ProcessMesh, Mesh]) -> Mesh:
    return mesh.get_jax_mesh() if isinstance(mesh, ProcessMesh) else mesh


def _dim_names(mesh: Union[ProcessMesh, Mesh]) -> List[str]:
    if isinstance(mesh, ProcessMesh):
        return mesh.dim_names
    return list(mesh.axis_names)


def _sharding_for(mesh, placements, ndim):
    spec, partial_axes = placements_to_spec(placements, _dim_names(mesh), ndim)
    return NamedSharding(_as_jax_mesh(mesh), spec), partial_axes


from ...ops.registry import register as _register_op


@_register_op("sharding_constraint")
def _sharding_constraint_op(x, sharding=None):
    if sharding is None:
        return x  # no-constraint: identity (no mesh context required)
    return jax.lax.with_sharding_constraint(x, sharding)


def sharding_constraint(t: Tensor, mesh, placements: Sequence[Placement]) -> Tensor:
    """Annotate an activation's sharding (tape-recorded, so the constraint
    also pins the backward layout).  The GSPMD analog of the reference's
    per-op SPMD rules (phi/infermeta/spmd_rules/) — applied only where
    propagation needs a hint."""
    sharding, _ = _sharding_for(mesh, placements, t.ndim)
    return _sharding_constraint_op(t, sharding=sharding)


def is_dist(t: Tensor) -> bool:
    """True if the tensor carries a non-trivial NamedSharding."""
    v = t._value if isinstance(t, Tensor) else t
    s = getattr(v, "sharding", None)
    return isinstance(s, NamedSharding)


def get_placements(t: Tensor) -> Optional[List[Placement]]:
    """Recover the placement list from a DTensor's sharding
    (reference: Tensor.placements property on DistTensor)."""
    v = t._value if isinstance(t, Tensor) else t
    s = getattr(v, "sharding", None)
    if not isinstance(s, NamedSharding):
        return None
    partial = getattr(t, "_partial_axes", ()) if isinstance(t, Tensor) else ()
    return spec_to_placements(s.spec, list(s.mesh.axis_names), v.ndim, partial)


def get_process_mesh(t: Tensor) -> Optional[ProcessMesh]:
    v = t._value if isinstance(t, Tensor) else t
    s = getattr(v, "sharding", None)
    if not isinstance(s, NamedSharding):
        return None
    m = s.mesh
    dev_to_rank = {d: i for i, d in enumerate(jax.devices())}
    ids = np.vectorize(lambda d: dev_to_rank[d])(np.asarray(m.devices))
    return ProcessMesh(ids, list(m.axis_names))


# --------------------------------------------------------------------------
# shard_tensor / reshard
# --------------------------------------------------------------------------

def shard_tensor(data, mesh: Union[ProcessMesh, Mesh],
                 placements: Sequence[Placement],
                 dtype=None, stop_gradient: Optional[bool] = None) -> Tensor:
    """Create a DTensor from (global) data + mesh + placements
    (reference: auto_parallel/api.py:181).

    The data is interpreted as the GLOBAL logical tensor; each device ends
    up holding its shard per the placements.  Partial placements in
    ``placements`` are rejected here (a fresh tensor has nothing pending) —
    they arise only from ops and reshard.
    """
    t = data if isinstance(data, Tensor) else Tensor(jnp.asarray(data))
    if dtype is not None:
        t = t.astype(dtype)
    if any(p.is_partial() for p in placements if p is not None):
        raise ValueError("shard_tensor cannot create Partial tensors")
    sharding, _ = _sharding_for(mesh, placements, t.ndim)
    val = jax.device_put(t._value, sharding)
    out = Tensor(val, stop_gradient=t.stop_gradient if stop_gradient is None
                 else stop_gradient, name=t.name)
    return out


def resolve_partial(val, partial_axes, default_mesh=None, op: Optional[str] = None):
    """Materialise pending reductions: reduce over each partial mesh axis via
    a tiny shard_map program (XLA lowers to all_reduce over ICI).  Shared by
    reshard and the eager collective layer.  ``op`` overrides the recorded
    reduce_type (used by collective.all_reduce)."""
    if not partial_axes:
        return val
    src_sharding = getattr(val, "sharding", None)
    spec = src_sharding.spec if isinstance(src_sharding, NamedSharding) \
        else PartitionSpec()
    m = src_sharding.mesh if isinstance(src_sharding, NamedSharding) \
        else default_mesh
    if m is None:
        raise ValueError("resolve_partial needs a mesh for an unsharded value")

    def body(x):
        from .. import functional as F
        for ax, reduce_type in partial_axes:
            x = F._reduce(x, op or reduce_type, ax)
        return x

    from jax import shard_map as _shard_map

    return jax.jit(_shard_map(body, mesh=m, in_specs=(spec,),
                              out_specs=spec))(val)


def reshard(t: Tensor, mesh: Union[ProcessMesh, Mesh],
            placements: Sequence[Placement]) -> Tensor:
    """Convert a DTensor to new placements (reference: api.py:703 → C++
    reshard engine, phi/core/distributed/auto_parallel/reshard/).

    All layout-only conversions (s→r all_gather, r→s slice, s→s all_to_all)
    are one ``jax.device_put``.  Pending-Partial resolution is an explicit
    psum over the partial mesh axes, then a device_put.
    """
    t = t if isinstance(t, Tensor) else Tensor(jnp.asarray(t))
    val = t._value
    partial_axes = tuple(getattr(t, "_partial_axes", ()))
    tgt_is_partial = [p for p in placements if p is not None and p.is_partial()]
    if tgt_is_partial:
        raise NotImplementedError(
            "reshard to Partial is not supported (the reference uses it only "
            "inside generated dist APIs)")
    val = resolve_partial(val, partial_axes, default_mesh=_as_jax_mesh(mesh))
    sharding, _ = _sharding_for(mesh, placements, val.ndim)
    out_val = jax.device_put(val, sharding)
    out = Tensor(out_val, stop_gradient=t.stop_gradient, name=t.name)
    return out


def mark_partial(t: Tensor, axes: Sequence[str], reduce_type: str = "sum") -> Tensor:
    """Tag a tensor as holding per-device partials over mesh ``axes`` —
    produced by ops like row-parallel matmul; resolved by reshard."""
    t._partial_axes = tuple((a, reduce_type) for a in axes)
    return t


def dtensor_from_local(local: Tensor, mesh: Union[ProcessMesh, Mesh],
                       placements: Sequence[Placement]) -> Tensor:
    """Assemble a DTensor from per-device local shards
    (reference: api.py:617).  Single-controller form: ``local`` is this
    controller's full set of shards laid out contiguously along each
    sharded dim; we install the sharding without moving data when possible.
    """
    t = local if isinstance(local, Tensor) else Tensor(jnp.asarray(local))
    sharding, _ = _sharding_for(mesh, placements, t.ndim)
    val = jax.make_array_from_process_local_data(sharding, np.asarray(t._value)) \
        if jax.process_count() > 1 else jax.device_put(t._value, sharding)
    return Tensor(val, stop_gradient=t.stop_gradient)


def dtensor_to_local(t: Tensor, mesh=None, placements=None) -> Tensor:
    """The local shard view (reference: api.py dtensor_to_local).  Under a
    single controller, returns the addressable shard of device 0 when
    sharded, else the tensor itself."""
    v = t._value
    if is_dist(t):
        shard = v.addressable_shards[0]
        return Tensor(shard.data, stop_gradient=t.stop_gradient)
    return t


def unshard_dtensor(t: Tensor) -> Tensor:
    """Gather a DTensor to a fully-replicated dense tensor
    (reference: api.py:2671)."""
    if not is_dist(t):
        return t
    sharding = t._value.sharding
    rep = NamedSharding(sharding.mesh, PartitionSpec())
    if getattr(t, "_partial_axes", ()):
        m = get_process_mesh(t)
        t = reshard(t, m, [Replicate()] * m.ndim)
    return Tensor(jax.device_put(t._value, rep), stop_gradient=t.stop_gradient)


# --------------------------------------------------------------------------
# shard_layer
# --------------------------------------------------------------------------

def shard_layer(layer, process_mesh: Union[ProcessMesh, Mesh],
                shard_fn: Optional[Callable] = None,
                input_fn: Optional[Callable] = None,
                output_fn: Optional[Callable] = None):
    """Shard a Layer's parameters in place (reference: api.py:804).

    ``shard_fn(name, layer, process_mesh)`` may re-place parameters itself;
    without one, every parameter is replicated over the mesh (matching the
    reference default) — FSDP/TP presets live in
    paddle_tpu.distributed.fleet.
    """
    from ...nn.layer import Layer

    assert isinstance(layer, Layer)
    for name, sub in list(layer.named_sublayers(include_self=True)):
        if shard_fn is not None:
            shard_fn(name, sub, process_mesh)
        else:
            for p in sub._parameters.values():
                if p is None:
                    continue
                # in-place re-placement keeps Parameter identity so
                # optimizers holding the object (and id-keyed state) work
                shard_parameter(p, process_mesh,
                                [Replicate()] * len(_dim_names(process_mesh)))
    if input_fn is not None or output_fn is not None:
        if input_fn is not None:
            layer.register_forward_pre_hook(
                lambda lyr, inputs: input_fn(inputs, process_mesh))
        if output_fn is not None:
            layer.register_forward_post_hook(
                lambda lyr, inputs, outputs: output_fn(outputs, process_mesh))
    return layer


def shard_parameter(p, mesh, placements):
    """Re-place one Parameter in place (keeps identity for optimizers)."""
    nd = shard_tensor(p, mesh, placements)
    p.set_value(nd._value)
    return p


# --------------------------------------------------------------------------
# shard_optimizer — ZeRO stages as placement rewrites
# --------------------------------------------------------------------------

class _ShardingStage:
    """Base: a callable deciding optimizer-state / gradient / parameter
    placements given the parameter's own placement (reference:
    api.py:1273-:1420 ShardingStage1/2/3)."""

    def __init__(self, mesh: Union[ProcessMesh, Mesh], axis: str = "dp"):
        self.mesh = mesh
        self.axis = axis

    def _shard_dim0_spec(self, p) -> List[Placement]:
        names = _dim_names(self.mesh)
        placements = [Replicate()] * len(names)
        if p.ndim >= 1 and p.shape[0] % _axis_len(self.mesh, self.axis) == 0:
            placements[names.index(self.axis)] = Shard(0)
        return placements


def _axis_len(mesh, axis):
    names = _dim_names(mesh)
    return (mesh.shape[names.index(axis)] if isinstance(mesh, ProcessMesh)
            else _as_jax_mesh(mesh).shape[axis])


class ShardingStage1(_ShardingStage):
    """ZeRO-1: shard optimizer states (moments, master weights) over the
    sharding axis; params+grads stay as placed."""

    shard_param = False
    shard_state = True


class ShardingStage2(ShardingStage1):
    """ZeRO-2: + gradients are reduce-scattered. Under jit, XLA derives the
    reduce-scatter automatically from the sharded optimizer-state layout; in
    EAGER mode stage 2 additionally installs a gradient re-placement hook
    (optimizer._grad_transform) that puts each grad in the Shard(0) layout
    before the update — the DTensor analog of the reference's grad
    reduce-scatter (group_sharded_stage2.py)."""

    shard_grad = True


class ShardingStage3(_ShardingStage):
    """ZeRO-3/FSDP: parameters themselves are sharded at rest; XLA
    all-gathers per-layer at use and reduce-scatters grads — the compiled
    equivalent of the reference's pre-hook allgather / post-hook release
    (group_sharded_stage3.py:1074,:1016)."""

    shard_param = True
    shard_state = True
    shard_grad = True


def shard_optimizer(optimizer, shard_fn: Optional[_ShardingStage] = None):
    """Wrap an optimizer so its states (and, for stage 3, the parameters)
    are sharded (reference: api.py:1512).

    The returned optimizer is the same object: we rewrite parameter
    placements now (stage 3) and install a state-placement hook the
    optimizer consults when creating accumulators.
    """
    if shard_fn is None:
        mesh = get_mesh()
        if mesh is None:
            raise RuntimeError("shard_optimizer needs a shard_fn or a global "
                               "mesh (dist.auto_parallel.set_mesh)")
        shard_fn = ShardingStage1(mesh, axis=_dim_names(mesh)[0])

    params = getattr(optimizer, "_parameter_list", None) or optimizer._parameters
    if getattr(shard_fn, "shard_param", False):
        for p in params:
            if p is None or p.ndim == 0:
                continue
            shard_parameter(p, shard_fn.mesh, shard_fn._shard_dim0_spec(p))

    if getattr(shard_fn, "shard_grad", False):
        # fail at install time on a bad axis, not silently per-grad
        _dim_names(shard_fn.mesh).index(shard_fn.axis)

        def _reshard_grad(p, g):
            placements = shard_fn._shard_dim0_spec(p)
            if not any(pl.is_shard() for pl in placements if pl is not None):
                return g  # indivisible dim 0: grad stays as placed
            # through reshard(): resolves pending-Partial grads with the
            # psum before the layout change (the one case device_put alone
            # would silently skip)
            return reshard(g if isinstance(g, Tensor) else Tensor(g),
                           shard_fn.mesh, placements)

        optimizer._grad_transform = _reshard_grad

    if getattr(shard_fn, "shard_state", False):
        inner_init = optimizer.init_param_state

        def sharded_init(value):
            st = inner_init(value)
            try:
                placements = shard_fn._shard_dim0_spec(Tensor(value))
            except Exception:
                return st
            out = {}
            for k, v in st.items():
                if getattr(v, "shape", None) == value.shape:
                    sharding, _ = _sharding_for(shard_fn.mesh, placements, v.ndim)
                    out[k] = jax.device_put(v, sharding)
                else:
                    out[k] = v
            return out

        optimizer.init_param_state = sharded_init
    return optimizer


# --------------------------------------------------------------------------
# shard_dataloader
# --------------------------------------------------------------------------

class ShardDataloader:
    """Wrap a DataLoader so each batch becomes a DTensor sharded over the
    data axes (reference: api.py:3016).

    Single-controller: by default the loader yields the GLOBAL batch and we
    shard dim 0 over ``shard_dims``.  With ``is_dataset_splitted=True`` the
    loader yields this PROCESS's local split (reference multi-host
    semantics) and batches are assembled via dtensor_from_local.
    ``input_keys`` restricts sharding to those keys of dict batches."""

    def __init__(self, dataloader, meshes, shard_dims: Union[str, Sequence[str], None] = None,
                 input_keys=None, is_dataset_splitted: bool = False):
        self._loader = dataloader
        self._mesh = meshes if not isinstance(meshes, (list, tuple)) else meshes[0]
        if shard_dims is None:
            shard_dims = _dim_names(self._mesh)[0]
        self._axes = (shard_dims,) if isinstance(shard_dims, str) else tuple(shard_dims)
        self._input_keys = set(input_keys) if input_keys else None
        self._splitted = is_dataset_splitted
        if is_dataset_splitted and jax.process_count() == 1:
            # one process = local split IS the global batch; nothing to do
            self._splitted = False

    def _placements(self, ndim) -> List[Placement]:
        names = _dim_names(self._mesh)
        placements: List[Placement] = [Replicate()] * len(names)
        for ax in self._axes:
            placements[names.index(ax)] = Shard(0)
        return placements

    def _shard(self, x):
        if isinstance(x, (Tensor, jax.Array, np.ndarray)):
            t = x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
            if self._splitted:
                return dtensor_from_local(t, self._mesh, self._placements(t.ndim))
            return shard_tensor(t, self._mesh, self._placements(t.ndim))
        return x

    def _shard_batch(self, batch):
        if isinstance(batch, dict) and self._input_keys is not None:
            return {k: (self._shard(v) if k in self._input_keys else v)
                    for k, v in batch.items()}
        return jax.tree_util.tree_map(
            self._shard, batch,
            is_leaf=lambda x: isinstance(x, (Tensor, np.ndarray)))

    def __iter__(self):
        for batch in self._loader:
            yield self._shard_batch(batch)

    def __len__(self):
        return len(self._loader)


def shard_dataloader(dataloader, meshes, shard_dims=None, is_dataset_splitted=False,
                     input_keys=None) -> ShardDataloader:
    return ShardDataloader(dataloader, meshes, shard_dims, input_keys,
                           is_dataset_splitted)


# --------------------------------------------------------------------------
# MoE sub-mesh APIs (reference: auto_parallel/api.py:439 moe_global_mesh_
# tensor, :580 moe_sub_mesh_tensors — dygraph MoE across sub-meshes, where
# experts live on slices of the global mesh along the expert mesh dim)
# --------------------------------------------------------------------------

def _sub_meshes_and_local_placements(mesh, placements, local_mesh_dim):
    """Slice the global mesh along ``local_mesh_dim``: one sub-mesh per
    index, with that mesh dim's placement removed from the local list."""
    jm = _as_jax_mesh(mesh)
    names = list(jm.axis_names)
    local_mesh_dim = local_mesh_dim % len(names)
    sub_names = tuple(n for j, n in enumerate(names) if j != local_mesh_dim)
    subs = []
    for i in range(jm.devices.shape[local_mesh_dim]):
        grid = np.take(jm.devices, i, axis=local_mesh_dim)
        subs.append(Mesh(grid.reshape([s for j, s in
                                       enumerate(jm.devices.shape)
                                       if j != local_mesh_dim] or [1]),
                         sub_names or ("_",)))
    placements = list(placements or [])
    while len(placements) < len(names):
        placements.append(Replicate())
    split_p = placements[local_mesh_dim]
    if isinstance(split_p, Partial):
        raise NotImplementedError(
            "moe_sub_mesh_tensors over a Partial mesh dim: resolve the "
            "pending sum first (reshard)")
    local_placements = [p for j, p in enumerate(placements)
                        if j != local_mesh_dim]
    return subs, local_placements, split_p, local_mesh_dim


def moe_sub_mesh_tensors(dist_tensor, global_mesh=None, local_mesh_dim=-1,
                         global_placements=None):
    """Split ``dist_tensor`` into its per-sub-mesh local parts along
    ``local_mesh_dim`` (reference auto_parallel/api.py:580): Shard over
    that mesh dim -> tensor-axis slices; Replicate -> full copies.  Each
    part is placed on its sub-mesh with the remaining placements.
    ``global_mesh``/``global_placements`` default to the dist tensor's
    own mesh/placements (reference behavior)."""
    if global_mesh is None:
        global_mesh = get_process_mesh(dist_tensor)
        if global_mesh is None:
            raise ValueError("moe_sub_mesh_tensors: dist_tensor carries no "
                             "mesh; pass global_mesh explicitly")
    if global_placements is None:
        global_placements = get_placements(dist_tensor)
    subs, local_placements, split_p, local_mesh_dim = \
        _sub_meshes_and_local_placements(global_mesh, global_placements,
                                         local_mesh_dim)
    v = dist_tensor._value if isinstance(dist_tensor, Tensor) \
        else jnp.asarray(dist_tensor)
    n = len(subs)
    outs = []
    for i, sub in enumerate(subs):
        if isinstance(split_p, Shard):
            d = split_p.get_dim()
            if v.shape[d] % n:
                raise ValueError(
                    f"moe_sub_mesh_tensors: dim {d} (size {v.shape[d]}) "
                    f"not divisible by {n} sub-meshes — slicing would "
                    "silently drop trailing entries")
            size = v.shape[d] // n
            piece = jax.lax.slice_in_dim(v, i * size, (i + 1) * size, axis=d)
        else:
            piece = v
        sharding, _ = _sharding_for(sub, local_placements, piece.ndim)
        outs.append(Tensor(jax.device_put(piece, sharding)))
    return outs


def moe_global_mesh_tensor(local_tensor_list, mesh, placements,
                           local_mesh_dim=-1):
    """Inverse of :func:`moe_sub_mesh_tensors` (reference
    auto_parallel/api.py:439): reassemble per-sub-mesh locals into one
    tensor on the global mesh — concat along the sharded tensor axis, or
    verify-and-take-first for a replicated split dim."""
    subs, _local_placements, split_p, local_mesh_dim = \
        _sub_meshes_and_local_placements(mesh, placements, local_mesh_dim)
    if len(local_tensor_list) != len(subs):
        raise ValueError(
            f"got {len(local_tensor_list)} local tensors for "
            f"{len(subs)} sub-meshes along mesh dim {local_mesh_dim}")
    # locals live on DISJOINT device sets (their sub-meshes): pull to
    # host before reassembly — this is a mesh-boundary reshard, the same
    # DCN-hop the reference's cross-mesh reshard performs
    vals = [np.asarray(t._value if isinstance(t, Tensor) else t)
            for t in local_tensor_list]
    if isinstance(split_p, Shard):
        full = jnp.asarray(np.concatenate(vals, axis=split_p.get_dim()))
    else:
        for i, vv in enumerate(vals[1:], 1):
            if not np.array_equal(vv, vals[0]):
                raise ValueError(
                    f"moe_global_mesh_tensor: replicated locals diverge "
                    f"(sub-mesh 0 vs {i}) — refusing to pick one silently")
        full = jnp.asarray(vals[0])
    return shard_tensor(Tensor(full), mesh, placements)
