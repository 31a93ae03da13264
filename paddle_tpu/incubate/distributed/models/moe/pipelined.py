"""Pipelined MoE blocks: pp x ep x mp composition in one program.

The shared harness behind the 8-device dryrun leg and
tests/test_gpt_moe.py::test_moe_pipeline_ep_mp_composition: a stack of
MoE-FFN residual blocks pipelined over ``pp`` (layer-major chunks,
pipeline_apply dataflow) with experts Shard(ep) and expert hidden dims
Shard(mp) left to GSPMD.  Reference analog: MoE transformer blocks as
PipelineLayer segments under expert parallelism
(incubate/distributed/models/moe/moe_layer.py:263 + pp_layers.py).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .gate import top_k_masks
from .moe_layer import _moe_forward_op
from .....parallel import compat as _compat
from .....parallel.pipelining import pipeline_apply

MOE_BLOCK_SPECS = {
    "gate_w": P("pp", None, None),
    "w_up": P("pp", "ep", None, "mp"),
    "b_up": P("pp", "ep", "mp"),
    "w_down": P("pp", "ep", "mp", None),
    "b_down": P("pp", "ep", None),
}


from jax.lax import axis_size as _axis_size

def init_pipelined_moe_params(mesh: Mesh, num_layers: int, num_expert: int,
                              d_model: int, d_hidden: int,
                              seed: int = 0) -> Dict[str, Any]:
    """Layer-major [L, E, ...] expert stacks placed per MOE_BLOCK_SPECS."""
    rng = np.random.RandomState(seed)
    params = {
        "gate_w": jnp.asarray(
            rng.randn(num_layers, d_model, num_expert).astype(np.float32)),
        "w_up": jnp.asarray(rng.randn(
            num_layers, num_expert, d_model, d_hidden).astype(np.float32)
            * 0.3),
        "b_up": jnp.zeros((num_layers, num_expert, d_hidden), jnp.float32),
        "w_down": jnp.asarray(rng.randn(
            num_layers, num_expert, d_hidden, d_model).astype(np.float32)
            * 0.3),
        "b_down": jnp.zeros((num_layers, num_expert, d_model), jnp.float32),
    }
    return {k: jax.device_put(v, NamedSharding(mesh, MOE_BLOCK_SPECS[k]))
            for k, v in params.items()}


def moe_block(lp: Dict[str, Any], act, topk: int = 2):
    """One residual MoE-FFN block on raw arrays (capacity = full batch,
    i.e. no dropping — the parity-friendly setting)."""
    y, _, _ = _moe_forward_op.raw_fn(
        act, lp["gate_w"], lp["w_up"], lp["b_up"], lp["w_down"],
        lp["b_down"], topk=topk, capacity=act.shape[0], aux_fn=None)
    return act + y


def pipelined_moe_forward(params: Dict[str, Any], x, mesh: Mesh,
                          topk: int = 2):
    """Run [m, mb, d_model] micro-batches through the pipelined MoE
    stack; returns [m, mb, d_model] (valid everywhere — last-stage psum
    broadcast)."""

    def stage_fn(sp, act):
        act, _ = jax.lax.scan(
            lambda h, lp: (moe_block(lp, h, topk=topk), None), act, sp)
        return act

    def body(sp, x):
        outs = pipeline_apply(stage_fn, sp, x, axis="pp",
                              squeeze_stage_dim=False)
        last = (jax.lax.axis_index("pp")
                == _axis_size("pp") - 1).astype(outs.dtype)
        return jax.lax.psum(outs * last, "pp")

    from jax import shard_map as _shard_map
    from .....parallel.specs import ambient_mesh

    # FULL-manual region (round-9): every mesh axis is named, so the
    # SPMD partitioner never sees a partial-manual shard_map.  The expert stacks keep
    # their Shard(ep)/Shard(mp) AT-REST placement; the P("pp") in_specs
    # gather them over ep/mp at the region boundary and the block
    # compute runs expert-replicated inside — the parity-friendly
    # setting this harness targets (capacity = full batch, no drops).
    with ambient_mesh(mesh, params, x):
        return jax.jit(_shard_map(
            body, mesh=mesh, axis_names=set(mesh.axis_names),
            in_specs=(P("pp"), P(None)), out_specs=P(None),
            check_vma=False))(params, x)


def moe_block_ep(lp: Dict[str, Any], act, topk: int = 2,
                 ep_axis: str = "ep"):
    """One residual MoE-FFN block with experts SHARDED over ``ep``
    inside the manual region (round-18's ep>1 variant of the pipelined
    harness): each ep rank holds E_local expert stacks, slices the
    global routing masks to its expert block, computes only its own
    experts' slots, and the residual combine psums the partial outputs
    over ``ep`` — true expert-parallel compute, vs ``moe_block``'s
    gather-at-the-boundary expert-replicated body.  Tokens here are
    replicated over ep (the pipelined harness's layout), so no token
    all-to-all is needed; the dispatch/combine all-to-all engine for
    token-sharded EP lives in parallel/expert.py."""
    e_local = lp["w_up"].shape[0]
    ep = _axis_size(ep_axis)
    e = e_local * ep
    r = jax.lax.axis_index(ep_axis)
    logits = act.astype(jnp.float32) @ lp["gate_w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    combine, dispatch = top_k_masks(probs, topk, act.shape[0])
    off = r * e_local
    cl = jax.lax.dynamic_slice_in_dim(combine, off, e_local, axis=1)
    dl = jax.lax.dynamic_slice_in_dim(dispatch, off, e_local, axis=1)
    cdt = cl.astype(act.dtype)
    ddt = dl.astype(act.dtype)
    expert_in = jnp.einsum("gec,gm->ecm", ddt, act)
    h = jnp.einsum("ecm,emh->ech", expert_in,
                   lp["w_up"].astype(act.dtype)) \
        + lp["b_up"].astype(act.dtype)[:, None, :]
    h = jax.nn.gelu(h)
    eo = jnp.einsum("ech,ehm->ecm", h, lp["w_down"].astype(act.dtype)) \
        + lp["b_down"].astype(act.dtype)[:, None, :]
    y_partial = jnp.einsum("gec,ecm->gm", cdt, eo)
    return act + _compat.psum(y_partial, ep_axis)


def pipelined_moe_forward_ep(params: Dict[str, Any], x, mesh: Mesh,
                             topk: int = 2):
    """The ep>1 variant of ``pipelined_moe_forward``: expert stacks stay
    Shard(ep) INSIDE the manual region (in_specs keep the ep entry on
    the [E] dim; only mp gathers at the boundary) and each pipeline
    stage runs ``moe_block_ep`` — pp x ep composition with ep-sharded
    compute in one program."""

    def stage_fn(sp, act):
        act, _ = jax.lax.scan(
            lambda h, lp: (moe_block_ep(lp, h, topk=topk), None), act, sp)
        return act

    def body(sp, x):
        outs = pipeline_apply(stage_fn, sp, x, axis="pp",
                              squeeze_stage_dim=False)
        last = (jax.lax.axis_index("pp")
                == _axis_size("pp") - 1).astype(outs.dtype)
        return jax.lax.psum(outs * last, "pp")

    from jax import shard_map as _shard_map
    from .....parallel.specs import ambient_mesh

    in_specs = ({
        "gate_w": P("pp", None, None),
        "w_up": P("pp", "ep", None, None),
        "b_up": P("pp", "ep", None),
        "w_down": P("pp", "ep", None, None),
        "b_down": P("pp", "ep", None),
    }, P(None))
    with ambient_mesh(mesh, params, x):
        return jax.jit(_shard_map(
            body, mesh=mesh, axis_names=set(mesh.axis_names),
            in_specs=in_specs, out_specs=P(None),
            check_vma=False))(params, x)


def sequential_moe_forward(params: Dict[str, Any], x, topk: int = 2):
    """Unsharded sequential reference for parity checks."""
    num_layers = params["gate_w"].shape[0]
    ref = x
    for i in range(num_layers):
        lp = {k: v[i] for k, v in params.items()}
        ref = jnp.stack([moe_block(lp, ref[j], topk=topk)
                         for j in range(x.shape[0])])
    return ref
