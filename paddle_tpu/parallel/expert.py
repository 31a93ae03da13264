"""MoE expert parallelism end-to-end (round-18 tentpole).

The reference's Fleet stack lists MoE expert parallel as a first-class
parallelism axis (PAPER.md layer map); until this round the repo's
``MoELayer`` ran dense/dropless single-device and pipelined bodies only
— no expert axis, so sparse models could not scale experts across
chips.  This module is the ``ep`` tactic done the PartIR way (PAPERS.md
2401.11202): a fourth NAMED axis over the canonical SpecLayout
vocabulary (``parallel/specs.py`` — expert-stacked leaves place their
leading [E] dim on ``ep``, shared params keep the existing
dp/sharding/tp rules), not a fourth hand-coded stack.

Three pieces:

1. **Capacity-factored token dispatch/combine as bucketed all-to-alls**
   — routing runs on each rank's local token shard (``top_k_masks``
   masks with per-(rank, expert) capacity), the static ``[E, C, d]``
   send buffer is one einsum of the dispatch mask, and the exchange is
   ONE tiled all-to-all over ``ep`` (`make_ep_all_to_all`).  The
   transport is a ``custom_vjp`` identity-of-layout: the tiled
   all-to-all block permutation is an involution (source p's block q ↔
   source q's block p), so the backward combine is EXACTLY the
   transposed dispatch — the same exchange applied to the cotangent,
   riding the same coded schedule.

2. **Quantized DCN dispatch** — when ``ep`` spans slices
   (distributed/topology.hierarchical_axis), the exchange decomposes
   into the standard hierarchical two-stage all-to-all: an intra-slice
   (ICI) stage delivering blocks to the destination's intra-slice rank,
   then an inter-slice (DCN) stage on destination-slice super-blocks.
   With a ``CollectiveCodec`` the DCN stage moves the block-scaled
   int8 payload (stochastic-rounded, EQuARX precedent — PAPERS.md
   2506.17615) under the strict placement rule of overlap.py §5:
   full precision intra-slice, tokens crossing slices are encoded
   exactly once and decoded at the receiving slice.  COMM004 prices
   the all-to-all wire bytes per ICI/DCN stage; codec=None keeps the
   schedule bit-identical to the flat all-to-all.

3. **Grad sync split expert-vs-shared via the per-leaf placement
   specs** — the region takes params AT REST, so each leaf's shard_map
   in_spec IS its sync tag: the transpose reduces a leaf's cotangent
   over exactly the axes the spec replicates it on.  Expert leaves
   (``Shard(ep)`` on [E]) receive tokens from EVERY ep rank through
   the dispatch — their grads are complete over ``ep`` and reduce over
   the true batch axes (dp/sharding) ONLY, never over ``ep``; the
   shared gate replicates everywhere and reduces over dp/sharding AND
   ep.  (The overlap engine's explicit ``make_grad_sync`` wrappers
   exist because its custom bucket gathers BYPASS the natural
   transpose; here the at-rest specs carry the contract, and
   tests/test_expert_parallel.py pins the split by parity against the
   dense global-batch gradient.)  The gate's load-balance aux loss and
   the drop counter reduce over the ep group (with the other batch
   axes) OUTSIDE the region from honestly-sharded per-rank stats, so
   every rank optimizes the GLOBAL expert balance.

The serving half (top-k expert routing in the unified ragged step,
gather-then-dequant int8 expert weights) lives in
``models/generation.py`` / ``models/llama_paged.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from . import compat as _compat
from .codec import CollectiveCodec, decode_rows, encode_rows
from .overlap import OverlapConfig
from .specs import (EXPERT_AXIS, SpecLayout, TensorSpec, expert_leaf_spec,
                    filter_divisible_spec, is_expert_leaf, layout_mesh_axes,
                    mesh_axis_sizes, spec_to_dim_axes)

__all__ = ["EXPERT_AXIS", "MoEEPConfig", "make_ep_all_to_all",
           "moe_ep_shapes", "moe_ep_spec_for", "moe_ep_layout",
           "init_moe_ep_params", "build_moe_ep_forward",
           "build_moe_ep_train_step", "build_moe_dense_train_step",
           "build_moe_ep_dropless_forward",
           "build_moe_ep_dropless_train_step"]


# ---------------------------------------------------------------------------
# config + the at-rest plan (the canonical-vocabulary side)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEEPConfig:
    """One expert-parallel MoE FFN block.

    ``capacity_factor`` sizes the per-(source rank, expert) slot count:
    ``ep_capacity(local_tokens)`` slots per expert per source shard —
    the static [E, C, d] dispatch buffer shape.  ``capacity`` overrides
    it with an explicit slot count (the parity tests pin no-drop
    capacities explicitly).  ``aux_weight`` scales the gate's
    load-balance aux loss into the training objective."""

    d_model: int
    d_hidden: int
    num_expert: int
    top_k: int = 2
    capacity_factor: float = 1.2
    capacity: Optional[int] = None
    activation: str = "gelu"
    aux_weight: float = 0.01

    def ep_capacity(self, local_tokens: int) -> int:
        if self.capacity is not None:
            return int(self.capacity)
        from ..incubate.distributed.models.moe.gate import moe_capacity

        return moe_capacity(local_tokens, self.top_k, self.num_expert,
                            self.capacity_factor)


def moe_ep_shapes(cfg: MoEEPConfig) -> Dict[str, Tuple[int, ...]]:
    """GLOBAL shapes of the EP block's leaves, keyed by suffix (the
    layout unit, mirroring ``overlap.llama_layer_shapes``)."""
    e, m, h = cfg.num_expert, cfg.d_model, cfg.d_hidden
    return {
        "gate_w": (m, e),
        "w_up": (e, m, h),
        "b_up": (e, h),
        "w_down": (e, h, m),
        "b_down": (e, m),
    }


def moe_ep_spec_for(name: str) -> P:
    """THE declared EP plan: expert-stacked leaves lead with ``ep``
    (specs.expert_leaf_spec — the single copy of the rule), shared
    leaves (the gate) replicate.  Same-name rule for the canonical
    table, the shard_map in_specs and the at-rest device_put."""
    if is_expert_leaf(name):
        return expert_leaf_spec()
    return P()


def moe_ep_layout(cfg: MoEEPConfig, mesh: Mesh,
                  dtype: str = "float32") -> SpecLayout:
    """Canonical SpecLayout table of the EP stack — what the Sharding
    Doctor's SHARD003 gate diffs against the placed arrays and the
    declared plan (``ep`` appears in ``mesh_axes``; DOCTOR.json carries
    the table).  ``PartitionSchedule.from_moe_ep`` wires this same
    shapes/spec vocabulary into the unified schedule, which is how the
    round-20 roofline enumerator emits composable ep points."""
    shapes = moe_ep_shapes(cfg)
    entries = {}
    for name, shape in shapes.items():
        spec = filter_divisible_spec(moe_ep_spec_for(name), shape, mesh)
        entries[name] = TensorSpec(
            shape=tuple(int(d) for d in shape), dtype=str(dtype),
            dim_axes=spec_to_dim_axes(spec, len(shape)))
    return SpecLayout(mesh_axes=layout_mesh_axes(mesh), entries=entries)


def init_moe_ep_params(cfg: MoEEPConfig, mesh: Optional[Mesh] = None,
                       seed: int = 0) -> Dict[str, Any]:
    """Expert-stacked params placed per the EP plan (replicated without
    a mesh — the dense reference path)."""
    rng = np.random.RandomState(seed)
    m, h, e = cfg.d_model, cfg.d_hidden, cfg.num_expert
    scale = 1.0 / (m ** 0.5)
    params = {
        "gate_w": jnp.asarray(rng.randn(m, e).astype(np.float32)),
        "w_up": jnp.asarray(rng.randn(e, m, h).astype(np.float32) * scale),
        "b_up": jnp.zeros((e, h), jnp.float32),
        "w_down": jnp.asarray(rng.randn(e, h, m).astype(np.float32)
                              * scale),
        "b_down": jnp.zeros((e, m), jnp.float32),
    }
    if mesh is None:
        return params
    return {
        k: jax.device_put(v, NamedSharding(mesh, filter_divisible_spec(
            moe_ep_spec_for(k), v.shape, mesh)))
        for k, v in params.items()}


# ---------------------------------------------------------------------------
# the token transport: tiled all-to-all over ep, hierarchical + coded
# ---------------------------------------------------------------------------


def _flat_a2a(x, axis: str, groups=None):
    return _compat.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=True, axis_index_groups=groups)


def _codec_resolve(codec: Optional[CollectiveCodec], kind: str):
    if codec is None:
        return None
    return codec.resolve(kind)


def _ep_exchange_impl(x, axis: str, hier, codec: Optional[CollectiveCodec],
                      kind: str = "grad"):
    """One tiled all-to-all over ``axis`` (leading dim = axis_size
    destination blocks), decomposed two-stage when the axis spans
    slices.  Layout-compatible with ``lax.all_to_all(tiled=True)``
    EXACTLY (the static reorders below align the stage outputs with the
    flat source-major order), so codec=None is bit-identical to the
    flat exchange.

    Stage 1 (ICI): blocks regroup by destination INTRA-slice index and
    exchange within the slice.  Stage 2 (DCN): destination-slice
    super-blocks exchange across slices — with a codec, each
    super-block is one encoded row: tokens crossing DCN move as the
    block-scaled int8 payload, encoded once, decoded at the receiving
    slice (placement rule, overlap.py §5)."""
    if hier is None:
        return _flat_a2a(x, axis)
    S, K = hier.num_slices, hier.per_slice
    N = hier.size
    if x.shape[0] % N:
        raise ValueError(
            f"ep exchange: leading dim {x.shape[0]} not divisible by the "
            f"ep axis size {N}")
    bs = x.shape[0] // N
    rest = x.shape[1:]
    blocks = x.reshape((N, bs) + rest)
    # stage-1 reorder: position j'*S + s' holds the block destined to
    # axis position ici_groups[s'][j'] — K super-blocks by destination
    # intra-slice index, each S sub-blocks by destination slice
    ord1 = np.empty(N, dtype=np.int64)
    for jp in range(K):
        for sp in range(S):
            ord1[jp * S + sp] = hier.ici_groups[sp][jp]
    b1 = blocks[ord1].reshape((N * bs,) + rest)
    r1 = _flat_a2a(b1, axis, groups=hier.ici_groups)
    # r1 block j''*S + s' = the block from intra-slice member j'' of MY
    # slice destined to (slice s', my intra-slice index); regroup into
    # destination-slice super-blocks: [K, S, ...] -> [S, K, ...]
    b2 = jnp.swapaxes(r1.reshape((K, S, bs) + rest), 0, 1)
    rp = _codec_resolve(codec, kind)
    if rp is None:
        r2 = _flat_a2a(b2.reshape((N * bs,) + rest), axis,
                       groups=hier.dcn_groups)
        r2 = r2.reshape((S, K, bs) + rest)
    else:
        r2 = _dcn_a2a_coded(b2, axis, hier, codec, rp)
    # r2 block s''*K + j'' came from source axis position
    # ici_groups[s''][j'']; un-permute to flat source-major order
    src_order = np.empty(N, dtype=np.int64)
    for sp in range(S):
        for jp in range(K):
            src_order[sp * K + jp] = hier.ici_groups[sp][jp]
    out = r2.reshape((N, bs) + rest)[np.argsort(src_order)]
    return out.reshape((N * bs,) + rest)


def _dcn_a2a_coded(b2, axis: str, hier, codec, rp):
    """The DCN stage on the packed payload: encode the S per-slice
    super-blocks as S rows, ONE int8 all_to_all over the DCN groups,
    decode the S received rows — ``_flat_a2a(..., dcn_groups)`` up to
    quantization at ~itemsize-fold fewer bytes on the DCN wire (plus
    the bf16 scale sidecar)."""
    profile, stochastic = rp
    S = hier.num_slices
    row_shape = b2.shape[1:]             # (K, bs, *rest)
    n = int(np.prod(row_shape))
    packed = encode_rows(b2.reshape(S, n).astype(jnp.float32), codec,
                         profile, stochastic=stochastic)
    ex = _compat.all_to_all(packed, axis, split_axis=0, concat_axis=0,
                            tiled=True, axis_index_groups=hier.dcn_groups)
    dec = decode_rows(ex, n, codec, profile)
    return dec.reshape((S,) + row_shape).astype(b2.dtype)


def make_ep_all_to_all(axis: Optional[str], hier=None,
                       codec: Optional[CollectiveCodec] = None,
                       kind: str = "grad") -> Callable:
    """Factory for the EP token transport: a ``custom_vjp`` whose
    forward is the (possibly two-stage, DCN-coded) tiled all-to-all and
    whose backward applies the SAME exchange to the cotangent — the
    tiled all-to-all's global block permutation is an involution, so
    the transposed dispatch IS the combine's exchange (and the
    cotangent crosses DCN through the identical coded schedule;
    ``kind="grad"`` = the stochastic int8 profile both ways, the
    EQuARX-style activation/gradient dispatch).  ``axis=None`` (ep
    degree 1) degenerates to identity."""
    if axis is None:
        return lambda x: x

    def _impl(x):
        return _ep_exchange_impl(x, axis, hier, codec, kind=kind)

    @jax.custom_vjp
    def ep_exchange(x):
        return _impl(x)

    def _ep_exchange_fwd(x):
        return _impl(x), None

    def _ep_exchange_bwd(_, g):
        return (_impl(g),)

    ep_exchange.defvjp(_ep_exchange_fwd, _ep_exchange_bwd)
    return ep_exchange


# ---------------------------------------------------------------------------
# the EP MoE forward (full-manual shard_map region)
# ---------------------------------------------------------------------------


def _top_k_masks_with_drops():
    from ..incubate.distributed.models.moe.gate import \
        top_k_masks_with_drops

    return top_k_masks_with_drops


def _activation(h, kind: str):
    if kind == "gelu":
        return jax.nn.gelu(h)
    if kind == "relu":
        return jax.nn.relu(h)
    if kind == "swiglu":
        a, b = jnp.split(h, 2, axis=-1)
        return jax.nn.silu(a) * b
    raise ValueError(f"activation {kind!r}")


def build_moe_ep_forward(cfg: MoEEPConfig, mesh: Mesh,
                         oc: Optional[OverlapConfig] = None,
                         batch_axes: Tuple[str, ...] = ("dp", "sharding",
                                                        EXPERT_AXIS),
                         local_tokens: Optional[int] = None):
    """Build the jittable EP MoE region:

        fwd(params, x2d) -> (y, aux, dropped, load)

    ``params``: the ``moe_ep_shapes`` dict at GLOBAL shapes (placed per
    the EP plan or not — the shard_map in_specs slice them).  ``x2d``:
    [G, d_model] with the token batch sharded over every batch axis
    (dp, sharding AND ep — ``ep`` is a data axis for tokens, a weight
    axis for experts).  ``aux`` is the GLOBAL load-balance loss
    (reduced over the ep group), ``dropped`` the global
    capacity-overflow count, ``load`` the global per-expert routed
    token fraction ([E], the bench trace's balance entropy input).

    ``local_tokens`` pins the per-rank shard size the capacity factor
    is computed from; default = derived at trace time from the global
    G and the batch-axis degrees."""
    EP = EXPERT_AXIS
    oc = oc if oc is not None else OverlapConfig()
    sizes = mesh_axis_sizes(mesh)
    data_axes = tuple(a for a in batch_axes
                      if sizes.get(a, 0) > 1)
    ep = int(sizes.get(EP, 1))
    ep_ax = EP if ep > 1 else None
    e = cfg.num_expert
    if e % ep:
        raise ValueError(
            f"num_expert {e} not divisible by ep degree {ep} — expert "
            f"stacks Shard(0) over ep need equal local expert counts")
    e_local = e // ep
    hier = oc.resolve_hier(mesh, ep_ax) if ep_ax is not None else None
    # quantize-across-DCN-only: no hierarchical ep axis -> codec inert
    codec = oc.codec if hier is not None else None
    exchange = make_ep_all_to_all(ep_ax, hier=hier, codec=codec)

    batch_entry = (data_axes if len(data_axes) > 1
                   else (data_axes[0] if data_axes else None))
    # the per-leaf sync tags, spec form: each leaf's in_spec declares
    # the axes it replicates on, and the shard_map transpose reduces
    # its cotangent over EXACTLY those — Shard(ep) expert leaves reduce
    # over dp/sharding only (never ep), the replicated gate over all
    in_specs = (
        {name: filter_divisible_spec(moe_ep_spec_for(name),
                                     moe_ep_shapes(cfg)[name], mesh)
         for name in moe_ep_shapes(cfg)},
        P(batch_entry, None),
    )
    # stats rows are honestly SHARDED (one [1, 2E+1] row per batch
    # shard): the aux/telemetry reductions over the ep group happen
    # OUTSIDE the region on the [num_shards, 2E+1] global, so no
    # replicated output needs a transpose convention
    out_specs = (P(batch_entry, None), P(batch_entry, None))

    def moe_ep_body(params, x2d):
        gate_w = params["gate_w"]
        w_up, b_up = params["w_up"], params["b_up"]
        w_down, b_down = params["w_down"], params["b_down"]

        g_local, m = x2d.shape
        cap = cfg.ep_capacity(local_tokens if local_tokens is not None
                              else g_local)
        logits = x2d.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        combine, dispatch, dropped = _top_k_masks_with_drops()(
            probs, cfg.top_k, cap)
        cdt = combine.astype(x2d.dtype)
        ddt = dispatch.astype(x2d.dtype)

        # ---- dispatch: [E, C, m] send buffer, one all-to-all over ep
        send = jnp.einsum("gec,gm->ecm", ddt, x2d)       # [E, C, m]
        recv = exchange(send)
        # received blocks are source-rank-major: [ep, E_local, C, m] ->
        # local experts see every source shard's slots
        buf = recv.reshape(ep, e_local, cap, m)
        buf = jnp.swapaxes(buf, 0, 1).reshape(e_local, ep * cap, m)

        # ---- local expert FFN on the gathered slots
        h = jnp.einsum("ecm,emh->ech", buf, w_up.astype(buf.dtype)) \
            + b_up.astype(buf.dtype)[:, None, :]
        h = _activation(h, cfg.activation)
        eo = jnp.einsum("ech,ehm->ecm", h, w_down.astype(h.dtype)) \
            + b_down.astype(h.dtype)[:, None, :]

        # ---- combine: transposed exchange back to the source shards
        back = jnp.swapaxes(eo.reshape(e_local, ep, cap, m), 0, 1)
        out = exchange(back.reshape(e, cap, m))
        y = jnp.einsum("gec,ecm->gm", cdt, out)

        # ---- per-shard gate stats: mean prob + top1 fraction per
        # expert, and the local overflow count, as ONE sharded row
        top1 = jnp.argmax(probs, axis=-1)
        frac = jax.nn.one_hot(top1, e, dtype=jnp.float32).mean(axis=0)
        me = probs.mean(axis=0)
        stats = jnp.concatenate(
            [me, lax.stop_gradient(frac),
             lax.stop_gradient(dropped).astype(jnp.float32)[None]])
        return y, stats[None, :]

    fwd = shard_map(moe_ep_body, mesh=mesh,
                    axis_names=set(mesh.axis_names),
                    in_specs=in_specs, out_specs=out_specs,
                    check_vma=False)

    # NOTE the name: the shard_map TRANSPOSE re-binds backward
    # collectives with the provenance of the region call site — this
    # wrapper must be in overlap.OVERLAP_REGION_FUNCS for COMM002 to
    # attribute them to the engine (same gotcha as overlap_stack_entry).
    def moe_ep_entry(params, x2d):
        y, stats = fwd(params, x2d)
        me = stats[:, :e].mean(axis=0)          # global mean prob  [E]
        load = lax.stop_gradient(
            stats[:, e:2 * e]).mean(axis=0)     # global top1 frac  [E]
        aux = e * jnp.sum(load * me)            # GShard eq.(4), global
        dropped = lax.stop_gradient(stats[:, 2 * e]).sum()
        return y, aux, dropped, load

    moe_ep_entry.hier = hier
    moe_ep_entry.codec = codec
    moe_ep_entry.ep = ep
    moe_ep_entry.e_local = e_local
    return moe_ep_entry


# ---------------------------------------------------------------------------
# the DROPLESS EP forward: sorted ragged dispatch + grouped matmul
# ---------------------------------------------------------------------------


def build_moe_ep_dropless_forward(cfg: MoEEPConfig, mesh: Mesh,
                                  oc: Optional[OverlapConfig] = None,
                                  batch_axes: Tuple[str, ...] = (
                                      "dp", "sharding", EXPERT_AXIS),
                                  block_rows: int = 8):
    """The dropless EP MoE region (round-20 tentpole; MegaBlocks'
    dropless formulation on the repo's ragged-kernel idiom):

        fwd(params, x2d) -> (y, aux, dropped, load)

    Same signature, plan and stats contract as ``build_moe_ep_forward``
    but NO ``[E, C, d]`` capacity buffer exists anywhere — ``dropped``
    is structurally zero and no capacity-factor sweep is needed.  Per
    rank:

    1. **sorted ragged dispatch** — the top-k (expert, weight) pairs
       come straight from ``lax.top_k`` (selection and raw-prob weights
       identical to the capacity gate's iterative argmax), token copies
       are argsorted by destination expert, and per-(rank, expert)
       segment counts are exchanged FIRST through the two-stage
       hierarchical all-to-all (codec=None — counts are int32 control
       plane, bit-exactness mandatory).  The payload then moves as a
       variable-split all-to-all emulated over the SAME coded exchange:
       each destination rank owns a static window of ``T = g_local *
       top_k`` rows (the dropless worst case) with only the first
       ``counts`` rows live, so tokens crossing DCN still ride the
       block-scaled stochastic-int8 stage (strict
       quantize-across-DCN-only) and the ``custom_vjp`` involution
       still makes backward combine the transposed dispatch.
    2. **grouped matmul expert FFN** — received copies compact into
       block-aligned ragged segments (one per local expert, lengths
       from the counts exchange) and ``ops/pallas/grouped_matmul``
       applies each expert's ``[in, out]`` slice to its row window in
       one launch; alignment-slack rows stay zero per the kernel
       contract.
    3. **combine** — the transposed gather back through the same coded
       exchange, then a weighted scatter-add into token order (for
       top_k<=2 bit-equal to the capacity einsum's expert-ascending
       summation by fp commutativity).

    ``block_rows`` is the kernel's row-block size (segment alignment
    quantum); tests run 8 to exercise multi-block segments at toy
    sizes."""
    EP = EXPERT_AXIS
    oc = oc if oc is not None else OverlapConfig()
    sizes = mesh_axis_sizes(mesh)
    data_axes = tuple(a for a in batch_axes if sizes.get(a, 0) > 1)
    ep = int(sizes.get(EP, 1))
    ep_ax = EP if ep > 1 else None
    e = cfg.num_expert
    if e % ep:
        raise ValueError(
            f"num_expert {e} not divisible by ep degree {ep} — expert "
            f"stacks Shard(0) over ep need equal local expert counts")
    e_local = e // ep
    hier = oc.resolve_hier(mesh, ep_ax) if ep_ax is not None else None
    # quantize-across-DCN-only: no hierarchical ep axis -> codec inert
    codec = oc.codec if hier is not None else None
    exchange = make_ep_all_to_all(ep_ax, hier=hier, codec=codec)
    # the control-plane exchange: int32 segment counts, never quantized
    exchange_counts = make_ep_all_to_all(ep_ax, hier=hier, codec=None)
    bm = int(block_rows)

    from ..ops.pallas.grouped_matmul import (align_rows, grouped_matmul,
                                             segment_starts)

    batch_entry = (data_axes if len(data_axes) > 1
                   else (data_axes[0] if data_axes else None))
    in_specs = (
        {name: filter_divisible_spec(moe_ep_spec_for(name),
                                     moe_ep_shapes(cfg)[name], mesh)
         for name in moe_ep_shapes(cfg)},
        P(batch_entry, None),
    )
    out_specs = (P(batch_entry, None), P(batch_entry, None))

    def moe_ep_dropless_body(params, x2d):
        gate_w = params["gate_w"]
        w_up, b_up = params["w_up"], params["b_up"]
        w_down, b_down = params["w_down"], params["b_down"]

        g_local, m = x2d.shape
        logits = x2d.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        # lax.top_k == the capacity gate's iterative argmax (ties to the
        # lowest index) with the same RAW-prob combine weights
        top_p, top_ids = lax.top_k(probs, cfg.top_k)

        T = g_local * cfg.top_k              # copies = dropless worst case
        W = T                                # per-destination row window
        flat_ids = top_ids.reshape(-1).astype(jnp.int32)
        order = jnp.argsort(flat_ids)        # stable: ascending expert id
        token_of = order // cfg.top_k
        sorted_ids = flat_ids[order]
        wsorted = top_p.reshape(-1)[order]

        # ---- counts first: per-(source rank, local expert) segment
        # lengths cross the wire before any payload — row p of
        # counts_from is what source rank p routed to MY local experts
        counts = jnp.bincount(flat_ids, length=e).astype(jnp.int32)
        counts_from = exchange_counts(
            counts.reshape(ep, e_local)).reshape(ep, e_local)

        # ---- dispatch: destination-windowed scatter, one coded a2a.
        # copies are expert-sorted, hence destination-rank-sorted: rank
        # r's copies occupy [rank_starts[r], rank_starts[r]+rank_counts
        # [r]) and land at the head of r's window; tail rows stay zero
        rank_of = sorted_ids // e_local
        rank_counts = counts.reshape(ep, e_local).sum(axis=1)
        rank_starts = jnp.cumsum(rank_counts) - rank_counts
        pos = jnp.arange(T, dtype=jnp.int32) - rank_starts[rank_of]
        send = jnp.zeros((ep * W, m), x2d.dtype).at[
            rank_of * W + pos].set(x2d[token_of])
        recv = exchange(send)                # window p = rows FROM rank p

        # ---- compact the windowed rows into block-aligned ragged
        # segments (one per local expert): row q of window p belongs to
        # local expert l = searchsorted(cumsum(counts_from[p]), q) and
        # lands at segment_start[l] + (rows from earlier ranks for l) +
        # (its index within the (p, l) run)
        cum_in = jnp.cumsum(counts_from, axis=1)          # incl, within row
        off_in = cum_in - counts_from                     # excl, within row
        col_ex = jnp.cumsum(counts_from, axis=0) - counts_from
        tot_l = counts_from.sum(axis=0)                   # [e_local] seg lens
        seg_st = segment_starts(tot_l, bm)
        rows_used = jnp.sum(align_rows(tot_l, bm))
        # static padded row count: every segment's alignment slack
        rpad = int(align_rows(ep * W, bm) + e_local * bm)
        q = jnp.arange(W, dtype=jnp.int32)
        l_pq = jax.vmap(
            lambda c: jnp.searchsorted(c, q, side="right"))(cum_in)
        l_c = jnp.minimum(l_pq, e_local - 1)              # [ep, W]
        valid = q[None, :] < cum_in[:, -1:]               # [ep, W]
        p_idx = jnp.arange(ep, dtype=jnp.int32)[:, None]
        dest = (seg_st[l_c] + col_ex[p_idx, l_c]
                + (q[None, :] - off_in[p_idx, l_c]))      # [ep, W]
        destf = jnp.where(valid, dest, rpad).reshape(-1)
        xr = jnp.zeros((rpad, m), x2d.dtype).at[destf].set(
            recv, mode="drop")

        # ---- grouped-matmul expert FFN over the ragged segments.
        # rexp maps padded row -> owning local expert (bias gather);
        # rows past the last segment are masked (kernel output there is
        # unspecified), which also zeroes their backward flow
        blk_cum = jnp.cumsum(align_rows(tot_l, bm))
        rexp = jnp.minimum(
            jnp.searchsorted(blk_cum, jnp.arange(rpad), side="right"),
            e_local - 1)
        row_valid = (jnp.arange(rpad) < rows_used)[:, None]
        wids = jnp.arange(e_local, dtype=jnp.int32)
        h = grouped_matmul(xr, w_up.astype(x2d.dtype), seg_st, tot_l,
                           wids, block_rows=bm)
        h = jnp.where(row_valid, h + b_up.astype(h.dtype)[rexp], 0.0)
        h = _activation(h, cfg.activation)
        eo = grouped_matmul(h, w_down.astype(h.dtype), seg_st, tot_l,
                            wids, block_rows=bm)

        # ---- combine: gather each window row's expert output (+ its
        # expert bias) back into the windowed layout, transposed
        # exchange, then the weighted scatter into token order
        dest_cl = jnp.minimum(dest, rpad - 1).reshape(-1)
        l_flat = l_c.reshape(-1)
        back = jnp.where(valid.reshape(-1)[:, None],
                         eo[dest_cl] + b_down.astype(eo.dtype)[l_flat],
                         0.0)
        recv2 = exchange(back.astype(x2d.dtype))
        ys = recv2[rank_of * W + pos]
        y = jnp.zeros((g_local, m), x2d.dtype).at[token_of].add(
            ys * wsorted.astype(x2d.dtype)[:, None])

        # ---- stats row: same contract as the capacity body; dropped
        # is STRUCTURALLY zero — that is the point
        top1 = jnp.argmax(probs, axis=-1)
        frac = jax.nn.one_hot(top1, e, dtype=jnp.float32).mean(axis=0)
        me = probs.mean(axis=0)
        stats = jnp.concatenate(
            [me, lax.stop_gradient(frac), jnp.zeros((1,), jnp.float32)])
        return y, stats[None, :]

    fwd = shard_map(moe_ep_dropless_body, mesh=mesh,
                    axis_names=set(mesh.axis_names),
                    in_specs=in_specs, out_specs=out_specs,
                    check_vma=False)

    # NOTE the name: the shard_map TRANSPOSE re-binds backward
    # collectives with the provenance of the region call site — this
    # wrapper must be in overlap.OVERLAP_REGION_FUNCS for COMM002 to
    # attribute them to the engine (same gotcha as moe_ep_entry).
    def moe_ep_dropless_entry(params, x2d):
        y, stats = fwd(params, x2d)
        me = stats[:, :e].mean(axis=0)
        load = lax.stop_gradient(stats[:, e:2 * e]).mean(axis=0)
        aux = e * jnp.sum(load * me)
        dropped = lax.stop_gradient(stats[:, 2 * e]).sum()
        return y, aux, dropped, load

    moe_ep_dropless_entry.hier = hier
    moe_ep_dropless_entry.codec = codec
    moe_ep_dropless_entry.ep = ep
    moe_ep_dropless_entry.e_local = e_local
    moe_ep_dropless_entry.block_rows = bm
    return moe_ep_dropless_entry


def build_moe_ep_dropless_train_step(cfg: MoEEPConfig, mesh: Mesh,
                                     oc: Optional[OverlapConfig] = None,
                                     batch_axes: Tuple[str, ...] = (
                                         "dp", "sharding", EXPERT_AXIS),
                                     lr: float = 1e-2,
                                     block_rows: int = 8):
    """Jitted donated DROPLESS EP train step — the same residual MSE +
    aux objective as ``build_moe_ep_train_step`` (1:1 loss comparisons,
    ``dropped`` always 0), over the sorted-ragged-dispatch forward."""
    fwd = build_moe_ep_dropless_forward(cfg, mesh, oc=oc,
                                        batch_axes=batch_axes,
                                        block_rows=block_rows)
    sizes = mesh_axis_sizes(mesh)
    data_axes = tuple(a for a in batch_axes if sizes.get(a, 0) > 1)
    batch_entry = (data_axes if len(data_axes) > 1
                   else (data_axes[0] if data_axes else None))
    data_sharding = NamedSharding(mesh, P(batch_entry, None))

    def loss_fn(params, x2d, tgt):
        y, aux, dropped, load = fwd(params, x2d)
        g = x2d.shape[0]
        total, aux_term = _moe_loss(y, x2d, tgt, aux, cfg.aux_weight)
        return total / g + aux_term, (aux, dropped, load)

    def step(params, x2d, tgt):
        x2d = jax.lax.with_sharding_constraint(x2d, data_sharding)
        tgt = jax.lax.with_sharding_constraint(tgt, data_sharding)
        (loss, (aux, dropped, load)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x2d, tgt)
        new_params = {k: v - lr * grads[k].astype(v.dtype)
                      for k, v in params.items()}
        return loss, aux, dropped, load, new_params

    return jax.jit(step, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# train steps (EP and the dense single-device reference)
# ---------------------------------------------------------------------------


def _moe_loss(y, x2d, tgt, aux, aux_weight: float, shards: int = 1):
    """MSE-against-target objective shared by the EP step and the dense
    reference.  The token sum is taken per batch shard and the partials
    added in shard order (``shards`` > 1 on the dense path mimics the
    EP psum's partial-sum structure, keeping the two losses bit-
    comparable when nothing drops)."""
    se = jnp.sum(jnp.square((x2d + y).astype(jnp.float32) - tgt), axis=-1)
    if shards > 1:
        partial = se.reshape(shards, -1).sum(axis=1)
        total = jnp.sum(partial)
    else:
        total = jnp.sum(se)
    return total, aux_weight * aux


def build_moe_ep_train_step(cfg: MoEEPConfig, mesh: Mesh,
                            oc: Optional[OverlapConfig] = None,
                            batch_axes: Tuple[str, ...] = ("dp", "sharding",
                                                           EXPERT_AXIS),
                            lr: float = 1e-2,
                            local_tokens: Optional[int] = None):
    """Jitted donated EP train step:

        step(params, x2d, tgt) -> (loss, aux, dropped, load, new_params)

    Residual MoE block (``y = x + moe(x)``) against an MSE target plus
    the aux-weighted load-balance loss, SGD update inline.  The loss is
    the GLOBAL mean over tokens (per-shard sums psum'd over the batch
    axes, divided by the global count) so it compares 1:1 against
    ``build_moe_dense_train_step`` on identical data."""
    fwd = build_moe_ep_forward(cfg, mesh, oc=oc, batch_axes=batch_axes,
                               local_tokens=local_tokens)
    sizes = mesh_axis_sizes(mesh)
    data_axes = tuple(a for a in batch_axes if sizes.get(a, 0) > 1)
    batch_entry = (data_axes if len(data_axes) > 1
                   else (data_axes[0] if data_axes else None))
    data_sharding = NamedSharding(mesh, P(batch_entry, None))

    def loss_fn(params, x2d, tgt):
        y, aux, dropped, load = fwd(params, x2d)
        g = x2d.shape[0]
        total, aux_term = _moe_loss(y, x2d, tgt, aux, cfg.aux_weight)
        return total / g + aux_term, (aux, dropped, load)

    def step(params, x2d, tgt):
        x2d = jax.lax.with_sharding_constraint(x2d, data_sharding)
        tgt = jax.lax.with_sharding_constraint(tgt, data_sharding)
        (loss, (aux, dropped, load)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x2d, tgt)
        new_params = {k: v - lr * grads[k].astype(v.dtype)
                      for k, v in params.items()}
        return loss, aux, dropped, load, new_params

    return jax.jit(step, donate_argnums=(0,))


def build_moe_dense_train_step(cfg: MoEEPConfig, lr: float = 1e-2,
                               capacity: Optional[int] = None,
                               shards: int = 1):
    """The dense single-device reference: the SAME residual objective
    over the existing ``_moe_forward_op`` (the MoELayer kernel) with a
    pinned global capacity.  ``shards`` structures the token-sum
    reduction like the EP step's per-shard psum (bit-comparability on
    no-drop routing); capacity defaults to "everything fits"."""
    from ..incubate.distributed.models.moe.gate import \
        load_balance_aux_loss
    from ..incubate.distributed.models.moe.moe_layer import _moe_forward_op

    def loss_fn(params, x2d, tgt):
        cap = capacity if capacity is not None else x2d.shape[0]
        y, aux, dropped = _moe_forward_op.raw_fn(
            x2d, params["gate_w"], params["w_up"], params["b_up"],
            params["w_down"], params["b_down"], topk=cfg.top_k,
            capacity=cap, aux_fn=load_balance_aux_loss,
            activation=cfg.activation)
        total, aux_term = _moe_loss(y, x2d, tgt, aux, cfg.aux_weight,
                                    shards=shards)
        return total / x2d.shape[0] + aux_term, (aux, dropped)

    def step(params, x2d, tgt):
        (loss, (aux, dropped)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x2d, tgt)
        new_params = {k: v - lr * grads[k].astype(v.dtype)
                      for k, v in params.items()}
        return loss, aux, dropped, new_params

    return jax.jit(step, donate_argnums=(0,))
