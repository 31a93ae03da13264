"""Communication-overlap engine (round-9).

PRs 1-2 made compute fast; on a sharding-3 x TP mesh the step is then
bounded by EXPOSED communication — GSPMD serializes the stage-3 param
all-gathers ahead of each layer's matmuls, lumps the grad reduction
after backward, and pays DCN latency per collective on multislice
meshes.  This module writes the collective schedule explicitly
(Megatron-style bucketed overlap; Wang et al.'s collective matmul /
async collective fusion, PAPERS.md) as four composable levers:

1. **Layer-ahead ZeRO-3 gather prefetch** — params live sharded over
   ``sharding``; a full-manual shard_map region scans the decoder stack
   with a double-buffered explicit all-gather: layer N+1's gather is
   issued inside layer N's scan body, so its latency hides under layer
   N's matmuls (XLA's latency-hiding scheduler can hoist it — the
   gather has no dependency on layer N's compute).  With ``remat`` the
   gather moves inside the checkpointed body (backward RE-gathers, the
   classic ZeRO-3 trade) and an unroll-2 scan keeps the overlap window.
2. **Bucketed grad reduce-scatter** — each layer's sharded leaves are
   flattened and concatenated into size-capped BUCKETS; the gather is a
   ``custom_vjp`` whose backward issues ONE reduce-scatter per bucket,
   at the point in backward where that layer's grads complete — not one
   post-backward lump, and not a hail of per-leaf collectives.
3. **Collective matmul for TP** — the row-parallel projections
   (o_proj/down_proj) normally end in an exposed all-reduce; above a
   size threshold they instead run a ppermute-ring decomposition that
   overlaps each output chunk's MXU work with the previous partial
   sum's transfer (dispatcher shape follows flash_attention_auto).
4. **Hierarchical ICI/DCN collectives** — when ``sharding`` spans
   slices (distributed/topology.hierarchical_axis), gathers and
   reduce-scatters run two-stage: intra-slice (ICI) first, inter-slice
   (DCN) on the 1/per_slice residue — DCN bytes drop by the intra-slice
   degree versus a flat ring that crosses DCN per hop.
5. **Quantized DCN collectives** (round-15; parallel/codec.py) — with
   ``OverlapConfig.codec`` set AND a hierarchical axis resolved, the
   residue that crosses DCN moves as a block-scaled int8/fp8 payload
   (per-block bf16 absmax scales packed into the same wire buffer).
   The placement rule is strict: quantize ONLY across DCN.  Stage-1
   intra-slice collectives accumulate in full precision over ICI; the
   1/per_slice residue is encoded exactly once; the DCN exchange runs
   on the packed payload (reduce-scatter becomes encode → one int8
   all_to_all over the DCN groups → decode → fp32 sum at the receiver;
   all-gather/psum become encode → int8 all-gather → decode); nothing
   is ever re-quantized through a reduction chain.  Gradients use the
   deterministic seeded stochastic-rounding int8 profile, the ZeRO-3
   weights-gather the non-stochastic fp8 profile
   (``CollectiveCodec.grad_profile`` / ``weight_profile``).  Without a
   hierarchical axis the codec is inert — flat collectives ride ICI,
   where quantization costs accuracy for bandwidth we are not short
   of.  ``codec=None`` (the default) leaves every schedule bit-
   identical to the unquantized engine.

Every lever has a flat/GSPMD fallback (toggle via OverlapConfig) and
CPU parity coverage on 8 fake devices (tests/test_overlap.py); the
Graph Doctor's ``collective_budget`` pass (COMM001/COMM002, and
COMM004 for post-codec bytes-on-the-wire per ICI/DCN stage) audits the
resulting collective schedule per entry point.

The module is deliberately model-agnostic at the EDGES (bucketing,
gather/scatter, ring matmul take arrays + axis names); the Llama
decoder body lives here too so llama.py's overlap path and
llama_hybrid's full-manual rewrite share one expression set.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from jax.lax import axis_size
from . import compat as _compat
from .codec import CollectiveCodec, decode_rows, encode_rows


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

# below this many output elements the ring's per-chunk matmuls are too
# small to hide a ppermute hop behind (MXU underutilization dominates);
# the plain matmul + one psum wins.  Structural default, measured on the
# next TPU session (BASELINE.md round-9 carries the prediction).
COLLECTIVE_MATMUL_MIN_OUT_ELEMS = 1 << 16


@dataclasses.dataclass(frozen=True)
class OverlapConfig:
    """Per-lever switches for the overlap engine.

    ``hierarchical`` — "auto" consults distributed/topology (two-stage
    only when the sharding axis actually spans slices), "on" requires an
    explicit ``slice_map`` (the fake-2-slice test path), "off" forces
    flat collectives.

    ``codec`` — the quantized-DCN-collective codec (parallel/codec.py,
    module docstring §5).  Only active when a hierarchical axis
    resolves: the codec's whole contract is "quantize across DCN only",
    so without a DCN stage there is nothing to encode.  None (default)
    keeps every schedule bit-identical to the unquantized engine.
    """

    prefetch: bool = True
    bucket_bytes: int = 4 << 20
    collective_matmul: bool = True
    collective_matmul_min_out_elems: int = COLLECTIVE_MATMUL_MIN_OUT_ELEMS
    hierarchical: str = "auto"          # "auto" | "on" | "off"
    slice_map: Optional[Tuple[int, ...]] = None   # fake/explicit slices
    codec: Optional[CollectiveCodec] = None

    def hides_collectives(self) -> bool:
        """Whether this schedule can hide collective time behind layer
        compute — the roofline estimate's exposed-comm contract
        (round-20: exposed = max(0, comm − compute) only when the
        layer-ahead prefetch pipeline runs; prefetch=False serializes
        gather → compute, so every wire second is exposed)."""
        return bool(self.prefetch)

    def resolve_hier(self, mesh: Mesh, axis: Optional[str]):
        from ..distributed.topology import hierarchical_axis

        if self.hierarchical == "off" or axis is None:
            return None
        if self.hierarchical not in ("auto", "on"):
            raise ValueError(
                f"OverlapConfig.hierarchical={self.hierarchical!r}; "
                "expected 'auto', 'on' or 'off'")
        hier = hierarchical_axis(mesh, axis, self.slice_map)
        if self.hierarchical == "on" and hier is None:
            raise ValueError(
                "hierarchical='on' but the mesh axis does not span "
                "slices and no slice_map was given")
        return hier


# ---------------------------------------------------------------------------
# hierarchical two-stage collectives (one named axis, grouped stages)
# ---------------------------------------------------------------------------


def _hier_block_order(hier) -> np.ndarray:
    """Static block permutation aligning the two-stage chunk layout with
    the FLAT reduce-scatter layout (axis position p holds block p).

    Stage-1 (ICI) scatter hands group member j chunk j; stage-2 (DCN)
    hands member s subchunk s — so axis position ``ici_groups[s][j]``
    ends holding block ``j*S + s``.  ``order[j*S+s] = ici_groups[s][j]``
    pre-permutes the blocks so the final residue lands in flat order
    (and its argsort restores order after the mirrored all-gather)."""
    S, K = hier.num_slices, hier.per_slice
    order = np.empty(S * K, dtype=np.int64)
    for s in range(S):
        for j in range(K):
            order[j * S + s] = hier.ici_groups[s][j]
    return order


def _split_blocks(x, n):
    lead = x.shape[0]
    if lead % n:
        raise ValueError(f"leading dim {lead} not divisible by {n} "
                         f"(hierarchical block split)")
    return x.reshape((n, lead // n) + x.shape[1:])


def _codec_resolve(codec: Optional[CollectiveCodec], kind: str):
    """(profile, stochastic) when the codec quantizes ``kind``'s
    direction, else None (codec off / direction profile "none")."""
    if codec is None:
        return None
    return codec.resolve(kind)


def hier_psum_scatter(x, axis: str, hier,
                      codec: Optional[CollectiveCodec] = None,
                      kind: str = "grad"):
    """Two-stage reduce-scatter over ``axis``; result matches
    ``lax.psum_scatter(x, axis, tiled=True)`` exactly (same chunk at the
    same axis position), with the inter-slice stage running on the
    1/per_slice intra-slice residue.  With ``codec``, stage 1 still
    accumulates in full precision over ICI and the residue crosses DCN
    as the block-scaled packed payload (codec placement rule, module
    docstring §5)."""
    order = _hier_block_order(hier)
    blocks = _split_blocks(x, hier.size)[order]
    x2 = blocks.reshape((-1,) + x.shape[1:])
    y = _compat.psum_scatter(x2, axis, axis_index_groups=hier.ici_groups)
    rp = _codec_resolve(codec, kind)
    if rp is None:
        return _compat.psum_scatter(y, axis,
                                    axis_index_groups=hier.dcn_groups)
    return _dcn_psum_scatter_coded(y, axis, hier, codec, rp)


def _dcn_psum_scatter_coded(y, axis: str, hier, codec, rp):
    """The DCN reduce-scatter on the packed payload: encode the S
    per-destination residue rows, ONE int8 all_to_all over the DCN
    groups, decode the S received rows in fp32 and sum — exactly
    ``psum_scatter(y, axis_index_groups=dcn_groups)`` up to
    quantization, at ~itemsize-fold fewer bytes on the DCN wire (plus
    the bf16 scale sidecar)."""
    profile, stochastic = rp
    S = hier.num_slices
    rows = _split_blocks(y, S)                     # [S, m/S, ...]
    row_shape = rows.shape[1:]
    n = int(np.prod(row_shape))
    packed = encode_rows(rows.reshape(S, n), codec, profile,
                         stochastic=stochastic)
    ex = _compat.all_to_all(packed, axis, split_axis=0, concat_axis=0,
                            tiled=True, axis_index_groups=hier.dcn_groups)
    dec = decode_rows(ex, n, codec, profile)       # [S, n] fp32
    return dec.sum(axis=0).reshape(row_shape).astype(y.dtype)


def hier_all_gather(x, axis: str, hier,
                    codec: Optional[CollectiveCodec] = None,
                    kind: str = "weight"):
    """Two-stage all-gather, the exact inverse of hier_psum_scatter (and
    layout-compatible with flat ``lax.all_gather(..., tiled=True)``):
    inter-slice residue gather (DCN) first, then the intra-slice (ICI)
    stage, then a static block un-permute.  With ``codec`` the DCN
    stage gathers the block-scaled packed payload and decodes at the
    receiver; the ICI stage re-gathers the DECODED values at full
    precision (quantize-across-DCN-only, module docstring §5)."""
    order = _hier_block_order(hier)
    rp = _codec_resolve(codec, kind)
    if rp is None:
        y = _compat.all_gather(x, axis, axis_index_groups=hier.dcn_groups)
    else:
        y = _dcn_all_gather_coded(x, axis, hier, codec, rp)
    z = _compat.all_gather(y, axis, axis_index_groups=hier.ici_groups)
    blocks = _split_blocks(z, hier.size)[np.argsort(order)]
    return blocks.reshape((-1,) + x.shape[1:])


def _dcn_all_gather_coded(x, axis: str, hier, codec, rp):
    """DCN all-gather on the packed payload: encode the local shard as
    one row, int8 all-gather over the DCN groups, decode every received
    row — tiled-layout-compatible with the unquantized stage."""
    profile, stochastic = rp
    n = int(np.prod(x.shape))
    packed = encode_rows(x.reshape(1, n), codec, profile,
                         stochastic=stochastic)
    g = _compat.all_gather(packed, axis,
                           axis_index_groups=hier.dcn_groups)  # [S, L]
    dec = decode_rows(g, n, codec, profile)
    return dec.reshape((hier.num_slices * x.shape[0],)
                       + x.shape[1:]).astype(x.dtype)


def hier_psum(x, axis: str, hier,
              codec: Optional[CollectiveCodec] = None,
              kind: str = "grad"):
    """Two-stage all-reduce over ``axis``: fp32-accumulate psum
    intra-slice (ICI), then the per-slice residue crosses DCN as the
    packed payload (encode → int8 all-gather over the DCN groups →
    decode → sum) — every rank decodes the SAME payloads, so the result
    is identical on all ranks like a flat psum.  Falls back to the flat
    psum when no codec applies (the flat schedule is already optimal
    without the bytes trade)."""
    rp = _codec_resolve(codec, kind)
    if rp is None:
        return _compat.psum(x, axis)
    profile, stochastic = rp
    y = _compat.psum(x, axis, axis_index_groups=hier.ici_groups)
    n = int(np.prod(y.shape))
    packed = encode_rows(y.reshape(1, n), codec, profile,
                         stochastic=stochastic)
    g = _compat.all_gather(packed, axis,
                           axis_index_groups=hier.dcn_groups)  # [S, L]
    dec = decode_rows(g, n, codec, profile)
    return dec.sum(axis=0).reshape(y.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# bucketed gather / reduce-scatter (the ZeRO-3 wire format)
# ---------------------------------------------------------------------------


def make_bucket_gather(axis: Optional[str], hier=None,
                       batch_psum_axes: Tuple[str, ...] = (),
                       grad_mode: str = "scatter",
                       codec: Optional[CollectiveCodec] = None):
    """Factory for the bucket transport: a custom_vjp identity-of-layout
    whose forward ALL-GATHERS a flat local bucket over ``axis`` and
    whose backward REDUCE-SCATTERS the bucket cotangent (then psums the
    scattered residue over ``batch_psum_axes`` — dp and friends, where
    the params are replicated but the batch is sharded).

    ``grad_mode`` describes how the BATCH relates to ``axis``:
    - "scatter" — the batch rides ``axis`` too (the FSDP convention):
      per-rank cotangents are batch-partial, so backward is a true
      reduce-scatter (sums them while scattering);
    - "slice" — ``axis`` is weights-only (the batch does not shard over
      it, so every rank computed IDENTICAL cotangents): backward just
      slices the rank's own shard — a reduce-scatter here would
      overcount by the axis size, and costs wire bytes for nothing.

    The custom_vjp (rather than relying on all_gather's transpose) is
    what pins the SEGMENTATION: one collective per bucket, issued
    exactly when that bucket's backward segment completes, and routed
    hierarchically when the axis spans slices.  ``codec`` (only
    meaningful with ``hier``) quantizes the DCN stage of both
    directions: the forward weights-gather under the non-stochastic
    weight profile, the backward grad reduce-scatter under the
    stochastic grad profile."""
    if grad_mode not in ("scatter", "slice"):
        raise ValueError(f"grad_mode {grad_mode!r}")
    if axis is None:
        def passthrough(bucket_local):
            if not batch_psum_axes:
                return bucket_local
            return _grad_sync(bucket_local, batch_psum_axes)
        return passthrough

    def _fwd_impl(bucket_local):
        if hier is not None:
            return hier_all_gather(bucket_local, axis, hier,
                                   codec=codec, kind="weight")
        return _compat.all_gather(bucket_local, axis)

    @jax.custom_vjp
    def bucket_gather(bucket_local):
        return _fwd_impl(bucket_local)

    def _fwd(bucket_local):
        return _fwd_impl(bucket_local), None

    def _bwd(_, g):
        if grad_mode == "slice":
            n_local = g.shape[0] // axis_size(axis)
            r = lax.axis_index(axis)
            gs = lax.dynamic_slice_in_dim(g, r * n_local, n_local, axis=0)
        elif hier is not None:
            gs = hier_psum_scatter(g, axis, hier, codec=codec,
                                   kind="grad")
        else:
            gs = _compat.psum_scatter(g, axis)
        for a in batch_psum_axes:
            gs = _compat.psum(gs, a)
        return (gs,)

    bucket_gather.defvjp(_fwd, _bwd)
    return bucket_gather


def make_grad_sync(reduce_axes: Tuple[str, ...], hier_axis=None,
                   hier=None, codec: Optional[CollectiveCodec] = None):
    """Identity whose backward psums the cotangent over ``reduce_axes``
    — the replicated-param (norm weights) grad reduction, issued in the
    owning layer's backward segment instead of after the whole
    backward.  When ``hier_axis`` (with its ``hier`` structure and a
    ``codec``) is among the reduce axes, that axis's psum runs
    two-stage with the residue quantized across DCN (``hier_psum``);
    the codec-off path is bit-identical to before."""
    if not reduce_axes:
        return lambda x: x
    axes = tuple(reduce_axes)
    use_codec = (hier is not None and hier_axis in axes
                 and _codec_resolve(codec, "grad") is not None)
    if not use_codec:
        return lambda x: _grad_sync(x, axes)

    @jax.custom_vjp
    def coded_sync(x):
        return x

    def _coded_sync_fwd(x):
        return x, None

    def _coded_sync_bwd(_, g):
        for a in axes:
            if a == hier_axis:
                g = hier_psum(g, a, hier, codec=codec, kind="grad")
            else:
                g = _compat.psum(g, a)
        return (g,)

    coded_sync.defvjp(_coded_sync_fwd, _coded_sync_bwd)
    return coded_sync


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_sync(x, reduce_axes):
    return x


def _grad_sync_fwd(x, reduce_axes):
    return x, None


def _grad_sync_bwd(reduce_axes, _, g):
    for a in reduce_axes:
        g = _compat.psum(g, a)
    return (g,)


_grad_sync.defvjp(_grad_sync_fwd, _grad_sync_bwd)


@dataclasses.dataclass
class _LeafPlace:
    suffix: str
    shape: Tuple[int, ...]        # GLOBAL shape
    sh_dim: Optional[int]         # dim sharded over 'sharding' (None: no)
    mp_dim: Optional[int]         # dim sharded over 'mp' (None: no)

    def local_shape(self, sh: int, mp: int) -> Tuple[int, ...]:
        s = list(self.shape)
        if self.sh_dim is not None:
            s[self.sh_dim] //= sh
        if self.mp_dim is not None:
            s[self.mp_dim] //= mp
        return tuple(s)


def plan_layer_layout(shapes: Dict[str, Tuple[int, ...]], mesh: Mesh,
                      spec_for: Callable[[str], P]) -> Dict[str, _LeafPlace]:
    """Per-suffix placement of one decoder layer's leaves on the mesh:
    which dim rides 'sharding' (ZeRO-3, gathered by the engine) and
    which rides 'mp' (TP, stays local).  Non-divisible dims fall back to
    replication per axis — the single copy of the pick rule lives in
    parallel.specs.axis_dim_picks (shared with the Sharding Doctor's
    extractor), because the manual region must KNOW the layout, not
    infer it."""
    from .specs import axis_dim_picks

    out: Dict[str, _LeafPlace] = {}
    for suffix, shape in shapes.items():
        picks = axis_dim_picks(spec_for(suffix), shape, mesh,
                               axes=("sharding", "mp"))
        out[suffix] = _LeafPlace(suffix, tuple(shape),
                                 picks["sharding"], picks["mp"])
    return out


def leaf_partition_spec(place: _LeafPlace, lead: Optional[str] = None) -> P:
    """PartitionSpec for one leaf (optionally with a leading stacked dim
    sharded over ``lead``, e.g. 'pp' for the hybrid path)."""
    ndim = len(place.shape)
    entries: List[Any] = [None] * ndim
    if place.sh_dim is not None:
        entries[place.sh_dim] = "sharding"
    if place.mp_dim is not None:
        entries[place.mp_dim] = "mp"
    if lead is not None:
        return P(lead, *entries)
    return P(None, *entries)        # leading stacked-layer dim, replicated


def chunk_leaf_spec(place: _LeafPlace) -> P:
    """[v, blk, *local] chunked-leaf spec of the schedule-explicit
    hybrid path: the chunk dim shards over pp (device-major VPP
    placement), the block dim replicates, the inner dims keep the
    leaf's own placement."""
    return P("pp", None, *tuple(leaf_partition_spec(place))[1:])


def split_by_bytes(items: Sequence[str], bytes_of, cap: int
                   ) -> List[List[str]]:
    """Greedy size-capped accumulate-and-split (the ONE bucketing rule:
    the cap splits, never reorders; an item larger than the cap gets its
    own bucket).  Shared by the per-layer bucket plan and the
    sched-path whole-tree entry gather."""
    buckets: List[List[str]] = []
    cur: List[str] = []
    cur_bytes = 0
    for it in items:
        nbytes = int(bytes_of(it))
        if cur and cur_bytes + nbytes > cap:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(it)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def plan_buckets(layout: Dict[str, _LeafPlace], order: Sequence[str],
                 sh: int, mp: int, bucket_bytes: int, itemsize: int
                 ) -> List[List[str]]:
    """Size-capped buckets over the GATHERED leaves, in traversal order
    (the cap splits; it never merges across layers — the scan is
    per-layer)."""
    gathered = [s for s in order if layout[s].sh_dim is not None]
    return split_by_bytes(
        gathered,
        lambda s: int(np.prod(layout[s].local_shape(sh, mp))) * itemsize,
        bucket_bytes)


def _pack_bucket(stacked: Dict[str, Any], bucket: Sequence[str]) -> Any:
    """[L, *local] leaves -> one [L, bucket_elems] flat array."""
    L = next(iter(stacked.values())).shape[0]
    return jnp.concatenate(
        [stacked[sfx].reshape(L, -1) for sfx in bucket], axis=1)


def _unpack_bucket_full(flat_full, bucket: Sequence[str],
                        layout: Dict[str, _LeafPlace], sh: int, mp: int
                        ) -> Dict[str, Any]:
    """Inverse of _pack_bucket AFTER the gather: ``flat_full`` is
    [sh * bucket_elems] (rank-major tiled all-gather of the per-rank flat
    concat); reassemble each leaf's FULL (sharding-gathered, still
    mp-local) array by slicing the per-rank segments and concatenating
    along the leaf's sharded dim."""
    out: Dict[str, Any] = {}
    seg = flat_full.reshape(sh, -1)
    off = 0
    for sfx in bucket:
        pl = layout[sfx]
        lshape = pl.local_shape(sh, mp)
        n = int(np.prod(lshape))
        pieces = seg[:, off:off + n].reshape((sh,) + lshape)
        out[sfx] = jnp.concatenate(
            [pieces[r] for r in range(sh)], axis=pl.sh_dim)
        off += n
    return out


def llama_layer_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    """GLOBAL shapes of one Llama decoder layer's leaves, keyed by the
    intra-layer suffix (the layout unit of the whole engine)."""
    h, nh, nkv, hd, it = (cfg.hidden_size, cfg.num_attention_heads,
                          cfg.num_key_value_heads, cfg.head_dim,
                          cfg.intermediate_size)
    return {
        "input_layernorm.weight": (h,),
        "self_attn.q_proj.weight": (h, nh * hd),
        "self_attn.k_proj.weight": (h, nkv * hd),
        "self_attn.v_proj.weight": (h, nkv * hd),
        "self_attn.o_proj.weight": (nh * hd, h),
        "post_attention_layernorm.weight": (h,),
        "mlp.gate_proj.weight": (h, it),
        "mlp.up_proj.weight": (h, it),
        "mlp.down_proj.weight": (it, h),
    }


def gather_tree_over_sharding(tree: Dict[str, Any],
                              layout: Dict[str, _LeafPlace],
                              lead_ndim: int, sh: int, mp: int,
                              axis: Optional[str], hier=None,
                              bucket_bytes: int = 4 << 20,
                              codec: Optional[CollectiveCodec] = None
                              ) -> Dict[str, Any]:
    """Gather a whole param tree's sharding-sharded leaves at once (the
    schedule-explicit pipeline path: the executor's divergent branches
    cannot host per-layer gathers, so the chunk gathers ONCE per step at
    region entry — ZeRO-3 with per-step granularity).  Leaves are
    flattened and concatenated into size-capped buckets, one all-gather
    (hierarchical when the axis spans slices) per bucket.

    ``lead_ndim`` leading dims (the [v, blk] chunk dims) ride along
    unsharded.  Non-sharded leaves pass through untouched.  Plain
    functions, no custom_vjp — callers on this path consume GRADS as
    values (the executor's channels) and slice their own shard."""
    if axis is None:
        return dict(tree)
    order = [s for s in sorted(tree) if layout[s].sh_dim is not None]
    passthrough = {s: v for s, v in tree.items()
                   if layout[s].sh_dim is None}
    out = dict(passthrough)
    itemsize = jnp.dtype(next(iter(tree.values())).dtype).itemsize
    buckets = split_by_bytes(
        order, lambda s: int(np.prod(tree[s].shape)) * itemsize,
        bucket_bytes)
    for bucket in buckets:
        flat = jnp.concatenate([tree[s].reshape(-1) for s in bucket])
        if hier is not None:
            full = hier_all_gather(flat, axis, hier, codec=codec,
                                   kind="weight")
        else:
            full = _compat.all_gather(flat, axis)
        seg = full.reshape(sh, -1)
        off = 0
        for s in bucket:
            pl = layout[s]
            lshape = tree[s].shape                     # [*lead, *local]
            n = int(np.prod(lshape))
            pieces = seg[:, off:off + n].reshape((sh,) + tuple(lshape))
            out[s] = jnp.concatenate(
                [pieces[r] for r in range(sh)],
                axis=lead_ndim + pl.sh_dim)
            off += n
    return out


def slice_tree_own_shard(tree: Dict[str, Any],
                         layout: Dict[str, _LeafPlace], lead_ndim: int,
                         sh: int, axis: Optional[str]) -> Dict[str, Any]:
    """Inverse of gather_tree_over_sharding for GRADS on the weights-only
    sharding path: every rank computed the identical full-leaf gradient
    (the batch does not ride the axis), so each keeps its own shard — a
    reduce-scatter would overcount by the axis size."""
    if axis is None:
        return dict(tree)
    r = lax.axis_index(axis)
    out = {}
    for s, v in tree.items():
        pl = layout[s]
        if pl.sh_dim is None:
            out[s] = v
            continue
        d = lead_ndim + pl.sh_dim
        n_local = v.shape[d] // sh
        out[s] = lax.dynamic_slice_in_dim(v, r * n_local, n_local, axis=d)
    return out


# ---------------------------------------------------------------------------
# collective matmul (ppermute-ring TP row-parallel projection)
# ---------------------------------------------------------------------------


def ring_collective_matmul(y, w_local, axis: str):
    """``psum_axis(y @ w_local)`` as an axis_size-step ppermute ring.

    ``w_local`` is the row shard ([k_local, n]); the output's n columns
    are cut into axis_size chunks.  Each step matmuls one chunk and
    ppermutes the accumulating partial to the next rank, so the chunk
    transfer rides under the next chunk's MXU work (Wang et al.'s
    collective matmul); a final chunk-gather (same bytes as the
    all-reduce's broadcast half) replicates the result.

    The step-t chunk index at rank r is ``(r + 1 - t) % size`` so that
    after ``size`` adds every chunk has passed every rank exactly once
    — the ring-order contract the Graph Doctor's COMM003 check pins."""
    size = axis_size(axis)
    if size == 1:
        return y @ w_local
    r = lax.axis_index(axis)
    n = w_local.shape[-1]
    if n % size:
        # no clean column split — fall back to the flat schedule
        return _compat.psum(y @ w_local, axis)
    chunk = n // size
    perm = [(i, (i + 1) % size) for i in range(size)]
    acc = None
    for t in range(size):
        c = (r + 1 - t) % size
        wc = lax.dynamic_slice_in_dim(w_local, c * chunk, chunk,
                                      axis=w_local.ndim - 1)
        part = y @ wc
        if acc is None:
            acc = part
        else:
            acc = _compat.ppermute(acc, axis, perm) + part
    # rank r now holds the completed chunk (r + 2) % size; gather and
    # statically un-permute into column order
    g = _compat.all_gather(acc, axis, axis=0, tiled=False)
    order = np.argsort([(i + 2) % size for i in range(size)])
    g = g[order]
    out = jnp.moveaxis(g, 0, -2)
    return out.reshape(y.shape[:-1] + (n,))


def tp_row_matmul(y, w_local, axis: Optional[str], oc: OverlapConfig):
    """Row-parallel TP projection with the size-threshold dispatcher
    (flash_attention_auto's shape): ring collective matmul when the
    output is big enough to hide the hops, flat matmul+psum otherwise.
    The choice is trace-time — the compiled program contains exactly one
    schedule."""
    if axis is None:
        return y @ w_local
    out_elems = int(np.prod(y.shape[:-1])) * int(w_local.shape[-1])
    if (oc.collective_matmul
            and out_elems >= oc.collective_matmul_min_out_elems):
        return ring_collective_matmul(y, w_local, axis)
    return _compat.psum(y @ w_local, axis)


# ---------------------------------------------------------------------------
# the Llama decoder layer on gathered/mp-local raw arrays
# ---------------------------------------------------------------------------


def _rope_rotate_half():
    from ..incubate.nn.fused import _rope_rotate_half as rh

    return rh


def _rms_norm_raw():
    from ..incubate.nn.fused import _fused_rms_norm_op

    return _fused_rms_norm_op.raw_fn


def decoder_layer_tp(lp: Dict[str, Any], x, cos, sin, cfg,
                     mp_axis: Optional[str], oc: OverlapConfig,
                     segment_ids=None,
                     attn_fn: Optional[Callable] = None):
    """One decoder layer, sharding-GATHERED params, mp-LOCAL TP compute.

    Expression-for-expression the math of llama_hybrid._decoder_layer
    (itself the functional twin of models/llama.py), with the TP wiring
    explicit: q/k/v/gate/up are column-parallel (local heads / local
    ffn columns, no collective), o_proj/down_proj row-parallel through
    the collective-matmul dispatcher.  ``attn_fn(q, k, v)`` overrides
    the attention entry (the hybrid path passes ulysses/ring sep
    attention); default is causal flash on the local heads.
    """
    mp = axis_size(mp_axis) if mp_axis is not None else 1
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    if nkv % mp or nh % mp:
        raise ValueError(
            f"num heads ({nh} q / {nkv} kv) not divisible by mp={mp} — "
            "the overlap engine computes attention on mp-local heads")
    nh_l, nkv_l = nh // mp, nkv // mp
    b, sl, _ = x.shape
    rms = _rms_norm_raw()
    rotate_half = _rope_rotate_half()

    h = rms(x, lp["input_layernorm.weight"], epsilon=cfg.rms_norm_eps)
    q = (h @ lp["self_attn.q_proj.weight"]).reshape(b, sl, nh_l, hd)
    k = (h @ lp["self_attn.k_proj.weight"]).reshape(b, sl, nkv_l, hd)
    v = (h @ lp["self_attn.v_proj.weight"]).reshape(b, sl, nkv_l, hd)
    cos_b = cos[None, :, None, :]
    sin_b = sin[None, :, None, :]
    q = q * cos_b + rotate_half(q) * sin_b
    k = k * cos_b + rotate_half(k) * sin_b
    if attn_fn is not None:
        attn = attn_fn(q, k, v)
    else:
        from ..ops.pallas.flash_attention import flash_attention_raw

        if segment_ids is not None:
            attn = flash_attention_raw(q, k, v, causal=True,
                                       q_segment_ids=segment_ids,
                                       kv_segment_ids=segment_ids)
        else:
            attn = flash_attention_raw(q, k, v, causal=True)
    attn = attn.astype(x.dtype).reshape(b, sl, nh_l * hd)
    # checkpoint_name tags on the residual-stream block outputs: the HBM
    # memory engine's NAMED remat policies (parallel/memory.py
    # SAVEABLE_NAMES) select/offload exactly these under the remat scan
    from .memory import tag_saveable

    attn_out = tag_saveable(
        tp_row_matmul(attn, lp["self_attn.o_proj.weight"], mp_axis, oc),
        "decoder_attn_out")
    x = x + attn_out
    h2 = rms(x, lp["post_attention_layernorm.weight"],
             epsilon=cfg.rms_norm_eps)
    gate = h2 @ lp["mlp.gate_proj.weight"]
    up = h2 @ lp["mlp.up_proj.weight"]
    mlp_out = tag_saveable(
        tp_row_matmul(jax.nn.silu(gate) * up,
                      lp["mlp.down_proj.weight"], mp_axis, oc),
        "decoder_mlp_out")
    return x + mlp_out


# ---------------------------------------------------------------------------
# the prefetch scan
# ---------------------------------------------------------------------------


def gathered_layer_scan(layer_fn, xs_buckets: List[Any], xs_sync: Any,
                        x, buckets: List[List[str]],
                        sync_suffixes: List[str],
                        layout: Dict[str, _LeafPlace], sh: int, mp: int,
                        gather_fns: List[Callable], sync_fn: Callable,
                        oc: OverlapConfig, remat: bool = False,
                        remat_policy=None):
    """Scan the decoder stack with the layer-ahead gather prefetch.

    ``xs_buckets[i]``: [L, bucket_elems_local] flat per-layer bucket
    shards; ``xs_sync``: [L, sync_elems] concat of the non-gathered
    leaves (norm weights, replication-fallback leaves, mp-only leaves).

    Two schedules:
    - ``remat=False`` (default): double-buffered carry — the scan body
      computes layer i from the CARRIED gathered buckets while issuing
      layer i+1's gathers (no data dependency between them, so the
      latency-hiding scheduler overlaps transfer with the layer's
      matmuls).  Plain scan AD saves body intermediates anyway, so the
      carry costs no extra memory versus gather-in-body here.
    - ``remat=True``: the gather moves INSIDE the jax.checkpoint'd body
      — the carry stays activations-only (remat-compatible: per-step
      residuals are just the layer-boundary activations, the same
      footprint as non-overlap per-layer remat), backward re-gathers
      each bucket (ZeRO-3's standard recompute trade), and ``unroll=2``
      keeps an issue-ahead window inside each unrolled pair.
    """

    def unpack(bucket_fulls, sync_row):
        lp: Dict[str, Any] = {}
        for bi, bucket in enumerate(buckets):
            lp.update(_unpack_bucket_full(bucket_fulls[bi], bucket,
                                          layout, sh, mp))
        off = 0
        srow = sync_fn(sync_row)
        for sfx in sync_suffixes:
            lshape = layout[sfx].local_shape(sh, mp)
            n = int(np.prod(lshape))
            lp[sfx] = srow[off:off + n].reshape(lshape)
            off += n
        return lp

    L = xs_sync.shape[0]

    if not remat and oc.prefetch:
        # double-buffered carry: layer i computes from the CARRIED
        # gathers while layer i+1's gathers issue.  Exactly L gathers
        # per bucket (layer 0's up front, layers 1..L-1 inside the
        # scan; the final layer runs OUTSIDE the scan from the last
        # carry, so no wasted wrap-around gather — whose backward would
        # also reduce-scatter a zero cotangent for nothing).
        g0 = tuple(gather_fns[bi](xs_buckets[bi][0])
                   for bi in range(len(buckets)))
        if L == 1:
            return layer_fn(unpack(g0, xs_sync[0]), x)
        nxt = tuple(xb[1:] for xb in xs_buckets)

        def step(carry, xs_row):
            xcur, gcur = carry
            next_shards, sync_row = xs_row
            y = layer_fn(unpack(gcur, sync_row), xcur)
            gnext = tuple(gather_fns[bi](next_shards[bi])
                          for bi in range(len(buckets)))
            return (y, gnext), None

        (y, glast), _ = lax.scan(step, (x, g0), (nxt, xs_sync[:L - 1]))
        return layer_fn(unpack(glast, xs_sync[L - 1]), y)

    def step(xcur, xs_row):
        # gather at the top of each step: the flat fallback
        # (prefetch=False, GSPMD-like serialization — the baseline the
        # profile leg compares to) and the remat body (the gather sits
        # INSIDE the checkpointed region: backward re-gathers, the
        # ZeRO-3 recompute trade, with unroll-2 keeping an issue-ahead
        # window)
        shards, sync_row = xs_row
        gcur = tuple(gather_fns[bi](shards[bi])
                     for bi in range(len(buckets)))
        y = layer_fn(unpack(gcur, sync_row), xcur)
        return y, None

    body = jax.checkpoint(step, policy=remat_policy) if remat else step
    y, _ = lax.scan(body, x, (tuple(xs_buckets), xs_sync),
                    unroll=2 if (remat and oc.prefetch) else 1)
    return y


# ---------------------------------------------------------------------------
# the full-manual decoder-stack region (build_train_step's overlap path)
# ---------------------------------------------------------------------------

# function names whose presence in a collective's trace-time call stack
# marks it as engine-issued — the Graph Doctor's COMM002 check treats
# collectives OUTSIDE these regions as unscheduled when an overlap
# engine is active.  Names are the engine's own entry points (deliberate:
# a generic name like "step" would whitelist unrelated collectives).
OVERLAP_REGION_FUNCS = frozenset({
    "overlap_stack_body", "overlap_stack_entry", "_fwd_impl", "_bwd",
    "_grad_sync_bwd", "ring_collective_matmul", "tp_row_matmul",
    "hier_psum_scatter", "hier_all_gather", "gathered_layer_scan",
    "gather_tree_over_sharding", "slice_tree_own_shard",
    # round-15 quantized-DCN entries (codec.py's encode/decode issue no
    # collectives themselves; the int8 exchanges live in these frames)
    "hier_psum", "_dcn_psum_scatter_coded", "_dcn_all_gather_coded",
    "_coded_sync_bwd",
    # round-18 expert-parallel entries (parallel/expert.py): the EP
    # dispatch/combine all-to-alls and their custom_vjp transposes, plus
    # the region entry whose name the shard_map transpose re-binds
    "ep_exchange", "_ep_exchange_impl", "_dcn_a2a_coded",
    "_ep_exchange_fwd", "_ep_exchange_bwd", "moe_ep_body", "moe_ep_entry",
    # round-20 dropless entries (parallel/expert.py): the sorted ragged
    # dispatch rides the SAME ep_exchange custom_vjp; these are the new
    # region body/entry frames the shard_map transpose re-binds to
    "moe_ep_dropless_body", "moe_ep_dropless_entry",
})


def stack_layout_plan(shapes: Dict[str, Tuple[int, ...]], mesh: Mesh,
                      spec_for: Callable[[str], P], oc: OverlapConfig,
                      compute_dtype=jnp.bfloat16):
    """The engine's at-rest layout decision as a pure shape-level plan:
    (layout, buckets, sync_suffixes) — the leaf placements
    (sharding/mp dim picks), the size-capped gather-bucket plan, and
    the non-gathered (grad-sync) leaves.  ``build_overlap_stack``
    consumes exactly this (single copy — no behavior change), and the
    Sharding Doctor's extractor reads the same hook to build this
    stack's canonical SpecLayout table without tracing the region."""
    layout = plan_layer_layout(shapes, mesh, spec_for)
    order = sorted(shapes)
    sh = int(mesh.shape.get("sharding", 1))
    mp = int(mesh.shape.get("mp", 1))
    itemsize = jnp.dtype(compute_dtype).itemsize
    buckets = plan_buckets(layout, order, sh, mp, oc.bucket_bytes,
                           itemsize)
    gathered = {s for b in buckets for s in b}
    sync_suffixes = [s for s in order if s not in gathered]
    return layout, buckets, sync_suffixes


def build_overlap_stack(cfg, mesh: Mesh,
                        shapes: Dict[str, Tuple[int, ...]],
                        spec_for: Callable[[str], P],
                        oc: OverlapConfig,
                        batch_axes: Tuple[str, ...] = ("dp", "sharding"),
                        remat: bool = False, remat_policy=None,
                        compute_dtype=jnp.bfloat16):
    """Build the jittable decoder-stack region:

        fwd(stacked, x, cos, sin, segment_ids=None) -> h

    ``stacked``: dict suffix -> [L, *global] (plain GSPMD-land arrays;
    the shard_map in_specs slice them to the at-rest ZeRO-3/TP layout).
    ``x``: [b, s, hidden] batch-sharded.  The region is FULL-manual
    (every mesh axis named), so no partial-manual PartitionId lowering
    is involved (the jax-0.4.x gap this round retires) — embedding, the
    final norm, LM head and the loss stay outside in plain GSPMD-land.
    """
    axis_names = tuple(mesh.axis_names)
    sh = int(mesh.shape.get("sharding", 1))
    mp = int(mesh.shape.get("mp", 1))
    sh_ax = "sharding" if sh > 1 else None
    mp_ax = "mp" if mp > 1 else None
    data_axes = tuple(a for a in batch_axes
                      if a in mesh.axis_names and mesh.shape[a] > 1)
    batch_entry = (data_axes if len(data_axes) > 1
                   else (data_axes[0] if data_axes else None))
    # params are REPLICATED over every batch axis except 'sharding'
    # (which the reduce-scatter folds in); their grads need the psum
    psum_axes = tuple(a for a in data_axes if a != "sharding")
    hier = oc.resolve_hier(mesh, sh_ax)

    layout, buckets, sync_suffixes = stack_layout_plan(
        shapes, mesh, spec_for, oc, compute_dtype)
    order = sorted(shapes)

    # the codec rides the hierarchical axis only (quantize-across-DCN
    # placement rule): no resolved hier -> no DCN stage -> codec inert
    codec = oc.codec if hier is not None else None
    gather_fns = [make_bucket_gather(sh_ax, hier, psum_axes, codec=codec)
                  for _ in buckets]
    # every batch axis (incl. sharding) reduces the replicated leaves
    sync_fn = make_grad_sync(data_axes, hier_axis=sh_ax, hier=hier,
                             codec=codec)

    in_specs = (
        {sfx: leaf_partition_spec(layout[sfx]) for sfx in order},
        P(batch_entry, None, None),
        P(None, None), P(None, None),
    )
    out_spec = P(batch_entry, None, None)

    # x is replicated over mp inside the region (batch rides dp/sharding
    # only): the column-parallel projections produce PARTIAL x-cotangents
    # per mp rank, so the embedding gradient needs the mp psum — issued
    # in x's own backward segment via the sync tag
    x_sync = make_grad_sync((mp_ax,) if mp_ax is not None else ())

    def overlap_stack_body(stacked, x, cos, sin, segment_ids=None):
        x = x_sync(x)
        xs_buckets = [_pack_bucket(stacked, b) for b in buckets]
        if sync_suffixes:
            xs_sync = _pack_bucket(stacked, sync_suffixes)
        else:
            L = next(iter(stacked.values())).shape[0]
            xs_sync = jnp.zeros((L, 0), compute_dtype)

        def layer_fn(lp, xcur):
            return decoder_layer_tp(lp, xcur, cos, sin, cfg, mp_ax, oc,
                                    segment_ids=segment_ids)

        return gathered_layer_scan(
            layer_fn, xs_buckets, xs_sync, x, buckets, sync_suffixes,
            layout, sh, mp, gather_fns, sync_fn, oc, remat=remat,
            remat_policy=remat_policy)

    fwd_nomask = shard_map(
        overlap_stack_body, mesh=mesh, axis_names=set(axis_names),
        in_specs=in_specs, out_specs=out_spec, check_vma=False)
    fwd_mask = shard_map(
        overlap_stack_body, mesh=mesh, axis_names=set(axis_names),
        in_specs=in_specs + (P(batch_entry, None),),
        out_specs=out_spec, check_vma=False)

    # NOTE the name: jax's shard_map TRANSPOSE re-binds the backward
    # collectives (the replicated-input cotangent psums) with the
    # provenance of the region CALL SITE, i.e. this function — so it
    # must be in OVERLAP_REGION_FUNCS for COMM002 to attribute them to
    # the engine.  Unique on purpose; don't rename to something generic.
    def overlap_stack_entry(stacked, x, cos, sin, segment_ids=None):
        if segment_ids is None:
            return fwd_nomask(stacked, x, cos, sin)
        return fwd_mask(stacked, x, cos, sin, segment_ids)

    overlap_stack_entry.layout = layout
    overlap_stack_entry.buckets = buckets
    overlap_stack_entry.sync_suffixes = sync_suffixes
    overlap_stack_entry.hier = hier
    return overlap_stack_entry
