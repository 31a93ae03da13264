"""Canonical per-tensor partition specs and shared mesh/axis introspection.

Three stacks hand-encode sharding independently (the flat GSPMD
``build_train_step``, the full-manual overlap engine, the hybrid
gpipe/sched bodies), and until round-14 each also carried its OWN copy
of the placement arithmetic: the divisibility-or-replicate fallback
(``apply_llama_sharding``, ``shard_hybrid_state``), the per-axis dim
pick (``overlap.plan_layer_layout``) and the batch-axes prefix rule
(``llama_hybrid._pick_batch_axes``).  This module is the first concrete
step of the ROADMAP's unified-partitioning item (PartIR, PAPERS.md
2401.11202): one canonical per-tensor spec type (``TensorSpec`` /
``SpecLayout`` — SNIPPETS [3]'s SpecLayout shape) plus the single copy
of each placement rule, consumed by the stacks AND by the Sharding
Doctor's extractor (``paddle_tpu.analysis.sharding``), which turns each
stack's placement into one comparable table.  The future unified
schedule object derives all three stacks from this table; today the
doctor proves the hand-written stacks still agree on it.

Everything here is host-side plan math (shapes, mesh axis sizes, byte
counts) — nothing traces.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# the expert-parallel axis (round-18: MoE expert parallelism)
# ---------------------------------------------------------------------------

# canonical name of the expert-parallel mesh axis.  ``ep`` is a WEIGHT
# axis for expert-stacked leaves (their leading [E] dim shards over it)
# and a BATCH axis for everything else (tokens ride it into the
# dispatch all-to-all; shared params replicate over it and their grads
# reduce over it) — the fourth named tactic of the unified-partitioning
# vocabulary (dp / sharding / tp / ep), not a fourth hand-coded stack.
EXPERT_AXIS = "ep"

# the dropless-transport tactic NAME on the expert axis (round-20):
# schedules/Doctor tables say "ep_dropless" to mean the sorted-ragged
# dispatch + grouped-matmul engine instead of the [E, C, d] capacity
# engine.  Placement vocabulary is unchanged — expert leaves still lead
# with EXPERT_AXIS — which is why this is a tactic name, not a new axis.
EXPERT_DROPLESS_TACTIC = "ep_dropless"

# name markers of expert-stacked leaves: the MoELayer/gpt_moe stacked
# parameter names (w_up/b_up/w_down/b_down with a leading [E] dim) and
# the serving sparse-checkpoint naming (model.layers.*.mlp.experts.*).
# One predicate shared by the EP engine's plan, the gpt_moe GSPMD plan
# and the Sharding Doctor's extractor — the single copy of "what is an
# expert leaf".
_EXPERT_LEAF_MARKERS = (".experts.", "mlp.w_up", "mlp.b_up",
                        "mlp.w_down", "mlp.b_down")


def is_expert_leaf(name: str) -> bool:
    """True when ``name`` denotes an expert-stacked leaf (leading [E]
    dim placed on the ``ep`` axis)."""
    return any(m in name for m in _EXPERT_LEAF_MARKERS) \
        or name in ("w_up", "b_up", "w_down", "b_down")


def expert_leaf_spec(tail: P = P()) -> P:
    """THE expert placement rule: the leading [E] dim rides ``ep``, the
    remaining dims follow ``tail`` (the existing dp/sharding/tp rules —
    e.g. the expert hidden dim Megatron-sharded over mp)."""
    return P(EXPERT_AXIS, *tuple(tail))


# ---------------------------------------------------------------------------
# the entry-layer spec vocabulary (round-19, AST003 migration): model
# bodies reference these named schedule decisions instead of
# hand-writing PartitionSpec literals — every helper is one reviewed
# placement rule with a name, not a scattering of P(...) calls
# ---------------------------------------------------------------------------

#: the replicated placement (plan defaults, unplanned names)
REPLICATED = P()


def batch_entry(axes: Sequence[str]):
    """Axes tuple -> one PartitionSpec ENTRY (None when empty, the bare
    axis when single — the repo-wide batch-entry convention)."""
    axes = tuple(axes)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def batch_partition_spec(mesh: Mesh,
                         data_axes: Sequence[str] = ("dp", "sharding")
                         ) -> P:
    """THE [B, ...]-leading batch placement: the data axes present on
    the mesh with real degree, folded into one leading entry (single
    copy of the rule ``make_batch_shardings`` and the bert/gpt_moe
    batch pins shared by hand before round 19)."""
    axes = tuple(a for a in data_axes
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    return P(batch_entry(axes))


def lead_batch_spec(spec: P, ndim: int = 1) -> P:
    """Keep only the LEADING (batch) entry of an existing batch spec,
    replicating ``ndim - 1`` trailing dims — the loss-reduction and
    activation layout pins."""
    entries = tuple(spec)
    return P(entries[0] if entries else None, *([None] * (ndim - 1)))


def activation_spec(entry, ndim: int = 3) -> P:
    """[B, S, H]-shaped activation pin: the batch entry leads, every
    other dim replicated (the Megatron convention the GSPMD stacks pin
    layer boundaries to)."""
    return P(entry, *([None] * (ndim - 1)))


def microbatched(*entries) -> P:
    """A leading micro/accum-batch axis is NEVER sharded (micro-steps
    are a sequential schedule, not data to place); the remaining dims
    follow ``entries``."""
    return P(None, *entries)


def token_batch_spec(batch, sep=None) -> P:
    """[B, S] ids/labels pin: batch entry on dim 0, the sequence
    (sep) entry on dim 1."""
    return P(batch, sep)


# ---------------------------------------------------------------------------
# mesh introspection
# ---------------------------------------------------------------------------


def ambient_mesh(mesh: Mesh, *args):
    """Context binding ``mesh`` as the ambient mesh around a jitted call
    with ``args``: ``jax.sharding.set_mesh`` eagerly; under a trace (the
    doctor tracing a step wrapper) jax refuses ``set_mesh``, and the
    abstract mesh is what the traced body needs."""
    import jax

    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree_util.tree_leaves(args)):
        return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
    return jax.sharding.set_mesh(mesh)


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """{axis name: size} for every mesh axis (size-1 axes included —
    callers that only care about real parallelism filter on > 1)."""
    return {str(a): int(mesh.shape[a]) for a in mesh.axis_names}


def mesh_device_ids(mesh: Mesh) -> frozenset:
    """The device-id set a mesh addresses.  Two meshes with EQUAL sets
    can redistribute in-place (portable collectives, no host staging);
    unequal sets are the elastic shrink/grow case — the reshard engine
    (parallel/reshard.py) routes those through bounded host chunks.
    (Moved here from distributed/topology.py, which re-exports it: the
    helper is mesh introspection, not cluster topology.)"""
    return frozenset(d.id for d in mesh.devices.flat)


def _entry_axes(entry) -> Tuple[str, ...]:
    """Normalize one PartitionSpec entry to a tuple of axis names."""
    if entry is None:
        return ()
    if isinstance(entry, tuple):
        return tuple(str(a) for a in entry)
    return (str(entry),)


def filter_spec_to_mesh(spec: P, mesh: Mesh) -> P:
    """Drop axes absent from the mesh or of size 1 (e.g. mp when running
    pure FSDP).  The single copy of the rule ``models/llama.py`` and the
    hybrid path both apply before placing anything."""
    sizes = mesh_axis_sizes(mesh)

    def keep(entry):
        kept = tuple(a for a in _entry_axes(entry)
                     if sizes.get(a, 0) > 1)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    return P(*(keep(e) for e in tuple(spec)))


def filter_divisible_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """The at-rest placement rule shared by ``apply_llama_sharding`` and
    ``shard_hybrid_state``: filter the plan spec to the mesh, then drop
    (replicate) any entry whose dim is not divisible by the PRODUCT of
    its axis sizes — an entry shards all its axes or none."""
    spec = filter_spec_to_mesh(spec, mesh)
    sizes = mesh_axis_sizes(mesh)
    entries = []
    for i, entry in enumerate(tuple(spec)):
        axes = _entry_axes(entry)
        if not axes:
            entries.append(None)
            continue
        ways = math.prod(sizes[a] for a in axes)
        if i >= len(shape) or int(shape[i]) % ways != 0:
            entries.append(None)
        else:
            entries.append(entry)
    return P(*entries)


def axis_dim_picks(spec: P, shape: Sequence[int], mesh: Mesh,
                   axes: Sequence[str] = ("sharding", "mp")
                   ) -> Dict[str, Optional[int]]:
    """The overlap engine's per-axis dim pick (``plan_layer_layout``):
    for each wanted axis, the FIRST dim whose plan entry names it and
    whose size the axis degree divides (per-axis divisibility — unlike
    the at-rest product rule, each axis falls back to replication
    independently).  A dim cannot host two picked axes: the
    earlier-listed axis wins (sharding over mp, matching the engine)."""
    sizes = mesh_axis_sizes(mesh)
    picks: Dict[str, Optional[int]] = {a: None for a in axes}
    for i, entry in enumerate(tuple(spec)):
        if i >= len(shape):
            continue
        for a in _entry_axes(entry):
            if a not in picks or picks[a] is not None:
                continue
            if sizes.get(a, 0) <= 1:
                continue
            if int(shape[i]) % sizes[a]:
                continue          # replication fallback for this axis
            picks[a] = i
    seen: Dict[int, str] = {}
    for a in axes:                # earlier-listed axis keeps the dim
        d = picks[a]
        if d is None:
            continue
        if d in seen:
            picks[a] = None
        else:
            seen[d] = a
    return picks


def pick_batch_axes(mesh: Mesh, axes: Sequence[str], size: int
                    ) -> Tuple[str, ...]:
    """Largest ``axes`` prefix whose degree product tiles ``size``
    exactly (manual in_specs demand exact tiling) — the hybrid path's
    batch-axes rule, where 'sharding' drops first and falls back to a
    weights-only axis."""
    sizes = mesh_axis_sizes(mesh)
    used = tuple(axes)
    while used and size % math.prod(sizes.get(a, 1) for a in used):
        used = used[:-1]
    return used


# ---------------------------------------------------------------------------
# the canonical per-tensor spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """Canonical placement of ONE logical tensor: global shape, dtype,
    per-dim mesh axes (empty tuple = replicated dim) and memory kind.
    The comparable unit of the Sharding Doctor's cross-stack table —
    two stacks agree on a tensor iff their TensorSpecs agree after
    restriction to the mesh axes both stacks know."""

    shape: Tuple[int, ...]
    dtype: str
    dim_axes: Tuple[Tuple[str, ...], ...]
    memory_kind: str = "device"

    def __post_init__(self):
        if len(self.dim_axes) != len(self.shape):
            raise ValueError(
                f"dim_axes rank {len(self.dim_axes)} != shape rank "
                f"{len(self.shape)} ({self.shape})")

    @property
    def nbytes(self) -> int:
        import jax.numpy as jnp

        return math.prod(self.shape) * jnp.dtype(self.dtype).itemsize \
            if self.shape else jnp.dtype(self.dtype).itemsize

    @property
    def axes_used(self) -> frozenset:
        return frozenset(a for axes in self.dim_axes for a in axes)

    def restrict(self, keep: frozenset) -> "TensorSpec":
        """Drop mesh axes outside ``keep`` from every dim (cross-mesh
        comparison: a hybrid table's 'pp' lead is invisible to a stack
        whose mesh has no pp axis)."""
        return dataclasses.replace(
            self, dim_axes=tuple(tuple(a for a in axes if a in keep)
                                 for axes in self.dim_axes))

    def partition_spec(self) -> P:
        return P(*(None if not axes
                   else (axes if len(axes) > 1 else axes[0])
                   for axes in self.dim_axes))

    def describe(self) -> str:
        dims = ",".join("/".join(axes) if axes else "-"
                        for axes in self.dim_axes)
        return (f"[{'x'.join(map(str, self.shape))}] {self.dtype} "
                f"dims=({dims}) mem={self.memory_kind}")


def spec_to_dim_axes(spec: P, ndim: int) -> Tuple[Tuple[str, ...], ...]:
    """PartitionSpec -> canonical per-dim axis tuples, padded to rank."""
    entries = tuple(spec)[:ndim]
    out = [_entry_axes(e) for e in entries]
    out += [()] * (ndim - len(out))
    return tuple(out)


@dataclasses.dataclass
class SpecLayout:
    """One stack's canonical table: logical tensor name ->
    ``TensorSpec``, plus the mesh axes (name, size) the table was
    derived against.  This table is the artifact the future unified
    partitioning schedule consumes (ROADMAP); today the Sharding Doctor
    extracts one per stack and diffs them (SHARD003)."""

    mesh_axes: Tuple[Tuple[str, int], ...]
    entries: Dict[str, TensorSpec] = dataclasses.field(default_factory=dict)

    def __getitem__(self, name: str) -> TensorSpec:
        return self.entries[name]

    def __contains__(self, name: str) -> bool:
        return name in self.entries

    def items(self):
        return self.entries.items()

    def axis_sizes(self) -> Dict[str, int]:
        return dict(self.mesh_axes)

    def active_axes(self) -> frozenset:
        return frozenset(a for a, n in self.mesh_axes if n > 1)

    def to_table(self) -> Dict[str, Any]:
        """JSON-able dump (DOCTOR.json's ``sharding.canonical_table``)."""
        return {
            "mesh_axes": [[a, n] for a, n in self.mesh_axes],
            "tensors": {
                name: {"shape": list(ts.shape), "dtype": ts.dtype,
                       "dim_axes": [list(axes) for axes in ts.dim_axes],
                       "memory_kind": ts.memory_kind}
                for name, ts in sorted(self.entries.items())},
        }


def layout_mesh_axes(mesh: Mesh) -> Tuple[Tuple[str, int], ...]:
    return tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names)


def _canon_memory_kind(kind: Optional[str]) -> str:
    """The backend's DEFAULT memory kind canonicalizes to "device" (on
    CPU the default is literally a host kind) so concrete-array tables
    compare against plan tables; only non-default residency (the
    offload engine's pinned_host parks) stays distinct."""
    if kind is None:
        return "device"
    try:
        from ..core.device import default_memory_kind

        if kind == default_memory_kind():
            return "device"
    except Exception:
        pass
    return str(kind)


def tensor_spec_from_array(x) -> TensorSpec:
    """Concrete jax array -> canonical spec (the at-rest truth): named
    shardings map straight to dim axes; single-device / fully-replicated
    shardings read as replicated."""
    shape = tuple(int(d) for d in x.shape)
    dtype = str(x.dtype)
    sharding = getattr(x, "sharding", None)
    kind = _canon_memory_kind(getattr(sharding, "memory_kind", None))
    spec = getattr(sharding, "spec", None)
    if spec is None:
        dim_axes = tuple(() for _ in shape)
    else:
        dim_axes = spec_to_dim_axes(spec, len(shape))
    return TensorSpec(shape=shape, dtype=dtype, dim_axes=dim_axes,
                      memory_kind=str(kind))


def layout_from_arrays(tree: Dict[str, Any],
                       mesh: Optional[Mesh] = None) -> SpecLayout:
    """Canonical table of a CONCRETE tree (serving params, a committed
    opt state): each leaf's actual ``.sharding`` is the spec.  ``mesh``
    defaults to the first NamedSharding's mesh; with none (single-chip
    trees) the table carries no axes."""
    if mesh is None:
        for v in tree.values():
            m = getattr(getattr(v, "sharding", None), "mesh", None)
            if m is not None and not getattr(m, "empty", False):
                try:
                    mesh = Mesh(m.devices, m.axis_names)
                except Exception:   # AbstractMesh and friends
                    mesh = None
                break
    axes = layout_mesh_axes(mesh) if mesh is not None else ()
    return SpecLayout(
        mesh_axes=axes,
        entries={name: tensor_spec_from_array(v)
                 for name, v in tree.items()})


def layout_from_plan(shapes: Dict[str, Tuple[int, ...]], mesh: Mesh,
                     spec_for: Callable[[str], P], dtype: str,
                     memory_kind: str = "device") -> SpecLayout:
    """Canonical table from a DECLARED plan: per-name global shapes +
    a name -> PartitionSpec rule, placed under the at-rest
    divisibility-or-replicate rule (``filter_divisible_spec``)."""
    entries = {}
    for name, shape in shapes.items():
        spec = filter_divisible_spec(spec_for(name), shape, mesh)
        entries[name] = TensorSpec(
            shape=tuple(int(d) for d in shape), dtype=str(dtype),
            dim_axes=spec_to_dim_axes(spec, len(shape)),
            memory_kind=memory_kind)
    return SpecLayout(mesh_axes=layout_mesh_axes(mesh), entries=entries)
