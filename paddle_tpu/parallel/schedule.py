"""Unified partitioning schedule (round-19 tentpole).

Three stacks hand-encoded sharding independently — the flat GSPMD
``build_train_step``, the full-manual overlap engine, the hybrid
gpipe/1F1B bodies — and round-14's Sharding Doctor proved (SHARD003)
that their hand-written tables agree on the flagship tree.  PartIR
(PAPERS.md 2401.11202) says partitioning should be a *composition of
named tactics* over one program, not three parallel implementations;
this module is that composition:

- ``PartitionSchedule`` = the canonical per-tensor ``SpecLayout`` table
  (the Doctor's round-14 artifact, DOCTOR.json
  ``sharding_canonical_table``) + an ordered list of named TACTICS
  (``dp`` / ``sharding3`` / ``tp`` / ``pp`` / ``sep`` / ``ep``),
  constructed from an explicit tactic list over a mesh
  (``from_plan`` / ``from_model``) or recovered from the Doctor's
  extracted table (``from_table``).
- All three stacks DERIVE from it: the GSPMD at-rest specs and batch
  pins (``spec_for`` / ``batch_spec``), the overlap engine's
  ``stack_plan`` (leaf layout, bucket plan, prefetch window, ring
  order, hierarchical/codec placement — byte-identical to
  ``overlap.stack_layout_plan``, which remains the single copy), and
  the hybrid bodies' ``hybrid_spec`` placement hook.
- ``FlatUpdateLayout`` is the schedule-level win behind the pinned
  SHARD001 reshard bill: the 2004.13336 flat-update tactic used to
  flatten every leaf ROW-MAJOR and pin the concat to an unrelated 1-D
  sharding, so GSPMD paid a silent layout conversion per leaf in BOTH
  directions (the flagship accum-4 step's 23 all-to-alls / 148
  collective-permutes were almost entirely this bill).  Because the
  schedule knows the ADJACENT tactic — each leaf's at-rest placement —
  it derives a SHARD-MAJOR wire format instead: each leaf flattens as
  [shard blocks in canonical axis order, local elements], exactly the
  rank-major tiled layout the overlap engine's bucket transport already
  uses.  The at-rest -> flat conversion becomes a LOCAL reshape (zero
  collectives), the update math is elementwise (any fixed permutation
  of the flat order is exact), and the only cross-device movement left
  is the real data movement the tactic composition demands.
- ``resilient_train_loop`` accepts a schedule-returning
  ``mesh_builder``: after an elastic shrink/grow the WHOLE schedule
  (not just GSPMD specs) re-derives from the new mesh — bucket plans,
  prefetch windows, ring order included.
- The joint autotuner extends ``tune_memory_config``'s memory x codec
  lattice (round-15) to a full search over partitioning x
  ``MemoryConfig`` x ``OverlapConfig``: ``joint_schedule_lattice``
  builds the product in increasing predicted step-time cost,
  ``choose_joint_config`` picks the cheapest point satisfying the
  compiled-peak (MEM001 machinery) AND DCN-wire (COMM004 machinery)
  budgets — pod-scale configs picked by budget instead of by hand.

Everything here is host-side plan math plus shape-level jnp transforms;
the only traced code paths are the flat-layout transforms, which are
reshape/transpose/constraint chains (no collectives of their own).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .specs import (EXPERT_AXIS, SpecLayout, TensorSpec, _entry_axes,
                    filter_divisible_spec, filter_spec_to_mesh,
                    layout_mesh_axes, mesh_axis_sizes, spec_to_dim_axes)


# ---------------------------------------------------------------------------
# the tactic vocabulary
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Tactic:
    """One named partitioning tactic: the mesh axis it rides and what it
    partitions.  ``kind``:

    - ``data``   — pure batch axis (params replicate, grads reduce),
    - ``weight`` — pure weight axis (batch replicates across it),
    - ``both``   — ZeRO-3-style: weights shard at rest AND the batch
      rides it (the reduce-scatter folds the grad sum).
    """

    name: str
    axis: str
    kind: str


#: the canonical tactic vocabulary, in composition order (outermost
#: first — the order meshes list their axes).  ``sharding3`` is the
#: ZeRO-3 tactic over the ``sharding`` axis; ``tp`` is Megatron tensor
#: parallelism over ``mp``; ``ep`` is round-18's expert axis.
TACTICS: Dict[str, Tactic] = {
    "pp": Tactic("pp", "pp", "weight"),
    "dp": Tactic("dp", "dp", "data"),
    "sharding3": Tactic("sharding3", "sharding", "both"),
    "sep": Tactic("sep", "sep", "data"),
    "tp": Tactic("tp", "mp", "weight"),
    "ep": Tactic("ep", "ep", "both"),
    # round-20: the dropless-transport variant of ``ep``.  Placement is
    # IDENTICAL (expert leaves Shard(ep), tokens batch over ep) — the
    # name declares the TRANSPORT: sorted ragged dispatch + grouped
    # matmul instead of the [E, C, d] capacity buffer, so schedules and
    # Doctor tables can carry which MoE engine a plan means.
    "ep_dropless": Tactic("ep_dropless", "ep", "both"),
}

# axis -> its PRIMARY tactic (first entry per axis wins: a mesh's bare
# "ep" axis still derives the capacity-engine tactic by default;
# "ep_dropless" is selected by name, e.g. from_moe_ep(dropless=True))
_AXIS_TO_TACTIC: Dict[str, Tactic] = {}
for _t in TACTICS.values():
    _AXIS_TO_TACTIC.setdefault(_t.axis, _t)
del _t


def tactics_for_mesh(mesh: Mesh) -> Tuple[Tactic, ...]:
    """The named tactics a mesh composes, in the mesh's axis order
    (size-1 axes contribute no parallelism and are dropped)."""
    sizes = mesh_axis_sizes(mesh)
    out = []
    for a in mesh.axis_names:
        t = _AXIS_TO_TACTIC.get(str(a))
        if t is not None and sizes[str(a)] > 1:
            out.append(t)
    return tuple(out)


_LAYER_RE = re.compile(r"^(model\.layers\.)(\d+)\.")
_LAYER_PREFIX = "model.layers."


def canonical_key(name: str) -> str:
    """Collapse the layer index: ``model.layers.<i>.X`` ->
    ``model.layers.*.X`` — one logical tensor per layer ROLE (the
    Doctor's table keying; analysis/sharding.py re-exports this)."""
    return _LAYER_RE.sub(r"\g<1>*.", name)


def hybrid_leaf_spec(name: str, shape: Sequence[int], mesh: Mesh,
                     plan_for: Callable[[str], P]) -> P:
    """At-rest spec of one hybrid-state leaf — the single copy of the
    pp-tactic stacking rule: stacked layer leaves
    (``model.layers.<suffix>``, leading [L] dim) lead with 'pp', inner
    dims follow the plan under the shared divisibility rule.
    ``llama_hybrid.hybrid_param_spec`` (the model hook the Doctor's
    extractor reads) and ``PartitionSchedule.hybrid_spec`` both
    delegate here."""
    shape = tuple(int(d) for d in shape)
    stacked = name.startswith(_LAYER_PREFIX)
    inner = shape[1:] if stacked else shape
    spec = filter_divisible_spec(plan_for(name), inner, mesh)
    if not stacked:
        return spec
    pp = int(mesh.shape["pp"]) if "pp" in mesh.axis_names else 1
    if shape[0] % max(pp, 1):
        raise ValueError(
            f"{name}: {shape[0]} layers not divisible by pp degree {pp}")
    lead = "pp" if pp > 1 else None
    return P(lead, *tuple(spec))


# ---------------------------------------------------------------------------
# the shard-major flat-update wire format
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _FlatLeafPlan:
    """Shard-major decomposition of one leaf: ``x.reshape(pre)
    .transpose(perm).reshape(ways, -1)`` is the [shard-blocks, local]
    form whose dim 0 shards exactly over the canonical axes — a LOCAL
    reshape under the leaf's at-rest placement."""

    shape: Tuple[int, ...]
    pre: Tuple[int, ...]
    perm: Tuple[int, ...]
    local: int                     # elements per shard block
    spec: Any = None               # the leaf's at-rest PartitionSpec


class FlatUpdateLayout:
    """The schedule-derived wire format of the fused flat optimizer
    update (the 2004.13336 tactic): leaves flatten SHARD-MAJOR over the
    canonical axes so the at-rest -> flat boundary needs no reshard.

    The element ORDER of the flat buffers differs from the legacy
    row-major concat, so the layout is part of the state's identity:
    ``signature`` is baked into the flat-group names
    (``decay|float32|sm[dp2.sharding2.mp2]``) — a state built under one
    layout fed to a step expecting another fails loudly on pytree
    structure, never silently misorders the master."""

    def __init__(self, mesh: Mesh, spec_for: Callable[[str, Tuple[int, ...]], P],
                 axes: Optional[Sequence[str]] = None):
        self.mesh = mesh
        self._spec_for = spec_for
        sizes = mesh_axis_sizes(mesh)
        if axes is None:
            axes = tuple(a for a in map(str, mesh.axis_names)
                         if sizes[a] > 1)
        self.axes: Tuple[str, ...] = tuple(axes)
        self.sizes = sizes
        self.ways = math.prod(sizes[a] for a in self.axes) \
            if self.axes else 1

    @property
    def signature(self) -> str:
        return "sm[" + ".".join(f"{a}{self.sizes[a]}"
                                for a in self.axes) + "]"

    def flat_spec(self) -> P:
        """Sharding of the 1-D flat group buffers (the SHARD005 pin)."""
        if not self.axes:
            return P()
        return P(self.axes if len(self.axes) > 1 else self.axes[0])

    def flat_spec_2d(self) -> P:
        """Sharding of the intermediate [ways, local] form."""
        if not self.axes:
            return P(None, None)
        return P(self.axes if len(self.axes) > 1 else self.axes[0], None)

    # -- per-leaf plans ------------------------------------------------------

    def leaf_plan(self, name: str, shape: Sequence[int]
                  ) -> Optional[_FlatLeafPlan]:
        """Shard-major decomposition for one leaf, or None when the
        shape cannot host every canonical axis (the caller falls back
        to the row-major wire format for the whole group — mixed orders
        inside one buffer would not be a layout, just a bug)."""
        shape = tuple(int(d) for d in shape)
        if not shape:
            return None
        spec = filter_divisible_spec(self._spec_for(name, shape), shape,
                                     self.mesh)
        entries = tuple(spec)
        dims: List[List[Any]] = []
        for i, dim in enumerate(shape):
            rem = int(dim)
            for a in (_entry_axes(entries[i]) if i < len(entries) else ()):
                n = self.sizes.get(a, 1)
                if n <= 1:
                    continue
                if rem % n:
                    return None        # post-filter this cannot happen
                dims.append([n, a])
                rem //= n
            dims.append([rem, None])
        used = {ax for _, ax in dims if ax is not None}
        for a in self.axes:
            if a in used:
                continue
            n = self.sizes[a]
            for j, (sz, ax) in enumerate(dims):
                if ax is None and sz % n == 0 and sz >= n:
                    dims[j:j + 1] = [[n, a], [sz // n, None]]
                    break
            else:
                return None            # leaf too small to subdivide
        block = [next(j for j, (_, ax) in enumerate(dims) if ax == a)
                 for a in self.axes]
        rest = [j for j in range(len(dims)) if j not in block]
        perm = tuple(block + rest)
        pre = tuple(int(sz) for sz, _ in dims)
        local = math.prod(pre[j] for j in rest)
        return _FlatLeafPlan(shape=shape, pre=pre, perm=perm, local=local,
                             spec=spec)

    # -- the transforms (shape math only; exact inverses) --------------------

    def flatten_leaf(self, plan: _FlatLeafPlan, x):
        """Leaf (global shape) -> [ways, local] shard-major 2-D form.
        A local relayout under the at-rest placement — no collective."""
        a = jnp.asarray(x).reshape(plan.pre)
        a = a.transpose(plan.perm)
        return a.reshape(self.ways, plan.local)

    def unflatten_leaf(self, plan: _FlatLeafPlan, flat2d):
        """Exact inverse of flatten_leaf."""
        mid_shape = tuple(plan.pre[j] for j in plan.perm)
        a = jnp.asarray(flat2d).reshape(mid_shape)
        a = a.transpose(tuple(np.argsort(plan.perm)))
        return a.reshape(plan.shape)

    def pack_group(self, plans: Dict[str, _FlatLeafPlan],
                   keys: Sequence[str], values: Dict[str, Any],
                   dtype=jnp.float32):
        """Group wire format: concat the [ways, local] leaf forms along
        the UNSHARDED dim, then merge into the 1-D flat buffer — every
        step local under the at-rest placements.  ``values[k]`` may be
        host arrays (init path: no pins, same element order)."""
        if not keys:
            return jnp.zeros((0,), dtype)
        cols = [self.flatten_leaf(plans[k],
                                  jnp.asarray(values[k]).astype(dtype))
                for k in keys]
        return jnp.concatenate(cols, axis=1).reshape(-1)

    def unpack_group(self, plans: Dict[str, _FlatLeafPlan],
                     keys: Sequence[str], flat,
                     pin_leaves: bool = False) -> Dict[str, Any]:
        """Inverse of pack_group: 1-D flat group -> per-leaf globals.
        ``pin_leaves`` constrains each leaf back to its at-rest spec
        (the traced slice-back path; eager state converters skip it)."""
        out: Dict[str, Any] = {}
        if not keys:
            return out
        f2 = jnp.asarray(flat).reshape(self.ways, -1)
        off = 0
        for k in keys:
            pl = plans[k]
            leaf = self.unflatten_leaf(pl, f2[:, off:off + pl.local])
            if pin_leaves and pl.spec is not None:
                leaf = jax.lax.with_sharding_constraint(
                    leaf, NamedSharding(self.mesh, pl.spec))
            out[k] = leaf
            off += pl.local
        return out

    def flat_sharding(self) -> NamedSharding:
        """The layout's OWN sharding of a 1-D flat buffer."""
        return NamedSharding(self.mesh, self.flat_spec())

    def pin(self, flat):
        """The SHARD005 cross-replica update pin, in the shard-major
        layout's OWN sharding (so the pin is a no-op relayout)."""
        return jax.lax.with_sharding_constraint(flat, self.flat_sharding())


# ---------------------------------------------------------------------------
# the stack-schedule derivation (what the overlap/hybrid engines consume)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StackSchedule:
    """The overlap engine's derived schedule for one decoder stack:
    leaf placements, gather-bucket plan, non-gathered (grad-sync)
    leaves, the prefetch window (layers of gather-ahead), the ppermute
    ring order of the collective matmul, and the resolved hierarchical
    (ICI/DCN) structure with its codec.  Byte-identical to the
    hand-written ``overlap.stack_layout_plan`` outputs — the derivation
    delegates to the same single-copy rules."""

    layout: Dict[str, Any]             # suffix -> overlap._LeafPlace
    buckets: List[List[str]]
    sync_suffixes: List[str]
    prefetch_window: int
    ring_order: Tuple[Tuple[int, int], ...]
    hier: Optional[Any] = None
    codec: Optional[Any] = None

    def describe(self) -> Dict[str, Any]:
        return {
            "buckets": [list(b) for b in self.buckets],
            "sync_suffixes": list(self.sync_suffixes),
            "prefetch_window": self.prefetch_window,
            "ring_order": [list(p) for p in self.ring_order],
            "hierarchical": None if self.hier is None else {
                "num_slices": self.hier.num_slices,
                "per_slice": self.hier.per_slice},
            "codec": (self.codec.to_json()
                      if self.codec is not None else None),
        }


# ---------------------------------------------------------------------------
# the schedule object
# ---------------------------------------------------------------------------


class PartitionSchedule:
    """THE unified partitioning schedule: canonical per-tensor table +
    ordered named tactics over one mesh.  All three training stacks
    (GSPMD / overlap / hybrid) and the elastic loop derive their
    placement decisions from this object; see the module docstring."""

    def __init__(self, mesh: Mesh, plan_for: Callable[[str], P],
                 table: SpecLayout,
                 tactics: Optional[Tuple[Tactic, ...]] = None):
        self.mesh = mesh
        self.plan_for = plan_for
        self.table = table
        self.tactics = (tactics if tactics is not None
                        else tactics_for_mesh(mesh))

    # -- construction --------------------------------------------------------

    @classmethod
    def from_plan(cls, mesh: Mesh, shapes: Dict[str, Tuple[int, ...]],
                  spec_for: Callable[[str], P], dtype: str = "float32",
                  tactics: Optional[Sequence[str]] = None
                  ) -> "PartitionSchedule":
        """Explicit construction: per-name global shapes + a declared
        plan rule, placed under the shared at-rest
        divisibility-or-replicate rule.  ``tactics`` optionally names
        the composition (default: derived from the mesh axes)."""
        entries: Dict[str, TensorSpec] = {}
        for name, shape in shapes.items():
            key = canonical_key(name)
            spec = filter_divisible_spec(spec_for(name), shape, mesh)
            ts = TensorSpec(shape=tuple(int(d) for d in shape),
                            dtype=str(dtype),
                            dim_axes=spec_to_dim_axes(spec, len(shape)))
            prev = entries.get(key)
            if prev is not None and prev != ts:
                raise ValueError(
                    f"{key}: layer roles disagree under the plan "
                    f"({prev.describe()} vs {ts.describe()})")
            entries[key] = ts
        table = SpecLayout(mesh_axes=layout_mesh_axes(mesh),
                           entries=entries)
        tac = (tuple(TACTICS[t] for t in tactics)
               if tactics is not None else None)
        return cls(mesh, spec_for, table, tac)

    @classmethod
    def from_model(cls, model, mesh: Mesh, plan=None
                   ) -> "PartitionSchedule":
        """The flagship constructor: a Llama-family model's named
        parameters under its declared plan (``LLAMA_SHARDING_PLAN`` by
        default) — the same table ``extract_gspmd_layout`` pins."""
        from ..models.llama import plan_spec_for

        shapes = {name: tuple(int(d) for d in p.shape)
                  for name, p in model.named_parameters()}
        return cls.from_plan(mesh, shapes,
                             lambda n: plan_spec_for(n, plan))

    @classmethod
    def from_table(cls, table: Dict[str, Any],
                   mesh: Optional[Mesh] = None) -> "PartitionSchedule":
        """Recover a schedule from the Doctor's extracted canonical
        table (DOCTOR.json ``sharding_canonical_table`` /
        ``SpecLayout.to_table()``).  ``mesh`` defaults to a mesh over
        the visible devices with the table's axis names/sizes."""
        axes = [(str(a), int(n)) for a, n in table["mesh_axes"]]
        if mesh is None:
            total = math.prod(n for _, n in axes) if axes else 1
            devs = np.asarray(jax.devices()[:total], dtype=object)
            if devs.size < total:
                raise ValueError(
                    f"table wants {total} devices, have {devs.size}")
            mesh = Mesh(devs.reshape([n for _, n in axes] or [1]),
                        tuple(a for a, _ in axes) or ("dp",))
        entries: Dict[str, TensorSpec] = {}
        for name, ts in table["tensors"].items():
            entries[name] = TensorSpec(
                shape=tuple(int(d) for d in ts["shape"]),
                dtype=str(ts["dtype"]),
                dim_axes=tuple(tuple(str(a) for a in axs)
                               for axs in ts["dim_axes"]),
                memory_kind=str(ts.get("memory_kind", "device")))
        layout = SpecLayout(mesh_axes=tuple(axes), entries=entries)

        def plan_for(name: str) -> P:
            """The recovered plan rule answers every naming the stacks
            query with: full dotted names (any layer index), the hybrid
            stacked form (``model.layers.<suffix>``, no index), and
            BARE intra-layer suffixes (the overlap engine's layout
            unit, e.g. ``self_attn.q_proj.weight``)."""
            key = canonical_key(name)
            ts = entries.get(key)
            if ts is None and key.startswith(_LAYER_PREFIX):
                ts = entries.get(_LAYER_PREFIX + "*."
                                 + key[len(_LAYER_PREFIX):])
            if ts is None:
                ts = entries.get(_LAYER_PREFIX + "*." + key)
            if ts is None:
                for k, v in entries.items():
                    if k.endswith("." + key):
                        ts = v
                        break
            if ts is None:
                return P()
            return ts.partition_spec()

        return cls(mesh, plan_for, layout)

    @classmethod
    def from_moe_ep(cls, cfg, mesh: Mesh, dtype: str = "float32",
                    tactics: Optional[Sequence[str]] = None,
                    dropless: bool = False) -> "PartitionSchedule":
        """The EP constructor: the MoE block's declared plan
        (``expert.moe_ep_layout`` — expert-stacked leaves lead with
        ``ep``, the shared gate replicates) wired through the unified
        schedule so ``ep`` composes with dp/sharding/tp/pp in the
        declared-plan vocabulary (and the roofline enumerator can emit
        ep points that answer the same table queries).  ``cfg`` is a
        ``MoEEPConfig``.

        ``dropless=True`` names the ``ep_dropless`` tactic on the ep
        axis instead of ``ep``: the at-rest table is byte-identical
        (the dropless engine changes the token TRANSPORT, not the
        placement), but the schedule's tactic names — what DOCTOR.json
        and the autotuner records carry — declare the sorted-ragged
        engine, so a recovered plan rebuilds the right train step."""
        from .expert import moe_ep_shapes, moe_ep_spec_for

        if tactics is None and dropless:
            tactics = ["ep_dropless" if t.axis == EXPERT_AXIS else t.name
                       for t in tactics_for_mesh(mesh)]
        return cls.from_plan(mesh, moe_ep_shapes(cfg), moe_ep_spec_for,
                             dtype=dtype, tactics=tactics)

    # -- tactic/axis introspection -------------------------------------------

    def tactic_names(self) -> Tuple[str, ...]:
        return tuple(t.name for t in self.tactics)

    # -- the GSPMD derivation ------------------------------------------------

    def spec_for(self, name: str, shape: Sequence[int]) -> P:
        """At-rest PartitionSpec of one leaf: the declared plan under
        the shared divisibility-or-replicate rule (what
        ``apply_llama_sharding`` places and the GSPMD step constrains
        against)."""
        return filter_divisible_spec(self.plan_for(name),
                                     tuple(int(d) for d in shape),
                                     self.mesh)

    def plan_spec_for(self, name: str) -> P:
        """The PRE-filter plan spec (the overlap engine's per-axis pick
        rule applies its own divisibility per axis)."""
        return filter_spec_to_mesh(self.plan_for(name), self.mesh)

    def named_sharding(self, name: str, shape: Sequence[int]
                       ) -> NamedSharding:
        """The sharding a leaf is PLACED with: ``spec_for`` less its
        trailing Nones, the form in which jit spells the shardings it
        chooses for outputs.  ``P(None) != P()`` in jit's cache key, so
        a step fed its own outputs would otherwise compile a second
        time."""
        spec = tuple(self.spec_for(name, shape))
        while spec and spec[-1] is None:
            spec = spec[:-1]
        return NamedSharding(self.mesh, P(*spec))

    def reshard_specs(self) -> Dict[str, P]:
        """Per-canonical-name at-rest specs in reshard-planner form
        (dotted path -> P) — what ``resilient_train_loop`` hands
        ``plan_reshard`` after deriving the schedule from a new mesh."""
        return {name: ts.partition_spec()
                for name, ts in self.table.items()}

    def reshard_spec(self, path: str, leaf=None) -> P:
        """Planner-callable form (``plan_reshard``'s ``(path, leaf) ->
        P`` contract): canonical-table lookup first, then the plan rule
        (the planner's ``fit_spec`` degrades either to a valid
        placement on any mesh)."""
        ts = self.table.entries.get(canonical_key(path))
        if ts is not None:
            return ts.partition_spec()
        shape = tuple(getattr(leaf, "shape", ()) or ())
        if shape:
            return self.spec_for(path, shape)
        return self.plan_for(path)

    def flat_update_layout(self, axes: Optional[Sequence[str]] = None
                           ) -> FlatUpdateLayout:
        """The shard-major flat-update wire format (module docstring);
        the 2004.13336 tactic derived FROM the at-rest tactics."""
        return FlatUpdateLayout(
            self.mesh, lambda n, s: self.plan_for(n), axes=axes)

    # -- the overlap derivation ----------------------------------------------

    def layer_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Per-layer leaf shapes keyed by intra-layer suffix (the
        overlap engine's layout unit), read from the canonical table."""
        out = {}
        for name, ts in self.table.items():
            if name.startswith(_LAYER_PREFIX + "*."):
                out[name[len(_LAYER_PREFIX) + 2:]] = ts.shape
        return out

    def stack_plan(self, oc=None, compute_dtype=jnp.bfloat16,
                   shapes: Optional[Dict[str, Tuple[int, ...]]] = None
                   ) -> StackSchedule:
        """Derive the overlap engine's whole schedule: delegates to
        ``overlap.stack_layout_plan`` (single copy — byte-identical to
        the hand-written path) and rides the resolved ring order,
        prefetch window and hierarchical/codec placement along."""
        from . import overlap as _ov

        oc = oc if oc is not None else _ov.OverlapConfig()
        shapes = shapes if shapes is not None else self.layer_shapes()
        layout, buckets, sync = _ov.stack_layout_plan(
            shapes, self.mesh,
            lambda sfx: self.plan_spec_for(sfx), oc,
            compute_dtype=compute_dtype)
        sizes = mesh_axis_sizes(self.mesh)
        sh = sizes.get("sharding", 1)
        sh_ax = "sharding" if sh > 1 else None
        hier = oc.resolve_hier(self.mesh, sh_ax)
        mp = sizes.get("mp", 1)
        ring = tuple((i, (i + 1) % mp) for i in range(mp)) if mp > 1 \
            else ()
        return StackSchedule(
            layout=layout, buckets=buckets, sync_suffixes=sync,
            prefetch_window=1 if oc.prefetch else 0,
            ring_order=ring, hier=hier,
            codec=oc.codec if hier is not None else None)

    # -- the hybrid derivation -----------------------------------------------

    def hybrid_spec(self, name: str, shape: Sequence[int]) -> P:
        """At-rest spec of one HYBRID-state leaf (the pp-tactic
        stacking rule; single copy: ``hybrid_leaf_spec``)."""
        return hybrid_leaf_spec(name, shape, self.mesh, self.plan_for)

    # -- elastic re-derivation ----------------------------------------------

    def rederive(self, mesh: Mesh) -> "PartitionSchedule":
        """The SAME tactic composition over a NEW mesh (elastic
        shrink/grow): the canonical table re-derives from the plan rule
        under the new axis sizes — bucket plans, prefetch windows and
        ring orders all follow (``stack_plan`` on the result)."""
        entries = {}
        for name, ts in self.table.items():
            spec = filter_divisible_spec(self.plan_for(name), ts.shape,
                                         mesh)
            entries[name] = TensorSpec(
                shape=ts.shape, dtype=ts.dtype,
                dim_axes=spec_to_dim_axes(spec, len(ts.shape)),
                memory_kind=ts.memory_kind)
        return PartitionSchedule(
            mesh, self.plan_for,
            SpecLayout(mesh_axes=layout_mesh_axes(mesh),
                       entries=entries))

    # -- reporting -----------------------------------------------------------

    def to_json(self) -> Dict[str, Any]:
        return {"tactics": list(self.tactic_names()),
                "mesh_axes": [[a, n] for a, n in
                              layout_mesh_axes(self.mesh)],
                "table": self.table.to_table()}

    def describe(self) -> str:
        axes = ", ".join(f"{a}={n}" for a, n in layout_mesh_axes(self.mesh)
                         if n > 1)
        return (f"PartitionSchedule[{' / '.join(self.tactic_names())}]"
                f" over ({axes}; {len(self.table.entries)} tensors)")


# ---------------------------------------------------------------------------
# the joint partition x memory x overlap autotuner
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PartitionPoint:
    """One partitioning point of the joint lattice: a tactic
    composition as concrete mesh axis degrees (outer..inner, the
    hybrid_mesh order), plus the slice map when the point spans slices
    (which arms the hierarchical schedule and prices DCN wire)."""

    name: str
    axes: Tuple[Tuple[str, int], ...]
    slice_map: Optional[Tuple[int, ...]] = None
    #: the slice map's axis (the hierarchical schedule's axis by
    #: convention; EP points pass "ep")
    dcn_axis: str = "sharding"

    def mesh(self, devices=None) -> Mesh:
        devs = list(jax.devices() if devices is None else devices)
        total = math.prod(n for _, n in self.axes)
        if len(devs) < total:
            raise ValueError(f"{self.name}: wants {total} devices, "
                             f"have {len(devs)}")
        grid = np.asarray(devs[:total], dtype=object).reshape(
            [n for _, n in self.axes])
        return Mesh(grid, tuple(a for a, _ in self.axes))

    def dcn_axes(self) -> Dict[str, List[int]]:
        """Axis -> slice map (collect_wire_table's shape) for the
        slice-spanning axis of this point; empty when single-slice."""
        if self.slice_map is None:
            return {}
        return {self.dcn_axis: list(self.slice_map)}

    def label(self) -> str:
        body = "x".join(f"{a}{n}" for a, n in self.axes if n > 1)
        return f"{self.name}({body})" + \
            ("[2slice]" if self.slice_map else "")

    def to_json(self) -> Dict[str, Any]:
        return {"name": self.name, "axes": [[a, n] for a, n in self.axes],
                "slice_map": (list(self.slice_map)
                              if self.slice_map else None)}


@dataclasses.dataclass(frozen=True)
class JointScheduleConfig:
    """One point of the FULL joint lattice: partitioning x memory
    residency x overlap/codec — what ``tune_memory_config`` walks when
    handed ``joint_schedule_lattice`` (its record/label/json duck-type
    matches ``memory.JointConfig``)."""

    partition: PartitionPoint
    memory: Any                        # parallel.memory.MemoryConfig
    overlap: Optional[Any] = None      # parallel.overlap.OverlapConfig

    def label(self) -> str:
        lab = self.partition.label() + "/" + self.memory.label()
        codec = getattr(self.overlap, "codec", None)
        lab += "/" + (codec.label() if codec is not None else "codec-off")
        return lab

    def to_json(self) -> Dict[str, Any]:
        codec = getattr(self.overlap, "codec", None)
        return {"partition": self.partition.to_json(),
                "memory": self.memory.to_json(),
                "codec": codec.to_json() if codec is not None else None}


def joint_schedule_lattice(points: Sequence[PartitionPoint],
                           memory_lattice: Optional[Sequence] = None,
                           codec_points: Optional[Sequence] = None,
                           base_overlap=None
                           ) -> Tuple[JointScheduleConfig, ...]:
    """Partitioning x MemoryConfig x codec product in increasing
    predicted step-time cost: partition points are listed
    cheapest-first by the caller (more compute-efficient compositions
    first), then per point the memory lattice (cheapest recompute
    first), then the codec points (increasing error tolerance) — the
    same cheapest-first-fitting-last walk as the round-15 lattice, one
    axis richer."""
    from .memory import MEMORY_LATTICE, codec_lattice_points
    from .overlap import OverlapConfig

    mem = tuple(MEMORY_LATTICE if memory_lattice is None
                else memory_lattice)
    cps = tuple(codec_lattice_points() if codec_points is None
                else codec_points)
    base = base_overlap if base_overlap is not None else OverlapConfig()
    out = []
    for pt in points:
        for m in mem:
            for c in cps:
                if c is not None and pt.slice_map is None:
                    continue        # codec is DCN-only; no DCN stage
                oc = dataclasses.replace(
                    base, codec=c,
                    hierarchical="on" if pt.slice_map else "off",
                    slice_map=pt.slice_map)
                out.append(JointScheduleConfig(pt, m, oc))
    return tuple(out)


def choose_joint_config(records: Sequence[Dict[str, Any]],
                        hbm_bytes: Optional[int] = None,
                        dcn_wire_bytes: Optional[int] = None
                        ) -> Optional[int]:
    """Index of the first (cheapest) record satisfying EVERY declared
    budget — compiled peak under ``hbm_bytes`` (MEM001's currency) and
    post-codec DCN wire bytes under ``dcn_wire_bytes`` (COMM004's) —
    or None when no point fits.  Records keep lattice (cost) order, so
    the choice is monotone: relaxing either budget never picks a
    LATER (more expensive) point."""
    for i, rec in enumerate(records):
        if hbm_bytes is not None and rec["peak_bytes"] > hbm_bytes:
            continue
        if dcn_wire_bytes is not None \
                and rec.get("dcn_wire_bytes", 0) > dcn_wire_bytes:
            continue
        return i
    return None


def measure_dcn_wire_bytes(cfg: JointScheduleConfig, fn, args) -> int:
    """Post-codec DCN bytes of one built step (the COMM004 cost-model
    leg of the joint walk): trace and price the manual collectives
    against the point's slice map."""
    from ..analysis.passes.collective_budget import collect_wire_table

    dcn_axes = cfg.partition.dcn_axes()
    if not dcn_axes:
        return 0
    jaxpr = jax.make_jaxpr(getattr(fn, "__wrapped__", fn))(*args).jaxpr
    return int(collect_wire_table(jaxpr, dcn_axes)["dcn"]["bytes"])


def tune_schedule_config(step_builder: Callable[[JointScheduleConfig],
                                                Tuple],
                         hbm_bytes: int,
                         lattice: Sequence[JointScheduleConfig], *,
                         dcn_wire_bytes: Optional[int] = None,
                         predict: bool = False,
                         estimator: Optional[Callable] = None,
                         top_k: int = 1):
    """The full joint search: ``tune_memory_config``'s walk (cheapest
    first, measure compiled peak, first fit wins) over the
    partitioning x memory x overlap lattice, with the DCN wire budget
    measured through the Doctor's COMM004 machinery.  Returns
    ``(chosen, records)`` exactly like the memory tuner.

    ``predict=True`` (round-20): rank the lattice by the analytic
    roofline estimate FIRST and compile only the top-K — the
    estimator (``roofline.joint_estimator(sheet, ...)``; a callable
    JointScheduleConfig -> StepTimeEstimate) orders the space and
    optionally pre-filters by its predicted budget verdict
    (``estimate.fits``), while the compiled MEM001 peak / COMM004 wire
    gates stay the ground-truth verifier on every point that IS
    compiled.  Records come back in lattice order, every point
    carrying its ``predicted`` estimate + ``predicted_rank``; only
    compiled points carry measured ``peak_bytes``/``fits``."""
    from .memory import tune_memory_config

    if not predict:
        if dcn_wire_bytes is None:
            return tune_memory_config(step_builder, hbm_bytes,
                                      lattice=tuple(lattice))
        return tune_memory_config(
            step_builder, hbm_bytes, lattice=tuple(lattice),
            dcn_wire_bytes=dcn_wire_bytes,
            dcn_bytes_fn=measure_dcn_wire_bytes)
    if estimator is None:
        raise ValueError(
            "tune_schedule_config(predict=True) needs an estimator "
            "(roofline.joint_estimator) — a predicted ranking with no "
            "estimate would silently fall back to lattice order")
    return _predicted_walk(step_builder, hbm_bytes, tuple(lattice),
                           estimator, dcn_wire_bytes=dcn_wire_bytes,
                           top_k=max(1, int(top_k)))


def _predicted_walk(step_builder, hbm_bytes, lattice, estimator, *,
                    dcn_wire_bytes=None, top_k=1):
    """The predict-mode walk: estimate every point (cheap, analytic),
    visit in predicted-cheapest order skipping points the estimator
    predicts infeasible (when it renders a verdict), compile at most
    ``top_k`` of them, and stop at the first point whose MEASURED peak
    (and, when budgeted, measured DCN wire bytes) fits."""
    from .memory import measure_step_memory

    ests = [estimator(jc) for jc in lattice]

    def _total(e):
        return e.total_s if hasattr(e, "total_s") else e["total_s"]

    order = sorted(range(len(lattice)), key=lambda i: _total(ests[i]))
    records = []
    for i, (jc, est) in enumerate(zip(lattice, ests)):
        ej = est.to_json() if hasattr(est, "to_json") else dict(est)
        records.append({"config": jc.to_json(), "label": jc.label(),
                        "predicted": ej,
                        "predicted_rank": order.index(i),
                        "compiled": False})
    chosen = None
    compiled = 0
    for idx in order:
        if compiled >= top_k:
            break
        fits_pred = records[idx]["predicted"].get("fits")
        if fits_pred is False:
            continue            # predicted misfit: not worth a compile
        jc = lattice[idx]
        fn, args = step_builder(jc)
        stats = measure_step_memory(fn, *args)
        rec = records[idx]
        rec.update(stats, compiled=True,
                   fits=stats["peak_bytes"] <= hbm_bytes)
        if dcn_wire_bytes is not None:
            dcn = int(measure_dcn_wire_bytes(jc, fn, args))
            rec["dcn_wire_bytes"] = dcn
            rec["fits"] = bool(rec["fits"] and dcn <= dcn_wire_bytes)
        compiled += 1
        if rec["fits"]:
            chosen = jc
            break
    return chosen, records
