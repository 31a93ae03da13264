"""HBM memory engine (round-10).

Rounds 6–9 made the train step compute- and communication-efficient;
the third resource that bounds MFU on a real chip is HBM CAPACITY — the
batch size (and with it the arithmetic intensity every prior win rests
on) is picked by hand and an over-budget config is discovered as a
compile-time OOM one TPU session later.  This module makes residency an
engineered, inspectable artifact, three levers + a meter:

1. **Named-policy rematerialization** — ``MemoryConfig(remat=...)``
   selects the per-decoder-layer ``jax.checkpoint`` policy by NAME
   (``none | dots | names | offload | full``) over ``checkpoint_name``-
   tagged saveables in the Llama decoder layer (models/llama.py and the
   overlap engine's ``decoder_layer_tp`` tag the attention and MLP
   block outputs — the residual-stream tensors that dominate activation
   memory).  This replaces the binary ``remat=True/False`` flag in both
   the GSPMD and the full-manual/overlap stacks.
2. **Host-offloaded optimizer state** — the fused AdamW flat fp32
   groups (optimizer.Adam.init_flat_state) gain a ``pinned_host``
   residency: each (decay, dtype) group lives on host SPLIT INTO
   size-capped buckets (the overlap engine's one bucketing rule,
   ``split_by_bytes``), and the update streams each bucket in, applies
   on device via the exact ``_flat_group_update`` math (elementwise, so
   bucket streaming is bit-equal with the device-resident apply), and
   streams the new moments/master back out — double-buffered so bucket
   i+1's host→device transfer is issued before bucket i's compute and
   the stream hides under the backward's reduce-scatter tail.
3. **Activation offload** — the tagged residual-stream saveables are
   routed to ``pinned_host`` by the ``offload`` checkpoint policy
   (arxiv 2112.01075's argument for staged, size-bounded host↔device
   movement: the per-layer saveables ARE the size-capped chunks), so
   backward streams each layer's residuals back one layer ahead.
4. **Peak-HBM budget + autotuner** — ``compiled.memory_analysis()``
   plumbed into the Graph Doctor's ``memory_budget`` pass (MEM001 peak
   bytes over the declared budget, MEM002 host-transfer bytes over the
   declared streaming budget) and ``tune_memory_config(step_builder,
   hbm_bytes)``, which walks the remat/offload lattice in increasing
   predicted step-time cost and returns the first (cheapest) config
   whose measured peak fits — "Automatic Cross-Replica Sharding of
   Weight Update" (arxiv 2004.13336) is the reference result that the
   optimizer-state partition/offload trade is the dominant capacity
   lever, which is why host residency sorts BEFORE heavier remat in the
   lattice.

CPU contract: the traced host<->device transfers are emitted on every
backend (place_on_host / place_on_device), so the bucket plan, the
streaming apply, the policy selection and the MEM002 transfer audit all
exercise the REAL code path on CPU, and every lattice point is
loss-parity-tested against the flat baseline
(tests/test_memory_engine.py).  Only the eager at-rest placement is
TPU-only (see _put_memory_space).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


# checkpoint_name tags planted in the decoder layer (models/llama.py
# LlamaDecoderLayer, models/llama_hybrid._decoder_layer and
# parallel/overlap.decoder_layer_tp): the attention-block and MLP-block
# outputs — the [b, s, hidden] residual-stream contributions that
# dominate per-layer activation memory.  The named policies key on
# exactly this set; adding a tag here without tagging the layers (or
# vice versa) makes "names"/"offload" silently equal to "full", which
# the lattice parity tests would not catch — the memory meter would.
SAVEABLE_NAMES: Tuple[str, ...] = ("decoder_attn_out", "decoder_mlp_out")

REMAT_POLICIES = ("none", "dots", "names", "offload", "full")
RESIDENCIES = ("device", "host")


def tag_saveable(x, name: str):
    """``checkpoint_name`` on a raw array — the tagging primitive the
    decoder layers use.  Identity (with the name still recorded) under
    every policy that doesn't reference it."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(x, name)


def named_save_policy():
    """save_only_these_names over the decoder saveables: keep the two
    residual-stream block outputs per layer, recompute everything else
    in backward — between ``dots`` (keeps every matmul output) and
    ``full`` (keeps nothing)."""
    return jax.checkpoint_policies.save_only_these_names(*SAVEABLE_NAMES)


def offload_names_policy():
    """The named saveables routed to host memory instead of HBM;
    everything else recomputed.  Degrades to named_save_policy() when
    the toolchain/backend has no host memory kind (the residency change
    is elided, the save/recompute split is identical)."""
    from ..core.device import host_memory_kind

    dst = host_memory_kind()
    if dst is None:
        return named_save_policy()
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=list(SAVEABLE_NAMES),
        offload_src="device", offload_dst=dst)


def _dots_saved_names_offloaded():
    """dots_saveable for matmuls, offload_names_policy() for the rest
    (jax's save_from_both_policies refuses to mix a boolean policy with
    one that answers in offload decisions)."""
    dots = jax.checkpoint_policies.dots_saveable
    names = offload_names_policy()

    def policy(prim, *avals, **params):
        if dots(prim, *avals, **params):
            return True
        return names(prim, *avals, **params)
    return policy


@dataclasses.dataclass(frozen=True)
class MemoryConfig:
    """One point on the residency lattice.

    ``remat`` — the named per-decoder-layer checkpoint policy:
      - ``"none"``: no checkpoint wrap; every activation stays in HBM,
      - ``"dots"``: ``dots_saveable`` — matmul outputs kept, the cheap
        elementwise chain recomputed (the classic TPU FLOPs/HBM trade),
      - ``"names"``: only the tagged residual-stream saveables kept,
      - ``"offload"``: the tagged saveables kept ON HOST (streamed back
        in backward), everything else recomputed,
      - ``"full"``: plain ``jax.checkpoint`` — nothing saved.
    ``optimizer_residency`` — where the fused AdamW flat fp32 groups
      live: ``"device"`` (HBM-resident, PR-2 behaviour) or ``"host"``
      (bucket-streamed; see apply_flat_offloaded).
    ``activation_offload`` — in the no-remat regime, trade the HBM-
      resident residual stream for host residency: the layer is
      checkpoint-wrapped with dots SAVED on device (so no matmul is
      recomputed — the "no-remat" FLOP profile) and the tagged
      residuals offloaded.  Composes with ``dots`` the same way; under
      ``names``/``full`` it promotes the tagged saveables to host
      (== the ``offload`` policy).
    ``stream_bucket_bytes`` — the size cap for optimizer-state stream
      buckets (the overlap engine's bucketing rule).
    ``hbm_budget_bytes`` / ``host_transfer_budget_bytes`` — optional
      declared budgets, forwarded to the Graph Doctor's
      ``memory_budget`` pass by callers that audit the built step.
    """

    remat: str = "none"
    optimizer_residency: str = "device"
    activation_offload: bool = False
    stream_bucket_bytes: int = 4 << 20
    hbm_budget_bytes: Optional[int] = None
    host_transfer_budget_bytes: Optional[int] = None

    def __post_init__(self):
        if self.remat not in REMAT_POLICIES:
            raise ValueError(
                f"MemoryConfig.remat={self.remat!r}; expected one of "
                f"{REMAT_POLICIES}")
        if self.optimizer_residency not in RESIDENCIES:
            raise ValueError(
                f"MemoryConfig.optimizer_residency="
                f"{self.optimizer_residency!r}; expected one of "
                f"{RESIDENCIES}")

    def act_keep_factor(self) -> float:
        """Activation bytes kept per token-layer relative to the
        no-remat baseline — the residency knob the roofline peak model
        reads (round-20: the factor table lives beside the estimator in
        roofline.py; THIS method is the policy-semantics owner, folding
        ``activation_offload``'s host-residency halving on top the same
        way ``resolve_remat`` folds it into the checkpoint policy)."""
        from .roofline import _ACT_KEEP_FACTOR

        keep = _ACT_KEEP_FACTOR.get(self.remat, 1.0)
        if self.activation_offload:
            keep *= 0.5
        return keep

    def recompute_fwd_passes(self) -> float:
        """Extra forward passes the backward recomputes under this
        remat policy — the roofline estimate's recompute FLOPs term
        (round-20; "dots" saves every matmul so its recompute is
        second-order, folded to 0)."""
        from .roofline import REMAT_RECOMPUTE_FACTOR

        return REMAT_RECOMPUTE_FACTOR.get(self.remat, 0.0)

    def resolve_remat(self):
        """(use_checkpoint, policy) for the decoder-layer wrap — the
        single translation point from policy NAME to jax.checkpoint
        arguments, shared by build_train_step (GSPMD path), the overlap
        stack and the hybrid executors."""
        cp = jax.checkpoint_policies
        if self.remat == "none":
            if not self.activation_offload:
                return False, None
            # no-remat + offload: dots stay saved on device (no matmul
            # recompute) while the tagged residual stream parks on host
            return True, _dots_saved_names_offloaded()
        if self.remat == "dots":
            return True, (_dots_saved_names_offloaded()
                          if self.activation_offload
                          else cp.dots_saveable)
        if self.remat == "names":
            return True, (offload_names_policy()
                          if self.activation_offload
                          else named_save_policy())
        if self.remat == "offload":
            return True, offload_names_policy()
        # "full": nothing saved; with activation_offload the tagged
        # saveables become the only survivors, parked on host
        if self.activation_offload:
            return True, offload_names_policy()
        return True, None

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def label(self) -> str:
        bits = [self.remat, self.optimizer_residency]
        if self.activation_offload:
            bits.append("act_offload")
        return "/".join(bits)


# The autotuner's walk order: increasing predicted step-time cost.
# Host residency for the optimizer state sorts BEFORE heavier remat
# (2004.13336: the optimizer-state partition/offload trade is the
# dominant capacity lever and costs a bucket stream, not recompute
# FLOPs); activation offload before matmul-recompute policies for the
# same reason; "full" is the last resort.
MEMORY_LATTICE: Tuple[MemoryConfig, ...] = (
    MemoryConfig(remat="none"),
    MemoryConfig(remat="none", optimizer_residency="host"),
    MemoryConfig(remat="none", optimizer_residency="host",
                 activation_offload=True),
    MemoryConfig(remat="dots"),
    MemoryConfig(remat="dots", optimizer_residency="host"),
    MemoryConfig(remat="dots", optimizer_residency="host",
                 activation_offload=True),
    MemoryConfig(remat="names"),
    MemoryConfig(remat="names", optimizer_residency="host"),
    MemoryConfig(remat="offload", optimizer_residency="host"),
    MemoryConfig(remat="full", optimizer_residency="host"),
)


# ---------------------------------------------------------------------------
# host-offloaded optimizer state (bucket-streamed fused AdamW)
# ---------------------------------------------------------------------------


def _put_memory_space(x, space: "jax.memory.Space", kind):
    """Move ``x`` to a memory space: under a trace by the space (the
    form jit accepts, visible to the MEM002 audit as a device_put eqn),
    on a concrete array by its sharding's memory kind (the form eager
    device_put places for real).  Identity when ``kind`` is None (the
    backend has no such space) or ``x`` already lives there.

    Concrete arrays are only re-labelled on a TPU: XLA:CPU executables
    do not report their outputs' memory kinds, so jit labels every
    output ``device`` whatever the program placed, and a donated input
    labelled ``pinned_host`` then aliases an output of another label
    and the runtime aborts.  On CPU the at-rest label stays ``device``;
    the traced transfers (and so the audited program) are the same."""
    from ..core.device import is_tpu

    if kind is None:
        return x
    if isinstance(x, jax.core.Tracer):
        return jax.device_put(x, space)
    if not is_tpu() or x.sharding.memory_kind == kind:
        return x
    return jax.device_put(x, x.sharding.with_memory_kind(kind))


def place_on_host(x):
    """Place ``x`` in the host (``pinned_host``) memory space — THE
    residency primitive of the offload engine, shared since round 16
    with the serving prefix cache's host tier (inference/serving.py
    demotes cold full pages through this instead of evicting them)."""
    from ..core.device import host_memory_kind

    return _put_memory_space(x, jax.memory.Space.Host, host_memory_kind())


def place_on_device(x):
    """Fetch ``x`` back into the compute-resident memory space — host
    state must pass through here before any arithmetic (jax refuses to
    mix memory spaces in one op)."""
    from ..core.device import default_memory_kind

    return _put_memory_space(x, jax.memory.Space.Device,
                             default_memory_kind())


# internal aliases (the optimizer-offload stream predates the public
# names; one implementation either way)
_to_host = place_on_host
_to_device = place_on_device


def stream_bucket_plan(n_elems: int, itemsize: int, cap: int
                       ) -> List[Tuple[int, int]]:
    """(offset, size) slices of a flat group under the size cap —
    split_by_bytes over virtual per-element items collapses to simple
    arithmetic here, but the RULE is the same: the cap splits, never
    reorders, and a zero/negative cap means one element per bucket is
    nonsense so it degrades to one bucket per group."""
    if n_elems <= 0:
        return []
    if cap <= 0:
        return [(0, n_elems)]
    per = max(int(cap) // int(itemsize), 1)
    plan = []
    off = 0
    while off < n_elems:
        size = min(per, n_elems - off)
        plan.append((off, size))
        off += size
    return plan


def offload_flat_state(flat_state: Dict[str, Any],
                       bucket_bytes: int = 4 << 20) -> Dict[str, Any]:
    """Flat fused-AdamW state ({'__flat__': {group: {moment1, moment2
    [, master]}}}) -> the host-resident bucketed form:

        {'__offload__': {group: {'moment1': (b0, b1, ...), ...}}}

    Each bucket is a contiguous slice of the flat fp32 buffer, placed in
    host memory (device_put with the host memory kind; identity where
    none exists).  The bucket SIZES are carried by the leaves
    themselves, so the apply path needs no side-channel plan."""
    if not (isinstance(flat_state, dict)
            and set(flat_state) == {"__flat__"}):
        raise ValueError("offload_flat_state expects a state from "
                         "init_flat_state ({'__flat__': ...})")
    out: Dict[str, Dict[str, Tuple]] = {}
    for gname, gs in flat_state["__flat__"].items():
        og: Dict[str, Tuple] = {}
        for key, arr in gs.items():
            arr = jnp.asarray(arr)
            plan = stream_bucket_plan(arr.shape[0], arr.dtype.itemsize,
                                      bucket_bytes)
            og[key] = tuple(place_on_host(arr[off:off + size])
                            for off, size in plan)
        out[gname] = og
    return {"__offload__": out}


def init_offloaded_state(optimizer, params, decay_mask=None,
                         master_from=None,
                         bucket_bytes: int = 4 << 20,
                         flat_layout=None) -> Dict[str, Any]:
    """init_flat_state + offload_flat_state in one call — what
    build_train_step callers use when
    MemoryConfig.optimizer_residency == 'host'.  ``flat_layout``
    builds the flat buffers in the schedule-derived shard-major wire
    format (parallel/schedule.py) before bucketing — bucket streaming
    is elementwise, so the split composes with any layout."""
    flat = optimizer.init_flat_state(params, decay_mask=decay_mask,
                                     master_from=master_from,
                                     flat_layout=flat_layout)
    return offload_flat_state(flat, bucket_bytes)


def state_is_offloaded(state) -> bool:
    return isinstance(state, dict) and set(state) == {"__offload__"}


def gather_offloaded_state(state) -> Dict[str, Any]:
    """Inverse of offload_flat_state (checkpoint interop and parity
    tests): concatenate each key's buckets back into the flat form."""
    if not state_is_offloaded(state):
        raise ValueError("not an offloaded state")
    flat = {}
    for gname, gs in state["__offload__"].items():
        flat[gname] = {k: jnp.concatenate([jnp.asarray(b) for b in bs])
                       if bs else jnp.zeros((0,), jnp.float32)
                       for k, bs in gs.items()}
    return {"__flat__": flat}


def apply_flat_offloaded(optimizer, params, grads, state, lr,
                         step: int = 0, decay_mask=None,
                         flat_sharding=None, flat_layout=None):
    """Fused multi-tensor AdamW over HOST-RESIDENT bucketed flat groups.

    Per group: the (device-resident) grads concatenate once; then each
    size-capped state bucket streams host→device, updates through the
    optimizer's own ``_flat_group_update`` (elementwise — bit-equal
    with the device-resident apply_flat), and streams the new
    moments/master back to host.  Double-buffered: bucket i+1's fetch
    is issued BEFORE bucket i's update math, so the latency-hiding
    scheduler can run the stream under the update (and, in the full
    train step, under the backward's reduce-scatter tail).  New params
    are assembled on device from the new-master buckets — the only
    full-group device materialization, and it is the one the forward
    needs anyway.

    ``flat_sharding`` pins the flat-buffer layout on mesh-sharded
    steps — same contract (and same GSPMD mis-lowering guard) as
    Adam.apply_flat; build_train_step supplies it whenever a mesh is
    present.  ``flat_layout`` routes groups whose state was built in
    the schedule-derived shard-major wire format (parallel/schedule.py;
    detected by group names like apply_flat) — the streamed update is
    elementwise, so bucketing composes with either layout."""
    from ..optimizer.optimizer import _pin_lr_f32 as pin_lr_f32

    if not state_is_offloaded(state):
        raise ValueError("apply_flat_offloaded needs a state from "
                         "init_offloaded_state / offload_flat_state")
    lr = pin_lr_f32(lr)
    groups = optimizer._match_flat_groups(
        params, {"__flat__": state["__offload__"]}, decay_mask,
        flat_layout)
    missing = [k for g in groups for k in g["keys"]
               if grads.get(k) is None]
    if missing:
        raise ValueError(
            f"apply_flat_offloaded: every grouped param needs a "
            f"gradient (missing: {missing[:3]}...)")
    new_params = dict(params)
    new_off: Dict[str, Dict[str, Tuple]] = {}
    for g in groups:
        lo = g.get("layout")

        def _pin_flat(x, _lo=lo):
            if _lo is not None:
                return _lo.pin(x)
            if flat_sharding is None:
                return x
            return jax.lax.with_sharding_constraint(x, flat_sharding)

        gs = state["__offload__"][g["name"]]
        m1_b, m2_b = gs["moment1"], gs["moment2"]
        master_b = gs.get("master")
        if g["keys"] and lo is not None:
            gflat = _pin_flat(lo.pack_group(
                g["plans"], g["keys"], {k: grads[k] for k in g["keys"]}))
        elif g["keys"]:
            gflat = _pin_flat(jnp.concatenate(
                [jnp.asarray(grads[k]).astype(jnp.float32).reshape(-1)
                 for k in g["keys"]]))
        else:
            gflat = jnp.zeros((0,), jnp.float32)
        # bucket offsets come from the state leaves themselves; plain
        # Python accumulation — these are static trace-time ints, and
        # the repo AST lint (AST001) bans host-numpy in traced bodies
        sizes = [int(b.shape[0]) for b in m1_b]
        offs = [0]
        for s in sizes[:-1]:
            offs.append(offs[-1] + s)
        if sum(sizes) != gflat.shape[0]:
            raise ValueError(
                f"offloaded state for group {g['name']} covers "
                f"{sum(sizes)} elements but the params/grads flatten "
                f"to {gflat.shape[0]} — state built for a different "
                f"param set")

        def fetch(i):
            m1 = _to_device(m1_b[i])
            m2 = _to_device(m2_b[i])
            if master_b is not None:
                mst = _to_device(master_b[i])
            else:
                # fp32 params carry no separate master: the slice of
                # the (device-resident) param concat IS the master
                mst = None
            return m1, m2, mst

        pflat = None
        if master_b is None:
            if g["keys"] and lo is not None:
                pflat = _pin_flat(lo.pack_group(
                    g["plans"], g["keys"],
                    {k: params[k] for k in g["keys"]}))
            elif g["keys"]:
                pflat = _pin_flat(jnp.concatenate(
                    [jnp.asarray(params[k]).astype(jnp.float32)
                     .reshape(-1) for k in g["keys"]]))
            else:
                pflat = jnp.zeros((0,), jnp.float32)

        nm1_out, nm2_out, nmst_out, master_parts = [], [], [], []
        cur = fetch(0) if sizes else None
        for i, (off, size) in enumerate(zip(offs, sizes)):
            nxt = fetch(i + 1) if i + 1 < len(sizes) else None
            m1, m2, mst = cur
            if mst is None:
                mst = jax.lax.dynamic_slice_in_dim(pflat, off, size)
            gsl = jax.lax.dynamic_slice_in_dim(gflat, off, size)
            new_master, nm1, nm2 = optimizer._flat_group_update(
                _pin_flat(gsl), _pin_flat(m1), _pin_flat(m2),
                _pin_flat(mst), lr, step, g["decay"])
            master_parts.append(new_master)
            nm1_out.append(_to_host(nm1))
            nm2_out.append(_to_host(nm2))
            if master_b is not None:
                nmst_out.append(_to_host(new_master))
            cur = nxt
        new_master_full = jnp.concatenate(master_parts) if master_parts \
            else jnp.zeros((0,), jnp.float32)
        ngs: Dict[str, Tuple] = {"moment1": tuple(nm1_out),
                                 "moment2": tuple(nm2_out)}
        if master_b is not None:
            ngs["master"] = tuple(nmst_out)
        new_off[g["name"]] = ngs
        out_dtype = jnp.dtype(g["dtype"])
        if lo is not None:
            leaves = lo.unpack_group(g["plans"], g["keys"],
                                     new_master_full, pin_leaves=True)
            for k in g["keys"]:
                new_params[k] = leaves[k].astype(out_dtype)
        else:
            off2 = 0
            for k, shape, size in zip(g["keys"], g["shapes"],
                                      g["sizes"]):
                new_params[k] = new_master_full[off2:off2 + size].reshape(
                    shape).astype(out_dtype)
                off2 += size
    return new_params, {"__offload__": new_off}


# ---------------------------------------------------------------------------
# the memory meter + autotuner
# ---------------------------------------------------------------------------


def _unwrap_jit(fn):
    """Follow __wrapped__ down to a lowerable jit entry (the same rule
    as analysis.core._unwrap, local so parallel/ stays independent of
    analysis/)."""
    seen = set()
    while not hasattr(fn, "lower") and id(fn) not in seen:
        seen.add(id(fn))
        inner = getattr(fn, "__wrapped__", None)
        if inner is None or not hasattr(inner, "lower"):
            break
        fn = inner
    return fn


def measure_step_memory(fn, *args, **kwargs) -> Dict[str, int]:
    """Compile ``fn(*args)`` and read ``compiled.memory_analysis()``
    into a plain dict.  ``peak_bytes`` is the capacity number the
    budget pass and the autotuner gate on: arguments + outputs + XLA
    temporaries, minus donation aliasing (a donated arg and its output
    share one buffer)."""
    target = _unwrap_jit(fn)
    if not hasattr(target, "lower"):
        target = jax.jit(target)
    compiled = target.lower(*args, **kwargs).compile()
    ma = compiled.memory_analysis()
    stats = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        "host_argument_bytes": int(ma.host_argument_size_in_bytes),
        "host_output_bytes": int(ma.host_output_size_in_bytes),
        "host_temp_bytes": int(ma.host_temp_size_in_bytes),
    }
    stats["peak_bytes"] = (stats["argument_bytes"]
                           + stats["output_bytes"]
                           + stats["temp_bytes"]
                           - stats["alias_bytes"])
    stats["host_bytes"] = (stats["host_argument_bytes"]
                           + stats["host_output_bytes"]
                           + stats["host_temp_bytes"])
    return stats


def choose_memory_config(records: Sequence[Dict[str, Any]],
                         hbm_bytes: int) -> Optional[int]:
    """Index of the first (cheapest) record whose peak fits the budget,
    None when nothing fits.  Records keep lattice (cost) order, so for
    budgets b1 <= b2 the chosen index for b2 is <= that for b1 — a
    larger budget can never pick a MORE-rematerialized config (the
    monotonicity contract tests/test_memory_engine.py pins)."""
    for i, rec in enumerate(records):
        if rec["peak_bytes"] <= hbm_bytes:
            return i
    return None


@dataclasses.dataclass(frozen=True)
class JointConfig:
    """One point on the JOINT MemoryConfig × OverlapConfig(codec)
    lattice (round-15): the autotuner walks memory residency AND the
    quantized-DCN-collective knob together, so a config that fits HBM
    but blows the DCN wire budget loses to one that trades a little
    codec error for 4× fewer DCN bytes.  ``overlap`` is an
    OverlapConfig (kept opaque here — parallel/memory stays independent
    of the overlap engine's types)."""

    memory: MemoryConfig
    overlap: Optional[Any] = None

    def label(self) -> str:
        lab = self.memory.label()
        codec = getattr(self.overlap, "codec", None)
        lab += "/" + (codec.label() if codec is not None else "codec-off")
        return lab

    def to_json(self) -> Dict[str, Any]:
        codec = getattr(self.overlap, "codec", None)
        return {"memory": self.memory.to_json(),
                "codec": codec.to_json() if codec is not None else None}


def codec_lattice_points() -> Tuple:
    """The codec knob's walk order: off (exact) first, then the int8
    stochastic grad profile (block-scaled — the tighter error bound),
    then all-fp8 (same wire bytes, cheaper en/decode, looser error) —
    increasing error tolerance, decreasing only when a DCN wire budget
    forces the trade."""
    from .codec import CollectiveCodec

    return (None,
            CollectiveCodec(),
            CollectiveCodec(grad_profile="fp8", weight_profile="fp8",
                            stochastic=False))


def joint_memory_codec_lattice(overlap,
                               memory_lattice: Optional[Sequence] = None,
                               codec_points: Optional[Sequence] = None
                               ) -> Tuple[JointConfig, ...]:
    """MemoryConfig × codec joint lattice over a base OverlapConfig:
    per memory point (cheapest recompute first), the codec points in
    increasing-error order — the walk a pod-scale config uses to trade
    codec error tolerance against DCN bytes alongside remat/offload."""
    import dataclasses as _dc

    mem = tuple(MEMORY_LATTICE if memory_lattice is None
                else memory_lattice)
    pts = tuple(codec_lattice_points() if codec_points is None
                else codec_points)
    return tuple(JointConfig(m, _dc.replace(overlap, codec=c))
                 for m in mem for c in pts)


def tune_memory_config(step_builder: Callable[[Any], Tuple],
                       hbm_bytes: int,
                       lattice: Optional[Sequence] = None, *,
                       dcn_wire_bytes: Optional[int] = None,
                       dcn_bytes_fn: Optional[Callable] = None):
    """Walk the remat/offload lattice (cheapest predicted step time
    first), measure each built step's compiled peak, and return
    ``(config, records)`` — ``config`` the cheapest fitting point
    (None if even the most aggressive point exceeds the budget),
    ``records`` the full per-point measurement list (what bench.py
    --profile surfaces as ``memory_levers`` / MEMCONFIG.json).

    ``step_builder(cfg)`` returns ``(fn, args)`` — typically
    ``build_train_step(model, opt, memory=cfg)`` plus example inputs
    with the real shapes/dtypes/shardings.  ``lattice`` entries may be
    MemoryConfig or JointConfig (memory × overlap-codec) points.

    ``dcn_wire_bytes`` adds the round-15 second budget axis: each
    point's post-codec DCN bytes (measured by ``dcn_bytes_fn(cfg, fn,
    args) -> int`` — typically collect_wire_table over the traced
    step) must ALSO fit, so the walk lands on the cheapest point that
    satisfies capacity AND the wire contract — the codec-error-vs-
    DCN-bytes trade made by the same cheapest-first rule as
    remat/offload."""
    if dcn_wire_bytes is not None and dcn_bytes_fn is None:
        raise ValueError(
            "tune_memory_config: dcn_wire_bytes declared but no "
            "dcn_bytes_fn to measure it — a budget with no measurement "
            "would silently pass every point")
    lattice = tuple(MEMORY_LATTICE if lattice is None else lattice)
    records: List[Dict[str, Any]] = []
    for cfg in lattice:
        fn, args = step_builder(cfg)
        stats = measure_step_memory(fn, *args)
        rec = {"config": cfg.to_json(), "label": cfg.label(), **stats,
               "fits": stats["peak_bytes"] <= hbm_bytes}
        if dcn_wire_bytes is not None:
            dcn = int(dcn_bytes_fn(cfg, fn, args))
            rec["dcn_wire_bytes"] = dcn
            rec["fits"] = bool(rec["fits"] and dcn <= dcn_wire_bytes)
        records.append(rec)
    idx = next((i for i, rec in enumerate(records) if rec["fits"]), None)
    chosen = lattice[idx] if idx is not None else None
    return chosen, records
