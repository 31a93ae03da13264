"""Roofline step-time estimator + enumerated partitioning search
(round-20 tentpole).

The round-19 joint autotuner walks a caller-hand-listed lattice
cheapest-first and pays one flagship compile per point.  This module
supplies the missing ANALYTIC half: a closed-form step-time estimate
for a ``(PartitionSchedule, MemoryConfig, OverlapConfig, codec)`` point
on a declared topology, so the search ranks an ENUMERATED space first
and compiles only the top-K (``tune_schedule_config(predict=True)``),
with the MEM001/COMM004 budget gates kept as the ground-truth verifier.

Three layers:

- CHIP TABLES + PRIMITIVES — the single copy of the peak-FLOPs /
  HBM-BW / link-bandwidth tables (``CHIP_SPECS``, per-generation
  overridable) and the roofline primitives ``matmul_time`` /
  ``elementwise_time`` / ``collective_time`` that
  ``cost_model.CostModel`` delegates to, plus ``ring_wire_cost`` — the
  one copy of the COMM004 ring formulas (the Doctor's
  ``collective_budget`` pass prices the traced jaxpr with the SAME
  function, so predicted and measured wire bytes share arithmetic by
  construction).

- THE ESTIMATE — ``ModelCostSheet`` (per-layer weight/activation/FLOP
  accounting derived from a LlamaConfig), ``predict_wire_table`` (an
  analytic mirror of the overlap engine's manual-collective schedule:
  per-layer hierarchical bucket all-gather forward, hierarchical
  reduce-scatter backward, per-layer norm grad-sync, the codec's
  packed-int8 wire dtypes via ``codec.packed_width``), and
  ``estimate_step_time`` — max-of-rooflines compute vs HBM with the
  remat recompute term folded in, plus per-tactic ICI/DCN collective
  time, overlap modeled as exposed-comm = max(0, comm − hideable
  compute).  On the fake-2-slice flagship the DCN prediction
  reproduces the four measured DOCTOR.json wire pins EXACTLY
  (446 208 / 150 916 / 226 048 / 76 612); ICI and peak-HBM are
  first-order structural models (peak supports one-point calibration —
  predict deltas, anchor the offset on a single compiled record).

- THE SEARCH — ``enumerate_partitionings(mesh_shape, model)``:
  candidate tactic compositions straight from the named-tactic
  vocabulary (dp / sharding3 / tp / pp / sep / ep over v5p-pod-shaped
  meshes), divisibility- and HBM-feasibility-pruned, and
  ``rank_partitionings`` ordering them by the estimate.

PartIR (PAPERS.md 2401.11202) is the shape of the argument: named
compositional tactics make the space enumerable and cheaply costable;
the scaling-book ring model prices the collectives.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "ChipSpec", "CHIP_SPECS", "chip_spec", "ring_wire_cost",
    "matmul_time", "elementwise_time", "collective_time",
    "ModelCostSheet", "llama_cost_sheet", "predict_wire_table",
    "predict_peak_bytes", "StepTimeEstimate", "estimate_step_time",
    "estimate_joint_config", "joint_estimator",
    "enumerate_partitionings", "rank_partitionings",
    "DEVICE_KIND_TO_CHIP", "chip_spec_for_device",
]


# ---------------------------------------------------------------------------
# chip tables — THE single copy (cost_model delegates here)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """One TPU generation's roofline constants.  ``hbm_bytes`` is the
    per-chip capacity the HBM-feasibility pruner checks against;
    bandwidths are per-chip aggregates (ICI: all links combined, the
    ring model's per-hop currency; DCN: per-host share)."""

    name: str
    peak_bf16_flops: float
    hbm_bytes_per_s: float
    hbm_bytes: int
    ici_bytes_per_s: float
    dcn_bytes_per_s: float

    def replace(self, **kw) -> "ChipSpec":
        return dataclasses.replace(self, **kw)


#: Per-generation table.  v5e carries the numbers the round-4 cost
#: model shipped with (197 TF bf16 / 819 GB/s HBM / 45 GB/s ICI) so the
#: dedup is value-preserving; the others follow the public spec sheets.
CHIP_SPECS: Dict[str, ChipSpec] = {
    "v4": ChipSpec("v4", 275e12, 1228e9, 32 << 30, 100e9, 6.25e9),
    "v5e": ChipSpec("v5e", 197e12, 819e9, 16 << 30, 45e9, 6.25e9),
    "v5p": ChipSpec("v5p", 459e12, 2765e9, 95 << 30, 100e9, 6.25e9),
    "v6e": ChipSpec("v6e", 918e12, 1640e9, 32 << 30, 90e9, 6.25e9),
}


#: ``jax.devices()[0].device_kind`` -> CHIP_SPECS key, as the runtime
#: spells it (a v5e reports "TPU v5 lite").
DEVICE_KIND_TO_CHIP: Dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}


def chip_spec_for_device(device_kind: str) -> ChipSpec:
    """Peaks of the attached device, from its ``device_kind``.  A device
    that is not in the table is an error, never a default."""
    try:
        return CHIP_SPECS[DEVICE_KIND_TO_CHIP[device_kind]]
    except KeyError:
        raise KeyError(
            f"no peaks known for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_KIND_TO_CHIP)} (add it to "
            f"parallel/roofline.DEVICE_KIND_TO_CHIP)") from None


def chip_spec(chip) -> ChipSpec:
    """Resolve a chip argument: a ChipSpec passes through, a name looks
    up the table (KeyError names the known generations)."""
    if isinstance(chip, ChipSpec):
        return chip
    try:
        return CHIP_SPECS[str(chip)]
    except KeyError:
        raise KeyError(f"unknown chip {chip!r}; known: "
                       f"{sorted(CHIP_SPECS)} (or pass a ChipSpec)")


def ring_wire_cost(kind: str, nbytes: int, g: int) -> int:
    """Ring cost model of one collective over a group of ``g``:
    bytes-on-the-wire given the INPUT buffer size (the scaling-book
    recipe the COMM004 pass prices the traced jaxpr with — this is the
    single copy; ``analysis.passes.collective_budget`` delegates here).
    all_gather moves the input to g-1 peers; reduce_scatter/all_to_all
    move (g-1)/g of it; all_reduce is gather+scatter; a permute
    forwards the buffer once."""
    if g <= 1:
        return 0
    if kind == "allgather":
        return nbytes * (g - 1)
    if kind == "reducescatter":
        return nbytes * (g - 1) // g
    if kind == "allreduce":
        return 2 * nbytes * (g - 1) // g
    if kind == "alltoall":
        return nbytes * (g - 1) // g
    return nbytes                       # collectivepermute


def _norm_kind(kind: str) -> str:
    return kind.replace("_", "").replace("-", "")


# ---------------------------------------------------------------------------
# roofline primitives — what cost_model.CostModel serves
# ---------------------------------------------------------------------------


def matmul_time(m: int, n: int, k: int, *, bytes_per_el: int = 2,
                peak_flops: Optional[float] = None,
                hbm_bytes_per_s: Optional[float] = None,
                chip="v5e") -> float:
    """MXU/HBM roofline of one (m,k)x(k,n) matmul: max(compute,
    memory) seconds."""
    spec = chip_spec(chip)
    peak = peak_flops if peak_flops is not None else spec.peak_bf16_flops
    bw = (hbm_bytes_per_s if hbm_bytes_per_s is not None
          else spec.hbm_bytes_per_s)
    flops = 2.0 * m * n * k
    bytes_moved = bytes_per_el * (m * k + k * n + m * n)
    return max(flops / peak, bytes_moved / bw)


def elementwise_time(numel: int, bytes_per_el: int = 4, *,
                     hbm_bytes_per_s: Optional[float] = None,
                     chip="v5e") -> float:
    """HBM-bound elementwise op: read + write each element once."""
    bw = (hbm_bytes_per_s if hbm_bytes_per_s is not None
          else chip_spec(chip).hbm_bytes_per_s)
    return 2.0 * numel * bytes_per_el / bw


def collective_time(bytes_total: int, n_devices: int, *,
                    link_bytes_per_s: Optional[float] = None,
                    kind: str = "all_reduce", chip="v5e",
                    link: str = "ici") -> float:
    """Ring-model collective estimate over ``bytes_total`` (the FULL
    payload — the all_gather result, the all_reduce operand) on a group
    of ``n_devices``.  Shares the ``ring_wire_cost`` formulas: an
    all_gather's ring input is the per-device shard bytes_total/n."""
    if n_devices <= 1:
        return 0.0
    spec = chip_spec(chip)
    bw = (link_bytes_per_s if link_bytes_per_s is not None
          else (spec.dcn_bytes_per_s if link == "dcn"
                else spec.ici_bytes_per_s))
    k = _norm_kind(kind)
    nb = bytes_total / n_devices if k == "allgather" else bytes_total
    # float mirror of ring_wire_cost (the int version keeps the COMM004
    # pins byte-exact; times are continuous)
    frac = {"allreduce": 2.0 * (n_devices - 1) / n_devices,
            "allgather": float(n_devices - 1),
            "reducescatter": (n_devices - 1) / n_devices,
            "alltoall": (n_devices - 1) / n_devices,
            "collectivepermute": 1.0}[k]
    return frac * nb / bw


# ---------------------------------------------------------------------------
# the model cost sheet
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelCostSheet:
    """Per-layer weight/FLOP accounting of a decoder-LM — everything
    the estimator needs, with no concrete Mesh or arrays (so the v5p
    pod enumeration runs on a laptop).  Derive one with
    ``llama_cost_sheet(cfg)``."""

    name: str
    num_layers: int
    hidden: int
    intermediate: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab: int
    num_experts: int = 0
    moe_top_k: int = 2
    # -- round-20 MoE engine pricing knobs (defaults keep every pinned
    #    prediction byte-identical: eff-rows-per-token = top_k) --------
    #: price the DROPLESS engine: expert FLOPs and dispatch payload are
    #: the variable segments actually routed — NO capacity padding term
    moe_dropless: bool = False
    #: measured balance point of the dropless engine (>= 1): ragged
    #: wall-clock tracks the max-loaded ep shard, so variable-segment
    #: work is priced at (balance * top_k) rows per token (1.0 =
    #: perfectly balanced routing; bench --moe-trace measures it as
    #: max/mean expert load)
    moe_balance: float = 1.0
    #: capacity engine's padding factor (cf): the static [E, C, d]
    #: buffer computes/ships cf * top_k rows per token regardless of
    #: routing.  0.0 = unpriced (legacy pins)
    moe_capacity_factor: float = 0.0

    # -- per-layer element counts ------------------------------------------

    @property
    def layer_attn_elems(self) -> int:
        """q/k/v/o projection weights (the sharding-gathered attention
        leaves of LLAMA_SHARDING_PLAN)."""
        h, kv = self.hidden, self.num_kv_heads * self.head_dim
        return 2 * h * h + 2 * h * kv

    @property
    def layer_mlp_elems(self) -> int:
        """gate/up/down of the DENSE MLP (0 when the layer is MoE)."""
        if self.num_experts:
            return 0
        return 3 * self.hidden * self.intermediate

    @property
    def layer_expert_elems(self) -> int:
        """Expert-stacked weights, placed on ``ep`` (leading [E] dim),
        plus the replicated router gate."""
        if not self.num_experts:
            return 0
        return (self.num_experts * 3 * self.hidden * self.intermediate
                + self.hidden * self.num_experts)

    @property
    def moe_eff_rows_per_token(self) -> float:
        """Expert-FFN rows computed (and dispatched) per token under the
        declared MoE engine: the DROPLESS engine prices the variable
        segments actually routed at the measured balance point —
        ``balance * top_k``, no capacity padding term — while the
        capacity engine prices its static padded buffer,
        ``cf * top_k`` (cf == 0 keeps the legacy unpriced top_k)."""
        if self.moe_dropless:
            return self.moe_balance * self.moe_top_k
        if self.moe_capacity_factor > 0:
            return self.moe_capacity_factor * self.moe_top_k
        return float(self.moe_top_k)

    @property
    def layer_gathered_elems(self) -> int:
        """The ZeRO-3 bucketed stack per layer: what the overlap
        engine's hierarchical all-gather/reduce-scatter moves."""
        return self.layer_attn_elems + self.layer_mlp_elems

    @property
    def layer_sync_elems(self) -> int:
        """Per-layer replicated sync leaves (the two RMSNorm weights):
        grad-synced with a flat psum over the data axes."""
        return 2 * self.hidden

    @property
    def misc_sync_elems(self) -> int:
        """Non-layer replicated leaves (the final norm): synced over
        ALL mesh axes."""
        return self.hidden

    @property
    def embed_elems(self) -> int:
        return self.vocab * self.hidden

    @property
    def head_elems(self) -> int:
        return self.hidden * self.vocab

    @property
    def params_total(self) -> int:
        return (self.num_layers * (self.layer_gathered_elems
                                   + self.layer_expert_elems
                                   + self.layer_sync_elems)
                + self.misc_sync_elems + self.embed_elems
                + self.head_elems)

    # -- FLOPs --------------------------------------------------------------

    def fwd_flops(self, batch: int, seq: int) -> float:
        """Forward FLOPs of one step (2*elems per matmul weight per
        token + the two attention batched matmuls); MoE layers route
        each token through top_k experts."""
        tokens = batch * seq
        per_tok = 2.0 * (self.layer_attn_elems + self.layer_mlp_elems)
        if self.num_experts:
            per_tok += 2.0 * self.moe_eff_rows_per_token * (
                3 * self.hidden * self.intermediate) \
                + 2.0 * self.hidden * self.num_experts
        attn = 4.0 * seq * self.hidden          # QK^T + AV per token
        lm = 2.0 * (self.hidden * self.vocab)   # lm_head (+tied embed)
        return tokens * (self.num_layers * (per_tok + attn) + lm)

    def step_flops(self, batch: int, seq: int,
                   recompute_factor: float = 0.0) -> float:
        """fwd + 2x bwd + remat recompute (an extra ``recompute_factor``
        forward passes)."""
        return self.fwd_flops(batch, seq) * (3.0 + recompute_factor)


def llama_cost_sheet(cfg) -> ModelCostSheet:
    """Cost sheet of a LlamaConfig (or any object with its fields)."""
    heads = int(cfg.num_attention_heads)
    hd = int(getattr(cfg, "head_dim", cfg.hidden_size // heads))
    return ModelCostSheet(
        name=type(cfg).__name__,
        num_layers=int(cfg.num_hidden_layers),
        hidden=int(cfg.hidden_size),
        intermediate=int(cfg.intermediate_size),
        num_heads=heads,
        num_kv_heads=int(cfg.num_key_value_heads),
        head_dim=hd,
        vocab=int(cfg.vocab_size),
        num_experts=int(getattr(cfg, "num_experts", 0) or 0),
        moe_top_k=int(getattr(cfg, "moe_top_k", 2) or 2),
        moe_dropless=bool(getattr(cfg, "moe_dropless", False)),
        moe_balance=float(getattr(cfg, "moe_balance", 1.0) or 1.0),
        moe_capacity_factor=float(
            getattr(cfg, "moe_capacity_factor", 0.0) or 0.0))


#: MemoryConfig.remat -> extra forward passes recomputed in backward
#: (the recompute term of the estimate).  "dots"-only remat rematerializes
#: cheap elementwise regions — second-order, folded to 0.
REMAT_RECOMPUTE_FACTOR = {"none": 0.0, "dots": 0.0, "names": 1.0,
                          "offload": 1.0, "full": 1.0}


def _axis_degrees(axes) -> Dict[str, int]:
    """Axis-name -> degree of a PartitionPoint.axes tuple / dict."""
    d = dict(axes if not hasattr(axes, "items") else axes.items())
    return {str(a): int(n) for a, n in d.items()}


def _slice_shape(axes: Dict[str, int],
                 slice_map: Optional[Sequence[int]]
                 ) -> Tuple[int, int]:
    """(num_slices S, per-slice degree K) of the slice-spanning
    sharding axis; (1, sh) when single-slice."""
    sh = axes.get("sharding", 1)
    if not slice_map:
        return 1, sh
    s = len(set(slice_map))
    return s, max(1, sh // s)


# ---------------------------------------------------------------------------
# the analytic wire table — mirror of the overlap engine's schedule
# ---------------------------------------------------------------------------


def _packed(codec, n_elems: int) -> int:
    """Post-codec wire bytes of an ``n_elems`` payload row (int8 blocks
    + per-block scales — ``CollectiveCodec.wire_bytes``, which owns the
    ``packed_width`` arithmetic; duck-typed fallback for bare
    block-carrying objects)."""
    if hasattr(codec, "wire_bytes"):
        return int(codec.wire_bytes(n_elems))
    from .codec import packed_width

    return packed_width(int(n_elems), codec.block,
                        getattr(codec, "checksum", False))


def predict_wire_table(axes, slice_map, sheet: ModelCostSheet, *,
                       codec=None, batch: int, seq: int,
                       compute_itemsize: int = 2) -> Dict[str, Any]:
    """Analytic ICI/DCN bytes-on-the-wire of one training step — the
    same currency as the COMM004 pass's ``collect_wire_table`` over the
    traced step (ring_wire_cost pricing, post-codec wire dtypes).

    DCN terms mirror the hierarchical overlap schedule exactly (per
    layer: bucket all-gather fwd, bucket reduce-scatter bwd, norm
    grad-sync, plus the final-norm all-axis psum) and reproduce the
    fake-2-slice flagship's four measured pins byte-for-byte.  ICI
    terms (dp grad psums, mp activation psums, the per-slice stages of
    the hierarchical collectives, pp microbatch permutes, ep dispatch
    all-to-alls) are first-order — no budget gates on them."""
    ax = _axis_degrees(axes)
    dp, sh, mp = (ax.get(k, 1) for k in ("dp", "sharding", "mp"))
    pp, sep, ep = (ax.get(k, 1) for k in ("pp", "sep", "ep"))
    S, K = _slice_shape(ax, slice_map)
    ndev = max(1, dp * sh * mp * pp * sep * ep)
    L = sheet.num_layers
    isz = compute_itemsize

    dcn: Dict[str, int] = {}
    ici: Dict[str, int] = {}

    def add(tab, key, cost):
        if cost > 0:
            tab[key] = tab.get(key, 0) + int(cost)

    # -- the ZeRO-3 bucketed stack: hier AG fwd / hier RS bwd per layer
    g_elems = sheet.layer_gathered_elems
    ways = max(1, sh * mp)
    local_elems = g_elems // ways
    local_bytes = local_elems * isz
    global_bytes = g_elems * isz
    for _ in range(L):
        if S > 1:
            if codec is None:
                add(dcn, "bucket_allgather",
                    ring_wire_cost("allgather", local_bytes, S))
                add(dcn, "bucket_reducescatter",
                    ring_wire_cost("reducescatter", global_bytes // K, S))
            else:
                w = _packed(codec, local_elems)
                add(dcn, "bucket_allgather",
                    ring_wire_cost("allgather", w, S))
                # _dcn_psum_scatter_coded: all_to_all of [S, packed(local)]
                add(dcn, "bucket_reducescatter",
                    ring_wire_cost("alltoall", S * w, S))
        if K > 1:
            add(ici, "bucket_allgather",
                ring_wire_cost("allgather", local_bytes * S, K))
            add(ici, "bucket_reducescatter",
                ring_wire_cost("reducescatter", global_bytes, K))

    # -- per-layer sync leaves (norm weights): fp32 grad psum over the
    #    data axes; coded path ships a packed int8 all-gather inter-slice
    sync_bytes = sheet.layer_sync_elems * 4
    for _ in range(L):
        if S > 1:
            if codec is None:
                add(dcn, "norm_sync",
                    ring_wire_cost("allreduce", sync_bytes, sh))
            else:
                add(dcn, "norm_sync",
                    ring_wire_cost("allgather",
                                   _packed(codec, sheet.layer_sync_elems),
                                   S))
                if K > 1:
                    add(ici, "norm_sync",
                        ring_wire_cost("allreduce", sync_bytes, K))
        elif sh > 1:
            add(ici, "norm_sync",
                ring_wire_cost("allreduce", sync_bytes, sh))
        if dp > 1:
            add(ici, "norm_sync_dp",
                ring_wire_cost("allreduce", sync_bytes, dp))

    # -- non-layer sync leaves (final norm): one fwd + one bwd psum
    #    over ALL mesh axes (uncoded even under the codec)
    misc = sheet.misc_sync_elems * 4
    stage = dcn if S > 1 else ici
    add(stage, "misc_sync", 2 * ring_wire_cost("allreduce", misc, ndev))

    # -- data-parallel grad psums (ICI): the bucketed grads reduce over
    #    dp after the sharding-axis scatter — first-order: the full
    #    bf16 grad set, mp-sharded
    if dp > 1:
        grads = sheet.params_total * isz // max(1, mp)
        add(ici, "dp_grad_psum", ring_wire_cost("allreduce", grads, dp))

    # -- tensor-parallel activation psums (ICI): o/down projections fwd
    #    + bwd per layer, plus the logits reduction
    if mp > 1:
        act = (batch // max(1, dp)) * (seq // max(1, sep)) \
            * sheet.hidden * isz
        add(ici, "mp_act_psum",
            (4 * L + 1) * ring_wire_cost("allreduce", act, mp))

    # -- pipeline microbatch boundary sends (ICI permutes, fwd + bwd)
    if pp > 1:
        act = (batch // max(1, dp)) * (seq // max(1, sep)) \
            * sheet.hidden * isz // max(1, mp)
        add(ici, "pp_permute",
            2 * (pp - 1) * ring_wire_cost("collectivepermute", act, pp))

    # -- sep (Ulysses) head/seq exchanges (ICI all-to-alls, fwd + bwd)
    if sep > 1:
        act = (batch // max(1, dp)) * seq * sheet.hidden * isz \
            // max(1, mp)
        add(ici, "sep_alltoall",
            4 * L * ring_wire_cost("alltoall", act, sep))

    # -- ep dispatch/return all-to-alls (ICI; engine-factored tokens:
    #    dropless ships balance*top_k rows, capacity ships cf*top_k)
    if ep > 1 and sheet.num_experts:
        tokens = (batch // max(1, dp)) * (seq // max(1, sep))
        payload = int(tokens * sheet.moe_eff_rows_per_token
                      * sheet.hidden)
        nbytes = (_packed(codec, payload) if codec is not None
                  else payload * isz)
        add(ici, "ep_dispatch",
            4 * L * ring_wire_cost("alltoall", nbytes, ep))

    return {"dcn": {"bytes": sum(dcn.values()), "by_part": dcn},
            "ici": {"bytes": sum(ici.values()), "by_part": ici}}


# ---------------------------------------------------------------------------
# the structural peak-HBM model
# ---------------------------------------------------------------------------

#: device bytes per parameter element when everything is resident:
#: fp32 master + AdamW m + v (12) + bf16 grads (2) + bf16 cast (2)
_STATE_BYTES_PER_PARAM = 16
_OPT_BYTES_PER_PARAM = 12

#: activation bytes kept per token per layer relative to the no-remat
#: baseline (input/output residuals + mlp activations + attn rows)
_ACT_KEEP_FACTOR = {"none": 1.0, "dots": 0.5, "names": 0.25,
                    "offload": 0.25, "full": 0.125}


def predict_peak_bytes(axes, sheet: ModelCostSheet, memory=None, *,
                       batch: int, seq: int, codec=None,
                       compute_itemsize: int = 2,
                       calibration_offset: int = 0) -> int:
    """Structural per-device peak-HBM estimate of one train step —
    params at rest + optimizer state + grads + bf16 cast sharded over
    the weight ways, activations over the data ways, remat keep-factor
    applied.  First-order by design: absolute accuracy comes from
    one-point calibration (``calibration_offset`` = measured − model on
    ONE compiled record; the structural DELTAS order the rest — the
    MEM001 gate stays the ground truth)."""
    ax = _axis_degrees(axes)
    dp, sh, mp = (ax.get(k, 1) for k in ("dp", "sharding", "mp"))
    pp, sep, ep = (ax.get(k, 1) for k in ("pp", "sep", "ep"))
    remat = getattr(memory, "remat", "none") if memory else "none"
    isz = compute_itemsize
    L = sheet.num_layers
    layers_here = max(1, L // max(1, pp))

    ways = max(1, sh * mp)
    sharded = (layers_here * sheet.layer_gathered_elems
               + sheet.embed_elems + sheet.head_elems) // ways
    sharded += layers_here * sheet.layer_expert_elems \
        // max(1, ep * mp)
    replicated = layers_here * sheet.layer_sync_elems \
        + sheet.misc_sync_elems

    state = _STATE_BYTES_PER_PARAM
    if memory is not None \
            and getattr(memory, "optimizer_residency", "device") == "host":
        state -= _OPT_BYTES_PER_PARAM
    params_bytes = (sharded + replicated) * state

    tokens = (batch // max(1, dp)) * (seq // max(1, sep))
    act_tok_layer = (4 * sheet.hidden + 2 * sheet.intermediate
                     + sheet.num_heads * (seq // max(1, sep))) \
        * isz // max(1, mp)
    if memory is not None and hasattr(memory, "act_keep_factor"):
        keep = memory.act_keep_factor()  # the policy-semantics owner
    else:
        keep = _ACT_KEEP_FACTOR.get(remat, 1.0)
        if memory is not None and getattr(memory, "activation_offload",
                                          False):
            keep *= 0.5
    acts = int(tokens * layers_here * act_tok_layer * keep)
    logits = tokens * sheet.vocab * 4 // max(1, mp)

    # gathered working set: one layer's full bucket (+ codec scratch)
    gathered = sheet.layer_gathered_elems * isz // max(1, mp)
    if codec is not None:
        gathered += _packed(codec, sheet.layer_gathered_elems // ways)

    return int(params_bytes + acts + logits + gathered
               + calibration_offset)


# ---------------------------------------------------------------------------
# the step-time estimate
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepTimeEstimate:
    """One point's analytic step time: max-of-rooflines compute/HBM +
    exposed collective time, with the wire/peak predictions the budget
    pre-filter reads.  ``fits`` is the PREDICTED budget verdict (None
    when no budgets were declared) — the compiled MEM001/COMM004 gates
    remain the ground truth."""

    label: str
    total_s: float
    compute_s: float
    hbm_s: float
    ici_s: float
    dcn_s: float
    exposed_comm_s: float
    peak_bytes: int
    dcn_wire_bytes: int
    ici_wire_bytes: int
    fits: Optional[bool] = None
    breakdown: Optional[Dict[str, Any]] = None

    def to_json(self) -> Dict[str, Any]:
        return {"label": self.label, "total_s": self.total_s,
                "compute_s": self.compute_s, "hbm_s": self.hbm_s,
                "ici_s": self.ici_s, "dcn_s": self.dcn_s,
                "exposed_comm_s": self.exposed_comm_s,
                "peak_bytes": self.peak_bytes,
                "dcn_wire_bytes": self.dcn_wire_bytes,
                "ici_wire_bytes": self.ici_wire_bytes,
                "fits": self.fits}


def estimate_step_time(axes, slice_map, sheet: ModelCostSheet, *,
                       memory=None, codec=None, overlap=None,
                       batch: int, seq: int, chip="v5e",
                       hbm_budget: Optional[int] = None,
                       dcn_budget: Optional[int] = None,
                       calibration_offset: int = 0,
                       label: str = "", ) -> StepTimeEstimate:
    """The analytic estimate of one (partitioning, memory, overlap,
    codec) point: per-layer compute FLOPs vs HBM bytes (max-of
    rooflines, remat recompute folded in) + per-tactic ICI/DCN
    collective time from the ring cost model and the codec's wire-dtype
    arithmetic, with overlap modeled as exposed-comm = max(0, comm −
    hideable compute)."""
    spec = chip_spec(chip)
    ax = _axis_degrees(axes)
    ndev = max(1, math.prod(ax.values()))
    remat = getattr(memory, "remat", "none") if memory else "none"
    recompute = (memory.recompute_fwd_passes()
                 if memory is not None
                 and hasattr(memory, "recompute_fwd_passes")
                 else REMAT_RECOMPUTE_FACTOR.get(remat, 0.0))

    flops_dev = sheet.step_flops(batch, seq, recompute) / ndev
    compute_s = flops_dev / spec.peak_bf16_flops

    # HBM traffic: weights touched once per pass (fwd + bwd + update +
    # recompute), activations written fwd / read bwd
    ax_peak = predict_peak_bytes(
        axes, sheet, memory, batch=batch, seq=seq, codec=codec,
        calibration_offset=calibration_offset)
    param_local = sheet.params_total * 2 // max(
        1, ax.get("sharding", 1) * ax.get("mp", 1))
    hbm_bytes = param_local * (3.0 + recompute) \
        + sheet.params_total * _STATE_BYTES_PER_PARAM / max(
            1, ax.get("sharding", 1) * ax.get("mp", 1)) \
        + 2.0 * ax_peak
    hbm_s = hbm_bytes / spec.hbm_bytes_per_s

    wire = predict_wire_table(axes, slice_map, sheet, codec=codec,
                              batch=batch, seq=seq)
    ici_b = wire["ici"]["bytes"]
    dcn_b = wire["dcn"]["bytes"]
    ici_s = ici_b / spec.ici_bytes_per_s
    dcn_s = dcn_b / spec.dcn_bytes_per_s

    # overlap: prefetch/bucketed schedules hide collectives behind
    # compute; exposed = what compute cannot cover
    if overlap is None:
        hides = True
    elif hasattr(overlap, "hides_collectives"):
        hides = overlap.hides_collectives()
    else:
        hides = bool(getattr(overlap, "prefetch", True))
    hideable = compute_s if hides else 0.0
    exposed = max(0.0, ici_s + dcn_s - hideable)
    total = max(compute_s, hbm_s) + exposed

    fits: Optional[bool] = None
    if hbm_budget is not None or dcn_budget is not None:
        fits = True
        if hbm_budget is not None and ax_peak > hbm_budget:
            fits = False
        if dcn_budget is not None and dcn_b > dcn_budget:
            fits = False

    return StepTimeEstimate(
        label=label, total_s=total, compute_s=compute_s, hbm_s=hbm_s,
        ici_s=ici_s, dcn_s=dcn_s, exposed_comm_s=exposed,
        peak_bytes=int(ax_peak), dcn_wire_bytes=int(dcn_b),
        ici_wire_bytes=int(ici_b), fits=fits,
        breakdown={"wire": wire, "ndev": ndev,
                   "recompute_factor": recompute})


def estimate_joint_config(jc, sheet: ModelCostSheet, *, batch: int,
                          seq: int, chip="v5e",
                          hbm_budget: Optional[int] = None,
                          dcn_budget: Optional[int] = None,
                          calibration_offset: int = 0
                          ) -> StepTimeEstimate:
    """Estimate one ``JointScheduleConfig`` lattice point (partition x
    memory x overlap/codec)."""
    codec = getattr(jc.overlap, "codec", None)
    return estimate_step_time(
        jc.partition.axes, jc.partition.slice_map, sheet,
        memory=jc.memory, codec=codec, overlap=jc.overlap,
        batch=batch, seq=seq, chip=chip, hbm_budget=hbm_budget,
        dcn_budget=dcn_budget, calibration_offset=calibration_offset,
        label=jc.label())


def joint_estimator(sheet: ModelCostSheet, *, batch: int, seq: int,
                    chip="v5e", hbm_budget: Optional[int] = None,
                    dcn_budget: Optional[int] = None,
                    calibration_offset: int = 0
                    ) -> Callable[[Any], StepTimeEstimate]:
    """Estimator factory for ``tune_schedule_config(predict=True)``:
    a callable JointScheduleConfig -> StepTimeEstimate closed over the
    model sheet, step shape, chip and (optionally) the budgets used as
    the predicted-feasibility pre-filter."""
    def estimate(jc) -> StepTimeEstimate:
        return estimate_joint_config(
            jc, sheet, batch=batch, seq=seq, chip=chip,
            hbm_budget=hbm_budget, dcn_budget=dcn_budget,
            calibration_offset=calibration_offset)

    return estimate


def calibration_offset_from(record: Dict[str, Any], jc,
                            sheet: ModelCostSheet, *, batch: int,
                            seq: int) -> int:
    """One-point peak calibration: measured − structural on a single
    compiled record (the cheapest anchor the walk already paid for).
    Apply the returned offset to every subsequent prediction."""
    codec = getattr(jc.overlap, "codec", None)
    structural = predict_peak_bytes(
        jc.partition.axes, sheet, jc.memory, batch=batch, seq=seq,
        codec=codec)
    return int(record["peak_bytes"]) - structural


# ---------------------------------------------------------------------------
# the enumerated partitioning search
# ---------------------------------------------------------------------------


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _mesh_shape(mesh_shape) -> Tuple[int, int]:
    """(num_slices, devices_per_slice) from an int (single slice), a
    (slices, per_slice) tuple, or a dict with those keys."""
    if isinstance(mesh_shape, int):
        return 1, int(mesh_shape)
    if hasattr(mesh_shape, "get"):
        return (int(mesh_shape.get("num_slices", 1)),
                int(mesh_shape.get("devices_per_slice")))
    s, per = mesh_shape
    return int(s), int(per)


def enumerate_partitionings(mesh_shape, model, *, batch: int = 8,
                            seq: int = 4096, chip="v5p", memory=None,
                            hbm_fraction: float = 0.9,
                            max_points: Optional[int] = None
                            ) -> Tuple:
    """Candidate tactic compositions over a pod-shaped mesh, straight
    from the named-tactic vocabulary (pp / dp / sharding3 / sep / tp /
    ep), divisibility- and HBM-feasibility-pruned.

    ``mesh_shape`` — total device count, or ``(num_slices,
    devices_per_slice)`` for a multi-slice pod (the slice-spanning axis
    is ``sharding``, matching the repo's quantize-across-DCN
    convention: points whose sharding degree cannot host the slice
    count are dropped).  ``model`` — a LlamaConfig or ModelCostSheet.

    Pruning: every tactic degree must divide its model dimension
    (pp | layers, mp | hidden/intermediate/kv-width/heads, sep | seq
    and heads, ep | num_experts, sharding | hidden, dp | batch) and the
    structural peak-HBM estimate must fit ``hbm_fraction`` of the
    chip's capacity.  Returns PartitionPoints (cheapest enumeration
    order is NOT meaningful — rank with ``rank_partitionings``)."""
    from .schedule import PartitionPoint

    sheet = model if isinstance(model, ModelCostSheet) \
        else llama_cost_sheet(getattr(model, "config", model))
    S, per_slice = _mesh_shape(mesh_shape)
    total = S * per_slice
    spec = chip_spec(chip)
    budget = int(spec.hbm_bytes * hbm_fraction)

    def ok_mp(mp):
        kvw = sheet.num_kv_heads * sheet.head_dim
        return (sheet.hidden % mp == 0 and sheet.intermediate % mp == 0
                and kvw % mp == 0 and sheet.num_heads % mp == 0)

    points = []
    for pp in _divisors(math.gcd(total, sheet.num_layers)):
        for mp in (m for m in _divisors(total // pp) if ok_mp(m)):
            for sep in (s for s in _divisors(total // (pp * mp))
                        if seq % s == 0 and sheet.num_heads % s == 0
                        and s <= seq):
                ep_opts = [e for e in _divisors(total // (pp * mp * sep))
                           if sheet.num_experts and
                           sheet.num_experts % e == 0] or [1]
                for ep in ep_opts:
                    rest = total // (pp * mp * sep * ep)
                    for sh in (d for d in _divisors(rest)
                               if sheet.hidden % d == 0):
                        dp = rest // sh
                        if batch % dp != 0:
                            continue
                        # multi-slice pods span slices on sharding
                        if S > 1 and sh % S != 0:
                            continue
                        slice_map = None
                        if S > 1:
                            k = sh // S
                            slice_map = tuple(i // k for i in range(sh))
                        axes = tuple(
                            (a, n) for a, n in
                            (("pp", pp), ("dp", dp), ("sharding", sh),
                             ("sep", sep), ("ep", ep), ("mp", mp)))
                        name = "auto"   # label() carries the degrees
                        peak = predict_peak_bytes(
                            axes, sheet, memory, batch=batch, seq=seq)
                        if peak > budget:
                            continue
                        points.append(PartitionPoint(
                            name, axes, slice_map=slice_map))
    if max_points is not None:
        points = points[:max_points]
    return tuple(points)


def rank_partitionings(points: Sequence, sheet: ModelCostSheet, *,
                       batch: int = 8, seq: int = 4096, chip="v5p",
                       memory=None, codec=None
                       ) -> List[Tuple[StepTimeEstimate, Any]]:
    """Order candidate PartitionPoints by the analytic estimate,
    cheapest first.  Returns [(estimate, point), ...] — feed the top-K
    to the compiled walk (``tune_schedule_config(predict=True)``)."""
    sheet = sheet if isinstance(sheet, ModelCostSheet) \
        else llama_cost_sheet(getattr(sheet, "config", sheet))
    ranked = []
    for pt in points:
        est = estimate_step_time(
            pt.axes, pt.slice_map, sheet, memory=memory, codec=codec,
            batch=batch, seq=seq, chip=chip, label=pt.label())
        ranked.append((est, pt))
    ranked.sort(key=lambda t: t[0].total_s)
    return ranked
