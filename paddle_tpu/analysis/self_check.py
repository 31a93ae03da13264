"""Graph Doctor self-check: the doctor proving it can still detect.

Three layers, all required green:
1. every seeded-bug fixture (fixtures.py) triggers EXACTLY its intended
   finding code — true-positive coverage per pass;
2. the clean flagship entry points (build_train_step unmasked-bf16 in
   both accum regimes, llama fwd/bwd, the serving step) report
   ZERO findings — false-positive coverage;
3. every standing exemption entry still matches a live suppressed
   finding — stale exemptions rot loudly (the masked grad-accum fp32
   carry must still be detected AND suppressed by
   EX-DT003-masked-grad-accum).

Wired into ``python -m paddle_tpu.analysis --self-check``, the
``doctor_self_check`` leg of ``bench.py --smoke``, and
tests/test_analysis_passes.py.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

# jaxpr/lowering-level passes (no XLA compile) — used for the fast clean
# sweeps; the accum train step and the serving step also run the
# compiled HLO checks.
FAST_PASSES = ("collective_order", "dtype_promotion", "donation")
ALL_PASSES = None

# The sweeps run DEBUG-shaped models (~200 KB of params), far below the
# donation pass's production default of 1 MB — at the default the gate
# would be VACUOUS (deleting donate_argnums from build_train_step would
# still pass).  Lower the bar to the debug param scale so the sweeps
# actually verify the donation contracts; the liveness test
# (tests/test_analysis_passes.py) asserts an undonated params dict of
# this size trips DON001 at this threshold.
DONATION_MIN_BYTES = 4 << 10

# Round-10 capacity contracts for the DEBUG-shaped flagship (see the
# step-2 comment below and BASELINE.md round-10): peak ~2.24 MB ->
# budget 3 MB; the memory-engine step streams the two fp32 moment
# groups (~1 MB each) in and out once per step (~4.2 MB of memory-kind
# transfers) -> streaming budget 6 MB.  Snug on purpose: one extra
# full-group round trip (+2 MB) or an un-donated params copy (+1 MB)
# fails the doctor.
FLAGSHIP_HBM_BUDGET = 3 << 20
FLAGSHIP_STREAM_BUDGET = 6 << 20

# Round-15 wire contract for the debug-shaped flagship on the fake
# 2-slice hierarchical mesh (dp1 x sharding4[2 slices] x mp2) with the
# DCN codec ON: the quantized schedule measures ~19.5 KB of post-codec
# DCN bytes per step (int8 payload + bf16 scale sidecars; the
# unquantized schedule moves ~56 KB).  24 KB pins it with ~20%
# headroom — silently dropping the codec (or re-inflating a DCN hop to
# a float dtype) blows COMM004 here, not a multislice TPU session.
FLAGSHIP_DCN_WIRE_BUDGET = 24 << 10
FLAGSHIP_SLICE_MAP = (0, 0, 1, 1)

# Round-18 wire contract for the debug-shaped EP MoE train step on the
# fake-2-slice dp1 x sharding2 x ep4 mesh (ep spans the slices) with
# the block-64 DCN codec ON: the quantized dispatch/combine schedule
# measures ~1.9 KB of post-codec DCN bytes per step (int8 token
# payloads + bf16 scale sidecars on the all-to-alls, plus the tiny
# uncoded fp32 gate-grad psum) vs ~4.6 KB uncoded — the dispatch
# all-to-alls alone shrink 3.88x (the >= 3x acceptance bar).  2.25 KB
# pins it with ~20% headroom: silently dropping the codec on the EP
# dispatch blows COMM004 here, not a multislice TPU session.
MOE_DCN_WIRE_BUDGET = 2304
MOE_SLICE_MAP = (0, 0, 1, 1)

# Round-20 wire contract for the DROPLESS EP MoE train step (sorted
# ragged dispatch + grouped matmul, no capacity buffer) on the same
# fake-2-slice dp1 x sharding2 x ep4 mesh with the block-64 DCN codec
# ON: the quantized dispatch/combine schedule measures ~2.4 KB of
# post-codec DCN bytes per step (the int32 count exchange stays uncoded
# by design — the control plane is bit-exact — while the token payload
# windows ship int8 + bf16 scale sidecars; the tiny fp32 gate-grad psum
# rides uncoded) vs ~6.9 KB uncoded, the dispatch all-to-alls alone
# shrinking 3.85x (the >= 3x acceptance bar).  3 KB pins it with ~20%
# headroom: silently dropping the codec on the payload leg blows
# COMM004 here, not a multislice TPU session.
MOE_DROPLESS_DCN_WIRE_BUDGET = 3072

# Round-17 probe-fusion contract (HEALTH001) for the health-probed
# flagship step: the probed entry's compiled peak may exceed the
# UNPROBED entry's measured peak by at most this allowance.  Measured
# delta on the installed toolchain (jax 0.9, XLA:CPU): 378 KB on the
# accum1 entry at 4 x 16 tokens — the no-op guard's select keeps one
# param-tree-sized temporary (427 KB in fp32) alive, which shows only
# where the optimizer phase is the program's peak (at 4 x 64 tokens the
# delta is 350 B).  448 KB pins it with ~18% headroom while a further
# tree-sized probe regression (fp32 grad concat, ~560 KB more at debug
# shapes) fails loudly.
HEALTH_PROBE_OVERHEAD = 448 << 10

# Round-11 capacity contract for the debug-shaped UNIFIED serving step
# (radix prefix cache + chunked prefill + speculative verify in one
# ragged launch): the self-check engine (2 slots, 9 pages, chunk 8)
# compiles to ~0.72 MB peak; 1 MB pins it with ~0.28 MB headroom — a
# materialized fp32 logits buffer over the packed rows or an un-donated
# pool copy fails MEM001 here, and the seeded MEM001[prefill_chunk]
# fixture proves a prefill_token_budget bump (48 -> ~1.13 MB) blows
# this same decode-sized contract.
SERVING_HBM_BUDGET = 1 << 20


def _memory_target(donation_opts):
    """The memory-engine flagship sweep: MemoryConfig(names, host) —
    named-saveable remat + host-offloaded bucket-streamed AdamW — under
    the peak + streaming budgets, donation, and the dtype audit."""
    from .core import check
    from paddle_tpu.models import build_train_step
    from paddle_tpu.models.llama import llama_decay_mask
    from paddle_tpu.parallel.memory import (MemoryConfig,
                                            init_offloaded_state)

    cfg, model, opt, params, ids, labels = _flagship()
    mask_all = llama_decay_mask(model)
    mc = MemoryConfig(remat="names", optimizer_residency="host",
                      stream_bucket_bytes=256 << 10)
    step = build_train_step(model, opt, compute_dtype=jnp.bfloat16,
                            memory=mc)
    st = init_offloaded_state(opt, params, decay_mask=mask_all,
                              bucket_bytes=mc.stream_bucket_bytes)
    return check(
        step, params, st, 0, 1e-4, ids, labels,
        passes=["dtype_promotion", "donation", "memory_budget"],
        options={**donation_opts,
                 "memory_budget":
                     {"hbm_bytes": FLAGSHIP_HBM_BUDGET,
                      "host_transfer_bytes": FLAGSHIP_STREAM_BUDGET}},
        declared_dtype=jnp.bfloat16,
        target="memory_train_step[names,host]")


def _flagship():
    """Tiny flagship bundle shared by the clean sweeps (debug shapes —
    the jaxprs have the same STRUCTURE as the bench config; only dims
    shrink)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    state = paddle.get_rng_state()
    paddle.seed(20260803)
    cfg = LlamaConfig.debug(vocab=128, hidden=64, layers=2, heads=4,
                            kv_heads=2, inter=128, max_pos=64)
    model = LlamaForCausalLM(cfg)
    paddle.set_rng_state(state)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    params = {k: jnp.asarray(v) for k, v in model.functional_state().items()}
    rng = np.random.default_rng(3)
    ids = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    return cfg, model, opt, params, ids, labels


def _clean_targets():
    """Yield (name, report) for the flagship clean sweeps."""
    from .core import check
    from paddle_tpu.models import build_train_step
    from paddle_tpu.models.llama import llama_decay_mask

    cfg, model, opt, params, ids, labels = _flagship()
    mask_all = llama_decay_mask(model)

    def deep(t):
        return jax.tree_util.tree_map(jnp.copy, t)

    # 1. single-batch bf16 step (fast passes — structure is a subset of
    # the accum step checked in full below)
    donation = {"donation": {"min_bytes": DONATION_MIN_BYTES}}
    # declared_dtype is pinned, not inferred: a regression that upcasts
    # EVERY matmul to fp32 also removes the bf16 dots the inference
    # keys on, and the audit would silently stand down exactly when it
    # is needed most (the sweeps KNOW compute_dtype=bf16)
    step1 = build_train_step(model, opt, compute_dtype=jnp.bfloat16)
    yield "build_train_step[bf16]", check(
        step1, deep(params), opt.init_state(deep(params)), 0, 1e-4, ids,
        labels, passes=list(FAST_PASSES), options=donation,
        declared_dtype=jnp.bfloat16, target="build_train_step[bf16]")

    # 2. grad-accum bf16-carry step with the fused flat optimizer — the
    # headline training config; full pass suite incl. compiled HLO.
    # The collective budget here is the single-chip contract: ZERO
    # collectives of any kind (an accidental psum in an eager helper
    # fails the doctor, not the next TPU session).  Round-10 adds the
    # capacity contract: the debug-shaped flagship compiles to ~2.24 MB
    # peak (arguments + outputs + temporaries − donation aliasing);
    # the declared FLAGSHIP_HBM_BUDGET pins it with ~0.8 MB headroom,
    # so an un-donated params copy (+1 MB) or a materialized fp32
    # logits buffer fails MEM001 here, not a TPU session.
    zero_budget = {k: {"count": 0} for k in
                   ("allreduce", "allgather", "reducescatter",
                    "collectivepermute", "alltoall")}
    step4 = build_train_step(model, opt, compute_dtype=jnp.bfloat16,
                             accum_steps=4)
    yield "build_train_step[bf16,accum4]", check(
        step4, deep(params),
        opt.init_flat_state(deep(params), decay_mask=mask_all), 0, 1e-4,
        ids.reshape(4, 1, 16), labels.reshape(4, 1, 16),
        passes=ALL_PASSES,
        options={**donation, "collective_budget": zero_budget,
                 "memory_budget": {"hbm_bytes": FLAGSHIP_HBM_BUDGET}},
        declared_dtype=jnp.bfloat16,
        target="build_train_step[bf16,accum4]")

    # 2c. round-17: the health-probed flagship step — the probe-fusion
    # contract pinned against the UNPROBED accum1 entry's peak measured
    # in-process (HEALTH001), zero added collectives on the single-chip
    # probe (HEALTH002: every baseline kind is 0), plus donation + the
    # dtype audit over the probed program.  The probed entry runs with
    # the production all-open gates array so the audited program IS the
    # one the guardian drives.  Memoized per backend like the sharding
    # section: the target compiles the flagship TWICE (baseline +
    # probed) and is reached from self_check, the doctor smoke leg and
    # the analysis test suite in one tier-1 process.
    key = (jax.default_backend(), len(jax.devices()))
    rep = _HEALTH_MEMO.get(key)
    if rep is None:
        from .core import AnalysisContext
        from .passes.health_probe import compiled_peak_bytes
        from paddle_tpu.distributed.health import (HealthConfig,
                                                   default_gates)

        base_peak = compiled_peak_bytes(AnalysisContext(
            step1, (deep(params), opt.init_state(deep(params)), 0, 1e-4,
                    ids, labels), {}))
        hstep = build_train_step(model, opt, compute_dtype=jnp.bfloat16,
                                 health=HealthConfig())
        rep = check(
            hstep, deep(params), opt.init_state(deep(params)), 0, 1e-4,
            ids, labels,
            kwargs={"health_gates": jnp.asarray(default_gates())},
            passes=["health_probe", "dtype_promotion", "donation"],
            options={**donation,
                     "health_probe": {
                         "baseline_peak_bytes": base_peak,
                         "probe_overhead_bytes": HEALTH_PROBE_OVERHEAD,
                         "baseline_collectives": {}}},
            declared_dtype=jnp.bfloat16,
            target="health_probed_step[bf16]")
        if rep.ok:          # never memoize a one-off compile hiccup red
            _HEALTH_MEMO[key] = rep
    yield "health_probed_step[bf16]", rep

    # 2a. the HBM memory engine's train step (round-10): named-policy
    # remat + host-offloaded bucket-streamed AdamW, audited under BOTH
    # capacity contracts — the peak budget and the streaming budget
    # (a regression to monolithic full-state round trips fails MEM002)
    # — plus donation (host-resident state must still donate cleanly)
    # and the dtype audit
    yield "memory_train_step[names,host]", _memory_target(donation)

    # 2b. the overlap-engine train step on the 8-virtual-device hybrid
    # mesh (dp2 x sharding2 x mp2): the engine's collective schedule
    # must stay within its declared per-step budget AND every manual
    # collective must be engine-attributed (COMM002) — self-skips on
    # hosts without the virtual mesh
    if len(jax.devices()) >= 8:
        for name, rep in _overlap_target():
            yield name, rep
        # 2d. round-18: the EP MoE train step under its pinned
        # post-codec DCN wire budget (COMM004) on the fake-2-slice
        # dp1 x sharding2 x ep4 mesh
        for name, rep in _moe_ep_target():
            yield name, rep

        # 2e. round-20: the DROPLESS EP train step under its own pinned
        # post-codec DCN wire budget (COMM004) on the same mesh
        for name, rep in _moe_ep_dropless_target():
            yield name, rep

    # 3. llama forward/backward in isolation (no optimizer): params are
    # read-only here, so they are declared persistent for the donation
    # audit
    from paddle_tpu.autograd import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.llama import _gold_logit

    def fwd_bwd(p, ids_, labels_):
        def loss(pp):
            cast = {k: (v.astype(jnp.bfloat16)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in pp.items()}
            with no_grad():
                logits = model.functional_call(cast, Tensor(ids_))
            lv = logits._value
            lse = jax.scipy.special.logsumexp(lv.astype(jnp.float32),
                                              axis=-1)
            return (lse - _gold_logit(lv, labels_)).mean()
        return jax.value_and_grad(loss)(p)

    yield "llama_fwd_bwd[bf16]", check(
        jax.jit(fwd_bwd), params, ids, labels, passes=list(FAST_PASSES),
        options={"donation": {"persistent": (0,),
                              "min_bytes": DONATION_MIN_BYTES}},
        declared_dtype=jnp.bfloat16, target="llama_fwd_bwd[bf16]")

    # 4. the serving engine's step (chunked prefill + speculative
    # verify rows mixed into the decode launch) — gated like the
    # training flagship: ZERO collectives on the single-chip serving
    # path (COMM001) and the pinned peak-HBM contract (MEM001), plus
    # the full pass suite over the ragged program
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    ueng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                    num_pages=9, page_size=16,
                                    max_seq_len=64,
                                    prefill_token_budget=8)
    ufn, uargs, ukwargs, uoptions = ueng.analysis_entry()
    zero_budget = {k: {"count": 0} for k in
                   ("allreduce", "allgather", "reducescatter",
                    "collectivepermute", "alltoall")}
    yield "serving_unified_step", check(
        ufn, *uargs, kwargs=ukwargs, passes=ALL_PASSES,
        options={**uoptions, "collective_budget": zero_budget,
                 "memory_budget": {"hbm_bytes": SERVING_HBM_BUDGET}},
        target="serving_unified_step")


def _moe_ep_flagship():
    """Debug-shaped EP MoE bundle shared by the EP clean sweep, the
    sharding section and the bench moe trace (fake-2-slice
    dp1 x sharding2 x ep4 mesh; shapes shrink, structure doesn't)."""
    from jax.sharding import Mesh

    from paddle_tpu.parallel.expert import MoEEPConfig, init_moe_ep_params

    cfg = MoEEPConfig(d_model=16, d_hidden=32, num_expert=8, top_k=2,
                      capacity_factor=2.0, aux_weight=0.01)
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        1, 2, 4), ("dp", "sharding", "ep"))
    params = init_moe_ep_params(cfg, mesh)
    rng = np.random.default_rng(7)
    x2d = jnp.asarray(rng.standard_normal((64, 16), np.float32))
    tgt = jnp.asarray(rng.standard_normal((64, 16), np.float32))
    return cfg, mesh, params, x2d, tgt


def _moe_ep_target():
    """Round-18 EP clean sweep: the expert-parallel MoE train step on
    the fake-2-slice mesh with the DCN codec ON, pinned to its
    post-codec wire budget (COMM004 — a silently-dropped codec on the
    dispatch all-to-alls fails here) with every manual collective
    engine-attributed (COMM002)."""
    from .core import check
    from paddle_tpu.parallel.codec import CollectiveCodec
    from paddle_tpu.parallel.expert import build_moe_ep_train_step
    from paddle_tpu.parallel.overlap import OverlapConfig

    cfg, mesh, params, x2d, tgt = _moe_ep_flagship()
    oc = OverlapConfig(hierarchical="on", slice_map=MOE_SLICE_MAP,
                       codec=CollectiveCodec(block=64))
    step = build_moe_ep_train_step(cfg, mesh, oc=oc)
    yield "moe_ep_train_step[hier2slice,codec]", check(
        step, params, x2d, tgt,
        passes=["collective_budget"],
        options={"collective_budget": {
            "overlap_active": True,
            "wire": {"dcn_axes": {"ep": list(MOE_SLICE_MAP)},
                     "dcn_bytes": MOE_DCN_WIRE_BUDGET}}},
        target="moe_ep_train_step[hier2slice,codec]")


def _moe_ep_dropless_target():
    """Round-20 dropless clean sweep: the sorted-ragged-dispatch EP
    train step on the fake-2-slice mesh with the DCN codec ON, pinned
    to its own measured post-codec wire budget (COMM004 — dropping the
    codec on the payload windows fails here; the uncoded int32 count
    exchange is part of the budget by design) with every manual
    collective engine-attributed (COMM002)."""
    from .core import check
    from paddle_tpu.parallel.codec import CollectiveCodec
    from paddle_tpu.parallel.expert import build_moe_ep_dropless_train_step
    from paddle_tpu.parallel.overlap import OverlapConfig

    cfg, mesh, params, x2d, tgt = _moe_ep_flagship()
    oc = OverlapConfig(hierarchical="on", slice_map=MOE_SLICE_MAP,
                       codec=CollectiveCodec(block=64))
    step = build_moe_ep_dropless_train_step(cfg, mesh, oc=oc)
    yield "moe_ep_dropless_train_step[hier2slice,codec]", check(
        step, params, x2d, tgt,
        passes=["collective_budget"],
        options={"collective_budget": {
            "overlap_active": True,
            "wire": {"dcn_axes": {"ep": list(MOE_SLICE_MAP)},
                     "dcn_bytes": MOE_DROPLESS_DCN_WIRE_BUDGET}}},
        target="moe_ep_dropless_train_step[hier2slice,codec]")


def _overlap_target():
    """Clean sweep over the communication-overlap engine's train step
    (parallel/overlap.py via build_train_step(overlap=...)): donation
    (the double-buffered gather carry must not defeat DON001's
    contract), collective order, and the collective budget with
    overlap_active — run on the dp2 x sharding2 x mp2 virtual mesh."""
    from jax.sharding import Mesh

    from .core import check
    from paddle_tpu.models import build_train_step
    from paddle_tpu.models.llama import apply_llama_sharding
    from paddle_tpu.parallel.overlap import OverlapConfig

    cfg, model, opt, params, ids, labels = _flagship()
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        2, 2, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    step = build_train_step(model, opt, mesh=mesh,
                            compute_dtype=jnp.bfloat16,
                            overlap=OverlapConfig())
    params = {k: jnp.asarray(v)
              for k, v in model.functional_state().items()}
    # per-step budget for the L=2 debug stack on this mesh, set snugly
    # above the engine's measured schedule (fwd gathers + bwd
    # reduce-scatters + TP/batch reductions + boundary reshards); a
    # per-leaf-collective regression (9 leaves x L x fwd/bwd) blows
    # straight through it
    budget = {"overlap_active": True,
              "allreduce": {"count": 48},
              "allgather": {"count": 24},
              "reducescatter": {"count": 12}}
    yield "overlap_train_step[dp2,sharding2,mp2]", check(
        step, params, opt.init_state(params), 0, 1e-4, ids, labels,
        passes=["collective_budget", "collective_order", "donation"],
        options={"donation": {"min_bytes": DONATION_MIN_BYTES},
                 "collective_budget": budget},
        declared_dtype=jnp.bfloat16,
        target="overlap_train_step[dp2,sharding2,mp2]")

    # round-15: the hierarchical fake-2-slice step with the quantized-
    # DCN codec ON, pinned to its post-codec wire budget (COMM004) —
    # and every coded collective still engine-attributed (COMM002)
    from paddle_tpu.parallel.codec import CollectiveCodec

    hmesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        1, 4, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, hmesh)
    hoc = OverlapConfig(hierarchical="on",
                        slice_map=FLAGSHIP_SLICE_MAP,
                        codec=CollectiveCodec())
    hstep = build_train_step(model, opt, mesh=hmesh,
                             compute_dtype=jnp.bfloat16, overlap=hoc)
    hparams = {k: jnp.asarray(v)
               for k, v in model.functional_state().items()}
    yield "overlap_train_step[hier2slice,codec]", check(
        hstep, hparams, opt.init_state(hparams), 0, 1e-4, ids, labels,
        passes=["collective_budget"],
        options={"collective_budget": {
            "overlap_active": True,
            "wire": {"dcn_axes":
                     {"sharding": list(FLAGSHIP_SLICE_MAP)},
                     "dcn_bytes": FLAGSHIP_DCN_WIRE_BUDGET}}},
        declared_dtype=jnp.bfloat16,
        target="overlap_train_step[hier2slice,codec]")


# ---------------------------------------------------------------------------
# round-14: the Sharding Doctor section (cross-stack partition
# consistency).  Each flagship stack's entry is audited for
# GSPMD-inserted resharding (SHARD001) against a DECLARED allowance,
# its canonical SpecLayout table for replication waste / shard padding
# (SHARD002/004), the flat-update entries for the 2004.13336
# cross-replica pin (SHARD005), and the stacks' tables against each
# other (SHARD003 — must be EMPTY on the llama flagship tree; this
# table is the artifact the unified-partitioning refactor consumes).
# ---------------------------------------------------------------------------

# SHARD001 allowances for the debug-shaped flagship entries, measured
# on the container toolchain and pinned as COMM001-style upper bounds.
# Round-14 pinned the flat accum-4 bill at 23 all-to-alls / 148
# collective-permutes / 75 all-gathers — almost entirely the fused
# flat-optimizer boundary: every leaf's row-major flatten (and the
# slice-back) was a GSPMD reshard against the at-rest placement.
# Round-19's unified schedule derives the flat-update wire format FROM
# the at-rest tactics (parallel/schedule.FlatUpdateLayout: shard-major
# flatten = a LOCAL relayout), so the accum-4 entry now compiles to
# 5 / 14 / 57 — the new, smaller bill is PINNED here; any regression
# above it fires the doctor.  (An explicit at-rest pin on the merged
# grad tree was tried on top and rejected: −3 collective-permutes for
# +17 all-reduces.)
SHARDING_RESHARD_ALLOWANCES = {
    "gspmd[accum1]": {"alltoall": 6, "collectivepermute": 0,
                      "allgather": 33},
    "gspmd[accum4]": {"alltoall": 5, "collectivepermute": 14,
                      "allgather": 57},
    # overlap: 2 manual bucket gathers; the rest is the GSPMD boundary
    # (embedding/norm/head/loss outside the manual region)
    "overlap": {"alltoall": 6, "collectivepermute": 0, "allgather": 7},
    "hybrid[gpipe]": {"alltoall": 4, "collectivepermute": 8,
                      "allgather": 3},
    "hybrid[1F1B]": {"alltoall": 0, "collectivepermute": 2,
                     "allgather": 3},
}

# SHARD002 floor for the debug-shaped tables (production default is
# 1 MB; debug leaves top out at ~64 KB) — at this floor an accidentally
# replicated projection leaf (16 KB) FAILS the sweep
SHARDING_REPLICATED_MIN_BYTES = 4 << 10

# params are replicated over the pure data axes by design (the grad
# all-reduce rides them); only sharding/mp replication is waste
SHARDING_DATA_AXES = ("dp", "pp", "sep")

_SHARDING_MEMO: Dict = {}
_HEALTH_MEMO: Dict = {}


def _sharding_section() -> Dict[str, dict]:
    """The per-stack sharding sweeps; memoized per backend (the hybrid
    entries each compile the whole flagship, and the section is reached
    from self_check, the smoke leg and the test suite in one process)."""
    key = (jax.default_backend(), len(jax.devices()))
    if key in _SHARDING_MEMO:
        return _SHARDING_MEMO[key]
    if len(jax.devices()) < 8:
        return {"_skipped": {
            "ok": True,
            "skipped": f"needs >= 8 devices, have {len(jax.devices())} "
                       f"(run under "
                       f"XLA_FLAGS=--xla_force_host_platform_device_count"
                       f"=8)"}}
    out: Dict[str, dict] = {}
    try:
        for name, rep in _sharding_targets():
            out[name] = {"ok": rep.ok,
                         "findings": [f.format() for f in rep.findings],
                         "suppressed": len(rep.suppressed),
                         "skipped_passes": dict(rep.skipped)}
    except Exception as e:  # noqa: BLE001 - structured failure, not a crash
        # report the failure but do NOT memoize it: a one-off compile
        # hiccup must not pin the doctor red for the process lifetime
        out["_sweep_error"] = {"ok": False, "error": repr(e)}
        return out
    _SHARDING_MEMO[key] = out
    return out


def _sharding_targets():
    """Yield (name, report) for the sharding sweeps + the cross-stack
    table check; also stashes the canonical table on the section via
    flagship_sharding_table()."""
    from jax.sharding import Mesh

    from .core import check
    from .sharding import (check_cross_stack, check_layout,
                           extract_gspmd_layout, extract_hybrid_layout,
                           extract_overlap_layout)
    from paddle_tpu.models import build_train_step
    from paddle_tpu.models.llama import apply_llama_sharding, llama_decay_mask
    from paddle_tpu.parallel.overlap import OverlapConfig

    cfg, model, opt, params0, ids, labels = _flagship()
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        2, 2, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    params = {k: jnp.asarray(v)
              for k, v in model.functional_state().items()}
    mask_all = llama_decay_mask(model)

    glayout = extract_gspmd_layout(model, mesh)
    table = {"layout": glayout,
             "replicated_min_bytes": SHARDING_REPLICATED_MIN_BYTES,
             "replication_ignore_axes": SHARDING_DATA_AXES}

    # 1. flat GSPMD, single-batch (per-param optimizer: no flat pin to
    # demand — the per-param update shards with the params themselves)
    step1 = build_train_step(model, opt, mesh=mesh,
                             compute_dtype=jnp.bfloat16)
    yield "gspmd_train_step[accum1]", check(
        step1, params, opt.init_state(params), 0, 1e-4, ids, labels,
        passes=["sharding_consistency"],
        options={"sharding_consistency": {
            **table,
            "declared": SHARDING_RESHARD_ALLOWANCES["gspmd[accum1]"]}},
        target="sharding:gspmd_train_step[accum1]")

    # 2. flat GSPMD, grad-accum + fused flat optimizer: the entry that
    # must carry the 2004.13336 flat-update pin (deleting
    # build_train_step's flat_sharding fails SHARD005 here, not a
    # wrong-values session on the 0.4.x toolchain).  Since round 19 the
    # opt state is built in the schedule-derived SHARD-MAJOR wire
    # format (PartitionSchedule.flat_update_layout) — the entry whose
    # reshard bill the unified schedule shrank; the smaller allowance
    # pins the win (a fallback to the row-major wire format blows it)
    from paddle_tpu.parallel.schedule import PartitionSchedule

    psched = PartitionSchedule.from_model(model, mesh)
    step4 = build_train_step(model, opt, mesh=mesh,
                             compute_dtype=jnp.bfloat16, accum_steps=4,
                             schedule=psched)
    yield "gspmd_train_step[accum4]", check(
        step4, params,
        opt.init_flat_state(params, decay_mask=mask_all,
                            flat_layout=psched.flat_update_layout()),
        0, 1e-4, ids.reshape(4, 1, 16), labels.reshape(4, 1, 16),
        passes=["sharding_consistency"],
        options={"sharding_consistency": {
            **table, "expect_update_pin": True,
            "declared": SHARDING_RESHARD_ALLOWANCES["gspmd[accum4]"]}},
        target="sharding:gspmd_train_step[accum4]")

    # 3. the overlap engine: manual bucket gathers attribute via the
    # jaxpr; the declared extras are the GSPMD-land boundary
    olayout = extract_overlap_layout(model, mesh)
    ostep = build_train_step(model, opt, mesh=mesh,
                             compute_dtype=jnp.bfloat16,
                             overlap=OverlapConfig())
    yield "overlap_train_step", check(
        ostep, params, opt.init_state(params), 0, 1e-4, ids, labels,
        passes=["sharding_consistency"],
        options={"sharding_consistency": {
            "layout": olayout,
            "replicated_min_bytes": SHARDING_REPLICATED_MIN_BYTES,
            "replication_ignore_axes": SHARDING_DATA_AXES,
            "declared": SHARDING_RESHARD_ALLOWANCES["overlap"]}},
        target="sharding:overlap_train_step")

    # 4. both hybrid bodies on the 5-axis mesh (pp2 x sharding2 x mp2)
    from paddle_tpu.models.llama_hybrid import (hybrid_mesh,
                                                shard_hybrid_state,
                                                stack_llama_state)

    hmesh = hybrid_mesh(jax.devices(), pp=2, dp=1, sharding=2, sep=1,
                        mp=2)
    hlayout = extract_hybrid_layout(model, hmesh)
    # one stacked+placed state serves both schedule sweeps: check()
    # only traces/compiles, never executes or donates the buffers
    hstate = shard_hybrid_state(
        stack_llama_state(dict(params), cfg.num_hidden_layers), hmesh)
    for sched, tag in (("gpipe", "hybrid[gpipe]"), ("1F1B",
                                                    "hybrid[1F1B]")):
        from paddle_tpu.models.llama_hybrid import build_hybrid_train_step

        hstep = build_hybrid_train_step(cfg, opt, hmesh,
                                        num_microbatches=2,
                                        compute_dtype=jnp.float32,
                                        schedule=sched)
        yield f"hybrid_train_step[{sched}]", check(
            hstep, hstate, opt.init_state(hstate), 0, 1e-4, ids, labels,
            passes=["sharding_consistency"],
            options={"sharding_consistency": {
                "layout": hlayout,
                "replicated_min_bytes": SHARDING_REPLICATED_MIN_BYTES,
                "replication_ignore_axes": SHARDING_DATA_AXES,
                "declared": SHARDING_RESHARD_ALLOWANCES[tag]}},
            target=f"sharding:hybrid_train_step[{sched}]")

    # 5. serving stack: the engine's CONCRETE committed params — the
    # single-chip flagship (params0, not the training-mesh copies; the
    # compiled unified step's zero-reshard contract rides the
    # serving_unified_step clean sweep via analysis_entry's options)
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(cfg, params0, max_slots=2,
                                   num_pages=9, page_size=16,
                                   max_seq_len=64,
                                   prefill_token_budget=8)
    yield "serving_param_layout", check_layout(
        eng.param_layout(),
        replicated_min_bytes=SHARDING_REPLICATED_MIN_BYTES,
        target="sharding:serving_param_layout")

    # 6. the cross-stack agreement gate: GSPMD, overlap and hybrid must
    # map the llama flagship parameter tree to the SAME canonical specs
    # (SHARD003 empty) — the precondition for deriving all three from
    # one schedule object
    yield "cross_stack", check_cross_stack(
        {"gspmd": glayout, "overlap": olayout, "hybrid": hlayout},
        target="sharding:cross_stack")

    # 6b. round-19: the unified-schedule derivation gates (SCHED001) —
    # the PartitionSchedule's canonical table must be BYTE-IDENTICAL to
    # the hand-written GSPMD table, its overlap stack_plan identical to
    # the engine's own stack_layout_plan, and the schedule recovered
    # from the Doctor's round-14 table artifact must re-derive the SAME
    # placements (table round-trip: the from_table constructor is the
    # elastic/pod-scale entry point)
    from .sharding import (check_schedule_derivation,
                           check_stack_plan_derivation)

    yield "schedule_derivation", check_schedule_derivation(
        psched, {"gspmd": glayout},
        target="sharding:schedule_derivation")
    yield "schedule_stack_plan", check_stack_plan_derivation(
        psched, model, mesh, target="sharding:schedule_stack_plan")
    rt = PartitionSchedule.from_table(psched.table.to_table(), mesh=mesh)
    yield "schedule_table_roundtrip", check_schedule_derivation(
        rt.rederive(mesh), {"declared": psched.table},
        target="sharding:schedule_table_roundtrip")

    # 7. round-18: the EP MoE stack — the DECLARED plan table
    # (expert.moe_ep_layout: leading [E] on ``ep``, shared gate
    # replicated) vs the CONCRETE at-rest placement of the placed
    # params; SHARD003 must be empty with ``ep`` among the canonical
    # mesh axes (the fourth named tactic covered by the same gate),
    # plus the SHARD002/004 table checks on the plan
    from paddle_tpu.parallel.expert import moe_ep_layout
    from paddle_tpu.parallel.specs import layout_from_arrays

    mcfg, mmesh, mparams, _, _ = _moe_ep_flagship()
    mplan = moe_ep_layout(mcfg, mmesh)
    mrest = layout_from_arrays(mparams, mesh=mmesh)
    # in the EP stack 'sharding' is a PURE batch axis (tokens ride it
    # into the dispatch; there is no ZeRO layer here) — expert weights
    # replicate over it by design, exactly like dp
    yield "moe_ep_layout", check_layout(
        mplan, replicated_min_bytes=SHARDING_REPLICATED_MIN_BYTES,
        ignore_axes=SHARDING_DATA_AXES + ("sharding",),
        target="sharding:moe_ep_layout")
    yield "moe_ep_cross_stack", check_cross_stack(
        {"moe_ep_plan": mplan, "moe_ep_at_rest": mrest},
        target="sharding:moe_ep_cross_stack")


# ---------------------------------------------------------------------------
# round-19: the joint partition x memory x overlap autotune section —
# DOCTOR.json carries the chosen schedule (the acceptance artifact of
# the unified-partitioning round)
# ---------------------------------------------------------------------------

# Joint budgets for the params-heavy debug flagship (vocab 512, hidden
# 128 — partitioning must move real bytes for the walk to mean
# anything) on the fake-2-slice 8-device pool.  Compiled peaks on the
# installed toolchain (jax 0.9, XLA:CPU; counts, not device metrics):
#   hybrid4 (dp2 x sharding2 x mp2, 4-way params)  codec-off:
#       peak 4 520 924, DCN 446 208;  codec-on: 4 389 980 / 150 916
#   tp8     (sharding4 x mp2, 8-way params)        codec-off:
#       peak 3 824 156, DCN 226 048;  codec-on: 3 742 940 /  76 612
# The pinned budgets sit BETWEEN the partition points' peaks and
# between the codec-on/off wire bytes, so the three walks land on
# THREE different lattice points:
#   HBM alone  -> tp8/codec-off   (first peak under budget),
#   DCN alone  -> hybrid4/codec-on (first wire under budget),
#   BOTH       -> tp8/codec-on    — a partitioning point neither
# budget alone forces, and one no hand-listed (codec-off, or
# hand-partition memory x codec) point reaches.  Margins >= 230 KB on
# peak and >= 20 KB on wire.
JOINT_HBM_BUDGET = 4_063_232          # 3.875 MB
JOINT_DCN_WIRE_BUDGET = 172_032       # 168 KB
JOINT_SLICE_MAPS = {"hybrid4": (0, 1), "tp8": (0, 0, 1, 1)}

_JOINT_MEMO: Dict = {}


def joint_flagship_config():
    """Shapes of the joint-autotune flagship (also the roofline drift
    check's cost-sheet input — one copy)."""
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig.debug(vocab=512, hidden=128, layers=2, heads=8,
                             kv_heads=4, inter=256, max_pos=64)


#: batch/seq of the joint flagship step (ids/labels shape)
JOINT_FLAGSHIP_BATCH, JOINT_FLAGSHIP_SEQ = 8, 16


def _joint_flagship():
    """The params-heavy debug flagship of the joint autotune section
    (partitioning must dominate the capacity picture, so vocab/hidden
    grow over _flagship's shapes; structure unchanged)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    state = paddle.get_rng_state()
    paddle.seed(20260804)
    cfg = joint_flagship_config()
    model = LlamaForCausalLM(cfg)
    paddle.set_rng_state(state)
    rng = np.random.default_rng(5)
    shape = (JOINT_FLAGSHIP_BATCH, JOINT_FLAGSHIP_SEQ)
    ids = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)
    return cfg, model, ids, labels


def joint_schedule_points():
    """The partition points of the joint lattice, cheapest predicted
    step time first (the hand hybrid composition, then the 8-way
    ZeRO-3 x TP point)."""
    from paddle_tpu.parallel.schedule import PartitionPoint

    return (
        PartitionPoint("hybrid4",
                       (("dp", 2), ("sharding", 2), ("mp", 2)),
                       slice_map=JOINT_SLICE_MAPS["hybrid4"]),
        PartitionPoint("tp8", (("dp", 1), ("sharding", 4), ("mp", 2)),
                       slice_map=JOINT_SLICE_MAPS["tp8"]),
    )


def joint_schedule_section() -> dict:
    """Run the joint partition x memory x overlap autotune on the
    fake-2-slice lattice under the pinned budgets; memoized per
    backend (4 flagship compiles — self_check, the bench schedule
    trace and tests/test_schedule.py all read one payment).  The
    result is DOCTOR.json's ``unified_schedule.joint_autotune``."""
    import paddle_tpu as paddle
    from paddle_tpu.models import build_train_step
    from paddle_tpu.models.llama import apply_llama_sharding
    from paddle_tpu.parallel.memory import MemoryConfig
    from paddle_tpu.parallel.schedule import (choose_joint_config,
                                              joint_schedule_lattice,
                                              tune_schedule_config)

    if len(jax.devices()) < 8:
        return {"ok": True, "skipped": "needs >= 8 devices"}
    key = (jax.default_backend(), len(jax.devices()))
    if key in _JOINT_MEMO:
        return _JOINT_MEMO[key]
    from paddle_tpu.parallel.codec import CollectiveCodec

    cfg, model, ids, labels = _joint_flagship()
    # two codec points (off / stochastic-int8), not the full
    # three-point codec lattice: the fp8 point prices IDENTICALLY to
    # int8 on both budget axes (same wire bytes, same peak) so it
    # would re-compile the flagship twice for two duplicate records —
    # tier-1 wall management (round-19), the full lattice rides
    # ``-m slow`` breadth if ever needed
    lattice = joint_schedule_lattice(
        joint_schedule_points(),
        memory_lattice=(MemoryConfig(remat="none"),),
        codec_points=(None, CollectiveCodec()))

    def builder(jc):
        mesh = jc.partition.mesh()
        apply_llama_sharding(model, mesh)
        params = {k: jnp.asarray(v)
                  for k, v in model.functional_state().items()}
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
        step = build_train_step(model, opt, mesh=mesh,
                                compute_dtype=jnp.bfloat16,
                                overlap=jc.overlap, memory=jc.memory)
        return step, (params, opt.init_state(params), jnp.int32(0),
                      jnp.float32(1e-4), ids, labels)

    chosen, records = tune_schedule_config(
        builder, JOINT_HBM_BUDGET, lattice,
        dcn_wire_bytes=JOINT_DCN_WIRE_BUDGET)
    hbm_only = choose_joint_config(records, hbm_bytes=JOINT_HBM_BUDGET)
    dcn_only = choose_joint_config(records,
                                   dcn_wire_bytes=JOINT_DCN_WIRE_BUDGET)
    joint = choose_joint_config(records, hbm_bytes=JOINT_HBM_BUDGET,
                                dcn_wire_bytes=JOINT_DCN_WIRE_BUDGET)
    # hand-listed points: the codec-off hand configs of each partition
    # point AND the round-15-style memory x codec walk pinned on the
    # hand partition (hybrid4) — none may satisfy both budgets, or the
    # partitioning axis added nothing
    hand = [i for i, r in enumerate(records)
            if r["label"].startswith("hybrid4")
            or r["label"].endswith("codec-off")]
    hand_fits = [i for i in hand
                 if r_fits(records[i])]
    ok = (chosen is not None and joint is not None
          and records[joint]["label"] == chosen.label()
          and hbm_only is not None and dcn_only is not None
          and len({hbm_only, dcn_only, joint}) == 3
          and joint > max(hbm_only, dcn_only)
          and not hand_fits)
    out = {"ok": bool(ok),
           "hbm_budget": JOINT_HBM_BUDGET,
           "dcn_wire_budget": JOINT_DCN_WIRE_BUDGET,
           "records": [{"label": r["label"],
                        "peak_bytes": r["peak_bytes"],
                        "dcn_wire_bytes": r.get("dcn_wire_bytes"),
                        "config": r["config"]} for r in records],
           "picked": {"hbm_only": records[hbm_only]["label"]
                      if hbm_only is not None else None,
                      "dcn_only": records[dcn_only]["label"]
                      if dcn_only is not None else None,
                      "joint": records[joint]["label"]
                      if joint is not None else None},
           "chosen": chosen.to_json() if chosen is not None else None,
           "chosen_label": chosen.label() if chosen is not None else None}
    if ok:                  # never memoize a one-off compile hiccup red
        _JOINT_MEMO[key] = out
    return out


def r_fits(rec) -> bool:
    """One record against BOTH pinned joint budgets."""
    return (rec["peak_bytes"] <= JOINT_HBM_BUDGET
            and rec.get("dcn_wire_bytes", 0) <= JOINT_DCN_WIRE_BUDGET)


#: The measured joint-autotune records (container toolchain, 8 fake
#: devices) in lattice order — the compile-free reference the roofline
#: drift check (and bench --roofline-trace --smoke-trace) falls back to
#: when the memoized compiled section isn't available in-process.
#: MUST track DOCTOR.json's ``unified_schedule.joint_autotune.records``.
RECORDED_JOINT_RECORDS = (
    {"label": "hybrid4(dp2xsharding2xmp2)[2slice]/none/device/"
              "codec-off",
     "peak_bytes": 4_520_924, "dcn_wire_bytes": 446_208},
    {"label": "hybrid4(dp2xsharding2xmp2)[2slice]/none/device/"
              "codec[g=int8/sr,w=fp8,b=256]",
     "peak_bytes": 4_389_980, "dcn_wire_bytes": 150_916},
    {"label": "tp8(sharding4xmp2)[2slice]/none/device/codec-off",
     "peak_bytes": 3_824_156, "dcn_wire_bytes": 226_048},
    {"label": "tp8(sharding4xmp2)[2slice]/none/device/"
              "codec[g=int8/sr,w=fp8,b=256]",
     "peak_bytes": 3_742_940, "dcn_wire_bytes": 76_612},
)


def roofline_drift_section(joint: Optional[dict] = None) -> dict:
    """Round-20: estimator-vs-measured drift gate.  The analytic
    roofline estimate re-ranks the fake-2-slice joint lattice and its
    PREDICTED winner (cheapest predicted point whose predicted peak +
    wire fit the pinned budgets, peak one-point-calibrated on the
    first measured record) must equal the MEASURED joint-autotune pick;
    per-record predicted fit/no-fit must agree with the measured
    frontier, and the predicted DCN wire bytes must track the measured
    pins (the wire model mirrors the overlap engine's collective
    schedule — byte-exact today; drift here means the engine's
    schedule and the estimator's mirror diverged).

    Compile-free: reads the memoized joint section when available
    (``joint`` argument / _JOINT_MEMO), else the RECORDED pins with a
    paper trail."""
    from paddle_tpu.parallel import roofline as rf
    from paddle_tpu.parallel.codec import CollectiveCodec
    from paddle_tpu.parallel.memory import MemoryConfig
    from paddle_tpu.parallel.schedule import joint_schedule_lattice

    if joint is None:
        joint = _JOINT_MEMO.get((jax.default_backend(),
                                 len(jax.devices())))
    measured_src = "compiled"
    records = (joint or {}).get("records")
    if not records:
        records = [dict(r) for r in RECORDED_JOINT_RECORDS]
        measured_src = "recorded"
    measured_pick = next((r["label"] for r in records if r_fits(r)),
                         None)

    lattice = joint_schedule_lattice(
        joint_schedule_points(),
        memory_lattice=(MemoryConfig(remat="none"),),
        codec_points=(None, CollectiveCodec()))
    by_label = {jc.label(): jc for jc in lattice}
    if set(by_label) != {r["label"] for r in records}:
        return {"ok": False, "target": "roofline:drift",
                "error": "lattice/record label mismatch",
                "lattice": sorted(by_label),
                "records": [r["label"] for r in records]}

    sheet = rf.llama_cost_sheet(joint_flagship_config())
    cal = rf.calibration_offset_from(
        records[0], by_label[records[0]["label"]], sheet,
        batch=JOINT_FLAGSHIP_BATCH, seq=JOINT_FLAGSHIP_SEQ)
    ests = {}
    for rec in records:
        ests[rec["label"]] = rf.estimate_joint_config(
            by_label[rec["label"]], sheet,
            batch=JOINT_FLAGSHIP_BATCH, seq=JOINT_FLAGSHIP_SEQ,
            hbm_budget=JOINT_HBM_BUDGET,
            dcn_budget=JOINT_DCN_WIRE_BUDGET,
            calibration_offset=cal)
    order = sorted(records, key=lambda r: ests[r["label"]].total_s)
    predicted_pick = next((r["label"] for r in order
                           if ests[r["label"]].fits), None)

    table = []
    frontier_ok = True
    max_wire_err = 0.0
    for rec in records:
        e = ests[rec["label"]]
        meas_fit = r_fits(rec)
        frontier_ok = frontier_ok and (e.fits == meas_fit)
        md = rec.get("dcn_wire_bytes") or 0
        if md:
            max_wire_err = max(max_wire_err,
                               abs(e.dcn_wire_bytes - md) / md)
        table.append({"label": rec["label"],
                      "predicted": e.to_json(),
                      "measured": {"peak_bytes": rec["peak_bytes"],
                                   "dcn_wire_bytes": md,
                                   "fits": meas_fit}})
    # the wire mirror is structural: > 10% relative drift on any pin
    # means the engine's schedule changed under the estimator
    ok = (predicted_pick is not None
          and predicted_pick == measured_pick
          and frontier_ok and max_wire_err <= 0.10)
    return {"ok": bool(ok), "target": "roofline:drift",
            "measured_source": measured_src,
            "predicted_winner": predicted_pick,
            "measured_pick": measured_pick,
            "frontier_parity": bool(frontier_ok),
            "max_dcn_wire_rel_err": max_wire_err,
            "calibration_offset": cal,
            "predicted_order": [r["label"] for r in order],
            "table": table}


_WIRE_MEMO: Dict = {}


def flagship_wire_table() -> dict:
    """Pre/post-codec ICI/DCN bytes-on-the-wire tables for the flagship
    overlap step on the fake-2-slice hierarchical mesh — DOCTOR.json's
    ``comm_wire`` per-stage bytes artifact (round-15).  Memoized per
    backend: both the bench smoke leg and the test suite read it in one
    process, and each variant traces the whole flagship."""
    from jax.sharding import Mesh

    from .core import AnalysisContext
    from .passes.collective_budget import collect_wire_table
    from paddle_tpu.models import build_train_step
    from paddle_tpu.models.llama import apply_llama_sharding
    from paddle_tpu.parallel.codec import CollectiveCodec
    from paddle_tpu.parallel.overlap import OverlapConfig

    if len(jax.devices()) < 8:
        return {"skipped": "needs >= 8 devices"}
    key = (jax.default_backend(), len(jax.devices()))
    if key in _WIRE_MEMO:
        return _WIRE_MEMO[key]
    cfg, model, opt, params0, ids, labels = _flagship()
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        1, 4, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    params = {k: jnp.asarray(v)
              for k, v in model.functional_state().items()}
    dcn_axes = {"sharding": list(FLAGSHIP_SLICE_MAP)}
    out: Dict[str, dict] = {"slice_map": list(FLAGSHIP_SLICE_MAP),
                            "dcn_budget": FLAGSHIP_DCN_WIRE_BUDGET}
    for name, codec in (("codec_off", None),
                        ("codec_on", CollectiveCodec())):
        oc = OverlapConfig(hierarchical="on",
                           slice_map=FLAGSHIP_SLICE_MAP, codec=codec)
        step = build_train_step(model, opt, mesh=mesh,
                                compute_dtype=jnp.bfloat16, overlap=oc)
        ctx = AnalysisContext(step, (params, opt.init_state(params), 0,
                                     1e-4, ids, labels), {})
        out[name] = collect_wire_table(ctx.jaxpr, dcn_axes)
    off_dcn, on_dcn = out["codec_off"]["dcn"], out["codec_on"]["dcn"]
    out["dcn_ratio"] = (off_dcn["bytes"] / on_dcn["bytes"]
                        if on_dcn["bytes"] else None)
    # the acceptance metric: the bucketed grad reduce-scatter's DCN leg
    # (fp-wire psum_scatter off, packed int8 all_to_all on)
    rs_off = off_dcn["kinds"].get("reducescatter", {}).get("bytes", 0)
    rs_on = on_dcn["kinds"].get("alltoall", {}).get("bytes", 0)
    out["reducescatter_ratio"] = rs_off / rs_on if rs_on else None
    _WIRE_MEMO[key] = out
    return out


def flagship_sharding_table() -> dict:
    """The canonical SpecLayout table of the flagship GSPMD stack on
    the 8-device hybrid-compatible mesh — DOCTOR.json's
    ``sharding.canonical_table``, the artifact the future unified
    partitioning schedule consumes (ROADMAP)."""
    from jax.sharding import Mesh

    from .sharding import extract_gspmd_layout
    from paddle_tpu.models.llama import apply_llama_sharding

    if len(jax.devices()) < 8:
        return {"skipped": "needs >= 8 devices"}
    cfg, model, opt, params, ids, labels = _flagship()
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        2, 2, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    return extract_gspmd_layout(model, mesh).to_table()


def moe_ep_sharding_table() -> dict:
    """The canonical SpecLayout table of the EP MoE stack on the
    fake-2-slice dp x sharding x ep mesh — DOCTOR.json's round-18
    rider: ``ep`` appears as a first-class axis in the canonical
    vocabulary the unified partitioning schedule consumes."""
    from .sharding import extract_moe_ep_layout

    if len(jax.devices()) < 8:
        return {"skipped": "needs >= 8 devices"}
    cfg, mesh, _, _, _ = _moe_ep_flagship()
    return extract_moe_ep_layout(cfg, mesh).to_table()


def _probe_masked_grad_accum():
    """Liveness probe for EX-DT003-masked-grad-accum: the masked accum
    branch still carries its by-design fp32 buffer and the audit still
    sees (and suppresses) it."""
    from .core import check
    from paddle_tpu.models import build_train_step

    cfg, model, opt, params, ids, labels = _flagship()
    stepm = build_train_step(model, opt, compute_dtype=jnp.bfloat16,
                             accum_steps=4)
    amask = np.ones((4, 1, 16), np.int32)
    amask[:, :, -4:] = 0
    return check(stepm, params, opt.init_state(params), 0, 1e-4,
                 ids.reshape(4, 1, 16), labels.reshape(4, 1, 16), amask,
                 passes=["dtype_promotion"], declared_dtype=jnp.bfloat16,
                 target="build_train_step[bf16,accum4,masked]")


# every standing exemption needs a probe that reproduces its finding —
# an Exemption without one FAILS self-check (a suppression whose hazard
# can no longer be demonstrated is either stale or untested)
_LIVENESS_PROBES = {
    "EX-DT003-masked-grad-accum": _probe_masked_grad_accum,
}


def _exemption_liveness() -> Dict[str, dict]:
    """Each standing exemption must still match a live suppressed finding
    in ITS OWN probe's report — one baked-in sweep cannot witness
    exemptions added later for other passes/targets."""
    from .exemptions import EXEMPTIONS

    out = {}
    for ex in EXEMPTIONS:
        probe = _LIVENESS_PROBES.get(ex.id)
        if probe is None:
            out[ex.id] = {"ok": False,
                          "error": f"no liveness probe registered for "
                                   f"{ex.id} — add one to "
                                   f"_LIVENESS_PROBES"}
            continue
        rep = probe()
        hit = [f for f in rep.suppressed if f.exemption_id == ex.id]
        out[ex.id] = {
            "ok": bool(hit) and not rep.findings,
            "matched": len(hit),
            "unsuppressed": [f.format() for f in rep.findings],
        }
    return out


_SEEDED_MEMO: Dict = {}


def _seeded_section() -> Dict[str, dict]:
    """The seeded-fixture sweep, memoized per backend: every fixture
    compiles a small program, the sweep is reached from self_check AND
    the parametrized test suite runs the same fixtures in the same
    tier-1 process — one payment is enough (a fixture regression still
    fails: the parametrized sweep calls the fixtures directly)."""
    from .fixtures import SEEDED, FixtureUnavailable

    key = (jax.default_backend(), len(jax.devices()))
    if key in _SEEDED_MEMO:
        return _SEEDED_MEMO[key]
    seeded = {}
    ok_all = True
    for code, fx in SEEDED.items():
        try:
            rep = fx()
        except FixtureUnavailable as e:
            seeded[code] = {"ok": True, "skipped": str(e)}
            continue
        except Exception as e:  # noqa: BLE001 - report, don't crash the CLI
            seeded[code] = {"ok": False, "error": repr(e)}
            ok_all = False
            continue
        codes = set(rep.codes())
        # registry keys may carry a "[variant]" suffix (two proofs of
        # one code on different entry points); the report must contain
        # the BARE code exactly
        expect = code.split("[", 1)[0]
        seeded[code] = {"ok": codes == {expect},
                        "codes": sorted(codes),
                        "n": len(rep.findings)}
        ok_all = ok_all and seeded[code]["ok"]
    if ok_all:          # never memoize a red sweep (one-off hiccups)
        _SEEDED_MEMO[key] = seeded
    return seeded


_CLEAN_MEMO: Dict = {}


def _clean_section() -> Dict[str, dict]:
    """The clean-flagship sweep as a JSON-able dict, memoized per
    backend (the targets compile several flagship variants and the
    section is reached from self_check, the doctor smoke leg and
    tests/test_analysis_passes.py in one tier-1 process)."""
    key = (jax.default_backend(), len(jax.devices()))
    if key in _CLEAN_MEMO:
        return _CLEAN_MEMO[key]
    clean_out = {}
    try:
        for name, rep in _clean_targets():
            clean_out[name] = {
                "ok": rep.ok,
                "findings": [f.format() for f in rep.findings],
                "suppressed": len(rep.suppressed),
                "skipped_passes": dict(rep.skipped)}
    except Exception as e:  # noqa: BLE001
        clean_out["_sweep_error"] = {"ok": False, "error": repr(e)}
        return clean_out
    if all(v.get("ok") for v in clean_out.values()):
        _CLEAN_MEMO[key] = clean_out
    return clean_out


_CONC_MEMO: Dict = {}


def _concurrency_section() -> dict:
    """Round-21 Concurrency Doctor block: the lock-discipline sweep over
    the host-side control plane plus the deterministic sanitizer
    self-test.  Backend-independent (pure AST + a barrier-stepped
    single-thread hammer) and reached from self_check, the smoke leg and
    tests in one tier-1 process — memoized per process, green runs
    only."""
    if "x" in _CONC_MEMO:
        return _CONC_MEMO["x"]
    from .concurrency import concurrency_section

    out = concurrency_section()
    if all(isinstance(v, dict) and v.get("ok") for v in out.values()):
        _CONC_MEMO["x"] = out
    return out


def self_check(clean: bool = True, joint: bool = True) -> dict:
    """Run the full self-check; returns a JSON-able dict with ``ok``.

    ``joint=False`` skips the round-19 joint-autotune section's 3
    flagship compiles (tier-1 wall management: the smoke legs pass it —
    the forcing CONTRACT is pinned by the seeded walk in
    tests/test_schedule.py and the byte-identity gates ride the
    sharding section; the real walk runs in the CLI ``--doctor`` /
    ``--schedule-trace`` (DOCTOR.json / SCHEDULE_r01.json carry the
    chosen schedule) and re-asserts under ``-m slow``)."""
    result = {"seeded": _seeded_section()}
    # round-21: the Concurrency Doctor — static lock-discipline sweep
    # over the control plane + the deterministic sanitizer self-test.
    # Cheap (no compiles) and host-side, so it runs in EVERY mode.
    try:
        result["concurrency"] = _concurrency_section()
    except Exception as e:  # noqa: BLE001
        result["concurrency"] = {"_section_error": {"ok": False,
                                                    "error": repr(e)}}
    if clean:
        # a sweep blowing up (toolchain drift, engine construction) must
        # degrade to a structured failure, not a raw traceback — the CLI
        # contract is "JSON report + non-zero exit", and DOCTOR.json
        # still gets written for the targets that did run
        result["clean"] = _clean_section()
        try:
            result["exemptions"] = _exemption_liveness()
        except Exception as e:  # noqa: BLE001
            result["exemptions"] = {"_liveness_error": {"ok": False,
                                                        "error": repr(e)}}
        # round-14: the Sharding Doctor section — per-stack reshard
        # audits, canonical-table checks and the cross-stack agreement
        # gate; DOCTOR.json additionally carries the canonical table
        # itself (the unified-partitioning refactor's input artifact)
        try:
            result["sharding"] = _sharding_section()
        except Exception as e:  # noqa: BLE001
            result["sharding"] = {"_section_error": {"ok": False,
                                                     "error": repr(e)}}
        try:
            result["sharding_canonical_table"] = flagship_sharding_table()
        except Exception as e:  # noqa: BLE001
            result["sharding_canonical_table"] = {"error": repr(e)}
        # round-18: the EP MoE stack's canonical table — ``ep`` as a
        # first-class axis in the vocabulary (the fourth named tactic)
        try:
            result["moe_ep_canonical_table"] = moe_ep_sharding_table()
        except Exception as e:  # noqa: BLE001
            result["moe_ep_canonical_table"] = {"error": repr(e)}
        # round-15: the per-stage (ICI/DCN) bytes-on-the-wire table for
        # the flagship hierarchical step, codec off vs on — the COMM004
        # contract's measurement artifact
        try:
            result["comm_wire"] = flagship_wire_table()
        except Exception as e:  # noqa: BLE001
            result["comm_wire"] = {"error": repr(e)}
        # round-19: the unified partitioning schedule — DOCTOR.json
        # carries the pinned (shrunk) reshard bill and the joint
        # partition x memory x overlap autotune's CHOSEN schedule (the
        # round's acceptance artifact); the derivation gates themselves
        # ride the sharding section above
        try:
            jsec = (joint_schedule_section() if joint
                    else {"ok": True,
                          "skipped": "joint=False (tier-1 wall): the "
                                     "real walk rides --doctor / "
                                     "--schedule-trace and -m slow; "
                                     "the forcing contract is pinned "
                                     "by tests/test_schedule.py's "
                                     "seeded walk"})
            result["unified_schedule"] = {
                "joint_autotune": jsec,
                # round-20: the estimator-drift gate (compile-free —
                # reads the joint records when compiled, else the
                # recorded pins)
                "roofline_drift": roofline_drift_section(
                    jsec if jsec.get("records") else None),
                "pinned_reshard_allowances":
                    {k: dict(v)
                     for k, v in SHARDING_RESHARD_ALLOWANCES.items()},
            }
        except Exception as e:  # noqa: BLE001
            result["unified_schedule"] = {
                "joint_autotune": {"ok": False, "error": repr(e)}}

    def _all_ok(d):
        return all(v.get("ok") for v in d.values()) if d else True

    result["ok"] = all(_all_ok(result.get(k, {}))
                       for k in ("seeded", "clean", "exemptions",
                                 "sharding", "concurrency")) \
        and (not clean
             or (bool(result.get("unified_schedule", {})
                      .get("joint_autotune", {}).get("ok"))
                 and bool(result.get("unified_schedule", {})
                          .get("roofline_drift", {"ok": True})
                          .get("ok"))))
    result["backend"] = jax.default_backend()
    result["num_devices"] = len(jax.devices())
    return result
