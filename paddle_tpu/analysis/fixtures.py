"""Seeded-bug fixtures: one deliberately-planted hazard per pass.

Every Graph Doctor pass must have a TRUE-POSITIVE proof, not just a
clean-run test — a pass that never fires is indistinguishable from a
pass that cannot fire.  Each fixture here builds a tiny program seeded
with exactly one bug of the class its pass hunts, runs the pass in
isolation (``exemptions=()`` so the standing table cannot mask a
regression in the pass itself), and returns the Report.  The self-check
(``python -m paddle_tpu.analysis --self-check``, the ``doctor_self_check``
smoke leg, and tests/test_analysis_passes.py) assert each report contains
its intended finding code and nothing else.

Fixtures that need capabilities the environment lacks (a multi-device
mesh on a bare single-CPU invocation) raise FixtureUnavailable, which
callers record as a skip — never a silent pass.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .core import check
from .findings import Report
from .passes.hlo_checks import scan_compile_warnings
from .passes.retrace import retrace_sentinel


class FixtureUnavailable(RuntimeError):
    """The environment cannot host this fixture (e.g. needs >= 2 devices)."""


def _mesh(min_devices: int = 1):
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < min_devices:
        raise FixtureUnavailable(
            f"needs >= {min_devices} devices, have {len(devs)} "
            f"(run under XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    n = max(min_devices, 2) if len(devs) >= 2 else 1
    return Mesh(np.asarray(devs[:n], dtype=object), ("x",))


# ---------------------------------------------------------------------------
# collective_order
# ---------------------------------------------------------------------------


def seeded_collective_order() -> Report:
    """COLL001: a shard_map cond whose true branch psums and whose false
    branch does not — ranks disagreeing on the predicate deadlock."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = _mesh(1)

    def body(v):
        return jax.lax.cond(v.sum() > 0.0,
                            lambda u: jax.lax.psum(u, "x"),
                            lambda u: u, v)

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                   check_vma=False)
    x = jnp.ones((8 * mesh.shape["x"],), jnp.float32)
    return check(fn, x, passes=["collective_order"], exemptions=(),
                 target="seeded:COLL001")


def seeded_ppermute_race() -> Report:
    """COLL002: a ppermute with two sources targeting one destination."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = _mesh(2)

    def body(v):
        return jax.lax.ppermute(v, "x", [(0, 1), (1, 1)])

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"))
    x = jnp.ones((2 * mesh.shape["x"],), jnp.float32)
    return check(fn, x, passes=["collective_order"], exemptions=(),
                 target="seeded:COLL002")


# ---------------------------------------------------------------------------
# dtype_promotion
# ---------------------------------------------------------------------------


def seeded_fp32_matmul() -> Report:
    """DT001: a bf16 program whose second matmul silently upcasts."""

    def bug(a, b):
        h = a @ b                                     # bf16 — declares it
        return (h.astype(jnp.float32)
                @ b.astype(jnp.float32)).sum()        # the silent upcast

    a = jnp.ones((128, 128), jnp.bfloat16)
    return check(bug, a, a, passes=["dtype_promotion"], exemptions=(),
                 target="seeded:DT001")


def seeded_f64_leak() -> Report:
    """DT002: an x64-enabled input drags float64 through the program."""

    def bug(a):
        return (a * np.float64(2.0)).sum()

    with jax.enable_x64(True):
        return check(bug, np.ones((64, 64), np.float64),
                     passes=["dtype_promotion"], exemptions=(),
                     target="seeded:DT002")


def seeded_fp32_carry() -> Report:
    """DT003: a bf16 micro-step loop accumulating into a full-width fp32
    carry — the exact HBM-traffic bug the round-7 bf16 grad carry fixed."""

    def bug(w, xs):
        def micro(acc, x):
            g = x @ w                                  # bf16 compute
            return acc + g.astype(jnp.float32), ()     # fp32 accumulate
        acc, _ = jax.lax.scan(
            micro, jnp.zeros((128, 128), jnp.float32), xs)
        return acc

    w = jnp.ones((128, 128), jnp.bfloat16)
    xs = jnp.ones((4, 128, 128), jnp.bfloat16)
    return check(bug, w, xs, passes=["dtype_promotion"], exemptions=(),
                 target="seeded:DT003")


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


def seeded_undonated_state() -> Report:
    """DON001: a param-sized pytree rides a jit entry without donation."""

    @jax.jit
    def bug(params, grads):
        return {k: v - 1e-3 * grads[k] for k, v in params.items()}

    params = {"w": jnp.ones((768, 768), jnp.float32)}
    grads = {"w": jnp.ones((768, 768), jnp.float32)}
    return check(bug, params, grads, passes=["donation"], exemptions=(),
                 target="seeded:DON001")


def seeded_use_after_donate() -> Report:
    """DON002: one buffer passed to a donated AND a read position."""
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def bug(a, b):
        return a * 2.0 + b

    x = jnp.ones((128, 128), jnp.float32)   # small: below DON001's bar
    return check(bug, x, x, passes=["donation"], exemptions=(),
                 target="seeded:DON002")


# ---------------------------------------------------------------------------
# retrace_sentinel
# ---------------------------------------------------------------------------


def seeded_weak_type_churn() -> Report:
    """RT001: alternating python-float and array lr retraces per flip."""
    step = retrace_sentinel(jax.jit(lambda x, lr: x * lr),
                            name="seeded:RT001")
    x = jnp.ones((8,), jnp.float32)
    step(x, 0.1)                       # weak f32 scalar
    step(x, jnp.float32(0.1))          # strong f32 scalar — same but weak
    return step.report()


def seeded_signature_churn() -> Report:
    """RT002: unbucketed lengths — every call is a fresh compile."""
    step = retrace_sentinel(jax.jit(lambda x: x.sum()), max_signatures=3,
                            name="seeded:RT002")
    for n in (1, 2, 3, 4):
        step(jnp.ones((n,), jnp.float32))
    return step.report()


# ---------------------------------------------------------------------------
# hlo_post_checks
# ---------------------------------------------------------------------------


def seeded_involuntary_remat() -> Report:
    """HLO001 over a captured-warning sample: the detector itself (the
    compile-and-capture plumbing is exercised by the clean-run checks and
    tests/test_no_involuntary_remat.py; XLA's fallback cannot be seeded
    portably on one CPU device)."""
    sample = (
        "2026-08-03 12:00:00.000000: W external/xla/xla/service/spmd/"
        "spmd_partitioner.cc:584] Involuntary full rematerialization. "
        "The compiled was not able to go from sharding "
        "{devices=[2,2]<=[4]} to {replicated} without doing a full "
        "rematerialization of the tensor.\n")
    findings = scan_compile_warnings(sample)
    return Report(target="seeded:HLO001", findings=findings,
                  passes_run=("hlo_post_checks",))


def seeded_full_param_allgather() -> Report:
    """HLO002: a stage-3-sharded param replicated wholesale inside the
    step.  The threshold is the documented stage-3 gate: no all-gather
    may exceed the largest per-layer parameter."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh(2)
    p = jax.device_put(jnp.ones((1024, 64), jnp.float32),
                       NamedSharding(mesh, P("x", None)))

    @jax.jit
    def bug(a):
        full = jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P()))     # gathers the whole param
        return full * 2.0

    return check(
        bug, p, passes=["hlo_post_checks"], exemptions=(),
        target="seeded:HLO002",
        options={"hlo_post_checks":
                 {"max_allgather_bytes": 1024 * 64 * 4 // 2}})


# ---------------------------------------------------------------------------
# collective_budget
# ---------------------------------------------------------------------------


def seeded_collective_budget() -> Report:
    """COMM001: a step whose compiled HLO carries TWO all-reduces against
    a declared budget of one (the per-leaf-collective regression class
    the bucketed overlap engine exists to prevent)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = _mesh(2)

    def body(a, b):
        # the second reduce consumes the first: XLA's all-reduce
        # combiner cannot merge the pair into one
        return jax.lax.psum(b * jax.lax.psum(a, "x"), "x")

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"), P("x")),
                   out_specs=P(), check_vma=False)
    x = jnp.ones((2 * mesh.shape["x"], 8), jnp.float32)
    return check(fn, x, x + 1.0, passes=["collective_budget"],
                 exemptions=(), target="seeded:COMM001",
                 options={"collective_budget":
                          {"allreduce": {"count": 1}}})


def seeded_unscheduled_collective() -> Report:
    """COMM002: with an overlap engine declared active, a shard_map body
    issues a bare psum whose call stack contains none of the engine's
    region functions — traffic the engine never scheduled."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = _mesh(1)

    def rogue_reduce(v):
        return jax.lax.psum(v, "x")

    fn = shard_map(rogue_reduce, mesh=mesh, in_specs=(P("x"),),
                   out_specs=P(), check_vma=False)
    x = jnp.ones((4 * mesh.shape["x"],), jnp.float32)
    return check(fn, x, passes=["collective_budget"], exemptions=(),
                 target="seeded:COMM002",
                 options={"collective_budget": {"overlap_active": True}})


def seeded_ppermute_ring_order() -> Report:
    """COMM003: a scanned pipeline ring whose perm mixes rotation steps
    (+1, +1, +2, 0) — stage pairings drift across ticks."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = _mesh(4)
    n = mesh.shape["x"]
    if n < 4:
        raise FixtureUnavailable("non-uniform ring needs an axis of >= 4")

    def body(v):
        def tick(c, _):
            return jax.lax.ppermute(
                c, "x", [(0, 1), (1, 2), (2, 0), (3, 3)]), None
        c, _ = jax.lax.scan(tick, v, None, length=2)
        return c

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),),
                   out_specs=P("x"), check_vma=False)
    x = jnp.ones((2 * n,), jnp.float32)
    return check(fn, x, passes=["collective_budget"], exemptions=(),
                 target="seeded:COMM003")


def seeded_codec_disabled() -> Report:
    """COMM004: a fake-2-slice hierarchical reduce-scatter whose codec
    is silently DISABLED, checked against the DCN wire budget its
    QUANTIZED schedule honors — the packed int8 payload prices at ~1/4
    the fp32 bytes, so the unquantized DCN stage blows straight through
    the post-codec contract (the regression class the codec knob makes
    possible: one dropped ``codec=`` kwarg re-inflates every DCN hop)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from ..distributed.topology import hierarchical_axis
    from ..parallel.codec import CollectiveCodec
    from ..parallel.overlap import hier_psum_scatter
    from .passes.collective_budget import collect_wire_table

    mesh = _mesh(4)
    if mesh.shape["x"] < 4:
        raise FixtureUnavailable("fake 2-slice split needs an axis of 4")
    sm = (0, 0, 1, 1)
    hier = hierarchical_axis(mesh, "x", slice_map=sm)
    codec = CollectiveCodec(block=64)

    def coded(v):
        return hier_psum_scatter(v, "x", hier, codec=codec)

    def uncoded(v):                      # the seeded bug: codec dropped
        return hier_psum_scatter(v, "x", hier)

    def wrap(body):
        return shard_map(body, mesh=mesh, in_specs=(P(),),
                         out_specs=P("x"), check_vma=False)

    x = jnp.ones((16, 64), jnp.float32)
    # the declared budget IS the quantized schedule's measured DCN bytes
    coded_jaxpr = jax.make_jaxpr(wrap(coded))(x).jaxpr
    budget = collect_wire_table(coded_jaxpr, {"x": sm})["dcn"]["bytes"]
    return check(wrap(uncoded), x, passes=["collective_budget"],
                 exemptions=(), target="seeded:COMM004",
                 options={"collective_budget":
                          {"wire": {"dcn_axes": {"x": list(sm)},
                                    "dcn_bytes": budget}}})


def seeded_moe_dispatch_codec_off() -> Report:
    """COMM004 on the round-18 EP dispatch: a fake-2-slice expert
    all-to-all whose codec is silently DISABLED, checked against the
    DCN wire budget its QUANTIZED schedule honors — the EP twin of the
    reduce-scatter fixture (one dropped ``codec=`` kwarg on the MoE
    dispatch re-inflates every DCN-crossing token payload to fp wire,
    blowing the post-codec contract the EP step is pinned to)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from ..distributed.topology import hierarchical_axis
    from ..parallel.codec import CollectiveCodec
    from ..parallel.expert import make_ep_all_to_all
    from .passes.collective_budget import collect_wire_table

    mesh = _mesh(4)
    if mesh.shape["x"] < 4:
        raise FixtureUnavailable("fake 2-slice split needs an axis of 4")
    sm = (0, 0, 1, 1)
    hier = hierarchical_axis(mesh, "x", slice_map=sm)
    codec = CollectiveCodec(block=64)

    def coded(v):
        return make_ep_all_to_all("x", hier=hier, codec=codec)(v)

    def uncoded(v):                      # the seeded bug: codec dropped
        return make_ep_all_to_all("x", hier=hier)(v)

    def wrap(body):
        return shard_map(body, mesh=mesh, in_specs=(P(),),
                         out_specs=P("x"), check_vma=False)

    x = jnp.ones((16, 64), jnp.float32)   # [E, C*d]-shaped send buffer
    # the declared budget IS the quantized dispatch's measured DCN bytes
    coded_jaxpr = jax.make_jaxpr(wrap(coded))(x).jaxpr
    budget = collect_wire_table(coded_jaxpr, {"x": sm})["dcn"]["bytes"]
    return check(wrap(uncoded), x, passes=["collective_budget"],
                 exemptions=(), target="seeded:COMM004[moe_dispatch]",
                 options={"collective_budget":
                          {"wire": {"dcn_axes": {"x": list(sm)},
                                    "dcn_bytes": budget}}})


def seeded_moe_dropless_codec_off() -> Report:
    """COMM004 on the round-20 DROPLESS dispatch composite: the sorted
    ragged dispatch is TWO exchanges — an uncoded int32 count exchange
    (the control plane stays bit-exact) followed by the coded token
    payload windows.  The seeded bug silently drops the codec on the
    payload leg only; the cheap count leg stays put while every
    DCN-crossing token window re-inflates to fp wire, blowing the
    budget the dropless step is pinned to."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from ..distributed.topology import hierarchical_axis
    from ..parallel.codec import CollectiveCodec
    from ..parallel.expert import make_ep_all_to_all
    from .passes.collective_budget import collect_wire_table

    mesh = _mesh(4)
    if mesh.shape["x"] < 4:
        raise FixtureUnavailable("fake 2-slice split needs an axis of 4")
    sm = (0, 0, 1, 1)
    hier = hierarchical_axis(mesh, "x", slice_map=sm)
    codec = CollectiveCodec(block=64)
    counts_a2a = make_ep_all_to_all("x", hier=hier)   # always uncoded

    def dispatch(payload_codec):
        pay = make_ep_all_to_all("x", hier=hier, codec=payload_codec)

        def body(c, v):
            return counts_a2a(c), pay(v)

        return shard_map(body, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P("x"), P("x")), check_vma=False)

    c = jnp.ones((4, 4), jnp.int32)       # [ep, e_local] counts
    x = jnp.ones((16, 64), jnp.float32)   # [ep*W, d] payload windows
    # the declared budget IS the coded composite's measured DCN bytes
    # (counts uncoded + payload coded)
    coded_jaxpr = jax.make_jaxpr(dispatch(codec))(c, x).jaxpr
    budget = collect_wire_table(coded_jaxpr, {"x": sm})["dcn"]["bytes"]
    return check(dispatch(None), c, x, passes=["collective_budget"],
                 exemptions=(), target="seeded:COMM004[moe_dropless]",
                 options={"collective_budget":
                          {"wire": {"dcn_axes": {"x": list(sm)},
                                    "dcn_bytes": budget}}})


# ---------------------------------------------------------------------------
# memory_budget
# ---------------------------------------------------------------------------


def seeded_peak_over_budget() -> Report:
    """MEM001: a step whose compiled peak (arguments alone, here) blows
    through a deliberately tiny declared HBM budget."""

    @jax.jit
    def bug(a, b):
        return (a @ b).sum()

    a = jnp.ones((512, 512), jnp.float32)          # 1 MB per operand
    return check(bug, a, a, passes=["memory_budget"], exemptions=(),
                 target="seeded:MEM001",
                 options={"memory_budget": {"hbm_bytes": 64 << 10}})


def seeded_host_round_trip() -> Report:
    """MEM002: a whole buffer round-tripped host↔device in one
    monolithic pair of transfers against a streaming budget sized for
    half of it — the accidental full-state movement the size-capped
    bucket engine exists to prevent."""
    from ..core.device import host_memory_kind
    from ..parallel.memory import place_on_device, place_on_host

    if host_memory_kind() is None:
        raise FixtureUnavailable(
            "backend exposes no host memory kind to transfer to")

    @jax.jit
    def bug(a):
        h = place_on_host(a)                                # all out...
        back = place_on_device(h)
        return back * 2.0                                   # ...all back

    a = jnp.ones((512, 512), jnp.float32)          # 1 MB each direction
    return check(bug, a, passes=["memory_budget"], exemptions=(),
                 target="seeded:MEM002",
                 options={"memory_budget":
                          {"host_transfer_bytes": 1 << 20}})


def seeded_prefill_chunk_over_budget() -> Report:
    """MEM001 on the SERVING entry: a unified ragged serving step whose
    prefill chunk (prefill_token_budget=48) blows through an HBM budget
    declared for the decode-sized launch (1 MB fits the chunk-8 step at
    ~0.97 MB; chunk-48 compiles to ~1.13 MB) — the round-11 overrun the
    serving budget pin exists to catch: bumping the token budget must
    re-justify the declared budget, not silently grow the hot path."""
    import paddle_tpu as paddle
    from ..inference.serving import ContinuousBatchingEngine
    from ..models import LlamaConfig, LlamaForCausalLM

    state = paddle.get_rng_state()
    paddle.seed(20260803)
    cfg = LlamaConfig.debug(vocab=128, hidden=64, layers=2, heads=4,
                            kv_heads=2, inter=128, max_pos=64)
    model = LlamaForCausalLM(cfg)
    paddle.set_rng_state(state)
    params = {k: jnp.asarray(v)
              for k, v in model.functional_state().items()}
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   num_pages=17, page_size=16,
                                   max_seq_len=64,
                                   prefill_token_budget=48)
    fn, args, kwargs, _ = eng.analysis_entry()
    return check(fn, *args, kwargs=kwargs, passes=["memory_budget"],
                 exemptions=(), target="seeded:MEM001[prefill_chunk]",
                 options={"memory_budget": {"hbm_bytes": 1 << 20}})


def seeded_reshard_over_budget() -> Report:
    """MEM001 on the round-12 reshard entry: an UNBOUNDED reshard plan
    (``max_transient_bytes=None`` — one step, whole leaves, the layout a
    hand-rolled device_put loop degenerates to) moves a 1 MB replicated
    leaf through a redistribution entry whose declared transient budget
    is 64 KB — the overrun the size-capped planner exists to prevent,
    and the budget pin that keeps it honest when someone bypasses the
    cap."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.reshard import check_reshard_budget, plan_reshard

    mesh = _mesh(1)
    tree = {"w": jax.device_put(jnp.ones((512, 512), jnp.float32),
                                NamedSharding(mesh, P()))}
    plan = plan_reshard(tree, mesh, {"w": P("x", None)},
                        max_transient_bytes=None)
    return check_reshard_budget(plan, tree, budget_bytes=64 << 10,
                                exemptions=(),
                                target="seeded:MEM001[reshard_plan]")


def seeded_replica_delivery_over_budget() -> Report:
    """MEM001 on the round-13 replica weight-delivery entry: an
    UNBOUNDED delivery plan (``max_transient_bytes=None`` — whole
    leaves in one step, the shape an ad-hoc per-replica device_put
    sweep degenerates to) streams a 1 MB host weight tree against a
    64 KB declared budget.  ``ReplicaSet.spawn`` always streams through
    the size-capped cached plan; this proves the budget pin fires when
    someone bypasses the cap."""
    from ..inference.fleet import FleetConfig, ReplicaSet

    host = {"w": np.ones((512, 512), np.float32)}     # 1 MB, host-side
    rs = ReplicaSet(host, engine_factory=lambda p: None,
                    config=FleetConfig(max_transient_bytes=None))
    return rs.check_delivery_budget(
        budget_bytes=64 << 10, exemptions=(),
        target="seeded:MEM001[replica_delivery]")


def seeded_kv_handoff_over_budget() -> Report:
    """MEM001 on the round-16 disaggregated KV-handoff entry: an
    UNBOUNDED handoff plan (``max_transient_bytes=None`` — whole page
    tree in one step, the shape an ad-hoc per-handoff device_put sweep
    degenerates to) streams a 256 KB fp32 KV page tree against a 64 KB
    declared budget.  ``DisaggRouter`` always streams through the
    planner's size-capped cached plan; this proves the budget pin
    fires when someone bypasses the cap."""
    from ..inference.disagg import KVHandoffPlanner

    # [L=2, npages=8, kvh=2, page=16, d=64] fp32 = 128 KB per pool side
    tree = {"k": np.ones((2, 8, 2, 16, 64), np.float32),
            "v": np.ones((2, 8, 2, 16, 64), np.float32)}
    planner = KVHandoffPlanner(max_transient_bytes=None)
    return planner.check_handoff_budget(
        tree, budget_bytes=64 << 10, exemptions=(),
        target="seeded:MEM001[kv_handoff]")


def seeded_while_peeling() -> Report:
    """HLO003 over a captured-HLO sample: a scanned body's all-gather
    duplicated TWICE into the hosting computation (XLA's peel+unroll
    cannot be forced portably on one CPU device, so — like HLO001 — the
    fixture proves the detector; the compile-and-scan plumbing rides
    the clean flagship sweeps)."""
    from .passes.hlo_checks import scan_while_peeling

    sample = """\
HloModule peeled_layer_stack

%body.7 (p.1: (f32[128,8], u32[])) -> (f32[128,8], u32[]) {
  %p.1 = (f32[128,8], u32[]) parameter(0)
  %x.1 = f32[128,8] get-tuple-element(%p.1), index=0
  %ag.1 = f32[256,8] all-gather(%x.1), replica_groups={}, dimensions={0}
  %r.1 = f32[128,8] slice(%ag.1), slice={[0:128], [0:8]}
}

%cond.7 (c.1: (f32[128,8], u32[])) -> pred[] {
  %c.1 = (f32[128,8], u32[]) parameter(0)
}

ENTRY %main.42 (a.1: f32[128,8]) -> f32[128,8] {
  %a.1 = f32[128,8] parameter(0)
  %ag.peel0 = f32[256,8] all-gather(%a.1), replica_groups={}, dimensions={0}
  %ag.peel1 = f32[256,8] all-gather(%a.1), replica_groups={}, dimensions={0}
  %t.1 = (f32[128,8], u32[]) tuple(%a.1)
  %w.1 = (f32[128,8], u32[]) while(%t.1), condition=%cond.7, body=%body.7
  %out.1 = f32[128,8] get-tuple-element(%w.1), index=0
}
"""
    findings = scan_while_peeling(sample)
    return Report(target="seeded:HLO003", findings=findings,
                  passes_run=("hlo_post_checks",))


# ---------------------------------------------------------------------------
# health_probe (round-17: the training health guardian)
# ---------------------------------------------------------------------------


def seeded_unfused_health_probe() -> Report:
    """HEALTH001: a "probe" whose output carries TREE-SIZED buffers —
    per-leaf finite masks returned alongside the scalars (the classic
    host-style detector ported naively: materialize, then look).  The
    fused contract is a handful of scalars + one bucket vector; the
    budget here is the UNPROBED step's measured peak + a deliberately
    small overhead, so the mask tree blows straight through it."""
    from .core import AnalysisContext
    from .passes.health_probe import compiled_peak_bytes

    params = {f"w{i}": jnp.ones((128, 128), jnp.float32)
              for i in range(8)}
    grads = {k: v * 1e-3 for k, v in params.items()}

    @jax.jit
    def base(params, grads):
        new = {k: v - 1e-3 * grads[k] for k, v in params.items()}
        return sum(jnp.sum(g) for g in grads.values()), new

    @jax.jit
    def bug(params, grads):
        new = {k: v - 1e-3 * grads[k] for k, v in params.items()}
        loss = sum(jnp.sum(g) for g in grads.values())
        probe = {
            "grad_norm": jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                      for g in grads.values())),
            # the seeded bug: the probe OUTPUT is a full tree of masks
            "finite_mask": {k: jnp.isfinite(g) for k, g in grads.items()},
        }
        return loss, new, probe

    baseline = compiled_peak_bytes(
        AnalysisContext(base, (params, grads), {}))
    return check(bug, params, grads, passes=["health_probe"],
                 exemptions=(), target="seeded:HEALTH001",
                 options={"health_probe":
                          {"baseline_peak_bytes": baseline,
                           "probe_overhead_bytes": 16 << 10}})


def seeded_collective_health_probe() -> Report:
    """HEALTH002: a probe that psums its grad-norm across the mesh
    inside an entry whose declared baseline carries ZERO collectives —
    communication the probe added (on the single-chip flagship, ANY
    collective is the regression)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = _mesh(2)

    def body(g):
        gnorm = jnp.sqrt(jax.lax.psum(jnp.sum(g * g), "x"))  # the bug
        return g * 2.0, gnorm

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),),
                   out_specs=(P("x"), P()), check_vma=False)
    x = jnp.ones((4 * mesh.shape["x"], 8), jnp.float32)
    return check(fn, x, passes=["health_probe"], exemptions=(),
                 target="seeded:HEALTH002",
                 options={"health_probe": {"baseline_collectives": {}}})


# ---------------------------------------------------------------------------
# sharding_consistency (round-14: the Sharding Doctor)
# ---------------------------------------------------------------------------


def seeded_gspmd_reshard() -> Report:
    """SHARD001: a step whose body re-constrains a sharded operand to
    the TRANSPOSED spec — GSPMD silently lowers the layout conversion
    to an all-to-all no schedule ever declared (the reshard class the
    unified-partitioning refactor must see, not discover on a TPU
    profile)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh(2)
    x = jax.device_put(jnp.ones((8, 8), jnp.float32),
                       NamedSharding(mesh, P("x", None)))

    @jax.jit
    def bug(a):
        b = jax.lax.with_sharding_constraint(
            a * 2.0, NamedSharding(mesh, P(None, "x")))   # spec transpose
        return b.sum()

    return check(bug, x, passes=["sharding_consistency"], exemptions=(),
                 target="seeded:SHARD001",
                 options={"sharding_consistency":
                          {"audit_resharding": True}})


def seeded_replication_waste() -> Report:
    """SHARD002: a 1 MB leaf left fully replicated on a 4-way axis its
    dims divide — 0.75 MB of per-device residency the plan ignores."""
    from ..parallel.specs import SpecLayout, TensorSpec
    from .sharding import check_layout

    layout = SpecLayout(
        mesh_axes=(("x", 4),),
        entries={"model.layers.*.mlp.up_proj.weight": TensorSpec(
            shape=(512, 512), dtype="float32", dim_axes=((), ()))})
    return check_layout(layout, replicated_min_bytes=256 << 10,
                        exemptions=(), target="seeded:SHARD002")


def seeded_cross_stack_divergence() -> Report:
    """SHARD003: two stacks mapping the same logical parameter to
    TRANSPOSED specs — every cross-stack handoff of that leaf pays a
    silent reshard."""
    from ..parallel.specs import SpecLayout, TensorSpec
    from .sharding import check_cross_stack

    key = "model.layers.*.self_attn.q_proj.weight"
    a = SpecLayout(mesh_axes=(("sharding", 2), ("mp", 2)),
                   entries={key: TensorSpec(
                       shape=(64, 64), dtype="float32",
                       dim_axes=(("sharding",), ("mp",)))})
    b = SpecLayout(mesh_axes=(("sharding", 2), ("mp", 2)),
                   entries={key: TensorSpec(
                       shape=(64, 64), dtype="float32",
                       dim_axes=(("mp",), ("sharding",)))})
    return check_cross_stack({"gspmd": a, "overlap": b}, exemptions=(),
                             target="seeded:SHARD003")


def seeded_shard_padding() -> Report:
    """SHARD004: a hand-written spec sharding a 129-row leaf 4 ways —
    XLA pads every shard to 33 rows; the at-rest rule would have fallen
    back to replication, a hand-rolled NamedSharding bypasses it (jax
    refuses such a device_put, but jit in_shardings and manual specs
    still reach it)."""
    from ..parallel.specs import SpecLayout, TensorSpec
    from .sharding import check_layout

    layout = SpecLayout(
        mesh_axes=(("x", 4),),
        entries={"lm_head.weight": TensorSpec(
            shape=(129, 64), dtype="float32",
            dim_axes=(("x",), ()))})
    return check_layout(layout, exemptions=(), target="seeded:SHARD004")


def seeded_unsharded_update() -> Report:
    """SHARD005: a flat optimizer update chain on a mesh with NO
    cross-replica sharding pin — the update runs replicated
    (2004.13336) and the unconstrained concat→update→slice layout is
    the exact region the 0.4.x GSPMD partitioner mis-lowers (PR 5's
    hand fix; Adam.apply_flat's flat_sharding is the pin this proves
    the doctor demands)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh(2)
    m = jax.device_put(jnp.ones((1 << 15,), jnp.float32),
                       NamedSharding(mesh, P()))

    @jax.jit
    def bug(master, g):
        return master - 0.1 * g        # no flat_sharding pin anywhere

    return check(bug, m, m * 0.5, passes=["sharding_consistency"],
                 exemptions=(), target="seeded:SHARD005",
                 options={"sharding_consistency":
                          {"expect_update_pin": True,
                           "update_min_bytes": 1 << 10}})


def seeded_schedule_divergence() -> Report:
    """SCHED001: a hand-written stack table whose q_proj placement is
    TRANSPOSED against the unified schedule's derivation — the
    byte-identity gate of the round-19 unified-partitioning refactor
    (deriving three stacks from one schedule object is only safe while
    the derivation moves NO placement)."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel.schedule import PartitionSchedule
    from ..parallel.specs import SpecLayout, TensorSpec
    from .sharding import check_schedule_derivation

    devs = jax.devices()
    if len(devs) < 4:
        raise FixtureUnavailable("needs >= 4 devices")
    mesh = Mesh(np.asarray(devs[:4], dtype=object).reshape(2, 2),
                ("sharding", "mp"))
    key = "model.layers.*.self_attn.q_proj.weight"
    sched = PartitionSchedule.from_plan(
        mesh, {key: (64, 64)}, lambda n: P("sharding", "mp"))
    hand = SpecLayout(
        mesh_axes=(("sharding", 2), ("mp", 2)),
        entries={key: TensorSpec(shape=(64, 64), dtype="float32",
                                 dim_axes=(("mp",), ("sharding",)))})
    return check_schedule_derivation(sched, {"gspmd": hand},
                                     exemptions=(),
                                     target="seeded:SCHED001")


# ---------------------------------------------------------------------------
# lock_discipline (round-21: the Concurrency Doctor)
# ---------------------------------------------------------------------------


def _race_report(code: str, src: str) -> Report:
    import textwrap

    from .passes.lock_discipline import analyze_source

    rel = f"seeded/{code.lower()}.py"
    findings = analyze_source(textwrap.dedent(src), rel)
    return Report(target=f"seeded:{code}", findings=findings,
                  passes_run=("lock_discipline",))


def seeded_unguarded_write() -> Report:
    """RACE001: a counter bumped under its lock but reset lock-free —
    the reset can interleave between the bump's read and write."""
    return _race_report("RACE001", """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.value = 0

            def bump(self):
                with self._lock:
                    self.value += 1

            def reset(self):
                self.value = 0
        """)


def seeded_lock_order_inversion() -> Report:
    """RACE002: one path nests send->recv, the other holds recv and
    reaches send THROUGH A HELPER CALL — the cross-method edge the
    analyzer must close over, and the classic two-thread deadlock."""
    return _race_report("RACE002", """
        import threading

        class Transfer:
            def __init__(self):
                self._send_lock = threading.Lock()
                self._recv_lock = threading.Lock()
                self.sent = 0
                self.received = 0

            def one(self):
                with self._send_lock:
                    with self._recv_lock:
                        self.sent += 1
                        self.received += 1

            def _locked_step(self):
                with self._send_lock:
                    self.sent += 1

            def other(self):
                with self._recv_lock:
                    self._locked_step()
                    self.received += 1
        """)


def seeded_blocking_under_lock() -> Report:
    """RACE003: a sleep inside the critical section — every other
    tick blocks on the lock for the full sleep (the serving-tick
    latency/deadlock hazard class: jit compile, collective, recv,
    fsync under a lock)."""
    return _race_report("RACE003", """
        import threading
        import time

        class Poller:
            def __init__(self):
                self._lock = threading.Lock()
                self.last = None

            def poll(self):
                with self._lock:
                    time.sleep(0.05)
                    self.last = 1
        """)


def seeded_check_then_act() -> Report:
    """RACE004: the PRE-FIX watchdog handler/flag race, minimized —
    ``complete`` checks ``task.timed_out`` OUTSIDE the lock, then
    acquires it to act, while the scanner flags ``timed_out`` under
    the same lock: the flag can flip between check and act, yielding
    a task both completed and flagged hung (the bug fixed in PRs 6-7;
    the pass must catch the bug we actually shipped)."""
    return _race_report("RACE004", """
        import threading

        class Manager:
            def __init__(self):
                self._lock = threading.Lock()
                self.tasks = {}

            def complete(self, task):
                if task.timed_out:          # check OUTSIDE the lock
                    return
                with self._lock:            # act UNDER it
                    task.done = True
                    self.tasks.pop(task.seq, None)

            def _scan(self):
                with self._lock:
                    for t in list(self.tasks.values()):
                        t.timed_out = True
        """)


SEEDED = {
    "COLL001": seeded_collective_order,
    "COLL002": seeded_ppermute_race,
    "COMM001": seeded_collective_budget,
    "COMM002": seeded_unscheduled_collective,
    "COMM003": seeded_ppermute_ring_order,
    # round-15: post-codec bytes-on-the-wire — a silently-disabled
    # quantized-DCN codec blows the declared DCN wire budget
    "COMM004": seeded_codec_disabled,
    # round-18: a second COMM004 proof on the EP MoE dispatch — the
    # codec silently off on the expert all-to-all blows the DCN wire
    # budget the quantized dispatch schedule honors
    "COMM004[moe_dispatch]": seeded_moe_dispatch_codec_off,
    # round-20: a third COMM004 proof on the DROPLESS dispatch
    # composite — codec silently off on the payload leg (counts stay
    # uncoded by design) blows the dropless step's measured DCN budget
    "COMM004[moe_dropless]": seeded_moe_dropless_codec_off,
    "DT001": seeded_fp32_matmul,
    "DT002": seeded_f64_leak,
    "DT003": seeded_fp32_carry,
    "DON001": seeded_undonated_state,
    "DON002": seeded_use_after_donate,
    "RT001": seeded_weak_type_churn,
    "RT002": seeded_signature_churn,
    "HLO001": seeded_involuntary_remat,
    "HLO002": seeded_full_param_allgather,
    "HLO003": seeded_while_peeling,
    # round-17: the training health guardian's probe-fusion contract —
    # a tree-sized probe output blows the fusion budget, a psum'd probe
    # adds collectives the baseline never had
    "HEALTH001": seeded_unfused_health_probe,
    "HEALTH002": seeded_collective_health_probe,
    "MEM001": seeded_peak_over_budget,
    # a second MEM001 proof on the round-11 serving entry — registry
    # keys carry a [variant] suffix; consumers expect the BARE code
    # before the bracket
    "MEM001[prefill_chunk]": seeded_prefill_chunk_over_budget,
    # a third on the round-12 reshard entry: an unbounded redistribution
    # plan overruns its declared transient budget
    "MEM001[reshard_plan]": seeded_reshard_over_budget,
    # a fourth on the round-13 replica weight-delivery entry: an
    # unbounded fleet delivery plan overruns its declared budget
    "MEM001[replica_delivery]": seeded_replica_delivery_over_budget,
    # a fifth on the round-16 disaggregated KV-handoff entry: an
    # unbounded handoff plan overruns its declared transient budget
    "MEM001[kv_handoff]": seeded_kv_handoff_over_budget,
    "MEM002": seeded_host_round_trip,
    # round-14: the Sharding Doctor (cross-stack partition consistency)
    "SHARD001": seeded_gspmd_reshard,
    "SHARD002": seeded_replication_waste,
    "SHARD003": seeded_cross_stack_divergence,
    "SHARD004": seeded_shard_padding,
    "SHARD005": seeded_unsharded_update,
    # round-19: the unified partitioning schedule's byte-identity gate —
    # a derivation that moves any placement against the hand-written
    # stack tables must fire, or deriving three stacks from one
    # schedule object is unverified
    "SCHED001": seeded_schedule_divergence,
    # round-21: the Concurrency Doctor (host-side lock discipline);
    # RACE004 is the minimized pre-fix watchdog race
    "RACE001": seeded_unguarded_write,
    "RACE002": seeded_lock_order_inversion,
    "RACE003": seeded_blocking_under_lock,
    "RACE004": seeded_check_then_act,
}


# Every fixture compiles a small seeded program, and one tier-1 process
# reaches the registry from THREE consumers (the parametrized fixture
# test, self_check inside the doctor smoke leg, and the per-round trace
# legs).  Reports are read-only, the programs deterministic — memoize
# per (code, backend) so the sweep is paid once per process (round-17
# tier-1 wall management).  FixtureUnavailable is never cached: an
# environment gaining devices mid-process should un-skip.
_REPORT_MEMO: dict = {}


def _memoized_fixture(code, fn):
    def run() -> Report:
        key = (code, jax.default_backend(), len(jax.devices()))
        rep = _REPORT_MEMO.get(key)
        if rep is None:
            rep = fn()
            _REPORT_MEMO[key] = rep
        return rep

    run.__name__ = fn.__name__
    run.__doc__ = fn.__doc__
    run.__wrapped__ = fn
    return run


SEEDED = {code: _memoized_fixture(code, fn)
          for code, fn in SEEDED.items()}
