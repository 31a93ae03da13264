"""HBM memory engine (round-10 tentpole, parallel/memory.py).

Acceptance bar: residency is NEVER numerically divergent — every point
on the remat/offload lattice (named checkpoint policy x optimizer
residency x activation offload) reproduces the flat fused step
bit-for-bit on one device and within the established mesh tolerance on
the dp2 x sharding2 x mp2 virtual mesh; the host-offloaded streamed
AdamW matches the device-resident flat apply on the plain, grad-accum
and masked paths; the memory_budget pass's seeded fixtures fire exactly
their codes; the autotuner is monotone in the budget; and the offloaded
step keeps the donation contract (DON001-clean)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, build_train_step
from paddle_tpu.models.llama import apply_llama_sharding, llama_decay_mask
from paddle_tpu.parallel import memory as M
from paddle_tpu.parallel.memory import (MemoryConfig, MEMORY_LATTICE,
                                        choose_memory_config,
                                        init_offloaded_state,
                                        measure_step_memory,
                                        offload_flat_state,
                                        gather_offloaded_state,
                                        tune_memory_config)


def _need(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


def _cfg():
    return LlamaConfig.debug(vocab=128, hidden=32, layers=2, heads=4,
                             kv_heads=2, inter=64, max_pos=64)


@pytest.fixture(scope="module")
def flat_ref():
    """(cfg, model, state0, mask, ids, labels, ref_loss, ref_params)
    from the flat fused-AdamW fp32 step — the baseline every lattice
    point must reproduce.  Explicit seeding (module-scoped fixtures
    must not lean on the autouse per-test seed)."""
    paddle.seed(20260810)
    np.random.seed(20260810)
    cfg = _cfg()
    model = LlamaForCausalLM(cfg)
    state0 = {k: jnp.copy(v) for k, v in model.functional_state().items()}
    mask = llama_decay_mask(model)
    rng = np.random.RandomState(7)
    ids = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(model, opt, compute_dtype=jnp.float32)
    p = {k: jnp.copy(v) for k, v in state0.items()}
    loss, newp, _ = step(
        p, opt.init_flat_state({k: jnp.copy(v) for k, v in state0.items()},
                               decay_mask=mask),
        0, 1e-3, ids, labels)
    return (cfg, model, state0, mask, ids, labels, float(loss),
            {k: np.asarray(v) for k, v in newp.items()})


def _deep(t):
    return {k: jnp.copy(v) for k, v in t.items()}


def _state_for(opt, state0, mask, mc):
    if mc.optimizer_residency == "host":
        return init_offloaded_state(opt, _deep(state0), decay_mask=mask,
                                    bucket_bytes=mc.stream_bucket_bytes)
    return opt.init_flat_state(_deep(state0), decay_mask=mask)


# ---------------------------------------------------------------------------
# lattice parity — single device (bit-equal) and mesh (established tol)
# ---------------------------------------------------------------------------


# round-16 tier policy: the full lattice sweeps are tier-2 breadth —
# tier-1 keeps the most-exercising point per sweep (offload/host: host
# residency + the offload checkpoint policy + bucket streaming in one)
# and the autotuner/doctor gates; the other points re-assert under
# ``-m slow``.
def _lattice_params(points, keep_label):
    return [pytest.param(m, id=m.label(),
                         marks=([] if m.label() == keep_label
                                else [pytest.mark.slow]))
            for m in points]


@pytest.mark.parametrize("mc", _lattice_params(MEMORY_LATTICE,
                                               "offload/host"))
def test_lattice_parity_single_device(flat_ref, mc):
    """Every lattice point matches the flat baseline on one device:
    remat recomputes the identical fp32 ops, activation offload and
    host residency only change WHERE bytes live.  The loss is bit-equal;
    the updated params agree to fp32 last digits (atol 1e-5 at |w| <= 4:
    XLA:CPU fuses the rematerialized backward differently, observed
    max 2.4e-6 across this file's and bench --smoke's configs)."""
    cfg, model, state0, mask, ids, labels, ref_loss, ref_params = flat_ref
    # stream buckets small enough that every group actually splits
    mc = MemoryConfig(**{**mc.to_json(), "stream_bucket_bytes": 8 << 10})
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(model, opt, compute_dtype=jnp.float32,
                            memory=mc)
    loss, newp, newst = step(_deep(state0),
                             _state_for(opt, state0, mask, mc),
                             0, 1e-3, ids, labels)
    assert float(loss) == ref_loss, mc.label()
    for k in ref_params:
        np.testing.assert_allclose(np.asarray(newp[k]), ref_params[k],
                                   rtol=0, atol=1e-5,
                                   err_msg=f"{mc.label()} {k}")
    if mc.optimizer_residency == "host":
        assert M.state_is_offloaded(newst)


_MESH_POINTS = [
    MemoryConfig(remat="dots"),
    MemoryConfig(remat="names", optimizer_residency="host",
                 stream_bucket_bytes=8 << 10),
    MemoryConfig(remat="offload", optimizer_residency="host",
                 stream_bucket_bytes=8 << 10),
    MemoryConfig(remat="none", optimizer_residency="host",
                 activation_offload=True, stream_bucket_bytes=8 << 10),
]


@pytest.mark.parametrize("mc", _lattice_params(_MESH_POINTS,
                                               "offload/host"))
def test_lattice_parity_mesh(flat_ref, mc):
    """Lattice points under GSPMD on dp2 x sharding2 x mp2: same bar as
    the overlap engine's parity suite (mesh reductions reorder, so
    allclose at the established tolerance, not bit-equal)."""
    _need(8)
    from jax.sharding import Mesh

    cfg, model, state0, mask, ids, labels, ref_loss, ref_params = flat_ref
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        2, 2, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(model, opt, mesh=mesh,
                            compute_dtype=jnp.float32, memory=mc)
    loss, newp, _ = step(_deep(state0), _state_for(opt, state0, mask, mc),
                         0, 1e-3, ids, labels)
    np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
    for k in ref_params:
        np.testing.assert_allclose(np.asarray(newp[k]), ref_params[k],
                                   atol=5e-4, rtol=2e-3,
                                   err_msg=(mc.label(), k))


@pytest.mark.slow
def test_overlap_stack_named_remat_parity(flat_ref):
    """Tier-2 (round-16 re-tier: overlap-stack twin; tier-1 home: test_overlap.test_overlap_remat_parity on the same policy).  MemoryConfig's named policy drives the OVERLAP stack's remat
    scan too (the checkpoint_name tags live inside decoder_layer_tp):
    overlap engine + names-remat + host-offloaded AdamW vs the flat
    baseline."""
    _need(8)
    from jax.sharding import Mesh

    from paddle_tpu.parallel.overlap import OverlapConfig

    cfg, model, state0, mask, ids, labels, ref_loss, ref_params = flat_ref
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        2, 2, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mc = MemoryConfig(remat="names", optimizer_residency="host",
                      stream_bucket_bytes=8 << 10)
    step = build_train_step(
        model, opt, mesh=mesh, compute_dtype=jnp.float32,
        overlap=OverlapConfig(collective_matmul_min_out_elems=1),
        memory=mc)
    loss, newp, _ = step(_deep(state0), _state_for(opt, state0, mask, mc),
                         0, 1e-3, ids, labels)
    np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
    for k in ref_params:
        np.testing.assert_allclose(np.asarray(newp[k]), ref_params[k],
                                   atol=5e-4, rtol=2e-3, err_msg=k)


# ---------------------------------------------------------------------------
# offloaded AdamW — accum, masked, and optimizer-level parity
# ---------------------------------------------------------------------------


def test_offloaded_adamw_accum_parity(flat_ref):
    """Host-offloaded streamed AdamW under gradient accumulation: the
    merged-grad update must match the device-resident flat apply
    bit-for-bit (same fold schedule, same elementwise math)."""
    cfg, model, state0, mask, ids, labels, _, _ = flat_ref
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    ids2 = ids.reshape(2, 4, 16)
    lab2 = labels.reshape(2, 4, 16)
    flat = build_train_step(model, opt, compute_dtype=jnp.float32,
                            accum_steps=2)
    rl, rp, _ = flat(_deep(state0),
                     opt.init_flat_state(_deep(state0), decay_mask=mask),
                     0, 1e-3, ids2, lab2)
    mc = MemoryConfig(optimizer_residency="host",
                      stream_bucket_bytes=8 << 10)
    off = build_train_step(model, opt, compute_dtype=jnp.float32,
                           accum_steps=2, memory=mc)
    l, p, _ = off(_deep(state0), _state_for(opt, state0, mask, mc),
                  0, 1e-3, ids2, lab2)
    assert float(l) == float(rl)
    for k in rp:
        assert np.array_equal(np.asarray(p[k]), np.asarray(rp[k])), k


@pytest.mark.slow
def test_offloaded_adamw_masked_parity(flat_ref):
    """Tier-2 (round-16 re-tier: decay-mask breadth over the streamed apply; tier-1 home: the accum-parity leg + DON001 offload gate).  The token-weighted masked accum path (fp32 carry by design)
    through the streamed optimizer — same numbers as the flat apply."""
    cfg, model, state0, mask, ids, labels, _, _ = flat_ref
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    ids2 = ids.reshape(2, 4, 16)
    lab2 = labels.reshape(2, 4, 16)
    amask = np.ones((2, 4, 16), np.int32)
    amask[:, :, -5:] = 0
    flat = build_train_step(model, opt, compute_dtype=jnp.float32,
                            accum_steps=2)
    rl, rp, _ = flat(_deep(state0),
                     opt.init_flat_state(_deep(state0), decay_mask=mask),
                     0, 1e-3, ids2, lab2, amask)
    mc = MemoryConfig(optimizer_residency="host",
                      stream_bucket_bytes=8 << 10)
    off = build_train_step(model, opt, compute_dtype=jnp.float32,
                           accum_steps=2, memory=mc)
    l, p, _ = off(_deep(state0), _state_for(opt, state0, mask, mc),
                  0, 1e-3, ids2, lab2, amask)
    assert float(l) == float(rl)
    for k in rp:
        assert np.array_equal(np.asarray(p[k]), np.asarray(rp[k])), k


def test_offloaded_apply_matches_apply_flat_bf16_master():
    """Optimizer-level parity with bf16 params (fp32 masters IN the
    streamed state): apply_flat vs apply_flat_offloaded over several
    steps, arbitrary grads, tiny buckets so every group splits."""
    paddle.seed(5)
    rng = np.random.default_rng(5)
    shapes = {"a": (33, 7), "b": (128,), "c": (9, 9, 3)}
    params_f32 = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
                  for k, s in shapes.items()}
    params = {k: v.astype(jnp.bfloat16) for k, v in params_f32.items()}
    mask = {"a": True, "b": False, "c": True}
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.1,
                                 parameters=[])
    flat = opt.init_flat_state(params, decay_mask=mask,
                               master_from=params_f32)
    off = offload_flat_state(flat, bucket_bytes=256)
    p1, p2 = dict(params), dict(params)
    st1, st2 = flat, off
    for step in range(1, 4):
        grads = {k: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
                 for k, s in shapes.items()}
        p1, st1 = opt.apply_flat(p1, grads, st1, 1e-2, step,
                                 decay_mask=mask)
        p2, st2 = M.apply_flat_offloaded(opt, p2, grads, st2, 1e-2,
                                         step, decay_mask=mask)
        for k in p1:
            assert np.array_equal(np.asarray(p1[k]), np.asarray(p2[k])), \
                (step, k)
    # the streamed state's flat gather matches the device-resident one
    g2 = gather_offloaded_state(st2)
    for gname, gs in st1["__flat__"].items():
        for key, arr in gs.items():
            assert np.array_equal(np.asarray(arr),
                                  np.asarray(g2["__flat__"][gname][key])), \
                (gname, key)


def test_offload_state_roundtrip_and_shapes():
    paddle.seed(6)
    params = {"w": jnp.arange(1000, dtype=jnp.float32)}
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=[])
    flat = opt.init_flat_state(params)
    off = offload_flat_state(flat, bucket_bytes=1024)   # 256 elems/bucket
    (gname, gs), = off["__offload__"].items()
    assert [b.shape[0] for b in gs["moment1"]] == [256, 256, 256, 232]
    assert M.state_is_offloaded(off) and not M.state_is_offloaded(flat)
    back = gather_offloaded_state(off)
    for key in flat["__flat__"][gname]:
        assert np.array_equal(np.asarray(flat["__flat__"][gname][key]),
                              np.asarray(back["__flat__"][gname][key]))


def test_stream_bucket_plan_rules():
    assert M.stream_bucket_plan(10, 4, 16) == [(0, 4), (4, 4), (8, 2)]
    assert M.stream_bucket_plan(10, 4, 0) == [(0, 10)]   # no-cap: 1 bucket
    assert M.stream_bucket_plan(0, 4, 16) == []
    assert M.stream_bucket_plan(3, 8, 4) == [(0, 1), (1, 1), (2, 1)]


def test_memory_config_validation():
    with pytest.raises(ValueError, match="remat"):
        MemoryConfig(remat="sometimes")
    with pytest.raises(ValueError, match="residency"):
        MemoryConfig(optimizer_residency="gpu")
    use, pol = MemoryConfig(remat="none").resolve_remat()
    assert use is False and pol is None
    use, pol = MemoryConfig(remat="none",
                            activation_offload=True).resolve_remat()
    assert use is True and pol is not None
    for name in ("dots", "names", "offload", "full"):
        use, _ = MemoryConfig(remat=name).resolve_remat()
        assert use is True


@pytest.mark.slow
def test_hybrid_accepts_named_policy():
    """Tier-2 (round-16 re-tier: hybrid x memory integration breadth; tier-1 home: the kept lattice point + the hybrid remat-clean compile leg).  The hybrid stack resolves the same named policies (string or
    MemoryConfig) through the engine's translation point."""
    _need(8)
    from paddle_tpu.models.llama_hybrid import (build_hybrid_train_step,
                                                hybrid_mesh,
                                                init_hybrid_state)

    cfg = _cfg()
    mesh = hybrid_mesh(jax.devices("cpu"), pp=2)
    paddle.seed(3)
    hstate = init_hybrid_state(LlamaForCausalLM(cfg), mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=[])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    base = build_hybrid_train_step(cfg, opt, mesh,
                                   compute_dtype=jnp.float32,
                                   remat=False)
    l0, _, _ = base({k: jnp.copy(v) for k, v in hstate.items()},
                    opt.init_state({k: jnp.copy(v)
                                    for k, v in hstate.items()}),
                    0, 1e-3, ids, labels)
    named = build_hybrid_train_step(cfg, opt, mesh,
                                    compute_dtype=jnp.float32,
                                    remat="names")
    l1, _, _ = named({k: jnp.copy(v) for k, v in hstate.items()},
                     opt.init_state({k: jnp.copy(v)
                                     for k, v in hstate.items()}),
                     0, 1e-3, ids, labels)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)


# ---------------------------------------------------------------------------
# memory_budget pass + autotuner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("code", ["MEM001", "MEM002", "HLO003"])
def test_seeded_memory_fixtures_fire_exactly(code):
    from paddle_tpu.analysis.fixtures import SEEDED, FixtureUnavailable

    try:
        rep = SEEDED[code]()
    except FixtureUnavailable as e:
        pytest.skip(str(e))
    assert set(rep.codes()) == {code}, rep.summary()


def test_memory_budget_pass_clean_when_within():
    import paddle_tpu.analysis as A

    @jax.jit
    def fn(a):
        return (a * 2.0).sum()

    a = jnp.ones((64, 64), jnp.float32)
    rep = A.check(fn, a, passes=["memory_budget"], exemptions=(),
                  options={"memory_budget": {"hbm_bytes": 64 << 20,
                                             "host_transfer_bytes": 0}},
                  target="within_budget")
    assert rep.ok, rep.summary()


def test_memory_budget_pass_skips_without_declaration():
    import paddle_tpu.analysis as A

    @jax.jit
    def fn(a):
        return a.sum()

    rep = A.check(fn, jnp.ones((8,)), passes=["memory_budget"],
                  exemptions=(), target="undeclared")
    assert rep.ok and "memory_budget" in rep.skipped


def test_hlo003_allows_single_prologue_copy():
    """One outside copy of a body collective is the engine's own
    double-buffered prologue — allowed by default; two is a peel."""
    from paddle_tpu.analysis.passes.hlo_checks import scan_while_peeling

    one_copy = """\
%body.1 (p: (f32[8], u32[])) -> (f32[8], u32[]) {
  %ag = f32[16] all-gather(%x), dimensions={0}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %ag.pre = f32[16] all-gather(%a), dimensions={0}
  %w = (f32[8], u32[]) while(%t), condition=%c, body=%body.1
}
"""
    assert scan_while_peeling(one_copy) == []
    assert len(scan_while_peeling(one_copy, max_peeled_copies=0)) == 1


@pytest.fixture(scope="module")
def tune_records():
    """One lattice measurement set shared by the autotune tests (each
    point compiles a full debug step; measure once)."""
    paddle.seed(11)
    cfg = LlamaConfig.debug(vocab=64, hidden=32, layers=2, heads=4,
                            kv_heads=2, inter=64, max_pos=32)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    params = {k: jnp.copy(v) for k, v in model.functional_state().items()}
    mask = llama_decay_mask(model)
    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    lattice = (MemoryConfig(remat="none"),
               MemoryConfig(remat="dots"),
               MemoryConfig(remat="names", optimizer_residency="host",
                            stream_bucket_bytes=8 << 10),
               MemoryConfig(remat="full", optimizer_residency="host",
                            stream_bucket_bytes=8 << 10))

    def builder(mc):
        step = build_train_step(model, opt, compute_dtype=jnp.float32,
                                memory=mc)
        if mc.optimizer_residency == "host":
            st = init_offloaded_state(opt, params, decay_mask=mask,
                                      bucket_bytes=mc.stream_bucket_bytes)
        else:
            st = opt.init_flat_state(params, decay_mask=mask)
        return step, (params, st, jnp.int32(0), jnp.float32(1e-3), ids,
                      labels)

    return lattice, builder


@pytest.mark.slow
def test_tune_returns_fitting_config(tune_records):
    # tier-2 (round-16 re-tier): autotuner breadth; tier-1 home: the
    # memory_parity smoke leg gates the autotune fitting config
    lattice, builder = tune_records
    # budget below the cheapest point's peak but above the minimum:
    # the walk must skip ahead to a remat point that fits
    chosen0, records = tune_memory_config(builder, 1 << 62,
                                          lattice=lattice)
    assert chosen0 == lattice[0]        # everything fits -> cheapest
    peaks = [r["peak_bytes"] for r in records]
    tight = min(peaks) if min(peaks) < peaks[0] else peaks[-1]
    idx = choose_memory_config(records, tight)
    assert idx is not None and records[idx]["peak_bytes"] <= tight
    # impossibly small budget -> explicit None, never a silent misfit
    assert choose_memory_config(records, 1) is None


@pytest.mark.slow
def test_tune_monotone_in_budget(tune_records):
    """Tier-2 (round-16 re-tier: derived monotonicity property; tier-1 home: test_tune_returns_fitting_config on the same records).  A larger budget never picks a MORE-rematerialized (later-in-
    lattice) config: chosen index is non-increasing in the budget."""
    lattice, builder = tune_records
    _, records = tune_memory_config(builder, 1 << 62, lattice=lattice)
    peaks = sorted({r["peak_bytes"] for r in records})
    budgets = [peaks[0] - 1] + [p for p in peaks] + [peaks[-1] * 2]
    prev_idx = None
    for b in sorted(budgets):
        idx = choose_memory_config(records, b)
        if prev_idx is not None and idx is not None:
            assert idx <= prev_idx, (b, idx, prev_idx)
        if idx is not None:
            prev_idx = idx


def test_measure_step_memory_fields(flat_ref):
    cfg, model, state0, mask, ids, labels, _, _ = flat_ref
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(model, opt, compute_dtype=jnp.float32)
    stats = measure_step_memory(
        step, _deep(state0),
        opt.init_flat_state(_deep(state0), decay_mask=mask),
        jnp.int32(0), jnp.float32(1e-3), ids, labels)
    assert stats["argument_bytes"] > 0
    assert stats["peak_bytes"] >= stats["temp_bytes"]
    # donation must show up as aliasing: params + opt state flow through
    assert stats["alias_bytes"] > 0


# ---------------------------------------------------------------------------
# donation under offload
# ---------------------------------------------------------------------------


def test_don001_clean_under_offload(flat_ref):
    """The host-resident bucketed opt state must keep the donation
    contract — DON001 silent at the debug threshold, MEM checks green
    under the declared budgets."""
    import paddle_tpu.analysis as A

    cfg, model, state0, mask, ids, labels, _, _ = flat_ref
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mc = MemoryConfig(remat="names", optimizer_residency="host",
                      stream_bucket_bytes=8 << 10)
    step = build_train_step(model, opt, compute_dtype=jnp.float32,
                            memory=mc)
    params = _deep(state0)
    st = _state_for(opt, state0, mask, mc)
    rep = A.check(
        step, params, st, 0, 1e-3, ids, labels,
        passes=["donation", "memory_budget"],
        options={"donation": {"min_bytes": 4 << 10},
                 "memory_budget": {"hbm_bytes": 64 << 20,
                                   "host_transfer_bytes": 64 << 20}},
        target="memory_step_offloaded")
    assert rep.ok, rep.summary()


def test_offloaded_streaming_within_budget_and_counted(flat_ref):
    """The streamed apply's transfer tally is visible to MEM002: a
    budget below the per-step stream traffic trips it, one above stays
    clean — the audit sees real transfer bytes, not zero."""
    import paddle_tpu.analysis as A

    cfg, model, state0, mask, ids, labels, _, _ = flat_ref
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mc = MemoryConfig(optimizer_residency="host",
                      stream_bucket_bytes=8 << 10)
    step = build_train_step(model, opt, compute_dtype=jnp.float32,
                            memory=mc)
    rep = A.check(
        step, _deep(state0), _state_for(opt, state0, mask, mc),
        0, 1e-3, ids, labels, passes=["memory_budget"], exemptions=(),
        options={"memory_budget": {"host_transfer_bytes": 1}},
        target="stream_budget_trip")
    assert any(f.code == "MEM002" for f in rep.findings), rep.summary()
