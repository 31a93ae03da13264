"""Communication-overlap engine (round-9 tentpole, parallel/overlap.py).

Acceptance bar: overlap-on is NEVER numerically divergent — every lever
(layer-ahead ZeRO-3 gather prefetch, bucketed grad reduce-scatter,
ppermute-ring collective matmul, hierarchical ICI/DCN collectives) is
parity-tested against the flat GSPMD step on the 8-virtual-device
dp2 x sharding2 x mp2 mesh, plus the donation contract (the
double-buffered gather carry must not defeat DON001), the COMM002
overlap-region attribution, and the XLA overlap-flag wiring down to the
compiler's option parser.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from jax import shard_map
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, build_train_step
from paddle_tpu.models.llama import apply_llama_sharding
from paddle_tpu.parallel import overlap as OV
from paddle_tpu.parallel.overlap import OverlapConfig


def _need(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


def _cfg():
    return LlamaConfig.debug(vocab=128, hidden=32, layers=2, heads=4,
                             kv_heads=2, inter=64, max_pos=64)


@pytest.fixture(scope="module")
def flat_ref():
    """(cfg, state0, ids, labels, ref_loss, ref_params) from the flat
    single-program fp32 step — the parity baseline every lever compares
    against.  Explicit seeding: module-scoped fixtures must not depend
    on the autouse per-test seed (the round-6 flake class)."""
    paddle.seed(20260803)
    np.random.seed(20260803)
    cfg = _cfg()
    model = LlamaForCausalLM(cfg)
    state0 = {k: jnp.copy(v) for k, v in model.functional_state().items()}
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(model, opt, mesh=None,
                            compute_dtype=jnp.float32)
    p = {k: jnp.copy(v) for k, v in state0.items()}
    loss, newp, _ = step(p, opt.init_state(
        {k: jnp.copy(v) for k, v in state0.items()}), 0, 1e-3, ids,
        labels)
    return (cfg, model, state0, ids, labels, float(loss),
            {k: np.asarray(v) for k, v in newp.items()})


def _mesh8(shape=(2, 2, 2)):
    return Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        *shape), ("dp", "sharding", "mp"))


def _run_overlap(flat_ref, oc, mesh_shape=(2, 2, 2), remat=False,
                 attn_mask=None):
    cfg, model, state0, ids, labels, ref_loss, ref_params = flat_ref
    mesh = _mesh8(mesh_shape)
    apply_llama_sharding(model, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(model, opt, mesh=mesh,
                            compute_dtype=jnp.float32, overlap=oc,
                            remat=remat)
    p = {k: jnp.copy(v) for k, v in state0.items()}
    st = opt.init_state({k: jnp.copy(v) for k, v in state0.items()})
    if attn_mask is not None:
        loss, newp, _ = step(p, st, 0, 1e-3, ids, labels, attn_mask)
    else:
        loss, newp, _ = step(p, st, 0, 1e-3, ids, labels)
    return float(loss), {k: np.asarray(v) for k, v in newp.items()}


def _assert_parity(got_loss, got_params, ref_loss, ref_params):
    np.testing.assert_allclose(got_loss, ref_loss, rtol=1e-5)
    for k in ref_params:
        # atol: AdamW's sign-amplification of attention-backend numeric
        # noise, same bar as tests/test_llama_hybrid.py
        np.testing.assert_allclose(got_params[k], ref_params[k],
                                   atol=5e-4, rtol=2e-3, err_msg=k)


# ---------------------------------------------------------------------------
# per-lever parity on dp2 x sharding2 x mp2
# ---------------------------------------------------------------------------


# round-16 tier policy: tier-1 keeps the all-levers-on point (it
# exercises prefetch + bucketing + collective matmul + hierarchy at
# once); the single-lever ablations re-assert under ``-m slow``
@pytest.mark.parametrize("lever,oc", [
    ("full", OverlapConfig(collective_matmul_min_out_elems=1)),
    pytest.param("no_prefetch",
                 OverlapConfig(prefetch=False,
                               collective_matmul_min_out_elems=1),
                 marks=pytest.mark.slow),
    pytest.param("unbucketed",
                 OverlapConfig(bucket_bytes=0,
                               collective_matmul_min_out_elems=1),
                 marks=pytest.mark.slow),
    pytest.param("no_collective_matmul",
                 OverlapConfig(collective_matmul=False),
                 marks=pytest.mark.slow),
    pytest.param("flat_collectives",
                 OverlapConfig(prefetch=False, collective_matmul=False,
                               hierarchical="off"),
                 marks=pytest.mark.slow),
])
def test_overlap_lever_parity(flat_ref, lever, oc):
    _need(8)
    loss, params = _run_overlap(flat_ref, oc)
    _assert_parity(loss, params, flat_ref[5], flat_ref[6])


@pytest.mark.slow
def test_overlap_hierarchical_parity(flat_ref):
    """Tier-2 (round-16 re-tier: hier-schedule twin; tier-1 home: test_codec fake-2-slice coded/uncoded parity on the same schedule).  Two-stage ICI/DCN collectives on a fake 2-slice sharding axis
    (sharding=4 split 2x2 via slice_map) — exact parity with the flat
    baseline."""
    _need(8)
    oc = OverlapConfig(hierarchical="on", slice_map=(0, 0, 1, 1),
                       collective_matmul_min_out_elems=1)
    loss, params = _run_overlap(flat_ref, oc, mesh_shape=(1, 4, 2))
    _assert_parity(loss, params, flat_ref[5], flat_ref[6])


def test_overlap_remat_parity(flat_ref):
    """remat=True moves the gather inside the checkpointed body
    (backward re-gathers, unroll-2 overlap window) — same numbers."""
    _need(8)
    loss, params = _run_overlap(
        flat_ref, OverlapConfig(collective_matmul_min_out_elems=1),
        remat=True)
    _assert_parity(loss, params, flat_ref[5], flat_ref[6])


@pytest.mark.slow
def test_overlap_masked_parity(flat_ref):
    # tier-2 (round-16 re-tier): masked x overlap composition breadth;
    # tier-1 home: flat masked accum (test_llama) + the full-lever
    # overlap parity leg
    """Segment-id attention masks ride into the manual region's flash
    kernel; parity vs the flat masked step."""
    _need(8)
    cfg, model, state0, ids, labels, _, _ = flat_ref
    amask = np.ones(ids.shape, np.int32)
    amask[:, -5:] = 0
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    flat = build_train_step(model, opt, mesh=None,
                            compute_dtype=jnp.float32)
    rl, rp, _ = flat({k: jnp.copy(v) for k, v in state0.items()},
                     opt.init_state({k: jnp.copy(v)
                                     for k, v in state0.items()}),
                     0, 1e-3, ids, labels, amask)
    loss, params = _run_overlap(
        flat_ref, OverlapConfig(collective_matmul_min_out_elems=1),
        attn_mask=amask)
    _assert_parity(loss, params, float(rl),
                   {k: np.asarray(v) for k, v in rp.items()})


@pytest.mark.slow
def test_overlap_accum_parity(flat_ref):
    """Tier-2 (round-16 re-tier: accum x overlap breadth; tier-1 home: the memory-engine accum parity + the full-lever leg).  The overlap engine under gradient accumulation (the scan of
    micro fwd+bwd re-gathers per micro-step, ZeRO-3 semantics)."""
    _need(8)
    cfg, model, state0, ids, labels, _, _ = flat_ref
    mesh = _mesh8()
    apply_llama_sharding(model, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    flat = build_train_step(model, opt, mesh=None,
                            compute_dtype=jnp.float32, accum_steps=2)
    ids2 = ids.reshape(2, 4, 16)
    lab2 = labels.reshape(2, 4, 16)
    rl, rp, _ = flat({k: jnp.copy(v) for k, v in state0.items()},
                     opt.init_state({k: jnp.copy(v)
                                     for k, v in state0.items()}),
                     0, 1e-3, ids2, lab2)
    step = build_train_step(
        model, opt, mesh=mesh, compute_dtype=jnp.float32, accum_steps=2,
        overlap=OverlapConfig(collective_matmul_min_out_elems=1))
    l, p, _ = step({k: jnp.copy(v) for k, v in state0.items()},
                   opt.init_state({k: jnp.copy(v)
                                   for k, v in state0.items()}),
                   0, 1e-3, ids2, lab2)
    _assert_parity(float(l), {k: np.asarray(v) for k, v in p.items()},
                   float(rl), {k: np.asarray(v) for k, v in rp.items()})


# ---------------------------------------------------------------------------
# donation + doctor conformance
# ---------------------------------------------------------------------------


def test_overlap_step_donation_clean(flat_ref):
    """The double-buffered gather carry must not defeat the donation
    contract: DON001 stays silent on the overlap step at the debug
    threshold (and the COMM002 attribution sees only engine-issued
    collectives)."""
    _need(8)
    import paddle_tpu.analysis as A

    cfg, model, state0, ids, labels, _, _ = flat_ref
    mesh = _mesh8()
    apply_llama_sharding(model, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(
        model, opt, mesh=mesh, compute_dtype=jnp.float32,
        overlap=OverlapConfig(collective_matmul_min_out_elems=1))
    params = {k: jnp.copy(v) for k, v in state0.items()}
    rep = A.check(
        step, params, opt.init_state(params), 0, 1e-3, ids, labels,
        passes=["donation", "collective_order", "collective_budget"],
        options={"donation": {"min_bytes": 4 << 10},
                 "collective_budget": {"overlap_active": True}},
        target="overlap_step")
    assert rep.ok, rep.summary()


def test_overlap_step_without_donation_trips_don001(flat_ref):
    """Liveness: the same program with donation REMOVED must trip DON001
    — proves the clean run above is a real gate, not a vacuous one."""
    _need(8)
    import functools

    import paddle_tpu.analysis as A

    cfg, model, state0, ids, labels, _, _ = flat_ref
    mesh = _mesh8()
    apply_llama_sharding(model, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(
        model, opt, mesh=mesh, compute_dtype=jnp.float32,
        overlap=OverlapConfig())
    inner = step.__wrapped__          # the donated jit entry

    @jax.jit
    def undonated(params, opt_state, ids, labels):
        return inner(params, opt_state, jnp.int32(0), jnp.float32(1e-3),
                     ids, labels)

    params = {k: jnp.copy(v) for k, v in state0.items()}
    rep = A.check(undonated, params, opt.init_state(params), ids,
                  labels, passes=["donation"],
                  options={"donation": {"min_bytes": 4 << 10}},
                  exemptions=(), target="overlap_step_undonated")
    assert any(f.code == "DON001" for f in rep.findings), rep.summary()


# ---------------------------------------------------------------------------
# primitive-level units
# ---------------------------------------------------------------------------


def test_ring_collective_matmul_matches_psum():
    _need(8)
    mesh = Mesh(np.asarray(jax.devices()[:4], dtype=object), ("mp",))
    rng = np.random.RandomState(0)
    y = rng.randn(2, 8, 32).astype(np.float32)
    w = rng.randn(32, 16).astype(np.float32)

    def body(y, w):
        return (OV.ring_collective_matmul(y, w, "mp"),
                lax.psum(y @ w, "mp"))

    got, ref = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(None, None, "mp"), P("mp", None)),
        out_specs=(P(), P()), check_vma=False))(y, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_collective_matmul_indivisible_falls_back():
    """Output columns not divisible by the ring size: the dispatcher
    must produce the flat psum result (and not crash)."""
    _need(8)
    mesh = Mesh(np.asarray(jax.devices()[:4], dtype=object), ("mp",))
    rng = np.random.RandomState(1)
    y = rng.randn(2, 4, 16).astype(np.float32)
    w = rng.randn(16, 13).astype(np.float32)     # 13 % 4 != 0

    def body(y, w):
        return (OV.ring_collective_matmul(y, w, "mp"),
                lax.psum(y @ w, "mp"))

    got, ref = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(None, None, "mp"), P("mp", None)),
        out_specs=(P(), P()), check_vma=False))(y, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_hierarchical_rs_ag_match_flat():
    """hier_psum_scatter == flat psum_scatter (same chunk at the same
    axis position) and hier_all_gather is its exact inverse."""
    _need(8)
    from paddle_tpu.distributed.topology import hierarchical_axis

    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object),
                ("sharding",))
    hier = hierarchical_axis(mesh, "sharding",
                             slice_map=(0, 0, 0, 0, 1, 1, 1, 1))
    assert hier is not None and hier.num_slices == 2 \
        and hier.per_slice == 4
    x = np.random.RandomState(0).randn(16, 6).astype(np.float32)

    def body(x):
        h_rs = OV.hier_psum_scatter(x, "sharding", hier)
        f_rs = lax.psum_scatter(x, "sharding", scatter_dimension=0,
                                tiled=True)
        round_trip = OV.hier_all_gather(h_rs, "sharding", hier)
        flat_sum = lax.psum(x, "sharding")
        return h_rs, f_rs, round_trip, flat_sum

    h_rs, f_rs, rt, fs = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(),),
        out_specs=(P("sharding"), P("sharding"), P(), P()),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(h_rs), np.asarray(f_rs),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(rt), np.asarray(fs),
                               rtol=1e-5, atol=1e-5)


def test_hierarchical_axis_detection():
    from paddle_tpu.distributed.topology import (hierarchical_axis,
                                                 mesh_spans_slices)

    mesh = Mesh(np.asarray(jax.devices()[:4], dtype=object), ("x",))
    # CPU devices carry no slice topology -> flat
    assert hierarchical_axis(mesh, "x") is None
    assert not mesh_spans_slices(mesh, "x")
    # explicit slice map -> grouped two-stage structure
    hier = hierarchical_axis(mesh, "x", slice_map=(0, 0, 1, 1))
    assert hier.ici_groups == [[0, 1], [2, 3]]
    assert hier.dcn_groups == [[0, 2], [1, 3]]
    # unbalanced slices -> no clean residue, flat fallback
    assert hierarchical_axis(mesh, "x", slice_map=(0, 0, 0, 1)) is None
    # wrong length rejected
    with pytest.raises(ValueError):
        hierarchical_axis(mesh, "x", slice_map=(0, 1))


def test_bucket_planning_caps_and_splits():
    cfg = _cfg()
    shapes = OV.llama_layer_shapes(cfg)
    mesh = _mesh8()
    from paddle_tpu.models.llama import (plan_spec_for,
                                         _filter_spec_to_mesh)

    layout = OV.plan_layer_layout(
        shapes, mesh,
        lambda s: _filter_spec_to_mesh(plan_spec_for(s), mesh))
    order = sorted(shapes)
    # generous cap -> one bucket holding every gathered leaf
    one = OV.plan_buckets(layout, order, 2, 2, 1 << 30, 4)
    gathered = [s for s in order if layout[s].sh_dim is not None]
    assert [s for b in one for s in b] == gathered
    assert len(one) == 1
    # zero cap -> one leaf per bucket (the unbucketed fallback)
    split = OV.plan_buckets(layout, order, 2, 2, 0, 4)
    assert len(split) == len(gathered)
    # norm weights are never gathered (replicated; sync path)
    assert all("layernorm" not in s for s in gathered)
    # mid cap splits without dropping leaves
    mid_cap = max(int(np.prod(layout[s].local_shape(2, 2))) * 4
                  for s in gathered)
    mid = OV.plan_buckets(layout, order, 2, 2, mid_cap, 4)
    assert [s for b in mid for s in b] == gathered
    assert 1 < len(mid) <= len(gathered)


# ---------------------------------------------------------------------------
# XLA overlap-flag wiring (device config -> compiler)
# ---------------------------------------------------------------------------


def test_xla_overlap_flags_reflect_registry():
    from paddle_tpu import device as D

    flags = D.xla_overlap_flags()
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in flags
    assert "--xla_tpu_enable_async_collective_fusion=true" in flags
    paddle.set_flags({"FLAGS_tpu_latency_hiding_scheduler": False})
    try:
        assert ("--xla_tpu_enable_latency_hiding_scheduler=false"
                in D.xla_overlap_flags())
    finally:
        paddle.set_flags({"FLAGS_tpu_latency_hiding_scheduler": True})


def test_xla_overlap_flags_env_merge_replaces_stale():
    from paddle_tpu import device as D

    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
                        "--xla_tpu_enable_latency_hiding_scheduler=false"}
    merged = D.apply_xla_overlap_flags(env)
    assert env["XLA_FLAGS"] == merged
    toks = merged.split()
    assert "--xla_force_host_platform_device_count=8" in toks
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in toks
    assert "--xla_tpu_enable_latency_hiding_scheduler=false" not in toks
    assert "--xla_tpu_enable_async_collective_fusion=true" in toks


def test_compiler_options_reach_the_compiler():
    """The per-compile plumbing REACHES XLA's option parser: a benign
    DebugOptions override compiles (and runs), a bogus option name is
    REJECTED — proving options are parsed, not silently dropped (on CPU
    the xla_tpu_* set itself is absent from the parser, which is why
    overlap_compiler_options() returns {} off-TPU)."""
    from paddle_tpu import device as D

    fn = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((4,), jnp.float32)
    compiled = D.compile_with_overlap_options(
        fn, x, extra_options={"xla_embed_ir_in_executable": False})
    np.testing.assert_allclose(np.asarray(compiled(x)), 2 * np.ones(4))
    with pytest.raises(Exception, match="[Nn]o such.*option|invalid"):
        fn.lower(x).compile(
            compiler_options={"xla_no_such_overlap_option": True})
    assert D.overlap_compiler_options() == {}  # cpu backend


def test_overlap_compiler_options_on_tpu(monkeypatch):
    from paddle_tpu.core import device as CD

    monkeypatch.setattr(CD, "is_compiled_with_tpu", lambda: True)
    opts = CD.overlap_compiler_options()
    assert opts.get("xla_tpu_enable_latency_hiding_scheduler") is True
    assert opts.get("xla_tpu_enable_async_collective_fusion") is True
