"""MoE expert parallelism end-to-end (round-18 tentpole;
parallel/expert.py + the serving sparse-checkpoint path).

Covers, per the round-18 contract:
- dispatch/combine round-trip: the two-stage (hierarchical) EP
  all-to-all is BIT-EXACT against the flat exchange with the codec off
  (and an involution), and within per-block quantization tolerance
  with the int8 codec, on the fake-2-slice mesh;
- expert-vs-shared grad-sync correctness: EP gradients match the dense
  global-batch reference per leaf (an ep-axis reduction on expert
  leaves would overcount by ep, a missing one on the gate would
  undercount — parity pins both);
- EP-vs-dense loss parity over a training run (codec off; step-0 loss
  bit-equal, trajectory at fp tolerance) and codec-on tolerance;
- capacity-overflow telemetry (dropped == 0 at ample capacity with the
  parity routing, > 0 under forced skew);
- serving: greedy parity of ContinuousBatchingEngine's unified ragged
  step against the one-shot generate path on a toy SPARSE checkpoint,
  fp32 and weight-only int8 (gather-then-dequant expert view);
- the Sharding Doctor's EP coverage: COMM004[moe_dispatch] fires
  exactly, the EP clean sweep + canonical-table agreement hold with
  ``ep`` among the mesh axes.

Heavy breadth combos are pytest.mark.slow with their tier-1 home
annotated in place (ROADMAP tier policy).
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle  # noqa: F401 (registers ops)
from jax import shard_map
from paddle_tpu.distributed.topology import hierarchical_axis
from paddle_tpu.parallel import compat as _compat
from paddle_tpu.parallel.codec import CollectiveCodec
from paddle_tpu.parallel.expert import (MoEEPConfig, _ep_exchange_impl,
                                        build_moe_dense_train_step,
                                        build_moe_ep_forward,
                                        build_moe_ep_train_step,
                                        init_moe_ep_params, moe_ep_layout,
                                        moe_ep_spec_for)
from paddle_tpu.parallel.overlap import OverlapConfig


def _devs(n=8):
    devs = jax.devices("cpu")
    assert len(devs) >= n, "conftest must force 8 host devices"
    return devs


def _ep_mesh():
    return Mesh(np.asarray(_devs()[:8], dtype=object).reshape(1, 2, 4),
                ("dp", "sharding", "ep"))


_CFG = dict(d_model=8, d_hidden=16, num_expert=4, top_k=2,
            capacity_factor=8.0, aux_weight=0.01)


def _data(g=64, m=8, seed=1):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(g, m).astype(np.float32)),
            jnp.asarray(rng.randn(g, m).astype(np.float32)))


# ---------------------------------------------------------------------------
# the transport: two-stage hierarchical all-to-all
# ---------------------------------------------------------------------------


def _x_mesh4():
    return Mesh(np.asarray(_devs()[:4], dtype=object), ("x",))


def _wrap4(mesh, body):
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x"),),
                             out_specs=P("x"), check_vma=False))


@pytest.mark.parametrize("slice_map", [(0, 0, 1, 1), (0, 1, 0, 1)])
def test_ep_exchange_two_stage_bitexact_vs_flat(slice_map):
    """Codec off: the hierarchical two-stage EP all-to-all must be
    BIT-IDENTICAL to the flat tiled all-to-all (the static block
    reorders align the stage outputs with the flat source-major
    layout), for both slice interleavings."""
    mesh = _x_mesh4()
    hier = hierarchical_axis(mesh, "x", slice_map=slice_map)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 8).astype(np.float32))
    flat = _wrap4(mesh, lambda v: _compat.all_to_all(
        v, "x", split_axis=0, concat_axis=0, tiled=True))(x)
    two = _wrap4(mesh, lambda v: _ep_exchange_impl(v, "x", hier, None))(x)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(two))


def test_ep_exchange_is_involution():
    """The tiled exchange's global block permutation is self-inverse —
    the property that makes the backward combine EXACTLY the
    transposed dispatch (custom_vjp applies the same exchange to the
    cotangent)."""
    mesh = _x_mesh4()
    hier = hierarchical_axis(mesh, "x", slice_map=(0, 0, 1, 1))
    x = jnp.arange(64, dtype=jnp.float32).reshape(32, 2)
    tw = _wrap4(mesh, lambda v: _ep_exchange_impl(
        _ep_exchange_impl(v, "x", hier, None), "x", hier, None))(x)
    np.testing.assert_array_equal(np.asarray(tw), np.asarray(x))


def test_ep_exchange_coded_tolerance():
    """int8 codec on the DCN stage: round-trip within the per-block
    absmax quantization bound (|err| <= absmax/127 per block), and the
    intra-slice-delivered blocks still move at full precision."""
    mesh = _x_mesh4()
    hier = hierarchical_axis(mesh, "x", slice_map=(0, 0, 1, 1))
    codec = CollectiveCodec(block=32)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(32, 8).astype(np.float32))
    flat = _wrap4(mesh, lambda v: _compat.all_to_all(
        v, "x", split_axis=0, concat_axis=0, tiled=True))(x)
    coded = _wrap4(mesh, lambda v: _ep_exchange_impl(
        v, "x", hier, codec))(x)
    err = np.abs(np.asarray(coded) - np.asarray(flat))
    bound = np.abs(np.asarray(x)).max() / 127.0 * 1.5  # bf16 scale slack
    assert err.max() <= bound, (err.max(), bound)


# ---------------------------------------------------------------------------
# EP forward / grads / training vs the dense reference
# ---------------------------------------------------------------------------


def test_ep_forward_matches_dense_no_drops():
    """EP forward on the dp x sharding x ep mesh vs the dense
    ``_moe_forward_op`` on identical routing with nothing dropped: y
    agrees at fp accumulation tolerance (XLA:CPU's matmul reduction
    order is shape-dependent; the TRANSPORT itself is bit-exact, see
    test_ep_exchange_two_stage_bitexact_vs_flat), aux matches, and the
    overflow telemetry reads zero."""
    from paddle_tpu.incubate.distributed.models.moe.gate import \
        load_balance_aux_loss
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import \
        _moe_forward_op

    cfg = MoEEPConfig(**_CFG)
    mesh = _ep_mesh()
    params = init_moe_ep_params(cfg, mesh)
    host = {k: jnp.asarray(np.asarray(v)) for k, v in params.items()}
    x2d, _ = _data()
    fwd = build_moe_ep_forward(cfg, mesh)
    y, aux, dropped, load = jax.jit(fwd)(params, x2d)
    yd, auxd, dd = jax.jit(lambda p, x: _moe_forward_op.raw_fn(
        x, p["gate_w"], p["w_up"], p["b_up"], p["w_down"], p["b_down"],
        topk=cfg.top_k, capacity=x.shape[0],
        aux_fn=load_balance_aux_loss))(host, x2d)
    assert float(dropped) == 0.0
    assert float(dd) == 0.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(yd),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(aux), float(auxd), rtol=1e-6)
    np.testing.assert_allclose(float(np.asarray(load).sum()), 1.0,
                               rtol=1e-6)


def test_ep_grad_sync_split_matches_dense():
    """The expert-vs-shared grad-sync split: every leaf's EP gradient
    equals the dense global-batch gradient.  This is the sharp pin on
    the per-leaf sync contract — reducing expert grads over ``ep``
    would scale them by 4, skipping the gate's ep reduction would
    divide it by 4; both far outside the asserted tolerance."""
    from paddle_tpu.incubate.distributed.models.moe.gate import \
        load_balance_aux_loss
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import \
        _moe_forward_op
    from paddle_tpu.parallel.expert import _moe_loss

    cfg = MoEEPConfig(**_CFG)
    mesh = _ep_mesh()
    params = init_moe_ep_params(cfg, mesh)
    host = {k: jnp.asarray(np.asarray(v)) for k, v in params.items()}
    x2d, tgt = _data()
    fwd = build_moe_ep_forward(cfg, mesh)

    def ep_loss(p, x, t):
        y, aux, dropped, load = fwd(p, x)
        total, aux_term = _moe_loss(y, x, t, aux, cfg.aux_weight)
        return total / x.shape[0] + aux_term

    def dense_loss(p, x, t):
        y, aux, dropped = _moe_forward_op.raw_fn(
            x, p["gate_w"], p["w_up"], p["b_up"], p["w_down"],
            p["b_down"], topk=cfg.top_k, capacity=x.shape[0],
            aux_fn=load_balance_aux_loss)
        total, aux_term = _moe_loss(y, x, t, aux, cfg.aux_weight)
        return total / x.shape[0] + aux_term

    eg = jax.jit(jax.grad(ep_loss))(params, x2d, tgt)
    dg = jax.jit(jax.grad(dense_loss))(host, x2d, tgt)
    for k in sorted(eg):
        np.testing.assert_allclose(
            np.asarray(eg[k]), np.asarray(dg[k]), rtol=2e-5, atol=2e-6,
            err_msg=f"grad-sync split broken on leaf {k}")


def test_ep_train_loss_parity_vs_dense():
    """EP train step vs the dense MoELayer-kernel reference over 5
    steps on identical data: step-0 loss BIT-EQUAL (identical routing,
    nothing dropped — asserted), trajectory within fp accumulation
    noise, final params in agreement."""
    cfg = MoEEPConfig(**_CFG)
    mesh = _ep_mesh()
    params = init_moe_ep_params(cfg, mesh)
    host = {k: jnp.asarray(np.asarray(v)) for k, v in params.items()}
    x2d, tgt = _data()
    step = build_moe_ep_train_step(cfg, mesh)
    dstep = build_moe_dense_train_step(cfg, shards=8)
    for i in range(5):
        loss, aux, dropped, load, params = step(params, x2d, tgt)
        dloss, daux, ddropped, host = dstep(host, x2d, tgt)
        assert float(dropped) == 0.0
        if i == 0:
            assert float(loss) == float(dloss), (float(loss),
                                                 float(dloss))
        np.testing.assert_allclose(float(loss), float(dloss), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(np.asarray(params[k]),
                                   np.asarray(host[k]),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ep_train_coded_tracks_uncoded():
    """Tier-2 breadth (round-18 tier policy; tier-1 homes: the
    ``moe_trace`` smoke leg trains the SAME coded step and asserts the
    loss decreases, and test_ep_exchange_coded_tolerance holds the
    dispatch numerics): the fake-2-slice coded EP step stays within a
    small relative band of the uncoded trajectory over 5 steps."""
    cfg = MoEEPConfig(**_CFG)
    mesh = _ep_mesh()
    x2d, tgt = _data()
    oc = OverlapConfig(hierarchical="on", slice_map=(0, 0, 1, 1),
                       codec=CollectiveCodec(block=64))
    cstep = build_moe_ep_train_step(cfg, mesh, oc=oc)
    ustep = build_moe_ep_train_step(cfg, mesh)
    cp = init_moe_ep_params(cfg, mesh)
    up = init_moe_ep_params(cfg, mesh)
    closs = uloss = None
    first = None
    for i in range(5):
        closs, _, _, _, cp = cstep(cp, x2d, tgt)
        uloss, _, _, _, up = ustep(up, x2d, tgt)
        if first is None:
            first = float(closs)
        np.testing.assert_allclose(float(closs), float(uloss), rtol=5e-3)
    assert float(closs) < first


def test_ep_hier_codec_off_bitexact_vs_flat_schedule():
    """The hierarchical EP step with codec=None is BIT-IDENTICAL to
    the flat-exchange EP step — the two-stage decomposition itself
    changes no numerics (the codec-off half of the acceptance
    criterion, at full train-step granularity)."""
    cfg = MoEEPConfig(**_CFG)
    mesh = _ep_mesh()
    x2d, tgt = _data()
    oc = OverlapConfig(hierarchical="on", slice_map=(0, 0, 1, 1))
    hstep = build_moe_ep_train_step(cfg, mesh, oc=oc)
    fstep = build_moe_ep_train_step(cfg, mesh)
    hp = init_moe_ep_params(cfg, mesh)
    fp = init_moe_ep_params(cfg, mesh)
    for _ in range(3):
        hloss, _, _, _, hp = hstep(hp, x2d, tgt)
        floss, _, _, _, fp = fstep(fp, x2d, tgt)
        assert float(hloss) == float(floss)
    for k in hp:
        np.testing.assert_array_equal(np.asarray(hp[k]),
                                      np.asarray(fp[k]))


def test_ep_capacity_overflow_surfaces():
    """Forced routing skew under a tight capacity factor: the EP step
    REPORTS the drops (telemetry > 0) instead of silently vanishing
    tokens; the run stays finite."""
    cfg = MoEEPConfig(d_model=8, d_hidden=16, num_expert=4, top_k=1,
                      capacity_factor=0.25, aux_weight=0.01)
    mesh = _ep_mesh()
    params = init_moe_ep_params(cfg, mesh)
    # steer every token to expert 1
    params["gate_w"] = jnp.zeros_like(params["gate_w"]).at[:, 1].set(4.0)
    x2d, _ = _data()
    x2d = jnp.abs(x2d)
    fwd = build_moe_ep_forward(cfg, mesh)
    y, aux, dropped, load = jax.jit(fwd)(params, x2d)
    assert float(dropped) > 0
    assert np.isfinite(np.asarray(y)).all()


# ---------------------------------------------------------------------------
# canonical vocabulary / Sharding Doctor coverage
# ---------------------------------------------------------------------------


def test_moe_ep_spec_vocabulary():
    from paddle_tpu.parallel.specs import (expert_leaf_spec,
                                           is_expert_leaf)

    assert is_expert_leaf("w_up") and is_expert_leaf(
        "model.layers.3.mlp.experts.gate_proj.weight")
    assert is_expert_leaf("blocks.1.mlp.w_down")
    assert not is_expert_leaf("model.layers.3.mlp.gate_proj.weight")
    assert tuple(expert_leaf_spec(P(None, "mp"))) == ("ep", None, "mp")
    assert tuple(moe_ep_spec_for("w_up"))[0] == "ep"
    assert tuple(moe_ep_spec_for("gate_w")) == ()


def test_moe_ep_canonical_table_and_cross_stack():
    """The EP stack's canonical SpecLayout carries ``ep`` as a
    first-class axis, and SHARD003 between the declared plan and the
    concrete at-rest placement is EMPTY (the acceptance gate; the
    memoized self_check section reruns the same entries)."""
    from paddle_tpu.analysis.sharding import check_cross_stack
    from paddle_tpu.parallel.specs import layout_from_arrays

    cfg = MoEEPConfig(**_CFG)
    mesh = _ep_mesh()
    plan = moe_ep_layout(cfg, mesh)
    assert dict(plan.mesh_axes)["ep"] == 4
    assert plan["w_up"].dim_axes[0] == ("ep",)
    assert plan["gate_w"].dim_axes == ((), ())
    rest = layout_from_arrays(init_moe_ep_params(cfg, mesh), mesh=mesh)
    rep = check_cross_stack({"moe_ep_plan": plan,
                             "moe_ep_at_rest": rest})
    assert rep.ok, [f.format() for f in rep.findings]


def test_moe_dispatch_codec_fixture_fires_exactly():
    from paddle_tpu.analysis.fixtures import SEEDED

    rep = SEEDED["COMM004[moe_dispatch]"]()
    assert set(rep.codes()) == {"COMM004"}
    assert len(rep.findings) == 1


def test_moe_ep_doctor_clean_and_fires_uncoded():
    """Both ways on the pinned wire budget: the coded EP step passes
    COMM004 under MOE_DCN_WIRE_BUDGET, and the SAME entry with the
    codec silently dropped fires it (the liveness pair — the budget is
    not vacuous)."""
    import paddle_tpu.analysis as A
    from paddle_tpu.analysis.self_check import (MOE_DCN_WIRE_BUDGET,
                                                MOE_SLICE_MAP,
                                                _moe_ep_flagship)

    cfg, mesh, params, x2d, tgt = _moe_ep_flagship()
    wire_opts = {"collective_budget": {
        "overlap_active": True,
        "wire": {"dcn_axes": {"ep": list(MOE_SLICE_MAP)},
                 "dcn_bytes": MOE_DCN_WIRE_BUDGET}}}
    coded = build_moe_ep_train_step(
        cfg, mesh, oc=OverlapConfig(hierarchical="on",
                                    slice_map=MOE_SLICE_MAP,
                                    codec=CollectiveCodec(block=64)))
    rep = A.check(coded, params, x2d, tgt, passes=["collective_budget"],
                  exemptions=(), options=wire_opts,
                  target="moe_ep_coded")
    assert rep.ok, [f.format() for f in rep.findings]
    uncoded = build_moe_ep_train_step(
        cfg, mesh, oc=OverlapConfig(hierarchical="on",
                                    slice_map=MOE_SLICE_MAP))
    rep2 = A.check(uncoded, init_moe_ep_params(cfg, mesh), x2d, tgt,
                   passes=["collective_budget"], exemptions=(),
                   options=wire_opts, target="moe_ep_uncoded")
    assert not rep2.ok
    assert set(rep2.codes()) == {"COMM004"}


# ---------------------------------------------------------------------------
# serving: the toy sparse checkpoint through the unified ragged step
# ---------------------------------------------------------------------------


def toy_sparse_llama(num_experts=4, top_k=2, seed=0):
    """A debug Llama whose every decoder FFN is a router + stacked
    expert bank (the round-18 sparse-checkpoint naming:
    ``model.layers.i.mlp.router.weight`` + ``.mlp.experts.*``)."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.debug(vocab=128, hidden=64, layers=2, heads=4,
                            kv_heads=2, inter=128, max_pos=64)
    cfg = dataclasses.replace(cfg, num_experts=num_experts,
                              moe_top_k=top_k)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    params = {k: jnp.asarray(v)
              for k, v in model.functional_state().items()}
    rng = np.random.RandomState(seed)
    E, h, it = num_experts, cfg.hidden_size, cfg.intermediate_size
    out = {k: v for k, v in params.items() if ".mlp." not in k}
    for i in range(cfg.num_hidden_layers):
        pre = f"model.layers.{i}.mlp."
        out[pre + "router.weight"] = jnp.asarray(
            rng.randn(h, E).astype(np.float32) * 0.5)
        out[pre + "experts.gate_proj.weight"] = jnp.asarray(
            rng.randn(E, h, it).astype(np.float32) / np.sqrt(h))
        out[pre + "experts.up_proj.weight"] = jnp.asarray(
            rng.randn(E, h, it).astype(np.float32) / np.sqrt(h))
        out[pre + "experts.down_proj.weight"] = jnp.asarray(
            rng.randn(E, it, h).astype(np.float32) / np.sqrt(it))
    return cfg, out


def _serve_and_reference(cfg, params, prompts, n_new=8):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.generation import (_generate_jit,
                                              register_config)

    cfg_id = register_config(cfg)
    key = jax.random.PRNGKey(0)
    refs = [np.asarray(_generate_jit(params, p[None], key, cfg_id,
                                     n_new, False, 1.0, 0, 1.0, -1))[0]
            for p in prompts]
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   num_pages=17, page_size=16,
                                   max_seq_len=64,
                                   prefill_token_budget=8)
    for p in prompts:
        eng.add_request(p, max_new_tokens=n_new)
    done = {f.rid: f for f in eng.run()}
    return refs, [done[i] for i in sorted(done)]


def test_serving_sparse_greedy_parity():
    """The unified ragged step serves the toy SPARSE checkpoint with
    greedy output BIT-IDENTICAL to the one-shot generate path (both
    route through generation._ffn's top-k expert gather)."""
    cfg, params = toy_sparse_llama()
    prompts = [np.array([3, 17, 9, 42, 7], np.int32),
               np.array([5, 99, 2], np.int32)]
    refs, done = _serve_and_reference(cfg, params, prompts)
    for ref, fin in zip(refs, done):
        assert list(fin.tokens) == list(ref[:len(fin.tokens)])


def test_int8_expert_gather_dequant_view():
    """The int8 expert bank's gather-then-dequant view: stacked
    [E, in, out] banks quantize per (expert, out-channel) with the
    router kept fp, ``_Weights.expert`` dequantizes exactly
    rows * scale, and ``_moe_ffn`` on the int8 checkpoint tracks the
    fp checkpoint within weight-only-int8 tolerance (the cheap tier-1
    core of the slow end-to-end int8 serving parity below)."""
    from paddle_tpu.models.generation import (_Weights, _moe_ffn,
                                              quantize_params_int8)

    cfg, params = toy_sparse_llama(seed=2)
    q = quantize_params_int8(params)
    wname = "model.layers.0.mlp.experts.gate_proj.weight"
    assert q[wname].dtype == jnp.int8
    assert q[wname + "._scale"].shape == (cfg.num_experts,
                                          cfg.intermediate_size)
    assert q["model.layers.0.mlp.router.weight"].dtype == jnp.float32
    wq, wf = _Weights(cfg, q), _Weights(cfg, params)
    idx = jnp.asarray([0, 3, 1], jnp.int32)
    got = np.asarray(wq.expert(0, "gate_proj", idx))
    want = (np.asarray(q[wname])[np.asarray(idx)].astype(np.float32)
            * np.asarray(q[wname + "._scale"])[np.asarray(idx)][:, None, :])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    x = jnp.asarray(np.random.RandomState(0)
                    .randn(5, cfg.hidden_size).astype(np.float32))
    yq = np.asarray(_moe_ffn(wq, 0, x))
    yf = np.asarray(_moe_ffn(wf, 0, x))
    assert np.abs(yq - yf).max() < 0.15 * max(np.abs(yf).max(), 1.0)


@pytest.mark.slow
def test_serving_sparse_int8_greedy_parity():
    """Tier-2 breadth (round-18 tier policy; tier-1 homes:
    test_serving_sparse_greedy_parity carries the unified sparse path
    end-to-end and test_int8_expert_gather_dequant_view the int8
    expert view): weight-only int8 sparse checkpoint — the engine's
    greedy stream is bit-identical to int8 generate (both consume the
    same gather-then-dequant expert view)."""
    from paddle_tpu.models.generation import quantize_params_int8

    cfg, params = toy_sparse_llama(seed=2)
    q = quantize_params_int8(params)
    prompts = [np.array([11, 23, 64, 8], np.int32)]
    refs, done = _serve_and_reference(cfg, q, prompts)
    assert list(done[0].tokens) == list(refs[0][:len(done[0].tokens)])


@pytest.mark.slow
def test_ep_forward_dp2_sharding1_variant():
    """Tier-2 breadth (tier-1 home: test_ep_forward_matches_dense_no_
    drops on the dp1 x sharding2 x ep4 mesh — same code path, different
    batch-axis split): the dp-led mesh variant agrees with dense."""
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import \
        _moe_forward_op

    cfg = MoEEPConfig(**_CFG)
    mesh = Mesh(np.asarray(_devs()[:8], dtype=object).reshape(2, 1, 4),
                ("dp", "sharding", "ep"))
    params = init_moe_ep_params(cfg, mesh)
    host = {k: jnp.asarray(np.asarray(v)) for k, v in params.items()}
    x2d, _ = _data(seed=5)
    fwd = build_moe_ep_forward(cfg, mesh)
    y, aux, dropped, load = jax.jit(fwd)(params, x2d)
    yd, _, _ = jax.jit(lambda p, x: _moe_forward_op.raw_fn(
        x, p["gate_w"], p["w_up"], p["b_up"], p["w_down"], p["b_down"],
        topk=cfg.top_k, capacity=x.shape[0], aux_fn=None))(host, x2d)
    assert float(dropped) == 0.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(yd),
                               rtol=1e-6, atol=1e-6)
