"""Quantized DCN collectives (round-15 tentpole, parallel/codec.py).

Acceptance bars:
- tolerance-parameterized codec roundtrip (ragged last block,
  non-divisible shapes, zero/inf/NaN guards) within the per-block
  absmax error bound;
- end-to-end grad-sync parity on the fake-2-slice ``slice_map`` path:
  the quantized overlap train step matches the fp32 flat schedule
  within tolerance, and the codec-off path stays the unquantized
  schedule (no int8 on any wire);
- BITWISE determinism of the seeded stochastic rounding across runs;
- COMM004 reports >= 3x fewer DCN bytes on the flagship bucketed
  reduce-scatter with the int8 codec enabled vs disabled;
- the quantized weight-delivery path (reshard.execute_encoded /
  fleet delivery_codec) round-trips within the weight profile's bound
  and prices its POST-codec transient through the doctor.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from jax import shard_map
from paddle_tpu.distributed.topology import hierarchical_axis
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, build_train_step
from paddle_tpu.models.llama import apply_llama_sharding
from paddle_tpu.parallel import overlap as OV
from paddle_tpu.parallel.codec import (CollectiveCodec, decode_rows,
                                       encode_rows, encode_rows_host,
                                       packed_width, wire_ratio)
from paddle_tpu.parallel.overlap import OverlapConfig


def _need(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


# ---------------------------------------------------------------------------
# codec roundtrip (tolerance-parameterized)
# ---------------------------------------------------------------------------

# (profile, stochastic, per-block relative error bound): int8 rounds
# within scale/2 deterministically and within scale stochastically
# (floor(r+u) lands on a neighbour of r); fp8 e4m3 carries 3 mantissa
# bits -> 1/16 relative.  2% slack covers the bf16 scale quantization.
ROUNDTRIP_TOLS = [
    ("int8", False, 0.5 / 127),
    ("int8", True, 1.0 / 127),
    ("fp8", False, 1.0 / 16),
]


@pytest.mark.parametrize("profile,stochastic,tol", ROUNDTRIP_TOLS)
@pytest.mark.parametrize("n", [64, 100, 257, 1000])  # ragged last blocks
def test_codec_roundtrip_within_block_bound(profile, stochastic, tol, n):
    codec = CollectiveCodec(block=64)
    rng = np.random.RandomState(n)
    # wide dynamic range across blocks — the case per-block scaling
    # exists for
    x = (rng.randn(3, n) * np.exp(2 * rng.randn(3, n))).astype(np.float32)
    packed = encode_rows(jnp.asarray(x), codec, profile,
                         stochastic=stochastic)
    assert packed.shape == (3, packed_width(n, codec.block))
    assert packed.dtype == jnp.int8
    y = np.asarray(decode_rows(packed, n, codec, profile))
    nb = -(-n // codec.block)
    xp = np.zeros((3, nb * codec.block), np.float32)
    xp[:, :n] = x
    amax = np.abs(xp.reshape(3, nb, codec.block)).max(-1)  # [3, nb]
    errp = np.zeros_like(xp)
    errp[:, :n] = np.abs(y - x)
    per_block_err = errp.reshape(3, nb, codec.block).max(-1)
    assert (per_block_err <= amax * tol * 1.02 + 1e-12).all()


def test_codec_zero_inf_nan_guards():
    codec = CollectiveCodec(block=64)
    x = np.zeros((1, 130), np.float32)
    x[0, 5] = np.nan
    x[0, 9] = np.inf
    x[0, 12] = -np.inf
    x[0, 70] = 3.0
    for profile in ("int8", "fp8"):
        y = np.asarray(decode_rows(
            encode_rows(jnp.asarray(x), codec, profile), 130, codec,
            profile))
        assert np.isfinite(y).all()
        assert y[0, 5] == 0.0                       # NaN -> 0
        assert y[0, 9] > 0 and y[0, 12] < 0         # inf saturates signed
        # an all-zero block round-trips to exact zeros
        assert (y[0, 64:70] == 0).all() and (y[0, 71:] == 0).all()
        assert abs(y[0, 70] - 3.0) <= 3.0 / 16 + 1e-6


def test_codec_wire_arithmetic():
    # 1 byte/elem payload + 2 bytes/block sidecar, last block padded
    assert packed_width(256, 256) == 256 + 2
    assert packed_width(257, 256) == 512 + 4
    assert wire_ratio(4096, 256) > 3.9
    with pytest.raises(ValueError):
        CollectiveCodec(grad_profile="int4")
    with pytest.raises(ValueError):
        CollectiveCodec(block=1)
    # profile resolution: "none" disables a direction; stochastic only
    # applies to int8 grads
    c = CollectiveCodec(weight_profile="none")
    assert c.resolve("weight") is None
    assert c.resolve("grad") == ("int8", True)
    assert CollectiveCodec().resolve("weight")[1] is False


def test_stochastic_rounding_bitwise_deterministic():
    """Two encodes of the same data are BIT-identical (the hash is a
    pure function of seed and position); a different seed draws a
    different pattern; and two jit instantiations agree."""
    codec = CollectiveCodec(block=64)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 500), jnp.float32)
    p1 = np.asarray(encode_rows(x, codec, "int8", stochastic=True))
    p2 = np.asarray(encode_rows(x, codec, "int8", stochastic=True))
    assert np.array_equal(p1, p2)
    pj = np.asarray(jax.jit(
        lambda v: encode_rows(v, codec, "int8", stochastic=True))(x))
    assert np.array_equal(p1, pj)
    p3 = np.asarray(encode_rows(x, CollectiveCodec(block=64, seed=1),
                                "int8", stochastic=True))
    assert not np.array_equal(p1, p3)


def test_host_encode_matches_device_decode():
    codec = CollectiveCodec(block=128)
    rng = np.random.RandomState(7)
    x = (rng.randn(1, 777) * 10).astype(np.float32)
    for profile, tol in (("int8", 0.5 / 127), ("fp8", 1.0 / 16)):
        packed = encode_rows_host(x, codec, profile)
        y = np.asarray(decode_rows(jnp.asarray(packed), 777, codec,
                                   profile))
        nb = -(-777 // 128)
        xp = np.zeros((1, nb * 128), np.float32)
        xp[:, :777] = x
        amax = np.abs(xp.reshape(1, nb, 128)).max(-1)
        errp = np.zeros_like(xp)
        errp[:, :777] = np.abs(y - x)
        per_block = errp.reshape(1, nb, 128).max(-1)
        assert (per_block <= amax * tol * 1.02 + 1e-12).all()


# ---------------------------------------------------------------------------
# quantized hierarchical collectives on the fake-2-slice slice_map path
# ---------------------------------------------------------------------------


def test_coded_hier_collectives_match_flat_within_tolerance():
    _need(8)
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object),
                ("sharding",))
    hier = hierarchical_axis(mesh, "sharding",
                             slice_map=(0, 0, 0, 0, 1, 1, 1, 1))
    codec = CollectiveCodec(block=64)
    x = np.random.RandomState(0).randn(16, 6).astype(np.float32)

    def body(x):
        f_rs = lax.psum_scatter(x, "sharding", scatter_dimension=0,
                                tiled=True)
        q_rs = OV.hier_psum_scatter(x, "sharding", hier, codec=codec,
                                    kind="grad")
        rt = OV.hier_all_gather(q_rs, "sharding", hier, codec=codec,
                                kind="weight")
        fs = lax.psum(x, "sharding")
        qs = OV.hier_psum(x, "sharding", hier, codec=codec, kind="grad")
        return f_rs, q_rs, rt, fs, qs

    f_rs, q_rs, rt, fs, qs = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(),),
        out_specs=(P("sharding"), P("sharding"), P(), P(), P()),
        check_vma=False))(x)
    f_rs, q_rs, rt, fs, qs = map(np.asarray, (f_rs, q_rs, rt, fs, qs))
    scale = np.abs(f_rs).max()
    # int8 stochastic reduce: residue quantized once, summed over 2
    # slices -> ~2/127 of the residue absmax
    assert np.abs(q_rs - f_rs).max() <= scale * 3 / 127
    # + the fp8 weights-gather on top for the round trip
    assert np.abs(rt - fs).max() <= np.abs(fs).max() * (3 / 127 + 1 / 8)
    assert np.abs(qs - fs).max() <= np.abs(fs).max() * 3 / 127


def test_codec_off_schedule_has_no_int8_wire():
    """codec=None keeps today's schedule: the jaxpr carries the same
    two-stage psum_scatter pair and no int8 payload anywhere."""
    _need(4)
    mesh = Mesh(np.asarray(jax.devices()[:4], dtype=object),
                ("sharding",))
    hier = hierarchical_axis(mesh, "sharding", slice_map=(0, 0, 1, 1))

    def off(v):
        return OV.hier_psum_scatter(v, "sharding", hier)

    fn = shard_map(off, mesh=mesh, in_specs=(P(),),
                   out_specs=P("sharding"), check_vma=False)
    x = jnp.ones((16, 8), jnp.float32)
    from paddle_tpu.analysis.core import walk_eqns

    jaxpr = jax.make_jaxpr(fn)(x).jaxpr
    prims = [e.primitive.name for e, _ in walk_eqns(jaxpr)]
    assert prims.count("reduce_scatter") == 2   # psum_scatter's prim
    assert "all_to_all" not in prims
    assert not any(getattr(v.aval, "dtype", None) == jnp.int8
                   for e, _ in walk_eqns(jaxpr) for v in e.outvars)
    assert OverlapConfig().codec is None


@pytest.fixture(scope="module")
def flat_ref():
    """fp32 flat single-program step — the parity baseline (explicit
    seeding per the module-fixture rule)."""
    paddle.seed(20260804)
    np.random.seed(20260804)
    cfg = LlamaConfig.debug(vocab=128, hidden=32, layers=2, heads=4,
                            kv_heads=2, inter=64, max_pos=64)
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


def _run_coded_step(flat_ref, codec):
    cfg, model, state0, ids, labels, _, _ = flat_ref
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        1, 4, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    oc = OverlapConfig(hierarchical="on", slice_map=(0, 0, 1, 1),
                       collective_matmul_min_out_elems=1, codec=codec)
    step = build_train_step(model, opt, mesh=mesh,
                            compute_dtype=jnp.float32, overlap=oc)
    p = {k: jnp.copy(v) for k, v in state0.items()}
    st = opt.init_state({k: jnp.copy(v) for k, v in state0.items()})
    loss, newp, _ = step(p, st, 0, 1e-3, ids, labels)
    return float(loss), {k: np.asarray(v) for k, v in newp.items()}


def test_grad_sync_parity_and_determinism_fake_2slice(flat_ref):
    """End-to-end: int8-stochastic grad codec (forward weights-gather
    unquantized -> loss exact vs the fp32 schedule), params within the
    AdamW sign-amplification tolerance of the flat step; two runs
    BITWISE identical (the seeded-rounding determinism contract)."""
    _need(8)
    codec = CollectiveCodec(weight_profile="none", block=128)
    loss1, p1 = _run_coded_step(flat_ref, codec)
    np.testing.assert_allclose(loss1, flat_ref[5], rtol=1e-5)
    for k, ref in flat_ref[6].items():
        # first-step AdamW is sign-like (update ~ +-lr): quantized
        # grads flip near-zero elements' signs -> up to ~2*lr per elem
        np.testing.assert_allclose(p1[k], ref, atol=3e-3, rtol=2e-3,
                                   err_msg=k)
    loss2, p2 = _run_coded_step(flat_ref, codec)
    assert loss1 == loss2
    for k in p1:
        assert np.array_equal(p1[k], p2[k]), k


@pytest.mark.slow
def test_full_codec_parity_fake_2slice(flat_ref):
    """Breadth leg (tier-2): fp8 weights-gather + int8 grads — the
    forward now carries the weight quantization error, so the bar is
    the fp8 relative bound on loss and a looser param tolerance."""
    _need(8)
    loss, p = _run_coded_step(flat_ref, CollectiveCodec(block=128))
    np.testing.assert_allclose(loss, flat_ref[5], rtol=2e-2)
    for k, ref in flat_ref[6].items():
        np.testing.assert_allclose(p[k], ref, atol=2e-2, rtol=2e-2,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# COMM004: the >= 3x DCN-bytes acceptance gate + fixture/pass wiring
# ---------------------------------------------------------------------------


def test_comm004_flagship_dcn_bytes_shrink_3x():
    """The acceptance criterion: the flagship bucketed reduce-scatter's
    DCN leg moves >= 3x fewer bytes with the int8 codec (fp-wire
    psum_scatter vs packed int8 all_to_all), and the total DCN bill
    shrinks."""
    _need(8)
    from paddle_tpu.analysis.self_check import flagship_wire_table

    t = flagship_wire_table()
    assert t["reducescatter_ratio"] >= 3.0, t
    assert t["codec_on"]["dcn"]["bytes"] < t["codec_off"]["dcn"]["bytes"]
    # the wire budget the self-check pins must actually sit between the
    # coded and uncoded schedules (the gate is live in both directions)
    from paddle_tpu.analysis.self_check import FLAGSHIP_DCN_WIRE_BUDGET

    assert (t["codec_on"]["dcn"]["bytes"] <= FLAGSHIP_DCN_WIRE_BUDGET
            < t["codec_off"]["dcn"]["bytes"])


def test_comm004_clean_on_coded_step_fires_on_uncoded():
    """COMM004 liveness both ways on one tiny entry: the coded schedule
    sweeps clean under its own measured budget; the identical entry
    without the codec fires exactly COMM004."""
    _need(4)
    import paddle_tpu.analysis as A
    from paddle_tpu.analysis.passes.collective_budget import \
        collect_wire_table

    mesh = Mesh(np.asarray(jax.devices()[:4], dtype=object), ("x",))
    sm = (0, 0, 1, 1)
    hier = hierarchical_axis(mesh, "x", slice_map=sm)
    codec = CollectiveCodec(block=64)

    def wrap(body):
        return shard_map(body, mesh=mesh, in_specs=(P(),),
                         out_specs=P("x"), check_vma=False)

    x = jnp.ones((16, 64), jnp.float32)
    coded = wrap(lambda v: OV.hier_psum_scatter(v, "x", hier,
                                                codec=codec))
    uncoded = wrap(lambda v: OV.hier_psum_scatter(v, "x", hier))
    budget = collect_wire_table(jax.make_jaxpr(coded)(x).jaxpr,
                                {"x": sm})["dcn"]["bytes"]
    opts = {"collective_budget":
            {"wire": {"dcn_axes": {"x": list(sm)},
                      "dcn_bytes": budget}}}
    clean = A.check(coded, x, passes=["collective_budget"],
                    exemptions=(), options=opts, target="coded")
    assert clean.ok, clean.summary()
    hot = A.check(uncoded, x, passes=["collective_budget"],
                  exemptions=(), options=opts, target="uncoded")
    assert set(hot.codes()) == {"COMM004"}, hot.summary()
    f = hot.findings[0]
    assert f.data["measured"] >= 3 * f.data["budget"]


def test_wire_table_scan_multiplier_and_stages():
    """collect_wire_table: scan-nested collectives multiply by trip
    count, ICI-group collectives classify as ici, slice-spanning ones
    as dcn, and int8 payloads bill 1 byte/element."""
    _need(4)
    from paddle_tpu.analysis.passes.collective_budget import \
        collect_wire_table

    mesh = Mesh(np.asarray(jax.devices()[:4], dtype=object), ("x",))
    sm = (0, 0, 1, 1)
    ici_groups = [[0, 1], [2, 3]]

    def body(v):
        def tick(c, _):
            return c + lax.psum(c, "x", axis_index_groups=ici_groups), \
                None
        c, _ = lax.scan(tick, v, None, length=3)
        return c + lax.psum(v, "x")

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                   check_vma=False)
    x = jnp.ones((8,), jnp.float32)
    t = collect_wire_table(jax.make_jaxpr(fn)(x).jaxpr, {"x": list(sm)})
    # scanned ici psum: 3 ticks x (2 elems * 4B * 2*(g-1)/g with g=2)
    assert t["ici"]["count"] == 3
    assert t["ici"]["bytes"] == 3 * (2 * 4)
    # the flat psum spans both slices -> dcn, g=4
    assert t["dcn"]["count"] == 1
    assert t["dcn"]["bytes"] == 2 * 2 * 4 * 3 // 4


# ---------------------------------------------------------------------------
# quantized weight delivery (reshard/fleet) + the joint autotune knob
# ---------------------------------------------------------------------------


def test_encoded_delivery_roundtrip_and_budget():
    _need(4)
    from paddle_tpu.parallel.reshard import (check_reshard_budget,
                                             execute_encoded,
                                             plan_reshard,
                                             reshard_step_entry)
    from paddle_tpu.parallel.memory import measure_step_memory

    mesh = Mesh(np.asarray(jax.devices()[:4], dtype=object).reshape(
        2, 2), ("dp", "mp"))
    rng = np.random.default_rng(5)
    host = {"w": rng.standard_normal((256, 64)).astype(np.float32),
            "b": rng.standard_normal((64,)).astype(np.float32),
            "steps": np.asarray(3, np.int32)}
    specs = {"w": P("dp", None), "b": P()}
    codec = CollectiveCodec(block=128)
    # cap forces w into chunks — the codec must encode per chunk
    plan = plan_reshard(host, mesh, specs, max_transient_bytes=32 << 10)
    out = execute_encoded(plan, host, codec)
    assert int(out["steps"]) == 3                     # non-float: exact
    for k, tol in (("w", 1 / 16), ("b", 1 / 16)):     # fp8 weight bound
        got = np.asarray(out[k])
        assert got.shape == host[k].shape
        assert np.abs(got - host[k]).max() <= \
            np.abs(host[k]).max() * tol * 1.05
    assert out["w"].sharding.is_equivalent_to(
        NamedSharding(mesh, P("dp", None)), 2)
    # weight_profile="none" degrades to the bit-exact path
    exact = execute_encoded(plan, host,
                            CollectiveCodec(weight_profile="none"))
    assert np.array_equal(np.asarray(exact["w"]), host["w"])
    # post-codec pricing: the encoded entry's compiled peak sits below
    # the raw one; a budget between the two fires MEM001 only on raw
    step = max(plan.steps, key=lambda s: s.transient_bytes)
    raw_fn, raw_args = reshard_step_entry(plan, step, host)
    cod_fn, cod_args = reshard_step_entry(plan, step, host, codec=codec)
    raw_peak = measure_step_memory(raw_fn, *raw_args)["peak_bytes"]
    cod_peak = measure_step_memory(cod_fn, *cod_args)["peak_bytes"]
    assert cod_peak < raw_peak
    mid = (raw_peak + cod_peak) // 2
    assert not check_reshard_budget(plan, host, budget_bytes=mid,
                                    exemptions=()).ok
    assert check_reshard_budget(plan, host, budget_bytes=mid,
                                exemptions=(), codec=codec).ok


def test_fleet_delivery_codec_wiring():
    from paddle_tpu.inference.fleet import FleetConfig, ReplicaSet

    rng = np.random.default_rng(9)
    host = {"w": rng.standard_normal((128, 64)).astype(np.float32)}
    codec = CollectiveCodec(weight_profile="int8", block=64)
    rs = ReplicaSet(host, engine_factory=lambda p: None,
                    config=FleetConfig(max_transient_bytes=16 << 10,
                                       delivery_codec=codec))
    got = np.asarray(rs._deliver()["w"])
    amax = np.abs(host["w"]).max()
    assert np.abs(got - host["w"]).max() <= amax / 127 * 1.05
    assert rs.check_delivery_budget().ok


def test_joint_codec_lattice_autotune():
    """The tune_memory_config joint knob: with a DCN wire budget only
    the codec points can satisfy, the walk lands on the FIRST codec-on
    point of the cheapest memory config — codec error traded for DCN
    bytes by the same cheapest-first rule as remat/offload."""
    from paddle_tpu.parallel.memory import (MEMORY_LATTICE, JointConfig,
                                            joint_memory_codec_lattice,
                                            tune_memory_config)

    base = OverlapConfig(hierarchical="on", slice_map=(0, 0, 1, 1))
    lattice = joint_memory_codec_lattice(base,
                                         memory_lattice=MEMORY_LATTICE[:2])
    assert len(lattice) == 6
    assert all(isinstance(c, JointConfig) for c in lattice)
    # per memory point: codec off first, then increasing error
    assert lattice[0].overlap.codec is None
    assert lattice[1].overlap.codec.grad_profile == "int8"
    assert lattice[2].overlap.codec.grad_profile == "fp8"
    assert "codec-off" in lattice[0].label()
    x = jnp.ones((8,), jnp.float32)

    def builder(cfg):
        return jax.jit(lambda v: v * 2.0), (x,)

    def dcn_bytes(cfg, fn, args):
        # structural stand-in: codec-off bills fp32, codec-on int8
        return 1024 if cfg.overlap.codec is None else 272

    chosen, records = tune_memory_config(
        builder, 1 << 62, lattice=lattice, dcn_wire_bytes=512,
        dcn_bytes_fn=dcn_bytes)
    assert chosen is lattice[1]          # cheapest memory, first codec
    assert records[0]["fits"] is False and records[1]["fits"] is True
    assert records[0]["dcn_wire_bytes"] == 1024
    # no wire budget -> the plain capacity walk picks the first point
    chosen2, _ = tune_memory_config(builder, 1 << 62, lattice=lattice)
    assert chosen2 is lattice[0]
