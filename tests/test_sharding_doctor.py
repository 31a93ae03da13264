"""Sharding Doctor (ISSUE 9 tentpole gate): cross-stack partition
consistency + the canonical SpecLayout extractor.

Four layers, mirroring the Graph Doctor's self-check contract:
- TRUE POSITIVES: each of the five seeded SHARD fixtures fires EXACTLY
  its code (a pass that never fires is indistinguishable from one that
  cannot fire);
- CLEAN SWEEPS: the flagship analysis entries — GSPMD train step in
  both accum regimes, the overlap step, both hybrid bodies, the serving
  param table — report zero findings under their declared reshard
  allowances, table floors and the 2004.13336 update-pin demand;
- CROSS-STACK AGREEMENT: the canonical tables extracted from the GSPMD,
  overlap and hybrid stacks map the llama flagship parameter tree
  identically (SHARD003 empty) — the precondition for the ROADMAP's
  unified-partitioning refactor, whose input artifact is this table;
- EXEMPTIONS: SHARD findings are detected without exemptions and
  suppressed by a tracked entry with one, and the suppression carries
  the exemption id (round-trip + liveness shape).

Plus unit coverage of the extractor plumbing (canonical keys, layer
collapse, axis restriction, the placement-hook parity with the real
placed state).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle  # noqa: F401 - registers ops
import paddle_tpu.analysis as A
from paddle_tpu.analysis import sharding as S
from paddle_tpu.analysis.fixtures import SEEDED, FixtureUnavailable
from paddle_tpu.analysis.self_check import (_flagship, _sharding_section)
from paddle_tpu.parallel.specs import (SpecLayout, TensorSpec,
                                       layout_from_arrays,
                                       tensor_spec_from_array)

SHARD_CODES = ("SHARD001", "SHARD002", "SHARD003", "SHARD004", "SHARD005")


# ---------------------------------------------------------------------------
# true positives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("code", SHARD_CODES)
def test_seeded_shard_fixture_fires_exactly_its_code(code):
    try:
        rep = SEEDED[code]()
    except FixtureUnavailable as e:
        pytest.skip(str(e))
    assert rep.findings, f"{code}: fixture produced no findings\n" \
        + rep.summary()
    assert set(rep.codes()) == {code}, rep.summary()


# ---------------------------------------------------------------------------
# clean flagship sweeps (the self-check's sharding section, memoized —
# GSPMD both accum regimes, overlap, both hybrid bodies, serving table,
# and the cross-stack gate ride one compile sweep)
# ---------------------------------------------------------------------------


def test_flagship_sharding_sweeps_are_clean():
    section = _sharding_section()
    assert section, "sharding section produced nothing"
    for name, res in section.items():
        assert res.get("ok"), (name, res)
    if "_skipped" not in section:
        for required in ("gspmd_train_step[accum1]",
                         "gspmd_train_step[accum4]",
                         "overlap_train_step",
                         "hybrid_train_step[gpipe]",
                         "hybrid_train_step[1F1B]",
                         "serving_param_layout", "cross_stack"):
            assert required in section, (required, sorted(section))


def test_cross_stack_agreement_on_flagship_tree():
    """The acceptance gate in isolation: GSPMD and overlap tables agree
    on the llama flagship parameter tree — SHARD003 EMPTY — and the
    table is the full tree, not a stub."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    from paddle_tpu.models.llama import apply_llama_sharding

    cfg, model, opt, params, ids, labels = _flagship()
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        2, 2, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    g = S.extract_gspmd_layout(model, mesh)
    o = S.extract_overlap_layout(model, mesh)
    rep = S.check_cross_stack({"gspmd": g, "overlap": o})
    assert rep.ok, rep.summary()
    # every named parameter role is covered by BOTH tables
    roles = {S.canonical_key(n) for n, _ in model.named_parameters()}
    assert roles == set(g.entries) == set(o.entries)
    # and the overlap table carries the engine's bucket-plan riders
    assert o.buckets and all(isinstance(b, list) for b in o.buckets)


def test_hybrid_table_agrees_after_axis_restriction():
    """The hybrid stack lives on a 5-axis mesh; its canonical per-layer
    entries must agree with GSPMD's after restriction to the shared
    axes (pp layer-stacking is layer-SET placement, dropped from the
    logical per-layer tensor)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    from paddle_tpu.models.llama import apply_llama_sharding
    from paddle_tpu.models.llama_hybrid import hybrid_mesh

    cfg, model, opt, params, ids, labels = _flagship()
    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(
        2, 2, 2), ("dp", "sharding", "mp"))
    apply_llama_sharding(model, mesh)
    hmesh = hybrid_mesh(jax.devices(), pp=2, dp=1, sharding=2, sep=1,
                        mp=2)
    g = S.extract_gspmd_layout(model, mesh)
    h = S.extract_hybrid_layout(model, hmesh)
    rep = S.check_cross_stack({"gspmd": g, "hybrid": h})
    assert rep.ok, rep.summary()


# ---------------------------------------------------------------------------
# exemption round-trip (detected without, suppressed with, id stamped)
# ---------------------------------------------------------------------------


def _waste_layout():
    return SpecLayout(
        mesh_axes=(("sharding", 4),),
        entries={"model.layers.*.mlp.up_proj.weight": TensorSpec(
            shape=(512, 512), dtype="float32", dim_axes=((), ()))})


def test_shard_finding_detected_without_exemption():
    rep = S.check_layout(_waste_layout(), replicated_min_bytes=256 << 10,
                         exemptions=())
    assert rep.codes() == ["SHARD002"], rep.summary()


def test_shard_finding_suppressed_by_tracked_entry():
    ex = A.Exemption(
        id="EX-SHARD002-test-replicated-leaf", code="SHARD002",
        file_pattern="",   # table-level findings carry no source where
        reason="test: accepted replication region")
    rep = S.check_layout(_waste_layout(), replicated_min_bytes=256 << 10,
                         exemptions=(ex,))
    assert rep.ok, rep.summary()
    assert [f.exemption_id for f in rep.suppressed] \
        == ["EX-SHARD002-test-replicated-leaf"]


def test_update_pin_positive_path_is_clean():
    """SHARD005's other half: a flat update chain THAT CARRIES the
    cross-replica pin sweeps clean — the liveness proof that the
    finding keys on the pin, not on the entry shape."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = Mesh(np.asarray(devs[:2], dtype=object), ("x",))
    m = jax.device_put(jnp.ones((1 << 15,), jnp.float32),
                       NamedSharding(mesh, P()))

    @jax.jit
    def pinned(master, g):
        master = jax.lax.with_sharding_constraint(
            master, NamedSharding(mesh, P("x")))
        return master - 0.1 * g

    rep = A.check(pinned, m, m * 0.5, passes=["sharding_consistency"],
                  exemptions=(),
                  options={"sharding_consistency":
                           {"expect_update_pin": True,
                            "update_min_bytes": 1 << 10}})
    assert rep.ok, rep.summary()


# ---------------------------------------------------------------------------
# extractor plumbing
# ---------------------------------------------------------------------------


def test_canonical_key_collapses_layer_index():
    assert S.canonical_key("model.layers.17.self_attn.q_proj.weight") \
        == "model.layers.*.self_attn.q_proj.weight"
    assert S.canonical_key("model.embed_tokens.weight") \
        == "model.embed_tokens.weight"


def test_collapse_layers_rejects_intra_stack_divergence():
    a = TensorSpec(shape=(8, 8), dtype="float32",
                   dim_axes=(("x",), ()))
    b = TensorSpec(shape=(8, 8), dtype="float32",
                   dim_axes=((), ("x",)))
    lo = SpecLayout(mesh_axes=(("x", 2),),
                    entries={"model.layers.0.w": a,
                             "model.layers.1.w": b})
    with pytest.raises(ValueError, match="layers disagree"):
        S.collapse_layers(lo)


def test_tensor_spec_restrict_drops_foreign_axes():
    ts = TensorSpec(shape=(4, 8, 16), dtype="bfloat16",
                    dim_axes=(("pp",), ("sharding", "sep"), ("mp",)))
    r = ts.restrict(frozenset({"sharding", "mp"}))
    assert r.dim_axes == ((), ("sharding",), ("mp",))


def test_layout_from_arrays_reads_concrete_shardings():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = Mesh(np.asarray(devs[:2], dtype=object), ("x",))
    tree = {
        "a": jax.device_put(jnp.ones((8, 4), jnp.float32),
                            NamedSharding(mesh, P("x", None))),
        "b": jax.device_put(jnp.ones((4,), jnp.bfloat16),
                            NamedSharding(mesh, P())),
    }
    lo = layout_from_arrays(tree)
    assert lo["a"].dim_axes == (("x",), ())
    assert lo["b"].dim_axes == ((),)
    assert lo["b"].dtype == "bfloat16"
    # the backend's default memory kind canonicalizes to "device"
    assert lo["a"].memory_kind == "device"
    assert dict(lo.mesh_axes)["x"] == 2


def test_hybrid_spec_hook_matches_placed_state():
    """hybrid_param_spec is the introspection hook the extractor reads;
    it must be the SAME rule shard_hybrid_state places by — compare the
    hook's specs against the concrete placed arrays."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    from paddle_tpu.models.llama_hybrid import (hybrid_mesh,
                                                hybrid_param_spec,
                                                shard_hybrid_state,
                                                stack_llama_state)

    cfg, model, opt, params, ids, labels = _flagship()
    hmesh = hybrid_mesh(jax.devices(), pp=2, dp=1, sharding=2, sep=1,
                        mp=2)
    hstate = shard_hybrid_state(
        stack_llama_state(dict(params), cfg.num_hidden_layers), hmesh)
    for name, v in hstate.items():
        want = hybrid_param_spec(name, tuple(v.shape), hmesh)
        got = tensor_spec_from_array(v)
        from paddle_tpu.parallel.specs import spec_to_dim_axes

        assert got.dim_axes == spec_to_dim_axes(want, v.ndim), \
            (name, want, got.describe())


def test_serving_param_layout_is_canonical_and_single_chip():
    cfg, model, opt, params, ids, labels = _flagship()
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, num_pages=9,
                                   page_size=16, max_seq_len=64,
                                   prefill_token_budget=8)
    lo = eng.param_layout()
    assert "model.layers.*.self_attn.q_proj.weight" in lo.entries
    assert all(axes == () for ts in lo.entries.values()
               for axes in ts.dim_axes)
    rep = S.check_layout(lo, replicated_min_bytes=4 << 10)
    assert rep.ok, rep.summary()


def test_shard001_counts_manual_collectives_as_declared():
    """A manual shard_map all-gather is the ENGINE's schedule: the
    reshard audit must attribute it (jaxpr-level, the collective_budget
    machinery) and stay quiet without a declared override."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    from jax import shard_map

    mesh = Mesh(np.asarray(devs[:2], dtype=object), ("x",))

    def body(v):
        return jax.lax.all_gather(v, "x", tiled=True)

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P(),
                   check_vma=False)
    rep = A.check(fn, jnp.ones((8,), jnp.float32),
                  passes=["sharding_consistency"], exemptions=(),
                  options={"sharding_consistency":
                           {"audit_resharding": True}})
    assert rep.ok, rep.summary()
