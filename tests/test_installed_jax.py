"""The code is written for the one installed toolchain (jax 0.9): these
cases hold the installed API as the package uses it — shard_map,
axis_size, set_mesh, jaxpr walking through jax.extend.core, source
provenance, memory spaces — and that no other-version branch is left.
They replace, one for one, the cases of the deleted
common/jax_compat.py shims."""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

PKG = pathlib.Path(__file__).resolve().parents[1] / "paddle_tpu"


def _mesh(n, name="x"):
    return Mesh(np.asarray(jax.devices()[:n]), (name,))


# ---------------------------------------------------------------------------
# no version probes, no shim module
# ---------------------------------------------------------------------------

def test_no_version_probe_of_jax_left_in_package():
    probe = re.compile(
        r"\b(?:getattr|hasattr)\(\s*(?:jax|jax\.lax|jax\.sharding|pltpu|pl)"
        r"\s*,")
    hits = [f"{p.relative_to(PKG)}:{i}"
            for p in PKG.rglob("*.py")
            for i, line in enumerate(p.read_text().splitlines(), 1)
            if probe.search(line)]
    assert not hits, hits


def test_compat_shim_module_is_gone():
    with pytest.raises(ImportError):
        import paddle_tpu.common.jax_compat  # noqa: F401


def test_kernels_use_installed_compiler_params():
    from paddle_tpu.ops.pallas import flash_attention as F

    assert not hasattr(F, "_CompilerParams")
    assert "pltpu.CompilerParams(" in (PKG / "ops/pallas/flash_attention.py"
                                       ).read_text()


# ---------------------------------------------------------------------------
# shard_map / axis_size / set_mesh, used directly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("check_vma", [True, False])
def test_shard_map_executes(check_vma):
    mesh = _mesh(4)
    fn = jax.shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                       in_specs=(P("x"),), out_specs=P(),
                       check_vma=check_vma)
    out = fn(jnp.arange(8.0))
    np.testing.assert_allclose(np.asarray(out), [12.0, 16.0])


@pytest.mark.parametrize("n", [2, 4])
def test_axis_size_inside_shard_map(n):
    from paddle_tpu.parallel.sep import _axis_size

    assert _axis_size is jax.lax.axis_size
    mesh = _mesh(n)
    fn = jax.shard_map(lambda v: v * _axis_size("x"), mesh=mesh,
                       in_specs=(P("x"),), out_specs=P("x"))
    np.testing.assert_allclose(np.asarray(fn(jnp.ones(4 * n))), float(n))


def test_set_mesh_binds_the_ambient_mesh():
    mesh = _mesh(2)
    with jax.sharding.set_mesh(mesh):
        assert jax.sharding.get_abstract_mesh().axis_names == ("x",)
    assert jax.sharding.get_abstract_mesh().axis_names == ()


# ---------------------------------------------------------------------------
# analysis: jaxpr walking and provenance
# ---------------------------------------------------------------------------

def test_sub_jaxprs_walks_closed_and_open_jaxprs():
    from paddle_tpu.analysis.core import sub_jaxprs, walk_eqns

    def f(x):
        return jax.lax.scan(lambda c, _: (jnp.sin(c), c), x, None,
                            length=3)[0]

    jaxpr = jax.make_jaxpr(f)(1.0).jaxpr
    scan = next(e for e in jaxpr.eqns if e.primitive.name == "scan")
    assert [n for n, _ in sub_jaxprs(scan)] == ["jaxpr"]
    assert "sin" in {e.primitive.name for e, _ in walk_eqns(jaxpr)}


def _outer_fn(x):
    def inner_fn(y):
        return jnp.cos(y)
    return inner_fn(x)


def test_eqn_source_gives_plain_function_names():
    from paddle_tpu.analysis.core import eqn_source, format_where

    eqn = jax.make_jaxpr(_outer_fn)(1.0).jaxpr.eqns[0]
    fname, line, func = eqn_source(eqn)
    assert fname.endswith("test_installed_jax.py") and line > 0
    assert func == "inner_fn"         # not _outer_fn.<locals>.inner_fn
    where, data = format_where(eqn)
    assert "inner_fn" in where
    assert data["stack_functions"][:2] == ("inner_fn", "_outer_fn")


def test_retrace_signature_sees_weak_types():
    from paddle_tpu.analysis.passes.retrace import _leaf_sig

    assert _leaf_sig(jnp.ones((2, 3), jnp.float32)) == \
        ("array", (2, 3), "float32", False)
    assert _leaf_sig(1.0)[3] is True              # python scalar: weak
    assert _leaf_sig("static")[0] == "static"


# ---------------------------------------------------------------------------
# memory spaces
# ---------------------------------------------------------------------------

def test_device_probe_surface():
    from paddle_tpu.core import device as D

    kinds = D.memory_kinds()
    assert kinds[0] == D.default_memory_kind() == "device"
    for k in kinds:
        assert D.supports_memory_kind(k)
    assert not D.supports_memory_kind("no_such_memory_space")
    assert D.host_memory_kind() == "pinned_host"
    assert D.host_offload_distinct() is True


def test_traced_transfers_are_visible_to_the_audit():
    from paddle_tpu.analysis.passes.memory_budget import \
        scan_memory_transfers
    from paddle_tpu.parallel.memory import place_on_device, place_on_host

    def f(h, g):
        return place_on_host(place_on_device(h) + g)

    x = jnp.ones((256,), jnp.float32)
    found = scan_memory_transfers(jax.make_jaxpr(f)(x, x).jaxpr)
    assert [(n, k) for n, k, _ in found] == [(1024, "device"),
                                             (1024, "host")]


def test_eager_placement_is_identity_off_the_tpu():
    """XLA:CPU labels every jit output ``device``; an eagerly
    host-labelled donated input would alias it and abort, so at rest
    the label only moves on a TPU."""
    from paddle_tpu.parallel.memory import place_on_device, place_on_host

    x = jnp.ones((16,), jnp.float32)
    assert place_on_host(x) is x and place_on_device(x) is x


def test_donated_step_round_trips_host_state():
    from paddle_tpu.parallel.memory import place_on_device, place_on_host

    step = jax.jit(lambda h, g: place_on_host(place_on_device(h) * 2 + g),
                   donate_argnums=0)
    g = jnp.ones((160,), jnp.float32)
    h = step(step(place_on_host(jnp.ones((160,), jnp.float32)), g), g)
    np.testing.assert_allclose(np.asarray(h), 7.0)


def test_host_state_moves_to_device_before_arithmetic():
    """jax refuses to mix memory spaces in one op; the helper is what
    makes ``host + device`` legal."""
    from paddle_tpu.parallel.memory import place_on_device, place_on_host

    def mixed(h, g):
        return place_on_host(h) + g

    x = jnp.ones((8,), jnp.float32)
    with pytest.raises(ValueError, match="memory_space"):
        jax.make_jaxpr(mixed)(x, x)
    jax.make_jaxpr(lambda h, g: place_on_device(place_on_host(h)) + g)(x, x)


def test_dots_saved_names_offloaded_policy():
    from paddle_tpu.parallel.memory import MemoryConfig, tag_saveable

    use, policy = MemoryConfig(remat="dots",
                               activation_offload=True).resolve_remat()
    assert use

    def f(w, x):
        h = tag_saveable(jnp.tanh(x @ w), "decoder_attn_out")
        return jnp.sum(h @ w)

    w = jnp.eye(8) * 0.5
    x = jnp.ones((4, 8))
    got = jax.grad(jax.checkpoint(f, policy=policy))(w, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jax.grad(f)(w, x)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# sharding forms the installed jit hands back
# ---------------------------------------------------------------------------

def test_placed_sharding_drops_trailing_nones():
    """jit returns P() for a replicated leaf and P(None) != P() in its
    cache key: a step fed its own outputs must not compile twice."""
    from paddle_tpu.parallel.schedule import PartitionSchedule

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("sharding", "mp"))
    sched = PartitionSchedule.from_plan(
        mesh, {"norm.weight": (64,), "q_proj.weight": (64, 64),
               "embed.weight": (64, 64)},
        lambda name: (P(None) if "norm" in name else
                      P(("mp", "sharding"), None) if "embed" in name else
                      P("sharding", "mp")))
    assert sched.spec_for("norm.weight", (64,)) == P(None)
    assert sched.named_sharding("norm.weight", (64,)).spec == P()
    assert sched.named_sharding("q_proj.weight", (64, 64)).spec == \
        P("sharding", "mp")
    assert sched.named_sharding("embed.weight", (64, 64)).spec == \
        P(("mp", "sharding"))


# ---------------------------------------------------------------------------
# the flash kernel under a GSPMD mesh: one launch per shard
# ---------------------------------------------------------------------------

def _qkv(b=4, s=128, h=4, kvh=2, d=32):
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for shape in ((b, s, h, d), (b, s, kvh, d), (b, s, kvh, d))]


@pytest.mark.parametrize("with_segments", [False, True])
def test_flash_op_runs_per_shard_under_kernel_mesh(with_segments):
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention_op,
                                                       kernel_mesh)

    q, k, v = _qkv()
    kw = {}
    if with_segments:
        seg = jnp.asarray(np.repeat([[1, 2]], 64, axis=1).reshape(1, 128)
                          .repeat(4, 0), jnp.int32)
        kw = {"q_segment_ids": seg, "kv_segment_ids": seg}
    want = flash_attention_op(q, k, v, **kw)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("sharding", "mp"))
    with kernel_mesh(mesh, ("dp", "sharding"), "mp"):
        def op(*a):
            return flash_attention_op(*a, **kw)._value

        jaxpr = jax.make_jaxpr(op)(q, k, v)
        got = jax.jit(op)(q, k, v)
    assert "shard_map" in {e.primitive.name for e in jaxpr.jaxpr.eqns}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want._value),
                               rtol=1e-5, atol=1e-5)


def test_flash_op_refuses_shapes_that_do_not_divide():
    from paddle_tpu.ops.pallas.flash_attention import (
        FlashUnsupportedError, flash_attention_op, kernel_mesh)

    q, k, v = _qkv(b=3)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("sharding", "mp"))
    with kernel_mesh(mesh, ("sharding",), "mp"):
        with pytest.raises(FlashUnsupportedError, match="do not divide"):
            flash_attention_op(q, k, v)
