"""The main-path Pallas kernels compile for a described TPU v5e.

No chip is attached: the TPU compiler that is installed here compiles
for a ``v5e:2x2`` topology that is described, not present (section 2 of
the on-chip-measurement guide).  This finds what interpret mode cannot:
unaligned slices, a kernel that asks for more VMEM than it may scope, a
program that cannot be partitioned.  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a module-scoped fixture (never at
import), all in this one file: only one process may load the TPU
library, and every xdist worker imports every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import SingleDeviceSharding

HEADS, KV_HEADS = 32, 8
# head dims of chip_smoke.py's serve (Llama-3-8B) and train
# (Llama-3.2-1B) phases
HEAD_DIMS = (128, 64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, kernels=()):
    """Compile ``fn`` for the described chip; the kernel must be in the
    program as a Mosaic custom call, and each of ``kernels`` (the
    ``name=`` of a ``pallas_call``) as the name of such an instruction:
    a device trace prints the instruction, and the benchmark's readers
    find a kernel's events by it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for name in kernels:
        # under autodiff the scope wraps the name: jvp_<name>_
        assert any(re.match(rf"\s*(ROOT )?%(\w+_)?{name}_*(\.\d+)? = ", ln)
                   for ln in calls), f"no instruction named after {name}"
    return text


def _qkv(d, b=2, s=2048):
    return [((b, s, HEADS, d), jnp.bfloat16),
            ((b, s, KV_HEADS, d), jnp.bfloat16),
            ((b, s, KV_HEADS, d), jnp.bfloat16)]


def _flash_loss(q, k, v):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    o = flash_attention_raw(q, k, v, causal=True, interpret=False)
    return jnp.sum(o.astype(jnp.float32) ** 2)


@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_forward_compiles(one_chip, d):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    _compile(lambda q, k, v: flash_attention_raw(
        q, k, v, causal=True, interpret=False), one_chip, *_qkv(d),
        kernels=["flash_attention_fwd_headbatched"])


@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_forward_backward_compiles(one_chip, d):
    _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)), one_chip, *_qkv(d),
             kernels=["flash_attention_fwd_headbatched",
                      "flash_attention_bwd_headbatched"])


@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_forward_backward_in_scan_compiles(one_chip, d):
    """The accum train step's structure: the head-batched kernels inside
    lax.scan (the program of tests/test_flash_headbatched_scan.py)."""
    def prog(q, k, v):
        def body(qc, _):
            val, g = jax.value_and_grad(_flash_loss)(qc, k, v)
            return qc - 1e-3 * g.astype(qc.dtype), val
        return lax.scan(body, q, None, length=2)

    _compile(prog, one_chip, *_qkv(d),
             kernels=["flash_attention_fwd_headbatched",
                      "flash_attention_bwd_headbatched"])


@pytest.mark.parametrize("d", HEAD_DIMS)
@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_ragged_paged_decode_compiles(one_chip, d, cache_dtype):
    from paddle_tpu.ops.pallas.decode_attention import \
        ragged_paged_decode_raw

    rows, slots, pages, page = 136, 8, 129, 128
    cache = ((pages, KV_HEADS, page, d), cache_dtype)
    _compile(lambda q, kc, vc, lens, slot, tables: ragged_paged_decode_raw(
        q, kc, vc, lens, slot, tables, interpret=False), one_chip,
        ((rows, HEADS, d), jnp.bfloat16), cache, cache,
        ((rows,), jnp.int32), ((rows,), jnp.int32),
        ((slots, 16), jnp.int32), kernels=["ragged_paged_attention"])


@pytest.mark.parametrize("d", HEAD_DIMS)
def test_paged_decode_compiles(one_chip, d):
    """The legacy chunked serving path's kernel (one query row a slot)."""
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_raw

    slots, pages, page = 8, 129, 128
    cache = ((pages, KV_HEADS, page, d), jnp.bfloat16)
    _compile(lambda q, kc, vc, lens, tables: paged_decode_raw(
        q, kc, vc, lens, tables, interpret=False), one_chip,
        ((slots, HEADS, d), jnp.bfloat16), cache, cache,
        ((slots,), jnp.int32), ((slots, 16), jnp.int32),
        kernels=["paged_decode_attention"])


@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_decode_compiles(one_chip, d):
    """``generate()``'s decode step over a dense cache."""
    from paddle_tpu.ops.pallas.decode_attention import flash_decode_raw

    b, t_max = 8, 2048
    cache = ((b, KV_HEADS, t_max, d), jnp.bfloat16)
    _compile(lambda q, kc, vc, lens: flash_decode_raw(
        q, kc, vc, lens, interpret=False), one_chip,
        ((b, HEADS, d), jnp.bfloat16), cache, cache, ((b,), jnp.int32),
        kernels=["flash_decode_attention"])


@pytest.mark.parametrize("d", HEAD_DIMS)
def test_flash_packed_forward_backward_compiles(one_chip, d):
    """The general kernels (segment ids: padded or packed batches) and
    the backward that goes with them, by name."""
    from paddle_tpu.ops.pallas import flash_attention as F

    def loss(q, k, v, ids):
        o = F.flash_attention_raw(q, k, v, causal=True, interpret=False,
                                  q_segment_ids=ids, kv_segment_ids=ids)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *_qkv(d),
                    ((2, 2048), jnp.int32), kernels=[F.FWD_KERNEL])
    assert f"{F.BWD_FUSED_KERNEL}_" in text or (
        f"{F.BWD_DQ_KERNEL}_" in text and f"{F.BWD_DKV_KERNEL}_" in text)


# expert widths of the dropless-MoE path (PR 17): K=2048, N=1408
_MOE_ROWS, _MOE_K, _MOE_N, _MOE_SEGS = 4096, 2048, 1408, 8


def test_grouped_matmul_compiles(one_chip):
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul_raw

    seg = ((_MOE_SEGS,), jnp.int32)
    _compile(lambda x, w, s, l, i: grouped_matmul_raw(
        x, w, s, l, i, interpret=False), one_chip,
        ((_MOE_ROWS, _MOE_K), jnp.bfloat16),
        ((_MOE_SEGS, _MOE_K, _MOE_N), jnp.bfloat16), seg, seg, seg)


def test_grouped_outer_compiles(one_chip):
    """The dW half of the grouped-matmul backward: (K, N) is tiled in
    the grid — held whole in fp32 it asked for 33 MB of 16 MB VMEM."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_outer_raw

    seg = ((_MOE_SEGS,), jnp.int32)
    _compile(lambda x, dy, s, l: grouped_outer_raw(
        x, dy, s, l, interpret=False), one_chip,
        ((_MOE_ROWS, _MOE_K), jnp.bfloat16),
        ((_MOE_ROWS, _MOE_N), jnp.bfloat16), seg, seg)


def test_grouped_outer_tiling_matches_reference():
    """Interpret-mode result of the tiled kernel at a shape that cuts
    both K and N into several tiles, against the plain einsum."""
    from paddle_tpu.ops.pallas import grouped_matmul as G

    rng = np.random.default_rng(0)
    rows, k, n, bm = 512, 1024, 4096, 128
    assert G._lane_tile(n, 2048) == 2048
    assert G._lane_tile(k, G._OUTER_TILE_ELEMS // 2048) == 512
    lens = np.array([100, 0, 256, 17], np.int32)
    starts = np.asarray(G.segment_starts(jnp.asarray(lens), bm))
    x = np.zeros((rows, k), np.float32)
    dy = rng.standard_normal((rows, n)).astype(np.float32)
    for s, l in zip(starts, lens):
        x[s:s + l] = rng.standard_normal((l, k))
    got = G.grouped_outer_raw(jnp.asarray(x), jnp.asarray(dy),
                              jnp.asarray(starts), jnp.asarray(lens),
                              block_rows=bm, interpret=True)
    for i, (s, l) in enumerate(zip(starts, lens)):
        want = x[s:s + l].T @ dy[s:s + l]
        np.testing.assert_allclose(np.asarray(got[i]), want, rtol=1e-4,
                                   atol=1e-3)
