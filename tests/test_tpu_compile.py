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
    """One query row a sequence: the registered op's and the incubate
    API's kernel, and the ragged wrapper's path for head dims that are
    not whole lanes."""
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_raw

    slots, pages, page = 8, 129, 128
    cache = ((pages, KV_HEADS, page, d), jnp.bfloat16)
    _compile(lambda q, kc, vc, lens, tables: paged_decode_raw(
        q, kc, vc, lens, tables, interpret=False), one_chip,
        ((slots, HEADS, d), jnp.bfloat16), cache, cache,
        ((slots,), jnp.int32), ((slots, 16), jnp.int32),
        kernels=["paged_decode_attention"])


# the serving cell's engine (benchmarks/configs/
# mistral-7b-v0.3-serve-l16.json): 705 pages of 128, 8 KV heads x 128,
# 32 slots of 22 pages, 32 decode rows + a 256-token prefill chunk
_CELL_PAGES, _CELL_PAGE, _CELL_SLOTS, _CELL_SEQ, _CELL_BUDGET = (
    705, 128, 32, 2816, 256)


def test_ragged_paged_decode_compiles_at_the_cells_geometry(one_chip):
    """``mistral7b-serve-l16.chat``'s launch: 288 packed rows (32 slots +
    a 256-token chunk), a 32 x 22 page table, 705 bf16 pages of 128."""
    from paddle_tpu.ops.pallas.decode_attention import \
        ragged_paged_decode_raw

    rows = _CELL_SLOTS + _CELL_BUDGET
    cache = ((_CELL_PAGES, KV_HEADS, _CELL_PAGE, 128), jnp.bfloat16)
    _compile(lambda q, kc, vc, lens, slot, tables: ragged_paged_decode_raw(
        q, kc, vc, lens, slot, tables, interpret=False), one_chip,
        ((rows, HEADS, 128), jnp.bfloat16), cache, cache,
        ((rows,), jnp.int32), ((rows,), jnp.int32),
        ((_CELL_SLOTS, -(-_CELL_SEQ // _CELL_PAGE)), jnp.int32),
        kernels=["ragged_paged_attention"])


def _pallas_calls(jaxpr, name):
    """The ``pallas_call`` equations named ``name`` in a jaxpr, however
    deep (a kernel jitted on its own is an equation of an inner one)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" \
                and eqn.params["name"] == name:
            found.append(eqn)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                found += _pallas_calls(inner, name)
    return found


def _ragged_calls(max_seq_len, layers=2):
    """The ``pallas_call``s named ``ragged_paged_attention`` in the
    jaxpr of a small engine's unified step (nothing is compiled)."""
    import functools

    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.debug(vocab=64, hidden=32, layers=layers, heads=4,
                            kv_heads=2, inter=64, max_pos=max_seq_len)
    params = {k: jnp.asarray(v) for k, v in
              LlamaForCausalLM(cfg).functional_state().items()}
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=8, num_pages=24, page_size=8,
        max_seq_len=max_seq_len, prefill_token_budget=120, pages_per_step=4)
    fn, args, kwargs, _ = eng.analysis_entry()
    static = {k: kwargs.pop(k) for k in ("self_cfg_id", "pages_per_step")}
    return eng, _pallas_calls(
        jax.make_jaxpr(functools.partial(fn, **static))(*args, **kwargs
                                                        ).jaxpr,
        "ragged_paged_attention")


def test_ragged_kernels_grid_is_the_query_tiles_alone():
    """One launch a layer, over the step's query tiles: the page walk is
    inside the kernel, so a longer page table (``pages_per_seq`` 8 and
    32 here, turns of 4 pages) adds no grid step.  The parent's grid was
    rows x page groups: 288 x 6 in the serving cell."""
    from paddle_tpu.ops.pallas.decode_attention import ragged_tile_rows

    grids = []
    for seq in (64, 256):
        eng, calls = _ragged_calls(seq)
        assert eng.pages_per_seq == seq // 8
        assert len(calls) == eng.cfg.num_hidden_layers == 2
        (grid,) = {c.params["grid_mapping"].grid for c in calls}
        grids.append(grid)
    cfg = eng.cfg
    tiles = -(-eng.rows_cap // ragged_tile_rows(
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim))
    assert grids == [(tiles,), (tiles,)] and tiles == 2


def _serving_step_text(one_chip, monkeypatch, cache_dtype, vocab=256):
    """Optimized HLO of the engine's step program (the unified ragged
    step) for the described chip, 2 layers at the cell's KV geometry.  The engine is built small (24 pages, on
    the CPU) and gives the call through ``analysis_entry()``; the shapes
    it is lowered with carry the cell's 705 pages."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops.pallas import decode_attention

    # the engine's own call leaves ``interpret`` to the backend, which
    # is the CPU here: steer it to the compiled kernel from the test
    monkeypatch.setattr(decode_attention, "pallas_interpret",
                        lambda: False)
    layers = 2
    cfg = LlamaConfig.debug(vocab=vocab, hidden=KV_HEADS * 128, layers=layers,
                            heads=KV_HEADS, kv_heads=KV_HEADS, inter=256,
                            max_pos=_CELL_SEQ)
    params = {k: jnp.asarray(v, jnp.bfloat16) for k, v in
              LlamaForCausalLM(cfg).functional_state().items()}
    small = 24
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=_CELL_SLOTS, num_pages=small,
        page_size=_CELL_PAGE, max_seq_len=_CELL_SEQ, pages_per_step=4,
        prefill_token_budget=_CELL_BUDGET, cache_dtype=cache_dtype)
    fn, args, kwargs, _ = eng.analysis_entry()
    assert args[3].shape == (_CELL_SLOTS + _CELL_BUDGET, 5)
    pool = (_CELL_PAGES, KV_HEADS, _CELL_PAGE, 128)

    def described(x):
        shape = pool if x.shape == (small,) + pool[1:] else x.shape
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=one_chip)

    static = {k: kwargs.pop(k) for k in ("self_cfg_id", "pages_per_step")}
    text = fn.lower(*jax.tree.map(described, args), **static,
                    **jax.tree.map(described, kwargs)).compile().as_text()
    assert "tpu_custom_call" in text
    return text, pool, 2 * layers


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_serving_step_writes_kv_in_place(one_chip, monkeypatch, cache_dtype):
    """The step's K/V write touches the rows it writes: no instruction
    of the compiled step copies or transposes a whole pool (the old
    write, ``pool.at[phys, :, off, :].set``, had XLA move each pool into
    a layout of its own and back around the kernel: 64 copies of 185 MB
    a step in the serving cell, PERF.md section 6, PR 25), and every
    pool is updated in the buffer it came in."""
    text, pool, npools = _serving_step_text(one_chip, monkeypatch,
                                            cache_dtype)
    count = int(np.prod(pool))
    moved = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", ln)
        if m and np.prod([int(x) for x in m.group(1).split(",")]) == count:
            moved.append(ln.strip()[:160])
    assert not moved, "\n".join(moved)
    dims = ",".join(map(str, pool))
    entry = text[text.index("\nENTRY "):]
    pools = {int(n) for n in re.findall(
        rf"= \w+\[{dims}\]\S* parameter\((\d+)\)", entry)}
    header = next(ln for ln in text.splitlines() if "HloModule" in ln)
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    assert len(pools) == npools and pools <= aliased, (pools, aliased)


def test_serving_step_samples_on_the_device(one_chip, monkeypatch):
    """The engine's own call (``analysis_entry()``: the previous
    launch's tokens among its arguments) is ONE program for the
    described chip whose results hold the sampled tokens, int32
    ``[gather_cap]``, beside the gathered logits; the token column it
    takes has the same shape, so the launch after it is the same
    program.  The head runs over the gathered rows alone: nothing of
    ``[rows_cap, vocab]`` exists in it."""
    vocab = 384                     # no other dimension of the tiny model
    text, pool, _ = _serving_step_text(one_chip, monkeypatch, jnp.bfloat16,
                                       vocab=vocab)
    assert text.count("\nENTRY ") == 1
    rows_cap = _CELL_SLOTS + _CELL_BUDGET
    gather_cap = 2 * _CELL_SLOTS
    entry = text[text.index("\nENTRY "):]
    root = next(ln for ln in entry.splitlines() if "ROOT " in ln)
    assert f"s32[{gather_cap}]" in root and f"f32[{gather_cap},{vocab}]" in root
    assert re.search(rf"= s32\[{gather_cap}\]\S* parameter\(", entry)
    assert not re.search(rf"\[{rows_cap},{vocab}\]", text)


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


@pytest.mark.parametrize("bank", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_grouped_matmul_compiles(one_chip, bank):
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul_raw

    seg = ((_MOE_SEGS,), jnp.int32)
    scale = [((_MOE_SEGS, _MOE_N), jnp.float32)] if bank == jnp.int8 else []
    _compile(lambda x, w, s, l, i, sc=None: grouped_matmul_raw(
        x, w, s, l, i, w_scale=sc, interpret=False), one_chip,
        ((_MOE_ROWS, _MOE_K), jnp.bfloat16),
        ((_MOE_SEGS, _MOE_K, _MOE_N), bank), seg, seg, seg, *scale)


# the DeepSeek-V3.2 serving cell (benchmarks/configs/
# deepseek-v3.2-serve-l5-ep16.json): 16 held experts of 7168 x 2048, a
# 528-row step's 4224 token copies in 16-row blocks
@pytest.mark.parametrize("k, n", [(7168, 2048), (2048, 7168)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_block_major_compiles(one_chip, k, n):
    """A 29 MB slice cannot ride in one block: tiled over N, a row block
    reading its segment's weight id."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul_raw

    seg = ((16,), jnp.int32)
    _compile(lambda x, w, s, l, i: grouped_matmul_raw(
        x, w, s, l, i, block_rows=16, interpret=False), one_chip,
        ((4496, k), jnp.bfloat16), ((16, k, n), jnp.bfloat16), seg, seg, seg,
        kernels=["grouped_matmul_blocks"])


# the Mellum2 serving cell's top rung (benchmarks/configs/
# mellum2-12b-a2.5b-serve-l8.json): 544 rows x 8 copies in 64-row
# blocks, a block of slack an expert and the park block: 8,512 rows
_M2_MOE_ROWS, _M2_MOE_K, _M2_MOE_N, _M2_MOE_E = 4352 + 64 * 64 + 64, 2304, 896, 64


@pytest.mark.parametrize("bank", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_grouped_matmul_dense_takes_its_buffer_as_it_stands(one_chip, bank):
    """The serving dispatch's launch: the buffer's last block is the
    park block, so nothing is appended to it and nothing cut off the
    output, which has the buffer's rows; no copy of either is made
    around the kernel."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul_raw

    seg = ((_M2_MOE_E,), jnp.int32)
    shapes = [((_M2_MOE_ROWS, _M2_MOE_K), jnp.bfloat16),
              ((_M2_MOE_E, _M2_MOE_K, _M2_MOE_N), bank), seg, seg, seg]
    if bank == jnp.int8:
        shapes.append(((_M2_MOE_E, _M2_MOE_N), jnp.float32))

    def launch(x, w, s, l, i, scale=None):
        return grouped_matmul_raw(x, w, s, l, i, block_rows=64,
                                  w_scale=scale, dense=True, interpret=False)

    text = _compile(launch, one_chip, *shapes,
                    kernels=["grouped_matmul_blocks"])
    entry = text[text.index("\nENTRY "):]
    assert re.search(rf"ROOT %\S+ = bf16\[{_M2_MOE_ROWS},{_M2_MOE_N}\]\S* "
                     r"custom-call\(", entry), entry
    moved = [ln.strip()[:160] for ln in text.splitlines() if re.match(
        rf"\s*(?:ROOT )?%[\w.\-]+ = \w+\[({_M2_MOE_ROWS}|"
        rf"{_M2_MOE_ROWS + 64}|{_M2_MOE_ROWS - 64}),\d+\]\S* "
        r"(copy|pad|concatenate|slice|fusion)\(", ln)]
    assert not moved, "\n".join(moved)


_DS_ROWS, _DS_PAGES, _DS_PAGE, _DS_SLOTS, _DS_SEQ = 528, 3072, 128, 16, 24704


def _ds_tile():
    """The tile the wrappers derive at the cell's widths: 8 rows, 66
    tiles of the 528-row step."""
    from paddle_tpu.ops.pallas.sparse_mla import sparse_tile_rows

    tile = sparse_tile_rows(128, 640, 512)
    assert tile == sparse_tile_rows(64, 128) == 8 and _DS_ROWS % tile == 0
    return tile


def _compile_tiled(fn, one_chip, *shapes, kernel):
    """Compile at the cell's geometry; the kernel's grid is the TILES of
    packed rows alone (the page walk is inside it)."""
    _compile(fn, one_chip, *shapes, kernels=[kernel])
    (call,) = _pallas_calls(jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(s, d) for s, d in shapes]).jaxpr, kernel)
    assert call.params["grid_mapping"].grid == (_DS_ROWS // _ds_tile(),)


@pytest.mark.parametrize("pages_per_step", [4, 16])
def test_lightning_index_scores_compiles(one_chip, pages_per_step):
    from paddle_tpu.ops.pallas.sparse_mla import lightning_index_scores_raw

    _compile_tiled(
        lambda q, w, pool, lens, slot, tables:
        lightning_index_scores_raw(q, w, pool, lens, slot, tables,
                                   pages_per_step=pages_per_step,
                                   interpret=False), one_chip,
        ((_DS_ROWS, 64, 128), jnp.bfloat16), ((_DS_ROWS, 64), jnp.float32),
        ((_DS_PAGES, _DS_PAGE, 128), jnp.bfloat16),
        ((_DS_ROWS,), jnp.int32), ((_DS_ROWS,), jnp.int32),
        ((_DS_SLOTS, 193), jnp.int32), kernel="lightning_index_scores")


@pytest.mark.parametrize("pages_per_step", [4, 16])
def test_sparse_mla_attention_compiles(one_chip, pages_per_step):
    from paddle_tpu.ops.pallas.sparse_mla import sparse_mla_attention_raw

    width = -(-193 // pages_per_step) * pages_per_step * _DS_PAGE
    _compile_tiled(
        lambda q, pool, scores, sel, lens, slot, tables:
        sparse_mla_attention_raw(q, pool, scores, sel, lens, slot, tables,
                                 dv=512, pages_per_step=pages_per_step,
                                 interpret=False), one_chip,
        ((_DS_ROWS, 128, 640), jnp.bfloat16),
        ((_DS_PAGES, _DS_PAGE, 640), jnp.bfloat16),
        ((_DS_ROWS, width), jnp.float32), ((_DS_ROWS, 2), jnp.float32),
        ((_DS_ROWS,), jnp.int32), ((_DS_ROWS,), jnp.int32),
        ((_DS_SLOTS, 193), jnp.int32), kernel="sparse_mla_attention")


def test_deepseek_step_writes_latents_and_index_keys_in_place(one_chip,
                                                              monkeypatch):
    """PR 25's test for the new pools: the DeepSeek unified step (one
    dense and one expert layer at the published widths, the cell's pool
    geometry) copies or transposes no whole pool, and every pool is
    updated in the buffer it came in."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.deepseek_v32 import DeepseekV32Config
    from paddle_tpu.ops.pallas import grouped_matmul, sparse_mla

    for mod in (sparse_mla, grouped_matmul):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    cfg = DeepseekV32Config(num_hidden_layers=2, first_k_dense_replace=1,
                            experts_held=(0, 16), vocab_size=16160)
    params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
              for k, s in cfg.leaf_shapes().items()}
    small = 24
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=_DS_SLOTS, num_pages=small,
        page_size=_DS_PAGE, max_seq_len=_DS_SEQ, prefill_token_budget=512,
        enable_prefix_cache=True)
    assert eng.pages_per_step == 16
    fn, args, kwargs, _ = eng.analysis_entry()
    assert args[3].shape == (_DS_ROWS, 5)
    pools = {(_DS_PAGES, _DS_PAGE, 640): 0, (_DS_PAGES, _DS_PAGE, 128): 0}

    def described(x):
        shape = (_DS_PAGES, *x.shape[1:]) if x.shape[:2] == (small, _DS_PAGE) \
            else x.shape
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=one_chip)

    static = {k: kwargs.pop(k) for k in ("self_cfg_id", "pages_per_step")}
    text = fn.lower(*jax.tree.map(described, args), **static,
                    **jax.tree.map(described, kwargs)).compile().as_text()
    names = {m.group(1) for m in re.finditer(
        r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text)}
    assert {"lightning_index_scores", "sparse_mla_attention",
            "grouped_matmul_blocks"} <= names, names
    moved = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", ln)
        if m and any(np.prod([int(x) for x in m.group(1).split(",")])
                     == np.prod(p) for p in pools):
            moved.append(ln.strip()[:160])
    assert not moved, "\n".join(moved)
    entry = text[text.index("\nENTRY "):]
    header = next(ln for ln in text.splitlines() if "HloModule" in ln)
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    for pool in pools:
        dims = ",".join(map(str, pool))
        found = {int(n) for n in re.findall(
            rf"= \w+\[{dims}\]\S* parameter\((\d+)\)", entry)}
        assert len(found) == cfg.num_hidden_layers and found <= aliased, \
            (pool, found, aliased)


# the Mellum2 serving cell's engine (benchmarks/configs/
# mellum2-12b-a2.5b-serve-l8.json): 32 slots of 195 pages of 128, a
# 512-token prefill chunk, 7168 pages of the full kind and 1024 of the
# window kind
_M2_SLOTS, _M2_SEQ, _M2_BUDGET, _M2_FULL, _M2_WINDOW = (32, 24960, 512,
                                                        7168, 1024)


def test_mellum2_step_compiles_with_both_kinds_written_in_place(
        one_chip, monkeypatch):
    """PR 25's test for two kinds of page: one period of layers (three
    window, one full) at the published widths and the cell's geometry.
    The step holds both attention kernels under their own names and the
    experts' grouped matmuls, copies or transposes no whole pool of
    either kind, and updates every pool in the buffer it came in."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models.mellum2 import Mellum2Config
    from paddle_tpu.ops.pallas import decode_attention, grouped_matmul

    for mod in (decode_attention, grouped_matmul):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    cfg = Mellum2Config(num_hidden_layers=4)
    params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
              for k, s in cfg.leaf_shapes().items()}
    small = {"full": 24, "window": 20}
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=_M2_SLOTS, num_pages=small, page_size=128,
        max_seq_len=_M2_SEQ, prefill_token_budget=_M2_BUDGET,
        enable_prefix_cache=True)
    assert [kp.bound for kp in eng.pages] == [None, 17]
    fn, args, kwargs, _ = eng.analysis_entry()
    assert args[3].shape == (_M2_SLOTS + _M2_BUDGET, 6)
    assert [t.shape for t in args[4]] == [(_M2_SLOTS, 195)] * 2
    real = {small["full"]: _M2_FULL, small["window"]: _M2_WINDOW}
    tail = (cfg.num_key_value_heads, 128, cfg.head_dim)

    def described(x):
        shape = (real[x.shape[0]], *tail) if x.shape[1:] == tail else x.shape
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=one_chip)

    static = {k: kwargs.pop(k) for k in ("self_cfg_id", "pages_per_step")}
    text = fn.lower(*jax.tree.map(described, args), **static,
                    **jax.tree.map(described, kwargs)).compile().as_text()
    names = [m.group(1) for m in re.finditer(
        r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text)]
    assert names.count("ragged_paged_attention") == 1
    assert names.count("ragged_paged_attention_window") == 3
    assert names.count("grouped_matmul_blocks") == 3 * 4
    pools = {(_M2_FULL, *tail): 2, (_M2_WINDOW, *tail): 6}
    moved = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", ln)
        if m and any(np.prod([int(x) for x in m.group(1).split(",")])
                     == np.prod(p) for p in pools):
            moved.append(ln.strip()[:160])
    assert not moved, "\n".join(moved)
    entry = text[text.index("\nENTRY "):]
    header = next(ln for ln in text.splitlines() if "HloModule" in ln)
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    for pool, n in pools.items():
        dims = ",".join(map(str, pool))
        found = {int(i) for i in re.findall(
            rf"= \w+\[{dims}\]\S* parameter\((\d+)\)", entry)}
        assert len(found) == n and found <= aliased, (pool, found, aliased)


def test_ssd_scan_compiles_at_the_cells_geometry(one_chip):
    """The Nemotron-3 cell's scan: 640 packed rows (128 slots and a
    512-token chunk) in tiles of 128, 128 heads of 64 in 8 groups, a
    state of 128, 161 state entries of 4.19 MB."""
    from paddle_tpu.ops.pallas.ssd_scan import (SSD_SCAN_KERNEL,
                                                mamba2_ssd_scan,
                                                ssd_max_units)

    T, H, P, G, N, E = 640, 128, 64, 8, 128, 161
    ids = ((T,), jnp.int32)
    _compile(lambda x, dt, a, B, C, pool, slot, lens, src, dst:
             mamba2_ssd_scan(x, dt, a, B, C, pool, slot, lens, src, dst,
                             tile_rows=128,
                             max_units=ssd_max_units(T, 128, 128)),
             one_chip, ((T, H, P), jnp.bfloat16), ((T, H), jnp.float32),
             ((T, H), jnp.float32), ((T, G, N), jnp.bfloat16),
             ((T, G, N), jnp.bfloat16), ((E, H, P, N), jnp.float32),
             ids, ids, ids, ids, kernels=[SSD_SCAN_KERNEL])


@pytest.mark.parametrize("T", [640, 128])
def test_kda_scan_compiles_at_the_cells_geometry(one_chip, T):
    """The Kimi-Linear cell's scan at the highest and the lowest rung of
    its ladder: 640 packed rows (128 slots and a 512-token chunk) and 128
    (decode rows alone) in tiles of 128, 32 heads of 128 with a decay a
    channel, 161 state entries of 2.10 MB, blocks of 8 heads."""
    from paddle_tpu.ops.pallas.kda_scan import KDA_SCAN_KERNEL, kda_delta_scan
    from paddle_tpu.ops.pallas.ssd_scan import ssd_max_units

    H, d, E = 32, 128, 161
    ids = ((T,), jnp.int32)
    row = ((T, H, d), jnp.bfloat16)
    _compile(lambda q, k, v, g, beta, pool, slot, lens, src, dst:
             kda_delta_scan(q, k, v, g, beta, pool, slot, lens, src, dst,
                            tile_rows=128,
                            max_units=ssd_max_units(T, 128, 128)),
             one_chip, row, row, row, ((T, H, d), jnp.float32),
             ((T, H), jnp.float32), ((E, H, d, d), jnp.float32),
             ids, ids, ids, ids, kernels=[KDA_SCAN_KERNEL])


def test_dense_mla_attention_compiles_at_the_cells_geometry(one_chip):
    """The Kimi-Linear cell's latent walk: 640 packed rows of 32 heads
    in tiles of 32 over two pools a page (``c~`` 512 and ``k_p`` padded
    to 128), 280 pages of 128 tokens a sequence, 16 pages a turn."""
    from paddle_tpu.ops.pallas.dense_mla import (DENSE_MLA_KERNEL,
                                                 dense_mla_attention_raw)

    T, H, pages, slots = 640, 32, 4096, 128
    ids = ((T,), jnp.int32)
    _compile(lambda qc, qp, lat, pos, lens, slot, tables:
             dense_mla_attention_raw(qc, qp, lat, pos, lens, slot, tables,
                                     pages_per_step=16, interpret=False),
             one_chip, ((T, H, 512), jnp.bfloat16), ((T, H, 128), jnp.bfloat16),
             ((pages, 128, 512), jnp.bfloat16),
             ((pages, 128, 128), jnp.bfloat16), ids, ids,
             ((slots, 280), jnp.int32), kernels=[DENSE_MLA_KERNEL])


def test_causal_conv_compiles_at_the_cells_geometry(one_chip):
    """The Nemotron-3 cell's convolution: 640 packed rows of 10240
    channels in tiles of 128, four taps, 161 tails of ``[3, 10240]``
    bf16."""
    from paddle_tpu.ops.pallas.causal_conv import (CAUSAL_CONV_KERNEL,
                                                   packed_causal_conv)

    T, C, K, E = 640, 10240, 4, 161
    ids = ((T,), jnp.int32)
    _compile(lambda x, w, b, pool, slot, src, dst:
             packed_causal_conv(x, w, b, pool, slot, src, dst,
                                tile_rows=128),
             one_chip, ((T, C), jnp.bfloat16), ((K, C), jnp.bfloat16),
             ((C,), jnp.bfloat16), ((E, K - 1, C), jnp.bfloat16),
             ids, ids, ids, kernels=[CAUSAL_CONV_KERNEL])


def test_nemotron_step_compiles_with_state_and_pages_written_in_place(
        one_chip, monkeypatch):
    """The Nemotron-H unified step (one M, one E and one * layer at the
    published widths, 64 of 512 experts held) holds the four kernels of
    the cell, copies or transposes no whole pool of either sort, and
    every pool (K/V pages, SSM states, conv tails) is updated in the
    buffer it came in."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import nemotron_h
    from paddle_tpu.ops.pallas import decode_attention, grouped_matmul

    for mod in (nemotron_h, decode_attention, grouped_matmul):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    cfg = nemotron_h.NemotronHConfig(
        num_hidden_layers=3, hybrid_override_pattern="ME*",
        experts_held=(0, 64), vocab_size=16384)
    params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
              for k, s in cfg.leaf_shapes().items()}
    slots, pages, snaps = 8, 24, 2
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=slots, num_pages=pages, page_size=128,
        max_seq_len=7680, prefill_token_budget=120, enable_prefix_cache=True,
        state_snapshots=snaps)
    fn, args, kwargs, _ = eng.analysis_entry()
    assert args[3].shape == (128, 8)
    entries = slots + snaps + 1
    pools = {(pages, 2, 128, 128): 0, (entries, 128, 64, 128): 0,
             (entries, 3, 10240): 0}

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    static = {k: kwargs.pop(k) for k in ("self_cfg_id", "pages_per_step")}
    text = fn.lower(*jax.tree.map(described, args), **static,
                    **jax.tree.map(described, kwargs)).compile().as_text()
    names = {m.group(1) for m in re.finditer(
        r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text)}
    assert {"mamba2_causal_conv", "mamba2_ssd_scan", "ragged_paged_attention",
            "grouped_matmul_blocks"} <= names, names
    # a device trace files each kernel under its layer's scope
    from paddle_tpu.profiler.device_trace import scope_of
    scopes = {m.group(1): scope_of(m.group(2)) for m in re.finditer(
        r'%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call[^\n]*op_name="([^"]*)"',
        text)}
    assert scopes["mamba2_causal_conv"] == "mamba_conv", scopes
    assert scopes["mamba2_ssd_scan"] == "ssd_scan", scopes
    moved = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", ln)
        if m and any(np.prod([int(x) for x in m.group(1).split(",")])
                     == np.prod(p) for p in pools):
            moved.append(ln.strip()[:160])
    assert not moved, "\n".join(moved)
    header = next(ln for ln in text.splitlines() if "HloModule" in ln)
    aliased = len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header))
    assert aliased == 4                 # K, V, one SSM pool, one conv pool


def test_ssd_scan_compiles_at_lightnings_geometry(one_chip):
    """The MiniCPM-SALA cell's scan: 640 packed rows (96 slots and a
    512-token chunk, padded to tiles of 128), 32 heads of 128 with a key
    and a query EACH (32 groups of one head), 16 heads a grid step, 129
    state entries of 2.1 MB."""
    from paddle_tpu.ops.pallas.ssd_scan import (SSD_SCAN_KERNEL,
                                                mamba2_ssd_scan,
                                                ssd_max_units)

    T, H, P, N, E = 640, 32, 128, 128, 129
    ids = ((T,), jnp.int32)
    _compile(lambda x, dt, a, B, C, pool, slot, lens, src, dst:
             mamba2_ssd_scan(x, dt, a, B, C, pool, slot, lens, src, dst,
                             tile_rows=128, heads_per_step=16,
                             max_units=ssd_max_units(T, 128, 96)),
             one_chip, ((T, H, P), jnp.bfloat16), ((T, H), jnp.float32),
             ((T, H), jnp.float32), ((T, H, N), jnp.bfloat16),
             ((T, H, N), jnp.bfloat16), ((E, H, P, N), jnp.float32),
             ids, ids, ids, ids, kernels=[SSD_SCAN_KERNEL])


def test_block_sparse_kernels_compile_at_the_cells_geometry(one_chip):
    """The MiniCPM-SALA cell's two kernels: 608 packed rows of 32 query
    heads over 2 K/V heads of 128; 96 slots of 4480 flat compressed keys
    (552 pages, padded to whole lanes); 4096 pages of 128 tokens, 64
    selected blocks of 64 tokens a row and group, the selection in SMEM
    a tile of 8 rows."""
    from paddle_tpu.ops.pallas import block_sparse_attention as bsa

    T, H, d, kvh, slots, pages, W = 608, 32, 128, 2, 96, 4096, 4480
    ids = ((T,), jnp.int32)
    _compile(lambda q, ck, slot, nck: bsa.infllm_block_scores(
                 q, ck, slot, nck, max_units=slots + T // 16),
             one_chip, ((T, H, d), jnp.bfloat16),
             ((slots, W, kvh * d), jnp.bfloat16), ids, ids,
             kernels=[bsa.BLOCK_SCORES_KERNEL])
    pool = ((pages, kvh, 128, d), jnp.bfloat16)
    _compile(lambda q, k, v, sel, lens, slot, table, live:
             bsa.block_sparse_paged_attention(q, k, v, sel, lens, slot, table,
                                              live, block=64),
             one_chip, ((T, H, d), jnp.bfloat16), pool, pool,
             ((T, kvh, 64), jnp.int32), ids, ids,
             ((slots, 552), jnp.int32), ((T,), jnp.bool_),
             kernels=[bsa.BLOCK_SPARSE_KERNEL])


def _aliased(text) -> int:
    """The arguments a compiled program writes in the buffers they came
    in, by its module's header."""
    header = next(ln for ln in text.splitlines() if "HloModule" in ln)
    return len(re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)", header))


def test_minicpm_sala_step_compiles_with_three_pools_and_state_in_place(
        one_chip, monkeypatch):
    """The MiniCPM-SALA unified step (one minicpm4 and one lightning-attn
    layer at the published widths, the whole vocabulary) holds the four
    kernels of the cell, each under its layer's scope, and every pool (K,
    V and compressed-key pages, lightning states) is updated in the
    buffer it came in."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import minicpm_sala
    from paddle_tpu.ops.pallas import decode_attention

    for mod in (minicpm_sala, decode_attention):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    cfg = minicpm_sala.MiniCPMSALAConfig(layers_run=(9, 11))
    params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
              for k, s in cfg.leaf_shapes().items()}
    slots, pages, snaps = 8, 24, 2
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=slots, num_pages=pages, page_size=128,
        max_seq_len=70656, prefill_token_budget=120, enable_prefix_cache=True,
        state_snapshots=snaps)
    fn, args, kwargs, _ = eng.analysis_entry()
    assert args[3].shape == (128, 8)

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    static = {k: kwargs.pop(k) for k in ("self_cfg_id", "pages_per_step")}
    text = fn.lower(*jax.tree.map(described, args), **static,
                    **jax.tree.map(described, kwargs)).compile().as_text()
    from paddle_tpu.profiler.device_trace import scope_of
    scopes = {m.group(1): scope_of(m.group(2)) for m in re.finditer(
        r'%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call[^\n]*op_name="([^"]*)"',
        text)}
    assert scopes == {"mamba2_ssd_scan": "ssd_scan",
                      "ragged_paged_attention": "paged_attn",
                      "infllm_block_scores": "block_select",
                      "block_sparse_paged_attention": "sparse_attn"}, scopes
    assert _aliased(text) == 4          # K, V, compressed keys, one state


def test_one_kind_of_page_lowers_the_step_it_lowered(one_chip, monkeypatch):
    """A Llama config has one kind of page, and its step is the program
    it was before layouts had kinds: one table, five columns a row, the
    ragged kernel under its one name with its five prefetched scalars,
    nothing of the window walk.  (The text itself was diffed against
    the parent commit's once: PERF.md section 6, PR 30.)"""
    _, calls = _ragged_calls(64)            # before the backend is steered
    for c in calls:
        assert c.params["grid_mapping"].num_index_operands == 5
    text, _, _ = _serving_step_text(one_chip, monkeypatch, jnp.bfloat16)
    assert "ragged_paged_attention_window" not in text
    entry = text[text.index("\nENTRY "):]
    tables = re.findall(rf"= s32\[{_CELL_SLOTS},{-(-_CELL_SEQ // _CELL_PAGE)}\]"
                        r"\S* parameter\(", entry)
    rows = re.findall(rf"= s32\[{_CELL_SLOTS + _CELL_BUDGET},(\d+)\]\S* "
                      r"parameter\(", entry)
    assert len(tables) == 1 and rows == ["5"]


def _rung_engine(layout, monkeypatch):
    """A small engine of one layout at the published widths and its
    cell's capacities (slots, prefill budget), with the kernels steered
    to their compiled form, and the kernels its step holds."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import kimi_linear, minicpm_sala, nemotron_h
    from paddle_tpu.models.deepseek_v32 import DeepseekV32Config
    from paddle_tpu.models.mellum2 import Mellum2Config
    from paddle_tpu.ops.pallas import (decode_attention, dense_mla,
                                       grouped_matmul, sparse_mla)

    for mod in (kimi_linear, minicpm_sala, nemotron_h, decode_attention,
                dense_mla, grouped_matmul, sparse_mla):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    kw = dict(num_pages=24, page_size=128, enable_prefix_cache=True)
    if layout == "kv":
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig.debug(vocab=256, hidden=HEADS * 128, layers=1,
                                heads=HEADS, kv_heads=KV_HEADS, inter=256,
                                max_pos=_CELL_SEQ)
        params = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16) for k, v in
                  LlamaForCausalLM(cfg).functional_state().items()}
        return ContinuousBatchingEngine(
            cfg, params, max_slots=_CELL_SLOTS, max_seq_len=_CELL_SEQ,
            prefill_token_budget=_CELL_BUDGET, **kw), (
                "ragged_paged_attention",), (32, 96, 160, 288)
    if layout == "kinds":
        cfg = Mellum2Config(num_hidden_layers=4)
        kernels = ("ragged_paged_attention", "ragged_paged_attention_window",
                   "grouped_matmul_blocks")
        cap = dict(max_slots=_M2_SLOTS, max_seq_len=_M2_SEQ,
                   prefill_token_budget=_M2_BUDGET)
        ladder = (32, 160, 288, 544)
    elif layout == "latent":
        cfg = DeepseekV32Config(num_hidden_layers=2, first_k_dense_replace=1,
                                experts_held=(0, 16), vocab_size=16160)
        kernels = ("lightning_index_scores", "sparse_mla_attention",
                   "grouped_matmul_blocks")
        cap = dict(max_slots=_DS_SLOTS, max_seq_len=_DS_SEQ,
                   prefill_token_budget=512)
        ladder = (528,)                 # the layout states no tile yet
    elif layout == "pools_state":
        # one minicpm4 and one lightning-attn layer of MiniCPM-SALA
        cfg = minicpm_sala.MiniCPMSALAConfig(layers_run=(9, 11),
                                             vocab_size=16384)
        kernels = ("infllm_block_scores", "block_sparse_paged_attention",
                   "mamba2_ssd_scan", "ragged_paged_attention")
        cap = dict(max_slots=96, max_seq_len=70656, prefill_token_budget=512,
                   state_snapshots=2)
        ladder = (128, 256, 384, 608)   # the tile is the scan's 128 rows
    elif layout == "latent_state":
        # a KDA layer with the dense FFN, then a MLA layer with experts
        cfg = kimi_linear.KimiLinearConfig(
            num_hidden_layers=2, kda_layers=(1,), full_attn_layers=(2,),
            experts_held=(0, 32), vocab_size=20480)
        kernels = ("mamba2_causal_conv", "kda_delta_scan",
                   "dense_mla_attention", "grouped_matmul_blocks")
        cap = dict(max_slots=128, max_seq_len=35840, prefill_token_budget=512,
                   state_snapshots=2)
        ladder = (128, 256, 384, 640)   # the tile is the scan's 128 rows
    else:
        cfg = nemotron_h.NemotronHConfig(
            num_hidden_layers=3, hybrid_override_pattern="ME*",
            experts_held=(0, 64), vocab_size=16384)
        kernels = ("mamba2_causal_conv", "mamba2_ssd_scan",
                   "ragged_paged_attention", "grouped_matmul_blocks")
        cap = dict(max_slots=128, max_seq_len=7680, prefill_token_budget=512,
                   state_snapshots=2)
        ladder = (256, 512, 640)        # the tile is two scan chunks
    params = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
              for k, s in cfg.leaf_shapes().items()}
    return ContinuousBatchingEngine(cfg, params, **cap, **kw), kernels, ladder


@pytest.mark.parametrize("layout", ["kv", "kinds", "latent", "state",
                                    "pools_state", "latent_state"])
def test_the_lowest_rung_of_each_layout_compiles(one_chip, monkeypatch,
                                                 layout):
    """The engine launches a step of decode rows alone at the LOWEST
    rung of its ladder (``paged_layout.step_ladder``): 32 rows in the Llama
    family's two cells, where the capacity is 288 and 544, 128, one
    tile of the scan, in MiniCPM-SALA's, where it is 608, and 256, two
    tiles (``nemotron_h.STEP_TILES``), in Nemotron-H's, where it is
    640, and 128, one tile of the KDA scan, in Kimi-Linear's, where it
    is 640 too.  Each layout's step compiles at that size with every
    kernel of its cell in it: the ragged kernel (one tile, or two of
    Mellum2's 16 rows) and the experts' grouped matmul; MiniCPM-SALA's
    two block-sparse kernels, its scan and the dense walk, with the
    three pools a page and the state aliased in place; Nemotron-H's
    convolution, scan, walk and grouped matmul, its two state pools a
    layer aliased in place; the one layout that states no tile yet
    (DeepSeek's sparse-MLA kernels) has the one rung, its capacity, and
    compiles there.  (Every rung the rule gives the cells, 16 to 640
    rows, compiled for the described chip by hand: PERF.md section 6,
    PRs 36 and 43.)"""
    eng, kernels, ladder = _rung_engine(layout, monkeypatch)
    assert eng.ladder == ladder and ladder[-1] == eng.rows_cap
    # (the capacity need not be whole tiles: a step's kernels pad their
    # rows themselves, as MiniCPM-SALA's 608 are to the scan's 640)
    assert all(n % max(eng.layout.tile_rows, 1) == 0 for n in ladder[:-1])
    fn, args, kwargs, _ = eng.analysis_entry()
    rows = eng._padding_rows(ladder[0])
    args = (*args[:3], rows, *args[4:])

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    static = {k: kwargs.pop(k) for k in ("self_cfg_id", "pages_per_step")}
    text = fn.lower(*jax.tree.map(described, args), **static,
                    **jax.tree.map(described, kwargs)).compile().as_text()
    names = {m.group(1) for m in re.finditer(
        r"%(\w+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text)}
    assert set(kernels) <= names, names
    entry = text[text.index("\nENTRY "):]
    assert re.search(rf"= s32\[{ladder[0]},{eng.row_cols}\]\S* parameter\(",
                     entry)
    assert len(ladder) == 1 or f"[{eng.rows_cap}," not in entry
    # every pool (MiniCPM-SALA's: K, V and compressed keys of the one
    # minicpm4 layer, the one lightning layer's state) is written in the
    # buffer it came in
    assert _aliased(text) == len(jax.tree.leaves(
        (eng.k_pages, eng.v_pages, eng._more_arguments())))
    # the chip's compiler leaves no working instruction of the step
    # outside the program's scopes (tests/test_device_scopes.py holds the
    # same at debug widths): the device's time by scope then names all
    # of a launch but the compiler's own copies
    from test_device_scopes import hlo_scopes

    found = hlo_scopes(text)
    assert not found.get("unscoped"), found["unscoped"]
    assert {"embed", "lm_head", "sample"} < set(found)


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
