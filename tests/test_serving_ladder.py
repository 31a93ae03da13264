"""The engine's ladder of step sizes: a launch takes the smallest
compiled row count that holds what was packed (``paged_layout.step_ladder``),
and nothing else about it changes.

Toy widths whose kernels' row tile is 8 or 16, so that the ladders the
engines derive have four rungs at capacities of a few dozen rows.
"""

import functools
import math

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.inference.paged_layout import step_ladder
from serving_ladder_toys import (  # noqa: F401 - compiles is a fixture
    LAYOUTS, _serve, check_a_ladder_serves_what_the_top_rung_serves,
    check_a_re_ask_late_hits_what_its_layout_can_restore, compiles)

# (max_slots, speculative_k, prefill_token_budget, the kernels' row
# tile) of the benchmark's five serving configurations
# (benchmarks/configs/*.json; the tile from the layout: ragged_tile_rows,
# the sparse-MLA tile of 8, the scan's chunk or tile of 128, two of
# them for Nemotron-H: nemotron_h.STEP_TILES) and of an engine with a
# draft model
CAPACITIES = {
    "mistral": ((32, 0, 256, 32), (32, 96, 160, 288)),
    "mellum2": ((32, 0, 512, 16), (32, 160, 288, 544)),
    "nemotron": ((128, 0, 512, 256), (256, 512, 640)),
    "deepseek": ((16, 0, 512, 8), (16, 144, 272, 528)),
    "minicpm_sala": ((96, 0, 512, 128), (128, 256, 384, 608)),
    "spec_k_2": ((8, 2, 100, 16), (32, 64, 80, 124)),
    "tile_past_capacity": ((3, 0, 8, 64), (11,)),
}


@pytest.mark.parametrize("cell", CAPACITIES)
def test_the_ladders_rule(cell):
    (slots, spec_k, budget, tile), want = CAPACITIES[cell]
    decode = slots * (1 + spec_k)
    ladder = step_ladder(decode, budget, tile)
    assert ladder == want
    assert ladder == tuple(sorted(set(ladder)))
    assert ladder[-1] == decode + budget            # the top rung: rows_cap
    assert ladder[0] >= decode                      # holds every decode row
    assert all(n % tile == 0 for n in ladder[:-1])
    assert all(n <= ladder[-1] for n in ladder)


def test_the_cells_layouts_state_the_tiles_of_the_rule():
    """The tiles the table above takes for the Llama family's two cells,
    for MiniCPM-SALA's and for Nemotron-H's are what their layouts state
    at the published widths."""
    from paddle_tpu.models import LlamaConfig, minicpm_sala, nemotron_h
    from paddle_tpu.models.deepseek_v32 import DeepseekV32Config
    from paddle_tpu.models.llama_paged import kv_layout
    from paddle_tpu.models.mellum2 import Mellum2Config
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    from paddle_tpu.ops.pallas import block_sparse_attention as bsa
    from paddle_tpu.ops.pallas.decode_attention import ragged_tile_rows

    mistral = LlamaConfig(vocab_size=32768, hidden_size=4096,
                          intermediate_size=14336, num_hidden_layers=1,
                          num_attention_heads=32, num_key_value_heads=8,
                          max_position_embeddings=4096)
    assert kv_layout(mistral).tile_rows == 32
    assert kv_layout(Mellum2Config()).tile_rows == 16
    # MiniCPM-SALA's: whole tiles of its four kernels, which is the
    # scan's, the largest (the scores' 16, the block-sparse kernel's 8
    # and the dense walk's 8 divide it)
    sala = minicpm_sala.MiniCPMSALAConfig().paged_layout().tile_rows
    assert sala == minicpm_sala.SCAN_TILE_ROWS == 128
    assert sala % bsa.SCORES_TILE_ROWS == sala % bsa.SPARSE_TILE_ROWS == 0
    assert sala == CAPACITIES["minicpm_sala"][0][3]
    # Nemotron-H's: whole tiles of the ragged walk (8 rows) and of the
    # scan and the convolution, whose tile is the config's chunk_size;
    # STEP_TILES of them, since the step compiled at one does not end on
    # the chip (PERF.md section 6, PR 43)
    nemo = NemotronHConfig()
    tile = nemo.paged_layout().tile_rows
    assert tile == nemotron_h.STEP_TILES * math.lcm(
        ragged_tile_rows(nemo.num_attention_heads, nemo.num_key_value_heads,
                         nemo.head_dim), nemo.chunk_size) == 256
    assert tile == CAPACITIES["nemotron"][0][3]
    assert step_ladder(128, 512, tile) == CAPACITIES["nemotron"][1]
    # and the toy's by the same line: its chunk_size, 8
    toy = NemotronHConfig.debug()
    assert toy.paged_layout().tile_rows == nemotron_h.STEP_TILES * math.lcm(
        ragged_tile_rows(toy.num_attention_heads, toy.num_key_value_heads,
                         toy.head_dim), toy.chunk_size)
    # the one layout that brings a step of its own and states no tile
    # yet (PERF.md section 6, PR 36): its engine keeps ONE rung, the
    # capacity; the rule above says what its ladder would be
    assert DeepseekV32Config().paged_layout().tile_rows == 0
    assert step_ladder(16, 512, 0) == (16 + 512,)


# ---- toy engines whose ladders have four rungs (serving_ladder_toys) ----

@pytest.mark.parametrize("name", ["llama", "deepseek"])
def test_a_ladder_serves_what_the_top_rung_serves(name, compiles):
    check_a_ladder_serves_what_the_top_rung_serves(name, compiles)


def test_a_re_ask_late_hits_what_its_layout_can_restore():
    """One kind of page and no state (DeepSeek's toy): a re-ask that
    waits behind its document's prefill is served from the pages that
    prefill has committed."""
    check_a_re_ask_late_hits_what_its_layout_can_restore("deepseek")


def test_step_counts_name_the_rung_launched(compiles):
    """``serving.step_counts`` carries the launched program's rows as
    ``rows_cap``: a rung that holds the step's rows, the lowest for a
    call with nothing to launch; ``engine.rows_cap`` stays the
    capacity."""
    from paddle_tpu import profiler

    build, want = LAYOUTS["small"]
    eng = build()()
    with profiler.Profiler(timer_only=True):
        _serve(eng, eng.cfg.vocab_size)
        eng.step()                      # nothing left to do
        counts = [e["args"] for e in profiler._host_events
                  if e["name"] == "serving.step_counts"]
    profiler._host_events.clear()       # leave nothing for a later test
    eng.shutdown()
    assert eng.rows_cap == want[-1]
    assert len({c["rows_cap"] for c in counts}) >= 2
    for c in counts:
        assert c["rows"] <= c["rows_cap"] <= eng.rows_cap
        assert c["rows_cap"] == min(n for n in want if n >= c["rows"])
    assert counts[-1]["rows"] == 0 and counts[-1]["rows_cap"] == want[0]
    steps = eng.serving_stats()["steps"]
    assert steps["rows_cap"] == sum(c["rows_cap"] for c in counts)
    assert sum(steps["launches_by_rows"].values()) \
        == sum(1 for c in counts if c["rows"])


def test_kept_lowerings_serve_the_same_and_are_found_again(
        compiles, tmp_path, monkeypatch):
    """Where a persistent compile cache is configured the rungs'
    lowerings are kept beside it (``compile_cache.kept_lowering``): the
    engine serves the same tokens through them, compiles nothing after
    its first launch, and an engine built again finds every rung's file
    and writes none."""
    from paddle_tpu.utils import compile_cache

    build, want = LAYOUTS["small"]
    make = build()
    plain = make()
    vocab = plain.cfg.vocab_size
    want_tokens, *_ = _serve(plain, vocab)
    plain.shutdown()

    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        eng = make()
        tokens, *_ = _serve(eng, vocab, compiles)
        eng.shutdown()
        kept = sorted(p.name for p in tmp_path.glob("paddle_tpu-lowering-*"))
        assert len(kept) == len(want)

        traced = []
        real = jax.export.export
        monkeypatch.setattr(jax.export, "export", lambda *a, **k: (
            traced.append(a), real(*a, **k))[1])
        again = make()
        again_tokens, *_ = _serve(again, vocab, compiles)
        again.shutdown()
        assert not traced               # every rung was read back
        assert kept == sorted(
            p.name for p in tmp_path.glob("paddle_tpu-lowering-*"))
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert tokens == again_tokens == want_tokens
    assert compile_cache._source_fingerprint()      # hashed once a process


def test_a_changed_config_keeps_its_own_lowering(tmp_path):
    """The key of a kept lowering holds what it was traced from: another
    model configuration at the same shapes does not find it."""
    from paddle_tpu.utils.compile_cache import kept_lowering

    @functools.partial(jax.jit, static_argnames="k")
    def fn(x, *, k):
        return x * k

    x = jnp.ones((4,), jnp.float32)
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert kept_lowering(fn, (x,), {}, {"k": 2}).func is fn
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        two = kept_lowering(fn, (x,), {}, {"k": 2}, what="a")
        three = kept_lowering(fn, (x,), {}, {"k": 3}, what="a")
        other = kept_lowering(fn, (x,), {}, {"k": 2}, what="b")
        assert len(list(tmp_path.glob("paddle_tpu-lowering-*"))) == 3
        kept_lowering(fn, (x,), {}, {"k": 2}, what="a")
        assert len(list(tmp_path.glob("paddle_tpu-lowering-*"))) == 3
        assert [float(f(x)[0]) for f in (two, three, other)] == [2, 3, 2]
        # a file that cannot be read back is made again
        for path in tmp_path.glob("paddle_tpu-lowering-*"):
            path.write_bytes(b"not a lowering")
        for f, k in ((2, "a"), (3, "a"), (2, "b")):
            assert float(kept_lowering(fn, (x,), {}, {"k": f}, what=k)(x)[0]) \
                == f
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
