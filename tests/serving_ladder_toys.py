"""Toy engines of the five layouts: those whose derived ladders have
several rungs (the kernels' row tile is 8 or 16 at these widths; four
rungs for the Llama-shaped engine, two for Mellum2's, whose step
compiles slowest here, three for Nemotron-H's, whose tile is two of
its ``chunk_size`` of 8, and for MiniCPM-SALA's with its scan's tile
cut to 16 rows) and the ONE that DeepSeek's layout keeps while it
states no tile, the mixed trace they serve, the check that a ladder
serves what the top rung serves and the check that a re-ask behind its
document's prefill is served what its layout can restore: shared by
``test_serving_ladder.py`` and ``test_serving_ladder_kinds_state.py``
(two files, so that two workers share the compiles)."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import ContinuousBatchingEngine

# ---- the five layouts at toy widths ------------------------------------

def _draw(cfg, seed, scale=0.08):
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in cfg.leaf_shapes().items():
        if name.endswith(("norm.weight", "layernorm.weight")):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(".A_log"):
            out[name] = jnp.asarray(np.log(rng.uniform(1, 4, shape)),
                                    jnp.float32)
        elif name.endswith((".dt_bias", ".D", ".bias")):
            out[name] = jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)
        else:
            out[name] = jnp.asarray(rng.normal(0, scale, shape), jnp.float32)
    return out


def _llama():
    """A Llama-shaped engine with a draft model: verify windows of 3."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import self_draft_params

    state = paddle.get_rng_state()
    paddle.seed(36)
    cfg = LlamaConfig.debug(vocab=64, hidden=64, layers=2, heads=8,
                            kv_heads=1, inter=64, max_pos=256)
    model = LlamaForCausalLM(cfg)
    params = {k: jnp.asarray(v) for k, v in model.functional_state().items()}
    paddle.set_rng_state(state)
    dcfg, dparams = self_draft_params(cfg, params, 1)
    return lambda: ContinuousBatchingEngine(
        cfg, params, max_slots=2, num_pages=40, page_size=16,
        max_seq_len=256, prefill_token_budget=64, enable_prefix_cache=True,
        draft_cfg=dcfg, draft_params=dparams, speculative_k=2)


def _small():
    """One Llama layer, no draft model: the cheapest engine whose ladder
    has more rungs than one."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    state = paddle.get_rng_state()
    paddle.seed(37)
    cfg = LlamaConfig.debug(vocab=64, hidden=64, layers=1, heads=8,
                            kv_heads=1, inter=64, max_pos=128)
    model = LlamaForCausalLM(cfg)
    params = {k: jnp.asarray(v) for k, v in model.functional_state().items()}
    paddle.set_rng_state(state)
    return lambda: ContinuousBatchingEngine(
        cfg, params, max_slots=3, num_pages=40, page_size=16,
        max_seq_len=128, prefill_token_budget=32, enable_prefix_cache=True)


def _mellum2():
    from paddle_tpu.models.mellum2 import Mellum2Config

    cfg = Mellum2Config.debug(
        num_attention_heads=16, num_key_value_heads=1, num_hidden_layers=2,
        layer_types=("sliding_attention", "full_attention"), num_experts=4)
    params = _draw(cfg, 1)
    return lambda: ContinuousBatchingEngine(
        cfg, params, max_slots=3, num_pages={"full": 60, "window": 24},
        page_size=4, max_seq_len=128, prefill_token_budget=8,
        enable_prefix_cache=True)


def _deepseek():
    from paddle_tpu.models.deepseek_v32 import DeepseekV32Config

    cfg = DeepseekV32Config.debug(experts_held=(4, 8))
    params = _draw(cfg, 2)
    return lambda: ContinuousBatchingEngine(
        cfg, params, max_slots=3, num_pages=60, page_size=8,
        max_seq_len=128, prefill_token_budget=32, enable_prefix_cache=True)


def _nemotron():
    """One layer of each letter.  Its layout derives the tile from the
    kernels' (``paged_layout``): the scan's and the convolution's
    ``chunk_size`` 8 and the ragged walk's 8 (16 query heads a group),
    two of them (``nemotron_h.STEP_TILES``): 16 rows; a budget of 32
    gives the rungs 16 / 32 / 35."""
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    cfg = NemotronHConfig.debug(experts_held=(4, 12), num_attention_heads=16,
                                num_key_value_heads=1, num_hidden_layers=3,
                                hybrid_override_pattern="ME*")
    params = _draw(cfg, 3)
    return lambda: ContinuousBatchingEngine(
        cfg, params, max_slots=3, num_pages=80, page_size=4,
        max_seq_len=128, prefill_token_budget=32, enable_prefix_cache=True,
        state_snapshots=4)


#: the rows of the scan's tile in the MiniCPM-SALA toy (the published
#: 128 would pass the toy's capacity and leave one rung)
SALA_SCAN_TILE = 16


def _minicpm_sala():
    """One ``minicpm4`` and one ``lightning-attn`` layer: three pools a
    page, a state layer, snapshots; rows select once their context
    passes 32 tokens.  Its layout derives the tile from the kernels'
    (``paged_layout``): 16 rows with the dense walk's 16 (8 query heads
    a group) once the caller has cut the scan's tile to
    ``SALA_SCAN_TILE`` (``models.minicpm_sala.SCAN_TILE_ROWS``; on the
    CPU the scan's reference runs, which has no tile)."""
    from paddle_tpu.models.minicpm_sala import MiniCPMSALAConfig

    cfg = MiniCPMSALAConfig.debug(num_attention_heads=16, layers_run=(0, 2))
    params = _draw(cfg, 4, scale=0.2)
    return lambda: ContinuousBatchingEngine(
        cfg, params, max_slots=3, num_pages=80, page_size=8,
        max_seq_len=128, prefill_token_budget=32, enable_prefix_cache=True,
        state_snapshots=4)


LAYOUTS = {"small": (_small, (16, 32, 35)),
           "llama": (_llama, (16, 32, 48, 70)),
           "mellum2": (_mellum2, (8, 11)),
           "deepseek": (_deepseek, (35,)),
           "nemotron": (_nemotron, (16, 32, 35)),
           "minicpm_sala": (_minicpm_sala, (16, 32, 35))}
# a mixed trace: prompts that fill a whole chunk and more, short ones
# that ride beside decode rows, two that share a prefix
PROMPTS = (70, 5, 33, 12, 41)
NEW_TOKENS = 6


def _serve(eng, vocab, compiles=None):
    """Serve the trace; returns the tokens by request, a launch the rows
    that were packed (without the padding) and the rung, and a call of
    ``step()`` what the newest committed launch's step added to its
    gathered rows (``last_extras``: MiniCPM-SALA's selections)."""
    rng = np.random.default_rng(7)
    shared = rng.integers(1, vocab, 24)
    prompts = [np.concatenate([shared, rng.integers(1, vocab, n - 24)])
               if n > 30 else rng.integers(1, vocab, n) for n in PROMPTS]
    packed = []
    pack = eng._pack_unified

    def spy(*a, **k):
        rows, gather, launch = pack(*a, **k)
        r = launch.counts["rows"]
        if r:
            packed.append((rows[:r].copy(), gather.copy(), len(rows),
                           launch.counts["rows_cap"]))
        return rows, gather, launch

    eng._pack_unified = spy
    extras = []

    def step():
        eng.step()
        extras.append(eng.last_extras)

    for p in prompts[:3]:
        eng.add_request(p.astype(np.int32), max_new_tokens=NEW_TOKENS)
    step()                              # the engine's first launch
    after_first = None if compiles is None else compiles[0]
    for _ in range(3):
        step()
    for p in prompts[3:]:               # arrive while the others decode
        eng.add_request(p.astype(np.int32), max_new_tokens=NEW_TOKENS)
    while eng.queue or eng.active.any():
        step()
    tokens = {f.rid: f.tokens.tolist() for f in eng.run()}
    if compiles is not None:
        # no program is compiled after the engine's first launch
        assert compiles[0] == after_first
    return tokens, packed, extras


@pytest.fixture(scope="module")
def compiles():
    """JAX's count of backend compilations, as the benchmark's
    ``CompileClock`` takes it (benchmarks/harness/clocks.py)."""
    import jax.monitoring

    count = [0]

    def on(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    return count


def one_rung(eng):
    """``eng`` with its capacity as its only rung (before its first
    launch): what a ladder's engine is compared with."""
    eng.ladder = (eng.rows_cap,)
    eng.launches_by_rows = {eng.rows_cap: 0}
    return eng


def check_a_ladder_serves_what_the_top_rung_serves(name, compiles):
    build, want = LAYOUTS[name]
    make = build()
    eng = make()
    assert eng.ladder == want and eng.ladder[-1] == eng.rows_cap
    vocab = eng.cfg.vocab_size
    tokens, packed, extras = _serve(eng, vocab, compiles)
    steps = eng.serving_stats()["steps"]
    eng.shutdown()

    top = one_rung(make())              # the same engine, one rung
    top_tokens, top_packed, top_extras = _serve(top, vocab)
    top.shutdown()

    assert tokens == top_tokens
    assert all(len(t) == NEW_TOKENS for t in tokens.values())
    # the same launches carry the same rows in the same order, and the
    # same gather: only the padding differs
    assert len(packed) == len(top_packed)
    for (rows, gather, n, cap), (t_rows, t_gather, t_n, t_cap) in zip(
            packed, top_packed):
        np.testing.assert_array_equal(rows, t_rows)
        np.testing.assert_array_equal(gather, t_gather)
        assert n == cap == min(m for m in want if m >= len(rows))
        assert t_n == t_cap == want[-1]
    # and what a step adds to its gathered rows is the same, call by call
    for got, t_got in zip(extras, top_extras, strict=True):
        for a, b in zip(got, t_got, strict=True):
            np.testing.assert_array_equal(a, b)
    # the trace reaches more rungs than one: decode-only steps, small
    # chunks and full ones
    by_rows = steps["launches_by_rows"]
    assert sum(by_rows.values()) == len(packed)
    # (a layout that states no tile has the one rung: DeepSeek's serves
    # as ever, and the check is that it does)
    used = [n for n in want if by_rows[n]]
    assert want[0] in used and len(used) >= min(3, len(want) - 1)
    assert want[-1] in used or len(want) < 4
    assert steps["rows"] <= steps["rows_cap"] <= steps["steps"] * eng.rows_cap
    assert steps["rows_cap"] < steps["steps"] * eng.rows_cap \
        or len(want) == 1
    assert steps["rows_cap"] >= sum(n * c for n, c in by_rows.items())
    return steps, extras


# ---- a re-ask behind its document's prefill ------------------------------

def _serve_re_asks(eng, vocab):
    """Document ``a`` of three chunks and three tokens; ``b``, the same,
    added while ``a`` prefills; ``c``, the same, once both are done; then
    ``d`` (three whole chunks), ``x`` (another document, a chunk and a
    quarter) and ``e``, the same as ``d``, all three in one call: ``e``
    waits behind ``x``, whose first chunk fills the launch after ``d``'s
    last, and finds ``d`` done when its own first chunk is packed beside
    ``x``'s second.  Returns the tokens, the cache's counters after
    ``b``, after ``c`` and at the end, each request's ``prefill_stats``
    and the two documents' lengths."""
    rng = np.random.default_rng(11)
    budget = eng.prefill_budget
    doc = rng.integers(1, vocab, 3 * budget + 3)
    doc2 = rng.integers(1, vocab, 3 * budget)
    other = rng.integers(1, vocab, budget + budget // 4)
    rids, seen = {}, {}

    def add(name, prompt):
        rids[name] = eng.add_request(prompt.astype(np.int32),
                                     max_new_tokens=4)

    def drain(name):
        while eng.queue or eng.active.any():
            eng.step()
            eng.assert_balanced()
        if eng.prefix_cache is not None:
            seen[name] = eng.serving_stats()["prefix_cache"]

    add("a", doc)
    eng.step()
    add("b", doc)
    drain("b")
    add("c", doc)
    drain("c")
    add("d", doc2), add("x", other), add("e", doc2)
    drain("e")
    tokens = {f.rid: f.tokens.tolist() for f in eng.run()}
    return ({n: tokens[r] for n, r in rids.items()}, seen,
            {n: eng.prefill_stats[r] for n, r in rids.items()},
            {"doc": len(doc), "doc2": len(doc2)})


def check_a_re_ask_late_hits_what_its_layout_can_restore(name):
    """The prefix cache takes a prompt's first-kind pages chunk by
    chunk, and a slot that waited is matched again when its first chunk
    is packed (``serving._late_hit``).  Every layout serves the tokens
    it serves with the prefix cache off.  One kind of page alone
    (DeepSeek's): ``b`` late-hits what ``a`` has committed.  A window
    kind or a recurrent state: a block committed before its prompt was
    done is not restorable, so ``b`` finds nothing while ``a`` prefills
    (as before) and ``c`` hits whole at its admission; ``e``, whose
    document was DONE by the time its first chunk was packed, late-hits
    in every layout, window pages, snapshot and all."""
    make = LAYOUTS[name][0]()
    eng = make()
    vocab, budget, page = (eng.cfg.vocab_size, eng.prefill_budget,
                           eng.page_size)
    tokens, seen, stats, lens = _serve_re_asks(eng, vocab)
    eng.assert_balanced()
    eng.shutdown()

    cold = make()
    cold.prefix_cache = None            # the same engine, no cache
    cold_tokens, _, cold_stats, _ = _serve_re_asks(cold, vocab)
    cold.shutdown()
    assert tokens == cold_tokens
    assert all(s["cached_tokens"] == 0 for s in cold_stats.values())

    whole = lens["doc"] - budget - page
    one_kind = len(eng.pages) == 1 and not eng.layout.state
    assert seen["b"]["late_hits"] == (1 if one_kind else 0)
    assert (stats["b"]["cached_tokens"] >= whole) if one_kind \
        else stats["b"]["cached_tokens"] == 0
    # done, the document is a whole hit at admission for every layout
    assert seen["c"]["late_hits"] == seen["b"]["late_hits"]
    assert stats["c"]["cached_tokens"] >= whole
    # and a late hit for every layout: the window kind's pages and the
    # snapshot came with the prompt's last insert
    assert seen["e"]["late_hits"] == seen["c"]["late_hits"] + 1
    assert stats["d"]["cached_tokens"] == stats["x"]["cached_tokens"] == 0
    cached = stats["e"]["cached_tokens"]
    if eng.layout.state:
        # as deep as the deepest snapshot ``d`` left on the chunks' grid
        # that ``x``'s own did not displace (four entries in all)
        assert cached == stats["e"]["state_restored_tokens"] > 0
        assert cached % budget == 0
    else:
        assert cached >= lens["doc2"] - budget - page
    for s in stats.values():
        assert s["prefilled"] == s["prompt_len"] - s["cached_tokens"]
