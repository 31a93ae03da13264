"""DeepSeek-V3.2 on the serving path, at a small size on the CPU in
float32: the engine's unified step (latent paged cache, lightning
indexer, selection inside the ragged step, one chip's share of the
experts) against the plain reference ``benchmarks/reference/
deepseek_v32_ref.py``, which shares no code with the program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_v32_ref as ref
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import generation
from paddle_tpu.models.deepseek_v32 import DEVICE_COUNTS, DeepseekV32Config
from paddle_tpu.ops.pallas import sparse_mla

PAGE, BUDGET, SLOTS, SEQ = 8, 6, 3, 64
HELD = (4, 8)                    # rank 1 of 4: experts 4..7 of 16
VOCAB = 96


def draw(cfg, seed=0, scale=0.3):
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in cfg.leaf_shapes().items():
        if name.endswith("router.bias"):
            out[name] = rng.normal(size=shape) * 0.05
        elif name.endswith("k_norm.bias"):
            out[name] = rng.normal(size=shape) * 0.1
        elif len(shape) == 1:
            out[name] = 1.0 + 0.1 * rng.normal(size=shape)
        else:
            out[name] = rng.normal(size=shape) * scale
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}


def ref_cfg(cfg, held):
    """The configuration as the benchmark's file states it: the experts
    HELD under ``n_routed_experts``, the rank beside them."""
    d = dataclasses.asdict(cfg)
    d["rope_scaling"] = dict(cfg.rope_scaling)
    d["n_routed_experts"] = held[1] - held[0]
    d["deployment_rank"] = held[0] // (held[1] - held[0])
    return d


@pytest.fixture(scope="module")
def model():
    cfg = DeepseekV32Config.debug(experts_held=HELD)
    return cfg, draw(cfg)


def engine(model, **kw):
    cfg, params = model
    opts = dict(max_slots=SLOTS, num_pages=40, page_size=PAGE,
                max_seq_len=SEQ, prefill_token_budget=BUDGET,
                enable_prefix_cache=True)
    opts.update(kw)
    return ContinuousBatchingEngine(cfg, params, **opts)


def serve(eng, prompts, max_new=5):
    """Run to the end; ``({rid: tokens}, {(rid, position): logits})``."""
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    logits = {}
    while eng.queue or eng.active.any():
        eng.step()
        for key, row in zip(*eng.last_logits):
            logits[key] = row
    done = {f.rid: f.tokens for f in eng.finished}
    return rids, {r: done[r] for r in rids}, logits


@pytest.fixture(scope="module")
def served(model):
    """One engine's life: a prompt shorter than top-k (5 < 8) and one
    longer (21: four chunks of the 6-token budget, three pages of 8)
    served together, then the long one asked again."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32) for n in (5, 21)]
    eng = engine(model)
    rids, tokens, logits = serve(eng, prompts)
    first_steps = dict(eng.serving_stats()["steps"])
    again, tokens2, logits2 = serve(eng, [prompts[1]])
    stats = eng.serving_stats()
    eng.shutdown()
    return dict(prompts=prompts, rids=rids, tokens=tokens, logits=logits,
                again=again[0], tokens2=tokens2, logits2=logits2,
                stats=stats, first_steps=first_steps)


def reference_logits(model, prompt, tokens):
    cfg, params = model
    ids = jnp.asarray(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))
    return np.asarray(ref.forward(params, ids, ref_cfg(cfg, HELD))[0])


# float32 on both sides and the same mathematics, but not the same order
# of operations: the program absorbs W_uk into the query and scores in
# pages with an online softmax; over 3 layers at logits of order 5 the
# two differ by some 1e-5.  1e-4 is ten times that and a hundredth of
# what a wrong selection or a wrong gate moves (tests below).
TOL = 1e-4


@pytest.mark.parametrize("which", [0, 1], ids=["shorter_than_topk",
                                               "longer_than_topk"])
def test_engine_logits_match_the_reference(model, served, which):
    rid, prompt = served["rids"][which], served["prompts"][which]
    want = reference_logits(model, prompt, served["tokens"][rid])
    got = {pos: row for (r, pos), row in served["logits"].items() if r == rid}
    # every chunk's last row and every decode row came back
    assert len(prompt) - 1 in got and len(got) >= 5
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0)
    assert np.array_equal(served["tokens"][rid],
                          want[len(prompt) - 1:].argmax(-1))


def test_reference_padded_to_a_shared_shape_reads_the_same(model, served):
    """The benchmark pads a sampled request to a shared length (one
    compilation for many lengths): causal, so nothing served moves."""
    cfg, params = model
    rid, prompt = served["rids"][1], served["prompts"][1]
    d = ref_cfg(cfg, HELD)
    plain = ref.served_token_gaps(params, prompt, served["tokens"][rid], d)
    padded = ref.served_token_gaps(params, prompt, served["tokens"][rid], d,
                                   pad_to=40)
    np.testing.assert_allclose(padded["gap"], plain["gap"], atol=1e-5)
    assert np.array_equal(padded["reference_tokens"],
                          plain["reference_tokens"])
    assert np.array_equal(plain["reference_tokens"], served["tokens"][rid])


def test_second_ask_is_served_from_the_prefix_cache(model, served):
    rid = served["again"]
    info = served["stats"]["prefill"][rid]
    assert info["cached_tokens"] == 16          # two whole pages of 8
    assert info["prefilled"] == 21 - 16
    first = served["rids"][1]
    assert np.array_equal(served["tokens2"][rid], served["tokens"][first])
    for (r, pos), row in served["logits2"].items():
        np.testing.assert_allclose(row, served["logits"][(first, pos)],
                                   atol=TOL, rtol=0)


def test_selected_sets_equal_the_reference(model):
    """One launch of the step over a 21-token prompt (top-k 8): every
    layer's selection, row by row, against ``lax.top_k`` over the
    reference's scores."""
    cfg, params = model
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, VOCAB, 21).astype(np.int32)
    eng = engine(model, prefill_token_budget=24, max_slots=1)
    fn, args, kwargs, _ = eng.analysis_entry()
    rows = np.zeros((eng.rows_cap, 5), np.int32)
    rows[:, 1], rows[:, 4] = eng.trash_page, -1
    tables = np.full_like(eng.tables, -1)
    tables[0, :3] = [5, 2, 9]
    for p, t in enumerate(prompt):
        rows[p] = (t, tables[0, p // PAGE], p % PAGE, p + 1, 0)
    args = (*args[:3], jnp.asarray(rows), (jnp.asarray(tables),), *args[5:])
    _, _, (_, _, _, masks) = fn(*args, **kwargs, debug_select=True)
    _, want = ref.forward(params, jnp.asarray(prompt), ref_cfg(cfg, HELD))
    assert len(masks) == cfg.num_hidden_layers
    for got, sel in zip(masks, want):
        got = np.asarray(got)[:21, :21]
        assert np.array_equal(got, np.asarray(sel))
        assert (got.sum(1) == np.minimum(np.arange(21) + 1, 8)).all()


def test_the_step_takes_and_returns_the_token_column(model, served):
    """The latent layout's step under the engine's run-ahead: beside the
    logits it returns their first maxima as int32 ``[gather_cap]``, and
    an input token below zero is entry ``-1 - tok`` of the launch
    before's tokens.  A prompt fed as tokens and the same prompt fed as
    references into a shuffled column give the same logits; and the
    fixture's engine, which ran ahead, served what the reference picks
    (``test_engine_logits_match_the_reference``)."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, VOCAB, 5).astype(np.int32)
    outs = []
    for by_reference in (False, True):
        eng = engine(model, max_slots=1)
        fn, args, kwargs, _ = eng.analysis_entry()
        rows = np.zeros((eng.rows_cap, 5), np.int32)
        rows[:, 1], rows[:, 4] = eng.trash_page, -1
        tables = np.full_like(eng.tables, -1)
        tables[0, 0] = 3
        prev = np.zeros(eng.gather_cap, np.int32)
        for p, t in enumerate(prompt):
            tok = t
            if by_reference and p < eng.gather_cap:
                where = eng.gather_cap - 1 - p
                prev[where], tok = t, -1 - where
            rows[p] = (tok, 3, p, p + 1, 0)
        gather = np.zeros(eng.gather_cap, np.int32)
        gather[:2] = (4, 2)
        kwargs = dict(kwargs, gather=jnp.asarray(gather),
                      prev_tokens=jnp.asarray(prev))
        args = (*args[:3], jnp.asarray(rows), (jnp.asarray(tables),), *args[5:])
        _, _, (logits, tokens, counts) = fn(*args, **kwargs)
        assert tokens.dtype == jnp.int32 \
            and tokens.shape == (eng.gather_cap,)
        assert counts.shape == (len(DEVICE_COUNTS),)
        assert np.array_equal(tokens, np.asarray(logits).argmax(-1))
        outs.append(np.asarray(logits)[:2])
    np.testing.assert_array_equal(outs[0], outs[1])
    steps = served["stats"]["steps"]
    assert steps["ahead"] > steps["steps"] // 2 and steps["stale_rows"] == 0


def test_the_shares_add_up_to_the_uncut_layer(model):
    """Expert parallelism's contract: the routed parts of every share of
    the experts, plus the shared expert ONCE, are the whole layer."""
    cfg, params = model
    i = cfg.first_k_dense_replace
    h = jnp.asarray(np.random.default_rng(3).normal(size=(11, cfg.hidden_size)),
                    jnp.float32)
    whole = DeepseekV32Config.debug()
    full = draw(whole)
    routed = 0.0
    for lo in range(0, 16, 4):
        share = dataclasses.replace(whole, experts_held=(lo, lo + 4))
        p = {k: (v[lo:lo + 4] if ".experts." in k else v)
             for k, v in full.items() if "shared_expert" not in k}
        routed = routed + generation._moe_ffn(generation._Weights(share, p), i, h)
    shared = generation._shared_expert(generation._Weights(whole, full), i, h)
    lw = ref.layer_leaves(full, i)
    want = ref.expert_layer(h, lw, ref_cfg(whole, (0, 16)), (0, 16))
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                               atol=2e-5, rtol=0)
    # and a share alone is the reference's share
    lw_share = {k: (v[8:12] if ".experts." in k else v) for k, v in lw.items()}
    part = ref.expert_layer(h, lw_share, ref_cfg(whole, (8, 12)), (8, 12),
                            shared=False)
    share = dataclasses.replace(whole, experts_held=(8, 12))
    p = {k: (v[8:12] if ".experts." in k else v) for k, v in full.items()
         if "shared_expert" not in k}
    got = generation._moe_ffn(generation._Weights(share, p), i, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(part), atol=2e-5,
                               rtol=0)


class TestRouting:
    cfg = DeepseekV32Config.debug()         # 16 experts, 4 groups, 2 of 2

    def route(self, logits, bias=None):
        ids, gates = generation._route_sigmoid_groups(
            self.cfg, jnp.asarray(logits, jnp.float32),
            None if bias is None else jnp.asarray(bias, jnp.float32))
        return np.asarray(ids), np.asarray(gates)

    def test_only_the_best_groups_are_eligible(self):
        # group 0 has the single best expert but a weak second; groups 1
        # and 2 have the best sums of two: the choice comes from them
        logits = np.full((1, 16), -4.0)
        logits[0, 0] = 3.0
        logits[0, [4, 5]] = 2.0
        logits[0, [8, 9]] = 1.5
        ids, _ = self.route(logits)
        assert set(ids[0]) == {4, 5}
        logits[0, 1] = 2.5                  # now group 0's two best win
        ids, _ = self.route(logits)
        assert set(ids[0]) == {0, 1}

    def test_the_bias_selects_and_never_gates(self):
        logits = np.zeros((1, 16))
        logits[0, [0, 1]] = 1.0
        bias = np.zeros(16)
        bias[[12, 13]] = 5.0
        ids, gates = self.route(logits, bias)
        assert set(ids[0]) == {12, 13}
        # the gates are the unbiased scores (both 0.5), normalised, x 2.5
        np.testing.assert_allclose(gates[0], [1.25, 1.25], rtol=1e-6)

    def test_gates_are_normalised_over_all_chosen_and_scaled(self):
        rng = np.random.default_rng(4)
        logits = rng.normal(size=(7, 16))
        ids, gates = self.route(logits)
        s = 1 / (1 + np.exp(-logits))
        picked = np.take_along_axis(s, ids, 1)
        np.testing.assert_allclose(
            gates, 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(gates.sum(1), 2.5, rtol=1e-5)

    def test_it_is_the_references_routing(self):
        rng = np.random.default_rng(5)
        h = jnp.asarray(rng.normal(size=(9, 64)), jnp.float32)
        lw = {"mlp.router.weight": jnp.asarray(rng.normal(size=(64, 16)),
                                               jnp.float32),
              "mlp.router.bias": jnp.asarray(rng.normal(size=16) * 0.3,
                                             jnp.float32)}
        d = ref_cfg(self.cfg, (0, 16))
        want_ids, want_s = ref.route(h, lw, d)
        ids, gates = self.route(np.asarray(h @ lw["mlp.router.weight"]),
                                np.asarray(lw["mlp.router.bias"]))
        assert np.array_equal(np.sort(ids, 1), np.sort(np.asarray(want_ids), 1))


@pytest.mark.parametrize("option, kw", [
    ("draft model", dict(speculative_k=2, draft_params={"x": jnp.zeros(1)})),
    ("int8 cache", dict(cache_dtype=jnp.int8)),
    ("host tier", dict(host_tier_pages=4)),
    ("prefill_only", dict(prefill_only=True)),
])
def test_what_the_latent_layout_cannot_do_raises_at_construction(model, option,
                                                                 kw):
    with pytest.raises(ValueError, match=f"latent pools do not support.*"
                                         f"{option.split()[0]}"):
        engine(model, **kw)


def test_the_engine_reaches_this_model_through_its_layout(model):
    """The same questions as of a Llama config: the program the engine
    launches, and hands the doctor, is the layout's ``step``; pages a
    turn left unset are the layout's rule (2048 keys a turn); and there
    is no engine without a prefill budget."""
    from paddle_tpu.models.deepseek_v32 import unified_step_jit

    cfg, _ = model
    eng = engine(model)
    fn, *_ = eng.analysis_entry()
    assert fn is eng.layout.step is unified_step_jit
    assert eng.pages_per_step == 2048 // PAGE
    assert engine(model, pages_per_step=4).pages_per_step == 4
    for budget in (0, None):
        with pytest.raises(ValueError, match="prefill_token_budget"):
            engine(model, prefill_token_budget=budget)


def test_a_latent_engine_adopts_no_handoff(model):
    eng = engine(model)
    with pytest.raises(ValueError, match="latent pools do not support the KV "
                                         "handoff"):
        eng.adopt_request(None, {"seq_len": 1, "first_token": 0}, 4)


def test_the_pools_are_latent_rows_and_index_keys(model):
    cfg, _ = model
    eng = engine(model)
    assert cfg.latent_row == 128            # 32 + 16, padded to a lane tile
    assert DeepseekV32Config().latent_row == 640
    assert len(eng.k_pages) == len(eng.v_pages) == cfg.num_hidden_layers
    assert eng.k_pages[0].shape == (40, PAGE, 128)
    assert eng.v_pages[0].shape == (40, PAGE, cfg.index_head_dim)


def test_the_counters_against_hand_counts(served):
    """The first life of the fixture's engine: prompts of 5 and 21
    tokens, 5 new tokens each, a budget of 6 prompt tokens a step."""
    st = served["first_steps"]
    # positions scored: every prompt token and every decode input sees
    # its position + 1; a request feeds prompt + 4 of its 5 new tokens
    vis = sum(sum(range(1, n + 4 + 1)) for n in (5, 21))
    assert st["rows"] == (5 + 4) + (21 + 4)
    assert st["index_row_ctx"] == vis
    assert st["sel_row_tokens"] == sum(
        sum(min(v, 8) for v in range(1, n + 4 + 1)) for n in (5, 21))
    # two expert layers, two copies a row; a quarter of the experts held
    assert st["moe_rows_routed"] == st["rows"] * 2 * 2
    assert 0 < st["moe_rows_held"] < st["moe_rows_routed"]
    assert 1 <= st["moe_expert_rows_max"] <= BUDGET + SLOTS
    assert st["latent_ctx_tokens"] >= st["rows"]
    assert set(DEVICE_COUNTS) <= set(st)


def test_the_walk_reads_what_the_packing_counts(model, monkeypatch):
    """Two decode rows and a 12-row chunk in one launch, tiles of 8: the
    chunk is cut at the tile boundary into units of 6 and 6, so its slot
    is fetched twice where a walk a row fetched it 12 times.  The count
    is the kernels' own units' (``ragged_units`` at the layer's tile,
    whole turns of ``walk_geometry``), rides ``serving.step_counts`` and
    is summed in ``serving_stats()["steps"]``.  The engine runs one step
    ahead: the call after the one that PACKS a launch commits it."""
    from paddle_tpu.inference import serving
    from paddle_tpu.ops.pallas.decode_attention import ragged_units

    cfg, _ = model
    assert cfg.walk_tile_rows == 8
    eng = engine(model, prefill_token_budget=12)
    rng = np.random.default_rng(3)
    for n in (5, 9):
        eng.add_request(rng.integers(0, VOCAB, n).astype(np.int32),
                        max_new_tokens=4)
    eng.step()
    eng.step()                      # both prompts prefilled, 5 + 9 rows
    eng.add_request(rng.integers(0, VOCAB, 12).astype(np.int32),
                    max_new_tokens=4)
    packed, marks = {}, []
    pack, event = eng._pack_unified, serving.RecordEvent

    def spy(*a):
        out = pack(*a)
        packed.setdefault("rows", out[0])
        return out

    def record(name, **kw):
        if name == "serving.step_counts":
            marks.append(kw)
        return event(name, **kw)

    eng._pack_unified = spy
    monkeypatch.setattr(serving, "RecordEvent", record)
    eng.step()
    before = dict(eng.serving_stats()["steps"])
    eng.step()
    after = eng.serving_stats()["steps"]
    rows = packed["rows"]
    live = rows[rows[:, 4] >= 0]
    assert len(live) == 14 and (live[:, 4] == live[2, 4])[2:].all()
    count, reach = ragged_units(rows[:, 4], rows[:, 3], 8, np)
    assert count[count > 0].tolist() == [1, 1, 6, 6]
    _, nk, _ = sparse_mla.walk_geometry(PAGE, eng.pages_per_seq,
                                        eng.pages_per_step)
    want = int((-(-reach[count > 0] // nk)).sum()) * nk
    assert want == 4 * 64               # a turn holds the table's 8 pages
    assert after["attn_kv_tokens_read"] - before["attn_kv_tokens_read"] \
        == want == marks[-1]["attn_kv_tokens_read"]
    assert after["latent_ctx_tokens"] - before["latent_ctx_tokens"] \
        == int(reach[count > 0][[0, 1, 3]].sum())
    eng.run()
    eng.shutdown()


def test_yarn_tables_are_the_references(model):
    cfg, _ = model
    cos, sin = cfg.rope_tables()
    rcos, rsin = ref.yarn_tables(ref_cfg(cfg, HELD), 256)
    np.testing.assert_allclose(np.asarray(cos), np.asarray(rcos), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.asarray(rsin), atol=1e-6)
    big = DeepseekV32Config()
    assert abs(big.softmax_scale - 192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2) \
        < 1e-12
    assert abs(big.softmax_scale - ref.softmax_scale(
        {"rope_scaling": dict(big.rope_scaling), "qk_nope_head_dim": 128,
         "qk_rope_head_dim": 64})) < 1e-12


# ---- the kernels alone, against numpy --------------------------------------

def _paged_case(seed=0):
    rng = np.random.default_rng(seed)
    P, page, di, dl = 12, 8, 16, 48
    tables = np.full((3, 5), -1, np.int32)
    tables[0, :3], tables[1, :5], tables[2, :1] = [3, 1, 7], [2, 4, 5, 6, 8], [9]
    return dict(
        kp=rng.normal(size=(P, page, di)).astype(np.float32),
        lp=rng.normal(size=(P, page, dl)).astype(np.float32),
        tables=tables, lens=np.array([20, 1, 37, 38, 0, 5, 24], np.int32),
        slot=np.array([0, 0, 1, 1, -1, 2, 0], np.int32),
        q=rng.normal(size=(7, 2, di)).astype(np.float32),
        w=rng.normal(size=(7, 2)).astype(np.float32),
        qq=(rng.normal(size=(7, 4, dl)) * 0.3).astype(np.float32))


def _chunks_case(seed=1):
    """A step as the engine packs them, 28 rows over pages of 8: a
    10-row chunk of slot 0 at visibilities 15..24 (it crosses two tile
    boundaries at tiles of 4, and the edge of a turn of 8, 16 or 24
    keys inside a unit), a 12-row chunk of slot 1 from position 0
    beside it (two slots' chunks in one tile; its first rows lie whole
    turns under the unit's reach), then a decode row, a padding row and
    another slot's decode row, and three padding rows at the end (tiles
    with no live row, whose blocks are not copied in)."""
    rng = np.random.default_rng(seed)
    P, page, di, dl = 16, 8, 16, 48
    tables = np.full((4, 5), -1, np.int32)
    tables[0, :3], tables[1, :2] = [3, 1, 7], [2, 4]
    tables[2, :5], tables[3, :1] = [5, 6, 8, 10, 11], [9]
    lens = np.concatenate([np.arange(15, 25), np.arange(1, 13),
                           [33, 0, 5, 0, 0, 0]])
    slot = np.concatenate([np.zeros(10), np.ones(12), [2, -1, 3, -1, -1, -1]])
    T = len(lens)
    return dict(
        kp=rng.normal(size=(P, page, di)).astype(np.float32),
        lp=rng.normal(size=(P, page, dl)).astype(np.float32),
        tables=tables, lens=lens.astype(np.int32), slot=slot.astype(np.int32),
        q=rng.normal(size=(T, 2, di)).astype(np.float32),
        w=rng.normal(size=(T, 2)).astype(np.float32),
        qq=(rng.normal(size=(T, 4, dl)) * 0.3).astype(np.float32))


def _context(pool, tables, slot, n):
    return np.concatenate([pool[max(p, 0)] for p in tables[slot]])[:n]


@pytest.mark.parametrize("pp", [1, 2, 3])
@pytest.mark.parametrize("case, tile_rows", [
    (_paged_case, None), (_chunks_case, 1), (_chunks_case, 2),
    (_chunks_case, 4), (_chunks_case, 64)],
    ids=["mixed-derived", "chunks-1", "chunks-2", "chunks-4", "chunks-64"])
def test_index_scores_selection_and_attention_kernels(case, tile_rows, pp):
    """The two kernels against dense numpy, a row at a time, whatever
    the tile: every unit of work walks its slot's pages once and each
    row keeps its own visibility, scores and cut.  Two index heads: a
    score is exactly 0 whenever both products are negative, so the k-th
    largest is often a tie, cut at the lower positions as ``lax.top_k``
    cuts it."""
    c = case()
    T = len(c["lens"])
    j = {k: jnp.asarray(v) for k, v in c.items()}
    sc = sparse_mla.lightning_index_scores_raw(
        j["q"], j["w"], j["kp"], j["lens"], j["slot"], j["tables"],
        pages_per_step=pp, tile_rows=tile_rows)
    lens = np.where(c["slot"] < 0, 0, c["lens"])
    want = np.full(sc.shape, -np.inf, np.float32)
    for r in range(T):
        keys = _context(c["kp"], c["tables"], c["slot"][r], lens[r])
        s = np.maximum(c["q"][r] @ keys.T, 0) * c["w"][r][:, None]
        want[r, :lens[r]] = s.sum(0)
    fin = np.isfinite(want)
    assert np.array_equal(np.isfinite(np.asarray(sc)), fin)
    np.testing.assert_allclose(np.asarray(sc)[fin], want[fin], atol=1e-5)
    k = 6
    sel = sparse_mla.select_top_k(sc, k)
    mask = np.asarray(sparse_mla.selected_mask(sc, sel, jnp.asarray(lens)))
    out = sparse_mla.sparse_mla_attention_raw(
        j["qq"], j["lp"], sc, sel, j["lens"], j["slot"], j["tables"], dv=32,
        pages_per_step=pp, tile_rows=tile_rows)
    ties = 0
    for r in range(T):
        n = lens[r]
        keep = np.zeros(n, bool)
        if n:
            scores = np.asarray(sc)[r, :n]
            keep[np.asarray(jax.lax.top_k(jnp.asarray(scores), min(k, n))[1])] = True
            ties += int((scores == scores[keep].min()).sum() > 1)
        assert np.array_equal(mask[r, :n], keep) and not mask[r, n:].any()
        if not n:
            assert not np.asarray(out[r]).any()
            continue
        lat = _context(c["lp"], c["tables"], c["slot"][r], n)
        s = np.where(keep[None], c["qq"][r] @ lat.T, -np.inf)
        p = np.exp(s - s.max(1, keepdims=True))
        np.testing.assert_allclose(np.asarray(out[r]),
                                   (p / p.sum(1, keepdims=True)) @ lat[:, :32],
                                   atol=1e-5)
    assert ties                              # the case does exercise the cut


def test_the_units_of_a_packed_step_and_what_their_walk_fetches():
    """The chunks pack at tiles of 4: a chunk is cut at every tile
    boundary and where the slot changes, a decode row is a unit of one,
    a padding row none; a unit fetches its slot as far as its largest
    visibility in whole turns, and the layout's ``attn_kv_tokens_read``
    is their sum at the layer's tile (8) and the layout's turn."""
    from paddle_tpu.inference.paged_layout import ragged_kv_tokens_read
    from paddle_tpu.ops.pallas.decode_attention import ragged_units

    c = _chunks_case()
    count, reach = ragged_units(c["slot"], c["lens"], 4, np)
    assert count[count > 0].tolist() == [4, 4, 2, 2, 4, 4, 2, 1, 1]
    assert reach[count > 0].tolist() == [18, 22, 24, 2, 6, 10, 12, 33, 5]
    for pp, want in ((1, 3 + 3 + 3 + 1 + 1 + 2 + 2 + 5 + 1),
                     (2, 2 + 2 + 2 + 1 + 1 + 1 + 1 + 3 + 1),
                     (16, 9)):            # clamped to the table's 5 pages
        _, nk, turns = sparse_mla.walk_geometry(8, 5, pp)
        assert ragged_kv_tokens_read(c["slot"], c["lens"], 4, nk, turns) \
            == want * nk
    # the layout's own count: tiles of 8, a turn the whole 5-page table,
    # so a unit fetches 40 positions: 2 + 2 units of the chunks' 22 rows
    # (rows 0..7, 8..9 | 10..15, 16..21) and the two decode rows
    cfg = DeepseekV32Config.debug()
    rows = np.zeros((len(c["lens"]), 5), np.int64)
    rows[:, 3], rows[:, 4] = c["lens"], c["slot"]
    got = cfg.paged_layout().row_counts(rows[c["slot"] >= 0], 0, 8, 5)
    assert got["attn_kv_tokens_read"] == 6 * 40


def test_kth_largest_without_a_sort():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(5, 40)).astype(np.float32)
    x[1, 7:] = -np.inf                       # fewer finite than k
    x[2, :] = 0.0
    x[3, ::2] = -0.0
    got = np.asarray(sparse_mla.kth_largest(jnp.asarray(x), 9))
    want = np.sort(x, 1)[:, ::-1][:, 8]
    assert np.array_equal(got, want)
    assert np.isneginf(np.asarray(
        sparse_mla.kth_largest(jnp.asarray(x[:, :9]), 9))).all()


@pytest.mark.parametrize("tile_n", [None, 128])
def test_grouped_matmul_block_major_form(tile_n):
    from paddle_tpu.ops.pallas.grouped_matmul import (align_rows,
                                                      grouped_matmul_raw,
                                                      segment_starts)

    rng = np.random.default_rng(7)
    E, K, N, bm = 5, 32, 256, 8
    lens = np.array([3, 0, 17, 8, 1], np.int32)
    st = np.asarray(segment_starts(jnp.asarray(lens), bm))
    R = int(align_rows(int(lens.sum()), bm) + E * bm) + bm
    x = np.zeros((R, K), np.float32)
    for e in range(E):
        x[st[e]:st[e] + lens[e]] = rng.normal(size=(lens[e], K))
    w = rng.normal(size=(E, K, N)).astype(np.float32)
    wids = np.array([4, 3, 2, 1, 0], np.int32)
    y = np.asarray(grouped_matmul_raw(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(st), jnp.asarray(lens),
        jnp.asarray(wids), block_rows=bm, tile_n=tile_n))
    for e in range(E):
        rows = slice(st[e], st[e] + lens[e])
        np.testing.assert_allclose(y[rows], x[rows] @ w[wids[e]], atol=1e-4)
