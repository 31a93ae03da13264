"""Continuous-batching serving engine (inference/serving.py): greedy
parity vs the one-shot generate() path, admission under page pressure,
eviction + page reuse.  Analog of the reference's serving stack around
block_multihead_attention (its seq_lens_encoder/decoder/this_time
triplet)."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.inference.page_cache import PageAllocator
from paddle_tpu.inference.paged_layout import ragged_kv_tokens_read
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import generate, self_draft_params
from paddle_tpu.models.llama_paged import kv_layout, unified_step_jit


@pytest.fixture(scope="module")
def tiny_model():
    # Seed EXPLICITLY before building the model: module-scoped fixtures
    # instantiate before the function-scoped autouse ``_seed`` fixture,
    # so without this the params depended on whatever RNG state the
    # previous test left behind — the root cause of the suite-order
    # flake in test_unified_int8_kv_cache_close_to_bf16 (VERDICT r5 Weak
    # #4: near-tie greedy tokens flipped with different random params).
    import paddle_tpu as paddle

    state = paddle.get_rng_state()
    paddle.seed(20240806)
    cfg = LlamaConfig.debug(vocab=64, hidden=32, layers=2, heads=4,
                            kv_heads=2, inter=64, max_pos=128)
    model = LlamaForCausalLM(cfg)
    params = {k: jnp.asarray(v) for k, v in model.functional_state().items()}
    paddle.set_rng_state(state)
    return cfg, model, params


def test_page_allocator_lifo():
    a = PageAllocator(4)
    got = [a.alloc() for _ in range(3)]
    assert got == [0, 1, 2]
    a.release([0, 1])
    assert a.alloc() == 0 or a.alloc() is not None  # reuse happens
    assert a.available >= 1


def test_serving_rejects_oversized_prompt(tiny_model):
    cfg, model, params = tiny_model
    eng = _unified(cfg, params, max_seq_len=32)
    with pytest.raises(ValueError):
        eng.add_request(np.zeros(30, np.int32), max_new_tokens=8)



# =====================================================================
# Round-11 unified serving plane: refcounted pages, radix prefix cache,
# chunked prefill mixed into the decode step, speculative decoding.
# =====================================================================


def _unified(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_pages", 33)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_token_budget", 16)
    return ContinuousBatchingEngine(cfg, params, **kw)


def test_page_allocator_refcounts():
    """Explicit acquire/release refcounting + the leak-check invariant
    (available + live == total); double release and dead-page acquire
    are hard failures."""
    a = PageAllocator(4)
    p = a.alloc()
    a.assert_balanced()
    a.acquire(p)                       # second owner
    a.release([p])                     # first owner gone
    assert a.refs[p] == 1 and p not in a.free
    a.assert_balanced()
    a.release([p])                     # last owner: back to the pool
    assert a.refs[p] == 0 and a.available == 4
    a.assert_balanced()
    with pytest.raises(AssertionError):
        a.release([p])                 # double release
    with pytest.raises(AssertionError):
        a.acquire(p)                   # acquire of a free page


def test_unified_matches_oneshot_generate(tiny_model):
    """The ragged unified step (chunked prefill + paged-kernel decode)
    reproduces one-shot generate() greedy output exactly — and the
    teardown leak check passes."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 21)]
    eng = _unified(cfg, params, max_slots=3, prefill_token_budget=8)
    for p in prompts:
        eng.add_request(p, max_new_tokens=6)
    done = eng.run()
    assert len(done) == len(prompts)
    for i, p in enumerate(prompts):
        ref = generate(model, p[None], max_new_tokens=6, do_sample=False)
        ref_new = np.asarray(ref._value if hasattr(ref, "_value") else ref
                             )[0, len(p):]
        np.testing.assert_array_equal(
            done[i].tokens, ref_new[:len(done[i].tokens)],
            err_msg=f"request {i} diverged under the unified step")
    eng.shutdown()                     # allocator leak check


def test_unified_page_and_slot_reuse(tiny_model):
    """ONE slot and 4 usable pages: three requests pass through slot 0
    one after another.  The second grows across a page boundary while it
    decodes (14 + 7 tokens), the third while it prefills (30 tokens in
    chunks of 8, then 7 more: 3 pages).  A request that takes a slot
    and pages another has left must see none of its K/V: greedy parity
    with one-shot generate() proves it.  Released page ids are handed
    out again, and after the drain nothing is held."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 14, 30)]
    eng = _unified(cfg, params, max_slots=1, num_pages=5,
                   prefill_token_budget=8)
    rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
    held = {}
    while eng.queue or eng.active.any():
        eng.step()
        if eng.active[0]:
            assert eng.active.sum() == 1
            held[int(eng.slot_rid[0])] = list(eng.slot_pages[0])
    done = sorted(eng.finished, key=lambda f: f.rid)
    assert [len(held[r]) for r in rids] == [1, 2, 3]
    # LIFO: the page a finished request gave back is the next one taken
    assert held[rids[0]][0] in held[rids[1]] \
        and set(held[rids[1]]) <= set(held[rids[2]])
    for f, p in zip(done, prompts):
        ref = generate(model, p[None], max_new_tokens=7, do_sample=False)
        ref_new = np.asarray(ref._value if hasattr(ref, "_value") else ref
                             )[0, len(p):]
        np.testing.assert_array_equal(
            f.tokens, ref_new, err_msg=f"request {f.rid} corrupted by "
                                       f"slot or page reuse")
    assert eng.alloc.available == eng.alloc.total == 4
    assert (eng.tables == -1).all() and not eng.slot_pages
    eng.alloc.assert_balanced()
    eng.shutdown()


def test_engine_has_one_step_path(tiny_model):
    """No option selects between engines: no ``decode_chunk_steps``, no
    ``"auto"``, a budget that is a number; and the program the engine
    launches is its layout's ``step``, which is what the doctor is
    handed."""
    import inspect

    cfg, model, params = tiny_model
    sig = inspect.signature(ContinuousBatchingEngine.__init__).parameters
    assert len(sig) == 3 + 15 and "decode_chunk_steps" not in sig
    assert "auto" not in [p.default for p in sig.values()]
    assert sig["prefill_token_budget"].default == 256 \
        and sig["page_size"].default == 128
    eng = _unified(cfg, params)
    assert not hasattr(eng, "unified")
    fn, *_ = eng.analysis_entry()
    assert fn is eng.layout.step is kv_layout(cfg).step \
        is cfg.paged_layout().step is unified_step_jit
    # left unset, the pages a turn are the layout's rule; an int overrides
    assert eng.pages_per_step == eng.layout.pages_per_step(
        eng.page_size, eng.pages_per_seq, 4) == 8
    assert _unified(cfg, params, pages_per_step=2).pages_per_step == 2


@pytest.mark.parametrize("chunk, want", [(0, 8 + 16), (70, 8 + 16 + 64 + 72)],
                         ids=["decode_rows", "chunk_crossing_a_tile"])
def test_kv_layout_row_counts(tiny_model, chunk, want):
    """What the K/V layout counts for a packed schedule, by hand (pages
    of 8, the tiny shapes' query tile of 64 rows): decode rows that see
    7 and 11 positions (the engine runs one step ahead: the second
    call packs each slot's SECOND decode row, the first is in flight)
    fetch 1 and 2 pages; behind them a 70-row chunk
    is two units of work, 62 rows in the first tile reaching 62
    positions (8 pages) and 8 rows in the second reaching 70 (9 pages).
    The same rows in a served step put the same number on
    ``serving.step_counts``."""
    cfg, model, params = tiny_model
    eng = _unified(cfg, params, max_slots=3, num_pages=33, page_size=8,
                   max_seq_len=96, prefill_token_budget=72)
    rng = np.random.default_rng(1)
    for n in (5, 9):
        eng.add_request(rng.integers(1, 64, n).astype(np.int32),
                        max_new_tokens=4)
    eng.step()                      # both prompts prefilled: 5 + 9 rows
    if chunk:
        eng.add_request(rng.integers(1, 64, chunk).astype(np.int32),
                        max_new_tokens=4)
    seen = {}
    pack = eng._pack_unified

    def spy(*a):
        out = pack(*a)
        seen["rows"], seen["counts"] = out[0], out[-1].counts
        return out

    eng._pack_unified = spy
    eng.step()
    r = 2 + chunk
    assert seen["counts"]["rows"] == r
    # the hand-built schedule: (token, page, offset, visibility, slot)
    vis = [7, 11] + list(range(1, chunk + 1))
    slot = [0, 1] + [2] * chunk
    rows = np.zeros((r, 5), np.int32)
    rows[:, 3], rows[:, 4] = vis, slot
    assert (seen["rows"][:r, 3:] == rows[:, 3:]).all()
    got = kv_layout(cfg).row_counts(rows, sum(vis[:2]) + chunk, 8, 12)
    assert got == {"attn_kv_tokens_read": want}
    assert got["attn_kv_tokens_read"] \
        == ragged_kv_tokens_read(rows[:, 4], rows[:, 3], 64, 8, 12) \
        == seen["counts"]["attn_kv_tokens_read"]
    eng.run()
    eng.shutdown()


def test_prefix_cache_hit_bit_identical_greedy(tiny_model):
    """A warm request sharing a system prompt produces BIT-IDENTICAL
    greedy output to the cold engine, and its prefill-token accounting
    shows it skipped >= the shared full pages' worth of prefill."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(12)
    sys_p = rng.integers(1, cfg.vocab_size, (37,)).astype(np.int32)
    pa = np.concatenate([sys_p, rng.integers(1, cfg.vocab_size, (6,))
                         .astype(np.int32)])
    pb = np.concatenate([sys_p, rng.integers(1, cfg.vocab_size, (9,))
                         .astype(np.int32)])

    cold = _unified(cfg, params)
    cold.add_request(pa, max_new_tokens=8)
    cold.add_request(pb, max_new_tokens=8)
    cold_out = {f.rid: f.tokens for f in cold.run()}
    cold.shutdown()

    warm = _unified(cfg, params, enable_prefix_cache=True)
    ra = warm.add_request(pa, max_new_tokens=8)
    out_a = {f.rid: f.tokens for f in warm.run()}
    rb = warm.add_request(pb, max_new_tokens=8)
    out_b = {f.rid: f.tokens for f in warm.run()}
    np.testing.assert_array_equal(cold_out[0], out_a[ra])
    np.testing.assert_array_equal(cold_out[1], out_b[rb])

    st = warm.serving_stats()
    # pb shares 37 sys tokens with pa -> 2 committed full pages (32
    # tokens) matched; the FLOPs-skip contract: prefilled counts ONLY
    # the private suffix
    assert st["prefix_cache"]["hits"] == 1
    assert st["prefill"][rb]["cached_tokens"] == 32
    assert st["prefill"][rb]["prefilled"] == len(pb) - 32
    assert st["prefill"][ra]["prefilled"] == len(pa)
    warm.shutdown()


def test_prefix_cache_hit_bit_identical_seeded_temperature(tiny_model):
    """Warm/cold parity must also hold for temperature sampling with a
    fixed seed: host-side fp64 sampling from returned logits replays the
    identical stream when the prefix comes from the cache."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(13)
    sys_p = rng.integers(1, cfg.vocab_size, (20,)).astype(np.int32)
    p = np.concatenate([sys_p, rng.integers(1, cfg.vocab_size, (5,))
                        .astype(np.int32)])

    cold = _unified(cfg, params)
    cold.add_request(p, max_new_tokens=8, temperature=0.8, seed=42)
    cold_toks = cold.run()[0].tokens
    cold.shutdown()

    warm = _unified(cfg, params, enable_prefix_cache=True)
    warm.add_request(p, max_new_tokens=8, temperature=0.8, seed=42)
    warm.run()                          # populates the trie
    r2 = warm.add_request(p, max_new_tokens=8, temperature=0.8, seed=42)
    warm_toks = {f.rid: f.tokens for f in warm.run()}[r2]
    assert warm.serving_stats()["prefill"][r2]["cached_tokens"] > 0
    np.testing.assert_array_equal(cold_toks, warm_toks)
    warm.shutdown()


@pytest.mark.slow  # round-20 tier policy: tier-1 homes = the kept
# test_prefix_cache_hit_bit_identical_greedy leg + the disagg host-tier
# roundtrip/cross-replica trie legs (same page-sharing machinery)
def test_prefix_cache_cow_isolation(tiny_model):
    """Two live requests share prefix pages copy-on-write while their
    suffixes diverge — and a THIRD request re-reading the shared prefix
    afterwards still sees uncorrupted pages (greedy output equals the
    cold engine's for all three)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(14)
    sys_p = rng.integers(1, cfg.vocab_size, (33,)).astype(np.int32)
    reqs = [np.concatenate([sys_p,
                            rng.integers(1, cfg.vocab_size, (n,))
                            .astype(np.int32)])
            for n in (4, 7, 5)]

    cold = _unified(cfg, params, max_slots=3)
    for q in reqs:
        cold.add_request(q, max_new_tokens=6)
    cold_out = {f.rid: f.tokens for f in cold.run()}
    cold.shutdown()

    warm = _unified(cfg, params, max_slots=3, enable_prefix_cache=True,
                    prefill_token_budget=8)
    r0 = warm.add_request(reqs[0], max_new_tokens=6)
    warm.run()
    # both warm requests decode CONCURRENTLY off the same prefix pages
    r1 = warm.add_request(reqs[1], max_new_tokens=6)
    r2 = warm.add_request(reqs[2], max_new_tokens=6)
    out = {f.rid: f.tokens for f in warm.run()}
    np.testing.assert_array_equal(cold_out[0], warm.finished[0].tokens)
    np.testing.assert_array_equal(cold_out[1], out[r1])
    np.testing.assert_array_equal(cold_out[2], out[r2])
    st = warm.serving_stats()
    assert st["prefill"][r1]["cached_tokens"] == 32
    assert st["prefill"][r2]["cached_tokens"] == 32
    warm.shutdown()



@pytest.mark.slow
def test_prefix_cache_eviction_under_pressure(tiny_model):
    """Tier-2 (round-16 re-tier: classic-evict breadth; tier-1 home: disagg host-tier pressure legs + the COW/teardown balance checks).

    With the pool mostly held by refcount-0 trie pages, a new
    request that needs them is still admitted: LRU eviction frees the
    cold chain bottom-up, and the teardown balance still holds."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(15)
    # pool: 8 usable pages; each 40+8-token request spans 3 pages and
    # commits 2 full prompt pages into the trie
    eng = _unified(cfg, params, num_pages=9, max_slots=1,
                   enable_prefix_cache=True)
    p1 = rng.integers(1, cfg.vocab_size, (40,)).astype(np.int32)
    p2 = rng.integers(1, cfg.vocab_size, (40,)).astype(np.int32)
    eng.add_request(p1, max_new_tokens=8)
    eng.run()
    eng.add_request(p2, max_new_tokens=8)
    eng.run()
    assert eng.prefix_cache.cached_pages == 4        # 2 prompts x 2
    # 4 trie pages + 8-page pool: a 3rd distinct request needs 3 pages
    # but only 4 are free -> fits; a 4th forces eviction of the LRU
    # chain (p1's pages, colder than p2's)
    p3 = rng.integers(1, cfg.vocab_size, (60,)).astype(np.int32)
    eng.add_request(p3, max_new_tokens=8)            # needs 5 pages
    done = eng.run()
    assert len(done) == 3
    assert eng.prefix_cache.evicted_pages >= 1
    stats = eng.serving_stats()["prefix_cache"]
    assert stats["evicted_pages"] == eng.prefix_cache.evicted_pages
    eng.shutdown()


def test_chunked_prefill_decode_latency_bound(tiny_model):
    """The chunked-prefill latency contract: a LONG prompt admitted
    mid-decode never stalls the running slot — the decode slot emits
    >= 1 token on EVERY engine step while the prompt trickles through
    at prefill_token_budget tokens per step."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(16)
    eng = _unified(cfg, params, prefill_token_budget=16)
    eng.add_request(rng.integers(1, cfg.vocab_size, (8,))
                    .astype(np.int32), max_new_tokens=20)
    eng.step()                          # prefill (8 <= 16: one chunk)
    long_p = rng.integers(1, cfg.vocab_size, (60,)).astype(np.int32)
    eng.add_request(long_p, max_new_tokens=4)
    prefill_steps = 0
    while eng.active[0]:
        before = len(eng.out_tokens[0])
        rows = eng.serving_stats()["steps"]["prefill_rows"]
        eng.step()
        # the prompt rows of the launch this call committed
        chunk = eng.serving_stats()["steps"]["prefill_rows"] - rows
        if eng.active[0] or int(eng.slot_rid[0]) != 0:
            after = len(eng.out_tokens[0]) if 0 in eng.out_tokens else 21
        else:
            after = 21                  # finished this step: it emitted
        assert after > before, \
            "decode slot starved by a co-scheduled long prompt"
        assert chunk <= 16                           # chunk bound
        if chunk > 0:
            prefill_steps += 1
    assert prefill_steps >= 4           # 60 tokens / 16-token chunks
    done = sorted(eng.run(), key=lambda f: f.rid)
    assert len(done[0].tokens) == 20 and len(done[1].tokens) == 4
    eng.shutdown()


@pytest.mark.slow
def test_chunked_prefill_splits_across_requests(tiny_model):
    # tier-2 (round-16 re-tier): chunk-splitting breadth; tier-1 home:
    # the serving_trace smoke leg drives chunked prefill over a trace
    """One step's prefill chunk packs tokens from MORE than one admitted
    request when the budget allows (ragged multi-request chunk)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(17)
    eng = _unified(cfg, params, max_slots=3, prefill_token_budget=24)
    eng.add_request(rng.integers(1, cfg.vocab_size, (10,))
                    .astype(np.int32), max_new_tokens=4)
    eng.add_request(rng.integers(1, cfg.vocab_size, (30,))
                    .astype(np.int32), max_new_tokens=4)
    eng.step()
    # the one launch this call committed held rows of both prompts
    took = [st["prefilled"] for st in eng.prefill_stats.values()]
    assert len(took) == 2 and min(took) > 0           # both prefilled
    assert sum(took) == 24 == eng.serving_stats()["steps"]["prefill_rows"]
    done = eng.run()
    assert len(done) == 2
    eng.shutdown()



@pytest.mark.slow
def test_speculative_greedy_exact_match(tiny_model):
    """Tier-2 (round-16 re-tier: exact-acceptance breadth; tier-1 home: the serving_trace smoke leg (oracle self-draft mean accepted length > 1 REQUIRES exact greedy prefix acceptance) + the temperature drain leg).

    Speculative decoding with a greedy target emits EXACTLY the
    non-speculative greedy stream across accept/reject boundaries —
    with a layer-truncated self-draft (imperfect proposer: both
    accepts and rejects occur) and with an oracle draft (all-accept)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(18)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (7, 26)]

    base = _unified(cfg, params)
    for p in prompts:
        base.add_request(p, max_new_tokens=10)
    want = {f.rid: f.tokens for f in base.run()}
    base.shutdown()

    dcfg, dparams = self_draft_params(cfg, params, 1)
    for draft_cfg, draft_params in ((dcfg, dparams), (None, params)):
        eng = _unified(cfg, params, draft_params=draft_params,
                       draft_cfg=draft_cfg, speculative_k=3)
        for p in prompts:
            eng.add_request(p, max_new_tokens=10)
        got = {f.rid: f.tokens for f in eng.run()}
        for r in want:
            np.testing.assert_array_equal(
                want[r], got[r],
                err_msg=f"speculative stream diverged (draft="
                        f"{'self' if draft_cfg else 'oracle'})")
        assert eng.accepted_lengths, "no verify windows recorded"
        if draft_cfg is None:           # oracle: every draft accepted
            assert np.mean(eng.accepted_lengths) > 1
        eng.shutdown()


def test_speculative_temperature_runs_and_drains(tiny_model):
    """Rejection-sampling speculative decode (temperature > 0) produces
    full-length output and balanced teardown; and the SAME seed gives
    the same stream twice (host sampling is deterministic)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(19)
    p = rng.integers(1, cfg.vocab_size, (12,)).astype(np.int32)
    dcfg, dparams = self_draft_params(cfg, params, 1)
    outs = []
    for _ in range(2):
        eng = _unified(cfg, params, draft_params=dparams, draft_cfg=dcfg,
                       speculative_k=2)
        eng.add_request(p, max_new_tokens=10, temperature=0.9, seed=7)
        outs.append(eng.run()[0].tokens)
        eng.shutdown()
    assert len(outs[0]) == 10
    np.testing.assert_array_equal(outs[0], outs[1])


def test_unified_guard_rails(tiny_model):
    """Config invariants: the prefill budget is an int >= 1 (there is
    no engine without one); speculative_k needs draft params; draft
    depth is bounded."""
    cfg, model, params = tiny_model
    for budget in (0, None):
        with pytest.raises(ValueError, match="prefill_token_budget"):
            _unified(cfg, params, prefill_token_budget=budget)
    with pytest.raises(ValueError, match="draft_params"):
        _unified(cfg, params, speculative_k=2)
    with pytest.raises(ValueError, match="speculative_k"):
        _unified(cfg, params, draft_params=params)  # a draft that never proposes
    with pytest.raises(ValueError):
        self_draft_params(cfg, params, cfg.num_hidden_layers + 1)



@pytest.mark.slow
def test_unified_int8_weights(tiny_model):
    """Tier-2 (round-16 re-tier: int8-weights breadth; tier-1 home: tests/test_int8_weights.py + the int8_weight_serving smoke leg).

    Weight-only int8 params ride the unified plane (dequant at the
    consumer dots, same scheduler): the run drains and mostly agrees
    with the fp engine (int8 may flip rare near-ties)."""
    from paddle_tpu.models.generation import quantize_params_int8

    cfg, model, params = tiny_model
    rng = np.random.default_rng(20)
    p = rng.integers(1, cfg.vocab_size, (9,)).astype(np.int32)
    fp = _unified(cfg, params)
    fp.add_request(p, max_new_tokens=8)
    want = fp.run()[0].tokens
    fp.shutdown()
    q8 = quantize_params_int8(params)
    eng = _unified(cfg, q8)
    eng.add_request(p, max_new_tokens=8)
    got = eng.run()[0].tokens
    eng.shutdown()
    assert len(got) == 8
    assert (np.asarray(want) == np.asarray(got)).mean() > 0.5


def test_unified_teardown_catches_leaks(tiny_model):
    """A seeded COW bug — an extra allocator reference that is never
    released — fails the teardown leak check loudly."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(21)
    eng = _unified(cfg, params)
    eng.add_request(rng.integers(1, cfg.vocab_size, (5,))
                    .astype(np.int32), max_new_tokens=4)
    eng.run()
    leaked = eng.alloc.alloc()          # simulated lost reference
    assert leaked is not None
    with pytest.raises(AssertionError, match="leak"):
        eng.shutdown()


# =====================================================================
# Round-13: int8 KV cache on the unified path + request withdrawal
# =====================================================================



@pytest.mark.slow
def test_unified_int8_kv_cache_close_to_bf16(tiny_model):
    """Tier-2 (round-16 re-tier: unified int8-KV tolerance leg; tier-1 home: the EXACT int8 parity gates in tests/test_serving_disagg.py).

    int8 KV cache: the first submission runs the calibration pass
    (absmax per (layer, kv head), 2x headroom, frozen), the
    ragged step quantizes every scattered K/V row with those scales,
    and the greedy streams must mostly agree with the fp-cache engine
    (parity under tolerance — int8 may flip rare near-ties)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 11)]

    outs = {}
    for dt in (None, jnp.int8):
        eng = _unified(cfg, params, cache_dtype=dt)
        if dt == jnp.int8:
            # the doctor entry must be traceable BEFORE calibration
            # (placeholder unit scales with the real pytree shape)
            from paddle_tpu.analysis import check

            fn, args, kwargs, options = eng.analysis_entry()
            assert check(fn, *args, kwargs=kwargs, options=options).ok
        for p in prompts:
            eng.add_request(p, max_new_tokens=8)
        done = eng.run()
        outs[dt] = {f.rid: f.tokens for f in done}
        if dt == jnp.int8:
            assert all(kp.dtype == jnp.int8 for kp in eng.k_pages)
            assert eng.kv_scales is not None
            # the FLOPs-skip contract still holds under int8
            stats = eng.serving_stats()["prefill"]
            assert all(v["prefilled"] == v["prompt_len"]
                       for v in stats.values())
        eng.shutdown()

    assert sorted(outs[None]) == sorted(outs[jnp.int8])
    match = sum(
        (np.asarray(a[:len(b)]) == np.asarray(b[:len(a)])).mean()
        for a, b in ((outs[None][r], outs[jnp.int8][r])
                     for r in sorted(outs[None]))) / len(prompts)
    assert match > 0.7, (outs, match)


@pytest.mark.slow
def test_unified_int8_kv_prefix_cache_consistent(tiny_model):
    """int8 KV + prefix cache: shared pages hold int8 quantized with
    the SAME frozen scales, so a warm request's stream equals the cold
    one's bit-for-bit (the cache serves self-consistent quantized
    pages, not a re-quantization)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(24)
    sysp = rng.integers(1, cfg.vocab_size, (16,)).astype(np.int32)
    body = rng.integers(1, cfg.vocab_size, (6,)).astype(np.int32)
    prompt = np.concatenate([sysp, body])
    eng = _unified(cfg, params, cache_dtype=jnp.int8,
                   enable_prefix_cache=True)
    eng.add_request(prompt, max_new_tokens=6)          # cold
    for _ in range(3):                     # commit the cold full pages
        eng.step()
    eng.add_request(prompt.copy(), max_new_tokens=6)   # warm (hit)
    done = eng.run()
    assert eng.prefix_cache.hits >= 1
    np.testing.assert_array_equal(done[0].tokens, done[1].tokens)
    eng.shutdown()


def test_unified_cancel_withdraws_without_finished(tiny_model):
    """engine.cancel (the router's migration/retry primitive): a
    queued request leaves the queue, an active one releases its slot
    and pages, NO Finished record is written, the survivor's stream is
    untouched, and teardown stays leak-free."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(25)
    p0 = rng.integers(1, cfg.vocab_size, (7,)).astype(np.int32)
    p1 = rng.integers(1, cfg.vocab_size, (9,)).astype(np.int32)
    p2 = rng.integers(1, cfg.vocab_size, (5,)).astype(np.int32)
    eng = _unified(cfg, params, max_slots=2)
    r0 = eng.add_request(p0, max_new_tokens=8)
    r1 = eng.add_request(p1, max_new_tokens=8)
    r2 = eng.add_request(p2, max_new_tokens=8)   # waits in queue
    eng.step()
    eng.step()                                   # r0/r1 mid-decode
    assert eng.cancel(r2) is True                # queued withdrawal
    assert eng.cancel(r0) is True                # active withdrawal
    assert eng.cancel(999) is False              # unknown rid
    done = eng.run()
    assert [f.rid for f in done] == [r1]
    ref = generate(model, p1[None], max_new_tokens=8, do_sample=False)
    ref_new = np.asarray(ref._value if hasattr(ref, "_value") else ref
                         )[0, len(p1):]
    np.testing.assert_array_equal(done[0].tokens, ref_new)
    eng.shutdown()                               # leak check passes


def test_unified_throttle_sheds_and_restores(tiny_model):
    """throttle(): spec_k/prefill budget shrink at runtime (no
    retrace, greedy parity intact) and restore to the constructor
    shapes; out-of-range values are rejected."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(26)
    p = rng.integers(1, cfg.vocab_size, (21,)).astype(np.int32)
    eng = _unified(cfg, params, draft_params=params, speculative_k=2)
    eng.throttle(speculative_k=0, prefill_token_budget=4)
    assert eng.spec_k == 0 and eng.prefill_budget == 4
    eng.add_request(p, max_new_tokens=6)
    done = eng.run()
    ref = generate(model, p[None], max_new_tokens=6, do_sample=False)
    ref_new = np.asarray(ref._value if hasattr(ref, "_value") else ref
                         )[0, len(p):]
    np.testing.assert_array_equal(done[0].tokens, ref_new)
    eng.throttle(speculative_k=2, prefill_token_budget=16)
    assert eng.spec_k == 2 and eng.prefill_budget == 16
    with pytest.raises(ValueError):
        eng.throttle(speculative_k=3)            # above the static cap
    with pytest.raises(ValueError):
        eng.throttle(prefill_token_budget=0)     # below the floor
    with pytest.raises(ValueError):
        eng.throttle(prefill_token_budget=32)    # above the static cap
    eng.shutdown()


# ---------------------------------------------------------------------
# PR 29: the engine runs one step AHEAD.  Launch n+1 is enqueued before
# launch n's tokens are read; a decode row's input token is then a
# reference into the tokens launch n sampled on the device.
# ---------------------------------------------------------------------


def _greedy(model, prompt, n):
    ref = generate(model, prompt[None], max_new_tokens=n, do_sample=False)
    return np.asarray(ref._value if hasattr(ref, "_value") else ref
                      )[0, len(prompt):]


def _spy_launches(eng):
    """Every launch the engine packs from now on: ``[(rows, launch)]``."""
    seen, pack = [], eng._pack_unified

    def spy(*a):
        out = pack(*a)
        if out[-1].counts["rows"]:
            seen.append((out[0].copy(), out[-1]))
        return out

    eng._pack_unified = spy
    return seen


def test_run_ahead_decodes_over_a_page_boundary(tiny_model):
    """Pages of 8, a prompt of 6 and 13 new tokens: the decode rows
    cross two page boundaries while every one of them but the first is
    launched before the token it continues has been read.  Token for
    token what ``generate()`` gives."""
    cfg, model, params = tiny_model
    p = np.random.default_rng(31).integers(1, 64, 6).astype(np.int32)
    eng = _unified(cfg, params, max_slots=1, num_pages=9, page_size=8,
                   max_seq_len=32)
    seen = _spy_launches(eng)
    compiled = eng.layout.step._cache_size()
    eng.add_request(p, max_new_tokens=13)
    (done,) = eng.run()
    np.testing.assert_array_equal(done.tokens, _greedy(model, p, 13))
    # the first launch (no tokens before it) and every later one (the
    # launch before's tokens, still on the device) are ONE program
    assert eng.layout.step._cache_size() == compiled + 1
    # the prompt's chunk, then 12 decode rows, each a reference
    assert [int(r[0, 0] < 0) for r, _ in seen] == [0] + [1] * 12
    # each writes the next position: pages 0, 1 and 2 in turn
    assert [(int(r[0, 1]), int(r[0, 2])) for r, _ in seen[1:]] \
        == [((6 + i) // 8, (6 + i) % 8) for i in range(12)]
    steps = eng.serving_stats()["steps"]
    assert steps["ahead"] == 12 and steps["stale_rows"] == 0
    eng.alloc.assert_balanced()
    eng.shutdown()


def test_run_ahead_first_decode_row_refers_to_the_final_chunk(tiny_model):
    """A prompt of 20 in chunks of 8: the final chunk (4 rows) and the
    first decode row sit in consecutive launches, so that row's input
    token is a reference to the chunk's gathered row, resolved on the
    device; the chunks before it produce no token anybody reads."""
    cfg, model, params = tiny_model
    p = np.random.default_rng(32).integers(1, 64, 20).astype(np.int32)
    eng = _unified(cfg, params, max_slots=1, prefill_token_budget=8)
    seen = _spy_launches(eng)
    eng.add_request(p, max_new_tokens=3)
    (done,) = eng.run()
    np.testing.assert_array_equal(done.tokens, _greedy(model, p, 3))
    kinds = [[m[0] for m in l.metas] for _, l in seen]
    assert kinds == [["prefill"]] * 3 + [["verify"]] * 2
    assert [l.counts["rows"] for _, l in seen] == [8, 8, 4, 1, 1]
    final, first = seen[2], seen[3]
    g = final[1].metas[0][2]                 # the final row's gathered place
    assert int(first[0][0, 0]) == -1 - g and int(first[0][0, 3]) == 21
    assert (np.concatenate([r[:l.counts["rows"], 0]
                            for r, l in seen[:3]]) == p).all()
    eng.alloc.assert_balanced()
    eng.shutdown()


def test_run_ahead_a_slot_that_ends_by_budget_is_not_packed_again(tiny_model):
    """Two slots, three requests: the first ends by its budget while the
    second keeps decoding, and the queued third takes its slot and its
    pages in the very next call.  No row is launched for a slot whose
    budget the launch in flight exhausts (``stale_rows`` 0)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(33)
    ps = [rng.integers(1, 64, n).astype(np.int32) for n in (7, 9, 11)]
    new = (3, 12, 5)
    eng = _unified(cfg, params, max_slots=2, num_pages=5, page_size=16,
                   max_seq_len=32)
    seen = _spy_launches(eng)
    rids = [eng.add_request(p, max_new_tokens=n) for p, n in zip(ps, new)]
    took = {}
    while eng.queue or eng.active.any():
        eng.step()
        for s in range(2):
            if eng.active[s]:
                took.setdefault(int(eng.slot_rid[s]),
                                (s, tuple(eng.slot_pages[s])))
    done = {f.rid: f.tokens for f in eng.finished}
    for rid, p, n in zip(rids, ps, new):
        np.testing.assert_array_equal(done[rid], _greedy(model, p, n))
    # the third request sat in the first one's slot, on its pages
    assert took[rids[2]] == took[rids[0]]
    steps = eng.serving_stats()["steps"]
    assert steps["stale_rows"] == 0
    # slot 0's rows by launch: 7 prompt rows and 2 decode rows for 3
    # tokens; the launch packed while the third token is in flight has
    # none (the slot is freed when that token is committed); the next
    # call admits the third request: 11 prompt rows, 4 decode rows
    s0 = [sum(m[3] for m in l.metas if m[1] == 0) for _, l in seen]
    assert s0[:9] == [7, 1, 1, 0, 11, 1, 1, 1, 1] and not any(s0[9:])
    eng.alloc.assert_balanced()
    eng.shutdown()


def test_run_ahead_a_slot_that_ends_on_eos_runs_one_stale_row(tiny_model):
    """``eos_id`` is known one step late: the slot's next row is already
    enqueued when the host reads the token that ends it.  Exactly one
    stale row runs (inside the slot's own pages), its token is dropped,
    and the next request reuses slot and pages with the right answer."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(44)
    p0, p1 = (rng.integers(1, 64, n).astype(np.int32) for n in (9, 12))
    want0, want1 = _greedy(model, p0, 8), _greedy(model, p1, 6)
    stop = next(i for i in range(2, 7) if want0[i] not in want0[:i]
                and want0[i] not in want1)
    eng = _unified(cfg, params, max_slots=1, num_pages=3, page_size=16,
                   max_seq_len=32, eos_id=int(want0[stop]))
    r0 = eng.add_request(p0, max_new_tokens=8)
    r1 = eng.add_request(p1, max_new_tokens=6)
    pages = {}
    while eng.queue or eng.active.any():
        eng.step()
        if eng.active[0]:
            pages.setdefault(int(eng.slot_rid[0]), tuple(eng.slot_pages[0]))
    done = {f.rid: f.tokens for f in eng.finished}
    np.testing.assert_array_equal(done[r0], want0[:stop + 1])
    np.testing.assert_array_equal(done[r1], want1)
    assert pages[r0] == pages[r1]
    assert eng.serving_stats()["steps"]["stale_rows"] == 1
    eng.alloc.assert_balanced()
    eng.shutdown()


def test_run_ahead_cancel_of_a_slot_with_a_row_in_flight(tiny_model):
    """``cancel(rid)`` between two calls, while a row of that slot is
    enqueued: the row runs stale, nothing of it is committed, the
    survivor's stream is untouched and the pages come back."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(35)
    p0, p1 = (rng.integers(1, 64, n).astype(np.int32) for n in (7, 9))
    eng = _unified(cfg, params, max_slots=2)
    r0 = eng.add_request(p0, max_new_tokens=8)
    r1 = eng.add_request(p1, max_new_tokens=8)
    eng.step()
    eng.step()
    slot = int(np.nonzero(eng.slot_rid == r0)[0][0])
    assert any(m[1] == slot for m in eng._flight.metas)
    free = eng.alloc.available
    assert eng.cancel(r0) is True
    assert eng.alloc.available > free
    assert not any(m[1] == slot for m in eng._flight.metas)
    assert eng._flight.counts["stale_rows"] == 1
    done = eng.run()
    assert [f.rid for f in done] == [r1] and r0 not in eng.out_tokens
    np.testing.assert_array_equal(done[0].tokens, _greedy(model, p1, 8))
    assert eng.serving_stats()["steps"]["stale_rows"] == 1
    eng.alloc.assert_balanced()
    eng.shutdown()


def test_run_ahead_run_and_shutdown_with_a_launch_in_flight(tiny_model):
    """``run()`` picks up an engine that has a launch in flight and
    drains it to the right tokens; ``shutdown()`` of an engine whose
    last launch holds only stale rows sees that launch through."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(36)
    p = rng.integers(1, 64, 10).astype(np.int32)
    eng = _unified(cfg, params, max_slots=1)
    eng.add_request(p, max_new_tokens=6)
    eng.step()
    eng.step()
    assert eng._flight is not None and eng._flight.metas
    (done,) = eng.run()
    np.testing.assert_array_equal(done.tokens, _greedy(model, p, 6))
    assert eng._flight is None
    rid = eng.add_request(p, max_new_tokens=6)
    eng.step()
    assert eng._flight is not None
    with pytest.raises(AssertionError, match="live requests"):
        eng.shutdown()
    eng.cancel(rid)
    assert eng._flight is not None and not eng._flight.metas
    eng.alloc.assert_balanced()
    eng.shutdown()
    assert eng._flight is None


@pytest.mark.parametrize("who", ["temperature", "draft"])
def test_the_host_waits_only_for_steps_it_has_to_see(tiny_model, who):
    """Where the host has to see a step's results before it can pack the
    next, the engine does not run ahead, and decides that from what it
    is serving.  ``temperature``: a greedy request decodes; a request
    with a temperature joins it for a while; the steps that carry it
    are read before the next is packed (``ahead`` 0), every other step
    but the first of a spell runs ahead (``ahead`` 1); the greedy
    stream is ``generate()``'s and the sampled one is what the same
    seed draws when the request is served alone, every step waited for
    (the order before PR 29).  ``draft``: a verify window's length is
    decided on the host, so no step runs ahead, and the stream is the
    greedy one."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(37)
    pg, pt = (rng.integers(1, 64, n).astype(np.int32) for n in (8, 11))
    kw = {}
    if who == "draft":
        dcfg, dparams = self_draft_params(cfg, params, 1)
        kw = dict(draft_cfg=dcfg, draft_params=dparams, speculative_k=2)
    eng = _unified(cfg, params, max_slots=2, **kw)
    log = []                             # (ahead, carries a temperature)
    commit = eng._commit_unified

    def spy(launch, *a):
        log.append((launch.counts["ahead"],
                    any(eng.req_info[m[1]].temperature > 0
                        for m in launch.metas)))
        return commit(launch, *a)

    eng._commit_unified = spy
    rg = eng.add_request(pg, max_new_tokens=16)
    for _ in range(4):
        eng.step()
    rt = eng.add_request(pt, max_new_tokens=4, temperature=0.8, seed=5)
    done = {f.rid: f.tokens for f in eng.run()}
    np.testing.assert_array_equal(done[rg], _greedy(model, pg, 16))
    alone = _unified(cfg, params, max_slots=2, **kw)
    alone.add_request(pt, max_new_tokens=4, temperature=0.8, seed=5)
    (want,) = alone.run()
    np.testing.assert_array_equal(done[rt], want.tokens)
    # what the commit before PR 29 served for this model, these prompts
    # and this seed (run there once, by hand)
    assert done[rt].tolist() == {"temperature": [51, 53, 49, 17],
                                 "draft": [51, 49, 32, 25]}[who]
    assert alone.serving_stats()["steps"]["ahead"] == 0
    alone.shutdown()
    steps = eng.serving_stats()["steps"]
    assert steps["ahead"] == sum(a for a, _ in log)
    if who == "draft":
        assert steps["ahead"] == 0 and eng.accepted_lengths
    else:
        with_t = [a for a, t in log if t]
        assert with_t and not any(with_t)
        first, last = log.index((0, True)), \
            len(log) - 1 - log[::-1].index((0, True))
        # before it came: one launch to fill, then every one ahead;
        # after it left: the same
        assert [a for a, _ in log[:first]] == [0] + [1] * (first - 1)
        assert [a for a, _ in log[last + 1:]] \
            == [0] + [1] * (len(log) - last - 2)
        assert first >= 3 and len(log) - last > 3
    eng.alloc.assert_balanced()
    eng.shutdown()


# (phys, off) of one step's packed rows at 7 pages of 16, the last the
# trash page: what ``_pack_unified`` builds for each kind of row
_KV_WRITE_TRASH = 6
_KV_WRITE_CASES = {
    # a 24-token prefill chunk from position 10: page 2, then page 4
    "chunk_over_page_boundary": (np.where(np.arange(10, 34) < 16, 2, 4),
                                 np.arange(10, 34) % 16),
    # 3 decode rows, then padding: all at the trash page's offset 0
    "duplicate_padding_rows": ([0, 3, 5] + [_KV_WRITE_TRASH] * 13,
                               [7, 15, 0] + [0] * 13),
    # two slots' k+1 = 4 rows each, the second crossing into page 1
    "verify_window": ([3, 3, 3, 3, 5, 5, 1, 1],
                      [4, 5, 6, 7, 14, 15, 0, 1]),
}


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("case", list(_KV_WRITE_CASES))
def test_write_kv_rows_equals_the_window_scatter(case, cache_dtype):
    """``_write_kv_rows`` (the head carried in the index, rows of ``d``
    scattered in place) against the spelling it replaced,
    ``pool.at[phys, :, off, :].set(x)``: the same values at the same
    addresses, bit for bit."""
    from paddle_tpu.inference.paged_layout import _write_kv_rows

    pages, kvh, page, d = 7, 2, 16, 8
    phys, off = (jnp.asarray(a, jnp.int32) for a in _KV_WRITE_CASES[case])
    rng = np.random.default_rng(len(case))
    # whole numbers up to 127: exact in bf16 and in int8 alike
    pool = jnp.asarray(rng.integers(-127, 128, (pages, kvh, page, d)),
                       cache_dtype)
    x = jnp.asarray(rng.integers(-127, 128, (len(phys), kvh, d)),
                    cache_dtype)
    want = np.asarray(pool.at[phys, :, off, :].set(x))
    got = np.asarray(_write_kv_rows(pool, phys, off, x))
    assert got.dtype == want.dtype and got.shape == want.shape
    # padding rows race for the trash page's first row in both
    # spellings; nothing reads it
    live = np.ones(got.shape, bool)
    live[_KV_WRITE_TRASH, :, 0, :] = False
    np.testing.assert_array_equal(got[live], want[live])
    assert not np.array_equal(got, np.asarray(pool))
