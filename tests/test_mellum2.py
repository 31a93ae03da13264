"""Mellum2 on the serving path, at a small size on the CPU in float32:
window and full layers behind two kinds of page (a table, an allocator
and a budget each), the prefix cache over both, and the softmax-routed
expert layer, through the engine's ONE step against the plain reference
``benchmarks/reference/mellum2_ref.py``, which shares no code with the
program."""

import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum2_ref as ref
from paddle_tpu.inference.page_cache import PageAllocator, PrefixCache
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import generation
from paddle_tpu.models.llama_paged import kv_layout, page_kinds
from paddle_tpu.models.mellum2 import FULL, SLIDING, Mellum2Config

PAGE, BUDGET, SLOTS, SEQ, VOCAB = 4, 6, 3, 64, 96
W = 8                           # two pages: contexts pass it by many
# pages(W + 2 chunks of 6) + 1: what a slot may hold of the window kind
BOUND = 6


def draw(cfg, seed=0, scale=0.2):
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in cfg.leaf_shapes().items():
        out[name] = (1.0 + 0.1 * rng.normal(size=shape) if len(shape) == 1
                     else rng.normal(size=shape) * scale)
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}


def ref_cfg(cfg):
    """The configuration as the benchmark's file states it."""
    d = dataclasses.asdict(cfg)
    d["rope_parameters"] = {k: dict(v) for k, v in cfg.rope_parameters}
    d["num_experts_per_tok"] = cfg.moe_top_k
    return d


@pytest.fixture(scope="module")
def model():
    cfg = Mellum2Config.debug()          # one period: S S S F, window 8
    return cfg, draw(cfg)


def engine(model, **kw):
    cfg, params = model
    opts = dict(max_slots=SLOTS, num_pages={"full": 49, "window": 19},
                page_size=PAGE, max_seq_len=SEQ, prefill_token_budget=BUDGET,
                enable_prefix_cache=True)
    opts.update(kw)
    return ContinuousBatchingEngine(cfg, params, **opts)


def serve(eng, prompts, max_new=6, watch=None):
    """Run to the end; ``(rids, {rid: tokens}, {(rid, position): logits})``."""
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    logits = {}
    while eng.queue or eng.active.any():
        eng.step()
        if watch is not None:
            watch(eng)
        for key, row in zip(*eng.last_logits):
            logits[key] = row
    done = {f.rid: f.tokens for f in eng.finished}
    return rids, {r: done[r] for r in rids}, logits


def reference_logits(model, prompt, tokens, **control):
    cfg, params = model
    ids = jnp.asarray(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))
    return np.asarray(ref.forward(params, ids, ref_cfg(cfg), **control))


def assert_matches(model, prompt, tokens, logits, rid):
    want = reference_logits(model, prompt, tokens)
    got = {pos: row for (r, pos), row in logits.items() if r == rid}
    assert len(prompt) - 1 in got and len(got) >= len(tokens)
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0)
    assert np.array_equal(tokens, want[len(prompt) - 1:].argmax(-1))


# float32 on both sides and the same mathematics in another order of
# operations (pages and an online softmax, a sorted expert dispatch):
# some 1e-5 over four layers at logits of order 5.  A missing window, a
# plain rope table or a wrong gate moves them by 1e-2 and more (below).
TOL = 1e-4


def prompts_of(*lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


# ---- (a) chunked prefill then decode against the reference --------------

@pytest.fixture(scope="module")
def served(model):
    """A prompt under the window (5) and one past it by six pages (33:
    six chunks of the 6-token budget) served together, decode rows
    beside prefill chunks, window-kind pages recycled mid-request."""
    prompts = prompts_of(5, 33)
    eng = engine(model, enable_prefix_cache=False)
    held = []
    rids, tokens, logits = serve(
        eng, prompts, watch=lambda e: held.append(
            max((len(h) for h in e.pages[1].held.values()), default=0)))
    stats = eng.serving_stats()["steps"]
    eng.shutdown()
    return dict(prompts=prompts, rids=rids, tokens=tokens, logits=logits,
                stats=stats, held=held)


@pytest.mark.parametrize("which", [0, 1], ids=["under_the_window",
                                               "past_the_window"])
def test_engine_logits_match_the_reference(model, served, which):
    rid, prompt = served["rids"][which], served["prompts"][which]
    assert_matches(model, prompt, served["tokens"][rid], served["logits"],
                   rid)


def test_window_pages_are_recycled_mid_request(served):
    st = served["stats"]
    # 33 + 6 positions are 10 pages; the slot never held more than BOUND
    assert st["window_pages_recycled"] >= 6
    assert 0 < max(served["held"]) <= BOUND
    assert st["ahead"] > 0          # and the engine ran one launch ahead
    # a window layer's least work: rows and slots capped at the window
    assert st["attn_row_ctx_window"] > 0
    assert 0 < st["kv_ctx_tokens_window"] < st["kv_ctx_tokens"]
    # every expert layer's copies are routed to an expert in the bank
    assert st["moe_rows_held"] == st["moe_rows_routed"] > 0
    assert 0 < st["moe_experts_hit"] <= st["moe_experts_total"]
    assert st["moe_experts_total"] % (8 * 4) == 0


@pytest.mark.parametrize("control,kw", [
    ("no_window", dict(use_window=False)), ("no_yarn", dict(yarn=False)),
    ("gates", dict(gates="softmax"))])
def test_a_control_moves_the_logits(model, served, control, kw):
    """What the reference's controls leave out shows at this size: the
    engine agrees with the sound reference and not with a control."""
    rid, prompt = served["rids"][1], served["prompts"][1]
    tokens = served["tokens"][rid]
    sound = reference_logits(model, prompt, tokens)
    other = reference_logits(model, prompt, tokens, **kw)
    assert np.abs(sound - other)[len(prompt) - 1:].max() > 100 * TOL


# ---- (b) the window's edge ----------------------------------------------

def test_the_windows_edge_exactly():
    """One sliding layer, then a full layer whose attention adds nothing
    (its output projection is zero): row p's logits depend on the tokens
    of positions p - W + 1 .. p alone.  The token W positions back
    changes no logit of row p; the one W - 1 back does."""
    cfg = Mellum2Config.debug(num_hidden_layers=2,
                              layer_types=(SLIDING, FULL))
    params = draw(cfg, seed=3)
    params["model.layers.1.self_attn.o_proj.weight"] = jnp.zeros_like(
        params["model.layers.1.self_attn.o_proj.weight"])
    base = prompts_of(30, seed=5)[0]
    p = len(base) - 1

    def last_row(prompt):
        eng = ContinuousBatchingEngine(
            cfg, params, max_slots=1, num_pages={"full": 12, "window": 8},
            page_size=PAGE, max_seq_len=40, prefill_token_budget=BUDGET)
        rid = eng.add_request(prompt, max_new_tokens=1)
        logits = {}
        while eng.queue or eng.active.any():
            eng.step()
            logits.update(zip(*eng.last_logits))
        eng.shutdown()
        return logits[(rid, p)]

    def changed(at):
        other = base.copy()
        other[at] = (other[at] + 1) % VOCAB
        return other

    want = last_row(base)
    np.testing.assert_array_equal(last_row(changed(p - W)), want)
    assert np.abs(last_row(changed(p - W + 1)) - want).max() > 1e-3


# ---- (c) a window pool far smaller than slots x pages_per_seq -----------

def test_a_small_window_pool_serves_long_contexts(model):
    """Three contexts of 58 to 63 positions (15 and 16 pages each) in
    flight at once: one table for all layers would need 47 pages of the
    window kind's pool, which has 3 x BOUND = 18.  No slot ever holds
    more than BOUND pages of it, and both kinds balance after the
    drain."""
    eng = engine(model, enable_prefix_cache=False,
                 num_pages={"full": 49, "window": 3 * BOUND + 1})
    assert eng.pages[1].bound == eng.window_bound(W) == BOUND
    assert eng.pages[1].alloc.total < SLOTS * eng.pages_per_seq
    most = []

    def watch(e):
        most.append(max((len(h) for h in e.pages[1].held.values()),
                        default=0))
        assert sum(len(h) for h in e.pages[1].held.values()) \
            == e.pages[1].alloc.live
    prompts = prompts_of(50, 53, 55, seed=7)
    rids, tokens, logits = serve(eng, prompts, max_new=8, watch=watch)
    assert 0 < max(most) <= BOUND
    # a lone prompt takes whole chunks a launch and reaches the bound
    # between a launch's packing and the commit before it
    del most[:]
    mapped = eng._map_pages

    def map_pages(slot, end):
        mapped(slot, end)
        most.append(len(eng.pages[1].held[slot]))
    eng._map_pages = map_pages
    serve(eng, prompts[:1], max_new=2)
    assert max(most) == BOUND
    assert_matches(model, prompts[2], tokens[rids[2]], logits, rids[2])
    for kp in eng.pages:
        kp.alloc.assert_balanced()
        assert kp.alloc.available == kp.alloc.total and not kp.held
        assert (kp.tables == -1).all()
    eng.shutdown()


def test_admission_waits_for_a_window_claim(model):
    """Head-of-line waiting counts each kind: a window pool of two
    claims admits two of three long requests and the third when a slot
    has ended."""
    eng = engine(model, enable_prefix_cache=False,
                 num_pages={"full": 49, "window": 2 * BOUND + 1})
    prompts = prompts_of(30, 31, 32, seed=8)
    for p in prompts:
        eng.add_request(p, max_new_tokens=4)
    eng.step()
    assert int(eng.active.sum()) == 2 and len(eng.queue) == 1
    done = eng.run()
    assert len(done) == 3
    with pytest.raises(ValueError, match="never be admitted"):
        engine(model, num_pages={"full": 49, "window": BOUND}
               ).add_request(prompts[0], max_new_tokens=4)
    eng.shutdown()


# ---- (d) prefix hits beyond the window ----------------------------------

def test_prefix_hits_beyond_the_window(model):
    """A pinned context of 24 tokens (6 pages, three windows), then
    requests that continue it: the hit maps the full kind's 6 pages and
    the window kind's last two, and what is served equals the uncached
    run's.  With window-kind pages of the prefix evicted ALONE the hit
    shrinks to what is whole (here nothing) and parity holds."""
    prefix = prompts_of(24, seed=11)[0]
    asks = [np.concatenate([prefix, t]) for t in prompts_of(9, 13, seed=12)]
    cold = engine(model, enable_prefix_cache=False)
    _, cold_tokens, cold_logits = serve(cold, asks)
    cold.shutdown()

    eng = engine(model)
    serve(eng, [prefix], max_new=2)          # a deployment's warm-up
    cache = eng.prefix_cache
    held = [n.more[0] is not None for n in cache._nodes()]
    assert len(held) == 6 and sum(held) >= 2
    rids, tokens, logits = serve(eng, asks[:1])
    st = eng.serving_stats()["prefill"][rids[0]]
    assert st["cached_tokens"] == 24 and st["prefilled"] == 9
    assert np.array_equal(tokens[rids[0]], cold_tokens[0])
    for (r, pos), row in logits.items():
        np.testing.assert_allclose(row, cold_logits[(0, pos)], atol=TOL,
                                   rtol=0)
    assert_matches(model, asks[0], tokens[rids[0]], logits, rids[0])

    # the window kind's pages go alone; the blocks stay
    blocks = cache.cached_pages
    assert cache.evict_window(0, 100) >= 2
    assert cache.cached_pages == blocks
    assert not any(n.more[0] is not None for n in cache._nodes())
    rids, tokens, logits = serve(eng, asks[1:])
    st = eng.serving_stats()["prefill"][rids[0]]
    assert st["cached_tokens"] == 0 and st["prefilled"] == len(asks[1])
    assert np.array_equal(tokens[rids[0]], cold_tokens[1])
    # that prefill put the window kind's pages back where blocks lacked
    # them, so the next ask hits again
    rids, tokens, _ = serve(eng, asks[1:])
    assert eng.serving_stats()["prefill"][rids[0]]["cached_tokens"] > 0
    assert np.array_equal(tokens[rids[0]], cold_tokens[1])
    for kp in eng.pages:
        kp.alloc.assert_balanced()
    cache.assert_consistent()
    eng.shutdown()


def test_a_hit_shrinks_to_what_is_whole():
    """The cache alone: blocks 0..9 of a chain, pages of 4, window 8 (a
    hit of j blocks reads the window kind's pages of blocks j - 2 and
    j - 1).  With block 9's page gone a walk of 10 blocks is served as
    9, with block 7's gone too as 7; block 8's page can then serve no
    hit and is the first to go, before older pages that can."""
    full, win = PageAllocator(32), PageAllocator(32)
    cache = PrefixCache(PAGE, full, windows=((win, W),))
    prompt = np.arange(41, dtype=np.int32)
    fp = [full.alloc() for _ in range(10)]
    wp = [win.alloc() for _ in range(10)]
    cache.insert(prompt, fp, [(0, wp)])
    full.release(fp), win.release(wp)        # the slot has ended
    node = dict(enumerate(_chain(cache)))

    def ask(matched, first):
        pages, got, _, _ = cache.lookup_all(prompt)
        assert got == matched and len(pages[0]) == matched // PAGE
        assert pages[1] == wp[first:matched // PAGE]
        full.release(pages[0]), win.release(pages[1])

    def drop(i):
        win.release([node[i].more[0]])
        node[i].more[0] = None

    ask(40, 8)
    assert _useless(cache, 10) == set()
    drop(9)
    ask(36, 7)
    drop(7)
    ask(28, 5)
    # a hit of 9 blocks lacks 7's page and one of 10 lacks 9's
    assert _useless(cache, 10) == {8}
    for n in node.values():
        n.tick = 5
    node[8].tick = 9                         # the most recently used
    assert cache.evict_window(0, 1) == 1 and node[8].more[0] is None
    assert cache.evicted_window_pages == 1
    assert cache.evict_window(0, 100) == 7
    ask(0, 0)
    cache.assert_consistent()
    cache.clear()
    full.assert_balanced(), win.assert_balanced()
    assert win.available == win.total and full.available == full.total


def _chain(cache):
    n = cache.root
    while n.children:
        (n,) = n.children.values()
        yield n


def _useless(cache, blocks):
    """Blocks whose window-kind page no hit can read, by brute force."""
    have = [n.more[0] is not None for n in _chain(cache)]

    def whole(j):
        return all(have[cache._first_read(j, W):j])
    return {i for i in range(blocks) if have[i] and not any(
        whole(j) and cache._first_read(j, W) <= i < j
        for j in range(1, blocks + 1))}


# ---- (e) one launch ahead, two kinds ------------------------------------

def test_cancel_eos_and_shutdown_mid_flight(model):
    """A slot that is canceled, or ends on ``eos_id``, with a row in
    flight runs that row stale inside pages it has given back; nothing
    is recycled under a launch in flight, so the requests served beside
    and after it equal the reference, and both kinds balance."""
    prompts = prompts_of(21, 34, 27, seed=13)
    plain = engine(model, enable_prefix_cache=False)
    _, tokens, _ = serve(plain, prompts[:1], max_new=8)
    plain.shutdown()
    eos = int(next(iter(tokens.values()))[3])      # its fourth token
    eng = engine(model, eos_id=eos, enable_prefix_cache=False)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    logits, canceled = {}, False
    while eng.queue or eng.active.any():
        eng.step()
        logits.update(zip(*eng.last_logits))
        if not canceled and len(eng.out_tokens.get(rids[2], ())) >= 2:
            assert eng._flight is not None          # a row of it in flight
            canceled = eng.cancel(rids[2])
    done = {f.rid: f.tokens for f in eng.finished}
    assert canceled and rids[2] not in done
    assert done[rids[0]][-1] == eos and len(done[rids[0]]) <= 4
    assert eng.serving_stats()["steps"]["stale_rows"] >= 2
    want = reference_logits(model, prompts[1], done[rids[1]])
    for (r, pos), row in logits.items():
        if r == rids[1]:
            np.testing.assert_allclose(row, want[pos], atol=TOL, rtol=0)
    for kp in eng.pages:
        kp.alloc.assert_balanced()
    eng.shutdown()
    # shutdown with only stale rows left in flight
    eng = engine(model, enable_prefix_cache=False)
    rid = eng.add_request(prompts[0], max_new_tokens=8)
    while len(eng.out_tokens.get(rid, ())) < 2:
        eng.step()
    assert eng._flight is not None and eng.cancel(rid)
    eng.shutdown()


# ---- (f) the tables and the router --------------------------------------

def test_yarn_table_against_the_closed_form():
    """The published parameters: 128 dimensions, theta 500000, factor 16
    over 8192, beta 32 and 1: the ramp runs from pair 18 to pair 35."""
    cfg = Mellum2Config(num_hidden_layers=4, max_position_embeddings=512)
    cos, sin = cfg.rope_tables()
    d, theta, af = 128, 500000.0, 1.2772588722239782
    j = np.arange(64)
    f = theta ** (-2.0 * j / d)
    low = math.floor(d * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    m = 1 - np.clip((j - low) / (high - low), 0, 1)
    inv = f / 16 * (1 - m) + f * m
    assert np.array_equal(inv[:19], f[:19])
    np.testing.assert_allclose(inv[35:], f[35:] / 16, rtol=1e-12)
    ang = np.outer(np.arange(512), inv)
    np.testing.assert_allclose(cos[FULL][:, :64], np.cos(ang) * af, atol=1e-6)
    np.testing.assert_allclose(sin[FULL][:, 64:], np.sin(ang) * af, atol=1e-6)
    plain = np.outer(np.arange(512), f)
    np.testing.assert_allclose(cos[SLIDING][:, :64], np.cos(plain), atol=1e-6)
    # the reference spells the same tables on its own
    rcfg = ref_cfg(cfg)
    for kind in (FULL, SLIDING):
        rc, rs = ref.rope_tables(rcfg, kind, 512)
        np.testing.assert_allclose(rc, cos[kind], atol=1e-6)
        np.testing.assert_allclose(rs, sin[kind], atol=1e-6)
    # one entry a config, the tables by kind
    key = generation.register_config(cfg)
    assert set(generation._CFGS[key][1]) == {FULL, SLIDING}


def test_router_against_the_reference(model):
    cfg, params = model
    h = jnp.asarray(np.random.default_rng(2).normal(size=(40, 64)),
                    jnp.float32)
    wr = params["model.layers.0.mlp.router.weight"]
    ids, gates = generation._route_softmax_topk(cfg, h @ wr)
    rids, rgates = ref.route(h, {"mlp.router.weight": wr}, ref_cfg(cfg))
    assert np.array_equal(np.sort(ids, -1), np.sort(rids, -1))
    np.testing.assert_allclose(np.sort(gates, -1), np.sort(rgates, -1),
                               atol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
    _, raw = ref.route(h, {"mlp.router.weight": wr}, ref_cfg(cfg),
                       gates="softmax")
    assert (raw.sum(-1) < 1).all() and raw.sum(-1).mean() < 0.9


def test_published_keys_and_the_kinds_of_page():
    cfg = Mellum2Config()
    assert (cfg.head_dim, cfg.hidden_size, cfg.num_experts, cfg.moe_top_k,
            cfg.moe_intermediate_size, cfg.sliding_window) == \
        (128, 2304, 64, 8, 896, 1024)
    assert cfg.layer_types.count(FULL) == 7 and len(cfg.layer_types) == 28
    full, window = page_kinds(cfg)
    assert full.layers == tuple(range(3, 28, 4)) and full.window is None
    assert window.window == 1024 and len(window.layers) == 21
    layout = kv_layout(cfg)
    assert [k.name for k in layout.kinds] == ["full", "window"]
    assert "moe_experts_hit" in layout.device_counts
    cut = Mellum2Config.from_published(
        {"num_hidden_layers": 8, "num_experts_per_tok": 8,
         "layer_types": list(cfg.layer_types), "model_type": "mellum",
         "mlp_layer_types": ["sparse"] * 28, "torch_dtype": "bfloat16"})
    assert cut.layer_types == cfg.layer_types[:8]
    params = sum(math.prod(s) for s in cut.leaf_shapes().values())
    assert params == 3_794_966_784
    # the bound of the issue's reckoning: window 1024, chunks of 512,
    # pages of 128: pages(1024 + 2 x 512) + 1
    wide = Mellum2Config.debug(sliding_window=1024,
                               max_position_embeddings=4096)
    eng = ContinuousBatchingEngine(
        wide, draw(wide), max_slots=1, num_pages={"full": 40, "window": 20},
        page_size=128, max_seq_len=4096, prefill_token_budget=512)
    assert eng.window_bound(1024) == eng.pages[1].bound == 17
    # a Llama config has one kind, as ever
    from paddle_tpu.models import LlamaConfig
    assert page_kinds(LlamaConfig.debug()) == ()
    assert kv_layout(LlamaConfig.debug()).kinds == ()


# ---- (g) what two kinds of page refuse ----------------------------------

@pytest.mark.parametrize("what,kw", [
    ("a draft model", dict(speculative_k=2, draft_params={})),
    ("an int8 cache", dict(cache_dtype=jnp.int8)),
    ("the host tier", dict(host_tier_pages=4)),
    ("prefill_only", dict(prefill_only=True))])
def test_two_kinds_of_page_refuse(model, what, kw):
    with pytest.raises(ValueError, match=f"2 kinds of page do not support "
                                         f"{what}"):
        engine(model, **kw)


def test_two_kinds_refuse_the_handoff_and_generate(model):
    eng = engine(model)
    with pytest.raises(ValueError, match="do not support the KV handoff"):
        eng.adopt_request({}, {}, 4)
    with pytest.raises(ValueError, match="kinds of page are"):
        engine(model, num_pages={"full": 9})
    with pytest.raises(ValueError, match="lookup_all"):
        eng.prefix_cache.lookup(np.arange(9))

    class Model:
        cfg = model[0]
    with pytest.raises(NotImplementedError, match="window and full"):
        generation.generate(Model(), np.zeros((1, 4), np.int32))
