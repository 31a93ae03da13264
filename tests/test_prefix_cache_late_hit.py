"""The prefix cache takes a prompt's full pages chunk by chunk, and a
slot that has launched nothing is matched again when its first chunk is
packed (``serving._late_hit``): a re-ask that waits behind its own
document's prefill is served from the pages that prefill has committed.

The cache alone (``PrefixCache.insert`` with a growing prefix) and the
tiny Llama engine (greedy and seeded temperature, a cancel mid-prefill,
the look-ups a request costs, a ``prefill_only`` engine).  The layouts
with a window kind of page or a recurrent state, whose early blocks are
not restorable until their prompt is done, are served by the toys of
``serving_ladder_toys.py`` in the two files that compile them already
(``test_serving_ladder.py``, ``test_serving_ladder_kinds_state.py``:
``check_a_re_ask_late_hits_what_its_layout_can_restore``).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.inference.page_cache import PageAllocator, PrefixCache
from paddle_tpu.inference.serving import ContinuousBatchingEngine


# ---- the cache alone ---------------------------------------------------

def _chain(cache):
    node, out = cache.root, []
    while node.children:
        (node,) = node.children.values()
        out.append(node)
    return out


def test_insert_with_a_growing_prefix_takes_one_reference_a_page():
    """A prompt of 10 blocks inserted as its chunks commit (3, 3, 6, 8
    blocks, each twice), then whole with its window pages and a
    snapshot: one trie reference a page however often a block was
    inserted, no look-up restores an early block before the last insert,
    and the last insert gives every block it still holds a window page
    of, and the block the snapshot ends, what they lacked."""
    page = 4
    full, win, snaps = PageAllocator(16), PageAllocator(16), PageAllocator(4)
    cache = PrefixCache(page, full, windows=((win, 8),), snaps=snaps)
    prompt = np.arange(43, dtype=np.int32)
    fp = [full.alloc() for _ in range(11)]
    for committed in (12, 12, 14, 27, 27, 35):
        added = cache.insert(prompt[:committed], fp)
        assert full.refs[:11] == [2] * (committed // page) \
            + [1] * (11 - committed // page)
    assert added == 2 and cache.inserted_pages == 8
    assert cache.probe(prompt) == 32
    # the blocks are there, and not restorable: no window page, no state
    pages, matched, snap, lost = cache.lookup_all(prompt)
    assert (pages, matched, snap, lost) == ([[], []], 0, None, 0)
    assert full.refs[:8] == [2] * 8
    # the prompt's last insert: the window pages the slot still holds
    # (blocks 6..9) and the snapshot at the end of block 8
    wp = [win.alloc() for _ in range(4)]
    entry = snaps.alloc()
    assert cache.insert(prompt, fp, [(6, wp)], snaps=[(8, entry)]) == 2
    chain = _chain(cache)
    assert [n.page for n in chain] == fp[:10]
    assert [n.more[0] for n in chain] == [None] * 6 + wp
    assert [n.snap for n in chain] == [None] * 7 + [entry] + [None] * 2
    assert full.refs[:10] == [2] * 10 and win.refs[:4] == [2] * 4
    pages, matched, snap, lost = cache.lookup_all(prompt)
    assert matched == 32 and snap == entry and lost == 8
    assert pages == [fp[:8], wp[:2]]
    full.release(pages[0]), win.release(pages[1]), snaps.release([snap])
    cache.assert_consistent()
    # the slot ends: what is left is the trie's, one reference each
    full.release(fp), win.release(wp)
    assert full.refs[:11] == [1] * 10 + [0] and win.refs[:4] == [1] * 4
    assert snaps.refs[entry] == 1
    cache.clear()
    assert full.available == full.total and win.available == win.total
    assert snaps.available == snaps.total


# ---- the tiny Llama engine ---------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    state = paddle.get_rng_state()
    paddle.seed(20240806)
    cfg = LlamaConfig.debug(vocab=64, hidden=32, layers=2, heads=4,
                            kv_heads=2, inter=64, max_pos=128)
    model = LlamaForCausalLM(cfg)
    params = {k: jnp.asarray(v) for k, v in model.functional_state().items()}
    paddle.set_rng_state(state)
    return cfg, params


BUDGET, PAGE = 16, 8


def _engine(tiny_model, **kw):
    cfg, params = tiny_model
    kw.setdefault("enable_prefix_cache", True)
    return ContinuousBatchingEngine(
        cfg, params, max_slots=3, num_pages=49, page_size=PAGE,
        max_seq_len=128, prefill_token_budget=BUDGET, **kw)


def _drain(eng):
    while eng.queue or eng.active.any():
        eng.step()
        eng.assert_balanced()
    return {f.rid: f.tokens.tolist() for f in eng.run()}


def _late_markers(monkeypatch):
    """The ``serving.late_hit`` markers the engine leaves, by their
    arguments."""
    from paddle_tpu.inference import serving

    seen, event = [], serving.RecordEvent

    def spy(name, **kw):
        if name == "serving.late_hit":
            seen.append(kw)
        return event(name, **kw)

    monkeypatch.setattr(serving, "RecordEvent", spy)
    return seen


@pytest.mark.parametrize("sampling", [{}, {"temperature": 0.8, "seed": 42}],
                         ids=["greedy", "seeded_temperature"])
def test_a_re_ask_behind_its_documents_prefill_is_served_from_its_pages(
        tiny_model, sampling, monkeypatch):
    """Prompt A of six chunks and four tokens and the same prompt B,
    admitted before A has committed anything: B matches nothing at its
    admission, waits behind A and is matched again when its first chunk
    is packed, beside A's last four tokens: it finds what A had
    COMMITTED by then (all but the chunk in flight and those four
    tokens) and prefills a chunk and a page at most; its tokens are
    those of B served with the prefix cache off."""
    prompt = np.random.default_rng(3).integers(1, 64, 100).astype(np.int32)
    cold = _engine(tiny_model, enable_prefix_cache=False)
    for _ in range(2):
        cold.add_request(prompt, max_new_tokens=6, **sampling)
    want = _drain(cold)
    cold.shutdown()

    markers = _late_markers(monkeypatch)
    eng = _engine(tiny_model)
    a = eng.add_request(prompt, max_new_tokens=6, **sampling)
    b = eng.add_request(prompt, max_new_tokens=6, **sampling)
    got = _drain(eng)
    assert got == {a: want[0], b: want[1]}
    stats = eng.serving_stats()
    assert stats["prefill"][a]["cached_tokens"] == 0
    cached = stats["prefill"][b]["cached_tokens"]
    assert len(prompt) - BUDGET - PAGE <= cached < len(prompt)
    assert stats["prefill"][b]["prefilled"] == len(prompt) - cached
    cache = stats["prefix_cache"]
    assert (cache["late_hits"], cache["late_hit_tokens"]) == (1, cached)
    assert (cache["hits"], cache["hit_tokens"]) == (1, cached)
    assert [(m["rid"], m["cached_tokens"]) for m in markers] == [(b, cached)]
    assert markers[0]["waited_us"] > 0
    eng.assert_balanced()
    eng.prefix_cache.assert_consistent()
    eng.shutdown()


def test_a_late_hit_adds_to_what_the_admission_matched(tiny_model):
    """B is admitted when A has committed three chunks: the admission
    maps those pages, the second look-up the chunks A committed while B
    waited, and B gives the first hit's references back."""
    prompt = np.random.default_rng(4).integers(1, 64, 100).astype(np.int32)
    eng = _engine(tiny_model)
    a = eng.add_request(prompt, max_new_tokens=4)
    for _ in range(3):
        eng.step()
    first = eng.prefix_cache.probe(prompt)
    assert first == 3 * BUDGET          # three chunks read, one in flight
    b = eng.add_request(prompt, max_new_tokens=4)
    got = _drain(eng)
    assert got[a] == got[b]
    cache = eng.serving_stats()["prefix_cache"]
    cached = eng.prefill_stats[b]["cached_tokens"]
    assert cached >= len(prompt) - BUDGET - PAGE
    assert (cache["hits"], cache["hit_tokens"]) == (1, cached)
    assert (cache["late_hits"], cache["late_hit_tokens"]) \
        == (1, cached - first)
    eng.shutdown()


def test_a_prompt_canceled_mid_prefill_leaves_its_committed_blocks(
        tiny_model):
    """A canceled with three chunks committed and a fourth in flight:
    the three chunks' blocks stay in the trie, a re-ask is served from
    them (the tokens of a cold engine), and once its references are back
    every block is evictable."""
    prompt = np.random.default_rng(5).integers(1, 64, 100).astype(np.int32)
    cold = _engine(tiny_model, enable_prefix_cache=False)
    cold.add_request(prompt, max_new_tokens=5)
    want = _drain(cold)[0]
    cold.shutdown()

    eng = _engine(tiny_model)
    a = eng.add_request(prompt, max_new_tokens=5)
    for _ in range(3):
        eng.step()
    assert eng._flight is not None and eng.prefill_stats[a]["prefilled"] == 48
    assert eng.cancel(a)
    eng.assert_balanced()
    pc = eng.prefix_cache
    blocks = 48 // PAGE
    assert pc.probe(prompt) == 48 and pc.cached_pages == blocks
    # the trie's reference alone: the slot's went back with the cancel
    assert sorted(eng.alloc.refs) == [0] * (48 - blocks) + [1] * blocks
    b = eng.add_request(prompt, max_new_tokens=5)
    got = _drain(eng)
    assert got == {b: want} and not any(f.rid == a for f in eng.finished)
    assert eng.prefill_stats[b]["cached_tokens"] == 48
    assert eng.serving_stats()["prefix_cache"]["late_hits"] == 0
    cached = pc.cached_pages
    assert cached == (len(prompt) // PAGE)
    assert pc.evict(cached) == cached and pc.cached_pages == 0
    eng.shutdown()


def test_a_slot_admitted_and_packed_in_one_call_is_looked_up_once(
        tiny_model):
    """The second look-up is for a request that waited: one admitted and
    packed in the same call costs one ``lookup_all`` and no ``probe``; one
    that waited behind another prompt costs one ``probe`` more, and a
    ``lookup_all`` more only if the trie has grown past its match."""
    rng = np.random.default_rng(6)
    eng = _engine(tiny_model)
    pc = eng.prefix_cache
    probes, probe = [], pc.probe

    def spy(prompt):
        probes.append(len(prompt))
        return probe(prompt)

    pc.probe = spy
    eng.add_request(rng.integers(1, 64, 40).astype(np.int32),
                    max_new_tokens=3)
    _drain(eng)
    assert (pc.lookups, probes) == (1, [])
    # two prompts that share nothing: the second waits behind the first,
    # is probed once when its first chunk is packed and not looked up
    eng.add_request(rng.integers(1, 64, 50).astype(np.int32),
                    max_new_tokens=3)
    eng.step()
    eng.add_request(rng.integers(1, 64, 30).astype(np.int32),
                    max_new_tokens=3)
    _drain(eng)
    assert (pc.lookups, probes) == (3, [30])
    assert eng.serving_stats()["prefix_cache"]["late_hits"] == 0
    assert not eng.unlaunched
    eng.shutdown()


def test_a_prefill_only_slot_late_hits_and_hands_off_the_same_pages(
        tiny_model):
    """A ``prefill_only`` slot is packed like any other, so it is matched
    again like any other: the handoff of a re-ask that late-hit carries
    the K/V and the first token of its document's first ask."""
    prompt = np.random.default_rng(8).integers(1, 64, 84).astype(np.int32)
    eng = _engine(tiny_model, prefill_only=True)
    eng.add_request(prompt, max_new_tokens=4)
    eng.step()
    eng.add_request(prompt, max_new_tokens=4)
    while len(eng.handoff_ready) < 2:
        eng.step()
        eng.assert_balanced()
    assert eng.serving_stats()["prefix_cache"]["late_hits"] == 1
    (sa, ia), (sb, ib) = sorted(eng.handoff_ready.items(),
                                key=lambda kv: kv[1]["rid"])
    assert eng.prefill_stats[ib["rid"]]["cached_tokens"] \
        >= len(prompt) - BUDGET - PAGE
    assert ia["first_token"] == ib["first_token"]
    tree_a, _ = eng.export_handoff(sa)
    tree_b, _ = eng.export_handoff(sb)
    for name in ("k", "v"):
        np.testing.assert_array_equal(tree_a[name], tree_b[name])
    eng.release_handoff(sa), eng.release_handoff(sb)
    eng.shutdown()
