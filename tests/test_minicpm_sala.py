"""MiniCPM-SALA on the serving path, at a small size on the CPU in
float32: InfLLM-V2 block-sparse attention over a paged K/V with a cache
of compressed keys (a THIRD pool a page) and Lightning linear-attention
layers whose state lives in the engine's state pool, through the
engine's ONE step against the plain reference
``benchmarks/reference/minicpm_sala_ref.py``, which shares no code with
the program; the two kernels and the generalised scan (interpret mode)
against plain ``jnp`` oracles; the selection's rules one by one."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import minicpm_sala_ref as ref
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import generation
from paddle_tpu.models.minicpm_sala import MiniCPMSALAConfig, lightning_decay
from paddle_tpu.ops.pallas import block_sparse_attention as bsa
from paddle_tpu.ops.pallas.ssd_scan import (mamba2_ssd_scan, ssd_max_units,
                                            ssd_scan_reference)

# a page is 2 blocks of 4 tokens and 4 compressed keys (one every 2
# tokens, each the mean of 4); a chunk of 9 rows ends inside a
# compressed key's 4 tokens and inside a page; rows select once their
# context passes 32 tokens
PAGE, BUDGET, SLOTS, SEQ, VOCAB = 8, 9, 3, 96, 96


def draw(cfg, seed=0):
    """Seeded leaves: gains near 1, matrices N(0, 0.2), the queries' and
    keys' projections N(0, 0.5) so that the selection and the softmax
    are far from uniform."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in cfg.leaf_shapes().items():
        if len(shape) == 1:
            v = 1.0 + 0.1 * rng.normal(size=shape)
        else:
            wide = "q_proj" in name or "k_proj" in name
            v = rng.normal(size=shape) * (0.5 if wide else 0.2)
        out[name] = jnp.asarray(v, jnp.float32)
    return out


def ref_cfg(cfg):
    """The configuration as the benchmark's file states it."""
    d = dataclasses.asdict(cfg)
    d["published"] = {"num_hidden_layers": cfg.num_hidden_layers}
    d["layers_run"] = list(cfg.layers_run or (0, cfg.num_hidden_layers))
    d["sparse_config"] = {k: d[k] for k in (
        "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
        "window_size", "dense_len")}
    return d


@pytest.fixture(scope="module")
def model():
    cfg = MiniCPMSALAConfig.debug()                  # S L L S
    return cfg, draw(cfg)


def engine(model, **kw):
    cfg, params = model
    opts = dict(max_slots=SLOTS, num_pages=64, page_size=PAGE,
                max_seq_len=SEQ, prefill_token_budget=BUDGET,
                enable_prefix_cache=True, state_snapshots=4)
    opts.update(kw)
    return ContinuousBatchingEngine(cfg, params, **opts)


def serve(eng, prompts, max_new=6):
    """Run ``prompts`` to the end; ``{rid: (prompt, tokens, {position:
    logits row})}``."""
    rows = {eng.add_request(p, max_new_tokens=max_new): (p, {})
            for p in prompts}
    while eng.queue or eng.active.any():
        eng.step()
        for (rid, pos), row in zip(*(eng.last_logits or ((), ()))):
            if rid in rows:
                rows[rid][1][pos] = row
    done = {f.rid: f.tokens for f in eng.finished}
    return {rid: (p, done[rid], got) for rid, (p, got) in rows.items()}


def worst_error(model, served, **control):
    """Largest error of an engine's logits row against the reference's
    ONE full forward over prompt + served tokens, relative to the row's
    largest logit."""
    cfg, params = model
    worst = 0.0
    for prompt, tokens, rows in served.values():
        seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        want = np.asarray(ref.forward(params, jnp.asarray(seq), ref_cfg(cfg),
                                      q_block=16, **control))
        assert len(rows) >= len(tokens)
        for pos, row in rows.items():
            worst = max(worst, float(np.abs(row - want[pos]).max()
                                     / np.abs(want[pos]).max()))
    return worst


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(3)
    pre = rng.integers(0, VOCAB, 43)
    return pre, [np.concatenate([pre, rng.integers(0, VOCAB, n)])
                 for n in (5, 17, 30)]


# float32 on both sides, the same sums in another order: 1e-4 of a row's
# largest logit is a hundred times what the runs show (4e-7) and a
# hundredth of what bf16 anywhere, or one block selected otherwise, would
TOL = 1e-4


def test_chunked_prefill_and_decode_match_the_reference(model, prompts):
    """Prompts of 48, 60 and 73 tokens in chunks of at most 9 beside each
    other's decode rows, then decode through pages, compressed keys and
    state: every sequence crosses ``dense_len`` (32), chunk ends inside a
    compressed key's 4 tokens, and page ends."""
    eng = engine(model, enable_prefix_cache=False, state_snapshots=0)
    served = serve(eng, prompts[1])
    assert worst_error(model, served) < TOL
    st = eng.serving_stats()["steps"]
    assert st["state_rows"] == st["rows"] == st["sparse_rows"] + st["dense_rows"]
    assert st["sparse_rows"] > 0 and st["dense_rows"] > 0
    kvh, topk, block = 2, 4, 4
    assert st["sel_blocks"] == st["sparse_rows"] * kvh * topk
    assert st["sel_kv_tokens_read"] == st["sel_blocks"] * block
    assert st["ckey_ctx"] >= st["sparse_rows"] * (32 // 2 - 1)
    assert st["state_slots"] >= st["steps"] - 1
    eng.shutdown()


@pytest.mark.parametrize("control", ["dense_all", "no_forced", "no_decay",
                                     "no_gate"])
def test_the_reference_without_a_mechanism_is_far_from_the_engine(
        model, prompts, control):
    """What the benchmark's controls leave out moves the logits by far
    more than the tolerance: the comparison sees each mechanism."""
    eng = engine(model, enable_prefix_cache=False, state_snapshots=0)
    served = serve(eng, prompts[1][2:])
    assert worst_error(model, served, **{control: True}) > 50 * TOL
    eng.shutdown()


def test_a_restored_request_equals_the_same_request_prefilled_whole(
        model, prompts):
    pre, (p1, p2, p3) = prompts
    cold = engine(model, enable_prefix_cache=False, state_snapshots=0)
    want = {tuple(p): t for p, t, _ in serve(cold, [p1, p2, p3]).values()}
    eng = engine(model)
    serve(eng, [p1])                    # leaves pages and snapshots
    warm = serve(eng, [p2, p3, p1])
    assert worst_error(model, warm) < TOL
    for p, tokens, _ in warm.values():
        assert np.array_equal(tokens, want[tuple(p)])
    stats = eng.serving_stats()["prefill"]
    # 43 shared tokens: 5 whole pages, the pages of K, V AND compressed
    # keys shared by their id; the snapshot at the page grid's 40
    assert [stats[r]["state_restored_tokens"] for r in (1, 2)] == [40, 40]
    assert [stats[r]["cached_tokens"] for r in (1, 2)] == [40, 40]
    assert stats[3]["prefilled"] == len(p1) - stats[3]["cached_tokens"]
    eng.assert_balanced()
    eng.shutdown()


@pytest.mark.parametrize("warm", [False, True], ids=["whole", "restored"])
def test_the_state_a_prompt_leaves_is_the_references(model, prompts, warm):
    """A request of ONE token ends with its prompt's state in its entry:
    the reference's ``S`` after the same tokens (``[H, key, value]``
    there, ``[H, value, key]`` in the pool), every layer and head."""
    cfg, params = model
    pre, (p1, p2, _) = prompts
    eng = engine(model)
    if warm:
        serve(eng, [p1])
    rid = eng.add_request(p2, max_new_tokens=1)
    while eng.queue or eng.active.any():
        eng.step()
    stats = eng.prefill_stats[rid]
    assert stats["state_restored_tokens"] == (40 if warm else 0)
    got = np.stack([np.asarray(pool[stats["state_entry"]])
                    for pool in eng.state[0]]).transpose(0, 1, 3, 2)
    kept = []
    ref.forward(params, jnp.asarray(p2.astype(np.int32)), ref_cfg(cfg),
                q_block=16, state_after=len(p2), states=kept)
    want = np.stack([np.asarray(k) for k in kept])
    assert got.shape == want.shape == (2, 4, 8, 8)
    every = np.tile(np.arange(4), (2, 1))
    assert ref.state_errors(got, want, every).max() < 1e-5
    # the slow heads are the last: the slopes fall with the head
    assert ref.slow_heads(ref_cfg(cfg), share=4).tolist() == [[3], [3]]
    assert np.allclose(ref.decay(ref_cfg(cfg), 1),
                       np.asarray(lightning_decay(cfg, 1)))
    # a restore from zeros is far from it
    kept = []
    ref.forward(params, jnp.asarray(p2.astype(np.int32)), ref_cfg(cfg),
                q_block=16, state_after=len(p2), states=kept,
                zero_state_at=37)
    assert ref.state_errors(np.stack(kept), want, every).min() > 1e-3
    eng.shutdown()


def test_the_reference_hands_on_what_a_history_leaves(model, prompts):
    """``keep_prefix`` and ``prefix``: the turn and the answer computed
    behind what the history's pass left are the whole pass's numbers
    (so the benchmark's three requests of one session cost one pass of
    its 65,536 tokens)."""
    cfg, params = model
    _, (_, _, p3) = prompts
    ids = jnp.asarray(p3.astype(np.int32))
    kept, kept2 = [], []
    whole, left = ref.forward(params, ids, ref_cfg(cfg), q_block=16,
                              state_after=len(p3), states=kept,
                              keep_prefix=43)
    assert left["n"] == 43 and left[0][0].shape == (43, 2, 8)
    rest = ref.forward(params, ids[43:], ref_cfg(cfg), q_block=16,
                       state_after=len(p3), states=kept2, prefix=left)
    assert float(jnp.abs(rest - whole[43:]).max()) < 1e-5
    assert float(jnp.abs(jnp.stack(kept) - jnp.stack(kept2)).max()) < 1e-5
    # a restore from zeros at the history's end: the prefix's state dropped
    zeroed = ref.forward(params, ids[43:], ref_cfg(cfg), q_block=16,
                         prefix=left, zero_state_at=43)
    want = ref.forward(params, ids, ref_cfg(cfg), q_block=16,
                       zero_state_at=43)
    assert float(jnp.abs(zeroed - want[43:]).max()) < 1e-5
    assert float(jnp.abs(zeroed - whole[43:]).max()) > 1e-3


def test_a_long_prompt_ends_with_a_snapshot_at_its_end(model):
    """A prompt that passes more chunk ends than there are snapshot
    entries gives up its own shallowest pending snapshot for the deeper
    one, and never more than half the entries: the session that comes
    back restores the END of its history, and another session's
    snapshot in the cache is not drained for it."""
    rng = np.random.default_rng(11)
    other, long = rng.integers(0, VOCAB, 17), rng.integers(0, VOCAB, 80)
    eng = engine(model, state_snapshots=4)
    serve(eng, [other], max_new=2)
    assert eng.prefix_cache.snapshots_live == 1     # at 8
    serve(eng, [long], max_new=2)           # chunk ends at 8, 16, .. 80
    pc = eng.prefix_cache
    # its last three (two pending and the one in flight when the next
    # was packed), at 64, 72 and 80, beside the other session's
    assert pc.snapshots_live == 4 and pc.evicted_snapshots == 0
    again = serve(eng, [np.concatenate([long, [1, 2, 3]])], max_new=2)
    (rid,) = again
    st = eng.serving_stats()["prefill"][rid]
    assert st["state_restored_tokens"] == st["cached_tokens"] == 80
    assert worst_error(model, again) < TOL
    eng.assert_balanced()
    eng.shutdown()


def test_cancel_mid_prefill_gives_back_what_the_slot_held(model, prompts):
    _, (p1, p2, p3) = prompts
    eng = engine(model)
    serve(eng, [p1])
    rid = eng.add_request(p3, max_new_tokens=4)
    eng.add_request(p2, max_new_tokens=4)
    eng.step()
    eng.assert_balanced()
    assert eng.cancel(rid)
    eng.assert_balanced()
    eng.run()
    eng.assert_balanced()
    eng.shutdown()


@pytest.mark.parametrize("what, kw", [
    ("draft model", dict(speculative_k=2, draft_params={})),
    ("int8 cache", dict(cache_dtype=jnp.int8)),
    ("host tier", dict(host_tier_pages=4)),
    ("prefill_only", dict(prefill_only=True)),
    ("state_snapshots", dict(enable_prefix_cache=False)),
])
def test_what_three_pools_and_a_state_cannot_do_refuses_at_construction(
        model, what, kw):
    with pytest.raises(ValueError, match=what):
        engine(model, **kw)


def test_a_further_pool_alone_refuses_too(model):
    """A layout with a third pool a page and NO state is refused the
    same things: what moves, mirrors or calibrates pages knows of K and
    V alone."""
    cfg, params = model

    @dataclasses.dataclass(frozen=True)
    class NoState(MiniCPMSALAConfig):
        def paged_layout(self):
            return dataclasses.replace(super().paged_layout(), state=(),
                                       state_layers=0)

    with pytest.raises(ValueError, match="further pools a page"):
        ContinuousBatchingEngine(NoState(**dataclasses.asdict(cfg)), params,
                                 max_slots=2, num_pages=8,
                                 page_size=PAGE, max_seq_len=SEQ,
                                 host_tier_pages=2, enable_prefix_cache=True)


def test_the_handoff_and_generate_are_refused(model):
    eng = engine(model)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.adopt_request({}, {"seq_len": 4, "first_token": 1,
                               "page_size": PAGE}, 4)
    eng.handoff_ready[0] = {"seq_len": 4}
    with pytest.raises(ValueError, match="recurrent state"):
        eng.export_handoff(0)
    eng.handoff_ready.clear()

    class Model:
        cfg = model[0]

    with pytest.raises(NotImplementedError, match="recurrent state"):
        generation.generate(Model(), np.zeros((1, 4), np.int32))
    eng.shutdown()


def test_published_widths_add_up_to_the_bytes_reckoned():
    """The published configuration cut to entries 9 to 20 of
    ``mixer_types``: 3 ``minicpm4`` layers of 253.8 M parameters, 9
    ``lightning-attn`` layers of 285.2 M, embedding and head 601.7 M:
    3.930 B, 7.86 GB in bf16; a state of 18.9 MB a slot; a page of 128
    tokens 128 KiB of K and V and 4 KiB of compressed keys a layer."""
    cfg = MiniCPMSALAConfig(layers_run=(9, 21))
    assert [cfg.mixer_types[l] for l in cfg.layers] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 2
        + ["lightning-attn"] * 3)
    assert sum(m == "minicpm4" for m in cfg.mixer_types) == 8
    shapes = cfg.leaf_shapes()

    def count(prefix):
        return sum(int(np.prod(s)) for k, s in shapes.items()
                   if k.startswith(prefix))

    assert round(count("model.layers.9.") / 1e6, 1) == 253.8
    assert round(count("model.layers.10.") / 1e6, 1) == 285.2
    assert round((count("model.embed") + count("lm_head")) / 1e6, 1) == 601.7
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert round(total / 1e9, 3) == 3.930 and round(2 * total / 1e9, 2) == 7.86
    layout = cfg.paged_layout()
    ((shape, dtype),) = layout.state
    assert layout.state_layers == 9 and dtype == "float32"
    assert round(9 * 4 * int(np.prod(shape)) / 1e6, 1) == 18.9
    assert layout.kinds[0].layers == (9, 16, 17)
    k_shape, v_shape = layout.pool_shapes(4096, 128)
    (ckeys,) = layout.more_pools
    assert 2 * 2 * int(np.prod(k_shape[1:])) == 128 * 1024
    assert 2 * int(np.prod(ckeys(128))) == 4 * 1024
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    with pytest.raises(ValueError, match="topk x block_size"):
        MiniCPMSALAConfig(dense_len=2048)
    with pytest.raises(ValueError, match="mixer_types"):
        MiniCPMSALAConfig(num_hidden_layers=12)


# ---- the selection's rules, one by one -----------------------------------

def select(scores, lens, **kw):
    opts = dict(stride=2, block=4, topk=4, init_blocks=1, window=6)
    opts.update(kw)
    return np.asarray(bsa.select_blocks(jnp.asarray(scores, jnp.float32),
                                        jnp.asarray(lens, jnp.int32), **opts))


def flat_scores(by_block, per=2, kvh=1):
    """Scores by flat index that give block ``b`` the score
    ``by_block[b]``: the block's first compressed key (flat ``per b +
    1``) holds it, the others nothing."""
    s = np.zeros((1, kvh, per * len(by_block) + per), np.float32)
    s[0, :, per * np.arange(len(by_block)) + 1] = np.asarray(
        by_block, np.float32)[:, None]
    return s


def test_forced_blocks_count_towards_topk():
    """A row of context 38 (own block 9): block 0 and the blocks that
    overlap its last 6 tokens (8 and 9) are forced, so ONE block is
    left to the scores, and the selection comes back ascending with the
    row's own block last."""
    by_block = [0, .1, .9, .2, .3, .8, .1, .1, 0, 0, .99, .99]
    assert select(flat_scores(by_block), [38]).tolist() == [[[0, 2, 8, 9]]]
    # without a window or a first block, the four best below the future
    assert select(flat_scores(by_block), [38], init_blocks=0,
                  window=1).tolist() == [[[2, 4, 5, 9]]]


def test_a_tie_goes_to_the_lower_block():
    by_block = [0, .5, .5, .5, .5, .5, .5, .5, 0, 0, 0, 0]
    assert select(flat_scores(by_block), [38]).tolist() == [[[0, 1, 8, 9]]]
    assert select(flat_scores(by_block), [38],
                  topk=6).tolist() == [[[0, 1, 2, 3, 8, 9]]]


def test_a_block_scores_its_best_overlapping_compressed_key():
    """Block b holds tokens [4 b, 4 b + 4): compressed keys j = 2 b - 1,
    2 b, 2 b + 1 (flat 2 b .. 2 b + 2) overlap it, so a key that
    straddles two blocks lifts both."""
    s = np.zeros((1, 1, 26), np.float32)
    s[0, 0, 6] = 1.0                    # j = 5: tokens 10..13, blocks 2 and 3
    s[0, 0, 11] = 0.5                   # j = 10: tokens 20..23, block 5 alone
    got = select(s, [45], topk=6, window=1)[0, 0].tolist()
    assert got == [0, 1, 2, 3, 5, 11]   # 1: the lowest of the zeros


def test_a_group_has_one_selection_and_groups_differ(model):
    """The heads of a K/V group share one selection (their softmaxes
    are SUMMED before a block is scored); two groups have their own."""
    a = flat_scores([0, .9, .1, .1, .1, .1, 0, 0, 0, 0, 0, 0])
    b = flat_scores([0, .1, .1, .1, .1, .9, 0, 0, 0, 0, 0, 0])
    got = select(np.concatenate([a, b], 1), [38])
    assert got.shape == (1, 2, 4)
    assert got[0].tolist() == [[0, 1, 8, 9], [0, 5, 8, 9]]


# ---- the kernels (interpret mode) against plain jnp ----------------------

def paged_case(runs, rows, seed=0, kvh=2, hpg=4, d=128, page=16, stride=2,
               dtype=jnp.float32):
    """Packed rows of ``runs`` ``(slot, rows, first position)`` over a
    paged K/V of ``page`` tokens a page, the compressed keys written as
    the step writes them, a token at a time."""
    rng = np.random.default_rng(seed)
    slots, per_seq, pages = 4, 6, 32
    table = rng.permutation(pages - 1)[:slots * per_seq].reshape(
        slots, per_seq).astype(np.int32)
    k_pool = jnp.asarray(rng.normal(size=(pages, kvh, page, d)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(pages, kvh, page, d)), dtype)
    c_pool = jnp.zeros((pages, page // stride, kvh * d), dtype)
    every = np.arange(per_seq * page)
    for s in range(slots):
        c_pool = bsa.write_compressed_keys(
            c_pool, k_pool, jnp.asarray(every + 1), jnp.full(len(every), s),
            jnp.asarray(table), stride=stride, max_final=len(every))
    slot = np.full(rows, -1, np.int32)
    lens = np.zeros(rows, np.int32)
    r = 0
    for s, n, first in runs:
        slot[r:r + n] = s
        lens[r:r + n] = first + 1 + np.arange(n)
        r += n
    q = jnp.asarray(rng.normal(size=(rows, kvh * hpg, d)) * d ** -0.5, dtype)
    return q, k_pool, v_pool, c_pool, jnp.asarray(slot), jnp.asarray(lens), \
        jnp.asarray(table)


LAYOUTS = {
    "decode": ([(0, 1, 70), (2, 1, 95), (1, 1, 40)], 8),
    "chunk": ([(3, 13, 50)], 16),
    "mixed": ([(0, 1, 90), (1, 1, 33), (2, 11, 60), (3, 3, 80)], 24),
    "short": ([(0, 1, 5), (1, 9, 20)], 16),
}


def test_compressed_keys_are_the_means_and_live_in_their_last_tokens_page():
    q, k_pool, v_pool, c_pool, slot, lens, table = paged_case([(0, 1, 70)], 8)
    page, stride = 16, 2
    keys = np.asarray(k_pool)[np.asarray(table)[1]].transpose(1, 0, 2, 3)
    keys = keys.reshape(2, -1, 128)                     # [kvh, tokens, d]
    ck = np.asarray(bsa.gather_compressed(c_pool, table))[1]
    assert ck.shape == (128, 256)                       # 48 flat, padded
    for j in (0, 6, 7, 8, 30, 46):                      # 7: straddles a page
        want = keys[:, stride * j:stride * j + 2 * stride].mean(1)
        assert np.allclose(ck[j + 1].reshape(2, 128), want, atol=1e-6), j
    assert int(bsa.compressed_count(jnp.asarray(71), stride)) == 34


@pytest.mark.parametrize("runs, rows", list(LAYOUTS.values()), ids=list(LAYOUTS))
@pytest.mark.parametrize("tile", [1, 8])
def test_block_scores_kernel_matches_plain_jnp(runs, rows, tile):
    q, k_pool, v_pool, c_pool, slot, lens, table = paged_case(runs, rows)
    ck = bsa.gather_compressed(c_pool, table)
    nck = jnp.where(lens > 32, bsa.compressed_count(lens, 2), 0)
    want = bsa.block_scores_reference(q, ck, slot, nck)
    got = bsa.infllm_block_scores(q, ck, slot, nck, tile_rows=tile,
                                  max_units=rows, interpret=True)
    assert got.shape == want.shape == (rows, 2, 128)
    assert float(jnp.abs(got - want).max()) < 1e-5
    live = np.asarray(nck) > 0
    # a group's scores sum to its heads: each head's softmax sums to 1
    assert np.allclose(np.asarray(got)[live].sum(-1), 4.0, atol=1e-4)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("runs, rows", list(LAYOUTS.values()), ids=list(LAYOUTS))
def test_block_sparse_kernel_matches_plain_jnp_and_dense_softmax(runs, rows):
    q, k_pool, v_pool, c_pool, slot, lens, table = paged_case(runs, rows)
    ck = bsa.gather_compressed(c_pool, table)
    live = lens > 32
    nck = jnp.where(live, bsa.compressed_count(lens, 2), 0)
    scores = bsa.block_scores_reference(q, ck, slot, nck)
    sel = bsa.select_blocks(scores, lens, stride=2, block=4, topk=8,
                            init_blocks=1, window=6)
    args = (q, k_pool, v_pool, sel, lens, slot, table, live)
    want = bsa.block_sparse_attention_reference(*args, block=4)
    got = bsa.block_sparse_paged_attention(*args, block=4, tile_rows=8,
                                           interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert not np.asarray(got)[~np.asarray(live)].any()
    # against a softmax over the row's whole context under the mask of
    # its selected blocks
    keys = np.asarray(k_pool)[np.asarray(table)].transpose(0, 2, 1, 3, 4)
    vals = np.asarray(v_pool)[np.asarray(table)].transpose(0, 2, 1, 3, 4)
    for t in np.nonzero(np.asarray(live))[0]:
        s, n = int(slot[t]), int(lens[t])
        for g in range(2):
            k = keys[s, g].reshape(-1, 128)[:n]
            v = vals[s, g].reshape(-1, 128)[:n]
            picked = np.isin(np.arange(n) // 4, np.asarray(sel)[t, g])
            sc = np.asarray(q)[t, 4 * g:4 * g + 4] @ k.T
            sc = np.where(picked[None, :], sc, -np.inf)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out = (p / p.sum(-1, keepdims=True)) @ v
            assert np.allclose(np.asarray(got)[t, 4 * g:4 * g + 4], out,
                               atol=1e-5)


def scan_case(H, P, G, N, runs, rows, dtype, entries=7, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, H, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (rows, H)), jnp.float32)
    a = dt * -jnp.asarray(rng.uniform(1, 4, (H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(rows, G, N)), dtype)
    C = jnp.asarray(rng.normal(size=(rows, G, N)), dtype)
    pool = jnp.asarray(rng.normal(size=(entries, H, P, N)), jnp.float32)
    slot = np.full(rows, -1, np.int32)
    src = np.full(rows, entries - 1, np.int32)
    dst = np.full(rows, entries - 1, np.int32)
    lens = np.zeros(rows, np.int32)
    r = 0
    for s, n, start in runs:
        slot[r:r + n], src[r:r + n], dst[r:r + n] = s, start, s
        lens[r:r + n] = np.arange(1, n + 1) + 10
        r += n
    return (x, dt, a, B, C, pool), [jnp.asarray(v)
                                    for v in (slot, lens, src, dst)]


MIXED = ([(0, 1, 0), (1, 1, 1), (2, 11, -1), (3, 3, 4)], 24)


@pytest.mark.parametrize("shape, hb", [
    ((4, 8, 2, 16), None),          # a block is one group (Mamba-2)
    ((4, 8, 4, 16), 2),             # a key and a query a head, two a block
    ((4, 8, 4, 16), 4),
    ((4, 8, 2, 16), 4),             # two groups of two heads a block
    ((128, 64, 8, 128), None),      # Nemotron's: 8 groups of 16
    ((32, 128, 32, 128), 16),       # Lightning's: 32 heads, own B and C
], ids=["one_group", "two_heads", "four_heads", "two_groups", "nemotron",
        "lightning"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_the_one_scan_serves_shared_and_own_keys(shape, hb, dtype, tol):
    args, (slot, lens, src, dst) = scan_case(*shape, *MIXED, dtype)
    y0, p0 = ssd_scan_reference(*args, slot, src, dst)
    y1, p1 = mamba2_ssd_scan(*args, slot, lens, src, dst, tile_rows=8,
                             max_units=ssd_max_units(MIXED[1], 8, 4),
                             heads_per_step=hb, interpret=True)
    assert float(jnp.abs(y0 - y1).max() / jnp.abs(y0).max()) < tol
    assert float(jnp.abs(p0[:-1] - p1[:-1]).max() / jnp.abs(p0).max()) < tol


def test_the_scans_units_are_sized_by_the_slots():
    """A unit a slot and one more for each tile boundary: the pool's
    entries (snapshots, the trash entry) have no rows."""
    assert ssd_max_units(640, 128, 128) == 133
    assert ssd_max_units(640, 128, 96) == 101
    assert ssd_max_units(16, 8, 4) == 6


def test_a_snapshot_sessions_come_back_to_outlives_a_burst_of_prompts(model):
    """Snapshots nobody ever restored from go first: the one at the end
    of a history that turns restore from survives prompts that each leave
    snapshots inside their own suffix, however fresh those are."""
    rng = np.random.default_rng(13)
    history = rng.integers(0, VOCAB, 40)
    eng = engine(model, state_snapshots=3)
    serve(eng, [history], max_new=2)                    # leaves one at 40
    turn = serve(eng, [np.concatenate([history, [5, 6, 7]])], max_new=2)
    (rid,) = turn
    assert eng.serving_stats()["prefill"][rid]["state_restored_tokens"] == 40
    # three unrelated prompts, two chunk ends each: six fresher snapshots
    serve(eng, [rng.integers(0, VOCAB, 20) for _ in range(3)], max_new=2)
    assert eng.prefix_cache.evicted_snapshots > 0
    again = serve(eng, [np.concatenate([history, [9, 8]])], max_new=2)
    (rid,) = again
    assert eng.serving_stats()["prefill"][rid]["state_restored_tokens"] == 40
    eng.assert_balanced()
    eng.shutdown()
