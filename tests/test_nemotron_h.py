"""Nemotron-H on the serving path, at a small size on the CPU in float32:
Mamba-2 layers whose per-slot state lives in ONE pool beside the paged
K/V, snapshots of that state for prefix reuse, and the relu2 experts in
their latent, through the engine's ONE step against the plain reference
``benchmarks/reference/nemotron_h_ref.py``, which shares no code with the
program; and the scan kernel and the convolution kernel (interpret mode)
against the sequential scan and the convolution in XLA's terms."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h_ref as ref
from paddle_tpu.inference.paged_layout import STATE_COUNTS
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import generation
from paddle_tpu.models.nemotron_h import NemotronHConfig
from paddle_tpu.ops.pallas.causal_conv import (packed_causal_conv,
                                               packed_causal_conv_reference)
from paddle_tpu.ops.pallas.ssd_scan import (mamba2_ssd_scan, ssd_max_units,
                                            ssd_scan_reference)

PAGE, BUDGET, SLOTS, SEQ, VOCAB = 4, 8, 3, 64, 96
HELD = (4, 12)


def draw(cfg, seed=0, scale=0.3):
    """Seeded leaves: matrices N(0, scale), gains near 1, the mixer's
    scalars as Mamba-2 starts them (a state that neither dies in a token
    nor never decays)."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in cfg.leaf_shapes().items():
        if name.endswith("A_log"):
            v = np.log(rng.uniform(1, 16, shape))
        elif name.endswith("dt_bias"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), shape))
            v = dt + np.log(-np.expm1(-dt))
        elif name.endswith(".D"):
            v = np.ones(shape)
        elif len(shape) == 1 and not name.endswith(".bias"):
            v = 1.0 + 0.1 * rng.normal(size=shape)
        else:
            v = rng.normal(size=shape) * scale
        out[name] = jnp.asarray(v, jnp.float32)
    return out


def ref_cfg(cfg):
    """The configuration as the benchmark's file states it: the experts
    HELD under ``n_routed_experts`` (the tests pass ``held=``)."""
    return dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def model():
    cfg = NemotronHConfig.debug(experts_held=HELD)      # M E M * E
    return cfg, draw(cfg)


def engine(model, **kw):
    cfg, params = model
    opts = dict(max_slots=SLOTS, num_pages=48, page_size=PAGE,
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


def worst_error(model, served):
    """Largest error of an engine's logits row against the reference's
    full forward over prompt + served tokens, relative to the row's
    largest logit."""
    cfg, params = model
    worst = 0.0
    for prompt, tokens, rows in served.values():
        seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        want = np.asarray(ref.forward(params, jnp.asarray(seq), ref_cfg(cfg),
                                      held=HELD))
        assert len(rows) >= len(tokens)
        for pos, row in rows.items():
            worst = max(worst, float(np.abs(row - want[pos]).max()
                                     / np.abs(want[pos]).max()))
    return worst


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(3)
    pre = rng.integers(0, VOCAB, 17)
    return pre, [np.concatenate([pre, rng.integers(0, VOCAB, n)])
                 for n in (5, 9, 20)]


# float32 on both sides, the same sums in another order: 1e-4 of a row's
# largest logit is a hundred times what the runs show (1e-6) and a
# hundredth of what bf16 anywhere would
TOL = 1e-4


def test_chunked_prefill_and_decode_match_the_reference(model, prompts):
    """Prompts of 22, 26 and 37 tokens in chunks of at most 8 beside each
    other's decode rows, then decode through state and cache."""
    eng = engine(model, enable_prefix_cache=False, state_snapshots=0)
    served = serve(eng, prompts[1])
    assert worst_error(model, served) < TOL
    st = eng.serving_stats()["steps"]
    assert st["ssm_rows"] == st["rows"] and st["ssm_prefill_rows"] > 0
    assert st["ssm_state_slots"] >= st["steps"] - 1
    assert st["moe_rows_held"] < st["moe_rows_routed"]
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
    # 17 shared tokens: 4 whole pages, the chunk grid's snapshot at 16
    assert [stats[r]["state_restored_tokens"] for r in (1, 2)] == [16, 16]
    assert [stats[r]["cached_tokens"] for r in (1, 2)] == [16, 16]
    # the same prompt again: 5 pages match, the deepest snapshot is at 16
    assert stats[3]["state_restored_tokens"] == 16
    assert stats[3]["state_lost_tokens"] == 4
    assert stats[3]["prefilled"] == len(p1) - 16
    steps = eng.serving_stats()["steps"]
    assert steps["state_restored_tokens"] == 48
    assert steps["state_lost_tokens"] == 4
    assert steps["state_snapshots_taken"] == \
        eng.prefix_cache.stats()["snapshots_taken"] > 0
    eng.assert_balanced()
    eng.shutdown()


@pytest.mark.parametrize("warm", [False, True], ids=["whole", "restored"])
def test_the_state_a_prompt_leaves_is_the_references(model, prompts, warm):
    """A request of ONE token ends with its prompt's state in its entry
    (``prefill_stats``' ``state_entry``), untouched by any decode row:
    the reference's ``S`` after the same tokens, every state layer and
    head, prefilled whole or restored from a snapshot at 16."""
    cfg, params = model
    pre, (p1, p2, _) = prompts
    eng = engine(model)
    if warm:
        serve(eng, [p1])
    rid = eng.add_request(p2, max_new_tokens=1)
    while eng.queue or eng.active.any():
        eng.step()
    stats = eng.prefill_stats[rid]
    assert stats["state_restored_tokens"] == (16 if warm else 0)
    got = np.stack([np.asarray(pool[stats["state_entry"]])
                    for pool in eng.state[0]])
    kept = []
    ref.forward(params, jnp.asarray(p2.astype(np.int32)), ref_cfg(cfg),
                held=HELD, state_after=len(p2), states=kept)
    want = np.stack([np.asarray(k) for k in kept])
    assert got.shape == want.shape == (2, 4, 8, 16)
    every = np.tile(np.arange(4), (2, 1))
    assert ref.state_errors(got, want, every).max() < 1e-5      # 3e-7 read
    # the slow heads are those of the least decay at rest, a layer
    slow = ref.slow_heads(params, ref_cfg(cfg))
    assert slow.shape == (2, 1)
    rate = np.exp(params["model.layers.0.mixer.A_log"]) * np.log1p(
        np.exp(params["model.layers.0.mixer.dt_bias"]))
    assert slow[0, 0] == np.argmin(rate)
    # a state that lost a token's update is far from it
    kept = []
    ref.forward(params, jnp.asarray(p2[:-1].astype(np.int32)), ref_cfg(cfg),
                held=HELD, state_after=len(p2) - 1, states=kept)
    assert ref.state_errors(np.stack(kept), want, every).min() > 1e-3
    eng.shutdown()


def test_a_match_that_outruns_every_snapshot_is_prefilled_again(model,
                                                                 prompts):
    """Pages without a snapshot are worth nothing to a recurrent layer:
    with every snapshot evicted the hit is 0 tokens, all of it counted
    as lost, and the answer is the cold one."""
    _, (p1, _, _) = prompts
    eng = engine(model)
    first = serve(eng, [p1])
    assert eng.prefix_cache.evict_snapshots(99) == \
        eng.prefix_cache.stats()["snapshots_evicted"] > 0
    assert eng.snap_alloc.available == eng.snap_alloc.total
    again = serve(eng, [p1])
    (_, t0, _), (_, t1, _) = first[0], again[1]
    assert np.array_equal(t0, t1)
    st = eng.serving_stats()["prefill"][1]
    assert (st["cached_tokens"], st["state_restored_tokens"],
            st["state_lost_tokens"]) == (0, 0, 20)
    assert worst_error(model, again) < TOL
    eng.assert_balanced()
    eng.shutdown()


def test_snapshots_have_a_budget_and_an_lru_of_their_own(model):
    rng = np.random.default_rng(5)
    eng = engine(model, state_snapshots=2)
    ps = [rng.integers(0, VOCAB, 26) for _ in range(3)]
    serve(eng, ps)                      # 3 snapshots a prompt want 9 entries
    pc = eng.prefix_cache.stats()
    assert pc["snapshots_live"] <= 2 and pc["snapshots_evicted"] > 0
    assert pc["cached_pages"] == 18     # the pages stay
    eng.assert_balanced()
    # evicting pages takes their snapshots along, and nothing leaks
    eng.prefix_cache.evict(99)
    eng.assert_balanced()
    assert eng.prefix_cache.stats()["snapshots_live"] == 0
    eng.shutdown()


def test_slots_recycled_under_run_ahead_with_stale_rows(model):
    """Requests that end on ``eos_id`` leave a stale row in the launch
    in flight, which writes the slot's state entry AFTER the slot was
    freed: the next tenant starts from zeros or a snapshot and is not
    touched by it."""
    rng = np.random.default_rng(7)
    ps = [rng.integers(0, VOCAB, n) for n in (9, 13, 6, 11, 7, 10, 8)]
    cold = engine(model, enable_prefix_cache=False, state_snapshots=0,
                  max_slots=2)
    want = [t for _, t, _ in serve(cold, ps, max_new=8).values()]
    eos = int(want[0][2])               # the first request ends early
    eng = engine(model, max_slots=2, eos_id=eos)
    got = serve(eng, ps, max_new=8)
    st = eng.serving_stats()["steps"]
    assert st["stale_rows"] >= 1 and st["ahead"] >= st["steps"] - 3
    for (_, tokens, _), full in zip(got.values(), want):
        cut = list(full).index(eos) + 1 if eos in full else len(full)
        assert np.array_equal(tokens, full[:cut])
    eng.assert_balanced()
    eng.shutdown()


def test_cancel_mid_prefill_gives_back_what_the_slot_held(model, prompts):
    _, (p1, p2, p3) = prompts
    eng = engine(model)
    serve(eng, [p1])
    rid = eng.add_request(p3, max_new_tokens=4)
    eng.add_request(p2, max_new_tokens=4)
    eng.step()                          # restored, first chunks in flight
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
def test_what_cannot_carry_state_refuses_at_construction(model, what, kw):
    with pytest.raises(ValueError, match=what):
        engine(model, **kw)


def test_a_state_refuses_the_handoff_and_generate(model):
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


def test_the_routed_parts_of_all_shares_add_up_to_the_whole_layer():
    """The guide's share test: 8 chips hold 2 of 16 experts each; their
    routed parts, with the router, the latent projections and the shared
    expert counted once, add up to the uncut reference's E layer."""
    cfg = NemotronHConfig.debug()
    params = draw(cfg, seed=11)
    i = cfg.pattern.index("E")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(7, cfg.hidden_size)),
                    jnp.float32)
    lw = ref.layer_leaves(params, i)
    whole = ref.expert_layer(x, lw, ref_cfg(cfg), (0, 16))
    _, shared = ref.expert_layer(x, lw, ref_cfg(cfg), (0, 16), parts=True)
    total = jnp.zeros_like(x)
    pre = f"model.layers.{i}.mlp.experts."
    for r in range(8):
        lo, hi = 2 * r, 2 * r + 2
        share = dataclasses.replace(cfg, experts_held=(lo, hi))
        p = dict(params)
        for proj in ("up_proj", "down_proj"):
            p[pre + proj + ".weight"] = params[pre + proj + ".weight"][lo:hi]
        w = generation._Weights(share, p)
        total = total + generation._moe_ffn(w, i, x) - shared
        # and each share is the reference's own share
        want = ref.expert_layer(x, lw_of(lw, lo, hi), ref_cfg(cfg), (lo, hi))
        assert float(jnp.abs(generation._moe_ffn(w, i, x) - want).max()) \
            < 1e-4 * float(jnp.abs(whole).max())
    err = float(jnp.abs(total + shared - whole).max()
                / jnp.abs(whole).max())
    assert err < TOL


def lw_of(lw, lo, hi):
    return {k: (v[lo:hi] if k.startswith("mlp.experts.") else v)
            for k, v in lw.items()}


# ---- the scan kernel (interpret mode) against the sequential scan ----

def row_columns(runs, rows, entries):
    """``slot``, ``lens``, ``src``, ``dst`` of packed rows of ``runs``
    ``(slot, rows, entry to start from)``; the last entry is the trash."""
    slot = np.full(rows, -1, np.int32)
    src = np.full(rows, entries - 1, np.int32)
    dst = np.full(rows, entries - 1, np.int32)
    lens = np.zeros(rows, np.int32)
    r = 0
    for s, n, start in runs:
        slot[r:r + n], src[r:r + n], dst[r:r + n] = s, start, s
        lens[r:r + n] = np.arange(1, n + 1) + 10
        r += n
    return [jnp.asarray(v) for v in (slot, lens, src, dst)]


def scan_case(runs, rows, tile, dtype=jnp.float32, seed=0, entries=7):
    """Packed rows of ``runs`` ``(slot, rows, entry to start from)``."""
    H, P, G, N = 4, 8, 2, 16
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, H, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (rows, H)), jnp.float32)
    a = dt * -jnp.asarray(rng.uniform(1, 4, (H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(rows, G, N)), dtype)
    C = jnp.asarray(rng.normal(size=(rows, G, N)), dtype)
    pool = jnp.asarray(rng.normal(size=(entries, H, P, N)), jnp.float32)
    return (x, dt, a, B, C, pool), row_columns(runs, rows, entries)


#: packed rows as ``(runs of (slot, rows, entry to start from), rows)``,
#: in tiles of 8
ROW_LAYOUTS = {
    "decode": ([(0, 1, 0), (1, 1, -1), (2, 1, 5)], 16),
    "chunk": ([(0, 13, -1)], 16),                       # 2 tiles
    "restore": ([(2, 20, 5)], 24),                      # from a snapshot
    "mixed": ([(0, 1, 0), (1, 1, 1), (2, 11, -1), (3, 3, 4)], 24),
    "edges": ([(1, 1, 1), (0, 7, 0), (3, 9, 3), (2, 1, -1)], 24),
}


@pytest.mark.parametrize("runs, rows", list(ROW_LAYOUTS.values()),
                         ids=list(ROW_LAYOUTS))
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_scan_kernel_matches_the_sequential_scan(runs, rows, dtype, tol):
    """bf16 rows: the chunked form's matmul operands are bf16 (products
    of 8 bits, float32 sums), so a hundredth of the largest value."""
    args, (slot, lens, src, dst) = scan_case(runs, rows, 8, dtype)
    y0, p0 = ssd_scan_reference(*args, slot, src, dst)
    y1, p1 = mamba2_ssd_scan(*args, slot, lens, src, dst, tile_rows=8,
                             max_units=ssd_max_units(rows, 8, 4),
                             interpret=True)
    assert float(jnp.abs(y0 - y1).max() / jnp.abs(y0).max()) < tol
    # the trash entry (the last) is the padding units' to scribble on
    assert float(jnp.abs(p0[:-1] - p1[:-1]).max() / jnp.abs(p0).max()) < tol
    touched = {s for s, _, _ in runs}
    for e in range(p0.shape[0] - 1):
        if e not in touched:            # snapshots and idle slots: as found
            assert jnp.array_equal(p1[e], args[5][e])


#: the conv's own: runs shorter than its K - 1 = 3 rows (the tail left
#: mixes old entries and new rows: fresh, from a snapshot, from the
#: slot's own), and runs whose first rows end a tile (a row's window
#: holds the tail and rows of the tile before)
CONV_LAYOUTS = {
    **ROW_LAYOUTS,
    "short": ([(0, 2, 0), (1, 2, -1), (3, 2, 5), (2, 1, 2)], 16),
    "halo": ([(0, 5, 0), (1, 6, 1), (2, 4, 5), (3, 2, -1)], 24),
}


@pytest.mark.parametrize("name", list(CONV_LAYOUTS))
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-6),
                                        (jnp.bfloat16, 2.0 ** -8)],
                         ids=["f32", "bf16"])
def test_conv_kernel_matches_the_reference(name, dtype, tol):
    """Live rows' outputs to 1e-6 of the largest (float32 sums in
    another order) and to one bf16 ulp; the tails of touched entries
    bit for bit, every other entry as found."""
    runs, rows = CONV_LAYOUTS[name]
    K, C, entries = 4, 96, 7
    rng = np.random.default_rng(3)
    slot, _, src, dst = row_columns(runs, rows, entries)
    x = jnp.asarray(rng.normal(size=(rows, C)), dtype)
    w = jnp.asarray(rng.normal(size=(K, C)), dtype)
    b = jnp.asarray(rng.normal(size=(C,)), dtype)
    pool = jnp.asarray(rng.normal(size=(entries, K - 1, C)), dtype)
    y0, p0 = packed_causal_conv_reference(x, w, b, pool, slot, src, dst)
    y1, p1 = packed_causal_conv(x, w, b, pool, slot, src, dst, tile_rows=8,
                                interpret=True)
    assert y1.dtype == x.dtype and p1.dtype == pool.dtype
    live = np.asarray(slot) >= 0
    y0, y1 = (np.asarray(y, np.float32) for y in (y0, y1))
    assert np.isfinite(y1).all()
    if dtype == jnp.float32:
        assert np.abs(y0 - y1)[live].max() < tol * np.abs(y0[live]).max()
    else:
        assert (np.abs(y0 - y1)[live]
                <= tol * np.abs(y0[live]) + 1e-30).all()
    touched = {s for s, _, _ in runs}
    for e in range(entries - 1):        # the trash entry is free to differ
        want = p0[e] if e in touched else pool[e]
        assert jnp.array_equal(p1[e], want), e


def test_one_chunk_equals_three(model):
    """A prompt prefilled in one launch against the same in three: the
    state each leaves, and the rows' outputs."""
    (x, dt, a, B, C, pool), _ = scan_case([(0, 24, -1)], 24, 8, seed=4)
    slot = jnp.zeros(24, jnp.int32)
    lens = jnp.arange(1, 25, dtype=jnp.int32)
    dst = jnp.zeros(24, jnp.int32)
    kw = dict(tile_rows=8, interpret=True)
    y, p = mamba2_ssd_scan(x, dt, a, B, C, pool, slot, lens,
                           jnp.full(24, -1, jnp.int32), dst, **kw)
    ys, q = [], pool
    for k, (lo, hi) in enumerate([(0, 8), (8, 19), (19, 24)]):
        n, pad = hi - lo, 24 - (hi - lo)

        def cut(v, fill=0):
            return jnp.concatenate(
                [v[lo:hi], jnp.full((pad, *v.shape[1:]), fill, v.dtype)])

        yk, q = mamba2_ssd_scan(
            cut(x), cut(dt), cut(a), cut(B), cut(C), q, cut(slot, -1),
            cut(lens), cut(jnp.full(24, -1 if k == 0 else 0, jnp.int32), 6),
            cut(dst, 6), **kw)
        ys.append(yk[:n])
    assert float(jnp.abs(jnp.concatenate(ys) - y).max()
                 / jnp.abs(y).max()) < 1e-5
    assert float(jnp.abs(q[0] - p[0]).max() / jnp.abs(p[0]).max()) < 1e-5


def test_published_keys_and_the_layout(model):
    cfg = NemotronHConfig()
    assert len(cfg.pattern) == 88
    assert [cfg.pattern.count(c) for c in "ME*"] == [40, 40, 8]
    assert cfg.pattern[:11] == "MEMEMEM*EME"
    assert (cfg.d_inner, cfg.conv_dim, cfg.moe_top_k) == (8192, 10240, 22)
    with pytest.raises(ValueError, match="M, \\* and E"):
        NemotronHConfig.debug(hybrid_override_pattern="MEMXE")
    small, _ = model
    lay = small.paged_layout()
    assert lay.kinds[0].layers == (3,) and lay.state_layers == 2
    assert lay.state == (((4, 8, 16), "float32"), ((3, 96), None))
    assert set(STATE_COUNTS) & set(lay.count_names) == set()
    eng = engine(model)
    # pools for the one * layer alone; an entry a slot, the snapshots',
    # the trash entry; three columns of state on every packed row
    assert len(eng.k_pages) == 1 and eng.state[0][0].shape == (8, 4, 8, 16)
    assert eng.state[1][1].shape == (8, 3, 96) and eng.row_cols == 8
    assert set(STATE_COUNTS) <= set(eng.serving_stats()["steps"])
    eng.shutdown()
    # the cell's shapes: the published widths, the chip's share
    shapes = NemotronHConfig(num_hidden_layers=11, vocab_size=16384,
                             experts_held=(0, 64)).leaf_shapes()
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n == 2_752_338_304           # 5.50 GB in bf16


def test_the_step_is_one_program_and_admission_compiles_nothing(model,
                                                                prompts):
    """Fresh slots, restores and recycling ride the packed rows: the
    step compiled for the first launch serves them all."""
    _, ps = prompts
    eng = engine(model)
    serve(eng, ps[:1])
    fn = eng.layout.step
    before = fn._cache_size()
    serve(eng, ps)
    serve(eng, ps[::-1])
    assert fn._cache_size() == before
    eng.shutdown()
