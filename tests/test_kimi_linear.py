"""Kimi-Linear on the serving path, at a small size on the CPU in
float32: KDA layers whose per-slot state lives in ONE pool beside the
paged LATENT cache of the MLA layers, snapshots of that state for prefix
reuse (a hit restores a snapshot AND maps latent pages), and the held
share of the sigmoid-routed experts, through the engine's ONE step
against the plain reference ``benchmarks/reference/kimi_linear_ref.py``,
which shares no code with the program."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kimi_linear_ref as ref
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import generation
from paddle_tpu.models.kimi_linear import KimiLinearConfig
# seeded leaves by shape and name (A_log, dt_bias as Mamba-2 starts its
# own), and an engine run to the end with every logits row kept
from test_nemotron_h import draw, serve

PAGE, BUDGET, SLOTS, SEQ, VOCAB = 4, 8, 3, 64, 96
HELD = (4, 12)


def ref_cfg(cfg):
    """The configuration as the benchmark's file states it: the published
    keys, ``linear_attn_config`` nested (the tests pass ``held=``)."""
    out = dataclasses.asdict(cfg)
    out["linear_attn_config"] = {
        "kda_layers": list(cfg.kda_layers),
        "full_attn_layers": list(cfg.full_attn_layers),
        "num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
        "short_conv_kernel_size": cfg.short_conv_kernel_size}
    return out


@pytest.fixture(scope="module")
def model():
    cfg = KimiLinearConfig.debug(experts_held=HELD)      # K K K M K
    return cfg, draw(cfg)


def engine(model, **kw):
    cfg, params = model
    opts = dict(max_slots=SLOTS, num_pages=48, page_size=PAGE,
                max_seq_len=SEQ, prefill_token_budget=BUDGET,
                enable_prefix_cache=True, state_snapshots=4)
    opts.update(kw)
    return ContinuousBatchingEngine(cfg, params, **opts)


def worst_error(model, served, **control):
    """Largest error of an engine's logits row against the reference's
    full forward over prompt + served tokens, relative to the row's
    largest logit."""
    cfg, params = model
    worst = 0.0
    for prompt, tokens, rows in served.values():
        seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        want = np.asarray(ref.forward(params, jnp.asarray(seq), ref_cfg(cfg),
                                      held=HELD, **control))
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
# twentieth of what a bf16 state gives on these 40 tokens (2e-3)
TOL = 1e-4


def test_chunked_prefill_and_decode_match_the_reference(model, prompts):
    """Prompts of 22, 26 and 37 tokens in chunks of at most 8 beside each
    other's decode rows, then decode through state and latent cache; the
    same rows are far from a reference that keeps the state or the decay
    in bf16, or leaves beta out."""
    eng = engine(model, enable_prefix_cache=False, state_snapshots=0)
    served = serve(eng, prompts[1])
    assert worst_error(model, served) < TOL
    for control in (dict(state_dtype="bfloat16"),
                    dict(decay_dtype="bfloat16"), dict(beta=False)):
        assert worst_error(model, served, **control) > 5 * TOL, control
    st = eng.serving_stats()["steps"]
    assert st["state_rows"] == st["rows"] > 0
    assert st["state_slots"] >= st["steps"] - 1
    assert st["attn_kv_tokens_read"] >= st["kv_ctx_tokens"] > 0
    assert 0 < st["moe_rows_held"] < st["moe_rows_routed"]
    eng.shutdown()


def test_a_prefix_hit_restores_a_snapshot_and_maps_latent_pages(model,
                                                                prompts):
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
    # 17 shared tokens: 4 whole latent pages, the chunk grid's snapshot
    # at 16, in ONE admission
    assert [stats[r]["state_restored_tokens"] for r in (1, 2)] == [16, 16]
    assert [stats[r]["cached_tokens"] for r in (1, 2)] == [16, 16]
    # the same prompt again: 5 pages match, the deepest snapshot is at 16
    assert stats[3]["state_restored_tokens"] == 16
    assert stats[3]["state_lost_tokens"] == 4
    assert stats[3]["prefilled"] == len(p1) - 16
    eng.assert_balanced()
    eng.shutdown()


@pytest.mark.parametrize("warm", [False, True], ids=["whole", "restored"])
def test_the_state_a_prompt_leaves_is_the_references(model, prompts, warm):
    """A request of ONE token ends with its prompt's state in its entry,
    untouched by any decode row: the reference's ``S`` after the same
    tokens, every KDA layer and head, prefilled whole or restored."""
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
    assert got.shape == want.shape == (4, 4, 8, 8)
    every = np.tile(np.arange(4), (4, 1))
    assert ref.state_errors(got, want, every).max() < 1e-5
    assert ref.slow_heads(params, ref_cfg(cfg)).shape == (4, 1)
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

    class Model:
        cfg = model[0]

    with pytest.raises(NotImplementedError, match="recurrent state"):
        generation.generate(Model(), np.zeros((1, 4), np.int32))
    eng.shutdown()


def lw_of(lw, lo, hi):
    return {k: (v[lo:hi] if k.startswith("mlp.experts.") else v)
            for k, v in lw.items()}


def test_the_routed_parts_of_all_shares_add_up_to_the_whole_layer():
    """The guide's share test: 8 ranks hold 2 of 16 experts each; their
    routed parts, with the router and the shared expert counted once, add
    up to the uncut reference's expert layer."""
    cfg = KimiLinearConfig.debug()
    params = draw(cfg, seed=11)
    i = 2
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
        for proj in ("gate_proj", "up_proj", "down_proj"):
            p[pre + proj + ".weight"] = params[pre + proj + ".weight"][lo:hi]
        w = generation._Weights(share, p)
        total = total + generation._moe_ffn(w, i, x) - shared
        # and each share is the reference's own share
        want = ref.expert_layer(x, lw_of(lw, lo, hi), ref_cfg(cfg), (lo, hi))
        assert float(jnp.abs(generation._moe_ffn(w, i, x) - want).max()) \
            < 1e-4 * float(jnp.abs(whole).max())
    err = float(jnp.abs(total + shared - whole).max() / jnp.abs(whole).max())
    assert err < TOL


def test_published_keys_and_the_layout():
    """The published keys at their published values give the published
    model: 27 layers, 20 KDA and 7 MLA, 48 B parameters; the layout has
    a state of two arrays over the KDA layers and ONE kind of page over
    the MLA layers alone, two pools of 640 numbers a token between them."""
    cfg = KimiLinearConfig.from_published({
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [n for n in range(1, 27) if n % 4],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "model_type": "kimi_linear", "rope_theta": 10000, "head_dim": 72})
    assert cfg == KimiLinearConfig()
    assert (len(cfg.layers_of(True)), len(cfg.layers_of(False))) == (20, 7)
    n = sum(int(np.prod(s)) for s in cfg.leaf_shapes().values())
    assert abs(n / 1e9 - 49.1) < 0.3
    lay = cfg.paged_layout()
    assert lay.rows == ((512,), (128,)) and not lay.head_major
    assert lay.kinds[0].layers == (3, 7, 11, 15, 19, 23, 26)
    assert lay.state == (((32, 128, 128), "float32"), ((3, 12288), None))
    assert lay.state_layers == 20 and lay.tile_rows == 128
    with pytest.raises(ValueError, match="each layer that runs once"):
        KimiLinearConfig(kda_layers=(1, 2), full_attn_layers=(2, 3),
                         num_hidden_layers=3)
