"""The expert layer's dispatch (``generation._moe_experts``): the token
copies travel into expert order and back by reads alone.  Against a
plain loop over tokens and their chosen experts in float32, over the
forms the three serving configurations take, and the lowered program of
the layer holds no scatter and no copy of the experts' buffer."""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import generation
from paddle_tpu.ops.pallas import grouped_matmul as gmm_mod
from paddle_tpu.ops.pallas.grouped_matmul import align_rows

H, INTER = 16, 24


def _bank(e, act, seed, int8=False):
    rng = np.random.default_rng(seed)
    projs = {"up_proj": (e, H, INTER), "down_proj": (e, INTER, H)}
    if act != "relu2":
        projs["gate_proj"] = (e, H, INTER)
    params = {f"model.layers.0.mlp.experts.{p}.weight":
              jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
              for p, s in projs.items()}
    if int8:
        params = generation.quantize_params_int8(params)
    return params


def _slice(params, proj, x):
    """Expert ``x``'s ``[in, out]`` slice of a projection, dequantized."""
    name = f"model.layers.0.mlp.experts.{proj}.weight"
    w = np.asarray(params[name], np.float32)[x]
    sc = params.get(name + "._scale")
    return w if sc is None else w * np.asarray(sc, np.float32)[x][None, :]


def _loop(params, act, x, ids, gates, lo, hi, valid):
    """Every live token through each expert it chose that the bank
    holds, one after another, in float32."""
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        if not valid[t]:
            continue
        for j, ex in enumerate(ids[t]):
            if not lo <= ex < hi:
                continue
            up = x[t] @ _slice(params, "up_proj", ex - lo)
            if act == "relu2":
                hid = np.square(np.maximum(up, 0))
            else:
                g = x[t] @ _slice(params, "gate_proj", ex - lo)
                hid = g / (1 + np.exp(-g)) * up
            y[t] += gates[t, j] * (hid @ _slice(params, "down_proj", ex - lo))
    return y


def _routing(t, k, e_all, within, seed):
    """``k`` distinct experts a token (``top_k``'s), all of them inside
    ``within`` where it is given; gates that sum to 1."""
    rng = np.random.default_rng(seed)
    lo, hi = within or (0, e_all)
    ids = np.stack([lo + rng.permutation(hi - lo)[:k] for _ in range(t)])
    gates = rng.random((t, k)).astype(np.float32) + 0.1
    return ids.astype(np.int32), gates / gates.sum(-1, keepdims=True)


def _stats(valid):
    return {"valid": jnp.asarray(valid), "moe_rows_routed": [],
            "moe_rows_held": [], "moe_expert_rows_max": [],
            "moe_experts_hit": []}


# e_all, (lo, hi), k, T, block, act, int8, rows valid, routed within, the
# branch the sizes must take ("all": one buffer; "few" / "tk": the cond)
CASES = {
    "all_held_gated": (8, (0, 8), 2, 13, 8, "silu", False, None, None, "all"),
    "all_held_relu2": (8, (0, 8), 3, 11, 4, "relu2", False, None, None,
                       "all"),
    "all_held_masked_rows": (8, (0, 8), 2, 13, 8, "silu", False, 9, None,
                             "all"),
    "all_held_no_row_valid": (8, (0, 8), 2, 6, 8, "silu", False, 0, None,
                              "all"),
    "all_held_no_stats": (8, (0, 8), 2, 13, 8, "silu", False, "none", None,
                          "all"),
    "all_held_int8": (8, (0, 8), 2, 13, 8, "silu", True, None, None, "all"),
    "all_held_one_token": (8, (0, 8), 2, 1, 8, "silu", False, None, None,
                           "all"),
    "all_held_five_tokens": (8, (0, 8), 2, 5, 8, "silu", False, 4, None,
                             "all"),
    "share_few_gated": (32, (8, 12), 4, 24, 4, "silu", False, None, None,
                        "few"),
    "share_few_relu2": (32, (8, 12), 4, 24, 4, "relu2", False, 20, None,
                        "few"),
    "share_few_int8": (32, (8, 12), 4, 24, 4, "relu2", True, None, None,
                       "few"),
    "share_forced_tk_gated": (16, (4, 8), 2, 12, 4, "silu", False, None,
                              (4, 8), "tk"),
    "share_forced_tk_relu2_masked": (16, (4, 8), 2, 13, 4, "relu2", False,
                                     10, (4, 8), "tk"),
    "share_one_token": (16, (4, 8), 2, 1, 4, "silu", False, None, None,
                        "all"),
    "share_five_tokens": (16, (4, 8), 4, 5, 8, "relu2", False, 3, None,
                          "few"),
    "share_none_held": (16, (4, 8), 2, 12, 4, "silu", False, None, (8, 16),
                        "few"),
    "share_few_token_major": (32, (8, 12), 4, 24, 4, "silu", False, 20, None,
                              "few"),
    "share_forced_tk_token_major": (16, (4, 8), 2, 13, 4, "relu2", False,
                                    10, (4, 8), "tk"),
}


@pytest.mark.parametrize("case", CASES)
def test_moe_experts_against_a_plain_loop(case, monkeypatch):
    e_all, (lo, hi), k, t, bm, act, int8, nvalid, within, branch = CASES[case]
    if case.endswith("_token_major"):   # a share held, past the product's size
        monkeypatch.setattr(generation, "_MOE_PRODUCT_CELLS", 0)
    e = hi - lo
    params = _bank(e, act, seed=len(case), int8=int8)
    rng = np.random.default_rng(t)
    x = rng.normal(size=(t, H)).astype(np.float32)
    ids, gates = _routing(t, k, e_all, within, seed=t + k)
    valid = np.ones(t, bool) if nvalid in (None, "none") \
        else np.arange(t) < nvalid
    stats = None if nvalid == "none" else _stats(valid)
    # the branch the static sizes take
    tk, few = t * k, int(align_rows(2 * t * k * e // e_all, bm))
    absent = e < e_all or stats is not None
    held = (ids >= lo) & (ids < hi) & valid[:, None]
    took = "all" if not absent or few >= tk else \
        "few" if held.sum() <= few else "tk"
    assert took == branch
    cfg = types.SimpleNamespace(moe_block_rows=bm, mlp_hidden_act=act)
    got = generation._moe_experts(
        generation._Weights(cfg, params), 0, jnp.asarray(x),
        jnp.asarray(ids), jnp.asarray(gates), lo, hi, e_all, stats)
    want = _loop(params, act, x, ids, gates, lo, hi, valid)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    if stats is None:
        return
    counts = np.bincount((ids - lo)[held], minlength=e)
    assert [int(stats[name][0]) for name in (
        "moe_rows_routed", "moe_rows_held", "moe_expert_rows_max",
        "moe_experts_hit")] == [int(valid.sum()) * k, int(held.sum()),
                                int(counts.max()), int((counts > 0).sum())]


def test_moe_experts_in_bfloat16_sums_a_token_in_float32():
    """bf16 rows, the combine in float32 and cast once: no further from
    the float32 loop than bf16's own rounding of the sum."""
    e_all, k, t, bm = 8, 4, 19, 8
    params = _bank(e_all, "silu", seed=3)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(t, H)).astype(np.float32)
    ids, gates = _routing(t, k, e_all, None, seed=9)
    cfg = types.SimpleNamespace(moe_block_rows=bm, mlp_hidden_act="silu")
    half = {n: v.astype(jnp.bfloat16) for n, v in params.items()}
    got = generation._moe_experts(
        generation._Weights(cfg, half), 0, jnp.asarray(x, jnp.bfloat16),
        jnp.asarray(ids), jnp.asarray(gates), 0, e_all, e_all,
        _stats(np.ones(t, bool)))
    assert got.dtype == jnp.bfloat16
    want = _loop(params, "silu", x, ids, gates, 0, e_all, np.ones(t, bool))
    err = np.abs(np.asarray(got, np.float32) - want)
    assert err.max() < 0.04 * np.abs(want).max()


# the debug sizes of the two configurations whose forms differ: Mellum2
# (every expert held: the token-major gather) and Nemotron-H (a share
# held, relu2, a latent: the product, inside the cond's two branches)
STEPS = {
    "mellum2": dict(e_all=8, held=(0, 8), k=2, t=9, bm=8, act="silu",
                    width=64, inter=32),
    "nemotron_h": dict(e_all=16, held=(4, 8), k=4, t=35, bm=8, act="relu2",
                       width=32, inter=48),
}


@pytest.mark.parametrize("name", STEPS)
def test_the_lowered_expert_layer_moves_its_copies_by_reads(name,
                                                            monkeypatch):
    """The layer as a serving step lowers it for a TPU (the kernel a
    custom call, not its interpreter): no scatter of any kind, and the
    experts' buffer, whatever its width, is never concatenated, padded,
    sliced or updated in place: it goes through the launches as the
    gather made it."""
    s = STEPS[name]
    monkeypatch.setattr(gmm_mod, "pallas_interpret", lambda: False)
    lo, hi = s["held"]
    e, t, k, bm = hi - lo, s["t"], s["k"], s["bm"]
    projs = {"up_proj": (e, s["width"], s["inter"]),
             "down_proj": (e, s["inter"], s["width"])}
    if s["act"] != "relu2":
        projs["gate_proj"] = projs["up_proj"]
    cfg = types.SimpleNamespace(moe_block_rows=bm, mlp_hidden_act=s["act"])

    def layer(params, x, ids, gates, valid):
        stats = _stats(valid)
        y = generation._moe_experts(generation._Weights(cfg, params), 0, x,
                                    ids, gates, lo, hi, s["e_all"], stats)
        return y, {n: v for n, v in stats.items() if n != "valid"}

    f32 = jnp.float32
    args = ({f"model.layers.0.mlp.experts.{p}.weight":
             jax.ShapeDtypeStruct(shape, jnp.bfloat16)
             for p, shape in projs.items()},
            jax.ShapeDtypeStruct((t, s["width"]), jnp.bfloat16),
            jax.ShapeDtypeStruct((t, k), jnp.int32),
            jax.ShapeDtypeStruct((t, k), f32),
            jax.ShapeDtypeStruct((t,), jnp.bool_))
    text = jax.jit(layer).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    launches = 2 if s["act"] == "relu2" else 3
    tk, few = t * k, int(align_rows(2 * t * k * e // s["e_all"], bm))
    sizes = [tk] if few >= tk else [few, tk]
    assert len(sizes) == (1 if name == "mellum2" else 2)
    assert text.count("tpu_custom_call") == launches * len(sizes)
    assert "scatter" not in text
    # the buffers' row counts: the copies' blocks, a block an expert of
    # slack, the park block
    rows = [int(align_rows(n, bm)) + e * bm + bm for n in sizes]
    assert all(f"tensor<{r}x{s['width']}xbf16>" in text for r in rows)
    moved = [ln.strip()[:200] for ln in text.splitlines()
             if re.search(r"stablehlo\.(concatenate|pad|slice|dynamic_slice|"
                          r"dynamic_update_slice)\b", ln)
             and any(f"tensor<{r}x" in ln for r in rows)]
    assert not moved, "\n".join(moved)
