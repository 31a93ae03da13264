"""Plain reference of the DeepSeek-V3.2 decoder on the serving path
(https://huggingface.co/deepseek-ai/DeepSeek-V3.2: ``config.json`` and
``inference/model.py``): multi-head latent attention in its absorbed
form, the lightning indexer's top-k selection, group-limited sigmoid
routing with a shared expert, YaRN rotary tables, and ONE chip's share
of the routed experts and of the vocabulary.

Straightforward ``jax.numpy`` in float32 with matmul precision
``highest``: the full sequence at once, no cache, no kernel, selection
by ``lax.top_k`` and attention over the rows it selected (``jnp.take``).  It imports nothing of the program and takes nothing
the program has made: weights come from
``benchmarks/harness/weights_deepseek_v32.py`` under the leaf names
listed there (Linear weights ``[in, out]``, expert banks ``[held, in,
out]``), upcast as they are used.  Attention and selection run in
blocks of ``q_block`` query rows so that a 24k-token prompt fits beside
the weights on one chip; a block's rows still see the whole sequence.

The layer (``h = RMSNorm(x)``, eps ``rms_norm_eps``):

- ``cq = RMSNorm(h W_qa)``; head i: ``q_i = cq W_qb[i]`` = ``[nope 128 ;
  rope 64]``, rope rotated at the position.  ``[ckv ; kr] = h W_kva``,
  ``ckv`` RMS-normed, ``kr`` rotated.  ``W_kvb[i] = [W_uk[i] ;
  W_uv[i]]``.  ``qa_i = [W_uk[i] q_nope_i ; q_rope_i]``; over the
  selected positions ``S_t``, ``p = softmax(c * qa_i . [ckv_s ;
  kr_s])``, ``o_i = W_uv[i]^T sum_s p_s ckv_s``; ``x += concat(o) W_o``.
  ``c = 192^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
- Indexer: ``qI_j = cq W_iq[j]`` (64 heads of 128), ``kI =
  LayerNorm(h W_ik)`` (eps 1e-6, with bias), the first 64 dimensions of
  both rotated; ``w = h W_iw * 64^-0.5 * 128^-0.5``; ``I[t, s] = sum_j
  w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t`` = the
  ``index_topk`` positions of largest ``I`` (all of them while ``t <
  index_topk``), ties to the lower position.
- Expert layer: ``s_e = sigmoid(h W_g[e])`` over the router's PUBLISHED
  width; selection on ``s_e + b_e``: groups of consecutive experts score
  the sum of their 2 best, the ``topk_group`` best groups stay, then the
  ``num_experts_per_tok`` best experts among them.  Gates ``g_e =
  routed_scaling_factor * s_e / sum_chosen s`` (over ALL chosen, held
  here or not).  ``y = SwiGLU_shared(h) + sum over chosen e in
  [held) of g_e SwiGLU_e(h)``: what the absent experts would add is left
  out, and the partial result goes on to the next layer.
- Leading layers: SwiGLU at ``intermediate_size``.  Head: final RMSNorm,
  then the held slice of the vocabulary.

Departures from the published code, each shared with the program
because it is a statement about the WEIGHTS, which are random here:
the rotated pairs are the two halves of the rotary dimensions (the
published code interleaves them: a permutation of columns); the indexer
skips the Hadamard rotation of ``qI`` and ``kI`` (orthogonal: every dot
product is unchanged) and nothing is held in FP8.

Controls, each of which has to come out as not correct: ``lowp`` rounds
every matmul operand to a lower precision (per-tensor scaled for fp8)
and reads it back; ``select=False`` lets every row attend its whole
context; ``gates="held"`` normalises the gates over the chosen experts
that are held here.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("lowp",))
def _rounded(x, lowp: str):
    if lowp == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if lowp == "fp8":                       # e4m3, scaled to its largest 448
        s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
    raise ValueError(f"unknown lower precision {lowp!r}")


def round_to(x, lowp: Optional[str]):
    """``x`` as the lower precision would hold it, back in float32 (one
    fused pass: a 24k-token activation is 1.6 GB, and a control runs
    beside 9.3 GB of weights)."""
    return x if lowp is None else _rounded(x, lowp)


def mm(a, b, lowp=None):
    return jnp.matmul(round_to(a.astype(F32), lowp),
                      round_to(b.astype(F32), lowp), precision=HIGHEST)


def ein(spec, a, b, lowp=None):
    return jnp.einsum(spec, round_to(a.astype(F32), lowp),
                      round_to(b.astype(F32), lowp), precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(F32)


def layer_norm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def yarn_tables(cfg: Dict[str, Any], n: int):
    """cos/sin ``[n, qk_rope_head_dim]``: theta's frequencies with
    YaRN's correction (each ``f_j`` becomes ``f_j / factor * (1 - g_j) +
    f_j * g_j``, ``g_j = 1 - clip((j - lo) / (hi - lo), 0, 1)``, ``lo``
    and ``hi`` the floor and ceiling of ``d ln(orig / (beta 2 pi)) / (2
    ln theta)`` for ``beta_fast`` and ``beta_slow``), halves layout."""
    rs, d, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    j = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / d)

    def bound(beta):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (beta * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(bound(rs["beta_fast"])), 0)
    hi = min(math.ceil(bound(rs["beta_slow"])), d - 1)
    g = 1.0 - np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    f = f / rs["factor"] * (1.0 - g) + f * g
    ang = np.outer(np.arange(n, dtype=np.float64), f)
    ang = np.concatenate([ang, ang], -1)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def rotate(x, cos, sin):
    """Rotary embedding of the last axis (halves layout)."""
    a, b = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-b, a], -1) * sin


def rotate_head(x, cos, sin, n: int):
    return jnp.concatenate([rotate(x[..., :n], cos, sin), x[..., n:]], -1)


def softmax_scale(cfg) -> float:
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


@functools.partial(jax.jit, static_argnames=("dims", "lowp", "use_selection"))
def _attention_block(cq_b, wi_b, cos_b, sin_b, pos_b, w_qb, w_iq, w_uk, w_uv,
                     w_o, lat, ki, dims, lowp, use_selection):
    """One block of query rows against the whole sequence's latents
    ``lat`` ``[S, dc + dr]`` and index keys ``ki`` ``[S, di]``:
    attention's addition to the block's rows ``[B, hidden]`` and the
    rows' selected positions ``[B, k]`` (``-1`` where fewer than k are
    visible; None without selection)."""
    H, dn, dr, dv, dc, Hi, di, topk, c = dims
    B, S = cq_b.shape[0], lat.shape[0]
    q = mm(cq_b, w_qb, lowp).reshape(B, H, dn + dr)
    q_rope = rotate(q[..., dn:], cos_b[:, None], sin_b[:, None])
    qa = jnp.concatenate([ein("bhd,chd->bhc", q[..., :dn], w_uk, lowp),
                          q_rope], -1)                        # [B, H, dc+dr]
    causal = jnp.arange(S)[None, :] <= pos_b[:, None]         # [B, S]
    if use_selection:
        qi = rotate_head(mm(cq_b, w_iq, lowp).reshape(B, Hi, di),
                         cos_b[:, None], sin_b[:, None], dr)
        per_head = jax.nn.relu(ein("bjd,sd->bjs", qi, ki, lowp))
        index = jnp.where(causal, jnp.einsum("bj,bjs->bs", wi_b, per_head,
                                             precision=HIGHEST), -jnp.inf)
        # the selected set, and attention over its rows alone
        vals, chosen = jax.lax.top_k(index, min(topk, S))     # ties: lower s
        seen = vals > -jnp.inf
        rows = jnp.take(lat, chosen, axis=0)                  # [B, k, dc+dr]
        sc = ein("bhc,bkc->bhk", qa, rows, lowp) * c
        p = jax.nn.softmax(jnp.where(seen[:, None, :], sc, -jnp.inf), -1)
        o = ein("bhk,bkc->bhc", p, rows[..., :dc], lowp)
        chosen = jnp.where(seen, chosen, -1)
    else:                                   # control: the whole context
        sc = ein("bhc,sc->bhs", qa, lat, lowp) * c
        p = jax.nn.softmax(jnp.where(causal[:, None, :], sc, -jnp.inf), -1)
        o = ein("bhs,sc->bhc", p, lat[:, :dc], lowp)
        chosen = None
    o = ein("bhc,chd->bhd", o, w_uv, lowp).reshape(B, H * dv)
    return mm(o, w_o, lowp), chosen


def attention(x, lw, cfg, cos, sin, lowp=None, use_selection=True,
              q_block: int = 128):
    """``(x + attention, selected [S, S] bool or None)``; x ``[S, hidden]``.
    The selection is returned only for sequences of at most 4096."""
    S = x.shape[0]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    dc, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    at = "self_attn."
    h = rms_norm(x, lw["input_layernorm.weight"], eps)
    cq = rms_norm(mm(h, lw[at + "q_a_proj.weight"], lowp),
                  lw[at + "q_a_layernorm.weight"], eps)
    kva = mm(h, lw[at + "kv_a_proj_with_mqa.weight"], lowp)
    ckv = rms_norm(kva[:, :dc], lw[at + "kv_a_layernorm.weight"], eps)
    lat = jnp.concatenate([ckv, rotate(kva[:, dc:], cos, sin)], -1)
    ki = rotate_head(layer_norm(mm(h, lw[at + "indexer.wk.weight"], lowp),
                                lw[at + "indexer.k_norm.weight"],
                                lw[at + "indexer.k_norm.bias"]), cos, sin, dr)
    wi = mm(h, lw[at + "indexer.weights_proj.weight"], lowp) \
        * (Hi ** -0.5 * di ** -0.5)
    wkvb = lw[at + "kv_b_proj.weight"].reshape(dc, H, dn + dv)
    dims = (H, dn, dr, dv, dc, Hi, di, cfg["index_topk"], softmax_scale(cfg))
    pos = jnp.arange(S)
    if not use_selection or lowp:
        # [B, H, S] scores and not [B, H, k]; a rounded copy of every operand
        q_block = min(q_block, 32)
    outs, sels = [], []
    for s0 in range(0, S, q_block):
        sl = slice(s0, min(s0 + q_block, S))
        o, chosen = _attention_block(
            cq[sl], wi[sl], cos[sl], sin[sl], pos[sl],
            lw[at + "q_b_proj.weight"], lw[at + "indexer.wq_b.weight"],
            wkvb[..., :dn], wkvb[..., dn:], lw[at + "o_proj.weight"], lat, ki,
            dims=dims, lowp=lowp, use_selection=use_selection)
        outs.append(o)
        if S <= 4096 and chosen is not None:
            n = chosen.shape[0]
            sels.append(jnp.zeros((n, S + 1), bool).at[
                jnp.arange(n)[:, None], chosen].set(True)[:, :S])
    x = x + jnp.concatenate(outs, 0)
    return x, (jnp.concatenate(sels, 0) if sels else None)


def by_rows(fn, h, block: int = 4096):
    """``fn`` over blocks of rows (a 24k-token MLP at width 18432 is
    three 1.8 GB intermediates at once otherwise)."""
    return jnp.concatenate([fn(h[s0:s0 + block])
                            for s0 in range(0, h.shape[0], block)], 0)


def swiglu(h, gate, up, down, lowp=None):
    return mm(jax.nn.silu(mm(h, gate, lowp)) * mm(h, up, lowp), down, lowp)


def route(h, lw, cfg):
    """``(chosen [S, k] expert ids, gates [S, k])`` over the router's
    full width; float32 throughout (the published router is)."""
    e, k = lw["mlp.router.weight"].shape[1], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(h, lw["mlp.router.weight"].astype(F32),
                                       precision=HIGHEST))
    pick = scores + lw["mlp.router.bias"].astype(F32)
    groups = pick.reshape(-1, cfg["n_group"], e // cfg["n_group"])
    gscore = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)
    _, gkeep = jax.lax.top_k(gscore, cfg["topk_group"])
    gmask = jnp.zeros(gscore.shape, bool).at[
        jnp.arange(gscore.shape[0])[:, None], gkeep].set(True)
    pick = jnp.where(jnp.repeat(gmask, e // cfg["n_group"], -1), pick, -jnp.inf)
    _, chosen = jax.lax.top_k(pick, k)
    return chosen, jnp.take_along_axis(scores, chosen, -1)


def expert_layer(h, lw, cfg, held: Tuple[int, int], lowp=None,
                 gates: str = "all", shared: bool = True):
    """The held experts' part of the layer's output, plus the shared
    expert (``shared=False`` leaves it out: the shares of several chips
    count it once)."""
    lo, hi = held
    chosen, s = route(h, lw, cfg)
    here = (chosen >= lo) & (chosen < hi)
    if gates == "all":
        den = jnp.sum(s, -1, keepdims=True)
    elif gates == "held":
        den = jnp.sum(jnp.where(here, s, 0.0), -1, keepdims=True)
    else:
        raise ValueError(f"gates over {gates!r}?")
    g = cfg["routed_scaling_factor"] * s / (den + 1e-20)
    y = jnp.zeros_like(h)
    for e in range(lo, hi):
        ge = jnp.sum(jnp.where(chosen == e, g, 0.0), -1, keepdims=True)
        y = y + ge * swiglu(h, lw["mlp.experts.gate_proj.weight"][e - lo],
                            lw["mlp.experts.up_proj.weight"][e - lo],
                            lw["mlp.experts.down_proj.weight"][e - lo], lowp)
    if shared:
        y = y + swiglu(h, lw["mlp.shared_expert.gate_proj.weight"],
                       lw["mlp.shared_expert.up_proj.weight"],
                       lw["mlp.shared_expert.down_proj.weight"], lowp)
    return y


def layer_leaves(params: Dict[str, Any], i: int) -> Dict[str, Any]:
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def held_range(cfg) -> Tuple[int, int]:
    """The configuration file's share: ``n_routed_experts`` experts are
    HELD, those of rank ``deployment_rank`` (0 where it is not given)."""
    n = cfg["n_routed_experts"]
    lo = n * int(cfg.get("deployment_rank", 0))
    return lo, lo + n


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any], held=None,
            lowp=None, use_selection: bool = True, gates: str = "all",
            q_block: int = 128, rows: Optional[slice] = None):
    """``(logits, selections)`` of token ids ``[S]``: logits ``[S,
    vocab]`` (of the positions ``rows`` alone where given), and each
    layer's selection ``[S, S]`` for short sequences (else None)."""
    held = held or held_range(cfg)
    S = ids.shape[0]
    cos, sin = yarn_tables(cfg, S)
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    sels = []
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_leaves(params, i)
        x, sel = attention(x, lw, cfg, cos, sin, lowp, use_selection, q_block)
        sels.append(sel)
        h = rms_norm(x, lw["post_attention_layernorm.weight"],
                     cfg["rms_norm_eps"])
        if "mlp.router.weight" in lw:
            x = x + by_rows(lambda hb: expert_layer(hb, lw, cfg, held, lowp,
                                                    gates), h)
        else:
            x = x + by_rows(lambda hb: swiglu(
                hb, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"],
                lw["mlp.down_proj.weight"], lowp), h)
    if rows is not None:
        x = x[rows]
    x = rms_norm(x, params["model.norm.weight"], cfg["rms_norm_eps"])
    return mm(x, params["lm_head.weight"], lowp), sels


def served_token_gaps(params, prompt, tokens, cfg: Dict[str, Any], lowp=None,
                      pad_to: int = 0, **control) -> Dict[str, Any]:
    """Teacher-forced check of one greedy request: the prompt plus the
    served tokens go through the reference once, and for every served
    token the gap by which its reference logit lies below the
    reference's best at that position is returned (0 where the
    reference would have served the same token).  With a control (a
    ``lowp``, ``use_selection=False`` or ``gates="held"``) the same is
    computed for the CONTROL's own greedy choice (``control_gap``): how
    far a program that computed the control's way would have strayed.

    ``pad_to`` appends token 0 up to that length.  Under the causal
    mask nothing before a position depends on what follows it, so the
    served positions' logits are the unpadded sequence's; what it buys
    is ONE shape for requests of many lengths: this sequence's shapes
    took 50 s to compile on the chip and 3 s to run (PERF.md, PR 26)."""
    prompt, tokens = np.asarray(prompt), np.asarray(tokens)
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    n, S = len(tokens), len(seq)
    ids = jnp.asarray(np.pad(seq, (0, max(0, pad_to - S))))
    rows = slice(S - n, S)
    logits = forward(params, ids, cfg, rows=rows)[0]
    best = jnp.max(logits, -1)
    served = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], -1)[:, 0]
    out = {"gap": np.asarray(best - served),
           "reference_tokens": np.asarray(jnp.argmax(logits, -1))}
    if lowp or control:
        alt = jnp.argmax(forward(params, ids, cfg, lowp=lowp, rows=rows,
                                 **control)[0], -1)
        out["control_gap"] = np.asarray(
            best - jnp.take_along_axis(logits, alt[:, None], -1)[:, 0])
    return out
