"""Plain reference of the Mellum2 decoder on the serving path
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
``config.json``; ``model_type`` ``mellum``): grouped-query attention
whose layers are of two kinds, ``sliding_attention`` (the last
``sliding_window`` positions, plain rotary tables) and ``full_attention``
(the whole context, YaRN tables), and an expert layer of softmax-routed
SwiGLU experts in every layer.

Straightforward ``jax.numpy`` in float32 with matmul precision
``highest``: the full sequence at once, no cache, no kernel, no batching,
a dense mask over the whole sequence for either kind of layer, every
token through every expert with its gate (0 where the expert was not
chosen).  It imports nothing of the program and takes nothing the
program has made: weights come from ``benchmarks/harness/
weights_mellum2.py`` under the leaf names listed there (Linear weights
``[in, out]``, expert banks ``[experts, in, out]``), upcast as they are
used.  Attention runs in blocks of ``q_block`` query rows and the expert
layer in blocks of 4096 rows, so that a 25k-token request fits beside
the weights on one chip; a block's rows still see the whole sequence.

The layer, ``x`` ``[S, hidden]``, layer ``i`` of kind ``layer_types[i]``:

- ``a = RMSNorm(x)``; ``q = a Wq`` ``[S, heads, head_dim]``, ``k = a Wk``,
  ``v = a Wv`` ``[S, kv_heads, head_dim]``, no bias, no QK-norm.
- Rotary embedding of ``q`` and ``k``, the rotated pairs the two HALVES
  of the head dimension, by kind.  ``sliding_attention``: ``f_j =
  theta^(-2j/d)``.  ``full_attention``: YaRN as transformers computes it:
  ``f_j / factor * (1 - m_j) + f_j * m_j``, ``m_j = 1 - clip((j - low) /
  (high - low), 0, 1)``, ``low`` and ``high`` the floor and ceiling of
  ``d ln(original / (beta 2 pi)) / (2 ln theta)`` for ``beta_fast`` and
  ``beta_slow``, and cos and sin multiplied by ``attention_factor``.
- Attention: query head ``h`` reads KV head ``h // (heads / kv_heads)``,
  scale ``head_dim^-0.5``, causal; a ``sliding_attention`` row at
  position p attends ``p - W + 1 .. p`` (transformers' mask ``kv > q -
  sliding_window``), a ``full_attention`` row ``0 .. p``.  ``x += ctx Wo``.
- ``m = RMSNorm(x)``; ``p = softmax(m Wr)`` over all experts in float32;
  the ``num_experts_per_tok`` largest; gates ``p_k / sum_chosen p``;
  ``x += sum_k g_k (silu(m Wg_k) * (m Wu_k)) Wd_k``.  No shared expert,
  no router bias.
- After the last layer RMSNorm and the untied head.

Departures from the published description: none known.  What the config
has no key for is assumed as the configuration's file says under
``assumed`` (no QK-norm, no router bias, no shared expert, no
multi-token-prediction module, ``intermediate_size`` unused).

Controls, each of which has to come out as not correct: ``lowp`` rounds
every matmul operand to a lower precision (per-tensor scaled for fp8)
and reads it back; ``use_window=False`` lets every layer attend its
whole context; ``yarn=False`` puts the full layers on the plain rotary
table; ``gates="softmax"`` leaves the gates as the softmax gave them,
not renormalised over the experts chosen.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"


@functools.partial(jax.jit, static_argnames=("lowp",))
def _rounded(x, lowp: str):
    if lowp == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if lowp == "fp8":                       # e4m3, scaled to its largest 448
        s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
    raise ValueError(f"unknown lower precision {lowp!r}")


def round_to(x, lowp: Optional[str]):
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


def rope_tables(cfg: Dict[str, Any], kind: str, n: int, yarn: bool = True):
    """cos/sin ``[n, head_dim]`` of the layers of ``kind`` (halves
    layout), from ``rope_parameters[kind]``; ``yarn=False`` (a control)
    gives a YaRN kind the plain table of its theta."""
    rp, d = cfg["rope_parameters"][kind], cfg["head_dim"]
    theta = float(rp["rope_theta"])
    j = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / d)
    scale = 1.0
    if rp["rope_type"] == "yarn" and yarn:
        def bound(beta):
            return d * math.log(rp["original_max_position_embeddings"]
                                / (beta * 2 * math.pi)) / (2 * math.log(theta))

        lo = max(math.floor(bound(rp["beta_fast"])), 0)
        hi = min(math.ceil(bound(rp["beta_slow"])), d - 1)
        m = 1.0 - np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
        f = f / rp["factor"] * (1.0 - m) + f * m
        scale = float(rp["attention_factor"])
    ang = np.outer(np.arange(n, dtype=np.float64), f)
    ang = np.concatenate([ang, ang], -1)
    return (jnp.asarray(np.cos(ang) * scale, F32),
            jnp.asarray(np.sin(ang) * scale, F32))


def rotate(x, cos, sin):
    """Rotary embedding of the last axis (halves layout)."""
    a, b = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-b, a], -1) * sin


@functools.partial(jax.jit, static_argnames=("window", "lowp"))
def _attention_block(q_b, pos_b, k, v, window, lowp):
    """A block of query rows ``[B, kvh, rep, d]`` at positions ``pos_b``
    against the whole sequence's keys and values ``[S, kvh, d]``."""
    S, d = k.shape[0], k.shape[-1]
    at = jnp.arange(S)[None, :]
    seen = at <= pos_b[:, None]
    if window is not None:
        seen = seen & (at > pos_b[:, None] - window)
    sc = ein("bgrd,sgd->bgrs", q_b, k, lowp) * d ** -0.5
    p = jax.nn.softmax(jnp.where(seen[:, None, None, :], sc, -jnp.inf), -1)
    return ein("bgrs,sgd->bgrd", p, v, lowp)


def attention(x, lw, cfg, kind, cos, sin, lowp=None, use_window=True,
              q_block: int = 128):
    """``x + attention``; x ``[S, hidden]``."""
    S = x.shape[0]
    H, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    a = rms_norm(x, lw["input_layernorm.weight"], cfg["rms_norm_eps"])
    q = rotate(mm(a, lw["self_attn.q_proj.weight"], lowp).reshape(S, H, d),
               cos[:, None], sin[:, None]).reshape(S, kvh, H // kvh, d)
    k = rotate(mm(a, lw["self_attn.k_proj.weight"], lowp).reshape(S, kvh, d),
               cos[:, None], sin[:, None])
    v = mm(a, lw["self_attn.v_proj.weight"], lowp).reshape(S, kvh, d)
    window = int(cfg["sliding_window"]) \
        if kind == SLIDING and use_window else None
    pos = jnp.arange(S)
    if lowp:
        q_block = min(q_block, 32)      # a rounded copy of every operand
    outs = [_attention_block(q[s0:s0 + q_block], pos[s0:s0 + q_block], k, v,
                             window=window, lowp=lowp)
            for s0 in range(0, S, q_block)]
    ctx = jnp.concatenate(outs, 0).reshape(S, H * d)
    return x + mm(ctx, lw["self_attn.o_proj.weight"], lowp)


def by_rows(fn, h, block: int = 4096):
    return jnp.concatenate([fn(h[s0:s0 + block])
                            for s0 in range(0, h.shape[0], block)], 0)


def swiglu(h, gate, up, down, lowp=None):
    return mm(jax.nn.silu(mm(h, gate, lowp)) * mm(h, up, lowp), down, lowp)


def _route(h, router, k: int, gates: str):
    p = jax.nn.softmax(jnp.matmul(h, router.astype(F32), precision=HIGHEST),
                       -1)
    top, chosen = jax.lax.top_k(p, k)
    if gates == "chosen":
        top = top / jnp.sum(top, -1, keepdims=True)
    elif gates != "softmax":
        raise ValueError(f"gates over {gates!r}?")
    return chosen, top


def route(h, lw, cfg, gates: str = "chosen"):
    """``(chosen [S, k] expert ids, gates [S, k])``; float32 throughout."""
    return _route(h, lw["mlp.router.weight"],
                  int(cfg["num_experts_per_tok"]), gates)


@functools.partial(jax.jit, static_argnames=("k", "gates", "lowp"))
def _expert_block(h, router, gate, up, down, k, gates, lowp):
    """A block of rows through the whole bank, an expert at a time (a
    ``lax.scan`` over the stacked slices: one compiled body, not 64)."""
    chosen, top = _route(h, router, k, gates)

    def one(y, ew):
        e, g_w, u_w, d_w = ew
        ge = jnp.sum(jnp.where(chosen == e, top, 0.0), -1, keepdims=True)
        return y + ge * swiglu(h, g_w, u_w, d_w, lowp), None

    n = gate.shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(n), gate, up, down))
    return y


def expert_layer(h, lw, cfg, lowp=None, gates: str = "chosen"):
    """Every row through every expert, weighted by its gate (0 where
    the expert was not chosen): ``route`` and ``swiglu`` as above, under
    one jit a block of rows."""
    return _expert_block(h, lw["mlp.router.weight"],
                         lw["mlp.experts.gate_proj.weight"],
                         lw["mlp.experts.up_proj.weight"],
                         lw["mlp.experts.down_proj.weight"],
                         k=int(cfg["num_experts_per_tok"]), gates=gates,
                         lowp=lowp)


def layer_leaves(params: Dict[str, Any], i: int) -> Dict[str, Any]:
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any], lowp=None,
            use_window: bool = True, yarn: bool = True,
            gates: str = "chosen", q_block: int = 128,
            rows: Optional[slice] = None, upto: Optional[int] = None):
    """Logits ``[S, vocab]`` of token ids ``[S]`` (of the positions
    ``rows`` alone where given).  ``upto`` stops after that many layers
    and returns the residual stream ``[S, hidden]`` instead (the tests
    look at a single layer)."""
    S = ids.shape[0]
    types = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    tabs = {kind: rope_tables(cfg, kind, S, yarn) for kind in set(types)}
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    for i, kind in enumerate(types[:upto]):
        lw = layer_leaves(params, i)
        x = attention(x, lw, cfg, kind, *tabs[kind], lowp, use_window, q_block)
        h = rms_norm(x, lw["post_attention_layernorm.weight"],
                     cfg["rms_norm_eps"])
        x = x + by_rows(lambda hb: expert_layer(hb, lw, cfg, lowp, gates), h)
    if upto is not None:
        return x
    if rows is not None:
        x = x[rows]
    x = rms_norm(x, params["model.norm.weight"], cfg["rms_norm_eps"])
    return mm(x, params["lm_head.weight"], lowp)


def served_token_gaps(params, prompt, tokens, cfg: Dict[str, Any], lowp=None,
                      pad_to: int = 0, **control) -> Dict[str, Any]:
    """Teacher-forced check of one greedy request: the prompt plus the
    served tokens go through the reference once, and for every served
    token the gap by which its reference logit lies below the
    reference's best at that position is returned (0 where the
    reference would have served the same token).  With a control (a
    ``lowp``, ``use_window=False``, ``yarn=False`` or
    ``gates="softmax"``) the same is computed for the CONTROL's own
    greedy choice (``control_gap``): how far a program that computed the
    control's way would have strayed.  ``logits`` (and
    ``control_logits``) are the rows themselves, ``[served tokens,
    vocab]`` on the device, for ``logit_errors``.

    ``pad_to`` appends token 0 up to that length: under the causal mask
    nothing before a position depends on what follows it, and requests
    of many lengths then share ONE compiled shape."""
    prompt, tokens = np.asarray(prompt), np.asarray(tokens)
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    n, S = len(tokens), len(seq)
    ids = jnp.asarray(np.pad(seq, (0, max(0, pad_to - S))))
    rows = slice(S - n, S)
    logits = forward(params, ids, cfg, rows=rows)
    best = jnp.max(logits, -1)
    served = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], -1)[:, 0]
    out = {"gap": np.asarray(best - served), "logits": logits,
           "reference_tokens": np.asarray(jnp.argmax(logits, -1))}
    if lowp or control:
        other = forward(params, ids, cfg, lowp=lowp, rows=rows, **control)
        alt = jnp.argmax(other, -1)
        out["control_logits"] = other
        out["control_gap"] = np.asarray(
            best - jnp.take_along_axis(logits, alt[:, None], -1)[:, 0])
    return out


def logit_errors(logits, reference) -> np.ndarray:
    """For each row, the norm of ``logits - reference`` over the norm of
    ``reference``: how far a computation of the same positions lies from
    the reference, as a number that moves smoothly with its precision
    (a served token only says on which side of a near-tie it fell)."""
    logits, reference = jnp.asarray(logits, F32), jnp.asarray(reference, F32)
    return np.asarray(jnp.linalg.norm(logits - reference, axis=-1)
                      / jnp.linalg.norm(reference, axis=-1))

