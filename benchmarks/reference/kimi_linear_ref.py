"""Plain reference of the Kimi-Linear decoder on the serving path
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct,
``config.json``; ``model_type`` ``kimi_linear``): Kimi Delta Attention in
the layers ``linear_attn_config.kda_layers`` names, NoPE multi-head
latent attention in ``full_attn_layers`` (both 1-BASED), a dense SwiGLU
in the first ``first_k_dense_replace`` layers and sigmoid-routed experts
with one shared expert in the rest, and ONE chip's share of the routed
experts and of the vocabulary.

Straightforward ``jax.numpy`` in float32 with matmul precision
``highest``: the full sequence at once, no cache, no kernel, no batching.
The KDA recurrence is a ``lax.scan`` a TOKEN (the program's kernel works
in chunks: nothing is shared with it), latent attention is NOT absorbed
(a head's ``k_n`` and ``v`` are projected from ``c~`` for every position)
with a dense causal mask in blocks of query rows, the expert layer every
token through every HELD expert with its gate (0 where the expert was
not chosen), in blocks of 4096 rows, so that a 35k-token request fits
beside the weights on one chip.  It imports nothing of the program;
weights come from ``benchmarks/harness/weights_kimi_linear.py`` under the
leaf names listed there, upcast as they are used.  The arithmetic that
is no model's own (RMSNorm, blocks of rows, rounding to a lower
precision, the errors of logits and of states) is
``reference/mellum2_ref.py``'s and ``reference/nemotron_h_ref.py``'s.

Layer ``n`` (leaves ``model.layers.<n - 1>``): ``h = x +
Mixer_n(RMSNorm(x))``, ``y = h + FFN_n(RMSNorm(h))``, ``rms_norm_eps``.

- KDA (``H`` heads of ``d``), on the normed ``u``: ``q^ = silu(conv(u
  W_q))``, ``k^``, ``v`` alike, ``conv`` causal and depthwise over the
  last ``short_conv_kernel_size`` inputs, no bias (``w_3`` meets the
  current one; before the sequence: zeros); a head: ``q = q^ / sqrt(|q^|^2
  + 1e-6) * d^-1/2``, ``k = k^ / sqrt(|k^|^2 + 1e-6)``; ``g = -exp(A_log[h])
  * softplus((u W_fa W_fb)[h, :] + dt_bias[h, :])``, ``alpha = exp(g)`` a
  key channel; ``beta = sigmoid((u W_b)[h])``; ``S' = Diag(alpha) S``,
  ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T q`` (``S`` key x value,
  zeros before the sequence); ``o~ = RMSNorm_d(o; o_norm) * sigmoid((u
  W_ga W_gb)[h, :])``; ``out = concat_h(o~) W_o``.
- MLA: ``q = u W_q`` (a head: ``[q_n 128 | q_p 64]``); ``[c | k_p] = u
  W_kva``; ``c~ = RMSNorm(c)``; a head's ``[k_n | v] = c~ W_kvb``; ``k_h
  = [k_n | k_p]``, NOTHING rotated (``mla_use_nope``); causal softmax of
  ``q_h . k_h * 192^-1/2`` over the whole context; ``out = concat_h(p v)
  W_o``.
- Experts: ``s = sigmoid(h W_r)`` in float32 over the router's PUBLISHED
  width; the ``num_experts_per_token`` largest of ``s +
  e_score_correction_bias`` (one group); gates ``s[chosen] / (sum +
  1e-20) * routed_scaling_factor`` (over ALL chosen, held here or not);
  ``y = SwiGLU_shared(h) + sum over chosen e in [held) of g_e
  SwiGLU_e(h)``: what the absent experts would add is left out.
- After the last layer RMSNorm and the held slice of the untied head.

Departures from the published code: none in the arithmetic; what
``config.json`` does not give (the L2 norm's epsilon, the norm before
the gate in ``o_norm``, the float32 state) is listed under ``assumed`` in
the configuration's file and shared with the program.

Controls, each of which has to come out as not correct: ``lowp`` rounds
every matmul operand to a lower precision; ``state_dtype="bfloat16"``
keeps the state ``S`` in bf16 from token to token; ``decay_dtype=
"bfloat16"`` rounds the decay ``alpha`` to bf16 (a channel that forgets
slower than a part in 256 a token then never forgets); ``beta=False``
leaves ``beta`` out (1: every token overwrites what its key reads);
``gates="held"`` normalises the gates over the held experts only.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .mellum2_ref import (F32, HIGHEST, by_rows, layer_leaves, logit_errors,
                          rms_norm)
from .nemotron_h_ref import mm, narrow, state_errors

__all__ = ["forward", "served_token_gaps", "logit_errors", "held_range",
           "slow_heads", "state_errors"]

L2_EPS = 1e-6


def held_range(cfg) -> Tuple[int, int]:
    """The configuration file's share: ``num_experts`` experts are HELD,
    those of rank ``deployment_rank`` (0 where it is not given)."""
    n = cfg["num_experts"]
    lo = n * int(cfg.get("deployment_rank", 0))
    return lo, lo + n


def is_kda(cfg, i: int) -> bool:
    """Layer ``i`` (0-based) under its published, 1-based index."""
    lin = cfg["linear_attn_config"]
    if i + 1 in lin["kda_layers"]:
        return True
    if i + 1 in lin["full_attn_layers"]:
        return False
    raise ValueError(f"layer {i + 1} is in neither list")


def short_conv(x, w):
    """``silu`` of the causal depthwise convolution of ``x`` ``[S, C]``
    with taps ``w`` ``[K, C]`` (the last meets the current position)."""
    S, K = x.shape[0], w.shape[0]
    w = w.astype(F32)
    out = jnp.zeros_like(x)
    for back in range(K):
        past = jnp.concatenate([jnp.zeros((back, x.shape[1]), F32),
                                x[:S - back]]) if back else x
        out = out + past * w[K - 1 - back]
    return jax.nn.silu(out)


@functools.partial(jax.jit, static_argnames=("state_dtype",))
def _scan_tokens(q, k, v, alpha, beta, reset, state_dtype, keep=None):
    """The recurrence a token at a time: ``(o, kept)``.  ``q``, ``k``,
    ``v``, ``alpha`` ``[S, H, d]``, ``beta`` ``[S, H]``, ``reset``
    ``[S]``: the state is zeros before that token.  ``keep`` ``[S]``:
    ``kept`` is the state ``[H, d, d]`` AFTER the token it marks."""

    def one(carry, tok):
        s, kept = carry
        qt, kt, vt, at, bt, z, mark = tok
        s = jnp.where(z, 0.0, s) * at[:, :, None]
        got = jnp.sum(s * kt[:, :, None], 1)                  # S'^T k
        s = s + (bt[:, None] * kt)[:, :, None] * (vt - got)[:, None, :]
        s = narrow(s, state_dtype)
        if kept is not None:
            kept = jnp.where(mark, s, kept)
        return (s, kept), jnp.sum(s * qt[:, :, None], 1)

    H, d = q.shape[1], q.shape[2]
    zeros = jnp.zeros((H, d, d), F32)
    marks = jnp.zeros(q.shape[0], bool) if keep is None else keep
    (_, kept), o = jax.lax.scan(
        one, (zeros, None if keep is None else zeros),
        (q, k, v, alpha, beta, reset, marks))
    return o, kept


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


@functools.partial(jax.jit, static_argnames=(
    "H", "d", "eps", "lowp", "state_dtype", "decay_dtype", "beta"))
def _kda(x, lw, reset, keep, *, H, d, eps, lowp, state_dtype, decay_dtype,
         beta):
    """``kda``'s arithmetic as ONE compiled function a layer (op by op
    the reference compiled for minutes a sequence length)."""
    S = x.shape[0]
    a = "self_attn."
    u = rms_norm(x, lw["input_layernorm.weight"], eps)
    q, k, v = (short_conv(mm(u, lw[a + f"{n}_proj.weight"], lowp),
                          lw[a + f"{n}_conv1d.weight"]).reshape(S, H, d)
               for n in "qkv")
    q, k = l2_norm(q) * d ** -0.5, l2_norm(k)
    f = mm(mm(u, lw[a + "f_a_proj.weight"], lowp), lw[a + "f_b_proj.weight"],
           lowp).reshape(S, H, d)
    g = -jnp.exp(lw[a + "A_log"].astype(F32))[None, :, None] \
        * jax.nn.softplus(f + lw[a + "dt_bias"].astype(F32).reshape(H, d))
    alpha = narrow(jnp.exp(g), decay_dtype)
    b = jax.nn.sigmoid(mm(u, lw[a + "b_proj.weight"], lowp)) if beta \
        else jnp.ones((S, H), F32)
    o, kept = _scan_tokens(q, k, v, alpha, b, reset, state_dtype=state_dtype,
                           keep=keep)
    z = mm(mm(u, lw[a + "g_a_proj.weight"], lowp), lw[a + "g_b_proj.weight"],
           lowp).reshape(S, H, d)
    o = rms_norm(o, lw[a + "o_norm.weight"], eps) * jax.nn.sigmoid(z)
    return x + mm(o.reshape(S, H * d), lw[a + "o_proj.weight"], lowp), kept


def kda(x, lw, cfg, lowp=None, state_dtype=None, decay_dtype=None,
        beta: bool = True, zero_state_at=None, state_after=None,
        states=None):
    """``x + mixer``; x ``[S, hidden]``.  With ``state_after=n`` the
    state ``S`` that the first ``n`` tokens leave is appended to the list
    ``states``."""
    S = x.shape[0]
    lin = cfg["linear_attn_config"]
    reset = np.zeros(S, bool)
    if zero_state_at is not None and zero_state_at < S:
        reset[zero_state_at] = True
    keep = None if state_after is None \
        else jnp.asarray(np.arange(S) == state_after - 1)
    mixer = {n: w for n, w in lw.items() if n.startswith("self_attn.")
             or n == "input_layernorm.weight"}
    out, kept = _kda(x, mixer, jnp.asarray(reset), keep, H=lin["num_heads"],
                     d=lin["head_dim"], eps=float(cfg["rms_norm_eps"]),
                     lowp=lowp, state_dtype=state_dtype,
                     decay_dtype=decay_dtype, beta=bool(beta))
    if keep is not None:
        states.append(kept)
    return out


@functools.partial(jax.jit, static_argnames=("lowp",))
def _attention_block(q_n, q_p, pos_b, k_n, k_p, v, lowp):
    """A block of query rows (``q_n`` ``[B, H, dn]``, ``q_p`` ``[B, H,
    dr]``) at positions ``pos_b`` against the whole sequence's keys
    (``k_n`` ``[S, H, dn]``, ``k_p`` ``[S, dr]``) and values ``[S, H,
    dv]``."""
    seen = jnp.arange(k_n.shape[0])[None, :] <= pos_b[:, None]
    sc = jnp.einsum("bhd,shd->bhs", narrow(q_n, lowp), narrow(k_n, lowp),
                    precision=HIGHEST) \
        + jnp.einsum("bhd,sd->bhs", narrow(q_p, lowp), narrow(k_p, lowp),
                     precision=HIGHEST)
    sc = sc * (q_n.shape[-1] + q_p.shape[-1]) ** -0.5
    p = jax.nn.softmax(jnp.where(seen[:, None, :], sc, -jnp.inf), -1)
    return jnp.einsum("bhs,shd->bhd", narrow(p, lowp), narrow(v, lowp),
                      precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("H", "dn", "dr", "dv", "eps",
                                             "lowp"))
def _mla_qkv(x, lw, *, H, dn, dr, dv, eps, lowp):
    """``(q [S, H, dn + dr], k_n [S, H, dn], k_p [S, dr], v [S, H, dv])``."""
    S, a = x.shape[0], "self_attn."
    dc = lw[a + "kv_a_layernorm.weight"].shape[0]
    u = rms_norm(x, lw["input_layernorm.weight"], eps)
    q = mm(u, lw[a + "q_proj.weight"], lowp).reshape(S, H, dn + dr)
    kva = mm(u, lw[a + "kv_a_proj_with_mqa.weight"], lowp)
    c = rms_norm(kva[:, :dc], lw[a + "kv_a_layernorm.weight"], eps)
    kv = mm(c, lw[a + "kv_b_proj.weight"], lowp).reshape(S, H, dn + dv)
    return q, kv[..., :dn], kva[:, dc:], kv[..., dn:]


def mla(x, lw, cfg, lowp=None, q_block: int = 128):
    """``x + attention``; no rotary embedding."""
    S = x.shape[0]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    q, k_n, k_p, v = _mla_qkv(x, lw, H=H, dn=dn, dr=dr, dv=dv,
                              eps=float(cfg["rms_norm_eps"]), lowp=lowp)
    pos = jnp.arange(S)
    if lowp:
        q_block = min(q_block, 32)
    outs = [_attention_block(q[s0:s0 + q_block, :, :dn],
                             q[s0:s0 + q_block, :, dn:],
                             pos[s0:s0 + q_block], k_n, k_p, v, lowp=lowp)
            for s0 in range(0, S, q_block)]
    ctx = jnp.concatenate(outs, 0).reshape(S, H * dv)
    return x + mm(ctx, lw["self_attn.o_proj.weight"], lowp)


def swiglu(h, lw, pre, lowp=None):
    return mm(jax.nn.silu(mm(h, lw[pre + "gate_proj.weight"], lowp))
              * mm(h, lw[pre + "up_proj.weight"], lowp),
              lw[pre + "down_proj.weight"], lowp)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _dense_ffn(x, lw, *, eps, lowp):
    """``x + SwiGLU(RMSNorm(x))`` of a block of rows."""
    return x + swiglu(rms_norm(x, lw["post_attention_layernorm.weight"], eps),
                      lw, "mlp.", lowp)


def route(h, lw, cfg, held: Tuple[int, int], gates: str = "chosen"):
    """``(chosen [S, k] expert ids, gates [S, k])`` over the router's
    full width; float32 throughout."""
    if int(cfg.get("num_expert_group", 1)) != 1 \
            or not cfg.get("moe_renormalize", True):
        raise ValueError("this reference routes over one group and "
                         "renormalises the chosen gates")
    return _route(h, lw["mlp.router.weight"], lw["mlp.router.bias"],
                  k=int(cfg["num_experts_per_token"]),
                  scale=float(cfg["routed_scaling_factor"]),
                  held=tuple(held), gates=gates)


@functools.partial(jax.jit, static_argnames=("k", "scale", "held", "gates"))
def _route(h, router, bias, *, k, scale, held, gates):
    s = jax.nn.sigmoid(jnp.matmul(h, router.astype(F32), precision=HIGHEST))
    _, chosen = jax.lax.top_k(s + bias.astype(F32), k)
    top = jnp.take_along_axis(s, chosen, -1)
    if gates == "chosen":
        norm = jnp.sum(top, -1, keepdims=True)
    elif gates == "held":
        here = (chosen >= held[0]) & (chosen < held[1])
        norm = jnp.sum(jnp.where(here, top, 0.0), -1, keepdims=True)
    else:
        raise ValueError(f"gates over {gates!r}?")
    return chosen, top / (norm + 1e-20) * scale


@functools.partial(jax.jit, static_argnames=("lo", "lowp"))
def _experts_block(h, chosen, top, gate, up, down, lo, lowp):
    """A block of rows through the held bank, an expert at a time (a
    ``lax.scan`` over the stacked slices)."""

    def one(y, ew):
        e, g_w, u_w, d_w = ew
        ge = jnp.sum(jnp.where(chosen == e, top, 0.0), -1, keepdims=True)
        act = jax.nn.silu(mm(h, g_w, lowp)) * mm(h, u_w, lowp)
        return y + ge * mm(act, d_w, lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (lo + jnp.arange(up.shape[0]), gate, up, down))
    return y


def expert_layer(h, lw, cfg, held: Tuple[int, int], lowp=None,
                 gates: str = "chosen", parts: bool = False):
    """The layer's output on normed rows ``h``: the held experts' part
    plus the shared expert.  ``parts``: the two apart, ``(routed,
    shared)`` (the share test adds the routed parts of all shares to one
    shared part)."""
    chosen, top = route(h, lw, cfg, held, gates)
    routed = _experts_block(
        h, chosen, top, lw["mlp.experts.gate_proj.weight"],
        lw["mlp.experts.up_proj.weight"], lw["mlp.experts.down_proj.weight"],
        lo=held[0], lowp=lowp)
    shared = _shared(h, {n: w for n, w in lw.items()
                         if n.startswith("mlp.shared_expert.")}, lowp=lowp)
    return (routed, shared) if parts else routed + shared


@functools.partial(jax.jit, static_argnames=("lowp",))
def _shared(h, lw, *, lowp):
    return swiglu(h, lw, "mlp.shared_expert.", lowp)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, *, eps):
    return rms_norm(x, w, eps)


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any], held=None,
            lowp=None, state_dtype=None, decay_dtype=None, beta: bool = True,
            zero_state_at=None, gates: str = "chosen", q_block: int = 128,
            rows: Optional[slice] = None, upto: Optional[int] = None,
            state_after: Optional[int] = None, states: Optional[list] = None):
    """Logits ``[S, vocab]`` of token ids ``[S]`` (of the positions
    ``rows`` alone where given).  ``upto`` stops after that many layers
    and returns the residual stream ``[S, hidden]`` instead.  With
    ``state_after=n`` every KDA layer appends to ``states`` the state
    ``[heads, head_dim, head_dim]`` that the first ``n`` tokens leave."""
    held = held or held_range(cfg)
    eps = cfg["rms_norm_eps"]
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"])[:upto]:
        lw = layer_leaves(params, i)
        if is_kda(cfg, i):
            x = kda(x, lw, cfg, lowp, state_dtype, decay_dtype, beta,
                    zero_state_at, state_after, states)
        else:
            x = mla(x, lw, cfg, lowp, q_block)
        if i < cfg["first_k_dense_replace"]:
            ffn = {n: w for n, w in lw.items() if n.startswith("mlp.")
                   or n == "post_attention_layernorm.weight"}
            x = by_rows(lambda xb: _dense_ffn(xb, ffn, eps=float(eps),
                                              lowp=lowp), x)
        else:
            x = by_rows(lambda xb: xb + expert_layer(
                _normed(xb, lw["post_attention_layernorm.weight"],
                        eps=float(eps)), lw, cfg, held, lowp, gates), x)
    if upto is not None:
        return x
    if rows is not None:
        x = x[rows]
    x = rms_norm(x, params["model.norm.weight"], eps)
    return mm(x, params["lm_head.weight"], lowp)


def slow_heads(params, cfg: Dict[str, Any], share: int = 8) -> np.ndarray:
    """``[KDA layers, heads / share]``: in each KDA layer the heads whose
    state decays slowest at rest (``exp(A_log) * mean softplus(dt_bias)``
    least; 4 of 32): they remember over hundreds of tokens, so what is
    lost or rounded away a token at a time adds up in them and shows in
    no logit."""
    lin, out = cfg["linear_attn_config"], []
    for i in range(cfg["num_hidden_layers"]):
        if is_kda(cfg, i):
            lw = layer_leaves(params, i)
            rest = jax.nn.softplus(lw["self_attn.dt_bias"].astype(F32)
                                   ).reshape(lin["num_heads"], -1).mean(-1)
            rate = jnp.exp(lw["self_attn.A_log"].astype(F32)) * rest
            out.append(np.argsort(np.asarray(rate))[:max(1, len(rate) // share)])
    return np.stack(out)


def served_token_gaps(params, prompt, tokens, cfg: Dict[str, Any],
                      pad_to: int = 0, states: bool = False,
                      **control) -> Dict[str, Any]:
    """Teacher-forced check of one greedy request, as
    ``nemotron_h_ref.served_token_gaps``: the gap by which each served
    token's reference logit lies below the reference's best (``gap``),
    the rows themselves (``logits``), and with a control the same of the
    CONTROL's own greedy choices and rows (``control_gap``,
    ``control_logits``).  ``pad_to`` appends token 0 up to that length:
    nothing before a position depends on what follows it.  ``states``:
    also the recurrent states the PROMPT leaves, ``[KDA layers, heads,
    head_dim, head_dim]`` (``states``, and ``control_states``)."""
    prompt, tokens = np.asarray(prompt), np.asarray(tokens)
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    n, S = len(tokens), len(seq)
    ids = jnp.asarray(np.pad(seq, (0, max(0, pad_to - S))))
    rows = slice(S - n, S)
    after = len(prompt) if states else None
    kept, kept_other = [], []
    logits = forward(params, ids, cfg, rows=rows, state_after=after,
                     states=kept)
    best = jnp.max(logits, -1)
    served = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], -1)[:, 0]
    out = {"gap": np.asarray(best - served), "logits": logits,
           "reference_tokens": np.asarray(jnp.argmax(logits, -1))}
    if control:
        other = forward(params, ids, cfg, rows=rows, state_after=after,
                        states=kept_other, **control)
        alt = jnp.argmax(other, -1)
        out["control_logits"] = other
        out["control_gap"] = np.asarray(
            best - jnp.take_along_axis(logits, alt[:, None], -1)[:, 0])
    if states:
        out["states"] = np.stack([np.asarray(k) for k in kept])
        if control:
            out["control_states"] = np.stack([np.asarray(k) for k in kept_other])
    return out
