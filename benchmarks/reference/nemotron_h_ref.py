"""Plain reference of the Nemotron-H decoder on the serving path
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``config.json``; ``model_type`` ``nemotron_h``): every layer is ONE part,
by the letters of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer,
``*`` grouped-query attention, ``E`` a LatentMoE expert layer.

Straightforward ``jax.numpy`` in float32 with matmul precision
``highest``: the full sequence at once, no cache, no kernel, no batching.
The Mamba-2 recurrence is a ``lax.scan`` a TOKEN (the program's kernel
works in chunks of 128: nothing is shared with it), attention a dense
causal mask in blocks of query rows, the expert layer every token
through every HELD expert with its gate (0 where the expert was not
chosen), in blocks of 4096 rows, so that a 7.7k-token request fits
beside the weights on one chip.  It imports nothing of the program;
weights come from ``benchmarks/harness/weights_nemotron_h.py`` under the
leaf names listed there, upcast as they are used.  The arithmetic that
is no model's own (RMSNorm, blocks of rows, the error of a row of
logits) is ``reference/mellum2_ref.py``'s.

Layer ``i``: ``x <- x + part_i(RMSNorm(x))``, ``norm_eps`` 1e-5.

- ``M``: ``[z | xBC | dt] = u W_in`` (widths ``d_in = heads x head_dim``,
  ``d_in + 2 G N``, ``heads``); ``xBC_t <- silu(sum_k w_k xBC_{t-3+k} +
  b)``, a causal depthwise convolution over 4 positions (``w_3`` meets
  the current one; before the sequence: zeros); ``xBC`` splits into ``x
  [heads, head_dim]``, ``B [G, N]``, ``C [G, N]``, head h reading group
  ``h // (heads / G)``; ``dt <- softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t
  = S_t C_t + D x_t``; ``y <- (y * silu(z))`` RMS-normed over each of the
  G groups of ``d_in / G``, times a gain; ``out = y W_out``.
- ``*``: GQA, no bias, causal over the whole context, scale
  ``head_dim^-0.5``, NO rotary embedding (the family's convention, under
  ``assumed`` in the configuration's file).
- ``E``: ``s = sigmoid(u W_r)`` in float32; the ``num_experts_per_tok``
  largest of ``s + e_score_correction_bias`` (``n_group`` 1); gates ``g_e
  = routed_scaling_factor * s_e / sum_chosen s`` (over ALL chosen, held
  here or not); ``l = u W_down`` into the latent; ``y = (sum_{e chosen,
  held} g_e W2_e relu(W1_e l)^2) W_up + W_d relu(W_u u)^2``: what the
  absent experts would add is left out.
- After the last layer RMSNorm and the held slice of the untied head.
  The multi-token-prediction module is not loaded.

Controls, each of which has to come out as not correct: ``lowp`` rounds
every matmul operand to a lower precision (per-tensor scaled for fp8);
``state_dtype="bfloat16"`` keeps the recurrent state ``S`` in bf16 from
token to token (both by ``lax.reduce_precision``, an operation of its
own: a pair of converts, to the narrow type and back, is what a compiler
that is allowed excess precision removes, and the TPU's removed it: the
state's control read 0.0 on the chip); ``zero_state_at=P`` starts position P from a zero state
and an empty convolution window (a restore that took zeros for the
snapshot at P); ``conv_runs`` (positions) empties the convolution's
window at each of them (the tail dropped where a launch's run of rows
begins: every chunk's first row, every decode row); ``gates="held"``
normalises the gates over the chosen experts that are held here.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .mellum2_ref import (F32, HIGHEST, by_rows, layer_leaves, logit_errors,
                          rms_norm)

__all__ = ["forward", "served_token_gaps", "logit_errors", "held_range",
           "slow_heads", "state_errors"]


#: (exponent bits, mantissa bits, largest finite value) of the formats a
#: control rounds to; fp8 is e4m3 with IEEE's top exponent kept for
#: infinities, as ``lax.reduce_precision`` has it
_FORMATS = {"bf16": (8, 7, None), "bfloat16": (8, 7, None),
            "fp8": (4, 3, 240.0)}


def narrow(x, lowp: Optional[str]):
    """``x`` rounded to the format ``lowp`` and read back as float32;
    where the format's range is short, scaled so that the tensor's
    largest magnitude is its largest finite value."""
    if lowp is None:
        return x
    e, m, top = _FORMATS[lowp]
    if top is None:
        return jax.lax.reduce_precision(x, e, m)
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.reduce_precision(x * s, e, m) / s


def mm(a, b, lowp=None):
    return jnp.matmul(narrow(a.astype(F32), lowp), narrow(b.astype(F32), lowp),
                      precision=HIGHEST)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def eps_of(cfg) -> float:
    return float(cfg.get("norm_eps", cfg.get("layer_norm_epsilon", 1e-5)))


def held_range(cfg) -> Tuple[int, int]:
    """The configuration file's share: ``n_routed_experts`` experts are
    HELD, those of rank ``deployment_rank`` (0 where it is not given)."""
    n = cfg["n_routed_experts"]
    lo = n * int(cfg.get("deployment_rank", 0))
    return lo, lo + n


@functools.partial(jax.jit, static_argnames=("state_dtype",))
def _scan_tokens(xs, dt, A, B, C, reset, state_dtype, keep=None):
    """The recurrence a token at a time: ``(y, kept)``.  ``xs`` ``[S, H,
    P]``, ``dt`` ``[S, H]``, ``A`` ``[H]``, ``B`` / ``C`` ``[S, H, N]``
    (a head's group's), ``reset`` ``[S]``: the state is zeros before that
    token.  ``keep`` ``[S]``: ``kept`` is the state ``[H, P, N]`` AFTER
    the token it marks (None where no mask is given)."""

    def one(carry, tok):
        s, kept = carry
        x, d, b, c, z, k = tok
        s = jnp.where(z, 0.0, s)
        s = s * jnp.exp(d * A)[:, None, None] \
            + (x * d[:, None])[:, :, None] * b[:, None, :]
        s = narrow(s, state_dtype)
        if kept is not None:
            kept = jnp.where(k, s, kept)
        return (s, kept), jnp.sum(s * c[:, None, :], -1)

    H, P, N = xs.shape[1], xs.shape[2], B.shape[-1]
    zeros = jnp.zeros((H, P, N), F32)
    marks = jnp.zeros(xs.shape[0], bool) if keep is None else keep
    (_, kept), y = jax.lax.scan(
        one, (zeros, None if keep is None else zeros),
        (xs, dt, B, C, reset, marks))
    return y, kept


def mamba(x, lw, cfg, lowp=None, state_dtype=None, zero_state_at=None,
          conv_runs=None, state_after=None, states=None):
    """``x + mixer``; x ``[S, hidden]``.  With ``state_after=n`` the
    state ``S`` that the first ``n`` tokens leave is appended to the list
    ``states``."""
    S = x.shape[0]
    H, P, G, N, K = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                     cfg["n_groups"], cfg["ssm_state_size"],
                     cfg["conv_kernel"])
    d_in, cd = H * P, H * P + 2 * G * N
    u = rms_norm(x, lw["norm.weight"], eps_of(cfg))
    zxd = mm(u, lw["mixer.in_proj.weight"], lowp)
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + cd], zxd[:, d_in + cd:]
    # where a window's history begins: the sequence's first token, and
    # whatever a control empties
    starts = np.zeros(S, bool)
    starts[0] = True
    if zero_state_at is not None and zero_state_at < S:
        starts[zero_state_at] = True
    if conv_runs is not None:
        starts[np.asarray(conv_runs)[np.asarray(conv_runs) < S]] = True
    pos = np.arange(S)
    begin = jnp.asarray(np.maximum.accumulate(np.where(starts, pos, 0)))
    w = lw["mixer.conv1d.weight"].astype(F32)               # [K, cd]
    conv = jnp.zeros_like(xbc) + lw["mixer.conv1d.bias"].astype(F32)
    for back in range(K):
        past = jnp.concatenate([jnp.zeros((back, cd), F32),
                                xbc[:S - back]]) if back else xbc
        seen = (jnp.asarray(pos) - back >= begin)[:, None]
        conv = conv + jnp.where(seen, past, 0.0) * w[K - 1 - back]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_in].reshape(S, H, P)
    B = jnp.repeat(xbc[:, d_in:d_in + G * N].reshape(S, G, N), H // G, 1)
    C = jnp.repeat(xbc[:, d_in + G * N:].reshape(S, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + lw["mixer.dt_bias"].astype(F32))
    A = -jnp.exp(lw["mixer.A_log"].astype(F32))
    reset = np.zeros(S, bool)
    if zero_state_at is not None and zero_state_at < S:
        reset[zero_state_at] = True
    keep = None if state_after is None else jnp.asarray(pos == state_after - 1)
    y, kept = _scan_tokens(xs, dt, A, B, C, jnp.asarray(reset),
                           state_dtype=state_dtype, keep=keep)
    if keep is not None:
        states.append(kept)
    y = y + xs * lw["mixer.D"].astype(F32)[None, :, None]
    y = (y.reshape(S, d_in) * jax.nn.silu(z)).reshape(S, G, d_in // G)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + eps_of(cfg))
    y = y.reshape(S, d_in) * lw["mixer.norm.weight"].astype(F32)
    return x + mm(y, lw["mixer.out_proj.weight"], lowp)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _attention_block(q_b, pos_b, k, v, lowp):
    """A block of query rows ``[B, kvh, rep, d]`` at positions ``pos_b``
    against the whole sequence's keys and values ``[S, kvh, d]``."""
    S, d = k.shape[0], k.shape[-1]
    seen = jnp.arange(S)[None, :] <= pos_b[:, None]
    sc = jnp.einsum("bgrd,sgd->bgrs", narrow(q_b, lowp), narrow(k, lowp),
                    precision=HIGHEST) * d ** -0.5
    p = jax.nn.softmax(jnp.where(seen[:, None, None, :], sc, -jnp.inf), -1)
    return jnp.einsum("bgrs,sgd->bgrd", narrow(p, lowp), narrow(v, lowp),
                      precision=HIGHEST)


def attention(x, lw, cfg, lowp=None, q_block: int = 128):
    """``x + attention``; no rotary embedding."""
    S = x.shape[0]
    H, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    a = rms_norm(x, lw["norm.weight"], eps_of(cfg))
    q = mm(a, lw["self_attn.q_proj.weight"], lowp).reshape(S, kvh, H // kvh, d)
    k = mm(a, lw["self_attn.k_proj.weight"], lowp).reshape(S, kvh, d)
    v = mm(a, lw["self_attn.v_proj.weight"], lowp).reshape(S, kvh, d)
    pos = jnp.arange(S)
    if lowp:
        q_block = min(q_block, 32)
    outs = [_attention_block(q[s0:s0 + q_block], pos[s0:s0 + q_block], k, v,
                             lowp=lowp)
            for s0 in range(0, S, q_block)]
    ctx = jnp.concatenate(outs, 0).reshape(S, H * d)
    return x + mm(ctx, lw["self_attn.o_proj.weight"], lowp)


def route(h, lw, cfg, held: Tuple[int, int], gates: str = "chosen"):
    """``(chosen [S, k] expert ids, gates [S, k])`` over the router's
    full width; float32 throughout."""
    s = jax.nn.sigmoid(jnp.matmul(h, lw["mlp.router.weight"].astype(F32),
                                  precision=HIGHEST))
    pick = s + lw["mlp.router.bias"].astype(F32)
    if int(cfg.get("n_group", 1)) != 1:
        raise ValueError("this reference routes over one group")
    _, chosen = jax.lax.top_k(pick, int(cfg["num_experts_per_tok"]))
    top = jnp.take_along_axis(s, chosen, -1)
    if gates == "chosen":
        norm = jnp.sum(top, -1, keepdims=True)
    elif gates == "held":
        here = (chosen >= held[0]) & (chosen < held[1])
        norm = jnp.sum(jnp.where(here, top, 0.0), -1, keepdims=True)
    else:
        raise ValueError(f"gates over {gates!r}?")
    return chosen, top / (norm + 1e-20) * float(cfg["routed_scaling_factor"])


@functools.partial(jax.jit, static_argnames=("lo", "lowp"))
def _experts_block(lat, chosen, top, up, down, lo, lowp):
    """A block of latent rows through the held bank, an expert at a time
    (a ``lax.scan`` over the stacked slices)."""

    def one(y, ew):
        e, u_w, d_w = ew
        ge = jnp.sum(jnp.where(chosen == e, top, 0.0), -1, keepdims=True)
        return y + ge * mm(relu2(mm(lat, u_w, lowp)), d_w, lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                        (lo + jnp.arange(up.shape[0]), up, down))
    return y


def expert_layer(h, lw, cfg, held: Tuple[int, int], lowp=None,
                 gates: str = "chosen", parts: bool = False):
    """The layer's output on normed rows ``h``: the held experts' part in
    the latent, projected up, plus the shared expert.  ``parts``: the two
    apart, ``(routed, shared)`` (the share test adds the routed parts of
    all shares to one shared part)."""
    chosen, top = route(h, lw, cfg, held, gates)
    lat = mm(h, lw["mlp.latent_down.weight"], lowp)
    y = _experts_block(lat, chosen, top, lw["mlp.experts.up_proj.weight"],
                       lw["mlp.experts.down_proj.weight"], lo=held[0],
                       lowp=lowp)
    routed = mm(y, lw["mlp.latent_up.weight"], lowp)
    shared = mm(relu2(mm(h, lw["mlp.shared_expert.up_proj.weight"], lowp)),
                lw["mlp.shared_expert.down_proj.weight"], lowp)
    return (routed, shared) if parts else routed + shared


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any], held=None,
            lowp=None, state_dtype=None, zero_state_at=None, conv_runs=None,
            gates: str = "chosen", q_block: int = 128,
            rows: Optional[slice] = None, upto: Optional[int] = None,
            state_after: Optional[int] = None, states: Optional[list] = None):
    """Logits ``[S, vocab]`` of token ids ``[S]`` (of the positions
    ``rows`` alone where given).  ``upto`` stops after that many layers
    and returns the residual stream ``[S, hidden]`` instead.  With
    ``state_after=n`` every ``M`` layer appends to ``states`` the state
    ``[heads, head_dim, state]`` that the first ``n`` tokens leave."""
    held = held or held_range(cfg)
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    for i, letter in enumerate(pattern[:upto]):
        lw = layer_leaves(params, i)
        if letter == "M":
            x = mamba(x, lw, cfg, lowp, state_dtype, zero_state_at, conv_runs,
                      state_after, states)
        elif letter == "*":
            x = attention(x, lw, cfg, lowp, q_block)
        elif letter == "E":
            h = rms_norm(x, lw["norm.weight"], eps_of(cfg))
            x = x + by_rows(lambda hb: expert_layer(hb, lw, cfg, held, lowp,
                                                    gates), h)
        else:
            raise ValueError(f"layer {i} is {letter!r}: M, * or E")
    if upto is not None:
        return x
    if rows is not None:
        x = x[rows]
    x = rms_norm(x, params["model.norm.weight"], eps_of(cfg))
    return mm(x, params["lm_head.weight"], lowp)


def slow_heads(params, cfg: Dict[str, Any], share: int = 16) -> np.ndarray:
    """``[state layers, heads / share]``: in each ``M`` layer the heads
    whose state decays slowest at rest (``exp(A_log) * softplus(dt_bias)``
    least; 8 of 128): they remember over hundreds of tokens, so what is
    lost or rounded away a token at a time adds up in them (a per-token
    rounding of 0.16% walks to 2 to 6% there and to 0.3% in the fastest
    32: PERF.md, PR 33) and shows in no logit."""
    out = []
    for i, letter in enumerate(
            cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]):
        if letter == "M":
            lw = layer_leaves(params, i)
            rate = jnp.exp(lw["mixer.A_log"].astype(F32)) * jax.nn.softplus(
                lw["mixer.dt_bias"].astype(F32))
            out.append(np.argsort(np.asarray(rate))[:max(1, len(rate) // share)])
    return np.stack(out)


def state_errors(got, want, heads: np.ndarray) -> np.ndarray:
    """``[state layers, len(heads[0])]``: for each of ``heads`` the norm
    of the difference between the states ``got`` and ``want`` (each
    ``[state layers, heads, head_dim, state]``) over the norm of
    ``want``'s."""
    got = np.take_along_axis(np.asarray(got, np.float32),
                             heads[:, :, None, None], 1)
    want = np.take_along_axis(np.asarray(want, np.float32),
                              heads[:, :, None, None], 1)
    norm = lambda a: np.sqrt(np.sum(np.square(a, dtype=np.float64), (-2, -1)))
    return norm(got - want) / np.maximum(norm(want), 1e-30)


def served_token_gaps(params, prompt, tokens, cfg: Dict[str, Any],
                      pad_to: int = 0, states: bool = False,
                      **control) -> Dict[str, Any]:
    """Teacher-forced check of one greedy request, as
    ``mellum2_ref.served_token_gaps``: the gap by which each served
    token's reference logit lies below the reference's best (``gap``),
    the rows themselves (``logits``), and with a control the same of the
    CONTROL's own greedy choices and rows (``control_gap``,
    ``control_logits``).  ``pad_to`` appends token 0 up to that length:
    nothing before a position depends on what follows it.  ``states``:
    also the recurrent states the PROMPT leaves, ``[state layers, heads,
    head_dim, state]`` (``states``, and ``control_states``)."""
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
