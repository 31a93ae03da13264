"""Plain reference of the MiniCPM-SALA decoder on the serving path
(https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json,
``model_type`` ``minicpm_sala``): each layer takes its mixer from
``mixer_types``, ``minicpm4`` (InfLLM-V2 block-sparse attention) or
``lightning-attn`` (Lightning linear attention), then a SwiGLU MLP.

Straightforward ``jax.numpy`` in float32 with matmul precision
``highest``: one full forward pass of the sequence, no kernel, no paged
cache, no batching; it imports nothing of the program.  So that 70k
tokens fit beside the weights on one chip, what is a row's own business
(norms, projections, the MLP) runs in blocks of rows, the recurrence in
chunks of 64 rows in closed form that hand the state on, and attention
in blocks of query rows against the keys up to the block's end; and so
that three requests of one session cost one pass of its history, a pass
can hand on what a sequence's first tokens leave (``forward``'s
``keep_prefix`` and ``prefix``).  Weights come from
``benchmarks/harness/weights_minicpm_sala.py`` under the leaf names
listed there, upcast as they are used.  The arithmetic that is no
model's own (RMSNorm, blocks of rows, the error of a row of logits, the
rounding of the low-precision control, the states' error) is
``reference/mellum2_ref.py``'s and ``reference/nemotron_h_ref.py``'s.

With ``L`` the PUBLISHED depth (``published.num_hidden_layers``) and
``l`` a layer's published index (the layers that run are ``layers_run =
[first, one past the last]``):

- ``h_0 = scale_emb E[token]``; ``h += (scale_depth / sqrt(L))
  Mixer_l(RMSNorm(h))``; ``h += (scale_depth / sqrt(L)) W_d(silu(W_g u)
  * W_u u)``, ``u = RMSNorm(h)``; ``logits = W_head (RMSNorm(h) /
  (hidden_size / dim_model_base))``; ``rms_norm_eps`` 1e-6, no biases.
- ``lightning-attn``: ``q, k, v = W_q x, W_k x, W_v x``, ``H`` heads of
  ``d``; RMSNorm with a gain ``[d]`` over each head of ``q`` and of
  ``k``; rotary on both (theta ``rope_theta``, the whole head, halves
  paired); ``S_t = lambda S_{t-1} + k_t v_t^T`` (``[d, d]`` a head,
  zeros at the start), ``o_t = S_t^T q_t / sqrt(d)``, ``lambda =
  exp(-s_h (1 - l / (L - 1) + 1e-5))``, ``s_h = 2^(-8 (h + 1) / H)``;
  out ``W_o (sigmoid(W_gate x) * RMSNorm_{H d}(concat o))``.
- ``minicpm4`` (``sparse_config``: ``kernel_size`` 32, ``kernel_stride``
  16, ``block_size`` 64, ``topk`` 64, ``init_blocks`` 1,
  ``window_size`` 2048, ``dense_len`` 8192): no rotary, no q/k norm.  A
  row at position ``t`` with context ``n = t + 1``: if ``n <=
  dense_len``, causal softmax attention over all ``n`` keys, scale ``1 /
  sqrt(d)``.  Else compressed keys ``c_j = mean(k[16 j : 16 j + 32])``
  for every ``j`` with ``16 j + 32 <= n``; ``p_h = softmax_j(q_h . c_j
  / sqrt(d))``; a K/V group's score of ``j`` is the sum of ``p_h`` over
  its heads; block ``b`` (tokens ``[64 b, 64 b + 64)``) scores the
  maximum over the compressed keys that overlap it; the ``init_blocks``
  first blocks and the blocks that overlap the last ``window_size``
  tokens score infinity; the ``topk`` highest blocks (ties: the lower)
  are the group's selection, and attention is causal softmax over their
  tokens alone.  Out ``W_o (sigmoid(W_gate x) * concat o)``.
  Departures from the family's code, both noted in the configuration's
  ``assumed``: the dense/sparse switch is by the ROW's context (a
  token's output does not depend on what follows it), and the second,
  coarser pooling stage (a speed-up of the same selection) is left out.

Controls, each of which has to come out as not correct: ``lowp`` rounds
every matmul operand to a lower precision (``nemotron_h_ref.narrow``);
``dense_all`` lets every row attend its whole context; ``no_forced``
leaves the forced first and local blocks out of the selection;
``zero_state_at=P`` starts position P from a zero state (a restore that
took zeros for the snapshot at P); ``no_decay`` leaves the decay out
(``lambda`` 1); ``no_gate`` leaves the attention layers' output gate
out.

``selection`` (no control): ``{published layer: (positions [n], blocks
[n, kvh, topk])}`` GIVES the rows at those positions their selection
instead of taking the reference's own.  Float32 and bf16 order near-tied
blocks differently (a few of a row's 64), and one block is a 64th of
what the row attends: a check that wants to see the arithmetic behind
the program's logits hands the reference the program's own selection at
the positions it compares, and the reference computes everything else,
the selection of every other row included.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .mellum2_ref import (F32, HIGHEST, by_rows, layer_leaves, logit_errors,
                          rms_norm)
from .nemotron_h_ref import mm, narrow, state_errors

__all__ = ["forward", "served_token_gaps", "logit_errors", "slow_heads",
           "state_errors", "decay"]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def depth_of(cfg) -> int:
    """The PUBLISHED depth, which the scalings and the decays read."""
    return int(cfg.get("published", {}).get("num_hidden_layers",
                                            cfg["num_hidden_layers"]))


def layers_of(cfg):
    lo, hi = cfg.get("layers_run") or (0, depth_of(cfg))
    return range(int(lo), int(hi))


def decay(cfg, l: int) -> np.ndarray:
    """``log lambda`` of each head of published layer ``l``."""
    H = int(cfg["lightning_nh"])
    s = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    return (-s * (1.0 - l / (depth_of(cfg) - 1) + 1e-5)).astype(np.float32)


def rotary(x, pos, theta: float):
    """``x`` ``[S, H, d]`` rotated by ``pos`` ``[S]``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-b, a], -1) * sin


@jax.jit
def _scan_chunks(s0, seg0, q, k, v, loglam, seg, keep):
    """The recurrence ``S_t = lambda S_{t-1} + k_t v_t^T``, ``o_t =
    S_t^T q_t`` over whole chunks of rows, a chunk at a time in closed
    form (a token at a time 70k tokens are 70k launches a layer): inside
    a chunk token ``i`` reads token ``j <= i`` through ``lambda^(i - j)
    (q_i . k_j) v_j`` and the state before the chunk through
    ``lambda^(i + 1) S^T q_i``.  ``q``, ``k``, ``v`` ``[chunks, C, H,
    d]``; ``s0`` ``[H, d, d]`` (key by value) the state before, of
    segment ``seg0``; ``seg`` ``[chunks, C]``: a token reads nothing of
    another segment (a reset starts one); ``keep`` ``[chunks, K]``: the
    index in the chunk after which the k-th kept state is read (below
    zero: not in this chunk).  Returns ``(o [chunks, C, H, d], state
    after, its segment, kept [K, H, d, d])``."""
    C = q.shape[1]
    i = jnp.arange(C)
    ein = functools.partial(jnp.einsum, precision=HIGHEST)

    def state_after(s, sg, kc, vc, sc, r):
        """The state after the chunk's token ``r``."""
        own = (i <= r) & (sc == sc[r])
        w = jnp.where(own[:, None], jnp.exp((r - i)[:, None] * loglam), 0.0)
        carried = jnp.where(sc[r] == sg, jnp.exp((r + 1) * loglam), 0.0)
        return s * carried[:, None, None] \
            + ein("jh,jhd,jhe->hde", w, kc, vc)

    def one(carry, chunk):
        s, sg, kept = carry
        qc, kc, vc, sc, at = chunk
        same = (i[:, None] >= i[None, :]) & (sc[:, None] == sc[None, :])
        dec = jnp.where(same[None], jnp.exp(
            (i[:, None] - i[None, :])[None] * loglam[:, None, None]), 0.0)
        a = ein("ihd,jhd->hij", qc, kc) * dec
        into = jnp.where((sc == sg)[:, None],
                         jnp.exp((i + 1)[:, None] * loglam), 0.0)     # [C, H]
        o = ein("hij,jhe->ihe", a, vc) \
            + ein("hde,ihd->ihe", s, qc) * into[:, :, None]
        kept = jnp.stack([jnp.where(
            r >= 0, state_after(s, sg, kc, vc, sc, jnp.maximum(r, 0)), old)
            for r, old in zip(at, kept)])
        return (state_after(s, sg, kc, vc, sc, C - 1), sc[C - 1], kept), o

    kept = jnp.zeros((keep.shape[1], *s0.shape), F32)
    (s, sg, kept), o = jax.lax.scan(one, (s0, seg0, kept), (q, k, v, seg, keep))
    return o, s, sg, kept


def lightning(x, lw, cfg, l: int, lowp=None, zero_state_at=None,
              no_decay=False, keep=(), pos0: int = 0, s0=None,
              block: int = 4096, chunk: int = 64):
    """``(x + scale * mixer, kept)``; x ``[S, hidden]``, the rows at
    positions ``pos0 ..`` of a sequence whose first ``pos0`` tokens left
    the state ``s0`` ``[H, key, value]`` (None: zeros).  ``keep``:
    token counts ``n``; ``kept[i]`` is the state the sequence's first
    ``keep[i]`` tokens leave."""
    S = x.shape[0]
    H, d = int(cfg["lightning_nh"]), int(cfg["lightning_head_dim"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    loglam = jnp.zeros(H, F32) if no_decay else jnp.asarray(decay(cfg, l))
    block = -(-block // chunk) * chunk
    pos = pos0 + np.arange(-(-S // block) * block)
    seg = (pos >= (np.inf if zero_state_at is None else zero_state_at))
    at = np.full((len(pos) // chunk, max(len(keep), 1)), -1, np.int32)
    for n, upto in enumerate(keep):
        if pos0 < upto <= pos0 + S:
            at[(upto - 1 - pos0) // chunk, n] = (upto - 1 - pos0) % chunk
    s = jnp.zeros((H, d, d), F32) if s0 is None else s0
    sg = jnp.asarray(False)
    kept = jnp.zeros((at.shape[1], H, d, d), F32)
    outs = []
    for b0 in range(0, S, block):
        rows = slice(b0, b0 + block)
        u = rms_norm(x[rows], lw["input_layernorm.weight"], eps)
        n = u.shape[0]
        p = jnp.asarray(pos[rows][:n])

        def heads(name, norm):
            y = mm(u, lw[f"self_attn.{name}_proj.weight"], lowp)
            y = y.reshape(-1, H, d)
            if norm:
                y = rotary(rms_norm(y, lw[f"self_attn.{name}_norm.weight"],
                                    eps), p, theta)
            # whole chunks: rows past the end hold zeros and add nothing
            y = jnp.pad(y, ((0, block - n), (0, 0), (0, 0)))
            return y.reshape(-1, chunk, H, d)

        c0 = b0 // chunk
        o, s, sg, got = _scan_chunks(
            s, sg, heads("q", True), heads("k", True), heads("v", False),
            loglam, jnp.asarray(seg[rows].reshape(-1, chunk)),
            jnp.asarray(at[c0:c0 + block // chunk]))
        here = (at[c0:c0 + block // chunk] >= 0).any(0)
        kept = jnp.where(jnp.asarray(here)[:, None, None, None], got, kept)
        o = rms_norm(o.reshape(-1, H * d)[:n] * d ** -0.5,
                     lw["self_attn.o_norm.weight"], eps)
        gate = jax.nn.sigmoid(mm(u, lw["self_attn.o_gate.weight"], lowp))
        outs.append(mm(gate * o, lw["self_attn.o_proj.weight"], lowp))
    return x + residual_scale(cfg) * jnp.concatenate(outs, 0), \
        [kept[n] for n in range(len(keep))]


def residual_scale(cfg) -> float:
    return float(cfg["scale_depth"]) / math.sqrt(depth_of(cfg))


def compressed_keys(k, sp):
    """``c_j = mean(k[stride j : stride j + kernel])`` for every whole
    window of ``k`` ``[S, kvh, d]``: ``[J, kvh, d]``."""
    S = k.shape[0]
    kernel, stride = int(sp["kernel_size"]), int(sp["kernel_stride"])
    J = max(0, (S - kernel) // stride + 1)
    if not J:
        return jnp.zeros((0, *k.shape[1:]), F32)
    idx = (stride * np.arange(J))[:, None] + np.arange(kernel)[None, :]
    return jnp.mean(k[jnp.asarray(idx)], axis=1)


@functools.partial(jax.jit, static_argnames=(
    "kernel", "stride", "block", "topk", "init_blocks", "window",
    "dense_len", "lowp", "dense_all", "no_forced"))
def _attention_block(q_b, pos_b, k, v, c, given, has, *, kernel, stride,
                     block, topk, init_blocks, window, dense_len, lowp,
                     dense_all, no_forced):
    """A block of query rows ``[B, kvh, rep, d]`` at positions ``pos_b``
    against the keys and values ``[S, kvh, d]`` and the compressed keys
    ``[J, kvh, d]`` of the sequence so far; a row with ``has`` takes the
    blocks ``given`` ``[B, kvh, topk]`` for its selection."""
    S, d = k.shape[0], k.shape[-1]
    n = pos_b + 1
    causal = jnp.arange(S)[None, :] < n[:, None]              # [B, S]
    seen = causal[:, None, :]
    J = c.shape[0]
    nb = -(-S // block)
    if not dense_all and J and nb > topk:
        sc = jnp.einsum("bgrd,jgd->bgrj", narrow(q_b, lowp), narrow(c, lowp),
                        precision=HIGHEST) * d ** -0.5
        final = (stride * jnp.arange(J)[None, :] + kernel <= n[:, None]
                 )[:, None, None, :]
        p = jax.nn.softmax(jnp.where(final, sc, -jnp.inf), -1)
        p = jnp.sum(jnp.where(final, p, 0.0), 2)              # [B, kvh, J]
        # a block's score: the best compressed key that overlaps it
        per = block // stride
        b = jnp.arange(nb)
        score = jnp.zeros((*p.shape[:2], nb), F32)
        for o in range(-(kernel // stride) + 1, per):
            j = per * b + o
            ok = (j >= 0) & (j < J)
            score = jnp.maximum(score, jnp.where(
                ok, p[..., jnp.clip(j, 0, J - 1)], 0.0))
        own = (pos_b // block)[:, None, None]
        if not no_forced:
            forced = (b[None, None, :] < init_blocks) | (
                block * (b[None, None, :] + 1) > (n - window)[:, None, None])
            score = jnp.where(forced, jnp.inf, score)
        score = jnp.where(b[None, None, :] > own, -jnp.inf, score)
        _, chosen = jax.lax.top_k(score, topk)                # [B, kvh, topk]
        chosen = jnp.where(has[:, None, None], given, chosen)
        picked = (b[None, None, None, :] == chosen[..., None]).any(-2)
        tokens = jnp.repeat(picked, block, axis=-1)[..., :S]  # [B, kvh, S]
        sparse = (n > dense_len)[:, None, None]
        seen = jnp.where(sparse, tokens & seen, seen)
    sc = jnp.einsum("bgrd,sgd->bgrs", narrow(q_b, lowp), narrow(k, lowp),
                    precision=HIGHEST) * d ** -0.5
    p = jax.nn.softmax(jnp.where(seen[:, :, None, :], sc, -jnp.inf), -1)
    p = jnp.where(seen[:, :, None, :], p, 0.0)
    return jnp.einsum("bgrs,sgd->bgrd", narrow(p, lowp), narrow(v, lowp),
                      precision=HIGHEST)


def sparse_attention(x, lw, cfg, lowp=None, dense_all=False, no_forced=False,
                     no_gate=False, q_block: int = 128, bucket: int = 8192,
                     block: int = 4096, selection=None, pos0: int = 0,
                     before=None):
    """``(x + scale * attention, (k, v))``: InfLLM-V2, no rotary
    embedding; x the rows at positions ``pos0 ..`` of a sequence whose
    first ``pos0`` tokens have the keys and values ``before`` ``(k,
    v)`` ``[pos0, kvh, d]``.  ``selection``: ``(positions, blocks [n,
    kvh, topk])`` given.  Returns the keys and values of the whole
    sequence so far too."""
    S = x.shape[0]
    H, kvh, d = (int(cfg["num_attention_heads"]),
                 int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    sp = cfg["sparse_config"]
    eps = float(cfg["rms_norm_eps"])
    norm = lambda xb: rms_norm(xb, lw["input_layernorm.weight"], eps)  # noqa: E731
    k = by_rows(lambda xb: mm(norm(xb), lw["self_attn.k_proj.weight"], lowp),
                x, block).reshape(S, kvh, d)
    v = by_rows(lambda xb: mm(norm(xb), lw["self_attn.v_proj.weight"], lowp),
                x, block).reshape(S, kvh, d)
    if before is not None:
        k = jnp.concatenate([before[0], k], 0)
        v = jnp.concatenate([before[1], v], 0)
    N = pos0 + S
    c = compressed_keys(k, sp)
    if lowp:
        q_block = min(q_block, 32)
    kw = dict(kernel=int(sp["kernel_size"]), stride=int(sp["kernel_stride"]),
              block=int(sp["block_size"]), topk=int(sp["topk"]),
              init_blocks=int(sp["init_blocks"]),
              window=int(sp["window_size"]), dense_len=int(sp["dense_len"]),
              lowp=lowp, dense_all=bool(dense_all), no_forced=bool(no_forced))
    given = np.zeros((S, kvh, kw["topk"]), np.int32)
    has = np.zeros(S, bool)
    if selection is not None:
        at = np.asarray(selection[0]) - pos0
        ok = (at >= 0) & (at < S)
        given[at[ok]] = np.asarray(selection[1])[ok]
        has[at[ok]] = True
    outs = []
    for s0 in range(0, S, q_block):
        u = norm(x[s0:s0 + q_block])
        q = mm(u, lw["self_attn.q_proj.weight"], lowp)
        q = q.reshape(-1, kvh, H // kvh, d)
        # the keys so far, in a few sizes (a size compiles once)
        upto = min(N, -(-(pos0 + s0 + q_block) // bucket) * bucket)
        nck = max(0, (upto - kw["kernel"]) // kw["stride"] + 1)
        ctx = _attention_block(q, pos0 + jnp.arange(s0, s0 + q.shape[0]),
                               k[:upto], v[:upto], c[:nck],
                               jnp.asarray(given[s0:s0 + q_block]),
                               jnp.asarray(has[s0:s0 + q_block]), **kw)
        ctx = ctx.reshape(-1, H * d)
        if not no_gate:
            ctx = ctx * jax.nn.sigmoid(
                mm(u, lw["self_attn.o_gate.weight"], lowp))
        outs.append(mm(ctx, lw["self_attn.o_proj.weight"], lowp))
    return x + residual_scale(cfg) * jnp.concatenate(outs, 0), (k, v)


def mlp(x, lw, cfg, lowp=None, block: int = 4096):
    eps = float(cfg["rms_norm_eps"])

    def one(xb):
        u = rms_norm(xb, lw["post_attention_layernorm.weight"], eps)
        return xb + residual_scale(cfg) * mm(
            jax.nn.silu(mm(u, lw["mlp.gate_proj.weight"], lowp))
            * mm(u, lw["mlp.up_proj.weight"], lowp),
            lw["mlp.down_proj.weight"], lowp)

    return by_rows(one, x, block)


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any], lowp=None,
            dense_all=False, no_forced=False, zero_state_at=None,
            no_decay=False, no_gate=False, q_block: int = 128,
            rows: Optional[slice] = None, state_after: Optional[int] = None,
            states: Optional[list] = None, selection=None, prefix=None,
            keep_prefix: Optional[int] = None):
    """Logits ``[S, vocab]`` of token ids ``[S]`` (of the positions
    ``rows`` alone where given).  With ``state_after=n`` every
    ``lightning-attn`` layer appends to ``states`` the state ``[H, key,
    value]`` that the sequence's first ``n`` tokens leave.

    Requests that share a session's history need its pass once:
    ``keep_prefix=n`` returns ``(logits, what the first n tokens leave)``
    (a layer's keys and values ``[n, kvh, d]``, or its state), and
    ``prefix=`` that makes ``ids`` the tokens FROM position ``n`` of
    such a sequence: the same numbers as the whole sequence's pass, by
    causality."""
    pos0 = 0 if prefix is None else int(prefix["n"])
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    x = x * float(cfg["scale_emb"])
    left = {"n": keep_prefix}
    keep = [n for n in (state_after, keep_prefix) if n is not None]
    for l in layers_of(cfg):
        lw = layer_leaves(params, l)
        had = None if prefix is None else prefix[l]
        if cfg["mixer_types"][l] == LIGHTNING:
            x, kept = lightning(x, lw, cfg, l, lowp, zero_state_at, no_decay,
                                keep, pos0, had, chunk=min(q_block, 64))
            if state_after is not None:
                states.append(kept[0])
            left[l] = kept[-1] if keep_prefix is not None else None
        elif cfg["mixer_types"][l] == SPARSE:
            x, (k, v) = sparse_attention(
                x, lw, cfg, lowp, dense_all, no_forced, no_gate, q_block,
                selection=(selection or {}).get(l), pos0=pos0, before=had)
            left[l] = (k[:keep_prefix], v[:keep_prefix])
        else:
            raise ValueError(f"layer {l} mixes by {cfg['mixer_types'][l]!r}")
        x = mlp(x, lw, cfg, lowp)
    if rows is not None:
        x = x[rows]
    x = rms_norm(x, params["model.norm.weight"], float(cfg["rms_norm_eps"]))
    x = x / (float(cfg["hidden_size"]) / float(cfg["dim_model_base"]))
    # the head in blocks of columns: its float32 copy whole is 1.2 GB
    head = params["lm_head.weight"]
    logits = jnp.concatenate([mm(x, head[:, c0:c0 + 16384], lowp)
                              for c0 in range(0, head.shape[1], 16384)], -1)
    return logits if keep_prefix is None else (logits, left)


def slow_heads(cfg: Dict[str, Any], share: int = 16) -> np.ndarray:
    """``[state layers, heads / share]``: in each ``lightning-attn``
    layer the heads whose state decays slowest (the last: ``s_h`` falls
    with ``h``; 2 of 32): they remember over hundreds of tokens, so what
    a restore lost shows in them long after the logits forgot it."""
    out = []
    for l in layers_of(cfg):
        if cfg["mixer_types"][l] == LIGHTNING:
            rate = -decay(cfg, l)
            out.append(np.argsort(rate)[:max(1, len(rate) // share)])
    return np.stack(out)


def served_token_gaps(params, prompt, tokens, cfg: Dict[str, Any],
                      pad_to: int = 0, states: bool = False, q_block=128,
                      selection=None, prefixes=None, keep_prefix=None,
                      **control) -> Dict[str, Any]:
    """Teacher-forced check of one greedy request, as
    ``nemotron_h_ref.served_token_gaps``: ``gap``, ``logits``, with a
    control ``control_gap`` and ``control_logits``, and with ``states``
    the recurrent states the PROMPT leaves, ``[state layers, H, key,
    value]`` (``states``, ``control_states``).  ``keep_prefix=n``: also
    what the request's first ``n`` tokens leave, ``prefixes`` ``(the
    sound pass's, the control's or None)``; handed back as
    ``prefixes=`` with a request that begins with the same ``n``
    tokens, only the rest of it is computed (``forward``).  ``pad_to``
    appends token 0 so that what is computed has a multiple of that
    length: nothing before a position depends on what follows it."""
    prompt, tokens = np.asarray(prompt), np.asarray(tokens)
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    n, S = len(tokens), len(seq)
    had, had_other = prefixes or (None, None)
    p0 = 0 if had is None else int(had["n"])
    part = seq[p0:]
    ids = jnp.asarray(np.pad(part, (0, -len(part) % max(pad_to, 1))))
    rows = slice(S - n - p0, S - p0)
    after = len(prompt) if states else None
    kept, kept_other = [], []

    def run(prefix, kept, **kw):
        res = forward(params, ids, cfg, rows=rows, state_after=after,
                      states=kept, q_block=q_block, selection=selection,
                      prefix=prefix, keep_prefix=keep_prefix, **kw)
        return res if keep_prefix is not None else (res, None)

    logits, left = run(had, kept)
    best = jnp.max(logits, -1)
    served = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], -1)[:, 0]
    out = {"gap": np.asarray(best - served), "logits": logits,
           "reference_tokens": np.asarray(jnp.argmax(logits, -1)),
           "prefixes": (left, None)}
    if control:
        other, left_other = run(had_other, kept_other, **control)
        alt = jnp.argmax(other, -1)
        out["control_logits"] = other
        out["control_gap"] = np.asarray(
            best - jnp.take_along_axis(logits, alt[:, None], -1)[:, 0])
        out["prefixes"] = (left, left_other)
    if states:
        out["states"] = np.stack([np.asarray(k) for k in kept])
        if control:
            out["control_states"] = np.stack([np.asarray(k) for k in kept_other])
    return out
