"""Plain reference of the GQA + SwiGLU decoder (Mistral-7B-v0.3,
https://huggingface.co/mistralai/Mistral-7B-v0.3: ``modeling_mistral``
with ``sliding_window`` null): RMSNorm, rotary embedding (half-rotation
layout), grouped-query softmax attention under a causal mask, SwiGLU,
untied head, mean cross-entropy, and AdamW for the training cells.

Straightforward ``jax.numpy`` in float32 with matmul precision
``highest``; no kernels, no cache, no batching tricks.  It imports
nothing of the program and takes nothing the program has made: weights
come from ``benchmarks/harness/weights.py`` under the leaf names of
the published checkpoint layout (Linear weights stored ``[in, out]``).
Stored weights are upcast as they are used (exact for bf16 storage).

``lowp`` names a lower precision for the CONTROL: every matmul operand
is rounded to it (per-tensor scaled for fp8/int8) and read back to
float32.  A control has to come out as not correct.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# the layer equations
# --------------------------------------------------------------------------

def round_to(x, lowp: Optional[str]):
    """``x`` as the lower precision would hold it, back in float32.  The
    gradient passes straight through the rounding (the backward matmuls
    then read the rounded operands), as a lower-precision training step
    does; differentiating the cast itself would give zeros."""
    if lowp is None:
        return x
    if lowp == "bf16":
        r = x.astype(jnp.bfloat16).astype(F32)
    else:
        amax = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
        if lowp == "fp8":                   # e4m3, scaled to its largest 448
            s = 448.0 / amax
            r = (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
        elif lowp == "int8":
            s = 127.0 / amax
            r = jnp.clip(jnp.round(x * s), -127, 127) / s
        else:
            raise ValueError(f"unknown lower precision {lowp!r}")
    return x + jax.lax.stop_gradient(r - x)


def mm(a, b, lowp=None):
    return jnp.matmul(round_to(a.astype(F32), lowp), round_to(b.astype(F32), lowp),
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope_tables(head_dim: int, n: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    ang = np.outer(np.arange(n, dtype=np.float64), inv)
    ang = np.concatenate([ang, ang], axis=-1)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def rotate_half(x):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-b, a], axis=-1)


def attention(q, k, v, lowp=None):
    """q [b, s, h, d]; k, v [b, s, kvh, d]; causal softmax attention,
    each group of h / kvh query heads reading one key/value head."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, d)
    sc = jnp.einsum("bsgrd,btgd->bgrst", round_to(qg, lowp), round_to(k, lowp),
                    precision=HIGHEST) * (d ** -0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bgrst,btgd->bsgrd", round_to(p, lowp), round_to(v, lowp),
                   precision=HIGHEST)
    return o.reshape(b, s, h * d)


def decoder_layer(x, lw: Dict[str, Any], cos, sin, heads: int, kv_heads: int,
                  eps: float, lowp=None):
    """x [b, s, hidden] float32; ``lw`` the layer's leaves by short name."""
    b, s, _ = x.shape
    xin = rms_norm(x, lw["input_layernorm.weight"], eps)
    q = mm(xin, lw["self_attn.q_proj.weight"], lowp).reshape(b, s, heads, -1)
    k = mm(xin, lw["self_attn.k_proj.weight"], lowp).reshape(b, s, kv_heads, -1)
    v = mm(xin, lw["self_attn.v_proj.weight"], lowp).reshape(b, s, kv_heads, -1)
    c, sn = cos[None, :s, None, :], sin[None, :s, None, :]
    q = q * c + rotate_half(q) * sn
    k = k * c + rotate_half(k) * sn
    x = x + mm(attention(q, k, v, lowp), lw["self_attn.o_proj.weight"], lowp)
    xm = rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    gate = jax.nn.silu(mm(xm, lw["mlp.gate_proj.weight"], lowp))
    up = mm(xm, lw["mlp.up_proj.weight"], lowp)
    return x + mm(gate * up, lw["mlp.down_proj.weight"], lowp)


def layer_leaves(params: Dict[str, Any], i: int) -> Dict[str, Any]:
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def head_logits(x, params, eps, lowp=None):
    x = rms_norm(x, params["model.norm.weight"], eps)
    w = params["lm_head.weight"] if "lm_head.weight" in params \
        else params["model.embed_tokens.weight"].T
    return mm(x, w, lowp)


def forward(params: Dict[str, Any], ids, cfg: Dict[str, Any], lowp=None):
    """Logits [b, s, vocab] of token ids [b, s], one program."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    cos, sin = rope_tables(d, ids.shape[1], cfg["rope_theta"])
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = decoder_layer(x, layer_leaves(params, i), cos, sin,
                          cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["rms_norm_eps"], lowp)
    return head_logits(x, params, cfg["rms_norm_eps"], lowp)


def loss_fn(params, ids, labels, cfg, lowp=None):
    """Mean cross-entropy of ``labels`` under ``forward``'s logits."""
    logits = forward(params, ids, cfg, lowp)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# --------------------------------------------------------------------------
# serving: logits of chosen positions, layer by layer so that it fits
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "lowp"))
def _layer_jit(x, lw, cos, sin, heads, kv_heads, eps, lowp):
    return decoder_layer(x, lw, cos, sin, heads, kv_heads, eps, lowp)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _head_jit(x, top, eps, lowp):
    return head_logits(x, top, eps, lowp)


def logits_at(params: Dict[str, Any], tokens: np.ndarray, rows: np.ndarray,
              cfg: Dict[str, Any], lowp=None, pad_to: int = 256) -> np.ndarray:
    """float32 logits [len(rows), vocab] at positions ``rows`` of ONE
    sequence of token ids, run once over the whole sequence.  The
    sequence is padded at its end to a multiple of ``pad_to`` (under the
    causal mask the padding changes no earlier position), so that few
    shapes compile; one decoder layer is one program."""
    n = len(tokens)
    width = -(-n // pad_to) * pad_to
    ids = np.zeros((1, width), np.int32)
    ids[0, :n] = tokens
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    cos, sin = rope_tables(d, width, cfg["rope_theta"])
    x = jnp.take(params["model.embed_tokens.weight"], jnp.asarray(ids),
                 axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_jit(x, layer_leaves(params, i), cos, sin,
                       cfg["num_attention_heads"], cfg["num_key_value_heads"],
                       cfg["rms_norm_eps"], lowp)
    head = "lm_head.weight" if "lm_head.weight" in params \
        else "model.embed_tokens.weight"
    top = {k: params[k] for k in ("model.norm.weight", head)}
    take = np.zeros(-(-len(rows) // 128) * 128, np.int32)   # few head shapes
    take[:len(rows)] = rows
    picked = jnp.take(x[0], jnp.asarray(take), axis=0)
    return np.asarray(_head_jit(picked, top, cfg["rms_norm_eps"],
                                lowp))[:len(rows)]


def served_token_gaps(params, prompt: np.ndarray, served: Sequence[int],
                      cfg: Dict[str, Any], lowp=None) -> Dict[str, np.ndarray]:
    """For one finished request: the reference's logits at every position
    that produced a served token (the prompt's last, then each served
    token but the last), and

    ``gap[j]`` = reference's best logit minus its logit of served token j
    (0 where the served token is the reference's own first choice).

    With ``lowp`` the SAME positions are also run in the lower precision
    and ``control_gap[j]`` is the gap of the token that precision puts
    first (the control need not decode)."""
    served = np.asarray(served, np.int64)
    tokens = np.concatenate([np.asarray(prompt, np.int64), served[:-1]])
    rows = np.arange(len(prompt) - 1, len(tokens))
    ref = logits_at(params, tokens, rows, cfg)
    best = ref.max(axis=-1)
    out = {"gap": best - ref[np.arange(len(rows)), served]}
    if lowp is not None:
        low = logits_at(params, tokens, rows, cfg, lowp=lowp)
        out["control_gap"] = best - ref[np.arange(len(rows)), low.argmax(-1)]
    return out


# --------------------------------------------------------------------------
# training: the first steps, followed in float32
# --------------------------------------------------------------------------

def decays(name: str) -> bool:
    """AdamW's decoupled decay skips the norms' weights."""
    return not name.endswith("norm.weight") and "layernorm" not in name


SAMPLE = 1 << 16


def sample_elements(x):
    """Up to ``SAMPLE`` elements of ``x`` at a fixed stride: a norm
    averages rounding errors away, the elements themselves show them."""
    flat = x.reshape(-1)
    return flat[::max(1, flat.shape[0] // SAMPLE)][:SAMPLE]


class TrainReference:
    """float32 copy of the seeded weights and AdamW over them, on the
    same batches as the program sees.  ``step`` returns the loss and, per
    leaf, the norm of the gradient the optimizer is given (the mean over
    the micro-batches) and a strided sample of its elements.

    It follows the FIRST steps only (two, at the cells' sizes), and holds
    the gradients seen so far in place of the two moments, which are sums
    over them: after one step that is one float32 copy where the moments
    are two, and at the cell's size weights, gradient sum, activations
    and both moments do not fit one chip together."""

    def __init__(self, params: Dict[str, Any], cfg: Dict[str, Any],
                 hp: Dict[str, float], lowp: Optional[str] = None):
        self.cfg, self.hp, self.lowp = dict(cfg), dict(hp), lowp
        self.p = {k: v.astype(F32) for k, v in params.items()}
        self.seen = []                  # gradients of the steps so far
        cfgt = tuple(sorted((k, v) for k, v in cfg.items()
                            if isinstance(v, (int, float, bool))))
        self._grads = jax.jit(functools.partial(
            _accumulated_grads, cfg_items=cfgt, lowp=lowp))
        self._update = jax.jit(functools.partial(_adamw, **{
            k: float(hp[k]) for k in ("lr", "beta1", "beta2", "eps",
                                      "weight_decay")}), donate_argnums=(0,))

    def step(self, ids: np.ndarray, labels: np.ndarray, last: bool = False):
        """One optimizer step; returns ``(loss, {leaf: gradient norm},
        {leaf: sample_elements(gradient)})``.  ``last`` says no step
        follows, so no gradient is kept."""
        seq = ids.shape[-1]
        ids = jnp.asarray(ids.reshape(-1, 1, seq))         # one row a time
        labels = jnp.asarray(labels.reshape(-1, 1, seq))
        loss, grads = self._grads(self.p, ids, labels)
        norms = {k: float(jnp.linalg.norm(g)) for k, g in grads.items()}
        samples = {k: np.asarray(sample_elements(g)) for k, g in grads.items()}
        self.seen.append(grads)
        self.p = self._update(self.p, tuple(self.seen))
        if last:
            self.seen = []
        return float(loss), norms, samples


def _accumulated_grads(p, ids, labels, cfg_items, lowp):
    cfg = dict(cfg_items)
    vg = jax.value_and_grad(lambda q, i, l: loss_fn(q, i, l, cfg, lowp))

    def body(carry, xs):
        gsum, lsum = carry
        loss, g = vg(p, *xs)
        return (jax.tree_util.tree_map(jnp.add, gsum, g), lsum + loss), None

    zero = (jax.tree_util.tree_map(jnp.zeros_like, p), jnp.zeros((), F32))
    (gsum, lsum), _ = jax.lax.scan(body, zero, (ids, labels))
    n = ids.shape[0]
    return lsum / n, jax.tree_util.tree_map(lambda g: g / n, gsum)


def _adamw(p, grads, lr, beta1, beta2, eps, weight_decay):
    """Step ``t = len(grads)`` of decoupled AdamW (Loshchilov & Hutter),
    bias-corrected, from the gradients of steps 1..t: the moments are
    ``m_t = (1 - beta1) sum_i beta1^(t-i) g_i`` and the like for ``v_t``."""
    t = len(grads)
    out = {}
    for k in p:
        m = sum((1 - beta1) * beta1 ** (t - 1 - i) * g[k]
                for i, g in enumerate(grads))
        v = sum((1 - beta2) * beta2 ** (t - 1 - i) * jnp.square(g[k])
                for i, g in enumerate(grads))
        upd = (m / (1 - beta1 ** t)) / (jnp.sqrt(v / (1 - beta2 ** t)) + eps)
        if decays(k):
            upd = upd + weight_decay * p[k]
        out[k] = p[k] - lr * upd
    return out
