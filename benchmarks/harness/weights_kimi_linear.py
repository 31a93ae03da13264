"""Seeded weights of a Kimi-Linear-shaped configuration, made on the
device by the benchmark and handed to both the program and the plain
reference (``reference/kimi_linear_ref.py``).  The leaves and their
shapes are spelled here from the configuration's file, not asked of the
program; the draw itself is ``harness/weights_deepseek_v32.py``'s (the
chip's own generator, a leaf at a time).

Leaf names (Linear weights ``[in, out]``), under ``model.layers.<n - 1>.``
for published layer ``n`` (``linear_attn_config``'s lists are 1-based):

    input_layernorm.weight, post_attention_layernorm.weight   every layer
    KDA  self_attn.{q,k,v}_proj.weight ([hidden, heads x head_dim]),
         self_attn.{q,k,v}_conv1d.weight ([taps, heads x head_dim]; the
         last tap meets the current token), self_attn.A_log ([heads]),
         self_attn.dt_bias ([heads x head_dim]), self_attn.f_a_proj.weight
         ([hidden, head_dim]), .f_b_proj.weight ([head_dim, heads x
         head_dim]), .b_proj.weight ([hidden, heads]), .g_a_proj.weight,
         .g_b_proj.weight (as f's), .o_norm.weight ([head_dim]),
         .o_proj.weight
    MLA  self_attn.q_proj.weight ([hidden, heads x (nope + rope)], a
         head's nope part first), .kv_a_proj_with_mqa.weight ([hidden,
         kv_lora_rank + rope]), .kv_a_layernorm.weight, .kv_b_proj.weight
         ([kv_lora_rank, heads x (nope + v)], a head's key part first),
         .o_proj.weight
    mlp.gate_proj / up_proj / down_proj .weight       (leading dense layers)
    mlp.router.weight ([hidden, PUBLISHED experts]), mlp.router.bias,
    mlp.shared_expert.{gate,up,down}_proj.weight,
    mlp.experts.{gate,up,down}_proj.weight ([experts HELD, in, out])

and ``model.embed_tokens.weight``, ``model.norm.weight``,
``lm_head.weight`` (untied) over the held slice of the vocabulary.

Assumed, since the source gives no values: matrices N(0, 0.02), norm
gains 1, the router's selection bias N(0, 0.02); the decay as
``harness/weights_nemotron_h.py`` draws Mamba-2's, from the file's
``kda_init``: ``dt_bias`` the inverse softplus of a ``dt`` a CHANNEL
drawn log-uniform in [``dt_min``, ``dt_max``] and floored at
``dt_floor``, ``A_log`` the log of a uniform [``a_min``, ``a_max``] a
head: a state that neither dies in a token nor never decays.  The
convolutions' taps are N(0, 0.3) as there.  A configuration may state
another deviation under ``"weights_std"`` and for single leaves under
``"weights_std_of"``.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import STD, TOP
from .weights_deepseek_v32 import _leaves, base_key, published, top_shapes
from .weights_nemotron_h import CONV_STD


def is_kda(cfg: Dict[str, Any], i: int) -> bool:
    return i + 1 in cfg["linear_attn_config"]["kda_layers"]


def layer_shapes(cfg: Dict[str, Any], i: int) -> Dict[str, tuple]:
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    a = "self_attn."
    out = {"input_layernorm.weight": (h,),
           "post_attention_layernorm.weight": (h,)}
    if is_kda(cfg, i):
        lin = cfg["linear_attn_config"]
        d, K = lin["head_dim"], lin["short_conv_kernel_size"]
        hd = lin["num_heads"] * d
        for n in "qkv":
            out[a + f"{n}_proj.weight"] = (h, hd)
            out[a + f"{n}_conv1d.weight"] = (K, hd)
        out.update({a + "f_a_proj.weight": (h, d),
                    a + "f_b_proj.weight": (d, hd),
                    a + "b_proj.weight": (h, lin["num_heads"]),
                    a + "g_a_proj.weight": (h, d),
                    a + "g_b_proj.weight": (d, hd),
                    a + "o_norm.weight": (d,),
                    a + "o_proj.weight": (hd, h)})
    else:
        dn, dr, dv, kl = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"], cfg["kv_lora_rank"])
        out.update({a + "q_proj.weight": (h, H * (dn + dr)),
                    a + "kv_a_proj_with_mqa.weight": (h, kl + dr),
                    a + "kv_a_layernorm.weight": (kl,),
                    a + "kv_b_proj.weight": (kl, H * (dn + dv)),
                    a + "o_proj.weight": (H * dv, h)})
    if i < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        out.update({"mlp.gate_proj.weight": (h, f), "mlp.up_proj.weight": (h, f),
                    "mlp.down_proj.weight": (f, h)})
        return out
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    wide = published(cfg, "num_experts")
    out.update({
        "mlp.router.weight": (h, wide), "mlp.router.bias": (wide,),
        "mlp.shared_expert.gate_proj.weight": (h, f),
        "mlp.shared_expert.up_proj.weight": (h, f),
        "mlp.shared_expert.down_proj.weight": (f, h),
        "mlp.experts.gate_proj.weight": (held, h, f),
        "mlp.experts.up_proj.weight": (held, h, f),
        "mlp.experts.down_proj.weight": (held, f, h),
    })
    return out


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "dtype",
                                             "dt", "a"))
def _decay_scalars(key, heads, head_dim, dtype, dt, a):
    k1, k2 = jax.random.split(key)
    lo, hi, floor = dt
    step = jnp.exp(jax.random.uniform(k1, (heads * head_dim,), jnp.float32,
                                      math.log(lo), math.log(hi)))
    step = jnp.maximum(step, floor)
    rate = jax.random.uniform(k2, (heads,), jnp.float32, *a)
    return {"self_attn.dt_bias":
            (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "self_attn.A_log": jnp.log(rate).astype(dtype)}


def draw_params(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole functional state under the program's leaf names."""
    key, std = base_key(seed), float(cfg.get("weights_std", STD))
    std_of = {f"self_attn.{n}_conv1d.weight": CONV_STD for n in "qkv"}
    std_of.update(cfg.get("weights_std_of", {}))
    params = _leaves(jax.random.fold_in(key, TOP), top_shapes(cfg), dtype, std,
                     std_of)
    lin, init = cfg["linear_attn_config"], cfg["kda_init"]
    for i in range(cfg["num_hidden_layers"]):
        lkey = jax.random.fold_in(key, i)
        layer = _leaves(lkey, layer_shapes(cfg, i), dtype, std, std_of)
        if is_kda(cfg, i):
            layer.update(_decay_scalars(
                jax.random.fold_in(lkey, zlib.crc32(b"kda")),
                lin["num_heads"], lin["head_dim"], jnp.dtype(dtype).name,
                (float(init["dt_min"]), float(init["dt_max"]),
                 float(init["dt_floor"])),
                (float(init["a_min"]), float(init["a_max"]))))
        params.update({f"model.layers.{i}.{n}": v for n, v in layer.items()})
    return params
