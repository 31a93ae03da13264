"""Seeded weights of a Mellum2-shaped configuration, made on the device
by the benchmark and handed to both the program and the plain reference
(``reference/mellum2_ref.py``).  The leaves and their shapes are spelled
here from the configuration's file, not asked of the program.  One
jitted draw a distinct (shape, kind), a leaf at a time (an expert bank
is 0.53 GB in float32 before it is cast), each in the dtype the
configuration serves in; the draw itself is
``harness/weights_deepseek_v32.py``'s (the chip's own generator).

Leaf names (Linear weights ``[in, out]``), under ``model.layers.<i>.``:

    input_layernorm.weight, post_attention_layernorm.weight
    self_attn.q_proj.weight ([hidden, heads x head_dim]), .k_proj.weight,
        .v_proj.weight ([hidden, kv_heads x head_dim]), .o_proj.weight
    mlp.router.weight ([hidden, experts]),
    mlp.experts.{gate,up,down}_proj.weight ([experts, in, out])

and ``model.embed_tokens.weight``, ``model.norm.weight``,
``lm_head.weight`` (untied).

Assumed, since the source gives no values: matrices N(0, 0.02), norm
gains 1.  A configuration may state another deviation under
``"weights_std"`` (the CPU rehearsal's 64-wide model does: at 0.02 its
every layer adds nothing) and for single leaves under
``"weights_std_of"`` (by the leaf's name within its layer).
"""

from __future__ import annotations

from typing import Any, Dict

import jax

from .weights import STD, TOP
from .weights_deepseek_v32 import _leaves, base_key


def layer_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    h, H, kvh, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    return {
        "input_layernorm.weight": (h,),
        "self_attn.q_proj.weight": (h, H * d),
        "self_attn.k_proj.weight": (h, kvh * d),
        "self_attn.v_proj.weight": (h, kvh * d),
        "self_attn.o_proj.weight": (H * d, h),
        "post_attention_layernorm.weight": (h,),
        "mlp.router.weight": (h, e),
        "mlp.experts.gate_proj.weight": (e, h, f),
        "mlp.experts.up_proj.weight": (e, h, f),
        "mlp.experts.down_proj.weight": (e, f, h),
    }


def top_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,),
            "lm_head.weight": (h, v)}


def draw_params(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole functional state under the program's leaf names."""
    key, std = base_key(seed), float(cfg.get("weights_std", STD))
    std_of = cfg.get("weights_std_of", {})
    params = _leaves(jax.random.fold_in(key, TOP), top_shapes(cfg), dtype, std,
                     std_of)
    for i in range(cfg["num_hidden_layers"]):
        layer = _leaves(jax.random.fold_in(key, i), layer_shapes(cfg), dtype,
                        std, std_of)
        params.update({f"model.layers.{i}.{n}": v for n, v in layer.items()})
    return params
