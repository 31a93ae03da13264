"""Seeded weights of a DeepSeek-V3.2-shaped configuration, made on the
device by the benchmark and handed to both the program and the plain
reference (``reference/deepseek_v32_ref.py``).  The leaves and their
shapes are spelled here from the configuration's file, not asked of the
program.  One jitted draw a distinct (shape, kind), a leaf at a time (an
expert bank is 0.94 GB in float32 before it is cast), each in the dtype
the configuration serves in.

Leaf names (Linear weights ``[in, out]``), under ``model.layers.<i>.``:

    input_layernorm.weight, post_attention_layernorm.weight
    self_attn.q_a_proj.weight, .q_a_layernorm.weight, .q_b_proj.weight
        ([q_lora_rank, heads x (nope + rope)], a head's nope part first)
    self_attn.kv_a_proj_with_mqa.weight ([hidden, kv_lora_rank + rope]),
        .kv_a_layernorm.weight, .kv_b_proj.weight ([kv_lora_rank, heads x
        (nope + v)], a head's key part first), .o_proj.weight
    self_attn.indexer.wq_b.weight, .wk.weight, .k_norm.weight, .k_norm.bias,
        .weights_proj.weight
    mlp.gate_proj / up_proj / down_proj .weight       (leading dense layers)
    mlp.router.weight ([hidden, PUBLISHED experts]), mlp.router.bias,
    mlp.shared_expert.{gate,up,down}_proj.weight,
    mlp.experts.{gate,up,down}_proj.weight ([experts HELD, in, out])

and ``model.embed_tokens.weight``, ``model.norm.weight``,
``lm_head.weight`` over the held slice of the vocabulary.

Assumed, since the source gives no values: matrices N(0, 0.02), norm
gains 1, the indexer's LayerNorm bias and the router's selection bias
N(0, 0.02).  A configuration may state another deviation under
``"weights_std"`` (the CPU rehearsal's 64-wide model does: at 0.02 its
every layer adds nothing), and for single leaves under
``"weights_std_of"`` (by the leaf's name within its layer): the
serving configuration gives attention's output path the scale at which
attention weighs in the residual stream as the experts do (PERF.md
section 2 has the readings).  The draw uses the chip's own generator
(``impl="rbg"``): 4.6 B normals by threefry take most of a minute.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import STD, TOP


def published(cfg: Dict[str, Any], key: str):
    """A key's published value where the file reduced it."""
    return cfg.get("published", {}).get(key, cfg[key])


def layer_shapes(cfg: Dict[str, Any], i: int) -> Dict[str, tuple]:
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    a = "self_attn."
    out = {
        "input_layernorm.weight": (h,),
        a + "q_a_proj.weight": (h, ql),
        a + "q_a_layernorm.weight": (ql,),
        a + "q_b_proj.weight": (ql, H * (dn + dr)),
        a + "kv_a_proj_with_mqa.weight": (h, kl + dr),
        a + "kv_a_layernorm.weight": (kl,),
        a + "kv_b_proj.weight": (kl, H * (dn + dv)),
        a + "o_proj.weight": (H * dv, h),
        a + "indexer.wq_b.weight": (ql, Hi * di),
        a + "indexer.wk.weight": (h, di),
        a + "indexer.k_norm.weight": (di,),
        a + "indexer.k_norm.bias": (di,),
        a + "indexer.weights_proj.weight": (h, Hi),
        "post_attention_layernorm.weight": (h,),
    }
    if i < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        out.update({"mlp.gate_proj.weight": (h, f), "mlp.up_proj.weight": (h, f),
                    "mlp.down_proj.weight": (f, h)})
        return out
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    wide = published(cfg, "n_routed_experts")
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


def top_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,),
            "lm_head.weight": (h, v)}


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _draw(key, shape, dtype, std):
    if std is None:
        return jnp.ones(shape, dtype)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def base_key(seed: int):
    """Any whole number up to a little over 2**31 (and beyond)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def _leaves(key, shapes: Dict[str, tuple], dtype, std, std_of) -> Dict[str, Any]:
    out = {}
    for name, shape in shapes.items():
        gain = len(shape) == 1 and not name.endswith(".bias")
        out[name] = _draw(jax.random.fold_in(key, zlib.crc32(name.encode())),
                          tuple(shape), jnp.dtype(dtype).name,
                          None if gain else float(std_of.get(name, std)))
    return out


def draw_params(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole functional state under the program's leaf names."""
    key, std = base_key(seed), float(cfg.get("weights_std", STD))
    std_of = cfg.get("weights_std_of", {})
    params = _leaves(jax.random.fold_in(key, TOP), top_shapes(cfg), dtype, std,
                     std_of)
    for i in range(cfg["num_hidden_layers"]):
        layer = _leaves(jax.random.fold_in(key, i), layer_shapes(cfg, i),
                        dtype, std, std_of)
        params.update({f"model.layers.{i}.{n}": v for n, v in layer.items()})
    return params
