"""Seeded weights, made on the device by the benchmark and handed to
both the program and the plain reference.  One jitted draw per decoder
layer (every layer has the same shapes, so it compiles once) and one
for the embedding, the head and the final norm; each in the dtype the
configuration serves or trains in.  N(0, 0.02) matrices, unit norms.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .flops import head_dim

STD = 0.02
TOP = 1 << 20          # fold-in index of the non-layer leaves


def base_key(seed: int):
    """Any whole number up to a little over 2**31 (and beyond)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def layer_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    h, d = cfg["hidden_size"], head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    inter = cfg["intermediate_size"]
    return {
        "input_layernorm.weight": (h,),
        "self_attn.q_proj.weight": (h, q),
        "self_attn.k_proj.weight": (h, kv),
        "self_attn.v_proj.weight": (h, kv),
        "self_attn.o_proj.weight": (q, h),
        "post_attention_layernorm.weight": (h,),
        "mlp.gate_proj.weight": (h, inter),
        "mlp.up_proj.weight": (h, inter),
        "mlp.down_proj.weight": (inter, h),
    }


def top_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,)}
    if not cfg.get("tie_word_embeddings"):
        out["lm_head.weight"] = (h, v)
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _draw(key, shapes, dtype):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        if len(shape) == 1:
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


def draw_layer(cfg: Dict[str, Any], seed: int, i: int, dtype) -> Dict[str, Any]:
    """The leaves of decoder layer ``i``, under their short names."""
    shapes = tuple(layer_shapes(cfg).items())
    return _draw(jax.random.fold_in(base_key(seed), i), shapes,
                 jnp.dtype(dtype).name)


def draw_top(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    shapes = tuple(top_shapes(cfg).items())
    return _draw(jax.random.fold_in(base_key(seed), TOP), shapes,
                 jnp.dtype(dtype).name)


def draw_params(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole functional state, under the program's leaf names
    (``model.layers.<i>.<leaf>``, ``model.embed_tokens.weight``,
    ``model.norm.weight``, ``lm_head.weight``); Linear weights are
    ``[in, out]``."""
    params = dict(draw_top(cfg, seed, dtype))
    for i in range(cfg["num_hidden_layers"]):
        for name, v in draw_layer(cfg, seed, i, dtype).items():
            params[f"model.layers.{i}.{name}"] = v
    return params

