"""Seeded weights of a MiniCPM-SALA-shaped configuration, made on the
device by the benchmark and handed to both the program and the plain
reference (``reference/minicpm_sala_ref.py``).  The leaves and their
shapes are spelled here from the configuration's file, not asked of the
program; the draw itself is ``harness/weights_deepseek_v32.py``'s (the
chip's own generator, a leaf at a time).

Leaf names (Linear weights ``[in, out]``), under ``model.layers.<l>.``
with ``l`` the layer's PUBLISHED index (``layers_run`` says which run),
by the layer's entry in ``mixer_types``:

    input_layernorm.weight, post_attention_layernorm.weight,
    mlp.{gate,up,down}_proj.weight                          every layer
    lightning-attn  self_attn.{q,k,v}_proj.weight ([hidden, heads x d]),
                    self_attn.{q,k}_norm.weight ([d]),
                    self_attn.o_norm.weight ([heads x d]),
                    self_attn.o_gate.weight ([hidden, heads x d]),
                    self_attn.o_proj.weight
    minicpm4        self_attn.q_proj.weight ([hidden, heads x d]),
                    self_attn.{k,v}_proj.weight ([hidden, kv heads x d]),
                    self_attn.o_gate.weight, self_attn.o_proj.weight

and ``model.embed_tokens.weight``, ``model.norm.weight``,
``lm_head.weight`` (untied).

Assumed, since the source gives no values: matrices N(0, 0.02), norm
gains 1.  A configuration states other deviations under
``"weights_std"`` and, by mixer and leaf, under ``"weights_std_of"``
(``{"minicpm4": {"self_attn.q_proj.weight": 0.03, ...}}``): at 0.02 a
``minicpm4`` layer's softmax over thousands of keys is nearly flat, its
output the mean of its values, a hundredth of the MLP's, and nothing the
attention does shows in a logit.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax

from .weights import STD, TOP
from .weights_deepseek_v32 import _leaves, base_key, top_shapes

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def layers_run(cfg: Dict[str, Any]):
    lo, hi = cfg["layers_run"]
    return range(int(lo), int(hi))


def layer_shapes(cfg: Dict[str, Any], mixer: str) -> Dict[str, tuple]:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"input_layernorm.weight": (h,),
           "post_attention_layernorm.weight": (h,),
           "mlp.gate_proj.weight": (h, f), "mlp.up_proj.weight": (h, f),
           "mlp.down_proj.weight": (f, h)}
    a = "self_attn."
    if mixer == LIGHTNING:
        d = cfg["lightning_head_dim"]
        q = cfg["lightning_nh"] * d
        out.update({a + "q_proj.weight": (h, q), a + "k_proj.weight": (h, q),
                    a + "v_proj.weight": (h, q), a + "q_norm.weight": (d,),
                    a + "k_norm.weight": (d,), a + "o_norm.weight": (q,),
                    a + "o_gate.weight": (h, q), a + "o_proj.weight": (q, h)})
    elif mixer == SPARSE:
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        out.update({a + "q_proj.weight": (h, q), a + "k_proj.weight": (h, kv),
                    a + "v_proj.weight": (h, kv), a + "o_gate.weight": (h, q),
                    a + "o_proj.weight": (q, h)})
    else:
        raise ValueError(f"a layer mixes by {mixer!r}?")
    return out


def draw_params(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole functional state under the program's leaf names."""
    key, std = base_key(seed), float(cfg.get("weights_std", STD))
    std_of = cfg.get("weights_std_of", {})
    params = _leaves(jax.random.fold_in(key, TOP), top_shapes(cfg), dtype, std,
                     {})
    for l in layers_run(cfg):
        mixer = cfg["mixer_types"][l]
        layer = _leaves(jax.random.fold_in(key, l), layer_shapes(cfg, mixer),
                        dtype, std, std_of.get(mixer, {}))
        params.update({f"model.layers.{l}.{n}": v for n, v in layer.items()})
    return params


def count(cfg: Dict[str, Any]) -> int:
    """Parameters of the layers that run, the embedding and the head."""
    shapes = list(top_shapes(cfg).values())
    for l in layers_run(cfg):
        shapes += layer_shapes(cfg, cfg["mixer_types"][l]).values()
    return sum(math.prod(s) for s in shapes)
