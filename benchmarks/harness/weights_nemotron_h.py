"""Seeded weights of a Nemotron-H-shaped configuration, made on the
device by the benchmark and handed to both the program and the plain
reference (``reference/nemotron_h_ref.py``).  The leaves and their
shapes are spelled here from the configuration's file, not asked of the
program; the draw itself is ``harness/weights_deepseek_v32.py``'s (the
chip's own generator, a leaf at a time).

Leaf names (Linear weights ``[in, out]``), under ``model.layers.<i>.``,
by the layer's letter in ``hybrid_override_pattern``:

    norm.weight                                            every layer
    M  mixer.in_proj.weight ([hidden, d_in + (d_in + 2 G N) + heads]),
       mixer.conv1d.weight ([taps, d_in + 2 G N]; the last tap meets the
       current token), mixer.conv1d.bias, mixer.dt_bias, mixer.A_log,
       mixer.D ([heads]), mixer.norm.weight ([d_in]),
       mixer.out_proj.weight ([d_in, hidden])
    *  self_attn.{q,k,v,o}_proj.weight
    E  mlp.router.weight ([hidden, experts routed]), mlp.router.bias,
       mlp.latent_down.weight ([hidden, latent]), mlp.latent_up.weight,
       mlp.experts.{up,down}_proj.weight ([experts HELD, in, out]),
       mlp.shared_expert.{up,down}_proj.weight

and ``model.embed_tokens.weight``, ``model.norm.weight``,
``lm_head.weight`` (untied).

Assumed, since the source gives no values: matrices N(0, 0.02), norm
gains 1, biases N(0, 0.02), and for the mixer what the config's own keys
describe (Mamba-2's initialisation): ``dt_bias`` the inverse softplus of
a ``dt`` drawn log-uniform in [``time_step_min``, ``time_step_max``] and
floored at ``time_step_floor``, ``A_log`` the log of a uniform [1, 16],
``D`` 1: a state that neither dies in a token nor never decays.  The
convolution's taps are N(0, 0.3), the spread of the uniform(-1/2, 1/2)
a 4-tap depthwise convolution is initialised with (at 0.02 the mixer's
input would be a fiftieth of its skip path ``D x`` and the state would
weigh nothing in the logits).  A configuration may state another
deviation under ``"weights_std"`` and for single leaves under
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

CONV_STD = 0.3


def layer_shapes(cfg: Dict[str, Any], letter: str) -> Dict[str, tuple]:
    h = cfg["hidden_size"]
    out = {"norm.weight": (h,)}
    if letter == "M":
        H, P, G, N = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                      cfg["n_groups"], cfg["ssm_state_size"])
        d_in, cd = H * P, H * P + 2 * G * N
        out.update({"mixer.in_proj.weight": (h, d_in + cd + H),
                    "mixer.conv1d.weight": (cfg["conv_kernel"], cd),
                    "mixer.conv1d.bias": (cd,),
                    "mixer.norm.weight": (d_in,),
                    "mixer.out_proj.weight": (d_in, h)})
    elif letter == "*":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        out.update({"self_attn.q_proj.weight": (h, q),
                    "self_attn.k_proj.weight": (h, kv),
                    "self_attn.v_proj.weight": (h, kv),
                    "self_attn.o_proj.weight": (q, h)})
    else:
        f, l = cfg["moe_intermediate_size"], cfg["moe_latent_size"]
        fs, held = (cfg["moe_shared_expert_intermediate_size"],
                    cfg["n_routed_experts"])
        wide = published(cfg, "n_routed_experts")
        out.update({"mlp.router.weight": (h, wide), "mlp.router.bias": (wide,),
                    "mlp.latent_down.weight": (h, l),
                    "mlp.latent_up.weight": (l, h),
                    "mlp.experts.up_proj.weight": (held, l, f),
                    "mlp.experts.down_proj.weight": (held, f, l),
                    "mlp.shared_expert.up_proj.weight": (h, fs),
                    "mlp.shared_expert.down_proj.weight": (fs, h)})
    return out


@functools.partial(jax.jit, static_argnames=("heads", "dtype", "lo", "hi",
                                             "floor"))
def _mixer_scalars(key, heads, dtype, lo, hi, floor):
    k1, k2 = jax.random.split(key)
    dt = jnp.exp(jax.random.uniform(k1, (heads,), jnp.float32,
                                    math.log(lo), math.log(hi)))
    dt = jnp.maximum(dt, floor)
    a = jax.random.uniform(k2, (heads,), jnp.float32, 1.0, 16.0)
    return {"mixer.dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "mixer.A_log": jnp.log(a).astype(dtype),
            "mixer.D": jnp.ones((heads,), dtype)}


def draw_params(cfg: Dict[str, Any], seed: int, dtype) -> Dict[str, Any]:
    """The whole functional state under the program's leaf names."""
    key, std = base_key(seed), float(cfg.get("weights_std", STD))
    std_of = {"mixer.conv1d.weight": CONV_STD, **cfg.get("weights_std_of", {})}
    params = _leaves(jax.random.fold_in(key, TOP), top_shapes(cfg), dtype, std,
                     std_of)
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    for i, letter in enumerate(pattern):
        lkey = jax.random.fold_in(key, i)
        layer = _leaves(lkey, layer_shapes(cfg, letter), dtype, std, std_of)
        if letter == "M":
            layer.update(_mixer_scalars(
                jax.random.fold_in(lkey, zlib.crc32(b"mixer")),
                cfg["mamba_num_heads"], jnp.dtype(dtype).name,
                float(cfg["time_step_min"]), float(cfg["time_step_max"]),
                float(cfg["time_step_floor"])))
        params.update({f"model.layers.{i}.{n}": v for n, v in layer.items()})
    return params
