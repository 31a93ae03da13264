"""Weight-only int8 (round 6): quantize_params_int8 + the _Weights
dequant-at-consumer views, through generate() and the serving engine —
the capability the bench.py llama-8B-shaped serving leg runs at scale
(reference analog: python/paddle/nn/quant/quantized_linear.py
weight_only_linear + weight_quantize)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import (_Weights, _generate_jit,
                                          quantize_params_int8,
                                          register_config)


@pytest.fixture(scope="module")
def tiny():
    import paddle_tpu as paddle

    state = paddle.get_rng_state()
    paddle.seed(424242)
    cfg = LlamaConfig.debug(vocab=64, hidden=32, layers=2, heads=4,
                            kv_heads=2, inter=64, max_pos=128)
    model = LlamaForCausalLM(cfg)
    params = {k: jnp.asarray(v)
              for k, v in model.functional_state().items()}
    paddle.set_rng_state(state)
    return cfg, params


def test_quantize_layout(tiny):
    cfg, params = tiny
    qp = quantize_params_int8(params)
    assert qp["model.layers.0.self_attn.q_proj.weight"].dtype == jnp.int8
    sc = qp["model.layers.0.self_attn.q_proj.weight._scale"]
    assert sc.shape == (cfg.hidden_size,)          # per-out-channel
    # norm gains stay fp
    assert qp["model.layers.0.input_layernorm.weight"].dtype != jnp.int8
    # embedding: per-ROW scales
    assert qp["model.embed_tokens.weight._scale"].shape == (cfg.vocab_size,)


def test_dequant_views_close(tiny):
    cfg, params = tiny
    qp = quantize_params_int8(params)
    w = _Weights(cfg, qp)
    name = "model.layers.1.mlp.gate_proj.weight"
    deq = np.asarray(w.layer(1, "mlp.gate_proj.weight"))
    ref = np.asarray(params[name])
    # symmetric absmax int8: worst-case error is scale/2 per channel
    scale = np.asarray(qp[name + "._scale"])
    assert (np.abs(deq - ref) <= scale[None, :] * 0.51).all()
    # embedding gather-then-dequant == dequant-then-gather
    ids = jnp.asarray([3, 9])
    rows = np.asarray(w.embed(ids))
    full = (np.asarray(qp["model.embed_tokens.weight"], np.float32)
            * np.asarray(qp["model.embed_tokens.weight._scale"])[:, None])
    np.testing.assert_allclose(rows, full[[3, 9]], rtol=1e-6)


def test_int8_generate_mostly_matches_fp(tiny):
    cfg, params = tiny
    qp = quantize_params_int8(params)
    cid = register_config(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 7)), jnp.int32)
    key = jax.random.PRNGKey(0)
    kw = dict(cfg_id=cid, max_new_tokens=8, do_sample=False,
              temperature=1.0, top_k=0, top_p=1.0, eos_id=-1)
    fp = np.asarray(_generate_jit(params, ids, key, **kw))
    q8 = np.asarray(_generate_jit(qp, ids, key, **kw))
    assert np.isfinite(q8.astype(np.float64)).all()
    # int8 weights flip only rare near-ties on a greedy stream
    assert (fp == q8).mean() > 0.6, (fp, q8)
