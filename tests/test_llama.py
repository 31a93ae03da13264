"""Llama flagship: eager forward, compiled+sharded train step on an
8-device dp×sharding×mp mesh, parity eager-vs-compiled."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               apply_llama_sharding, build_train_step,
                               make_batch_shardings)


def _mesh(dp=2, sharding=2, mp=2):
    devs = np.asarray(jax.devices()[:dp * sharding * mp], dtype=object)
    return Mesh(devs.reshape(dp, sharding, mp),
                axis_names=("dp", "sharding", "mp"))


def test_llama_forward_shapes():
    cfg = LlamaConfig.debug()
    model = LlamaForCausalLM(cfg)
    ids = paddle.randint(0, cfg.vocab_size, [2, 16])
    logits = model(ids)
    assert logits.shape == [2, 16, cfg.vocab_size]
    # causality: token t's logits must not depend on tokens > t
    ids2 = paddle.to_tensor(np.asarray(ids._value).copy())
    arr = np.asarray(ids2._value).copy()
    arr[:, 10:] = (arr[:, 10:] + 1) % cfg.vocab_size
    logits2 = model(paddle.to_tensor(arr))
    np.testing.assert_allclose(np.asarray(logits._value)[:, :10],
                               np.asarray(logits2._value)[:, :10],
                               rtol=2e-4, atol=2e-4)


def test_llama_sharding_plan_applied():
    cfg = LlamaConfig.debug(vocab=256, hidden=64, heads=4, kv_heads=2, inter=128)
    model = LlamaForCausalLM(cfg)
    mesh = _mesh()
    apply_llama_sharding(model, mesh)
    specs = {n: tuple(p._value.sharding.spec)
             for n, p in model.named_parameters()}
    # placed specs drop trailing Nones (the form jit hands back)
    assert specs["model.embed_tokens.weight"] == (("mp", "sharding"),)
    assert specs["model.layers.0.self_attn.q_proj.weight"] == ("sharding", "mp")
    assert specs["model.layers.0.mlp.down_proj.weight"] == ("mp", "sharding")
    assert specs["model.norm.weight"] in ((), (None,))


@pytest.mark.slow
def test_llama_train_step_compiled_sharded():
    # tier-2 (round-16 re-tier): GSPMD sharded-step twin; tier-1 home:
    # the smoke overlap_parity leg + the memory-lattice mesh point +
    # the doctor flagship sharding sweeps
    cfg = LlamaConfig.debug()
    model = LlamaForCausalLM(cfg)
    mesh = _mesh()
    apply_llama_sharding(model, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = build_train_step(model, opt, mesh)

    params = model.functional_state()
    opt_state = opt.init_state(params)
    bs = make_batch_shardings(mesh)
    ids = jax.device_put(
        np.random.randint(0, cfg.vocab_size, (8, 32), dtype=np.int32), bs)
    labels = jax.device_put(
        np.random.randint(0, cfg.vocab_size, (8, 32), dtype=np.int32), bs)

    losses = []
    for i in range(4):
        loss, params, opt_state = step(params, opt_state, i, 1e-3, ids, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], f"no learning: {losses}"
    # params keep their FSDP/TP placements through the step (donated)
    w = params["model.layers.0.self_attn.q_proj.weight"]
    assert tuple(w.sharding.spec) == ("sharding", "mp")


def test_rope_buffers_not_in_state():
    cfg = LlamaConfig.debug(layers=1)
    model = LlamaForCausalLM(cfg)
    keys = set(model.functional_state())
    assert not any("rope_cos" in k or "rope_sin" in k for k in keys), \
        "non-persistable rope tables must not be trained"


def test_tied_embeddings_eager_grad():
    cfg = LlamaConfig.debug(layers=1)
    cfg.tie_word_embeddings = True
    model = LlamaForCausalLM(cfg)
    ids = paddle.randint(0, cfg.vocab_size, [2, 8])
    labels = paddle.randint(0, cfg.vocab_size, [2, 8])
    logits = model(ids)
    loss = paddle.nn.functional.cross_entropy(
        logits.reshape([-1, cfg.vocab_size]), labels.reshape([-1])).mean()
    loss.backward()
    g = model.model.embed_tokens.weight.grad
    assert g is not None
    # head grads touch rows beyond the input ids (lookup-only grads would not)
    used = set(np.asarray(ids._value).flatten().tolist())
    unused = next(i for i in range(cfg.vocab_size) if i not in used)
    assert np.abs(np.asarray(g._value)[unused]).sum() > 0


def test_position_ids_honored():
    cfg = LlamaConfig.debug(layers=1)
    model = LlamaForCausalLM(cfg)
    ids = paddle.randint(0, cfg.vocab_size, [1, 8])
    base = model(ids, position_ids=paddle.to_tensor(np.arange(8)[None]))
    prefix = model(ids)
    np.testing.assert_allclose(np.asarray(base._value),
                               np.asarray(prefix._value), rtol=1e-4, atol=1e-5)
    # RoPE is relative: a UNIFORM shift must not change outputs
    shifted = model(ids, position_ids=paddle.to_tensor((np.arange(8) + 5)[None]))
    np.testing.assert_allclose(np.asarray(shifted._value),
                               np.asarray(prefix._value), rtol=1e-3, atol=1e-4)
    # but a non-uniform layout (packed sequences) must
    packed = model(ids, position_ids=paddle.to_tensor(
        np.array([0, 1, 2, 3, 0, 1, 2, 3])[None]))
    assert not np.allclose(np.asarray(packed._value),
                           np.asarray(prefix._value), atol=1e-3)


@pytest.mark.slow
def test_remat_matches_no_remat():
    import jax.numpy as jnp
    cfg = LlamaConfig.debug(layers=2)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(parameters=model.parameters())
    ids = np.random.randint(0, cfg.vocab_size, (2, 8), dtype=np.int32)
    lab = np.random.randint(0, cfg.vocab_size, (2, 8), dtype=np.int32)
    def fresh():
        # copies: the step donates its inputs and would delete the model's
        # live parameter buffers otherwise
        params = {k: jnp.array(v) for k, v in model.functional_state().items()}
        return params, opt.init_state(params)

    params, ostate = fresh()
    l0, p0, _ = build_train_step(model, opt, remat=False,
                                 compute_dtype=jnp.float32)(params, ostate, 0, 1e-3, ids, lab)
    params, ostate = fresh()
    l1, p1, _ = build_train_step(model, opt, remat=True,
                                 compute_dtype=jnp.float32)(params, ostate, 0, 1e-3, ids, lab)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    k = "model.layers.0.self_attn.q_proj.weight"
    np.testing.assert_allclose(np.asarray(p0[k]), np.asarray(p1[k]),
                               rtol=1e-5, atol=1e-6)


def test_llama_eager_vs_compiled_loss_parity():
    cfg = LlamaConfig.debug(layers=1, hidden=32, heads=2, kv_heads=1, inter=64)
    model = LlamaForCausalLM(cfg)
    ids_np = np.random.randint(0, cfg.vocab_size, (2, 8), dtype=np.int32)
    lab_np = np.random.randint(0, cfg.vocab_size, (2, 8), dtype=np.int32)

    # eager loss (fp32 path for exact comparison)
    logits = model(paddle.to_tensor(ids_np))
    eager = paddle.nn.functional.cross_entropy(
        logits.reshape([-1, cfg.vocab_size]),
        paddle.to_tensor(lab_np.reshape(-1))).mean()

    opt = paddle.optimizer.AdamW(parameters=model.parameters())
    step = build_train_step(model, opt, compute_dtype=jnp.float32)
    params = model.functional_state()
    opt_state = opt.init_state(params)
    loss, _, _ = step(params, opt_state, 0, 0.0, ids_np, lab_np)
    np.testing.assert_allclose(float(loss), float(eager), rtol=1e-5)


def test_grad_accum_matches_full_batch():
    """Tier-2 (round-16 re-tier: remat parity twin; tier-1 home: the memory engine's named-policy lattice point on the same decoder).  accum=2 over [2, b, s] must match one step over the concatenated
    [2b, s] batch: per-micro mean losses average to the global mean and
    accumulated grads are averaged, so params after AdamW agree."""
    cfg = LlamaConfig.debug(layers=1, hidden=32, heads=2, kv_heads=1, inter=64)
    model = LlamaForCausalLM(cfg)
    ids = np.random.randint(0, cfg.vocab_size, (4, 8), dtype=np.int32)
    lab = np.random.randint(0, cfg.vocab_size, (4, 8), dtype=np.int32)

    opt = paddle.optimizer.AdamW(parameters=model.parameters())
    params = model.functional_state()
    opt_state = opt.init_state(params)

    import jax

    def deep(t):  # the jitted steps donate their buffers
        return jax.tree_util.tree_map(jnp.copy, t)

    full = build_train_step(model, opt, compute_dtype=jnp.float32)
    l_full, p_full, _ = full(deep(params), deep(opt_state), 0, 1e-3, ids, lab)

    acc = build_train_step(model, opt, compute_dtype=jnp.float32,
                           accum_steps=2)
    l_acc, p_acc, _ = acc(deep(params), deep(opt_state), 0, 1e-3,
                          ids.reshape(2, 2, 8), lab.reshape(2, 2, 8))

    np.testing.assert_allclose(float(l_acc), float(l_full), rtol=1e-5)
    for k in p_full:
        np.testing.assert_allclose(np.asarray(p_acc[k]),
                                   np.asarray(p_full[k]), atol=1e-5,
                                   err_msg=k)


def test_masked_grad_accum_token_weighted():
    """Masked accumulation with UNEQUAL per-micro token counts must match
    the full-batch masked step: micro contributions are token-weighted
    (weighted-grad-sum / total tokens), not equal-weighted."""
    cfg = LlamaConfig.debug(layers=1, hidden=32, heads=2, kv_heads=1, inter=64)
    model = LlamaForCausalLM(cfg)
    ids = np.random.randint(0, cfg.vocab_size, (4, 8), dtype=np.int32)
    lab = np.random.randint(0, cfg.vocab_size, (4, 8), dtype=np.int32)
    # rows have 8/3/5/2 valid tokens -> micro 0 carries 11, micro 1 carries 7
    mask = (np.arange(8)[None, :] < np.array([8, 3, 5, 2])[:, None]) \
        .astype(np.int32)

    opt = paddle.optimizer.AdamW(parameters=model.parameters())
    params = model.functional_state()
    opt_state = opt.init_state(params)

    import jax

    def deep(t):
        return jax.tree_util.tree_map(jnp.copy, t)

    full = build_train_step(model, opt, compute_dtype=jnp.float32)
    l_full, p_full, _ = full(deep(params), deep(opt_state), 0, 1e-3, ids,
                             lab, mask)

    acc = build_train_step(model, opt, compute_dtype=jnp.float32,
                           accum_steps=2)
    l_acc, p_acc, _ = acc(deep(params), deep(opt_state), 0, 1e-3,
                          ids.reshape(2, 2, 8), lab.reshape(2, 2, 8),
                          mask.reshape(2, 2, 8))

    np.testing.assert_allclose(float(l_acc), float(l_full), rtol=1e-5)
    for k in p_full:
        np.testing.assert_allclose(np.asarray(p_acc[k]),
                                   np.asarray(p_full[k]), atol=1e-5,
                                   err_msg=k)


def test_attention_mask_isolates_padding():
    """A bool [b, s] keep-mask must make valid-position logits invariant
    to pad-token content (rides the segment-masked flash path on TPU)."""
    cfg = LlamaConfig.debug()
    m = LlamaForCausalLM(cfg)
    ids = np.random.randint(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    am = np.arange(12)[None, :] < np.array([9, 6])[:, None]
    o1 = m(paddle.to_tensor(ids), attention_mask=paddle.to_tensor(am))
    ids2 = ids.copy()
    ids2[0, 10] = 7
    ids2[1, 8] = 3
    o2 = m(paddle.to_tensor(ids2), attention_mask=paddle.to_tensor(am))
    np.testing.assert_allclose(o1.numpy()[0, :9], o2.numpy()[0, :9],
                               atol=1e-5)
    np.testing.assert_allclose(o1.numpy()[1, :6], o2.numpy()[1, :6],
                               atol=1e-5)


def test_attention_mask_under_remat_matches_eager():
    cfg = LlamaConfig.debug()
    m = LlamaForCausalLM(cfg)
    ids = np.random.randint(0, cfg.vocab_size, (2, 8)).astype(np.int32)
    am = np.arange(8)[None, :] < np.array([6, 8])[:, None]

    plain = m(paddle.to_tensor(ids),
              attention_mask=paddle.to_tensor(am)).numpy()

    import jax as j

    params = m.functional_state()

    def fwd(params, ids_v, am_v):
        from paddle_tpu.autograd import no_grad

        m.model.remat = True
        try:
            with no_grad():
                return m.functional_call(params, paddle.Tensor(ids_v),
                                         attention_mask=paddle.Tensor(am_v)
                                         )._value
        finally:
            m.model.remat = False

    got = np.asarray(j.jit(fwd)(params, ids, am))
    np.testing.assert_allclose(got, plain, rtol=1e-4, atol=1e-4)


def test_attention_mask_rejects_additive_float():
    cfg = LlamaConfig.debug(layers=1)
    m = LlamaForCausalLM(cfg)
    ids = np.random.randint(0, cfg.vocab_size, (1, 4)).astype(np.int32)
    bad = np.array([[0.0, 0.0, -1e9, -1e9]], "float32")  # additive style
    with pytest.raises(TypeError):
        m(paddle.to_tensor(ids), attention_mask=paddle.to_tensor(bad))


def test_train_step_attention_mask_isolates_pads():
    """Compiled train step with a keep-mask: loss must be invariant to
    pad-token content (attention AND the CE both masked)."""
    cfg = LlamaConfig.debug(layers=1, hidden=32, heads=2, kv_heads=1,
                            inter=64)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(parameters=model.parameters())
    step = build_train_step(model, opt, compute_dtype=jnp.float32)
    params = model.functional_state()
    st = opt.init_state(params)

    def deep(t):
        return jax.tree_util.tree_map(jnp.copy, t)

    ids = np.random.randint(0, cfg.vocab_size, (2, 8), dtype=np.int32)
    am = (np.arange(8)[None, :] < np.array([6, 8])[:, None]).astype(np.int32)
    ids2 = ids.copy()
    ids2[0, 7] = (ids2[0, 7] + 3) % cfg.vocab_size
    la, _, _ = step(deep(params), deep(st), 0, 0.0, ids, ids, am)
    lb, _, _ = step(deep(params), deep(st), 0, 0.0, ids2, ids2, am)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)


def test_packed_sequences_via_int_segment_ids():
    """Int segment ids pack two sequences per row: the first packed
    sequence's logits must equal running it alone."""
    cfg = LlamaConfig.debug(layers=2)
    m = LlamaForCausalLM(cfg)
    a = np.random.randint(0, cfg.vocab_size, (1, 6)).astype(np.int32)
    b = np.random.randint(0, cfg.vocab_size, (1, 6)).astype(np.int32)
    packed = np.concatenate([a, b], axis=1)
    seg = np.array([[1] * 6 + [2] * 6], np.int32)
    pos = np.array([list(range(6)) + list(range(6))], np.int32)
    out = m(paddle.to_tensor(packed), position_ids=paddle.to_tensor(pos),
            attention_mask=paddle.to_tensor(seg))
    alone = m(paddle.to_tensor(a))
    np.testing.assert_allclose(out.numpy()[0, :6], alone.numpy()[0],
                               rtol=1e-4, atol=1e-4)


def test_additive_int_mask_rejected():
    cfg = LlamaConfig.debug(layers=1)
    m = LlamaForCausalLM(cfg)
    ids = np.random.randint(0, cfg.vocab_size, (1, 4)).astype(np.int32)
    bad = np.array([[0, 0, -10000, -10000]], np.int64)
    with pytest.raises(TypeError):
        m(paddle.to_tensor(ids), attention_mask=paddle.to_tensor(bad))


def test_cost_sheet_delegates_to_roofline():
    """Round-20: LlamaConfig.cost_sheet() is the roofline sheet — the
    counts the enumerated partitioning search prices with (param total
    cross-checked against a hand count of the debug config)."""
    from paddle_tpu.parallel.roofline import llama_cost_sheet

    cfg = LlamaConfig.debug()
    sheet = cfg.cost_sheet()
    assert sheet.params_total == llama_cost_sheet(cfg).params_total
    h, kv_h = cfg.hidden_size, cfg.num_key_value_heads * cfg.head_dim
    per_layer = (2 * h * h + 2 * h * kv_h          # q/o + k/v proj
                 + 3 * h * cfg.intermediate_size   # gate/up/down
                 + 2 * h)                          # the two rmsnorms
    embed = 2 * cfg.vocab_size * h + h             # tok+lm_head+final norm
    assert sheet.params_total == cfg.num_hidden_layers * per_layer + embed
    assert sheet.step_flops(2, 16) > sheet.fwd_flops(2, 16) > 0
