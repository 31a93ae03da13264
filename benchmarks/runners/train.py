"""Runner "train": ``build_train_step`` on ``LlamaForCausalLM`` with the
AdamW state resident, on one chip or (``job.mesh`` in the configuration)
sharding-stage-3 x TP over a mesh; builders copied from
``chip_smoke.run_train``.

Set-up builds ONE object, the compiled step with its state, drives it
through its first two steps by the window's own call and feed, and
hands that same object to the window.  The plain float32 reference
follows those two steps first, before the program's state is made
(two and not three: each of its steps costs every run four seconds).
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

SPANS = ("train.step", "next_batch")
WINDOW_SPAN = "traced_window"
REF_STEPS = 2

# The limits are the configuration's own (its file's "check"), set from
# readings at its size; PERF.md section 2 gives them.  The lower precision
# (the reference in fp8) moves the sampled elements and hardly the loss or
# the norms, so those three are held to about three times the sound runs'
# largest, against the faults they are there to catch (a part of the batch
# left out; a step that returns its state unchanged).
# step-0 loss band around ln(vocab): N(0, 0.02) weights behind a unit
# RMS-norm give logits of std 0.02*sqrt(hidden), which adds about
# std^2/2 to the uniform-guess loss (chip_smoke.TrainSize.loss_band)
LOSS_BAND = (-0.1, 1.0)


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float]) -> float:
    """Worst leaf of |got - ref| over the larger of the reference's norm
    of that leaf and of the median leaf (some gradients are all but 0)."""
    med = float(np.median(list(ref.values())))
    return max(abs(got[k] - ref[k]) / max(ref[k], med, 1e-30) for k in ref)


def worst_sample_gap(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> float:
    """Worst leaf of the norm of the difference of the sampled elements,
    over the larger of the reference sample's norm and the median leaf's."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(norms.values())))
    return max(float(np.linalg.norm(got[k] - ref[k])) / max(norms[k], med, 1e-30)
               for k in ref)


def hyper(job: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(job[k]) for k in ("lr", "beta1", "beta2", "eps",
                                       "weight_decay")}


def follow_reference(seed: int, cfg, mix, lowp=None) -> Dict[str, Any]:
    """The plain reference's first steps on the seeded weights and the
    same batches: each step's loss, the first gradient's norm and sampled
    elements by leaf, the norm of the parameters' change by leaf."""
    import jax.numpy as jnp

    from benchmarks.harness import traffic as gen, weights
    from benchmarks.reference import decoder_ref

    dtype = jnp.dtype(cfg["torch_dtype"])
    ref = decoder_ref.TrainReference(weights.draw_params(cfg, seed, dtype),
                                     cfg, hyper(cfg["job"]), lowp=lowp)
    losses, grad_norms, grad_samples = [], None, None
    for s in range(REF_STEPS):
        ids, labels = gen.train_batch(mix, seed, s, cfg["vocab_size"])
        loss, norms, samples = ref.step(ids, labels, last=s == REF_STEPS - 1)
        losses.append(loss)
        if s == 0:
            grad_norms, grad_samples = norms, samples
    # the seeded weights again, a layer at a time, for the change's norm
    start = dict(weights.draw_top(cfg, seed, dtype))
    change = {}
    for i in range(cfg["num_hidden_layers"] + 1):
        change.update({k: float(jnp.linalg.norm(ref.p[k] - v.astype(jnp.float32)))
                       for k, v in start.items()})
        if i < cfg["num_hidden_layers"]:
            start = {f"model.layers.{i}.{k}": v for k, v in
                     weights.draw_layer(cfg, seed, i, dtype).items()}
    del ref
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples, "change_norms": change}


def flat_leaf_stats(opt, params, mask, flat_layout, state, field: str,
                    minus=None, sample=None) -> Dict[str, Dict[str, Any]]:
    """Per leaf of one field of the fused flat optimizer state (``moment1``
    or ``master``): ``norm`` of the leaf minus ``minus[leaf]`` where given,
    and with ``sample`` (a function of the leaf) its ``sample``."""
    import jax
    import jax.numpy as jnp

    out: Dict[str, Dict[str, Any]] = {}
    for g in opt._flat_groups(params, mask, flat_layout):
        flat = state["__flat__"][g["name"]][field]

        def stats(flat, sub, g=g):
            if "layout" in g:
                leaves = g["layout"].unpack_group(g["plans"], g["keys"], flat)
            else:
                leaves, off = {}, 0
                for k, shape, n in zip(g["keys"], g["shapes"], g["sizes"]):
                    leaves[k] = flat[off:off + n].reshape(shape)
                    off += n
            res = {}
            for k, v in leaves.items():
                if sub:
                    v = v - sub[k].astype(jnp.float32)
                res[k] = {"norm": jnp.linalg.norm(v.reshape(-1))}
                if sample is not None:
                    res[k]["sample"] = sample(v)
            return res

        sub = {k: minus[k] for k in g["keys"]} if minus is not None else None
        out.update(jax.device_get(jax.jit(stats)(flat, sub)))
    return out


def build_mesh(job: Dict[str, Any], devices):
    from jax.sharding import Mesh

    axes = ("pp", "dp", "sharding", "sep", "mp")
    shape = [int(job["mesh"].get(a, 1)) for a in axes]
    if math.prod(shape) != len(devices):
        raise ValueError(f"mesh {dict(zip(axes, shape))} needs "
                         f"{math.prod(shape)} devices, found {len(devices)}")
    return Mesh(np.asarray(devices, dtype=object).reshape(shape), axis_names=axes)


def build_step(ctx, params):
    """(step, params, opt_state, readers' handles): the program's normal
    entry points, as ``chip_smoke.run_train`` builds them."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaForCausalLM, apply_llama_sharding,
                                   build_train_step)
    from paddle_tpu.models.llama import llama_decay_mask
    from benchmarks.runners.serve import llama_config

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    job = cfg["job"]
    model = LlamaForCausalLM(llama_config(cfg))
    for name, p in model.named_parameters():
        p.set_value(params[name])
    params = model.functional_state()
    opt = paddle.optimizer.AdamW(
        learning_rate=job["lr"], beta1=job["beta1"], beta2=job["beta2"],
        epsilon=job["eps"], weight_decay=job["weight_decay"],
        parameters=model.parameters(), multi_precision=job["multi_precision"])
    mask = llama_decay_mask(model)
    mesh = build_mesh(job, ctx.devices) if job.get("mesh") else None
    flat_layout = None
    kw = dict(compute_dtype=jnp.dtype(job["compute_dtype"]),
              accum_steps=mix["accum"], remat=bool(job.get("remat", False)))
    if mesh is not None:
        from paddle_tpu.parallel.schedule import PartitionSchedule

        sched = PartitionSchedule.from_model(model, mesh)
        apply_llama_sharding(model, mesh, schedule=sched)
        params = model.functional_state()
        flat_layout = sched.flat_update_layout()
        step = build_train_step(model, opt, mesh, schedule=sched, **kw)
    else:
        step = build_train_step(model, opt, **kw)
    opt_state = opt.init_flat_state(params, decay_mask=mask,
                                    flat_layout=flat_layout)
    like = {k: (v.shape, v.dtype, v.sharding) for k, v in params.items()}
    return step, params, opt_state, (opt, mask, flat_layout, like)


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import context, stats, traffic as gen, weights
    from benchmarks.reference import decoder_ref

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    job = cfg["job"]
    if not job["multi_precision"]:
        raise ValueError("the check reads the fp32 master: the job needs "
                         "multi_precision")
    vocab, lr = cfg["vocab_size"], float(job["lr"])
    dtype = jnp.dtype(cfg["torch_dtype"])
    # ---- the reference, before the program's weights and state exist ----
    t_ref = time.perf_counter()
    ref = follow_reference(ctx.seed, cfg, mix)
    ref_s = time.perf_counter() - t_ref
    ctx.say(f"reference: losses {[round(x, 5) for x in ref['losses']]} "
            f"in {ref_s:.1f}s (not counted in setup_s)")

    params = weights.draw_params(cfg, ctx.seed, dtype)
    step, params, opt_state, (opt, mask, flat_layout, like) = build_step(ctx, params)
    step = ctx.overrides.get("wrap_step", lambda s: s)(step)
    tokens = gen.tokens_per_step(mix)
    ctx.say(f"state built: {sum(math.prod(s) for s, _, _ in like.values()) / 1e6:.0f}M "
            f"params, {tokens} tokens a step")

    def feed(i):
        with ctx.spans.span("next_batch"):
            return gen.train_batch(mix, ctx.seed, i, vocab)

    def issue(i, batch):
        nonlocal params, opt_state
        loss, params, opt_state = step(params, opt_state, i, lr, *batch)
        return loss

    # ---- its first steps, through the window's own call and feed ----
    losses: List[float] = []
    batch = feed(0)
    for i in range(REF_STEPS):
        loss = issue(i, batch)
        batch = feed(i + 1)
        losses.append(float(np.asarray(loss)))
        if i == 0:
            # m = (1 - beta1) g after one step from zero moments
            scale = 1.0 / (1.0 - float(job["beta1"]))
            m1 = flat_leaf_stats(opt, params, mask, flat_layout, opt_state,
                                 "moment1", sample=decoder_ref.sample_elements)
            grad_norms = {k: float(v["norm"]) * scale for k, v in m1.items()}
            grad_samples = {k: v["sample"] * scale for k, v in m1.items()}
    p0 = weights.draw_params(cfg, ctx.seed, dtype)
    p0 = {k: jax.device_put(v, like[k][2]) for k, v in p0.items()}
    change = {k: float(v["norm"]) for k, v in flat_leaf_stats(
        opt, params, mask, flat_layout, opt_state, "master", minus=p0).items()}
    del p0
    checks, limit = ctx.checks, cfg["check"]
    checks.at_most("loss_gap_worst_step",
                   max(abs(a - b) for a, b in zip(losses, ref["losses"])),
                   limit["loss_gap_worst_step"])
    checks.at_most("first_grad_norm_gap_worst_leaf",
                   worst_leaf_gap(grad_norms, ref["grad_norms"]),
                   limit["first_grad_norm_gap_worst_leaf"])
    checks.at_most("first_grad_sample_gap_worst_leaf",
                   worst_sample_gap(grad_samples, ref["grad_samples"]),
                   limit["first_grad_sample_gap_worst_leaf"])
    checks.at_most("param_change_norm_gap_worst_leaf",
                   worst_leaf_gap(change, ref["change_norms"]),
                   limit["param_change_norm_gap_worst_leaf"])
    lo, hi = (math.log(vocab) + d for d in LOSS_BAND)
    checks.within("step0_loss", losses[0], lo, hi)
    ctx.say(f"first steps: losses {[round(x, 5) for x in losses]}; backend "
            f"compile {ctx.clock.total:.1f}s in {ctx.clock.count} programs")

    # ---- the window ----
    tw = context.TraceWindow(ctx, WINDOW_SPAN, mix.get("trace_s", 3.0))
    compiled_before = ctx.clock.count
    ends: List[float] = []
    i = REF_STEPS
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process - ref_s
    while time.perf_counter() - t0 < ctx.seconds:
        tw.poll(time.perf_counter() - t0)
        with ctx.spans.span("train.step"):
            loss = issue(i, batch)
            batch = feed(i + 1)
            losses.append(float(np.asarray(loss)))
        ends.append(time.perf_counter())
        i += 1
    tw.close()
    t_trace = tw.interval

    summ = stats.training_summary(ends, t0, tokens)
    ctx.say(f"window: {summ}; last loss {losses[-1]:.4f}")
    checks.at_most("compilations_in_window", ctx.clock.count - compiled_before, 0)
    checks.at_most("non_finite_losses",
                   sum(1 for x in losses if not math.isfinite(x)), 0)
    inner = getattr(step, "__wrapped__", None)
    if inner is not None and hasattr(inner, "_cache_size"):
        checks.at_most("compilations_of_the_step", inner._cache_size(), 1)

    out = {"attempted": len(ends), "failed": 0,
           "device": context.device_report(ctx.devices), "summary": summ,
           "metrics": {"setup_s": setup_s,
                       "train_tokens_per_s": summ["train_tokens_per_s"]}}
    if ctx.trace:
        in_trace = [e for e in ends if t_trace[0] <= e <= t_trace[1]]
        counters = {}
        if len(in_trace) >= 2:
            counters["train_tokens_per_s"] = \
                (len(in_trace) - 1) * tokens / (in_trace[-1] - in_trace[0])
        out["obs"] = {
            "spans": {n: ctx.spans.durations(n, *t_trace) for n in SPANS},
            "counters": counters,
            "trace": context.traced(ctx, SPANS, WINDOW_SPAN),
        }
    return out
