"""Runner "serve_deepseek_v32": a DeepSeek-V3.2-shaped configuration
behind ``ContinuousBatchingEngine``, driven exactly as runner "serve"
drives a Llama-shaped one.  ``measure`` (with its ``Driver``) and
``warm_up`` are ``runners/serve.py``'s own (the open loop, the stamps, the one compiled
unified step); what is this file's is the engine's build from the
configuration's file (the chip's share of the experts and of the
vocabulary among it), the seeded draw
(``harness/weights_deepseek_v32.py``) and the call of the plain
reference (``reference/deepseek_v32_ref.py``).

``correct`` is decided as in the Llama cells: once the window has
closed, a seeded sample of finished requests (the longest among them)
goes through the reference once, prompt plus served tokens, and the
widest and the mean gap by which a served token's reference logit lies
below the reference's best are held to the configuration's limits
(``"check"``); plus no compilation in the window, no wrong token count,
allocator and prefix cache consistent.

Controls (``ctx.overrides``, driven by ``tools/controls_deepseek_v32.py``
and the tests), each of which has to come out as NOT correct: the run is
a sound one, and the CONTROL's greedy choices stand in the served
tokens' place when the gaps are held to the limits, since a program that
computed the control's way would have served them:

    control_lowp: "fp8"          the reference with every matmul operand in fp8
    control_no_selection: true   every row attends its whole context
    control_gates: "held"        gates normalised over the held experts only
"""

from __future__ import annotations

import importlib.util
import pathlib
import time
from typing import Any, Dict, List

import numpy as np


def _serve():
    """``runners/serve.py``, loaded as ``harness/manifest.load_runner``
    loads a runner (the directory is no package)."""
    path = pathlib.Path(__file__).with_name("serve.py")
    spec = importlib.util.spec_from_file_location("benchmarks.runners.serve",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


serve = _serve()
measure, warm_up = serve.measure, serve.warm_up     # tools/sweep.py's too
SPANS, WINDOW_SPAN = serve.SPANS, serve.WINDOW_SPAN
PAD_TO = 8192        # the reference runs a sampled request at a multiple


def model_config(cfg: Dict[str, Any]):
    """The program's configuration from the file: every published key it
    knows, the router at its PUBLISHED width, and the experts held here
    (``n_routed_experts`` of them, those of ``deployment_rank``)."""
    from paddle_tpu.models.deepseek_v32 import DeepseekV32Config

    held = int(cfg["n_routed_experts"])
    lo = held * int(cfg.get("deployment_rank", 0))
    wide = int(cfg.get("published", {}).get("n_routed_experts", held))
    extra = {k: cfg[k] for k in ("moe_block_rows",) if k in cfg}
    return DeepseekV32Config.from_published(
        cfg, n_routed_experts=wide, experts_held=(lo, lo + held), **extra)


def build_engine(ctx, params):
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    kw = dict(ctx.cell.config["engine"])
    kw["cache_dtype"] = jnp.dtype(kw.pop("cache_dtype"))
    kw.update(ctx.overrides.get("engine", {}))
    return ContinuousBatchingEngine(model_config(ctx.cell.config), params, **kw)


def set_up(ctx):
    """Weights from the seed and the engine over them: ``(params, eng)``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import traffic as gen, weights_deepseek_v32

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    need = gen.longest_request_tokens(mix)
    if need > cfg["engine"]["max_seq_len"]:
        raise ValueError(f"the mix's longest request is {need} tokens, the "
                         f"engine's max_seq_len {cfg['engine']['max_seq_len']}")
    params = weights_deepseek_v32.draw_params(cfg, ctx.seed,
                                              jnp.dtype(cfg["torch_dtype"]))
    jax.block_until_ready(params)
    ctx.say(f"weights drawn ({sum(v.nbytes for v in params.values()) / 2**30:.2f} GiB)")
    return params, build_engine(ctx, params)


def control_of(ctx) -> Dict[str, Any]:
    """The reference's keyword arguments of the control asked for."""
    ov, out = ctx.overrides, {}
    if ov.get("control_lowp"):
        out["lowp"] = ov["control_lowp"]
    if ov.get("control_no_selection"):
        out["use_selection"] = False
    if ov.get("control_gates"):
        out["gates"] = ov["control_gates"]
    return out


def reference_gaps(ctx, params, done: List[Dict[str, Any]], cfg, mix):
    """``(gaps, control gaps or None)`` of the served tokens of a seeded
    sample of finished requests (the longest among them)."""
    from benchmarks.reference import deepseek_v32_ref

    if not done:
        return None, None
    k = int(mix.get("check_sample", 4))
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i]["prompt"]) + done[i]["want"]))
    rng = np.random.default_rng([int(ctx.seed), 3])
    rest = [int(i) for i in rng.permutation(order[1:])[:k - 1]]
    control = control_of(ctx)
    gaps, cgaps = [], []
    for i in [order[0], *rest]:
        r = done[i]
        # lengths share the shapes of their multiple of PAD_TO (the
        # reference's compile time is most of its cost), up to the
        # longest sequence the engine takes
        n = len(r["prompt"]) + len(r["tokens"]) - 1
        pad_to = min(-(-n // PAD_TO) * PAD_TO, cfg["engine"]["max_seq_len"])
        g = deepseek_v32_ref.served_token_gaps(
            params, r["prompt"], r["tokens"], cfg, pad_to=pad_to, **control)
        gaps.append(g["gap"])
        if control:
            cgaps.append(g["control_gap"])
    gaps = np.concatenate(gaps)
    if not control:
        return gaps, None
    c = np.concatenate(cgaps)
    ctx.say(f"control {control}: gap widest {c.max():.6g} mean {c.mean():.6g} "
            f"over {len(c)} positions (the sound run's: widest "
            f"{gaps.max():.6g} mean {gaps.mean():.6g})")
    ctx.report["control"] = {**{k: str(v) for k, v in control.items()},
                             "widest": float(c.max()), "mean": float(c.mean()),
                             "positions": len(c),
                             "sound_widest": float(gaps.max()),
                             "sound_mean": float(gaps.mean())}
    return gaps, c


def run(ctx) -> Dict[str, Any]:
    from benchmarks.harness import context, stats, traffic as gen

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    params, eng = set_up(ctx)
    traffic = gen.serve_requests(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    warm_up(ctx, eng, traffic, cfg["vocab_size"])
    ctx.say(f"engine warm: rows_cap {eng.rows_cap}, {eng.num_pages} pages, "
            f"backend compile {ctx.clock.total:.1f}s in {ctx.clock.count} programs")
    setup_s = time.perf_counter() - ctx.t_process
    drain_s = float(mix["drain_s"])
    drv, t0, t_trace, compiled_in_window = measure(ctx, eng, mix,
                                                   traffic["requests"])

    # ---- the window has closed: numbers, then what decides `correct` ----
    sample = [{**r, "due": t0 + r["due"], "sent": t0 + r["sent"]}
              for r in drv.recs.values()]
    summ = stats.serving_summary(sample, t0, ctx.seconds, drain_s)
    steps = ctx.spans.durations("engine.step", t0, t0 + ctx.seconds)
    ctx.say(f"window: {summ}; engine.step max "
            f"{max(steps, default=0.0) * 1e3:.1f} ms over {len(steps)} steps")
    done = [r for r in drv.recs.values() if r["tokens"] is not None]
    st = eng.serving_stats()
    ctx.say(f"engine steps: {st['steps']}")
    prefill = st["prefill"]
    counters = {
        "prompt_tokens": sum(v["prompt_len"] for v in prefill.values()),
        "cached_prompt_tokens": sum(v["cached_tokens"] for v in prefill.values()),
    }
    checks = ctx.checks
    checks.at_most("compilations_in_window", compiled_in_window, 0)
    checks.at_most("finished_with_wrong_token_count",
                   sum(1 for r in done if len(r["tokens"]) != r["want"]), 0)
    leaked = 0
    try:
        eng.alloc.assert_consistent()
        if eng.prefix_cache is not None:
            eng.prefix_cache.assert_consistent()
        eng.shutdown()
    except AssertionError as e:
        ctx.say(f"engine teardown: {e}")
        leaked = 1
    checks.at_most("allocator_or_cache_inconsistent", leaked, 0)
    device = context.device_report(ctx.devices)     # the program's peak
    eng.k_pages = eng.v_pages = None                # free the pools
    del eng

    t_ref = time.perf_counter()
    gaps, control = reference_gaps(ctx, params, done, cfg, mix)
    checks.at_most("no_finished_request_to_compare", int(gaps is None), 0)
    judged = control if control is not None else gaps
    if judged is not None:
        checks.at_most("served_token_gap_widest", float(judged.max()),
                       cfg["check"]["served_token_gap_widest"])
        checks.at_most("served_token_gap_mean", float(judged.mean()),
                       cfg["check"]["served_token_gap_mean"])
    ctx.say(f"reference over {0 if gaps is None else len(gaps)} served tokens "
            f"in {time.perf_counter() - t_ref:.1f}s")

    out = {"attempted": summ["requests"], "failed": summ["failed"],
           "device": device, "summary": summ,
           "metrics": {"setup_s": setup_s,
                       **{k: summ[k] for k in ("ttft_p95_ms", "itl_p95_ms",
                                               "serve_tokens_per_s") if k in summ}}}
    if ctx.trace:
        out["obs"] = {
            "spans": {n: ctx.spans.durations(n, *t_trace) for n in SPANS},
            "counters": counters,
            "trace": context.traced(ctx, SPANS, WINDOW_SPAN),
        }
    return out
