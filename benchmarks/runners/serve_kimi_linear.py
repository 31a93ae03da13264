"""Runner "serve_kimi_linear": a Kimi-Linear-shaped configuration (KDA
layers whose per-slot state rides in the engine's state pool, NoPE latent
attention over a paged latent cache, sigmoid-routed experts of which the
chip holds its share) behind ``ContinuousBatchingEngine``, driven exactly
as runner "serve" drives a Llama-shaped one.  ``measure`` (with its
``Driver``) and ``warm_up`` are ``runners/serve.py``'s own, the probe of
the engine's own logits ``runners/serve_mellum2.py``'s, the sample and
the states' probe ``runners/serve_nemotron_h.py``'s; what is this file's
is the engine's build from the configuration's file, the seeded draw
(``harness/weights_kimi_linear.py``), the call of the plain reference
(``reference/kimi_linear_ref.py``) and the counters.

``correct`` is decided as in the Nemotron cell: once the window has
closed, a seeded sample of finished requests (the longest, and a draw
from those whose prompts end soonest after the snapshot they restored)
goes through the reference once, prompt plus served tokens, and the
widest and the mean gap by which a served token's reference logit lies
below the reference's best are held to the configuration's limits
(``"check"``); the sampled prompts go through the idle engine once more
(every one RESTORES a snapshot and maps the latent pages of its shared
prefix) and its logits where the answer begins are held against the
reference's rows (``probe_logit_err_mean``); they go through it a third
time for one token each, and the KDA state each prompt leaves in its
slot's entry is held against the reference's ``S`` after the same
tokens, in the heads that forget slowest, of the FIRST KDA layer
(``state_err_slow_mean``: that layer reads the embedding's rows, the
same numbers on both sides, so what differs there is the scan's own
arithmetic, 0.5% on the chip; a deeper layer's state differs by what
bf16 did to the layers before it, 1% more a layer, 11% at the tenth,
which would hide a state kept in bf16; every layer's is printed); the
state pools are of the type the configuration's file states
(``state_dtype``), exactly; plus no compilation in the window, no wrong
token count, every allocator, the prefix cache and the snapshot entries
consistent and balanced, nothing leaked.

Controls (``ctx.overrides``, driven by ``tools/controls_kimi_linear.py``
and the tests), each of which has to come out as NOT correct: the run is
a sound one, and the CONTROL's greedy choices, logits and states stand
in the served tokens' and the engine's place when they are held to the
limits:

    control_lowp: "fp8"          the reference with every matmul operand in fp8
    control_state: "bfloat16"    the KDA state kept in bf16 token to token
    control_decay: "bfloat16"    the decay a channel rounded to bf16
    control_beta: "dropped"      beta left out (1)
    control_gates: "held"        gates normalised over the held experts only
"""

from __future__ import annotations

import pathlib
import time
from typing import Any, Dict

import numpy as np

from benchmarks.harness import manifest

# a sibling runner (the directory is no package)
nemotron = manifest.load_runner(pathlib.Path(__file__).resolve().parents[2],
                                "serve_nemotron_h")
measure, warm_up = nemotron.measure, nemotron.warm_up   # tools/sweep.py's too
SPANS, WINDOW_SPAN = nemotron.SPANS, nemotron.WINDOW_SPAN
probe_logits, probe_states = nemotron.probe_logits, nemotron.probe_states
sample_of = nemotron.sample_of

#: the reference runs a sampled request at a multiple of this share of
#: the engine's longest sequence: a compile a length, at most four
REFERENCE_LENGTHS = 4


def model_config(cfg: Dict[str, Any]):
    """The program's configuration from the file: every published key it
    knows; the router keeps its published width and the chip its share.
    A program without this model fails here, before any weight is drawn."""
    from benchmarks.reference.kimi_linear_ref import held_range
    from paddle_tpu.models.kimi_linear import KimiLinearConfig

    extra = {k: cfg[k] for k in ("moe_block_rows",) if k in cfg}
    wide = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
    return KimiLinearConfig.from_published(
        cfg, num_experts=wide, experts_held=held_range(cfg), **extra)


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

    from benchmarks.harness import traffic as gen, weights_kimi_linear

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    model_config(cfg)               # the parent of this model's PR ends here
    need = gen.longest_request_tokens(mix)
    if need > cfg["engine"]["max_seq_len"]:
        raise ValueError(f"the mix's longest request is {need} tokens, the "
                         f"engine's max_seq_len {cfg['engine']['max_seq_len']}")
    params = weights_kimi_linear.draw_params(cfg, ctx.seed,
                                             jnp.dtype(cfg["torch_dtype"]))
    jax.block_until_ready(params)
    ctx.say(f"weights drawn ({sum(v.nbytes for v in params.values()) / 2**30:.2f} GiB)")
    return params, build_engine(ctx, params)


def control_of(ctx) -> Dict[str, Any]:
    """The reference's keyword arguments of the control asked for."""
    ov, out = ctx.overrides, {}
    if ov.get("control_lowp"):
        out["lowp"] = ov["control_lowp"]
    if ov.get("control_state"):
        out["state_dtype"] = ov["control_state"]
    if ov.get("control_decay"):
        out["decay_dtype"] = ov["control_decay"]
    if ov.get("control_beta"):
        out["beta"] = False
    if ov.get("control_gates"):
        out["gates"] = ov["control_gates"]
    return out


def reference_numbers(ctx, params, sample, probes, states, cfg):
    """What the reference says of the sampled requests: ``{"gap": the
    served tokens' gaps, "err": the probed logits' errors, "state": the
    slow heads' state errors}``, and the same of the control's choices,
    logits and states (or None)."""
    from benchmarks.reference import kimi_linear_ref

    if not sample:
        return None, None
    unit = int(ctx.overrides.get(
        "reference_pad",
        -(-cfg["engine"]["max_seq_len"] // REFERENCE_LENGTHS)))
    sound = {"gap": [], "err": [], "state": []}
    other = {"gap": [], "err": [], "state": []}
    control = control_of(ctx)
    heads = kimi_linear_ref.slow_heads(params, cfg)
    for r, probe, state in zip(sample, probes, states):
        n = len(r["prompt"]) + len(r["tokens"]) - 1
        g = kimi_linear_ref.served_token_gaps(
            params, r["prompt"], r["tokens"], cfg,
            pad_to=-(-n // unit) * unit, states=True, **control)
        at = np.asarray(sorted(probe), np.int32)
        rows = g["logits"][at]
        sound["gap"].append(g["gap"])
        sound["err"].append(kimi_linear_ref.logit_errors(
            np.stack([probe[j] for j in at]), rows))
        err = kimi_linear_ref.state_errors(state, g["states"], heads)
        sound["state"].append(err[0])
        ctx.say(f"state of a prompt of {len(r['prompt'])} tokens "
                f"({r['restored']} restored in the window), the slow heads' "
                f"error a KDA layer: "
                + " ".join(f"{v:.3g}" for v in err.mean(1)))
        if control:
            other["gap"].append(g["control_gap"])
            other["err"].append(kimi_linear_ref.logit_errors(
                g["control_logits"][at], rows))
            other["state"].append(kimi_linear_ref.state_errors(
                g["control_states"], g["states"], heads)[0])
    sound = {k: np.concatenate(v) for k, v in sound.items()}
    ctx.report["positions"] = {k: v.tolist() for k, v in sound.items()}
    if not control:
        return sound, None
    other = {k: np.concatenate(v) for k, v in other.items()}
    ctx.report["positions"].update(
        {"control_" + k: v.tolist() for k, v in other.items()})
    c, e, st = other["gap"], other["err"], other["state"]
    asked = {k: v for k, v in ctx.overrides.items() if k.startswith("control_")}
    ctx.say(f"control {asked}: gap widest {c.max():.6g} mean {c.mean():.6g} "
            f"over {len(c)} positions, logit error mean {e.mean():.6g} over "
            f"{len(e)}, state error mean {st.mean():.6g} (the sound run's: "
            f"widest {sound['gap'].max():.6g} mean {sound['gap'].mean():.6g}, "
            f"logit error mean {sound['err'].mean():.6g}, state error mean "
            f"{sound['state'].mean():.6g})")
    ctx.report["control"] = {**{k: str(v) for k, v in asked.items()},
                             "widest": float(c.max()), "mean": float(c.mean()),
                             "positions": len(c), "logit_err": float(e.mean()),
                             "state_err": float(st.mean()),
                             "sound_state_err": float(sound["state"].mean()),
                             "sound_widest": float(sound["gap"].max()),
                             "sound_mean": float(sound["gap"].mean()),
                             "sound_logit_err": float(sound["err"].mean())}
    return sound, other


def run(ctx) -> Dict[str, Any]:
    from benchmarks.harness import context, stats, traffic as gen

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    params, eng = set_up(ctx)
    traffic = gen.serve_requests(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    warm_up(ctx, eng, traffic, cfg["vocab_size"])
    ctx.say(f"engine warm: ladder {eng.ladder}, pages {eng.num_pages}, "
            f"state entries {eng.state[0][0].shape[0]} x "
            f"{len(eng.state[0])} layers, snapshots "
            f"{eng.prefix_cache.snapshots_live}, backend compile "
            f"{ctx.clock.total:.1f}s in {ctx.clock.count} programs")
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
    st = eng.serving_stats()
    ctx.say(f"engine steps: {st['steps']}; prefix cache: "
            f"{st.get('prefix_cache')}")
    prefill = st["prefill"]
    done = [{**r, "restored": prefill[rid]["state_restored_tokens"]}
            for rid, r in drv.recs.items() if r["tokens"] is not None]
    restored = sum(v["state_restored_tokens"] for v in prefill.values())
    counters = {
        "prompt_tokens": sum(v["prompt_len"] for v in prefill.values()),
        "cached_prompt_tokens": sum(v["cached_tokens"] for v in prefill.values()),
        "state_restored_tokens": restored,
        "state_matched_tokens": restored + sum(
            v["state_lost_tokens"] for v in prefill.values()),
    }
    checks = ctx.checks
    checks.at_most("compilations_in_window", compiled_in_window, 0)
    checks.at_most("finished_with_wrong_token_count",
                   sum(1 for r in done if len(r["tokens"]) != r["want"]), 0)
    sample = sample_of(ctx, done, mix)
    t_probe = time.perf_counter()
    probes = probe_logits(eng, sample)
    states = probe_states(eng, sample)
    ctx.say(f"probed {sum(len(p) for p in probes)} positions and the states "
            f"of {len(sample)} requests in {time.perf_counter() - t_probe:.1f}s")
    checks.at_most("state_dtype_differs", sum(
        1 for pool in eng.state[0] if pool.dtype != cfg["state_dtype"]), 0)
    leaked = 0
    try:
        eng.assert_balanced()               # pages, cache, state entries
        eng.shutdown()                      # nothing of either sort leaked
    except AssertionError as e:
        ctx.say(f"engine teardown: {e}")
        leaked = 1
    checks.at_most("allocator_or_cache_inconsistent", leaked, 0)
    device = context.device_report(ctx.devices)     # the program's peak
    eng.k_pages = eng.v_pages = eng.state = None    # free the pools
    del eng

    t_ref = time.perf_counter()
    sound, control = reference_numbers(ctx, params, sample, probes, states,
                                       cfg)
    checks.at_most("no_finished_request_to_compare", int(sound is None), 0)
    judged = control if control is not None else sound
    if judged is not None:
        checks.at_most("served_token_gap_widest", float(judged["gap"].max()),
                       cfg["check"]["served_token_gap_widest"])
        checks.at_most("served_token_gap_mean", float(judged["gap"].mean()),
                       cfg["check"]["served_token_gap_mean"])
        checks.at_most("probe_logit_err_mean", float(judged["err"].mean()),
                       cfg["check"]["probe_logit_err_mean"])
        checks.at_most("state_err_slow_mean", float(judged["state"].mean()),
                       cfg["check"]["state_err_slow_mean"])
        ctx.say(f"probed logits' error: mean {judged['err'].mean():.6g} "
                f"median {np.median(judged['err']):.6g} widest "
                f"{judged['err'].max():.6g} over {len(judged['err'])} "
                f"positions")
    ctx.say(f"reference over {0 if sound is None else len(sound['gap'])} "
            f"served tokens in {time.perf_counter() - t_ref:.1f}s")

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
