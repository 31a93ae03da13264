"""Runner "serve_nemotron_h": a Nemotron-H-shaped configuration (Mamba-2,
attention and expert layers by the letters of a pattern; a recurrent
state a slot beside the paged K/V) behind ``ContinuousBatchingEngine``,
driven exactly as runner "serve" drives a Llama-shaped one.  ``measure``
(with its ``Driver``) and ``warm_up`` are ``runners/serve.py``'s own, the
probe of the engine's own logits ``runners/serve_mellum2.py``'s; what is
this file's is the engine's build from the configuration's file
(``engine.state_snapshots``), the seeded draw
(``harness/weights_nemotron_h.py``), the sample (``sample_of``), the call
of the plain reference (``reference/nemotron_h_ref.py``) and the state's
counters.

``correct`` is decided as in the Mellum2 cell: once the window has
closed, a seeded sample of finished requests (the longest, and a draw
from those whose prompts end soonest after the snapshot they restored:
what a restore got wrong fades within a few hundred tokens)
goes through the reference once, prompt plus served tokens, and the
widest and the mean gap by which a served token's reference logit lies
below the reference's best are held to the configuration's limits
(``"check"``); the sampled prompts go through the idle engine once more
(every one of them then RESTORES a snapshot of its own prompt's state)
and its logits where the answer begins are held against the reference's
rows (``probe_logit_err_mean``); they go through it a third time for
one token each, and the recurrent state each prompt leaves in its slot's
entry is held against the reference's ``S`` after the same tokens, in
the heads that forget slowest (``state_err_slow_mean``: what a restore
lost or a narrower state rounded away a token at a time adds up there
over hundreds of tokens, where no logit shows it); the state pools are of
the type the configuration's file states (``ssm_state_dtype``), exactly;
plus no compilation in the window, no wrong token count, every
allocator, the prefix cache and the state snapshot entries consistent
and balanced, nothing leaked.

Controls (``ctx.overrides``, driven by ``tools/controls_nemotron_h.py``
and the tests), each of which has to come out as NOT correct: the run is
a sound one, and the CONTROL's greedy choices and logits stand in the
served tokens' and the engine's place when they are held to the limits,
since a program that computed the control's way would have served them:

    control_lowp: "fp8"          the reference with every matmul operand in fp8
    control_state: "bfloat16"    the recurrent state kept in bf16 token to token
    control_restore: "zeros"     a restore that starts from zeros, not the
                                 snapshot (at the tokens the request restored)
    control_conv: "dropped"      the convolution's tail dropped where a
                                 launch's run of rows begins: each chunk of
                                 the prefill budget, every decode row
    control_gates: "held"        gates normalised over the held experts only
"""

from __future__ import annotations

import pathlib
import time
from typing import Any, Dict

import numpy as np

from benchmarks.harness import manifest

# a sibling runner (the directory is no package)
mellum2 = manifest.load_runner(pathlib.Path(__file__).resolve().parents[2],
                               "serve_mellum2")
serve = mellum2.serve
measure, warm_up = serve.measure, serve.warm_up     # tools/sweep.py's too
SPANS, WINDOW_SPAN = serve.SPANS, serve.WINDOW_SPAN
probe_logits = mellum2.probe_logits

#: the finished requests nearest their restore point that ``sample_of``
#: draws from
NEAR = 8


def sample_of(ctx, done, mix):
    """The sample that decides ``correct``: the longest finished request
    (a context near the engine's limit) and a seeded draw of the others
    from the ``NEAR`` whose prompts end soonest after the snapshot they
    restored.  The recurrent state forgets: a restore from zeros moves
    the logits by 15% some 40 tokens on, by 2% some 500 on and by 0.2%
    some 2000 on (PERF.md, PR 33), so only a request that begins to
    answer soon after its restore says whether the restore was right.
    Where fewer than asked restored anything, the draw is from all."""
    if not done:
        return []
    k = int(mix.get("check_sample", 4))
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i]["prompt"]) + done[i]["want"]))
    near = sorted((i for i in order[1:] if done[i]["restored"] > 0),
                  key=lambda i: len(done[i]["prompt"]) - done[i]["restored"])
    pool = near[:NEAR] if len(near) >= k - 1 else order[1:]
    rng = np.random.default_rng([int(ctx.seed), 3])
    rest = [int(i) for i in rng.permutation(pool)[:k - 1]]
    return [done[i] for i in [order[0], *rest]]


def probe_states(eng, sample):
    """The recurrent state each sampled PROMPT leaves: the prompts go
    through the idle engine once more, one after another (each restores
    the deepest snapshot of its own prompt and prefills the rest in
    chunks), for ONE token, so that no decode row has touched the
    request's entry when it ends; ``[state layers, heads, head_dim,
    state]`` a request, read from its entry (``prefill_stats``'
    ``state_entry``: its slot's own, which stays as the request left it
    until the slot's next tenant's first launch) of ``eng.state``'s
    first pools."""
    out = []
    for r in sample:
        rid = eng.add_request(np.asarray(r["prompt"]), max_new_tokens=1)
        while eng.queue or eng.active.any():
            eng.step()
        entry = eng.prefill_stats[rid]["state_entry"]
        out.append(np.stack([np.asarray(pool[entry], np.float32)
                             for pool in eng.state[0]]))
    return out


def model_config(cfg: Dict[str, Any]):
    """The program's configuration from the file: every published key it
    knows; the router keeps its published width and the chip its share.
    A program without this model fails here, before any weight is drawn."""
    from benchmarks.reference.nemotron_h_ref import held_range
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    extra = {k: cfg[k] for k in ("moe_block_rows",) if k in cfg}
    wide = cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])
    return NemotronHConfig.from_published(
        cfg, n_routed_experts=wide, experts_held=held_range(cfg), **extra)


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

    from benchmarks.harness import traffic as gen, weights_nemotron_h

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    model_config(cfg)               # the parent of this model's PR ends here
    need = gen.longest_request_tokens(mix)
    if need > cfg["engine"]["max_seq_len"]:
        raise ValueError(f"the mix's longest request is {need} tokens, the "
                         f"engine's max_seq_len {cfg['engine']['max_seq_len']}")
    params = weights_nemotron_h.draw_params(cfg, ctx.seed,
                                            jnp.dtype(cfg["torch_dtype"]))
    jax.block_until_ready(params)
    ctx.say(f"weights drawn ({sum(v.nbytes for v in params.values()) / 2**30:.2f} GiB)")
    return params, build_engine(ctx, params)


def control_of(ctx, request: Dict[str, Any], chunk: int) -> Dict[str, Any]:
    """The reference's keyword arguments of the control asked for, for
    one sampled request (``restored``: the tokens a snapshot gave it)."""
    ov, out = ctx.overrides, {}
    n = len(request["prompt"])
    if ov.get("control_lowp"):
        out["lowp"] = ov["control_lowp"]
    if ov.get("control_state"):
        out["state_dtype"] = ov["control_state"]
    if ov.get("control_restore"):
        out["zero_state_at"] = int(request["restored"])
    if ov.get("control_conv"):
        out["conv_runs"] = np.concatenate([
            np.arange(int(request["restored"]), n, chunk),
            np.arange(n, n + len(request["tokens"]))]).astype(np.int64)
    if ov.get("control_gates"):
        out["gates"] = ov["control_gates"]
    return out


def reference_numbers(ctx, params, sample, probes, states, cfg):
    """What the reference says of the sampled requests: ``{"gap": the
    served tokens' gaps, "err": the probed logits' errors, "state": the
    slow heads' state errors}``, and the same of the control's choices,
    logits and states (or None)."""
    from benchmarks.reference import nemotron_h_ref

    if not sample:
        return None, None
    # every sampled request runs at ONE length, the longest the engine
    # takes: the reference's compile time is most of its cost
    unit = int(ctx.overrides.get("reference_pad", cfg["engine"]["max_seq_len"]))
    chunk = int(cfg["engine"]["prefill_token_budget"])
    sound = {"gap": [], "err": [], "state": []}
    other = {"gap": [], "err": [], "state": []}
    control = {}
    heads = nemotron_h_ref.slow_heads(params, cfg)
    for r, probe, state in zip(sample, probes, states):
        control = control_of(ctx, r, chunk)
        n = len(r["prompt"]) + len(r["tokens"]) - 1
        g = nemotron_h_ref.served_token_gaps(
            params, r["prompt"], r["tokens"], cfg,
            pad_to=-(-n // unit) * unit, states=True, **control)
        at = np.asarray(sorted(probe), np.int32)
        rows = g["logits"][at]
        sound["gap"].append(g["gap"])
        sound["err"].append(nemotron_h_ref.logit_errors(
            np.stack([probe[j] for j in at]), rows))
        err = nemotron_h_ref.state_errors(state, g["states"], heads)
        sound["state"].append(err.ravel())
        ctx.say(f"state of a prompt of {len(r['prompt'])} tokens "
                f"({r['restored']} restored in the window), the slow heads' "
                f"error a state layer: "
                + " ".join(f"{v:.3g}" for v in err.mean(1)))
        if control:
            other["gap"].append(g["control_gap"])
            other["err"].append(nemotron_h_ref.logit_errors(
                g["control_logits"][at], rows))
            other["state"].append(nemotron_h_ref.state_errors(
                g["control_states"], g["states"], heads).ravel())
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
    ctx.say(f"engine warm: rows_cap {eng.rows_cap}, pages {eng.num_pages}, "
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
    checks.at_most("ssm_state_dtype_differs", sum(
        1 for pool in eng.state[0] if pool.dtype != cfg["ssm_state_dtype"]), 0)
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
