"""Runner "serve_mellum2": a Mellum2-shaped configuration (window and
full layers mixed, an expert layer in every layer) behind
``ContinuousBatchingEngine``, driven exactly as runner "serve" drives a
Llama-shaped one.  ``measure`` (with its ``Driver``) and ``warm_up`` are
``runners/serve.py``'s own (the open loop, the stamps, the one compiled
unified step); what is this file's is the engine's build from the
configuration's file (a pool of pages a KIND: ``engine.num_pages`` is a
mapping), the seeded draw (``harness/weights_mellum2.py``) and the call
of the plain reference (``reference/mellum2_ref.py``).

``correct`` is decided as in the other serving cells: once the window
has closed, a seeded sample of finished requests (the longest among
them) goes through the reference once, prompt plus served tokens, and
the widest and the mean gap by which a served token's reference logit
lies below the reference's best are held to the configuration's limits
(``"check"``); plus no compilation in the window, no wrong token count,
EVERY kind's allocator and the prefix cache consistent and no page of
either kind leaked.  This model's greedy choice is rarely a near-tie, so
those gaps are a handful of flipped ties and say little of precision
(PERF.md section 2).  So the sampled prompts also go through the idle
engine once more before it is torn down (``probe_logits``): it decodes
the first ``PROBE_TOKENS`` tokens of each answer again and its LOGITS
at those positions, as far as it serves the tokens it served in the
window, are held against the reference's rows there
(``probe_logit_err_mean``: the norm of the difference over the norm of
the reference's row, a position's mean).

Controls (``ctx.overrides``, driven by ``tools/controls_mellum2.py`` and
the tests), each of which has to come out as NOT correct: the run is a
sound one, and the CONTROL's greedy choices and logits stand in the
served tokens' and the engine's place when they are held to the limits,
since a program that computed the control's way would have served them:

    control_lowp: "fp8"        the reference with every matmul operand in fp8
    control_no_window: true    every layer attends its whole context
    control_no_yarn: true      the full layers on the plain rotary table
    control_gates: "softmax"   gates not renormalised over the 8 chosen
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


def model_config(cfg: Dict[str, Any]):
    """The program's configuration from the file: every published key it
    knows; ``layer_types`` is cut to the layers that run.  A program
    without this model fails here, before any weight is drawn."""
    from paddle_tpu.models.mellum2 import Mellum2Config

    extra = {k: cfg[k] for k in ("moe_block_rows",) if k in cfg}
    return Mellum2Config.from_published(cfg, **extra)


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

    from benchmarks.harness import traffic as gen, weights_mellum2

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    model_config(cfg)               # the parent of this model's PR ends here
    need = gen.longest_request_tokens(mix)
    if need > cfg["engine"]["max_seq_len"]:
        raise ValueError(f"the mix's longest request is {need} tokens, the "
                         f"engine's max_seq_len {cfg['engine']['max_seq_len']}")
    params = weights_mellum2.draw_params(cfg, ctx.seed,
                                         jnp.dtype(cfg["torch_dtype"]))
    jax.block_until_ready(params)
    ctx.say(f"weights drawn ({sum(v.nbytes for v in params.values()) / 2**30:.2f} GiB)")
    return params, build_engine(ctx, params)


def control_of(ctx) -> Dict[str, Any]:
    """The reference's keyword arguments of the control asked for."""
    ov, out = ctx.overrides, {}
    if ov.get("control_lowp"):
        out["lowp"] = ov["control_lowp"]
    if ov.get("control_no_window"):
        out["use_window"] = False
    if ov.get("control_no_yarn"):
        out["yarn"] = False
    if ov.get("control_gates"):
        out["gates"] = ov["control_gates"]
    return out


#: tokens of each sampled answer that the engine decodes again for its
#: logits (``probe_logits``)
PROBE_TOKENS = 16


def sample_of(ctx, done: List[Dict[str, Any]], mix) -> List[Dict[str, Any]]:
    """A seeded sample of the finished requests, the longest first."""
    if not done:
        return []
    k = int(mix.get("check_sample", 4))
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i]["prompt"]) + done[i]["want"]))
    rng = np.random.default_rng([int(ctx.seed), 3])
    rest = [int(i) for i in rng.permutation(order[1:])[:k - 1]]
    return [done[i] for i in [order[0], *rest]]


def probe_logits(eng, sample) -> List[Dict[int, np.ndarray]]:
    """The engine's own logits where each sampled answer begins: the
    prompts go through the idle engine together (chunked prefill, the
    prefix cache, both kinds of page, as in the window), each decodes
    ``PROBE_TOKENS`` tokens, and ``eng.last_logits`` is read after every
    call.  For each request ``{j: the logits [vocab] from which token j
    of the answer was chosen}``, for every ``j`` up to the first at
    which the engine now serves another token than it served in the
    window (that row still follows the served tokens; a later one does
    not, and a near-tie may fall either way between two batches)."""
    if not sample:
        return []
    first, rows = {}, {}
    for n, r in enumerate(sample):
        rid = eng.add_request(np.asarray(r["prompt"]), max_new_tokens=min(
            PROBE_TOKENS, len(r["tokens"])))
        first[rid], rows[rid] = (n, len(r["prompt"]) - 1), {}
    while eng.queue or eng.active.any():
        eng.step()
        for (rid, pos), row in zip(*(eng.last_logits or ((), ()))):
            if rid in rows:
                rows[rid][pos - first[rid][1]] = row
    again = {f.rid: f.tokens for f in eng.finished if f.rid in rows}
    out = [{} for _ in sample]
    for rid, (n, _) in first.items():
        served = np.asarray(sample[n]["tokens"])[:len(again[rid])]
        same = np.asarray(again[rid]) == served
        upto = len(same) if same.all() else int(np.argmin(same)) + 1
        out[n] = {j: rows[rid][j] for j in range(upto)}
    return out


def reference_numbers(ctx, params, sample, probes, cfg):
    """What the reference says of the sampled requests: ``{"gap": the
    served tokens' gaps, "err": the probed logits' errors}``, and the
    same of the control's choices and logits (or None)."""
    from benchmarks.reference import mellum2_ref

    if not sample:
        return None, None
    control = control_of(ctx)
    # every sampled request runs at ONE length, the longest the engine
    # takes (the longest finished request is among them and is near it):
    # the reference's compile time is most of its cost (25 s a shape
    # against 7 s a run at 25k tokens: PERF.md, PR 30).  A tool that
    # runs many seeds in one process may ask for a shorter unit
    unit = int(ctx.overrides.get("reference_pad", cfg["engine"]["max_seq_len"]))
    sound = {"gap": [], "err": []}
    other = {"gap": [], "err": []}
    for r, probe in zip(sample, probes):
        n = len(r["prompt"]) + len(r["tokens"]) - 1
        g = mellum2_ref.served_token_gaps(params, r["prompt"], r["tokens"],
                                          cfg, pad_to=-(-n // unit) * unit,
                                          **control)
        at = np.asarray(sorted(probe), np.int32)
        rows = g["logits"][at]
        sound["gap"].append(g["gap"])
        sound["err"].append(mellum2_ref.logit_errors(
            np.stack([probe[j] for j in at]), rows))
        if control:
            other["gap"].append(g["control_gap"])
            other["err"].append(mellum2_ref.logit_errors(
                g["control_logits"][at], rows))
    sound = {k: np.concatenate(v) for k, v in sound.items()}
    ctx.report["positions"] = {k: v.tolist() for k, v in sound.items()}
    if not control:
        return sound, None
    other = {k: np.concatenate(v) for k, v in other.items()}
    ctx.report["positions"].update(
        {"control_" + k: v.tolist() for k, v in other.items()})
    c, e = other["gap"], other["err"]
    ctx.say(f"control {control}: gap widest {c.max():.6g} mean {c.mean():.6g} "
            f"over {len(c)} positions, logit error mean {e.mean():.6g} over "
            f"{len(e)} (the sound run's: widest {sound['gap'].max():.6g} "
            f"mean {sound['gap'].mean():.6g}, logit error mean "
            f"{sound['err'].mean():.6g})")
    ctx.report["control"] = {**{k: str(v) for k, v in control.items()},
                             "widest": float(c.max()), "mean": float(c.mean()),
                             "positions": len(c), "logit_err": float(e.mean()),
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
    ctx.say(f"engine warm: rows_cap {eng.rows_cap}, pages "
            f"{ {kp.kind.name: kp.num_pages for kp in eng.pages} }, "
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
    ctx.say(f"engine steps: {st['steps']}; prefix cache: "
            f"{st.get('prefix_cache')}")
    prefill = st["prefill"]
    counters = {
        "prompt_tokens": sum(v["prompt_len"] for v in prefill.values()),
        "cached_prompt_tokens": sum(v["cached_tokens"] for v in prefill.values()),
    }
    checks = ctx.checks
    checks.at_most("compilations_in_window", compiled_in_window, 0)
    checks.at_most("finished_with_wrong_token_count",
                   sum(1 for r in done if len(r["tokens"]) != r["want"]), 0)
    sample = sample_of(ctx, done, mix)
    t_probe = time.perf_counter()
    probes = probe_logits(eng, sample)
    ctx.say(f"probed {sum(len(p) for p in probes)} positions of "
            f"{len(sample)} requests in {time.perf_counter() - t_probe:.1f}s")
    leaked = 0
    try:
        for kp in eng.pages:                # every kind of page
            kp.alloc.assert_consistent()
        if eng.prefix_cache is not None:
            eng.prefix_cache.assert_consistent()
        eng.shutdown()                      # no page of any kind leaked
    except AssertionError as e:
        ctx.say(f"engine teardown: {e}")
        leaked = 1
    checks.at_most("allocator_or_cache_inconsistent", leaked, 0)
    device = context.device_report(ctx.devices)     # the program's peak
    eng.k_pages = eng.v_pages = None                # free the pools
    del eng

    t_ref = time.perf_counter()
    sound, control = reference_numbers(ctx, params, sample, probes, cfg)
    checks.at_most("no_finished_request_to_compare", int(sound is None), 0)
    judged = control if control is not None else sound
    if judged is not None:
        checks.at_most("served_token_gap_widest", float(judged["gap"].max()),
                       cfg["check"]["served_token_gap_widest"])
        checks.at_most("served_token_gap_mean", float(judged["gap"].mean()),
                       cfg["check"]["served_token_gap_mean"])
        checks.at_most("probe_logit_err_mean", float(judged["err"].mean()),
                       cfg["check"]["probe_logit_err_mean"])
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
