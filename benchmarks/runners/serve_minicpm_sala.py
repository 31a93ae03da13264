"""Runner "serve_minicpm_sala": a MiniCPM-SALA-shaped configuration
(``minicpm4`` block-sparse attention layers over a paged K/V with a cache
of compressed keys, ``lightning-attn`` layers whose state rides in the
engine's state pool) behind ``ContinuousBatchingEngine``, driven exactly
as runner "serve" drives a Llama-shaped one.  ``measure`` (with its
``Driver``) is ``runners/serve.py``'s own, the states' probe
``runners/serve_nemotron_h.py``'s; what is this file's is the engine's
build from the configuration's file, the seeded draw
(``harness/weights_minicpm_sala.py``), the warm-up that leaves every
session history in the prefix cache with a snapshot at its END, the
sample (requests of ONE session), the probe (the engine's logits AND its
block selections), the call of the plain reference
(``reference/minicpm_sala_ref.py``) and the counters.

``correct`` is decided as in the Nemotron cell: once the window has
closed, a seeded sample of finished requests (the longest, and a draw
from those whose turns are shortest: what a restore got wrong fades
within a few hundred tokens) goes through the reference once, history,
turn and served tokens, and the widest and the mean gap by which a
served token's reference logit lies below the reference's best are held
to the configuration's limits (``"check"``); the sampled prompts go
through the idle engine once more (every one RESTORES a snapshot and
the pages of its history) and its logits where the answer begins are
held against the reference's rows (``probe_logit_err_mean``), the
reference taking at THOSE positions the blocks the engine selected
(``engine.last_extras``): bf16 and float32 order near-tied blocks
differently, a few of a row's 64, and each is a 64th of what the row
attends, which is noise of the size of the precision control
(``reference_selection: "own"`` reads it without); they go through it a
third time for one token each, and the lightning state each prompt
leaves in its slot's entry is held against the reference's ``S`` after
the same tokens, in the heads that forget slowest
(``state_err_slow_mean``); the state pools are of the type the
configuration's file states (``state_dtype``), exactly; plus no
compilation in the window, no wrong token count, every allocator, the
prefix cache and the snapshot entries consistent and balanced.

Controls (``ctx.overrides``, driven by ``tools/controls_minicpm_sala.py``
and the tests), each of which has to come out as NOT correct: the run is
a sound one, and the CONTROL's greedy choices, logits and states stand
in the served tokens' and the engine's place when they are held to the
limits:

    control_lowp: "fp8"          the reference with every matmul operand in fp8
    control_attend: "dense"      every row attending its whole context
    control_forced: "dropped"    the forced first and local blocks left out
                                 of the selection
    control_restore: "zeros"     a restore that starts from zeros, not the
                                 snapshot (at the tokens the request restored)
    control_decay: "dropped"     the decay left out (lambda 1)
    control_gate: "dropped"      the attention layers' output gate left out
"""

from __future__ import annotations

import pathlib
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks.harness import manifest

# sibling runners (the directory is no package)
nemotron = manifest.load_runner(pathlib.Path(__file__).resolve().parents[2],
                                "serve_nemotron_h")
serve = nemotron.serve
measure = serve.measure                             # tools/sweep.py's too
SPANS, WINDOW_SPAN = serve.SPANS, serve.WINDOW_SPAN
NEAR = nemotron.NEAR
PROBE_TOKENS = nemotron.mellum2.PROBE_TOKENS

#: the reference's keyword a control sets, by the override that asks
CONTROLS = {"control_attend": "dense_all", "control_forced": "no_forced",
            "control_decay": "no_decay", "control_gate": "no_gate"}


def sample_of(ctx, done, mix):
    """``runners/serve_nemotron_h.sample_of`` within ONE session: the
    longest finished request, and a seeded draw of the others from the
    ``NEAR`` requests of the SAME history whose turns are shortest (the
    lightning state forgets: only a request that begins to answer soon
    after its restore says whether the restore was right).  The sample
    shares its history so that the reference passes 65,536 tokens once a
    run and not once a request.  Where the session has too few, the
    draw is from all."""
    if not done:
        return []
    k = int(mix.get("check_sample", 4))
    hist = int(mix.get("prefix", {}).get("tokens", 0))
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i]["prompt"]) + done[i]["want"]))
    head = np.asarray(done[order[0]]["prompt"][:hist])
    near = sorted((i for i in order[1:] if done[i]["restored"] > 0
                   and np.array_equal(done[i]["prompt"][:hist], head)),
                  key=lambda i: len(done[i]["prompt"]) - done[i]["restored"])
    pool = near[:NEAR] if len(near) >= k - 1 else order[1:]
    rng = np.random.default_rng([int(ctx.seed), 3])
    rest = [int(i) for i in rng.permutation(pool)[:k - 1]]
    return [done[i] for i in [order[0], *rest]]


def model_config(cfg: Dict[str, Any]):
    """The program's configuration from the file: every published key it
    knows, the published depth, the layers that run and the family's
    sparse sizes.  A program without this model fails here, before any
    weight is drawn."""
    from paddle_tpu.models.minicpm_sala import MiniCPMSALAConfig

    return MiniCPMSALAConfig.from_published(
        cfg, num_hidden_layers=cfg["published"]["num_hidden_layers"],
        layers_run=tuple(cfg["layers_run"]), **cfg["sparse_config"])


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

    from benchmarks.harness import traffic as gen, weights_minicpm_sala

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    model_config(cfg)               # the parent of this model's PR ends here
    need = gen.longest_request_tokens(mix)
    if need > cfg["engine"]["max_seq_len"]:
        raise ValueError(f"the mix's longest request is {need} tokens, the "
                         f"engine's max_seq_len {cfg['engine']['max_seq_len']}")
    params = weights_minicpm_sala.draw_params(cfg, ctx.seed,
                                              jnp.dtype(cfg["torch_dtype"]))
    jax.block_until_ready(params)
    ctx.say(f"weights drawn ({sum(v.nbytes for v in params.values()) / 2**30:.2f} GiB, "
            f"{weights_minicpm_sala.count(cfg) / 1e9:.3f} B parameters)")
    return params, build_engine(ctx, params)


def warm_up(ctx, eng, traffic, vocab: int) -> None:
    """Compile the one unified step and leave each session history in
    the prefix cache with a state snapshot at its END, as a deployment
    whose sessions have been going on would hold them.  A history of
    512 pages passes 128 chunk ends and the cache keeps 32 snapshots,
    least recently restored first out: the histories are prefilled one
    after the other, and after each a turn of one token restores from
    every history so far, so that what the next history's prefill evicts
    is the snapshots INSIDE the earlier ones."""
    histories = traffic["prefixes"] or \
        [np.random.default_rng(0).integers(0, vocab, eng.page_size).astype(np.int32)]
    t0 = time.perf_counter()
    restored = []
    for n, history in enumerate(histories):
        eng.add_request(history, max_new_tokens=2)
        eng.run()
        if n == 0:
            ctx.say(f"the first history ({len(history)} tokens) prefilled, the "
                    f"step compiled: {time.perf_counter() - t0:.1f}s")
        restored = []
        for h in histories[:n + 1]:
            rid = eng.add_request(np.concatenate([h, h[:1]]), max_new_tokens=1)
            eng.run()
            restored.append(eng.prefill_stats[rid]["state_restored_tokens"])
    ctx.say(f"{len(histories)} histories of {len(histories[0])} tokens in the "
            f"prefix cache in {time.perf_counter() - t0:.1f}s; a turn restores "
            f"{restored} tokens of them")
    ctx.checks.at_most("histories_without_a_snapshot_at_their_end", sum(
        1 for h, r in zip(histories, restored)
        if r != len(h) // eng.page_size * eng.page_size), 0)
    eng.finished.clear()
    eng.prefill_stats.clear()


def probe(eng, sample) -> List[Dict[int, tuple]]:
    """The engine's own logits AND block selections where each sampled
    answer begins, as ``runners/serve_mellum2.probe_logits`` reads the
    logits: the prompts go through the idle engine together, each
    decodes ``PROBE_TOKENS`` tokens, and ``eng.last_logits`` and
    ``eng.last_extras`` are read after every call.  For each request
    ``{j: (the logits [vocab] from which token j of the answer was
    chosen, the blocks [minicpm4 layers, kvh, topk] that row
    selected)}``, for every ``j`` up to the first at which the engine
    now serves another token than it served in the window."""
    if not sample:
        return []
    first, rows = {}, {}
    for n, r in enumerate(sample):
        rid = eng.add_request(np.asarray(r["prompt"]), max_new_tokens=min(
            PROBE_TOKENS, len(r["tokens"])))
        first[rid], rows[rid] = (n, len(r["prompt"]) - 1), {}
    while eng.queue or eng.active.any():
        eng.step()
        gathered, logits = eng.last_logits or ((), ())
        (sel,) = eng.last_extras or ((),)
        for (rid, pos), row, blocks in zip(gathered, logits, sel):
            if rid in rows:
                rows[rid][pos - first[rid][1]] = (row, blocks)
    again = {f.rid: f.tokens for f in eng.finished if f.rid in rows}
    out = [{} for _ in sample]
    for rid, (n, _) in first.items():
        served = np.asarray(sample[n]["tokens"])[:len(again[rid])]
        same = np.asarray(again[rid]) == served
        upto = len(same) if same.all() else int(np.argmin(same)) + 1
        out[n] = {j: rows[rid][j] for j in range(upto)}
    return out


def probe_states(eng, sample):
    """``runners/serve_nemotron_h.probe_states`` in the reference's
    layout: ``[state layers, heads, key, value]`` a request (the pool
    holds ``[heads, value, key]``)."""
    return [s.transpose(0, 1, 3, 2)
            for s in nemotron.probe_states(eng, sample)]


def control_of(ctx, request: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's keyword arguments of the control asked for, for
    one sampled request (``restored``: the tokens a snapshot gave it)."""
    ov = ctx.overrides
    out = {kw: True for name, kw in CONTROLS.items() if ov.get(name)}
    if ov.get("control_lowp"):
        out["lowp"] = ov["control_lowp"]
    if ov.get("control_restore"):
        out["zero_state_at"] = int(request["restored"])
    return out


def selection_of(cfg, request, probed) -> Dict[int, tuple]:
    """The engine's selections at the probed positions, as the reference
    takes them: ``{published layer: (positions, blocks [n, kvh, topk])}``."""
    at = np.asarray(sorted(probed), np.int64)
    layers = [l for l in range(*cfg["layers_run"])
              if cfg["mixer_types"][l] == "minicpm4"]
    blocks = np.stack([probed[j][1] for j in at])   # [n, layers, kvh, topk]
    return {l: (len(request["prompt"]) - 1 + at, blocks[:, i])
            for i, l in enumerate(layers)}


def reference_numbers(ctx, params, sample, probes, states, cfg):
    """What the reference says of the sampled requests: ``{"gap": the
    served tokens' gaps, "err": the probed logits' errors, "state": the
    slow heads' state errors}``, and the same of the control's choices,
    logits and states (or None)."""
    from benchmarks.reference import minicpm_sala_ref as ref

    if not sample:
        return None, None
    # a session's history is passed ONCE: the first request that begins
    # with it leaves what its tokens leave (keys, values, states) to the
    # others of the sample, whose turns and answers alone are computed
    hist = int(ctx.cell.traffic.get("prefix", {}).get("tokens", 0))
    left: Dict[bytes, tuple] = {}
    unit = int(ctx.overrides.get("reference_pad", 1024))
    q_block = int(ctx.overrides.get("reference_q_block", 64))
    own = ctx.overrides.get("reference_selection") == "own"
    sound = {"gap": [], "err": [], "state": []}
    other = {"gap": [], "err": [], "state": []}
    control = {}
    heads = ref.slow_heads(cfg)
    for r, probed, state in zip(sample, probes, states):
        control = control_of(ctx, r)
        key = np.asarray(r["prompt"][:hist]).tobytes() \
            if 0 < hist < len(r["prompt"]) else None
        g = ref.served_token_gaps(
            params, r["prompt"], r["tokens"], cfg, pad_to=unit, states=True,
            q_block=q_block, prefixes=left.get(key),
            keep_prefix=hist if key is not None and key not in left else None,
            selection=None if own else selection_of(cfg, r, probed),
            **control)
        if key is not None:
            left.setdefault(key, g["prefixes"])
        at = np.asarray(sorted(probed), np.int32)
        rows = g["logits"][at]
        sound["gap"].append(g["gap"])
        sound["err"].append(ref.logit_errors(
            np.stack([probed[j][0] for j in at]), rows))
        err = ref.state_errors(state, g["states"], heads)
        sound["state"].append(err.ravel())
        ctx.say(f"state of a prompt of {len(r['prompt'])} tokens "
                f"({r['restored']} restored in the window), the slow heads' "
                f"error a state layer: "
                + " ".join(f"{v:.3g}" for v in err.mean(1)))
        if control:
            other["gap"].append(g["control_gap"])
            other["err"].append(ref.logit_errors(g["control_logits"][at], rows))
            other["state"].append(ref.state_errors(
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
    ctx.say(f"engine warm: rows_cap {eng.rows_cap}, pages {eng.num_pages} "
            f"({eng.num_pages - 1 - eng.alloc.available} held), state entries "
            f"{eng.state[0][0].shape[0]} x {len(eng.state[0])} layers, "
            f"snapshots {eng.prefix_cache.snapshots_live}, backend compile "
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
            f"{st.get('prefix_cache')}; pages held at the window's end "
            f"{eng.num_pages - 1 - eng.alloc.available} of {eng.num_pages}")
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
    probes = probe(eng, sample)
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
    eng.k_pages = eng.v_pages = eng.state = eng.more_pools = None
    eng.last_logits = None                          # free the pools
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
