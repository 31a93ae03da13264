"""Runner "serve": a model behind ``ContinuousBatchingEngine``, driven
by one process with an open (or closed) loop from a traffic mix.

The loop is the open loop of ``bench.py serving_trace`` (arrivals keyed
to wall time, time to first token from the DUE time), at the
configuration's sizes, with every token's arrival at the host
timestamped.  The engine and its compiled unified step that the window
drives are the ones whose served tokens are compared with the plain
reference once the window has closed.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

SPANS = ("admit", "engine.step", "bookkeeping", "wait_for_arrival")
WINDOW_SPAN = "traced_window"

# `correct` rests on the widest and the mean gap, over a seeded sample of
# finished requests, by which a served (greedy) token's float32-reference
# logit lies below the reference's best.  The limits are the
# configuration's own (its file's "check"); PERF.md section 2 gives the
# readings they were set from.


def llama_config(cfg: Dict[str, Any]):
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"])


def build_engine(ctx, params):
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    kw = dict(ctx.cell.config["engine"])
    kw["cache_dtype"] = jnp.dtype(kw.pop("cache_dtype"))
    kw.update(ctx.overrides.get("engine", {}))
    return ContinuousBatchingEngine(llama_config(ctx.cell.config), params, **kw)


class Driver:
    """Sends requests when they are due, steps the engine, and stamps
    every token with the time it reached the host."""

    def __init__(self, ctx, eng):
        self.ctx, self.eng, self.span = ctx, eng, ctx.spans.span
        self.recs: Dict[int, Dict[str, Any]] = {}
        self.seen_finished = len(eng.finished)
        self.n_open = 0                       # sent and not yet finished
        self.timeline: List[tuple] = []       # (time, n_open) after each step

    def send(self, req: Dict[str, Any], due: float, now: float) -> None:
        rid = self.eng.add_request(req["prompt"], max_new_tokens=req["max_new"])
        self.recs[rid] = {"due": due, "sent": now, "emit": [],
                          "want": req["max_new"], "prompt": req["prompt"],
                          "tokens": None}
        self.n_open += 1

    def step(self) -> None:
        with self.span("engine.step"):
            self.eng.step()
        with self.span("bookkeeping"):
            t = time.perf_counter()
            for rid, toks in self.eng.out_tokens.items():
                rec = self.recs.get(rid)
                if rec is not None:
                    rec["emit"].extend([t] * (len(toks) - len(rec["emit"])))
            for f in self.eng.finished[self.seen_finished:]:
                rec = self.recs.get(f.rid)
                if rec is not None:
                    rec["emit"].extend([t] * (len(f.tokens) - len(rec["emit"])))
                    rec["tokens"] = np.asarray(f.tokens)
                    self.n_open -= 1
            self.seen_finished = len(self.eng.finished)
            self.timeline.append((t, self.n_open))

    def busy(self) -> bool:
        return bool(self.eng.queue) or bool(self.eng.active.any())

    def open_at(self, t: float) -> int:
        """Requests sent and not finished at time ``t`` (the backlog)."""
        n = 0
        for when, n_open in self.timeline:
            if when > t:
                break
            n = n_open
        return n


def warm_up(ctx, eng, traffic, vocab: int) -> None:
    """Compile the one unified step and leave each shared prefix in the
    prefix cache, as a deployment that has been up would hold them."""
    prompts = traffic["prefixes"] or \
        [np.random.default_rng(0).integers(0, vocab, eng.page_size).astype(np.int32)]
    for p in prompts:
        eng.add_request(p, max_new_tokens=2)
    eng.run()
    eng.finished.clear()
    eng.prefill_stats.clear()


def set_up(ctx):
    """Weights from the seed and the engine over them: ``(params, eng)``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import traffic as gen, weights

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    need = gen.longest_request_tokens(mix)
    if need > cfg["engine"]["max_seq_len"]:
        raise ValueError(f"the mix's longest request is {need} tokens, the "
                         f"engine's max_seq_len {cfg['engine']['max_seq_len']}")
    params = weights.draw_params(cfg, ctx.seed, jnp.dtype(cfg["torch_dtype"]))
    jax.block_until_ready(params)
    ctx.say(f"weights drawn ({sum(v.nbytes for v in params.values()) / 2**30:.2f} GiB)")
    eng = build_engine(ctx, params)
    return params, eng


def measure(ctx, eng, mix, reqs):
    """Drive one window of ``ctx.seconds`` and its drain.  Returns the
    driver, the window's start, the traced interval and the number of
    programs compiled meanwhile."""
    from benchmarks.harness import context

    drv = Driver(ctx, eng)
    arr = mix["arrivals"]
    closed = arr["process"] == "closed"
    drain_s = float(mix["drain_s"])
    tw = context.TraceWindow(ctx, WINDOW_SPAN, mix.get("trace_s", 3.0))
    compiled_before = ctx.clock.count
    nxt = 0
    t0 = time.perf_counter()
    while True:
        tw.poll(time.perf_counter() - t0)
        now = time.perf_counter() - t0
        with drv.span("admit"):
            if closed:
                while now < ctx.seconds and drv.n_open < arr["clients"]:
                    drv.send(reqs[nxt % len(reqs)], now, now)
                    nxt += 1
            else:
                while nxt < len(reqs) and reqs[nxt]["due"] <= now:
                    drv.send(reqs[nxt], reqs[nxt]["due"], now)
                    nxt += 1
        all_sent = now >= ctx.seconds if closed else nxt >= len(reqs)
        if drv.busy():
            if all_sent and now >= ctx.seconds + drain_s:
                break
            drv.step()
        elif all_sent:
            break
        else:
            with drv.span("wait_for_arrival"):
                wait = (ctx.seconds if closed else reqs[nxt]["due"]) - now
                time.sleep(max(0.0, min(wait, 0.05)))
    tw.close()
    for rid, rec in drv.recs.items():         # what the drain limit cut off
        if rec["tokens"] is None:
            eng.cancel(rid)
    return drv, t0, tw.interval, ctx.clock.count - compiled_before


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
    prefill = eng.serving_stats()["prefill"]
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
    gaps = reference_gaps(ctx, params, done, cfg, mix)
    checks.at_most("no_finished_request_to_compare", int(gaps is None), 0)
    if gaps is not None:
        checks.at_most("served_token_gap_widest", float(gaps.max()),
                       cfg["check"]["served_token_gap_widest"])
        checks.at_most("served_token_gap_mean", float(gaps.mean()),
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


def reference_gaps(ctx, params, done: List[Dict[str, Any]], cfg, mix):
    """Gaps of the served tokens of a seeded sample of finished requests
    (the longest among them) under the plain reference."""
    from benchmarks.reference import decoder_ref

    if not done:
        return None
    k = int(mix.get("check_sample", 4))
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i]["prompt"]) + done[i]["want"]))
    rng = np.random.default_rng([int(ctx.seed), 3])
    rest = [int(i) for i in rng.permutation(order[1:])[:k - 1]]
    lowp = ctx.overrides.get("control_lowp")
    gaps, control = [], []
    for i in [order[0], *rest]:
        r = done[i]
        g = decoder_ref.served_token_gaps(params, r["prompt"], r["tokens"],
                                          cfg, lowp=lowp)
        gaps.append(g["gap"])
        if lowp:
            control.append(g["control_gap"])
    if lowp:
        c = np.concatenate(control)
        ctx.say(f"control {lowp}: gap widest {c.max():.6g} mean {c.mean():.6g} "
                f"over {len(c)} positions")
        ctx.report["control"] = {"lowp": lowp, "widest": float(c.max()),
                                 "mean": float(c.mean()), "positions": len(c)}
    return np.concatenate(gaps)
