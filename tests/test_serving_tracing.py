"""The measurement inside the unified serving step: ``serving.*`` spans
in whatever profiler trace runs (``profiler.RecordEvent`` opens a
``jax.profiler.TraceAnnotation``), their per-step counts and per-request
stamps, the same counts kept in ``serving_stats()["steps"]``, and the
``jax.named_scope``s of the step, which change nothing that is compiled.
"""

import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import self_draft_params

PHASES = ("serving.admit", "serving.pack", "serving.launch",
          "serving.fetch_logits", "serving.commit")
MARKERS = ("serving.admit_request", "serving.first_token",
           "serving.step_counts")
STEP_COUNTS = ("step", "admitted", "queued", "free_pages", "rows", "rows_cap",
               "decode_rows", "prefill_rows", "slots", "prefill_backlog",
               "attn_row_ctx", "kv_ctx_tokens", "attn_kv_tokens_read",
               "gathered", "produced", "finished", "ahead", "stale_rows",
               "launch")
PROMPT_LENS = (20, 9, 13, 30)
NEW_TOKENS = 5


@pytest.fixture(scope="module")
def tiny():
    import paddle_tpu as paddle

    state = paddle.get_rng_state()
    paddle.seed(20240924)
    cfg = LlamaConfig.debug(vocab=64, hidden=32, layers=2, heads=4,
                            kv_heads=2, inter=64, max_pos=128)
    model = LlamaForCausalLM(cfg)
    params = {k: jnp.asarray(v) for k, v in model.functional_state().items()}
    paddle.set_rng_state(state)
    return cfg, params


def _engine(cfg, params, draft=False):
    kw = {}
    if draft:
        dcfg, dparams = self_draft_params(cfg, params, 1)
        kw = dict(draft_cfg=dcfg, draft_params=dparams, speculative_k=2)
    # two slots for four requests: the last two wait for a slot
    return ContinuousBatchingEngine(
        cfg, params, max_slots=2, num_pages=33, page_size=16, max_seq_len=128,
        prefill_token_budget=8, enable_prefix_cache=True, **kw)


def _serve(eng):
    rng = np.random.default_rng(0)
    rids = [eng.add_request(rng.integers(1, 64, n).astype(np.int32),
                            max_new_tokens=NEW_TOKENS) for n in PROMPT_LENS]
    return rids, {f.rid: f.tokens.tolist() for f in eng.run()}


def _program_spans(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of the ``serving.*`` events
    of the one trace under ``trace_dir``, in time order."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serving."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


@contextlib.contextmanager
def _trace(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module", params=["plain", "draft"])
def traced(request, tiny, tmp_path_factory):
    """One traced run of the tiny engine (with and without a draft
    model): its spans, its counters, what it served, and what the same
    engine serves with no trace running."""
    draft = request.param == "draft"
    _, untraced_tokens = _serve(_engine(*tiny, draft=draft))
    eng = _engine(*tiny, draft=draft)
    trace_dir = tmp_path_factory.mktemp(f"trace-{request.param}")
    with _trace(trace_dir):
        rids, tokens = _serve(eng)
        eng.step()                  # nothing left to do: the early return
    return {"draft": draft, "spans": _program_spans(trace_dir), "rids": rids,
            "tokens": tokens, "untraced_tokens": untraced_tokens,
            "stats": eng.serving_stats(), "rows_cap": eng.rows_cap,
            "ladder": eng.ladder}


def _named(traced, name):
    return [sp for sp in traced["spans"] if sp[0] == name]


@pytest.mark.parametrize("name", PHASES + ("serving.propose",) + MARKERS)
def test_every_span_is_in_the_trace_under_a_step(traced, name):
    spans = _named(traced, name)
    if name == "serving.propose" and not traced["draft"]:
        assert not spans            # only where a draft model proposes
        return
    assert spans, f"no {name} in the trace"
    steps = _named(traced, "serving.step")
    for _, a, b, _ in spans:
        assert any(s <= a and b <= e for _, s, e, _ in steps), (name, a, b)


def test_the_phases_cover_their_step(traced):
    """A step's time is its phases': what lies between them (building
    the list of decoding slots, the report, the marker) stays small."""
    working = {c["step"] for _, _, _, c in _named(traced, "serving.step_counts")
               if c["rows"]}
    step_ns = phase_ns = 0
    for _, s, e, arg in _named(traced, "serving.step"):
        if arg["step"] not in working:
            continue
        step_ns += e - s
        phase_ns += sum(b - a for name, a, b, _ in traced["spans"]
                        if name in PHASES + ("serving.propose",)
                        and s <= a and b <= e)
    assert phase_ns >= 0.9 * step_ns


def test_step_numbers_run_on_and_each_step_ends_with_its_counts(traced):
    steps = _named(traced, "serving.step")
    counts = _named(traced, "serving.step_counts")
    assert [a["step"] for *_, a in steps] == list(range(1, len(steps) + 1))
    assert [a["step"] for *_, a in counts] == [a["step"] for *_, a in steps]
    for (_, s, e, _), (_, a, b, c) in zip(steps, counts):
        assert s <= a and b <= e and set(c) == set(STEP_COUNTS)
        inside = [sp for sp in traced["spans"] if s <= sp[1] and sp[2] <= e]
        assert max(inside, key=lambda sp: sp[1])[0] == "serving.step_counts"


def test_every_call_that_commits_a_launch_writes_every_phase(traced):
    """The engine runs one step ahead and the readers still find what
    they read, in every call: the call that commits a launch (its
    marker has rows) has ``serving.admit``, ``serving.fetch_logits``
    and ``serving.commit``; it packs unless the host had to see the
    launch before (a draft model) and it was in flight already, which
    never happens here; and there is one ``serving.launch`` a launch
    (two in the call that fills the pipeline, none in the call that
    finds nothing left to launch)."""
    counts = {c["step"]: c for *_, c in _named(traced, "serving.step_counts")}
    launches = 0
    for _, s, e, arg in _named(traced, "serving.step"):
        inside = [sp[0] for sp in traced["spans"] if s <= sp[1] and sp[2] <= e]
        launches += inside.count("serving.launch")
        if counts[arg["step"]]["rows"]:
            for name in ("serving.admit", "serving.pack",
                         "serving.fetch_logits", "serving.commit"):
                assert name in inside, (arg["step"], name)
            assert inside.count("serving.fetch_logits") \
                == inside.count("serving.commit") == 1
    assert launches == sum(1 for c in counts.values() if c["rows"])
    ahead = [c["ahead"] for c in counts.values() if c["rows"]]
    assert not any(ahead) if traced["draft"] else sum(ahead) >= len(ahead) - 4


def test_a_calls_marker_carries_the_launch_it_commits(tiny, tmp_path):
    """Three calls by hand: one request, a prompt of 5 and 3 new tokens.
    Call 1 enqueues the prompt's launch (5 rows) AND the first decode
    row's, and commits the first; call 2 enqueues the second decode
    row and commits the first decode row; call 3 finds nothing to
    launch (the budget is spent by what is in flight) and commits the
    second.  Each call's marker, joined to its ``serving.step`` by
    ``step``, counts the launch the device ran while the host was
    inside that call, and its serial (``launch``), which the
    ``serving.launch`` span that enqueued it carries too."""
    cfg, params = tiny
    eng = ContinuousBatchingEngine(cfg, params, max_slots=1, num_pages=9,
                                   page_size=16, max_seq_len=32,
                                   prefill_token_budget=8)
    eng.add_request(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    with _trace(tmp_path):
        produced = [eng.step() for _ in range(3)]
    assert produced == [1, 1, 1] and not eng.active.any()
    spans = _program_spans(tmp_path)
    steps = [a["step"] for n, *_, a in spans if n == "serving.step"]
    marks = [a for n, *_, a in spans if n == "serving.step_counts"]
    assert [m["step"] for m in marks] == steps == [1, 2, 3]
    want = [dict(rows=5, prefill_rows=5, decode_rows=0, ahead=0, admitted=1,
                 attn_row_ctx=15, kv_ctx_tokens=5, gathered=1, finished=0,
                 launch=1),
            dict(rows=1, prefill_rows=0, decode_rows=1, ahead=1, admitted=0,
                 attn_row_ctx=6, kv_ctx_tokens=6, gathered=1, finished=0,
                 launch=2),
            dict(rows=1, prefill_rows=0, decode_rows=1, ahead=1, admitted=0,
                 attn_row_ctx=7, kv_ctx_tokens=7, gathered=1, finished=1,
                 launch=3)]
    for mark, w in zip(marks, want):
        assert {k: mark[k] for k in w} == w
        assert mark["produced"] == 1 and mark["stale_rows"] == 0
    launches = [[n for n, a, b, _ in spans if n in ("serving.pack",
                                                    "serving.launch")
                 and s <= a and b <= e]
                for name, s, e, _ in spans if name == "serving.step"]
    assert launches == [["serving.pack", "serving.launch"] * 2,
                        ["serving.pack", "serving.launch"],
                        ["serving.pack"]]
    # a launch is enqueued one call before the call that commits it
    enqueued = [[a["launch"] for n, a_, b, a in spans if n == "serving.launch"
                 and s <= a_ and b <= e]
                for name, s, e, _ in spans if name == "serving.step"]
    assert enqueued == [[1, 2], [3], []]
    eng.shutdown()


@pytest.mark.parametrize("key", ["rows", "slots", "gathered", "context",
                                 "walk", "backlog", "produced", "empty"])
def test_per_step_counts_hold_together(traced, key):
    counts = [c for *_, c in _named(traced, "serving.step_counts")]
    last = counts[-1]               # the step with nothing left to do
    if key == "rows":
        # ``rows_cap`` is the rows of the program launched: the
        # smallest rung of the engine's ladder that holds the step's
        for c in counts:
            assert c["rows"] == c["decode_rows"] + c["prefill_rows"] \
                <= c["rows_cap"] <= traced["rows_cap"]
            assert c["rows_cap"] == min(n for n in traced["ladder"]
                                        if n >= c["rows"])
        assert any(c["decode_rows"] and c["prefill_rows"] for c in counts)
    elif key == "slots":
        assert all(c["slots"] <= 2 and (c["slots"] > 0) == (c["rows"] > 0)
                   for c in counts)
    elif key == "gathered":
        # every verify-window row and one row per prefill chunk
        assert all(c["decode_rows"] <= c["gathered"]
                   <= c["decode_rows"] + c["slots"] for c in counts)
    elif key == "context":
        # a slot's context is read once, each of its rows attends to it
        assert all(c["kv_ctx_tokens"] <= c["attn_row_ctx"]
                   <= c["kv_ctx_tokens"] * max(c["rows"], 1) for c in counts)
        assert all(c["kv_ctx_tokens"] >= c["rows"] for c in counts)
    elif key == "walk":
        # the kernel's walk fetches whole pages of 16, every scheduled
        # slot's at least once and (one tile holds this engine's rows,
        # a slot's rows are one run) here exactly once
        for c in counts:
            assert c["kv_ctx_tokens"] <= c["attn_kv_tokens_read"] \
                < c["kv_ctx_tokens"] + 16 * max(c["slots"], 1)
            assert c["attn_kv_tokens_read"] % 16 == 0
    elif key == "backlog":
        # prompt tokens admitted and not yet prefilled: ends at nothing
        assert counts[0]["prefill_backlog"] > 0
        assert last["prefill_backlog"] == 0 and last["queued"] == 0
    elif key == "produced":
        assert sum(c["produced"] for c in counts) \
            == NEW_TOKENS * len(PROMPT_LENS)
        assert sum(c["finished"] for c in counts) == len(PROMPT_LENS)
    else:
        assert last["rows"] == last["slots"] == last["produced"] == 0
        assert last["rows_cap"] == traced["ladder"][0]
        assert last["free_pages"] >= counts[0]["free_pages"]


@pytest.mark.parametrize("key", ["steps", "rows", "rows_cap", "decode_rows",
                                 "prefill_rows", "admitted", "kv_ctx_tokens",
                                 "attn_kv_tokens_read", "queue_wait_s",
                                 "prefill_s", "ahead", "stale_rows"])
def test_the_spans_arguments_add_up_to_serving_stats(traced, key):
    kept = traced["stats"]["steps"][key]
    counts = [c for *_, c in _named(traced, "serving.step_counts")]
    if key == "steps":
        assert kept == len(counts) == counts[-1]["step"]
    elif key == "queue_wait_s":
        us = [a["queue_wait_us"] for *_, a in _named(traced, "serving.admit_request")]
        assert kept == {"sum": sum(us) / 1e6, "max": max(us) / 1e6}
    elif key == "prefill_s":
        us = [a["prefill_us"] for *_, a in _named(traced, "serving.first_token")]
        assert kept == {"sum": sum(us) / 1e6, "max": max(us) / 1e6}
    else:
        assert kept == sum(c[key] for c in counts)


def test_the_walk_reads_what_the_packing_counts(tiny):
    """2 decode rows and one 20-row chunk over 3 pages of 8: the K/V
    positions the ragged kernel's walk fetches in a layer, by hand, and
    the same launch through the kernel's own units of work.  The engine
    runs one step ahead: the second call PACKS that launch (each slot's
    second decode row) and the third call COMMITS it, which is when its
    counts reach ``serving_stats()``."""
    from paddle_tpu.ops.pallas.decode_attention import (ragged_tile_rows,
                                                        ragged_units)

    cfg, params = tiny
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=3, num_pages=33, page_size=8, max_seq_len=64,
        prefill_token_budget=20)
    rng = np.random.default_rng(1)
    for n in (5, 9):
        eng.add_request(rng.integers(1, 64, n).astype(np.int32),
                        max_new_tokens=4)
    eng.step()                      # both prompts prefilled: 5 + 9 rows
    eng.add_request(rng.integers(1, 64, 20).astype(np.int32),
                    max_new_tokens=4)
    packed = {}
    pack = eng._pack_unified

    def spy(*a):
        out = pack(*a)
        packed.setdefault("rows", out[0])
        return out

    eng._pack_unified = spy
    eng.step()
    before = dict(eng.serving_stats()["steps"])
    eng.step()
    after = eng.serving_stats()["steps"]
    got = {k: after[k] - before[k] for k in
           ("rows", "decode_rows", "kv_ctx_tokens", "attn_kv_tokens_read")}
    # the decode rows see 7 and 11 positions (1 and 2 pages), the chunk's
    # last row 20 (3 pages): 8 + 16 + 24 fetched for 7 + 11 + 20 attended
    assert got == {"rows": 22, "decode_rows": 2, "kv_ctx_tokens": 38,
                   "attn_kv_tokens_read": 48}
    rows = packed["rows"]
    tile = ragged_tile_rows(cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
    count, reach = ragged_units(rows[:, 4], rows[:, 3], tile, np)
    assert count[count > 0].tolist() == [1, 1, 20]
    assert reach[count > 0].tolist() == [7, 11, 20]
    eng.run()
    eng.shutdown()


def test_each_request_is_stamped_once_at_admission_and_first_token(traced):
    admits = [a for *_, a in _named(traced, "serving.admit_request")]
    firsts = [a for *_, a in _named(traced, "serving.first_token")]
    assert sorted(a["rid"] for a in admits) == sorted(traced["rids"])
    assert sorted(a["rid"] for a in firsts) == sorted(traced["rids"])
    by_rid = {a["rid"]: a for a in admits}
    for rid, n in zip(traced["rids"], PROMPT_LENS):
        assert by_rid[rid]["prompt_len"] == n
        assert by_rid[rid]["cached_tokens"] == \
            traced["stats"]["prefill"][rid]["cached_tokens"]
    # the last two were submitted while both slots were taken: they
    # waited for a whole request to finish, the first two for nothing
    waits = [by_rid[rid]["queue_wait_us"] for rid in traced["rids"]]
    assert min(waits) >= 0 and min(waits[2:]) > 10 * max(waits[:2]) > 0
    for a in firsts:
        # 8 prompt tokens a step: a prompt of n takes ceil(n / 8) chunks
        # when it prefills alone, fewer tokens a chunk when it shares
        n = by_rid[a["rid"]]["prompt_len"] - by_rid[a["rid"]]["cached_tokens"]
        assert a["chunks"] >= -(-n // 8) and a["prefill_us"] > 0


def test_a_late_hit_leaves_one_marker_in_the_trace(tiny, tmp_path):
    """A prompt of six chunks and the same prompt behind it, in a real
    profiler trace: the second look-up's hit leaves ONE
    ``serving.late_hit`` marker, inside the ``serving.pack`` that packs
    the re-ask's first chunk, after the request's
    ``serving.admit_request``, with the tokens ``prefill_stats`` and the
    cache's counters show."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    prompt = np.random.default_rng(48).integers(1, 64, 50).astype(np.int32)
    with _trace(tmp_path):
        a = eng.add_request(prompt, max_new_tokens=2)
        b = eng.add_request(prompt, max_new_tokens=2)
        tokens = {f.rid: f.tokens.tolist() for f in eng.run()}
    assert tokens[a] == tokens[b]
    spans = _program_spans(tmp_path)
    (late,) = [sp for sp in spans if sp[0] == "serving.late_hit"]
    cached = eng.prefill_stats[b]["cached_tokens"]
    cache = eng.serving_stats()["prefix_cache"]
    assert (cache["late_hits"], cache["late_hit_tokens"]) == (1, cached)
    assert (late[3]["rid"], late[3]["cached_tokens"]) == (b, cached)
    assert cached >= 32 and late[3]["waited_us"] > 0
    assert any(n == "serving.pack" and s <= late[1] and late[2] <= e
               for n, s, e, _ in spans)
    (admit,) = [sp for sp in spans if sp[0] == "serving.admit_request"
                and sp[3]["rid"] == b]
    assert admit[3]["cached_tokens"] == 0 and admit[2] <= late[1]
    eng.shutdown()


def test_greedy_outputs_do_not_depend_on_a_trace_running(traced):
    assert traced["tokens"] == traced["untraced_tokens"]
    assert all(len(t) == NEW_TOKENS for t in traced["tokens"].values())


def test_no_span_is_recorded_without_a_trace_and_stats_are_always_on(tiny):
    eng = _engine(*tiny)
    before = len(profiler._host_events)
    _serve(eng)
    assert len(profiler._host_events) == before
    steps = eng.serving_stats()["steps"]
    assert steps["steps"] > 0 and steps["admitted"] == len(PROMPT_LENS)
    assert 0 < steps["rows"] < steps["rows_cap"]
    assert steps["queue_wait_s"]["max"] <= steps["queue_wait_s"]["sum"]


def test_profiler_records_the_engines_spans_with_their_nesting(tiny, tmp_path):
    """``Profiler`` + ``RecordEvent`` is the same one system: the
    engine's spans land in ``summary()`` and the chrome trace, each with
    its arguments and the span that enclosed it."""
    eng = _engine(*tiny)
    with profiler.Profiler(timer_only=True) as prof:
        with profiler.RecordEvent("serve_all", "UserDefined", requests=4):
            _serve(eng)
    events = {e["name"]: e for e in profiler._host_events}
    assert events["serve_all"]["args"] == {"requests": 4}
    assert events["serving.step"]["args"]["parent"] == "serve_all"
    assert events["serving.pack"]["args"] == {"parent": "serving.step"}
    assert events["serving.admit_request"]["args"]["parent"] == "serving.admit"
    assert events["serving.first_token"]["args"]["parent"] == "serving.commit"
    assert events["serving.step_counts"]["args"]["rows_cap"] in eng.ladder
    assert eng.ladder[-1] == eng.rows_cap
    table = prof.summary(top_n=40)
    rows = {tuple(ln.split()[i] for i in (0, -1)) for ln in table.splitlines()
            if ln.startswith(("serv", "serve_all"))}
    assert {("serve_all", "-"), ("serving.step", "serve_all"),
            ("serving.fetch_logits", "serving.step"),
            ("serving.first_token", "serving.commit")} <= rows
    path = tmp_path / "trace.json"
    prof.export_chrome_tracing(str(path))
    assert ("serving.launch", "serving.step") in {
        tuple(ln.split()[i] for i in (0, -1))
        for ln in profiler.summarize_chrome_trace(str(path), top_n=40).splitlines()
        if ln.startswith("serving.")}


def test_a_span_left_open_when_recording_stops_is_dropped():
    with profiler.Profiler(timer_only=True):
        span = profiler.RecordEvent("left_open")
        span.begin()
        with profiler.RecordEvent("inside"):
            pass
    span.end()
    names = [e["name"] for e in profiler._host_events]
    assert names == ["inside"]
    assert profiler._host_events[0]["args"] == {"parent": "left_open"}
    with profiler.Profiler(timer_only=True):
        with profiler.RecordEvent("after"):
            pass
    assert "args" not in profiler._host_events[0]     # no stale parent


def test_the_jitted_step_is_called_from_the_step_itself(tiny, monkeypatch):
    """JAX writes the Python call stack into every operation's location:
    one helper frame between ``_step_unified`` and the jitted call cost
    0.6 s of lowering at 16 layers on the chip (PERF.md, PR 24; PR 36's
    probe found 8 or 20 frames more inside the noise of a trace, 1.3 to
    3.2 s, and keeps the call where it is for every rung all the same)."""
    import sys

    from paddle_tpu.models import llama_paged

    real = llama_paged.unified_step_jit
    callers = []

    def spy(*args, **kw):
        callers.append(sys._getframe(1).f_code.co_name)
        return real(*args, **kw)

    monkeypatch.setattr(llama_paged, "unified_step_jit", spy)
    eng = _engine(*tiny)
    _serve(eng)
    # every rung of the ladder is launched once before the first launch
    # (over padding rows: its compile), from the same frame
    assert len(callers) == len(eng._padding_launches()) \
        + sum(eng.launches_by_rows.values())
    assert callers and set(callers) == {"_step_unified"}


def _optimized_hlo(eng):
    """The optimized HLO of the engine's unified step with everything
    that names a source or a scope taken out: the tables of files and
    stack frames at its head and each instruction's ``metadata=``."""
    from paddle_tpu.models.llama_paged import unified_step_jit

    raw = unified_step_jit.__wrapped__
    # a function of its own each time, or jit hands back the cached trace
    fn = jax.jit(lambda *a, **k: raw(*a, **k),
                 static_argnames=("self_cfg_id", "pages_per_step", "with_head"))
    text = fn.lower(
        eng.params, eng.k_pages, eng.v_pages,
        jnp.zeros((eng.rows_cap, 5), jnp.int32), (jnp.asarray(eng.tables),),
        eng.cos_tab, eng.sin_tab, self_cfg_id=eng.cfg_id,
        pages_per_step=eng.pages_per_step,
        gather=jnp.zeros(eng.gather_cap, jnp.int32)).compile().as_text()
    scoped = "attn_qkv" in text and "lm_head" in text
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    if "\nStackFrames" in text:
        text = text[text.index("\n\n", text.index("\nStackFrames")):]
    return text, scoped


def test_named_scopes_change_nothing_that_is_compiled(tiny, monkeypatch):
    eng = _engine(*tiny)
    with_scopes, scoped = _optimized_hlo(eng)
    assert scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without, scoped = _optimized_hlo(eng)
    assert not scoped
    assert with_scopes == without


# ---- a layout of two kinds of page (window and full layers mixed) -------

@pytest.fixture(scope="module")
def traced_kinds(tmp_path_factory):
    """One traced run of a small Mellum2-shaped engine: two kinds of
    page, an expert layer in every layer."""
    from paddle_tpu.models.mellum2 import Mellum2Config

    cfg = Mellum2Config.debug()
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(
        1.0 + 0.1 * rng.normal(size=s) if len(s) == 1
        else 0.2 * rng.normal(size=s), jnp.float32)
        for k, s in cfg.leaf_shapes().items()}
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=2, num_pages={"full": 33, "window": 13},
        page_size=4, max_seq_len=64, prefill_token_budget=6,
        enable_prefix_cache=True)
    trace_dir = tmp_path_factory.mktemp("trace-kinds")
    with _trace(trace_dir):
        for n in (5, 30, 17):
            eng.add_request(rng.integers(1, 96, n).astype(np.int32),
                            max_new_tokens=5)
        eng.run()
        eng.step()
    stats = eng.serving_stats()
    eng.shutdown()
    return {"spans": _program_spans(trace_dir), "stats": stats}


@pytest.mark.parametrize("key", [
    "attn_row_ctx_window", "kv_ctx_tokens_window", "window_pages_live",
    "window_pages_recycled", "moe_rows_routed", "moe_rows_held",
    "moe_experts_hit", "moe_experts_total", "moe_expert_rows_max"])
def test_two_kinds_counts_add_up_to_serving_stats(traced_kinds, key):
    kept = traced_kinds["stats"]["steps"][key]
    counts = [c for *_, c in _named(traced_kinds, "serving.step_counts")]
    if key.endswith("_max"):
        assert kept == max(c.get(key, 0) for c in counts) > 0
    else:
        assert kept == sum(c.get(key, 0) for c in counts) > 0


def test_two_kinds_counts_hold_together(traced_kinds):
    counts = [c for *_, c in _named(traced_kinds, "serving.step_counts")]
    busy = [c for c in counts if c["rows"]]
    for c in busy:
        # a window layer's least work is capped at the window (8)
        assert c["attn_row_ctx_window"] <= min(c["attn_row_ctx"],
                                               8 * c["rows"])
        assert c["kv_ctx_tokens_window"] <= min(c["kv_ctx_tokens"],
                                                8 * c["slots"])
        # 4 expert layers of 8 experts, 2 copies a row and layer
        assert c["moe_experts_total"] == 32
        assert c["moe_rows_routed"] == c["moe_rows_held"] == 8 * c["rows"]
        assert 0 < c["moe_experts_hit"] <= min(32, c["moe_rows_routed"])
        assert c["moe_expert_rows_max"] <= c["rows"]
    # both kinds' free pages ride on the marker; at the end all are back
    assert all("free_pages_window" in c and "free_pages" in c
               for c in counts)
    # (what is not free at the end the prefix cache holds)
    assert 0 < counts[-1]["free_pages_window"] <= 12
    assert counts[-1]["window_pages_live"] == 0
    assert any(c["window_pages_recycled"] for c in counts)
    assert any(c["attn_row_ctx_window"] < c["attn_row_ctx"] for c in busy)


# ---- a layout with a recurrent state beside its pages -------------------

@pytest.fixture(scope="module")
def traced_state(tmp_path_factory):
    """One traced run of a small Nemotron-H-shaped engine: a state entry
    a slot, snapshots in the prefix cache, two requests that restore."""
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    cfg = NemotronHConfig.debug(experts_held=(4, 12))
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(
        np.ones(s) if k.endswith(".D") else np.log(rng.uniform(1, 16, s))
        if k.endswith("A_log") else 1.0 + 0.1 * rng.normal(size=s)
        if len(s) == 1 else 0.3 * rng.normal(size=s), jnp.float32)
        for k, s in cfg.leaf_shapes().items()}
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=2, num_pages=40, page_size=4, max_seq_len=64,
        prefill_token_budget=8, enable_prefix_cache=True, state_snapshots=2)
    pre = rng.integers(1, 96, 17)
    tasks = [rng.integers(1, 96, n) for n in (5, 9)]
    trace_dir = tmp_path_factory.mktemp("trace-state")
    with _trace(trace_dir):
        for task in (*tasks, tasks[0]):
            eng.add_request(np.concatenate([pre, task]).astype(np.int32),
                            max_new_tokens=4)
            eng.run()
        eng.step()
    stats = eng.serving_stats()
    eng.assert_balanced()
    eng.shutdown()
    return {"spans": _program_spans(trace_dir), "stats": stats}


@pytest.mark.parametrize("key", [
    "ssm_state_slots", "ssm_rows", "ssm_prefill_rows",
    "state_snapshots_live", "state_snapshots_taken",
    "state_snapshots_evicted", "state_restored_tokens",
    "moe_rows_routed", "moe_rows_held", "moe_experts_hit"])
def test_state_counts_add_up_to_serving_stats(traced_state, key):
    kept = traced_state["stats"]["steps"][key]
    counts = [c for *_, c in _named(traced_state, "serving.step_counts")]
    assert kept == sum(c.get(key, 0) for c in counts) > 0


def test_state_counts_hold_together(traced_state):
    counts = [c for *_, c in _named(traced_state, "serving.step_counts")]
    for c in (c for c in counts if c["rows"]):
        assert c["ssm_rows"] == c["rows"]
        assert c["ssm_state_slots"] == c["slots"]
        assert c["ssm_prefill_rows"] <= c["prefill_rows"]
        assert c["state_snapshots_live"] <= 2
        # 2 expert layers, 3 copies a row and layer, 8 of 16 experts held
        assert c["moe_rows_routed"] == 6 * c["rows"]
        assert c["moe_rows_held"] <= c["moe_rows_routed"]
        assert c["moe_experts_total"] == 16
    # the second and third requests restored the snapshot at 16 tokens;
    # the third matched 20 tokens of pages, 4 of them beyond it
    admits = [a for *_, a in _named(traced_state, "serving.admit_request")]
    assert [(a["cached_tokens"], a["state_restored_tokens"],
             a["state_lost_tokens"]) for a in admits] == [
        (0, 0, 0), (16, 16, 0), (16, 16, 4)]
    # where each request's state lives: its slot's own entry of the pools
    prefill = traced_state["stats"]["prefill"]
    assert [a["state_entry"] for a in admits] == [
        prefill[a["rid"]]["state_entry"] for a in admits]
    assert all(0 <= a["state_entry"] < 2 for a in admits)
    steps = traced_state["stats"]["steps"]
    assert steps["state_lost_tokens"] == 4
    assert steps["state_restored_tokens"] == 32
    # the first prompt's chunks end at 8 and 16, the second's at 24: three
    # snapshots for two entries, so the least recently restored-from went
    pc = traced_state["stats"]["prefix_cache"]
    assert pc["snapshots_taken"] == steps["state_snapshots_taken"] == 3
    assert pc["snapshots_evicted"] == steps["state_snapshots_evicted"] == 1
    assert pc["snapshots_live"] == 2


# ---- a layout with three pools a page and a recurrent state -------------

@pytest.fixture(scope="module")
def traced_sala(tmp_path_factory):
    """One traced run of a small MiniCPM-SALA-shaped engine: a session
    history past ``dense_len`` in the prefix cache, two turns that
    restore its snapshot and select blocks from its compressed keys."""
    from paddle_tpu.models.minicpm_sala import MiniCPMSALAConfig

    cfg = MiniCPMSALAConfig.debug()
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(1.0 + 0.1 * rng.normal(size=s) if len(s) == 1
                             else 0.3 * rng.normal(size=s), jnp.float32)
              for k, s in cfg.leaf_shapes().items()}
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=2, num_pages=40, page_size=8, max_seq_len=96,
        prefill_token_budget=8, enable_prefix_cache=True, state_snapshots=3)
    history = rng.integers(1, 96, 48)
    turns = [rng.integers(1, 96, n) for n in (5, 11)]
    trace_dir = tmp_path_factory.mktemp("trace-sala")
    with _trace(trace_dir):
        for turn in ([], *turns):
            eng.add_request(np.concatenate([history, turn]).astype(np.int32),
                            max_new_tokens=4)
            eng.run()
        eng.step()
    stats = eng.serving_stats()
    eng.assert_balanced()
    eng.shutdown()
    return {"spans": _program_spans(trace_dir), "stats": stats}


@pytest.mark.parametrize("key", [
    "sparse_rows", "dense_rows", "ckey_ctx", "ckey_slot_ctx", "sel_blocks",
    "sel_kv_tokens", "sel_kv_tokens_read", "state_rows", "state_slots",
    "state_snapshots_taken", "state_restored_tokens"])
def test_sala_counts_add_up_to_serving_stats(traced_sala, key):
    kept = traced_sala["stats"]["steps"][key]
    counts = [c for *_, c in _named(traced_sala, "serving.step_counts")]
    assert kept == sum(c.get(key, 0) for c in counts) > 0


def test_sala_counts_hold_together(traced_sala):
    counts = [c for *_, c in _named(traced_sala, "serving.step_counts")]
    for c in (c for c in counts if c["rows"]):
        assert c["sparse_rows"] + c["dense_rows"] == c["state_rows"] \
            == c["rows"]
        assert c["state_slots"] == c["slots"]
        # 2 K/V groups, 4 blocks of 4 tokens a selecting row and group,
        # each fetched once
        assert c["sel_blocks"] == 8 * c["sparse_rows"]
        assert c["sel_kv_tokens_read"] == c["sel_kv_tokens"] \
            == 4 * c["sel_blocks"]
        # a slot's compressed keys are scored by each of its rows
        assert c["ckey_slot_ctx"] <= c["ckey_ctx"]
        assert (c["ckey_ctx"] > 0) == (c["sparse_rows"] > 0)
    # the first 32 tokens of the history attend densely, the rest select
    assert sum(c["dense_rows"] for c in counts) == 32
    # both turns restored the snapshot at the history's end, 48 tokens
    admits = [a for *_, a in _named(traced_sala, "serving.admit_request")]
    assert [(a["cached_tokens"], a["state_restored_tokens"],
             a["state_lost_tokens"]) for a in admits] == [
        (0, 0, 0), (48, 48, 0), (48, 48, 0)]
