"""Device time by the program's own scopes (``profiler/device_trace.py``):
the loader of ``.xplane.pb`` on recorded files, the reduction on
synthetic event lists, the COVERAGE of the four serving steps (every
working instruction of a compiled step lies under a scope of
``DEVICE_SCOPES``), and the benchmark's readers of it off the chip.
"""

import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.profiler import device_trace as dt

ROOT = pathlib.Path(__file__).resolve().parents[1]
RECORDED = ROOT / "benchmarks" / "tests" / "recorded_v5e.xplane.pb"
# two recordings of a small engine on the chip (the planes and lines that
# ``device_trace`` reads, every kept byte as recorded): two layers, three
# requests, nine launches of one rung, two of which read BEFORE the span
# that enqueued them; one layer, two requests, four launches of two rungs
TINY = ROOT / "tests" / "data" / "tiny_engine_v5e.xplane.pb"
TINY_RUNGS = ROOT / "tests" / "data" / "tiny_engine_rungs_v5e.xplane.pb"
PLANE = "/device:TPU:0"


# ---- (a) the loader ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return dt.load_xplane(RECORDED, ("traced_window", "train."))


@pytest.mark.parametrize("name, want", [
    ("convolution_reduce_fusion",
     dict(tf_op="jit(<lambda>)/dot_general:",
          hlo_category="convolution fusion", flops=17184063488,
          bytes_accessed=16777218)),
    ("copy-start", dict(tf_op="", hlo_category="copy-start", flops=0)),
    ("copy-done", dict(tf_op="", hlo_category="copy-done", flops=0)),
])
def test_the_loader_reads_an_operations_metadata(recorded, name, want):
    ops = [op for op in recorded.ops[PLANE] if op.name == name]
    assert len(ops) == 5                # five launches were recorded
    for op in ops:
        assert {k: getattr(op, k) for k in want} == want
        assert op.program_id == 14530554794882571194 and op.duration_ns > 0


def test_the_loader_agrees_with_profile_data_on_names_and_times(recorded):
    """The same events as ``jax.profiler.ProfileData`` hands out (which
    rounds to whole nanoseconds), under the names the benchmark's
    ``trace_reduce.op_name`` prints."""
    from benchmarks.harness import trace_reduce

    theirs = trace_reduce.load_xplane(RECORDED, ["traced_window"])
    mine = recorded.ops[PLANE]
    assert [op.name for op in mine] \
        == [name for name, _, _ in theirs["devices"][PLANE]]
    for op, (_, start, dur) in zip(mine, theirs["devices"][PLANE]):
        assert abs(op.start_ns - start) < 1 and abs(op.duration_ns - dur) < 1
    (window,) = [sp for sp in recorded.spans if sp[0] == "traced_window"]
    assert [window[1:3]] == [e[1:] for e in theirs["host"]]


def test_the_loader_reads_the_launches(recorded):
    mods = recorded.modules[PLANE]
    assert len(mods) == 5
    assert {m.name for m in mods} == {"jit__lambda(14530554794882571194)"}
    assert {m.program_id for m in mods} == {14530554794882571194}
    ops = recorded.ops[PLANE]
    for m, last in zip(mods, ops[2::3]):     # a launch holds its three ops
        assert m.start_ns <= last.start_ns
        assert last.start_ns + last.duration_ns <= m.start_ns + m.duration_ns


def test_the_proto_is_read_without_a_proto_library():
    """Reading a trace imports neither tensorflow nor protobuf, and
    importing the package and the serving engine does not import the
    reader."""
    code = (
        "import sys\n"
        "import paddle_tpu, paddle_tpu.inference.serving\n"
        "assert 'paddle_tpu.profiler.device_trace' not in sys.modules\n"
        "from paddle_tpu.profiler import device_trace\n"
        f"device_trace.load_xplane({str(RECORDED)!r})\n"
        "bad = [m for m in sys.modules if m.split('.')[0] == 'tensorflow'\n"
        "       or m.startswith('google.protobuf')]\n"
        "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


# ---- (b) the reduction on synthetic events -------------------------------

P, Q = 11, 22           # a step program and another program


def _op(name, start, dur, path="", program=P, flops=0, nbytes=0):
    return dt.DeviceOp(name, float(start), float(dur),
                       f"jit(step)/{path}:" if path else "", "", flops,
                       nbytes, program)


def _mod(start, dur, program=P):
    return dt.Module(f"jit_step({program})", float(start), float(dur), program)


@pytest.mark.parametrize("path, want", [
    ("attn_qkv/dot_general", "attn_qkv"),
    ("mlp/moe_experts/scatter", "moe_experts"),
    ("mlp/moe_route/jit(argsort)/sort", "moe_route"),
    ("mlp/add", "mlp"),
    ("jit(call)/jit(step)/lm_head/transpose(jvp(mlp))/dot", "lm_head"),
    ("convert_element_type", dt.UNSCOPED),
    ("mlpx/add", dt.UNSCOPED),
])
def test_an_operations_scope_is_the_innermost_in_the_set(path, want):
    assert dt.scope_of(f"jit(step)/{path}:") == want


@pytest.mark.parametrize("tf_op", ["", "params['model.norm.weight']", "rows:"])
def test_an_operation_without_a_path_is_the_compilers(tf_op):
    assert dt.scope_of(tf_op) == dt.COMPILER


def test_nested_bodies_are_not_counted_twice_and_parts_add_up():
    """A ``while`` of 60 holds two body operations of 20; a launch's
    parts, with ``unscoped`` and ``compiler``, are its busy time."""
    ops = [
        _op("fusion", 100, 30, "attn_qkv/dot_general", flops=7, nbytes=5),
        _op("while", 140, 60, "mlp/moe_route/while"),
        _op("fusion", 145, 20, "mlp/moe_route/while/body/add"),
        _op("sort", 170, 20, "mlp/moe_experts/while/body/sort"),
        _op("kernel (pallas)", 210, 40, "paged_attn/pallas_call"),
        _op("copy-done", 250, 5),
        _op("fusion", 260, 10, "convert_element_type"),
    ]
    times = dt.device_time_by_scope(ops, [_mod(90, 200)])
    assert times.ns == {
        ("attn_qkv", "xla"): 30, ("moe_route", "xla"): 20 + 20,
        ("moe_experts", "xla"): 20, ("paged_attn", "pallas"): 40,
        (dt.COMPILER, "xla"): 5, (dt.UNSCOPED, "xla"): 10}
    assert sum(times.ns.values()) == times.busy_ns == 145
    (launch,) = times.launches
    assert launch.busy_ns == 145 and launch.by_scope == times.ns
    assert times.flops[("attn_qkv", "xla")] == 7
    assert times.bytes_accessed[("attn_qkv", "xla")] == 5
    assert times.scope_ns(["moe_route", "moe_experts"]) == 60
    assert times.scope_ns(["paged_attn", "attn_qkv"], "pallas") == 40
    assert times.scope_ns(["paged_attn", "attn_qkv"], "xla") == 30
    assert "attn_qkv" in dt.scope_table(times)


def test_only_the_steps_programs_are_launches():
    """A program none of whose operations carries a scope (an upload's
    copy, another jit) is no launch, and its operations count nowhere."""
    ops = [_op("fusion", 10, 5, "lm_head/dot_general"),
           _op("fusion", 30, 5, "add", program=Q),
           _op("fusion", 50, 5, "lm_head/dot_general")]
    times = dt.device_time_by_scope(
        ops, [_mod(8, 10), _mod(28, 10, Q), _mod(48, 10)])
    assert len(times.launches) == 2 and times.busy_ns == 10
    assert times.ns == {("lm_head", "xla"): 10}


MS = 1e6                # the joins' tests count in ms: the clocks' slack is 0.1


def _launch_spans(rows):
    """``serving.launch`` and ``serving.step_counts`` of an engine that
    runs one launch ahead: ``rows`` is ``[(serial, enqueued at,
    committed at, prefill_rows)]``, in ms."""
    spans = []
    for serial, at, done, prefill in rows:
        spans.append(("serving.launch", at * MS, 2 * MS, {"launch": serial}))
        if done is not None:
            spans.append(("serving.step_counts", done * MS, 0.0,
                          {"launch": serial, "prefill_rows": prefill}))
    return spans


def test_launches_join_to_spans_and_markers_in_order():
    """The trace opens while launch 7 runs: its span is not in it.
    Launch 8 was enqueued at 95, before the trace, and starts at 100
    when 7 ends; 9 is enqueued at 104 and starts at 120.  The window
    cuts launch 7 at its head and launch 11 at its tail."""
    mods = [_mod(t * MS, 20 * MS) for t in (80, 100, 120, 140, 160)]
    ops = [_op("fusion", m.start_ns + MS, 18 * MS, "mlp/dot") for m in mods]
    spans = _launch_spans([(9, 104, 141, 0), (10, 124, 161, 64),
                           (11, 144, None, 0)])
    spans.append(("serving.step_counts", 121 * MS, 0.0,
                  {"launch": 8, "prefill_rows": 0}))
    times = dt.device_time_by_scope(ops, mods, (90 * MS, 170 * MS), spans)
    assert [la.serial for la in times.launches] == [8, 9, 10]
    assert [la.counts["prefill_rows"] for la in times.launches] == [0, 0, 64]
    assert times.cut == 2 and times.busy_ns == 3 * 18 * MS


def test_a_launch_the_engine_compiled_before_its_first_is_not_joined():
    """The engine's first ``serving.launch`` runs every rung once over
    padding rows before launch 1: the device's launches under that span
    count back from it and find no marker."""
    mods = [_mod(t * MS, 5 * MS) for t in (10, 20, 30, 40)]
    ops = [_op("fusion", m.start_ns + MS, 3 * MS, "mlp/dot") for m in mods]
    spans = _launch_spans([(1, 5, 50, 5), (2, 36, 60, 0)])
    times = dt.device_time_by_scope(ops, mods, None, spans)
    assert [la.serial for la in times.launches] == [-1, 0, 1, 2]
    assert [la.counts is not None for la in times.launches] \
        == [False, False, True, True]


def test_a_launch_may_read_a_little_before_the_span_that_enqueued_it():
    """The two clocks agree to tens of microseconds: an idle device's
    launch read 25 us before its ``serving.launch`` opened on the chip
    (the recorded trace below holds two such)."""
    mods = [_mod(t * MS, 0.1 * MS) for t in (10.2, 12.1, 13.975, 16.05)]
    ops = [_op("fusion", m.start_ns, 0.1 * MS, "mlp/dot") for m in mods]
    spans = _launch_spans([(4, 10, 12.9, 8), (5, 12, 14.9, 0),
                           (6, 14, 16.9, 0), (7, 16, 18.9, 0)])
    times = dt.device_time_by_scope(ops, mods, None, spans)
    assert [la.serial for la in times.launches] == [4, 5, 6, 7]


def test_without_a_serial_nothing_is_joined_and_scopes_still_add_up():
    """The parent's trace: spans without ``launch``."""
    mods = [_mod(10, 5)]
    ops = [_op("fusion", 11, 3, "mlp/dot")]
    spans = [("serving.launch", 5.0, 2.0, {}),
             ("serving.step_counts", 20.0, 0.0, {"prefill_rows": 0})]
    times = dt.device_time_by_scope(ops, mods, None, spans)
    (launch,) = times.launches
    assert launch.serial is None and launch.counts is None
    assert times.ns == {("mlp", "xla"): 3}


def test_a_window_without_a_whole_launch_reduces_to_nothing():
    times = dt.device_time_by_scope(
        [_op("fusion", 11, 3, "mlp/dot")], [_mod(10, 5)], (12.0, 100.0))
    assert not times.launches and not times.ns and times.cut == 1
    assert dt.device_time_by_scope([], [], (0.0, 1.0)).launches == []


# ---- the trace of a small engine, recorded on the chip -------------------

@pytest.fixture(scope="module", params=[(TINY, 9, 2, 1), (TINY_RUNGS, 4, 0, 2)],
                ids=["one_rung", "two_rungs"])
def tiny(request):
    """``(trace, its reduction, launches, those that read before their
    span, rungs launched)``."""
    path, *want = request.param
    trace = dt.load_xplane(path)
    return (trace, dt.device_time_by_scope(
        trace.ops[PLANE], trace.modules[PLANE], spans=trace.spans), *want)


def test_a_recorded_steps_operations_carry_the_scopes(tiny):
    """The chip's own trace of a small engine (the compiled step called
    through its kept lowering): fusions carry ``.../attn_qkv/...``
    paths, the kernel is under ``paged_attn``, and what no scope names
    is small."""
    trace, times, *_ = tiny
    scopes = {dt.scope_of(op.tf_op) for op in trace.ops[PLANE]}
    assert {"embed", "attn_qkv", "kv_scatter", "paged_attn", "attn_out",
            "mlp", "lm_head", "sample"} <= scopes
    assert any("/attn_qkv/" in op.tf_op and "fusion" in op.name
               for op in trace.ops[PLANE])
    assert {scope for (scope, kind) in times.ns if kind == "pallas"} \
        == {"paged_attn"}           # beside the walk's gathers in XLA
    assert times.scope_ns([dt.UNSCOPED]) <= 0.02 * times.busy_ns
    assert abs(sum(times.ns.values()) - times.busy_ns) <= 1e-6 * times.busy_ns


def test_a_recorded_steps_launches_join_their_markers(tiny):
    """Every launch of the recorded run has its marker, serials run on,
    a launch starts after the span that enqueued it opened (two of them
    read 1.4 and 25 us BEFORE it: the clocks' slack) and ends before the
    marker that commits it is written, and the marker's rung tells the
    program."""
    trace, times, launches, early_want, rungs = tiny
    opened = {sp[3]["launch"]: sp[1] for sp in trace.spans
              if sp[0] == dt.LAUNCH_SPAN}
    marked = {sp[3]["launch"]: sp[1] for sp in trace.spans
              if sp[0] == dt.COUNTS_SPAN and sp[3]["launch"]}
    serials = [la.serial for la in times.launches]
    assert serials == list(range(serials[0], serials[0] + len(serials)))
    assert set(serials) == set(opened) == set(marked)
    program_of, early = {}, 0
    for la in times.launches:
        assert opened[la.serial] - dt.CLOCK_SLACK_NS <= la.start_ns
        early += la.start_ns < opened[la.serial]
        assert la.start_ns + la.duration_ns <= marked[la.serial]
        assert la.counts["rows"] <= la.counts["rows_cap"]
        assert program_of.setdefault(la.counts["rows_cap"], la.program_id) \
            == la.program_id
    assert (len(serials), early, len(program_of)) \
        == (launches, early_want, rungs)
    assert {la.counts["prefill_rows"] > 0 for la in times.launches} \
        == {True, False}            # chunk launches and decode launches


def test_the_profilers_summary_appends_the_devices_time_by_scope(tmp_path):
    """The operator's view: after a recording on a TPU ``summary()``
    reads the trace it wrote (here: the recorded one, put where
    ``jax.profiler`` writes)."""
    from paddle_tpu import profiler

    profile = tmp_path / "plugins" / "profile" / "t"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(TINY.read_bytes())
    prof = profiler.Profiler()
    assert "Scope" not in prof.summary()        # nothing was recorded
    prof.trace_dir = str(tmp_path)
    table = prof.summary()
    assert PLANE in table and "9 launches" in table
    (row,) = [ln.split() for ln in table.splitlines()
              if ln.split()[:2] == ["paged_attn", "pallas"]]
    assert row[2] == "9" and 0 < float(row[3]) < 0.1    # ms a launch


# ---- (c) coverage: no working instruction outside the scopes -------------

def _draw(cfg):
    rng = np.random.default_rng(0)
    return {k: jnp.asarray(1.0 + 0.1 * rng.normal(size=s) if len(s) == 1
                           else 0.2 * rng.normal(size=s), jnp.float32)
            for k, s in cfg.leaf_shapes().items()}


def _llama():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    state = paddle.get_rng_state()
    cfg = LlamaConfig.debug(vocab=64, hidden=32, layers=2, heads=4,
                            kv_heads=2, inter=64, max_pos=128)
    model = LlamaForCausalLM(cfg)
    paddle.set_rng_state(state)
    params = {k: jnp.asarray(v) for k, v in model.functional_state().items()}
    return ContinuousBatchingEngine(
        cfg, params, max_slots=2, num_pages=33, page_size=16, max_seq_len=128,
        prefill_token_budget=8)


def _mellum2():
    from paddle_tpu.models.mellum2 import Mellum2Config

    cfg = Mellum2Config.debug()
    return ContinuousBatchingEngine(
        cfg, _draw(cfg), max_slots=2, num_pages={"full": 33, "window": 13},
        page_size=4, max_seq_len=64, prefill_token_budget=6)


def _deepseek():
    from paddle_tpu.models.deepseek_v32 import DeepseekV32Config

    cfg = DeepseekV32Config.debug(experts_held=(4, 8))
    return ContinuousBatchingEngine(
        cfg, _draw(cfg), max_slots=3, num_pages=40, page_size=8,
        max_seq_len=64, prefill_token_budget=6)


def _nemotron():
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    cfg = NemotronHConfig.debug(experts_held=(4, 12))
    return ContinuousBatchingEngine(
        cfg, _draw(cfg), max_slots=2, num_pages=40, page_size=4,
        max_seq_len=64, prefill_token_budget=8, enable_prefix_cache=True,
        state_snapshots=2)


def _minicpm_sala():
    from paddle_tpu.models.minicpm_sala import MiniCPMSALAConfig

    cfg = MiniCPMSALAConfig.debug()
    return ContinuousBatchingEngine(
        cfg, _draw(cfg), max_slots=2, num_pages=40, page_size=8,
        max_seq_len=64, prefill_token_budget=8, enable_prefix_cache=True,
        state_snapshots=2)


def _kimi_linear():
    from paddle_tpu.models.kimi_linear import KimiLinearConfig

    cfg = KimiLinearConfig.debug(experts_held=(4, 12))
    return ContinuousBatchingEngine(
        cfg, _draw(cfg), max_slots=2, num_pages=40, page_size=4,
        max_seq_len=64, prefill_token_budget=8, enable_prefix_cache=True,
        state_snapshots=2)


WORKS = re.compile(r" (fusion|dot|convolution|custom-call|scatter|gather|sort"
                   r"|reduce|reduce-window|while|conditional|call)\(")


def hlo_scopes(text):
    """``{scope: [instruction, ...]}`` of the working instructions of an
    optimized HLO module's entry computation, by their ``op_name``."""
    found = {}
    entry = text[text.index("\nENTRY"):]
    for line in entry[:entry.index("\n}")].splitlines():
        if WORKS.search(line):
            path = re.search(r'op_name="([^"]*)"', line)
            found.setdefault(dt.scope_of(path.group(1) if path else ""),
                             []).append(line.strip()[:200])
    return found


@pytest.mark.parametrize("engine, must", [
    (_llama, {"embed", "attn_qkv", "kv_scatter", "paged_attn", "attn_out",
              "mlp", "lm_head", "sample"}),
    (_mellum2, {"embed", "attn_qkv", "kv_scatter", "paged_attn", "attn_out",
                "mlp", "moe_route", "moe_experts", "lm_head", "sample"}),
    (_deepseek, {"embed", "mla_qkv", "index_select", "sparse_attn",
                 "attn_out", "mlp", "moe_route", "moe_experts",
                 "shared_expert", "lm_head", "sample"}),
    (_nemotron, {"embed", "mamba_in_proj", "mamba_conv", "ssd_scan",
                 "mamba_out", "state_snapshot", "attn_qkv", "kv_scatter",
                 "paged_attn", "attn_out", "mlp", "moe_route", "moe_experts",
                 "shared_expert", "moe_latent_down", "moe_latent_up",
                 "lm_head", "sample"}),
    (_minicpm_sala, {"embed", "lightning_qkv", "ssd_scan", "lightning_out",
                     "state_snapshot", "attn_qkv", "kv_scatter", "ckey_write",
                     "paged_attn", "block_select", "sparse_attn", "attn_out",
                     "mlp", "lm_head", "sample"}),
    (_kimi_linear, {"embed", "kda_qkv", "kda_conv", "kda_scan", "kda_out",
                    "state_snapshot", "mla_qkv", "latent_attn", "attn_out",
                    "mlp", "moe_route", "moe_experts", "shared_expert",
                    "lm_head", "sample"}),
], ids=["llama", "mellum2", "deepseek_v32", "nemotron_h", "minicpm_sala",
        "kimi_linear"])
def test_every_working_instruction_of_a_step_is_under_a_scope(engine, must):
    """The compiled step at debug widths: every instruction of its entry
    computation that does work carries an ``op_name`` with a component
    in ``DEVICE_SCOPES``, so that a later change cannot add work that
    ``unscoped_device_share.serve`` would be the first to see.  What the
    compiler made itself carries no path (the CPU's ``wrapped_*``
    fusions, copies of an argument) and is ``compiler``."""
    eng = engine()
    fn, args, kwargs, _ = eng.analysis_entry()
    found = hlo_scopes(fn.lower(*args, **kwargs).compile().as_text())
    assert not found.get(dt.UNSCOPED)
    for line in found.get(dt.COMPILER, ()):
        assert re.match(r"(ROOT )?%?(wrapped_|copy|transpose_copy)", line), line
    assert must <= set(found) <= set(dt.DEVICE_SCOPES) | {dt.COMPILER}
    eng.shutdown()


def test_the_steps_open_no_scope_outside_the_set():
    """Every ``jax.named_scope`` of the serving steps' files is in
    ``DEVICE_SCOPES``, and every scope of the set is opened somewhere."""
    opened = set()
    for rel in ("inference/paged_layout.py", "models/generation.py",
                "models/llama_paged.py", "models/deepseek_v32.py",
                "models/nemotron_h.py", "models/minicpm_sala.py",
                "models/kimi_linear.py"):
        opened |= set(re.findall(r'jax\.named_scope\("(\w+)"\)',
                                 (ROOT / "paddle_tpu" / rel).read_text()))
    assert opened == set(dt.DEVICE_SCOPES)


# ---- (e) the benchmark's readers, where there is nothing to read ---------

def _reader(name):
    from benchmarks.harness import readers

    return readers.find_reader(ROOT, name)


READERS = [("scope_ms_per_launch", dict(scopes=["mlp"], ops="all")),
           ("scope_busy_share_pct", dict(scopes=["unscoped"])),
           ("launch_device_ms", dict(kind="decode")),
           ("launch_device_ms", dict(kind="chunk"))]


def _fake_trace(ops=(), modules=(), spans=()):
    return dt.XplaneTrace(ops={PLANE: list(ops)} if ops else {},
                          modules={PLANE: list(modules)}, spans=list(spans))


def _trace_file(root, data=b""):
    """A trace file where ``program_trace.newest_xplane(root)`` finds it."""
    profile = root / ".bench_out" / "trace-x" / "plugins" / "profile" / "t"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(data)


@pytest.mark.parametrize("reader, args", READERS)
@pytest.mark.parametrize("case", ["off_the_chip", "no_trace_file",
                                  "no_program_spans", "no_device_operation",
                                  "no_whole_launch", "a_file_cut_short"])
def test_a_reader_with_nothing_to_read_returns_none(reader, args, case,
                                                    tmp_path, monkeypatch):
    from benchmarks.readers import program_trace

    window = ("traced_window", 100.0, 900.0, {})
    marker = ("serving.step_counts", 500.0, 0.0,
              {"launch": 3, "prefill_rows": 0})
    fake = {
        "no_program_spans": _fake_trace(
            [_op("fusion", 200, 50, "mlp/dot")], [_mod(190, 100)], [window]),
        "no_device_operation": _fake_trace(spans=[window, marker]),
        "no_whole_launch": _fake_trace(
            [_op("fusion", 60, 50, "mlp/dot")], [_mod(50, 100)],
            [window, marker]),
    }.get(case)
    if fake is not None:
        _trace_file(tmp_path)
        monkeypatch.setattr(dt, "load_xplane", lambda *a, **k: fake)
    if case == "a_file_cut_short":
        _trace_file(tmp_path, RECORDED.read_bytes()[:12000])
    monkeypatch.setattr(program_trace, "ROOT", tmp_path)
    obs = {"trace": None if case == "off_the_chip" else {"idle_share": 0.5}}
    assert _reader(reader)(obs, **args) is None


@pytest.mark.parametrize("reader, args, want", [
    ("scope_ms_per_launch", dict(scopes=["mlp"], ops="all"), 50e-6),
    ("scope_ms_per_launch", dict(scopes=["mlp"], ops="pallas"), 0.0),
    ("scope_busy_share_pct", dict(scopes=["unscoped"]), 100 * 10 / 60),
    ("launch_device_ms", dict(kind="decode"), 60e-6),
    ("launch_device_ms", dict(kind="chunk"), None),
])
def test_a_reader_reads_the_windows_whole_launches(reader, args, want,
                                                   tmp_path, monkeypatch):
    from benchmarks.readers import program_trace

    fake = _fake_trace(
        [_op("fusion", 200, 50, "mlp/dot"), _op("fusion", 250, 10, "add")],
        [_mod(190, 100)],
        [("traced_window", 100.0, 900.0, {}),
         ("serving.launch", 150.0, 5.0, {"launch": 3}),
         ("serving.step_counts", 500.0, 0.0,
          {"launch": 3, "prefill_rows": 0})])
    _trace_file(tmp_path)
    monkeypatch.setattr(dt, "load_xplane", lambda *a, **k: fake)
    monkeypatch.setattr(program_trace, "ROOT", tmp_path)
    got = _reader(reader)({"trace": {"idle_share": 0.5}}, **args)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_readers_read_a_recorded_trace_without_program_spans(tmp_path,
                                                                 monkeypatch):
    """The benchmark's own recorded file: a window, device operations,
    no ``serving.*`` span (and no scope): every reader leaves its metric
    out."""
    from benchmarks.readers import device_scopes, program_trace

    _trace_file(tmp_path, RECORDED.read_bytes())
    monkeypatch.setattr(program_trace, "ROOT", tmp_path)
    obs = {"trace": {"idle_share": 0.5}}
    for reader, args in READERS:
        assert _reader(reader)(obs, **args) is None
    assert obs[device_scopes.KEY] is None
