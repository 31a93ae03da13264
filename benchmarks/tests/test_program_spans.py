"""The readers that read the program's own spans (``benchmarks/readers``):
each on a synthetic event list with stats, against hand counts; on a
trace of the tiny cell recorded here on the CPU, with a stand-in for
the device's reduction; ``None`` off the chip; and the metric files."""

import json
import pathlib

import pytest

from test_benchmarks import ROOT, _run, tiny_root  # noqa: F401 - a fixture

from benchmarks.harness import manifest, readers
from benchmarks.readers import program_trace

CELL = "mistral7b-serve-l16.chat"
NEW = {"host_pack_ms.serve", "host_commit_ms.serve", "idle_in_pack.serve",
       "idle_in_launch.serve", "idle_in_fetch.serve", "idle_in_commit.serve",
       "batch_occupancy.serve", "prefill_backlog.serve", "queue_wait_ms.serve",
       "paged_attn_ms.serve", "paged_attn_roofline.serve"}
KERNEL = "ragged_paged_attention (pallas)"
U = 1e6         # the synthetic trace counts in ms: 1 unit = 1e6 ns


def _step(n, start, end, phases, counts=None):
    """A ``serving.step`` with its phases and, last inside it, its
    counts marker."""
    out = [("serving.step", start * U, (end - start) * U, {"step": n})]
    out += [(f"serving.{name}", a * U, (b - a) * U, {})
            for name, a, b in phases]
    if counts is not None:
        out.append(("serving.step_counts", (end - 4) * U, U,
                    {"step": n, **counts}))
    return out


def synthetic():
    """A window of 1000 ms.  Step 1 began before it and step 4 ends
    after it; steps 2 and 3 lie inside.  The device is busy for 530 ms."""
    program = [
        *_step(1, -50, 150, [("fetch_logits", -20, 140), ("commit", 140, 148)],
               {"rows": 99, "rows_cap": 100, "prefill_backlog": 999,
                "attn_row_ctx": 10**9, "kv_ctx_tokens": 10**9}),
        *_step(2, 200, 500, [("admit", 201, 210), ("pack", 210, 250),
                             ("launch", 250, 300), ("fetch_logits", 300, 470),
                             ("commit", 470, 495)],
               {"rows": 30, "rows_cap": 100, "prefill_backlog": 40,
                "attn_row_ctx": 60_000, "kv_ctx_tokens": 27_500}),
        ("serving.admit_request", 203 * U, U, {"rid": 7, "queue_wait_us": 1500}),
        *_step(3, 520, 900, [("admit", 521, 525), ("pack", 530, 560),
                             ("launch", 560, 600), ("fetch_logits", 600, 885),
                             ("commit", 885, 895)],
               {"rows": 50, "rows_cap": 100, "prefill_backlog": 100,
                "attn_row_ctx": 100_000, "kv_ctx_tokens": 30_000}),
        ("serving.admit_request", 522 * U, U, {"rid": 8, "queue_wait_us": 500}),
        *_step(4, 950, 1100, [("pack", 955, 990), ("launch", 990, 1050)]),
    ]
    device = [("fusion", 0, 100 * U), (KERNEL, 310 * U, 90 * U),
              ("fusion", 400 * U, 60 * U), (KERNEL, 600 * U, 100 * U),
              ("fusion", 700 * U, 180 * U), (KERNEL, 1000 * U, 50 * U)]
    return {"devices": {"/device:TPU:0": device},
            "window": [("traced_window", 0.0, 1000 * U)], "program": program}


@pytest.fixture()
def obs():
    cfg = json.loads((ROOT / "benchmarks/configs"
                      / "mistral-7b-v0.3-serve-l16.json").read_text())
    return {"trace": {"stand": "in"}, "config": cfg,
            "device_kind": "TPU v5 lite",
            program_trace.KEY: program_trace.reduce(synthetic())}


def _read(name, obs):
    m = {m["name"]: m for m in manifest.load_cell(ROOT, CELL).per_layer}[name]
    return readers.find_reader(ROOT, m["reader"])(obs, **m["args"])


def test_span_medians_take_the_spans_wholly_inside_the_window(obs):
    # serving.pack: 40, 30 and 35 ms; serving.commit: 8 (step 1's, whole
    # though its step is cut), 25 and 10 ms
    assert _read("host_pack_ms.serve", obs) == pytest.approx(35.0)
    assert _read("host_commit_ms.serve", obs) == pytest.approx(10.0)


def test_idle_time_goes_to_the_innermost_program_span(obs, capsys):
    # 470 ms idle of 1000: by hand, gap by gap (see synthetic())
    want = {"idle_in_pack.serve": 10.5, "idle_in_launch.serve": 10.0,
            "idle_in_fetch.serve": 6.5, "idle_in_commit.serve": 4.3}
    for name, pct in want.items():
        assert _read(name, obs) == pytest.approx(pct), name
    said = capsys.readouterr().out
    # what no phase covers falls to the step, what no step covers is seen
    assert "serving.step 2.400%" in said and "(no span) 12.000%" in said
    assert "serving.admit 1.300%" in said and said.count("# idle time") == 1


def test_counts_come_from_the_whole_steps_only(obs, capsys):
    assert _read("batch_occupancy.serve", obs) == pytest.approx(40.0)
    assert _read("prefill_backlog.serve", obs) == pytest.approx(70.0)
    assert "max 100" in capsys.readouterr().out
    assert _read("queue_wait_ms.serve", obs) == pytest.approx(1.0)


def test_kernel_time_and_roofline_at_the_cells_shapes(obs, capsys):
    # the kernel ran 90 + 100 ms inside steps 2 and 3 (50 ms more outside)
    assert _read("paged_attn_ms.serve", obs) == pytest.approx(95.0)
    # 32 heads x 128, 8 KV heads, 16 layers, bf16, by hand:
    # operations 4 x 32 x 128 x 16 x 160,000 = 4.194304e10 -> 0.2129 ms
    # bytes 57,500 x 8 x 128 x 2 x 2 x 16 = 3.76832e9 -> 4.6011 ms
    share = _read("paged_attn_roofline.serve", obs)
    assert share == pytest.approx(100 * (3.76832e9 / 819e9) / 0.190)
    assert 0 < share < 100
    assert "bound by bytes" in capsys.readouterr().out


def test_a_kernel_is_found_by_its_name_under_an_autodiff_scope_too():
    hit = program_trace.is_kernel
    assert hit(KERNEL, "ragged_paged_attention")
    assert hit("jvp_flash_attention_fwd_headbatched_ (pallas)",
               "flash_attention_fwd_headbatched")
    assert hit("transpose_jvp_flash_attention_bwd_headbatched__ (pallas)",
               "flash_attention_bwd_headbatched")
    # not a kernel whose name merely starts the same, nor a plain operation
    assert not hit("jvp_flash_attention_fwd_headbatched_ (pallas)",
                   "flash_attention_fwd")
    assert not hit("ragged_paged_attention", "ragged_paged_attention")
    assert not hit("_unified_step_jit (pallas)", "ragged_paged_attention")


def test_roofline_names_the_bound():
    mod = readers.find_reader(ROOT, "paged_attn_roofline_pct").__globals__
    cfg = json.loads((ROOT / "benchmarks/configs"
                      / "mistral-7b-v0.3-serve-l16.json").read_text())
    least = mod["least_seconds"]
    s, bound, flops_s, bytes_s = least(
        cfg, [{"attn_row_ctx": 3 * 10**8, "kv_ctx_tokens": 1000}],
        "TPU v5 lite")
    assert bound == "flops" and s == flops_s > bytes_s
    assert flops_s == pytest.approx(4 * 32 * 128 * 16 * 3e8 / 197e12)
    with pytest.raises(KeyError, match="no published peaks"):
        least(cfg, [{"attn_row_ctx": 1, "kv_ctx_tokens": 1}], "cpu")


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_gives_nothing_off_the_chip_or_without_program_spans(name):
    assert _read(name, {"trace": None}) is None
    # the parent of the PR that added the spans: a trace with none in it
    bare = {**synthetic(), "program": []}
    assert program_trace.reduce(bare) is None
    assert _read(name, {"trace": {"stand": "in"}, program_trace.KEY: None}) is None


def test_readers_on_a_trace_of_the_tiny_cell_recorded_here(tiny_root):
    line = _run(tiny_root, "tiny-serve.chat", trace=True)
    assert line["correct"] is True
    assert not NEW & set(line["metrics"])       # off the chip: left out
    assert program_trace.newest_xplane(tiny_root).name.endswith(".xplane.pb")
    cell = manifest.load_cell(tiny_root, "tiny-serve.chat")
    obs = {"trace": {"stand": "in"}, "config": cell.config,
           "device_kind": "cpu"}
    # the readers look in the checkout their files are in; this run
    # wrote into a copy, so hand them its trace as the first of them would
    pt = program_trace.of(obs, tiny_root)
    got = {m["name"]: readers.find_reader(tiny_root, m["reader"])(obs, **m["args"])
           for m in cell.per_layer if m["name"] in NEW}
    assert set(got) == NEW and obs[program_trace.KEY] is pt
    steps = pt.named("serving.step")
    assert len(steps) >= 3 and len(pt.step_counts()) == len(steps)
    assert got["host_pack_ms.serve"] > 0 and got["host_commit_ms.serve"] > 0
    assert 0 < got["batch_occupancy.serve"] <= 100
    assert got["prefill_backlog.serve"] >= 0
    assert got["queue_wait_ms.serve"] >= 0
    # a CPU trace holds no TPU plane: nothing the device did is read
    assert not pt.device
    for name in NEW:
        if name.startswith(("idle_in_", "paged_attn_")):
            assert got[name] is None, name


def test_every_real_metric_names_a_reader_that_exists():
    man = manifest.load_manifest(ROOT)
    assert NEW <= {m["name"] for m in man["per_layer"]}
    for w in man["workloads"]:
        for m in manifest.load_cell(ROOT, w["name"]).per_layer:
            assert callable(readers.find_reader(ROOT, m["reader"])), m["name"]
    for name in NEW:
        spec = json.loads((ROOT / "benchmarks/layer_metrics"
                           / f"{name}.json").read_text())
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert (spec["unit"], spec["moves"], spec["layer"]) == \
            (entry["unit"], entry["moves"], entry["layer"])


def test_a_reader_can_be_added_beside_the_readers_that_are_there(tiny_root):
    (tiny_root / "benchmarks/readers/queue_peak.py").write_text(
        "def read(obs, counter):\n    return obs['counters'].get(counter)\n")
    per_layer = [{"name": "queue_peak.serve", "unit": "requests",
                  "reader": "queue_peak", "args": {"counter": "peak"}}]
    assert readers.read_all(tiny_root, per_layer, {"counters": {"peak": 7}}) == \
        {"queue_peak.serve": {"value": 7.0, "unit": "requests"}}
    assert readers.read_all(tiny_root, per_layer, {"counters": {}}) == {}
    with pytest.raises(KeyError, match="no reader"):
        readers.find_reader(tiny_root, "nope")
