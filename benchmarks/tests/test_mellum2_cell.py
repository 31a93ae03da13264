"""Rehearsal of the Mellum2 serving cell off the chip: runner
``serve_mellum2`` end to end at a tiny size (interpret-mode kernels,
float32, two kinds of page), its four controls coming out as NOT
correct, the real cell's files loading, and the kind roofline reader's
arithmetic."""

import json
import math
import pathlib
import shutil
import sys
import time

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import context, manifest, readers  # noqa: E402

CPU = context.Target(platform="cpu", trace_device=False)
CELL = "mellum2-serve-l8.ide-mixed"
NEW_METRICS = ("window_attn_ms.serve", "window_attn_roofline.serve",
               "full_attn_roofline.serve", "moe_gmm_ms.serve",
               "moe_experts_hit_share.serve", "window_kv_share.serve")

TINY = {
    "model_type": "mellum", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "sliding_window": 8, "tie_word_embeddings": False,
    # the published lists are longer than the layers that run
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 8,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 4,
                           "original_max_position_embeddings": 32,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "torch_dtype": "float32", "moe_block_rows": 8, "weights_std": 0.3,
}


@pytest.fixture(scope="module")
def mellum2_root(tmp_path_factory):
    """A checkout of its own with a throw-away cell of this runner."""
    root = tmp_path_factory.mktemp("mellum2") / "checkout"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "benchmarks"
    (b / "configs/tiny-mellum2.json").write_text(json.dumps({
        **TINY, "name": "tiny-mellum2", "runner": "serve_mellum2",
        "chips": 1,
        # float32 on both sides: a sound run's gaps are 0 or a last-bit tie
        "check": {"served_token_gap_widest": 1e-3,
                  "served_token_gap_mean": 1e-4,
                  "probe_logit_err_mean": 1e-4},
        "engine": {"max_slots": 3, "max_seq_len": 64,
                   "num_pages": {"full": 49, "window": 25},
                   "page_size": 4, "prefill_token_budget": 6,
                   "enable_prefix_cache": True, "cache_dtype": "float32"}}))
    (b / "traffic/tiny-ide.json").write_text(json.dumps({
        "kind": "serve", "schedule_seed": 0,
        "arrivals": {"process": "poisson", "rate_per_s": 2.0},
        # a pinned context of three windows before every request
        "prefix": {"pool": 1, "tokens": 24},
        "user_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                        "min": 4, "max": 30},
        "max_new_tokens": {"dist": "fixed", "value": 6},
        "repeats": 1, "drain_s": 120, "check_sample": 3, "trace_s": 1.0}))
    man["configs"].append({"name": "tiny-mellum2", "source": "rehearsal",
                           "file": "benchmarks/configs/tiny-mellum2.json",
                           "reduced": [], "why": "rehearsal"})
    man["workloads"].append({"name": "tiny-mellum2.ide",
                             "config": "tiny-mellum2", "traffic": "tiny-ide",
                             "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-mellum2.ide")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _run(root, overrides=None, trace=False, seed=2**31 + 23):
    report = {}
    line = bench_run.run_cell(root, "tiny-mellum2.ide", seed, 3.0, trace,
                              target=CPU, devices=jax.devices()[:1],
                              overrides=overrides, report=report,
                              t_process=time.perf_counter())
    return line, report


def test_mellum2_sound_run_is_correct_and_hits_beyond_the_window(
        mellum2_root):
    # a tool that runs many seeds in a process pads the reference's
    # sequences to a shorter unit than the engine's longest
    line, report = _run(mellum2_root, trace=True,
                        overrides={"reference_pad": 16})
    assert line["correct"] is True, report["checks"]
    assert line["attempted"] == 6 and line["failed"] == 0
    names = [c["name"] for c in report["checks"]]
    assert "served_token_gap_widest" in names and "control" not in report
    # the engine's logits where each sampled answer begins, against the
    # reference's rows: 3 requests, 6 tokens each
    assert "probe_logit_err_mean" in names
    assert len(report["positions"]["err"]) == 18
    assert max(report["positions"]["err"]) <= 1e-4
    assert "allocator_or_cache_inconsistent" in names
    # host-side readers report: every request hit its pinned context,
    # three windows long; what needs the chip's trace is left out
    assert line["metrics"]["prefix_hit_share.serve"]["value"] > 30
    for name in NEW_METRICS:
        assert name not in line["metrics"]


@pytest.mark.parametrize("control", [{"control_lowp": "fp8"},
                                     {"control_no_window": True},
                                     {"control_no_yarn": True},
                                     {"control_gates": "softmax"}],
                         ids=["fp8", "no_window", "no_yarn", "gates_softmax"])
def test_a_mellum2_control_comes_out_not_correct(mellum2_root, control):
    line, report = _run(mellum2_root, overrides=control)
    assert line["correct"] is False, report
    c = report["control"]
    # the run beside it was sound: only the control's choices stray
    assert c["sound_widest"] <= 1e-3 < c["widest"]
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert bad and set(bad) <= {"served_token_gap_widest",
                                "served_token_gap_mean",
                                "probe_logit_err_mean"}
    # the control's logits stand in the engine's place: the engine's own
    # are float32 here, as the reference's
    assert c["sound_logit_err"] <= 1e-4 < c["logit_err"]
    if control == {"control_lowp": "fp8"}:
        assert "probe_logit_err_mean" in bad
    # the cell reports all four end-to-end metrics
    assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                    "serve_tokens_per_s", "setup_s"}


def test_the_real_mellum2_cell_loads_with_its_readers():
    cell = manifest.load_cell(ROOT, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["runner"] == "serve_mellum2"
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p95_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert set(cfg["check"]) == {"served_token_gap_widest",
                                 "served_token_gap_mean",
                                 "probe_logit_err_mean"}
    per_layer = {m["name"]: m for m in cell.per_layer}
    for name in NEW_METRICS:
        assert callable(readers.find_reader(ROOT, per_layer[name]["reader"]))
    # every per-layer metric of the cell moves a metric the cell reports
    assert {m["moves"] for m in cell.per_layer} \
        <= {m["name"] for m in cell.end_to_end}
    # no width differs from the published row; the cut is in `reduced`
    man = manifest.load_manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"]) \
        == ["num_hidden_layers"]
    runner = manifest.load_runner(ROOT, cfg["runner"])
    model = runner.model_config(cfg)
    assert (model.hidden_size, model.head_dim, model.num_attention_heads,
            model.num_key_value_heads, model.num_experts, model.moe_top_k,
            model.moe_intermediate_size, model.vocab_size,
            model.sliding_window, model.num_hidden_layers) == \
        (2304, 128, 32, 4, 64, 8, 896, 98304, 1024, 8)
    assert model.layer_types.count("full_attention") == 2
    assert dict(dict(model.rope_parameters)["full_attention"])[
        "attention_factor"] == 1.2772588722239782
    from benchmarks.harness import traffic, weights_mellum2

    assert traffic.longest_request_tokens(cell.traffic) \
        <= cfg["engine"]["max_seq_len"]
    shapes = {f"model.layers.{i}.{n}": s
              for i in range(cfg["num_hidden_layers"])
              for n, s in weights_mellum2.layer_shapes(cfg).items()}
    shapes.update(weights_mellum2.top_shapes(cfg))
    assert shapes == model.leaf_shapes()
    params = sum(math.prod(s) for s in shapes.values())
    assert params == 3_794_966_784               # ISSUE's 7.59 GB in bf16
    # the pools' arithmetic: pages of 128 tokens x 4 KV heads x 128 x K and
    # V x 2 B are 256 KiB a layer
    pages = cfg["engine"]["num_pages"]
    assert pages["full"] * 2 * 256 * 1024 == int(3.5 * 2**30)
    assert pages["window"] * 6 * 256 * 1024 == int(1.5 * 2**30)


def test_the_mellum2_cell_is_not_under_the_one_kind_roofline():
    """``paged_attn_roofline.serve``'s reader multiplies the least work
    by ``num_hidden_layers``: in a cell whose layers are of two kinds it
    would read up to four times the truth."""
    man = manifest.load_manifest(ROOT)
    one_kind = next(m for m in man["per_layer"]
                    if m["name"] == "paged_attn_roofline.serve")
    assert CELL not in one_kind["workloads"]
    for name in NEW_METRICS:
        m = next(m for m in man["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "ttft_p95_ms"
    # the accepted metrics the cell takes part in are those that move a
    # metric it reports: the nine ISSUE 30 lists
    mine = {m["name"]: m for m in man["per_layer"] if CELL in m["workloads"]}
    assert set(mine) - set(NEW_METRICS) == {
        "engine_step_ms.serve", "prefix_hit_share.serve", "device_idle.serve",
        "host_pack_ms.serve", "host_commit_ms.serve", "batch_occupancy.serve",
        "prefill_backlog.serve", "queue_wait_ms.serve", "paged_attn_ms.serve"}
    assert {m["moves"] for m in mine.values()} == {"ttft_p95_ms",
                                                   "itl_p95_ms"}


def test_kind_roofline_reader_counts_least_work_by_kind():
    import importlib

    mod = importlib.import_module(
        "benchmarks.readers.paged_attn_kind_roofline_pct")
    cfg = manifest.load_cell(ROOT, CELL).config
    # 32 decode rows at context 6000 and a 512-row chunk at 4096..4607
    chunk = sum(range(4097, 4609))
    counts = [{"attn_row_ctx": 32 * 6000 + chunk,
               "kv_ctx_tokens": 32 * 6000 + 4608,
               "attn_row_ctx_window": 544 * 1024,
               "kv_ctx_tokens_window": 33 * 1024}]
    from benchmarks.harness import peaks

    peak = peaks.peaks_for("TPU v5 lite")
    s, bound, flops_s, bytes_s = mod.least_seconds(cfg, counts,
                                                   "TPU v5 lite", "full")
    assert flops_s == 4 * 32 * 128 * 2 * (32 * 6000 + chunk) \
        / peak["bf16_flops_per_s"]
    assert bytes_s == (32 * 6000 + 4608) * 4 * 128 * 2 * 2 * 2 \
        / peak["hbm_bytes_per_s"]
    assert s == max(flops_s, bytes_s) and bound in ("flops", "bytes")
    s, bound, flops_s, bytes_s = mod.least_seconds(cfg, counts,
                                                   "TPU v5 lite", "window")
    assert flops_s == 4 * 32 * 128 * 6 * 544 * 1024 / peak["bf16_flops_per_s"]
    assert bytes_s == 33 * 1024 * 4 * 128 * 2 * 2 * 6 / peak["hbm_bytes_per_s"]
    with pytest.raises(ValueError):
        mod.least_seconds(cfg, counts, "TPU v5 lite", "other")
    # a program that writes no such counts: nothing to read, no error
    assert mod.least_seconds(cfg, [{"attn_row_ctx": 1, "kv_ctx_tokens": 1}],
                             "TPU v5 lite", "window") is None
    assert mod.read({"trace": None}, "ragged_paged_attention_window",
                    "window") is None
