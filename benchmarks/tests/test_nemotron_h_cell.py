"""Rehearsal of the Nemotron-3-Super serving cell off the chip: runner
``serve_nemotron_h`` end to end at a tiny size (float32, the sequential
scan, a recurrent state a slot beside one kind of page, snapshots in the
prefix cache), its five controls and a state pool of another type than
the file states coming out as NOT correct, the real cell's files loading, and the scan roofline
reader's arithmetic."""

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
CELL = "nemotron3-super-serve-l11-ep8.agent-fanout"
NEW_METRICS = ("ssd_scan_ms.serve", "ssd_scan_roofline.serve",
               "state_restored_share.serve", "latent_moe_gmm_ms.serve",
               "latent_moe_experts_hit_share.serve")

TINY = {
    "model_type": "nemotron_h", "hidden_size": 32, "num_hidden_layers": 5,
    # the published list is longer than the layers that run
    "hybrid_override_pattern": "MEM*EMEM*E",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "use_conv_bias": True, "mamba_proj_bias": False,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 5, "moe_intermediate_size": 24,
    "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 40,
    "mlp_hidden_act": "relu2", "norm_eps": 1e-5, "vocab_size": 96,
    "max_position_embeddings": 256, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4,
    "published": {"n_routed_experts": 16}, "deployment_rank": 1,
    "torch_dtype": "float32", "moe_block_rows": 8, "weights_std": 0.3,
    "ssm_state_dtype": "float32",
}


@pytest.fixture(scope="module")
def nemotron_root(tmp_path_factory):
    """A checkout of its own with a throw-away cell of this runner."""
    root = tmp_path_factory.mktemp("nemotron") / "checkout"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "benchmarks"
    (b / "configs/tiny-nemotron.json").write_text(json.dumps({
        **TINY, "name": "tiny-nemotron", "runner": "serve_nemotron_h",
        "chips": 1,
        # float32 on both sides: a sound run's gaps are 0 or a last-bit tie
        "check": {"served_token_gap_widest": 1e-3,
                  "served_token_gap_mean": 1e-4,
                  "probe_logit_err_mean": 1e-4,
                  "state_err_slow_mean": 1e-4},
        "engine": {"max_slots": 3, "max_seq_len": 64, "num_pages": 49,
                   "page_size": 4, "prefill_token_budget": 8,
                   "enable_prefix_cache": True, "state_snapshots": 6,
                   "cache_dtype": "float32"}}))
    (b / "traffic/tiny-fanout.json").write_text(json.dumps({
        "kind": "serve", "schedule_seed": 0,
        "arrivals": {"process": "poisson", "rate_per_s": 2.0},
        # a shared preamble of six pages, three chunks, before every task
        "prefix": {"pool": 1, "tokens": 24},
        "user_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                        "min": 4, "max": 28},
        "max_new_tokens": {"dist": "fixed", "value": 6},
        "repeats": 1, "drain_s": 120, "check_sample": 3, "trace_s": 1.0}))
    man["configs"].append({"name": "tiny-nemotron", "source": "rehearsal",
                           "file": "benchmarks/configs/tiny-nemotron.json",
                           "reduced": [], "why": "rehearsal"})
    man["workloads"].append({"name": "tiny-nemotron.fanout",
                             "config": "tiny-nemotron",
                             "traffic": "tiny-fanout", "chips": 1,
                             "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-nemotron.fanout")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _run(root, overrides=None, trace=False, seed=2**31 + 29):
    report = {}
    line = bench_run.run_cell(root, "tiny-nemotron.fanout", seed, 3.0, trace,
                              target=CPU, devices=jax.devices()[:1],
                              overrides=overrides, report=report,
                              t_process=time.perf_counter())
    return line, report


def test_nemotron_sound_run_is_correct_and_restores_its_preamble(
        nemotron_root):
    line, report = _run(nemotron_root, trace=True,
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
    # the state each sampled prompt leaves in its slot's entry, against
    # the reference's S: 3 requests x 2 state layers x the slowest head
    assert "state_err_slow_mean" in names
    assert "ssm_state_dtype_differs" in names
    assert len(report["positions"]["state"]) == 6
    assert max(report["positions"]["state"]) <= 1e-5
    # host-side readers report: every request found the snapshot at its
    # preamble's end; what needs the chip's trace is left out
    m = line["metrics"]
    assert m["state_restored_share.serve"]["value"] == 100.0
    for name in NEW_METRICS:
        assert (name in m) == (name == "state_restored_share.serve")


@pytest.mark.parametrize("control", [{"control_lowp": "fp8"},
                                     {"control_state": "bfloat16"},
                                     {"control_restore": "zeros"},
                                     {"control_conv": "dropped"},
                                     {"control_gates": "held"}],
                         ids=["fp8", "state_bf16", "restore_zeros",
                              "conv_dropped", "gates_held"])
def test_a_nemotron_control_comes_out_not_correct(nemotron_root, control):
    line, report = _run(nemotron_root, overrides=control)
    assert line["correct"] is False, report
    c = report["control"]
    # the run beside it was sound: only the control's logits stray
    assert c["sound_widest"] <= 1e-3
    assert c["sound_logit_err"] <= 1e-4 < c["logit_err"]
    assert c["sound_state_err"] <= 1e-5
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert "probe_logit_err_mean" in bad
    assert set(bad) <= {"served_token_gap_widest", "served_token_gap_mean",
                        "probe_logit_err_mean", "state_err_slow_mean"}
    # what a control does to the state a prompt leaves shows in the slow
    # heads (gates_held: the first state layer precedes every expert
    # layer, the later ones read what the gates changed)
    assert "state_err_slow_mean" in bad
    # the cell's end-to-end metrics: not ttft_p95_ms, five steps of a
    # request at the 95th percentile, which spreads by half its bound
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s",
                                    "setup_s"}


def test_a_state_pool_of_another_type_is_not_correct(nemotron_root):
    """An engine that kept the SSM state in bf16 (half the cell's largest
    stream) strays less in its logits than the bf16 engine itself; what
    holds it is the pools' type against the file's ``ssm_state_dtype``,
    exactly.  (The scan takes a float32 pool only, so here the FILE
    states the other type.)"""
    path = nemotron_root / "benchmarks/configs/tiny-nemotron.json"
    sound = path.read_text()
    path.write_text(json.dumps({**json.loads(sound),
                                "ssm_state_dtype": "bfloat16"}))
    try:
        line, report = _run(nemotron_root, overrides={"reference_pad": 16})
    finally:
        path.write_text(sound)
    assert line["correct"] is False
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert bad == ["ssm_state_dtype_differs"]


def test_the_real_nemotron_cell_loads_with_its_readers():
    cell = manifest.load_cell(ROOT, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["runner"] == "serve_nemotron_h"
    assert {m["name"] for m in cell.end_to_end} == {
        "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert set(cfg["check"]) == {"served_token_gap_widest",
                                 "served_token_gap_mean",
                                 "probe_logit_err_mean",
                                 "state_err_slow_mean"}
    # each limit lies between its two readings (PERF.md section 2)
    assert cfg["check"]["served_token_gap_widest"] == 0.5
    assert cfg["check"]["served_token_gap_mean"] == 0.005
    assert cfg["ssm_state_dtype"] == "float32"
    per_layer = {m["name"]: m for m in cell.per_layer}
    for name in NEW_METRICS:
        assert callable(readers.find_reader(ROOT, per_layer[name]["reader"]))
    assert {m["moves"] for m in cell.per_layer} \
        <= {m["name"] for m in cell.end_to_end}
    # the accepted metrics the cell takes part in, and not the one-kind
    # attention roofline (its reader multiplies by num_hidden_layers)
    assert set(per_layer) - set(NEW_METRICS) == {
        "engine_step_ms.serve", "device_idle.serve", "host_pack_ms.serve",
        "host_commit_ms.serve", "idle_in_pack.serve", "idle_in_launch.serve",
        "idle_in_fetch.serve", "idle_in_commit.serve",
        "batch_occupancy.serve", "paged_attn_ms.serve"}
    # (not the accepted metrics that move ttft_p95_ms, which the cell does
    # not report: moe_gmm_ms.serve and moe_experts_hit_share.serve, which
    # ISSUE 33 lists, are among them, and test_mellum2_cell.py, an
    # accepted benchmark file, holds both lists to the Mellum2 cell alone;
    # the latent_moe_* pair reads the same kernel and counters and moves
    # the metric this cell reports)
    # no width differs from the published row; the cut is in `reduced`
    man = manifest.load_manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"]) == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    catalog = pathlib.Path("/opt/skills/guides/model-configs/"
                           "architectures.jsonl")
    if catalog.is_file():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["source_url"] == cfg["source"])
        for k, v in row["config"].items():
            assert cfg[k] == v or k in cfg["reduced"], k
            if k in cfg["reduced"]:
                assert cfg["published"][k] == v
    runner = manifest.load_runner(ROOT, cfg["runner"])
    model = runner.model_config(cfg)
    assert (model.hidden_size, model.mamba_num_heads, model.mamba_head_dim,
            model.n_groups, model.ssm_state_size, model.conv_kernel,
            model.chunk_size, model.num_attention_heads,
            model.num_key_value_heads, model.head_dim,
            model.n_routed_experts, model.moe_top_k, model.experts_held,
            model.moe_intermediate_size, model.moe_latent_size,
            model.moe_shared_expert_intermediate_size, model.vocab_size) == \
        (4096, 128, 64, 8, 128, 4, 128, 32, 2, 128, 512, 22, (0, 64), 2688,
         1024, 5376, 16384)
    assert model.pattern == cfg["layers_run"] == "MEMEMEM*EME"
    from benchmarks.harness import traffic, weights_nemotron_h

    assert traffic.longest_request_tokens(cell.traffic) \
        <= cfg["engine"]["max_seq_len"]
    shapes = {f"model.layers.{i}.{n}": s
              for i, letter in enumerate(model.pattern)
              for n, s in weights_nemotron_h.layer_shapes(cfg, letter).items()}
    for i in model.layers_of("M"):
        for n in ("dt_bias", "A_log", "D"):
            shapes[f"model.layers.{i}.mixer.{n}"] = (128,)
    shapes.update(weights_nemotron_h.top_shapes(cfg))
    assert shapes == model.leaf_shapes()
    assert sum(math.prod(s) for s in shapes.values()) == 2_752_338_304
    # the pools' arithmetic: a slot's state is 4.19 MB of float32 S and
    # 61 kB of bf16 conv tail a layer, 5 layers; 161 entries; a page of
    # 128 tokens x 2 KV heads x 128 x K and V x 2 B is 128 KiB
    e = cfg["engine"]
    entry_bytes = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert entry_bytes == 21_278_720
    assert (e["max_slots"] + e["state_snapshots"] + 1) * entry_bytes \
        == 3_425_873_920
    assert e["num_pages"] * 128 * 1024 == 1_006_632_960


def test_scan_roofline_reader_counts_least_work():
    import importlib

    mod = importlib.import_module(
        "benchmarks.readers.mamba2_ssd_roofline_pct")
    cfg = manifest.load_cell(ROOT, CELL).config
    from benchmarks.harness import peaks

    peak = peaks.peaks_for("TPU v5 lite")
    # 100 decode rows and a 512-row chunk of one more slot
    counts = [{"ssm_rows": 612, "ssm_state_slots": 101}]
    s, bound, flops_s, bytes_s = mod.least_seconds(cfg, counts, "TPU v5 lite")
    assert flops_s == 4 * 128 * 64 * 128 * 612 * 5 / peak["bf16_flops_per_s"]
    row = (2 * 8192 + 2 * 1024) * 2 + 128 * 4
    assert bytes_s == (2 * 4194304 * 101 + row * 612) * 5 \
        / peak["hbm_bytes_per_s"]
    assert s == bytes_s and bound == "bytes"        # a decode-heavy step
    # a program that writes no such counts: nothing to read, no error
    assert mod.least_seconds(cfg, [{"rows": 1}], "TPU v5 lite") is None
    assert mod.read({"trace": None}, "mamba2_ssd_scan") is None
