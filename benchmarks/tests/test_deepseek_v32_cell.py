"""Rehearsal of the DeepSeek-V3.2 serving cell off the chip: runner
``serve_deepseek_v32`` end to end at a tiny size (interpret-mode
kernels, float32), its three controls coming out as NOT correct, the
real cell's files loading, and the roofline reader's arithmetic."""

import json
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
CELL = "dsv32-serve-l5-ep16.longdoc-reask"

TINY = {
    "model_type": "deepseek_v32", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "n_routed_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "index_n_heads": 2, "index_head_dim": 16, "index_topk": 8,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "vocab_size": 96,
    "max_position_embeddings": 256, "torch_dtype": "float32",
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "published": {"n_routed_experts": 16}, "deployment_rank": 1,
    "moe_block_rows": 8, "weights_std": 0.3,
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout of its own with a throw-away cell of this runner."""
    root = tmp_path_factory.mktemp("dsv32") / "checkout"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "benchmarks"
    (b / "configs/tiny-dsv32.json").write_text(json.dumps({
        **TINY, "name": "tiny-dsv32", "runner": "serve_deepseek_v32",
        "chips": 1,
        # float32 on both sides: a sound run's gaps are 0 or a last-bit tie
        "check": {"served_token_gap_widest": 1e-3,
                  "served_token_gap_mean": 1e-4},
        "engine": {"max_slots": 3, "max_seq_len": 64, "num_pages": 25,
                   "page_size": 8, "prefill_token_budget": 6,
                   "enable_prefix_cache": True, "cache_dtype": "float32"}}))
    (b / "traffic/tiny-reask.json").write_text(json.dumps({
        "kind": "serve", "schedule_seed": 0,
        "arrivals": {"process": "poisson", "rate_per_s": 2.0},
        "prefix": {"pool": 0, "tokens": 0},
        "user_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.4,
                        "min": 10, "max": 40},
        "max_new_tokens": {"dist": "fixed", "value": 6},
        "repeats": 3, "drain_s": 120, "check_sample": 3, "trace_s": 1.0}))
    man["configs"].append({"name": "tiny-dsv32", "source": "rehearsal",
                           "file": "benchmarks/configs/tiny-dsv32.json",
                           "reduced": [], "why": "rehearsal"})
    man["workloads"].append({"name": "tiny-dsv32.reask", "config": "tiny-dsv32",
                             "traffic": "tiny-reask", "chips": 1,
                             "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-dsv32.reask")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _run(root, overrides=None, trace=False, seed=2**31 + 17):
    report = {}
    line = bench_run.run_cell(root, "tiny-dsv32.reask", seed, 3.0, trace,
                              target=CPU, devices=jax.devices()[:1],
                              overrides=overrides, report=report,
                              t_process=time.perf_counter())
    return line, report


def test_sound_run_is_correct_and_asks_again_from_the_cache(tiny_root):
    line, report = _run(tiny_root)
    assert line["correct"] is True, report["checks"]
    assert line["attempted"] == 6 and line["failed"] == 0
    # the cell reports the tail of time to first token; the gaps between
    # tokens and the tokens per second spread wider than half their
    # bounds at this rate (PERF.md section 4) and are printed only
    assert set(line["metrics"]) == {"ttft_p95_ms", "setup_s"}
    names = [c["name"] for c in report["checks"]]
    assert "served_token_gap_widest" in names and "control" not in report


@pytest.mark.parametrize("control", [{"control_lowp": "fp8"},
                                     {"control_no_selection": True},
                                     {"control_gates": "held"}],
                         ids=["fp8", "no_selection", "gates_held"])
def test_a_control_comes_out_not_correct(tiny_root, control):
    line, report = _run(tiny_root, overrides=control)
    assert line["correct"] is False, report
    c = report["control"]
    # the run beside it was sound: only the control's choices stray
    assert c["sound_widest"] <= 1e-3 < c["widest"]
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert bad and set(bad) <= {"served_token_gap_widest",
                                "served_token_gap_mean"}


def test_a_traced_rehearsal_leaves_device_metrics_out(tiny_root):
    line, _ = _run(tiny_root, trace=True)
    assert line["correct"] is True
    # host-side readers report; what needs the chip's trace is left out
    assert "prefix_hit_share.serve" in line["metrics"]
    assert line["metrics"]["prefix_hit_share.serve"]["value"] > 0
    for name in ("index_select_ms.serve", "sparse_attn_roofline.serve",
                 "moe_held_share.serve"):
        assert name not in line["metrics"]


def test_the_real_cell_loads_with_its_readers():
    cell = manifest.load_cell(ROOT, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["runner"] == "serve_deepseek_v32"
    assert {m["name"] for m in cell.end_to_end} == {"ttft_p95_ms", "setup_s"}
    per_layer = {m["name"]: m for m in cell.per_layer}
    for name in ("index_select_ms.serve", "sparse_attn_ms.serve",
                 "index_select_roofline.serve", "sparse_attn_roofline.serve",
                 "moe_held_share.serve"):
        assert callable(readers.find_reader(ROOT, per_layer[name]["reader"]))
    assert "paged_attn_ms.serve" not in per_layer
    # every per-layer metric of the cell moves a metric the cell reports
    assert {m["moves"] for m in cell.per_layer} == {"ttft_p95_ms"}
    # no width differs from the published row; the cut is in `reduced`
    man = manifest.load_manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        cfg["published"])
    runner = manifest.load_runner(ROOT, cfg["runner"])
    model = runner.model_config(cfg)
    assert (model.n_routed_experts, model.experts_held) == (256, (0, 16))
    assert (model.hidden_size, model.kv_lora_rank, model.index_topk,
            model.num_hidden_layers, model.vocab_size) == (7168, 512, 2048,
                                                           5, 16160)
    from benchmarks.harness import traffic, weights_deepseek_v32

    assert traffic.longest_request_tokens(cell.traffic) \
        <= cfg["engine"]["max_seq_len"]
    shapes = {f"model.layers.{i}.{n}": s
              for i in range(cfg["num_hidden_layers"])
              for n, s in weights_deepseek_v32.layer_shapes(cfg, i).items()}
    shapes.update(weights_deepseek_v32.top_shapes(cfg))
    assert shapes == model.leaf_shapes()
    import math

    params = sum(math.prod(s) for s in shapes.values())
    assert abs(params - 4.636e9) < 0.01e9          # ISSUE's 4.636 B, 9.27 GB


def test_roofline_reader_counts_least_work():
    import importlib

    mod = importlib.import_module("benchmarks.readers.sparse_mla_roofline_pct")
    cfg = manifest.load_cell(ROOT, CELL).config
    counts = [{"rows": 528, "latent_ctx_tokens": 16 * 8192 + 8192,
               "index_row_ctx": 512 * 8192 + 16 * 8192,
               "sel_row_tokens": 528 * 2048}]
    ops, nbytes = mod.least_work(cfg, counts, "index")
    assert ops == 2 * 64 * 128 * (528 * 8192) * 5
    assert nbytes == (17 * 8192 + 528 * 64) * 128 * 2 * 5
    ops, nbytes = mod.least_work(cfg, counts, "attn")
    assert ops == 2 * 128 * (576 + 512) * 528 * 2048 * 5
    assert nbytes == (528 * 128 * 1088 + 2048 * 576) * 2 * 5
    with pytest.raises(ValueError):
        mod.least_work(cfg, counts, "other")
    assert mod.read({"trace": None}, "sparse_mla_attention", "attn") is None
