"""Rehearsal of the Kimi-Linear serving cell off the chip: runner
``serve_kimi_linear`` end to end at a tiny size (float32, the sequential
scan, a KDA state a slot beside one kind of LATENT page, snapshots in the
prefix cache), its controls and a state pool of another type than the
file states coming out as NOT correct, the real cells' files loading
(this PR's two), and the two roofline readers' arithmetic."""

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
CELL = "kimi-linear-serve-l13-ep8.reason-longtail"
UNSHARED = "mistral7b-serve-l16.chat-unshared"
NEW_METRICS = ("kda_scan_ms.serve", "kda_conv_ms.serve", "kda_proj_ms.serve",
               "latent_attn_ms.serve", "kda_scan_roofline.serve",
               "latent_attn_roofline.serve")

TINY = {
    "model_type": "kimi_linear", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_hidden_layers": 5,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "mla_use_nope": True,
    # the published lists are longer than the layers that run
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5, 6, 7],
                           "full_attn_layers": [4, 8], "num_heads": 4,
                           "head_dim": 8, "short_conv_kernel_size": 4},
    "num_experts": 8, "num_experts_per_token": 3, "num_shared_experts": 1,
    "num_expert_group": 1, "topk_group": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "vocab_size": 96,
    "model_max_length": 256,
    "kda_init": {"dt_min": 0.001, "dt_max": 0.1, "dt_floor": 1e-4,
                 "a_min": 1.0, "a_max": 16.0},
    "published": {"num_experts": 16}, "deployment_rank": 1,
    "torch_dtype": "float32", "moe_block_rows": 8, "weights_std": 0.3,
    "state_dtype": "float32",
}


@pytest.fixture(scope="module")
def kimi_root(tmp_path_factory):
    """A checkout of its own with a throw-away cell of this runner."""
    root = tmp_path_factory.mktemp("kimi") / "checkout"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "benchmarks"
    (b / "configs/tiny-kimi.json").write_text(json.dumps({
        **TINY, "name": "tiny-kimi", "runner": "serve_kimi_linear",
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
    (b / "traffic/tiny-longtail.json").write_text(json.dumps({
        "kind": "serve", "schedule_seed": 0,
        "arrivals": {"process": "poisson", "rate_per_s": 2.0},
        # a shared prompt of six pages, three chunks, before every question
        "prefix": {"pool": 1, "tokens": 24},
        "user_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                        "min": 4, "max": 28},
        "max_new_tokens": {"dist": "fixed", "value": 6},
        "repeats": 1, "drain_s": 120, "check_sample": 3, "trace_s": 1.0}))
    man["configs"].append({"name": "tiny-kimi", "source": "rehearsal",
                           "file": "benchmarks/configs/tiny-kimi.json",
                           "reduced": [], "why": "rehearsal"})
    man["workloads"].append({"name": "tiny-kimi.longtail",
                             "config": "tiny-kimi",
                             "traffic": "tiny-longtail", "chips": 1,
                             "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-kimi.longtail")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _run(root, overrides=None, trace=False, seed=2**31 + 46):
    report = {}
    line = bench_run.run_cell(root, "tiny-kimi.longtail", seed, 3.0, trace,
                              target=CPU, devices=jax.devices()[:1],
                              overrides=overrides, report=report,
                              t_process=time.perf_counter())
    return line, report


def test_kimi_sound_run_is_correct_and_restores_its_prompt(kimi_root):
    line, report = _run(kimi_root, trace=True,
                        overrides={"reference_pad": 16})
    assert line["correct"] is True, report["checks"]
    assert line["attempted"] == 6 and line["failed"] == 0
    names = [c["name"] for c in report["checks"]]
    assert {"served_token_gap_widest", "probe_logit_err_mean",
            "state_err_slow_mean", "state_dtype_differs",
            "allocator_or_cache_inconsistent"} <= set(names)
    assert "control" not in report
    # the engine's logits where each sampled answer begins: 3 requests,
    # 6 tokens each; the state each prompt leaves in the FIRST KDA layer:
    # 3 requests x the slowest head
    assert len(report["positions"]["err"]) == 18
    assert max(report["positions"]["err"]) <= 1e-4
    assert len(report["positions"]["state"]) == 3
    assert max(report["positions"]["state"]) <= 1e-5
    # host-side readers report (the host's clock); what needs the chip's
    # trace is left out, and so is the restored share, which moves a
    # metric the cell does not report
    m = line["metrics"]
    assert "engine_step_ms.serve" in m
    assert "state_restored_share.serve" not in m
    for name in NEW_METRICS:
        assert name not in m


@pytest.mark.parametrize("control", [{"control_state": "bfloat16"},
                                     {"control_decay": "bfloat16"},
                                     {"control_beta": "dropped"},
                                     {"control_lowp": "fp8"},
                                     {"control_gates": "held"}],
                         ids=["state_bf16", "decay_bf16", "beta_dropped",
                              "fp8", "gates_held"])
def test_a_kimi_control_comes_out_not_correct(kimi_root, control):
    line, report = _run(kimi_root, overrides=control)
    assert line["correct"] is False, report
    c = report["control"]
    # the run beside it was sound: only the control's numbers stray
    assert c["sound_widest"] <= 1e-3
    assert c["sound_logit_err"] <= 1e-4 < c["logit_err"]
    assert c["sound_state_err"] <= 1e-5
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert "probe_logit_err_mean" in bad
    assert set(bad) <= {"served_token_gap_widest", "served_token_gap_mean",
                        "probe_logit_err_mean", "state_err_slow_mean"}
    # the first KDA layer precedes every expert layer and reads the
    # embedding: what the matmuls' precision or the gates change reaches
    # the logits, and only the scan's own controls its state
    assert ("state_err_slow_mean" in bad) == any(
        k in control for k in ("control_state", "control_decay",
                               "control_beta", "control_lowp"))


def test_a_kimi_state_pool_of_another_type_is_not_correct(kimi_root):
    """What holds an ENGINE that kept the state in bf16 is the pools'
    type against the file's ``state_dtype``, exactly.  (The scan takes a
    float32 pool only, so here the FILE states the other type.)"""
    path = kimi_root / "benchmarks/configs/tiny-kimi.json"
    sound = path.read_text()
    path.write_text(json.dumps({**json.loads(sound),
                                "state_dtype": "bfloat16"}))
    try:
        line, report = _run(kimi_root, overrides={"reference_pad": 16})
    finally:
        path.write_text(sound)
    assert line["correct"] is False
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert bad == ["state_dtype_differs"]


def test_the_real_kimi_cell_loads_with_its_readers():
    cell = manifest.load_cell(ROOT, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["runner"] == "serve_kimi_linear"
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert set(cfg["check"]) == {"served_token_gap_widest",
                                 "served_token_gap_mean",
                                 "probe_logit_err_mean",
                                 "state_err_slow_mean"}
    assert cfg["state_dtype"] == "float32"
    per_layer = {m["name"]: m for m in cell.per_layer}
    for name in NEW_METRICS:
        assert callable(readers.find_reader(ROOT, per_layer[name]["reader"]))
        assert per_layer[name]["workloads"] == [CELL]
    assert {m["moves"] for m in cell.per_layer} <= e2e
    # the accepted metrics the cell takes part in: a closed list (and not
    # moe_gmm_ms.serve / moe_experts_hit_share.serve, which
    # test_mellum2_cell.py holds to the Mellum2 cell alone)
    assert set(per_layer) - set(NEW_METRICS) <= {
        "engine_step_ms.serve", "device_idle.serve", "host_pack_ms.serve",
        "host_commit_ms.serve", "batch_occupancy.serve",
        "head_sample_ms.serve",
        "compiler_ops_ms.serve", "unscoped_device_share.serve",
        "moe_dispatch_ms.serve", "state_restored_share.serve"}
    # no width differs from the published row; the cut is in `reduced`
    man = manifest.load_manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"]) == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
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
    assert (model.hidden_size, model.intermediate_size, model.kda_num_heads,
            model.kda_head_dim, model.short_conv_kernel_size,
            model.num_attention_heads, model.kv_lora_rank,
            model.qk_nope_head_dim, model.qk_rope_head_dim, model.v_head_dim,
            model.num_experts, model.moe_top_k, model.experts_held,
            model.moe_intermediate_size, model.routed_scaling_factor,
            model.vocab_size, model.num_hidden_layers) == \
        (2304, 9216, 32, 128, 4, 32, 512, 128, 64, 128, 256, 8, (0, 32), 1024,
         2.446, 20480, 13)
    assert model.layers_of(False) == (3, 7, 11)
    assert len(model.layers_of(True)) == 10
    from benchmarks.harness import traffic, weights_kimi_linear

    assert traffic.longest_request_tokens(cell.traffic) \
        == cfg["engine"]["max_seq_len"] == 35840
    shapes = {f"model.layers.{i}.{n}": s
              for i in range(cfg["num_hidden_layers"])
              for n, s in weights_kimi_linear.layer_shapes(cfg, i).items()}
    for i in model.layers_of(True):
        shapes[f"model.layers.{i}.self_attn.A_log"] = (32,)
        shapes[f"model.layers.{i}.self_attn.dt_bias"] = (4096,)
    shapes.update(weights_kimi_linear.top_shapes(cfg))
    assert shapes == model.leaf_shapes()
    # ISSUE 46 reckons 3,450 M parameters (6.90 GB in bf16)
    n = sum(math.prod(s) for s in shapes.values())
    assert abs(n / 3.450e9 - 1) < 0.005 and n == 3_450_547_008
    # the pools' arithmetic: a slot's state is 2.10 MB of float32 S and
    # 74 kB of bf16 conv tail a layer, 10 layers; 161 entries; a page id
    # is 128 tokens x 640 x 2 B in each of the 3 MLA layers
    e = cfg["engine"]
    entry_bytes = 10 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert entry_bytes == 21_708_800
    assert (e["max_slots"] + e["state_snapshots"] + 1) * entry_bytes \
        == 3_495_116_800
    lay = model.paged_layout()
    assert sum(r[0] for r in lay.rows) == 640
    assert e["num_pages"] * 128 * 640 * 2 * 3 == 2_013_265_920


def test_the_unshared_chat_cell_is_data_beside_the_chat_cell():
    chat = manifest.load_cell(ROOT, "mistral7b-serve-l16.chat")
    cell = manifest.load_cell(ROOT, UNSHARED)
    assert cell.config == chat.config and cell.chips == 1
    same = {k: v for k, v in cell.traffic.items()
            if k not in ("what", "prefix")}
    assert same == {k: v for k, v in chat.traffic.items()
                    if k not in ("what", "prefix")}
    assert cell.traffic["prefix"] == {"pool": 0, "tokens": 768}
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    names = {m["name"] for m in cell.per_layer}
    assert "prefix_hit_share.serve" not in names
    assert names <= {m["name"] for m in chat.per_layer}
    assert names


def test_kimi_roofline_readers_count_least_work():
    import importlib

    from benchmarks.harness import peaks

    cfg = manifest.load_cell(ROOT, CELL).config
    peak = peaks.peaks_for("TPU v5 lite")
    scan = importlib.import_module("benchmarks.readers.kda_scan_roofline_pct")
    # 100 decode rows and a 512-row chunk of one more slot
    counts = [{"state_rows": 612, "state_slots": 101}]
    s, bound, flops_s, bytes_s = scan.least_seconds(cfg, counts, "TPU v5 lite")
    assert flops_s == 6 * 32 * 128 * 128 * 612 * 10 / peak["bf16_flops_per_s"]
    row = 4096 * (3 * 2 + 4 + 4) + 32 * 4
    assert bytes_s == (2 * 2097152 * 101 + row * 612) * 10 \
        / peak["hbm_bytes_per_s"]
    assert s == bytes_s and bound == "bytes"        # a decode-heavy step
    assert scan.least_seconds(cfg, [{"rows": 1}], "TPU v5 lite") is None
    assert scan.read({"trace": None}, "kda_delta_scan") is None
    attn = importlib.import_module("benchmarks.readers.dense_mla_roofline_pct")
    counts = [{"rows": 612, "attn_row_ctx": 3_000_000,
               "kv_ctx_tokens": 250_000}]
    s, bound, flops_s, bytes_s = attn.least_seconds(cfg, counts, "TPU v5 lite")
    assert flops_s == 2 * 32 * (576 + 512) * 3_000_000 * 3 \
        / peak["bf16_flops_per_s"]
    assert bytes_s == (250_000 * 576 + 612 * 32 * (576 + 512)) * 2 * 3 \
        / peak["hbm_bytes_per_s"]
    assert attn.least_seconds(cfg, [{"rows": 1}], "TPU v5 lite") is None
    assert attn.read({"trace": None}, "dense_mla_attention") is None
