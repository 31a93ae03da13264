"""Rehearsal of the two cells PR 39 adds, off the chip: runner
``serve_minicpm_sala`` end to end at a tiny size (float32, the plain
``jnp`` paths: three pools a page, a recurrent state a slot, session
histories in the prefix cache with a snapshot at their end), its six
controls, a token altered, a state pool of another type and a row made
dense coming out as NOT correct; the real cells' files loading with
their readers; the new roofline readers' arithmetic; and the closed-loop
mix of the saturated Mistral cell through runner ``serve``."""

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
CELL = "minicpm-sala-serve-l12.longsession-turns"
SATURATED = "mistral7b-serve-l16.saturated"
NEW_METRICS = ("block_select_ms.serve", "block_sparse_attn_ms.serve",
               "block_select_roofline.serve",
               "block_sparse_attn_roofline.serve",
               "lightning_scan_roofline.serve", "sparse_row_share.serve",
               "sel_reread.serve", "lightning_proj_ms.serve",
               "block_select_xla_ms.serve")
LIMITS = ("served_token_gap_widest", "served_token_gap_mean",
          "probe_logit_err_mean", "state_err_slow_mean")

TINY = {
    "model_type": "minicpm_sala", "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 4,
    # the published list is longer than the layers that run
    "mixer_types": ["lightning-attn", "minicpm4", "lightning-attn",
                    "lightning-attn", "minicpm4", "lightning-attn"],
    "published": {"num_hidden_layers": 6}, "layers_run": [1, 5],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 8,
    "lightning_use_rope": True, "attn_use_rope": False, "qk_norm": True,
    "use_output_norm": True, "use_output_gate": True,
    "attn_use_output_gate": True, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "vocab_size": 96, "max_position_embeddings": 256,
    "tie_word_embeddings": False,
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 4,
                      "topk": 4, "init_blocks": 1, "window_size": 6,
                      "dense_len": 32},
    "torch_dtype": "float32", "state_dtype": "float32", "weights_std": 0.2,
    "weights_std_of": {"minicpm4": {"self_attn.q_proj.weight": 0.5,
                                    "self_attn.k_proj.weight": 0.5}},
}


@pytest.fixture(scope="module")
def sala_root(tmp_path_factory):
    """A checkout of its own with a throw-away cell of this runner."""
    root = tmp_path_factory.mktemp("sala") / "checkout"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "benchmarks"
    (b / "configs/tiny-sala.json").write_text(json.dumps({
        **TINY, "name": "tiny-sala", "runner": "serve_minicpm_sala",
        "chips": 1,
        # float32 on both sides: a sound run's gaps are 0 or a last-bit tie
        "check": {"served_token_gap_widest": 1e-3,
                  "served_token_gap_mean": 1e-4,
                  "probe_logit_err_mean": 1e-4,
                  "state_err_slow_mean": 1e-4},
        "engine": {"max_slots": 3, "max_seq_len": 96, "num_pages": 64,
                   "page_size": 8, "prefill_token_budget": 9,
                   "enable_prefix_cache": True, "state_snapshots": 6,
                   "cache_dtype": "float32"}}))
    (b / "traffic/tiny-turns.json").write_text(json.dumps({
        "kind": "serve", "schedule_seed": 0,
        "arrivals": {"process": "poisson", "rate_per_s": 2.0},
        # two session histories of six pages, past dense_len, before
        # every turn
        "prefix": {"pool": 2, "tokens": 48},
        "user_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                        "min": 3, "max": 20},
        "max_new_tokens": {"dist": "fixed", "value": 6},
        "repeats": 1, "drain_s": 120, "check_sample": 3, "trace_s": 1.0}))
    man["configs"].append({"name": "tiny-sala", "source": "rehearsal",
                           "file": "benchmarks/configs/tiny-sala.json",
                           "reduced": [], "why": "rehearsal"})
    man["workloads"].append({"name": "tiny-sala.turns", "config": "tiny-sala",
                             "traffic": "tiny-turns", "chips": 1,
                             "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-sala.turns")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _run(root, overrides=None, trace=False, seed=2**31 + 29):
    report = {}
    line = bench_run.run_cell(
        root, "tiny-sala.turns", seed, 3.0, trace, target=CPU,
        devices=jax.devices()[:1], report=report,
        overrides={"reference_pad": 16, "reference_q_block": 16,
                   **(overrides or {})}, t_process=time.perf_counter())
    return line, report


def test_sala_sound_run_is_correct_and_restores_its_histories(sala_root):
    line, report = _run(sala_root, trace=True)
    assert line["correct"] is True, report["checks"]
    assert line["attempted"] == 6 and line["failed"] == 0
    names = [c["name"] for c in report["checks"]]
    assert set(LIMITS) <= set(names) and "control" not in report
    assert "histories_without_a_snapshot_at_their_end" in names
    assert "state_dtype_differs" in names
    assert "allocator_or_cache_inconsistent" in names
    # the engine's logits where each sampled answer begins, against the
    # reference's rows: 3 requests, 6 tokens each
    assert len(report["positions"]["err"]) == 18
    assert max(report["positions"]["err"]) <= 1e-4
    # the state each sampled prompt leaves: 3 requests x 2 state layers
    # x the slowest head
    assert len(report["positions"]["state"]) == 6
    assert max(report["positions"]["state"]) <= 1e-5
    # host-side readers report: every turn restored its whole history,
    # every attention row selected, each selected block fetched once;
    # what needs the chip's trace is left out
    m = line["metrics"]
    assert m["state_restored_share.serve"]["value"] == 100.0
    host = {"state_restored_share.serve"}
    for name in NEW_METRICS:
        assert (name in m) == (name in host), name
    assert set(line["metrics"]) >= {"engine_step_ms.serve"}


def test_the_references_own_selection_reads_the_same_in_float32(sala_root):
    """Float32 on both sides orders the blocks alike: the reading with
    the reference's OWN selection at the probed positions is the sound
    one too (on the chip the two readings differ: PERF.md section 2)."""
    line, report = _run(sala_root, overrides={"reference_selection": "own"})
    assert line["correct"] is True, report["checks"]
    assert max(report["positions"]["err"]) <= 1e-4


# what holds each control: the logits of a row (everything that moves
# them) or the slow heads' state (what the lightning layers carry)
CONTROLS = {"fp8": ({"control_lowp": "fp8"}, "probe_logit_err_mean"),
            "dense": ({"control_attend": "dense"}, "probe_logit_err_mean"),
            "no_forced": ({"control_forced": "dropped"},
                          "probe_logit_err_mean"),
            "restore_zeros": ({"control_restore": "zeros"},
                              "state_err_slow_mean"),
            "no_decay": ({"control_decay": "dropped"}, "state_err_slow_mean"),
            "no_gate": ({"control_gate": "dropped"}, "probe_logit_err_mean")}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_sala_control_comes_out_not_correct(sala_root, control):
    overrides, held_by = CONTROLS[control]
    line, report = _run(sala_root, overrides=overrides)
    assert line["correct"] is False, report
    c = report["control"]
    # the run beside it was sound: only the control strays
    assert c["sound_widest"] <= 1e-3 and c["sound_logit_err"] <= 1e-4
    assert c["sound_state_err"] <= 1e-5
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert held_by in bad and set(bad) <= set(LIMITS)
    # the cell's end-to-end metrics
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s",
                                    "setup_s"}


def test_a_token_altered_is_not_correct(sala_root, monkeypatch):
    """A served token that is not the reference's choice lies a whole
    logit's spread under its best."""
    runner = manifest.load_runner(sala_root, "serve_minicpm_sala")
    real = runner.serve.Driver.step

    def step(self):
        real(self)
        for rec in self.recs.values():
            if rec["tokens"] is not None and not rec.get("altered"):
                rec["tokens"] = rec["tokens"].copy()
                rec["tokens"][2] = (rec["tokens"][2] + 1) % 96
                rec["altered"] = True

    monkeypatch.setattr(runner.serve.Driver, "step", step)
    monkeypatch.setattr(manifest, "load_runner", lambda root, name: runner)
    line, report = _run(sala_root)
    assert line["correct"] is False
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert "served_token_gap_widest" in bad


def test_a_state_pool_of_another_type_is_not_correct(sala_root):
    """What holds a state kept in another type is the pools' type
    against the file's ``state_dtype``, exactly.  (The scan takes a
    float32 pool only, so here the FILE states the other type.)"""
    path = sala_root / "benchmarks/configs/tiny-sala.json"
    sound = path.read_text()
    path.write_text(json.dumps({**json.loads(sound),
                                "state_dtype": "bfloat16"}))
    try:
        line, report = _run(sala_root)
    finally:
        path.write_text(sound)
    assert line["correct"] is False
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert bad == ["state_dtype_differs"]


def test_a_row_made_dense_is_not_correct(sala_root):
    """A program whose rows attend their whole context where the file
    says they select (here: the file's ``dense_len`` lowered under the
    program's feet, so that the REFERENCE selects where the engine was
    dense)."""
    path = sala_root / "benchmarks/configs/tiny-sala.json"
    sound = json.loads(path.read_text())
    runner = manifest.load_runner(sala_root, "serve_minicpm_sala")
    real = runner.model_config

    def dense_engine(cfg):
        return real({**cfg, "sparse_config": {**cfg["sparse_config"],
                                              "dense_len": 90}})

    runner.model_config = dense_engine
    orig = manifest.load_runner
    manifest.load_runner = lambda root, name: runner
    try:
        line, report = _run(sala_root)
    finally:
        manifest.load_runner = orig
        path.write_text(json.dumps(sound))
    assert line["correct"] is False
    bad = [r["name"] for r in report["checks"] if not r["ok"]]
    assert "probe_logit_err_mean" in bad


def test_the_real_sala_cell_loads_with_its_readers():
    cell = manifest.load_cell(ROOT, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["runner"] == "serve_minicpm_sala"
    assert {m["name"] for m in cell.end_to_end} >= {
        "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert set(cfg["check"]) == set(LIMITS)
    assert cfg["state_dtype"] == "float32"
    per_layer = {m["name"]: m for m in cell.per_layer}
    for name in NEW_METRICS:
        assert callable(readers.find_reader(ROOT, per_layer[name]["reader"]))
    assert {m["moves"] for m in cell.per_layer} \
        <= {m["name"] for m in cell.end_to_end}
    # the accepted metrics the cell takes part in (not paged_attn_ms: the
    # traced window holds no dense row; not Nemotron's scan roofline: its
    # reader is by that model's keys)
    assert set(per_layer) - set(NEW_METRICS) == {
        "engine_step_ms.serve", "device_idle.serve", "host_pack_ms.serve",
        "host_commit_ms.serve", "batch_occupancy.serve",
        "head_sample_ms.serve", "compiler_ops_ms.serve",
        "unscoped_device_share.serve", "decode_launch_device_ms.serve",
        "attn_proj_ms.serve", "dense_mlp_ms.serve", "kv_scatter_ms.serve",
        "state_restored_share.serve", "ssd_scan_ms.serve"}
    # no width differs from the published row; the cut is in `reduced`
    man = manifest.load_manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"]) == [
        "num_hidden_layers"]
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
    assert (model.hidden_size, model.intermediate_size, model.vocab_size,
            model.num_attention_heads, model.num_key_value_heads,
            model.head_dim, model.lightning_nh, model.lightning_head_dim,
            model.num_hidden_layers, model.layers_run, model.topk,
            model.block_size, model.dense_len, model.window_size) == \
        (4096, 16384, 73448, 32, 2, 128, 32, 128, 32, (9, 21), 64, 64, 8192,
         2048)
    assert [cfg["mixer_types"][l] for l in model.layers] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 2
        + ["lightning-attn"] * 3)
    from benchmarks.harness import traffic, weights_minicpm_sala as w

    mix = cell.traffic
    assert traffic.longest_request_tokens(mix) == 65536 + 4096 + 1024 \
        == cfg["engine"]["max_seq_len"]
    assert (mix["prefix"], mix["schedule_seed"], mix["drain_s"],
            mix["check_sample"], mix["trace_s"]) == (
        {"pool": 4, "tokens": 65536}, 0, 60, 3, 3.0)
    shapes = {f"model.layers.{l}.{n}": s for l in model.layers
              for n, s in w.layer_shapes(cfg, cfg["mixer_types"][l]).items()}
    shapes.update(w.top_shapes(cfg))
    assert shapes == model.leaf_shapes()
    assert w.count(cfg) == sum(math.prod(s) for s in shapes.values()) \
        == 3_930_007_808
    # the pools' arithmetic: a slot's state is 2.097 MB of float32 a
    # layer, 9 layers; 129 entries; a page of 128 tokens x 2 KV heads x
    # 128 x K and V x 2 B is 128 KiB and 8 x 256 x 2 B = 4 KiB of
    # compressed keys, 3 layers
    e = cfg["engine"]
    entry_bytes = 9 * 32 * 128 * 128 * 4
    assert entry_bytes == 18_874_368
    assert (e["max_slots"] + e["state_snapshots"] + 1) * entry_bytes \
        == 2_434_793_472
    assert e["num_pages"] * 3 * (128 + 4) * 1024 == 1_660_944_384


def test_the_saturated_cell_is_data_beside_the_chat_cell():
    cell = manifest.load_cell(ROOT, SATURATED)
    chat = manifest.load_cell(ROOT, "mistral7b-serve-l16.chat")
    assert cell.config == chat.config and cell.chips == 1
    mix, base = cell.traffic, chat.traffic
    assert mix["arrivals"] == {"process": "closed", "clients": 48,
                               "pool": 2048}
    for k in ("prefix", "user_tokens", "max_new_tokens", "schedule_seed",
              "drain_s", "check_sample", "trace_s"):
        assert mix[k] == base[k], k
    assert "ttft_p95_ms" not in {m["name"] for m in cell.end_to_end}
    assert {m["name"] for m in cell.end_to_end} >= {"serve_tokens_per_s",
                                                    "setup_s"}
    # every per-layer metric it lists is one the chat cell lists
    assert {m["name"] for m in cell.per_layer} \
        <= {m["name"] for m in chat.per_layer}
    assert cell.per_layer
    from benchmarks.harness import traffic

    reqs = traffic.serve_requests(mix, 5, 51.0, 32768)["requests"]
    assert len(reqs) == 2048 and reqs[0]["due"] is None
    assert len({r["prompt"].tobytes() for r in reqs}) == 2048


def test_block_sparse_roofline_reader_counts_least_work():
    import importlib

    mod = importlib.import_module(
        "benchmarks.readers.block_sparse_roofline_pct")
    cfg = manifest.load_cell(ROOT, CELL).config
    from benchmarks.harness import peaks

    peak = peaks.peaks_for("TPU v5 lite")
    # 60 decode rows of 60 slots at a context of 65,600 tokens
    nck = 65600 // 16 - 1
    counts = [{"sparse_rows": 60, "ckey_ctx": 60 * nck,
               "ckey_slot_ctx": 60 * nck, "sel_blocks": 60 * 2 * 64,
               "sel_kv_tokens": 60 * 2 * 64 * 64}]
    s, bound, flops_s, bytes_s = mod.least_seconds(cfg, counts, "TPU v5 lite",
                                                   "scores")
    assert flops_s == 2 * 32 * 128 * 60 * nck * 3 / peak["bf16_flops_per_s"]
    assert bytes_s == (2 * 128 * 2 * 60 * nck + 32 * 128 * 2 * 60) * 3 \
        / peak["hbm_bytes_per_s"]
    assert s == bytes_s and bound == "bytes"
    s, bound, flops_s, bytes_s = mod.least_seconds(cfg, counts, "TPU v5 lite",
                                                   "attn")
    assert flops_s == 4 * 16 * 128 * 64 * 7680 * 3 / peak["bf16_flops_per_s"]
    assert bytes_s == (2 * 128 * 2 * 7680 * 64 + 2 * 32 * 128 * 2 * 60) * 3 \
        / peak["hbm_bytes_per_s"]
    assert s == bytes_s and bound == "bytes"        # 251 MB a layer
    assert round(bytes_s * 1e3, 2) == 0.93
    # a program that writes no such counts: nothing to read, no error
    assert mod.least_seconds(cfg, [{"rows": 1}], "TPU v5 lite", "attn") is None
    assert mod.read({"trace": None}, "infllm_block_scores", "scores") is None


def test_lightning_scan_roofline_reader_counts_least_work():
    import importlib

    mod = importlib.import_module(
        "benchmarks.readers.lightning_scan_roofline_pct")
    cfg = manifest.load_cell(ROOT, CELL).config
    from benchmarks.harness import peaks

    peak = peaks.peaks_for("TPU v5 lite")
    counts = [{"state_rows": 572, "state_slots": 61}]
    s, bound, flops_s, bytes_s = mod.least_seconds(cfg, counts, "TPU v5 lite")
    assert flops_s == 4 * 32 * 128 * 128 * 572 * 9 / peak["bf16_flops_per_s"]
    row = 32 * 128 * (3 * 2 + 4)
    assert bytes_s == (2 * 2097152 * 61 + row * 572) * 9 \
        / peak["hbm_bytes_per_s"]
    assert s == bytes_s and bound == "bytes"
    assert mod.least_seconds(cfg, [{"rows": 1}], "TPU v5 lite") is None
    assert mod.read({"trace": None}, "mamba2_ssd_scan") is None
