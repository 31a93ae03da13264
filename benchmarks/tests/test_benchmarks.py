"""Rehearsal of the benchmark off the chip: both runners end to end at
a tiny size (interpret-mode kernels, the device expectations injected
here), the train runner's mesh branch on four virtual CPU devices, the
controls and the broken-path runs that must come out as not correct,
the loader's refusals, and the yardstick's arithmetic."""

import copy
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (context, flops, manifest, peaks, readers,  # noqa: E402
                                stats, trace_reduce, traffic, weights)
from benchmarks.reference import decoder_ref  # noqa: E402

CPU = context.Target(platform="cpu", trace_device=False)
RECORDED = pathlib.Path(__file__).with_name("recorded_v5e.xplane.pb")

TINY = {"model_type": "mistral", "hidden_act": "silu", "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
        "max_position_embeddings": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-05, "sliding_window": None,
        "tie_word_embeddings": False, "num_hidden_layers": 2}


def _write(path: pathlib.Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout of its own: the real harness, runners and per-layer
    readers, with throw-away cells, configurations and mixes added the
    way a later PR adds them: as files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "benchmarks"
    _write(b / "configs/tiny-serve.json", {
        **TINY, "name": "tiny-serve", "torch_dtype": "float32",
        "runner": "serve", "chips": 1,
        "check": {"served_token_gap_widest": 0.5, "served_token_gap_mean": 0.02},
        "engine": {"max_slots": 3, "max_seq_len": 96, "num_pages": 19,
                   "page_size": 16, "prefill_token_budget": 16,
                   "enable_prefix_cache": True, "cache_dtype": "float32"}})
    job = {"optimizer": "adamw", "lr": 1e-3, "beta1": 0.9, "beta2": 0.95,
           "eps": 1e-8, "weight_decay": 0.1, "multi_precision": True,
           "compute_dtype": "bfloat16", "remat": False, "mesh": None}
    # limits for this size (a 64-wide leaf averages less than a 4096-wide)
    check = {"loss_gap_worst_step": 0.01, "first_grad_norm_gap_worst_leaf": 0.02,
             "first_grad_sample_gap_worst_leaf": 0.05,
             "param_change_norm_gap_worst_leaf": 0.1}
    _write(b / "configs/tiny-train.json", {
        **TINY, "name": "tiny-train", "torch_dtype": "bfloat16",
        "runner": "train", "chips": 1, "job": job, "check": check})
    _write(b / "configs/tiny-train-s2mp2.json", {
        **TINY, "name": "tiny-train-s2mp2", "torch_dtype": "bfloat16",
        "runner": "train", "chips": 4, "check": check,
        "job": {**job, "mesh": {"sharding": 2, "mp": 2}}})
    _write(b / "traffic/tiny-chat.json", {
        "kind": "serve", "arrivals": {"process": "poisson", "rate_per_s": 4.0},
        "prefix": {"pool": 2, "tokens": 32},
        "user_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                        "min": 3, "max": 24},
        "max_new_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                           "min": 3, "max": 12},
        "drain_s": 60, "check_sample": 3, "trace_s": 1.0})
    _write(b / "traffic/tiny-pretrain.json", {
        "kind": "train", "micro_batch": 2, "seq_len": 128, "accum": 2,
        "trace_s": 1.0})
    src = "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json"
    for name in ("tiny-serve", "tiny-train", "tiny-train-s2mp2"):
        man["configs"].append({"name": name, "source": src,
                               "file": f"benchmarks/configs/{name}.json",
                               "reduced": [], "why": "rehearsal"})
    cells = {"tiny-serve.chat": ("tiny-serve", "tiny-chat", 1),
             "tiny-train.pretrain": ("tiny-train", "tiny-pretrain", 1),
             "tiny-train-s2mp2.pretrain": ("tiny-train-s2mp2", "tiny-pretrain", 4)}
    for name, (cfg, mix, chips) in cells.items():
        man["workloads"].append({"name": name, "config": cfg, "traffic": mix,
                                 "chips": chips, "why": "rehearsal"})
    # the training metrics come as data too (their readers' files are here)
    names = {m["name"] for m in man["end_to_end"] + man["per_layer"]}
    if "train_tokens_per_s" not in names:
        man["end_to_end"].append({
            "name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
            "bound": 0.05, "source": "host_clock", "workloads": []})
    for name in ("step_ms.train", "mfu.train", "device_idle.train"):
        if name not in names:
            spec = json.loads((b / f"layer_metrics/{name}.json").read_text())
            man["per_layer"].append({
                "name": name, "unit": spec["unit"], "better": "lower",
                "source": "host_clock", "layer": spec["layer"],
                "moves": spec["moves"], "workloads": []})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            kind = "train" if "train" in m["name"] else "serve"
            m["workloads"] += [c for c in cells if kind in c]
    _write(root / "BENCHMARK.json", man)
    return root


def _run(root, cell, trace=False, devices=None, overrides=None, seconds=3.0,
         seed=2**31 + 11):
    return bench_run.run_cell(root, cell, seed, seconds, trace, target=CPU,
                              devices=devices or jax.devices()[:1],
                              overrides=overrides,
                              t_process=time.perf_counter())


LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


# --------------------------------------------------------------------------
# the runners, end to end
# --------------------------------------------------------------------------

def test_serve_runner_end_to_end_and_control(tiny_root):
    line = _run(tiny_root, "tiny-serve.chat",
                overrides={"control_lowp": "fp8"})
    assert set(line) == LINE_KEYS and line["correct"] is True, line
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                    "serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    json.dumps(line)


def test_serve_runner_traced_reports_per_layer_metrics(tiny_root):
    line = _run(tiny_root, "tiny-serve.chat", trace=True)
    assert line["correct"] is True
    # off the chip there is no device plane, so the device metric is left out
    assert set(line["metrics"]) == {"engine_step_ms.serve",
                                    "prefix_hit_share.serve"}
    assert 0 < line["metrics"]["prefix_hit_share.serve"]["value"] < 100


def test_serve_is_not_correct_when_a_token_is_altered(tiny_root, monkeypatch):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine as E

    real = E._sample_row
    monkeypatch.setattr(
        E, "_sample_row",
        lambda self, row, req: (real(self, row, req) + 1) % len(row))
    line = _run(tiny_root, "tiny-serve.chat")
    assert line["correct"] is False


def test_train_runner_end_to_end(tiny_root):
    # a traced run reads mfu against the published peak of the device
    # it ran on: a device that is not in the table is an error
    with pytest.raises(KeyError, match="no published peaks for device kind 'cpu'"):
        _run(tiny_root, "tiny-train.pretrain", trace=True)
    line = _run(tiny_root, "tiny-train.pretrain")
    assert set(line) == LINE_KEYS and line["correct"] is True, line
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_train_runner_mesh_branch_on_four_virtual_devices(tiny_root):
    line = _run(tiny_root, "tiny-train-s2mp2.pretrain",
                devices=jax.devices()[:4])
    assert line["correct"] is True, line
    assert line["device"]["count"] == 4


def test_train_is_not_correct_when_the_step_returns_its_state(tiny_root):
    def unchanged(step):
        def wrapped(params, opt_state, i, lr, ids, labels):
            loss, _, _ = step(jax.tree_util.tree_map(lambda x: x.copy(), params),
                              jax.tree_util.tree_map(lambda x: x.copy(), opt_state),
                              i, lr, ids, labels)
            return loss, params, opt_state
        return wrapped

    line = _run(tiny_root, "tiny-train.pretrain",
                overrides={"wrap_step": unchanged})
    assert line["correct"] is False


def test_train_control_in_fp8_is_not_correct(tiny_root):
    """The reference in the program's place, one precision below bf16."""
    cell = manifest.load_cell(tiny_root, "tiny-train.pretrain")
    cfg, mix = cell.config, cell.traffic
    from benchmarks.runners import train as train_runner

    ref = train_runner.follow_reference(5, cfg, mix)
    low = train_runner.follow_reference(5, cfg, mix, lowp="fp8")
    gap = train_runner.worst_sample_gap(low["grad_samples"], ref["grad_samples"])
    print("fp8 control sample gap", gap, "norm gap", train_runner.worst_leaf_gap(
        low["grad_norms"], ref["grad_norms"]))
    assert gap > cfg["check"]["first_grad_sample_gap_worst_leaf"], gap


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload",
         "mistral7b-serve-l16.chat", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


# --------------------------------------------------------------------------
# the loader
# --------------------------------------------------------------------------

def test_loader_finds_the_real_cells():
    man = manifest.load_manifest(ROOT)
    for w in man["workloads"]:
        cell = manifest.load_cell(ROOT, w["name"])
        assert cell.config["runner"] in ("serve", "train")
        assert cell.per_layer and cell.end_to_end
        manifest.load_runner(ROOT, cell.config["runner"])
        for m in cell.per_layer:
            assert m["reader"] in readers.READERS


@pytest.mark.parametrize("missing", [
    "benchmarks/configs/tiny-serve.json", "benchmarks/traffic/tiny-chat.json",
    "benchmarks/layer_metrics/engine_step_ms.serve.json"])
def test_loader_refuses_a_cell_with_a_missing_file(tiny_root, missing):
    (tiny_root / missing).unlink()
    with pytest.raises(manifest.ManifestError, match="no file"):
        manifest.load_cell(tiny_root, "tiny-serve.chat")


@pytest.mark.parametrize("field,value", [
    ("name", "has space"), ("name", "a/b"), ("name", "x" * 65),
    ("unit", "tokens per s"), ("unit", "µs"), ("better", "faster")])
def test_loader_refuses_bad_names_and_units(tiny_root, field, value):
    man = json.loads((tiny_root / "BENCHMARK.json").read_text())
    man["end_to_end"][0][field] = value
    _write(tiny_root / "BENCHMARK.json", man)
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell(tiny_root, "tiny-serve.chat")


def test_a_reader_can_be_added_as_a_file(tiny_root):
    (tiny_root / "benchmarks/readers").mkdir()
    (tiny_root / "benchmarks/readers/queue_peak.py").write_text(
        "def read(obs, counter):\n    return obs['counters'].get(counter)\n")
    per_layer = [{"name": "queue_peak.serve", "unit": "requests",
                  "reader": "queue_peak", "args": {"counter": "peak"}}]
    assert readers.read_all(tiny_root, per_layer, {"counters": {"peak": 7}}) == \
        {"queue_peak.serve": {"value": 7.0, "unit": "requests"}}
    assert readers.read_all(tiny_root, per_layer, {"counters": {}}) == {}
    with pytest.raises(KeyError, match="no reader"):
        readers.find_reader(tiny_root, "nope")


def test_loader_refuses_an_unknown_workload_and_runner(tiny_root):
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load_cell(tiny_root, "nope")
    with pytest.raises(manifest.ManifestError, match="no runner"):
        manifest.load_runner(tiny_root, "nope")


# --------------------------------------------------------------------------
# the yardstick's arithmetic
# --------------------------------------------------------------------------

def test_percentile_gap_and_due_time_arithmetic():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
    # a hand-made schedule: t0 = 100, window 10 s, drain limit 30 s
    reqs = [
        {"due": 100.0, "sent": 100.5, "emit": [101.0, 101.5, 102.5], "want": 3},
        {"due": 104.0, "sent": 104.0, "emit": [104.2, 111.0], "want": 2},
        {"due": 109.0, "sent": 109.1, "emit": [], "want": 4},          # never ran
        {"due": 109.5, "sent": 109.5, "emit": [109.9], "want": 2},     # cut off
    ]
    s = stats.serving_summary(reqs, 100.0, 10.0, 30.0)
    assert s["requests"] == 4 and s["failed"] == 2
    # TTFT from the DUE time: 1.0, 0.2, 30 (limit), 0.4 -> p95 by interpolation
    assert s["ttft_p95_ms"] == pytest.approx(
        stats.percentile([1.0, 0.2, 30.0, 0.4], 95) * 1e3)
    # gaps: 0.5, 1.0 | 6.8 | 30 (limit) | 30 (limit)
    assert s["n_gaps"] == 5
    assert s["itl_p95_ms"] == pytest.approx(30e3)
    assert s["itl_p50_ms"] == pytest.approx(6.8e3)
    # tokens emitted inside [100, 110): 3 + 1 + 0 + 1
    assert s["serve_tokens_per_s"] == pytest.approx(0.5)
    assert s["sent_late_max_ms"] == pytest.approx(500.0)
    t = stats.training_summary([1.5, 2.0, 2.5], 1.0, 1000)
    assert t["train_tokens_per_s"] == pytest.approx(2000.0)
    assert t["step_p50_ms"] == pytest.approx(500.0)
    slow = stats.training_summary([1.5, 2.0, 2.5, 4.0], 1.0, 1000)
    assert slow["step_max_ms"] == pytest.approx(1500.0)
    assert slow["slow_steps"] == [(3, 1500.0)]


def test_flops_match_the_published_shapes():
    cfg = json.loads((ROOT / "benchmarks/configs/mistral-7b-v0.3-train-l2.json"
                      ).read_text())
    assert flops.layer_matmul_params(cfg) == 218_103_808
    assert flops.total_params(cfg) == 704_663_552
    assert flops.train_flops_per_token(cfg, 2048) / 1e9 == pytest.approx(3.52, abs=0.005)
    l8 = {**cfg, "num_hidden_layers": 8}
    assert flops.train_flops_per_token(l8, 4096) / 1e9 == pytest.approx(12.1, abs=0.05)
    l32 = {**cfg, "num_hidden_layers": 32}
    assert flops.total_params(l32) / 1e9 == pytest.approx(7.25, abs=0.005)
    peak = peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    assert flops.mfu_pct(28_000, 3.52e9, 1, peak) == pytest.approx(50.03, abs=0.01)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")


def test_traffic_gives_every_seed_the_same_work():
    mix = json.loads((ROOT / "benchmarks/traffic/chat.json").read_text())
    a = traffic.serve_requests(mix, 1, 40.0, 32768)["requests"]
    b = traffic.serve_requests(mix, 2**31 + 7, 40.0, 32768)["requests"]
    assert len(a) == len(b) == round(mix["arrivals"]["rate_per_s"] * 40)
    # the mix fixes its schedule: the same lengths at the same times, and
    # other tokens
    assert [(r["due"], len(r["prompt"]), r["max_new"]) for r in a] == \
        [(r["due"], len(r["prompt"]), r["max_new"]) for r in b]
    assert not any((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    # a mix without a schedule of its own: the same set, in another order
    free = {k: v for k, v in mix.items() if k != "schedule_seed"}
    c = traffic.serve_requests(free, 2, 40.0, 32768)["requests"]
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in c)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in c)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    due = [r["due"] for r in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0
    assert max(len(r["prompt"]) + r["max_new"] for r in a) <= \
        traffic.longest_request_tokens(mix) == 2816
    again = traffic.serve_requests(mix, 1, 40.0, 32768)["requests"]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, again))
    burst = traffic.arrival_times({"process": "onoff", "on_s": 2, "off_s": 4},
                                  60, 12.0, np.random.default_rng(0))
    assert all(t % 6.0 < 2.0 for t in burst) and burst[-1] < 12.0
    ids, labels = traffic.train_batch({"micro_batch": 2, "seq_len": 8, "accum": 2},
                                      3, 0, 100)
    assert ids.shape == (2, 2, 8) and (labels[..., :-1] == ids[..., 1:]).all()


# --------------------------------------------------------------------------
# the trace reduction
# --------------------------------------------------------------------------

def test_trace_reduce_on_a_synthetic_event_list():
    ev = [("fusion.1", 0, 40), ("while", 50, 100), ("fusion.2", 60, 30),
          ("all-gather-start.3", 100, 20), ("fusion.1", 200, 50)]
    assert trace_reduce.busy_ns(ev) == 40 + 100 + 50
    assert trace_reduce.idle_share(ev, (0, 300)) == pytest.approx(1 - 190 / 300)
    assert trace_reduce.idle_share(ev, (20, 220)) == pytest.approx(1 - 140 / 200)
    st = trace_reduce.self_times(ev)
    assert st == {"fusion.1": 90, "while": 50, "fusion.2": 30,
                  "all-gather-start.3": 20}
    assert trace_reduce.top_ops(ev, 2) == [["fusion.1", 90 / 1e9],
                                           ["while", 50 / 1e9]]
    assert trace_reduce.collective_share(ev) == pytest.approx(20 / 190)
    spans = [("engine.step", 0, 160), ("pack", 35, 10), ("bookkeeping", 160, 30)]
    gaps = dict(trace_reduce.idle_gaps(ev, spans, (0, 300)))
    # idle: [40,45) under pack, which nests in engine.step; [45,50) and
    # [150,160) under engine.step; [160,190) under bookkeeping; [190,200)
    # and [250,300) under no span
    assert gaps == {"pack": pytest.approx(5 / 1e9),
                    "engine.step": pytest.approx(15 / 1e9),
                    "bookkeeping": pytest.approx(30 / 1e9),
                    "(no span)": pytest.approx(60 / 1e9)}
    assert trace_reduce.op_name(
        '%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop') == "fusion"
    assert trace_reduce.op_name(
        '%step.3 = bf16[8] custom-call(bf16[8] %p), custom_call_target='
        '"tpu_custom_call"') == "step (pallas)"
    red = trace_reduce.reduce_trace(
        {"devices": {"/device:TPU:0": ev, "/device:TPU:1": ev[:1]},
         "host": [("traced_window", 0, 300), *spans]}, "traced_window")
    assert dict(red["idle_gaps"])["pack"] == pytest.approx(5 / 1e9)
    assert red["window_s"] == pytest.approx(300 / 1e9)
    assert red["busy_s"] == pytest.approx((190 + 40) / 2 / 1e9)
    assert red["idle_share"] == pytest.approx(1 - 115 / 300)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace beside the test")
def test_trace_reduce_on_a_recorded_v5e_trace():
    trace = trace_reduce.load_xplane(RECORDED, ["traced_window", "train.step",
                                                "next_batch"])
    assert list(trace["devices"]) == ["/device:TPU:0"]
    red = trace_reduce.reduce_trace(trace, "traced_window")
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 <= red["idle_share"] < 1
    assert red["device_ops"] and red["device_ops"][0][1] > 0
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=0.05)


# --------------------------------------------------------------------------
# the plain reference against the program's model
# --------------------------------------------------------------------------

def test_decoder_ref_matches_llama_for_causal_lm():
    import jax.numpy as jnp

    from paddle_tpu.autograd import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import LlamaForCausalLM
    from benchmarks.runners.serve import llama_config

    cfg = {**TINY, "torch_dtype": "float32"}
    params = weights.draw_params(cfg, 2**31 + 3, jnp.float32)
    model = LlamaForCausalLM(llama_config(cfg))
    for name, p in model.named_parameters():
        p.set_value(params[name])
    ids = np.random.default_rng(0).integers(0, 512, (2, 128)).astype(np.int32)
    with no_grad():
        got = np.asarray(model.functional_call(model.functional_state(),
                                               Tensor(ids))._value)
    want = np.asarray(decoder_ref.forward(params, jnp.asarray(ids), cfg))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    rows = np.array([5, 90, 127])
    np.testing.assert_allclose(
        decoder_ref.logits_at(params, ids[0], rows, cfg), want[0, rows],
        rtol=0, atol=2e-5)
    assert not np.allclose(
        np.asarray(decoder_ref.forward(params, jnp.asarray(ids), cfg, lowp="fp8")),
        want, atol=1e-4)
