"""What ``benchmarks/tests/test_benchmarks.py::
test_serve_is_not_correct_when_a_token_is_altered`` meant, held here
since PR 29: that test alters a token in ``_sample_row``, where the host
sampled it; greedy tokens are sampled on the device now and the engine
commits them in ``_commit_unified``, so the alteration is made there.
Only a ``benchmark`` PR may re-point the test itself (PERF.md section
7)."""

import numpy as np

from benchmarks.tests.test_benchmarks import _run, tiny_root  # noqa: F401


def test_serve_is_not_correct_when_a_committed_token_is_altered(
        tiny_root, monkeypatch):  # noqa: F811
    from paddle_tpu.inference.serving import ContinuousBatchingEngine as E

    real = E._commit_unified

    def altered(self, launch, tokens, logits):
        bad = (np.asarray(tokens) + 1) % self.cfg.vocab_size
        return real(self, launch, bad, logits)

    monkeypatch.setattr(E, "_commit_unified", altered)
    line = _run(tiny_root, "tiny-serve.chat")
    assert line["correct"] is False
    monkeypatch.undo()
    assert _run(tiny_root, "tiny-serve.chat")["correct"] is True


# PR 30: the Mellum2 cell's rehearsal (runner ``serve_mellum2`` at a tiny
# size with two kinds of page, its four controls, its files and its
# reader) runs with the tier-1 tests too
from benchmarks.tests.test_mellum2_cell import (  # noqa: E402,F401
    mellum2_root, test_a_mellum2_control_comes_out_not_correct,
    test_kind_roofline_reader_counts_least_work_by_kind,
    test_mellum2_sound_run_is_correct_and_hits_beyond_the_window,
    test_the_real_mellum2_cell_loads_with_its_readers)


# PR 33: the Nemotron-3-Super cell's rehearsal (runner ``serve_nemotron_h``
# at a tiny size with a recurrent state beside its pages, its five
# controls, its files and its reader) runs with the tier-1 tests too
from benchmarks.tests.test_nemotron_h_cell import (  # noqa: E402,F401
    nemotron_root, test_a_nemotron_control_comes_out_not_correct,
    test_a_state_pool_of_another_type_is_not_correct,
    test_nemotron_sound_run_is_correct_and_restores_its_preamble,
    test_scan_roofline_reader_counts_least_work)


# PR 37: two accepted tests hold a CLOSED list of the per-layer metrics
# that list their cell, and PR 37's metrics of the device's time by scope
# list both cells.  Only a ``benchmark`` PR may edit those files (PERF.md
# section 7), so what each meant is held here with PR 37's names beside
# the accepted ones: still closed, so a metric that lists a cell by
# mistake is seen.
BY_SCOPE = {"attn_proj_ms.serve", "kv_scatter_ms.serve",
            "head_sample_ms.serve", "moe_dispatch_ms.serve",
            "compiler_ops_ms.serve", "unscoped_device_share.serve",
            "decode_launch_device_ms.serve"}


def test_the_mellum2_cell_is_not_under_the_one_kind_roofline():
    from benchmarks.harness import manifest
    from benchmarks.tests import test_mellum2_cell as accepted

    man = manifest.load_manifest(accepted.ROOT)
    listed = {m["name"]: m for m in man["per_layer"]
              if accepted.CELL in m["workloads"]}
    assert "paged_attn_roofline.serve" not in listed
    for name in accepted.NEW_METRICS:
        assert listed[name]["workloads"] == [accepted.CELL]
        assert listed[name]["moves"] == "ttft_p95_ms"
    assert set(listed) - set(accepted.NEW_METRICS) == {
        "engine_step_ms.serve", "prefix_hit_share.serve", "device_idle.serve",
        "host_pack_ms.serve", "host_commit_ms.serve", "batch_occupancy.serve",
        "prefill_backlog.serve", "queue_wait_ms.serve", "paged_attn_ms.serve",
        "chunk_launch_device_ms.serve"} | BY_SCOPE
    assert {m["moves"] for m in listed.values()} == {"ttft_p95_ms",
                                                     "itl_p95_ms"}


def test_the_real_nemotron_cell_loads_with_its_readers(monkeypatch):
    """The accepted test itself, with PR 37's metrics of this cell taken
    off its closed list as its own ``NEW_METRICS`` are (each must have a
    reader and move a metric the cell reports, as they must)."""
    from benchmarks.tests import test_nemotron_h_cell as accepted

    monkeypatch.setattr(accepted, "NEW_METRICS", (
        *accepted.NEW_METRICS, *sorted(BY_SCOPE), "moe_dense_ms.serve",
        "mamba_proj_ms.serve", "mamba_conv_ms.serve"))
    accepted.test_the_real_nemotron_cell_loads_with_its_readers()


# PR 39: the MiniCPM-SALA cell's rehearsal (runner ``serve_minicpm_sala``
# at a tiny size with three pools a page and a recurrent state, its six
# controls, a token altered, a state pool of another type, a row made
# dense, its files and its readers) and the saturated Mistral cell's
# files run with the tier-1 tests too
from benchmarks.tests.test_minicpm_sala_cell import (  # noqa: E402,F401
    sala_root, test_a_row_made_dense_is_not_correct,
    test_a_sala_control_comes_out_not_correct,
    test_a_token_altered_is_not_correct,
    test_block_sparse_roofline_reader_counts_least_work,
    test_lightning_scan_roofline_reader_counts_least_work,
    test_sala_sound_run_is_correct_and_restores_its_histories,
    test_the_real_sala_cell_loads_with_its_readers,
    test_the_references_own_selection_reads_the_same_in_float32,
    test_the_saturated_cell_is_data_beside_the_chat_cell)
from benchmarks.tests.test_minicpm_sala_cell import (  # noqa: E402,F401
    test_a_state_pool_of_another_type_is_not_correct as
    test_a_sala_state_pool_of_another_type_is_not_correct)


def test_the_saturated_mix_runs_closed_loop_through_runner_serve(
        tiny_root):  # noqa: F811
    """The accepted rehearsal's tiny Llama cell under a closed-loop mix of
    the saturated cell's form: clients keep the engine full, every
    request sent finishes or is cut by the drain, and ``correct`` holds."""
    import json

    b = tiny_root / "benchmarks"
    chat = json.loads((b / "traffic/tiny-chat.json").read_text())
    (b / "traffic/tiny-saturated.json").write_text(json.dumps({
        **chat, "arrivals": {"process": "closed", "clients": 5, "pool": 64}}))
    man = json.loads((tiny_root / "BENCHMARK.json").read_text())
    if not any(w["name"] == "tiny-serve.saturated" for w in man["workloads"]):
        man["workloads"].append({"name": "tiny-serve.saturated",
                                 "config": "tiny-serve",
                                 "traffic": "tiny-saturated", "chips": 1,
                                 "why": "rehearsal"})
        for m in man["end_to_end"] + man["per_layer"]:
            if "tiny-serve.chat" in m.get("workloads", []) \
                    and m["name"] != "ttft_p95_ms":
                m["workloads"].append("tiny-serve.saturated")
        (tiny_root / "BENCHMARK.json").write_text(json.dumps(man))
    line = _run(tiny_root, "tiny-serve.saturated")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 5       # (a tiny model cycles the pool)
    assert "ttft_p95_ms" not in line["metrics"]
    assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"])


# PR 46: the Kimi-Linear cell's rehearsal (runner ``serve_kimi_linear`` at
# a tiny size with a KDA state beside latent pages, its five controls, a
# state pool of another type, its files and its readers) and the
# unshared chat cell's files run with the tier-1 tests too
from benchmarks.tests.test_kimi_linear_cell import (  # noqa: E402,F401
    kimi_root, test_a_kimi_control_comes_out_not_correct,
    test_a_kimi_state_pool_of_another_type_is_not_correct,
    test_kimi_roofline_readers_count_least_work,
    test_kimi_sound_run_is_correct_and_restores_its_prompt,
    test_the_real_kimi_cell_loads_with_its_readers,
    test_the_unshared_chat_cell_is_data_beside_the_chat_cell)
