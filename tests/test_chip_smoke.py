"""CPU rehearsal of chip_smoke.py: its phase functions at a tiny size,
with the device expectations injected here (interpret-mode kernels, no
Mosaic custom call in the compiled text), plus the ``--chips 4`` phase on
four virtual CPU devices — so that paths, arguments and the
sharded-vs-single comparison stay sound between chip runs."""

import dataclasses
import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke as cs  # noqa: E402

CPU = cs.Target(interpret=True, kernel_marker=None, min_sharded_bytes=4096)
TINY_TRAIN = cs.TrainSize(name="tiny", vocab=512, hidden=64, inter=128,
                          heads=4, kv_heads=2, full_layers=2, layers=2,
                          batch=2, seq=128, accum=2, steps=3)
TINY_SERVE = cs.ServeSize(name="tiny", vocab=512, hidden=64, inter=128,
                          heads=4, kv_heads=2, full_layers=2, bf16_layers=2,
                          int8_layers=2, page=16, max_seq_len=128, slots=3,
                          num_pages=25, prefill_budget=16, prefix_len=20,
                          suffix_lens=(5, 9, 3, 12), max_new=4)


@pytest.fixture(scope="module")
def clock():
    return cs.CompileClock()


def test_kernels_phase_rehearsal():
    cs.phase_kernels(cs.KernelShapes(
        heads=4, kv_heads=2, head_dims=(64,), batch=1, seq=256, page=16,
        pages_per_seq=4, slots=3, prefill_rows=8), CPU)


def test_train_phase_rehearsal(clock):
    losses = cs.run_train(TINY_TRAIN, CPU, clock)
    assert len(losses) == TINY_TRAIN.steps and losses[-1] < losses[0]
    assert clock.total > 0            # the compile clock hears JAX


def test_serve_phase_rehearsal(clock):
    cs.phase_serve(TINY_SERVE, CPU, clock)


def test_multichip_phase_on_four_virtual_devices(clock):
    cs.phase_multichip(TINY_TRAIN,
                       dataclasses.replace(TINY_TRAIN, name="tiny-untied",
                                           tied=False),
                       CPU, clock, jax.devices()[:4])


def test_multichip_phase_refuses_other_device_counts(clock):
    with pytest.raises(AssertionError, match="needs 4 devices"):
        cs.phase_multichip(TINY_TRAIN, TINY_TRAIN, CPU, clock,
                           jax.devices()[:2])


def test_failed_check_fails_the_phase(clock):
    """A phase does not carry on past a failed check: a loss band the
    run cannot meet raises out of it."""
    off = dataclasses.replace(TINY_TRAIN, loss_band=(5.0, 6.0))
    with pytest.raises(AssertionError, match="step-0 loss"):
        cs.run_train(off, CPU, clock)


def test_main_refuses_to_run_without_a_tpu(capsys):
    """On the CPU the script exits non-zero, prints ``"ok": false`` as
    its last line and runs no model."""
    assert cs.main([]) == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": False, "device": None}
    assert len(out) == 1              # no phase printed anything
