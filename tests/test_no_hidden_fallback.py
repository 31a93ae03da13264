"""Nothing on the main paths hides the device: a place with no device
raises, a kernel failure propagates, an unknown device has no peaks, and
the compile cache goes where it is told."""

import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import device as D


def test_tpu_place_without_a_tpu_raises():
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        D.Place("tpu").jax_device
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        paddle.to_tensor(np.zeros(2, np.float32), place="tpu")
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        paddle.to_tensor(np.zeros(2, np.float32)).tpu()
    assert D.Place("cpu").jax_device.platform == "cpu"


def test_one_predicate_answers_is_this_a_tpu():
    class Dev:
        platform = "tpu"

    assert D.is_tpu(Dev()) and not D.is_tpu()
    assert not D.is_compiled_with_tpu()
    assert D.pallas_interpret() is True          # CPU tests interpret
    assert D.current_place().device_type == "cpu"


def _qkv():
    x = paddle.to_tensor(np.ones((1, 128, 2, 32), np.float32))
    return x, x, x


def test_kernel_exception_propagates_out_of_flash_attention(monkeypatch):
    """Only FlashUnsupportedError (a shape outside the kernel's envelope)
    may take the XLA path; anything else from the kernel is raised."""
    from paddle_tpu.incubate.nn import attention as A
    from paddle_tpu.ops.pallas import flash_attention as F

    monkeypatch.setattr(A, "is_tpu", lambda device=None: True)

    def broken(*a, **k):
        raise ZeroDivisionError("kernel regression")

    monkeypatch.setattr(F, "flash_attention_raw", broken)
    with pytest.raises(ZeroDivisionError, match="kernel regression"):
        A.flash_attention(*_qkv(), causal=True)


def test_unsupported_shape_takes_the_xla_path(monkeypatch):
    from paddle_tpu.incubate.nn import attention as A
    from paddle_tpu.ops.pallas import flash_attention as F

    monkeypatch.setattr(A, "is_tpu", lambda device=None: True)

    def outside(*a, **k):
        raise F.FlashUnsupportedError("outside the envelope")

    monkeypatch.setattr(F, "flash_attention_raw", outside)
    out = A.flash_attention(*_qkv(), causal=True)
    assert tuple(out.shape) == (1, 128, 2, 32)
    assert np.isfinite(np.asarray(out._value)).all()


def test_peaks_come_from_device_kind_and_unknown_is_an_error():
    from paddle_tpu.parallel.roofline import (CHIP_SPECS,
                                              DEVICE_KIND_TO_CHIP,
                                              chip_spec_for_device)

    v5e = chip_spec_for_device("TPU v5 lite")
    assert v5e is CHIP_SPECS["v5e"] and v5e.peak_bf16_flops == 197e12
    assert set(DEVICE_KIND_TO_CHIP.values()) <= set(CHIP_SPECS)
    with pytest.raises(KeyError, match="no peaks known"):
        chip_spec_for_device("cpu")
    with pytest.raises(KeyError, match="no peaks known"):
        chip_spec_for_device(jax.devices()[0].device_kind)


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    from jax.experimental.compilation_cache import compilation_cache

    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    root = pathlib.Path(paddle.__file__).resolve().parents[1]
    assert enable_compile_cache() == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_compile_cache_set_from_outside_is_left_alone(monkeypatch, tmp_path,
                                                      cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, no directory is set in code
    and a compile writes there and nowhere else."""
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # what JAX itself does with the variable at start-up
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    root = pathlib.Path(paddle.__file__).resolve().parents[1]
    before = set(os.listdir(root / ".jax_cache")) \
        if (root / ".jax_cache").exists() else set()
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    jax.jit(lambda x: jnp.sin(x) * 3.25 + 17)(jnp.arange(7.0)
                                              ).block_until_ready()
    made = set(os.listdir(tmp_path))
    assert made
    after = set(os.listdir(root / ".jax_cache")) \
        if (root / ".jax_cache").exists() else set()
    # nothing THIS compile made went to the repository's directory (other
    # workers of a parallel run may be writing entries of their own there)
    assert not made & (after - before)


def test_launcher_refuses_two_workers_on_a_tpu_host(monkeypatch):
    """One process drives all local chips; a second local worker on a
    TPU host is an error with the reason, not a hang."""
    from paddle_tpu.distributed.launch import main as L

    monkeypatch.setattr(L, "local_tpu_chips", lambda: ["/dev/vfio/0"])
    L.check_process_layout(1, env={})
    L.check_process_layout(4, env={"JAX_PLATFORMS": "cpu"})
    with pytest.raises(SystemExit, match="a chip belongs to one process"):
        L.check_process_layout(2, env={})
    with pytest.raises(SystemExit, match="a chip belongs to one process"):
        L.check_process_layout(2, env={"JAX_PLATFORMS": "tpu,cpu"})
    monkeypatch.setattr(L, "local_tpu_chips", lambda: [])
    L.check_process_layout(2, env={})       # no chip here: CPU gangs run
