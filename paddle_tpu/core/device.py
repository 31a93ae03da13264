"""Device / place management.

Analog of the reference's paddle.device (python/paddle/device/__init__.py:281
``set_device``, :201 ``_convert_to_place``) and the phi Place hierarchy,
mapped onto JAX devices. ``set_device('tpu')`` routes all subsequent eager op
execution onto the TPU backend — the reference's north-star API shape.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

_state = threading.local()


class Place:
    """A concrete device placement (analog of phi::Place)."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    @property
    def jax_device(self):
        devs = _accel_devices(self.device_type)
        if not devs:
            raise RuntimeError(
                f"{self!r}: this process has no {self.device_type!r} "
                f"device (jax.devices() reports "
                f"{sorted({d.platform for d in jax.devices()})})")
        return devs[min(self.device_id, len(devs) - 1)]


def _accel_devices(device_type: str):
    """Platform-matching devices, filtered by FLAGS_selected_gpus when set
    (the reference's trainer device-selection contract: a comma-separated
    index list restricting which accelerators this process uses)."""
    devs = [d for d in jax.devices()
            if _platform_matches(d.platform, device_type)]
    from ..common import flags as _flags

    sel = _flags.get_flag("FLAGS_selected_gpus")
    if sel and device_type != "cpu":
        try:
            idx = {int(i) for i in str(sel).split(",") if i.strip() != ""}
        except ValueError:
            raise ValueError(
                f"FLAGS_selected_gpus={sel!r} is not a comma-separated "
                "index list") from None
        picked = [d for i, d in enumerate(devs) if i in idx]
        if not picked and devs:
            # silently widening to ALL devices would defeat the
            # restriction the operator asked for — fail loudly instead
            raise ValueError(
                f"FLAGS_selected_gpus={sel!r} selects none of the "
                f"{len(devs)} visible {device_type} devices")
        return picked or devs
    return devs


def is_tpu(device=None) -> bool:
    """THE "is this a TPU" predicate: ``device.platform == "tpu"`` of
    the given device (default: the process's first device)."""
    d = device if device is not None else jax.devices()[0]
    return d.platform == "tpu"


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode by default: on
    anything but a TPU (what the CPU tests use); compiled on the chip."""
    return not is_tpu()


def _platform_matches(platform: str, device_type: str) -> bool:
    if device_type == "tpu":
        return platform == "tpu"
    # registered custom device types resolve through the plugin registry
    # (device/custom.py — the phi custom-device ABI analog)
    try:
        from ..device.custom import resolve as _custom_resolve

        hit = _custom_resolve(device_type)
        if hit is not None:
            return platform == hit[0]
    except ImportError:
        pass
    return platform == device_type


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CPUPlace() -> Place:
    return Place("cpu", 0)


def _default_device_type() -> str:
    return jax.devices()[0].platform


def set_device(device: str) -> Place:
    """Set the global default device, e.g. ``set_device('tpu')`` / ``'tpu:0'``."""
    if ":" in device:
        dev_type, _, idx = device.partition(":")
        place = Place(dev_type, int(idx))
    else:
        place = Place(device, 0)
    _state.place = place
    return place


def get_device() -> str:
    place = current_place()
    return f"{place.device_type}:{place.device_id}"


def current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        place = Place(_default_device_type(), 0)
        _state.place = place
    return place


def device_count(device_type: Optional[str] = None) -> int:
    dt = device_type or current_place().device_type
    return len(_accel_devices(dt)) or 1


def is_compiled_with_tpu() -> bool:
    return any(is_tpu(d) for d in jax.devices())


# ---------------------------------------------------------------------------
# memory-kind capability probe (round-10)
#
# The HBM memory engine (parallel/memory.py) parks optimizer state and
# activation saveables in host memory and streams them back per bucket.
# TPU and the CPU backend both expose {"device", "pinned_host",
# "unpinned_host"} with "device" the default.  These probes are the
# single source of truth the engine keys on.
# ---------------------------------------------------------------------------


def memory_kinds() -> tuple:
    """Memory kinds of the current default device, default kind first."""
    d = jax.devices()[0]
    default = d.default_memory().kind
    return (default,) + tuple(m.kind for m in d.addressable_memories()
                              if m.kind != default)


def default_memory_kind():
    """The device's default (compute-resident) memory kind."""
    return memory_kinds()[0]


def supports_memory_kind(kind: str) -> bool:
    return kind in memory_kinds()


def host_memory_kind():
    """The memory kind the offload engine streams state TO:
    ``pinned_host`` where the backend has it, else None (no offload
    support; callers keep device residency)."""
    return "pinned_host" if "pinned_host" in memory_kinds() else None


def host_offload_distinct() -> bool:
    """True when a ``pinned_host`` space distinct from the compute
    memory exists, so host offload moves bytes off it."""
    return host_memory_kind() is not None


# ---------------------------------------------------------------------------
# XLA communication-overlap compiler knobs (round-9)
#
# The overlap engine (parallel/overlap.py) makes gathers/reduce-scatters
# SCHEDULABLE under compute; these flags tell XLA's scheduler to actually
# do it.  xla_tpu_* switches live in the TPU compiler's flag registry
# (reachable via XLA_FLAGS before backend init, not via per-compile
# DebugOptions on other backends), so the wiring is env-merge first,
# per-compile options where the backend accepts them.
# ---------------------------------------------------------------------------

# FLAGS_* registry name -> XLA flag name (bool-valued)
XLA_OVERLAP_FLAG_SPECS = {
    "FLAGS_tpu_latency_hiding_scheduler":
        "xla_tpu_enable_latency_hiding_scheduler",
    "FLAGS_tpu_async_collective_fusion":
        "xla_tpu_enable_async_collective_fusion",
    "FLAGS_tpu_async_all_gather": "xla_enable_async_all_gather",
    "FLAGS_tpu_async_collective_permute":
        "xla_enable_async_collective_permute",
}


def xla_overlap_flags() -> list:
    """The overlap-scheduling XLA flags as ``--name=true/false`` strings,
    reflecting the CURRENT FLAGS_* registry values."""
    from ..common import flags as _flags

    vals = _flags.get_flags(list(XLA_OVERLAP_FLAG_SPECS))
    return [f"--{xla}={'true' if vals[name] else 'false'}"
            for name, xla in XLA_OVERLAP_FLAG_SPECS.items()]


def apply_xla_overlap_flags(env=None) -> str:
    """Merge the overlap flags into ``env['XLA_FLAGS']`` (default
    ``os.environ``), REPLACING any stale occurrence of the same flag and
    preserving unrelated flags.  Returns the merged string.  Must run
    before the first jax backend instantiation to take effect — the
    launcher path (distributed/launch) is the intended call site; late
    calls still merge (harmless) so tests can exercise the plumbing on
    a live backend."""
    import os

    env = os.environ if env is None else env
    ours = {f.split("=", 1)[0]: f for f in xla_overlap_flags()}
    kept = [tok for tok in env.get("XLA_FLAGS", "").split()
            if tok.split("=", 1)[0] not in ours]
    merged = " ".join(kept + list(ours.values()))
    env["XLA_FLAGS"] = merged
    return merged


def overlap_compiler_options() -> dict:
    """Per-compile DebugOptions overrides for backends whose option
    parser carries the overlap switches (TPU).  CPU/GPU builds reject
    unknown xla_tpu_* names at compile time — the doctor-grade behavior
    (options are PARSED, never silently dropped) that
    tests/test_overlap.py pins — so this returns {} off-TPU."""
    if not is_compiled_with_tpu():
        return {}
    from ..common import flags as _flags

    vals = _flags.get_flags(list(XLA_OVERLAP_FLAG_SPECS))
    return {xla: bool(vals[name])
            for name, xla in XLA_OVERLAP_FLAG_SPECS.items()}


def compile_with_overlap_options(fn, *args, extra_options=None,
                                 **kwargs):
    """Lower + compile a jittable with the overlap compiler options (and
    ``extra_options``) applied — the per-entry-point alternative to the
    global XLA_FLAGS merge.  Returns the compiled executable."""
    opts = dict(overlap_compiler_options())
    if extra_options:
        opts.update(extra_options)
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jitted.lower(*args, **kwargs)
    if not opts:
        return lowered.compile()
    return lowered.compile(compiler_options=opts)
