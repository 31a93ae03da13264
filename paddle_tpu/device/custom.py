"""Custom-device plugin ABI.

Analog of the reference's custom-device runtime
(paddle/phi/capi/ + paddle/phi/backends/custom/custom_device.cc:42): a
vendor ships a plugin library; the framework registers it under a device
type name and user code addresses it as ``paddle.set_device("npu:0")``.

TPU-native translation: accelerator plugins are PJRT plugins.  The
framework-level ABI here is the registration + naming layer the
reference provides on top of the raw runtime:

- ``register_custom_device(name, library_path=None, platform=None)``
  binds a paddle device-type name to a PJRT plugin .so (loaded through
  jax's PJRT_NAMES_AND_LIBRARY_PATHS discovery) or to an existing jax
  platform (aliasing — e.g. tests bind a fake type to "cpu"),
- ``paddle.set_device("<name>:<i>")`` then resolves through this
  registry (core/device.py consults resolve()),
- introspection parity: get_all_custom_device_type(),
  is_compiled_with_custom_device().

The C-ABI kernel-registration half of phi/capi is intentionally NOT
reproduced: on a PJRT backend, kernels arrive via XLA lowering, not
per-op C hooks (SURVEY §2.10 decision records).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

_CUSTOM_DEVICES: Dict[str, dict] = {}


def register_custom_device(name: str, library_path: Optional[str] = None,
                           platform: Optional[str] = None) -> None:
    """Register device type ``name``.

    library_path: a PJRT plugin shared library — appended to jax's
        PJRT_NAMES_AND_LIBRARY_PATHS so the next backend initialization
        discovers it (must be called before first jax device use, same
        constraint as the reference's plugin loading at framework init).
    platform: alias onto an already-available jax platform instead
        (what single-process tests and re-branded backends use).
    """
    if not name or ":" in name:
        raise ValueError(f"invalid custom device type {name!r}")
    if name in ("cpu", "tpu", "gpu", "cuda"):
        raise ValueError(
            f"{name!r} is a builtin device type and cannot be remapped "
            "(registering it would silently re-route every placement)")
    if (library_path is None) == (platform is None):
        raise ValueError("register_custom_device needs exactly one of "
                         "library_path= or platform=")
    if library_path is not None:
        if not os.path.exists(library_path):
            raise FileNotFoundError(library_path)
        entry = f"{name}:{library_path}"
        cur = os.environ.get("PJRT_NAMES_AND_LIBRARY_PATHS", "")
        # replace any existing binding for this name: a stale .so first
        # in discovery order would win over the new one
        kept = [e for e in cur.split(",")
                if e and not e.startswith(f"{name}:")]
        os.environ["PJRT_NAMES_AND_LIBRARY_PATHS"] = \
            ",".join(kept + [entry])
        platform = name
    _CUSTOM_DEVICES[name] = {"platform": platform,
                             "library_path": library_path}


def unregister_custom_device(name: str) -> None:
    info = _CUSTOM_DEVICES.pop(name, None)
    if info and info.get("library_path"):
        # drop the plugin entry from PJRT discovery so a later
        # re-registration under this name cannot leave a stale .so bound
        cur = os.environ.get("PJRT_NAMES_AND_LIBRARY_PATHS", "")
        kept = [e for e in cur.split(",")
                if e and not e.startswith(f"{name}:")]
        if kept:
            os.environ["PJRT_NAMES_AND_LIBRARY_PATHS"] = ",".join(kept)
        else:
            os.environ.pop("PJRT_NAMES_AND_LIBRARY_PATHS", None)


def get_all_custom_device_type() -> List[str]:
    """Reference: paddle.device.get_all_custom_device_type()."""
    return sorted(_CUSTOM_DEVICES)


def is_compiled_with_custom_device(name: str) -> bool:
    return name in _CUSTOM_DEVICES


def resolve(device: str):
    """``"<type>:<idx>"`` or ``"<type>"`` -> (jax_platform, index) if the
    type is a registered custom device, else None."""
    dtype, _, idx = device.partition(":")
    info = _CUSTOM_DEVICES.get(dtype)
    if info is None:
        return None
    return info["platform"], int(idx or 0)


def custom_devices(name: str):
    """The jax devices backing a registered type (reference:
    paddle.device.custom_device_count cousin)."""
    import jax

    info = _CUSTOM_DEVICES.get(name)
    if info is None:
        raise ValueError(f"custom device type {name!r} is not registered")
    return jax.devices(info["platform"])
