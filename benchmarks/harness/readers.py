"""Readers of per-layer metrics.  Each takes the observations of a
traced run and its own arguments (from ``layer_metrics/<metric>.json``)
and returns the value, or ``None`` where it finds nothing to read: the
harness then leaves the metric out of the line.

Observations (``obs``): ``spans`` {name: [seconds, ...]} of the traced
window, ``counters`` {name: number}, ``trace`` (``reduce_trace``'s
result), ``config``, ``traffic``, ``chips`` and ``device_kind``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import flops, peaks, stats


def span_median_ms(obs: Dict[str, Any], span: str) -> Optional[float]:
    durs = obs["spans"].get(span)
    if not durs:
        return None
    return stats.median(durs) * 1e3


def counter_ratio_pct(obs: Dict[str, Any], num: str, den: str) -> Optional[float]:
    c = obs["counters"]
    if num not in c or not c.get(den):
        return None
    return 100.0 * c[num] / c[den]


def device_idle_pct(obs: Dict[str, Any]) -> Optional[float]:
    if not obs.get("trace"):
        return None
    return 100.0 * obs["trace"]["idle_share"]


def collective_share_pct(obs: Dict[str, Any]) -> Optional[float]:
    if not obs.get("trace") or obs["trace"]["collective_share"] is None:
        return None
    return 100.0 * obs["trace"]["collective_share"]


def train_mfu_pct(obs: Dict[str, Any], rate: str) -> Optional[float]:
    """Required operations per token (``flops.train_flops_per_token`` at
    the job's sequence length) times the traced steps' tokens per second,
    over chips times the published bf16 peak."""
    if rate not in obs["counters"]:
        return None
    per_token = flops.train_flops_per_token(obs["config"],
                                            obs["traffic"]["seq_len"])
    peak = peaks.peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return flops.mfu_pct(obs["counters"][rate], per_token, obs["chips"], peak)


READERS = {f.__name__: f for f in (span_median_ms, counter_ratio_pct,
                                   device_idle_pct, collective_share_pct,
                                   train_mfu_pct)}


def find_reader(root, name: str):
    """A built-in reader, or ``read`` of ``benchmarks/readers/<name>.py``
    (how a later PR adds a reader without editing this file)."""
    if name in READERS:
        return READERS[name]
    import importlib.util
    import pathlib

    path = pathlib.Path(root) / "benchmarks" / "readers" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader {name!r}: not one of {sorted(READERS)} "
                       f"and no file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmarks.readers.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(root, per_layer, obs: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """``{metric: {"value", "unit"}}`` for every metric whose reader
    finds something; a reader that does not exist is an error."""
    out = {}
    for m in per_layer:
        value = find_reader(root, m["reader"])(obs, **m["args"])
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
