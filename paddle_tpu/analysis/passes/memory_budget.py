"""MEM — peak-HBM and host-transfer budgets per entry point.

The HBM memory engine (parallel/memory.py) makes residency an engineered
artifact; this pass keeps it that way.  A declared entry point carries a
capacity contract the way round-9 steps carry a collective budget: the
compiled program's peak bytes must fit the declared HBM budget, and the
host↔device streaming traffic must stay inside the declared streaming
budget — an accidental FULL-state round trip (one un-bucketed
device_put of a whole optimizer group, a forgotten fallback that
gathers every offloaded leaf per step) fails the doctor, not a TPU
session with an OOM or a step-time cliff.

Codes:
- MEM000: the target failed to XLA-compile — the capacity numbers are
  moot and the step cannot run (same contract as HLO000: a compile
  regression gates red, never skips).
- MEM001: ``compiled.memory_analysis()`` peak bytes (arguments +
  outputs + temporaries − donation aliasing) exceed the entry point's
  declared budget, ``options={"memory_budget": {"hbm_bytes": N}}``.
  No declared budget → that check is skipped (a budget is a
  per-entry-point contract, not a global default).
- MEM002: the summed bytes of memory-kind transfers (``device_put``
  eqns whose target names a memory kind — the offload engine's
  streaming primitive) exceed the declared streaming budget,
  ``options={"memory_budget": {"host_transfer_bytes": N}}``.  Counted
  at the jaxpr level so the audit is backend-independent (on CPU the
  transfers are aliases, but the eqns — and a regression to
  monolithic full-state movement — are equally visible).
"""

from __future__ import annotations

from typing import List

import jax

from ..core import (AnalysisContext, AnalysisPass, SkipPass, aval_size,
                    format_where, register_pass, walk_eqns)
from ..findings import Finding


def _transfer_memory_kind(eqn):
    """The target memory kind of a device_put eqn, or None when the
    transfer carries no explicit memory-kind (plain device placement /
    sharding constraint)."""
    for dev in eqn.params.get("devices", ()):
        if isinstance(dev, jax.memory.Space):
            return dev.name.lower()
        kind = getattr(dev, "memory_kind", None)
        if kind is not None:
            return str(kind)
    return None


def scan_memory_transfers(jaxpr):
    """(bytes, kind, eqn) for every explicit memory-kind transfer in
    the program (nested jaxprs included — the streamed optimizer apply
    lives inside the jitted step's body)."""
    out = []
    for eqn, _stack in walk_eqns(jaxpr):
        if eqn.primitive.name != "device_put":
            continue
        kind = _transfer_memory_kind(eqn)
        if kind is None:
            continue
        nbytes = sum(aval_size(v.aval) * v.aval.dtype.itemsize
                     for v in eqn.outvars
                     if hasattr(v.aval, "dtype"))
        out.append((nbytes, kind, eqn))
    return out


@register_pass
class MemoryBudgetPass(AnalysisPass):
    name = "memory_budget"
    codes = ("MEM000", "MEM001", "MEM002")
    # MEM001 needs the compiled executable, but only when an HBM budget
    # is actually declared; MEM002 is jaxpr-level
    requires = "jaxpr"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        opts = ctx.options.get(self.name, {}) if ctx.options else {}
        hbm = opts.get("hbm_bytes")
        host = opts.get("host_transfer_bytes")
        if hbm is None and host is None:
            raise SkipPass(
                "no memory budget declared for this entry point "
                "(options={'memory_budget': {'hbm_bytes': ..., "
                "'host_transfer_bytes': ...}})")
        findings: List[Finding] = []
        if hbm is not None:
            findings.extend(self._check_peak(ctx, int(hbm)))
        if host is not None:
            findings.extend(self._check_transfers(ctx, int(host)))
        return findings

    # ---- MEM001 ----------------------------------------------------------

    def _check_peak(self, ctx, hbm: int) -> List[Finding]:
        try:
            compiled, _ = ctx.compile()
            ma = compiled.memory_analysis()
        except Exception as e:  # noqa: BLE001 — gate red, never skip
            return [self.finding(
                "MEM000",
                f"target failed to XLA-compile — the peak-memory check "
                f"is moot and the step cannot run: {e!r}"[:500],
                data={"error": repr(e)[:300]})]
        arg = int(ma.argument_size_in_bytes)
        out = int(ma.output_size_in_bytes)
        temp = int(ma.temp_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        peak = arg + out + temp - alias
        if peak <= hbm:
            return []
        return [self.finding(
            "MEM001",
            f"compiled peak memory {peak / 1e6:.2f} MB exceeds the "
            f"declared HBM budget of {hbm / 1e6:.2f} MB "
            f"(arguments {arg / 1e6:.2f} + outputs {out / 1e6:.2f} + "
            f"temporaries {temp / 1e6:.2f} − donation aliasing "
            f"{alias / 1e6:.2f}) — pick a heavier point on the "
            f"remat/offload lattice (parallel.memory.tune_memory_config)"
            f" or raise the declared budget deliberately",
            data={"peak_bytes": peak, "budget_bytes": hbm,
                  "argument_bytes": arg, "output_bytes": out,
                  "temp_bytes": temp, "alias_bytes": alias})]

    # ---- MEM002 ----------------------------------------------------------

    def _check_transfers(self, ctx, budget: int) -> List[Finding]:
        transfers = scan_memory_transfers(ctx.jaxpr)
        total = sum(nb for nb, _, _ in transfers)
        if total <= budget:
            return []
        worst = sorted(transfers, key=lambda t: -t[0])[:3]
        where, data = format_where(worst[0][2]) if worst else (None, {})
        return [self.finding(
            "MEM002",
            f"memory-kind transfer traffic of {total / 1e6:.2f} MB per "
            f"step exceeds the declared streaming budget of "
            f"{budget / 1e6:.2f} MB over {len(transfers)} transfers — "
            f"an un-bucketed full-state round trip defeats the offload "
            f"engine's size-capped streaming (largest: "
            f"{', '.join(f'{nb / 1e6:.2f} MB→{k}' for nb, k, _ in worst)})",
            where=where,
            data={**data, "total_bytes": total, "budget_bytes": budget,
                  "transfers": len(transfers),
                  "largest_bytes": [int(nb) for nb, _, _ in worst]})]
