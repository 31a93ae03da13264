"""RT — retrace sentinel.

Recompilation is the silent step-time killer jit makes easy: a caller
that alternates ``0.1`` (python float, weak-typed) with
``jnp.float32(0.1)`` (strong) retraces the WHOLE train step twice; an
object whose repr churns per call (a fresh tuple of floats, a config
dataclass) retraces every step.  Unlike the other doctor passes this is
call-driven — one trace cannot show signature churn — so the sentinel is
a wrapper: it forwards calls, fingerprints every signature, and reports
typed findings.

    step = retrace_sentinel(build_train_step(...))
    ... run ...
    step.report().raise_if_findings()

Codes:
- RT001: two call signatures identical except for weak-type flags — the
  python-scalar vs array churn; every flip is a full retrace.
- RT002: more distinct signatures than ``max_signatures`` — shape or
  static-argument churn (unbucketed lengths, per-call config objects).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.tree_util as jtu

from ..findings import Finding, Report


def _leaf_sig(x) -> Tuple:
    """(kind, shape, dtype, weak) fingerprint of one argument leaf."""
    try:
        aval = jax.typeof(x)
        return ("array", tuple(aval.shape), str(aval.dtype),
                bool(getattr(aval, "weak_type", False)))
    except Exception:
        return ("static", repr(x), "", False)


class RetraceSentinel:
    """Wraps a (usually jitted) callable; counts call signatures and
    flags weak-type/static-arg churn.  ``max_signatures`` bounds healthy
    signature diversity (bucketed prefill lengths are a legitimate
    handful; hundreds are churn)."""

    def __init__(self, fn, max_signatures: int = 8,
                 name: Optional[str] = None):
        self._fn = fn
        self._max = int(max_signatures)
        self.name = name or getattr(fn, "__name__", repr(fn))
        self.signatures: Dict[Tuple, int] = {}
        self._findings: List[Finding] = []
        self._rt002_emitted = False
        functools.update_wrapper(self, fn, updated=())

    # -- call path ----------------------------------------------------------

    def _signature(self, args, kwargs) -> Tuple:
        leaves, treedef = jtu.tree_flatten((args, kwargs))
        return (str(treedef),) + tuple(_leaf_sig(x) for x in leaves)

    @staticmethod
    def _strip_weak(sig: Tuple) -> Tuple:
        return (sig[0],) + tuple(
            leaf[:3] for leaf in sig[1:])

    def __call__(self, *args, **kwargs):
        sig = self._signature(args, kwargs)
        fresh = sig not in self.signatures
        self.signatures[sig] = self.signatures.get(sig, 0) + 1
        if fresh:
            self._on_new_signature(sig)
        return self._fn(*args, **kwargs)

    def _on_new_signature(self, sig: Tuple):
        stripped = self._strip_weak(sig)
        twins = [s for s in self.signatures
                 if s != sig and self._strip_weak(s) == stripped]
        if twins:
            diffs = [i - 1 for i, (a, b) in
                     enumerate(zip(sig, twins[0])) if a != b]
            self._findings.append(Finding(
                code="RT001", pass_name="retrace_sentinel",
                message=(
                    f"{self.name}: call signature differs from an earlier "
                    f"one ONLY in weak-type flags (leaf index(es) "
                    f"{diffs}) — a python scalar and an array are "
                    f"alternating in the same position; each flip "
                    f"retraces and recompiles the whole program.  Pin "
                    f"the caller to one form (e.g. jnp.asarray(lr, "
                    f"jnp.float32))"),
                data={"leaves": diffs}))
        if len(self.signatures) > self._max and not self._rt002_emitted:
            self._rt002_emitted = True
            self._findings.append(Finding(
                code="RT002", pass_name="retrace_sentinel",
                message=(
                    f"{self.name}: {len(self.signatures)} distinct call "
                    f"signatures (> max_signatures={self._max}) — shape "
                    f"or static-argument churn; every new signature is a "
                    f"compile.  Bucket dynamic lengths and hoist "
                    f"per-call objects out of the signature"),
                data={"count": len(self.signatures)}))

    # -- reporting ----------------------------------------------------------

    @property
    def compilations(self) -> Optional[int]:
        """Underlying jit cache size when the wrapped fn (or the jit
        entry behind its wrapper — build_train_step normalizes scalars
        in front of its jit) exposes it."""
        from ..core import _unwrap

        try:
            return int(_unwrap(self._fn)._cache_size())
        except Exception:
            return None

    def report(self) -> Report:
        """Signature findings plus the ground truth: when the entry
        normalized the churn away (compilations < signatures), the
        caller hygiene finding stands but says so."""
        comps = self.compilations
        findings = list(self._findings)
        if comps is not None:
            for f in findings:
                f.data.setdefault("compilations", comps)
                if comps <= 1 and f.code == "RT001":
                    f.severity = "warning"
                    if "entry normalized" not in f.message:
                        f.message += (
                            f"  (this entry normalized the signature "
                            f"before jit — {comps} compile(s) actually "
                            f"happened — but the caller churn is real "
                            f"and other entries will pay for it)")
        return Report(target=self.name, findings=findings,
                      passes_run=("retrace_sentinel",))


def retrace_sentinel(fn, max_signatures: int = 8,
                     name: Optional[str] = None) -> RetraceSentinel:
    return RetraceSentinel(fn, max_signatures=max_signatures, name=name)
