"""Graph Doctor core: the pass framework.

``check(fn, *args, **kwargs)`` traces ``fn`` exactly as jit would, hands
the closed jaxpr (and, for passes that need it, the lowered/compiled HLO)
to every registered AnalysisPass, and returns a typed findings Report.
The framework generalizes the one-off HLO-grep regression tests (round-4's
involuntary-remat gate) into reusable machinery: PartIR-style, partitioning
and precision decisions over our programs are inspectable artifacts, not
side effects (PAPERS.md; arxiv 2112.01075 for statically-checkable
collective sequences).

Cost model: passes declare what they need — ``"jaxpr"`` (a trace, cheap),
``"lowered"`` (StableHLO lowering, adds donation metadata), or
``"compiled"`` (full XLA compile with fd-level stderr capture, the
expensive one) — and the context materializes each artifact at most once
per check() call.
"""

from __future__ import annotations

import os
import tempfile
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
from jax._src import source_info_util as _siu
from jax.extend import core as jax_core

from .exemptions import apply_exemptions
from .findings import Finding, Report

# ---------------------------------------------------------------------------
# jaxpr walking utilities (shared by passes)
# ---------------------------------------------------------------------------


def sub_jaxprs(eqn) -> Iterator[Tuple[str, Any]]:
    """Yield (param_name, Jaxpr) for every inner jaxpr of an eqn —
    pjit/remat ``jaxpr``, scan ``jaxpr``, cond ``branches``, while
    ``cond_jaxpr``/``body_jaxpr``, custom_* ``call_jaxpr``/``fun_jaxpr``,
    shard_map ``jaxpr`` — without hardcoding the primitive zoo."""
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                yield name, v.jaxpr
            elif isinstance(v, jax_core.Jaxpr):
                yield name, v


def walk_eqns(jaxpr, _stack: Tuple = ()) -> Iterator[Tuple[Any, Tuple]]:
    """Depth-first traversal of every eqn in ``jaxpr`` and all nested
    jaxprs.  Yields (eqn, stack) where ``stack`` is the tuple of ancestor
    eqns (outermost first) — passes use it for region context (inside a
    shard_map? nested in a scan?)."""
    for eqn in jaxpr.eqns:
        yield eqn, _stack
        for _, inner in sub_jaxprs(eqn):
            yield from walk_eqns(inner, _stack + (eqn,))


def _plain_name(qualname: str) -> str:
    """``f.<locals>.g`` -> ``g``: frames carry qualified names; region
    and exemption tables match on the plain function name."""
    return qualname.rsplit(".", 1)[-1]


def eqn_source(eqn) -> Tuple[str, int, str]:
    """(file, line, function) provenance of an eqn, from its traceback.
    Returns ("", 0, "") when jax carries no source info (e.g. synthetic
    eqns from transposition)."""
    frame = _siu.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "", 0, ""
    return (frame.file_name, int(frame.start_line),
            _plain_name(frame.function_name))


def format_where(eqn) -> Tuple[Optional[str], Dict[str, Any]]:
    """(where-string, data-dict) from eqn provenance, for Finding fields.
    ``data["stack_functions"]`` carries the full user-code call stack at
    trace time (innermost first) — exemptions match on it, so a hazard
    produced by a lambda inside ``micro_step_masked`` is still
    attributable to that function."""
    fname, line, func = eqn_source(eqn)
    if not fname:
        return None, {}
    stack = tuple(_plain_name(fr.function_name)
                  for fr in _siu.user_frames(eqn.source_info.traceback))
    short = os.path.join(*fname.split(os.sep)[-2:]) if os.sep in fname \
        else fname
    return f"{short}:{line} ({func})", {"function": func, "file": fname,
                                        "line": line,
                                        "stack_functions": stack}


def aval_size(aval) -> int:
    try:
        size = 1
        for d in aval.shape:
            size *= int(d)
        return size
    except Exception:
        return 0


def capture_stderr(fn: Callable[[], Any]) -> Tuple[Any, str]:
    """Run ``fn`` with fd-level stderr capture (XLA C++ warnings bypass
    sys.stderr).  Returns (result, captured_text)."""
    import sys

    sys.stderr.flush()
    saved = os.dup(2)
    tmp = tempfile.TemporaryFile(mode="w+b")
    os.dup2(tmp.fileno(), 2)
    try:
        result = fn()
    finally:
        sys.stderr.flush()
        os.dup2(saved, 2)
        os.close(saved)
    tmp.seek(0)
    text = tmp.read().decode(errors="replace")
    tmp.close()
    return result, text


# ---------------------------------------------------------------------------
# Context
# ---------------------------------------------------------------------------


def _unwrap(fn):
    """Follow ``__wrapped__`` DOWN to a jit entry, and only to a jit
    entry: build_train_step returns a scalar-normalizing plain wrapper
    around its jitted step, and the doctor must audit the jit boundary
    (donation lives there).  A fn that is already a jit entry stays put
    (jit itself sets __wrapped__ to the raw python body — unwrapping
    past it would lose the entry), and plain wrappers over plain
    functions (shard_map over a collective body) stay put too (the raw
    body is not traceable outside its wrapper)."""
    seen = set()
    while not hasattr(fn, "lower") and id(fn) not in seen:
        seen.add(id(fn))
        inner = getattr(fn, "__wrapped__", None)
        if inner is None or not hasattr(inner, "lower"):
            break
        fn = inner
    return fn


class AnalysisContext:
    """Everything a pass may ask for about one (fn, args) target, built
    lazily and cached: the closed jaxpr, the Lowered (with donation
    metadata), the compiled executable plus the stderr XLA emitted while
    compiling, and per-pass options."""

    def __init__(self, fn, args, kwargs, target: str = "",
                 declared_dtype=None, options: Optional[Dict] = None):
        self.fn = fn
        self.inner_fn = _unwrap(fn)
        self.args = args
        self.kwargs = kwargs or {}
        self.target = target or getattr(fn, "__name__", repr(fn))
        self.declared_dtype = declared_dtype
        self.options = options or {}
        self._jaxpr = None
        self._lowered = ...
        self._compiled = None
        self._compile_stderr = None

    def opt(self, pass_name: str, key: str, default=None):
        return self.options.get(pass_name, {}).get(key, default)

    @property
    def closed_jaxpr(self):
        if self._jaxpr is None:
            if self.is_jit_entry and hasattr(self.inner_fn, "trace"):
                # AOT trace respects the entry's static_argnums/argnames
                # (make_jaxpr would abstractify config objects like the
                # serving chunk's cfg_id and crash)
                self._jaxpr = self.inner_fn.trace(
                    *self.args, **self.kwargs).jaxpr
            else:
                self._jaxpr = jax.make_jaxpr(self.inner_fn)(
                    *self.args, **self.kwargs)
        return self._jaxpr

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr

    @property
    def is_jit_entry(self) -> bool:
        """True when the (unwrapped) target is a jit-compiled entry point
        — only those carry a donation contract worth auditing."""
        return hasattr(self.inner_fn, "lower") \
            and not isinstance(self.inner_fn, type)

    @property
    def lowered(self):
        """jax Lowered for jit entries (None for plain functions)."""
        if self._lowered is ...:
            if self.is_jit_entry:
                self._lowered = self.inner_fn.lower(*self.args,
                                                    **self.kwargs)
            else:
                self._lowered = None
        return self._lowered

    def compile(self):
        """(compiled, compile_stderr_text); compiles at most once.  Plain
        functions are jitted first (no donation) — HLO text checks still
        apply."""
        if self._compiled is None:
            lowered = self.lowered
            if lowered is None:
                lowered = jax.jit(self.inner_fn).lower(*self.args,
                                                       **self.kwargs)
            self._compiled, self._compile_stderr = capture_stderr(
                lowered.compile)
        return self._compiled, self._compile_stderr

    @property
    def compiled_text(self) -> str:
        compiled, _ = self.compile()
        try:
            return compiled.as_text()
        except Exception:  # pragma: no cover - backend without HLO dump
            return ""


# ---------------------------------------------------------------------------
# Pass base + registry
# ---------------------------------------------------------------------------

PASS_REGISTRY: Dict[str, type] = {}


def register_pass(cls):
    PASS_REGISTRY[cls.name] = cls
    return cls


class AnalysisPass:
    name: str = ""
    codes: Tuple[str, ...] = ()
    #: artifacts this pass forces: "jaxpr" | "lowered" | "compiled"
    requires: str = "jaxpr"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        raise NotImplementedError

    def finding(self, code, message, severity="error", **kw) -> Finding:
        return Finding(code=code, message=message, severity=severity,
                       pass_name=self.name, **kw)


def resolve_passes(passes=None) -> List[AnalysisPass]:
    """None -> all registered passes; names/classes/instances accepted."""
    from . import passes as _passes  # noqa: F401 - populates the registry

    if passes is None:
        return [cls() for cls in PASS_REGISTRY.values()]
    out = []
    for p in passes:
        if isinstance(p, str):
            if p not in PASS_REGISTRY:
                raise KeyError(
                    f"unknown pass {p!r}; registered: "
                    f"{sorted(PASS_REGISTRY)}")
            out.append(PASS_REGISTRY[p]())
        elif isinstance(p, type):
            out.append(p())
        else:
            out.append(p)
    return out


def check(fn, *args, passes: Optional[Sequence] = None, target: str = "",
          declared_dtype=None, options: Optional[Dict] = None,
          exemptions=None, kwargs: Optional[Dict] = None) -> Report:
    """Run the Graph Doctor over one entry point.

    ``fn`` — the function to analyze (a jitted entry, a wrapper around
    one, or a plain traceable function); ``args``/``kwargs`` — example
    arguments with the real shapes/dtypes/shardings;
    ``passes`` — pass names/instances (None = all registered);
    ``declared_dtype`` — the declared compute dtype for the dtype audit
    (None = infer from the dominant matmul dtype);
    ``options`` — per-pass knobs, ``{"donation": {"persistent": (0,)}}``;
    ``exemptions`` — exemption table (None = the tracked standing table,
    ``()`` = none).

    Returns a Report; ``report.ok`` is the gate.
    """
    ctx = AnalysisContext(fn, args, kwargs, target=target,
                          declared_dtype=declared_dtype, options=options)
    instances = resolve_passes(passes)
    findings: List[Finding] = []
    skipped: Dict[str, str] = {}
    for p in instances:
        try:
            findings.extend(p.run(ctx))
        except SkipPass as e:
            skipped[p.name] = str(e)
    active, suppressed = apply_exemptions(findings, exemptions)
    return Report(target=ctx.target, findings=active, suppressed=suppressed,
                  passes_run=tuple(p.name for p in instances),
                  skipped=skipped)


class SkipPass(Exception):
    """A pass raises this when its preconditions don't hold for the
    target (e.g. HLO sharding checks on a single-device program) —
    recorded on the report instead of failing the run."""
