"""Graph Doctor (paddle_tpu.analysis) — ISSUE 3 tentpole gate.

Three layers, mirroring the self-check:
- TRUE POSITIVES: every seeded-bug fixture triggers exactly its intended
  finding code (a pass that never fires is indistinguishable from one
  that cannot fire);
- CLEAN RUNS: the flagship entry points — build_train_step (unmasked
  bf16, both accum regimes), llama fwd/bwd, the serving decode chunk —
  report zero findings;
- EXEMPTIONS: the masked grad-accum fp32 carry is DETECTED (DT003 with
  exemptions disabled) and SUPPRESSED by its tracked entry with the
  standing table, so the accepted-region paper trail stays live.

Plus unit coverage of the framework plumbing (pass resolution, options,
report formatting, the jit-entry unwrap).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle  # noqa: F401 - registers ops
import paddle_tpu.analysis as A
from paddle_tpu.analysis.fixtures import SEEDED, FixtureUnavailable
from paddle_tpu.analysis.self_check import _flagship


# ---------------------------------------------------------------------------
# seeded-bug true positives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("code", sorted(SEEDED))
def test_seeded_fixture_triggers_exactly_its_code(code):
    try:
        rep = SEEDED[code]()
    except FixtureUnavailable as e:
        pytest.skip(str(e))
    assert rep.findings, f"{code}: fixture produced no findings\n" \
        + rep.summary()
    # registry keys may carry a "[variant]" suffix (two proofs of one
    # code on different entry points) — the report carries the bare code
    assert set(rep.codes()) == {code.split("[", 1)[0]}, rep.summary()


# ---------------------------------------------------------------------------
# clean flagship sweeps
# ---------------------------------------------------------------------------


def test_flagship_entry_points_are_clean():
    # the memoized section (one set of flagship compiles per tier-1
    # process — the doctor smoke leg reuses it through self_check)
    from paddle_tpu.analysis.self_check import _clean_section

    section = _clean_section()
    assert section, "clean sweep yielded no targets"
    for name, rep in section.items():
        assert rep.get("ok"), (f"{name} is not doctor-clean:\n"
                               + "\n".join(rep.get("findings", [])
                                           or [rep.get("error", "")]))


# ---------------------------------------------------------------------------
# the tracked exemption: masked grad-accum fp32 carry
# ---------------------------------------------------------------------------


def _masked_accum_report(exemptions):
    from paddle_tpu.models import build_train_step

    cfg, model, opt, params, ids, labels = _flagship()
    step = build_train_step(model, opt, compute_dtype=jnp.bfloat16,
                            accum_steps=4)
    amask = np.ones((4, 1, 16), np.int32)
    amask[:, :, -4:] = 0
    return A.check(step, params, opt.init_state(params), 0, 1e-4,
                   ids.reshape(4, 1, 16), labels.reshape(4, 1, 16), amask,
                   passes=["dtype_promotion"], exemptions=exemptions,
                   target="masked-accum")


def test_masked_accum_fp32_carry_detected_without_exemptions():
    rep = _masked_accum_report(exemptions=())
    assert "DT003" in rep.codes(), rep.summary()


def test_masked_accum_fp32_carry_suppressed_by_tracked_entry():
    rep = _masked_accum_report(exemptions=None)   # the standing table
    assert rep.ok, rep.summary()
    ids_ = [f.exemption_id for f in rep.suppressed]
    assert "EX-DT003-masked-grad-accum" in ids_, rep.summary()


# ---------------------------------------------------------------------------
# framework plumbing
# ---------------------------------------------------------------------------


def test_unknown_pass_name_raises():
    with pytest.raises(KeyError):
        A.check(lambda x: x, jnp.ones(3), passes=["no_such_pass"])


def test_report_raise_if_findings_carries_summary():
    rep = A.Report(target="t", findings=[A.Finding(code="DT001",
                                                   message="boom")])
    with pytest.raises(A.AnalysisError) as ei:
        rep.raise_if_findings()
    assert "DT001" in str(ei.value)


def test_donation_persistent_option_silences_don001():
    @jax.jit
    def served(weights, x):
        return x @ weights

    w = jnp.ones((768, 768), jnp.float32)
    x = jnp.ones((8, 768), jnp.float32)
    noisy = A.check(served, w, x, passes=["donation"], exemptions=())
    assert noisy.by_code("DON001"), noisy.summary()
    quiet = A.check(served, w, x, passes=["donation"], exemptions=(),
                    options={"donation": {"persistent": (0,)}})
    assert quiet.ok, quiet.summary()


def test_exemption_without_liveness_probe_fails_self_check(monkeypatch):
    """Adding an Exemption without registering a probe must FAIL the
    liveness check, not silently pass — that is what keeps the table
    honest for passes/targets beyond the baked-in sweeps."""
    import paddle_tpu.analysis.exemptions as ex_mod
    from paddle_tpu.analysis.self_check import _exemption_liveness

    orphan = A.Exemption(id="EX-TEST-orphan", code="DT001",
                         file_pattern="nowhere.py", reason="test")
    monkeypatch.setattr(ex_mod, "EXEMPTIONS", (orphan,))
    out = _exemption_liveness()
    assert out["EX-TEST-orphan"]["ok"] is False
    assert "no liveness probe" in out["EX-TEST-orphan"]["error"]


def test_functional_apply_preserves_param_dtype_with_strong_lr():
    """The base Optimizer.apply enforces the param-dtype invariant: a
    strong-f32 lr (build_train_step's signature pin) through an
    SGD-class `value - lr * grad` update must NOT return f32 params for
    bf16 inputs."""
    import paddle_tpu as paddle

    opt = paddle.optimizer.SGD(learning_rate=0.01)
    p = {"w": jnp.ones((8,), jnp.bfloat16)}
    g = {"w": jnp.ones((8,), jnp.bfloat16)}
    new_p, _ = opt.apply(p, g, opt.init_state(p),
                         jnp.asarray(0.01, jnp.float32), 1)
    assert new_p["w"].dtype == jnp.bfloat16


def test_clean_sweep_donation_gate_is_live():
    """The sweeps run debug-shaped params (~200 KB); at the production
    default min_bytes (1 MB) DON001 could never fire there and deleting
    donate_argnums from build_train_step would still pass self-check.
    Prove the sweep threshold actually gates: an UNdonated params dict
    of exactly the flagship debug size must trip DON001."""
    from paddle_tpu.analysis.self_check import DONATION_MIN_BYTES

    cfg, model, opt, params, ids, labels = _flagship()

    @jax.jit
    def undonated_step(p, g):
        return jax.tree_util.tree_map(lambda a, b: a - 1e-3 * b, p, g)

    rep = A.check(undonated_step, params, params, passes=["donation"],
                  exemptions=(),
                  options={"donation": {"min_bytes": DONATION_MIN_BYTES}})
    assert rep.by_code("DON001"), rep.summary()


def test_serving_donation_gate_is_live():
    """Same liveness property for the serving entry: analysis_entry's
    threshold is sized to the page pools, so an engine-shaped program
    that does NOT donate its pools must be flagged."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    cfg, model, opt, params, ids, labels = _flagship()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2, num_pages=9,
                                   page_size=16, max_seq_len=64,
                                   prefill_token_budget=8)
    fn, args, kwargs, options = eng.analysis_entry()

    @jax.jit
    def undonated_pools(k_pages, v_pages):
        return (tuple(k * 2 for k in k_pages),
                tuple(v * 2 for v in v_pages))

    # keep the entry's pool-sized threshold, drop its persistent indices
    # (they describe the REAL decode signature, not this synthetic one)
    rep = A.check(undonated_pools, args[1], args[2], passes=["donation"],
                  exemptions=(),
                  options={"donation": {
                      "min_bytes": options["donation"]["min_bytes"]}})
    assert rep.by_code("DON001"), rep.summary()


def test_unwrap_reaches_jit_entry_through_wrapper():
    """build_train_step returns a scalar-normalizing wrapper; the doctor
    must still audit the jit boundary (donation metadata lives there)."""
    from paddle_tpu.analysis.core import AnalysisContext, _unwrap
    from paddle_tpu.models import build_train_step

    cfg, model, opt, params, ids, labels = _flagship()
    step = build_train_step(model, opt, compute_dtype=jnp.float32)
    inner = _unwrap(step)
    assert hasattr(inner, "lower") and inner is not step
    ctx = AnalysisContext(step, (params, opt.init_state(params), 0, 1e-4,
                                 ids, labels), {})
    assert ctx.is_jit_entry


def test_retrace_sentinel_stable_signature_is_quiet():
    step = A.retrace_sentinel(jax.jit(lambda x, lr: x * lr))
    x = jnp.ones((4,), jnp.float32)
    for _ in range(3):
        step(x, jnp.float32(0.1))
    rep = step.report()
    assert rep.ok and len(step.signatures) == 1, rep.summary()


def test_compile_failure_is_an_error_finding_not_a_skip(monkeypatch):
    """A flagship step that cannot XLA-compile must gate the doctor RED:
    skips don't affect Report.ok, so a compile regression routed through
    SkipPass would pass bench --doctor green."""
    from paddle_tpu.analysis.core import AnalysisContext

    def boom(self):
        raise RuntimeError("PartitionId instruction is not supported")

    monkeypatch.setattr(AnalysisContext, "compile", boom)
    rep = A.check(jax.jit(lambda x: x * 2), jnp.ones((4,), jnp.float32),
                  passes=["hlo_post_checks"], exemptions=())
    assert rep.codes() == ["HLO000"] and not rep.ok, rep.summary()
    assert "PartitionId" in rep.findings[0].message


def test_allgather_parser_counts_async_results_once():
    """TPU emits async collectives: all-gather-start's tuple is
    (operands..., results...) — only the results are gathered bytes.
    Summing the whole tuple would false-trip HLO002 on legitimate
    per-layer gathers."""
    from paddle_tpu.analysis.passes.hlo_checks import scan_allgather_sizes

    sync = "%all-gather.1 = f32[1024,64]{1,0} all-gather(%p0), dimensions={0}"
    asyn = ("%all-gather-start.1 = (f32[512,64]{1,0}, f32[1024,64]{1,0}) "
            "all-gather-start(%p0), dimensions={0}")
    done = ("%all-gather-done.1 = f32[1024,64]{1,0} "
            "all-gather-done(%all-gather-start.1)")
    combined = ("%ag = (f32[1024,64]{1,0}, f32[256,64]{1,0}) "
                "all-gather(%a, %b), dimensions={0}")
    sizes = dict((snip.split()[0], b) for b, snip in
                 scan_allgather_sizes("\n".join([sync, asyn, done,
                                                 combined])))
    full = 1024 * 64 * 4
    assert sizes["%all-gather.1"] == full
    assert sizes["%all-gather-start.1"] == full          # result only
    assert "%all-gather-done.1" not in sizes             # counted once
    assert sizes["%ag"] == full + 256 * 64 * 4           # combined: sum


def test_mixed_precision_dot_flagged():
    """bf16 x f32 dots promote and run fp32 — the exact shape of the
    rope-table bug DT001 first caught on the real train step."""
    def bug(a, w32):
        h = a @ a                       # declares bf16 compute
        return (h @ w32).sum()          # mixed: promotes h to f32

    a = jnp.ones((128, 128), jnp.bfloat16)
    w32 = jnp.ones((128, 128), jnp.float32)
    rep = A.check(bug, a, w32, passes=["dtype_promotion"], exemptions=())
    hits = rep.by_code("DT001")
    assert hits and hits[0].data["mixed"] is True, rep.summary()


def test_cond_branches_with_different_perms_flagged():
    """Both branches ppermute, but with different routing tables — still
    a deadlock (ranks consult different send/recv pairs)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = Mesh(np.asarray(devs[:2], dtype=object), ("x",))

    def body(v):
        # full ring-swap vs a one-directional send: rank 1 pairs a recv
        # with nothing in the false branch
        return jax.lax.cond(
            v.sum() > 0.0,
            lambda u: jax.lax.ppermute(u, "x", [(0, 1), (1, 0)]),
            lambda u: jax.lax.ppermute(u, "x", [(0, 1)]), v)

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"))
    rep = A.check(fn, jnp.ones((4,), jnp.float32),
                  passes=["collective_order"], exemptions=())
    assert "COLL001" in rep.codes(), rep.summary()


def test_collective_order_clean_on_symmetric_cond():
    """Branches issuing the SAME collective sequence are fine (no false
    positive on e.g. add-vs-multiply cond bodies that both psum)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:2], dtype=object), ("x",))

    def body(v):
        return jax.lax.cond(v.sum() > 0.0,
                            lambda u: jax.lax.psum(u * 2.0, "x"),
                            lambda u: jax.lax.psum(u + 1.0, "x"), v)

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"))
    rep = A.check(fn, jnp.ones((4,), jnp.float32),
                  passes=["collective_order"], exemptions=())
    assert rep.ok, rep.summary()
