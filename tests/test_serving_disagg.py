"""Disaggregated prefill/decode serving with a tiered KV plane
(round-16 tentpole; inference/disagg.py).

The acceptance contract these tests pin:

- disaggregated greedy output is BIT-IDENTICAL to the unified engine on
  the same request trace — including prefix-cache warm hits and at
  least one MID-DECODE handoff (a decode-replica kill replays the
  request through the prefill pool and hands its KV off again);
- the KV handoff stream is gated: ``check_handoff_budget`` sweeps clean
  on the flagship config (the seeded ``MEM001[kv_handoff]`` fixture
  rides tests/test_analysis_passes.py's SEEDED sweep) and the int8 KV
  handoff moves measurably fewer bytes than the raw float form;
- the host-tier prefix cache: demote→promote round trip bit-identical
  to a never-demoted page, and a CROSS-REPLICA host-tier hit observed
  in the fleet trace (hits > 0 structural, like PR 6's gate);
- load-driven autoscale moves ``FleetConfig.pool_targets`` per pool
  with hysteresis pinned on the fake clock so it cannot flap.

Tier policy (ROADMAP): the representative bit-parity leg and the
handoff-budget leg stay tier-1; the long fault × load breadth sweeps
are ``slow`` (tier-2).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from fault_injection import (OverloadBurst, ReplicaFaultEvent,
                             build_disagg_fleet, run_fleet_trace,
                             toy_llama)
from paddle_tpu.inference.disagg import AutoscaleConfig, KVHandoffPlanner
from paddle_tpu.inference.fleet import RouterConfig
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.generation import generate


@pytest.fixture(scope="module")
def tiny_model():
    return toy_llama()


def _refs(model, prompts, n):
    outs = []
    for p in prompts:
        ref = generate(model, p[None], max_new_tokens=n, do_sample=False)
        outs.append(np.asarray(ref._value if hasattr(ref, "_value")
                               else ref)[0, len(p):])
    return outs


def _prompts(rng, lens, shared=None):
    out = []
    for n in lens:
        body = rng.integers(1, 64, (n,)).astype(np.int32)
        out.append(np.concatenate([shared, body])
                   if shared is not None else body)
    return out


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# =====================================================================
# the acceptance gate: bit parity incl. warm hits + mid-decode handoff
# =====================================================================


def test_disagg_bit_parity_with_unified(tiny_model):
    """1 prefill + 2 decode replicas, a shared system prompt (warm
    prefix-cache hits on the prefill pool) and a scripted DECODE-replica
    kill mid-stream: the killed requests replay through the prefill
    pool and hand off AGAIN (the mid-decode handoff), and every greedy
    stream is bit-identical to one-shot generate()."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(200)
    sysp = rng.integers(1, 64, (16,)).astype(np.int32)   # one full page
    prompts = _prompts(rng, (5, 9, 13), shared=sysp) \
        + _prompts(rng, (7, 11))
    router, rs = build_disagg_fleet(
        cfg, params, prefill=1, decode=2,
        scripts={1: [ReplicaFaultEvent(step=4, kind="kill")]})
    assert sorted(r.role for r in rs.replicas.values()) \
        == ["decode", "decode", "prefill"]
    rids = [router.submit(prompts[0], max_new_tokens=6)]
    for _ in range(4):                     # warm the prefill trie and
        router.step()                      # put decode mid-stream
    rids += [router.submit(p, max_new_tokens=6) for p in prompts[1:]]
    out = router.run()
    assert sorted(out) == sorted(rids)          # zero requests lost
    for rid, p, ref in zip(rids, prompts, _refs(model, prompts, 6)):
        np.testing.assert_array_equal(
            out[rid], ref, err_msg=f"rid {rid} diverged under "
                                   f"disaggregation")
        assert len(out[rid]) == 6
    # every request crossed the KV plane at least once; the kill forced
    # a replay whose re-handoff (or a handoff into a live decode batch)
    # is the mid-decode shape
    assert router.telemetry["handoffs"] >= len(prompts)
    assert router.telemetry["handoffs_mid_decode"] >= 1
    assert [ev.fault for ev in router.telemetry["recoveries"]] \
        == ["ReplicaKilled"]
    # warm hits landed on the prefill pool's radix trie
    pre = rs.serving("prefill")[0]
    assert pre.engine.prefix_cache.stats()["hits"] >= 2
    # plan-once/stream-per-handoff: far fewer plans than handoffs
    assert router.planner.telemetry["plans_built"] \
        < router.planner.telemetry["handoffs"]
    assert len(rs.serving("decode")) == 2       # fleet healed in-pool


def test_sampled_request_hands_off_with_rng_state(tiny_model):
    """Round-17 (ROADMAP disagg leftover): temperature>0 requests no
    longer pin to a unified pool — the per-slot PRNG key rides the
    handoff payload, so a sampled stream crossing a MID-DECODE handoff
    is token-identical to the same (temperature, seed) request on one
    unified engine (the prefill side's first-token draw advances the
    stream; the decode side resumes it mid-state)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(209)
    filler = rng.integers(1, 64, (9,)).astype(np.int32)
    prompt = rng.integers(1, 64, (13,)).astype(np.int32)

    # reference: ONE unified engine, same seeds, same sampling machinery
    ekw = dict(max_slots=2, num_pages=33, page_size=16, max_seq_len=128,
               prefill_token_budget=16, enable_prefix_cache=True)
    ref_eng = ContinuousBatchingEngine(cfg, params, **ekw)
    r0 = ref_eng.add_request(filler, max_new_tokens=6)
    r1 = ref_eng.add_request(prompt, max_new_tokens=6, temperature=0.8,
                             seed=42)
    ref = {f.rid: list(f.tokens) for f in ref_eng.run()}

    # disaggregated: no unified pool anywhere — the sampled request
    # MUST cross the prefill→decode handoff to complete
    router, rs = build_disagg_fleet(cfg, params, prefill=1, decode=1)
    assert "unified" not in rs.pool_targets()
    d0 = router.submit(filler, max_new_tokens=6)
    d1 = router.submit(prompt, max_new_tokens=6, temperature=0.8,
                       seed=42)
    out = router.run()
    assert sorted(out) == sorted([d0, d1])
    np.testing.assert_array_equal(out[d0], np.asarray(ref[r0]))
    np.testing.assert_array_equal(
        out[d1], np.asarray(ref[r1]),
        err_msg="sampled stream diverged across the KV handoff — the "
                "PRNG state did not migrate")
    assert router.telemetry["handoffs"] >= 2
    # the second handoff lands while the first request decodes
    assert router.telemetry["handoffs_mid_decode"] >= 1


@pytest.mark.slow  # round-20 tier policy: tier-1 homes = the seeded
# MEM001[kv_handoff] fixture + handoff COMM004 gate (test_analysis_passes)
# and the disagg bit-parity leg above; the wire-ratio breadth re-asserts here
def test_kv_handoff_budget_and_int8_wire(tiny_model):
    """The handoff leg: the int8-KV fleet's handoff stream moves
    measurably fewer bytes than the float-cache form of the SAME page
    payload, stays bit-identical to an int8 unified engine, and its
    plan sweeps the declared MEM001 + wire budgets clean."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(201)
    prompts = _prompts(rng, (9, 17))

    router_i, _ = build_disagg_fleet(cfg, params, prefill=1, decode=1,
                                     cache_dtype=jnp.int8)
    rids_i = [router_i.submit(p, max_new_tokens=5) for p in prompts]
    out_i = router_i.run()
    assert sorted(out_i) == sorted(rids_i)
    assert router_i.planner.telemetry["handoffs"] == len(prompts)
    # the raw denominator: the SAME page payload in the float-cache
    # form (what a fp32-KV fleet's planner would stream per handoff)
    from paddle_tpu.parallel.reshard import plan_wire_bytes
    tree_i = router_i.planner.last_tree
    tree_raw = {k: np.ones(v.shape, np.float32)
                for k, v in tree_i.items()}
    planner_raw = KVHandoffPlanner()
    raw = plan_wire_bytes(planner_raw.plan_for(tree_raw))["wire_bytes"]
    wire = plan_wire_bytes(router_i.planner.plan_for(tree_i))[
        "wire_bytes"]
    assert wire < raw and raw / wire > 1.5, (raw, wire)

    # int8 disagg == int8 unified engine, bit for bit (both calibrate
    # their frozen scales on the same first prompt)
    eng = ContinuousBatchingEngine(
        cfg, {k: jnp.asarray(v) for k, v in params.items()},
        max_slots=2, num_pages=33, page_size=16, max_seq_len=128,
        prefill_token_budget=16, enable_prefix_cache=True,
        cache_dtype=jnp.int8)
    erids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    done = {f.rid: f.tokens for f in eng.run()}
    for rid, erid in zip(rids_i, erids):
        np.testing.assert_array_equal(out_i[rid], done[erid])

    # the doctor gate on the flagship (int8) config's real payload
    rep = router_i.planner.check_handoff_budget(
        tree_i, wire_budget_bytes=wire)
    assert rep.ok, rep.summary()
    assert "handoff_wire" in rep.passes_run
    # and the wire gate FIRES on the raw float form under the int8
    # budget (the codec-disabled regression class)
    bad = planner_raw.check_handoff_budget(
        tree_raw, wire_budget_bytes=wire)
    assert bad.codes() == ["COMM004"], bad.summary()


# =====================================================================
# tiered prefix cache
# =====================================================================


def test_host_tier_roundtrip_bit_identical(tiny_model):
    """Pool pressure DEMOTES refcount-0 full pages to pinned host
    instead of evicting; a later lookup PROMOTES them back and the warm
    request replays the cold request's stream bit-for-bit."""
    cfg, model, params = tiny_model
    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    rng = np.random.default_rng(202)
    A = rng.integers(1, 64, (33,)).astype(np.int32)   # 2 full pages
    B = rng.integers(1, 64, (40,)).astype(np.int32)

    def fresh():
        return ContinuousBatchingEngine(
            cfg, jparams, max_slots=1, num_pages=6, page_size=16,
            max_seq_len=64, prefill_token_budget=16,
            enable_prefix_cache=True, host_tier_pages=4)

    cold = fresh()
    cold.add_request(A, max_new_tokens=7)
    ref = cold.run()[0].tokens

    eng = fresh()
    eng.add_request(A, max_new_tokens=7)
    eng.run()
    eng.finished.clear()
    eng.add_request(B, max_new_tokens=24)    # needs 4 pages -> demote
    eng.run()
    st = eng.prefix_cache.stats()
    assert st["demoted_pages"] > 0 and st["evicted_pages"] == 0
    eng.finished.clear()
    eng.add_request(A, max_new_tokens=7)     # warm: promote + hit
    warm = eng.run()[0].tokens
    st = eng.prefix_cache.stats()
    assert st["host_hits"] > 0 and st["promoted_pages"] > 0
    np.testing.assert_array_equal(warm, ref)
    # teardown: the tiered trie still balances the allocator
    eng.prefix_cache.clear()
    eng.alloc.assert_balanced()


def test_cross_replica_host_tier_hit(tiny_model):
    """A host-tier page on ANY replica is reachable fleet-wide: with
    affinity pins off, the router's probe routes a warm prompt to the
    replica whose trie holds the prefix IN THE HOST TIER, and the hit
    promotes (the acceptance's cross-replica host-tier observation —
    hits > 0 structural, like PR 6's gate)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(203)
    sysp = rng.integers(1, 64, (16,)).astype(np.int32)
    a, b = _prompts(rng, (5, 9), shared=sysp)
    router, rs = build_disagg_fleet(
        cfg, params, prefill=2, decode=1, host_tier_pages=4,
        router_cfg=RouterConfig(admission_token_cap=64, affinity=False))
    r0 = router.submit(a, max_new_tokens=4)
    out = router.run()
    warmed = [r for r in rs.serving("prefill")
              if r.engine.prefix_cache.stats()["inserted_pages"] > 0]
    assert len(warmed) == 1
    pre = warmed[0]
    # push the committed page into the host tier
    pre.engine.prefix_cache.evict(1)
    assert pre.engine.prefix_cache.stats()["host_pages"] == 1
    r1 = router.submit(b, max_new_tokens=4)
    out = router.run()
    assert sorted(out) == [r0, r1]
    st = pre.engine.prefix_cache.stats()
    assert st["host_hits"] > 0 and st["promoted_pages"] > 0  # structural
    assert len(pre.engine.prefill_stats) == 2   # probe routed b HERE
    for rid, p, ref in zip([r0, r1], [a, b], _refs(model, [a, b], 4)):
        np.testing.assert_array_equal(out[rid], ref)


# =====================================================================
# two-pool scheduling edges + autoscale
# =====================================================================


@pytest.mark.slow
def test_unified_pool_fallback(tiny_model):
    """An empty decode pool falls back to unified replicas: handoffs
    land there and streams stay bit-identical.  Tier-2 per the tier
    policy (a whole extra fleet spawn for one routing branch); the
    tier-1 parity leg covers the handoff path itself."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(204)
    prompts = _prompts(rng, (6, 10))
    router, rs = build_disagg_fleet(cfg, params, prefill=1, decode=0,
                                    unified=1)
    rids = [router.submit(p, max_new_tokens=4) for p in prompts]
    out = router.run()
    assert sorted(out) == sorted(rids)
    assert router.telemetry["handoffs"] == len(prompts)
    for rid, p, ref in zip(rids, prompts, _refs(model, prompts, 4)):
        np.testing.assert_array_equal(out[rid], ref)


@pytest.mark.slow
def test_autoscale_hysteresis_no_flap(tiny_model):
    """Sustained admission pressure scales the prefill pool UP (once
    per cooldown window, never past max); a drained queue scales it
    back DOWN through the drain path after the idle window — and on the
    fake clock the event log proves it cannot flap: same-pool events
    are spaced by at least ``cooldown_ticks``."""
    cfg, model, params = tiny_model
    clock = _Clock()
    asc = AutoscaleConfig(enabled=True, min_replicas=1, max_replicas=2,
                          up_sustain_ticks=2, down_idle_ticks=4,
                          cooldown_ticks=5)
    router, rs = build_disagg_fleet(
        cfg, params, prefill=1, decode=1, autoscale=asc, clock=clock,
        router_cfg=RouterConfig(admission_token_cap=32))
    rng = np.random.default_rng(205)
    rids = []
    for _ in range(10):                     # the sustained burst
        p = rng.integers(1, 64, (12,)).astype(np.int32)
        rids.append(router.submit(p, max_new_tokens=4))
    for _ in range(60):
        clock.t += 1.0
        router.step()
        if not router.pending():
            break
    # drain long enough for the idle window + cooldown to pass
    for _ in range(2 * (asc.down_idle_ticks + asc.cooldown_ticks)):
        clock.t += 1.0
        router.step()
    out = router.results()
    assert sorted(out) == sorted(rids)      # autoscale lost nothing
    log = router.telemetry["autoscale_log"]
    ups = [ev for ev in log if ev["dir"] == "up"]
    downs = [ev for ev in log if ev["dir"] == "down"]
    assert ups, "sustained pressure never scaled up"
    assert downs, "idle fleet never scaled down"
    assert all(ev["target"] <= asc.max_replicas for ev in ups)
    assert rs.pool_targets()["prefill"] == asc.min_replicas
    # the hysteresis pin: same-pool events spaced >= cooldown_ticks
    by_pool = {}
    for ev in log:
        by_pool.setdefault(ev["pool"], []).append(ev["tick"])
    for pool, ticks in by_pool.items():
        gaps = np.diff(ticks)
        assert (gaps >= asc.cooldown_ticks).all(), (pool, ticks)


def test_multi_prefill_int8_shares_one_calibration(tiny_model):
    """TWO int8 prefill replicas: the router shares the FIRST engine's
    frozen K/V calibration fleet-wide before the second replica could
    freeze its own, so every handoff dequantizes with one scale set
    and streams stay bit-identical to the int8 unified engine (which
    calibrates on the same first prompt)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(208)
    prompts = _prompts(rng, (9, 13, 7))
    router, rs = build_disagg_fleet(
        cfg, params, prefill=2, decode=1, cache_dtype=jnp.int8,
        router_cfg=RouterConfig(admission_token_cap=32, affinity=False))
    rids = []
    for p in prompts:                      # small cap: spreads load
        rids.append(router.submit(p, max_new_tokens=5))
    out = router.run()
    assert sorted(out) == sorted(rids)
    # both prefill engines served work, and every engine holds the
    # SAME frozen scales
    pres = rs.serving("prefill")
    assert sorted(len(r.engine.prefill_stats) > 0 for r in pres) \
        == [True, True]
    ref_scales = router._fleet_kv_scales
    assert ref_scales is not None
    for r in rs.live():
        for k, v in ref_scales.items():
            np.testing.assert_array_equal(
                np.asarray(r.engine.kv_scales[k]), v)
    eng = ContinuousBatchingEngine(
        cfg, {k: jnp.asarray(v) for k, v in params.items()},
        max_slots=2, num_pages=33, page_size=16, max_seq_len=128,
        prefill_token_budget=16, enable_prefix_cache=True,
        cache_dtype=jnp.int8)
    erids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    done = {f.rid: f.tokens for f in eng.run()}
    for rid, erid in zip(rids, erids):
        np.testing.assert_array_equal(out[rid], done[erid])


def test_prefill_only_engine_guards(tiny_model):
    """Constructor/adopt contracts: prefill_only excludes speculation;
    the host tier needs the prefix cache; adopt refuses prefill-only
    engines and mismatched pools."""
    cfg, model, params = tiny_model
    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    kw = dict(max_slots=2, num_pages=17, page_size=16, max_seq_len=64)
    with pytest.raises(ValueError, match="prefill_only"):
        ContinuousBatchingEngine(cfg, jparams, prefill_only=True,
                                 speculative_k=2, draft_params=jparams, **kw)
    with pytest.raises(ValueError, match="host_tier"):
        ContinuousBatchingEngine(cfg, jparams, prefill_token_budget=16,
                                 host_tier_pages=2, **kw)
    pre = ContinuousBatchingEngine(cfg, jparams, prefill_token_budget=16,
                                   prefill_only=True, **kw)
    with pytest.raises(ValueError, match="decode-capable"):
        pre.adopt_request({"k": np.zeros(1), "v": np.zeros(1)},
                          {"seq_len": 1, "first_token": 0,
                           "page_size": 16}, 4)
    dec = ContinuousBatchingEngine(cfg, jparams, prefill_token_budget=16,
                                   **kw)
    with pytest.raises(ValueError, match="page_size"):
        dec.adopt_request({"k": np.zeros(1), "v": np.zeros(1)},
                          {"seq_len": 1, "first_token": 0,
                           "page_size": 32}, 4)


# =====================================================================
# breadth: long fault x load sweep (tier-2 per the ROADMAP policy)
# =====================================================================


@pytest.mark.slow
def test_disagg_fault_and_load_sweep(tiny_model):
    """Tier-2 breadth: a prefill-replica kill AND a decode-replica kill
    plus a sustained overload burst through the two-pool router with
    autoscale enabled — zero accepted requests lost, every greedy
    stream bit-identical, both pools healed to target."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(206)
    sysp = rng.integers(1, 64, (16,)).astype(np.int32)
    named = _prompts(rng, (5, 9, 13), shared=sysp) + _prompts(rng, (7, 11))
    requests = [(t % 2, p, 6) for t, p in enumerate(named)]
    router, rs = build_disagg_fleet(
        cfg, params, prefill=1, decode=2,
        autoscale=AutoscaleConfig(enabled=True, min_replicas=1,
                                  max_replicas=3, up_sustain_ticks=3,
                                  down_idle_ticks=6, cooldown_ticks=5),
        scripts={0: [ReplicaFaultEvent(step=5, kind="kill")],
                 2: [ReplicaFaultEvent(step=3, kind="kill")]},
        router_cfg=RouterConfig(admission_token_cap=48))
    res = run_fleet_trace(
        router, requests,
        bursts=[OverloadBurst(tick=2, n_requests=4, duration=6,
                              prompt_len=20, max_new_tokens=4)],
        seed=206)
    out = router.results()
    assert sorted(out) == sorted(res["rids"])
    for rid, prompt, mnew in res["submitted"]:
        ref = _refs(model, [prompt], mnew)[0]
        np.testing.assert_array_equal(
            out[rid], ref, err_msg=f"rid {rid} diverged under the "
                                   f"fault x load sweep")
    faults = sorted(ev.fault for ev in router.telemetry["recoveries"])
    assert faults == ["ReplicaKilled", "ReplicaKilled"]
    assert router.telemetry["handoffs"] > 0
    assert len(rs.serving("prefill")) >= 1
    assert len(rs.serving("decode")) >= 1


@pytest.mark.slow
def test_disagg_int8_full_trace(tiny_model):
    """Tier-2 breadth: the int8-KV disaggregated fleet under a longer
    mixed trace with a decode kill — parity against the int8 unified
    engine held end to end (the tier-1 leg keeps a 2-request
    representative)."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(207)
    prompts = _prompts(rng, (5, 9, 13, 17, 7, 11))
    router, rs = build_disagg_fleet(
        cfg, params, prefill=1, decode=2, cache_dtype=jnp.int8,
        scripts={1: [ReplicaFaultEvent(step=4, kind="kill")]})
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]
    out = router.run()
    assert sorted(out) == sorted(rids)
    eng = ContinuousBatchingEngine(
        cfg, {k: jnp.asarray(v) for k, v in params.items()},
        max_slots=2, num_pages=65, page_size=16, max_seq_len=128,
        prefill_token_budget=16, enable_prefix_cache=True,
        cache_dtype=jnp.int8)
    erids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    done = {f.rid: f.tokens for f in eng.run()}
    for rid, erid in zip(rids, erids):
        np.testing.assert_array_equal(out[rid], done[erid])
