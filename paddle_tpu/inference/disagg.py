"""Disaggregated prefill/decode serving over a tiered KV plane
(round-16 tentpole).

The unified engine (round 11) deliberately mixes chunked prefill INTO
the decode step so one replica serves both phases.  At heavy traffic
the opposite split wins — the production pattern behind
Ragged-Paged-Attention-class TPU serving (PAPERS.md 2604.15464):
dedicated PREFILL replicas absorb prompt bursts while DECODE replicas
keep p99 per-token latency flat regardless of the prompt-length
distribution.  Every primitive already exists in-repo; this module
composes them:

- **split pools** — ``ReplicaSet`` replicas carry a ``role``
  (``prefill | decode | unified``); prefill replicas run prompt-only
  ragged steps (``ContinuousBatchingEngine(prefill_only=True)`` — no
  decode slots, prompt pages only), decode replicas run decode/verify
  steps and receive their prompt KV by handoff.  Either pool being
  empty falls back to unified replicas, so a disaggregated fleet
  degrades to the round-13 fleet, never to an outage.

- **KV handoff as a reshard-engine route** — a finished prefill's
  per-layer KV pages (``engine.export_handoff``: host-staged
  ``{"k","v"}`` of shape ``[L, npages, kvh, page, d]`` in the CACHE
  dtype) become a ``plan_reshard`` tree.  ``KVHandoffPlanner`` plans
  ONCE per (src, dst) topology + payload signature and streams per
  handoff — the same plan-once/stream-per-replica discipline as weight
  delivery — executing through ``reshard.execute_encoded`` when a
  handoff codec is configured.  With the int8 KV cache (round 13) the
  payload is ALREADY the quantized wire form: int8 leaves ride the
  codec's bit-exact integer path, so the handoff moves ~1 byte/element
  with NO added loss — which is why the flagship disagg config is
  int8-KV and why disaggregated greedy output stays BIT-IDENTICAL to
  the unified engine.  (A float-cache fleet hands off bit-exact float
  pages; opting a float cache INTO the block-scaled codec is the only
  lossy combination and is therefore not the default.)
  ``check_handoff_budget`` prices the plan through the Graph Doctor's
  MEM001 budget (seeded proof: ``MEM001[kv_handoff]`` in
  analysis/fixtures.py) and gates the structural wire bytes
  (``reshard.plan_wire_bytes``) against a declared COMM004-style
  handoff wire budget.

- **tiered prefix cache** — the radix cache's LRU now DEMOTES
  refcount-0 full pages to ``pinned_host`` (parallel/memory.py
  residency primitives) instead of evicting, and promotes on hit (page_cache.PrefixCache,
  ``host_tier_pages``).  The router makes a host-tier page on ANY
  replica reachable fleet-wide: ``PrefixCache.probe`` answers
  cross-replica reachability queries and ``DisaggRouter`` prefers the
  replica holding the longest cached prefix — device or host tier.

- **two-pool scheduling** — ``DisaggRouter`` admits prefill by
  outstanding-TOKEN budget (``admission_token_cap`` per prefill
  replica) and decode by SLOT occupancy (free engine slots), with
  SEPARATE degradation ladders per pool (prefill: shrink the chunk
  budget then reject; decode: shed speculation then reject) and a
  load-driven autoscale policy that moves ``FleetConfig.pool_targets``
  per pool — scale-up on sustained admission pressure, scale-down
  through the existing drain path, hysteresis so it cannot flap.

Fault tolerance is inherited, not reimplemented: a decode replica
dying mid-stream migrates its requests through the round-13 replay
path (prompt ++ committed tokens re-enqueues), which re-prefills on
the prefill pool and hands off AGAIN — the mid-decode handoff the
acceptance gate demands — and greedy output stays bit-identical
because the unified step computes identical logits for a position
whether it arrives as prefill or decode.

Gated the repo's way (tests/test_serving_disagg.py + the
``serving_disagg`` bench smoke leg): disaggregated greedy output
bit-identical to the unified engine on the same trace (warm
prefix-cache hits and a mid-decode handoff included), handoff plan
MEM001-clean with the int8 wire measurably below raw, host-tier
demote→promote bit-identical, autoscale hysteresis pinned on the fake
clock.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .fleet import FleetRouter, Replica, RouterConfig

logger = logging.getLogger(__name__)

DEFAULT_HANDOFF_TRANSIENT = 8 << 20


# ---------------------------------------------------------------------------
# KV handoff: plan-once / stream-per-handoff over the reshard engine
# ---------------------------------------------------------------------------


class KVHandoffPlanner:
    """The KV handoff stream: ``plan_reshard`` over a finished
    prefill's page tree, cached per (destination topology, payload
    signature) — prompt-length buckets collapse onto few signatures
    because pages quantize lengths — and re-executed per handoff.
    ``codec`` (a parallel/codec.CollectiveCodec) routes delivery
    through ``execute_encoded``: float pages would be block-scale
    quantized (lossy, opt-in), int8 pages ride its bit-exact integer
    path, so the flagship int8-KV fleet pays no added error."""

    def __init__(self, *, dst_mesh=None, codec=None,
                 max_transient_bytes: Optional[int] =
                 DEFAULT_HANDOFF_TRANSIENT,
                 budget_bytes: Optional[int] = None,
                 wire_budget_bytes: Optional[int] = None):
        self.dst_mesh = dst_mesh
        self.codec = codec
        self.max_transient_bytes = max_transient_bytes
        self.budget_bytes = budget_bytes
        self.wire_budget_bytes = wire_budget_bytes
        self._plans: Dict[Any, Any] = {}
        self.last_tree = None          # the doctor/bench entry payload
        self.telemetry: Dict[str, Any] = {
            "plans_built": 0, "handoffs": 0,
            "bytes_raw": 0, "bytes_wire": 0}

    def _mesh(self):
        if self.dst_mesh is not None:
            return self.dst_mesh
        import jax
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices()[:1], dtype=object)
        return Mesh(devs, ("replica",))

    def _key(self, tree):
        from ..distributed import topology as topo
        from ..parallel.reshard import path_leaves

        mesh = self._mesh()
        sig = tuple((p, tuple(np.shape(v)), str(np.asarray(v).dtype
                                                if not hasattr(v, "dtype")
                                                else v.dtype))
                    for p, v in path_leaves(tree)[0])
        return (tuple(mesh.axis_names),
                tuple(int(mesh.shape[a]) for a in mesh.axis_names),
                topo.mesh_device_ids(mesh), sig)

    def plan_for(self, tree):
        """The cached redistribution plan for this payload signature —
        plan once, stream per handoff."""
        key = self._key(tree)
        plan = self._plans.get(key)
        if plan is None:
            from ..parallel.reshard import plan_reshard

            plan = plan_reshard(
                tree, self._mesh(), None,
                max_transient_bytes=self.max_transient_bytes)
            self._plans[key] = plan
            self.telemetry["plans_built"] += 1
        return plan

    def deliver(self, tree):
        """Stream one handoff: execute the cached plan (codec-routed
        when configured) and account the structural wire bytes."""
        from ..parallel.reshard import execute_encoded, plan_wire_bytes

        plan = self.plan_for(tree)
        wb = plan_wire_bytes(plan, codec=self.codec)
        self.telemetry["handoffs"] += 1
        self.telemetry["bytes_raw"] += wb["raw_bytes"]
        self.telemetry["bytes_wire"] += wb["wire_bytes"]
        self.last_tree = tree
        if self.codec is not None:
            return execute_encoded(plan, tree, self.codec)
        return plan.execute(tree)

    def uncount(self, tree):
        """Reverse one ``deliver``'s accounting — a delivered payload
        whose adoption was refused never landed, and telemetry records
        DELIVERED handoffs only.  The inverse lives next to the
        bookkeeping it inverts."""
        from ..parallel.reshard import plan_wire_bytes

        wb = plan_wire_bytes(self.plan_for(tree), codec=self.codec)
        self.telemetry["handoffs"] -= 1
        self.telemetry["bytes_raw"] -= wb["raw_bytes"]
        self.telemetry["bytes_wire"] -= wb["wire_bytes"]

    def check_handoff_budget(self, tree, *,
                             budget_bytes: Optional[int] = None,
                             wire_budget_bytes: Optional[int] = None,
                             exemptions=(), target: str = "kv_handoff"):
        """Price one handoff payload: the Graph Doctor's MEM001 budget
        over the plan's worst step (``check_reshard_budget``) plus the
        COMM004-style structural wire gate — handoff bytes-on-the-wire
        over a declared budget is the same finding class as a silently
        disabled DCN codec (one dropped int8 cache re-inflates every
        handoff 2-4x)."""
        from ..analysis.findings import Finding
        from ..parallel.reshard import (check_reshard_budget,
                                        plan_wire_bytes)

        budget = budget_bytes
        if budget is None:
            budget = self.budget_bytes or self.max_transient_bytes
        plan = self.plan_for(tree)
        rep = check_reshard_budget(plan, tree, budget_bytes=budget,
                                   exemptions=exemptions, target=target,
                                   codec=self.codec)
        wire_budget = (wire_budget_bytes if wire_budget_bytes is not None
                       else self.wire_budget_bytes)
        if wire_budget is not None:
            wb = plan_wire_bytes(plan, codec=self.codec)
            rep.passes_run = tuple(rep.passes_run) + ("handoff_wire",)
            if wb["wire_bytes"] > int(wire_budget):
                rep.findings.append(Finding(
                    code="COMM004",
                    message=(f"KV handoff moves {wb['wire_bytes']} "
                             f"bytes on the wire against a declared "
                             f"budget of {int(wire_budget)} (raw "
                             f"{wb['raw_bytes']}) — the int8 KV page "
                             f"form or a handoff codec is the fix"),
                    pass_name="handoff_wire",
                    data=dict(wb, budget=int(wire_budget))))
        return rep


# ---------------------------------------------------------------------------
# load-driven autoscale (ROADMAP fleet item (b))
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AutoscaleConfig:
    """Per-pool load-driven autoscale over ``FleetConfig.pool_targets``.

    Scale-UP on SUSTAINED admission pressure — ``up_sustain_ticks``
    consecutive ticks where the pool rejected work (prefill: the queue
    could not fully dispatch or submits were shed; decode: handoffs
    were left parked for want of slots).  Scale-DOWN reuses the drain
    path after ``down_idle_ticks`` consecutive idle ticks.  Both
    directions honor a ``cooldown_ticks`` hysteresis window per pool —
    after any action, NO action (either direction) until the window
    expires, so an oscillating load cannot flap the fleet (the pinned
    fake-clock test)."""

    enabled: bool = True
    min_replicas: int = 1
    max_replicas: int = 4
    up_sustain_ticks: int = 3
    down_idle_ticks: int = 8
    cooldown_ticks: int = 6


# ---------------------------------------------------------------------------
# the two-pool router
# ---------------------------------------------------------------------------


class DisaggRouter(FleetRouter):
    """FleetRouter over a role-split ReplicaSet (see module docstring).

    One tick = ladders → dispatch (prefill pool, token-budget
    admission, fleet-wide prefix reachability) → replica steps →
    KV handoffs (decode pool, slot-occupancy admission) → harvest →
    deadlines → autoscale → reap/respawn.  Single-threaded and
    deterministic like the base router."""

    def __init__(self, replica_set, config: Optional[RouterConfig] = None,
                 *, planner: Optional[KVHandoffPlanner] = None,
                 autoscale: Optional[AutoscaleConfig] = None,
                 clock: Callable[[], float] = time.monotonic):
        # per-pool ladder state must exist before the base constructor
        # applies stage knobs to the freshly spawned fleet
        self.stage_prefill = 0
        self.stage_decode = 0
        self.planner = planner or KVHandoffPlanner()
        self.autoscale_cfg = autoscale or AutoscaleConfig(enabled=False)
        self._as_up_streak = {"prefill": 0, "decode": 0}
        self._as_idle_streak = {"prefill": 0, "decode": 0}
        self._as_cooldown_until = {"prefill": 0, "decode": 0}
        self._pressure = {"prefill": False, "decode": False}
        # the fleet's ONE frozen int8 K/V calibration (host copies of
        # the first engine's kv_scales): shared into every
        # still-uncalibrated engine so a second prefill replica (or a
        # respawn) never freezes divergent scales — adopt_request's
        # scale-equality guard turns any leak past this into a loud
        # error instead of silently-wrong dequantization
        self._fleet_kv_scales = None
        super().__init__(replica_set, config, clock=clock)
        self.telemetry.update({
            "handoffs": 0, "handoffs_mid_decode": 0,
            "handoff_backlog_ticks": 0, "completed_at_prefill": 0,
            "autoscale_log": []})

    # -- pools -------------------------------------------------------------

    def _prefill_pool(self) -> List[Replica]:
        return self.set.serving("prefill") or self.set.serving("unified")

    def _decode_pool(self, exclude: Optional[int] = None) -> List[Replica]:
        pool = self.set.serving("decode") or self.set.serving("unified")
        return [r for r in pool if r.id != exclude]

    # -- dispatch: prefill admission by token budget -----------------------

    def _pick_replica(self, req) -> Optional[Replica]:
        """Prefill-pool pick: fleet-wide prefix reachability first (the
        replica whose radix trie — device OR host tier — holds the
        longest full-page prefix of this prompt), then the base
        affinity-pin/least-loaded rule, always under the per-replica
        outstanding-token admission budget.  Sampled (temperature>0)
        requests route through the SAME pools since round-17: the
        per-slot PRNG state rides the handoff payload
        (serving.export_handoff meta), so the decode side resumes the
        seeded stream mid-state instead of pinning to a unified pool."""
        cands = self._prefill_pool()
        if not cands:
            return None
        cap = self.cfg.admission_token_cap
        fits = [r for r in cands
                if self._outstanding(r) + req.footprint() <= cap]
        if not fits:
            return None
        best, best_m = None, 0
        for r in fits:
            pc = getattr(r.engine, "prefix_cache", None)
            if pc is None:
                continue
            m = pc.probe(req.prompt)
            if m > best_m:
                best, best_m = r, m
        if best is not None:
            return best
        key = self._affinity_key(req)
        if key is not None:
            pin = self._affinity.get(key)
            rep = next((r for r in fits if r.id == pin), None)
            if rep is not None:
                self._pin(key, rep.id)
                return rep
        rep = min(fits, key=lambda r: (self._outstanding(r), r.id))
        if key is not None:
            self._pin(key, rep.id)
        return rep

    # -- one fleet, one int8 calibration -----------------------------------

    def _share_calibration(self, eng) -> None:
        """Install the fleet calibration on a still-uncalibrated int8
        engine (new prefill prompt, respawned replica, adoption
        target) BEFORE it could calibrate its own."""
        import jax.numpy as jnp

        if (self._fleet_kv_scales is not None
                and getattr(eng, "kv_scales", None) is None
                and np.dtype(eng.cache_dtype) == np.dtype(np.int8)):
            eng.kv_scales = {k: jnp.asarray(v)
                             for k, v in self._fleet_kv_scales.items()}

    def _capture_calibration(self, eng) -> None:
        if (self._fleet_kv_scales is None
                and getattr(eng, "kv_scales", None) is not None):
            self._fleet_kv_scales = {
                k: np.asarray(v) for k, v in eng.kv_scales.items()}

    def _assign(self, req, rep) -> None:
        """Every engine add_request routes through here — the exact
        point where a first real prompt would freeze an engine's own
        calibration, so share the fleet's first (or capture it)."""
        self._share_calibration(rep.engine)
        super()._assign(req, rep)
        self._capture_calibration(rep.engine)

    # -- the KV handoff phase: decode admission by slot occupancy ----------

    def _pick_decode_replica(self, seq_len: int, remaining: int,
                             exclude: Optional[int] = None
                             ) -> Optional[Replica]:
        """Least-occupied decode replica that can ACTUALLY adopt this
        handoff (free slot + pages, ``engine.can_adopt``) — the
        capacity gate runs before the expensive page export/stream, so
        backpressure costs a parked prefill slot, never a delivered
        payload."""
        best = None
        for r in self._decode_pool(exclude):
            eng = r.engine
            if not eng.can_adopt(seq_len, remaining):
                continue
            occ = int(np.count_nonzero(eng.active))
            if best is None or occ < best[0]:
                best = (occ, r)
        return best[1] if best else None

    def _do_handoffs(self) -> int:
        """Stream every handoff-ready prefill slot to a decode replica
        through the cached reshard plan.  No decode capacity leaves the
        slot parked (pages reserved on the prefill replica — explicit
        backpressure, counted, retried next tick)."""
        moved = 0
        backlog = 0
        for rep in list(self.set.live()):
            eng = rep.engine
            if eng is None or not getattr(eng, "handoff_ready", None):
                continue
            amap = self._assigned.get(rep.id, {})
            for slot in list(eng.handoff_ready):
                info = eng.handoff_ready[slot]
                req = amap.get(info["rid"])
                if req is None:
                    # canceled / migrated since parking: nothing owns
                    # this slot any more
                    eng.release_handoff(slot)
                    continue
                first = int(info["first_token"])
                if req.remaining <= 1 or first == eng.eos_id:
                    # the first token already completes the request —
                    # commit it router-side, never moving any KV
                    req.emitted.append(first)
                    del amap[info["rid"]]
                    eng.release_handoff(slot)
                    self._complete(req)
                    self.telemetry["completed_at_prefill"] += 1
                    continue
                dst = self._pick_decode_replica(
                    int(info["seq_len"]), req.remaining, exclude=rep.id)
                if dst is None:
                    backlog += 1
                    continue
                tree, meta = eng.export_handoff(slot)
                placed = self.planner.deliver(tree)
                mid_decode = bool(np.count_nonzero(dst.engine.active))
                new_rid = dst.engine.adopt_request(
                    placed, meta, max_new_tokens=req.remaining)
                if new_rid is None:
                    # can_adopt was optimistic (classic-cache interior
                    # pages): the payload did not land — un-count it
                    self.planner.uncount(tree)
                    backlog += 1
                    continue
                eng.release_handoff(slot)
                del amap[info["rid"]]
                req.replica, req.engine_rid = dst.id, new_rid
                req.harvested = 0
                req.dispatched_at = self.clock()
                self._assigned.setdefault(dst.id, {})[new_rid] = req
                self.telemetry["handoffs"] += 1
                if req.emitted or mid_decode:
                    # either the REQUEST is mid-stream (a replayed
                    # migration) or the destination engine is actively
                    # decoding other slots — both are the "handoff into
                    # live decode" shape the acceptance gate wants seen
                    self.telemetry["handoffs_mid_decode"] += 1
                moved += 1
        self._pressure["decode"] = backlog > 0
        if backlog:
            self.telemetry["handoff_backlog_ticks"] += 1
        return moved

    # -- per-pool degradation ladders --------------------------------------

    def _update_ladder(self) -> None:
        """Two pressures, two ladders, one stage move per tick each —
        same engage-in-order/hysteresis discipline as the base ladder.
        ``self.stage`` stays the max of the two so the base submit()
        reject gate and telemetry keep their meaning."""
        prefill_cap = max(1, len(self._prefill_pool())) \
            * self.cfg.admission_token_cap
        p_prefill = self._queued_tokens() / prefill_cap
        slots = sum(r.engine.max_slots for r in self._decode_pool()) or 1
        occ = sum(int(np.count_nonzero(r.engine.active))
                  for r in self._decode_pool())
        parked = sum(len(getattr(r.engine, "handoff_ready", ()))
                     for r in self.set.live())
        p_decode = (occ + parked) / slots
        for pool, pressure in (("prefill", p_prefill),
                               ("decode", p_decode)):
            stage = getattr(self, f"stage_{pool}")
            if pressure > self.cfg.overload_high and stage < 3:
                self._set_pool_stage(pool, stage + 1, pressure)
            elif pressure < self.cfg.overload_low and stage > 0:
                self._set_pool_stage(pool, stage - 1, pressure)
        self._pressure["prefill"] = p_prefill > self.cfg.overload_high

    def _set_pool_stage(self, pool: str, stage: int, pressure: float):
        prev = getattr(self, f"stage_{pool}")
        setattr(self, f"stage_{pool}", stage)
        self.stage = max(self.stage_prefill, self.stage_decode)
        self.telemetry["ladder_log"].append(
            {"tick": self._tick, "pool": pool, "from": prev,
             "to": stage, "pressure": round(float(pressure), 3)})
        logger.warning("[disagg] %s ladder %d -> %d (pressure %.2f)",
                       pool, prev, stage, pressure)
        self._apply_stage_knobs()

    def _apply_stage_knobs(self, replicas=None) -> None:
        """Per-pool throttles: the prefill ladder shrinks the chunk
        budget (halve, then floor), the decode ladder sheds speculation
        — each pool degrades along its own axis, and stage 3 of either
        rejects at submit (the base gate on ``self.stage``)."""
        for rep in (replicas if replicas is not None else self.set.live()):
            eng = rep.engine
            if eng is None:
                continue
            if rep.role in ("prefill", "unified"):
                floor = min(self.cfg.min_prefill_budget,
                            eng._init_prefill_budget)
                if self.stage_prefill >= 2:
                    budget = floor
                elif self.stage_prefill >= 1:
                    budget = max(floor, eng._init_prefill_budget // 2)
                else:
                    budget = eng._init_prefill_budget
                eng.throttle(prefill_token_budget=budget)
            if rep.role in ("decode", "unified"):
                eng.throttle(speculative_k=(
                    0 if self.stage_decode >= 1 else eng._init_spec_k))

    # -- load-driven autoscale ---------------------------------------------

    def _pool_idle(self, pool: str) -> bool:
        if pool == "prefill":
            busy = any(self._assigned.get(r.id)
                       for r in self.set.live("prefill"))
            return not self.queue and not busy
        busy = any(self._assigned.get(r.id)
                   for r in self.set.live("decode"))
        return not busy

    def _autoscale(self) -> None:
        """Move ``FleetConfig.pool_targets`` per pool from the router's
        own pressure signals, with hysteresis (AutoscaleConfig)."""
        cfg = self.autoscale_cfg
        targets = self.set.config.pool_targets
        if not cfg.enabled or targets is None:
            return
        for pool in ("prefill", "decode"):
            if pool not in targets:
                continue
            pressured = self._pressure[pool] or (
                pool == "prefill" and bool(self.queue))
            self._as_up_streak[pool] = \
                self._as_up_streak[pool] + 1 if pressured else 0
            self._as_idle_streak[pool] = \
                self._as_idle_streak[pool] + 1 \
                if self._pool_idle(pool) else 0
            if self._tick < self._as_cooldown_until[pool]:
                continue
            if (self._as_up_streak[pool] >= cfg.up_sustain_ticks
                    and targets[pool] < cfg.max_replicas):
                targets[pool] += 1
                self._as_cooldown_until[pool] = \
                    self._tick + cfg.cooldown_ticks
                self._as_up_streak[pool] = 0
                self.telemetry["autoscale_log"].append(
                    {"tick": self._tick, "pool": pool, "dir": "up",
                     "target": targets[pool]})
            elif (self._as_idle_streak[pool] >= cfg.down_idle_ticks
                    and targets[pool] > cfg.min_replicas):
                targets[pool] -= 1
                self._as_cooldown_until[pool] = \
                    self._tick + cfg.cooldown_ticks
                self._as_idle_streak[pool] = 0
                victim = next(
                    (r for r in self.set.serving(pool)
                     if not self._assigned.get(r.id)), None)
                if victim is not None:
                    self.drain(victim.id)   # scale-down IS the drain path
                self.telemetry["autoscale_log"].append(
                    {"tick": self._tick, "pool": pool, "dir": "down",
                     "target": targets[pool]})

    # -- the tick ----------------------------------------------------------

    def step(self) -> int:
        """One disaggregated router tick."""
        self._tick += 1
        self._update_ladder()
        self._dispatch()
        self._step_replicas()
        self._do_handoffs()
        produced = self._harvest()
        self._check_deadlines()
        self._autoscale()
        self._reap_and_respawn()
        return produced

    def stats(self) -> Dict[str, Any]:
        t = super().stats()
        t["stage_prefill"] = self.stage_prefill
        t["stage_decode"] = self.stage_decode
        t["handoff"] = dict(self.planner.telemetry)
        t["pool_targets"] = dict(self.set.pool_targets())
        return t
