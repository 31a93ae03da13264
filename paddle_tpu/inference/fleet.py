"""Serving resilience plane (round-13 tentpole): replica fleet manager,
SLO-aware router, request-level fault tolerance.

PR 6 built the single-replica unified serving plane; PR 7 made TRAINING
preemption-tolerant.  This module is the serving half of that resilience
core: a fleet of ``ContinuousBatchingEngine`` replicas whose weights
arrive through the portable reshard engine and whose requests ride a
router that survives replica loss without losing or corrupting a single
request.

Three layers:

- ``ReplicaSet`` — replica lifecycle (spawn → warm → serve → drain →
  remove).  Weight delivery is PLAN-ONCE / STREAM-PER-REPLICA: the
  redistribution of the host weights onto the serving topology is
  planned by ``parallel.reshard.plan_reshard`` exactly once per
  topology (size-capped steps, so the delivery transient stays bounded
  no matter how large the model) and every new/replacement replica
  re-executes the cached plan.  ``check_delivery_budget`` prices the
  plan's worst step through the Graph Doctor's MEM001 budget — the
  seeded ``MEM001[replica_delivery]`` fixture proves an unbounded
  delivery is caught.  Health is the comm watchdog: every replica step
  runs inside a ``comm_watch`` window (the heartbeat), and a flagged
  step raises ``ReplicaHung`` — the same scanner that watches training
  collectives watches serving steps.

- ``FleetRouter`` — continuous batching ACROSS replicas.  Dispatch is
  prefix-cache-affine: the FIRST full prompt page (the trie's own
  sharing granularity — body-length-independent) is hashed and pinned
  to a replica, so a shared system prompt warms each replica's radix
  trie once, not once per request.  Admission control rides on top of
  the engines' per-chunk prefill/decode token budgets: a replica only
  accepts a request while its outstanding prompt+generation tokens fit
  ``admission_token_cap``.  Per-request deadline/timeout withdraws a
  stalled request (``engine.cancel`` — no Finished record) and retries
  it elsewhere after a jittered exponential backoff; committed tokens
  are kept, so a retry can never re-emit them.  Under pressure the
  router degrades along an ordered ladder — shed speculative decoding,
  shrink the prefill chunk budget, reject with explicit overload
  telemetry — one stage per router tick, so the ladder ENGAGES IN
  ORDER and queue growth is never silent.

- request migration — when a replica is killed or hung mid-decode, its
  in-flight requests re-enqueue at the head of the router queue and
  replay on survivors from the original prompt PLUS the tokens the
  router already committed (prompt ++ emitted becomes the replay
  prompt; the survivor's prefix cache serves whatever full pages it
  already holds).  Because the unified engine computes identical
  logits for a position whether it arrives as prefill or decode,
  greedy outputs after migration are BIT-IDENTICAL to an unfaulted
  run — the property tests/test_serving_fleet.py pins.

The fault-injection harness (tests/fault_injection.py ``FakeReplica``)
drives kill/hang/slow/preempt and scripted overload bursts through this
module end-to-end in one process; ``bench.py --serving-fleet-trace``
records recovery time, shed rate and p99-under-fault.
"""

from __future__ import annotations

import dataclasses
import logging
import random
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

import numpy as np

from ..distributed.resilience import ReplicaHung, ServingRecoveryEvent
from ..distributed.store import jittered_backoff
from ..distributed.watchdog import comm_watch

logger = logging.getLogger(__name__)

# lifecycle states (spawn -> warm -> serve -> drain -> remove; dead is
# the involuntary exit)
SPAWNING = "spawning"
WARMING = "warming"
SERVING = "serving"
DRAINING = "draining"
DEAD = "dead"
REMOVED = "removed"


class OverloadRejected(RuntimeError):
    """Admission rejected at the ladder's top stage — the EXPLICIT
    overload signal (callers see a typed error + telemetry counter,
    never silent queue growth)."""


# ---------------------------------------------------------------------------
# replica
# ---------------------------------------------------------------------------


class Replica:
    """One serving replica: an engine + lifecycle + watchdog heartbeat.

    ``engine_factory(params) -> ContinuousBatchingEngine`` builds the
    replica's engine from its DELIVERED weights (the ReplicaSet executes
    the cached reshard plan and hands the placed tree in) — page pools,
    prefix-cache trie and scheduler state are per-replica by
    construction.  ``step()`` wraps the engine step in a ``comm_watch``
    window: the watchdog scanner thread is the heartbeat monitor, and a
    flagged step raises ``ReplicaHung`` so the router can treat the
    step's output as suspect and migrate."""

    def __init__(self, replica_id: int, engine_factory: Callable,
                 step_timeout_s: float = 0.0, role: str = "unified"):
        self.id = int(replica_id)
        self._factory = engine_factory
        self.step_timeout_s = float(step_timeout_s)
        # round-16 disaggregated serving: which POOL this replica
        # serves — "prefill" (prompt-only engine, KV hands off),
        # "decode" (continuation-only by routing) or "unified" (both).
        # The ReplicaSet stamps it at spawn; the engine's own
        # prefill_only flag is the enforcement, the role is the
        # router's scheduling key.
        self.role = role
        self.state = SPAWNING
        self.engine = None
        self.fault: Optional[BaseException] = None
        self.steps = 0                      # completed engine steps
        self.last_beat: Optional[float] = None
        self.spawned_at = time.monotonic()

    def warm(self, params) -> None:
        """Build the engine from the delivered weights, compile its
        step, then report SERVING."""
        self.state = WARMING
        self.engine = self._factory(params)
        self._warmup()
        self.state = SERVING

    def _warmup(self) -> None:
        """Compile the unified step BEFORE the replica reports SERVING:
        the watchdog heartbeat must time the steady-state step, not the
        first-step jit compile (a cold replica would otherwise be
        flagged hung the moment it took real traffic).  One throwaway
        2-token request — too short to commit a prefix-cache page —
        generates THREE tokens: the first launch compiles against the
        engine's fresh (uncommitted) page pools, the later ones against
        the pools the first launch returned committed to the delivery
        sharding, and — under speculative decoding — the budget leaves
        room for one draft proposal round, compiling the proposal
        launch too.  Every jit variant real traffic hits is warm before
        SERVING; its records are scrubbed afterwards."""
        eng = self.engine
        rid = eng.add_request(np.asarray([1, 2], np.int32),
                              max_new_tokens=3)
        for _ in range(64):
            eng.step()
            # a prefill-only engine parks the completed dummy for KV
            # handoff: drain it through the export path, warming the
            # page-gather dispatch the real handoffs use
            for slot in list(getattr(eng, "handoff_ready", ())):
                eng.export_handoff(slot)
                eng.release_handoff(slot)
            if not eng.active.any() and not eng.queue:
                break
        eng.finished.clear()
        eng.prefill_stats.pop(rid, None)
        if np.dtype(eng.cache_dtype) == np.dtype(np.int8):
            # the dummy must not become the one-shot int8 calibration
            # prompt: drop its throwaway scales so the FIRST REAL
            # submission calibrates on real activations (the dummy's
            # quantized pages were released; nothing live used them —
            # and calibration runs at add_request, OUTSIDE the
            # heartbeat window, so the recalibration compile cannot be
            # flagged as a hang)
            eng.kv_scales = None

    def step(self) -> int:
        """One engine step under the watchdog heartbeat.  Any exception
        out of the engine (typed ReplicaFault injection or a raw engine
        error) propagates to the router, which treats it as THIS
        replica's death — never the fleet's; a step the watchdog
        flagged raises ``ReplicaHung`` AFTER the late result arrives —
        the terminal timed_out state is decided by the scanner under
        the manager lock, so a hung verdict is never retracted by a
        late completion."""
        with comm_watch(f"replica[{self.id}].step",
                        timeout_s=self.step_timeout_s) as task:
            produced = self._engine_step()
        self.steps += 1
        self.last_beat = time.monotonic()
        if task.timed_out:
            raise ReplicaHung(
                f"replica {self.id} step flagged by the watchdog after "
                f"{task.elapsed():.2f}s > {task.timeout_s:.2f}s")
        return produced

    def _engine_step(self) -> int:
        """The injection point FakeReplica overrides (kill/stall INSIDE
        the watch window)."""
        return self.engine.step()

    @property
    def alive(self) -> bool:
        return self.state in (SPAWNING, WARMING, SERVING, DRAINING)

    def __repr__(self):
        return f"Replica(id={self.id}, state={self.state}, steps={self.steps})"


# ---------------------------------------------------------------------------
# fleet manager
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetConfig:
    target_replicas: int = 2
    # round-16 disaggregated pools: role -> target replica count (None
    # keeps the classic single unified pool at ``target_replicas``).
    # The autoscale policy (inference/disagg.py) MUTATES this mapping;
    # ensure_target respawns per pool, so a dead prefill replica is
    # replaced by a prefill replica.
    pool_targets: Optional[Dict[str, int]] = None
    step_timeout_s: float = 0.0            # 0 = heartbeat watchdog off
    # weight-delivery plan transient cap (the reshard planner's
    # size-capped steps) and the doctor budget the plan is priced
    # against (None = use the cap)
    max_transient_bytes: Optional[int] = 64 << 20
    delivery_budget_bytes: Optional[int] = None
    # round-15: the quantized weight-delivery codec
    # (parallel/codec.CollectiveCodec, weight profile).  When set, every
    # spawn's delivery streams host-route float leaves as block-scaled
    # packed int8 payloads and decodes replica-side — the ROADMAP's
    # "int8 weight path at serving load time".  LOSSY (block-scaled
    # quantization error); check_delivery_budget then prices the
    # POST-codec transient.  None keeps delivery bit-exact.
    delivery_codec: Optional[Any] = None


class ReplicaSet:
    """Replica fleet manager: lifecycle + plan-once/stream-per-replica
    weight delivery.

    ``params`` is the source weight tree (host numpy arrays straight
    from a checkpoint, or device arrays from a co-located trainer);
    ``dst_mesh``/``dst_specs`` describe the per-replica serving layout
    (None = one-device replicated — the single-chip replica).  The
    redistribution plan for a topology is built ONCE and cached; every
    ``spawn()`` re-executes it, so N replacement replicas stream
    through the same bounded-transient schedule instead of N ad-hoc
    device_put sweeps."""

    def __init__(self, params, engine_factory: Callable,
                 config: Optional[FleetConfig] = None, *,
                 dst_mesh=None, dst_specs=None,
                 replica_factory: Optional[Callable] = None,
                 engine_factories: Optional[Dict[str, Callable]] = None):
        self.params = params
        self.engine_factory = engine_factory
        # per-ROLE engine factories (round-16 disaggregation): a
        # prefill pool builds prompt-only engines, decode/unified pools
        # build full engines; a role without its own factory falls back
        # to the default
        self.engine_factories = engine_factories or {}
        self.config = config or FleetConfig()
        self.dst_mesh = dst_mesh
        self.dst_specs = dst_specs
        self.replica_factory = replica_factory or Replica
        self.replicas: Dict[int, Replica] = {}
        self._next_id = 0
        self._plans: Dict[Any, Any] = {}     # topology key -> ReshardPlan
        self.telemetry: Dict[str, Any] = {
            "plans_built": 0, "deliveries": 0, "spawns": 0,
            "removed": 0, "deaths": {}}

    # -- weight delivery ---------------------------------------------------

    def _mesh(self):
        if self.dst_mesh is not None:
            return self.dst_mesh
        import jax
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices()[:1], dtype=object)
        return Mesh(devs, ("replica",))

    def _topology_key(self):
        mesh = self._mesh()
        from ..distributed import topology as topo

        return (tuple(mesh.axis_names),
                tuple(int(mesh.shape[a]) for a in mesh.axis_names),
                topo.mesh_device_ids(mesh))

    def delivery_plan(self):
        """The cached redistribution plan for the CURRENT topology —
        plan once, stream per replica."""
        key = self._topology_key()
        plan = self._plans.get(key)
        if plan is None:
            from ..parallel.reshard import plan_reshard

            plan = plan_reshard(
                self.params, self._mesh(), self.dst_specs,
                max_transient_bytes=self.config.max_transient_bytes)
            self._plans[key] = plan
            self.telemetry["plans_built"] += 1
        return plan

    def _deliver(self):
        """Execute the cached plan — through the quantized
        weight-delivery path when a delivery codec is configured."""
        plan = self.delivery_plan()
        codec = self.config.delivery_codec
        if codec is None:
            return plan.execute(self.params)
        from ..parallel.reshard import execute_encoded

        return execute_encoded(plan, self.params, codec)

    def check_delivery_budget(self, budget_bytes: Optional[int] = None,
                              exemptions=(), target: Optional[str] = None):
        """Price the delivery plan's worst step through the Graph
        Doctor's MEM001 budget (``check_reshard_budget``).  With a
        delivery codec the entry is priced on its POST-codec packed
        payloads — the bytes an encoded delivery actually stages.  An
        unbounded plan against a real budget fires MEM001 — the seeded
        ``MEM001[replica_delivery]`` fixture keeps that honest."""
        from ..parallel.reshard import check_reshard_budget

        budget = budget_bytes
        if budget is None:
            budget = (self.config.delivery_budget_bytes
                      or self.config.max_transient_bytes)
        return check_reshard_budget(self.delivery_plan(), self.params,
                                    budget_bytes=budget,
                                    exemptions=exemptions,
                                    target=target or "replica_delivery",
                                    codec=self.config.delivery_codec)

    # -- lifecycle ---------------------------------------------------------

    def spawn(self, role: str = "unified") -> Replica:
        """spawn → deliver weights (cached plan) → warm → SERVING.
        A delivery/warmup failure marks the half-spawned replica DEAD
        (reaped like any other death) and re-raises — callers that must
        survive spawn failure (``ensure_target``) catch and retry.
        ``role`` picks the pool (and with it the per-role engine
        factory); the default keeps the classic unified fleet."""
        factory = self.engine_factories.get(role, self.engine_factory)
        rep = self.replica_factory(self._next_id, factory,
                                   step_timeout_s=self.config.step_timeout_s)
        rep.role = role
        self._next_id += 1
        self.replicas[rep.id] = rep
        try:
            delivered = self._deliver()
            self.telemetry["deliveries"] += 1
            rep.warm(delivered)
        except Exception:
            rep.engine = None
            self.note_death(rep, "SpawnFailed")
            raise
        self.telemetry["spawns"] += 1
        return rep

    def note_death(self, rep: Replica, kind: str) -> None:
        rep.state = DEAD
        d = self.telemetry["deaths"]
        d[kind] = d.get(kind, 0) + 1

    def remove(self, rep: Replica) -> None:
        """drain/dead → REMOVED.  A drained replica's engine passes the
        teardown leak check (its slots are empty by the drain
        contract); a dead replica's engine state is suspect and is
        dropped without the shutdown assertions.  The corpse leaves the
        replica table — a long-running fleet on preemptible capacity
        must not grow (or iterate) its dead history forever; telemetry
        keeps the counts."""
        if rep.state == DRAINING and rep.engine is not None:
            rep.engine.shutdown()
        rep.engine = None
        rep.state = REMOVED
        self.replicas.pop(rep.id, None)
        self.telemetry["removed"] += 1

    def serving(self, role: Optional[str] = None) -> List[Replica]:
        return [r for r in self.replicas.values() if r.state == SERVING
                and (role is None or r.role == role)]

    def live(self, role: Optional[str] = None) -> List[Replica]:
        return [r for r in self.replicas.values()
                if r.state in (SERVING, DRAINING)
                and (role is None or r.role == role)]

    def pool_targets(self) -> Dict[str, int]:
        """The per-role target map (the classic single-pool fleet is
        {"unified": target_replicas})."""
        if self.config.pool_targets is not None:
            return self.config.pool_targets
        return {"unified": self.config.target_replicas}

    def ensure_target(self) -> List[Replica]:
        """Spawn until each pool's SPAWNING+WARMING+SERVING count meets
        its target (DRAINING replicas are on their way out and do not
        count) — a dead prefill replica respawns as a prefill replica.
        A spawn failure is a REPLICA death, never the caller's: it is
        logged, counted (deaths["SpawnFailed"]) and retried on the next
        call — the router tick that triggered the respawn survives."""
        spawned = []
        for role, target in self.pool_targets().items():
            while len([r for r in self.replicas.values()
                       if r.state in (SPAWNING, WARMING, SERVING)
                       and r.role == role]) < int(target):
                try:
                    spawned.append(self.spawn(role))
                except Exception:  # noqa: BLE001 — logged + retried
                    # THIS pool retries next tick; a persistently
                    # failing pool must never block the other pools'
                    # healing, so move on rather than returning
                    logger.exception("[fleet] %s replica spawn failed; "
                                     "will retry next tick", role)
                    break
        return spawned


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RouterRequest:
    """One request as the ROUTER owns it.  ``emitted`` is the committed
    output — tokens harvested from a replica are appended exactly once
    and survive migration/retry (the idempotence anchor: a replayed
    request can only ever EXTEND this list)."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    timeout_s: Optional[float] = None      # per-assignment SLO deadline
    submitted_at: float = 0.0
    emitted: List[int] = dataclasses.field(default_factory=list)
    replica: Optional[int] = None
    engine_rid: Optional[int] = None
    harvested: int = 0                     # continuation tokens pulled
    tries: int = 0                         # timeout retries consumed
    migrations: int = 0
    not_before: float = 0.0                # backoff gate
    dispatched_at: Optional[float] = None
    done: bool = False
    failed: Optional[str] = None
    finished_at: Optional[float] = None

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.emitted)

    def footprint(self) -> int:
        """Admission currency: prompt + full generation budget (the
        replay prompt prompt++emitted plus the remaining budget sums to
        exactly this, so migration never changes a request's cost)."""
        return len(self.prompt) + self.max_new_tokens


@dataclasses.dataclass
class RouterConfig:
    admission_token_cap: int = 256         # outstanding tokens / replica
    affinity: bool = True                  # pin by first-full-page hash
    default_timeout_s: Optional[float] = None
    max_retries: int = 3
    backoff_base_s: float = 0.01
    backoff_max_s: float = 0.25
    backoff_jitter: float = 0.25
    seed: int = 0
    # degradation ladder: pressure = queued tokens / fleet capacity.
    # One stage per tick in each direction -> stages engage IN ORDER
    # (shed speculation, shrink prefill, reject) with hysteresis
    overload_high: float = 1.0
    overload_low: float = 0.5
    min_prefill_budget: int = 4
    # bounded retention (a long-running server must not hold every
    # prompt/token stream/pin/recovery record it ever produced):
    # completed+failed requests kept for results()/stats, affinity pins
    # kept LRU, recovery telemetry kept as a rolling window
    max_done_retained: int = 4096
    max_affinity_pins: int = 4096
    max_recovery_events: int = 1024


class FleetRouter:
    """SLO-aware request router over a ReplicaSet (see module
    docstring).  Single-threaded and deterministic: ``step()`` is one
    scheduler tick (health → ladder → dispatch → replica steps →
    harvest → deadlines → reap → respawn), ``run()`` drains."""

    def __init__(self, replica_set: ReplicaSet,
                 config: Optional[RouterConfig] = None, *,
                 autoscale=None,
                 clock: Callable[[], float] = time.monotonic):
        self.set = replica_set
        self.cfg = config or RouterConfig()
        self.clock = clock
        # round-17 (ROADMAP fleet item (b) remainder): the classic
        # single-pool autoscale — an AutoscaleConfig
        # (inference/disagg.py) pointed at FleetConfig.target_replicas.
        # Same policy as the disagg pools: scale-up on sustained
        # admission pressure, scale-down through the drain path after
        # sustained idleness, one cooldown window for both directions
        # (hysteresis — pinned on the fake clock).  DisaggRouter sets
        # its own per-pool autoscale_cfg BEFORE delegating here.
        if autoscale is not None or not hasattr(self, "autoscale_cfg"):
            self.autoscale_cfg = autoscale
        self._uas_up_streak = 0
        self._uas_idle_streak = 0
        self._uas_cooldown_until = 0
        self.queue: Deque[RouterRequest] = deque()
        self.requests: Dict[int, RouterRequest] = {}
        self._done_order: Deque[int] = deque()   # retirement FIFO
        self._pending_recoveries: List[ServingRecoveryEvent] = []
        self._assigned: Dict[int, Dict[int, RouterRequest]] = {}
        self._affinity: Dict[int, int] = {}      # prefix hash -> replica
        self._next_rid = 0
        self._tick = 0
        self.stage = 0
        self._rng = random.Random(self.cfg.seed)
        self.telemetry: Dict[str, Any] = {
            "submitted": 0, "completed": 0, "rejected": 0,
            "retries": 0, "migrations": 0, "timeouts_failed": 0,
            "ladder_log": [],
            "recoveries": deque(maxlen=self.cfg.max_recovery_events)}
        self.set.ensure_target()
        self._apply_stage_knobs()

    # -- submission --------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 32, *,
               temperature: float = 0.0, seed: int = 0,
               timeout_s: Optional[float] = None) -> int:
        """Enqueue a request.  At the ladder's top stage admission is
        REJECTED with a typed error — the explicit overload signal."""
        if self.stage >= 3:
            self.telemetry["rejected"] += 1
            raise OverloadRejected(
                f"fleet at degradation stage {self.stage}: "
                f"{self._queued_tokens()} queued tokens over "
                f"{self._fleet_capacity()} capacity — retry later")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        footprint = len(prompt) + int(max_new_tokens)
        if footprint > self.cfg.admission_token_cap:
            raise ValueError(
                f"request footprint {footprint} tokens exceeds "
                f"admission_token_cap {self.cfg.admission_token_cap}: it "
                f"could never be dispatched (head-of-queue livelock)")
        rid = self._next_rid
        self._next_rid += 1
        req = RouterRequest(
            rid=rid, prompt=prompt, max_new_tokens=int(max_new_tokens),
            temperature=float(temperature), seed=int(seed),
            timeout_s=(timeout_s if timeout_s is not None
                       else self.cfg.default_timeout_s),
            submitted_at=self.clock())
        self.queue.append(req)
        self.requests[rid] = req
        self.telemetry["submitted"] += 1
        return rid

    # -- pressure + ladder -------------------------------------------------

    def _queued_tokens(self) -> int:
        return sum(r.footprint() for r in self.queue)

    def _fleet_capacity(self) -> int:
        return max(1, len(self.set.serving())) * self.cfg.admission_token_cap

    def _update_ladder(self) -> None:
        pressure = self._queued_tokens() / self._fleet_capacity()
        if pressure > self.cfg.overload_high and self.stage < 3:
            self._set_stage(self.stage + 1, pressure)
        elif pressure < self.cfg.overload_low and self.stage > 0:
            self._set_stage(self.stage - 1, pressure)

    def _set_stage(self, stage: int, pressure: float) -> None:
        prev, self.stage = self.stage, stage
        self.telemetry["ladder_log"].append(
            {"tick": self._tick, "from": prev, "to": stage,
             "pressure": round(float(pressure), 3)})
        logger.warning("[fleet] degradation stage %d -> %d "
                       "(pressure %.2f)", prev, stage, pressure)
        self._apply_stage_knobs()

    def _apply_stage_knobs(self, replicas=None) -> None:
        """Translate the current stage into engine throttles.  Stage 1
        sheds speculative decoding, stage 2 also halves the prefill
        chunk budget (floored), stage 3 additionally rejects at
        submit().  De-escalation restores the constructor shapes."""
        for rep in (replicas if replicas is not None else self.set.live()):
            eng = rep.engine
            if eng is None:
                continue
            # floor clamped to the engine's own static budget: an engine
            # built with a tiny prefill chunk must not be throttled PAST
            # its constructor shape (throttle would reject that)
            floor = min(self.cfg.min_prefill_budget,
                        eng._init_prefill_budget)
            eng.throttle(
                speculative_k=(0 if self.stage >= 1 else eng._init_spec_k),
                prefill_token_budget=(
                    max(floor, eng._init_prefill_budget // 2)
                    if self.stage >= 2 else eng._init_prefill_budget))

    # -- dispatch ----------------------------------------------------------

    def _affinity_key(self, req: RouterRequest) -> Optional[int]:
        """Hash of the FIRST full prompt page — the prefix-cache trie's
        own sharing granularity.  Exactly one page, never more: keying
        on additional pages would fold body tokens into the key for
        longer prompts, splitting same-system-prompt requests across
        replicas (different pins for bodies of different lengths)."""
        live = self.set.serving()
        if not self.cfg.affinity or not live:
            return None
        ps = live[0].engine.page_size
        if len(req.prompt) <= ps:          # no full page to share
            return None
        return hash(tuple(int(t) for t in req.prompt[:ps]))

    def _outstanding(self, rep: Replica) -> int:
        return sum(r.footprint()
                   for r in self._assigned.get(rep.id, {}).values())

    def _pick_replica(self, req: RouterRequest) -> Optional[Replica]:
        """Prefix-affine pick with admission control: the pinned
        replica when it exists and fits, else the least-loaded serving
        replica that fits (and the pin moves with the pick, so the
        trie warms on the replica that actually serves the prefix)."""
        serving = self.set.serving()
        if not serving:
            return None
        key = self._affinity_key(req)
        if key is not None:
            pin = self._affinity.get(key)
            rep = next((r for r in serving if r.id == pin), None)
            if rep is not None and (self._outstanding(rep)
                                    + req.footprint()
                                    <= self.cfg.admission_token_cap):
                self._pin(key, rep.id)      # refresh LRU recency
                return rep
        fits = [r for r in serving
                if self._outstanding(r) + req.footprint()
                <= self.cfg.admission_token_cap]
        if not fits:
            return None
        rep = min(fits, key=lambda r: (self._outstanding(r), r.id))
        if key is not None:
            self._pin(key, rep.id)
        return rep

    def _pin(self, key: int, replica_id: int) -> None:
        """LRU-bounded affinity pin: re-insertion refreshes recency
        (dict insertion order), the cap evicts the coldest prefix —
        many distinct prompt prefixes must not grow the map forever."""
        self._affinity.pop(key, None)
        self._affinity[key] = replica_id
        while len(self._affinity) > self.cfg.max_affinity_pins:
            self._affinity.pop(next(iter(self._affinity)))

    def _assign(self, req: RouterRequest, rep: Replica) -> None:
        """Hand the request (or its post-migration remainder) to a
        replica: the replay prompt is prompt ++ committed tokens, the
        budget is what the committed tokens left over."""
        engine_prompt = (np.concatenate(
            [req.prompt, np.asarray(req.emitted, np.int32)])
            if req.emitted else req.prompt)
        erid = rep.engine.add_request(
            engine_prompt, max_new_tokens=req.remaining,
            temperature=req.temperature, seed=req.seed)
        req.replica, req.engine_rid = rep.id, erid
        req.harvested = 0
        req.dispatched_at = self.clock()
        self._assigned.setdefault(rep.id, {})[erid] = req

    def _dispatch(self) -> None:
        now = self.clock()
        still: Deque[RouterRequest] = deque()
        while self.queue:
            req = self.queue.popleft()
            if req.not_before > now:
                still.append(req)
                continue
            rep = self._pick_replica(req)
            if rep is None:
                still.append(req)
                continue
            self._assign(req, rep)
        self.queue = still

    # -- harvest + completion ----------------------------------------------

    def _retire(self, req: RouterRequest) -> None:
        """Shared terminal bookkeeping for completed AND failed
        requests: both enter the bounded retention window."""
        req.done = True
        req.replica = req.engine_rid = None
        req.finished_at = self.clock()
        self._done_order.append(req.rid)
        while len(self._done_order) > self.cfg.max_done_retained:
            self.requests.pop(self._done_order.popleft(), None)

    def _complete(self, req: RouterRequest) -> None:
        self._retire(req)
        self.telemetry["completed"] += 1

    def _harvest(self) -> int:
        """Commit every replica's newly produced tokens to the router-
        level ``emitted`` lists (exactly once), and retire engine-
        finished requests.  Dead/hung replicas were already unmapped by
        migration, so a suspect step's output is never committed."""
        produced = 0
        for rep in self.set.live():
            amap = self._assigned.get(rep.id)
            if not amap:
                continue
            eng = rep.engine
            for erid, req in list(amap.items()):
                toks = eng.out_tokens.get(erid)
                if toks is not None and len(toks) > req.harvested:
                    new = toks[req.harvested:]
                    req.emitted.extend(int(t) for t in new)
                    req.harvested = len(toks)
                    produced += len(new)
            keep = []
            for f in eng.finished:
                req = amap.pop(f.rid, None)
                if req is None:
                    keep.append(f)
                    continue
                if len(f.tokens) > req.harvested:
                    new = f.tokens[req.harvested:]
                    req.emitted.extend(int(t) for t in new)
                    produced += len(new)
                self._complete(req)
            eng.finished[:] = keep
        return produced

    # -- fault handling ----------------------------------------------------

    def _migrate_from(self, rep: Replica) -> int:
        """Re-enqueue a dead/hung replica's in-flight requests at the
        HEAD of the queue (they have already waited).  Committed tokens
        stay; the replay conditions on them.  The dead engine is only
        unmapped — nothing is canceled on a corpse."""
        amap = self._assigned.pop(rep.id, {})
        moved = 0
        for erid, req in amap.items():
            req.replica = req.engine_rid = None
            req.harvested = 0
            req.migrations += 1
            if (req.remaining <= 0
                    or (req.emitted and self._hit_eos(rep, req))):
                self._complete(req)
            else:
                self.queue.appendleft(req)
            moved += 1
        self.telemetry["migrations"] += moved
        return moved

    @staticmethod
    def _hit_eos(rep: Replica, req: RouterRequest) -> bool:
        eos = getattr(rep.engine, "eos_id", -1) if rep.engine else -1
        return bool(req.emitted) and req.emitted[-1] == eos

    def _check_deadlines(self) -> None:
        """Per-request SLO timeout: a request whose current assignment
        outlived its deadline is withdrawn (engine.cancel — no Finished
        record, committed tokens kept) and retried after a jittered
        exponential backoff; the retry budget exhausting marks the
        request failed LOUDLY."""
        now = self.clock()
        for rep in self.set.live():
            amap = self._assigned.get(rep.id)
            if not amap:
                continue
            for erid, req in list(amap.items()):
                if (req.timeout_s is None or req.dispatched_at is None
                        or now - req.dispatched_at <= req.timeout_s):
                    continue
                rep.engine.cancel(erid)
                del amap[erid]
                req.replica = req.engine_rid = None
                req.harvested = 0
                req.tries += 1
                self.telemetry["retries"] += 1
                if req.tries > self.cfg.max_retries:
                    req.failed = (f"timeout after {req.tries} tries "
                                  f"({req.timeout_s}s each)")
                    self._retire(req)
                    self.telemetry["timeouts_failed"] += 1
                    continue
                req.not_before = now + jittered_backoff(
                    req.tries - 1, base=self.cfg.backoff_base_s,
                    max_s=self.cfg.backoff_max_s,
                    jitter=self.cfg.backoff_jitter,
                    rand=self._rng.random)
                self.queue.append(req)

    def _reap_and_respawn(self) -> None:
        """Finish the lifecycle: drained replicas with no in-flight
        requests are removed (AFTER completion — the drain contract),
        dead replicas are reaped, and the fleet respawns to target
        (completing the pending recovery events' timing)."""
        for rep in list(self.set.replicas.values()):
            if rep.state == DRAINING and not self._assigned.get(rep.id):
                self.set.remove(rep)
            elif rep.state == DEAD:
                self.set.remove(rep)
        spawned = self.set.ensure_target()
        if spawned:
            self._apply_stage_knobs(spawned)
            matched = list(zip(self._pending_recoveries, spawned))
            del self._pending_recoveries[:len(matched)]
            for ev, rep in matched:
                ev.replacement_id = rep.id
                ev.serving_at_tick = self._tick
                ev.recovery_ticks = self._tick - ev.died_at_tick
                ev.wall_s = time.monotonic() - rep.spawned_at

    def _autoscale(self) -> None:
        """Classic single-pool autoscale: move
        ``FleetConfig.target_replicas`` from the router's own pressure
        signals (the disagg router overrides this with its per-pool
        policy).  Scale-up after ``up_sustain_ticks`` consecutive ticks
        of admission pressure (undispatched queue or an engaged
        ladder); scale-down through the drain path after
        ``down_idle_ticks`` idle ticks; ``cooldown_ticks`` of
        hysteresis after any action in either direction."""
        cfg = self.autoscale_cfg
        if cfg is None or not getattr(cfg, "enabled", False) \
                or self.set.config.pool_targets is not None:
            return
        pressured = bool(self.queue) or self.stage >= 1
        idle = not self.queue and not any(
            self._assigned.get(r.id) for r in self.set.live())
        self._uas_up_streak = self._uas_up_streak + 1 if pressured else 0
        self._uas_idle_streak = self._uas_idle_streak + 1 if idle else 0
        if self._tick < self._uas_cooldown_until:
            return
        log = self.telemetry.setdefault("autoscale_log", [])
        target = int(self.set.config.target_replicas)
        if (self._uas_up_streak >= cfg.up_sustain_ticks
                and target < cfg.max_replicas):
            self.set.config.target_replicas = target + 1
            self._uas_cooldown_until = self._tick + cfg.cooldown_ticks
            self._uas_up_streak = 0
            log.append({"tick": self._tick, "pool": "unified",
                        "dir": "up", "target": target + 1})
        elif (self._uas_idle_streak >= cfg.down_idle_ticks
                and target > cfg.min_replicas):
            self.set.config.target_replicas = target - 1
            self._uas_cooldown_until = self._tick + cfg.cooldown_ticks
            self._uas_idle_streak = 0
            victim = next((r for r in self.set.serving()
                           if not self._assigned.get(r.id)), None)
            if victim is not None:
                self.drain(victim.id)   # scale-down IS the drain path
            log.append({"tick": self._tick, "pool": "unified",
                        "dir": "down", "target": target - 1})

    def drain(self, replica_id: int) -> None:
        """Graceful removal: stop routing to the replica; its in-flight
        requests COMPLETE there before removal.  (The fleet respawns to
        ``target_replicas`` — for a real scale-down, lower the target
        first.)"""
        rep = self.set.replicas[replica_id]
        if rep.state == SERVING:
            rep.state = DRAINING
        self._affinity = {k: v for k, v in self._affinity.items()
                          if v != replica_id}

    # -- the tick ----------------------------------------------------------

    def _step_replicas(self) -> None:
        """Step every live replica, treating ANY engine exception as
        that replica's death (migrate + heal) — the shared middle of
        the base and disaggregated router ticks."""
        for rep in list(self.set.live()):
            try:
                rep.step()
            except Exception as fault:  # noqa: BLE001 — any engine death
                # a replica failing for ANY reason (typed ReplicaFault,
                # XLA resource exhaustion, device loss surfacing as a
                # RuntimeError) is a replica death, never a fleet death:
                # migrate its requests and let the respawn heal it
                kind = type(fault).__name__
                rep.fault = fault
                self.set.note_death(rep, kind)
                self._affinity = {k: v for k, v in self._affinity.items()
                                  if v != rep.id}
                moved = self._migrate_from(rep)
                ev = ServingRecoveryEvent(
                    replica_id=rep.id, fault=kind,
                    died_at_tick=self._tick, migrated_requests=moved)
                self.telemetry["recoveries"].append(ev)
                self._pending_recoveries.append(ev)
                logger.warning("[fleet] replica %d %s at tick %d; "
                               "migrated %d in-flight requests",
                               rep.id, kind, self._tick, moved)

    def step(self) -> int:
        """One router tick.  Returns tokens committed this tick."""
        self._tick += 1
        self._update_ladder()
        self._dispatch()
        self._step_replicas()
        produced = self._harvest()
        self._check_deadlines()
        self._autoscale()
        self._reap_and_respawn()
        return produced

    def pending(self) -> int:
        return (len(self.queue)
                + sum(len(m) for m in self._assigned.values()))

    def run(self, max_iters: int = 10_000):
        """Drive until every submitted request completed (or failed its
        retry budget).  Returns {rid: np.ndarray emitted tokens} for
        the completed set, sorted by rid."""
        it = 0
        while self.pending() and it < max_iters:
            self.step()
            it += 1
        if self.pending():
            left = {k: len(v) for k, v in self._assigned.items() if v}
            raise RuntimeError(
                f"fleet router did not drain: queue={len(self.queue)}, "
                f"assigned={left}")
        return self.results()

    def results(self) -> Dict[int, np.ndarray]:
        return {rid: np.asarray(req.emitted, np.int32)
                for rid, req in sorted(self.requests.items())
                if req.done and req.failed is None}

    def stats(self) -> Dict[str, Any]:
        t = dict(self.telemetry)
        offered = t["submitted"] + t["rejected"]
        t["shed_rate"] = t["rejected"] / offered if offered else 0.0
        t["stage"] = self.stage
        t["recoveries"] = [dataclasses.asdict(ev)
                           for ev in self.telemetry["recoveries"]]
        return t
