"""Concurrency Doctor (round-21) — lock-discipline static analysis +
race sanitizer for the host-side control plane.

Four layers, mirroring the doctor gates before it:
- TRUE POSITIVES: the RACE001-004 seeded fixtures fire exactly their
  codes (RACE004 is the minimized PRE-FIX watchdog handler/flag race —
  the pass must catch the bug we actually shipped), asserted both here
  and by the SEEDED registry sweep in test_analysis_passes.py;
- CLEAN SWEEP: the control-plane modules pass the lock-discipline sweep
  under the reviewed allowlist — every entry justified in-place and
  LIVE (an entry no finding matches fails);
- SANITIZER: the instrumented-lock monitor detects a scripted
  lock-order inversion, the barrier-stepped fake scheduler makes hammer
  runs reproducible from their seed, and the static guarded-write map
  cross-checks against the runtime acquisition sites;
- HAMMERS: small genuinely-threaded storms on the real PageAllocator
  and watchdog pin the fixed single-writer terminal transition and the
  ``assert_consistent`` pool contract under contention.
"""

import textwrap
import threading

import pytest

from paddle_tpu.analysis.concurrency import (
    ALLOWLIST_PATH, CONTROL_PLANE_MODULES, load_allowlist,
    sweep_control_plane)
from paddle_tpu.analysis.fixtures import SEEDED
from paddle_tpu.analysis.lock_sanitizer import (
    BarrierScheduler, LockMonitor, SanitizedLock, hammer_page_allocator,
    hammer_watchdog, instrument_lock, sanitizer_self_test)
from paddle_tpu.analysis.passes.lock_discipline import (
    analyze_source, guarded_write_map)


# ---------------------------------------------------------------------------
# static pass: true positives (unit level, beyond the SEEDED registry)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("code", ["RACE001", "RACE002", "RACE003",
                                  "RACE004"])
def test_seeded_race_fixture_fires_exactly(code):
    rep = SEEDED[code]()
    assert rep.findings, f"{code} fixture produced no findings"
    assert set(rep.codes()) == {code}, rep.summary()


def test_race004_matches_the_shipped_watchdog_bug():
    """The RACE004 fixture is the pre-fix watchdog shape; the REAL
    pre-fix module (complete() checking task.timed_out outside the
    manager lock / the scanner appending the trace record lock-free)
    must fire the pass too — the historical-bug regression half of the
    permanent pair (the fixed module's clean sweep is the other)."""
    pre_fix = textwrap.dedent("""
        import threading

        class CommTaskManager:
            def __init__(self):
                self._tasks = {}
                self._lock = threading.Lock()
                self.timed_out = []

            def complete(self, task):
                with self._lock:
                    if task.timed_out:
                        return
                    task.done = True
                    self._tasks.pop(task.seq, None)

            def _loop(self, now):
                expired = []
                with self._lock:
                    for seq, t in list(self._tasks.items()):
                        if now - t.start_time > t.timeout_s:
                            t.timed_out = True
                            expired.append(t)
                            del self._tasks[seq]
                for t in expired:
                    self.timed_out.append(t)
        """)
    codes = {f.code for f in analyze_source(pre_fix, "prefix/watchdog.py")}
    assert "RACE001" in codes, (
        "the pre-fix watchdog's lock-free timed_out append must fire")


def test_lock_free_module_is_trivially_clean():
    src = "class Router:\n    def step(self):\n        self.tick = 1\n"
    assert analyze_source(src, "m.py") == []


def test_guarded_write_map_exports_lock_fields():
    src = textwrap.dedent("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def bump(self):
                with self._lock:
                    self.n += 1
        """)
    m = guarded_write_map(src, "m.py")
    assert "n" in m.get("_lock", {})
    assert m["_lock"]["n"] == ["C.bump"]


# ---------------------------------------------------------------------------
# clean sweep + allowlist review rules
# ---------------------------------------------------------------------------


def test_control_plane_sweeps_clean_with_live_allowlist():
    report, unused = sweep_control_plane()
    assert report.ok, report.summary()
    assert unused == [], f"stale allowlist entries: {unused}"
    # the accepted hazard stays DETECTED (suppressed, never silent)
    assert any(f.code == "RACE003" and "store.py" in (f.where or "")
               for f in report.suppressed), (
        "the store.py lazy-build RACE003 must remain visible in "
        "report.suppressed")


def test_fixed_watchdog_sweeps_clean():
    report, _ = sweep_control_plane(modules=("distributed/watchdog.py",))
    assert report.ok and not report.suppressed, report.summary()


def test_allowlist_entries_all_justified():
    table = load_allowlist(ALLOWLIST_PATH)
    assert table, "allowlist exists and parses"
    for key, reason in table.items():
        assert reason.strip(), f"{key} has no justification"


def test_allowlist_rejects_unjustified_entry(tmp_path):
    p = tmp_path / "allow.txt"
    p.write_text("inference/serving.py::PageAllocator.alloc::RACE003\n")
    with pytest.raises(ValueError, match="justification"):
        load_allowlist(str(p))


def test_stale_allowlist_entry_fails_the_sweep():
    extra = dict(load_allowlist())
    extra[("inference/fleet.py", "FleetRouter.step", "RACE001")] = \
        "stale test entry"
    report, unused = sweep_control_plane(allowlist=extra)
    assert report.ok
    assert unused == ["inference/fleet.py::FleetRouter.step::RACE001"]


def test_control_plane_module_paths_exist():
    import os

    from paddle_tpu.analysis.concurrency import _PKG_ROOT

    for rel in CONTROL_PLANE_MODULES:
        assert os.path.exists(os.path.join(_PKG_ROOT, rel)), rel


# ---------------------------------------------------------------------------
# sanitizer: monitor, deterministic scheduler, cross-check
# ---------------------------------------------------------------------------


def test_monitor_detects_scripted_order_inversion():
    mon = LockMonitor()
    a, b = SanitizedLock("A", mon), SanitizedLock("B", mon)
    with a:
        with b:
            pass
    assert mon.order_violations() == []
    with b:
        with a:
            pass
    assert mon.order_violations() == [("A", "B")]


def test_monitor_unguarded_field_detection():
    mon = LockMonitor()
    lk = SanitizedLock("L", mon)
    with lk:
        mon.access("Obj", "field")
    mon.access("Obj", "field")          # same field, lock NOT held
    assert mon.unguarded("L") == [("Obj", "field")]
    # a field only ever touched under the lock is not reported
    with lk:
        mon.access("Obj", "other")
    assert ("Obj", "other") not in mon.unguarded("L")


def test_barrier_scheduler_is_reproducible():
    def mk(log, tag):
        return [lambda i=i: log.append((tag, i)) for i in range(5)]

    log1, log2 = [], []
    t1 = BarrierScheduler(seed=11).run([mk(log1, "a"), mk(log1, "b")])
    t2 = BarrierScheduler(seed=11).run([mk(log2, "a"), mk(log2, "b")])
    assert t1 == t2 and log1 == log2
    t3 = BarrierScheduler(seed=12).run([mk([], "a"), mk([], "b")])
    assert len(t3) == len(t1)           # same ops, any order


def test_instrument_lock_swaps_in_place():
    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def bump(self):
            with self._lock:
                self.n += 1

    box = Box()
    mon = instrument_lock(box, "_lock", name="box")
    box.bump()
    assert mon.acquisitions == 1
    assert "bump" in mon.sites["box"]


def test_sanitizer_self_test_green():
    st = sanitizer_self_test()
    assert st["ok"], st
    assert st["order_inversion_detected"]
    assert st["trace_stable"]


# ---------------------------------------------------------------------------
# hammers: the genuinely-threaded tier-1 smokes
# ---------------------------------------------------------------------------


def test_page_allocator_hammer_threaded():
    h = hammer_page_allocator(num_pages=8, threads=4, ops=100, seed=5)
    assert h["ok"], h
    assert h["order_violations"] == []
    # static map vs runtime sites: every under-lock mutator the source
    # declares was exercised under the instrumented lock
    assert h["cross_check"]["unexercised"] == []


def test_page_allocator_hammer_deterministic_replay():
    h1 = hammer_page_allocator(num_pages=6, threads=3, ops=60, seed=9,
                               deterministic=True)
    h2 = hammer_page_allocator(num_pages=6, threads=3, ops=60, seed=9,
                               deterministic=True)
    assert h1["ok"] and h2["ok"]
    assert h1["deterministic_trace_len"] == h2["deterministic_trace_len"]
    assert h1["acquisitions"] == h2["acquisitions"]


def test_watchdog_hammer_pins_single_writer_transition():
    """The permanent regression pin for the PR-6 handler/flag race:
    completions racing the scanner must leave every task in exactly one
    terminal state."""
    w = hammer_watchdog(threads=4, tasks_per_thread=10, seed=2)
    assert w["ok"], w
    assert w["both_terminal"] == 0 and w["neither_terminal"] == 0
    assert w["timed_out"] + w["completed"] == w["tasks"]
    # the race was CONTENDED: the scanner won at least once (aged tasks
    # linger several scan intervals, so this is deterministic in
    # practice)
    assert w["timed_out"] > 0


# ---------------------------------------------------------------------------
# assert_consistent: the checked pool/trie contracts
# ---------------------------------------------------------------------------


def test_page_allocator_assert_consistent_positive_and_violations():
    from paddle_tpu.inference.page_cache import PageAllocator

    alloc = PageAllocator(4)
    p = alloc.alloc()
    alloc.acquire(p)
    alloc.assert_consistent()
    alloc.release([p, p])
    alloc.assert_consistent()
    assert alloc.available == 4

    # corruption: a page both free and referenced
    bad = PageAllocator(4)
    q = bad.alloc()
    bad.free.append(q)
    with pytest.raises(AssertionError):
        bad.assert_consistent()

    # corruption: negative refcount
    neg = PageAllocator(2)
    r = neg.alloc()
    neg.refs[r] = -1
    with pytest.raises(AssertionError):
        neg.assert_consistent()

    # back-compat alias routes to the same contract
    ok = PageAllocator(2)
    ok.assert_balanced()


def test_prefix_cache_assert_consistent():
    from paddle_tpu.inference.page_cache import PageAllocator, PrefixCache

    alloc = PageAllocator(8)
    cache = PrefixCache(page_size=2, alloc=alloc)
    pages = [alloc.alloc() for _ in range(2)]
    cache.insert([1, 2, 3, 4], pages)
    cache.assert_consistent()

    # tier corruption: a node claiming both a device page and a host
    # payload must fail the disjointness check
    node = next(iter(cache.root.children.values()))
    node.host_kv = object()
    with pytest.raises(AssertionError, match="both tiers"):
        cache.assert_consistent()
    node.host_kv = None

    # counter drift: host_pages disagreeing with the actual node count
    cache.host_pages = 3
    with pytest.raises(AssertionError, match="counter drift"):
        cache.assert_consistent()
    cache.host_pages = 0
    cache.assert_consistent()


def test_assert_consistent_under_hammer_mid_flight():
    """The contract is callable DURING the storm, not just after: a
    checker thread asserts consistency concurrently with mutators."""
    from paddle_tpu.analysis.lock_sanitizer import run_threaded
    from paddle_tpu.inference.page_cache import PageAllocator

    alloc = PageAllocator(8)

    def mutate():
        for _ in range(60):
            p = alloc.alloc()
            if p is not None:
                alloc.release([p])

    def check():
        for _ in range(30):
            alloc.assert_consistent()

    run_threaded([[mutate], [mutate], [check]])
    alloc.assert_consistent()
    assert alloc.available == 8
