"""Launcher — ``python -m paddle_tpu.distributed.launch``.

Analog of the reference's launch CLI (python/paddle/distributed/launch/
main.py:23, __main__.py; collective controller launch/controllers/
collective.py:126-132 which sets the env contract, master rendezvous
controllers/master.py).  TPU-native notes: on a TPU host ONE process drives
all local chips (jax.distributed + PJRT own the per-chip fan-out), which
is what the rest of the code assumes, so ``--nproc_per_node`` defaults to
1 and more than one local worker on a host with TPU chips is an ERROR: a
chip belongs to one process, and a second worker would fail or hang at
backend start-up (nothing here gives each child a chip of its own).
Several local workers remain what the CPU gang tests use
(``JAX_PLATFORMS=cpu``).  The launcher itself never touches JAX, so it
holds no chip.  The env contract (PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM / PADDLE_CURRENT_ENDPOINT / PADDLE_TRAINER_ENDPOINTS /
PADDLE_RANK_IN_NODE / PADDLE_MASTER — SURVEY §5 launcher contract) is kept
verbatim so reference scripts port unchanged, and is also mapped onto
jax.distributed's coordinator env for in-process consumption.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch distributed training processes")
    p.add_argument("--nnodes", type=str, default="1",
                   help="N or N1:N2 elastic range")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--master", type=str, default=None,
                   help="coordinator host:port (default: self)")
    p.add_argument("--rank", type=int, default=0, help="this node's rank")
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--run_mode", type=str, default="collective")
    p.add_argument("--job_id", type=str, default="default")
    p.add_argument("--devices", "--gpus", type=str, default=None)
    p.add_argument("--max_restart", type=int, default=0)
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def build_env(rank: int, local_rank: int, world: int, endpoints: List[str],
              master: str, jax_coordinator: str = None) -> dict:
    env = dict(os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_RANK_IN_NODE": str(local_rank),
        "PADDLE_MASTER": master,
        # jax.distributed consumption (multi-host TPU)
        "JAX_COORDINATOR_ADDRESS": jax_coordinator or master,
        "JAX_NUM_PROCESSES": str(world),
        "JAX_PROCESS_ID": str(rank),
        # generic torch-style aliases some scripts read
        "RANK": str(rank),
        "WORLD_SIZE": str(world),
        "LOCAL_RANK": str(local_rank),
        "MASTER_ADDR": master.split(":")[0],
        "MASTER_PORT": master.split(":")[-1],
    })
    return env


def local_tpu_chips() -> List[str]:
    """Device files of the TPU chips on this host, found without
    starting a JAX backend (the launcher must not hold the chip):
    ``/dev/vfio/<n>`` on v5e and newer, ``/dev/accel<n>`` before."""
    import glob

    return sorted(glob.glob("/dev/accel[0-9]*")
                  + glob.glob("/dev/vfio/[0-9]*"))


def check_process_layout(local_workers: int, env=None) -> None:
    """One process drives all local chips.  Several local workers that
    would each reach for the TPU are refused with the reason, instead
    of hanging at backend start-up."""
    env = os.environ if env is None else env
    if local_workers <= 1:
        return
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return                      # workers are pinned off the TPU
    chips = local_tpu_chips()
    if chips:
        raise SystemExit(
            f"paddle_tpu.distributed.launch: {local_workers} local "
            f"workers on a host with {len(chips)} TPU chip(s) "
            f"({chips[0]}...): a chip belongs to one process, so the "
            f"second worker would fail or hang at start-up.  Run ONE "
            f"worker per host (--nproc_per_node 1; it drives all local "
            f"chips through one mesh), or pin the workers to the CPU "
            f"with JAX_PLATFORMS=cpu.")


def _run_gang(args, world: int, nproc: int, endpoints: List[str],
              master: str, restart_count: int, shutdown_flag: dict
              ) -> List[int]:
    """Launch one generation of the worker gang and wait for it; returns
    per-worker exit codes. Any failure terminates the whole gang
    (collective semantics — a half-dead ring cannot progress)."""
    procs: List[subprocess.Popen] = []
    logs = []
    suffix = f".restart{restart_count}" if restart_count else ""
    for local_rank in range(nproc):
        rank = args.rank * nproc + local_rank
        env = build_env(rank, local_rank, world, endpoints, master,
                        jax_coordinator=shutdown_flag.get("jax_coordinator"))
        env["PADDLE_RESTART_COUNT"] = str(restart_count)
        log_path = os.path.join(args.log_dir, f"workerlog.{local_rank}{suffix}")
        logf = open(log_path, "w")
        logs.append(logf)
        cmd = [sys.executable, "-u", args.training_script,
               *args.training_script_args]
        procs.append(subprocess.Popen(cmd, env=env, stdout=logf,
                                      stderr=subprocess.STDOUT))

    def _kill_workers():
        for p in procs:
            if p.poll() is None:
                p.terminate()

    # the SIGTERM handler is installed once in launch(); this generation's
    # kill hook is published through the shared flag dict so a signal
    # arriving between generations still stops the next one (the monitor
    # loop below also polls the flag)
    shutdown_flag["kill"] = _kill_workers
    try:
        while True:
            if shutdown_flag["requested"] or shutdown_flag.get("scale_up"):
                # shutdown, or an elastic JOIN preempting this generation
                # for a re-rendezvous at a larger world
                _kill_workers()
                break
            done = [p.poll() for p in procs]
            if any(c is not None and c != 0 for c in done):
                _kill_workers()
                break
            if all(c == 0 for c in done):
                break
            time.sleep(0.5)
    finally:
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in logs:
            f.close()
    return [p.returncode for p in procs]


def announce_join(master: str = "127.0.0.1:49178", timeout: float = 30):
    """Announce a (returning or new) node to an elastic launcher: bumps
    the control store's join counter; the launcher preempts the running
    gang and re-rendezvous at a larger world (<= max_nodes).  The analog
    of a node's etcd registration waking the reference elastic manager
    (fleet/elastic/manager.py watch path)."""
    from ..store import TCPStore

    mhost, mport = master.rsplit(":", 1)
    store = TCPStore(mhost, int(mport), is_master=False, world_size=1,
                     timeout=timeout)
    return store.add("elastic/join_req", 1)


def launch(args=None) -> int:
    from ..fleet.elastic import ElasticManager, ElasticStatus

    args = args if args is not None else parse_args()
    mgr = ElasticManager(nnodes=args.nnodes, max_restart=args.max_restart)
    nproc = args.nproc_per_node
    # single-host mode: one node, OR an elastic range driven entirely by
    # this (rank-0, masterless) launcher — each "node" is then a local
    # proc, which is the scale-down testbed.  Multi-launcher setups
    # (explicit --master or --rank > 0) keep the min_nodes rendezvous
    # semantics: scaling them requires a coordinated re-rendezvous.
    # the local scale-down testbed needs an explicit opt-in
    # (PADDLE_ELASTIC_LOCAL=1 or --standalone-ish single node): inferring
    # it from a missing --master would silently give a genuine
    # multi-node elastic deployment the wrong (all-local) topology
    local_elastic = os.environ.get("PADDLE_ELASTIC_LOCAL", "") in (
        "1", "true", "True")
    # under the explicit opt-in, a loopback --master stays local too (it
    # just pins the control-store port — concurrent testbeds need
    # distinct ports)
    master_is_local = (args.master is None
                       or args.master.rsplit(":", 1)[0] in
                       ("127.0.0.1", "localhost"))
    single_host = (mgr.max_nodes == 1
                   or (local_elastic and master_is_local
                       and args.rank == 0
                       and mgr.max_nodes > mgr.min_nodes))
    # single-host elastic starts at FULL size and scales DOWN one node
    # per failed generation until min_nodes (the reference manager's
    # re-rendezvous-at-smaller-world path, fleet/elastic/manager.py:125)
    nnodes = mgr.max_nodes if single_host else mgr.min_nodes
    world = nnodes * nproc
    check_process_layout(world if single_host else nproc)
    master = args.master or "127.0.0.1:49178"
    base_port = 52700
    os.makedirs(args.log_dir, exist_ok=True)

    shutdown_flag = {"requested": False, "kill": lambda: None}
    rdv_store = None
    if single_host:
        endpoints = [f"127.0.0.1:{base_port + i}" for i in range(world)]
        if local_elastic and mgr.max_nodes > mgr.min_nodes:
            # elastic control store: a returning/new node announces
            # itself (announce_join) and the launcher preempts the gang
            # for a SCALE-UP re-rendezvous — the reference elastic
            # manager's watch-and-expand path
            # (fleet/elastic/manager.py:125)
            import threading

            from ..store import TCPStore

            mhost, mport = master.rsplit(":", 1)
            ctrl = TCPStore(mhost, int(mport), is_master=True,
                            world_size=1, timeout=60)
            # the ctrl store owns the master port; workers' jax
            # coordinator must not collide with it (same split as the
            # multi-node rendezvous branch)
            shutdown_flag["jax_coordinator"] = f"{mhost}:{int(mport) + 1}"
            shutdown_flag["joins_consumed"] = 0
            # one lock covers flag-set (watcher) and pop+consume (main
            # loop): without it a watcher tick between the two could
            # turn one announce_join into two scale-ups
            shutdown_flag["join_lock"] = threading.Lock()

            def _watch_joins():
                while not shutdown_flag["requested"]:
                    try:
                        n = ctrl.add("elastic/join_req", 0)
                    except Exception:
                        return
                    # each announced join is consumed by ONE scale-up;
                    # pending joins keep preempting until drained
                    with shutdown_flag["join_lock"]:
                        fire = (n > shutdown_flag["joins_consumed"]
                                and not shutdown_flag.get("scale_up"))
                        if fire:
                            shutdown_flag["scale_up"] = True
                    if fire:
                        shutdown_flag["kill"]()
                    time.sleep(0.5)

            threading.Thread(target=_watch_joins, daemon=True).start()
    else:
        # multi-node rendezvous over the native TCPStore hosted at
        # --master by node 0 (the HTTPMaster/ETCDMaster analog,
        # launch/controllers/master.py): every node registers its local
        # endpoints, barriers, then reads the agreed global list
        from ..store import TCPStore

        mhost, mport = master.rsplit(":", 1)
        this_host = os.environ.get("PADDLE_NODE_IP", mhost)
        node_base = base_port + args.rank * nproc  # distinct on one host
        local_eps = [f"{this_host}:{node_base + i}" for i in range(nproc)]
        # the 120s windows are defaults: FLAGS_store_barrier_timeout_s
        # overrides both (round-12 satellite — throttled-CPU containers
        # stretch the gang-import rendezvous via env, with jittered
        # backoff retries inside the store instead of one long wait)
        rdv_store = TCPStore(mhost, int(mport), is_master=(args.rank == 0),
                             world_size=nnodes, timeout=120)
        rdv_store.set(f"launch/node/{args.rank}", ",".join(local_eps))
        rdv_store.barrier("launch_rendezvous", timeout=120)
        endpoints = []
        for r in range(nnodes):
            endpoints += rdv_store.get(f"launch/node/{r}").decode().split(",")
        # the TCPStore owns the master port; jax.distributed gets its own
        shutdown_flag["jax_coordinator"] = f"{mhost}:{int(mport) + 1}"

    def _on_sigterm(*_):
        # operator-initiated shutdown must NOT look like a worker failure
        # (which would trigger an elastic gang restart)
        shutdown_flag["requested"] = True
        shutdown_flag["kill"]()

    signal.signal(signal.SIGTERM, _on_sigterm)
    generation = 0
    while True:
        if shutdown_flag["requested"]:
            sys.stderr.write("launch: shutdown requested (SIGTERM); not "
                             "starting a new gang\n")
            return 0
        codes = _run_gang(args, world, world if single_host else nproc,
                          endpoints, master, generation, shutdown_flag)
        if shutdown_flag["requested"]:
            # intentional stop is a clean exit, not a failure
            sys.stderr.write("launch: shutdown requested (SIGTERM); not "
                             "restarting\n")
            return 0
        join_lock = shutdown_flag.get("join_lock")
        if join_lock:
            with join_lock:
                scale_up = shutdown_flag.pop("scale_up", False)
                if scale_up:
                    shutdown_flag["joins_consumed"] += 1
        else:
            scale_up = shutdown_flag.pop("scale_up", False)
        if scale_up and all(c == 0 for c in codes):
            # the gang finished cleanly while the join raced in: the job
            # is done — do not restart a completed job
            sys.stderr.write("launch: join raced a completed gang; job "
                             "finished\n")
            return 0
        if scale_up and not all(c in (0, -signal.SIGTERM) for c in codes):
            # a REAL worker crash raced the join: route it through the
            # elastic manager (restart budget) — the pending join fires
            # again on the next generation via the watcher
            with join_lock:
                shutdown_flag["joins_consumed"] -= 1
            scale_up = False
        if scale_up:
            # a node announced itself: re-rendezvous at a LARGER world
            # (bounded by max_nodes); a join is capacity returning, so it
            # does not consume the restart budget
            generation += 1
            if nnodes < mgr.max_nodes:
                nnodes += 1
                world = nnodes * nproc
                endpoints = [f"127.0.0.1:{base_port + i}"
                             for i in range(world)]
                sys.stderr.write(
                    f"launch: node joined; elastic SCALE-UP "
                    f"re-rendezvous at world={world}\n")
            else:
                sys.stderr.write(
                    "launch: join announced at max_nodes; restarting "
                    "at the same world\n")
            continue
        status = mgr.decide(codes)
        if status is ElasticStatus.COMPLETED:
            return 0
        if status is ElasticStatus.RESTART:
            generation += 1
            if single_host and nnodes > mgr.min_nodes:
                nnodes -= 1
                world = nnodes * nproc
                endpoints = endpoints[:world]
                sys.stderr.write(
                    f"launch: worker failed (codes={codes}); elastic "
                    f"SCALE-DOWN re-rendezvous at world={world} "
                    f"(restart {mgr.restart_count}/{mgr.max_restart})\n")
            else:
                sys.stderr.write(
                    f"launch: worker failed (codes={codes}); elastic gang "
                    f"restart {mgr.restart_count}/{mgr.max_restart}\n")
            continue
        code = next(c for c in codes if c)  # first failure wins
        sys.stderr.write(
            f"launch: a worker failed with exit code {code}; logs in "
            f"{args.log_dir}/workerlog.*\n")
        return code
