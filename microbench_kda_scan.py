#!/usr/bin/env python3
"""Microbenchmark of ONE layer's ``kda_delta_scan`` at the Kimi-Linear
cell's geometry, on the chip: bf16 rows, 32 heads of 128, 161 state
entries, tiles of 128 rows, ``max_units=ssd_max_units(T, 128, 128)``.

    python3 microbench_kda_scan.py [--against <another checkout>] [--iters 20]

Three launches a tree (this one, and with ``--against`` another
checkout's ``paddle_tpu/ops/pallas/kda_scan.py`` in the same process; it
imports THIS tree's ``ssd_scan``):

  chunk640   25 decode rows, then a 512-row chunk of one slot, then
             padding: what a rung-640 chunk launch hands the kernel (the
             engine packs decode rows first, so the chunk is 5 units)
  decode640  the same rows with the chunk's made padding
  decode128  38 decode rows in one tile: a rung-128 decode launch

The kernel's time is the DEVICE's: the median duration of the ``--iters``
launches' ``kda_delta_scan`` events in a profiler trace of the run, taken
in the order launched (the wrapper's XLA operations are not in it).
``us a head-tile`` is ``(chunk640 - decode640) / (chunk units x heads)``:
what one head of one chunk unit costs.  The pool is donated from launch
to launch, as the engine's step donates it.  Prints one line a tree and
writes ``chiprun_out/microbench-kda-scan.json``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

H, D, ENTRIES, TILE, SLOTS = 32, 128, 161, 128, 128
#: launch -> (packed rows, decode rows, chunk rows)
LAUNCHES = {"chunk640": (640, 25, 512), "decode640": (640, 25, 0),
            "decode128": (128, 38, 0)}


def rows_of(T: int, decode: int, chunk: int, seed: int = 0):
    """The scan's operands for ``decode`` decode rows (slots 0..), then
    ``chunk`` rows of the next slot, then padding."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(T, H, D))) * D ** -0.5
    k = unit(rng.normal(size=(T, H, D)))
    v = rng.normal(size=(T, H, D))
    a = np.exp(rng.uniform(0, np.log(16), (1, H, 1)))
    g = -a * np.log1p(np.exp(rng.normal(size=(T, H, D)) - 4.0))
    beta = 1 / (1 + np.exp(-rng.normal(size=(T, H))))
    slot = np.full(T, -1, np.int32)
    slot[:decode] = np.arange(decode)
    slot[decode:decode + chunk] = decode
    src = np.where(slot >= 0, slot, ENTRIES - 1).astype(np.int32)
    lens = np.where(slot >= 0, 2000 + np.arange(T), 0).astype(np.int32)
    return ([jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)]
            + [jnp.asarray(x, jnp.float32) for x in (g, beta)],
            [jnp.asarray(x) for x in (slot, lens, src, src)])


def kernel_of(checkout: pathlib.Path, name: str):
    """``kda_delta_scan`` of ``checkout``'s ``kda_scan.py``, loaded beside
    this tree's (its ``.ssd_scan`` is this tree's)."""
    import paddle_tpu.ops.pallas  # noqa: F401  (the package it joins)

    spec = importlib.util.spec_from_file_location(
        f"paddle_tpu.ops.pallas.{name}",
        checkout / "paddle_tpu/ops/pallas/kda_scan.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod.kda_delta_scan


def measure(kernels, iters: int):
    """``{tree: {launch: median kernel ms, "us_head_tile": ...}}`` for
    ``kernels``, a ``kda_delta_scan`` a tree, from ONE trace of all."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.kda_scan import KDA_SCAN_KERNEL
    from paddle_tpu.ops.pallas.ssd_scan import ssd_max_units
    from paddle_tpu.profiler import device_trace

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("microbench_kda_scan.py times the chip: no TPU here")
    pool = jnp.zeros((ENTRIES, H, D, D), jnp.float32) + 0.01
    operands = {launch: rows_of(*shape) for launch, shape in LAUNCHES.items()}
    runs = []
    for tree, kernel in kernels.items():
        for launch, (rows, cols) in operands.items():
            T = LAUNCHES[launch][0]

            def step(pool, rows, cols, kernel=kernel, T=T):
                return kernel(*rows, pool, *cols, tile_rows=TILE,
                              max_units=ssd_max_units(T, TILE, SLOTS))

            fn = jax.jit(step, donate_argnums=0)
            t = time.perf_counter()
            for _ in range(3):                  # compile, and warm
                o, pool = fn(pool, rows, cols)
            jax.block_until_ready(o)
            print(f"# {tree} {launch}: compiled and warm in "
                  f"{time.perf_counter() - t:.1f} s", flush=True)
            runs.append((tree, launch, fn, rows, cols))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for *_, fn, rows, cols in runs:
                for _ in range(iters):
                    o, pool = fn(pool, rows, cols)
                jax.block_until_ready(o)
        trace = device_trace.load_xplane(device_trace.find_xplane(tmp), ())
    # two launches of one shape are ONE program to the runtime, so the
    # kernel's events are told apart by their order, not by a name
    (plane,) = list(trace.ops)[:1]
    ms = [op.duration_ns / 1e6
          for op in sorted(trace.ops[plane], key=lambda op: op.start_ns)
          if op.name.startswith(KDA_SCAN_KERNEL)]
    assert len(ms) == iters * len(runs), (len(ms), iters, len(runs))
    out = {tree: {} for tree in kernels}
    for n, (tree, launch, *_) in enumerate(runs):
        out[tree][launch] = statistics.median(ms[n * iters:(n + 1) * iters])
    _, decode, chunk = LAUNCHES["chunk640"]
    chunk_units = len({r // TILE for r in range(decode, decode + chunk)})
    for got in out.values():
        got["us_head_tile"] = 1e3 * (got["chunk640"] - got["decode640"]) \
            / (chunk_units * H)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--against", type=pathlib.Path)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args(argv)

    from paddle_tpu.ops.pallas.kda_scan import kda_delta_scan

    kernels = {"here": kda_delta_scan}
    if args.against:
        kernels["against"] = kernel_of(args.against, "_kda_scan_against")
    out = measure(kernels, args.iters)
    for tree, got in out.items():
        print(f"# {tree}: " + ", ".join(f"{k} {v:.4f}" for k, v in got.items())
              + " (ms a launch of ONE layer; us a head of a chunk unit)")
    dest = ROOT / "chiprun_out"
    dest.mkdir(exist_ok=True)
    (dest / "microbench-kda-scan.json").write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
