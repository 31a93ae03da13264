#!/usr/bin/env python3
"""Quickest proof that the framework's main paths start on the chip.

``python chip_smoke.py`` needs one TPU chip and runs, in ONE process:

- ``kernels``: the compiled Pallas kernels (flash fwd+bwd, also inside
  ``lax.scan``; paged and ragged-paged decode in bf16 and int8) at the
  head shapes of the two phases below, each against a plain XLA
  reference on the device;
- ``train``: ``build_train_step`` on ``LlamaForCausalLM`` at the
  Llama-3.2-1B widths (depth cut to what one 16 GB chip holds with the
  full AdamW state resident), a few steps on one repeated batch;
- ``serve``: ``ContinuousBatchingEngine`` at the Llama-3-8B widths:
  bf16 weights at the depth that fits, logits against the model's own
  full forward; then all 32 layers with int8 weights and int8 KV.

``python chip_smoke.py --chips 4`` needs four chips and runs only the
sharded train step (sharding-stage-3 x TP over a (1,1,2,1,2) mesh) and
what it is compared with.

Any failed check or exception fails the run.  The last line of stdout
is ``{"ok": ..., "device": {"platform", "kind", "count"}}``.  Weights
are random, drawn from SEED; times printed here are smoke readings,
not metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
import traceback
from typing import Optional

SEED = 0
# the flag every phase is held to: a Pallas kernel in a compiled TPU
# program is a custom call of this name
TPU_KERNEL_MARKER = "tpu_custom_call"


# --------------------------------------------------------------------------
# what the run is held to, and at which sizes
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Target:
    """main() builds the chip's; the CPU rehearsal test injects its own."""
    interpret: bool = False                 # kernels phase: compiled
    kernel_marker: Optional[str] = TPU_KERNEL_MARKER
    min_sharded_bytes: int = 4 << 20        # leaves held to the 1/n rule


@dataclasses.dataclass(frozen=True)
class KernelShapes:
    heads: int = 32
    kv_heads: int = 8
    head_dims: tuple = (128, 64)            # serve phase, train phase
    batch: int = 2
    seq: int = 1024
    scan_steps: int = 2
    page: int = 128
    pages_per_seq: int = 4
    slots: int = 4
    prefill_rows: int = 24
    chunk_rows: int = 40


@dataclasses.dataclass(frozen=True)
class TrainSize:
    """Llama-3.2-1B widths (published: 16 layers, tied head)."""
    name: str = "llama-3.2-1b"
    vocab: int = 128256
    hidden: int = 2048
    inter: int = 8192
    heads: int = 32
    kv_heads: int = 8
    tied: bool = True
    full_layers: int = 16
    layers: int = 8             # depth cut: bf16 weights + fp32 master +
    #                             two moments (14 B/param) on one chip
    batch: int = 2
    seq: int = 2048
    accum: int = 2
    steps: int = 3
    lr: float = 1e-3
    # step-0 loss band around ln(vocab): N(0, 0.02) weights and a unit
    # RMS-norm give logits of std ~0.02*sqrt(hidden) <= 1.3, which adds
    # about std^2/2 <= 0.85 to the uniform-guess loss
    loss_band: tuple = (-0.1, 1.0)


@dataclasses.dataclass(frozen=True)
class ServeSize:
    """Llama-3-8B widths (published: 32 layers, untied head)."""
    name: str = "llama-3-8b"
    vocab: int = 128256
    hidden: int = 4096
    inter: int = 14336
    heads: int = 32
    kv_heads: int = 8
    full_layers: int = 32
    bf16_layers: int = 16       # depth cut of the bf16 leg (2 B/param)
    int8_layers: int = 32       # the whole model in int8
    page: int = 128
    max_seq_len: int = 512
    slots: int = 4
    num_pages: int = 33
    prefill_budget: int = 128
    prefix_len: int = 160       # shared prefix: one full page and more
    suffix_lens: tuple = (40, 17, 64, 33, 25)
    max_new: int = 8


# sizes of the four-chip run: (a) is TrainSize as it is, (b) the 8B
# widths at a depth one chip cannot hold: 4 layers + embedding + head
# are 1.9e9 params x 14 B = 27 GB
MULTICHIP_8B = TrainSize(name="llama-3-8b", hidden=4096, inter=14336,
                         tied=False, full_layers=32, layers=4)


# --------------------------------------------------------------------------
# small helpers
# --------------------------------------------------------------------------

def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileClock:
    """Seconds JAX spent in backend compilation (cache reads included),
    from its own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += secs


def hbm() -> str:
    """bytes in use / peak so far, per device (peak never resets in a
    process, so a phase's peak is also every earlier phase's)."""
    import jax

    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append(f"{d.id}:{st.get('bytes_in_use', 0) / 2**30:.2f}"
                   f"/{st.get('peak_bytes_in_use', 0) / 2**30:.2f}GiB")
    return "in_use/peak " + " ".join(out)


def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|, in fp32."""
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def compiled_text(jitted, *args, **kwargs) -> str:
    """Optimized HLO of the program ``jitted(*args)`` runs; the second
    compile of the same program is a cache read."""
    import jax

    def spec(a):
        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)
        return a

    args, kwargs = jax.tree_util.tree_map(spec, (args, kwargs))
    return jitted.lower(*args, **kwargs).compile().as_text()


def check_marker(text: str, target: Target, what: str) -> None:
    if target.kernel_marker is not None:
        check(target.kernel_marker in text,
              f"{what}: no {target.kernel_marker} in the compiled program "
              f"(the Pallas kernel is not in it)")


def seeded_params(model, seed: int):
    """Re-draw every weight of ``model`` from ``seed`` on the device:
    N(0, 0.02) matrices (the Llama init), unit norms, in each param's
    own dtype; one param at a time, so nothing is held twice."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    for i, (name, p) in enumerate(model.named_parameters()):
        if name.endswith("norm.weight"):
            v = jnp.ones(p.shape, p._value.dtype)
        else:
            v = (0.02 * jax.random.normal(
                jax.random.fold_in(key, i), tuple(p.shape), jnp.float32)
                 ).astype(p._value.dtype)
        p.set_value(v)
    return model.functional_state()


# --------------------------------------------------------------------------
# phase: kernels
# --------------------------------------------------------------------------

# bf16 carries 8 mantissa bits (eps 2^-8 = 0.0039).  Kernel and reference
# both round scores' softmax weights and the output to bf16 and
# accumulate in fp32 in different orders, so a few eps of the largest
# value is the floor; 0.02 is five eps and far below any real fault
# (a wrong mask, block or head mapping gives errors of order 1).  The
# backward chains three more bf16 matmuls (dP, dS, dQ/dK/dV) on the
# forward's rounding and came to five eps on the chip (0.0197 at s=1024,
# d=128), so it is held to ten.
KERNEL_TOL = 0.02
KERNEL_BWD_TOL = 0.04


def _paged_reference(q, k_cache, v_cache, lens, tables, scale):
    """Plain XLA paged attention: q [n, h, d]; caches [pages, kvh, page,
    d]; row i sees the first lens[i] positions of the pages tables[i]."""
    import jax
    import jax.numpy as jnp

    n, h, d = q.shape
    kvh, page = k_cache.shape[1], k_cache.shape[2]
    rep = h // kvh
    idx = jnp.maximum(tables, 0)

    def seq(cache):                      # [n, kvh, pages*page, d] fp32
        c = cache[idx].astype(jnp.float32)
        return c.transpose(0, 2, 1, 3, 4).reshape(n, kvh, -1, d)

    k, v = seq(k_cache), seq(v_cache)
    qg = q.reshape(n, kvh, rep, d).astype(jnp.float32)
    s = jnp.einsum("ngrd,ngtd->ngrt", qg, k) * scale
    vis = jnp.arange(k.shape[2])[None, None, None, :] \
        < lens[:, None, None, None]
    p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1)
    p = jnp.where(vis, p, 0.0)
    return jnp.einsum("ngrt,ngtd->ngrd", p, v).reshape(n, h, d)


def _kernels_for_head_dim(d: int, ks: KernelShapes, target: Target) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from paddle_tpu.ops.pallas.decode_attention import (
        paged_decode_raw, ragged_paged_decode_raw)
    from paddle_tpu.ops.pallas.flash_attention import (_attn_reference,
                                                       flash_attention_raw)

    h, kvh, b, s = ks.heads, ks.kv_heads, ks.batch, ks.seq
    scale = d ** -0.5
    rng = np.random.default_rng(SEED + d)

    def bf16(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    # ---- flash forward + backward (head-batched default) ----
    q, k, v = bf16(b, s, h, d), bf16(b, s, kvh, d), bf16(b, s, kvh, d)

    def flash(q, k, v):
        return flash_attention_raw(q, k, v, causal=True,
                                   interpret=target.interpret)

    def ref(q, k, v):
        return _attn_reference(q, k, v, True, scale)

    def sq_loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    flash_j = jax.jit(flash)
    check_marker(compiled_text(flash_j, q, k, v), target, f"flash d={d}")
    fwd = rel_err(flash_j(q, k, v), jax.jit(ref)(q, k, v))
    g_f = jax.jit(jax.grad(sq_loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_r = jax.jit(jax.grad(sq_loss(ref), argnums=(0, 1, 2)))(q, k, v)
    bwd = max(rel_err(a, r) for a, r in zip(g_f, g_r))
    say(f"kernels d={d}: flash fwd rel_err={fwd:.4f} bwd rel_err={bwd:.4f}")
    check(fwd < KERNEL_TOL and bwd < KERNEL_BWD_TOL,
          f"flash d={d} out of tolerance: fwd {fwd}, bwd {bwd}")

    # ---- the same kernels inside lax.scan (the accum step's shape) ----
    def scan_prog(fn):
        def body(qc, _):
            val, g = jax.value_and_grad(sq_loss(fn))(qc, k, v)
            return qc - 1e-3 * g.astype(qc.dtype), val
        return jax.jit(lambda q0: lax.scan(body, q0, None,
                                           length=ks.scan_steps))

    scan_j = scan_prog(flash)
    check_marker(compiled_text(scan_j, q), target, f"flash-in-scan d={d}")
    (q_f, vals_f), (q_r, vals_r) = scan_j(q), scan_prog(ref)(q)
    e_q = rel_err(q_f, q_r)
    e_v = float(jnp.max(jnp.abs(vals_f - vals_r) / jnp.abs(vals_r)))
    say(f"kernels d={d}: flash in scan q rel_err={e_q:.4f} "
        f"loss rel_err={e_v:.4f}")
    check(e_q < KERNEL_BWD_TOL and e_v < KERNEL_TOL,
          f"flash-in-scan d={d} out of tolerance: {e_q}, {e_v}")

    # ---- paged + ragged paged decode, bf16 and int8 caches ----
    page, pps, slots = ks.page, ks.pages_per_seq, ks.slots
    n_pages = slots * pps + 1
    tables = jnp.asarray(
        rng.permutation(n_pages - 1)[:slots * pps].reshape(slots, pps),
        jnp.int32)
    cap = page * pps
    seq_lens = jnp.asarray(rng.integers(1, cap + 1, slots), jnp.int32)
    # ragged rows: one decode row per slot, a prefill run on slot 0, a
    # chunk of slot 1 that starts mid-page in its second page (with the
    # rows before it, longer than one query tile of the kernel: a tile
    # of one slot's rows walks that slot's pages once) and padding rows
    # (slot -1) the kernel must zero
    pre = min(ks.prefill_rows, cap)
    at = page + page // 2 - 1
    chunk = min(ks.chunk_rows, cap - at)
    row_slot = np.concatenate([np.arange(slots), np.zeros(pre, np.int64),
                               np.ones(chunk, np.int64),
                               -np.ones(4, np.int64)])
    row_lens = np.concatenate([np.asarray(seq_lens), np.arange(1, pre + 1),
                               at + np.arange(1, chunk + 1),
                               np.zeros(4, np.int64)])
    row_slot = jnp.asarray(row_slot, jnp.int32)
    row_lens = jnp.asarray(row_lens, jnp.int32)
    live = (row_slot >= 0)[:, None, None]
    row_tables = tables[jnp.maximum(row_slot, 0)]
    for cache_dtype in (jnp.bfloat16, jnp.int8):
        name = jnp.dtype(cache_dtype).name
        shape = (n_pages, kvh, page, d)
        if cache_dtype == jnp.int8:
            # int8 KV as serving uses it: the dequant scale is folded
            # into q by the caller, the kernel reads raw int8
            kc = jnp.asarray(np.clip(np.round(
                rng.standard_normal(shape) * 32), -127, 127), jnp.int8)
            vc = jnp.asarray(np.clip(np.round(
                rng.standard_normal(shape) * 32), -127, 127), jnp.int8)
            q_scale = 1.0 / 32
        else:
            kc, vc, q_scale = bf16(*shape), bf16(*shape), 1.0
        qd = (bf16(slots, h, d).astype(jnp.float32) * q_scale
              ).astype(jnp.bfloat16)
        qr = (bf16(row_slot.shape[0], h, d).astype(jnp.float32) * q_scale
              ).astype(jnp.bfloat16)

        paged_j = jax.jit(lambda q, kc, vc: paged_decode_raw(
            q, kc, vc, seq_lens, tables, scale=scale,
            interpret=target.interpret))
        check_marker(compiled_text(paged_j, qd, kc, vc), target,
                     f"paged decode {name} d={d}")
        e_p = rel_err(paged_j(qd, kc, vc), _paged_reference(
            qd, kc, vc, seq_lens, tables, scale))

        ragged_j = jax.jit(lambda q, kc, vc: ragged_paged_decode_raw(
            q, kc, vc, row_lens, row_slot, tables, scale=scale,
            interpret=target.interpret))
        check_marker(compiled_text(ragged_j, qr, kc, vc), target,
                     f"ragged paged decode {name} d={d}")
        got = ragged_j(qr, kc, vc)
        want = jnp.where(live, _paged_reference(
            qr, kc, vc, row_lens, row_tables, scale), 0.0)
        e_r = rel_err(got, want)
        pad_zero = bool(jnp.all(jnp.where(live, 0.0,
                                          got.astype(jnp.float32)) == 0.0))
        say(f"kernels d={d} {name}: paged rel_err={e_p:.4f} "
            f"ragged rel_err={e_r:.4f}")
        check(e_p < KERNEL_TOL and e_r < KERNEL_TOL and pad_zero,
              f"paged decode {name} d={d} out of tolerance: {e_p}, {e_r}, "
              f"padding rows zero={pad_zero}")


def phase_kernels(ks: KernelShapes, target: Target) -> None:
    for d in ks.head_dims:
        _kernels_for_head_dim(d, ks, target)


# --------------------------------------------------------------------------
# phase: train (one chip, or sharded over a mesh)
# --------------------------------------------------------------------------

def _llama_cfg(size, layers: int, max_pos: int):
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=size.vocab, hidden_size=size.hidden,
        intermediate_size=size.inter, num_hidden_layers=layers,
        num_attention_heads=size.heads, num_key_value_heads=size.kv_heads,
        max_position_embeddings=max_pos,
        tie_word_embeddings=getattr(size, "tied", False), dtype="bfloat16")


def check_sharded(tree, n_devices: int, target: Target, what: str) -> None:
    """Every large leaf has shards on ``n_devices`` distinct devices at
    about 1/n of its bytes each (replication or a first-device pile-up
    fails here)."""
    import jax

    worst = 0.0
    for path, a in jax.tree_util.tree_leaves_with_path(tree):
        if a.nbytes < target.min_sharded_bytes:
            continue
        shards = a.addressable_shards
        devs = {s.device for s in shards}
        share = max(s.data.nbytes for s in shards) / a.nbytes
        worst = max(worst, share)
        check(len(devs) == n_devices and share <= 1.25 / n_devices,
              f"{what}{jax.tree_util.keystr(path)}: shards on {len(devs)} "
              f"device(s), largest holds {share:.2f} of the bytes")
    say(f"{what}: every leaf >= {target.min_sharded_bytes} B is cut over "
        f"{n_devices} devices (largest shard share {worst:.3f})")


def run_train(size: TrainSize, target: Target, clock: CompileClock,
              mesh=None) -> list:
    """A few AdamW steps on one repeated batch through the normal entry
    points; returns the loss of each step.  With ``mesh``, the
    sharding-stage-3 x TP step over it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaForCausalLM, apply_llama_sharding,
                                   build_train_step)
    from paddle_tpu.models.llama import llama_decay_mask

    where = "one chip" if mesh is None else \
        f"mesh {dict(mesh.shape)} over {mesh.devices.size} devices"
    say(f"train {size.name}: hidden {size.hidden} inter {size.inter} "
        f"heads {size.heads}:{size.kv_heads} x {size.hidden // size.heads} "
        f"vocab {size.vocab} tied={size.tied}; depth cut {size.layers} of "
        f"{size.full_layers} layers; batch {size.batch} x {size.seq} "
        f"accum {size.accum}; {where}")
    t0 = time.perf_counter()
    c0 = clock.total
    model = LlamaForCausalLM(_llama_cfg(size, size.layers, size.seq))
    params = seeded_params(model, SEED)
    opt = paddle.optimizer.AdamW(learning_rate=size.lr,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    mask = llama_decay_mask(model)
    flat_layout = None
    if mesh is not None:
        from paddle_tpu.parallel.schedule import PartitionSchedule

        sched = PartitionSchedule.from_model(model, mesh)
        apply_llama_sharding(model, mesh, schedule=sched)
        params = model.functional_state()
        flat_layout = sched.flat_update_layout()
        step = build_train_step(model, opt, mesh,
                                compute_dtype=jnp.bfloat16,
                                accum_steps=size.accum, schedule=sched)
    else:
        step = build_train_step(model, opt, compute_dtype=jnp.bfloat16,
                                accum_steps=size.accum)
    opt_state = opt.init_flat_state(params, decay_mask=mask,
                                    flat_layout=flat_layout)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    say(f"train {size.name}: {n_params / 1e6:.0f}M params, state built in "
        f"{time.perf_counter() - t0:.1f}s; {hbm()}")
    if mesh is not None:
        check_sharded(params, mesh.devices.size, target, "params")
        check_sharded(opt_state, mesh.devices.size, target,
                      "optimizer state")

    rng = np.random.default_rng(SEED)
    shape = (size.accum, size.batch, size.seq) if size.accum > 1 \
        else (size.batch, size.seq)
    ids = rng.integers(0, size.vocab, shape).astype(np.int32)
    labels = np.roll(ids, -1, axis=-1)

    jit_step = step.__wrapped__
    text = compiled_text(jit_step, params, opt_state, np.int32(0),
                         np.float32(size.lr), ids, labels)
    check_marker(text, target, f"train step {size.name}")
    if mesh is not None:
        n_ag = text.count(" all-gather(") + text.count(" all-gather-start(")
        n_rs = text.count(" reduce-scatter(")
        n_ar = text.count(" all-reduce(") + text.count(" all-reduce-start(")
        say(f"train {size.name}: compiled step holds {n_ag} all-gather, "
            f"{n_rs} reduce-scatter, {n_ar} all-reduce")
        check(n_ag > 0 and n_rs + n_ar > 0,
              "sharded step without all-gather / reduce-scatter: the "
              "program does not communicate, so it is not sharded")

    losses, secs = [], []
    for i in range(size.steps):
        t = time.perf_counter()
        loss, params, opt_state = step(params, opt_state, i, size.lr,
                                       ids, labels)
        losses.append(float(jax.block_until_ready(loss)))
        secs.append(time.perf_counter() - t)
    n_compiled = jit_step._cache_size()
    say(f"train {size.name}: losses {[round(x, 4) for x in losses]}; "
        f"wall s/step {[round(x, 2) for x in secs]} (first includes the "
        f"compile); backend compile {clock.total - c0:.1f}s; {hbm()}")
    lo, hi = (math.log(size.vocab) + d for d in size.loss_band)
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(lo <= losses[0] <= hi,
          f"step-0 loss {losses[0]:.3f} outside [{lo:.2f}, {hi:.2f}]")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"loss not falling on a repeated batch: {losses}")
    check(n_compiled == 1, f"{n_compiled} compilations of the step, not 1")
    if mesh is not None:
        check_sharded(params, mesh.devices.size, target,
                      "params after the steps")
        check_sharded(opt_state, mesh.devices.size, target,
                      "optimizer state after the steps")
    return losses


# sharded vs one-chip loss, per step: both run bf16 matmuls, but TP cuts
# the contraction dims, so partial sums round in another order (~1e-3 at
# step 0 over 8k tokens); AdamW then turns last-bit gradient differences
# into +-lr steps on near-zero gradients, which widens the gap step by
# step.  0.05 is 0.4% of a loss of 12; a wrong shard (a lost quarter of
# a contraction) moves the loss by whole units.
MULTICHIP_LOSS_TOL = 0.05


def phase_multichip(size_a: TrainSize, size_b: TrainSize, target: Target,
                    clock: CompileClock, devices) -> None:
    import numpy as np
    from jax.sharding import Mesh

    check(len(devices) == 4, f"--chips 4 needs 4 devices, "
                             f"found {len(devices)}")
    mesh = Mesh(np.asarray(devices, dtype=object).reshape(1, 1, 2, 1, 2),
                axis_names=("pp", "dp", "sharding", "sep", "mp"))
    # (a) the one-chip step on devices[0] (JAX's default device), then
    # the same parameters and batch over the mesh
    ref = run_train(size_a, target, clock)
    sharded = run_train(size_a, target, clock, mesh=mesh)
    gaps = [abs(a - b) for a, b in zip(ref, sharded)]
    say(f"multichip (a): |sharded - one-chip| loss per step "
        f"{[round(g, 5) for g in gaps]} (tolerance {MULTICHIP_LOSS_TOL})")
    check(max(gaps) <= MULTICHIP_LOSS_TOL,
          f"sharded losses {sharded} differ from one-chip {ref}")
    # (b) the configuration that needs four chips
    run_train(size_b, target, clock, mesh=mesh)


# --------------------------------------------------------------------------
# phase: serve
# --------------------------------------------------------------------------

# engine logits vs the model's full forward, max |diff| over max |ref|:
# both are bf16 programs of the same weights (chunked paged prefill +
# decode against one causal flash pass), so they differ by rounding
# accumulated over the layers: a few bf16 eps (0.0039) per layer-pair,
# growing like sqrt(depth).  0.05 holds that for 16-32 layers; a wrong
# page, position or cached prefix changes logits by order 1.
SERVE_LOGIT_TOL = 0.05


def _serve_requests(size: ServeSize):
    import numpy as np

    rng = np.random.default_rng(SEED)
    prefix = rng.integers(0, size.vocab, size.prefix_len)
    return [np.concatenate([prefix, rng.integers(0, size.vocab, n)]
                           ).astype(np.int32) for n in size.suffix_lens]


def _drive_engine(eng, prompts, size: ServeSize):
    """Staggered admission: two requests first, the rest one by one
    while the earlier ones decode.  Returns ({rid: tokens}, {(rid,
    position): logits row}, seconds per step)."""
    import numpy as np

    rows, secs = {}, []
    pending = list(prompts)

    def step():
        t = time.perf_counter()
        eng.step()
        secs.append(time.perf_counter() - t)
        if eng.last_logits is not None:
            labels, logits = eng.last_logits
            check(bool(np.isfinite(logits).all()), "non-finite logits")
            for (rid, pos), row in zip(labels, logits):
                rows[(rid, pos)] = row
            eng.last_logits = None

    for p in pending[:2]:
        eng.add_request(p, max_new_tokens=size.max_new)
    for p in pending[2:]:
        step()
        step()
        eng.add_request(p, max_new_tokens=size.max_new)
    it = 0
    while eng.queue or eng.active.any():
        step()
        it += 1
        check(it < 10_000, "serving loop did not drain")
    done = {f.rid: list(f.tokens) for f in eng.finished}
    check(sorted(done) == list(range(len(prompts))),
          f"finished {sorted(done)} of {len(prompts)} requests")
    for rid, toks in done.items():
        check(len(toks) == size.max_new
              and all(0 <= t < size.vocab for t in toks),
              f"request {rid}: bad tokens {toks}")
    return done, rows, secs


def _engine(cfg, params, size: ServeSize, **kw):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    return ContinuousBatchingEngine(
        cfg, params, max_slots=size.slots, num_pages=size.num_pages,
        page_size=size.page, max_seq_len=size.max_seq_len,
        prefill_token_budget=size.prefill_budget,
        enable_prefix_cache=True, **kw)


def _finish_engine(eng, target: Target, what: str) -> None:
    """Cache-hit, consistency, leak and kernel-in-program checks."""
    stats = eng.serving_stats()
    say(f"{what}: prefix cache {stats['prefix_cache']}")
    check(stats["prefix_cache"]["hits"] >= 1,
          f"{what}: no prefix-cache hit on a shared {eng.page_size}+ "
          f"token prefix")
    eng.alloc.assert_consistent()
    eng.prefix_cache.assert_consistent()
    fn, args, kwargs, _ = eng.analysis_entry()
    check_marker(compiled_text(fn, *args, **kwargs), target,
                 f"{what} decode step")
    eng.shutdown()                       # page-leak assertion


def _serve_bf16(size: ServeSize, target: Target, clock: CompileClock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.autograd import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import LlamaForCausalLM

    c0, t0 = clock.total, time.perf_counter()
    cfg = _llama_cfg(size, size.bf16_layers, size.max_seq_len)
    model = LlamaForCausalLM(cfg)
    params = seeded_params(model, SEED)
    say(f"serve bf16: weights built in {time.perf_counter() - t0:.1f}s; "
        f"{hbm()}")
    prompts = _serve_requests(size)
    eng = _engine(cfg, params, size)
    done, rows, secs = _drive_engine(eng, prompts, size)
    _finish_engine(eng, target, "serve bf16")

    # the model's own full forward over prompt + generated tokens
    seqs = [np.concatenate([p, np.asarray(done[i], np.int32)])
            for i, p in enumerate(prompts)]
    width = -(-max(map(len, seqs)) // 128) * 128     # flash tile multiple
    ids = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s

    @jax.jit
    def forward(params, ids):
        with no_grad():
            return model.functional_call(params, Tensor(ids))._value

    check_marker(compiled_text(forward, params, ids), target,
                 "serve bf16 reference forward")
    ref = np.asarray(forward(params, ids).astype(jnp.float32))
    worst, agree = 0.0, 0
    for (rid, pos), row in rows.items():
        want = ref[rid, pos]
        worst = max(worst, float(np.abs(row - want).max()
                                 / np.abs(want).max()))
        agree += int(row.argmax() == want.argmax())
    n_decode = sum(1 for (rid, pos) in rows if pos >= len(prompts[rid]))
    say(f"serve bf16: {len(rows)} logit rows ({n_decode} decode) vs the "
        f"full forward: rel_err={worst:.4f} (tolerance {SERVE_LOGIT_TOL}), "
        f"argmax agrees on {agree}; {len(secs)} steps, median wall "
        f"{sorted(secs)[len(secs) // 2] * 1e3:.1f} ms/step, first "
        f"{secs[0]:.1f}s; backend compile {clock.total - c0:.1f}s; {hbm()}")
    check(n_decode >= len(prompts) * (size.max_new - 1),
          f"only {n_decode} decode rows were compared")
    check(worst <= SERVE_LOGIT_TOL,
          f"engine logits differ from the full forward: {worst}")


def _int8_params(size: ServeSize):
    """Weight-only int8 (per-out-channel scale, tied head) drawn on the
    device; the scale puts the dequantized weights at std ~0.02."""
    import jax
    import jax.numpy as jnp

    h, inter = size.hidden, size.inter
    kv = size.kv_heads * (h // size.heads)
    key = jax.random.PRNGKey(SEED)
    scale = 0.02 / 73.3                   # std of uniform int8 is 73.3
    n = [0]

    def w8(shape):
        n[0] += 1
        return jax.random.randint(jax.random.fold_in(key, n[0]), shape,
                                  -127, 128, jnp.int8)

    params = {
        "model.embed_tokens.weight": w8((size.vocab, h)),
        "model.embed_tokens.weight._scale":
            jnp.full((size.vocab,), scale, jnp.float32),
        "model.norm.weight": jnp.ones((h,), jnp.bfloat16),
    }
    shapes = {
        "self_attn.q_proj.weight": (h, h),
        "self_attn.k_proj.weight": (h, kv),
        "self_attn.v_proj.weight": (h, kv),
        "self_attn.o_proj.weight": (h, h),
        "mlp.gate_proj.weight": (h, inter),
        "mlp.up_proj.weight": (h, inter),
        "mlp.down_proj.weight": (inter, h),
    }
    for i in range(size.int8_layers):
        pre = f"model.layers.{i}."
        params[pre + "input_layernorm.weight"] = jnp.ones((h,), jnp.bfloat16)
        params[pre + "post_attention_layernorm.weight"] = \
            jnp.ones((h,), jnp.bfloat16)
        for name, shape in shapes.items():
            params[pre + name] = w8(shape)
            params[pre + name + "._scale"] = \
                jnp.full((shape[1],), scale, jnp.float32)
    return params


def _serve_int8(size: ServeSize, target: Target, clock: CompileClock):
    import dataclasses as dc

    import jax.numpy as jnp

    c0, t0 = clock.total, time.perf_counter()
    cfg = dc.replace(_llama_cfg(size, size.int8_layers, size.max_seq_len),
                     tie_word_embeddings=True)
    params = _int8_params(size)
    gib = sum(v.nbytes for v in params.values()) / 2**30
    say(f"serve int8: {size.int8_layers} layers, {gib:.2f} GiB of weights "
        f"built in {time.perf_counter() - t0:.1f}s; {hbm()}")
    eng = _engine(cfg, params, size, cache_dtype=jnp.int8)
    done, rows, secs = _drive_engine(eng, _serve_requests(size), size)
    _finish_engine(eng, target, "serve int8")
    say(f"serve int8: {len(done)} requests x {size.max_new} tokens to "
        f"completion, {len(rows)} finite logit rows; {len(secs)} steps, "
        f"median wall {sorted(secs)[len(secs) // 2] * 1e3:.1f} ms/step, "
        f"first {secs[0]:.1f}s; backend compile "
        f"{clock.total - c0:.1f}s; {hbm()}")


def phase_serve(size: ServeSize, target: Target,
                clock: CompileClock) -> None:
    say(f"serve {size.name}: hidden {size.hidden} inter {size.inter} heads "
        f"{size.heads}:{size.kv_heads} x {size.hidden // size.heads} vocab "
        f"{size.vocab}; bf16 leg depth cut {size.bf16_layers} of "
        f"{size.full_layers} layers, int8 leg {size.int8_layers} of "
        f"{size.full_layers}; {len(size.suffix_lens)} requests sharing a "
        f"{size.prefix_len}-token prefix, page {size.page}")
    _serve_bf16(size, target, clock)
    _serve_int8(size, target, clock)


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def require_tpu(n_chips: int):
    """The attached devices, or an error: there is no CPU branch."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {devs[0].platform!r} "
            f"({devs[0].device_kind})")
    if len(devs) != n_chips:
        raise RuntimeError(f"chip_smoke was asked for {n_chips} chip(s); "
                           f"JAX found {len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step and what "
                         "it is compared with")
    args = ap.parse_args(argv)
    device = None
    t0 = time.perf_counter()
    try:
        devs = require_tpu(args.chips)
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        from paddle_tpu.utils.compile_cache import enable_compile_cache

        say(f"device {device}; compile cache at {enable_compile_cache()}")
        clock, target = CompileClock(), Target()
        if args.chips == 4:
            phases = [("multichip", lambda: phase_multichip(
                TrainSize(), MULTICHIP_8B, target, clock, devs))]
        else:
            phases = [
                ("kernels", lambda: phase_kernels(KernelShapes(), target)),
                ("train", lambda: run_train(TrainSize(), target, clock)),
                ("serve", lambda: phase_serve(ServeSize(), target, clock)),
            ]
        for name, run in phases:
            t = time.perf_counter()
            c = clock.total
            run()
            say(f"phase {name}: ok in {time.perf_counter() - t:.1f}s "
                f"(backend compile {clock.total - c:.1f}s); {hbm()}")
        say(f"all phases ok in {time.perf_counter() - t0:.1f}s, backend "
            f"compile {clock.total:.1f}s in total")
    except Exception:  # noqa: BLE001 — report the failure, fail the run
        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
