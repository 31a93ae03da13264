"""Head-batched GQA flash inside lax.scan — the former crash repro,
now the REGRESSION GATE for the root-caused fix (round-7).

History: the head-batched kernels (one k/v stream per GQA group, fused
group-summed backward; ops/pallas/flash_attention.py _flash_hb) measure
~7% faster fwd+bwd than the per-head kernels at the flagship shape, but
shipped disabled because embedding them in a lax.scan/fori_loop
reproducibly crashed the TPU compiler (standalone jit
compiled and passed the numeric gate).  Round-7 root-caused the crash to
in-kernel sublane<->lane relayouts (the flush-branch ``swapaxes`` on lse,
the backward's swapaxes loads, and 2D<->3D broadcast-reshape round trips
on the softmax state) — constructs absent from the scan-proven per-head
kernels — and removed them; see the relayout note above the HB kernel
section in flash_attention.py.  The kernels are now the DEFAULT
(PADDLE_TPU_FLASH_HEAD_BATCHED=0 opts out), and this file asserts the
exact program that used to crash compiles and matches the XLA reference
on whatever backend is attached."""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.device import pallas_interpret
from paddle_tpu.ops.pallas.flash_attention import (_attn_reference,
                                                   _flash_hb, _to_hb)


def _scan_program(q, k, v, h, kvh, steps, interpret):
    """The formerly-crashing program: the head-batched flash fwd+bwd
    embedded in a lax.scan (the accum-train-step structure)."""
    b, s, _, d = q.shape
    rep = h // kvh
    qhb, khb, vhb = _to_hb(q, k, v, h, kvh)

    def loss(qx):
        o = _flash_hb(qx, khb, vhb, True, d ** -0.5, interpret)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def body(carry, _):
        qc = carry
        val, g = jax.value_and_grad(loss)(qc)
        return qc - 1e-3 * g.astype(qc.dtype), val

    final, vals = lax.scan(body, qhb, None, length=steps)
    out = final.reshape(b, kvh, rep, s, d).reshape(
        b, kvh * rep, s, d).transpose(0, 2, 1, 3)
    return out, vals


def test_head_batched_flash_in_scan_compiles_and_matches():
    """Formerly skip-marked on TPU with the tpu_compile_helper crash
    signature; un-skipped in round-7 after the relayout root-cause fix.
    Green here on a TPU backend is the proof the fix holds on-device
    (this session's CPU run exercises the compiled-interpret variant)."""
    _run(interpret=pallas_interpret())


def test_head_batched_flash_in_scan_interpret():
    """Interpret-mode anchor: proves the PROGRAM is well-formed and
    numerically right independent of the Mosaic/compile layer (the split
    that localised the original crash to the compiler)."""
    _run(interpret=True)


def _run(interpret):
    rng = np.random.default_rng(0)
    b, s, h, kvh, d = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)

    prog = jax.jit(lambda q, k, v: _scan_program(q, k, v, h, kvh,
                                                 steps=2,
                                                 interpret=interpret))
    out, vals = prog(q, k, v)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(vals)).all()

    # step-0 loss must equal the XLA reference attention's loss (the
    # kernel ran correctly inside the scan, not just compiled)
    ref = _attn_reference(q, k, v, True, d ** -0.5)
    want = float(jnp.sum(ref.astype(jnp.float32) ** 2))
    got = float(np.asarray(vals)[0])
    assert abs(got - want) / abs(want) < 2e-3, (got, want)
