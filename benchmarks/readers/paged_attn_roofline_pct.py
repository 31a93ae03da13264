"""The ragged paged attention kernel's share of its roofline, in %: the
least time the chip could take for the attention of the window's steps,
over the device time the kernel ``kernel`` took in them.

The least time is the larger of

    operations / peak bf16 rate,  operations = 4 x heads x head_dim
                                    x sum(attn_row_ctx) x layers
    bytes / peak HBM rate,        bytes = sum(kv_ctx_tokens) x kv_heads
                                    x head_dim x 2 (K and V) x itemsize
                                    x layers

``attn_row_ctx`` is the sum of the rows' visibilities (each row's QK^T
and PV against its own context: 2 x 2 x head_dim operations a head and
a context token); ``kv_ctx_tokens`` counts every scheduled slot's
context ONCE, in tokens and not in pages, whatever the kernel re-reads
for a slot's further rows.  Both are the least work, so the share cannot
pass 100% for a right count.  Which of the two bounds it is printed.
"""

from __future__ import annotations

from benchmarks.harness import peaks
from benchmarks.readers import program_trace


def least_seconds(config, counts, device_kind: str):
    """``(seconds, "flops" | "bytes", flops_s, bytes_s)`` for the steps
    whose ``serving.step_counts`` are ``counts``."""
    import jax.numpy as jnp

    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    layers = config["num_hidden_layers"]
    itemsize = jnp.dtype(config["engine"]["cache_dtype"]).itemsize
    flops = 4 * heads * head_dim * layers \
        * sum(c["attn_row_ctx"] for c in counts)
    nbytes = sum(c["kv_ctx_tokens"] for c in counts) \
        * config["num_key_value_heads"] * head_dim * 2 * itemsize * layers
    peak = peaks.peaks_for(device_kind)
    flops_s = flops / peak["bf16_flops_per_s"]
    bytes_s = nbytes / peak["hbm_bytes_per_s"]
    return (max(flops_s, bytes_s), "flops" if flops_s > bytes_s else "bytes",
            flops_s, bytes_s)


def read(obs, kernel: str):
    pt = program_trace.of(obs)
    if pt is None:
        return None
    counts = pt.step_counts()
    kernel_s = pt.kernel_ns_in_steps(kernel) / 1e9
    if not counts or not kernel_s:
        return None
    least, bound, flops_s, bytes_s = least_seconds(
        obs["config"], counts, obs["device_kind"])
    print(f"# {kernel} roofline over {len(counts)} steps: bound by {bound} "
          f"(operations {flops_s * 1e3:.4g} ms, bytes {bytes_s * 1e3:.4g} ms "
          f"at the peaks) against {kernel_s * 1e3:.4g} ms on the device",
          flush=True)
    return 100.0 * least / kernel_s
