"""One KIND of layer's share of its attention roofline, in %, for a model
that mixes window and full layers (``layer_types``): the least time the
chip could take for that kind's attention in the window's steps, over
the device time of the kernel ``kernel`` (each kind's launches carry
their own name).

``benchmarks/readers/paged_attn_roofline_pct.py``'s two bounds, with the
kind's own counts and the kind's own number of layers:

    operations / peak bf16 rate,  operations = 4 x heads x head_dim
                                    x sum(row contexts) x layers of the kind
    bytes / peak HBM rate,        bytes = sum(slot contexts) x kv_heads
                                    x head_dim x 2 (K and V) x itemsize
                                    x layers of the kind

``kind`` ``"full"``: ``attn_row_ctx`` and ``kv_ctx_tokens`` over the
``full_attention`` layers.  ``kind`` ``"window"``: ``attn_row_ctx_window``
(a row's keys capped at the window) and ``kv_ctx_tokens_window`` (a
slot's positions capped at the window) over the ``sliding_attention``
layers.  Both are the least work, so the share cannot pass 100% for a
right count.  Which of the two bounds it is printed.  Where the program
writes no such counts (a parent that has none) there is nothing to read.
"""

from __future__ import annotations

from benchmarks.harness import peaks
from benchmarks.readers import program_trace

KINDS = {"full": ("attn_row_ctx", "kv_ctx_tokens", "full_attention"),
         "window": ("attn_row_ctx_window", "kv_ctx_tokens_window",
                    "sliding_attention")}


def least_seconds(config, counts, device_kind: str, kind: str):
    """``(seconds, "flops" | "bytes", flops_s, bytes_s)`` for the steps
    whose ``serving.step_counts`` are ``counts``, or None where they do
    not hold the kind's counts."""
    import jax.numpy as jnp

    if kind not in KINDS:
        raise ValueError(f"kind {kind!r}: one of {sorted(KINDS)}")
    rows_key, slots_key, layer_type = KINDS[kind]
    if not all(rows_key in c and slots_key in c for c in counts):
        return None
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    types = list(config["layer_types"])[:config["num_hidden_layers"]]
    layers = types.count(layer_type)
    itemsize = jnp.dtype(config["engine"]["cache_dtype"]).itemsize
    flops = 4 * heads * head_dim * layers * sum(c[rows_key] for c in counts)
    nbytes = sum(c[slots_key] for c in counts) \
        * config["num_key_value_heads"] * head_dim * 2 * itemsize * layers
    peak = peaks.peaks_for(device_kind)
    flops_s = flops / peak["bf16_flops_per_s"]
    bytes_s = nbytes / peak["hbm_bytes_per_s"]
    return (max(flops_s, bytes_s), "flops" if flops_s > bytes_s else "bytes",
            flops_s, bytes_s)


def read(obs, kernel: str, kind: str):
    pt = program_trace.of(obs)
    if pt is None:
        return None
    counts = pt.step_counts()
    kernel_s = pt.kernel_ns_in_steps(kernel) / 1e9
    if not counts or not kernel_s:
        return None
    least = least_seconds(obs["config"], counts, obs["device_kind"], kind)
    if least is None:
        return None
    seconds, bound, flops_s, bytes_s = least
    print(f"# {kernel} ({kind} layers) roofline over {len(counts)} steps: "
          f"bound by {bound} (operations {flops_s * 1e3:.4g} ms, bytes "
          f"{bytes_s * 1e3:.4g} ms at the peaks) against "
          f"{kernel_s * 1e3:.4g} ms on the device", flush=True)
    return 100.0 * seconds / kernel_s
