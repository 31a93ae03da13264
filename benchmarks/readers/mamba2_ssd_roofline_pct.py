"""The Mamba-2 scan kernel's share of its roofline, in %: the least
time the chip could take for the M layers' scan in the window's steps
over the device time of the kernel ``kernel``.

Least work only, from ``serving.step_counts`` (``ssm_rows``: the live
packed rows; ``ssm_state_slots``: the scheduled slots, each of which has
its state read once and written once a state layer):

    operations / peak bf16 rate,  operations = 4 x heads x head_dim x state
                                    x sum(ssm_rows) x M layers
                                    (a row's outer product into the state
                                    and its read-out against C, a
                                    multiply and an add each)
    bytes / peak HBM rate,        bytes = (2 x state bytes x
                                    sum(ssm_state_slots) + row bytes x
                                    sum(ssm_rows)) x M layers

``state bytes`` = heads x head_dim x state x 4 (float32); ``row bytes``:
a row's x and its y (heads x head_dim each), its B and C (groups x state
each) in the served dtype and its dt (heads, float32).  The chunked form
a prefill unit takes does MORE arithmetic than this (the products inside
a chunk), so the share cannot pass 100% for a right count.  Which of the
two bounds it is printed.  Where the program writes no such counts (a
parent that has none) there is nothing to read.
"""

from __future__ import annotations

from benchmarks.harness import peaks
from benchmarks.readers import program_trace


def least_seconds(config, counts, device_kind: str):
    """``(seconds, "flops" | "bytes", flops_s, bytes_s)`` for the steps
    whose ``serving.step_counts`` are ``counts``, or None where they do
    not hold the scan's counts."""
    import jax.numpy as jnp

    if not all("ssm_rows" in c and "ssm_state_slots" in c for c in counts):
        return None
    H, P, N, G = (config["mamba_num_heads"], config["mamba_head_dim"],
                  config["ssm_state_size"], config["n_groups"])
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    layers = pattern.count("M")
    itemsize = jnp.dtype(config["torch_dtype"]).itemsize
    rows = sum(c["ssm_rows"] for c in counts)
    slots = sum(c["ssm_state_slots"] for c in counts)
    flops = 4 * H * P * N * rows * layers
    row_bytes = (2 * H * P + 2 * G * N) * itemsize + H * 4
    nbytes = (2 * H * P * N * 4 * slots + row_bytes * rows) * layers
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
    least = least_seconds(obs["config"], counts, obs["device_kind"])
    if least is None:
        return None
    seconds, bound, flops_s, bytes_s = least
    print(f"# {kernel} roofline over {len(counts)} steps: bound by {bound} "
          f"(operations {flops_s * 1e3:.4g} ms, bytes {bytes_s * 1e3:.4g} ms "
          f"at the peaks) against {kernel_s * 1e3:.4g} ms on the device",
          flush=True)
    return 100.0 * seconds / kernel_s
