"""The KDA scan kernel's share of its roofline, in %: the least time the
chip could take for the KDA layers' recurrence in the window's steps over
the device time of the kernel ``kernel`` (reckoned as
``lightning_scan_roofline_pct.py`` reckons Lightning attention's).

Least work only, whatever implements the scan, from
``serving.step_counts`` (``state_rows``: the live packed rows;
``state_slots``: the scheduled slots, each of which has its state read
once and written once a layer):

    operations / peak bf16 rate,  operations = 6 x heads x head_dim
                                    x head_dim x sum(state_rows) x layers
                                    (a row's read of the state against its
                                    key, its rank-one term into the state
                                    and its read-out against the query;
                                    the decay's multiply is not counted)
    bytes / peak HBM rate,        bytes = (2 x state bytes x
                                    sum(state_slots) + row bytes x
                                    sum(state_rows)) x layers

``state bytes`` = heads x head_dim x head_dim x 4 (float32); ``row
bytes``: a row's q, k and v in the served dtype, its g and its o in
float32 and its beta a head.  ``layers``: the KDA layers among those
that run.  Which of the two bounds it is printed.  Where the program
writes no such counts (a parent that has none) there is nothing to read.
"""

from __future__ import annotations

from benchmarks.harness import peaks
from benchmarks.readers.block_sparse_roofline_pct import share_of_roofline


def least_seconds(config, counts, device_kind: str):
    """``(seconds, "flops" | "bytes", flops_s, bytes_s)`` for the steps
    whose ``serving.step_counts`` are ``counts``, or None where they do
    not hold the scan's counts."""
    import jax.numpy as jnp

    if not all("state_rows" in c and "state_slots" in c for c in counts):
        return None
    lin = config["linear_attn_config"]
    H, d = lin["num_heads"], lin["head_dim"]
    layers = sum(n <= config["num_hidden_layers"] for n in lin["kda_layers"])
    itemsize = jnp.dtype(config["torch_dtype"]).itemsize
    rows = sum(c["state_rows"] for c in counts)
    slots = sum(c["state_slots"] for c in counts)
    flops = 6 * H * d * d * rows * layers
    row_bytes = H * d * (3 * itemsize + 4 + 4) + H * 4
    nbytes = (2 * H * d * d * 4 * slots + row_bytes * rows) * layers
    peak = peaks.peaks_for(device_kind)
    flops_s = flops / peak["bf16_flops_per_s"]
    bytes_s = nbytes / peak["hbm_bytes_per_s"]
    return (max(flops_s, bytes_s), "flops" if flops_s > bytes_s else "bytes",
            flops_s, bytes_s)


def read(obs, kernel: str):
    return share_of_roofline(obs, kernel, least_seconds, " (kda)")
