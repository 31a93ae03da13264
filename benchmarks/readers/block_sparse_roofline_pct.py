"""A block-sparse attention kernel's share of its roofline, in %: the
least time the chip could take for the ``minicpm4`` layers' part
``part`` in the window's steps over the device time of the kernel
``kernel``.

Least work only, from ``serving.step_counts`` (a layer's counts, times
the ``minicpm4`` layers that run):

    part "scores" (``infllm_block_scores``):
      operations = 2 x query heads x head_dim x sum(ckey_ctx)
                   (every query head of a selecting row against every
                   compressed key of its context)
      bytes      = compressed-key bytes x sum(ckey_slot_ctx) (a slot's
                   compressed keys once a step) + the selecting rows'
                   queries
    part "attn" (``block_sparse_paged_attention``):
      operations = 4 x heads of a group x head_dim x block_size
                   x sum(sel_blocks) (a group's heads over a selected
                   block's tokens: scores and values)
      bytes      = K and V bytes of sum(sel_kv_tokens) tokens (each
                   selected block once a row and group) + the selecting
                   rows' queries in and outputs out

over the peak bf16 rate and the peak HBM rate: the larger is the least
time, and which it is is printed.  The scores kernel's softmax and the
attention's exponentials come on top, so the share cannot pass 100% for
a right count.  Where the program writes no such counts (a parent that
has none) there is nothing to read.
"""

from __future__ import annotations

from benchmarks.harness import peaks
from benchmarks.readers import program_trace

NEEDS = {"scores": ("ckey_ctx", "ckey_slot_ctx", "sparse_rows"),
         "attn": ("sel_blocks", "sel_kv_tokens", "sparse_rows")}


def least_seconds(config, counts, device_kind: str, part: str):
    """``(seconds, "flops" | "bytes", flops_s, bytes_s)`` for the steps
    whose ``serving.step_counts`` are ``counts``, or None where they do
    not hold the part's counts."""
    import jax.numpy as jnp

    if not all(k in c for c in counts for k in NEEDS[part]):
        return None
    H, kvh, d = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    layers = sum(config["mixer_types"][l] == "minicpm4"
                 for l in range(*config["layers_run"]))
    itemsize = jnp.dtype(config["torch_dtype"]).itemsize
    total = lambda k: sum(c[k] for c in counts)  # noqa: E731
    row = H * d * itemsize
    if part == "scores":
        flops = 2 * H * d * total("ckey_ctx")
        nbytes = kvh * d * itemsize * total("ckey_slot_ctx") \
            + row * total("sparse_rows")
    else:
        block = config["sparse_config"]["block_size"]
        flops = 4 * (H // kvh) * d * block * total("sel_blocks")
        nbytes = 2 * d * itemsize * total("sel_kv_tokens") \
            + 2 * row * total("sparse_rows")
    peak = peaks.peaks_for(device_kind)
    flops_s = flops * layers / peak["bf16_flops_per_s"]
    bytes_s = nbytes * layers / peak["hbm_bytes_per_s"]
    return (max(flops_s, bytes_s), "flops" if flops_s > bytes_s else "bytes",
            flops_s, bytes_s)


def share_of_roofline(obs, kernel: str, least, what: str = ""):
    """``least(config, step counts, device kind)``'s seconds over the
    device time of ``kernel`` in the window's whole steps, in %; None
    where there is no trace, no step, no event of the kernel or no such
    counts.  Which bound it is is printed."""
    pt = program_trace.of(obs)
    if pt is None:
        return None
    counts = pt.step_counts()
    kernel_s = pt.kernel_ns_in_steps(kernel) / 1e9
    if not counts or not kernel_s:
        return None
    found = least(obs["config"], counts, obs["device_kind"])
    if found is None:
        return None
    seconds, bound, flops_s, bytes_s = found
    print(f"# {kernel}{what} roofline over {len(counts)} steps: bound by "
          f"{bound} (operations {flops_s * 1e3:.4g} ms, bytes "
          f"{bytes_s * 1e3:.4g} ms at the peaks) against "
          f"{kernel_s * 1e3:.4g} ms on the device", flush=True)
    return 100.0 * seconds / kernel_s


def read(obs, kernel: str, part: str):
    return share_of_roofline(
        obs, kernel, lambda *a: least_seconds(*a, part), f" ({part})")
