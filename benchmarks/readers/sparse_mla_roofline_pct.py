"""A sparse-latent-attention kernel's share of its roofline, in %: the
least time the chip could take for that part of the window's steps,
over the device time the kernel ``kernel`` took in them.  ``part`` says
which part, and with it which counts of ``serving.step_counts`` are read:

``index`` (``lightning_index_scores``): every packed row scores the
positions up to its own.

    operations = 2 x index_n_heads x index_head_dim x sum(index_row_ctx)
                 x layers                (the heads' products; the ReLU
                 and the weighted sum over heads are not counted)
    bytes      = (sum(latent_ctx_tokens) + sum(rows) x index_n_heads)
                 x index_head_dim x itemsize x layers
                 (each scheduled slot's index keys ONCE, each row's
                 queries; the scores it writes are not counted)

``attn`` (``sparse_mla_attention``): every row attends the positions
SELECTED for it, at most ``index_topk``: the masked kernel computes over
the row's whole context, and that is its cost, not the algorithm's.

    operations = 2 x heads x ((kv_lora_rank + qk_rope_head_dim)
                 + kv_lora_rank) x sum(sel_row_tokens) x layers
    bytes      = (sum(rows) x heads x ((kv_lora_rank + qk_rope_head_dim)
                 + kv_lora_rank) + min(sum(latent_ctx_tokens), index_topk)
                 x (kv_lora_rank + qk_rope_head_dim)) x itemsize x layers
                 (each row's absorbed queries in and latent-space output
                 out; of the latents, one slot's selection at the least)

Both are the least work, so the share cannot pass 100% for a right
count.  Which of the two bounds it is printed.  Peaks:
``harness/peaks.py``.  A program without these kernels or counts (the
parent of the PR that added them) gives nothing to read: ``None``.
"""

from __future__ import annotations

from benchmarks.harness import peaks
from benchmarks.readers import program_trace


def least_work(config, counts, part: str):
    """``(operations, bytes)`` for the steps whose counts are ``counts``."""
    import jax.numpy as jnp

    layers = config["num_hidden_layers"]
    itemsize = jnp.dtype(config["engine"]["cache_dtype"]).itemsize
    rows = sum(c["rows"] for c in counts)
    ctx_once = sum(c["latent_ctx_tokens"] for c in counts)
    if part == "index":
        hi, di = config["index_n_heads"], config["index_head_dim"]
        ops = 2 * hi * di * sum(c["index_row_ctx"] for c in counts)
        nbytes = (ctx_once + rows * hi) * di * itemsize
    elif part == "attn":
        h = config["num_attention_heads"]
        dk = config["kv_lora_rank"] + config["qk_rope_head_dim"]
        dv = config["kv_lora_rank"]
        ops = 2 * h * (dk + dv) * sum(c["sel_row_tokens"] for c in counts)
        nbytes = (rows * h * (dk + dv)
                  + min(ctx_once, config["index_topk"]) * dk) * itemsize
    else:
        raise ValueError(f"part {part!r}: 'index' or 'attn'")
    return ops * layers, nbytes * layers


def read(obs, kernel: str, part: str):
    pt = program_trace.of(obs)
    if pt is None:
        return None
    counts = [c for c in pt.step_counts() if "latent_ctx_tokens" in c]
    kernel_s = pt.kernel_ns_in_steps(kernel) / 1e9
    if not counts or not kernel_s:
        return None
    ops, nbytes = least_work(obs["config"], counts, part)
    peak = peaks.peaks_for(obs["device_kind"])
    flops_s = ops / peak["bf16_flops_per_s"]
    bytes_s = nbytes / peak["hbm_bytes_per_s"]
    print(f"# {kernel} roofline over {len(counts)} steps: bound by "
          f"{'operations' if flops_s > bytes_s else 'bytes'} (operations "
          f"{flops_s * 1e3:.4g} ms, bytes {bytes_s * 1e3:.4g} ms at the "
          f"peaks) against {kernel_s * 1e3:.4g} ms on the device", flush=True)
    return 100.0 * max(flops_s, bytes_s) / kernel_s
