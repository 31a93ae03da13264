"""The dense latent-attention kernel's share of its roofline, in %: the
least time the chip could take for the MLA layers' attention of the
window's steps over the device time of the kernel ``kernel``.
(``sparse_mla_roofline_pct.py`` counts SELECTED positions by an indexer's
counts, which a dense walk does not have.)

Least work only, absorbed form, from ``serving.step_counts``
(``attn_row_ctx``: the sum of the rows' visibilities; ``kv_ctx_tokens``:
every scheduled slot's context ONCE; ``rows``):

    operations / peak bf16 rate,  operations = 2 x heads x ((kv_lora_rank
                                    + qk_rope_head_dim) + kv_lora_rank)
                                    x sum(attn_row_ctx) x layers
    bytes / peak HBM rate,        bytes = (sum(kv_ctx_tokens) x
                                    (kv_lora_rank + qk_rope_head_dim) +
                                    sum(rows) x heads x ((kv_lora_rank +
                                    qk_rope_head_dim) + kv_lora_rank))
                                    x itemsize x layers

(a row's scores against ``[c~ ; k_p]`` and its weighted sum of ``c~``;
each slot's latent rows once, at their published 576 numbers, each row's
absorbed queries in and latent-space output out).  ``layers``: the MLA
layers among those that run.  Which of the two bounds it is printed.
"""

from __future__ import annotations

from benchmarks.harness import peaks
from benchmarks.readers.block_sparse_roofline_pct import share_of_roofline


def least_seconds(config, counts, device_kind: str):
    """``(seconds, "flops" | "bytes", flops_s, bytes_s)`` for the steps
    whose ``serving.step_counts`` are ``counts``."""
    import jax.numpy as jnp

    if not all("attn_row_ctx" in c and "kv_ctx_tokens" in c for c in counts):
        return None
    h = config["num_attention_heads"]
    dk = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    dv = config["kv_lora_rank"]
    layers = sum(n <= config["num_hidden_layers"]
                 for n in config["linear_attn_config"]["full_attn_layers"])
    itemsize = jnp.dtype(config["engine"]["cache_dtype"]).itemsize
    flops = 2 * h * (dk + dv) * layers * sum(c["attn_row_ctx"] for c in counts)
    nbytes = (sum(c["kv_ctx_tokens"] for c in counts) * dk
              + sum(c["rows"] for c in counts) * h * (dk + dv)) \
        * itemsize * layers
    peak = peaks.peaks_for(device_kind)
    flops_s = flops / peak["bf16_flops_per_s"]
    bytes_s = nbytes / peak["hbm_bytes_per_s"]
    return (max(flops_s, bytes_s), "flops" if flops_s > bytes_s else "bytes",
            flops_s, bytes_s)


def read(obs, kernel: str):
    return share_of_roofline(obs, kernel, least_seconds, " (dense latent)")
