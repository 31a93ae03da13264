"""Operations a decoder needs, computed from the configuration's shapes.

The yardstick for ``mfu``: the operations the forward and backward
passes REQUIRE per token.  Recomputed operations (remat) do not count.
"""

from __future__ import annotations

from typing import Any, Dict


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = h * cfg["num_attention_heads"] * d          # q_proj, and o_proj
    kv = h * cfg["num_key_value_heads"] * d         # k_proj, v_proj each
    mlp = 3 * h * cfg["intermediate_size"]          # gate, up, down
    return 2 * q + 2 * kv + mlp


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matmul per token: every layer's
    projections and the output head.  The embedding lookup is a gather."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: Dict[str, Any]) -> int:
    h = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * h
    embed = cfg["vocab_size"] * h
    head = 0 if cfg.get("tie_word_embeddings") else cfg["vocab_size"] * h
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg) + norms
            + embed + head)


def attention_flops_per_token(cfg: Dict[str, Any], seq_len: int,
                              passes: int) -> float:
    """Causal attention, per token of a sequence of ``seq_len``: QK^T and
    PV are 2*seq*d each per head over the whole square, half of it under
    the causal mask; ``passes`` is 1 for forward, 3 with the backward."""
    full = 4.0 * seq_len * cfg["num_attention_heads"] * head_dim(cfg)
    return passes * 0.5 * full * cfg["num_hidden_layers"]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """6 x matmul parameters (2 forward, 4 backward) plus causal
    attention forward and backward."""
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq_len, 3)


def mfu_pct(tokens_per_s: float, flops_per_token: float, chips: int,
            peak_flops_per_s: float) -> float:
    return 100.0 * tokens_per_s * flops_per_token / (chips * peak_flops_per_s)
