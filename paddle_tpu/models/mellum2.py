"""Mellum2 (JetBrains, ``model_type`` ``mellum``) on the serving path: a
Llama-shaped decoder whose layers come in two KINDS and whose every FFN
is a bank of softmax-routed experts.

Source: https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct
(``config.json``).  Layer ``i`` is of kind ``layer_types[i]``:

- ``sliding_attention``: GQA over the last ``sliding_window`` positions
  (a row at position p attends ``p - W + 1 .. p``: transformers' mask
  ``kv > q - sliding_window``), plain RoPE at ``rope_theta``;
- ``full_attention``: GQA over the whole context, YaRN tables
  (``llama.yarn_rope_tables``: the correction range of ``beta_fast`` /
  ``beta_slow`` over ``original_max_position_embeddings``, cos and sin
  multiplied by ``attention_factor``).

Both rotate the two halves of the head dimension; no QK-norm, no bias.
The FFN of every layer: fp32 router logits over ``num_experts``
experts, softmax, the ``num_experts_per_tok`` largest, gates
renormalised over those (``norm_topk_prob``), SwiGLU experts of width
``moe_intermediate_size``, no shared expert: ``generation._moe_ffn`` as
it stands (``moe_scoring = "softmax"``).

There is no step of this model's own.  ``Mellum2Config`` is a
``LlamaConfig`` with the published keys that class lacks, and the
family's step (``llama_paged.unified_step_jit``) picks a layer's rope
table, page table and window by ``layer_types[i]``; the layout it
inherits (``llama_paged.kv_layout``) gives such a config two KINDS of
page, so a window layer holds ``sliding_window`` positions of a context
and not all of it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

from .llama import LlamaConfig, _rope_tables, yarn_rope_tables

__all__ = ["Mellum2Config"]

FULL, SLIDING = "full_attention", "sliding_attention"

_ROPE = ((FULL, (("attention_factor", 1.2772588722239782), ("beta_fast", 32),
                 ("beta_slow", 1), ("factor", 16),
                 ("original_max_position_embeddings", 8192),
                 ("rope_theta", 500000), ("rope_type", "yarn"))),
         (SLIDING, (("rope_theta", 500000), ("rope_type", "default"))))


def _frozen(tree):
    """dicts and lists as sorted tuples: a config is a hashable value."""
    if isinstance(tree, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in tree.items()))
    if isinstance(tree, (list, tuple)):
        return tuple(_frozen(v) for v in tree)
    return tree


@dataclasses.dataclass
class Mellum2Config(LlamaConfig):
    """The published keys (defaults: the published values).
    ``moe_top_k`` is the published ``num_experts_per_tok`` under the
    name ``generation._moe_ffn`` reads; ``intermediate_size`` is
    published and unused (every layer is sparse)."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    max_position_embeddings: int = 131072
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    num_experts: int = 64
    moe_top_k: int = 8
    head_dim: int = 128
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    #: one kind a layer; periods of three sliding and one full
    layer_types: Tuple[str, ...] = ()
    rope_parameters: Tuple[Tuple[str, Any], ...] = _ROPE
    #: row block of the experts' grouped matmuls: a 512-token chunk gives
    #: an expert some 68 rows, and a block of fewer rows costs the MXU
    #: the same weight loads (PERF.md section 6, PR 30)
    moe_block_rows: int = 64

    moe_scoring = "softmax"

    def __post_init__(self):
        self.rope_parameters = _frozen(self.rope_parameters)
        self.layer_types = tuple(self.layer_types) or tuple(
            FULL if i % 4 == 3 else SLIDING
            for i in range(self.num_hidden_layers))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        odd = set(self.layer_types) - {FULL, SLIDING}
        if odd:
            raise ValueError(f"layer_types {sorted(odd)}: a layer is "
                             f"{FULL!r} or {SLIDING!r}")
        if not self.norm_topk_prob:
            raise ValueError("gates are renormalised over the experts "
                             "chosen: norm_topk_prob is true")

    @classmethod
    def from_published(cls, published: Dict[str, Any], **changed):
        """From a ``config.json``'s keys; those this model has no use for
        (``model_type``, ``mlp_layer_types``, ...) are passed over.  A
        ``layer_types`` longer than ``num_hidden_layers`` (a file cut in
        depth alone) is cut to its first entries."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in published.items() if k in names}
        if "num_experts_per_tok" in published:
            kw["moe_top_k"] = published["num_experts_per_tok"]
        if "torch_dtype" in published:
            kw["dtype"] = published["torch_dtype"]
        if any(t != "sparse" for t in published.get("mlp_layer_types", ())):
            raise ValueError("every layer's FFN is sparse in this model")
        kw.update(changed)
        if "layer_types" in kw and "num_hidden_layers" in kw:
            kw["layer_types"] = tuple(
                kw["layer_types"])[:kw["num_hidden_layers"]]
        return cls(**kw)

    @classmethod
    def debug(cls, **changed):
        """The CPU tests' size: one period of layers, a window of 8."""
        kw = dict(vocab_size=96, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=4, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  max_position_embeddings=256, dtype="float32",
                  num_experts=8, moe_top_k=2, moe_intermediate_size=32,
                  sliding_window=8, moe_block_rows=8,
                  rope_parameters=dict(
                      (k, dict(v, **({"factor": 4,
                                      "original_max_position_embeddings": 32}
                                     if k == FULL else {})))
                      for k, v in _ROPE))
        kw.update(changed)
        return cls(**kw)

    def rope_tables(self):
        """``(cos, sin)``, each a dict BY KIND of ``[positions, head_dim]``
        tables: the engine's step takes a layer's rows from the table of
        its kind."""
        cos, sin = {}, {}
        for kind, rp in self.rope_parameters:
            rp = dict(rp)
            if rp["rope_type"] == "yarn":
                cos[kind], sin[kind] = yarn_rope_tables(
                    self.head_dim, self.max_position_embeddings,
                    rp["rope_theta"], factor=rp["factor"],
                    original_max_position_embeddings=rp[
                        "original_max_position_embeddings"],
                    beta_fast=rp["beta_fast"], beta_slow=rp["beta_slow"],
                    attention_factor=rp["attention_factor"])
            elif rp["rope_type"] == "default":
                cos[kind], sin[kind] = _rope_tables(
                    self.head_dim, self.max_position_embeddings,
                    rp["rope_theta"])
            else:
                raise ValueError(f"rope_type {rp['rope_type']!r} of "
                                 f"{kind}: 'default' or 'yarn'")
        return cos, sin

    def leaf_shapes(self) -> Dict[str, tuple]:
        """Every leaf of the functional state the step reads, by name
        (Linear weights ``[in, out]``, expert banks ``[experts, in,
        out]``)."""
        c = self
        h, H, kvh, d = (c.hidden_size, c.num_attention_heads,
                        c.num_key_value_heads, c.head_dim)
        f, e = c.moe_intermediate_size, c.num_experts
        out = {"model.embed_tokens.weight": (c.vocab_size, h),
               "model.norm.weight": (h,), "lm_head.weight": (h, c.vocab_size)}
        for i in range(c.num_hidden_layers):
            p = f"model.layers.{i}."
            out.update({
                p + "input_layernorm.weight": (h,),
                p + "self_attn.q_proj.weight": (h, H * d),
                p + "self_attn.k_proj.weight": (h, kvh * d),
                p + "self_attn.v_proj.weight": (h, kvh * d),
                p + "self_attn.o_proj.weight": (H * d, h),
                p + "post_attention_layernorm.weight": (h,),
                p + "mlp.router.weight": (h, e),
                p + "mlp.experts.gate_proj.weight": (e, h, f),
                p + "mlp.experts.up_proj.weight": (e, h, f),
                p + "mlp.experts.down_proj.weight": (e, f, h),
            })
        return out
