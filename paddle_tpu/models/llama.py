"""Llama family — the flagship causal-LM (north-star config 4).

Capability analog of the reference's Llama path: PaddleNLP Llama on top of
paddle.incubate fused ops (fused_rms_norm.py, fused_rotary_position_embedding
.py, swiglu.py — python/paddle/incubate/nn/functional/) + the flash-attention
kernel (paddle/phi/kernels/gpu/flash_attn_kernel.cu, SPMD rule
phi/infermeta/spmd_rules/flash_attention.cc) trained under Fleet hybrid
parallelism.

TPU-first design decisions:
- bf16 compute / fp32 master weights (MXU-native; no GradScaler needed),
- GQA attention through incubate.flash_attention (Pallas kernel on TPU,
  XLA-fused softmax path elsewhere),
- rotary embeddings precomputed once as buffers (no per-step gather),
- one GSPMD sharding PLAN (param-name pattern → PartitionSpec) instead of
  per-layer wrapper classes: FSDP ('sharding') × tensor ('mp') × data
  ('dp') × sequence ('sep') axes on a single mesh; XLA inserts all
  collectives,
- the train step is a single jitted, donated, functional program
  (build_train_step) — the analog of the reference's whole
  dygraph-hybrid-runtime hot loop (§3.3) collapsed into one XLA program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import nn
from ..core.tensor import Tensor
from ..nn.layer import Layer, Parameter
from ..incubate.nn.fused import fused_rms_norm, fused_rotary_position_embedding, swiglu
from ..incubate.nn.attention import flash_attention


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # param dtype at rest.  Sub-blocks are cast as they are built, so a
    # bf16 model never holds more than one block's fp32 init values
    # (an 8B-width stack built whole in fp32 does not fit one chip).
    dtype: str = "float32"
    # round-18 sparse-serving surface: a checkpoint whose decoder FFNs
    # are mixtures of experts (stacked ``model.layers.i.mlp.experts.*``
    # weights + a ``mlp.router.weight`` gate per MoE layer).  The layer
    # set is checkpoint-driven (a layer is MoE iff its expert stack is
    # present); these fields size the routing (generation._moe_ffn).
    num_experts: int = 0
    moe_top_k: int = 2

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def cost_sheet(self):
        """Roofline ``ModelCostSheet`` for this config — the analytic
        per-layer FLOP/byte/collective-element counts the round-20
        partitioning search prices candidates with (lazy delegate so the
        models package never imports the parallel stack eagerly)."""
        from ..parallel.roofline import llama_cost_sheet
        return llama_cost_sheet(self)

    def paged_layout(self):
        """What the serving engine learns of this model: K and V pages
        and the family's step (``llama_paged.kv_layout``)."""
        from .llama_paged import kv_layout
        return kv_layout(self)

    def calibration_prefill(self, params, ids, cos_tab, sin_tab, cfg_id,
                            bucket):
        """The dense forward an int8 K/V cache's scales are calibrated
        from (``llama_paged.calibration_prefill_jit``)."""
        from .llama_paged import calibration_prefill_jit
        return calibration_prefill_jit(params, ids, cos_tab, sin_tab,
                                       self_cfg_id=cfg_id, bucket=bucket)

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, hidden_size=4096,
                           intermediate_size=14336, num_hidden_layers=32,
                           num_attention_heads=32, num_key_value_heads=8)

    @staticmethod
    def debug(vocab=256, hidden=64, layers=2, heads=4, kv_heads=2,
              inter=128, max_pos=256) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                           intermediate_size=inter, num_hidden_layers=layers,
                           num_attention_heads=heads, num_key_value_heads=kv_heads,
                           max_position_embeddings=max_pos, rope_theta=10000.0)


class LlamaRMSNorm(Layer):
    def __init__(self, hidden_size: int, eps: float = 1e-5):
        super().__init__()
        self.weight = Parameter(jnp.ones((hidden_size,), dtype=jnp.float32))
        self.eps = eps

    def forward(self, x):
        return fused_rms_norm(x, self.weight, epsilon=self.eps)


def _rope_tables(head_dim: int, max_pos: int, theta: float):
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    t = np.arange(max_pos, dtype=np.float64)
    freqs = np.outer(t, inv_freq)                       # [max_pos, head_dim/2]
    emb = np.concatenate([freqs, freqs], axis=-1)       # [max_pos, head_dim]
    return (jnp.asarray(np.cos(emb), dtype=jnp.float32),
            jnp.asarray(np.sin(emb), dtype=jnp.float32))


def yarn_rope_tables(dim: int, max_pos: int, theta: float, *, factor: float,
                     original_max_position_embeddings: int,
                     beta_fast: float = 32, beta_slow: float = 1,
                     attention_factor: float = 1.0):
    """YaRN's cos/sin ``[max_pos, dim]`` (halves layout), as transformers
    computes them: each of theta's frequencies ``f_j`` becomes ``f_j /
    factor * (1 - m_j) + f_j * m_j`` with ``m_j = 1 - clip((j - low) /
    (high - low), 0, 1)``, ``low`` and ``high`` the floor and ceiling of
    ``dim ln(original / (beta 2 pi)) / (2 ln theta)`` for ``beta_fast``
    and ``beta_slow``; cos and sin are multiplied by
    ``attention_factor``.  The ONE spelling: DeepSeek-V3.2's rotary
    dimensions and Mellum2's full-attention layers both take it."""
    j = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * j / dim)

    def bound(beta):
        return dim * math.log(original_max_position_embeddings
                              / (beta * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(bound(beta_fast)), 0)
    hi = min(math.ceil(bound(beta_slow)), dim - 1)
    keep = 1.0 - np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freq = freq / factor * (1.0 - keep) + freq * keep
    ang = np.outer(np.arange(max_pos, dtype=np.float64), freq)
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang) * attention_factor, jnp.float32),
            jnp.asarray(np.sin(ang) * attention_factor, jnp.float32))


class LlamaAttention(Layer):
    """GQA attention. Layout [b, s, h, d] throughout (flash kernel layout)."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = nn.Linear(h, cfg.num_attention_heads * d, bias_attr=False)
        self.k_proj = nn.Linear(h, cfg.num_key_value_heads * d, bias_attr=False)
        self.v_proj = nn.Linear(h, cfg.num_key_value_heads * d, bias_attr=False)
        self.o_proj = nn.Linear(cfg.num_attention_heads * d, h, bias_attr=False)

    @jax.named_scope("attention")
    def forward(self, x, cos, sin, attn_mask=None,
                startend_row_indices=None):
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, cfg.num_attention_heads, cfg.head_dim])
        k = self.k_proj(x).reshape([b, s, cfg.num_key_value_heads, cfg.head_dim])
        v = self.v_proj(x).reshape([b, s, cfg.num_key_value_heads, cfg.head_dim])
        # sin/cos arrive [s, d] (prefix positions) or [b, s, d] (explicit
        # position_ids, pre-gathered by LlamaModel); broadcast over (b,·,h,·)
        lead = 1 if cos.ndim == 2 else b
        cos_b = cos.reshape([lead, s, 1, cfg.head_dim])
        sin_b = sin.reshape([lead, s, 1, cfg.head_dim])
        q, k = fused_rotary_position_embedding(q, k, sin=sin_b, cos=cos_b)
        # GQA goes to the attention entry unexpanded: the Pallas kernel
        # routes q heads to kv groups via index maps (no HBM repeat); the
        # XLA fallback repeats internally.  ``attn_mask`` arrives as int32
        # SEGMENT ids ([b, s], normalized by LlamaModel): 1/0 for padded
        # batches, arbitrary ids for packed sequences — splash-attention
        # semantics on both backends.
        if startend_row_indices is not None:
            if attn_mask is not None:
                # composing band masks with segment ids is ambiguous —
                # encode BOTH constraints into startend_row_indices (a
                # causal document mask expresses packed segments) and
                # pass only that; the reference flash API likewise
                # rejects conflicting mask arguments
                raise ValueError(
                    "pass either attention_mask (segment ids) or "
                    "startend_row_indices (FlashMask bands), not both")
            # FlashMask band masks (causal document / share-question /
            # sliding window — python/paddle/nn/functional/
            # flash_attention.py:1098 semantics) on the flagship path
            from ..ops.registry import dispatch

            out = dispatch("flashmask_attention", q, k, v,
                           startend_row_indices, causal=True)
        elif attn_mask is not None:
            out = flash_attention(q, k, v, causal=True,
                                  q_segment_ids=attn_mask,
                                  kv_segment_ids=attn_mask)
        else:
            out = flash_attention(q, k, v, causal=True)
        return self.o_proj(out.reshape([b, s, -1]))


class LlamaMLP(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size, bias_attr=False)
        self.up_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size, bias_attr=False)
        self.down_proj = nn.Linear(cfg.intermediate_size, cfg.hidden_size, bias_attr=False)

    @jax.named_scope("mlp")
    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


def _tag_saveable(t: Tensor, name: str) -> Tensor:
    """checkpoint_name the residual-stream block outputs (the HBM memory
    engine's named saveables — parallel/memory.SAVEABLE_NAMES): the
    ``names``/``offload`` remat policies key on exactly these tags.
    Skipped under an active eager tape — re-wrapping the value would
    sever the Tensor's grad history, and policies only ever see tags
    through the jitted functional path anyway."""
    from ..autograd import is_grad_enabled

    if is_grad_enabled():
        return t
    from ..parallel.memory import tag_saveable

    return Tensor(tag_saveable(t._value, name))


def _cast_params(layer: Layer, cfg: LlamaConfig) -> None:
    """Cast a freshly built sub-block's params to ``cfg.dtype``."""
    if cfg.dtype != "float32":
        for p in layer.parameters():
            p.set_value(p._value.astype(cfg.dtype))


class LlamaDecoderLayer(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        _cast_params(self, cfg)

    # the same scope name in every layer (and in a trace: decoder_layer >
    # attention | mlp); scopes are metadata and compile to nothing
    @jax.named_scope("decoder_layer")
    def forward(self, x, cos, sin, attn_mask=None,
                startend_row_indices=None):
        attn = self.self_attn(self.input_layernorm(x), cos, sin,
                              attn_mask=attn_mask,
                              startend_row_indices=startend_row_indices)
        x = x + _tag_saveable(attn, "decoder_attn_out")
        mlp = self.mlp(self.post_attention_layernorm(x))
        return x + _tag_saveable(mlp, "decoder_mlp_out")


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.remat = False  # set by build_train_step(remat=...)
        self.remat_policy = None  # jax.checkpoint policy (None = full remat)
        # optional NamedSharding pinned onto activations at layer
        # boundaries (set by build_train_step when a mesh is given):
        # without it GSPMD propagates the mp-sharded embed weight into a
        # hidden-sharded activation, then has to fully rematerialize to
        # reach the batch-sharded layout the loss wants (the round-1
        # dryrun's "involuntary full rematerialization" warnings)
        self.act_sharding = None
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        _cast_params(self.embed_tokens, cfg)
        self.layers = nn.LayerList([LlamaDecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        _cast_params(self.norm, cfg)
        cos, sin = _rope_tables(cfg.head_dim, cfg.max_position_embeddings,
                                cfg.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                startend_row_indices=None):
        from ..autograd import is_grad_enabled

        if startend_row_indices is not None and not isinstance(
                startend_row_indices, Tensor):
            startend_row_indices = Tensor(
                jnp.asarray(startend_row_indices, jnp.int32))

        s = input_ids.shape[-1]
        x = self.embed_tokens(input_ids)
        if position_ids is not None:
            # gather per-token rotary phases: [b, s, head_dim]
            pid = position_ids._value if isinstance(position_ids, Tensor) \
                else jnp.asarray(position_ids)
            cos = Tensor(jnp.take(self._buffers["rope_cos"]._value, pid, axis=0))
            sin = Tensor(jnp.take(self._buffers["rope_sin"]._value, pid, axis=0))
        else:
            cos = Tensor(self._buffers["rope_cos"]._value[:s])
            sin = Tensor(self._buffers["rope_sin"]._value[:s])
        # remat only on the functional (jit) path — tape-eager keeps
        # activations anyway, and jax.checkpoint needs pure callees
        use_remat = self.remat and not is_grad_enabled()

        def _pin(t):
            if self.act_sharding is None:
                return t
            return Tensor(jax.lax.with_sharding_constraint(
                t._value, self.act_sharding))

        if attention_mask is not None and not isinstance(attention_mask,
                                                         Tensor):
            attention_mask = Tensor(jnp.asarray(attention_mask))
        if attention_mask is not None:
            mv = attention_mask._value
            if not (jnp.issubdtype(mv.dtype, jnp.bool_)
                    or jnp.issubdtype(mv.dtype, jnp.integer)):
                # a blind cast would INVERT the additive convention
                # (0 = keep, -1e9 = masked); demand keep-mask/segment ids
                raise TypeError(
                    "LlamaModel.attention_mask expects a bool keep-mask or "
                    f"int segment ids [b, s], got dtype {mv.dtype}; convert "
                    "an additive float mask with (mask == 0) first")
            if (jnp.issubdtype(mv.dtype, jnp.integer)
                    and not isinstance(mv, jax.core.Tracer)
                    and bool(jnp.any(mv < 0))):
                # negative values are the additive-int convention in
                # disguise — reject rather than treat them as segment ids
                raise TypeError(
                    "integer attention_mask values must be >= 0 (segment "
                    "ids; 0 marks padding) — additive masks are not "
                    "accepted")
            attention_mask = Tensor(mv.astype(jnp.int32))
        x = _pin(x)
        for layer in self.layers:
            if use_remat:
                x = _remat_layer_call(layer, x, cos, sin, self.remat_policy,
                                      attention_mask, startend_row_indices)
            else:
                x = layer(x, cos, sin, attn_mask=attention_mask,
                          startend_row_indices=startend_row_indices)
            x = _pin(x)
        return self.norm(x)


def _remat_layer_call(layer: "LlamaDecoderLayer", x: Tensor, cos: Tensor,
                      sin: Tensor, policy=None, attn_mask=None,
                      startend_row_indices=None) -> Tensor:
    """Run one decoder layer under jax.checkpoint: activations inside the
    layer are recomputed in backward (the analog of the reference's
    recompute pass, strategy.recompute / fleet recompute_configs).

    ``policy`` selects what to SAVE instead of recompute (e.g.
    ``jax.checkpoint_policies.dots_with_no_batch_dims_saveable`` keeps
    matmul outputs and recomputes only the cheap elementwise chain — the
    usual FLOPs/HBM trade on TPU where recomputing a matmul is 4x the cost
    of recomputing the silu/norm around it)."""
    from ..autograd import no_grad

    state = {k: (t._value if isinstance(t, Tensor) else t)
             for k, t in layer.state_dict().items()}

    @functools.partial(jax.checkpoint, policy=policy,
                       static_argnums=(4, 6))
    def body(state, xv, cosv, sinv, has_mask, maskv, has_sri, sriv):
        with no_grad():
            out = layer.functional_call(
                state, Tensor(xv), Tensor(cosv), Tensor(sinv),
                attn_mask=Tensor(maskv) if has_mask else None,
                startend_row_indices=Tensor(sriv) if has_sri else None)
        return out._value

    mv = attn_mask._value if attn_mask is not None else jnp.zeros((), bool)
    sv = (startend_row_indices._value if startend_row_indices is not None
          else jnp.zeros((), bool))
    return Tensor(body(state, x._value, cos._value, sin._value,
                       attn_mask is not None, mv,
                       startend_row_indices is not None, sv))


class LlamaForCausalLM(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size, bias_attr=False)
            _cast_params(self.lm_head, cfg)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                startend_row_indices=None):
        from ..ops.linalg import matmul

        h = self.model(input_ids, position_ids, attention_mask,
                       startend_row_indices=startend_row_indices)
        if self.cfg.tie_word_embeddings:
            # tape-recorded matmul against the embedding Parameter itself so
            # the head contributes gradients to embed_tokens in eager mode
            return matmul(h, self.model.embed_tokens.weight, transpose_y=True)
        return self.lm_head(h)

    def generate(self, input_ids, **kwargs):
        """KV-cached autoregressive decoding (models/generation.py)."""
        from .generation import generate

        return generate(self, input_ids, **kwargs)


# --------------------------------------------------------------------------
# GSPMD sharding plan (the analog of the reference's per-layer TP wrappers +
# sharded-param init in PaddleNLP; see SURVEY.md §2.7)
# --------------------------------------------------------------------------

# param-name suffix → logical placement (fsdp = ZeRO-3 axis, mp = tensor axis)
LLAMA_SHARDING_PLAN = {
    # vocab sharded over BOTH parallel axes, hidden replicated: the lookup
    # output is then batch-sharded x hidden-replicated — exactly the
    # layer-boundary activation layout — so GSPMD never has to convert a
    # hidden-sharded gather result (the round-1 "involuntary full
    # rematerialization" on the embed path); at-rest memory matches the
    # old P("mp", "sharding") 2-D plan (same total ways)
    "embed_tokens.weight":  P(("mp", "sharding"), None),   # [vocab, hidden]
    "q_proj.weight":        P("sharding", "mp"),   # [hidden, heads*d]
    "k_proj.weight":        P("sharding", "mp"),
    "v_proj.weight":        P("sharding", "mp"),
    "o_proj.weight":        P("mp", "sharding"),   # [heads*d, hidden]
    "gate_proj.weight":     P("sharding", "mp"),
    "up_proj.weight":       P("sharding", "mp"),
    "down_proj.weight":     P("mp", "sharding"),   # [inter, hidden]
    "lm_head.weight":       P("sharding", "mp"),   # [hidden, vocab]
    "input_layernorm.weight": P(None),
    "post_attention_layernorm.weight": P(None),
    "norm.weight":          P(None),
}


def _gold_logit(lv, labels):
    """Label-logit pick as an iota-compare masked reduction, NOT
    ``take_along_axis``: the gather's transpose is a [tokens, vocab]
    scatter-add whose SPMD placement falls back to involuntary full
    rematerialization on hybrid meshes (replicating the logits-grad every
    step), while a select+reduce fuses with the adjacent logsumexp pass
    and shards like any elementwise op.  Exact same values — one nonzero
    per row (the reference reads the label column directly in its fused
    softmax-with-CE kernel, paddle/phi/kernels/gpu/
    c_softmax_with_cross_entropy_kernel.cu)."""
    vocab = lv.shape[-1]
    hit = labels[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, lv.shape, lv.ndim - 1)
    return jnp.where(hit, lv.astype(jnp.float32), 0.0).sum(axis=-1)


def plan_spec_for(name: str, plan: Optional[Dict[str, P]] = None) -> P:
    from ..parallel.specs import REPLICATED

    plan = plan if plan is not None else LLAMA_SHARDING_PLAN
    for suffix, spec in plan.items():
        if name.endswith(suffix):
            return spec
    return REPLICATED


def _filter_spec_to_mesh(spec: P, mesh: Mesh) -> P:
    """Drop axes absent from the mesh (e.g. mp when running pure FSDP).
    Canonical home: ``parallel.specs.filter_spec_to_mesh`` (shared with
    the hybrid path and the Sharding Doctor's extractor)."""
    from ..parallel.specs import filter_spec_to_mesh

    return filter_spec_to_mesh(spec, mesh)


def apply_llama_sharding(model: Layer, mesh: Mesh,
                         plan: Optional[Dict[str, P]] = None,
                         schedule=None) -> None:
    """Place every parameter per the unified partitioning schedule
    (round 19): the declared plan under the shared at-rest
    divisibility-or-replicate rule, read through
    ``PartitionSchedule.spec_for`` — the same derivation
    ``build_train_step`` constrains against and the Sharding Doctor's
    extractor pins."""
    if schedule is None:
        from ..parallel.schedule import PartitionSchedule

        schedule = PartitionSchedule.from_model(model, mesh, plan=plan)
    for name, p in model.named_parameters():
        p.set_value(jax.device_put(
            p._value, schedule.named_sharding(name, tuple(p.shape))))


# --------------------------------------------------------------------------
# The compiled train step
# --------------------------------------------------------------------------

def _accum_fold(accum_steps: int, cap: int = 8) -> int:
    """Largest divisor of ``accum_steps`` not exceeding ``cap`` — the
    number of consecutive bf16 micro-grad adds between fp32 folds (caps
    the bf16 summation depth, so the carry error stays ~cap * 2^-9
    relative per element)."""
    for f in range(min(cap, accum_steps), 0, -1):
        if accum_steps % f == 0:
            return f
    return 1


def llama_decay_mask(model: Layer) -> Dict[str, bool]:
    """Per-parameter AdamW decay mask for the Llama family: norm weights
    and biases are exempt.  Shared by build_train_step and external
    callers (bench.py's fused-optimizer flat state must group params by
    the SAME mask the step applies)."""
    return {n: not ("layernorm" in n or n.endswith("norm.weight")
                    or n.endswith(".bias"))
            for n, _ in model.named_parameters()}


@jax.named_scope("loss")
def _ce_loss(lv, labels, attn_mask, batch_sharding, mesh):
    """Streaming CE: lse + label-logit pick, fp32 accumulation over bf16
    logits — never materializes a full fp32 log_softmax copy
    ([tokens, vocab] fp32 is >1GB at bench shapes; the cast and the
    extra read/write were pure HBM burn)."""
    if batch_sharding is not None:
        from ..parallel.specs import lead_batch_spec

        lv = jax.lax.with_sharding_constraint(
            lv, NamedSharding(mesh, lead_batch_spec(batch_sharding.spec)))
    lse = jax.scipy.special.logsumexp(lv.astype(jnp.float32), axis=-1)
    nll = lse - _gold_logit(lv, labels)
    if attn_mask is None:
        return nll.mean()
    w = (attn_mask > 0).astype(jnp.float32)
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)


_LAYER_PREFIX = "model.layers."


def _build_overlap_forward(model: LlamaForCausalLM, mesh: Mesh, overlap,
                           data_axes: Tuple[str, ...], compute_dtype,
                           remat: bool, remat_policy, schedule=None):
    """Build the overlap-engine forward: cast params dict -> logits.

    The decoder stack runs inside parallel/overlap.py's FULL-manual
    shard_map region (layer-ahead ZeRO-3 prefetch, bucketed grad RS,
    collective matmul, hierarchical collectives); embedding, final norm,
    LM head and the loss stay in GSPMD-land.  Per-layer params are
    stacked [L, ...] at trace time — a bf16 relayout that fuses with the
    compute-dtype cast already paid every step."""
    from ..parallel.overlap import build_overlap_stack

    cfg = model.cfg
    L = cfg.num_hidden_layers
    shapes: Dict[str, Tuple[int, ...]] = {}
    for name, p in model.named_parameters():
        if name.startswith(_LAYER_PREFIX + "0."):
            shapes[name[len(_LAYER_PREFIX) + 2:]] = tuple(p.shape)

    if schedule is None:
        from ..parallel.schedule import PartitionSchedule

        schedule = PartitionSchedule.from_model(model, mesh)

    def spec_for(suffix):
        # the schedule's pre-filter plan spec: the overlap engine's
        # per-axis pick rule applies its own divisibility per axis
        return schedule.plan_spec_for(suffix)

    stack_fwd = build_overlap_stack(
        cfg, mesh, shapes, spec_for, overlap, batch_axes=data_axes,
        remat=remat, remat_policy=remat_policy,
        compute_dtype=compute_dtype)
    cos_full, sin_full = _rope_tables(cfg.head_dim,
                                      cfg.max_position_embeddings,
                                      cfg.rope_theta)
    axes = tuple(a for a in data_axes
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    batch_entry = axes if len(axes) > 1 else (axes[0] if axes else None)
    from ..incubate.nn.fused import _fused_rms_norm_op

    rms_raw = _fused_rms_norm_op.raw_fn

    def fwd(cast: Dict[str, Any], input_ids, attn_mask=None):
        stacked = {
            sfx: jnp.stack([cast[f"{_LAYER_PREFIX}{i}.{sfx}"]
                            for i in range(L)])
            for sfx in shapes}
        s = input_ids.shape[-1]
        # mode="clip": ids are in-range by construction; the bounds-check
        # pred ops are extra reshard candidates for GSPMD (same rationale
        # as llama_hybrid)
        x = jnp.take(cast["model.embed_tokens.weight"], input_ids, axis=0,
                     mode="clip")
        from ..parallel.specs import activation_spec

        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, activation_spec(batch_entry)))
        cos = cos_full[:s].astype(compute_dtype)
        sin = sin_full[:s].astype(compute_dtype)
        seg = None
        if attn_mask is not None:
            seg = attn_mask.astype(jnp.int32)
        h = stack_fwd(stacked, x, cos, sin, seg)
        h = rms_raw(h, cast["model.norm.weight"],
                    epsilon=cfg.rms_norm_eps)
        if cfg.tie_word_embeddings:
            logits = h @ cast["model.embed_tokens.weight"].T
        else:
            logits = h @ cast["lm_head.weight"]
        return logits

    fwd.stack_fwd = stack_fwd
    return fwd


def build_train_step(model: LlamaForCausalLM, optimizer, mesh: Optional[Mesh] = None,
                     data_axes: Tuple[str, ...] = ("dp", "sharding"),
                     remat: bool = False, remat_policy=None,
                     compute_dtype=jnp.bfloat16, accum_steps: int = 1,
                     accum_dtype=None, overlap=None, memory=None,
                     health=None, schedule=None):
    """Build a single donated, jitted train step:

        step_fn(params, opt_state, step_no, lr, input_ids, labels)
            -> (loss, new_params, new_opt_state)

    - params/opt_state keep their NamedShardings (FSDP/TP at rest),
    - with ``mesh``, the batch and logits are constrained to the data axes
      (pins GSPMD's layout choice for the loss reduction),
    - ``remat=True`` checkpoints each decoder layer (jax.checkpoint) —
      activations recomputed in backward; the analog of the reference's
      recompute pass (strategy.recompute).  ``remat_policy`` (a
      jax.checkpoint_policies entry) selects SELECTIVE remat: e.g.
      ``dots_with_no_batch_dims_saveable`` keeps matmul outputs and only
      recomputes the elementwise chain,
    - forward/backward math in ``compute_dtype`` (bf16 on the MXU),
      optimizer math fp32 (master weights in Adam state,
      optimizer.py multi_precision),
    - ``accum_dtype`` picks the gradient-merge accumulator dtype for the
      unmasked accum path.  None (default) resolves to bf16 when
      compute_dtype is bf16 (the backward already emits bf16 grads; the
      round-5 trace put the fp32 accumulator's read-modify-write at
      ~173 ms/step of HBM traffic) and fp32 otherwise (exact parity for
      fp32 test configs).  bf16 accumulation folds into an fp32 carry
      every _accum_fold(accum_steps) micro-steps, bounding the bf16
      summation depth; loss/grad parity vs the fp32 scheme is gated by
      tests/test_grad_accum_bf16_carry.py at accum=32,
    - ``opt_state`` built by ``optimizer.init_flat_state`` routes the
      update through the fused multi-tensor ``apply_flat`` (one pass
      over flattened param groups); per-param pytree state keeps the
      legacy per-tensor ``apply``,
    - ``overlap`` (an ``parallel.overlap.OverlapConfig``; needs ``mesh``)
      routes the decoder stack through the communication-overlap engine:
      a FULL-manual shard_map region with layer-ahead ZeRO-3 gather
      prefetch, bucketed grad reduce-scatter, ppermute-ring collective
      matmul for the mp projections, and hierarchical ICI/DCN
      collectives on multislice meshes (parallel/overlap.py).  Embedding,
      final norm, LM head and the loss stay in plain GSPMD-land;
      ``overlap=None`` keeps the flat GSPMD program (the fallback every
      overlap lever compares against),
    - ``memory`` (a ``parallel.memory.MemoryConfig``) drives the HBM
      memory engine: its NAMED remat policy (``none | dots | names |
      offload | full`` over the checkpoint_name-tagged decoder
      saveables) replaces the binary ``remat``/``remat_policy`` pair on
      BOTH the GSPMD and overlap paths, and
      ``optimizer_residency='host'`` routes the update through the
      bucket-streamed ``apply_flat_offloaded`` when ``opt_state`` was
      built by ``parallel.memory.init_offloaded_state`` (detection is
      structural, like the flat state),
    - ``health`` (a ``distributed.health.HealthConfig``) fuses the
      round-17 health probe INTO this step: the step additionally takes
      a ``health_gates`` fp32[3] cutoff vector (loss / grad-norm /
      update-ratio; None = all-open) and returns a 4th output — the
      probe dict (loss, global grad-norm, per-bucket nonfinite counts,
      update/param ratio, ok flag) — while GUARDING the update in-step:
      a probe that trips any gate makes params and optimizer state pass
      through untouched (bit-exact skip-and-quarantine; the host
      monitor in distributed/health.py decides the ladder response).
      The probe is reductions only — HEALTH001/002 prove it adds no
      full-tree materialization and no collectives,
    - ``schedule`` (a ``parallel.schedule.PartitionSchedule``) is the
      round-19 unified partitioning schedule this step derives from.
      With a mesh and no explicit schedule, one is built from the
      model's declared plan (``PartitionSchedule.from_model``) — so
      every mesh-sharded step IS schedule-derived.  The schedule
      supplies the at-rest specs, the batch pins and the SHARD-MAJOR
      flat-update wire format (``FlatUpdateLayout``): the fused flat
      optimizer's at-rest -> flat boundary becomes a local relayout
      instead of a per-leaf GSPMD reshard — the cut behind the
      round-19 SHARD001 reshard bill (the flat-update pin itself, the
      2004.13336 tactic SHARD005 demands, is unchanged).
    """
    from ..autograd import no_grad
    from ..parallel import memory as _memory

    if schedule is None and mesh is not None:
        from ..parallel.schedule import PartitionSchedule

        schedule = PartitionSchedule.from_model(model, mesh)
    if memory is not None:
        # the named policy owns the remat decision end to end — a
        # caller mixing memory= with the legacy binary flag would get
        # whichever traced last, so resolve once, here
        remat, remat_policy = memory.resolve_remat()
    decay_mask = llama_decay_mask(model)
    if accum_dtype is None:
        accum_dtype = (jnp.bfloat16 if compute_dtype == jnp.bfloat16
                       else jnp.float32)
    batch_sharding = make_batch_shardings(mesh, data_axes) if mesh is not None \
        else None
    ov_forward = None
    if overlap is not None:
        if mesh is None:
            raise ValueError("overlap=OverlapConfig(...) needs a mesh")
        ov_forward = _build_overlap_forward(model, mesh, overlap,
                                            data_axes, compute_dtype,
                                            remat, remat_policy,
                                            schedule=schedule)

    def _kernel_partition():
        if mesh is None:
            return contextlib.nullcontext()
        from ..ops.pallas.flash_attention import kernel_mesh

        return kernel_mesh(mesh, data_axes, "mp")

    def loss_fn(params: Dict[str, Any], input_ids, labels, attn_mask=None):
        cast = {k: (v.astype(compute_dtype)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v)
                for k, v in params.items()}
        if ov_forward is not None:
            lv = ov_forward(cast, input_ids, attn_mask)
            return _ce_loss(lv, labels, attn_mask, batch_sharding, mesh)
        # set the remat flag only for the duration of THIS trace: jit
        # traces lazily, so a build-time flag would leak across steps
        # built with different remat settings (and into eager inference)
        saved_remat = model.model.remat
        saved_policy = model.model.remat_policy
        saved_act = model.model.act_sharding
        model.model.remat = remat
        model.model.remat_policy = remat_policy
        if batch_sharding is not None:
            # activations ride the batch axes with hidden replicated
            # (Megatron convention); pinning every layer boundary keeps
            # GSPMD from flip-flopping between weight-induced layouts
            from ..parallel.specs import lead_batch_spec

            model.model.act_sharding = NamedSharding(
                mesh, lead_batch_spec(batch_sharding.spec, 3))
        try:
            # tape off: jax.grad provides the gradients; under a mesh
            # the flash kernel runs per (batch, head) shard
            with no_grad(), _kernel_partition():
                logits = model.functional_call(
                    cast, Tensor(input_ids),
                    attention_mask=None if attn_mask is None
                    else Tensor(attn_mask))
        finally:
            model.model.remat = saved_remat
            model.model.remat_policy = saved_policy
            model.model.act_sharding = saved_act
        return _ce_loss(logits._value, labels, attn_mask, batch_sharding,
                        mesh)

    grad_fn = jax.value_and_grad(loss_fn)

    # flat-buffer layout pin for the fused optimizer paths on a mesh:
    # shards the bandwidth-bound update chain across every device (the
    # 2004.13336 cross-replica weight-update sharding) AND guards the
    # concat→update→slice chain against the GSPMD mis-lowering the
    # round-10 parity tests caught (see Adam.apply_flat).  The schedule
    # additionally derives the SHARD-MAJOR wire format (FlatUpdateLayout)
    # consumed when the opt state was built under it; legacy row-major
    # states keep the plain pin.
    flat_sharding = None
    flat_layout = None
    if mesh is not None:
        flat_layout = schedule.flat_update_layout()
        flat_sharding = flat_layout.flat_sharding()
        if not flat_layout.axes:
            flat_layout = None      # single-device mesh: nothing to cut

    # NOTE (round-19, measured): an explicit at-rest pin on the merged
    # grad tree before the optimizer boundary was tried and REJECTED —
    # on the flagship accum-4 entry it saves 3 collective-permutes but
    # forces 17 extra all-reduces (the deferred dp grad reduction
    # materializes per leaf instead of folding into the flat chain).
    # The shard-major FlatUpdateLayout alone is the right cut.

    def _health_tail(loss, grads, params, opt_state, new_params,
                     new_opt_state, health_gates):
        """The fused probe + in-step no-op guard (round-17) —
        distributed/health.py owns the contract and the implementation."""
        from ..distributed import health as _health

        return _health.probe_and_guard(loss, grads, params, opt_state,
                                       new_params, new_opt_state,
                                       health_gates, health)

    @jax.named_scope("optimizer")
    def apply_update(params, grads, opt_state, lr, step_no):
        # host-offloaded bucketed state (parallel/memory.py) routes the
        # streamed fused AdamW; flat (fused multi-tensor) state the
        # single-pass device-resident one — detection is structural in
        # both cases so legacy per-param state keeps working
        if _memory.state_is_offloaded(opt_state):
            return _memory.apply_flat_offloaded(
                optimizer, params, grads, opt_state, lr, step_no + 1,
                decay_mask=decay_mask, flat_sharding=flat_sharding,
                flat_layout=flat_layout)
        if hasattr(optimizer, "apply_flat") \
                and getattr(optimizer, "state_is_flat", lambda s: False)(
                    opt_state):
            return optimizer.apply_flat(
                params, grads, opt_state, lr, step_no + 1,
                decay_mask=decay_mask, flat_sharding=flat_sharding,
                flat_layout=flat_layout)
        return optimizer.apply(
            params, grads, opt_state, lr, step_no + 1,
            decay_mask=decay_mask)

    def step_fn(params, opt_state, step_no, lr, input_ids, labels,
                attention_mask=None, health_gates=None):
        if batch_sharding is not None:
            input_ids = jax.lax.with_sharding_constraint(input_ids, batch_sharding)
            labels = jax.lax.with_sharding_constraint(labels, batch_sharding)
            if attention_mask is not None:
                attention_mask = jax.lax.with_sharding_constraint(
                    attention_mask, batch_sharding)
        loss, grads = grad_fn(params, input_ids, labels, attention_mask)
        new_params, new_opt_state = apply_update(params, grads, opt_state,
                                                 lr, step_no)
        if health is not None:
            return _health_tail(loss, grads, params, opt_state,
                                new_params, new_opt_state, health_gates)
        return loss, new_params, new_opt_state

    def accum_step_fn(params, opt_state, step_no, lr, input_ids, labels,
                      attention_mask=None, health_gates=None):
        """Gradient accumulation (reference: strategy gradient-merge /
        GradientMergeOptimizer): ids/labels carry a leading [accum_steps]
        micro-batch axis; one fp32 grad buffer is accumulated by a
        lax.scan of fwd+bwd micro-steps, then AdamW runs ONCE — the
        HBM-bound optimizer read-modify-write (4 fp32 tensors the size of
        the model) is amortized over accum_steps of compute."""
        if batch_sharding is not None:
            from ..parallel.specs import microbatched

            micro = NamedSharding(mesh,
                                  microbatched(*tuple(batch_sharding.spec)))
            input_ids = jax.lax.with_sharding_constraint(input_ids, micro)
            labels = jax.lax.with_sharding_constraint(labels, micro)
            if attention_mask is not None:
                attention_mask = jax.lax.with_sharding_constraint(
                    attention_mask, micro)

        # two scan bodies, NOT a fabricated all-ones mask: the mask-free
        # path must keep the unmasked attention kernel and plain-mean CE
        # (the headline bench runs here — a dummy mask would drag the
        # segment-masked kernel variant into every layer)
        def micro_step(acc, xs):
            mids, mlabels = xs
            loss, g = grad_fn(params, mids, mlabels, None)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), acc, g)
            return acc, loss

        def micro_step_masked(carry, xs):
            # token-weighted accumulation: micro-batches with unequal
            # valid-token counts must contribute in proportion to their
            # tokens, or the merged gradient deviates from the true
            # global token-mean (per-micro grad_fn returns the gradient
            # of a per-micro token MEAN, so scale by that micro's count)
            #
            # DESIGN NOTE — accepted fp32 region (Graph Doctor DT003,
            # tracked exemption EX-DT003-masked-grad-accum in
            # paddle_tpu/analysis/exemptions.py): this accumulator stays
            # fp32 on purpose.  The bf16-carry scheme needs a fold point
            # where a bounded number of micro-grads collapse into the
            # fp32 carry; here every micro-grad is pre-scaled by its
            # token count w and the normalization (1/wsum) is only known
            # at the END of the window, so partial sums span the whole
            # window and a bounded-depth bf16 carry has no clean fold.
            # Folding unnormalized w-scaled bf16 sums would compound
            # quantization error by the full accum depth — worse than
            # the fp32 traffic it saves.  The headline bench runs the
            # unmasked path; the dtype audit keeps this decision visible
            # (and the exemption-liveness self-check fails if this
            # branch ever loses the fp32 carry without updating the
            # exemption table).
            acc, wsum = carry
            mids, mlabels, mmask = xs
            loss, g = grad_fn(params, mids, mlabels, mmask)
            # true token count, no clamp: an all-padding micro contributes
            # zero weight (its loss/grads are already zero via loss_fn's
            # own divide guard); clamping HERE would add a phantom token
            # and shrink every real micro's contribution by n/(n+1)
            w = (mmask > 0).sum().astype(jnp.float32)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + w * b.astype(jnp.float32), acc, g)
            return (acc, wsum + w), loss * w

        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # fold == 1 (accum_steps prime > cap) would be strictly worse
        # than the fp32 accumulator — full fp32 carry traffic PLUS bf16
        # quantization of every micro-grad — so it falls through
        if attention_mask is None and accum_dtype != jnp.float32 \
                and accum_steps > 1 and _accum_fold(accum_steps) > 1:
            # bf16 micro-grad carry (round-7): the accumulator the scan
            # reads-modifies-writes every micro-step is bf16 (half the
            # HBM bytes of the fp32 scheme); an fp32 carry absorbs it
            # every ``fold`` micro-steps so at most ``fold`` bf16 adds
            # compound before a fold (fold <= 8 -> ~fold * 2^-9 relative
            # carry error, gated by tests/test_grad_accum_bf16_carry.py).
            # Traffic per micro-step drops from 2x fp32-bytes to
            # 2x bf16-bytes + (2/fold)x fp32-bytes ≈ 5/8 at fold=8.
            fold = _accum_fold(accum_steps)
            ids_c = input_ids.reshape(
                (accum_steps // fold, fold) + input_ids.shape[1:])
            lab_c = labels.reshape(
                (accum_steps // fold, fold) + labels.shape[1:])

            def micro_lo(acc16, xs):
                mids, mlabels = xs
                loss, g = grad_fn(params, mids, mlabels, None)
                acc16 = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(accum_dtype), acc16, g)
                return acc16, loss

            def fold_step(acc32, xs):
                zero16 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, accum_dtype), params)
                acc16, losses = jax.lax.scan(micro_lo, zero16, xs)
                acc32 = jax.tree_util.tree_map(
                    lambda c, a: c + a.astype(jnp.float32), acc32, acc16)
                return acc32, losses

            acc, losses = jax.lax.scan(fold_step, zero, (ids_c, lab_c))
            grads = jax.tree_util.tree_map(lambda a: a / accum_steps, acc)
            mean_loss = losses.mean()
        elif attention_mask is None:
            acc, losses = jax.lax.scan(micro_step, zero,
                                       (input_ids, labels))
            grads = jax.tree_util.tree_map(lambda a: a / accum_steps, acc)
            mean_loss = losses.mean()
        else:
            # masked accumulation stays fp32: token-weighted partial sums
            # span the full accum window (wsum-scaled), so a bounded-depth
            # bf16 carry has no clean fold point; the headline bench runs
            # the unmasked path
            (acc, wsum), wlosses = jax.lax.scan(
                micro_step_masked, (zero, jnp.zeros((), jnp.float32)),
                (input_ids, labels, attention_mask))
            wsum = jnp.maximum(wsum, 1.0)  # guard only the TOTAL
            grads = jax.tree_util.tree_map(lambda a: a / wsum, acc)
            mean_loss = wlosses.sum() / wsum
        new_params, new_opt_state = apply_update(params, grads, opt_state,
                                                 lr, step_no)
        if health is not None:
            return _health_tail(mean_loss, grads, params, opt_state,
                                new_params, new_opt_state, health_gates)
        return mean_loss, new_params, new_opt_state

    fn = step_fn if accum_steps <= 1 else accum_step_fn
    jit_step = jax.jit(fn, donate_argnums=(0, 1))

    @functools.wraps(jit_step, updated=())  # no __dict__ merge: the
    # wrapper must NOT inherit the pjit's aot methods — the doctor
    # reaches them through __wrapped__
    def step(params, opt_state, step_no, lr, input_ids, labels,
             attention_mask=None, health_gates=None):
        # scalar-signature pinning (Graph Doctor retrace sentinel, RT001):
        # callers alternate python ints/floats (weak-typed avals) with
        # arrays (strong) for step_no/lr, and every flip retraces and
        # recompiles the WHOLE step; normalizing at the entry pins one
        # signature.  Donation is untouched — params/opt_state flow into
        # the jit boundary unchanged (the doctor's donation pass audits
        # the inner entry via __wrapped__).
        step_no = jnp.asarray(step_no, jnp.int32)
        lr = jnp.asarray(lr, jnp.float32)
        kw = {}
        if health is not None:
            from ..distributed import health as _health

            kw["health_gates"] = _health.normalize_gates(health_gates)
        if attention_mask is None:
            return jit_step(params, opt_state, step_no, lr, input_ids,
                            labels, **kw)
        return jit_step(params, opt_state, step_no, lr, input_ids, labels,
                        attention_mask, **kw)

    return step


def make_batch_shardings(mesh: Mesh, data_axes: Tuple[str, ...] = ("dp", "sharding")):
    from ..parallel.specs import batch_partition_spec

    return NamedSharding(mesh, batch_partition_spec(mesh, data_axes))
