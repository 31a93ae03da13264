"""The composed hybrid-parallel Llama train step: pp x dp x sharding x sep
x mp in ONE jitted program.

Capability analog of the reference's full Fleet hybrid runtime — one model
trained simultaneously under pipeline parallelism
(fleet/meta_parallel/pipeline_parallel.py:547), data parallelism + sharded
optimizer states, segment/sequence parallelism (segment_parallel.py,
topology.py:503 get_sep_*) and Megatron tensor parallelism (mp_layers.py)
over the 5-axis HybridCommunicateGroup (topology.py:189).

TPU-first composition (no actor runtime, no per-rank branching code):

- ``pp`` and ``sep`` are MANUAL mesh axes inside one
  ``jax.shard_map(..., axis_names={"pp","sep"})`` region: pipeline-stage
  advance is one ``lax.ppermute`` per tick (GPipe dataflow; XLA reverses
  the statically-bounded loop for backward), and sequence parallelism is
  the Ulysses alltoall pair (seq<->heads) or an exact ring schedule around
  flash attention.
- ``dp``/``sharding``/``mp`` stay AUTO (GSPMD): per-layer weights are
  stacked layer-major ([L, ...] leaves, dim 0 sharded over pp) with their
  remaining dims carrying the same FSDP('sharding') x TP('mp') placements
  as the single-program plan (LLAMA_SHARDING_PLAN); XLA inserts the
  Megatron collectives inside each pipeline tick.
- Embedding, final norm, LM head and the streaming fp32 cross-entropy run
  OUTSIDE the manual region in plain GSPMD land; their gradients flow
  through the shard_map boundary (ppermute/alltoall transpose rules), so
  tied/untied embeddings train correctly — no special-cased first/last
  pipeline stage.

The decoder-layer math here is the functional twin of
``models/llama.py`` (LlamaAttention/LlamaMLP/LlamaRMSNorm, which follow
incubate/nn/fused.py) — kept expression-for-expression identical so the
pp=1 GSPMD step and this pipelined step agree to float tolerance
(tests/test_llama_hybrid.py parity).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .llama import (LlamaConfig, LLAMA_SHARDING_PLAN, plan_spec_for,
                    _filter_spec_to_mesh, _gold_logit, _rope_tables)
from ..parallel import compat as _compat
from ..parallel.pipelining import pipeline_apply
from ..parallel.sep import ulysses_attention
from ..parallel.ring_attention import ring_flash_attention

HYBRID_AXES = ("pp", "dp", "sharding", "sep", "mp")

_LAYER_PREFIX = "model.layers."


from jax.lax import axis_size as _axis_size

def hybrid_mesh(devices, pp=1, dp=1, sharding=1, sep=1, mp=1) -> Mesh:
    """Build the 5-axis hybrid mesh (reference: topology.py:189 order
    pp->dp->sharding->sep->mp, outermost..innermost so mp rides the
    fastest-varying / closest ICI neighbours)."""
    n = pp * dp * sharding * sep * mp
    grid = np.asarray(devices[:n], dtype=object).reshape(pp, dp, sharding,
                                                         sep, mp)
    return Mesh(grid, axis_names=HYBRID_AXES)


# --------------------------------------------------------------------------
# state layout: layer-major stacking
# --------------------------------------------------------------------------

def stack_llama_state(state: Dict[str, Any], num_layers: int
                      ) -> Dict[str, Any]:
    """Collapse per-layer params ``model.layers.{i}.X`` into layer-major
    stacks ``model.layers.X`` with leading dim [L].  Sharding dim 0 over
    ``pp`` then gives pipeline stage s the contiguous layer block
    [s*L/P, (s+1)*L/P) — the reference's segment_parallel layer split
    (fleet/meta_parallel/parallel_layers/pp_layers.py segment methods)."""
    out: Dict[str, Any] = {}
    per_layer: Dict[str, list] = {}
    for k, v in state.items():
        if k.startswith(_LAYER_PREFIX):
            rest = k[len(_LAYER_PREFIX):]
            idx, suffix = rest.split(".", 1)
            per_layer.setdefault(suffix, [None] * num_layers)[int(idx)] = v
        else:
            out[k] = v
    for suffix, vals in per_layer.items():
        assert all(v is not None for v in vals), f"missing layers for {suffix}"
        out[_LAYER_PREFIX + suffix] = jnp.stack(
            [jnp.asarray(v) for v in vals], axis=0)
    return out


def unstack_llama_state(hstate: Dict[str, Any], num_layers: int
                        ) -> Dict[str, Any]:
    """Inverse of stack_llama_state (checkpoint interop / parity tests)."""
    out: Dict[str, Any] = {}
    for k, v in hstate.items():
        if k.startswith(_LAYER_PREFIX) and "." in k[len(_LAYER_PREFIX):] \
                and not k[len(_LAYER_PREFIX):].split(".", 1)[0].isdigit():
            suffix = k[len(_LAYER_PREFIX):]
            for i in range(num_layers):
                out[f"{_LAYER_PREFIX}{i}.{suffix}"] = v[i]
        else:
            out[k] = v
    return out


def hybrid_param_spec(name: str, shape: Tuple[int, ...], mesh: Mesh,
                      plan: Optional[Dict[str, P]] = None) -> P:
    """At-rest PartitionSpec of ONE hybrid-state leaf — the placement
    rule of ``shard_hybrid_state``, exposed as a pure shape-level hook
    so the Sharding Doctor's extractor can read this stack's canonical
    layout without materializing state.  Since round 19 the rule
    itself lives in the schedule layer
    (``parallel.schedule.hybrid_leaf_spec`` — the pp tactic's stacking
    rule, shared with ``PartitionSchedule.hybrid_spec``); this hook
    only binds the llama plan."""
    from ..parallel.schedule import hybrid_leaf_spec

    return hybrid_leaf_spec(name, shape, mesh,
                            lambda n: plan_spec_for(n, plan))


def shard_hybrid_state(hstate: Dict[str, Any], mesh: Mesh,
                       plan: Optional[Dict[str, P]] = None) -> Dict[str, Any]:
    """Place the stacked state on the hybrid mesh per
    ``hybrid_param_spec`` (single copy of the placement rule — the
    extractor reads the same hook)."""
    return {
        name: jax.device_put(
            v, NamedSharding(mesh,
                             hybrid_param_spec(name, tuple(v.shape), mesh,
                                               plan)))
        for name, v in hstate.items()}


def init_hybrid_state(model, mesh: Mesh) -> Dict[str, Any]:
    """model (LlamaForCausalLM) -> stacked+sharded hybrid param dict."""
    return shard_hybrid_state(
        stack_llama_state(model.functional_state(),
                          model.cfg.num_hidden_layers),
        mesh)


# --------------------------------------------------------------------------
# functional decoder layer (expression-identical to models/llama.py)
# --------------------------------------------------------------------------

# raw-array twins of the fused ops (same functions models/llama.py runs
# through dispatch) — shared so the math cannot drift from the pp=1 path
from ..incubate.nn.fused import _fused_rms_norm_op, _rope_rotate_half

_rms_norm_raw = _fused_rms_norm_op.raw_fn


def _rms_norm(x, w, eps):
    return _rms_norm_raw(x, w, epsilon=eps)


_rotate_half = _rope_rotate_half


def _decoder_layer(lp: Dict[str, Any], x, cos, sin, cfg: LlamaConfig,
                   sep_axis: Optional[str], sep_attn: str):
    """One decoder layer on raw arrays inside the manual region.

    x: [mb, s_local, h]; cos/sin: [s_local, head_dim] (this sep-rank's
    position slice); lp: this layer's params keyed by intra-layer suffix.
    """
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    b, sl, _ = x.shape
    h = _rms_norm(x, lp["input_layernorm.weight"], cfg.rms_norm_eps)
    q = (h @ lp["self_attn.q_proj.weight"]).reshape(b, sl, nh, hd)
    k = (h @ lp["self_attn.k_proj.weight"]).reshape(b, sl, nkv, hd)
    v = (h @ lp["self_attn.v_proj.weight"]).reshape(b, sl, nkv, hd)
    cos_b = cos[None, :, None, :]
    sin_b = sin[None, :, None, :]
    q = q * cos_b + _rotate_half(q) * sin_b
    k = k * cos_b + _rotate_half(k) * sin_b
    if sep_axis is None:
        from ..ops.pallas.flash_attention import flash_attention_raw

        attn = flash_attention_raw(q, k, v, causal=True)
    elif sep_attn == "ring":
        attn = ring_flash_attention(q, k, v, axis=sep_axis, causal=True)
    else:
        attn = ulysses_attention(q, k, v, axis=sep_axis, causal=True)
    attn = attn.astype(x.dtype).reshape(b, sl, nh * hd)
    # residual-stream saveable tags (parallel/memory.SAVEABLE_NAMES):
    # the named remat policies select/offload these on the hybrid path
    # exactly as on the GSPMD and overlap stacks
    from ..parallel.memory import tag_saveable

    x = x + tag_saveable(attn @ lp["self_attn.o_proj.weight"],
                         "decoder_attn_out")
    h2 = _rms_norm(x, lp["post_attention_layernorm.weight"],
                   cfg.rms_norm_eps)
    gate = h2 @ lp["mlp.gate_proj.weight"]
    up = h2 @ lp["mlp.up_proj.weight"]
    return x + tag_saveable((jax.nn.silu(gate) * up)
                            @ lp["mlp.down_proj.weight"],
                            "decoder_mlp_out")


# --------------------------------------------------------------------------
# the composed train step
# --------------------------------------------------------------------------

def build_hybrid_train_step(cfg: LlamaConfig, optimizer, mesh: Mesh,
                            num_microbatches: int = 1,
                            compute_dtype=jnp.bfloat16,
                            remat=False,
                            sep_attn: str = "ulysses",
                            schedule: str = "gpipe",
                            virtual_chunks: int = 1,
                            data_axes: Tuple[str, ...] = ("dp", "sharding"),
                            cpu_bf16: str = "promote",
                            overlap=None, health=None):
    """Build the fully-composed hybrid train step:

        step(params, opt_state, step_no, lr, input_ids, labels)
            -> (loss, new_params, new_opt_state)

    ``params`` is the stacked+sharded dict from ``init_hybrid_state``.
    input_ids/labels: [B, S] with B divisible by num_microbatches (and by
    the data-axes degrees), S by the sep degree.  The mesh must carry all
    of HYBRID_AXES (degree 1 axes are fine — ppermute/alltoall over a
    size-1 axis are no-ops, so the same program serves every composition).

    ``schedule`` selects the pipeline runtime:

    - ``"gpipe"`` (default): differentiable dataflow — jax.grad reverses
      the statically-bounded tick loop; memory holds all m micro
      activations.
    - ``"1F1B"`` / ``"ZBH1"`` / ``"FThenB"``: the schedule-explicit
      executor (parallel/pipelining.pipeline_train_step) with the static
      tables from parallel/schedules.py — backward interleaves with
      forward per the table (1F1B's min(p, m) activation bound; ZBH1's
      dx/dw split filling bubbles), grads computed in-schedule, and the
      embedding/LM-head outside the pipeline get their gradients through
      the executor's x-grad / loss-params channels.

    Round-9: both region bodies are FULL-manual (every mesh axis in
    ``axis_names``) — 'sharding' is handled by the overlap engine's
    explicit ZeRO-3 bucket gathers (per-layer with prefetch on the
    gpipe path; once per step at region entry on the schedule-explicit
    path, whose divergent per-rank branches cannot host per-layer
    collectives) and 'mp' by the TP-manual decoder layer
    (parallel/overlap.decoder_layer_tp, collective-matmul dispatcher
    included).  This retires the jax-0.4.x partial-manual shard_map gap:
    no auto axis of degree > 1 remains inside either region, so the
    PartitionId lowering the 0.4.37 SPMD partitioner rejects is never
    emitted.  ``overlap`` (an overlap.OverlapConfig) tunes the engine;
    None uses the defaults.

    Round-10: ``remat`` also accepts a NAMED policy string (``none |
    dots | names | offload | full``) or a ``parallel.memory.
    MemoryConfig`` — resolved through the HBM memory engine's single
    translation point, so the hybrid stack honors the same
    checkpoint_name-tagged saveable set as the GSPMD/overlap paths.
    """
    from ..parallel import overlap as _ov
    from ..parallel.memory import MemoryConfig as _MemCfg

    remat_policy = None
    if isinstance(remat, _MemCfg):
        remat, remat_policy = remat.resolve_remat()
    elif isinstance(remat, str):
        remat, remat_policy = _MemCfg(remat=remat).resolve_remat()
    pp_axis, sep_axis = "pp", "sep"
    for ax in HYBRID_AXES:
        if ax not in mesh.axis_names:
            raise ValueError(f"hybrid mesh must carry axis {ax!r}")
    fp32_wire = False
    if compute_dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        # XLA:CPU's AllReducePromotion pass aborts ("Invalid binary
        # instruction opcode copy") cloning any shardy-emitted bf16
        # all-reduce (the reduction region is rooted at a Sharding
        # custom-call CreateBinary can't clone); TPU handles bf16
        # collectives natively.  Two CPU modes:
        # - "promote" (default): whole program fp32 — safe everywhere.
        # - "fp32-wire": COMPUTE stays genuinely bf16; only the
        #   shard_map boundary values and the manual collectives
        #   (parallel/compat.py) ride fp32 wires.  This is the CI mode
        #   that exercises the same bf16 program the TPU runs; it
        #   cannot host auto-axis (mp/sharding) bf16 reductions, which
        #   the partitioner inserts out of our reach.
        if cpu_bf16 == "promote":
            compute_dtype = jnp.float32
        elif cpu_bf16 == "fp32-wire":
            fp32_wire = True
            if mesh.shape["mp"] > 1 or mesh.shape["sharding"] > 1:
                raise NotImplementedError(
                    "cpu_bf16='fp32-wire' supports manual-axis "
                    "compositions (pp/sep, and dp on the schedule-"
                    "explicit path); mp/sharding insert auto bf16 "
                    "reductions that crash XLA:CPU — use "
                    "cpu_bf16='promote' for those meshes")
            if mesh.shape["dp"] > 1 and schedule.lower() == "gpipe":
                # on the gpipe path dp is an AUTO axis: the outer
                # jax.grad makes the partitioner insert a bf16 grad
                # all-reduce over dp — the same crash.  dp is manual
                # (and safe) only on the schedule-explicit path.
                raise NotImplementedError(
                    "cpu_bf16='fp32-wire' with dp>1 needs the "
                    "schedule-explicit path (schedule='1F1B'/'ZBH1'), "
                    "where dp is a manual axis; gpipe's auto-dp grad "
                    "reduction is bf16 and crashes XLA:CPU")
        else:
            raise ValueError(f"unknown cpu_bf16 mode {cpu_bf16!r}")

    def _wire_in(t):
        """bf16 -> fp32 at the shard_map boundary (cpu fp32-wire)."""
        return (t.astype(jnp.float32)
                if fp32_wire and t.dtype == jnp.bfloat16 else t)

    def _wire_body(t):
        """fp32 -> bf16 on entry into the manual region body."""
        return (t.astype(jnp.bfloat16)
                if fp32_wire and t.dtype == jnp.float32 else t)
    L = cfg.num_hidden_layers
    pp = mesh.shape[pp_axis]
    sep = mesh.shape[sep_axis]
    if L % pp:
        raise ValueError(f"{L} layers not divisible by pp={pp}")
    m = num_microbatches

    batch_axes = tuple(a for a in data_axes
                       if a in mesh.axis_names and mesh.shape[a] > 1)
    sep_entry = sep_axis if sep > 1 else None

    # ---- round-9 full-manual machinery (parallel/overlap.py) ----
    oc = overlap if overlap is not None else _ov.OverlapConfig()
    sh_deg = int(mesh.shape["sharding"])
    mp_deg = int(mesh.shape["mp"])
    sh_ax = "sharding" if sh_deg > 1 else None
    mp_ax = "mp" if mp_deg > 1 else None
    hier = oc.resolve_hier(mesh, sh_ax)
    # quantized-DCN codec: only with a resolved hierarchical axis (the
    # quantize-across-DCN placement rule, overlap.py docstring §5)
    codec = oc.codec if hier is not None else None
    shapes = _ov.llama_layer_shapes(cfg)
    layout = _ov.plan_layer_layout(
        shapes, mesh, lambda sfx: _filter_spec_to_mesh(
            plan_spec_for(sfx), mesh))
    suffix_order = sorted(shapes)
    manual_axes = set(HYBRID_AXES)
    if sep > 1:
        def _sep_gqa(q, k, v):
            """With mp-manual head splitting the LOCAL kv-head count can
            drop below the sep degree; repeating kv heads up to the q
            grouping is exact GQA semantics (each q head keeps its own
            kv group) and restores ulysses' head-divisibility."""
            if k.shape[2] % sep:
                rep = q.shape[2] // k.shape[2]
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            return q, k, v

        if sep_attn == "ring":
            def attn_fn(q, k, v):
                q, k, v = _sep_gqa(q, k, v)
                return ring_flash_attention(q, k, v, axis=sep_axis,
                                            causal=True)
        else:
            def attn_fn(q, k, v):
                q, k, v = _sep_gqa(q, k, v)
                return ulysses_attention(q, k, v, axis=sep_axis,
                                         causal=True)
    else:
        attn_fn = None

    def _split(params):
        stacked = {k[len(_LAYER_PREFIX):]: v for k, v in params.items()
                   if k.startswith(_LAYER_PREFIX)}
        outer = {k: v for k, v in params.items()
                 if not k.startswith(_LAYER_PREFIX)}
        return outer, stacked

    cos_full, sin_full = _rope_tables(cfg.head_dim,
                                      cfg.max_position_embeddings,
                                      cfg.rope_theta)

    from jax import shard_map as _shard_map

    stacked_in_specs = {
        sfx: _ov.leaf_partition_spec(layout[sfx], lead="pp")
        for sfx in suffix_order}

    _gpipe_cache: Dict[Tuple[str, ...], Any] = {}

    def _gpipe_shmap(batch_axes_used: Tuple[str, ...]):
        """Full-manual GPipe region for one batch-axes choice (the
        micro-batch dim must tile EXACTLY over manual axes, so the axes
        actually used depend on the call's shapes — cached per choice).
        """
        if batch_axes_used in _gpipe_cache:
            return _gpipe_cache[batch_axes_used]
        batch_entry = (batch_axes_used if len(batch_axes_used) > 1 else
                       (batch_axes_used[0] if batch_axes_used else None))
        seq_axes = (sep_axis,) if sep > 1 else ()
        # gather-bucket backward: reduce-scatter folds the 'sharding'
        # sum; the remaining batch-partial axes psum the residue
        gather_psum = tuple(a for a in batch_axes_used
                            if a != "sharding") + seq_axes
        # replicated (non-gathered) leaves are batch-partial over EVERY
        # batch/seq axis
        sync_axes = tuple(batch_axes_used) + seq_axes
        grad_mode = "scatter" if "sharding" in batch_axes_used else "slice"
        itemsize = jnp.dtype(jnp.float32 if fp32_wire
                             else compute_dtype).itemsize
        buckets = _ov.plan_buckets(layout, suffix_order, sh_deg, mp_deg,
                                   oc.bucket_bytes, itemsize)
        in_bucket = {s for b in buckets for s in b}
        sync_sfx = [s for s in suffix_order if s not in in_bucket]
        gather_fns = [_ov.make_bucket_gather(sh_ax, hier, gather_psum,
                                             grad_mode, codec=codec)
                      for _ in buckets]
        sync_fn = _ov.make_grad_sync(sync_axes, hier_axis=sh_ax,
                                     hier=hier, codec=codec)
        # x is replicated over pp (only stage 0 consumes it; the other
        # ranks' cotangents are zero) and over mp (column-parallel
        # backward emits PARTIAL x-cotangents per mp rank)
        x_sync = _ov.make_grad_sync(tuple(
            a for a, d in ((pp_axis, pp), ("mp", mp_deg)) if d > 1))

        def pipeline_body(stacked, x, cos, sin):
            """FULL-manual region over all five axes.  stacked leaves:
            [L/pp, *zero3/tp-local]; x: [m, mb_local, s_local, hidden];
            cos/sin: [s_local, head_dim]."""
            stacked = jax.tree_util.tree_map(_wire_body, stacked)
            x, cos, sin = _wire_body(x), _wire_body(cos), _wire_body(sin)
            x = x_sync(x)

            def layer_fn(lp, act):
                return _ov.decoder_layer_tp(lp, act, cos, sin, cfg,
                                            mp_ax, oc, attn_fn=attn_fn)

            def stage_fn(stage_params, act):
                xs_buckets = [_ov._pack_bucket(stage_params, b)
                              for b in buckets]
                if sync_sfx:
                    xs_sync = _ov._pack_bucket(stage_params, sync_sfx)
                else:
                    Lloc = next(iter(stage_params.values())).shape[0]
                    xs_sync = jnp.zeros((Lloc, 0), x.dtype)
                return _ov.gathered_layer_scan(
                    layer_fn, xs_buckets, xs_sync, act, buckets,
                    sync_sfx, layout, sh_deg, mp_deg, gather_fns,
                    sync_fn, oc, remat=remat,
                    remat_policy=remat_policy)

            outs = pipeline_apply(stage_fn, stacked, x, axis=pp_axis,
                                  squeeze_stage_dim=False)
            # only the last stage holds real outputs; broadcast across
            # pp so every rank returns the valid batch shard
            is_last = (lax.axis_index(pp_axis)
                       == _axis_size(pp_axis) - 1).astype(outs.dtype)
            return _wire_in(_compat.psum(outs * is_last, pp_axis))

        sm = _shard_map(
            pipeline_body, mesh=mesh, axis_names=manual_axes,
            in_specs=(stacked_in_specs,
                      P(None, batch_entry, sep_entry, None),
                      P(sep_entry, None), P(sep_entry, None)),
            out_specs=P(None, batch_entry, sep_entry, None),
            check_vma=False)
        _gpipe_cache[batch_axes_used] = (sm, batch_entry)
        return sm, batch_entry

    def _pick_batch_axes(mb: int) -> Tuple[str, ...]:
        """Largest data_axes prefix whose degree product tiles mb
        exactly (manual in_specs demand exact tiling; 'sharding' drops
        first and falls back to a weights-only axis).  Single copy of
        the rule: parallel.specs.pick_batch_axes."""
        from ..parallel.specs import pick_batch_axes

        return pick_batch_axes(mesh, batch_axes, mb)

    # ---- schedule-explicit runtime (1F1B / ZBH1 / FThenB) ----
    sched = None
    if schedule.lower() == "gpipe":
        if int(virtual_chunks) > 1:
            raise ValueError(
                "virtual_chunks > 1 needs a schedule-explicit runtime "
                "(schedule='VPP'); the gpipe dataflow has no interleaved "
                "placement")
    else:
        if cfg.tie_word_embeddings:
            raise NotImplementedError(
                "schedule-explicit hybrid needs an untied lm_head (the "
                "embedding lives outside the pipeline)")
        # dp composes as a MANUAL axis here: batch dims must not be
        # sharded over AUTO axes inside the executor (its per-rank
        # lax.switch branches diverge across pp rows, and GSPMD-inserted
        # batch collectives inside those branches deadlock the
        # collective rendezvous — XLA:CPU reproduces it
        # deterministically).  Instead the batch is split over dp
        # manually, each dp rank runs the schedule on its shard, and the
        # micro-batch grads are psum'ed over dp AT SCHEDULE END —
        # uniform across ranks, outside the divergent branches (the
        # fused_allreduce_gradients analog,
        # fleet/utils/hybrid_parallel_util.py:249).
        from ..parallel.pipelining import pipeline_train_step
        from ..parallel.schedules import build_schedule

        vch = max(int(virtual_chunks), 1)
        if schedule.upper() == "ZBV" and vch == 1:
            vch = 2              # ZBV's two-chunk zigzag is intrinsic
        if L % (pp * vch):
            raise ValueError(
                f"{L} layers not divisible by pp*virtual_chunks = "
                f"{pp}*{vch}")
        sched = build_schedule(schedule, p=pp, m=m, v=vch)
        # chunk placement (single source of truth: the schedule's
        # stage_of — Megatron-interleaved for VPP, zigzag for ZBV),
        # applied here to layer-BLOCKS instead of per-stage param lists
        from ..parallel.pipelining import device_major_order

        _vpp_order, _vpp_inv = device_major_order(sched)

    dpd = mesh.shape["dp"]
    dp_entry = "dp" if dpd > 1 else None
    chunk_specs = {sfx: _ov.chunk_leaf_spec(layout[sfx])
                   for sfx in suffix_order}

    def pipeline_body_sched(chunked, x, y, cos, sin, head_params):
        """chunked leaves arrive [v, L/(pp*v), *zero3/tp-local] per rank
        (v=1 for 1F1B/ZBH1; VPP device-major chunks otherwise); x
        [m, mb_local, s_local, h] (mb split over manual dp); y
        [m, mb_local, s_local]; head_params = final norm + LM head
        (replicated in-region; grads via the loss-params channel).

        FULL-manual: the sharded chunk leaves are bucket-gathered over
        'sharding' ONCE at region entry (the executor's per-rank
        lax.switch branches cannot host per-layer collectives — the
        per-layer prefetch lives on the gpipe path), mp runs TP-manual
        inside the stages, and the executor's grads are sliced back to
        each rank's shard at region exit (batch does not ride 'sharding'
        here, so every rank computes the identical full gradient)."""
        chunked = jax.tree_util.tree_map(_wire_body, chunked)
        head_params = jax.tree_util.tree_map(_wire_body, head_params)
        x, cos, sin = _wire_body(x), _wire_body(cos), _wire_body(sin)
        chunked_full = _ov.gather_tree_over_sharding(
            chunked, layout, lead_ndim=2, sh=sh_deg, mp=mp_deg,
            axis=sh_ax, hier=hier, bucket_bytes=oc.bucket_bytes,
            codec=codec)

        def layer_step(h, lp):
            return _ov.decoder_layer_tp(lp, h, cos, sin, cfg, mp_ax,
                                        oc, attn_fn=attn_fn), None

        wrapped_step = jax.checkpoint(layer_step, policy=remat_policy) \
            if remat else layer_step

        def stage_fn(chunk, act):
            act, _ = lax.scan(wrapped_step, act, chunk)
            return act

        def loss_fn(lp, act, y_mb):
            h = _rms_norm(act, lp["norm"], cfg.rms_norm_eps)
            logits = h @ lp["head"]
            lse = jax.scipy.special.logsumexp(
                logits.astype(jnp.float32), axis=-1)
            gold = _gold_logit(logits, y_mb)
            # local-token mean / (sep*dp) degree: summed over sep+dp
            # below, this is the GLOBAL token mean (equal shard sizes)
            return (lse - gold).mean() / (sep * dpd)

        loss, sgrads, hgrads, dxs = pipeline_train_step(
            stage_fn, loss_fn, sched, chunked_full, x, y, axis=pp_axis,
            loss_params=head_params, want_x_grad=True)
        reduce_axes = tuple(ax for ax, deg in ((sep_axis, sep),
                                               ("dp", dpd)) if deg > 1)
        if reduce_axes:
            # uniform across ranks, AFTER the divergent schedule — the
            # manual-dp grad allreduce (and the sep grad reduction)
            loss = _compat.psum(loss, reduce_axes)
            sgrads = jax.tree_util.tree_map(
                lambda a: _compat.psum(a, reduce_axes), sgrads)
            hgrads = jax.tree_util.tree_map(
                lambda a: _compat.psum(a, reduce_axes), hgrads)
        # executor grads are w.r.t. the GATHERED chunk; keep this rank's
        # shard (identical full grads across 'sharding' — see docstring)
        sgrads = _ov.slice_tree_own_shard(sgrads, layout, lead_ndim=2,
                                          sh=sh_deg, axis=sh_ax)
        if mp_deg > 1:
            # column-parallel backward leaves the stage-0 input grads
            # PARTIAL per mp rank; stage ranks other than stage 0 hold
            # zeros, so the pp psum both completes and broadcasts them
            dxs = _compat.psum(dxs, "mp")
        if pp > 1:
            dxs = _compat.psum(dxs, pp_axis)
        sgrads = jax.tree_util.tree_map(_wire_in, sgrads)
        hgrads = jax.tree_util.tree_map(_wire_in, hgrads)
        return loss, sgrads, hgrads, _wire_in(dxs)

    shmap_sched = _shard_map(
        pipeline_body_sched, mesh=mesh, axis_names=manual_axes,
        in_specs=(chunk_specs, P(None, dp_entry, sep_entry, None),
                  P(None, dp_entry, sep_entry),
                  P(sep_entry, None), P(sep_entry, None), P()),
        out_specs=(P(), chunk_specs, P(),
                   P(None, dp_entry, sep_entry, None)),
        check_vma=False) if sched is not None else None

    def loss_fn(params, input_ids, labels):
        cast = _cast(params)
        outer, stacked = _split(cast)
        B, S = input_ids.shape
        mb = B // m
        shmap, batch_entry = _gpipe_shmap(_pick_batch_axes(mb))
        ids = input_ids.reshape(m, mb, S)
        # mode="clip": token ids are in-range by construction; the default
        # fill mode's bounds-check pred ops are extra reshard candidates
        # for the SPMD partitioner on hybrid meshes
        x = jnp.take(outer["model.embed_tokens.weight"], ids, axis=0,
                     mode="clip")
        from ..parallel.specs import microbatched

        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh,
                             microbatched(batch_entry, sep_entry, None)))
        cos = cos_full[:S].astype(compute_dtype)
        sin = sin_full[:S].astype(compute_dtype)
        h = shmap(jax.tree_util.tree_map(_wire_in, stacked), _wire_in(x),
                  _wire_in(cos), _wire_in(sin))
        h = _wire_body(h)
        h = _rms_norm(h, outer["model.norm.weight"], cfg.rms_norm_eps)
        if cfg.tie_word_embeddings:
            logits = h @ outer["model.embed_tokens.weight"].T
        else:
            logits = h @ outer["lm_head.weight"]
        logits = lax.with_sharding_constraint(
            logits, NamedSharding(mesh, microbatched(batch_entry)))
        lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32),
                                          axis=-1)
        ylb = labels.reshape(m, mb, S)
        nll = lse - _gold_logit(logits, ylb)
        if batch_entry is not None:
            # pin the per-token nll to the batch layout BEFORE the mean:
            # without it GSPMD mixes the lse/gold operand shardings and
            # falls back to involuntary full rematerialization on the add
            nll = lax.with_sharding_constraint(
                nll, NamedSharding(mesh, microbatched(batch_entry)))
        return nll.mean()

    grad_fn = jax.value_and_grad(loss_fn)

    def _cast(params):
        return {k: (v.astype(compute_dtype)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v)
                for k, v in params.items()}

    def _apply_optimizer(params, grads, opt_state, lr, step_no):
        """Single copy of the decay-mask rule + apply: the gpipe and
        schedule-explicit paths must not drift."""
        names = list(params.keys())  # trace-time only: retrace-safe
        no_decay = {n for n in names
                    if "layernorm" in n or n.endswith("norm.weight")
                    or n.endswith(".bias")}
        return optimizer.apply(
            params, grads, opt_state, lr, step_no + 1,
            decay_mask={n: n not in no_decay for n in names})

    def _finish(loss, grads, params, opt_state, lr, step_no,
                health_gates):
        """Shared optimizer tail of both schedule paths — and, with
        ``health``, the round-17 fused probe + in-step no-op guard
        (same contract as build_train_step: a fired gate passes params
        and optimizer state through bit-identically and the probe
        rides out as a 4th output)."""
        new_params, new_opt_state = _apply_optimizer(params, grads,
                                                     opt_state, lr,
                                                     step_no)
        if health is None:
            return loss, new_params, new_opt_state
        from ..distributed import health as _health

        return _health.probe_and_guard(loss, grads, params, opt_state,
                                       new_params, new_opt_state,
                                       health_gates, health)

    def step_fn(params, opt_state, step_no, lr, input_ids, labels,
                health_gates=None):
        outer_batch = (batch_axes if len(batch_axes) > 1
                       else (batch_axes[0] if batch_axes else None))
        if outer_batch is not None or sep_entry is not None:
            from ..parallel.specs import token_batch_spec

            bs = NamedSharding(mesh, token_batch_spec(outer_batch,
                                                      sep_entry))
            input_ids = lax.with_sharding_constraint(input_ids, bs)
            labels = lax.with_sharding_constraint(labels, bs)
        loss, grads = grad_fn(params, input_ids, labels)
        return _finish(loss, grads, params, opt_state, lr, step_no,
                       health_gates)

    def sched_step_fn(params, opt_state, step_no, lr, input_ids, labels,
                      health_gates=None):
        """Schedule-explicit train step: grads come from the executor's
        in-schedule vjps (stages), loss-params channel (norm + head) and
        x-grad channel (embedding), not from an outer jax.grad."""
        if sep_entry is not None or dp_entry is not None:
            # batch splits over MANUAL dp (and sep); 'sharding' stays a
            # weights-only (FSDP-at-rest) axis on this path
            from ..parallel.specs import token_batch_spec

            bs = NamedSharding(mesh, token_batch_spec(dp_entry,
                                                      sep_entry))
            input_ids = lax.with_sharding_constraint(input_ids, bs)
            labels = lax.with_sharding_constraint(labels, bs)
        cast = _cast(params)
        outer, stacked = _split(cast)
        B, S = input_ids.shape
        mb = B // m
        if mb % dpd:
            raise ValueError(
                f"micro-batch size {mb} not divisible by dp degree {dpd}")
        ids = input_ids.reshape(m, mb, S)
        y = labels.reshape(m, mb, S)

        def embed_fn(w):
            return jnp.take(w, ids, axis=0, mode="clip")

        x, embed_vjp = jax.vjp(embed_fn, outer["model.embed_tokens.weight"])
        from ..parallel.specs import microbatched

        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh,
                             microbatched(dp_entry, sep_entry, None)))
        cos = cos_full[:S].astype(compute_dtype)
        sin = sin_full[:S].astype(compute_dtype)
        nstage = pp * sched.v

        def _to_chunks(a):
            # [L, ...] -> [nstage, L/nstage, ...] in VPP device-major
            # order, so sharding dim 0 over pp yields [v, blk, ...] per
            # rank with chunk j = global stage j*pp + rank
            blk = a.reshape((nstage, a.shape[0] // nstage) + a.shape[1:])
            return blk[jnp.asarray(_vpp_order)] if sched.v > 1 else blk

        chunked = jax.tree_util.tree_map(_to_chunks, stacked)
        head_params = {"norm": cast["model.norm.weight"],
                       "head": cast["lm_head.weight"]}
        loss, sgrads, hgrads, dxs = shmap_sched(
            jax.tree_util.tree_map(_wire_in, chunked), _wire_in(x), y,
            _wire_in(cos), _wire_in(sin),
            jax.tree_util.tree_map(_wire_in, head_params))
        (d_embed,) = embed_vjp(dxs.astype(x.dtype))
        grads = {}
        for suffix, g in sgrads.items():
            # [nstage(dev-major), blk, ...] -> stage order -> [L, ...]
            if sched.v > 1:
                g = g[jnp.asarray(_vpp_inv)]
            grads[_LAYER_PREFIX + suffix] = g.reshape((L,) + g.shape[2:])
        grads["model.norm.weight"] = hgrads["norm"]
        grads["lm_head.weight"] = hgrads["head"]
        grads["model.embed_tokens.weight"] = d_embed.astype(jnp.float32)
        return _finish(loss, grads, params, opt_state, lr, step_no,
                       health_gates)

    jstep = jax.jit(step_fn if sched is None else sched_step_fn,
                    donate_argnums=(0, 1))

    def step(params, opt_state, step_no, lr, input_ids, labels,
             health_gates=None):
        from ..parallel.specs import ambient_mesh

        kw = {}
        if health is not None:
            from ..distributed import health as _health

            kw["health_gates"] = _health.normalize_gates(health_gates)
        with ambient_mesh(mesh, params):
            return jstep(params, opt_state, step_no, lr, input_ids,
                         labels, **kw)

    return step
