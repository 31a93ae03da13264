"""Global flag registry.

TPU-native analog of the reference's exported-flag registry
(paddle/common/flags.h:93 ``PD_DEFINE_*`` + ``GetExportedFlagInfoMap``
flags.h:337; 183 definitions in paddle/common/flags.cc). Flags are
settable from the environment (``FLAGS_*``), from Python via
``set_flags``/``get_flags``, and are queried by subsystems at call time.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Optional, Union

_LOCK = threading.RLock()


@dataclass
class FlagInfo:
    name: str
    default: Any
    doc: str
    type: type
    value: Any
    on_set: Optional[Callable[[Any], None]] = None


_REGISTRY: Dict[str, FlagInfo] = {}


def _coerce(raw: str, ty: type) -> Any:
    if ty is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return ty(raw)


def define_flag(name: str, default: Any, doc: str = "",
                on_set: Optional[Callable[[Any], None]] = None) -> None:
    """Register a flag. Environment variable ``name`` overrides the default.
    ``on_set`` runs on every set_flags update (and once at definition if the
    environment overrode the default) — used to push a flag into an
    external config (e.g. jax.config)."""
    ty = type(default)
    value = default
    env = os.environ.get(name)
    if env is not None:
        try:
            value = _coerce(env, ty)
        except (TypeError, ValueError):
            value = default
    if on_set is not None and value != default:
        try:
            on_set(value)
        except Exception:
            value = default  # bad env value must not break import
    with _LOCK:
        _REGISTRY[name] = FlagInfo(name=name, default=default, doc=doc,
                                   type=ty, value=value, on_set=on_set)


def get_flags(flags: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    with _LOCK:
        if flags is None:
            return {k: v.value for k, v in _REGISTRY.items()}
        if isinstance(flags, str):
            flags = [flags]
        out = {}
        for name in flags:
            if name not in _REGISTRY:
                raise ValueError(f"unknown flag {name!r}")
            out[name] = _REGISTRY[name].value
        return out


def get_flag(name: str) -> Any:
    with _LOCK:
        return _REGISTRY[name].value


_VERSION = 0


def version() -> int:
    """Monotone counter bumped by every set_flags commit — lets hot paths
    cache a flag snapshot and revalidate with one int compare instead of
    per-call lock trips (ops/registry.py fast dispatch)."""
    return _VERSION


def set_flags(flags: Dict[str, Any]) -> None:
    """Atomic batch update: every hook runs (and may reject) BEFORE any
    value commits, so a raised hook leaves the whole registry unchanged and
    external configs rolled back to the committed values. Runs under the
    re-entrant lock, so hook+commit pairs cannot interleave across threads
    (hooks may re-enter flags from the same thread)."""
    with _LOCK:
        pending = []
        for name, value in flags.items():
            if name not in _REGISTRY:
                raise ValueError(f"unknown flag {name!r}")
            info = _REGISTRY[name]
            coerced = _coerce(value, info.type) if isinstance(value, str) \
                else info.type(value)
            pending.append((info, coerced))
        hooked = []
        try:
            for info, coerced in pending:
                if info.on_set is not None:
                    info.on_set(coerced)
                    hooked.append(info)
        except Exception:
            for info in hooked:  # restore external state to committed values
                try:
                    info.on_set(info.value)
                except Exception:
                    pass
            raise
        for info, coerced in pending:
            info.value = coerced
        global _VERSION
        _VERSION += 1


def flag_info_map() -> Dict[str, FlagInfo]:
    with _LOCK:
        return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Behavior-critical flags mirrored from the reference (paddle/common/flags.cc)
# plus TPU-native additions.
# ---------------------------------------------------------------------------
define_flag("FLAGS_check_nan_inf", False, "Check every op output for NaN/Inf (debug).")
define_flag("FLAGS_check_nan_inf_level", 0, "0: error on nan/inf; >0 only report.")
define_flag("FLAGS_use_autotune", False, "Enable runtime autotuning of kernel variants.")
define_flag("FLAGS_benchmark", False,
            "Synchronize after every op — eager timings then measure device "
            "time, not queue depth (wired: dispatch blocks on outputs).")
define_flag("FLAGS_tpu_eager_compile_cache", True,
            "Alias of FLAGS_eager_executable_cache kept from round 1; both "
            "must be on for the cache (wired: ops/registry).")


def _set_matmul_precision(value):
    import jax

    allowed = ("default", "float32", "bfloat16", "bfloat16_3x",
               "tensorfloat32", "high", "highest")
    if value not in allowed:
        raise ValueError(
            f"FLAGS_tpu_default_matmul_precision={value!r}; expected one "
            f"of {allowed}")
    jax.config.update("jax_default_matmul_precision",
                      None if value == "default" else value)


define_flag("FLAGS_tpu_default_matmul_precision", "default",
            "default|float32|bfloat16_3x|highest — pushed into "
            "jax.config.jax_default_matmul_precision on set (wired).",
            on_set=_set_matmul_precision)
define_flag("FLAGS_host_trace_level", 1, "Host profiler verbosity level.")
define_flag("FLAGS_enable_async_trace", False, "Enable async dispatch tracing.")
define_flag("FLAGS_tensor_operants_mode", "eager", "eager|static tensor operants mode.")
define_flag("FLAGS_comm_timeout_s", 1800, "Collective timeout (watchdog) in seconds.")
define_flag("FLAGS_store_barrier_timeout_s", 0.0,
            "Override for every TCPStore connect/barrier timeout (round-12 "
            "elastic satellite): 0 keeps each call site's default; set "
            "e.g. FLAGS_store_barrier_timeout_s=300 in the env to stretch "
            "the gang-rendezvous windows on throttled-CPU containers. "
            "Waits retry in slices with jittered exponential backoff "
            "(wired: distributed/store.py resolve_store_timeout).")
define_flag("FLAGS_allocator_strategy", "auto_growth", "Allocator strategy name (compat).")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92, "Compat only; XLA manages HBM.")
define_flag("FLAGS_log_memory_stats", False, "Log live/peak memory stats per step.")
define_flag("FLAGS_eager_executable_cache", True,
            "Cache a jitted executable per eager op call signature (op, "
            "arg structure, static kwargs); the backward executable "
            "rematerializes the op's forward inside the fused vjp. Turns "
            "per-op python retracing into an XLA cache hit (the analog of "
            "the reference's phi kernel cache).")
define_flag("FLAGS_eager_double_grad", True,
            "Record the create_graph (double-grad) re-derivation on eager "
            "ops. Disable to drop the saved-input captures and restore the "
            "minimal first-order memory profile (grad(create_graph=True) "
            "then falls back to constants).")

# -- round-2 breadth: reference flags kept for source compatibility. Wired
# flags are marked; "compat" flags are accepted + readable so ported
# scripts' set_flags calls keep working, with the TPU-native behavior
# documented (XLA owns what the flag tuned on CUDA).
define_flag("FLAGS_comm_abort_on_timeout", False,
            "Watchdog kills the process on a hung collective so the "
            "launcher's elastic restart recovers the job (wired).")
define_flag("FLAGS_nccl_blocking_wait", False,
            "Reference alias of FLAGS_comm_abort_on_timeout (wired).")
define_flag("FLAGS_benchmark_nccl", False,
            "compat: collective timing comes from the profiler timeline.")
define_flag("FLAGS_allreduce_record_one_event", True,
            "compat: XLA schedules collective/compute overlap itself.")
define_flag("FLAGS_dynamic_static_unified_comm", True,
            "compat: one collective path (XLA) serves eager and compiled.")
define_flag("FLAGS_use_cinn", False,
            "compat: fusion compilation is always XLA on TPU.")
define_flag("FLAGS_allow_cinn_ops", "",
            "compat: XLA fusion has no per-op allowlist.")
define_flag("FLAGS_deny_cinn_ops", "",
            "compat: XLA fusion has no per-op denylist.")
define_flag("FLAGS_enable_cinn_accuracy_check", False,
            "compat: use FLAGS_check_nan_inf / tests for accuracy checks.")
define_flag("FLAGS_enable_pir_api", True,
            "compat: the trace->StableHLO path is always on (PIR analog).")
define_flag("FLAGS_enable_pir_in_executor", True,
            "compat: XLA executables are the only executor.")
define_flag("FLAGS_new_executor_use_cuda_graph", False,
            "compat: XLA compiles whole-step programs; no graph capture.")
define_flag("FLAGS_new_executor_serial_run", False,
            "compat: PJRT launches are async by design.")
define_flag("FLAGS_fraction_of_cpu_memory_to_use", 1.0,
            "compat: host allocations are malloc'd, not pooled.")
define_flag("FLAGS_initial_gpu_memory_in_mb", 0,
            "compat: XLA preallocates HBM per XLA_PYTHON_CLIENT_* env.")
define_flag("FLAGS_reallocate_gpu_memory_in_mb", 0, "compat.")
define_flag("FLAGS_gpu_memory_limit_mb", 0, "compat.")
define_flag("FLAGS_eager_delete_tensor_gb", 0.0,
            "compat: XLA/PJRT buffer lifetime is reference-counted.")
define_flag("FLAGS_fast_eager_deletion_mode", True, "compat.")
define_flag("FLAGS_use_pinned_memory", True,
            "compat: H2D staging is owned by PJRT.")
define_flag("FLAGS_init_allocated_mem", False, "compat.")
define_flag("FLAGS_conv_workspace_size_limit", 512,
            "compat: XLA conv algorithm picking replaces cuDNN workspace.")
define_flag("FLAGS_cudnn_deterministic", False,
            "compat: set FLAGS_tpu_deterministic instead.")
define_flag("FLAGS_tpu_deterministic", False,
            "Force deterministic XLA reductions (wired via jax config by "
            "user scripts; surfaced here for parity).")
define_flag("FLAGS_cudnn_exhaustive_search", False,
            "Enables runtime kernel autotune, same switch as "
            "FLAGS_use_autotune (wired: ops/autotune.enabled).")
define_flag("FLAGS_embedding_deterministic", 0, "compat.")
define_flag("FLAGS_max_inplace_grad_add", 0, "compat.")
define_flag("FLAGS_pe_profile_fname", "", "compat profiler filename knob.")
define_flag("FLAGS_enable_async_trace", False,
            "Enable async dispatch tracing (wired: profiler).")
def _reset_low_precision_list(value):
    if value:  # (re-)enabling starts a fresh report, like the reference's
        from ..ops import registry  # per-run op list

        registry._LOW_PRECISION_OPS.clear()


define_flag("FLAGS_low_precision_op_list", 0,
            "Record ops AMP routes to low precision; read the set via "
            "paddle.amp.debugging.low_precision_op_list() (wired).",
            on_set=_reset_low_precision_list)
define_flag("FLAGS_enable_auto_parallel", True,
            "compat: DTensor/GSPMD auto-parallel is always available.")
define_flag("FLAGS_retain_grad_for_all_tensor", False,
            "Keep .grad on non-leaf tensors by default (wired: tape).")
define_flag("FLAGS_print_ir", False,
            "Dump StableHLO of compiled functions (wired: jit).")
define_flag("FLAGS_call_stack_level", 1,
            "Error reports include Python stack (wired: enforce).")

# -- round-2 (second pass) breadth: the next tier of reference flags users
# actually set in training scripts. Same convention: (wired) names the
# consumer; "compat" flags are accepted/readable with the TPU-native story
# documented.
define_flag("FLAGS_search_cache_max_number", 4096,
            "Upper bound on cached eager executables, the reference's "
            "kernel-search cache cap (wired: ops/registry executable "
            "cache; dispatch falls back inline once full).")
define_flag("FLAGS_sort_sum_gradient", False,
            "compat: the tape accumulates gradients in deterministic "
            "reverse-topological order unconditionally.")
define_flag("FLAGS_paddle_num_threads", 1,
            "compat: host-side parallelism belongs to XLA:CPU thread pools.")
define_flag("FLAGS_inner_op_parallelism", 0,
            "compat: intra-op parallelism is scheduled by XLA.")
define_flag("FLAGS_dist_threadpool_size", 0,
            "compat: collective execution threads are PJRT-owned.")
define_flag("FLAGS_initial_cpu_memory_in_mb", 500,
            "compat: host allocations are malloc'd, not pooled.")
define_flag("FLAGS_use_mkldnn", False,
            "compat: CPU fallback kernels compile through XLA:CPU.")
define_flag("FLAGS_conv2d_disable_cudnn", False,
            "compat: convs lower to XLA convolutions on TPU.")
define_flag("FLAGS_use_fast_math", False,
            "compat: matmul precision is per-op (bf16 MXU by default; "
            "request fp32 accumulation via precision= on matmul ops).")
define_flag("FLAGS_gemm_use_half_precision_compute_type", False,
            "compat: MXU accumulates in fp32 regardless.")
define_flag("FLAGS_communicator_max_merge_var_num", 20,
            "compat: PS communicator knob; PS stack is stubs-by-design.")
define_flag("FLAGS_communicator_send_queue_size", 20,
            "compat: PS communicator knob; PS stack is stubs-by-design.")
define_flag("FLAGS_apply_pass_to_program", False,
            "compat: XLA passes replace Program passes.")
define_flag("FLAGS_convert_all_blocks", True,
            "compat: whole-function tracing has no sub-block conversion.")
define_flag("FLAGS_jit_engine_type", "XLA",
            "compat: the only JIT engine is XLA (reference: Executor/PE).")
define_flag("FLAGS_use_shm_cache", False,
            "compat: DataLoader workers ship arrays via pipes, not shm.")
define_flag("FLAGS_dataloader_use_file_descriptor", False,
            "compat: see FLAGS_use_shm_cache.")
define_flag("FLAGS_enable_record_memory", False,
            "compat: FLAGS_log_memory_stats is the one the profiler "
            "reads.")
define_flag("FLAGS_get_host_by_name_time", 120,
            "Rendezvous DNS wait budget in seconds (wired: launch/TCPStore "
            "connect retry window).")
define_flag("FLAGS_start_cpu_core_id", 0,
            "compat: no CPU core pinning on TPU hosts.")
define_flag("FLAGS_enable_cublas_tensor_op_math", False,
            "compat: MXU usage is implicit in dtype choice.")
define_flag("FLAGS_cublaslt_exhaustive_search_times", 0,
            "compat: see FLAGS_use_autotune.")
define_flag("FLAGS_cudnn_batchnorm_spatial_persistent", False,
            "compat: batch_norm lowers to XLA-fused normalization.")
define_flag("FLAGS_enable_gpu_memory_usage_log", False,
            "compat: use paddle.device.memory_stats / profiler.")
define_flag("FLAGS_enable_gpu_memory_usage_log_mb", True, "compat.")
define_flag("FLAGS_free_idle_chunk", False,
            "compat: XLA's BFC allocator manages HBM chunks.")
define_flag("FLAGS_free_when_no_cache_hit", False, "compat.")
define_flag("FLAGS_gpu_allocator_retry_time", 2000,
            "compat: allocation retry is PJRT-internal.")
define_flag("FLAGS_enable_dependency_builder_debug_info", False,
            "compat: XLA owns instruction scheduling.")
define_flag("FLAGS_executor_log_deps_every_microseconds", 0, "compat.")
define_flag("FLAGS_check_kernel_launch", False,
            "compat: use FLAGS_check_nan_inf; launches are checked by PJRT.")
define_flag("FLAGS_enable_unused_var_check", False,
            "compat: jax tracing prunes unused values structurally.")
define_flag("FLAGS_prim_all", False,
            "compat: composite-op decomposition is jax-native (every op "
            "is already expressed in primitives).")
define_flag("FLAGS_prim_enable_dynamic", False, "compat.")
define_flag("FLAGS_print_allocator_trace_info", False, "compat.")
define_flag("FLAGS_npu_storage_format", False, "compat.")
define_flag("FLAGS_set_to_1d", True,
            "compat: 0-d vs 1-d scalar semantics follow numpy/jax (0-d).")

# ---- round 3: remaining behavior-critical flags from the reference's
# paddle/common/flags.cc (the GPU/oneDNN/graph-store-only tail is ported
# as documented compat no-ops; wired flags say what consumes them) ----

def deterministic_enabled() -> bool:
    """True when bit-stable math is requested — by the determinism flag
    itself OR by auto-parallel align mode (consumer-side OR instead of a
    hook: a nested set_flags inside a hook would break the atomic-
    rollback guarantee above)."""
    f = get_flags(("FLAGS_tpu_deterministic",
                   "FLAGS_enable_auto_parallel_align_mode"))
    return bool(f["FLAGS_tpu_deterministic"]
                or f["FLAGS_enable_auto_parallel_align_mode"])


define_flag("FLAGS_enable_auto_parallel_align_mode", False,
            "Alignment-debug mode for auto-parallel runs (wired: "
            "deterministic_enabled() ORs it with FLAGS_tpu_deterministic "
            "so dp/mp/pp recompositions are bit-comparable; reference "
            "uses it to align dygraph vs static).")
define_flag("FLAGS_alloc_fill_value", -1,
            "When >= 0, paddle.empty/empty_like fill new buffers with this "
            "value instead of zeros (wired: ops/yaml empty impls) — the "
            "uninitialized-memory bug shaker (reference init_allocated_mem "
            "cousin).")
define_flag("FLAGS_logging_pir_py_code_dir", "",
            "When set, jit.to_static dumps each traced function's "
            "StableHLO text into this directory (wired: jit/__init__.py) — "
            "the analog of dumping PIR python code.")
define_flag("FLAGS_logging_trunc_pir_py_code", False,
            "Truncate prior IR dumps instead of appending (wired with "
            "FLAGS_logging_pir_py_code_dir).")
define_flag("FLAGS_accuracy_check_rtol_fp32", 1e-5,
            "Tolerances for amp.debugging.check_accuracy comparisons "
            "(wired: amp/debugging.py).")
define_flag("FLAGS_accuracy_check_atol_fp32", 1e-6, "See rtol_fp32 (wired).")
define_flag("FLAGS_accuracy_check_rtol_fp16", 1e-3, "See rtol_fp32 (wired).")
define_flag("FLAGS_accuracy_check_atol_fp16", 1e-3, "See rtol_fp32 (wired).")
define_flag("FLAGS_accuracy_check_rtol_bf16", 1e-2, "See rtol_fp32 (wired).")
define_flag("FLAGS_accuracy_check_atol_bf16", 1e-2, "See rtol_fp32 (wired).")
define_flag("FLAGS_pir_debug", False,
            "Print jaxpr of each to_static trace to stderr (wired: "
            "jit/__init__.py).")
define_flag("FLAGS_async_trace_count", 0,
            "compat: host->device dispatch is PJRT-async by default.")
define_flag("FLAGS_prim_check_ops", False,
            "compat: jax primitives are closed under tracing; no "
            "decomposition completeness check needed.")
define_flag("FLAGS_disable_dyshape_in_train", False,
            "compat: jit shapes are static per specialization already.")
define_flag("FLAGS_enable_cse_in_dy2st", True,
            "compat: XLA always runs CSE.")
define_flag("FLAGS_enable_fuse_parallel_matmul_pass", True,
            "compat: XLA fusion subsumes the pass.")
define_flag("FLAGS_enable_fusion_fallback", False,
            "compat: Pallas kernels fall back per-op (incubate.nn).")
define_flag("FLAGS_pir_apply_inplace_pass", True,
            "compat: XLA buffer donation/aliasing replaces inplace passes.")
define_flag("FLAGS_pir_apply_shape_optimization_pass", True, "compat.")
define_flag("FLAGS_enable_pir_with_pt_in_dy2st", False, "compat.")
define_flag("FLAGS_enable_pir_in_executor_trace_run", False, "compat.")
define_flag("FLAGS_logging_pir_py_code_dump_symbolic_dims", False, "compat.")
define_flag("FLAGS_enable_collect_shape", False,
            "compat: shape collection is trace-time in jax.")
define_flag("FLAGS_cudnn_exhaustive_search_times", 0,
            "compat: see FLAGS_use_autotune.")
define_flag("FLAGS_cudnn_cache_saturation_count", 1, "compat.")
define_flag("FLAGS_enable_cudnn_frontend", False, "compat: no cuDNN.")
define_flag("FLAGS_batch_norm_use_miopen", False, "compat: no MIOpen.")
define_flag("FLAGS_run_kp_kernel", False, "compat: no Kunlun XPU here.")
define_flag("FLAGS_trt_ibuilder_cache", False, "compat: no TensorRT.")
define_flag("FLAGS_use_cuda_malloc_async_allocator", False,
            "compat: PJRT owns the allocator.")
define_flag("FLAGS_custom_device_mem_record", False, "compat.")
define_flag("FLAGS_enable_blaslt_global_search", False,
            "compat: see FLAGS_use_autotune.")
define_flag("FLAGS_cublaslt_device_best_config", "", "compat.")
define_flag("FLAGS_tracer_onednn_ops_on", "", "compat: no oneDNN tracer.")
define_flag("FLAGS_tracer_onednn_ops_off", "", "compat.")
define_flag("FLAGS_static_runtime_data_save_path", "", "compat.")
define_flag("FLAGS_use_fast_math", False,
            "compat: use FLAGS_tpu_default_matmul_precision for the "
            "speed/accuracy trade.")
define_flag("FLAGS_gemm_use_half_precision_compute_type", False,
            "compat: MXU accumulates fp32 regardless.")
define_flag("FLAGS_enable_async_trace", False, "compat.")
define_flag("FLAGS_use_mkldnn", False, "compat: no oneDNN.")

# ---- round-4 wired additions (reference paddle/common/flags.cc) ----
define_flag("FLAGS_multi_block_attention_min_partition_size", 512,
            "KV-chunk size for chunked decode attention "
            "(incubate.nn.memory_efficient_attention) — the TPU analog "
            "of the GPU multi-block decode partition size.")
define_flag("FLAGS_einsum_opt", False,
            "einsum contraction-order search: True = exhaustive "
            "('optimal'), False = greedy. The reference flag gates its "
            "einsum intermediate cache; contraction planning is the XLA-"
            "native equivalent knob.")
define_flag("FLAGS_selected_gpus", "",
            "comma-separated accelerator indices visible to this process "
            "(reference: device selection for the trainer); filters "
            "paddle.device accelerator enumeration.")
define_flag("FLAGS_sync_nccl_allreduce", True,
            "eager collectives block until the result is ready "
            "(XLA dispatch is async; the wait is block_until_ready, "
            "the NCCL-stream-sync analog).")

# ---- round-9 wired additions: the communication-overlap compiler knobs.
# The overlap engine (parallel/overlap.py) structures programs so
# gathers/reduce-scatters CAN hide under compute; whether they DO is the
# XLA scheduler's call — these flags push the latency-hiding scheduler
# and async-collective-fusion switches to the compiler
# (device.xla_overlap_flags / device.apply_xla_overlap_flags merge them
# into XLA_FLAGS before backend init; tests/test_overlap.py proves the
# plumbing reaches the compiler's option parser).
define_flag("FLAGS_tpu_latency_hiding_scheduler", True,
            "Enable XLA's latency-hiding scheduler "
            "(--xla_tpu_enable_latency_hiding_scheduler): reorders "
            "independent collectives ahead of compute so the overlap "
            "engine's layer-ahead gathers actually overlap (wired: "
            "device.xla_overlap_flags).")
define_flag("FLAGS_tpu_async_collective_fusion", True,
            "Enable async collective fusion "
            "(--xla_tpu_enable_async_collective_fusion): splits "
            "collectives into start/done pairs XLA can schedule compute "
            "between (wired: device.xla_overlap_flags).")
define_flag("FLAGS_tpu_async_all_gather", True,
            "Async all-gather lowering (--xla_enable_async_all_gather) "
            "— the ZeRO-3 prefetch gather rides this (wired: "
            "device.xla_overlap_flags).")
define_flag("FLAGS_tpu_async_collective_permute", True,
            "Async collective-permute lowering "
            "(--xla_enable_async_collective_permute) — the "
            "collective-matmul ppermute ring rides this (wired: "
            "device.xla_overlap_flags).")


# ---- exemption record: reference flags with NO TPU/XLA analog --------
# Every name in paddle/common/flags.cc is either WIRED above (same
# FLAGS_ name, real effect) or EXEMPT here with the reason.  The
# completeness test (tests/test_flags_wiring.py) asserts
# wired + exempt covers the reference list exactly.
_CUDA_LIB_DIRS = ("cublas_dir cudnn_dir cupti_dir curand_dir cusolver_dir "
                  "cusparse_dir cusparselt_dir lapack_dir mkl_dir "
                  "mklml_dir nccl_dir nvidia_package_dir op_dir "
                  "win_cuda_bin_dir").split()
_GPUGRAPH = ("gpugraph_debug_gpu_memory gpugraph_dedup_pull_push_mode "
             "gpugraph_enable_gpu_direct_access "
             "gpugraph_enable_hbm_table_collision_stat "
             "gpugraph_enable_segment_merge_grads "
             "gpugraph_hbm_table_load_factor "
             "gpugraph_load_node_list_into_hbm "
             "gpugraph_merge_grads_segment_size "
             "gpugraph_slot_feasign_max_num "
             "gpugraph_sparse_table_storage_mode gpugraph_storage_mode "
             "graph_embedding_split_infer_mode graph_get_neighbor_id "
             "graph_load_in_parallel graph_metapath_split_opt "
             "graph_neighbor_size_percent "
             "enable_graph_multi_node_sampling "
             "enable_neighbor_list_use_uva multi_node_sample_use_gpu_table "
             "query_dest_rank_by_multi_node enable_auto_detect_gpu_topo "
             "enable_auto_rdma_trans enable_all2all_use_fp16 "
             "enable_tracker_all2all enable_sparse_inner_gather "
             "enable_opt_get_features enable_ins_parser_file "
             "enable_slotpool_wait_release enable_slotrecord_reset_shrink "
             "record_pool_max_size slotpool_thread_num").split()
_CINN = ("cinn_compile_thread_num cinn_input_dynamic_dim_spec_file "
         "cinn_specify_input_dynamic_dim cinn_subgraph_graphviz_dir "
         "enable_cinn_auto_tune enable_cinn_compile_cache "
         "enable_interpretercore_launch_cinn check_infer_symbolic").split()
_CUDA_ALLOC = ("auto_free_cudagraph_allocations_on_launch "
               "auto_growth_chunk_size_in_mb "
               "cuda_malloc_async_pool_memory_throttle_ratio "
               "fraction_of_cuda_pinned_memory_to_use "
               "use_auto_growth_pinned_allocator pinned_memory_as_cpu_backend "
               "sync_after_alloc").split()
_LEGACY_EXEC = ("cache_inference_while_scope eager_delete_scope "
                "local_exe_sub_scope_limit memory_fraction_of_eager_deletion "
                "reader_queue_speed_test_mode save_static_runtime_data "
                "multiple_of_cupti_buffer_size "
                "communicator_is_sgd_optimizer "
                "enable_exit_when_partial_worker "
                "enable_adjust_op_order").split()
_PIR_PRIM = ("cse_max_count ir_inplace_kernel_blacklist "
             "logging_pir_py_code_int_tensor_element_limit "
             "pir_broadcast_tree_limit pir_subgraph_saving_dir "
             "prim_forward_blacklist prim_skip_dynamic "
             "manually_trans_conv_filter").split()

FLAG_EXEMPTIONS: Dict[str, str] = {}
for _n in _CUDA_LIB_DIRS:
    FLAG_EXEMPTIONS[_n] = ("CUDA/BLAS library dlopen search path — no "
                           "dynamic GPU library loading under PJRT/XLA")
for _n in _GPUGRAPH:
    FLAG_EXEMPTIONS[_n] = ("GPU-graph-engine / BoxPS / slot-pool data "
                           "feed — documented scope cut (SURVEY §2.10.2: "
                           "heter PS pipeline)")
for _n in _CINN:
    FLAG_EXEMPTIONS[_n] = ("CINN compiler stack — XLA replaces CINN "
                           "wholesale (SURVEY §2.10.1 L6 decision)")
for _n in _CUDA_ALLOC:
    FLAG_EXEMPTIONS[_n] = ("CUDA allocator / pinned-host pool tuning — "
                           "PJRT owns allocation on TPU; stats surfaced "
                           "via device.memory_stats")
for _n in _LEGACY_EXEC:
    FLAG_EXEMPTIONS[_n] = ("legacy fluid executor scope/communicator "
                           "machinery — no scope tree in the jit "
                           "execution model")
for _n in _PIR_PRIM:
    FLAG_EXEMPTIONS[_n] = ("PIR pass / prim-decomposition internals — "
                           "jaxpr->StableHLO has no analogous pass knob; "
                           "IR dumps are FLAGS_logging_pir_py_code_dir")
FLAG_EXEMPTIONS["fused_multi_transformer_op_use_mbfmha"] = (
    "CUDA mbFMHA kernel selector — Pallas flash is the one attention "
    "kernel family on TPU")
FLAG_EXEMPTIONS["use_xqa_optim"] = (
    "CUDA XQA decode kernel selector — decode attention is "
    "incubate.nn.decode_attention on TPU")
FLAG_EXEMPTIONS["trt_min_group_size"] = "TensorRT subgraph engine — no TRT"
