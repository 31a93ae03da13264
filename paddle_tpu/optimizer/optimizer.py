"""Optimizers.

Analog of python/paddle/optimizer/optimizer.py (base with master-weight AMP
support, optimizer.py:127) and adamw.py:49 etc. Two execution modes:

- **eager**: ``opt.step()`` reads ``param.grad`` accumulated by the tape and
  rebinds each parameter's buffer (XLA executes the fused update).
- **functional**: ``opt.init_state(params)`` / ``opt.apply(params, grads,
  state, lr)`` are pure pytree functions used by the compiled train step
  (paddle_tpu.jit) and the distributed engine — the update math is written
  once and shared by both modes.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.layer import Parameter
from . import lr as lr_mod


def _pin_lr_f32(lr):
    """Guard the functional update paths against f64 lr creep (Graph
    Doctor dtype audit, DT002 class): a STRONG float64 lr (np.float64,
    an x64 jnp array) would promote the whole update chain — master
    weights included — to double.  Python floats stay untouched: their
    WEAK typing is what lets ``value - lr * grad`` preserve bf16/f16
    param dtypes in optimizers whose update doesn't cast back (SGD,
    Momentum); pinning those to strong f32 would itself be a silent
    upcast of every non-fp32 param."""
    dt = getattr(lr, "dtype", None)
    if dt is None or str(dt) != "float64":
        return lr
    if getattr(lr, "weak_type", False):
        return lr                     # weak f64 defers to the param dtype
    return jnp.asarray(lr, jnp.float32)


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._lr = learning_rate
        self._parameters = list(parameters) if parameters is not None else []
        # weight_decay accepts a float (decoupled/L2 per optimizer) or a
        # paddle.regularizer instance (reference regularizer.py precedence:
        # a per-parameter ``param.regularizer`` overrides this one, so the
        # instance must stay a regularizer — folding L2Decay into the float
        # path would keep applying it under a per-param override)
        from ..regularizer import WeightDecayRegularizer

        self._regularizer = None
        if isinstance(weight_decay, WeightDecayRegularizer):
            self._regularizer = weight_decay
            weight_decay = None
        self._weight_decay = 0.0 if weight_decay is None else weight_decay
        self._grad_clip = grad_clip
        # per-parameter state: dict name -> dict of arrays, keyed by id(param)
        self._state: Dict[int, Dict[str, Any]] = {}
        self._global_step = 0
        # optional (param, grad) -> grad hook installed by shard_optimizer
        # stage >= 2: re-places gradients (reduce-scatter layout) pre-update
        self._grad_transform = None

    # ------------------------- lr ------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, lr_mod.LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, lr: float):
        self._lr = lr

    @property
    def _learning_rate(self):
        return self._lr

    # ------------------------- functional core ------------------------------
    def init_param_state(self, value) -> Dict[str, Any]:
        """Fresh per-parameter state arrays for a raw param value."""
        return {}

    def update(self, value, grad, state: Dict[str, Any], lr, step: int):
        """Pure single-param update: returns (new_value, new_state)."""
        raise NotImplementedError

    def init_state(self, params: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
        return {k: self.init_param_state(v) for k, v in params.items()}

    def apply(self, params: Dict[str, Any], grads: Dict[str, Any],
              state: Dict[str, Dict[str, Any]], lr, step: int = 0,
              decay_mask: Optional[Dict[str, bool]] = None,
              regularizers: Optional[Dict[str, Any]] = None):
        """Pure pytree update used under jit. Returns (new_params, new_state).

        ``regularizers`` carries per-parameter regularizer overrides (the
        functional analog of ``param.regularizer`` on the eager path, same
        precedence: per-param beats the optimizer-level one).
        """
        lr = _pin_lr_f32(lr)
        new_params, new_state = {}, {}
        for k, v in params.items():
            g = grads.get(k)
            if g is None:
                new_params[k] = v
                new_state[k] = state.get(k, {})
                continue
            masked = decay_mask is not None and not decay_mask.get(k, True)
            has_override = regularizers is not None and k in regularizers
            reg = regularizers[k] if has_override else self._regularizer
            if reg is not None and not masked:
                g = g + reg._apply(v).astype(g.dtype)
            if masked or has_override:
                # per-param override also replaces the float weight_decay
                # (same precedence as the eager path)
                saved, self._weight_decay = self._weight_decay, 0.0
                try:
                    nv, ns = self.update(v, g, state.get(k, self.init_param_state(v)), lr, step)
                finally:
                    self._weight_decay = saved
            else:
                nv, ns = self.update(v, g, state.get(k, self.init_param_state(v)), lr, step)
            # param dtype is an INVARIANT of the functional step: an
            # update whose arithmetic promoted (strong-f32 lr from
            # build_train_step's signature pin x bf16 param in SGD-class
            # `value - lr * grad`) must cast back, or the donated input
            # mismatches the output dtype and every later step retrains
            # in the promoted dtype (Adam already casts via its master;
            # this enforces the same contract for every subclass)
            dt = getattr(v, "dtype", None)
            if dt is not None and getattr(nv, "dtype", dt) != dt:
                nv = nv.astype(dt)
            new_params[k] = nv
            new_state[k] = ns
        return new_params, new_state

    # ------------------------- eager path -----------------------------------
    def step(self):
        self._global_step += 1
        params = self._parameters
        grads = [p._grad for p in params]
        # reshard BEFORE clipping: the reshard is a linear layout change, so
        # global-norm clip over sharded grads is equivalent — one transform
        # serves both the update and the p.grad write-back (pre-clip)
        if self._grad_transform is not None:
            grads = list(grads)
            for i, (p, g) in enumerate(zip(params, grads)):
                if g is None:
                    continue
                ng = self._grad_transform(p, g)
                if ng is not g:
                    grads[i] = ng
                    # write back: releases the replicated grad buffer, so
                    # the sharded layout is what survives the step (the
                    # ZeRO-2 memory effect); holds the ACCUMULATED (un-
                    # clipped) gradient — the clip below only affects the
                    # values fed to the update
                    p._grad = ng
        if self._grad_clip is not None:
            grads = self._grad_clip(params, grads)
        lr = self.get_lr()
        for p, g in zip(params, grads):
            if g is None or p.stop_gradient:
                continue
            pid = id(p)
            if pid not in self._state:
                self._state[pid] = self.init_param_state(p._value)
            no_decay = getattr(p, "no_weight_decay", False)
            param_reg = getattr(p, "regularizer", None)
            # a per-parameter regularizer REPLACES every optimizer-level
            # decay (regularizer instance and float weight_decay alike) —
            # the reference's ParamAttr precedence rule
            suppress_wd = no_decay or param_reg is not None
            if suppress_wd:
                saved, self._weight_decay = self._weight_decay, 0.0
            p_lr = lr
            ratio_fn = getattr(self, "_lr_ratio_fn", None)
            if ratio_fn is not None:
                p_lr = lr * float(ratio_fn(p))
            try:
                gv = g._value if isinstance(g, Tensor) else g
                reg = param_reg if param_reg is not None else self._regularizer
                if reg is not None and not no_decay:
                    gv = gv + reg._apply(p._value).astype(gv.dtype)
                new_v, new_s = self.update(p._value, gv.astype(p._value.dtype),
                                           self._state[pid], p_lr, self._global_step)
            finally:
                if suppress_wd:
                    self._weight_decay = saved
            p.set_value(new_v)
            self._state[pid] = new_s

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._parameters:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    # ------------------------- state dict ------------------------------------
    def state_dict(self):
        out = {"global_step": self._global_step}
        if isinstance(self._lr, lr_mod.LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        for i, p in enumerate(self._parameters):
            st = self._state.get(id(p))
            if st:
                for k, v in st.items():
                    out[f"param{i}.{k}"] = Tensor(v) if not isinstance(v, Tensor) else v
        return out

    def set_state_dict(self, state):
        self._global_step = state.get("global_step", 0)
        if "LR_Scheduler" in state and isinstance(self._lr, lr_mod.LRScheduler):
            self._lr.set_state_dict(state["LR_Scheduler"])
        for i, p in enumerate(self._parameters):
            st = {}
            prefix = f"param{i}."
            for k, v in state.items():
                if isinstance(k, str) and k.startswith(prefix):
                    st[k[len(prefix):]] = v._value if isinstance(v, Tensor) else jnp.asarray(v)
            if st:
                self._state[id(p)] = st


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def update(self, value, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * value
        return value - lr * grad, state


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def init_param_state(self, value):
        return {"velocity": jnp.zeros_like(value)}

    def update(self, value, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * value
        v = self._momentum * state["velocity"] + grad
        if self._nesterov:
            new_value = value - lr * (grad + self._momentum * v)
        else:
            new_value = value - lr * v
        return new_value, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None,
                 lazy_mode=False, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._eps = epsilon
        self._multi_precision = multi_precision
        self._decoupled = False  # Adam couples weight decay into grad

    def init_param_state(self, value):
        st = {
            "moment1": jnp.zeros(value.shape, dtype=jnp.float32),
            "moment2": jnp.zeros(value.shape, dtype=jnp.float32),
        }
        if self._multi_precision and value.dtype != jnp.float32:
            st["master"] = value.astype(jnp.float32)
        return st

    def update(self, value, grad, state, lr, step):
        g = grad.astype(jnp.float32)
        master = state.get("master", value.astype(jnp.float32) if value.dtype != jnp.float32 else value)
        if self._weight_decay and not self._decoupled:
            g = g + self._weight_decay * master
        m1 = self._beta1 * state["moment1"] + (1 - self._beta1) * g
        m2 = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g)
        bc1 = 1 - self._beta1 ** step
        bc2 = 1 - self._beta2 ** step
        update = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + self._eps)
        if self._weight_decay and self._decoupled:
            update = update + self._weight_decay * master
        new_master = master - lr * update
        new_state = {"moment1": m1, "moment2": m2}
        if "master" in state or (self._multi_precision and value.dtype != jnp.float32):
            new_state["master"] = new_master
        return new_master.astype(value.dtype), new_state


    # ---- fused multi-tensor (flat) path — round-7 ----------------------
    #
    # The per-param ``apply`` emits one update chain per tensor; at the
    # bench shape that is ~100 small fusions whose launch latency (not
    # bandwidth) dominates the ~25 ms optimizer slice (BASELINE.md r5
    # attribution).  The flat path groups float params by
    # (decay?, dtype), keeps moment1/moment2/master as ONE flat fp32
    # buffer per group, and runs the whole AdamW update as a single
    # bandwidth-bound pass per group; XLA fuses the gather (concatenate)
    # of grads and the scatter (slices) of new params into the same
    # fusion, so no extra materialized copies ride along.  Grouping is
    # recomputed from (sorted keys, dtypes, decay_mask) at trace time —
    # all static — so the state carries no python metadata.
    #
    # Scope: the functional/jit path only (build_train_step detects a
    # flat state via ``state_is_flat`` and calls ``apply_flat``).  The
    # eager ``step()``, per-param regularizer overrides, and lr_ratio
    # stay on the per-param path — ``apply_flat`` rejects those configs
    # loudly instead of silently diverging.

    def _flat_groups(self, params, decay_mask=None, flat_layout=None):
        """Deterministic float-param grouping: list of dicts with keys
        ``name/keys/shapes/sizes/dtype/decay`` (sorted, so init and
        every subsequent apply agree).

        ``flat_layout`` (a ``parallel.schedule.FlatUpdateLayout``)
        switches a group to the schedule-derived SHARD-MAJOR wire
        format when every leaf in it decomposes: the group gains
        ``layout``/``plans`` entries and its NAME carries the layout
        signature — the element order of the flat buffers is part of
        the state's pytree identity, so a layout mismatch fails on
        structure, never silently misorders the master.  A group with
        any non-decomposable leaf stays row-major (mixed orders inside
        one buffer would be a bug, not a layout)."""
        # a layout with no parallel axes has nothing to cut (its element
        # order IS row-major): ignore it, so states built against an
        # all-size-1 mesh keep the legacy naming and match a step that
        # dropped the layout for the same reason
        if flat_layout is not None and not getattr(flat_layout, "axes",
                                                   ()):
            flat_layout = None
        by_group: Dict[Any, List[str]] = {}
        for k in sorted(params):
            v = params[k]
            if not jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating):
                continue
            decay = True if decay_mask is None else bool(
                decay_mask.get(k, True))
            by_group.setdefault((decay, str(jnp.asarray(v).dtype)),
                                []).append(k)
        out = []
        for (decay, dt), keys in sorted(by_group.items()):
            shapes = [tuple(jnp.asarray(params[k]).shape) for k in keys]
            sizes = [int(np.prod(s)) if s else 1 for s in shapes]
            g = {"name": ("decay" if decay else "nodecay") + "|" + dt,
                 "keys": keys, "shapes": shapes, "sizes": sizes,
                 "dtype": dt, "decay": decay}
            if flat_layout is not None:
                plans = {k: flat_layout.leaf_plan(k, s)
                         for k, s in zip(keys, shapes)}
                if keys and all(p is not None for p in plans.values()):
                    g["name"] += "|" + flat_layout.signature
                    g["layout"] = flat_layout
                    g["plans"] = plans
            out.append(g)
        return out

    def _match_flat_groups(self, params, state, decay_mask, flat_layout):
        """Groups whose names match the STATE's keys: try the
        schedule-derived shard-major naming first, fall back to the
        legacy row-major naming (states built without a layout keep
        working through a schedule-built step), and fail loudly on
        anything else — a state whose wire format cannot be identified
        must never reach the elementwise update."""
        candidates = [flat_layout] if flat_layout is not None else []
        candidates.append(None)
        want = set(state["__flat__"])
        tried = []
        for lo in candidates:
            groups = self._flat_groups(params, decay_mask, lo)
            names = {g["name"] for g in groups}
            if names == want:
                return groups
            tried.append(sorted(names))
        raise ValueError(
            f"flat state's groups {sorted(want)} match neither the "
            f"schedule-derived shard-major naming nor the legacy "
            f"row-major naming {tried} — the state was built under a "
            f"different flat layout (mesh/schedule changed?); rebuild "
            f"it with init_flat_state(params, ..., flat_layout=...) "
            f"for THIS step's schedule")

    def init_flat_state(self, params, decay_mask=None, master_from=None,
                        flat_layout=None):
        """Flat per-group state: {'__flat__': {group: {moment1, moment2
        [, master]}}}.  ``master_from`` optionally seeds fp32 masters
        from UNROUNDED source values (bench.py casts params to bf16 at
        rest but wants exact fp32 masters).  ``flat_layout`` (a
        ``parallel.schedule.FlatUpdateLayout``) builds the state in the
        schedule-derived shard-major wire format — the master's element
        order then matches a step built from the same schedule, and the
        group names carry the layout signature (see _flat_groups)."""
        st = {}
        for g in self._flat_groups(params, decay_mask, flat_layout):
            n = sum(g["sizes"])
            # a shard-major group is BORN on its layout's own sharding:
            # eager zeros/concats would land whole on the first device,
            # which a state sized for the mesh does not fit
            sh = g["layout"].flat_sharding() if "layout" in g else None
            gs = {"moment1": jnp.zeros((n,), jnp.float32, device=sh),
                  "moment2": jnp.zeros((n,), jnp.float32, device=sh)}
            if self._multi_precision and g["dtype"] != "float32":
                src = master_from if master_from is not None else params
                if "layout" in g:
                    gs["master"] = jax.jit(
                        functools.partial(g["layout"].pack_group,
                                          g["plans"], g["keys"]),
                        out_shardings=sh)({k: src[k] for k in g["keys"]})
                else:
                    gs["master"] = jnp.concatenate(
                        [jnp.asarray(src[k]).astype(jnp.float32)
                         .reshape(-1) for k in g["keys"]]) \
                        if g["keys"] else jnp.zeros((0,), jnp.float32)
            st[g["name"]] = gs
        return {"__flat__": st}

    @staticmethod
    def state_is_flat(state) -> bool:
        return isinstance(state, dict) and set(state) == {"__flat__"}

    def _flat_group_update(self, gflat, m1, m2, master, lr, step,
                           decay: bool):
        """The elementwise AdamW update over one flat group (or any
        contiguous SLICE of one — the math is elementwise, so the
        host-offload engine's size-capped bucket streaming
        (parallel/memory.py apply_flat_offloaded) reuses this verbatim
        and stays bit-equal with the device-resident apply_flat).
        Returns (new_master, new_m1, new_m2)."""
        wd = self._weight_decay if decay else 0.0
        gg = gflat + wd * master if (wd and not self._decoupled) \
            else gflat
        nm1 = self._beta1 * m1 + (1 - self._beta1) * gg
        nm2 = self._beta2 * m2 + (1 - self._beta2) * jnp.square(gg)
        bc1 = 1 - self._beta1 ** step
        bc2 = 1 - self._beta2 ** step
        update = (nm1 / bc1) / (jnp.sqrt(nm2 / bc2) + self._eps)
        if wd and self._decoupled:
            update = update + wd * master
        return master - lr * update, nm1, nm2

    def apply_flat(self, params, grads, state, lr, step: int = 0,
                   decay_mask: Optional[Dict[str, bool]] = None,
                   flat_sharding=None, flat_layout=None):
        """Fused multi-tensor Adam/AdamW update over flat groups.
        Returns (new_params, new_state) with new_state flat again.

        ``flat_sharding`` (a NamedSharding over the flat 1-D buffers)
        MUST be passed when params are mesh-sharded: it pins the
        concat→update→slice chain's layout, (a) sharding the
        bandwidth-bound update across every device — the cross-replica
        weight-update sharding of arxiv 2004.13336 — and (b) keeping
        GSPMD's propagation from choosing the invalid partition that
        mis-lowers this chain on the 0.4.x CPU toolchain (found by the
        round-10 memory-engine parity tests: concat of two sharded
        leaves + elementwise chain + slice-back returns wrong VALUES
        without the constraint; build_train_step supplies it whenever a
        mesh is present).

        ``flat_layout`` (a ``parallel.schedule.FlatUpdateLayout``)
        routes groups whose STATE was built in the schedule-derived
        shard-major wire format: the at-rest -> flat boundary becomes a
        local relayout (no GSPMD reshard per leaf — the round-19
        SHARD001 bill cut) while the update math and the 2004.13336
        cross-replica pin are unchanged.  States built without a
        layout keep the legacy row-major path (detected by group
        names)."""
        if not self.state_is_flat(state):
            raise ValueError("apply_flat needs a state from "
                             "init_flat_state (got per-param pytree)")
        lr = _pin_lr_f32(lr)   # same f64-creep guard as ``apply``

        def _pin_flat(x):
            if flat_sharding is None:
                return x
            return jax.lax.with_sharding_constraint(x, flat_sharding)
        if self._regularizer is not None:
            raise NotImplementedError(
                "apply_flat: optimizer-level regularizer instances ride "
                "the per-param apply; pass weight_decay as a float")
        groups = self._match_flat_groups(params, state, decay_mask,
                                         flat_layout)
        missing = [k for g in groups for k in g["keys"]
                   if grads.get(k) is None]
        if missing:
            raise ValueError(
                f"apply_flat: every grouped param needs a gradient "
                f"(missing: {missing[:3]}...); frozen params belong on "
                f"the per-param apply path")
        new_params = dict(params)
        new_flat = {}
        for g in groups:
            gs = state["__flat__"][g["name"]]
            lo = g.get("layout")
            pin = lo.pin if lo is not None else _pin_flat
            if lo is not None:
                gflat = pin(lo.pack_group(
                    g["plans"], g["keys"],
                    {k: grads[k] for k in g["keys"]}))
            else:
                gflat = pin(jnp.concatenate(
                    [jnp.asarray(grads[k]).astype(jnp.float32)
                     .reshape(-1) for k in g["keys"]]))
            master = gs.get("master")
            if master is None:
                if lo is not None:
                    master = lo.pack_group(
                        g["plans"], g["keys"],
                        {k: params[k] for k in g["keys"]})
                else:
                    master = jnp.concatenate(
                        [jnp.asarray(params[k]).astype(jnp.float32)
                         .reshape(-1) for k in g["keys"]])
            master = pin(master)
            new_master, m1, m2 = self._flat_group_update(
                gflat, pin(gs["moment1"]), pin(gs["moment2"]),
                master, lr, step, g["decay"])
            ngs = {"moment1": m1, "moment2": m2}
            if "master" in gs:
                ngs["master"] = new_master
            new_flat[g["name"]] = ngs
            out_dtype = jnp.dtype(g["dtype"])
            if lo is not None:
                leaves = lo.unpack_group(g["plans"], g["keys"],
                                         new_master, pin_leaves=True)
                for k in g["keys"]:
                    new_params[k] = leaves[k].astype(out_dtype)
            else:
                off = 0
                for k, shape, size in zip(g["keys"], g["shapes"],
                                          g["sizes"]):
                    new_params[k] = new_master[off:off + size].reshape(
                        shape).astype(out_dtype)
                    off += size
        return new_params, {"__flat__": new_flat}


class AdamW(Adam):
    """Decoupled weight decay (analog of python/paddle/optimizer/adamw.py:49)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, multi_precision=multi_precision,
                         name=name)
        self._decoupled = True
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio_fn = lr_ratio

    def step(self):
        if self._apply_decay_param_fun is not None:
            for p in self._parameters:
                if not self._apply_decay_param_fun(p.name or ""):
                    p.no_weight_decay = True
        super().step()


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def init_param_state(self, value):
        return {"moment": jnp.full(value.shape, self._init_acc, dtype=jnp.float32)}

    def update(self, value, grad, state, lr, step):
        g = grad.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * value.astype(jnp.float32)
        acc = state["moment"] + jnp.square(g)
        new_value = value.astype(jnp.float32) - lr * g / (jnp.sqrt(acc) + self._eps)
        return new_value.astype(value.dtype), {"moment": acc}


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho = rho
        self._eps = epsilon
        self._momentum = momentum
        self._centered = centered

    def init_param_state(self, value):
        st = {"mean_square": jnp.zeros(value.shape, dtype=jnp.float32),
              "momentum": jnp.zeros(value.shape, dtype=jnp.float32)}
        if self._centered:
            st["mean_grad"] = jnp.zeros(value.shape, dtype=jnp.float32)
        return st

    def update(self, value, grad, state, lr, step):
        g = grad.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * value.astype(jnp.float32)
        ms = self._rho * state["mean_square"] + (1 - self._rho) * jnp.square(g)
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._eps)
        else:
            mg = None
            denom = jnp.sqrt(ms + self._eps)
        mom = self._momentum * state["momentum"] + lr * g / denom
        new_value = value.astype(jnp.float32) - mom
        st = {"mean_square": ms, "momentum": mom}
        if mg is not None:
            st["mean_grad"] = mg
        return new_value.astype(value.dtype), st


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def init_param_state(self, value):
        return {"moment": jnp.zeros(value.shape, dtype=jnp.float32),
                "inf_norm": jnp.zeros(value.shape, dtype=jnp.float32)}

    def update(self, value, grad, state, lr, step):
        g = grad.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * value.astype(jnp.float32)
        m = self._beta1 * state["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * state["inf_norm"], jnp.abs(g))
        bc = 1 - self._beta1 ** step
        new_value = value.astype(jnp.float32) - lr / bc * m / (u + self._eps)
        return new_value.astype(value.dtype), {"moment": m, "inf_norm": u}


class Lamb(Optimizer):
    """Layer-wise adaptive moments (reference: python/paddle/optimizer/lamb.py)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def step(self):
        if self._exclude_fn is not None:
            for p in self._parameters:
                if self._exclude_fn(p):
                    p.no_weight_decay = True
        super().step()

    def init_param_state(self, value):
        return {"moment1": jnp.zeros(value.shape, dtype=jnp.float32),
                "moment2": jnp.zeros(value.shape, dtype=jnp.float32)}

    def update(self, value, grad, state, lr, step):
        g = grad.astype(jnp.float32)
        vf = value.astype(jnp.float32)
        m1 = self._beta1 * state["moment1"] + (1 - self._beta1) * g
        m2 = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g)
        bc1 = 1 - self._beta1 ** step
        bc2 = 1 - self._beta2 ** step
        r = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + self._eps) + self._weight_decay * vf
        w_norm = jnp.linalg.norm(vf)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new_value = vf - lr * trust * r
        return new_value.astype(value.dtype), {"moment1": m1, "moment2": m2}


class LarsMomentum(Optimizer):
    """LARS momentum (reference: fluid LarsMomentumOptimizer /
    lars_momentum op): per-layer trust ratio
    ``local_lr = lr * coeff * ||w|| / (||g|| + wd * ||w|| + eps)``."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 epsilon=1e-8, exclude_from_weight_decay=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._momentum = momentum
        self._coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._eps = epsilon
        self._exclude = exclude_from_weight_decay or []

    def init_param_state(self, value):
        return {"velocity": jnp.zeros(value.shape, dtype=jnp.float32)}

    def update(self, value, grad, state, lr, step):
        g = grad.astype(jnp.float32)
        w = value.astype(jnp.float32)
        w_norm = jnp.linalg.norm(w)
        g_norm = jnp.linalg.norm(g)
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            lr * self._coeff * w_norm
            / (g_norm + self._lars_wd * w_norm + self._eps),
            lr)
        v = self._momentum * state["velocity"] \
            + local_lr * (g + self._lars_wd * w)
        return (w - v).astype(value.dtype), {"velocity": v}


# ---- round-5 optimizer long tail (reference python/paddle/optimizer) ----


class Adadelta(Optimizer):
    """Reference paddle.optimizer.Adadelta (Zeiler 2012): accumulated
    squared gradients + accumulated squared updates, no learning-rate
    sensitivity beyond the scale factor."""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._epsilon = epsilon
        self._rho = rho

    def init_param_state(self, value):
        return {"avg_squared_grad": jnp.zeros_like(value),
                "avg_squared_update": jnp.zeros_like(value)}

    def update(self, value, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * value
        g2 = self._rho * state["avg_squared_grad"] \
            + (1 - self._rho) * grad * grad
        upd = grad * jnp.sqrt(state["avg_squared_update"] + self._epsilon) \
            / jnp.sqrt(g2 + self._epsilon)
        u2 = self._rho * state["avg_squared_update"] \
            + (1 - self._rho) * upd * upd
        return value - lr * upd, {"avg_squared_grad": g2,
                                  "avg_squared_update": u2}


class ASGD(Optimizer):
    """Averaged SGD (reference paddle.optimizer.ASGD; phi asgd_kernel):
    ``d`` is the running SUM of the last ``batch_num`` gradients held in
    a circular buffer; the step is param -= (lr / n) * d with
    n = min(seen, batch_num) — SGD over the gradient average."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._batch_num = max(int(batch_num), 1)

    def init_param_state(self, value):
        flat = int(np.prod(value.shape)) if value.shape else 1
        return {"d": jnp.zeros((flat,), jnp.float32),
                "hist": jnp.zeros((self._batch_num, flat), jnp.float32),
                "seen": jnp.zeros((), jnp.int32)}

    def update(self, value, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * value
        g = jnp.asarray(grad, jnp.float32).reshape(-1)
        slot = state["seen"] % self._batch_num
        y = state["hist"][slot]                    # grad evicted this turn
        d = state["d"] - y + g                     # kernel: d - y + grad
        hist = state["hist"].at[slot].set(g)
        n = jnp.minimum(state["seen"] + 1, self._batch_num).astype(
            jnp.float32)
        new_value = value - ((lr / n) * d).reshape(value.shape).astype(
            value.dtype)
        return new_value, {"d": d, "hist": hist, "seen": state["seen"] + 1}


class Rprop(Optimizer):
    """Resilient backprop (reference paddle.optimizer.Rprop): per-weight
    step sizes grown/shrunk by the gradient-sign agreement; gradients'
    magnitudes are ignored."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas

    def init_param_state(self, value):
        return {"prev_grad": jnp.zeros_like(value),
                "step_size": jnp.full_like(jnp.asarray(value, jnp.float32),
                                           float(self.get_lr()))}

    def update(self, value, grad, state, lr, step):
        sign = jnp.sign(grad * state["prev_grad"])
        factor = jnp.where(sign > 0, self._eta_pos,
                           jnp.where(sign < 0, self._eta_neg, 1.0))
        step_size = jnp.clip(state["step_size"] * factor, self._lr_min,
                             self._lr_max)
        # on sign flip the reference zeroes the gradient (no step, keep
        # direction memory cleared)
        eff_grad = jnp.where(sign < 0, 0.0, grad)
        new_value = value - jnp.sign(eff_grad) * step_size
        return new_value, {"prev_grad": eff_grad, "step_size": step_size}


class NAdam(Optimizer):
    """Reference paddle.optimizer.NAdam (Dozat 2016): Adam with Nesterov
    momentum via the mu-product schedule."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._beta1, self._beta2 = beta1, beta2
        self._epsilon = epsilon
        self._psi = momentum_decay

    def init_param_state(self, value):
        return {"m": jnp.zeros_like(value, jnp.float32),
                "v": jnp.zeros_like(value, jnp.float32),
                "mu_product": jnp.ones((), jnp.float32)}

    def update(self, value, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * value
        t = jnp.asarray(step, jnp.float32)
        gf = grad.astype(jnp.float32)
        mu_t = self._beta1 * (1.0 - 0.5 * 0.96 ** (t * self._psi))
        mu_t1 = self._beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        mu_prod = state["mu_product"] * mu_t
        m = self._beta1 * state["m"] + (1 - self._beta1) * gf
        v = self._beta2 * state["v"] + (1 - self._beta2) * gf * gf
        m_hat = mu_t1 * m / (1 - mu_prod * mu_t1) \
            + (1 - mu_t) * gf / (1 - mu_prod)
        v_hat = v / (1 - self._beta2 ** t)
        upd = lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon)
        return (value - upd.astype(value.dtype),
                {"m": m, "v": v, "mu_product": mu_prod})


class RAdam(Optimizer):
    """Rectified Adam (reference paddle.optimizer.RAdam, Liu et al.
    2020): variance rectification switches between SGD-with-momentum and
    Adam as the variance estimate warms up."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._beta1, self._beta2 = beta1, beta2
        self._epsilon = epsilon

    def init_param_state(self, value):
        return {"m": jnp.zeros_like(value, jnp.float32),
                "v": jnp.zeros_like(value, jnp.float32)}

    def update(self, value, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * value
        t = jnp.asarray(step, jnp.float32)
        gf = grad.astype(jnp.float32)
        m = self._beta1 * state["m"] + (1 - self._beta1) * gf
        v = self._beta2 * state["v"] + (1 - self._beta2) * gf * gf
        rho_inf = 2.0 / (1 - self._beta2) - 1.0
        # 1 - beta2^t via expm1 — the naive f32 subtraction loses enough
        # precision to flip the rho_t > 5 branch near the threshold
        # (torch/paddle compute this in float64)
        log_b2 = jnp.log(jnp.asarray(self._beta2, jnp.float32))
        one_minus_beta2_t = -jnp.expm1(t * log_b2)
        beta2_t = 1.0 - one_minus_beta2_t
        rho_t = rho_inf - 2.0 * t * beta2_t / one_minus_beta2_t
        m_hat = m / (1 - self._beta1 ** t)
        rect = jnp.sqrt(((rho_t - 4) * (rho_t - 2) * rho_inf)
                        / jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t,
                                      1e-12))
        v_hat = jnp.sqrt(v / one_minus_beta2_t)
        adam_step = rect * m_hat / (v_hat + self._epsilon)
        sgd_step = m_hat
        upd = lr * jnp.where(rho_t > 5.0, adam_step, sgd_step)
        return value - upd.astype(value.dtype), {"m": m, "v": v}


class LBFGS(Optimizer):
    """Limited-memory BFGS (reference paddle.optimizer.LBFGS): two-loop
    recursion over the last ``history_size`` (s, y) pairs.  The eager
    API follows the reference: ``step(closure)`` re-evaluates the loss;
    the functional update() performs ONE direction step using the stored
    curvature pairs (line search ``strong_wolfe`` is approximated by the
    fixed learning rate — the reference's default line_search_fn=None
    path)."""

    def __init__(self, learning_rate=1.0, max_iter=20, tolerance_grad=1e-7,
                 tolerance_change=1e-9, history_size=10,
                 line_search_fn=None, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._hist = int(history_size)
        self._max_iter = int(max_iter)
        self._tol_grad = float(tolerance_grad)
        self._tol_change = float(tolerance_change)

    def init_param_state(self, value):
        h = self._hist
        flat = int(np.prod(value.shape)) if value.shape else 1
        return {"s": jnp.zeros((h, flat), jnp.float32),
                "y": jnp.zeros((h, flat), jnp.float32),
                "rho": jnp.zeros((h,), jnp.float32),
                "prev_x": jnp.zeros((flat,), jnp.float32),
                "prev_g": jnp.zeros((flat,), jnp.float32),
                "count": jnp.zeros((), jnp.int32)}

    def update(self, value, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * value
        shape = value.shape
        x = jnp.asarray(value, jnp.float32).reshape(-1)
        g = jnp.asarray(grad, jnp.float32).reshape(-1)
        h = self._hist
        cnt = state["count"]

        # push the newest (s, y) pair once we have a previous point
        s_new = x - state["prev_x"]
        y_new = g - state["prev_g"]
        sy = jnp.dot(s_new, y_new)
        valid = (cnt > 0) & (sy > 1e-10)
        s_buf = jnp.where(valid, jnp.roll(state["s"], -1, 0)
                          .at[-1].set(s_new), state["s"])
        y_buf = jnp.where(valid, jnp.roll(state["y"], -1, 0)
                          .at[-1].set(y_new), state["y"])
        rho_buf = jnp.where(valid, jnp.roll(state["rho"], -1)
                            .at[-1].set(1.0 / jnp.maximum(sy, 1e-10)),
                            state["rho"])

        # two-loop recursion (zero rho entries are inert)
        def first(i, carry):
            q, alphas = carry
            j = h - 1 - i
            a = rho_buf[j] * jnp.dot(s_buf[j], q)
            return q - a * y_buf[j], alphas.at[j].set(a)

        q, alphas = jax.lax.fori_loop(
            0, h, first, (g, jnp.zeros((h,), jnp.float32)))
        ys = jnp.dot(y_buf[-1], y_buf[-1])
        gamma = jnp.where(ys > 0, jnp.dot(s_buf[-1], y_buf[-1])
                          / jnp.maximum(ys, 1e-10), 1.0)
        r = q * jnp.where(valid | (cnt > 1), gamma, 1.0)

        def second(j, r):
            b = rho_buf[j] * jnp.dot(y_buf[j], r)
            return r + s_buf[j] * (alphas[j] - b)

        r = jax.lax.fori_loop(0, h, second, r)
        new_x = x - lr * r
        new_state = {"s": s_buf, "y": y_buf, "rho": rho_buf,
                     "prev_x": x, "prev_g": g, "count": cnt + 1}
        return new_x.reshape(shape).astype(value.dtype), new_state

    def step(self, closure=None):
        """Reference LBFGS.step(closure): up to ``max_iter`` inner
        iterations, stopping on the gradient / parameter-change
        tolerances; returns the final loss.  Without a closure, one
        direction step over the accumulated .grad."""
        if closure is None:
            return super().step()
        import numpy as _np

        loss = None
        for _ in range(self._max_iter):
            for p in self._parameters:
                if getattr(p, "_grad", None) is not None:
                    p._grad = None
            loss = closure()
            gmax = 0.0
            before = [_np.asarray(p._value).copy()
                      for p in self._parameters]
            for p in self._parameters:
                if getattr(p, "_grad", None) is not None:
                    gmax = max(gmax, float(_np.abs(
                        _np.asarray(p._grad._value
                                    if hasattr(p._grad, "_value")
                                    else p._grad)).max()))
            if gmax <= self._tol_grad:
                break
            super().step()
            change = max(float(_np.abs(_np.asarray(p._value) - b).max())
                         for p, b in zip(self._parameters, before))
            if change <= self._tol_change:
                break
        return loss
