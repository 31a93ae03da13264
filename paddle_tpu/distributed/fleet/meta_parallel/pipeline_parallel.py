"""Pipeline-parallel runtime: micro-batch schedules.

Analog of python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py
(PipelineParallel:231, forward_backward_pipeline:547, train_batch:792, and
the interleaved variant :1143) plus the P2P layer
(pp_utils/p2p_communication.py) it drives.

TPU-native design: the reference hand-schedules per-rank send/recv because
every GPU runs its own process.  Under XLA there are two regimes:

1. **Compiled schedule** (paddle_tpu.parallel.pipelining +
   parallel.schedules): ``schedule_mode`` selects a static schedule table
   — FThenB, 1F1B, interleaved VPP, or zero-bubble ZBH1 — executed inside
   ONE jitted shard_map over a ``pp`` mesh, one ppermute per direction per
   tick.  Used whenever the PipelineLayer's stages are structurally
   uniform (same param tree per stage — the same constraint the stacked
   [P, ...] layout imposes in every compiled-pipeline system).
2. **Eager fallback**: micro-batch F-then-B with grad accumulation on the
   controller (identical math; used for structurally uneven stage
   partitions).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....core.tensor import Tensor
from ....ops import registry as _reg
from .pp_layers import PipelineLayer

logger = logging.getLogger(__name__)


class PipelineParallel:
    """train_batch/eval_batch over a PipelineLayer (reference :231)."""

    def __init__(self, layers, hcg=None, strategy=None):
        if not isinstance(layers, PipelineLayer):
            raise TypeError("PipelineParallel expects a PipelineLayer")
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        cfg = getattr(strategy, "pipeline_configs", None) or {}
        self.accumulate_steps = int(cfg.get("accumulate_steps", 1))
        self.micro_batch_size = int(cfg.get("micro_batch_size", 1))
        self.schedule_mode = cfg.get("schedule_mode", "1F1B")
        self.total_loss = None
        self._compiled_cache: Dict[Tuple, Any] = {}
        self._warned_fallback = False

    # Layer passthrough ----------------------------------------------------
    def __call__(self, *a, **k):
        return self._layers(*a, **k)

    def parameters(self, *a, **k):
        return self._layers.parameters(*a, **k)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        return self._layers.set_state_dict(*a, **k)

    def train(self):
        self._layers.train()

    def eval(self):
        self._layers.eval()

    # schedules ------------------------------------------------------------
    def _split_micro(self, data):
        x, y = data
        n = self.accumulate_steps
        xs = jnp.split(x._value if isinstance(x, Tensor) else jnp.asarray(x), n)
        ys = jnp.split(y._value if isinstance(y, Tensor) else jnp.asarray(y), n)
        return [(Tensor(a), Tensor(b)) for a, b in zip(xs, ys)]

    def forward_backward_pipeline(self, data, scaler=None):
        """Run the selected schedule (reference :547).  ``schedule_mode``
        in {FThenB, 1F1B, VPP, ZBH1} executes the compiled schedule table
        when the stage partition is uniform; otherwise the eager F-then-B
        loop (same math) runs."""
        compiled = self._compiled_schedule_step(data, scaler)
        if compiled is not None:
            self.total_loss = compiled
            return compiled
        return self._eager_fthenb(data, scaler)

    # -- compiled path -----------------------------------------------------
    def _stage_states(self):
        """Per-global-stage flat state dicts + the Parameter refs behind
        them; None if stages are structurally uneven."""
        pl = self._layers
        n_global = len(pl.segment_parts) - 1
        states, refs = [], []
        for s in range(n_global):
            st, rf = {}, {}
            for j, layer in enumerate(pl.get_stage_layers(s)):
                for k, t in layer.state_dict().items():
                    st[f"{j}.{k}"] = t._value
                params = dict(layer.named_parameters())
                for k in params:
                    rf[f"{j}.{k}"] = params[k]
            states.append(st)
            refs.append(rf)
        sig = {tuple(sorted((k, v.shape, str(v.dtype))
                            for k, v in st.items())) for st in states}
        if len(sig) != 1:
            return None, None
        return states, refs

    def _compiled_schedule_step(self, data, scaler):
        from ....parallel.pipelining import (pipeline_train_step,
                                             stack_stage_params,
                                             stack_stage_params_interleaved)
        from ....parallel.schedules import build_schedule
        from jax.sharding import Mesh, PartitionSpec as P

        pl = self._layers
        p = pl.get_num_stages()
        v = max(1, pl._num_virtual_stages)
        mode = self.schedule_mode
        if v > 1 and mode in ("1F1B", "FThenB"):
            # reference semantics: virtual stages alone select interleaving
            # (PipelineParallelWithInterleave is chosen by v>1, not by a
            # mode string) — map to the interleaved table
            mode = "VPP"
        if mode not in ("FThenB", "1F1B", "VPP", "ZBH1") or \
                (mode == "VPP") != (v > 1):
            return self._fallback(f"schedule_mode {mode!r} with v={v}")
        if p <= 1 or len(jax.devices()) < p or pl._loss_fn is None:
            return self._fallback("needs >=p devices and a loss_fn")
        states, refs = self._stage_states()
        if states is None:
            return self._fallback("stage partitions are structurally uneven")

        x, y = data
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        yv = y._value if isinstance(y, Tensor) else jnp.asarray(y)
        m = self.accumulate_steps
        if xv.shape[0] % m:
            return self._fallback(f"batch {xv.shape[0]} % {m} microbatches")
        xm = xv.reshape((m, xv.shape[0] // m) + xv.shape[1:])
        ym = yv.reshape((m, yv.shape[0] // m) + yv.shape[1:])

        key = (mode, p, v, m, xm.shape, str(xm.dtype))
        if key not in self._compiled_cache:
            sched = build_schedule(mode, p=p, m=m, v=v)
            template = pl.get_stage_layers(0)
            loss_ref = pl._loss_fn

            def stage_fn(state, a):
                from ....autograd import no_grad
                t = Tensor(a)
                with no_grad():
                    for j, layer in enumerate(template):
                        pre = f"{j}."
                        sub = {k[len(pre):]: val for k, val in state.items()
                               if k.startswith(pre)}
                        t = layer.functional_call(sub, t)
                return t._value

            def loss_fn(a, yb):
                from ....autograd import no_grad
                with no_grad():
                    out = loss_ref(Tensor(a), Tensor(yb))
                val = out._value if isinstance(out, Tensor) else out
                return val.mean() if val.ndim else val

            mesh = Mesh(np.asarray(jax.devices()[:p], dtype=object), ("pp",))
            leaf_spec = lambda a: P(*(("pp",) + (None,) * (a.ndim - 1)))
            proto = (stack_stage_params_interleaved(states, p) if v > 1
                     else stack_stage_params(states))
            pspec = jax.tree_util.tree_map(leaf_spec, proto)

            def body(sp, xb, yb):
                return pipeline_train_step(stage_fn, loss_fn, sched, sp,
                                           xb, yb, axis="pp")

            from jax import shard_map as _shard_map

            fn = jax.jit(_shard_map(
                body, mesh=mesh, in_specs=(pspec, P(None), P(None)),
                out_specs=(P(), pspec), check_vma=False))
            self._compiled_cache[key] = fn
        fn = self._compiled_cache[key]

        stacked = (stack_stage_params_interleaved(states, p) if v > 1
                   else stack_stage_params(states))
        loss, grads = fn(stacked, xm, ym)

        # scatter grads back onto the Parameters (accumulate, like the
        # tape does across micro-batches); scaler parity: step() divides
        # p.grad by the scale, so pre-multiply
        factor = scaler._scale if scaler is not None else 1.0
        order = ([j * p + r for r in range(p) for j in range(v)] if v > 1
                 else list(range(p * v)))
        for pos, stage in enumerate(order):
            for k, param in refs[stage].items():
                g = grads[k][pos].astype(param._value.dtype) * factor
                if param._grad is None:
                    param._grad = Tensor(g)
                else:
                    param._grad = Tensor(param._grad._value + g)
        return Tensor(loss)

    def _fallback(self, why: str):
        if not self._warned_fallback:
            self._warned_fallback = True
            logger.warning(
                "PipelineParallel: compiled %s schedule unavailable (%s); "
                "using the eager F-then-B loop", self.schedule_mode, why)
        return None

    # -- eager fallback ----------------------------------------------------
    def _eager_fthenb(self, data, scaler=None):
        """F-then-B over micro-batches with grad accumulation
        (reference :547; grads sum across micro-batches, loss averages)."""
        micro = self._split_micro(data)
        total = None
        for mx, my in micro:
            out = self._layers(mx)
            loss = self._layers._loss_fn(out, my)
            if loss.ndim > 0:
                loss = loss.mean()
            scaled = loss / self.accumulate_steps
            if scaler is not None:
                scaled = scaler.scale(scaled)
            scaled.backward()
            d = loss.detach()
            total = d if total is None else total + d
        self.total_loss = total / self.accumulate_steps
        return self.total_loss

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        """Reference :792: run schedule, then step."""
        self._layers.train()
        loss = self.forward_backward_pipeline(data, scaler)
        if scaler is not None:
            scaler.step(optimizer)
            scaler.update()
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return loss

    def eval_batch(self, data, compute_loss: bool = True):
        self._layers.eval()
        micro = self._split_micro(data)
        total = None
        with _no_grad():
            for mx, my in micro:
                out = self._layers(mx)
                if compute_loss:
                    loss = self._layers._loss_fn(out, my)
                    if loss.ndim > 0:
                        loss = loss.mean()
                    total = loss if total is None else total + loss
        return (total / self.accumulate_steps) if total is not None else None


def _no_grad():
    from ....autograd import no_grad
    return no_grad()
