"""Ulysses-style segment parallelism (the reference's ``sep`` axis).

Analog of the reference's segment-parallel path: a dedicated mesh axis for
sequence segments (fleet.py:678 sep_degree, topology.py:503 get_sep_*,
meta_parallel/segment_parallel.py:26) whose redistribution helpers are
alltoall-shaped (hybrid_parallel_util.py:254-287).

TPU-native: inside a shard_map body over the ``sep`` axis, attention for a
seq-sharded batch runs as  alltoall(seq→heads) → full-seq flash attention
on h/P heads → alltoall(heads→seq).  Two ICI alltoalls replace the P²
point-to-point exchanges a naive implementation would need; head count must
be divisible by the sep degree (DeepSpeed-Ulysses' constraint — ring
attention covers the rest).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import compat as _compat


from jax.lax import axis_size as _axis_size

def ulysses_attention(q, k, v, axis: str = "sep", causal: bool = True,
                      scale: Optional[float] = None):
    """Attention for seq-sharded q/k/v inside a shard_map body.

    q: [b, s_local, h, d]; k,v: [b, s_local, kvh, d].  Requires h and kvh
    divisible by the axis size.  Returns [b, s_local, h, d].
    """
    p = _axis_size(axis)
    b, sl, h, d = q.shape
    kvh = k.shape[2]
    if h % p or kvh % p:
        raise ValueError(f"heads ({h}, kv {kvh}) must divide sep degree {p}")

    # seq→heads: [b, s/P, h, d] → [b, s, h/P, d]
    def fwd(x):
        return _compat.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    # heads→seq: inverse exchange
    def bwd(x):
        return _compat.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    qg, kg, vg = fwd(q), fwd(k), fwd(v)
    from ..ops.pallas.flash_attention import flash_attention_raw

    og = flash_attention_raw(qg, kg, vg, causal=causal, scale=scale)
    return bwd(og)
