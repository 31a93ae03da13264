"""The seam between a model and the serving engine.

The engine (``inference/serving.py``) learns a model from
``cfg.paged_layout()``, a ``PagedLayout``, and from nothing else; a
model's step (``models/<name>.py``) learns the engine from this module
and from nothing else.  So this module imports no model and no engine.
It holds what a sequence keeps on the device as the engine counts it
(``PageKind``, ``PagedLayout``), the contract of a step (``PagedLayout``,
under ``step``), the row counts a step is compiled at (``step_ladder``),
the names of the counts a step or the engine takes, and the few device
helpers every step calls.

What a sequence holds, and how a launch is told of it:

- Everything the host tells the device rides in ONE int32 upload a
  step, the packed ``rows``, beside the page tables.  Padding rows are
  the price of a static shape, up to the rung launched: they compute
  garbage that is never read and write it to the TRASH page, the last
  physical page, which no slot owns.
- The page pools are PER-LAYER arrays, donated through the step, so a
  layer's cache update is one scatter into its own pool; a fused
  ``[L, pages, ...]`` slab cost a slice and a whole-layer update a
  layer.
- A model may have several KINDS of page, FURTHER pools a page beside
  the two, and a SECOND SORT of state beside its pages, a recurrent
  one: ``PagedLayout``'s ``kinds``, ``more_pools`` and ``state``.  A
  layout of one kind with neither runs the same code with tuples of
  length one and none.
- The CONSUMED rows alone (every verify-window row and each prefill
  chunk's final row) are gathered on the device before the final norm
  and the vocabulary projection (``gathered_logits``): the head matmul
  and the fp32 logits are sized to the engine's ``gather_cap`` whatever
  the rung, so a launch's sampled tokens have one shape and launches of
  different rungs chain.  Their first maxima (``sample_greedy``) are
  what a step copies back, 4 bytes a row; the logits themselves cross
  only for a request with a temperature, or when ``last_logits`` is
  read.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

#: the state snapshots a launch takes at most, for every layout with a
#: recurrent state (its ``state_snapshots_a_step``): ``snapshot_plan``
#: finds that many rows that name one
SNAPSHOTS_A_STEP = 2


def _write_kv_rows(pool, phys, off, x):
    """Write token rows ``x`` [T, kvh, d] into ``pool`` [pages, kvh,
    page, d]: row ``t`` to page ``phys[t]``, in-page offset ``off[t]``.
    The index carries the HEAD too, so the update window is one
    contiguous row of ``d`` and XLA scatters in place into the donated
    pool.  ``pool.at[phys, :, off, :]`` has a strided window [kvh, d],
    for which XLA copied the WHOLE pool into another layout and back
    (PERF.md section 6, PR 25)."""
    heads = jnp.arange(pool.shape[1])
    return pool.at[phys[:, None], heads[None, :], off[:, None], :].set(
        x.astype(pool.dtype))


def resolve_row_tokens(tok, prev_tokens):
    """The packed rows' input tokens, references resolved.  ``tok >= 0``
    is a token the host knew when it packed the step; ``tok < 0`` names
    entry ``-1 - tok`` of ``prev_tokens``, what the launch before this
    one sampled (``sample_greedy`` of its gathered rows): the host
    enqueued this launch before it had read that token.  Every model's
    step calls this before its embedding."""
    ref = jnp.take(prev_tokens, jnp.maximum(-1 - tok, 0), mode="clip")
    return jnp.where(tok < 0, ref, tok)


def row_columns(rows, prev_tokens=None):
    """The packed rows' columns by position (``PagedLayout``, under
    ``step``), every column sliced and then the input tokens' references
    resolved (``prev_tokens`` None: a launch that holds none)."""
    tok, *cols = (rows[:, c] for c in range(rows.shape[1]))
    if prev_tokens is not None:
        tok = resolve_row_tokens(tok, prev_tokens)
    return (tok, *cols)


def sample_greedy(logits):
    """int32 ``[G]``: the first maximum of each row of fp32 logits
    ``[G, vocab]``, which is what ``np.argmax`` of the same row gives
    the host.  It stays on the device for the next launch's
    ``resolve_row_tokens`` and is all a greedy step copies back."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def gathered_logits(x, gather, norm, head, also=()):
    """A step's epilogue up to its sampling: the CONSUMED rows of ``x``
    ``[T, hidden]`` gathered (``gather`` ``[G]``; None: every row)
    BEFORE the final norm and the vocabulary projection, so that the
    head matmul, the fp32 logits and what crosses to the host are sized
    to the gather and a prefill chunk's other rows exist for their cache
    writes alone.  ``norm`` is the model's final norm (with whatever it
    puts between the norm and the head), ``head`` its vocabulary
    projection.  Returns the fp32 logits ``[G, vocab]``; with ``also``
    (arrays of a row a packed row that the step returns after its
    tokens), ``(logits, *those gathered)``."""
    with jax.named_scope("lm_head"):
        if gather is not None:
            x = jnp.take(x, gather, axis=0)
            also = tuple(jnp.take(a, gather, axis=0) for a in also)
        logits = head(norm(x)).astype(jnp.float32)
    return (logits, *also) if also else logits


def snapshot_plan(snap, dst, trash: int):
    """The state snapshots a step takes, from its rows' ``dst`` and
    ``snap`` columns: ``(entries copied from, entries copied to)``, each
    ``[SNAPSHOTS_A_STEP]``; trash to trash where fewer rows name one."""
    with jax.named_scope("state_snapshot"):
        (at,) = jnp.nonzero(snap >= 0, size=SNAPSHOTS_A_STEP, fill_value=0)
        taken = snap[at] >= 0
        return (jnp.where(taken, dst[at], trash),
                jnp.where(taken, snap[at], trash))


def copy_snapshots(pool, plan):
    """``pool`` ``[entries, ...]`` of one state layer with the step's
    snapshots (``snapshot_plan``) copied: called once the layer has left
    its rows' states in their entries."""
    snap_from, snap_to = plan
    with jax.named_scope("state_snapshot"):
        return pool.at[snap_to].set(pool[snap_from])


@dataclasses.dataclass(frozen=True)
class PageKind:
    """One KIND of page of a model: the layers whose pools hold it and
    what those layers retain of a context, every position (``window``
    None) or the last ``window`` positions.  A kind has a pool size, an
    allocator and a page table ``[slots, pages_per_seq]`` of its own in
    the engine (``page_cache._KindPages``)."""
    name: str
    layers: tuple
    window: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """What a model tells the engine of what a sequence holds on the
    device and of its part of the unified step.  Every layer THAT HAS
    PAGES (a layer of some kind: all of them where ``kinds`` is empty)
    has TWO pools whose page holds a row a token (``rows``), and one
    page id names a page in each pool of every layer OF ONE KIND, so
    slots, tables, the allocators and the prefix cache never know what
    a page holds.

    ``kinds``: the kinds of page the model has (``PageKind``: window
    and full attention layers mixed), empty for ONE kind that every
    layer shares.  The engine holds, for each kind, a pool size, an
    allocator, a table ``[slots, pages_per_seq]`` and a budget
    (``page_cache._KindPages``).  The first kind retains every
    position; each further kind retains a window: it maps a block when
    a launch first writes into it and gives it back when the launch
    that last read it is committed, so a slot holds at most
    ``ContinuousBatchingEngine.window_bound`` of its pages whatever its
    context; the prefix cache holds a page of each kind a block and
    serves a hit as far as every kind is whole.

    ``rows``: the shape of one token's row in each of the two pools: K
    and V rows ``[kvh, d]``, laid out head-major as the paged kernels
    read them (``[pages, kvh, page, d]``, ``head_major``), or whatever
    the model keeps a token (a latent row and an index key, ``[pages,
    page, n]``).  The engine calls them ``k_pages`` and ``v_pages``
    whatever they hold, one pool a layer that has pages, in the layers'
    order.

    ``step``: the model's part of the engine step, ONE jitted function
    that runs a packed batch of token rows from many sequences through
    one forward: a decode slot's token, the k+1 tokens of a speculative
    verify window, the prompt tokens of a prefill chunk.  The contract,
    which every model's step keeps and states once, here:

    - ``step(params, k_pages, v_pages, rows, tables, cos_tab, sin_tab,
      self_cfg_id, pages_per_step, kv_scales=None, with_head=True,
      gather=None, prev_tokens=None)``, with ``state=`` where the layout
      has a ``state`` and ``pools=`` where it has ``more_pools`` (the
      engine passes the first seven by position, the rest by name).
      ``self_cfg_id`` (the config's id in ``models.generation``'s
      registry, whose rope tables are ``cos_tab`` / ``sin_tab``),
      ``pages_per_step`` (pages a turn of the kernels' page walk) and
      ``with_head`` are static.  ``k_pages``, ``v_pages``, ``state`` and
      ``pools`` are DONATED and written in place.  Everything is sized
      by ``rows.shape[0]``, a rung of the engine's ladder
      (``step_ladder``), but the results of the gathered rows, which
      are sized by ``gather``.
    - ``rows`` ``[T, columns]`` int32, by position: 0 the input token,
      or below zero a REFERENCE: ``-1 - g`` names entry ``g`` of
      ``prev_tokens``, the int32 ``[gather_cap]`` that the launch before
      this one sampled, which the host had not read when it enqueued
      this one (``resolve_row_tokens``); 1 the physical page the row's
      cache rows are written to (in the first kind of page); 2 the
      in-page offset; 3 the causal visibility, absolute position + 1;
      4 the slot, the row of ``tables`` (a tuple, a table ``[slots,
      pages_per_seq]`` a kind of page).  Then one column a FURTHER kind
      of page, the page the row writes there (5..).  Then, where the
      layout has a ``state``, three: the entry the row's slot starts
      from (below zero: zeros), the entry its state is left in, and, on
      a slot's last row, the entry a snapshot of that state is copied to
      (below zero: none; at most ``state_snapshots_a_step`` rows a
      launch name one).  A padding row carries slot -1, visibility 0,
      every kind's trash page and the state's trash entry (the last).
    - It returns ``(k_pages, v_pages, out)``, then the ``state`` pools
      if it took them, then ``pools`` if it took them.  ``out`` is
      ``(logits, tokens)``: the fp32 logits ``[G, vocab]`` of the rows
      ``gather`` names (``gathered_logits``) and their first maxima,
      int32 ``[G]`` (``sample_greedy``); then, if ``device_counts``
      names counts the step takes on the device, those as one int32
      array in that order; what a step appends after these, a row a
      gathered row, stays on the device until
      ``ContinuousBatchingEngine.last_extras`` is read (a model's block
      selection, for a check to hold).  With ``with_head=False`` (a
      draft model's mirror launch: the cache writes alone) ``out`` is
      None and nothing is gathered.
    - Its ``jax.named_scope``s are ``profiler.device_trace.DEVICE_SCOPES``,
      the SAME names in every layer, so that a layer's parts add up
      across layers in the device's time by scope; scopes are metadata
      and change nothing that is compiled.

    ``row_counts(rows, ctx_tokens, page_size, pages_per_seq)`` gives the
    step's counts the packed rows determine.  Both kinds of count ride
    on ``serving.step_counts`` and are summed in
    ``serving_stats()["steps"]`` under ``count_names``.
    ``pages_per_step(page_size, pages_per_seq, itemsize)`` is how many
    pages the step's kernels take a turn of their page walk, where the
    constructor is given no number.
    ``tile_rows``: the packed rows a tile of the step's kernels holds,
    where the step may be launched at any number of whole tiles: the
    engine then compiles it at a ladder of row counts
    (``step_ladder``).  0, the default, where the layout states none:
    its step is compiled at the capacity alone.

    ``more_pools``: a function a FURTHER pool beside the two, from the
    page size to the shape of ONE PAGE of it (a cache of compressed
    keys: ``page // 16`` rows of ``kvh * d``), in the cache's dtype.
    The same page id names a page in each, so tables, allocators and
    the prefix cache share them as they share K and V.  The engine
    holds a pool ``[pages, *shape]`` a layer that has pages for each
    (``ContinuousBatchingEngine.more_pools``); what a page of one
    holds, and when a row of it is final, is the step's business, but
    it has to depend on the tokens up to that page's end alone, since
    the prefix cache shares it under the page's id.

    ``state``: ``(shape, dtype)`` of each array of a SECOND SORT of
    state a slot has in ONE of ``state_layers`` layers (dtype None: the
    cache's; Mamba-2's per-sequence SSM state and conv tail):
    overwritten every token, so not paged, not addressed by position
    and not shareable by reference.  The engine holds, for each, one
    pool ``[entries, *shape]`` a state layer
    (``ContinuousBatchingEngine.state``): a slot's own entry is its
    number, then the snapshot entries, the last the trash entry for the
    padding rows.  The rows' three state columns (under ``step``) make
    admission, restore and recycling numbers in the one upload: no host
    copy, no launch of their own that the run-ahead would wait for, no
    second program (``snapshot_plan``, ``copy_snapshots``).  A stale
    row writes its slot's own entry, which the slot's next tenant never
    reads: it starts from zeros or a snapshot, in a later launch.

    What the engine can do with K/V pages of one kind alone (a draft
    model's mirror, an int8 cache, the host tier, the prefill-only
    handoff) refuses other pools, further pools, more kinds than one,
    and a recurrent state, at construction."""
    name: str
    rows: tuple
    head_major: bool = True
    step: Any = None
    row_counts: Any = None
    device_counts: tuple = ()
    count_names: tuple = ()             # row_counts' keys + device_counts
    pages_per_step: Any = None
    tile_rows: int = 0
    kinds: tuple = ()
    more_pools: tuple = ()
    state: tuple = ()
    state_layers: int = 0
    state_snapshots_a_step: int = 0

    def pool_shapes(self, num_pages: int, page_size: int):
        if self.head_major:
            return tuple((num_pages, r[0], page_size, *r[1:])
                         for r in self.rows)
        return tuple((num_pages, page_size, *r) for r in self.rows)


def ragged_kv_tokens_read(row_slot, row_lens, tile_rows: int, page: int,
                          max_pages: int) -> int:
    """K/V positions the ragged kernel's walk fetches for these packed
    rows in one layer: whole pages, each unit's slot as far as the
    unit's reach."""
    from ..ops.pallas.decode_attention import ragged_units

    _, reach = ragged_units(np.asarray(row_slot), np.asarray(row_lens),
                            tile_rows, np)
    pages = np.minimum(-(-reach // page), max_pages)
    return int(pages.sum()) * page


#: what a step counts on the device where a layer has experts, in the
#: order it returns them (``models.generation._moe_device_counts``):
#: token copies routed and those of an expert in the bank, the fullest
#: expert's rows, the experts that got at least one row (how much of the
#: bank a step streams) and the experts there are, both summed over the
#: expert layers
MOE_DEVICE_COUNTS = ("moe_rows_routed", "moe_rows_held",
                     "moe_expert_rows_max", "moe_experts_hit",
                     "moe_experts_total")
#: what the packed rows give where a kind retains a window
WINDOW_ROW_COUNTS = ("attn_row_ctx_window", "kv_ctx_tokens_window")
#: what the engine counts of a window kind's pages at a commit
WINDOW_PAGE_COUNTS = ("window_pages_live", "window_pages_recycled")
#: what the engine counts a call where sequences hold a recurrent state
#: (``PagedLayout.state``): snapshots the prefix cache holds, those this
#: call's commit gave it and those evicted since the last call; of the
#: requests this call admitted, the prompt tokens a snapshot restored
#: and those the cache's pages matched beyond the deepest snapshot,
#: which are prefilled again
STATE_COUNTS = ("state_snapshots_live", "state_snapshots_taken",
                "state_snapshots_evicted", "state_restored_tokens",
                "state_lost_tokens")


def step_ladder(decode_rows: int, prefill_budget: int,
                tile_rows: int = 1) -> tuple:
    """The row counts the engine's step is compiled at, ascending: the
    decode rows alone, a quarter and a half of the prefill budget above
    them, and the capacity, ``decode_rows + prefill_budget``; each
    rounded up to whole tiles of the step's kernels and held to the
    capacity.  A launch takes the smallest that holds its rows, so a
    step of decode rows alone does not compute a prefill chunk's
    padding: everything outside the kernels (embedding, norms,
    projections, the cache writes, the experts' dispatch) is XLA work at
    the launched size.  (Mistral's cell: 32, 96, 160, 288.)  With no
    tile stated (``tile_rows`` 0: ``PagedLayout``) the capacity alone."""
    cap = decode_rows + prefill_budget
    if not tile_rows:
        return (cap,)
    rungs = {min(cap, -(-(decode_rows + -(-prefill_budget * q // 4))
                        // tile_rows) * tile_rows) for q in (0, 1, 2)}
    return tuple(sorted(rungs | {cap}))
