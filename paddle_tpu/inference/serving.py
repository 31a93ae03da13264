"""Continuous-batching LLM serving engine over a paged cache: the
scheduler.

The capability the reference's block_multihead_attention signature exists
for (paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu;
Python entry python/paddle/incubate/nn/functional/
block_multihead_attention.py): a scheduler that ADMITS new prompts into a
RUNNING batch, grows sequences page by page, EVICTS finished ones and
reuses their pages.

This file holds the scheduler and nothing of any model.  It learns a
model from ``cfg.paged_layout()`` (``inference/paged_layout.py``: what a
sequence holds on the device and the contract of the model's step,
which lives in ``models/<name>.py``); the host side of the cache
(allocators, the radix prefix cache, a kind of page's tables) is
``inference/page_cache.py``.

The host owns what is cheap and branchy: slots, page tables, admission,
temperature sampling, speculative accept/reject, eviction.  The device
runs ONE step function a model, ``PagedLayout.step``: a packed batch of
token rows from many sequences through one forward, the greedy token of
every consumed row sampled at its end.  A row is a decode slot's token,
one of the k+1 tokens of a speculative verify window, or one prompt
token of a prefill chunk; at most ``prefill_token_budget`` prompt tokens
ride a step, so a decode slot emits a token EVERY step whatever prompt
is prefilled beside it.  The step's one free parameter is its number of
rows, read off its input, and where the layout states its kernels' row
tile (``PagedLayout.tile_rows``) the engine compiles it at a short
LADDER of row counts (``paged_layout.step_ladder``), all of them before
its first launch (``_padding_launches``), and launches each step at the
smallest rung that holds the rows it packed; nothing else about a
launch depends on the rung.

``engine.step()`` runs ONE STEP AHEAD of what it has read
(``_step_unified``).  With launch n enqueued by the call before, a call
is: admit (prefix-cache hits map shared full pages copy-on-write and
skip their prefill), pack launch n+1 from the SCHEDULED state
(``_schedule``: launch n counted as done; a slot that waited a call or
more with nothing launched is matched against the prefix cache again
before its first chunk is packed, ``_late_hit``), launch it, and only
then fetch launch n's tokens and commit them.  The one thing launch n+1
needs from launch n that the host does not know when it packs is one
token a decode row: that input is a REFERENCE into the tokens launch n
sampled, which never leave the device on their way
(``paged_layout.resolve_row_tokens``).  The device so always has a
launch queued, and a token reaches the host when its own device step
ends.  What is scheduled (a slot's position and budget as the packing
sees them) advances at launch; what is committed (``out_tokens``,
``cur_tok``, ``finished``, pages and slots freed, the prefix cache's
inserts, one a prefill chunk: the full pages of what the prompt has
committed, and with the last what a window kind and a recurrent state
add, the handoff record) changes at commit.  A slot that ends on
``eos_id``, or is canceled, with a row enqueued runs that row STALE: it
writes inside the slot's own pages, which are freed after it was
enqueued and so cannot be reused under it (a device runs its launches
in order), and its token is dropped.  Where the host has to see a step
before it can pack the next (a request with a temperature draws from
its own seeded numpy stream; a draft model's window is accepted or
rejected on the host: ``_host_samples``) the call reads and commits the
launch it just made: the engine decides that from what it is serving, a
step at a time, and nothing selects it from outside.

A layout with a recurrent state serves only layers with pages through
``k_pages`` and refuses what cannot carry state: a draft model, the
int8 cache, the host tier, ``prefill_only``, ``adopt_request`` and
``export_handoff``.  Weight-only int8 params
(models/generation.quantize_params_int8) run through the same program:
dequant fuses into the consumer dots.  An int8 K/V cache takes its
scales from ONE calibration pass over the first submitted prompt
(``_calibrate_int8_unified``: absmax per (layer, kv head), 2x headroom,
frozen); the step quantizes every row it scatters with them.

``cancel(rid)`` withdraws a request with no ``Finished`` record (the
fleet router's migration and retry primitive, inference/fleet.py);
``throttle()`` sheds work at run time (``speculative_k``,
``prefill_token_budget``) under the constructor's static shapes.

Measurement: every phase of ``_step_unified`` is a
``profiler.RecordEvent`` (``serving.step`` > ``serving.admit``,
``serving.propose``, ``serving.pack``, ``serving.launch``,
``serving.fetch_logits``, ``serving.commit``), so a profiler trace that
runs, whoever started it, holds them on the device's clock; one marker
a call (``serving.step_counts``: the counts of the launch the call
COMMITS, with ``ahead``, ``stale_rows`` and ``launch``, that launch's
serial, which ``serving.launch`` carries too where it is enqueued) and
one per request at admission, at a hit of its second look-up and at its
first token (``serving.admit_request``, ``serving.late_hit``,
``serving.first_token``) carry the counts.
The same counts are summed in ``serving_stats()["steps"]`` whether or
not anything traces.  A step's ``rows_cap`` count is the rows of the
program LAUNCHED (the rung); ``engine.rows_cap`` stays the capacity.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Any, Deque, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..profiler import RecordEvent
from .page_cache import PageAllocator, PrefixCache, _KindPages
from .paged_layout import (STATE_COUNTS, WINDOW_PAGE_COUNTS, PageKind,
                           step_ladder)
# benchmarks/tools/microbench_ragged_attention.py reads it under this
# module's name
from .paged_layout import ragged_kv_tokens_read  # noqa: F401


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [S] int32
    max_new_tokens: int
    temperature: float = 0.0            # 0 = greedy
    seed: int = 0                       # per-request sampling stream
    rng: Any = None                     # np.random.Generator at admission
    # time.perf_counter() at add_request and at admission (the queue
    # wait lies between them; admission to the first token is prefill)
    submitted: float = 0.0
    admitted: float = 0.0
    chunks: int = 0                     # prefill chunks launched so far


@dataclasses.dataclass
class Finished:
    rid: int
    tokens: np.ndarray                  # generated tokens (incl. first)
    prompt_len: int


@dataclasses.dataclass
class _Launch:
    """One enqueued step, as the host needs it to commit the step when
    its tokens come back: ``metas`` ``(kind, slot, first gathered row,
    rows)`` a scheduled slot, ``gathered`` ``(rid, position)`` a
    gathered row, the step's ``counts`` for ``serving.step_counts``,
    the draft's ``props``, ``out``, the program's third result, still
    on the device, and ``snaps``: by slot, the ``(blocks, entry)`` of the state snapshot
    the launch takes at the end of that slot's chunk."""
    metas: List[tuple]
    gathered: List[tuple]
    counts: Dict[str, int]
    props: Dict[int, tuple]
    out: Any = None
    snaps: Dict[int, tuple] = dataclasses.field(default_factory=dict)


def _softmax_np(logits: np.ndarray, temperature: float) -> np.ndarray:
    """Host-side fp64 softmax over one row of returned logits —
    deterministic (no device reduction-order variance), so a warm
    prefix-cache request replays the cold request's sampling stream
    bit-for-bit given the same seed."""
    x = logits.astype(np.float64) / max(float(temperature), 1e-6)
    x = x - x.max()
    e = np.exp(x)
    return e / e.sum()


class ContinuousBatchingEngine:
    """Continuous batching over a paged cache: the one ragged step.

    params/cfg: a model's functional state (models/generation.py weight
    naming; weight-only int8 dicts from quantize_params_int8 work
    unchanged).  ``max_slots`` bounds the in-flight batch; ``num_pages``
    x ``page_size`` is the shared pool of every layer, or, for a layout
    of several kinds of page (``PagedLayout.kinds``), a mapping from a
    kind's name to its pool's pages (an int gives every kind that many);
    ``prefill_token_budget`` is the most prompt tokens a step carries
    (256: what the ledger's Mistral cell is timed at).  ``pages_per_step``
    left unset is the layout's rule (``PagedLayout.pages_per_step``).
    ``state_snapshots``: for a model whose sequences hold a recurrent
    state (``PagedLayout.state``), the entries of the state pools that
    keep snapshots for the prefix cache, beside an entry a slot."""

    def __init__(self, cfg, params, max_slots: int = 8,
                 num_pages: int = 64, page_size: int = 128,
                 max_seq_len: Optional[int] = None, eos_id: int = -1,
                 cache_dtype=None, pages_per_step: Optional[int] = None,
                 prefill_token_budget: int = 256,
                 enable_prefix_cache: bool = False,
                 draft_params=None, draft_cfg=None,
                 speculative_k: int = 0,
                 prefill_only: bool = False,
                 host_tier_pages: int = 0,
                 state_snapshots: int = 0):
        from ..models.generation import _CFGS, register_config

        self.cfg = cfg
        self.params = params
        self.cfg_id = register_config(cfg)
        _, self.cos_tab, self.sin_tab = _CFGS[self.cfg_id]
        self.layout = cfg.paged_layout()
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len or cfg.max_position_embeddings)
        self.page_size = int(page_size)
        self.pages_per_seq = -(-self.max_seq_len // self.page_size)
        self.eos_id = int(eos_id)
        if prefill_token_budget is None or int(prefill_token_budget) < 1:
            raise ValueError(
                f"prefill_token_budget {prefill_token_budget!r}: the most "
                f"prompt tokens a step carries is an int >= 1")
        self.prefill_budget = int(prefill_token_budget)
        self._init_prefill_budget = self.prefill_budget

        L = cfg.num_hidden_layers
        dt = next(iter(v for k, v in params.items()
                       if not k.endswith("._scale"))).dtype
        if not jnp.issubdtype(dt, jnp.floating):
            dt = jnp.bfloat16              # int8-weight dicts: bf16 cache
        if cache_dtype is not None:
            dt = jnp.dtype(cache_dtype)
        self.cache_dtype = dt
        if pages_per_step is None:
            pages_per_step = self.layout.pages_per_step(
                self.page_size, self.pages_per_seq, jnp.dtype(dt).itemsize)
        self.pages_per_step = int(pages_per_step)
        kinds = self.layout.kinds or (PageKind("pages", tuple(range(L))),)
        if kinds[0].window is not None or any(k.window is None
                                              for k in kinds[1:]):
            raise ValueError("a layout's first kind of page retains every "
                             "position and each further kind a window")
        if self.layout.state and len(kinds) > 1:
            raise ValueError("a layout with a recurrent state has one kind "
                             "of page")
        if int(state_snapshots) and not (self.layout.state
                                         and enable_prefix_cache):
            raise ValueError("state_snapshots are what the prefix cache of "
                             "a layout with a recurrent state keeps")
        if self.layout.name != "kv" or len(kinds) > 1 or self.layout.state \
                or self.layout.more_pools:
            # the step is all that knows these pools (PagedLayout); what
            # follows moves, mirrors or calibrates K/V pages of ONE kind
            # and knows of no state beside them
            pools = (f"{self.layout.name} pools of {len(kinds)} kinds of page"
                     if len(kinds) > 1 else
                     f"{self.layout.name} pools beside a recurrent state"
                     if self.layout.state else
                     f"{self.layout.name} pools with further pools a page"
                     if self.layout.more_pools else
                     f"{self.layout.name} pools")
            for what, asked in (
                    ("a draft model: its mirror launches assume the "
                     "target's K/V geometry", draft_params is not None
                     or speculative_k),
                    ("an int8 cache: the scales are calibrated per KV head",
                     dt == jnp.int8),
                    ("the host tier: demotion copies K and V pages",
                     host_tier_pages),
                    ("prefill_only and the KV handoff: the wire format is "
                     "K and V pages", prefill_only)):
                if asked:
                    raise ValueError(f"{pools} do not support {what}")
        # int8 cache: frozen per-(layer, kv-head) scales, auto-calibrated
        # from the FIRST prefill's K/V absmax (2x headroom) — a single
        # self-consistent quant/dequant pair for the whole run (the
        # reference's static cachekv_quant mode; see incubate/nn/
        # decode_attention.py for the dynamic per-sequence contract)
        self.kv_scales = None
        # one _KindPages a kind of page: pool size, allocator, table.
        # The LAST physical page of a kind's pool is a reserved scribble
        # target: the static step's padding rows write their garbage
        # there instead of corrupting a live page
        sizes = (dict(num_pages) if isinstance(num_pages, dict)
                 else {k.name: num_pages for k in kinds})
        if set(sizes) != {k.name for k in kinds}:
            raise ValueError(f"num_pages {sorted(sizes)}: the layout's kinds "
                             f"of page are {[k.name for k in kinds]}")
        self.pages = tuple(
            _KindPages(k, sizes[k.name], self.max_slots, self.pages_per_seq,
                       None if k.window is None
                       else self.window_bound(k.window))
            for k in kinds)
        self.more_pages = self.pages[1:]    # the window kinds, if any
        # the FIRST kind's, under the names they have always had (a
        # layout of one kind has no other)
        first = self.pages[0]
        self.num_pages, self.trash_page = first.num_pages, first.trash
        self.alloc, self.tables = first.alloc, first.tables
        self.slot_pages = first.held
        # PER-LAYER pools: a layer's cache write is one direct scatter
        # into its own pool (a fused [L, ...] slab would cost a slice +
        # whole-layer dynamic-update per layer per step)
        # (a layer of no kind has no pools: ``k_pages`` goes by the
        # layers that have pages, in their order)
        kind_of = {i: kp for kp in self.pages for i in kp.kind.layers}
        shapes = [self.layout.pool_shapes(kind_of[i].num_pages,
                                          self.page_size)
                  for i in sorted(kind_of)]
        # (what the step takes from the device is COMMITTED to it from
        # the start, as every result of the step is: a program called
        # through ``kept_lowering`` is lowered again for an argument
        # that turns from uncommitted to committed)
        home = next(iter(jnp.zeros((), jnp.int32).devices()))
        self.k_pages = tuple(jnp.zeros(ka, dt, device=home)
                             for ka, _ in shapes)
        self.v_pages = tuple(jnp.zeros(vb, dt, device=home)
                             for _, vb in shapes)
        # the FURTHER pools of a page (``PagedLayout.more_pools``), one a
        # layer that has pages each, under the same page ids
        self.more_pools = tuple(
            tuple(jnp.zeros((kind_of[i].num_pages, *shape(self.page_size)),
                            dt, device=home) for i in sorted(kind_of))
            for shape in self.layout.more_pools)
        # the SECOND sort of state: a pool ``[entries, *shape]`` a state
        # layer for each array of a slot's recurrent state.  Entry s is
        # slot s's own, the next ``state_snapshots`` are snapshots (the
        # prefix cache's, by ``snap_alloc``), the last is the trash entry
        # that padding rows name.  ``state_src[s]`` is the entry slot
        # s's NEXT launch starts from: below zero (zeros) or a snapshot
        # for a slot just admitted, its own once a launch was packed
        self.state = None
        self.state_snapshots = int(state_snapshots)
        self.snap_alloc = PageAllocator(self.state_snapshots)
        self.state_src = np.full(self.max_slots, -1, np.int32)
        self.slot_snap_ref: Dict[int, int] = {}   # a slot's restore ref
        self.slot_snaps: Dict[int, List[tuple]] = {}  # taken, not inserted
        self._admitted_state = [0, 0]     # restored, lost since the marker
        self._snaps_seen = [0, 0]         # taken, evicted at the marker
        if self.layout.state:
            entries = self.max_slots + self.state_snapshots + 1
            self.state_trash = entries - 1
            self.state = tuple(
                tuple(jnp.zeros((entries, *shape), dt if sdt is None
                                else jnp.dtype(sdt), device=home)
                      for _ in range(self.layout.state_layers))
                for shape, sdt in self.layout.state)
        #: the packed rows' columns: 4 + one a kind of page + 3 of state
        self.row_cols = 4 + len(self.pages) + (3 if self.layout.state else 0)
        self.count_names = (*self.layout.count_names,
                            *(STATE_COUNTS if self.layout.state else ()))
        # host-side slot state
        self.seq_lens = np.zeros(self.max_slots, np.int32)
        self.active = np.zeros(self.max_slots, bool)
        self.cur_tok = np.zeros(self.max_slots, np.int32)
        self.budget = np.zeros(self.max_slots, np.int32)
        self.slot_rid = np.full(self.max_slots, -1, np.int64)
        self.out_tokens: Dict[int, List[int]] = {}
        self.prompt_lens: Dict[int, int] = {}
        self.queue: deque[Request] = deque()
        self._next_rid = 0
        self.finished: List[Finished] = []
        # the launch that is enqueued and not yet read (``_step_unified``
        # runs one step ahead), and the newest COMMITTED launch's
        # gathered rows and logits, still on the device (``last_logits``)
        self._flight: Optional[_Launch] = None
        self._last_logits: Optional[tuple] = None

        self.spec_k = int(speculative_k)
        if self.spec_k and draft_params is None:
            raise ValueError("speculative_k > 0 needs draft_params "
                             "(the small proposer model)")
        if draft_params is not None and not self.spec_k:
            raise ValueError(
                "draft_params without speculative_k >= 1: the draft "
                "would mirror every step without ever proposing")
        # ---- round-16 disaggregated serving (inference/disagg.py) ----
        # prefill_only: prompt-only ragged steps — a completed prompt
        # parks in ``handoff_ready`` (KV pages + first sampled token)
        # for the fleet's KV handoff instead of entering decode.
        self.prefill_only = bool(prefill_only)
        if self.prefill_only and self.spec_k:
            raise ValueError(
                "prefill_only excludes speculative decoding: a prefill "
                "replica never runs a verify window")
        # slot -> handoff record (kept until the router streams the KV
        # out or the request is canceled; pages stay reserved)
        self.handoff_ready: Dict[int, Dict[str, Any]] = {}
        self.host_tier_pages = int(host_tier_pages)
        if self.host_tier_pages > 0 and not enable_prefix_cache:
            raise ValueError(
                "host_tier_pages > 0 is a prefix-cache tier — enable "
                "the prefix cache")
        if self.host_tier_pages > 0 and draft_params is not None:
            raise ValueError(
                "the host-tier prefix cache does not compose with a "
                "draft model: demotion moves only the target's pools, "
                "so a promoted page's draft mirror would be stale")
        self.prefix_cache = (PrefixCache(
            self.page_size, self.alloc,
            host_tier_pages=self.host_tier_pages,
            demote_fn=(self._demote_page if self.host_tier_pages
                       else None),
            promote_fn=(self._promote_page if self.host_tier_pages
                        else None),
            windows=tuple((kp.alloc, kp.window) for kp in self.pages[1:]),
            snaps=self.snap_alloc if self.layout.state else None)
            if enable_prefix_cache else None)
        # static packed-row capacity of one launch: one decode row per
        # slot (k+1 under speculation) + the prefill chunk
        self.rows_cap = self.max_slots * (1 + self.spec_k) \
            + self.prefill_budget
        # static capacity of the CONSUMED-row gather (round-13): every
        # verify-window row + at most one chunk-final row per slot —
        # the head matmul, fp32 logits buffer and host transfer are
        # sized to this, not to rows_cap (a long prefill chunk's
        # intermediate rows never reach the host)
        self.gather_cap = self.max_slots * (1 + self.spec_k) \
            + self.max_slots
        # the row counts the step is compiled at (``step_ladder``): a
        # launch takes the smallest that holds its rows.  All of them
        # are compiled by the first launch (``_padding_launches``)
        self.ladder = step_ladder(self.max_slots * (1 + self.spec_k),
                                  self.prefill_budget,
                                  self.layout.tile_rows)
        self.launches_by_rows: Dict[int, int] = dict.fromkeys(self.ladder, 0)
        self._programs: Optional[Dict[int, Any]] = None   # the step, by rung
        # what the first launch resolves token references against (it
        # holds none): the same shape as a launch's sampled tokens
        # whatever the rung, so that launches of any two rungs chain
        self._no_tokens = jnp.zeros(self.gather_cap, jnp.int32, device=home)
        # runtime degradation floors: throttle() may shed work but
        # never grow past the constructor's static shapes
        self._init_spec_k = self.spec_k
        self.pending_prompt: Dict[int, np.ndarray] = {}
        self.prefill_order: List[int] = []       # FIFO over mid-prefill slots
        # with a prefix cache: slot -> the call (``step_totals["steps"]``)
        # that admitted it, until its first chunk is packed: such a slot
        # has written no page of its own and may be matched again
        # (``_late_hit``)
        self.unlaunched: Dict[int, int] = {}
        self.req_info: Dict[int, Request] = {}   # slot -> live request
        # per-rid prefill accounting (the FLOPs-skip contract: warm
        # requests must show prefilled == prompt_len - cached; run-scoped
        # by design — bench/tests sum it over the whole trace)
        self.prefill_stats: Dict[int, Dict[str, int]] = {}
        # what the steps did, summed over the engine's life
        # (serving_stats()["steps"]); the waits in whole microseconds,
        # as the spans' arguments carry them
        self.step_totals: Dict[str, int] = dict.fromkeys(
            ("steps", "rows", "rows_cap", "decode_rows", "prefill_rows",
             "ahead", "stale_rows", "admitted", "queue_wait_us",
             "queue_wait_us_max", "prefill_us", "prefill_us_max",
             *self.count_names), 0)
        # spec telemetry: one entry per verify window, bounded so a
        # long-running server doesn't grow it without limit
        self.accepted_lengths: Deque[int] = deque(maxlen=65536)
        self.draft = None
        if draft_params is not None:
            dcfg = draft_cfg if draft_cfg is not None else cfg
            did = register_config(dcfg)
            _, dcos, dsin = _CFGS[did]
            ddt = next(iter(v for k, v in draft_params.items()
                            if not k.endswith("._scale"))).dtype
            if not jnp.issubdtype(ddt, jnp.floating):
                ddt = jnp.bfloat16
            dL = dcfg.num_hidden_layers
            # draft pools mirror the target's page GEOMETRY (same ids,
            # same tables) so the one page table serves both models;
            # shared prefix pages are therefore shared for the draft
            # too (the donor's draft prefill wrote them)
            dlayout = dcfg.paged_layout()
            dka, dvb = dlayout.pool_shapes(self.num_pages, self.page_size)
            self.draft = {
                "cfg": dcfg, "params": draft_params, "cfg_id": did,
                "cos_tab": dcos, "sin_tab": dsin, "step": dlayout.step,
                "k_pages": tuple(jnp.zeros(dka, ddt) for _ in range(dL)),
                "v_pages": tuple(jnp.zeros(dvb, ddt) for _ in range(dL)),
            }

    # ---------------- device programs ----------------

    @partial(jax.jit, donate_argnums=(0, 1))
    def _set_page_jit(k_pages, v_pages, k, v, page):
        """Write ONE page's per-layer K/V ([L, kvh, page, d]) into the
        (donated) pools — the prefix-cache host-tier PROMOTE scatter."""
        L = len(k_pages)
        nk = tuple(k_pages[i].at[page].set(k[i].astype(k_pages[i].dtype))
                   for i in range(L))
        nv = tuple(v_pages[i].at[page].set(v[i].astype(v_pages[i].dtype))
                   for i in range(L))
        return nk, nv

    @partial(jax.jit, donate_argnums=(0, 1))
    def _adopt_pages_jit(k_pages, v_pages, k, v, pg):
        """Write an adopted handoff's per-layer page block
        ([L, npages, kvh, page, d]) into the (donated) pools at the
        destination page ids — one batched scatter per pool, the
        decode-side landing of the round-16 KV handoff."""
        L = len(k_pages)
        nk = tuple(k_pages[i].at[pg].set(k[i].astype(k_pages[i].dtype))
                   for i in range(L))
        nv = tuple(v_pages[i].at[pg].set(v[i].astype(v_pages[i].dtype))
                   for i in range(L))
        return nk, nv

    # ---- round-16 host-tier residency hooks (the prefix cache calls
    # these through its demote_fn/promote_fn; parallel/memory.py owns
    # the residency primitive) ----

    def _demote_page(self, page: int):
        """Gather one pool page's per-layer K/V to the pinned-host
        memory space and free the device page.  jax arrays are
        immutable, so the gathered copy is safe against later pool
        writes; the host placement degrades to identity on backends
        without memory kinds (the residency contract still exercises
        the same code path — parallel/memory.py's CPU rule)."""
        from ..parallel.memory import place_on_host

        pg = int(page)
        k = place_on_host(jnp.stack([kp[pg] for kp in self.k_pages]))
        v = place_on_host(jnp.stack([vp[pg] for vp in self.v_pages]))
        self.alloc.release([pg])
        return (k, v)

    def _promote_page(self, host_kv):
        """Inverse of ``_demote_page``: allocate a device page (demoting
        a colder page if the pool is full), fetch the host payload back
        and scatter it in.  Returns the page id at trie-refcount 1, or
        None when no device page could be found (the lookup then treats
        the node as a miss)."""
        from ..parallel.memory import place_on_device

        p = self.alloc.alloc()
        if p is None and self.prefix_cache is not None:
            # ancestors on the lookup path hold extra refs, so this can
            # never demote the chain being promoted
            self.prefix_cache.evict(1)
            p = self.alloc.alloc()
        if p is None:
            return None
        k, v = host_kv
        self.k_pages, self.v_pages = ContinuousBatchingEngine._set_page_jit(
            self.k_pages, self.v_pages, place_on_device(k),
            place_on_device(v), jnp.asarray(p, jnp.int32))
        return p

    # ---------------- host scheduler ----------------

    def add_request(self, prompt, max_new_tokens: int = 32, rid=None,
                    temperature: float = 0.0, seed: int = 0):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new_tokens} exceeds "
                f"max_seq_len {self.max_seq_len}")
        # prefill-only engines reserve prompt pages alone — decode-side
        # budget pages belong to the replica the KV hands off to
        reserve = len(prompt) + (0 if self.prefill_only
                                 else max_new_tokens)
        for kp in self.pages:
            claim = kp.claim_of(self._pages_needed(reserve))
            if claim > kp.alloc.total:
                raise ValueError(
                    f"request needs {claim} pages but the pool only has "
                    f"{kp.alloc.total} — it could never be admitted "
                    f"(head-of-line livelock)")
        if self.cache_dtype == jnp.int8 and self.kv_scales is None:
            # calibrate on the FIRST real prompt at SUBMISSION time —
            # outside any caller's step/heartbeat window, so the
            # calibration prefill's jit compile can never be mistaken
            # for a hung serving step (inference/fleet.py's watchdog)
            self._calibrate_int8_unified(prompt)
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        self.queue.append(Request(int(rid), prompt, int(max_new_tokens),
                                  float(temperature), int(seed),
                                  submitted=time.perf_counter()))
        return rid

    def _pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    def window_bound(self, window: int) -> int:
        """The most pages of a kind that retains ``window`` positions a
        slot holds at once, whatever its context.  With c positions
        committed, nothing under position ``c + 1 - window`` is read
        again (``_recycle`` gives those blocks back when the launch that
        last read them is committed), the launch in flight wrote a <=
        chunk positions from c on and the one being packed writes b <=
        chunk more (``_step_unified`` runs one launch ahead), chunk the
        constructor's ``prefill_token_budget``: the blocks held span
        positions ``c + 1 - window .. c + a + b - 1``, and a span of n
        positions touches at most ``(n - 2) // page + 2`` pages, which
        ``pages(window + 2 chunk) + 1`` never falls short of (17 at
        window 1024, chunk 512, pages of 128)."""
        return self._pages_needed(int(window)
                                  + 2 * self._init_prefill_budget) + 1

    def _release_slot(self, slot: int):
        """Return a slot's pages and clear its host state — the shared
        tail of normal completion (``_finish``) and withdrawal
        (``cancel``)."""
        for kp in self.pages:
            kp.release(slot)
        # the state entries the slot holds a reference on: the snapshot
        # it was to restore from, those its chunks took and the trie has
        # not been given, the one a chunk in flight is taking.  The
        # slot's OWN entry needs no release: its next tenant starts from
        # zeros or a snapshot, in a launch after any stale row's
        taken = [e for _, e in self.slot_snaps.pop(slot, ())]
        if slot in self.slot_snap_ref:
            taken.append(self.slot_snap_ref.pop(slot))
        if self._flight is not None and slot in self._flight.snaps:
            taken.append(self._flight.snaps.pop(slot)[1])
        self.snap_alloc.release(taken)
        self.state_src[slot] = -1
        if self._flight is not None:
            # rows already enqueued for this slot are STALE: they run
            # (the device executes launches in order, so they write this
            # slot's pages, and its state entry, before any later launch
            # can reuse them) and what they sample is never committed
            for m in self._flight.metas:
                if m[1] == slot:        # a slot has one entry a launch
                    self._flight.counts["stale_rows"] += m[3]
                    self._flight.metas.remove(m)
                    break
        self.active[slot] = False
        self.seq_lens[slot] = 0
        self.slot_rid[slot] = -1
        self.pending_prompt.pop(slot, None)
        self.unlaunched.pop(slot, None)
        if slot in self.prefill_order:
            self.prefill_order.remove(slot)
        self.req_info.pop(slot, None)
        self.handoff_ready.pop(slot, None)

    def _finish(self, slot: int):
        rid = int(self.slot_rid[slot])
        self.finished.append(Finished(rid,
                                      np.asarray(self.out_tokens.pop(rid),
                                                 np.int32),
                                      self.prompt_lens.pop(rid)))
        self._release_slot(slot)

    def cancel(self, rid: int) -> bool:
        """Withdraw a request WITHOUT recording a ``Finished`` entry —
        the fleet router's migration/retry path (the request replays
        elsewhere from its committed prefix, so completing it here would
        double-count it).  Queued requests leave the queue; an active
        request's slot releases its pages (prefix-cache refs on shared
        pages are the trie's own and survive).  Returns True when the
        rid was found."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                return True
        hit = np.nonzero(self.slot_rid == rid)[0]
        if len(hit):
            slot = int(hit[0])
            self.out_tokens.pop(rid, None)
            self.prompt_lens.pop(rid, None)
            self._release_slot(slot)
            return True
        return False

    def throttle(self, *, speculative_k=None, prefill_token_budget=None):
        """Runtime degradation knobs (the router's shed ladder).  Both
        only REDUCE work relative to the constructor's static shapes —
        ``rows_cap``/``gather_cap`` keep the spawn-time capacity, so a
        throttled engine reuses the compiled step (fewer live rows, no
        retrace) and can be restored to full service later."""
        if speculative_k is not None:
            k = int(speculative_k)
            if not 0 <= k <= self._init_spec_k:
                raise ValueError(
                    f"speculative_k {k} outside [0, {self._init_spec_k}] "
                    f"(the constructor's static verify-window capacity)")
            self.spec_k = k
        if prefill_token_budget is not None:
            b = int(prefill_token_budget)
            if not 1 <= b <= self._init_prefill_budget:
                raise ValueError(
                    f"prefill_token_budget {b} outside "
                    f"[1, {self._init_prefill_budget}] (the constructor's "
                    f"static chunk capacity)")
            self.prefill_budget = b

    # ---------------- round-16 KV handoff (disaggregated serving) ----

    def export_handoff(self, slot: int):
        """Gather a handoff-ready slot's committed KV to HOST and
        return ``(tree, meta)`` — the reshard-planner payload of the
        disaggregated KV handoff (inference/disagg.KVHandoffPlanner).

        ``tree`` is ``{"k", "v"}``, each ``[L, npages, kvh, page, d]``
        host numpy in the CACHE dtype — int8 pools export their int8
        pages (the round-15-precedented quantized-wire form: 1 byte per
        element on the handoff wire, bit-exact because no re-encode
        happens), float pools export bit-exact float pages.  ``meta``
        carries the scheduler state the decode side needs (first
        sampled token, committed length, frozen int8 scales).  Pages
        stay reserved until ``release_handoff``."""
        if self.layout.state:
            raise ValueError("the KV handoff's wire format is K and V "
                             "pages: it carries no recurrent state")
        info = self.handoff_ready[slot]
        npg = self._pages_needed(info["seq_len"])
        pg = jnp.asarray(np.asarray(self.slot_pages[slot][:npg],
                                    np.int32))
        tree = {
            "k": np.asarray(jnp.stack([kp[pg] for kp in self.k_pages])),
            "v": np.asarray(jnp.stack([vp[pg] for vp in self.v_pages])),
        }
        meta = dict(info, page_size=self.page_size,
                    cache_dtype=str(np.dtype(self.cache_dtype)))
        if self.kv_scales is not None:
            meta["kv_scales"] = {k: np.asarray(v)
                                 for k, v in self.kv_scales.items()}
        return tree, meta

    def release_handoff(self, slot: int) -> None:
        """Free a handed-off (or abandoned) prefill slot WITHOUT a
        Finished record — the request continues on the decode replica
        (or replays elsewhere); prefix-cache refs on shared pages are
        the trie's own and survive."""
        info = self.handoff_ready.pop(slot)
        self.prompt_lens.pop(info["rid"], None)
        self.out_tokens.pop(info["rid"], None)
        self._release_slot(slot)

    def can_adopt(self, seq_len: int, max_new_tokens: int) -> bool:
        """Capacity probe for a KV handoff: a free slot plus enough
        free (or prefix-evictable refcount-1) pages for the committed
        prefix and the generation budget.  The router gates the
        EXPENSIVE side of a handoff (page export + reshard stream) on
        this, so a no-capacity replica costs a parked slot, never a
        delivered-then-discarded payload.  Slightly optimistic for the
        classic (non-tiered) cache — interior trie pages free only as
        their chains drain — so ``adopt_request`` keeps its own None
        return as the authoritative answer."""
        if self.prefill_only or self.active.all():
            return False
        if int(seq_len) + int(max_new_tokens) > self.max_seq_len:
            return False
        need = self._pages_needed(int(seq_len) + int(max_new_tokens))
        avail = self.alloc.available
        if self.prefix_cache is not None:
            avail += sum(1 for n in self.prefix_cache._nodes()
                         if n.host_kv is None
                         and self.alloc.refs[n.page] == 1)
        return need <= avail

    def adopt_request(self, kv, meta, max_new_tokens: int, rid=None):
        """Decode-side landing of a KV handoff: allocate pages for the
        committed prefix PLUS the generation budget, scatter the
        delivered page block in, and enter the slot directly in DECODE
        state (seq_len = committed prefix, cur_tok = the prefill
        replica's first sampled token — already part of the stream, so
        ``out_tokens`` starts with it).  Frozen int8 K/V scales ride
        ``meta`` and install on a still-uncalibrated engine, keeping
        the fleet's quant/dequant pair single-sourced.  Returns the
        engine rid, or None when no slot/pages are free (the router's
        backpressure signal — retry next tick)."""
        if self.prefill_only:
            raise ValueError("adopt_request needs a decode-capable "
                             "engine")
        if self.layout.name != "kv" or len(self.pages) > 1 \
                or self.layout.state:
            raise ValueError(f"{self.layout.name} pools"
                             + (f" of {len(self.pages)} kinds of page"
                                if len(self.pages) > 1 else "")
                             + (" beside a recurrent state"
                                if self.layout.state else "")
                             + " do not support the KV handoff: the wire "
                             "format is K and V pages of one kind")
        plen = int(meta["seq_len"])
        first = int(meta["first_token"])
        if int(meta["page_size"]) != self.page_size:
            raise ValueError(
                f"handoff page_size {meta['page_size']} != this "
                f"engine's {self.page_size} — pools are incompatible")
        src_dtype = meta.get("cache_dtype")
        if (src_dtype is not None
                and np.dtype(src_dtype) != np.dtype(self.cache_dtype)):
            # a raw int8 payload astype'd into a float pool (or vice
            # versa) would be silently-wrong KV, not an error — refuse
            raise ValueError(
                f"handoff cache_dtype {src_dtype} != this engine's "
                f"{np.dtype(self.cache_dtype)} — pools are "
                f"incompatible")
        if plen + int(max_new_tokens) > self.max_seq_len:
            raise ValueError("adopted prefix + budget exceeds "
                             "max_seq_len")
        free = [s for s in range(self.max_slots) if not self.active[s]]
        if not free:
            return None
        need = self._pages_needed(plen + int(max_new_tokens))
        npg = int(np.shape(kv["k"])[1])
        if need > self.alloc.available and self.prefix_cache is not None:
            self.prefix_cache.evict(need - self.alloc.available)
        if need > self.alloc.available:
            return None
        scales = meta.get("kv_scales")
        if scales is not None:
            if self.kv_scales is None:
                self.kv_scales = {k: jnp.asarray(v)
                                  for k, v in scales.items()}
            elif any(not np.array_equal(np.asarray(self.kv_scales[k]),
                                        np.asarray(v))
                     for k, v in scales.items()):
                # int8 pages quantized under DIFFERENT frozen scales
                # would dequantize wrong — one fleet, ONE calibration
                # (DisaggRouter shares the first calibration fleet-wide;
                # this guard turns any leak past that into a loud error)
                raise ValueError(
                    "handoff kv_scales diverge from this engine's "
                    "frozen calibration — the fleet must share one "
                    "int8 K/V calibration")
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        slot = free[0]
        pages = [self.alloc.alloc() for _ in range(need)]
        self.slot_pages[slot] = pages
        self.tables[slot] = -1
        self.tables[slot, :need] = pages
        pg = jnp.asarray(np.asarray(pages[:npg], np.int32))
        self.k_pages, self.v_pages = \
            ContinuousBatchingEngine._adopt_pages_jit(
                self.k_pages, self.v_pages, jnp.asarray(kv["k"]),
                jnp.asarray(kv["v"]), pg)
        self.active[slot] = True
        self.seq_lens[slot] = plen
        self.cur_tok[slot] = first
        self.budget[slot] = int(max_new_tokens) - 1
        self.slot_rid[slot] = rid
        self.out_tokens[rid] = [first]
        self.prompt_lens[rid] = plen
        req = Request(int(rid), np.zeros(0, np.int32),
                      int(max_new_tokens),
                      temperature=float(meta.get("temperature", 0.0)),
                      seed=int(meta.get("seed", 0)))
        req.rng = np.random.default_rng(req.seed)
        if meta.get("rng_state") is not None:
            # resume the prefill side's seeded stream mid-state: the
            # handoff carries the PRNG exactly as the KV pages carry
            # the committed prefix (seeded-sampling parity across the
            # handoff is pinned in tests/test_serving_disagg.py)
            req.rng.bit_generator.state = meta["rng_state"]
        self.req_info[slot] = req
        if self.budget[slot] <= 0 or first == self.eos_id:
            self._finish(slot)
        return rid

    def _calibrate_int8_unified(self, prompt) -> None:
        """One-shot K/V scale calibration: a dense prefill of the FIRST
        submitted prompt, absmax per (layer, kv head) over its real
        tokens with 2x headroom, frozen as quant/dequant pairs.  That
        prefill's K/V are DISCARDED: the step prefills the prompt again
        through its own quantized scatter, so the cache holds one
        self-consistent int8 stream."""
        s = len(prompt)
        bucket = max(16, 1 << (s - 1).bit_length())
        ids = np.zeros(bucket, np.int32)
        ids[:s] = prompt
        ks, vs = self.cfg.calibration_prefill(
            self.params, jnp.asarray(ids), self.cos_tab, self.sin_tab,
            self.cfg_id, bucket)
        kabs = jnp.max(jnp.abs(ks[:, :s].astype(jnp.float32)),
                       axis=(1, 3)) * 2.0 + 1e-6          # [L, kvh]
        vabs = jnp.max(jnp.abs(vs[:, :s].astype(jnp.float32)),
                       axis=(1, 3)) * 2.0 + 1e-6
        self.kv_scales = {"kq": 127.0 / kabs, "kdq": kabs / 127.0,
                          "vq": 127.0 / vabs, "vdq": vabs / 127.0}

    # ---------------- the step ----------------
    #
    # One ragged launch per engine step serves THREE request phases at
    # once: decode slots (one row each), prompt-prefill chunks (up to
    # ``prefill_token_budget`` rows, split across one or more admitted
    # requests), and speculative verify windows (k+1 rows per slot).
    # Admission walks the radix prefix cache first, so chat-shaped
    # traffic with a shared system prompt maps the shared full pages
    # copy-on-write and prefills only its private suffix.

    def _phys(self, slot: int, pos: int) -> int:
        """Physical page holding ``pos`` of ``slot``'s sequence (pages
        are reserved through prompt+max_new at admission, so a write
        position past the table is a scheduler bug, not pool pressure)."""
        page = int(self.tables[slot, pos // self.page_size])
        if page < 0:
            raise AssertionError(
                f"slot {slot} writing position {pos} past its reserved "
                f"pages — admission under-reserved")
        return page

    def _admit_unified(self) -> List[tuple]:
        """Admit queued prompts into free slots.  NO prefill runs here:
        the prompt enters the pending queue and is consumed
        ``prefill_token_budget`` tokens per step by the launch, so a
        long prompt never stalls in-flight decode slots.  Full prompt +
        generation budget is reserved up front (no mid-flight OOM).
        Prefix-cache hits map the shared full pages into the new table
        (copy-on-write: the request only ever writes at or past its
        private suffix) and skip their prefill entirely (``_map_hit``).
        The look-up is made again, once, for a slot that then WAITS a
        call or more before its first chunk is packed (``_late_hit``):
        what it waits behind may be the prefill of its own prefix."""
        admitted = []
        if (self.cache_dtype == jnp.int8 and self.kv_scales is None
                and self.queue):
            # normally already calibrated at add_request; kept as a
            # safety net for scales dropped after submission
            self._calibrate_int8_unified(self.queue[0].prompt)
        free_slots = [s for s in range(self.max_slots)
                      if not self.active[s]]
        si = 0
        while self.queue and si < len(free_slots):
            req = self.queue[0]
            plen = len(req.prompt)
            need = self._pages_needed(
                plen if self.prefill_only else plen + req.max_new_tokens)
            shared: List[List[int]] = [[] for _ in self.pages]
            matched, snap, lost = 0, None, 0
            if self.prefix_cache is not None:
                # a hit is worth what BOTH sorts of state cover of it:
                # pages and the deepest snapshot of the recurrent state
                shared, matched, snap, lost = self.prefix_cache.lookup_all(
                    req.prompt)
            if not self._reserve(need, shared):
                self._release_hit(shared, snap)   # aborted hit: refs back
                break               # head-of-line waits for pages
            self.queue.popleft()
            slot = free_slots[si]
            si += 1
            for kp in self.pages:
                kp.claim[slot] = kp.claim_of(need)
            self.active[slot] = True
            self.cur_tok[slot] = 0
            self.budget[slot] = req.max_new_tokens
            self.slot_rid[slot] = req.rid
            self.prefill_order.append(slot)
            if self.prefix_cache is not None:
                self.unlaunched[slot] = self.step_totals["steps"]
            req.rng = np.random.default_rng(req.seed)
            self.req_info[slot] = req
            self.prompt_lens[req.rid] = plen
            self.prefill_stats[req.rid] = {"prompt_len": plen,
                                           "cached_tokens": 0,
                                           "prefilled": 0}
            state_args = self._map_hit(slot, shared, matched, snap, lost)
            admitted.append((slot, plen))
            req.admitted = time.perf_counter()
            wait_us = int((req.admitted - req.submitted) * 1e6)
            tot = self.step_totals
            tot["admitted"] += 1
            tot["queue_wait_us"] += wait_us
            tot["queue_wait_us_max"] = max(tot["queue_wait_us_max"], wait_us)
            with RecordEvent("serving.admit_request", rid=req.rid,
                             queue_wait_us=wait_us, prompt_len=plen,
                             cached_tokens=matched, **state_args):
                pass
        return admitted

    def _release_hit(self, shared, snap) -> None:
        """Give back what a look-up acquired and nobody mapped."""
        for kp, pages in zip(self.pages, shared):
            kp.alloc.release(pages)
        if snap is not None:
            self.snap_alloc.release([snap])

    def _map_hit(self, slot: int, shared, matched: int, snap, lost: int,
                 before: Optional[int] = None) -> Dict[str, int]:
        """Map a look-up's hit (``PrefixCache.lookup_all``'s four) into
        ``slot``, whose claims are made: the tables and
        held pages of every kind, the entry the slot's first launch
        starts its state from, where its prefill begins and what is left
        of its prompt, and the hit's accounting.  Used by the admission
        and, for a slot that has launched nothing (no page of its own
        written), by the second look-up (``_late_hit``), which says what
        the admission had matched (``before``): there the slot gives
        back what it held for the blocks now shared, its private pages
        and the references of its admission's hit alike.  Returns the
        state's arguments of the request's markers."""
        req = self.req_info[slot]
        stats = self.prefill_stats[req.rid]
        for kp, pages in zip(self.pages, shared):
            old = [] if before is None else kp.held[slot]
            kp.tables[slot] = -1
            if kp.window is None:
                # every position is retained: the whole context's pages
                # now (no mid-flight OOM); a slot mapped before keeps
                # its private pages of the blocks the hit leaves it
                kp.lo[slot] = 0
                kp.held[slot] = list(pages) + (
                    [kp.alloc.alloc()
                     for _ in range(kp.claim[slot] - len(pages))]
                    if before is None else old[len(pages):])
                kp.alloc.release(old[:len(pages)])
            else:
                # the hit's pages of the blocks its rows will read;
                # every later block is mapped when a launch first
                # writes into it (``_map_pages``)
                kp.lo[slot] = matched // self.page_size - len(pages)
                kp.held[slot] = list(pages)
                kp.tables[slot, :kp.lo[slot]] = kp.trash
                kp.alloc.release(old)
            lo = kp.lo[slot]
            kp.tables[slot, lo:lo + len(kp.held[slot])] = kp.held[slot]
        state_args = {}
        if self.layout.state:
            # the slot's first launch starts from the snapshot (the
            # slot holds its reference until that launch is
            # committed) or from zeros: no copy, no launch of its own
            if slot in self.slot_snap_ref:
                self.snap_alloc.release([self.slot_snap_ref.pop(slot)])
            self.state_src[slot] = -1
            if snap is not None:
                self.state_src[slot] = self.max_slots + snap
                self.slot_snap_ref[slot] = snap
            self._admitted_state[0] += matched - (before or 0)
            self._admitted_state[1] += lost - stats.get(
                "state_lost_tokens", 0)
            # state_entry: where the request's state lives (its
            # slot's own entry), and stays once it has ended until
            # the slot's next tenant's first launch
            state_args = {"state_restored_tokens": matched,
                          "state_lost_tokens": lost,
                          "state_entry": slot}
        self.seq_lens[slot] = matched
        self.pending_prompt[slot] = np.asarray(req.prompt[matched:], np.int32)
        stats.update(cached_tokens=matched, **state_args)
        if self.prefix_cache is not None:
            self.prefix_cache.record_hit(matched, before)
        return state_args

    def _late_hit(self, slot: int) -> None:
        """The look-up again, for a slot that waited: admitted by an
        earlier call, nothing launched yet, its first chunk about to be
        packed.  What it waited behind may have been the prefill of its
        own prefix (a document asked again while its first ask
        prefills), whose full pages the trie has taken chunk by chunk
        since the admission looked.  ``probe`` (no references, no LRU
        change) says whether the trie is deeper than the slot's match;
        only then is the prompt looked up again and, if the hit that
        can be served is longer, mapped anew (``_map_hit``)."""
        req, pc = self.req_info[slot], self.prefix_cache
        matched = int(self.seq_lens[slot])
        if pc.probe(req.prompt) <= matched:
            return
        shared, deeper, snap, lost = pc.lookup_all(req.prompt)
        if deeper <= matched:
            # the blocks are there and not restorable (a window kind's
            # pages, a snapshot: they come with the prompt's last insert)
            self._release_hit(shared, snap)
            return
        state_args = self._map_hit(slot, shared, deeper, snap, lost,
                                   before=matched)
        with RecordEvent("serving.late_hit", rid=req.rid,
                         cached_tokens=deeper, waited_us=int(
                             (time.perf_counter() - req.admitted) * 1e6),
                         **state_args):
            pass

    def _reserve(self, need: int, shared) -> bool:
        """Whether every kind of page can take a request of ``need``
        blocks of which the prefix cache gave ``shared[k]``, evicting
        from the cache where that helps.  A kind that retains every
        position needs the blocks it does not share FREE now; a window
        kind needs room for the request's claim beside the live slots'
        claims (``_KindPages``)."""
        for kp, pages in zip(self.pages, shared):
            if kp.window is not None:
                if sum(kp.claim.values()) + kp.claim_of(need) \
                        > kp.alloc.total:
                    return False
                continue
            short = need - len(pages) - kp.alloc.available
            if short > 0 and self.prefix_cache is not None:
                self.prefix_cache.evict(short)
            if need - len(pages) > kp.alloc.available:
                return False
        return True

    def _map_pages(self, slot: int, end: int) -> None:
        """Give ``slot`` a page of every window kind for each block up
        to the one holding position ``end - 1`` (a launch is about to
        write there).  A page is there to be had: see ``_KindPages``."""
        for k, kp in enumerate(self.more_pages):
            held = kp.held[slot]
            while (kp.lo[slot] + len(held)) * self.page_size < end:
                page = kp.alloc.alloc()
                if page is None and self.prefix_cache is not None:
                    # the cache gives up pages of this kind alone; a few
                    # dozen a time, so that its walk is rare
                    self.prefix_cache.evict_window(k, 4 * self.max_slots)
                    page = kp.alloc.alloc()
                if page is None:
                    raise AssertionError(
                        f"no page of kind {kp.kind.name!r} for slot {slot}: "
                        f"the live slots' claims passed the pool")
                kp.tables[slot, kp.lo[slot] + len(held)] = page
                held.append(page)
                if len(held) > kp.claim[slot]:
                    raise AssertionError(
                        f"slot {slot} holds {len(held)} pages of kind "
                        f"{kp.kind.name!r}, its claim is {kp.claim[slot]}")

    def _recycle(self, slot: int) -> None:
        """Give back ``slot``'s blocks of every window kind that no row
        to come can read: with ``seq_lens[slot]`` positions committed a
        row's visibility is at least one more, so nothing under
        ``seq_lens + 1 - window`` is read again, by the launch in flight
        (packed from at least this length) or by any later one.  Called
        when a launch is COMMITTED, so the launch that last read a block
        has run; the table entry becomes the trash page."""
        for kp in self.more_pages:
            keep = max(0, int(self.seq_lens[slot]) + 1 - kp.window) \
                // self.page_size
            n = min(keep - kp.lo[slot], len(kp.held[slot]))
            if n > 0:
                kp.alloc.release(kp.held[slot][:n])
                del kp.held[slot][:n]
                kp.tables[slot, kp.lo[slot]:kp.lo[slot] + n] = kp.trash
                kp.lo[slot] += n
                kp.recycled += n

    def _sample_row(self, logits_row: np.ndarray, req: Request) -> int:
        """Draw the next token of a request with a temperature from one
        returned logits row and the request's seeded stream.  (A greedy
        row's token is the device's ``sample_greedy``: the host never
        sees its logits.)"""
        p = _softmax_np(logits_row, req.temperature)
        return int(req.rng.choice(len(p), p=p))

    def _draft_launch(self, rows_np: np.ndarray, need_logits: bool = True):
        """One draft-model launch over a packed row schedule; returns
        host logits.  The draft pools mirror the target's page geometry
        so the SAME rows/tables drive both models.  ``need_logits=False``
        (the cache-mirror call) compiles a head-less variant of the step
        (no vocab projection, no logits buffer) and skips the
        device-to-host copy — the mirror only needs the K/V scatter."""
        d = self.draft
        d["k_pages"], d["v_pages"], out = d["step"](
            d["params"], d["k_pages"], d["v_pages"],
            jnp.asarray(rows_np), (jnp.asarray(self.tables),),
            d["cos_tab"], d["sin_tab"], self_cfg_id=d["cfg_id"],
            pages_per_step=self.pages_per_step, with_head=need_logits)
        return np.asarray(out[0]) if need_logits else None

    def _propose(self, decoding: List[int]) -> Dict[int, tuple]:
        """Draft-model proposals: up to ``spec_k`` tokens per decoding
        slot, one batched draft launch per proposal depth (the draft's
        K/V for each proposed token is scattered by its own launch, so
        proposal j+1 attends proposal j).  Returns
        slot -> (draft_tokens, draft_prob_rows) — prob rows are None
        under greedy (exact prefix-match acceptance needs no q)."""
        props: Dict[int, tuple] = {}
        keff: Dict[int, int] = {}
        for s in decoding:
            cap = len(self.slot_pages[s]) * self.page_size
            keff[s] = max(0, min(self.spec_k,
                                 int(self.budget[s]) - 1,
                                 cap - int(self.seq_lens[s]) - 1))
            props[s] = ([], [])
        for j in range(max(keff.values(), default=0)):
            rows = np.zeros((self.max_slots, 5), np.int32)
            rows[:, 1] = self.trash_page
            rows[:, 4] = -1
            live = []
            for s in decoding:
                if keff[s] <= j:
                    continue
                tok = (int(self.cur_tok[s]) if j == 0
                       else props[s][0][j - 1])
                p = int(self.seq_lens[s]) + j
                rows[s] = (tok, self._phys(s, p), p % self.page_size,
                           p + 1, s)
                live.append(s)
            if not live:
                break
            logits = self._draft_launch(rows)
            for s in live:
                req = self.req_info[s]
                if req.temperature <= 0:
                    props[s][0].append(int(np.argmax(logits[s])))
                    props[s][1].append(None)
                else:
                    q = _softmax_np(logits[s], req.temperature)
                    props[s][0].append(int(req.rng.choice(len(q), p=q)))
                    props[s][1].append(q)
        return props

    def _commit_window(self, slot: int, start: int, n: int,
                       tokens: np.ndarray, logits: Optional[np.ndarray],
                       prop) -> List[int]:
        """Accept/reject one slot's verify window (rows ``start`` ..
        ``start+n-1``; window inputs were [cur_tok, d_1..d_{n-1}]) and
        commit the emitted tokens.  Greedy targets use exact
        prefix-match acceptance against the device's ``tokens``;
        temperature>0 uses standard rejection sampling over the
        ``logits`` (accept d with prob min(1, p(d)/q(d)), resample the
        first rejection from max(p-q, 0)).  n == 1 (no draft tokens)
        degenerates to plain decode.  Returns the emitted tokens."""
        req = self.req_info[slot]
        rid = int(self.slot_rid[slot])
        drafts = prop[0] if prop else []
        qrows = prop[1] if prop else []
        emitted: List[int] = []
        if req.temperature <= 0:
            for j in range(n - 1):
                t = int(tokens[start + j])
                emitted.append(t)
                if drafts[j] != t:
                    break
            else:
                emitted.append(int(tokens[start + n - 1]))
        else:
            rng = req.rng
            for j in range(n - 1):
                p = _softmax_np(logits[start + j], req.temperature)
                d = drafts[j]
                q = qrows[j]
                if rng.random() < min(1.0, p[d] / max(q[d], 1e-30)):
                    emitted.append(d)
                else:
                    resid = np.maximum(p - q, 0.0)
                    tot = resid.sum()
                    tok = (int(np.argmax(p)) if tot <= 0
                           else int(rng.choice(len(p), p=resid / tot)))
                    emitted.append(tok)
                    break
            else:
                p = _softmax_np(logits[start + n - 1], req.temperature)
                emitted.append(int(rng.choice(len(p), p=p)))
        if n > 1:
            self.accepted_lengths.append(len(emitted))
        take: List[int] = []
        for t in emitted:
            take.append(t)
            if t == self.eos_id:
                break
        for t in take:
            self.out_tokens[rid].append(t)
        # window rows committed K/V for positions len..len+len(take)-1
        # (inputs cur_tok, d_1..); positions past the accepted prefix
        # hold rejected-draft garbage ABOVE the new length — invisible
        # (visibility is bounded by lens) and overwritten by later steps
        self.seq_lens[slot] += len(take)
        self.cur_tok[slot] = take[-1]
        self.budget[slot] -= len(take)
        if self.budget[slot] <= 0 or take[-1] == self.eos_id:
            self._finish(slot)
        return take

    def _host_samples(self) -> bool:
        """Whether the host has to see the next launch's results before
        it can pack the one after it: a draft model's verify windows
        (how many tokens a window emits is decided by accept/reject on
        the host) or a scheduled request with a temperature (it draws
        from its own seeded numpy stream, which migrates with a
        handoff).  Such a step is read and committed in the call that
        launches it; every other step runs one step ahead."""
        return self.draft is not None or any(
            req.temperature > 0 for s, req in self.req_info.items()
            if s not in self.handoff_ready)

    def _schedule(self):
        """What the next launch may carry, with the launch in flight (if
        there is one) counted as done, because the device runs launches
        in order: ``(decode, prefill)``.  ``decode`` holds ``(slot,
        position, input token)`` a decoding slot; the token is
        ``cur_tok`` where the host has read it, and else ``-1 - g``, a
        reference to gathered row ``g`` of the launch in flight (a
        decode row there, or the final row of the slot's prompt).  A
        slot whose budget the launch in flight exhausts is left out;
        one that will end on ``eos_id`` is not known yet and rides one
        stale row.  ``prefill`` holds ``(slot, position, prompt tokens
        not launched yet)`` in admission order."""
        flying = {}
        if self._flight is not None:
            for kind, s, g, n in self._flight.metas:
                last = kind == "verify" or n == len(self.pending_prompt[s])
                flying[s] = (n, g if last else None)
        decode, prefill = [], []
        for s in range(self.max_slots):
            if not self.active[s] or s in self.handoff_ready:
                continue
            n, ref = flying.get(s, (0, None))
            if s in self.pending_prompt and (ref is None
                                             or self.prefill_only):
                continue        # prefilling still, or parks at commit
            if self.budget[s] - (ref is not None) <= 0:
                continue        # the launch in flight emits its last
            decode.append((s, int(self.seq_lens[s]) + n,
                           int(self.cur_tok[s]) if ref is None
                           else -1 - ref))
        for s in self.prefill_order:
            n, ref = flying.get(s, (0, None))
            if ref is None:
                prefill.append((s, int(self.seq_lens[s]) + n,
                                self.pending_prompt[s][n:]))
        return decode, prefill

    def _step_unified(self) -> int:
        """One engine step, one step AHEAD of the device's results.
        With launch n enqueued by the call before: admit, pack launch
        n+1 from the SCHEDULED state (``_schedule``), launch it, and
        only then fetch launch n's sampled tokens (the host blocks while
        the device runs n, with n+1 queued behind it) and commit n.  A
        token so reaches the host as soon as its own device step ends,
        the device never waits for the host between two steps, and a
        request sent between two calls is admitted by the next one.
        Slots, pages, ``out_tokens``, ``finished`` and the prefix
        cache change at COMMIT (the cache at every prefill chunk's: the
        full pages of launch n's chunks are in the trie once n's tokens
        are back, so the packing of launch n+2 may map them into a slot
        that waited, ``_late_hit``); a slot's position and budget as
        the packing sees them advance at LAUNCH.

        With nothing in flight (the first call, after an idle spell)
        the call enqueues two launches and commits the first.  Where
        the host must see a launch's results before the next can be
        packed (``_host_samples``) it enqueues one at most and commits
        it in the same call: the order of work before PR 29.  Decode
        slots emit at least one token EVERY call regardless of any
        co-scheduled prompt's length: that is the latency contract
        chunked prefill exists for.

        Each phase is a span under ``serving.step``; the call's ONE
        ``serving.step_counts`` marker carries the counts of the launch
        the call COMMITS, whose device work ran while the host was
        inside this call (an annotation takes its arguments when it
        opens, so the phase spans carry none)."""
        tot = self.step_totals
        tot["steps"] += 1
        with RecordEvent("serving.step", step=tot["steps"]):
            with RecordEvent("serving.admit"):
                admitted = self._admit_unified()
            deep = 1 if self._host_samples() else 2
            queued = [] if self._flight is None else [self._flight]
            idle = None
            while len(queued) < deep:
                self._flight = queued[-1] if queued else None
                decode, prefill = self._schedule()
                props = {}
                if self.draft is not None and self.spec_k > 0 and decode:
                    with RecordEvent("serving.propose"):
                        props = self._propose([s for s, _, _ in decode])
                with RecordEvent("serving.pack"):
                    rows, gather, new = self._pack_unified(decode, prefill,
                                                           props)
                if not new.counts["rows"]:
                    idle = new
                    break
                # 1: enqueued before the launch before it was read
                new.counts["ahead"] = len(queued)
                # the serial rides on the span that enqueues the launch
                # and on the marker of the call that commits it, so that
                # a reader of the device's trace joins the k-th launch
                # the device ran to both (profiler/device_trace.py)
                with RecordEvent("serving.launch",
                                 launch=new.counts["launch"]):
                    launches = [(rows, gather, queued[-1].out[1] if queued
                                 else self._no_tokens)]
                    if self._programs is None:
                        # the first launch of the engine's life: before
                        # it, every rung once over padding rows, so that
                        # each is compiled here and no launch ever
                        # compiles again
                        self._programs = self._step_programs()
                        launches = [*self._padding_launches(), *launches]
                    # called from HERE, not from a helper, the ladder's
                    # first launches too: a tier-1 test holds the call
                    # where it is (PERF.md section 6, PRs 24 and 36).
                    # The tables are COPIED: a commit changes them while
                    # this launch may not have taken them yet
                    for l_rows, l_gather, l_prev in launches:
                        self.k_pages, self.v_pages, new.out, *more = \
                            self._programs[len(l_rows)](
                                self.params, self.k_pages, self.v_pages,
                                jnp.asarray(l_rows),
                                tuple(jnp.asarray(kp.tables.copy())
                                      for kp in self.pages),
                                self.cos_tab, self.sin_tab,
                                kv_scales=self.kv_scales,
                                gather=jnp.asarray(l_gather),
                                prev_tokens=l_prev,
                                **self._more_arguments())
                        if self.layout.state:
                            self.state = more.pop(0)
                        if self.layout.more_pools:
                            self.more_pools = more.pop(0)
                        if self.draft is not None:
                            # mirror the SAME rows through the draft: its
                            # paged cache tracks the target's committed
                            # stream (prefill chunks included), so the
                            # next proposal round starts in sync:
                            # rejected-draft positions land above the
                            # rolled-back length, exactly like the
                            # target's own window writes
                            self._draft_launch(l_rows, need_logits=False)
                self.launches_by_rows[len(rows)] += 1
                queued.append(new)
            cur = queued[0] if queued else None
            self._flight = queued[1] if len(queued) > 1 else None
            n_finished = len(self.finished)
            produced = 0
            if cur is not None:
                with RecordEvent("serving.fetch_logits"):
                    # the host blocks here until the device has run
                    # launch ``cur``, then copies its tokens back (the
                    # span keeps its name: the benchmark reads it); the
                    # logits only if a slot of it draws from them
                    tokens = np.asarray(cur.out[1])
                    if self.layout.device_counts:
                        cur.counts.update(zip(
                            self.layout.device_counts,
                            (int(v) for v in np.asarray(cur.out[2]))))
                    logits = None
                    if any(self.req_info[m[1]].temperature > 0
                           for m in cur.metas):
                        logits = np.asarray(cur.out[0])
                self._last_logits = (cur.gathered, *cur.out)
                with RecordEvent("serving.commit"):
                    produced = self._commit_unified(cur, tokens, logits)
            else:
                cur = idle
            counts = cur.counts
            windows = self.more_pages
            if windows:
                # a window kind's pages the slots hold now, and those
                # this call's commit gave back
                counts.update(zip(WINDOW_PAGE_COUNTS, (
                    sum(len(h) for kp in windows for h in kp.held.values()),
                    sum(kp.recycled for kp in windows))))
                for kp in windows:
                    kp.recycled = 0
            if self.layout.state:
                pc = self.prefix_cache
                now = [pc.snapshots_taken, pc.evicted_snapshots] \
                    if pc is not None else [0, 0]
                counts.update(zip(STATE_COUNTS, (
                    pc.snapshots_live if pc is not None else 0,
                    now[0] - self._snaps_seen[0],
                    now[1] - self._snaps_seen[1], *self._admitted_state)))
                self._snaps_seen, self._admitted_state = now, [0, 0]
            for k in ("rows", "rows_cap", "decode_rows", "prefill_rows",
                      "ahead", "stale_rows"):
                tot[k] += counts[k]
            for k in self.count_names:
                if k.endswith("_max"):
                    tot[k] = max(tot[k], counts.get(k, 0))
                else:
                    tot[k] += counts.get(k, 0)
            with RecordEvent(
                    "serving.step_counts", step=tot["steps"],
                    admitted=len(admitted), queued=len(self.queue),
                    free_pages=self.alloc.available,
                    **{f"free_pages_{kp.kind.name}": kp.alloc.available
                       for kp in windows},
                    prefill_backlog=sum(
                        len(p) for p in self.pending_prompt.values()),
                    produced=produced,
                    finished=len(self.finished) - n_finished, **counts):
                pass
        return produced

    def _padding_rows(self, n: int) -> np.ndarray:
        """``n`` packed rows that belong to no slot: they write the trash
        page of every kind and the trash entry of the state pools, see
        nothing and take no snapshot."""
        rows = np.zeros((n, self.row_cols), np.int32)
        rows[:, 4] = -1
        for k, kp in zip((1, *range(5, 4 + len(self.pages))), self.pages):
            rows[:, k] = kp.trash
        if self.layout.state:
            rows[:, -3:-1] = self.state_trash
            rows[:, -1] = -1
        return rows

    def _step_programs(self) -> Dict[int, Any]:
        """The model's step (``layout.step``) by rung of ``ladder``, each
        a function of the step's arguments with ``self_cfg_id`` and
        ``pages_per_step`` bound.  Every rung traces the kernels' bodies
        again, seconds of Python each; where a persistent compile cache
        is configured the rungs' lowerings are kept beside it
        (``compile_cache.kept_lowering``), so that an engine that starts
        again pays for none of them."""
        from ..utils.compile_cache import kept_lowering

        _, args, kwargs, _ = self.analysis_entry()
        static = {k: kwargs.pop(k) for k in ("self_cfg_id", "pages_per_step")}
        if len(self.ladder) == 1:
            # one shape: JAX's own caches serve it, as they always have
            return {self.rows_cap: partial(self.layout.step, **static)}
        state = tuple(self._more_arguments())
        programs = {}
        for n in self.ladder:
            rung = (*args[:3], self._padding_rows(n), *args[4:])
            programs[n] = kept_lowering(
                self.layout.step, rung, kwargs, static, donate_argnums=(1, 2),
                donate_argnames=state, what=repr(self.cfg))
        return programs

    def _more_arguments(self) -> Dict[str, Any]:
        """What the step takes beside the K and V pools, donated, and
        returns after its third result in this order: the recurrent
        state's pools (``state=``) and a page's further pools
        (``pools=``), for a layout that has them."""
        return {**({"state": self.state} if self.layout.state else {}),
                **({"pools": self.more_pools}
                   if self.layout.more_pools else {})}

    def _padding_launches(self):
        """One launch of padding rows a rung of ``ladder``: what
        ``_step_unified`` launches before the engine's first launch, so
        that every shape the step will ever take is compiled by then
        (with a draft model, the proposals' launch too).  None where the
        ladder has one rung: the first launch compiles it."""
        if len(self.ladder) == 1:
            return []
        if self.draft is not None:
            self._draft_launch(self._padding_rows(self.max_slots))
        gather = np.zeros(self.gather_cap, np.int32)
        return [(self._padding_rows(n), gather, self._no_tokens)
                for n in self.ladder]

    def _pack_unified(self, decode, prefill, props: Dict[int, tuple]):
        """The packed row schedule of one launch (``PagedLayout.step``'s
        ``rows`` and ``gather``) for what ``_schedule`` found, and the
        ``_Launch`` that commits it: what each gathered row is, the
        commit loop's ``metas``, the prompt tokens scheduled by slot and
        the step's counts for ``serving.step_counts``.  ``rows`` has the
        rows of the smallest rung of ``ladder`` that holds what was
        packed (its ``rows_cap`` count); ``gather`` is ``[gather_cap]``
        whatever the rung."""
        rows = self._padding_rows(self.rows_cap)
        stateful = bool(self.layout.state)
        sc = 4 + len(self.pages)        # the first of the state columns
        snaps: Dict[int, tuple] = {}
        # consumed-row gather schedule: metas carry GATHERED offsets, so
        # the commit loop indexes the gathered tokens directly
        gather = np.zeros(self.gather_cap, np.int32)
        gathered = []                 # (rid, position) per gathered row
        g = 0
        r = 0
        kv_ctx = 0      # context each scheduled slot attends to, once each
        metas = []
        for s, base, tok in decode:
            window = [tok] + list(props.get(s, ([], []))[0])
            gstart = g
            if self.more_pages:
                self._map_pages(s, base + len(window))
            for j, t in enumerate(window):
                p = base + j
                rows[r, :5] = (t, self._phys(s, p), p % self.page_size,
                               p + 1, s)
                gather[g] = r
                gathered.append((int(self.slot_rid[s]), p))
                g += 1
                r += 1
            if stateful:
                rows[r - len(window):r, sc:sc + 2] = (self.state_src[s], s)
                self.state_src[s] = s
            kv_ctx += base + len(window)
            metas.append(("verify", s, gstart, len(window)))
        decode_rows = r
        left = self.prefill_budget
        call = self.step_totals["steps"]
        for s, base, pend in prefill:
            if left <= 0:
                break
            if self.unlaunched.pop(s, call) < call:
                # the slot's first chunk, a call or more after it was
                # admitted: what it waited behind may be in the trie
                self._late_hit(s)
                base, pend = int(self.seq_lens[s]), self.pending_prompt[s]
            chunk = min(len(pend), left)
            if stateful:
                chunk = self._state_chunk(base, chunk, len(pend))
            if self.more_pages:
                self._map_pages(s, base + chunk)
            for j in range(chunk):
                p = base + j
                rows[r, :5] = (int(pend[j]), self._phys(s, p),
                               p % self.page_size, p + 1, s)
                r += 1
            if stateful:
                rows[r - chunk:r, sc:sc + 2] = (self.state_src[s], s)
                self.state_src[s] = s
                entry = self._snapshot_entry(s, base + chunk, len(snaps))
                if entry is not None:
                    snaps[s] = ((base + chunk) // self.page_size, entry)
                    rows[r - 1, sc + 2] = self.max_slots + entry
            left -= chunk
            kv_ctx += base + chunk
            # only the chunk's FINAL row can seed generation — it is
            # the one prefill row the gather hands to the host
            gather[g] = r - 1
            gathered.append((int(self.slot_rid[s]), base + chunk - 1))
            metas.append(("prefill", s, g, chunk))
            g += 1
        # the launched step's rows: the smallest rung that holds them
        rung = next(n for n in self.ladder if n >= r)
        counts = {
            "rows": r, "rows_cap": rung,
            "decode_rows": decode_rows, "prefill_rows": r - decode_rows,
            "slots": len(metas), "gathered": g,
            # whether the launch was enqueued before the one before it
            # was read, and its rows whose slot had ended by then
            "ahead": 0, "stale_rows": 0,
            # its serial among the engine's launches, from 1 (it is
            # enqueued next); 0: no rows, nothing will be launched
            "launch": 1 + sum(self.launches_by_rows.values()) if r else 0,
            # sum of the rows' visibilities: the attention's arithmetic
            "attn_row_ctx": int(rows[:r, 3].sum()),
            # the K/V the step has to read at least: its bytes
            "kv_ctx_tokens": kv_ctx,
        }
        counts.update(self.layout.row_counts(
            rows[:r], kv_ctx, self.page_size, self.pages_per_seq))
        # the page a row writes in each further kind of page, from that
        # kind's table (columns 5..; a padding row's is the kind's trash)
        for k, kp in enumerate(self.more_pages, 5):
            rows[:r, k] = kp.tables[rows[:r, 4],
                                    (rows[:r, 3] - 1) // self.page_size]
        return rows[:rung], gather, _Launch(metas, gathered, counts, props,
                                            snaps=snaps)

    def _state_chunk(self, base: int, chunk: int, pending: int) -> int:
        """A prefill chunk of a sequence with a recurrent state, cut so
        that it ENDS where a snapshot can be taken: a snapshot is the
        state at a chunk's end, and the prefix cache can only use one at
        a block's end.  A chunk that does not finish its prompt ends on
        a multiple of the chunk budget where it reaches one (the same
        positions for every request, so two prompts that share a prefix
        take and find their snapshots at the same places), else on a
        page boundary, else where the budget ends it."""
        if chunk >= pending:
            return chunk
        page = self.page_size
        for grid in (max(page, self._init_prefill_budget // page * page),
                     page):
            cut = (base + chunk) // grid * grid
            if cut > base:
                return cut - base
        return chunk

    def _snapshot_entry(self, slot: int, end: int, taken: int):
        """The snapshot entry for the state of ``slot`` at position
        ``end``, the end of the chunk being packed, or None: no prefix
        cache, not the end of a block the prompt's insert will commit,
        this launch's snapshots taken, or every entry in use by a
        restore.  A prompt holds at most half the entries before its
        insert: past that, and where no entry can be had, it gives up
        its own shallowest."""
        pc = self.prefix_cache
        prompt_len = self.prompt_lens[int(self.slot_rid[slot])]
        if (pc is None or end % self.page_size or end > prompt_len
                or taken >= self.layout.state_snapshots_a_step):
            return None
        # taken by a committed launch and not yet the cache's (the
        # prompt's insert hands them over): nobody can restore from them
        own = self.slot_snaps.get(slot, [])
        if len(own) < max(1, self.state_snapshots // 2):
            entry = self.snap_alloc.alloc()
            # (only the snapshot at a prompt's END, where a session goes
            # on, may displace one that was restored from)
            if entry is None and pc.evict_snapshots(1,
                                                    used=end == prompt_len):
                entry = self.snap_alloc.alloc()
            if entry is not None:
                return entry
        # a long prompt passes more chunk ends than there are entries:
        # its own SHALLOWEST pending snapshot makes room for the deeper
        # one (a hit is served as far as the deepest), so that one
        # session's prefill neither ends without a snapshot at its end
        # nor drains the cache of every other session's
        return own.pop(0)[1] if own else None

    def _commit_unified(self, launch: _Launch, tokens: np.ndarray,
                        logits: Optional[np.ndarray]) -> int:
        """Commit every slot of ``launch`` that is still scheduled from
        the tokens the device sampled (``logits`` too where a request
        draws from them); returns the tokens produced."""
        produced = 0
        for kind, s, gstart, n in launch.metas:
            rid = int(self.slot_rid[s])
            if kind == "verify":
                take = self._commit_window(s, gstart, n, tokens, logits,
                                           launch.props.get(s))
                produced += len(take)
                continue
            # prefill chunk: commit the scattered prompt K/V
            req = self.req_info[s]
            req.chunks += 1
            self.seq_lens[s] += n
            self.prefill_stats[rid]["prefilled"] += n
            if self.layout.state:
                # the launch that read the snapshot the slot started
                # from has run: its reference goes back; the snapshot
                # this chunk took waits for the prompt's insert
                if s in self.slot_snap_ref:
                    self.snap_alloc.release([self.slot_snap_ref.pop(s)])
                if s in launch.snaps:
                    self.slot_snaps.setdefault(s, []).append(
                        launch.snaps.pop(s))
            pend = self.pending_prompt[s]
            if n < len(pend):
                self.pending_prompt[s] = pend[n:]
                if self.prefix_cache is not None:
                    # the full pages of what the prompt has committed so
                    # far, of the first kind alone: a window kind's pages
                    # and the state's snapshots go over with the prompt's
                    # last chunk, below (a block is restorable from then)
                    self.prefix_cache.insert(
                        req.prompt[:self.seq_lens[s]], self.slot_pages[s])
                continue
            # prompt complete: the chunk's final row (gathered at
            # ``gstart``) carries the first token; commit the rest of
            # its full pages to the prefix cache, and what the blocks
            # committed chunk by chunk still lack
            del self.pending_prompt[s]
            self.prefill_order.remove(s)
            if self.prefix_cache is not None:
                self.prefix_cache.insert(
                    req.prompt, self.slot_pages[s],
                    [(kp.lo[s], kp.held[s]) for kp in self.pages[1:]],
                    snaps=self.slot_snaps.pop(s, ()))
            tok = (int(tokens[gstart]) if req.temperature <= 0
                   else self._sample_row(logits[gstart], req))
            prefill_us = int((time.perf_counter() - req.admitted) * 1e6)
            tot = self.step_totals
            tot["prefill_us"] += prefill_us
            tot["prefill_us_max"] = max(tot["prefill_us_max"], prefill_us)
            with RecordEvent("serving.first_token", rid=rid,
                             prefill_us=prefill_us, chunks=req.chunks):
                pass
            if self.prefill_only:
                # park for KV handoff: pages stay reserved, the first
                # sampled token rides the handoff record (committed by
                # the DECODE side, so the router never double-counts it)
                self.cur_tok[s] = tok
                self.handoff_ready[s] = {
                    "rid": rid, "first_token": int(tok),
                    "seq_len": int(self.seq_lens[s]),
                    "temperature": float(req.temperature),
                    "max_new_tokens": int(req.max_new_tokens),
                    # round-17: the per-slot PRNG migrates WITH the KV —
                    # the first token above consumed one draw, so the
                    # decode side resumes the seeded stream mid-state
                    # instead of restarting it (sampled requests no
                    # longer pin to the unified pool)
                    "seed": int(req.seed),
                    "rng_state": (req.rng.bit_generator.state
                                  if req.temperature > 0 else None),
                }
                continue
            self.cur_tok[s] = tok
            self.out_tokens[rid] = [tok]
            self.budget[s] = req.max_new_tokens - 1
            produced += 1
            if tok == self.eos_id or self.budget[s] <= 0:
                self._finish(s)
        if self.more_pages:
            for _, s, _, _ in launch.metas:
                if self.active[s]:
                    self._recycle(s)
        return produced

    def shutdown(self) -> None:
        """Engine teardown: drop the prefix cache's page references and
        run the allocator leak check — a COW refcount bug (double
        release, leaked trie ref) fails HERE, not as silent pool
        exhaustion three requests later."""
        if self.active.any() or self.queue:
            raise AssertionError(
                "shutdown with live requests — drain via run() first")
        if self._flight is not None:
            # only stale rows can be left in flight (their slots ended
            # or were canceled): see them through, commit nothing
            jax.block_until_ready(self._flight.out)
            self._flight = None
        if self.prefix_cache is not None:
            self.prefix_cache.assert_consistent()
            self.prefix_cache.clear()
        for kp in self.pages:
            kp.alloc.assert_consistent()
            if kp.alloc.available != kp.alloc.total:
                raise AssertionError(
                    f"page leak at teardown: "
                    f"{kp.alloc.total - kp.alloc.available} pages still "
                    f"referenced")
        self.snap_alloc.assert_consistent()
        if self.snap_alloc.available != self.snap_alloc.total:
            raise AssertionError(
                f"state snapshot leak at teardown: "
                f"{self.snap_alloc.total - self.snap_alloc.available} "
                f"entries still referenced")

    def assert_balanced(self) -> None:
        """Every allocator consistent, and every state snapshot entry
        that is live accounted for: the prefix cache's blocks hold one
        reference each, a slot one on the snapshot it is to restore from
        and one on each its chunks took that the trie has not been
        given.  Callable mid-flight; after a drain nothing but the
        cache's are left."""
        for kp in self.pages:
            kp.alloc.assert_consistent()
        self.snap_alloc.assert_consistent()
        refs = [0] * self.snap_alloc.total
        if self.prefix_cache is not None:
            self.prefix_cache.assert_consistent()
            for e in self.prefix_cache.snap_nodes:
                refs[e] += 1
        held = [*self.slot_snap_ref.values(),
                *(e for v in self.slot_snaps.values() for _, e in v),
                *(e for _, e in (self._flight.snaps.values()
                                 if self._flight is not None else ()))]
        for e in held:
            refs[e] += 1
        if refs != self.snap_alloc.refs:
            raise AssertionError(
                f"state snapshot entries out of balance: held {refs}, "
                f"counted {self.snap_alloc.refs}")

    def serving_stats(self) -> Dict[str, Any]:
        """Serving-plane telemetry: prefix-cache counters, per-request
        prefill accounting (the FLOPs-skip contract), speculative
        accepted-length distribution and ``"steps"``: what the engine's
        steps did since it was built, for an operator who never traces
        (how full the steps are: ``rows`` over ``rows_cap``; how many
        launches were enqueued before the one before them was read,
        ``ahead``, and how many rows ran for a slot that had ended,
        ``stale_rows``; how long requests queue and prefill, sum and max
        in seconds; under ``"prefix_cache"`` the hits the second look-up
        found, ``late_hits``, and the tokens they added,
        ``late_hit_tokens``).  The same numbers, per step and per
        request, ride on the ``serving.step_counts``,
        ``serving.admit_request``, ``serving.late_hit`` and
        ``serving.first_token`` markers of a profiler trace."""
        t = self.step_totals
        out: Dict[str, Any] = {
            "prefill": dict(self.prefill_stats),
            "accepted_lengths": list(self.accepted_lengths),
            "steps": {
                **{k: t[k] for k in ("steps", "rows", "rows_cap",
                                     "decode_rows", "prefill_rows",
                                     "ahead", "stale_rows", "admitted",
                                     *self.count_names)},
                "launches_by_rows": dict(self.launches_by_rows),
                "queue_wait_s": {"sum": t["queue_wait_us"] / 1e6,
                                 "max": t["queue_wait_us_max"] / 1e6},
                "prefill_s": {"sum": t["prefill_us"] / 1e6,
                              "max": t["prefill_us_max"] / 1e6},
            },
        }
        if self.accepted_lengths:
            out["mean_accepted_len"] = float(
                np.mean(self.accepted_lengths))
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        return out

    @property
    def last_logits(self) -> Optional[tuple]:
        """The newest COMMITTED launch's gathered rows, for checks
        against a reference: ``([(rid, absolute position of the input
        token), ...], fp32 [len(rows), vocab])``.  The logits are copied
        from the device when this is read; a greedy step never copies
        them.  Setting it to ``None`` forgets them."""
        if self._last_logits is None:
            return None
        gathered, logits = self._last_logits[:2]
        return gathered, np.asarray(logits)[:len(gathered)]

    @property
    def last_extras(self) -> tuple:
        """What the newest committed launch's step returned of its
        gathered rows beyond logits, tokens and the device's counts
        (``PagedLayout.step``: whatever a model adds for a check to read,
        a row a gathered row each), copied from the device when this is
        read and cut to the gathered rows; ``()`` where there is none."""
        if self._last_logits is None:
            return ()
        gathered = self._last_logits[0]
        skip = 3 + bool(self.layout.device_counts)
        return tuple(np.asarray(x)[:len(gathered)]
                     for x in self._last_logits[skip:])

    @last_logits.setter
    def last_logits(self, value) -> None:
        if value is not None:
            raise ValueError("last_logits can only be cleared")
        self._last_logits = None

    def step(self):
        """One scheduler iteration; returns the number of tokens
        produced.  It stays a call of ``_step_unified``: the jitted step
        is lowered from that frame, no deeper (PERF.md, PR 24)."""
        return self._step_unified()

    def run(self, max_iters: int = 10_000):
        """Drive until queue and slots drain.  Returns finished requests
        sorted by rid."""
        it = 0
        while (self.queue or self.active.any()) and it < max_iters:
            self.step()
            it += 1
        if self.queue or self.active.any():
            raise RuntimeError("serving loop did not drain")
        return sorted(self.finished, key=lambda f: f.rid)

    # ---------------- graph-doctor entry ----------------

    def analysis_entry(self):
        """(fn, args, kwargs, options) for ``paddle_tpu.analysis.check``
        over the step program: the SAME jit the scheduler launches
        (``layout.step``), at the TOP rung of its ladder, its row
        capacity (decode rows + spec windows + a full prefill chunk).
        ``options`` declares the donation contract: params and the rope
        tables persist across steps BY DESIGN (every step re-reads
        them; donating would force a re-upload), while the page pools are donated through the
        program (donate_argnums=(1, 2)) and the doctor verifies that
        stays true; the packed row schedule and page table are per-step
        uploads (small int32, below the donation floor by construction).

            fn, args, kwargs, options = engine.analysis_entry()
            report = paddle_tpu.analysis.check(
                fn, *args, kwargs=kwargs, options=options)
        """
        rows = self._padding_rows(self.rows_cap)
        kv_scales = self.kv_scales
        if kv_scales is None and self.cache_dtype == jnp.int8:
            # doctor sweep BEFORE the first admission calibrated: unit
            # placeholder scales with the post-calibration pytree shape,
            # so the priced program is the one real traffic runs
            ones = jnp.ones((self.cfg.num_hidden_layers,
                             self.cfg.num_key_value_heads), jnp.float32)
            kv_scales = {"kq": ones, "kdq": ones,
                         "vq": ones, "vdq": ones}
        args = (self.params, self.k_pages, self.v_pages,
                jnp.asarray(rows),
                tuple(jnp.asarray(kp.tables) for kp in self.pages),
                self.cos_tab, self.sin_tab)
        kwargs = dict(self_cfg_id=self.cfg_id,
                      pages_per_step=self.pages_per_step,
                      kv_scales=kv_scales,
                      gather=jnp.zeros(self.gather_cap, jnp.int32),
                      prev_tokens=self._no_tokens, **self._more_arguments())
        # min_bytes sized to the page pools, not the 1MB production
        # default: tiny test/debug engines must still FAIL the doctor if
        # the pools stop being donated (a vacuous gate passes when the
        # contract breaks)
        pool_bytes = min(int(np.prod(k.shape)) * k.dtype.itemsize
                         for k in self.k_pages)
        options = {"donation": {"persistent": (0, 5, 6),
                                "min_bytes": min(1 << 20,
                                                 max(1, pool_bytes // 2))},
                   # round-14 sharding contract: the single-chip serving
                   # hot path schedules ZERO reshard-class collectives —
                   # a GSPMD-inserted all-to-all/permute/gather here
                   # means a spec leaked into the unified step
                   "sharding_consistency": {"audit_resharding": True}}
        return self.layout.step, args, kwargs, options

    def param_layout(self):
        """Canonical SpecLayout of the engine's committed params (the
        Sharding Doctor's serving-stack extractor entry; see
        paddle_tpu.analysis.sharding.extract_serving_layout)."""
        from ..analysis.sharding import extract_serving_layout

        return extract_serving_layout(self)
