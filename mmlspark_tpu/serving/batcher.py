"""Continuous batching for autoregressive decode.

Beyond-reference serving capability (the reference batches fixed-function
transforms; it has no decode loop at all): many concurrent generation
streams share ONE jitted slot-decode step per token tick.  Each request
owns a slot in a static [S, max_len, ...] KV cache; slots sit at their
OWN positions (`decode_step` slot mode, models/transformer.py), so
requests admit/finish independently — a new stream joins the running
batch the tick after an old one leaves, no recompile (the vLLM-style
continuous-batching shape).  `paged=True` swaps the per-slot cache for
shared page pools + a page table (vLLM paged KV): HBM is pay-per-page,
so co-tenant density stops being bounded by max_slots * max_len.

The cache is only ever updated IN PLACE.  Page pools are flat
[NP, page, Hkv*D] from allocation on — the one shape whose default
device layout the decode step's row scatter, the admission's page
scatter and the page-walk kernel all read (ops/paged_attention.py) —
and every program that takes the cache takes it DONATED: a call
consumes `self._cache`'s buffers and hands back the same memory,
updated.  Nothing may keep a reference to a pool across such a call.

Host loop per tick: admit pending prompts into free slots (one prefill
forward each; its padded cache rows overwrite the slot), DISPATCH one
batched decode step for ALL slots, and only then fetch what was
dispatched before it and emit those tokens.  Greedy decode — the
serving-stream shape; outputs are exactly `generate()`'s for every
stream regardless of co-tenancy (tested).

ONE STEP AHEAD.  The decode loop is a pipeline of depth one: step N+1
is queued before step N's tokens have come back, so the device runs it
while the host fetches, emits and accounts step N.  What that takes:

* The next token and the positions live on the device.  The step picks
  its tokens (argmax inside the program) and hands them back beside
  `pos + 1`; the next step takes both vectors as they stand.  The host
  uploads only what the device cannot know — a `[2, S]` override of
  token and position (-1: keep) when a slot was admitted, parked or
  fed a given token, and the page tables when one changed.  A tick in
  which nothing changed uploads nothing.  `_tok`/`_pos` are the host's
  mirrors: `_pos` is bumped at DISPATCH (growth and the counters read
  the position the queued step writes), `_tok` follows a tick late.
* An admission does not wait for its first tokens either: the prefill
  program scatters them into a `[S]` vector the next step reads (-1:
  none), and the host fetches them after that step is queued.  What is
  dispatched and not yet fetched sits in `_flight`, oldest first, and
  `_inflight[slot]` counts the slot's tokens in it.
* A request's end the host can foresee (`max_new_tokens`, `max_len`:
  `_Request.limit`) parks the slot — position 0 over a zeroed table
  row, as a free slot is — in the first dispatch that would overrun.
  An `eos_id` end is seen a tick late: the step already in flight
  wrote one more row into a page the slot still owns (the reservation
  covers every position up to `max_new_tokens`); its token is dropped
  and counted (`tick.late_discards`), and the slot, its pages and its
  reservation go back only after that fetch.
* Order on the device does the rest: every program takes the pools
  donated, so an admission's page load runs after the step in flight
  and before the step that reads its rows.

`teacher_force` feeds every step a GIVEN token and `_speculative_tick`
verifies before it proposes again: both dispatch and fetch at once.
`stop()` fetches whatever is in flight, so a stopped batcher's cache,
mirrors and tables agree.

Compose with serving: `stream_reply(lambda row: batcher.stream_text(...))`
gives token-by-token HTTP with cross-request batching on the device.

KINDS OF CACHE.  A model says what state each cached layer keeps
(`cache_kinds`, `layer_kinds`, `cache_rows`; models/moe_lm.py,
models/longcat_lm.py): the whole context ("full": a K and a V pool;
"latent": ONE pool of latent rows, two cached sublayers a block) or only
the last `window` positions ("window"); and, per kind, the row width of
every pool a layer of the kind keeps.  The batcher sizes its pools from
that, keeps a pool set, a page table and a free list PER KIND, and an
admission reserves in both.  The full kind is the state described above;
a window slot's table is a RING of window / page + 1 pages
(`_WindowPages`): logical page lp lives at entry lp % ring, so the page
that has fallen wholly behind the window is the one the next page
overwrites — recycled while the request runs, never more than the ring
held.  Such a model also brings its own admission forward (`prefill`:
the last position's logits only, cache rows of the bucket's length, at
most `max_len` prompt tokens a program), names per kind the head shape
its admission attends at (`attn_shapes`: query heads a KV head, q/k and
v head widths, for the count of the flash forward's tiles) and may name
statistics (`stat_counters`), which ride back with the tick's token
fetch.
`TransformerLM` is the case of one kind, "full".
Each arm below asks for the one thing it needs: `_own_prefill` (the
model's admission forward; its programs hand back the model's
statistics behind their tokens), `_win` (a second kind of page).  The
decode tick has no arms: every step takes the step before's vector and
one page table per kind, and hands back tokens, then statistics.

`teacher_force` replays given requests through the same host path and
the same programs, with what the programs computed handed back: the
verifier's view of what is served (benchmarks/drivers/laguna_serve.py).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from queue import Empty, Queue
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry
from ..core.flow import AdmissionStage, FlowGraph, Stage
from ..ops.pallas_kernels import on_single_tpu
from ..utils.sync import make_rlock

__all__ = ["ContinuousBatcher", "PrefillStage", "TokenStream"]

# The loop thread's profiler annotations (`telemetry.phase`): under a
# `jax.profiler` capture they land in the host plane, nested as below, on
# the device trace's clock.  The names are a contract with the reducers
# under benchmarks/ and with docs/observability.md; written once, here.
TICK = "serving.batcher.tick"               # an iteration with work to do
TICK_INTAKE = TICK + ".intake"              # control ops, intake -> buffer
TICK_ADMIT = TICK + ".admit"                # an admission: dispatch; fetch
ADMIT_PACK = "serving.batcher.admit.pack"   # host packing of one bucket
ADMIT_PREFILL = "serving.batcher.admit.prefill"   # upload, forward, load
ADMIT_FIRST_TOKEN = "serving.batcher.admit.first_token"   # blocking fetch
TICK_GROW = TICK + ".grow"                  # just-in-time page growth
TICK_DRAFT = TICK + ".draft"                # speculative: the draft's steps
TICK_UPLOAD = TICK + ".upload"              # what changed, in one put
TICK_DISPATCH = TICK + ".dispatch"          # the call of the decode step
TICK_FETCH = TICK + ".fetch"                # blocked on the step before
TICK_EMIT = TICK + ".emit"                  # fetched tokens -> streams
IDLE = "serving.batcher.idle"               # nothing live: wait on intake
# counters of the one-step-ahead loop (module doc)
TICK_OVERLAPPED = TICK + ".overlapped"      # dispatched before that fetch
TICK_LATE_DISCARDS = TICK + ".late_discards"   # dead rows' tokens dropped
# positions whose taps one replayed admission program hands back
# (`teacher_force`): a longer prompt is replayed once a piece
TAP_ROWS = 1024


class PrefillStage(Stage):
    """Host-side prompt packing for admission prefill buckets, as a
    registered flow stage: bucket i+1 packs on a flow worker while
    bucket i's prefill forward occupies the device.  The bounded credit
    budget caps how many packed buckets stage ahead of the device (lint
    rule G405 holds every registered Stage subclass to one)."""

    name = "prefill"
    credits = 4


class _WindowPages:
    """Host bookkeeping of the window kind: per slot a ring of at most
    `ring` = window / page + 1 physical pages.  Loop-thread-owned, like
    the full kind's free list and table."""

    def __init__(self, window: int, page: int, slots: int,
                 num_pages: Optional[int] = None):
        if window % page:
            raise ValueError(f"page_size {page} must divide the attention "
                             f"window {window}")
        self.window, self.page = int(window), int(page)
        self.ring = self.window // self.page + 1
        self.np = (int(num_pages) if num_pages is not None
                   else slots * self.ring + 1)     # page 0 is trash here too
        self.free: List[int] = list(range(1, self.np))
        self.avail = len(self.free)
        self.slot_pages: List[List[int]] = [[] for _ in range(slots)]
        self.slot_reserved = [0] * slots
        self.table = np.zeros((slots, self.ring), np.int32)

    def worst(self, tokens: int) -> int:
        """Pages a request of `tokens` positions can ever hold."""
        return min(-(-tokens // self.page), self.ring)

    def admit(self, slot: int, n: int) -> list:
        """Allocate the pages a prompt of n tokens leaves live: its last
        `ring` logical pages.  -> [(logical page, physical page)] to load."""
        cur = (n - 1) // self.page
        pages = [self.free.pop() for _ in range(min(cur + 1, self.ring))]
        self.slot_pages[slot] = pages
        self.table[slot].fill(0)
        self.table[slot, :len(pages)] = pages
        return [(lp, pages[lp % self.ring])
                for lp in range(max(0, cur - self.ring + 1), cur + 1)]

    def grow(self, slot: int, pos: int) -> int:
        """Make the page of write position `pos` exist.  -> 1 when that
        write starts overwriting a recycled page, else 0."""
        lp = pos // self.page
        pages = self.slot_pages[slot]
        if lp < self.ring:
            while lp >= len(pages):
                pages.append(self.free.pop())
                self.table[slot, len(pages) - 1] = pages[-1]
            return 0
        return int(pos % self.page == 0)

    def release(self, slot: int) -> None:
        self.free.extend(self.slot_pages[slot])
        self.slot_pages[slot] = []
        self.table[slot].fill(0)
        self.avail += self.slot_reserved[slot]
        self.slot_reserved[slot] = 0


def _sum_stats(stats, names) -> jax.Array:
    """A model's `stats` collection -> int32 [len(names)], each summed
    over the layers that sowed it."""
    flat = jax.tree_util.tree_flatten_with_path(stats)[0]
    return jnp.stack([
        sum((v.astype(jnp.int32) for path, v in flat
             if getattr(path[-1], "key", None) == name),
            jnp.zeros((), jnp.int32)) for name in names])


def _by_tap(routing) -> dict:
    """A model's `routing` collection ({layerN: {...: {tap: (value,)}}})
    -> {tap: [layers that sowed it, in layer order, ...]}."""
    flat = jax.tree_util.tree_flatten_with_path(routing)[0]
    taps: dict = {}
    for path, v in sorted(flat, key=lambda pv: int(pv[0][0].key[5:])):
        tap = next(p.key for p in reversed(path) if hasattr(p, "key"))
        taps.setdefault(tap, []).append(v)
    return {tap: jnp.stack(vs) for tap, vs in taps.items()}


def _one_step_ahead(forward, slots: int):
    """The decode step as the loop dispatches it, over `forward` (tokens
    [S, 1], cache, positions, page tables -> (an int32 vector led by the
    S tokens it chose, or a tuple led by that vector; cache)).  `prev`
    and `pos` are the step before's two outputs as they stand on the
    device; each slot takes its token from the host's override `ovr[0]`,
    else from `adm` (an admission's first token), else from `prev`, and
    its position from `ovr[1]` or `pos` (-1 throughout: not given).
    -> (forward's first output, the next step's positions, cache): a
    parked slot stays at position 0, every other moves on one."""
    def step(v, c, prev, pos, ovr, adm, tables):
        tok = jnp.where(ovr[0] >= 0, ovr[0],
                        jnp.where(adm >= 0, adm, prev[:slots]))
        pos = jnp.where(ovr[1] >= 0, ovr[1], pos)
        out, cache = forward(v, tok[:, None], c, pos, tables)
        return out, pos + (pos > 0), cache
    return step


def _paged_rows(rows, n_blocks: int, page: int):
    """[K, S, W] cache rows -> [n_blocks, page, W] page blocks, S padded
    up to whole pages."""
    k, s_, w = rows.shape
    pad = -s_ % page
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
    return rows.reshape(n_blocks, page, w)


class TokenStream:
    """Iterator over one request's generated token ids (host ints).
    Blocks until tokens arrive; ends when the request finishes.  A
    request the scheduler had to abandon (e.g. its paged reservation
    can never fit after a later prefix registration shrank the pool)
    closes the stream with `error` set and iteration raises it —
    consumers must never block forever on a request that cannot run."""

    def __init__(self):
        # one request's tokens, capped by its max_new_tokens; bounding it
        # would let one slow client stall the batch loop for every slot
        self._q: "Queue[Optional[int]]" = Queue()  # graftlint: disable=G403
        self.error: Optional[Exception] = None

    def __iter__(self) -> Iterator[int]:
        while True:
            tok = self._q.get()
            if tok is None:
                if self.error is not None:
                    raise self.error
                return
            yield tok

    def tokens(self) -> List[int]:
        """Drain the whole stream (blocking)."""
        return list(self)


class _Request:
    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 eos_id: Optional[int], prefix: Optional[int] = None,
                 deadline: Optional[float] = None):
        self.prompt = prompt          # FULL ids (shared prefix + suffix)
        self.max_new = int(max_new_tokens)
        self.eos_id = eos_id
        self.prefix = prefix          # register_prefix handle, or None
        self.deadline = deadline      # absolute monotonic admission budget
        self.stream = TokenStream()
        self.emitted = 0
        self.limit = self.max_new     # tokens it can ever get (_go_live)
        self.closed = False           # its stream has ended
        # submitter's trace context: admission latency is attributed back
        # to the submitting request's span (the loop is another thread)
        self.trace = telemetry.current_context()
        self.submitted_at = time.monotonic()
        self.first_token_at = 0.0     # set by the admission that took it


class _Flight:
    """A program dispatched whose tokens the host has not fetched: a
    decode step or one bucket of an admission.  `out` is its int32
    vector on the device, `n_tok` tokens then the model's statistics;
    `rows` the (index into it, slot) pairs it made a token for;
    `admitted` an admission's (group, bucket, rows of the program,
    start) for `_note_prefill`, None for a decode step."""

    __slots__ = ("out", "n_tok", "rows", "admitted")

    def __init__(self, out, n_tok: int, rows, admitted=None):
        self.out, self.n_tok, self.rows = out, n_tok, rows
        self.admitted = admitted


class ContinuousBatcher:
    """Schedule many decode streams onto one slotted device batch.

    model: a TransformerLM; variables: its weights.  `max_slots` is the
    device batch width (a compile-time constant — one compiled step
    serves every mix of tenants).

    `draft_model`/`draft_variables` turn on SPECULATIVE continuous
    batching (vLLM-style): each tick the draft proposes `gamma` tokens
    for every slot ((gamma+1) cheap slot steps on a dense draft cache),
    then ONE target slot-BLOCK step verifies all slots' proposals at
    their own positions — up to gamma+1 tokens emitted per slot per
    target forward, outputs still EXACTLY generate()'s greedy tokens per
    stream (the per-slot speculative-decoding argument, composed with
    co-tenancy; tested).  The draft must share the target's vocabulary.
    """

    def __init__(self, model, variables, max_slots: int = 8,
                 idle_sleep_s: float = 0.001,
                 max_pending: Optional[int] = None,
                 kv_cache_dtype: str = None,
                 paged: bool = False, page_size: int = 64,
                 num_pages: Optional[int] = None,
                 draft_model=None, draft_variables=None, gamma: int = 4,
                 feed=None):
        if kv_cache_dtype not in (None, "int8"):
            raise ValueError(f"kv_cache_dtype must be None or 'int8', "
                             f"got {kv_cache_dtype!r}")
        if (draft_model is None) != (draft_variables is None):
            raise ValueError("draft_model and draft_variables go together")
        # a model that brings its own admission forward and cache
        # description (module doc, KINDS OF CACHE)
        self._own_prefill = hasattr(model, "prefill")
        if self._own_prefill and (not paged or kv_cache_dtype is not None
                         or draft_model is not None):
            raise ValueError(
                f"{type(model).__name__} is served over page pools in its "
                "own dtype: paged=True, no kv_cache_dtype, no draft model")
        if draft_model is not None:
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError("draft and target must share a vocabulary")
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
            if model.moe_experts > 0 and model.moe_capacity < model.moe_experts:
                # MoE expert capacity scales with the tokens per forward,
                # so a [B, gamma+1] verify block could drop tokens that
                # s=1 decode keeps — breaking the exactness contract.
                # capacity_factor >= num_experts makes every block width
                # drop-free (see TransformerLM.moe_capacity).
                raise ValueError(
                    "speculative batching with MoE needs drop-free "
                    f"capacity: set moe_capacity >= moe_experts "
                    f"({model.moe_experts}), got {model.moe_capacity}")
        from ..io.feed import DeviceFeed

        self.model = model
        self.variables = {c: v for c, v in variables.items()
                          if c != "kvcache"}
        # every host->device upload (per-tick token/pos/page-table vectors,
        # admission prefill batches) rides the shared feed engine: the
        # tick's 2-3 small arrays byte-pack into ONE device_put — each
        # separate transfer is a fixed cost on the decode tick's critical
        # path.  Callers may inject a
        # configured feed (`feed=`) — e.g. one carrying the autotuner's
        # winner (io.feed.load_tuned) or a meshed sharded engine — and
        # the prefill uploads inherit it; the default feed still adopts
        # MMLSPARK_FEED_TUNED on its own
        self._feed = feed if feed is not None else DeviceFeed()
        self.max_slots = int(max_slots)
        self.idle_sleep_s = float(idle_sleep_s)
        # bounded intake: submit() sheds (raises Overloaded) once this many
        # requests wait for a slot; None = unbounded (the seed behavior)
        self.max_pending = None if max_pending is None else int(max_pending)
        self.kv_cache_dtype = kv_cache_dtype
        self.paged = bool(paged)
        self.draft_model = draft_model
        self.gamma = int(gamma) if draft_model is not None else 0
        # a model's own admission program computes at most `max_len`
        # prompt tokens (rows x bucket): a larger group of one bucket is
        # split, so its temporaries are those of one full-length prompt
        self._prefill_cap = model.max_len if self._own_prefill else None
        s, L = self.max_slots, model.max_len
        # what state a layer keeps is the model's to say (module doc,
        # KINDS OF CACHE): the kinds, each cached layer's kind, and the
        # row width of every pool a layer of the kind keeps
        kinds = model.cache_kinds
        self._layer_kinds = tuple(model.layer_kinds)
        # per kind, the head shape of the admission's flash forward, for
        # the count of its tiles: where that kernel runs (one TPU)
        self._attn_shapes = (tuple(model.attn_shapes) if self._own_prefill
                             and on_single_tpu() else ())
        # the whole-context kind's counters, by its name
        self._count = {what: f"serving.batcher.{what}.{kinds[0][0]}"
                       for what in ("pages", "attended", "prefill.attended")}
        self._win: Optional[_WindowPages] = None
        dt = jnp.float32 if model.dtype == jnp.float32 else model.dtype
        if self.paged:
            # vLLM-style paged KV: per-layer PAGE POOLS shared by every
            # slot + a [S, MP] page table.  HBM cost is pay-per-page
            # (Σ ceil(live_len_i / page) pages) instead of S * max_len —
            # the stream-density lever past int8's 4x, and it composes
            # with kv_cache_dtype="int8".  Admission reserves each
            # request's WORST-CASE page count up front (counts only;
            # allocation stays lazy), so a running stream can never hit
            # pool exhaustion mid-decode.  Physical page 0 is the
            # write-trash page: free slots' dead writes and unallocated
            # table entries land there harmlessly (gathered trash rows
            # sit at positions the <= pos validity mask already hides).
            if L % int(page_size):
                raise ValueError(
                    f"page_size {page_size} must divide max_len {L}")
            self.page_size = int(page_size)
            self._mp = L // self.page_size          # max pages per slot
            self._np = (int(num_pages) if num_pages is not None
                        else s * self._mp + 1)      # default: dense parity
            if self._np < 2:
                raise ValueError("num_pages must be >= 2 (page 0 is trash)")
            self._free: List[int] = list(range(1, self._np))
            self._avail = len(self._free)           # unreserved budget
            self._slot_pages: List[List[int]] = [[] for _ in range(s)]
            self._slot_reserved = [0] * s
            self._slot_shared = [0] * s   # leading SHARED-prefix pages
            self._table = np.zeros((s, self._mp), np.int32)
            self._prefixes: dict = {}     # handle -> shared-prefix record
            self._next_prefix = 1
            if len(kinds) > 1:
                self._win = _WindowPages(kinds[1][1], self.page_size, s)
        if self.paged and kv_cache_dtype is None:
            # FLAT pools (module doc): per cached layer, one pool a row
            # width of its kind: K and V with the heads folded into the
            # minor axis, or a latent kind's single row
            pages = [self._np] + ([self._win.np] if self._win else [])
            self._cache = tuple(
                tuple(jnp.zeros((pages[kind], self.page_size, w), dt)
                      for w in model.cache_rows[kind])
                for kind in self._layer_kinds)
        else:
            # the dense slot cache and int8 pools are (K, V) of separate
            # heads: the models that take them say both
            h, d = model.kv_heads, model.head_dim
            if self.paged:
                shape_kv = (self._np, self.page_size, h * d)
                shape_sc = (self._np, self.page_size, h)
            else:
                shape_kv, shape_sc = (s, L, h, d), (s, L, h)
            if kv_cache_dtype == "int8":
                # 4x the co-tenant density per HBM byte: int8 rows + f32
                # per-(pos, head) scales (ops/quant.quantize_kv_row)
                self._cache = tuple(
                    (jnp.zeros(shape_kv, jnp.int8),
                     jnp.zeros(shape_sc, jnp.float32),
                     jnp.zeros(shape_kv, jnp.int8),
                     jnp.zeros(shape_sc, jnp.float32))
                    for _ in self._layer_kinds)
            else:
                self._cache = tuple(
                    (jnp.zeros(shape_kv, dt), jnp.zeros(shape_kv, dt))
                    for _ in self._layer_kinds)
        # loop-thread state of the decode pipeline (module doc, ONE STEP
        # AHEAD).  Host mirrors: `_pos` the position the NEXT dispatch
        # writes, `_tok` the last token fetched, `_give` a token the host
        # knows and the device does not (-1: none), `_inflight` the
        # slot's tokens dispatched and not fetched, `_flight` those
        # programs.  What the device holds: `_d_out`/`_d_pos` the last
        # step's two outputs (`_held`: the host's copy of `_d_pos`),
        # `_adm` the admissions' first tokens since then, `_d_tables`
        # the tables as last uploaded (`_sent`: the host's copy)
        self._pos = np.zeros(s, np.int32)
        self._tok = np.zeros(s, np.int32)
        self._give = np.full(s, -1, np.int32)
        self._inflight = np.zeros(s, np.int32)
        self._flight: "deque[_Flight]" = deque()
        self._stat_counters: tuple = ()
        self._held = np.zeros(s, np.int32)
        self._sent: Optional[list] = None
        self._d_tables: tuple = ()
        self._live: List[Optional[_Request]] = [None] * s
        # the intake is a graftflow AdmissionStage: bounded shed at
        # submit() (Overloaded/503 past max_pending), expired-deadline
        # reaping, and graceful drain are the runtime's one code path —
        # with the batcher's historical counter/gauge names mirrored
        self._intake = AdmissionStage(
            max_pending=self.max_pending, label="batcher",
            shed_counter="batcher.shed",
            expired_counter="batcher.deadline_expired",
            depth_gauge="serving.batcher.queue_depth")
        # loop-thread-only FIFO between intake and admission: paged mode
        # may defer the queue head until enough pages free up (alias —
        # reap/drain mutate the deque in place, so it stays valid)
        self._buffer: "deque[_Request]" = self._intake.buffer
        # control ops (prefix register/release) serviced by the loop
        # thread, which owns the pool/free-list/device cache; low-rate
        # and must never drop or block the caller
        self._ctl: Queue = Queue()  # graftlint: disable=G403
        self._running = threading.Event()
        self._stopped = False
        # serializes the stopped-check+enqueue in submit() against stop()'s
        # drain: without it a submit racing stop can enqueue after the
        # drain, leaving a stream whose consumer blocks forever.  RLock:
        # _ctl_call executes control ops INLINE under this lock when no
        # loop thread runs, and _exec_release_prefix re-acquires it
        self._submit_lock = make_rlock("serving.batcher.submit")
        self._thread: Optional[threading.Thread] = None
        # the cache argument of every program below is DONATED (module
        # doc): each call site rebinds `self._cache` from the result
        def greedy_step(v, t, c, p, tables):
            lg, cache = self.model.apply(
                v, t, c, p, tables[0] if tables else None,
                method=self.model.decode_step)
            return jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32), cache

        self._step = jax.jit(_one_step_ahead(greedy_step, s),
                             donate_argnums=(1,))
        # slot BLOCK decode, logits out: a shared prefix's suffix forward
        # and the speculative verification, whose callers pick per row
        self._block_step = jax.jit(
            lambda v, t, c, p, pt: self.model.apply(
                v, t, c, p, pt, method=self.model.decode_step),
            donate_argnums=(2,))
        # admission prefill as ONE program per (rows, bucket) shape: run
        # eagerly it is an op-by-op dispatch (and a compile per op per
        # shape) of the whole forward on the admission path
        from ..models.generation import _prefill_cache

        self._prefill = jax.jit(lambda v, toks: _prefill_cache(
            self.model, v, toks, self.kv_cache_dtype))

        def prefill_first(v, toks, last, slots, adm):
            # an admission's forward: each row's first token, picked at
            # its last prompt position and scattered into `adm` at its
            # slot (a pad row's slot is out of range and drops)
            logits, rows = _prefill_cache(self.model, v, toks,
                                          self.kv_cache_dtype)
            firsts = jnp.argmax(logits[jnp.arange(toks.shape[0]), last],
                                axis=-1).astype(jnp.int32)
            return firsts, adm.at[slots].set(firsts, mode="drop"), rows

        self._prefill_first = jax.jit(prefill_first)
        # whole-slot overwrite: admitted requests' padded cache rows
        # replace their slots across every layer in one jitted update;
        # pad rows carry the OUT-OF-RANGE slot id S so mode="drop"
        # discards them (NOT -1: jax wraps negative indices numpy-style
        # BEFORE the bounds check, which would corrupt the last slot)
        self._load_many = jax.jit(
            lambda c, rows, slots: jax.tree.map(
                lambda dst, src: dst.at[slots].set(
                    src.astype(dst.dtype), mode="drop"),
                c, rows), donate_argnums=(0,))
        # paged admit: each row's prefill [K, L, Hkv(, D)] reshapes into
        # [K*MP, page, Hkv(*D)] blocks and scatters into the flat pools
        # at its page ids (flat [K*MP]); blocks past an allocation carry
        # the out-of-range id NP and drop
        self._load_paged_many = jax.jit(
            lambda c, rows, ids: jax.tree.map(
                lambda pool, r: pool.at[ids].set(
                    r.reshape(ids.shape[0], pool.shape[1],
                              -1).astype(pool.dtype),
                    mode="drop"),
                c, rows), donate_argnums=(0,))
        if self._own_prefill:
            self._build_own_programs()
        # what a step reads when nothing is given: no override, no
        # admission, and before the first step no step before
        self._keep = jnp.full((2, s), -1, jnp.int32)
        self._none = jnp.full(s, -1, jnp.int32)
        self._adm = self._none
        self._d_out = jnp.zeros(s + len(self._stat_counters), jnp.int32)
        self._d_pos = jnp.zeros(s, jnp.int32)
        if draft_model is not None:
            # speculative mode: the draft keeps a plain DENSE f32/bf16
            # slot cache (it is the small/cheap model; paging and int8
            # buy nothing there) at the same logical positions as the
            # target's cache
            self.draft_variables = {c: v for c, v in draft_variables.items()
                                    if c != "kvcache"}
            dL = draft_model.max_len
            dh = draft_model.kv_heads
            dd = draft_model.head_dim
            ddt = (jnp.float32 if draft_model.dtype == jnp.float32
                   else draft_model.dtype)
            self._d_cache = tuple(
                (jnp.zeros((s, dL, dh, dd), ddt),
                 jnp.zeros((s, dL, dh, dd), ddt))
                for _ in range(draft_model.num_layers))
            self._d_step = jax.jit(
                lambda v, t, c, p: self.draft_model.apply(
                    v, t, c, p, None, method=self.draft_model.decode_step),
                donate_argnums=(2,))
            self._d_prefill = jax.jit(lambda v, toks: _prefill_cache(
                self.draft_model, v, toks))

    def _build_own_programs(self):
        """The programs of a model with its own `prefill`: the decode
        step and the admission forward hand back ONE int32 vector, the
        greedy tokens followed by the model's statistics (one fetch
        each), and the page load takes one id vector per cache kind."""
        self._stat_counters = tuple(getattr(self.model, "stat_counters", ()))
        self._step, self._prefill_last = self._own_programs(taps=False)

        def load(c, rows, ids):
            return tuple(
                tuple(pool.at[ids[kind]].set(
                    _paged_rows(r, ids[kind].shape[0],
                                self.page_size).astype(pool.dtype),
                    mode="drop") for pool, r in zip(pools, layer_rows))
                for pools, layer_rows, kind in zip(c, rows,
                                                   self._layer_kinds))

        self._load_kinds = jax.jit(load, donate_argnums=(0,))

    def _own_programs(self, taps):
        """(decode step, admission forward) over the model's own methods.
        `taps`: the same functions also hand back the logits and the
        model's `routing` collection (what `teacher_force` reads): all of
        it (True), or the taps named.  The admission forward then takes
        one more argument, a position `r0`, and hands back the taps of
        positions [r0, r0 + TAP_ROWS) only, each layer's cut before the
        layers are stacked: a whole context's taps of every layer are
        gigabytes beside the weights and the pools, and the replayed
        program needs what the served one needs and one piece."""
        model = self.model
        names = tuple(name for name, _counter in self._stat_counters)
        asked = ["stats", "routing"] if taps else ["stats"]
        tap_rows = TAP_ROWS

        def out(logits, kept, r0=None):
            packed = jnp.concatenate([
                jnp.argmax(logits, axis=-1).astype(jnp.int32),
                _sum_stats(kept.get("stats", {}), names)])
            if taps:
                routing = kept.get("routing", {})
                if r0 is not None:
                    # each layer's tap cut where it was sown, before the
                    # layers are stacked: no whole context's tap outlives
                    # its layer
                    routing = jax.tree.map(
                        lambda x: jax.lax.dynamic_slice_in_dim(
                            x, r0, min(tap_rows, x.shape[1]), axis=1),
                        routing)
                routing = _by_tap(routing)
                if taps is not True:
                    routing = {k: v for k, v in routing.items() if k in taps}
                return packed, logits, routing
            return packed

        def forward(v, t, c, p, tables):
            (lg, cache), kept = model.apply(
                v, t, c, p, tables, method=model.decode_step, mutable=asked)
            return out(lg[:, 0], kept), cache

        def prefill(v, toks, last, slots, adm, r0=None):
            (lg, rows), kept = model.apply(
                v, toks, last, method=model.prefill, mutable=asked)
            made = out(lg, kept, r0)
            firsts = (made[0] if taps else made)[:toks.shape[0]]
            return made, adm.at[slots].set(firsts, mode="drop"), rows

        return (jax.jit(_one_step_ahead(forward, self.max_slots),
                        donate_argnums=(1,)), jax.jit(prefill))

    def _note_stats(self, values) -> None:
        for (_name, counter), value in zip(self._stat_counters, values):
            telemetry.incr(counter, int(value))

    def teacher_force(self, pairs, taps=True) -> list:
        """Replay (prompt ids, reply ids) pairs as they would be served:
        the same host path (reservation, page tables of every kind,
        just-in-time growth, the ring), the same program functions at
        the same shapes (every prompt admitted alone, all replies decoded
        together among the idle slots), but each step is fed the GIVEN
        reply token and fetched before the next is dispatched, and the
        programs hand back what they computed.

        -> per pair {"logits": [len(reply), V] (row j is what chose reply
        token j), "routing": {tap: [routed layers, P, ...]}} over the
        P = len(prompt) + len(reply) - 1 positions fed, the taps being
        the model's `routing` collection (`taps`: all of them, or the
        ones named: a tap not asked for is not computed); a tap that only
        the decode step sows covers the len(reply) - 1 positions it fed.
        For a stopped (or never started) batcher whose model brings its
        own programs."""
        if not self._own_prefill:
            raise ValueError("teacher_force needs a model with its own "
                             "prefill and decode programs")
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("stop the loop before teacher_force")
        if len(pairs) > self.max_slots:
            raise ValueError(f"{len(pairs)} pairs on {self.max_slots} slots")
        served = self._step, self._prefill_last
        seen: list = []
        replay_step, replay_admission = self._own_programs(taps)

        def step(*args):
            (packed, logits, routing), *rest = replay_step(*args)
            seen.append((logits, routing))
            return (packed, *rest)

        def admission(*args):
            # the same program once for every TAP_ROWS positions of the
            # prompt: all it computes is the same each time, the taps it
            # hands back are the next piece's
            pieces = []
            for r0 in range(0, len(admitting.prompt), TAP_ROWS):
                (packed, logits, routing), *rest = replay_admission(
                    *args, np.int32(r0))
                pieces.append(jax.tree.map(np.asarray, routing))
                # the next piece's program finds this piece's taps gone
                # from the device
                del routing
            seen.append((logits, {
                k: np.concatenate([p[k] for p in pieces], axis=2)
                for k in pieces[0]}))
            return (packed, *rest)

        self._step, self._prefill_last = step, admission
        admitting = None              # the request `admission` replays
        try:
            live, out = {}, []
            for prompt, reply in pairs:
                req = admitting = _Request(
                    np.asarray(prompt, np.int32).reshape(-1), len(reply),
                    None)
                self._buffer.append(req)
                batch = self._plan_admit()
                if len(batch) != 1:
                    self._buffer.remove(req)
                    raise RuntimeError("no free slot or pages to replay in")
                slot = batch[0][0]
                for flight in self._admit_batch(batch):
                    self._collect(flight)
                logits, routing = seen.pop()
                n = len(req.prompt)
                rec = {"logits": [np.asarray(logits[0])],
                       "routing": {tap: [np.asarray(v[:, 0, :n])]
                                   for tap, v in routing.items()}}
                out.append(rec)
                if self._live[slot] is req:
                    live[slot] = (req, reply, rec)
            while live:
                active = sorted(live)
                for slot in active:
                    req, reply, _rec = live[slot]
                    self._give[slot] = reply[req.emitted - 1]
                self._grow_pages(active)
                self._collect(self._dispatch(active))
                logits, routing = seen.pop()
                logits = np.asarray(logits)
                routing = {tap: np.asarray(v) for tap, v in routing.items()}
                for slot in active:
                    req, _reply, rec = live[slot]
                    rec["logits"].append(logits[slot])
                    for tap, v in routing.items():    # a tap of the
                        # decode step alone has no rows of the prompt
                        rec["routing"].setdefault(tap, []).append(v[:, slot])
                    if self._live[slot] is not req:
                        del live[slot]
        finally:
            self._step, self._prefill_last = served
        return [{"logits": np.stack(rec["logits"]),
                 "routing": {tap: np.concatenate(parts, axis=1)
                             for tap, parts in rec["routing"].items()}}
                for rec in out]

    def _page_ceiling(self) -> int:
        """Pages that can EVER be simultaneously free for one request:
        the pool minus every registered prefix's held pages.  submit()'s
        reject and _try_admit()'s drop are the two ends of the same
        admission invariant and MUST share this expression — divergence
        would let submit accept a request the scheduler then errors (or
        silently wedge valid ones)."""
        return self._np - 1 - sum(
            r["shared"] for r in self._prefixes.values())

    def _worst_pages(self, prompt_len: int, max_new: int,
                     shared_pages: int = 0) -> int:
        """Worst-case page count for one request — THE reservation
        invariant: submit()'s rejection and _try_admit()'s reservation
        must both use exactly this, or just-in-time growth in the loop
        can pop an empty free list mid-decode.  Speculative mode writes
        up to `gamma` rows past the emitted position per verify block,
        so the reservation covers them too.  A shared prefix's leading
        pages are the HANDLE's, not the request's."""
        return min(-(-(prompt_len + max_new + self.gamma)
                     // self.page_size), self._mp) - shared_pages

    # ---- shared-prefix caching (paged mode) ----------------------------
    # The page pool, free list, and device cache are LOOP-THREAD-OWNED;
    # prefix registration/release therefore route through a control queue
    # the loop drains each tick (executed inline when the loop isn't
    # running — the common register-at-setup case).

    def _ctl_call(self, op, payload):
        rec = {"op": op, "payload": payload, "event": threading.Event(),
               "result": None, "error": None}
        with self._submit_lock:
            if self._stopped:
                raise RuntimeError("ContinuousBatcher is stopped")
            # inline only while no loop thread can possibly be running —
            # a thread that is merely STOPPING may still be mid-tick,
            # and the queue is drained (with errors) by stop() after the
            # join, so enqueueing is always safe when it is alive.  The
            # inline execution stays UNDER the lock: start() also takes
            # it, so a racing start() cannot spawn a ticking loop while
            # the caller thread mutates the loop-owned pool state.
            alive = self._thread is not None and self._thread.is_alive()
            if alive:
                self._ctl.put(rec)
            else:
                return op(payload)
        if not rec["event"].wait(timeout=300):
            raise RuntimeError("batcher loop did not service the request")
        if rec["error"] is not None:
            raise rec["error"]
        return rec["result"]

    def register_prefix(self, prefix_ids) -> int:
        """Prefill a shared prompt prefix (system prompt) ONCE into
        dedicated read-only pages; `submit(..., prefix=handle)` requests
        then reuse them — admission prefills only each request's suffix,
        attending over the shared pages through its page table.  Only
        the full pages share (floor(len/page) * page tokens); the
        remainder rides with each request's suffix.  Write isolation is
        structural: request writes start at the first non-shared
        position, whose table entry is always a request-owned page.
        Returns a handle for submit()/release_prefix()."""
        if not self.paged:
            raise ValueError("prefix caching needs paged=True")
        if self._own_prefill:
            raise ValueError("shared prefixes need slot BLOCK decode, which "
                             f"{type(self.model).__name__} does not have")
        ids = np.asarray(prefix_ids, np.int32).reshape(-1)
        if len(ids) < 1:
            raise ValueError("empty prefix")
        if len(ids) + 1 + self.gamma > self.model.max_len:
            raise ValueError("prefix leaves no room to generate")
        if (self.draft_model is not None
                and len(ids) + 1 + self.gamma > self.draft_model.max_len):
            # mirror submit()'s limit: the dense draft cache must hold the
            # FULL prompt (prefix + suffix), and _bucket caps prefill
            # widths at the draft's max_len — without this check a long
            # prefix dies later in an opaque broadcast error
            raise ValueError(
                f"prefix of {len(ids)} tokens exceeds the draft model's "
                f"max_len {self.draft_model.max_len} - 1 - gamma "
                f"{self.gamma} (speculative mode prefills the full "
                "prompt into the draft cache)")
        return self._ctl_call(self._exec_register_prefix, ids)

    def release_prefix(self, handle: int):
        """Free a prefix's shared pages.  Refuses while any live or
        pending request still uses it."""
        return self._ctl_call(self._exec_release_prefix, int(handle))

    def _exec_register_prefix(self, ids) -> int:
        shared = len(ids) // self.page_size          # full pages only
        if shared > self._avail:
            raise ValueError(
                f"prefix needs {shared} pages but only {self._avail} "
                "are unreserved")
        self._avail -= shared
        pages = [self._free.pop() for _ in range(shared)]
        try:
            b = self._bucket(len(ids))
            padded = np.zeros((1, b), np.int32)
            padded[0, :len(ids)] = ids
            logits, cache = self._prefill(self.variables,
                                          jnp.asarray(padded))
            if shared:
                page_ids = np.full(self._mp, self._np, np.int32)
                page_ids[:shared] = pages
                self._cache = self._load_paged_many(self._cache, cache,
                                                    jnp.asarray(page_ids))
        except Exception:
            # a failed prefill must not leak the pool allocation.  The
            # pools are whole here unless the load program itself died
            # on the device: the load is the block's last call and
            # donates only when it is dispatched, so a prefill, trace,
            # compile or upload error leaves `self._cache` bound to live
            # buffers that hold no row of this prefix.  A load that
            # fails AFTER dispatch has consumed the pools (the next step
            # raises "Array has been deleted"): freeing pages cannot
            # mend that, and the batcher has to be rebuilt
            self._free.extend(pages)
            self._avail += shared
            raise
        handle = self._next_prefix
        self._next_prefix += 1
        # under _submit_lock (re-entrant on the inline path): submit()
        # iterates _prefixes.values() for the page ceiling under this
        # lock from client threads — an unguarded insert from the loop
        # thread would intermittently blow up that iteration
        with self._submit_lock:
            self._prefixes[handle] = {
                "ids": ids, "pages": pages, "shared": shared,
                # logits at the last prefix position: the first generated
                # token when a request adds no suffix
                "last_logits": np.asarray(logits[0, len(ids) - 1]),
                "refs": 0,
            }
        return handle

    def _exec_release_prefix(self, handle: int):
        # the refs check + delete serialize against submit()'s refs
        # increment (both under _submit_lock), so release can never slip
        # between a submit's validation and its increment
        with self._submit_lock:
            rec = self._prefixes[handle]
            if rec["refs"] > 0:
                raise ValueError(f"prefix {handle} still has "
                                 f"{rec['refs']} active request(s)")
            del self._prefixes[handle]
        self._free.extend(rec["pages"])
        self._avail += rec["shared"]

    # ---- client side ---------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               prefix: Optional[int] = None,
               deadline: Optional[float] = None) -> TokenStream:
        """`prefix`: a register_prefix handle — `prompt_ids` is then the
        SUFFIX appended to the shared prefix (may be empty), and
        admission prefills only the suffix.

        `deadline`: absolute `time.monotonic()` budget for ADMISSION — a
        request still waiting for a slot past it is failed fast with a
        TimeoutError on its stream instead of being computed (already
        admitted streams run to completion).  Load shedding: when
        `max_pending` is set and that many requests already wait,
        submit raises Overloaded (serving maps it to 503 + Retry-After)."""
        self._intake.shed_check()
        shared_pages = 0
        if prefix is not None:
            if not self.paged:
                raise ValueError("prefix caching needs paged=True")
            try:
                rec = self._prefixes[prefix]
            except KeyError:
                raise ValueError(f"unknown or released prefix {prefix}")
            prompt = np.concatenate(
                [rec["ids"], np.asarray(prompt_ids, np.int32).reshape(-1)])
            shared_pages = rec["shared"]
        else:
            prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        limit = self.model.max_len - self.gamma
        if self.draft_model is not None:
            # draft writes ride to the same positions (+gamma lookahead)
            limit = min(limit, self.draft_model.max_len - self.gamma)
        if len(prompt) + max_new_tokens > limit:
            raise ValueError(
                f"prompt {len(prompt)} + {max_new_tokens} exceeds "
                f"max_len {self.model.max_len}"
                + (f" - gamma {self.gamma} (speculative lookahead)"
                   if self.gamma else ""))
        req = _Request(prompt, max_new_tokens, eos_id, prefix=prefix,
                       deadline=deadline)
        with self._submit_lock:
            if self.paged:
                worst = self._worst_pages(len(prompt), int(max_new_tokens),
                                          shared_pages)
                # own prefix included in the ceiling — _worst_pages
                # already credits the own prefix's shared count; pages
                # held by other prefixes never return to _avail, so a
                # request that only fits without them would sit at the
                # FIFO head forever, wedging everyone behind it
                ceiling = self._page_ceiling()
                if worst > ceiling:
                    raise ValueError(
                        f"request needs up to {worst} pages but only "
                        f"{ceiling} of the pool's {self._np - 1} can ever "
                        "free up (registered prefixes hold the rest); "
                        "raise num_pages or release prefixes")
            if self._stopped:
                # a late submit racing stop() would otherwise wait forever
                # on a stream nobody will ever close
                raise RuntimeError("ContinuousBatcher is stopped")
            if prefix is not None:
                if prefix not in self._prefixes:  # released since lookup
                    raise ValueError(f"prefix {prefix} was released")
                self._prefixes[prefix]["refs"] += 1
            self._intake.put(req)
        return req.stream

    def stream_text(self, tokenizer, text: str,
                    max_new_tokens: int = 32) -> Iterator[str]:
        """serving.stream_reply-ready: text in, decoded word chunks out.

        Ids buffer until a token COMPLETES a word (tokenizer.is_word_end:
        its vocab string carries the end-of-word marker, or eos), then the
        whole word decodes as one piece — a word split across BPE subword
        tokens must never stream with spaces inside it.  Tokenizers
        without the concept degrade to per-token emission."""
        ids = tokenizer.encode(text, append_eos=False)
        word_end = getattr(tokenizer, "is_word_end", lambda _t: True)
        buf: List[int] = []
        for tok in self.submit(ids, max_new_tokens,
                               eos_id=tokenizer.eos_id):
            buf.append(tok)
            if word_end(tok):
                piece = tokenizer.decode(buf)
                buf.clear()
                if piece:
                    yield piece + " "
        if buf:  # stream ended mid-word (max_new_tokens hit)
            piece = tokenizer.decode(buf)
            if piece:
                yield piece + " "

    # ---- scheduler loop ------------------------------------------------
    def start(self) -> "ContinuousBatcher":
        # under _submit_lock: _ctl_call's inline path decides "no loop
        # thread is running" and mutates pool state under this lock — the
        # spawn must not interleave with that decision
        with self._submit_lock:
            self._running.set()
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="continuous-batcher")
            self._thread.start()
        return self

    def stop(self):
        with self._submit_lock:
            # after this block no submit() can enqueue, so the drain
            # below is complete
            self._stopped = True
        self._running.clear()
        if self._thread is not None:
            # the drain below treats _buffer/_live as single-owner, so the
            # loop thread must actually be DEAD first — one tick can
            # legitimately take tens of seconds (first XLA compile of a
            # new prefill bucket), so keep joining
            # well past that before declaring the loop wedged
            deadline = 300.0
            while self._thread.is_alive() and deadline > 0:
                self._thread.join(timeout=10)
                deadline -= 10
            if self._thread.is_alive():
                raise RuntimeError(
                    "ContinuousBatcher loop thread failed to exit within "
                    "300s; refusing to drain its queues concurrently")
        # the loop left between two iterations, with a step (and maybe an
        # admission) dispatched and not fetched: their tokens belong to
        # their streams, and cache, mirrors and tables agree only once
        # they are in (teacher_force replays in what is left)
        while self._flight:
            self._collect(self._flight.popleft())
        # unblock any consumers still waiting on admitted streams
        for req in self._live:
            if req is not None:
                req.stream._q.put(None)
        # loop thread is dead; the intake (buffer + pending) is ours now —
        # the runtime's one graceful-drain path settles every stream
        self._intake.drain_all(lambda req: req.stream._q.put(None))
        while True:  # unblock any caller waiting on a control op
            try:
                rec = self._ctl.get_nowait()
            except Empty:
                break
            rec["error"] = RuntimeError("ContinuousBatcher is stopped")
            rec["event"].set()

    def _bucket(self, n: int) -> int:
        """Power-of-two prompt bucket so admission compiles O(log
        max_len) prefill shapes total instead of one per distinct length
        (seconds-long XLA stalls in the serving hot path).  The padded
        tail is sound: causal masking keeps positions < n exact, and
        the garbage K/V rows >= n are never attendable — a decode step
        at pos p masks rows > p and overwrites row p itself first."""
        b = 16
        while b < n:
            b *= 2
        b = min(b, self.model.max_len)
        if self.draft_model is not None:
            b = min(b, self.draft_model.max_len)
        return b

    def _admit_batch(self, batch):
        """Admit several (slot, request) pairs with ONE prefill forward
        per prompt bucket: a burst of arrivals costs one device program
        instead of one per request.  Row counts pad to powers of two
        (capped at max_slots) so each bucket compiles O(log max_slots)
        batch shapes; pad rows compute garbage that the slot-indexed
        loads drop (out-of-range sentinel + mode='drop').  Nothing here
        waits for the device: -> the buckets' `_Flight`s, whose first
        tokens `_collect` fetches (a shared prefix's rows, whose first
        tokens the host picks itself, are live on return)."""
        now = time.monotonic()
        queue_wait = telemetry.histogram("serving.batcher.queue_wait")
        for slot, req in batch:
            queue_wait.observe(now - req.submitted_at)
            # slot-wait span on the SUBMITTER's trace (cross-thread hop)
            if req.trace is not None:
                telemetry.record_span("serving.batcher.wait", req.trace,
                                      now - req.submitted_at, slot=slot)
        by_bucket: dict = {}
        prefix_groups: dict = {}
        for slot, req in batch:
            if req.prefix is not None:
                rec = self._prefixes[req.prefix]
                rest = len(req.prompt) - rec["shared"] * self.page_size
                rb = self._bucket(max(rest, 1)) if rest else 0
                prefix_groups.setdefault(rb, []).append((slot, req))
            else:
                by_bucket.setdefault(self._bucket(len(req.prompt)),
                                     []).append((slot, req))
        if prefix_groups:
            self._admit_prefix_groups(prefix_groups)

        def pack_bucket(item):
            # host-side prompt packing for one bucket; runs on the input
            # pipeline so bucket i+1 packs while bucket i's prefill
            # forward occupies the device
            b, group = item
            with telemetry.phase(ADMIT_PACK):
                kp = self._pad_rows(len(group))
                padded = np.zeros((kp, b), np.int32)
                slots = np.full(kp, self.max_slots, np.int32)  # OOB = dropped
                for i, (slot, req) in enumerate(group):
                    padded[i, :len(req.prompt)] = req.prompt
                    slots[i] = slot
            return group, kp, padded, slots

        buckets = sorted(by_bucket.items())
        if self._prefill_cap is not None:
            buckets = [(b, group[i:i + cap]) for b, group in buckets
                       for cap in [self._rows_cap(b)]
                       for i in range(0, len(group), cap)]
        if len(buckets) > 1:
            packed = FlowGraph([PrefillStage(fn=pack_bucket)],
                               label="prefill").run(buckets)
        else:  # one bucket: nothing to overlap, skip the worker thread
            packed = map(pack_bucket, buckets)
        flights = []
        for group, kp, padded, slots in packed:
            t_bucket = time.monotonic()
            if self._own_prefill:
                firsts = self._admit_bucket_own(group, kp, padded, slots)
                flights.append(self._finish_admit(
                    group, firsts, padded.shape[1], kp, t_bucket))
                continue
            with telemetry.phase(ADMIT_PREFILL):
                # the upload rides the feed engine: counted bytes, transfer
                # spans on the request trace, the feed.device_put fault point
                last = np.zeros(kp, np.int32)
                last[:len(group)] = [len(r.prompt) - 1 for _s, r in group]
                ups = [padded, last, slots]
                if self.paged:
                    # allocate each slot's prompt pages: all rows' prefill
                    # pages scatter in one update below; bucketing garbage
                    # inside the last page is masked/overwritten as in dense
                    ids = np.full((kp, self._mp), self._np, np.int32)
                    for i, (slot, req) in enumerate(group):
                        pages = self._take_prompt_pages(slot,
                                                        len(req.prompt))
                        ids[i, :len(pages)] = pages
                    ups.append(ids.reshape(-1))
                d_padded, d_last, d_slots, *d_ids = self._feed.put_group(ups)
                firsts, self._adm, cache = self._prefill_first(
                    self.variables, d_padded, d_last, d_slots, self._adm)
                if self.draft_model is not None:
                    # the draft's cache must hold the same prompt history;
                    # its prefill logits are unused — the first pending
                    # token is the TARGET's (exactness requires it)
                    _dlg, d_rows = self._d_prefill(self.draft_variables,
                                                   d_padded)
                    self._d_cache = self._load_many(self._d_cache, d_rows,
                                                    d_slots)
                if self.paged:
                    self._cache = self._load_paged_many(self._cache, cache,
                                                        *d_ids)
                else:
                    self._cache = self._load_many(self._cache, cache,
                                                  d_slots)
            flights.append(self._finish_admit(
                group, firsts, padded.shape[1], kp, t_bucket))
        return flights

    def _take_prompt_pages(self, slot: int, n: int) -> list:
        """Allocate the full-kind pages of an n-token prompt to `slot`
        and wire its table."""
        pages = [self._free.pop() for _ in range(-(-n // self.page_size))]
        self._slot_pages[slot] = pages
        self._slot_shared[slot] = 0
        self._table[slot].fill(0)
        self._table[slot, :len(pages)] = pages
        return pages

    def _finish_admit(self, group, firsts, bucket: int, kp: int,
                      t_bucket: float) -> _Flight:
        """A bucket's forward and load are dispatched: its slots are
        live from here, their first tokens in flight (`firsts`, on the
        device: `kp` tokens, then the model's statistics)."""
        for slot, req in group:
            self._go_live(slot, req)
        return _Flight(firsts, kp,
                       [(i, slot) for i, (slot, _r) in enumerate(group)],
                       admitted=(group, bucket, kp, t_bucket))

    def _go_live(self, slot: int, req: _Request, first: Optional[int] = None):
        """The slot is `req`'s: the next dispatch writes the position
        behind the prompt.  `first`: its first token where the host
        picked it (the next step is given it); else that token is in
        flight, and in `_adm` for the next step."""
        self._live[slot] = req
        req.limit = min(req.max_new, self.model.max_len - len(req.prompt))
        self._pos[slot] = len(req.prompt)
        if first is None:
            self._inflight[slot] += 1
        else:
            self._tok[slot] = self._give[slot] = first
            self._emit(slot, first)

    def _admit_bucket_own(self, group, kp: int, padded, slots):
        """One bucket through the model's own `prefill`: the logits of
        each row's last position only, cache rows of the bucket's length,
        loaded into the pages of every kind (a window layer keeps only
        the prompt's last ring of pages).  -> the program's vector on
        the device: the first tokens, then the statistics."""
        page, win = self.page_size, self._win
        n_pg = -(-padded.shape[1] // page)
        with telemetry.phase(ADMIT_PREFILL):
            last = np.full(kp, -1, np.int32)       # a pad row has no token
            ids = [np.full((kp, n_pg), self._np, np.int32)]
            if win is not None:
                ids.append(np.full((kp, n_pg), win.np, np.int32))
            for i, (slot, req) in enumerate(group):
                n = len(req.prompt)
                last[i] = n - 1
                pages = self._take_prompt_pages(slot, n)
                ids[0][i, :len(pages)] = pages
                # (query, key) pairs a layer of each kind attends: in
                # all (with the decode ticks'), and the admissions' own
                pairs = n * (n + 1) // 2
                telemetry.incr(self._count["attended"], pairs)
                telemetry.incr(self._count["prefill.attended"], pairs)
                self._note_attn_tiles(n, padded.shape[1])
                if win is not None:
                    for lp, pg in win.admit(slot, n):
                        ids[1][i, lp] = pg
                    w = min(n, win.window)
                    pairs = w * (w + 1) // 2 + (n - w) * w
                    telemetry.incr("serving.batcher.attended.window", pairs)
                    telemetry.incr("serving.batcher.prefill.attended.window",
                                   pairs)
            d_padded, d_last, d_slots, *d_ids = self._feed.put_group(
                [padded, last, slots, *(x.reshape(-1) for x in ids)])
            out, self._adm, rows = self._prefill_last(
                self.variables, d_padded, d_last, d_slots, self._adm)
            self._cache = self._load_kinds(self._cache, rows, tuple(d_ids))
        return out

    def _note_attn_tiles(self, n: int, bucket: int) -> None:
        """Score tiles the admission flash forward visits for a prompt
        of n tokens, one layer of each kind, beside what the bucket's
        whole causal (windowed) schedule holds: their ratio is the share
        of the bucket's attention work the prompts needed.  Nothing
        where the kernel does not run (off one TPU, or a bucket or head
        shape it declines: `prefill_tile_counts`)."""
        from ..ops.attention_kernels import prefill_tile_counts

        for (_name, window), shape in zip(self.model.cache_kinds,
                                          self._attn_shapes):
            own, whole = prefill_tile_counts(
                bucket, n, window, *shape,
                itemsize=jnp.dtype(self.model.dtype).itemsize)
            telemetry.incr("serving.batcher.prefill.attn_tiles", own)
            telemetry.incr("serving.batcher.prefill.attn_tiles_bucket",
                           whole)

    def _rows_cap(self, bucket: int) -> int:
        """Most rows of one admission program of this bucket under
        `_prefill_cap` prompt tokens: a power of two, at least one."""
        cap = 1
        while cap * 2 * bucket <= self._prefill_cap:
            cap *= 2
        return cap

    def _pad_rows(self, k: int) -> int:
        """Rows of a prefill program for `k` requests: the next power of
        two, capped at the slots."""
        kp = 1
        while kp < k:
            kp *= 2
        return min(kp, self.max_slots)

    def _note_prefill(self, rows, bucket: int, kp: int, t_bucket: float):
        """Account one bucket's admission forward: `rows` is (slot,
        request, prompt tokens the forward computed for it); the program
        computed `kp * bucket`.  Each request gets the forward it rode
        as a span on its submitter's trace, and its decode clock starts
        here, with its first token."""
        now = time.monotonic()
        telemetry.incr("serving.batcher.prefill.tokens",
                       sum(n for _s, _r, n in rows))
        telemetry.incr("serving.batcher.prefill.padded_tokens", kp * bucket)
        for slot, req, _n in rows:
            req.first_token_at = now
            if req.trace is not None:
                telemetry.record_span("serving.batcher.prefill", req.trace,
                                      now - t_bucket, bucket=bucket,
                                      rows=kp, slot=slot)

    def _admit_prefix_groups(self, prefix_groups):
        """Admit shared-prefix requests: wire each slot's page table to
        the prefix's read-only pages + freshly allocated own pages, then
        prefill ONLY the suffix via one slot-BLOCK decode per rest
        bucket (the block attends the shared rows through the table —
        exactly the full prefill's math for those positions).  rest=0
        requests skip the forward entirely: their first token comes from
        the logits the prefix registration stored."""
        if self.draft_model is not None:
            # the dense draft cache cannot share pages — prefill the FULL
            # prompts, batched per bucket like _admit_batch (the draft is
            # the cheap model; the TARGET's prefix reuse is the win)
            by_draft_bucket: dict = {}
            for group in prefix_groups.values():
                for slot, req in group:
                    by_draft_bucket.setdefault(
                        self._bucket(len(req.prompt)), []).append((slot, req))
            for db, dgroup in sorted(by_draft_bucket.items()):
                dkp = self._pad_rows(len(dgroup))
                dpad = np.zeros((dkp, db), np.int32)
                dslots = np.full(dkp, self.max_slots, np.int32)
                for i, (slot, req) in enumerate(dgroup):
                    dpad[i, :len(req.prompt)] = req.prompt
                    dslots[i] = slot
                _dl, d_rows = self._d_prefill(self.draft_variables,
                                              jnp.asarray(dpad))
                self._d_cache = self._load_many(self._d_cache, d_rows,
                                                jnp.asarray(dslots))
        for rb, group in sorted(prefix_groups.items()):
            t_bucket = time.monotonic()
            fill = []                  # rows that need a suffix forward
            with telemetry.phase(ADMIT_PACK):
                for slot, req in group:
                    rec = self._prefixes[req.prefix]
                    shared = rec["shared"]
                    shared_tokens = shared * self.page_size
                    n = len(req.prompt)
                    need = -(-n // self.page_size) - shared
                    pages = [self._free.pop() for _ in range(need)]
                    self._slot_pages[slot] = pages
                    self._slot_shared[slot] = shared
                    self._table[slot].fill(0)
                    self._table[slot, :shared] = rec["pages"]
                    self._table[slot, shared:shared + need] = pages
                    if n > shared_tokens:
                        fill.append((slot, req, shared_tokens))
                    else:
                        first = int(np.argmax(rec["last_logits"]))
                        self._note_prefill([(slot, req, 0)], 0, 0, t_bucket)
                        self._go_live(slot, req, first)
                if not fill:
                    continue
                k = len(fill)
                kp = self._pad_rows(k)
                toks = np.zeros((kp, rb), np.int32)
                pos = np.zeros(kp, np.int32)
                tables = np.zeros((kp, self._mp), np.int32)
                for i, (slot, req, st) in enumerate(fill):
                    toks[i, :len(req.prompt) - st] = req.prompt[st:]
                    pos[i] = st
                    tables[i] = self._table[slot]
            with telemetry.phase(ADMIT_PREFILL):
                d_toks, d_fpos, d_tbls = self._feed.put_group(
                    [toks, pos, tables])
                logits, self._cache = self._block_step(
                    self.variables, d_toks, self._cache, d_fpos, d_tbls)
            with telemetry.phase(ADMIT_FIRST_TOKEN):
                firsts = np.asarray(jnp.argmax(logits[
                    jnp.arange(kp), jnp.asarray(
                        [len(r.prompt) - st - 1 for _s, r, st in fill]
                        + [0] * (kp - k))], axis=-1), np.int32)
            self._note_prefill(
                [(slot, req, len(req.prompt) - st) for slot, req, st in fill],
                rb, kp, t_bucket)
            for i, (slot, req, _st) in enumerate(fill):
                self._go_live(slot, req, int(firsts[i]))

    def _emit(self, slot: int, tok: int):
        """Hand `tok` to the slot's stream, and end the request where it
        ends.  The slot goes back at once unless a token of its is still
        in flight (an `eos_id` end, module doc): then `_collect`
        releases it with that step's dropped token."""
        req = self._live[slot]
        req.emitted += 1
        req.stream._q.put(tok)
        if (req.emitted >= req.limit
                or (req.eos_id is not None and tok == req.eos_id)):
            req.stream._q.put(None)
            req.closed = True
            if req.trace is not None:
                telemetry.record_span(
                    "serving.batcher.decode", req.trace,
                    time.monotonic() - req.first_token_at,
                    tokens=req.emitted, slot=slot)
            if not self._inflight[slot]:
                self._release(slot)

    def _release(self, slot: int):
        """A finished request's slot, pages and reservation go back."""
        req = self._live[slot]
        self._live[slot] = None
        # park the freed slot at position 0: a slot that finished
        # near max_len must not leave a stale pos that speculative
        # lookahead (pos + gamma) could push past the cache bound
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._give[slot] = -1
        if self.paged:  # return OWNED pages + release the reservation
            self._free.extend(self._slot_pages[slot])
            self._slot_pages[slot] = []
            self._slot_shared[slot] = 0
            self._table[slot].fill(0)
            self._avail += self._slot_reserved[slot]
            self._slot_reserved[slot] = 0
            if self._win is not None:
                self._win.release(slot)
            if req.prefix is not None:
                with self._submit_lock:
                    self._prefixes[req.prefix]["refs"] -= 1

    def _drain_intake(self):
        while True:  # control ops first: admissions may depend on them
            try:
                rec = self._ctl.get_nowait()
            except Empty:
                break
            try:
                rec["result"] = rec["op"](rec["payload"])
            except Exception as e:  # noqa: BLE001 — surfaced to the caller
                rec["error"] = e
            rec["event"].set()
        self._intake.drain_to_buffer()

    def _try_admit(self):
        """Admit from the FIFO head into free slots — collected into ONE
        batched prefill (_admit_batch)."""
        batch = self._plan_admit()
        if batch:
            self._admit_batch(batch)

    def _plan_admit(self) -> list:
        """The (slot, request) pairs the next admission takes, host work
        only.  Paged mode admits only while the head's worst-case page
        reservation fits the unreserved budget — strict FIFO (no
        skipping), so a big request can't be starved by a stream of
        small ones."""
        if self.paged:
            # fail-fast pre-pass: a prefix registered AFTER a request
            # passed submit()'s ceiling check can shrink the achievable
            # budget below its reservation — a head that can NEVER fit
            # must error its stream, not wedge the FIFO forever
            ceiling = self._page_ceiling()
            while self._buffer:
                head = self._buffer[0]
                shared = (self._prefixes[head.prefix]["shared"]
                          if head.prefix is not None else 0)
                if self._worst_pages(len(head.prompt), head.max_new,
                                     shared) <= ceiling:
                    break
                self._buffer.popleft()
                if head.prefix is not None:
                    with self._submit_lock:
                        self._prefixes[head.prefix]["refs"] -= 1
                head.stream.error = RuntimeError(
                    "request dropped: its worst-case page reservation "
                    f"exceeds the {ceiling} pages that can ever free up "
                    "(prefixes registered after submit hold the rest)")
                head.stream._q.put(None)
        if any(r.deadline is not None for r in self._buffer):
            # fail-fast: an expired request must not consume a prefill —
            # its client has already given up (deadline semantics match
            # WorkerServer._admit; docs/robustness.md).  The reap itself
            # is the AdmissionStage's one code path.
            def _expire(req: _Request):
                if req.prefix is not None:
                    with self._submit_lock:
                        self._prefixes[req.prefix]["refs"] -= 1
                req.stream.error = TimeoutError(
                    "request deadline expired before batch admission")
                req.stream._q.put(None)

            self._intake.reap_expired(lambda r: r.deadline, _expire)
        batch = []
        for slot in range(self.max_slots):
            if not self._buffer:
                break
            if self._live[slot] is not None:
                continue
            req = self._buffer[0]
            if self.paged:
                shared = (self._prefixes[req.prefix]["shared"]
                          if req.prefix is not None else 0)
                worst = self._worst_pages(len(req.prompt), req.max_new,
                                          shared)
                if worst > self._avail:
                    break
                if self._win is not None:
                    worst_w = self._win.worst(len(req.prompt) + req.max_new)
                    if worst_w > self._win.avail:
                        break
                    self._win.avail -= worst_w
                    self._win.slot_reserved[slot] = worst_w
                self._avail -= worst
                self._slot_reserved[slot] = worst
            self._buffer.popleft()
            batch.append((slot, req))  # each slot index visited once
        return batch

    def _loop(self):
        while self._running.is_set():
            if (not self._buffer and self._ctl.empty()
                    and all(r is None for r in self._live)):
                # nothing to decode and nothing waiting: this is not a
                # tick.  A device idle for want of requests reads as
                # this span, not as unexplained
                with telemetry.phase(IDLE):
                    try:
                        self._buffer.append(
                            self._intake.get(timeout=self.idle_sleep_s))
                    except Empty:
                        continue
            self._tick()

    def _tick(self):
        """One loop iteration with work to do: drain the intake, admit
        into free slots, DISPATCH one decode step for every slot that
        wants a token, then fetch and emit what was dispatched before it
        (module doc, ONE STEP AHEAD).  The timers are the loop's own:
        `admit.latency` is the admission (packing and dispatch, and the
        fetch of its first tokens), `tick.latency` the iteration without
        it, `tick.host` the same without the blocking fetch, which is
        the PREVIOUS step's: what the host does beside the step it has
        just queued.  An iteration that dispatches no step (nothing
        could be admitted, or it only fetches the last tokens in flight)
        observes neither."""
        t0 = time.perf_counter()
        admit_s = fetch_s = 0.0
        with telemetry.phase(TICK):
            with telemetry.phase(TICK_INTAKE):
                self._drain_intake()
            batch = self._plan_admit()
            if batch:
                with telemetry.phase(TICK_ADMIT) as admit:
                    flights = self._admit_batch(batch)
                    if self.draft_model is not None:
                        # the speculative round proposes from the host's
                        # tokens: it needs them now
                        for flight in flights:
                            self._collect(flight)
                    else:
                        self._flight.extend(flights)
                admit_s = admit.elapsed_s
            # the slots this iteration's step makes a token for: live,
            # and short of their last token even with those in flight.
            # Every other slot is parked in it.  (None live and nothing
            # in flight -> every reservation is released, so the head
            # always fits; the next iteration admits it)
            active = [s for s, req in enumerate(self._live)
                      if req is not None and not req.closed
                      and req.emitted + self._inflight[s] < req.limit]
            if active:
                telemetry.histogram("serving.batcher.batch_fill").observe(
                    len(active) / self.max_slots)
                # the K/V rows this step's attention has to read
                telemetry.incr("serving.batcher.live_tokens",
                               int(self._pos[active].sum()))
                if self.paged:
                    # grow each active slot's page list just-in-time for
                    # this step's write positions — speculative mode
                    # writes up to pos + gamma (the admission reservation
                    # guarantees the free list can cover it)
                    with telemetry.phase(TICK_GROW):
                        self._grow_pages(active)
            if self.draft_model is None:
                fetch_s, first_s = self._decode_tick(active)
                admit_s += first_s
            elif active:
                fetch_s = self._speculative_tick(active)
            tick_s = time.perf_counter() - t0 - admit_s
        if batch:
            telemetry.histogram("serving.batcher.admit.latency").observe(
                admit_s)
        if active:
            telemetry.histogram("serving.batcher.tick.latency").observe(
                tick_s)
            telemetry.histogram("serving.batcher.tick.host").observe(
                max(0.0, tick_s - fetch_s))

    def _grow_pages(self, active) -> None:
        """Make the pages of this tick's write positions exist, in every
        kind of pool, and count the two page populations (pages in use,
        summed a tick as `live_tokens` is) with the K/V rows a layer of
        each kind attends."""
        for sl in active:
            idx = (int(self._pos[sl]) + self.gamma) // self.page_size
            while idx >= (self._slot_shared[sl]
                          + len(self._slot_pages[sl])):
                pg = self._free.pop()
                self._table[sl, self._slot_shared[sl]
                            + len(self._slot_pages[sl])] = pg
                self._slot_pages[sl].append(pg)
        pos = self._pos[active].astype(np.int64) + 1
        telemetry.incr(self._count["pages"],
                       sum(len(self._slot_pages[sl]) for sl in active))
        telemetry.incr(self._count["attended"], int(pos.sum()))
        win = self._win
        if win is None:
            return
        recycled = sum(win.grow(sl, int(self._pos[sl])) for sl in active)
        telemetry.incr("serving.batcher.pages.window",
                       sum(len(win.slot_pages[sl]) for sl in active))
        telemetry.incr("serving.batcher.attended.window",
                       int(np.minimum(pos, win.window).sum()))
        if recycled:
            telemetry.incr("serving.batcher.pages.window_recycled", recycled)

    def _decode_tick(self, active):
        """Dispatch ONE batched step for `active`, then fetch and emit
        everything dispatched before it: the step of the iteration
        before, and this iteration's admission.  -> (seconds blocked on
        that step, seconds spent on the admission's first tokens)."""
        older = list(self._flight)
        self._flight.clear()
        if active:
            if any(f.admitted is None for f in older):
                telemetry.incr(TICK_OVERLAPPED)
            self._flight.append(self._dispatch(active))
        fetch_s = first_s = 0.0
        for flight in older:
            if flight.admitted is None:
                fetch_s += self._collect(flight)
            else:
                with telemetry.phase(TICK_ADMIT) as admit:
                    self._collect(flight)
                first_s += admit.elapsed_s
        return fetch_s, first_s

    def _dispatch(self, active) -> _Flight:
        """Queue ONE batched step for every slot: an `active` slot
        writes its row at its position, every other slot is PARKED
        (position 0 over a zeroed table row: a free slot, or one whose
        last token is still to be fetched, computes too, and its write
        is dead — dense mode overwrites the rows on admit, paged mode
        routes them to the trash page).  Token and position come from
        the step before, on the device; the host uploads, in ONE packed
        put, the override and the tables, and only when the device's
        copies are stale."""
        with telemetry.phase(TICK_UPLOAD):
            go = np.zeros(self.max_slots, bool)
            go[active] = True
            pos = np.where(go, self._pos, 0)
            tables = [self._table] if self.paged else []
            if self._win is not None:
                tables.append(self._win.table)
            tables = [np.where(go[:, None], t, 0) for t in tables]
            stale = self._sent is None or not all(
                map(np.array_equal, tables, self._sent))
            ovr = np.stack([np.where(go, self._give, -1),
                            np.where(pos != self._held, pos, -1)])
            self._give[active] = -1
            d_ovr = self._keep
            if stale or (ovr >= 0).any():
                d_ovr, *d_tables = self._feed.put_group([ovr, *tables])
                self._d_tables, self._sent = tuple(d_tables), tables
            self._held = pos + (pos > 0)    # what the step hands back
            self._pos[active] += 1
            self._inflight[active] += 1
        with telemetry.phase(TICK_DISPATCH):
            self._d_out, self._d_pos, self._cache = self._step(
                self.variables, self._cache, self._d_out, self._d_pos,
                d_ovr, self._adm, self._d_tables)
            self._adm = self._none
        return _Flight(self._d_out, self.max_slots,
                       [(slot, slot) for slot in active])

    def _collect(self, flight: _Flight) -> float:
        """Fetch a dispatched program's tokens (and the model's
        statistics behind them) and hand each slot's to its stream.  A
        slot whose request ended while this ran (an `eos_id` in the
        token before) gets nothing: the token is counted and dropped,
        and the slot goes back with its last one.  -> the seconds
        blocked on the device."""
        admission = flight.admitted is not None
        with telemetry.phase(ADMIT_FIRST_TOKEN if admission
                             else TICK_FETCH) as fetch:
            nxt = np.asarray(flight.out)
        with telemetry.phase(TICK_EMIT):
            self._note_stats(nxt[flight.n_tok:])
            if admission:
                group, bucket, kp, t_bucket = flight.admitted
                self._note_prefill(
                    [(slot, req, len(req.prompt)) for slot, req in group],
                    bucket, kp, t_bucket)
            late = 0
            for row, slot in flight.rows:
                self._inflight[slot] -= 1
                if self._live[slot].closed:
                    late += 1
                    if not self._inflight[slot]:
                        self._release(slot)
                else:
                    self._tok[slot] = nxt[row]
                    self._emit(slot, int(nxt[row]))
            telemetry.incr(TICK_LATE_DISCARDS, late)
        return fetch.elapsed_s

    def _speculative_tick(self, active) -> float:
        """One speculative round for ALL slots: (gamma+1) draft slot
        steps propose, ONE target slot-block step verifies, each slot
        emits its accepted prefix + the target's own next token — the
        per-slot speculative-decoding recurrence (speculative_generate's
        round, vectorized over co-tenant slots).  The +1 extra draft
        step writes the would-be-next K/V row so a fully-accepted round
        leaves no hole in the draft cache.  Returns the seconds the host
        stood blocked on the device (both fetches)."""
        g = self.gamma
        with telemetry.phase(TICK_DRAFT):
            dpos = self._pos.copy()
            # the round's first draft step is the only one that uploads
            # host data (later steps chain device outputs): tok+pos ride
            # one packed transfer; per-step position bumps re-upload
            # through the feed so the telemetry sees every byte on the wire
            d_tok, d_pos = self._feed.put_group([self._tok[:, None], dpos])
            prop_list = []
            for i in range(g + 1):
                lg, self._d_cache = self._d_step(
                    self.draft_variables, d_tok, self._d_cache, d_pos)
                nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
                if i < g:
                    # keep proposals ON DEVICE: a host sync here would
                    # block async dispatch of the next draft step
                    prop_list.append(nxt)
                d_tok = nxt[:, None]
                dpos += 1
                if i < g:
                    d_pos = self._feed.put(dpos)
            with telemetry.phase(TICK_FETCH) as fetch_props:
                props = np.asarray(jnp.stack(prop_list, axis=1),
                                   np.int32)                    # [S, g]
        # ONE target forward verifies every slot's pending token + its g
        # proposals at the slot's own position: logits[:, j] predicts
        # position pos+j+1
        with telemetry.phase(TICK_UPLOAD):
            block = np.concatenate([self._tok[:, None], props], axis=1)
            if self.paged:
                d_blk, d_vpos, d_tbl = self._feed.put_group(
                    [block, self._pos, self._table])
            else:
                d_blk, d_vpos = self._feed.put_group([block, self._pos])
                d_tbl = None
        with telemetry.phase(TICK_DISPATCH):
            lg, self._cache = self._block_step(
                self.variables, d_blk, self._cache, d_vpos, d_tbl)
        with telemetry.phase(TICK_FETCH) as fetch:
            t_pred = np.asarray(jnp.argmax(lg, axis=-1), np.int32)  # [S, g+1]
        with telemetry.phase(TICK_EMIT):
            for slot in active:
                match = t_pred[slot, :g] == props[slot]
                m = int(np.argmin(np.concatenate(
                    [match, np.zeros(1, bool)])))                   # 0..g
                for j in range(m + 1):
                    tok = (int(props[slot, j]) if j < m
                           else int(t_pred[slot, m]))
                    self._pos[slot] += 1
                    self._tok[slot] = tok
                    self._emit(slot, tok)
                    if self._live[slot] is None:
                        break  # finished mid-block: discard the rest
        return fetch_props.elapsed_s + fetch.elapsed_s
