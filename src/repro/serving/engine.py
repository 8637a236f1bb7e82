"""Site-local serving engine: KV-cache slots + batched continuous batching.

This is the per-site engine the paper assumes (vLLM in their testbed) —
built here in JAX because Heron needs a real serving substrate to route
into. Design:

  * a fixed pool of ``max_batch`` cache *slots*; each slot owns one
    sequence's decode cache (KV / recurrent state, family-specific pytree);
  * **continuous batching**: new requests are admitted into free slots via
    a *batched admission pipeline* (below); every engine step runs ONE
    batched decode over all slots (fixed shapes → one compiled program);
  * finished sequences retire their slot immediately via ``release_slot``
    — no batch barriers;
  * per-request TTFT / TBT / E2E metrics (means and p50/p99 tails) against
    the class SLOs, which is what Heron's goodput accounting consumes.

Batched admission pipeline (the burst path — a site absorbing a drained
neighbour's traffic sees all of its requests at once):

  1. waiting requests are grouped by the largest power-of-2 prefix of
     their prompt (*bucket*) and prefilled TOGETHER — one compiled
     ``prefill`` call per (bucket, pow2-padded batch) shape;
  2. each prompt's tail (prompt minus bucket) runs through DESCENDING
     power-of-2 chunks of ``Model.extend_fn`` — prefill continued from the
     engine cache at per-row offsets. Tails are the binary digits of the
     remaining length, so a round admits every slot that has the current
     chunk-size bit set: O(log S) compiled calls shared across the whole
     admission group, instead of up to S/2 serial B=1 decodes per request;
  3. a per-step admission token budget (``admit_token_budget``) bounds how
     many prompt tokens one ``step()`` may prefill, so already-live slots'
     TBT cannot balloon under a thundering herd (at least one request is
     always admitted so oversized prompts cannot starve).

On the paged cache an extend call runs only the rows that take its chunk,
padded to the next power of two; on the dense cache it runs at the
engine's fixed batch with a row mask (masked rows keep their old cache
bits). The compile cache stays O(log max_seq) x O(log max_batch) paged
extend entries (O(log max_seq) dense) + O(log max_seq) x
O(log max_batch) prefill entries + one decode entry. Right-padding
prompts instead would corrupt recurrent/SSM states and shift last-token
logits, so it is deliberately not used. ``admit_mode="serial"`` keeps
the old one-request-at-a-time path (pow2-prefix prefill + B=1 decode
tail) as the equivalence reference.

Paged KV cache (``paged=True``)
-------------------------------
The dense layout burns a full ``max_seq`` cache row per slot and every
attention call streams all of it, live or dead. The paged layout
(``models.transformer.make_paged_decode_cache``; GQA attention-trunk
families — dense / moe / vlm / enc-dec self-attention, incl. int8; MLA
and recurrent families silently stay dense, their state is O(1) per
token) replaces rows with a shared pool of ``num_pages`` pages of
``page_size`` tokens addressed through a per-slot block table:

  * pages for a request's whole contract (prompt + max_new_tokens - 1)
    are reserved at admission from a host-side free list — admission
    REJECTS a request that could never fit the pool and simply *waits*
    when the pool is temporarily exhausted; nothing live is ever evicted
    to make room, and the admission error path returns every reserved
    page (no leak);
  * freed pages (``release_slot``, preempt, finish) recycle to any later
    request — fragmentation is impossible by construction since pages
    are interchangeable;
  * decode runs over the block table SLICED to the smallest power-of-2
    page count covering the live slots, so short sequences stop paying
    attention bandwidth for the dead tail of ``max_seq`` — the compile
    cache stays O(log max_seq/page_size) decode variants (admission
    extends run at the full table width over only the rows that take
    the chunk, keyed by chunk length and power-of-two row count);
  * ``num_pages`` defaults to dense-equivalent capacity
    (``max_batch * max_seq / page_size``) but the two knobs decouple:
    the same byte budget can back far more *slots* than the dense
    layout could hold when typical sequences are short — that is where
    the paged burst-TTFT win comes from;
  * the paged attention math is BITWISE the dense math (the gathered
    page view carries identical live bits; the softmax denominator pads
    to max_seq — see ``models.layers.paged_view``), so token streams
    are bit-identical across ``paged`` on/off and every ``admit_mode``.

Async admission (``admit_mode="async"``)
----------------------------------------
"batched" still runs a whole admission wave to completion before the
step's decode — a burst stalls in-flight decodes for the full wave.
"async" splits admission across steps and interleaves it with decode:

  1. a persistent pending set carries each admitted-but-unfinished
     prompt tail (slot -> consumed offset) across steps, its slot's
     pages already reserved (the allocation buffer) while its cache
     fills chunk by chunk (the insertion buffer) — double-buffered in
     the JAX async-dispatch sense: the host schedules the next chunk's
     pages and inserts while the device still runs the previous
     dispatch, and the decode for live slots queues behind them without
     a host sync;
  2. a token-budget arbiter (``admit_token_budget``, default
     ``max_seq`` tokens per step) spends each step's budget on, in
     order: one guaranteed descending-pow-2 extend chunk for the oldest
     tails (no starvation), new-request bucket prefills, then leftover
     budget on more tail chunks — so fresh bursts never stall in-flight
     decodes for more than a bounded slice of work;
  3. the step's decode then runs over live slots with pending slots
     row-masked (dense: cache select; paged: their table rows sentinel
     out, so their in-flight pages are untouched).

``admit_mode="serial"`` still guarantees: exact one-request-at-a-time
admission order, one prefill + B=1 decode tail per request, and the
pinned reference token stream — "batched" and "async" are REQUIRED to
reproduce it bit-identically (per-(seed, rid, token-index) sampling keys
make streams independent of admission interleaving), which is what the
equivalence tests pin.

Sampling policy: every token draw uses a key derived from (engine seed,
request id, token index) — see ``serving.sampling.fold_keys`` — so a
request's token stream is bit-identical regardless of admission order,
batching, or slot placement. (Previous engines split one engine-global
key per step, which made streams depend on batch composition.) Per-row
temperatures still let greedy (t == 0) and sampled requests coexist in
one batched decode.

Cache insertion is family-agnostic: every cache leaf is [B]-batched at
axis 0 (1-D leaves like ``pos``) or axis 1 (stacked [L, B, ...] leaves),
so one ``dynamic_update_slice`` rule covers GQA/MLA/SSM/hybrid/enc-dec.

Request lifecycle (fault tolerance)
-----------------------------------
A request moves through::

    queued --admit--> admitted/live --finish--> completed
       |                  |   |
       | (watermark /     |   +--deadline--> timed_out
       |  oversize /      +--preempt--> snapshot --resume--> queued (again)
       |  deadline)                          |
       +--> rejected / timed_out             +--(budget spent)--> failed

Heron's premise is that sites *lose power mid-decode*. ``preempt(slots)``
snapshots each in-flight request's full transcript (prompt + generated
tokens) into a ``checkpoint.store.TranscriptSnapshot`` and frees the
slot; ``drain()`` is the site-death path (every live slot plus the
waiting queue). ``resume(snapshot)`` re-admits the transcript — the
whole prompt+generated prefix replays through the admission pipeline's
prefill-from-cache chunks, and sampling continues at token index
``len(tokens)``. Because every draw is keyed by (seed, rid, token-index)
and the snapshot carries the seed that keyed the stream, a preempted-
and-resumed request's token stream is **bit-identical** to the
uninterrupted run — on any engine serving the same model, regardless of
that engine's own seed. That identity is this module's pinned
correctness anchor (tests/test_faults.py), and it is also what makes
cross-site failover accounting honest: recovered tokens are real tokens
the user would have received anyway, never a divergent re-generation.

Spans and stamps: every step writes host spans through
``jax.profiler.TraceAnnotation`` (``engine.step`` and, nested in it,
``engine.admit``, ``engine.prefill``, ``engine.extend``,
``engine.first_token``, ``engine.decode``, ``engine.sample``,
``engine.retire``), with the row counts each dispatch serves and computes
as arguments. They record only while a profiler session runs, on the
profiler's clock beside the device's ops. ``Request.admit_s`` stamps when a
request left the queue for a slot, on the engine clock, so a TTFT splits
into the wait before admission and the service after it.

Backpressure and brownout: ``queue_watermark`` rejects new submissions
beyond a queue depth (fail fast under overload); ``set_brownout(frac)``
enters power-brownout mode — admissions shed their ``max_new_tokens``
to ``ceil(frac * requested)`` (graceful degradation instead of drops)
and the per-step admission token budget scales by ``frac``. Requests
may carry a ``deadline_s`` (absolute, engine clock) after which they
time out whether queued or live, and a ``not_before_s`` backoff gate
(see ``retry_backoff``) so failover retries don't thundering-herd a
surviving site. ``EngineMetrics`` keeps the watchdog ledger: lost vs
recovered vs duplicated tokens, preemptions, resumes, timeouts, shed
tokens — ``reconcile()`` checks the books balance.
"""
from __future__ import annotations

import functools
import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import TranscriptSnapshot
from repro.configs.base import ModelConfig
from repro.stats import percentile
from repro.models.api import Model
from repro.serving.sampling import fold_idx, fold_keys, sample_batch


def retry_backoff(attempts: int, *, base: float = 0.05,
                  cap: float = 2.0) -> float:
    """Capped exponential backoff delay before retry ``attempts`` (1-based):
    ``min(base * 2**(attempts-1), cap)``. Deterministic (no jitter) so
    chaos runs replay exactly; the per-request sampling keys make jitter
    unnecessary for correctness."""
    return min(base * (2.0 ** max(attempts - 1, 0)), cap)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [S] int32 token ids
    max_new_tokens: int                 # TOTAL tokens (incl. resumed prefix)
    arrival_s: float = 0.0
    temperature: float = 0.0
    # fault-tolerance lifecycle
    seed: Optional[int] = None          # sampling-seed override; a resumed
    #                                     request carries its origin seed so
    #                                     its stream survives engine changes
    deadline_s: Optional[float] = None  # absolute deadline (engine clock)
    not_before_s: float = 0.0           # backoff gate for (re-)admission
    attempts: int = 0                   # admission/failover attempts so far
    resumed_from: int = 0               # tokens carried in from a snapshot
    # filled by the engine
    tokens: list = field(default_factory=list)
    admit_s: Optional[float] = None     # first left the queue for a slot
    prefill_done_s: Optional[float] = None
    finish_s: Optional[float] = None

    @property
    def ttft(self) -> Optional[float]:
        if self.prefill_done_s is None:
            return None
        return self.prefill_done_s - self.arrival_s

    @property
    def e2e(self) -> Optional[float]:
        if self.finish_s is None:
            return None
        return self.finish_s - self.arrival_s

    @property
    def tbt(self) -> Optional[float]:
        if self.finish_s is None or len(self.tokens) < 2:
            return None
        return (self.finish_s - self.prefill_done_s) / max(len(self.tokens) - 1, 1)


def _host_span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Host span ``engine.<name>`` with integer arguments; it costs about a
    microsecond and records nothing unless a profiler session runs."""
    return jax.profiler.TraceAnnotation("engine." + name, **args)


def _insert_leaf(engine_leaf, req_leaf, slot: int):
    """Write a single-sequence cache leaf into slot ``slot``."""
    req_leaf = req_leaf.astype(engine_leaf.dtype)
    if engine_leaf.ndim == 1:                       # e.g. pos: [B]
        return jax.lax.dynamic_update_slice(engine_leaf, req_leaf, (slot,))
    # stacked leaves: [L, B, ...] — batch at axis 1, write at origin elsewhere
    start = (0, slot) + (0,) * (engine_leaf.ndim - 2)
    return jax.lax.dynamic_update_slice(engine_leaf, req_leaf, start)


def _take_rows(leaf, rows):
    """Rows ``rows`` of a [B]-batched cache leaf (batch at axis 0 for 1-D
    leaves, axis 1 for stacked ones); an id past the batch reads zeros."""
    return jnp.take(leaf, rows, axis=0 if leaf.ndim == 1 else 1,
                    mode="fill", fill_value=0)


@jax.jit
def insert_cache(engine_cache, req_cache, slot):
    """Insert a B=1 request cache into the engine's slot ``slot``."""
    return jax.tree.map(lambda e, r: _insert_leaf(e, r, slot),
                        engine_cache, req_cache)


@jax.jit
def insert_cache_rows(engine_cache, group_cache, slots):
    """Scatter a batched prefill cache into engine slots: row ``r`` of
    ``group_cache`` lands in slot ``slots[r]``, one compiled call (and one
    functional cache copy) per (bucket, batch) shape for the whole group.
    Out-of-range slot ids drop their row — how pow2 padding rows and their
    garbage prefill results are discarded."""
    def ins(e, g):
        g = g.astype(e.dtype)
        if e.ndim == 1:
            return e.at[slots].set(g, mode="drop")
        idx = (slice(None), slots) + tuple(slice(0, d) for d in g.shape[2:])
        return e.at[idx].set(g, mode="drop")

    return jax.tree.map(ins, engine_cache, group_cache)


@jax.jit
def insert_cache_pages(pool_kv, group_kv, page_map):
    """Scatter a batched DENSE prefill's kv cache ([L, kb, S, ...] leaves)
    into the engine's paged pools ([L, P, page, ...] leaves): row ``r``'s
    tokens land in physical pages ``page_map[r]`` ([kb, npages] int32,
    with ``npages = ceil(S / page)``). Sentinel entries (>= P) drop their
    page — how pow2 padding rows AND table entries beyond a slot's
    reservation are discarded. One compiled call per (bucket, batch)
    shape, exactly like the dense ``insert_cache_rows``."""
    def ins(pool, g):
        g = g.astype(pool.dtype)
        page = pool.shape[2]
        L, kb, S = g.shape[:3]
        npr = page_map.shape[1]
        if npr * page > S:
            g = jnp.pad(g, ((0, 0), (0, 0), (0, npr * page - S))
                        + ((0, 0),) * (g.ndim - 3))
        g = g.reshape(L, kb, npr, page, *g.shape[3:])
        return pool.at[:, page_map].set(g, mode="drop")

    return jax.tree.map(ins, pool_kv, group_kv)


# shared percentile helper (core.stats): empty samples report NaN, not a
# fake-perfect 0.0 — an engine that completed nothing has no tail
_pct = percentile


@dataclass
class EngineMetrics:
    completed: list
    rejected: list = field(default_factory=list)
    timed_out: list = field(default_factory=list)
    steps: int = 0
    prefills: int = 0          # requests admitted (one prefill each, logically)
    prefill_calls: int = 0     # compiled model dispatches spent on admission
    # watchdog ledger (preempt/resume fault tolerance)
    submitted: int = 0         # requests accepted into the queue
    preemptions: int = 0       # live slots snapshotted + freed
    evicted: int = 0           # snapshots handed out (preempted + drained)
    resumed: int = 0           # snapshots re-admitted on this engine
    recovered_tokens: int = 0  # tokens carried into a resume (not re-sampled)
    lost_tokens: int = 0       # generated tokens discarded (timeout/failure)
    duplicated_tokens: int = 0 # tokens re-emitted past a delivery high-water
    #                            mark — MUST stay 0; nonzero means a request
    #                            was resumed behind its own stream
    shed_tokens: int = 0       # max_new_tokens haircut under brownout
    # decode-utilization counters (async admission overlap accounting)
    decode_steps: int = 0      # decode dispatches with >= 1 live row
    extend_chunks: int = 0     # masked extend-chunk dispatches (admission
    #                            tails interleaved between decode steps)
    admit_stall_steps: int = 0 # steps that did admission work with ZERO
    #                            live decode rows — pure stalls the async
    #                            pipeline exists to shrink

    def summary(self) -> dict:
        ttfts = [r.ttft for r in self.completed if r.ttft is not None]
        e2es = [r.e2e for r in self.completed if r.e2e is not None]
        tbts = [r.tbt for r in self.completed if r.tbt is not None]
        f = lambda xs: float(np.mean(xs)) if xs else 0.0
        out = {"num_completed": len(self.completed), "steps": self.steps,
               "prefills": self.prefills, "prefill_calls": self.prefill_calls,
               "rejected": len(self.rejected),
               "timed_out": len(self.timed_out),
               "submitted": self.submitted,
               "preemptions": self.preemptions,
               "resumed": self.resumed,
               "served_tokens": sum(len(r.tokens) for r in self.completed),
               "recovered_tokens": self.recovered_tokens,
               "lost_tokens": self.lost_tokens,
               "duplicated_tokens": self.duplicated_tokens,
               "shed_tokens": self.shed_tokens,
               "decode_steps": self.decode_steps,
               "extend_chunks": self.extend_chunks,
               "admit_stall_steps": self.admit_stall_steps,
               "mean_ttft": f(ttfts), "mean_tbt": f(tbts), "mean_e2e": f(e2es)}
        # tail percentiles: what the goodput accounting and the serving
        # bench consume — burst admission shows up in p99, not the mean
        for name, xs in (("ttft", ttfts), ("tbt", tbts), ("e2e", e2es)):
            out[f"p50_{name}"] = _pct(xs, 50)
            out[f"p99_{name}"] = _pct(xs, 99)
        return out


class ServingEngine:
    """Continuous-batching engine over one model replica.

    ``admit_mode``: "batched" (default — grouped prefill + chunked extend
    tails), "serial" (the reference: one request at a time, B=1 decode
    tail) or "async" (admission split across steps and interleaved with
    decode under a token-budget arbiter). Token streams are bit-identical
    across all three.
    ``admit_token_budget``: max prompt tokens admitted per step (None =
    unlimited for batched/serial, ``max_seq`` for async); bounds TBT
    inflation for live slots under bursts.
    ``queue_watermark``: max waiting-queue depth before ``submit`` rejects
    (None = unbounded) — the fail-fast half of backpressure; the
    shed-to-shorter half is ``set_brownout``.
    ``paged=True`` swaps the dense per-slot cache rows for the shared
    page pool + block tables (see module docstring); ``page_size`` tokens
    per page, ``num_pages`` pool size (default: dense-equivalent
    capacity). Families without a paged layout (MLA, recurrent) silently
    stay dense.

    Placement: the engine runs on the one device that holds ``params``.
    Its cache, sampling keys and per-step inputs are placed there too, so
    every program it dispatches executes on that device — engines whose
    params sit on different chips of a host run side by side.
    """

    def __init__(self, model: Model, params, *, max_batch: int = 8,
                 max_seq: int = 512, eos_token: int = -1, seed: int = 0,
                 clock=None, admit_mode: str = "batched",
                 admit_token_budget: Optional[int] = None,
                 queue_watermark: Optional[int] = None,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None):
        if admit_mode not in ("batched", "serial", "async"):
            raise ValueError(f"admit_mode {admit_mode!r}")
        self.model = model
        self.cfg: ModelConfig = model.cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos = eos_token
        self.admit_mode = admit_mode
        self.admit_token_budget = admit_token_budget
        self.queue_watermark = queue_watermark
        self.seed = seed
        self.brownout = 1.0
        devices = {d for leaf in jax.tree.leaves(params)
                   for d in leaf.devices()}
        if len(devices) != 1:
            raise ValueError("ServingEngine params must live on exactly one "
                             f"device, found {sorted(map(str, devices))}")
        self.device = devices.pop()
        put = functools.partial(jax.device_put, device=self.device)
        self._base_key = put(jax.random.key(seed))
        self._clock = clock or time.perf_counter
        self._has_deadlines = False

        from repro.models import transformer as T
        self.paged = bool(paged) and T.supports_paged_cache(self.cfg)
        if self.paged:
            if page_size < 1 or page_size & (page_size - 1):
                raise ValueError(f"page_size {page_size} not a power of 2")
            if max_seq % page_size:
                raise ValueError("max_seq must be a multiple of page_size")
            self.page_size = page_size
            self._maxP = max_seq // page_size
            self.num_pages = (num_pages if num_pages is not None
                              else max_batch * self._maxP)
            cache = T.make_paged_decode_cache(
                self.cfg, max_batch, max_seq, page_size=page_size,
                num_pages=self.num_pages)
            # the block table lives HOST-side (allocation is host work);
            # the span marker is injected per call — the device cache
            # carries only the pools + pos (+ enc_kv)
            self._span = put(cache.pop("span"))
            cache.pop("table")
            self.cache = put(cache)
            self._tbl = np.full((max_batch, self._maxP), self.num_pages,
                                np.int32)
            self._free_pages = list(range(self.num_pages - 1, -1, -1))
            self.slot_pages: list[list[int]] = [[] for _ in range(max_batch)]
            self._slot_len = np.zeros((max_batch,), np.int64)
        else:
            self.page_size = 0
            self.num_pages = 0
            self._maxP = 0
            self.cache = put(T.make_decode_cache(self.cfg, max_batch,
                                                 max_seq))
        self.active: list[Optional[Request]] = [None] * max_batch
        self.last_token = put(jnp.zeros((max_batch,), jnp.int32))
        self.new_counts = [0] * max_batch
        # per-slot request base keys: fold_in(key(seed), rid), set at
        # admission; step() folds the token index on top (fold_idx), which
        # is bitwise fold_keys(base, rid, idx) — but lets a resumed request
        # carry its ORIGIN seed onto this engine (cross-engine identity)
        self._slot_keys = fold_keys(self._base_key,
                                    jnp.zeros((max_batch,), jnp.int32),
                                    jnp.zeros((max_batch,), jnp.int32))
        self.waiting: deque[Request] = deque()
        self.metrics = EngineMetrics(completed=[])
        # async mode: admitted-but-unfinished prompt tails carried across
        # steps (slot -> [req, full_prompt, consumed]); the slot's pages /
        # cache row are already reserved while the arbiter fills them
        self._pend: dict[int, list] = {}
        # named methods, not the model's functools.partials: XLA names a
        # program after its function, and a partial has no name
        self._decode = jax.jit(self._decode_call)
        self._prefill = jax.jit(self._prefill_call)
        self._extend = jax.jit(self._masked_extend)
        self._extend_paged = jax.jit(self._masked_extend_paged)
        self._decode_masked = jax.jit(self._masked_decode)
        # zeros template for the serial-mode B=1 prompt-tail continuation;
        # built lazily — batched mode (the default) never needs it
        self._b1_cache = None

    # --------------------------------------------------------------- admit
    def submit(self, req: Request) -> bool:
        """Queue a request. Returns False (and records a rejection) when
        the queue is past ``queue_watermark`` — backpressure fails fast
        instead of letting deadlines rot in an unbounded queue."""
        self.metrics.submitted += 1
        if (self.queue_watermark is not None
                and len(self.waiting) >= self.queue_watermark):
            req.finish_s = self._clock()
            self.metrics.rejected.append(req)
            return False
        if req.deadline_s is not None:
            self._has_deadlines = True
        self.waiting.append(req)
        return True

    def _request_base_key(self, req: Request):
        """fold_in(key(seed), rid) — the request's stream base. A resumed
        request's carried ``seed`` overrides the engine seed, so the
        stream it continues is the one its origin engine started."""
        base = (self._base_key if req.seed is None
                else jax.random.key(req.seed))
        return jax.random.fold_in(base, req.rid)

    @staticmethod
    def _effective_prompt(req: Request) -> np.ndarray:
        """What admission must prefill: the prompt plus every token already
        generated before a preemption — replaying the transcript rebuilds
        the decode cache exactly as the uninterrupted run had it."""
        if not req.tokens:
            return np.asarray(req.prompt, np.int32)
        return np.concatenate([np.asarray(req.prompt, np.int32),
                               np.asarray(req.tokens, np.int32)])

    def _prefill_call(self, params, inputs):
        return self.model.prefill_fn(params, inputs)

    def _decode_call(self, params, inputs, cache):
        return self.model.decode_fn(params, inputs, cache)

    def _masked_extend(self, params, tokens, mask, cache):
        """One extend chunk over the full engine cache; rows with
        ``mask[b] == False`` keep their old cache bits (so live decode
        slots and idle slots are untouched). Compiled once per chunk
        length — the engine batch is fixed. Serves the dense cache (MLA,
        the recurrent families, ``paged=False``), whose per-slot rows
        would need a whole-row gather and scatter to run only the takers;
        the paged twin does that."""
        logits, new_cache = self.model.extend_fn(params, {"tokens": tokens},
                                                 cache)

        def sel(new, old):
            m = mask if new.ndim <= 1 else mask.reshape(
                (1, new.shape[1]) + (1,) * (new.ndim - 2))
            return jnp.where(m, new, old)

        return logits, jax.tree.map(sel, new_cache, cache)

    def _masked_extend_paged(self, params, tokens, rows, cache):
        """Paged extend over only the rows that take the chunk: ``tokens``
        [R, C] and ``cache["table"]`` [R, maxP] are the takers' rows,
        ``rows`` [R] their slots. The [B]-batched leaves (``pos``, enc-dec
        ``enc_kv``) are gathered at ``rows`` inside the program, the new
        ``pos`` is scattered back, and the shared pools go in whole.
        Padding rows carry an all-sentinel table row, so their page writes
        drop, and a slot id past ``max_batch``, so their ``pos`` scatter
        drops too. The table keeps its full width, so each row's attention
        is the full-batch call's; compiled once per (chunk, R). Returns
        the logits [R, V] and the pools + ``pos`` (+ ``enc_kv``)."""
        rest = {k: v for k, v in cache.items()
                if k not in ("kv", "table", "span")}
        logits, new_cache = self.model.extend_fn(
            params, {"tokens": tokens},
            {**cache, **jax.tree.map(lambda x: _take_rows(x, rows), rest)})
        pos = cache["pos"].at[rows].set(new_cache["pos"], mode="drop")
        return logits, {**rest, "kv": new_cache["kv"], "pos": pos}

    def _masked_decode(self, params, inputs, mask, cache):
        """Async-mode decode with pending-admission rows masked out. Dense:
        masked rows keep their old cache bits (tree select, same rule as
        ``_masked_extend``). Paged: masked rows' table rows sentinel out so
        their in-flight pages are untouched, and their ``pos`` is
        restored."""
        if self.paged:
            tbl = jnp.where(mask[:, None], cache["table"], self.num_pages)
            logits, new_cache = self.model.decode_fn(
                params, inputs, {**cache, "table": tbl})
            new_cache["pos"] = jnp.where(mask, new_cache["pos"],
                                         cache["pos"])
            new_cache["table"] = cache["table"]
            return logits, new_cache
        logits, new_cache = self.model.decode_fn(params, inputs, cache)

        def sel(new, old):
            m = mask if new.ndim <= 1 else mask.reshape(
                (1, new.shape[1]) + (1,) * (new.ndim - 2))
            return jnp.where(m, new, old)

        return logits, jax.tree.map(sel, new_cache, cache)

    # --------------------------------------------------------------- pages
    def _call_cache(self, width: int) -> dict:
        """Assemble the per-call paged cache: device pools + the host block
        table sliced to ``width`` pages + the span marker. The table is
        tiny ([B, width] int32) so re-uploading it per dispatch is noise
        next to the attention it saves."""
        return {**self.cache,
                "table": jnp.asarray(self._tbl[:, :width]),
                "span": self._span}

    @staticmethod
    def _strip_table(new_cache: dict) -> dict:
        """Drop the per-call table/span from a returned cache — the block
        table is host state; only pools + pos (+ enc_kv) persist."""
        new_cache.pop("table", None)
        new_cache.pop("span", None)
        return new_cache

    def _pages_needed(self, req: Request, prefix: int) -> int:
        """Pages covering the request's whole contract: prompt + prefix +
        (max_new_tokens - 1) cache positions — the final sampled token
        never writes KV. Reserved up front at admission so a request can
        never strand mid-decode out of pages."""
        total = prefix + len(req.prompt) + req.max_new_tokens - 1
        return -(-total // self.page_size)

    def _alloc_pages(self, slot: int, need: int) -> None:
        """Reserve ``need`` pages for ``slot`` from the free list and point
        the slot's block-table row at them (rest stays sentinel). Caller
        has already checked availability."""
        pages = [self._free_pages.pop() for _ in range(need)]
        self.slot_pages[slot] = pages
        self._tbl[slot, :] = self.num_pages
        self._tbl[slot, :need] = pages

    def _decode_width(self, live: list) -> int:
        """Smallest power-of-2 page count covering every live row's NEXT
        token write (undersized widths would clip the write into another
        slot's page). Dead/pending rows don't count — their table rows are
        sentinel at decode time. O(log max_seq/page) distinct widths."""
        need = 1
        for i in live:
            need = max(need, int(self._slot_len[i]) + 1)
        pages = -(-need // self.page_size)
        pw = 1
        while pw < pages:
            pw <<= 1
        return min(pw, self._maxP)

    def _insert_group_cache(self, gcache: dict, slots: np.ndarray) -> int:
        """Scatter a batched (or B=1) DENSE prefill cache into engine
        slots; paged mode routes the kv leaves through the page pools and
        everything else ([B]-batched pos, enc-dec cross KV) through the
        row scatter. Returns the per-row kv length inserted (0 when
        dense — only the paged length mirror needs it)."""
        if not self.paged:
            self.cache = insert_cache_rows(self.cache, gcache,
                                           jnp.asarray(slots))
            return 0
        kv_g = gcache["kv"]
        S_g = int(next(iter(jax.tree.leaves(kv_g))).shape[2])
        npr = -(-S_g // self.page_size)
        pm = np.full((len(slots), npr), self.num_pages, np.int32)
        for r, s in enumerate(slots):
            if 0 <= s < self.max_batch:
                # entries past the slot's reservation stay sentinel, so a
                # serial-tail cache (padded to max_seq) can't write stray
                # pages
                pm[r] = self._tbl[s, :npr]
        kv_new = insert_cache_pages(self.cache["kv"], kv_g, jnp.asarray(pm))
        rest_e = {k: v for k, v in self.cache.items() if k != "kv"}
        rest_g = {k: v for k, v in gcache.items() if k != "kv"}
        rest_new = insert_cache_rows(rest_e, rest_g, jnp.asarray(slots))
        self.cache = {**rest_new, "kv": kv_new}
        return S_g

    def _prefill_inputs(self, tokens: np.ndarray) -> dict:
        inputs: dict[str, Any] = {"tokens": jnp.asarray(tokens, jnp.int32)}
        B = tokens.shape[0]
        if self.cfg.family == "encdec":
            inputs["frames"] = jnp.zeros(
                (B, self.cfg.num_prefix_embeddings, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "vlm":
            inputs["patches"] = jnp.zeros(
                (B, self.cfg.num_prefix_embeddings, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return inputs

    def _finalize_admits(self, items: list, logits) -> None:
        """Sample first tokens for every request finalized by one model
        call and make their slots live — ONE batched (fold_keys,
        sample_batch) dispatch and one host sync for the whole group.
        Per-row keys make each row's draw bitwise identical to a B=1 call
        through the same pair, so grouping (and the decode step's own
        sampling) can never change a stream.

        items: [(slot, req, row)] with ``row`` indexing ``logits``.
        """
        if not items:
            return
        with _host_span("first_token", rows=len(items)):
            rows = jnp.asarray([row for _, _, row in items], jnp.int32)
            slot_arr = jnp.asarray([slot for slot, _, _ in items], jnp.int32)
            idxs = jnp.asarray([len(req.tokens) for _, req, _ in items],
                               jnp.int32)
            temps = jnp.asarray([req.temperature for _, req, _ in items],
                                jnp.float32)
            # slot base keys were pinned at admission (engine seed or the
            # request's carried seed); the token index is len(tokens) — 0 for
            # a fresh request, the resume point for a replayed transcript —
            # so a resumed stream continues exactly where it left off
            keys = fold_idx(self._slot_keys[slot_arr], idxs)
            toks = np.asarray(sample_batch(logits[rows], keys, temps))
            now = self._clock()
            live_slots, live_toks = [], []
            for j, (slot, req, _) in enumerate(items):
                tok = int(toks[j])
                req.tokens.append(tok)
                if req.prefill_done_s is None:
                    # a resumed request keeps its ORIGINAL prefill time: TTFT
                    # measures when the user first saw a token, not the replay
                    req.prefill_done_s = now
                self.metrics.prefills += 1
                if len(req.tokens) >= req.max_new_tokens or tok == self.eos:
                    # complete at admission: the prompt's last logits already
                    # gave the only remaining requested (or an EOS) token —
                    # the slot never goes live, no unrequested decode runs
                    req.finish_s = now
                    self.metrics.completed.append(req)
                    self.release_slot(slot)
                    continue
                self.active[slot] = req
                self.new_counts[slot] = len(req.tokens)
                live_slots.append(slot)
                live_toks.append(tok)
            if live_slots:
                at = jnp.asarray(live_slots)
                self.last_token = self.last_token.at[at].set(
                    jnp.asarray(live_toks, jnp.int32))

    def _sweep_waiting_deadlines(self, now: float) -> None:
        """Expire queued requests whose absolute deadline has passed —
        before admission, so a dead request never burns prefill compute."""
        keep: deque[Request] = deque()
        while self.waiting:
            req = self.waiting.popleft()
            if req.deadline_s is not None and now >= req.deadline_s:
                req.finish_s = now
                self.metrics.timed_out.append(req)
                self.metrics.lost_tokens += len(req.tokens)
            else:
                keep.append(req)
        self.waiting = keep

    def _admit(self) -> tuple[int, int]:
        """Admit what the budget allows from the queue; returns (requests
        admitted, prompt tokens scheduled)."""
        if not self.waiting:
            return 0, 0
        now = self._clock()
        if self._has_deadlines:
            self._sweep_waiting_deadlines(now)
        free = [i for i, r in enumerate(self.active)
                if r is None and i not in self._pend]
        admits: list[tuple[int, Request]] = []
        stamped: list[Request] = []    # first admitted in this round
        held: list[Request] = []       # backoff-gated, keep queue order
        spent = 0
        budget = self.admit_token_budget
        if budget is not None and self.brownout < 1.0:
            # brownout scales how much prefill work one step may take on
            budget = max(1, int(budget * self.brownout))
        # VLM rows spend cache positions on the patch prefix too (enc-dec
        # frames live in the separate encoder cache, so they don't)
        prefix = (self.cfg.num_prefix_embeddings
                  if self.cfg.family == "vlm" else 0)
        while self.waiting and free:
            req = self.waiting[0]
            if req.not_before_s > now:
                # retry backoff: not eligible yet — hold WITHOUT blocking
                # the requests behind it (no head-of-line starvation)
                held.append(self.waiting.popleft())
                continue
            # effective prompt length: a resumed transcript replays
            # prompt + generated prefix through the prefill path
            S = len(req.prompt) + len(req.tokens)
            if req.max_new_tokens <= len(req.tokens):
                # degenerate but legal: nothing (left) to generate —
                # complete as-is, no slot, no prefill
                self.waiting.popleft()
                req.finish_s = self._clock()
                self.metrics.completed.append(req)
                continue
            need = self._pages_needed(req, prefix) if self.paged else 0
            if (len(req.prompt) == 0 or
                    prefix + len(req.prompt) + req.max_new_tokens - 1
                    > self.max_seq or need > self.num_pages):
                # can never fit this engine's cache (or page pool): reject
                # without consuming a slot (burst-proof: the queue keeps
                # draining)
                self.waiting.popleft()
                req.finish_s = self._clock()
                self.metrics.lost_tokens += len(req.tokens)
                self.metrics.rejected.append(req)
                continue
            if self.paged and need > len(self._free_pages):
                # pool temporarily exhausted: WAIT for live requests to
                # finish and recycle pages — never evict to make room.
                # Cannot deadlock: pages are only held by admitted
                # requests, which retire in bounded steps
                break
            if (admits and budget is not None and spent + S > budget):
                break  # budget spent; the rest waits for the next step
            self._leave_queue(stamped)
            if self.brownout < 1.0 and not req.tokens:
                # graceful degradation: fresh admissions under brownout
                # shed max_new_tokens instead of being dropped (resumed
                # transcripts keep their contract — shedding them would
                # break the bit-identity anchor)
                want = req.max_new_tokens
                shed_to = max(1, int(math.ceil(want * self.brownout)))
                if shed_to < want:
                    self.metrics.shed_tokens += want - shed_to
                    req.max_new_tokens = shed_to
            slot = free.pop(0)
            if self.paged:
                # recompute after any brownout shed (never more than the
                # pre-shed `need` the availability check cleared)
                self._alloc_pages(slot, self._pages_needed(req, prefix))
            admits.append((slot, req))
            spent += S
        if held:
            self.waiting.extendleft(reversed(held))
        if not admits:
            return 0, 0
        for slot, req in admits:
            self._slot_keys = self._slot_keys.at[slot].set(
                self._request_base_key(req))
        try:
            if self.admit_mode == "serial":
                for slot, req in admits:
                    self._admit_serial(slot, req)
            else:
                self._admit_batched(admits)
        except Exception:
            # a failed admission must not strand its round-mates: anything
            # not yet live goes back to the FRONT of the queue with clean
            # state, so completed + rejected + waiting + active always
            # reconciles. (Serial mode attributes the failure and records
            # that one request as rejected; batched failures cannot be
            # attributed to a single request, so everything is retried.)
            # Membership is by identity: Request.__eq__ would compare
            # ndarray prompts and raise. A resumed request keeps its
            # carried transcript prefix — only tokens sampled during the
            # failed round are rolled back.
            self._rollback_admits(admits, stamped)
            raise
        return len(admits), spent

    def _leave_queue(self, stamped: list) -> None:
        """Pop the queue's head for a slot, stamping its first admission;
        ``stamped`` collects the requests this round stamped, which a
        failed round unstamps."""
        req = self.waiting.popleft()
        if req.admit_s is None:
            req.admit_s = self._clock()
            stamped.append(req)

    def _admit_serial(self, slot: int, req: Request) -> None:
        """Reference path: pow2-prefix prefill + serial B=1 decode tail."""
        full = self._effective_prompt(req)
        S = len(full)
        bucket = 1 << (S.bit_length() - 1)
        # one span over the B=1 prefill, its decode tail and the insert;
        # the first token nests in it
        with _host_span("prefill", bucket=bucket, rows=1, batch=1):
            logits, req_cache = self._prefill(
                self.params, self._prefill_inputs(full[None, :bucket]))
            self.metrics.prefill_calls += 1
            if bucket < S:
                # continue the prompt token-by-token at B=1: decode(prefill
                # of a prefix) is the exact sequential continuation, so the
                # final logits/cache match a full-length prefill
                if self._b1_cache is None:
                    from repro.models import transformer as T
                    self._b1_cache = T.make_decode_cache(self.cfg, 1,
                                                         self.max_seq)
                req_cache = insert_cache(self._b1_cache, req_cache, 0)
                for tok in full[bucket:]:
                    logits, req_cache = self._decode(
                        self.params, {"token": jnp.asarray([tok], jnp.int32)},
                        req_cache)
                    self.metrics.prefill_calls += 1
            try:
                if self.paged:
                    self._insert_group_cache(req_cache,
                                             np.asarray([slot], np.int32))
                    # logical length = the request cache's pos (covers the
                    # VLM patch prefix); NOT the kv length — the serial tail
                    # cache is padded out to max_seq
                    self._slot_len[slot] = int(req_cache["pos"][0])
                else:
                    self.cache = insert_cache(self.cache, req_cache, slot)
                self._finalize_admits([(slot, req, 0)], logits)
            except Exception:
                self._reject_failed(slot, req)
                raise

    def _reject_failed(self, slot: int, req: Request) -> None:
        """Admission error path: release the slot and record the failing
        request as rejected, keeping the engine's accounting consistent
        (completed + rejected + waiting + active == submitted). A resumed
        request keeps its carried transcript prefix (and original TTFT)
        so a failover layer can still retry it elsewhere."""
        self.release_slot(slot)
        self.metrics.lost_tokens += max(0, len(req.tokens) - req.resumed_from)
        del req.tokens[req.resumed_from:]
        if req.resumed_from == 0:
            req.prefill_done_s = None
        req.finish_s = self._clock()
        self.metrics.rejected.append(req)

    def _dispatch_extend(self, chunks: list, C: int) -> tuple:
        """One extend-chunk dispatch for ``chunks`` [(slot, C tokens)],
        shared by the batched tail loop and the async arbiter. Paged: only
        the takers' rows run, padded to the next power of two ``R``, at
        the full table width (``_masked_extend_paged``). Dense: the full
        engine batch with a cache-select mask. Returns the logits and each
        taker's row in them, in ``chunks`` order."""
        takers = [slot for slot, _ in chunks]
        if self.paged:
            R = 1 << (len(chunks) - 1).bit_length()
            toks = np.zeros((R, C), np.int32)
            rows = np.full((R,), self.max_batch, np.int32)
            tbl = np.full((R, self._maxP), self.num_pages, np.int32)
            for r, (slot, t) in enumerate(chunks):
                toks[r], rows[r], tbl[r] = t, slot, self._tbl[slot]
            at = list(range(len(chunks)))
        else:
            R = self.max_batch
            toks = np.zeros((R, C), np.int32)
            mask = np.zeros((R,), bool)
            for slot, t in chunks:
                toks[slot], mask[slot] = t, True
            at = takers
        with _host_span("extend", chunk=C, rows=len(chunks), batch=R):
            if self.paged:
                logits, self.cache = self._extend_paged(
                    self.params, jnp.asarray(toks), jnp.asarray(rows),
                    {**self.cache, "table": jnp.asarray(tbl),
                     "span": self._span})
                self._slot_len[takers] += C
            else:
                logits, self.cache = self._extend(
                    self.params, jnp.asarray(toks), jnp.asarray(mask),
                    self.cache)
        self.metrics.prefill_calls += 1
        self.metrics.extend_chunks += 1
        return logits, at

    def _admit_batched(self, admits: list) -> None:
        """Grouped prefill + shared descending-pow2 extend tails. Operates
        on the *effective* prompt (prompt + resumed transcript prefix), so
        a resumed request rides the same pipeline as a fresh one."""
        groups: dict[int, list] = {}
        for slot, req in admits:
            full = self._effective_prompt(req)
            bucket = 1 << (len(full).bit_length() - 1)
            groups.setdefault(bucket, []).append((slot, req, full))
        pend: dict[int, list] = {}          # slot -> [req, full, consumed]
        for bucket in sorted(groups, reverse=True):
            group = groups[bucket]
            kp = 1 << (len(group) - 1).bit_length()   # pow2-padded batch
            toks = np.zeros((kp, bucket), np.int32)
            # padding rows scatter to slot id max_batch -> dropped
            slots = np.full((kp,), self.max_batch, np.int32)
            for r, (slot, req, full) in enumerate(group):
                toks[r] = full[:bucket]
                slots[r] = slot
            with _host_span("prefill", bucket=bucket, rows=len(group),
                            batch=kp):
                logits, gcache = self._prefill(self.params,
                                               self._prefill_inputs(toks))
                self.metrics.prefill_calls += 1
                self._insert_group_cache(gcache, slots)
                if self.paged:
                    # every row of the bucket group lands at the same
                    # logical length: the group cache's pos (covers the
                    # VLM prefix)
                    S_ins = int(gcache["pos"][0])
                    for slot, _req, _full in group:
                        self._slot_len[slot] = S_ins
            fins = []
            for r, (slot, req, full) in enumerate(group):
                if bucket == len(full):
                    fins.append((slot, req, r))
                else:
                    pend[slot] = [req, full, bucket]
            self._finalize_admits(fins, logits)
        while pend:
            # chunk = the largest remaining binary digit across pending
            # rows; every row with that bit set advances this round
            C = max(1 << ((len(full) - cons).bit_length() - 1)
                    for req, full, cons in pend.values())
            chunks = [(slot, full[cons:cons + C])
                      for slot, (req, full, cons) in pend.items()
                      if (len(full) - cons) & C]
            logits, at = self._dispatch_extend(chunks, C)
            fins = []
            for (slot, _), row in zip(chunks, at):
                req, full, cons = pend[slot]
                cons += C
                if cons == len(full):
                    del pend[slot]
                    fins.append((slot, req, row))
                else:
                    pend[slot][2] = cons
            self._finalize_admits(fins, logits)

    # --------------------------------------------------------------- async
    def _arbiter(self, budget: int, *, force: bool = False) -> int:
        """Spend up to ``budget`` prompt tokens advancing pending tails in
        descending-pow-2 chunks (every pending row with at least a full
        chunk remaining rides each dispatch). ``force`` guarantees one
        minimal chunk even on an exhausted budget, so tails can never
        starve behind a continuous arrival stream. Returns tokens spent."""
        spent = 0
        while self._pend:
            remaining = budget - spent
            if remaining < 1:
                if not (force and spent == 0):
                    break
                remaining = 1
            max_rem = max(len(full) - cons
                          for _req, full, cons in self._pend.values())
            cap = min(max_rem, remaining)
            C = 1 << (cap.bit_length() - 1)
            chunks = [(slot, full[cons:cons + C])
                      for slot, (_req, full, cons) in self._pend.items()
                      if len(full) - cons >= C]
            logits, at = self._dispatch_extend(chunks, C)
            fins = []
            for (slot, _), row in zip(chunks, at):
                entry = self._pend[slot]
                entry[2] += C
                if entry[2] == len(entry[1]):
                    del self._pend[slot]
                    fins.append((slot, entry[0], row))
            self._finalize_admits(fins, logits)
            spent += C * len(chunks)
        return spent

    def _admit_async(self) -> tuple[int, int]:
        """One bounded slice of admission work: a guaranteed arbiter chunk
        for in-flight tails, new-request bucket prefills with the
        remaining budget (tails deferred to ``self._pend``), then leftover
        budget on more tail chunks. Returns (requests admitted, prompt
        tokens spent)."""
        now = self._clock()
        if self._has_deadlines:
            self._sweep_waiting_deadlines(now)
        budget = self.admit_token_budget or self.max_seq
        if self.brownout < 1.0:
            budget = max(1, int(budget * self.brownout))
        spent = self._arbiter(budget, force=True)
        free = [i for i, r in enumerate(self.active)
                if r is None and i not in self._pend]
        admits: list[tuple[int, Request]] = []
        stamped: list[Request] = []
        held: list[Request] = []
        prefix = (self.cfg.num_prefix_embeddings
                  if self.cfg.family == "vlm" else 0)
        while self.waiting and free:
            req = self.waiting[0]
            if req.not_before_s > now:
                held.append(self.waiting.popleft())
                continue
            S = len(req.prompt) + len(req.tokens)
            if req.max_new_tokens <= len(req.tokens):
                self.waiting.popleft()
                req.finish_s = self._clock()
                self.metrics.completed.append(req)
                continue
            need = self._pages_needed(req, prefix) if self.paged else 0
            if (len(req.prompt) == 0 or
                    prefix + len(req.prompt) + req.max_new_tokens - 1
                    > self.max_seq or need > self.num_pages):
                self.waiting.popleft()
                req.finish_s = self._clock()
                self.metrics.lost_tokens += len(req.tokens)
                self.metrics.rejected.append(req)
                continue
            if self.paged and need > len(self._free_pages):
                break  # wait for pages to recycle — never evict
            # admission charges only the bucket prefill this step; the
            # tail is deferred to the arbiter. Admit unconditionally when
            # the step has done no work yet (no starvation of oversized
            # prompts)
            bucket = 1 << (S.bit_length() - 1)
            if (admits or spent) and spent + bucket > budget:
                break
            self._leave_queue(stamped)
            if self.brownout < 1.0 and not req.tokens:
                want = req.max_new_tokens
                shed_to = max(1, int(math.ceil(want * self.brownout)))
                if shed_to < want:
                    self.metrics.shed_tokens += want - shed_to
                    req.max_new_tokens = shed_to
            slot = free.pop(0)
            if self.paged:
                self._alloc_pages(slot, self._pages_needed(req, prefix))
            admits.append((slot, req))
            spent += bucket
        if held:
            self.waiting.extendleft(reversed(held))
        if admits:
            for slot, req in admits:
                self._slot_keys = self._slot_keys.at[slot].set(
                    self._request_base_key(req))
            try:
                self._prefill_async(admits)
            except Exception:
                self._rollback_admits(admits, stamped)
                raise
        spent += self._arbiter(budget - spent)
        return len(admits), spent

    def _prefill_async(self, admits: list) -> None:
        """Bucket-group prefills for a fresh async admission wave;
        full-bucket prompts finalize immediately, everything else lands in
        ``self._pend`` for the arbiter (pages/rows already reserved)."""
        groups: dict[int, list] = {}
        for slot, req in admits:
            full = self._effective_prompt(req)
            bucket = 1 << (len(full).bit_length() - 1)
            groups.setdefault(bucket, []).append((slot, req, full))
        for bucket in sorted(groups, reverse=True):
            group = groups[bucket]
            kp = 1 << (len(group) - 1).bit_length()
            toks = np.zeros((kp, bucket), np.int32)
            slots = np.full((kp,), self.max_batch, np.int32)
            for r, (slot, _req, full) in enumerate(group):
                toks[r] = full[:bucket]
                slots[r] = slot
            with _host_span("prefill", bucket=bucket, rows=len(group),
                            batch=kp):
                logits, gcache = self._prefill(self.params,
                                               self._prefill_inputs(toks))
                self.metrics.prefill_calls += 1
                self._insert_group_cache(gcache, slots)
                if self.paged:
                    S_ins = int(gcache["pos"][0])
                    for slot, _req, _full in group:
                        self._slot_len[slot] = S_ins
            fins = []
            for r, (slot, req, full) in enumerate(group):
                if bucket == len(full):
                    fins.append((slot, req, r))
                else:
                    self._pend[slot] = [req, full, bucket]
            self._finalize_admits(fins, logits)

    def _rollback_admits(self, admits: list, stamped: list) -> None:
        """Failed-round cleanup shared with ``_admit``: anything not yet
        settled (live, pending, completed or rejected) goes back to the
        front of the queue with clean state and its slot/pages released.
        A request this round stamped (``stamped``) loses the stamp; one
        admitted before keeps it."""
        requeue = []
        for slot, req in admits:
            settled = (self.active[slot] is req
                       or (slot in self._pend
                           and self._pend[slot][0] is req)
                       or any(r is req for r in self.metrics.completed)
                       or any(r is req for r in self.metrics.rejected))
            if not settled:
                del req.tokens[req.resumed_from:]
                if any(r is req for r in stamped):
                    req.admit_s = None
                self.release_slot(slot)
                requeue.append(req)
        self.waiting.extendleft(reversed(requeue))

    # --------------------------------------------------------------- slots
    def release_slot(self, slot: int) -> None:
        """Family-agnostic slot retirement: clear the slot's bookkeeping
        and zero its cache position, so every family's valid-length reads
        mask out the stale cache rows. Used on sequence finish, preemption
        and admission error paths. Idempotent — releasing a free (or
        never-admitted) slot is a no-op; an out-of-range slot id raises."""
        if not 0 <= slot < self.max_batch:
            raise ValueError(
                f"slot {slot} out of range [0, {self.max_batch})")
        self.active[slot] = None
        self.new_counts[slot] = 0
        self._pend.pop(slot, None)
        self.cache["pos"] = self.cache["pos"].at[slot].set(0)
        if self.paged and self.slot_pages[slot]:
            # recycle: the freed pages go back on the free list and the
            # block-table row goes all-sentinel, so any stale write into
            # this slot drops instead of corrupting the pages' next owner
            self._free_pages.extend(self.slot_pages[slot])
            self.slot_pages[slot] = []
            self._tbl[slot, :] = self.num_pages
            self._slot_len[slot] = 0

    # ----------------------------------------------------- preempt / resume
    def preempt(self, slots: Optional[list] = None) -> list[TranscriptSnapshot]:
        """Snapshot in-flight requests and free their cache slots.

        ``slots=None`` preempts every live slot (the power-drop path).
        Each snapshot carries the full transcript and the seed that keys
        the request's sampling stream, so ``resume`` — here or on any
        other engine serving the same model — continues it bit-identically.
        """
        if slots is None:
            slots = ([i for i, r in enumerate(self.active) if r is not None]
                     + list(self._pend))
        snaps = []
        for slot in slots:
            req = self.active[slot]
            if req is None and slot in self._pend:
                # an async-pending admission owes its transcript too: the
                # prefix already inserted is abandoned (pages/rows freed)
                # and the resume replays it from the prompt
                req = self._pend[slot][0]
            if req is None:
                continue
            seed = req.seed if req.seed is not None else self.seed
            snaps.append(TranscriptSnapshot.from_request(req, seed=seed))
            self.metrics.preemptions += 1
            self.metrics.evicted += 1
            self.release_slot(slot)
        return snaps

    def drain(self) -> list[TranscriptSnapshot]:
        """Site-death path: preempt every live slot AND evict the waiting
        queue — everything this engine owes comes back as snapshots for a
        failover layer to carry to surviving sites."""
        snaps = self.preempt()
        while self.waiting:
            req = self.waiting.popleft()
            seed = req.seed if req.seed is not None else self.seed
            snaps.append(TranscriptSnapshot.from_request(req, seed=seed))
            self.metrics.evicted += 1
        return snaps

    def resume(self, snap: TranscriptSnapshot, *,
               not_before_s: float = 0.0) -> Optional[Request]:
        """Re-admit a preempted transcript. The carried seed keeps the
        stream's keys; the carried ``admit_s`` and ``prefill_done_s`` keep
        the original admission and TTFT honest. Returns the queued Request,
        or None when the watermark rejected it (the caller keeps the
        snapshot and may retry elsewhere)."""
        req = Request(rid=snap.rid,
                      prompt=np.asarray(snap.prompt, np.int32),
                      max_new_tokens=snap.max_new_tokens,
                      arrival_s=snap.arrival_s,
                      temperature=snap.temperature,
                      seed=snap.seed,
                      deadline_s=snap.deadline_s,
                      not_before_s=not_before_s,
                      attempts=snap.attempts,
                      resumed_from=len(snap.tokens),
                      tokens=list(snap.tokens),
                      admit_s=snap.admit_s,
                      prefill_done_s=snap.prefill_done_s)
        if not self.submit(req):
            return None
        self.metrics.resumed += 1
        self.metrics.recovered_tokens += len(snap.tokens)
        return req

    def set_brownout(self, frac: float) -> None:
        """Enter (or leave, frac=1.0) brownout: fresh admissions shed
        ``max_new_tokens`` to ``ceil(frac * requested)`` and the per-step
        admission token budget scales by ``frac`` — graceful degradation
        under a power drop instead of wholesale drops."""
        self.brownout = float(min(max(frac, 0.0), 1.0))

    def reconcile(self) -> dict:
        """Watchdog: every submitted request must be in exactly one of
        completed / rejected / timed_out / waiting / active / evicted
        (handed out as a snapshot). Returns the books and a ``balanced``
        flag — an unbalanced ledger means the engine leaked a request."""
        m = self.metrics
        books = {"submitted": m.submitted,
                 "completed": len(m.completed),
                 "rejected": len(m.rejected),
                 "timed_out": len(m.timed_out),
                 "waiting": len(self.waiting),
                 "active": (sum(r is not None for r in self.active)
                            + len(self._pend)),
                 "evicted": m.evicted}
        books["balanced"] = (
            books["submitted"] == books["completed"] + books["rejected"]
            + books["timed_out"] + books["waiting"] + books["active"]
            + books["evicted"])
        # decode-utilization ledger: how well admission overlapped decode
        books["decode_utilization"] = {
            "decode_steps": m.decode_steps,
            "extend_chunks": m.extend_chunks,
            "admit_stall_steps": m.admit_stall_steps,
        }
        return books

    # --------------------------------------------------------------- step
    def step(self) -> int:
        """Admit waiting requests, run one batched decode. Returns #active."""
        live = sum(r is not None for r in self.active)
        with _host_span("step", live=live, waiting=len(self.waiting),
                        pending=len(self._pend)), \
                jax.default_device(self.device):
            return self._step()

    def _step(self) -> int:
        if self._has_deadlines:
            now = self._clock()
            for i, r in enumerate(self.active):
                if (r is not None and r.deadline_s is not None
                        and now >= r.deadline_s):
                    r.finish_s = now
                    self.metrics.timed_out.append(r)
                    self.metrics.lost_tokens += len(r.tokens)
                    self.release_slot(i)
            for i in list(self._pend):
                r = self._pend[i][0]
                if r.deadline_s is not None and now >= r.deadline_s:
                    r.finish_s = now
                    self.metrics.timed_out.append(r)
                    self.metrics.lost_tokens += len(r.tokens)
                    self.release_slot(i)   # pops the pend entry too
        pc_before = self.metrics.prefill_calls
        admit = _host_span("admit")
        with admit:
            admitted, tokens = (self._admit_async()
                                if self.admit_mode == "async"
                                else self._admit())
            admit.set_metadata(admitted=admitted, tokens=tokens)
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            if self.metrics.prefill_calls > pc_before:
                # admission ran with nothing to decode against — the stall
                # the async overlap exists to shrink
                self.metrics.admit_stall_steps += 1
            return 0
        width = self._decode_width(live) if self.paged else 0
        with _host_span("decode", rows=len(live), batch=self.max_batch,
                        pages=width):
            if self.admit_mode == "async":
                # pending-admission rows are masked out of the decode
                # (their half-filled caches must not move)
                mask = np.zeros((self.max_batch,), bool)
                mask[live] = True
                if self.paged:
                    logits, new_cache = self._decode_masked(
                        self.params, {"token": self.last_token},
                        jnp.asarray(mask), self._call_cache(width))
                    self.cache = self._strip_table(new_cache)
                else:
                    logits, self.cache = self._decode_masked(
                        self.params, {"token": self.last_token},
                        jnp.asarray(mask), self.cache)
            elif self.paged:
                logits, new_cache = self._decode(
                    self.params, {"token": self.last_token},
                    self._call_cache(width))
                self.cache = self._strip_table(new_cache)
            else:
                logits, self.cache = self._decode(
                    self.params, {"token": self.last_token}, self.cache)
        self.metrics.decode_steps += 1
        if self.paged:
            for i in live:
                self._slot_len[i] += 1
        with _host_span("sample"):
            temps = np.zeros(self.max_batch, np.float32)
            idxs = np.zeros(self.max_batch, np.int32)
            for i in live:
                temps[i] = self.active[i].temperature
                idxs[i] = len(self.active[i].tokens)
            # per-(request, token-index) keys + per-row temperatures: a
            # row's draw is independent of its batch-mates and its
            # admission order. Slot base keys were pinned at admission
            # (fold_idx on top equals fold_keys bitwise), so a resumed
            # request keeps its origin stream
            keys = fold_idx(self._slot_keys, jnp.asarray(idxs))
            toks = sample_batch(logits, keys, jnp.asarray(temps))
            toks_np = np.asarray(toks)
        self.last_token = toks
        self.metrics.steps += 1
        retire = _host_span("retire")
        with retire:
            now = self._clock()
            finished = 0
            for i in live:
                req = self.active[i]
                req.tokens.append(int(toks_np[i]))
                self.new_counts[i] += 1
                done = (self.new_counts[i] >= req.max_new_tokens
                        or int(toks_np[i]) == self.eos)
                if done:
                    req.finish_s = now
                    self.metrics.completed.append(req)
                    self.release_slot(i)
                    finished += 1
            retire.set_metadata(finished=finished)
        return len([r for r in self.active if r is not None])

    def run(self, max_steps: int = 10_000) -> EngineMetrics:
        """Drain all waiting + active requests."""
        for _ in range(max_steps):
            n = self.step()
            if n == 0 and not self.waiting and not self._pend:
                break
        return self.metrics
