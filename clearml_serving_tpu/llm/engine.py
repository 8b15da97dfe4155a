"""Continuous-batching LLM engine core (JetStream-style; replaces vLLM).

Design (TPU-first, SURVEY.md §7 step 6):

- **Slot-based decode batch**: a fixed ``max_batch`` of cache slots; the decode
  step is ONE jitted function over the full slot batch (static shapes — no
  recompilation as requests come and go). Inactive slots compute garbage that
  is never read; occupancy, not shapes, varies.
- **Bucketed prefill**: prompts pad to the next seq-len bucket; one compiled
  prefill per bucket. Prefill emits KV shaped [L,1,bucket,H,D] which a jitted
  donate-insert writes into the slot's region of the big cache — the cache
  lives in HBM across the whole request lifetime, is donated through every
  step, and is never copied host-side.
- **Continuous batching loop**: an asyncio task interleaves admissions
  (prefill) with decode steps; each step's sampled tokens fan out to
  per-request queues (SSE streaming sits directly on top).
- **Multi-step decode**: ``decode_steps`` tokens are generated per dispatch
  with an on-device ``lax.scan`` (sampling included). Host dispatch overhead
  is amortized over the whole chunk (the per-dispatch cost on a directly
  attached chip: not measured). Finished sequences inside a chunk are
  truncated host-side; their slots free at the chunk boundary.
- **Sampling as data**: per-slot temperature/top-k/top-p arrays — one compiled
  sampler for any mix of requests.
- Optional ``jax.sharding.Mesh``: params/cache get TP/DP shardings from
  parallel/sharding.py; GSPMD handles the collectives; the loop is unchanged.

The reference's equivalent surface is vLLM's AsyncLLM behind
VllmPreprocessRequest (reference preprocess_service.py:619-1348).
"""

from __future__ import annotations

import asyncio
import contextlib
import heapq
import itertools
import logging
import math
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, AsyncIterator, Deque, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import (
    compile_sentry,
    faults,
    kv_sanitizer,
    lifecycle_ledger,
    sharding_sentry,
)
from .shapes import decode_steps_bucket, pad_to_multiple
from ..errors import (
    DeadlineExceededError,
    EngineOverloadedError,
    EngineStepError,
    EngineStuckError,
    EngineUnavailableError,
    is_hbm_oom,
)
from .sampling import (
    SamplingExtras,
    SamplingParams,
    greedy_tree_walk,
    penalize_logits,
    speculative_sample_chain,
    speculative_sample_tree,
    row_needs,
    sample_tokens,
)

_DEFAULT_PREFILL_BUCKETS = [32, 64, 128, 256, 512, 1024, 2048]

# per-engine tag for the process-wide sharding sentry's spec table:
# co-hosted replica engines must not alias each other's array paths
_ENGINE_IDS = itertools.count()


@dataclass
class GenRequest:
    prompt_ids: List[int]
    max_new_tokens: int = 128
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop_token_ids: Optional[List[int]] = None
    # OpenAI/vLLM sampling-parameter parity (applied on-device as batch data)
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    seed: Optional[int] = None
    logit_bias: Optional[Dict[int, float]] = None
    # number of top-alternative logprobs to record per emitted token
    # (None = no logprob tracking; 0 = chosen token's logprob only)
    logprobs: Optional[int] = None
    # named LoRA adapter to apply (None = base model); resolved against the
    # engine's adapter registry at validate/admission time
    adapter: Optional[str] = None
    # vLLM min_tokens: suppress EOS until this many tokens were generated
    min_tokens: int = 0
    # grammar constraint (llm/guided.py GuidedSpec); compiled at admission,
    # enforced on device inside the decode scan
    guided: Optional[Any] = None
    # SLO class (docs/slo_scheduling.md): "interactive" | "batch" |
    # "best_effort". Strict class order across the per-class pending queues,
    # EDF within a class; under overload best_effort sheds first, then
    # batch, and batch-lane slots are preemptible when interactive work is
    # queued. Endpoint-level default via aux engine.default_priority.
    priority: str = "interactive"
    # engine-internal: combined-table DFA state after the first token
    _gstate0: int = -1
    _guided_key: Optional[str] = None
    # engine-internal (legacy admission): the (bias, prompt mask) rows the
    # worker staged for the first token, for the commit's device-row reset
    _extras_rows: Optional[tuple] = None
    # filled by the engine:
    out_queue: "asyncio.Queue" = field(default_factory=asyncio.Queue)
    produced: int = 0
    prompt_len: int = 0
    submitted_at: float = field(default_factory=time.time)
    first_token_at: Optional[float] = None
    error: Optional[BaseException] = None
    # per emitted token (when logprobs is not None): {"id", "logprob",
    # "top_ids", "top_logprobs"}; entry i is appended BEFORE token i is
    # queued, so a consumer that just received token i may read entry i
    logprob_entries: List[dict] = field(default_factory=list)
    # set by the API layer when a stop STRING matched in the decoded text
    # (stop token ids are handled by the engine; strings need detokenization)
    stopped_on_string: bool = False
    # set by the consumer (e.g. an SSE wrapper on client disconnect); the
    # engine frees the slot and KV pages at the next emission point instead
    # of decoding the request to max_new_tokens for nobody
    cancelled: bool = False
    # engine-internal (paged prefix cache): pinned shared-page hit carried
    # from the admission worker to the loop-thread commit; every failure
    # path between the two must release it (engine._release_prefix_hit)
    _prefix_hit: Optional[Any] = None
    # per-request lifecycle budgets in seconds (None = engine defaults):
    # queue_timeout bounds the wait in _pending, ttft_timeout the time to
    # the first emitted token, total_timeout the whole request
    queue_timeout: Optional[float] = None
    ttft_timeout: Optional[float] = None
    total_timeout: Optional[float] = None
    # engine-internal monotonic deadlines resolved once at submission
    _queue_deadline: Optional[float] = None
    _ttft_deadline: Optional[float] = None
    _deadline: Optional[float] = None
    # engine-internal (preemptible batch lane): tokens emitted since the
    # last (re)admission — a preempted request's full token history is
    # prompt_ids + _gen_ids, which becomes the resume prompt so the radix
    # prefix cache replays the generated-so-far KV with near-zero prefill
    _gen_ids: List[int] = field(default_factory=list)
    # times this request was preempted (bounded by the engine's preemption
    # budget: an exhausted budget makes the request immune, so batch work
    # still finishes under sustained interactive pressure)
    _preempt_count: int = 0
    # engine-internal (paged prefix cache): eviction pin on the preempted
    # history's radix run, held from preemption until the resume admission's
    # lookup (prefix_cache.pin_run) — without it, pool pressure while the
    # request waits in the queue can evict exactly the KV the preemption
    # promised to replay. Every queue-exit path must release it
    # (engine._release_resume_pin)
    _resume_pin: Optional[Any] = None
    # disaggregated prefill/decode (docs/disaggregation.md): the replica
    # group sets _ship_to on the PREFILL leg's clone (destination decode
    # replica name — the engine exports the committed prefix pages into a
    # KV-transport shipment addressed there) and _shipped on the ORIGINAL
    # request once the leg ran (the decode replica's admission then books
    # the shipped prefix as a ship hit or a recompute)
    _ship_to: Optional[str] = None
    _shipped: bool = False
    # engine-internal, ``_clock`` seconds: the request's way to its first
    # token (lifecycle_stats()["requests"]). _queued is the submission, or
    # the re-queue of a preempted request (a new wait, not a new TTFT);
    # _slot_at the pop from the queue with a slot reserved; _job_at the
    # ragged job's opening (legacy path: the admission task's start);
    # _prefill_launches the launches that carried one of its prompt chunks;
    # _enqueue_at the first of those launches' ``enqueue`` stamp and
    # _ready_at the last one's ``ready`` stamp (_CycleClock; 0.0 on the
    # dense cache's path, whose prefill rides no launch)
    _submitted: float = 0.0
    _queued: float = 0.0
    _slot_at: float = 0.0
    _job_at: float = 0.0
    _prefill_launches: int = 0
    _enqueue_at: float = 0.0
    _ready_at: float = 0.0

    def cancel(self) -> None:
        self.cancelled = True


logger = logging.getLogger(__name__)

_FINISHED = object()


class _ShipShim:
    """Carrier for fault matching on the ``engine.kv.receive`` seam: the
    receive path has no GenRequest in hand (the group calls it before the
    stream's admission), so the shim carries the prompt ids for
    ``match_token`` selection (the router's ``_ReplicaShim`` pattern)."""

    def __init__(self, prompt_ids):
        self.prompt_ids = list(prompt_ids)

# stop tokens honored by min_tokens suppression per request (requests with
# more stop ids than this keep finishing on all of them — only the floor's
# suppression is bounded)
_STOP_SLOTS = 8

# decode pipeline depth: in-flight decode-chunk dispatches the loop keeps
# enqueued ahead of retirement. 1 = the historical serial
# dispatch->sync->emit loop; 2 (default) overlaps chunk N's host readback +
# emission with chunk N+1's device compute (docs/pipelined_decode.md)
_DEFAULT_PIPELINE_DEPTH = 2

# watchdog: a dispatch still in its worker is given this many intervals before
# it counts as a stall (a first-use XLA compile runs inside the call)
_DISPATCH_GRACE_INTERVALS = 10.0
# the ragged step's loop thread stands still (blocking, GIL released) until
# its worker's ``enqueued`` stamp, so that no handler's Python runs beside the
# worker's upload + enqueue; bounded, so that a first-use compile inside the
# call cannot hold the event loop (the await that follows takes over)
_ENQUEUED_WAIT_S = 0.05

# host-tier auto-sizing clamps (aux engine.prefix_cache_host_mb: "auto",
# docs/kv_tiering.md): half of /proc/meminfo MemAvailable, bounded so a
# tiny CI box still gets a usable tier and a 1 TiB host does not
# preallocate absurd slabs
_AUTO_HOST_TIER_MIN_BYTES = 64 << 20
_AUTO_HOST_TIER_MAX_BYTES = 16 << 30


def _env_pipeline_depth() -> int:
    raw = os.environ.get("TPUSERVE_PIPELINE_DEPTH", "")
    try:
        return max(1, int(raw)) if raw else _DEFAULT_PIPELINE_DEPTH
    except ValueError:
        return _DEFAULT_PIPELINE_DEPTH


class _MsHistogram:
    """Host-side fixed-bucket histogram for scrape-time export
    (statistics.metrics turns snapshots into Prometheus histograms). One
    writer at a time (the dispatch worker / retire stage); snapshot()
    copies under the GIL so scrapes never see torn lists. The default
    bucket set is millisecond-scaled; callers may pass their own (the
    ragged scheduler's budget-utilization ratios use a [0, 1] grid)."""

    BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0)

    def __init__(self, buckets=None):
        self.buckets = tuple(buckets) if buckets is not None else self.BUCKETS
        self.counts = [0] * (len(self.buckets) + 1)
        self.total_ms = 0.0
        self.n = 0

    def observe(self, ms: float) -> None:
        for i, edge in enumerate(self.buckets):
            if ms <= edge:
                break
        else:
            i = len(self.buckets)
        self.counts[i] += 1
        self.total_ms += float(ms)
        self.n += 1

    def snapshot(self) -> dict:
        return {
            "buckets": list(self.buckets),
            "counts": list(self.counts),
            "sum_ms": self.total_ms,
            "count": self.n,
        }


# the one clock of every stamp on the launch timeline and on a request's way
# to its first token (_CycleClock, _WorkerStamps, GenRequest's stamps), which
# are subtracted from each other across threads
_clock = time.monotonic

# request-phase grid: the default grid ends at 1 s, and a long prompt's
# first token can take tens of seconds behind other prefills
_REQUEST_MS_BUCKETS = (
    1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
)
_PREFILL_LAUNCH_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 128)
# prefill_ms of a request whose prompt rode launches, cut on their timeline
_PREFILL_STRETCHES = ("first_launch_wait_ms", "prefill_span_ms", "first_emit_ms")


class _CycleClock:
    """The loop thread's time through one scheduling cycle, cut into phases
    that add up (docs/pipelined_decode.md "Observability"): ``admin`` (loop
    top until the step is entered), ``plan`` (_prepare_ragged /
    _prepare_dispatch), ``launch`` (waiting for the dispatch worker: a
    ragged step stands still until the worker's ``enqueued`` stamp, then
    awaits its hop back), ``wait`` (the dispatch landed, or a chunk's
    retire entered, until every host copy is in hand: a worker thread
    waits for the device and copies, the loop thread awaits it, and the
    HTTP handlers run meanwhile), ``emit`` (the rest of the retire) and
    ``yield`` (sanitizer + the sleep(0) that hands the event loop to the
    HTTP handlers; none after a ragged step that awaited its worker, whose
    streams are served inside the next launch's ``launch`` and ``wait``).
    A cycle is a loop iteration that dispatched or retired; it runs from
    its loop top to the next one. ``mark`` closes the running phase and
    opens the next on ONE clock read, so the phases partition the cycle
    (``cycle_ms`` is their sum), and each phase is an ``engine.<phase>``
    annotation on the profiler's host plane while a profiler session is
    open (a flag test otherwise).

    One launch timeline lies over the phases. ``landed`` takes the dispatch
    worker's four reads of a launch (_WorkerStamps) between the loop's own
    around the hop, ``ready`` the instant the FIRST device-to-host copy of
    the launch's results returned (the device has finished; the read
    worker's own read, handed over with the copies). ``parts`` cuts the
    way from the ``launch`` mark to the loop having the result into
    ``hop_out`` / ``upload`` / ``enqueue`` / ``tail`` / ``hop_back``;
    ``readback`` is
    ``ready`` until ``wait`` closes; ``starve`` is ``enqueue(N) -
    ready(N-1)``, never below 0: the stretch in which the program KNOWS the
    chip had nothing queued (none for a launch with no predecessor since a
    park: an engine without work is not starved). In the serial ragged step
    the parts add up to the ``launch`` phase and a starve is readback + emit
    + yield of one cycle plus admin + plan + hop_out + upload of the next
    (shared reads; that readback ends with the read worker's hop back, and
    that yield holds no handler). Where launches overlap (pipelined step)
    the worker's three parts still add up to ``dispatch_ms``, the
    ``launch`` phase is only what the concurrent retire left of the hop,
    and a launch whose predecessor is not back yet starves 0. A ragged step
    that keeps a launch in flight runs plan(N+1) | launch(N+1) | wait(N) |
    emit(N) in one cycle: the parts still add up to ``launch``, N+1 starves
    0, and ``wait`` is what is left of N's run once the host is through
    with N+1. Loop-thread only."""

    PHASES = ("admin", "plan", "launch", "wait", "emit", "yield")
    PARTS = ("hop_out", "upload", "enqueue", "tail", "hop_back")

    def __init__(self):
        self.phases = {p: _MsHistogram() for p in self.PHASES}
        self.cycle = _MsHistogram()
        self.parts = {p: _MsHistogram() for p in self.PARTS}
        self.readback = _MsHistogram()
        self.starve = _MsHistogram()
        self._acc = dict.fromkeys(self.PHASES, 0.0)
        self._phase = None   # None = between cycles (parked or stopped)
        self._span = None
        self._t = 0.0
        self._worked = False
        self._prev = None    # seq of the last launch landed since a park
        self._ready = None   # (seq, read) of the last first copy
        self._copied = None  # this wait's ready read, until the wait closes

    def _open(self, phase: str, seq: int, now: float) -> None:
        self._phase, self._t = phase, now
        self._span = jax.profiler.TraceAnnotation("engine." + phase, seq=seq)
        self._span.__enter__()

    def _close(self, now: float) -> None:
        if self._phase is not None:
            if self._copied is not None:
                self.readback.observe((now - self._copied) * 1e3)
                self._copied = None
            self._acc[self._phase] += now - self._t
            self._span.__exit__(None, None, None)
            self._phase = self._span = None

    def top(self, seq: int) -> None:
        """Loop top: close the iteration that just ran (observed only when
        it dispatched or retired) and open the next one's ``admin``."""
        now = _clock()
        self._close(now)
        if self._worked:
            for phase, acc in self._acc.items():
                self.phases[phase].observe(acc * 1e3)
            self.cycle.observe(sum(self._acc.values()) * 1e3)
        self._acc = dict.fromkeys(self.PHASES, 0.0)
        self._worked = False
        self._open("admin", seq, now)

    def mark(self, phase: str, seq: int) -> float:
        """Enter ``phase`` (no-op when already in it or between cycles);
        returns the boundary's clock read for callers that share it."""
        now = _clock()
        if self._phase is not None and phase != self._phase:
            self._close(now)
            self._open(phase, seq, now)
            if phase in ("launch", "wait"):
                self._worked = True
        return now

    def park(self) -> None:
        """The loop waits for work or exits: not a cycle, drop the stretch."""
        self._close(_clock())
        self._worked = False
        self._prev = self._ready = None

    def landed(self, seq: int, launch_at: float, stamps: tuple,
               now: float) -> None:
        """Launch ``seq`` is back from the dispatch worker: ``stamps`` are
        the worker's reads (worker_in, enqueue, enqueued, worker_out),
        ``launch_at`` and ``now`` the loop's own around the hop."""
        edges = (launch_at, *stamps, now)
        for part, a, b in zip(self.PARTS, edges, edges[1:]):
            self.parts[part].observe((b - a) * 1e3)
        if self._prev is not None:
            # a predecessor whose first copy has not returned is still in
            # flight: the chip had work queued when this launch arrived
            back = self._ready is not None and self._ready[0] == self._prev
            self.starve.observe(
                max(0.0, stamps[1] - self._ready[1]) * 1e3 if back else 0.0
            )
        self._prev = seq

    def ready(self, seq: int, at: Optional[float] = None) -> float:
        """The retire's first device-to-host copy of launch ``seq`` returned
        (``at`` where a readback worker took the read)."""
        at = _clock() if at is None else at
        self._ready, self._copied = (seq, at), at
        return at

    def snapshot(self) -> dict:
        return {p + "_ms": h.snapshot() for p, h in self.phases.items()}

    def timeline(self) -> dict:
        """The launch timeline's blocks of lifecycle_stats()["pipeline"]."""
        return {
            "launch_parts": {
                p + "_ms": h.snapshot() for p, h in self.parts.items()
            },
            "readback_ms": self.readback.snapshot(),
            "starve_ms": self.starve.snapshot(),
        }


class _WorkerStamps:
    """The dispatch worker's side of a launch's timeline: four ``_clock``
    reads, ``worker_in`` (first line of the worker's half), ``enqueue``
    (immediately before the jitted step is called: a ragged launch's one
    upload and its unpack, a decode chunk's four uploads, page allocation
    and table build lie before it), ``enqueued`` (the call returned) and
    ``worker_out`` (before the return), handed back with the
    worker's result for the loop thread to account (_CycleClock.landed: the
    clock stays loop-thread only). While a profiler session is open,
    ``engine.upload`` and ``engine.enqueue`` annotations cover the first two
    stretches inside the caller's ``engine.dispatch``. ``launched``, where the
    caller gives one, is set with the ``enqueued`` read (or when the worker
    leaves without it): the ragged step's loop thread stands still until
    then (_ENQUEUED_WAIT_S). The jitted step is
    called between ``enqueue()`` and ``enqueued()`` with plain positional
    operands uploaded before: called through a ``call(fn, *args)`` helper
    or with starred operands, the first call of each program took 1.7-3.4 s
    longer on the state cache (set-up +10-15%; PERF.md section 6, PR 40)."""

    def __init__(self, seq: int, launched: Optional[threading.Event] = None):
        self.seq, self.reads, self._span = seq, [_clock()], None
        self._launched = launched

    def _open(self, name: str) -> None:
        # an annotation starts where it is built
        self._span = jax.profiler.TraceAnnotation(name, seq=self.seq)
        self._span.__enter__()

    def _shut(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def _release(self) -> None:
        if self._launched is not None:
            self._launched.set()

    def __enter__(self):
        self._open("engine.upload")
        return self

    def __exit__(self, *exc):
        self._shut()
        # a worker that raised before its jitted call releases the loop too
        self._release()

    def enqueue(self) -> None:
        self._shut()
        self.reads.append(_clock())
        self._open("engine.enqueue")

    def enqueued(self) -> None:
        self._shut()
        self.reads.append(_clock())
        self._release()


@dataclass
class _InFlightChunk:
    """One dispatched-but-unretired decode chunk. ``chunk``/``gstate``/``lp``
    are DEVICE arrays (possibly still computing); retire syncs them to host.
    ``active_mask`` is the host snapshot the dispatch was built from — the
    retire stage emits exactly those slots and nothing newer."""

    seq: int
    epoch: int
    active_mask: "np.ndarray"
    chunk: Any
    gstate: Any = None
    lp: Any = None
    want_lp: bool = False
    # the dispatch worker's four reads (_WorkerStamps), for the loop thread
    stamps: tuple = ()
    # paged backend: slots dropped from this chunk because the pool could
    # not hold their page extension (failed by the loop thread on landing)
    exhausted: List[int] = field(default_factory=list)
    # paged backend: (rows x passes, tokens those rows attended) of the
    # chunk's decode passes, counted by the loop thread on landing
    chain_work: tuple = (0, 0)
    # rows the chunk's sampler calls treat as live ([B] bool; the paged
    # chunk's device-side ``active`` is every row that holds tokens)
    live: Any = None


@dataclass
class _RaggedFlight:
    """One ragged launch that is enqueued and not retired: its ``plan``
    (host arrays, the rows' requests) and the worker's ``result`` (device
    arrays, possibly still computing)."""

    plan: dict
    result: dict

    @property
    def seq(self) -> int:
        return self.plan["seq"]

    @property
    def chunk(self):
        # what _wait_chunks blocks on: the launch's program has written
        # its rows' pages / state when its tokens exist
        return self.result["sampled"]

    def carries(self, slot: int) -> bool:
        return slot in self.plan["spans"]


def _decode_pass_work(first, passes) -> tuple:
    """(rows x passes, tokens attended) of rows that ride ``passes[r]``
    consecutive decode passes over paged KV, attending ``first[r]`` tokens
    in the first of them and one more in each next: what the paged decode
    kernel is given (``ragged.decode_chain_rows`` / ``_kv_tokens``)."""
    passes = np.asarray(passes, np.int64)
    tokens = passes * first + passes * (passes - 1) // 2
    return int(passes.sum()), int(tokens.sum())


def _mixed_pass_work(row_lens, kv_lens) -> tuple:
    """(rows, tokens of context, (query, key) pairs under the causal mask)
    of a mixed pass over paged KV: a row of ``row_lens[r]`` queries on
    ``kv_lens[r]`` tokens is read once and computes its history times its
    queries plus their triangle: what the ragged kernel is given
    (``ragged.mixed_rows`` / ``mixed_kv_tokens`` / ``mixed_qk_pairs``)."""
    n = np.asarray(row_lens, np.int64)
    kv = np.asarray(kv_lens, np.int64)[n > 0]
    n = n[n > 0]
    pairs = n * (kv - n) + n * (n + 1) // 2
    return int(n.size), int(kv.sum()), int(pairs.sum())


_LATENT_COUNTERS = (
    "rows_full", "rows_window", "index_keys_scored", "index_keys_kept",
    "window_keys", "decode_keys_full", "decode_keys_window",
    "mixed_keys_full", "mixed_keys_window", "decode_latent_tokens",
)


def _pass_visible(mixed_visible, chain_first, chain_passes, row_lens,
                  kv_lens) -> tuple:
    """A launch's visible keys as int64 arrays: of the mixed pass's valid
    tokens, of every (row, chained pass) (row r rides ``chain_passes[r]``
    passes, ``chain_first[r]`` visible keys in the first and one more in
    each next), and the mixed pass's live rows' queries and keys."""
    mixed = np.asarray(mixed_visible, np.int64)
    chain = np.concatenate([np.zeros(0, np.int64)] + [
        first + np.arange(int(n), dtype=np.int64)
        for first, n in zip(np.asarray(chain_first, np.int64),
                            np.asarray(chain_passes, np.int64)) if n > 0
    ])
    n = np.asarray(row_lens, np.int64)
    kv = np.asarray(kv_lens, np.int64)[n > 0]
    return mixed, chain, n[n > 0], kv


def _latent_pass_work(layout, mixed_visible=(), chain_first=(),
                      chain_passes=(), row_lens=(), kv_lens=()) -> dict:
    """What one launch over a latent page layout adds to ``latent.*`` of
    lifecycle_stats (docs/latent_cache.md). A token with v visible keys (its
    position + 1): a full layer's indexer scores all v and its attention
    reads min(v, index_topk) rows, a window layer's reads min(v, window).
    ``mixed_visible``: the visible keys of a mixed pass's valid tokens, with
    the pass's rows (``row_lens`` queries on ``kv_lens`` tokens each);
    the chained decode passes as :func:`_decode_pass_work` takes them: row r
    rides ``chain_passes[r]`` passes, ``chain_first[r]`` visible keys in the
    first and one more in each next. ``decode_*``: what the decode kernel
    reads; ``mixed_keys_*``: the rows the mixed pass's kernel must read at
    least, a ROW of the launch once (its last token's selection; the union
    of its tokens' windows)."""
    topk, window = layout.index_topk, layout.window
    n_full, n_window = layout.n_full, layout.n_window
    mixed, chain, n, kv = _pass_visible(
        mixed_visible, chain_first, chain_passes, row_lens, kv_lens)
    seen = np.concatenate([mixed, chain])
    out = {
        "rows_full": seen.size * n_full,
        "rows_window": seen.size * n_window,
        "index_keys_scored": int(seen.sum()) * n_full,
        "index_keys_kept": int(np.minimum(seen, topk).sum()) * n_full,
        "window_keys": int(np.minimum(seen, window).sum()) * n_window,
        "decode_keys_full": int(np.minimum(chain, topk).sum()) * n_full,
        "decode_keys_window": int(np.minimum(chain, window).sum()) * n_window,
        "mixed_keys_full": int(np.minimum(kv, topk).sum()) * n_full,
        "mixed_keys_window": int(
            np.minimum(kv, window + n - 1).sum()) * n_window,
    }
    out["decode_latent_tokens"] = (
        out["decode_keys_full"] + out["decode_keys_window"])
    return out


_WINDOW_COUNTERS = (
    "rows_full", "rows_window", "decode_keys_full", "decode_keys_window",
    "mixed_keys_full", "mixed_keys_window", "mixed_pairs_full",
    "mixed_pairs_window", "window_keys_unbounded",
)


def _window_pass_work(kinds, mixed_visible=(), chain_first=(),
                      chain_passes=(), row_lens=(), kv_lens=()) -> dict:
    """What one launch of a model whose paged path windows adds to
    ``window.*`` of lifecycle_stats (docs/window_attention.md), layers
    counted. ``kinds`` = ``bundle.paged_window`` (window, n_full, n_window);
    the operands as :func:`_latent_pass_work` takes them. A token with v
    visible keys reads v on a full layer and min(v, window) on a window
    layer. ``decode_keys_*``: what the decode kernel has to read in the
    chained passes; ``mixed_keys_*``: the keys the mixed pass's kernel has
    to read at least, a ROW of the launch once (the union of its tokens'
    windows); ``mixed_pairs_*``: the (query, visible key) pairs of the mixed
    pass; ``window_keys_unbounded``: what the window layers would read
    without the bound."""
    window, n_full, n_window = kinds.window, kinds.n_full, kinds.n_window
    mixed, chain, n, kv = _pass_visible(
        mixed_visible, chain_first, chain_passes, row_lens, kv_lens)
    tokens = mixed.size + chain.size
    return {
        "rows_full": tokens * n_full,
        "rows_window": tokens * n_window,
        "decode_keys_full": int(chain.sum()) * n_full,
        "decode_keys_window": int(np.minimum(chain, window).sum()) * n_window,
        "mixed_keys_full": int(kv.sum()) * n_full,
        "mixed_keys_window": int(
            np.minimum(kv, window + n - 1).sum()) * n_window,
        "mixed_pairs_full": int(mixed.sum()) * n_full,
        "mixed_pairs_window": int(np.minimum(mixed, window).sum()) * n_window,
        "window_keys_unbounded": int(chain.sum() + kv.sum()) * n_window,
    }


def _staging_layout(entries) -> tuple:
    """``(name, shape, is_bool)`` entries laid end to end in one int32
    staging buffer: ``((name, offset, shape, is_bool), ...)`` and the
    buffer's length (docs/ragged_attention.md, "The launch's operands").
    Hashable: the unpack program takes it as a static argument."""
    layout, offset = [], 0
    for name, shape, is_bool in entries:
        layout.append((name, offset, tuple(shape), is_bool))
        offset += math.prod(shape)
    return tuple(layout), offset


def _unpack_staged(staged, layout):
    """Slices a staged int32 buffer (:func:`_staging_layout`) back into
    its entries, by name, each at its shape and dtype (booleans crossed as
    0/1). Traced inside the program that takes the buffer."""
    out = {}
    for name, offset, shape, is_bool in layout:
        part = jax.lax.slice(
            staged, (offset,), (offset + math.prod(shape),)
        ).reshape(shape)
        out[name] = part != 0 if is_bool else part
    return out


def _unpack_ragged_operands(staged, layout, chain):
    """The device half of a ragged launch's ONE upload: the staged buffer
    back as the operands the jitted step takes, each as its own upload used
    to give it. A program of its own, dispatched before the step: the
    step's signature and trace know nothing of the buffer. ``chain`` is
    the device's [B] vector of each row's pending token (what the launch
    before this one sampled last for the row, or a finishing prompt's
    first token): ``chain_at[r]`` says where in ``tokens`` row ``r``'s goes,
    and a row whose token the host wrote itself points past the end."""
    out = _unpack_staged(staged, layout)
    out["tokens"] = out["tokens"].at[out.pop("chain_at")].set(
        chain, mode="drop"
    )
    return out


# what the first-token program is told of its row, one int32 word each; the
# last five cross as the bits of a float32
_FIRST_TOKEN_WORDS = ("slot", "seed", "top_k", "min_new", "guided")
_FIRST_TOKEN_FLOATS = (
    "temperature", "top_p", "presence", "frequency", "repetition",
)


def _first_token_layout(vocab: int) -> tuple:
    """The staging buffer of the first-token program
    (docs/ragged_attention.md, "The first token"): the row's words, its
    stop set, its bias row (float32 bits) and its prompt and grammar masks
    (one bit a token, 32 to the word, little end first)."""
    words = -(-vocab // 32)
    return _staging_layout(
        [(name, (1,), False)
         for name in _FIRST_TOKEN_WORDS + _FIRST_TOKEN_FLOATS]
        + [
            ("stop", (1, _STOP_SLOTS), False),
            ("bias", (1, vocab), False),
            ("pmask", (1, words), False),
            ("gmask", (1, words), False),
        ]
    )


# ragged scheduler (docs/ragged_attention.md): stage-3 brownout shrinks the
# per-step admission share to roughly one minimal chunk instead of the
# legacy gate's one-segment-per-chunk budget
_RAGGED_BROWNOUT_CHUNK = 16


def _window_paged_refusal(*, cache_mode, mesh, speculation, spec_tree,
                          lora_adapters) -> Optional[str]:
    """Why this engine cannot be built over a model that windows its paged
    path (``bundle.paged_window``), or None: what the window bound is not
    taught is refused by name (docs/window_attention.md)."""
    if cache_mode != "paged":
        return (
            "this model's window lives in the paged kernels: serve it with "
            "engine.cache=paged (got engine.cache={}); it has no dense-cache "
            "path".format(cache_mode)
        )
    if mesh is not None and mesh.size > 1:
        return (
            "a {}-device mesh cannot serve this model yet: its expert "
            "layers hold the experts the configuration names "
            "(experts_held) and the exchange of expert inputs across chips "
            "is not built. Serve one engine per chip".format(mesh.size)
        )
    if speculation or spec_tree:
        return (
            "speculation cannot serve a windowed paged path yet: verify "
            "rows need per-position logits from the model and a draft "
            "tree's ancestor mask is written against the causal bound "
            "alone (ops.paged_attention.window_kernel_unsupported_reason)"
        )
    if lora_adapters:
        return (
            "lora adapters are not served by this model: its projections "
            "have no adapter rows"
        )
    return None


def _latent_cache_refusal(bundle, *, cache_mode, mesh,
                          prefix_cache_host_pages, prefix_cache_host_bytes,
                          speculation, spec_tree,
                          lora_adapters) -> Optional[str]:
    """Why this engine cannot be built over a model's LATENT page layout
    (``bundle.paged_layout``: one compressed row a token for all heads, a
    window bound on some layers, an indexer-key plane on the others), or
    None. What the layout cannot do yet is refused by name with what it
    would take (docs/latent_cache.md)."""
    if cache_mode != "paged":
        return (
            "this model attends through a latent (one compressed row a "
            "token, shared by all heads): serve it with engine.cache=paged "
            "(got engine.cache={}); it keeps no per-head K/V for a dense "
            "cache and no recurrent state".format(cache_mode)
        )
    if mesh is not None and mesh.size > 1:
        return (
            "a {}-device mesh cannot serve the latent page layout yet: the "
            "latent kernels have no partitioning rule and the held experts "
            "are this chip's by configuration (experts_held); the exchange "
            "of expert inputs across chips is not built. Serve one engine "
            "per chip".format(mesh.size)
        )
    if bundle.config.get("kv_quant"):
        return (
            "kv_quant cannot serve the latent page layout: its rows are "
            "bfloat16 by the configuration (a quantised latent or "
            "indexer-key plane is another configuration)"
        )
    if prefix_cache_host_pages or prefix_cache_host_bytes:
        return (
            "prefix_cache_host_pages / prefix_cache_host_mb (HostKVTier) "
            "cannot serve the latent page layout yet: the host tier's slabs "
            "are [L, Hkv, P, D] K/V pages, and a latent page is three "
            "planes of other widths"
        )
    if speculation or spec_tree:
        return (
            "speculation cannot serve the latent page layout yet: verify "
            "rows (verify_paged, draft trees) need per-position logits and "
            "a tree mask in the latent kernels, and a rejected draft's "
            "indexer keys rolled back"
        )
    if lora_adapters:
        return (
            "lora_adapters are not served on the latent page layout yet: "
            "the low-rank projections have no adapter rows"
        )
    return None


def _state_cache_refusal(bundle, *, mesh, prefix_cache,
                         prefix_cache_host_pages, prefix_cache_host_bytes,
                         speculation, spec_tree,
                         lora_adapters, cache_mode="state") -> Optional[str]:
    """Why this engine cannot be built over a ROW STATE, or None: on
    ``engine.cache=state`` (a model with no keys and values at all), or for
    a model whose rows keep a state BESIDE their K/V pages
    (``bundle.row_state``, served from ``engine.cache=paged``:
    docs/hybrid_cache.md). Every feature that assumes that a sequence IS its
    pages is refused by name with its reason (docs/state_cache.md and
    docs/hybrid_cache.md list what each would need)."""
    beside = getattr(bundle, "row_state", None) is not None
    if beside and cache_mode != "paged":
        return (
            "this model's rows own K/V pages AND a recurrent state slot: "
            "serve it with engine.cache=paged (got engine.cache={}); it has "
            "no dense-cache path, and engine.cache=state is for models "
            "with no keys and values at all".format(cache_mode)
        )
    if not beside and getattr(bundle, "forward_ragged_state", None) is None:
        return (
            "engine.cache=state needs a model whose layers keep a recurrent "
            "state (forward_ragged_state / decode_state / init_state "
            "surfaces, e.g. config attention='power_retention'); this "
            "model attends over keys and values: use engine.cache=paged or "
            "dense"
        )
    what = ("a model that keeps a recurrent state beside its pages"
            if beside else "engine.cache=state")
    if prefix_cache:
        return (
            "prefix_cache cannot serve {}: a cached prefix "
            "is shared K/V pages, and a recurrent state has none; reuse "
            "would need snapshots of the state at block boundaries "
            "(RadixPrefixCache holds pages only). Set prefix_cache to 0"
            .format(what)
        )
    if prefix_cache_host_pages or prefix_cache_host_bytes:
        return (
            "prefix_cache_host_pages / prefix_cache_host_mb (HostKVTier) "
            "cannot serve {}: the host tier spills the "
            "prefix cache's K/V pages, and a state cache has neither"
            .format(what)
        )
    if bundle.config.get("kv_quant"):
        return (
            "kv_quant cannot serve {}: there are no keys "
            "and values to quantise, and the state is float32 by the "
            "configuration (a bfloat16 or int8 state is another model)"
            .format(what)
            if not beside else
            "kv_quant cannot serve {}: the scale pools have no place in a "
            "carry that holds the state planes, and the state is float32 "
            "by the configuration".format(what)
        )
    if speculation or spec_tree:
        return (
            "speculation cannot serve {}: a rejected draft "
            "cannot be rolled back out of a recurrent state without a "
            "snapshot of it (verify rows overwrite K/V positions; a state "
            "has no positions)".format(what)
        )
    if lora_adapters:
        return (
            "lora_adapters are not served from engine.cache=state yet (the "
            "state step takes no per-row adapter index)"
            if not beside else
            "lora_adapters are not served by {} yet (its projections have "
            "no adapter rows)".format(what)
        )
    if mesh is not None and mesh.size > 1:
        return (
            "engine.cache=state cannot serve under a {}-device mesh: the "
            "retention kernels have no partitioning rule and the sequence-"
            "parallel prefill (prefill_ring) passes K/V blocks round a ring, "
            "which a recurrent state does not have. Serve one engine per "
            "chip".format(mesh.size)
            if not beside else
            "a {}-device mesh cannot serve {} yet: the state kernels have "
            "no partitioning rule and the slot pool no sharding. Serve one "
            "engine per chip".format(mesh.size, what)
        )
    return None


@dataclass(eq=False)  # identity semantics: jobs live in (and leave) lists
class _RaggedJob:
    """One admission riding the ragged scheduler (docs/ragged_attention.md):
    the request's prompt prefills in budget-bounded chunk rows of the
    loop's ragged launches, writing straight into its reserved slot's KV
    (no mini cache, no separate prefill dispatch). ``pos`` is the next
    unprefilled prompt index (a radix prefix hit starts it past the shared
    run); the slot stays reserved via ``engine._admitting`` until the final
    chunk's commit or a failure path frees it."""

    request: GenRequest
    slot: int
    pos: int = 0
    started_at: float = field(default_factory=time.monotonic)


class _PrefillGate:
    """Decode-first chunked-prefill scheduling policy.

    Admission prefills run in worker threads concurrently with decode
    chunks, but every dispatch lands in the SAME device queue — an unpaced
    long-prompt segment train would enqueue ahead of the next decode chunk
    and blow up the decoding requests' inter-token latency. The gate bounds
    the interleave: while decode is active, at most ``segments_per_chunk``
    prefill dispatches may enter the queue per decode chunk (the decode loop
    ``deposit()``s that many permits after each chunk; admission threads
    ``acquire()`` one per prefill dispatch).

    ``stall_timeout`` is the prefill-starvation bound in the other
    direction: if decode stops depositing (loop stalled on commits or
    emission), a waiting prefill proceeds anyway after this many seconds —
    admission can be slowed by decode, never parked indefinitely. The
    default must comfortably EXCEED one decode-chunk duration (host
    dispatch plus device time), or
    permit-exhausted segments would time out past the gate mid-chunk and
    silently void the segments_per_chunk bound; it only ever bites when the
    loop is wedged, so seconds-scale is correct.
    """

    def __init__(self, segments_per_chunk: int = 2, stall_timeout: float = 2.0):
        self._spc_cfg = max(1, int(segments_per_chunk))
        self._spc = self._spc_cfg
        self._stall_timeout = float(stall_timeout)
        self._cond = threading.Condition()
        self._permits = self._spc
        self._active = False

    def set_budget(self, segments_per_chunk: Optional[int]) -> None:
        """Brownout override of the per-chunk prefill budget (stage >= 3
        shrinks it to 1 so decode slots drain ahead of new admissions);
        ``None`` restores the configured value."""
        with self._cond:
            self._spc = (
                max(1, int(segments_per_chunk))
                if segments_per_chunk
                else self._spc_cfg
            )
            self._permits = min(self._permits, self._spc)
            self._cond.notify_all()

    def set_active(self, active: bool) -> None:
        """Loop thread: decode has (in)active slots; inactive opens the gate."""
        with self._cond:
            self._active = bool(active)
            if not self._active:
                self._permits = self._spc
                self._cond.notify_all()

    def deposit(self) -> None:
        """Loop thread: a decode chunk completed — refresh the permit budget.

        Permits are SET, not accumulated: idle decode periods must not bank
        an unbounded burst allowance for a later admission."""
        with self._cond:
            self._permits = self._spc
            self._cond.notify_all()

    def acquire(self, bypass: bool = False) -> None:
        """Admission thread: blocks (boundedly) before one prefill dispatch.

        ``bypass`` (SINGLE-dispatch interactive admissions,
        docs/slo_scheduling.md): skip the pacing — the gate exists to keep
        multi-segment prefill trains from queueing ahead of decode chunks;
        a one-dispatch admission cannot train, and parking that
        first-token-critical enqueue behind a batch resume's permit is
        priority inversion at the device queue. Multi-segment interactive
        prefills stay paced: their segment train hurts co-resident
        inter-token latency exactly like a batch one."""
        if bypass:
            return
        with self._cond:
            if not self._active:
                return
            if self._permits <= 0:
                self._cond.wait_for(
                    lambda: self._permits > 0 or not self._active,
                    timeout=self._stall_timeout,
                )
            if self._permits > 0:
                self._permits -= 1
            # timed out with no permit: proceed — starvation bound


PRIORITY_CLASSES = ("interactive", "batch", "best_effort")
_CLASS_RANK = {c: i for i, c in enumerate(PRIORITY_CLASSES)}


class _ClassedPendingQueue:
    """Per-class pending queues replacing the single `_pending` FIFO
    (docs/slo_scheduling.md): strict class order across classes
    (interactive > batch > best_effort), earliest-deadline-first within a
    class (requests without a deadline order FIFO after every deadlined
    one), and a starvation floor — a lower class that waited through
    ``floor`` consecutive higher-class pops takes the next pop, so batch
    work keeps trickling through sustained interactive load.

    Production callers all run on the engine's event-loop thread, but the
    structure is internally locked (tests and the watchdog's deadline sweep
    may observe it from elsewhere)."""

    __guarded_by__ = {"_lock": ("_heaps", "_starve")}

    def __init__(self, starvation_floor: int = 8):
        self._heaps: Dict[str, list] = {c: [] for c in PRIORITY_CLASSES}
        self._seq = itertools.count()
        self._floor = max(1, int(starvation_floor))
        # consecutive higher-class pops each class sat through while
        # non-empty; reset when the class pops
        self._starve = {c: 0 for c in PRIORITY_CLASSES}
        self._lock = threading.Lock()

    @staticmethod
    def _key(request: "GenRequest") -> float:
        d = request._deadline
        return d if d is not None else float("inf")

    def put_nowait(self, request: "GenRequest") -> None:
        cls = getattr(request, "priority", None) or "interactive"
        if cls not in self._heaps:
            cls = "interactive"
        with self._lock:
            heapq.heappush(
                self._heaps[cls], (self._key(request), next(self._seq), request)
            )

    def _pop_class(self, cls: str) -> "GenRequest":  # tpuserve: ignore[TPU301] lock held by caller
        _, _, request = heapq.heappop(self._heaps[cls])
        self._starve[cls] = 0
        return request

    def get_nowait(self) -> "GenRequest":
        with self._lock:
            # starvation floor first: a class that waited through `floor`
            # higher-class pops gets this one (lowest starved class wins —
            # it has, by construction, waited the longest)
            for cls in reversed(PRIORITY_CLASSES):
                if self._heaps[cls] and self._starve[cls] >= self._floor:
                    return self._pop_class(cls)
            for i, cls in enumerate(PRIORITY_CLASSES):
                if self._heaps[cls]:
                    for lower in PRIORITY_CLASSES[i + 1:]:
                        if self._heaps[lower]:
                            self._starve[lower] += 1
                    return self._pop_class(cls)
        raise asyncio.QueueEmpty

    def qsize(self) -> int:
        with self._lock:
            return sum(len(h) for h in self._heaps.values())

    def empty(self) -> bool:
        return self.qsize() == 0

    def depths(self) -> Dict[str, int]:
        """Per-class queue depths (lifecycle_stats / Prometheus)."""
        with self._lock:
            return {c: len(h) for c, h in self._heaps.items()}

    def waiting(self, cls: str) -> int:
        """LIVE queued requests of ``cls`` — cancelled/failed entries stay
        heap-resident until a pop discards them, and preempting a batch
        slot for a dead interactive request would burn its preemption
        budget for nobody (the admission pop just drops the corpse)."""
        with self._lock:
            return sum(
                1
                for e in self._heaps.get(cls, ())
                if not e[2].cancelled and e[2].error is None
            )

    def requests(self) -> List["GenRequest"]:
        """Snapshot of every queued request (deadline sweeps)."""
        with self._lock:
            return [e[2] for h in self._heaps.values() for e in h]

    def shed_lowest(self, above: str) -> Optional["GenRequest"]:
        """Remove and return the lowest-class, latest-deadline queued
        request whose class is STRICTLY lower priority than ``above``
        (None when there is none): the class-aware shed path evicts it to
        make room for a higher-class arrival — best-effort sheds first,
        then batch."""
        above_rank = _CLASS_RANK.get(above, 0)
        with self._lock:
            for cls in reversed(PRIORITY_CLASSES):
                if _CLASS_RANK[cls] <= above_rank:
                    return None
                heap = self._heaps[cls]
                # mid-stream requests (preempted resumes: produced > 0,
                # consumer attached) are immune — shedding one turns an
                # in-progress 200/SSE response into a mid-stream 429 and
                # throws away its committed KV; with only resumes queued
                # the ARRIVAL sheds at the door instead
                live = [
                    e for e in heap
                    if not e[2].cancelled and e[2].error is None
                    and e[2].produced == 0
                ]
                if not live:
                    continue
                victim = max(live, key=lambda e: (e[0], e[1]))
                heap.remove(victim)
                heapq.heapify(heap)
                return victim[2]
        return None

    def pop_all(self) -> List["GenRequest"]:
        """Drain every queued request (engine stop)."""
        with self._lock:
            out = [e[2] for h in self._heaps.values() for e in h]
            for h in self._heaps.values():
                h.clear()
            return out


class _BrownoutController:
    """Staged overload degradation with hysteresis (docs/slo_scheduling.md).

    A pressure score in [0, ~2] (max over queue-depth, pool-headroom,
    deadline-hit and watchdog signals) drives the stage:

    - stage 0: normal operation;
    - stage 1: speculative decoding disabled (verify slack pressure off the
      pool, fewer wasted positions per dispatch);
    - stage 2: + batch-class ``max_new_tokens`` capped (long batch decodes
      release their slots early);
    - stage 3: + prefill admission budget shrunk to one segment per decode
      chunk and best-effort traffic shed at the door.

    Raising is immediate (the overload response must be fast). Lowering
    requires the score to fall below the stage's DOWN threshold — strictly
    below its UP threshold, the hysteresis band — AND a minimum dwell since
    the last change, so a score oscillating across a threshold cannot flap
    the stage."""

    UP = (0.70, 0.85, 0.95)
    DOWN = (0.50, 0.65, 0.80)

    def __init__(self, dwell: float = 2.0):
        self.dwell = float(dwell)
        self.stage = 0
        self.score = 0.0
        self.signals: Dict[str, float] = {}
        self.transitions = 0
        self._changed_at = float("-inf")

    def update(self, score: float, signals: Optional[dict] = None,
               now: Optional[float] = None) -> int:
        now = time.monotonic() if now is None else now
        self.score = float(score)
        if signals is not None:
            self.signals = dict(signals)
        target_up = 0
        for i, threshold in enumerate(self.UP):
            if self.score >= threshold:
                target_up = i + 1
        if target_up > self.stage:
            self.stage = target_up
            self.transitions += 1
            self._changed_at = now
        elif (
            self.stage > 0
            and self.score < self.DOWN[self.stage - 1]
            and now - self._changed_at >= self.dwell
        ):
            self.stage -= 1
            self.transitions += 1
            self._changed_at = now
        return self.stage


class LLMEngineCore:
    """Slot-based continuous batching over a dense per-slot KV cache."""

    # thread-affinity registry (tpuserve-analyze TPU501,
    # docs/static_analysis.md): this state has NO lock on purpose — exactly
    # one thread owns it. "loop" = the asyncio event-loop thread (handlers,
    # the decode loop, the watchdog task); "worker" = asyncio.to_thread
    # dispatch/readback/prefill workers. The pipeline queue, quarantine
    # map, slot table, and host token/DFA mirrors are loop-owned (workers
    # receive snapshots via the prep dict and hand results back through the
    # retire stage); the device-resident chains are worker-owned (the
    # dispatch worker is the only stage running device programs; the loop
    # resets them only at protocol-serialized points, annotated at the
    # definition sites).
    __affine_to__ = {
        "loop": (
            "_inflight", "_quarantine", "_dispatching", "_slot_req",
            "_admitting", "_next_token", "_gstate", "_slot_overrides",
            "_prefill_jobs", "_tier_counters", "_ragged_flights",
            # multi-step / spec-as-row chain observability
            # (docs/ragged_attention.md): per-launch window and acceptance
            # state is planned and retired on the loop thread only; the
            # dispatch worker reads plan snapshots, never these attrs
            "_step_rows", "_hist_launch_tokens", "_hist_spec_accept",
            # draft-tree verify rows (docs/spec_decode_trees.md): the
            # proposer's hit counters and the accept-depth histogram are
            # planned/retired on the loop thread; draft-ahead shipping
            # watermarks advance at retire chunk boundaries
            "_spec_proposer", "_hist_spec_tree_depth",
            "_kv_draft_ahead",
        ),
        "worker": ("_next_token_dev", "_gstate_dev"),
    }

    # compile-surface registry (tpuserve-analyze TPU603,
    # docs/static_analysis.md): every jit entry this class creates must be
    # declared here, and every "serve"-role entry must appear in the warmup
    # shape registry (llm/warmup.py WARMUP_COVERED) so its key space
    # compiles before the serve fence — a serve-time XLA compile is a
    # 100-1000 ms loop-thread stall that masquerades as scheduling tail.
    # "lazy" = request-path entries compiled on first use BY DESIGN (rare
    # features whose one-per-variant compile is bounded and attributed by
    # the compile sentry, not a per-request key).
    __compile_keys__ = {
        "serve": (
            "_prefill_jit", "_prefill_ring_jit", "_prefill_pipeline_jit",
            "_prefill_chunk_first_jit", "_prefill_chunk_jit",
            "_assemble_prefix_jit", "_insert_jit",
            "_merge_rows_jit", "_decode_chunk_jit",
            "_decode_paged_chunk_jit", "_first_token_jit",
            "_set_sampling_row_jit", "_spec_chunk_jit",
            "_ragged_paged_jit", "_ragged_state_jit",
            "_ragged_unpack_jit", "_ragged_chain_jit",
        ),
        # prompt scoring runs only for completions echo+logprobs requests:
        # one compile per prefill bucket on first use, sentry-attributed
        "lazy": ("_score_prompt_jit",),
    }

    # sharding registry (tpuserve-analyze TPU802, docs/static_analysis.md):
    # the sharding builder covering each donated/sharded operand family the
    # serve-path jit entries above consume. Every builder named here must be
    # in parallel/sharding.py's __sharding_builders__ closed world; the
    # runtime sharding sentry (llm/sharding_sentry.py) audits the live
    # arrays against what these builders declared at init.
    __shardings__ = {
        "params": "parallel.sharding.llama_param_sharding",
        "params_quantized": "parallel.sharding.llama_quantized_param_sharding",
        "kv_cache": "parallel.sharding.llama_cache_sharding",
        "tokens": "parallel.sharding.batch_sharding",
        "host_state": "parallel.sharding.replicated",
    }

    # ownership-discipline registry (tpuserve-analyze TPU7xx,
    # docs/static_analysis.md): the engine's two cross-function protocols.
    # Quarantined slots release at the barrier retire (or the pipeline-
    # discard paths); grammar refs release at slot teardown / admission
    # failure. Both pair across functions by design ("static": False), so
    # the runtime ownership ledger audits them at the drain boundary.
    __acquires__ = {
        "_quarantine_slot": {"resource": "slot.quarantine",
                             "releases": ("_release_quarantine",),
                             "drops": ("_discard_pipeline",),
                             "static": False},
        "_ensure_grammar": {"resource": "guided.ref",
                            "releases": ("_deref_guided_key",
                                         "_deref_guided_request",
                                         "_release_guided"),
                            "static": False},
    }

    def __init__(
        self,
        bundle,
        params,
        *,
        max_batch: int = 8,
        max_seq_len: int = 2048,
        prefill_buckets: Optional[List[int]] = None,
        mesh=None,
        eos_token_id: Optional[int] = None,
        rng_seed: int = 0,
        decode_steps: int = 4,
        quantize: Optional[str] = None,
        # canonical name for the weight-quantization knob (docs/w4a16.md);
        # ``quantize`` stays as the historical alias
        weight_quant: Optional[str] = None,
        cache_mode: str = "dense",
        page_size: int = 16,
        num_pages: Optional[int] = None,
        long_prefill_threshold: Optional[int] = None,
        long_bucket_step: Optional[int] = None,
        chunked_prefill_size: Optional[int] = None,
        prefill_segments_per_decode: Optional[int] = 2,
        prefill_stall_timeout: Optional[float] = None,
        speculation: Optional[str] = None,
        spec_k: int = 4,
        spec_ngram: int = 2,
        spec_sampling: bool = True,
        # draft TREES on the verify rows (docs/spec_decode_trees.md):
        # the ragged scheduler's q=k+1 verify row becomes a fixed-budget
        # draft tree from the n-gram FOREST proposer — same verify budget,
        # higher acceptance. Paged cache only (the dense chunk layers have
        # no per-token tree mask); spec_branch caps root branching.
        spec_tree: bool = False,
        spec_branch: int = 2,
        pipeline_chunk: int = 512,
        lora_adapters: Optional[Dict[str, Any]] = None,
        prefix_cache: Optional[int] = None,
        prefix_block: int = 64,
        prefix_cache_bytes: Optional[int] = None,
        prefix_cache_pages: Optional[int] = None,
        # host-RAM KV tier (docs/kv_tiering.md, paged backend only): number
        # of preallocated host pages behind the prefix cache — device-budget
        # eviction demotes cached runs there instead of dropping them, and a
        # hit on a demoted run re-onlines via async DMA overlapped with the
        # tail prefill. None/0 disables (legacy drop-on-evict).
        prefix_cache_host_pages: Optional[int] = None,
        prefix_cache_host_bytes: Optional[int] = None,
        logprobs_k: int = 20,  # OpenAI's top_logprobs ceiling
        tokenizer=None,  # required for guided decoding (token byte tables)
        # -- request-lifecycle hardening (None disables each knob; the
        # serving front installs production defaults — unit tests keep the
        # historical unbounded behavior unless they opt in) ---------------
        max_pending: Optional[int] = None,   # admission bound on _pending
        queue_timeout: Optional[float] = None,  # default queue-wait budget
        ttft_timeout: Optional[float] = None,   # default first-token budget
        total_timeout: Optional[float] = None,  # default whole-request budget
        watchdog_interval: Optional[float] = None,  # stall detector period
        # decode pipeline depth (None -> TPUSERVE_PIPELINE_DEPTH env, default
        # 2); 1 restores the serial dispatch->sync->emit loop
        pipeline_depth: Optional[int] = None,
        # -- ragged scheduling (docs/ragged_attention.md) ------------------
        # not a choice: cache_mode decides ("paged" and "state" run the
        # ragged token-budget step, "dense" the two-dispatch loop). A value
        # is checked against that and a mismatch refused by name; the
        # keyword stays while benchmark/configs pass it (ROADMAP D2a).
        scheduler: Optional[str] = None,
        # ragged mode: max tokens (decode rows + prefill-chunk rows) per
        # launch; must exceed max_batch so admissions always make progress.
        # None -> TPUSERVE_STEP_TOKEN_BUDGET, default max(128, 4*max_batch)
        step_token_budget: Optional[int] = None,
        # ragged mode: decode rows carry up to this many chained token
        # positions per mixed launch (multi-step decode rows,
        # docs/ragged_attention.md) — the launch advances each decode slot
        # by up to this many tokens, amortizing the per-launch dispatch
        # bubble and weight read the way the pipelined chunk does. The
        # per-launch window buckets to a power of two
        # (llm/shapes.decode_steps_bucket) and shrinks with the token
        # budget. None inherits ``decode_steps``; 1 restores q=1 rows.
        ragged_decode_steps: Optional[int] = None,
        # -- SLO-aware scheduling (docs/slo_scheduling.md) -----------------
        # preemptible batch lane: under slot pressure with interactive work
        # queued, batch-class slots are preempted at a chunk boundary (their
        # generated-so-far KV committed into the radix prefix cache) and
        # requeued; preempt_budget bounds preemptions per request
        preempt_batch: bool = True,
        preempt_budget: int = 2,
        # starvation floor: a lower class that waited through this many
        # higher-class queue pops takes the next pop
        starvation_floor: int = 8,
        # brownout controller: None -> enabled iff admission control is on
        # (max_pending set); explicit True/False overrides
        brownout: Optional[bool] = None,
        brownout_batch_cap: int = 32,   # stage>=2 batch max_new_tokens cap
        brownout_dwell: float = 2.0,    # min seconds between stage drops
        # replica identity (docs/replication.md): set by the replica group
        # (llm/replica.py) so health()/lifecycle_stats() — and through them
        # the Prometheus lifecycle series — carry a ``replica`` label.
        # None keeps the legacy single-engine payload shape.
        replica: Optional[str] = None,
    ):
        self.bundle = bundle
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self.eos_token_id = eos_token_id
        self.decode_steps = max(1, int(decode_steps))
        # a model whose paged path windows says so itself
        # (``bundle.paged_window``: docs/window_attention.md)
        self._window = getattr(bundle, "paged_window", None)
        if cache_mode == "paged" and self._window is None and int(
            bundle.config.get("sliding_window", 0) or 0
        ):
            raise ValueError(
                "sliding_window models of arch llama need engine.cache=dense:"
                " the paged kernels take a window bound "
                "(ops/paged_attention.py, window=) but models/llama.py does "
                "not hand it to them on its paged path yet (forward_ragged / "
                "decode_paged attend every causal key); arch afmoe does "
                "(models/afmoe.py, docs/window_attention.md)"
            )
        if self._window is not None:
            refused = _window_paged_refusal(
                cache_mode=cache_mode, mesh=mesh, speculation=speculation,
                spec_tree=spec_tree, lora_adapters=lora_adapters,
            )
            if refused:
                raise ValueError(refused)
        self._window_counts = dict.fromkeys(_WINDOW_COUNTERS, 0)
        if cache_mode == "paged" and getattr(
            bundle, "paged_unsupported_reason", None
        ):
            raise ValueError(bundle.paged_unsupported_reason)
        if cache_mode not in ("dense", "paged", "state"):
            raise ValueError(
                "cache_mode must be 'dense', 'paged' or 'state'"
            )
        # the state cache (docs/state_cache.md): models whose layers keep a
        # recurrent state in place of keys and values own one fixed-size
        # SLOT per sequence. Everything that assumes K/V pages says no here,
        # by name, until it is taught otherwise — never a silent fallback.
        recurrent = getattr(bundle, "attention", "softmax") == "power_retention"
        if recurrent and cache_mode != "state":
            raise ValueError(
                "this model mixes tokens through power retention (a recurrent "
                "state, no keys and values): serve it with engine.cache=state "
                "(got engine.cache={})".format(cache_mode)
            )
        # the cache kind decides the scheduler (docs/ragged_attention.md):
        # pages and state slots are served by the ragged token-budget step,
        # the dense cache by the two-dispatch loop with its prefill programs,
        # its gate and its serial speculative scan. ``scheduler`` is no
        # choice: a given value is only checked against the cache's own.
        # The dense-only knobs (prefill_buckets, chunked_prefill_size,
        # long_prefill_threshold, long_bucket_step, pipeline_chunk,
        # prefill_segments_per_decode, prefill_stall_timeout) are accepted
        # and reach no program of a ragged engine (prefill_buckets still
        # sizes the prompts of llm/warmup.py's sweep).
        self._ragged = cache_mode in ("paged", "state")
        sched = "ragged" if self._ragged else "two_dispatch"
        if scheduler is not None and scheduler != sched:
            if scheduler not in ("two_dispatch", "ragged"):
                raise ValueError(
                    "scheduler must be 'two_dispatch' or 'ragged' (got {!r})"
                    .format(scheduler)
                )
            raise ValueError(
                "cache={} runs the {} scheduler; scheduler={!r} exists only "
                "on cache={}".format(
                    cache_mode, sched, scheduler,
                    "dense" if self._ragged else "paged or state",
                )
            )
        # a model whose rows keep a recurrent state BESIDE their pages says
        # so itself (``bundle.row_state``: docs/hybrid_cache.md): the engine
        # then holds a slot pool next to the page pool, slot = batch row
        self._row_state = getattr(bundle, "row_state", None)
        if cache_mode == "state" or self._row_state is not None:
            refused = _state_cache_refusal(
                bundle, mesh=mesh, prefix_cache=prefix_cache,
                prefix_cache_host_pages=prefix_cache_host_pages,
                prefix_cache_host_bytes=prefix_cache_host_bytes,
                speculation=speculation, spec_tree=spec_tree,
                lora_adapters=lora_adapters, cache_mode=cache_mode,
            )
            if refused:
                raise ValueError(refused)
        self._ssm_counts = dict.fromkeys(
            ("update_rows", "chunk_rows", "chunk_tokens"), 0)
        # a model's own page layout (docs/latent_cache.md): the pools'
        # planes, the kernels and the counters follow from the model
        self._latent = getattr(bundle, "paged_layout", None)
        if self._latent is not None:
            refused = _latent_cache_refusal(
                bundle, cache_mode=cache_mode, mesh=mesh,
                prefix_cache_host_pages=prefix_cache_host_pages,
                prefix_cache_host_bytes=prefix_cache_host_bytes,
                speculation=speculation, spec_tree=spec_tree,
                lora_adapters=lora_adapters,
            )
            if refused:
                raise ValueError(refused)
        self._latent_counts = dict.fromkeys(_LATENT_COUNTERS, 0)
        # a launch's keys by layer kind: (what counts them, the model's
        # layer kinds, where they add up), for a latent page layout or a
        # windowed paged path; None for a model of one layer kind
        self._by_kind = None
        if self._latent is not None:
            self._by_kind = (
                _latent_pass_work, self._latent, self._latent_counts)
        elif self._window is not None:
            self._by_kind = (
                _window_pass_work, self._window, self._window_counts)
        self.cache_mode = cache_mode
        # kernel or XLA gather for paged pools of this model's shape
        # (ops.paged_attention): the same pure function models/llama.py
        # evaluates at trace time, over the same arguments, so health()'s
        # "kernels" block states what the traced programs run. None = the
        # Mosaic kernels. An int8 pool on 16-token pages, a head_dim-64
        # model and a non-TPU backend all get their reason here.
        from ..ops.paged_attention import paged_kernel_unsupported_reason

        paged_reason = paged_kernel_unsupported_reason(
            bundle.head_dim if self._latent is None
            else tuple(self._latent.row_widths), page_size,
            "int8" if bundle.config.get("kv_quant")
            else bundle.config.get("dtype", "bfloat16"),
        )
        if cache_mode == "paged":
            self._paged_kernel_reason = paged_reason
        elif cache_mode == "state":
            # the state pools' two launches (ops/power_retention.py): the
            # same pure function models/llama.py evaluates at trace time
            from ..ops.power_retention import (
                retention_kernel_unsupported_reason,
            )

            self._paged_kernel_reason = retention_kernel_unsupported_reason(
                bundle.head_dim
            )
        else:
            # the dense cache never reaches the paged kernels; say so, and
            # say whether this model's paged pools would reach them
            self._paged_kernel_reason = (
                "engine.cache=dense: the Pallas kernels serve "
                "engine.cache=paged pools only"
                + ("; paged pools would take XLA too: " + paged_reason
                   if paged_reason else "")
            )
        if (
            cache_mode == "paged" and paged_reason is None
            and mesh is not None and mesh.size > 1
        ):
            # established on the v5e toolchain (PR 21): lowering the paged
            # kernels over tp-sharded pools fails with "Mosaic kernels
            # cannot be automatically partitioned. Please wrap the call in
            # a shard_map." — fail at endpoint load, not on the first
            # request's trace
            raise ValueError(
                "engine.cache=paged cannot serve under a {}-device mesh on "
                "TPU: the Pallas paged-attention kernels have no "
                "partitioning rule (Mosaic kernels cannot be automatically "
                "partitioned) and are not shard_map-wrapped yet. Serve one "
                "engine per chip, or use engine.cache=dense with the mesh"
                .format(mesh.size)
            )
        # host-tier knob validation (docs/kv_tiering.md): a budget that
        # silently does nothing reads as "tiering on" to the operator —
        # fail at construction (= endpoint load) naming the knob instead.
        # "auto" sizes the tier from /proc/meminfo at construction
        # (clamped; HostTierAutoSizeError names unsupported platforms).
        host_auto = (
            isinstance(prefix_cache_host_bytes, str)
            and prefix_cache_host_bytes.strip().lower() == "auto"
        )
        if isinstance(prefix_cache_host_bytes, str) and not host_auto:
            raise ValueError(
                "prefix_cache_host_bytes (aux engine.prefix_cache_host_mb) "
                "must be a size or 'auto': got {!r}".format(
                    prefix_cache_host_bytes
                )
            )
        if host_auto and prefix_cache_host_pages:
            raise ValueError(
                "prefix_cache_host_mb='auto' derives the host page count "
                "itself; drop engine.prefix_cache_host_pages (or set an "
                "explicit size)"
            )
        if prefix_cache_host_bytes and not host_auto \
                and not prefix_cache_host_pages:
            raise ValueError(
                "prefix_cache_host_bytes (aux engine.prefix_cache_host_mb) "
                "is set but the host tier is disabled: set "
                "prefix_cache_host_pages (aux "
                "engine.prefix_cache_host_pages) to enable it"
            )
        if (prefix_cache_host_pages or host_auto) and (
            cache_mode != "paged"
            or not prefix_cache
            or not hasattr(bundle, "prefill_chunk")
        ):
            raise ValueError(
                "prefix_cache_host_pages (or prefix_cache_host_mb='auto') "
                "needs cache_mode='paged' and a prefix_cache on a bundle "
                "with prefill_chunk (the host tier spills the paged radix "
                "prefix cache; docs/kv_tiering.md)"
            )
        if cache_mode == "paged" and getattr(
            bundle, "forward_ragged", None
        ) is None:
            raise ValueError(
                "engine.cache=paged runs the ragged scheduler, which needs a "
                "model bundle with a forward_ragged surface"
            )
        if step_token_budget is None:
            raw = os.environ.get("TPUSERVE_STEP_TOKEN_BUDGET", "")
            step_token_budget = int(raw) if raw else None
        # default: 128 tokens a launch (4 per row at large batches). A state
        # cache's decode pass streams every row's whole state whatever the
        # launch carries, so a prompt chunk rides it for little: 256 there
        # (measured on the v5e, PERF.md PR 26: at 128 a third of the time
        # went to the prompts' launches and the cell's TPOT swung with the
        # prompts a window happened to hold)
        self._step_token_budget = (
            int(step_token_budget)
            if step_token_budget is not None
            else max(256 if cache_mode == "state" else 128,
                     4 * self.max_batch)
        )
        if self._ragged and self._step_token_budget <= self.max_batch:
            # every decode row costs one budget token; a budget at or below
            # max_batch could starve admissions forever
            raise ValueError(
                "step_token_budget ({}) must exceed max_batch ({}) so "
                "prefill chunks always fit beside a full decode batch"
                .format(self._step_token_budget, self.max_batch)
            )
        # multi-step ragged decode rows (docs/ragged_attention.md): each
        # launch advances every decode slot by up to this many chained
        # tokens. Capped by decode_steps' slack sizing below: the paged
        # table width and the dense cache slack are dimensioned from
        # decode_steps, so the ragged window may not exceed it.
        self._ragged_decode_steps = (
            max(1, int(ragged_decode_steps))
            if ragged_decode_steps is not None
            else self.decode_steps
        )
        if self._ragged_decode_steps > self.decode_steps:
            raise ValueError(
                "ragged_decode_steps ({}) must not exceed decode_steps "
                "({}): per-slot KV slack and page-table width are sized "
                "from decode_steps".format(
                    self._ragged_decode_steps, self.decode_steps
                )
            )
        # the largest per-launch window actually reachable (pow2-bucketed);
        # warmup enumerates every power of two up to it
        self._ragged_steps_cap = decode_steps_bucket(self._ragged_decode_steps)
        self._buckets = sorted(
            b for b in (prefill_buckets or _DEFAULT_PREFILL_BUCKETS) if b <= max_seq_len
        ) or [max_seq_len]
        self._mesh = mesh
        # long-context sequence parallelism: prompts past the threshold
        # prefill through ring attention over the mesh's sp axis (the prompt
        # spreads across chips; SURVEY.md §5.7) — needs sp > 1 and a bundle
        # with a prefill_ring surface
        self._sp = int(dict(mesh.shape).get("sp", 1)) if mesh is not None else 1
        if self._sp > 1 and getattr(bundle, "prefill_ring", None) is None:
            self._sp = 1
        self._long_threshold = (
            int(long_prefill_threshold)
            if long_prefill_threshold is not None
            else self._buckets[-1]
        )
        # long-prefill shapes pad to multiples of this (must divide sp)
        step = int(long_bucket_step) if long_bucket_step else self._sp * 512
        self._long_step = -(-step // self._sp) * self._sp
        # largest sp-divisible ring bucket that still fits the cache: prompts
        # between this and max_seq_len fall back to plain prefill (rounding
        # the bucket UP past max_seq_len would crash the cache insert)
        self._long_cap = (self.max_seq_len // self._sp) * self._sp if self._sp > 1 else 0

        # multi-LoRA: install each named adapter into the param tree's
        # stacked factors (models/lora.py) BEFORE quantization/sharding —
        # the stacks stay full precision (quantize only touches base
        # projections) and shard/replicate per parallel/sharding.py
        self._adapter_index: Dict[str, int] = {}
        if lora_adapters:
            from ..models import lora as lora_lib

            if not int(getattr(bundle, "lora_rank", 0) or 0):
                raise ValueError(
                    "lora_adapters given but the model was built without "
                    "lora_rank (set engine.lora.rank / config lora_rank)"
                )
            if len(lora_adapters) > int(bundle.max_loras):
                raise ValueError(
                    "{} adapters exceed max_loras {}".format(
                        len(lora_adapters), bundle.max_loras
                    )
                )
            for i, (name, tree) in enumerate(lora_adapters.items(), start=1):
                params = lora_lib.install_adapter(params, i, tree)
                self._adapter_index[name] = i
        self._lora_enabled = bool(self._adapter_index)

        # int8 weight quantization: params live in HBM as int8 + scales; the
        # model's weight accessor (models/llama.py `_w`) dequantizes each
        # weight INSIDE the traced layer body — per layer even under
        # scan_layers — so XLA fuses dequant next to each consumer matmul and
        # weights at rest stay int8 (HBM ~halves) or group-int4 (~quarters;
        # the decode path is weight-read bound, so bytes saved are tok/s).
        if weight_quant and quantize and weight_quant != quantize:
            raise ValueError(
                "weight_quant={!r} conflicts with the legacy quantize={!r} "
                "alias; set only one".format(weight_quant, quantize)
            )
        quantize = weight_quant or quantize
        self._quantized = False
        self.weight_quant = ""
        # offline-quantized bundles (scripts/quantize_ckpt.py) arrive
        # already packed: detect BEFORE quantizing so a redundant (or
        # mismatched) weight_quant knob becomes a no-op (or a clear error)
        # instead of quantize_llama_params choking on the packed dicts —
        # and so TP sharding picks the quantized specs / stats report the
        # real weight format when no knob is set at all.
        from ..ops.quant import detect_weight_quant

        pre = detect_weight_quant(params)
        if quantize and quantize not in ("int8", "int4"):
            raise ValueError(
                "unsupported weight_quant mode {!r} (expected 'int8' or "
                "'int4')".format(quantize)
            )
        if pre and quantize and pre != quantize:
            raise ValueError(
                "weight_quant={!r} requested but the bundle is already "
                "{}-quantized (scripts/quantize_ckpt.py output); drop the "
                "knob or quantize from the original full-precision "
                "checkpoint".format(quantize, pre)
            )
        if pre:
            self._quantized = True
            self.weight_quant = pre
        elif quantize:
            from ..ops.quant import quantize_llama_params

            params = quantize_llama_params(
                params, bits=4 if quantize == "int4" else 8
            )
            self._quantized = True
            self.weight_quant = quantize
        # weight-tree HBM footprint (global bytes; per-chip is 1/tp under a
        # mesh) — the decode roofline's dominant bytes/step term, surfaced
        # through lifecycle_stats()["weights"] and health()
        import jax as _jax

        self._weight_bytes = int(sum(
            leaf.nbytes
            for leaf in _jax.tree.leaves(params)
            if hasattr(leaf, "nbytes")
        ))

        if mesh is not None:
            from ..parallel.sharding import (
                llama_cache_sharding,
                llama_param_sharding,
                llama_quantized_param_sharding,
                shard_params,
            )

            heads = dict(
                n_kv_heads=getattr(bundle, "n_kv_heads", None),
                n_heads=bundle.config.get("n_heads"),
            )
            if not self._quantized:
                self.params = shard_params(
                    mesh, params, llama_param_sharding(mesh, params, **heads)
                )
            else:
                # int8 tree TP-shards like the bf16 weights (scales lose the
                # input-axis entry) — per-chip HBM ≈ 1/tp of the model
                self.params = shard_params(
                    mesh, params,
                    llama_quantized_param_sharding(mesh, params, **heads),
                )
            self._cache_sharding = llama_cache_sharding(
                mesh, quantized=bool(bundle.config.get("kv_quant"))
            )
        else:
            self.params = params
            self._cache_sharding = None

        # speculative chunks verify spec_k+1 positions per round and
        # decode_steps rounds per dispatch; both cache backends carry that
        # much per-slot slack so in-chunk writes never clamp/overflow
        # (sized from the CLAMPED spec_k — max(1, ...), applied again below —
        # a raw spec_k<=0 would under-allocate)
        spec_slack = (
            self.decode_steps * (max(1, int(spec_k)) + 1) if speculation else 0
        )
        # kept for supervised recovery: a poisoned dense decode step may have
        # consumed (donated) the cache — rebuilding needs the original size
        self._cache_slack = spec_slack
        # int8 paged KV (docs/paged_kv_quant.md): the same kv_quant knob the
        # dense cache honors now reaches the paged backend — int8 pools +
        # per-(token, head) scale pools, dequant inside the paged kernel
        self._paged_quant = (
            self.cache_mode == "paged"
            and bool(bundle.config.get("kv_quant"))
        )
        if self.cache_mode == "paged":
            from .kv_cache import PagedKVCache

            # default pool: every slot can hold max_seq_len + one decode chunk
            # (no oversubscription by default; page 0 is the reserved null page).
            # Speculation over-allocates decode_steps*(k+1) tokens per chunk
            # and rolls back (PagePool.truncate), so the table width and the
            # default pool must cover that worst case.
            pages_per_slot = -(
                -(self.max_seq_len + max(self.decode_steps, spec_slack))
                // page_size
            )
            total_pages = num_pages or (self.max_batch * pages_per_slot + 1)
            row_state = None
            if self._row_state is not None:
                from .kv_cache import StateCache

                row_state = StateCache(bundle.init_state, self.max_batch)
            self.paged_cache = PagedKVCache(
                bundle.n_layers, bundle.n_kv_heads, bundle.head_dim,
                num_pages=total_pages, page_size=page_size,
                max_slots=self.max_batch,
                dtype=bundle.config.get("dtype", "bfloat16"),
                kv_quant=str(bundle.config.get("kv_quant") or ""),
                layout=self._latent,
                counters=getattr(self._window, "counters", 0),
                row_state=row_state,
            )
            if mesh is not None:
                # shard the pools' kv-head dim over tp (pools [L,Hkv,N,P,D]) —
                # without this every chip replicates the full pool
                from jax.sharding import NamedSharding, PartitionSpec as P

                pool_sharding = NamedSharding(mesh, P(None, "tp", None, None, None))
                self.paged_cache.k = jax.device_put(self.paged_cache.k, pool_sharding)
                self.paged_cache.v = jax.device_put(self.paged_cache.v, pool_sharding)
                if self._paged_quant:
                    # scale pools [L, Hkv, N, P] shard the same kv-head dim
                    scale_sharding = NamedSharding(mesh, P(None, "tp", None, None))
                    self.paged_cache.k_scale = jax.device_put(
                        self.paged_cache.k_scale, scale_sharding
                    )
                    self.paged_cache.v_scale = jax.device_put(
                        self.paged_cache.v_scale, scale_sharding
                    )
            self._pages_per_seq = pages_per_slot
            self.cache = None
            if self._expert_counters() is not None:
                # the scrape's copy (_moe_snapshot) is an eager add: run
                # once here, so that its one small program compiles at
                # construction and not under the first scrape inside a
                # serving window
                self._expert_counters() + 0
            if self._paged_kernel_reason is None:
                self._check_kernel_smem()
            # None, or the slot pool whose planes ride ``v_carry``
            self.state_cache = row_state
        elif self.cache_mode == "state":
            from .kv_cache import StateCache

            # one slot per batch row: admission is bounded by free rows, so
            # the pool cannot run out under a row (no num_pages, no page
            # table, no oversubscription)
            self.paged_cache = None
            self.cache = None
            self.state_cache = StateCache(bundle.init_state, self.max_batch)
        else:
            self.paged_cache = None
            self.state_cache = None
            # dense: the slack keeps verify's dynamic_update_slice writes
            # from clamping at the buffer edge (a clamp would overwrite
            # live K/V)
            self.cache = bundle.init_cache(
                self.max_batch, self.max_seq_len + spec_slack
            )
            if self._cache_sharding is not None:
                self.cache = {
                    k: jax.device_put(v, self._cache_sharding[k])
                    for k, v in self.cache.items()
                }

        # slot bookkeeping (host side)
        self._slot_req: List[Optional[GenRequest]] = [None] * self.max_batch
        self._next_token = np.zeros(self.max_batch, np.int32)
        self._temperature = np.zeros(self.max_batch, np.float32)
        self._top_k = np.zeros(self.max_batch, np.int32)
        self._top_p = np.ones(self.max_batch, np.float32)
        self._lora_slots = np.zeros(self.max_batch, np.int32)  # 0 = base
        # sampling extras (penalties / bias / seeds): host mirrors per slot;
        # the [B, V] device state (generated-token counts, prompt mask, dense
        # bias) allocates lazily on the first request that needs any of it
        self._vocab = int(bundle.config.get("vocab_size", 0))
        self._presence = np.zeros(self.max_batch, np.float32)
        self._frequency = np.zeros(self.max_batch, np.float32)
        self._repetition = np.ones(self.max_batch, np.float32)
        self._seeds = np.full(self.max_batch, -1, np.int64)
        self._min_tokens = np.zeros(self.max_batch, np.int32)
        # per-slot stop-token sets for min_tokens suppression (the same set
        # _emit finishes on: stop_token_ids or [eos]); -1-padded, first
        # _STOP_SLOTS honored
        self._stop_rows = np.full((self.max_batch, _STOP_SLOTS), -1, np.int32)
        self._slot_extra = np.zeros(self.max_batch, bool)
        self._counts_dev = None   # [B, V] int32 generated-token histogram
        self._bias_dev = None     # [B, V] float32 dense logit bias
        self._pmask_dev = None    # [B, V] bool prompt-token mask

        # per-class pending queues (strict class order, EDF within a class,
        # starvation floor) — docs/slo_scheduling.md
        self._pending = _ClassedPendingQueue(starvation_floor)
        self._loop_task: Optional[asyncio.Task] = None
        # replica identity in a fleet (docs/replication.md); None = legacy
        # single-engine payloads (no `replica` key in health/stats)
        self.replica_id = str(replica) if replica is not None else None
        # -- request-lifecycle hardening state ----------------------------
        self.max_pending = int(max_pending) if max_pending else None
        self._queue_timeout = float(queue_timeout) if queue_timeout else None
        self._ttft_timeout = float(ttft_timeout) if ttft_timeout else None
        self._total_timeout = float(total_timeout) if total_timeout else None
        self._watchdog_interval = (
            float(watchdog_interval) if watchdog_interval else None
        )
        self._watchdog_task: Optional[asyncio.Task] = None
        self._last_progress = time.monotonic()
        # bumped by the watchdog when it fails a stalled batch; the loop
        # compares it around every dispatch and discards stale results
        self._recover_epoch = 0
        self._recovering = False
        self.counters: Dict[str, int] = {
            "sheds_queue": 0,
            "sheds_pool": 0,
            "deadline_queue": 0,
            "deadline_ttft": 0,
            "deadline_total": 0,
            "watchdog_trips": 0,
            "step_failures": 0,
            "preemptions": 0,
            "ragged_steps": 0,
            # decode tokens advanced by ragged mixed launches (multi-step
            # windows + accepted spec tokens): ragged_steps / this ratio is
            # dispatches-per-decode-token, the bubble-amortization headline
            "ragged_decode_tokens": 0,
            # prompt tokens that rode ragged launches as chunk rows, and
            # model passes (a launch is one mixed pass plus its chained
            # decode steps): what a roofline of the step needs to count
            "ragged_prefill_tokens": 0,
            "ragged_passes": 0,
            # rows the dense layers of the mixed passes multiplied: the
            # compact axis, whole, a launch
            "ragged_dense_rows": 0,
            # host-to-device transfers the dispatch worker made before the
            # jitted call, summed over ragged launches: over ragged_steps
            # it reads 1 (the staged buffer)
            "ragged_h2d_transfers": 0,
            # ragged launches whose results a worker thread waited for and
            # copied while the event loop ran (under ragged_steps by the
            # launches that had already run when the loop looked)
            "ragged_waits_off_loop": 0,
            "ragged_first_tokens": 0,
            "ragged_first_tokens_behind_launch": 0,
            # ragged launches enqueued while the one before them had not
            # been read back (the launch kept in flight), and rows such a
            # launch carried for nothing: they had ended at the launch
            # before for a reason only the device knew, and their step was
            # dropped at retire
            "ragged_launches_behind": 0,
            "ragged_surplus_rows": 0,
            # rows x decode passes over paged KV (the chained passes of a
            # ragged launch, the passes of a decode chunk) and the tokens
            # those rows attended there: the paged decode kernel's work
            "decode_chain_rows": 0,
            "decode_chain_kv_tokens": 0,
            # rows of the mixed passes over paged KV, the context those rows
            # hold and the (query, key) pairs they compute: the ragged
            # kernel's work, a row once a pass and layer
            "mixed_rows": 0,
            "mixed_kv_tokens": 0,
            "mixed_qk_pairs": 0,
            # rows the engine.spec.tree chaos seam demoted from spec-verify
            # back to plain decode (docs/spec_decode_trees.md fallback row)
            "spec_tree_fallbacks": 0,
        }
        # the ``sampler`` block of health() / lifecycle_stats(): sampler calls
        # inside launches (a ragged launch's mixed pass and chained steps, a
        # decode chunk's steps) and those of them whose live rows switch on
        # the sort / the random draw (llm/sampling.py row_needs); a request's
        # first token is sampled alone at [1, V] and is not a launch pass
        self._sampler_passes = {
            "passes": 0, "filtered_passes": 0, "drawn_passes": 0,
        }
        # -- SLO-aware scheduling state (docs/slo_scheduling.md) ----------
        # per-(reason, class) shed counters backing engine_sheds_total
        self._class_sheds: Dict[str, Dict[str, int]] = {}
        # recent admission-commit timestamps: the observed drain rate turns
        # a 429's Retry-After from a constant into queue_depth / rate
        self._admit_times: Deque[float] = deque(maxlen=32)
        self._admit_count = 0
        self._preempt = bool(preempt_batch)
        self._preempt_budget = max(0, int(preempt_budget))
        self._brownout = (
            _BrownoutController(dwell=brownout_dwell)
            if (brownout if brownout is not None else max_pending is not None)
            else None
        )
        self._brownout_batch_cap = max(1, int(brownout_batch_cap))
        self._brownout_checked = 0.0
        # (t, deadline_hits, watchdog_trips, admits) snapshot anchoring the
        # pressure window's deadline/watchdog rates
        self._pressure_window: Optional[tuple] = None
        self._rng = jax.random.PRNGKey(rng_seed)
        self._rng_lock = threading.Lock()
        self._step_counter = itertools.count()
        self._stopped = False
        self._prefill_templates: Dict[int, Any] = {}
        self._template_lock = threading.Lock()
        # admission overlap: prefills run in worker threads while decode
        # chunks continue; finished prefills land here and are committed into
        # their reserved slot at the next chunk boundary (loop thread only)
        self._ready: "asyncio.Queue" = asyncio.Queue()
        self._admitting: set = set()
        self._admission_tasks: set = set()  # strong refs; see _run_loop_inner
        # guided decoding (llm/guided.py): grammars compile once per unique
        # spec into a COMBINED state space (per-grammar state offsets) so
        # mixed-grammar batches share one mask/byte-table pair on device.
        # Retraces are bounded by padding the combined state count to
        # power-of-two buckets.
        self._guided_lock = threading.Lock()
        self._tokenizer = tokenizer
        self._grammars: Dict[str, dict] = {}      # key -> entry
        self._gmask_np: Optional[np.ndarray] = None   # [S, Vb] uint8
        self._gbyte_np: Optional[np.ndarray] = None   # [S, 256] int16
        self._gmask_dev = None
        self._gbyte_dev = None
        self._gtok_dev = None                     # (tok_bytes, tok_len)
        self._gtok_np = None
        self._gtok_bytes = None                   # cached token_byte_table
        self._gstate = np.full(self.max_batch, -1, np.int32)
        self._slot_guided_key: List[Optional[str]] = [None] * self.max_batch
        self._guided_dirty = False
        # decode-first prefill pacing (None/0 disables the policy). The
        # ragged scheduler REPLACES the gate outright: admission pacing is
        # the per-step token budget, and there are no standalone prefill
        # dispatches left to pace (docs/ragged_attention.md)
        self._prefill_gate = (
            _PrefillGate(
                int(prefill_segments_per_decode),
                **(
                    {"stall_timeout": float(prefill_stall_timeout)}
                    if prefill_stall_timeout
                    else {}
                ),
            )
            if (prefill_segments_per_decode and not self._ragged)
            else None
        )
        # -- ragged scheduler state (docs/ragged_attention.md) -------------
        # in-progress chunked admissions, consumed by the loop in order
        # (class order held by the admission pop); loop-affine
        self._prefill_jobs: List[_RaggedJob] = []
        # admissions whose worker-thread prep (grammar compile) finished,
        # waiting for the loop to open their job
        self._ragged_ready: "asyncio.Queue" = asyncio.Queue()
        # per-step token-budget utilization (used / budget) and per-phase
        # row counters, exported as engine_step_token_budget_utilization /
        # engine_step_rows{phase} (statistics/metrics.py)
        self._hist_budget = _MsHistogram(
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0)
        )
        self._step_rows = {"prefill": 0, "decode": 0, "spec_verify": 0}
        # multi-step / spec-as-row observability (loop-affine, like the
        # budget histogram): decode tokens advanced per mixed launch
        # (multi-step windows + accepted spec tokens) and the per-launch
        # mean accepted-draft fraction over spec verify rows — the two
        # numbers that say whether the per-launch dispatch bubble is
        # actually amortized (engine_decode_tokens_per_launch /
        # engine_spec_acceptance_rate in statistics/metrics.py)
        self._hist_launch_tokens = _MsHistogram(
            buckets=(1, 2, 4, 8, 16, 32, 64)
        )
        self._hist_spec_accept = _MsHistogram(
            buckets=(0.0, 0.2, 0.4, 0.6, 0.8, 1.0)
        )
        self._wake: Optional[asyncio.Event] = None

        # -- pipelined decode (docs/pipelined_decode.md) -------------------
        # Bounded in-flight dispatch queue: chunk N+1 is enqueued while
        # chunk N still computes on device; chunk N's readback + emission
        # (the retire stage) overlaps chunk N+1's compute. The only
        # cross-chunk data dependency — the last sampled token — chains on
        # device (chunk[:, -1]), so no host roundtrip sits between chunks.
        self.pipeline_depth = (
            max(1, int(pipeline_depth))
            if pipeline_depth is not None
            else _env_pipeline_depth()
        )
        self._inflight: Deque[_InFlightChunk] = deque()
        # the ragged step's launches that are enqueued and not retired,
        # oldest first: one, or two while the next launch goes behind the
        # one in flight (docs/ragged_attention.md, "A launch in flight")
        self._ragged_flights: Deque[_RaggedFlight] = deque()
        self._dispatch_seq = 0
        # (seq, active_mask) of a chunk whose worker-thread dispatch is in
        # progress (not yet an _inflight entry): the slot-reuse barrier
        # must see it — the concurrent retire stage can free slots
        self._dispatching: Optional[tuple] = None
        # slot -> dispatch seq that must retire before the slot's pages may
        # be freed / the slot re-admitted: it was freed at a retire while
        # younger chunks that still decode it were in flight (their extra
        # tokens are dropped by _emit's None check; their KV writes must not
        # land in re-allocated pages)
        self._quarantine: Dict[int, int] = {}
        # device-resident cross-chunk state (None -> upload the host
        # mirror); _slot_overrides marks slots whose host value must win at
        # the next dispatch (fresh commits between dispatches)
        self._next_token_dev = None
        self._gstate_dev = None
        self._slot_overrides = np.zeros(self.max_batch, bool)
        # (slot's request) whose sampling rows the host mirrors hold: a
        # launch planned behind the one that ends a prompt writes them
        # before the commit does (_stage_slot_config)
        self._slot_config_of: List[Optional[GenRequest]] = (
            [None] * self.max_batch
        )
        # cached device-side sampling constants: re-uploading temperature /
        # top_k / top_p (and the static extras rows) as fresh device arrays
        # every chunk puts 6+ tiny host->device transfers on every dispatch;
        # they only change at commit (invalidated there)
        self._sampling_dev = None
        self._extras_dev = None
        # dispatch/retire stage timing for the lifecycle collector.
        # retire_ms = wait_ms + emit_ms of the cycle clock: it INCLUDES the
        # blocking device->host sync, so it is not host time
        self._hist_dispatch = _MsHistogram()
        self._hist_retire = _MsHistogram()
        self._cycle = _CycleClock()
        # a request's way to its first token, observed at the first _emit
        # (queue_wait_ms at every slot reservation: a preempted request's
        # re-admission is a new wait, not a new TTFT)
        self._hist_request = {
            name: _MsHistogram(_REQUEST_MS_BUCKETS)
            for name in ("queue_wait_ms", "admit_ms", "prefill_ms", "ttft_ms")
            + _PREFILL_STRETCHES
        }
        self._hist_prefill_launches = _MsHistogram(_PREFILL_LAUNCH_BUCKETS)
        # host-tier promotion reaping (docs/kv_tiering.md): loop-affine —
        # completed promotion DMAs are observed at retire boundaries
        self._tier_counters = {"reaps": 0}

        # -- disaggregated prefill/decode (docs/disaggregation.md) ---------
        # KV-transport endpoint + role, attached by the replica group
        # (attach_kv_transport); None = monolithic engine, every ship/
        # receive path short-circuits. Counters are plain GIL-atomic bumps:
        # ships land on the loop thread (commit), receives on the group's
        # receive worker, hit/recompute accounting on admission workers.
        self._kv_transport = None
        self.replica_role = "hybrid"
        self._kv_ship_stats = {
            "ships": 0,            # shipments exported + sent
            "ship_pages": 0,
            "ship_drops": 0,       # transport full / injected ship fault
            "receives": 0,         # shipments imported into the cache
            "receive_pages": 0,
            "receive_empty": 0,    # nothing queued for the key
            "receive_failures": 0, # fault/pool/geometry -> dropped
            "hits": 0,             # shipped request admitted over the
            "recomputes": 0,       # shipped prefix vs. recomputed it
            "draft_ships": 0,      # draft-ahead partial frames sent at
            "draft_pages": 0,      # ragged chunk boundaries
            "draft_aborts": 0,     # kv.ship.partial fault / send failure
        }
        # draft-ahead shipping state (loop thread): slot -> {offset pages
        # already shipped unsealed, aborted}. Sealed/cleared at commit
        # (_maybe_ship), dropped with the slot on every failure path
        # (_free_ragged_slot) — an unsealed receiver assembly is never
        # consumable, so dropping the state IS the remote cleanup.
        self._kv_draft_ahead: Dict[int, dict] = {}
        # ship (export+send, loop thread) / receive (import, group worker)
        # wall-time — engine_kv_ship_ms{direction} in statistics/metrics.py
        self._hist_ship_ms = _MsHistogram()
        self._hist_receive_ms = _MsHistogram()

        # -- compiled functions --------------------------------------------
        # frozen config the traced closures need is captured as LOCALS, not
        # read off self: a jitted function that closes over self bakes the
        # attribute value into the trace, and a later mutation is silently
        # ignored (tpuserve-analyze TPU201 enforces this tree-wide)
        decode_steps = self.decode_steps

        def _prefill(params, tokens, seq_lens, cache_template, lora_idx=None):
            if lora_idx is None:  # static at trace: non-LoRA graphs unchanged
                return bundle.prefill(params, tokens, seq_lens, cache_template)
            return bundle.prefill(
                params, tokens, seq_lens, cache_template, lora_idx
            )

        self._prefill_jit = jax.jit(_prefill)

        if self._sp > 1:

            def _prefill_ring(params, tokens, seq_lens, cache_template,
                              lora_idx=None):
                if lora_idx is None:
                    return bundle.prefill_ring(
                        params, tokens, seq_lens, cache_template, mesh
                    )
                return bundle.prefill_ring(
                    params, tokens, seq_lens, cache_template, mesh, lora_idx
                )

            self._prefill_ring_jit = jax.jit(_prefill_ring)
        else:
            self._prefill_ring_jit = None

        # pipeline-parallel prefill over the mesh's pp axis: long prompts
        # flow through layer-stage slabs as sequence-chunk microbatches
        # (models/llama.py prefill_pipeline) so all pp groups compute
        # concurrently instead of all-gathering weights per layer. Gated to
        # configs the stage body reproduces exactly (no LoRA here: adapter
        # stacks ride the scanned layer axis the pipeline re-slabs).
        self._pp = int(dict(mesh.shape).get("pp", 1)) if mesh is not None else 1
        self._pp_chunk = max(1, int(pipeline_chunk))
        if (
            self._pp > 1
            and getattr(bundle, "prefill_pipeline", None) is not None
            and bundle.n_layers % self._pp == 0
            and not lora_adapters
        ):

            pp_stages, pp_chunk = self._pp, self._pp_chunk

            def _prefill_pp(params, tokens, seq_lens, cache_template,
                            lora_idx=None):
                assert lora_idx is None
                return bundle.prefill_pipeline(
                    params, tokens, seq_lens, cache_template,
                    stages=pp_stages, chunk=pp_chunk,
                )

            self._prefill_pipeline_jit = jax.jit(_prefill_pp)
        else:
            self._prefill_pipeline_jit = None

        # chunked prefill: bound each admission dispatch to C tokens so
        # decode chunks interleave on the device stream between prompt
        # segments instead of queueing behind one full-prompt prefill
        self._chunked = int(chunked_prefill_size or 0)
        if self._chunked > 0 and hasattr(bundle, "prefill_chunk"):
            # the first chunk reads the shared never-mutated template, so it
            # must NOT donate; later chunks own their cache and do. Non-final
            # chunks skip the lm_head projection (static with_logits arg).
            self._prefill_chunk_first_jit = jax.jit(
                bundle.prefill_chunk, static_argnames=("with_logits",)
            )
            self._prefill_chunk_jit = jax.jit(
                bundle.prefill_chunk,
                donate_argnums=(4,),
                static_argnames=("with_logits",),
            )
        else:
            self._chunked = 0

        # automatic prefix caching (llm/prefix_cache.py): radix tree of
        # block-granular prompt-prefix KV shared across admissions. On the
        # dense path a hit assembles the stored KV into the mini cache and
        # prefills only the remainder via prefill_chunk; on the paged path a
        # hit maps refcounted pool pages straight into the slot's page table
        # (zero KV copies for the shared run) and storing a prompt is a
        # refcount bump on the slot's own pages. Ring-prefill prompts skip it.
        self._prefix = None
        if prefix_cache and hasattr(bundle, "prefill_chunk"):
            from .prefix_cache import RadixPrefixCache

            if cache_mode == "paged":
                # shared runs must cover whole pages (a block ending mid-page
                # would put live-slot writes inside shared pages): round the
                # block up to the page size
                block = -(-int(prefix_block) // page_size) * page_size
                pool = self.paged_cache.pool
                # a cached page's true HBM cost — K+V data planes plus, on
                # int8 pools, the f32 scale rows that share its lifecycle —
                # derived from the pools themselves so the budget can't
                # drift from the layout kv_cache.py owns
                page_bytes = (
                    sum(self.paged_cache.pool_bytes().values())
                    // pool.num_pages
                )
                # host-RAM tier (docs/kv_tiering.md): preallocate the host
                # page slabs and hand the cache the demote/promote backend —
                # leaf-LRU eviction then spills to host RAM instead of
                # dropping, and warm TTFT becomes capacity-planned
                tier_backend = None
                if host_auto:
                    # size the tier from MemAvailable at CONSTRUCTION
                    # (docs/kv_tiering.md): half of what the host reports,
                    # clamped, converted through the true per-page bytes the
                    # pools themselves define. Off-Linux the probe raises
                    # the named HostTierAutoSizeError — endpoint load
                    # fails fast instead of serving tierless.
                    from .kv_cache import (
                        available_host_memory_bytes,
                        cohosted_worker_processes,
                    )

                    # the half-of-MemAvailable heuristic is PER HOST, not
                    # per process: co-hosted process-backend workers
                    # (TPUSERVE_COHOSTED_PROCS, serving/process_replica.py)
                    # each run this same sizer against the same meminfo
                    # reading, so the budget divides by the fleet width or
                    # a 2-worker fleet over-commits host RAM 2x
                    budget = (
                        available_host_memory_bytes() // 2
                        // cohosted_worker_processes()
                    )
                    budget = min(
                        max(budget, _AUTO_HOST_TIER_MIN_BYTES),
                        _AUTO_HOST_TIER_MAX_BYTES,
                    )
                    prefix_cache_host_pages = max(1, budget // page_bytes)
                    prefix_cache_host_bytes = None  # budget = capacity
                if prefix_cache_host_pages:
                    self.paged_cache.enable_host_tier(
                        int(prefix_cache_host_pages)
                    )
                    tier_backend = self.paged_cache
                self._prefix = RadixPrefixCache(
                    int(prefix_cache), block, max_bytes=prefix_cache_bytes,
                    max_pages=prefix_cache_pages, pool=pool,
                    page_bytes=page_bytes,
                    backend=tier_backend,
                    host_max_bytes=prefix_cache_host_bytes,
                )
            else:
                self._prefix = RadixPrefixCache(
                    int(prefix_cache), int(prefix_block),
                    max_bytes=prefix_cache_bytes,
                )
            self._prefix_chunk = self._chunked or int(prefix_block)

            def _assemble(template, prefix_bufs, plen):
                out = {
                    name: jax.lax.dynamic_update_slice(
                        template[name], pre, (0,) * template[name].ndim
                    )
                    for name, pre in prefix_bufs.items()
                }
                out["length"] = jnp.reshape(plen, (1,)).astype(jnp.int32)
                return out

            self._assemble_prefix_jit = jax.jit(_assemble)
            if self._chunked == 0:
                # the hit path drives (the donating) prefill_chunk even when
                # chunked prefill is not configured — it always owns its
                # assembled cache, so no non-donating first-segment variant
                # is needed here
                self._prefill_chunk_jit = jax.jit(
                    bundle.prefill_chunk,
                    donate_argnums=(4,),
                    static_argnames=("with_logits",),
                )

        def _insert(cache, mini_kv, length, slot):
            """Route a prefilled mini cache's buffers into the slot batch.
            Generic over the cache's buffer keys (k/v plus the int8 KV
            path's k_scale/v_scale)."""
            out = {}
            for key, buf in cache.items():
                if key == "length":
                    continue
                zeros = (0,) * (buf.ndim - 2)
                out[key] = jax.lax.dynamic_update_slice(
                    buf, mini_kv[key], (0, slot) + zeros
                )
            out["length"] = jax.lax.dynamic_update_slice(
                cache["length"], length[None].astype(jnp.int32), (slot,)
            )
            return out

        self._insert_jit = jax.jit(_insert, donate_argnums=(0,))

        def _merge_rows(dev, host, override):
            """Fold host-side per-slot overrides (fresh commits) into a
            device-chained [B] vector without a full re-upload."""
            return jnp.where(override, host, dev)

        self._merge_rows_jit = jax.jit(_merge_rows)

        self._lp_k = lp_k = max(1, int(logprobs_k))

        @jax.named_scope("logprobs")
        def _lp_of(logits, sampled, nb):
            """(chosen logprob [B], top ids [B,K], top logprobs [B,K]).
            Callers pass the PENALIZED logits when bias/penalties are active
            — reported logprobs reflect what was actually sampled from
            (OpenAI semantics for logit_bias)."""
            lp_full = jax.nn.log_softmax(logits)
            chosen = lp_full[jnp.arange(nb), sampled]
            top_lp, top_id = jax.lax.top_k(lp_full, lp_k)
            return chosen, top_id.astype(jnp.int32), top_lp

        def _guided_mask(logits, gstate, guided):
            """Constrain logits to the slots' grammar states (llm/guided.py
            compiled tables). gstate < 0 = unguided slot."""
            mask_bits, _bt, _tb, _tl = guided
            nb = logits.shape[0]
            guided_on = gstate >= 0
            rows = mask_bits[jnp.clip(gstate, 0)]               # [B, Vb] u8
            bits = (rows[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
            allowed = bits.reshape(nb, -1)[:, : logits.shape[-1]] > 0
            allowed = jnp.where(guided_on[:, None], allowed, True)
            # a fully-masked row (cannot happen for pruned grammars; belt
            # and braces) degrades to unconstrained instead of NaN
            any_ok = jnp.any(allowed, axis=-1, keepdims=True)
            allowed = allowed | ~any_ok
            return jnp.where(allowed, logits, jnp.float32(-1e30))

        def _guided_advance(gstate, sampled, ok, guided):
            """Walk the sampled token's bytes through the byte DFA (on
            device; Lmax tiny gathers). Zero-length tokens (EOS/specials)
            leave the state in place — EOS finishes the request anyway."""
            _mb, byte_trans, tok_bytes, tok_len = guided
            tb = tok_bytes[sampled]                              # [B, L]
            tl = tok_len[sampled]                                # [B]
            s0 = jnp.clip(gstate, 0)

            def step(i, s):
                nxt = byte_trans[
                    jnp.clip(s, 0), tb[:, i].astype(jnp.int32)
                ].astype(jnp.int32)
                return jnp.where(i < tl, nxt, s)

            walked = jax.lax.fori_loop(0, tok_bytes.shape[1], step, s0)
            return jnp.where((gstate >= 0) & ok, walked, gstate)

        def _decode_chunk(params, tokens, cache, active, sampling, rng,
                          lora_idx=None, extras=None, counts=None, pmask=None,
                          guided=None, gstate=None, want_lp=False):
            """`decode_steps` decode+sample steps fused in one executable
            (lax.scan) — host dispatch overhead amortizes over the chunk.
            ``extras``/``counts``/``pmask`` (penalties, bias, seeds, token
            histogram) are optional: the no-extras trace is unchanged.
            ``guided``/``gstate`` (grammar tables + per-slot DFA states)
            constrain sampling on device when present.
            ``want_lp`` (static) additionally emits per-token logprobs."""
            nb = tokens.shape[0]

            def body(carry, xs):
                tokens, cache, counts, gstate = carry
                step_rng, step_off = xs
                old_len = cache["length"]
                if lora_idx is None:
                    logits, cache = bundle.decode(params, tokens, cache)
                else:
                    logits, cache = bundle.decode(params, tokens, cache, lora_idx)
                # inactive slots: keep their length frozen (their garbage KV
                # write sits beyond `length` and is masked / later overwritten)
                cache["length"] = jnp.where(active, cache["length"], old_len)
                logits = logits.astype(jnp.float32)
                if guided is not None:
                    logits = _guided_mask(logits, gstate, guided)
                if extras is None:
                    sampled = sample_tokens(
                        logits, sampling, step_rng, live=active
                    )
                    lp_src = logits
                else:
                    ex = extras._replace(counters=extras.counters + step_off)
                    sampled = sample_tokens(
                        logits, sampling, step_rng, ex, counts, pmask,
                        live=active,
                    )
                    # reported logprobs reflect bias/penalties (OpenAI
                    # semantics); XLA CSEs this against the sampler's own
                    # penalize pass
                    lp_src = (
                        penalize_logits(logits, ex, counts, pmask)
                        if want_lp
                        else logits
                    )
                    counts = counts.at[jnp.arange(nb), sampled].add(
                        active.astype(jnp.int32)
                    )
                if guided is not None:
                    gstate = _guided_advance(gstate, sampled, active, guided)
                out = (sampled, _lp_of(lp_src, sampled, nb)) if want_lp else sampled
                return (sampled, cache, counts, gstate), out

            rngs = jax.random.split(rng, decode_steps)
            steps = jnp.arange(decode_steps, dtype=jnp.int32)
            if gstate is None:
                gstate = jnp.full((nb,), -1, jnp.int32)
            (_, cache, counts, gstate), out = jax.lax.scan(
                body, (tokens, cache, counts, gstate), (rngs, steps)
            )
            if want_lp:
                toks, (chosen, top_id, top_lp) = out
                # [steps, ...] -> batch-major
                lp = (chosen.T, jnp.swapaxes(top_id, 0, 1), jnp.swapaxes(top_lp, 0, 1))
                return toks.T, cache, counts, lp, gstate
            return out.T, cache, counts, None, gstate  # [B, decode_steps]

        self._decode_chunk_jit = jax.jit(
            _decode_chunk, donate_argnums=(2,), static_argnames=("want_lp",)
        )
        # first-token (admission) logprobs from the prefill logits
        def _score_prompt(params, tokens, lora_idx=None):
            """Teacher-forced scoring: tokens [1, S] -> (chosen [S-1],
            top_ids [S-1, K], top_lp [S-1, K]) for positions 1..S-1 (the
            first token has no conditional). OpenAI completions
            `echo` + `logprobs` needs per-prompt-token logprobs.

            The softmax/top-k pass runs in SEQUENTIAL position blocks
            (lax.map): a full-bucket float32 log_softmax over a 128k vocab
            would be a multi-GB HBM transient next to resident weights +
            KV — an OOM that kills in-flight decode."""
            logits = bundle.apply(params, tokens, lora_idx=lora_idx)[0]
            src = logits[:-1]                            # [S-1, V] model dtype
            tgt = tokens[0, 1:]
            block = 256
            s1, v = src.shape
            pad = (-s1) % block
            src = jnp.pad(src, ((0, pad), (0, 0)))
            tgt = jnp.pad(tgt, (0, pad))

            def blk(args):
                lg, tg = args
                lp = jax.nn.log_softmax(lg.astype(jnp.float32))
                chosen = jnp.take_along_axis(lp, tg[:, None], axis=1)[:, 0]
                # exact rank among the full vocab (vLLM prompt_logprobs
                # reports true ranks, not top-k positions)
                rank = 1 + jnp.sum(lp > chosen[:, None], axis=-1)
                tl, ti = jax.lax.top_k(lp, lp_k)
                return chosen, rank.astype(jnp.int32), ti.astype(jnp.int32), tl

            ch, rk, ti, tl = jax.lax.map(
                blk,
                (src.reshape(-1, block, v), tgt.reshape(-1, block)),
            )
            return (
                ch.reshape(-1)[:s1],
                rk.reshape(-1)[:s1],
                ti.reshape(-1, lp_k)[:s1],
                tl.reshape(-1, lp_k)[:s1],
            )

        self._score_prompt_jit = jax.jit(_score_prompt)

        def _first_token(logits, staged, layout, key, state, keyed):
            """A prompt's FIRST token (docs/ragged_attention.md, "The first
            token"), from the logits of the launch that carried its last
            chunk (``logits`` [R, V]: the row is picked on the device) and
            ONE staged host buffer (``_first_token_layout``): the
            grammar's start mask, then the sampler over this one row, with
            the request's bias / penalties / ``min_tokens`` / seed where
            it has any (``keyed``, static: the two traces the admission
            sampler always had), and the log-probabilities of what was
            sampled from. ``state`` (the slots' [B, V] counts / bias /
            prompt-mask rows, or None while no request has needed them)
            takes the slot's reset from the sampled id as a device value."""
            ops = _unpack_staged(staged, layout)
            vocab = ops["bias"].shape[1]
            slot = ops["slot"][0]

            def f32(name):
                return jax.lax.bitcast_convert_type(ops[name], jnp.float32)

            def bits(name):
                words = jax.lax.bitcast_convert_type(ops[name], jnp.uint32)
                lanes = (
                    words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)
                ) & 1
                return lanes.reshape(1, -1)[:, :vocab] > 0

            row = jax.lax.dynamic_index_in_dim(logits, slot, keepdims=True)
            logits32 = jnp.where(
                (ops["guided"] == 0)[:, None] | bits("gmask"),
                row.astype(jnp.float32), jnp.float32(-1e30),
            )
            sampling = SamplingParams(
                f32("temperature"), ops["top_k"], f32("top_p")
            )
            bias, prompt = f32("bias"), bits("pmask")
            lp_src = logits32
            if keyed:
                extras = SamplingExtras(
                    presence=f32("presence"), frequency=f32("frequency"),
                    repetition=f32("repetition"), bias=bias,
                    seeds=ops["seed"], counters=jnp.zeros((1,), jnp.int32),
                    min_new=ops["min_new"], stop=ops["stop"],
                )
                counts0 = jnp.zeros((1, vocab), jnp.int32)
                first = sample_tokens(
                    logits32, sampling, key, extras, counts0, prompt
                )
                # reported logprobs reflect bias/penalties (OpenAI
                # semantics); XLA CSEs this against the sampler's own pass
                lp_src = penalize_logits(logits32, extras, counts0, prompt)
            else:
                first = sample_tokens(logits32, sampling, key)
            lp = _lp_of(lp_src, first, 1)
            if state is not None:
                counts, bias_rows, pmask_rows = state
                # the slot's histogram restarts at the prefill-sampled
                # token (it IS generated output for penalty purposes)
                state = (
                    counts.at[slot].set(0).at[slot, first[0]].set(1),
                    bias_rows.at[slot].set(bias[0]),
                    pmask_rows.at[slot].set(prompt[0]),
                )
            return first, lp, state

        self._first_token_jit = jax.jit(
            _first_token, static_argnums=(2, 5), donate_argnums=(4,)
        )
        self._first_layout = _first_token_layout(self._vocab)

        # -- n-gram speculative decoding (per-slot; dense or paged cache) --
        # Fully on-device draft-and-verify: each scan round proposes spec_k
        # draft tokens per slot by matching the last spec_ngram tokens
        # against the slot's own history (prompt-lookup speculation), then
        # ONE verify pass scores all spec_k+1 positions with a single weight
        # read. Accepted-prefix + bonus token means every round emits 1 to
        # spec_k+1 tokens — never fewer tokens/dispatch than the plain scan,
        # and far fewer HBM weight reads per token when drafts hit
        # (repetitive spans: summarization, extraction, code).
        #
        # Per-slot gating (VERDICT r3 #5): only greedy unconstrained slots
        # accept drafts (spec_mask). Plain temperature>0 slots speculate
        # too (sspec_mask) via REJECTION SAMPLING over the draft chain
        # (sampling.speculative_sample_chain — vLLM spec-sampling
        # semantics; distribution-exact, gated by engine.spec_sampling).
        # Slots with sampling extras, grammar constraints, or logprob
        # tracking ride the SAME verify dispatch but take exactly one token
        # per round, fully sampled from position 0's logits with the plain
        # chunk's semantics (penalties/bias/seeds, guided masks + DFA
        # advance, logprobs). On a weight-read-bound decode the k extra
        # verify positions are nearly free, so a mixed batch never forces
        # the engine off the speculative path.
        self._speculation = None
        if speculation:
            if speculation != "ngram":
                raise ValueError("speculation must be 'ngram' (got {!r})".format(speculation))
            need = "verify_paged" if cache_mode == "paged" else "verify"
            if getattr(bundle, need, None) is None:
                raise ValueError(
                    "model bundle has no {}() surface; speculation needs a "
                    "decoder with multi-position verification".format(need)
                )
            self._speculation = speculation
        self._spec_sampling = bool(spec_sampling)
        self._spec_k = max(1, int(spec_k))
        self._spec_ngram = max(1, int(spec_ngram))
        self._spec_slack = self.decode_steps * (self._spec_k + 1)
        # -- draft-tree verify rows (docs/spec_decode_trees.md) ------------
        # spec_tree routes the ragged verify rows through the pluggable
        # proposer's FOREST topology: same k+1 node budget per row, but the
        # nodes form a tree (ancestor-masked attention, longest-path
        # acceptance, in-launch KV path compaction). Chain engines keep the
        # legacy code path byte-for-byte: no tree arrays enter their jit.
        self._spec_tree = bool(spec_tree)
        self._spec_proposer = None
        if self._spec_tree:
            if not self._speculation:
                raise ValueError(
                    "spec_tree needs speculation='ngram' (the tree is a "
                    "topology over the n-gram proposer's drafts)"
                )
            if cache_mode != "paged":
                raise ValueError(
                    "spec_tree needs cache_mode='paged': the dense chunk "
                    "layers apply plain causal masks and cannot express a "
                    "draft tree's ancestor visibility "
                    "(docs/spec_decode_trees.md)"
                )
        if self._speculation:
            from .spec_proposer import make_proposer

            self._spec_proposer = (
                make_proposer(
                    "ngram-forest",
                    ngram=self._spec_ngram,
                    branch=max(1, int(spec_branch)),
                )
                if self._spec_tree
                else make_proposer("ngram-chain", ngram=self._spec_ngram)
            )
        # accepted PATH DEPTH per tree verify row (0..k), the tree
        # headline engine_spec_tree_accept_depth reads — integer-valued,
        # bucketed at every possible depth for the default k=4
        self._hist_spec_tree_depth = _MsHistogram(
            buckets=(0, 1, 2, 3, 4, 8, 16)
        )
        if self._speculation:
            k_, n_ = self._spec_k, self._spec_ngram
            buf_len = self.max_seq_len + self._spec_slack + 1
            self._tokbuf = np.zeros((self.max_batch, buf_len), np.int32)

            def _spec_chunk(params, tokbuf, pending, cache, active,
                            spec_mask, sspec_mask, sampling, rng,
                            lora_idx=None,
                            extras=None, counts=None, pmask=None,
                            guided=None, gstate=None, want_lp=False,
                            with_sspec=False):
                t_idx = jnp.arange(buf_len, dtype=jnp.int32)
                nb = pending.shape[0]
                # position-0 plain-path slots (extras/guided/logprobs)
                ns_mask = active & ~spec_mask
                if with_sspec:
                    ns_mask = ns_mask & ~sspec_mask
                if gstate is None:
                    gstate = jnp.full((nb,), -1, jnp.int32)

                def round_body(carry, xs):
                    step_rng, step_off = xs
                    tokbuf, pending, cache, counts, gstate = carry
                    length = cache["length"]                # [B]
                    hist = length + 1  # known tokens incl. pending
                    # ---- n-gram proposal from each slot's own history ----
                    tail_pos = (hist[:, None] - n_ + jnp.arange(n_)[None]).clip(0)
                    tail = jnp.take_along_axis(tokbuf, tail_pos, axis=1)  # [B,n]
                    n_win = buf_len - n_ + 1
                    match = jnp.ones((tokbuf.shape[0], n_win), bool)
                    for j in range(n_):  # n_ is static and tiny
                        match = match & (
                            tokbuf[:, j : n_win + j] == tail[:, j : j + 1]
                        )
                    win_idx = jnp.arange(n_win, dtype=jnp.int32)[None]
                    # window must end before the tail starts (a previous
                    # occurrence, not the tail matching itself)
                    valid = match & (win_idx < (hist - n_)[:, None] - n_ + 1)
                    has = jnp.any(valid, axis=1)
                    i_best = jnp.argmax(
                        jnp.where(valid, win_idx, -1), axis=1
                    ).astype(jnp.int32)                         # [B]
                    draft_pos = (
                        i_best[:, None] + n_ + jnp.arange(k_, dtype=jnp.int32)[None]
                    ).clip(0, buf_len - 1)
                    drafts = jnp.take_along_axis(tokbuf, draft_pos, axis=1)
                    # no-match slots: draft the tail's last token repeated —
                    # cheap, and a reject still emits the bonus token
                    drafts = jnp.where(has[:, None], drafts, tail[:, -1:])
                    # ---- one verify pass over pending + drafts ----------
                    tokens_in = jnp.concatenate([pending[:, None], drafts], axis=1)
                    if lora_idx is None:
                        logits, cache = bundle.verify(params, tokens_in, cache)
                    else:
                        logits, cache = bundle.verify(
                            params, tokens_in, cache, lora_idx
                        )
                    logits = logits.astype(jnp.float32)
                    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B,k+1]
                    acc = jnp.sum(
                        jnp.cumprod((drafts == g[:, :k_]).astype(jnp.int32), axis=1),
                        axis=1,
                    )                                            # [B] 0..k
                    if with_sspec:
                        # rejection-sampled draft chain for plain
                        # temperature>0 slots (distribution-exact)
                        step_rng, chain_rng = jax.random.split(step_rng)
                    # ---- plain-path slots: one token from position 0,
                    # plain-chunk semantics (mask -> penalize -> sample ->
                    # count -> DFA advance) -------------------------------
                    l0 = logits[:, 0, :]
                    if guided is not None:
                        l0 = _guided_mask(l0, gstate, guided)
                    if extras is None:
                        sampled = sample_tokens(
                            l0, sampling, step_rng, live=ns_mask
                        )
                        lp_src = l0
                    else:
                        ex = extras._replace(counters=extras.counters + step_off)
                        sampled = sample_tokens(
                            l0, sampling, step_rng, ex, counts, pmask,
                            live=ns_mask,
                        )
                        lp_src = (
                            penalize_logits(l0, ex, counts, pmask)
                            if want_lp
                            else l0
                        )
                        counts = counts.at[jnp.arange(nb), sampled].add(
                            ns_mask.astype(jnp.int32)
                        )
                    if guided is not None:
                        gstate = _guided_advance(gstate, sampled, ns_mask, guided)
                    acc = jnp.where(spec_mask, acc, 0)
                    if with_sspec:
                        g_s, acc_s = speculative_sample_chain(
                            logits, drafts, sampling, chain_rng
                        )
                        acc = jnp.where(sspec_mask, acc_s, acc)
                        g = jnp.where(sspec_mask[:, None], g_s, g)
                        keep = spec_mask | sspec_mask
                    else:
                        keep = spec_mask
                    g = g.at[:, 0].set(jnp.where(keep, g[:, 0], sampled))
                    new_pending = jnp.take_along_axis(g, acc[:, None], axis=1)[:, 0]
                    new_len = jnp.where(active, length + 1 + acc, length)
                    # append the emitted tokens to the history buffer
                    for i in range(k_ + 1):
                        w = (t_idx[None] == (hist + i)[:, None]) & (
                            (i <= acc) & active
                        )[:, None]
                        tokbuf = jnp.where(w, g[:, i : i + 1], tokbuf)
                    pending = jnp.where(active, new_pending, pending)
                    out = (
                        (g, acc, _lp_of(lp_src, sampled, nb))
                        if want_lp
                        else (g, acc)
                    )
                    cache = {**cache, "length": new_len.astype(jnp.int32)}
                    return (tokbuf, pending, cache, counts, gstate), out

                rngs = jax.random.split(rng, decode_steps)
                steps = jnp.arange(decode_steps, dtype=jnp.int32)
                carry, out = jax.lax.scan(
                    round_body, (tokbuf, pending, cache, counts, gstate),
                    (rngs, steps),
                )
                if want_lp:
                    gs, accs, lp = out  # lp round-major [R, B, ...]
                else:
                    (gs, accs), lp = out, None
                tokbuf, pending, cache, counts, gstate = carry
                # gs [rounds, B, k+1], accs [rounds, B]
                return (tokbuf, pending, cache, gs, accs, counts, gstate, lp)

            # the serial scan is the dense engine's; on pages drafts ride
            # the ragged step as verify rows
            self._spec_chunk_jit = (
                jax.jit(
                    _spec_chunk, donate_argnums=(3,),
                    static_argnames=("want_lp", "with_sspec"),
                )
                if cache_mode == "dense"
                else None
            )
        else:
            self._tokbuf = None
            self._spec_chunk_jit = None

        # captured as a local for the jitted closures below (TPU201: a jit
        # closing over self would trace against stale state)
        paged_quant = self._paged_quant

        def _decode_paged_chunk(
            params, tokens, k_pools, v_pools, k_scales, v_scales,
            page_table, lengths0,
            write_pages, write_offsets, sampling, rng, lora_idx=None,
            extras=None, counts=None, pmask=None, guided=None, gstate=None,
            want_lp=False,
        ):
            """Paged-cache variant of the fused decode chunk. Page/offset
            write coordinates for every step come pre-computed from the host
            page allocator (write_pages/offsets: [B, steps]).
            ``k_scales``/``v_scales`` are the int8 pools' dequant scale
            pools (None on bf16 pools), chained through the scan like the
            data pools."""
            nb = tokens.shape[0]
            active = jnp.asarray(
                lengths0 > 0
            )  # paged slots with content; inactive rows count nothing

            def body(carry, xs):
                (tokens, k_pools, v_pools, k_scales, v_scales, counts,
                 step, gstate) = carry
                step_rng, wp, wo = xs
                # an empty slot attends nothing
                scale_kw = {"active": active}
                if paged_quant:
                    scale_kw.update(k_scales=k_scales, v_scales=v_scales)
                if lora_idx is None:
                    out = bundle.decode_paged(
                        params, tokens, k_pools, v_pools, page_table,
                        lengths0 + step, wp, wo, **scale_kw,
                    )
                else:
                    out = bundle.decode_paged(
                        params, tokens, k_pools, v_pools, page_table,
                        lengths0 + step, wp, wo, lora_idx, **scale_kw,
                    )
                if paged_quant:
                    logits, k_pools, v_pools, k_scales, v_scales = out
                else:
                    logits, k_pools, v_pools = out
                logits = logits.astype(jnp.float32)
                if guided is not None:
                    logits = _guided_mask(logits, gstate, guided)
                if extras is None:
                    sampled = sample_tokens(
                        logits, sampling, step_rng, live=active
                    )
                    lp_src = logits
                else:
                    ex = extras._replace(counters=extras.counters + step)
                    sampled = sample_tokens(
                        logits, sampling, step_rng, ex, counts, pmask,
                        live=active,
                    )
                    lp_src = (
                        penalize_logits(logits, ex, counts, pmask)
                        if want_lp
                        else logits
                    )
                    counts = counts.at[jnp.arange(nb), sampled].add(
                        active.astype(jnp.int32)
                    )
                if guided is not None:
                    gstate = _guided_advance(gstate, sampled, active, guided)
                out = (sampled, _lp_of(lp_src, sampled, nb)) if want_lp else sampled
                return (
                    (sampled, k_pools, v_pools, k_scales, v_scales, counts,
                     step + 1, gstate),
                    out,
                )

            rngs = jax.random.split(rng, decode_steps)
            if gstate is None:
                gstate = jnp.full((nb,), -1, jnp.int32)
            (
                (_, k_pools, v_pools, k_scales, v_scales, counts, _, gstate),
                out,
            ) = jax.lax.scan(
                body,
                (tokens, k_pools, v_pools, k_scales, v_scales, counts,
                 jnp.int32(0), gstate),
                (rngs, write_pages.T, write_offsets.T),
            )
            if want_lp:
                toks, (chosen, top_id, top_lp) = out
                lp = (chosen.T, jnp.swapaxes(top_id, 0, 1), jnp.swapaxes(top_lp, 0, 1))
                return (toks.T, k_pools, v_pools, k_scales, v_scales, counts,
                        lp, gstate)
            return (out.T, k_pools, v_pools, k_scales, v_scales, counts,
                    None, gstate)

        self._decode_paged_chunk_jit = jax.jit(
            _decode_paged_chunk,
            # donate the data pools (2, 3) and, on int8 pools, the scale
            # pools (4, 5) — donating a None arg is rejected by jax, so the
            # tuple is built per backend
            donate_argnums=(2, 3, 4, 5) if self._paged_quant else (2, 3),
            static_argnames=("want_lp",),
        )
        # -- ragged mixed prefill+decode step (docs/ragged_attention.md) ---
        # ONE launch per loop iteration: every decode row advances one token
        # while prefill rows process budget-bounded prompt chunks, all
        # through bundle.forward_ragged / forward_ragged_state. Decode-row
        # sampling mirrors the plain chunk body exactly (guided mask ->
        # penalized sample -> count -> DFA advance), which is what keeps
        # paged streams byte-identical to the dense engine's; finishing
        # prefill rows return their raw last-token logits for the loop's
        # host-side first-token sampling (the same code the dense
        # admission path runs).
        if self._ragged:

            def _sample_rows(logits, mask, sampling, rng, extras, counts,
                             pmask, guided, gstate, want_lp):
                nb = logits.shape[0]
                if gstate is None:
                    gstate = jnp.full((nb,), -1, jnp.int32)
                masked = logits
                if guided is not None:
                    masked = _guided_mask(masked, gstate, guided)
                if extras is None:
                    sampled = sample_tokens(masked, sampling, rng, live=mask)
                    lp_src = masked
                else:
                    sampled = sample_tokens(
                        masked, sampling, rng, extras, counts, pmask,
                        live=mask,
                    )
                    lp_src = (
                        penalize_logits(masked, extras, counts, pmask)
                        if want_lp
                        else masked
                    )
                    counts = counts.at[jnp.arange(nb), sampled].add(
                        mask.astype(jnp.int32)
                    )
                if guided is not None:
                    gstate = _guided_advance(gstate, sampled, mask, guided)
                lp = _lp_of(lp_src, sampled, nb) if want_lp else None
                return sampled, counts, lp, gstate

            def _spec_accept(spec, spec_logits, sampling, tree=None):
                """In-launch draft acceptance over the spec-verify rows'
                per-position logits [B, K+1, V]: greedy rows take the
                argmax-match chain, sampled (sspec) rows the
                rejection-sampled chain from llm/sampling.py — the same
                acceptance math the legacy serial scan ran, applied once
                per launch instead of decode_steps times. With ``tree``
                (tree_tokens [B, K+1], tree_parents [B, K+1], tree_n [B])
                the rows are draft TREES and acceptance is the longest
                root-to-leaf walk (docs/spec_decode_trees.md) — the chain
                is its degenerate single-branch case, byte-identical
                (tests/test_spec_tree.py). Returns
                (g [B, K+1], acc [B], spec_any [B], nodes) where nodes
                [B, K+1] is the position->node KV compaction map (None on
                the chain path: accepted positions are already
                contiguous)."""
                spec_sel, sspec_sel, drafts, _idx, spec_rng = spec
                spec_any = spec_sel | sspec_sel
                sl = spec_logits.astype(jnp.float32)
                k_ = drafts.shape[1]
                if tree is not None:
                    t_tok, t_par, t_n = tree
                    g_arg = jnp.argmax(sl, axis=-1).astype(jnp.int32)
                    g_g, acc_g, nodes_g = greedy_tree_walk(
                        g_arg, t_tok, t_par, t_n
                    )
                    g_s, acc_s, nodes_s = speculative_sample_tree(
                        sl, t_tok, t_par, t_n, sampling, spec_rng
                    )
                    g = jnp.where(sspec_sel[:, None], g_s, g_g)
                    acc = jnp.where(
                        sspec_sel, acc_s,
                        jnp.where(spec_sel, acc_g, jnp.zeros_like(acc_g)),
                    ).astype(jnp.int32)
                    ident = jnp.broadcast_to(
                        jnp.arange(t_tok.shape[1], dtype=jnp.int32),
                        t_tok.shape,
                    )
                    nodes = jnp.where(sspec_sel[:, None], nodes_s, nodes_g)
                    nodes = jnp.where(spec_any[:, None], nodes, ident)
                    return g, acc, spec_any, nodes
                g = jnp.argmax(sl, axis=-1).astype(jnp.int32)  # [B, K+1]
                acc_g = jnp.sum(
                    jnp.cumprod(
                        (drafts == g[:, :k_]).astype(jnp.int32), axis=1
                    ),
                    axis=1,
                )
                g_s, acc_s = speculative_sample_chain(
                    sl, drafts, sampling, spec_rng
                )
                g = jnp.where(sspec_sel[:, None], g_s, g)
                acc = jnp.where(
                    sspec_sel, acc_s,
                    jnp.where(spec_sel, acc_g, jnp.zeros_like(acc_g)),
                ).astype(jnp.int32)
                return g, acc, spec_any, None

            def _chain_sample(l, m, step, s_rng, sampling, extras, counts,
                              pmask, guided, gstate, want_lp, nb):
                """One chained decode step's sampling tail — the plain
                chunk body's exact semantics (guided mask -> penalized
                sample -> count -> DFA advance) with the per-step seed
                counter offset, masked to the rows whose window is still
                open this step."""
                l = l.astype(jnp.float32)
                if guided is not None:
                    l = _guided_mask(l, gstate, guided)
                if extras is None:
                    s_tok = sample_tokens(l, sampling, s_rng, live=m)
                    lp_src = l
                else:
                    ex = extras._replace(
                        counters=extras.counters + step + 1
                    )
                    s_tok = sample_tokens(
                        l, sampling, s_rng, ex, counts, pmask, live=m
                    )
                    lp_src = (
                        penalize_logits(l, ex, counts, pmask)
                        if want_lp
                        else l
                    )
                    counts = counts.at[jnp.arange(nb), s_tok].add(
                        m.astype(jnp.int32)
                    )
                if guided is not None:
                    gstate = _guided_advance(gstate, s_tok, m, guided)
                lp = _lp_of(lp_src, s_tok, nb) if want_lp else None
                return s_tok, counts, gstate, lp

            def _stack_chain(sampled, lp, chain_out, want_lp):
                """[B] step-0 outputs + [S-1, B] chained outputs -> step-major
                [S, B] (and the lp triple likewise)."""
                if want_lp:
                    chain_toks, chain_lp = chain_out
                    sampled = jnp.concatenate([sampled[None], chain_toks])
                    lp = tuple(
                        jnp.concatenate([a[None], b])
                        for a, b in zip(lp, chain_lp)
                    )
                else:
                    sampled = jnp.concatenate([sampled[None], chain_out])
                return sampled, lp

            if cache_mode == "paged":

                def _ragged_paged_step(params, tokens, tok_pos, tok_row,
                                       tok_valid, tok_slot, row_last,
                                       k_pools, v_pools,
                                       k_scales, v_scales, page_table,
                                       kv_lens, row_starts, row_lens,
                                       write_page, write_offset, item_rows,
                                       item_q0, decode_mask, sampling, rng,
                                       lora_idx=None, extras=None,
                                       counts=None, pmask=None, guided=None,
                                       gstate=None, want_lp=False,
                                       spec=None, chain=None, tree=None):
                    scale_kw = (
                        {"k_scales": k_scales, "v_scales": v_scales}
                        if paged_quant
                        else {}
                    )
                    logit_kw = (
                        {"row_logit_idx": spec[3]} if spec is not None else {}
                    )
                    if tree is not None:
                        # draft-tree verify rows: per-token ancestor lists
                        # route the attention mask down to the kernel
                        # (docs/spec_decode_trees.md)
                        logit_kw["tree_anc"] = tree[3]
                    out = bundle.forward_ragged(
                        params, tokens, tok_pos, tok_row, tok_valid,
                        tok_slot, row_last, k_pools, v_pools, page_table,
                        kv_lens,
                        row_starts, row_lens, write_page, write_offset,
                        item_rows, item_q0, lora_idx, **scale_kw,
                        **logit_kw,
                    )
                    if paged_quant:
                        logits, k_pools, v_pools, k_scales, v_scales = out
                    else:
                        logits, k_pools, v_pools = out
                    spec_g = spec_acc = None
                    plain_mask = decode_mask
                    if spec is not None:
                        logits, spec_logits = logits
                        spec_g, spec_acc, spec_any, spec_nodes = _spec_accept(
                            spec, spec_logits, sampling,
                            tree=None if tree is None else tree[:3],
                        )
                        plain_mask = decode_mask & ~spec_any
                        if tree is not None:
                            # KV PATH COMPACTION: a tree row's accepted
                            # root-to-leaf nodes sit at non-contiguous row
                            # positions in the pools — gather each accepted
                            # node's just-written K/V and rewrite it at its
                            # path depth, so the retire-stage truncate to
                            # pre+1+acc keeps a contiguous prefix exactly
                            # like a chain row's. Non-moves (and non-tree
                            # rows) scatter to the null page (page 0), the
                            # same discard target every pad write uses.
                            # The write coordinates are per token, on the
                            # compact axis: a verify row's first token is
                            # its first logit index.
                            nn = spec_nodes.shape[1]
                            pos = jnp.arange(1, nn, dtype=jnp.int32)
                            row_first = spec[3][:, :1]
                            src = (row_first + spec_nodes[:, 1:]).reshape(-1)
                            dst = (row_first + pos[None, :]).reshape(-1)
                            move = (
                                spec_any[:, None]
                                & (spec_nodes[:, 1:] != pos[None, :])
                            ).reshape(-1)
                            sp, so = write_page[src], write_offset[src]
                            dp = jnp.where(move, write_page[dst], 0)
                            do = jnp.where(move, write_offset[dst], 0)

                            def compact(pool):
                                # layers and heads as indices, not slices:
                                # the scatter's window stays one row, so
                                # the stack keeps the kernels' layout (a
                                # [L, Hkv, D] window makes the v5e compiler
                                # convert the whole stack there and back;
                                # ops.paged_attention.paged_kv_write_xla)
                                ls = jnp.arange(pool.shape[0])[:, None, None]
                                hs = jnp.arange(pool.shape[1])[None, :, None]
                                return pool.at[ls, hs, dp, do].set(
                                    pool[ls, hs, sp, so]
                                )

                            k_pools, v_pools = compact(k_pools), compact(v_pools)
                            if paged_quant:
                                k_scales = compact(k_scales)
                                v_scales = compact(v_scales)
                    raw = logits.astype(jnp.float32)
                    sampled, counts, lp, gstate = _sample_rows(
                        raw, plain_mask, sampling, rng, extras, counts,
                        pmask, guided, gstate, want_lp,
                    )
                    if chain is not None:
                        # multi-step decode rows: chain the sampled token
                        # through S-1 further fused decode steps — the
                        # pipelined chunk's scan, riding the SAME launch as
                        # the mixed ragged pass (docs/ragged_attention.md)
                        step_rngs, chain_mask, chain_wp, chain_wo = chain
                        nb = sampled.shape[0]

                        def body(carry, xs):
                            (tok_c, k_p, v_p, k_s, v_s, counts_c,
                             gstate_c, step) = carry
                            s_rng, m, wp, wo = xs
                            # a row whose window has closed, a prefill row
                            # and an empty slot attend nothing in this pass
                            skw = {"active": m}
                            if paged_quant:
                                skw.update(k_scales=k_s, v_scales=v_s)
                            if lora_idx is None:
                                o = bundle.decode_paged(
                                    params, tok_c, k_p, v_p, page_table,
                                    kv_lens + step, wp, wo, **skw,
                                )
                            else:
                                o = bundle.decode_paged(
                                    params, tok_c, k_p, v_p, page_table,
                                    kv_lens + step, wp, wo, lora_idx, **skw,
                                )
                            if paged_quant:
                                l, k_p, v_p, k_s, v_s = o
                            else:
                                l, k_p, v_p = o
                            s_tok, counts_c, gstate_c, lp_s = _chain_sample(
                                l, m, step, s_rng, sampling, extras,
                                counts_c, pmask, guided, gstate_c, want_lp,
                                nb,
                            )
                            tok_next = jnp.where(m, s_tok, tok_c)
                            out_s = (
                                (tok_next, lp_s) if want_lp else tok_next
                            )
                            return (
                                (tok_next, k_p, v_p, k_s, v_s, counts_c,
                                 gstate_c, step + 1),
                                out_s,
                            )

                        (
                            (_, k_pools, v_pools, k_scales, v_scales,
                             counts, gstate, _),
                            chain_out,
                        ) = jax.lax.scan(
                            body,
                            (sampled, k_pools, v_pools, k_scales, v_scales,
                             counts, gstate, jnp.int32(0)),
                            (step_rngs, chain_mask, chain_wp, chain_wo),
                        )
                        sampled, lp = _stack_chain(
                            sampled, lp, chain_out, want_lp
                        )
                    return (sampled, raw, k_pools, v_pools, k_scales,
                            v_scales, counts, lp, gstate, spec_g, spec_acc)

                self._ragged_paged_jit = jax.jit(
                    _ragged_paged_step,
                    donate_argnums=(
                        (7, 8, 9, 10) if self._paged_quant else (7, 8)
                    ),
                    static_argnames=("want_lp",),
                )
                self._ragged_state_jit = None
            else:

                def _ragged_state_step(params, tokens, tok_pos, tok_row,
                                       tok_valid, row_last, s_pool, z_pool,
                                       positions, row_starts, row_lens,
                                       row_reset, decode_mask, sampling, rng,
                                       extras=None, counts=None, pmask=None,
                                       guided=None, gstate=None,
                                       want_lp=False, chain=None):
                    """The ragged step over the state cache: one mixed pass
                    (decode rows one token, prefill rows a chunk) and the
                    decode rows' chained window, the pools in the carry of
                    both. ``positions`` [B]: tokens of each row's sequence
                    AFTER the mixed pass = the position of the window's
                    next token. A chained step advances only the rows whose
                    window is still open (``chain_mask``): a closed window's
                    pad positions and the rows in prefill leave their slots
                    untouched (docs/ragged_attention.md)."""
                    logits, s_pool, z_pool = bundle.forward_ragged_state(
                        params, tokens, tok_pos, tok_row, tok_valid,
                        row_last, s_pool, z_pool, row_starts, row_lens,
                        row_reset,
                    )
                    raw = logits.astype(jnp.float32)
                    sampled, counts, lp, gstate = _sample_rows(
                        raw, decode_mask, sampling, rng, extras, counts,
                        pmask, guided, gstate, want_lp,
                    )
                    if chain is not None:
                        step_rngs, chain_mask = chain
                        nb = sampled.shape[0]

                        def body(carry, xs):
                            tok_c, s_p, z_p, counts_c, gstate_c, step = carry
                            s_rng, m = xs
                            l, s_p, z_p = bundle.decode_state(
                                params, tok_c, s_p, z_p, positions + step, m,
                            )
                            s_tok, counts_c, gstate_c, lp_s = _chain_sample(
                                l, m, step, s_rng, sampling, extras,
                                counts_c, pmask, guided, gstate_c, want_lp,
                                nb,
                            )
                            tok_next = jnp.where(m, s_tok, tok_c)
                            out_s = (
                                (tok_next, lp_s) if want_lp else tok_next
                            )
                            return (
                                (tok_next, s_p, z_p, counts_c, gstate_c,
                                 step + 1),
                                out_s,
                            )

                        (
                            (_, s_pool, z_pool, counts, gstate, _),
                            chain_out,
                        ) = jax.lax.scan(
                            body,
                            (sampled, s_pool, z_pool, counts, gstate,
                             jnp.int32(0)),
                            (step_rngs, chain_mask),
                        )
                        sampled, lp = _stack_chain(
                            sampled, lp, chain_out, want_lp
                        )
                    return (sampled, raw, s_pool, z_pool, counts, lp, gstate)

                self._ragged_state_jit = jax.jit(
                    _ragged_state_step,
                    donate_argnums=(6, 7),
                    static_argnames=("want_lp",),
                )
                self._ragged_paged_jit = None
            # Two static token axes per launch, ONE trace per
            # (extras/guided/lp variant). Everything that is per token (the
            # dense layers, the pool writes, the logit gathers) runs on the
            # COMPACT axis: ``_ragged_dense`` rows, the budget rounded up
            # to whole alignment blocks, the launch's tokens packed row
            # after row (the budget counts every reserved position, so they
            # always fit). A token-mixing kernel reads a row's tokens by
            # the row's start: when the Pallas kernel serves the launch
            # each row's segment aligns to the 8 tokens its q / out copies
            # move, in an aligned VIEW of ``_ragged_tpad`` rows (worst-case
            # waste = one copy less a token per row) that holds q and the
            # kernel's output and nothing else; the XLA reference needs no
            # alignment and the view is the compact axis. The alignment is
            # the KERNEL'S constant and the view's size the kernel module's
            # arithmetic (ragged_view_tokens, which forward_ragged evaluates
            # over the same shapes): one contract, not two constants that
            # happen to agree.
            from ..ops.paged_attention import _RAGGED_QB, ragged_view_tokens

            self._ragged_kernel = self._paged_kernel_reason is None
            qb = _RAGGED_QB if self._ragged_kernel else 1
            if cache_mode == "state":
                # the chunk kernel slices a row's tokens at its start:
                # whole 8-row float32 tiles, with the kernel or its twin
                from ..ops.power_retention import ROW_ALIGN

                qb = ROW_ALIGN
            self._ragged_qb = qb
            self._ragged_dense = pad_to_multiple(self._step_token_budget, qb)
            self._ragged_tpad = ragged_view_tokens(
                self._ragged_dense, self.max_batch, qb
            )
            if cache_mode == "state":
                # forward_ragged_state runs on ONE axis, the view
                self._ragged_dense = self._ragged_tpad
            if self._ragged_kernel and cache_mode == "paged":
                # the kernel's work plan: a row, or a query tile of it, per
                # item; the tile follows from the shapes the kernel sees,
                # and the list has one static length that holds any launch
                from ..ops.paged_attention import (
                    ragged_item_count, ragged_query_tile,
                )

                self._ragged_tile = ragged_query_tile(
                    bundle.n_kv_heads, bundle.n_heads // bundle.n_kv_heads,
                    bundle.head_dim, bundle.config.get("dtype", "bfloat16"),
                )
                self._ragged_items = ragged_item_count(
                    self.max_batch, self._ragged_tpad, self._ragged_tile
                )
                self._check_kernel_smem(
                    self._ragged_tpad,
                    self._spec_k + 1 if self._spec_tree else 0,
                    self._ragged_items,
                )

            # a launch's host vectors cross in ONE staged buffer; its
            # layout per step variant is static (docs/ragged_attention.md)
            self._ragged_unpack_jit = jax.jit(
                _unpack_ragged_operands, static_argnums=(1,)
            )
            # the rows' pending tokens, kept on the device from one launch
            # to the one behind it: a launch's last sampled row, and a
            # finishing prompt's first token put at its slot (``at`` past
            # the end: none)
            self._ragged_chain_jit = jax.jit(
                lambda sampled, first_id, at: (
                    sampled if sampled.ndim == 1 else sampled[-1]
                ).at[at].set(first_id[0], mode="drop")
            )
            self._chain_null = jnp.zeros(self.max_batch, jnp.int32)
            self._first_null = jnp.zeros(1, jnp.int32)
            windows = [1]
            while windows[-1] < self._ragged_steps_cap:
                windows.append(2 * windows[-1])
            self._ragged_layouts = {
                (steps, extras, spec): self._ragged_operand_layout(
                    steps, extras, spec
                )
                for steps in windows
                for extras in (False, True)
                for spec in (False, True)
                if not spec or (self._speculation and cache_mode == "paged")
            }

        self._kernels = self._kernel_routes()

        # runtime KV/refcount sanitizer (llm/kv_sanitizer.py): armed via
        # TPUSERVE_SANITIZE=1 (tests arm it for the chaos + paged suites).
        # After every decode step and at drain it audits refcount
        # conservation across slot tables, the radix cache, admission pins,
        # and pending CoW — a violated invariant raises instead of limping.
        self._sanitizer = None
        if self.paged_cache is not None and kv_sanitizer.enabled():
            self._sanitizer = kv_sanitizer.KVSanitizer(
                self.paged_cache.pool, self._prefix,
                paged_cache=self.paged_cache,
            )

        # runtime compile sentry (llm/compile_sentry.py): armed via
        # TPUSERVE_COMPILE_SENTRY=1|strict. Hooks JAX's compile path,
        # splits compilations at the warmup fence (llm/warmup.py), and in
        # strict mode a post-fence compile raises CompileSentryError at
        # the next loop boundary — the dynamic half of the TPU6xx
        # compile-surface discipline (docs/static_analysis.md).
        self._compile_sentry = (
            compile_sentry.get() if compile_sentry.enabled() else None
        )

        # runtime ownership ledger (llm/lifecycle_ledger.py): armed via
        # TPUSERVE_LEDGER=1|strict. Records every declared acquire/release
        # with owner + site, audits pairing per request at emit/fail/cancel
        # and globally at drain — the dynamic half of the TPU7xx ownership
        # discipline (docs/static_analysis.md), covering the static pass's
        # declared blind spots (cross-function, cross-thread transfers).
        self._ledger = (
            lifecycle_ledger.arm() if lifecycle_ledger.enabled() else None
        )

        # runtime sharding sentry (llm/sharding_sentry.py): armed via
        # TPUSERVE_SHARD_SENTRY=1|strict. At every loop boundary it audits
        # the live KV pools and chained device state (plus the params tree
        # at init/drain) against the specs the __shardings__ builders gave
        # them at init, counting implicit device<->host transfers and
        # unplanned reshards per launch; strict mode raises
        # ShardSentryError through the structured step-failure path — the
        # dynamic half of the TPU8xx sharding discipline
        # (docs/static_analysis.md).
        self._shard_sentry = (
            sharding_sentry.arm() if sharding_sentry.enabled() else None
        )
        # co-hosted replica engines share the process-wide sentry: a
        # per-engine path prefix keeps their spec tables disjoint
        self._shard_prefix = "engine[{}]".format(next(_ENGINE_IDS))
        if self._shard_sentry is not None:
            self._shard_sentry.audit(
                self._shard_audit_entries(params=True), where="init"
            )

    def _shard_audit_entries(self, params: bool = False) -> list:
        """(path, value, declared) entries for the sharding sentry's
        boundary audit: chained device state and the KV pools every
        boundary; the params tree only at init and drain boundaries (it
        never rebinds mid-serve, and walking it per step is wasted work).
        """
        p = self._shard_prefix
        entries = [
            (p + "._next_token_dev", self._next_token_dev, None),
            (p + "._gstate_dev", self._gstate_dev, None),
        ]
        if self.paged_cache is not None:
            entries += [
                (p + ".paged_cache.k", self.paged_cache.k, None),
                (p + ".paged_cache.v", self.paged_cache.v, None),
                (p + ".paged_cache.k_scale", self.paged_cache.k_scale, None),
                (p + ".paged_cache.v_scale", self.paged_cache.v_scale, None),
            ]
        elif self.cache is not None:
            entries += [
                (p + ".cache.{}".format(k), v, None)
                for k, v in self.cache.items()
            ]
        if params:
            import jax as _jax

            for path, leaf in _jax.tree_util.tree_leaves_with_path(
                self.params
            ):
                entries.append(
                    (p + ".params" + _jax.tree_util.keystr(path), leaf, None)
                )
        if faults.active():
            # seeded-defect seam (llm/faults.py engine.shard.drift): swap a
            # host-materialized copy in for the chained decode row, exactly
            # the silent device->host round-trip the sentry exists to catch
            # — the self-test proves strict mode raises on it
            try:
                faults.fire("engine.shard.drift")
            except faults.InjectedFault:
                drifted = (
                    np.asarray(self._next_token_dev)
                    if self._next_token_dev is not None
                    else np.zeros(self.max_batch, np.int32)
                )
                entries.append((p + "._next_token_dev", drifted, None))
        return entries

    def _ledger_domains(self) -> list:
        """The primitives whose drain-zero entries THIS engine audits
        (co-hosted replica engines share one process-wide ledger)."""
        domains = [self]
        if self.paged_cache is not None:
            domains += [self.paged_cache, self.paged_cache.pool]
            if self.paged_cache.host_tier is not None:
                domains.append(self.paged_cache.host_tier)
        if self._prefix is not None:
            domains.append(self._prefix)
        return domains

    def _ledger_audit_request(self, request: "GenRequest",
                              where: str) -> None:
        """Per-request pairing audit at a request exit boundary (emit
        finish / fail / cancel): every request-scoped acquire attributed
        to this request must have been released. Strict mode raises on
        the loop thread — the structured step-failure path handles it,
        exactly like a sanitizer violation."""
        if self._ledger is not None and request is not None:
            self._ledger.audit_request(
                lifecycle_ledger.request_tag(request), where=where
            )

    def _sanitize(self, where: str, drained: bool = False) -> None:
        if self._sanitizer is not None:
            self._sanitizer.check(
                where, drained=drained,
                inflight=len(self._inflight) + len(self._ragged_flights),
            )
        if self._compile_sentry is not None:
            # strict-mode violations surface here, on the loop thread,
            # through the structured step-failure path (like the sanitizer)
            self._compile_sentry.check(where=where)
        if self._ledger is not None:
            self._ledger.check(
                where=where,
                drained=(
                    drained and not self._inflight
                    and not self._ragged_flights
                ),
                domains=self._ledger_domains(),
            )
        if self._shard_sentry is not None:
            self._shard_sentry.audit(
                self._shard_audit_entries(params=drained), where=where
            )
            # strict-mode sharding violations surface here too, on the
            # loop thread, naming array path + declared vs actual spec
            self._shard_sentry.check(where=where)

    @contextlib.contextmanager
    def _sentry_scope(self, phase: str, **ctx):
        """Thread-local launch attribution for a dispatch/prefill worker
        (no-op unless a sentry is armed): the compile sentry tags the
        compiles and the sharding sentry tags the transfer/reshard
        violations this thread's launches surface."""
        with contextlib.ExitStack() as stack:
            if self._compile_sentry is not None:
                stack.enter_context(self._compile_sentry.context(
                    phase=phase, depth=self.pipeline_depth, **ctx
                ))
            if self._shard_sentry is not None:
                stack.enter_context(self._shard_sentry.context(
                    phase=phase, depth=self.pipeline_depth, **ctx
                ))
            yield

    async def warmup(self, full: bool = True) -> dict:
        """Compile the serve loop's XLA key space ahead of traffic: drive
        the shared warmup shape registry (llm/warmup.py) against this
        engine and set the compile sentry's warmup fence when armed.
        Endpoint startup, a replica's re-admission and the coverage tests
        all run THIS sweep — one coverage-checked list."""
        from . import warmup as _warmup

        return await _warmup.run_warmup(self, full=full)

    # -- public API ----------------------------------------------------------

    def validate(self, request: GenRequest) -> None:
        """Raises ValueError for inadmissible requests. Callers that stream
        MUST call this before sending response headers."""
        if len(request.prompt_ids) >= self.max_seq_len:
            raise ValueError(
                "prompt length {} exceeds engine max_seq_len {}".format(
                    len(request.prompt_ids), self.max_seq_len
                )
            )
        if request.priority not in PRIORITY_CLASSES:
            raise ValueError(
                "priority must be one of {} (got {!r})".format(
                    "/".join(PRIORITY_CLASSES), request.priority
                )
            )
        if request.adapter and request.adapter not in self._adapter_index:
            raise ValueError(
                "unknown lora adapter {!r} (loaded: {})".format(
                    request.adapter, sorted(self._adapter_index) or "none"
                )
            )
        if request.logit_bias:
            for tok in request.logit_bias:
                try:
                    tok_i = int(tok)
                except (TypeError, ValueError):
                    raise ValueError(
                        "logit_bias keys must be token ids (got {!r})".format(tok)
                    )
                if not (0 <= tok_i < self._vocab):
                    raise ValueError(
                        "logit_bias token id {} out of range for vocab {}".format(
                            tok_i, self._vocab
                        )
                    )
        if request.repetition_penalty is not None and request.repetition_penalty <= 0:
            raise ValueError("repetition_penalty must be > 0")
        if request.min_tokens:
            if request.min_tokens < 0:
                raise ValueError("min_tokens must be >= 0")
            if request.min_tokens > request.max_new_tokens:
                raise ValueError(
                    "min_tokens {} exceeds max_tokens {}".format(
                        request.min_tokens, request.max_new_tokens
                    )
                )
            if len(request.stop_token_ids or []) > _STOP_SLOTS:
                # suppression rows are fixed-width; an unsuppressed stop id
                # could end the sequence before the floor (ADVICE r3) —
                # reject up front instead of silently under-enforcing
                raise ValueError(
                    "min_tokens supports at most {} stop_token_ids "
                    "(got {})".format(
                        _STOP_SLOTS, len(request.stop_token_ids)
                    )
                )
        if request.logprobs is not None:
            if request.logprobs < 0:
                raise ValueError("logprobs must be >= 0")
            if request.logprobs > self._lp_k:
                raise ValueError(
                    "logprobs={} exceeds the engine's logprobs_k={}".format(
                        request.logprobs, self._lp_k
                    )
                )
        if request.guided is not None:
            from . import guided as _g

            if self._tokenizer is None:
                raise ValueError(
                    "guided decoding needs the engine's tokenizer "
                    "(constructed without one)"
                )
            if self.eos_token_id is None:
                raise ValueError("guided decoding requires an eos token")
            spec = request.guided
            if spec.kind not in ("regex", "json_schema", "json_object"):
                raise ValueError("unknown guided kind {!r}".format(spec.kind))
            # cheap syntactic pre-flight so 4xx errors precede streaming
            # headers; the full (token-lifting) compile runs at admission
            try:
                if spec.kind == "regex":
                    _g._Parser(spec.payload).parse()
                elif spec.kind == "json_schema":
                    import json as _json

                    _g.json_schema_to_regex(_json.loads(spec.payload))
            except _g.RegexError as ex:
                raise ValueError("invalid guided grammar: {}".format(ex))
            except Exception as ex:
                raise ValueError("invalid guided schema: {}".format(ex))

    # -- guided-decoding registry (llm/guided.py) ------------------------

    def _ensure_grammar(self, request: GenRequest) -> dict:
        """Compile (or reuse) the request's grammar and splice it into the
        COMBINED device tables. Runs in the admission worker thread — the
        compile (DFA + token lifting) can take seconds for large vocabs.
        Returns the registry entry {offset, n_states, terminal, refs}."""
        from . import guided as _g

        key = request.guided.cache_key()
        with self._guided_lock:
            entry = self._grammars.get(key)
            if entry is not None:
                entry["refs"] += 1
                request._guided_key = key
                self._ledger_guided_acquire(key, request)
                return entry
        # the O(V) token byte table is per-tokenizer: build once, reuse for
        # every grammar (compile AND device walk share it)
        with self._guided_lock:
            token_bytes = self._gtok_bytes
        if token_bytes is None:
            token_bytes = _g.token_byte_table(self._tokenizer, self._vocab)
        # compile outside the lock (pure); splice under it
        grammar = _g.compile_guided(
            request.guided, self._tokenizer, self._vocab, self.eos_token_id,
            token_bytes=token_bytes,
        )
        with self._guided_lock:
            entry = self._grammars.get(key)
            if entry is not None:  # raced another admission; reuse theirs
                entry["refs"] += 1
                request._guided_key = key
                self._ledger_guided_acquire(key, request)
                return entry
            if self._gtok_bytes is None:
                self._gtok_bytes = token_bytes
            if self._gtok_dev is None:
                tb, tl = _g.build_token_byte_arrays(token_bytes)
                self._gtok_np = (tb, tl)
                self._gtok_dev = (jnp.asarray(tb), jnp.asarray(tl))
            # int16 device states: bound the combined table so offsets can
            # never wrap; fails only THIS request, and only when many
            # distinct grammars are concurrently alive
            total = self._gmask_np.shape[0] if self._gmask_np is not None else 0
            if total + grammar.n_states > 32000:
                raise ValueError(
                    "guided-grammar state budget exhausted ({} + {} states); "
                    "retry when active grammars drain".format(
                        total, grammar.n_states
                    )
                )
            # opportunistic compaction, ONLY when every grammar is dead
            # (refs==0 means no slot state and no in-flight admission holds
            # a key — a partial rebuild would shift offsets under states
            # computed by concurrent admissions, so all-or-nothing)
            if self._grammars and all(
                e["refs"] <= 0 for e in self._grammars.values()
            ):
                self._grammars.clear()
                self._gmask_np = None
                self._gbyte_np = None
                self._guided_dirty = True
            offset = self._gmask_np.shape[0] if self._gmask_np is not None else 0
            entry = {
                "offset": offset,
                "n_states": grammar.n_states,
                "terminal": offset + grammar.terminal,
                "start": offset + grammar.start,
                "refs": 1,
                "grammar": grammar,
            }
            self._grammars[key] = entry
            self._append_guided_tables_locked(grammar)
            request._guided_key = key
            self._ledger_guided_acquire(key, request)
            return entry

    def _append_guided_tables_locked(self, grammar) -> None:
        from . import guided as _g

        offset = self._gmask_np.shape[0] if self._gmask_np is not None else 0
        byte = grammar.byte_trans.astype(np.int32)
        byte = np.where(byte == _g.DEAD, _g.DEAD, byte + offset).astype(np.int16)
        if self._gmask_np is None:
            self._gmask_np = grammar.mask_bits.copy()
            self._gbyte_np = byte
        else:
            self._gmask_np = np.vstack([self._gmask_np, grammar.mask_bits])
            self._gbyte_np = np.vstack([self._gbyte_np, byte])
        self._guided_dirty = True

    def _guided_device_tables(self):
        """(mask_bits, byte_trans, tok_bytes, tok_len) on device, padded to
        power-of-two state counts so trace shapes are bucketed."""
        with self._guided_lock:
            if self._gmask_np is None:
                return None
            if self._guided_dirty or self._gmask_dev is None:
                s = self._gmask_np.shape[0]
                bucket = 1
                while bucket < s:
                    bucket *= 2
                pad = bucket - s
                mask = np.vstack(
                    [self._gmask_np,
                     np.zeros((pad, self._gmask_np.shape[1]), np.uint8)]
                )
                byte = np.vstack(
                    [self._gbyte_np, np.full((pad, 256), -1, np.int16)]
                )
                self._gmask_dev = jnp.asarray(mask)
                self._gbyte_dev = jnp.asarray(byte)
                self._guided_dirty = False
            return (self._gmask_dev, self._gbyte_dev) + self._gtok_dev

    def _release_guided(self, slot: int, request: GenRequest = None) -> None:
        """Slot freed: clear its DFA state and deref its grammar. The key is
        captured at commit time in _slot_guided_key because _slot_req[slot]
        is already None on some finish paths (callers that still hold the
        request pass it so the ledger discharges ITS slab on a grammar key
        shared by concurrent requests)."""
        self._gstate[slot] = -1
        key = self._slot_guided_key[slot]
        if key is None:
            return
        self._slot_guided_key[slot] = None
        self._deref_guided_key(key, request=request)

    def _deref_guided_request(self, request: GenRequest) -> None:
        """Admission failed/dropped before its slot commit: return the
        grammar ref taken by _ensure_grammar."""
        if request._guided_key is not None:
            key, request._guided_key = request._guided_key, None
            self._deref_guided_key(key, request=request)

    def _deref_guided_key(self, key: str,
                          request: GenRequest = None) -> None:
        with self._guided_lock:
            entry = self._grammars.get(key)
            if entry is not None:
                entry["refs"] -= 1
                if self._ledger is not None:
                    lifecycle_ledger.release(
                        "guided.ref", key=key, domain=self,
                        owner=(
                            lifecycle_ledger.request_tag(request)
                            if request is not None else None
                        ),
                    )

    def _ledger_guided_acquire(self, key: str, request: "GenRequest") -> None:
        """One grammar-registry ref taken on the request's behalf
        (_ensure_grammar's three take paths share this record)."""
        if self._ledger is not None:
            lifecycle_ledger.acquire(
                "guided.ref", key=key, domain=self,
                owner=lifecycle_ledger.request_tag(request),
            )

    @property
    def adapter_names(self) -> List[str]:
        return list(self._adapter_index)

    def _slot_lora(self, request: GenRequest) -> int:
        return self._adapter_index.get(request.adapter or "", 0)

    # -- sampling extras (penalties / bias / seeds) -------------------------

    def _request_stop_row(self, request: GenRequest) -> "np.ndarray":
        """The stop set min_tokens suppresses — identical to what _emit
        finishes on: stop_token_ids if given, else the engine eos."""
        ids = request.stop_token_ids or (
            [self.eos_token_id] if self.eos_token_id is not None else []
        )
        row = np.full(_STOP_SLOTS, -1, np.int32)
        for i, t in enumerate(ids[:_STOP_SLOTS]):
            row[i] = int(t)
        return row

    @staticmethod
    def _request_has_extras(request: GenRequest) -> bool:
        return bool(
            request.presence_penalty
            or request.frequency_penalty
            or (request.repetition_penalty and request.repetition_penalty != 1.0)
            or request.seed is not None
            or request.logit_bias
            or request.min_tokens > 0
        )

    def _ensure_extras_state(self) -> None:
        if self._counts_dev is None:
            self._counts_dev = jnp.zeros((self.max_batch, self._vocab), jnp.int32)
            self._bias_dev = jnp.zeros((self.max_batch, self._vocab), jnp.float32)
            self._pmask_dev = jnp.zeros((self.max_batch, self._vocab), bool)

            def _set_row(counts, bias, pmask, slot, first_tok, bias_row, pmask_row):
                # reset the slot's histogram to just the prefill-sampled
                # token (it IS generated output for penalty purposes)
                counts = counts.at[slot].set(0).at[slot, first_tok].set(1)
                bias = bias.at[slot].set(bias_row)
                pmask = pmask.at[slot].set(pmask_row)
                return counts, bias, pmask

            self._set_sampling_row_jit = jax.jit(
                _set_row, donate_argnums=(0, 1, 2)
            )

    def _extras_active(self, active_mask: np.ndarray) -> bool:
        return self._counts_dev is not None and bool(
            np.any(self._slot_extra[active_mask])
        )

    def _batch_sampling(self) -> "SamplingParams":
        """Device-side SamplingParams for the slot batch, cached across
        chunks — the rows only change at commit (which invalidates). The
        host rows are COPIED before upload: zero-copy aliasing of a live,
        commit-mutated buffer would let a future commit rewrite what an
        in-flight chunk samples with (see _chain_input)."""
        if self._sampling_dev is None:
            self._sampling_dev = SamplingParams(
                temperature=jnp.asarray(self._temperature.copy()),
                top_k=jnp.asarray(self._top_k.copy()),
                top_p=jnp.asarray(self._top_p.copy()),
            )
        return self._sampling_dev

    def _extras_constants(self) -> "SamplingExtras":
        """Device-side sampling extras less the per-dispatch counters: the
        per-slot config rows (penalties / seeds / min_tokens / stop sets)
        are cached device constants, invalidated only at commit, and the
        bias is device-chained state."""
        if self._extras_dev is None:
            seeds = np.where(
                self._seeds < 0, -1, self._seeds & 0x7FFFFFFF
            ).astype(np.int32)
            # host rows COPIED before upload (live buffers; see _chain_input)
            self._extras_dev = SamplingExtras(
                presence=jnp.asarray(self._presence.copy()),
                frequency=jnp.asarray(self._frequency.copy()),
                repetition=jnp.asarray(self._repetition.copy()),
                bias=None,       # device-chained state, patched per call
                seeds=jnp.asarray(seeds),
                counters=None,   # per-dispatch, patched below
                min_new=jnp.asarray(self._min_tokens.copy()),
                stop=jnp.asarray(self._stop_rows.copy()),
            )
        return self._extras_dev._replace(bias=self._bias_dev)

    def _produced_counters(self) -> np.ndarray:
        """The produced-token counters [B] of a dispatch, on the host: they
        account for chunks still in flight (a live slot advances
        decode_steps per in-flight chunk — dead slots' counters are garbage
        by then, but their samples are dropped at retire anyway)."""
        produced = np.asarray(
            [r.produced if r is not None else 0 for r in self._slot_req],
            np.int32,
        )
        for entry in self._inflight:
            produced = produced + (
                entry.active_mask.astype(np.int32) * self.decode_steps
            )
        return produced

    def _batch_extras(self) -> "SamplingExtras":
        """Device-side sampling extras of a pipelined chunk: the constants
        and the counters in an upload of their own (a ragged launch stages
        them with its other vectors)."""
        return self._extras_constants()._replace(
            counters=jnp.asarray(self._produced_counters())
        )

    def _bias_pmask_rows(self, request: GenRequest):
        """The request's dense bias row [V] float32 and prompt mask [V]
        bool, as the slots' device state holds them."""
        bias = np.zeros(self._vocab, np.float32)
        if request.logit_bias:
            toks = np.fromiter(
                (int(t) for t in request.logit_bias), np.int64,
                len(request.logit_bias),
            )
            vals = np.fromiter(
                (float(v) for v in request.logit_bias.values()), np.float32,
                len(request.logit_bias),
            )
            ok = (toks >= 0) & (toks < self._vocab)
            bias[toks[ok]] = vals[ok]
        pmask = np.zeros(self._vocab, bool)
        ids = np.asarray(request.prompt_ids, np.int64)
        pmask[ids[(ids >= 0) & (ids < self._vocab)]] = True
        return bias, pmask

    def _first_token_ops(self, request: GenRequest) -> dict:
        """What a request's first token needs of the host beside its
        logits, taken when the launch that ends its prompt is planned (the
        ragged plan, on the loop thread) or when its prefill returned (the
        legacy admission worker): its key of the shared stream, and its
        grammar's entry with the start state's mask row."""
        gentry = grow = None
        if request.guided is not None:
            # compile/register the grammar (slow part; on the legacy path
            # we're in the admission worker thread — the ragged path
            # compiled it there already and only refetches its entry) and
            # constrain the FIRST token — subsequent tokens are constrained
            # inside the decode scan
            if request._guided_key is not None:
                with self._guided_lock:
                    gentry = self._grammars.get(request._guided_key)
            if gentry is None:
                gentry = self._ensure_grammar(request)
            with self._guided_lock:
                grow = self._gmask_np[gentry["start"]]
        return {
            "request": request, "key": self._next_rng(),
            "gentry": gentry, "grow": grow,
        }

    def _sample_first_token(self, op: dict, slot: int, logits, state):
        """ONE dispatch and ONE transfer for a prompt's first token: the
        request's sampling settings, stop set, bias row and prompt /
        grammar masks go into one fresh int32 buffer (never written
        again: the device array may alias it), which crosses as the
        argument of the first-token program over ``logits[slot]``
        (``_first_token_jit``). Worker thread; nothing here waits for the
        device. Returns the program's (ids, logprob triple, state) and the
        request's (bias, prompt mask) rows, for a caller that resets the
        slot's device rows itself."""
        request = op["request"]
        layout, total = self._first_layout
        staged = np.zeros(total, np.int32)
        at = {
            name: staged[offset : offset + math.prod(shape)].reshape(shape)
            for name, offset, shape, _ in layout
        }
        at["slot"][0] = slot
        at["seed"][0] = (
            -1 if request.seed is None else int(request.seed) & 0x7FFFFFFF
        )
        at["top_k"][0] = request.top_k
        at["min_new"][0] = min(
            max(0, int(request.min_tokens or 0)), 2**31 - 1
        )
        at["stop"][0] = self._request_stop_row(request)
        for name, value in (
            ("temperature", request.temperature),
            ("top_p", request.top_p),
            ("presence", request.presence_penalty),
            ("frequency", request.frequency_penalty),
            ("repetition", request.repetition_penalty or 1.0),
        ):
            at[name].view(np.float32)[0] = value
        bias, pmask = self._bias_pmask_rows(request)
        at["bias"].view(np.float32)[0] = bias
        packed = np.packbits(pmask, bitorder="little")
        at["pmask"].view(np.uint8)[0, : packed.size] = packed
        if op["grow"] is not None:
            at["guided"][0] = 1
            at["gmask"].view(np.uint8)[0, : op["grow"].size] = op["grow"]
        out = self._first_token_jit(
            logits, staged, layout, op["key"], state,
            self._request_has_extras(request),
        )
        return out, (bias, pmask)

    def _first_token_commit(self, op: dict, first):
        """Host half of a first token, over the HOST copies of the
        program's results (``first``: the id [1], then the logprob
        triple): the guided DFA's walk over the sampled token's bytes and
        the first logprob entry. Returns (first_id, first_lp)."""
        request, gentry = op["request"], op["gentry"]
        ids, (chosen, top_id, top_lp) = first
        first_id = int(ids[0])
        if gentry is not None:
            # host-side byte walk for the first token's state advance
            if first_id == self.eos_token_id:
                request._gstate0 = gentry["terminal"]
            else:
                s = gentry["start"]
                with self._guided_lock:
                    byte_np = self._gbyte_np
                    tb, tl = self._gtok_np
                for b in tb[first_id][: int(tl[first_id])]:
                    s = int(byte_np[s, int(b)])
                    if s < 0:
                        break
                request._gstate0 = s
        first_lp = None
        if request.logprobs is not None:
            first_lp = {
                "id": first_id,
                "logprob": float(chosen[0]),
                "top_ids": top_id[0].tolist(),
                "top_logprobs": top_lp[0].tolist(),
            }
        return first_id, first_lp

    def check_admission(self, request: GenRequest, reserve: int = 0) -> None:
        """Load shedding: raise a structured 429/503 error instead of
        queueing a request the engine cannot serve in time. Streaming
        callers MUST run this before sending response headers (generate()
        re-checks at submission). ``reserve``: sibling requests the caller
        will submit ahead of this one (an n-choice batch pre-checks all n
        against one queue snapshot — without the reservation, the batch's
        own earlier submissions could shed the later ones mid-SSE)."""
        if self._stopped:
            raise EngineUnavailableError("engine is stopped")
        tot = (
            request.total_timeout
            if request.total_timeout is not None
            else self._total_timeout
        )
        if tot is not None and tot <= 0:
            # an already-expired budget fails fast, before any queueing —
            # this is also the pre-headers 408 path for streaming clients
            self.counters["deadline_total"] += 1
            raise DeadlineExceededError(
                "request budget {}s already elapsed at submission".format(tot),
                stage="total",
            )
        cls = (
            request.priority
            if request.priority in PRIORITY_CLASSES
            else "interactive"
        )
        self._update_brownout()
        try:
            faults.fire("engine.admit", request=request)
        except faults.InjectedFault as ex:
            self._count_shed("queue", cls)
            raise EngineOverloadedError(
                "admission shed (injected): {}".format(ex),
                retry_after=self._retry_after_hint(),
                shed_class=cls,
            ) from ex
        try:
            # class-aware admission seam: chaos forces a class-policy shed
            # regardless of queue state
            faults.fire("engine.admit.class", request=request)
        except faults.InjectedFault as ex:
            self._count_shed("class", cls)
            raise EngineOverloadedError(
                "admission shed by class policy (injected): {}".format(ex),
                retry_after=self._retry_after_hint(),
                shed_class=cls,
            ) from ex
        if (
            self._brownout is not None
            and self._brownout.stage >= 3
            and cls == "best_effort"
        ):
            # deepest brownout stage: best-effort traffic sheds at the door
            # so interactive + batch keep the engine's remaining headroom
            self._count_shed("brownout", cls)
            raise EngineOverloadedError(
                "brownout stage {}: best-effort traffic shed".format(
                    self._brownout.stage
                ),
                retry_after=self._retry_after_hint(),
                shed_class=cls,
            )
        if (
            self.max_pending is not None
            and self._pending.qsize() + reserve >= self.max_pending
        ):
            # class-aware shedding: evict a strictly-lower-class queued
            # request (best-effort first, then batch) to make room for a
            # higher-class arrival; only a queue with nothing lower sheds
            # the arrival itself
            victim = self._pending.shed_lowest(cls)
            if victim is not None:
                self._release_resume_pin(victim)
                self._count_shed("queue", victim.priority)
                victim.error = EngineOverloadedError(
                    "shed from the queue by a higher-priority admission",
                    retry_after=self._retry_after_hint(),
                    shed_class=victim.priority,
                )
                victim.cancelled = True  # admission pop skips it
                victim.out_queue.put_nowait(_FINISHED)
            else:
                self._count_shed("queue", cls)
                raise EngineOverloadedError(
                    "pending queue full ({} waiting, bound {})".format(
                        self._pending.qsize() + reserve, self.max_pending
                    ),
                    retry_after=self._retry_after_hint(),
                    shed_class=cls,
                )
        # KV-pool headroom: only enforced when admission control is
        # configured (max_pending set) — with unbounded admission the
        # historical queue-until-pages-free behavior stands
        if self.max_pending is not None and self.paged_cache is not None:
            pool = self.paged_cache.pool
            need_tokens = len(request.prompt_ids) + 1
            if self._prefix is not None:
                # a cached prefix maps in by reference — only the tail needs
                # fresh pages; without this, the shedder would reject exactly
                # the cheap shared-prefix requests the cache accelerates
                need_tokens -= self._prefix.match_len(
                    request.prompt_ids, self._slot_lora(request)
                )
            saturated = not pool.can_allocate(need_tokens)
            try:
                faults.fire("engine.pool", request=request)
            except faults.InjectedFault:
                saturated = True
            if saturated:
                self._count_shed("pool", cls)
                raise EngineOverloadedError(
                    "kv page pool saturated ({} free pages)".format(
                        pool.free_pages
                    ),
                    retry_after=self._retry_after_hint(),
                    shed_class=cls,
                )

    def _count_shed(self, reason: str, cls: str) -> None:
        """Book one shed under both the legacy totals (sheds_queue /
        sheds_pool) and the per-(reason, class) table backing
        ``engine_sheds_total{reason,class}``."""
        if reason == "pool":
            self.counters["sheds_pool"] += 1
        else:
            self.counters["sheds_queue"] += 1
        per = self._class_sheds.setdefault(reason, {})
        per[cls] = per.get(cls, 0) + 1

    def _retry_after_hint(self, ahead: Optional[int] = None) -> float:
        """Seconds until the queue has likely drained enough for a retry to
        land, derived from the OBSERVED admission drain rate (commits/s over
        the recent window) instead of a constant: hint = (depth ahead + 1) /
        rate, clamped to [0.5, 60]. With no drain observed yet the fallback
        still grows with depth, so deep queues never advertise a 1 s retry."""
        if ahead is None:
            ahead = self._pending.qsize()
        times = self._admit_times
        rate = None
        if len(times) >= 2:
            # anchor the span at NOW, not at the last commit: a wedged loop
            # would otherwise advertise the rate of a historical burst
            # forever, inviting clients to hammer a non-draining engine
            span = time.monotonic() - times[0]
            if span > 0:
                rate = (len(times) - 1) / span
        if rate:
            hint = (ahead + 1) / rate
        else:
            hint = 1.0 + 0.25 * ahead
        return min(60.0, max(0.5, hint))

    # -- brownout controller (docs/slo_scheduling.md) ---------------------

    def _pressure_score(self) -> tuple:
        """(score, signals): overload pressure in [0, ~2] as the max over
        queue depth vs the admission bound, paged-pool occupancy, and the
        deadline-hit / watchdog rates over a sliding ~5 s window."""
        signals: Dict[str, float] = {}
        if self.max_pending:
            signals["queue"] = min(
                2.0, self._pending.qsize() / float(self.max_pending)
            )
        if self.paged_cache is not None:
            pool = self.paged_cache.pool
            usable = max(1, pool.num_pages - 1)  # page 0 is the null page
            headroom = pool.free_pages
            if self._prefix is not None:
                # budget-retained prefix-cache pages are reclaimable on
                # demand (leaf-LRU eviction frees them when allocation
                # needs room): counting them as occupancy would read a
                # warm-but-idle cache as permanent overload and pin the
                # brownout stage high with zero traffic. (Transiently
                # optimistic about pinned preempted-history runs, which
                # unpin at their resume's admission.)
                headroom += self._prefix.cached_pages
            signals["pool"] = max(0.0, (usable - headroom) / usable)
        c = self.counters
        deadlines = (
            c["deadline_queue"] + c["deadline_ttft"] + c["deadline_total"]
        )
        now = time.monotonic()
        win = self._pressure_window
        if win is not None:
            d_dead = deadlines - win[1]
            d_trips = c["watchdog_trips"] - win[2]
            d_admit = self._admit_count - win[3]
            if d_dead + d_admit >= 4:
                # minimum-volume floor: one expired request against zero
                # admissions is a ratio of 1.0 — a single misbehaving
                # client (e.g. submitting already-elapsed budgets) must
                # not slam an idle engine into stage-3 brownout
                signals["deadline"] = d_dead / float(d_dead + d_admit)
            if d_trips > 0:
                signals["watchdog"] = 1.0
        if win is None or now - win[0] >= 5.0:
            self._pressure_window = (
                now, deadlines, c["watchdog_trips"], self._admit_count
            )
        return max(signals.values(), default=0.0), signals

    def _update_brownout(self) -> None:
        """Feed the pressure score into the brownout controller (throttled;
        called from the loop top and from check_admission so the stage stays
        live even while the loop sits in a long chunk) and apply the
        stage's side effects that live outside the hot path."""
        controller = self._brownout
        if controller is None:
            return
        now = time.monotonic()
        if now - self._brownout_checked < 0.1:
            return
        self._brownout_checked = now
        score, signals = self._pressure_score()
        prev = controller.stage
        stage = controller.update(score, signals, now)
        if stage != prev and self._prefill_gate is not None:
            # stage 3 shrinks the prefill admission budget to one segment
            # per decode chunk; dropping below restores the configured value
            self._prefill_gate.set_budget(1 if stage >= 3 else None)

    def _brownout_snapshot(self) -> Optional[dict]:
        if self._brownout is None:
            return None
        return {
            "stage": self._brownout.stage,
            "score": round(self._brownout.score, 4),
            "signals": {
                k: round(v, 4) for k, v in self._brownout.signals.items()
            },
        }

    def _effective_max_new(self, request: GenRequest) -> int:
        """Brownout stage >= 2 caps batch-lane generation length so long
        batch decodes release their slots early; the cap lifts with the
        stage (a capped request already past the cap finishes at its next
        emission)."""
        if (
            self._brownout is not None
            and self._brownout.stage >= 2
            and request.priority != "interactive"
        ):
            return min(request.max_new_tokens, self._brownout_batch_cap)
        return request.max_new_tokens

    # -- preemptible batch lane (docs/slo_scheduling.md) ------------------

    def _maybe_preempt(self) -> None:
        """Loop-thread, chunk boundary: under slot pressure with interactive
        work queued, preempt batch-lane slots — one per queued interactive
        request that has no free slot waiting for it. Each victim's
        generated-so-far KV is committed into the radix prefix cache by page
        reference first, so its re-admission replays the whole history with
        near-zero prefill; the freed slots go through the normal
        quarantine/pipeline-barrier machinery before reuse."""
        if not self._preempt:
            return
        want = self._pending.waiting("interactive")
        if want <= 0:
            return
        # quarantined-but-unowned slots count as free HERE: they become
        # admissible the moment their pipeline barrier retires (within one
        # chunk), and preempting another batch slot because the one just
        # freed hasn't cleared quarantine yet would double-preempt per
        # interactive arrival at pipeline depth >= 2
        free = sum(
            1
            for i, r in enumerate(self._slot_req)
            if r is None and i not in self._admitting
        )
        need = want - free
        while need > 0:
            victim_slot = None
            victim_key = None
            for slot, request in enumerate(self._slot_req):
                if request is None or request.priority == "interactive":
                    continue
                if request.cancelled or request.produced < 1:
                    continue
                if request._preempt_count >= self._preempt_budget:
                    continue  # budget exhausted: immune (starvation floor)
                # resume replays through a fresh prefill of prompt+generated:
                # exact only for plain sampling — grammar states, penalties,
                # seeds-with-counters and logprob streams do not survive the
                # round trip, so those slots are never victims
                if request.guided is not None or self._gstate[slot] >= 0:
                    continue
                if (
                    self._request_has_extras(request)
                    or request.logprobs is not None
                ):
                    continue
                key = (
                    _CLASS_RANK[request.priority],      # lowest class first
                    request._deadline
                    if request._deadline is not None
                    else float("inf"),                   # latest deadline
                    -request.produced,                   # least progress
                )
                if victim_key is None or key > victim_key:
                    victim_slot, victim_key = slot, key
            if victim_slot is None or not self._preempt_slot(victim_slot):
                return
            need -= 1

    def _preempt_slot(self, slot: int) -> bool:
        """Preempt the batch-lane request in ``slot`` at a chunk boundary:
        commit its generated-so-far KV into the radix prefix cache, free the
        slot (quarantined while in-flight chunks still reference it), and
        requeue the request with its full token history as the resume
        prompt. The consumer's stream is untouched — resume continues
        emitting into the same out_queue. Returns False when an injected
        ``engine.preempt`` fault aborted the preemption (nothing leaks: the
        radix store alone is the same store every admission commit runs)."""
        request = self._slot_req[slot]
        if request is None:
            return False
        history = list(request.prompt_ids) + [int(t) for t in request._gen_ids]
        if self.paged_cache is not None and self._prefix is not None:
            # store the final-KV prefix by reference to this slot's pages.
            # Only block-aligned WHOLE pages are stored and the stored run
            # ends at/below len(history)-1 — the last emitted token's KV is
            # not written yet, and in-flight chunks only write at/after it,
            # so every stored page is immutable from here on.
            self._prefix.store_pages(
                history, self._slot_lora(request),
                self.paged_cache.pool.slot_pages(slot),
            )
        try:
            faults.fire("engine.preempt", request=request)
        except faults.InjectedFault:
            # chaos seam, mid-commit: a failure here ABORTS the preemption.
            # The request keeps decoding in its slot; the radix store above
            # is identical to a normal admission-commit store (refcounted,
            # CoW-protected), so no page leaks and no state is torn.
            return False
        self.counters["preemptions"] += 1
        request._preempt_count += 1
        request.prompt_ids = history
        request._gen_ids = []
        if self._prefix is not None and self.paged_cache is not None:
            # hold the stored run against eviction until the resume's
            # lookup: the whole point of the commit above is a near-zero
            # prefill on re-admission, and queue-time pool pressure must
            # not LRU it away (the resume would then recompile a fresh
            # full-length prefill on the serving loop). A prior leg's pin
            # is impossible here: it was released at this leg's admission
            with lifecycle_ledger.owner(
                lifecycle_ledger.request_tag(request)
            ):
                request._resume_pin = self._prefix.pin_run(
                    history, self._slot_lora(request)
                )
        # the queue-wait budget restarts for the resume leg: the request
        # already proved admissible once, and expiring it for time spent
        # GENERATING would punish the preempted class twice
        qt = (
            request.queue_timeout
            if request.queue_timeout is not None
            else self._queue_timeout
        )
        request._queue_deadline = (
            time.monotonic() + qt if qt is not None else None
        )
        self._slot_req[slot] = None
        self._release_guided(slot, request)  # no-op for victims; kept for symmetry
        self._free_slot_pages(slot)
        request._queued = _clock()  # the resume leg's own wait
        self._pending.put_nowait(request)
        self._wake_loop()
        return True

    def _resolve_deadlines(self, request: GenRequest) -> None:
        """Pin the request's monotonic deadlines at submission (per-request
        budgets override the engine defaults)."""
        now = time.monotonic()
        qt = (
            request.queue_timeout
            if request.queue_timeout is not None
            else self._queue_timeout
        )
        tt = (
            request.ttft_timeout
            if request.ttft_timeout is not None
            else self._ttft_timeout
        )
        tot = (
            request.total_timeout
            if request.total_timeout is not None
            else self._total_timeout
        )
        request._queue_deadline = now + qt if qt is not None else None
        request._ttft_deadline = now + tt if tt is not None else None
        request._deadline = now + tot if tot is not None else None

    async def generate(self, request: GenRequest) -> AsyncIterator[int]:
        """Submit a request; yields sampled token ids as they decode."""
        if self._stopped:
            raise EngineUnavailableError("engine is stopped")
        self.validate(request)
        self.check_admission(request)
        self._resolve_deadlines(request)
        request.prompt_len = len(request.prompt_ids)
        request.out_queue = asyncio.Queue()
        request._submitted = request._queued = _clock()
        self._pending.put_nowait(request)
        self._ensure_loop()
        self._wake_loop()
        try:
            while True:
                token = await request.out_queue.get()
                if token is _FINISHED:
                    if request.error is not None:
                        raise request.error
                    return
                yield token
        finally:
            # consumer stopped early (client disconnect / generator close):
            # flag the request so the engine frees its slot and pages instead
            # of decoding to max_new_tokens for nobody. No-op after a normal
            # finish (the slot is already free).
            request.cancelled = True

    def stop(self) -> None:
        """Stop the loop and fail out every active/pending request (their
        consumers must never hang on a dead engine). A request mid-admission is
        caught by the loop's post-exit drain (_run_loop's stopped check)."""
        self._stopped = True
        err = EngineUnavailableError("engine stopped")
        self._fail_all(err)
        for request in self._pending.pop_all():
            self._release_resume_pin(request)
            request.error = err
            request.out_queue.put_nowait(_FINISHED)
        self._wake_loop()  # unblock an idle loop so its cleanup runs

    @property
    def active_slots(self) -> int:
        return sum(1 for r in self._slot_req if r is not None)

    async def wait_drained(self, timeout: float = 30.0) -> None:
        """Await the decode loop going fully idle (loop task returned: no
        active slots, no in-flight pipeline chunks, no admissions). Under
        the pipelined loop a consumer can see its last token while younger
        chunks are still in flight — page accounting is only FINAL at
        drain, so tests/ops code that audits the pool should await this."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            task = self._loop_task
            if task is None or task.done():
                return
            await asyncio.sleep(0.005)
        raise TimeoutError("engine loop did not drain within {}s".format(timeout))

    @property
    def is_ready(self) -> bool:
        """Liveness signal for the HTTP /ready endpoint: False while the
        engine is stopped or the watchdog is mid-recovery."""
        return not self._stopped and not self._recovering

    def _kv_pool_snapshot(self):
        """Paged-pool capacity block shared by health() and
        lifecycle_stats() (docs/paged_kv_quant.md): bytes split by kind so
        the int8 win shows up on a dashboard. None on the dense backend."""
        if self.paged_cache is None:
            return None
        return dict(
            self.paged_cache.pool_bytes(),
            dtype=self.paged_cache.pool_dtype,
            num_pages=self.paged_cache.pool.num_pages,
            page_size=self.paged_cache.pool.page_size,
            # raw high-water mark, prefix-cache pages included (the brownout
            # "pool" signal leaves out what the cache could give back; the
            # current occupancy is the kv_pool_free_pages gauge)
            used_pages_peak=self.paged_cache.pool.used_pages_peak,
        )

    def _state_pool_snapshot(self):
        """State-cache block shared by health() and lifecycle_stats()
        (docs/state_cache.md): slots, how many are owned now and at most
        since the engine started, bytes, and the launches that zeroed a slot
        for a new owner. None on the K/V backends."""
        if self.state_cache is None:
            return None
        return self.state_cache.snapshot()

    def _reap_promotions(self, force: bool = False) -> None:
        """Loop-thread: retire-stage observation of completed host-tier
        promotion DMAs (docs/kv_tiering.md). A no-op without a host tier;
        ``force`` blocks on stragglers (drain/stop)."""
        pc = self.paged_cache
        if pc is None or (pc.host_tier is None and self._kv_transport is None):
            # transport imports ride the same promotion-fence records as
            # host-tier re-onlines, so a transport-attached engine reaps
            # even without a host tier (docs/disaggregation.md)
            return
        reaped = pc.reap_promotions(force=force)
        if reaped:
            self._tier_counters["reaps"] += reaped

    def _kv_tier_snapshot(self):
        """Host-tier capacity/movement block shared by health() and
        lifecycle_stats() (docs/kv_tiering.md). None when no tier."""
        pc = self.paged_cache
        if pc is None or pc.host_tier is None:
            return None
        backend = pc.tier_stats()
        prefix = self._prefix.stats() if self._prefix is not None else {}
        page_bytes = sum(pc.pool_bytes().values()) // pc.pool.num_pages
        return {
            "pages": {
                "hbm": prefix.get("cached_pages", 0),
                "host": prefix.get("host_pages", 0),
            },
            "bytes": {
                "hbm": prefix.get("cached_bytes", 0),
                "host": prefix.get("host_bytes", 0),
            },
            "nodes": {
                "hbm": (
                    prefix.get("nodes", 0) - prefix.get("host_nodes", 0)
                ),
                "host": prefix.get("host_nodes", 0),
            },
            "demotions": prefix.get("demotions", 0),
            "promotions": prefix.get("promotions", 0),
            "hits_by_tier": prefix.get("hits_by_tier", {}),
            "host_pages_capacity": backend["host_pages_capacity"],
            "host_pages_used": backend["host_pages_used"],
            "demoted_pages_total": backend["demoted_pages_total"],
            "promoted_pages_total": backend["promoted_pages_total"],
            "promo_overlap_ratio": backend["overlap_ratio"],
            "promo_wait_ms": backend["promo_wait_ms"],
            "promo_total_ms": backend["promo_total_ms"],
            "reaps": self._tier_counters["reaps"],
            "page_bytes": page_bytes,
        }

    # -- disaggregated prefill/decode (docs/disaggregation.md) -------------

    def attach_kv_transport(self, endpoint, role: str = "hybrid") -> None:
        """Wire a KV-transport endpoint (llm/kv_transport.py) and this
        replica's role into the engine. Called by the replica group at
        construction; requires the paged backend with a prefix cache —
        the shipment payload IS the radix-storable prefix."""
        if role not in ("prefill", "decode", "hybrid"):
            raise ValueError(
                "replica role must be prefill/decode/hybrid: got {!r}"
                .format(role)
            )
        if endpoint is not None and self.cache_mode == "state":
            raise ValueError(
                "KV transport (KVShipment) cannot serve engine.cache=state: "
                "a shipment is the prefix cache's K/V pages, and a "
                "recurrent state would have to travel as a snapshot of the "
                "slot (docs/state_cache.md)"
            )
        if endpoint is not None and self._row_state is not None:
            raise ValueError(
                "KV transport (KVShipment) cannot serve a model that keeps "
                "a recurrent state beside its pages: a shipment is the "
                "prefix cache's K/V pages, and the state of the tokens they "
                "hold would have to travel with them as a snapshot of the "
                "slot (docs/hybrid_cache.md)"
            )
        if endpoint is not None and (
            self.cache_mode != "paged" or self._prefix is None
        ):
            raise ValueError(
                "KV transport needs cache_mode='paged' and a prefix_cache "
                "(the shipment payload is the radix-storable prefix; "
                "docs/disaggregation.md)"
            )
        self._kv_transport = endpoint
        self.replica_role = role

    def _maybe_ship_draft(self, job) -> None:
        """Draft-ahead KV shipping (loop thread; docs/spec_decode_trees.md):
        at a ragged prefill chunk boundary, the job's newly-FINAL storable
        pages export into an unsealed partial shipment — the transport
        overlaps the remaining prefill compute instead of serializing
        behind the commit. Always holds back the last storable page so the
        commit-time seal (:meth:`_maybe_ship`) carries real tail pages.
        Best-effort by contract: an injected ``kv.ship.partial`` fault, a
        real export/send failure, or a transport drop ABORTS the job's
        whole draft-ahead stream and skips the seal — the receiver's
        unsealed assembly is never consumable, so the decode replica falls
        back to recompute with zero page leaks on either side."""
        request = job.request
        dst = request._ship_to
        endpoint = self._kv_transport
        if not dst or endpoint is None or self.paged_cache is None \
                or self._prefix is None:
            return
        ids = request.prompt_ids
        storable = self._prefix.longest_prefix_len(len(ids))
        if storable < self._prefix.block:
            return
        page_size = self.paged_cache.pool.page_size
        state = self._kv_draft_ahead.get(job.slot)
        if state is not None and state["aborted"]:
            return
        # whole pages the prefilled prefix now covers, minus the held-back
        # tail page (the seal's payload)
        n_pages = min(
            min(job.pos, storable) // page_size,
            storable // page_size - 1,
        )
        offset = state["offset"] if state is not None else 0
        if n_pages <= offset:
            return
        from .kv_transport import KVShipment, shipment_key

        lora = self._slot_lora(request)
        pages = self.paged_cache.pool.slot_pages(job.slot)[offset:n_pages]
        if state is None:
            state = self._kv_draft_ahead[job.slot] = {
                "offset": 0, "aborted": False,
            }
        try:
            faults.fire("kv.ship.partial", request=request)
            slabs = self.paged_cache.export_pages(pages)
            sent = endpoint.send(dst, KVShipment(
                key=shipment_key(ids, self._prefix.block, lora),
                src=self.replica_id or "r?",
                prefix_len=n_pages * page_size,
                page_size=page_size,
                lora=lora,
                hk=slabs["hk"], hv=slabs["hv"],
                hk_scale=slabs.get("hk_scale"),
                hv_scale=slabs.get("hv_scale"),
                page_offset=offset, final=False,
            ))
        except faults.InjectedFault:
            state["aborted"] = True
            self._kv_ship_stats["draft_aborts"] += 1
            return
        except Exception as ex:  # noqa: BLE001 - best-effort by contract
            state["aborted"] = True
            self._kv_ship_stats["draft_aborts"] += 1
            logger.warning(
                "draft-ahead kv ship to %s aborted (%s: %s); decode-side "
                "recompute", dst, type(ex).__name__, ex,
            )
            return
        if not sent:
            state["aborted"] = True
            self._kv_ship_stats["draft_aborts"] += 1
            return
        state["offset"] = n_pages
        self._kv_ship_stats["draft_ships"] += 1
        self._kv_ship_stats["draft_pages"] += n_pages - offset

    def _maybe_ship(self, request: GenRequest, slot: int) -> None:
        """Ship-at-commit (loop thread): export the just-committed
        admission's block-aligned prefix pages into a KV-transport
        shipment addressed to ``request._ship_to`` (docs/disaggregation.md).
        When draft-ahead shipping already streamed the prefix head
        (:meth:`_maybe_ship_draft`), only the TAIL pages ship here as the
        sealing final frame; an aborted draft-ahead stream skips the seal
        outright (the unsealed assembly must stay unconsumable).
        Best-effort by contract — an injected ``engine.kv.ship`` fault or
        a full receive slab drops the shipment and the decode replica
        recomputes; nothing here can fail the request."""
        state = self._kv_draft_ahead.pop(slot, None)
        dst = request._ship_to
        endpoint = self._kv_transport
        if not dst or endpoint is None or self.paged_cache is None \
                or self._prefix is None:
            return
        if state is not None and state["aborted"]:
            # the partial stream died mid-flight: sealing now could attach
            # a prefix we cannot prove contiguous — drop to recompute
            self._kv_ship_stats["ship_drops"] += 1
            return
        offset = state["offset"] if state is not None else 0
        from .kv_transport import KVShipment, shipment_key

        ids = request.prompt_ids
        prefix_len = self._prefix.longest_prefix_len(len(ids))
        if prefix_len < self._prefix.block:
            return
        t0 = time.perf_counter()
        lora = self._slot_lora(request)
        n_pages = prefix_len // self.paged_cache.pool.page_size
        pages = self.paged_cache.pool.slot_pages(slot)[offset:n_pages]
        try:
            faults.fire("engine.kv.ship", request=request)
            slabs = self.paged_cache.export_pages(pages)
            sent = endpoint.send(dst, KVShipment(
                key=shipment_key(ids, self._prefix.block, lora),
                src=self.replica_id or "r?",
                prefix_len=prefix_len,
                page_size=self.paged_cache.pool.page_size,
                lora=lora,
                hk=slabs["hk"], hv=slabs["hv"],
                hk_scale=slabs.get("hk_scale"),
                hv_scale=slabs.get("hv_scale"),
                page_offset=offset, final=True,
            ))
        except faults.InjectedFault:
            self._kv_ship_stats["ship_drops"] += 1
            return
        except Exception as ex:  # noqa: BLE001 - ship is best-effort by contract
            # a REAL export/send failure (e.g. MemoryError staging the
            # host slabs) must degrade exactly like an injected one:
            # dropped + counted, never a failed commit on the loop thread
            self._kv_ship_stats["ship_drops"] += 1
            logger.warning(
                "kv ship to %s dropped (%s: %s); decode-side recompute",
                dst, type(ex).__name__, ex,
            )
            return
        if not sent:
            self._kv_ship_stats["ship_drops"] += 1
            return
        self._kv_ship_stats["ships"] += 1
        # ship_pages counts the WHOLE prefix (head pages rode the draft
        # frames): the overlap gauge divides draft_pages by it, and page
        # accounting stays comparable with the single-frame path
        self._kv_ship_stats["ship_pages"] += n_pages
        self._hist_ship_ms.observe((time.perf_counter() - t0) * 1e3)

    def receive_shipment(self, prompt_ids: List[int], lora: int = 0) -> dict:
        """Receive-and-promote (docs/disaggregation.md): pop the shipment
        for this prompt's prefix from the transport receive slab and
        re-online it through the promote-under-dispatch-lock fence —
        fresh device pages, the async host→device scatter ENQUEUED before
        the page ids publish, the radix-cache attach last
        (prefix_cache.store_shipped). The next admission's prefix lookup
        then hits the shipped run.

        Called by the replica group off the event loop (any thread is
        safe: the tree lock and dispatch lock serialize against the
        serving loop). Returns ``{"status": "imported"|"empty"|"failed"|
        "off", "pages": n}`` — ``failed`` (injected ``engine.kv.receive``
        fault, pool pressure, geometry mismatch) drops the shipment with
        zero page leaks; the group then re-routes the stream to a
        hybrid-capable sibling."""
        endpoint = self._kv_transport
        if endpoint is None or self.paged_cache is None \
                or self._prefix is None:
            return {"status": "off", "pages": 0}
        from .kv_transport import shipment_key

        key = shipment_key(prompt_ids, self._prefix.block, lora)
        shipment = endpoint.recv(key)
        if shipment is None:
            self._kv_ship_stats["receive_empty"] += 1
            return {"status": "empty", "pages": 0}
        t0 = time.perf_counter()
        try:
            faults.fire(
                "engine.kv.receive",
                request=_ShipShim(prompt_ids),
            )
            pages = self._prefix.store_shipped(
                prompt_ids, lora, shipment, self.paged_cache
            )
        except (faults.InjectedFault, MemoryError, ValueError) as ex:
            # the shipment's slabs are plain host memory: dropping the
            # reference IS the cleanup (no pool pages were published)
            self._kv_ship_stats["receive_failures"] += 1
            return {"status": "failed", "pages": 0, "error": repr(ex)[:200]}
        self._kv_ship_stats["receives"] += 1
        self._kv_ship_stats["receive_pages"] += pages
        self._hist_receive_ms.observe((time.perf_counter() - t0) * 1e3)
        return {"status": "imported", "pages": pages}

    def _count_ship_outcome(self, request: GenRequest) -> None:
        """Admission-time ship accounting on the decode replica: a request
        the group marked ``_shipped`` either finds its whole storable
        prefix resident (ship HIT — it recomputes none of the shipped KV)
        or recomputes (transport drop, eviction, receive failure). The
        hit-rate gauge is the disaggregation headline
        (engine_kv_ship_hit_rate; tests/test_kv_transport.py asserts
        1.0 on the clean path). One-shot per request."""
        if not request._shipped or self._prefix is None:
            return
        request._shipped = False
        ids = request.prompt_ids
        storable = self._prefix.longest_prefix_len(len(ids))
        lora = self._slot_lora(request)
        if storable >= self._prefix.block and (
            self._prefix.match_len(ids, lora) >= storable
        ):
            self._kv_ship_stats["hits"] += 1
        else:
            self._kv_ship_stats["recomputes"] += 1

    def _kv_ship_snapshot(self):
        """KV-transport movement block shared by health() and
        lifecycle_stats() (docs/disaggregation.md). None when no
        transport is attached."""
        if self._kv_transport is None:
            return None
        s = self._kv_ship_stats
        judged = s["hits"] + s["recomputes"]
        return {
            "role": self.replica_role,
            "ships": s["ships"],
            "ship_pages": s["ship_pages"],
            "ship_drops": s["ship_drops"],
            "receives": s["receives"],
            "receive_pages": s["receive_pages"],
            "receive_empty": s["receive_empty"],
            "receive_failures": s["receive_failures"],
            "hits": s["hits"],
            "recomputes": s["recomputes"],
            "hit_rate": (
                round(s["hits"] / judged, 4) if judged else None
            ),
            "draft_ships": s["draft_ships"],
            "draft_pages": s["draft_pages"],
            "draft_aborts": s["draft_aborts"],
            # share of shipped prefix pages that overlapped the prefill
            # tail instead of serializing behind the commit
            # (engine_kv_ship_overlap_ratio; docs/spec_decode_trees.md)
            "overlap_ratio": (
                round(s["draft_pages"] / s["ship_pages"], 4)
                if s["ship_pages"] else 0.0
            ),
            "ship_ms": self._hist_ship_ms.snapshot(),
            "receive_ms": self._hist_receive_ms.snapshot(),
            "transport": self._kv_transport.stats(),
        }

    def _device_snapshot(self) -> dict:
        """Device block shared by health() and lifecycle_stats(): the
        backend's identity as JAX reports it plus ``memory_stats()`` of the
        engine's devices. This process owns the chip, so this is the one
        place HBM use is read (the statistics service never imports jax)."""
        from ..utils.tpu import device_identity, device_memory_stats

        memory = device_memory_stats()
        out = dict(device_identity(), memory=memory)
        peaks = [m["peak_bytes_in_use"] for m in memory
                 if "peak_bytes_in_use" in m]
        out["peak_bytes_in_use"] = max(peaks) if peaks else None
        return out

    def _kernel_routes(self) -> dict:
        """Each device kernel's route and why not the Pallas one, evaluated
        ONCE at construction by the ``*_kernel_unsupported_reason`` functions
        the model code calls at trace time, over the engine's own shapes:
        ``{decode, ragged, int4: "pallas"|"xla"|None, reason}`` and, where the
        model has such layers, ``ssd_update``, ``ssd_chunk``, ``moe``. None =
        no such launch; ``int4`` is judged at M = max_batch."""
        reason = {}
        paged = self._paged_kernel_reason
        routes = {"decode": "pallas" if paged is None else "xla"}
        if paged is not None:
            reason["decode"] = paged
        routes["ragged"] = None
        if self._ragged:
            routes["ragged"] = routes["decode"]
            if paged is not None:
                reason["ragged"] = paged
        if self._row_state is not None:
            # the mixer's two kernels (ops/mamba2.py): the same pure
            # function models/falcon_h1.py evaluates at trace time, over the
            # token axes its two surfaces hand it
            from ..ops.mamba2 import ssd_kernel_unsupported_reason

            rs = self._row_state
            for name, tokens in (("ssd_update", None),
                                 ("ssd_chunk", self._ragged_dense)):
                why = ssd_kernel_unsupported_reason(
                    rs.n_heads, rs.n_groups, rs.head_dim, rs.d_state, tokens)
                routes[name] = "pallas" if why is None else "xla"
                if why is not None:
                    reason[name] = why
        if getattr(self.bundle, "expert_stack", None) is not None:
            from ..ops.moe_experts import kernel_route  # held experts
            routes["moe"], why = kernel_route(
                self.bundle, self.params, (self._ragged_dense, self.max_batch))
            reason.update({"moe": why} if why else {})
        routes["int4"] = None
        if self.weight_quant == "int4":
            int4 = self._int4_kernel_reason()
            routes["int4"] = "pallas" if int4 is None else "xla"
            if int4 is not None:
                reason["int4"] = int4
        routes["reason"] = reason or None
        return routes

    def _int4_kernel_reason(self) -> Optional[str]:
        """First reason an int4 projection of this engine's tree misses the
        fused kernel at the decode shape, or None when all take it."""
        from ..ops.fused_matmul import int4_kernel_unsupported_reason

        if not self.bundle.config.get("int4_fused", True):
            return "config int4_fused=false pins the XLA inline dequant"
        dtype = jnp.dtype(self.bundle.config.get("dtype", "bfloat16"))

        def leaves(tree, name=""):
            if isinstance(tree, dict):
                if "_q4" in tree:
                    yield name, tree["_q4"], tree["_scale4"]
                    return
                for key, value in tree.items():
                    yield from leaves(value, key)
            elif isinstance(tree, (list, tuple)):
                for value in tree[:1]:  # per-layer dicts share shapes
                    yield from leaves(value, name)

        for name, packed, scale in leaves(self.params):
            k2, n = packed.shape[-2:]
            if name.endswith("_e"):
                continue  # expert einsums keep the XLA dequant (models/llama)
            why = int4_kernel_unsupported_reason(
                jax.ShapeDtypeStruct((self.max_batch, 2 * k2), dtype),
                jax.ShapeDtypeStruct((k2, n), packed.dtype),
                jax.ShapeDtypeStruct(scale.shape[-2:], scale.dtype),
            )
            if why is not None:
                return "{}: {}".format(name, why)
        return None

    def _check_kernel_smem(
        self, tokens: int = 0, tree_width: int = 0, items: int = 0
    ) -> None:
        """The paged kernels' scalar-prefetch operands (page table, row
        vectors, work plan, ancestor table) live in SMEM; a configuration
        that overflows it must fail at construction (= endpoint load), not
        as a compile error on the first request."""
        from ..ops.paged_attention import SMEM_BYTES, paged_kernel_smem_bytes

        need = paged_kernel_smem_bytes(
            self.max_batch, self._pages_per_seq, tokens, tree_width, items
        )
        if need > SMEM_BYTES:
            raise ValueError(
                "max_batch={} x {} pages per sequence (max_seq_len={}){} "
                "needs {} bytes of scalar memory for the paged attention "
                "kernel's tables and the chip has {}: lower "
                "engine.max_seq_len, engine.max_batch or "
                "engine.step_token_budget, or raise engine.page_size".format(
                    self.max_batch, self._pages_per_seq, self.max_seq_len,
                    " with a {}-token launch".format(tokens) if tokens else "",
                    need, SMEM_BYTES,
                )
            )

    def health(self) -> dict:
        out = {
            "ready": self.is_ready,
            "stopped": self._stopped,
            "recovering": self._recovering,
            "active_slots": self.active_slots,
            "queue_depth": self._pending.qsize(),
            "queue_depths": self._pending.depths(),
            "preemptions": self.counters["preemptions"],
            "brownout": self._brownout_snapshot(),
            "watchdog_trips": self.counters["watchdog_trips"],
            "step_failures": self.counters["step_failures"],
            "pipeline": {
                "depth": self.pipeline_depth,
                "inflight": len(self._inflight) + len(self._ragged_flights),
                # over pipeline.cycle_ms: the share of the chip the host
                # wastes, as far as the program knows (_CycleClock)
                "starve_ms": self._cycle.starve.snapshot(),
            },
            "scheduler": "ragged" if self._ragged else "two_dispatch",
            "ragged": (
                {
                    "step_token_budget": self._step_token_budget,
                    "effective_budget": self._effective_token_budget(),
                    "prefill_jobs": len(self._prefill_jobs),
                    "steps": self.counters["ragged_steps"],
                    "step_rows": dict(self._step_rows),
                    "decode_steps": self._ragged_decode_steps,
                    "decode_tokens": self.counters["ragged_decode_tokens"],
                    "dense_axis": self._ragged_dense,
                    "layout_axis": self._ragged_tpad,
                    "h2d_transfers": self.counters["ragged_h2d_transfers"],
                }
                if self._ragged
                else None
            ),
            "sampler": dict(self._sampler_passes),
            "kv_pool": self._kv_pool_snapshot(),
            "state_pool": self._state_pool_snapshot(),
            "kv_tier": self._kv_tier_snapshot(),
            "kv_ship": self._kv_ship_snapshot(),
            "weights": {
                "quant": self.weight_quant or "none",
                "bytes": self._weight_bytes,
            },
            "device": self._device_snapshot(),
            "kernels": self._kernels,
            "compile": self._compile_snapshot(),
            "ledger": self._ledger_snapshot(),
            "sharding": self._shard_snapshot(),
            # certificate block like compile/ledger/sharding: None when
            # unarmed. Needed over the process-backend health RPC — the
            # parent cannot reach a worker engine's _sanitizer directly
            "sanitizer": (
                self._sanitizer.stats()
                if self._sanitizer is not None else None
            ),
        }
        if self.replica_id is not None:
            out["replica"] = self.replica_id
        return out

    def _ledger_snapshot(self):
        """Ownership-ledger block shared by health() and lifecycle_stats()
        (docs/static_analysis.md TPU7xx). None when the ledger is unarmed.
        The ledger is process-wide (co-hosted replica engines record into
        one), so counters are fleet totals — per-entry attribution lives
        in the owner/site records, not the counters."""
        if self._ledger is None:
            return None
        return self._ledger.stats()

    def _compile_snapshot(self):
        """Compile-sentry block shared by health() and lifecycle_stats()
        (docs/static_analysis.md TPU6xx). None when the sentry is unarmed.
        The sentry is process-wide (the compile hook surface is global), so
        co-hosted engines report the same counters — attribution lives in
        the per-event context, not the counters."""
        if self._compile_sentry is None:
            return None
        return self._compile_sentry.stats_brief()

    def _shard_snapshot(self):
        """Sharding-sentry block shared by health() and lifecycle_stats()
        (docs/static_analysis.md TPU8xx). None when the sentry is unarmed.
        The sentry is process-wide (co-hosted engines audit into one spec
        table under per-engine path prefixes), so counters are fleet
        totals — per-violation attribution lives in the event records."""
        if self._shard_sentry is None:
            return None
        return self._shard_sentry.stats_brief()

    def lifecycle_stats(self) -> dict:
        """Scrape-time snapshot for statistics.metrics' lifecycle collector
        (counters monotonic; gauges instantaneous)."""
        c = self.counters
        out = {
            "queue_depth": self._pending.qsize(),
            "queue_depths": self._pending.depths(),
            "active_slots": self.active_slots,
            "ready": int(self.is_ready),
            "sheds": {"queue": c["sheds_queue"], "pool": c["sheds_pool"]},
            "sheds_by_class": {
                reason: dict(per)
                for reason, per in self._class_sheds.items()
            },
            "preemptions": c["preemptions"],
            "brownout": self._brownout_snapshot(),
            "deadlines": {
                "queue": c["deadline_queue"],
                "ttft": c["deadline_ttft"],
                "total": c["deadline_total"],
            },
            "watchdog_trips": c["watchdog_trips"],
            "step_failures": c["step_failures"],
            "pipeline": {
                "depth": self.pipeline_depth,
                "inflight": len(self._inflight) + len(self._ragged_flights),
                "dispatch_ms": self._hist_dispatch.snapshot(),
                # wait_ms + emit_ms: the device->host sync is inside it
                "retire_ms": self._hist_retire.snapshot(),
                # the loop thread's time per cycle; the six phases add up
                # to cycle_ms (_CycleClock)
                "phases": self._cycle.snapshot(),
                "cycle_ms": self._cycle.cycle.snapshot(),
                # the launch timeline across the loop thread and the
                # dispatch worker: launch_parts, readback_ms, starve_ms
                **self._cycle.timeline(),
            },
            "requests": dict(
                {k: h.snapshot() for k, h in self._hist_request.items()},
                prefill_launches=self._hist_prefill_launches.snapshot(),
            ),
            # ragged token-budget scheduler (docs/ragged_attention.md):
            # per-step budget utilization + per-phase row counters backing
            # engine_step_token_budget_utilization / engine_step_rows
            "scheduler": "ragged" if self._ragged else "two_dispatch",
            "ragged": (
                {
                    "step_token_budget": self._step_token_budget,
                    "effective_budget": self._effective_token_budget(),
                    "prefill_jobs": len(self._prefill_jobs),
                    "steps": self.counters["ragged_steps"],
                    # of those, the launches a worker thread waited out
                    "waits_off_loop": self.counters["ragged_waits_off_loop"],
                    # prompts whose first token a ragged retire committed,
                    # and of those the ones whose id came back with the
                    # launch's own copies (sampled behind it)
                    "first_tokens": self.counters["ragged_first_tokens"],
                    "first_tokens_behind_launch": self.counters[
                        "ragged_first_tokens_behind_launch"
                    ],
                    # of ``steps``, the launches enqueued while the one
                    # before them had not been read back, and the rows such
                    # launches carried for nothing (ended at the launch
                    # before, dropped at retire)
                    "launches_behind": self.counters[
                        "ragged_launches_behind"
                    ],
                    "surplus_rows": self.counters["ragged_surplus_rows"],
                    "budget_utilization": self._hist_budget.snapshot(),
                    "step_rows": dict(self._step_rows),
                    # multi-step decode rows + spec-as-row
                    # (docs/ragged_attention.md): decode tokens advanced
                    # per launch and the per-launch draft acceptance —
                    # launches/decode_tokens is dispatches-per-decode-token
                    "decode_steps": self._ragged_decode_steps,
                    "decode_tokens": self.counters["ragged_decode_tokens"],
                    "prefill_tokens": self.counters["ragged_prefill_tokens"],
                    "passes": self.counters["ragged_passes"],
                    # the mixed pass's two token axes (static) and the
                    # rows its dense layers multiplied, summed over launches
                    "dense_axis": self._ragged_dense,
                    "layout_axis": self._ragged_tpad,
                    "dense_rows": self.counters["ragged_dense_rows"],
                    # uploads the worker made before the jitted call
                    "h2d_transfers": self.counters["ragged_h2d_transfers"],
                    "decode_chain_rows": self.counters["decode_chain_rows"],
                    "decode_chain_kv_tokens": (
                        self.counters["decode_chain_kv_tokens"]
                    ),
                    "mixed_rows": self.counters["mixed_rows"],
                    "mixed_kv_tokens": self.counters["mixed_kv_tokens"],
                    "mixed_qk_pairs": self.counters["mixed_qk_pairs"],
                    "tokens_per_launch": self._hist_launch_tokens.snapshot(),
                    "spec_acceptance": self._hist_spec_accept.snapshot(),
                    # draft-tree verify rows (docs/spec_decode_trees.md):
                    # accepted path depth + pluggable-proposer hit counts
                    # (engine_spec_tree_accept_depth /
                    # engine_spec_proposer_hits_total in
                    # statistics/metrics.py)
                    "spec_tree_depth": (
                        self._hist_spec_tree_depth.snapshot()
                        if self._spec_tree
                        else None
                    ),
                    "spec_tree_fallbacks": (
                        self.counters["spec_tree_fallbacks"]
                    ),
                    "spec_proposer": (
                        dict(
                            self._spec_proposer.stats(),
                            name=self._spec_proposer.name,
                        )
                        if self._spec_proposer is not None
                        else None
                    ),
                }
                if self._ragged
                else None
            ),
            "sampler": dict(self._sampler_passes),
            "kv_pool": self._kv_pool_snapshot(),
            "state_pool": self._state_pool_snapshot(),
            "kv_tier": self._kv_tier_snapshot(),
            "kv_ship": self._kv_ship_snapshot(),
            "weights": {
                "quant": self.weight_quant or "none",
                "bytes": self._weight_bytes,
            },
            "device": self._device_snapshot(),
            "kernels": self._kernels,
            "compile": self._compile_snapshot(),
            "ledger": self._ledger_snapshot(),
            "sharding": self._shard_snapshot(),
        }
        if self._latent is not None:
            out["latent"] = dict(self._latent_counts)
        if self._window is not None:
            out["window"] = dict(self._window_counts)
        if self._row_state is not None:
            out["ssm"] = self._ssm_snapshot()
        if self._expert_counters() is not None:
            out["moe"] = self._moe_snapshot()
        if self.replica_id is not None:
            out["replica"] = self.replica_id
        return out

    def _ssm_snapshot(self) -> dict:
        """``lifecycle_stats()["ssm"]`` (docs/hybrid_cache.md): the work the
        launches gave a model's state-space mixer, a row once whatever the
        layers: ``update_rows`` rows x passes that advanced ONE token (the
        mixed passes' one-token rows, every chained pass and every pass of a
        decode chunk: ``ragged.decode_chain_rows``), ``chunk_rows`` /
        ``chunk_tokens`` the mixed passes' rows of more tokens, ``resets``
        the launches that counted a slot as zero for a new owner,
        ``rewinds`` the prompts a recovery started again, ``passes`` the
        model's passes, and the slot pool's snapshot beside them."""
        pool = self.state_cache.snapshot()
        return dict(
            self._ssm_counts,
            update_rows=(self._ssm_counts["update_rows"]
                         + self.counters["decode_chain_rows"]),
            resets=pool["resets"], rewinds=pool["rewinds"],
            # every pass of the model, a launch's mixed pass, its chained
            # passes and a decode chunk's alike (the sampler runs once each)
            passes=self._sampler_passes["passes"],
            layers=int(self.bundle.n_layers), state_pool=pool,
        )

    def _expert_counters(self):
        """The counters a model's expert layers keep on the device beside
        the pools, or None: a plane-less leaf of a model's own page layout
        (docs/latent_cache.md) or the standard pools' carried companion
        (``PagedKVCache.v_carry``)."""
        if self.paged_cache is None:
            return None
        if self._latent is not None:
            return self.paged_cache.v["counters"]
        return self.paged_cache.counters

    def _moe_snapshot(self) -> dict:
        """``moe`` of lifecycle_stats (docs/latent_cache.md): held experts
        that received a token, summed over the expert layers of every pass;
        assignments that stayed here; expert layers run."""
        with self.paged_cache.dispatch_lock:
            # a copy taken under the lock: the next launch donates the
            # pools' own buffer, and the read below waits for this one only
            counters = self._expert_counters() + 0
        hit, local, layers = (int(x) for x in np.asarray(counters)[:3])
        return {
            "experts_held": self._by_kind[1].experts_held,
            "experts_hit": hit,
            "local_assignments": local,
            "layer_passes": layers,
        }

    @property
    def logprobs_k(self) -> int:
        """Public top-k ceiling for logprob reporting (OpenAI top_logprobs
        and vLLM prompt_logprobs validate against this)."""
        return self._lp_k

    # -- internals -------------------------------------------------------------

    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(self._run_loop())
        if self._watchdog_interval and (
            self._watchdog_task is None or self._watchdog_task.done()
        ):
            self._watchdog_task = asyncio.get_running_loop().create_task(
                self._watchdog_loop()
            )

    # -- watchdog + supervised recovery ---------------------------------------

    async def _watchdog_loop(self) -> None:
        """Detects a stuck decode loop (no chunk progress within
        ``watchdog_interval`` while slots are active), fails ONLY the
        in-flight requests with a structured error, and arms the loop's
        epoch-based recovery so it reclaims state and keeps serving. Also
        sweeps queue-wait deadlines so queued requests expire even when the
        loop is wedged."""
        interval = float(self._watchdog_interval)
        tick = max(0.01, interval / 4.0)
        try:
            while not self._stopped:
                await asyncio.sleep(tick)
                self._expire_pending()
                if (
                    self._loop_task is None
                    or self._loop_task.done()
                    or self.active_slots == 0
                ):
                    # idle (or the loop drained between requests): nothing to
                    # supervise. Stay alive — exiting here would race
                    # _ensure_loop's done() check on the next request and
                    # leave that request unsupervised.
                    self._last_progress = time.monotonic()
                    continue
                disp = self._dispatching
                if disp is not None and disp[2] is not None and (
                    time.monotonic() - disp[2]
                    < _DISPATCH_GRACE_INTERVALS * interval
                ):
                    # a dispatch call is mid-flight in its worker thread:
                    # first-use XLA compiles run inside that call and can
                    # legitimately take many seconds. The grace is BOUNDED
                    # at 10x the interval — a dispatch wedged past that
                    # (lock deadlock, hung inline backend) is a stall, not
                    # a compile. A device hang surfaces at the wait for the
                    # launch's results, where no grace applies: a decode
                    # chunk's retire sync and a ragged step's read both
                    # wait in a worker (the ragged step drops its dispatch
                    # start once the launch is enqueued), so this task
                    # keeps running and trips one interval after the last
                    # progress.
                    continue
                if time.monotonic() - self._last_progress > interval:
                    self._watchdog_trip(interval)
        except asyncio.CancelledError:
            return

    def _watchdog_trip(self, interval: float) -> None:
        if faults.active():
            # yield-point seam: a trip is about to bump the epoch and fail
            # the in-flight batch (chaos + interleaving-explorer boundary)
            faults.fire(
                "engine.watchdog",
                requests=[r for r in self._slot_req if r is not None],
            )
        self.counters["watchdog_trips"] += 1
        self._recovering = True
        self._recover_epoch += 1
        err = EngineStuckError(
            "decode loop made no progress for {:.1f}s; failing in-flight "
            "requests and recovering".format(interval)
        )
        for slot, request in enumerate(self._slot_req):
            if request is not None:
                request.error = err
                request.out_queue.put_nowait(_FINISHED)
                self._slot_req[slot] = None
                self._release_guided(slot, request)
                # pool pages deliberately NOT freed here: a worker thread may
                # be mutating the pool mid-dispatch; the loop reclaims them at
                # the next safe boundary (_finish_recovery)
        self._last_progress = time.monotonic()

    async def _finish_recovery(self) -> None:
        """After a stale-epoch dispatch returned (or raised): discard the
        whole in-flight pipeline, reclaim freed slots' pages and report
        ready again. DEFERRED while a dispatch worker is still mid-call —
        its device program may still be writing the very pages this would
        free; the dispatch leg (or the step-failure handler) completes
        recovery once it lands, and a dispatch wedged forever correctly
        keeps the engine not-ready instead of freeing pages under it."""
        if self._dispatching is not None:
            return
        await self._discard_pipeline()
        if self.paged_cache is not None or self.state_cache is not None:
            for slot in range(self.max_batch):
                if self._slot_req[slot] is None and slot not in self._admitting:
                    self._release_cache_slot(slot)
        self._recovering = False
        self._last_progress = time.monotonic()

    def _fail_slot(self, slot: int, err: BaseException) -> None:
        """Fail one active request with a structured error and reclaim its
        slot/pages/grammar state. Loop-thread-only."""
        request = self._slot_req[slot]
        if request is None:
            return
        request.error = err
        request.out_queue.put_nowait(_FINISHED)
        self._slot_req[slot] = None
        self._release_guided(slot, request)
        self._free_slot_pages(slot)
        self._ledger_audit_request(request, "fail")

    # -- pipelined decode: slot-reuse barrier ---------------------------------

    def _pipeline_barrier(self, slot: int) -> Optional[int]:
        """Newest in-flight (or currently-dispatching) chunk that still
        decodes ``slot`` (None when the pipeline holds no reference)."""
        barrier = None
        for entry in self._inflight:
            if entry.active_mask[slot]:
                barrier = entry.seq
        for flight in self._ragged_flights:
            # a ragged launch in flight writes the pages / the state of
            # every row it carries, prompt chunks included
            if flight.carries(slot):
                barrier = flight.seq
        disp = self._dispatching
        if disp is not None and disp[1][slot]:
            barrier = disp[0]
        return barrier

    def _free_slot_pages(self, slot: int) -> None:
        """Release a freed slot's KV pages — immediately when no in-flight
        chunk still references the slot, otherwise deferred to the retire of
        the newest chunk that does. Until then the slot is also quarantined
        against re-admission: a chunk dispatched before the slot was freed
        still writes its KV region / pages, and a new occupant would receive
        the dead request's leftover tokens at that chunk's retire."""
        barrier = self._pipeline_barrier(slot)
        if barrier is not None:
            self._quarantine_slot(slot, barrier)
            return
        self._release_cache_slot(slot)

    def _release_cache_slot(self, slot: int) -> None:
        """Give a batch row's sequence state back to its cache: the slot's
        pages to the page pool and the state slot (zeroed when its next
        owner's first launch starts at position 0). The dense cache keeps
        nothing per row."""
        if self.paged_cache is not None:
            self.paged_cache.pool.free(slot)
        if self.state_cache is not None:
            # alone, or beside the pages: a row's pages and its state slot
            # never outlive each other
            self.state_cache.free(slot)

    def _quarantine_slot(self, slot: int, barrier: int) -> None:
        """Defer a freed slot's page release to the retire of in-flight
        chunk ``barrier`` (the declared acquire of the ``slot.quarantine``
        protocol: _release_quarantine / the pipeline-discard paths are its
        releases, and the ownership ledger audits the pairing — a slot
        stuck in quarantine at drain is a lost free). Loop-thread only."""
        self._quarantine[slot] = barrier
        if self._ledger is not None:
            lifecycle_ledger.acquire("slot.quarantine", key=slot,
                                     domain=self)

    def _release_quarantine(self, retired_seq: int) -> None:
        """Retire point: slots whose barrier has passed become reusable and
        their deferred page frees execute (loop-thread only)."""
        for slot, barrier in list(self._quarantine.items()):
            if barrier <= retired_seq:
                del self._quarantine[slot]
                if self._ledger is not None:
                    lifecycle_ledger.release("slot.quarantine", key=slot,
                                             domain=self, all_of_key=True)
                if (
                    self._slot_req[slot] is None
                    and slot not in self._admitting
                ):
                    self._release_cache_slot(slot)

    async def _discard_pipeline(self) -> None:
        """Drop every in-flight chunk and the device-resident chains
        (watchdog recovery / batch-wide step failure: the queued results are
        stale or poisoned). Deferred frees execute now — after waiting out
        the discarded chunks' DEVICE work: an async-dispatched chunk may
        still be writing its slots' pages, and freeing them under that
        write would hand corrupted pages to the next admission (the same
        hazard the quarantine barrier covers on the normal path). The wait
        runs in a worker thread — blocking the event loop on a wedged
        device would freeze /ready, admissions and the watchdog itself.
        The host mirrors become the source of truth for the next dispatch."""
        dropped = list(self._inflight)
        self._inflight.clear()
        flights = list(self._ragged_flights)
        self._ragged_flights.clear()
        pending = list(self._quarantine)
        self._quarantine.clear()
        if self._ledger is not None:
            for slot in pending:
                lifecycle_ledger.release("slot.quarantine", key=slot,
                                         domain=self, all_of_key=True)
        self._reset_device_chains()
        if (self.paged_cache is not None and dropped) or flights:
            await asyncio.to_thread(self._wait_chunks, dropped + flights)
        # prompts whose chunks rode the dropped ragged launches and that
        # are still served go back to where the oldest of them found them
        self._ragged_rollback([flight.plan for flight in flights])
        for slot in pending:
            if (
                self._slot_req[slot] is None
                and slot not in self._admitting
            ):
                self._release_cache_slot(slot)

    @staticmethod
    def _wait_chunks(entries) -> None:
        """Worker-thread wait for discarded chunks' device programs (their
        pool writes complete with the same program that produces tokens)."""
        for entry in entries:
            try:
                if entry.chunk is not None:
                    jax.block_until_ready(entry.chunk)
            except Exception:
                pass  # failed execution: nothing more will be written

    def _reset_device_chains(self) -> None:  # tpuserve: ignore[TPU501] pipeline drained/discarded: no dispatch worker is live when the loop resets the chains
        """Forget the device-resident token/DFA chains; the next dispatch
        re-uploads from the host mirrors."""
        self._next_token_dev = None
        self._gstate_dev = None
        self._slot_overrides[:] = False

    async def _handle_step_failure(self, ex: BaseException, epoch: int) -> None:
        """A decode dispatch raised. Fail the affected request(s) and keep
        the loop alive — one poisoned step must not kill the engine."""
        if epoch != self._recover_epoch:
            # the watchdog already failed this batch while the dispatch was
            # stuck; nothing left to fail — just reclaim
            await self._finish_recovery()
            return
        if is_hbm_oom(ex):
            # device allocator poisoned: wrapping in a RequestError would
            # route this away from the router's crash-and-restart policy —
            # let the loop die with the ORIGINAL error (consumers see it
            # verbatim; the generic handler then os._exit(1)s the process)
            raise ex
        self.counters["step_failures"] += 1
        target = getattr(ex, "request", None)
        if target is not None:
            # per-request poison (fault injection / host-side attribution):
            # isolate the blast radius to that single request
            for slot, request in enumerate(self._slot_req):
                if request is target:
                    self._fail_slot(
                        slot,
                        EngineStepError(
                            "decode step failed for this request: {}".format(ex)
                        ),
                    )
                    break
            return
        # batch-wide failure: every in-flight request's device state is
        # suspect — discard the whole pipeline (queued chunks chain off the
        # poisoned buffers), fail all requests with a structured error, then
        # reset what the failed dispatch may have consumed (donated buffers)
        await self._discard_pipeline()
        err = EngineStepError("decode step failed: {}".format(ex))
        for slot, request in enumerate(self._slot_req):
            if request is not None:
                self._fail_slot(slot, err)
        if self._prefill_jobs:
            # a batch-wide ragged failure poisons the very launch the jobs'
            # chunks rode — their KV progress is suspect; fail them too
            self._abort_ragged_jobs(err)
        self._reset_device_state()
        self._last_progress = time.monotonic()

    def _reset_device_state(self) -> None:
        """Best-effort rebuild of donated-through device buffers after a
        failed dispatch (a jit error after donation leaves them deleted)."""
        try:
            if self.cache is not None and any(
                getattr(v, "is_deleted", lambda: False)()
                for v in self.cache.values()
            ):
                self.cache = self.bundle.init_cache(
                    self.max_batch, self.max_seq_len + self._cache_slack
                )
                if self._cache_sharding is not None:
                    self.cache = {
                        k: jax.device_put(v, self._cache_sharding[k])
                        for k, v in self.cache.items()
                    }
            if self.state_cache is not None:
                # every live sequence's state went with the donated pools:
                # their requests were failed by the step-failure path
                self.state_cache.reinit_if_lost()
        except Exception:
            pass  # recovery is best-effort; the next dispatch surfaces it

    def _expire_pending(self) -> None:
        """Fail queued requests whose queue-wait or total deadline elapsed.
        Runs on the loop thread (each iteration) and from the watchdog (so
        queued requests expire even while the loop is wedged)."""
        queue = self._pending.requests()
        if not queue:
            return
        now = time.monotonic()
        for request in queue:
            if request.cancelled or request.error is not None:
                continue
            err = None
            if (
                request._queue_deadline is not None
                and now > request._queue_deadline
            ):
                self.counters["deadline_queue"] += 1
                err = DeadlineExceededError(
                    "request spent its queue-wait budget before admission",
                    stage="queue",
                )
            elif request._deadline is not None and now > request._deadline:
                self.counters["deadline_total"] += 1
                err = DeadlineExceededError(
                    "request budget elapsed while queued", stage="total"
                )
            if err is not None:
                request.error = err
                request.cancelled = True  # admission pop skips it
                request.out_queue.put_nowait(_FINISHED)

    def _deadline_error_at_commit(
        self, request: GenRequest
    ) -> Optional[BaseException]:
        """TTFT/total deadline check right before the slot commit (the
        prefill may have been slow or the ready queue backed up)."""
        now = time.monotonic()
        if (
            request._ttft_deadline is not None
            and request.first_token_at is None
            and now > request._ttft_deadline
        ):
            self.counters["deadline_ttft"] += 1
            return DeadlineExceededError(
                "no first token within the ttft budget", stage="ttft"
            )
        if request._deadline is not None and now > request._deadline:
            self.counters["deadline_total"] += 1
            return DeadlineExceededError(
                "request budget elapsed during admission", stage="total"
            )
        return None

    def _bucket_for(self, n: int) -> int:
        if faults.active():
            try:
                # chaos seam: SKIP the bucketizer — raw per-request lengths
                # become prefill compile keys, the exact shape-drift defect
                # the compile sentry exists to catch (its self-test arms
                # this point and proves the post-fence compile is caught)
                faults.fire("engine.compile.bucket")
            except faults.InjectedFault:
                return max(1, n)
        for b in self._buckets:
            if n <= b:
                return b
        return self.max_seq_len

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _next_rng(self):
        with self._rng_lock:  # called from the loop thread AND prefill workers
            self._rng, sub = jax.random.split(self._rng)
        return sub

    def score_prompt(
        self, prompt_ids: List[int], adapter: Optional[str] = None
    ) -> List[dict]:
        """Per-token prompt logprob entries (same shape as
        GenRequest.logprob_entries) for positions 1..n-1 — the first token
        has no conditional. Serves OpenAI completions ``echo`` +
        ``logprobs``; ``adapter`` selects the same LoRA the generation uses
        so prompt and generated logprobs come from ONE model. Pads to the
        prefill bucket (causal attention keeps right padding from touching
        real positions) so traces stay bounded; read-only on params, safe
        alongside decode dispatches."""
        n = len(prompt_ids)
        if n < 2:
            return []
        bucket = self._bucket_for(n)
        row = np.zeros((1, bucket), np.int32)
        row[0, :n] = prompt_ids
        lora_idx = (
            jnp.full((1,), self._adapter_index.get(adapter or "", 0), jnp.int32)
            if self._lora_enabled
            else None
        )
        # _score_prompt_jit is declared "lazy" in __compile_keys__: one
        # bounded compile per bucket on first echo+logprobs use, exempt
        # from the strict post-fence rule (the sentry still counts it)
        with self._sentry_scope("score", lazy=True):
            chosen, rank, top_id, top_lp = self._score_prompt_jit(
                self.params, jnp.asarray(row), lora_idx
            )
        chosen = np.asarray(chosen)
        rank = np.asarray(rank)
        top_id = np.asarray(top_id)
        top_lp = np.asarray(top_lp)
        return [
            {
                "id": int(prompt_ids[i + 1]),
                "logprob": float(chosen[i]),
                "rank": int(rank[i]),
                "top_ids": top_id[i].tolist(),
                "top_logprobs": top_lp[i].tolist(),
            }
            for i in range(n - 1)
        ]

    def _wake_loop(self) -> None:
        if self._wake is not None:
            self._wake.set()

    def _prefill_device(self, request: GenRequest):
        """Device side of admission: prefill the prompt, sample the first
        token. Runs in a worker thread CONCURRENTLY with decode chunks — it
        touches no slot state, so decode throughput does not stall while a
        long prompt prefills. The cheap commit happens on the loop thread at
        the next chunk boundary (_commit_admission)."""
        with self._sentry_scope("prefill", prompt_len=len(request.prompt_ids)), \
                jax.profiler.TraceAnnotation("engine.admit"):
            return self._prefill_device_inner(request)

    def _prefill_device_inner(self, request: GenRequest):
        if faults.active():
            # chaos seam: delayed prefill (deadline tests) or a raised
            # admission failure (isolated by _admission_task's except path)
            faults.fire("engine.prefill", request=request)
        # disaggregated ship-hit accounting (docs/disaggregation.md): book
        # the shipped prefix's fate before the lookup consumes it
        self._count_ship_outcome(request)
        ids = request.prompt_ids
        use_ring = (
            self._prefill_ring_jit is not None
            and self._long_threshold < len(ids) <= self._long_cap
        )
        use_pp = False
        if (
            not use_ring
            and self._prefill_pipeline_jit is not None
            and len(ids) > self._long_threshold
        ):
            pp_bucket = -(-len(ids) // self._pp_chunk) * self._pp_chunk
            # only pipeline when there are at least as many microbatches as
            # stages — below that the fill/drain bubble dominates and the
            # plain bucketed prefill is faster (m=1 would be fully serial)
            use_pp = (
                pp_bucket <= self.max_seq_len
                and pp_bucket // self._pp_chunk >= self._pp
            )
        if use_ring:
            # sp-sharded long prefill: pad to a multiple of the sp axis,
            # never past the sp-divisible cap
            bucket = min(
                -(-len(ids) // self._long_step) * self._long_step,
                self._long_cap,
            )
        elif use_pp:
            bucket = pp_bucket  # pipeline pads to whole sequence chunks
        else:
            bucket = self._bucket_for(len(ids))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, : len(ids)] = ids
        seq_lens = jnp.asarray([len(ids)], jnp.int32)
        # prefill KV sized to the bucket: one cached, never-mutated template per
        # bucket (prefill reads only its shape; re-allocating [L,1,bucket,H,D]
        # per admission would put hundreds of MB of HBM traffic on the
        # admission path for 8B-class models)
        template_len = max(bucket, 1)
        with self._template_lock:
            template = self._prefill_templates.get(template_len)
            if template is None:
                template = self.bundle.init_cache(1, template_len)
                self._prefill_templates[template_len] = template
        lora_i = self._slot_lora(request)
        lora_arr = jnp.asarray([lora_i], jnp.int32) if self._lora_enabled else None
        # automatic prefix caching: a stored block-aligned prefix of this
        # prompt (same adapter) skips straight to its remainder
        # single-dispatch interactive admissions skip the prefill gate's
        # pacing (a first-token-critical lone enqueue must not park behind
        # a batch resume's permit — docs/slo_scheduling.md); multi-segment
        # interactive trains stay paced like any other
        gate_bypass = request.priority == "interactive"
        prefix_result = None
        if self._prefix is not None and not use_ring:
            prefix_result = self._prefix_admission(
                ids, lora_arr, lora_i, gate_bypass, request
            )
        c = self._chunked
        # the chunked mini cache must be a multiple of C: a final chunk
        # overflowing the bucket would be CLAMPED backward by
        # dynamic_update_slice, silently overwriting earlier prompt K/V
        chunk_bucket = -(-bucket // c) * c if c else 0
        use_chunked = (
            prefix_result is None
            and not use_ring
            and not use_pp
            and c > 0
            and len(ids) > c
            and chunk_bucket <= self.max_seq_len
        )
        if use_chunked and chunk_bucket != bucket:
            bucket = chunk_bucket
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, : len(ids)] = ids
        if prefix_result is not None:
            last_logits, mini_cache = prefix_result
        elif use_chunked:
            # incremental prefill: C-token segments attend over the cache so
            # far; the template is read (not donated) on the first segment
            with self._template_lock:
                template = self._prefill_templates.get(bucket)
                if template is None:
                    template = self.bundle.init_cache(1, bucket)
                    self._prefill_templates[bucket] = template
            cache = template
            last_logits = None
            n_segs = -(-len(ids) // c)
            for seg_i, s in enumerate(range(0, len(ids), c)):
                seg = ids[s : s + c]
                seg_tokens = np.zeros((1, c), np.int32)
                seg_tokens[0, : len(seg)] = seg
                fn = (
                    self._prefill_chunk_first_jit
                    if seg_i == 0
                    else self._prefill_chunk_jit
                )
                if self._prefill_gate is not None:
                    # pace the segment train against decode chunks so the
                    # device queue interleaves instead of bursting (chunked
                    # admissions are multi-segment by construction: no
                    # single-dispatch bypass here)
                    self._prefill_gate.acquire()
                request._prefill_launches += 1
                last_logits, cache = fn(
                    self.params,
                    jnp.asarray(seg_tokens),
                    jnp.asarray([s], jnp.int32),
                    jnp.asarray([len(seg) - 1], jnp.int32),
                    cache,
                    with_logits=(seg_i == n_segs - 1),
                    lora_idx=lora_arr,
                )
            mini_cache = cache
        else:
            if use_ring:
                prefill_fn = self._prefill_ring_jit
            elif use_pp:
                prefill_fn = self._prefill_pipeline_jit
            else:
                prefill_fn = self._prefill_jit
            if self._prefill_gate is not None:
                self._prefill_gate.acquire(bypass=gate_bypass)
            request._prefill_launches += 1
            last_logits, mini_cache = prefill_fn(
                self.params, jnp.asarray(tokens), seq_lens, template, lora_arr
            )
        if self._prefix is not None and not use_ring:
            # make this prompt's prefix available to future admissions
            self._prefix.store(
                ids, lora_i,
                {k: v for k, v in mini_cache.items() if k != "length"},
            )
        first_id, first_lp = self._first_token_from_logits(request, last_logits)
        return first_id, mini_cache, first_lp

    def _first_token_from_logits(self, request: GenRequest, last_logits):
        """The legacy admission worker's first token, from its prefill
        logits [1, V]: the ragged scheduler's first-token program
        (``_first_token_jit``) over that one row, and the same host half —
        the two paths sampling through ONE program is what makes their
        first tokens byte-identical. The slot's device rows are reset at
        the commit (``_commit_admission``: the state belongs to the loop
        thread's chunks), from the rows staged here."""
        op = self._first_token_ops(request)
        (ids, lp, _), request._extras_rows = self._sample_first_token(
            op, 0, last_logits, None
        )
        return self._first_token_commit(
            op, jax.tree.map(np.asarray, (ids, lp))
        )

    def _prefix_bucket(self, prefix_len: int, n_tokens: int) -> Optional[int]:
        """Mini-cache bucket covering the prefix plus the tail's segment
        windows, from the bounded engine bucket set — minting a size per
        (prefix_len, remainder) combination would permanently cache a fresh
        multi-hundred-MB template (8B-class) and recompile prefill_chunk for
        every new shape, turning "hits" into compile storms and an HBM
        leak. None when no bucket fits."""
        c2 = self._prefix_chunk
        remainder = n_tokens - prefix_len
        required = prefix_len + -(-remainder // c2) * c2
        bucket = self._bucket_for(required)
        if bucket < required or bucket > self.max_seq_len:
            return None
        return bucket

    def _prefill_tail(self, cache, ids, prefix_len: int, lora_arr,
                      gate_bypass: bool = False, request=None):
        """Prefill only the non-shared tail of ``ids`` through the donating
        prefill_chunk, attending over the prefix KV already in ``cache``.
        The cache is owned by this admission, so every segment may donate it
        (unlike the cold chunked path, whose first segment reads the shared
        template). Returns (last_logits, cache)."""
        c2 = self._prefix_chunk
        last_logits = None
        starts = list(range(prefix_len, len(ids), c2))
        # the single-dispatch bypass only applies to a one-segment tail: a
        # longer train is paced exactly like a chunked cold prefill
        gate_bypass = gate_bypass and len(starts) == 1
        if request is not None:
            request._prefill_launches += len(starts)
        for si, s in enumerate(starts):
            seg = ids[s : s + c2]
            seg_tokens = np.zeros((1, c2), np.int32)
            seg_tokens[0, : len(seg)] = seg
            if self._prefill_gate is not None:
                self._prefill_gate.acquire(bypass=gate_bypass)
            last_logits, cache = self._prefill_chunk_jit(
                self.params,
                jnp.asarray(seg_tokens),
                jnp.asarray([s], jnp.int32),
                jnp.asarray([len(seg) - 1], jnp.int32),
                cache,
                with_logits=(si == len(starts) - 1),
                lora_idx=lora_arr,
            )
        return last_logits, cache

    def _prefix_admission(self, ids, lora_arr, lora_i,
                          gate_bypass: bool = False, request=None):
        """Dense prefix-cache hit path: assemble the tree's block run into a
        mini cache and prefill only the remainder through prefill_chunk.
        Returns (last_logits, mini_cache) or None (miss / doesn't fit)."""
        hit = self._prefix.lookup(ids, lora_i)
        if hit is None:
            return None
        prefix_len = hit["len"]
        bucket = self._prefix_bucket(prefix_len, len(ids))
        if bucket is None:
            self._prefix.uncount_hit(hit)  # recomputed cold: not a real hit
            return None
        with self._template_lock:
            template = self._prefill_templates.get(bucket)
            if template is None:
                template = self.bundle.init_cache(1, bucket)
                self._prefill_templates[bucket] = template
        cache = self._assemble_prefix_jit(
            template, hit["bufs"], jnp.asarray(prefix_len, jnp.int32)
        )
        return self._prefill_tail(cache, ids, prefix_len, lora_arr,
                                  gate_bypass, request)

    def _release_prefix_hit(self, request: GenRequest) -> None:
        """Admission failed/dropped before its slot commit: drop the pin the
        paged lookup took on the shared pages. No-op otherwise."""
        hit, request._prefix_hit = request._prefix_hit, None
        if hit is not None and self._prefix is not None:
            with lifecycle_ledger.owner(
                lifecycle_ledger.request_tag(request)
            ):
                self._prefix.release(hit)

    def _release_resume_pin(self, request: GenRequest) -> None:
        """Drop the eviction pin a preemption took on the request's stored
        history (prefix_cache.pin_run). Called once the resume's admission
        lookup ran (the hit holds its own page pins from there) or when the
        request leaves the queue without admission (shed, expired,
        cancelled, engine stop). No-op otherwise."""
        pin, request._resume_pin = request._resume_pin, None
        if pin is not None and self._prefix is not None:
            if faults.active():
                try:
                    # chaos seam: an injected raise models a teardown bug
                    # that drops the handle WITHOUT running the unpin — a
                    # lost free no page audit can see (node pins are not
                    # page refcounts). The armed ownership ledger must
                    # name it at the drain audit (tests/test_chaos.py).
                    faults.fire("engine.ledger.leak", request=request)
                except faults.InjectedFault:
                    return
            self._prefix.unpin_run(pin)

    def _commit_admission(self, request: GenRequest, slot: int, first_id: int, mini_cache, first_lp=None) -> None:
        """Loop-thread-only: route the prefilled KV into the dense cache's
        slot row and activate the slot. Never runs concurrently with a
        decode chunk."""
        self.cache = self._insert_jit(
            self.cache,
            {k: v for k, v in mini_cache.items() if k != "length"},
            jnp.asarray(len(request.prompt_ids), jnp.int32),  # tpuserve: ignore[TPU601] a scalar operand (the row's length), never a shape
            slot,
        )
        (bias_row, pmask_row), request._extras_rows = request._extras_rows, None
        if self._request_has_extras(request) or self._counts_dev is not None:
            # the [B, V] state exists as soon as anyone needs it; rows must
            # then be reset on EVERY admission (stale bias/mask from a
            # previous occupant would leak into this request)
            self._ensure_extras_state()
            (
                self._counts_dev,
                self._bias_dev,
                self._pmask_dev,
            ) = self._set_sampling_row_jit(
                self._counts_dev,
                self._bias_dev,
                self._pmask_dev,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(first_id, jnp.int32),
                jnp.asarray(bias_row),
                jnp.asarray(pmask_row),
            )
        self._activate_slot(request, slot, first_id, first_lp)

    def _activate_slot(self, request: GenRequest, slot: int, first_id: int,
                       first_lp=None) -> None:
        """Slot activation shared by the legacy commit and the ragged
        scheduler's finishing-chunk commit (whose KV is already in place —
        it was written slot-resident, chunk by chunk): per-slot sampling /
        extras / guided mirrors, admission bookkeeping, and the first
        token's emission. Host bookkeeping only: the slot's device rows
        (counts / bias / prompt mask) were reset by whoever sampled the
        first token's id onto them (the ragged launch's first-token
        program, the legacy ``_commit_admission``)."""
        self._slot_req[slot] = request
        # admission-drain bookkeeping: the Retry-After hint derives from the
        # rate these commits land at
        self._admit_times.append(time.monotonic())
        self._admit_count += 1
        request._gen_ids = []  # resume leg: history now lives in prompt_ids
        self._next_token[slot] = first_id
        if self._tokbuf is not None:
            # speculation history invariant: row holds the prompt plus every
            # emitted token; length+1 tokens are known (pending included)
            row = np.zeros(self._tokbuf.shape[1], np.int32)
            ids = request.prompt_ids[: self._tokbuf.shape[1] - 1]
            row[: len(ids)] = ids
            row[len(ids)] = first_id
            self._tokbuf[slot] = row
        self._stage_slot_config(request, slot)
        self._slot_config_of[slot] = None     # the next owner stages anew
        if request._guided_key is not None:
            # transfer the grammar ref from the request to the slot; the
            # first token may already have completed the match (terminal)
            self._slot_guided_key[slot] = request._guided_key
            request._guided_key = None
            self._gstate[slot] = request._gstate0
        # mark the slot so the next dispatch merges the host value into the
        # device-chained token/DFA vectors
        self._slot_overrides[slot] = True
        self._emit(slot, first_id, first_lp)

    def _stage_slot_config(self, request: GenRequest, slot: int) -> None:
        """The slot's rows of the host's sampling mirrors, written from its
        request (loop thread): by the commit, or earlier by the plan of a
        launch that goes behind the one ending the request's prompt and
        carries it as a decode row (the slot is the request's since its
        admission, and no launch reads the rows of a slot it does not
        decode). Once a request: the cached device constants are dropped
        only when the rows changed hands."""
        if self._slot_config_of[slot] is request:
            return
        self._slot_config_of[slot] = request
        self._temperature[slot] = request.temperature
        self._top_k[slot] = request.top_k
        self._top_p[slot] = request.top_p
        self._lora_slots[slot] = self._slot_lora(request)
        self._presence[slot] = request.presence_penalty
        self._frequency[slot] = request.frequency_penalty
        self._repetition[slot] = request.repetition_penalty or 1.0
        # mask BEFORE the int64 store: JSON ints are unbounded and a seed
        # >= 2**63 would overflow the numpy slot array on the loop thread
        self._seeds[slot] = (
            -1 if request.seed is None else int(request.seed) & 0x7FFFFFFF
        )
        self._min_tokens[slot] = min(
            max(0, int(request.min_tokens or 0)), 2**31 - 1
        )
        self._stop_rows[slot] = self._request_stop_row(request)
        self._slot_extra[slot] = self._request_has_extras(request)
        # unguided until the commit says otherwise: a launch that still
        # carried the slot's last owner may have written its state back
        self._gstate[slot] = -1
        # fresh per-slot config: invalidate the cached device constants
        self._sampling_dev = None
        self._extras_dev = None

    async def _admission_task(self, request: GenRequest, slot: int) -> None:
        """Background prefill for one request; reserves `slot` via
        self._admitting until committed or failed."""
        request._job_at = _clock()
        try:
            first_id, mini_cache, first_lp = await asyncio.to_thread(
                self._prefill_device, request
            )
        except Exception as ex:
            # a failed admission fails only its own request
            self._release_resume_pin(request)
            self._deref_guided_request(request)
            self._release_prefix_hit(request)
            request.error = ex
            request.out_queue.put_nowait(_FINISHED)
            self._admitting.discard(slot)
            self._wake_loop()
            return
        # the prefill's prefix lookup ran (hit or miss): the preemption-era
        # eviction pin on the stored history has done its job
        self._release_resume_pin(request)
        if self._stopped:
            self._deref_guided_request(request)
            self._release_prefix_hit(request)
            request.error = EngineUnavailableError("engine stopped")
            request.out_queue.put_nowait(_FINISHED)
            self._admitting.discard(slot)
            return
        await self._ready.put((request, slot, first_id, mini_cache, first_lp))
        self._wake_loop()
        if self._loop_task is None or self._loop_task.done():
            # loop died between prefill and hand-off: nobody will commit —
            # fail anything stranded in the ready queue (incl. our item)
            self._drain_ready(EngineUnavailableError("engine loop exited"))

    def _emit(self, slot: int, token_id: int, lp: dict | None = None) -> None:
        request = self._slot_req[slot]
        if request is None:
            return
        if request.cancelled:
            # consumer is gone — free the slot (and its KV pages) early
            request.out_queue.put_nowait(_FINISHED)
            self._slot_req[slot] = None
            self._release_guided(slot, request)
            self._free_slot_pages(slot)
            self._ledger_audit_request(request, "cancel")
            return
        if (
            request._deadline is not None
            and time.monotonic() > request._deadline
        ):
            # total budget elapsed mid-decode: structured 408, slot reclaimed
            self.counters["deadline_total"] += 1
            self._fail_slot(
                slot,
                DeadlineExceededError(
                    "request budget elapsed after {} tokens".format(
                        request.produced
                    ),
                    stage="total",
                ),
            )
            return
        if lp is not None and request.logprobs is not None:
            # appended BEFORE the token is queued (see GenRequest contract)
            request.logprob_entries.append(lp)
        request.produced += 1
        if request.priority != "interactive":
            # preemptible lane: track emitted tokens so a preemption can
            # fold them into the resume prompt (docs/slo_scheduling.md)
            request._gen_ids.append(int(token_id))
        if request.first_token_at is None:
            request.first_token_at = time.time()  # client-observable TTFT
            self._observe_first_token(request)
        request.out_queue.put_nowait(token_id)
        stop_ids = request.stop_token_ids or (
            [self.eos_token_id] if self.eos_token_id is not None else []
        )
        total_len = request.prompt_len + request.produced
        if (
            token_id in stop_ids
            or request.produced >= self._effective_max_new(request)
            or total_len >= self.max_seq_len
        ):
            request.out_queue.put_nowait(_FINISHED)
            self._slot_req[slot] = None
            self._release_guided(slot, request)
            try:
                # chaos seam: an injected raise here models a teardown
                # bug that loses the slot's page references — the armed
                # KV sanitizer must then fail the drain check, naming
                # the leaked pages (tests/test_chaos.py)
                if self.paged_cache is not None:
                    faults.fire("engine.release", request=request)
                self._free_slot_pages(slot)  # recycle (or quarantine) pages
            except faults.InjectedFault:
                pass
            self._ledger_audit_request(request, "emit-finish")

    def _observe_first_token(self, request: GenRequest) -> None:
        """The request's stamps into lifecycle_stats()["requests"]: with
        queue_wait_ms (observed at the slot reservation) the three stretches
        add up to ttft_ms for a request that was never preempted. Where its
        prompt rode launches, prefill_ms is cut on the launch timeline into
        first_launch_wait_ms (job opened -> the ``enqueue`` of the first
        launch that carried a chunk of it), prefill_span_ms (-> the ``ready``
        of the launch that carried the last) and first_emit_ms (-> now)."""
        if not request._job_at:
            return  # activated without an admission (a test's direct commit)
        now = _clock()
        h = self._hist_request
        h["admit_ms"].observe((request._job_at - request._slot_at) * 1e3)
        h["prefill_ms"].observe((now - request._job_at) * 1e3)
        h["ttft_ms"].observe((now - request._submitted) * 1e3)
        self._hist_prefill_launches.observe(request._prefill_launches)
        if request._enqueue_at:
            edges = (request._job_at, request._enqueue_at,
                     request._ready_at, now)
            for name, a, b in zip(_PREFILL_STRETCHES, edges, edges[1:]):
                h[name].observe((b - a) * 1e3)

    def _drain_ready(self, err: BaseException) -> None:
        """Fail every completed-but-uncommitted admission (loop is exiting)."""
        while not self._ready.empty():
            request, slot, _first, _cache, _lp = self._ready.get_nowait()
            self._admitting.discard(slot)
            self._deref_guided_request(request)
            self._release_prefix_hit(request)
            request.error = err
            request.out_queue.put_nowait(_FINISHED)

    def _fail_all(self, err: BaseException) -> None:
        """Terminate every active request with `err` (nothing may hang).

        Does NOT touch the page pool: _fail_all can run (via stop()) while a
        worker thread is inside _run_paged_chunk mutating the pool — the loop
        frees all slots itself when it exits (sole-owner point)."""
        for slot, request in enumerate(self._slot_req):
            if request is not None:
                request.error = err
                request.out_queue.put_nowait(_FINISHED)
                self._slot_req[slot] = None
                self._release_guided(slot, request)

    def _spec_eligible_mask(self, active_mask: np.ndarray):
        """(greedy_mask, sampled_mask): greedy_mask — slots the greedy
        verify chain reproduces exactly (temperature 0, no sampling extras,
        no grammar constraint, no logprob tracking); sampled_mask — plain
        temperature>0 slots eligible for rejection-sampled speculation
        (same exclusions; gated by engine.spec_sampling). Everything else
        takes the sampled position-0 path inside the same dispatch."""
        lp_free = np.array(
            [r is None or r.logprobs is None for r in self._slot_req]
        )
        clean = (
            active_mask
            & ~self._slot_extra
            & (self._gstate < 0)
            & lp_free
        )
        greedy = clean & (self._temperature == 0.0)
        sampled = (
            clean & (self._temperature > 0.0)
            if self._spec_sampling
            else np.zeros_like(greedy)
        )
        return greedy, sampled

    def _spec_common_args(self, active_mask, spec_mask, sspec_mask, sampling):
        """Argument tail of the dense spec dispatch."""
        use_extras = self._extras_active(active_mask)
        use_guided = bool(np.any(self._gstate[active_mask] >= 0))
        gtables = self._guided_device_tables() if use_guided else None
        args = (
            jnp.asarray(active_mask),
            jnp.asarray(spec_mask),
            jnp.asarray(sspec_mask),
            sampling,
            self._next_rng(),
            # host mirrors snapshot-COPIED at the thread handoff: the spec
            # dispatch runs on a worker thread and jnp.asarray is zero-copy
            # aliasing on CPU (tpuserve-analyze TPU502; same rationale as
            # _chain_input)
            jnp.asarray(self._lora_slots.copy()) if self._lora_enabled else None,
            self._batch_extras() if use_extras else None,
            self._counts_dev if use_extras else None,
            self._pmask_dev if use_extras else None,
            gtables,
            jnp.asarray(self._gstate.copy()) if gtables is not None else None,
        )
        return args, use_extras, gtables

    def _spec_commit_state(self, tokbuf, new_counts, gstate_out, lp,  # tpuserve: ignore[TPU501] serial spec path: the loop is suspended awaiting this worker call and commits land at loop tops, so no loop-thread mutator runs concurrently
                           use_extras, gtables):
        if use_extras:
            self._counts_dev = new_counts
        if gtables is not None:
            # np.array (copy): asarray would alias the immutable device
            # buffer and commit/release paths write rows in place
            self._gstate = np.array(gstate_out)
        # same copy rationale: _commit_admission writes tokbuf rows in place
        self._tokbuf = np.array(tokbuf)
        return tuple(np.asarray(a) for a in lp) if lp is not None else None

    def _dispatch_spec_chunk(self, active_mask: np.ndarray, spec_mask,
                             sspec_mask, sampling, want_lp: bool = False):
        """Worker-thread side of a dense speculative dispatch: run the fused
        draft-verify rounds and read back (gs [R,B,k+1], accs [R,B],
        pending [B], lp). The host token buffer round-trips through the
        executable so the on-device n-gram proposer sees each slot's full
        history."""
        if faults.active():
            faults.fire(
                "engine.decode.stall",
                requests=[r for r in self._slot_req if r is not None],
            )
        tail, use_extras, gtables = self._spec_common_args(
            active_mask, spec_mask, sspec_mask, sampling
        )
        (tokbuf, pending, self.cache, gs, accs, new_counts, gstate_out,
         lp) = self._spec_chunk_jit(
            self.params,
            # copies: worker-thread upload of loop-owned host mirrors
            # (tpuserve-analyze TPU502)
            jnp.asarray(self._tokbuf.copy()),
            jnp.asarray(self._next_token.copy()),
            self.cache,
            *tail,
            want_lp=want_lp,
            with_sspec=bool(sspec_mask.any()),
        )
        lp_np = self._spec_commit_state(
            tokbuf, new_counts, gstate_out, lp, use_extras, gtables
        )
        return np.asarray(gs), np.asarray(accs), np.asarray(pending), lp_np

    # -- ragged scheduler: token-budget admission (docs/ragged_attention.md) --

    def _ragged_spec_wanted(self, active_mask: np.ndarray) -> bool:
        """Spec-as-row routing (docs/ragged_attention.md): with speculation
        on, eligible decode slots ride the ragged scheduler's mixed
        launches as q=k+1 verify rows — the legacy serial scan
        (_dispatch_spec_chunk) and its pipeline drain never run under the
        ragged scheduler. Brownout stage 1+ parks speculation exactly like
        the pipelined path: the verify slack and the k wasted positions
        per reject are headroom an overloaded engine no longer has."""
        if not (self._ragged and self._speculation) or not active_mask.any():
            return False
        if self._brownout is not None and self._brownout.stage >= 1:
            return False
        greedy, sampled = self._spec_eligible_mask(active_mask)
        return bool(greedy.any() or sampled.any())

    def _ngram_draft_rows(self, slots, hists) -> "np.ndarray":
        """Host-side n-gram proposal for spec-verify rows ([len(slots), k]
        draft tokens), mirroring the device proposer the legacy serial scan
        ran in-jit: match the history's n-token tail against every earlier
        window of the slot's token buffer, continue from the LAST match;
        no-match rows draft the tail's last token repeated (a reject still
        emits the bonus token). Host-side because the drafts become ragged
        ROW CONTENT — they must be known before the launch is laid out."""
        n_, k_ = self._spec_ngram, self._spec_k
        buf_len = self._tokbuf.shape[1]
        out = np.zeros((len(slots), k_), np.int32)
        for i, (slot, hist) in enumerate(zip(slots, hists)):
            buf = self._tokbuf[slot]
            tail_pos = np.clip(hist - n_ + np.arange(n_), 0, buf_len - 1)
            tail = buf[tail_pos]
            # window must end before the tail starts (a previous
            # occurrence, not the tail matching itself); only the hist
            # tokens actually written participate — the scan is bounded by
            # the generated length, not the buffer capacity (this runs on
            # the loop thread every launch)
            limit = hist - 2 * n_ + 1
            best = -1
            if limit > 0:
                match = np.ones(limit, bool)
                for j in range(n_):
                    match &= buf[j : limit + j] == tail[j]
                idx = np.nonzero(match)[0]
                if idx.size:
                    best = int(idx[-1])
            if best >= 0:
                pos = np.clip(best + n_ + np.arange(k_), 0, buf_len - 1)
                out[i] = buf[pos]
            else:
                out[i] = tail[-1]
        return out

    async def _ragged_admission_task(self, request: GenRequest, slot: int) -> None:
        """Ragged-mode admission: no standalone prefill dispatch — the
        prompt rides the loop's ragged launches as budget-bounded chunk
        rows. Only worker-thread-worthy host prep runs here (a grammar
        compile can take seconds); the slot stays reserved via _admitting
        until the final chunk's commit or a failure path releases it."""

        def prep():
            with jax.profiler.TraceAnnotation("engine.admit"):
                if faults.active():
                    # the same chaos seam the legacy admission worker fires
                    # (delay = slow admission, raise = failed admission)
                    faults.fire("engine.prefill", request=request)
                if request.guided is not None:
                    self._ensure_grammar(request)

        try:
            await asyncio.to_thread(prep)
        except Exception as ex:
            self._release_resume_pin(request)
            self._deref_guided_request(request)
            request.error = ex
            request.out_queue.put_nowait(_FINISHED)
            self._admitting.discard(slot)
            self._wake_loop()
            return
        if self._stopped:
            self._release_resume_pin(request)
            self._deref_guided_request(request)
            request.error = EngineUnavailableError("engine stopped")
            request.out_queue.put_nowait(_FINISHED)
            self._admitting.discard(slot)
            return
        await self._ragged_ready.put((request, slot))
        self._wake_loop()
        if self._loop_task is None or self._loop_task.done():
            # loop died between prep and hand-off: nobody will open the job
            self._drain_ragged_ready(
                EngineUnavailableError("engine loop exited")
            )

    def _drain_ragged_ready(self, err: BaseException) -> None:
        """Fail every prepped-but-unopened ragged admission (loop exiting)."""
        while not self._ragged_ready.empty():
            request, slot = self._ragged_ready.get_nowait()
            self._admitting.discard(slot)
            self._release_resume_pin(request)
            self._deref_guided_request(request)
            request.error = err
            request.out_queue.put_nowait(_FINISHED)

    def _start_ragged_job(self, request: GenRequest, slot: int):
        """Loop-thread: open a ragged admission job for a prepped request.
        Paged radix prefix hits map their shared pages into the slot's
        table by reference HERE (zero KV copies; the tail then prefills
        through chunk rows — the prefix-cache tail-chunk path). Dense
        ragged mode skips prefix reuse: there is no mini cache to assemble
        stored buffers into (documented limitation)."""
        pos = 0
        hit = None
        try:
            # disaggregated ship-hit accounting (docs/disaggregation.md)
            self._count_ship_outcome(request)
            if self.cache_mode == "paged" and self._prefix is not None:
                lora_i = self._slot_lora(request)
                with lifecycle_ledger.owner(
                    lifecycle_ledger.request_tag(request)
                ):
                    hit = self._prefix.lookup_pages(
                        request.prompt_ids, lora_i
                    )
                if hit is not None:
                    plen = hit["len"]
                    page_size = self.paged_cache.pool.page_size
                    if (
                        0 < plen < len(request.prompt_ids)
                        and plen % page_size == 0
                    ):
                        # the mapped prefix pages ride the slot's table
                        # from here: _emit/_fail_ragged_job (and the
                        # except arm below) free the slot — cross-function
                        # pairing the ownership ledger audits at drain
                        self.paged_cache.pool.map_shared(  # tpuserve: ignore[TPU701] pages ride the slot table
                            slot, list(hit["pages"]), plen
                        )
                        pos = plen
                        with lifecycle_ledger.owner(
                            lifecycle_ledger.request_tag(request)
                        ):
                            self._prefix.release(hit)
                    else:
                        # whole-prompt or misaligned hit: recompute cold
                        # (at least one tail token must produce logits)
                        with lifecycle_ledger.owner(
                            lifecycle_ledger.request_tag(request)
                        ):
                            self._prefix.release(hit)
                        self._prefix.uncount_hit(hit)
        except Exception as ex:
            self._release_resume_pin(request)
            self._deref_guided_request(request)
            # a raise between the lookup/map_shared above and the job's
            # activation would otherwise strand resources on a slot no job
            # owns (the less-traveled teardown path the ownership ledger
            # flagged): drop the hit's pin — release() is pop-idempotent,
            # so a hit the happy path already released is a no-op — and
            # free the slot (its table is authoritative: a plain free
            # reclaims whatever was mapped, nothing when nothing was)
            if hit is not None:
                with lifecycle_ledger.owner(
                    lifecycle_ledger.request_tag(request)
                ):
                    self._prefix.release(hit)  # tpuserve: ignore[TPU702] release() pops; re-release is a no-op
            self._free_ragged_slot(slot)
            request.error = ex
            request.out_queue.put_nowait(_FINISHED)
            self._admitting.discard(slot)
            return None
        # the prefix lookup ran (hit or miss): the preemption-era eviction
        # pin on the stored history has done its job (legacy parity)
        self._release_resume_pin(request)
        if self.state_cache is not None:
            # the row's state slot changes hands here; it is zeroed by the
            # launch that carries the job's first chunk (row_reset)
            self.state_cache.free(slot)
            self.state_cache.allocate(slot)
        request._job_at = _clock()
        return _RaggedJob(request=request, slot=slot, pos=pos)

    def _free_ragged_slot(self, slot: int) -> None:
        """Reclaim a ragged job's slot pages and state slot."""
        # a failed/cancelled job never seals its draft-ahead stream: the
        # receiver's unsealed assembly stays unconsumable and ages out
        self._kv_draft_ahead.pop(slot, None)
        # at once where no launch in flight carries the row, else when the
        # newest that does retires (the slot-reuse barrier)
        self._free_slot_pages(slot)

    def _fail_ragged_job(self, job: "_RaggedJob",
                         err: Optional[BaseException]) -> None:
        """Fail one in-progress ragged admission (err None = cancelled):
        release its grammar ref and slot pages and unblock its consumer."""
        if job in self._prefill_jobs:  # identity (dataclass eq=False)
            self._prefill_jobs.remove(job)
        self._admitting.discard(job.slot)
        request = job.request
        self._deref_guided_request(request)
        self._release_prefix_hit(request)  # defensive; released at job start
        if err is not None:
            request.error = err
        request.out_queue.put_nowait(_FINISHED)
        self._free_ragged_slot(job.slot)
        self._ledger_audit_request(request, "fail-ragged")

    def _abort_ragged_jobs(self, err: BaseException) -> None:
        for job in list(self._prefill_jobs):
            self._fail_ragged_job(job, err)

    def _sweep_ragged_jobs(self) -> None:
        """Drop cancelled / deadline-expired jobs before planning a step —
        budget spent on a dead admission is budget stolen from live ones."""
        for job in list(self._prefill_jobs):
            request = job.request
            if request.cancelled:
                self._fail_ragged_job(job, None)
                continue
            err = self._deadline_error_at_commit(request)
            if err is not None:
                self._fail_ragged_job(job, err)

    def _effective_token_budget(self) -> int:
        """Ragged admission budget for the NEXT step. Brownout stage >= 3
        re-expresses the legacy prefill gate's ``set_budget(1)`` on the
        token budget: the admission share shrinks to about one minimal
        chunk beside the decode batch, so decode slots drain ahead of new
        admissions (docs/slo_scheduling.md; regression in
        tests/test_scheduler.py)."""
        if self._brownout is not None and self._brownout.stage >= 3:
            return min(
                self._step_token_budget,
                self.max_batch + _RAGGED_BROWNOUT_CHUNK,
            )
        return self._step_token_budget

    def _ragged_ahead(self, flight: dict, budget: int) -> Optional[dict]:
        """Loop thread, launch ``flight`` enqueued and not read back: the
        host state as it WILL stand once it retires, for the plan of the
        launch that goes behind it, or None where the step stays serial
        (docs/ragged_attention.md, "A launch in flight"). Nothing is
        written here.

        What the next launch needs of this one is host arithmetic on its
        plan (a decode row advances by its window, a job by its share, a
        finishing slot becomes a decode row; the pools' lengths already
        moved in its worker) and, for the rows it carried, their pending
        tokens, which stay on the device (``carried``). A row that ends at
        this launch for a reason the host knows (``max_new_tokens``,
        ``max_seq_len``) is not planned; one that ends for a reason only
        the device knows rides the next launch for nothing (a surplus row,
        dropped at that launch's retire).

        Serial, because the plan cannot be written ahead: a verify row
        (the accepted length is the device's, and drafts come from the
        host's token history: speculation keeps the step serial while it
        is on), a guided prompt that ends here (the host walks its DFA
        over the first token), a row the worker dropped for want of pages,
        a recovery epoch that moved, a KV transport (ships read the pools
        at the commit). Serial, because a request arriving now could have
        used the next launch: neither (a) the prompts in the engine hold at
        least the tokens the budget leaves after the decode rows, nor (b)
        every slot is taken and nothing waits for admission; or a request
        of a higher class than the backlog's waits or was just admitted.
        And the next step is not this one's to plan where a paged engine
        has no prompt left: the pipelined chunk runs its decode phases."""
        if (
            flight["epoch"] != self._recover_epoch
            or flight["exhausted"] or flight["failed_jobs"]
            or flight["spec_mask"].any() or flight["sspec_mask"].any()
            or self._kv_transport is not None
            or (
                self._speculation
                and (self._brownout is None or self._brownout.stage < 1)
            )
        ):
            return None
        reqs: List[Optional[GenRequest]] = list(self._slot_req)
        produced = self._produced_counters()
        carried = np.zeros(self.max_batch, bool)
        staged = []

        def stays(request, made: int) -> bool:
            # _emit's endings that the host can tell before the token
            return not (
                request.cancelled
                or made >= self._effective_max_new(request)
                or request.prompt_len + made >= self.max_seq_len
            )

        for slot in np.nonzero(flight["decode_mask"])[0]:
            slot = int(slot)
            request = reqs[slot]
            if request is None or request is not flight["row_req"][slot]:
                reqs[slot] = None
                continue
            produced[slot] += int(flight["row_steps"][slot])
            if stays(request, int(produced[slot])):
                carried[slot] = True
            else:
                reqs[slot] = None
        pos = {}
        backlog = 0
        ranks = []
        takes = {id(job): take for job, take in flight["shares"]}
        for job in self._prefill_jobs:
            request = job.request
            pos[id(job)] = job.pos + takes.get(id(job), 0)
            left = len(request.prompt_ids) - pos[id(job)]
            if left > 0:
                backlog += left
                ranks.append(_CLASS_RANK.get(request.priority, 0))
                continue
            if request.guided is not None:
                return None
            if stays(request, request.produced + 1):
                reqs[job.slot] = request
                produced[job.slot] = request.produced + 1
                carried[job.slot] = True
                staged.append((job.slot, request))
        if backlog == 0 and self.cache_mode != "state":
            return None
        decode_mask = np.array([r is not None for r in reqs])
        spoken_for = backlog >= budget - int(decode_mask.sum())
        unopened = len(self._admitting) - len(self._prefill_jobs)
        full = (
            self._pending.empty() and unopened <= 0
            and not any(
                r is None and i not in self._admitting
                and i not in self._quarantine
                for i, r in enumerate(self._slot_req)
            )
        )
        if not (spoken_for or full):
            return None
        waiting = self._pending.requests() + [
            request for request, _slot in self._ragged_ready._queue
        ]
        if ranks and any(
            _CLASS_RANK.get(r.priority, 0) < max(ranks) for r in waiting
        ):
            return None
        return {
            "reqs": reqs, "decode_mask": decode_mask, "produced": produced,
            "carried": carried, "decoded": carried & flight["decode_mask"],
            "pos": pos, "staged": staged,
        }

    def _prepare_ragged(self, active_mask: np.ndarray,
                        epoch: int) -> Optional[dict]:
        """Loop-thread half of a ragged step: sweep dead jobs, classify the
        live rows (docs/ragged_attention.md row taxonomy — plain decode
        rows carrying a q=row_steps multi-token window, spec-verify rows
        carrying a q=k+1 draft chain, prefill-chunk rows), hand each live
        job its token share under the step budget (class/arrival order —
        the jobs list is in admission-pop order), and snapshot every piece
        of shared host state the worker needs. A q=N row is N tokens of
        budget; admissions keep their PR-9 share (decode baseline is one
        token per row) and only the LEFTOVER budget widens decode windows,
        so saturating admission traffic sees the historical schedule while
        steady-state decode amortizes the launch across up to
        ``ragged_decode_steps`` tokens. Returns None when nothing is
        dispatchable."""
        self._last_progress = time.monotonic()
        self._sweep_ragged_jobs()
        budget = self._effective_token_budget()
        behind = bool(self._ragged_flights)
        if behind:
            # one launch is in flight: this one goes behind it, planned
            # from the host state as it will stand once that one retires,
            # where the plan can be written ahead and the launch is spoken
            # for (_ragged_ahead); else the step reads the one in flight
            # back first and plans at its next iteration
            view = self._ragged_ahead(self._ragged_flights[-1].plan, budget)
            if view is None:
                return None
            for slot, request in view["staged"]:
                self._stage_slot_config(request, slot)
        else:
            none = np.zeros(self.max_batch, bool)
            view = {
                "reqs": list(self._slot_req),
                "decode_mask": active_mask.copy(),
                "produced": self._produced_counters(),
                "carried": none, "decoded": none, "pos": {},
            }
        reqs, produced, job_pos = view["reqs"], view["produced"], view["pos"]
        decode_mask = view["decode_mask"]
        n_decode = int(decode_mask.sum())
        k_ = self._spec_k
        # spec-as-row: eligible decode slots become q=k+1 verify rows in
        # THIS mixed launch (host-drafted chain, device-verified, accepted
        # at retire) — the serial spec scan never runs under this scheduler
        spec_mask = np.zeros(self.max_batch, bool)
        sspec_mask = np.zeros(self.max_batch, bool)
        if not behind and self._ragged_spec_wanted(decode_mask):
            greedy, sampled_m = self._spec_eligible_mask(decode_mask)
            spec_mask, sspec_mask = greedy.copy(), sampled_m.copy()
            if faults.active() and (spec_mask.any() or sspec_mask.any()):
                # chaos seam: a mid-verify proposer/tree-layout failure
                # falls back to PLAIN DECODE for the poisoned row — it
                # rides this same launch as an ordinary q=1/multi-step
                # decode row; nothing was allocated yet, so the fallback
                # is leak-free by construction (docs/spec_decode_trees.md)
                try:
                    faults.fire(
                        "engine.spec.tree",
                        requests=[
                            self._slot_req[int(s)]
                            for s in np.nonzero(spec_mask | sspec_mask)[0]
                        ],
                    )
                except faults.InjectedFault as ex:
                    self.counters["spec_tree_fallbacks"] += 1
                    if ex.request is None:
                        spec_mask[:] = False
                        sspec_mask[:] = False
                    else:
                        for s in np.nonzero(spec_mask | sspec_mask)[0]:
                            if self._slot_req[int(s)] is ex.request:
                                spec_mask[int(s)] = False
                                sspec_mask[int(s)] = False
            # a verify row costs k extra budget tokens: demote rows
            # (highest slot first) until the baseline fits the budget
            spec_slots = [int(s) for s in np.nonzero(spec_mask | sspec_mask)[0]]
            while spec_slots and n_decode + k_ * len(spec_slots) > budget:
                drop = spec_slots.pop()
                spec_mask[drop] = False
                sspec_mask[drop] = False
        spec_any = spec_mask | sspec_mask
        n_spec = int(spec_any.sum())
        shares: List[tuple] = []
        left = max(0, budget - n_decode - k_ * n_spec)
        for job in list(self._prefill_jobs):
            if left <= 0:
                break
            pos = job_pos.get(id(job), job.pos)
            remaining = len(job.request.prompt_ids) - pos
            take = min(left, remaining)
            if take <= 0:
                continue
            if faults.active():
                try:
                    # chaos seam: budget admission of one prefill job into
                    # this step (docs/ragged_attention.md)
                    faults.fire("engine.admit.budget", request=job.request)
                except faults.InjectedFault as ex:
                    self._count_shed("budget", job.request.priority)
                    self._fail_ragged_job(job, EngineOverloadedError(
                        "ragged budget admission shed (injected): {}".format(
                            ex
                        ),
                        retry_after=self._retry_after_hint(),
                        shed_class=job.request.priority,
                    ))
                    continue
            shares.append((job, take))
            left -= take
        if n_decode == 0 and not shares:
            return None
        # multi-step decode windows from the LEFTOVER budget: the launch
        # window buckets to a power of two (bounded compile keys, each
        # warmed by llm/warmup.py) and every row clamps host-side to its
        # own max-token / sequence bounds — a brownout stage-2 cap landing
        # mid-stream clamps the window exactly like max_new_tokens does
        plain_slots = [
            int(s) for s in np.nonzero(decode_mask & ~spec_any)[0]
        ]
        launch_steps = 1
        if plain_slots and self._ragged_steps_cap > 1 and left > 0:
            launch_steps = decode_steps_bucket(
                1 + left // len(plain_slots), cap=self._ragged_steps_cap
            )
        row_steps = np.zeros(self.max_batch, np.int32)
        for slot in plain_slots:
            request = reqs[slot]
            remaining_new = (
                self._effective_max_new(request) - int(produced[slot])
            )
            remaining_len = self.max_seq_len - (
                request.prompt_len + int(produced[slot])
            )
            row_steps[slot] = max(
                1, min(launch_steps, remaining_new, remaining_len)
            )
        # drafts for the verify rows, proposed from the host token buffer
        # (kept warm at every ragged retire) through the pluggable
        # proposer: chain engines get the ngram-chain backend (drafts
        # byte-identical to the legacy _ngram_draft_rows,
        # tests/test_spec_tree.py pins it); spec_tree engines get the
        # ngram-forest topology plus the per-row tree arrays the device
        # acceptance walk and ancestor mask consume
        drafts = None
        tree_tokens = tree_parents = tree_depths = tree_n = None
        if n_spec:
            spec_slots = [int(s) for s in np.nonzero(spec_any)[0]]
            hists = [
                self._slot_req[s].prompt_len + self._slot_req[s].produced
                for s in spec_slots
            ]
            forest = self._spec_proposer.propose(
                spec_slots, hists, self._tokbuf, k_
            )
            drafts = np.zeros((self.max_batch, k_), np.int32)
            drafts[spec_slots] = forest.tokens[:, 1:]
            if self._spec_tree:
                from .spec_proposer import chain_parents

                tree_tokens = np.zeros((self.max_batch, k_ + 1), np.int32)
                tree_parents = np.broadcast_to(
                    chain_parents(k_), (self.max_batch, k_ + 1)
                ).copy()
                tree_depths = np.broadcast_to(
                    np.arange(k_ + 1, dtype=np.int32),
                    (self.max_batch, k_ + 1),
                ).copy()
                tree_n = np.full(self.max_batch, k_ + 1, np.int32)
                tree_tokens[spec_slots] = forest.tokens
                tree_parents[spec_slots] = forest.parents
                tree_depths[spec_slots] = forest.depths
                tree_n[spec_slots] = forest.n_nodes
        want_lp = any(
            reqs[s] is not None and reqs[s].logprobs is not None
            for s in np.nonzero(decode_mask)[0]
        )
        use_extras = self._extras_active(decode_mask)
        use_guided = bool(np.any(self._gstate[decode_mask] >= 0))
        gtables = self._guided_device_tables() if use_guided else None
        gstate = None
        if gtables is not None:
            # behind a launch in flight the rows it DECODED chain their DFA
            # state on the device, as a pipelined chunk's do; the host's
            # value wins for every other row (_chain_input): a slot that
            # changed hands still holds its last owner's state there
            self._slot_overrides[:] = decode_mask & ~view["decoded"]
            gstate = self._chain_input(self._gstate_dev, self._gstate)
            self._slot_overrides[:] = False
        self._dispatch_seq += 1
        plan = {
            "seq": self._dispatch_seq,
            "epoch": epoch,
            # enqueued while the launch before it has not been read back
            "behind": behind,
            # each row's request as planned: a row whose slot holds another
            # (or none) when the launch retires rode it for nothing
            "row_req": reqs,
            "decode_mask": decode_mask,
            "shares": shares,
            "budget": budget,
            "want_lp": want_lp,
            "use_extras": use_extras,
            "sampling": self._batch_sampling(),
            # the cached constants; the produced-token counters ride the
            # launch's one upload (_upload_ragged_operands)
            "extras": self._extras_constants() if use_extras else None,
            "counters": produced if use_extras else None,
            "gtables": gtables,
            "gstate": gstate,
            "rng": self._next_rng(),
            "lora": (
                jnp.asarray(self._lora_slots.copy())
                if self._lora_enabled
                else None
            ),
            "requests": [r for r in reqs if r is not None]
            + [j.request for j, _ in shares],
            "exhausted": [],
            "failed_jobs": [],
            # rows whose admission completes THIS step (host-known at
            # planning time): the dispatch worker gathers only these rows'
            # logits device-side before readback
            "finish_slots": [
                job.slot for job, take in shares
                if job_pos.get(id(job), job.pos) + take
                >= len(job.request.prompt_ids)
            ],
            # multi-step / spec-as-row row taxonomy
            # (docs/ragged_attention.md)
            "spec_mask": spec_mask,
            "sspec_mask": sspec_mask,
            "spec_k": k_,
            "drafts": drafts,
            # draft-tree verify rows (docs/spec_decode_trees.md): per-row
            # topology arrays + the flat per-token ancestor lists (filled
            # by the paged layout below; None on chain engines so their
            # jit trace is byte-identical to the pre-tree one)
            "tree_tokens": tree_tokens,
            "tree_parents": tree_parents,
            "tree_depths": tree_depths,
            "tree_n": tree_n,
            "tree_anc": None,
            "row_steps": row_steps,
            "launch_steps": launch_steps,
            "step_rngs": (
                jnp.stack([self._next_rng() for _ in range(launch_steps - 1)])
                if launch_steps > 1
                else None
            ),
            "spec_rng": self._next_rng() if n_spec else None,
            # per-step window mask: step i runs for rows whose window is
            # still open ([S-1, B]; host-known — EOS mid-window is masked
            # at retire, max-token/seq bounds here)
            "chain_mask": (
                (
                    np.arange(1, launch_steps)[:, None]
                    < row_steps[None, :]
                )
                if launch_steps > 1
                else None
            ),
            "used_tokens": (
                int(row_steps.sum()) + (k_ + 1) * n_spec
                + sum(t for _, t in shares)
            ),
        }
        job_of = {job.slot: job for job, _ in shares}
        take_of = {job.slot: take for job, take in shares}
        from ..ops.paged_attention import ragged_layout, ragged_work_items

        # tokens each row's cache holds before this launch: the page
        # pool's slot length, or the state slot's
        pool = (
            self.paged_cache.pool
            if self.paged_cache is not None
            else None
        )
        # layout lens reserve each row's WHOLE window in both token axes
        # (a q=N decode row owns N positions: position 0 rides the
        # mixed pass, positions 1.. are written by the in-launch chain);
        # kernel row_lens count only the positions the ragged pass
        # itself computes
        span_lens = np.zeros(self.max_batch, np.int32)
        row_lens = np.zeros(self.max_batch, np.int32)
        for slot in np.nonzero(decode_mask)[0]:
            slot = int(slot)
            if spec_any[slot]:
                span_lens[slot] = row_lens[slot] = k_ + 1
            else:
                span_lens[slot] = row_steps[slot]
                row_lens[slot] = 1
        for slot, take in take_of.items():
            span_lens[slot] = row_lens[slot] = take
        # the compact axis packs the spans (``s`` below, and every index
        # the launch reads a token by); the aligned view places the same
        # spans where the kernel's row map says (``starts``), and
        # ``tok_slot`` takes a token from the one to the other
        dense = self._ragged_dense
        starts, tpad = ragged_layout(
            span_lens, self._ragged_qb, total=self._ragged_tpad
        )
        # one axis where the sizes coincide: the XLA twin (rows pack
        # densely in its view too) and the state cache
        packed = (
            starts if dense == tpad
            else ragged_layout(span_lens, 1, total=dense)[0]
        )
        tokens = np.zeros(dense, np.int32)
        tok_pos = np.zeros(dense, np.int32)
        tok_row = np.zeros(dense, np.int32)
        tok_valid = np.zeros(dense, bool)
        tok_slot = np.full(dense, tpad, np.int32)
        # where the device puts a carried row's pending token (past the
        # end: the host wrote the row's token itself)
        chain_at = np.full(self.max_batch, dense, np.int32)
        row_last = np.zeros(self.max_batch, np.int32)
        kv_lens = np.zeros(self.max_batch, np.int32)
        pre_lens = np.zeros(self.max_batch, np.int32)
        spans: Dict[int, tuple] = {}
        for slot in range(self.max_batch):
            n = int(span_lens[slot])
            if n == 0:
                continue
            s = int(packed[slot])
            v = int(row_lens[slot])
            pre = (
                pool.slot_length(slot)
                if pool is not None
                else self.state_cache.length(slot)
            )
            pre_lens[slot] = pre
            if slot in job_of:
                job = job_of[slot]
                pos = job_pos.get(id(job), job.pos)
                tokens[s : s + n] = job.request.prompt_ids[pos : pos + n]
            elif spec_any[slot]:
                tokens[s] = self._next_token[slot]
                tokens[s + 1 : s + n] = drafts[slot]
            elif view["carried"][slot]:
                chain_at[slot] = s
            else:
                tokens[s] = self._next_token[slot]
            spans[slot] = (s, n)
            if tree_depths is not None and spec_any[slot]:
                # a tree node's ABSOLUTE position is its path depth,
                # not its node index: sibling drafts at the same depth
                # share a RoPE position, and the accepted path's K/V
                # (compacted in-launch to positions pre+1..pre+acc)
                # was embedded at exactly those positions
                tok_pos[s : s + n] = pre + tree_depths[slot, :n]
            else:
                tok_pos[s : s + n] = pre + np.arange(n, dtype=np.int32)
            tok_row[s : s + n] = slot
            # reserved multi-step positions stay invalid in the mixed
            # pass: their tokens are sampled in-launch and their K/V
            # written by the chained decode steps
            tok_valid[s : s + v] = True
            tok_slot[s : s + v] = starts[slot] + np.arange(v, dtype=np.int32)
            row_last[slot] = s + v - 1
            kv_lens[slot] = pre + v
        if tree_parents is not None and n_spec:
            # per-token ancestor lists for the kernel's tree mask, in the
            # aligned view's order (ops.paged_attention.tree_ancestors
            # layout): every non-tree token keeps the -2 plain-causal
            # sentinel
            from ..ops.paged_attention import tree_ancestors

            tree_anc = np.full((tpad, k_ + 1), -1, np.int32)
            tree_anc[:, 0] = -2
            for slot in np.nonzero(spec_any)[0]:
                slot = int(slot)
                s = int(starts[slot])
                tree_anc[s : s + k_ + 1] = tree_ancestors(
                    tree_parents[slot], int(tree_n[slot]),
                    width=k_ + 1,
                )
            plan["tree_anc"] = tree_anc
        if n_spec:
            row_logit_idx = np.zeros(
                (self.max_batch, k_ + 1), np.int32
            )
            for slot in range(self.max_batch):
                if row_lens[slot] > 0:
                    row_logit_idx[slot] = packed[slot] + np.minimum(
                        np.arange(k_ + 1), row_lens[slot] - 1
                    )
        else:
            row_logit_idx = None
        plan.update(
            tokens=tokens, tok_pos=tok_pos, tok_row=tok_row,
            tok_valid=tok_valid, tok_slot=tok_slot, row_last=row_last,
            chain_at=chain_at, kv_lens=kv_lens,
            pre_lens=pre_lens, row_starts=starts, row_lens=row_lens,
            span_lens=span_lens, spans=spans,
            # every row the launch writes pages / state for ([B] bool)
            rows=span_lens > 0,
            # state cache: a row whose tokens start its sequence finds
            # its slot as the last owner left it — the launch zeroes it
            row_reset=(pre_lens == 0) & (row_lens > 0),
            row_logit_idx=row_logit_idx,
            write_page=np.zeros(dense, np.int32),
            write_offset=np.zeros(dense, np.int32),
            item_rows=None, item_q0=None,
        )
        if self._ragged_kernel and pool is not None:
            item_rows, item_q0 = ragged_work_items(
                row_lens, self._ragged_tile, total=self._ragged_items
            )
            plan.update(item_rows=item_rows, item_q0=item_q0)
        # the finishing rows' first tokens are sampled behind this launch
        # (_enqueue_first_token): each row's key of the shared stream, in
        # the order of the commits (this launch's keys are taken above;
        # the next launch's come after these)
        plan["first_ops"] = {
            job.slot: self._first_token_ops(job.request)
            for job, _ in shares if job.slot in plan["finish_slots"]
        }
        if self._counts_dev is None and any(
            self._request_has_extras(op["request"])
            for op in plan["first_ops"].values()
        ):
            # the [B, V] state exists as soon as anyone needs it; the
            # program then resets the rows of EVERY finishing slot
            self._ensure_extras_state()
        if faults.active():
            # yield-point seam parity with _prepare_dispatch: snapshot
            # complete, worker not yet started
            faults.fire("engine.dispatch.prepare", requests=plan["requests"])
        return plan

    def _ragged_operand_layout(
        self, launch_steps: int, extras: bool, spec: bool
    ) -> tuple:
        """The staging buffer of one step variant (a decode window, extras
        on or off, verify rows or none): every host vector the launch
        hands the device, in the order the worker fills them, at sizes the
        engine holds since its build (docs/ragged_attention.md)."""
        b, dense, chain = self.max_batch, self._ragged_dense, launch_steps - 1
        entries = [
            ("tokens", (dense,), False), ("tok_pos", (dense,), False),
            ("tok_row", (dense,), False), ("tok_valid", (dense,), True),
            ("row_last", (b,), False), ("kv_lens", (b,), False),
            ("row_starts", (b,), False), ("row_lens", (b,), False),
            ("decode_mask", (b,), True), ("chain_at", (b,), False),
        ]
        if self.cache_mode == "paged":
            entries += [
                ("tok_slot", (dense,), False),
                ("page_table", (b, self._pages_per_seq), False),
                ("write_page", (dense,), False),
                ("write_offset", (dense,), False),
            ]
            if self._ragged_kernel:
                entries += [
                    ("item_rows", (self._ragged_items,), False),
                    ("item_q0", (self._ragged_items,), False),
                ]
            if chain:
                entries += [
                    ("chain_mask", (chain, b), True),
                    ("chain_wp", (chain, b), False),
                    ("chain_wo", (chain, b), False),
                ]
            if spec:
                k1 = self._spec_k + 1
                entries += [
                    ("spec_mask", (b,), True), ("sspec_mask", (b,), True),
                    ("drafts", (b, k1 - 1), False),
                    ("row_logit_idx", (b, k1), False),
                ]
                if self._spec_tree:
                    entries += [
                        ("tree_tokens", (b, k1), False),
                        ("tree_parents", (b, k1), False),
                        ("tree_n", (b,), False),
                        ("tree_anc", (self._ragged_tpad, k1), False),
                    ]
        else:
            entries.append(("row_reset", (b,), True))
            if chain:
                entries.append(("chain_mask", (chain, b), True))
        if extras:
            entries.append(("counters", (b,), False))
        return _staging_layout(entries)

    def _upload_ragged_operands(self, plan: dict) -> dict:
        """Worker thread: the launch's host vectors as device operands, in
        ONE transfer. A FRESH staging buffer takes every vector of the
        plan's layout (after any pool-exhaustion drop edited them), crosses
        as the unpack program's one argument (the call makes the transfer:
        0.17 ms less on the chip's host than a ``device_put`` before it,
        PERF.md section 6) and is never written again (on the CPU backend
        the device array may alias it: the hazard _chain_input records);
        the unpack program hands back the operands by name, before the
        ``enqueue`` stamp. Behind a launch in flight the program also puts
        the carried rows' pending tokens, which never left the device
        (``_next_token_dev``), where ``chain_at`` says; a launch planned
        from the host's own tokens passes a null chain and every
        ``chain_at`` past the end."""
        layout, total = self._ragged_layouts[(
            plan["launch_steps"], plan["use_extras"],
            plan["row_logit_idx"] is not None,
        )]
        staged = np.empty(total, np.int32)
        for name, offset, shape, _ in layout:
            staged[offset : offset + math.prod(shape)] = plan[name].reshape(-1)
        plan["h2d_transfers"] = 1
        return self._ragged_unpack_jit(
            staged, layout,
            self._next_token_dev if plan["behind"] else self._chain_null,
        )

    def _ragged_drop_row(self, plan: dict, slot: int) -> None:
        """Worker-side removal of a row whose page extension failed: its
        tokens become pads (null-page writes, masked compute); the retire
        stage fails the decode request / admission job it carried."""
        s, n = plan["spans"].pop(slot)
        plan["tokens"][s : s + n] = 0
        plan["tok_pos"][s : s + n] = 0
        plan["tok_row"][s : s + n] = 0
        plan["tok_valid"][s : s + n] = False
        plan["tok_slot"][s : s + n] = self._ragged_tpad
        plan["chain_at"][slot] = len(plan["tokens"])
        plan["row_lens"][slot] = 0
        plan["span_lens"][slot] = 0
        plan["kv_lens"][slot] = plan["pre_lens"][slot]
        plan["row_last"][slot] = 0
        plan["row_steps"][slot] = 0
        plan["spec_mask"][slot] = False
        plan["sspec_mask"][slot] = False
        if plan["chain_mask"] is not None:
            plan["chain_mask"][:, slot] = False
        if plan["row_logit_idx"] is not None:
            plan["row_logit_idx"][slot] = 0
        if plan.get("tree_anc") is not None:
            # the dropped verify row's pad tokens revert to plain-causal
            # sentinels (they are never live queries, but the mask arrays
            # must not carry a freed row's topology into the launch)
            a = int(plan["row_starts"][slot])
            plan["tree_anc"][a : a + n] = -1
            plan["tree_anc"][a : a + n, 0] = -2
        if plan["decode_mask"][slot]:
            plan["decode_mask"][slot] = False
            plan["exhausted"].append(slot)
        else:
            job = next(j for j, _ in plan["shares"] if j.slot == slot)
            plan["failed_jobs"].append((
                job,
                MemoryError("kv page pool exhausted during ragged admission"),
            ))

    def _dispatch_ragged_device(self, plan: dict) -> dict:
        """Worker-thread half of a ragged step: page allocation for every
        row's chunk plus the ONE device launch (donated pools/cache,
        rebound under the dispatch lock — same discipline as the legacy
        dispatch workers)."""
        stamps = _WorkerStamps(plan["seq"], plan.get("launched"))
        with self._sentry_scope("ragged", seq=plan["seq"]), \
                jax.profiler.TraceAnnotation("engine.dispatch", seq=plan["seq"]), \
                stamps:
            return self._dispatch_ragged_device_inner(plan, stamps)

    def _dispatch_ragged_device_inner(
        self, plan: dict, stamps: _WorkerStamps
    ) -> dict:
        if faults.active():
            # chaos seam, BEFORE any device work: a per-request poison
            # fails only its row's request/job, never the launch
            faults.fire("engine.decode", requests=plan["requests"])
        use_extras = plan["use_extras"]
        gtables = plan["gtables"]
        want_lp = plan["want_lp"]
        launch_steps = plan["launch_steps"]

        if self.cache_mode == "paged":
            pool = self.paged_cache.pool
            for slot in list(plan["spans"]):
                s, n = plan["spans"][slot]
                try:
                    # surplus rides the slot: _retire_ragged truncates to
                    # what the window kept; _ragged_recover rolls back to
                    # pre_lens on a tripped step (cross-function pairing
                    # the ownership ledger audits)
                    pool.extend(slot, n)  # tpuserve: ignore[TPU701] rolled back at retire/recover
                except MemoryError:
                    self._ragged_drop_row(plan, slot)
                    continue
                coords = pool.token_coords(
                    slot, int(plan["pre_lens"][slot]), n
                )
                for i, (page, offset) in enumerate(coords):
                    plan["write_page"][s + i] = page
                    plan["write_offset"][s + i] = offset
            if launch_steps > 1:
                # multi-step decode rows: the reserved span positions 1..
                # become the chained steps' per-step write coordinates —
                # the mixed pass writes only position 0 (the others go to
                # the null page there, exactly like any pad)
                chain_wp = plan["chain_wp"] = np.zeros(
                    (launch_steps - 1, self.max_batch), np.int32
                )
                chain_wo = plan["chain_wo"] = np.zeros(
                    (launch_steps - 1, self.max_batch), np.int32
                )
                for slot, (s, n) in plan["spans"].items():
                    if (
                        not plan["decode_mask"][slot]
                        or plan["spec_mask"][slot]
                        or plan["sspec_mask"][slot]
                        or n <= 1
                    ):
                        continue
                    for i in range(1, n):
                        chain_wp[i - 1, slot] = plan["write_page"][s + i]
                        chain_wo[i - 1, slot] = plan["write_offset"][s + i]
                        plan["write_page"][s + i] = 0
                        plan["write_offset"][s + i] = 0
            self.paged_cache.apply_pending_cow()
            plan["page_table"] = pool.page_table(self._pages_per_seq)
            # AFTER any pool-exhaustion drop: _ragged_drop_row edits the
            # host vectors in place (a dropped verify row's masks are False
            # and its ancestor rows reverted to plain-causal sentinels), and
            # the device operands must see the post-drop state
            dev = self._upload_ragged_operands(plan)
            extras = (
                plan["extras"]._replace(counters=dev["counters"])
                if use_extras
                else None
            )
            chain_arrays = (
                (
                    plan["step_rngs"], dev["chain_mask"],
                    dev["chain_wp"], dev["chain_wo"],
                )
                if launch_steps > 1
                else None
            )
            spec = tree = None
            if "row_logit_idx" in dev:
                spec = (
                    dev["spec_mask"], dev["sspec_mask"], dev["drafts"],
                    dev["row_logit_idx"], plan["spec_rng"],
                )
                # tree topology operands (docs/spec_decode_trees.md)
                if "tree_anc" in dev:
                    tree = (
                        dev["tree_tokens"], dev["tree_parents"],
                        dev["tree_n"], dev["tree_anc"],
                    )
            with self.paged_cache.dispatch_lock:
                stamps.enqueue()
                (
                    sampled, logits,
                    self.paged_cache.k, self.paged_cache.v_carry,
                    new_ks, new_vs, new_counts, lp, gstate_out,
                    spec_g, spec_acc,
                ) = self._ragged_paged_jit(
                    self.params,
                    dev["tokens"],
                    dev["tok_pos"],
                    dev["tok_row"],
                    dev["tok_valid"],
                    dev["tok_slot"],
                    dev["row_last"],
                    self.paged_cache.k,
                    self.paged_cache.v_carry,
                    self.paged_cache.k_scale,
                    self.paged_cache.v_scale,
                    dev["page_table"],
                    dev["kv_lens"],
                    dev["row_starts"],
                    dev["row_lens"],
                    dev["write_page"],
                    dev["write_offset"],
                    dev.get("item_rows"),
                    dev.get("item_q0"),
                    dev["decode_mask"],
                    plan["sampling"],
                    plan["rng"],
                    plan["lora"],
                    extras,
                    self._counts_dev if use_extras else None,
                    self._pmask_dev if use_extras else None,
                    gtables,
                    plan["gstate"],
                    want_lp=want_lp,
                    spec=spec,
                    chain=chain_arrays,
                    tree=tree,
                )
                stamps.enqueued()
                if self._paged_quant:
                    self.paged_cache.k_scale = new_ks
                    self.paged_cache.v_scale = new_vs
            if self.state_cache is not None:
                # a row state beside the pages took every token of every
                # row's span, as below
                for slot, (_s, n) in plan["spans"].items():
                    self.state_cache.advance(slot, n)
        else:
            # state cache: a launch's plan carries (slot = row, reset) per
            # row in place of page tables and write coordinates; nothing is
            # allocated here (admission gave the row its slot) and nothing
            # can run out
            cache = self.state_cache
            dev = self._upload_ragged_operands(plan)
            extras = (
                plan["extras"]._replace(counters=dev["counters"])
                if use_extras
                else None
            )
            chain_arrays = (
                (plan["step_rngs"], dev["chain_mask"])
                if launch_steps > 1
                else None
            )
            with cache.dispatch_lock:
                stamps.enqueue()
                (
                    sampled, logits, cache.s, cache.z, new_counts, lp,
                    gstate_out,
                ) = self._ragged_state_jit(
                    self.params,
                    dev["tokens"],
                    dev["tok_pos"],
                    dev["tok_row"],
                    dev["tok_valid"],
                    dev["row_last"],
                    cache.s,
                    cache.z,
                    dev["kv_lens"],
                    dev["row_starts"],
                    dev["row_lens"],
                    dev["row_reset"],
                    dev["decode_mask"],
                    plan["sampling"],
                    plan["rng"],
                    extras,
                    self._counts_dev if use_extras else None,
                    self._pmask_dev if use_extras else None,
                    gtables,
                    plan["gstate"],
                    want_lp=want_lp,
                    chain=chain_arrays,
                )
                stamps.enqueued()
            spec_g = spec_acc = None
            # the state took every token of every row's span (a decode
            # row's whole window: a row that stops inside it is freed at
            # retire, its slot zeroed for the next owner)
            for slot, (_s, n) in plan["spans"].items():
                cache.advance(slot, n)
        if use_extras:
            self._counts_dev = new_counts
        # the rows whose admission completes this step (minus any the
        # pool-exhaustion path dropped): their first tokens are sampled
        # right here, behind the launch — the [R, vocab] matrix never
        # crosses the device boundary, and neither does a row of it
        finish = [s for s in plan["finish_slots"] if s in plan["spans"]]
        first = [
            self._enqueue_first_token(plan["first_ops"][slot], slot, logits)
            for slot in finish
        ]
        if self.pipeline_depth > 1:
            # what a launch behind this one takes on the device: each
            # row's pending token (the window's last sample; a finishing
            # prompt's first token at its slot) and the DFA states
            chain = sampled
            for slot, (ids, _lp) in zip(finish, first):
                chain = self._ragged_chain_jit(chain, ids, np.int32(slot))
            if chain.ndim > 1:
                chain = self._ragged_chain_jit(
                    chain, self._first_null, np.int32(self.max_batch)
                )
            self._next_token_dev = chain
            self._gstate_dev = gstate_out if gtables is not None else None
        self._last_progress = time.monotonic()
        return {
            "stamps": self._worker_out(stamps),
            "sampled": sampled,
            "first": first,
            "lp": lp,
            "gstate": gstate_out if gtables is not None else None,
            "finish_rows": finish,
            "spec_g": spec_g,
            "spec_acc": spec_acc,
        }

    def _enqueue_first_token(self, op: dict, slot: int, logits):
        """Worker thread, right behind the launch: the program that
        samples a finishing prompt's first token from its row of the
        launch's logits and resets the slot's counts / bias / prompt-mask
        rows (``_sample_first_token``). The chip is still running the
        launch, so nothing here waits for it; the id and the logprob
        triple are read back with the launch's own copies
        (``_read_back``). A row a launch (two or more prompts end in under
        2% of the finishing launches) keeps the program at ONE shape: its
        sampler holds a whole-vocabulary sort, 16 s of the v5e compiler a
        variant."""
        state = (
            None if self._counts_dev is None
            else (self._counts_dev, self._bias_dev, self._pmask_dev)
        )
        (ids, lp, state), _rows = self._sample_first_token(
            op, slot, logits, state
        )
        if state is not None:
            self._counts_dev, self._bias_dev, self._pmask_dev = state
        return ids, lp

    async def _ragged_step(self, active_mask: np.ndarray, epoch: int) -> bool:
        """One ragged scheduling iteration (docs/ragged_attention.md): ONE
        device launch carries every decode row (one token each) plus as
        many prefill-chunk rows as fit the step token budget — admissions
        no longer stall the decode loop, they share its launches. The step
        keeps ONE launch in flight (``pipeline_depth`` 2 or more; at 1 it
        is the serial dispatch -> read -> emit): with launch N enqueued it
        plans launch N+1 from the host state as it will stand once N
        retires and hands it to the dispatch worker, where the plan can be
        written ahead and the launch is spoken for (_ragged_ahead), and
        only then reads N back, retires it and emits its tokens, while N+1
        runs; N+1 stays out for the next iteration. All waits are worker
        threads': the event loop's handlers (the streams the LAST emission
        woke) run while the launches do. True once the step awaited a
        worker, so the caller need not hand the event loop over again; the
        pipelined in-flight queue resumes the moment the admission backlog
        drains."""
        flights = self._ragged_flights
        ahead = self.pipeline_depth > 1
        wait_at = None
        if not flights:
            # post-ragged decode must re-upload the host mirrors: the
            # device chains were built by the (drained) pipelined path
            self._reset_device_chains()
            launched, wait_at = await self._ragged_launch(
                active_mask, epoch, "plan" if ahead else "wait"
            )
            if not launched:
                return launched is False
        if ahead:
            # behind the launch in flight, where it is spoken for
            wait_at = (
                await self._ragged_launch(active_mask, epoch, "wait")
            )[1]
        plan, result = flights[0].plan, flights[0].result
        seq = plan["seq"]
        # the read that closed the ``launch`` of a dispatch that just
        # landed opened this ``wait``
        plan["wait_at"] = (
            self._cycle.mark("wait", seq) if wait_at is None else wait_at
        )
        # enqueued: what is left is the device's run, and a hang there
        # gets no compile grace from the watchdog. The launch still
        # counts as dispatching (its program writes the rows' pages)
        # until its results are in hand
        self._dispatching = (seq, plan["rows"], None)
        try:
            sampled, rest, ready_at, awaited = await self._read_back(
                seq,
                result["sampled"],
                {
                    name: result[name]
                    for name in ("gstate", "lp", "spec_acc", "spec_g", "first")
                },
            )
        finally:
            self._dispatching = None
        if plan["epoch"] != self._recover_epoch:
            await self._ragged_recover(plan)
            return True
        self._retire_ragged(plan, dict(
            result, sampled=sampled, ready_at=ready_at,
            off_loop=int(awaited), **rest,
        ))
        flights.popleft()
        if not flights:
            # the pipelined chunk starts from the host mirrors
            self._reset_device_chains()
        return True

    async def _ragged_launch(self, active_mask: np.ndarray, epoch: int,
                             then: str) -> tuple:
        """Plan a ragged launch and hand it to the dispatch worker: pages,
        the staged upload, the jitted step, the first-token programs. True
        with the launch in ``_ragged_flights``; None where nothing was
        planned (nothing dispatchable, or a launch is in flight and the
        next cannot go behind it); False where the worker raised for one
        admission's request and only that job was failed. With it, the
        clock read that closed the cycle's ``launch`` and opened ``then``
        (``plan``: the step tries the next launch behind this one;
        ``wait``), None where nothing landed."""
        self._cycle.mark("plan", self._dispatch_seq + 1)
        plan = self._prepare_ragged(active_mask, epoch)
        if plan is None:
            return None, None
        seq = plan["seq"]
        plan["launch_at"] = self._cycle.mark("launch", seq)
        plan["launched"] = threading.Event()
        self._dispatching = (seq, plan["rows"], time.monotonic())
        try:
            launch = asyncio.get_running_loop().run_in_executor(
                None, self._dispatch_ragged_device, plan
            )
            # no handler runs between ``plan`` and the ``enqueued`` stamp:
            # the streams' Python beside the worker's upload + enqueue
            # would only make the launch late (one GIL)
            plan["launched"].wait(_ENQUEUED_WAIT_S)
            try:
                result = await launch
            except asyncio.CancelledError:
                raise
            except BaseException as ex:
                req = getattr(ex, "request", None)
                job = (
                    next(
                        (j for j in self._prefill_jobs if j.request is req),
                        None,
                    )
                    if req is not None
                    else None
                )
                if job is not None:
                    # per-request fault attributed to an admission row: the
                    # seam fires before any device work, so decode rows lost
                    # nothing — fail only the job; next iteration re-plans
                    self.counters["step_failures"] += 1
                    self._fail_ragged_job(job, EngineStepError(
                        "ragged admission chunk failed for this request: "
                        "{}".format(ex)
                    ))
                    return False, None
                raise
        finally:
            self._dispatching = None
        self._ragged_flights.append(_RaggedFlight(plan, result))
        now = self._cycle.mark(
            then,
            self._ragged_flights[0].seq if then == "wait"
            else self._dispatch_seq + 1,
        )
        self._cycle.landed(seq, plan["launch_at"], result["stamps"], now)
        if plan["behind"]:
            self.counters["ragged_launches_behind"] += 1
        return True, now

    async def _read_back(self, seq: int, first, rest) -> tuple:
        """A launch's blocking device-to-host copies, off the loop thread: a
        worker waits for ``first`` (its copy back says the device has
        finished: the worker's ``ready`` read) and copies ``rest`` (a pytree
        of device arrays and None), the loop awaits it under its open
        ``wait`` phase, so the event loop's handlers and the watchdog run
        while the device does. The copies are all asked for before the
        wait (``copy_to_host_async``), so they overlap each other behind
        the launch. Where ``first`` has already landed (the
        device ran ahead, an inline backend) the copies are microseconds
        and the loop thread takes them itself. Returns the host copies,
        the ``ready`` read and whether the worker was awaited."""
        chaos = faults.active()
        # a stall can be matched to the requests the watchdog can fail: the
        # rows that hold a slot
        held = [r for r in self._slot_req if r is not None] if chaos else ()

        def _sync():
            if chaos:
                # worker-thread stall seam: wedges THIS launch's wait
                # without blocking the event loop, so the watchdog can
                # observe it
                faults.fire("engine.decode.stall", requests=held)
            head = np.asarray(first)
            ready_at = _clock()        # the device has finished the launch
            with jax.profiler.TraceAnnotation("engine.readback", seq=seq):
                # np.array (copy): asarray can alias a device buffer that
                # the next launch is given to overwrite (the DFA chain)
                tail = jax.tree.map(np.array, rest)
            return head, tail, ready_at

        # every copy back is asked for at once, behind the launch: the
        # worker then finds them done, or under way together, and not one
        # round trip after another with an idle chip
        for leaf in jax.tree.leaves((first, rest)):
            start = getattr(leaf, "copy_to_host_async", None)
            if start is not None:
                start()
        landed = getattr(first, "is_ready", None)
        awaited = chaos or landed is None or not landed()
        if awaited:
            head, tail, ready_at = await asyncio.to_thread(_sync)
        else:
            head, tail, ready_at = _sync()
        self._cycle.ready(seq, ready_at)
        return head, tail, ready_at, awaited

    async def _ragged_recover(self, plan: dict) -> None:
        """The watchdog tripped while ragged launches were out: the decode
        results are stale (those requests were already failed) and no
        commit may run. ``plan`` is the OLDEST of them, whose read has
        waited its program out; a launch enqueued behind it is waited out
        here, off-thread. The surviving jobs' page extensions roll back to
        what the oldest launch found (the next step redoes the chunks
        cleanly: its K/V rewrites are value-identical), then the shared
        recovery runs."""
        younger = [f for f in self._ragged_flights if f.plan is not plan]
        self._ragged_flights.clear()
        if younger:
            await asyncio.to_thread(self._wait_chunks, younger)
        self._ragged_rollback([plan] + [f.plan for f in younger])
        await self._finish_recovery()

    def _ragged_rollback(self, plans: list) -> None:
        """Loop thread, the launches of ``plans`` (oldest first) waited out
        and given up: each job that is still served goes back to before
        the first of them that carried a chunk of it."""
        seen = set()
        for plan in plans:
            for job, _take in plan["shares"]:
                if id(job) in seen or job not in self._prefill_jobs:
                    continue                     # identity compare
                seen.add(id(job))
                if self.state_cache is not None:
                    # a state took the chunk and cannot give it back: the
                    # job starts its prompt again (recompute; the launch
                    # that carries its first chunk zeroes the slot), and
                    # where pages lie beside the state they go too
                    if self.paged_cache is not None:
                        self.paged_cache.pool.truncate(job.slot, 0)
                    self.state_cache.rewind(job.slot)
                    job.pos = 0
                    continue
                self.paged_cache.pool.truncate(
                    job.slot, int(plan["pre_lens"][job.slot])
                )

    def _retire_ragged(self, plan: dict, result: dict) -> None:
        """Loop-thread tail of a ragged step, over the HOST copies of its
        results (_read_back): decode emissions re-anchor
        the host mirrors exactly like a pipelined retire — a q=N decode
        row emits its whole window in order under the MID-WINDOW EOS MASK
        (a row finishing inside its window delivers the tokens up to the
        stop and drops the surplus; the q=1 path simply stopped
        launching), a spec-verify row emits its accepted chain after the
        pool rolls its over-allocation back to what the verify kept, and
        finishing prefill jobs commit the first token that was sampled
        behind the launch (_enqueue_first_token) and activate their slot:
        host bookkeeping, no device call."""
        seq = plan["seq"]
        self._cycle.mark("emit", seq)
        sampled, gstate_np, lp_np = (
            result["sampled"], result["gstate"], result["lp"]
        )
        spec_acc, spec_g = result["spec_acc"], result["spec_g"]
        ready_at = result["ready_at"]
        if sampled.ndim == 1:
            sampled = sampled[None]               # step-major [S, B]
        if lp_np is not None and lp_np[0].ndim == 1:
            lp_np = tuple(a[None] for a in lp_np)  # step-major [S, B, ...]
        spec_any = plan["spec_mask"] | plan["sspec_mask"]
        # the per-request retire fault on a MULTI-TOKEN row fails the
        # request with its partial window delivered (all but the last
        # token): the tokens were already sampled device-side and the
        # failure is a host-emission failure, not a compute one
        partial: Dict[int, BaseException] = {}
        if faults.active():
            try:
                faults.fire("engine.decode.retire", requests=plan["requests"])
            except faults.InjectedFault as ex:
                if ex.request is None:
                    raise  # batch-wide: loop-level step-failure handling
                self.counters["step_failures"] += 1
                handled = False
                for slot, request in enumerate(self._slot_req):
                    if request is not ex.request:
                        continue
                    window = (
                        int(spec_acc[slot]) + 1
                        if spec_acc is not None and spec_any[slot]
                        else int(plan["row_steps"][slot])
                    )
                    err = EngineStepError(
                        "retire failed for this request: {}".format(ex)
                    )
                    if plan["decode_mask"][slot] and window > 1:
                        partial[slot] = err
                    else:
                        self._fail_slot(slot, err)
                    handled = True
                    break
                if not handled:
                    job = next(
                        (
                            j for j, _ in plan["shares"]
                            if j.request is ex.request
                        ),
                        None,
                    )
                    if job is not None:
                        plan["failed_jobs"].append((job, EngineStepError(
                            "retire failed for this request: {}".format(ex)
                        )))
        for slot in plan["exhausted"]:
            self._fail_slot(
                slot, MemoryError("kv page pool exhausted for this sequence")
            )
        decode_slots = [int(s) for s in np.nonzero(plan["decode_mask"])[0]]
        # a row that ended at the launch before this one for a reason only
        # the device knew (EOS, a stop token, a cancel or a deadline found
        # at the emission) rode this launch, planned behind that one, for
        # one surplus step: nothing of it is emitted, and its pages and its
        # state slot go back below, when the slot leaves quarantine
        surplus = {
            s for s in decode_slots
            if self._slot_req[s] is not plan["row_req"][s]
        }
        self.counters["ragged_surplus_rows"] += len(surplus)
        decode_slots = [s for s in decode_slots if s not in surplus]
        plain_slots = [s for s in decode_slots if not spec_any[s]]
        spec_slots = [s for s in decode_slots if spec_any[s]]
        if spec_slots:
            # roll each verify row's over-allocation back to the tokens the
            # acceptance actually kept (pending + accepted drafts). BEFORE
            # emission: _emit frees a finishing slot's pages entirely. A
            # slot the retire fault already failed (its 1-token window made
            # the failure immediate) freed its pages wholesale — nothing
            # left to truncate
            pool = self.paged_cache.pool
            for slot in spec_slots:
                if self._slot_req[slot] is None:
                    continue
                pool.truncate(
                    slot,
                    int(plan["pre_lens"][slot]) + 1 + int(spec_acc[slot]),
                )
        emitted_decode = 0

        def _window_emit(slot, toks, lp_of_step):
            """Emit one row's window in order; the mid-window EOS mask is
            the break on a freed slot — _emit finishes the request on a
            stop token / max-token / max-seq bound and the surplus never
            reaches the stream. Returns tokens delivered."""
            nonlocal emitted_decode
            fail_err = partial.pop(slot, None)
            delivered = 0
            for i, tok in enumerate(toks):
                if fail_err is not None and i == len(toks) - 1:
                    self._fail_slot(slot, fail_err)
                    return delivered
                request = self._slot_req[slot]
                if request is None:
                    break                      # mid-window EOS mask
                if self._tokbuf is not None:
                    # speculation history stays warm through ragged phases
                    # so the n-gram proposer keeps drafting well
                    idx = request.prompt_len + request.produced
                    if idx < self._tokbuf.shape[1]:
                        self._tokbuf[slot, idx] = tok
                self._emit(slot, tok, lp_of_step(i, request))
                delivered += 1
                emitted_decode += 1
            return delivered

        for slot in plain_slots:
            n = int(plan["row_steps"][slot])
            if n <= 0:
                continue

            def _lp_entry(i, request, slot=slot):
                if lp_np is None or request.logprobs is None:
                    return None
                chosen, top_id, top_lp = lp_np
                return {
                    "id": int(sampled[i, slot]),
                    "logprob": float(chosen[i, slot]),
                    "top_ids": top_id[i, slot].tolist(),
                    "top_logprobs": top_lp[i, slot].tolist(),
                }

            _window_emit(
                slot, [int(sampled[i, slot]) for i in range(n)], _lp_entry
            )
            if self._slot_req[slot] is not None:
                # the window's last token is the next launch's pending one
                self._next_token[slot] = int(sampled[n - 1, slot])
                if gstate_np is not None:
                    self._gstate[slot] = int(gstate_np[slot])
        accept_fracs = []
        for slot in spec_slots:
            acc = int(spec_acc[slot])
            accept_fracs.append(acc / max(1, plan["spec_k"]))
            if self._spec_tree:
                # accepted PATH DEPTH per tree verify row — the headline
                # engine_spec_tree_accept_depth reads at scrape time
                self._hist_spec_tree_depth.observe(acc)
            _window_emit(
                slot,
                [int(spec_g[slot, i]) for i in range(acc + 1)],
                lambda i, request: None,
            )
            if self._slot_req[slot] is not None:
                self._next_token[slot] = int(spec_g[slot, acc])
        for slot, err in partial.items():
            # defensive: a deferred partial-window failure whose row never
            # emitted (dropped between planning and retire) still fails
            self._fail_slot(slot, err)
        failed = [j for j, _ in plan["failed_jobs"]]
        live_shares = [
            (j, t) for j, t in plan["shares"]
            if not any(j is f for f in failed)
        ]
        self.counters["ragged_steps"] += 1
        self.counters["ragged_waits_off_loop"] += result["off_loop"]
        self.counters["ragged_decode_tokens"] += emitted_decode
        self.counters["ragged_prefill_tokens"] += sum(
            t for _, t in live_shares
        )
        self.counters["ragged_passes"] += int(plan["launch_steps"])
        self.counters["ragged_dense_rows"] += len(plan["tokens"])
        self.counters["ragged_h2d_transfers"] += plan["h2d_transfers"]
        self._count_sampler_passes(
            int(plan["launch_steps"]), plan["row_steps"]
        )
        if self.cache_mode == "paged":
            # a row of window n rides chained passes 1..n-1 and attends
            # pre_len + 1 + step tokens in pass ``step``
            chained = (
                plan["pre_lens"] + 2, np.maximum(plan["row_steps"] - 1, 0)
            )
            work = _decode_pass_work(*chained)
            if self._by_kind is not None:
                valid = np.asarray(plan["tok_valid"], bool)
                pass_work, kinds, _ = self._by_kind
                work += (pass_work(
                    kinds, np.asarray(plan["tok_pos"])[valid] + 1,
                    *chained, plan["row_lens"], plan["kv_lens"],
                ),)
            self._count_decode_passes(work)
            for name, n in zip(
                ("mixed_rows", "mixed_kv_tokens", "mixed_qk_pairs"),
                _mixed_pass_work(plan["row_lens"], plan["kv_lens"]),
            ):
                self.counters[name] += n
            if self._row_state is not None:
                # the mixed pass's rows by what the mixer ran for them: one
                # token through the update, more through the chunk
                n = np.asarray(plan["row_lens"], np.int64)
                self._ssm_counts["update_rows"] += int((n == 1).sum())
                self._ssm_counts["chunk_rows"] += int((n > 1).sum())
                self._ssm_counts["chunk_tokens"] += int(n[n > 1].sum())
        self._step_rows["decode"] += len(plain_slots)
        self._step_rows["spec_verify"] += len(spec_slots)
        self._step_rows["prefill"] += len(live_shares)
        if plain_slots or spec_slots:
            self._hist_launch_tokens.observe(emitted_decode)
        if accept_fracs:
            self._hist_spec_accept.observe(
                sum(accept_fracs) / len(accept_fracs)
            )
        used = (
            int(plan["row_steps"].sum())
            + (plan["spec_k"] + 1) * len(spec_slots)
            + sum(t for _, t in live_shares)
        )
        self._hist_budget.observe(used / max(1, plan["budget"]))
        for job, err in plan["failed_jobs"]:
            self._fail_ragged_job(job, err)
        for job, take in live_shares:
            if job not in self._prefill_jobs:  # failed since planning
                continue
            job.pos += take
            job.request._prefill_launches += 1
            if not job.request._enqueue_at:
                job.request._enqueue_at = result["stamps"][1]
            job.request._ready_at = ready_at
            if job.pos < len(job.request.prompt_ids):
                # draft-ahead KV shipping: the chunk boundary just made
                # whole storable pages final — overlap the transport with
                # the remaining prefill (docs/spec_decode_trees.md)
                self._maybe_ship_draft(job)
                continue
            # final chunk landed: the row's last-token logits are the
            # prompt's prefill logits — first token + slot activation
            request = job.request
            self._prefill_jobs.remove(job)
            self._admitting.discard(job.slot)
            if request.cancelled:
                self._deref_guided_request(request)
                request.out_queue.put_nowait(_FINISHED)
                self._free_ragged_slot(job.slot)
                continue
            err = self._deadline_error_at_commit(request)
            if err is not None:
                self._deref_guided_request(request)
                request.error = err
                request.out_queue.put_nowait(_FINISHED)
                self._free_ragged_slot(job.slot)
                continue
            # sampled behind the launch; its id and logprob entry came
            # back with the launch's copies: host bookkeeping from here
            first_id, first_lp = self._first_token_commit(
                plan["first_ops"][job.slot],
                result["first"][result["finish_rows"].index(job.slot)],
            )
            self.counters["ragged_first_tokens"] += 1
            self.counters["ragged_first_tokens_behind_launch"] += 1
            if self.cache_mode == "paged" and self._prefix is not None:
                # zero-copy store, same point as the legacy commit: the
                # slot's own pages now hold the whole prompt's KV
                self._prefix.store_pages(
                    request.prompt_ids,
                    self._slot_lora(request),
                    self.paged_cache.pool.slot_pages(job.slot),
                )
                # disaggregated ship-at-commit, ragged scheduler's commit
                # point (docs/disaggregation.md)
                self._maybe_ship(request, job.slot)
            self._activate_slot(request, job.slot, first_id, first_lp)
        # slots freed while this launch still carried their rows (a
        # surplus row, a job failed after it was planned) are free now
        self._release_quarantine(seq)
        # retire-stage promotion reap, same rule as the pipelined retire
        self._reap_promotions()
        self._last_progress = time.monotonic()
        t1 = self._cycle.mark("yield", seq)
        self._hist_retire.observe((t1 - plan["wait_at"]) * 1e3)

    async def _run_loop(self) -> None:
        try:
            await self._run_loop_inner()
        except BaseException as ex:
            self._fail_all(ex)
            self._drain_ready(ex)
            raise
        finally:
            self._cycle.park()
            if self._prefill_gate is not None:
                # no decode loop -> nothing to pace against; unblock waiters
                self._prefill_gate.set_active(False)
            # ragged scheduler: no loop means no further chunk rows — fail
            # in-progress jobs and prepped-but-unopened admissions (their
            # consumers must never hang; slot pages reclaimed below)
            exit_err = EngineUnavailableError(
                "engine stopped" if self._stopped else "engine loop exited"
            )
            if self._prefill_jobs:
                self._abort_ragged_jobs(exit_err)
            self._drain_ragged_ready(exit_err)
            if self._stopped:
                # catch requests admitted while stop() was racing the loop
                # (popped from _pending before stop drained it)
                self._fail_all(EngineUnavailableError("engine stopped"))
                self._drain_ready(EngineUnavailableError("engine stopped"))
            # loop exit: the pipeline dies with the loop — no retire will
            # ever run, so drop the queue and its deferred frees here,
            # waiting out still-executing chunks off-thread before their
            # pages recycle (skipped on hard cancellation = teardown)
            dropped = list(self._inflight) + list(self._ragged_flights)
            self._inflight.clear()
            self._ragged_flights.clear()
            if self._ledger is not None:
                for slot in self._quarantine:
                    lifecycle_ledger.release("slot.quarantine", key=slot,
                                             domain=self, all_of_key=True)
            self._quarantine.clear()
            self._reset_device_chains()
            if (
                self.paged_cache is not None or self.state_cache is not None
            ) and dropped:
                try:
                    await asyncio.to_thread(self._wait_chunks, dropped)
                except BaseException:
                    pass
            if self.paged_cache is not None or self.state_cache is not None:
                # loop exit = no worker thread alive -> safe to reclaim every
                # slot whose request was failed out without freeing its pages
                for slot in range(self.max_batch):
                    if self._slot_req[slot] is None:
                        self._release_cache_slot(slot)
            self._recovering = False
            if self._stopped and self._watchdog_task is not None:
                # engine shut down for good: stop the supervisor too (a
                # drained-but-live engine keeps it — cancelling here would
                # race _ensure_loop's restart check on the next request)
                self._watchdog_task.cancel()

    async def _run_loop_inner(self) -> None:
        """The continuous-batching loop: admit (overlapped) -> decode -> emit.

        Admission prefills run as background tasks in worker threads, so
        decode chunks keep dispatching while long prompts prefill; only the
        cheap cache-insert commit synchronizes with the loop (chunk
        boundaries). TTFT no longer serializes behind other admissions, and
        decode throughput does not stall during admission (VERDICT r1 #6)."""
        self._wake = asyncio.Event()
        while not self._stopped:
            self._cycle.top(self._dispatch_seq + 1)
            # deadline sweep: queued requests expire where they wait
            self._expire_pending()
            # host-tier promotions that completed since the last boundary
            # (docs/kv_tiering.md): cheap no-op without in-flight DMAs
            self._reap_promotions()
            # SLO scheduling (docs/slo_scheduling.md): refresh the brownout
            # stage from the pressure signals, then — under slot pressure
            # with interactive work queued — preempt one batch-lane slot at
            # this chunk boundary before admissions run
            self._update_brownout()
            self._maybe_preempt()
            # launch admissions for pending requests into reserved free slots
            # (quarantined slots stay unavailable: an in-flight chunk still
            # decodes their previous occupant — docs/pipelined_decode.md)
            free = [
                i
                for i, r in enumerate(self._slot_req)
                if r is None
                and i not in self._admitting
                and i not in self._quarantine
            ]
            while free and not self._pending.empty():
                request = self._pending.get_nowait()
                if request.cancelled:
                    self._release_resume_pin(request)
                    request.out_queue.put_nowait(_FINISHED)
                    continue
                slot = free.pop(0)
                self._admitting.add(slot)
                request._slot_at = _clock()
                self._hist_request["queue_wait_ms"].observe(
                    (request._slot_at - request._queued) * 1e3
                )
                # hold a strong ref: the loop keeps only weak refs to tasks,
                # so an unreferenced admission could be GC'd mid-flight,
                # leaving the slot stuck in _admitting forever. Ragged mode
                # routes to the chunk-row admission (no prefill dispatch).
                task = asyncio.get_running_loop().create_task(
                    self._ragged_admission_task(request, slot)
                    if self._ragged
                    else self._admission_task(request, slot)
                )
                self._admission_tasks.add(task)
                task.add_done_callback(self._admission_tasks.discard)
            # commit finished prefills (loop thread; between decode chunks).
            # Interactive commits land first: a commit IS the first token,
            # so class order holds at this boundary too, not just at the
            # queue pop (docs/slo_scheduling.md)
            ready_batch = []
            while not self._ready.empty():
                ready_batch.append(self._ready.get_nowait())
            if len(ready_batch) > 1:
                ready_batch.sort(
                    key=lambda item: _CLASS_RANK.get(item[0].priority, 0)
                )
            for request, slot, first_id, mini_cache, first_lp in ready_batch:
                self._admitting.discard(slot)
                if request.cancelled:
                    self._deref_guided_request(request)
                    self._release_prefix_hit(request)
                    request.out_queue.put_nowait(_FINISHED)
                    continue
                err = self._deadline_error_at_commit(request)
                if err is not None:
                    # prefill outlived the request's ttft/total budget:
                    # structured 408 instead of a pointless slot commit
                    self._deref_guided_request(request)
                    self._release_prefix_hit(request)
                    request.error = err
                    request.out_queue.put_nowait(_FINISHED)
                    continue
                self._commit_admission(request, slot, first_id, mini_cache, first_lp)
                self._last_progress = time.monotonic()
            # ragged scheduler: open jobs for prepped admissions — their
            # prompts start riding this loop's launches as chunk rows
            while not self._ragged_ready.empty():
                request, slot = self._ragged_ready.get_nowait()
                if request.cancelled or request.error is not None:
                    self._release_resume_pin(request)
                    self._deref_guided_request(request)
                    request.out_queue.put_nowait(_FINISHED)
                    self._admitting.discard(slot)
                    continue
                job = self._start_ragged_job(request, slot)
                if job is not None:
                    self._prefill_jobs.append(job)
                    self._last_progress = time.monotonic()
            active_mask = np.array([r is not None for r in self._slot_req])
            if self._prefill_gate is not None:
                # open the gate while decode idles; pace prefills while active
                self._prefill_gate.set_active(
                    bool(active_mask.any() or self._inflight)
                )
            if (
                not active_mask.any()
                and not self._inflight
                and not self._prefill_jobs
                and not self._ragged_flights
            ):
                if (
                    self._pending.empty()
                    and self._ready.empty()
                    and not self._admitting
                ):
                    # drained: nothing owns pages but the prefix cache —
                    # anything else is a leak the sanitizer names by id
                    if faults.active():
                        # yield-point seam: the drained boundary, before
                        # the leak audit
                        faults.fire("engine.drain")
                    # straggler promotion DMAs must settle before the
                    # drained audit (and before the loop parks)
                    self._reap_promotions(force=True)
                    self._sanitize("drain", drained=True)
                    return  # drained; a new generate() restarts the loop
                # idle but admissions in flight: sleep until a prefill lands
                # or a new request arrives (no busy-spin)
                self._cycle.park()
                await self._wake.wait()
                self._wake.clear()
                continue
            # pipelined decode over the whole slot batch, supervised: a
            # dispatch/retire exception fails only the affected request(s)
            # and a watchdog trip (epoch bump) discards the whole in-flight
            # queue — the loop itself survives both and keeps serving
            step_epoch = self._recover_epoch
            # a serial iteration of the ragged phase (the step, or the
            # retire that drains the pipeline before it) that awaited its
            # worker has handed the event loop over already: the streams
            # its emission wakes are served inside the NEXT launch's
            # awaits, while the chip works, and not here, where it has
            # nothing queued
            handed_over = False
            try:
                if (
                    self._prefill_jobs
                    # a ragged launch is out: its step reads it back
                    or self._ragged_flights
                    or self._ragged_spec_wanted(active_mask)
                    # the state cache has ONE step: decode-only phases run
                    # it too (rows of one token and their chained windows)
                    or self.cache_mode == "state"
                ):
                    # ragged scheduling phase (docs/ragged_attention.md):
                    # drain the pipelined queue first (host mirrors must be
                    # current — same rule the legacy spec step used), then
                    # each iteration is ONE mixed launch of every decode
                    # row (multi-step windows), spec-verify row, and
                    # budget-bounded prefill-chunk row. With speculation
                    # on, spec rows ride these launches — the serial
                    # pipeline-draining spec scan never runs here.
                    if self._inflight:
                        handed_over = await self._retire_oldest()
                    else:
                        handed_over = await self._ragged_step(
                            active_mask, step_epoch
                        )
                else:
                    await self._decode_step(active_mask, step_epoch)
            except asyncio.CancelledError:
                raise
            except Exception as ex:
                await self._handle_step_failure(ex, step_epoch)
            self._cycle.mark("yield", self._dispatch_seq)
            # armed sanitizer: audit page accounting after every step —
            # including steps that just went through failure recovery, which
            # is exactly where reclamation bugs hide. A violation raises out
            # of the loop (fail loud beats serving corrupted KV).
            self._sanitize("decode-step")
            if not handed_over:
                await asyncio.sleep(0)  # let HTTP handlers interleave


    # -- pipelined decode: dispatch / retire ----------------------------------

    async def _decode_step(self, active_mask: np.ndarray, epoch: int) -> None:
        """One pipelined scheduling step. The in-flight queue fills to
        ``pipeline_depth - 1`` chunks, then every iteration OVERLAPS the
        oldest chunk's retirement (device->host readback + token emission,
        host work) with the next chunk's dispatch, which runs in a worker
        thread — on backends whose dispatch is asynchronous (TPU) the
        worker only enqueues; on backends that execute inline (current
        XLA:CPU) the worker carries the device compute itself. Either way
        chunk N's emission and chunk N+1's compute proceed concurrently,
        and the cross-chunk token dependency stays device-resident. At
        depth 1 this degenerates to the historical serial
        dispatch->sync->emit loop.

        Speculative chunks already amortize dispatch over k+1 verify
        positions and stay serial; they drain the pipeline first so the
        host-side token history they feed from is fully retired."""
        spec_masks = (
            self._spec_eligible_mask(active_mask)
            if self._speculation
            and active_mask.any()
            # the ragged scheduler never takes the serial spec scan: spec
            # rides its mixed launches as q=k+1 verify rows instead
            # (_ragged_spec_wanted routes those phases to _ragged_step)
            and not self._ragged
            # brownout stage 1+ parks speculation: the verify slack's page
            # over-allocation and the k wasted positions per reject are
            # exactly the headroom an overloaded engine no longer has
            and (self._brownout is None or self._brownout.stage < 1)
            else None
        )
        if spec_masks is not None and bool(
            spec_masks[0].any() or spec_masks[1].any()
        ):
            if self._inflight:
                # drain one chunk per step; commits keep landing between
                # steps at the loop top, as at any retire boundary
                await self._retire_oldest()
                return
            self._reset_device_chains()
            await self._spec_step(active_mask, spec_masks, epoch)
            return
        # fill: depth-1 keeps exactly one dispatch outstanding; deeper
        # pipelines keep depth-1 chunks queued ahead of the retire stage
        fill_target = max(1, self.pipeline_depth - 1)
        dispatch_mask = self._dispatchable_mask(active_mask)
        while dispatch_mask.any() and len(self._inflight) < fill_target:
            await self._dispatch_or_recover(dispatch_mask.copy(), epoch)
            # a dispatch can fail slots host-side (paged pool exhaustion):
            # drop them from the mask before topping up further
            active_mask &= np.array([r is not None for r in self._slot_req])
            dispatch_mask = self._dispatchable_mask(active_mask)
        if not self._inflight:
            return
        # the retiring chunk stays in the deque until its emissions land:
        # the concurrent dispatch's prep must still count its undelivered
        # steps (seeded-sampling counters, predictable-finish masking)
        entry = self._inflight[0]
        if dispatch_mask.any() and len(self._inflight) < self.pipeline_depth:
            # steady state: dispatch chunk N+1 (worker thread) while chunk
            # N retires (loop thread + readback worker) — the overlap that
            # hides the per-chunk host work behind device compute
            dispatch_res, retire_res = await asyncio.gather(
                self._dispatch_async(dispatch_mask.copy(), epoch),
                self._retire_beside_dispatch(entry),
                return_exceptions=True,
            )
            if self._inflight and self._inflight[0] is entry:
                self._inflight.popleft()
            # surface failures AFTER both stages settled (no orphaned
            # worker mutating engine state during recovery). A retire
            # failure reaching here is batch-wide (per-request retire
            # faults are isolated inside _retire_chunk) and outranks a
            # dispatch error: chunk N's tokens are lost for EVERY stream,
            # so the batch-wide reset must run even when the dispatch also
            # failed — raising only the dispatch error would silently skip
            # decode_steps tokens for the surviving requests.
            if isinstance(retire_res, BaseException):
                raise retire_res
            if isinstance(dispatch_res, BaseException):
                await self._recover_failed_dispatch()
                raise dispatch_res
        else:
            await self._retire_oldest()

    async def _dispatch_or_recover(self, mask: np.ndarray, epoch: int) -> None:
        """Dispatch with failure recovery, for call sites where no retire
        runs concurrently (the gather branch recovers after both settle)."""
        try:
            await self._dispatch_async(mask, epoch)
        except asyncio.CancelledError:
            raise
        except BaseException:
            await self._recover_failed_dispatch()
            raise

    async def _recover_failed_dispatch(self) -> None:
        """A dispatch raised after its prep consumed the commit overrides
        and advanced the RNG, but no chunk landed: retire whatever is still
        in flight (their results are valid — the failure happened before or
        instead of a new device program) so the host mirrors are current,
        then forget the device chains so the next dispatch re-uploads from
        them. Without this, a poisoned dispatch would leave an innocent
        freshly-committed slot chaining a stale token."""
        while self._inflight:
            await self._retire_oldest()
        self._reset_device_chains()

    async def _retire_oldest(self) -> bool:
        """Retire the oldest in-flight chunk; it leaves the queue only once
        its emissions landed (recovery may clear the queue mid-retire).
        _retire_chunk's answer."""
        entry = self._inflight[0]
        awaited = await self._retire_chunk(entry)
        if self._inflight and self._inflight[0] is entry:
            self._inflight.popleft()
        return awaited

    def _dispatchable_mask(self, active_mask: np.ndarray) -> np.ndarray:
        """Slots worth including in the NEXT chunk: active, and not already
        guaranteed to finish inside the chunks in flight. A request whose
        remaining max_new_tokens budget is covered by undelivered in-flight
        steps will be freed at an earlier retire — dispatching more compute
        for it is certain waste (stop-token finishes stay unpredictable and
        may still overshoot by design; their surplus tokens are dropped)."""
        if not self._inflight and self._dispatching is None:
            return active_mask
        pending_steps = np.zeros(self.max_batch, np.int64)
        for entry in self._inflight:
            pending_steps += entry.active_mask * self.decode_steps
        if self._dispatching is not None:
            pending_steps += self._dispatching[1] * self.decode_steps
        mask = active_mask.copy()
        for slot in np.nonzero(active_mask)[0]:
            request = self._slot_req[slot]
            if request is not None and (
                request.produced + pending_steps[slot]
                >= self._effective_max_new(request)
            ):
                mask[slot] = False
        return mask

    async def _dispatch_async(self, active_mask: np.ndarray, epoch: int) -> None:
        """Dispatch one chunk: shared host state is snapshotted on the loop
        thread (_prepare_dispatch), then the device call runs in a worker
        thread, possibly concurrently with the previous chunk's retirement.
        Appends the in-flight entry and fails pool-exhausted slots."""
        self._cycle.mark("plan", self._dispatch_seq + 1)
        prep = self._prepare_dispatch(active_mask, epoch)
        launch_at = self._cycle.mark("launch", prep["seq"])
        # barrier visibility: a slot freed by the concurrent retire stage
        # must see this chunk before its entry lands in the queue. The
        # timestamp bounds the watchdog's compile-tolerance grace.
        self._dispatching = (prep["seq"], active_mask, time.monotonic())
        try:
            entry = await asyncio.to_thread(self._dispatch_device, prep)
        finally:
            self._dispatching = None
        self._cycle.landed(entry.seq, launch_at, entry.stamps, _clock())
        if entry.epoch != self._recover_epoch:
            # the watchdog tripped while this chunk was being dispatched:
            # it was failed wholesale. Queue the entry so the discard path
            # waits out ITS device writes too, then reclaim.
            self._inflight.append(entry)
            await self._finish_recovery()
            return
        self._inflight.append(entry)
        self._count_decode_passes(entry.chain_work)
        self._count_sampler_passes(
            self.decode_steps, entry.live * self.decode_steps
        )
        for slot in entry.exhausted:
            self._fail_slot(
                slot, MemoryError("kv page pool exhausted for this sequence")
            )

    def _prepare_dispatch(self, active_mask: np.ndarray, epoch: int) -> dict:
        """Loop-thread half of a dispatch: snapshot every piece of shared
        host state the device call needs (slot table reads, device-constant
        caches, the chained token/DFA inputs, the RNG draw) so the worker
        thread never races the concurrently-running retire stage."""
        self._last_progress = time.monotonic()
        want_lp = any(
            self._slot_req[s] is not None
            and self._slot_req[s].logprobs is not None
            for s in np.nonzero(active_mask)[0]
        )
        use_extras = self._extras_active(active_mask)
        use_guided = bool(np.any(self._gstate[active_mask] >= 0))
        gtables = self._guided_device_tables() if use_guided else None
        tokens = self._chain_input(self._next_token_dev, self._next_token)
        gstate_in = (
            self._chain_input(self._gstate_dev, self._gstate)
            if gtables is not None
            else None
        )
        self._slot_overrides[:] = False
        self._dispatch_seq += 1
        if faults.active():
            # yield-point seam (docs/static_analysis.md, interleaving
            # explorer): the loop-thread snapshot is complete, the
            # worker-thread device call has not started — the boundary the
            # PR-4 host-buffer aliasing race lived on
            faults.fire(
                "engine.dispatch.prepare",
                requests=[r for r in self._slot_req if r is not None],
            )
        return {
            "seq": self._dispatch_seq,
            "epoch": epoch,
            "active_mask": active_mask,
            # copy: paged pool exhaustion mutates active_mask after this
            # (a zero-copy alias would flip the device value under the jit)
            "active_dev": jnp.asarray(active_mask.copy()),
            "want_lp": want_lp,
            "use_extras": use_extras,
            "sampling": self._batch_sampling(),
            "extras": self._batch_extras() if use_extras else None,
            "gtables": gtables,
            "gstate_in": gstate_in,
            "tokens": tokens,
            "rng": self._next_rng(),
            "lora": (
                jnp.asarray(self._lora_slots.copy())
                if self._lora_enabled
                else None
            ),
            "requests": [r for r in self._slot_req if r is not None],
        }

    def _dispatch_device(self, prep: dict) -> "_InFlightChunk":
        """Worker-thread half of a dispatch: the device program call (plus,
        on the paged backend, the host page allocation it needs). Only
        touches state the retire stage never reads: the cache/pool handles,
        the device-resident chains, and the dispatch histogram."""
        # in ``prep`` for _dispatch_paged, whose signature tests spy on
        stamps = prep["stamps"] = _WorkerStamps(prep["seq"])
        with self._sentry_scope("decode", seq=prep["seq"]), \
                jax.profiler.TraceAnnotation("engine.dispatch", seq=prep["seq"]), \
                stamps:
            return self._dispatch_device_inner(prep)

    def _dispatch_device_inner(self, prep: dict) -> "_InFlightChunk":
        if faults.active():
            # chaos seam (BEFORE any device dispatch, so a per-request
            # poison never corrupts innocent slots' cache state)
            faults.fire("engine.decode", requests=prep["requests"])
        active_mask = prep["active_mask"]
        use_extras = prep["use_extras"]
        gtables = prep["gtables"]
        want_lp = prep["want_lp"]
        exhausted: List[int] = []
        chain_work = (0, 0)
        live = active_mask
        if self.cache_mode == "paged":
            chunk, lp, gstate_out, chain_work, live = self._dispatch_paged(
                prep, exhausted
            )
        else:
            prep["stamps"].enqueue()
            chunk, self.cache, new_counts, lp, gstate_out = (
                self._decode_chunk_jit(
                    self.params,
                    prep["tokens"],
                    self.cache,
                    prep["active_dev"],
                    prep["sampling"],
                    prep["rng"],
                    prep["lora"],
                    prep["extras"],
                    self._counts_dev if use_extras else None,
                    self._pmask_dev if use_extras else None,
                    gtables,
                    prep["gstate_in"],
                    want_lp=want_lp,
                )
            )
            prep["stamps"].enqueued()
            if use_extras:
                self._counts_dev = new_counts
        # device-resident chaining: the NEXT dispatch reads these without
        # any host sync (chunk[:, -1] is a lazy slice of the pending output)
        self._next_token_dev = chunk[:, -1]
        self._gstate_dev = gstate_out if gtables is not None else None
        self._last_progress = time.monotonic()
        return _InFlightChunk(
            seq=prep["seq"],
            epoch=prep["epoch"],
            active_mask=active_mask,
            chunk=chunk,
            gstate=gstate_out if gtables is not None else None,
            lp=lp,
            want_lp=want_lp,
            stamps=self._worker_out(prep["stamps"]),
            exhausted=exhausted,
            chain_work=chain_work,
            live=live,
        )

    def _dispatch_paged(self, prep: dict, exhausted: List[int]):
        """Paged half of a chunk dispatch (worker thread). Pre-allocates
        each active slot's pages for the whole chunk host-side and hands
        the per-step write coordinates to the scan. Slots whose allocation
        fails are dropped from the chunk (their device rows write the null
        page; their tokens are discarded at retire) and reported through
        ``exhausted`` for the loop thread to fail — one sequence hitting
        pool capacity must not take the engine down."""
        active_mask = prep["active_mask"]
        pool = self.paged_cache.pool
        n = self.decode_steps
        lengths0 = pool.lengths().copy()          # pre-extension lengths
        # pass ``s`` of the chunk attends lengths0 + s + 1 tokens in every
        # row that holds any (the device's ``active``)
        live = lengths0 > 0
        held = lengths0[live]
        chain_work = _decode_pass_work(held + 1, np.full(held.shape, n))
        if self._by_kind is not None:
            pass_work, kinds, _ = self._by_kind
            chain_work += (pass_work(
                kinds, chain_first=held + 1,
                chain_passes=np.full(held.shape, n)),)
        write_pages = np.zeros((self.max_batch, n), np.int32)   # null page 0
        write_offsets = np.zeros((self.max_batch, n), np.int32)
        for slot in np.nonzero(active_mask)[0]:
            slot = int(slot)
            start = pool.slot_length(slot)
            try:
                # the chunk's decode_steps tokens land in these pages at
                # retire; a failed step frees them with the slot in the
                # loop's recovery (cross-function pairing the ownership
                # ledger audits)
                pool.extend(slot, n)  # tpuserve: ignore[TPU701] consumed by the chunk; recovery frees the slot
            except MemoryError:
                active_mask[slot] = False
                exhausted.append(slot)
                continue
            for i, (page, offset) in enumerate(pool.token_coords(slot, start, n)):
                write_pages[slot, i] = page
                write_offsets[slot, i] = offset
        # copy-on-write: extends may have swapped a shared tail page for a
        # private one; its contents must be duplicated before this chunk's
        # writes land in it (the copy consumes the in-flight chunk's output
        # pool handle, so ordering holds by data dependency)
        self.paged_cache.apply_pending_cow()
        page_table = pool.page_table(self._pages_per_seq)
        use_extras = prep["use_extras"]
        # dispatch under the pool lock: admission workers concurrently
        # enqueue prefix-page gathers against the same (here donated) pools
        page_table, lengths0_dev, write_pages, write_offsets = (
            jnp.asarray(page_table), jnp.asarray(lengths0),
            jnp.asarray(write_pages), jnp.asarray(write_offsets),
        )
        with self.paged_cache.dispatch_lock:
            prep["stamps"].enqueue()
            (
                chunk,
                self.paged_cache.k,
                self.paged_cache.v_carry,
                new_k_scale,
                new_v_scale,
                new_counts,
                lp,
                gstate_out,
            ) = self._decode_paged_chunk_jit(
                self.params,
                prep["tokens"],
                self.paged_cache.k,
                self.paged_cache.v_carry,
                self.paged_cache.k_scale,
                self.paged_cache.v_scale,
                page_table,
                lengths0_dev,
                write_pages,
                write_offsets,
                prep["sampling"],
                prep["rng"],
                prep["lora"],
                prep["extras"],
                self._counts_dev if use_extras else None,
                self._pmask_dev if use_extras else None,
                prep["gtables"],
                prep["gstate_in"],
                want_lp=prep["want_lp"],
            )
            prep["stamps"].enqueued()
            if self._paged_quant:
                self.paged_cache.k_scale = new_k_scale
                self.paged_cache.v_scale = new_v_scale
        if use_extras:
            self._counts_dev = new_counts
        return chunk, lp, gstate_out, chain_work, live

    def _worker_out(self, stamps: _WorkerStamps) -> tuple:
        """Worker thread, before its return: the launch's four reads for the
        loop thread. ``pipeline.dispatch_ms`` is worker_in -> worker_out of
        the same reads (upload + enqueue + tail of ``pipeline.launch_parts``),
        not a second measurement; its count is the launches."""
        reads = (*stamps.reads, _clock())
        self._hist_dispatch.observe((reads[3] - reads[0]) * 1e3)
        return reads

    def _count_decode_passes(self, work: tuple) -> None:
        """Loop thread: add a launch's :func:`_decode_pass_work`, and its
        keys by layer kind behind it (:func:`_latent_pass_work` over a latent
        page layout, :func:`_window_pass_work` over a windowed paged path)."""
        self.counters["decode_chain_rows"] += work[0]
        self.counters["decode_chain_kv_tokens"] += work[1]
        for name, n in (work[2] if len(work) > 2 else {}).items():
            self._by_kind[2][name] += n

    def _count_sampler_passes(self, passes: int, row_passes) -> None:
        """Loop thread: a launch sampled ``passes`` times and slot ``r`` was
        live in the first ``row_passes[r]`` of them. Counts how many of those
        calls took the sampler's sorted / drawing branch, from the host rows
        the launch's ``SamplingParams`` were copied from: a slot's rows
        change only at commit, which needs the slot free."""
        filters, draws = row_needs(
            self._temperature, self._top_k, self._top_p, row_passes > 0
        )
        count = self._sampler_passes
        count["passes"] += passes
        count["filtered_passes"] += int(
            np.max(row_passes, where=filters, initial=0)
        )
        count["drawn_passes"] += int(
            np.max(row_passes, where=draws, initial=0)
        )

    def _chain_input(self, dev, host_vec):
        """Next chunk's [B] input vector: chained from the previous chunk's
        device output when possible (no host->device upload), with host
        overrides (slots committed since the last dispatch) merged in.

        Host buffers are snapshot-COPIED before upload: jnp.asarray of a
        suitably-aligned numpy array is zero-copy on CPU, and these buffers
        are mutated in place (retire writebacks, commits) while the
        async-dispatched merge may not have read them yet — an alias there
        is a rare wrong-token race, observed in the A/B harness."""
        if dev is None:
            return jnp.asarray(host_vec.copy())
        if self._slot_overrides.any():
            return self._merge_rows_jit(
                dev,
                jnp.asarray(host_vec.copy()),
                jnp.asarray(self._slot_overrides.copy()),
            )
        return dev

    async def _retire_chunk(self, entry: "_InFlightChunk") -> bool:
        """Device->host readback + token emission for the OLDEST in-flight
        chunk, running while the next chunk computes. Every anchor point of
        the old serial loop re-lands here: slot frees / EOS handling,
        prefill-gate deposits, the watchdog-epoch check, the quarantine
        release, and (via the caller) the sanitizer audit — admission
        commits follow at the next loop top. True where the readback
        awaited its worker (the event loop's handlers ran meanwhile)."""

        t0 = self._cycle.mark("wait", entry.seq)
        chunk_np, (gstate_np, lp_np), _, awaited = await self._read_back(
            entry.seq,
            entry.chunk,
            (entry.gstate, entry.lp),
        )
        self._cycle.mark("emit", entry.seq)
        if entry.epoch != self._recover_epoch:
            # the watchdog failed this batch while the pipeline was in
            # flight: every queued chunk is stale — discard them all and
            # reclaim (epoch bump covers the whole in-flight queue).
            # _finish_recovery defers itself while the concurrent dispatch
            # leg is mid-worker; that leg completes recovery on landing.
            await self._finish_recovery()
            return awaited
        if faults.active():
            try:
                # chaos seam: a retire-stage failure (host emission path)
                # with younger chunks possibly still in flight
                faults.fire(
                    "engine.decode.retire",
                    requests=[r for r in self._slot_req if r is not None],
                )
            except faults.InjectedFault as ex:
                if ex.request is None:
                    raise  # batch-wide: loop-level step-failure handling
                self.counters["step_failures"] += 1
                for slot, request in enumerate(self._slot_req):
                    if request is ex.request:
                        self._fail_slot(
                            slot,
                            EngineStepError(
                                "retire failed for this request: {}".format(ex)
                            ),
                        )
                        break
                # fall through: the rest of the chunk still emits
        slots = [int(s) for s in np.nonzero(entry.active_mask)[0]]
        for slot in slots:
            # host mirrors re-anchor at retire (the device chain moved on
            # at dispatch); slots committed after this chunk's dispatch are
            # not in its mask, so fresh state is never clobbered, and a
            # slot whose row ended in an older chunk keeps what its release
            # wrote (its DFA state cleared: the next owner's)
            if self._slot_req[slot] is None:
                continue
            self._next_token[slot] = int(chunk_np[slot, -1])
            if gstate_np is not None:
                self._gstate[slot] = int(gstate_np[slot])
        if self._prefill_gate is not None:
            # decode chunk done: grant the next prefill-dispatch budget
            self._prefill_gate.deposit()
        for slot in slots:
            for i, token_id in enumerate(chunk_np[slot]):
                # _emit frees the slot on finish; the rest of the chunk for
                # that slot is dropped by the None check inside _emit
                lp_entry = None
                if lp_np is not None:
                    chosen, top_id, top_lp = lp_np
                    lp_entry = {
                        "id": int(token_id),
                        "logprob": float(chosen[slot, i]),
                        "top_ids": top_id[slot, i].tolist(),
                        "top_logprobs": top_lp[slot, i].tolist(),
                    }
                self._emit(slot, int(token_id), lp_entry)
        self._release_quarantine(entry.seq)
        # promotion completion is a retire-stage event (docs/kv_tiering.md):
        # a DMA that finished while this chunk computed cost the loop nothing
        self._reap_promotions()
        self._last_progress = time.monotonic()
        t1 = self._cycle.mark("yield", entry.seq)
        self._hist_retire.observe((t1 - t0) * 1e3)
        return awaited

    async def _retire_beside_dispatch(self, entry: "_InFlightChunk") -> None:
        """The gather leg of the steady pipelined step: once the retire is
        through, what is left of the concurrent dispatch to wait for is
        the cycle's ``launch`` time."""
        await self._retire_chunk(entry)
        self._cycle.mark("launch", self._dispatch_seq)

    async def _spec_step(self, active_mask: np.ndarray, spec_masks,
                         epoch: int) -> None:
        """Serial speculative step (draft-and-verify rounds); the pipeline
        is already drained when this runs. Unchanged semantics from the
        pre-pipelining loop."""
        spec_mask, sspec_mask = spec_masks
        want_lp = any(
            self._slot_req[s] is not None
            and self._slot_req[s].logprobs is not None
            for s in np.nonzero(active_mask)[0]
        )
        sampling = self._batch_sampling()
        # draft-and-verify rounds: device work off-loop, emission on
        # the loop thread like the plain path
        gs, accs, pending, lp_np = await asyncio.to_thread(
            self._dispatch_spec_chunk,
            active_mask, spec_mask, sspec_mask, sampling,
            want_lp,
        )
        if epoch != self._recover_epoch:
            await self._finish_recovery()
            return
        # each round samples the rows that are not speculating
        self._count_sampler_passes(
            gs.shape[0],
            gs.shape[0] * (active_mask & ~spec_mask & ~sspec_mask),
        )
        for r in range(gs.shape[0]):
            for slot in np.nonzero(active_mask)[0]:
                slot = int(slot)
                for i in range(int(accs[r, slot]) + 1):
                    entry = None
                    if (
                        lp_np is not None
                        and i == 0
                        and not spec_mask[slot]
                        and not sspec_mask[slot]
                    ):
                        chosen, top_id, top_lp = lp_np
                        entry = {
                            "id": int(gs[r, slot, 0]),
                            "logprob": float(chosen[r, slot]),
                            "top_ids": top_id[r, slot].tolist(),
                            "top_logprobs": top_lp[r, slot].tolist(),
                        }
                    self._emit(slot, int(gs[r, slot, i]), entry)
        for slot in np.nonzero(active_mask)[0]:
            self._next_token[slot] = int(pending[slot])
        if self._prefill_gate is not None:
            self._prefill_gate.deposit()
        self._last_progress = time.monotonic()
