"""Fault-injection seam for request-lifecycle chaos testing.

Production code calls :func:`fire` at a handful of well-known points; with no
faults configured the call is a single attribute read (``active()`` short
circuit), so the seam costs nothing on the hot path. Tests (and operators,
via the ``TPUSERVE_FAULTS`` env var) arm :class:`FaultSpec` entries that make
a point delay, raise, or surface a fake gRPC status — which is how the chaos
suite proves the deadline, shedding, watchdog-recovery, and retry paths
without real hardware failures.

Known points (ctx carried with each):

- ``engine.prefill``   — inside the admission worker, before device prefill
                         (``request``); ``delay`` = slow prefill,
                         ``raise`` = failed admission.
- ``engine.decode``    — inside the decode-chunk dispatch worker, before the
                         device step (``requests`` = active GenRequests);
                         ``match_token`` poisons only the request whose
                         prompt contains that token; ``delay`` = stuck loop.
- ``engine.decode.stall`` — at the top of a speculative decode dispatch
                         (``requests``), before any page over-allocation;
                         ``delay`` models a slow spec round wedging the
                         loop (the watchdog's view of a stuck spec scan),
                         ``raise`` fails the dispatch before it touches
                         the pool. Also in the worker that waits for a
                         launch's results (a chunk's retire sync, a ragged
                         step's read): ``delay`` there is a launch that
                         does not come back, with the event loop free.
- ``engine.decode.retire`` — on the loop thread at chunk retirement, after
                         the device->host sync and before emission
                         (``requests``); ``match_token`` fails only the
                         matched request (the rest of the chunk still
                         emits), an unmatched raise is a batch-wide retire
                         failure. Younger chunks may still be in flight.
- ``engine.admit``     — inside check_admission (``request``); a raise is
                         converted to a load-shed (429).
- ``engine.admit.class`` — inside check_admission's class-aware admission
                         path (``request``); a raise forces a class-policy
                         shed (429 with the request's priority class in the
                         payload) regardless of queue state.
- ``engine.admit.budget`` — ragged scheduler (docs/ragged_attention.md): on
                         the loop thread as one prefill job's chunk is
                         admitted into a step's token budget (``request``);
                         a raise sheds that admission (structured 429) —
                         decode rows and the other jobs ride the step
                         untouched.
- ``engine.pool``      — inside check_admission's KV-pool headroom check; a
                         raise simulates pool exhaustion.
- ``engine.preempt``   — on the loop thread mid-preemption, AFTER the
                         victim's generated-so-far KV was committed into the
                         radix prefix cache and BEFORE its slot is freed /
                         the request requeued (``request``); a raise aborts
                         the preemption — the armed KV sanitizer must stay
                         green (the store alone is a normal admission-commit
                         store, so nothing may leak).
- ``engine.release``   — at paged-slot teardown, before the slot's pages are
                         freed (``request``); a raise simulates a teardown
                         bug that LEAKS the slot's pages — the KV sanitizer
                         (llm/kv_sanitizer.py, TPUSERVE_SANITIZE=1) must
                         catch it at drain.
- ``engine.kv.demote`` — in the radix prefix cache as device-budget eviction
                         is about to demote a cached run's pages to the
                         host-RAM tier (``pages``; docs/kv_tiering.md); a
                         raise aborts the demotion — the node drops for
                         real (legacy eviction), leak-free under the armed
                         sanitizer.
- ``engine.compile.bucket`` — inside the engine's prefill bucket picker
                         (``_bucket_for``); a raise makes the picker return
                         the RAW request length instead of a bucket — the
                         seeded shape-drift defect of the compile-surface
                         discipline (docs/static_analysis.md TPU6xx): every
                         novel prompt length then mints a fresh XLA program,
                         which the armed compile sentry
                         (llm/compile_sentry.py) must count post-fence and,
                         in strict mode, raise on. Proven caught by the
                         sentry self-test in tests/test_compile_sentry.py.
- ``engine.shard.drift`` — inside the engine's sharding-sentry audit-entry
                         builder (``_shard_audit_entries``); a raise swaps a
                         HOST-MATERIALIZED numpy copy in for the chained
                         decode row — the seeded implicit-transfer defect of
                         the sharding discipline (docs/static_analysis.md
                         TPU8xx): the armed sharding sentry
                         (llm/sharding_sentry.py) must count it as an
                         implicit device->host transfer and, in strict mode,
                         raise naming the array path and declared-vs-actual
                         spec. Proven caught by the sentry self-test in
                         tests/test_sharding_sentry.py.
- ``engine.kv.promote`` — as a lookup on a demoted run is about to allocate
                         device pages and enqueue the host→device re-online
                         DMA (``pages``); a raise aborts the promotion — the
                         demoted suffix drops, the hit shortens to the
                         resident prefix, and the tail falls back to
                         recompute with zero page leaks.
- ``engine.kv.ship``   — on the prefill replica's loop thread at commit,
                         BEFORE the finished admission's prefix pages are
                         exported into a KV-transport shipment
                         (``request``; docs/disaggregation.md); a raise
                         aborts the ship leak-free — nothing reaches the
                         transport, and the decode replica falls back to
                         recomputing the prefix.
- ``kv.ship.partial``  — on the prefill replica's loop thread as a
                         DRAFT-AHEAD partial shipment (storable pages of a
                         still-running prefill, docs/spec_decode_trees.md)
                         is about to export at a chunk boundary
                         (``request``); a raise aborts the job's entire
                         draft-ahead stream AND the commit-time seal — the
                         receiver's unsealed assembly is never consumable,
                         so the decode replica falls back to recompute
                         with zero page leaks on either side.
- ``engine.spec.tree`` — on the loop thread in the ragged scheduler's
                         step planner, after spec-verify eligibility is
                         decided and BEFORE drafts are proposed or any
                         row laid out (``requests`` = the eligible
                         slots' GenRequests); ``match_token`` demotes
                         only the matched request's row to PLAIN DECODE
                         in the same launch (an unmatched raise demotes
                         every verify row that step). Nothing was
                         allocated yet, so the fallback is leak-free by
                         construction and the stream stays byte-identical
                         — the row just decodes without drafts.
- ``engine.kv.receive`` — on the decode replica as a popped shipment is
                         about to import (fresh device pages + the fenced
                         host→device scatter + radix-cache attach;
                         ``request`` carries the prompt ids); a raise
                         drops the shipment with zero page leaks and the
                         replica group re-routes the stream to a
                         hybrid-capable sibling (recompute there).
- ``engine.ledger.leak`` — at the preemption resume-pin teardown
                         (``_release_resume_pin``), AFTER the handle is
                         detached from the request and BEFORE the
                         underlying unpin runs (``request``); a raise
                         models a lost free — the handle drops, the unpin
                         never fires, and the armed ownership ledger
                         (llm/lifecycle_ledger.py, TPUSERVE_LEDGER) must
                         name the leaked ``prefix.resume_pin`` and its
                         acquire site at the drain audit. Node pins are
                         invisible to page refcount accounting, so this
                         leak class is the ledger's alone.
- ``engine.dispatch.prepare`` — on the loop thread at the end of
                         ``_prepare_dispatch`` (``requests``): the shared
                         host state is snapshotted, the worker-thread device
                         call has not started. The boundary where the PR-4
                         host-buffer aliasing window sat; the interleaving
                         explorer (llm/schedule_explorer.py) permutes thread
                         orderings at exactly this class of seam.
- ``engine.watchdog``  — at the top of a watchdog trip, before the epoch
                         bump and in-flight request failure (``requests``);
                         ``delay`` = slow trip, ``raise`` = the watchdog
                         task dies until the next request restarts it.
- ``engine.drain``     — on the loop thread at the drained boundary, before
                         the drained sanitizer audit; a raise fails the loop
                         through the structured step-failure path.
- ``transport.wire.send`` — in the socket KV-transport backend
                         (llm/kv_wire.py) before a shipment is framed and
                         written to the destination replica's listener; a
                         raise drops the shipment sender-side (counted wire
                         send failure, ``send`` returns False) and the
                         decode replica recomputes — the same
                         drop-to-recompute contract as a full receive slab.
- ``transport.wire.recv`` — on the receiving endpoint's listener thread
                         before a received frame is decoded/validated; a
                         raise drops the frame leak-free (nothing was
                         attached — the slabs are views into the frame
                         buffer), nacks the sender, and the stream falls
                         back to recompute. The same path truncated or
                         geometry-lying frames take via WireFormatError.
- ``replica.proc.crash`` — in the process-replica supervisor's heartbeat
                         (serving/process_replica.py) with the replica
                         INDEX as the shim's ``prompt_ids`` (the
                         ``router.eject`` convention); ``match_token:
                         <index>`` SIGKILLs exactly that worker process —
                         the chaos suite's handle for a real worker death
                         (EOF mid-stream -> history-as-prompt failover,
                         ejection, bounded restart-with-rewarm).
- ``router.pick``      — in the replica router as a route decision is
                         about to return its pick (``request``;
                         serving/replica_router.py, docs/replication.md);
                         a raise makes the router fall to the next ring
                         member (counted as a ``rebalance``) instead of
                         failing the request — the structured-fallback
                         contract of the routing path.
- ``router.eject``     — fired per replica during each ring sweep; the
                         carried shim's ``prompt_ids`` holds the replica
                         INDEX, so ``match_token: <index>`` force-ejects
                         exactly that replica from the ring while the
                         spec stays armed. Used by the chaos suite to
                         prove ejection drains traffic to siblings and
                         re-admission re-warms through the warmup gate.
- ``grpc.call``        — before each gRPC attempt (``attempt``); set
                         ``grpc_code`` ("UNAVAILABLE"/"DEADLINE_EXCEEDED")
                         to exercise the transient-retry path.

The three ``engine.dispatch.prepare``/``engine.watchdog``/``engine.drain``
points double as the engine's YIELD-POINT SEAMS for the deterministic
interleaving explorer (llm/schedule_explorer.py): together with the
existing dispatch/retire/preempt points they mark every thread-ownership
boundary of the pipelined loop, and the explorer's scenario seam labels
must stay a subset of this registry (test_schedule_explorer pins that).

Every point a production call site fires MUST be listed in
:data:`KNOWN_POINTS`: the static analyzer (``tpuserve-analyze`` TPU403)
checks call-site literals against it, and :func:`configure` rejects specs
targeting unknown points — a typo'd point would otherwise arm a fault that
never fires and silently prove nothing.

Env format (``TPUSERVE_FAULTS``): a JSON list of spec dicts, e.g.::

    TPUSERVE_FAULTS='[{"point": "engine.decode", "action": "raise",
                       "match_token": 300, "times": 1}]'
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, List, Optional


# the registry of production fault seams (module docstring documents each).
# tpuserve-analyze parses this assignment from source (stdlib ast, no import)
# — keep it a literal.
KNOWN_POINTS = frozenset({
    "engine.prefill",
    "engine.decode",
    "engine.decode.stall",
    "engine.decode.retire",
    "engine.dispatch.prepare",
    "engine.watchdog",
    "engine.drain",
    "engine.admit",
    "engine.admit.class",
    "engine.admit.budget",
    "engine.pool",
    "engine.preempt",
    "engine.release",
    "engine.kv.demote",
    "engine.kv.promote",
    "engine.kv.ship",
    "kv.ship.partial",
    "engine.kv.receive",
    "engine.spec.tree",
    "engine.ledger.leak",
    "engine.compile.bucket",
    "engine.shard.drift",
    "transport.wire.send",
    "transport.wire.recv",
    "replica.proc.crash",
    "router.pick",
    "router.eject",
    "grpc.call",
})


@dataclass
class FaultSpec:
    point: str
    action: str = "raise"          # "raise" | "delay"
    times: int = -1                # firings before the spec disarms (-1 = inf)
    delay: float = 0.0             # seconds slept before acting
    match_token: Optional[int] = None  # only fire when a request's prompt has it
    grpc_code: Optional[str] = None    # fake upstream status for grpc points
    message: str = "injected fault"
    fired: int = field(default=0, compare=False)

    def exhausted(self) -> bool:
        return 0 <= self.times <= self.fired


class InjectedFault(Exception):
    """Raised by an armed ``action="raise"`` spec. Carries the spec and the
    matched request (when ``match_token`` selected one) so the engine can
    fail ONLY that request instead of the whole batch."""

    def __init__(self, spec: FaultSpec, request: Any = None):
        super().__init__("{} [{}]".format(spec.message, spec.point))
        self.spec = spec
        self.request = request

    @property
    def grpc_code(self) -> Optional[str]:
        return self.spec.grpc_code


class FaultInjector:
    def __init__(self):
        self._specs: List[FaultSpec] = []
        self._lock = threading.Lock()
        self.load_env()

    # -- configuration ----------------------------------------------------

    def configure(self, specs) -> None:
        """Arm the given specs (list of FaultSpec or dicts). Replaces any
        previously armed set. Unknown points are rejected loudly — a spec
        that can never fire reads as "chaos test passed"."""
        armed = []
        for s in specs or []:
            spec = s if isinstance(s, FaultSpec) else FaultSpec(**s)
            if spec.point not in KNOWN_POINTS:
                raise ValueError(
                    "unknown fault point {!r} (known: {})".format(
                        spec.point, ", ".join(sorted(KNOWN_POINTS))
                    )
                )
            armed.append(spec)
        with self._lock:
            self._specs = armed

    def clear(self) -> None:
        with self._lock:
            self._specs = []

    def load_env(self) -> None:
        raw = os.environ.get("TPUSERVE_FAULTS")
        if not raw:
            return
        try:
            specs = json.loads(raw)
        except ValueError as ex:
            raise ValueError("unparseable TPUSERVE_FAULTS: {}".format(ex))
        # configure() raises its own precise error for valid-JSON specs with
        # an unknown point/field — don't relabel that as a parse failure
        self.configure(specs)

    def active(self) -> bool:
        return bool(self._specs)

    # -- firing -----------------------------------------------------------

    @staticmethod
    def _match(spec: FaultSpec, request, requests) -> Any:
        """The request a spec applies to, or None when match_token filters
        everything out. Specs without match_token apply unconditionally."""
        if spec.match_token is None:
            return request
        candidates = list(requests or [])
        if request is not None:
            candidates.append(request)
        for r in candidates:
            if spec.match_token in (getattr(r, "prompt_ids", None) or []):
                return r
        return None

    def fire(self, point: str, request: Any = None, requests=None, **ctx) -> None:
        """Run every armed spec for ``point``: sleep for ``delay`` actions,
        raise :class:`InjectedFault` for ``raise`` actions. No-op when
        nothing matches."""
        with self._lock:
            specs = [s for s in self._specs if s.point == point]
        for spec in specs:
            target = self._match(spec, request, requests)
            if spec.match_token is not None and target is None:
                continue
            with self._lock:
                # check-and-claim one firing atomically: the loop thread and
                # dispatch workers race here, and a times-bounded spec must
                # never fire more than its limit
                if spec.exhausted():
                    continue
                spec.fired += 1
            if spec.delay:
                time.sleep(spec.delay)
            if spec.action == "raise":
                raise InjectedFault(spec, target)


# module singleton: production call sites and tests share it
injector = FaultInjector()


def active() -> bool:
    return injector.active()


def fire(point: str, request: Any = None, requests=None, **ctx) -> None:
    if injector.active():
        injector.fire(point, request=request, requests=requests, **ctx)


def configure(specs) -> None:
    injector.configure(specs)


def clear() -> None:
    injector.clear()
