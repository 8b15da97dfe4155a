"""Runtime compile sentry: attribute every serve-time XLA compilation
(docs/static_analysis.md TPU6xx — the dynamic net behind the static rules).

The compile-surface invariant says the set of (function, shape, dtype)
keys the serve loop presents to XLA is finite and fully compiled before
serving starts. The static analyzer proves the bucketizer discipline the
invariant rests on; this sentry proves the INVARIANT ITSELF at runtime:
armed with ``TPUSERVE_COMPILE_SENTRY=1`` (count) or ``=strict`` (raise),
it listens to JAX's compile events, splits compilations at the warmup fence
(``llm/warmup.py`` sets it after the sweep), attributes each post-fence
compilation to the in-flight launch (phase, dispatch seq, pipeline depth —
the engine tags its dispatch workers through a thread-local context), and
feeds ``engine_xla_compiles_total{phase}`` / ``engine_xla_compile_ms``
(statistics/metrics.py). In strict mode a post-fence compilation records a
violation naming the jitted function; the engine
raises :class:`CompileSentryError` for it at the next loop boundary (the
same check-at-the-boundary shape as the KV sanitizer).

Hook mechanics: one listener on the public ``jax.monitoring`` duration
event ``/jax/core/compile/backend_compile_duration``, which JAX records
once per executable it builds (a persistent-compilation-cache hit
included: the event wraps the build-or-fetch), on the thread that
triggered it, with the jitted function's name as ``fun_name`` and the
wall time as the duration. Argument avals are not part of the event; the
thread context carries what the engine knows about the launch (phase,
prompt length, dispatch seq) instead.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax.monitoring

ENV = "TPUSERVE_COMPILE_SENTRY"

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# scrape-time histogram edges (ms): compile stalls live in the 10 ms (tiny
# eager op) .. multi-second (big fused graph) range
_BUCKETS_MS = (10.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 5000.0)

# keep full per-compile attribution for the most recent N events; counters
# and the histogram are unbounded
_MAX_EVENTS = 256


def enabled() -> bool:
    return os.environ.get(ENV, "") not in ("", "0")


def strict_enabled() -> bool:
    return os.environ.get(ENV, "") == "strict"


class CompileSentryError(RuntimeError):
    """A post-warmup-fence XLA compilation under strict mode: names the
    jitted function and the launch context it was attributed to."""


class CompileSentry:
    """Process-wide compile listener (one per process: the hook surface is
    global). Thread-safe; attribution context is thread-local so worker
    threads tag the compiles their own dispatches trigger."""

    def __init__(self, strict: bool = False):
        self.strict = bool(strict)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._fence = False
        self._installed = False
        self.counts = {"warmup": 0, "serve": 0}
        self._hist_counts = [0] * (len(_BUCKETS_MS) + 1)
        self._hist_sum_ms = 0.0
        self.events: List[Dict[str, Any]] = []
        self.violations: List[Dict[str, Any]] = []

    # -- install / uninstall ----------------------------------------------

    def install(self) -> "CompileSentry":
        if not self._installed:
            jax.monitoring.register_event_duration_secs_listener(
                self._on_event
            )
            self._installed = True
        return self

    def uninstall(self) -> None:
        if self._installed:
            jax.monitoring.unregister_event_duration_listener(self._on_event)
            self._installed = False

    # -- attribution context ----------------------------------------------

    @contextlib.contextmanager
    def context(self, **ctx):
        """Tag compiles triggered on THIS thread (the engine wraps its
        dispatch/prefill workers: phase, dispatch seq, pipeline depth)."""
        prev = getattr(self._tls, "ctx", None)
        self._tls.ctx = dict(prev or {}, **ctx)
        try:
            yield
        finally:
            self._tls.ctx = prev

    # -- event intake ------------------------------------------------------

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event != _BACKEND_COMPILE_EVENT:
            return
        fn = str(kwargs.get("fun_name") or "<unknown>")
        duration_ms = duration * 1e3
        ctx = dict(getattr(self._tls, "ctx", None) or {})
        with self._lock:
            phase = "serve" if self._fence else "warmup"
            self.counts[phase] += 1
            event = {
                "fn": fn,
                "phase": phase,
                "context": ctx,
                "t": time.time(),
                "duration_ms": duration_ms,
            }
            self.events.append(event)
            del self.events[:-_MAX_EVENTS]
            self._observe_locked(duration_ms)
            # a `lazy=True` context marks a __compile_keys__ "lazy"-role
            # entry (one bounded compile per variant on first use, by
            # declared design): counted and attributed, never a violation
            if phase == "serve" and self.strict and not ctx.get("lazy"):
                self.violations.append(event)

    def _observe_locked(self, ms: float) -> None:
        for i, edge in enumerate(_BUCKETS_MS):
            if ms <= edge:
                self._hist_counts[i] += 1
                break
        else:
            self._hist_counts[len(_BUCKETS_MS)] += 1
        self._hist_sum_ms += ms

    # -- fence / check / stats --------------------------------------------

    def fence(self) -> None:
        """Everything compiled so far was warmup; everything after is a
        serve-time compile (and, in strict mode, a violation)."""
        with self._lock:
            self._fence = True

    def reset(self, strict: Optional[bool] = None) -> None:
        """Drop the fence and all accumulated state (tests; a new engine's
        warmup phase starts clean)."""
        with self._lock:
            self._fence = False
            self.counts = {"warmup": 0, "serve": 0}
            self.events = []
            self.violations = []
            self._hist_counts = [0] * (len(_BUCKETS_MS) + 1)
            self._hist_sum_ms = 0.0
            if strict is not None:
                self.strict = bool(strict)

    def check(self, where: str = "") -> None:
        """Raise the first pending strict violation (engine loop
        boundaries call this the way they call the KV sanitizer)."""
        with self._lock:
            if not (self.strict and self.violations):
                return
            v = self.violations[0]
        raise CompileSentryError(
            "XLA compiled {} AFTER the warmup fence{}{} — "
            "a serve-time compile stall; extend llm/warmup.py's sweep or "
            "bucketize the shape source (docs/static_analysis.md TPU6xx)"
            .format(
                v["fn"],
                " at {}".format(where) if where else "",
                " (context: {})".format(v["context"]) if v["context"] else "",
            )
        )

    @property
    def post_fence_compiles(self) -> int:
        with self._lock:
            return self.counts["serve"]

    def hist_snapshot(self) -> Dict[str, Any]:
        """engine._MsHistogram-shaped snapshot (buckets/counts/sum_ms) so
        the metrics collector reuses its histogram plumbing."""
        with self._lock:
            return {
                "buckets": list(_BUCKETS_MS),
                "counts": list(self._hist_counts),
                "sum_ms": self._hist_sum_ms,
            }

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "strict": self.strict,
                "fenced": self._fence,
                "compiles": dict(self.counts),
                "violations": len(self.violations),
                "events": [dict(e) for e in self.events],
            }

    def stats_brief(self) -> Dict[str, Any]:
        """The lifecycle_stats()/health() "compile" block (and what the
        metrics collector reads): counters + histogram, no event list."""
        with self._lock:
            return {
                "strict": self.strict,
                "fenced": self._fence,
                "warmup": self.counts["warmup"],
                "serve": self.counts["serve"],
                "violations": len(self.violations),
                "compile_ms": {
                    "buckets": list(_BUCKETS_MS),
                    "counts": list(self._hist_counts),
                    "sum_ms": self._hist_sum_ms,
                },
            }


# -- module singleton ---------------------------------------------------------

_sentry: Optional[CompileSentry] = None
_sentry_lock = threading.Lock()


def get() -> CompileSentry:
    """The process-wide sentry, installed on first use (strictness from
    the env at creation; tests flip ``.strict`` / call ``.reset()``)."""
    global _sentry
    with _sentry_lock:
        if _sentry is None:
            _sentry = CompileSentry(strict=strict_enabled()).install()
        return _sentry
