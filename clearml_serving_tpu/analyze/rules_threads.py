"""TPU5xx — thread-affinity discipline over the pipelined engine.

The engine tier is a small orchestration system: an asyncio loop thread
(handlers, the decode loop, the watchdog), ``asyncio.to_thread`` dispatch /
readback / prefill workers (docs/pipelined_decode.md), and daemon threads on
the control plane (model_request_processor's sync + stats senders). Which
thread may touch which state is the load-bearing correctness rule of that
design — and before this rule family it lived only in comments ("loop-thread
only", "worker thread half") and reviewer memory.

The pass builds a **thread-context call graph** per module (stdlib ast only,
intra-module, like every other rule family):

- roots: every ``async def`` body runs on the **loop** thread; every function
  handed to ``asyncio.to_thread(f, ...)``, ``threading.Thread(target=f)`` or
  ``loop.run_in_executor(None, f)`` runs on a **worker** thread;
- propagation: contexts flow through intra-module calls (``self.m()``, bare
  ``f()`` through the lexical scope chain, and ``x.m()`` when ``m`` names
  exactly one method in the module) to a fixpoint. A function reachable from
  both kinds of root carries BOTH contexts.

Known blind spots (documented in docs/static_analysis.md): cross-module
calls, dynamic dispatch (callables in variables, ``getattr``), and functions
never reached from a root (no context -> not checked). The rules fail open
on those — the deterministic interleaving explorer
(llm/schedule_explorer.py) is the dynamic net behind this static one.

Rules:

- **TPU501** — a function reachable from the wrong thread mutates state
  declared thread-affine via the ``__affine_to__`` class annotation
  (sibling of ``__guarded_by__``)::

      class LLMEngineCore:
          __affine_to__ = {"loop": ("_inflight", "_quarantine", ...),
                           "worker": ("_next_token_dev", ...)}

  Affinity is the third synchronization discipline next to lock-guarded
  (``__guarded_by__`` / TPU301) and immutable: affine state has NO lock on
  purpose — exactly one thread owns it — so an off-thread mutation is a
  data race with no second chance at runtime.

- **TPU502** — cross-thread handoff of a mutable host buffer without a
  copy: ``jnp.asarray(self._buf)`` on a shared host mirror.
  ``jnp.asarray`` of a suitably-aligned numpy array is ZERO-COPY on CPU,
  and the resulting device value may be read lazily, after the producer
  thread has mutated the buffer in place — the exact rare wrong-token race
  PR 4 fixed by hand in ``_prepare_dispatch``/``_chain_input``. Snapshot
  with ``.copy()`` at the handoff.

- **TPU503** — ``await`` while holding a synchronous lock (``with
  self._lock: ... await ...``): every other coroutine on the loop that
  needs the lock deadlocks against the suspended holder, and worker
  threads convoy behind an arbitrarily long suspension.

- **TPU504** — a "lock held by caller" helper (a ``# tpuserve:
  ignore[TPU301]``-annotated method mutating ``__guarded_by__`` state)
  called from thread-context code WITHOUT the declared lock lexically
  held. TPU301's scope ignores are load-bearing holes; this closes them
  across the call graph, so the donated-handle rebind helpers can never be
  reached lock-free from either thread.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

from . import Finding, RULES, _ignore_map, dotted_name as _dotted
from .rules_locks import (
    PROJECT_REGISTRY as _GUARDED_REGISTRY,
    _MUTATORS,
    _file_declarations as _guarded_declarations,
    _strip_subscripts,
)

LOOP = "loop"
WORKER = "worker"
_THREADS = (LOOP, WORKER)

# attr name -> (owning thread, receiver-basename filter or None), mirroring
# the __affine_to__ declarations at the definition sites the same way
# rules_locks.PROJECT_REGISTRY mirrors __guarded_by__ (test_analyze checks
# the two agree). Cross-module pokes of affine state are rare but real —
# keep names distinctive enough for a None filter.
AFFINITY_REGISTRY: Dict[str, Tuple[str, Optional[Tuple[str, ...]]]] = {
    # engine.LLMEngineCore pipeline/quarantine/chain state
    # (docs/pipelined_decode.md): owned by the event-loop thread; dispatch
    # workers receive snapshots (prep dicts), never these attrs
    "_inflight": (LOOP, ("self", "engine")),
    "_quarantine": (LOOP, ("self", "engine")),
    "_dispatching": (LOOP, ("self", "engine")),
    "_slot_req": (LOOP, None),
    "_admitting": (LOOP, None),
    "_next_token": (LOOP, ("self", "engine")),
    "_gstate": (LOOP, ("self", "engine")),
    "_slot_overrides": (LOOP, None),
    # ragged scheduler job list (docs/ragged_attention.md): the loop opens,
    # shares out, and retires jobs; dispatch workers only read plan dicts
    "_prefill_jobs": (LOOP, ("self", "engine")),
    # the ragged step's launches in flight: appended when the dispatch
    # lands, popped at the retire, both on the loop thread
    "_ragged_flights": (LOOP, ("self", "engine")),
    # multi-step / spec-as-row per-launch chain state
    # (docs/ragged_attention.md): window planning and retire-side
    # acceptance land these counters/histograms on the loop thread only
    "_step_rows": (LOOP, ("self", "engine")),
    "_hist_launch_tokens": (LOOP, ("self", "engine")),
    "_hist_spec_accept": (LOOP, ("self", "engine")),
    # host-tier promotion reap counters (docs/kv_tiering.md): bumped only
    # at loop-thread retire boundaries
    "_tier_counters": (LOOP, ("self", "engine")),
    # draft-tree verify rows (docs/spec_decode_trees.md): proposer hit
    # counters and the accept-depth histogram are planned/retired on the
    # loop thread; per-slot draft-ahead shipping watermarks advance at
    # loop-thread retire chunk boundaries
    "_spec_proposer": (LOOP, ("self", "engine")),
    "_hist_spec_tree_depth": (LOOP, ("self", "engine")),
    "_kv_draft_ahead": (LOOP, ("self", "engine")),
    # device-resident cross-chunk chains: written by the dispatch worker
    # (the only stage that runs device programs); the loop resets them only
    # at protocol-serialized points (annotated at the definition site)
    "_next_token_dev": (WORKER, None),
    "_gstate_dev": (WORKER, None),
    # replica-router ring membership (serving/replica_router.py,
    # docs/replication.md): sweeps/picks rebind an immutable frozenset on
    # the serving loop; the scrape thread reads snapshots by reference
    "_ring_members": (LOOP, ("self", "router", "_router")),
    # process-replica supervision (serving/process_replica.py): the
    # heartbeat miss counter is owned by the dedicated supervisor thread —
    # loop-side code reads liveness through is_ready snapshots only
    "_hb_misses": (WORKER, ("self", "replica")),
    # model_request_processor daemon-shared registries: read on the serving
    # event loop; the sync daemon swaps them only through the zero-downtime
    # drain protocol (annotated at the definition sites)
    "_endpoints": (LOOP, ("self", "processor")),
    "_model_monitoring": (LOOP, ("self", "processor")),
    "_model_monitoring_endpoints": (LOOP, ("self", "processor")),
    "_model_monitoring_versions": (LOOP, ("self", "processor")),
    "_canary_endpoints": (LOOP, ("self", "processor")),
    "_canary_route": (LOOP, ("self", "processor")),
    "_metric_logging": (LOOP, ("self", "processor")),
    "_engine_processor_lookup": (LOOP, ("self", "processor")),
    "_telemetry": (LOOP, ("self", "processor")),
}

# call shapes that move a callable onto a worker thread
_TO_THREAD_TAILS = ("to_thread",)          # asyncio.to_thread(f, ...)
_THREAD_CTORS = ("Thread",)                # threading.Thread(target=f)
_EXECUTOR_TAILS = ("run_in_executor",)     # loop.run_in_executor(None, f)

# host->device upload entry points whose zero-copy aliasing TPU502 polices:
# `jnp.asarray` and the spelled-out `jax.numpy.asarray` (matched on the last
# two dotted components). Deliberately NOT plain `np.asarray` — that is the
# standard device->host readback idiom (`np.asarray(entry.chunk)` on an
# immutable device buffer), and flagging it would drown the rule; a worker
# handoff built from `np.asarray` views is a documented blind spot.
_ASARRAY_TAILS = (("jnp", "asarray"), ("numpy", "asarray"))

_LOCKISH = ("lock", "mutex")


def _is_lock_name(name: Optional[str]) -> bool:
    if not name:
        return False
    leaf = name.split(".")[-1].lower()
    return any(marker in leaf for marker in _LOCKISH)


class _Fn:
    """One function/method in the module, with its lexical position and the
    thread contexts the call-graph pass assigns."""

    __slots__ = (
        "node", "name", "cls", "parent", "children", "contexts", "is_async",
    )

    def __init__(self, node, cls: Optional[str], parent: Optional["_Fn"]):
        self.node = node
        self.name = node.name
        self.cls = cls
        self.parent = parent
        self.children: Dict[str, "_Fn"] = {}
        self.contexts: Set[str] = set()
        self.is_async = isinstance(node, ast.AsyncFunctionDef)


def _collect_functions(tree: ast.AST) -> List[_Fn]:
    out: List[_Fn] = []

    def visit(node: ast.AST, cls: Optional[str], parent: Optional[_Fn]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = _Fn(child, cls, parent)
                out.append(fn)
                if parent is not None:
                    parent.children[fn.name] = fn
                visit(child, cls, fn)
            elif isinstance(child, ast.ClassDef):
                visit(child, child.name, None)
            else:
                visit(child, cls, parent)

    visit(tree, None, None)
    return out


def _own_statements(fn: _Fn):
    """Walk fn's body WITHOUT descending into nested function definitions
    (those are separate _Fn entries with their own contexts)."""
    stack = list(fn.node.body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.append(child)


class _Index:
    def __init__(self, fns: Sequence[_Fn]):
        self.methods: Dict[Tuple[str, str], _Fn] = {}
        self.module_fns: Dict[str, _Fn] = {}
        method_names: Dict[str, List[_Fn]] = {}
        for fn in fns:
            if fn.cls is not None and fn.parent is None:
                self.methods[(fn.cls, fn.name)] = fn
                method_names.setdefault(fn.name, []).append(fn)
            elif fn.cls is None and fn.parent is None:
                self.module_fns[fn.name] = fn
        # unambiguous method-name lookup for `x.m()` style calls
        self.unique_methods: Dict[str, _Fn] = {
            name: cands[0]
            for name, cands in method_names.items()
            if len(cands) == 1
        }

    def resolve(self, caller: _Fn, name: Optional[str]) -> Optional[_Fn]:
        if not name:
            return None
        parts = name.split(".")
        if len(parts) == 1:
            # lexical chain: nested defs of enclosing functions, then module
            scope = caller
            while scope is not None:
                if parts[0] in scope.children:
                    return scope.children[parts[0]]
                scope = scope.parent
            if parts[0] in self.module_fns:
                return self.module_fns[parts[0]]
            return None
        if parts[0] == "self" and len(parts) == 2 and caller.cls:
            hit = self.methods.get((caller.cls, parts[1]))
            if hit is not None:
                return hit
        # x.y.m(): fall back to the unambiguous method-name table
        return self.unique_methods.get(parts[-1])


def _worker_target(node: ast.Call) -> Optional[ast.AST]:
    """The callable expression a call moves onto a worker thread, if any."""
    name = _dotted(node.func)
    if name is None:
        return None
    leaf = name.split(".")[-1]
    if leaf in _TO_THREAD_TAILS and node.args:
        return node.args[0]
    if leaf in _THREAD_CTORS:
        for kw in node.keywords:
            if kw.arg == "target":
                return kw.value
    if leaf in _EXECUTOR_TAILS and len(node.args) >= 2:
        return node.args[1]
    return None


def _assign_contexts(fns: List[_Fn]) -> _Index:
    index = _Index(fns)
    edges: Dict[int, List[_Fn]] = {}
    for fn in fns:
        if fn.is_async:
            fn.contexts.add(LOOP)
        callees: List[_Fn] = []
        for node in _own_statements(fn):
            if not isinstance(node, ast.Call):
                continue
            target = _worker_target(node)
            if target is not None:
                worker_fn = index.resolve(fn, _dotted(target))
                if worker_fn is not None:
                    worker_fn.contexts.add(WORKER)
            callee = index.resolve(fn, _dotted(node.func))
            if callee is not None and callee is not fn:
                callees.append(callee)
        edges[id(fn)] = callees
    # propagate to a fixpoint (contexts only grow; bounded by 2 per fn)
    changed = True
    while changed:
        changed = False
        for fn in fns:
            if not fn.contexts:
                continue
            for callee in edges[id(fn)]:
                if not fn.contexts <= callee.contexts:
                    callee.contexts |= fn.contexts
                    changed = True
    return index


def _affine_declarations(
    tree: ast.AST,
) -> Dict[str, Tuple[str, Optional[Tuple[str, ...]]]]:
    """``__affine_to__`` class declarations: attr -> (thread, None)."""
    out: Dict[str, Tuple[str, Optional[Tuple[str, ...]]]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        for stmt in node.body:
            if not isinstance(stmt, ast.Assign):
                continue
            if not any(
                isinstance(t, ast.Name) and t.id == "__affine_to__"
                for t in stmt.targets
            ):
                continue
            try:
                decl = ast.literal_eval(stmt.value)
            except (ValueError, SyntaxError):
                continue
            if not isinstance(decl, dict):
                continue
            for thread, attrs in decl.items():
                if str(thread) not in _THREADS:
                    continue
                for attr in attrs:
                    out[str(attr)] = (str(thread), None)
    return out


def _affine_split(node: ast.AST, registry):
    node = _strip_subscripts(node)
    if not isinstance(node, ast.Attribute):
        return None
    entry = registry.get(node.attr)
    if entry is None:
        return None
    thread, receivers = entry
    recv = _dotted(node.value)
    if recv is None:
        return None
    if receivers is not None and recv.split(".")[-1] not in receivers:
        return None
    return recv, node.attr, thread


def _iter_mutations(fn: _Fn):
    """(target_expr, stmt_node) pairs for every mutation in fn's own body —
    the same mutation surface rules_locks checks (assign/augassign/del +
    mutating method calls)."""
    for node in _own_statements(fn):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (
                list(node.targets) if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                if isinstance(t, ast.Tuple):
                    for elt in t.elts:
                        yield elt, node
                else:
                    yield t, node
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                yield t, node
        elif isinstance(node, ast.Call):
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATORS
            ):
                yield node.func.value, node


def _emit(findings: List[Finding], code: str, path: str, node: ast.AST,
          detail: str) -> None:
    summary, hint = RULES[code]
    findings.append(
        Finding(
            code, path, node.lineno, node.col_offset,
            "{} ({})".format(summary, detail), hint,
        )
    )


# -- TPU501 -------------------------------------------------------------------


def _check_tpu501(fn: _Fn, registry, path: str,
                  findings: List[Finding]) -> None:
    if fn.name == "__init__":
        return  # object under construction is not yet shared
    for target, stmt in _iter_mutations(fn):
        hit = _affine_split(target, registry)
        if hit is None:
            continue
        recv, attr, thread = hit
        off_thread = fn.contexts - {thread}
        if not off_thread:
            continue
        _emit(
            findings, "TPU501", path, stmt,
            "{}.{} is {}-thread-affine but `{}` is reachable from the "
            "{} thread".format(
                recv, attr, thread, fn.name, "/".join(sorted(off_thread))
            ),
        )


# -- TPU502 -------------------------------------------------------------------


def _check_tpu502(fn: _Fn, path: str, findings: List[Finding]) -> None:
    for node in _own_statements(fn):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        name = _dotted(node.func)
        if name is None:
            continue
        parts = name.split(".")
        pair = tuple(parts[-2:]) if len(parts) >= 2 else None
        if pair not in _ASARRAY_TAILS:
            continue
        arg = _strip_subscripts(node.args[0])
        if not isinstance(arg, ast.Attribute):
            continue  # locals and fresh call results can't be shared mirrors
        buf = _dotted(arg)
        if buf is None:
            continue
        _emit(
            findings, "TPU502", path, node,
            "{}({}) aliases a shared host buffer across the thread "
            "handoff".format(name, buf),
        )


# -- TPU503 -------------------------------------------------------------------


class _AwaitUnderLockVisitor(ast.NodeVisitor):
    def __init__(self, path: str):
        self.path = path
        self.findings: List[Finding] = []
        self._fn: List[bool] = []         # innermost function kind
        self._locks: List[str] = []       # sync locks lexically held

    def _visit_fn(self, node, is_async: bool):
        # a nested def inside a `with lock:` body runs LATER, without the
        # lock — its awaits are not under this lock scope
        self._fn.append(is_async)
        saved, self._locks = self._locks, []
        self.generic_visit(node)
        self._locks = saved
        self._fn.pop()

    def visit_AsyncFunctionDef(self, node):
        self._visit_fn(node, True)

    def visit_FunctionDef(self, node):
        self._visit_fn(node, False)

    def visit_Lambda(self, node):
        self._visit_fn(node, False)

    def visit_With(self, node: ast.With):
        names = [
            _dotted(item.context_expr)
            for item in node.items
            if _is_lock_name(_dotted(item.context_expr))
        ]
        self._locks.extend(n for n in names if n)
        self.generic_visit(node)
        for _ in names:
            if _:
                self._locks.pop()

    # async with takes asyncio locks, which are await-safe by design

    def visit_Await(self, node: ast.Await):
        if self._fn and self._fn[-1] and self._locks:
            _emit(
                self.findings, "TPU503", self.path, node,
                "await while holding `{}`".format(self._locks[-1]),
            )
        self.generic_visit(node)


# -- TPU504 -------------------------------------------------------------------


def _is_tpu301_scoped(fn: _Fn, ignores) -> bool:
    """Does fn's def (or decorator) line carry a TPU301 scope ignore — the
    'lock held by caller' marker? One predicate shared by helper detection
    and the caller exemption so the two can never diverge."""
    decl_lines = [fn.node.lineno] + [d.lineno for d in fn.node.decorator_list]
    return any(
        line in ignores
        and (ignores[line] is None or "TPU301" in (ignores[line] or ()))
        for line in decl_lines
    )


def _lock_helpers(fns: Sequence[_Fn], guarded,
                  ignores) -> Dict[int, FrozenSet[str]]:
    """fn-id -> lock attr names, for every method whose def line carries a
    TPU301 scope ignore AND whose body mutates guarded state — the "lock
    held by caller" helpers whose callers TPU504 audits."""
    out: Dict[int, FrozenSet[str]] = {}
    for fn in fns:
        if not _is_tpu301_scoped(fn, ignores):
            continue
        locks: Set[str] = set()
        for target, _stmt in _iter_mutations(fn):
            node = _strip_subscripts(target)
            if not isinstance(node, ast.Attribute):
                continue
            entry = guarded.get(node.attr)
            if entry is not None:
                locks.add(entry[0])
        if locks:
            out[id(fn)] = frozenset(locks)
    return out


def _check_tpu504(fn: _Fn, index: _Index, helpers, ignores, path: str,
                  findings: List[Finding]) -> None:
    if _is_tpu301_scoped(fn, ignores):
        return  # the fn is itself a lock-held context; the annotation covers it

    def walk(node: ast.AST, held: FrozenSet[str]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            now = set(held)
            for item in node.items:
                text = _dotted(item.context_expr)
                if text:
                    now.add(text)
            for child in node.body:
                walk(child, frozenset(now))
            for item in node.items:
                walk(item.context_expr, held)
            return
        if isinstance(node, ast.Call):
            callee = index.resolve(fn, _dotted(node.func))
            if callee is not None and id(callee) in helpers:
                prefix = "self"
                if isinstance(node.func, ast.Attribute):
                    prefix = _dotted(node.func.value) or "self"
                required = {
                    "{}.{}".format(prefix, lock)
                    for lock in helpers[id(callee)]
                }
                if not required <= held:
                    _emit(
                        findings, "TPU504", path, node,
                        "`{}` mutates lock-guarded state for its caller, "
                        "but `{}` does not hold {}".format(
                            callee.name, fn.name, ", ".join(sorted(required))
                        ),
                    )
        for child in ast.iter_child_nodes(node):
            walk(child, held)

    for stmt in fn.node.body:
        walk(stmt, frozenset())


# -- entry --------------------------------------------------------------------


def check(tree: ast.AST, path: str, source: str) -> List[Finding]:
    fns = _collect_functions(tree)
    index = _assign_contexts(fns)
    affine = dict(AFFINITY_REGISTRY)
    affine.update(_affine_declarations(tree))
    guarded = dict(_GUARDED_REGISTRY)
    guarded.update(_guarded_declarations(tree))
    ignores = _ignore_map(source)
    helpers = _lock_helpers(fns, guarded, ignores)
    has_worker = any(WORKER in fn.contexts for fn in fns)

    findings: List[Finding] = []
    for fn in fns:
        if not fn.contexts:
            continue  # not reachable from a thread root: blind spot, fail open
        _check_tpu501(fn, affine, path, findings)
        if has_worker:
            _check_tpu502(fn, path, findings)
        _check_tpu504(fn, index, helpers, ignores, path, findings)
    lock_visitor = _AwaitUnderLockVisitor(path)
    lock_visitor.visit(tree)
    findings.extend(lock_visitor.findings)
    return findings
