"""TPU301 — lock discipline over KV bookkeeping state.

PagePool refcounts, per-slot page tables, pending copy-on-write pairs, and
radix-cache tree state are mutated concurrently by the engine loop thread,
decode worker threads, and admission workers. Every one of those structures
is guarded by a declared lock; a mutation that slips outside the lock is a
refcount-corruption bug that only reproduces under load (the exact class of
failure the runtime KV sanitizer — llm/kv_sanitizer.py — exists to catch
after the fact; this rule catches it before merge).

The guarded-attribute registry comes from two sources, merged:

1. ``__guarded_by__`` class declarations in the analyzed file::

       class PagePool:
           __guarded_by__ = {"_lock": ("_free", "_refs", ...)}

2. the project-level table below (cross-module mutations — e.g. engine.py
   poking ``pool._refs`` — are checked even though the declaration lives in
   kv_cache.py, which the analyzer may not be looking at right now).

A mutation of ``<recv>.<attr>`` (assignment, augmented assignment, ``del``,
or a mutating method call like ``.append``/``.pop``) must sit lexically
inside ``with <recv>.<lock>:``. ``__init__`` bodies are exempt (the object
is not shared yet). Helpers called with the lock already held annotate their
``def`` line with ``# tpuserve: ignore[TPU301] lock held by caller``.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, List, Optional, Tuple

from . import Finding, RULES, dotted_name as _dotted

# attr name -> (lock attr name, receiver-basename filter or None).
# Project-wide registry: kv_cache.PagePool and PagedKVCache,
# prefix_cache.RadixPrefixCache. Keep in sync with the __guarded_by__
# declarations at the definition sites (test_analyze checks the two agree).
# A None filter matches any receiver (the attr names are distinctive); a
# tuple restricts the rule to receivers whose FINAL dotted component is
# listed — used for generic names like `k`/`v`, where matching every class's
# `self.k` tree-wide would drown real findings in false positives.
PROJECT_REGISTRY: Dict[str, Tuple[str, Optional[Tuple[str, ...]]]] = {
    # PagePool bookkeeping (kv_cache.py)
    "_free": ("_lock", None),
    "_slot_pages": ("_lock", None),
    "_slot_len": ("_lock", None),
    "_refs": ("_lock", None),
    "_pending_cow": ("_lock", None),
    "_pins": ("_lock", None),
    "_used_peak": ("_lock", None),
    # RadixPrefixCache tree state (prefix_cache.py)
    "_roots": ("_lock", None),
    "_leaf_nodes": ("_lock", None),
    "_n_nodes": ("_lock", None),
    "_clock": ("_lock", None),
    # host-RAM KV tier (docs/kv_tiering.md): the cache's resident frontier
    # + per-tier accounting, and the HostKVTier id allocator (kv_cache.py;
    # its "_free"/"_used" ride the existing "_free" entry and this one)
    "_frontier": ("_lock", None),
    "_n_resident": ("_lock", None),
    "_host_pages": ("_lock", ("self", "cache", "prefix", "_prefix")),
    "_host_bytes": ("_lock", None),
    "_used": ("_lock", ("self", "tier", "host_tier", "host")),
    # PagedKVCache pool handles: a donating dispatch invalidates the old
    # handle, so rebinds happen only under the dispatch lock. Receiver-
    # filtered to the engine's naming for the paged cache object; inside
    # kv_cache.py itself the class's own __guarded_by__ declaration (no
    # filter) takes precedence.
    "k": ("dispatch_lock", ("paged_cache", "cache", "paged_kv", "kv_cache")),
    "v": ("dispatch_lock", ("paged_cache", "cache", "paged_kv", "kv_cache")),
    # int8 paged KV scale pools (docs/paged_kv_quant.md): rebinds follow the
    # same donation discipline as the data pools
    "k_scale": (
        "dispatch_lock", ("paged_cache", "cache", "paged_kv", "kv_cache"),
    ),
    "v_scale": (
        "dispatch_lock", ("paged_cache", "cache", "paged_kv", "kv_cache"),
    ),
    # in-flight host->device promotion records (docs/kv_tiering.md):
    # appended at copy-enqueue (dispatch path), drained at retire reaps
    "_promotions": (
        "dispatch_lock", ("paged_cache", "cache", "paged_kv", "kv_cache"),
    ),
    # KV-transport receive-slab mailboxes (llm/kv_transport.py,
    # docs/disaggregation.md): senders on replica loop threads, receivers
    # on the group's receive worker
    "_slabs": ("_lock", None),
    "_slab_pages": ("_lock", None),
    "_ship_seq": ("_lock", None),
    # draft-ahead partial-frame assemblies (docs/spec_decode_trees.md):
    # unsealed frames accumulate under the same mailbox lock until the
    # sealing frame fuses them (fusion itself runs OUTSIDE the lock on a
    # popped list — only the map mutations are guarded)
    "_assemblies": ("_lock", None),
    # socket KV-wire backend (llm/kv_wire.py): the per-peer connection
    # cache is shared between the sender's loop thread and close()
    "_conns": ("_lock", ("self", "transport", "endpoint", "_kv_transport",
                         "ep")),
    # process-replica control plane (serving/process_replica.py): the
    # blocking sync channel is shared between the serving loop, to_thread
    # receive workers, and the Prometheus scrape thread
    "_sync_sock": ("_sync_lock", ("self", "proxy", "client", "_client",
                                  "engine")),
    # process-replica supervisor state (serving/process_replica.py): the
    # worker Popen handle and restart budget are rebound by both the
    # supervisor thread (crash/restart) and the serving loop (stop)
    "_proc": ("_lock", ("self", "replica")),
    "_restarts_left": ("_lock", ("self", "replica")),
    # SLO scheduler pending-queue state (engine._ClassedPendingQueue,
    # docs/slo_scheduling.md): per-class heaps + starvation counters
    "_heaps": ("_lock", None),
    "_starve": ("_lock", None),
    # replica-router shared maps (serving/replica_router.py,
    # docs/replication.md): route/event counters written on the serving
    # loop, read by the Prometheus scrape thread
    "_route_counts": ("_lock", ("self", "router", "_router")),
    "_router_events": ("_lock", ("self", "router", "_router")),
}

_MUTATORS = {
    "append", "appendleft", "extend", "insert", "pop", "popleft", "popitem",
    "remove", "clear", "add", "discard", "update", "setdefault",
}


def _strip_subscripts(node: ast.AST) -> ast.AST:
    while isinstance(node, ast.Subscript):
        node = node.value
    return node


def _guarded_split(node: ast.AST, registry):
    """(recv_text, attr, lock_attr) when ``node`` is `<recv>.<guarded>` and
    the receiver passes the entry's basename filter."""
    node = _strip_subscripts(node)
    if not isinstance(node, ast.Attribute):
        return None
    attr = node.attr
    entry = registry.get(attr)
    if entry is None:
        return None
    lock, receivers = entry
    recv = _dotted(node.value)
    if recv is None:
        return None
    if receivers is not None and recv.split(".")[-1] not in receivers:
        return None
    return recv, attr, lock


def _file_declarations(tree: ast.AST):
    """Collect ``__guarded_by__`` class declarations: attr -> (lock, None).
    A declaration at the definition site applies to any receiver."""
    out = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        for stmt in node.body:
            if not isinstance(stmt, ast.Assign):
                continue
            if not any(
                isinstance(t, ast.Name) and t.id == "__guarded_by__"
                for t in stmt.targets
            ):
                continue
            try:
                decl = ast.literal_eval(stmt.value)
            except (ValueError, SyntaxError):
                continue
            if not isinstance(decl, dict):
                continue
            for lock_attr, attrs in decl.items():
                for attr in attrs:
                    out[str(attr)] = (str(lock_attr), None)
    return out


class _LockVisitor:
    def __init__(self, path: str, registry):
        self.path = path
        self.registry = registry
        self.findings: List[Finding] = []

    def _emit(self, node: ast.AST, recv: str, attr: str, lock: str) -> None:
        summary, hint = RULES["TPU301"]
        self.findings.append(
            Finding(
                "TPU301", self.path, node.lineno, node.col_offset,
                "{} ({}.{} mutated outside `with {}.{}`)".format(
                    summary, recv, attr, recv, lock
                ),
                hint,
            )
        )

    def _check_mutation(self, target: ast.AST, node: ast.AST,
                        locks: FrozenSet[str]) -> None:
        hit = _guarded_split(target, self.registry)
        if hit is None:
            return
        recv, attr, lock = hit
        if "{}.{}".format(recv, lock) not in locks:
            self._emit(node, recv, attr, lock)

    def walk_function(self, fn: ast.AST) -> None:
        if getattr(fn, "name", "") == "__init__":
            return  # object under construction is not yet shared
        for stmt in getattr(fn, "body", []):
            self._walk(stmt, frozenset())

    def _walk(self, node: ast.AST, locks: FrozenSet[str]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a nested def runs later, possibly without the lock; check()
            # visits every def separately with a clean lock state
            return
        if isinstance(node, ast.Lambda):
            self._walk(node.body, frozenset())
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            held = set(locks)
            for item in node.items:
                text = _dotted(item.context_expr)
                if text:
                    held.add(text)
                elif isinstance(item.context_expr, ast.Call):
                    # with lock.acquire_timeout(...) style helpers: count the
                    # receiver chain as held
                    text = _dotted(item.context_expr.func)
                    if text and "." in text:
                        held.add(text.rsplit(".", 1)[0])
            for child in node.body:
                self._walk(child, frozenset(held))
            for item in node.items:
                self._walk(item.context_expr, locks)
            return
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (
                list(node.targets)
                if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                if isinstance(t, ast.Tuple):
                    for elt in t.elts:
                        self._check_mutation(elt, node, locks)
                else:
                    self._check_mutation(t, node, locks)
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                self._check_mutation(t, node, locks)
        elif isinstance(node, ast.Call):
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATORS
            ):
                self._check_mutation(node.func.value, node, locks)
        for child in ast.iter_child_nodes(node):
            self._walk(child, locks)


def check(tree: ast.AST, path: str, source: str) -> List[Finding]:
    registry = dict(PROJECT_REGISTRY)
    registry.update(_file_declarations(tree))
    visitor = _LockVisitor(path, registry)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            visitor.walk_function(node)
    return visitor.findings
