"""TPU6xx — compile-surface discipline (docs/static_analysis.md).

TPU performance is a *compile-surface* property: the set of (function,
shape, dtype) keys XLA ever sees from the serve loop must be FINITE and
KNOWN AHEAD of serving, because every serve-time recompile is a 100-1000 ms
stall of the loop thread that masquerades as scheduling tail (the PR-6
loadtest and the PR-10 tiering work each independently burned debugging
time on exactly this: unbucketed mini-cache slice keys, unwarmed
resume-commit shapes). This rule family machine-checks the invariant the
way TPU301 checks lock discipline and TPU5xx checks thread affinity,
against two project registries:

- **bucketizers** (``llm/shapes.py`` + ``__bucketizers__`` module
  declarations): the functions that collapse request-varying values into a
  finite key space (power-of-two buckets, page-multiple pads, null-page
  list padding);
- **the warmup shape registry** (``llm/warmup.py`` ``WARMUP_COVERED``):
  the jit entries whose shape keys the shared warmup sweep compiles before
  the serve fence.

Rules:

- **TPU601** — a request-varying value (prompt length, token list, page
  list: a name in ``REQUEST_VARYING``, or anything derived from one by the
  local taint pass) reaches an eager device upload/alloc (``jnp.asarray``/
  ``jnp.array``/``jnp.zeros``-family) without flowing through a registered
  bucketizer. Each distinct length is a distinct XLA program — unbounded
  compile-key cardinality on the serve path.
- **TPU602** — dtype/weak-type drift into a jit boundary: a bare Python
  float literal, a ``float(...)`` conversion, or a dtype-less
  ``np.asarray``/``np.array`` passed to a ``*_jit`` wrapper. Weak-typed
  scalars and platform-default numpy dtypes split the compile cache
  against the explicitly-typed cached-constant pattern (PR 4) and recompile
  when a caller's host types shift.
- **TPU603** — compile-surface closed world: inside a class declaring
  ``__compile_keys__``, every jit-wrapper attribute (``self.X =
  jax.jit(...)`` or any ``self.X_jit = ...``) must be declared under a
  role, and every ``"serve"``-role entry must appear in the warmup shape
  registry (``llm/warmup.py``, parsed from source like faults.KNOWN_POINTS;
  ``WARMUP_COVERED`` below is the build-time mirror, consistency-tested).
  A new dispatch-path jit entry that nobody warmed is exactly the mid-run
  compile stall this family exists to prevent.
- **TPU604** — a request-varying (tainted) value fed to a
  ``static_argnums``/``static_argnames`` position of a jitted wrapper:
  static arguments hash into the compile key, so a per-request value there
  IS a recompile per request.

The taint pass is local (per function, statements in source order) and
fails OPEN on anything it cannot prove: calls to unknown functions launder
taint, slices of clean buffers are clean even when the bounds vary. The
runtime compile sentry (``llm/compile_sentry.py``) is the dynamic net
behind those blind spots, exactly as the KV sanitizer backs TPU301 and the
interleaving explorer backs TPU5xx.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from . import Finding, RULES, dotted_name as _dotted
from .rules_jit import _collect as _collect_jit_wrappers, _is_jit_call

# -- registries ---------------------------------------------------------------

# Names whose VALUE LENGTH varies per request: prompt token lists, page-id
# lists, grammar token sets. A bare read of one of these (parameter, outer
# binding, attribute leaf like ``request.prompt_ids``) is tainted; a local
# assignment from a clean expression (np.zeros of a bucketed shape, a
# bucketizer call) makes the same name clean. Keep the set DISTINCTIVE —
# a generic name here drowns real findings in false positives.
REQUEST_VARYING: FrozenSet[str] = frozenset({
    "prompt_ids",
    "prompt",
    "token_ids",
    "ids",
    "pages",
    "host_ids",
    "host_pages",
    "allowed",
    "history",
})

# Call leaf names that collapse request-varying values into a finite key
# space. Project-level homes: llm/shapes.py (pow2_bucket/pad_to_multiple/
# pad_pages), the engine's prefill bucket picker, the pool's page-count
# round-up, and the ragged layout builder (its outputs are 8-aligned
# and total-padded by construction). A module can extend the set for its
# own helpers with a literal module-level declaration::
#
#     __bucketizers__ = ("_my_bucket_helper",)
#
# tests/test_analyze_compile.py pins every project-level name here to a
# real definition in the tree.
BUCKETIZERS: FrozenSet[str] = frozenset({
    "pow2_bucket",
    "pad_to_multiple",
    "pad_pages",
    "decode_steps_bucket",
    "_bucket_for",
    "pages_needed",
    "ragged_layout",
})

# Build-time mirror of llm/warmup.py's WARMUP_COVERED (the jit entries the
# shared warmup sweep drives). TPU603 prefers the registry parsed from the
# llm/warmup.py nearest the analyzed file — this literal is the fallback
# for out-of-tree fixtures, and tests/test_analyze_compile.py asserts the
# two never drift.
WARMUP_COVERED: FrozenSet[str] = frozenset({
    "_prefill_jit",
    "_prefill_ring_jit",
    "_prefill_pipeline_jit",
    "_prefill_chunk_first_jit",
    "_prefill_chunk_jit",
    "_assemble_prefix_jit",
    "_insert_jit",
    "_merge_rows_jit",
    "_decode_chunk_jit",
    "_decode_paged_chunk_jit",
    "_first_token_jit",
    "_set_sampling_row_jit",
    "_spec_chunk_jit",
    "_ragged_paged_jit",
    "_ragged_state_jit",
    "_ragged_unpack_jit",
    "_ragged_chain_jit",
})

_warmup_cache: Dict[str, FrozenSet[str]] = {}


def _warmup_registry(path: str) -> FrozenSet[str]:
    """WARMUP_COVERED parsed from the llm/warmup.py nearest to ``path``
    (same resolution rule as rules_errors' faults.KNOWN_POINTS)."""
    directory = os.path.dirname(os.path.abspath(path))
    candidate: Optional[str] = None
    for _ in range(8):
        cand = os.path.join(directory, "llm", "warmup.py")
        if os.path.isfile(cand):
            candidate = cand
            break
        parent = os.path.dirname(directory)
        if parent == directory:
            break
        directory = parent
    if candidate is None:
        return WARMUP_COVERED
    if candidate in _warmup_cache:
        return _warmup_cache[candidate]
    covered = WARMUP_COVERED
    try:
        with open(candidate, "r", encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            if not any(
                isinstance(t, ast.Name) and t.id == "WARMUP_COVERED"
                for t in node.targets
            ):
                continue
            value = node.value
            if isinstance(value, ast.Call) and value.args:
                value = value.args[0]  # frozenset({...})
            try:
                literal = ast.literal_eval(value)
                covered = frozenset(str(p) for p in literal)
            except (ValueError, SyntaxError):
                pass
            break
    except (OSError, SyntaxError):
        pass
    _warmup_cache[candidate] = covered
    return covered


def _module_bucketizers(tree: ast.AST) -> FrozenSet[str]:
    """Literal module-level ``__bucketizers__ = ("name", ...)`` extensions."""
    out: Set[str] = set()
    for node in getattr(tree, "body", []):
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "__bucketizers__"
            for t in node.targets
        ):
            continue
        try:
            literal = ast.literal_eval(node.value)
        except (ValueError, SyntaxError):
            continue
        if isinstance(literal, (tuple, list, set, frozenset)):
            out |= {str(name) for name in literal}
    return frozenset(out)


# -- expression taint ---------------------------------------------------------

# device upload/alloc entry points whose SHAPE comes from the first
# argument. The module part distinguishes eager device ops (jnp/jax.numpy:
# each novel shape is an XLA program) from host numpy (taints the result,
# sinks only when later uploaded).
_UPLOAD_TAILS = ("asarray", "array")
_ALLOC_TAILS = ("zeros", "ones", "empty", "full", "arange")


def _call_parts(node: ast.Call) -> Tuple[Optional[str], Optional[str]]:
    """(module leaf, function leaf) of a call's dotted name."""
    name = _dotted(node.func)
    if name is None:
        return None, None
    parts = name.split(".")
    return (parts[-2] if len(parts) >= 2 else None), parts[-1]


def _is_device_call(node: ast.Call) -> bool:
    """True for the jax.numpy entry points whose eager dispatch mints an
    XLA program per shape: `jnp.*` and the spelled-out `jax.numpy.*`.
    Plain-numpy spellings (`np.*`, bare `numpy.*`) are HOST calls — they
    only propagate taint, the later upload is the sink."""
    name = _dotted(node.func)
    if name is None:
        return False
    parts = name.split(".")
    if len(parts) < 2:
        return False
    if parts[-2] == "jnp":
        return True
    return len(parts) >= 3 and parts[-3] == "jax" and parts[-2] == "numpy"


class _TaintPass:
    """Forward pass over one function's own statements: tracks which local
    names hold request-varying-length values, and reports sink hits."""

    def __init__(self, registry: FrozenSet[str],
                 bucketizers: FrozenSet[str]):
        self.registry = registry
        self.bucketizers = bucketizers
        self.tainted: Set[str] = set()
        self.clean: Set[str] = set()

    def name_tainted(self, text: Optional[str]) -> bool:
        if text is None:
            return False
        if text in self.tainted:
            return True
        if text in self.clean:
            return False
        return text.split(".")[-1] in self.registry

    def expr_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, (ast.Name, ast.Attribute)):
            return self.name_tainted(_dotted(node))
        if isinstance(node, ast.Call):
            mod, leaf = _call_parts(node)
            if leaf is None:
                return False  # dynamic callee: fail open
            if leaf in self.bucketizers:
                return False  # registered collapse
            if leaf == "len" and node.args:
                return self.expr_tainted(node.args[0])
            if leaf in ("min", "max", "abs", "sum", "sorted", "list",
                        "tuple"):
                return any(self.expr_tainted(a) for a in node.args)
            if leaf in _UPLOAD_TAILS and node.args:
                return self.expr_tainted(node.args[0])
            if leaf in _ALLOC_TAILS and node.args:
                return self.shape_tainted(node.args[0])
            return False  # unknown call launders: fail open
        if isinstance(node, ast.BinOp):
            if isinstance(node.op, ast.FloorDiv):
                # integer division by a bucket/page size collapses the key
                # space (the `-(-n // m) * m` pad idiom stays clean)
                return False
            return self.expr_tainted(node.left) or self.expr_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr_tainted(node.operand)
        if isinstance(node, ast.Subscript):
            return self.expr_tainted(node.value)
        if isinstance(node, ast.IfExp):
            return self.expr_tainted(node.body) or self.expr_tainted(node.orelse)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return bool(node.generators) and self.expr_tainted(
                node.generators[0].iter
            )
        if isinstance(node, ast.NamedExpr):
            return self.expr_tainted(node.value)
        if isinstance(node, ast.Starred):
            return self.expr_tainted(node.value)
        return False  # literals, lambdas, comparisons, ...

    def shape_tainted(self, node: ast.AST) -> bool:
        """A shape argument is tainted when the whole expression is, or —
        for a literal tuple/list shape — when any DIMENSION is."""
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr_tainted(e) for e in node.elts)
        return self.expr_tainted(node)

    def bind(self, stmt: ast.stmt) -> None:
        """Update the taint state for an assignment statement."""
        targets: List[ast.AST] = []
        value: Optional[ast.AST] = None
        if isinstance(stmt, ast.Assign):
            targets, value = list(stmt.targets), stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        elif isinstance(stmt, ast.AugAssign):
            targets, value = [stmt.target], stmt.value
        if value is None:
            return
        is_tainted = self.expr_tainted(value)
        if isinstance(stmt, ast.AugAssign):
            # x += tainted keeps/raises taint but never cleans
            tgt = _dotted(stmt.target)
            if tgt is not None and is_tainted:
                self.tainted.add(tgt)
                self.clean.discard(tgt)
            return
        for t in targets:
            names = (
                [_dotted(e) for e in t.elts]
                if isinstance(t, ast.Tuple)
                else [_dotted(t)]
            )
            for name in names:
                if name is None:
                    continue
                if is_tainted:
                    self.tainted.add(name)
                    self.clean.discard(name)
                else:
                    self.clean.add(name)
                    self.tainted.discard(name)


# -- per-function statement walk (shared shape with rules_jit) ----------------

_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)


def _own_statements(fn: ast.AST) -> List[ast.stmt]:
    out: List[ast.stmt] = []
    stack: List[ast.AST] = list(getattr(fn, "body", []))
    while stack:
        cur = stack.pop()
        if isinstance(cur, ast.stmt):
            out.append(cur)
        for child in ast.iter_child_nodes(cur):
            if isinstance(child, _SCOPE_NODES):
                continue
            stack.append(child)
    return out


def _walk_stmt(stmt: ast.AST):
    stack = [stmt]
    while stack:
        cur = stack.pop()
        yield cur
        for child in ast.iter_child_nodes(cur):
            if isinstance(child, _SCOPE_NODES + (ast.stmt,)):
                continue
            stack.append(child)


# -- TPU602 helpers -----------------------------------------------------------


def _dtype_drift_detail(arg: ast.AST) -> Optional[str]:
    """Why an argument drifts dtype into a jit boundary, or None."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, float):
        return "bare float literal {!r} (weak-typed)".format(arg.value)
    if isinstance(arg, ast.Call):
        mod, leaf = _call_parts(arg)
        if leaf == "float":
            return "float(...) host conversion (weak-typed)"
        if (
            leaf in _UPLOAD_TAILS
            and mod in ("np", "numpy")
            and not any(kw.arg == "dtype" for kw in arg.keywords)
            and not (len(arg.args) >= 2)
        ):
            return "dtype-less {}.{}(...) (platform-default dtype)".format(
                mod, leaf
            )
    return None


# -- TPU603: __compile_keys__ closed world ------------------------------------


def _compile_keys_decl(cls: ast.ClassDef) -> Optional[Dict[str, Tuple[str, ...]]]:
    for stmt in cls.body:
        if not isinstance(stmt, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "__compile_keys__"
            for t in stmt.targets
        ):
            continue
        try:
            decl = ast.literal_eval(stmt.value)
        except (ValueError, SyntaxError):
            return None
        if not isinstance(decl, dict):
            return None
        return {
            str(role): tuple(str(n) for n in names)
            for role, names in decl.items()
        }
    return None


def _class_jit_attrs(cls: ast.ClassDef) -> List[Tuple[str, ast.AST]]:
    """(attr name, node) for every self-attribute that is a jit wrapper:
    assigned from a jit call, named with the ``_jit`` suffix convention, or
    rebound from a local name that holds a jit call's result."""
    jit_locals: Set[str] = set()
    out: List[Tuple[str, ast.AST]] = []
    seen: Set[str] = set()
    for node in ast.walk(cls):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        value_is_jit = isinstance(value, ast.Call) and _is_jit_call(value)
        if value_is_jit:
            for t in node.targets:
                name = _dotted(t)
                if name and "." not in name:
                    jit_locals.add(name)
        for t in node.targets:
            name = _dotted(t)
            if not name or not name.startswith("self."):
                continue
            attr = name.split(".", 1)[1]
            if "." in attr:
                continue
            rhs_name = _dotted(value)
            is_entry = (
                value_is_jit
                or attr.endswith("_jit")
                or (rhs_name is not None and rhs_name in jit_locals)
            )
            if is_entry and attr not in seen:
                seen.add(attr)
                out.append((attr, node))
    return out


# -- entry --------------------------------------------------------------------


def check(tree: ast.AST, path: str, source: str) -> List[Finding]:
    findings: List[Finding] = []

    def emit(code: str, node: ast.AST, detail: str) -> None:
        summary, hint = RULES[code]
        findings.append(
            Finding(
                code, path, node.lineno, node.col_offset,
                "{} ({})".format(summary, detail), hint,
            )
        )

    bucketizers = BUCKETIZERS | _module_bucketizers(tree)
    _defs, _jit_calls, wrappers = _collect_jit_wrappers(tree)

    # static_argnames registries for TPU604 (rules_jit._collect keeps only
    # int static_argnums; names need their own sweep)
    static_names: Dict[str, Tuple[str, ...]] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
            continue
        call = node.value
        if not _is_jit_call(call):
            continue
        for kw in call.keywords:
            if kw.arg != "static_argnames":
                continue
            try:
                literal = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                continue
            names = (
                (literal,) if isinstance(literal, str)
                else tuple(str(n) for n in literal)
            )
            for t in node.targets:
                tname = _dotted(t)
                if tname:
                    static_names[tname.split(".")[-1]] = names

    # -- TPU601/602/604: per-function taint + sink walk --------------------
    fn_nodes = [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for fn in fn_nodes:
        taint = _TaintPass(REQUEST_VARYING, bucketizers)
        for stmt in sorted(_own_statements(fn), key=lambda s: s.lineno):
            for node in _walk_stmt(stmt):
                if not isinstance(node, ast.Call):
                    continue
                mod, leaf = _call_parts(node)
                # TPU601: eager device upload/alloc of a tainted value
                if (
                    leaf in _UPLOAD_TAILS
                    and _is_device_call(node)
                    and node.args
                    and taint.expr_tainted(node.args[0])
                ):
                    emit(
                        "TPU601", node,
                        "{}.{}({}) uploads a request-varying length".format(
                            mod, leaf, _dotted(node.args[0]) or "<expr>"
                        ),
                    )
                elif (
                    leaf in _ALLOC_TAILS
                    and _is_device_call(node)
                    and node.args
                    and taint.shape_tainted(node.args[0])
                ):
                    emit(
                        "TPU601", node,
                        "{}.{} shaped by a request-varying value".format(
                            mod, leaf
                        ),
                    )
                # wrapper call sites: TPU602 dtype drift + TPU604 statics
                cal = _dotted(node.func)
                wrapper_leaf = cal.split(".")[-1] if cal else None
                if wrapper_leaf and (
                    wrapper_leaf.endswith("_jit")
                    or wrapper_leaf in wrappers
                    or wrapper_leaf in static_names
                ):
                    for arg in node.args:
                        drift = _dtype_drift_detail(arg)
                        if drift is not None:
                            emit(
                                "TPU602", arg,
                                "{} passed to {}".format(drift, wrapper_leaf),
                            )
                    for kw in node.keywords:
                        if kw.arg is None:
                            continue
                        drift = _dtype_drift_detail(kw.value)
                        if drift is not None:
                            emit(
                                "TPU602", kw.value,
                                "{} passed to {} ({}=)".format(
                                    drift, wrapper_leaf, kw.arg
                                ),
                            )
                    wrapper = wrappers.get(wrapper_leaf)
                    if wrapper is not None:
                        for pos in wrapper.static:
                            if pos < len(node.args) and taint.expr_tainted(
                                node.args[pos]
                            ):
                                emit(
                                    "TPU604", node.args[pos],
                                    "argument {} of {} is static".format(
                                        pos, wrapper_leaf
                                    ),
                                )
                    for kw in node.keywords:
                        if (
                            kw.arg is not None
                            and kw.arg in static_names.get(wrapper_leaf, ())
                            and taint.expr_tainted(kw.value)
                        ):
                            emit(
                                "TPU604", kw.value,
                                "{}= of {} is a static argname".format(
                                    kw.arg, wrapper_leaf
                                ),
                            )
            taint.bind(stmt)

    # -- TPU603: compile-surface closed world ------------------------------
    covered = _warmup_registry(path)
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        decl = _compile_keys_decl(node)
        if decl is None:
            continue
        declared: Set[str] = set()
        for names in decl.values():
            declared |= set(names)
        serve = set(decl.get("serve", ()))
        for attr, assign in _class_jit_attrs(node):
            if attr not in declared:
                emit(
                    "TPU603", assign,
                    "jit entry `self.{}` is not declared in {}'s "
                    "__compile_keys__".format(attr, node.name),
                )
            elif attr in serve and attr not in covered:
                emit(
                    "TPU603", assign,
                    "serve-path jit entry `self.{}` is missing from the "
                    "warmup shape registry (llm/warmup.py "
                    "WARMUP_COVERED)".format(attr),
                )
    return findings
