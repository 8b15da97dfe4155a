"""tpuserve-analyze TPU7xx (analyze/rules_lifecycle.py): per-rule fixtures
(positive / negative / ignore), the __acquires__/LIFECYCLE_REGISTRY
consistency gate, source-mutation gates proving the committed fixes are
load-bearing, and the CLI's family-select/--changed-only/--timings modes.

The tree-wide zero-findings acceptance gate lives in test_analyze.py (it
runs every family); here a family-selected pass pins that TPU7xx alone is
clean, so a future failure names the family immediately.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from clearml_serving_tpu.analyze import (
    RULES,
    analyze_paths,
    analyze_source,
    expand_select,
)
from clearml_serving_tpu.analyze import rules_lifecycle

PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(PKG_ROOT, "clearml_serving_tpu")
LLM_PATH = "clearml_serving_tpu/llm/fixture.py"


def codes(source, path=LLM_PATH, select=None):
    return [
        f.code
        for f in analyze_source(textwrap.dedent(source), path, select=select)
    ]


# -- TPU701: leaking exception paths -----------------------------------------


def test_tpu701_exception_path_leak():
    src = """
        def admit(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)
            prepare_dispatch()
            pool.free(slot)
    """
    assert codes(src) == ["TPU701"]


def test_tpu701_normal_path_leak():
    src = """
        def admit(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)
    """
    assert codes(src) == ["TPU701"]


def test_tpu701_catch_all_cleanup_is_fine():
    src = """
        def admit(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)
            try:
                prepare_dispatch()
            except Exception:
                pool.free(slot)
                raise
            pool.free(slot)
    """
    assert codes(src) == []


def test_tpu701_typed_handler_still_leaks_other_exceptions():
    src = """
        def admit(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)
            try:
                prepare_dispatch()
            except MemoryError:
                pool.free(slot)
                raise
            pool.free(slot)
    """
    assert codes(src) == ["TPU701"]


def test_tpu701_try_finally_is_fine():
    src = """
        def admit(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)
            try:
                prepare_dispatch()
            finally:
                pool.free(slot)
    """
    assert codes(src) == []


def test_tpu701_none_check_early_return_is_fine():
    src = """
        def admit(cache, ids):
            hit = cache.lookup_pages(ids)
            if hit is None:
                return None
            use(hit)
    """
    # `use(hit)` is an ownership hand-off (fail-open); the None branch is
    # vacuous — neither path leaks
    assert codes(src) == []


def test_tpu701_ownership_transfers_discharge():
    # stash on an object / return / registered drop handler all transfer
    src = """
        def stash(cache, request, ids):
            hit = cache.lookup_pages(ids)
            request._prefix_hit = hit

        def forward(cache, ids):
            hit = cache.lookup_pages(ids)
            return hit

        def degrade(cache, ids):
            hit = cache.lookup_pages(ids)
            cache.uncount_hit(hit)
    """
    assert codes(src) == []


def test_tpu701_release_in_loop_over_collection_is_fine():
    src = """
        def sweep(pool, jobs, lengths0):
            extended = []
            for slot in jobs:
                pool.extend(slot, 4)
                extended.append(slot)
            try:
                dispatch()
            except Exception:
                for slot in extended:
                    pool.truncate(slot, 0)
                raise
            for slot in extended:
                pool.truncate(slot, 0)
    """
    assert codes(src) == []


def test_tpu701_pin_run_and_host_tier_pairs():
    src = """
        def preempt(cache, tier, ids, pages):
            handle = cache.pin_run(ids)
            commit()
            cache.unpin_run(handle)

        def demote(tier, pages):
            ids = tier.allocate(len(pages))
            copy_rows()
            tier.free(ids)
    """
    # commit()/copy_rows() can raise with the handle held
    assert codes(src) == ["TPU701", "TPU701"]


def test_tpu701_ignore_comment():
    src = """
        def transfer(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)  # tpuserve: ignore[TPU701] pages ride the slot table
            publish()
    """
    assert codes(src) == []


def test_tpu701_static_false_protocols_are_ledger_only():
    # cross-function protocols (declared "static": False) never produce
    # TPU701: the runtime ownership ledger audits them instead
    src = """
        def store(cache, pool, pages):
            pool.ref_pages(pages)
            attach_nodes()
    """
    assert codes(src) == []


# -- TPU702: double release ---------------------------------------------------


def test_tpu702_double_free():
    src = """
        def teardown(pool, slot):
            pages = pool.allocate(slot, 8)
            pool.free(slot)
            pool.free(slot)
    """
    assert codes(src) == ["TPU702"]


def test_tpu702_single_release_per_path_is_fine():
    src = """
        def teardown(pool, slot, ok):
            pages = pool.allocate(slot, 8)
            if ok:
                pool.free(slot)
            else:
                pool.truncate(slot, 0)
    """
    assert codes(src) == []


def test_tpu702_loop_release_not_flagged():
    # the SAME release statement re-visited by a loop back edge is not a
    # double free (each iteration pairs with its own acquire)
    src = """
        def per_job(pool, jobs):
            for slot in jobs:
                pages = pool.allocate(slot, 8)
                emit()
                pool.free(slot)
    """
    assert "TPU702" not in codes(src)


def test_tpu702_ignore_comment():
    src = """
        def teardown(pool, slot):
            pages = pool.allocate(slot, 8)
            pool.free(slot)
            pool.free(slot)  # tpuserve: ignore[TPU702] idempotent by construction
    """
    assert codes(src) == []


# -- TPU703: publish before the fence ----------------------------------------


def test_tpu703_publish_before_fence():
    src = """
        def promote(pool, backend, node, n):
            fresh = pool.allocate_cache_pages(n)
            node.pages = list(fresh)
            backend.import_pages(hk, hv, fresh)
    """
    assert "TPU703" in codes(src)


def test_tpu703_fenced_publish_is_fine():
    src = """
        def promote(pool, backend, node, n):
            fresh = pool.allocate_cache_pages(n)
            try:
                backend.import_pages(hk, hv, fresh)
            except BaseException:
                pool.unref_pages(fresh)
                raise
            node.pages = list(fresh)
    """
    assert codes(src) == []


def test_tpu703_tracks_derived_names():
    # the publish uses a name DERIVED from the mint (the store_shipped
    # shape: pages = list(fresh[i:j]))
    src = """
        def promote(pool, backend, node, n):
            fresh = pool.allocate_cache_pages(n)
            pages = list(fresh)
            node.pages = pages
            backend.import_pages(hk, hv, fresh)
    """
    assert "TPU703" in codes(src)


def test_tpu703_ignore_comment():
    src = """
        def promote(pool, backend, node, n):
            fresh = pool.allocate_cache_pages(n)
            node.pages = list(fresh)  # tpuserve: ignore[TPU703] fixture
            backend.import_pages(hk, hv, fresh)
    """
    assert "TPU703" not in codes(src)


# -- TPU704: consume-once transport ------------------------------------------


def test_tpu704_reuse_after_attach():
    src = """
        def receive(transport, cache, key, ids, backend):
            shipment = transport.recv(key)
            if shipment is None:
                return 0
            cache.store_shipped(ids, 0, shipment, backend)
            return shipment.hk
    """
    assert codes(src) == ["TPU704"]


def test_tpu704_double_recv_same_key():
    src = """
        def receive(transport, key):
            shipment = transport.recv(key)
            again = transport.recv(key)
            return again
    """
    assert codes(src) == ["TPU704"]


def test_tpu704_clean_receive_is_fine():
    src = """
        def receive(transport, cache, key, ids, backend):
            shipment = transport.recv(key)
            if shipment is None:
                return 0
            cache.store_shipped(ids, 0, shipment, backend)
            return 1
    """
    assert codes(src) == []


def test_tpu704_retry_loop_is_fine():
    # the explorer's bounded-retry receiver: the rebinding recv in a loop
    # is one logical pop, not a double consume
    src = """
        def receive(transport, cache, key, ids, backend):
            got = None
            for _ in range(6):
                got = transport.recv(key)
                if got is not None:
                    break
            if got is not None:
                cache.store_shipped(ids, 0, got, backend)
    """
    assert codes(src) == []


def test_tpu704_receiver_filter():
    # an unrelated .recv() (sockets, queues) never matches
    src = """
        def pump(sock, cache, ids, backend):
            data = sock.recv(4096)
            cache.store_shipped(ids, 0, data, backend)
            return data
    """
    assert codes(src) == []


def test_tpu704_ignore_comment():
    src = """
        def receive(transport, cache, key, ids, backend):
            shipment = transport.recv(key)
            cache.store_shipped(ids, 0, shipment, backend)
            return shipment.hk  # tpuserve: ignore[TPU704] fixture
    """
    assert codes(src) == []


# -- declarations <-> registry consistency ------------------------------------


def test_acquires_declarations_match_lifecycle_registry():
    """Every __acquires__ class declaration must appear in the analyzer's
    LIFECYCLE_REGISTRY (resource + releases + static flag agree): the
    declaration next to the code and the cross-module registry can never
    drift apart."""
    from clearml_serving_tpu.llm.engine import LLMEngineCore
    from clearml_serving_tpu.llm.kv_cache import HostKVTier, PagePool
    from clearml_serving_tpu.llm.kv_transport import SharedSlabTransport
    from clearml_serving_tpu.llm.kv_wire import SocketSlabTransport
    from clearml_serving_tpu.llm.prefix_cache import RadixPrefixCache
    from clearml_serving_tpu.serving.process_replica import (
        ProcessEngineReplica,
    )

    for cls in (PagePool, HostKVTier, RadixPrefixCache, SharedSlabTransport,
                SocketSlabTransport, ProcessEngineReplica, LLMEngineCore):
        for method, decl in cls.__acquires__.items():
            entries = rules_lifecycle.LIFECYCLE_REGISTRY.get(method)
            assert entries, (
                "{}.{} declared in __acquires__ but missing from "
                "LIFECYCLE_REGISTRY".format(cls.__name__, method)
            )
            match = [
                e for e in entries if e["resource"] == decl["resource"]
            ]
            assert match, (
                "{}.{}: resource {!r} not in the registry's entries "
                "{}".format(cls.__name__, method, decl["resource"], entries)
            )
            entry = match[0]
            assert set(decl["releases"]) <= set(entry["releases"]), (
                "{}.{}: declared releases {} not all in registry "
                "{}".format(cls.__name__, method, decl["releases"],
                            entry["releases"])
            )
            assert bool(decl.get("static", True)) == bool(
                entry.get("static", True)
            ), "{}.{}: static flag disagrees".format(cls.__name__, method)


def test_registry_resources_are_ledger_resources():
    """Every registry resource the static pass names must be a resource
    the runtime ledger tracks — the two halves audit ONE protocol set."""
    from clearml_serving_tpu.llm import lifecycle_ledger

    for entries in rules_lifecycle.LIFECYCLE_REGISTRY.values():
        for entry in entries:
            assert entry["resource"] in lifecycle_ledger.RESOURCES, (
                "registry resource {!r} unknown to the ledger".format(
                    entry["resource"]
                )
            )


def test_file_declarations_parse_from_source():
    """__acquires__ declarations parse with stdlib ast (no import of the
    declaring module) — the analyzer must work on detached fixtures."""
    import ast

    path = os.path.join(PKG_DIR, "llm", "kv_cache.py")
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    decls = rules_lifecycle.file_declarations(tree)
    assert "allocate" in decls and "pin_pages" in decls


def test_every_tpu7_code_is_in_the_catalog():
    for code in ("TPU701", "TPU702", "TPU703", "TPU704"):
        assert code in RULES
    assert len(RULES) == 28, sorted(RULES)


# -- tree gate (family-selected) ----------------------------------------------


def test_tree_is_clean_under_tpu7xx():
    findings = analyze_paths([PKG_DIR], select=["TPU7xx"])
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)


# -- source-mutation gates: the committed fixes are load-bearing --------------


def _mutate(path, old, new):
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    mutated = source.replace(old, new)
    assert mutated != source, "mutation target not found in {}".format(path)
    return source, mutated


def test_mutation_store_shipped_unref_guard_is_load_bearing():
    """Stripping the unref-on-failure guard from store_shipped's mint
    resurfaces the exception-path leak as TPU701 (the fix this PR made:
    a raise out of the row gather used to leak the fresh pages)."""
    path = os.path.join(PKG_DIR, "llm", "prefix_cache.py")
    source, mutated = _mutate(
        path,
        "            except BaseException:\n"
        "                self._pool.unref_pages(fresh)\n"
        "                raise",
        "            except BaseException:\n"
        "                raise",
    )
    assert "TPU701" in [f.code for f in analyze_source(mutated, path)]
    assert "TPU701" not in [f.code for f in analyze_source(source, path)]


def test_mutation_verify_row_rollback_annotation_is_load_bearing():
    """The ragged launch over-allocates a row's pages (a verify row's k+1,
    a decode window) and rolls them back in ANOTHER function, at retire or
    recovery: the TPU701 annotation on that extend says so. Stripping it
    resurfaces the finding — the pairing is declared, not overlooked."""
    path = os.path.join(PKG_DIR, "llm", "engine.py")
    source, mutated = _mutate(
        path,
        "  # tpuserve: ignore[TPU701] rolled back at retire/recover",
        "",
    )
    assert "TPU701" in [f.code for f in analyze_source(mutated, path)]
    assert "TPU701" not in [f.code for f in analyze_source(source, path)]


def test_mutation_fence_call_is_load_bearing():
    """Renaming store_shipped's import_pages fence call resurfaces TPU703:
    fresh page ids would publish before any upload was enqueued."""
    path = os.path.join(PKG_DIR, "llm", "prefix_cache.py")
    source, mutated = _mutate(
        path, "backend.import_pages(", "backend.import_pages_deferred("
    )
    assert "TPU703" in [f.code for f in analyze_source(mutated, path)]
    assert "TPU703" not in [f.code for f in analyze_source(source, path)]


def test_mutation_deleting_transfer_annotation_fails_the_tree():
    """The TPU701 ownership-transfer annotations are load-bearing, not
    decorative: stripping the lookup_pages pin-transfer annotation
    resurfaces the finding."""
    path = os.path.join(PKG_DIR, "llm", "prefix_cache.py")
    source, mutated = _mutate(
        path, "# tpuserve: ignore[TPU701] pin rides the returned hit", ""
    )
    assert "TPU701" in [f.code for f in analyze_source(mutated, path)]


# -- select expansion + CLI ---------------------------------------------------


def test_expand_select_families_and_codes():
    assert expand_select(["TPU7xx"]) == {
        "TPU701", "TPU702", "TPU703", "TPU704",
    }
    assert expand_select(["TPU3"]) == {"TPU301"}
    assert expand_select(["tpu301"]) == {"TPU301"}
    assert expand_select(["TPU301", "TPU7XX"]) == {
        "TPU301", "TPU701", "TPU702", "TPU703", "TPU704",
    }
    # unknown exact codes pass through (forward compatibility)
    assert "TPU999" in expand_select(["TPU999"])


def test_select_family_filters_findings():
    src = """
        import time
        def admit(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)
            time.sleep(1)
    """
    # full run: TPU701 only (sleep is fine in a sync def)
    assert codes(src) == ["TPU701"]
    assert codes(src, select=["TPU7xx"]) == ["TPU701"]
    assert codes(src, select=["TPU1xx"]) == []


def _run_cli(args, cwd=None):
    # the analyzer package must be importable from ANY cwd (the
    # --changed-only test runs inside a scratch git repo)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = PKG_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "clearml_serving_tpu.analyze"] + args,
        capture_output=True, text=True, env=env,
        cwd=cwd or PKG_ROOT,
    )


def test_cli_select_family_and_timings():
    proc = _run_cli(
        ["--select", "TPU7xx", "--timings", "clearml_serving_tpu/analyze"]
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout
    assert "rules_lifecycle" in proc.stdout  # per-family timing table


def test_cli_changed_only(tmp_path):
    """--changed-only reports only findings on diff-touched lines, with
    json format and exit codes unchanged."""
    repo = tmp_path / "repo"
    repo.mkdir()
    subprocess.run(["git", "init", "-q"], cwd=repo, check=True)
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t",
         "commit", "-q", "--allow-empty", "-m", "seed"],
        cwd=repo, check=True,
    )
    clean = textwrap.dedent("""
        def admit(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)
            pool.free(slot)
    """)
    target = repo / "mod.py"
    target.write_text(clean)
    subprocess.run(["git", "add", "mod.py"], cwd=repo, check=True)
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t",
         "commit", "-q", "-m", "clean"],
        cwd=repo, check=True,
    )
    # introduce a leak on a NEW line plus an untouched pre-existing one
    leaky = textwrap.dedent("""
        def admit(pool, slot, tokens):
            pages = pool.allocate(slot, tokens)
            prepare_dispatch()
            pool.free(slot)
    """)
    target.write_text(leaky)
    # full run flags the acquire line (line 3, unchanged text but the
    # finding anchors there); changed-only keeps it only if the diff
    # touched it — the diff touched line 4 (the inserted call), so the
    # acquire-line finding is filtered out
    proc = _run_cli(["--format", "json", str(target)], cwd=repo)
    assert proc.returncode == 1
    rows = [json.loads(line) for line in proc.stdout.splitlines()]
    assert any(r["rule"] == "TPU701" for r in rows)
    proc = _run_cli(
        ["--format", "json", "--changed-only", str(target)], cwd=repo
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.strip() == ""
    # a change ON the acquire line itself survives the filter
    target.write_text(leaky.replace(
        "pages = pool.allocate(slot, tokens)",
        "pages = pool.allocate(slot, tokens)  # touched",
    ))
    proc = _run_cli(
        ["--format", "json", "--changed-only", str(target)], cwd=repo
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rows = [json.loads(line) for line in proc.stdout.splitlines()]
    assert [r["rule"] for r in rows] == ["TPU701"]
    # ...and from a SUBDIRECTORY with a relative path: the pathspec must
    # resolve against the caller's cwd, not the repo root (a silent empty
    # diff would filter real findings and report the run clean)
    sub = repo / "sub"
    sub.mkdir()
    proc = _run_cli(
        ["--format", "json", "--changed-only", os.path.join("..", "mod.py")],
        cwd=sub,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rows = [json.loads(line) for line in proc.stdout.splitlines()]
    assert [r["rule"] for r in rows] == ["TPU701"]
