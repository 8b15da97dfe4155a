"""TPU6xx compile-surface discipline: per-rule fixtures, registry
consistency, source-mutation regressions, and the github CLI format
(docs/static_analysis.md; analyze/rules_compile.py).

Mirrors test_analyze.py's contract for the new rule family: every rule has
a positive, a negative, and an ignore-comment fixture; the project
registries (bucketizers, warmup coverage, ``__compile_keys__``) are pinned
to the definitions they mirror; and stripping the PR's bucketizer fixes
from kv_cache.py resurfaces TPU601 — the annotations and pads are
load-bearing, not decorative.
"""

import ast
import os
import subprocess
import sys
import textwrap

from clearml_serving_tpu.analyze import RULES, analyze_paths, analyze_source
from clearml_serving_tpu.analyze import rules_compile

PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(PKG_ROOT, "clearml_serving_tpu")

# in-package fixture path: TPU603 resolves the REAL llm/warmup.py registry
# relative to it; the out-of-tree path falls back to the analyzer's mirror
LLM_PATH = os.path.join(PKG_DIR, "llm", "fixture.py")
OUT_OF_TREE = "/nonexistent/fixture.py"


def codes(source, path=LLM_PATH):
    return [f.code for f in analyze_source(textwrap.dedent(source), path)]


# -- TPU601: unbucketed request-varying shape keys ----------------------------


def test_tpu601_raw_request_varying_upload():
    src = """
        import jax.numpy as jnp
        def f(self, request):
            ids = request.prompt_ids
            return jnp.asarray(ids, jnp.int32)
    """
    assert codes(src) == ["TPU601"]


def test_tpu601_parameter_name_is_a_taint_source():
    src = """
        import jax.numpy as jnp
        def demote(self, pages):
            return jnp.asarray(pages, jnp.int32)
    """
    assert codes(src) == ["TPU601"]


def test_tpu601_bucketizer_launders():
    src = """
        import jax.numpy as jnp
        from .shapes import pad_pages
        def demote(self, pages):
            return jnp.asarray(pad_pages(pages), jnp.int32)
    """
    assert codes(src) == []


def test_tpu601_taint_flows_through_host_buffers():
    # shape taint survives an intermediate np.zeros of a tainted shape ...
    bad = """
        import jax.numpy as jnp, numpy as np
        def f(self, ids):
            row = np.zeros((1, len(ids)), np.int32)
            return jnp.asarray(row)
    """
    assert codes(bad) == ["TPU601"]
    # ... and a bucketed shape cleans the SAME name
    good = """
        import jax.numpy as jnp, numpy as np
        def f(self, ids):
            bucket = self._bucket_for(len(ids))
            tokens = np.zeros((1, bucket), np.int32)
            return jnp.asarray(tokens)
    """
    assert codes(good) == []


def test_tpu601_floor_div_pad_idiom_is_clean():
    # the `-(-n // m) * m` page-multiple pad collapses the key space
    src = """
        import jax.numpy as jnp, numpy as np
        def f(self, ids):
            bucket = -(-len(ids) // 512) * 512
            tokens = np.zeros((1, bucket), np.int32)
            return jnp.asarray(tokens)
    """
    assert codes(src) == []


def test_tpu601_device_alloc_shaped_by_request():
    src = """
        import jax.numpy as jnp
        def f(self, ids):
            return jnp.zeros(len(ids))
    """
    assert codes(src) == ["TPU601"]


def test_tpu601_module_bucketizer_registration():
    src = """
        import jax.numpy as jnp
        __bucketizers__ = ("_my_pad",)
        def f(self, pages):
            return jnp.asarray(_my_pad(pages), jnp.int32)
    """
    assert codes(src) == []


def test_tpu601_ignore_comment():
    src = """
        import jax.numpy as jnp
        def f(self, pages):
            return jnp.asarray(pages, jnp.int32)  # tpuserve: ignore[TPU601] page-count-keyed, warmup-covered
    """
    assert codes(src) == []


def test_tpu601_plain_np_asarray_is_readback_not_upload():
    # np.asarray is the device->host readback idiom (TPU502's rationale);
    # only the jnp-family uploads mint device programs
    src = """
        import numpy as np
        def f(self, pages):
            return np.asarray(pages, np.int32)
    """
    assert codes(src) == []
    # the spelled-out host module is host too — only jax.numpy is device
    bare = """
        import numpy
        def f(self, pages):
            return numpy.asarray(pages)
    """
    assert codes(bare) == []
    spelled = """
        import jax
        def f(self, pages):
            return jax.numpy.asarray(pages)
    """
    assert codes(spelled) == ["TPU601"]


# -- TPU602: dtype/weak-type drift at jit boundaries --------------------------


def test_tpu602_float_literal_at_jit_call():
    src = """
        def f(self, x):
            return self._decode_chunk_jit(x, 0.5)
    """
    assert codes(src) == ["TPU602"]


def test_tpu602_typed_constant_is_fine():
    src = """
        import jax.numpy as jnp
        def f(self, x):
            return self._decode_chunk_jit(x, jnp.float32(0.5))
    """
    assert codes(src) == []


def test_tpu602_dtype_less_np_asarray():
    src = """
        import numpy as np
        def f(self, x):
            return self._decode_chunk_jit(np.asarray(x))
    """
    assert codes(src) == ["TPU602"]
    src_typed = """
        import numpy as np
        def f(self, x):
            return self._decode_chunk_jit(np.asarray(x, np.int32))
    """
    assert codes(src_typed) == []


def test_tpu602_ignore_comment():
    src = """
        def f(self, x):
            return self._decode_chunk_jit(x, 0.5)  # tpuserve: ignore[TPU602] reasoned
    """
    assert codes(src) == []


def test_tpu602_non_jit_calls_not_checked():
    src = """
        def f(self, x):
            return helper(x, 0.5)
    """
    assert codes(src) == []


# -- TPU603: __compile_keys__ closed world ------------------------------------


def test_tpu603_undeclared_jit_entry():
    src = """
        import jax
        class E:
            __compile_keys__ = {"serve": ()}
            __shardings__ = {"params": "llama_param_sharding"}
            def __init__(self):
                self._rogue_jit = jax.jit(lambda x: x)
    """
    assert codes(src, path=OUT_OF_TREE) == ["TPU603"]


def test_tpu603_serve_entry_missing_from_warmup_registry():
    src = """
        import jax
        class E:
            __compile_keys__ = {"serve": ("_never_warmed_jit",)}
            __shardings__ = {"params": "llama_param_sharding"}
            def __init__(self):
                self._never_warmed_jit = jax.jit(lambda x: x)
    """
    assert codes(src, path=OUT_OF_TREE) == ["TPU603"]
    # the same entry under a non-serve role is a deliberate classification
    lazy = src.replace('"serve"', '"lazy"')
    assert codes(lazy, path=OUT_OF_TREE) == []


def test_tpu603_covered_serve_entry_is_fine():
    src = """
        import jax
        class E:
            __compile_keys__ = {"serve": ("_decode_chunk_jit",)}
            __shardings__ = {"params": "llama_param_sharding"}
            def __init__(self):
                self._decode_chunk_jit = jax.jit(lambda x: x)
    """
    assert codes(src, path=OUT_OF_TREE) == []


def test_tpu603_jit_suffix_convention_counts_without_jit_call():
    # `self._sample_jit = sample_tokens` (a module-level jitted function
    # re-exported under the naming convention) is still a compile entry
    src = """
        class E:
            __compile_keys__ = {"serve": ()}
            __shardings__ = {"params": "llama_param_sharding"}
            def __init__(self):
                self._sneaky_jit = sample_tokens
    """
    assert codes(src, path=OUT_OF_TREE) == ["TPU603"]


def test_tpu603_reads_registry_from_real_warmup_py():
    # a file INSIDE the package resolves WARMUP_COVERED from llm/warmup.py
    # — an entry the real registry covers passes with no mirror involved
    src = """
        import jax
        class E:
            __compile_keys__ = {"serve": ("_first_token_jit",)}
            __shardings__ = {"params": "llama_param_sharding"}
            def __init__(self):
                self._first_token_jit = jax.jit(lambda x: x)
    """
    assert codes(src, path=LLM_PATH) == []


def test_tpu603_classes_without_declaration_are_not_checked():
    src = """
        import jax
        class Free:
            def __init__(self):
                self._whatever_jit = jax.jit(lambda x: x)
    """
    assert codes(src, path=OUT_OF_TREE) == []


# -- TPU604: request-varying static args --------------------------------------


def test_tpu604_tainted_static_argnum():
    src = """
        import jax
        g = jax.jit(fn, static_argnums=(1,))
        def f(self, request):
            n = len(request.prompt_ids)
            return g(0, n)
    """
    assert codes(src) == ["TPU604"]


def test_tpu604_bucketized_static_is_fine():
    src = """
        import jax
        g = jax.jit(fn, static_argnums=(1,))
        def f(self, request):
            n = self._bucket_for(len(request.prompt_ids))
            return g(0, n)
    """
    assert codes(src) == []


def test_tpu604_tainted_static_argname():
    src = """
        import jax
        g = jax.jit(fn, static_argnames=("n",))
        def f(self, request):
            return g(0, n=len(request.prompt_ids))
    """
    assert codes(src) == ["TPU604"]


def test_tpu604_ignore_comment():
    src = """
        import jax
        g = jax.jit(fn, static_argnums=(1,))
        def f(self, request):
            return g(0, len(request.prompt_ids))  # tpuserve: ignore[TPU604] reasoned
    """
    assert codes(src) == []


# -- registry consistency -----------------------------------------------------


def test_warmup_registry_mirror_matches_warmup_py():
    from clearml_serving_tpu.llm import warmup

    assert rules_compile.WARMUP_COVERED == warmup.WARMUP_COVERED, (
        "analyze/rules_compile.WARMUP_COVERED and llm/warmup.WARMUP_COVERED "
        "drifted — update both together"
    )


def test_compile_keys_serve_entries_are_warmup_covered():
    from clearml_serving_tpu.llm import warmup
    from clearml_serving_tpu.llm.engine import LLMEngineCore

    serve = set(LLMEngineCore.__compile_keys__["serve"])
    missing = serve - warmup.WARMUP_COVERED
    assert not missing, (
        "serve-path jit entries missing from the warmup shape registry: "
        "{}".format(sorted(missing))
    )


def test_compile_keys_declaration_matches_engine_source():
    """Closed world both ways: every jit attribute the engine source
    assigns is declared, and every declared name is actually assigned
    (a stale declaration would grandfather a removed entry's name)."""
    from clearml_serving_tpu.llm.engine import LLMEngineCore

    path = os.path.join(PKG_DIR, "llm", "engine.py")
    with open(path, "r", encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    cls = next(
        n for n in ast.walk(tree)
        if isinstance(n, ast.ClassDef) and n.name == "LLMEngineCore"
    )
    assigned = {attr for attr, _node in rules_compile._class_jit_attrs(cls)}
    declared = set()
    for names in LLMEngineCore.__compile_keys__.values():
        declared |= set(names)
    assert assigned == declared, (
        "engine.__compile_keys__ out of sync with the jit assignments: "
        "undeclared={} stale={}".format(
            sorted(assigned - declared), sorted(declared - assigned)
        )
    )


def test_bucketizer_registry_names_exist_in_tree():
    """Every project-level bucketizer name resolves to a real definition
    somewhere in the package — a typo'd registry entry would silently
    launder nothing."""
    defined = set()
    for dirpath, _dirs, files in os.walk(PKG_DIR):
        if "__pycache__" in dirpath:
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name), "r",
                      encoding="utf-8") as fh:
                try:
                    tree = ast.parse(fh.read())
                except SyntaxError:
                    continue
            for node in ast.walk(tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    defined.add(node.name)
    missing = rules_compile.BUCKETIZERS - defined
    assert not missing, "bucketizers with no definition: {}".format(
        sorted(missing)
    )


def test_shapes_helpers_behave():
    from clearml_serving_tpu.llm.shapes import (
        pad_pages,
        pad_to_multiple,
        pow2_bucket,
    )

    assert [pow2_bucket(n) for n in (0, 1, 2, 3, 5, 8, 9)] == [
        1, 1, 2, 4, 8, 8, 16,
    ]
    assert pow2_bucket(3, lo=8) == 8
    assert pad_to_multiple(17, 16) == 32
    assert pad_to_multiple(16, 16) == 16
    assert pad_pages([4, 7, 9]) == [4, 7, 9, 0]
    assert pad_pages([5]) == [5]


def test_every_tpu6xx_code_is_in_the_catalog():
    for code in ("TPU601", "TPU602", "TPU603", "TPU604"):
        assert code in RULES


# -- satellite: the tier-path fixes are load-bearing --------------------------


def test_mutation_unbucketed_demote_is_caught_statically():
    """Stripping the demotion gather's pad_pages bucketizer resurfaces
    TPU601 — the regression test for this PR's tier-path fix."""
    path = os.path.join(PKG_DIR, "llm", "kv_cache.py")
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    stripped = source.replace(
        "idx = jnp.asarray(pad_pages(pages), jnp.int32)",
        "idx = jnp.asarray(pages, jnp.int32)",
    )
    assert stripped != source, "expected the demote pad_pages call"
    found = [f.code for f in analyze_source(stripped, path)]
    assert "TPU601" in found


def test_mutation_unbucketed_promote_is_caught_statically():
    path = os.path.join(PKG_DIR, "llm", "kv_cache.py")
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    stripped = source.replace(
        "page_ids = jnp.asarray(padded, jnp.int32)",
        "page_ids = jnp.asarray(pages, jnp.int32)",
    )
    assert stripped != source, "expected the promote padded upload"
    found = [f.code for f in analyze_source(stripped, path)]
    assert "TPU601" in found


def test_mutation_undeclared_engine_jit_entry_is_caught():
    """Grafting a new undeclared jit entry into the engine class is
    flagged: the compile surface is closed-world."""
    path = os.path.join(PKG_DIR, "llm", "engine.py")
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    grafted = source.replace(
        "        self._insert_jit = jax.jit(_insert, donate_argnums=(0,))",
        "        self._insert_jit = jax.jit(_insert, donate_argnums=(0,))\n"
        "        self._grafted_jit = jax.jit(_insert)",
    )
    assert grafted != source
    found = [f.code for f in analyze_source(grafted, path)]
    assert "TPU603" in found


def test_tree_is_clean_for_tpu6xx():
    findings = [
        f for f in analyze_paths([PKG_DIR])
        if f.code.startswith("TPU6")
    ]
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)


# -- CLI: --format github -----------------------------------------------------


def test_cli_github_format(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax.numpy as jnp
        def f(self, pages):
            return jnp.asarray(pages, jnp.int32)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "clearml_serving_tpu.analyze",
         "--format", "github", str(bad)],
        capture_output=True, text=True, cwd=PKG_ROOT,
    )
    assert proc.returncode == 1
    lines = [l for l in proc.stdout.splitlines() if l]
    assert len(lines) == 1
    assert lines[0].startswith("::error file=")
    assert "title=TPU601" in lines[0]
    assert "line=4" in lines[0]

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "clearml_serving_tpu.analyze",
         "--format", "github", str(clean)],
        capture_output=True, text=True, cwd=PKG_ROOT,
    )
    assert proc.returncode == 0
    assert proc.stdout.strip() == ""
