"""The documents name what exists (README.md and docs/*.md).

What rotted once: for twenty PRs the README and twelve files under docs/
cited a measurement tree (a root-level ``bench`` script, a ``benchmarks``
directory of CPU-timed JSON records) that nothing ran any more, beside the
yardstick the driver does run (``benchmark/run.py``, PERF.md, the ledger).
Two holds, a case per document, files only:

- every back-quoted path (a word with a slash whose first segment is an entry
  of the root or of the package: ``tests/test_x.py::test_y``, ``llm/engine.py``,
  ``llm/engine._emit``) exists, and a ``module.name`` is a word of its module;
- no document names the deleted tree.
"""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BASES = (REPO, REPO / "clearml_serving_tpu")
DOCUMENTS = ["README.md"] + sorted(
    "docs/" + p.name for p in (REPO / "docs").glob("*.md")
)
# the deleted tree, by the names the documents used for it
GONE = re.compile(
    r"(?<![\w/])bench\.py|(?<![\w/])benchmarks/|\w+_cpu\.json|ROOFLINE"
)
QUOTED = re.compile(r"`([^`\n]+)`")
# segments joined by slashes; not the tail of a URL or of an absolute path,
# and not a pattern (``tests/test_*.py``, ``configs/<config>.json``)
PATH = re.compile(r"(?<![\w./<>*{}$-])[\w.-]+(?:/[\w.-]+)+(?![\w/]*[*<{$])")


def exists(word):
    for base in BASES:
        if (base / word).exists():
            return True
        module, _, name = word.rpartition(".")
        source = base / (module + ".py")
        if source.is_file() and re.search(r"\b%s\b" % name, source.read_text()):
            return True
    return False


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    text = (REPO / document).read_text()
    roots = {p.name for base in BASES for p in base.iterdir()}
    cited = {
        word.rstrip(".")
        for span in QUOTED.findall(text) for word in PATH.findall(span)
    }
    cited = {word for word in cited if word.split("/")[0] in roots}
    assert cited, document + " cites no path: does PATH still match?"
    missing = sorted(word for word in cited if not exists(word))
    assert not missing, "{} names paths that do not exist: {}".format(
        document, missing
    )
    gone = sorted(set(GONE.findall(text)))
    assert not gone, "{} names the deleted measurement tree: {}".format(
        document, gone
    )
