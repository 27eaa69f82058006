"""The documents a new owner reads first name what the tree holds.

A document names a file in backticks, in full from the repo's root
(`chipbench/run.py`), from its own directory, or by the tail of its path
inside the program's own trees (`crypto/tpu/curve.py`, `wire.py`).  A name
whose file is gone is a pointer to a system that is no longer there.
"""

import functools
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "CLAUDE.md"] + sorted(
    "docs/" + fn for fn in os.listdir(os.path.join(REPO, "docs")) if fn.endswith(".md")
)
#: a backticked token ending in a source or record suffix, with an optional
#: ``::test`` or ``:line`` tail; URL paths (``/trace.json``) start with a slash
_NAMED = re.compile(r"`([^`\s/][^`\s]*\.(?:py|json|md|cpp|h))(?:::?[^`\s]*)?`")
#: trees whose files a document may name by the tail of their path
_SHORTHAND_ROOTS = ("hbbft_tpu/", "native/", "tests/", "benchmarks/", "tools/")
_SKIP_DIRS = {"build", "__pycache__", "chiprun_out", "chiprun_src"}


@functools.lru_cache(maxsize=None)
def _tree():
    files = set()
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [
            d for d in dirnames if not d.startswith(".") and d not in _SKIP_DIRS
        ]
        for fn in filenames:
            files.add(os.path.relpath(os.path.join(dirpath, fn), REPO))
    return files


def _found(name: str, doc: str, files) -> bool:
    if name in files:
        return True
    if os.path.normpath(os.path.join(os.path.dirname(doc), name)) in files:
        return True
    return any(
        f.startswith(_SHORTHAND_ROOTS) and f.endswith("/" + name) for f in files
    )


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    files = _tree()
    names = {m.group(1) for m in _NAMED.finditer(text)}
    names = {n for n in names if "<" not in n and "*" not in n}
    missing = sorted(n for n in names if not _found(n, doc, files))
    assert not missing, f"{doc} names files that are not in the tree: {missing}"


def test_readme_names_every_cell():
    """README.md gives the benchmark's one command and every cell of
    ``BENCHMARK.json`` by name."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    assert " ".join(bench["command"]) in readme
    for cell in bench["workloads"]:
        assert f"`{cell['name']}`" in readme, cell["name"]
