"""The documents name only files that exist (ROADMAP D13's guard).

Every ``python[3] <path>`` command and every back-quoted repo path that
ends in ``.py`` / ``.sh`` / ``.json`` / ``.md`` in a document must be a
file of the tree. A path may be written from the repo root, from the
package, from the document's own directory, or from ``tools/`` or
``tests/``; a bare file name may be any file of that name. A name that is
a pattern, an output a command writes, or history is allow-listed below
with its reason; a dangling path is mended in the document, not listed.
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    "README.md",
    "docs/API.md",
    "docs/DESIGN.md",
    "docs/OBSERVABILITY.md",
    "docs/PARITY.md",
    "docs/RESILIENCE.md",
    "docs/RUNBOOKS.md",
    "docs/SERVING.md",
    "benchmark/README.md",
)

_SUFFIXES = (".py", ".sh", ".json", ".md")
_SKIP_DIRS = {".git", "__pycache__", "chiprun_out", ".jax_cache", ".pytest_cache"}

# name -> why a document may name a file the tree does not hold; a key
# "<document>::<name>" allows the name in that document alone
ALLOWED = {
    "Graphframes.py": "the upstream script this repo was modelled on (SURVEY.md)",
    "/root/reference/CommunityDetection/Graphframes.py": "the same, where "
    "the reference checkout is mounted",
    "manifest.json": "output: a snapshot's or a checkpoint's own manifest",
    "GRAPHMINE_ROOFLINE_FILE=/path.json": "placeholder: the operator's own file",
    "ops/blocking.py": "history: docs/DESIGN.md 'Tried, measured, deleted', "
    "with the commit that still holds it",
    "benchmark/README.md::bench.py": "an accepted file says nothing there "
    "imports it, which is true of a file that is gone",
}


@functools.cache
def _tree_files() -> frozenset[str]:
    out = set()
    for base, dirs, files in os.walk(REPO):
        rel = os.path.relpath(base, REPO)
        # _proof/ keeps its own scripts; a directory there is an unpacked checkout
        dirs[:] = [] if rel == "_proof" else [d for d in dirs if d not in _SKIP_DIRS]
        for name in files:
            out.add(os.path.normpath(os.path.join(rel, name)))
    return frozenset(out)


_COMMAND = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+)*([A-Za-z0-9_./-]+\.py)\b")
_QUOTED = re.compile(r"`([^`\s]+)`")


def _named_paths(text: str) -> set[str]:
    names = set(_COMMAND.findall(text))
    for token in _QUOTED.findall(text):
        # `ops/lpa.py:_cached_auto_plan`, `README.md:284`, `tests/x.py::test_y`
        path = token.split(":", 1)[0].rstrip(".,;)")
        if path.endswith(_SUFFIXES):
            names.add(path)
    return names


def _exists(path: str, doc: str) -> bool:
    if any(c in path for c in "*<>{}$%"):  # a pattern or a placeholder, not a file
        return True
    if path in ALLOWED or f"{doc}::{path}" in ALLOWED:
        return True
    path = os.path.normpath(path)
    tree = _tree_files()
    if "/" not in path:
        return any(os.path.basename(p) == path for p in tree)
    bases = ("", "graphmine_tpu", os.path.dirname(doc), "tools", "tests")
    return any(os.path.normpath(os.path.join(b, path)) in tree for b in bases)


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    named = _named_paths(text)
    assert named, f"{doc} names no file: the patterns above no longer see it"
    missing = sorted(p for p in named if not _exists(p, doc))
    assert not missing, f"{doc} names files the tree does not hold: {missing}"
