"""The documents name files that exist.

``README.md`` and every ``docs/*.md`` are read for back-quoted repo paths:
a token with a ``/`` whose first segment is a top-level directory of the
checkout, a bare UPPER_CASE ``*.md`` / ``*.json`` / ``*.jsonl`` (how this
repo names the documents and records at its root), or a bare ``*.py``
(which must be some source file's name).  What the pattern leaves out, it
leaves out by construction: HTTP routes start with ``/``; package-relative
paths (``serving/fastpath.py``) and user files (``engine.json``,
``pio-env.sh``) start with no top-level directory; run outputs live in
directories ``.gitignore`` lists; placeholders (``<id>``, ``$VAR``, ``…``)
name no one file.  ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are
histories — they name what was — and are not read.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    os.path.join("docs", f)
    for f in os.listdir(os.path.join(REPO, "docs"))
    if f.endswith(".md")
)

_ROOT_RECORD = re.compile(r"[A-Z][A-Z0-9_]*\.(md|json|jsonl)")
_BARE_PY = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\.py")
_PLACEHOLDER = re.compile(r"[<>${}…=]")


@functools.lru_cache(maxsize=None)
def _top_level_dirs() -> frozenset:
    """Directories at the root that git would commit: not hidden, and not
    a run output ``.gitignore`` lists (``chiprun_out/``)."""
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        ignored = {line.strip().rstrip("/") for line in f}
    return frozenset(
        d for d in os.listdir(REPO)
        if os.path.isdir(os.path.join(REPO, d))
        and not d.startswith(".") and d not in ignored
    )


@functools.lru_cache(maxsize=None)
def _source_names() -> frozenset:
    names = {f for f in os.listdir(REPO) if f.endswith(".py")}
    for top in _top_level_dirs():
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(f for f in files if f.endswith(".py"))
    return frozenset(names)


def _missing(text: str) -> list:
    tops, sources = _top_level_dirs(), _source_names()
    missing = []
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for token in quoted.split():
            token = token.strip(",.;()[]\"'")
            # `path.py:line`, `path.py:function`, `path.py::Class::test`
            token = token.split(":", 1)[0]
            if not token or _PLACEHOLDER.search(token):
                continue
            if "/" in token:
                if token.split("/", 1)[0] not in tops:
                    continue
                found = glob.glob(os.path.join(REPO, token))
            elif _ROOT_RECORD.fullmatch(token):
                found = os.path.exists(os.path.join(REPO, token))
            elif _BARE_PY.fullmatch(token):
                found = token in sources
            else:
                continue
            if not found:
                missing.append(token)
    return sorted(set(missing))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_that_exist(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        missing = _missing(f.read())
    assert not missing, f"{document} names files that do not exist: {missing}"
