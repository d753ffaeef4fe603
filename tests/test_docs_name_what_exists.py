"""Every file a document names is there.

One case per document a newcomer reads first.  In each, a token that begins
with one of the tree's top-level directories (``torchsnapshot_tpu/``,
``tests/``, ...), or that is a bare ``*.py`` / ``*.json`` / ``*.sh`` name,
must resolve: a path to a file or directory of the tree, a bare name to a
file somewhere in it.  Where a case fails the document is corrected, not
the rule loosened: the day a file goes, its mentions go with it.

One rule for patterns (a token holding ``*``, ``<...>`` or ``{a,b}``): one
that begins with a top-level directory is expanded against the tree, each
``<...>`` as ``*``, and every alternative of it must match something; a bare one is skipped, because
a bare pattern in these documents names what a run writes
(``<kind>-<op>.trace.json``), not what the tree holds.  ``PERF.md``,
``ROADMAP.md`` and ``CHANGES.md`` are history and are not in the list.
"""

import fnmatch
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("torchsnapshot_tpu", "tests", "tools", "chipbench", "docs", "examples")
DOCUMENTS = [
    "README.md",
    *sorted(
        os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
    ),
    ".claude/skills/verify/SKILL.md",
    "tools/check.sh",
]
_TOKEN = re.compile(r"[A-Za-z0-9_.*<>{},/-]+")
_BARE_NAME = re.compile(r"\.(py|json|sh)$")
_PATTERN_CHARS = re.compile(r"[*<{]")


def _tree():
    """Every file and directory under the top-level directories, and the
    files at the root, as paths relative to the root."""
    paths = {n for n in os.listdir(ROOT) if os.path.isfile(os.path.join(ROOT, n))}
    for top in TOP_DIRS:
        for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            rel = os.path.relpath(dirpath, ROOT)
            paths.add(rel)
            paths.update(os.path.join(rel, f) for f in filenames)
    return paths


def _braces(pattern):
    """``a/{b,c}.py`` as ``a/b.py`` and ``a/c.py``."""
    m = re.search(r"\{([^{}]*)\}", pattern)
    if m is None:
        return [pattern]
    return [
        expanded
        for part in m.group(1).split(",")
        for expanded in _braces(pattern[: m.start()] + part + pattern[m.end():])
    ]


def _missing(text, tree):
    basenames = {os.path.basename(p) for p in tree}
    missing = []
    for token in (m.group().rstrip(".,/") for m in _TOKEN.finditer(text)):
        under_top_dir = token.startswith(tuple(d + "/" for d in TOP_DIRS))
        if _PATTERN_CHARS.search(token):
            if under_top_dir and not all(
                fnmatch.filter(tree, re.sub(r"<[^>]*>", "*", p)) for p in _braces(token)
            ):
                missing.append(token)
        elif under_top_dir:
            if token not in tree:
                missing.append(token)
        elif "/" not in token and _BARE_NAME.search(token) and token not in basenames:
            missing.append(token)
    return sorted(set(missing))


@pytest.fixture(scope="module")
def tree():
    return _tree()


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_what_exists(document, tree):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        assert _missing(f.read(), tree) == []


def test_the_rule_catches_a_file_that_went(tree):
    gone = "`python old_driver.py`, tools/gone_tool.py:12, `tests/{conftest,test_x}.py`"
    assert _missing(gone, tree) == [
        "old_driver.py", "tests/{conftest,test_x}.py", "tools/gone_tool.py"
    ]
    there = (
        "`snapshot.py:130`, chipbench/metrics/<name>.py, `RECORD_r*.json`, "
        "docs/*.md, tests/conftest.py::x, torchsnapshot_tpu/{knobs,snapshot}.py"
    )
    assert _missing(there, tree) == []
