"""Tier-1 enforcement + golden tests for the `tpusnap lint` analyzer.

Two halves:

- **Repo gate** — every rule over the whole repository must report zero
  findings (the tier-1 complement of the CLI exit code): a new violation
  anywhere fails CI here, with the finding text in the assertion.
- **Golden fixtures** — each rule must fire on its seeded violations in
  ``tests/analysis_fixtures/`` (lines marked ``# LINT-EXPECT: <rules>``)
  and stay silent everywhere else in the same file, proving both the
  trigger and the no-trigger half of each rule.  Suppression comments and
  the unknown-rule-in-suppression finding are covered by the fixtures
  too.
"""

from __future__ import annotations

import os
import re

import pytest

from torchsnapshot_tpu._analysis import core
from torchsnapshot_tpu._analysis.rules_knobs import KnobDocsRule
from torchsnapshot_tpu._analysis.rules_native import NativeAbiRule

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "analysis_fixtures")

_EXPECT_RE = re.compile(r"#\s*LINT-EXPECT:\s*([A-Za-z0-9_,\- ]+)")


# ------------------------------------------------------------- repo gate


def test_repo_is_lint_clean():
    """The whole repository passes every rule — the tier-1 gate the
    `tpusnap lint` CLI exit code mirrors."""
    findings = core.lint_project(REPO_ROOT)
    assert findings == [], "tpusnap lint found violations:\n" + "\n".join(
        str(f) for f in findings
    )


def test_cli_exit_codes(tmp_path, capsys):
    from torchsnapshot_tpu.__main__ import main

    assert main(["lint", REPO_ROOT]) == 0
    capsys.readouterr()

    # A seeded violation must flip the exit code.
    (tmp_path / "pyproject.toml").write_text("")
    (tmp_path / "bad.py").write_text(
        'import os\nv = os.environ.get("TPUSNAP_CAS")\n'
    )
    assert main(["lint", str(tmp_path), "--rules", "knob-discipline"]) == 1
    out = capsys.readouterr().out
    assert "bad.py:2" in out and "knob-discipline" in out


def test_fixture_dir_is_excluded_from_repo_walk():
    """The deliberate violations must never leak into the repo lint."""
    rels = [rel for _, rel in core.iter_python_files(REPO_ROOT)]
    assert not any("analysis_fixtures" in rel for rel in rels)
    assert "torchsnapshot_tpu/knobs.py" in rels
    assert "chip_smoke.py" in rels


# -------------------------------------------------------- golden fixtures


def _expected_findings(source: str):
    expected = set()
    for lineno, line in enumerate(source.splitlines(), start=1):
        m = _EXPECT_RE.search(line)
        if m:
            for rule in m.group(1).split(","):
                expected.add((rule.strip(), lineno))
    return expected


@pytest.mark.parametrize(
    "fixture",
    [
        "knob_discipline.py",
        "event_taxonomy.py",
        "phase_registry.py",
        "durability_flow.py",
        "async_blocking.py",
        "async_blocking_deep.py",
        "collective_divergence.py",
        "lock_discipline.py",
        "resource_leak.py",
        "exception_taxonomy.py",
        "suppression.py",
    ],
)
def test_fixture_golden(fixture):
    """Each rule fires exactly on its marked lines and nowhere else in
    the fixture — trigger and no-trigger halves in one assertion."""
    path = os.path.join(FIXTURES, fixture)
    with open(path, "r", encoding="utf-8") as f:
        source = f.read()
    expected = _expected_findings(source)
    assert expected, f"{fixture} has no LINT-EXPECT markers"
    findings = core.lint_sources({fixture: source}, core.all_rules())
    actual = {(f.rule, f.line) for f in findings}
    assert actual == expected, (
        f"{fixture}: findings mismatch\n"
        f"  unexpected: {sorted(actual - expected)}\n"
        f"  missing:    {sorted(expected - actual)}\n"
        "  all: " + "\n  ".join(str(f) for f in findings)
    )


def test_suppression_silences_and_typo_is_flagged():
    """Direct (non-golden) statement of the suppression contract: a valid
    disable produces no finding, an unknown rule name is itself one."""
    # Concatenated so the repo-wide suppression scanner (which reads raw
    # lines, string literals included) doesn't see a disable in THIS file
    # — the stale-suppression test would flag it.
    src_ok = (
        "import os\n"
        'v = os.environ.get("TPUSNAP_CAS")  # tpusnap-lint: '
        "disable=knob-discipline\n"
    )
    assert core.lint_sources({"s.py": src_ok}, core.all_rules()) == []

    # Concatenated so the repo-wide suppression scanner (which reads raw
    # lines, string literals included) doesn't see a disable in THIS file.
    src_typo = (
        "import os\n"
        'v = os.environ.get("TPUSNAP_CAS")  # tpusnap-lint: '
        "disable=knob-dicsipline\n"
    )
    findings = core.lint_sources({"s.py": src_typo}, core.all_rules())
    rules = sorted(f.rule for f in findings)
    assert rules == ["knob-discipline", "suppression"], findings


def test_parse_error_is_a_finding():
    findings = core.lint_sources({"broken.py": "def f(:\n"}, core.all_rules())
    assert [f.rule for f in findings] == ["parse-error"]
    assert findings[0].path == "broken.py"


def test_no_stale_suppressions_repo_wide():
    """Every suppression comment in the repo still suppresses a live
    finding: with the flow-sensitive durability rule, the suppressions it
    proves safe (pristine renames) are GONE, and nothing else rotted into
    a decoration.  A failure names the comment to delete."""
    stale = core.unused_suppressions(REPO_ROOT)
    assert stale == [], (
        "stale suppression comments (the named rule no longer fires "
        "there — delete the comment):\n"
        + "\n".join(f"{p}:{line}: disable={rule}" for p, line, rule in stale)
    )


# ------------------------------------------- interprocedural evasion proofs


def _fixture_source(name):
    with open(os.path.join(FIXTURES, name), "r", encoding="utf-8") as f:
        return f.read()


def test_lexical_async_rule_misses_the_deep_fixture():
    """The acceptance case: the PR 9 lexical async-blocking rule reports
    NOTHING on the async→sync-helper→time.sleep fixture, while the deep
    rule reports every marked line — proving the interprocedural engine
    closes a real evasion rather than re-finding lexical hits."""
    from torchsnapshot_tpu._analysis.rules_async import (
        AsyncBlockingDeepRule,
        AsyncBlockingRule,
    )

    src = _fixture_source("async_blocking_deep.py")
    lexical = core.lint_sources(
        {"async_blocking_deep.py": src}, [AsyncBlockingRule()]
    )
    assert lexical == [], lexical
    deep = core.lint_sources(
        {"async_blocking_deep.py": src}, [AsyncBlockingDeepRule()]
    )
    assert {f.line for f in deep} == {
        lineno
        for lineno, line in enumerate(src.splitlines(), start=1)
        if "LINT-EXPECT" in line
    }


def test_flow_durability_catches_rename_in_callee_lexical_cannot():
    """The write is in the caller, the rename in the callee: no single
    function body contains both, so the lexical fsync-before-rename shape
    can never fire — the flow rule follows the written name into the
    publish helper."""
    from torchsnapshot_tpu._analysis.rules_durability import (
        DurabilityFlowRule,
    )

    src = _fixture_source("durability_flow.py")
    findings = core.lint_sources(
        {"durability_flow.py": src}, [DurabilityFlowRule()]
    )
    messages = {f.line: f.message for f in findings}
    helper_line = next(
        lineno
        for lineno, line in enumerate(src.splitlines(), start=1)
        if "_publish(tmp, path)  # LINT-EXPECT" in line
    )
    assert helper_line in messages
    assert "_publish" in messages[helper_line]
    # And the fsync-in-callee + pristine-rename shapes (the two lexical
    # suppression classes) stay silent.
    assert all("ok_" not in m for m in messages.values())


def test_collective_divergence_through_two_call_hops():
    from torchsnapshot_tpu._analysis.rules_collective import (
        CollectiveDivergenceRule,
    )

    src = _fixture_source("collective_divergence.py")
    findings = core.lint_sources(
        {"collective_divergence.py": src}, [CollectiveDivergenceRule()]
    )
    two_hop = [f for f in findings if "_commit_path" in f.message]
    assert two_hop, findings
    assert "LinearBarrier.depart" in two_hop[0].message


def test_lock_order_inversion_across_functions():
    from torchsnapshot_tpu._analysis.rules_locks import LockDisciplineRule

    src = _fixture_source("lock_discipline.py")
    findings = core.lint_sources(
        {"lock_discipline.py": src}, [LockDisciplineRule()]
    )
    inversions = [f for f in findings if "inversion" in f.message]
    assert len(inversions) == 1, findings
    assert "_takes_a" in inversions[0].message


# --------------------------------------------------- call graph + dataflow


def test_callgraph_resolution_and_honesty():
    """Name/attribute resolution across modules, classes, self-methods,
    and nested defs — and unresolved calls recorded honestly with their
    chain, never guessed at."""
    from torchsnapshot_tpu._analysis import callgraph

    sources = {
        "pkg/util.py": (
            "def helper():\n"
            "    return 1\n"
        ),
        "pkg/mod.py": (
            "from . import util\n"
            "from .util import helper as h2\n"
            "class Base:\n"
            "    def shared(self):\n"
            "        return util.helper()\n"
            "class Impl(Base):\n"
            "    def run(self):\n"
            "        self.shared()\n"
            "        h2()\n"
            "        self._unknown.thing()\n"
            "    def nested_owner(self):\n"
            "        def inner():\n"
            "            return h2()\n"
            "        return inner()\n"
        ),
    }
    modules = []
    for rel, src in sources.items():
        import ast as _ast

        modules.append(
            core.ModuleFile(
                path=rel, rel=rel, source=src, tree=_ast.parse(src)
            )
        )
    graph = callgraph.build_graph(modules)
    run_sites = graph.sites_of("pkg/mod.py::Impl.run")
    by_chain = {s.chain: s for s in run_sites}
    # self-method through the base class:
    assert by_chain["self.shared"].targets == ("pkg/mod.py::Base.shared",)
    # from-import alias:
    assert by_chain["h2"].targets == ("pkg/util.py::helper",)
    # unknown-callee honesty: chain kept, no targets invented.
    assert by_chain["self._unknown.thing"].targets == ()
    # module alias inside a method:
    shared_sites = graph.sites_of("pkg/mod.py::Base.shared")
    assert shared_sites[0].targets == ("pkg/util.py::helper",)
    # nested defs are their own nodes, owned calls attributed to them:
    nested = graph.sites_of(
        "pkg/mod.py::Impl.nested_owner.<locals>.inner"
    )
    assert [s.targets for s in nested] == [("pkg/util.py::helper",)]
    owner_sites = graph.sites_of("pkg/mod.py::Impl.nested_owner")
    assert ("pkg/mod.py::Impl.nested_owner.<locals>.inner",) in [
        s.targets for s in owner_sites
    ]


def test_dataflow_fixpoint_converges_on_recursion():
    from torchsnapshot_tpu._analysis import callgraph, dataflow

    import ast as _ast

    src = (
        "def a():\n    b()\n"
        "def b():\n    a()\n    c()\n"
        "def c():\n    pass\n"
    )
    module = core.ModuleFile(
        path="m.py", rel="m.py", source=src, tree=_ast.parse(src)
    )
    graph = callgraph.build_graph([module])
    summary = dataflow.propagate(graph, {"m.py::c": frozenset({"fact"})})
    assert summary["m.py::a"] == frozenset({"fact"})
    assert summary["m.py::b"] == frozenset({"fact"})


# ------------------------------------------------- --changed + AST cache


def _git(tmp_path, *args):
    import subprocess

    return subprocess.run(
        ["git", "-C", str(tmp_path), *args],
        capture_output=True,
        text=True,
        check=True,
    )


def test_lint_changed_only_analyzes_touched_files(tmp_path, capsys):
    """--changed: a violation in the committed base is NOT re-reported;
    one in a touched (untracked) file is — while the call graph still
    spans the whole tree."""
    from torchsnapshot_tpu.__main__ import main

    (tmp_path / "pyproject.toml").write_text("")
    (tmp_path / "committed_bad.py").write_text(
        'import os\nv = os.environ.get("TPUSNAP_CAS")\n'
    )
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(
        tmp_path,
        "-c", "user.name=t",
        "-c", "user.email=t@t",
        "commit", "-q", "-m", "base",
    )

    # Nothing changed: exits clean without analyzing anything.
    assert main(["lint", str(tmp_path), "--changed"]) == 0
    assert "no .py files changed" in capsys.readouterr().out

    (tmp_path / "touched_bad.py").write_text(
        'import os\nw = os.environ.get("TPUSNAP_JOURNAL")\n'
    )
    assert main(["lint", str(tmp_path), "--changed"]) == 1
    out = capsys.readouterr().out
    assert "touched_bad.py:2" in out
    assert "committed_bad.py" not in out

    # Full lint still sees both.
    assert main(["lint", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "committed_bad.py:2" in out and "touched_bad.py:2" in out


def test_changed_rel_paths_none_outside_git(tmp_path):
    assert core.changed_rel_paths(str(tmp_path)) is None


def test_ast_cache_reuses_and_invalidates(tmp_path):
    """The mtime-keyed parse cache: identical stat → same ModuleFile
    object; a rewrite (different mtime/size) → fresh parse."""
    (tmp_path / "pyproject.toml").write_text("")
    target = tmp_path / "cached.py"
    target.write_text("X = 1\n")
    first = core.load_project(str(tmp_path)).module("cached.py")
    second = core.load_project(str(tmp_path)).module("cached.py")
    assert first is second
    import os as _os

    target.write_text("X = 2  # changed\n")
    _os.utime(target, ns=(1, 1))  # force a distinct stat stamp
    third = core.load_project(str(tmp_path)).module("cached.py")
    assert third is not first
    assert "changed" in third.source


# ------------------------------------------------- project-level cross-checks


def _write(root, rel, text):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_knob_docs_bidirectional(tmp_path):
    _write(
        tmp_path,
        "torchsnapshot_tpu/knobs.py",
        '_P = "TPUSNAP_"\n'
        'FOO_ENV_VAR = _P + "FOO"\n'
        'BAR_ENV_VAR = "TPUSNAP_BAR"\n',
    )
    _write(
        tmp_path,
        "docs/knobs.md",
        "| `TPUSNAP_FOO` | on | documented |\n"
        "| `TPUSNAP_GHOST` | ? | documented but unregistered |\n",
    )
    project = core.Project(root=str(tmp_path), modules=[])
    findings = list(KnobDocsRule().project_check(project))
    by_rule = {(f.path, "TPUSNAP_BAR" in f.message, "TPUSNAP_GHOST" in f.message) for f in findings}
    assert len(findings) == 2, findings
    assert ("torchsnapshot_tpu/knobs.py", True, False) in by_rule  # undocumented
    assert ("docs/knobs.md", False, True) in by_rule  # ghost knob


def test_knob_docs_clean_when_in_sync(tmp_path):
    _write(tmp_path, "torchsnapshot_tpu/knobs.py", 'FOO_ENV_VAR = "TPUSNAP_FOO"\n')
    _write(tmp_path, "docs/knobs.md", "`TPUSNAP_FOO` documented here\n")
    project = core.Project(root=str(tmp_path), modules=[])
    assert list(KnobDocsRule().project_check(project)) == []


_CC_TEMPLATE = """\
#include <stdint.h>
extern "C" {
int tpusnap_abi_version() { return %(abi)s; }
int %(sym)s(const char* path) { return 0; }
}  // extern "C"
"""

_PY_TEMPLATE = """\
NATIVE_ABI_VERSION = %(abi)s
class N:
    def bind(self, lib):
        lib.tpusnap_abi_version
        fn = lib.%(sym)s
"""


def test_native_abi_detects_drift(tmp_path):
    """A symbol exported but unprobed (and vice-versa) and an ABI-number
    mismatch are each findings — the acceptance-criterion drift case."""
    _write(
        tmp_path,
        "torchsnapshot_tpu/_native/tpustore.cc",
        _CC_TEMPLATE % {"abi": "2", "sym": "tpusnap_only_in_cc"},
    )
    _write(
        tmp_path,
        "torchsnapshot_tpu/native_io.py",
        _PY_TEMPLATE % {"abi": "1", "sym": "tpusnap_only_in_python"},
    )
    project = core.Project(root=str(tmp_path), modules=[])
    findings = list(NativeAbiRule().project_check(project))
    messages = "\n".join(f.message for f in findings)
    assert len(findings) == 3, findings
    assert "tpusnap_only_in_cc" in messages
    assert "tpusnap_only_in_python" in messages
    assert "NATIVE_ABI_VERSION=1" in messages


def test_native_abi_clean_when_in_sync(tmp_path):
    _write(
        tmp_path,
        "torchsnapshot_tpu/_native/tpustore.cc",
        _CC_TEMPLATE % {"abi": "1", "sym": "tpusnap_shared"},
    )
    _write(
        tmp_path,
        "torchsnapshot_tpu/native_io.py",
        _PY_TEMPLATE % {"abi": "1", "sym": "tpusnap_shared"},
    )
    project = core.Project(root=str(tmp_path), modules=[])
    assert list(NativeAbiRule().project_check(project)) == []


def test_native_abi_repo_contract():
    """On the real tree: every exported symbol is probed, every probed
    symbol exists, ABI constants agree (parsed, not imported)."""
    from torchsnapshot_tpu._analysis.rules_native import (
        exported_symbols,
        probed_symbols,
    )
    from torchsnapshot_tpu.native_io import NATIVE_ABI_VERSION

    with open(
        os.path.join(REPO_ROOT, "torchsnapshot_tpu/_native/tpustore.cc")
    ) as f:
        cc = f.read()
    with open(os.path.join(REPO_ROOT, "torchsnapshot_tpu/native_io.py")) as f:
        py = f.read()
    exported = set(exported_symbols(cc))
    probed = set(probed_symbols(py))
    assert exported, "no exported symbols parsed from tpustore.cc"
    assert exported == probed, (exported - probed, probed - exported)
    # The raw-speed-frontier exports (PR 12) are part of the fenced ABI:
    # dropping any of them from either surface must fail tier-1, not
    # silently degrade the fast path forever.
    assert {
        "tpusnap_zstd_encode",
        "tpusnap_zstd_decode",
        "tpusnap_write_parts_hash_batch",
        "tpusnap_direct_io_configure",
        "tpusnap_direct_io_mode",
        # Round 15: content-defined chunk boundaries + advanced zstd
        # parameters — both fenced ABI surfaces (boundaries name CAS
        # chunks; dropping either side must fail tier-1, not silently
        # degrade forever).
        "tpusnap_cdc_boundaries",
        "tpusnap_zstd_encode2",
    } <= exported
    m = re.search(r"int\s+tpusnap_abi_version\s*\(\s*\)\s*\{\s*return\s+(\d+)", cc)
    assert m and int(m.group(1)) == NATIVE_ABI_VERSION


# ----------------------------------------------------------------- external


def test_external_tools_skip_gracefully(tmp_path):
    """--external must never fail because ruff/mypy aren't installed; on a
    root without pyproject.toml it skips wholesale."""
    from torchsnapshot_tpu._analysis.external import run_external

    results = run_external(str(tmp_path))
    assert all(r.ok for r in results)

    results = run_external(REPO_ROOT)
    for r in results:
        # Installed -> must pass on our tree; missing -> skipped cleanly.
        assert r.ok, f"{r.tool} failed:\n{r.output}"


# ------------------------------------------------- review-round regressions


def test_lock_order_comma_with_form_detected():
    """`with A, B:` acquires in item order exactly like nesting — the
    comma form must participate in inversion detection."""
    src = (
        "import threading\n"
        "_A = threading.Lock()\n"
        "_B = threading.Lock()\n"
        "def f():\n"
        "    with _A, _B:\n"
        "        pass\n"
        "def g():\n"
        "    with _B:\n"
        "        with _A:\n"
        "            pass\n"
    )
    from torchsnapshot_tpu._analysis.rules_locks import LockDisciplineRule

    findings = core.lint_sources({"m.py": src}, [LockDisciplineRule()])
    assert len(findings) == 1 and "inversion" in findings[0].message


def test_divergent_raise_in_else_branch_detected():
    """An `else: raise` before an in-loop collective diverges exactly
    like `if: raise` — orelse bodies must be scanned too."""
    src = (
        "def f(pg, keys, state):\n"
        "    for key in keys:\n"
        "        if key in state:\n"
        "            pass\n"
        "        else:\n"
        "            raise RuntimeError(key)\n"
        "        pg.barrier()\n"
    )
    from torchsnapshot_tpu._analysis.rules_collective import (
        CollectiveDivergenceRule,
    )

    findings = core.lint_sources({"m.py": src}, [CollectiveDivergenceRule()])
    assert [f.line for f in findings] == [6], findings


def test_changed_rel_paths_from_git_subdirectory(tmp_path):
    """git diff prints toplevel-relative paths; when the lint root is a
    SUBDIRECTORY of the checkout they must still resolve to root-relative
    module paths (a mismatch silently lints nothing)."""
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "pyproject.toml").write_text("")
    (proj / "base.py").write_text("X = 1\n")
    (tmp_path / "outside.py").write_text("Y = 2\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(
        tmp_path,
        "-c", "user.name=t",
        "-c", "user.email=t@t",
        "commit", "-q", "-m", "base",
    )
    (proj / "base.py").write_text(
        'import os\nv = os.environ.get("TPUSNAP_CAS")\n'
    )
    (tmp_path / "outside.py").write_text("Y = 3\n")
    changed = core.changed_rel_paths(str(proj))
    assert changed == {"base.py"}  # root-relative; outside.py excluded
    findings = core.lint_project(str(proj), only=changed)
    assert any(
        f.path == "base.py" and f.rule == "knob-discipline"
        for f in findings
    )


def test_changed_mode_omits_project_findings_in_untouched_files(tmp_path):
    """--changed reports only on touched files — a registry-level
    finding anchored in an untouched file is the full gate's job."""
    _write(
        tmp_path,
        "torchsnapshot_tpu/knobs.py",
        'FOO_ENV_VAR = "TPUSNAP_FOO"\n',  # undocumented -> knob-docs
    )
    _write(tmp_path, "docs/knobs.md", "nothing here\n")
    _write(tmp_path, "pyproject.toml", "")
    full = core.lint_project(str(tmp_path))
    assert any(f.rule == "knob-docs" for f in full)
    restricted = core.lint_project(str(tmp_path), only={"other.py"})
    assert restricted == []
