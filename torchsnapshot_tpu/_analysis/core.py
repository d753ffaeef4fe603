"""Analyzer framework: file walker, rule protocol, findings, suppressions.

Every rule is a class with a ``name``, a ``description``, and either a
per-module ``check(module)`` (AST rules) or a cross-file
``project_check(project)`` (registry cross-checks like knob-docs and the
native ABI contract).  The driver (``lint_project``) walks the repo once,
parses each Python file once, fans the shared :class:`ModuleFile` out to
every applicable rule, then filters findings through the suppression
comments.

Suppression: a trailing ``# tpusnap-lint: disable=<rule>[,<rule>...]`` on
the offending line, or the same comment alone on the line directly above
it.  Unknown rule names inside a suppression are themselves findings
(rule ``suppression``) — a typo'd disable must not silently suppress
nothing while looking like it did.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# Directories the walker descends into, relative to the project root.
SCAN_DIRS = ("torchsnapshot_tpu", "tests", "examples")
# Directory basenames never descended into.  ``analysis_fixtures`` holds
# the golden rule-trigger snippets — deliberate violations that must fail
# only their own test, never the repo-wide lint.
EXCLUDED_DIR_NAMES = frozenset(
    {"__pycache__", ".git", "analysis_fixtures", ".pytest_cache"}
)

_SUPPRESS_RE = re.compile(r"#\s*tpusnap-lint:\s*disable=([A-Za-z0-9_,\- ]+)")


@dataclass(frozen=True)
class Finding:
    """One rule violation, anchored to a file:line."""

    rule: str
    path: str  # project-root-relative, '/'-separated
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass
class ModuleFile:
    """One parsed Python source file, shared by every rule."""

    path: str  # absolute
    rel: str  # root-relative, '/'-separated
    source: str
    tree: Optional[ast.AST]
    parse_error: Optional[str] = None
    _suppressions: Optional[Dict[int, Set[str]]] = field(
        default=None, repr=False
    )

    @property
    def lines(self) -> List[str]:
        return self.source.splitlines()

    def suppressions(self) -> Dict[int, Set[str]]:
        """1-based line -> set of rule names disabled on that line."""
        if self._suppressions is None:
            out: Dict[int, Set[str]] = {}
            for i, text in enumerate(self.lines, start=1):
                m = _SUPPRESS_RE.search(text)
                if m:
                    out[i] = {
                        name.strip()
                        for name in m.group(1).split(",")
                        if name.strip()
                    }
            self._suppressions = out
        return self._suppressions

    def suppressed(self, rule: str, line: int) -> bool:
        sup = self.suppressions()
        if rule in sup.get(line, ()):
            return True
        # A standalone suppression comment on the line directly above
        # covers the next line (for lines too long to carry a trailing
        # comment).
        above = sup.get(line - 1)
        if above and rule in above:
            text = self.lines[line - 2].strip() if line >= 2 else ""
            if text.startswith("#"):
                return True
        return False


class Rule:
    """Base rule.  Subclasses set ``name``/``description`` and override
    ``check`` (per-module), ``project_check`` (cross-file registry
    checks), and/or ``graph_check`` (interprocedural rules fed the
    shared call graph built over the whole scanned file set)."""

    name: str = ""
    description: str = ""

    def applies_to(self, rel: str) -> bool:
        """Whether findings for this root-relative path are reported
        during a project lint (fixture tests bypass this via
        ``lint_sources``)."""
        return True

    def check(self, module: ModuleFile) -> Iterable[Finding]:
        return ()

    def project_check(self, project: "Project") -> Iterable[Finding]:
        return ()

    def graph_check(
        self, project: "Project", graph: "object"
    ) -> Iterable[Finding]:
        return ()


def in_package(rel: str) -> bool:
    return rel.startswith("torchsnapshot_tpu/")


@dataclass
class Project:
    """The lint target: a root directory plus its parsed Python modules."""

    root: str
    modules: List[ModuleFile]

    def module(self, rel: str) -> Optional[ModuleFile]:
        for m in self.modules:
            if m.rel == rel:
                return m
        return None

    def read_text(self, rel: str) -> Optional[str]:
        path = os.path.join(self.root, *rel.split("/"))
        try:
            with open(path, "r", encoding="utf-8") as f:
                return f.read()
        except OSError:
            return None


def find_project_root(start: Optional[str] = None) -> str:
    """Nearest ancestor of ``start`` (default: this package's parent)
    holding a ``pyproject.toml`` — the repo checkout the lint runs over."""
    here = start or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    probe = os.path.abspath(here)
    while True:
        if os.path.exists(os.path.join(probe, "pyproject.toml")):
            return probe
        parent = os.path.dirname(probe)
        if parent == probe:
            # Fall back to the package parent; the walker will still find
            # the package itself.
            return os.path.abspath(here)
        probe = parent


# mtime-keyed parsed-AST cache: the tier-1 suite lints the repo many
# times per process (repo gate + CLI tests + the stale-suppression
# scan), and the interprocedural rules parse every file to build the
# call graph even under ``--changed``.  Keyed on (mtime_ns, size) so an
# edited file reparses; bounded only by the repo's file count.
_AST_CACHE: Dict[str, Tuple[Tuple[int, int], ModuleFile]] = {}


def _load_module(path: str, rel: str) -> ModuleFile:
    try:
        st = os.stat(path)
        stamp: Optional[Tuple[int, int]] = (st.st_mtime_ns, st.st_size)
    except OSError:
        stamp = None
    if stamp is not None:
        cached = _AST_CACHE.get(path)
        if cached is not None and cached[0] == stamp and cached[1].rel == rel:
            return cached[1]
    with open(path, "r", encoding="utf-8") as f:
        source = f.read()
    try:
        tree: Optional[ast.AST] = ast.parse(source, filename=path)
        err = None
    except SyntaxError as e:
        tree, err = None, f"{e.msg} (line {e.lineno})"
    module = ModuleFile(
        path=path, rel=rel, source=source, tree=tree, parse_error=err
    )
    if stamp is not None:
        _AST_CACHE[path] = (stamp, module)
    return module


def iter_python_files(root: str) -> Iterable[Tuple[str, str]]:
    """Yield (abs_path, rel_path) for every lintable .py under the scan
    roots, plus top-level .py files (chip_smoke.py and friends)."""
    for entry in sorted(os.listdir(root)):
        full = os.path.join(root, entry)
        if entry.endswith(".py") and os.path.isfile(full):
            yield full, entry
    for scan_dir in SCAN_DIRS:
        base = os.path.join(root, scan_dir)
        if not os.path.isdir(base):
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(
                d for d in dirnames if d not in EXCLUDED_DIR_NAMES
            )
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                rel = os.path.relpath(full, root).replace(os.sep, "/")
                yield full, rel


def load_project(root: Optional[str] = None) -> Project:
    root = os.path.abspath(root or find_project_root())
    modules = [_load_module(path, rel) for path, rel in iter_python_files(root)]
    return Project(root=root, modules=modules)


def all_rules() -> List[Rule]:
    """Every registered rule, instantiated fresh (rules hold no state
    across runs beyond construction-time registries)."""
    from .rules_async import AsyncBlockingDeepRule, AsyncBlockingRule
    from .rules_collective import CollectiveDivergenceRule
    from .rules_durability import DurabilityFlowRule
    from .rules_events import EventTaxonomyRule, PhaseRegistryRule
    from .rules_exceptions import ExceptionTaxonomyRule
    from .rules_knobs import KnobDisciplineRule, KnobDocsRule
    from .rules_leaks import ResourceLeakRule
    from .rules_locks import LockDisciplineRule
    from .rules_native import NativeAbiRule

    return [
        KnobDisciplineRule(),
        KnobDocsRule(),
        EventTaxonomyRule(),
        PhaseRegistryRule(),
        DurabilityFlowRule(),
        AsyncBlockingRule(),
        AsyncBlockingDeepRule(),
        CollectiveDivergenceRule(),
        LockDisciplineRule(),
        ResourceLeakRule(),
        ExceptionTaxonomyRule(),
        NativeAbiRule(),
    ]


def rule_names() -> List[str]:
    return [r.name for r in all_rules()]


def _suppression_findings(
    module: ModuleFile, known: Set[str]
) -> Iterable[Finding]:
    for line, names in module.suppressions().items():
        for name in sorted(names - known):
            yield Finding(
                rule="suppression",
                path=module.rel,
                line=line,
                message=(
                    f"unknown rule {name!r} in suppression comment "
                    f"(known rules: {', '.join(sorted(known))})"
                ),
            )


# Shared call graphs keyed by the module set's identity (file path +
# mtime stamp per module): the graph is package-wide even when only a
# subset of files is re-linted (--changed), so reuse across lint calls
# is what keeps the tier-1 gate under its wall.
_GRAPH_CACHE: Dict[frozenset, object] = {}
_GRAPH_CACHE_MAX = 4


def _graph_for(project: Project) -> object:
    from . import callgraph

    key_parts = []
    cacheable = True
    for m in project.modules:
        cached = _AST_CACHE.get(m.path)
        if cached is not None and cached[1] is m:
            key_parts.append((m.path, cached[0]))
        else:
            cacheable = False
            break
    if cacheable:
        key = frozenset(key_parts)
        graph = _GRAPH_CACHE.get(key)
        if graph is None:
            graph = callgraph.build_graph(project.modules)
            if len(_GRAPH_CACHE) >= _GRAPH_CACHE_MAX:
                _GRAPH_CACHE.clear()
            _GRAPH_CACHE[key] = graph
        return graph
    return callgraph.build_graph(project.modules)


def _run_rules(
    project: Project,
    rules: Sequence[Rule],
    modules: Sequence[ModuleFile],
    scoped: bool,
    apply_suppressions: bool = True,
    run_project_rules: bool = True,
    restrict_project: Optional[Set[str]] = None,
) -> List[Finding]:
    known = {r.name for r in rules} | {r.name for r in all_rules()}
    report_rels = {m.rel for m in modules}
    module_by_rel = {m.rel: m for m in project.modules}
    for m in modules:
        module_by_rel.setdefault(m.rel, m)

    def keep(rule: Rule, f: Finding) -> bool:
        if f.path not in report_rels:
            return False
        if scoped and not rule.applies_to(f.path):
            return False
        if not apply_suppressions:
            return True
        module = module_by_rel.get(f.path)
        return module is None or not module.suppressed(f.rule, f.line)

    findings: List[Finding] = []
    for module in modules:
        if module.parse_error is not None:
            findings.append(
                Finding(
                    rule="parse-error",
                    path=module.rel,
                    line=1,
                    message=f"syntax error: {module.parse_error}",
                )
            )
            continue
        findings.extend(_suppression_findings(module, known))
        for rule in rules:
            if type(rule).check is Rule.check:
                continue
            if scoped and not rule.applies_to(module.rel):
                continue
            for f in rule.check(module):
                if not apply_suppressions or not module.suppressed(
                    f.rule, f.line
                ):
                    findings.append(f)
    graph_rules = [
        r for r in rules if type(r).graph_check is not Rule.graph_check
    ]
    if graph_rules:
        graph = _graph_for(project)
        for rule in graph_rules:
            for f in rule.graph_check(project, graph):
                if keep(rule, f):
                    findings.append(f)
    if run_project_rules:
        for rule in rules:
            for f in rule.project_check(project):
                if (
                    restrict_project is not None
                    and f.path not in restrict_project
                ):
                    # --changed contract: only report on touched files
                    # (registry findings in untouched files are the full
                    # gate's job).
                    continue
                module = project.module(f.path)
                if not apply_suppressions or (
                    module is None
                    or not module.suppressed(f.rule, f.line)
                ):
                    findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def lint_project(
    root: Optional[str] = None,
    rules: Optional[Sequence[Rule]] = None,
    only: Optional[Set[str]] = None,
) -> List[Finding]:
    """Lint the whole project: every rule (or ``rules``) over every
    walked module, project-level cross-checks included.  ``only``
    restricts per-file analysis and reported findings to the given
    root-relative paths (``tpusnap lint --changed``) — the call graph is
    still built package-wide, so interprocedural findings in a changed
    file see unchanged callees."""
    project = load_project(root)
    modules = project.modules
    if only is not None:
        modules = [m for m in modules if m.rel in only]
    return _run_rules(
        project,
        list(rules or all_rules()),
        modules,
        scoped=True,
        restrict_project=only,
    )


def changed_rel_paths(root: str, base: str = "HEAD") -> Optional[Set[str]]:
    """Root-relative ``.py`` paths touched vs ``base`` (committed diff +
    worktree + untracked), or None when git is unavailable/errors —
    callers fall back to a full lint."""
    import subprocess

    def run(*args: str) -> Optional[List[str]]:
        try:
            proc = subprocess.run(
                ["git", "-C", root, *args],
                capture_output=True,
                text=True,
                timeout=30,
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        if proc.returncode != 0:
            return None
        return [line.strip() for line in proc.stdout.splitlines()]

    toplevel = run("rev-parse", "--show-toplevel")
    committed = run("diff", "--name-only", base, "--")
    worktree = run("diff", "--name-only", "--")
    staged = run("diff", "--name-only", "--cached", "--")
    untracked = run("ls-files", "--others", "--exclude-standard")
    if committed is None or worktree is None or not toplevel:
        return None
    # git diff prints TOPLEVEL-relative paths while ls-files prints
    # cwd-relative ones; when ``root`` is a subdirectory of the git
    # checkout the two disagree and naive mixing silently matches no
    # module (a changed file would pass the gate unanalyzed).
    # Re-anchor everything on the toplevel, then relativize to root.
    abs_root = os.path.abspath(root)
    out: Set[str] = set()

    def add(path: str, base_dir: str) -> None:
        if not path.endswith(".py"):
            return
        abs_path = os.path.normpath(os.path.join(base_dir, path))
        rel = os.path.relpath(abs_path, abs_root)
        if not rel.startswith(".."):
            out.add(rel.replace(os.sep, "/"))

    for batch in (committed, worktree, staged or []):
        for path in batch:
            add(path, toplevel[0])
    for path in untracked or []:
        add(path, abs_root)
    return out


def unused_suppressions(
    root: Optional[str] = None,
) -> List[Tuple[str, int, str]]:
    """Suppression comments that no longer suppress anything: ``(path,
    line, rule)`` for every ``disable=<rule>`` with no matching raw
    finding on its line (or the next line, for standalone comments).
    A stale suppression is debt — it reads as "this is a known
    exception" while guarding nothing."""
    project = load_project(root)
    rules = all_rules()
    raw = _run_rules(
        project,
        rules,
        project.modules,
        scoped=True,
        apply_suppressions=False,
    )
    known = {r.name for r in rules}
    hits: Dict[Tuple[str, str], Set[int]] = {}
    for f in raw:
        hits.setdefault((f.path, f.rule), set()).add(f.line)
    stale: List[Tuple[str, int, str]] = []
    for module in project.modules:
        for line, names in sorted(module.suppressions().items()):
            standalone = (
                line <= len(module.lines)
                and module.lines[line - 1].strip().startswith("#")
            )
            for name in sorted(names):
                if name not in known:
                    continue  # typo'd names are already findings
                lines = hits.get((module.rel, name), set())
                if line in lines or (standalone and line + 1 in lines):
                    continue
                stale.append((module.rel, line, name))
    return stale


def lint_sources(
    sources: Dict[str, str],
    rules: Sequence[Rule],
    root: Optional[str] = None,
) -> List[Finding]:
    """Lint in-memory sources (fixture tests): ``sources`` maps a
    root-relative pseudo-path to Python source.  Scope filters are
    bypassed — the named rules run on every given file; project rules run
    against ``root`` when given (else skipped)."""
    modules = []
    for rel, source in sources.items():
        try:
            tree: Optional[ast.AST] = ast.parse(source, filename=rel)
            err = None
        except SyntaxError as e:
            tree, err = None, f"{e.msg} (line {e.lineno})"
        modules.append(
            ModuleFile(
                path=rel, rel=rel, source=source, tree=tree, parse_error=err
            )
        )
    project = Project(
        root=os.path.abspath(root) if root is not None else "", modules=modules
    )
    # Project-level cross-checks only run against an EXPLICIT root:
    # defaulting to os.curdir would make fixture tests silently
    # cwd-dependent (knob-docs/native-abi would lint whatever tree
    # pytest happened to be launched from).  Per-file AND graph rules
    # always run — the interprocedural rules build their call graph
    # over exactly the given sources, which is how the golden fixtures
    # prove cross-function evasions without a repo checkout.
    return _run_rules(
        project,
        list(rules),
        modules,
        scoped=False,
        run_project_rules=root is not None,
    )


# --------------------------------------------------------------- AST utils


def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for nested Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def module_string_constants(tree: ast.AST) -> Dict[str, Tuple[str, int]]:
    """Module-level ``NAME = <str expr>`` bindings resolvable statically:
    literals and ``+`` concatenations of literals/previously-resolved
    names.  Returns {name: (value, lineno)} — how the analyzer evaluates
    ``_ENV_PREFIX + "FOO"`` style knob registrations."""
    out: Dict[str, Tuple[str, int]] = {}

    def resolve(expr: ast.AST) -> Optional[str]:
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return expr.value
        if isinstance(expr, ast.Name) and expr.id in out:
            return out[expr.id][0]
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
            left = resolve(expr.left)
            right = resolve(expr.right)
            if left is not None and right is not None:
                return left + right
        return None

    for node in ast.iter_child_nodes(tree):
        targets: List[ast.expr] = []
        value: Optional[ast.expr] = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        if value is None:
            continue
        resolved = resolve(value)
        if resolved is None:
            continue
        for target in targets:
            if isinstance(target, ast.Name):
                out[target.id] = (resolved, node.lineno)
    return out
