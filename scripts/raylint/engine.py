"""raylint core: parsed-file cache, rule registry, suppression handling.

One ``ast.parse`` per file feeds every rule (the whole-repo run must fit
the tier-1 time budget). Findings are repo-root-relative so the baseline
stays stable across checkouts.

Suppression syntax:

- ``# raylint: disable=<rule>[,<rule>...]`` on the offending line
  silences those rules for that line (``all`` silences every rule).
- ``# raylint: disable-file=<rule>[,<rule>...]`` anywhere in a file
  silences those rules for the whole file.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set

__all__ = [
    "Finding", "Project", "Rule", "RunResult", "SourceFile",
    "REGISTRY", "register", "run",
]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation: rule name, repo-relative path, 1-based line."""

    rule: str
    path: str
    line: int
    message: str

    @property
    def location(self) -> str:
        return f"{self.path}:{self.line}"


_SUPPRESS_LINE = re.compile(r"#\s*raylint:\s*disable=([\w\-, ]+)")
_SUPPRESS_FILE = re.compile(r"#\s*raylint:\s*disable-file=([\w\-, ]+)")


class SourceFile:
    """One file under analysis: text, split lines, lazily parsed AST and
    suppression table, all computed once and shared across rules."""

    def __init__(self, path: Path, root: Path):
        self.path = path
        self.root = root
        try:
            self.rel = path.relative_to(root).as_posix()
        except ValueError:
            self.rel = path.as_posix()
        self.text = path.read_text()
        self.lines = self.text.splitlines()
        self._tree: Optional[ast.AST] = None
        self._suppressions: Optional[Dict[int, Set[str]]] = None

    @property
    def tree(self) -> ast.AST:
        if self._tree is None:
            self._tree = ast.parse(self.text)
        return self._tree

    @property
    def suppressions(self) -> Dict[int, Set[str]]:
        """line -> suppressed rule names; key 0 covers the whole file."""
        if self._suppressions is None:
            table: Dict[int, Set[str]] = {}
            for lineno, line in enumerate(self.lines, 1):
                m = _SUPPRESS_FILE.search(line)
                if m:
                    table.setdefault(0, set()).update(
                        r.strip() for r in m.group(1).split(",") if r.strip()
                    )
                    continue
                m = _SUPPRESS_LINE.search(line)
                if m:
                    table.setdefault(lineno, set()).update(
                        r.strip() for r in m.group(1).split(",") if r.strip()
                    )
            self._suppressions = table
        return self._suppressions

    def suppressed(self, rule: str, line: int) -> bool:
        for scope in (0, line):
            rules = self.suppressions.get(scope)
            if rules and (rule in rules or "all" in rules):
                return True
        return False


class Project:
    """The tree under analysis: repo root and the package."""

    def __init__(self, root, package: str = "ray_tpu"):
        self.root = Path(root).resolve()
        self.package_root = self.root / package
        paths: List[Path] = []
        if self.package_root.exists():
            paths.extend(sorted(self.package_root.rglob("*.py")))
        self.files: List[SourceFile] = [SourceFile(p, self.root) for p in paths]
        self._by_rel = {sf.rel: sf for sf in self.files}

    def file(self, rel: str) -> Optional[SourceFile]:
        return self._by_rel.get(rel)

    def files_under(self, *rel_prefixes: str) -> List[SourceFile]:
        return [
            sf for sf in self.files
            if any(sf.rel.startswith(p) for p in rel_prefixes)
        ]


class Rule:
    """A registered analysis pass. Subclasses set `name`/`doc` and yield
    Findings from check(); suppression and baselining are applied by the
    engine afterwards."""

    name: str = ""
    doc: str = ""

    def check(self, project: Project) -> Iterable[Finding]:
        raise NotImplementedError


REGISTRY: Dict[str, Rule] = {}


def register(cls):
    """Class decorator: instantiate and add to the global registry."""
    rule = cls()
    assert rule.name, f"{cls.__name__} has no rule name"
    REGISTRY[rule.name] = rule
    return cls


@dataclasses.dataclass
class RunResult:
    findings: List[Finding]           # actionable (neither suppressed nor baselined)
    baselined: List[Finding]
    suppressed: int
    stale_baseline: List[dict]        # baseline entries that no longer match
    counts: Dict[str, int]            # actionable findings per ran rule (0s included)
    ran_rules: List[str]

    @property
    def ok(self) -> bool:
        return not self.findings


def run(project_or_root, rules: Optional[Sequence[str]] = None,
        baseline=None) -> RunResult:
    """Run `rules` (default: all registered) over the project; apply
    suppression comments, then the baseline. `baseline` is a
    baseline.Baseline or None."""
    project = (
        project_or_root if isinstance(project_or_root, Project)
        else Project(project_or_root)
    )
    names = list(rules) if rules else sorted(REGISTRY)
    unknown = [n for n in names if n not in REGISTRY]
    if unknown:
        raise ValueError(
            f"unknown rule(s): {', '.join(unknown)} "
            f"(known: {', '.join(sorted(REGISTRY))})"
        )
    raw: List[Finding] = []
    for name in names:
        raw.extend(REGISTRY[name].check(project))
    kept: List[Finding] = []
    suppressed = 0
    for f in raw:
        sf = project.file(f.path)
        if sf is not None and sf.suppressed(f.rule, f.line):
            suppressed += 1
        else:
            kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    if baseline is not None:
        actionable, baselined, stale = baseline.apply(kept, project)
    else:
        actionable, baselined, stale = kept, [], []
    counts = {name: 0 for name in names}
    for f in actionable:
        counts[f.rule] += 1
    return RunResult(
        findings=actionable,
        baselined=baselined,
        suppressed=suppressed,
        stale_baseline=stale,
        counts=counts,
        ran_rules=names,
    )
