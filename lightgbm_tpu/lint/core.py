"""graftlint framework: findings, rule registry, suppression, baseline.

Pure stdlib + ``ast`` — importing this module must never import jax (the
full-repo lint runs in tier-1 on CPU and stays well under the ~5 s budget;
parsing is the only cost).

Suppression syntax (same line as the finding)::

    t0 = time.time()   # graftlint: disable=naked-timer
    cache = {}         # graftlint: disable=module-mutable-state -- why...
    x = foo()          # graftlint: disable   (suppresses every rule)

Baseline: ``lint_baseline.json`` at the repo root freezes pre-existing
findings. Entries key on ``(path, rule, stripped source line)`` with a
count, NOT on line numbers, so unrelated edits that shift lines do not
unfreeze old findings. ``scripts/lint.py --update-baseline`` rewrites it.
"""
from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

BASELINE_NAME = "lint_baseline.json"

#: repo-relative roots linted by default (ISSUE 4 scope: the package, the
#: perf-harness scripts, the bench driver and the chip smoke; tests are
#: free to use raw timers and host syncs).
DEFAULT_PATHS = ("lightgbm_tpu", "scripts", "bench.py", "chip_smoke.py")

_DISABLE_RE = re.compile(
    r"#\s*graftlint:\s*disable(?:=([A-Za-z0-9_,\- ]+))?")


@dataclass(frozen=True)
class Finding:
    """One lint hit. ``text`` (the stripped source line) is the baseline
    key component, so findings survive line renumbering."""

    path: str          # repo-relative, posix separators
    line: int          # 1-based
    col: int           # 0-based (ast convention)
    rule: str          # rule id, e.g. "naked-timer"
    message: str
    text: str = ""

    def render(self) -> str:
        return "%s:%d:%d: %s %s" % (self.path, self.line, self.col,
                                    self.rule, self.message)

    def baseline_key(self) -> Tuple[str, str, str]:
        return (self.path, self.rule, self.text)


class SourceFile:
    """One parsed file handed to rules. Parse errors surface as a
    ``syntax-error`` finding instead of crashing the whole lint."""

    def __init__(self, abspath: str, rel: str) -> None:
        self.abspath = abspath
        self.rel = rel.replace(os.sep, "/")
        with open(abspath, "r", encoding="utf-8", errors="replace") as f:
            self.source = f.read()
        self.lines = self.source.splitlines()
        self.tree: Optional[ast.Module] = None
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(self.source, filename=self.rel)
        except SyntaxError as e:  # pragma: no cover - repo parses today
            self.parse_error = e

    def walk_nodes(self) -> list:
        """Every AST node of this file, cached: five per-file rules scan
        the full tree, and one materialized list beats five generator
        walks inside the <5s full-lint budget."""
        nodes = self.__dict__.get("_walk_nodes")
        if nodes is None:
            nodes = self._walk_nodes = \
                list(ast.walk(self.tree)) if self.tree is not None else []
        return nodes

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, node_or_line, rule: str, message: str,
                col: Optional[int] = None) -> Finding:
        if isinstance(node_or_line, int):
            line, c = node_or_line, col or 0
        else:
            line = getattr(node_or_line, "lineno", 1)
            c = getattr(node_or_line, "col_offset", 0) if col is None else col
        return Finding(self.rel, line, c, rule, message, self.line_text(line))

    def disabled_rules(self, lineno: int) -> Optional[set]:
        """Rules suppressed on ``lineno``; empty set means suppress ALL."""
        m = _DISABLE_RE.search(self.lines[lineno - 1]) \
            if 1 <= lineno <= len(self.lines) else None
        if m is None:
            return None
        if m.group(1) is None:
            return set()
        return {r.strip() for r in m.group(1).replace(" ", ",").split(",")
                if r.strip()}


@dataclass
class Project:
    """All files of one lint run, for rules that need cross-file context
    (the host-sync rule builds a call graph over the hot modules)."""

    root: str
    files: List[SourceFile] = field(default_factory=list)

    def by_rel(self, rel: str) -> Optional[SourceFile]:
        for f in self.files:
            if f.rel == rel:
                return f
        return None


class Rule:
    """Base rule. Subclasses set ``id``/``description`` and implement
    either :meth:`check_file` (per-file) or :meth:`check_project`
    (cross-file). Registration is by :func:`register` decorator."""

    id: str = ""
    description: str = ""

    def check_file(self, f: SourceFile) -> Iterator[Finding]:
        return iter(())

    def check_project(self, project: Project) -> Iterator[Finding]:
        return iter(())


_REGISTRY: Dict[str, Rule] = {}  # graftlint: disable=module-mutable-state -- the rule registry is the linter's own plugin seam


def register(cls):
    """Class decorator adding a rule (by instance) to the registry."""
    inst = cls()
    if not inst.id:
        raise ValueError("rule %s has no id" % cls.__name__)
    _REGISTRY[inst.id] = inst
    return cls


def all_rules() -> Dict[str, Rule]:
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Running
# ---------------------------------------------------------------------------

def _iter_py_files(root: str, paths: Sequence[str]) -> Iterator[str]:
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap):
            if ap.endswith(".py"):
                yield ap
        elif os.path.isdir(ap):
            for dirpath, dirnames, filenames in os.walk(ap):
                dirnames[:] = sorted(d for d in dirnames
                                     if not d.startswith((".", "__pycache__")))
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


@dataclass
class LintResult:
    findings: List[Finding]          # after inline suppression
    suppressed: List[Finding]        # killed by # graftlint: disable
    project: Project

    def render(self) -> str:
        return "\n".join(f.render() for f in self.findings)


def run(root: str, paths: Sequence[str] = DEFAULT_PATHS,
        rules: Optional[Iterable[str]] = None) -> LintResult:
    """Lint ``paths`` (relative to ``root``) with the registered rules.

    Returns every finding that survives inline suppression; baseline
    filtering is a separate step (:func:`split_new_findings`) so callers
    can render both views.
    """
    root = os.path.abspath(root)
    project = Project(root=root)
    for ap in _iter_py_files(root, paths):
        rel = os.path.relpath(ap, root)
        project.files.append(SourceFile(ap, rel))

    active = all_rules()
    if rules is not None:
        wanted = set(rules)
        unknown = wanted - set(active)
        if unknown:
            raise ValueError("unknown rule(s): %s" % ", ".join(sorted(unknown)))
        active = {k: v for k, v in active.items() if k in wanted}

    raw: List[Finding] = []
    for f in project.files:
        if f.parse_error is not None:  # pragma: no cover - repo parses today
            raw.append(f.finding(f.parse_error.lineno or 1, "syntax-error",
                                 str(f.parse_error)))
            continue
        for rule in active.values():
            raw.extend(rule.check_file(f))
    for rule in active.values():
        raw.extend(rule.check_project(project))

    kept, suppressed = [], []
    for fi in raw:
        sf = project.by_rel(fi.path)
        dis = sf.disabled_rules(fi.line) if sf is not None else None
        if dis is not None and (not dis or fi.rule in dis):
            suppressed.append(fi)
        else:
            kept.append(fi)
    kept.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return LintResult(findings=kept, suppressed=suppressed, project=project)


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

def baseline_from_findings(findings: Sequence[Finding]) -> dict:
    counts: Dict[Tuple[str, str, str], int] = {}
    for f in findings:
        counts[f.baseline_key()] = counts.get(f.baseline_key(), 0) + 1
    entries = [{"path": p, "rule": r, "text": t, "count": c}
               for (p, r, t), c in sorted(counts.items())]
    return {"version": 1, "findings": entries}


def save_baseline(path: str, baseline: dict) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(baseline, f, indent=2, sort_keys=True)
        f.write("\n")


def load_baseline(path: str) -> dict:
    if not os.path.exists(path):
        return {"version": 1, "findings": []}
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def stale_baseline_entries(root: str, baseline: dict) -> List[dict]:
    """Baseline entries whose ``(path, text)`` no longer matches any
    source line — the frozen finding was fixed (or its file deleted)
    without the baseline shrinking. Text-based, like the baseline keys
    themselves, so the check needs no lint run: ``check.sh`` fails on
    drift in every mode, including ``--fast`` where only changed files
    are linted."""
    out: List[dict] = []
    cache: Dict[str, set] = {}
    for e in baseline.get("findings", []):
        path = e.get("path", "")
        lines = cache.get(path)
        if lines is None:
            try:
                with open(os.path.join(root, path), "r", encoding="utf-8",
                          errors="replace") as fh:
                    lines = {ln.strip() for ln in fh}
            except OSError:
                lines = set()
            cache[path] = lines
        if e.get("text", "") not in lines:
            out.append(e)
    return out


def split_new_findings(findings: Sequence[Finding], baseline: dict
                       ) -> Tuple[List[Finding], List[Finding]]:
    """Partition into (new, baselined). A finding is baselined while its
    ``(path, rule, text)`` entry has remaining count budget."""
    budget: Dict[Tuple[str, str, str], int] = {}
    for e in baseline.get("findings", []):
        key = (e["path"], e["rule"], e["text"])
        budget[key] = budget.get(key, 0) + int(e.get("count", 1))
    new, old = [], []
    for f in findings:
        k = f.baseline_key()
        if budget.get(k, 0) > 0:
            budget[k] -= 1
            old.append(f)
        else:
            new.append(f)
    return new, old
