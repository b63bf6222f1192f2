"""Pass orchestration: run passes, apply per-line suppressions and the
committed baseline, enforce that both are *exercised*.

Suppression policy (the framework's own rules, reported under framework
pass ids):

- ``unused-suppression`` — a ``# graftlint: disable=<pass>`` comment on
  a line the named pass no longer flags.  Suppressions are load-bearing
  documentation; a stale one claims a hazard that is not there.  Only
  enforced when the full default pass set runs (a ``--passes`` subset
  cannot tell "unused" from "not checked this run").
- ``stale-baseline`` — a baseline entry no finding matched.  Same
  argument, for the grandfather file.

Baseline format (``scripts/graftlint/baseline.txt``)::

    <pass-id> <path>::<symbol>   # one-line justification

Symbols (the enclosing function) key the match instead of line numbers,
so routine edits above a grandfathered site don't churn the file.
"""

from __future__ import annotations

import os

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from .core import Finding, Project
from .passes import ALL_PASSES

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.txt")


def all_passes() -> list:
    return [cls() for cls in ALL_PASSES]


@dataclass
class BaselineEntry:
    fingerprint: str
    justification: str
    line: int
    hits: int = 0


def load_baseline(path: str) -> List[BaselineEntry]:
    if not os.path.isfile(path):
        return []
    entries = []
    with open(path) as f:
        for i, raw in enumerate(f, start=1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            body, _, why = line.partition("#")
            parts = body.split()
            if len(parts) != 2 or "::" not in parts[1]:
                entries.append(BaselineEntry(
                    fingerprint=f"<malformed:{line}>",
                    justification="", line=i))
                continue
            entries.append(BaselineEntry(
                fingerprint=f"{parts[0]} {parts[1]}",
                justification=why.strip(), line=i))
    return entries


@dataclass
class Report:
    findings: List[Finding] = field(default_factory=list)
    suppressed: List[Finding] = field(default_factory=list)
    baselined: List[Finding] = field(default_factory=list)
    files_scanned: int = 0

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.pass_id] = out.get(f.pass_id, 0) + 1
        return out

    def as_dict(self) -> dict:
        return {
            "findings": [f.as_dict() for f in self.findings],
            "counts": self.counts(),
            "suppressed": len(self.suppressed),
            "baselined": len(self.baselined),
            "files_scanned": self.files_scanned,
        }

    def render(self) -> str:
        lines = [f.render() for f in self.findings]
        counts = self.counts()
        if counts:
            per = ", ".join(f"{k}={v}" for k, v in sorted(counts.items()))
            lines.append(f"graftlint: {len(self.findings)} finding(s) "
                         f"({per}); {len(self.baselined)} baselined, "
                         f"{len(self.suppressed)} suppressed")
        else:
            lines.append(
                f"graftlint clean ({self.files_scanned} file(s); "
                f"{len(self.baselined)} baselined, "
                f"{len(self.suppressed)} suppressed finding(s))")
        return "\n".join(lines)


def run(repo: str = REPO, passes: Optional[Sequence] = None,
        paths: Optional[Sequence[str]] = None,
        baseline_path: str = BASELINE,
        enforce_suppressions: Optional[bool] = None) -> Report:
    """Run ``passes`` (default: all) over ``repo``; apply suppressions
    and the baseline.  ``paths`` narrows the passes to explicit files or
    directories."""
    project = Project(repo=repo)
    chosen = list(passes) if passes is not None else all_passes()
    if enforce_suppressions is None:
        enforce_suppressions = (passes is None and paths is None)
    for p in paths or ():
        # a typo'd CI path must fail loudly, never pass by checking
        # zero files (the legacy checkers raised here too)
        if not (os.path.exists(p)
                or os.path.exists(os.path.join(repo, p))):
            raise FileNotFoundError(f"graftlint: no such path: {p}")

    raw: List[Finding] = []
    for p in chosen:
        raw += p.run(project, paths=paths)

    report = Report(files_scanned=len(project.scanned))
    by_rel = {m.rel: m for m in project._cache.values()}
    used: Dict[str, set] = {}        # module path -> {(line, pass_id)}
    for f in raw:
        mod = by_rel.get(f.path)
        disabled = mod.suppressions.get(f.line, set()) if mod else set()
        if f.pass_id in disabled or "all" in disabled:
            used.setdefault(mod.path, set()).add(
                (f.line, f.pass_id if f.pass_id in disabled else "all"))
            report.suppressed.append(f)
        else:
            report.findings.append(f)

    entries = load_baseline(baseline_path)
    by_fp = {e.fingerprint: e for e in entries}
    kept = []
    for f in report.findings:
        entry = by_fp.get(f.fingerprint)
        if entry is not None:
            entry.hits += 1
            report.baselined.append(f)
        else:
            kept.append(f)
    report.findings = kept

    if enforce_suppressions:
        base_rel = os.path.relpath(baseline_path, repo)
        for e in entries:
            if not e.hits:
                report.findings.append(Finding(
                    pass_id="stale-baseline", path=base_rel, line=e.line,
                    message=(f"baseline entry {e.fingerprint!r} matched no "
                             "finding — the grandfathered hazard is gone"),
                    hint="delete the entry (or fix the fingerprint)"))
        for mod_path in sorted(project.scanned):
            mod = project._cache[mod_path]
            for line, ids in sorted(mod.suppressions.items()):
                for pass_id in sorted(ids):
                    if (line, pass_id) in used.get(mod_path, set()):
                        continue
                    report.findings.append(Finding(
                        pass_id="unused-suppression", path=mod.rel,
                        line=line,
                        message=(f"'# graftlint: disable={pass_id}' "
                                 "suppresses nothing on this line"),
                        hint="remove the comment (the hazard it claims "
                             "is not flagged here)"))
    report.findings.sort(key=lambda f: (f.path, f.line, f.pass_id))
    return report
