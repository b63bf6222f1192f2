"""Pass protocol: a pass declares an ``id``, the repo-relative ``roots``
it scans, and implements ``check_module``."""

from __future__ import annotations

import os

from typing import List, Optional, Sequence

from ..core import Finding, ModuleInfo, Project


class LintPass:
    #: the name suppressions and the baseline refer to
    id: str = ""
    #: one-line description for --list-passes / the pass catalog doc
    describes: str = ""
    #: repo-relative directories/files scanned by default
    roots: Sequence[str] = ()
    #: True = ``roots`` define WHERE THE CONVENTION APPLIES (the
    #: durable layer, the step trees) and explicit paths can only
    #: narrow them; False = ``roots`` are just the default scan surface
    #: and an explicit path substitutes for them (lint any tree)
    scope_fixed: bool = False

    def run(self, project: Project,
            paths: Optional[Sequence[str]] = None) -> List[Finding]:
        """Findings over the pass's roots, optionally narrowed by
        explicit ``paths``.

        For a ``scope_fixed`` pass an in-repo path RESTRICTS the pass to
        the intersection of the path and the pass's own roots —
        ``graftlint flink_ml_tpu`` must not run the durable-layer-only
        atomic-writes rule over the whole package.  Generic passes scan
        whatever tree they are pointed at; a path OUTSIDE the repo is
        always scanned as given — the point-the-tool-at-a-fixture
        behavior the legacy checkers had."""
        findings: List[Finding] = []
        for mod in project.iter_modules(
                self._scoped(project, paths) if paths else self.roots):
            findings += self.check_module(mod, project)
        return findings

    def _scoped(self, project: Project,
                paths: Sequence[str]) -> List[str]:
        def _norm(p: str) -> str:
            return os.path.abspath(p if os.path.isabs(p)
                                   else os.path.join(project.repo, p))

        def _under(child: str, parent: str) -> bool:
            return child == parent or \
                child.startswith(parent.rstrip(os.sep) + os.sep)

        repo = os.path.abspath(project.repo)
        # roots absent from THIS project (a fixture repo, typically)
        # cannot scope anything — explicit paths then scan as given
        abs_roots = [r for r in (_norm(r) for r in self.roots)
                     if os.path.exists(r)]
        scoped: List[str] = []
        for p in paths:
            ap = _norm(p)
            if not _under(ap, repo) or not abs_roots \
                    or not self.scope_fixed:
                scoped.append(ap)               # scan as given
                continue
            for root in abs_roots:
                if _under(ap, root):
                    scoped.append(ap)           # path narrows the root
                    break
                if _under(root, ap):
                    scoped.append(root)         # path contains the root
        return list(dict.fromkeys(scoped))

    def check_module(self, mod: ModuleInfo,
                     project: Project) -> List[Finding]:
        raise NotImplementedError
