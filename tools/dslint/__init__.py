"""dslint — DSTPU-specific static lint rules (``bin/dstpu_lint``).

AST-based checks for invariants generic linters cannot see (docs/
analysis.md has the full catalog with examples). The package runs off
ONE shared AST pass: ``lint()`` builds a :class:`RepoIndex` that parses
each file at most once, and every rule — per-file, drift, and the
cross-module analyses — consumes the same cached trees.

  DSL001 hot-path-host-sync   blocking host sync (``np.asarray`` /
         ``np.array``, ``jax.device_get``, ``.block_until_ready()``,
         ``.item()``, ``int()``/``float()`` coercion of non-trivial
         expressions) inside a registered overlap-critical function —
         the plan/dispatch phases of the serve pipeline and the runner
         program builders must never block on the device.
  DSL002 undonated-jit        ``jax.jit`` without ``donate_argnums`` /
         ``donate_argnames`` under ``deepspeed_tpu/inference/v2/``
         (serving pools are large; an undonated jit silently doubles
         peak HBM). Suppress per-site with a justification.
  DSL003 raw-shard-map-import ``jax.experimental.shard_map`` imported
         anywhere: the tree targets ``jax.shard_map`` (through
         ``utils/jax_compat.shard_map``) and carries no legacy spelling.
  DSL004 undocumented-knob    a ``DSTPU_*`` env knob read in code but
         absent from docs/CONFIG.md's generated knob table.
  DSL005 stale-knob-doc       a knob documented in docs/CONFIG.md that
         no code reads any more.
  DSL006 metric-drift         telemetry.REGISTERED_METRICS and the
         docs/observability.md metric catalog must match two-way.
  DSL007 lock-discipline      cross-module race detector over the
         registered serving thread roots: shared ``self.*`` state
         mutated from two thread groups under no common lock,
         lock-order inversions, and blocking syncs while a lock is
         held (see tools/dslint/locks.py).
  DSL008 collective-budget    static collective-site auditor over the
         seq/TP program builders against the declarative registry in
         deepspeed_tpu/analysis/budgets.py (see
         tools/dslint/budget_rule.py).

Suppression: ``# dslint: allow(DSL002): <justification>`` on any line of
the flagged statement (or the line directly above it).

Usage: ``bin/dstpu_lint [paths...] [--json] [--changed-only]`` — prints
``rule-id file:line message`` per finding and exits non-zero if any
survive.
"""

from __future__ import annotations

import argparse
import json as _json
import os
import subprocess
import sys
from typing import List, Mapping, Optional, Sequence, Tuple

from .core import (REPO, Finding, RepoIndex, _dotted, _import_aliases,
                   _module_aliases, _node_lines, _py_files, _suppressed)
from .intra import HOT_PATHS, file_findings, sync_call_msg
from .knobs import (ENV_SCAN_ROOTS, KnobRead, documented_knobs,
                    knob_findings, scan_env_knobs)
from .metrics import (METRICS_TABLE_FILE, OBSERVABILITY_DOC,
                      documented_metrics, metric_findings,
                      registered_metrics)
from .locks import THREAD_ROOTS, lock_findings
from .budget_rule import (BUDGET_REGISTRY_FILE, budget_findings,
                          load_registry)

__all__ = [
    "REPO", "RULES", "HOT_PATHS", "ENV_SCAN_ROOTS", "THREAD_ROOTS",
    "BUDGET_REGISTRY_FILE", "Finding", "KnobRead", "RepoIndex",
    "lint", "main", "scan_env_knobs", "documented_knobs",
    "documented_metrics", "registered_metrics",
]

RULES: Mapping[str, str] = {
    "DSL001": "blocking host sync inside a registered hot-path function",
    "DSL002": "jax.jit without donate_argnums/donate_argnames in "
              "inference/v2 (justify with # dslint: allow(DSL002): why)",
    "DSL003": "jax.experimental.shard_map import (use "
              "utils/jax_compat.shard_map, i.e. jax.shard_map)",
    "DSL004": "DSTPU_* env knob read in code but not documented in "
              "docs/CONFIG.md (re-run tools/gen_config_doc.py)",
    "DSL005": "DSTPU_* knob documented in docs/CONFIG.md but read "
              "nowhere (re-run tools/gen_config_doc.py)",
    "DSL006": "telemetry metric drift: telemetry.REGISTERED_METRICS and "
              "the docs/observability.md metric catalog must match "
              "two-way",
    "DSL007": "lock-discipline race: shared self.* state mutated from "
              "two thread roots with no common lock, a lock-order "
              "inversion, or a blocking sync while holding a lock",
    "DSL008": "collective-budget drift: a psum/ppermute/all_gather/"
              "all_to_all site unregistered in, or mismatching, "
              "deepspeed_tpu/analysis/budgets.py SITE_BUDGETS",
}


def lint(paths: Sequence[str], repo_root: str = REPO,
         hot_paths: Optional[Mapping[str, Tuple[str, ...]]] = None,
         knob_rules: bool = True,
         thread_roots: Optional[Mapping] = None,
         site_budgets: Optional[Mapping] = None) -> List[Finding]:
    """Lint ``paths`` (files or directories). The repo-level rules —
    DSL004/DSL005 (env knobs), DSL006 (telemetry metric catalog),
    DSL007 (thread roots) and DSL008 (collective budgets) — scan their
    anchors under ``repo_root`` regardless of ``paths``;
    ``knob_rules=False`` disables the knob/metric drift pair
    (synthetic-tree tests). ``thread_roots``/``site_budgets`` override
    the built-in registries (fixtures); the defaults no-op when the
    anchor files don't exist under ``repo_root``."""
    hot_paths = HOT_PATHS if hot_paths is None else hot_paths
    index = RepoIndex(repo_root)
    findings: List[Finding] = []
    for p in paths:
        full = p if os.path.isabs(p) else os.path.join(repo_root, p)
        for path in _py_files(full):
            fi = index.get(path)
            if fi is not None:
                findings.extend(file_findings(fi, hot_paths))
    if knob_rules:
        findings.extend(knob_findings(index))
        findings.extend(metric_findings(index))
    findings.extend(lock_findings(
        index, THREAD_ROOTS if thread_roots is None else thread_roots))
    if site_budgets is None:
        site, hop, err, reg_line = load_registry(index)
        if err is not None:
            findings.append(err)
        elif site is not None:
            findings.extend(budget_findings(
                index, site, hop, registry_line=reg_line))
    else:
        findings.extend(budget_findings(index, site_budgets))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def _changed_files(repo_root: str) -> Optional[set]:
    """Repo-relative paths changed vs HEAD (tracked) plus untracked
    files; None when git is unavailable (fall back to a full lint)."""
    try:
        diff = subprocess.run(
            ["git", "-C", repo_root, "diff", "--name-only", "HEAD", "--"],
            capture_output=True, text=True, timeout=30)
        untracked = subprocess.run(
            ["git", "-C", repo_root, "ls-files", "--others",
             "--exclude-standard"],
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.SubprocessError):
        return None
    if diff.returncode != 0 or untracked.returncode != 0:
        return None
    return {ln.strip() for ln in
            (diff.stdout + untracked.stdout).splitlines() if ln.strip()}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="dstpu_lint",
        description="DSTPU-specific static lint (see docs/analysis.md)")
    ap.add_argument("paths", nargs="*", default=["deepspeed_tpu"],
                    help="files/directories to lint (default: "
                         "deepspeed_tpu)")
    ap.add_argument("--root", default=REPO,
                    help="repo root (docs/CONFIG.md + knob scan anchor)")
    ap.add_argument("--no-knob-rules", action="store_true",
                    help="skip the repo-level DSL004/DSL005 knob scan")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output (findings + count)")
    ap.add_argument("--changed-only", action="store_true",
                    help="fast mode: report only findings in files "
                         "changed vs git HEAD (clean exit without "
                         "parsing when nothing changed)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)
    if args.list_rules:
        for rid, desc in RULES.items():
            print(f"{rid}  {desc}")
        return 0

    changed: Optional[set] = None
    if args.changed_only:
        changed = _changed_files(args.root)
        if changed is not None and not changed:
            if args.json:
                print(_json.dumps({"count": 0, "clean": True,
                                   "changed_only": True, "findings": []}))
            else:
                print("dslint: 0 findings — clean (no changed files)")
            return 0

    findings = lint(args.paths or ["deepspeed_tpu"], repo_root=args.root,
                    knob_rules=not args.no_knob_rules)
    if changed is not None:
        findings = [f for f in findings if f.path in changed]

    if args.json:
        print(_json.dumps({
            "count": len(findings),
            "clean": not findings,
            "changed_only": bool(args.changed_only),
            "findings": [{"rule": f.rule, "path": f.path,
                          "line": f.line, "message": f.message}
                         for f in findings],
        }, indent=2))
        return 1 if findings else 0

    for f in findings:
        print(f)
    n = len(findings)
    print(f"dslint: {n} finding{'s' if n != 1 else ''}"
          + ("" if n else " — clean"))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
