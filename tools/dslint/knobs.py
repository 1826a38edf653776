"""dslint env-knob scan — DSL004/DSL005 plus the shared
``scan_env_knobs`` helper tools/gen_config_doc.py generates the
docs/CONFIG.md table from."""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, List, Mapping, Optional, Tuple

from .core import REPO, Finding, RepoIndex, _dotted, _py_files

#: roots scanned for DSTPU_* env reads (knob rules + gen_config_doc) —
#: everything an operator can set, test-only knobs excluded
ENV_SCAN_ROOTS = ("deepspeed_tpu", "tools", "bin", "examples")

_KNOB_DOC_ROW_RE = re.compile(r"^\|\s*`(DSTPU_[A-Z0-9_]+)`")
_ENV_METHODS = ("get", "pop", "setdefault")


@dataclasses.dataclass
class KnobRead:
    name: str
    path: str       # repo-relative
    line: int
    #: repr of the literal default; "(dynamic)" for a computed default
    #: expression; None when the read has NO default (required)
    default: Optional[str]


def _default_repr(call: ast.Call) -> str:
    if len(call.args) < 2:
        return "None"      # .get/.pop/getenv with implicit None default
    dflt = call.args[1]
    return repr(dflt.value) if isinstance(dflt, ast.Constant) \
        else "(dynamic)"


def _env_read(node: ast.AST, aliases: Mapping[str, str]
              ) -> Optional[Tuple[str, Optional[str]]]:
    """(knob name, default repr) when ``node`` reads an env var with a
    literal name; None otherwise. Covers os.environ.get/pop/setdefault,
    os.environ[...], os.getenv(...) and ``"X" in os.environ``."""
    def lit(n):
        return n.value if isinstance(n, ast.Constant) \
            and isinstance(n.value, str) else None

    if isinstance(node, ast.Call):
        dotted = _dotted(node.func, aliases)
        if dotted == "os.getenv" and node.args:
            name = lit(node.args[0])
            if name:
                return name, _default_repr(node)
        if dotted and dotted.startswith("os.environ.") \
                and dotted.rsplit(".", 1)[1] in _ENV_METHODS and node.args:
            name = lit(node.args[0])
            if name:
                return name, _default_repr(node)
    elif isinstance(node, ast.Subscript):
        if _dotted(node.value, aliases) == "os.environ":
            name = lit(node.slice)
            if name:
                return name, None
    elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
            and isinstance(node.ops[0], (ast.In, ast.NotIn)):
        if _dotted(node.comparators[0], aliases) == "os.environ":
            name = lit(node.left)
            if name:
                return name, None
    return None


def scan_env_knobs(repo_root: str = REPO, prefix: str = "DSTPU_",
                   index: Optional[RepoIndex] = None) -> List[KnobRead]:
    """Every literal ``<prefix>*`` env read under ENV_SCAN_ROOTS — shared
    by the knob-drift rules and tools/gen_config_doc.py (which generates
    the docs/CONFIG.md table DSL004/DSL005 check against). Pass the
    ``lint()`` call's ``index`` to keep the scan on the one shared AST
    pass."""
    if index is None:
        index = RepoIndex(repo_root)
    reads: List[KnobRead] = []
    for root in ENV_SCAN_ROOTS:
        full = os.path.join(repo_root, root)
        if not os.path.exists(full):
            continue
        for path in _py_files(full):
            fi = index.get(path)
            if fi is None or fi.tree is None:
                continue
            for node in ast.walk(fi.tree):
                hit = _env_read(node, fi.aliases)
                if hit and hit[0].startswith(prefix):
                    reads.append(KnobRead(
                        hit[0], fi.relpath, node.lineno, hit[1]))
    return reads


def documented_knobs(config_md: str) -> List[Tuple[str, int]]:
    """(knob, line) rows of the generated env-knob table in CONFIG.md."""
    out: List[Tuple[str, int]] = []
    in_section = False
    for i, line in enumerate(config_md.splitlines(), 1):
        if line.startswith("## "):
            in_section = "Environment knobs" in line
        if in_section:
            m = _KNOB_DOC_ROW_RE.match(line)
            if m:
                out.append((m.group(1), i))
    return out


def knob_findings(index: RepoIndex) -> List[Finding]:
    repo_root = index.repo_root
    cfg_path = os.path.join(repo_root, "docs", "CONFIG.md")
    if not os.path.exists(cfg_path):
        return [Finding("DSL004", "docs/CONFIG.md", 0,
                        "missing — run tools/gen_config_doc.py to "
                        "generate the env-knob table")]
    with open(cfg_path, encoding="utf-8") as f:
        doc_rows = documented_knobs(f.read())
    documented = {k for k, _ in doc_rows}
    reads = scan_env_knobs(repo_root, index=index)
    findings: List[Finding] = []
    seen = set()
    for r in reads:
        if r.name not in documented and r.name not in seen:
            seen.add(r.name)
            findings.append(Finding(
                "DSL004", r.path, r.line,
                f"env knob {r.name} is read here but undocumented in "
                f"docs/CONFIG.md — run tools/gen_config_doc.py"))
    read_names = {r.name for r in reads}
    for name, line in doc_rows:
        if name not in read_names:
            findings.append(Finding(
                "DSL005", "docs/CONFIG.md", line,
                f"documented env knob {name} is read nowhere — run "
                f"tools/gen_config_doc.py"))
    return findings
