"""dslint DSL008 — static collective-budget auditor.

The declarative registry lives in ``deepspeed_tpu/analysis/budgets.py``
as PURE LITERALS: the runtime (the budget tests) imports it,
while this rule ``ast.literal_eval``s the same assignments — one source
of truth, checked without ever importing the package (no jax needed at
lint time).

``SITE_BUDGETS`` maps each audited file to its registered
program-builder functions and the number of DISTINCT collective call
sites (by primitive kind) reachable from each through the call graph —
calls into ``comm/comm.py`` are the decomposed-collective layer's own
domain and form the audit boundary. The rule flags:

  * a collective call site in an audited file not reachable from any
    registered builder (an unregistered collective),
  * a registered builder whose reachable site counts do not match its
    registered budget (drift — someone added/removed a collective
    without updating the registry),
  * a registered builder that no longer exists,
  * a ``HOP_BUDGETS`` entry naming a collective kind no registered
    builder has a site for (a runtime budget nothing can satisfy).

Counting SITES is deliberate: runtime hop counts (layers x steps x
ring hops) live in ``HOP_BUDGETS`` and are asserted by the program
auditor; lint pins the static shape that feeds them.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Mapping, Optional, Set, Tuple

from .core import FileIndex, Finding, RepoIndex, _dotted
from .callgraph import (Unit, UnitKey, file_units, resolve_ref,
                        unit_refs, _walk_unit)

#: the shared budget registry (runtime imports it; lint parses it)
BUDGET_REGISTRY_FILE = "deepspeed_tpu/analysis/budgets.py"

#: collective primitives the auditor counts (last dotted component,
#: receiver must resolve through ``lax`` or ``comm``)
COLLECTIVE_KINDS = ("psum", "pmax", "pmin", "ppermute", "pshuffle",
                    "all_gather", "all_to_all")

#: comm-layer wrapper names that count as a canonical kind at the call
#: site (``comm.all_to_all_single`` IS the repo's all_to_all — the
#: torch.distributed-shaped flat wrapper the EP dispatch/combine uses)
_SITE_ALIASES = {"all_to_all_single": "all_to_all"}

#: HOP_BUDGETS canonical kinds -> site kinds that can produce them
_HOP_TO_SITE = {
    "all_reduce": ("psum", "pmax", "pmin"),
    "all_gather": ("all_gather",),
    "ppermute": ("ppermute",),
    "reduce_scatter": ("ppermute", "psum"),
    "all_to_all": ("all_to_all",),
}


def load_registry(index: RepoIndex) -> Tuple[Optional[dict],
                                             Optional[dict],
                                             Optional[Finding], int]:
    """(site_budgets, hop_budgets, literal-error finding, assign line)
    parsed from the registry file without importing it."""
    fi = index.get_rel(BUDGET_REGISTRY_FILE)
    if fi is None or fi.tree is None:
        return None, None, None, 0
    site: Optional[dict] = None
    hop: Optional[dict] = None
    line = 0
    for node in fi.tree.body:
        if not isinstance(node, ast.Assign):
            continue
        names = {t.id for t in node.targets if isinstance(t, ast.Name)}
        for want in ("SITE_BUDGETS", "HOP_BUDGETS"):
            if want not in names:
                continue
            try:
                val = ast.literal_eval(node.value)
            except (ValueError, SyntaxError):
                return None, None, Finding(
                    "DSL008", fi.relpath, node.lineno,
                    f"{want} must be a pure literal (dslint parses it "
                    f"without importing the package)"), node.lineno
            if want == "SITE_BUDGETS":
                site, line = val, node.lineno
            else:
                hop = val
    return site, hop, None, line


def _collective_kind(node: ast.Call,
                     mod_aliases: Mapping[str, str]) -> Optional[str]:
    """Collective primitive kind of a call site, or None. Accepts
    ``jax.lax.<kind>``, ``lax.<kind>`` and ``comm.<kind>`` receivers
    (the decomposed-collective wrappers count as their kind)."""
    dotted = _dotted(node.func, mod_aliases)
    if not dotted:
        return None
    parts = dotted.split(".")
    name = _SITE_ALIASES.get(parts[-1], parts[-1])
    if name not in COLLECTIVE_KINDS or len(parts) < 2:
        return None
    if parts[-2] in ("lax", "comm"):
        return name
    return None


def _unit_sites(fi: FileIndex, unit: Unit) -> List[Tuple[str, int]]:
    """(kind, line) of every collective call directly inside a unit
    (nested defs included — they trace as part of the builder)."""
    out: List[Tuple[str, int]] = []
    for n in _walk_unit(unit.node):
        if isinstance(n, ast.Call):
            kind = _collective_kind(n, fi.mod_aliases)
            if kind is not None:
                out.append((kind, n.lineno))
    return out


def budget_findings(index: RepoIndex,
                    site_budgets: Optional[Mapping[str, Mapping]] = None,
                    hop_budgets: Optional[Mapping[str, Mapping]] = None,
                    registry_line: int = 0,
                    registry_relpath: str = BUDGET_REGISTRY_FILE
                    ) -> List[Finding]:
    """DSL008 over the audited files named by ``site_budgets`` keys."""
    if site_budgets is None:
        return []
    files: Dict[str, FileIndex] = {}
    units_by_file: Dict[str, Dict[str, Unit]] = {}
    for relpath in site_budgets:
        fi = index.get_rel(relpath)
        if fi is None or fi.tree is None:
            continue
        files[relpath] = fi
        units_by_file[relpath] = file_units(fi)

    # direct sites per unit + per file
    sites_of: Dict[UnitKey, List[Tuple[str, int]]] = {}
    for relpath, units in units_by_file.items():
        for unit in units.values():
            sites_of[unit.key] = _unit_sites(files[relpath], unit)

    # call-graph closure restricted to the audited files
    edges: Dict[UnitKey, Set[UnitKey]] = {}
    for relpath, units in units_by_file.items():
        fi = files[relpath]
        for unit in units.values():
            tgts: Set[UnitKey] = set()
            for kind, spec, _ln in unit_refs(fi, unit):
                tgt = resolve_ref(index, fi, unit, kind, spec,
                                  units_by_file)
                if tgt is not None and tgt != unit.key:
                    tgts.add(tgt)
            edges[unit.key] = tgts

    def closure(start: UnitKey) -> Set[UnitKey]:
        seen: Set[UnitKey] = set()
        stack = [start]
        while stack:
            k = stack.pop()
            if k in seen:
                continue
            seen.add(k)
            stack.extend(edges.get(k, ()))
        return seen

    raw: List[Tuple[Finding, range, FileIndex]] = []
    covered: Set[Tuple[str, int]] = set()   # (relpath, line) of sites
    for relpath, budgets in sorted(site_budgets.items()):
        if relpath not in files:
            if budgets:
                raw.append((Finding(
                    "DSL008", registry_relpath, registry_line,
                    f"SITE_BUDGETS names missing file {relpath}"),
                    range(registry_line, registry_line + 1),
                    index.get_rel(registry_relpath) or _dummy(index)))
            continue
        fi = files[relpath]
        units = units_by_file[relpath]
        for qual, expected in sorted(budgets.items()):
            if qual not in units:
                raw.append((Finding(
                    "DSL008", relpath, 0,
                    f"registered builder '{qual}' not found — remove "
                    f"its SITE_BUDGETS entry or restore the function"),
                    range(0, 1), fi))
                continue
            reach = closure(units[qual].key)
            actual_sites: Dict[str, Set[Tuple[str, int]]] = {}
            for k in reach:
                for kind, line in sites_of.get(k, ()):
                    actual_sites.setdefault(kind, set()).add((k[0], line))
                    covered.add((k[0], line))
            actual = {k: len(v) for k, v in sorted(actual_sites.items())}
            if actual != dict(expected):
                node = units[qual].node
                raw.append((Finding(
                    "DSL008", relpath, node.lineno,
                    f"collective site budget mismatch for '{qual}': "
                    f"registry says {dict(expected)}, call graph "
                    f"reaches {actual} — update "
                    f"deepspeed_tpu/analysis/budgets.py or the code"),
                    range(node.lineno, node.lineno + 1), fi))

    # unregistered collectives: sites no registered builder reaches
    for relpath, units in sorted(units_by_file.items()):
        fi = files[relpath]
        for unit in units.values():
            for kind, line in sites_of.get(unit.key, ()):
                if (relpath, line) not in covered:
                    raw.append((Finding(
                        "DSL008", relpath, line,
                        f"unregistered collective: {kind} at "
                        f"{relpath}:{line} is not reachable from any "
                        f"SITE_BUDGETS builder — register it or justify "
                        f"with # dslint: allow(DSL008): why"),
                        range(line, line + 1), fi))
        # module-level collectives (outside any def) are always stray
        if fi.tree is not None:
            in_unit_lines = {ln for u in units.values()
                             for _, ln in sites_of.get(u.key, ())}
            for n in ast.walk(fi.tree):
                if isinstance(n, ast.Call):
                    kind = _collective_kind(n, fi.mod_aliases)
                    if kind is not None and n.lineno not in in_unit_lines:
                        raw.append((Finding(
                            "DSL008", relpath, n.lineno,
                            f"unregistered module-level collective: "
                            f"{kind} outside any builder"),
                            range(n.lineno, n.lineno + 1), fi))

    # hop budgets must name kinds some builder can actually issue
    if hop_budgets:
        site_kinds: Set[str] = set()
        for v in sites_of.values():
            site_kinds.update(k for k, _ in v)
        reg_fi = index.get_rel(registry_relpath)
        for prog, spec in sorted(hop_budgets.items()):
            kinds = set(spec.get("per_layer", {})) \
                | set(spec.get("per_program", {}))
            for k in sorted(kinds):
                base = k.split("@", 1)[0]
                producers = _HOP_TO_SITE.get(base, (base,))
                if not any(p in site_kinds for p in producers):
                    raw.append((Finding(
                        "DSL008", registry_relpath, registry_line,
                        f"HOP_BUDGETS['{prog}'] budgets '{base}' but no "
                        f"registered builder has a matching collective "
                        f"site"), range(registry_line, registry_line + 1),
                        reg_fi or _dummy(index)))

    return [f for f, lines, fi in raw
            if fi is None or not fi.suppressed(lines, f.rule)]


def _dummy(index: RepoIndex) -> Optional[FileIndex]:
    return None
