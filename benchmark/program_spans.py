"""The program's own spans beside the benchmark's, from the same
``.xplane.pb`` that ``reduce_trace`` reads.

The program brackets its boundaries with ``dstpu:<layer>/<phase>``
``TraceAnnotation`` spans (``deepspeed_tpu/telemetry/trace.py``); the
benchmark brackets its calls into the program with ``bench:<call>``.
This module names every idle gap of the device by the innermost span of
each kind that covers it, benchmark name first
(``decode_pipelined/serve/dispatch``), so the breakdown says what the
host was doing while the device waited. It also gives:

* ``idle_by_phase``: idle seconds by innermost program span (``none``
  where no program span covers), with a 0.0 for every program span
  that appears in the trace, so a ratio over an idle-free phase reads 0
  and a trace without program spans gives nothing to read;
* ``idle_named_share``: the share of the idle seconds that lie under a
  program span, under no benchmark span at all (the harness's loop), or
  under a benchmark span that brackets the harness's own code (one no
  program span ever opens under: ``harvest``, a sleep). What is left is
  inside a call into the program but outside every bracket of its own:
  the program's blind spot. A trace without any program span (a parent
  of PR 25) counts only the harness's loop as named;
* ``device_programs``: device seconds and runs per program, from the
  device plane's ``XLA Modules`` line (one event per program run, named
  by its jit function);
* ``clock_offset_s``: what to add to the device's clock to read the
  host's, estimated from causality: no program run may start before the
  host enqueued it (``DoEnqueueProgram`` and the module event share a
  ``run_id``), so the least shift under which none does is the offset,
  exact up to the shortest launch latency in the trace (taken on the
  first device, whose gaps are the ones named). It is used ONLY
  to place the gaps among the host's spans: the gaps themselves, the
  window and every sum are computed on the clocks as recorded, exactly
  as ``reduce_trace.reduce`` computes them.

A gap that crosses span boundaries is split at them, each piece named
on its own; a single gap in ``idle_gaps`` carries the name that covers
most of it.

``run.py`` reads the profile once (``reduce_trace.load``) and hands it
to ``reduce_trace.reduce`` and, through :func:`from_trace`, to
:func:`name_gaps`: the keys above reach the readers under
``obs["trace"]`` and the printed ``breakdown.idle_gaps`` are this
module's. A traced run leaves its profile under ``.bench_trace/<cell>``;
to read a saved one again:

    python3 -m benchmark.program_spans .bench_trace/serve-chat-steady
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

from . import reduce_trace as rt

Span = Tuple[float, float, str]


def from_trace(trace: Dict[str, Any]) -> Dict[str, Any]:
    """What :func:`name_gaps` reads, out of ``reduce_trace.load``'s one
    reading of the profile: {"bench": [...], "program": [(start_s, end_s,
    name)], "ops": {plane: [(start_s, end_s)]}, "modules": {plane:
    [(start_s, end_s, name, run_id)]}, "launches": {(device ordinal,
    run_id): start_s}}."""
    return {"bench": trace["spans"], "program": trace.get("program", []),
            "ops": {plane: [(s, e) for s, e, _ in events]
                    for plane, events in trace["devices"].items()},
            "modules": trace.get("modules", {}),
            "launches": trace.get("launches", {})}


def load(path: str) -> Dict[str, Any]:
    return from_trace(rt.load(path))


def clock_offset(modules: Dict[str, List[Tuple]],
                 launches: Dict[Tuple[int, Any], float]
                 ) -> Optional[float]:
    """Seconds to add to a device time to read the host's clock: the
    least shift under which no program run starts before its launch.
    None when no run can be matched with its launch."""
    worst = None
    for plane, runs in modules.items():
        ordinal = int(plane.rsplit(":", 1)[1])
        for start, _end, _name, run_id in runs:
            launched = launches.get((ordinal, run_id))
            if launched is not None:
                lag = launched - start
                worst = lag if worst is None else max(worst, lag)
    return worst


def _innermost(spans: List[Span]) -> Tuple[List[float], List[Optional[str]]]:
    """Elementary segments of the time axis, each with the span that
    opened last among those covering it: (segment starts, names); the
    segment i runs from starts[i] to starts[i + 1]."""
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    names: List[Optional[str]] = []
    active: List[Span] = []
    nxt = 0
    for t in bounds:
        while nxt < len(spans) and spans[nxt][0] <= t:
            active.append(spans[nxt])
            nxt += 1
        active = [a for a in active if a[1] > t]
        names.append(max(active, key=lambda a: (a[0], -a[1]))[2]
                     if active else None)
    return bounds, names


def _name_at(bounds: List[float], names: List[Optional[str]],
             t: float) -> Optional[str]:
    i = bisect.bisect_right(bounds, t) - 1
    return names[i] if i >= 0 else None


def _program_name(module_event: str) -> str:
    return re.sub(r"\(\d+\)$", "", module_event)


def name_gaps(extra: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """See the module's docstring. ``extra`` is what :func:`load` gave.
    Returns the new keys of ``obs["trace"]`` under ``trace`` and those
    of the printed breakdown under ``breakdown`` (its ``idle_gaps``
    takes the place of ``reduce_trace``'s)."""
    ops, bench = extra["ops"], extra["bench"]
    if not ops:
        return {"trace": {}, "breakdown": {}}
    outer = [s for s in bench if s[2] == "window"] or bench
    if outer:
        lo, hi = min(s[0] for s in outer), max(s[1] for s in outer)
    else:
        every = [e for evs in ops.values() for e in evs]
        lo, hi = min(e[0] for e in every), max(e[1] for e in every)
    first = sorted(ops)[0]
    busy = rt.union((max(s, lo), min(e, hi)) for s, e in ops[first]
                    if min(e, hi) > max(s, lo))
    gaps = rt.subtract([(lo, hi)], busy)
    # the offset of the device whose gaps are named
    offset = clock_offset({first: extra["modules"].get(first, [])},
                          extra["launches"])
    shift = offset or 0.0

    b_bounds, b_names = _innermost([s for s in bench if s[2] != "window"])
    p_bounds, p_names = _innermost(extra["program"])
    # a benchmark span that no program span ever opens under is the
    # harness's own code (``harvest``, a sleep), not a call into the
    # program: idle under it has its whole name already
    starts = [s[0] for s in extra["program"]]
    calls = {name for s, e, name in bench
             if bisect.bisect_left(starts, e) > bisect.bisect_left(starts, s)}
    cuts = sorted(set(b_bounds) | set(p_bounds))
    by_name: Dict[str, float] = {}
    by_phase: Dict[str, float] = {s[2]: 0.0 for s in extra["program"]}
    named = 0.0
    singles: List[Tuple[str, float]] = []
    for gs, ge in gaps:
        gs, ge = gs + shift, ge + shift
        inner = cuts[bisect.bisect_right(cuts, gs):
                     bisect.bisect_left(cuts, ge)]
        parts: Dict[str, float] = {}
        for a, b in zip([gs] + inner, inner + [ge]):
            mid = (a + b) / 2
            bench_name = _name_at(b_bounds, b_names, mid)
            phase = _name_at(p_bounds, p_names, mid)
            name = "/".join(n for n in (bench_name, phase) if n) or "none"
            parts[name] = parts.get(name, 0.0) + (b - a)
            by_phase[phase or "none"] = \
                by_phase.get(phase or "none", 0.0) + (b - a)
            if phase or bench_name is None or (starts and bench_name
                                               not in calls):
                named += b - a
        for name, dt in parts.items():
            by_name[name] = by_name.get(name, 0.0) + dt
        singles.append((max(parts, key=parts.get), ge - gs))
    singles.sort(key=lambda x: -x[1])
    idle = [[f"all_gaps_under_{k}", v] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])][:top // 2]
    idle += [[f"one_gap_under_{k}", v] for k, v in singles[:top - len(idle)]]

    programs: Dict[str, List[float]] = {}
    for runs in extra["modules"].values():
        for s, e, name, _run in runs:
            if min(e, hi) > max(s, lo):
                row = programs.setdefault(_program_name(name), [0.0, 0])
                row[0] += min(e, hi) - max(s, lo)
                row[1] += 1
    total = sum(by_phase.values())
    shared = {
        "idle_named_share": named / total if total else None,
        "device_programs": [[k, v[0], v[1]] for k, v in sorted(
            programs.items(), key=lambda kv: -kv[1][0])[:top]],
        "clock_offset_s": offset,
    }
    return {"trace": dict(shared, idle_by_phase=by_phase,
                          idle_by_name=by_name),
            "breakdown": dict(shared, idle_gaps=idle)}


def read(path: str, top: int = 10) -> Dict[str, Any]:
    return name_gaps(load(path), top)


def main(argv: Optional[List[str]] = None) -> int:
    """Print the named breakdown of one traced run as a line of JSON:
    the argument is a ``.xplane.pb`` or a directory that holds one."""
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(main.__doc__, file=sys.stderr)
        return 2
    path = argv[0] if argv[0].endswith(".pb") else rt.find_xplane(argv[0])
    named = read(path)
    print(json.dumps(dict(named["breakdown"],
                          idle_by_phase=named["trace"].get("idle_by_phase"))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
