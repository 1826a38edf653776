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

``run.py`` does not call this module yet (a PR that changes the program
may not edit the harness). A traced run leaves its profile under
``.bench_trace/<cell>``; read it with

    python3 -m benchmark.program_spans .bench_trace/serve-chat-steady
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

from . import reduce_trace as rt

PROGRAM_PREFIX = "dstpu:"
MODULE_LINE = "XLA Modules"
LAUNCH_EVENT = "DoEnqueueProgram"

Span = Tuple[float, float, str]


def load(path: str) -> Dict[str, Any]:
    """{"bench": [...], "program": [(start_s, end_s, name)], "ops":
    {plane: [(start_s, end_s)]}, "modules": {plane: [(start_s, end_s,
    name, run_id)]}, "launches": {(device ordinal, run_id): start_s}}."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Any] = {"bench": [], "program": [], "ops": {},
                           "modules": {}, "launches": {}}
    for plane in data.planes:
        if rt.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == rt.OP_LINE:
                    out["ops"][plane.name] = [
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
                elif line.name == MODULE_LINE:
                    out["modules"][plane.name] = [
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name,
                         dict(e.stats).get("run_id"))
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    for prefix, key in ((rt.SPAN_PREFIX, "bench"),
                                        (PROGRAM_PREFIX, "program")):
                        if name.startswith(prefix):
                            out[key].append((
                                e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                name[len(prefix):]))
                    if name == LAUNCH_EVENT:
                        st = dict(e.stats)
                        key = (int(st.get("device_ordinal", 0)),
                               st.get("run_id"))
                        t = e.start_ns * 1e-9
                        if key[1] is not None \
                                and t < out["launches"].get(key, t + 1):
                            out["launches"][key] = t
    out["bench"].sort()
    out["program"].sort()
    return out


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
    cuts = sorted(set(b_bounds) | set(p_bounds))
    by_name: Dict[str, float] = {}
    by_phase: Dict[str, float] = {s[2]: 0.0 for s in extra["program"]}
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
    named = sum(v for k, v in by_phase.items() if k != "none") \
        + by_name.get("none", 0.0)
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
