"""From a profiler trace (``.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone. What it gives:

* ``busy_s`` / ``window_s``: union of the intervals in which an operation
  ran on a device, averaged over the devices used, and the traced window
  (first benchmark span's start to the last one's end, else the extent of
  the device events);
* ``ops``: device time by operation name (stable names: the HLO op or the
  kernel's own name), summed over devices;
* ``idle_gaps``: the device's idle gaps attributed to the benchmark span
  (``bench:<name>`` ``TraceAnnotation`` on the host) that covered them;
* ``collective_s`` / ``exposed_collective_s``: time in collective
  operations (synchronous ones on the operation line, asynchronous ones on
  the ``Async XLA Ops`` line), and the part of it during which no other
  operation ran on that device, averaged over devices.

``load`` is the one reading of the profile: beside what ``reduce`` needs
it keeps the program's own spans, the program runs and their launches,
which ``program_spans`` names the idle gaps by (``run.py`` hands the one
reading to both).

The device's operation line nests (a ``while`` or a fusion's parent spans
its children), so busy time is a union, never a sum, and ``ops`` counts
only events that contain no other event of their line (leaves).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench:"
PROGRAM_PREFIX = "dstpu:"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
LAUNCH_EVENT = "DoEnqueueProgram"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the sorted disjoint ``a`` not covered by sorted disjoint
    ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events: List[Tuple[float, float, str]]
           ) -> List[Tuple[float, float, str]]:
    """Events that contain no other event of the same line."""
    ev = sorted(events, key=lambda x: (x[0], -x[1]))
    out = []
    for i, (s, e, name) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][0] < e and ev[i + 1][1] <= e \
                and (ev[i + 1][0] > s or ev[i + 1][1] < e):
            continue
        out.append((s, e, name))
    return out


_HLO = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = \(?(\w+)\[([\d,]*)\]")


def stable_name(name: str) -> str:
    """A short name that survives a recompile. The TPU's operation events
    are named by their whole HLO instruction, ``%fusion.340 =
    bf16[16,512,8960]{...} fusion(...)``: keep the instruction's name
    without XLA's numbering and the (first) output's type and shape,
    ``fusion-bf16_16_512_8960``. Any other name only loses a trailing
    number."""
    m = _HLO.match(name)
    if m:
        dims = m.group(3).replace(",", "_")
        return f"{m.group(1)}-{m.group(2)}_{dims}"[:64]
    return re.sub(r"\.\d+$", "", name)[:64]


def load(path: str) -> Dict[str, Any]:
    """The one reading of a profile: {"devices": {plane: [(start_s, end_s,
    name)]}, "async": the same of the asynchronous line, "spans": the
    benchmark's spans} for :func:`reduce`, and beside them what
    ``program_spans`` names the idle gaps by: "program" (the program's own
    spans), "modules" ({plane: [(start_s, end_s, name, run_id)]}, one
    event a program run) and "launches" ({(device ordinal, run_id):
    start_s} of the host's enqueues)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    overlapped: Dict[str, List[Tuple[float, float, str]]] = {}
    modules: Dict[str, List[Tuple[float, float, str, Any]]] = {}
    spans: List[Tuple[float, float, str]] = []
    program: List[Tuple[float, float, str]] = []
    launches: Dict[Tuple[int, Any], float] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                into = {OP_LINE: devices, ASYNC_LINE: overlapped}.get(
                    line.name)
                if into is not None:
                    into[plane.name] = [
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for e in line.events]
                elif line.name == MODULE_LINE:
                    modules[plane.name] = [
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name,
                         dict(e.stats).get("run_id"))
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    for prefix, into in ((SPAN_PREFIX, spans),
                                         (PROGRAM_PREFIX, program)):
                        if name.startswith(prefix):
                            into.append((e.start_ns * 1e-9,
                                         (e.start_ns + e.duration_ns) * 1e-9,
                                         name[len(prefix):]))
                    if name == LAUNCH_EVENT:
                        st = dict(e.stats)
                        key = (int(st.get("device_ordinal", 0)),
                               st.get("run_id"))
                        t = e.start_ns * 1e-9
                        if key[1] is not None \
                                and t < launches.get(key, t + 1):
                            launches[key] = t
    return {"devices": devices, "async": overlapped, "spans": sorted(spans),
            "program": sorted(program), "modules": modules,
            "launches": launches}


def reduce(trace: Dict[str, Any], top: int = 10,
           window: Optional[Interval] = None) -> Dict[str, Any]:
    devices, spans = trace["devices"], trace["spans"]
    if not devices:
        raise ValueError("the trace holds no device plane with an "
                         f"{OP_LINE!r} line: nothing ran on the device")
    if window is None:
        outer = [s for s in spans if s[2] == "window"] or spans
        if outer:
            window = (min(s[0] for s in outer), max(s[1] for s in outer))
        else:
            every = [e for evs in devices.values() for e in evs]
            window = (min(e[0] for e in every), max(e[1] for e in every))
    lo, hi = window
    ops: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    busy_s, coll_s, exposed_s = [], [], []
    gaps: List[Tuple[float, float]] = []
    for plane, events in sorted(devices.items()):
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in events
                  if min(e, hi) > max(s, lo)]
        busy = union((s, e) for s, e, _ in inside)
        busy_s.append(total(busy))
        leaf = leaves(inside)
        for s, e, n in leaf:
            key = stable_name(n)
            ops[key] = ops.get(key, 0.0) + (e - s)
            counts[key] = counts.get(key, 0) + 1
        # a collective is either an operation of the line or, when XLA
        # made it asynchronous, a span of the async line between its
        # -start and -done (which on the operation line are a launch and
        # a wait, not compute)
        in_flight = [(max(s, lo), min(e, hi)) for s, e, n
                     in trace.get("async", {}).get(plane, [])
                     if COLLECTIVE.match(n) and min(e, hi) > max(s, lo)]
        coll = union([(s, e) for s, e, n in leaf if COLLECTIVE.match(n)]
                     + in_flight)
        other = union((s, e) for s, e, n in leaf
                      if not COLLECTIVE.match(n))
        coll_s.append(total(coll))
        exposed_s.append(total(subtract(coll, other)))
        if plane == sorted(devices)[0]:
            gaps = subtract([(lo, hi)], busy)
    n_dev = len(devices)
    inner = [s for s in spans if s[2] != "window"]
    by_span: Dict[str, float] = {}
    singles: List[Tuple[str, float]] = []
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        cover = [s for s in inner if s[0] <= mid <= s[1]]
        name = min(cover, key=lambda s: s[1] - s[0])[2] if cover else "none"
        by_span[name] = by_span.get(name, 0.0) + (ge - gs)
        singles.append((name, ge - gs))
    singles.sort(key=lambda x: -x[1])
    idle = [[f"all_gaps_under_{k}", v] for k, v in
            sorted(by_span.items(), key=lambda kv: -kv[1])][:top // 2]
    idle += [[f"one_gap_under_{k}", v] for k, v in singles[:top - len(idle)]]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_s) / n_dev,
        "n_devices": n_dev,
        "ops": ops, "op_counts": counts,
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle,
        "collective_s": sum(coll_s) / n_dev,
        "exposed_collective_s": sum(exposed_s) / n_dev,
    }


def kernel_seconds(reduced: Dict[str, Any], pattern: str) -> Tuple[float, int]:
    """Device seconds and calls of the operations whose stable name
    matches ``pattern`` (summed over devices)."""
    rx = re.compile(pattern)
    keys = [k for k in reduced["ops"] if rx.search(k)]
    return (sum(reduced["ops"][k] for k in keys),
            sum(reduced["op_counts"][k] for k in keys))
