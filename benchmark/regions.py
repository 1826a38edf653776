"""Device seconds by region of the program, from the same ``.xplane.pb``
that ``reduce_trace`` reads.

The program opens ``jax.named_scope("rg.<region>")`` around the parts of
its step programs (``deepspeed_tpu/telemetry/trace.py``: ``REGIONS``,
``region``), so every operation traced inside carries ``rg.<region>`` in
its ``op_name``. The profiler writes that path beside each device
operation: every ``XEventMetadata`` of a device plane holds, as stats of
the METADATA, ``tf_op`` (the ``op_name``: ``jit(f)/rg.norm/rsqrt``),
``source`` (file:line), ``hlo_category``, ``program_id`` and XLA's own
``flops`` and ``bytes_accessed``. ``jax.profiler.ProfileData`` hands out
an event's OWN stats only, so this module reads those stats from the raw
protobuf, by the wire walker ``tests/record_serve_spans.py`` already has
(no ``xplane_pb2`` is importable here short of importing TensorFlow), and
joins them to ``reduce_trace.load``'s events by (operation name, program).
A profile whose metadata was cut (``record_serve_spans.strip``) or that
comes from a program without regions gives nothing to read.

Over ``reduce_trace``'s own window and its own leaf events an operation
falls in exactly one bin:

* ``regions.<region>``: its ``tf_op`` has a marked component; the
  INNERMOST one is its region. ``regions_by_pass.<region>.<fwd|bwd|remat>``
  splits by the pass read from the path (``rematted_computation`` is a
  recompute, else ``transpose(jvp`` the backward), and
  ``regions_by_program.<program>.<region>`` by the program run (the
  ``XLA Modules`` event) that holds the operation. Once a trace shows any
  region, every name of the program's vocabulary is there, at 0.0 where
  the compiler left it nothing of its own, so a sum over regions reads;
* ``unscoped.<hlo_category>``: a ``tf_op`` with no marked component;
  ``unscoped_top`` lists the largest by ``tf_op`` and ``source``;
* ``xla_inserted.<hlo_category>``: no ``tf_op`` at all (XLA's own copies
  and converts), or one half of an asynchronous pair (``copy-start`` /
  ``copy-done``, ``slice-start`` / ``-done``, a collective's): no
  primitive of the program is one, and where XLA hoists a weight prefetch
  into a loop the pair takes the ``while``'s ``op_name`` with it.

``region_flops`` / ``region_bytes`` are XLA's ``flops`` /
``bytes_accessed`` of each operation times its runs: XLA's estimate (a
custom call counts 0), a yardstick for the anonymous fusions and not for
the Pallas kernels, which keep their cost files. ``region_named_share`` is
the regions' seconds over the seconds of the program's own operations
(every bin but ``xla_inserted``). Seconds, flops and bytes are a
device's, as ``busy_s`` is: summed over devices, over ``n_devices``; so
``n_devices x (regions + unscoped + xla_inserted)`` equals the sum of
``reduce_trace.reduce``'s ``ops`` (closure). ``owners`` gives, for each of ``reduce``'s ``top``
``device_ops`` names, its seconds by bin, in ``device_ops``' own unit
(summed over devices): who owns ``fusion-f32_128``.

Known limit: a fusion carries ONE ``op_name``, its root's, so a fusion
XLA formed across a region's boundary counts whole on one side.

``run.py::read_trace`` calls :func:`from_trace` on every traced run since
PR 54 (``obs["trace"].update(...)``: the keys above are addressed as
``trace.regions.<region>`` and ``trace.region_named_share`` by the
``*_share`` readers over regions in ``layer_metrics/``); an untraced run
never imports this module. For any profile taken with
``jax.profiler.trace`` around a live engine, or a saved one again:

    python3 -m benchmark.regions .bench_trace/serve-chat-steady
"""

from __future__ import annotations

import bisect
import json
import re
import struct
import sys
from typing import Any, Dict, List, Optional, Tuple

from . import reduce_trace as rt
from .tests.record_serve_spans import _fields

MARK = "rg."
_MARKED = re.compile(re.escape(MARK) + r"(\w+)")
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")
#: the two halves of an asynchronous copy, slice or collective: XLA's own
_ASYNC = re.compile(r"-(start|done)$")
#: the stats of an operation's metadata this module reads
_WANTED = ("tf_op", "source", "hlo_category", "program_id", "flops",
           "bytes_accessed")

Meta = Dict[str, Any]


def _stat_value(fields: Dict[int, Any], names: Dict[int, str]) -> Any:
    """An ``XStat``'s value: double 2, uint64 3, int64 4, str 5, bytes 6,
    ref 7 (the name of another stat's metadata)."""
    if 5 in fields:
        return fields[5].decode("utf-8", "replace")
    if 7 in fields:
        return names.get(fields[7], "")
    if 2 in fields:
        return struct.unpack("<d", fields[2])[0]
    for f in (3, 4):
        if f in fields:
            return fields[f]
    return None


def _message(b: bytes) -> Dict[int, Any]:
    """One protobuf message as {field number: (last) value}."""
    return {f: v for f, _, v in _fields(b)}


def op_metadata(path: str) -> Dict[str, Dict[str, List[Meta]]]:
    """{device plane: {operation name: [its metadata's stats, one dict a
    program that holds an operation of that name]}} out of the raw
    ``.xplane.pb``. Field numbers are tsl's ``xplane.proto``:
    XSpace.planes 1; XPlane name 2, event_metadata 4 and stat_metadata 5
    (maps: key 1, value 2); XEventMetadata name 2, stats 5;
    XStatMetadata id 1, name 2; XStat metadata_id 1."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, List[Meta]]] = {}
    for f1, _, plane in _fields(space):
        if f1 != 1:
            continue
        parts = list(_fields(plane))
        name = next((v for f, _, v in parts if f == 2), b"").decode()
        if not rt.DEVICE_PLANE.match(name):
            continue
        stat_names: Dict[int, str] = {}
        for f, _, entry in parts:
            if f == 5:
                meta = _message(_message(entry)[2])
                stat_names[meta[1]] = meta.get(2, b"").decode()
        wanted = {i for i, n in stat_names.items() if n in _WANTED}
        ops: Dict[str, List[Meta]] = {}
        for f, _, entry in parts:
            if f != 4:
                continue
            info: Meta = {}
            op_name = b""
            for a, _, v in _fields(_message(entry)[2]):
                if a == 2:
                    op_name = v
                elif a == 5:
                    stat = _message(v)
                    if stat.get(1) in wanted:
                        info[stat_names[stat[1]]] = _stat_value(
                            stat, stat_names)
            if info:
                ops.setdefault(op_name.decode("utf-8", "replace"),
                               []).append(info)
        out[name] = ops
    return out


def _pass_of(tf_op: str) -> str:
    if "rematted_computation" in tf_op:
        return "remat"
    return "bwd" if "transpose(jvp" in tf_op else "fwd"


def _vocabulary() -> Tuple[str, ...]:
    """The program's closed table of regions; none from a program that
    has no such table (a parent of the PR that brought it)."""
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return ()
    return tuple(getattr(trace, "REGIONS", ()))


def _add(into: Dict[str, float], key: str, value: float) -> None:
    into[key] = into.get(key, 0.0) + value


def from_trace(trace: Dict[str, Any], path: str, top: int = 10,
               window: Optional[rt.Interval] = None) -> Dict[str, Any]:
    """The keys of the module's docstring, for ``obs["trace"]``.
    ``trace`` is ``reduce_trace.load(path)``; the window defaults as
    ``reduce_trace.reduce``'s does."""
    devices, spans = trace["devices"], trace["spans"]
    if not devices:
        return {}
    if window is None:
        outer = [s for s in spans if s[2] == "window"] or spans
        if outer:
            window = (min(s[0] for s in outer), max(s[1] for s in outer))
        else:
            every = [e for evs in devices.values() for e in evs]
            window = (min(e[0] for e in every), max(e[1] for e in every))
    lo, hi = window
    metadata = op_metadata(path)
    if not any(metadata.values()):
        return {}           # a profile cut of its operations' metadata
    n_dev = len(devices)

    regions: Dict[str, float] = {}
    by_pass: Dict[str, Dict[str, float]] = {}
    by_program: Dict[str, Dict[str, float]] = {}
    flops: Dict[str, float] = {}
    nbytes: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    inserted: Dict[str, float] = {}
    loose: Dict[Tuple[str, str], float] = {}
    by_op: Dict[str, Dict[str, float]] = {}
    with_tf_op = 0.0
    for plane, events in sorted(devices.items()):
        ops = metadata.get(plane, {})
        runs = sorted(trace.get("modules", {}).get(plane, []))
        starts = [r[0] for r in runs]
        # a run's (program, program id): ``jit_step(1234)``
        programs = [(m.group(1), int(m.group(2))) if m else (r[2], None)
                    for r in runs for m in (_PROGRAM.match(r[2]),)]
        # (operation name, program id) -> (bin, region, pass, category,
        # flops, bytes, tf_op, source): one classification an operation
        known: Dict[Tuple[str, Optional[int]], Tuple] = {}
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in events
                  if min(e, hi) > max(s, lo)]
        for s, e, n in rt.leaves(inside):
            i = bisect.bisect_right(starts, s) - 1
            program, pid = programs[i] if i >= 0 and runs[i][1] >= e \
                else ("none", None)
            what = known.get((n, pid))
            if what is None:
                found = ops.get(n, [])
                info = next((x for x in found
                             if x.get("program_id") == pid),
                            found[0] if found else {})
                tf_op = info.get("tf_op") or ""
                marked = _MARKED.findall(tf_op)
                category = info.get("hlo_category") or "unknown"
                what = known[(n, pid)] = (
                    "xla_inserted" if not tf_op or _ASYNC.search(category)
                    else "region" if marked else "unscoped",
                    marked[-1] if marked else None, _pass_of(tf_op),
                    category,
                    float(info.get("flops") or 0),
                    float(info.get("bytes_accessed") or 0), tf_op,
                    info.get("source") or "")
            kind, region, pas, category, fl, by, tf_op, source = what
            dt = e - s
            if kind == "region":
                _add(regions, region, dt)
                _add(by_pass.setdefault(region, {}), pas, dt)
                _add(by_program.setdefault(program, {}), region, dt)
                _add(flops, region, fl)
                _add(nbytes, region, by)
                owner = region
            elif kind == "unscoped":
                _add(unscoped, category, dt)
                _add(loose, (tf_op, source), dt)
                owner = "unscoped"
            else:
                _add(inserted, category, dt)
                owner = "xla_inserted"
            if kind != "xla_inserted":
                with_tf_op += dt            # the program's own operations
            _add(by_op.setdefault(rt.stable_name(n), {}), owner, dt)

    def per_device(d):
        return {k: (per_device(v) if isinstance(v, dict) else v / n_dev)
                for k, v in d.items()}
    out: Dict[str, Any] = {
        "unscoped": per_device(unscoped),
        "xla_inserted": per_device(inserted),
        "unscoped_top": [[k[0][-120:], k[1], v / n_dev] for k, v in sorted(
            loose.items(), key=lambda kv: -kv[1])[:top]],
        "owners": {name: by_op[name] for name in sorted(
            by_op, key=lambda k: -sum(by_op[k].values()))[:top]},
    }
    if regions:
        for name in _vocabulary():
            regions.setdefault(name, 0.0)
        out.update(
            regions=per_device(regions), regions_by_pass=per_device(by_pass),
            regions_by_program=per_device(by_program),
            region_flops=per_device(flops), region_bytes=per_device(nbytes),
            region_named_share=sum(regions.values()) / with_tf_op)
    return out


def read(path: str, top: int = 10) -> Dict[str, Any]:
    """One saved profile: the regions' keys beside the two sums of
    ``reduce_trace.reduce`` they close against."""
    trace = rt.load(path)
    reduced = rt.reduce(trace, top)
    out = from_trace(trace, path, top)
    n_dev = reduced["n_devices"]
    binned = sum(sum(out.get(k, {}).values())
                 for k in ("regions", "unscoped", "xla_inserted"))
    out.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"],
               n_devices=n_dev, device_ops=reduced["device_ops"],
               closure_s=sum(reduced["ops"].values()) - n_dev * binned)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    """Print one traced run's device seconds by region as a line of JSON:
    the argument is a ``.xplane.pb`` or a directory that holds one."""
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(main.__doc__, file=sys.stderr)
        return 2
    path = argv[0] if argv[0].endswith(".pb") else rt.find_xplane(argv[0])
    print(json.dumps(read(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
