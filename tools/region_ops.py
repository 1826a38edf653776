"""Device seconds of ONE region of a saved profile, operation by operation.

    python3 tools/region_ops.py .bench_trace/<cell> <region> [top]

``benchmark/regions.py`` splits a traced run's device time by region of
the step programs; this lists what a region (``moe_route``, ``attn_proj``,
..; ``unscoped`` / ``xla_inserted`` for what carries none) is made of: a
line an operation and pass with its milliseconds over the traced window, its
count, its stable device name and the tail of its ``op_name``. Run it
where the profile is (the chip's machine: a profile is not copied back)
and write the output under ``chiprun_out/``. PERF.md section 5's reading
of the sparse train cell's ``moe_route`` is this script's (PR 64)."""

import collections
import re
import sys

sys.path.insert(0, ".")
from benchmark import reduce_trace as rt  # noqa: E402
from benchmark import regions as rg  # noqa: E402


def main(argv):
    path = argv[0] if argv[0].endswith(".pb") else rt.find_xplane(argv[0])
    region, top = argv[1], int(argv[2]) if len(argv) > 2 else 60
    trace, meta = rt.load(path), rg.op_metadata(path)
    outer = [s for s in trace["spans"] if s[2] == "window"] or trace["spans"]
    lo, hi = min(s[0] for s in outer), max(s[1] for s in outer)
    seconds, count = collections.Counter(), collections.Counter()
    for plane, events in trace["devices"].items():
        ops = meta.get(plane, {})
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in events
                  if min(e, hi) > max(s, lo)]
        for s, e, n in rt.leaves(inside):
            found = ops.get(n, [])
            tf_op = (found[0].get("tf_op") if found else "") or ""
            marked = rg._MARKED.findall(tf_op)
            owner = marked[-1] if marked else \
                "unscoped" if tf_op else "xla_inserted"
            if owner != region:
                continue
            key = (rg._pass_of(tf_op), rt.stable_name(n),
                   re.sub(r"^.*rg\.%s/" % region, "", tf_op)[-90:])
            seconds[key] += e - s
            count[key] += 1
    print(f"{region}: {sum(seconds.values()) * 1e3:.6f} ms")
    for key, v in seconds.most_common(top):
        print(f"{v * 1e3:10.6f} x{count[key]:<4d} {key[0]:5s} {key[1][:44]:44s} "
              f"{key[2]}")


if __name__ == "__main__":
    main(sys.argv[1:])
