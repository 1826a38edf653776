"""Per-layer metrics: each is a reader file, ``layer_metrics/<name>.json``,
that names what it reads (a series, a counter or kernels of the trace) and
one reducer of the fixed set below. A reader that finds nothing to read
returns nothing, and the harness leaves the metric out of the line.

Observations are addressed by dotted keys into the job's ``obs`` (with
``trace.*`` from ``reduce_trace.reduce``, ``setup.*`` from the compile
clock, ``memory_peak_bytes`` and ``peak.*`` from ``peaks.json``).
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from . import kernel_cost, reduce_trace
from .common import percentile


def lookup(obs: Dict[str, Any], key: str) -> Any:
    cur: Any = obs
    for part in key.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _sum(obs, keys) -> Optional[float]:
    vals = [lookup(obs, k) for k in keys]
    return None if any(v is None for v in vals) else float(sum(vals))


def r_value(spec, obs):
    v = lookup(obs, spec["key"])
    return None if v is None else float(v) * spec.get("scale", 1.0)


def r_percentile(spec, obs):
    series = lookup(obs, spec["series"])
    if not series:
        return None
    return percentile(series, spec["q"]) * spec.get("scale", 1.0)


def r_share_true(spec, obs):
    series = lookup(obs, spec["series"])
    if not series:
        return None
    return spec.get("scale", 1.0) * sum(bool(v) for v in series) / len(series)


def r_ratio(spec, obs):
    num, den = _sum(obs, spec["num"]), _sum(obs, spec["den"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den


def r_roofline(spec, obs):
    """Least time the chip could take for the work of the named kernels,
    over the device time they took in the trace. Each kernel entry has a
    ``pattern`` over the trace's stable operation names and one or more
    ``costs``: a function of ``kernel_cost`` with its arguments taken from
    the observations, counted ``calls_share`` times per call seen in the
    trace (per device), or a number of times taken from the observations
    (``per``)."""
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak:
        return None
    least, took = 0.0, 0.0
    for k in spec["kernels"]:
        # a pattern may name sizes of the run: {attention.q_heads}
        pattern = re.sub(r"\{([\w.]+)\}",
                         lambda m: str(lookup(obs, m.group(1))), k["pattern"])
        seconds, calls = reduce_trace.kernel_seconds(trace, pattern)
        if not calls:
            return None
        took += seconds / trace["n_devices"]
        for c in k["costs"]:
            args = {a: lookup(obs, key) for a, key in c["args"].items()}
            times = lookup(obs, c["per"]) if "per" in c \
                else c["calls_share"] * calls / trace["n_devices"]
            if times is None or any(v is None for v in args.values()):
                return None
            cost = getattr(kernel_cost, c["cost"])(**args,
                                                   **c.get("fixed", {}))
            least += times * kernel_cost.roofline_seconds(cost,
                                                          peak)["seconds"]
    return 100.0 * least / took if took else None


REDUCERS = {"value": r_value, "percentile": r_percentile,
            "share_true": r_share_true, "ratio": r_ratio,
            "roofline": r_roofline}


def read(spec: Dict[str, Any], obs: Dict[str, Any]) -> Optional[float]:
    return REDUCERS[spec["reducer"]](spec, obs)
