"""Per-layer metrics: each is a reader file, ``layer_metrics/<name>.json``,
that names what it reads (a series, a counter or kernels of the trace) and
one reducer of the fixed set below. A reader that finds nothing to read
returns nothing, and the harness leaves the metric out of the line.

Observations are addressed by dotted keys into the job's ``obs`` (with
``trace.*`` from ``reduce_trace.reduce``, ``setup.*`` from the compile
clock, ``memory_peak_bytes``, ``peak.*`` from ``peaks.json`` and ``cell.*``,
the cell's own file). A key or a kernel's pattern may hold ``{dotted.key}``
placeholders, resolved in the observations first: a family's reader names
``trace.ops.{cell.kernels.grouped_ffn.op}`` and every cell of its list
states its kernel's traced name and sizes in its file's ``kernels`` block.
"""

from __future__ import annotations

import importlib
import re
from typing import Any, Callable, Dict, Optional

from . import kernel_cost, reduce_trace
from .common import percentile

_COST_MODULE = re.compile(r"^[a-z0-9_]*_cost$")
# a key or a pattern may name what the run or the cell's file states:
# {attention.q_heads}, {cell.kernels.grouped_ffn.op}
_PLACEHOLDER = re.compile(r"\{([\w.]+)\}")


def _walk(obs: Dict[str, Any], key: str) -> Any:
    cur: Any = obs
    for part in key.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def resolve(obs: Dict[str, Any], text: str) -> Optional[str]:
    """``text`` with every ``{dotted.key}`` replaced by what the
    observations hold under it; nothing where one of them holds nothing
    (the reader then has nothing to read)."""
    found = {key: _walk(obs, key) for key in _PLACEHOLDER.findall(text)}
    if any(v is None for v in found.values()):
        return None
    return _PLACEHOLDER.sub(lambda m: str(found[m.group(1)]), text)


def lookup(obs: Dict[str, Any], key: str) -> Any:
    """What the observations hold under a dotted key, its placeholders
    resolved first: wherever a reader names a key it may name the
    cell's."""
    key = resolve(obs, key)
    return None if key is None else _walk(obs, key)


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


def cost_function(name: str) -> Callable[..., Dict[str, float]]:
    """The cost function a reader file names: ``<function>`` of
    ``kernel_cost``, or ``<module>.<function>`` of any
    ``benchmark/<module>.py`` whose name ends in ``_cost``, so a kernel's
    yardstick comes with its own file. A name that is not there raises:
    a roofline with no yardstick is a fault of the reader file, not a
    metric with nothing to read."""
    module, _, function = name.rpartition(".")
    if module and not _COST_MODULE.match(module):
        raise KeyError(f"cost {name!r}: {module!r} is not a *_cost module "
                       f"of benchmark/")
    try:
        mod = importlib.import_module(
            f"{__package__}.{module or 'kernel_cost'}")
        return getattr(mod, function)
    except (ImportError, AttributeError) as e:
        raise KeyError(f"no cost function {name!r} under benchmark/: {e}")


def r_roofline(spec, obs):
    """Least time the chip could take for the work of the named kernels,
    over the device time they took in the trace. Each kernel entry has a
    ``pattern`` over the trace's stable operation names and one or more
    ``costs``: a function found by :func:`cost_function` with its
    arguments taken from the observations, counted ``calls_share`` times
    per call seen in the trace (per device), or ``per`` times: a number,
    or a key of the observations (a cost that is linear in its arguments
    is then ONE evaluation over the traced stretch's own totals, ``per``
    1)."""
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak:
        return None
    least, took = 0.0, 0.0
    for k in spec["kernels"]:
        pattern = resolve(obs, k["pattern"])
        if pattern is None:
            return None
        seconds, calls = reduce_trace.kernel_seconds(trace, pattern)
        if not calls:
            return None
        took += seconds / trace["n_devices"]
        for c in k["costs"]:
            args = {a: lookup(obs, key) for a, key in c["args"].items()}
            per = c.get("per")
            if per is None:
                times = c["calls_share"] * calls / trace["n_devices"]
            else:
                times = lookup(obs, per) if isinstance(per, str) else per
            if times is None or any(v is None for v in args.values()):
                return None
            cost = cost_function(c["cost"])(**args, **c.get("fixed", {}))
            least += times * kernel_cost.roofline_seconds(cost,
                                                          peak)["seconds"]
    return 100.0 * least / took if took and least else None


def keys_of(spec: Dict[str, Any]) -> list:
    """Every key of the observations a reader file names: what a job has
    to export for the reader to find something to read."""
    keys = [spec[k] for k in ("key", "series") if k in spec]
    keys += list(spec.get("num", ())) + list(spec.get("den", ()))
    named = []
    for k in spec.get("kernels", ()):
        named += _PLACEHOLDER.findall(k["pattern"])
        for c in k["costs"]:
            keys += list(c["args"].values())
            if isinstance(c.get("per"), str):
                keys.append(c["per"])
    # a key with placeholders is only known once they resolve: what the
    # job has to export is the placeholders' own keys
    for key in keys:
        named += _PLACEHOLDER.findall(key) or [key]
    return named


REDUCERS = {"value": r_value, "percentile": r_percentile,
            "share_true": r_share_true, "ratio": r_ratio,
            "roofline": r_roofline}


def read(spec: Dict[str, Any], obs: Dict[str, Any]) -> Optional[float]:
    return REDUCERS[spec["reducer"]](spec, obs)
