"""bench_compare — the bench-trajectory regression sentinel.

Every round leaves a ``BENCH_*.json`` behind; until now they only
*accumulated*. This tool diffs any two rounds per phase metric with
tolerance bands and **exits non-zero on a regression**, so a capture
script can gate on the trajectory instead of hoping someone reads it.

What counts as comparable: every numeric leaf under each phase of the
round's ``detail`` dict (the orchestrator shape), or of the row itself
(single-phase captures like ``BENCH_HIER_r16.json``). Each leaf's
dotted path is classified by the **direction catalog** below —
throughput-like metrics must not fall, latency-like metrics must not
rise, boolean gates (``token_parity`` etc.) must not flip false;
paths matching neither direction are reported informationally and
never gate (a config echo is not a metric). Noisy wall-clock metrics
get wider built-in bands than counters; ``--tolerance`` overrides the
default band globally.

A phase present in the OLD round but missing (or ``error``-shaped) in
the NEW one is itself a regression: a silently skipped bench is how
trajectories rot. ``--allow-missing`` downgrades that to a warning for
intentionally retired phases.

Usage::

    python tools/bench_compare.py BENCH_r16.json BENCH_r17.json
    python tools/bench_compare.py old.json new.json --tolerance 0.15 \
        --phases serve_attrib,serve_hier --json

Exit codes: 0 = no regressions, 1 = regressions found, 2 = usage /
unreadable input.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

#: direction catalog: (fnmatch pattern over the dotted metric path,
#: "higher" | "lower"). First match wins — order matters (e.g.
#: ``*goodput*`` must classify before a generic ``*_frac`` rule would).
#: Paths matching nothing are informational: reported, never gated.
DIRECTIONS: Tuple[Tuple[str, str], ...] = (
    ("*tokens_per_sec*", "higher"),
    ("*steps_per_sec*", "higher"),
    ("*requests_per_sec*", "higher"),
    ("*tflops*", "higher"),
    # goodput-ledger BUCKETS (seconds lost) must classify before the
    # generic "*goodput*" rule below — their dotted paths live under
    # goodput_drill.* and first-match-wins would invert the gate
    ("*restart_lost*", "lower"),
    ("*replay_catchup*", "lower"),
    ("*stall*", "lower"),
    ("*checkpoint_save*", "lower"),
    ("*goodput*", "higher"),
    ("*knee*", "higher"),
    # overload-control bench (bench.py serve_admission): brownout
    # transitions during the steady A/B pass are a bug, not jitter —
    # the controller must stay silent at 0.4x capacity. The boolean
    # gates (token_parity_armed_vs_off, controller_engaged_spike,
    # balance_ok_*) ride the generic true->false rule; the spike
    # rejection/retry counts are mechanism, not cost, and stay
    # informational on purpose
    ("*steady_transitions*", "lower"),
    ("*speedup*", "higher"),
    ("*accept_rate*", "higher"),
    ("*hit_frac*", "higher"),
    ("*skipped_frac*", "higher"),
    ("*host_gap_hidden_frac*", "higher"),
    ("value", "higher"),
    ("vs_baseline", "higher"),
    ("*overhead*", "lower"),
    ("*exposed*", "lower"),
    ("*closure_err*", "lower"),
    # training observatory (bench.py train_obs): the data-wait share
    # and host skew must not creep up
    ("*data_wait*", "lower"),
    ("*step_time_skew*", "lower"),
    # long-context bench (bench.py serve_longctx): per-chip pool bytes
    # are the capacity lever — they must stay FLAT (or shrink) as the
    # workload's context grows; the per-chip share of the longest chain
    # likewise. Throughput/speedup/TTFT ride the generic rules above.
    ("*kv_pool_bytes*per_chip*", "lower"),
    ("*chain_tokens_per_chip*", "lower"),
    # expert-parallel MoE serving (bench.py serve_moe): per-chip expert
    # stack bytes are the sparse-model capacity lever — flat or
    # shrinking as experts scale; the chunked overlap's EXPOSED a2a
    # fraction must not creep toward 1.0 (1.0 = the chunking hides
    # nothing). Decode tokens/s and the vs-dense ratio ride the
    # generic *tokens_per_sec* rule above.
    ("*expert_bytes*per_chip*", "lower"),
    ("*a2a_exposed_fraction*", "lower"),
    ("*capacity_rps*", "higher"),
    ("*ttft*", "lower"),
    ("*tpot*", "lower"),
    ("*queue_wait*", "lower"),
    ("*latency*", "lower"),
    ("*recovery_s*", "lower"),
    ("*drain_s*", "lower"),
    ("*dispatches_per_token*", "lower"),
    ("*fresh_compiles*", "lower"),
    # repo lint capture (bin/dstpu_lint --json's count): any finding is
    # a regression, zero slack below
    ("*lint_findings*", "lower"),
    ("*_p99*", "lower"),
    ("*_p90*", "lower"),
    ("*_p50*", "lower"),
)

#: built-in tolerance bands: (path pattern, relative tolerance). First
#: match wins; the default band covers everything else. Wall-clock
#: throughputs/latencies on a shared box jitter far more than counters.
BANDS: Tuple[Tuple[str, float], ...] = (
    ("*fresh_compiles*", 0.0),       # a fresh warm-path compile is a bug
    ("*lint_findings*", 0.0),        # the repo lints clean, period
    ("*tokens_per_sec*", 0.20),
    ("*steps_per_sec*", 0.20),
    ("*tflops*", 0.20),
    ("*knee*", 0.25),
    ("*ttft*", 0.30),
    ("*tpot*", 0.30),
    # single-prompt prefill wall clocks on a shared box (serve_longctx)
    ("*prefill_speedup*", 0.25),
    ("*capacity_rps*", 0.25),
    ("*queue_wait*", 0.30),
    ("*recovery_s*", 0.50),
    ("*drain_s*", 0.50),
    # goodput through an injected kill depends on subprocess startup
    # wall clock — band it like the other drill timings
    ("*goodput_frac*", 0.25),
    # spike-pass goodput RATES are wall-clock measurements under a
    # deliberately saturating arrival schedule — band them like the
    # knee sweep; steady brownout transitions get zero slack
    ("*spike_goodput_rps*", 0.25),
    ("*steady_transitions*", 0.0),
    # overlap hiding is a ratio of two wall-clock step latencies on a
    # shared box (serve_moe) — band it like the other timing ratios;
    # expert_bytes gauges are exact counters and keep zero-ish slack
    ("*a2a_exposed_fraction*", 0.30),
    ("*restart_lost*", 0.50),
    ("*replay_catchup*", 0.50),
    ("*checkpoint_save*", 0.50),
)

DEFAULT_TOLERANCE = 0.10

#: metrics whose magnitude never exceeds this are noise-dominated in
#: RELATIVE terms (a closure error drifting 0.0002 -> 0.005 is still
#: far inside every bench's own absolute gate) — they only gate when
#: at least one side clears the floor. ``--min-abs`` overrides.
DEFAULT_MIN_ABS = 0.02

#: detail keys that are configuration echoes, not metrics.
#: component_deltas_s is the injection experiments' per-component
#: diagnostic breakdown — its magnitudes scale with the injection KNOB
#: (DSTPU_ATTRIB_INJECT_MS / DSTPU_TRAINOBS_STALL_MS), so gating them
#: would flag deliberate knob changes; the boolean localization gates
#: (localized_to_*) still gate.
#: "mix" is the serve_disagg workload echo; "exposed_wait_s" is that
#: bench's diagnostic histogram summary — its count/sum scale with the
#: request knob, and the gated number is handoff_exposed_frac
_SKIP_SUBTREES = ("serve_config", "train_config", "config", "probe",
                  "detail_flags", "schedule", "component_deltas_s",
                  "mix", "exposed_wait_s")


def _direction(path: str) -> Optional[str]:
    leaf = path.lower()
    for pat, d in DIRECTIONS:
        if fnmatch.fnmatch(leaf, pat) or fnmatch.fnmatch(
                leaf.rsplit(".", 1)[-1], pat):
            return d
    return None


def _band(path: str, default: float) -> float:
    leaf = path.lower()
    for pat, tol in BANDS:
        if fnmatch.fnmatch(leaf, pat) or fnmatch.fnmatch(
                leaf.rsplit(".", 1)[-1], pat):
            return tol
    return default


def _flatten(node: Any, prefix: str = "",
             out: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Numeric/bool leaves of a phase row keyed by dotted path; config
    echoes and error strings are skipped."""
    if out is None:
        out = {}
    if isinstance(node, dict):
        for k, v in node.items():
            if k in _SKIP_SUBTREES:
                continue
            _flatten(v, f"{prefix}{k}.", out)
    elif isinstance(node, bool):
        out[prefix[:-1]] = node
    elif isinstance(node, (int, float)) and node == node:  # not NaN
        out[prefix[:-1]] = float(node)
    return out


def _last_json_line(text: str) -> Optional[Dict[str, Any]]:
    for ln in reversed(text.splitlines()):
        ln = ln.strip()
        if ln.startswith("{") and ln.endswith("}"):
            try:
                obj = json.loads(ln)
            except ValueError:
                continue
            if isinstance(obj, dict):
                return obj
    return None


def load_round(path: str) -> Dict[str, Any]:
    """A round capture, whichever shape the round left behind:

    * the orchestrator's (or a single phase's) stdout capture — the
      LAST parseable JSON object line wins (progress rows print above
      the final row);
    * a driver wrapper (``{"n": .., "rc": .., "tail": "..."}``) whose
      stdout tail embeds the bench row — the row is extracted from
      ``tail``;
    * a bare JSON document.
    """
    with open(path, encoding="utf-8") as f:
        text = f.read()
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict):
        tail = obj.get("tail")
        if isinstance(tail, str):
            inner = _last_json_line(tail)
            if inner is not None:
                return inner
        return obj
    inner = _last_json_line(text)
    if inner is None:
        raise ValueError(f"{path}: no JSON row found")
    return inner


def phases_of(row: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """{phase name: flattened metrics}. An orchestrator row explodes its
    ``detail`` per phase (headline value/vs_baseline under ``headline``);
    a bare single-phase row becomes one pseudo-phase."""
    detail = row.get("detail")
    if not isinstance(detail, dict):
        return {"(single)": _flatten(row)}
    out: Dict[str, Dict[str, Any]] = {}
    headline = {k: v for k, v in row.items() if k != "detail"}
    out["headline"] = _flatten(headline)
    loose: Dict[str, Any] = {}
    for k, v in detail.items():
        if k in _SKIP_SUBTREES:
            continue
        if isinstance(v, dict):
            if v.get("error"):
                out[k] = {"__error__": str(v["error"])}
            else:
                out[k] = _flatten(v)
        else:
            loose[k] = v
    if loose:
        out["headline"].update(_flatten(loose))
    return out


def compare_rounds(old: Dict[str, Any], new: Dict[str, Any],
                   tolerance: float = DEFAULT_TOLERANCE,
                   phases: Optional[List[str]] = None,
                   allow_missing: bool = False,
                   min_abs: float = DEFAULT_MIN_ABS) -> Dict[str, Any]:
    """Diff two round rows. Returns a result dict with ``regressions``,
    ``improvements``, ``missing_phases``, ``info`` (direction-less
    drifts) and ``ok`` — the sentinel verdict the CLI exits on."""
    po, pn = phases_of(old), phases_of(new)
    wanted = set(phases) if phases else None
    regressions: List[Dict[str, Any]] = []
    improvements: List[Dict[str, Any]] = []
    info: List[Dict[str, Any]] = []
    missing: List[str] = []
    for phase, old_m in sorted(po.items()):
        if wanted is not None and phase not in wanted:
            continue
        if "__error__" in old_m:
            continue                   # old round already broken there
        new_m = pn.get(phase)
        if new_m is None or "__error__" in new_m:
            missing.append(phase)
            continue
        for path, ov in sorted(old_m.items()):
            nv = new_m.get(path)
            if nv is None:
                continue               # metric retired: not a gate
            full = f"{phase}.{path}"
            if isinstance(ov, bool) or isinstance(nv, bool):
                if bool(ov) and not bool(nv):
                    regressions.append({
                        "metric": full, "old": ov, "new": nv,
                        "kind": "gate_flipped_false"})
                elif not bool(ov) and bool(nv):
                    improvements.append({
                        "metric": full, "old": ov, "new": nv,
                        "kind": "gate_now_true"})
                continue
            d = _direction(path)
            scale = max(abs(ov), abs(nv))
            if scale <= 0.0 or (scale < min_abs
                                and _band(path, tolerance) > 0.0):
                # both sides in the noise floor: relative deltas are
                # meaningless (0.0002 -> 0.005 closure error reads as
                # "25x worse"). Zero-band metrics (fresh compiles)
                # still gate: 0 -> 1 is a real event, not noise.
                continue
            delta = (nv - ov) / scale
            tol = _band(path, tolerance)
            rec = {"metric": full, "old": ov, "new": nv,
                   "delta_frac": round(delta, 4), "tolerance": tol}
            if d is None:
                if abs(delta) > tol:
                    info.append(rec)
                continue
            worse = -delta if d == "higher" else delta
            if worse > tol:
                regressions.append({**rec, "direction": d})
            elif -worse > tol:
                improvements.append({**rec, "direction": d})
    ok = not regressions and (allow_missing or not missing)
    return {
        "ok": ok,
        "regressions": regressions,
        "improvements": improvements,
        "missing_phases": missing,
        "info": info,
        "phases_compared": sorted(
            p for p in po if p in pn
            and (wanted is None or p in wanted)
            and "__error__" not in po[p]),
    }


def _fmt(rec: Dict[str, Any]) -> str:
    if "delta_frac" in rec:
        return (f"{rec['metric']}: {rec['old']:g} -> {rec['new']:g} "
                f"({rec['delta_frac']:+.1%}, band ±{rec['tolerance']:.0%})")
    return f"{rec['metric']}: {rec['old']} -> {rec['new']} ({rec['kind']})"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_compare",
        description="diff two BENCH_*.json rounds per phase metric; "
                    "exit non-zero on regression (docs/observability.md "
                    "'Regression sentinel')")
    ap.add_argument("old", help="earlier round capture")
    ap.add_argument("new", help="later round capture")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help=f"default relative band (built-in per-metric "
                         f"bands still apply; default "
                         f"{DEFAULT_TOLERANCE})")
    ap.add_argument("--phases", default=None,
                    help="comma-separated phase allowlist")
    ap.add_argument("--min-abs", type=float, default=DEFAULT_MIN_ABS,
                    help=f"noise floor: metrics whose magnitude stays "
                         f"below this on both sides never gate "
                         f"(default {DEFAULT_MIN_ABS}; zero-band "
                         f"metrics still gate)")
    ap.add_argument("--allow-missing", action="store_true",
                    help="a phase missing from the new round warns "
                         "instead of gating")
    ap.add_argument("--json", action="store_true",
                    help="emit the structured result instead of text")
    args = ap.parse_args(argv)
    try:
        old = load_round(args.old)
        new = load_round(args.new)
    except (OSError, ValueError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2
    res = compare_rounds(
        old, new, tolerance=args.tolerance,
        phases=args.phases.split(",") if args.phases else None,
        allow_missing=args.allow_missing, min_abs=args.min_abs)
    if args.json:
        print(json.dumps(res, indent=1))
    else:
        print(f"bench_compare {args.old} -> {args.new}: "
              f"{len(res['phases_compared'])} phases compared")
        for rec in res["regressions"]:
            print(f"  REGRESSION  {_fmt(rec)}")
        for p in res["missing_phases"]:
            tag = "warning " if args.allow_missing else "REGRESSION"
            print(f"  {tag}  phase {p}: present in old round, missing/"
                  f"errored in new")
        for rec in res["improvements"]:
            print(f"  improved    {_fmt(rec)}")
        for rec in res["info"]:
            print(f"  info        {_fmt(rec)} (no direction — not gated)")
        print("OK" if res["ok"] else "FAIL: bench trajectory regressed")
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
