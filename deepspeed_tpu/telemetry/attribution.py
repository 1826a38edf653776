"""Step-time attribution — where every millisecond of a serve step goes.

The observatory can say a step was slow; this module says WHY. The
serve observer (telemetry/serve.py) already brackets the pipeline's
host-side boundaries; with ``DSTPU_ATTRIB=1`` (default) it additionally
closes the books on every committed step, so a step's wall clock
decomposes into:

  * ``plan``            — scheduler + staged-buffer fill
    (``serve_plan_s``);
  * ``dispatch``        — compiled-step enqueue (``serve_dispatch_s``;
    fused decode/verify dispatches land here too);
  * ``device_execute``  — the exposed device wait at the commit's
    blocking readback (``serve_commit_block_s``): device time the
    pipeline failed to hide under host work;
  * ``commit_apply``    — host-side commit application after the
    readback: token bookkeeping, journal appends, rollbacks, deferred
    flushes (``serve_commit_apply_s``);
  * ``host_gap``        — the RESIDUAL: loop time inside the serve loop
    but outside every bracket (resume scans, deadline sweeps, ring
    bookkeeping, GC pauses — ``serve_host_gap_s``). This is the
    component a "mysteriously slow" step usually hides in, which is
    why it is measured as the closure of the sum rather than by
    enumerating its causes;
  * ``promote_wait``    — the hierarchical-KV promotion dispatch wait
    the ADMISSION path pays (``prefix_promote_wait_s``; put()-side, so
    it is reported as its own component, not part of the step sum).

By construction ``plan + dispatch + device_execute + commit_apply +
host_gap`` equals the serve loop's wall clock (each step's wall is the
interval between commit boundaries; the loop exit closes the tail), so
the components sum to externally measured step wall-clock within
tolerance — ``tests/unit/test_attribution.py`` gates exactly that.
Everything is
host-side ``perf_counter`` arithmetic at existing boundaries: traced
programs gain 0 host callbacks and the warm path 0 fresh compiles with
attribution on (same gates as the PR 8 observer).

The **audited-collective share** rides along without any device timer:
the program auditor's trip-weighted reports give the steady decode
program's exact per-step collective hop count (ring-decomposed
schedules included) and — new here — its trip-weighted ``dot_general``
count, so :func:`comm_share` derives an op-level comm-vs-compute split
of ``device_execute`` straight from the compiled schedule. It is a
schedule-derived share (ops, not seconds): honest about what host-side
observation can know, and exactly the per-knob evidence the autotuning
item needs (a schedule with 4x the hops at the same device_execute is
hiding its comm; one with rising device_execute AND rising hop share is
comm-bound).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

#: component -> the histogram whose SUM carries its seconds. Order is
#: the attribution bar's render order (dstpu_top); the first five are
#: the step-wall partition, promote_wait is admission-side.
ATTRIBUTION_COMPONENTS = (
    ("plan", "serve_plan_s"),
    ("dispatch", "serve_dispatch_s"),
    ("device_execute", "serve_commit_block_s"),
    ("commit_apply", "serve_commit_apply_s"),
    ("host_gap", "serve_host_gap_s"),
    ("promote_wait", "prefix_promote_wait_s"),
)

#: the components that partition one committed step's wall clock
STEP_WALL_COMPONENTS = ("plan", "dispatch", "device_execute",
                        "commit_apply", "host_gap")

#: the TRAIN-side partition (telemetry/train.py,
#: docs/observability.md "Training observatory"): one committed
#: train_batch's wall clock — the interval between step-exit
#: boundaries — decomposes into these six, host_gap again the closure
#: of the sum. data_wait is the between-step span (the caller's data
#: fetch), checkpoint saves between steps ride commit_apply.
TRAIN_ATTRIBUTION_COMPONENTS = (
    ("data_wait", "train_data_wait_s"),
    ("stage", "train_stage_s"),
    ("dispatch", "train_dispatch_s"),
    ("device_execute", "train_device_execute_s"),
    ("commit_apply", "train_commit_apply_s"),
    ("host_gap", "train_host_gap_s"),
)

TRAIN_STEP_WALL_COMPONENTS = tuple(c for c, _ in
                                   TRAIN_ATTRIBUTION_COMPONENTS)

TRAIN_WALL_HIST = "train_step_wall_s"


def _hist_sums(snap: Mapping[str, Any]) -> Dict[str, float]:
    """{histogram name: sum seconds} from a registry snapshot (the
    ``snapshot()`` dict or an exported JSON blob)."""
    hists = snap.get("histograms", {})
    out: Dict[str, float] = {}
    for key, s in hists.items():
        out[key.split("{", 1)[0]] = float(s.get("sum", 0.0))
    return out


def component_totals(snap: Mapping[str, Any],
                     prev: Optional[Mapping[str, Any]] = None,
                     components: Any = ATTRIBUTION_COMPONENTS
                     ) -> Dict[str, float]:
    """Per-component attributed seconds from a snapshot — deltas against
    ``prev`` when given (the measured-window discipline every bench
    sibling uses: warm-up must not pollute the gated numbers).
    ``components`` selects the partition (serve default;
    :data:`TRAIN_ATTRIBUTION_COMPONENTS` for the train observer)."""
    cur = _hist_sums(snap)
    old = _hist_sums(prev) if prev is not None else {}
    return {comp: max(0.0, cur.get(h, 0.0) - old.get(h, 0.0))
            for comp, h in components}


def step_wall_total(snap: Mapping[str, Any],
                    prev: Optional[Mapping[str, Any]] = None,
                    wall_hist: str = "serve_step_wall_s") -> float:
    """Total step wall-clock seconds the observer accounted
    (``serve_step_wall_s`` / ``train_step_wall_s`` sum, optionally
    delta'd)."""
    cur = _hist_sums(snap).get(wall_hist, 0.0)
    old = _hist_sums(prev).get(wall_hist, 0.0) \
        if prev is not None else 0.0
    return max(0.0, cur - old)


def attribution_report(snap: Mapping[str, Any],
                       prev: Optional[Mapping[str, Any]] = None,
                       components: Any = ATTRIBUTION_COMPONENTS,
                       wall_components: Any = STEP_WALL_COMPONENTS,
                       wall_hist: str = "serve_step_wall_s"
                       ) -> Dict[str, Any]:
    """The attribution summary over a snapshot (or a window between two
    snapshots): per-component seconds and fractions of the step wall,
    the dominant component, and the closure error
    (``|wall − Σ components| / wall`` — the quantity test_attribution /
    test_train_obs gate; a large residual means a new unbracketed
    code path crept into the loop). Defaults cover the serve partition;
    pass the TRAIN_* tables for the train observer."""
    comps = component_totals(snap, prev, components=components)
    wall = step_wall_total(snap, prev, wall_hist=wall_hist)
    step_sum = sum(comps[c] for c in wall_components)
    denom = wall if wall > 0 else step_sum
    out: Dict[str, Any] = {
        "components_s": {c: round(v, 6) for c, v in comps.items()},
        "step_wall_s": round(wall, 6),
        "components_sum_s": round(step_sum, 6),
        "closure_err_frac": round(abs(wall - step_sum) / denom, 6)
        if denom > 0 else None,
        "fracs": {c: round(comps[c] / denom, 4) if denom > 0 else None
                  for c in wall_components},
    }
    if denom > 0:
        out["dominant"] = max(wall_components,
                              key=lambda c: comps[c])
    else:
        out["dominant"] = None
    return out


def train_attribution_report(snap: Mapping[str, Any],
                             prev: Optional[Mapping[str, Any]] = None
                             ) -> Dict[str, Any]:
    """:func:`attribution_report` over the train observer's partition."""
    return attribution_report(
        snap, prev, components=TRAIN_ATTRIBUTION_COMPONENTS,
        wall_components=TRAIN_STEP_WALL_COMPONENTS,
        wall_hist=TRAIN_WALL_HIST)


def share_from_report(rep: Any, program: str) -> Dict[str, Any]:
    """The comm-op share dict from one trip-weighted
    :class:`~..analysis.program_audit.ProgramReport` — the ONE copy of
    the arithmetic :func:`comm_share` (serve) and
    ``telemetry.train.train_comm_share`` share."""
    coll = rep.total_collectives
    dots = rep.dot_generals
    return {
        "program": program,
        "collectives_per_step": coll,
        "by_kind": dict(sorted(rep.by_kind().items())),
        "dot_generals_per_step": dots,
        "comm_op_share": round(coll / (coll + dots), 4)
        if coll + dots else 0.0,
        "host_callbacks": rep.host_callbacks,
    }


def comm_share(engine, program: str = "step_greedy_fb"
               ) -> Optional[Dict[str, Any]]:
    """The audited-collective share of one serve program's device work,
    derived entirely from the program auditor's trip-weighted jaxpr
    counts (0 host callbacks, 0 device timers): per-step collective
    executions by kind, the trip-weighted GEMM count, and their
    op-level ratio — the schedule-derived comm-vs-compute split of the
    ``device_execute`` component. Report-time only (lowers the program;
    never call on the hot path). None when the program is unavailable
    on this runner."""
    from ..analysis.program_audit import audit_serve_programs
    try:
        reports = audit_serve_programs(engine, programs=(program,))
    except (AttributeError, NotImplementedError):
        return None
    rep = reports.get(program)
    if rep is None:
        return None
    return share_from_report(rep, program)
