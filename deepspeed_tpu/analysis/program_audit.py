"""Static program auditor — machine-checked structure of compiled programs.

PRs 2 and 3 ship hard structural claims ("exactly 2 per-layer TP
all-reduces + 1 pre-sampling logits gather", "zero host round-trips on the
steady decode path", "KV pool donated on TPU") that token-parity tests
cannot see: a refactor can double comm volume or drop donation and every
output still matches. This module lowers any jitted / shard_mapped program
to its jaxpr (and StableHLO for aliasing) and produces a
:class:`ProgramReport`:

* collective counts by kind (``all_reduce`` / ``all_gather`` /
  ``reduce_scatter`` / ``ppermute`` / ``all_to_all``), mesh axis and comm
  dtype (int8 ZeRO++ comm is distinguishable from bf16/f32), with counts
  inside ``lax.scan`` bodies weighted by the trip count — a fused n-step
  decode loop reports n× its body's collectives;
* host callbacks / infeed / outfeed (the "zero host round-trips" claim);
* input→output buffer aliasing (donation), parsed from the lowered
  StableHLO — visible on every backend, including the CPU test mesh;
* a :class:`RecompileTripwire` that counts XLA backend compiles across a
  region (jit cache misses on a warm serve pipeline are a silent
  latency/VMEM regression).

Declarative :class:`CollectiveBudget` specs turn the structural claims
into tier-1 regression tests (tests/unit/test_program_audit.py); see
docs/analysis.md for the field and spec reference.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax

from ..parallel.tp_rules import MODEL_AXIS

# ------------------------------------------------------------------ #
# jaxpr traversal
# ------------------------------------------------------------------ #

#: primitive -> canonical collective kind. pmax/pmin are reductions over a
#: named axis too — a planted pmax must trip an all_reduce budget, not
#: slip past it.
COLLECTIVE_PRIMS: Mapping[str, str] = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_gather": "all_gather",
    "psum_scatter": "reduce_scatter",
    "reduce_scatter": "reduce_scatter",
    "ppermute": "ppermute",
    "pshuffle": "ppermute",
    "all_to_all": "all_to_all",
}

#: primitives that round-trip through the host (or pin a host transfer)
#: inside a compiled program — the decode hot path must contain none
HOST_CALLBACK_PRIMS = frozenset([
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed",
])


def _axis_names(params: Mapping[str, Any]) -> Tuple[str, ...]:
    """Named mesh axes a collective eqn communicates over (positional
    ints — vmapped axes — are dropped)."""
    raw = params.get("axes", params.get("axis_name", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    names = tuple(a for a in raw if isinstance(a, str))
    return names or ("<positional>",)


def _subjaxprs(params: Mapping[str, Any]):
    """Every sub-jaxpr held by an eqn's params (jit/shard_map/scan/
    while/cond/custom_* all store them under different keys)."""
    from jax._src.core import ClosedJaxpr, Jaxpr
    for v in params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, Jaxpr):
                    yield item


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """Aggregation key for one kind of collective in one program."""
    kind: str                  # canonical kind (COLLECTIVE_PRIMS values)
    axes: Tuple[str, ...]      # named mesh axes it communicates over
    dtype: str                 # dtype of the communicated operand

    def __str__(self):
        return f"{self.kind}[{','.join(self.axes)}]({self.dtype})"


#: inner-jit (``jit`` eqn) name fragments that canonicalize the ppermute
#: hops traced inside them: the decomposed TP collectives
#: (``comm.ring_reduce_scatter`` / ``comm.ring_all_gather``) are built
#: from ppermute rings, and counting those hops as raw ppermutes would
#: make a reduce-scatter indistinguishable from pipeline p2p traffic.
#: Any ppermute inside a region whose jit name carries one of these
#: fragments reports as the canonical decomposed kind — so a planted
#: extra ring hop trips a reduce_scatter/all_gather budget diff.
RING_REGION_KINDS: Mapping[str, str] = {
    "ring_reduce_scatter": "reduce_scatter",
    "ring_all_gather": "all_gather",
}


def _ring_kind_for(name: Optional[str]) -> Optional[str]:
    if not name:
        return None
    for frag, kind in RING_REGION_KINDS.items():
        if frag in name:
            return kind
    return None


def _walk(jaxpr, counts: Dict[CollectiveSite, int], state: Dict[str, Any],
          mult: int, ring_kind: Optional[str] = None) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        kind = COLLECTIVE_PRIMS.get(prim)
        if kind is not None:
            if kind == "ppermute" and ring_kind is not None:
                # a hop of a decomposed ring: canonicalize to the
                # reduce-scatter / all-gather family it implements
                kind = ring_kind
            site = CollectiveSite(
                kind=kind, axes=_axis_names(eqn.params),
                dtype=str(eqn.invars[0].aval.dtype))
            counts[site] = counts.get(site, 0) + mult
        if prim in HOST_CALLBACK_PRIMS:
            state["host_callbacks"] += mult
        if prim == "dot_general":
            # trip-weighted GEMM count: together with the collective
            # counts this gives an op-level comm-vs-compute split of a
            # step program (telemetry/attribution.py derives its
            # audited-collective share from exactly these two numbers)
            state["dot_generals"] += mult
        sub_ring = ring_kind
        if prim == "jit":
            sub_ring = _ring_kind_for(eqn.params.get("name")) or ring_kind
        if prim == "scan":
            # a scan body executes `length` times: weight its collectives
            # so an n-step fused decode loop reports n x its per-step comm
            inner_mult = mult * int(eqn.params.get("length", 1))
            for sub in _subjaxprs(eqn.params):
                _walk(sub, counts, state, inner_mult, ring_kind)
            continue
        if prim == "while":
            # trip count is dynamic: counts stay per-iteration, flagged
            state["dynamic_loops"] += 1
        for sub in _subjaxprs(eqn.params):
            _walk(sub, counts, state, mult, sub_ring)


# ------------------------------------------------------------------ #
# report
# ------------------------------------------------------------------ #


@dataclasses.dataclass
class ProgramReport:
    """Structural audit of one compiled program.

    ``collectives`` maps :class:`CollectiveSite` -> execution count
    (scan-weighted). ``donated_args`` are flat input indices the lowering
    aliases to outputs (donation); empty when the program was audited
    without a lowerable (jitted) callable. ``dynamic_loops`` counts
    ``while`` loops whose bodies could not be trip-weighted.
    """

    name: str
    collectives: Dict[CollectiveSite, int]
    host_callbacks: int = 0
    donated_args: Tuple[int, ...] = ()
    dynamic_loops: int = 0
    #: trip-weighted dot_general executions — the compute-op denominator
    #: of the attribution layer's audited comm-op share
    dot_generals: int = 0

    # ------------------------- accessors -------------------------- #

    def count(self, kind: Optional[str] = None, axis: Optional[str] = None,
              dtype: Optional[str] = None) -> int:
        """Total executions of collectives matching the given filters."""
        total = 0
        for site, n in self.collectives.items():
            if kind is not None and site.kind != kind:
                continue
            if axis is not None and axis not in site.axes:
                continue
            if dtype is not None and site.dtype != dtype:
                continue
            total += n
        return total

    def by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for site, n in self.collectives.items():
            out[site.kind] = out.get(site.kind, 0) + n
        return out

    @property
    def total_collectives(self) -> int:
        return sum(self.collectives.values())

    @property
    def donates(self) -> bool:
        return bool(self.donated_args)

    def summary(self) -> str:
        try:
            from ..parallel.topology import AXIS_ROLES
        except ImportError:                      # pragma: no cover
            AXIS_ROLES = {}
        lines = [f"ProgramReport '{self.name}':"]
        if not self.collectives:
            lines.append("  collectives: none")
        for site, n in sorted(self.collectives.items(), key=str):
            role = ", ".join(AXIS_ROLES.get(a, a) for a in site.axes)
            lines.append(f"  {site}: x{n}  ({role})")
        lines.append(f"  host_callbacks: {self.host_callbacks}")
        lines.append(f"  donated_args: {list(self.donated_args)}")
        if self.dynamic_loops:
            lines.append(f"  dynamic (while) loops: {self.dynamic_loops} "
                         f"— their bodies counted once per loop")
        return "\n".join(lines)


# donation entries in the lowered StableHLO main signature — single-device
# lowerings resolve the alias eagerly, sharded lowerings defer it to the
# compiler:
#   %arg7: tensor<...> {..., tf.aliasing_output = 0 : i32, ...}
#   %arg0: tensor<...> {jax.buffer_donor = true, mhlo.sharding = ...}
_ARG_RE = re.compile(r"%arg(\d+):")
_DONOR_MARKS = ("tf.aliasing_output", "jax.buffer_donor")


def donated_arg_indices(stablehlo_text: str) -> Tuple[int, ...]:
    """Flat input indices aliased/donated to outputs, parsed from the
    lowered module's ``@main`` signature. Lowering records donation on
    every backend, so the tier-1 CPU mesh can verify a program *requests*
    donation. Each argument's attributes are everything up to the next
    argument: an attribute dict may nest braces (a committed input carries
    ``sdy.sharding = #sdy.sharding<@mesh, [{}, {}]>``), so it is not
    matched as a ``{...}`` group."""
    for line in stablehlo_text.splitlines():
        if "@main(" not in line:
            continue
        args = line.split(") -> ")[0]
        found = list(_ARG_RE.finditer(args))
        ends = [m.start() for m in found[1:]] + [len(args)]
        return tuple(sorted(
            int(m.group(1)) for m, end in zip(found, ends)
            if any(d in args[m.end():end] for d in _DONOR_MARKS)))
    return ()


def audit_fn(fn: Callable, *args, name: Optional[str] = None,
             static_kwargs: Optional[Mapping[str, Any]] = None,
             **kwargs) -> ProgramReport:
    """Audit one program: trace ``fn(*args, **kwargs)`` to a jaxpr and —
    when ``fn`` is jitted (has ``.lower``) — lower it for donation info.

    ``static_kwargs`` are compile-time arguments of a jitted ``fn``
    (``static_argnames``); they are forwarded without being traced.
    """
    static_kwargs = dict(static_kwargs or {})
    if static_kwargs:
        traced = functools.partial(fn, **static_kwargs)
    else:
        traced = fn
    jaxpr = jax.make_jaxpr(traced)(*args, **kwargs)
    counts: Dict[CollectiveSite, int] = {}
    state = {"host_callbacks": 0, "dynamic_loops": 0, "dot_generals": 0}
    _walk(jaxpr.jaxpr, counts, state, 1)
    donated: Tuple[int, ...] = ()
    if hasattr(fn, "lower"):
        lowered = fn.lower(*args, **kwargs, **static_kwargs)
        donated = donated_arg_indices(lowered.as_text())
    return ProgramReport(
        name=name or getattr(fn, "__name__", "program"),
        collectives=counts, host_callbacks=state["host_callbacks"],
        donated_args=donated, dynamic_loops=state["dynamic_loops"],
        dot_generals=state["dot_generals"])


# ------------------------------------------------------------------ #
# declarative collective budgets
# ------------------------------------------------------------------ #


def _budget_key(key: str) -> Tuple[str, Optional[str]]:
    """Split a budget key into (kind, dtype): plain ``"reduce_scatter"``
    covers every dtype; ``"reduce_scatter@int8"`` pins the comm dtype —
    how the decomposed quantized schedule asserts its int8 value hops
    separately from the f32 per-chunk scale hops."""
    kind, sep, dt = key.partition("@")
    return kind, (dt if sep else None)


@dataclasses.dataclass
class CollectiveBudget:
    """Expected collective structure of one program, as a regression spec.

    ``per_layer`` maps canonical kind -> count per transformer layer per
    executed step; ``per_program`` maps kind -> count per executed step
    regardless of depth (e.g. the single pre-sampling logits gather).
    A key may pin the comm dtype as ``"kind@dtype"`` (e.g.
    ``"reduce_scatter@int8"``) — the decomposed quantized ring's int8
    value hops and f32 scale hops are then budgeted separately; a plain
    ``"kind"`` key aggregates over every dtype no sibling pinned key of
    the same kind claims (so plain + pinned keys compose instead of
    double-counting). ``steps`` is the scan trip
    count for fused loops (1 for plain steps). Expected total per key =
    ``steps * (num_layers * per_layer[key] + per_program[key])``. Kinds
    absent from both maps must not appear at all; collectives over axes
    other than ``axis`` are violations unless ``allow_other_axes``.
    Ring-decomposed collectives (ppermute hops inside the
    ``comm.ring_*`` regions) are already canonicalized to
    reduce_scatter/all_gather by the walker — budget those kinds, not
    ppermute.
    """

    name: str
    num_layers: int = 1
    steps: int = 1
    per_layer: Mapping[str, int] = dataclasses.field(default_factory=dict)
    per_program: Mapping[str, int] = dataclasses.field(default_factory=dict)
    axis: str = MODEL_AXIS
    allow_other_axes: bool = False
    max_host_callbacks: Optional[int] = 0

    def expected(self) -> Dict[str, int]:
        kinds = set(self.per_layer) | set(self.per_program)
        return {k: self.steps * (self.num_layers * self.per_layer.get(k, 0)
                                 + self.per_program.get(k, 0))
                for k in kinds}

    def check(self, report: ProgramReport) -> List[str]:
        """Violations of this budget in ``report`` (empty = conforming)."""
        out: List[str] = []
        expected = self.expected()
        # (kind, dtype|None) -> budget key string; a plain-kind key
        # absorbs every dtype of its kind EXCEPT dtypes a sibling pinned
        # key already claims — so {"all_gather@int8": k, "all_gather": 1}
        # budgets the ring's int8 hops and the f32 logits gather without
        # double-counting the hops under the plain key
        by_pair = {_budget_key(k): k for k in expected}
        plain_kinds = {kind for kind, dt in by_pair if dt is None}
        pinned: Dict[str, set] = {}
        for kind, dt in by_pair:
            if dt is not None:
                pinned.setdefault(kind, set()).add(dt)
        pairs = set(by_pair)
        for site, n in report.collectives.items():
            if self.axis in site.axes and n:
                if site.dtype in pinned.get(site.kind, ()):
                    pairs.add((site.kind, site.dtype))
                elif site.kind in plain_kinds:
                    pairs.add((site.kind, None))
                else:
                    pairs.add((site.kind, site.dtype))
        for kind, dt in sorted(pairs, key=lambda t: (t[0], t[1] or "")):
            key = by_pair.get((kind, dt), f"{kind}@{dt}" if dt else kind)
            want = expected.get(key, 0)
            got = report.count(kind=kind, axis=self.axis, dtype=dt)
            if dt is None:
                # subtract sites a sibling pinned key claims
                got -= sum(report.count(kind=kind, axis=self.axis,
                                        dtype=pdt)
                           for pdt in pinned.get(kind, ()))
            if got != want:
                label = kind if dt is None else f"{kind}@{dt}"
                out.append(
                    f"{label}[{self.axis}]: expected {want} "
                    f"({self.steps} step(s) x ({self.num_layers} layers x "
                    f"{self.per_layer.get(key, 0)}/layer + "
                    f"{self.per_program.get(key, 0)}/program)), got {got}")
        if not self.allow_other_axes:
            for site, n in sorted(report.collectives.items(), key=str):
                if self.axis not in site.axes and n:
                    out.append(f"unbudgeted axis: {site} x{n} "
                               f"(budget covers '{self.axis}' only)")
        if self.max_host_callbacks is not None \
                and report.host_callbacks > self.max_host_callbacks:
            out.append(f"host callbacks: expected <= "
                       f"{self.max_host_callbacks}, got "
                       f"{report.host_callbacks}")
        return out


def assert_budget(report: ProgramReport, budget: CollectiveBudget) -> None:
    """Raise ``AssertionError`` with a diff of every violated budget line
    (this is the failure message the tier-1 regression tests surface)."""
    violations = budget.check(report)
    if violations:
        raise AssertionError(
            f"CollectiveBudget '{budget.name}' violated by program "
            f"'{report.name}':\n  " + "\n  ".join(violations)
            + "\n" + report.summary())


# ------------------------------------------------------------------ #
# serve-engine convenience: audit every runner program of an engine
# ------------------------------------------------------------------ #


_SERVE_PROGRAMS = ("step", "step_greedy", "step_greedy_fb",
                   "step_sample_fb", "decode_loop", "decode_verify",
                   "flush_ring")


def serve_program_calls(engine, programs: Tuple[str, ...] = _SERVE_PROGRAMS
                        ) -> Dict[str, Tuple[Callable, tuple, dict]]:
    """{program name: (jitted fn, args, static kwargs)}: the v2 ragged
    engine's runner programs with representative decode-shaped inputs
    (S = max_seqs slots, one token each) — what ``audit_serve_programs``
    audits, for any other walk over the same traced programs."""
    import jax.numpy as jnp

    from ..inference.v2.kv_quant import pool_parts
    from ..inference.v2.model_runner import RaggedBatch

    cfg, r = engine.config, engine.runner
    S, MAXB = cfg.max_seqs, cfg.max_blocks_per_seq
    params, kv = engine.params, engine._kv_data
    batch = RaggedBatch(
        tokens=jnp.zeros((S, 1), jnp.int32),
        start_pos=jnp.zeros((S,), jnp.int32),
        n_tokens=jnp.ones((S,), jnp.int32),
        block_tables=jnp.zeros((S, MAXB), jnp.int32))
    zeros_s = jnp.zeros((S,), jnp.int32)
    ones_s = jnp.ones((S,), jnp.int32)
    ones_f = jnp.ones((S,), jnp.float32)
    n = max(2, int(cfg.decode_loop_steps) or 2)
    samp_dummies = (jnp.zeros((1,), jnp.int32),
                    jnp.zeros((1,), jnp.float32),
                    jnp.zeros((1,), jnp.int32),
                    jnp.ones((1,), jnp.float32))
    loop_static = dict(n=n, mode="greedy", cand=1, eos_id=-1)
    pool_arr, pool_scales = pool_parts(kv)
    ring = jnp.zeros(
        (n, r.kv_layers, 2, S, r.kv_heads * r.head_dim),
        pool_arr.dtype if pool_scales is None else r.compute_dtype)
    # a model with recurrent layers: every row names its state row, and
    # the fused loop takes the state apart from the paged planes
    lin = sslots = None
    planes = kv
    if r.state_spec is not None:
        sslots = jnp.arange(S, dtype=jnp.int32)
        batch = batch._replace(state_slots=sslots)
        lin = (kv.state, kv.conv)
        planes = kv._replace(state=None, conv=None)

    calls = {
        "step": (r._step, (params, kv, batch), {}),
        "step_greedy": (r._step_greedy, (params, kv, batch), {}),
        "step_greedy_fb": (r._step_greedy_fb,
                           (params, kv, batch, zeros_s, ones_s, zeros_s), {}),
        "decode_loop": (r._decode_loop_ring,
                        (params, planes, lin, sslots, zeros_s, zeros_s,
                         ones_s,
                         batch.block_tables, *samp_dummies,
                         jnp.zeros((1, 1), jnp.int32)),
                        dict(loop_static, feed="self")),
        # the speculative verify program: identical scan, draft-fed
        "decode_verify": (r._decode_loop_ring,
                          (params, planes, lin, sslots, zeros_s, zeros_s,
                           ones_s,
                           batch.block_tables, *samp_dummies,
                           jnp.zeros((S, n), jnp.int32)),
                          dict(loop_static, feed="given")),
        "flush_ring": (r._flush_ring,
                       (planes, ring, batch.block_tables, zeros_s, ones_s),
                       {}),
    }
    if hasattr(r, "_step_sample_fb"):
        calls["step_sample_fb"] = (
            r._step_sample_fb,
            (params, kv, batch, zeros_s, ones_s, zeros_s, zeros_s, zeros_s,
             ones_f, zeros_s, ones_f), {})
    return {name: calls[name] for name in programs if name in calls}


def audit_serve_programs(engine, programs: Tuple[str, ...] = _SERVE_PROGRAMS
                         ) -> Dict[str, ProgramReport]:
    """Audit the v2 ragged engine's jitted runner programs against
    ``serve_program_calls``'s inputs. Returns {program name:
    ProgramReport}. The sampled feedback step and the speculative verify
    loop are audited alongside the greedy programs: sampling/verification
    must add ZERO collectives and zero host callbacks over their greedy
    siblings."""
    return {name: audit_fn(fn, *args, static_kwargs=static, name=name)
            for name, (fn, args, static)
            in serve_program_calls(engine, programs).items()}


# ------------------------------------------------------------------ #
# recompile tripwire
# ------------------------------------------------------------------ #

_COMPILES = {"n": 0}
_LISTENING = {"on": False}


def _ensure_compile_listener() -> None:
    """Register (once) a jax monitoring listener counting XLA backend
    compiles. Raises if this jax has no such hook: a tripwire that cannot
    count must not report zero."""
    if _LISTENING["on"]:
        return
    from jax._src import monitoring

    def _on_event(event, *a, **kw):
        if "backend_compile" in event:
            _COMPILES["n"] += 1

    monitoring.register_event_duration_secs_listener(_on_event)
    _LISTENING["on"] = True


class RecompileTripwire:
    """Counts XLA backend compiles inside a ``with`` region.

    A warm serve-pipeline run must report ``fresh_compiles == 0``: a jit
    cache miss mid-serve means a shape/dtype/static-arg leak — a silent
    latency cliff the tier-1 tests now catch. A jit cache miss that the
    persistent compilation cache then serves still counts: the event fires
    around the cache lookup. Construction raises on a jax without the
    monitoring hook.
    """

    def __init__(self):
        _ensure_compile_listener()
        self._start = 0
        self._stop: Optional[int] = None

    def __enter__(self) -> "RecompileTripwire":
        self._start = _COMPILES["n"]
        self._stop = None
        return self

    def __exit__(self, *exc) -> None:
        self._stop = _COMPILES["n"]

    @property
    def fresh_compiles(self) -> int:
        end = self._stop if self._stop is not None else _COMPILES["n"]
        return end - self._start
