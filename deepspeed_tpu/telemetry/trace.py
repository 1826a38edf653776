"""Program spans on the profiler's clock: one bracket per engine boundary.

An engine owns a :class:`SpanSet` whether or not ``DSTPU_TELEMETRY`` is
on. ``with spans.span("serve/dispatch", step=n, fed=1):`` opens a
``jax.profiler.TraceAnnotation("dstpu:serve/dispatch", ...)`` (a ``TraceMe``
is inert while no profiler session is live, so there is no gate) and, on
leaving, takes one ``perf_counter`` pair and hands the duration to every
consumer that used to time the boundary itself:

  * the engine's own totals (``InferenceEngineV2.pipeline_stats``,
    ``Engine.step_stats``), under the span's ``total`` key;
  * the watchdog, which is told the span's ``phase`` on entry (if any);
  * the attached observer (``on_span``), which files a span that has a
    ``phase`` in the flight ring under it and in a registry histogram
    (``hist`` for the serve engine; the train observer's own, by phase,
    at step exit).

Every span has a ``total``, and an engine starts each key at 0.0: the
seconds of a call into an engine (``serve/put``, ``serve/decode_batch``,
``train/batch``, ..) equal the seconds of the brackets beneath it plus a
remainder that is itself a number. A span without a ``phase`` (the
calls, the stretches between their steps, the counters' own arithmetic)
is a total and an annotation only: watchdog, ring and registry never
see it. Two are a total alone (``annotated=False``): ``serve/plan_count``
and ``train/batch`` open no ``TraceAnnotation``, so the profile's gaps
keep the names they had (an idle gap during the counters' arithmetic
reads ``serve/plan``, one between a train step's brackets reads as
unnamed) and the benchmark's readers of those names read what they
read. A wait has a total like any bracket; it is read as an addend of
its call, never as a target.

:data:`SPANS` is the whole vocabulary: a span that is not in the table
cannot be opened. Names carry no dots (the benchmark's readers split
keys on dots). The annotation's arguments are small host integers read
after planning; they never reach a jit signature.

The device side has the same kind of table. ``with region("norm"):``
opens ``jax.named_scope("rg.norm")`` around a part of a step program as
it is traced, so every operation traced inside carries ``rg.norm`` in its
``op_name``; the profiler writes that path beside each device operation
(``tf_op``), and ``benchmark/regions.py`` sums device seconds by the
INNERMOST marked component. A scope changes an instruction's metadata and
never the instruction: the compiled program is the unscoped one.
:data:`REGIONS` is that vocabulary, closed like :data:`SPANS`; the names
mean the same in serving and in training, carry no dot and no slash
(readers split keys on dots, ``op_name`` on slashes), and say nothing of
the pass: forward, backward and recompute are read from the path
(``transpose(jvp``, ``rematted_computation``).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax

PREFIX = "dstpu:"


class SpanSpec(NamedTuple):
    total: str             # key in the engine's totals dict
    phase: Optional[str]   # flight-ring / watchdog phase name
    hist: Optional[str]    # registry histogram the serve observer files it in
    annotated: bool = True  # opens a TraceAnnotation (False: a total alone)


SPANS: Dict[str, SpanSpec] = {
    # the serve engine's public entry points: a call's own seconds, the
    # sum its brackets below are held against (no phase, no histogram:
    # the ring, the registry and the watchdog never see them)
    "serve/put": SpanSpec("put_s", None, None),
    "serve/decode_pipelined": SpanSpec("decode_pipelined_s", None, None),
    "serve/decode_batch": SpanSpec("decode_batch_s", None, None),
    # a call's stretch before its first plan: put's admission loop,
    # decode_pipelined's checks of its batch and its first tokens queued
    "serve/admit": SpanSpec("admit_s", None, None),
    # the pipelined step: plan -> dispatch -> commit (readback, apply)
    "serve/plan": SpanSpec("plan_s", "plan", "serve_plan_s"),
    # the counters' own arithmetic at the end of a plan, nested in
    # serve/plan (plan_s includes it)
    "serve/plan_count": SpanSpec("plan_count_s", None, None,
                                 annotated=False),
    "serve/dispatch": SpanSpec("dispatch_s", "dispatch", "serve_dispatch_s"),
    "serve/commit_block": SpanSpec("commit_block_s", "commit",
                                   "serve_commit_block_s"),
    "serve/commit_apply": SpanSpec("commit_apply_s", "commit_apply",
                                   "serve_commit_apply_s"),
    # the fused decode loop: one dispatch and one readback cover n steps.
    # decode_batch's validation, reservation and staging before the
    # dispatch; the counters' arithmetic around the readback
    "serve/fused_stage": SpanSpec("fused_stage_s", None, None),
    "serve/fused_dispatch": SpanSpec("fused_dispatch_s", "dispatch",
                                     "serve_dispatch_s"),
    "serve/fused_readback": SpanSpec("fused_readback_s", "commit",
                                     "serve_commit_block_s"),
    "serve/fused_count": SpanSpec("fused_count_s", None, None),
    "serve/fused_apply": SpanSpec("fused_apply_s", "commit_apply",
                                  "serve_commit_apply_s"),
    # the train step, and the whole call around it. Its observer files
    # each phase's seconds itself, at step exit, under train_<phase>_s
    # (the wait keeps the registry's and the ring's name, device_execute)
    "train/batch": SpanSpec("train_batch_s", None, None, annotated=False),
    "train/stage": SpanSpec("stage_s", "stage", None),
    "train/dispatch": SpanSpec("dispatch_s", "dispatch", None),
    "train/device_wait": SpanSpec("device_wait_s", "device_execute", None),
    "train/commit_apply": SpanSpec("commit_apply_s", "commit_apply", None),
    # the observer closing its books (the sentinel's scalar reads of the
    # previous step, sampling, export)
    "train/step_exit": SpanSpec("step_exit_s", None, None),
}


REGION_MARK = "rg."

REGIONS = (
    "embed",        # token gather, positions, validity, position code
    "norm",         # RMSNorm / LayerNorm of the stream and of a branch
    "attn_proj",    # q/k/v/out projections, rope, QK-norm, output gate
    "attn_core",    # the paged / flash / dense attention call, its masks
    "kv_write",     # rows into the pool or the loop's ring; the flush
    "linear_attn",  # a linear-attention layer (delta rule or Lightning):
                    # projections, convolution if any, decay, state update
    "ssm",          # a state-space layer: projection, convolution, scan, gate
    "mla_proj",     # latent attention's low-rank projections, absorption
    "mla_core",     # the latent attention call over the one-plane pool
    "ffn_dense",    # a dense feed-forward
    "moe_route",    # router, top-k, layout, gathers, the weighted sum
    "moe_experts",  # the grouped expert matmuls
    "moe_shared",   # the always-on shared expert
    "residual",     # the residual adds
    "head",         # final norm, last-row gather, unembedding
    "sample",       # argmax, sampling, the chosen token's log-probability
    "loop_carry",   # token feed, positions and counters between steps
    "loss",         # the (chunked) cross-entropy
    "grad_clip",    # mean over micro-batches, unscale, global norm, clip
    "optimizer",    # the update, the overflow gate, the new state
    "attn_window",  # attn_core's twin around a sliding-window layer's call
    "attn_select",  # a block-selected layer's selection: compressed scores,
                    # group sum, window maximum, forced blocks, top-k, rows
    "attn_sparse",  # attn_core's twin around the call that reads the
                    # selected blocks alone
    "conv_mixer",   # a gated short-convolution mixer: projection, gates,
                    # convolution, output projection
)


def region(name: str):
    """``with region("norm"):`` around a part of a step program: a
    ``jax.named_scope`` under :data:`REGION_MARK`. A name outside
    :data:`REGIONS` raises when the program is traced."""
    if name not in REGIONS:
        raise KeyError(f"{name!r} is not in telemetry.trace.REGIONS")
    return jax.named_scope(REGION_MARK + name)


class SpanSet:
    """The brackets of one engine. ``totals`` is the engine's own dict
    of accumulated seconds and counts; ``observer()`` gives the engine's
    observer as it is now (anything with ``on_span``, or None: benches
    switch it on and off on a live engine); ``watchdog`` (anything with
    ``phase``) is attached by the engine and may be None."""

    def __init__(self, totals: Dict[str, Any],
                 observer: Callable[[], Any] = lambda: None):
        self.totals = totals
        self.observer = observer
        self.watchdog = None

    def span(self, name: str, **args: int) -> "Span":
        return Span(self, name, SPANS[name], args)


class Span:
    """One open bracket. ``args`` (``step`` among them) go on the
    annotation; ``set`` adds those known only once the work is done
    (what a plan scheduled), ``count`` adds to the engine's totals,
    ``void`` withdraws the bracket.
    Registered DSL001 hot path: two clock reads, a dict add and the
    observer's fan-out."""

    __slots__ = ("_set", "name", "spec", "args", "_ann", "_t0")

    def __init__(self, span_set: SpanSet, name: str, spec: SpanSpec,
                 args: Dict[str, int]):
        self._set, self.name, self.spec, self.args = \
            span_set, name, spec, args

    def set(self, **args: int) -> None:
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def count(self, **counts: int) -> None:
        totals = self._set.totals
        for key, n in counts.items():
            totals[key] = totals.get(key, 0) + n

    def void(self) -> None:
        """Nothing was done under this bracket (a plan that scheduled
        nothing): it closes without reaching totals, ring or histogram,
        as the boundary it replaced returned before its clock read."""
        self._t0 = None

    def __enter__(self) -> "Span":
        wd = self._set.watchdog
        if wd is not None and self.spec.phase is not None:
            wd.phase(self.spec.phase)
        self._ann = jax.profiler.TraceAnnotation(
            PREFIX + self.name, **self.args) if self.spec.annotated else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._t0 is None:
            return
        total, totals = self.spec.total, self._set.totals
        totals[total] = totals.get(total, 0.0) + (t1 - self._t0)
        obs = self._set.observer()
        if obs is not None and self.spec.phase is not None:
            obs.on_span(self, self._t0, t1)
