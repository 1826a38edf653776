"""Record ``fixtures/v5e_regions.xplane.pb`` on the chip:

    chiprun -- python3 benchmark/tests/record_regions.py

A toy train step under the program's own ``region`` scopes
(``deepspeed_tpu/telemetry/trace.py``): three layers of two regions each
(``norm``, then ``ffn_dense`` with ``residual`` opened INSIDE it) under
``lax.scan`` + ``jax.checkpoint`` + ``grad``, a ``loss`` region, and an
``optimizer`` region that holds one Pallas call and, nested in it, a
``grad_clip`` region whose reduction XLA cannot fuse into a neighbour
(the innermost region wins), run three times under ``bench:window``
between two 5 ms sleeps (the device's clock runs ~1 ms off the host's:
the steps must lie inside the window on both). Traced as
``record_serve_spans.py`` traces, the Python tracer and the HLO protos
off.

The recorded file is cut to the device planes and the host lines that
carry a span (:func:`keep`), but the device planes stay WHOLE: a region
fixture must NOT go through ``record_serve_spans.strip``, which drops
the stats of every operation's metadata. Those stats (``tf_op``,
``source``, ``hlo_category``, ``program_id``, ``flops``,
``bytes_accessed``) are exactly what ``benchmark/regions.py`` reads; an
event's own stats, which ``jax.profiler.ProfileData`` shows, hold none
of them. The file lands in ``chiprun_out/``; ``test_regions.py`` pins
what it holds.
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tests.record_serve_spans import _fields, _rebuild  # noqa: E402

LAYERS, ROWS, WIDTH = 3, 64, 256


def keep(xspace: bytes) -> bytes:
    """XSpace -> XSpace of the device planes, whole, and of the host's
    lines that carry a ``bench:`` span (XSpace.planes = 1; XPlane name 2,
    lines 3, event_metadata 4; XLine events 4; XEvent metadata_id 1)."""
    def plane(f, w, v):
        if f != 1:
            return None
        parts = list(_fields(v))
        name = next(x for ff, _, x in parts if ff == 2)
        if name.startswith(b"/device:TPU:"):
            return v
        if not name.startswith(b"/host:CPU"):
            return None
        spans = set()
        for ff, _, x in parts:
            if ff == 4:
                meta = dict((a, y) for a, _, y in _fields(
                    dict((a, y) for a, _, y in _fields(x))[2]))
                if meta.get(2, b"").startswith(b"bench:"):
                    spans.add(meta[1])

        def line(ff, ww, x):
            if ff == 4:
                key = dict((a, y) for a, _, y in _fields(x))[1]
                return x if key in spans else None
            if ff != 3:
                return x
            ids = {dict((a, y) for a, _, y in _fields(y)).get(1)
                   for f3, _, y in _fields(x) if f3 == 4}
            return x if ids & spans else None
        return _rebuild(v, line)
    return _rebuild(xspace, plane)


def build():
    """(step, (ws, x)): the jitted toy step and its inputs."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from deepspeed_tpu.telemetry.trace import region

    def halve(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 0.5

    def layer(x, w):
        with region("norm"):
            h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        with region("ffn_dense"):
            h = jnp.tanh(h @ w)
            with region("residual"):        # the innermost region wins
                x = x + h
        return x

    def loss(ws, x):
        y, _ = jax.lax.scan(lambda c, w: (jax.checkpoint(layer)(c, w), None),
                            x, ws)
        with region("loss"):
            return jnp.mean(y * y)

    def bench_fixture_regions(ws, x):
        grads = jax.grad(loss)(ws, x)
        with region("optimizer"):
            with region("grad_clip"):       # the innermost region wins
                scale = jax.lax.rsqrt(jnp.sum(grads * grads) + 1.0)
            half = pl.pallas_call(
                halve, out_shape=jax.ShapeDtypeStruct(grads.shape,
                                                      grads.dtype),
                interpret=jax.default_backend() != "tpu",
                name="halve")(grads)
            return ws - scale * half

    key = jax.random.PRNGKey(38)
    ws = jax.random.normal(key, (LAYERS, WIDTH, WIDTH)) * WIDTH ** -0.5
    x = jax.random.normal(jax.random.fold_in(key, 1), (ROWS, WIDTH))
    return jax.jit(bench_fixture_regions), (ws, x)


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    step, (ws, x) = build()
    jax.block_until_ready(step(ws, x))          # compiles here
    out = os.path.join(ROOT, "chiprun_out", "regions_trace")
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            time.sleep(0.005)
            for _ in range(3):
                ws = step(ws, x)
            jax.block_until_ready(ws)
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    dst = os.path.join(ROOT, "chiprun_out", "v5e_regions.xplane.pb")
    with open(src, "rb") as f:
        raw = f.read()
    with open(dst, "wb") as f:
        f.write(keep(raw))
    print(dst, len(raw), "bytes recorded,", os.path.getsize(dst), "kept,",
          dev.device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
