"""Record ``fixtures/v5e_serve_spans.xplane.pb`` on the chip:

    chiprun -- python3 benchmark/tests/record_serve_spans.py

A one-layer toy decoder (so the trace stays under 100 KB) behind the real
``InferenceEngineV2``: under ``bench:window``, one ``bench:put`` of two
prompts, a ``bench:decode_pipelined`` burst of four steps, a 5 ms
``bench:sleep`` and a second burst, traced the way ``Ctx.traced_window``
traces a cell, but with the Python tracer and the HLO protos off. The
program's own ``dstpu:`` spans are inside. The recorded file is then cut
to what the reductions read (:func:`strip`): the device planes' module
and operation lines with each operation's name cut to its head, and the
host lines that carry a span or a launch; a few times 100 KB of source
stacks and of host threads nothing reads go. Times, durations, names'
heads, run ids and span arguments are as recorded. The file lands in
``chiprun_out/``; ``test_program_spans.py`` pins what it holds.
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


NAME_HEAD = 72          # enough for reduce_trace.stable_name


def _varint(b: bytes, i: int):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if not c & 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, wire type, value) of one protobuf message."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        else:
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        yield key >> 3, wire, v


def _enc(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _put(field: int, wire: int, v) -> bytes:
    key = _enc(field << 3 | wire)
    if wire == 0:
        return key + _enc(v)
    return key + (_enc(len(v)) if wire == 2 else b"") + v


def _rebuild(msg: bytes, edit) -> bytes:
    """The message with each field passed through ``edit(field, wire,
    value)``, which returns the new value or None to drop the field."""
    out = bytearray()
    for f, w, v in _fields(msg):
        v = edit(f, w, v)
        if v is not None:
            out += _put(f, w, v)
    return bytes(out)


def strip(xspace: bytes) -> bytes:
    """XSpace -> XSpace holding what ``reduce_trace`` and
    ``program_spans`` read. Field numbers are those of tsl's
    ``xplane.proto``: XSpace.planes = 1; XPlane name 2, lines 3,
    event_metadata 4 (a map: key 1, value 2); XLine name 2, events 4;
    XEvent metadata_id 1, stats 4; XEventMetadata id 1, name 2."""
    keep_lines = (b"XLA Modules", b"XLA Ops", b"Async XLA Ops")
    wanted = (b"bench:", b"dstpu:", b"DoEnqueueProgram")

    def plane(f, w, v):
        if f != 1:
            return None
        parts = list(_fields(v))
        name = next(x for ff, _, x in parts if ff == 2)
        device = name.startswith(b"/device:TPU:")
        if not device and not name.startswith(b"/host:CPU"):
            return None
        names = {}
        for ff, _, x in parts:
            if ff == 4:
                meta = dict((f3, x3) for f3, _, x3 in _fields(x))[2]
                m = dict((f4, x4) for f4, _, x4 in _fields(meta))
                names[m[1]] = m.get(2, b"")

        def line(ff, ww, x):
            if ff == 4:                         # event_metadata entry
                def value(f4, w4, x4):
                    if f4 == 2:
                        return x4[:NAME_HEAD] if device else x4
                    return x4 if f4 == 1 else None
                return _rebuild(x, lambda f3, w3, x3: _rebuild(x3, value)
                                if f3 == 2 else x3)
            if ff != 3:
                return x
            lp = list(_fields(x))
            lname = next(y for f3, _, y in lp if f3 == 2)
            events = [dict((f4, y4) for f4, _, y4 in _fields(y))
                      for f3, _, y in lp if f3 == 4]
            if device:
                if lname not in keep_lines:
                    return None
                if lname != b"XLA Modules":     # run_id is a stat there
                    return _rebuild(x, lambda f3, w3, y: _rebuild(
                        y, lambda f4, w4, y4: None if f4 == 4 else y4)
                        if f3 == 4 else y)
                return x
            if not any(names.get(e.get(1), b"").startswith(wanted)
                       for e in events):
                return None
            return x
        return _rebuild(v, line)
    return _rebuild(xspace, plane)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    mcfg = GPT2Config(vocab_size=512, max_seq_len=128, num_layers=1,
                      num_heads=2, hidden_size=256, dtype=jnp.float32)
    params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
        max_seqs=4, chunk_size=8, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32", attention_impl="dense",
        decode_loop_steps=0, serve_pipeline_depth=2))
    rng = np.random.default_rng(25)

    def serve(uids):
        with jax.profiler.TraceAnnotation("bench:put"):
            first = eng.put(uids, [rng.integers(1, 512, n).tolist()
                                   for n in (13, 5)], _greedy=True)
        last = [int(first[u]) for u in uids]
        for i in range(2):
            with jax.profiler.TraceAnnotation("bench:decode_pipelined"):
                outs = eng.decode_pipelined(uids, last, 4)
            last = [outs[u][-1] for u in uids]
            if i == 0:
                with jax.profiler.TraceAnnotation("bench:sleep"):
                    time.sleep(0.005)
        for u in uids:
            eng.flush(u)

    serve([0, 1])                       # every program compiles here
    out = os.path.join(ROOT, "chiprun_out", "serve_spans_trace")
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            serve([2, 3])
    finally:
        jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    dst = os.path.join(ROOT, "chiprun_out", "v5e_serve_spans.xplane.pb")
    with open(src, "rb") as f:
        raw = f.read()
    with open(dst, "wb") as f:
        f.write(strip(raw))
    print(dst, len(raw), "bytes recorded,", os.path.getsize(dst), "kept,",
          dev.device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
