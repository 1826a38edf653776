"""``benchmark/regions.py`` on the CPU: against the two profiles recorded
on a v5e that keep their operations' metadata (``v5e_matmul_loop``: no
regions, so everything is ``unscoped`` or ``xla_inserted``;
``v5e_regions``: ``tests/record_regions.py``'s toy train step under the
program's own scopes), against the stripped one (nothing to read), and
against a synthetic set of four device planes written field by field."""

import json
import os

import pytest

from benchmark import reduce_trace as rt
from benchmark import regions
from benchmark.tests.record_serve_spans import _put

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
MATMUL = os.path.join(FIXTURES, "v5e_matmul_loop.xplane.pb")
REGIONS = os.path.join(FIXTURES, "v5e_regions.xplane.pb")
STRIPPED = os.path.join(FIXTURES, "v5e_serve_spans.xplane.pb")


def _closes(path, out=None):
    """regions + unscoped + xla_inserted against the sum of
    ``reduce_trace.reduce``'s ``ops``, to 1e-6 s."""
    trace = rt.load(path)
    reduced = rt.reduce(trace)
    out = out or regions.from_trace(trace, path)
    binned = sum(sum(out.get(k, {}).values())
                 for k in ("regions", "unscoped", "xla_inserted"))
    assert abs(reduced["n_devices"] * binned
               - sum(reduced["ops"].values())) < 1e-6
    for name, seconds in reduced["device_ops"]:
        assert sum(out["owners"][name].values()) == \
            pytest.approx(seconds, abs=1e-9)
    return reduced, out


# ------------------- a recorded profile without regions ------------------- #


def test_the_metadata_carries_tf_op_source_and_xlas_own_cost():
    ops, = regions.op_metadata(MATMUL).values()
    name, = [n for n in ops if n.startswith("%convolution_tanh_fusion")]
    info, = ops[name]
    assert info["tf_op"] == "jit(bench_fixture_matmul)/dot_general:"
    assert info["hlo_category"] == "convolution fusion"
    assert info["flops"] == 17_188_257_792 >= 2 * 2048 ** 3
    assert info["bytes_accessed"] == 25_165_824
    assert info["source"].endswith("record_fixture.py:9")
    assert info["program_id"] == 14836250070554842513
    # XLA's own copies carry a category and no tf_op
    done, = [n for n in ops if n.startswith("%copy-done")]
    assert ops[done][0]["hlo_category"] == "copy-done"
    assert "tf_op" not in ops[done][0]


def test_without_regions_everything_is_unscoped_or_xla_inserted():
    reduced, out = _closes(MATMUL)
    assert "regions" not in out and "region_named_share" not in out
    assert out["unscoped"] == {"convolution fusion": pytest.approx(
        reduced["ops"]["convolution_tanh_fusion-bf16_2048_2048"])}
    assert set(out["xla_inserted"]) == {"copy-start", "copy-done"}
    assert out["owners"]["copy-done-bf16_2048_2048"] == {
        "xla_inserted": pytest.approx(
            reduced["ops"]["copy-done-bf16_2048_2048"])}
    top, = out["unscoped_top"]
    assert top[0] == "jit(bench_fixture_matmul)/dot_general:" \
        and top[1].endswith("record_fixture.py:9")


def test_a_profile_cut_of_its_metadata_gives_nothing_to_read():
    assert regions.from_trace(rt.load(STRIPPED), STRIPPED) == {}


# -------------------- a recorded profile with regions -------------------- #


@pytest.fixture(scope="module")
def recorded():
    return _closes(REGIONS)


def test_the_recorded_step_splits_by_region_and_closes(recorded):
    reduced, out = recorded
    assert reduced["n_devices"] == 1 and reduced["busy_s"] > 0
    got = {k for k, v in out["regions"].items() if v > 0}
    assert {"norm", "ffn_dense", "loss", "optimizer", "grad_clip"} <= got
    # a fusion carries ONE op_name, its root's: the residual add went
    # into the matmul's fusion and counts under ffn_dense (the known
    # limit), so the region is there at 0.0
    assert out["regions"]["residual"] == 0.0
    assert 0.9 < out["region_named_share"] <= 1.0
    assert out["regions_by_program"] == {
        "jit_bench_fixture_regions": {k: pytest.approx(v) for k, v in
                                      out["regions"].items() if v > 0}}


def test_the_innermost_region_wins(recorded):
    # grad_clip is opened inside optimizer: its reduction is grad_clip's
    _, out = recorded
    ops, = regions.op_metadata(REGIONS).values()
    nested = [i["tf_op"] for infos in ops.values() for i in infos
              if "rg.optimizer/rg.grad_clip" in i.get("tf_op", "")]
    assert nested
    assert out["regions"]["grad_clip"] > 0
    assert set(out["regions_by_pass"]["grad_clip"]) == {"fwd"}


def test_the_three_passes_are_read_from_the_path(recorded):
    _, out = recorded
    for region in ("norm", "ffn_dense"):
        passes = out["regions_by_pass"][region]
        assert set(passes) == {"fwd", "bwd", "remat"}
        assert sum(passes.values()) == pytest.approx(out["regions"][region])
    assert set(out["regions_by_pass"]["optimizer"]) == {"fwd"}


def test_xlas_cost_is_a_yardstick_for_fusions_not_for_pallas(recorded):
    _, out = recorded
    # three matmuls a layer and pass of 2 x 64 x 256 x 256 flops
    assert out["region_flops"]["ffn_dense"] > 3 * 3 * 2 * 64 * 256 * 256
    assert out["region_bytes"]["norm"] > 0
    # the Pallas call is the optimizer's and XLA counts it 0 flops
    owners = {k: v for k, v in out["owners"].items() if k.startswith("halve")}
    assert owners and all(set(v) == {"optimizer"} for v in owners.values())
    ops, = regions.op_metadata(REGIONS).values()
    halve, = [i for n, infos in ops.items() if n.startswith("%halve")
              for i in infos]
    assert halve["flops"] == 0 and halve["hlo_category"] == "custom-call"
    assert halve["tf_op"].endswith("rg.optimizer/halve/pallas_call:")


def test_the_command_prints_one_line(capsys):
    assert regions.main([REGIONS]) == 0
    line = json.loads(capsys.readouterr().out)
    assert abs(line["closure_s"]) < 1e-6 and line["n_devices"] == 1
    assert line["regions"]["ffn_dense"] > 0 and line["device_ops"]
    assert regions.main([]) == 2


# --------------------- four synthetic device planes --------------------- #


def _msg(*fields):
    return b"".join(_put(f, w, v) for f, w, v in fields)


def _entry(key, value):
    return _msg((1, 0, key), (2, 2, value))


STATS = {1: "tf_op", 2: "hlo_category", 3: "program_id", 4: "flops",
         5: "bytes_accessed", 6: "source"}
#: metadata id -> (operation name, program, tf_op, category, flops)
OPS = {
    1: ("%while.1 = (s32[], f32[8]{0}) while(%tuple)", 7, None, "while", 0),
    2: ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 7,
        "jit(step)/rg.attn_proj/rg.attn_core/mul:", "loop fusion", 100),
    3: ("%fusion.2 = f32[8,4]{1,0} fusion(f32[8]{0} %p)", 7,
        "jit(step)/transpose(jvp(rg.loss))/add_any:", "loop fusion", 10),
    # a weight prefetch hoisted into a loop takes the while's op_name
    4: ("%copy-done = f32[16]{0} copy-done(%copy-start)", 7,
        "jit(step)/rg.loop_carry/while:", "copy-done", 0),
    5: ("%fusion.3 = f32[4]{0} fusion(f32[8]{0} %p)", 7, "jit(step)/mul:",
        "loop fusion", 1),
    # the same operation text in another program, under another region
    6: ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 9,
        "jit(other)/checkpoint/rematted_computation/rg.norm/mul:",
        "loop fusion", 1000),
}


def _device_plane(k):
    """Device ``k``: one run of program 7 whose operations take (k + 1)
    x their base microseconds, and on device 0 one run of program 9."""
    us = 1_000_000                      # picoseconds
    scale = k + 1

    def event(meta, start_us, dur_us):
        return (4, 2, _msg((1, 0, meta), (2, 0, start_us * us),
                           (3, 0, dur_us * us)))
    ops = [event(1, 10, 160 * scale),           # the while holds 2 and 3
           event(2, 10, 100 * scale), event(3, 10 + 100 * scale, 50 * scale),
           event(4, 10 + 170 * scale, 30 * scale),
           event(5, 10 + 210 * scale, 20 * scale)]
    runs = [event(101, 5, 300 * scale)]
    if k == 0:
        ops.append(event(6, 2000, 40))
        runs.append(event(102, 1990, 100))
    fields = [(1, 0, k + 1), (2, 2, f"/device:TPU:{k}".encode()),
              (3, 2, _msg((1, 0, 1), (2, 2, b"XLA Modules"), (3, 0, 1000),
                          *runs)),
              (3, 2, _msg((1, 0, 2), (2, 2, b"XLA Ops"), (3, 0, 1000), *ops))]
    for sid, name in STATS.items():
        fields.append((5, 2, _entry(sid, _msg((1, 0, sid),
                                              (2, 2, name.encode())))))
    for mid, (name, program, tf_op, category, flops) in OPS.items():
        stats = [(5, 2, _msg((1, 0, 2), (5, 2, category.encode()))),
                 (5, 2, _msg((1, 0, 3), (3, 0, program))),
                 (5, 2, _msg((1, 0, 4), (4, 0, flops))),
                 (5, 2, _msg((1, 0, 5), (4, 0, 8 * flops)))]
        if tf_op:
            stats.append((5, 2, _msg((1, 0, 1), (5, 2, tf_op.encode()))))
        fields.append((4, 2, _entry(mid, _msg(
            (1, 0, mid), (2, 2, name.encode()), *stats))))
    for mid, name in ((101, b"jit_step(7)"), (102, b"jit_other(9)")):
        fields.append((4, 2, _entry(mid, _msg((1, 0, mid), (2, 2, name)))))
    return _msg(*fields)


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    host = _msg(
        (1, 0, 9), (2, 2, b"/host:CPU"),
        (3, 2, _msg((1, 0, 1), (2, 2, b"main"), (3, 0, 1000),
                    (4, 2, _msg((1, 0, 1), (2, 0, 0),
                                (3, 0, 5000 * 1_000_000))))),
        (4, 2, _entry(1, _msg((1, 0, 1), (2, 2, b"bench:window")))))
    space = _msg(*[(1, 2, _device_plane(k)) for k in range(4)],
                 (1, 2, host))
    path = str(tmp_path_factory.mktemp("synthetic") / "four.xplane.pb")
    with open(path, "wb") as f:
        f.write(space)
    return path


def test_four_devices_divide_by_n_devices_and_close(four_devices):
    reduced, out = _closes(four_devices)
    assert reduced["n_devices"] == 4
    us = 1e-6
    mean = (1 + 2 + 3 + 4) / 4
    # the while is no leaf; its two children are
    assert "while" not in out["xla_inserted"]
    assert out["regions"]["attn_core"] == pytest.approx(100 * us * mean)
    assert out["regions"]["loss"] == pytest.approx(50 * us * mean)
    assert out["regions"]["attn_proj"] == 0.0       # the innermost wins
    assert out["regions_by_pass"]["loss"] == {
        "bwd": pytest.approx(50 * us * mean)}
    assert out["xla_inserted"] == {"copy-done": pytest.approx(30 * us * mean)}
    assert out["unscoped"] == {"loop fusion": pytest.approx(20 * us * mean)}
    # one name, two programs: the run that holds the operation decides
    assert out["regions"]["norm"] == pytest.approx(40 * us / 4)
    assert out["regions_by_pass"]["norm"] == {
        "remat": pytest.approx(40 * us / 4)}
    assert out["regions_by_program"]["jit_other"] == {
        "norm": pytest.approx(40 * us / 4)}
    assert out["regions_by_program"]["jit_step"]["attn_core"] == \
        pytest.approx(100 * us * mean)
    # owners are in device_ops' unit: summed over devices
    assert out["owners"]["fusion-f32_8"] == {
        "attn_core": pytest.approx(100 * us * 10),
        "norm": pytest.approx(40 * us)}
    # XLA's cost times the runs, a device's
    assert out["region_flops"]["attn_core"] == pytest.approx(100.0)
    assert out["region_bytes"]["attn_core"] == pytest.approx(800.0)
    assert out["region_flops"]["norm"] == pytest.approx(1000 / 4)
    named = (100 + 50) * mean + 40 / 4
    assert out["region_named_share"] == pytest.approx(
        named / (named + 20 * mean))
