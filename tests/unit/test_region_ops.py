"""``tools/region_ops.py``: a region of a recorded profile, operation by
operation, adds up to what ``benchmark/regions.py`` gives the region."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("region", ["norm", "ffn_dense", "unscoped"])
def test_a_regions_operations_add_up_to_the_region(region, capsys,
                                                   monkeypatch):
    monkeypatch.chdir(ROOT)
    spec = importlib.util.spec_from_file_location(
        "region_ops", os.path.join(ROOT, "tools", "region_ops.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    from benchmark import regions
    path = os.path.join(ROOT, "benchmark", "fixtures",
                        "v5e_regions.xplane.pb")
    tool.main([path, region, "1000"])
    head, *rows = capsys.readouterr().out.strip().splitlines()
    want = regions.read(path)
    want = want["regions"][region] if region in want["regions"] \
        else sum(want["unscoped"].values())
    assert want > 0 and rows
    assert float(head.split()[1]) == pytest.approx(want * 1e3, rel=1e-3)
    assert sum(float(r.split()[0]) for r in rows) == pytest.approx(
        want * 1e3, rel=1e-2)
    assert all(r.split()[2] in ("fwd", "bwd", "remat") for r in rows)
