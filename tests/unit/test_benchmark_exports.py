"""Tier-1 twin of ``benchmark/tests/test_rehearse_exports.py`` (which the
driver's tier-1 command, ``pytest tests/``, never collects): a program PR
that renames or drops a counter a per-layer reader names fails HERE, on
the CPU, and not first as a metric gone silent on the ledger. One
rehearsed cell a job kind; the test itself is the benchmark's, imported
(job kind ``train_sparse``'s from its own cell's test file, PR 61).

And the regions' keys (``benchmark/regions.from_trace``, which
``run.py::read_trace`` calls on every traced run since PR 54; twelve
per-layer entries read them): for every name of the program's closed vocabulary, what the reader of a
chip-recorded profile hands out is addressed by the readers' dotted keys
and reduced by the two reducers a share of busy needs, and a profile of
a program without regions gives them nothing to read (the metric is left
out of the line, nothing raises).
"""

import os

import pytest

from benchmark import readers, regions
from benchmark.tests.test_afmoe_cell import (  # noqa: F401
    test_the_rehearsal_fills_every_key_the_cells_readers_name as
    test_the_train_sparse_kinds_rehearsal_fills_every_key)
from benchmark.tests.test_rehearse_exports import (  # noqa: F401
    test_a_rehearsal_fills_every_key_its_cells_readers_name)
from deepspeed_tpu.telemetry.trace import REGIONS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _keep_the_environment(monkeypatch):
    """``run.run_cell(["--rehearse", ...])`` edits the process's
    environment (it is a command line's entry point); later tests of this
    worker get it back as it was."""
    for key in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR"):
        if key in os.environ:
            monkeypatch.setenv(key, os.environ[key])
        else:
            monkeypatch.delenv(key, raising=False)


FIXTURES = os.path.join(ROOT, "benchmark", "fixtures")


@pytest.fixture(scope="module")
def with_regions():
    return {"trace": regions.read(
        os.path.join(FIXTURES, "v5e_regions.xplane.pb"))}


@pytest.fixture(scope="module")
def without_regions():
    return {"trace": regions.read(
        os.path.join(FIXTURES, "v5e_matmul_loop.xplane.pb"))}


def _share(region):
    return {"reducer": "ratio", "num": [f"trace.regions.{region}"],
            "den": ["trace.busy_s"], "scale": 100.0}


#: the regions of the recorded toy step (``benchmark/tests/record_regions.py``)
RECORDED = ("norm", "ffn_dense", "loss", "grad_clip", "optimizer")
NAMED = {"reducer": "value", "key": "trace.region_named_share",
         "scale": 100.0}


@pytest.mark.parametrize("region", REGIONS)
def test_a_regions_share_of_busy_reads_through_the_ratio_reducer(
        region, with_regions, without_regions):
    value = readers.read(_share(region), with_regions)
    # every name of the vocabulary is there once any is, at 0.0 where the
    # recorded toy step has nothing of it
    assert value is not None and 0.0 <= value <= 100.0
    assert (value > 0) == (region in RECORDED)
    assert readers.read(_share(region), without_regions) is None
    assert readers.read(_share(region), {}) is None


def test_the_named_share_reads_through_the_value_reducer(
        with_regions, without_regions):
    assert 0 < readers.read(NAMED, with_regions) <= 100.0
    assert readers.read(NAMED, without_regions) is None
    shares = sum(readers.read(_share(r), with_regions) for r in REGIONS)
    assert 0 < shares <= 100.0 + 1e-9
