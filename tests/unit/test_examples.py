"""The examples/ ladder stays green (each script self-verifies: loss
drops / memory claims hold). Subprocess runs, full tier."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

EXAMPLES = ["cifar_pipeline.py", "bert_zero1.py",
            "llama7b_serve_woq.py", "mixtral_ep_ulysses.py"]


@pytest.mark.full
@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
