"""Smoke-run every example script: the examples double as end-to-end
lifecycle drives (the reference exercises its notebooks in CI via the
docs build; here the scripts run directly)."""

import os
import subprocess
import sys

import pytest

EXAMPLES_DIR = os.path.join(os.path.dirname(__file__), "..", "examples")
EXAMPLES = sorted(
    f for f in os.listdir(EXAMPLES_DIR) if f.endswith(".py")
)


@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # examples run on the host CPU backend
    out = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES_DIR, script)],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert out.returncode == 0, f"{script} failed:\n{out.stderr[-2000:]}"
