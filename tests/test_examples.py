"""Every examples/ script must run end-to-end (reference: ray's doc/code
examples are exercised in CI)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(_REPO, "examples"))
    if f.endswith(".py"))


@pytest.mark.parametrize("script", _EXAMPLES)
@pytest.mark.timeout(420)
def test_example_runs(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the examples run on the CPU in tests
    env["RAY_TPU_WORKER_QUIET"] = "1"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", script)],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, (script, proc.stderr[-3000:])
    assert f"OK: {script[:-3]}" in proc.stdout, (script, proc.stdout[-1000:])
