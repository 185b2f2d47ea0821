"""The benchmark tests that count the devices, run as ``python -m pytest
benchmark/tests`` runs them: under ``benchmark/tests/conftest.py``'s four
virtual devices, where this suite has eight."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_resnet_train_step_matches_the_reference_on_its_own_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # the benchmark's conftest sets its own
    r = subprocess.run(
        [sys.executable, "-m", "pytest",
         "benchmark/tests/test_reference.py", "-k", "resnet_train_step",
         "-q", "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert "2 passed" in r.stdout, r.stdout[-2000:]
