"""Collects ``benchmark/tests/test_mellum.py`` under tier-1: the same test
functions, parametrisations and module fixtures."""

import os
import sys

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))    # `import rehearse`

from benchmark.tests.test_mellum import *  # noqa: E402,F401,F403
