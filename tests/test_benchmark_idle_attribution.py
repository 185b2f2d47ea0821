"""Collects ``benchmark/tests/test_idle_attribution.py`` under tier-1: the same
test functions, parametrisations and module fixtures, no test logic here."""

from benchmark.tests.test_idle_attribution import *  # noqa: F401,F403
