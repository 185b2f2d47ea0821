"""Collects ``benchmark/tests/test_traffic.py`` under tier-1: the same
test functions, parametrisations and module fixtures, no test logic here."""

from benchmark.tests.test_traffic import *  # noqa: F401,F403
