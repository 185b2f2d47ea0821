"""Collects ``benchmark/tests/test_reduce.py`` under tier-1: the same
test functions, parametrisations and module fixtures, no test logic here."""

from benchmark.tests.test_reduce import *  # noqa: F401,F403
