"""Collects ``benchmark/tests/test_reference.py`` under tier-1: the same
test functions, parametrisations and module fixtures, no test logic here.
``test_resnet_train_step_matches_the_reference`` wants the benchmark's four
devices, not this suite's eight: ``tests/test_benchmark_four_devices.py``
runs it as the benchmark does."""

from benchmark.tests.test_reference import *  # noqa: F401,F403

del test_resnet_train_step_matches_the_reference  # noqa: F821
