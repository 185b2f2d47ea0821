"""Tests of the benchmark itself, on the CPU at tiny sizes:
``python -m pytest benchmark/tests -q``.  Nothing here touches a TPU topology
while a module is imported."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
