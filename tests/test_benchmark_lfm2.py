"""Collects ``benchmark/tests/test_lfm2.py`` under tier-1: the same test
functions, parametrisations and module fixtures; the one test that pins the
cell's list of per-layer metrics is taken with the step log's metrics counted
apart (``benchmark_cells.py`` says why)."""

import os
import sys

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))    # `import rehearse`

from benchmark.tests.test_lfm2 import *  # noqa: E402,F401,F403
from benchmark.tests.test_lfm2 import CELL  # noqa: E402

import benchmark_cells  # noqa: E402


def test_the_traced_run_reports_the_cells_per_layer_metrics(copy):  # noqa: F811
    last = benchmark_cells.traced_cell(copy, CELL, ".lfm2", {
        "tick_dev_ms.lfm2", "chunk_dev_ms.lfm2", "rows_per_tick.lfm2",
        "sched_host_ms.lfm2", "moe_experts_touched_pct.lfm2",
        "moe_load_max_over_mean.lfm2", "prefix_skip_pct.lfm2",
        "device_idle_pct.lfm2", "hbm_peak_gb.lfm2"})
    assert 0.0 < last["metrics"]["moe_experts_touched_pct.lfm2"]["value"] \
        <= 100.0
    assert last["metrics"]["prefix_skip_pct.lfm2"]["value"] > 0.0
