"""Collects ``benchmark/tests/test_kexaone.py`` under tier-1: the same test
functions, parametrisations and module fixtures; the one test that pins the
cell's list of per-layer metrics is taken with the step log's metrics counted
apart (``benchmark_cells.py`` says why)."""

import os
import sys

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))    # `import rehearse`

from benchmark.tests.test_kexaone import *  # noqa: E402,F401,F403
from benchmark.tests.test_kexaone import CELL  # noqa: E402

import benchmark_cells  # noqa: E402


def test_the_traced_run_reports_the_cells_per_layer_metrics(copy):  # noqa: F811
    last = benchmark_cells.traced_cell(copy, CELL, ".kexaone", {
        "tick_dev_ms.kexaone", "chunk_dev_ms.kexaone",
        "rows_per_tick.kexaone", "moe_held_share_pct.kexaone",
        "moe_load_max_over_mean.kexaone", "kv_bytes_per_live_token.kexaone",
        "prefix_skip_pct.kexaone", "device_idle_pct.kexaone",
        "hbm_peak_gb.kexaone"})
    assert 0.0 < last["metrics"]["moe_held_share_pct.kexaone"]["value"] < 100.0
    assert last["metrics"]["prefix_skip_pct.kexaone"]["value"] > 0.0
    assert last["metrics"]["kv_bytes_per_live_token.kexaone"]["value"] > 0.0
