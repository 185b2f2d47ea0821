"""``run.py`` end to end on the CPU at toy sizes (the look for a chip patched
by this test, in a child process): the result line's keys, the four-chip
driver on four virtual devices, and a configuration, a traffic mix and a
per-layer metric added as new files with no edit to a file that is there."""

import json
import os
import shutil

import pytest

import rehearse

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearse.make_copy(str(tmp_path_factory.mktemp("bench_copy")))


def _ok(rc, last, out, err):
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last is not None
    return last


@pytest.mark.parametrize("cell,e2e", [
    ("resnet50_train", {"train_tput", "setup_s"}),
    ("mistral7b_chat", {"ttft_mean_ms", "itl_p90_ms", "setup_s"}),
    ("mistral7b_longdoc", {"serve_tput", "setup_s"})])
def test_result_line_has_exactly_the_contracts_keys(copy, cell, e2e):
    last = _ok(*rehearse.run_in_copy(copy, cell, seed=2**31 + 77))
    assert set(last) == KEYS
    assert set(last["device"]) == DEVICE_KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == e2e
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_four_chip_driver_runs_on_four_virtual_devices(copy):
    last = _ok(*rehearse.run_in_copy(copy, "resnet50_dp4", devices=4))
    assert last["correct"] is True
    assert set(last["metrics"]) == {"train_tput", "setup_s"}
    # with fewer devices than the cell asks for there is no result line
    rc, last, out, err = rehearse.run_in_copy(copy, "resnet50_dp4", devices=2)
    assert rc != 0 and last is None


def test_any_platform_but_tpu_is_refused_with_no_result_line(copy):
    rc, last, out, err = rehearse.run_in_copy(copy, "resnet50_train",
                                              patch_device=False)
    assert rc != 0 and last is None
    assert "needs 1 TPU chip" in err and "platform='cpu'" in err
    assert '"correct"' not in out


def test_traced_run_reports_per_layer_metrics_and_a_breakdown(copy):
    """The capture runs for real on the CPU; the chip's part of the trace is
    the recorded one (tests/data), reduced by the same code."""
    last = _ok(*rehearse.run_in_copy(copy, "mistral7b_chat", trace=1))
    assert set(last) == KEYS | {"breakdown"}
    assert set(last["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < last["device"]["busy_s"] <= last["device"]["window_s"]
    spec = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"]
            if "mistral7b_chat" in m["workloads"]}
    assert set(last["metrics"]) <= want
    assert {"gen_late_p99_ms", "route_ms_p50", "ttft_p90_ms", "rows_per_tick",
            "prefix_skip_pct", "tick_dev_ms", "chunk_dev_ms",
            "device_idle_pct.lat", "hbm_peak_gb.lat"} <= set(last["metrics"])
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(last["breakdown"]["device_ops"]) <= 10
    assert all(len(e) == 2 for e in last["breakdown"]["idle_gaps"])


def test_a_cell_a_mix_a_config_and_a_metric_come_as_new_files(copy, tmp_path):
    """A later PR adds files and entries and edits no file that is there:
    every file of the copy but BENCHMARK.json keeps its bytes."""
    dst = str(tmp_path / "grown")
    shutil.copytree(copy, dst, ignore=shutil.ignore_patterns(".jax_cache"))
    b = os.path.join(dst, "benchmark")
    before = {}
    for root, _, files in os.walk(b):
        for f in files:
            p = os.path.join(root, f)
            before[p] = open(p, "rb").read()
    cfg = json.load(open(os.path.join(b, "configs", "mistral-7b-v0.3.json")))
    cfg.update(name="tiny-wide", hidden_size=96, num_attention_heads=6,
               head_dim=16, num_key_value_heads=3, num_hidden_layers=3)
    json.dump(cfg, open(os.path.join(b, "configs", "tiny-wide.json"), "w"))
    mix = json.load(open(os.path.join(b, "traffic", "chat.json")))
    mix["arrivals"] = {"process": "fixed_rate", "rate_rps": 9.0}
    json.dump(mix, open(os.path.join(b, "traffic", "steady.json"), "w"))
    with open(os.path.join(b, "layer_metrics", "steps_per_s.py"), "w") as f:
        f.write('"""Engine steps per second of the window."""\n\n\n'
                "def read(rec):\n"
                "    lo, hi = rec['window']\n"
                "    n = sum(1 for s in rec['steps'] if lo <= s[1] <= hi)\n"
                "    return n / (hi - lo)\n")
    spec = json.load(open(os.path.join(dst, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny-wide", "source": "test",
                            "file": "benchmark/configs/tiny-wide.json",
                            "reduced": ["num_hidden_layers"], "why": "test"})
    spec["workloads"].append({"name": "tiny_steady", "config": "tiny-wide",
                              "traffic": "steady", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "itl_p90_ms"):
            m["workloads"].append("tiny_steady")
    spec["per_layer"].append({
        "name": "steps_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "scheduler", "moves": "itl_p90_ms",
        "workloads": ["tiny_steady"]})
    json.dump(spec, open(os.path.join(dst, "BENCHMARK.json"), "w"))

    last = _ok(*rehearse.run_in_copy(dst, "tiny_steady"))
    assert last["correct"] is True
    assert set(last["metrics"]) == {"ttft_mean_ms", "itl_p90_ms", "setup_s"}
    last = _ok(*rehearse.run_in_copy(dst, "tiny_steady", trace=1))
    assert set(last["metrics"]) == {"steps_per_s"}
    assert last["metrics"]["steps_per_s"]["value"] > 0
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    """Without the program (only BENCHMARK.json and the files under paths)
    the command exits non-zero and prints no result."""
    import subprocess
    import sys
    dst = str(tmp_path)
    rehearse.make_copy(dst)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet50_train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=dst, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
