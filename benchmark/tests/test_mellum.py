"""The ``mellum_train`` family on the CPU at toy sizes: the cell end to end
through ``run.py`` (this file cuts its own configuration and traffic in the
copy; the kernels run in the Pallas interpreter, which the child's start-up
lines turn on; ``test_mellum_faults.py`` has the whole runs with the trained
path broken), the configuration against the catalog's row and its own
``deployment``, the family's operation counts against a hand count, and the
new readers on a recorded ``rec``."""

import json
import os

import pytest

import rehearse
from benchmark import lib

CELL = "mellum2_pretrain8k"
CONFIG = os.path.join(rehearse.ROOT, "benchmark", "configs",
                      "mellum2-12b-a2.5b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 128, "num_experts_published": 16,
    "num_experts": 8, "num_experts_per_tok": 4, "sliding_window": 40,
    "vocab_size": 64}
#: the Pallas interpreter for the child (Mosaic has no CPU target), and
#: blocks that cut the toy's 288 positions
INTERPRET = (
    "import importlib\n"
    "importlib.import_module('horovod_tpu.parallel.flash_attention')"
    "._interpret = True\n"
    "import horovod_tpu.models.moe_decoder as _md, dataclasses\n"
    "_C = _md.MoEDecoderConfig\n"
    "_md.MoEDecoderConfig = lambda **k: _C(**dict(k, block_q=32, block_k=32,"
    " xent_chunk=48))\n")


def make_copy(dst: str) -> str:
    rehearse.make_copy(dst)
    b = os.path.join(dst, "benchmark")

    def config(d):
        d.update(TINY)
        d["training"].update(seq_len=288, compute_dtype="float32")
        d["training"]["optimizer"].update(lr=1e-4, warmup_steps=4)

    rehearse._edit(os.path.join(b, "configs", "mellum2-12b-a2.5b.json"),
                   config)
    rehearse._edit(os.path.join(b, "traffic", "packed8k_b4.json"),
                   lambda d: d.update(per_chip_batch=2, warm_steps=1,
                                      trace_s=0.2))
    return dst


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("bench_mellum")))


def _ok(rc, last, out, err):
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last is not None
    return last


def test_the_cell_runs_untraced_and_is_correct(copy):
    rc, last, out, err = rehearse.run_in_copy(copy, CELL, seed=2**31 + 11,
                                              extra=INTERPRET)
    last = _ok(rc, last, out, err)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"train_tput", "setup_s"}
    for name in ("loss_rel", "grad_norm_worst", "grad_norm_median",
                 "delta_norm_worst", "programs_compiled_in_window"):
        assert f"check {name}: value=" in out and "NOT CORRECT" not in out


def test_the_traced_run_reports_the_cells_per_layer_metrics(copy):
    """The chip's part of the trace is the recorded one (another program's:
    it holds none of this family's kernels, so their three shares are left
    out of the line and nothing raises); the counters are this step's own."""
    last = _ok(*rehearse.run_in_copy(copy, CELL, trace=1, extra=INTERPRET))
    spec = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert len(want) == 10
    assert {"step_ms", "mfu_pct", "device_idle_pct.train", "hbm_peak_gb.train",
            "attn_key_blocks_pct.mellum", "moe_held_share_pct.mellum",
            "moe_load_max_over_mean.mellum"} == set(last["metrics"])
    m = last["metrics"]
    assert 30 < m["moe_held_share_pct.mellum"]["value"] < 70    # 8 of 16 held
    assert 1.0 <= m["moe_load_max_over_mean.mellum"]["value"] < 4.0
    # 9 blocks of 32: 45 pairs causal, a band of 40 visits 1 + 2 + 7 * 3
    assert m["attn_key_blocks_pct.mellum"]["value"] == pytest.approx(
        100 * (3 * 24 + 45) / (4 * 45))


def test_the_configuration_is_the_catalogs_row_cut_as_the_file_says():
    with open(CONFIG) as f:
        cfg = json.load(f)
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == row["source_url"]
    spec = lib.benchmark_spec()
    entry = lib.find(spec["configs"], "mellum2-12b-a2.5b", "configuration")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value
            assert cfg[key + "_published"] == value
        else:
            assert cfg[key] == value, key
    # the floors: a whole period and four layers, 8 experts, an eighth
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert cfg["num_hidden_layers"] == 4 and cfg["num_experts"] == 16 >= 8
    assert cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert cfg["chips_sharing_a_layer"] * cfg["num_experts"] == 64
    assert cfg["chips_sharing_a_layer"] * cfg["vocab_size"] == 98304
    # the deployment's arithmetic is the tree's
    fam = lib.load_module("families", "mellum_train")
    from horovod_tpu.models import moe_decoder as md

    n = md.param_count(fam.model_config(cfg))
    assert n == 595_153_152
    assert "595.1 M parameters" in cfg["deployment"]
    assert "9.52 GB" in cfg["deployment"] and round(16 * n / 1e9, 2) == 9.52


def test_the_traffic_is_the_issues():
    spec = lib.benchmark_spec()
    cell = lib.find(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-a2.5b", "packed8k_b4", 1)
    mix = lib.load_json("traffic", "packed8k_b4.json")
    assert mix["driver"] == "train_job_counters"
    assert (mix["per_chip_batch"], mix["check_steps"], mix["warm_steps"],
            mix["in_flight"], mix["trace_s"]) == (4, 3, 5, 2, 3.0)
    with open(CONFIG) as f:
        assert json.load(f)["training"]["seq_len"] == 8192
    for name in ("train_tput", "step_ms", "mfu_pct", "device_idle_pct.train",
                 "hbm_peak_gb.train"):
        entries = spec["end_to_end"] + spec["per_layer"]
        assert CELL == lib.find(entries, name, "metric")["workloads"][-1]


def test_the_operation_counts_are_a_hand_count():
    with open(CONFIG) as f:
        cfg = json.load(f)
    fam = lib.load_module("families", "mellum_train")
    t, h, dh, d, f = 8192, 32, 128, 2304, 896
    band = 1024 * 1025 // 2 + (t - 1024) * 1024
    full = t * (t + 1) // 2
    assert fam.visible_pairs(t, 1024) == band
    assert fam.visible_pairs(t, None) == full
    assert fam.visible_pairs(16, 100) == 16 * 17 // 2
    proj = d * dh * (2 * 32 + 2 * 4)
    macs = (4 * proj + 2 * dh * h * (3 * band + full) / t
            + 4 * 8 * 16 / 64 * 3 * d * f + d * 24576)
    assert fam.flops_per_item(cfg) == pytest.approx(6 * macs)
    assert 1.4e9 < fam.flops_per_item(cfg) < 1.6e9      # the issue's 1.5
    ops = fam.kernel_ops(cfg, {"per_chip_batch": 4})
    assert ops["flash_band_fwd"] == 2 * 2 * dh * 4 * h * band
    assert ops["flash_dkv"] == 4 * 2 * dh * 4 * h * full
    assert ops["grouped_swiglu_dx"] == 5 * 2 * d * f


def _rec():
    fam = lib.load_module("families", "mellum_train")
    with open(CONFIG) as f:
        cfg = json.load(f)
    kernels = {n: {"count": 12, "total_s": 0.3}
               for names in fam.KERNELS.values() for n in names}
    counters = {"moe.choices_total": 4000, "moe.choices_held": 1000,
                "moe.expert_calls": 16, "attn.key_blocks_visited": 271,
                "attn.key_blocks_causal": 544}
    counters.update({f"moe.held_load.{e}": 50 + 5 * (e == 3)
                     for e in range(16)})
    return {"device_kind": "TPU v5 lite", "steps": 1, "counters": counters,
            "traced_counters": dict(counters, **{"moe.choices_held": 1000}),
            "kernels": kernels, "kernel_names": fam.KERNELS,
            "kernel_ops": fam.kernel_ops(cfg, {"per_chip_batch": 4})}


READERS = ["flash_band_mfu_pct.mellum", "flash_full_mfu_pct.mellum",
           "experts_mfu_pct.mellum", "attn_key_blocks_pct.mellum",
           "moe_held_share_pct.mellum", "moe_load_max_over_mean.mellum"]


def test_the_new_readers_on_a_recorded_run():
    rec = _rec()
    read = {n: lib.load_module("layer_metrics", n).read(rec) for n in READERS}
    assert read["moe_held_share_pct.mellum"] == 25.0
    assert read["attn_key_blocks_pct.mellum"] == pytest.approx(49.8, abs=0.1)
    assert read["moe_load_max_over_mean.mellum"] == pytest.approx(
        55 / (805 / 16))
    peak = lib.peaks("TPU v5 lite")["bf16_flops_per_s"]
    ops = rec["kernel_ops"]
    band = sum(ops[n] for n in ("flash_band_fwd", "flash_band_dq",
                                "flash_band_dkv"))
    assert read["flash_band_mfu_pct.mellum"] == pytest.approx(
        100 * 12 * band / 0.9 / peak)
    rows = 1000 / 16
    assert read["experts_mfu_pct.mellum"] == pytest.approx(
        100 * 12 * rows * 11 * 2 * 2304 * 896 / 0.9 / peak)
    assert 0 < read["flash_full_mfu_pct.mellum"] <= 100


def test_a_share_over_the_peak_is_an_error_not_a_clip():
    rec = _rec()
    for k in rec["kernels"].values():
        k["total_s"] = 1e-5
    with pytest.raises(ValueError, match="of the peak"):
        lib.load_module("layer_metrics", READERS[0]).read(rec)


@pytest.mark.parametrize("name", READERS)
def test_the_new_readers_find_nothing_on_a_program_without_the_counters(name):
    """Another family's record, or the parent's: no counter, no kernel."""
    for rec in ({"device_kind": "TPU v5 lite", "steps": 3, "trace": None},
                {"device_kind": "TPU v5 lite", "steps": 3, "counters": {},
                 "kernels": {}, "kernel_names": {}, "kernel_ops": {}}):
        assert lib.load_module("layer_metrics", name).read(rec) is None


def test_the_kernels_times_come_from_the_operations_that_hold_their_names():
    driver = lib.load_module("drivers", "train_job_counters")
    reduced = {"op_intervals": {
        "checkpoint_jvp_flash_band_fwd_": [(0, 1e6), (2e6, 3e6)],
        "transpose_jvp_flash_band_dq__": [(0, 5e5)],
        "jvp_flash_fwd_": [(0, 2e6)],
        "grouped_swiglu": [(0, 1e6)], "grouped_swiglu_dx": [(0, 3e6)],
        "fusion": [(0, 9e9)]}}
    fam = lib.load_module("families", "mellum_train")
    got = driver.kernel_times(reduced, [
        n for names in fam.KERNELS.values() for n in names])
    assert got["flash_band_fwd"] == {"count": 2, "total_s": 0.002}
    assert got["flash_band_dq"] == {"count": 1, "total_s": 0.0005}
    assert got["flash_fwd"] == {"count": 1, "total_s": 0.002}
    assert got["grouped_swiglu"] == {"count": 1, "total_s": 0.001}
    assert got["grouped_swiglu_dx"] == {"count": 1, "total_s": 0.003}
    assert got["grouped_swiglu_dw"]["count"] == 0
