"""The reduction from a trace to numbers, the operation and byte counts, and
the table of peaks."""

import json
import os

import pytest

from benchmark import lib, serve_stats, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _hand_made():
    """Two programs on one chip in a 100 us window (times in ns):
    ``_tick`` runs 10-30 us (two ops, a 500 ns pause between them) and
    60-70 us; ``_chunk`` runs 40-50 us.  The host thread holds
    ``engine.step`` 5-52 us with a readback 30-39 us inside it, and
    ``engine.step`` 58-72 us."""
    return {
        "window": [0, 100_000],
        "devices": {"/device:TPU:0": {
            "modules": [["jit__tick(11)", 10_000, 20_000],
                        ["jit__chunk(12)", 40_000, 10_000],
                        ["jit__tick(11)", 60_000, 10_000]],
            "ops": [["%fusion.1 = bf16[8]{0} fusion(...)", 10_000, 9_000],
                    ["%copy.3 = bf16[8]{0} copy(...)", 19_500, 10_500],
                    ["%fusion.7 = f32[8]{0} fusion(...)", 40_000, 10_000],
                    ["%fusion.1 = bf16[8]{0} fusion(...)", 60_000, 10_000]],
            "async": [["%all-reduce-start.2 = f32[4]{0} all-reduce-start(...)",
                       45_000, 20_000]]}},
        "host": {"python3/1": [["engine.step", 5_000, 47_000],
                               ["np.asarray(jax.Array)", 30_000, 9_000],
                               ["engine.step", 58_000, 14_000]],
                 "worker/2": [["ReadSyncFlag", 0, 100_000]]}}


def test_busy_idle_programs_and_gaps_of_a_hand_made_trace():
    red = trace_reduce.reduce(_hand_made(), spans=("engine.step",))
    us = 1e-6
    assert red["window_s"] == pytest.approx(100 * us)
    assert red["busy_s"] == pytest.approx(39.5 * us)   # 9 + 10.5 + 10 + 10
    assert red["programs"]["_tick"]["count"] == 2
    assert red["programs"]["_tick"]["total_s"] == pytest.approx(30 * us)
    assert red["programs"]["_chunk"]["total_s"] == pytest.approx(10 * us)
    assert red["ops"]["fusion"] == pytest.approx(29 * us)
    assert red["ops"]["copy"] == pytest.approx(10.5 * us)
    assert red["device_ops"][0][0] == "fusion"
    assert red["span_totals"]["engine.step"] == (2, pytest.approx(61 * us))
    gaps = dict(red["idle_gaps"])
    # idle: 0-10 us (middle 5 us: engine.step is just open), 19-19.5 (under a
    # microsecond: the device's own), 30-40 (middle 35: the readback,
    # innermost), 50-60 (middle 55: nothing open), 70-100 (nothing open)
    assert gaps == {
        "engine.step": pytest.approx(10 * us),
        trace_reduce.BETWEEN_OPS: pytest.approx(0.5 * us),
        "np.asarray(jax.Array)": pytest.approx(10 * us),
        trace_reduce.NO_SPAN: pytest.approx(40 * us)}
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(100 * us)
    # only the thread that carries the program's spans names a gap
    assert "ReadSyncFlag" not in gaps
    # asynchronous operations are kept from start to done
    assert trace_reduce.union_seconds(
        red["op_intervals"]["all-reduce-start"]) == pytest.approx(20 * us)


def test_names():
    assert trace_reduce.op_name(
        "%convolution_tanh_fusion.3 = bf16[2048,4096]{1,0} fusion(...)") \
        == "convolution_tanh_fusion"
    assert trace_reduce.op_name("%copy-start = (bf16[4096]) copy-start(%w)") \
        == "copy-start"
    assert trace_reduce.program_name("jit__tick(5269204718465959385)") \
        == "_tick"
    assert trace_reduce.program_name("jit_smapped(1)") == "smapped"


@pytest.mark.parametrize("name", ["trace_v5e_serve.json",
                                  "trace_v5e_train.json"])
def test_recorded_chip_trace(name):
    """A slice of a real trace of the chat cell and of the training cell on
    the v5e, as ``load_xplane`` gave it."""
    with open(os.path.join(DATA, name)) as f:
        raw = json.load(f)
    red = trace_reduce.reduce(raw, spans=("engine.step", "route",
                                          "train.dispatch", "train.wait"))
    assert 0.0 < red["busy_s"] <= red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    assert red["programs"] and red["device_ops"]
    if "serve" in name:
        assert {"_tick", "_chunk"} <= set(red["programs"])
        assert red["span_totals"]["engine.step"][0] >= 1


def test_resnet50_operation_count_matches_a_hand_count():
    fam = lib.load_module("families", "resnet_train")
    cfg = lib.load_json("configs", "resnet50.json")
    # By hand, multiply-adds of one 224x224 image, forward:
    stem = 112 * 112 * 49 * 3 * 64                                # 118.0 M
    s1 = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) \
        + 2 * 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    s2 = (56 * 56 * 256 * 128 + 28 * 28 * (9 * 128 * 128 + 128 * 512
                                            + 256 * 512)
          + 3 * 28 * 28 * (512 * 128 + 9 * 128 * 128 + 128 * 512))
    s3 = (28 * 28 * 512 * 256 + 14 * 14 * (9 * 256 * 256 + 256 * 1024
                                            + 512 * 1024)
          + 5 * 14 * 14 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024))
    s4 = (14 * 14 * 1024 * 512 + 7 * 7 * (9 * 512 * 512 + 512 * 2048
                                           + 1024 * 2048)
          + 2 * 7 * 7 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048))
    macs = stem + s1 + s2 + s3 + s4 + 2048 * 1000
    assert 4.0e9 < macs < 4.2e9             # the well-known 4.1 GMACs
    assert fam.flops_per_item(cfg) == 6.0 * macs
    assert fam.flops_per_item(cfg) == pytest.approx(24.6e9, rel=0.01)


def test_tick_byte_count_matches_a_hand_count():
    fam = lib.load_module("families", "llama_serve")
    cfg = lib.load_json("configs", "mistral-7b-v0.3.json")
    layer = (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
             + 2 * 4096)                    # 218.1 M parameters
    assert layer == 218_112_000
    weights = (24 * layer + 4096 + 4096 * 32768) * 2
    assert fam.weight_bytes(cfg) == weights
    assert weights == pytest.approx(10.74e9, rel=0.01)
    assert fam.kv_bytes_per_token(cfg) == 2 * 24 * 8 * 128 * 2   # 98,304
    assert fam.tick_bytes(cfg, 1000.0) == weights + 98_304_000


def test_unknown_device_kind_is_an_error():
    assert lib.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert lib.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in"):
        lib.peaks("TPU v9 imaginary")


def test_no_share_of_a_peak_passes_100_silently():
    assert lib.share_of_peak(50.0, 200.0, "x") == 25.0
    with pytest.raises(ValueError, match="of the peak"):
        lib.share_of_peak(201.0, 200.0, "x")
    rec = {"items": 1e6, "window_s": 1.0, "chips": 1,
           "flops_per_item": 24.6e9, "device_kind": "TPU v5 lite"}
    with pytest.raises(ValueError):
        lib.load_module("layer_metrics", "mfu_pct").read(rec)
    rec["items"] = 2585.0
    assert lib.load_module("layer_metrics", "mfu_pct").read(rec) == \
        pytest.approx(32.28, rel=0.01)
    # a tick that claims more bytes a second than the memory has
    tick = {"window": (0.0, 10.0), "steps": [(0.0, 1.0, 4, 0, 0)],
            "requests": [], "weight_bytes": 10.7e9,
            "kv_bytes_per_token": 98304, "device_kind": "TPU v5 lite",
            "trace": {"programs": {"_tick": {"count": 1, "total_s": 0.001}}}}
    with pytest.raises(ValueError):
        serve_stats.tick_roofline_pct(tick)
    tick["trace"]["programs"]["_tick"]["total_s"] = 0.047
    assert serve_stats.tick_roofline_pct(tick) == pytest.approx(27.8, rel=0.01)
