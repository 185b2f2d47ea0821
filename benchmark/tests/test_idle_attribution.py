"""The ``idle_*_pct`` readers on two recorded traces of the chat cell on the
v5e: one taken with the program's own spans (``serve.step.*``,
``replica.pump.*``, ``router.*``) and the older one, from before the program
had any, in which only the benchmark's wrappers and jax's spans name a gap."""

import json
import os

import pytest

from benchmark import lib, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LAYERS = ("idle_front_door_pct", "idle_sched_pct", "idle_readback_pct")
ALL = LAYERS + ("idle_unattributed_pct",)


def _rec(name: str) -> dict:
    with open(os.path.join(DATA, name)) as f:
        raw = json.load(f)
    return {"trace": trace_reduce.reduce(raw, spans=("engine.step", "route"))}


def _read(metric: str, rec: dict):
    return lib.load_module("layer_metrics", metric).read(rec)


@pytest.mark.parametrize("name", ["trace_v5e_serve_phases.json",
                                  "trace_v5e_serve.json"])
def test_the_four_shares_add_up_to_the_idle_share(name):
    rec = _rec(name)
    tr = rec["trace"]
    between = dict(tr["idle_gaps"]).get(trace_reduce.BETWEEN_OPS, 0.0)
    parts = [_read(m, rec) for m in ALL]
    assert all(p is not None and p >= 0.0 for p in parts)
    assert sum(parts) == pytest.approx(
        lib.device_idle_pct(rec) - 100.0 * between / tr["window_s"],
        rel=1e-9)
    # the twins of the cells judged by serve_tput read the same
    for m, p in zip(ALL, parts):
        assert _read(m + ".tput", rec) == p
    # and without a trace there is nothing to read
    assert all(_read(m, {"trace": None}) is None for m in ALL)


@pytest.mark.parametrize("name", ["trace_v5e_serve_phases.json",
                                  "trace_v5e_serve.json"])
def test_no_gap_name_is_claimed_twice(name):
    mods = [lib.load_module("layer_metrics", m) for m in ALL]
    names = [n for n, _ in _rec(name)["trace"]["idle_gaps"]
             if n != trace_reduce.BETWEEN_OPS]
    assert names
    for n in names:
        assert sum(bool(m.claims(n)) for m in mods) == 1, n


def test_with_the_programs_spans_next_to_nothing_is_left_unnamed():
    rec = _rec("trace_v5e_serve_phases.json")
    gaps = dict(rec["trace"]["idle_gaps"])
    assert _read("idle_unattributed_pct", rec) < 1.0
    # the pump between two steps and the step's phases name the gaps that the
    # wrappers alone left to no_host_span and engine.step
    assert any(n.startswith("replica.pump.") for n in gaps)
    assert any(n.startswith("serve.step.") for n in gaps)
    assert _read("idle_front_door_pct", rec) > 0.0
    assert _read("idle_readback_pct", rec) > 0.0
    assert gaps.get("engine.step", 0.0) < 0.1 * sum(gaps.values())


def test_the_split_follows_the_traces_clock_alignment_the_sum_does_not():
    """One idle gap of the chat cell is the host's whole turn-round between
    two ticks (readback tail, postprocess, bookkeeping, pump, admit,
    dispatch: 3-4 ms) and goes whole to the span at its middle; the
    profiler lines the device's clock up with the host's to about a
    millisecond, differently in each trace (PERF.md, PR 25).  With the
    device's events 1 ms earlier the same gaps fall into the readback."""
    with open(os.path.join(DATA, "trace_v5e_serve_phases.json")) as f:
        raw = json.load(f)
    rec = {"trace": trace_reduce.reduce(raw, spans=("engine.step", "route"))}
    for lines in raw["devices"].values():
        for evs in lines.values():
            for e in evs:
                e[1] -= 1.0e6
    early = {"trace": trace_reduce.reduce(raw, spans=("engine.step", "route"))}
    assert _read("idle_front_door_pct", rec) > 3.0
    assert _read("idle_front_door_pct", early) < 1.0
    assert _read("idle_readback_pct", rec) < 1.0
    assert _read("idle_readback_pct", early) > 5.0
    assert lib.device_idle_pct(early) == pytest.approx(
        lib.device_idle_pct(rec), abs=0.1)
    assert _read("idle_unattributed_pct", early) < 1.0


def test_without_them_only_wrappers_and_jax_name_a_gap():
    rec = _rec("trace_v5e_serve.json")
    gaps = dict(rec["trace"]["idle_gaps"])
    w = rec["trace"]["window_s"]
    assert _read("idle_front_door_pct", rec) == pytest.approx(
        100.0 * gaps.get("route", 0.0) / w)
    assert _read("idle_readback_pct", rec) == pytest.approx(
        100.0 * gaps["np.asarray(jax.Array)"] / w)
    assert _read("idle_sched_pct", rec) == pytest.approx(100.0 * (
        gaps["engine.step"] + gaps["PjitFunction(convert_element_type)"]) / w)
    assert _read("idle_unattributed_pct", rec) == pytest.approx(
        100.0 * gaps[trace_reduce.NO_SPAN] / w)


def test_ttft_intervals_from_the_programs_stamps():
    reqs = [{"in_window": True, "ok": True, "enqueue": 1.0, "admit": 1.04,
             "first_token": 1.14},
            {"in_window": True, "ok": True, "enqueue": 2.0, "admit": 2.02,
             "first_token": 2.10},
            {"in_window": False, "ok": True, "enqueue": 0.0, "admit": 0.5,
             "first_token": 0.9},                      # lead-in: not counted
            {"in_window": True, "ok": True, "enqueue": 3.0, "admit": None,
             "first_token": None}]                     # no stamps: left out
    rec = {"requests": reqs}
    assert _read("queue_wait_ms_mean", rec) == pytest.approx(30.0)
    assert _read("prefill_ms_mean", rec) == pytest.approx(90.0)
    none = {"requests": reqs[2:]}
    assert _read("queue_wait_ms_mean", none) is None
    assert _read("prefill_ms_mean", none) is None
