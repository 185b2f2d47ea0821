"""The ``lfm2_serve`` family on the CPU at toy sizes: the cell end to end
through ``run.py`` (this file cuts its own configuration and traffic in the
copy, as ``test_dots3`` does), whole runs with the served path broken, which
have to come out not correct (a convolution state restored as zeros among
them), the family's byte counts against the tree it builds, the configuration
against the catalog's row, the traffic against the engine, and the new
readers on a recorded ``rec``."""

import json
import os
import re

import jax
import numpy as np
import pytest

import rehearse
from benchmark import lib, traffic_gen

CELL = "lfm2_batchgen"
CONFIG = os.path.join(rehearse.ROOT, "benchmark", "configs",
                      "lfm2-8b-a1b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 7, "rope_theta": 1e4,
    "vocab_size": 128,
    # float32: at this size one expert is a quarter of a layer, so a near-tie
    # that bfloat16 flips moves a logit by more than any limit; the
    # precision's own readings are the chip's
    "torch_dtype": "float32"}


def make_copy(dst: str) -> str:
    rehearse.make_copy(dst)
    b = os.path.join(dst, "benchmark")
    rehearse._edit(os.path.join(b, "configs", "lfm2-8b-a1b.json"),
                   lambda d: d.update(TINY))

    def mix(d):
        d["engine"].update(n_slots=2, max_len=64, chunk=8, n_blocks=21)
        d.update(requests_per_window_second=8.0, trace_s=0.3, stratify=2)
        d["shapes"].update(rehearse.TINY_SHAPES)
        d["shapes"]["system_prompts"] = {"count": 2, "tokens": 16}
        d["check"] = {"sample": 4, "pad_to": 64}

    rehearse._edit(os.path.join(b, "traffic", "batchgen.json"), mix)
    return dst


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("bench_lfm2")))


def _ok(rc, last, out, err):
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last is not None
    return last


def test_the_cell_runs_untraced_and_is_correct(copy):
    rc, last, out, err = rehearse.run_in_copy(copy, CELL, seed=2**31 + 5)
    last = _ok(rc, last, out, err)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"serve_tput", "setup_s"}
    # the sample held a request whose state came from a snapshot, and each
    # of the two templates was probed right behind its restored state
    hits = re.search(r"check: 4 requests \((\d+) admitted on a prefix hit", out)
    assert hits and int(hits.group(1)) >= 1, out[-1500:]
    assert "check: 2 restore probes, 16 served tokens" in out, out[-1500:]
    assert re.search(r"check restore_gap_mean: value=\S+ limit=\S+ ok", out)


def test_the_traced_run_reports_the_cells_per_layer_metrics(copy):
    """The chip's part of the trace is the recorded one, so the device times
    are another program's; the readers, the stamps and the counters are this
    family's own."""
    last = _ok(*rehearse.run_in_copy(copy, CELL, trace=1))
    spec = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert len(want) == 11 and all(n.endswith(".lfm2") for n in want)
    assert {"tick_dev_ms.lfm2", "chunk_dev_ms.lfm2", "rows_per_tick.lfm2",
            "sched_host_ms.lfm2", "moe_experts_touched_pct.lfm2",
            "moe_load_max_over_mean.lfm2", "prefix_skip_pct.lfm2",
            "device_idle_pct.lfm2", "hbm_peak_gb.lfm2"} \
        <= set(last["metrics"]) <= want
    assert 0.0 < last["metrics"]["moe_experts_touched_pct.lfm2"]["value"] \
        <= 100.0
    assert last["metrics"]["prefix_skip_pct.lfm2"]["value"] > 0.0


#: Whole runs with the served path broken.  A lower precision in the program's
#: place is not among them: the limits are set at the cell's own size, where
#: bfloat16's flipped expert choices read 0.08 of ``gap_mean`` and fp8 4.4
#: (``limits_probe.py`` on the chip, PERF.md section 2); a toy model in
#: float32 with fp8 products reads 0.13.
BROKEN = {
    "a convolution state restored as zeros": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.shortconv_moe as M\n"
        "_s = M.set_row\n"
        "def _zeroed(pcache, slot, row, length):\n"
        "    return _s(pcache._replace(snap=jnp.zeros_like(pcache.snap)),\n"
        "              slot, row, length)\n"
        "M.set_row = _zeroed\n"),
    "the routed experts dropped": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.latent_moe as L\n"
        "L.held_experts = lambda cfg, lp, h2, valid: (\n"
        "    jnp.zeros_like(h2), jnp.zeros((cfg.held_count,), jnp.int32))\n"),
    "a served token altered where it is produced": (
        "import horovod_tpu.models.shortconv_moe as M\n"
        "_d = M.decode_chunk_paged\n"
        "def _neg(*a, **k):\n"
        "    logits, cache = _d(*a, **k)\n"
        "    return -logits, cache\n"
        "M.decode_chunk_paged = _neg\n"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_served_path_is_not_correct(copy, fault):
    rc, last, out, err = rehearse.run_in_copy(copy, CELL, extra=BROKEN[fault])
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last["correct"] is False
    failed = [ln for ln in out.splitlines() if "NOT CORRECT" in ln]
    assert any("check gap_" in ln or "check restore_gap_mean" in ln
               for ln in failed), failed
    if "restored as zeros" in fault:
        assert any("check restore_gap_mean" in ln for ln in failed), failed


def test_weight_bytes_is_the_byte_count_of_the_tree_make_params_builds():
    fam = lib.load_module("families", "lfm2_serve")
    with open(CONFIG) as f:
        full = json.load(f)
    for cfg in (dict(full, **TINY), full):
        tree = jax.eval_shape(lambda: fam.make_params(cfg, 3))
        n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(tree))
        assert fam.weight_bytes(cfg) == n_bytes
    # the full size: within 1 % of the 4,667 M parameters of the issue's count
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert abs(n_params / 4667e6 - 1.0) < 0.01
    assert fam.expert_bytes(full) == 22_020_096
    assert fam.kv_bytes_per_token(full) == 6144
    assert fam.state_bytes_per_row(full) == 90_112
    # the experts are 91 % of what a tick that touches all of them reads
    share = 1.0 - fam.dense_bytes(full) / fam.weight_bytes(full)
    assert 0.90 < share < 0.92


def test_the_configuration_is_the_catalogs_row_cut_in_depth_only():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CONFIG) as f:
        cfg = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 14
    assert cfg["num_hidden_layers_published"] == row["config"][
        "num_hidden_layers"] == len(cfg["layer_types"])
    kinds = cfg["layer_types"][:14]
    # the two leading dense layers, then three whole periods
    assert kinds[:2] == ["conv", "conv"] and cfg["num_dense_layers"] == 2
    assert kinds[2:] == ["full_attention", "conv", "conv", "conv"] * 3
    # what the row does not give is listed as assumed
    assumed = " ".join(cfg["assumed"])
    for key in ("tie_word_embeddings", "route_norm_eps", "torch_dtype",
                "split3", "head_dim"):
        assert key in assumed, key


def test_the_traffic_is_the_issues_and_fits_the_engine():
    mix = lib.load_json("traffic", "batchgen.json")
    e = mix["engine"]
    assert (e["n_slots"], e["max_len"], e["chunk"]) == (128, 2048, 256)
    per = e["max_len"] // e["chunk"]
    sp = mix["shapes"]["system_prompts"]
    assert (sp["count"], sp["tokens"]) == (8, 512)
    assert sp["tokens"] % e["chunk"] == 0       # whole cache blocks
    # the slots fully backed, the templates' blocks, the trash block
    assert e["n_blocks"] == e["n_slots"] * per + sp["count"] * (
        sp["tokens"] // e["chunk"]) + 1
    n = round(45 * mix["requests_per_window_second"])
    rng = np.random.default_rng([7, 1])
    systems = traffic_gen.draw_system_prompts(mix, 65536, rng)
    reqs = traffic_gen.plan(mix, n, 65536, rng, systems, timed=False)
    assert len(reqs) == n > e["n_slots"]
    own = sorted(r.own_len for r in reqs)
    outs = sorted(r.n_out for r in reqs)
    assert 32 <= own[0] and own[-1] <= 480 and 64 <= outs[0] \
        and outs[-1] <= 768
    assert 110 <= own[n // 2] <= 150 and 230 <= outs[n // 2] <= 290
    # a prompt's last window is padded to whole chunks and has to fit too
    assert max(-(-len(r.prompt) // e["chunk"]) * e["chunk"] + 0 * r.n_out
               for r in reqs) <= e["max_len"]
    assert max(len(r.prompt) + r.n_out for r in reqs) <= e["max_len"]
    heads = {}
    for r in reqs:
        heads[tuple(r.prompt[:512])] = heads.get(tuple(r.prompt[:512]), 0) + 1
    assert len(heads) == 8
    assert max(heads.values()) - min(heads.values()) <= 1   # an eighth each
    assert mix["check"]["pad_to"] == e["max_len"]


def _rec(stamped: bool) -> dict:
    """A recorded run: four steps, two of them ticking inside the trace."""
    def stamp(t0, rows, fin, touched, total, restores, snaps, visible):
        base = (t0, t0 + 0.02, rows, 1, fin)
        if not stamped:
            return base
        load = [total // 32] * 32
        return base + (touched, total, restores, snaps, visible, *load)
    steps = [stamp(10.00, 0, 0, 0, 0, 0, 0, 0),
             stamp(10.02, 100, 0, 300, 48 * 400, 3, 2, 3 * 400 * 500),
             stamp(10.04, 120, 1, 340, 48 * 1000, 5, 4, 3 * 1000 * 500),
             stamp(10.06, 110, 0, 330, 48 * 1700, 6, 6, 3 * 1700 * 500)]
    run = lambda a, b: [int(a * 1e9), int(b * 1e9)]     # noqa: E731
    return {
        "window": (10.0, 11.0), "steps": steps, "device_kind": "TPU v5 lite",
        "memory_peak_bytes": 11.4e9,
        "requests": [{"ok": True, "in_window": True, "first_token": 10.01,
                      "terminal": 10.9, "prompt_len": 700, "n_out": 200,
                      "prefix_skipped": 512}] * 100,
        "trace": {"busy_s": 0.9, "window_s": 1.0,
                  "span_totals": {"engine.step": [4, 0.12]},
                  "programs": {
                      "_tick": {"count": 3, "total_s": 0.045,
                                "runs": [run(0.030, 0.045), run(0.080, 0.095),
                                         run(0.130, 0.145)]},
                      "_chunk": {"count": 2, "total_s": 0.060,
                                 "runs": [run(0.046, 0.076),
                                          run(0.096, 0.126)]}}}}


def test_the_new_readers_on_a_recorded_run():
    read = lambda name, rec: lib.load_module(       # noqa: E731
        "layer_metrics", name + ".lfm2").read(rec)
    rec = _rec(stamped=True)
    assert read("tick_dev_ms", rec) == pytest.approx(15.0)
    assert read("chunk_dev_ms", rec) == pytest.approx(30.0)
    assert read("rows_per_tick", rec) == pytest.approx(110.0)
    # from the first tick's start to the second's end: 65 ms of which the
    # two ticks took 30 and the chunk between them 30
    assert read("sched_host_ms", rec) == pytest.approx((65 - 60) / 2)
    assert read("moe_experts_touched_pct", rec) == pytest.approx(
        100.0 * (300 + 340 + 330) / 3 / 384)
    assert read("moe_load_max_over_mean", rec) == pytest.approx(1.0)
    assert read("prefix_skip_pct", rec) == pytest.approx(100 * 512 / 700)
    assert read("device_idle_pct", rec) == pytest.approx(10.0)
    assert read("hbm_peak_gb", rec) == pytest.approx(11.4)
    # two whole ticks in the trace (the third may be cut): their bytes over
    # 15 ms, under the memory's peak
    fam = lib.load_module("families", "lfm2_serve")
    cfg = lib.load_json("configs", "lfm2-8b-a1b.json")
    roof = read("tick_roofline", rec)
    low = fam.tick_bytes(cfg, 100, 100 * 700, 300) / 15e-3 / 819e9
    high = fam.tick_bytes(cfg, 120, 100 * 900, 340) / 15e-3 / 819e9
    assert 100 * low < roof < 100 * high < 100.0
    # the chunks between the two ticks' ends: 600 tokens' choices less the
    # second tick's rows, over the one chunk run that lies between them
    mfu = read("chunk_mfu_pct", rec)
    assert 0.0 < mfu < 100.0
    tokens = 600 - 120
    assert mfu == pytest.approx(100 * fam.chunk_flops(
        cfg, tokens, 3 * 600 * 500 - 3 * 100 * (700 + 200 * 0.03 / 0.89),
        tokens * 48) / 30e-3 / 197e12, rel=0.02)


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """The parent's stamps are ``llama_serve``'s five fields: the readers of
    this family's counters return nothing and do not raise."""
    read = lambda name, rec: lib.load_module(       # noqa: E731
        "layer_metrics", name + ".lfm2").read(rec)
    rec = _rec(stamped=False)
    for name in ("moe_experts_touched_pct", "moe_load_max_over_mean",
                 "tick_roofline", "chunk_mfu_pct"):
        assert read(name, rec) is None, name
    assert read("tick_dev_ms", rec) == pytest.approx(15.0)
    rec["trace"] = None
    for name in ("tick_dev_ms", "chunk_dev_ms", "sched_host_ms",
                 "device_idle_pct", "tick_roofline", "chunk_mfu_pct"):
        assert read(name, rec) is None, name
