"""The ``kexaone_serve`` family on the CPU at toy sizes: the cell end to end
through ``run.py`` (this file cuts its own configuration and traffic in the
copy, as ``test_lfm2`` does), whole runs with the served path broken, which
have to come out not correct (a ring restored as zeros, the held experts
dropped, the window widened to the whole length, a served token altered), the
family's byte counts against the tree it builds, the configuration against
the catalog's row, the traffic against the engine, and the new readers on a
recorded ``rec``."""

import json
import os
import re

import jax
import numpy as np
import pytest

import rehearse
from benchmark import lib, traffic_gen

CELL = "kexaone_mixedq"
CONFIG = os.path.join(rehearse.ROOT, "benchmark", "configs",
                      "k-exaone-236b-a23b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "moe_intermediate_size": 16,
    "num_experts": 8, "num_experts_published": 16, "num_experts_per_tok": 2,
    "num_hidden_layers": 5, "sliding_window": 6, "vocab_size": 128,
    "rope_parameters": {"rope_theta": 1e4, "rope_type": "default"},
    # float32: at this size one expert is a quarter of a layer, so a near-tie
    # that bfloat16 flips moves a logit by more than any limit; the
    # precision's own readings are the chip's
    "torch_dtype": "float32"}


def make_copy(dst: str) -> str:
    rehearse.make_copy(dst)
    b = os.path.join(dst, "benchmark")
    rehearse._edit(os.path.join(b, "configs", "k-exaone-236b-a23b.json"),
                   lambda d: d.update(TINY))

    def mix(d):
        d["engine"].update(n_slots=2, max_len=64, chunk=8, n_blocks=21)
        d.update(requests_per_window_second=8.0, trace_s=0.3, stratify=2)
        d["shapes"].update(rehearse.TINY_SHAPES)
        d["shapes"]["system_prompts"] = {"count": 2, "tokens": 16}
        d["check"] = {"sample": 4, "pad_to": 64}

    rehearse._edit(os.path.join(b, "traffic", "mixedq.json"), mix)
    return dst


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("bench_kexaone")))


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
    # the sample held a request whose ring came from a snapshot, and each of
    # the two system prompts was probed right behind its restored ring
    hits = re.search(r"check: 4 requests \((\d+) admitted on a prefix hit", out)
    assert hits and int(hits.group(1)) >= 1, out[-1500:]
    assert "check: 2 restore probes, 16 served tokens" in out, out[-1500:]
    assert re.search(r"check restore_gap_mean: value=\S+ limit=\S+ ok", out)
    assert re.search(r"check programs_compiled_in_window: value=0 ", out)


def test_the_traced_run_reports_the_cells_per_layer_metrics(copy):
    """The chip's part of the trace is the recorded one, so the device times
    are another program's; the readers, the stamps and the counters are this
    family's own."""
    last = _ok(*rehearse.run_in_copy(copy, CELL, trace=1))
    spec = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert len(want) == 11 and all(n.endswith(".kexaone") for n in want)
    assert {"tick_dev_ms.kexaone", "chunk_dev_ms.kexaone",
            "rows_per_tick.kexaone", "moe_held_share_pct.kexaone",
            "moe_load_max_over_mean.kexaone",
            "kv_bytes_per_live_token.kexaone", "prefix_skip_pct.kexaone",
            "device_idle_pct.kexaone", "hbm_peak_gb.kexaone"} \
        <= set(last["metrics"]) <= want
    assert 0.0 < last["metrics"]["moe_held_share_pct.kexaone"]["value"] < 100.0
    assert last["metrics"]["prefix_skip_pct.kexaone"]["value"] > 0.0
    assert last["metrics"]["kv_bytes_per_live_token.kexaone"]["value"] > 0.0


#: Whole runs with the served path broken.  A lower precision in the program's
#: place is not among them: the limits are set at the cell's own size, from
#: the chip's readings (``limits_probe.py``, PERF.md section 2); a toy model in
#: float32 reads otherwise.
BROKEN = {
    "a ring restored as zeros": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.window_moe as M\n"
        "_s = M.set_row\n"
        "def _zeroed(pcache, slot, row, length):\n"
        "    return _s(pcache._replace(snap=jnp.zeros_like(pcache.snap)),\n"
        "              slot, row, length)\n"
        "M.set_row = _zeroed\n"),
    "the held experts dropped": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.latent_moe as L\n"
        "L.held_experts = lambda cfg, lp, h2, valid: (\n"
        "    jnp.zeros_like(h2), jnp.zeros((cfg.held_count,), jnp.int32))\n"),
    "the window widened to the whole length": (
        "import dataclasses\n"
        "from benchmark import lib\n"
        "F = lib.load_module('families', 'kexaone_serve')\n"
        "_m = F.model_config\n"
        "F.model_config = lambda cfg, max_len: dataclasses.replace(\n"
        "    _m(cfg, max_len), window=max_len)\n"),
    "a served token altered where it is produced": (
        "import horovod_tpu.models.window_moe as M\n"
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
    fam = lib.load_module("families", "kexaone_serve")
    with open(CONFIG) as f:
        full = json.load(f)
    for cfg in (dict(full, **TINY), full):
        tree = jax.eval_shape(lambda: fam.make_params(cfg, 3))
        n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(tree))
        assert fam.weight_bytes(cfg) == n_bytes
    # the full size: within 0.1 % of the 5,979 M parameters of the issue's
    # count, 11.96 GB
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert abs(n_params / 5979e6 - 1.0) < 0.001
    assert abs(n_bytes / 11.96e9 - 1.0) < 0.001
    assert fam.expert_bytes(full) == 75_497_472
    assert fam.kv_bytes_per_token(full) == 8192
    assert fam.ring_bytes_per_position(full) == 24_576
    # the held experts are 71 % of what a tick that touches all of them reads
    held = 7 * 16 * fam.expert_bytes(full)
    assert 0.70 < held / (fam.dense_bytes(full) + held) < 0.73
    # a tick of 40 rows at the mean request's length, every expert touched
    full_tick = fam.tick_bytes(full, rows=40, live_tokens=40 * 7600,
                               experts_touched=7 * 16)
    assert full_tick == fam.dense_bytes(full) + held + 40 * 7600 * 8192 \
        + 40 * 128 * 24_576
    # and a row shorter than the window reads no more ring than it has
    assert fam.tick_bytes(full, 1, 50, 0) == fam.dense_bytes(full) \
        + 50 * (8192 + 24_576)


def test_the_models_cache_is_the_familys_byte_counts():
    """What the program allocates a token, a slot and a block is what the
    family's functions say the algorithm needs."""
    from horovod_tpu.models import window_moe as wm

    fam = lib.load_module("families", "kexaone_serve")
    with open(CONFIG) as f:
        full = json.load(f)
    mc = fam.model_config(full, 32768)
    cache = jax.eval_shape(lambda: wm.init_paged_cache(
        mc, 64, 32768, block_size=1024, n_blocks=40))
    per_block = wm.paged_pool_bytes(cache)
    assert (per_block["k"] + per_block["v"]) // 1024 == \
        fam.kv_bytes_per_token(full)
    assert per_block["snap"] == 128 * fam.ring_bytes_per_position(full) \
        == 3_145_728
    assert sum(per_block.values()) == 11_534_336        # 11.53 MB a block
    assert cache.ring.size * 2 // 64 == per_block["snap"]
    assert mc.layer_kinds == (wm.SLIDING,) * 3 + (wm.FULL,) \
        + (wm.SLIDING,) * 3 + (wm.FULL,)
    assert (mc.held_first, mc.held_count, mc.n_experts, mc.top_k) == \
        (0, 16, 128, 8)


def test_the_configuration_is_the_catalogs_row_cut_as_the_file_says():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CONFIG) as f:
        cfg = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 16, 19200)
    assert (cfg["num_hidden_layers_published"], cfg["num_experts_published"],
            cfg["vocab_size_published"]) == tuple(
        row["config"][k] for k in cfg["reduced"])
    assert cfg["chips_sharing_a_layer"] * cfg["num_experts"] == 128
    assert cfg["chips_sharing_a_layer"] * cfg["vocab_size"] == 153600
    # the guide's floors: a whole period and four more expert layers, 8
    # experts, an eighth of the vocabulary
    kinds = cfg["layer_types"][:8]
    assert kinds == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert cfg["mlp_layer_types"][:8] == ["dense"] + ["sparse"] * 7
    assert cfg["first_k_dense_replace"] == 1
    assert "not held" in cfg["mtp"] and cfg["num_nextn_predict_layers"] == 1
    assumed = " ".join(cfg["assumed"])
    for key in ("norm placement", "q_norm", "rotary", "sliding_window 128",
                "selection bias", "torch_dtype"):
        assert key in assumed, key
    entry = lib.find(lib.benchmark_spec()["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_traffic_is_the_issues_and_fits_the_engine():
    mix = lib.load_json("traffic", "mixedq.json")
    e = mix["engine"]
    assert (e["n_slots"], e["max_len"], e["chunk"]) == (64, 32768, 1024)
    assert "block_size" not in e                # the chunk's: 1,024
    sp = mix["shapes"]["system_prompts"]
    assert (sp["count"], sp["tokens"]) == (4, 1024)
    assert sp["tokens"] % e["chunk"] == 0       # one whole cache block
    assert mix["shapes"]["own_prompt_quantiles"] == [
        [0, 256], [0.35, 1024], [0.7, 3072], [0.71, 8192], [0.85, 16384],
        [0.97, 26624], [1, 30720]]
    assert mix["stratify"] == 8 and mix["driver"] == "offline_batch"
    n = round(45 * mix["requests_per_window_second"])
    rng = np.random.default_rng([7, 1])
    systems = traffic_gen.draw_system_prompts(mix, 19200, rng)
    reqs = traffic_gen.plan(mix, n, 19200, rng, systems, timed=False)
    assert len(reqs) == n
    own = sorted(r.own_len for r in reqs)
    outs = sorted(r.n_out for r in reqs)
    assert 256 <= own[0] and own[-1] <= 30720
    assert 128 <= outs[0] and outs[-1] <= 1024
    assert 330 <= outs[n // 2] <= 440
    short = [x for x in own if x <= 3072]
    assert 0.65 <= len(short) / n <= 0.75
    # the table's hundredth between the two modes holds a hundredth of the
    # requests at most
    assert sum(1 for x in own if 3072 < x < 8192) <= -(-n // 100)
    # the longest request there can be fills a row to its last position
    assert sp["tokens"] + 30720 + 1024 == e["max_len"]
    assert max(len(r.prompt) + r.n_out for r in reqs) <= e["max_len"]
    # the pool is overcommitted by the batch that runs, not by one there
    # could be: what admission reserves for these requests (prompt + max_new,
    # in blocks) is over half as much again as the pool holds, so requests
    # wait for blocks and later ones are admitted on a prefix hit; the
    # largest request still fits, and the slots are more than the requests
    # the pool holds at once, so it is the blocks that admission waits for
    need = [-(-(len(r.prompt) + r.n_out) // e["chunk"]) for r in reqs]
    pool = e["n_blocks"] - 1                    # block 0 is the trash block
    assert sum(need) > 1.5 * pool and pool > max(need)
    assert n > e["n_slots"] > pool / (sum(need) / n)
    assert all(max(r.prompt) < 19200 for r in reqs)     # ids of the slice
    heads = {}
    for r in reqs:
        heads[tuple(r.prompt[:1024])] = heads.get(
            tuple(r.prompt[:1024]), 0) + 1
    assert len(heads) == 4
    assert max(heads.values()) - min(heads.values()) <= 1   # a quarter each
    assert e["max_len"] % mix["check"]["pad_to"] == 0


def _rec(stamped: bool) -> dict:
    """A recorded run: four steps, two of them ticking inside the trace."""
    def stamp(t0, rows, fin, touched, total, held, restores, visible, live):
        base = (t0, t0 + 0.02, rows, 1, fin)
        if not stamped:
            return base
        load = [held // 16] * 15 + [held - 15 * (held // 16)]
        return base + (touched, total, held, restores, visible, *load,
                       live, live * 9000, rows * 3_145_728 + live * 3100)
    steps = [stamp(10.00, 0, 0, 0, 0, 0, 0, 0, 0),
             stamp(10.02, 30, 0, 100, 56 * 400, 7 * 400, 3, 400 * 3000,
                   200_000),
             stamp(10.04, 40, 1, 110, 56 * 2500, 7 * 2500, 5, 2500 * 3000,
                   300_000),
             stamp(10.06, 36, 0, 105, 56 * 4700, 7 * 4700, 6, 4700 * 3000,
                   280_000)]
    run = lambda a, b: [int(a * 1e9), int(b * 1e9)]     # noqa: E731
    return {
        "window": (10.0, 11.0), "steps": steps, "device_kind": "TPU v5 lite",
        "memory_peak_bytes": 16.2e9,
        "requests": [{"ok": True, "in_window": True, "first_token": 10.01,
                      "terminal": 10.9, "prompt_len": 7000, "n_out": 400,
                      "prefix_skipped": 1024}] * 30,
        "trace": {"busy_s": 0.9, "window_s": 1.0,
                  "span_totals": {"engine.step": [4, 0.12]},
                  "programs": {
                      "_tick": {"count": 3, "total_s": 0.075,
                                "runs": [run(0.030, 0.055), run(0.120, 0.145),
                                         run(0.210, 0.235)]},
                      "_chunk": {"count": 2, "total_s": 0.120,
                                 "runs": [run(0.056, 0.116),
                                          run(0.146, 0.206)]}}}}


def test_the_new_readers_on_a_recorded_run():
    read = lambda name, rec: lib.load_module(       # noqa: E731
        "layer_metrics", name + ".kexaone").read(rec)
    rec = _rec(stamped=True)
    assert read("tick_dev_ms", rec) == pytest.approx(25.0)
    assert read("chunk_dev_ms", rec) == pytest.approx(60.0)
    assert read("rows_per_tick", rec) == pytest.approx((30 + 40 + 36) / 3)
    assert read("moe_held_share_pct", rec) == pytest.approx(12.5)
    assert read("moe_load_max_over_mean", rec) == pytest.approx(1.0, abs=0.01)
    assert read("prefix_skip_pct", rec) == pytest.approx(100 * 1024 / 7000)
    assert read("device_idle_pct", rec) == pytest.approx(10.0)
    assert read("hbm_peak_gb", rec) == pytest.approx(16.2)
    live = 200_000 + 300_000 + 280_000
    assert read("kv_bytes_per_live_token", rec) == pytest.approx(
        (live * 9000 + (30 + 40 + 36) * 3_145_728 + live * 3100) / live)
    assert read("kv_bytes_per_live_token", rec) < 20_000
    # two whole ticks in the trace (the third may be cut): their bytes over
    # 25 ms, under the memory's peak
    fam = lib.load_module("families", "kexaone_serve")
    cfg = lib.load_json("configs", "k-exaone-236b-a23b.json")
    roof = read("tick_roofline", rec)
    low = fam.tick_bytes(cfg, 30, 30 * 7000, 100) / 25e-3 / 819e9
    high = fam.tick_bytes(cfg, 40, 30 * 7400, 110) / 25e-3 / 819e9
    assert 100 * low < roof < 100 * high < 100.0
    # the chunks between the two ticks' ends: 2,100 tokens' choices less the
    # second tick's rows, over the one chunk run that lies between them
    mfu = read("chunk_mfu_pct", rec)
    assert 0.0 < mfu < 100.0
    tokens = 2100 - 40
    ctx = 30 * (7000 + 400 * 0.03 / 0.89)
    assert mfu == pytest.approx(100 * fam.chunk_flops(
        cfg, tokens, 2100 * 3000 - (2 * ctx + 6 * 40 * 128),
        tokens * 7) / 60e-3 / 197e12, rel=0.02)


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """A program whose stamps are ``llama_serve``'s five fields: the readers
    of this family's counters return nothing and do not raise."""
    read = lambda name, rec: lib.load_module(       # noqa: E731
        "layer_metrics", name + ".kexaone").read(rec)
    rec = _rec(stamped=False)
    for name in ("moe_held_share_pct", "moe_load_max_over_mean",
                 "kv_bytes_per_live_token", "tick_roofline", "chunk_mfu_pct"):
        assert read(name, rec) is None, name
    assert read("tick_dev_ms", rec) == pytest.approx(25.0)
    rec["trace"] = None
    for name in ("tick_dev_ms", "chunk_dev_ms", "device_idle_pct",
                 "tick_roofline", "chunk_mfu_pct"):
        assert read(name, rec) is None, name
