"""The ``granite_serve`` family on the CPU at toy sizes: the cell end to end
through ``run.py`` (this file cuts its own configuration and traffic in the
copy, as ``test_kexaone`` does), whole runs with the served path broken, which
have to come out not correct (a state restored as zeros, a held expert
dropped, a stale state left in a slot, the state rounded to bfloat16, a
served token altered), the family's
byte counts against the tree it builds and against the cache the program
allocates, the configuration against the catalog's row, the traffic against
the engine, and the new readers on a recorded ``rec``."""

import json
import os
import re

import jax
import numpy as np
import pytest

import rehearse
from benchmark import lib, traffic_gen

CELL = "granite_toolcalls"
CONFIG = os.path.join(rehearse.ROOT, "benchmark", "configs",
                      "granite-4.0-h-small.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {
    "hidden_size": 32, "intermediate_size": 16, "shared_intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.125, "mamba_n_heads": 8, "mamba_d_head": 8,
    "mamba_d_state": 8, "mamba_chunk_size": 4, "num_local_experts": 4,
    "num_local_experts_published": 8, "num_experts_per_tok": 3,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "vocab_size": 128,
    # float32: at this size one expert is a quarter of a layer, so a near-tie
    # that bfloat16 flips moves a logit by more than any limit; the
    # precision's own readings are the chip's
    "torch_dtype": "float32"}
#: the toy model's logits spread as the cell's do (by 4 / (12 x 16) = 0.005,
#: whatever the width), but in float32 on the CPU a sound run reads 0.0 where
#: bfloat16 on the chip reads up to 0.0016: the toy's limits are a fiftieth of
#: the cell's, so that a path broken at a toy's scale shows
TOY_LIMITS = ("import benchmark.lib as _lib\n"
              "_F = _lib.load_module('families', 'granite_serve')\n"
              "_F.LIMITS = {k: v / 50 for k, v in _F.LIMITS.items()}\n")


def make_copy(dst: str) -> str:
    rehearse.make_copy(dst)
    b = os.path.join(dst, "benchmark")
    rehearse._edit(os.path.join(b, "configs", "granite-4.0-h-small.json"),
                   lambda d: d.update(TINY))

    def mix(d):
        d["engine"].update(n_slots=2, max_len=64, chunk=4, block_size=8,
                           n_blocks=21, snapshots=4)
        d.update(requests_per_window_second=8.0, trace_s=0.3, stratify=2)
        d["shapes"].update(rehearse.TINY_SHAPES)
        d["shapes"]["system_prompts"] = {"count": 2, "tokens": 16}
        d["check"] = {"sample": 4, "pad_to": 64, "state_served": 16,
                      "state_fresh": 6}

    rehearse._edit(os.path.join(b, "traffic", "toolcalls.json"), mix)
    return dst


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("bench_granite")))


def _ok(rc, last, out, err):
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last is not None
    return last


def test_the_cell_runs_untraced_and_is_correct(copy):
    rc, last, out, err = rehearse.run_in_copy(copy, CELL, seed=2**31 + 5,
                                              extra=TOY_LIMITS)
    last = _ok(rc, last, out, err)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"serve_tput", "setup_s"}
    # the sample held a request whose state came from a snapshot, and each of
    # the two system prompts was probed right behind its restored state
    hits = re.search(r"check: 4 requests \((\d+) admitted on a prefix hit", out)
    assert hits and int(hits.group(1)) >= 1, out[-1500:]
    assert "check: 2 restore probes, 16 served tokens" in out, out[-1500:]
    assert re.search(r"check restore_gap_mean: value=\S+ limit=\S+ ok", out)
    # ... and the recurrent state itself was read behind each system prompt
    # restored and behind a fresh prompt, 16 ticks on
    assert "check: 3 state probes, the state read after [34, 34, 22] " \
        "tokens" in out, out[-1500:]
    for name in ("state_gap_first", "state_gap", "state_bf16_share"):
        assert re.search(rf"check {name}: value=\S+ limit=\S+ ok", out)
    assert re.search(r"check programs_compiled_in_window: value=0 ", out)


def test_the_traced_run_reports_the_cells_per_layer_metrics(copy):
    """The chip's part of the trace is the recorded one, so the device times
    are another program's; the readers, the stamps and the counters are this
    family's own."""
    last = _ok(*rehearse.run_in_copy(copy, CELL, trace=1, extra=TOY_LIMITS))
    spec = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert len(want) == 18 and all(n.endswith(".granite") for n in want)
    step_log = {"step_host_ms.granite", "step_sync_wait_ms.granite",
                "between_steps_ms.granite", "chunk_dispatch_ms.granite",
                "attn_walk_over_live.granite"}
    assert step_log | {
        "tick_dev_ms.granite", "chunk_dev_ms.granite",
        "rows_per_tick.granite", "moe_held_share_pct.granite",
        "moe_load_max_over_mean.granite", "prefix_skip_pct.granite",
        "device_idle_pct.granite", "hbm_peak_gb.granite",
        "snapshot_restored_over_matched.granite",
        "snapshots_evicted_per_request.granite"} \
        <= set(last["metrics"]) <= want
    value = lambda name: last["metrics"][name + ".granite"]["value"]  # noqa: E731,E501
    assert 0.0 < value("moe_held_share_pct") < 100.0
    assert value("prefix_skip_pct") > 0.0
    assert 0.0 < value("snapshot_restored_over_matched") <= 1.0
    assert value("snapshots_evicted_per_request") >= 0.0


#: Whole runs with the served path broken.  A lower precision in the program's
#: place is not among them: the limits are set at the cell's own size, from
#: the chip's readings (``limits_probe.py``, PERF.md section 2); a toy model in
#: float32 reads otherwise.
BROKEN = {
    "a state restored as zeros": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.state_space_moe as M\n"
        "_s = M.set_row\n"
        "def _zeroed(pcache, slot, row, length, snaps):\n"
        "    return _s(pcache._replace(\n"
        "        snap_ssm=jnp.zeros_like(pcache.snap_ssm),\n"
        "        snap_conv=jnp.zeros_like(pcache.snap_conv)),\n"
        "        slot, row, length, snaps)\n"
        "M.set_row = _zeroed\n"),
    "the held experts dropped": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.latent_moe as L\n"
        "L.held_experts = lambda cfg, lp, h2, valid: (\n"
        "    jnp.zeros_like(h2), jnp.zeros((cfg.held_count,), jnp.int32))\n"),
    "a stale state left in the slot": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.state_space_moe as M\n"
        "_s = M.set_row\n"
        "def _stale(pcache, slot, row, length, snaps):\n"
        "    new = _s(pcache, slot, row, length, snaps)\n"
        "    fresh = jnp.asarray(length) == 0\n"
        "    return new._replace(\n"
        "        ssm=jnp.where(fresh, pcache.ssm, new.ssm),\n"
        "        conv=jnp.where(fresh, pcache.conv, new.conv))\n"
        "M.set_row = _stale\n"),
    "the state rounded to bfloat16 after every program": (
        # (on the chip a pair of converts is simplified away)
        "import jax\n"
        "import horovod_tpu.models.state_space_moe as M\n"
        "_r = lambda s: jax.lax.reduce_precision(s, 8, 7)\n"
        "_a, _d, _o = M.advance_state, M._ssd, M.advance_one_token\n"
        "def _adv(lp, state, kept, n):\n"
        "    s, c = _a(lp, state, kept, n)\n"
        "    return _r(s), c\n"
        "def _ssd(cfg, lp, state, dt, x, b, c):\n"
        "    y, s = _d(cfg, lp, state, dt, x, b, c)\n"
        "    return y, _r(s)\n"
        "M.advance_state, M._ssd = _adv, _ssd\n"
        "M.advance_one_token = lambda *a: _r(_o(*a))\n"),
    "a served token altered where it is produced": (
        "import horovod_tpu.models.state_space_moe as M\n"
        "_d = M.decode_chunk_paged\n"
        "def _neg(*a, **k):\n"
        "    logits, cache = _d(*a, **k)\n"
        "    return -logits, cache\n"
        "M.decode_chunk_paged = _neg\n"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_served_path_is_not_correct(copy, fault):
    rc, last, out, err = rehearse.run_in_copy(
        copy, CELL, extra=TOY_LIMITS + BROKEN[fault])
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last["correct"] is False
    failed = [ln for ln in out.splitlines() if "NOT CORRECT" in ln]
    assert any("check gap_" in ln or "check restore_gap_mean" in ln
               or "check state_gap" in ln for ln in failed), failed
    if "restored as zeros" in fault:
        assert any("check restore_gap_mean" in ln for ln in failed), failed
    if "restored as zeros" in fault or "stale" in fault:
        assert any("check state_gap" in ln for ln in failed), failed
    if "bfloat16" in fault:
        assert any("check state_bf16_share" in ln for ln in failed), failed


def test_the_references_bf16_state_control_holds_a_bfloat16s_state(copy):
    """The control rounds the recurrent state after every token (by
    ``reduce_precision``: on the chip the compiler simplifies a pair of
    converts away, and the control was a float32 run there): every element of its states is a
    bfloat16's, the float32 reference's are not, and the two differ."""
    ref = lib.load_module("reference", "granite")
    with open(os.path.join(copy, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        cfg = json.load(f)
    seq = np.random.default_rng(3).integers(1, 128, 40).tolist()
    full, = ref.states_at(cfg, 5, [seq], [37], "float32", 64)
    low, = ref.states_at(cfg, 5, [seq], [37], "bf16_state", 64)
    assert full.shape == low.shape == (3, 8, 8, 8)
    bits = lambda a: np.ascontiguousarray(a).view(np.uint32) & 0xFFFF  # noqa: E731,E501
    assert not bits(low).any() and bits(full).any()
    err = np.linalg.norm(low - full) / np.linalg.norm(full)
    assert 1e-4 < err < 2e-2, err


def test_weight_bytes_is_the_byte_count_of_the_tree_make_params_builds():
    fam = lib.load_module("families", "granite_serve")
    with open(CONFIG) as f:
        full = json.load(f)
    for cfg in (dict(full, **TINY), full):
        tree = jax.eval_shape(lambda: fam.make_params(cfg, 3))
        n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(tree))
        assert fam.weight_bytes(cfg) == n_bytes
    # the full size: the issue's count to the parameter, 9.51 GB
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert n_params == 9 * 461_203_072 + 400_859_136 + 205_520_896 + 4096
    assert abs(n_bytes / 9.51e9 - 1.0) < 0.001
    sizes = fam._sizes(full)
    assert sizes["mixer_mats"] + sizes["mixer_rest"] + sizes["mixer_f32"] \
        == 102_286_976
    assert sizes["attn_params"] == 41_943_040
    assert fam.expert_bytes(full) == 2 * 9_437_184
    assert sizes["shared_params"] == 18_874_368
    assert fam.kv_bytes_per_token(full) == 4096
    assert fam.state_bytes_per_slot(full) == \
        9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2) == 38_204_928
    # the held experts are 71 % of the weights a tick that touches all of
    # them reads
    held = 10 * 36 * fam.expert_bytes(full)
    assert 0.70 < held / (fam.dense_bytes(full) + held) < 0.73
    # a tick of 64 rows at the mean request's length, every expert touched:
    # a third of its bytes is recurrent state, the context next to nothing
    full_tick = fam.tick_bytes(full, rows=64, live_tokens=64 * 3900,
                               experts_touched=10 * 36)
    assert full_tick == fam.weight_bytes(full) + 64 * 3900 * 4096 \
        + 2 * 64 * 38_204_928
    assert 0.30 < 2 * 64 * 38_204_928 / full_tick < 0.34
    assert 64 * 3900 * 4096 / full_tick < 0.07


def test_the_models_cache_is_the_familys_byte_counts():
    """What the program allocates a token, a slot and a snapshot is what the
    family's functions say the algorithm needs; no snapshot a block."""
    from horovod_tpu.models import state_space_moe as sm

    fam = lib.load_module("families", "granite_serve")
    with open(CONFIG) as f:
        full = json.load(f)
    e = lib.load_json("traffic", "toolcalls.json")["engine"]
    mc = fam.model_config(full, e["max_len"], e["snapshots"])
    cache = jax.eval_shape(lambda: sm.init_paged_cache(
        mc, e["n_slots"], e["max_len"], block_size=e["block_size"],
        n_blocks=e["n_blocks"]))
    per_block = sm.paged_pool_bytes(cache)
    assert set(per_block) == {"k", "v"}
    assert (per_block["k"] + per_block["v"]) // 1024 == \
        fam.kv_bytes_per_token(full)
    assert sm.state_bytes(cache) == fam.state_bytes_per_slot(full)
    assert cache.ssm.shape == (9, 64, 128, 64, 128)
    assert cache.snap_ssm.shape == (9, 24, 128, 64, 128)
    assert cache.snap_conv.shape == (9, 24, 3, 8448)
    assert cache.snap_dest.shape == (64, 9)
    assert cache.k.shape == (1, 585, 1024, 8, 128)
    assert mc.layer_kinds == (sm.SSM,) * 5 + (sm.ATTN,) + (sm.SSM,) * 4
    assert (mc.held_first, mc.held_count, mc.n_experts, mc.top_k) == \
        (0, 36, 72, 10)
    # the issue's arithmetic: 2.44 GB of states, 2.45 GB of keys and values,
    # 0.92 GB of snapshots
    gb = lambda *arrays: sum(a.size * a.dtype.itemsize  # noqa: E731
                             for a in arrays) / 1e9
    assert abs(gb(cache.ssm, cache.conv) - 2.445) < 0.01
    assert abs(gb(cache.k, cache.v) - 2.454) < 0.01
    assert abs(gb(cache.snap_ssm, cache.snap_conv) - 0.917) < 0.01


def test_the_configuration_is_the_catalogs_row_cut_as_the_file_says():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CONFIG) as f:
        cfg = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert (cfg["num_hidden_layers_published"],
            cfg["num_local_experts_published"],
            cfg["vocab_size_published"]) == tuple(
        row["config"][k] for k in cfg["reduced"])
    assert cfg["chips_sharing_a_layer"] * cfg["num_local_experts"] == 72
    assert cfg["chips_sharing_a_layer"] * cfg["vocab_size"] == 100352
    assert (cfg["held_experts_first"], cfg["vocab_first_row"]) == (0, 0)
    assert "layers 0-9 of 40" in cfg["pipeline_stage"]
    # the guide's floors: a whole period, 8 experts, an eighth of the
    # vocabulary
    kinds = cfg["layer_types"][:10]
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["layer_types"][10:20] == kinds       # one period is 10 layers
    assumed = " ".join(cfg["assumed"])
    for key in ("float32 between programs", "intermediate_size 768",
                "head_dim", "torch_dtype", "softmax over those 10",
                "gate first", "time_step_limit", "random from the seed"):
        assert key in assumed, key
    entry = lib.find(lib.benchmark_spec()["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_traffic_is_the_issues_and_fits_the_engine():
    mix = lib.load_json("traffic", "toolcalls.json")
    e = mix["engine"]
    assert (e["n_slots"], e["max_len"], e["block_size"]) == (64, 9216, 1024)
    assert e["chunk"] in (512, 1024) and e["prefix_cache"] is True
    assert e["snapshots"] <= 24 < e["n_blocks"] // 8     # far below a block's
    sp = mix["shapes"]["system_prompts"]
    assert (sp["count"], sp["tokens"]) == (4, 2048)
    assert sp["tokens"] % e["block_size"] == 0      # two whole cache blocks
    assert mix["shapes"]["own_prompt_quantiles"] == [
        [0, 256], [0.25, 512], [0.5, 1024], [0.75, 2048], [0.9, 3584],
        [1, 6144]]
    assert mix["shapes"]["output_quantiles"] == [
        [0.0, 128], [0.25, 224], [0.5, 384], [0.75, 576], [0.9, 768],
        [1.0, 1024]]
    assert mix["stratify"] == 8
    # offline_batch, with the traced slice placed after the first wave's
    # prefill (the traffic file's `trace_s_is` says why)
    assert mix["driver"] == "offline_batch_late_trace"
    assert mix["trace_s"] <= 20.0 and mix["trace_after_s"] >= 15.0
    n = round(45 * mix["requests_per_window_second"])
    rng = np.random.default_rng([7, 1])
    systems = traffic_gen.draw_system_prompts(mix, 50176, rng)
    reqs = traffic_gen.plan(mix, n, 50176, rng, systems, timed=False)
    assert len(reqs) == n > 2 * e["n_slots"]
    own = sorted(r.own_len for r in reqs)
    outs = sorted(r.n_out for r in reqs)
    assert 256 <= own[0] and own[-1] <= 6144
    assert 128 <= outs[0] and outs[-1] <= 1024
    assert 330 <= outs[n // 2] <= 440
    # the longest request there can be fills a row to its last position
    assert sp["tokens"] + 6144 + 1024 == e["max_len"]
    assert max(len(r.prompt) + r.n_out for r in reqs) <= e["max_len"]
    # the slots are fully backed (nothing waits for a block, nothing is
    # preempted), 8 blocks more hold the system prompts while no row maps
    # them, one is the trash block
    per = e["max_len"] // e["block_size"]
    assert e["n_blocks"] <= e["n_slots"] * per + sp["count"] * 2 + 1
    assert e["n_blocks"] - 1 >= 48 * per
    assert all(max(r.prompt) < 50176 for r in reqs)     # ids of the slice
    heads = {}
    for r in reqs:
        heads[tuple(r.prompt[:2048])] = heads.get(
            tuple(r.prompt[:2048]), 0) + 1
    assert len(heads) == 4
    assert max(heads.values()) - min(heads.values()) <= 1   # a quarter each
    assert e["max_len"] % mix["check"]["pad_to"] == 0
    # the state probes: a state read after half the longest answer's ticks,
    # behind a restored system prompt and behind a fresh prompt of one chunk,
    # each within one padded pass of the reference
    fam = lib.load_module("families", "granite_serve")
    c = mix["check"]
    assert c["state_served"] >= 512 and c["state_fresh"] <= e["chunk"]
    assert sp["tokens"] + 2 + c["state_served"] + fam.STATE_SLACK \
        <= fam.PROBE_PAD <= e["max_len"]


def _rec(stamped: bool) -> dict:
    """A recorded run: four steps, three of them ticking inside the trace."""
    slot = 38_204_928

    def stamp(t0, rows, fin, touched, total, held, restores, visible, moved,
              matched, restored, evicted):
        base = (t0, t0 + 0.02, rows, 1, fin)
        if not stamped:
            return base
        load = [held // 36] * 35 + [held - 35 * (held // 36)]
        return base + (touched, total, held, restores, visible, *load,
                       restores + 4, evicted, moved, matched, restored, 20)
    steps = [stamp(10.00, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
             stamp(10.02, 30, 0, 300, 100 * 400, 50 * 400, 3, 400 * 3000,
                   2 * slot * 60, 10, 4, 0),
             stamp(10.04, 40, 1, 330, 100 * 2500, 50 * 2500, 5, 2500 * 3000,
                   2 * slot * (60 + 40 + 4), 20, 10, 1),
             stamp(10.06, 36, 0, 320, 100 * 4700, 50 * 4700, 6, 4700 * 3000,
                   2 * slot * (60 + 40 + 4 + 36 + 4), 40, 30, 3)]
    run = lambda a, b: [int(a * 1e9), int(b * 1e9)]     # noqa: E731
    return {
        "window": (10.0, 11.0), "steps": steps, "device_kind": "TPU v5 lite",
        "memory_peak_bytes": 15.9e9,
        "requests": [{"ok": True, "in_window": True, "first_token": 10.01,
                      "terminal": 10.9, "prompt_len": 3500, "n_out": 400,
                      "prefix_skipped": 2048}] * 30,
        "trace": {"busy_s": 0.9, "window_s": 1.0,
                  "span_totals": {"engine.step": [4, 0.12]},
                  "programs": {
                      "_tick": {"count": 3, "total_s": 0.075,
                                "runs": [run(0.030, 0.055), run(0.120, 0.145),
                                         run(0.210, 0.235)]},
                      "_chunk": {"count": 8, "total_s": 0.120,
                                 "runs": [run(0.056 + 0.015 * i,
                                              0.071 + 0.015 * i)
                                          for i in range(4)]
                                 + [run(0.146 + 0.015 * i,
                                        0.161 + 0.015 * i)
                                    for i in range(4)]}}}}


def test_the_new_readers_on_a_recorded_run():
    read = lambda name, rec: lib.load_module(       # noqa: E731
        "layer_metrics", name + ".granite").read(rec)
    rec = _rec(stamped=True)
    assert read("tick_dev_ms", rec) == pytest.approx(25.0)
    assert read("chunk_dev_ms", rec) == pytest.approx(15.0)
    assert read("rows_per_tick", rec) == pytest.approx((30 + 40 + 36) / 3)
    assert read("moe_held_share_pct", rec) == pytest.approx(50.0)
    assert read("moe_load_max_over_mean", rec) == pytest.approx(1.0, abs=0.01)
    assert read("prefix_skip_pct", rec) == pytest.approx(100 * 2048 / 3500)
    assert read("device_idle_pct", rec) == pytest.approx(10.0)
    assert read("hbm_peak_gb", rec) == pytest.approx(15.9)
    assert read("snapshot_restored_over_matched", rec) == pytest.approx(0.75)
    assert read("snapshots_evicted_per_request", rec) == pytest.approx(0.1)
    # two whole ticks in the trace (the third may be cut): their bytes over
    # 25 ms, under the memory's peak
    fam = lib.load_module("families", "granite_serve")
    cfg = lib.load_json("configs", "granite-4.0-h-small.json")
    roof = read("tick_roofline", rec)
    low = fam.tick_bytes(cfg, 30, 30 * 3500, 300) / 25e-3 / 819e9
    high = fam.tick_bytes(cfg, 40, 30 * 3900, 330) / 25e-3 / 819e9
    assert 100 * low < roof < 100 * high < 100.0
    # between the two ticks' ends: the counter gained the second tick's 40
    # rows and 4 chunks' rows, of which the trace holds the 4 chunk runs
    share = read("ssm_state_share_pct", rec)
    ctx = 30 * (3500 + 400 * 0.05 / 0.89)     # the second tick's step ends
    assert share == pytest.approx(
        100 * 2 * 40 * 38_204_928 / fam.tick_bytes(cfg, 40, ctx, 330),
        rel=1e-6)
    assert 20.0 < share < 40.0
    # the chunks between the two ticks' ends: 2,100 tokens' choices less the
    # second tick's rows, over the four chunk runs that lie between them
    mfu = read("chunk_mfu_pct", rec)
    assert 0.0 < mfu < 100.0
    tokens = 2100 - 40
    assert mfu == pytest.approx(100 * fam.chunk_flops(
        cfg, tokens, 2100 * 3000 - ctx, tokens * 50) / 60e-3 / 197e12,
        rel=0.02)


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """A program whose stamps are ``llama_serve``'s five fields: the readers
    of this family's counters return nothing and do not raise."""
    read = lambda name, rec: lib.load_module(       # noqa: E731
        "layer_metrics", name + ".granite").read(rec)
    rec = _rec(stamped=False)
    for name in ("moe_held_share_pct", "moe_load_max_over_mean",
                 "tick_roofline", "chunk_mfu_pct", "ssm_state_share_pct",
                 "snapshot_restored_over_matched",
                 "snapshots_evicted_per_request"):
        assert read(name, rec) is None, name
    assert read("tick_dev_ms", rec) == pytest.approx(25.0)
    rec["trace"] = None
    for name in ("tick_dev_ms", "chunk_dev_ms", "device_idle_pct",
                 "tick_roofline", "chunk_mfu_pct", "ssm_state_share_pct"):
        assert read(name, rec) is None, name
