"""The ``sdar_serve`` family on the CPU at toy sizes: the cell end to end
through ``run.py`` (this file cuts its own configuration and traffic in the
copy, as ``test_lfm2`` does), whole runs with the served path broken, which
have to come out not correct (a proposed token altered, the unmask rule run on
negated logits) or be seen (the commit skipped, the experts dropped), the two forms of
the reference against each other, the family's byte and operation counts
against the tree it builds and a hand count, the configuration against the
catalog's row and its own ``deployment``, the traffic against the engine, and
the new readers on a recorded ``rec``."""

import json
import os
import re

import jax
import numpy as np
import pytest

import rehearse
from benchmark import lib, traffic_gen

CELL = "sdar_blockgen"
CONFIG = os.path.join(rehearse.ROOT, "benchmark", "configs",
                      "sdar-30b-a3b-chat.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 3, "vocab_size": 128,
    "rope_theta": 1e4, "mask_token_id": 127, "prompt_ids_below": 120,
    # float32: at this size one expert is a quarter of a layer, so a near-tie
    # that bfloat16 flips moves a logit by more than any limit; the
    # precision's own readings are the chip's
    "torch_dtype": "float32"}


def make_copy(dst: str) -> str:
    rehearse.make_copy(dst)
    b = os.path.join(dst, "benchmark")
    rehearse._edit(os.path.join(b, "configs", "sdar-30b-a3b-chat.json"),
                   lambda d: d.update(TINY))

    def mix(d):
        d["engine"].update(n_slots=3, max_len=64, chunk=8, n_blocks=27)
        d.update(requests_per_window_second=8.0, trace_s=0.3,
                 trace_after_s=0.1, stratify=2)
        d["shapes"].update(rehearse.TINY_SHAPES)
        d["shapes"]["system_prompts"] = {"count": 2, "tokens": 16}
        d["check"] = {"sample": 4, "pad_to": 32}

    rehearse._edit(os.path.join(b, "traffic", "blockgen.json"), mix)
    return dst


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("bench_sdar")))


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
    # the sample held a request admitted on a prefix hit and one whose prompt
    # leaves a tail in its first block, and every position of theirs was read
    m = re.search(r"check: 4 requests \((\d+) admitted on a prefix hit, "
                  r"(\d+) with a tail in the first block\), (\d+) served", out)
    assert m and int(m.group(1)) >= 1 and int(m.group(2)) >= 1, out[-1500:]
    assert int(m.group(3)) > 0
    for name in ("gap_max", "gap_mean", "order_off_share",
                 "programs_compiled_in_window"):
        assert re.search(rf"check {name}: value=\S+ limit=\S+ ok", out), name


def test_the_traced_run_reports_the_cells_per_layer_metrics(copy):
    """The chip's part of the trace is the recorded one, so the device times
    are another program's; the readers, the stamps and the counters are this
    family's own."""
    last = _ok(*rehearse.run_in_copy(copy, CELL, trace=1))
    spec = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert len(want) == 12 and all(n.endswith(".sdar") for n in want)
    assert {"tick_dev_ms.sdar", "chunk_dev_ms.sdar", "rows_per_tick.sdar",
            "tokens_per_forward.sdar", "moe_experts_touched_pct.sdar",
            "moe_load_max_over_mean.sdar", "prefix_skip_pct.sdar",
            "device_idle_pct.sdar", "hbm_peak_gb.sdar",
            "step_host_ms.sdar"} <= set(last["metrics"]) <= want
    # two denoise steps and a commit a block, fewer where a tail is given
    assert 4 / 3 <= last["metrics"]["tokens_per_forward.sdar"]["value"] < 2.0
    assert 0 < last["metrics"]["moe_experts_touched_pct.sdar"]["value"] <= 100
    assert last["metrics"]["prefix_skip_pct.sdar"]["value"] > 0.0


#: Whole runs with the served path broken.  A lower precision in the program's
#: place is not among them: the limits are set at the cell's own size, from
#: the chip's readings (``limits_probe.py``, PERF.md section 2); a toy model in
#: float32 reads otherwise.  The first two alter what is served and fail the
#: limits at any size.  The other two alter what a block's successors read:
#: the toy's three narrow layers lean on earlier blocks too little for the
#: cell's limits (at the cell's size the chip's readings decide: PERF.md
#: section 2 has the skipped commit's), so here they have to be *seen*: a
#: sound toy run in float32 reads exactly 0.
FAINT = ("the commit is skipped and the last denoise step's keys stand",
         "the experts dropped")
BROKEN = {
    "the commit is skipped and the last denoise step's keys stand": (
        "import horovod_tpu.models.block_diffusion_moe as M\n"
        "_f = M._forward_paged\n"
        "def _tick(params, toks, cfg, pcache, *, active, commit):\n"
        "    M._forward_paged = lambda *a, **k: _f(*a, **dict(\n"
        "        k, there=commit == 0))\n"
        "    try:\n"
        "        return _d(params, toks, cfg, pcache, active=active,\n"
        "                  commit=commit)\n"
        "    finally:\n"
        "        M._forward_paged = _f\n"
        "_d, M.decode_block_paged = M.decode_block_paged, _tick\n"),
    "the experts dropped": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.latent_moe as L\n"
        "L.held_experts = lambda cfg, lp, h2, valid: (\n"
        "    jnp.zeros_like(h2), jnp.zeros((cfg.held_count,), jnp.int32))\n"),
    "the unmask rule run on negated logits": (
        "import horovod_tpu.models.block_diffusion_moe as M\n"
        "_u = M.unmask\n"
        "M.unmask = lambda cfg, logits, toks, step: _u(\n"
        "    cfg, -logits, toks, step)\n"),
    "a proposed token altered where it is produced": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.block_diffusion_moe as M\n"
        "_u = M.unmask\n"
        "def _off(cfg, logits, toks, step):\n"
        "    new, left, thr = _u(cfg, logits, toks, step)\n"
        "    took = (toks == cfg.mask_token_id) & (new != cfg.mask_token_id)\n"
        "    return jnp.where(took, (new + 1) % 100, new), left, thr\n"
        "M.unmask = _off\n"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_served_path_is_not_correct(copy, fault):
    rc, last, out, err = rehearse.run_in_copy(copy, CELL, extra=BROKEN[fault])
    assert rc == 0, (out[-2000:], err[-2000:])
    if fault in FAINT:
        read = {m.group(1): float(m.group(2)) for m in re.finditer(
            r"check (\w+): value=(\S+) limit", out)}
        assert read["gap_mean"] > 0.01 and read["gap_max"] > 0.1, read
        return
    assert last["correct"] is False
    failed = [ln for ln in out.splitlines() if "NOT CORRECT" in ln]
    assert any("check gap_" in ln or "check order_off_share" in ln
               for ln in failed), failed


def _toy():
    cfg = dict(json.load(open(CONFIG)), **TINY)
    cfg["vocab_size"], cfg["mask_token_id"] = 64, 63
    ref = lib.load_module("reference", "sdar")
    return cfg, ref, ref.make_weights(cfg, 9)


@pytest.mark.parametrize("steps,remasking", [
    (2, "low_confidence_static"), (4, "low_confidence_dynamic")])
def test_the_two_forms_of_the_reference_agree(steps, remasking):
    """The sampler run naively (a whole-sequence forward a step) and the
    training-time form (one pass over ``[clean ; noisy blocks of step s]`` a
    step) read the same logits at every position every step left masked."""
    cfg, ref, w = _toy()
    s = {"denoising_steps": steps, "remasking": remasking,
         "confidence_threshold": 0.1}
    rng = np.random.default_rng(steps)
    samples, naive = [], []
    for length, n_out in ((10, 7), (8, 9), (3, 5)):
        prompt = rng.integers(1, 60, length).tolist()
        out = ref.sample(cfg, w, s, prompt, n_out, pad_to=32)
        naive.append(out)
        samples.append((prompt, out["blocks"], out["steps"]))
    with jax.default_matmul_precision("highest"):
        rows = ref.step_rows(cfg, 9, samples, steps, pad_to=32, weights=w)
    n = 0
    for out, r in zip(naive, rows):
        for st, bi, i, best, logc, arg, tok, taken in zip(
                r["step"], r["block"], r["at"], r["best"], r["logc"],
                r["argmax"], r["token"], r["taken"]):
            x0, lc = ref.confidences(cfg, out["logits"][bi][st])
            assert abs(float(np.max(np.delete(
                out["logits"][bi][st][i], 63))) - best) < 2e-5
            assert abs(lc[i] - logc) < 2e-5 and x0[i] == arg
            assert taken == (out["steps"][bi][i] == st)
            assert (arg == tok) or not taken
            n += 1
        # the reference's own sampler took its own first choices, in its
        # own order
        assert not ref.gaps(r).any() and not ref.order_gaps(r).any()
        assert len(ref.gaps(r)) == sum(
            1 for when in out["steps"] for x in when if x >= 0)
    assert n >= 30


def test_weight_bytes_is_the_byte_count_of_the_tree_make_params_builds():
    fam = lib.load_module("families", "sdar_serve")
    full = json.load(open(CONFIG))
    for cfg in (dict(full, **TINY), full):
        tree = jax.eval_shape(lambda: fam.make_params(cfg, 3))
        n_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(tree))
        assert fam.weight_bytes(cfg) == n_bytes
    # the full size: the numbers of the configuration's `deployment`
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert n_params == 4_361_055_744 == 6 * 623_120_640 + 2 * 311_164_928 \
        + 2048
    assert n_bytes == 8_722_111_488
    for number in ("4,361,055,744", "8,722,111,488", "623,120,640",
                   "603,979,776", "18,874,368", "311,164,928", "12,288 B",
                   "3,145,728 B", "3,236,954,112"):
        assert number in full["deployment"], number
    assert fam.expert_bytes(full) == 2 * 4_718_592
    assert fam.kv_bytes_per_token(full) == 12_288
    mix = lib.load_json("traffic", "blockgen.json")
    assert mix["engine"]["n_blocks"] * 256 * 12_288 == 3_236_954_112
    # the experts are 89 % of what a tick that touches all of them reads
    experts = 6 * 128 * fam.expert_bytes(full)
    assert 0.89 < experts / (fam.dense_bytes(full) + experts) < 0.90
    # a tick of 128 rows at 1,300 positions each, every expert touched: by
    # hand, the weights but the embedding, the committed keys, the block's
    # keys twice and its logits in float32
    tick = fam.tick_bytes(full, rows=128, live_tokens=128 * 1300,
                          experts_touched=6 * 128)
    assert tick == (8_722_111_488 - 311_164_928 * 2) \
        + 128 * 1300 * 12_288 + 2 * 512 * 12_288 + 512 * 151_936 * 4
    assert 10.4e9 < tick < 10.6e9
    # a chunk of 256 tokens after a page of 256, by hand: the attention's
    # and the router's products, 8 experts a token a layer, and every query
    # over its 256 + (block index + 1) * 4 keys
    seen = 6 * sum(256 + (i // 4 + 1) * 4 for i in range(256))
    flops = fam.chunk_flops(full, tokens=256, keys_visible=seen,
                            choices=256 * 8 * 6)
    assert flops == 2 * 256 * 6 * (18_874_368 + 262_144) \
        + 2 * 256 * 8 * 6 * 4_718_592 + 4 * seen * 32 * 128
    assert 0.18e12 < flops < 0.19e12


def test_the_models_cache_is_the_familys_byte_counts():
    from horovod_tpu.models import block_diffusion_moe as bd

    fam = lib.load_module("families", "sdar_serve")
    full = json.load(open(CONFIG))
    e = lib.load_json("traffic", "blockgen.json")["engine"]
    mc = fam.model_config(full, e, e["max_len"])
    assert (mc.block_length, mc.denoising_steps, mc.remasking,
            mc.confidence_threshold, mc.mask_token_id) == (
        4, 2, "low_confidence_dynamic", 0.9, 151669)
    assert (mc.n_experts, mc.top_k, mc.held_count, mc.n_layers) == (
        128, 8, 128, 6)
    cache = jax.eval_shape(lambda: bd.init_paged_cache(
        mc, e["n_slots"], e["max_len"], block_size=e["chunk"],
        n_blocks=e["n_blocks"]))
    per_block = bd.paged_pool_bytes(cache)
    assert (per_block["k"] + per_block["v"]) // 256 == \
        fam.kv_bytes_per_token(full)
    assert sum(per_block.values()) == 3_145_728
    assert e["chunk"] % mc.block_length == 0


def test_the_configuration_is_the_catalogs_row_cut_as_the_file_says():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    cfg = json.load(open(CONFIG))
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6
    assert cfg["num_hidden_layers_published"] == \
        row["config"]["num_hidden_layers"] == 48
    assert cfg["pipeline_stages"] * cfg["num_hidden_layers"] == 48
    assert (cfg["num_experts"], cfg["vocab_size"]) == (128, 151936)
    assert cfg["block_length"] == 4 and cfg["mask_token_id"] == 151669
    assert cfg["mask_token_id"] >= cfg["prompt_ids_below"] == 151643
    assumed = " ".join(cfg["assumed"])
    for key in ("block_length 4", "mask_token_id", "q_norm", "torch_dtype",
                "minus infinity", "noise schedule", "norm_topk_prob"):
        assert key in assumed, key
    assert set(row["not_given"]) == {"block length", "noise schedule"}
    entry = lib.find(lib.benchmark_spec()["configs"], cfg["name"], "config")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/sdar-30b-a3b-chat.json"


def test_the_traffic_is_the_issues_and_fits_the_engine():
    mix = lib.load_json("traffic", "blockgen.json")
    cfg = json.load(open(CONFIG))
    e = mix["engine"]
    assert (e["n_slots"], e["max_len"], e["chunk"], e["n_blocks"]) == (
        128, 2048, 256, 1029)
    assert "block_size" not in e                # the chunk's: 256
    assert e["n_blocks"] == 128 * (2048 // 256) + 4 + 1
    assert (e["denoising_steps"], e["remasking"],
            e["confidence_threshold"]) == (2, "low_confidence_dynamic", 0.9)
    sp = mix["shapes"]["system_prompts"]
    assert (sp["count"], sp["tokens"]) == (4, 256)
    assert mix["shapes"]["own_prompt_quantiles"] == [
        [0, 61], [0.25, 125], [0.5, 190], [0.75, 318], [0.9, 510], [1, 765]]
    assert mix["shapes"]["output_quantiles"] == [
        [0.0, 128], [0.25, 250], [0.5, 384], [0.75, 574], [0.9, 768],
        [1.0, 1024]]
    assert mix["stratify"] == 8
    assert mix["driver"] in ("offline_batch", "offline_batch_late_trace")
    vocab = cfg["prompt_ids_below"]
    n = round(45 * mix["requests_per_window_second"])
    rng = np.random.default_rng([7, 1])
    systems = traffic_gen.draw_system_prompts(mix, vocab, rng)
    reqs = traffic_gen.plan(mix, n, vocab, rng, systems, timed=False)
    assert len(reqs) == n
    own = sorted(r.own_len for r in reqs)
    outs = sorted(r.n_out for r in reqs)
    assert 61 <= own[0] and own[-1] <= 765
    assert 128 <= outs[0] and outs[-1] <= 1024
    assert 170 <= own[n // 2] <= 210 and 350 <= outs[n // 2] <= 420
    # no prompt holds the mask id, most leave a tail in their first block,
    # some budgets cut the last block
    assert all(max(r.prompt) < cfg["mask_token_id"] for r in reqs)
    assert sum(1 for r in reqs if len(r.prompt) % 4) > 0.6 * n
    assert sum(1 for r in reqs if (len(r.prompt) + r.n_out) % 4) > 0.6 * n
    # the longest request there can be fits a row, written in whole blocks;
    # the pool backs every slot fully: nothing waits for a page, nothing is
    # preempted
    assert -(-(sp["tokens"] + 765 + 1024) // 4) * 4 <= e["max_len"]
    assert max(len(r.prompt) + r.n_out for r in reqs) <= e["max_len"]
    heads = {}
    for r in reqs:
        heads[tuple(r.prompt[:256])] = heads.get(tuple(r.prompt[:256]), 0) + 1
    assert len(heads) == 4
    assert max(heads.values()) - min(heads.values()) <= 1   # a quarter each
    # what the reference's joint pass is padded to holds whole blocks
    assert mix["check"]["pad_to"] % 256 == 0 and mix["check"]["sample"] >= 4


def test_the_sample_holds_what_the_comparison_has_to_see():
    fam = lib.load_module("families", "sdar_serve")
    rng = np.random.default_rng(0)
    finished = [(rng.integers(1, 99, n).tolist(), [1] * k) for n, k in
                ((40, 12), (24, 8), (16, 4), (12, 8), (13, 3), (20, 6),
                 (8, 7))]
    skipped = {tuple(finished[5][0]): 16}
    for seed in range(8):
        got = fam.pick_sample(finished, 4, seed, skipped, 4)
        assert len(got) == 4 and got[0] is finished[0]
        assert any(skipped.get(tuple(p), 0) for p, _ in got)
        assert any(len(p) % 4 for p, _ in got)
        assert any((len(p) % 4 + len(t)) % 4 for p, t in got)
    assert fam.pick_sample([], 4, 0, {}, 4) == []
    assert fam._tokens_of([5] * 6, [[5, 5, 7, 8], [9, 1, 2, 3]], 4, 5) == \
        [7, 8, 9, 1, 2]


def _rec(stamped: bool) -> dict:
    """A recorded run: four steps, two of them ticking inside the trace."""
    def stamp(t0, rows, fin, touched, total, visible, denoise, commit):
        base = (t0, t0 + 0.02, rows, 1, fin)
        if not stamped:
            return base
        load = [total // 128] * 127 + [total - 127 * (total // 128)]
        return base + (touched, total, visible, denoise, commit, *load)
    steps = [stamp(10.00, 0, 0, 0, 0, 0, 0, 0),
             stamp(10.02, 100, 0, 700, 48 * 1000, 1000 * 6 * 600, 200, 100),
             stamp(10.04, 120, 1, 760, 48 * 3500, 3500 * 6 * 600, 280, 140),
             stamp(10.06, 110, 0, 768, 48 * 6000, 6000 * 6 * 600, 360, 180)]
    run = lambda a, b: [int(a * 1e9), int(b * 1e9)]     # noqa: E731
    return {
        "window": (10.0, 11.0), "steps": steps, "device_kind": "TPU v5 lite",
        "memory_peak_bytes": 13.1e9,
        "requests": [{"ok": True, "in_window": True, "first_token": 10.01,
                      "terminal": 10.9, "prompt_len": 500, "n_out": 400,
                      "prefix_skipped": 256}] * 120,
        "trace": {"busy_s": 0.95, "window_s": 1.0,
                  "span_totals": {"engine.step": [4, 0.12]},
                  "programs": {
                      "_tick": {"count": 3, "total_s": 0.054,
                                "runs": [run(0.030, 0.048), run(0.120, 0.138),
                                         run(0.210, 0.228)]},
                      "_chunk": {"count": 2, "total_s": 0.060,
                                 "runs": [run(0.050, 0.080),
                                          run(0.140, 0.170)]}}}}


def test_the_new_readers_on_a_recorded_run():
    read = lambda name, rec: lib.load_module(       # noqa: E731
        "layer_metrics", name + ".sdar").read(rec)
    rec = _rec(stamped=True)
    assert read("tick_dev_ms", rec) == pytest.approx(18.0)
    assert read("chunk_dev_ms", rec) == pytest.approx(30.0)
    assert read("rows_per_tick", rec) == pytest.approx((100 + 120 + 110) / 3)
    assert read("tokens_per_forward", rec) == pytest.approx(4 * 180 / 540)
    assert read("moe_experts_touched_pct", rec) == pytest.approx(
        100 * (700 + 760 + 768) / 3 / 768)
    assert read("moe_load_max_over_mean", rec) == pytest.approx(1.0, abs=0.01)
    assert read("prefix_skip_pct", rec) == pytest.approx(100 * 256 / 500)
    assert read("device_idle_pct", rec) == pytest.approx(5.0)
    assert read("hbm_peak_gb", rec) == pytest.approx(13.1)
    # two whole ticks in the trace (the third may be cut): their bytes over
    # 18 ms, under the memory's peak
    fam = lib.load_module("families", "sdar_serve")
    cfg = lib.load_json("configs", "sdar-30b-a3b-chat.json")
    roof = read("tick_roofline", rec)
    low = fam.tick_bytes(cfg, 100, 120 * 500, 700) / 18e-3 / 819e9
    high = fam.tick_bytes(cfg, 120, 120 * 520, 760) / 18e-3 / 819e9
    assert 100 * low < roof < 100 * high < 100.0
    # the chunks between the two ticks' ends: 2,500 tokens' choices less the
    # second tick's 120 rows of 4, over the one chunk run between them
    mfu = read("chunk_mfu_pct", rec)
    tokens = 2500 - 480
    ctx = 120 * (500 + 400 * 0.03 / 0.89)
    assert mfu == pytest.approx(100 * fam.chunk_flops(
        cfg, tokens, 2500 * 6 * 600 - 6 * 4 * (ctx + 480), tokens * 48)
        / 30e-3 / 197e12, rel=0.02)
    assert 0.0 < mfu < 100.0


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """A program whose stamps are ``llama_serve``'s five fields: the readers
    of this family's counters return nothing and do not raise."""
    read = lambda name, rec: lib.load_module(       # noqa: E731
        "layer_metrics", name + ".sdar").read(rec)
    rec = _rec(stamped=False)
    for name in ("tokens_per_forward", "moe_experts_touched_pct",
                 "moe_load_max_over_mean", "tick_roofline", "chunk_mfu_pct"):
        assert read(name, rec) is None, name
    assert read("tick_dev_ms", rec) == pytest.approx(18.0)
    rec["trace"] = None
    for name in ("tick_dev_ms", "chunk_dev_ms", "device_idle_pct",
                 "tick_roofline", "chunk_mfu_pct"):
        assert read(name, rec) is None, name
