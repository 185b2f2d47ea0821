"""The ``dots3_serve`` family on the CPU at toy sizes: the cell end to end
through ``run.py`` (``rehearse.make_copy`` leaves a configuration it does not
know at full size, so this file cuts its own configuration and traffic in the
copy), whole runs with the served path broken, which have to come out not
correct, and the reference's blocked computation against its unblocked one."""

import json
import os

import numpy as np
import pytest

import rehearse

CELL = "dots3_longdoc32k"
TINY = {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 1e4, "index_n_heads": 2, "index_head_dim": 8,
    "index_topk": 6, "swa_num_attention_heads": 2,
    "swa_num_key_value_heads": 2, "swa_q_lora_rank": 16,
    "swa_kv_lora_rank": 16, "swa_qk_nope_head_dim": 12,
    "swa_qk_rope_head_dim": 4, "swa_v_head_dim": 8, "swa_rope_theta": 1e3,
    "sliding_window_size": 5, "n_routed_experts": 8,
    "n_routed_experts_published": 16, "moe_intermediate_size": 16,
    "num_experts_per_tok": 4, "vocab_size": 128,
    # float32: at this size one expert is a quarter of a layer and one key a
    # sixth of a selection, so a near-tie that bfloat16 flips moves a logit by
    # more than any limit; the precision's own readings are the chip's
    "torch_dtype": "float32"}


def toy_config() -> dict:
    with open(os.path.join(rehearse.ROOT, "benchmark", "configs",
                           "dots3-note-prev.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    return cfg


def make_copy(dst: str) -> str:
    rehearse.make_copy(dst)
    b = os.path.join(dst, "benchmark")
    rehearse._edit(os.path.join(b, "configs", "dots3-note-prev.json"),
                   lambda d: d.update(TINY))

    def mix(d):
        d["engine"].update(n_slots=2, max_len=64, chunk=8)
        d.update(requests_per_window_second=6.0, trace_s=0.3, stratify=2)
        d["shapes"].update(rehearse.TINY_SHAPES)
        d["shapes"]["tail_tokens"] = 4
        d["check"] = {"sample": 3, "pad_to": 64}

    rehearse._edit(os.path.join(b, "traffic", "longdoc32k.json"), mix)
    return dst


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("bench_dots3")))


def _ok(rc, last, out, err):
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last is not None
    return last


def test_the_cell_runs_untraced_and_is_correct(copy):
    last = _ok(*rehearse.run_in_copy(copy, CELL, seed=2**31 + 5))
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"serve_tput", "setup_s"}


def test_the_traced_run_reports_the_cells_per_layer_metrics(copy):
    """The chip's part of the trace is the recorded one, so the device times
    are another program's; the readers, the stamps and the counters are this
    family's own."""
    last = _ok(*rehearse.run_in_copy(copy, CELL, trace=1))
    spec = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert {"tick_dev_ms.dots3", "chunk_dev_ms.dots3", "rows_per_tick.dots3",
            "moe_held_share_pct.dots3", "moe_load_max_over_mean.dots3",
            "dsa_selected_pct.dots3", "device_idle_pct.dots3",
            "hbm_peak_gb.dots3"} <= set(last["metrics"]) <= want
    held = last["metrics"]["moe_held_share_pct.dots3"]["value"]
    assert 20.0 < held < 80.0          # 8 of 16 experts held: about half
    assert 0.0 < last["metrics"]["dsa_selected_pct.dots3"]["value"] < 100.0


BROKEN = {
    "selection ignored: every visible key attended": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.latent_moe as M\n"
        "def _all(cfg, lp, h, c_q, qpos, index_flat, off, table, bs):\n"
        "    b, t, _ = h.shape\n"
        "    m = table.shape[1] * bs\n"
        "    idx = jnp.broadcast_to(jnp.arange(m), (b, t, m))\n"
        "    return idx, idx <= qpos[:, :, None]\n"
        "M._index_select = _all\n"),
    "the routed experts dropped": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.latent_moe as M\n"
        "M.held_experts = lambda cfg, lp, h2, valid: (\n"
        "    jnp.zeros_like(h2), jnp.zeros((cfg.held_count,), jnp.int32))\n"),
    "a served token altered where it is produced": (
        "import horovod_tpu.models.latent_moe as M\n"
        "_d = M.decode_chunk_paged\n"
        "def _neg(*a, **k):\n"
        "    logits, cache = _d(*a, **k)\n"
        "    return -logits, cache\n"
        "M.decode_chunk_paged = _neg\n"),
    "fp8 in the program's place": (
        "import jax.numpy as jnp\n"
        "import horovod_tpu.models.latent_moe as M\n"
        "def _f8(x):\n"
        "    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)\n"
        "M._dot = lambda x, w, dt: _f8(_f8(x) @ _f8(w.astype(dt)))\n"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_served_path_is_not_correct(copy, fault):
    rc, last, out, err = rehearse.run_in_copy(copy, CELL, extra=BROKEN[fault])
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last["correct"] is False
    failed = [ln for ln in out.splitlines() if "NOT CORRECT" in ln]
    assert any("check gap_" in ln for ln in failed), failed


def test_blocked_reference_equals_unblocked():
    """The reference takes queries, heads and key sets a block at a time so
    that 32k positions fit; at a small size the blocks change nothing."""
    from benchmark import lib
    ref = lib.load_module("reference", "dots3")
    cfg = toy_config()
    rng = np.random.default_rng(3)
    seq = rng.integers(1, cfg["vocab_size"], 32).tolist()
    pos = [list(range(32))]
    whole = ref.logits_at(cfg, 9, [seq], pos, "float32", pad_to=32,
                          q_block=32, head_block=4)[0]
    blocked = ref.logits_at(cfg, 9, [seq], pos, "float32", pad_to=32,
                            q_block=8, head_block=1)[0]
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=2e-5, rtol=0)
    # each expert over only the tokens that chose it: the same sum, and a
    # cap that is too small says so
    m, w = ref._dims(cfg), ref.layer_weights(cfg, ref.seed_arg(9), 1)
    import jax.numpy as jnp
    h = jnp.asarray(np.random.default_rng(4).standard_normal((32, 32)),
                    jnp.float32)
    every, _, _ = ref.moe(m, h, w, "float32")
    capped, _, over = ref.moe(m, h, w, "float32", cap=32)
    np.testing.assert_allclose(np.asarray(capped), np.asarray(every),
                               atol=2e-6, rtol=0)
    assert not bool(over) and bool(ref.moe(m, h, w, "float32", cap=2)[2])
    # padding the sequence at its end reaches no earlier position
    padded = ref.logits_at(cfg, 9, [seq], pos, "float32", pad_to=48,
                           q_block=8, head_block=2)[0]
    np.testing.assert_allclose(np.asarray(padded), np.asarray(whole),
                               atol=2e-5, rtol=0)
