"""Unit coverage for bench.py's measurement stack — the driver-facing
artifact generator.  Mirrors the reference's practice of testing its
harness conventions (reference examples/pytorch_synthetic_benchmark.py is
the timing-loop model) and pins what the harness promises:

* it runs on a TPU or exits non-zero: no child process, no CPU result;
* every timing fence is a host read of a value;
* MFU handles unknown flops/peak as None, never 0.0;
* a step that will not lower, and an arm that raises, end the run.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(_REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_peak_flops_device_kind_mapping(bench):
    """One peaks table for the repo (device_telemetry): 'TPU v5 lite' (this
    deployment's device kind) is the v5e; a bare 'v5' of unknown flavour has
    NO peak rather than the v5p's."""
    from horovod_tpu.device_telemetry import lookup_peak_flops

    assert lookup_peak_flops("TPU v5 lite") == 197e12
    assert lookup_peak_flops("TPU v5p") == 459e12
    assert lookup_peak_flops("TPU v5") is None
    assert bench._peak_flops_per_chip() is None     # the suite's CPU


def test_mfu_none_propagation(bench):
    assert bench._mfu(None, 10.0) is None          # no flops -> no MFU
    # The test env pins the cpu backend: unknown device kind -> no peak
    # -> MFU must be None (never 0.0 masquerading as a measurement).
    assert jax.default_backend() == "cpu"
    assert bench._mfu(1e12, 10.0) is None


def test_main_refuses_cpu_backend(bench, capsys):
    """No chip: non-zero, the platform found named on stderr, and no
    result line — a CPU run is not a measurement."""
    assert bench.main() != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "platform='cpu'" in err


def test_time_loop_counts_every_step(bench):
    calls = []

    def step_once():
        calls.append(1)
        return jnp.float32(len(calls))

    rate = bench._time_loop(step_once, num_iters=3, num_batches=4)
    assert len(calls) == 12
    assert rate > 0


def test_readback_forces_host_values(bench):
    # A pytree with nested arrays must come back without raising, and the
    # helper must accept scalars produced by timed loops.
    bench._readback({"a": jnp.arange(3.0), "b": (jnp.float32(1),)})
    bench._readback(jnp.float32(2))


def test_aot_compile_returns_warm_output_and_flops(bench):
    @jax.jit
    def step(x):
        return x * 2.0

    fn, flops, out = bench._aot_compile(step, jnp.arange(4.0))
    assert jnp.allclose(out, jnp.arange(4.0) * 2)
    # Compiled path: callable must be reusable.
    again = fn(jnp.ones(4))
    assert jnp.allclose(again, 2.0)
    # flops is float-or-None, never 0.0 masquerading as a measurement.
    assert flops is None or flops > 0


def test_aot_compile_needs_a_lowerable_step(bench):
    """A step that will not lower is a failure, not a slower path."""
    def plain_step(x):           # no .lower attribute
        return x + 1.0

    with pytest.raises(AttributeError):
        bench._aot_compile(plain_step, jnp.zeros(2))


def test_compile_cache_dir_env_set_leaves_config_alone(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set jax reads it itself: the program
    reports that directory and sets nothing in code."""
    from horovod_tpu.utils.env import compile_cache_dir

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert compile_cache_dir(str(tmp_path)) == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_default_is_checkout(tmp_path, monkeypatch):
    """Unset: the fixed <checkout>/.jax_cache — the path is part of the
    cache key, so nothing about the host, process or time goes into it."""
    from horovod_tpu.utils.env import compile_cache_dir

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        want = os.path.join(str(tmp_path), ".jax_cache")
        assert compile_cache_dir(str(tmp_path)) == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache_dir(str(tmp_path)) == want     # and stays put
    finally:
        # The config is process-global: restore so later suite compiles
        # don't write into this test's deleted tmp dir.
        jax.config.update("jax_compilation_cache_dir", before)


def test_generate_arm_rehearsal_path(bench, monkeypatch):
    """The generation extras arm's rehearsal config runs end-to-end on the
    CPU stand-in and reports the labeled shape."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_BENCH_FORCE_TPU_PATHS", "1")
    out = bench._bench_llama_decode(hvd)
    assert out["generate_tokens_per_sec_per_chip"] > 0
    assert out["generate_ms_per_new_token"] > 0
    assert out["generate_shape"] == "b2_prompt8_new8"


def test_serving_arm_rehearsal_schema(bench, monkeypatch):
    """The serving extras arm's rehearsal config runs the real
    ServeEngine-vs-static measurement end-to-end on the CPU stand-in and
    reports the schema the dashboard keys on.  (The ratio itself is only
    asserted > 1 at tuned scale in test_serving_scheduler.py — the toy
    rehearsal is dispatch-bound on CPU.)"""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_BENCH_FORCE_TPU_PATHS", "1")
    out = bench._bench_serving(hvd)
    assert out["serve_tokens_per_sec"] > 0
    assert isinstance(out["serve_vs_static_ratio"], float)
    assert out["serve_shape"] == "s2_len32_chunk8_req6"


def test_serving_overcommit_arm_rehearsal_schema(bench, monkeypatch):
    """The fault-tolerant serving arm (overcommitted paged pool +
    preemption-with-replay) runs the real measure_throughput path on
    the CPU stand-in and reports the dashboard schema, including the
    timed pass's preemption count."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_BENCH_FORCE_TPU_PATHS", "1")
    out = bench._bench_serving_overcommit(hvd)
    assert out["serve_overcommit_tokens_per_sec"] > 0
    assert out["serve_overcommit_preemptions"] >= 0
    assert out["serve_overcommit_shape"] == (
        "s2_len32_chunk8_blk6_pre2_req6")


def test_bench_fusion_autotune_arm_cpu(bench, monkeypatch):
    """The fusion A/B plus the autotuner-trajectory arm (VERDICT r3 #2's
    converged-threshold record) runs end-to-end on the CPU stand-in: both
    A/B arms report, the autotune arm completes some rounds, and the
    trajectory/threshold fields land in the extras dict."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_BENCH_AUTOTUNE_S", "5")
    monkeypatch.setenv("HVD_TPU_BENCH_FUSION_ROUNDS", "2")
    out = bench._bench_fusion(hvd)
    assert out["fused_ms"] > 0 and out["unfused_ms"] > 0
    assert out["fused_arm_tensors_fused"] > 0
    assert out["autotune_rounds"] >= 1
    # The hill climber may legitimately pin threshold 0 on CPU (fusion is
    # slower there) — assert the field exists, not a value.
    assert isinstance(out["autotune_threshold_bytes"], int)
    assert isinstance(out["autotune_log"], list)


def test_main_spawns_no_process(bench, monkeypatch, capsys):
    """bench.py is one process: refusing the CPU involves no child, no
    probe and no fallback worker."""
    def no_children(*a, **kw):
        raise AssertionError(f"bench spawned a process: {a}")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(subprocess, "run", no_children)
    assert bench.main() == 1
    assert capsys.readouterr().out == ""


def test_script_exits_nonzero_without_a_chip():
    """`JAX_PLATFORMS=cpu python bench.py`: the exit code is the verdict."""
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert out.stdout == ""
    assert "platform='cpu'" in out.stderr


def _fake_primary(monkeypatch, bench):
    monkeypatch.setattr(bench, "_bench_resnet", lambda hvd: {
        "images_per_sec_per_chip": 207.1, "mfu": None,
        "flops_per_step": None})


def test_failed_arm_prints_line_then_raises(bench, monkeypatch, capsys):
    """An arm that raises ends the run with its error — after the line
    gathered so far (primary number, finished arms) is printed."""
    import horovod_tpu as hvd

    def good(hvd):
        return {"good_arm": 1}

    def bad(hvd):
        raise RuntimeError("arm broke")

    def never(hvd):
        raise AssertionError("ran past a failed arm")

    _fake_primary(monkeypatch, bench)
    monkeypatch.setattr(bench, "_ARMS", (good, bad, never))
    with pytest.raises(RuntimeError, match="arm broke"):
        bench._measure(hvd, "TPU v5 lite", "tpu")
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] == 207.1 and line["vs_baseline"] == 2.0
    assert line["extras"]["good_arm"] == 1
    assert line["extras"]["device"] == "TPU v5 lite"


def test_budget_skips_arms_by_name(bench, monkeypatch, capsys):
    """Past HVD_TPU_BENCH_BUDGET the remaining arms are skipped and
    named, so a short line is never mistaken for a full one."""
    import horovod_tpu as hvd

    def never(hvd):
        raise AssertionError("ran past the budget")

    _fake_primary(monkeypatch, bench)
    monkeypatch.setattr(bench, "_ARMS", (never,))
    monkeypatch.setenv("HVD_TPU_BENCH_BUDGET", "0")
    bench._measure(hvd, "TPU v5 lite", "tpu")
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["extras"]["skipped"] == ["never"]


def test_vit_arm_rehearsal_path(bench, monkeypatch):
    """The ViT extras arm's rehearsal config runs end-to-end on the CPU
    stand-in — AOT-lowered through ``make_train_step(...).lower`` like on
    the chip — and reports the labeled tiny shape."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_BENCH_FORCE_TPU_PATHS", "1")
    out = bench._bench_vit(hvd)
    assert out["vit_b16_images_per_sec_per_chip"] > 0
    assert out["vit_shape"] == "b2_img16_tiny"


def test_eager_overhead_bench_single_arm():
    """tools/eager_overhead_bench.py --mode single: one arm end-to-end in
    a subprocess (the docs/benchmarks.md "Eager engine overhead" table's
    producer), RESULT line parseable with sane fields."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu", EAGER_OVH_ROUNDS="2",
               EAGER_OVH_BURST="4")
    out = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "tools", "eager_overhead_bench.py"),
         "--mode", "single", "--threshold", str(64 * 1024 * 1024)],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    lines = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert len(lines) == 1, out.stdout
    rec = json.loads(lines[0].split("RESULT ", 1)[1])
    assert rec["arm"] == "single.fused"
    assert rec["ops_per_sec"] > 0
    assert rec["tensors_fused"] == 8  # 2 rounds x 4-tensor fused bursts


def test_sustained_run_smoke():
    """tools/tpu_sustained_run.py --smoke: the stability harness's CPU CI
    shape, SUMMARY parseable with the drift/stall fields present."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "tools", "tpu_sustained_run.py"), "--smoke"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    line = [l for l in out.stdout.splitlines() if l.startswith("SUMMARY ")]
    assert len(line) == 1, out.stdout
    rec = json.loads(line[0].split("SUMMARY ", 1)[1])
    assert rec["smoke"] is True
    assert rec["total_steps"] > 0
    assert "drift_pct" in rec and "stalled_groups" in rec


def test_spec_arm_rehearsal_counts_divergence(bench, monkeypatch):
    """The speculation arm reports, per workload, how many requests'
    spec-on tokens differ from spec-off instead of asserting there are
    none (bf16 near-ties on the chip part a few); in the float32 rehearsal
    the greedy bit-identity holds and both counts are 0."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_BENCH_FORCE_TPU_PATHS", "1")
    out = bench._bench_serve_spec(hvd)
    assert out["serve_spec_diverged_requests"] == 0
    assert out["serve_spec_hostile_diverged_requests"] == 0
    assert out["serve_spec_accepted_per_round"] > 1
    assert out["serve_spec_shape"] == "s2_len32_chunk4_k4_new20_req6"
