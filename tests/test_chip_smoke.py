"""chip_smoke.py's phases at toy sizes on the CPU mesh, and its refusal of a
backend that is not a TPU.  The real sizes run on the chip only."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models import llama
from horovod_tpu.models.resnet import ResNet

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_cpu_backend(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""                          # no result line from a CPU run
    assert "platform='cpu'" in err


def test_trainer_and_dp_equivalence_toy(smoke):
    r = smoke.phase_trainer(
        model=ResNet(stage_sizes=(1,), width=4, num_classes=10),
        image_size=8, batch_per_chip=2, num_classes=10, steps=3)
    assert r["loss_last"] < r["loss_first"]
    assert smoke.phase_dp_equivalence()["max_abs_param_diff"] < 1e-4


def test_kernel_toy_interpreted(smoke):
    r = smoke.phase_kernel(
        head_dims=(16,), batch=1, seq=16, heads=2, kv_heads=1,
        llama_shape=dict(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                         n_kv_heads=1, ffn_dim=64, max_seq_len=16),
        batch_per_chip=1, interpret=True)
    assert r["flash_d16"]["dq"] < 5e-2
    assert len(r["llama_train"]["losses"]) == 3


def test_server_toy_with_tp(smoke):
    cfg = llama.llama_tiny(dtype=jnp.float32, n_kv_heads=4)
    # 513 blocks where 17 would back both slots: at toy widths the pool has
    # to outweigh a layer's weights for "scratch under half a pool" to bite
    r = smoke.phase_server(cfg=cfg, n_slots=2, max_len=32, chunk=4,
                           max_new=6, http=2, tp_size=4, n_blocks=513)
    assert r["tp1_logit_err"] <= r["logit_tol"]
    assert r["tp4_logit_err"] <= r["logit_tol"]
    for tp in (1, 4):
        scratch = r[f"tp{tp}"]["scratch"]
        assert scratch["pool_bytes"] * tp == 2 * 2 * 513 * 4 * 64 * 4
        for prog in ("tick", "chunk"):
            assert 0 < scratch[f"{prog}_temp_bytes"] < scratch["pool_bytes"] / 2


def test_eager_toy(smoke):
    assert smoke.phase_eager()["first_call_s"] > 0


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
