"""Tensor-parallel sharded serving: ServeEngine on a ('tp',) mesh.

The acceptance pins of the sharded decode path, all on the conftest's
faked 8-device CPU mesh:

1. *Sharded parity*: a ``tp_size=N`` engine emits tokens identical to
   the unsharded engine on the same request stream — prefix cache
   on/off × speculation on/off, and through a preempt-replay round
   trip.  GSPMD only changes the psum reduction order inside a logit
   (~1e-6); greedy argmax makes the token stream deterministic.
2. *Fixed signature*: explicit in/out shardings on every jit boundary
   keep ``compile_cache_sizes()`` at one signature per program under
   the mesh, retrace sentry silent.
3. *Shard accounting*: the head-split pool's per-chip gauges times
   ``tp.size`` equal the logical ``kv.*`` totals, and the block pool /
   prefix cache stay host-side (``free_block_count`` is shard-blind).
4. *Zero new plumbing*: a sharded engine slots under ``LocalReplica``
   and clones via ``clone_engine`` unchanged.

Mesh construction error paths (``make_mesh`` / ``data_parallel_mesh`` /
``tensor_parallel_mesh`` ValueError with the counts in the message)
ride along, plus a fresh-process worker that re-execs with
``--xla_force_host_platform_device_count=8`` forced and the
``HVD_TPU_TP`` env knob set (tests/multiprocess_tp_worker.py).
"""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as metrics_mod
from horovod_tpu.models import llama
from horovod_tpu.parallel.mesh import (
    data_parallel_mesh, make_mesh, tensor_parallel_mesh,
)
from horovod_tpu.router import LocalReplica
from horovod_tpu.serving import Request
from horovod_tpu.serving_scheduler import ServeEngine
from horovod_tpu.supervisor import clone_engine

HERE = os.path.dirname(os.path.abspath(__file__))
TP_WORKER = os.path.join(HERE, "multiprocess_tp_worker.py")


@pytest.fixture(scope="module")
def world():
    # n_kv_heads=4 (llama_tiny default is 2) so the KV-head axis splits
    # at tp=4 too; every other sharded axis of the tiny config already
    # divides 4.
    cfg = llama.llama_tiny(dtype=jnp.float32, n_kv_heads=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    return cfg, params


def _solo(params, cfg, prompt, n_new, max_len=32):
    return np.asarray(llama.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg,
        max_new_tokens=n_new, max_len=max_len,
    ))[0]


def _requests():
    # Shared 9-token stem (2+ cache blocks at block_size=4) plus short
    # per-request tails — prefix-cache-hittable AND drafter-friendly.
    stem = list(range(2, 11))
    return [Request(prompt=stem + [40 + i], max_new_tokens=5)
            for i in range(3)]


# -- mesh construction error paths (no devices harmed) -----------------------


def test_make_mesh_device_count_error():
    with pytest.raises(ValueError) as e:
        make_mesh(dp=3)                     # 8 faked devices, need 3
    assert "need 3 devices" in str(e.value) and "have 8" in str(e.value)


def test_make_mesh_axis_size_error():
    with pytest.raises(ValueError) as e:
        make_mesh(dp=0)
    assert "'dp' must be >= 1" in str(e.value)


def test_data_parallel_mesh_empty_devices_error():
    with pytest.raises(ValueError) as e:
        data_parallel_mesh([])
    assert "non-empty" in str(e.value) and "0 devices" in str(e.value)


def test_tensor_parallel_mesh_errors_and_shape():
    with pytest.raises(ValueError) as e:
        tensor_parallel_mesh(16)
    assert "needs 16" in str(e.value) and "have 8" in str(e.value)
    with pytest.raises(ValueError):
        tensor_parallel_mesh(0)
    mesh = tensor_parallel_mesh(2)
    assert mesh.axis_names == ("tp",)
    assert mesh.devices.shape == (2,)


# -- ServeEngine knob validation + tp_size=1 unchanged -----------------------


def test_engine_tp_validation(world):
    cfg, params = world
    kw = dict(n_slots=2, max_len=16, chunk=4,
              metrics=metrics_mod.NULL)
    with pytest.raises(ValueError, match="tp_size must be >= 1"):
        ServeEngine(params, cfg, tp_size=0, **kw)
    with pytest.raises(ValueError, match="does not divide"):
        ServeEngine(params, cfg, tp_size=3, **kw)   # n_heads=4 % 3
    # Every sharded axis of this config divides 16 (heads=16 via
    # override), so the 8-device host hits the mesh device-count error.
    wide = llama.llama_tiny(dtype=jnp.float32, n_heads=16,
                            n_kv_heads=16)
    wide_params = llama.init_params(wide, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="needs 16"):
        ServeEngine(wide_params, wide, tp_size=16, **kw)


def test_tp1_default_unsharded(world):
    cfg, params = world
    reg = metrics_mod.MetricsRegistry()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      metrics=reg)
    assert eng.tp_size == 1 and eng.mesh is None
    # no device_put detour: the engine holds the caller's own arrays, in
    # its model's serving tree (wq / wk / wv laid out anew as one wqkv)
    given = {id(x) for x in jax.tree.leaves(params)}
    assert [k for k, v in eng.params["layers"].items()
            if id(v) not in given] == ["wqkv"]
    assert all(eng.params[k] is params[k]
               for k in ("embed", "final_norm", "lm_head"))
    g = eng.metrics_snapshot()["gauges"]
    assert g["tp.size"] == 1
    assert g["kv.shard_total_bytes"] == g["kv.total_bytes"]
    assert g["kv.shard_block_bytes"] == g["kv.block_bytes"]


# -- sharded parity / frozen signatures / shard accounting -------------------


@pytest.mark.tp
@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("spec", [False, True])
def test_sharded_token_parity(world, tp_devices, prefix_cache, spec):
    """The acceptance pin: tp=2 tokens == tp=1 tokens on the same
    stream, for every prefix-cache × speculation combination, with one
    jit signature per program on the sharded engine."""
    cfg, params = world
    reqs = _requests()
    kw = dict(n_slots=2, max_len=32, chunk=4,
              prefix_cache=prefix_cache, spec=spec, draft_k=3,
              metrics=metrics_mod.NULL)
    outs = {}
    for tp in (1, 2):
        eng = ServeEngine(params, cfg, tp_size=tp, **kw)
        out = eng.run(reqs)
        assert all(r.ok for r in out), [r.status for r in out]
        outs[tp] = [list(r) for r in out]
        live = {k: v for k, v in eng.compile_cache_sizes().items()
                if not (k in ("sample", "tick") and spec)}   # spec: neither
        assert set(live.values()) == {1}, (tp, live)
    assert outs[2] == outs[1]
    # and both match the solo run (invariant 2, now across the mesh)
    for req, got in zip(reqs, outs[2]):
        want = _solo(params, cfg, req.prompt, req.max_new_tokens)
        np.testing.assert_array_equal(np.asarray(got, np.int64),
                                      want.astype(np.int64))


@pytest.mark.tp
def test_sharded_compile_frozen_and_shard_gauges(world, tp_devices):
    """Two serve passes on one sharded engine: the jit caches never
    move past one signature, the retrace sentry stays silent, and the
    per-shard KV gauges times tp_size equal the logical pool."""
    cfg, params = world
    reg = metrics_mod.MetricsRegistry()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=4,
                      tp_size=2, prefix_cache=True, metrics=reg)
    for _ in range(2):
        out = eng.run(_requests())
        assert all(r.ok for r in out)
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    snap = eng.metrics_snapshot()
    assert snap["counters"].get("serve.retrace", 0) == 0
    g = snap["gauges"]
    assert g["tp.size"] == 2
    assert g["kv.shard_total_bytes"] * 2 == g["kv.total_bytes"]
    assert g["kv.shard_block_bytes"] * 2 == g["kv.block_bytes"]
    for state in ("free", "referenced", "cached"):
        assert (g[f"kv.shard_{state}_bytes"] * 2
                == g[f"kv.{state}_bytes"]), state
    kv = snap["memory"]["kv"]
    assert kv["tp_size"] == 2
    assert kv["shard_total_bytes"] * 2 == kv["total_bytes"]
    # host-side block accounting is shard-blind: every non-trash block
    # is free/referenced/cached exactly once, in *blocks*, not bytes
    n_blocks = eng.pcache.k.shape[1]
    assert (kv["free_blocks"] + kv["referenced_blocks"]
            + kv["cached_blocks"]) == n_blocks - 1
    # supervisor respawn path: the clone carries the mesh degree
    clone = clone_engine(eng)
    assert clone.tp_size == 2
    req = _requests()[0]
    got = clone.run([req])[0]
    np.testing.assert_array_equal(
        np.asarray(list(got), np.int64),
        _solo(params, cfg, req.prompt, req.max_new_tokens).astype(
            np.int64))


@pytest.mark.tp
@pytest.mark.parametrize("tp", [1, 2])
def test_sampling_program_keeps_one_signature(world, tp_devices, tp):
    """The program a step reads its tokens from is pinned like the others:
    one signature after 50 steps of arrivals, prefix hits, recycling and
    preemption, on one device and across the mesh (replicated, as every
    array the host reads), and the retrace sentry has counted nothing."""
    cfg, params = world
    reg = metrics_mod.MetricsRegistry(event_log=None)
    eng = ServeEngine(params, cfg, tp_size=tp, n_slots=3, max_len=32,
                      chunk=4, n_blocks=14, preempt_after=2,
                      prefix_cache=True, metrics=reg)
    rng = np.random.default_rng(5)
    for _ in range(50):
        if rng.random() < 0.4:
            n = int(rng.integers(1, 13))
            eng.submit(Request(
                prompt=[1, 2, 3, 4] + rng.integers(
                    5, cfg.vocab_size, n).tolist(),
                max_new_tokens=int(rng.integers(1, 14))))
        eng.step()
    while eng.pending():
        eng.step()
    assert eng.step_index >= 50
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    counters = reg.snapshot()["counters"]
    assert counters["serve.retrace"] == 0
    assert counters["serve.tokens_emitted"] > 50
    assert {e.kind for e in eng.events} >= {"admit", "hit", "recycle"}


@pytest.mark.tp
def test_sharded_preempt_replay_parity(world, tp_devices):
    """Preemption-with-replay on the sharded engine: the starved head
    evicts a decoding victim, the replay resumes through the head-split
    pool, and both outputs stay solo-exact with zero new signatures
    (the block tables being host-side data is what makes this free)."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      block_size=4, n_blocks=6, preempt_after=2,
                      tp_size=2, metrics=metrics_mod.NULL)
    victim = Request(prompt=[5, 17, 42], max_new_tokens=13)
    head = Request(prompt=[7, 8], max_new_tokens=6)
    out = eng.run([victim, head])
    assert eng.counters["preemptions"] >= 1
    for req, res in zip([victim, head], out):
        assert res.status == "OK"
        want = _solo(params, cfg, req.prompt, req.max_new_tokens,
                     max_len=16)
        np.testing.assert_array_equal(np.asarray(list(res), np.int64),
                                      want.astype(np.int64))
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    assert eng.free_block_count() == 5


@pytest.mark.tp
def test_sharded_engine_under_local_replica(world, tp_devices):
    """A sharded engine behind the router's LocalReplica handle: the
    pump thread drives it untouched, the probe view reports the mesh
    degree (capacity accounting for multi-chip replicas), and the
    served tokens stay solo-exact."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=4,
                      tp_size=2, metrics=metrics_mod.NULL)
    rep = LocalReplica(eng, name="tp2")
    try:
        req = _requests()[0]
        done = threading.Event()
        box = {}
        rep.submit(req, lambda res: (box.update(res=res), done.set()))
        assert done.wait(timeout=120), "sharded replica never answered"
        res = box["res"]
        assert res is not None and res.ok
        np.testing.assert_array_equal(
            np.asarray(list(res), np.int64),
            _solo(params, cfg, req.prompt,
                  req.max_new_tokens).astype(np.int64))
        assert rep.probe()["tp_size"] == 2
    finally:
        rep.stop()


def test_tp_worker_subprocess(world):
    """A fresh interpreter re-execs with the 8-virtual-device flag
    forced and HVD_TPU_TP=2 — the env-knob path end to end, skipping
    cleanly when devices can't be faked."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # the worker forces its own
    proc = subprocess.Popen(
        [sys.executable, TP_WORKER], env=env,
        cwd=os.path.dirname(HERE),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    out = proc.communicate(timeout=300)[0]
    assert proc.returncode == 0, f"worker rc={proc.returncode}:\n{out}"
    if "WORKER_SKIP" in out:
        pytest.skip("worker could not fake a multi-device CPU host:\n"
                    + out)
    assert "WORKER_OK" in out, out
    payload = json.loads(out.split("WORKER_OK ", 1)[1].splitlines()[0])
    assert payload["tp_size"] == 2
    assert payload["compile_cache_sizes"] == {
        "sample": 0, "tick": 0, "chunk": 1, "set_row": 1, "spec_tick": 1}
    # greedy determinism across processes: the worker's sharded tokens
    # match this process's solo runs
    cfg, params = world
    for req, toks in zip(_requests(), payload["tokens"]):
        np.testing.assert_array_equal(
            np.asarray(toks, np.int64),
            _solo(params, cfg, req.prompt,
                  req.max_new_tokens).astype(np.int64))
