"""chip_smoke.py — does the system still start on the chip?

One process takes the locally attached TPU and drives both device paths
once through the calls a user makes (README's five-step recipe,
docs/inference.md): the ResNet-50 trainer, the Pallas flash kernels and a
Llama train step that uses them, ``ServeEngine`` on the 1.11 B Llama shape
(directly and behind ``RouterServer``), and the eager collectives over the
native controller.  With more than one chip it also checks that the work
spreads over all of them.  Weights are random, made from a seed; widths are
the real ones.

It fails unless ``jax.devices()[0].platform == "tpu"`` and never pins, probes
or falls back to another platform.  A phase that raises ends the run.  The
times it prints are smoke timings (one cold call, a few warm ones), not
benchmark results.  The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The phase functions take their sizes as arguments so that
tests/test_chip_smoke.py can run them at toy sizes on the CPU mesh.  Alone in
a directory, without the package, the import below is where it fails.
"""

from __future__ import annotations

import importlib.metadata
import json
import math
import os
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import metrics as metrics_mod
from horovod_tpu.device_telemetry import lookup_peak_flops
from horovod_tpu.models import llama
from horovod_tpu.models.mnist import MnistMLP
from horovod_tpu.models.resnet import ResNet50
from horovod_tpu.parallel.attention import dense_attention
from horovod_tpu.parallel.flash_attention import (
    flash_attention,
    interpret_mode,
)
from horovod_tpu.router import LocalReplica, RouterServer
from horovod_tpu.serving import Request
from horovod_tpu.serving_scheduler import ServeEngine
from horovod_tpu.utils.env import compile_cache_dir

HERE = os.path.dirname(os.path.abspath(__file__))
_T0 = time.monotonic()


def _say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def _timed_steps(step_once, n: int) -> tuple[float, float, list]:
    """Run ``step_once`` ``n`` times, each waiting for its result.  Returns
    (seconds of the first call, which compiles; median milliseconds of the
    rest; every call's value)."""
    times, values = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        values.append(step_once())
        times.append(time.perf_counter() - t0)
    rest = sorted(times[1:]) or [float("nan")]
    return times[0], rest[len(rest) // 2] * 1e3, values


def _assert_one_compile(step) -> None:
    """The step saw one signature, although the optimizer state it was first
    given (a bare ``tx.init``, as in README's recipe) sat on one device."""
    assert step._cache_size() == 1, step._cache_size()


# ── phase 1: the trainer ───────────────────────────────────────────────────


def phase_trainer(*, model=None, image_size: int = 224,
                  batch_per_chip: int = 64, num_classes: int = 1000,
                  steps: int = 10) -> dict:
    """README's recipe on ResNet-50: ``steps`` SGD steps on one fixed batch;
    the loss stays finite and ends lower than it began."""
    n = hvd.size()
    if model is None:
        model = ResNet50(dtype=jnp.bfloat16)
    kimg, klab = jax.random.split(jax.random.key(7))
    shape = (batch_per_chip * n, image_size, image_size, 3)
    # Placed as ShardedLoader places a batch: dim 0 split over the ranks.
    images = jax.device_put(
        jax.random.normal(kimg, shape, jnp.float32), hvd.rank_sharding())
    labels = jax.device_put(
        jax.random.randint(klab, shape[:1], 0, num_classes, jnp.int32),
        hvd.rank_sharding())
    variables = jax.jit(model.init, static_argnames="train")(
        jax.random.key(0), images[:1], train=False)
    batch_stats = variables["batch_stats"]

    def loss_fn(params, batch):
        x, y = batch
        logits, _ = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    tx = hvd.DistributedOptimizer(optax.sgd(0.01 * n, momentum=0.9))
    params = hvd.broadcast_parameters(variables["params"], root_rank=0)
    opt_state = jax.jit(tx.init)(params)
    step = hvd.make_train_step(loss_fn, tx)
    state = [params, opt_state]

    def one() -> float:
        out = step(state[0], state[1], (images, labels))
        state[:] = out.params, out.opt_state
        return float(out.loss)

    first_s, steady_ms, losses = _timed_steps(one, steps)
    assert all(math.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0], losses
    _assert_one_compile(step)

    if n > 1:
        devices = set(jax.devices())
        for leaf in jax.tree.leaves(state[0]):
            assert leaf.sharding.device_set == devices, leaf.sharding
        shards = images.addressable_shards
        assert len(shards) == n, len(shards)
        assert {s.data.shape[0] for s in shards} == {batch_per_chip}
        assert {s.device for s in shards} == devices
        for d in jax.devices():
            stats = d.memory_stats()      # None on the CPU test mesh
            assert stats is not None or d.platform != "tpu", d
            if stats is not None:
                assert stats["bytes_in_use"] > 0, (d, stats)
    return {"first_step_s": first_s, "steady_step_ms": steady_ms,
            "loss_first": losses[0], "loss_last": losses[-1]}


def phase_dp_equivalence() -> dict:
    """One ``make_train_step`` step of a BN-free model on a rank-dependent
    batch lands on the parameters one device reaches on the whole batch."""
    n, per_rank = hvd.size(), 8
    model = MnistMLP(hidden=64)
    rng = np.random.RandomState(3)
    # Row r*per_rank+i belongs to rank r, and every rank's rows differ.
    x = rng.randn(n * per_rank, 28, 28, 1).astype(np.float32)
    x += 0.1 * np.repeat(np.arange(n, dtype=np.float32),
                         per_rank)[:, None, None, None]
    y = rng.randint(0, 10, size=(n * per_rank,)).astype(np.int32)

    def loss_fn(p, batch):
        bx, by = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply({"params": p}, bx), by).mean()

    # The chip multiplies float32 in bfloat16 passes unless told otherwise;
    # this comparison is about the collectives, so both sides get float32.
    with jax.default_matmul_precision("highest"):
        params = model.init(jax.random.key(1), jnp.asarray(x[:1]))["params"]
        # The reference: plain jax and optax on one device, whole batch.
        opt = optax.sgd(0.1)
        grads = jax.jit(jax.grad(loss_fn))(
            params, (jnp.asarray(x), jnp.asarray(y)))
        updates, _ = opt.update(grads, opt.init(params), params)
        want = optax.apply_updates(params, updates)

        tx = hvd.DistributedOptimizer(opt)
        rep = hvd.broadcast_parameters(params, root_rank=0)
        batch = (jax.device_put(x, hvd.rank_sharding()),
                 jax.device_put(y, hvd.rank_sharding()))
        out = hvd.make_train_step(loss_fn, tx)(
            rep, jax.jit(tx.init)(rep), batch)
    worst = 0.0
    for a, b in zip(jax.tree.leaves(out.params), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        # float32: the shards' means are summed in another order.
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        worst = max(worst, float(np.abs(a - b).max()))
    return {"max_abs_param_diff": worst}


# ── phase 2: the kernel ────────────────────────────────────────────────────

#: A Llama train shape of 189 M parameters.
LLAMA_TRAIN = dict(vocab_size=32768, dim=1024, n_layers=8, n_heads=16,
                   n_kv_heads=4, ffn_dim=4096, max_seq_len=2048)


def phase_kernel(*, head_dims=(64, 128), batch: int = 2,
                 seq: int = 2048, heads: int = 8, kv_heads: int = 2,
                 llama_shape: dict | None = None, batch_per_chip: int = 4,
                 interpret: bool = False) -> dict:
    """Flash forward and both backward kernels against ``dense_attention``,
    then three Llama train steps with ``attn_impl="flash"``.  Unless
    ``interpret`` (CPU tests), each lowered program must hold a Mosaic
    custom call, so an interpreted or substituted kernel cannot pass."""
    out: dict = {}

    def sq_loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True).astype(jnp.float32) ** 2)

    with interpret_mode(interpret):
        for d in head_dims:
            ks = jax.random.split(jax.random.key(d), 3)
            q = jax.random.normal(ks[0], (batch, seq, heads, d), jnp.bfloat16)
            k = jax.random.normal(ks[1], (batch, seq, kv_heads, d),
                                  jnp.bfloat16)
            v = jax.random.normal(ks[2], (batch, seq, kv_heads, d),
                                  jnp.bfloat16)
            f_flash = jax.jit(jax.value_and_grad(sq_loss(flash_attention),
                                                 argnums=(0, 1, 2)))
            f_dense = jax.jit(jax.value_and_grad(sq_loss(dense_attention),
                                                 argnums=(0, 1, 2)))
            if not interpret:
                assert "tpu_custom_call" in f_flash.lower(q, k, v).as_text()
            first_s, steady_ms, vals = _timed_steps(
                lambda: jax.block_until_ready(f_flash(q, k, v)), 4)
            lf, gf = jax.device_get(vals[-1])
            ld, gd = jax.device_get(f_dense(q, k, v))
            # bf16 storage, f32 accumulation in the kernels.
            rel = abs(lf - ld) / max(abs(ld), 1e-9)
            assert rel < 2e-2, (d, rel)
            errs = {}
            for name, a, b in zip(("dq", "dk", "dv"), gf, gd):
                a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
                errs[name] = float(np.abs(a - b).max() / (np.abs(b).max()
                                                          or 1.0))
                assert errs[name] < 5e-2, (d, name, errs[name])
            out[f"flash_d{d}"] = {"first_call_s": first_s,
                                  "fwd_bwd_ms": steady_ms,
                                  "loss_rel": rel, **errs}

        n = hvd.size()
        cfg = llama.llama_tiny(attn_impl="flash", remat=False,
                               **(llama_shape or LLAMA_TRAIN))
        tx = hvd.DistributedOptimizer(optax.adamw(1e-4))
        params = hvd.broadcast_parameters(
            jax.jit(llama.init_params, static_argnums=0)(
                cfg, jax.random.key(0)), root_rank=0)
        opt_state = jax.jit(tx.init)(params)
        tokens = jax.device_put(
            jax.random.randint(
                jax.random.key(11), (batch_per_chip * n, cfg.max_seq_len),
                0, cfg.vocab_size, jnp.int32), hvd.rank_sharding())
        step = hvd.make_train_step(llama.make_loss_fn(cfg), tx)
        if not interpret:
            text = step.lower(params, opt_state, (tokens, tokens)).as_text()
            assert "tpu_custom_call" in text
        state = [params, opt_state]

        def one() -> float:
            r = step(state[0], state[1], (tokens, tokens))
            state[:] = r.params, r.opt_state
            return float(r.loss)

        first_s, steady_ms, losses = _timed_steps(one, 3)
    assert all(math.isfinite(x) for x in losses), losses
    _assert_one_compile(step)
    # Random weights know nothing: the first loss is the uniform guess's.
    assert abs(losses[0] - math.log(cfg.vocab_size)) < 1.0, losses
    out["llama_train"] = {"first_step_s": first_s,
                          "steady_step_ms": steady_ms, "losses": losses}
    return out


# ── phase 3: the server ────────────────────────────────────────────────────

#: A Llama shape of 1.11 B parameters.
LLAMA_SERVE = dict(vocab_size=32768, dim=2048, n_layers=16, n_heads=16,
                   n_kv_heads=4, ffn_dim=8192, max_seq_len=2048)


def logit_tolerance(dtype, n_layers: int, peak: float) -> float:
    """How far two correct programs of different shape may disagree on a
    logit.  Each of the ``2·n_layers + 1`` residual additions and the head
    rounds to ``dtype`` once more in one program than in the other; the
    rounding errors are independent, so they add in quadrature.  Relative to
    the largest logit, with a factor 4 of room (a wrong program is off by
    about the largest logit itself).  Fixed here, before any run on the
    chip."""
    return 4.0 * float(jnp.finfo(dtype).eps) * math.sqrt(2 * n_layers + 2) \
        * peak


def _serve_requests(vocab: int, chunk: int, max_new: int):
    """A dozen requests of mixed length: four share a two-block prefix, four
    are longer than a chunk, and there are more of them than slots."""
    rng = np.random.RandomState(5)

    def toks(k):
        return [int(t) for t in rng.randint(1, vocab, size=k)]

    prefix = toks(2 * chunk)
    shared = [prefix + toks(k) for k in (chunk // 8 + 1, chunk // 4 + 1,
                                         chunk // 2 + 1,
                                         chunk + chunk // 4 + 1)]
    alone = [toks(k) for k in (chunk // 16 + 1, chunk // 4, chunk - 1, chunk,
                               chunk + 1, 2 * chunk + chunk // 2,
                               3 * chunk + 1, chunk // 2)]
    reqs = [Request(prompt=p, max_new_tokens=int(rng.randint(2, max_new + 1)))
            for p in shared + alone]
    return reqs, prefix


def _serve_and_probe(eng, reqs, probes, http: int) -> tuple[list, dict]:
    """Drive one engine: the batch through ``run()``, ``http`` of the requests
    again through the router's front door, then each probe alone.  Returns
    the engine's logits after each probe's last token, and smoke timings."""
    t0 = time.perf_counter()
    results = eng.run(reqs)
    times = {"first_run_s": time.perf_counter() - t0}    # compiles included
    for req, res in zip(reqs, results):
        assert res.status == "OK", res
        assert len(res) == req.max_new_tokens, (len(res), req.max_new_tokens)
    if http:
        router = RouterServer([LocalReplica(eng, "r0")]).start()
        try:
            for req in reqs[:http]:
                body = json.dumps({"prompt": req.prompt,
                                   "max_new_tokens": req.max_new_tokens})
                post = urllib.request.Request(
                    f"http://{router.host}:{router.port}/v1/generate",
                    data=body.encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(post, timeout=300) as r:
                    reply = json.loads(r.read())
                assert reply["status"] == "OK", reply
                assert len(reply["tokens"]) == req.max_new_tokens, reply
        finally:
            router.stop()        # joins the replica's pump: the engine is ours
    logits = []
    t0 = time.perf_counter()
    for req in probes:
        # Alone in the engine, a request's last tick is the engine's last:
        # last_logits[slot] then follows the request's final token.
        rid = eng.submit(req)
        while eng.pending():
            eng.step()
        res = eng.results[rid]
        assert res.status == "OK" and len(res) == req.max_new_tokens, res
        slot = [e.slot for e in eng.events if e.request_id == rid
                and e.kind in ("admit", "hit")][-1]
        logits.append((list(req.prompt) + list(res),
                       np.asarray(eng.last_logits[slot], np.float32)))
    times["warm_request_ms"] = (time.perf_counter() - t0) / len(probes) * 1e3
    times["scratch"] = _pool_held_once(eng)
    # Counts only grow, so once at the end covers run(), HTTP, the probes and
    # the AOT compiles: admission, recycling and prefix hits never retraced a
    # pinned program, and lowering one mints no entry ("chunk": one signature
    # a width of the chunk program, all compiled by the constructor).
    sizes = eng.compile_cache_sizes()
    assert sizes == {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}, sizes
    return logits, times


def _pool_held_once(eng) -> dict:
    """AOT-compile the tick and the chunk at the engine's own signature and
    require each program's scratch (per device) to stay under half of the KV
    pool's share of a device: the pool rides the layer scan as a carry that
    aliases the donated input, and a second copy of it would be the whole
    pool again."""
    pool = (eng.pcache.k.nbytes + eng.pcache.v.nbytes) // eng.tp_size
    out = {"pool_bytes": pool}
    progs = eng.pinned_programs()
    for name in ("tick", "chunk"):
        fn, *avals = progs[name]
        temp = fn.lower(*avals).compile().memory_analysis().temp_size_in_bytes
        out[f"{name}_temp_bytes"] = temp
        assert temp < pool / 2, (name, temp, pool)
    _say(f"  scratch at tp={eng.tp_size}: {out}")
    return out


def phase_server(*, cfg=None, n_slots: int = 8, max_len: int = 2048,
                 chunk: int = 256, max_new: int = 24, http: int = 4,
                 tp_size: int | None = None,
                 n_blocks: int | None = None) -> dict:
    """``ServeEngine`` with the prefix cache on: every request ``OK`` with the
    asked number of tokens, one compiled signature per pinned program, the
    KV pool held once by the tick and the chunk, and logits within
    :func:`logit_tolerance` of ``llama.forward`` on the same tokens.  With
    four devices or more, the same through ``tp_size=4``.  ``n_blocks`` is
    for toy sizes, where full backing is smaller than a layer's weights."""
    if cfg is None:
        cfg = llama.llama_tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                               attn_impl="dense", **LLAMA_SERVE)
    if tp_size is None:
        tp_size = 4 if jax.device_count() >= 4 else 1
    params = jax.jit(llama.init_params, static_argnums=0)(
        cfg, jax.random.key(0))
    reqs, prefix = _serve_requests(cfg.vocab_size, chunk, max_new)
    rng = np.random.RandomState(9)
    # Two probes of one length (one reference compile): the first continues
    # the cached prefix, the second shares nothing; both span several chunks.
    tail = [int(t) for t in rng.randint(1, cfg.vocab_size,
                                        size=chunk + chunk // 2 + 1)]
    probes = [Request(prompt=prefix + tail, max_new_tokens=2),
              Request(prompt=[int(t) for t in rng.randint(
                  1, cfg.vocab_size, size=len(prefix) + len(tail))],
                  max_new_tokens=2)]

    def engine(tp: int) -> ServeEngine:
        return ServeEngine(
            params, cfg, n_slots=n_slots, max_len=max_len, chunk=chunk,
            prefix_cache=True, tp_size=tp, n_blocks=n_blocks,
            metrics=metrics_mod.MetricsRegistry(event_log=None))

    out: dict = {}
    eng = engine(1)
    got1, out["tp1"] = _serve_and_probe(eng, reqs, probes, http)
    assert eng.prefix_counters["hits"] > 0, eng.prefix_counters

    last = jax.jit(lambda p, t: llama.forward(p, t, cfg)[:, -1])
    ref = np.asarray(last(params, jnp.asarray([t for t, _ in got1])),
                     np.float32)
    assert np.isfinite(ref).all()
    tol = logit_tolerance(cfg.dtype, cfg.n_layers, float(np.abs(ref).max()))
    out["logit_tol"] = tol
    out["tp1_logit_err"] = float(max(
        np.abs(got - want).max() for (_, got), want in zip(got1, ref)))
    assert out["tp1_logit_err"] <= tol, out

    if tp_size > 1:
        eng_tp = engine(tp_size)
        assert eng_tp.tp_size == tp_size
        got_tp, out[f"tp{tp_size}"] = _serve_and_probe(eng_tp, reqs, probes,
                                                       http)
        # The probes' own tokens may differ between the engines at a bf16
        # near-tie, so compare logits only where the tokens agree and
        # against the reference otherwise.
        ref_tp = np.asarray(last(params, jnp.asarray([t for t, _ in got_tp])),
                            np.float32)
        errs = [np.abs(got - want).max()
                for (_, got), want in zip(got_tp, ref_tp)]
        errs += [np.abs(a - b).max()
                 for (ta, a), (tb, b) in zip(got_tp, got1) if ta == tb]
        out[f"tp{tp_size}_logit_err"] = float(max(errs))
        assert out[f"tp{tp_size}_logit_err"] <= tol, out
    return out


# ── phase 4: the eager namesake ────────────────────────────────────────────


def phase_eager() -> dict:
    """``hvd.allreduce`` and one ``allreduce_async``/``synchronize`` pair."""
    n = hvd.size()
    want = np.arange(4.0) + (n - 1) / 2

    def values():
        return hvd.per_rank(lambda r: jnp.arange(4.0) + r)

    t0 = time.perf_counter()
    got = np.asarray(hvd.allreduce(values(), average=True))
    first_s = time.perf_counter() - t0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    t0 = time.perf_counter()
    handle = hvd.allreduce_async(values(), average=True, name="smoke.async")
    got = np.asarray(hvd.synchronize(handle))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    return {"first_call_s": first_s,
            "second_call_ms": (time.perf_counter() - t0) * 1e3}


# ── entry point ────────────────────────────────────────────────────────────


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax found platform="
              f"{dev.platform!r} ({dev.device_kind!r}, "
              f"{jax.device_count()} device(s)); not running on it",
              file=sys.stderr)
        return 1
    # "on" raises where "auto" would skip the controller (one process) or
    # swallow a failed g++ build: libhvdtpu.so is built here, by first use.
    os.environ["HOROVOD_TPU_NATIVE_CONTROLLER"] = "on"

    cache = compile_cache_dir(HERE)
    warm = os.path.isdir(cache) and any(os.scandir(cache))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    _say(f"platform={dev.platform} device_kind={dev.device_kind!r} "
         f"count={jax.device_count()} jax={jax.__version__} "
         f"libtpu={importlib.metadata.version('libtpu')}")
    _say(f"compile cache: {cache} ({'warm' if warm else 'cold'})")
    assert lookup_peak_flops(dev.device_kind) is not None, (
        f"device_kind {dev.device_kind!r} is not in "
        f"device_telemetry.PEAK_FLOPS_TABLE")

    hvd.init()
    assert hvd.size() == jax.device_count(), (hvd.size(), jax.device_count())
    phases = [phase_trainer, phase_kernel, phase_server, phase_eager]
    if hvd.size() > 1:
        phases.insert(1, phase_dp_equivalence)
    for phase in phases:
        t0 = time.monotonic()
        result = phase()
        _say(f"{phase.__name__} ok in {time.monotonic() - t0:.1f}s "
             f"(smoke timings, not benchmark results): "
             f"{json.dumps(result, default=float)}")
    hvd.shutdown()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
