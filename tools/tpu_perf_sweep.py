"""Perf sweep: batch sizes, flash block sizes, remat — readback-fenced
timings, printed as a table.  Needs a TPU:

    python tools/tpu_perf_sweep.py

Reuses bench.py's measurement stack (``_aot_compile`` warmup+fence,
``_readback`` value fencing, ``_mfu`` device-kind peak lookup) so sweep
numbers are comparable to the bench's and any fence fix lands in one
place.  Prints one `RESULT {json}` line per config, so the findings
survive as parseable logs even if the run is cut mid-sweep.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from bench import _aot_compile, _mfu, _readback

t0 = time.monotonic()


def note(msg):
    print(f"[+{time.monotonic() - t0:.1f}s] {msg}", flush=True)


note(f"backend={jax.default_backend()} devices={jax.devices()}")
if jax.devices()[0].platform != "tpu":
    sys.exit(f"needs a TPU; jax found {jax.devices()[0].platform!r}")

# The bench's compile cache, so the sweep warms the bench and vice versa.
from horovod_tpu.utils.env import compile_cache_dir

compile_cache_dir(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import horovod_tpu as hvd

hvd.init()


def time_steps(step, state0, batch, iters=None, group=None):
    """steps/sec over donation-chained groups, readback-fenced.

    Returns the BEST group (least interference) — a tuning signal, unlike
    bench.py's mean-of-groups reporting number.
    """
    iters = iters if iters is not None else 3
    group = group if group is not None else 12
    state = state0
    rates = []
    for _ in range(iters):
        t = time.perf_counter()
        for _ in range(group):
            r = step(state["p"], state["o"], batch)
            state = {"p": r.params, "o": r.opt_state, "loss": r.loss}
        _readback(state["loss"])
        rates.append(group / (time.perf_counter() - t))
    return max(rates)


def result(name, **kv):
    print("RESULT " + json.dumps({"config": name, **kv}), flush=True)


# ── ResNet-101 batch sweep ────────────────────────────────────────────────
def resnet_sweep():
    import horovod_tpu.models.resnet as resnet_mod

    # (bs, donate): the bs64 donate-off arm is the donated-buffers rung of
    # the tuning ladder — same program minus donation, so the delta is
    # pure allocation/HBM-pressure cost.
    configs = ((64, True), (64, False), (128, True), (256, True))
    img = 224
    for bs, donate in configs:
        note(f"resnet101 bs{bs} donate={donate}: building")
        model = resnet_mod.ResNet101(dtype=jnp.bfloat16)
        kimg, klab = jax.random.split(jax.random.key(7))
        images = jax.random.normal(kimg, (bs, img, img, 3), jnp.float32)
        labels = jax.random.randint(klab, (bs,), 0, 1000, jnp.int32)
        variables = jax.jit(model.init, static_argnames="train")(
            jax.random.key(0), images[:1], train=False)
        params, batch_stats = variables["params"], variables["batch_stats"]

        def loss_fn(params, batch):
            x, y = batch
            logits, _ = model.apply(
                {"params": params, "batch_stats": batch_stats},
                x, train=True, mutable=["batch_stats"])
            return optax.softmax_cross_entropy(
                logits, jax.nn.one_hot(y, logits.shape[-1])).mean()

        tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
        opt_state = jax.jit(tx.init)(params)
        tag = f"resnet101_bs{bs}" + ("" if donate else "_nodonate")
        try:
            step, flops, out = _aot_compile(
                hvd.make_train_step(loss_fn, tx, donate=donate),
                params, opt_state, (images, labels))
            note(f"{tag}: warm, timing")
            sps = time_steps(step, {"p": out.params, "o": out.opt_state},
                             (images, labels))
            mfu = _mfu(flops, sps)
            result(tag, img_per_sec=round(sps * bs, 1),
                   mfu=round(mfu, 4) if mfu is not None else None,
                   step_ms=round(1e3 / sps, 2))
        except Exception as exc:
            result(tag, error=f"{type(exc).__name__}: {exc}")


# ── flash-attention block-size sweep (fwd+bwd, llama-shaped) ─────────────
def flash_sweep():
    from horovod_tpu.parallel.flash_attention import flash_attention

    B, L, H, KVH, D = 4, 2048, 16, 4, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, L, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, L, KVH, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, L, KVH, D), jnp.bfloat16)
    # Analytic attention FLOPs (fwd+bwd ≈ 3.5x fwd): fwd = 2·2·B·H·L²·D
    # (QK^T + PV); causal halves it.  cost_analysis can't see inside the
    # pallas custom call, hence analytic.
    flops = 3.5 * 2 * 2 * B * H * L * L * D / 2

    # Pre-warm the fence reducer OUTSIDE any timed region: its first
    # compile would otherwise land inside the first config's measurement
    # and skew the block-size comparison.
    reps = 20
    reduce_fence = jax.jit(lambda xs: jnp.stack(xs).sum())
    _readback(reduce_fence([jnp.float32(0)] * reps))

    for bq, bk in ((256, 256), (512, 512), (1024, 512), (512, 1024),
                   (1024, 1024)):
        note(f"flash bq={bq} bk={bk}: compiling")

        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
            ).astype(jnp.float32) ** 2)

        fn = jax.jit(jax.value_and_grad(loss))
        try:
            _readback(fn(q, k, v)[0])
            t = time.perf_counter()
            accs = [fn(q, k, v)[0] for _ in range(reps)]
            _readback(reduce_fence(accs))
            ms = (time.perf_counter() - t) / reps * 1e3
            result(f"flash_bq{bq}_bk{bk}", ms=round(ms, 2),
                   tflops=round(flops / (ms / 1e3) / 1e12, 1))
        except Exception as exc:
            result(f"flash_bq{bq}_bk{bk}", error=f"{type(exc).__name__}: {exc}")


# ── llama end-to-end: remat and attention-impl choices ───────────────────
def llama_sweep():
    from horovod_tpu.models import llama

    seq = 2048
    base_shape = dict(vocab_size=32768, dim=1024, n_layers=8, n_heads=16,
                      n_kv_heads=4, ffn_dim=4096)
    # ~570M params: MFU rises with model size (bigger matmuls occupy the
    # MXU better than the 189M bench model's); remat+donation make it fit.
    big_shape = dict(vocab_size=32768, dim=1536, n_layers=14, n_heads=16,
                     n_kv_heads=4, ffn_dim=6144)
    # 1.11B: the single-chip capacity ceiling — fits ONLY with the full
    # memory ladder (remat + fused loss + donation + SGD-momentum's 1x
    # state; fp32 params 4.4G + momentum 4.4G of the 15.75G HBM).
    onex_shape = dict(vocab_size=32768, dim=2048, n_layers=16, n_heads=16,
                      n_kv_heads=4, ffn_dim=8192)
    for name, kw, shape in (
        ("flash", dict(attn_impl="flash", remat=False), base_shape),
        ("flash_remat", dict(attn_impl="flash", remat=True), base_shape),
        ("dense", dict(attn_impl="dense", remat=False), base_shape),
        ("flash_big", dict(attn_impl="flash", remat=True), big_shape),
        ("flash_1b", dict(attn_impl="flash", remat=True,
                          fused_loss_chunk=2048), onex_shape),
    ):
        note(f"llama {name}: building")
        cfg = llama.llama_tiny(max_seq_len=seq, **shape, **kw)
        loss = llama.make_loss_fn(cfg)
        # AdamW's 2x fp32 state does not fit at 1B on one chip; SGD-momentum
        # (the reference benchmarks' optimizer) is the 1B rung's point.
        opt = optax.sgd(1e-3, momentum=0.9) if name == "flash_1b" \
            else optax.adamw(1e-4)
        tx = hvd.DistributedOptimizer(opt)
        params = llama.init_params(cfg, jax.random.key(0))
        opt_state = jax.jit(tx.init)(params)
        lbs = 2 if name == "flash_1b" else 4
        tokens = jax.random.randint(
            jax.random.key(11), (lbs, seq), 0, cfg.vocab_size, jnp.int32)
        batch = (tokens, tokens)
        try:
            step, _flops, out = _aot_compile(
                hvd.make_train_step(loss, tx, donate=True),
                params, opt_state, batch)
            note(f"llama {name}: warm, timing")
            sps = time_steps(step, {"p": out.params, "o": out.opt_state},
                             batch)
            n_par = llama.num_params(cfg)
            # 6·N·D against the device-kind peak (same convention as
            # bench.py's llama_mfu_6nd).
            mfu_6nd = _mfu(6.0 * n_par * lbs * seq, sps)
            result(f"llama_{name}",
                   tok_per_sec=round(sps * lbs * seq, 1),
                   mfu_6nd=round(mfu_6nd, 4) if mfu_6nd is not None else None,
                   step_ms=round(1e3 / sps, 2))
        except Exception as exc:
            result(f"llama_{name}", error=f"{type(exc).__name__}: {exc}")


# ── ViT-B/16 batch sweep (transformer-vision MFU ladder) ─────────────────
def vit_sweep():
    from horovod_tpu.models.vit import ViT_B16

    for bs in (64, 128):
        note(f"vit_b16 bs{bs}: building")
        # Dense attention: 196 tokens is less than one block of the flash
        # kernel (bench.py _bench_vit).
        model = ViT_B16(dtype=jnp.bfloat16)
        img = 224
        kimg, klab = jax.random.split(jax.random.key(29))
        images = jax.random.normal(kimg, (bs, img, img, 3), jnp.float32)
        labels = jax.random.randint(klab, (bs,), 0, model.num_classes,
                                    jnp.int32)
        variables = jax.jit(model.init, static_argnames="train")(
            jax.random.key(0), images[:1], train=False)

        def loss_fn(params, batch):
            x, y = batch
            logits = model.apply({"params": params}, x, train=True)
            return optax.softmax_cross_entropy(
                logits, jax.nn.one_hot(y, logits.shape[-1])).mean()

        tx = hvd.DistributedOptimizer(optax.adamw(1e-3))
        params = variables["params"]
        opt_state = jax.jit(tx.init)(params)
        try:
            step, flops, out = _aot_compile(
                hvd.make_train_step(loss_fn, tx, donate=True),
                params, opt_state, (images, labels))
            note(f"vit_b16 bs{bs}: warm, timing")
            sps = time_steps(step, {"p": out.params, "o": out.opt_state},
                             (images, labels))
            mfu = _mfu(flops, sps)
            result(f"vit_b16_bs{bs}", img_per_sec=round(sps * bs, 1),
                   mfu=round(mfu, 4) if mfu is not None else None,
                   step_ms=round(1e3 / sps, 2))
        except Exception as exc:
            result(f"vit_b16_bs{bs}", error=f"{type(exc).__name__}: {exc}")


# ── Serving sweep: speculative decode + continuous batching ──────────────
def serving_sweep():
    """Single-chip serving rungs: plain generate vs speculative (self
    draft = acceptance upper bound; tiny draft = the realistic shape) and
    the slot-pool batcher.  All greedy, so every variant's tokens are
    bit-identical — only speed differs.  Plain generate is one fully
    jitted program (zero host round-trips after launch), while the
    speculative loop and the batcher pay ≥2 host↔device round-trips per
    round by design."""
    import time as _t

    import numpy as np

    from horovod_tpu.models import llama
    from horovod_tpu.serving import (ContinuousBatcher, Request,
                                     speculative_generate)

    shape = dict(vocab_size=32768, dim=1024, n_layers=8, n_heads=16,
                 n_kv_heads=4, ffn_dim=4096)     # the 189M bench model
    draft_shape = dict(vocab_size=32768, dim=256, n_layers=2,
                       n_heads=8, n_kv_heads=2, ffn_dim=1024)
    b, plen, n_new, max_len = 8, 128, 256, 512
    cfg = llama.llama_tiny(max_seq_len=max_len, attn_impl="dense", **shape)
    dcfg = llama.llama_tiny(max_seq_len=max_len, attn_impl="dense",
                            **draft_shape)
    params = llama.init_params(cfg, jax.random.key(0))
    dparams = llama.init_params(dcfg, jax.random.key(1))
    prompt = jax.random.randint(jax.random.key(2), (b, plen), 0,
                                cfg.vocab_size, jnp.int32)

    def timed(label, fn):
        try:
            jax.block_until_ready(fn())      # compile + warm
            t0 = _t.monotonic()
            jax.block_until_ready(fn())
            dt = _t.monotonic() - t0
            result(label, tok_per_sec=round(b * n_new / dt, 1),
                   ms_per_token=round(1e3 * dt / n_new, 3))
        except Exception as exc:
            result(label, error=f"{type(exc).__name__}: {exc}")

    gen = jax.jit(lambda p, t: llama.generate(
        p, t, cfg, max_new_tokens=n_new, max_len=max_len))
    timed("serve_generate", lambda: np.asarray(gen(params, prompt)))
    timed("serve_spec_selfdraft", lambda: np.asarray(speculative_generate(
        params, cfg, params, cfg, prompt, max_new_tokens=n_new,
        draft_k=4, max_len=max_len + 8)))
    timed("serve_spec_tinydraft", lambda: np.asarray(speculative_generate(
        params, cfg, dparams, dcfg, prompt, max_new_tokens=n_new,
        draft_k=4, max_len=max_len + 8)))

    # ONE batcher instance: its jitted closures are per-instance, so the
    # warm run must hit the same object the timed run uses.
    srv = ContinuousBatcher(params, cfg, n_slots=b, max_len=max_len,
                            admit_width=plen)

    def batcher_run(n_requests, toks):
        reqs = [Request(prompt=list(range(1, plen + 1)),
                        max_new_tokens=toks) for _ in range(n_requests)]
        return srv.run(reqs)

    try:
        batcher_run(1, 2)                    # compile _prefill_one/_tick
        t0 = _t.monotonic()
        res = batcher_run(b + b // 2, n_new)
        dt = _t.monotonic() - t0
        total = sum(len(r) for r in res)
        result("serve_batcher", tok_per_sec=round(total / dt, 1),
               ms_per_token=round(1e3 * dt / total, 3),
               requests=len(res), total_tokens=total)
    except Exception as exc:
        result("serve_batcher", error=f"{type(exc).__name__}: {exc}")


if __name__ == "__main__":
    which = os.environ.get("SWEEP", "resnet,flash,llama,vit,serving").split(",")
    if "resnet" in which:
        resnet_sweep()
    if "flash" in which:
        flash_sweep()
    if "llama" in which:
        llama_sweep()
    if "vit" in which:
        vit_sweep()
    if "serving" in which:
        serving_sweep()
    note("sweep done")
