"""Scaling-efficiency benchmark — the reference's headline metric.

The reference's published claim is ~90% scaling efficiency for Inception V3
and ResNet-101 on 512 GPUs (/root/reference/README.md:51-57,
/root/reference/docs/benchmarks.md:1-7): per-chip throughput at n workers
divided by per-chip throughput at 1.  This harness measures the same ratio
over growing sub-meshes of the available devices: for each n in
{1, 2, 4, ..., N} it re-initializes the framework on an n-device world,
times the synthetic training step (DistributedOptimizer = fused-psum
gradient averaging), and prints the efficiency table.

On a TPU pod slice the collectives ride ICI and the ratio is the real
scaling number; under the CPU simulation mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu) the
absolute numbers are meaningless but the harness exercises the identical
program path end to end.

Usage:
    python examples/scaling_benchmark.py [--model resnet50|inception|vit|mlp] [--bs 32]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd


def _build(model_name: str, on_tpu: bool, image_size: int):
    if model_name == "mlp":
        from horovod_tpu.models.mnist import MnistMLP as MLP

        model = MLP()
        x = jnp.ones((1, 28 * 28), jnp.float32)
        classes = 10
    elif model_name == "vit":
        from horovod_tpu.models.vit import ViT_B16

        # Dense attention: 224px/patch16 = 196 tokens, far below the
        # flash kernel's ~2k-token crossover.
        model = ViT_B16(dtype=jnp.bfloat16 if on_tpu else jnp.float32)
        x = jnp.ones((1, image_size, image_size, 3), jnp.float32)
        classes = 1000
    elif model_name == "inception":
        from horovod_tpu.models.inception import InceptionV3

        model = InceptionV3(dtype=jnp.bfloat16 if on_tpu else jnp.float32)
        x = jnp.ones((1, image_size, image_size, 3), jnp.float32)
        classes = 1000
    else:
        from horovod_tpu.models.resnet import ResNet50

        model = ResNet50(dtype=jnp.bfloat16 if on_tpu else jnp.float32)
        x = jnp.ones((1, image_size, image_size, 3), jnp.float32)
        classes = 1000
    variables = model.init(jax.random.key(0), x)
    return model, variables, x.shape[1:], classes


def _throughput(model, variables, in_shape, classes, batch_per_chip,
                iters, batches) -> float:
    """Images/sec/chip of the full distributed step on the current world."""
    n = hvd.size()
    global_bs = batch_per_chip * n
    images = jnp.ones((global_bs, *in_shape), jnp.float32)
    labels = jnp.zeros((global_bs,), jnp.int32)

    params = variables["params"]
    extra = {k: v for k, v in variables.items() if k != "params"}

    def loss_fn(params, batch):
        x, y = batch
        out = model.apply(
            {"params": params, **extra}, x,
            **({"train": True, "mutable": ["batch_stats"]} if "batch_stats" in extra else {}),
        )
        logits = out[0] if isinstance(out, tuple) else out
        return optax.softmax_cross_entropy(
            logits, jax.nn.one_hot(y, classes)
        ).mean()

    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    opt_state = tx.init(params)
    step = hvd.make_train_step(loss_fn, tx, donate=False)
    out = step(params, opt_state, (images, labels))
    jax.block_until_ready(out.loss)
    state = [out.params, out.opt_state]

    rates = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(batches):
            r = step(state[0], state[1], (images, labels))
            state[0], state[1] = r.params, r.opt_state
        jax.block_until_ready(r.loss)
        rates.append(global_bs * batches / (time.perf_counter() - t0))
    return max(rates) / n


def _contention_baseline(devices, n, batch_per_chip, iters, batches) -> float:
    """Per-chip throughput of a communication-FREE SPMD workload on the
    same ``n`` devices — the contention curve C(n).

    On the CPU simulation the n virtual devices share physical cores, so
    per-chip throughput falls with n for reasons that have nothing to do
    with collectives; dividing the model curve by C(n) isolates what the
    gradient collectives actually cost (``collective_efficiency`` in the
    output).  On a real pod slice each chip is real hardware, C(n) ≈ C(1),
    and the raw and normalized efficiencies coincide — so the same
    command is the rehearsed recipe for the v5p run."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(devices[:n]), ("w",))
    d = 192
    x = jnp.ones((n * batch_per_chip, d, d), jnp.float32)

    def local(chunk):  # shard-local batched matmul chain, zero collectives
        for _ in range(6):
            chunk = jnp.tanh(chunk @ chunk)
        return chunk

    f = jax.jit(shard_map(local, mesh=mesh, in_specs=P("w"),
                          out_specs=P("w")))
    r = f(x)
    jax.block_until_ready(r)
    rates = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(batches):
            r = f(r)
        jax.block_until_ready(r)
        rates.append(n * batch_per_chip * batches
                     / (time.perf_counter() - t0))
    return max(rates) / n


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "inception", "vit", "mlp"])
    p.add_argument("--bs", type=int, default=None, help="batch per chip")
    p.add_argument("--img", type=int, default=None)
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--batches", type=int, default=5)
    p.add_argument("--no-contention-baseline", action="store_true",
                   help="skip the communication-free C(n) normalization "
                        "arm (it is what makes CPU-sim numbers "
                        "interpretable; on a real pod it is ~free)")
    args = p.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    bs = args.bs or (32 if on_tpu else 2)
    if args.model == "inception":
        # Inception V3's stride-2 VALID reductions need H,W >= 75.
        img = args.img or (299 if on_tpu else 128)
    else:
        img = args.img or (224 if on_tpu else 32)

    devices = jax.devices()
    sizes = [n for n in (1, 2, 4, 8, 16, 32, 64, 128) if n <= len(devices)]
    model, variables, in_shape, classes = _build(args.model, on_tpu, img)

    results = {}
    contention = {}
    for n in sizes:
        hvd.shutdown()
        hvd.init(devices=devices[:n])
        results[n] = _throughput(
            model, variables, in_shape, classes, bs, args.iters, args.batches
        )
        line = f"n={n:4d}  {results[n]:10.2f} img/s/chip"
        if not args.no_contention_baseline:
            contention[n] = _contention_baseline(
                devices, n, bs, args.iters, args.batches
            )
            line += f"   C(n)={contention[n]:12.1f}"
        print(line, flush=True)

    base = results[sizes[0]]
    table = {}
    for n, r in results.items():
        row = {"img_per_sec_per_chip": round(r, 2),
               "scaling_efficiency": round(r / base, 4)}
        if contention:
            c_rel = contention[n] / contention[sizes[0]]
            row["contention_factor"] = round(c_rel, 4)
            row["collective_efficiency"] = round((r / base) / c_rel, 4)
        table[n] = row
    print(json.dumps({"model": args.model, "batch_per_chip": bs,
                      "scaling": table}))


if __name__ == "__main__":
    main()
