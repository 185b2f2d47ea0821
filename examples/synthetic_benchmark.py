"""ResNet-50 synthetic throughput benchmark.

Equivalent of reference examples/pytorch_synthetic_benchmark.py:96-110:
ResNet-50 on random data, img/sec per chip as mean ± 1.96σ over
``--num-iters`` groups of ``--num-batches-per-iter`` batches, plus total
img/sec and the implied scaling efficiency.

Run: python examples/synthetic_benchmark.py            (real chip)
     JAX_PLATFORMS=cpu python examples/synthetic_benchmark.py --smoke
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models.resnet import ResNet50


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-chip batch (reference default 32)")
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument(
        "--compression",
        choices=["none", "fp16", "bf16", "int8", "powersgd", "ef-topk"],
        default="none",
        help="gradient compression on the wire (docs/compression.md)",
    )
    p.add_argument("--adasum", action="store_true",
                   help="combine gradients with op=Adasum instead of Average")
    p.add_argument("--smoke", action="store_true")
    args = p.parse_args()
    if args.adasum and args.compression in ("int8", "powersgd", "ef-topk"):
        p.error("--adasum composes with none/fp16/bf16 compression only")
    if args.smoke:
        args.image_size, args.num_iters, args.num_batches_per_iter = 32, 2, 2

    hvd.init()
    n = hvd.size()
    on_tpu = jax.default_backend() == "tpu"
    model = ResNet50(dtype=jnp.bfloat16 if on_tpu else jnp.float32)

    global_bs = args.batch_size * n
    images = jnp.ones((global_bs, args.image_size, args.image_size, 3),
                      jnp.float32)
    labels = jnp.zeros((global_bs,), jnp.int32)

    variables = model.init(jax.random.key(0), images[:1], train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, batch):
        x, y = batch
        logits, _ = model.apply(
            {"params": params, "batch_stats": batch_stats},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    compression = {
        "none": hvd.Compression.none,
        "fp16": hvd.Compression.fp16,
        "bf16": hvd.Compression.bf16,
        "int8": hvd.Compression.int8,
        "powersgd": hvd.PowerSGDCompressor(rank=4),
        "ef-topk": hvd.ErrorFeedback(
            hvd.ops.compression.TopKCompressor(ratio=0.01)
        ),
    }[args.compression]
    tx = hvd.DistributedOptimizer(
        optax.sgd(0.01 * n, momentum=0.9),
        compression=compression,
        op=hvd.Adasum if args.adasum else hvd.Average,
    )
    # Broadcast both: state left on one device is another signature than
    # the mesh-resident state a step returns, and the step would compile a
    # second time inside the first timed iteration.
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt_state = hvd.broadcast_optimizer_state(tx.init(params), root_rank=0)
    step = hvd.make_train_step(loss_fn, tx)

    if hvd.rank() == 0:
        print(f"Model: ResNet50  Batch size/chip: {args.batch_size}  "
              f"Chips: {n}  Backend: {jax.default_backend()}  "
              f"Compression: {args.compression}"
              + ("  Op: Adasum" if args.adasum else ""))

    out = step(params, opt_state, (images, labels))  # compile + warmup
    params, opt_state = out.params, out.opt_state
    jax.block_until_ready(out.loss)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            out = step(params, opt_state, (images, labels))
            params, opt_state = out.params, out.opt_state
        jax.block_until_ready(out.loss)
        rate = global_bs * args.num_batches_per_iter / (
            time.perf_counter() - t0
        )
        img_secs.append(rate / n)
        if hvd.rank() == 0:
            print(f"Iter #{i}: {rate / n:.1f} img/sec per chip")

    mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
    if hvd.rank() == 0:
        print(f"Img/sec per chip: {mean:.1f} +-{conf:.1f}")
        print(f"Total img/sec on {n} chip(s): {mean * n:.1f} "
              f"+-{conf * n:.1f}")


if __name__ == "__main__":
    main()
