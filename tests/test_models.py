"""Model zoo smoke: the image models' forward shapes, parameter counts and a
training step.  Llama's correctness: ``test_models_llama.py``."""

import jax
import jax.numpy as jnp
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import (
    MnistConvNet,
    MnistMLP,
    ResNet50,
    VGG16,
)


def test_mnist_models_forward():
    x = jnp.ones((4, 28, 28, 1))
    for model in (MnistConvNet(), MnistMLP()):
        params = model.init(jax.random.PRNGKey(0), x, train=False)
        out = model.apply(params, x, train=False)
        assert out.shape == (4, 10)
        assert out.dtype == jnp.float32


def test_resnet50_forward_and_param_count():
    model = ResNet50(num_classes=1000)
    x = jnp.ones((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 1000)
    n_params = sum(p.size for p in jax.tree.leaves(variables["params"]))
    # ResNet-50 has ~25.5M params; BN stats excluded
    assert 24e6 < n_params < 27e6, n_params


def test_resnet_train_step_updates_batchstats():
    model = ResNet50(num_classes=10, width=16)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    out, mutated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    assert out.shape == (2, 10)
    assert "batch_stats" in mutated


def test_inception_v3_forward_param_count():
    """Inception V3 at canonical 299×299: ~23.8M params (torchvision's
    no-aux count ≈ 23.83M) and correct logits shape; aux head adds a second
    output in train mode."""
    from horovod_tpu.models import InceptionV3

    model = InceptionV3(num_classes=1000)
    x = jnp.ones((1, 299, 299, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (1, 1000)
    n_params = sum(p.size for p in jax.tree.leaves(variables["params"]))
    assert 22e6 < n_params < 25e6, n_params

    aux_model = InceptionV3(num_classes=10, aux_logits=True)
    v2 = aux_model.init(jax.random.PRNGKey(0), x, train=True)
    (logits, aux), _ = aux_model.apply(
        v2, x, train=True, mutable=["batch_stats"]
    )
    assert logits.shape == (1, 10) and aux.shape == (1, 10)


def test_vgg16_forward_param_count():
    model = VGG16(num_classes=100)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 100)


def test_vit_b16_forward_param_count():
    from horovod_tpu.models import ViT_B16

    model = ViT_B16(num_classes=1000)
    x = jnp.ones((2, 224, 224, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 1000)
    n_params = sum(p.size for p in jax.tree.leaves(variables["params"]))
    # ViT-B/16 is ~86M (85.8M + head; no CLS token here, mean-pool head)
    assert 84e6 < n_params < 89e6, n_params


def test_vit_trains_and_flash_matches_dense():
    """A tiny ViT trains (loss decreases), and the flash-attention path
    agrees with dense on the same params (bidirectional causal=False use
    of the pallas kernel's interpret-mode fallback on CPU)."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.vit import ViT

    kw = dict(patch=4, dim=32, depth=2, n_heads=2, num_classes=10)
    model = ViT(**kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 16, 3))
    y = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 10)
    variables = model.init(jax.random.PRNGKey(2), x, train=False)

    # Flash-vs-dense agreement FIRST: the train step donates its input
    # buffers, so `variables` is consumed by the loop below.
    flash = ViT(attn_impl="flash", **kw)
    dense_out = model.apply(variables, x, train=False)
    flash_out = flash.apply(variables, x, train=False)
    assert jnp.allclose(dense_out, flash_out, atol=2e-2), (
        float(jnp.abs(dense_out - flash_out).max())
    )

    def loss_fn(params, batch):
        bx, by = batch
        logits = model.apply({"params": params}, bx, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, by
        ).mean()

    tx = hvd.DistributedOptimizer(optax.adam(1e-2))
    params = variables["params"]
    opt_state = tx.init(params)
    step = hvd.make_train_step(loss_fn, tx)
    losses = []
    for _ in range(5):
        # Flat rank-major batch: 8 rows over the 8-device mesh (1/chip).
        out = step(params, opt_state, (x, y))
        params, opt_state = out.params, out.opt_state
        losses.append(float(out.loss))
    assert losses[-1] < losses[0], losses


def test_vit_unknown_attn_impl_raises():
    from horovod_tpu.models.vit import ViT

    m = ViT(patch=4, dim=32, depth=1, n_heads=2, num_classes=10,
            attn_impl="Flash")          # typo'd case must not run dense
    x = jnp.ones((1, 16, 16, 3))
    with pytest.raises(ValueError, match="unknown attn_impl"):
        m.init(jax.random.PRNGKey(0), x, train=False)
