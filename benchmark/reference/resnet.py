"""The plain reference for the ``resnet_train`` family: ResNet v1.5
(bottleneck blocks, stride on the 3x3 convolution), batch normalisation on
the batch's own statistics, softmax cross-entropy, SGD with momentum — in
straightforward ``jax.numpy`` and float32 with every product at
``Precision.HIGHEST``.  It imports nothing of the program and makes its own
weights and batch from the seed (the family hands the same to the program).

Departures from He et al. 2015 / the torchvision model the source's
benchmark script runs, all shared with the program: the weights are drawn
from the seed with the last scale of each block small (between the
program's own zero-init and a trained value; with scales near one a fresh
random ResNet amplifies any rounding so far that bfloat16 and fp8 read
alike); statistics are those of each chip's own rows (``groups``), as
Horovod trains, so a batch over four chips is four groups.

``precision`` other than ``"float32"`` is the **control**: the same
arithmetic with every tensor that the program holds in its compute type —
the weights and inputs of every convolution and of the head, and the output
of every convolution, normalisation, block and of the pooling — rounded to a
lower precision (``"fp8"``: float8_e4m3fn after scaling to its range, and on
the way back the gradient of each rounded to float8_e5m2, as fp8 training
does), which a comparison that is worth anything has to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


def seed_arg(seed: int):
    """The seed as an argument of a jitted maker (not a constant in it), so
    that one compiled program serves every seed."""
    return np.uint32(int(seed) % 2**32)


def param_shapes(cfg: dict) -> dict:
    """``{path: shape}`` of every weight, in the program's naming (flax's):
    the family turns ``a/b/c`` into the nested tree the model takes."""
    w = int(cfg["width"])
    shapes = {"conv_init/kernel": (7, 7, 3, w),
              "bn_init/scale": (w,), "bn_init/bias": (w,)}
    c_in, k = w, 0
    for stage, count in enumerate(cfg["stage_sizes"]):
        f = w * 2 ** stage
        for j in range(count):
            b = f"BottleneckBlock_{k}"
            shapes[f"{b}/Conv_0/kernel"] = (1, 1, c_in, f)
            shapes[f"{b}/Conv_1/kernel"] = (3, 3, f, f)
            shapes[f"{b}/Conv_2/kernel"] = (1, 1, f, 4 * f)
            for i, c in enumerate((f, f, 4 * f)):
                shapes[f"{b}/BatchNorm_{i}/scale"] = (c,)
                shapes[f"{b}/BatchNorm_{i}/bias"] = (c,)
            if j == 0:
                shapes[f"{b}/downsample_conv/kernel"] = (1, 1, c_in, 4 * f)
                shapes[f"{b}/downsample_bn/scale"] = (4 * f,)
                shapes[f"{b}/downsample_bn/bias"] = (4 * f,)
            c_in, k = 4 * f, k + 1
    shapes["head/kernel"] = (c_in, int(cfg["num_classes"]))
    shapes["head/bias"] = (int(cfg["num_classes"]),)
    return shapes


def make_params(cfg: dict, seed) -> dict:
    """Float32 weights from the seed: kernels normal at ``sqrt(2 / fan_in)``,
    scales uniform in [0.5, 1.5] (the last of each block in [0.05, 0.15]),
    biases normal at 0.1.  Traceable: the
    family jits it so that the weights are made on the device in one call."""
    key = jax.random.key(seed)
    out = {}
    for i, (path, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if path.endswith("kernel"):
            fan_in = 1
            for d in shape[:-1]:
                fan_in *= d
            out[path] = jax.random.normal(k, shape, jnp.float32) * (
                2.0 / fan_in) ** 0.5
        elif path.endswith("BatchNorm_2/scale"):
            # the last scale of a block is small, as after zero-init and a
            # little training: each block is a small step off the identity,
            # so rounding is not amplified block after block
            out[path] = jax.random.uniform(k, shape, jnp.float32, 0.05, 0.15)
        elif path.endswith("scale"):
            out[path] = jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5)
        else:
            out[path] = 0.1 * jax.random.normal(k, shape, jnp.float32)
    return out


def make_batch(cfg: dict, seed, n: int):
    """``n`` images (float32, standard normal, every row different) and
    their labels, from the seed."""
    ki, kl = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2**20))
    s = int(cfg["image_size"])
    return (jax.random.normal(ki, (n, s, s, 3), jnp.float32),
            jax.random.randint(kl, (n,), 0, int(cfg["num_classes"]),
                               jnp.int32))


def _lower(x, precision: str):
    """Round ``x`` to the control's precision, gradient straight through."""
    if precision == "float32":
        return x
    if precision == "fp8":
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    elif precision == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + lax.stop_gradient(q - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _lower_grad(y, precision: str):
    """``y`` itself; on the way back its gradient is rounded as fp8 training
    rounds gradients (float8_e5m2 after scaling to its range)."""
    return y


def _lower_grad_fwd(y, precision):
    return y, None


def _lower_grad_bwd(precision, _, g):
    if precision != "fp8":
        return (g,)
    scale = 57344.0 / jnp.maximum(jnp.max(jnp.abs(g)), 1e-30)
    return ((g * scale).astype(jnp.float8_e5m2).astype(jnp.float32) / scale,)


_lower_grad.defvjp(_lower_grad_fwd, _lower_grad_bwd)


def _act(x, precision: str):
    """A tensor the program holds in its compute type: rounded in the
    control, forward and (its gradient) backward."""
    return _lower_grad(_lower(x, precision), precision)


def _conv(x, w, stride, padding, precision):
    return _act(lax.conv_general_dilated(
        _lower(x, precision), _lower(w, precision), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI), precision)


def _bn(x, p, name, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(x * x, axis=(0, 1, 2)) - mean * mean
    return ((x - mean) * lax.rsqrt(var + eps) * p[name + "/scale"]
            + p[name + "/bias"])


def _block(x, p, b, stride, has_down, eps, precision):
    y = _conv(x, p[b + "/Conv_0/kernel"], 1, "SAME", precision)
    y = _act(jax.nn.relu(_bn(y, p, b + "/BatchNorm_0", eps)), precision)
    y = _conv(y, p[b + "/Conv_1/kernel"], stride, "SAME", precision)
    y = _act(jax.nn.relu(_bn(y, p, b + "/BatchNorm_1", eps)), precision)
    y = _conv(y, p[b + "/Conv_2/kernel"], 1, "SAME", precision)
    y = _act(_bn(y, p, b + "/BatchNorm_2", eps), precision)
    if has_down:
        x = _conv(x, p[b + "/downsample_conv/kernel"], stride, "SAME",
                  precision)
        x = _act(_bn(x, p, b + "/downsample_bn", eps), precision)
    return _act(jax.nn.relu(y + x), precision)


def logits(cfg: dict, p: dict, images, precision: str = "float32"):
    eps = float(cfg["bn_epsilon"])
    x = _conv(images, p["conv_init/kernel"], 2, [(3, 3), (3, 3)], precision)
    x = _act(jax.nn.relu(_bn(x, p, "bn_init", eps)), precision)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    k = 0
    for stage, count in enumerate(cfg["stage_sizes"]):
        for j in range(count):
            b = f"BottleneckBlock_{k}"
            sub = {n: v for n, v in p.items() if n.startswith(b + "/")}
            # checkpointed per block so that a float32 batch fits beside
            # nothing else: values are unchanged, only recomputed
            x = jax.checkpoint(functools.partial(
                _block, b=b, stride=2 if stage > 0 and j == 0 else 1,
                has_down=j == 0, eps=eps, precision=precision))(x, sub)
            k += 1
    x = _act(jnp.mean(x, axis=(1, 2)), precision)
    return _lower_grad(jnp.dot(_lower(x, precision),
                               _lower(p["head/kernel"], precision),
                               precision=HI), precision) + p["head/bias"]


def loss(cfg: dict, p: dict, images, labels, precision: str = "float32"):
    lg = logits(cfg, p, images, precision)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def train_steps(cfg: dict, seed: int, *, groups: int, per_group: int,
                steps: int = 3, precision: str = "float32") -> dict:
    """``steps`` steps of SGD with momentum from the seed's weights on the
    seed's batch, the batch in ``groups`` groups of ``per_group`` rows whose
    losses and gradients are averaged.  Returns each step's loss, the norm
    of every leaf of the first gradient, and the norm of every leaf's change
    over the steps, as ``{path: float}``."""
    opt = cfg["optimizer"]
    lr = float(opt["lr_per_chip"]) * groups
    mu = float(opt["momentum"])
    p = jax.jit(functools.partial(make_params, cfg))(seed_arg(seed))
    images, labels = jax.jit(functools.partial(
        make_batch, cfg, n=groups * per_group))(seed_arg(seed))
    vg = jax.jit(jax.value_and_grad(
        lambda p, x, y: loss(cfg, p, x, y, precision)))
    p0, m = p, None
    losses, first = [], None
    for _ in range(steps):
        tot, g = 0.0, None
        for i in range(groups):
            rows = slice(i * per_group, (i + 1) * per_group)
            li, gi = vg(p, images[rows], labels[rows])
            tot += float(li) / groups
            g = gi if g is None else jax.tree.map(jnp.add, g, gi)
        g = jax.tree.map(lambda a: a / groups, g)
        if first is None:
            first = {k: float(jnp.linalg.norm(v.ravel()))
                     for k, v in g.items()}
        m = g if m is None else jax.tree.map(lambda a, b: mu * a + b, m, g)
        p = jax.tree.map(lambda a, b: a - lr * b, p, m)
        losses.append(tot)
    delta = {k: float(jnp.linalg.norm((p[k] - p0[k]).ravel())) for k in p}
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
