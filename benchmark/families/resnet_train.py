"""Family ``resnet_train``: a ResNet of ``horovod_tpu.models.resnet`` trained
the way README's recipe trains it — ``hvd.DistributedOptimizer(optax.sgd)``
through ``hvd.make_train_step`` on a synthetic batch that lives on the
device, split over the ranks as ``ShardedLoader`` places a batch.

The weights and the batch are the benchmark's own, made on the device from
the seed by ``reference/resnet.py`` (which the check runs again by itself);
from the program come the model, the optimizer wrapper and the train step.
"""

from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import lib

REFERENCE = "resnet"
CONTROL = "fp8"         # the nearest precision below bfloat16 compute

#: Limits of the comparison, each from chip readings at the cell's own size
#: (PERF.md, section 2): the largest of 15 sound seeds and the smallest of 3
#: seeds of the control.  ``loss_rel`` (sound 1.4e-4, control 1.6e-4: fp8
#: hardly moves it) is held against a part of the batch left out, at three
#: times the sound runs' largest; the others lie between sound and control
#: (worst leaf 0.062 / 1.83, median leaf 0.0016 / 0.028, change 0.047 / 1.41).
LIMITS = {"loss_rel": 4e-4, "grad_norm_worst": 0.3, "grad_norm_median": 0.007,
          "delta_norm_worst": 0.25}


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def _flat(tree) -> dict:
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = v
    return out


class Job:
    """The compiled step with its state: set-up builds one, drives it through
    its first steps, and hands the same object to the window."""

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int):
        import horovod_tpu as hvd
        import optax
        from horovod_tpu.models.resnet import ResNet

        ref = lib.load_module("reference", REFERENCE)
        devices = jax.devices()[:chips]
        hvd.init(devices=devices)
        self.n = hvd.size()
        self.per_chip = int(mix["per_chip_batch"])
        self.items_per_step = self.per_chip * self.n
        model = ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                       num_classes=int(cfg["num_classes"]),
                       width=int(cfg["width"]),
                       dtype=jnp.dtype(cfg["compute_dtype"]))
        flat = jax.jit(functools.partial(ref.make_params, cfg))(
            ref.seed_arg(seed))
        params = _nest(flat)
        shapes = jax.eval_shape(
            functools.partial(model.init, train=False), jax.random.key(0),
            jnp.zeros((1, cfg["image_size"], cfg["image_size"], 3)))
        want = {k: v.shape for k, v in _flat(shapes["params"]).items()}
        have = {k: v.shape for k, v in flat.items()}
        if want != have:
            raise SystemExit("benchmark: reference/resnet.py's weights are "
                             "not the model's: "
                             f"{set(want.items()) ^ set(have.items())}")
        batch_stats = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes["batch_stats"])
        images, labels = jax.jit(functools.partial(
            ref.make_batch, cfg, n=self.items_per_step))(ref.seed_arg(seed))
        self.batch = (jax.device_put(images, hvd.rank_sharding()),
                      jax.device_put(labels, hvd.rank_sharding()))

        def loss_fn(p, batch):
            x, y = batch
            logits, _ = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        opt = cfg["optimizer"]
        tx = hvd.DistributedOptimizer(optax.sgd(
            float(opt["lr_per_chip"]) * self.n,
            momentum=float(opt["momentum"])))
        self.params = hvd.broadcast_parameters(params, root_rank=0)
        self.opt_state = jax.jit(tx.init)(self.params)
        self._step = hvd.make_train_step(loss_fn, tx)
        self._hvd = hvd
        # The TPU runtime's ``peak_bytes_in_use`` counts arrays, not the
        # scratch a running program holds (activations kept for the backward
        # pass): the compiler states it, so the peak can be told whole.
        mem = self._step.lower(self.params, self.opt_state,
                               self.batch).compile().memory_analysis()
        self.scratch_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)

    def step(self):
        """One step through the window's own call and feed; returns the
        loss as it sits on the device (no host read)."""
        out = self._step(self.params, self.opt_state, self.batch)
        self.params, self.opt_state = out.params, out.opt_state
        return out.loss

    def first_steps(self, steps: int = 3) -> dict:
        """Drive the first ``steps`` steps and read what the check compares:
        each loss, the norm of each leaf of the first gradient as the
        optimizer got it (its momentum after one step from zero), and the
        norm of each leaf's change over the steps."""
        norms = jax.jit(lambda t: [jnp.linalg.norm(
            x.astype(jnp.float32).ravel()) for x in jax.tree.leaves(t)])
        diff = jax.jit(lambda a, b: [jnp.linalg.norm(
            (x - y).astype(jnp.float32).ravel()) for x, y in zip(
                jax.tree.leaves(a), jax.tree.leaves(b))])
        names = list(_flat(self.params))
        p0 = jax.tree.map(jnp.copy, self.params)
        losses, grad = [], None
        for i in range(steps):
            losses.append(float(self.step()))
            if i == 0:
                trace = [x for x in jax.tree.leaves(self.opt_state)
                         if getattr(x, "ndim", 0) > 0]
                if [x.shape for x in trace] != [
                        x.shape for x in jax.tree.leaves(self.params)]:
                    raise SystemExit("benchmark: the optimizer's state is "
                                     "not one momentum leaf per weight")
                grad = dict(zip(names, map(float, norms(trace))))
        delta = dict(zip(names, map(float, diff(self.params, p0))))
        return {"losses": losses, "grad_norms": grad, "delta_norms": delta}

    def signatures(self) -> int:
        """Signatures the step has compiled: one, or something compiled in
        the window."""
        return int(self._step._cache_size())

    def free(self) -> None:
        self.params = self.opt_state = self.batch = self._step = None
        self._hvd.shutdown()


def build(ctx) -> Job:
    return Job(ctx.config, ctx.mix, ctx.seed, ctx.chips)


def _leaf_gaps(prog: dict, ref: dict) -> list:
    """The gap between the program's norm and the reference's, leaf by leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    floor = statistics.median(ref.values())
    return [abs(prog[k] - ref[k]) / max(ref[k], floor) for k in ref]


def _worst_leaf(prog: dict, ref: dict) -> float:
    return max(_leaf_gaps(prog, ref))


def worst_leaves(prog: dict, ref: dict, k: int = 3) -> list:
    floor = statistics.median(ref.values())
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], floor) for n in ref}
    return [(n, round(gaps[n], 4), prog[n], ref[n])
            for n in sorted(gaps, key=gaps.get)[-k:]]


def compare(prog: dict, ref: dict) -> list:
    values = {
        "loss_rel": max(abs(a - b) / abs(b) for a, b in zip(
            prog["losses"], ref["losses"])),
        "grad_norm_worst": _worst_leaf(prog["grad_norms"],
                                       ref["grad_norms"]),
        "grad_norm_median": statistics.median(_leaf_gaps(
            prog["grad_norms"], ref["grad_norms"])),
        "delta_norm_worst": _worst_leaf(prog["delta_norms"],
                                        ref["delta_norms"]),
    }
    return [{"name": k, "value": float(v), "limit": LIMITS[k],
             "ok": bool(np.isfinite(v) and v <= LIMITS[k])}
            for k, v in values.items()]


def reference_readings(ctx, steps: int, precision: str = "float32") -> dict:
    ref = lib.load_module("reference", REFERENCE)
    return ref.train_steps(ctx.config, ctx.seed, groups=ctx.chips,
                           per_group=int(ctx.mix["per_chip_batch"]),
                           steps=steps, precision=precision)


def check(ctx, readings: dict) -> list:
    """After the window, with the program's state freed: the reference
    follows the first steps from the seed and each number is compared."""
    return compare(readings, reference_readings(ctx, len(readings["losses"])))


def conv_shapes(cfg: dict) -> list:
    """Every convolution and the head as ``(out_h, out_w, k, c_in, c_out)``:
    the shapes the operation count is made from."""
    w, s = int(cfg["width"]), int(cfg["image_size"])
    s = -(-s // 2)                              # 7x7 stride 2
    out = [(s, s, 7, 3, w)]
    s = -(-s // 2)                              # 3x3 max-pool stride 2
    c_in = w
    for stage, count in enumerate(cfg["stage_sizes"]):
        f = w * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            out.append((s, s, 1, c_in, f))
            s_out = -(-s // stride)
            out.append((s_out, s_out, 3, f, f))
            out.append((s_out, s_out, 1, f, 4 * f))
            if j == 0:
                out.append((s_out, s_out, 1, c_in, 4 * f))
            s, c_in = s_out, 4 * f
    out.append((1, 1, 1, c_in, int(cfg["num_classes"])))
    return out


def flops_per_item(cfg: dict) -> float:
    """Operations one image needs through forward and backward: two per
    multiply-add of every convolution and of the head, once forward and
    twice backward (for the inputs and for the weights).  Normalisation,
    pooling, the loss and the update are left out, so the share of the peak
    reads a little low, never high."""
    macs = sum(h * w * k * k * ci * co for h, w, k, ci, co in
               conv_shapes(cfg))
    return 3.0 * 2.0 * macs


def probe(ctx, control: bool) -> dict:
    """For ``limits_probe.py``: the program's first steps against the
    reference and, if asked, the control's."""
    job = build(ctx)
    got = job.first_steps(int(ctx.mix["check_steps"]))
    job.free()
    steps = len(got["losses"])
    ref = reference_readings(ctx, steps)
    out = {"sound": {c["name"]: c["value"] for c in compare(got, ref)},
           "worst": {"grad": worst_leaves(got["grad_norms"],
                                          ref["grad_norms"]),
                     "delta": worst_leaves(got["delta_norms"],
                                           ref["delta_norms"])}}
    if control:
        low = reference_readings(ctx, steps, precision=CONTROL)
        out["control"] = {c["name"]: c["value"] for c in compare(low, ref)}
        out["worst_control"] = worst_leaves(low["grad_norms"],
                                            ref["grad_norms"])
    return out
