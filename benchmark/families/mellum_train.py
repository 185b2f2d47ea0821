"""Family ``mellum_train``: the trainable decoder of
``horovod_tpu.models.moe_decoder`` (sliding and full flash attention, dropless
held experts, the fused head) trained the way README's recipe trains a model:
``hvd.DistributedOptimizer(optax.adamw)`` through ``hvd.make_train_step`` on a
synthetic batch of packed sequences that lives on the device, split over the
ranks as ``ShardedLoader`` places a batch.

The weights and the batch are the benchmark's own, made on the device from
the seed by ``reference/mellum.py`` (which the check runs again by itself);
from the program come the model, the optimizer wrapper and the train step.
An item is a token.
"""

from __future__ import annotations

import functools
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import lib
from horovod_tpu.models import moe_decoder as md

_resnet = lib.load_module("families", "resnet_train")
worst_leaves = _resnet.worst_leaves

REFERENCE = "mellum"
CONTROL = "fp8"         # the nearest precision below bfloat16 products

#: Limits of the comparison, from chip readings at the cell's own size
#: (PERF.md, section 2): under each the largest of 25 sound seeds, over it
#: the smallest of 3 seeds of the control and two planted faults (the band
#: left out of the sliding layers, half of the batch left out of the loss).
#: ``loss_rel``: sound 1.89e-5; fp8 hardly moves a fresh model's loss (4.7e-6
#: to 3.7e-5), so its upper readings are the faults', 9.1e-5 and 2.4e-4, and
#: the accepted training cells' 4e-4 would pass both.  The two gradient
#: numbers lie between sound and control at about their geometric mean
#: (worst leaf 0.00235 / 0.0132, median leaf 1.64e-4 / 1.51e-3; the faults
#: read 0.27 and 0.0089, 0.43 and 0.39).  ``delta_norm_worst``: sound 0.0082;
#: the control's 0.0233 is under three times that, so the precision hardly
#: moves it and its upper reading is a state left as it was, 1 (the half
#: batch reads 0.17): three times the sound runs' largest, which the control
#: passes.
LIMITS = {"loss_rel": 6e-5, "grad_norm_worst": 0.006,
          "grad_norm_median": 5e-4, "delta_norm_worst": 0.025}

#: steps :func:`probe` times after the checked ones (how far a seed's routing
#: moves a step's time is part of what a cell's spread is made of)
PROBE_TIMED_STEPS = 6

#: the device trace's names of the kernels whose time the per-layer metrics
#: read, by layer kind and part (a substring of the operation's name)
KERNELS = {
    "flash_band": ("flash_band_fwd", "flash_band_dq", "flash_band_dkv"),
    "flash_full": ("flash_fwd", "flash_dq", "flash_dkv"),
    "experts": ("grouped_swiglu", "grouped_swiglu_dx", "grouped_swiglu_dw"),
}


def model_config(cfg: dict, **overrides) -> md.MoEDecoderConfig:
    """The program's config of the configuration's file (HF's keys)."""
    n_layers = int(cfg["num_hidden_layers"])
    kinds = tuple({"sliding_attention": "window", "full_attention": "full"}[k]
                  for k in cfg["layer_types"][:n_layers])
    rp = cfg["rope_parameters"]
    full, plain = rp["full_attention"], rp["sliding_attention"]
    if plain["rope_type"] != "default" or full["rope_type"] != "yarn" or \
            float(full["rope_theta"]) != float(plain["rope_theta"]):
        raise SystemExit("benchmark: the sliding layers' rotary is not plain, "
                         "or the full layers' not YaRN at the same theta")
    train = cfg["training"]
    fields = dict(
        vocab_size=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        n_layers=n_layers, n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), layer_kinds=kinds,
        window=int(cfg["sliding_window"]),
        rope_theta=float(plain["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        expert_dim=int(cfg["moe_intermediate_size"]),
        n_experts=int(cfg["num_experts_published"]),
        top_k=int(cfg["num_experts_per_tok"]),
        held_first=int(cfg["held_experts_first"]),
        held_count=int(cfg["num_experts"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype(train["compute_dtype"]),
        param_dtype=jnp.dtype(train["param_dtype"]))
    fields.update(overrides)
    return md.MoEDecoderConfig(**fields)


def _flat(tree) -> dict:
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)] = v
    return out


def optimizer(cfg: dict):
    import optax

    opt = cfg["training"]["optimizer"]
    lr, warmup = float(opt["lr"]), float(opt["warmup_steps"])

    def rate(count):        # the step that takes it is ``count + 1``
        return lr * jnp.minimum((count + 1) / warmup, 1.0)

    return optax.adamw(rate, b1=float(opt["beta1"]),
                       b2=float(opt["beta2"]), eps=float(opt["eps"]),
                       weight_decay=float(opt["weight_decay"]))


class Job:
    """The compiled step with its state: set-up builds one, drives it through
    its first steps, and hands the same object to the window."""

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int,
                 **overrides):
        import horovod_tpu as hvd

        self.ref = lib.load_module("reference", REFERENCE)
        self.cfg, self.seed = cfg, seed
        hvd.init(devices=jax.devices()[:chips])
        self._hvd = hvd
        self.n = hvd.size()
        self.mc = model_config(cfg, **overrides)
        self.seq_len = int(cfg["training"]["seq_len"])
        self.sequences = int(mix["per_chip_batch"]) * self.n
        self.items_per_step = self.sequences * self.seq_len
        flat = jax.jit(functools.partial(self.ref.make_params, cfg))(
            self.ref.seed_arg(seed))
        want = md.param_shapes(self.mc)
        have = {k: v.shape for k, v in flat.items()}
        if want != have:
            raise SystemExit("benchmark: reference/mellum.py's weights are "
                             "not the model's: "
                             f"{set(want.items()) ^ set(have.items())}")
        ids, targets = jax.jit(functools.partial(
            self.ref.make_batch, cfg, n=self.sequences))(
                self.ref.seed_arg(seed))
        self.batch = (jax.device_put(ids, hvd.rank_sharding()),
                      jax.device_put(targets, hvd.rank_sharding()))
        tx = hvd.DistributedOptimizer(optimizer(cfg))
        self.params = hvd.broadcast_parameters(md.nest(flat), root_rank=0)
        del flat
        self.opt_state = jax.jit(tx.init)(self.params)
        self._step = hvd.make_train_step(
            functools.partial(md.loss_and_counters, cfg=self.mc), tx,
            has_aux=True)
        # The TPU runtime's ``peak_bytes_in_use`` counts arrays, not the
        # scratch a running program holds (the gradients, the activations
        # kept for the backward pass): the compiler states it.
        mem = self._step.lower(self.params, self.opt_state,
                               self.batch).compile().memory_analysis()
        self.scratch_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self.totals = None

    def step(self):
        """One step through the window's own call and feed; returns the loss
        as it sits on the device.  The step's counters are added to the
        running totals on the device (no host read)."""
        out = self._step(self.params, self.opt_state, self.batch)
        self.params, self.opt_state = out.params, out.opt_state
        self.totals = out.aux if self.totals is None else self._add(
            self.totals, out.aux)
        return out.loss

    def take_counters(self) -> dict:
        """The counters summed over the steps since the last call, read from
        the device once; the totals start again."""
        totals, self.totals = self.totals, None
        return {} if totals is None else md.read_counters(totals)

    def first_steps(self, steps: int = 3) -> dict:
        """Drive the first ``steps`` steps and read what the check compares:
        each loss, the norm of each leaf of the first gradient as the
        optimizer got it (AdamW's first moment after one step from zero,
        over ``1 - beta1``), and the norm of each leaf's change over the
        steps (against the seed's weights, made again inside the program
        that takes the norms: a copy kept beside the state would count in
        the run's peak)."""
        b1 = float(self.cfg["training"]["optimizer"]["beta1"])
        names = list(_flat(self.params))
        norms = jax.jit(lambda t: [jnp.linalg.norm(
            x.astype(jnp.float32).ravel()) for x in jax.tree.leaves(t)])
        losses, grad = [], None
        for i in range(steps):
            losses.append(float(self.step()))
            if i == 0:
                mu = [s.mu for s in jax.tree.leaves(
                    self.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                    if hasattr(s, "mu")]
                if len(mu) != 1 or list(_flat(mu[0])) != names:
                    raise SystemExit("benchmark: the optimizer's state holds "
                                     "no first moment per weight")
                grad = {k: float(v) / (1.0 - b1)
                        for k, v in zip(names, norms(mu[0]))}

        @jax.jit
        def moved(params, seed):
            flat0 = self.ref.make_params(self.cfg, seed)
            return [jnp.linalg.norm((v - flat0[k]).ravel())
                    for k, v in _flat(params).items()]

        delta = dict(zip(names, map(float, moved(
            self.params, self.ref.seed_arg(self.seed)))))
        return {"losses": losses, "grad_norms": grad, "delta_norms": delta}

    def signatures(self) -> int:
        """Signatures the step has compiled: one, or something compiled in
        the window."""
        return int(self._step._cache_size())

    def free(self) -> None:
        self.params = self.opt_state = self.batch = self._step = None
        self.totals = None
        self._hvd.shutdown()


def build(ctx) -> Job:
    return Job(ctx.config, ctx.mix, ctx.seed, ctx.chips)


def compare(prog: dict, ref: dict) -> list:
    """``resnet_train``'s four numbers (its leaf-by-leaf gaps: against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger), under this family's limits."""
    grad = _resnet._leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    values = {
        "loss_rel": max(abs(a - b) / abs(b) for a, b in zip(
            prog["losses"], ref["losses"])),
        "grad_norm_worst": max(grad),
        "grad_norm_median": statistics.median(grad),
        "delta_norm_worst": max(_resnet._leaf_gaps(prog["delta_norms"],
                                                   ref["delta_norms"])),
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


# --- operations, counted from the shapes ----------------------------------

def visible_pairs(seq_len: int, window: int | None) -> int:
    """The (query, key) pairs one head sees over a sequence: the causal
    triangle, or the band of ``window`` keys within it."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def kernel_ops(cfg: dict, mix: dict) -> dict:
    """What one call of each traced kernel computes, two operations a
    multiply-add, under the mask (a block's masked corner is not counted:
    the shares read a little low, never high).  The flash kernels by call
    (one layer, the batch's sequences and heads): the forward two products a
    pair (scores, values), dQ three (scores, dP, dQ), dK/dV four.  The
    grouped products by choice, a row of ``[hidden, expert width]``: the
    forward three products, the rows' gradient five (gate and up again, dA,
    and two back), the weights' gradient three."""
    mc = model_config(cfg)
    t = int(cfg["training"]["seq_len"])
    heads = int(mix["per_chip_batch"]) * mc.n_heads
    ops = {}
    for kind, window in (("flash_band", mc.window), ("flash_full", None)):
        pair = 2.0 * mc.head_dim * heads * visible_pairs(t, window)
        for name, products in zip(KERNELS[kind], (2, 3, 4)):
            ops[name] = products * pair
    df = 2.0 * mc.dim * mc.expert_dim
    ops.update({"grouped_swiglu": 3 * df, "grouped_swiglu_dx": 5 * df,
                "grouped_swiglu_dw": 3 * df})
    return ops


def flops_per_item(cfg: dict) -> float:
    """Operations one token needs through forward and backward, two per
    multiply-add, once forward and twice backward; recomputation is not
    counted.  The projections, the scores and values under the band's and
    the causal triangle's masks at the configuration's ``seq_len``, the
    expert products for ``top_k x held / n_experts`` choices a layer (what
    even routing sends here), the head.  The router, the norms, the rotary,
    the softmax and the update are left out, so the share of the peak reads
    a little low, never high."""
    mc = model_config(cfg)
    t = int(cfg["training"]["seq_len"])
    hd = mc.head_dim
    proj = mc.dim * hd * (2 * mc.n_heads + 2 * mc.n_kv_heads)
    macs = 0.0
    for kind in mc.layer_kinds:
        pairs = visible_pairs(t, mc.window if kind == "window" else None)
        macs += proj + 2.0 * hd * mc.n_heads * pairs / t
        macs += (mc.top_k * mc.held_count / mc.n_experts
                 * 3 * mc.dim * mc.expert_dim)
    macs += mc.dim * mc.vocab_size
    return 3.0 * 2.0 * macs


def choices_differing(ctx, params: dict, batch: tuple, mc) -> dict:
    """How many of the batch's (token, layer) top-k choices the program's
    forward pass makes otherwise than the reference's, by layer."""
    ref = lib.load_module("reference", REFERENCE)
    flat = _flat(params)
    prog = np.asarray(jax.jit(functools.partial(
        md.expert_choices, cfg=mc))(params, batch[0]))      # [L, B, T, k]
    theirs = jax.jit(functools.partial(ref.choices, ctx.config))
    want = np.stack([np.asarray(theirs(flat, ids))
                     for ids in batch[0]], axis=1)
    differ = (prog != want).any(axis=-1)
    one = (prog[..., None] == want[..., None, :]).any(-1).sum(-1)
    return {"tokens_with_another_choice": differ.sum(axis=(1, 2)).tolist(),
            "choices_differing": (prog.shape[-1] - one).sum(
                axis=(1, 2)).tolist(),
            "choices_a_layer": int(prog[0].size)}


def probe(ctx, control: bool) -> dict:
    """For ``limits_probe.py``: the program's first steps against the
    reference and, if asked, the control's; and the choices that differ."""
    job = build(ctx)
    differ = choices_differing(ctx, job.params, job.batch, job.mc)
    got = job.first_steps(int(ctx.mix["check_steps"]))
    counters = job.take_counters()
    t0 = time.monotonic()
    for _ in range(PROBE_TIMED_STEPS):
        loss = job.step()
    loss.block_until_ready()
    step_ms = 1e3 * (time.monotonic() - t0) / PROBE_TIMED_STEPS
    job.free()
    steps = len(got["losses"])
    ref = reference_readings(ctx, steps)
    out = {"sound": {c["name"]: c["value"] for c in compare(got, ref)},
           "losses": got["losses"], "losses_ref": ref["losses"],
           "step_ms": step_ms,
           "held_share": counters["moe.choices_held"]
           / counters["moe.choices_total"],
           "choices": differ, "held_load": [v for k, v in sorted(
               counters.items()) if k.startswith("moe.held_load.")],
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
