"""The plain reference for the ``mellum_train`` family: a decoder of sliding
and full attention layers over softmax-routed SwiGLU experts, its loss, the
loss's gradients and AdamW's update, in straightforward ``jax.numpy`` and
float32 with every product at ``Precision.HIGHEST``.  It imports nothing of
the program and makes its own weights and batch from the seed (the family
hands the same to the program).

**The equations** (``h`` hidden, ``T`` tokens of one sequence, positions
``t`` from 0; the configuration's file gives the sizes):

* ``x_0 = E[ids]``.  For layer ``l``: ``a = x + Attn_l(RMSNorm(x; g1_l))``,
  ``x' = a + MoE_l(RMSNorm(a; g2_l))``; ``RMSNorm(x; g) = x / sqrt(mean(x^2)
  + eps) * g``; no biases.  After the last layer ``RMSNorm(x; g_f)`` and
  ``logits = x W_head``; loss = mean next-token cross-entropy.
* ``Attn``: ``q = x W_q`` [T, H, Dh], ``k = x W_k``, ``v = x W_v`` [T, KVH,
  Dh]; half-split rotary on q and k (dimension ``i`` pairs with ``i + Dh/2``)
  with the layer kind's table; scores ``q k^T / sqrt(Dh)``; query ``t`` sees
  key ``j`` iff ``j <= t`` and, in a sliding layer, ``t - j <
  sliding_window``; softmax; then ``W_o``.  Query head ``g`` reads
  key-value head ``g // (H / KVH)``.
* rotary, sliding layers: ``inv_freq_i = theta^(-2i / Dh)``, ``i = 0 ..
  Dh/2 - 1``; cos and sin unscaled.
* rotary, full layers (YaRN as ``transformers`` computes it, ``truncate``
  true): ``dim(r) = Dh ln(original_max / (2 pi r)) / (2 ln theta)``; ``low =
  floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` clipped to [0,
  Dh/2 - 1]; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i
  = (1 - ramp_i) theta^(-2i / Dh) + ramp_i theta^(-2i / Dh) / factor``; cos
  and sin both times ``attention_factor``.
* ``MoE``: ``p = softmax(u W_r)`` over all published experts (``u`` the
  normed input); the ``num_experts_per_tok`` largest are chosen (ties to
  the lower index), weights ``p_e / sum_chosen p``; ``y = sum_{chosen e held}
  w_e W_down_e (silu(W_gate_e u) * W_up_e u)``.

**Departures, all shared with the program.**  The share held: experts
``held_experts_first .. + num_experts - 1`` of ``num_experts_published`` (the
router keeps its published width; what the absent experts would add is left
out and the partial sum goes on), the first ``vocab_size`` rows of the
vocabulary, the first ``num_hidden_layers`` layers.  The blocks it computes
in: attention a block of :data:`QUERY_BLOCK` queries at a time, the head
:data:`HEAD_ROWS` positions at a time, each layer under ``jax.checkpoint``,
a sequence at a time with the gradients summed, so that 8,192 positions fit
(a score matrix of one layer whole is 8.6 GB): values are unchanged, only
recomputed.  AdamW's moments wait on the host while a step's gradients are
taken.  The head is stored as ``[hidden, vocabulary]`` (``logits = x @
head``).

``precision`` other than ``"float32"`` is the **control**: the same
arithmetic with every tensor that the program holds in its compute type (the
inputs and weights of every product and each product's outcome) rounded to a
lower precision, forward and (the gradient) backward (``"fp8"``:
float8_e4m3fn after scaling to its range, gradients float8_e5m2, as fp8
training does; ``reference/resnet.py``'s rounding with the scaled values
clipped to the type's range before the conversion: on the chip a largest
element that the scaling's own rounding puts a hair over the range converts
to NaN, and at these sizes some tensor always has one: my chip run, PR 48),
which the comparison has to refuse.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import lib

seed_arg = lib.load_module("reference", "resnet").seed_arg

HI = lax.Precision.HIGHEST
#: the largest finite value of the control's two types
E4M3_MAX, E5M2_MAX = 448.0, 57344.0


def _to_fp8(x, dtype, top: float):
    """``x`` rounded to ``dtype`` after scaling its largest element to
    ``top``, the type's largest finite value."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jnp.clip(x * scale, -top, top).astype(dtype).astype(
        jnp.float32) / scale


def _lower(x, precision: str):
    """Round ``x`` to the control's precision, gradient straight through."""
    if precision == "float32":
        return x
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    return x + lax.stop_gradient(_to_fp8(x, jnp.float8_e4m3fn, E4M3_MAX) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _lower_grad(y, precision: str):
    """``y`` itself; on the way back its gradient is rounded as fp8 training
    rounds gradients."""
    return y


def _lower_grad_fwd(y, precision):
    return y, None


def _lower_grad_bwd(precision, _, g):
    if precision != "fp8":
        return (g,)
    return (_to_fp8(g, jnp.float8_e5m2, E5M2_MAX),)


_lower_grad.defvjp(_lower_grad_fwd, _lower_grad_bwd)


def _act(x, precision: str):
    """A tensor the program holds in its compute type: rounded in the
    control, forward and (its gradient) backward."""
    return _lower_grad(_lower(x, precision), precision)

QUERY_BLOCK = 256
HEAD_ROWS = 1024
NEG = -1e30


def sizes(cfg: dict) -> dict:
    """The sizes the equations name, from the configuration's keys."""
    n_layers = int(cfg["num_hidden_layers"])
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]), "layers": n_layers,
        "kinds": [str(k) for k in cfg["layer_types"][:n_layers]],
        "window": int(cfg["sliding_window"]),
        "experts": int(cfg["num_experts_published"]),
        "held": int(cfg["num_experts"]),
        "held_first": int(cfg["held_experts_first"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "f": int(cfg["moe_intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
        "vocab_first": int(cfg["vocab_first_row"]),
        "eps": float(cfg["rms_norm_eps"]),
        "seq_len": int(cfg["training"]["seq_len"])}


def param_shapes(cfg: dict) -> dict:
    """``{path: shape}`` of every weight, in the program's naming."""
    z = sizes(cfg)
    d, hd = z["d"], z["head_dim"]
    shapes = {"embed": (z["vocab"], d), "head": (d, z["vocab"]),
              "final_norm": (d,)}
    for l in range(z["layers"]):
        shapes.update({
            f"layers/{l}/attn_norm": (d,), f"layers/{l}/moe_norm": (d,),
            f"layers/{l}/wq": (d, z["heads"] * hd),
            f"layers/{l}/wk": (d, z["kv_heads"] * hd),
            f"layers/{l}/wv": (d, z["kv_heads"] * hd),
            f"layers/{l}/wo": (z["heads"] * hd, d),
            f"layers/{l}/w_router": (d, z["experts"]),
            f"layers/{l}/e_gate": (z["held"], d, z["f"]),
            f"layers/{l}/e_up": (z["held"], d, z["f"]),
            f"layers/{l}/e_down": (z["held"], z["f"], d)})
    return shapes


def make_params(cfg: dict, seed) -> dict:
    """Float32 weights from the seed: every matrix normal at 0.02, the
    embedding normal at 1, the norms' gains uniform in [0.5, 1.5] (a gain of
    exactly one has no say in the comparison).  With the embedding at 0.02
    too, a fresh model's residual stream is its attention's outcome, a
    near-uniform mean over the window that neighbouring tokens share: whole
    neighbourhoods then choose the same experts, the held experts' load
    swings by seed (24,589 to 72,626 choices an expert, 23.6 and 24.9 % of
    the choices held on two seeds: my chip run, PR 48) and a step's time
    with it.  At 1 a token's own vector decides its choices, as in a model
    whose router has been trained to an even load.
    An expert's weights depend on the seed, the layer and the
    expert's own published index only, and a vocabulary row's on its own
    index, so the shares of one seed tile the uncut model.  Traceable: the
    family jits it so that the weights are made on the device in one call."""
    z = sizes(cfg)
    key = jax.random.key(seed)
    out = {}
    for i, (path, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        name = path.split("/")[-1]
        if name.endswith("norm"):
            out[path] = jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5)
        elif name in ("e_gate", "e_up", "e_down"):
            ids = z["held_first"] + jnp.arange(z["held"])
            out[path] = 0.02 * jax.vmap(lambda e: jax.random.normal(
                jax.random.fold_in(k, e), shape[1:], jnp.float32))(ids)
        elif name in ("embed", "head"):
            rows = jax.vmap(lambda r: jax.random.normal(
                jax.random.fold_in(k, r), (z["d"],), jnp.float32))(
                    z["vocab_first"] + jnp.arange(z["vocab"]))
            out[path] = rows if name == "embed" else 0.02 * rows.T
        else:
            out[path] = 0.02 * jax.random.normal(k, shape, jnp.float32)
    return out


def make_batch(cfg: dict, seed, n: int):
    """``n`` sequences of ``training.seq_len`` ids, uniform over the held
    rows of the vocabulary, and their targets (the next id)."""
    z = sizes(cfg)
    ids = jax.random.randint(
        jax.random.fold_in(jax.random.key(seed), 2**20),
        (n, z["seq_len"] + 1), 0, z["vocab"], jnp.int32)
    return ids[:, :-1], ids[:, 1:]


def inv_freq(cfg: dict, kind: str):
    """``(inv_freq [Dh/2], the factor on cos and sin)`` of a layer kind."""
    dh = int(cfg["head_dim"])
    half = dh // 2
    rp = cfg["rope_parameters"][kind]
    theta = float(rp["rope_theta"])
    plain = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    if rp["rope_type"] == "default":
        return plain, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rp['rope_type']!r}")

    def dim_of(r):
        return dh * math.log(rp["original_max_position_embeddings"]
                             / (2 * math.pi * r)) / (2 * math.log(theta))

    low = min(max(math.floor(dim_of(rp["beta_fast"])), 0), half - 1)
    high = min(max(math.ceil(dim_of(rp["beta_slow"])), 0), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / float(rp["factor"]),
            float(rp["attention_factor"]))


def _mm(x, w, precision):
    """A product the program makes in its compute type: inputs, weights and
    outcome rounded in the control."""
    return _act(jnp.matmul(_lower(x, precision), _lower(w, precision),
                           precision=HI), precision)


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary(x, cos, sin):
    """Half-split rotary on ``x`` [T, heads, Dh] with tables [T, Dh/2]."""
    a, b = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, a * s + b * c], axis=-1)


def attention(cfg: dict, kind: str, p: dict, pre: str, u, precision):
    """``Attn`` of one sequence ``u`` [T, h], a block of queries at a time."""
    z = sizes(cfg)
    t = u.shape[0]
    h, kvh, dh = z["heads"], z["kv_heads"], z["head_dim"]
    freq, factor = inv_freq(cfg, kind)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    q = _rotary(_mm(u, p[pre + "wq"], precision).reshape(t, h, dh), cos, sin)
    k = _rotary(_mm(u, p[pre + "wk"], precision).reshape(t, kvh, dh), cos,
                sin)
    v = _mm(u, p[pre + "wv"], precision).reshape(t, kvh, dh)
    q, k = _act(q, precision), _act(k, precision)
    k = jnp.repeat(k, h // kvh, axis=1)          # head g reads g // (h / kvh)
    v = jnp.repeat(v, h // kvh, axis=1)
    block = math.gcd(QUERY_BLOCK, t)     # whole blocks whatever ``t`` is

    def one_block(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(dh)
        tq = start + jnp.arange(block)[:, None]
        tk = jnp.arange(t)[None, :]
        seen = tk <= tq
        if kind == "sliding_attention":
            seen = seen & (tq - tk < z["window"])
        w = jax.nn.softmax(jnp.where(seen[None], s, NEG), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _lower(w, precision), v,
                          precision=HI)

    o = lax.map(jax.checkpoint(one_block), jnp.arange(0, t, block))
    o = _act(o.reshape(t, h * dh), precision)
    return _mm(o, p[pre + "wo"], precision)


def route(cfg: dict, p: dict, pre: str, u):
    """``(experts [T, k], weights [T, k])``: softmax over every published
    expert, the ``k`` largest (ties to the lower index), renormalised."""
    z = sizes(cfg)
    prob = jax.nn.softmax(jnp.matmul(u, p[pre + "w_router"], precision=HI),
                          axis=-1)
    top, experts = lax.top_k(prob, z["top_k"])
    return experts, top / jnp.sum(top, axis=-1, keepdims=True)


def experts_part(cfg: dict, p: dict, pre: str, u, precision):
    """``MoE``'s sum over the held experts for ``u`` [T, h]: every token
    through every held expert, weighted where it chose it."""
    z = sizes(cfg)
    experts, weights = route(cfg, p, pre, u)

    def one_expert(y, j):
        gate = _mm(u, p[pre + "e_gate"][j], precision)
        up = _mm(u, p[pre + "e_up"][j], precision)
        out = _mm(_act(jax.nn.silu(gate) * up, precision),
                  p[pre + "e_down"][j], precision)
        w = jnp.sum(jnp.where(experts == z["held_first"] + j, weights, 0.0),
                    axis=-1)
        return y + w[:, None] * out, None

    y, _ = lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                    jnp.arange(z["held"]))
    return _act(y, precision)


def _layer(cfg, kind, pre, precision, p, x):
    z = sizes(cfg)
    a = x + attention(cfg, kind, p, pre, _act(rmsnorm(
        x, p[pre + "attn_norm"], z["eps"]), precision), precision)
    return a + experts_part(cfg, p, pre, _act(rmsnorm(
        a, p[pre + "moe_norm"], z["eps"]), precision), precision)


def hidden(cfg: dict, p: dict, ids, precision: str = "float32"):
    """The last layer's outcome, normed, for one sequence ``ids`` [T]."""
    z = sizes(cfg)
    x = _act(p["embed"][ids], precision)
    for l, kind in enumerate(z["kinds"]):
        pre = f"layers/{l}/"
        sub = {n: v for n, v in p.items() if n.startswith(pre)}
        x = jax.checkpoint(functools.partial(
            _layer, cfg, kind, pre, precision))(sub, x)
    return _act(rmsnorm(x, p["final_norm"], z["eps"]), precision)


def logits(cfg: dict, p: dict, ids, precision: str = "float32"):
    """``[T, vocabulary held]`` for one sequence."""
    return jnp.matmul(_lower(hidden(cfg, p, ids, precision), precision),
                      _lower(p["head"], precision), precision=HI)


def loss(cfg: dict, p: dict, ids, targets, precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence, the logits of
    :data:`HEAD_ROWS` positions at a time (a sequence's whole are 0.8 GB,
    and their gradient as much again)."""
    x = _lower(hidden(cfg, p, ids, precision), precision)
    head = _lower(p["head"], precision)
    t = x.shape[0]
    rows = math.gcd(HEAD_ROWS, t)

    def block(xt):
        xb, tb = xt
        lg = jnp.matmul(xb, head, precision=HI)
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        return -jnp.sum(jnp.take_along_axis(logp, tb[:, None], axis=-1))

    parts = lax.map(jax.checkpoint(block), (
        x.reshape(t // rows, rows, -1), targets.reshape(t // rows, rows)))
    return jnp.sum(parts) / t


def choices(cfg: dict, p: dict, ids):
    """``[L, T, k]``: the experts each token of one sequence chooses in each
    layer, in ascending order."""
    z = sizes(cfg)
    x = p["embed"][ids]
    chosen = []
    for l, kind in enumerate(z["kinds"]):
        pre = f"layers/{l}/"
        a = x + attention(cfg, kind, p, pre, rmsnorm(
            x, p[pre + "attn_norm"], z["eps"]), "float32")
        u = rmsnorm(a, p[pre + "moe_norm"], z["eps"])
        chosen.append(jnp.sort(route(cfg, p, pre, u)[0], axis=-1))
        x = a + experts_part(cfg, p, pre, u, "float32")
    return jnp.stack(chosen)


def train_steps(cfg: dict, seed: int, *, groups: int, per_group: int,
                steps: int = 3, precision: str = "float32") -> dict:
    """``steps`` steps of AdamW (written out; ``training.optimizer``'s
    numbers: the learning rate rises linearly over ``warmup_steps`` steps,
    step ``t`` from 1 takes ``lr * min(t / warmup_steps, 1)``; decay on
    every leaf) from the seed's weights on the seed's
    batch of ``groups * per_group`` sequences, a sequence at a time with the
    losses and gradients averaged.  Returns each step's loss, the norm of
    every leaf of the first gradient, and the norm of every leaf's change
    over the steps, as ``{path: float}``."""
    opt = cfg["training"]["optimizer"]
    lr, b1, b2 = float(opt["lr"]), float(opt["beta1"]), float(opt["beta2"])
    eps, wd = float(opt["eps"]), float(opt["weight_decay"])
    warmup = float(opt["warmup_steps"])
    n = groups * per_group
    p = jax.jit(functools.partial(make_params, cfg))(seed_arg(seed))
    ids, targets = jax.jit(functools.partial(make_batch, cfg, n=n))(
        seed_arg(seed))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add_grad(acc, p, ids, targets):
        value, g = jax.value_and_grad(
            lambda p: loss(cfg, p, ids, targets, precision))(p)
        return jax.tree.map(lambda a, b: a + b / n, acc, g), value

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adamw(p, m, v, g, t):
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        rate = lr * jnp.minimum(t / warmup, 1.0)
        p = jax.tree.map(
            lambda p, m, v: p - rate * (
                m / (1 - b1 ** t) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                + wd * p), p, m, v)
        return p, m, v

    norms = jax.jit(lambda t: {k: jnp.linalg.norm(x.ravel())
                               for k, x in t.items()})
    m = v = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    losses, first = [], None
    for step in range(steps):
        g = jax.tree.map(jnp.zeros_like, p)
        total = 0.0
        for i in range(n):
            g, value = add_grad(g, p, ids[i], targets[i])
            total += float(value) / n
        if first is None:
            first = {k: float(x) for k, x in norms(g).items()}
        p, m, v = adamw(p, jax.device_put(m), jax.device_put(v), g,
                        jnp.float32(step + 1))
        del g
        # the moments wait on the host while the gradients are taken: at the
        # cell's size weights, gradients, moments and a sequence's
        # activations in float32 do not fit the chip together
        if step + 1 < steps:
            m, v = jax.device_get((m, v))
        losses.append(total)
    del m, v
    # the seed's weights are made again, not kept beside the state: at the
    # cell's size a fifth copy of the weights does not fit
    p0 = jax.jit(functools.partial(make_params, cfg))(seed_arg(seed))
    delta = {k: float(x) for k, x in jax.jit(lambda a, b: {
        k: jnp.linalg.norm((a[k] - b[k]).ravel()) for k in a})(p, p0).items()}
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
