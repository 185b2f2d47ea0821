"""The plain reference for the ``lfm2_serve`` family: the language model of
``LFM2-8B-A1B`` (``model_type`` ``lfm2_moe``) as its ``config.json`` gives it —
a full causal forward pass in straightforward ``jax.numpy`` and float32, every
product at ``Precision.HIGHEST``, with no cache, no batching and no kernel.  It
imports nothing of the program.

**Layer equations.**  ``u = RMSNorm_op(x)``, ``h = x + Op(u)``,
``y = h + FFN(RMSNorm_ffn(h))``, eps ``norm_eps`` (1e-5), no biases anywhere.

* *Short convolution* (``layer_types[i] == "conv"``): ``[B, C, X] = split3(u
  W_in)`` (``W_in``: ``d -> 3 d``, in that order); ``z = B * X``; ``c_t =
  sum_{j=0..K-1} w[j] * z_{t-(K-1)+j}`` with ``K = conv_L_cache`` (3):
  depthwise, causal, ``conv_bias`` false, ``z`` before position 0 is zero —
  written here as the explicit sum over ``K`` shifted copies of ``z``;
  ``Op = (C * c) W_out``.
* *Attention* (``"full_attention"``): q ``d -> heads x head_dim``, k and v
  ``d -> kv_heads x head_dim``; RMSNorm over the ``head_dim`` of each q and k
  head (own weights), then rotary (half-split pairs, ``rope_theta``) on all of
  it; causal softmax at ``1 / sqrt(head_dim)``; output ``d -> d``.
* *FFN*, the first ``num_dense_layers`` layers: ``W2(silu(W1 h) * W3 h)`` of
  width ``intermediate_size``.  After them: ``s = sigmoid(h W_r)`` over
  ``num_experts``; the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the
  expert bias, ``use_expert_bias``: in the choice only); weights ``s_chosen /
  (sum s_chosen + 1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``;
  each expert a SwiGLU of width ``moe_intermediate_size``, computed here as a
  dense loop over every expert with a mask; no shared expert.
* Final RMSNorm, then the head, **tied to the embedding**.

``assumed`` (the catalog row leaves these out): the tied head (the family's
convention), the ``1e-6`` of the normaliser, the split order ``B, C, X`` of
``W_in``, ``torch_dtype`` bfloat16, the router computed in float32, and
``head_dim = hidden_size / num_attention_heads``.

**The cut.**  The configuration keeps ``layer_types`` whole and runs its first
``num_hidden_layers``; every width, every expert and the whole vocabulary are
here.

It makes the weights itself, from the seed, one layer at a time (the layers
held at once in float32 would not fit a chip), in the type the configuration
states (bfloat16), and upcasts them: matrices ``[in, out]`` normal at
``1/sqrt(in)``, the embedding normal at ``1/sqrt(d)`` (it is the head too:
:func:`top_weights`), norm weights uniform in [0.5, 1.5],
the expert bias uniform in [-0.05, 0.05] (float32, small and not zero, so that
it changes some choices), conv taps ``[K, d]`` normal at ``1/sqrt(K)``.
``precision="fp8"`` is the **control**: every tensor the program holds in
bfloat16 rounded to float8_e4m3fn instead (weights per output channel,
activations per row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
ROUTE_NORM_EPS = 1e-6


def seed_arg(seed: int):
    """The seed as an argument of a jitted maker (not a constant in it)."""
    return np.uint32(int(seed) % 2**32)


def _dims(cfg: dict) -> dict:
    n_layers = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["layer_types"][:n_layers])
    if len(kinds) != n_layers or set(kinds) - {"conv", "full_attention"}:
        raise ValueError("layer_types has to name every layer's kind")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "d": d, "L": n_layers, "kinds": kinds,
        "dense": int(cfg["num_dense_layers"]),
        "f": int(cfg["intermediate_size"]),
        "K": int(cfg["conv_L_cache"]),
        "h": h, "kvh": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "theta": float(cfg["rope_theta"]),
        "e": int(cfg["num_experts"]), "ef": int(cfg["moe_intermediate_size"]),
        "k": int(cfg["num_experts_per_tok"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "v": int(cfg["vocab_size"]), "eps": float(cfg["norm_eps"]),
    }


def layer_kind(cfg: dict, i: int) -> tuple:
    """``("conv" | "attn", "dense" | "moe")`` of layer ``i``."""
    m = _dims(cfg)
    return ("conv" if m["kinds"][i] == "conv" else "attn",
            "dense" if i < m["dense"] else "moe")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _mat(key, n_in, n_out, dt):
    return (jax.random.normal(key, (n_in, n_out), jnp.float32)
            * n_in ** -0.5).astype(dt)


def _norm_w(key, n, dt):
    return jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5).astype(dt)


def _layer_weights(cfg: dict, kind: tuple, seed, i) -> dict:
    m = _dims(cfg)
    d, hd = m["d"], m["hd"]
    dt = jnp.dtype(cfg["torch_dtype"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), 1), i)
    ks = iter(jax.random.split(key, 16))
    w = {"op_norm": _norm_w(next(ks), d, dt),
         "ffn_norm": _norm_w(next(ks), d, dt)}
    if kind[0] == "conv":
        w.update(
            w_in=_mat(next(ks), d, 3 * d, dt),
            conv_w=(jax.random.normal(next(ks), (m["K"], d), jnp.float32)
                    * m["K"] ** -0.5).astype(dt),
            w_out=_mat(next(ks), d, d, dt))
    else:
        w.update(
            wq=_mat(next(ks), d, m["h"] * hd, dt),
            wk=_mat(next(ks), d, m["kvh"] * hd, dt),
            wv=_mat(next(ks), d, m["kvh"] * hd, dt),
            q_norm=_norm_w(next(ks), hd, dt),
            k_norm=_norm_w(next(ks), hd, dt),
            wo=_mat(next(ks), m["h"] * hd, d, dt))
    if kind[1] == "dense":
        w.update(w_gate=_mat(next(ks), d, m["f"], dt),
                 w_up=_mat(next(ks), d, m["f"], dt),
                 w_down=_mat(next(ks), m["f"], d, dt))
        return w
    w["w_router"] = _mat(next(ks), d, m["e"], dt)
    w["router_bias"] = jax.random.uniform(
        next(ks), (m["e"],), jnp.float32, -0.05, 0.05)
    k_exp = next(ks)

    def expert(e):          # an expert's weights depend on its index alone
        k3 = jax.random.split(jax.random.fold_in(k_exp, e), 3)
        return (_mat(k3[0], d, m["ef"], dt), _mat(k3[1], d, m["ef"], dt),
                _mat(k3[2], m["ef"], d, dt))

    w["e_gate"], w["e_up"], w["e_down"] = jax.vmap(expert)(
        jnp.arange(m["e"]))
    return w


def layer_weights(cfg: dict, seed, i: int) -> dict:
    """Layer ``i``'s weights from the seed.  ``i`` is a Python int (it decides
    the layer's kind, so its shapes)."""
    return _layer_weights(cfg, layer_kind(cfg, int(i)), seed, jnp.int32(i))


def top_weights(cfg: dict, seed) -> dict:
    """The embedding and the final norm.  The head is the embedding's
    transpose, so its entries are normal at ``1/sqrt(d)``, which gives the
    logits the unit spread an untied head drawn at ``1/sqrt(d)`` gives them
    (drawn at 1 the logits would spread over ``sqrt(d)``, 45 at 2048, and
    every comparison of logits would read 45 times coarser); the residual
    stream then starts small and the first layer's output sets its scale,
    as in a trained model with a tied head."""
    m = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    ks = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2), 2)
    return {"embed": (jax.random.normal(ks[0], (m["v"], m["d"]), jnp.float32)
                      * m["d"] ** -0.5).astype(dt),
            "final_norm": _norm_w(ks[1], m["d"], dt)}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                                1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _act(x, precision):
    """A tensor the program holds in its activation type: rounded in the
    control, row by row."""
    if precision == "float32":
        return x
    if precision == "fp8":
        return _fp8(x, -1)
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def _weight(w, precision):
    w = w.astype(jnp.float32)
    return _fp8(w, 0) if precision == "fp8" else w


def _proj(x, w, precision):
    return _act(jnp.dot(_act(x, precision), _weight(w, precision),
                        precision=HI), precision)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """``x`` [T, heads, n]: dimension i pairs with i + n/2; row r stands at
    position r."""
    t, _, n = x.shape
    half = n // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def short_conv(m: dict, u, w, precision):
    """The gated short convolution over one sequence ``u`` [T, d]."""
    t, d = u.shape
    gate_b, gate_c, x = jnp.split(_proj(u, w["w_in"], precision), 3, axis=-1)
    z = _act(gate_b * x, precision)
    zp = jnp.concatenate([jnp.zeros((m["K"] - 1, d), jnp.float32), z])
    taps = w["conv_w"].astype(jnp.float32)
    c = sum(taps[j] * zp[j:j + t] for j in range(m["K"]))     # shifted copies
    return _proj(_act(gate_c * _act(c, precision), precision), w["w_out"],
                 precision)


def attention(m: dict, u, w, precision):
    """Grouped-query attention over one sequence ``u`` [T, d], causal."""
    t = u.shape[0]
    h, kvh, hd = m["h"], m["kvh"], m["hd"]
    q = _proj(u, w["wq"], precision).reshape(t, h, hd)
    k = _proj(u, w["wk"], precision).reshape(t, kvh, hd)
    v = _proj(u, w["wv"], precision).reshape(t, kvh, hd)
    q = _act(_rope(_act(_rms(q, w["q_norm"], m["eps"]), precision),
                   m["theta"]), precision)
    k = _act(_rope(_act(_rms(k, w["k_norm"], m["eps"]), precision),
                   m["theta"]), precision)
    qg = q.reshape(t, kvh, h // kvh, hd)
    s = jnp.einsum("qkrd,mkd->krqm", qg, k, precision=HI) * hd ** -0.5
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("krqm,mkd->qkrd", p, v, precision=HI)
    return _proj(_act(o, precision).reshape(t, h * hd), w["wo"], precision)


def _swiglu(h, w_gate, w_up, w_down, precision):
    g = _act(jax.nn.silu(_proj(h, w_gate, precision)), precision)
    return _proj(_act(g * _proj(h, w_up, precision), precision), w_down,
                 precision)


def route(m: dict, h, w, precision):
    """The experts each token chose ([T, k]) and their weights; the router in
    float32, the bias in the choice only."""
    s = jax.nn.sigmoid(jnp.dot(_act(h, precision),
                               _weight(w["w_router"], precision),
                               precision=HI))
    _, experts = lax.top_k(s + w["router_bias"], m["k"])
    picked = jnp.take_along_axis(s, experts, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + ROUTE_NORM_EPS)
    return experts, weights * m["route_scale"]


def moe(m: dict, h, w, precision):
    """Every token through every expert, weighted 0 where it did not choose
    it; also the choices."""
    experts, weights = route(m, h, w, precision)

    def one(y, j):
        wt = jnp.sum(jnp.where(experts == j, weights, 0.0), -1)
        out = _swiglu(h, w["e_gate"][j], w["e_up"][j], w["e_down"][j],
                      precision)
        return y + wt[:, None] * out, None

    y, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(m["e"]))
    return _act(y, precision), experts


def layer(cfg: dict, kind: tuple, x, w: dict, precision: str = "float32",
          aux: bool = False):
    """A layer of ``kind`` (:func:`layer_kind`) over one sequence ``x``
    [T, d] (float32), causal.  With ``aux`` also the experts each token chose
    ([T, k], or ``None`` in a dense layer)."""
    m = _dims(cfg)
    op, ffn = kind
    u = _act(_rms(x, w["op_norm"], m["eps"]), precision)
    o = (short_conv if op == "conv" else attention)(m, u, w, precision)
    x = _act(x + o, precision)
    h = _act(_rms(x, w["ffn_norm"], m["eps"]), precision)
    if ffn == "dense":
        y, experts = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"],
                             precision), None
    else:
        y, experts = moe(m, h, w, precision)
    x = _act(x + y, precision)
    return (x, experts) if aux else x


def logits_at(cfg: dict, seed: int, seqs: list, positions: list,
              precision: str = "float32", pad_to: int = 1024) -> list:
    """For each token sequence the logits [n, vocab] at its ``positions``, by
    a full causal pass: layer by layer over all the sequences, each padded at
    its end to a multiple of ``pad_to`` (what follows a position cannot reach
    it)."""
    m = _dims(cfg)
    top = jax.jit(functools.partial(top_weights, cfg))(seed_arg(seed))
    xs = []
    for s in seqs:
        n = -(-len(s) // pad_to) * pad_to
        ids = jnp.asarray(list(s) + [0] * (n - len(s)), jnp.int32)
        xs.append(top["embed"][ids].astype(jnp.float32))
    makers, steps = {}, {}
    for i in range(m["L"]):
        kind = layer_kind(cfg, i)
        if kind not in makers:      # one program per kind of layer
            makers[kind] = jax.jit(functools.partial(_layer_weights, cfg,
                                                     kind))
            steps[kind] = jax.jit(functools.partial(layer, cfg, kind,
                                                    precision=precision))
        w = makers[kind](seed_arg(seed), jnp.int32(i))
        xs = [steps[kind](x, w) for x in xs]
        del w

    @jax.jit
    def head(x, pos, norm, embed):       # weights as arguments, not constants
        return _proj(_act(_rms(x[pos], norm, m["eps"]), precision), embed.T,
                     precision)

    return [head(x, jnp.asarray(p, jnp.int32), top["final_norm"],
                 top["embed"]) for x, p in zip(xs, positions)]


def served_gaps(ref_rows, tokens) -> "jax.Array":
    """How far each token's logit lies below the reference's best, per row:
    0 where the token is the reference's own choice."""
    tok = jnp.asarray(tokens, jnp.int32)
    picked = jnp.take_along_axis(ref_rows, tok[:, None], axis=-1)[:, 0]
    return jnp.max(ref_rows, axis=-1) - picked
