"""The plain reference for the ``llama_serve`` family at Mistral-7B's
architecture: pre-norm decoder blocks with RMSNorm, grouped-query attention
with rotary embeddings (half-split pairs, as the published checkpoints),
SwiGLU, an untied head — a full causal forward pass in straightforward
``jax.numpy`` and float32, every product at ``Precision.HIGHEST``, with no
cache, no batching and no kernel.  It imports nothing of the program.

It makes the weights itself, from the seed, one layer at a time (the family
stacks the same layers for the program), in the type the configuration
states (bfloat16) and upcasts them; so a layer-by-layer pass over a few
sequences fits beside nothing else and needs only one layer's weights.

``precision="fp8"`` is the **control**: the same pass with every tensor the
program holds in bfloat16 — the weights (scaled per output channel) and,
scaled per row, the residual stream, the outputs of the norms, of every
projection, of the rotation and of attention — rounded to float8_e4m3fn, the
nearest precision below the configuration's bfloat16.
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


def _dims(cfg: dict) -> dict:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg["head_dim"])
    if hd * h != d:
        raise ValueError("head_dim x heads has to be the hidden size")
    return {"d": d, "h": h, "kv": int(cfg["num_key_value_heads"]), "hd": hd,
            "f": int(cfg["intermediate_size"]), "v": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"])}


def layer_weights(cfg: dict, seed, i) -> dict:
    """Layer ``i``'s weights from the seed; ``i`` may be traced.  Matrices
    are ``[in, out]``, normal at ``1/sqrt(in)``; norm weights uniform in
    [0.5, 1.5]."""
    m = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), 1), i)
    ks = jax.random.split(key, 9)
    kd = m["kv"] * m["hd"]

    def mat(k, n_in, n_out):
        return (jax.random.normal(k, (n_in, n_out), jnp.float32)
                * n_in ** -0.5).astype(dt)

    def norm(k):
        return jax.random.uniform(k, (m["d"],), jnp.float32, 0.5, 1.5
                                  ).astype(dt)

    return {"attn_norm": norm(ks[0]), "wq": mat(ks[1], m["d"], m["d"]),
            "wk": mat(ks[2], m["d"], kd), "wv": mat(ks[3], m["d"], kd),
            "wo": mat(ks[4], m["d"], m["d"]), "mlp_norm": norm(ks[5]),
            "w_gate": mat(ks[6], m["d"], m["f"]),
            "w_up": mat(ks[7], m["d"], m["f"]),
            "w_down": mat(ks[8], m["f"], m["d"])}


def top_weights(cfg: dict, seed) -> dict:
    """Embedding (normal at 1), final norm, head (normal at ``1/sqrt(d)``)."""
    m = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    ks = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2), 3)
    return {
        "embed": jax.random.normal(ks[0], (m["v"], m["d"]), jnp.float32
                                   ).astype(dt),
        "final_norm": jax.random.uniform(ks[1], (m["d"],), jnp.float32,
                                         0.5, 1.5).astype(dt),
        "lm_head": (jax.random.normal(ks[2], (m["d"], m["v"]), jnp.float32)
                    * m["d"] ** -0.5).astype(dt)}


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


def _proj(x, w, precision):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        w = _fp8(w, 0)
    return _act(jnp.dot(_act(x, precision), w, precision=HI), precision)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """``x`` [T, heads, hd]: dimension i pairs with i + hd/2."""
    t, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(cfg: dict, x, w: dict, precision: str = "float32",
          q_block: int = 1024):
    """One decoder block over one sequence ``x`` [T, d] (float32), causal,
    the queries taken ``q_block`` rows at a time so that the scores fit."""
    m = _dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    t = x.shape[0]
    h = _act(_rms(x, w["attn_norm"], eps), precision)
    q = _act(_rope(_proj(h, w["wq"], precision).reshape(t, m["h"], m["hd"]),
                   theta), precision)
    k = _act(_rope(_proj(h, w["wk"], precision).reshape(t, m["kv"], m["hd"]),
                   theta), precision)
    v = _proj(h, w["wv"], precision).reshape(t, m["kv"], m["hd"])
    rep = m["h"] // m["kv"]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qb = min(q_block, t)
    if t % qb:
        raise ValueError(f"sequence {t} is not a multiple of {qb}")

    def attend(start):
        qs = lax.dynamic_slice_in_dim(q, start, qb, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qs, k, precision=HI) * m["hd"] ** -0.5
        rows = start + jnp.arange(qb)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = _act(lax.map(attend, jnp.arange(0, t, qb)).reshape(t, m["d"]),
             precision)
    x = _act(x + _proj(o, w["wo"], precision), precision)
    h = _act(_rms(x, w["mlp_norm"], eps), precision)
    gate = _act(jax.nn.silu(_proj(h, w["w_gate"], precision)), precision)
    up = _proj(h, w["w_up"], precision)
    return _act(x + _proj(_act(gate * up, precision), w["w_down"],
                          precision), precision)


def logits_at(cfg: dict, seed: int, seqs: list, positions: list,
              precision: str = "float32", pad_to: int = 1024) -> list:
    """For each token sequence (a list of ints) the logits [n, V] at its
    ``positions``, by a full causal pass: layer by layer over all the
    sequences, each padded at its end to a multiple of ``pad_to`` (what
    follows a position cannot reach it)."""
    m = _dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    top = jax.jit(functools.partial(top_weights, cfg))(seed_arg(seed))
    weights = jax.jit(functools.partial(layer_weights, cfg))
    q_block = pad_to if pad_to <= 2048 else 1024     # has to divide pad_to
    step = jax.jit(functools.partial(layer, cfg, precision=precision,
                                     q_block=q_block))
    xs = []
    for s in seqs:
        n = -(-len(s) // pad_to) * pad_to
        ids = jnp.asarray(list(s) + [0] * (n - len(s)), jnp.int32)
        xs.append(top["embed"][ids].astype(jnp.float32))
    for i in range(m["L"]):
        w = weights(seed_arg(seed), jnp.int32(i))
        xs = [step(x, w) for x in xs]

    @jax.jit
    def head(x, pos, norm, lm_head):     # weights as arguments, not constants
        return _proj(_act(_rms(x[pos], norm, eps), precision), lm_head,
                     precision)

    return [head(x, jnp.asarray(p, jnp.int32), top["final_norm"],
                 top["lm_head"]) for x, p in zip(xs, positions)]


def served_gaps(ref_rows, tokens) -> "jax.Array":
    """How far each token's logit lies below the reference's best, per row:
    0 where the token is the reference's own choice."""
    tok = jnp.asarray(tokens, jnp.int32)
    picked = jnp.take_along_axis(ref_rows, tok[:, None], axis=-1)[:, 0]
    return jnp.max(ref_rows, axis=-1) - picked
