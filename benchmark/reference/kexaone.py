"""The plain reference for the ``kexaone_serve`` family: the language model of
``K-EXAONE-236B-A23B`` (``model_type`` ``exaone_moe``) as its ``config.json``
gives it — a full causal forward pass in straightforward ``jax.numpy`` and
float32, every product at ``Precision.HIGHEST``, with no cache, no batching and
no kernel.  It imports nothing of the program.

**Layer equations** (``x`` [T, d]; no biases anywhere; eps ``rms_norm_eps``).
Each sub-layer reads the residual stream as it is and its *output* is normed
before it is added: ``x = x + RMSNorm_attn(Attn(x))``, ``x = x +
RMSNorm_ffn(FFN(x))``.

* *Attention*: q ``d -> heads x head_dim``, k and v ``d -> kv_heads x
  head_dim``; RMSNorm over the ``head_dim`` of each q and k head (own
  weights); on ``sliding_attention`` layers only, rotary (half-split pairs,
  ``rope_parameters.rope_theta``) on all of it; causal softmax at ``1 /
  sqrt(head_dim)``, and on sliding layers key ``j`` visible to query ``t`` iff
  ``t - j < sliding_window`` — written as a band mask; output ``heads x
  head_dim -> d``.
* *FFN*, the first ``first_k_dense_replace`` layers: ``W2(silu(W1 x) * W3 x)``
  of width ``intermediate_size``.  After them: ``s = sigmoid(x W_r)`` over the
  published ``num_experts``; the ``num_experts_per_tok`` largest of ``s + b``
  (``b`` the selection bias: in the choice only); weights ``s_chosen / sum
  s_chosen`` (``norm_topk_prob``) times ``routed_scaling_factor``; each expert
  a SwiGLU of width ``moe_intermediate_size``, computed here as a loop over
  the experts held with a mask; plus one shared expert of the same width.
* Final RMSNorm, then the untied head.

``assumed`` (the config gives only ``rms_norm_eps`` for its norms, and no
``exaone_moe`` modelling code is on this machine): the norm placement, the q/k
norm and the rotary on sliding layers only are the EXAONE 4.0 family's
(``transformers/models/exaone4/modeling_exaone4.py``); the selection bias is
the DeepSeek-V3 router's that the config's keys name; the window counts the
query's own position; ``torch_dtype`` bfloat16.  The multi-token-prediction
layer is not part of the next-token forward pass and is not here.

**Departures from the published description**, each the configuration file's:
``num_experts`` counts the experts *held* (``held_experts_first`` on; the
router stays ``num_experts_published`` wide), ``vocab_size`` the rows of the
embedding and the head held (``vocab_first_row`` on), ``num_hidden_layers``
the layers run (the first of ``layer_types``).  What the absent experts would
add is left out.  Long sequences are computed in blocks (queries of a layer's
attention, tokens of its feed-forward part), which changes no number.

It makes the weights itself, from the seed, one layer at a time, in the type
the configuration states (bfloat16), and upcasts them: matrices ``[in, out]``
normal at ``1/sqrt(in)``, the embedding normal at 1, the head normal at
``1/sqrt(d)``, norm weights uniform in [0.5, 1.5], the selection bias uniform
in [-0.05, 0.05] (float32).  An expert's and a vocabulary row's weights depend
on its own index alone, so the shares of one seed tile the uncut model.
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
FFN_BLOCK = 4096            # tokens of a feed-forward part computed at once


def seed_arg(seed: int):
    """The seed as an argument of a jitted maker (not a constant in it)."""
    return np.uint32(int(seed) % 2**32)


def _dims(cfg: dict) -> dict:
    n_layers = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["layer_types"][:n_layers])
    if len(kinds) != n_layers or set(kinds) - {"full_attention",
                                               "sliding_attention"}:
        raise ValueError("layer_types has to name every layer's kind")
    held = int(cfg["num_experts"])
    return {
        "d": int(cfg["hidden_size"]), "L": n_layers, "kinds": kinds,
        "dense": int(cfg["first_k_dense_replace"]),
        "f": int(cfg["intermediate_size"]),
        "h": int(cfg["num_attention_heads"]),
        "kvh": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "window": int(cfg["sliding_window"]),
        "e_held": held, "e_first": int(cfg.get("held_experts_first", 0)),
        "e_all": int(cfg.get("num_experts_published", held)),
        "ef": int(cfg["moe_intermediate_size"]),
        "k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["num_shared_experts"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "v_rows": int(cfg["vocab_size"]),
        "v_first": int(cfg.get("vocab_first_row", 0)),
        "eps": float(cfg["rms_norm_eps"]),
    }


def layer_kind(cfg: dict, i: int) -> tuple:
    """``("sliding" | "full", "dense" | "moe")`` of layer ``i``."""
    m = _dims(cfg)
    return ("full" if m["kinds"][i] == "full_attention" else "sliding",
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
    w = {"attn_norm": _norm_w(next(ks), d, dt),
         "ffn_norm": _norm_w(next(ks), d, dt),
         "wq": _mat(next(ks), d, m["h"] * hd, dt),
         "wk": _mat(next(ks), d, m["kvh"] * hd, dt),
         "wv": _mat(next(ks), d, m["kvh"] * hd, dt),
         "q_norm": _norm_w(next(ks), hd, dt),
         "k_norm": _norm_w(next(ks), hd, dt),
         "wo": _mat(next(ks), m["h"] * hd, d, dt)}
    if kind[1] == "dense":
        w.update(w_gate=_mat(next(ks), d, m["f"], dt),
                 w_up=_mat(next(ks), d, m["f"], dt),
                 w_down=_mat(next(ks), m["f"], d, dt))
        return w
    # the router is as wide as published; its selection bias is float32
    w["w_router"] = _mat(next(ks), d, m["e_all"], dt)
    w["router_bias"] = jax.random.uniform(
        next(ks), (m["e_all"],), jnp.float32, -0.05, 0.05)
    k_exp = next(ks)

    def expert(e):          # an expert's weights depend on its index alone
        k3 = jax.random.split(jax.random.fold_in(k_exp, e), 3)
        return (_mat(k3[0], d, m["ef"], dt), _mat(k3[1], d, m["ef"], dt),
                _mat(k3[2], m["ef"], d, dt))

    # one expert at a time: sixteen of 75 MB drawn at once in float32 would
    # not fit beside the layers already made
    w["e_gate"], w["e_up"], w["e_down"] = lax.map(
        expert, m["e_first"] + jnp.arange(m["e_held"]))
    sf = m["shared"] * m["ef"]
    w.update(s_gate=_mat(next(ks), d, sf, dt), s_up=_mat(next(ks), d, sf, dt),
             s_down=_mat(next(ks), sf, d, dt))
    return w


def layer_weights(cfg: dict, seed, i: int) -> dict:
    """Layer ``i``'s weights from the seed.  ``i`` is a Python int (it decides
    the layer's kind, so its shapes)."""
    return _layer_weights(cfg, layer_kind(cfg, int(i)), seed, jnp.int32(i))


def top_weights(cfg: dict, seed) -> dict:
    """The held rows of the embedding (normal at 1) and of the head (normal at
    ``1/sqrt(d)``), each row from its own index, and the final norm."""
    m = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    ks = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2), 3)
    rows = m["v_first"] + jnp.arange(m["v_rows"])

    def row(k, r, scale):
        return (jax.random.normal(jax.random.fold_in(k, r), (m["d"],),
                                  jnp.float32) * scale).astype(dt)

    return {"embed": jax.vmap(lambda r: row(ks[0], r, 1.0))(rows),
            "final_norm": _norm_w(ks[1], m["d"], dt),
            "lm_head": jax.vmap(lambda r: row(ks[2], r, m["d"] ** -0.5)
                                )(rows).T}


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


def _blocks(fn, n: int, block: int):
    """``fn(start)`` over ``range(0, n, block)``, the outcomes side by side
    along their first axis (``block`` divides ``n``, or is ``n``)."""
    out = lax.map(fn, jnp.arange(0, n, block))
    return jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), out)


def attention(m: dict, kind: str, x, w, precision, q_block: int):
    """Grouped-query attention over one sequence ``x`` [T, d], causal; a
    ``sliding`` layer rotates q and k and masks the band.  Queries are taken
    ``q_block`` at a time: a block of a full layer over every key, a block of
    a sliding layer over the ``window - 1`` positions before it and its own
    (the band mask over the rest is all false)."""
    t = x.shape[0]
    h, kvh, hd, win = m["h"], m["kvh"], m["hd"], m["window"]
    q = _act(_rms(_proj(x, w["wq"], precision).reshape(t, h, hd),
                  w["q_norm"], m["eps"]), precision)
    k = _act(_rms(_proj(x, w["wk"], precision).reshape(t, kvh, hd),
                  w["k_norm"], m["eps"]), precision)
    v = _proj(x, w["wv"], precision).reshape(t, kvh, hd)
    if kind == "sliding":
        q = _act(_rope(q, m["theta"]), precision)
        k = _act(_rope(k, m["theta"]), precision)
    qb = q_block if t % q_block == 0 else t
    # a sliding block's keys: from `back` positions before it (zeros before
    # position 0, masked) to its end
    back = min(win - 1, t) if kind == "sliding" else 0
    span = qb + back if kind == "sliding" else t
    kp = jnp.concatenate([jnp.zeros((back, kvh, hd), jnp.float32), k])
    vp = jnp.concatenate([jnp.zeros((back, kvh, hd), jnp.float32), v])

    def block(start):
        lo = start if kind == "sliding" else 0      # first key, less `back`
        qg = lax.dynamic_slice_in_dim(q, start, qb).reshape(
            qb, kvh, h // kvh, hd)
        kk = lax.dynamic_slice_in_dim(kp, lo, span)
        vv = lax.dynamic_slice_in_dim(vp, lo, span)
        s = jnp.einsum("qkrd,mkd->krqm", qg, kk, precision=HI) * hd ** -0.5
        qpos = start + jnp.arange(qb)[:, None]
        kpos = lo - back + jnp.arange(span)[None, :]
        seen = (kpos >= 0) & (kpos <= qpos)
        if kind == "sliding":
            seen = seen & (qpos - kpos < win)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("krqm,mkd->qkrd", p, vv, precision=HI)

    o = _blocks(block, t, qb)
    return _proj(_act(o, precision).reshape(t, h * hd), w["wo"], precision)


def _swiglu(x, w_gate, w_up, w_down, precision):
    g = _act(jax.nn.silu(_proj(x, w_gate, precision)), precision)
    return _proj(_act(g * _proj(x, w_up, precision), precision), w_down,
                 precision)


def route(m: dict, x, w, precision):
    """The experts each token chose ([T, k] indices over the published
    router) and their weights; the router in float32, the bias in the choice
    only."""
    s = jax.nn.sigmoid(jnp.dot(_act(x, precision),
                               _weight(w["w_router"], precision),
                               precision=HI))
    _, experts = lax.top_k(s + w["router_bias"], m["k"])
    picked = jnp.take_along_axis(s, experts, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return experts, weights * m["route_scale"]


def moe(m: dict, x, w, precision, shared: bool = True):
    """What the held experts add for the tokens that chose them (every token
    through every held expert, weighted 0 where it did not choose it), plus
    the shared expert; also the choices."""
    experts, weights = route(m, x, w, precision)

    def one(y, j):
        wt = jnp.sum(jnp.where(experts == m["e_first"] + j, weights, 0.0), -1)
        out = _swiglu(x, w["e_gate"][j], w["e_up"][j], w["e_down"][j],
                      precision)
        return y + wt[:, None] * out, None

    y, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(m["e_held"]))
    y = _act(y, precision)
    if shared and m["shared"]:
        y = _act(y + _swiglu(x, w["s_gate"], w["s_up"], w["s_down"],
                             precision), precision)
    return y, experts


def layer(cfg: dict, kind: tuple, x, w: dict, precision: str = "float32",
          q_block: int = 256, aux: bool = False):
    """A layer of ``kind`` (:func:`layer_kind`) over one sequence ``x``
    [T, d] (float32), causal.  With ``aux`` also the experts each token chose
    ([T, k], or ``None`` in a dense layer)."""
    m = _dims(cfg)
    op, ffn = kind
    t = x.shape[0]
    o = attention(m, op, x, w, precision, q_block)
    x = _act(x + _act(_rms(o, w["attn_norm"], m["eps"]), precision),
             precision)
    fb = FFN_BLOCK if t % FFN_BLOCK == 0 else t

    def ffn_block(start):           # a feed-forward part is per token
        xb = lax.dynamic_slice_in_dim(x, start, fb)
        if ffn == "dense":
            return _swiglu(xb, w["w_gate"], w["w_up"], w["w_down"],
                           precision), jnp.zeros((fb, 0), jnp.int32)
        return moe(m, xb, w, precision)

    y, experts = _blocks(ffn_block, t, fb)
    x = _act(x + _act(_rms(y, w["ffn_norm"], m["eps"]), precision),
             precision)
    return (x, experts if ffn == "moe" else None) if aux else x


def logits_at(cfg: dict, seed: int, seqs: list, positions: list,
              precision: str = "float32", pad_to: int = 1024) -> list:
    """For each token sequence (ids within the held slice) the logits
    [n, rows held] at its ``positions``, by a full causal pass: layer by
    layer over all the sequences, each padded at its end to a multiple of
    ``pad_to`` (what follows a position cannot reach it)."""
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
        w = makers[kind](seed_arg(seed), jnp.int32(i))
        for j, x in enumerate(xs):
            # a block's scores are heads x q_block x keys in float32
            qb = 128 if x.shape[0] > 16384 else 256
            if (kind, qb) not in steps:
                steps[kind, qb] = jax.jit(functools.partial(
                    layer, cfg, kind, precision=precision, q_block=qb))
            xs[j] = steps[kind, qb](x, w)
        del w

    @jax.jit
    def head(x, pos, norm, lm_head):     # weights as arguments, not constants
        return _proj(_act(_rms(x[pos], norm, m["eps"]), precision), lm_head,
                     precision)

    return [head(x, jnp.asarray(p, jnp.int32), top["final_norm"],
                 top["lm_head"]) for x, p in zip(xs, positions)]


def served_gaps(ref_rows, tokens) -> "jax.Array":
    """How far each token's logit lies below the reference's best, per row:
    0 where the token is the reference's own choice."""
    tok = jnp.asarray(tokens, jnp.int32)
    picked = jnp.take_along_axis(ref_rows, tok[:, None], axis=-1)[:, 0]
    return jnp.max(ref_rows, axis=-1) - picked
