"""The plain reference for the ``dots3_serve`` family: the language model of
``dots3-note-prev`` (``model_type`` ``dots3_note``) as its ``config.json``
gives it — a full causal forward pass in straightforward ``jax.numpy`` and
float32, every product at ``Precision.HIGHEST``, with no cache, no batching
and no kernel.  It imports nothing of the program.

Three kinds of layer in one model (``layer_types``, ``first_k_dense_replace``):

* **full attention**: multi-head latent attention (queries through a rank
  ``q_lora_rank`` latent, keys and values through a rank ``kv_lora_rank``
  latent plus one rotary key shared by all heads) over the keys a **learned
  sparse indexer** selects: ``I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])``
  over ``index_n_heads`` heads, the exact ``index_topk`` largest ``s <= t``;
  a sigmoid gate per head on the attention output;
* **sliding attention**: the same latent attention with the ``swa_*`` sizes,
  no indexer, over the query's own position and the ``sliding_window_size - 1``
  before it;
* the feed-forward part: SwiGLU in the first ``first_k_dense_replace`` layers,
  after them ``n_routed_experts`` sigmoid-routed experts (``noaux_tc``: the
  bias ``b`` only chooses, the weights are ``s_e / sum_sel s``), top
  ``num_experts_per_tok``, plus one shared expert.

The expanded form is used throughout (keys and values of every head made from
the latents); the program may fold ``W_kvb`` into the query, which is the same
mathematics.

**The share.**  The configuration is one chip's share of a deployment that
splits the experts and the vocabulary: ``n_routed_experts`` counts the experts
held (``held_experts_first`` ...), the router stays ``n_routed_experts_published``
wide, and ``vocab_size`` rows of the embedding and the head from
``vocab_first_row`` on.  What the absent experts would add is left out and the
partial result goes on to the next layer.  An expert's and a row's weights
depend on its own index only, so the shares of one seed tile the uncut model.

``assumed`` (the config names these and does not define them): (a)
``apply_mla_qkv_lora_rescale``: the normed latents ``c_q`` and ``c_kv`` are
multiplied by ``sqrt(hidden_size / rank)``, in both kinds of layer; (b) a
headwise gate is ``sigmoid(h W_g)`` of the normed layer input with its own
``[hidden_size, heads]`` matrix; (c) a window of 513 is the query's own
position and the 512 before it.  The indexer follows the published
DeepSeek-V3.2-Exp inference code without its Hadamard rotation and fp8
quantisation (precision devices).  No multi-token prediction, no towers.

It makes the weights itself, from the seed, one layer at a time, in the type
the configuration states (bfloat16), and upcasts them.  ``precision="fp8"`` is
the **control**: every tensor the program holds in bfloat16 rounded to
float8_e4m3fn instead (weights per output channel, activations per row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
LN_EPS = 1e-6           # the indexer's LayerNorm (DeepSeek-V3.2-Exp's default)


def seed_arg(seed: int):
    """The seed as an argument of a jitted maker (not a constant in it)."""
    return np.uint32(int(seed) % 2**32)


def _dims(cfg: dict) -> dict:
    n_layers = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["layer_types"][:n_layers])
    if len(kinds) != n_layers or set(kinds) - {"full_attention",
                                               "sliding_attention"}:
        raise ValueError("layer_types has to name every layer's kind")
    held = int(cfg["n_routed_experts"])
    return {
        "d": int(cfg["hidden_size"]), "L": n_layers, "kinds": kinds,
        "dense": int(cfg["first_k_dense_replace"]),
        "f": int(cfg["intermediate_size"]),
        "full": {"h": int(cfg["num_attention_heads"]),
                 "qr": int(cfg["q_lora_rank"]),
                 "kr": int(cfg["kv_lora_rank"]),
                 "nope": int(cfg["qk_nope_head_dim"]),
                 "rope": int(cfg["qk_rope_head_dim"]),
                 "v": int(cfg["v_head_dim"]),
                 "theta": float(cfg["rope_theta"])},
        "swa": {"h": int(cfg["swa_num_attention_heads"]),
                "qr": int(cfg["swa_q_lora_rank"]),
                "kr": int(cfg["swa_kv_lora_rank"]),
                "nope": int(cfg["swa_qk_nope_head_dim"]),
                "rope": int(cfg["swa_qk_rope_head_dim"]),
                "v": int(cfg["swa_v_head_dim"]),
                "theta": float(cfg["swa_rope_theta"])},
        "ih": int(cfg["index_n_heads"]), "id": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
        "window": int(cfg["sliding_window_size"]),
        "e_held": held, "e_first": int(cfg.get("held_experts_first", 0)),
        "e_all": int(cfg.get("n_routed_experts_published", held)),
        "ef": int(cfg["moe_intermediate_size"]),
        "k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["n_shared_experts"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "v_rows": int(cfg["vocab_size"]),
        "v_first": int(cfg.get("vocab_first_row", 0)),
        "eps": float(cfg["rms_norm_eps"]),
        "rescale": bool(cfg["apply_mla_qkv_lora_rescale"]),
    }


def layer_kind(cfg: dict, i: int) -> tuple:
    """``("full" | "swa", "dense" | "moe")`` of layer ``i``."""
    m = _dims(cfg)
    return ("full" if m["kinds"][i] == "full_attention" else "swa",
            "dense" if i < m["dense"] else "moe")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _mat(key, n_in, n_out, dt, scale=1.0):
    return (jax.random.normal(key, (n_in, n_out), jnp.float32)
            * (n_in ** -0.5 * scale)).astype(dt)


def _norm_w(key, n, dt):
    return jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5).astype(dt)


def _layer_weights(cfg: dict, kind: tuple, seed, i) -> dict:
    m = _dims(cfg)
    d = m["d"]
    dt = jnp.dtype(cfg["torch_dtype"])
    a = m[kind[0]]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), 1), i)
    ks = iter(jax.random.split(key, 32))
    hq = a["h"] * (a["nope"] + a["rope"])
    # the matrices that read a rescaled latent are drawn smaller by the
    # rescale, as trained ones would have become: queries, keys and values
    # then have unit scale and the attention scores a spread of about one.
    # Drawn at 1/sqrt(fan_in) like the rest, the rescale (assumed, a) makes
    # the scores seven times wider, attention all but picks a single key,
    # and one key that bfloat16 selects otherwise moves a whole position
    # (measured: PERF.md, Findings, PR 27).
    sq = (d / a["qr"]) ** -0.5 if m["rescale"] else 1.0
    skv = (d / a["kr"]) ** -0.5 if m["rescale"] else 1.0
    w = {"attn_norm": _norm_w(next(ks), d, dt),
         "w_qa": _mat(next(ks), d, a["qr"], dt),
         "q_norm": _norm_w(next(ks), a["qr"], dt),
         "w_qb": _mat(next(ks), a["qr"], hq, dt, sq),
         "w_kva": _mat(next(ks), d, a["kr"] + a["rope"], dt),
         "kv_norm": _norm_w(next(ks), a["kr"], dt),
         "w_kvb": _mat(next(ks), a["kr"], a["h"] * (a["nope"] + a["v"]), dt,
                       skv),
         "w_o": _mat(next(ks), a["h"] * a["v"], d, dt),
         "w_g": _mat(next(ks), d, a["h"], dt),
         "mlp_norm": _norm_w(next(ks), d, dt)}
    if kind[0] == "full":
        w.update(
            w_iq=_mat(next(ks), a["qr"], m["ih"] * m["id"], dt, sq),
            w_ik=_mat(next(ks), d, m["id"], dt),
            ik_norm_w=_norm_w(next(ks), m["id"], dt),
            ik_norm_b=(jax.random.normal(next(ks), (m["id"],), jnp.float32)
                       * 0.1).astype(dt),
            w_iw=_mat(next(ks), d, m["ih"], dt))
    if kind[1] == "dense":
        w.update(w_gate=_mat(next(ks), d, m["f"], dt),
                 w_up=_mat(next(ks), d, m["f"], dt),
                 w_down=_mat(next(ks), m["f"], d, dt))
        return w
    # the router is as wide as published; its bias (noaux_tc) is float32,
    # small and not zero, so that it is exercised
    w["w_router"] = _mat(next(ks), d, m["e_all"], dt)
    w["router_bias"] = jax.random.uniform(
        next(ks), (m["e_all"],), jnp.float32, -0.05, 0.05)
    k_exp = next(ks)

    def expert(e):          # an expert's weights depend on its index alone
        k3 = jax.random.split(jax.random.fold_in(k_exp, e), 3)
        return (_mat(k3[0], d, m["ef"], dt), _mat(k3[1], d, m["ef"], dt),
                _mat(k3[2], m["ef"], d, dt))

    w["e_gate"], w["e_up"], w["e_down"] = jax.vmap(expert)(
        m["e_first"] + jnp.arange(m["e_held"]))
    sf = m["shared"] * m["ef"]
    w.update(s_gate=_mat(next(ks), d, sf, dt), s_up=_mat(next(ks), d, sf, dt),
             s_down=_mat(next(ks), sf, d, dt))
    return w


def layer_weights(cfg: dict, seed, i: int) -> dict:
    """Layer ``i``'s weights from the seed.  ``i`` is a Python int (it decides
    the layer's kind, so its shapes); matrices are ``[in, out]``, normal at
    ``1/sqrt(in)``, norm weights uniform in [0.5, 1.5]."""
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


def _rope(x, theta, pos=None):
    """``x`` [T, heads, n]: dimension i pairs with i + n/2; row r stands at
    position ``pos[r]`` (at r without ``pos``)."""
    t, _, n = x.shape
    half = n // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = jnp.arange(t) if pos is None else pos
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _latents(m: dict, a: dict, h, w, precision):
    """``c_q`` [T, qr] and the cached latent: ``c_kv`` [T, kr], ``k_r`` [T, rope]."""
    t = h.shape[0]
    scale_q = (m["d"] / a["qr"]) ** 0.5 if m["rescale"] else 1.0
    scale_kv = (m["d"] / a["kr"]) ** 0.5 if m["rescale"] else 1.0
    c_q = _act(_rms(_proj(h, w["w_qa"], precision), w["q_norm"], m["eps"])
               * scale_q, precision)
    kv = _proj(h, w["w_kva"], precision)
    c_kv = _act(_rms(kv[:, :a["kr"]], w["kv_norm"], m["eps"]) * scale_kv,
                precision)
    k_r = _act(_rope(kv[:, a["kr"]:].reshape(t, 1, a["rope"]), a["theta"]),
               precision)[:, 0]
    return c_q, c_kv, k_r


def index_keys(m: dict, h, w, precision):
    """The indexer's keys ``k_I`` [T, index_head_dim]: LayerNorm of
    ``h W_Ik``, rotary on the first ``qk_rope_head_dim`` dimensions."""
    a = m["full"]
    k_i = _proj(h, w["w_ik"], precision)
    mu = jnp.mean(k_i, axis=-1, keepdims=True)
    var = jnp.mean((k_i - mu) ** 2, axis=-1, keepdims=True)
    k_i = ((k_i - mu) * lax.rsqrt(var + LN_EPS)
           * w["ik_norm_w"].astype(jnp.float32)
           + w["ik_norm_b"].astype(jnp.float32))
    rd = a["rope"]
    return _act(jnp.concatenate(
        [_rope(k_i[:, None, :rd], a["theta"])[:, 0], k_i[:, rd:]], -1),
        precision)


def index_scores(m: dict, h, c_q, k_i, w, precision, rows):
    """``I[t, s]`` for the queries at positions ``rows`` against every key,
    one indexer head at a time; ``-inf`` where ``s > t``."""
    a = m["full"]
    rd = a["rope"]
    q_i = _proj(c_q[rows], w["w_iq"], precision).reshape(-1, m["ih"], m["id"])
    q_i = _act(jnp.concatenate(
        [_rope(q_i[..., :rd], a["theta"], rows), q_i[..., rd:]], -1),
        precision)
    wgt = _proj(h[rows], w["w_iw"], precision) \
        * m["ih"] ** -0.5 * m["id"] ** -0.5

    def head(acc, j):
        s = jnp.dot(q_i[:, j], k_i.T, precision=HI)
        return acc + wgt[:, j, None] * jnp.maximum(s, 0.0), None

    scores, _ = lax.scan(
        head, jnp.zeros((q_i.shape[0], k_i.shape[0]), jnp.float32),
        jnp.arange(m["ih"]))
    seen = jnp.arange(k_i.shape[0])[None, :] <= rows[:, None]
    return jnp.where(seen, _act(scores, precision), -jnp.inf)


def attention(m: dict, kind: str, h, w, precision: str, q_block: int,
              head_block: int):
    """The attention output [T, d] of one layer over one sequence, and for a
    full layer the positions each query selected ([T, topk], -1 where fewer
    are visible)."""
    a = m[kind]
    t = h.shape[0]
    qb = min(q_block, t)
    hb = min(head_block, a["h"])
    if t % qb or a["h"] % hb:
        raise ValueError(f"blocks {qb}, {hb} do not divide {t}, {a['h']}")
    c_q, c_kv, k_r = _latents(m, a, h, w, precision)
    starts = jnp.arange(0, t, qb)
    if kind == "full":
        k_sel = min(m["topk"], t)
        k_i = index_keys(m, h, w, precision)

        def select(start):
            rows = start + jnp.arange(qb)
            sc = index_scores(m, h, c_q, k_i, w, precision, rows)
            val, idx = lax.top_k(sc, k_sel)       # exact; ties: lowest index
            idx = jnp.where(val > -jnp.inf, idx, -1)
            # the block's rows of the mask "query t may see key s" (a -1
            # lands in a column past the end, cut off)
            ok = jnp.zeros((qb, t + 1), bool).at[
                jnp.arange(qb)[:, None], idx].set(True)[:, :t]
            return idx, ok

        selected, visible = lax.map(select, starts)
        selected = selected.reshape(t, k_sel)
        n_keys = t                                  # keys a block scores
    else:
        selected = visible = None
        # a block of queries reaches its own positions and window - 1 before
        n_keys = min(qb + m["window"] - 1, t)
    gate = jax.nn.sigmoid(_proj(h, w["w_g"], precision))          # [T, H]
    w_qb = w["w_qb"].reshape(a["qr"], a["h"], a["nope"] + a["rope"])
    w_kvb = w["w_kvb"].reshape(a["kr"], a["h"], a["nope"] + a["v"])
    scale = (a["nope"] + a["rope"]) ** -0.5

    def heads(h0):
        hs = h0 + jnp.arange(hb)
        q = _proj(c_q, w_qb[:, hs].reshape(a["qr"], -1), precision
                  ).reshape(t, hb, a["nope"] + a["rope"])
        q = _act(jnp.concatenate(
            [q[..., :a["nope"]], _rope(q[..., a["nope"]:], a["theta"])], -1),
            precision)
        kv = _proj(c_kv, w_kvb[:, hs].reshape(a["kr"], -1), precision
                   ).reshape(t, hb, a["nope"] + a["v"])
        k = jnp.concatenate(
            [kv[..., :a["nope"]],
             jnp.broadcast_to(k_r[:, None, :], (t, hb, a["rope"]))], -1)
        v = kv[..., a["nope"]:]

        def block(i):
            rows = starts[i] + jnp.arange(qb)
            first = jnp.clip(starts[i] + qb - n_keys, 0, t - n_keys)
            keys = first + jnp.arange(n_keys)
            ks = lax.dynamic_slice_in_dim(k, first, n_keys)
            vs = lax.dynamic_slice_in_dim(v, first, n_keys)
            s = jnp.einsum("qhd,khd->hqk", q[rows], ks, precision=HI) * scale
            if kind == "full":
                ok = visible[i]
            else:
                ok = ((keys[None, :] <= rows[:, None])
                      & (keys[None, :] > rows[:, None] - m["window"]))
            s = jnp.where(ok[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vs,
                              precision=HI)

        return lax.map(block, jnp.arange(t // qb)).reshape(t, hb, a["v"])

    o = lax.map(heads, jnp.arange(0, a["h"], hb))        # [H/hb, T, hb, v]
    o = jnp.moveaxis(o, 0, 1).reshape(t, a["h"], a["v"])
    o = _act(_act(o, precision) * gate[:, :, None], precision)
    return _proj(o.reshape(t, a["h"] * a["v"]), w["w_o"], precision), selected


def _swiglu(h, w_gate, w_up, w_down, precision):
    g = _act(jax.nn.silu(_proj(h, w_gate, precision)), precision)
    return _proj(_act(g * _proj(h, w_up, precision), precision), w_down,
                 precision)


def route(m: dict, h, w, precision):
    """The experts each token chose ([T, k] indices over the published
    router) and their weights."""
    s = jax.nn.sigmoid(jnp.dot(_act(h, precision),
                               _weight(w["w_router"], precision),
                               precision=HI))
    _, experts = lax.top_k(s + w["router_bias"], m["k"])
    picked = jnp.take_along_axis(s, experts, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return experts, weights * m["route_scale"]


def moe(m: dict, h, w, precision, shared: bool = True, cap: int = 0):
    """What the held experts add for the tokens that chose them, plus the
    shared expert; also the choices and whether ``cap`` was too small.

    With ``cap`` 0 every token goes through every held expert, weighted 0
    where it did not choose it.  With a ``cap`` each expert takes the (at most
    ``cap``) tokens that chose it, which is the same sum at a fraction of the
    products; where more than ``cap`` chose one the result is wrong and the
    flag says so (:func:`logits_at` then takes the other way: nothing is ever
    dropped)."""
    t = h.shape[0]
    experts, weights = route(m, h, w, precision)

    def every_token(y, j):
        wt = jnp.sum(jnp.where(experts == m["e_first"] + j, weights, 0.0), -1)
        out = _swiglu(h, w["e_gate"][j], w["e_up"][j], w["e_down"][j],
                      precision)
        return y + wt[:, None] * out, jnp.bool_(False)

    def its_tokens(y, j):
        chose = jnp.any(experts == m["e_first"] + j, axis=-1)
        wt = jnp.sum(jnp.where(experts == m["e_first"] + j, weights, 0.0), -1)
        rows = jnp.nonzero(chose, size=cap, fill_value=t)[0]
        out = _swiglu(h[jnp.minimum(rows, t - 1)], w["e_gate"][j],
                      w["e_up"][j], w["e_down"][j], precision)
        out = out * wt[jnp.minimum(rows, t - 1)][:, None]
        return y.at[rows].add(out, mode="drop"), jnp.sum(chose) > cap

    y, over = lax.scan(its_tokens if cap else every_token, jnp.zeros_like(h),
                       jnp.arange(m["e_held"]))
    if shared and m["shared"]:
        y = y + _swiglu(h, w["s_gate"], w["s_up"], w["s_down"], precision)
    return _act(y, precision), experts, jnp.any(over)


def layer(cfg: dict, kind: tuple, x, w: dict, precision: str = "float32",
          q_block: int = 512, head_block: int = 16, aux: bool = False,
          cap: int = 0):
    """A layer of ``kind`` (:func:`layer_kind`) over one sequence ``x``
    [T, d] (float32), causal.  With ``aux`` also what the discrete parts
    chose: ``selected`` [T, topk] (full layers), ``experts`` [T, k] (expert
    layers) and ``over`` (:func:`moe`'s ``cap`` was too small)."""
    m = _dims(cfg)
    kind, ffn = kind
    h = _act(_rms(x, w["attn_norm"], m["eps"]), precision)
    o, selected = attention(m, kind, h, w, precision, q_block, head_block)
    x = _act(x + o, precision)
    h = _act(_rms(x, w["mlp_norm"], m["eps"]), precision)
    if ffn == "dense":
        y, experts, over = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"],
                                   precision), None, jnp.bool_(False)
    else:
        y, experts, over = moe(m, h, w, precision, cap=cap)
    x = _act(x + y, precision)
    aux_out = {"selected": selected, "experts": experts, "over": over}
    return (x, aux_out) if aux else x


def logits_at(cfg: dict, seed: int, seqs: list, positions: list,
              precision: str = "float32", pad_to: int = 1024,
              q_block: int = 512, head_block: int = 16) -> list:
    """For each token sequence (ids within the held slice) the logits
    [n, rows held] at its ``positions``, by a full causal pass: layer by
    layer over all the sequences, each padded at its end to a multiple of
    ``pad_to`` (what follows a position cannot reach it)."""
    m = _dims(cfg)
    top = jax.jit(functools.partial(top_weights, cfg))(seed_arg(seed))
    qb = q_block if pad_to % q_block == 0 else pad_to
    xs = []
    for s in seqs:
        n = -(-len(s) // pad_to) * pad_to
        ids = jnp.asarray(list(s) + [0] * (n - len(s)), jnp.int32)
        xs.append(top["embed"][ids].astype(jnp.float32))
    makers, steps = {}, {}

    def step(kind, cap):            # one program per kind of layer and cap
        if (kind, cap) not in steps:
            steps[kind, cap] = jax.jit(functools.partial(
                layer, cfg, kind, precision=precision, q_block=qb,
                head_block=head_block, aux=True, cap=cap))
        return steps[kind, cap]

    for i in range(m["L"]):
        kind = layer_kind(cfg, i)
        if kind not in makers:
            makers[kind] = jax.jit(functools.partial(_layer_weights, cfg,
                                                     kind))
        w = makers[kind](seed_arg(seed), jnp.int32(i))
        for k, x in enumerate(xs):
            # an expert's tokens: at most an eighth of a long sequence (four
            # times an even share); where one has more, every token instead
            cap = x.shape[0] // 8 if x.shape[0] >= 4096 else 0
            y, aux = step(kind, cap)(x, w)
            if bool(aux["over"]):
                y, _ = step(kind, 0)(x, w)
            xs[k] = y
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
