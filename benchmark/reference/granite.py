"""The plain reference for the ``granite_serve`` family: the language model of
``granite-4.0-h-small`` (``model_type`` ``granitemoehybrid``) as its
``config.json`` gives it and ``transformers``' modelling code of the family
computes it — a full causal forward pass in straightforward ``jax.numpy`` and
float32, every product at ``Precision.HIGHEST``, with no cache, no batching
and no kernel: the state-space layers are the recurrence itself, token by
token.  It imports nothing of the program.

**Layer equations** (``x`` [T, d]; pre-norm, eps ``rms_norm_eps``; no biases
but the convolution's)::

    x = E[ids] * embedding_multiplier
    x = x + residual_multiplier * Mixer_i(RMSNorm(x))
    h = RMSNorm(x);  x = x + residual_multiplier * (Routed(h) + Shared(h))
    logits = RMSNorm(x) E^T / logits_scaling            (tie_word_embeddings)

* *State-space mixer* (``layer_types[i] == "mamba"``): ``[z | xBC | dt] = u
  W_in`` with widths ``inner = mamba_expand x hidden_size = mamba_n_heads x
  mamba_d_head``, ``inner + 2 x mamba_n_groups x mamba_d_state``,
  ``mamba_n_heads``; ``xBC_t = silu(b_c + sum_j w_c[:, j] xBC_{t-(K-1)+j})``
  (depthwise, causal, ``K = mamba_d_conv``, zeros before position 0); split
  into ``x_t`` [H, P], ``B_t`` [N], ``C_t`` [N] (one group: every head shares
  ``B`` and ``C``); ``dt_t = softplus(dt_t + dt_bias)`` (``time_step_limit``
  (0, inf): no clamp); ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; ``y = RMSNorm_w(y
  * silu(z))`` over all of ``inner`` (gate first, then the norm, one group);
  out ``y W_out``.
* *Attention* (``"attention"``): q ``d -> heads x head_dim``, k and v ``d ->
  kv_heads x head_dim``, no rotary (``position_embedding_type`` ``nope``),
  causal softmax at ``attention_multiplier``, output ``heads x head_dim -> d``.
* *Routed*: ``l = h W_r`` over the published ``num_local_experts``; the
  ``num_experts_per_tok`` largest ``l`` chosen, weights a softmax over the
  chosen; expert ``e``: ``[a | b] = h W_in,e`` (``2 x intermediate_size``
  wide), ``(silu(a) * b) W_out,e``, computed here as a loop over the experts
  held with a mask.  *Shared*: the same form at ``shared_intermediate_size``,
  unweighted.

**Departures from the published description**, each the configuration
file's: ``num_local_experts`` counts the experts *held*
(``held_experts_first`` on; the router stays ``num_local_experts_published``
wide), ``vocab_size`` the rows of the tied embedding held
(``vocab_first_row`` on), ``num_hidden_layers`` the layers run (the first of
``layer_types``).  What the absent experts would add is left out.  The state
is float32 between tokens (the configuration's ``assumed`` says why).  Long
sequences are computed in blocks (queries of the attention, tokens of a
feed-forward part), which changes no number.

It makes the weights itself, from the seed, one layer at a time, in the type
the configuration states (bfloat16; ``A_log``, ``dt_bias`` and ``D``
float32), and upcasts them: matrices ``[in, out]`` normal at ``1/sqrt(in)``,
the convolution's taps normal at ``1/sqrt(K)`` and its bias normal at 0.1,
``A_log = log(1..H)``, ``dt_bias`` such that ``softplus`` of it is log-uniform
in [1e-3, 1e-1] (a head's own draw), ``D`` ones, norm weights uniform in
[0.5, 1.5], the embedding normal at ``1 / (embedding_multiplier
sqrt(hidden_size))``: the head is the embedding, so a token's own row answers
its own embedding in the residual stream, and at any larger spread every
position's largest logit would be the token just read, whatever the layers
computed; at this one that term is about one spread of the logits (which
spread by ``4 / (embedding_multiplier sqrt(hidden_size))``, 0.005 at the
published sizes: the gaps of the comparison are on that scale).  An expert's
and a vocabulary row's
weights depend on its own index alone, so the shares of one seed tile the
uncut model.  Two **controls** take a lower precision in the program's
place: ``precision="fp8"`` rounds every tensor the program holds in bfloat16
to float8_e4m3fn instead (weights per output channel, activations per row);
``precision="bf16_state"`` keeps everything float32 but the recurrent state,
rounded to bfloat16 after every token (the precision the file does not
state: what ``transformers``' cache does in a bfloat16 model).
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
    if len(kinds) != n_layers or set(kinds) - {"mamba", "attention"}:
        raise ValueError("layer_types has to name every layer's kind")
    if int(cfg["mamba_n_groups"]) != 1:
        raise ValueError("one group of B and C is what is written here")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    held = int(cfg["num_local_experts"])
    m = {
        "d": d, "L": n_layers, "kinds": kinds,
        "sh": int(cfg["mamba_n_heads"]), "sp": int(cfg["mamba_d_head"]),
        "sn": int(cfg["mamba_d_state"]), "taps": int(cfg["mamba_d_conv"]),
        "h": h, "kvh": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "attn_scale": float(cfg["attention_multiplier"]),
        "e_held": held, "e_first": int(cfg.get("held_experts_first", 0)),
        "e_all": int(cfg.get("num_local_experts_published", held)),
        "ef": int(cfg["intermediate_size"]),
        "sf": int(cfg["shared_intermediate_size"]),
        "k": int(cfg["num_experts_per_tok"]),
        "v_rows": int(cfg["vocab_size"]),
        "v_first": int(cfg.get("vocab_first_row", 0)),
        "embed_mult": float(cfg["embedding_multiplier"]),
        "res_mult": float(cfg["residual_multiplier"]),
        "logits_div": float(cfg["logits_scaling"]),
        "eps": float(cfg["rms_norm_eps"]),
    }
    m["inner"] = m["sh"] * m["sp"]
    if m["inner"] != int(cfg["mamba_expand"]) * d:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    m["conv"] = m["inner"] + 2 * m["sn"]
    return m


def layer_kind(cfg: dict, i: int) -> str:
    """``"mamba"`` or ``"attention"``: layer ``i``'s mixer."""
    return _dims(cfg)["kinds"][i]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _mat(key, n_in, n_out, dt):
    return (jax.random.normal(key, (n_in, n_out), jnp.float32)
            * n_in ** -0.5).astype(dt)


def _norm_w(key, n, dt):
    return jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5).astype(dt)


def _layer_weights(cfg: dict, kind: str, seed, i) -> dict:
    m = _dims(cfg)
    d = m["d"]
    dt = jnp.dtype(cfg["torch_dtype"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), 1), i)
    ks = iter(jax.random.split(key, 16))
    w = {"mixer_norm": _norm_w(next(ks), d, dt),
         "ffn_norm": _norm_w(next(ks), d, dt)}
    if kind == "mamba":
        step = jnp.exp(jax.random.uniform(
            next(ks), (m["sh"],), jnp.float32, np.log(1e-3), np.log(1e-1)))
        w.update(
            w_in=_mat(next(ks), d, m["inner"] + m["conv"] + m["sh"], dt),
            conv_w=_mat(next(ks), m["taps"], m["conv"], dt).T,  # [C, K]
            conv_b=(0.1 * jax.random.normal(next(ks), (m["conv"],),
                                            jnp.float32)).astype(dt),
            dt_bias=jnp.log(jnp.expm1(step)),
            A_log=jnp.log(jnp.arange(1, m["sh"] + 1, dtype=jnp.float32)),
            D=jnp.ones((m["sh"],), jnp.float32),
            gate_norm=_norm_w(next(ks), m["inner"], dt),
            w_out=_mat(next(ks), m["inner"], d, dt))
    else:
        w.update(wq=_mat(next(ks), d, m["h"] * m["hd"], dt),
                 wk=_mat(next(ks), d, m["kvh"] * m["hd"], dt),
                 wv=_mat(next(ks), d, m["kvh"] * m["hd"], dt),
                 wo=_mat(next(ks), m["h"] * m["hd"], d, dt))
    ks = iter(jax.random.split(jax.random.fold_in(key, 99), 8))
    # the router is as wide as published
    w["w_router"] = _mat(next(ks), d, m["e_all"], dt)
    k_exp = next(ks)

    def expert(e):          # an expert's weights depend on its index alone
        k2 = jax.random.split(jax.random.fold_in(k_exp, e), 2)
        w_in = _mat(k2[0], d, 2 * m["ef"], dt)      # the fused [a | b]
        return (w_in[:, :m["ef"]], w_in[:, m["ef"]:],
                _mat(k2[1], m["ef"], d, dt))

    w["e_gate"], w["e_up"], w["e_down"] = lax.map(
        expert, m["e_first"] + jnp.arange(m["e_held"]))
    s_in = _mat(next(ks), d, 2 * m["sf"], dt)
    w.update(s_gate=s_in[:, :m["sf"]], s_up=s_in[:, m["sf"]:],
             s_down=_mat(next(ks), m["sf"], d, dt))
    return w


def layer_weights(cfg: dict, seed, i: int) -> dict:
    """Layer ``i``'s weights from the seed.  ``i`` is a Python int (it decides
    the layer's kind, so its shapes)."""
    return _layer_weights(cfg, layer_kind(cfg, int(i)), seed, jnp.int32(i))


def top_weights(cfg: dict, seed) -> dict:
    """The held rows of the tied embedding (normal at ``1 /
    (embedding_multiplier sqrt(d))``), each row from its own index, and the
    final norm."""
    m = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    ks = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2), 2)
    rows = m["v_first"] + jnp.arange(m["v_rows"])

    def row(r):
        return (jax.random.normal(jax.random.fold_in(ks[0], r), (m["d"],),
                                  jnp.float32)
                / (m["embed_mult"] * m["d"] ** 0.5)).astype(dt)

    return {"embed": jax.vmap(row)(rows),
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
    fp8 control, row by row."""
    if precision in ("float32", "bf16_state"):
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


def _blocks(fn, n: int, block: int):
    """``fn(start)`` over ``range(0, n, block)``, the outcomes side by side
    along their first axis (``block`` divides ``n``, or is ``n``)."""
    out = lax.map(fn, jnp.arange(0, n, block))
    return jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), out)


def mixer(m: dict, u, w, precision, state=None, count=None):
    """The state-space mixer over one sequence ``u`` [T, d] from position 0
    (``state``: an ``(S, carry)`` to start from in its place): the
    recurrence token by token.  Returns the output [T, d] and the state
    after the last token (after the first ``count`` tokens, if given: what
    follows them is padding)."""
    t = u.shape[0]
    h, p, n, taps = m["sh"], m["sp"], m["sn"], m["taps"]
    zxd = _proj(u, w["w_in"], precision)
    z, xbc, dt = jnp.split(zxd, [m["inner"], m["inner"] + m["conv"]], axis=-1)
    s0, carry = state if state is not None else (
        jnp.zeros((h, p, n), jnp.float32),
        jnp.zeros((taps - 1, m["conv"]), jnp.float32))
    rows = jnp.concatenate([carry, xbc])                     # [K-1+T, C]
    cw = w["conv_w"].astype(jnp.float32)
    xbc = _act(jax.nn.silu(w["conv_b"].astype(jnp.float32) + sum(
        rows[j:j + t] * cw[:, j] for j in range(taps))), precision)
    x = xbc[:, :m["inner"]].reshape(t, h, p)
    b = xbc[:, m["inner"]:m["inner"] + n]
    c = xbc[:, m["inner"] + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [T, H]
    decay = jnp.exp(dt * -jnp.exp(w["A_log"]))

    last = t if count is None else count

    def token(carried, tok):
        s, kept = carried
        i, decay_t, dt_t, x_t, b_t, c_t = tok
        s = decay_t[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        if precision == "bf16_state":
            # (on the chip the compiler simplifies a pair of converts away)
            s = lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        kept = jnp.where(i + 1 == last, s, kept)
        return (s, kept), jnp.sum(s * c_t[None, None, :], axis=-1)

    (_, s), y = lax.scan(token, (s0, s0),
                         (jnp.arange(t), decay, dt, x, b, c))
    y = y + w["D"][:, None] * x
    y = y.reshape(t, m["inner"]) * jax.nn.silu(z)
    y = _act(_rms(y, w["gate_norm"], m["eps"]), precision)
    return _proj(y, w["w_out"], precision), (
        s, lax.dynamic_slice_in_dim(rows, last, taps - 1))


def attention(m: dict, u, w, precision, q_block: int):
    """Grouped-query attention over one sequence ``u`` [T, d], causal, no
    rotary, scores at ``attention_multiplier``; queries ``q_block`` at a
    time over every key."""
    t = u.shape[0]
    h, kvh, hd = m["h"], m["kvh"], m["hd"]
    q = _proj(u, w["wq"], precision).reshape(t, h, hd)
    k = _proj(u, w["wk"], precision).reshape(t, kvh, hd)
    v = _proj(u, w["wv"], precision).reshape(t, kvh, hd)
    qb = q_block if t % q_block == 0 else t

    def block(start):
        qg = lax.dynamic_slice_in_dim(q, start, qb).reshape(
            qb, kvh, h // kvh, hd)
        s = jnp.einsum("qkrd,mkd->krqm", qg, k, precision=HI) \
            * m["attn_scale"]
        seen = jnp.arange(t)[None, :] <= start + jnp.arange(qb)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("krqm,mkd->qkrd", p, v, precision=HI)

    o = _blocks(block, t, qb)
    return _proj(_act(o, precision).reshape(t, h * hd), w["wo"], precision)


def _swiglu(x, w_gate, w_up, w_down, precision):
    g = _act(jax.nn.silu(_proj(x, w_gate, precision)), precision)
    return _proj(_act(g * _proj(x, w_up, precision), precision), w_down,
                 precision)


def route(m: dict, x, w, precision):
    """The experts each token chose ([T, k] indices over the published
    router) and their weights: the largest logits, a softmax over them; the
    router in float32."""
    logits = jnp.dot(_act(x, precision), _weight(w["w_router"], precision),
                     precision=HI)
    top, experts = lax.top_k(logits, m["k"])
    return experts, jax.nn.softmax(top, axis=-1)


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
    if shared:
        y = _act(y + _swiglu(x, w["s_gate"], w["s_up"], w["s_down"],
                             precision), precision)
    return y, experts


def layer(cfg: dict, kind: str, x, w: dict, precision: str = "float32",
          q_block: int = 256, aux: bool = False, count=None):
    """A layer of ``kind`` (:func:`layer_kind`) over one sequence ``x``
    [T, d] (float32), causal.  With ``aux`` also the experts each token
    chose [T, k] and, of a state-space layer, the state after the last
    token (after the first ``count``, if given)."""
    m = _dims(cfg)
    t = x.shape[0]
    u = _act(_rms(x, w["mixer_norm"], m["eps"]), precision)
    state = None
    if kind == "mamba":
        o, state = mixer(m, u, w, precision, count=count)
    else:
        o = attention(m, u, w, precision, q_block)
    x = _act(x + m["res_mult"] * o, precision)
    hn = _act(_rms(x, w["ffn_norm"], m["eps"]), precision)
    fb = FFN_BLOCK if t % FFN_BLOCK == 0 else t
    y, experts = _blocks(
        lambda start: moe(m, lax.dynamic_slice_in_dim(hn, start, fb), w,
                          precision), t, fb)
    x = _act(x + m["res_mult"] * y, precision)
    return (x, experts, state) if aux else x


def _through_layers(cfg: dict, seed: int, seqs: list, precision: str,
                    pad_to: int, counts: list | None = None):
    """Every sequence (ids within the held slice), padded at its end to a
    multiple of ``pad_to`` (what follows a position cannot reach it), through
    the layers one after the other.  Returns the top weights, each
    sequence's last hidden states and, with ``counts``, each sequence's
    recurrent states [state-space layers, H, P, N] after its first
    ``counts[j]`` tokens (on the host)."""
    m = _dims(cfg)
    top = jax.jit(functools.partial(top_weights, cfg))(seed_arg(seed))
    xs = []
    for s in seqs:
        n = -(-len(s) // pad_to) * pad_to
        ids = jnp.asarray(list(s) + [0] * (n - len(s)), jnp.int32)
        xs.append(_act(top["embed"][ids].astype(jnp.float32)
                       * m["embed_mult"], precision))
    states = [[] for _ in seqs]
    makers, steps = {}, {}
    for i in range(m["L"]):
        kind = layer_kind(cfg, i)
        if kind not in makers:      # one program per kind of layer
            makers[kind] = jax.jit(functools.partial(_layer_weights, cfg,
                                                     kind))
            steps[kind] = jax.jit(functools.partial(
                layer, cfg, kind, precision=precision,
                aux=counts is not None))
        w = makers[kind](seed_arg(seed), jnp.int32(i))
        for j, x in enumerate(xs):
            if counts is None:
                xs[j] = steps[kind](x, w)
                continue
            xs[j], _, state = steps[kind](x, w, count=jnp.int32(counts[j]))
            if state is not None:
                states[j].append(np.asarray(state[0]))
        del w
    return top, xs, [np.stack(s) for s in states] if counts else None


def logits_at(cfg: dict, seed: int, seqs: list, positions: list,
              precision: str = "float32", pad_to: int = 1024) -> list:
    """For each token sequence (ids within the held slice) the logits
    [n, rows held] at its ``positions``, by a full causal pass: layer by
    layer over all the sequences."""
    m = _dims(cfg)
    top, xs, _ = _through_layers(cfg, seed, seqs, precision, pad_to)

    @jax.jit
    def head(x, pos, norm, embed):       # weights as arguments, not constants
        hn = _act(_rms(x[pos], norm, m["eps"]), precision)
        return _proj(hn, embed.T, precision) / m["logits_div"]

    return [head(x, jnp.asarray(p, jnp.int32), top["final_norm"],
                 top["embed"]) for x, p in zip(xs, positions)]


def states_at(cfg: dict, seed: int, seqs: list, counts: list,
              precision: str = "float32", pad_to: int = 1024) -> list:
    """For each token sequence the recurrent state of every state-space
    layer, [layers, H, P, N] float32 on the host, after its first
    ``counts[j]`` tokens: the same pass, the recurrence's state kept where
    the count is reached."""
    return _through_layers(cfg, seed, seqs, precision, pad_to, counts)[2]


def served_gaps(ref_rows, tokens) -> "jax.Array":
    """How far each token's logit lies below the reference's best, per row:
    0 where the token is the reference's own choice."""
    tok = jnp.asarray(tokens, jnp.int32)
    picked = jnp.take_along_axis(ref_rows, tok[:, None], axis=-1)[:, 0]
    return jnp.max(ref_rows, axis=-1) - picked
