"""The plain reference for the ``sdar_serve`` family: the language model of
``SDAR-30B-A3B-Chat`` (``model_type`` ``sdar_moe``) as its ``config.json``
gives it, and the published block-diffusion sampler — straightforward
``jax.numpy`` in float32, every product at ``Precision.HIGHEST``, with no
cache, no batching and no kernel.  It imports nothing of the program.

**Layer equations** (``h`` [T, d] at positions ``p``; no biases; eps
``rms_norm_eps``; ``B`` the block length).

* ``a = RMSNorm(h)``; ``q = a Wq`` [T, heads, head_dim], ``k = a Wk``, ``v = a
  Wv`` [T, kv_heads, head_dim]; ``q`` and ``k`` RMS-normed over ``head_dim``
  with own weights, then rotary (half-split pairs, ``rope_theta``) at ``p``;
  scores ``q k^T / sqrt(head_dim)``, ``heads / kv_heads`` query heads a key
  head; softmax over the keys ``j`` with ``p_j // B <= p_i // B`` — everything
  in the earlier blocks and the whole of the own block, in both directions
  (the **block-causal** mask); ``h += attn Wo``.
* ``m = RMSNorm(h)``; ``l = m Wr`` [T, num_experts] in float32; the
  ``num_experts_per_tok`` largest are chosen; weights ``softmax(l)`` over all
  the experts divided by their sum over the chosen (``norm_topk_prob``);
  ``h += sum_e w_e W2_e(silu(W1_e m) * W3_e m)``, computed here as a loop over
  the experts with a mask.  No shared expert, no dense layer.
* Final RMSNorm, then the untied head.

**Generation** (the published ``block_diffusion_generate``, greedy).  Prefill
the prompt's whole blocks ``floor(L / B) * B`` under the mask above.  Then a
block at a time: its input is the prompt's tail ``L mod B`` (given) and the
mask id elsewhere.  For step ``s = 0..S-1`` while a position is masked: run
the block against what is before it, ``x0 = argmax``, ``c = softmax(logits)[
x0]`` at the masked positions; ``low_confidence_static`` unmasks the ``n_s``
most confident (``n_s = B // S``, one more on the first ``B % S`` steps; ties
to the lower position; all that are left where fewer are masked);
``low_confidence_dynamic`` unmasks every masked position with ``c >
threshold`` where those are at least ``n_s``, else the static rule.  A
position once unmasked never changes.  When no mask is left the block is
committed (its keys, computed from the clean block, are what later blocks
see) and the next begins.  The answer is the first ``n_out`` tokens
generated.

**Departures**, in the program and here alike.  (1) The mask id's logit is set
to minus infinity before the argmax and the softmax: the published code does
not, and with random weights one token in 152 k would otherwise be the mask id
and a block would never come clean.  (2) The published static rule's
``topk(n_s)`` over confidences that are minus infinity off the masked
positions can pick a position that is not masked when fewer than ``n_s`` are
(a first block that holds a prompt's tail) and overwrite a given token; here a
given or unmasked position never changes.  (3) ``num_hidden_layers`` counts
the layers run (the configuration file's cut).

``assumed``: the q/k RMS-norm and the Qwen3-MoE layer the ``sdar_moe``
modelling code follows; ``torch_dtype`` bfloat16 with the router in float32.

**Two forms of one pass.**  :func:`sample` runs the sampler naively: one
forward over the whole sequence so far a step.  :func:`step_rows` is the
**training-time form** the block-diffusion papers give, for sequences already
generated: one forward over ``[clean sequence ; noisy blocks of step s]`` in
which noisy block ``i`` sees the clean blocks before ``i`` and itself, so that
every block's step-``s`` logits of a request cost one pass.  A test shows the
two agree.

It makes the weights itself, from the seed, one layer at a time, in the type
the configuration states (bfloat16), and upcasts them: matrices ``[in, out]``
normal at ``1/sqrt(in)``, the embedding normal at ``embedding_std`` (the
configuration's: a masked position's input is the mask id's embedding whatever
its row, so at 1 it swamps what attention brings, rms 0.06, and every masked
position of every row proposes the same token and chooses the same experts; at
the family's initialiser range, 0.02, a masked position's state is its
context's), the head normal at ``1/sqrt(d)``, norm weights uniform in [0.5,
1.5].  An expert's weights depend
on its own index alone.  ``precision="fp8"`` is the **control**: every tensor
the program holds in bfloat16 rounded to float8_e4m3fn instead (weights per
output channel, activations per row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
Q_BLOCK = 256               # queries of an attention computed at once
DYNAMIC, STATIC = "low_confidence_dynamic", "low_confidence_static"


def seed_arg(seed: int):
    """The seed as an argument of a jitted maker (not a constant in it)."""
    return np.uint32(int(seed) % 2**32)


def _dims(cfg: dict) -> dict:
    if cfg["mlp_only_layers"] or int(cfg["decoder_sparse_step"]) != 1:
        raise ValueError("every layer of this reference is an expert layer")
    return {
        "d": int(cfg["hidden_size"]), "L": int(cfg["num_hidden_layers"]),
        "h": int(cfg["num_attention_heads"]),
        "kvh": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "theta": float(cfg["rope_theta"]), "e": int(cfg["num_experts"]),
        "ef": int(cfg["moe_intermediate_size"]),
        "k": int(cfg["num_experts_per_tok"]), "v": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]), "B": int(cfg["block_length"]),
        "mask": int(cfg["mask_token_id"])}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _mat(key, n_in, n_out, dt):
    return (jax.random.normal(key, (n_in, n_out), jnp.float32)
            * n_in ** -0.5).astype(dt)


def _norm_w(key, n, dt):
    return jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5).astype(dt)


def layer_weights(cfg: dict, seed, i) -> dict:
    """Layer ``i``'s weights from the seed (``i`` may be traced: every layer
    has the same shapes)."""
    m = _dims(cfg)
    d, hd = m["d"], m["hd"]
    dt = jnp.dtype(cfg["torch_dtype"])
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), 1), i)
    ks = iter(jax.random.split(key, 12))
    w = {"attn_norm": _norm_w(next(ks), d, dt),
         "ffn_norm": _norm_w(next(ks), d, dt),
         "wq": _mat(next(ks), d, m["h"] * hd, dt),
         "wk": _mat(next(ks), d, m["kvh"] * hd, dt),
         "wv": _mat(next(ks), d, m["kvh"] * hd, dt),
         "q_norm": _norm_w(next(ks), hd, dt),
         "k_norm": _norm_w(next(ks), hd, dt),
         "wo": _mat(next(ks), m["h"] * hd, d, dt),
         "w_router": _mat(next(ks), d, m["e"], dt)}
    k_exp = next(ks)

    def expert(e):          # an expert's weights depend on its index alone
        k3 = jax.random.split(jax.random.fold_in(k_exp, e), 3)
        return (_mat(k3[0], d, m["ef"], dt), _mat(k3[1], d, m["ef"], dt),
                _mat(k3[2], m["ef"], d, dt))

    # a few experts at a time: all 128 drawn at once in float32 are 2.4 GB
    w["e_gate"], w["e_up"], w["e_down"] = lax.map(
        expert, jnp.arange(m["e"]), batch_size=8)
    return w


def top_weights(cfg: dict, seed) -> dict:
    """The embedding (normal at the configuration's ``embedding_std``, which
    it has to state: a KeyError otherwise, since at 1 the model is the
    degenerate one of the module's docstring), the head (normal at ``1/sqrt(d)``), each row from its own
    index, and the final norm."""
    m = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    ks = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2), 3)
    rows = jnp.arange(m["v"])

    def row(k, r, scale):
        return (jax.random.normal(jax.random.fold_in(k, r), (m["d"],),
                                  jnp.float32) * scale).astype(dt)

    return {"embed": jax.vmap(lambda r: row(
                ks[0], r, float(cfg["embedding_std"])))(rows),
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


def _rope(x, pos, theta):
    """``x`` [T, heads, n] at positions ``pos`` [T]: dimension i pairs with
    i + n/2."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _seen(m: dict, q_pos, q_noisy, k_pos, k_noisy):
    """Which keys each query sees, [Tq, Tk].  A clean query sees the clean
    keys of the earlier blocks and of its own.  A noisy query (a position of
    a block in flight) sees the clean keys of the earlier blocks only, and
    the noisy keys of its own block.  Positions below 0 are padding and are
    seen by nothing."""
    qb, kb = q_pos[:, None] // m["B"], k_pos[None, :] // m["B"]
    qn, kn = q_noisy[:, None], k_noisy[None, :]
    clean_key = ~kn & jnp.where(qn, kb < qb, kb <= qb)
    noisy_key = kn & qn & (kb == qb)
    return (k_pos[None, :] >= 0) & (clean_key | noisy_key)


def attention(m: dict, x, w, pos, noisy, precision):
    """Grouped-query attention over the tokens ``x`` [T, d] at positions
    ``pos`` [T], of which ``noisy`` [T] are positions of blocks in flight
    (:func:`_seen`); queries ``Q_BLOCK`` at a time."""
    t = x.shape[0]
    h, kvh, hd = m["h"], m["kvh"], m["hd"]
    q = _act(_rms(_proj(x, w["wq"], precision).reshape(t, h, hd),
                  w["q_norm"], m["eps"]), precision)
    k = _act(_rms(_proj(x, w["wk"], precision).reshape(t, kvh, hd),
                  w["k_norm"], m["eps"]), precision)
    v = _proj(x, w["wv"], precision).reshape(t, kvh, hd)
    q = _act(_rope(q, pos, m["theta"]), precision)
    k = _act(_rope(k, pos, m["theta"]), precision)
    qb = Q_BLOCK if t % Q_BLOCK == 0 else t

    def block(start):
        qg = lax.dynamic_slice_in_dim(q, start, qb).reshape(
            qb, kvh, h // kvh, hd)
        s = jnp.einsum("qkrd,mkd->krqm", qg, k, precision=HI) * hd ** -0.5
        seen = _seen(m, lax.dynamic_slice_in_dim(pos, start, qb),
                     lax.dynamic_slice_in_dim(noisy, start, qb), pos, noisy)
        # a row of padding sees nothing: give it key 0, nobody reads it
        seen = seen.at[:, 0].set(seen[:, 0] | ~jnp.any(seen, axis=1))
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("krqm,mkd->qkrd", p, v, precision=HI)

    o = lax.map(block, jnp.arange(0, t, qb)).reshape(t, h * hd)
    return _proj(_act(o, precision), w["wo"], precision)


def route(m: dict, x, w, precision):
    """The experts each token chose ([T, k]) and their weights: softmax over
    all the experts in float32, normalised over the chosen."""
    logits = jnp.dot(_act(x, precision), _weight(w["w_router"], precision),
                     precision=HI)
    p = jax.nn.softmax(logits, axis=-1)
    picked, experts = lax.top_k(p, m["k"])
    return experts, picked / jnp.sum(picked, axis=-1, keepdims=True)


def moe(m: dict, x, w, precision):
    """Every token through every expert, weighted 0 where it did not choose
    it."""
    experts, weights = route(m, x, w, precision)

    def one(y, j):
        wt = jnp.sum(jnp.where(experts == j, weights, 0.0), -1)
        g = _act(jax.nn.silu(_proj(x, w["e_gate"][j], precision)), precision)
        out = _proj(_act(g * _proj(x, w["e_up"][j], precision), precision),
                    w["e_down"][j], precision)
        return y + wt[:, None] * out, None

    y, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(m["e"]))
    return _act(y, precision)


def layer(cfg: dict, x, w: dict, pos, noisy, precision: str = "float32"):
    """One layer over the tokens ``x`` [T, d] (float32) at ``pos`` / ``noisy``
    (:func:`_seen`)."""
    m = _dims(cfg)
    a = _act(_rms(x, w["attn_norm"], m["eps"]), precision)
    x = _act(x + attention(m, a, w, pos, noisy, precision), precision)
    f = _act(_rms(x, w["ffn_norm"], m["eps"]), precision)
    return _act(x + moe(m, f, w, precision), precision)


def _head(cfg: dict, x, norm, lm_head, precision):
    m = _dims(cfg)
    return _proj(_act(_rms(x, norm, m["eps"]), precision), lm_head, precision)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight at once, for the small sizes of the tests."""
    return {"top": top_weights(cfg, seed_arg(seed)),
            "layers": [layer_weights(cfg, seed_arg(seed), i)
                       for i in range(_dims(cfg)["L"])]}


def forward(cfg: dict, weights: dict, ids, precision: str = "float32"):
    """Logits [T, V] of one whole sequence ``ids`` [T] (a whole number of
    blocks) under the block-causal mask."""
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0])
    noisy = jnp.zeros(ids.shape, bool)
    x = weights["top"]["embed"][ids].astype(jnp.float32)
    for w in weights["layers"]:
        x = layer(cfg, x, w, pos, noisy, precision)
    return _head(cfg, x, weights["top"]["final_norm"],
                 weights["top"]["lm_head"], precision)


# ---------------------------------------------------------------------------
# the sampler
# ---------------------------------------------------------------------------

def schedule(block_length: int, steps: int, s: int) -> int:
    """``n_s``: the positions step ``s`` of ``steps`` unmasks of a block."""
    return block_length // steps + (1 if s < block_length % steps else 0)


def confidences(cfg: dict, logits):
    """``(x0, log c)`` of block logits [.., V]: with the mask id's logit at
    minus infinity, the best id and the log of its softmax probability."""
    mask_id = _dims(cfg)["mask"]
    logits = jnp.asarray(logits).at[..., mask_id].set(-jnp.inf)
    return (np.asarray(jnp.argmax(logits, -1)),
            np.asarray(jnp.max(logits, -1)
                       - jax.nn.logsumexp(logits, axis=-1)))


def unmask_rule(cfg: dict, sampler: dict, logits, tokens: list, s: int
                ) -> tuple:
    """The rule of the module docstring over one block: ``(new tokens, the
    positions unmasked, whether the threshold decided)``."""
    m = _dims(cfg)
    x0, logc = confidences(cfg, logits)
    masked = [i for i, t in enumerate(tokens) if t == m["mask"]]
    n_s = schedule(m["B"], int(sampler["denoising_steps"]), s)
    take = sorted(masked, key=lambda i: (-logc[i], i))[:n_s]
    by_threshold = False
    if sampler["remasking"] == DYNAMIC:
        thr = np.log(float(sampler["confidence_threshold"]))
        high = [i for i in masked if logc[i] > thr]
        if len(high) >= n_s:
            take, by_threshold = high, True
    elif sampler["remasking"] != STATIC:
        raise ValueError(f"unknown remasking {sampler['remasking']!r}")
    new = list(tokens)
    for i in take:
        new[i] = int(x0[i])
    return new, sorted(take), by_threshold


def sample(cfg: dict, weights: dict, sampler: dict, prompt: list,
           n_out: int, pad_to: int | None = None) -> dict:
    """The sampler run naively: one forward over the whole sequence so far a
    denoise step (padded at its end to ``pad_to`` positions, so one program:
    what follows a block cannot reach it).  Returns ``tokens`` (the first
    ``n_out`` generated), ``blocks`` (every generated block whole, the
    prompt's tail in the first), ``steps`` (per position of each block the
    step that unmasked it, -1 for a given one) and ``logits`` (per block, per
    denoise step, the block's logits [B, V] that step saw)."""
    m = _dims(cfg)
    b, mask_id = m["B"], m["mask"]
    fwd = jax.jit(functools.partial(forward, cfg, weights))
    seq = list(prompt[:len(prompt) // b * b])
    tail = list(prompt[len(seq):])
    out = {"blocks": [], "steps": [], "logits": []}
    need = len(tail) + n_out
    while len(out["blocks"]) * b < need:
        cur = tail + [mask_id] * (b - len(tail)) if not out["blocks"] \
            else [mask_id] * b
        when = [-1 if t != mask_id else None for t in cur]
        seen = []
        s = 0
        while mask_id in cur:
            ids = seq + cur
            ids = ids + [0] * ((pad_to or len(ids)) - len(ids))
            logits = fwd(jnp.asarray(ids, jnp.int32))[len(seq):len(seq) + b]
            seen.append(np.asarray(logits))
            cur, took, _ = unmask_rule(cfg, sampler, logits, cur, s)
            for i in took:
                when[i] = s
            s += 1
        seq += cur
        out["blocks"].append(cur)
        out["steps"].append(when)
        out["logits"].append(seen)
    flat = [t for blk in out["blocks"] for t in blk]
    out["tokens"] = flat[len(tail):len(tail) + n_out]
    return out


# ---------------------------------------------------------------------------
# the training-time form, for sequences already generated
# ---------------------------------------------------------------------------

def noisy_blocks(cfg: dict, blocks: list, steps: list, s: int) -> list:
    """The blocks as denoise step ``s`` saw them: a position unmasked at step
    ``s`` or later holds the mask id, a given or earlier one its token."""
    mask_id = _dims(cfg)["mask"]
    return [[mask_id if w >= s else t for t, w in zip(blk, when)]
            for blk, when in zip(blocks, steps)]


def _joint(cfg: dict, head: list, blocks: list, steps: list, s: int,
           pad_to: int) -> tuple:
    """``[clean sequence ; noisy blocks of step s]`` padded: ids, positions,
    which are noisy (padding at position -1), and where the noisy part
    starts."""
    b = _dims(cfg)["B"]
    clean = list(head) + [t for blk in blocks for t in blk]
    noisy = [t for blk in noisy_blocks(cfg, blocks, steps, s) for t in blk]
    n, k = len(clean), len(noisy)
    width = -(-(n + k) // pad_to) * pad_to
    ids = clean + noisy + [0] * (width - n - k)
    pos = list(range(n)) + list(range(len(head), n)) + [-1] * (width - n - k)
    flag = [False] * n + [True] * k + [False] * (width - n - k)
    assert len(head) % b == 0
    return (np.asarray(ids, np.int32), np.asarray(pos, np.int32),
            np.asarray(flag, bool), n)


def step_rows(cfg: dict, seed: int, samples: list, n_steps: int,
              precision: str = "float32", pad_to: int = 1024,
              also: list | None = None, weights: dict | None = None) -> list:
    """For each sample ``(prompt, blocks, steps)`` — a prompt, the generated
    blocks whole and the step that unmasked each position — and each denoise
    step ``s < n_steps``, what the reference reads at every position that was
    still masked when step ``s`` ran: one forward over ``[clean sequence ;
    noisy blocks of step s]`` a step, layer by layer over all the samples.

    Returns per sample a dict of arrays over those readings, in order of
    step, block, position: ``step``, ``block``, ``at`` (which reading),
    ``token`` (what the program put there in the end), ``taken`` (whether
    step ``s`` unmasked it), ``best`` (the reference's best logit, the mask
    id's at minus infinity), ``logc`` (its log-confidence), ``picked`` (the
    logit of ``token``), ``argmax``; and, with ``also`` (per sample, ids in
    the readings' order), ``picked_also``."""
    m = _dims(cfg)
    b = m["B"]
    top = (weights["top"] if weights is not None else
           jax.jit(functools.partial(top_weights, cfg))(seed_arg(seed)))
    passes = []                 # (sample, step, ids, pos, noisy, n, x)
    for j, (prompt, blocks, steps) in enumerate(samples):
        head = list(prompt[:len(prompt) // b * b])
        for s in range(n_steps):
            if not any(w >= s for when in steps for w in when):
                continue
            ids, pos, flag, n = _joint(cfg, head, blocks, steps, s, pad_to)
            passes.append([j, s, pos, flag, n,
                           top["embed"][jnp.asarray(ids)].astype(
                               jnp.float32)])
    make = jax.jit(functools.partial(layer_weights, cfg))
    run = jax.jit(functools.partial(layer, cfg, precision=precision))
    for i in range(m["L"]):
        w = (weights["layers"][i] if weights is not None
             else make(seed_arg(seed), jnp.int32(i)))
        for p in passes:
            p[5] = run(p[5], w, jnp.asarray(p[2]), jnp.asarray(p[3]))
        del w

    @jax.jit
    def read(x, at, tok, other, norm, lm_head):
        logits = _head(cfg, x[at], norm, lm_head, precision)
        logits = logits.at[:, m["mask"]].set(-jnp.inf)
        best = jnp.max(logits, -1)
        pick = lambda t: jnp.take_along_axis(     # noqa: E731
            logits, t[:, None], axis=-1)[:, 0]
        return (best, best - jax.nn.logsumexp(logits, axis=-1), pick(tok),
                pick(other), jnp.argmax(logits, -1))

    out = [{k: [] for k in ("step", "block", "at", "token", "taken", "best",
                            "logc", "picked", "picked_also", "argmax")}
           for _ in samples]
    for j, s, _, _, n, x in passes:
        _, blocks, steps = samples[j]
        where = [(bi, i) for bi, when in enumerate(steps)
                 for i, w in enumerate(when) if w >= s]
        at = np.asarray([n + bi * b + i for bi, i in where], np.int32)
        tok = np.asarray([blocks[bi][i] for bi, i in where], np.int32)
        o = out[j]
        k0 = len(o["step"])
        other = tok if also is None else np.asarray(
            also[j][k0:k0 + len(where)], np.int32)
        # whole multiples of 256 readings a call: a few shapes to compile
        pad = -(-len(at) // 256) * 256 - len(at)
        got = read(x, jnp.asarray(np.pad(at, (0, pad))),
                   jnp.asarray(np.pad(tok, (0, pad))),
                   jnp.asarray(np.pad(other, (0, pad))), top["final_norm"],
                   top["lm_head"])
        best, logc, picked, picked_also, arg = (
            np.asarray(g)[:len(at)] for g in got)
        o["step"] += [s] * len(where)
        o["block"] += [bi for bi, _ in where]
        o["at"] += [i for _, i in where]
        o["token"] += tok.tolist()
        o["taken"] += [steps[bi][i] == s for bi, i in where]
        for name, a in (("best", best), ("logc", logc), ("picked", picked),
                        ("picked_also", picked_also), ("argmax", arg)):
            o[name] += a.tolist()
    return [{k: np.asarray(v) for k, v in o.items()} for o in out]


def gaps(rows: dict, picked: str = "picked") -> "np.ndarray":
    """How far each served token's logit lay under the reference's best at
    its position in the step that unmasked it: 0 where the token is the
    reference's own choice."""
    taken = rows["taken"].astype(bool)
    return (rows["best"] - rows[picked])[taken]


def groups(rows: dict) -> list:
    """The readings of each (step, block), as index arrays."""
    key = rows["step"].astype(np.int64) * (int(rows["block"].max(initial=0))
                                           + 1) + rows["block"]
    return [np.flatnonzero(key == g) for g in np.unique(key)]


def order_gaps(rows: dict) -> "np.ndarray":
    """Per position a step unmasked, how far the reference's log-confidence
    of it lay under that of the best position the step left masked in the
    same block: 0 where the program took the reference's own order."""
    out = []
    took = rows["taken"].astype(bool)
    for of in groups(rows):
        taken, left = of[took[of]], of[~took[of]]
        if left.size and taken.size:
            out += np.maximum(rows["logc"][left].max()
                              - rows["logc"][taken], 0.0).tolist()
        else:
            out += [0.0] * taken.size
    return np.asarray(out)
