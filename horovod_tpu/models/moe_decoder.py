"""A trainable decoder of banded and full attention layers over a dropless
expert layer: the training-side form of the sparse models the engine serves,
for ``hvd.DistributedOptimizer`` through ``hvd.make_train_step``.

**The equations** (``h`` hidden, ``T`` tokens of one sequence, positions ``t``
from 0; every product in ``cfg.dtype`` over float32 weights, accumulated in
float32):

* ``x_0 = E[ids]`` over the rows of the vocabulary held here.  For layer
  ``l``: ``a = x + Attn_l(RMSNorm(x; g1_l))``, ``x' = a + MoE_l(RMSNorm(a;
  g2_l))``; RMSNorm in float32 with ``norm_eps``; no biases.  After the last
  layer ``RMSNorm(x; g_f)`` and ``logits = x W_head`` over the held rows;
  the loss is the mean next-token cross-entropy over those rows.
* ``Attn``: ``q = x W_q`` [T, H, Dh], ``k = x W_k``, ``v = x W_v`` [T, KVH,
  Dh]; half-split rotary on q and k with the layer kind's table; scores
  ``q k^T / sqrt(Dh)``; query ``t`` sees key ``j`` iff ``j <= t`` and, in a
  ``"window"`` layer, ``t - j < window``; softmax in float32; then ``W_o``.
  Each group of ``H / KVH`` query heads shares a key-value head.
* rotary, window layers: ``inv_freq_i = theta^(-2i / Dh)``; full layers:
  YaRN's blend of that and it over ``yarn_factor``
  (:func:`horovod_tpu.models.llama.yarn_inv_freq`), cos and sin times
  ``yarn_attention_factor``.
* ``MoE``: ``p = softmax(u W_r)`` over all ``n_experts`` in float32 (``u`` the
  normed input); the ``top_k`` largest are chosen (ties to the lower index),
  weights ``p_e / sum_chosen p``; ``y = sum_{chosen e held here} w_e
  W_down_e (silu(W_gate_e u) * W_up_e u)``.  This process holds experts
  ``held_first .. held_first + held_count - 1``; what the others would add
  is left out and the partial sum goes on
  (:func:`horovod_tpu.models.latent_moe.held_experts`: no choice dropped,
  the backward over the same sorted tiles).

**How it is computed.**  Attention is
:func:`horovod_tpu.parallel.flash_attention.flash_attention` with the band
in its grids; the expert layer sorts one sequence's choices at a time (the
worst case of its rows is every choice held: ``T * top_k`` rows a sequence,
311 MB at 8,192 tokens of 2,304), so one sequence's rows are all that is
alive at once (two at a time read 2.5 % faster on one v5e for 1.5 GB more
scratch: PERF.md, PR 48); a layer is recomputed in the
backward pass (``jax.checkpoint``), and the head goes through
:func:`horovod_tpu.ops.fused_xent.fused_linear_cross_entropy`, which never
holds the ``[tokens, vocabulary]`` logits.

**Counters.**  :func:`loss_and_counters` returns them beside the loss, for
``make_train_step(..., has_aux=True)``: nothing is read on the host inside a
step.  Device scopes: ``attn.window``, ``attn.full``, ``moe.route``,
``moe.experts``, ``xent.head``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import metrics
from horovod_tpu.models import latent_moe, llama
from horovod_tpu.ops.fused_xent import fused_linear_cross_entropy
from horovod_tpu.parallel.flash_attention import (flash_attention,
                                                  key_blocks_visited)

KINDS = ("window", "full")


@dataclasses.dataclass(frozen=True)
class MoEDecoderConfig:
    """A model's shape has no default here: the caller's configuration names
    every width, count and rotary parameter."""
    vocab_size: int                 # the rows of the vocabulary held here
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    layer_kinds: tuple              # of KINDS, a layer each
    window: int
    rope_theta: float
    # YaRN, the full layers' rotary
    yarn_factor: float
    yarn_original_max: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_attention_factor: float
    expert_dim: int
    n_experts: int                  # the router's width
    top_k: int
    held_first: int                 # the experts this process holds
    held_count: int
    norm_eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    block_q: int = 512
    block_k: int = 512
    xent_chunk: int = 4096
    # what latent_moe.route reads: softmax over all, the chosen renormalised
    route_softmax_top_k: bool = True
    routed_scale: float = 1.0
    route_norm_eps: float = 0.0

    def __post_init__(self):
        if len(self.layer_kinds) != self.n_layers or \
                set(self.layer_kinds) - set(KINDS):
            raise ValueError(f"layer_kinds must name {self.n_layers} layers "
                             f"of {KINDS}, got {self.layer_kinds!r}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError("the held experts must lie within the router")


def moe_decoder_tiny(**overrides) -> MoEDecoderConfig:
    """A toy for the CPU tests: widths in whole lanes so that the grouped
    kernels run (in the Pallas interpreter), a band narrower than the
    sequence, YaRN on the full layer, 8 of 16 experts held."""
    base = dict(vocab_size=64, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                head_dim=16, layer_kinds=("window", "full"), window=24,
                rope_theta=500000.0, yarn_factor=16.0, yarn_original_max=32,
                yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                yarn_attention_factor=1.2772588722239782, expert_dim=128,
                n_experts=16, top_k=4, held_first=0, held_count=8,
                norm_eps=1e-6, block_q=16, block_k=16, xent_chunk=32,
                dtype=jnp.float32)
    base.update(overrides)
    return MoEDecoderConfig(**base)


def param_shapes(cfg: MoEDecoderConfig) -> dict:
    """``{path: shape}`` of every weight; ``layers/<l>/<name>`` is leaf
    ``name`` of ``params["layers"][l]``."""
    d, hd = cfg.dim, cfg.head_dim
    e, f = cfg.held_count, cfg.expert_dim
    shapes = {"embed": (cfg.vocab_size, d), "head": (d, cfg.vocab_size),
              "final_norm": (d,)}
    for l in range(cfg.n_layers):
        shapes.update({
            f"layers/{l}/attn_norm": (d,), f"layers/{l}/moe_norm": (d,),
            f"layers/{l}/wq": (d, cfg.n_heads * hd),
            f"layers/{l}/wk": (d, cfg.n_kv_heads * hd),
            f"layers/{l}/wv": (d, cfg.n_kv_heads * hd),
            f"layers/{l}/wo": (cfg.n_heads * hd, d),
            f"layers/{l}/w_router": (d, cfg.n_experts),
            f"layers/{l}/e_gate": (e, d, f), f"layers/{l}/e_up": (e, d, f),
            f"layers/{l}/e_down": (e, f, d)})
    return shapes


def nest(flat: dict) -> dict:
    """``{path: leaf}`` as the tree :func:`loss` takes: ``layers`` a list."""
    tree: dict = {k: v for k, v in flat.items() if "/" not in k}
    layers: dict = {}
    for path, v in flat.items():
        if "/" in path:
            _, l, name = path.split("/")
            layers.setdefault(int(l), {})[name] = v
    tree["layers"] = [layers[l] for l in sorted(layers)]
    return tree


def init_params(cfg: MoEDecoderConfig, key: jax.Array,
                scale: float = 0.02) -> dict:
    """Float32 weights: normal at ``scale``, the norms' gains one."""
    flat = {}
    for i, (path, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if path.endswith("norm"):
            flat[path] = jnp.ones(shape, cfg.param_dtype)
        else:
            flat[path] = (scale * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            ).astype(cfg.param_dtype)
    return nest(flat)


def param_count(cfg: MoEDecoderConfig) -> int:
    total = 0
    for shape in param_shapes(cfg).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def rope_tables(cfg: MoEDecoderConfig, kind: str, positions: jax.Array):
    """cos / sin ``[..., T, head_dim / 2]`` of a layer of ``kind``."""
    if kind == "window":
        return llama.rope_tables_from(
            llama.rope_inv_freq(cfg.head_dim, cfg.rope_theta), positions)
    inv = llama.yarn_inv_freq(
        cfg.head_dim, cfg.rope_theta, factor=cfg.yarn_factor,
        original_max=cfg.yarn_original_max, beta_fast=cfg.yarn_beta_fast,
        beta_slow=cfg.yarn_beta_slow)
    return llama.rope_tables_from(inv, positions, cfg.yarn_attention_factor)


def _dot(x, w, dt):
    return jnp.dot(x, w.astype(dt), preferred_element_type=jnp.float32
                   ).astype(dt)


def _attention(cfg: MoEDecoderConfig, kind: str, lp: dict, h, tables):
    b, t, _ = h.shape
    dt = cfg.dtype
    with jax.named_scope("attn." + kind):
        q = _dot(h, lp["wq"], dt).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = _dot(h, lp["wk"], dt).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        v = _dot(h, lp["wv"], dt).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        cos, sin = tables[kind]
        q, k = llama.apply_rope(q, cos, sin), llama.apply_rope(k, cos, sin)
        o = flash_attention(
            q, k, v, causal=True, block_q=cfg.block_q, block_k=cfg.block_k,
            window=cfg.window if kind == "window" else None)
        return _dot(o.reshape(b, t, cfg.n_heads * cfg.head_dim), lp["wo"], dt)


def _experts(cfg: MoEDecoderConfig, lp: dict, h):
    """The held experts' part for ``h`` [B, T, d], a sequence at a time, and
    the held experts' load over the batch."""
    valid = jnp.ones((h.shape[1],), bool)
    y, load = lax.map(
        lambda rows: latent_moe.held_experts(cfg, lp, rows, valid), h)
    return y, jnp.sum(load, axis=0)


def _mixed(cfg: MoEDecoderConfig, kind: str, lp: dict, x, tables):
    """``a = x + Attn(RMSNorm(x))`` and the expert layer's normed input."""
    a = x + _attention(cfg, kind, lp, llama.rmsnorm(
        x, lp["attn_norm"], cfg.norm_eps), tables)
    return a, llama.rmsnorm(a, lp["moe_norm"], cfg.norm_eps)


def _layer(cfg: MoEDecoderConfig, kind: str, lp: dict, x, tables):
    a, u = _mixed(cfg, kind, lp, x, tables)
    y, load = _experts(cfg, lp, u)
    return a + y, load


def _tables(cfg: MoEDecoderConfig, seq_len: int) -> dict:
    positions = jnp.arange(seq_len)[None, :]
    # in KINDS' order: a set's order differs from process to process, and
    # with it the traced program and its key in the compile cache
    return {kind: rope_tables(cfg, kind, positions)
            for kind in KINDS if kind in cfg.layer_kinds}


def hidden(params: dict, ids: jax.Array, cfg: MoEDecoderConfig) -> tuple:
    """The last layer's outcome, normed, ``[B, T, d]`` in ``cfg.dtype``, and
    each layer's held load ``[L, held_count]``."""
    tables = _tables(cfg, ids.shape[1])
    x = params["embed"][ids].astype(cfg.dtype)
    loads = []
    for kind, lp in zip(cfg.layer_kinds, params["layers"]):
        x, load = jax.checkpoint(functools.partial(_layer, cfg, kind))(
            lp, x, tables)
        loads.append(load)
    return llama.rmsnorm(x, params["final_norm"], cfg.norm_eps), \
        jnp.stack(loads)


def expert_choices(params: dict, ids: jax.Array,
                   cfg: MoEDecoderConfig) -> jax.Array:
    """``[L, B, T, top_k]``: the experts every token chose in every layer,
    in ascending order, as the forward pass of :func:`hidden` chooses them
    (for a comparison with a reference's choices; no step calls this)."""
    b, t = ids.shape
    tables = _tables(cfg, t)
    x = params["embed"][ids].astype(cfg.dtype)
    chosen = []
    for kind, lp in zip(cfg.layer_kinds, params["layers"]):
        a, u = _mixed(cfg, kind, lp, x, tables)
        experts, _ = latent_moe.route(cfg, lp, u.reshape(b * t, cfg.dim))
        chosen.append(jnp.sort(experts, axis=-1).reshape(b, t, cfg.top_k))
        x = a + _experts(cfg, lp, u)[0]
    return jnp.stack(chosen)


def logits(params: dict, ids: jax.Array, cfg: MoEDecoderConfig) -> jax.Array:
    """``[B, T, vocab_size]`` float32 over the held rows: for tests and
    evaluation (training never holds them: :func:`loss`)."""
    x, _ = hidden(params, ids, cfg)
    return jnp.dot(x, params["head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


def counters(cfg: MoEDecoderConfig, batch: int, seq_len: int, loads) -> dict:
    """What one step counts, as a tree of arrays: the router's choices and
    those that fell on a held expert, each held expert's load (over the
    layers), and the expert layer's calls (a layer and sequence).  The
    ``attn.key_blocks_*`` pair is not counted by the kernels: it is the size
    of the grids they were launched with (:func:`key_blocks_visited`, the
    helper that sizes them; of one kernel's pass: the forward, dQ and dK/dV
    kernels visit the same) against causal layers' throughout, so it says
    what the band was asked to save and cannot see a kernel that fetches
    more; the band kernels' traced time can."""
    heads = batch * cfg.n_heads
    blocks = functools.partial(key_blocks_visited, seq_len,
                               block_q=cfg.block_q, block_k=cfg.block_k)
    visited = sum(blocks(window=cfg.window if kind == "window" else None)
                  for kind in cfg.layer_kinds)
    return {
        "moe.choices_total": jnp.int32(
            batch * seq_len * cfg.top_k * cfg.n_layers),
        "moe.choices_held": jnp.sum(loads).astype(jnp.int32),
        "moe.held_load": jnp.sum(loads, axis=0).astype(jnp.int32),
        "moe.expert_calls": jnp.int32(cfg.n_layers * batch),
        "attn.key_blocks_visited": jnp.int32(heads * visited),
        "attn.key_blocks_causal": jnp.int32(
            heads * cfg.n_layers * blocks(window=None)),
    }


def read_counters(aux, registry=None) -> dict:
    """A step's (or many steps' summed) :func:`counters` read to the host as
    ``{name: int}``, the held load as ``moe.held_load.<e>``, and added to
    the registry's counters of those names."""
    reg = registry or metrics.DEFAULT
    out = {}
    for name, value in aux.items():
        value = jax.device_get(value)
        if name == "moe.held_load":
            out.update({f"{name}.{e}": int(v) for e, v in enumerate(value)})
        else:
            out[name] = int(value)
    for name, value in out.items():
        reg.counter(name).inc(value)
    return out


def publish_gauges(cfg: MoEDecoderConfig, registry=None) -> None:
    """``train.params_held`` and ``train.state_bytes`` (weights, gradients
    and AdamW's two moments in ``param_dtype``), set when a step is traced,
    beside ``fusion.*``'s."""
    reg = registry or metrics.DEFAULT
    n = param_count(cfg)
    reg.gauge("train.params_held").set(n)
    reg.gauge("train.state_bytes").set(
        4 * n * jnp.dtype(cfg.param_dtype).itemsize)


def loss_and_counters(params: dict, batch: tuple,
                      cfg: MoEDecoderConfig) -> tuple:
    """``(loss, counters)`` for ``make_train_step(..., has_aux=True)``:
    ``batch`` is ``(ids, targets)``, both ``[B, T]`` within the held rows."""
    ids, targets = batch
    publish_gauges(cfg)
    x, loads = hidden(params, ids, cfg)
    with jax.named_scope("xent.head"):
        value = fused_linear_cross_entropy(
            x.reshape(-1, cfg.dim), params["head"].astype(cfg.dtype),
            targets.reshape(-1), chunk_size=cfg.xent_chunk)
    return value, counters(cfg, *ids.shape, loads)


def loss(params: dict, batch: tuple, cfg: MoEDecoderConfig) -> jax.Array:
    """Mean next-token cross-entropy over the held rows of the vocabulary."""
    return loss_and_counters(params, batch, cfg)[0]
