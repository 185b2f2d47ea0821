"""Llama-3-family transformer — the flagship model (BASELINE config 5:
"Llama-3 8B data-parallel via DistributedOptimizer on v5p-128").

The reference has no transformer (its zoo is ResNet/MNIST-era); this is the
capability-extension model the baseline tracks, built TPU-first:

* **Stacked-layer ``lax.scan``**: all L layers' weights are stacked on a
  leading axis and the forward is one scanned block → O(1) HLO size, fast
  compiles at 8B scale, natural remat boundary.
* **bfloat16 activations / float32 master params** (cast at use).
* **GQA** (n_kv_heads < n_heads), rotary embeddings, SwiGLU, RMSNorm —
  matching Llama-3 architecture.  Rotary uses the half-split (HF/NeoX)
  convention, so HuggingFace-layout checkpoints map 1:1; Meta-native
  checkpoints need the standard per-head interleave→half permutation of
  wq/wk first.
* **Pluggable attention engine**: dense / blockwise (O(L) memory) /
  ring (sequence-parallel over a mesh axis) / ulysses (all-to-all SP) from
  :mod:`horovod_tpu.parallel.attention`, plus the pallas flash kernel.
* **Explicit partition specs** for DP/TP/SP: :func:`param_partition_specs`
  returns the GSPMD sharding pytree (megatron-style column/row splits) so
  ``jit(in_shardings=...)`` lays q/k/v/gate/up column-parallel and
  o/down row-parallel over the ``tp`` axis — XLA inserts the psums.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import paged
from horovod_tpu.parallel import attention as attn_mod


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.float32     # master weights
    attn_impl: str = "dense"  # dense | blockwise | ring | ulysses | ulysses_flash | flash
    attn_block_size: int = 512
    remat: bool = True                 # jax.checkpoint each scanned layer
    # Named jax.checkpoint policy for the layer remat — the middle ground
    # between remat=False (keep everything) and full remat (recompute
    # everything).  "dots_saveable" keeps every matmul output (incl.
    # attention scores) and recomputes only the cheap elementwise chains —
    # usually the best FLOPs/HBM trade on TPU.
    # "dots_with_no_batch_dims_saveable" keeps just the weight-projection
    # matmuls and also recomputes the head-batched attention einsums — a
    # notch more recompute/less memory than dots_saveable (NOT near-full
    # remat: the eight projections per layer are all saved).
    # None = full remat (save nothing).
    remat_policy: str | None = None
    # Chunked fused linear+cross-entropy (ops/fused_xent.py): loss without
    # the [B·L, V] logits tensor; None keeps the plain path.
    fused_loss_chunk: int | None = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


def llama3_8b(**overrides) -> LlamaConfig:
    return dataclasses.replace(LlamaConfig(), **overrides)


def llama_tiny(**overrides) -> LlamaConfig:
    """Test/dryrun configuration: same architecture, toy widths."""
    base = LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq_len=128, rope_theta=10000.0, remat=False,
    )
    return dataclasses.replace(base, **overrides)


def init_params(cfg: LlamaConfig, key: jax.Array) -> dict:
    """Stacked-layer parameter pytree.

    Layout (L = n_layers, D = dim, H·Dh = dim, K = n_kv_heads·head_dim,
    F = ffn_dim):
      embed      [V, D]
      layers:
        attn_norm [L, D]   wq [L, D, H·Dh]  wk [L, D, K]  wv [L, D, K]
        wo        [L, H·Dh, D]
        mlp_norm  [L, D]   w_gate [L, D, F] w_up [L, D, F] w_down [L, F, D]
      final_norm [D]
      lm_head    [D, V]
    """
    keys = jax.random.split(key, 10)
    d, f = cfg.dim, cfg.ffn_dim
    kdim = cfg.n_kv_heads * cfg.head_dim
    L = cfg.n_layers
    dt = cfg.param_dtype

    def dense_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, dt) / jnp.sqrt(fan_in)).astype(dt)

    return {
        "embed": dense_init(keys[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((L, d), dt),
            "wq": dense_init(keys[1], (L, d, d), d),
            "wk": dense_init(keys[2], (L, d, kdim), d),
            "wv": dense_init(keys[3], (L, d, kdim), d),
            "wo": dense_init(keys[4], (L, d, d), d),
            "mlp_norm": jnp.ones((L, d), dt),
            "w_gate": dense_init(keys[5], (L, d, f), d),
            "w_up": dense_init(keys[6], (L, d, f), d),
            "w_down": dense_init(keys[7], (L, f, d), f),
        },
        "final_norm": jnp.ones((d,), dt),
        "lm_head": dense_init(keys[8], (d, cfg.vocab_size), d),
    }


def param_partition_specs(cfg: LlamaConfig, *, tp_axis: str = "tp") -> dict:
    """Megatron-style tensor-parallel layout over ``tp_axis``.

    Column-parallel (output dim sharded): wq/wk/wv/w_gate/w_up + lm_head.
    Row-parallel (input dim sharded): wo/w_down — GSPMD inserts the psum
    after the row-parallel matmul, exactly the collective placement of
    hand-written Megatron TP, derived from these specs.
    """
    t = tp_axis
    return {
        "embed": P(None, t),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, t),
            "wk": P(None, None, t),
            "wv": P(None, None, t),
            "wo": P(None, t, None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, t),
            "w_up": P(None, None, t),
            "w_down": P(None, t, None),
        },
        "final_norm": P(None),
        "lm_head": P(None, t),
    }


_QKV = ("wq", "wk", "wv")


# hvdlint: disable=HVD001 -- runs once an engine, at construction, before any served program: one program a (shapes, tp_size), nothing dispatched in a step
@partial(jax.jit, static_argnums=3)
def _fuse_qkv(wq, wk, wv, tp: int):
    split = lambda w: w.reshape(*w.shape[:2], tp, -1)       # noqa: E731
    return jnp.concatenate([split(wq), split(wk), split(wv)], axis=-1)


def serving_params(params: dict, cfg: LlamaConfig, *, tp_size: int = 1) -> dict:
    """The tree as the paged programs read it (:mod:`horovod_tpu.models.
    paged`), made once from the tree :func:`init_params` describes: ``layers``
    holds, in place of ``wq`` / ``wk`` / ``wv``, their columns side by side,

      wqkv [L, D, tp, (H + 2 KVH) Dh / tp]

    shard ``j`` of ``tp_size`` holding ``[q_j | k_j | v_j]``, the heads a
    tensor-parallel shard computes, so that a layer reads them in one product
    and slices within a shard (:func:`_qkv_heads`).  One program on the device
    the weights are on writes it; every other leaf is the caller's own array,
    and the caller's tree is not touched.  A tree that already holds ``wqkv``
    (an engine's, handed to its clone) is returned as it is."""
    layers = params["layers"]
    if "wqkv" in layers:
        if layers["wqkv"].shape[2] != tp_size:
            raise ValueError(
                f"a serving tree laid out for tp_size="
                f"{layers['wqkv'].shape[2]} cannot serve tp_size={tp_size}")
        return params
    return _with_wqkv(params, _fuse_qkv(*(layers[k] for k in _QKV), tp_size))


def serving_partition_specs(cfg: LlamaConfig, *, tp_axis: str = "tp") -> dict:
    """:func:`param_partition_specs` of :func:`serving_params`' tree: the
    shards of ``wqkv`` are its third axis."""
    return _with_wqkv(param_partition_specs(cfg, tp_axis=tp_axis),
                      P(None, None, tp_axis, None))


def _with_wqkv(tree: dict, wqkv) -> dict:
    """``tree`` (the parameters or their specs) with ``wqkv`` in the place of
    its layers' ``wq`` / ``wk`` / ``wv``; a new tree over the same leaves."""
    rest = {k: v for k, v in tree["layers"].items() if k not in _QKV}
    return {**tree, "layers": {**rest, "wqkv": wqkv}}


def paged_cache_partition_specs(*, tp_axis: str = "tp") -> "PagedKVCache":
    """Head-sharded layout for the paged KV pool over ``tp_axis``.

    k/v ``[n_layers, n_blocks, block_size, KVH, Dh]`` shard on the KV-head
    axis — the same heads the column-parallel wk/wv produce locally, so a
    sharded decode writes its own head slice with zero cross-chip traffic
    and the per-chip pool holds ``KVH / tp`` heads (KV HBM split across
    chips).  ``block_table``/``length`` stay replicated: block ids are
    host-side bookkeeping, one logical block id addresses the same slot of
    every chip's head slice, which is what keeps the BlockPool / radix
    prefix cache / preemption replay shard-agnostic.
    """
    return PagedKVCache(
        k=P(None, None, None, tp_axis, None),
        v=P(None, None, None, tp_axis, None),
        block_table=P(),
        length=P(),
    )


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w.astype(x.dtype)


def rope_inv_freq(head_dim: int, theta: float) -> jax.Array:
    """Plain rotary: ``theta ** (-2i / head_dim)`` for ``i`` in the half."""
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def yarn_inv_freq(head_dim: int, theta: float, *, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jax.Array:
    """YaRN's frequencies as ``transformers`` computes them (``truncate``
    true): pair ``i`` keeps its plain frequency below ``low``, takes it
    divided by ``factor`` above ``high``, and a linear blend between, where
    ``low`` / ``high`` are the pairs that turn ``beta_fast`` / ``beta_slow``
    times over ``original_max`` positions."""
    half = head_dim // 2

    def pair_of(turns: float) -> float:
        return head_dim * math.log(original_max / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = min(max(math.floor(pair_of(beta_fast)), 0), half - 1)
    high = min(max(math.ceil(pair_of(beta_slow)), 0), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    plain = rope_inv_freq(head_dim, theta)
    return (1.0 - ramp) * plain + ramp * plain / factor


def rope_tables_from(inv_freq: jax.Array, positions: jax.Array,
                     attention_factor: float | None = None) -> tuple:
    """cos/sin tables for ``positions`` [..., L] → [..., L, head_dim//2],
    both times ``attention_factor`` where a scaling gives one (YaRN's)."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if attention_factor is None:
        return cos, sin
    return cos * attention_factor, sin * attention_factor


def rope_tables(cfg: LlamaConfig, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for ``positions`` [..., L] → [..., L, head_dim//2]."""
    return rope_tables_from(rope_inv_freq(cfg.head_dim, cfg.rope_theta),
                            positions)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotary rotation, half-split (HF/NeoX) convention: dimension i pairs
    with i + Dh/2.  x: [B, L, H, Dh]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x1 * s + x2 * c], axis=-1
    ).astype(x.dtype)


def _attention(cfg: LlamaConfig, q, k, v, *, positions_offset, sp_axis):
    impl = cfg.attn_impl
    if impl == "dense":
        return attn_mod.dense_attention(
            q, k, v, causal=True,
            q_offset=positions_offset, kv_offset=positions_offset,
        )
    if impl == "blockwise":
        return attn_mod.blockwise_attention(
            q, k, v, causal=True, block_size=cfg.attn_block_size,
            q_offset=positions_offset, kv_offset=positions_offset,
        )
    if impl == "ring":
        return attn_mod.ring_attention(q, k, v, axis_name=sp_axis, causal=True)
    if impl in ("ulysses", "ulysses_flash"):
        local = None
        if impl == "ulysses_flash":
            # Sequence-parallel a2a re-shard + the pallas kernel as the
            # local engine: the long-context fast path.
            from horovod_tpu.parallel.flash_attention import flash_attention

            local = flash_attention
        return attn_mod.ulysses_attention(
            q, k, v, axis_name=sp_axis, causal=True, impl=local
        )
    if impl == "flash":
        from horovod_tpu.parallel.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    raise ValueError(f"unknown attn_impl {impl!r}")


# Zero-config policies only: jax.checkpoint_policies also exposes policy
# FACTORIES (save_only_these_names, save_from_both_policies, ...) that
# take arguments — passing one of those bare to jax.checkpoint misbehaves
# at trace time instead of failing fast, hence the explicit allowlist.
_REMAT_POLICIES = (
    "dots_saveable",
    "dots_with_no_batch_dims_saveable",
    "everything_saveable",
    "nothing_saveable",
)


def _resolve_remat_policy(cfg: "LlamaConfig"):
    if cfg.remat_policy is None:
        return None
    if cfg.remat_policy not in _REMAT_POLICIES:
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; pick one of "
            f"{_REMAT_POLICIES}"
        )
    return getattr(jax.checkpoint_policies, cfg.remat_policy)


def forward(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions_offset: int | jax.Array = 0,
    sp_axis: str | None = None,
    return_hidden: bool = False,
) -> jax.Array:
    """Token ids [B, L] → logits [B, L, V].

    ``positions_offset``: global position of tokens[:, 0] (nonzero on
    sequence shards).  ``sp_axis``: mesh axis name for ring/ulysses
    attention (call under shard_map with the sequence axis sharded).
    ``return_hidden=True`` stops after the final norm ([B, L, D]) so the
    fused loss can stream the vocab projection itself.
    """
    if cfg.remat_policy is not None and not cfg.remat:
        raise ValueError(
            "remat_policy is set but remat=False — policy-based remat "
            "needs remat=True (remat_policy alone does nothing)"
        )
    _resolve_remat_policy(cfg)      # fail fast on a bad name either way
    b, l = tokens.shape
    dt = cfg.dtype
    # gather first, THEN cast: converts [B, L, D] activations, not a full
    # [V, D] bf16 copy of the table (~1 GB at 8B scale) every step.
    x = params["embed"][tokens].astype(dt)  # [B, L, D]
    positions = positions_offset + jnp.arange(l)[None, :]
    cos, sin = rope_tables(cfg, jnp.broadcast_to(positions, (b, l)))

    def layer(x, lp):
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["wq"].astype(dt)).reshape(b, l, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"].astype(dt)).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"].astype(dt)).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        o = _attention(cfg, q, k, v, positions_offset=positions_offset,
                       sp_axis=sp_axis)
        x = x + o.reshape(b, l, cfg.dim) @ lp["wo"].astype(dt)
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ lp["w_gate"].astype(dt))
        up = h @ lp["w_up"].astype(dt)
        x = x + (gate * up) @ lp["w_down"].astype(dt)
        return x, None

    if cfg.remat:
        layer = jax.checkpoint(layer, policy=_resolve_remat_policy(cfg))

    x, _ = lax.scan(layer, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x
    logits = x @ params["lm_head"].astype(dt)
    return logits.astype(jnp.float32)


def loss_fn(
    params: dict, batch: tuple[jax.Array, jax.Array], cfg: LlamaConfig,
    **fw_kwargs,
) -> jax.Array:
    """Next-token cross-entropy; batch = (tokens [B, L], targets [B, L]).

    With ``cfg.fused_loss_chunk`` the vocab projection and the softmax run
    chunk-by-chunk (ops/fused_xent.py) — same math, no [B·L, V] logits
    residency."""
    tokens, targets = batch
    # `is not None`, not truthiness: fused_loss_chunk=0 must hit the op's
    # chunk validation, not silently select the materialized path.
    if cfg.fused_loss_chunk is not None:
        from horovod_tpu.ops.fused_xent import fused_linear_cross_entropy

        hidden = forward(params, tokens, cfg, return_hidden=True,
                         **fw_kwargs)
        b, l, d = hidden.shape
        return fused_linear_cross_entropy(
            hidden.reshape(b * l, d),
            params["lm_head"].astype(cfg.dtype),
            targets.reshape(-1),
            chunk_size=cfg.fused_loss_chunk,
        )
    logits = forward(params, tokens, cfg, **fw_kwargs)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def make_loss_fn(cfg: LlamaConfig, **fw_kwargs) -> Callable:
    return partial(loss_fn, cfg=cfg, **fw_kwargs)


def num_params(cfg: LlamaConfig) -> int:
    d, f, L, v = cfg.dim, cfg.ffn_dim, cfg.n_layers, cfg.vocab_size
    kdim = cfg.n_kv_heads * cfg.head_dim
    per_layer = 2 * d + d * d * 2 + 2 * d * kdim + 3 * d * f
    return v * d * 2 + L * per_layer + d


# ---------------------------------------------------------------------------
# Autoregressive decoding with a KV cache (inference path).
#
# The reference's inference story is "load the checkpoint, run it in one
# process" (its docs/inference.md); for a transformer that means prefill +
# cached decode.  TPU-first shape: the cache is a static [n_layers, B,
# max_len, KVH, Dh] buffer updated with dynamic_update_slice, the decode
# step is one scanned layer block (same stacked-params layout as forward),
# and generation is a lax.scan over steps — one compiled program, no
# per-token retracing.
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Per-layer key/value buffers: k/v [n_layers, B, max_len, KVH, Dh];
    ``length`` is the number of filled positions — a scalar int32 when all
    rows are in lockstep (the fast path: one dynamic_update_slice per
    step), or [B] int32 for ragged rows (continuous-batching shape: each
    row's next write lands at its own position via scatter)."""

    k: jax.Array
    v: jax.Array
    length: jax.Array


def init_cache(cfg: LlamaConfig, batch_size: int, max_len: int) -> KVCache:
    shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, cfg.dtype),
        v=jnp.zeros(shape, cfg.dtype),
        length=jnp.zeros((), jnp.int32),
    )


def _validate_lengths(lengths, b: int, l: int, fn: str) -> None:
    """Concrete-value precondition check for ragged ``lengths`` [B] in
    [1, padded width]; traced values are the caller's contract."""
    if lengths is None or isinstance(lengths, jax.core.Tracer):
        return
    ln = np.asarray(lengths)
    if ln.shape != (b,) or ln.min() < 1 or ln.max() > l:
        raise ValueError(
            f"{fn} lengths must be [batch]={b} values in [1, padded "
            f"width {l}], got shape {ln.shape} range "
            f"[{ln.min() if ln.size else '-'}, "
            f"{ln.max() if ln.size else '-'}]")


def prefill(
    params: dict, tokens: jax.Array, cfg: LlamaConfig, cache: KVCache,
    lengths: jax.Array | None = None,
) -> tuple[jax.Array, KVCache]:
    """Run the prompt through the model, filling cache[:, :, :L].

    Returns (last-position logits [B, V], updated cache).  Uses the same
    stacked-layer scan as :func:`forward`; attention is the configured
    engine (the flash kernel applies here — prefill is the MXU-bound
    phase).

    ``lengths`` [B]: optional per-row prompt lengths for RIGHT-padded
    ragged batches (continuous-batching shape), each in [1, L].
    Causality already keeps valid queries from seeing the padded tail,
    the returned logits come from each row's last valid position, and
    the cache becomes per-row-length (pad slots carry garbage K/V that
    the decode mask never reads and later writes overwrite).
    """
    b, l = tokens.shape
    _validate_lengths(lengths, b, l, "prefill")
    dt = cfg.dtype
    x = params["embed"][tokens].astype(dt)
    positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
    cos, sin = rope_tables(cfg, positions)

    def layer(x, lp):
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["wq"].astype(dt)).reshape(b, l, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"].astype(dt)).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"].astype(dt)).reshape(b, l, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        o = _attention(cfg, q, k, v, positions_offset=0, sp_axis=None)
        x = x + o.reshape(b, l, cfg.dim) @ lp["wo"].astype(dt)
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ lp["w_gate"].astype(dt))
        up = h @ lp["w_up"].astype(dt)
        x = x + (gate * up) @ lp["w_down"].astype(dt)
        return x, (k, v)

    x, (ks, vs) = lax.scan(layer, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if lengths is None:
        last = x[:, -1]
        new_len = jnp.asarray(l, jnp.int32)
    else:
        last = x[jnp.arange(b), jnp.asarray(lengths, jnp.int32) - 1]
        new_len = jnp.asarray(lengths, jnp.int32)     # [B] — ragged cache
    logits = (last @ params["lm_head"].astype(dt)).astype(jnp.float32)
    cache = KVCache(
        k=lax.dynamic_update_slice(cache.k, ks, (0, 0, 0, 0, 0)),
        v=lax.dynamic_update_slice(cache.v, vs, (0, 0, 0, 0, 0)),
        length=new_len,
    )
    return logits, cache


def decode_step(
    params: dict, token: jax.Array, cfg: LlamaConfig, cache: KVCache,
) -> tuple[jax.Array, KVCache]:
    """One autoregressive step: ``token`` [B] → logits [B, V] + cache.

    Attends over the cached keys/values (masked past ``length``); the new
    position's K/V are written at index ``length``.  Decode is
    matvec-bound, so attention is a plain masked einsum in f32 — no kernel
    needed.

    A scalar ``cache.length`` is the lockstep fast path (one
    dynamic_update_slice per step); a [B] ``cache.length`` (ragged
    prefill / continuous batching) delegates to :func:`decode_chunk`
    with T=1 — identical math, per-row scatter writes and masks.
    """
    if jnp.ndim(cache.length) > 0:           # ragged: one code path (T=1)
        logits, cache = decode_chunk(params, token[:, None], cfg, cache)
        return logits[:, 0], cache
    b = token.shape[0]
    dt = cfg.dtype
    max_len = cache.k.shape[2]
    pos = cache.length                       # scalar int32
    x = params["embed"][token][:, None, :].astype(dt)     # [B, 1, D]
    cos, sin = rope_tables(cfg, jnp.broadcast_to(pos, (b, 1)))
    n_rep = cfg.n_heads // cfg.n_kv_heads
    scale = 1.0 / (cfg.head_dim ** 0.5)
    # mask over cache positions: attend to [0, pos] inclusive —
    # broadcasts over the [B, KVH, R, 1, M] score layout
    valid = (jnp.arange(max_len) <= pos)[None, None, None, None, :]

    def layer(x, inputs):
        lp, kc, vc = inputs                               # kc/vc [B, M, KVH, Dh]
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["wq"].astype(dt)).reshape(b, 1, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"].astype(dt)).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"].astype(dt)).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        kc = lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
        vc = lax.dynamic_update_slice(vc, v, (0, pos, 0, 0))
        # GQA via grouped einsum: fold the query heads onto their KV head
        # ([B, 1, H, Dh] → [B, 1, KVH, R, Dh], q head h ↔ kv head h//R —
        # the same mapping _repeat_kv uses) instead of materializing the
        # repeat-expanded cache.  The expansion would read/write R× the
        # cache per step — decode's whole cost is cache traffic — while
        # the grouped form reads it once and hands the MXU R query rows
        # per KV-head matmul instead of one.
        qg = q.reshape(b, 1, cfg.n_kv_heads, n_rep, cfg.head_dim)
        s = jnp.einsum(
            "bqkrd,bmkd->bkrqm", qg.astype(jnp.float32),
            kc.astype(jnp.float32)
        ) * scale                                         # [B, KVH, R, 1, M]
        s = jnp.where(valid, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkrqm,bmkd->bqkrd", p, vc.astype(jnp.float32))
        x = x + o.astype(dt).reshape(b, 1, cfg.dim) @ lp["wo"].astype(dt)
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ lp["w_gate"].astype(dt))
        up = h @ lp["w_up"].astype(dt)
        x = x + (gate * up) @ lp["w_down"].astype(dt)
        return x, (kc, vc)

    x, (ks, vs) = lax.scan(layer, x, (params["layers"], cache.k, cache.v))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ params["lm_head"].astype(dt)).astype(jnp.float32)
    return logits, KVCache(k=ks, v=vs, length=pos + 1)


def decode_chunk(
    params: dict, tokens: jax.Array, cfg: LlamaConfig, cache: KVCache,
) -> tuple[jax.Array, KVCache]:
    """Consume T tokens per row in ONE pass: ``tokens`` [B, T] →
    (logits [B, T, V], cache advanced by T).

    The T-token generalization of :func:`decode_step` (same per-row
    position/mask machinery, scalar or [B] ``cache.length``): token j of
    row r lands at cache position ``pos_r + j`` and attends to
    ``[0, pos_r + j]``.  Logits at every chunk position come back — this
    is the verification pass of speculative decoding (one MXU-friendly
    T-row matmul instead of T matvecs) and equally the chunked-prefill
    building block for feeding long prompts through a bounded window.
    """
    b, t = tokens.shape
    dt = cfg.dtype
    max_len = cache.k.shape[2]
    pos = cache.length
    posv = pos if jnp.ndim(pos) > 0 else jnp.broadcast_to(pos, (b,))
    x = params["embed"][tokens].astype(dt)                # [B, T, D]
    qpos = posv[:, None] + jnp.arange(t)[None, :]         # [B, T]
    cos, sin = rope_tables(cfg, qpos)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    scale = 1.0 / (cfg.head_dim ** 0.5)
    # key m visible to query j of row r iff m <= pos_r + j
    valid = jnp.arange(max_len)[None, None, :] <= qpos[:, :, None]
    valid = valid[:, None, None, :, :]                    # [B,1,1,T,M]
    rows = jnp.arange(b)[:, None]

    def layer(x, inputs):
        lp, kc, vc = inputs
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["wq"].astype(dt)).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"].astype(dt)).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"].astype(dt)).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        kc = kc.at[rows, qpos].set(k)                     # [B,T,…] scatter
        vc = vc.at[rows, qpos].set(v)
        qg = q.reshape(b, t, cfg.n_kv_heads, n_rep, cfg.head_dim)
        s = jnp.einsum(
            "bqkrd,bmkd->bkrqm", qg.astype(jnp.float32),
            kc.astype(jnp.float32)
        ) * scale                                         # [B,KVH,R,T,M]
        s = jnp.where(valid, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkrqm,bmkd->bqkrd", p, vc.astype(jnp.float32))
        x = x + o.astype(dt).reshape(b, t, cfg.dim) @ lp["wo"].astype(dt)
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ lp["w_gate"].astype(dt))
        up = h @ lp["w_up"].astype(dt)
        x = x + (gate * up) @ lp["w_down"].astype(dt)
        return x, (kc, vc)

    x, (ks, vs) = lax.scan(layer, x, (params["layers"], cache.k, cache.v))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(dt)).astype(jnp.float32)
    return logits, KVCache(k=ks, v=vs, length=pos + t)


# ---------------------------------------------------------------------------
# Block/paged KV cache (vLLM/PagedAttention layout, SOSP '23).
#
# The dense KVCache above reserves a full [B, max_len] stripe per slot; a
# serving pool that recycles slots wants cache memory to follow the LIVE
# requests instead.  Here K/V live in a pool of fixed-size blocks
# ([n_layers, n_blocks, block_size, KVH, Dh]) and each slot owns an int32
# ``block_table`` row mapping its logical positions to physical blocks.
# Admission allocates just the blocks a request needs; retirement returns
# them — all on the host, with device programs keeping ONE compiled
# signature (the tables are data, not shapes, so admission never retraces).
# Those programs donate the pool and ``_paged_attend`` carries it through
# the layer scan, so it is updated in place: a step moves the positions it
# writes and the blocks attention reads, never a block it does not touch.
# Attention's work follows the live rows: it walks the block tables tile by
# tile with a running softmax, the rows in groups of like length, each group
# up to a bound read from its rows' lengths, and neither gathers nor scores
# the depth a table could hold.
#
# Block 0 is the TRASH block: it is never allocated, and unallocated table
# entries point at it.  Free/idle rows that tick along with the batch (the
# fixed-signature tick decodes every row) scatter their garbage K/V into
# trash, where nothing valid ever reads it — the paged form of the slot
# pool's write-before-read invariant.
# ---------------------------------------------------------------------------


class PagedKVCache(NamedTuple):
    """Paged K/V pool: k/v ``[n_layers, n_blocks, block_size, KVH, Dh]``,
    ``block_table`` [B, blocks_per_slot] int32 (physical block of each
    logical block; 0 = trash), ``length`` [B] int32 filled positions."""

    k: jax.Array
    v: jax.Array
    block_table: jax.Array
    length: jax.Array

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def logical_len(self) -> int:
        """Positions each row's table can map (== max_len): the bound on a
        row's length, not a width attention pays (``_paged_attend`` reads
        the blocks up to the longest row of each group of live rows)."""
        return self.block_table.shape[1] * self.k.shape[2]


def init_paged_cache(
    cfg: LlamaConfig, n_slots: int, max_len: int, *,
    block_size: int, n_blocks: int | None = None,
) -> PagedKVCache:
    """A paged pool for ``n_slots`` rows of logical depth ``max_len``.

    ``n_blocks`` defaults to full backing (every slot can hold max_len)
    plus the trash block; pass less to overcommit — the paged win — and
    let the scheduler admission-gate on free blocks."""
    if max_len % block_size:
        raise ValueError(
            f"max_len {max_len} not a multiple of block_size {block_size}")
    per = max_len // block_size
    if n_blocks is None:
        n_blocks = n_slots * per + 1          # +1: the trash block
    if n_blocks < per + 1:
        raise ValueError(
            f"n_blocks {n_blocks} cannot back even one full slot "
            f"({per} blocks) plus the trash block")
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    return PagedKVCache(
        k=jnp.zeros(shape, cfg.dtype),
        v=jnp.zeros(shape, cfg.dtype),
        block_table=jnp.zeros((n_slots, per), jnp.int32),
        length=jnp.zeros((n_slots,), jnp.int32),
    )


def paged_pool_bytes(pcache: PagedKVCache) -> dict:
    """Device bytes one block holds in each pool (all layers): part of the
    paged model interface (:mod:`horovod_tpu.models.paged`)."""
    return {name: int(np.prod(a.shape) // a.shape[1]) * a.dtype.itemsize
            for name, a in (("k", pcache.k), ("v", pcache.v))}


def paged_counters(pcache: PagedKVCache) -> None:
    """This model keeps no counters on the device."""
    return None


def publish_paged_metrics(metrics, cfg, pcache, stats_host=None,
                          row_blocks=(), programs=(), *,
                          walk_split: int = 1) -> None:
    """Beside the engine's ``kv.*`` this model has three counters, of how
    closely its attention's walk follows the rows: ``attn.blocks_live`` (the
    blocks the read rows' own positions span) within ``attn.blocks_visited``
    (what the programs read) within ``attn.blocks_in_table``.  ``programs``
    holds each program a step dispatched as the host knows it from its slots
    (:class:`paged.Dispatched`); nothing is read back.  ``walk_split``: the
    entries the walk's table has for one block of the pool's (a model that
    walks a block in pieces counts in pieces)."""
    bs, per = pcache.block_size // walk_split, \
        pcache.block_table.shape[1] * walk_split
    walked = [paged_blocks_walked(p.lengths, p.active, p.t, bs, per)
              for p in programs]
    metrics.counter("attn.blocks_visited").inc(sum(v for v, _ in walked))
    metrics.counter("attn.blocks_live").inc(sum(n for _, n in walked))
    metrics.counter("attn.blocks_in_table").inc(
        per * sum(p.rows for p in programs))


def tp_split_dims(cfg: LlamaConfig) -> tuple:
    """``(name, size)`` of every axis tensor-parallel serving splits."""
    return (("n_heads", cfg.n_heads), ("n_kv_heads", cfg.n_kv_heads),
            ("dim", cfg.dim), ("ffn_dim", cfg.ffn_dim),
            ("vocab_size", cfg.vocab_size))


class BlockPool:
    """Host-side reference-counted allocator over the paged pool's
    physical blocks — the free-list's successor once blocks can be
    SHARED across slot rows (prefix caching: one physical block mapped
    by many block-table rows).

    Every physical block (1..n_blocks-1; block 0 is trash and never
    allocated) is in exactly one of three states:

    * **free** — on the free list, content garbage, allocatable;
    * **referenced** — mapped by >= 1 live rows (``refcount(b)`` users);
      never reclaimed while any reference remains;
    * **cached** — zero references but *indexed* by a prefix index
      (:class:`horovod_tpu.prefix_cache.RadixPrefixCache`): content is
      a valid, immutable KV chunk kept for future reuse.  Cached blocks
      sit in LRU order and are reclaimed by the index's eviction walk
      when admission needs them — eviction of cache always precedes
      preemption of live rows.

    The pool is policy-free: it tracks states and counts; *which*
    cached block to evict (leaf-first, LRU) is the radix index's call,
    because evictability depends on tree structure the pool can't see.
    All bookkeeping is host-side — device programs never observe any of
    it (block tables change data, never shapes).
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(
                f"n_blocks {n_blocks} leaves no allocatable block "
                f"beyond trash block 0")
        self.n_blocks = n_blocks
        # pop() takes low ids first, matching the old free-list order so
        # cache-off engines allocate bit-identical block layouts
        self._free = list(range(n_blocks - 1, 0, -1))
        self._ref: dict[int, int] = {}       # block -> live references
        self._indexed: set[int] = set()      # owned by a prefix index
        self._lru: dict[int, None] = {}      # zero-ref indexed, LRU order
        #: called with a block as it returns to the free list (its content
        #: is garbage from here on): what else is kept under the block's id
        #: goes with it (a snapshot entry, paged.SnapshotBudget.drop)
        self.on_free = None

    # -- counts ------------------------------------------------------------

    def free_count(self) -> int:
        return len(self._free)

    def cached_count(self) -> int:
        return len(self._lru)

    def ref_count(self) -> int:
        """Blocks currently mapped by at least one live row."""
        return len(self._ref)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    # -- allocation / references -------------------------------------------

    def alloc(self) -> int:
        """Take a free block (caller increfs it when a row maps it).
        Raises IndexError when the free list is empty — callers gate on
        ``free_count()`` (and evict cache first when they can)."""
        return self._free.pop()

    def incref(self, block: int) -> int:
        """One more row maps ``block``; a cached block leaves the LRU
        (it is pinned while referenced — eviction can't touch it)."""
        self._lru.pop(block, None)
        n = self._ref.get(block, 0) + 1
        self._ref[block] = n
        return n

    def decref(self, block: int) -> int:
        """One row unmapped ``block``.  At zero references an indexed
        block parks in the LRU cache (release-to-cache); an unindexed
        one returns to the free list."""
        n = self._ref[block] - 1
        if n > 0:
            self._ref[block] = n
            return n
        del self._ref[block]
        if block in self._indexed:
            self._lru[block] = None          # MRU end
        else:
            self._to_free_list(block)
        return 0

    def _to_free_list(self, block: int) -> None:
        self._free.append(block)
        if self.on_free is not None:
            self.on_free(block)

    # -- index ownership ----------------------------------------------------

    def mark_indexed(self, block: int) -> None:
        """A prefix index now owns ``block``'s content (it became a tree
        node): zero-ref no longer means free, it means cached."""
        self._indexed.add(block)

    def drop_indexed(self, block: int) -> None:
        """The index evicted ``block`` (must be zero-ref): back to the
        free list."""
        if block in self._ref:
            raise RuntimeError(
                f"evicting block {block} with {self._ref[block]} live "
                f"references")
        self._indexed.discard(block)
        self._lru.pop(block, None)
        self._to_free_list(block)

    def lru_blocks(self) -> list[int]:
        """Zero-ref cached blocks, least-recently-used first (the
        eviction candidate order)."""
        return list(self._lru)

    def state_lines(self) -> list[str]:
        """Human-readable pool picture for scheduler state dumps."""
        shared = {b: n for b, n in sorted(self._ref.items()) if n > 1}
        return [
            f"block pool: free={len(self._free)} "
            f"cached_zero_ref={len(self._lru)} "
            f"referenced={len(self._ref)} "
            f"of {self.n_blocks - 1} allocatable",
            f"  lru (old->new)={list(self._lru)} shared_refcounts="
            f"{shared if shared else '{}'}",
        ]


# Key positions one step of ``_paged_attend``'s loop scores per row, in
# whole pool blocks.  Measured on one v5e at Mistral-7B widths, blocks of
# 256: tiles of 512-1,024 keys are fastest for the one-token tick and the
# 256-token chunk alike (PERF.md, PR 28), so the tile follows the block
# size alone.
_KEY_TILE = 512

# Rows of a program that walk their key tiles together, to one bound: the
# group's longest row's.  A program of more rows is walked group by group,
# its rows in the order of their own bounds, so a short row stops where the
# short rows beside it stop.  Measured on one v5e (PERF.md, PR 34): the tick
# of 64 rows over tables of 64 tiles, 32 of them decoding at 1.3-31 k, takes
# 39.8 ms as one group, 21.3 in groups of 16 rows, 19.4 of 8, 18.6 of 4,
# 18.9 of 2; a least-squares fit of those readings gives 13.3 ms + 3.1 us a
# trip of a group's loop + 3.8 us a row and tile.  Eight and not four: four
# gains 4 % on that mix (read), and by the fit, not by a reading (no cell
# holds such rows at depth), where every row is as long as the longest, so
# that no grouping saves a byte, the trips of 8 rows a group would cost 6 %
# and of 4 rows 13 %.
_ROW_GROUP = 8


def _tile_blocks(block_size: int, per: int) -> int:
    """Pool blocks of each row that one step of the key loop reads."""
    return max(1, min(per, _KEY_TILE // block_size))


def _row_groups(rows: int, n_tiles: int, t: int = 1) -> tuple[int, int]:
    """``(groups, rows a group)`` of the walk of a program of ``t`` tokens a
    row: groups of :data:`_ROW_GROUP` rows, but no more groups than the table
    is deep in tiles, since the groups' bounds can differ by no more than
    that and each group is a loop of its own (a table of four tiles under 128
    rows is walked in four groups of 32).  One group is the whole program: a
    chunk's one row, and any program of no more rows than a group holds.  A
    row that brings more queries than a group has rows (a prefill chunk's)
    is a group of its own: a tile then costs its row ``t`` times what it
    costs a tick's, a loop's trip what it ever did, so rows of unlike length
    that shared a bound would pay the longest's tiles ``t`` queries each
    (``kexaone_mixedq``, PERF.md, PR 39: pairs of rows to their longer row's
    bound made a program of two slower than two of one)."""
    if t > _ROW_GROUP:
        return rows, 1
    r = -(-rows // max(1, min(-(-rows // _ROW_GROUP), n_tiles)))
    return -(-rows // r), r             # no group is all padding


def paged_blocks_walked(lengths, active, t: int, block_size: int,
                        per: int) -> tuple[int, int]:
    """``(visited, live)`` of a program over ``t`` tokens a row whose rows
    hold ``lengths`` [B] positions and of whose rows ``active`` [B] are read.
    ``visited``: the blocks of the rows' tables the program reads, each row
    whole key tiles up to its group's bound, no further than the table: the
    host's form of the trip counts :func:`tile_walk` reads from ``qpos``.
    A table entry counts once: the places :func:`tile_walk` pads the first
    group with walk the shortest row's tiles again, to the group's bound as
    that row does, and read no entry it does not (``pad x bound[0]`` tiles a
    layer of traffic that this count leaves out; fewer places than a group).
    ``live``: the blocks the active rows' own query positions span, what a
    walk with no tile and no group would read."""
    g = _tile_blocks(block_size, per)
    n_tiles = -(-per // g)
    end = np.asarray(lengths, np.int64) + (t - 1)   # the last query positions
    active = np.asarray(active) > 0
    last = np.where(active,
                    np.minimum(end // (g * block_size) + 1, n_tiles), 1)
    groups, r = _row_groups(len(last), n_tiles, t)
    # as the device orders them: by last tile, padded in front with the
    # shortest row (fewer places than a group), a group's bound its last row's
    pad = groups * r - len(last)
    bound = np.sort(last)[np.arange(1, groups + 1) * r - (pad + 1)]
    in_group = np.full(groups, r)
    in_group[0] -= pad
    visited = int(np.dot(np.minimum(bound * g, per), in_group))
    live = int(np.minimum(end // block_size + 1, per)[active].sum())
    return visited, live


class TileWalk(NamedTuple):
    """What one program's walk over key tiles shares between its layers.
    The rows stand in groups (:func:`_row_groups`), in the order of their own
    last tiles: ``table`` [groups, R, n_tiles * g] (the rows' block tables,
    padded with trash to whole tiles) and ``qpos`` [groups, R, T] in that
    order, ``g`` blocks a tile spans, ``n_live`` [groups] the tiles up to
    each group's longest row's last query position (traced bounds: data, not
    shape), ``m`` the table's logical depth, and between the program's rows
    and the walk's ``order`` [groups * R] (the row at each place; ``None``
    where one group holds the rows as they stand) and ``place`` [B] (each
    row's place)."""

    table: jax.Array
    qpos: jax.Array
    g: int
    n_live: jax.Array
    m: int
    order: jax.Array | None
    place: jax.Array | None


def tile_walk(table: jax.Array, qpos: jax.Array, bs: int,
              active: jax.Array | None = None, *,
              span: int = 1) -> TileWalk:
    """The walk of a program whose queries stand at ``qpos`` [B, T] under
    block tables ``table`` [B, per] of ``bs``-position blocks, computed once
    for all its layers.  ``active`` [B] marks the rows whose outputs are read
    (default: all).  A row that is not walks one tile whatever its length: a
    free slot, a row still prefilling, a row held in place.  Every query
    still sees key 0, so its softmax has a real maximum and a sum above
    zero; its output is of a prefix of its keys, and nobody reads it.

    ``span`` is the mask inside the program's own tokens: 1 is causal (a
    query sees the keys up to its own position); ``span`` > 1 is
    **block-causal** over spans of that many positions, ``key_pos // span <=
    query_pos // span``: a query sees every earlier span and the whole of its
    own, in both directions (a model that denoises a span at a time).  The
    walk stores, in place of a query's position, the last position it sees,
    which is all :func:`paged_attend_tiles` asks of it; ``span`` has to
    divide a key tile, so the tiles a row walks are the same."""
    if span > 1:        # a causal program is traced as it ever was
        qpos = (qpos // span + 1) * span - 1
    b, per = table.shape
    g = _tile_blocks(bs, per)               # blocks a key tile spans
    n_tiles = -(-per // g)
    table = jnp.pad(table, ((0, 0), (0, n_tiles * g - per)))  # with trash
    last = jnp.minimum(qpos[:, -1] // (g * bs) + 1, n_tiles)  # [B] tiles
    if active is not None:
        last = jnp.where(jnp.asarray(active) > 0, last, 1)
    groups, r = _row_groups(b, n_tiles, qpos.shape[1])
    if groups == 1:     # today's loop: the rows as they stand, one bound
        return TileWalk(table=table[None], qpos=qpos[None], g=g,
                        n_live=jnp.max(last)[None], m=per * bs,
                        order=None, place=None)
    by_last = jnp.argsort(last)
    pad = groups * r - b                    # places in front: the shortest
    order = jnp.concatenate([jnp.broadcast_to(by_last[:1], (pad,)), by_last])
    place = jnp.zeros((b,), jnp.int32).at[by_last].set(
        pad + jnp.arange(b, dtype=jnp.int32))
    return TileWalk(
        table=table[order].reshape(groups, r, -1),
        qpos=qpos[order].reshape(groups, r, -1), g=g,
        n_live=last[order].reshape(groups, r)[:, -1], m=per * bs,
        order=order, place=place)


def paged_attend_tiles(q, k, v, kf, vf, layer, walk: TileWalk, wflat,
                       n_blocks: int, bs: int, scale: float | None = None):
    """One layer's paged attention, for any model whose keys and values are
    ``[.., KVH, Dh]`` rows of flat pools: scatter the chunk's ``k`` / ``v``
    [B, T, KVH, Dh] into ``kf`` / ``vf`` ``[n_pool_layers * n_blocks * bs,
    KVH, Dh]`` at ``wflat`` [B, T] within pool layer ``layer``'s stripe, then
    attend ``q`` [B, T, H, Dh] (rotated, unscaled) over the row's blocks a
    key tile at a time with a running softmax, each of ``walk``'s groups of
    rows no further than its own ``walk.n_live`` tiles, scores scaled by
    ``scale`` (``1 / sqrt(Dh)`` where none is given).  A tile past a shorter
    row's frontier (its table points at trash there) is masked to an
    exact-zero softmax term, so within a row the walk's grouping changes
    nothing: the same tiles in the same order, and past them terms of zero.
    Products of K and V as stored, accumulated in float32; maximum, sum and
    output accumulator float32; one division after the loop.  Returns the
    heads' outputs [B, T, KVH, H / KVH, Dh] (float32) and the two pools."""
    b, t, n_heads, dh = q.shape
    kvh = k.shape[2]
    n_rep = n_heads // kvh
    scale = 1.0 / (dh ** 0.5) if scale is None else scale
    g, w = walk.g, walk.g * bs
    off = layer * (n_blocks * bs)           # the layer's flat positions
    kf = kf.at[wflat + off].set(k)
    vf = vf.at[wflat + off].set(v)
    kb = kf.reshape(-1, bs, kvh, dh)        # a bitcast
    vb = vf.reshape(-1, bs, kvh, dh)
    qg = q.reshape(b, t, kvh, n_rep, dh)

    def rows_walk(group):
        q_rows, qpos, table, n_live = group     # [R, T, ..], [R, T], [R, ..]
        r = q_rows.shape[0]
        stat = (r, kvh, n_rep, t)

        def tile(j, acc):
            mx, den, o = acc
            blk = lax.dynamic_slice_in_dim(table, j * g, g, axis=1)
            blk = blk + layer * n_blocks                      # [R, G]
            kt = kb[blk].reshape(r, w, kvh, dh)
            vt = vb[blk].reshape(r, w, kvh, dh)
            s = jnp.einsum("bqkrd,bmkd->bkrqm", q_rows, kt,
                           preferred_element_type=jnp.float32) * scale
            kpos = j * w + jnp.arange(w)
            seen = (kpos <= qpos[:, :, None]) & (kpos < walk.m)  # [R, T, W]
            s = jnp.where(seen[:, None, None], s, NEG_INF_LOGIT)
            mx_new = jnp.maximum(mx, jnp.max(s, axis=-1))
            p = jnp.exp(s - mx_new[..., None])            # [R,KVH,Rep,T,W]
            fade = jnp.exp(mx - mx_new)
            den = fade * den + jnp.sum(p, axis=-1)
            o = fade[..., None] * o + jnp.einsum(
                "bkrqm,bmkd->bkrqd", p, vt.astype(jnp.float32))
            return mx_new, den, o

        # every query sees key 0, so the first tile makes `mx` a real
        # maximum and a masked term is exp(-1e30 - mx) == 0 from there on
        _, den, o = lax.fori_loop(
            0, n_live, tile,
            (jnp.full(stat, NEG_INF_LOGIT, jnp.float32),
             jnp.zeros(stat, jnp.float32),
             jnp.zeros(stat + (dh,), jnp.float32)))
        return jnp.moveaxis(o / den[..., None], 3, 1)     # [R,T,KVH,Rep,Dh]

    if walk.order is None:      # one group: no outer loop, no permutation
        return rows_walk((qg, walk.qpos[0], walk.table[0],
                          walk.n_live[0])), kf, vf
    r = walk.table.shape[1]
    o = lax.map(rows_walk, (qg[walk.order].reshape((-1, r) + qg.shape[1:]),
                            walk.qpos, walk.table, walk.n_live))
    return o.reshape((-1,) + o.shape[2:])[walk.place], kf, vf


def _qkv_heads(h, lp, cfg: LlamaConfig):
    """One layer's queries, keys and values of ``h`` [B, T, D], in heads
    ([B, T, H, Dh] and twice [B, T, KVH, Dh]): one product with the layer's
    ``wqkv`` [D, tp, n / tp] of the serving tree (:func:`serving_params`).

    The product's output is **sliced before it is reshaped into heads**.
    Compiled for the TPU, a product whose output goes straight into a
    reshape to heads takes the reshape into itself, then wants its weight
    with ``D`` minor, and gets it by staging each layer's slice of the
    stacked weights out of HBM as an op of its own and transposing the copy
    (16 % of ``mistral7b_chat``'s window: PERF.md, PR 42).  With a slice
    between, the weight streams from HBM inside the product's fusion, as
    ``wo`` and the MLP's do.  ``tests/test_chip_compile.py`` holds the
    compiled programs to that."""
    b, t, _ = h.shape
    dh = cfg.head_dim
    w = lp["wqkv"].astype(cfg.dtype)
    qkv = jnp.einsum("btd,dsn->btsn", h, w)         # a shard's [q | k | v]
    nq, nk = (n * dh // w.shape[1] for n in (cfg.n_heads, cfg.n_kv_heads))
    q, k, v = qkv[..., :nq], qkv[..., nq:nq + nk], qkv[..., nq + nk:]
    return (q.reshape(b, t, cfg.n_heads, dh),
            k.reshape(b, t, cfg.n_kv_heads, dh),
            v.reshape(b, t, cfg.n_kv_heads, dh))


def _paged_attend(params, tokens, cfg: LlamaConfig, kv_k, kv_v,
                  qpos, wflat, table, active=None, sel=None):
    """Shared body of the paged decode paths, over ``params`` the serving
    tree (:func:`serving_params`): scatter the chunk's K/V at
    flat physical positions ``wflat`` [B, T], then attend block-wise
    through ``table`` [B, blocks_per_row] (the rows' block tables) with a
    running softmax: a loop over key tiles of whole pool blocks that ends,
    for each group of rows of like length, at its longest row's last query
    position, traced bounds read from ``qpos`` and ``active`` [B], the rows
    whose outputs are read (:func:`tile_walk`, :func:`paged_attend_tiles`).
    Nothing as deep as the table is gathered or scored.  The numbers are
    :func:`decode_chunk`'s up to the order of summation.  With ``sel`` [B]
    the logits are of each row's position ``sel`` alone, [B, V], picked
    before the final norm and the head.

    The pool is written IN PLACE: ``kv_k`` / ``kv_v`` ride the layer scan
    as CARRIES (flattened to ``[L * n_blocks * bs, KVH, Dh]``, a bitcast),
    never as scanned inputs or stacked outputs, and layer ``i`` scatters
    to and reads blocks from its own stripe at ``i * n_blocks``.  With the
    caller's pool donated the carry aliases it, so a program's only pool
    traffic is the B x T scatter and the live blocks attention reads — no
    layer slice is copied and the pool is held once."""
    b, t = tokens.shape
    nl, n_blocks, bs, kvh, dh = kv_k.shape
    dt = cfg.dtype
    x = params["embed"][tokens].astype(dt)                # [B, T, D]
    cos, sin = rope_tables(cfg, qpos)
    stripe = n_blocks * bs                  # one layer's flat positions
    walk = tile_walk(table, qpos, bs, active)

    def layer(carry, lp):
        x, kf, vf, i = carry                # kf/vf [L * stripe, KVH, Dh]
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv_heads(h, lp, cfg)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        o, kf, vf = paged_attend_tiles(q, k, v, kf, vf, i, walk, wflat,
                                       n_blocks, bs)
        x = x + o.astype(dt).reshape(b, t, cfg.dim) @ lp["wo"].astype(dt)
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ lp["w_gate"].astype(dt))
        up = h @ lp["w_up"].astype(dt)
        x = x + (gate * up) @ lp["w_down"].astype(dt)
        return (x, kf, vf, i + 1), None

    (x, kf, vf, _), _ = lax.scan(
        layer,
        (x, kv_k.reshape(nl * stripe, kvh, dh),
         kv_v.reshape(nl * stripe, kvh, dh), jnp.int32(0)),
        params["layers"])
    if sel is not None:
        x = x[jnp.arange(b), sel]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(dt)).astype(jnp.float32)
    return logits, kf.reshape(kv_k.shape), vf.reshape(kv_v.shape)


def decode_chunk_paged(
    params: dict, tokens: jax.Array, cfg: LlamaConfig,
    pcache: PagedKVCache, *, advance: jax.Array | None = None,
    active: jax.Array | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """Paged :func:`decode_chunk`: T tokens per row against the block
    pool; token j of row r lands in the physical block its table maps
    position ``length_r + j`` to.

    ``advance`` [B]: optional per-row length increments (0 or T) so a
    fixed-signature serving tick can hold idle rows in place — idle rows
    still compute (one program for the whole pool) but their writes land
    in their table's blocks (trash for free rows) and their length stays
    put.  ``None`` advances every row by T.

    ``active`` [B]: the rows whose logits are read (default: the rows that
    advance).  Attention walks the others' keys no further than one tile
    (:func:`tile_walk`): their logits are of a prefix of their keys."""
    b, t = tokens.shape
    bs = pcache.block_size
    per = pcache.block_table.shape[1]
    pos = pcache.length                                   # [B]
    qpos = pos[:, None] + jnp.arange(t)[None, :]          # [B, T]
    # writes past the table (an overflowing row) clamp into its last
    # logical block — in-bounds garbage, never validly read
    wblk = jnp.take_along_axis(
        pcache.block_table, jnp.clip(qpos // bs, 0, per - 1), axis=1)
    wflat = wblk * bs + qpos % bs                         # [B, T]
    adv = (jnp.asarray(t, jnp.int32) if advance is None
           else jnp.asarray(advance, jnp.int32))
    if active is None and advance is not None:
        active = adv
    logits, ks, vs = _paged_attend(
        params, tokens, cfg, pcache.k, pcache.v, qpos, wflat,
        pcache.block_table, active)
    return logits, pcache._replace(k=ks, v=vs, length=pos + adv)


def spec_verify_paged(
    params: dict, cfg: LlamaConfig, pcache: PagedKVCache,
    last_logits: jax.Array, drafts: jax.Array, active: jax.Array,
    *, decode: Callable | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, PagedKVCache]:
    """One batched self-speculation verify round over the paged pool:
    every row argmaxes its last logits into ``tok`` and decodes the
    fixed ``(K + 1)``-wide chunk ``[tok, d_1..d_K]`` in ONE
    :func:`decode_chunk_paged` dispatch; greedy longest-matching-prefix
    acceptance is computed IN-PROGRAM (a cumprod of per-position
    matches), so the host never round-trips between dispatch and the
    length advance.  ``drafts`` [B, K] pads with ``-1`` — argmax preds
    are always >= 0, so pads can never be accepted — and ``active`` [B]
    gates the advance exactly as the plain tick's does.

    Rollback of rejected positions is the per-row ``length`` alone: the
    chunk's K/V writes beyond ``length + 1 + accept`` are stale garbage
    in the row's own private frontier blocks (or trash, for inactive
    rows), masked by every reader and overwritten before the frontier
    reaches them — the same write-before-read invariant the slot pool
    already relies on, so no block-table or cache surgery is needed.

    With greedy acceptance every emitted token is the target's own
    argmax (accepted ``d_i`` equals ``preds[i-1]`` by construction), so
    the output stream is bit-identical to solo greedy :func:`generate`
    no matter what the drafter proposed.  Returns ``(tok, accept,
    next_logits, pcache)``: the unconditional token [B], accepted draft
    counts [B], the logits following each row's last accepted token
    [B, V] (seeding the next round), and the advanced cache.

    The round is generic over the wide tick: ``decode`` (default
    :func:`decode_chunk_paged` with ``active`` for its ``active``: the round
    holds every row's length, so the advance cannot say whose logits are
    read) is any model's function of that signature whose cache has a
    per-row ``length`` that alone rolls back.
    """
    if decode is None:
        decode = partial(decode_chunk_paged, active=active)
    b, k = drafts.shape
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)       # [B]
    chunk = jnp.concatenate([tok[:, None], drafts], axis=1)   # [B, K+1]
    hold = jnp.zeros((b,), jnp.int32)
    logits, pcache = decode(params, chunk, cfg, pcache, advance=hold)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [B, K+1]
    match = (drafts == preds[:, :k]).astype(jnp.int32)
    accept = jnp.sum(jnp.cumprod(match, axis=1), axis=1)           # [B]
    adv = jnp.asarray(active, jnp.int32) * (1 + accept)
    pcache = pcache._replace(length=pcache.length + adv)
    next_logits = logits[jnp.arange(b), accept]                 # [B, V]
    return tok, accept, next_logits, pcache


def decode_chunk_paged_rows(
    params: dict, tokens: jax.Array, cfg: LlamaConfig,
    pcache: PagedKVCache, slots: jax.Array, *, new_length: jax.Array,
    sel: jax.Array | None,
) -> tuple[jax.Array, PagedKVCache]:
    """A chunk of prefill for several rows in one program, one read of the
    weights for all of them: ``tokens`` [R, T] continue the slots ``slots``
    [R] (each at most once) from their current lengths, which become
    ``new_length`` [R] (the true frontier: for a padded final window that is
    less than ``length + T``, exactly :func:`prefill_chunked`'s contract).
    Returns the logits of each row's position ``sel`` [R] alone, [R, V] (of
    every position, [R, T, V], with ``sel`` ``None``), and the cache.  Only
    these slots' blocks are touched, so in-flight rows are untouched
    mid-prefill.  A row whose slot is past the slots (``n_slots``) is not
    there: it writes no key and no length."""
    slots = jnp.asarray(slots, jnp.int32)
    nl, n_blocks, bs = pcache.k.shape[:3]
    per = pcache.block_table.shape[1]
    there, _, _, qpos, table = paged.chunk_rows(pcache, slots,
                                                tokens.shape[1])
    wblk = jnp.take_along_axis(table, jnp.clip(qpos // bs, 0, per - 1),
                               axis=1)
    # a row that is not there writes past every layer's stripe: dropped
    wflat = jnp.where(there[:, None], wblk * bs + qpos % bs,
                      nl * n_blocks * bs)
    logits, ks, vs = _paged_attend(
        params, tokens, cfg, pcache.k, pcache.v, qpos, wflat, table, there,
        None if sel is None else jnp.asarray(sel, jnp.int32))
    length = pcache.length.at[slots].set(
        jnp.asarray(new_length, jnp.int32), mode="drop")
    return logits, pcache._replace(k=ks, v=vs, length=length)


def decode_chunk_paged_row(
    params: dict, tokens: jax.Array, cfg: LlamaConfig,
    pcache: PagedKVCache, slot: jax.Array, *, new_length: jax.Array,
) -> tuple[jax.Array, PagedKVCache]:
    """:func:`decode_chunk_paged_rows` for one row, with the logits of every
    position: ``tokens`` [1, T] continue slot ``slot`` from its current
    length, which becomes ``new_length``; returns logits [1, T, V]."""
    b, t = tokens.shape
    if b != 1:
        raise ValueError(f"decode_chunk_paged_row is a B=1 program, "
                         f"got batch {b}")
    return decode_chunk_paged_rows(
        params, tokens, cfg, pcache, jnp.asarray(slot, jnp.int32)[None],
        new_length=jnp.asarray(new_length, jnp.int32)[None], sel=None)


def prefill_chunked(
    params: dict, tokens: jax.Array, cfg: LlamaConfig, cache: KVCache,
    *, window: int, lengths: jax.Array | None = None,
) -> tuple[jax.Array, KVCache]:
    """Prefill a long prompt through fixed-size :func:`decode_chunk`
    windows: activation memory is O(window·L_cache) instead of O(L²) —
    the chunked-prefill pattern serving engines use to keep long-prompt
    admission from spiking memory (and to interleave it with decode
    ticks).  Output == :func:`prefill` (each row's last-valid-position
    logits + an equivalent cache: scalar length stays scalar, so the
    decode fast path is preserved).

    The padded width must satisfy ``L % window == 0``; ragged true
    lengths go in ``lengths`` [B] exactly as in :func:`prefill` (pad
    positions beyond a row's length are masked by later decodes and
    overwritten by its next tokens).  One ``lax.scan`` over windows —
    compile size is one chunk body regardless of prompt length.
    """
    b, l = tokens.shape
    if l % window:
        raise ValueError(f"padded prompt length {l} not a multiple of "
                         f"window {window}")
    _validate_lengths(lengths, b, l, "prefill_chunked")
    base = cache.length                              # scalar or [B]
    if not isinstance(base, jax.core.Tracer):
        # decode_chunk's scatter DROPS out-of-bounds writes, so an
        # overflowing chunked prefill would silently return logits
        # attending to never-written slots — fail loudly instead (the
        # analogous one-shot prefill overflow fails at trace time).
        if int(np.max(np.asarray(base))) + l > cache.k.shape[2]:
            raise ValueError(
                f"prefill_chunked would overflow the cache: base length "
                f"{int(np.max(np.asarray(base)))} + padded width {l} > "
                f"max_len {cache.k.shape[2]}")
    basev = (base if jnp.ndim(base) > 0
             else jnp.broadcast_to(base, (b,)))      # [B]
    true_len = (jnp.asarray(lengths, jnp.int32) if lengths is not None
                else jnp.full((b,), l, jnp.int32))
    target = basev + true_len - 1     # absolute pos of each last token
    windows = jnp.moveaxis(tokens.reshape(b, l // window, window), 1, 0)

    def step(carry, toks_w):
        cache, last = carry
        start = cache.length
        startv = (start if jnp.ndim(start) > 0
                  else jnp.broadcast_to(start, (b,)))
        logits, cache = decode_chunk(params, toks_w, cfg, cache)
        # rows whose last valid token falls inside this window pick
        # their logits; others keep what they have
        hit = (target >= startv) & (target < startv + window)
        idx = jnp.clip(target - startv, 0, window - 1)
        cand = logits[jnp.arange(b), idx]
        last = jnp.where(hit[:, None], cand, last)
        return (cache, last), None

    last0 = jnp.zeros((b, cfg.vocab_size), jnp.float32)
    (cache, last), _ = lax.scan(step, (cache, last0), windows)
    if lengths is not None:
        cache = cache._replace(length=basev + true_len)
    # else: decode_chunk preserved the scalar/[B] shape of `base`, and
    # the scanned advance already totals base + l.
    return last, cache


def sample_logits(
    logits: jax.Array,
    key: jax.Array,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """One sampling step on [B, V] logits → [B] token ids.

    ``temperature<=0`` is greedy argmax (filters are irrelevant there).
    ``top_k`` keeps the k largest logits; ``top_p`` keeps the smallest
    nucleus whose cumulative probability reaches p (always ≥ 1 token);
    both compose (top-k filter first, then the nucleus).  All branching is
    trace-time, so the whole thing jits into the decode scan.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(
        key, filtered_logits(logits, temperature, top_k=top_k,
                             top_p=top_p), axis=-1)


def filtered_logits(
    logits: jax.Array,
    temperature,
    *,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """Temperature-scaled, top-k/top-p-filtered logits [B, V] — the
    sampling math of :func:`sample_logits`, exposed so callers with a
    TRACED temperature (e.g. per-request temperatures in the serving
    batcher) compute bit-identical distributions.  ``temperature`` must
    be positive (the greedy short-circuit lives in the caller)."""
    logits = logits / temperature
    v = logits.shape[-1]
    use_k = top_k is not None and top_k < v
    if top_p is not None and top_p < 1.0:
        # ONE descending sort serves both filters (this runs per decoded
        # token inside the scan — no second O(V log V) pass): top-k is a
        # positional mask in sorted space, the nucleus is computed on the
        # (possibly k-masked) sorted logits.
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        if use_k:
            pos = jnp.arange(v)[None, :]
            sorted_desc = jnp.where(pos < top_k, sorted_desc, NEG_INF_LOGIT)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # Keep a sorted position while the mass BEFORE it is < p — the
        # first token always qualifies (mass 0 < p).
        keep = (csum - probs) < top_p
        thresh = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits >= thresh, logits, NEG_INF_LOGIT)
    elif use_k:
        # top-k alone: lax.top_k gives the kth value without a full sort.
        kth = lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits >= kth, logits, NEG_INF_LOGIT)
    return logits


NEG_INF_LOGIT = -1e30


def generate(
    params: dict,
    prompt: jax.Array,
    cfg: LlamaConfig,
    *,
    max_new_tokens: int,
    max_len: int | None = None,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    key: jax.Array | None = None,
    prompt_lengths: jax.Array | None = None,
) -> jax.Array:
    """Greedy (or sampled) generation: prompt [B, L] → [B, max_new_tokens].

    One prefill + one ``lax.scan`` of cached decode steps; jit-friendly
    end to end (static shapes, no per-token retracing).  Sampling knobs:
    ``temperature`` (0 = greedy), ``top_k``, ``top_p`` (nucleus).

    ``prompt_lengths`` [B]: per-row lengths of a RIGHT-padded ragged
    prompt batch — each row continues from its own last valid token
    (mixed-length serving without per-length bucketing; the cache runs
    ragged from the prefill on).
    """
    b, l = prompt.shape
    max_len = max_len or (l + max_new_tokens)
    if max_len < l + max_new_tokens:
        raise ValueError(
            f"max_len={max_len} < prompt {l} + max_new_tokens {max_new_tokens}"
        )
    cache = init_cache(cfg, b, max_len)
    logits, cache = prefill(params, prompt, cfg, cache,
                            lengths=prompt_lengths)
    if key is None:
        key = jax.random.key(0)

    def pick(logits, k):
        return sample_logits(
            logits, k, temperature=temperature, top_k=top_k, top_p=top_p
        ).astype(prompt.dtype)

    def step(carry, k):
        logits, cache = carry
        tok = pick(logits, k)
        logits, cache = decode_step(params, tok, cfg, cache)
        return (logits, cache), tok

    keys = jax.random.split(key, max_new_tokens)
    (_, _), toks = lax.scan(step, (logits, cache), keys)
    return jnp.moveaxis(toks, 0, 1)                       # [B, T]
