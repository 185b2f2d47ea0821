"""A served decoder that generates by **diffusion over blocks**: a row's answer
grows a block of ``block_length`` positions at a time, and a block is
*denoised* — its positions start as the mask id, a forward pass over the whole
block proposes a token for each, the most confident are kept, and the pass is
repeated until none is masked — before its keys are committed and the next
block begins.  Attention is grouped-query over every layer under a
**block-causal** mask (``key_pos // B <= query_pos // B``: everything in the
earlier blocks and the whole of the own block, in both directions); the
feed-forward part of every layer is softmax-routed experts, all held, with no
shared expert.

This is the architecture of the SDAR expert models (``sdar_moe``: a Qwen3-MoE
layer under the block-causal mask, sampled by the published
``block_diffusion_generate``), written for
:class:`~horovod_tpu.serving_scheduler.ServeEngine`: the module implements the
engine's paged model interface (:mod:`horovod_tpu.models.paged`) and its
optional entries for a model that decodes a block a row, walks its blocks with
:func:`~horovod_tpu.models.llama.paged_attend_tiles` (the walk's ``span`` is
the mask) and computes its expert layers with
:func:`~horovod_tpu.models.latent_moe.held_experts`.

**Layers.**  ``a = RMSNorm(h)``; ``q = a Wq`` [T, H, Dh], ``k = a Wk``, ``v =
a Wv`` [T, KVH, Dh]; ``q`` and ``k`` RMS-normed over ``Dh`` with own weights,
then half-split rotary; softmax over the visible keys at ``1 / sqrt(Dh)``;
``h += attn Wo``; ``m = RMSNorm(h)``; the router's logits ``m Wr`` in
float32, the ``top_k`` largest chosen, weights a softmax over the chosen
(what softmax over all then normalise over the chosen equals); ``h += sum_e
w_e SwiGLU_e(m)``.  Final RMSNorm, untied head.

**What a block costs the cache.**  The pools ``k`` / ``v`` ``[n_layers,
n_blocks, bs, KVH, Dh]`` are per position and immutable once *committed*.  A
row's length is a whole number of blocks.  A block tick
(:func:`decode_block_paged`) writes the keys of the block in flight **past the
row's length** (write-before-read, as a speculative verify round does) and
rewrites them every denoise step; only a tick with ``commit`` set for the row
advances its length, after which the block's keys are as immutable as a
prompt's.  A prefill chunk takes the same mask, so the page size, the chunk
and the lengths the engine maps rows at must be whole numbers of blocks (the
engine refuses a chunk or a page that is not).

**The unmask rule** runs on the device (:func:`unmask`): what the host reads
back of a step is the block's ids and two small vectors, never logits.

**Counters.**  ``stats`` rides in the cache as in ``latent_moe``: the programs
add to it on the device and the engine reads it beside the step's ids.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import latent_moe, llama, paged
from horovod_tpu.models.latent_moe import LOAD0, _add_stats, _dot
from horovod_tpu.models.llama import rmsnorm
from horovod_tpu.models.shortconv_moe import _layer_once

DYNAMIC, STATIC = "low_confidence_dynamic", "low_confidence_static"
#: stats columns (``latent_moe``'s layout: four running sums, the touched
#: gauge at ``TOUCHED``, the experts' load from ``LOAD0``, last the layers
#: batched)
_SUMS = ("choices_total", "choices_held", "keys_visible", "blocks_committed")
_TAIL = ("layers_batched",)
CHOICES_TOTAL, CHOICES_HELD, KEYS_VISIBLE, COMMITTED = range(4)


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMoEConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 6
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    # experts: every one is held
    n_experts: int = 128
    expert_dim: int = 768
    top_k: int = 8
    norm_eps: float = 1e-6
    max_seq_len: int = 2048
    # the model's block and the sampler's settings
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = DYNAMIC
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads has to be a multiple of n_kv_heads")
        if self.remasking not in (DYNAMIC, STATIC):
            raise ValueError(f"remasking is {DYNAMIC!r} or {STATIC!r}, not "
                             f"{self.remasking!r}")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} has to be within "
                f"1..block_length {self.block_length}: a step unmasks a "
                f"position at least")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is not an "
                             f"id of the vocabulary")

    # what latent_moe.held_experts / route / choices_in_place read of a config
    route_softmax_top_k = True
    first_dense = 0
    held_first = 0

    @property
    def held_count(self) -> int:
        return self.n_experts


def block_diffusion_moe_tiny(**overrides) -> BlockDiffusionMoEConfig:
    """The CPU tests' preset: 3 layers, 8 experts top-2, blocks of 4."""
    base = dict(
        vocab_size=64, dim=32, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=8, rope_theta=1e4, n_experts=8, expert_dim=16, top_k=2,
        max_seq_len=64, block_length=4, denoising_steps=2,
        remasking=STATIC, confidence_threshold=0.9, mask_token_id=63,
        dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(overrides)
    return BlockDiffusionMoEConfig(**base)


def block_length(cfg: BlockDiffusionMoEConfig) -> int:
    """The interface's sign that a row decodes a block: its positions."""
    return cfg.block_length


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(cfg: BlockDiffusionMoEConfig, key: jax.Array) -> dict:
    """Random parameters: matrices ``[in, out]`` normal at ``1/sqrt(in)``,
    norm weights 1."""
    dt = cfg.param_dtype

    def mat(k, n_in, *out):
        return (jax.random.normal(k, (n_in, *out), jnp.float32)
                * n_in ** -0.5).astype(dt)

    d, hd, e, f = cfg.dim, cfg.head_dim, cfg.n_experts, cfg.expert_dim
    layers = []
    for i in range(cfg.n_layers):
        ks = iter(jax.random.split(jax.random.fold_in(key, i), 8))
        layers.append({
            "attn_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt),
            "wq": mat(next(ks), d, cfg.n_heads * hd),
            "wk": mat(next(ks), d, cfg.n_kv_heads * hd),
            "wv": mat(next(ks), d, cfg.n_kv_heads * hd),
            "q_norm": jnp.ones((hd,), dt), "k_norm": jnp.ones((hd,), dt),
            "wo": mat(next(ks), cfg.n_heads * hd, d),
            "w_router": mat(next(ks), d, e),
            "e_gate": mat(next(ks), d, e, f).transpose(1, 0, 2),
            "e_up": mat(next(ks), d, e, f).transpose(1, 0, 2),
            "e_down": mat(next(ks), f, e, d).transpose(1, 0, 2)})
    top = jax.random.split(jax.random.fold_in(key, 10_000), 2)
    return {"embed": jax.random.normal(top[0], (cfg.vocab_size, d),
                                       jnp.float32).astype(dt),
            "layers": tuple(layers),
            "final_norm": jnp.ones((d,), dt),
            "lm_head": mat(top[1], d, cfg.vocab_size)}


def param_partition_specs(cfg: BlockDiffusionMoEConfig, *,
                          tp_axis: str = "tp"):
    raise NotImplementedError(
        "tensor-parallel serving of a model that generates by diffusion over "
        "blocks is not written: the block tick's logits of every position "
        "and the unmask rule over them would have to be gathered a step; "
        "serve it at tp_size=1")


def paged_cache_partition_specs(*, tp_axis: str = "tp"):
    return param_partition_specs(None, tp_axis=tp_axis)


def tp_split_dims(cfg: BlockDiffusionMoEConfig) -> tuple:
    """Asked only at ``tp_size > 1``, which this model does not serve."""
    return param_partition_specs(cfg)


# ---------------------------------------------------------------------------
# the paged state
# ---------------------------------------------------------------------------

class BlockPagedCache(NamedTuple):
    """``k`` / ``v`` ``[n_layers, n_blocks, bs, KVH, Dh]`` (block 0 is
    trash), ``block_table`` [B, blocks_per_slot] int32, ``length`` [B] int32
    (the committed positions: a whole number of the model's blocks), and
    ``stats`` [2, 5 + n_experts + 1] int32, the device-side counters."""

    k: jax.Array
    v: jax.Array
    block_table: jax.Array
    length: jax.Array
    stats: jax.Array

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def logical_len(self) -> int:
        return self.block_table.shape[1] * self.k.shape[2]


def init_paged_cache(
    cfg: BlockDiffusionMoEConfig, n_slots: int, max_len: int, *,
    block_size: int, n_blocks: int | None = None,
) -> BlockPagedCache:
    """The state for ``n_slots`` rows of logical depth ``max_len``;
    ``n_blocks`` defaults to full backing plus the trash block."""
    if max_len % block_size:
        raise ValueError(
            f"max_len {max_len} not a multiple of block_size {block_size}")
    per = max_len // block_size
    if n_blocks is None:
        n_blocks = n_slots * per + 1
    if n_blocks < per + 1:
        raise ValueError(
            f"n_blocks {n_blocks} cannot back even one full slot "
            f"({per} blocks) plus the trash block")
    kv = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
    return BlockPagedCache(
        k=jnp.zeros(kv, cfg.dtype), v=jnp.zeros(kv, cfg.dtype),
        block_table=jnp.zeros((n_slots, per), jnp.int32),
        length=jnp.zeros((n_slots,), jnp.int32),
        stats=jnp.zeros((2, LOAD0 + cfg.n_experts + len(_TAIL)), jnp.int32))


def paged_pool_bytes(pcache: BlockPagedCache) -> dict:
    """Device bytes one block holds in each pool (all layers)."""
    return llama.paged_pool_bytes(pcache)


def paged_counters(pcache: BlockPagedCache) -> jax.Array:
    """The device array the engine reads back beside the step's ids."""
    return pcache.stats


def read_counters(stats_host: np.ndarray) -> dict:
    """The counters as Python ints (sums exact past 2**31)."""
    return paged.read_stats(stats_host, _SUMS, _TAIL)


def _counted(metrics) -> tuple:
    """The registry's counter of each of the device's running sums, beside it
    the gauge ``<name>.device`` (:func:`paged.count_from_device`) and the
    sum's name in :func:`read_counters` (written out for the names lint)."""
    return (
        (metrics.counter("moe.choices_total"),
         metrics.gauge("moe.choices_total.device"), "choices_total"),
        (metrics.counter("moe.choices_held"),
         metrics.gauge("moe.choices_held.device"), "choices_held"),
        (metrics.counter("moe.layers_batched"),
         metrics.gauge("moe.layers_batched.device"), "layers_batched"),
        (metrics.counter("attn.keys_visible"),
         metrics.gauge("attn.keys_visible.device"), "keys_visible"))


def publish_paged_metrics(metrics, cfg: BlockDiffusionMoEConfig,
                          pcache: BlockPagedCache,
                          stats_host: np.ndarray | None = None,
                          row_blocks: tuple = (),
                          programs: tuple = ()) -> None:
    """The model's own gauges and counters in the engine's registry: at
    construction what a cached token holds; after a step the share of the
    tables attention walked (``attn.blocks_*``, as :mod:`llama` counts them
    from ``programs``: a block tick is a program of ``block_length`` tokens a
    row), ``moe.choices_in_place`` and ``moe.choices_grouped``; where a
    step's readback brought ``stats_host``, the device's counters, the
    experts the last block tick touched, each expert's load, the largest
    load, and the device's own count of the blocks it committed (``diffusion.blocks_committed.device``: what
    the engine's ``diffusion.commit_forwards`` has to come to)."""
    if stats_host is None and not programs:     # once, at construction
        per_block = paged_pool_bytes(pcache)
        metrics.gauge("kv.bytes_per_token").set(
            (per_block["k"] + per_block["v"]) // pcache.block_size)
        for _, device_total, _ in _counted(metrics):
            device_total.set(0)
    llama.publish_paged_metrics(metrics, cfg, pcache, programs=programs)
    latent_moe.count_choices_by_form(metrics, cfg, programs)
    if stats_host is None:          # nothing was read back: no tick ran
        return
    c = read_counters(stats_host)
    paged.count_from_device(_counted(metrics), c)
    metrics.gauge("diffusion.blocks_committed.device").set(
        c["blocks_committed"])
    metrics.gauge("moe.experts_touched").set(c["experts_touched"])
    for e, n in enumerate(c["held_load"]):
        metrics.gauge(f"moe.held_load.{e}").set(n)
    metrics.gauge("moe.load_max").set(max(c["held_load"]))


# ---------------------------------------------------------------------------
# layer mathematics
# ---------------------------------------------------------------------------

def _attention(cfg: BlockDiffusionMoEConfig, lp: dict, x, cos, sin, kf, vf,
               layer, walk, wflat, n_blocks, bs):
    """``x + Attn(RMSNorm(x))`` over the walk's visible keys, and the two
    pools."""
    dt = cfg.dtype
    b, t, _ = x.shape
    hd = cfg.head_dim
    u = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q = _dot(u, lp["wq"], dt).reshape(b, t, cfg.n_heads, hd)
    k = _dot(u, lp["wk"], dt).reshape(b, t, cfg.n_kv_heads, hd)
    v = _dot(u, lp["wv"], dt).reshape(b, t, cfg.n_kv_heads, hd)
    q = llama.apply_rope(rmsnorm(q, lp["q_norm"], cfg.norm_eps), cos, sin)
    k = llama.apply_rope(rmsnorm(k, lp["k_norm"], cfg.norm_eps), cos, sin)
    o, kf, vf = llama.paged_attend_tiles(q, k, v, kf, vf, layer, walk, wflat,
                                         n_blocks, bs)
    return x + _dot(o.astype(dt).reshape(b, t, cfg.n_heads * hd), lp["wo"],
                    dt), kf, vf


def _ffn_experts(cfg: BlockDiffusionMoEConfig, lp: dict, x, valid):
    """``x + Experts(RMSNorm(x))`` and the experts' load."""
    b, t, d = x.shape
    y, load = latent_moe.held_experts(
        cfg, lp, rmsnorm(x, lp["ffn_norm"], cfg.norm_eps).reshape(b * t, d),
        valid.reshape(b * t))
    return x + y.reshape(b, t, d), load


def _forward_paged(params, tokens, cfg: BlockDiffusionMoEConfig,
                   pcache: BlockPagedCache, qpos, table, valid,
                   set_touched: bool, sel=None, there=None):
    """The shared body of the paged programs: ``tokens`` [B, T] at positions
    ``qpos`` under block tables ``table`` [B, per], block-causal over blocks
    of ``cfg.block_length``; ``valid`` [B, T] marks the tokens that count
    (for the counters and the routing; a row with none is one whose output
    nobody reads, and attention walks it one tile).  Writes keys and values,
    none for a row that is not ``there`` [B] (default: all are).  With
    ``sel`` [B] the logits are of each row's position ``sel`` alone, [B, V],
    picked before the final norm and the head; else of every position.
    Returns ``(logits, k, v, stats)``."""
    dt = cfg.dtype
    b, t = tokens.shape
    nl, n_blocks, bs, kvh, hd = pcache.k.shape
    per = table.shape[1]
    wblk = jnp.take_along_axis(table, jnp.clip(qpos // bs, 0, per - 1),
                               axis=1)
    wflat = wblk * bs + qpos % bs                                # [B, T]
    if there is not None:       # past every layer's stripe: a dropped write
        wflat = jnp.where(there[:, None], wflat, nl * n_blocks * bs)
    kf = pcache.k.reshape(nl * n_blocks * bs, kvh, hd)
    vf = pcache.v.reshape(nl * n_blocks * bs, kvh, hd)
    cos, sin = llama.rope_tables(cfg, qpos)
    walk = llama.tile_walk(table, qpos, bs, jnp.any(valid, axis=1),
                           span=cfg.block_length)
    x = params["embed"][tokens].astype(dt)
    # inside a program the expert layer's body is traced once; a call
    # outside any computes op by op
    once = _layer_once if isinstance(x, jax.core.Tracer) else (
        lambda fn, cfg, form, *args: fn(cfg, *args))
    load = jnp.zeros((cfg.n_experts,), jnp.int32)
    touched = batched = jnp.int32(0)
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("attn.gqa"):
            x, kf, vf = _attention(cfg, lp, x, cos, sin, kf, vf, i, walk,
                                   wflat, n_blocks, bs)
        x, layer_load = once(
            _ffn_experts, cfg,
            (latent_moe.held_experts, latent_moe.IN_PLACE_ROWS), lp, x, valid)
        load = load + layer_load
        touched = touched + jnp.sum(layer_load > 0, dtype=jnp.int32)
        batched = batched + latent_moe.layers_batched(b * t, layer_load)
    if sel is not None:
        x = x[jnp.arange(b), sel]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x, params["lm_head"].astype(dt),
                     preferred_element_type=jnp.float32)
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    span = cfg.block_length
    seen = jnp.sum(jnp.where(valid, (qpos // span + 1) * span, 0),
                   dtype=jnp.int32)
    choices = n_valid * (cfg.top_k * cfg.n_layers)
    add = jnp.concatenate([
        jnp.stack([choices, choices, seen * nl, jnp.int32(0), jnp.int32(0)]),
        load, batched[None]])
    stats = _add_stats(pcache.stats, add, touched if set_touched else None)
    return (logits, kf.reshape(pcache.k.shape), vf.reshape(pcache.v.shape),
            stats)


# ---------------------------------------------------------------------------
# the engine's interface
# ---------------------------------------------------------------------------

def decode_block_paged(
    params: dict, block_tokens: jax.Array, cfg: BlockDiffusionMoEConfig,
    pcache: BlockPagedCache, *, active: jax.Array, commit: jax.Array,
) -> tuple[jax.Array, BlockPagedCache]:
    """The block tick: every row's ``block_length`` positions ``[length,
    length + B)`` hold ``block_tokens`` [n_slots, B] (mask ids where a
    position is still masked) and attend, under the block-causal mask, to the
    row's committed pages and to each other; the block's keys are written
    past the length.  ``active`` [n_slots] marks the rows whose logits are
    read (a free or prefilling row ticks along, walks one tile and writes
    into its own frontier or trash); ``length += B`` where ``commit``
    [n_slots] is set.  Returns the block's logits ``[n_slots, B, V]``
    (float32) and the cache."""
    b, t = block_tokens.shape
    if t != cfg.block_length:
        raise ValueError(f"a block tick carries block_length "
                         f"{cfg.block_length} tokens a row, got {t}")
    active = jnp.asarray(active, jnp.int32)
    commit = jnp.asarray(commit, jnp.int32) * active
    pos = pcache.length
    qpos = pos[:, None] + jnp.arange(t)[None, :]
    valid = jnp.broadcast_to((active > 0)[:, None], block_tokens.shape)
    logits, k, v, stats = _forward_paged(
        params, block_tokens, cfg, pcache, qpos, pcache.block_table, valid,
        True)
    add = jnp.zeros((stats.shape[1],), jnp.int32).at[COMMITTED].set(
        jnp.sum(commit))
    return logits, pcache._replace(
        k=k, v=v, length=pos + t * commit,
        stats=_add_stats(stats, add, None))


def unmask(cfg: BlockDiffusionMoEConfig, logits: jax.Array,
           block_tokens: jax.Array, step: jax.Array) -> tuple:
    """One step of the sampler's rule, on the device: of the block's masked
    positions, which take their proposed token now.

    ``logits`` [N, B, V] are a block tick's, ``block_tokens`` [N, B] the ids
    it ran over, ``step`` [N] how many denoise steps each row's block has had
    (0 for the first).  With the mask id's logit at minus infinity, ``x0 =
    argmax`` and ``c = softmax(logits)[x0]`` at the masked positions.  The
    schedule gives step ``s`` ``n_s = B // S`` positions, one more on the
    first ``B % S`` steps.  ``low_confidence_static``: the ``n_s`` most
    confident masked positions are unmasked (ties to the lower position; all
    of them where fewer are masked).  ``low_confidence_dynamic``: every masked
    position with ``c > confidence_threshold`` where those are at least
    ``n_s``, else the static rule.  A position once unmasked never changes.

    Returns ``(block_tokens, left, by_threshold)``: the new ids [N, B], how
    many are still masked [N], and how many this step unmasked by the
    threshold [N] (0 where the schedule decided)."""
    b, s_total = cfg.block_length, cfg.denoising_steps
    masked = block_tokens == cfg.mask_token_id                   # [N, B]
    ids = jnp.arange(logits.shape[-1])
    logits = jnp.where(ids == cfg.mask_token_id, -jnp.inf, logits)
    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    best = jnp.max(logits, axis=-1)
    conf = jnp.exp(best - jax.nn.logsumexp(logits, axis=-1))
    conf = jnp.where(masked, conf, -jnp.inf)
    n_s = b // s_total + (jnp.asarray(step) < b % s_total)       # [N]
    at = jnp.arange(b)
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & (at[None, :] < at[:, None]))
    rank = jnp.sum(ahead, axis=-1)                               # [N, B]
    take = masked & (rank < n_s[:, None])
    by_threshold = jnp.zeros(block_tokens.shape[:1], jnp.int32)
    if cfg.remasking == DYNAMIC:
        high = masked & (conf > cfg.confidence_threshold)
        n_high = jnp.sum(high, axis=-1, dtype=jnp.int32)
        clear = n_high >= n_s
        take = jnp.where(clear[:, None], high, take)
        by_threshold = jnp.where(clear, n_high, 0)
    new = jnp.where(take, x0, block_tokens)
    left = jnp.sum(masked & ~take, axis=-1, dtype=jnp.int32)
    return new, left, by_threshold


def decode_chunk_paged_rows(
    params: dict, tokens: jax.Array, cfg: BlockDiffusionMoEConfig,
    pcache: BlockPagedCache, slots: jax.Array, *, new_length: jax.Array,
    sel: jax.Array | None,
) -> tuple[jax.Array, BlockPagedCache]:
    """A chunk of prefill for several rows in one program, under the
    block-causal mask: ``tokens`` [R, T] continue the slots ``slots`` [R]
    (each at most once) from their lengths, which become ``new_length`` [R]
    (whole numbers of blocks, as the lengths are, so that no real query sees
    a position of padding).  Returns the logits of each row's position
    ``sel`` [R] alone, [R, V] (of every position, [R, T, V], with ``sel``
    ``None``), and the cache.  A row whose slot is past the slots
    (``n_slots``) is not there: it writes no key and no length."""
    slots = jnp.asarray(slots, jnp.int32)
    new_length = jnp.asarray(new_length, jnp.int32)
    there, _, _, qpos, table = paged.chunk_rows(pcache, slots,
                                                tokens.shape[1])
    valid = (qpos < new_length[:, None]) & there[:, None]
    logits, k, v, stats = _forward_paged(
        params, tokens, cfg, pcache, qpos, table, valid, False,
        None if sel is None else jnp.asarray(sel, jnp.int32), there)
    return logits, pcache._replace(
        k=k, v=v, stats=stats,
        length=pcache.length.at[slots].set(new_length, mode="drop"))


def decode_chunk_paged_row(
    params: dict, tokens: jax.Array, cfg: BlockDiffusionMoEConfig,
    pcache: BlockPagedCache, slot: jax.Array, *, new_length: jax.Array,
) -> tuple[jax.Array, BlockPagedCache]:
    """:func:`decode_chunk_paged_rows` for one row, with the logits of every
    position: ``tokens`` [1, T] continue slot ``slot`` from its length, which
    becomes ``new_length``; returns logits [1, T, V]."""
    b, t = tokens.shape
    if b != 1:
        raise ValueError(f"decode_chunk_paged_row is a B=1 program, "
                         f"got batch {b}")
    return decode_chunk_paged_rows(
        params, tokens, cfg, pcache, jnp.asarray(slot, jnp.int32)[None],
        new_length=jnp.asarray(new_length, jnp.int32)[None], sel=None)


def forward(params: dict, tokens: jax.Array,
            cfg: BlockDiffusionMoEConfig) -> jax.Array:
    """Logits [B, L, V] of whole sequences under the block-causal mask with
    no cache kept: every row through one chunk of a cache made for the call
    and thrown away.  ``L`` is a whole number of blocks."""
    b, l = tokens.shape
    pcache = init_paged_cache(cfg, b, l, block_size=l)
    pcache = pcache._replace(
        block_table=1 + jnp.arange(b, dtype=jnp.int32)[:, None])
    return decode_chunk_paged_rows(
        params, tokens, cfg, pcache, jnp.arange(b),
        new_length=jnp.full((b,), l, jnp.int32), sel=None)[0]
