"""A served decoder whose attention layers are of two kinds with two lifetimes
of cache: grouped-query layers that see the whole sequence, which page a row
per position, beside grouped-query layers that see the last ``window``
positions, which keep a ring of that many keys and values a sequence and
nothing older; the feed-forward part is a SwiGLU in the first layers and
sigmoid-routed experts beside a shared expert after them.

This is the architecture of the EXAONE expert models (``exaone_moe``), written
for :class:`~horovod_tpu.serving_scheduler.ServeEngine`: the module implements
the engine's paged model interface (:mod:`horovod_tpu.models.paged`) beside
:mod:`horovod_tpu.models.llama`, :mod:`horovod_tpu.models.latent_moe` and
:mod:`horovod_tpu.models.shortconv_moe`, walks its full layers' blocks with the
first's :func:`~horovod_tpu.models.llama.paged_attend_tiles`, computes its
expert layers with the second's :func:`~horovod_tpu.models.latent_moe.
held_experts` and keeps its per-sequence state by the third's snapshot rule.

**Layers.**  ``layer_kinds[i]`` is ``"sliding"`` or ``"full"``; the first
``first_dense`` layers have a SwiGLU, the others the expert layer.  Each
sub-layer's *output* is normed before it is added: ``x = x + RMSNorm_attn(
Attn(x))``, ``x = x + RMSNorm_ffn(FFN(x))``; no biases anywhere.

* *attention*: ``n_heads`` queries and ``n_kv_heads`` keys and values of
  ``head_dim``; RMSNorm over each query and key head (own weights); causal
  softmax at ``1 / sqrt(head_dim)``.  A sliding layer rotates its queries and
  keys (half-split rotary) and its query at ``t`` sees key ``j`` iff ``t - j
  < window``; a full layer applies no rotary and sees every key.
* *experts*: ``s = sigmoid(x W_r)`` in float32; the ``top_k`` largest ``s +
  bias`` are chosen and weighted ``s_e / sum_sel s`` times ``routed_scale``;
  this chip computes the ``held_count`` experts from ``held_first`` on (the
  router stays ``n_experts`` wide) and the shared expert.
* The head is its own matrix over the held rows of the vocabulary.

**Two lifetimes behind one block table.**  :class:`WindowPagedCache` holds the
full layers' ``k`` / ``v`` pools ``[n_full, n_blocks, bs, KVH, Dh]``, per
position and immutable once written, and for the sliding layers

* ``ring`` ``[2, n_sliding, n_slots, window, KVH, Dh]`` (keys, values): the
  last ``window`` positions of the sequence in each slot *at its length*,
  position ``p`` at index ``p % window``.  A program attends its sliding
  layers over the ring and its own keys and leaves the ring as after the
  tokens that counted (a chunk's real tokens, a tick's one, a verify round's
  ``1 + accepted``: the lengths alone do **not** roll a ring back).
* ``snap`` ``[2, n_sliding, n_blocks, window, KVH, Dh]``: per physical block,
  the ring at the block's last position, by the snapshot rule of
  :mod:`horovod_tpu.models.paged`; :func:`set_row` at a length past 0 restores
  the slot's ring from the block that ends there.

What a row holds for its sliding layers is then the ring, fixed, and one
snapshot a block, however long it is.

**Counters.**  ``stats`` rides in the cache as in ``latent_moe``: the programs
add to it on the device and the engine reads it with the tick's readback.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.models import latent_moe, llama, paged
from horovod_tpu.models.latent_moe import LOAD0, _add_stats, _dot, _swiglu
from horovod_tpu.models.llama import NEG_INF_LOGIT, rmsnorm

SLIDING, FULL = "sliding", "full"
#: stats columns: four running sums, the touched gauge at ``TOUCHED``, the
#: held experts' load from ``LOAD0``, then two more sums (snapshots written,
#: expert layers batched) and three gauges the tick sets (the positions the
#: slots hold, the blocks their tables map, the slots that hold any)
CHOICES_TOTAL, CHOICES_HELD, RESTORES, KEYS_VISIBLE = 0, 1, 2, 3
_SUMS = ("choices_total", "choices_held", "state_restores", "keys_visible")
_TAIL = ("snapshots_written", "layers_batched", "tokens_live", "blocks_live",
         "rows_live")
_GAUGES = 3                 # of the tail, from its end
#: the full layers' walk gathers its key tiles in pieces of at most this many
#: positions.  A gather whose slices are longer (a block of 1,024 positions of
#: 8 heads of 128 is 2 MB) the TPU's compiler splits into four, each from a
#: slice of the whole pool that it copies first: seen by compiling for the
#: chip with blocks of 1,024, the tick's scratch held the keys' pool once
#: more and every step of the walk wrote it.  256 is what the walk was
#: measured with (:data:`llama._KEY_TILE`).
GATHER_ROWS = 256


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab_size: int = 19200            # rows of embedding and head held here
    dim: int = 6144
    layer_kinds: tuple = (SLIDING, SLIDING, SLIDING, FULL) * 2
    first_dense: int = 1
    ffn_dim: int = 18432
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    window: int = 128                  # the query's own position counts
    # experts
    n_experts: int = 128               # the router's width
    expert_dim: int = 2048
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 2.5
    route_norm_eps: float = 0.0
    held_first: int = 0                # the experts this chip holds
    held_count: int = 16
    norm_eps: float = 1e-5
    max_seq_len: int = 32768
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.layer_kinds) - {SLIDING, FULL}:
            raise ValueError(f"layer_kinds {self.layer_kinds} may hold only "
                             f"{SLIDING!r} and {FULL!r}")
        if FULL not in self.layer_kinds:
            raise ValueError("the block table pages the full layers: "
                             "layer_kinds has to hold one")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError(
                f"held experts {self.held_first}..+{self.held_count} are not "
                f"within the router's {self.n_experts}")
        if self.n_heads % self.n_kv_heads or self.window < 1:
            raise ValueError("n_heads has to be a multiple of n_kv_heads and "
                             "window at least 1")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    def n_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_kinds if k == kind)


def window_moe_tiny(**overrides) -> WindowMoEConfig:
    """The CPU tests' preset: the published order of the first five layers
    (a dense layer first), a window shorter than the test lengths, 16 experts
    of which 8 are held, top-2."""
    base = dict(
        vocab_size=64, dim=32, layer_kinds=(SLIDING, SLIDING, SLIDING, FULL,
                                            SLIDING),
        first_dense=1, ffn_dim=64, n_heads=4, n_kv_heads=2, head_dim=8,
        rope_theta=1e4, window=6, n_experts=16, expert_dim=16, top_k=2,
        held_count=8, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    base.update(overrides)
    return WindowMoEConfig(**base)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(cfg: WindowMoEConfig, key: jax.Array) -> dict:
    """Random parameters: matrices ``[in, out]`` normal at ``1/sqrt(in)``,
    norm weights 1, a small router bias that is not zero."""
    dt = cfg.param_dtype

    def mat(k, n_in, *out):
        return (jax.random.normal(k, (n_in, *out), jnp.float32)
                * n_in ** -0.5).astype(dt)

    d, hd = cfg.dim, cfg.head_dim
    layers = []
    for i in range(cfg.n_layers):
        ks = iter(jax.random.split(jax.random.fold_in(key, i), 16))
        lp = {"attn_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt),
              "wq": mat(next(ks), d, cfg.n_heads * hd),
              "wk": mat(next(ks), d, cfg.n_kv_heads * hd),
              "wv": mat(next(ks), d, cfg.n_kv_heads * hd),
              "q_norm": jnp.ones((hd,), dt), "k_norm": jnp.ones((hd,), dt),
              "wo": mat(next(ks), cfg.n_heads * hd, d)}
        if i < cfg.first_dense:
            lp.update(w_gate=mat(next(ks), d, cfg.ffn_dim),
                      w_up=mat(next(ks), d, cfg.ffn_dim),
                      w_down=mat(next(ks), cfg.ffn_dim, d))
        else:
            e, f = cfg.held_count, cfg.expert_dim
            lp.update(
                w_router=mat(next(ks), d, cfg.n_experts),
                router_bias=jax.random.uniform(
                    next(ks), (cfg.n_experts,), jnp.float32, -0.05, 0.05),
                e_gate=mat(next(ks), d, e, f).transpose(1, 0, 2),
                e_up=mat(next(ks), d, e, f).transpose(1, 0, 2),
                e_down=mat(next(ks), f, e, d).transpose(1, 0, 2))
            if cfg.n_shared:
                sf = cfg.n_shared * f
                lp.update(s_gate=mat(next(ks), d, sf),
                          s_up=mat(next(ks), d, sf),
                          s_down=mat(next(ks), sf, d))
        layers.append(lp)
    top = jax.random.split(jax.random.fold_in(key, 10_000), 2)
    return {"embed": jax.random.normal(top[0], (cfg.vocab_size, d),
                                       jnp.float32).astype(dt),
            "layers": tuple(layers),
            "final_norm": jnp.ones((d,), dt),
            "lm_head": mat(top[1], d, cfg.vocab_size)}


def param_partition_specs(cfg: WindowMoEConfig, *, tp_axis: str = "tp"):
    raise NotImplementedError(
        "tensor-parallel serving of a WindowMoEConfig is not written: its "
        "rings and snapshots would split by key head as the pools do, but "
        "no spec for them exists yet; serve it at tp_size=1")


def paged_cache_partition_specs(*, tp_axis: str = "tp"):
    return param_partition_specs(None, tp_axis=tp_axis)


def tp_split_dims(cfg: WindowMoEConfig) -> tuple:
    """Asked only at ``tp_size > 1``, which this model does not serve."""
    return param_partition_specs(cfg)


# ---------------------------------------------------------------------------
# the paged state
# ---------------------------------------------------------------------------

class WindowPagedCache(NamedTuple):
    """The full layers' pools and the sliding layers' two states behind one
    block table (block 0 is trash in each): ``k`` / ``v`` ``[n_full,
    n_blocks, bs, KVH, Dh]``; ``ring`` ``[2, n_sliding, n_slots, window, KVH,
    Dh]``, each slot's last ``window`` keys and values at its length;
    ``snap`` ``[2, n_sliding, n_blocks, window, KVH, Dh]``, each full block's
    ring at its last position; ``block_table`` [B, blocks_per_slot] int32,
    ``length`` [B] int32, and ``stats`` [2, 5 + held_count + 5] int32, the
    device-side counters."""

    k: jax.Array
    v: jax.Array
    ring: jax.Array
    snap: jax.Array
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
    cfg: WindowMoEConfig, n_slots: int, max_len: int, *,
    block_size: int, n_blocks: int | None = None,
) -> WindowPagedCache:
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
    kv = (cfg.n_of(FULL), n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
    state = (cfg.window, cfg.n_kv_heads, cfg.head_dim)
    n_sl = cfg.n_of(SLIDING)
    return WindowPagedCache(
        k=jnp.zeros(kv, cfg.dtype), v=jnp.zeros(kv, cfg.dtype),
        ring=jnp.zeros((2, n_sl, n_slots) + state, cfg.dtype),
        snap=jnp.zeros((2, n_sl, n_blocks) + state, cfg.dtype),
        block_table=jnp.zeros((n_slots, per), jnp.int32),
        length=jnp.zeros((n_slots,), jnp.int32),
        stats=jnp.zeros((2, LOAD0 + cfg.held_count + len(_TAIL)), jnp.int32))


def paged_pool_bytes(pcache: WindowPagedCache) -> dict:
    """Device bytes one block holds in each pool: its keys and values of the
    full layers and its snapshot of the sliding layers' ring."""
    return {name: int(np.prod(a.shape) // n) * a.dtype.itemsize
            for name, a, n in (("k", pcache.k, pcache.k.shape[1]),
                               ("v", pcache.v, pcache.v.shape[1]),
                               ("snap", pcache.snap, pcache.snap.shape[2]))}


def paged_counters(pcache: WindowPagedCache) -> jax.Array:
    """The device array the engine reads back beside the tick's tokens."""
    return pcache.stats


def read_counters(stats_host: np.ndarray) -> dict:
    """The counters as Python ints (sums exact past 2**31)."""
    return paged.read_stats(stats_host, _SUMS, _TAIL)


def publish_paged_metrics(metrics, cfg: WindowMoEConfig,
                          pcache: WindowPagedCache,
                          stats_host: np.ndarray | None = None,
                          row_blocks: tuple = (),
                          programs: tuple = ()) -> None:
    """The model's own gauges and counters in the engine's registry:
    :func:`paged.publish_state_metrics`'s (a slot's state is its ring) and,
    where a tick's readback brought ``stats_host``, the largest held load
    and what the live rows hold: ``kv.full_bytes_live`` the pools' bytes of
    the blocks their tables map, ``kv.window_bytes_live`` a ring a live row
    and a snapshot a mapped block, ``kv.tokens_live`` the positions they
    hold, all three as the tick that was read back left them."""
    per_block = paged_pool_bytes(pcache)
    ring_slot = int(np.prod(pcache.ring.shape) // pcache.ring.shape[2]
                    ) * pcache.ring.dtype.itemsize
    c = paged.publish_state_metrics(
        metrics, cfg, pcache, stats_host, programs, per_block=per_block,
        slot_bytes=ring_slot, counted=_counted(metrics), read=read_counters,
        walk_split=_walk_split(pcache.block_size))
    if c is None:
        return
    metrics.gauge("moe.load_max").set(max(c["held_load"]))
    metrics.gauge("kv.tokens_live").set(c["tokens_live"])
    metrics.gauge("kv.full_bytes_live").set(
        c["blocks_live"] * (per_block["k"] + per_block["v"]))
    metrics.gauge("kv.window_bytes_live").set(
        c["rows_live"] * ring_slot + c["blocks_live"] * per_block["snap"])


def _counted(metrics) -> tuple:
    """The registry's counter of each of the device's running sums, beside
    it the gauge ``<name>.device`` (:func:`paged.count_from_device`) and the
    sum's name in :func:`read_counters` (written out, as in
    ``shortconv_moe``, for the names lint)."""
    return (
        (metrics.counter("moe.choices_total"),
         metrics.gauge("moe.choices_total.device"), "choices_total"),
        (metrics.counter("moe.choices_held"),
         metrics.gauge("moe.choices_held.device"), "choices_held"),
        (metrics.counter("moe.layers_batched"),
         metrics.gauge("moe.layers_batched.device"), "layers_batched"),
        (metrics.counter("window.state_restores"),
         metrics.gauge("window.state_restores.device"), "state_restores"),
        (metrics.counter("window.snapshots_written"),
         metrics.gauge("window.snapshots_written.device"),
         "snapshots_written"),
        (metrics.counter("attn.keys_visible"),
         metrics.gauge("attn.keys_visible.device"), "keys_visible"))


def set_row(pcache: WindowPagedCache, slot, row, length) -> WindowPagedCache:
    """Map slot ``slot`` to the blocks ``row`` at ``length`` (a whole number
    of blocks): the table and the length as every model's, and the slot's
    ring as the sequence has it at ``length`` — the snapshot of the block
    that ends there, zeros at 0.  The interface's optional function;
    ``ServeEngine._set_row`` is its only caller."""
    length = jnp.asarray(length, jnp.int32)
    last = paged.block_before(row, length, pcache.block_size)
    state = jnp.where(length > 0, pcache.snap[:, :, last], 0)
    add = jnp.zeros((pcache.stats.shape[1],), jnp.int32).at[RESTORES].set(
        (length > 0).astype(jnp.int32))
    return pcache._replace(
        block_table=pcache.block_table.at[slot].set(row),
        length=pcache.length.at[slot].set(length),
        ring=pcache.ring.at[:, :, slot].set(state),
        stats=_add_stats(pcache.stats, add, None))


# ---------------------------------------------------------------------------
# layer mathematics
# ---------------------------------------------------------------------------

def _ring_positions(pos, window: int):
    """The position each ring index holds for a sequence of length ``pos``
    [B]: the latest one below ``pos`` that is the index modulo ``window``,
    negative where none has been written.  [B, window]."""
    last = pos[:, None] - 1
    return last - jnp.mod(last - jnp.arange(window)[None, :], window)


def _window_attend(cfg: WindowMoEConfig, q, k, v, ring_k, ring_v, pos):
    """Banded attention of ``q`` [B, T, H, Dh] (rotated) at positions ``pos +
    0..T-1`` over the rows' rings ``[B, window, KVH, Dh]`` and their own keys
    and values ``k`` / ``v`` [B, T, KVH, Dh]: queries in blocks of ``c =
    min(T, window)``, each over the block before it (the ring, for the first)
    and its own, so no query scores more than ``window + c`` keys.  Scores,
    softmax and output accumulate in float32.  Returns [B, T, H, Dh]
    (float32)."""
    b, t, n_heads, hd = q.shape
    kvh, w = k.shape[2], cfg.window
    c = min(t, w)
    nb = -(-t // c)
    pad = ((0, 0), (0, nb * c - t), (0, 0), (0, 0))
    q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    qpos = pos[:, None] + jnp.arange(nb * c)[None, :]
    prev_pos = _ring_positions(pos, w)
    prev_k, prev_v = ring_k, ring_v
    if nb > 1:                  # then c == window: whole blocks before each
        prev_pos = jnp.concatenate([prev_pos, qpos[:, :-c]], axis=1)
        prev_k = jnp.concatenate([ring_k, k[:, :-c]], axis=1)
        prev_v = jnp.concatenate([ring_v, v[:, :-c]], axis=1)
    qb = q.reshape(b, nb, c, kvh, n_heads // kvh, hd)
    qp = qpos.reshape(b, nb, c, 1)

    def scores(keys, kpos, m):
        s = jnp.einsum("bnqkrd,bnmkd->bnkrqm", qb,
                       keys.reshape(b, nb, m, kvh, hd),
                       preferred_element_type=jnp.float32) * hd ** -0.5
        kp = kpos.reshape(b, nb, 1, m)
        seen = (kp >= 0) & (kp <= qp) & (qp - kp < w)
        return jnp.where(seen[:, :, None, None], s, NEG_INF_LOGIT)

    # every query sees its own key, so the maximum is a real score
    p = jax.nn.softmax(jnp.concatenate(
        [scores(prev_k, prev_pos, w), scores(k, qpos, c)], axis=-1), axis=-1)

    def weighed(p_part, values, m):
        return jnp.einsum("bnkrqm,bnmkd->bnqkrd", p_part,
                          values.reshape(b, nb, m, kvh, hd).astype(
                              jnp.float32))

    o = weighed(p[..., :w], prev_v, w) + weighed(p[..., w:], v, c)
    return o.reshape(b, nb * c, n_heads, hd)[:, :t]


def _walk_split(bs: int) -> int:
    """The pieces the full layers' walk sees a block of ``bs`` positions as:
    the fewest whose size is within :data:`GATHER_ROWS`."""
    return bs // max(p for p in range(1, min(bs, GATHER_ROWS) + 1)
                     if bs % p == 0)


class _Ran(NamedTuple):
    """What a program's forward pass leaves for :func:`_commit`."""

    k: jax.Array
    v: jax.Array
    own: jax.Array              # [2, n_sliding, B, T, KVH, Dh]
    stats: jax.Array


def _forward_paged(params, tokens, cfg: WindowMoEConfig,
                   pcache: WindowPagedCache, qpos, table, ring, valid,
                   set_touched: bool, sel=None, there=None):
    """The shared body of the paged programs: ``tokens`` [B, T] at positions
    ``qpos`` under block tables ``table`` [B, per], the rows' rings ``ring``
    [2, n_sliding, B, window, KVH, Dh]; ``valid`` [B, T] marks the tokens
    that count (for the counters and the routing; a row with none is one
    whose output nobody reads, and the full layers walk it one tile).  Writes
    the full layers' keys and values, none for a row that is not ``there``
    [B] (default: all are); the rings are the caller's to commit.  With
    ``sel`` [B] the logits are of each row's position ``sel`` alone, [B, V],
    picked before the final norm and the head."""
    dt = cfg.dtype
    b, t = tokens.shape
    n_full, n_blocks, bs, kvh, hd = pcache.k.shape
    per = table.shape[1]
    wblk = jnp.take_along_axis(table, jnp.clip(qpos // bs, 0, per - 1),
                               axis=1)
    wflat = wblk * bs + qpos % bs                                # [B, T]
    if there is not None:       # past every layer's stripe: a dropped write
        wflat = jnp.where(there[:, None], wflat, n_full * n_blocks * bs)
    kf = pcache.k.reshape(n_full * n_blocks * bs, kvh, hd)
    vf = pcache.v.reshape(n_full * n_blocks * bs, kvh, hd)
    cos, sin = llama.rope_tables(cfg, qpos)
    # the walk sees each block as `split` pieces of `piece` positions: the
    # same flat positions of the same pools under a finer table
    split = _walk_split(bs)
    piece = bs // split
    walk = llama.tile_walk(
        (table[:, :, None] * split + jnp.arange(split)).reshape(b, -1), qpos,
        piece, jnp.any(valid, axis=1))
    x = params["embed"][tokens].astype(dt)
    i_full = i_sl = 0
    own = []
    load = jnp.zeros((cfg.held_count,), jnp.int32)
    touched = batched = jnp.int32(0)
    for i, (kind, lp) in enumerate(zip(cfg.layer_kinds, params["layers"])):
        q = rmsnorm(_dot(x, lp["wq"], dt).reshape(b, t, cfg.n_heads, hd),
                    lp["q_norm"], cfg.norm_eps)
        k = rmsnorm(_dot(x, lp["wk"], dt).reshape(b, t, kvh, hd),
                    lp["k_norm"], cfg.norm_eps)
        v = _dot(x, lp["wv"], dt).reshape(b, t, kvh, hd)
        if kind == SLIDING:
            with jax.named_scope("attn.window"):
                q = llama.apply_rope(q, cos, sin)
                k = llama.apply_rope(k, cos, sin)
                o = _window_attend(cfg, q, k, v, ring[0, i_sl], ring[1, i_sl],
                                   qpos[:, 0])
            own.append(jnp.stack([k, v]))
            i_sl += 1
        else:
            with jax.named_scope("attn.full"):
                o, kf, vf = llama.paged_attend_tiles(
                    q, k, v, kf, vf, i_full, walk, wflat, n_blocks * split,
                    piece)
            i_full += 1
        o = _dot(o.astype(dt).reshape(b, t, cfg.n_heads * hd), lp["wo"], dt)
        x = x + rmsnorm(o, lp["attn_norm"], cfg.norm_eps)
        if i < cfg.first_dense:
            m = _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"], dt)
        else:
            x2 = x.reshape(b * t, cfg.dim)
            m, layer_load = latent_moe.held_experts(cfg, lp, x2,
                                                    valid.reshape(b * t))
            if cfg.n_shared:
                with jax.named_scope("moe.shared"):
                    m = m + _swiglu(x2, lp["s_gate"], lp["s_up"],
                                    lp["s_down"], dt)
            m = m.reshape(b, t, cfg.dim)
            load = load + layer_load
            touched = touched + jnp.sum(layer_load > 0, dtype=jnp.int32)
            batched = batched + latent_moe.layers_batched(b * t, layer_load)
        x = x + rmsnorm(m, lp["ffn_norm"], cfg.norm_eps)
    if sel is not None:
        x = x[jnp.arange(b), sel]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _dot(x, params["lm_head"], dt).astype(jnp.float32)
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    seen = jnp.sum(jnp.where(
        valid, n_full * (qpos + 1)
        + cfg.n_of(SLIDING) * jnp.minimum(qpos + 1, cfg.window), 0),
        dtype=jnp.int32)
    n_moe = cfg.n_layers - cfg.first_dense
    add = jnp.concatenate([
        jnp.stack([n_valid * (cfg.top_k * n_moe), jnp.sum(load),
                   jnp.int32(0), seen, jnp.int32(0)]), load,
        jnp.zeros((len(_TAIL),), jnp.int32).at[1].set(batched)])
    stats = _add_stats(pcache.stats, add, touched if set_touched else None)
    return logits, _Ran(kf.reshape(pcache.k.shape),
                        vf.reshape(pcache.v.shape), jnp.stack(own, axis=1),
                        stats)


def _write_snapshots(cfg: WindowMoEConfig, ring, snap, own, pos, n, table,
                     slots, bs: int):
    """A snapshot in every block whose last position is among the ``n`` [B]
    tokens the rows ``slots`` [B] count of the program's ``T`` (they started
    at ``pos`` under ``table``): the ring as of that position, from the rows'
    rings as they were (``ring``, not yet committed) and their own keys and
    values ``own`` [2, n_sliding, B, T, KVH, Dh].  One step of a loop a block
    end reached, none in most ticks: a ring is ``window`` positions of every
    sliding layer, and gathering one a row whether or not it is kept would
    read the rings once more every tick.  Returns the pools and how many were
    written."""
    b, t, w = own.shape[2], own.shape[3], cfg.window
    j, reached, dest = paged.block_ends(pos, n, t, table, bs, snap.shape[2])
    ends = j.shape[1]
    flat = reached.reshape(b * ends)
    n_reached = jnp.sum(flat, dtype=jnp.int32)
    ids = jnp.zeros((b * ends,), jnp.int32).at[
        jnp.where(flat, jnp.cumsum(flat) - 1, b * ends)].set(
            jnp.arange(b * ends, dtype=jnp.int32), mode="drop")

    def one_end(i, carry):
        ring, snap = carry
        r, e = ids[i] // ends, ids[i] % ends
        end = pos[r] + j[r, e]                      # the block's last position
        at = end - jnp.mod(end - jnp.arange(w), w)  # what each index holds
        new = lax.dynamic_index_in_dim(own, r, axis=2, keepdims=False)[
            :, :, jnp.clip(at - pos[r], 0, t - 1)]
        old = lax.dynamic_index_in_dim(ring, slots[r], axis=2, keepdims=False)
        state = jnp.where((at >= pos[r])[None, None, :, None, None], new, old)
        return ring, lax.dynamic_update_index_in_dim(snap, state, dest[r, e],
                                                     axis=2)

    ring, snap = lax.fori_loop(0, n_reached, one_end, (ring, snap))
    return ring, snap, n_reached


def _commit(cfg: WindowMoEConfig, pcache: WindowPagedCache, ran: _Ran,
            pos, n, table, slots, live: bool) -> WindowPagedCache:
    """Leave the cache as after ``n`` [B] tokens of each of the program's
    rows ``slots`` [B] (which started at ``pos`` [B] under ``table``): the
    pools as written, a snapshot in every block whose last position is among
    the ``n``, each slot's ring with the last ``window`` of its ``n`` tokens
    written over the positions they push out, and the lengths.  A row whose
    slot is past the slots is not there, and leaves nothing.  ``live``: the
    program is over every slot, and sets the gauges of what they hold."""
    b, t, w = ran.own.shape[2], ran.own.shape[3], cfg.window
    bs = pcache.block_size
    ring, snap, n_snaps = _write_snapshots(
        cfg, pcache.ring, pcache.snap, ran.own, pos, n, table, slots, bs)
    i = jnp.arange(t)[None, :]
    kept = (i < n[:, None]) & (i >= n[:, None] - w)
    at = jnp.where(kept, (pos[:, None] + i) % w, w)        # past the ring: drop
    ring = ring.at[:, :, slots[:, None], at].set(ran.own, mode="drop")
    length = pcache.length.at[slots].set(pos + n, mode="drop")
    add = jnp.zeros((pcache.stats.shape[1],), jnp.int32).at[
        -len(_TAIL)].set(n_snaps)
    stats = _add_stats(ran.stats, add, None)
    if live:
        mapped = jnp.zeros((pcache.k.shape[1],), jnp.int32).at[
            pcache.block_table.reshape(-1)].set(1)
        gauges = jnp.stack([jnp.sum(length), jnp.sum(mapped[1:]),
                            jnp.sum(length > 0, dtype=jnp.int32)])
        stats = stats.at[:, -_GAUGES:].set(jnp.stack(
            [gauges >> latent_moe._LO_BITS,
             gauges & ((1 << latent_moe._LO_BITS) - 1)]))
    return pcache._replace(k=ran.k, v=ran.v, ring=ring, snap=snap,
                           length=length, stats=stats)


# ---------------------------------------------------------------------------
# the engine's interface (the signatures of models/llama.py)
# ---------------------------------------------------------------------------

def _forward_all_slots(params, tokens, cfg, pcache, counted):
    """The forward pass of a program over every slot: ``(logits, ran)``."""
    t = tokens.shape[1]
    pos = pcache.length
    qpos = pos[:, None] + jnp.arange(t)[None, :]
    valid = jnp.broadcast_to((counted > 0)[:, None], tokens.shape)
    return _forward_paged(params, tokens, cfg, pcache, qpos,
                          pcache.block_table, pcache.ring, valid, True)


def decode_chunk_paged(
    params: dict, tokens: jax.Array, cfg: WindowMoEConfig,
    pcache: WindowPagedCache, *, advance: jax.Array | None = None,
) -> tuple[jax.Array, WindowPagedCache]:
    """T tokens per row against the cache (the tick).  ``advance`` [B] (0 or
    T) gates the rows as in :func:`llama.decode_chunk_paged`: a row held in
    place keeps its length and its ring."""
    b, t = tokens.shape
    adv = (jnp.full((b,), t, jnp.int32) if advance is None
           else jnp.asarray(advance, jnp.int32))
    logits, ran = _forward_all_slots(params, tokens, cfg, pcache, adv)
    return logits, _commit(cfg, pcache, ran, pcache.length, adv,
                           pcache.block_table, jnp.arange(b), True)


def decode_chunk_paged_rows(
    params: dict, tokens: jax.Array, cfg: WindowMoEConfig,
    pcache: WindowPagedCache, slots: jax.Array, *, new_length: jax.Array,
    sel: jax.Array | None,
) -> tuple[jax.Array, WindowPagedCache]:
    """A chunk of prefill for several rows in one program, one read of the
    weights for all of them: ``tokens`` [R, T] continue the slots ``slots``
    [R] (each at most once) from their lengths, which become ``new_length``
    [R]; positions past it are padding and count for nothing, the ring
    included.  Returns the logits of each row's position ``sel`` [R] alone,
    [R, V] (of every position, [R, T, V], with ``sel`` ``None``), and the
    cache.  A row whose slot is past the slots (``n_slots``) is not there: it
    writes no key, no ring, no snapshot and no length."""
    slots = jnp.asarray(slots, jnp.int32)
    new_length = jnp.asarray(new_length, jnp.int32)
    r, t = tokens.shape
    there, at, pos, qpos, table = paged.chunk_rows(pcache, slots, t)
    valid = (qpos < new_length[:, None]) & there[:, None]
    # a slice a row and not one gather by `at`: a ring is megabytes, and a
    # gather by an index array may be served from a copy of every slot's
    ring = jnp.stack([pcache.ring[:, :, at[i]] for i in range(r)], axis=2)
    logits, ran = _forward_paged(
        params, tokens, cfg, pcache, qpos, table, ring, valid, False,
        None if sel is None else jnp.asarray(sel, jnp.int32), there)
    return logits, _commit(cfg, pcache, ran, pos,
                           jnp.where(there, new_length - pos, 0), table,
                           slots, False)


def decode_chunk_paged_row(
    params: dict, tokens: jax.Array, cfg: WindowMoEConfig,
    pcache: WindowPagedCache, slot: jax.Array, *, new_length: jax.Array,
) -> tuple[jax.Array, WindowPagedCache]:
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


def spec_verify_paged(params, cfg, pcache, last_logits, drafts, active):
    """:func:`llama.spec_verify_paged`'s round over this model: the same
    ``[tok, d_1..d_K]`` wide tick and greedy longest-prefix acceptance, but
    the lengths alone do not roll a ring back: the round keeps the sliding
    layers' keys and values of all ``K + 1`` positions and leaves each slot's
    ring, and any snapshot of a block that filled, as after its ``1 +
    accepted`` tokens.  What a rejected position wrote to ``k`` / ``v`` lies
    past the length."""
    b, k = drafts.shape
    active = jnp.asarray(active, jnp.int32)
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    chunk = jnp.concatenate([tok[:, None], drafts], axis=1)
    logits, ran = _forward_all_slots(params, chunk, cfg, pcache, active)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    match = (drafts == preds[:, :k]).astype(jnp.int32)
    accept = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    pcache = _commit(cfg, pcache, ran, pcache.length, active * (1 + accept),
                     pcache.block_table, jnp.arange(b), True)
    return tok, accept, logits[jnp.arange(b), accept], pcache


def forward(params: dict, tokens: jax.Array,
            cfg: WindowMoEConfig) -> jax.Array:
    """Logits [B, L, V] of whole sequences with no cache kept: every row
    through one chunk of a cache made for the call and thrown away."""
    b, l = tokens.shape
    pcache = init_paged_cache(cfg, b, l, block_size=l)
    pcache = pcache._replace(
        block_table=1 + jnp.arange(b, dtype=jnp.int32)[:, None])
    return decode_chunk_paged(params, tokens, cfg, pcache)[0]


def generate(params: dict, cfg: WindowMoEConfig, prompt: list,
             max_new_tokens: int, pad_to: int | None = None) -> list:
    """Greedy decoding with no cache: the whole sequence again for every
    token (padded to ``pad_to``, so one program).  For tests."""
    seq = list(prompt)
    width = pad_to or len(prompt) + max_new_tokens
    fwd = jax.jit(partial(forward, cfg=cfg))
    for _ in range(max_new_tokens):
        toks = jnp.asarray([seq + [0] * (width - len(seq))], jnp.int32)
        seq.append(int(jnp.argmax(fwd(params, toks)[0, len(seq) - 1])))
    return seq[len(prompt):]
