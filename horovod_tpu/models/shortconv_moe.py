"""A served decoder whose layers are of two kinds with two kinds of cache: a
gated short convolution, which carries a fixed-size recurrent state per
sequence, beside grouped-query attention, which pages a row per position; the
feed-forward part is a SwiGLU in the first layers and sigmoid-routed experts
after them.

This is the architecture of the LFM2 expert models (``lfm2_moe``), written for
:class:`~horovod_tpu.serving_scheduler.ServeEngine`: the module implements the
engine's paged model interface (:mod:`horovod_tpu.models.paged`) beside
:mod:`horovod_tpu.models.llama` and :mod:`horovod_tpu.models.latent_moe`, walks
its attention layers' blocks with the first's :func:`~horovod_tpu.models.
llama.paged_attend_tiles` and computes its expert layers with the second's
:func:`~horovod_tpu.models.latent_moe.held_experts`.

**Layers.**  ``layer_kinds[i]`` is ``"conv"`` or ``"attn"``; the first
``first_dense`` layers have a SwiGLU, the others the expert layer.  The layers
differ in shape, so they are a Python loop over a tuple of per-layer parameter
dicts.  ``u = RMSNorm_op(x)``, ``h = x + Op(u)``, ``y = h + FFN(RMSNorm_ffn(
h))``; no biases anywhere.

* *conv*: ``[B, C, X] = split3(u W_in)``; ``z = B * X``; ``c_t = sum_j w[j] *
  z_{t - (K - 1) + j}`` over the ``K = conv_kernel`` taps (depthwise, causal,
  ``z`` before position 0 is zero); ``Op = (C * c) W_out``.  The state a
  sequence carries is its last ``K - 1`` values of ``z``.
* *attn*: ``n_heads`` queries and ``n_kv_heads`` keys and values of
  ``head_dim``; RMSNorm over each query and key head (own weights), then
  half-split rotary on the whole head; causal softmax at
  ``1 / sqrt(head_dim)``.
* *experts*: ``s = sigmoid(h W_r)``; the ``top_k`` largest ``s + bias`` are
  chosen and weighted ``s_e / (sum_sel s + route_norm_eps)`` times
  ``routed_scale``.  No shared expert.
* The head is the embedding, transposed (tied).

**Two kinds of state behind one block table.**  :class:`ShortConvPagedCache`
holds the attention layers' ``k`` / ``v`` pools ``[n_attn, n_blocks, bs, KVH /
p, p * Dh]`` (``p`` key heads side by side fill a row of 128 lanes:
:attr:`ShortConvMoEConfig.kv_pack`), which are per position and immutable
once written, and two arrays of
the convolution's state, each row ``(K - 1) * dim`` wide (the ``K - 1``
values of ``z`` side by side):

* ``conv`` ``[n_conv, n_slots, (K - 1) * dim]``: the state of the sequence in
  each slot *at its length*.  Every program reads its rows' carries from here
  and leaves them as after the tokens that counted: a prefill chunk after its
  real tokens (not its padding), a tick after one token for the rows that
  advance, a verify round after ``1 + accepted`` tokens (the lengths alone do
  **not** roll a recurrent state back, so the round keeps ``z`` of all its
  positions and picks).
* ``snap`` ``[n_conv, n_blocks, (K - 1) * dim]``: per physical block, the
  state at the block's last position.  Whichever program's counted tokens
  reach a block's last position writes it, so every block that is full holds
  one, and a block id means the same block of ``k``, ``v`` and ``snap``:
  :class:`~horovod_tpu.models.llama.BlockPool`, the prefix cache, release to
  cache at retirement and preemption replay need to know nothing of it.

**The rule for a row that is (re)mapped** is :func:`set_row`, the interface's
optional function that ``ServeEngine._set_row`` calls in its one table-write
program: a row mapped at length ``p`` (a whole number of blocks: 0, or a
prefix hit's frontier) gets the snapshot of the block before ``p`` in its new
table as its slot's state, zeros at ``p = 0``.  A prefix hit, a replay after
preemption and a fresh engine's first request all go through it.

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

from horovod_tpu.models import latent_moe, llama, paged
from horovod_tpu.models.latent_moe import LOAD0, _add_stats, _dot, _swiglu
from horovod_tpu.models.llama import rmsnorm

CONV, ATTN = "conv", "attn"
#: stats columns (``latent_moe``'s layout: running sums, the touched gauge at
#: ``TOUCHED``, the experts' load from ``LOAD0``, last the layers batched)
CHOICES_TOTAL, RESTORES, SNAPSHOTS, KEYS_VISIBLE = 0, 1, 2, 3
_SUMS = ("choices_total", "state_restores", "snapshots_written",
         "keys_visible")            # the first columns' names, in that order


@dataclasses.dataclass(frozen=True)
class ShortConvMoEConfig:
    vocab_size: int = 65536
    dim: int = 2048
    layer_kinds: tuple = (CONV, CONV) + (ATTN, CONV, CONV, CONV) * 3
    first_dense: int = 2
    ffn_dim: int = 7168
    conv_kernel: int = 3
    # attention layers
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    # experts
    n_experts: int = 32
    expert_dim: int = 1792
    top_k: int = 4
    routed_scale: float = 1.0
    route_norm_eps: float = 1e-6
    held_first: int = 0                # every expert is held here
    held_count: int = 32
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.layer_kinds) - {CONV, ATTN}:
            raise ValueError(f"layer_kinds {self.layer_kinds} may hold only "
                             f"{CONV!r} and {ATTN!r}")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError(
                f"held experts {self.held_first}..+{self.held_count} are not "
                f"within the router's {self.n_experts}")
        if self.n_heads % self.n_kv_heads or self.conv_kernel < 2:
            raise ValueError("n_heads has to be a multiple of n_kv_heads and "
                             "conv_kernel at least 2")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    def n_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_kinds if k == kind)

    @property
    def kv_pack(self) -> int:
        """Key heads side by side in one row of the ``k`` / ``v`` pools: as
        many as fill a row of 128 lanes.  The TPU tiles an array's last
        dimension in 128 lanes, and a pool whose rows are narrower is held
        padded inside a program and copied whole by it (seen by compiling
        for the chip with rows of 64: each layer's scatter made two copies
        of the pool); see also :func:`latent_moe._lanes`."""
        fit = [p for p in range(1, self.n_kv_heads + 1)
               if self.n_kv_heads % p == 0
               and p * self.head_dim <= latent_moe.LANES]
        return max(fit, default=1)

    @property
    def state_width(self) -> int:
        """One conv layer's state of one sequence, as one row."""
        return (self.conv_kernel - 1) * self.dim


def shortconv_moe_tiny(**overrides) -> ShortConvMoEConfig:
    """The CPU tests' preset: both kinds of layer in the published order, a
    dense layer, 8 experts top-2."""
    base = dict(
        vocab_size=64, dim=32, layer_kinds=(CONV, ATTN, CONV, CONV, ATTN),
        first_dense=1, ffn_dim=64, n_heads=4, n_kv_heads=2, head_dim=8,
        rope_theta=1e4, n_experts=8, expert_dim=16, top_k=2, held_count=8,
        max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(overrides)
    return ShortConvMoEConfig(**base)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(cfg: ShortConvMoEConfig, key: jax.Array) -> dict:
    """Random parameters: matrices ``[in, out]`` normal at ``1/sqrt(in)``,
    conv taps ``[K, dim]`` normal at ``1/sqrt(K)``, norm weights 1, a small
    router bias that is not zero.  No head: it is the embedding."""
    dt = cfg.param_dtype

    def mat(k, n_in, *out):
        return (jax.random.normal(k, (n_in, *out), jnp.float32)
                * n_in ** -0.5).astype(dt)

    d, hd = cfg.dim, cfg.head_dim
    layers = []
    for i, kind in enumerate(cfg.layer_kinds):
        ks = iter(jax.random.split(jax.random.fold_in(key, i), 16))
        lp = {"op_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt)}
        if kind == CONV:
            lp.update(
                w_in=mat(next(ks), d, 3 * d),
                conv_w=(jax.random.normal(next(ks), (cfg.conv_kernel, d),
                                          jnp.float32)
                        * cfg.conv_kernel ** -0.5).astype(dt),
                w_out=mat(next(ks), d, d))
        else:
            lp.update(
                wq=mat(next(ks), d, cfg.n_heads * hd),
                wk=mat(next(ks), d, cfg.n_kv_heads * hd),
                wv=mat(next(ks), d, cfg.n_kv_heads * hd),
                q_norm=jnp.ones((hd,), dt), k_norm=jnp.ones((hd,), dt),
                wo=mat(next(ks), cfg.n_heads * hd, d))
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
        layers.append(lp)
    return {"embed": jax.random.normal(jax.random.fold_in(key, 10_000),
                                       (cfg.vocab_size, d),
                                       jnp.float32).astype(dt),
            "layers": tuple(layers),
            "final_norm": jnp.ones((d,), dt)}


def param_partition_specs(cfg: ShortConvMoEConfig, *, tp_axis: str = "tp"):
    raise NotImplementedError(
        "tensor-parallel serving of a ShortConvMoEConfig is not written: "
        "its convolution state is one row per sequence, not per head, so "
        "the head split of llama.paged_cache_partition_specs does not "
        "cover it; serve it at tp_size=1")


def paged_cache_partition_specs(*, tp_axis: str = "tp"):
    return param_partition_specs(None, tp_axis=tp_axis)


def tp_split_dims(cfg: ShortConvMoEConfig) -> tuple:
    """Asked only at ``tp_size > 1``, which this model does not serve."""
    return param_partition_specs(cfg)


# ---------------------------------------------------------------------------
# the paged state
# ---------------------------------------------------------------------------

class ShortConvPagedCache(NamedTuple):
    """The attention layers' pools and the convolution's two states behind
    one block table (block 0 is trash in each): ``k`` / ``v`` ``[n_attn,
    n_blocks, bs, KVH / p, p * Dh]``; ``conv`` ``[n_conv, n_slots,
    (K - 1) * dim]``, each slot's state at its length; ``snap`` ``[n_conv,
    n_blocks, (K - 1) * dim]``, each full block's state at its last position;
    ``block_table`` [B, blocks_per_slot] int32, ``length`` [B] int32, and
    ``stats`` [2, 5 + held_count + 1] int32, the device-side counters."""

    k: jax.Array
    v: jax.Array
    conv: jax.Array
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
    cfg: ShortConvMoEConfig, n_slots: int, max_len: int, *,
    block_size: int, n_blocks: int | None = None,
) -> ShortConvPagedCache:
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
    kv = (cfg.n_of(ATTN), n_blocks, block_size, cfg.n_kv_heads // cfg.kv_pack,
          cfg.kv_pack * cfg.head_dim)
    n_conv = cfg.n_of(CONV)
    return ShortConvPagedCache(
        k=jnp.zeros(kv, cfg.dtype), v=jnp.zeros(kv, cfg.dtype),
        conv=jnp.zeros((n_conv, n_slots, cfg.state_width), cfg.dtype),
        snap=jnp.zeros((n_conv, n_blocks, cfg.state_width), cfg.dtype),
        block_table=jnp.zeros((n_slots, per), jnp.int32),
        length=jnp.zeros((n_slots,), jnp.int32),
        stats=jnp.zeros((2, LOAD0 + cfg.held_count + 1), jnp.int32))


def paged_pool_bytes(pcache: ShortConvPagedCache) -> dict:
    """Device bytes one block holds in each pool: its keys, its values and
    its snapshot of the convolution's state."""
    return {name: int(np.prod(a.shape) // a.shape[1]) * a.dtype.itemsize
            for name, a in (("k", pcache.k), ("v", pcache.v),
                            ("snap", pcache.snap))}


def paged_counters(pcache: ShortConvPagedCache) -> jax.Array:
    """The device array the engine reads back beside the tick's tokens."""
    return pcache.stats


def read_counters(stats_host: np.ndarray) -> dict:
    """The counters as Python ints (sums exact past 2**31)."""
    return paged.read_stats(stats_host, _SUMS, ("layers_batched",))


def publish_paged_metrics(metrics, cfg: ShortConvMoEConfig,
                          pcache: ShortConvPagedCache,
                          stats_host: np.ndarray | None = None,
                          row_blocks: tuple = (),
                          programs: tuple = ()) -> None:
    """The model's own gauges and counters in the engine's registry, all of
    them :func:`paged.publish_state_metrics`'s: a slot's row is a block's
    snapshot's size, and ``conv.state_restores`` counts rows mapped at a
    length past 0, which took their state from a block's snapshot
    (:func:`set_row`)."""
    per_block = paged_pool_bytes(pcache)
    paged.publish_state_metrics(
        metrics, cfg, pcache, stats_host, programs, per_block=per_block,
        slot_bytes=per_block["snap"], counted=_counted(metrics),
        read=read_counters)


def _counted(metrics) -> tuple:
    """The registry's counter of each of the device's running sums, beside
    it the gauge ``<name>.device`` (:func:`paged.count_from_device`) and the
    sum's name in :func:`read_counters`.  Each name stands written out at its
    call: the names lint (``tools/hvdlint``, HVD005) holds
    ``metrics.METRIC_HELP`` against literal call sites."""
    return (
        (metrics.counter("moe.choices_total"),
         metrics.gauge("moe.choices_total.device"), "choices_total"),
        (metrics.counter("moe.layers_batched"),
         metrics.gauge("moe.layers_batched.device"), "layers_batched"),
        (metrics.counter("conv.state_restores"),
         metrics.gauge("conv.state_restores.device"), "state_restores"),
        (metrics.counter("conv.snapshots_written"),
         metrics.gauge("conv.snapshots_written.device"),
         "snapshots_written"),
        (metrics.counter("attn.keys_visible"),
         metrics.gauge("attn.keys_visible.device"), "keys_visible"))


def set_row(pcache: ShortConvPagedCache, slot, row, length
            ) -> ShortConvPagedCache:
    """Map slot ``slot`` to the blocks ``row`` at ``length`` (a whole number
    of blocks): the table and the length as every model's, and the slot's
    convolution state as the sequence has it at ``length`` — the snapshot of
    the block that ends there, zeros at 0.  The interface's optional
    function; ``ServeEngine._set_row`` is its only caller."""
    length = jnp.asarray(length, jnp.int32)
    last = paged.block_before(row, length, pcache.block_size)
    state = jnp.where(length > 0, pcache.snap[:, last], 0)
    add = jnp.zeros((pcache.stats.shape[1],), jnp.int32).at[RESTORES].set(
        (length > 0).astype(jnp.int32))
    return pcache._replace(
        block_table=pcache.block_table.at[slot].set(row),
        length=pcache.length.at[slot].set(length),
        conv=pcache.conv.at[:, slot].set(state),
        stats=_add_stats(pcache.stats, add, None))


# ---------------------------------------------------------------------------
# layer mathematics
# ---------------------------------------------------------------------------

def _short_conv(cfg: ShortConvMoEConfig, lp: dict, u, carry):
    """One conv layer over ``u`` [B, T, d] with the rows' carries [B,
    (K - 1) * d]: the residual update and ``z`` of the carried and the new
    positions, [B, K - 1 + T, d], from which the caller takes what the rows
    carry on."""
    dt = cfg.dtype
    b, t, d = u.shape
    gate_b, gate_c, x = jnp.split(_dot(u, lp["w_in"], dt), 3, axis=-1)
    zs = jnp.concatenate([carry.reshape(b, cfg.conv_kernel - 1, d),
                          gate_b * x], axis=1)
    w = lp["conv_w"].astype(jnp.float32)
    c = sum(w[j] * zs[:, j:j + t].astype(jnp.float32)
            for j in range(cfg.conv_kernel))
    return _dot(gate_c * c.astype(dt), lp["w_out"], dt), zs


def _attention(cfg: ShortConvMoEConfig, lp: dict, u, cos, sin, kf, vf, layer,
               walk, wflat, n_blocks, bs):
    """One attention layer: q/k norm, rotary, then the shared walk over the
    row's live blocks, over packed heads (:attr:`ShortConvMoEConfig.
    kv_pack`): ``p`` neighbouring key heads are one pool row ``p * Dh`` wide,
    and a query stands in its own key head's part of such a row with zeros
    in the others', so its score is its own head's and the part of the
    output it keeps its own head's values.  Returns the residual update and
    the two pools."""
    dt = cfg.dtype
    b, t, _ = u.shape
    hd, p = cfg.head_dim, cfg.kv_pack
    rows, n_rep = cfg.n_kv_heads // p, cfg.n_heads // cfg.n_kv_heads
    q = _dot(u, lp["wq"], dt).reshape(b, t, cfg.n_heads, hd)
    k = _dot(u, lp["wk"], dt).reshape(b, t, cfg.n_kv_heads, hd)
    v = _dot(u, lp["wv"], dt).reshape(b, t, rows, p * hd)
    q = llama.apply_rope(rmsnorm(q, lp["q_norm"], cfg.norm_eps), cos, sin)
    k = llama.apply_rope(rmsnorm(k, lp["k_norm"], cfg.norm_eps), cos, sin)
    own = jnp.eye(p, dtype=dt)          # [its key head among p, part of row]
    q = (q.reshape(b, t, rows, p, n_rep, 1, hd)
         * own[:, None, :, None]).reshape(b, t, cfg.n_heads, p * hd)
    o, kf, vf = llama.paged_attend_tiles(
        q, k.reshape(b, t, rows, p * hd), v, kf, vf, layer, walk, wflat,
        n_blocks, bs, scale=hd ** -0.5)
    o = o.reshape(b, t, rows, p, n_rep, p, hd)
    o = jnp.stack([o[:, :, :, i, :, i] for i in range(p)], axis=3)
    return _dot(o.astype(dt).reshape(b, t, cfg.n_heads * hd), lp["wo"],
                dt), kf, vf


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_once(fn, cfg, form, *args):
    """``fn(cfg, *args)``, traced and lowered once for all the layers of a
    program that call it on like shapes: the layers are a Python loop, and a
    program's set-up is mostly the time to trace and lower it (14 layers
    unrolled: 2.3 s a program on the chip's host, PERF.md, PR 39).  XLA
    inlines the calls, so the compiled program is the one it was.  ``fn``
    and ``form`` (what of the module's own constants decides the traced
    form) are part of the key, so a function or a constant replaced for a
    test is traced anew."""
    return fn(cfg, *args)


def _conv_op(cfg: ShortConvMoEConfig, lp: dict, x, carry):
    """A conv layer's first half: ``x + Op(RMSNorm_op(x))`` and ``z``."""
    with jax.named_scope("conv.short"):
        o, z = _short_conv(cfg, lp, rmsnorm(x, lp["op_norm"], cfg.norm_eps),
                           carry)
    return x + o, z


def _ffn_dense(cfg: ShortConvMoEConfig, lp: dict, x):
    """A dense layer's second half: ``x + SwiGLU(RMSNorm_ffn(x))``."""
    h = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.dtype)


def _ffn_experts(cfg: ShortConvMoEConfig, lp: dict, x, valid):
    """An expert layer's second half, and the held experts' load."""
    b, t, d = x.shape
    y, load = latent_moe.held_experts(
        cfg, lp, rmsnorm(x, lp["ffn_norm"], cfg.norm_eps).reshape(b * t, d),
        valid.reshape(b * t))
    return x + y.reshape(b, t, d), load


class _Ran(NamedTuple):
    """What a program's forward pass leaves for :func:`_commit`."""

    k: jax.Array
    v: jax.Array
    zs: jax.Array               # [n_conv, B, K - 1 + T, d]
    stats: jax.Array


def _forward_paged(params, tokens, cfg: ShortConvMoEConfig,
                   pcache: ShortConvPagedCache, qpos, table, carry, valid,
                   set_touched: bool, sel=None, there=None):
    """The shared body of the paged programs: ``tokens`` [B, T] at positions
    ``qpos`` under block tables ``table`` [B, per], the rows' convolution
    carries ``carry`` [n_conv, B, (K - 1) * d]; ``valid`` [B, T] marks the
    tokens that count (for the counters and the routing; a row with none is
    one whose output nobody reads, and attention walks it one tile).  Writes
    keys and values, none for a row that is not ``there`` [B] (default:
    all are); the convolution's state is the caller's to commit.  With
    ``sel`` [B] the logits are of each row's position ``sel`` alone, [B, V],
    picked before the final norm and the head."""
    dt = cfg.dtype
    b, t = tokens.shape
    n_attn, n_blocks, bs, kvh, hd = pcache.k.shape
    per = table.shape[1]
    wblk = jnp.take_along_axis(table, jnp.clip(qpos // bs, 0, per - 1),
                               axis=1)
    wflat = wblk * bs + qpos % bs                                # [B, T]
    if there is not None:       # past every layer's stripe: a dropped write
        wflat = jnp.where(there[:, None], wflat, n_attn * n_blocks * bs)
    kf = pcache.k.reshape(n_attn * n_blocks * bs, kvh, hd)
    vf = pcache.v.reshape(n_attn * n_blocks * bs, kvh, hd)
    cos, sin = llama.rope_tables(cfg, qpos)
    walk = llama.tile_walk(table, qpos, bs, jnp.any(valid, axis=1))
    x = params["embed"][tokens].astype(dt)
    # inside a program a layer's body is traced once (`_layer_once`); a call
    # outside any (`forward` run eagerly) computes op by op as it always did
    once = _layer_once if isinstance(x, jax.core.Tracer) else (
        lambda fn, cfg, form, *args: fn(cfg, *args))
    i_attn = i_conv = 0
    zs = []
    load = jnp.zeros((cfg.held_count,), jnp.int32)
    touched = batched = jnp.int32(0)
    for i, (kind, lp) in enumerate(zip(cfg.layer_kinds, params["layers"])):
        if kind == CONV:
            x, z = once(_conv_op, cfg, None, lp, x, carry[i_conv])
            zs.append(z)
            i_conv += 1
        else:
            u = rmsnorm(x, lp["op_norm"], cfg.norm_eps)
            with jax.named_scope("attn.gqa"):
                o, kf, vf = _attention(cfg, lp, u, cos, sin, kf, vf, i_attn,
                                       walk, wflat, n_blocks, bs)
            x = x + o
            i_attn += 1
        if i < cfg.first_dense:
            x = once(_ffn_dense, cfg, None, lp, x)
        else:
            x, layer_load = once(
                _ffn_experts, cfg,
                (latent_moe.held_experts, latent_moe.IN_PLACE_ROWS), lp, x,
                valid)
            load = load + layer_load
            touched = touched + jnp.sum(layer_load > 0, dtype=jnp.int32)
            batched = batched + latent_moe.layers_batched(b * t, layer_load)
    if sel is not None:
        x = x[jnp.arange(b), sel][:, None]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(dt),
                        preferred_element_type=jnp.float32)
    if sel is not None:
        logits = logits[:, 0]
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    seen = jnp.sum(jnp.where(valid, qpos + 1, 0), dtype=jnp.int32)
    n_moe = cfg.n_layers - cfg.first_dense
    add = jnp.concatenate([
        jnp.stack([n_valid * (cfg.top_k * n_moe), jnp.int32(0), jnp.int32(0),
                   seen * n_attn, jnp.int32(0)]), load, batched[None]])
    stats = _add_stats(pcache.stats, add, touched if set_touched else None)
    return logits, _Ran(kf.reshape(pcache.k.shape),
                        vf.reshape(pcache.v.shape), jnp.stack(zs), stats)


def _commit(cfg: ShortConvMoEConfig, pcache: ShortConvPagedCache, ran: _Ran,
            pos, n, table, slots) -> ShortConvPagedCache:
    """Leave the cache as after ``n`` [B] tokens of each of the program's
    rows ``slots`` [B] (which started at ``pos`` [B] under ``table``): the
    pools as written, each slot's state the ``K - 1`` values of ``z`` that
    end at its ``n``-th token (its carry where ``n`` is 0), a snapshot in
    every block whose last position is among the ``n``, and the lengths.  A
    row whose slot is past the slots is not there, and leaves nothing."""
    n_conv, b, width, d = ran.zs.shape      # width = K - 1 + T
    bs = pcache.block_size
    n_blocks = pcache.snap.shape[1]
    keep = cfg.conv_kernel - 1
    t = width - keep

    rows = jnp.arange(b)
    # z of tokens j - (K - 1) + 1 .. j stands at zs[j + 1 .. j + K - 1]
    idx = n[:, None] + jnp.arange(keep)[None, :]                 # [B, K-1]
    conv = pcache.conv.at[:, slots].set(
        ran.zs[:, rows[:, None], idx].reshape(n_conv, b, keep * d),
        mode="drop")
    j, reached, dest = paged.block_ends(pos, n, t, table, bs, n_blocks)
    ends = j.shape[1]
    dest = dest.reshape(b * ends)
    idx = (jnp.minimum(j, t - 1)[..., None] + 1
           + jnp.arange(keep))                              # [B, E, K-1]
    vals = ran.zs[:, rows[:, None, None], idx].reshape(
        n_conv, b * ends, keep * d)
    snap = pcache.snap.at[:, dest].set(vals, mode="drop")
    add = jnp.zeros((pcache.stats.shape[1],), jnp.int32).at[SNAPSHOTS].set(
        jnp.sum(reached, dtype=jnp.int32))
    return pcache._replace(
        k=ran.k, v=ran.v, conv=conv, snap=snap,
        length=pcache.length.at[slots].set(pos + n, mode="drop"),
        stats=_add_stats(ran.stats, add, None))


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
                          pcache.block_table, pcache.conv, valid, True)


def decode_chunk_paged(
    params: dict, tokens: jax.Array, cfg: ShortConvMoEConfig,
    pcache: ShortConvPagedCache, *, advance: jax.Array | None = None,
) -> tuple[jax.Array, ShortConvPagedCache]:
    """T tokens per row against the cache (the tick).  ``advance`` [B] (0 or
    T) gates the rows as in :func:`llama.decode_chunk_paged`: a row held in
    place keeps its length and its convolution state."""
    b, t = tokens.shape
    adv = (jnp.full((b,), t, jnp.int32) if advance is None
           else jnp.asarray(advance, jnp.int32))
    logits, ran = _forward_all_slots(params, tokens, cfg, pcache, adv)
    return logits, _commit(cfg, pcache, ran, pcache.length, adv,
                           pcache.block_table, jnp.arange(b))


def decode_chunk_paged_rows(
    params: dict, tokens: jax.Array, cfg: ShortConvMoEConfig,
    pcache: ShortConvPagedCache, slots: jax.Array, *, new_length: jax.Array,
    sel: jax.Array | None,
) -> tuple[jax.Array, ShortConvPagedCache]:
    """A chunk of prefill for several rows in one program, one read of the
    weights for all of them: ``tokens`` [R, T] continue the slots ``slots``
    [R] (each at most once) from their lengths, which become ``new_length``
    [R]; positions past it are padding and count for nothing, the
    convolution's state included.  Returns the logits of each row's position
    ``sel`` [R] alone, [R, V] (of every position, [R, T, V], with ``sel``
    ``None``), and the cache.  A row whose slot is past the slots
    (``n_slots``) is not there: it writes no key, no state, no snapshot and
    no length."""
    slots = jnp.asarray(slots, jnp.int32)
    new_length = jnp.asarray(new_length, jnp.int32)
    there, at, pos, qpos, table = paged.chunk_rows(pcache, slots,
                                                   tokens.shape[1])
    valid = (qpos < new_length[:, None]) & there[:, None]
    logits, ran = _forward_paged(
        params, tokens, cfg, pcache, qpos, table, pcache.conv[:, at], valid,
        False, None if sel is None else jnp.asarray(sel, jnp.int32), there)
    return logits, _commit(cfg, pcache, ran, pos,
                           jnp.where(there, new_length - pos, 0), table,
                           slots)


def decode_chunk_paged_row(
    params: dict, tokens: jax.Array, cfg: ShortConvMoEConfig,
    pcache: ShortConvPagedCache, slot: jax.Array, *, new_length: jax.Array,
) -> tuple[jax.Array, ShortConvPagedCache]:
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
    the lengths alone do not roll a recurrent state back: the round keeps
    ``z`` of all ``K + 1`` positions and leaves each slot's state, and any
    snapshot of a block that filled, as after its ``1 + accepted`` tokens.
    What a rejected position wrote to ``k`` / ``v`` lies past the length."""
    b, k = drafts.shape
    active = jnp.asarray(active, jnp.int32)
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    chunk = jnp.concatenate([tok[:, None], drafts], axis=1)
    logits, ran = _forward_all_slots(params, chunk, cfg, pcache, active)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    match = (drafts == preds[:, :k]).astype(jnp.int32)
    accept = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    pcache = _commit(cfg, pcache, ran, pcache.length, active * (1 + accept),
                     pcache.block_table, jnp.arange(b))
    return tok, accept, logits[jnp.arange(b), accept], pcache


def forward(params: dict, tokens: jax.Array,
            cfg: ShortConvMoEConfig) -> jax.Array:
    """Logits [B, L, V] of whole sequences with no cache kept: every row
    through one chunk of a cache made for the call and thrown away."""
    b, l = tokens.shape
    pcache = init_paged_cache(cfg, b, l, block_size=l)
    pcache = pcache._replace(
        block_table=1 + jnp.arange(b, dtype=jnp.int32)[:, None])
    return decode_chunk_paged(params, tokens, cfg, pcache)[0]


def generate(params: dict, cfg: ShortConvMoEConfig, prompt: list,
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
