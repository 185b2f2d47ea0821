"""A served decoder whose layers are mostly state-space mixers: a selective
state-space recurrence (the "SSD" form: a scalar decay a head, one group of
input and output projections of the state shared by all heads) behind a short
causal convolution, with a grouped-query attention layer without rotary among
them; every layer's feed-forward part is softmax-routed experts beside a
shared expert.

This is the architecture of the Granite 4.0-H models (``granitemoehybrid``),
written for :class:`~horovod_tpu.serving_scheduler.ServeEngine`: the module
implements the engine's paged model interface (:mod:`horovod_tpu.models.paged`)
beside the four others, walks its attention layers' blocks with
:func:`~horovod_tpu.models.llama.paged_attend_tiles` and computes its expert
layers with :func:`~horovod_tpu.models.latent_moe.held_experts`.

**Layers** (``x`` [T, d]; pre-norm, no biases but the convolution's)::

    x = E[ids] * embed_scale
    x = x + residual_scale * Mixer_i(RMSNorm(x))
    h = RMSNorm(x);  x = x + residual_scale * (Routed(h) + Shared(h))
    logits = RMSNorm(x) E^T / logits_scale          (the head is tied)

* *state-space mixer*: ``[z | xBC | dt] = u W_in`` (widths ``inner``, ``inner
  + 2 N``, ``H``); ``xBC`` through a depthwise causal convolution of
  ``conv_kernel`` taps with bias and SiLU, its first taps the sequence's last
  ``conv_kernel - 1`` inputs; split into ``x`` [H, P], ``B`` [N], ``C`` [N];
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, and per head ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; ``y =
  RMSNorm_w(y * silu(z))`` over all of ``inner``; out ``y W_out``.  ``dt``,
  the decay and the state are float32.
* *attention*: ``n_heads`` queries and ``n_kv_heads`` keys and values of
  ``head_dim``, no rotary, causal softmax at ``attn_scale``.
* *experts*: the ``top_k`` largest router logits (float32) are chosen and
  weighted by a softmax over those ``top_k`` (:func:`latent_moe.route`'s
  second rule); each expert a SwiGLU whose published fused input matrix is
  held as its two halves (``e_gate``, ``e_up``: what ``held_experts`` reads);
  this chip computes the ``held_count`` experts from ``held_first`` on; the
  shared expert is the same form at ``shared_dim``, unweighted.

**The mixer in two forms.**  A program of one token a row (the tick) advances
each row's state by the recurrence itself, in place: each layer reads the
state where it stands for its output (``S_t C = exp(dt A) (S_{t-1} C) + dt x
(B C)``) and all the layers' states are advanced in one elementwise pass
after the last (:func:`advance_one_token`: one read and one write of
``[layers, rows, H, P, N]``, which the compiler cannot run twice).  A program
of more (a chunk of prefill, the verify round) computes its outputs in the
chunked form over pieces of
``ssm_chunk`` tokens (the decay's cumulative sums, ``C B^T`` masked by the
decay within a piece, the state carried between pieces) and reads and writes
the row's state once.  A token that does not count (a chunk's pad, an idle
row of a tick, a rejected draft) has ``dt = 0``, which leaves the state as it
was, and does not enter the convolution's carry.

**State.**  :class:`StatePagedCache` holds the attention layers' ``k`` / ``v``
pools ``[n_attn, n_blocks, bs, KVH, Dh]``, per position and immutable once
written, and per slot ``ssm`` ``[n_ssm, n_slots, H, P, N]`` (float32) and
``conv`` ``[n_ssm, n_slots, conv_kernel - 1, inner + 2 N]``: the sequence's
state *at its length*, which the lengths alone do not roll back.

**The snapshot budget.**  The state is far larger than a block of keys and
values (38 MB against 4 MB at the published widths), so the snapshot rule of
:mod:`horovod_tpu.models.paged` is kept under a budget: ``snap_ssm`` /
``snap_conv`` hold ``cfg.snapshots`` entries, far fewer than blocks.  Which
block holds which entry is the host's to say
(:class:`~horovod_tpu.models.paged.SnapshotBudget`, made by
:func:`snapshot_budget`): :func:`set_row` is told, beside the row, the entry of
each of its blocks (``snaps`` [blocks_per_slot], ``n_snaps`` for none), keeps
them in ``snap_dest`` [n_slots, blocks_per_slot], restores the slot's state
from the entry of the block that ends at ``length``, and a chunk whose counted
tokens reach a block's last position writes the state there into that block's
entry, if it was given one (past the pool otherwise: the scatter drops it).
The host grants entries only inside prompts, so a tick writes none.

**Counters.**  ``stats`` rides in the cache as in ``latent_moe``.
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
from horovod_tpu.models.llama import rmsnorm
from horovod_tpu.models.window_moe import _walk_split

SSM, ATTN = "ssm", "attention"
HI = lax.Precision.HIGHEST
#: stats columns: four running sums, the touched gauge at ``TOUCHED``, the
#: held experts' load from ``LOAD0``, then two more sums
CHOICES_TOTAL, CHOICES_HELD, RESTORES, KEYS_VISIBLE = 0, 1, 2, 3
_SUMS = ("choices_total", "choices_held", "state_restores", "keys_visible")
_TAIL = ("snapshots_written", "layers_batched")


@dataclasses.dataclass(frozen=True)
class StateSpaceMoEConfig:
    vocab_size: int = 50176            # rows of the tied embedding held here
    dim: int = 4096
    layer_kinds: tuple = (SSM,) * 5 + (ATTN,) + (SSM,) * 4
    # the state-space mixer (one group: every head shares B and C)
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    conv_kernel: int = 4
    ssm_chunk: int = 256
    # attention
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    attn_scale: float = 1.0 / 128
    # experts
    n_experts: int = 72                # the router's width
    expert_dim: int = 768
    top_k: int = 10
    shared_dim: int = 1536
    held_first: int = 0                # the experts this chip holds
    held_count: int = 36
    # the family's multipliers
    embed_scale: float = 12.0
    residual_scale: float = 0.22
    logits_scale: float = 16.0
    norm_eps: float = 1e-5
    #: entries of the snapshot budget: how many states the cache keeps for
    #: prefix hits and replays, beside the one a slot
    snapshots: int = 24
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    #: what :func:`latent_moe.held_experts` and its counters read of a config
    route_softmax_top_k = True
    routed_scale = 1.0
    route_norm_eps = 0.0
    first_dense = 0

    def __post_init__(self):
        if set(self.layer_kinds) - {SSM, ATTN}:
            raise ValueError(f"layer_kinds {self.layer_kinds} may hold only "
                             f"{SSM!r} and {ATTN!r}")
        if ATTN not in self.layer_kinds or SSM not in self.layer_kinds:
            raise ValueError("the block table pages the attention layers and "
                             "the slots carry the state-space layers' state: "
                             "layer_kinds has to hold one of each")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError(
                f"held experts {self.held_first}..+{self.held_count} are not "
                f"within the router's {self.n_experts}")
        if self.n_heads % self.n_kv_heads or self.conv_kernel < 2:
            raise ValueError("n_heads has to be a multiple of n_kv_heads and "
                             "conv_kernel at least 2")
        if self.snapshots < 1:
            raise ValueError("the snapshot budget holds at least one entry")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    def n_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_kinds if k == kind)

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """What the convolution runs over: ``x``, ``B`` and ``C``."""
        return self.inner + 2 * self.ssm_state


def state_space_moe_tiny(**overrides) -> StateSpaceMoEConfig:
    """The CPU tests' preset: the published order around the attention layer
    (state-space layers on both sides), pieces shorter than the test lengths,
    8 experts of which 4 are held, top-3."""
    base = dict(
        vocab_size=64, dim=32, layer_kinds=(SSM, SSM, ATTN, SSM),
        ssm_heads=4, ssm_head_dim=8, ssm_state=8, conv_kernel=4, ssm_chunk=4,
        n_heads=4, n_kv_heads=2, head_dim=8, attn_scale=1.0 / 8,
        n_experts=8, expert_dim=16, top_k=3, shared_dim=24, held_count=4,
        snapshots=3, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    base.update(overrides)
    return StateSpaceMoEConfig(**base)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(cfg: StateSpaceMoEConfig, key: jax.Array) -> dict:
    """Random parameters: matrices ``[in, out]`` normal at ``1/sqrt(in)``,
    norm weights 1, ``A_log = log(1..H)``, ``dt_bias`` so that ``softplus``
    spans 1e-3 to 1e-1, ``D`` ones, the tied embedding normal at ``1 /
    (embed_scale sqrt(d))`` (a token's own row must not outweigh what the
    layers computed)."""
    dt = cfg.param_dtype

    def mat(k, n_in, *out):
        return (jax.random.normal(k, (n_in, *out), jnp.float32)
                * n_in ** -0.5).astype(dt)

    d, hd, h = cfg.dim, cfg.head_dim, cfg.ssm_heads
    layers = []
    for i, kind in enumerate(cfg.layer_kinds):
        ks = iter(jax.random.split(jax.random.fold_in(key, i), 16))
        lp = {"mixer_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt)}
        if kind == SSM:
            step = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), h))
            lp.update(
                w_in=mat(next(ks), d, cfg.inner + cfg.conv_dim + h),
                conv_w=mat(next(ks), cfg.conv_kernel, cfg.conv_dim).T,
                conv_b=jnp.zeros((cfg.conv_dim,), dt),
                dt_bias=jnp.log(jnp.expm1(step)).astype(jnp.float32),
                A_log=jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
                D=jnp.ones((h,), jnp.float32),
                gate_norm=jnp.ones((cfg.inner,), dt),
                w_out=mat(next(ks), cfg.inner, d))
        else:
            lp.update(wq=mat(next(ks), d, cfg.n_heads * hd),
                      wk=mat(next(ks), d, cfg.n_kv_heads * hd),
                      wv=mat(next(ks), d, cfg.n_kv_heads * hd),
                      wo=mat(next(ks), cfg.n_heads * hd, d))
        e, f, sf = cfg.held_count, cfg.expert_dim, cfg.shared_dim
        lp.update(w_router=mat(next(ks), d, cfg.n_experts),
                  e_gate=mat(next(ks), d, e, f).transpose(1, 0, 2),
                  e_up=mat(next(ks), d, e, f).transpose(1, 0, 2),
                  e_down=mat(next(ks), f, e, d).transpose(1, 0, 2),
                  s_gate=mat(next(ks), d, sf), s_up=mat(next(ks), d, sf),
                  s_down=mat(next(ks), sf, d))
        layers.append(lp)
    return {"embed": (jax.random.normal(
                jax.random.fold_in(key, 10_000), (cfg.vocab_size, d),
                jnp.float32) * d ** -0.5 / cfg.embed_scale).astype(dt),
            "layers": tuple(layers), "final_norm": jnp.ones((d,), dt)}


def param_partition_specs(cfg: StateSpaceMoEConfig, *, tp_axis: str = "tp"):
    raise NotImplementedError(
        "tensor-parallel serving of a StateSpaceMoEConfig is not written: "
        "its states and snapshots would split by state-space head as the "
        "pools do by key head, but no spec for them exists yet; serve it at "
        "tp_size=1")


def paged_cache_partition_specs(*, tp_axis: str = "tp"):
    return param_partition_specs(None, tp_axis=tp_axis)


def tp_split_dims(cfg: StateSpaceMoEConfig) -> tuple:
    """Asked only at ``tp_size > 1``, which this model does not serve."""
    return param_partition_specs(cfg)


# ---------------------------------------------------------------------------
# the paged state
# ---------------------------------------------------------------------------

class StatePagedCache(NamedTuple):
    """The attention layers' pools behind the block table (block 0 is trash)
    and the state-space layers' state a slot: ``k`` / ``v`` ``[n_attn,
    n_blocks, bs, KVH, Dh]``; ``ssm`` ``[n_ssm, n_slots, H, P, N]`` float32
    and ``conv`` ``[n_ssm, n_slots, conv_kernel - 1, inner + 2 N]``, each
    slot's state at its length; ``snap_ssm`` / ``snap_conv`` the same with
    ``n_snaps`` entries in the slots' place; ``snap_dest`` [n_slots,
    blocks_per_slot] int32, the entry of each of a slot's blocks (``n_snaps``:
    none); ``block_table``, ``length`` and ``stats`` [2, 5 + held_count + 2]
    int32, the device-side counters."""

    k: jax.Array
    v: jax.Array
    ssm: jax.Array
    conv: jax.Array
    snap_ssm: jax.Array
    snap_conv: jax.Array
    snap_dest: jax.Array
    block_table: jax.Array
    length: jax.Array
    stats: jax.Array

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def logical_len(self) -> int:
        return self.block_table.shape[1] * self.k.shape[2]

    @property
    def n_snaps(self) -> int:
        return self.snap_ssm.shape[1]


def init_paged_cache(
    cfg: StateSpaceMoEConfig, n_slots: int, max_len: int, *,
    block_size: int, n_blocks: int | None = None,
) -> StatePagedCache:
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
    kv = (cfg.n_of(ATTN), n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
    state = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    taps = (cfg.conv_kernel - 1, cfg.conv_dim)
    n_ssm, n_snaps = cfg.n_of(SSM), cfg.snapshots
    return StatePagedCache(
        k=jnp.zeros(kv, cfg.dtype), v=jnp.zeros(kv, cfg.dtype),
        ssm=jnp.zeros((n_ssm, n_slots) + state, jnp.float32),
        conv=jnp.zeros((n_ssm, n_slots) + taps, cfg.dtype),
        snap_ssm=jnp.zeros((n_ssm, n_snaps) + state, jnp.float32),
        snap_conv=jnp.zeros((n_ssm, n_snaps) + taps, cfg.dtype),
        snap_dest=jnp.full((n_slots, per), n_snaps, jnp.int32),
        block_table=jnp.zeros((n_slots, per), jnp.int32),
        length=jnp.zeros((n_slots,), jnp.int32),
        stats=jnp.zeros((2, LOAD0 + cfg.held_count + len(_TAIL)), jnp.int32))


def paged_pool_bytes(pcache: StatePagedCache) -> dict:
    """Device bytes one block holds in each pool: its keys and values of the
    attention layers.  (A snapshot is not a block's: :func:`state_bytes`.)"""
    return {name: int(np.prod(a.shape) // a.shape[1]) * a.dtype.itemsize
            for name, a in (("k", pcache.k), ("v", pcache.v))}


def state_bytes(pcache: StatePagedCache) -> int:
    """Device bytes of one sequence's state, all state-space layers: what a
    slot carries and what one entry of the budget holds."""
    return sum(int(np.prod(a.shape) // a.shape[1]) * a.dtype.itemsize
               for a in (pcache.ssm, pcache.conv))


def snapshot_budget(cfg: StateSpaceMoEConfig, pcache: StatePagedCache,
                    metrics) -> paged.SnapshotBudget:
    """The host's side of the budget, for the engine: which block holds
    which of the cache's entries, counted under this model's names."""
    return paged.SnapshotBudget(
        pcache.n_snaps, evicted=metrics.counter("ssm.snapshots_evicted"),
        live=metrics.gauge("ssm.snapshots_live"))


def paged_counters(pcache: StatePagedCache) -> jax.Array:
    """The device array the engine reads back beside the tick's tokens."""
    return pcache.stats


def read_counters(stats_host: np.ndarray) -> dict:
    """The counters as Python ints (sums exact past 2**31)."""
    return paged.read_stats(stats_host, _SUMS, _TAIL)


def publish_paged_metrics(metrics, cfg: StateSpaceMoEConfig,
                          pcache: StatePagedCache,
                          stats_host: np.ndarray | None = None,
                          row_blocks: tuple = (),
                          programs: tuple = ()) -> None:
    """The model's own gauges and counters in the engine's registry:
    :func:`paged.publish_state_metrics`'s (a snapshot is one entry of the
    budget) and ``ssm.state_bytes_moved``: the state each dispatched program
    read and wrote for the rows that advanced (a tick's decoding rows, a
    chunk's one), reckoned here from ``programs`` with no read-back."""
    slot = state_bytes(pcache)
    c = paged.publish_state_metrics(
        metrics, cfg, pcache, stats_host, programs,
        per_block=dict(paged_pool_bytes(pcache), snap=slot), slot_bytes=slot,
        counted=_counted(metrics), read=read_counters,
        walk_split=_walk_split(pcache.block_size))
    metrics.counter("ssm.state_bytes_moved").inc(
        2 * slot * sum(int(np.sum(np.asarray(p.active) > 0))
                       for p in programs))
    if c is not None:
        metrics.gauge("moe.load_max").set(max(c["held_load"]))


def _counted(metrics) -> tuple:
    """The registry's counter of each of the device's running sums, beside
    it the gauge ``<name>.device`` (:func:`paged.count_from_device`) and the
    sum's name in :func:`read_counters` (written out for the names lint)."""
    return (
        (metrics.counter("moe.choices_total"),
         metrics.gauge("moe.choices_total.device"), "choices_total"),
        (metrics.counter("moe.choices_held"),
         metrics.gauge("moe.choices_held.device"), "choices_held"),
        (metrics.counter("moe.layers_batched"),
         metrics.gauge("moe.layers_batched.device"), "layers_batched"),
        (metrics.counter("ssm.state_restores"),
         metrics.gauge("ssm.state_restores.device"), "state_restores"),
        (metrics.counter("ssm.snapshots_written"),
         metrics.gauge("ssm.snapshots_written.device"), "snapshots_written"),
        (metrics.counter("attn.keys_visible"),
         metrics.gauge("attn.keys_visible.device"), "keys_visible"))


def set_row(pcache: StatePagedCache, slot, row, length,
            snaps) -> StatePagedCache:
    """Map slot ``slot`` to the blocks ``row`` at ``length`` (a whole number
    of blocks), with ``snaps`` [blocks_per_slot] the budget's entry of each
    of them (``n_snaps``: none): the table and the length as every model's,
    the entries a chunk will write at those blocks' ends, and the slot's
    state as the sequence has it at ``length`` — the entry of the block that
    ends there (which the host has made sure holds one), zeros at 0.  The
    interface's optional function; ``ServeEngine._set_row`` is its only
    caller."""
    length = jnp.asarray(length, jnp.int32)
    src = jnp.minimum(paged.block_before(snaps, length, pcache.block_size),
                      pcache.n_snaps - 1)
    hit = length > 0
    add = jnp.zeros((pcache.stats.shape[1],), jnp.int32).at[RESTORES].set(
        hit.astype(jnp.int32))
    return pcache._replace(
        block_table=pcache.block_table.at[slot].set(row),
        length=pcache.length.at[slot].set(length),
        snap_dest=pcache.snap_dest.at[slot].set(snaps),
        ssm=pcache.ssm.at[:, slot].set(
            jnp.where(hit, pcache.snap_ssm[:, src], 0)),
        conv=pcache.conv.at[:, slot].set(
            jnp.where(hit, pcache.snap_conv[:, src], 0)),
        stats=_add_stats(pcache.stats, add, None))


# ---------------------------------------------------------------------------
# the state-space mixer
# ---------------------------------------------------------------------------

class Kept(NamedTuple):
    """What one mixer layer's pass over a program's tokens keeps so that the
    state can be advanced afterwards by any number of them (small: no
    state): the convolution's inputs ``xbc`` [B, K-1+T, C] with the carry in
    front, ``dt`` [B, T, H] (float32, unmasked), ``x`` [B, T, H, P] and ``b``
    [B, T, N]."""

    xbc: jax.Array
    dt: jax.Array
    x: jax.Array
    b: jax.Array


def _carry(kept: Kept, n):
    """The convolution's carry after the first ``n`` [B] tokens."""
    taps = kept.xbc.shape[1] - kept.dt.shape[1]
    return jax.vmap(lambda rows, i: lax.dynamic_slice_in_dim(rows, i, taps))(
        kept.xbc, n)


def advance_state(lp: dict, state, kept: Kept, n):
    """The recurrent state ``[B, H, P, N]`` (float32) and the convolution's
    carry ``[B, K-1, C]`` after the first ``n`` [B] of the tokens ``kept``
    describes, from ``state`` before them: the recurrence's closed form over
    all of them at once (``dt`` zeroed past ``n``: the state stays).  What a
    snapshot at a block's end and the verify round take; a tick advances by
    :func:`advance_one_token`."""
    t = kept.dt.shape[1]
    counts = jnp.arange(t)[None, :] < n[:, None]                 # [B, T]
    dt = jnp.where(counts[..., None], kept.dt, 0.0)
    cs = jnp.cumsum(dt * -jnp.exp(lp["A_log"]), axis=1)          # [B, T, H]
    w = jnp.exp(cs[:, -1:] - cs) * dt
    state = (jnp.exp(cs[:, -1])[..., None, None] * state
             + jnp.einsum("bthp,btn->bhpn",
                          w[..., None] * kept.x.astype(jnp.float32),
                          kept.b.astype(jnp.float32), precision=HI))
    return state, _carry(kept, n)


def advance_one_token(layers: list, ssm, kept_all: list, n):
    """Every state-space layer's recurrent state ``ssm`` [L, B, H, P, N] one
    token on (rows with ``n`` 0 stay bit for bit: ``dt = 0``), from what each
    layer's pass kept: the recurrence itself, over all the layers in one
    elementwise pass, so that a donated ``ssm`` is read and written where it
    stands exactly once.  (Layer by layer, each write read by the next
    layer's, XLA:TPU rematerialised the first in-place update at 64 slots of
    the published widths and ran it twice a tick: PERF.md, PR 38;
    ``tests/test_chip_compile.py`` holds the compiled tick to one.)"""
    dt = jnp.where((n > 0)[None, :, None],
                   jnp.stack([k.dt[:, 0] for k in kept_all]), 0.0)  # [L,B,H]
    a = dt * -jnp.exp(jnp.stack([lp["A_log"] for lp in layers]))[:, None]
    x = jnp.stack([k.x[:, 0] for k in kept_all]).astype(jnp.float32)
    b = jnp.stack([k.b[:, 0] for k in kept_all]).astype(jnp.float32)
    return (jnp.exp(a)[..., None, None] * ssm
            + (dt[..., None] * x)[..., None] * b[:, :, None, None, :])


def _ssd(cfg: StateSpaceMoEConfig, lp: dict, state, dt, x, b, c):
    """The chunked form: outputs ``y`` [B, T, H, P] (float32, without the
    ``D`` term) of the recurrence from ``state`` [B, H, P, N] over ``T``
    tokens (``dt`` [B, T, H] already zero where a token does not count), in
    pieces of ``cfg.ssm_chunk``, and the state after them."""
    bsz, t, h, p = x.shape
    q = min(cfg.ssm_chunk, t)
    nc = -(-t // q)
    pad = nc * q - t

    def pieces(z):
        z = jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
        return z.reshape((bsz, nc, q) + z.shape[2:])

    dt, x = pieces(dt), pieces(x.astype(jnp.float32))
    b, c = pieces(b.astype(jnp.float32)), pieces(c.astype(jnp.float32))
    cs = jnp.cumsum(dt * -jnp.exp(lp["A_log"]), axis=2)          # [B,nc,Q,H]
    # within a piece: token s reaches token q decayed by exp(cs_q - cs_s)
    cb = jnp.einsum("bcqn,bcsn->bcqs", c, b)
    later = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(later, cs[:, :, :, None] - cs[:, :, None],
                              -jnp.inf))
    y = jnp.einsum("bcqsh,bcshp->bcqhp",
                   cb[..., None] * decay * dt[:, :, None], x)
    # each piece's own part of the state at its end, then the carry
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt                    # [B,nc,Q,H]
    own = jnp.einsum("bcshp,bcsn->bchpn", to_end[..., None] * x, b,
                     precision=HI)
    whole = jnp.exp(cs[:, :, -1])                                # [B,nc,H]

    def carry(s, piece):
        own_c, whole_c = piece
        return whole_c[..., None, None] * s + own_c, s

    state, before = lax.scan(carry, state, (own.swapaxes(0, 1),
                                            whole.swapaxes(0, 1)))
    y = y + jnp.einsum("bcqn,bchpn->bcqhp", c, before.swapaxes(0, 1),
                       precision=HI) * jnp.exp(cs)[..., None]
    return y.reshape(bsz, nc * q, h, p)[:, :t], state


def _mixer(cfg: StateSpaceMoEConfig, lp: dict, u, state, conv, valid):
    """One state-space mixer over ``u`` [B, T, d] from the rows' ``state``
    [B, H, P, N] and convolution carry ``conv`` [B, K-1, C]; ``valid``
    [B, T] marks the tokens that count towards the outputs that follow them.
    Returns the layer's output [B, T, d], what :func:`advance_state` needs,
    and, of a program of more than one token a row, the state after its
    valid tokens (``None`` of a one-token program, whose caller advances
    every layer's state in one pass: :func:`advance_one_token`)."""
    dtp = cfg.dtype
    bsz, t, _ = u.shape
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    with jax.named_scope("ssm.in_proj"):
        zxd = _dot(u, lp["w_in"], dtp)
        z = zxd[..., :cfg.inner]
        xbc_in = zxd[..., cfg.inner:cfg.inner + cfg.conv_dim]
        dt = jax.nn.softplus(zxd[..., cfg.inner + cfg.conv_dim:].astype(
            jnp.float32) + lp["dt_bias"])
    with jax.named_scope("ssm.conv"):
        rows = jnp.concatenate([conv, xbc_in], axis=1)           # [B,K-1+T,C]
        w = lp["conv_w"].astype(jnp.float32)
        xbc = lp["conv_b"].astype(jnp.float32) + sum(
            rows[:, j:j + t].astype(jnp.float32) * w[:, j]
            for j in range(cfg.conv_kernel))
        xbc = jax.nn.silu(xbc).astype(dtp)
    x = xbc[..., :cfg.inner].reshape(bsz, t, h, p)
    b = xbc[..., cfg.inner:cfg.inner + n]
    c = xbc[..., cfg.inner + n:]
    kept = Kept(rows, dt, x, b)
    if t == 1:
        with jax.named_scope("ssm.update"):
            # ``y = S_t C`` from the state as it stood: ``exp(dt A) (S_{t-1}
            # C) + dt x (B C)``.  The new state is not made here: the caller
            # advances every layer's at once (:func:`advance_one_token`)
            c1 = c[:, 0].astype(jnp.float32)
            dt1 = jnp.where(valid[:, :1, None], dt, 0.0)[:, 0]      # [B, H]
            carried = jnp.einsum("bhpn,bn->bhp", state, c1, precision=HI)
            bc = jnp.sum(b[:, 0].astype(jnp.float32) * c1, axis=-1)  # [B]
            y = (jnp.exp(dt1 * -jnp.exp(lp["A_log"]))[..., None] * carried
                 + (dt1 * bc[:, None])[..., None]
                 * x[:, 0].astype(jnp.float32))[:, None]
        state_out = None
    else:
        with jax.named_scope("ssm.scan"):
            y, state_out = _ssd(cfg, lp, state,
                                jnp.where(valid[..., None], dt, 0.0), x, b, c)
    with jax.named_scope("ssm.gate_norm"):
        y = y + lp["D"][:, None] * x.astype(jnp.float32)
        y = y.reshape(bsz, t, cfg.inner) * jax.nn.silu(z.astype(jnp.float32))
        y = rmsnorm(y, lp["gate_norm"], cfg.norm_eps).astype(dtp)
    with jax.named_scope("ssm.out_proj"):
        return _dot(y, lp["w_out"], dtp), kept, state_out


# ---------------------------------------------------------------------------
# the programs' shared body
# ---------------------------------------------------------------------------

def _forward_paged(params, tokens, cfg: StateSpaceMoEConfig,
                   pcache: StatePagedCache, qpos, table, slot, valid, n,
                   set_touched: bool, snap_ends=None):
    """The shared body of the paged programs: ``tokens`` [B, T] at positions
    ``qpos`` under block tables ``table`` [B, per], of the one row in slot
    ``slot`` (``None``: of every slot in order); ``valid`` [B, T] marks the
    tokens that count (for the mixers' outputs, the counters and the
    routing; a row with none is one whose output nobody reads, and the
    attention layers walk it one tile).  Writes the attention layers' keys
    and values.  With ``n`` [B] it leaves each row's state as after its first
    ``n`` tokens; with ``None`` it leaves the states alone and returns, a
    state-space layer, what advances them (the verify round).  ``snap_ends``
    ``(j, dest)``, each [B, ends]: a snapshot of the state after token ``j``
    into entry ``dest`` (past the pool: dropped).  Returns the logits, the
    cache as the program leaves it but for the lengths, and those
    :class:`Kept`."""
    dt = cfg.dtype
    b, t = tokens.shape
    n_attn, n_blocks, bs, kvh, hd = pcache.k.shape
    per = table.shape[1]
    wblk = jnp.take_along_axis(table, jnp.clip(qpos // bs, 0, per - 1),
                               axis=1)
    wflat = wblk * bs + qpos % bs                                # [B, T]
    kf = pcache.k.reshape(n_attn * n_blocks * bs, kvh, hd)
    vf = pcache.v.reshape(n_attn * n_blocks * bs, kvh, hd)
    # the walk sees each block as `split` pieces of `piece` positions
    # (window_moe.GATHER_ROWS says why)
    split = _walk_split(bs)
    piece = bs // split
    walk = llama.tile_walk(
        (table[:, :, None] * split + jnp.arange(split)).reshape(b, -1), qpos,
        piece, jnp.any(valid, axis=1))
    # a tick reads each layer's state where it stands and advances all the
    # layers' in one pass after the last (advance_one_token says why); one
    # row's is read as one slice of every layer before anything is written
    # and written as one after the last (a scatter by an index array copied
    # all the slots' states first, and so did a slice a layer between the
    # layers' writes)
    whole = ssm, conv = pcache.ssm, pcache.conv
    if slot is not None:
        ssm, conv = (lax.dynamic_slice(
            a, (0, slot) + (0,) * (a.ndim - 2), (a.shape[0], 1) + a.shape[2:])
            for a in whole)                                     # [n_ssm, 1, ..]
    snap_ssm, snap_conv = pcache.snap_ssm, pcache.snap_conv
    x = (params["embed"][tokens] * cfg.embed_scale).astype(dt)
    i_attn = i_ssm = 0
    kept_all = []
    load = jnp.zeros((cfg.held_count,), jnp.int32)
    touched = batched = jnp.int32(0)
    for kind, lp in zip(cfg.layer_kinds, params["layers"]):
        u = rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
        if kind == SSM:
            state0, conv0 = ssm[i_ssm], conv[i_ssm]
            m, kept, state_n = _mixer(cfg, lp, u, state0, conv0, valid)
            if state_n is None or n is None:
                kept_all.append(kept)
            else:
                ssm = ssm.at[i_ssm].set(state_n)
                conv = conv.at[i_ssm].set(_carry(kept, n))
            if snap_ends is not None:
                for e in range(snap_ends[0].shape[1]):
                    s_e, c_e = advance_state(lp, state0, kept,
                                             snap_ends[0][:, e] + 1)
                    dest = snap_ends[1][:, e]
                    snap_ssm = snap_ssm.at[i_ssm, dest].set(s_e, mode="drop")
                    snap_conv = snap_conv.at[i_ssm, dest].set(c_e,
                                                              mode="drop")
            i_ssm += 1
        else:
            q = _dot(u, lp["wq"], dt).reshape(b, t, cfg.n_heads, hd)
            k = _dot(u, lp["wk"], dt).reshape(b, t, kvh, hd)
            v = _dot(u, lp["wv"], dt).reshape(b, t, kvh, hd)
            with jax.named_scope("attn.full"):
                o, kf, vf = llama.paged_attend_tiles(
                    q, k, v, kf, vf, i_attn, walk, wflat, n_blocks * split,
                    piece, scale=cfg.attn_scale)
            m = _dot(o.astype(dt).reshape(b, t, cfg.n_heads * hd), lp["wo"],
                     dt)
            i_attn += 1
        x = x + (cfg.residual_scale * m).astype(dt)
        h2 = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps).reshape(b * t, cfg.dim)
        y, layer_load = latent_moe.held_experts(cfg, lp, h2,
                                                valid.reshape(b * t))
        with jax.named_scope("moe.shared"):
            y = y + _swiglu(h2, lp["s_gate"], lp["s_up"], lp["s_down"], dt)
        x = x + (cfg.residual_scale * y).astype(dt).reshape(b, t, cfg.dim)
        load = load + layer_load
        touched = touched + jnp.sum(layer_load > 0, dtype=jnp.int32)
        batched = batched + latent_moe.layers_batched(b * t, layer_load)
    if t == 1 and n is not None:        # one token a row: all layers at once
        with jax.named_scope("ssm.update"):
            ssm = advance_one_token(
                [lp for kind, lp in zip(cfg.layer_kinds, params["layers"])
                 if kind == SSM], ssm, kept_all, n)
            conv = jnp.stack([_carry(kept, n) for kept in kept_all])
    if slot is not None:
        ssm, conv = (lax.dynamic_update_slice(
            a, rows, (0, slot) + (0,) * (a.ndim - 2))
            for a, rows in zip(whole, (ssm, conv)))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(dt),
                        preferred_element_type=jnp.float32) / cfg.logits_scale
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    seen = jnp.sum(jnp.where(valid, n_attn * (qpos + 1), 0), dtype=jnp.int32)
    written = jnp.int32(0) if snap_ends is None else jnp.sum(
        snap_ends[1] < pcache.n_snaps, dtype=jnp.int32)
    add = jnp.concatenate([
        jnp.stack([n_valid * (cfg.top_k * cfg.n_layers), jnp.sum(load),
                   jnp.int32(0), seen, jnp.int32(0)]), load,
        jnp.stack([written, batched])])
    stats = _add_stats(pcache.stats, add, touched if set_touched else None)
    return logits, pcache._replace(
        k=kf.reshape(pcache.k.shape), v=vf.reshape(pcache.v.shape), ssm=ssm,
        conv=conv, snap_ssm=snap_ssm, snap_conv=snap_conv,
        stats=stats), tuple(kept_all)


# ---------------------------------------------------------------------------
# the engine's interface (the signatures of models/llama.py)
# ---------------------------------------------------------------------------

def decode_chunk_paged(
    params: dict, tokens: jax.Array, cfg: StateSpaceMoEConfig,
    pcache: StatePagedCache, *, advance: jax.Array | None = None,
) -> tuple[jax.Array, StatePagedCache]:
    """T tokens per row against the cache (the tick).  ``advance`` [B] (0 or
    T) gates the rows as in :func:`llama.decode_chunk_paged`: a row held in
    place keeps its length and its state."""
    b, t = tokens.shape
    adv = (jnp.full((b,), t, jnp.int32) if advance is None
           else jnp.asarray(advance, jnp.int32))
    pos = pcache.length
    qpos = pos[:, None] + jnp.arange(t)[None, :]
    logits, pcache, _ = _forward_paged(
        params, tokens, cfg, pcache, qpos, pcache.block_table, None,
        jnp.broadcast_to((adv > 0)[:, None], tokens.shape), adv, True)
    return logits, pcache._replace(length=pos + adv)


def decode_chunk_paged_row(
    params: dict, tokens: jax.Array, cfg: StateSpaceMoEConfig,
    pcache: StatePagedCache, slot: jax.Array, *, new_length: jax.Array,
) -> tuple[jax.Array, StatePagedCache]:
    """One row's T-token chunk (chunked prefill): ``tokens`` [1, T] continue
    slot ``slot`` from its length, which becomes ``new_length``; positions
    past it are padding and count for nothing, the state included.  A block
    end among the counted tokens writes its snapshot, where the host gave
    that block an entry."""
    b, t = tokens.shape
    if b != 1:
        raise ValueError(f"decode_chunk_paged_row is a B=1 program, "
                         f"got batch {b}")
    slot = jnp.asarray(slot, jnp.int32)
    new_length = jnp.asarray(new_length, jnp.int32)
    pos = pcache.length[slot][None]
    n = new_length[None] - pos
    qpos = pos[:, None] + jnp.arange(t)[None, :]
    table = pcache.block_table[slot][None]
    j, _, dest = paged.block_ends(pos, n, t, pcache.snap_dest[slot][None],
                                  pcache.block_size, pcache.n_snaps)
    logits, pcache, _ = _forward_paged(
        params, tokens, cfg, pcache, qpos, table, slot, qpos < new_length, n,
        False, snap_ends=(j, dest))
    return logits, pcache._replace(
        length=pcache.length.at[slot].set(new_length))


def spec_verify_paged(params, cfg, pcache, last_logits, drafts, active):
    """:func:`llama.spec_verify_paged`'s round over this model: the same
    ``[tok, d_1..d_K]`` wide tick and greedy longest-prefix acceptance.  The
    lengths alone do not roll a recurrent state back, and a state a drafted
    position cannot be kept (each is the whole of a slot's): the one pass
    keeps each state-space layer's ``dt``, ``B`` and ``x`` of the ``K + 1``
    positions, and once the acceptance is known each slot's state is advanced
    from where it stood by its ``1 + accepted`` tokens.  What a rejected
    position wrote to ``k`` / ``v`` lies past the length."""
    b, k = drafts.shape
    active = jnp.asarray(active, jnp.int32)
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    chunk = jnp.concatenate([tok[:, None], drafts], axis=1)
    pos = pcache.length
    qpos = pos[:, None] + jnp.arange(k + 1)[None, :]
    logits, pcache, kept_all = _forward_paged(
        params, chunk, cfg, pcache, qpos, pcache.block_table, None,
        jnp.broadcast_to((active > 0)[:, None], chunk.shape), None, True)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    match = (drafts == preds[:, :k]).astype(jnp.int32)
    accept = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    n = active * (1 + accept)
    ssm, conv = pcache.ssm, pcache.conv
    ssm_layers = [lp for kind, lp in zip(cfg.layer_kinds, params["layers"])
                  if kind == SSM]
    with jax.named_scope("ssm.update"):
        for i, (lp, kept) in enumerate(zip(ssm_layers, kept_all)):
            state, carry = advance_state(lp, ssm[i], kept, n)
            ssm = ssm.at[i].set(state)
            conv = conv.at[i].set(carry)
    return tok, accept, logits[jnp.arange(b), accept], pcache._replace(
        ssm=ssm, conv=conv, length=pos + n)


def forward(params: dict, tokens: jax.Array,
            cfg: StateSpaceMoEConfig) -> jax.Array:
    """Logits [B, L, V] of whole sequences with no cache kept: every row
    through one chunk of a cache made for the call and thrown away."""
    b, l = tokens.shape
    pcache = init_paged_cache(cfg, b, l, block_size=l)
    pcache = pcache._replace(
        block_table=1 + jnp.arange(b, dtype=jnp.int32)[:, None])
    return decode_chunk_paged(params, tokens, cfg, pcache)[0]


def generate(params: dict, cfg: StateSpaceMoEConfig, prompt: list,
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
