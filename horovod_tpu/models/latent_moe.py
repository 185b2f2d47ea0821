"""A served decoder with three kinds of layer in one model: multi-head latent
attention over the keys a learned sparse indexer selects, windowed latent
attention, and a dropless expert layer that is told which experts it holds.

This is the architecture of the large open latent-attention expert models
(``dots3_note``, DeepSeek-V3.2 and their kin), written for
:class:`~horovod_tpu.serving_scheduler.ServeEngine`: the module implements the
engine's paged model interface (:mod:`horovod_tpu.models.paged`) beside
:mod:`horovod_tpu.models.llama`, and shares the engine, the block pool, the
radix prefix cache, the replica pump and the router with it unchanged.

**Layers.**  ``layer_kinds[i]`` is ``"full"`` or ``"window"``; the first
``first_dense`` layers have a SwiGLU, the others the expert layer.  The layers
differ in shape, so they are a Python loop over a tuple of per-layer parameter
dicts, not one ``lax.scan`` over stacked weights.  With ``h`` the RMS-normed
layer input:

* *full*: ``c_q = rescale * RMSNorm(h W_qa)``; ``q = c_q W_qb`` in ``n_heads``
  heads of ``nope_dim`` plus ``rope_dim`` rotary dimensions;
  ``[c_kv | k_r] = h W_kva``, ``c_kv = rescale * RMSNorm(c_kv)``, ``k_r``
  rotated, one for all heads; keys and values of head ``i`` would be
  ``c_kv W_kvb[i]``.  The **indexer** scores every cached key,
  ``I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])`` with ``q_I = c_q W_Iq``
  (``index_heads`` heads), ``k_I = LayerNorm(h W_Ik)`` and
  ``w = h W_Iw / sqrt(index_heads * index_dim)``, and attention runs over the
  **exact** ``index_topk`` largest ``s <= t``, never an approximation of
  them.  The set is one; it is held in two forms.  A program that has few
  queries a row (the tick, the verify round) sorts (``lax.top_k``) and
  gathers the selected latents as a *list*.  A program whose queries share a
  row's keys many times over (the chunk of prefill: ``T * index_topk``
  listed rows against the ``m`` its table holds) finds each query's k-th
  largest score by counting, keeps the selection as a *mask*, and scores
  every visible key a tile at a time along the block table with a running
  softmax, while the visible context is short enough for that to be the
  cheaper (:func:`mask_reach`, ``MASK_REACH_TOPKS``); beyond it the chunk
  takes the list too.  A sigmoid gate per head, ``sigmoid(h W_g)``, scales
  the heads' outputs before ``W_o``.
* *window*: the same latent attention with the ``w_*`` sizes, no indexer, over
  the query's own position and the ``window - 1`` before it.
* *experts*: ``s = sigmoid(h W_r)`` over all ``n_experts``; the ``top_k``
  largest ``s + router_bias`` are chosen and weighted ``s_e / sum_sel s``
  (times ``routed_scale``); this chip **holds** experts ``held_first ..
  held_first + held_count`` and adds what they give for the tokens that chose
  them, plus the shared expert.  No capacity exists and no token is dropped.
  A program of at most ``IN_PLACE_ROWS`` rows (a tick, a short chunk) leaves
  its rows where they are: every row through each computed expert, the row's
  weight on it selected onto the outcome, all held experts at once where
  most are touched and one touched expert a step where few are.  A longer
  program sorts the held choices by expert into tile-aligned segments and
  one grouped product (:mod:`horovod_tpu.models.grouped_experts`, a Pallas
  kernel) puts every tile in use through its expert's matrices, the next
  tile's weights streaming in while the present tile is multiplied; widths
  that are not whole lanes (the CPU tests' toys) keep the plain form, a loop
  over the tiles.  Only the form that takes all experts at once reads one
  that no row chose, and it runs where at least three quarters are touched.
  What the absent experts would add is left out — on one chip the layer
  runs without its exchange — and the partial result goes on to the next
  layer.

**The absorbed form.**  Nothing per head is cached.  ``W_kvb``'s key half is
folded into the query (``q_nope W_kvb_k -> [heads, kv_rank]``) and its value
half applied after the weighted sum of latents, so attention is products
against the ``kv_rank + rope_dim`` wide latents themselves.

**Three pools behind one block table.**  :class:`LatentPagedCache` holds
``latent`` ``[n_full, n_blocks, bs, kv_rank + rope_dim]``, ``index``
``[n_full, n_blocks, bs, index_dim]`` and ``window`` ``[n_window, n_blocks,
bs, w_kv_rank + w_rope_dim]``, each row padded with zeros to whole 128-lane
tiles (576 -> 640, 1088 -> 1152: :func:`_lanes`) so that the programs update
the pools in place.  A block id means the same block in all three,
so :class:`~horovod_tpu.models.llama.BlockPool`, the prefix cache and
preemption replay need to know nothing of them.  The window layers page their
latents over the whole length (the window is applied by positions); a
window-sized pool is later work, and ``kv.window_bytes_beyond_window`` says
what it would free.

**Counters.**  ``stats`` rides in the cache: the tick and the chunk programs
add to it on the device and the engine reads it with the tick's readback.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.models import grouped_experts, llama
from horovod_tpu.models.llama import rmsnorm

FULL, WINDOW = "full", "window"
LN_EPS = 1e-6                 # the indexer's LayerNorm
NEG = -1e30
#: stats columns: four running sums, one gauge, the held experts' load, and
#: last the expert layers that computed every held expert at once
#: (:func:`layers_batched`)
CHOICES_TOTAL, CHOICES_HELD, KEYS_VISIBLE, KEYS_SELECTED, TOUCHED, LOAD0 = \
    0, 1, 2, 3, 4, 5
_LO_BITS = 24                 # a running sum is hi * 2**24 + lo, both int32
LANES = 128
#: how much of a long computation one loop step takes: cached keys the
#: indexer scores at once, queries that attend at once (their selected
#: latents gathered, or a tile of keys scored for them), and the most rows
#: of one expert's tile in a program that sorts its choices into tiles
#: (:func:`tile_rows`)
INDEX_STEP_KEYS = 2048
QUERY_BLOCK = 128
TILE_ROWS = 128
#: a program of at most this many rows computes its experts over the rows in
#: place and sorts no choice into tiles (:func:`held_experts`).  Every row
#: then goes through every expert that is computed: 6·d·f operations a row
#: and expert against the expert's 6·d·f bytes, so up to 197 TFLOP/s / 819
#: GB/s = 240 rows the products hide behind the weights' read whatever the
#: widths.  Measured on one v5e at lfm2's widths (PERF.md, PR 32) against
#: the loop over sorted tiles that was the other side then: a tick of 128
#: rows 23.2 -> 16.6 ms, a chunk of 256 rows (the balance point) 21.0 ->
#: 16.1 ms; a chunk of 512 would spend twice the MXU's time of its read.
#: Read again with the grouped product as the other side (PERF.md, PR 46; the
#: layer alone, ``tools/expert_layer_sweep.py``): at 256 rows in place 1.23
#: ms a layer and grouped 1.15 at lfm2's widths, 1.99 and 1.53 at sdar's with
#: 101 of 128 experts touched (1.69 and 1.46 at 128 rows: in place reads the
#: experts nobody chose, too); at 512 rows 4.24 and 2.04.  Left at 256: the
#: ticks and the one-row chunks of five cells lower to the text they had, and
#: what 6-23 % of their expert layers is worth there is ROADMAP S19 (d)'s.
IN_PLACE_ROWS = 256
#: the selection is kept as a mask over key tiles (and never made into a
#: list) while a program's last query sees no more than this many times
#: ``index_topk`` keys; beyond it scoring every visible key costs more than
#: the sort and the gather it spares (:func:`mask_reach`).  Measured on one
#: v5e at dots3's widths (PERF.md, PR 30): a 512-token chunk breaks even at
#: 25 k of context, and key tiles of one 512-position block are fastest.
MASK_REACH_TOPKS = 12
MASK_KEY_TILE = 512


def _lanes(n: int) -> int:
    """``n`` rounded up to whole rows of 128 lanes.  The TPU tiles an array's
    last dimension in 128 lanes; a pool whose rows are not a multiple of that
    is held padded inside a program and compact outside it, and every program
    that updates it copies the whole pool in and out (seen by compiling for
    the chip: 576 and 1088 wide, the tick's scratch held a second pool)."""
    return -(-n // LANES) * LANES


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 19008            # rows of embedding and head held here
    dim: int = 5120
    layer_kinds: tuple = (FULL, FULL, WINDOW, WINDOW, WINDOW)
    first_dense: int = 1
    ffn_dim: int = 13824
    # full layers
    n_heads: int = 128
    q_rank: int = 1024
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 8e7
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    # window layers
    w_heads: int = 64
    w_q_rank: int = 1024
    w_kv_rank: int = 1024
    w_nope_dim: int = 192
    w_rope_dim: int = 64
    w_v_dim: int = 128
    w_rope_theta: float = 5e4
    window: int = 513
    # experts
    n_experts: int = 256               # the router's width
    expert_dim: int = 1536
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 1.0
    route_norm_eps: float = 0.0        # added to the chosen scores' sum
    held_first: int = 0                # the experts this chip holds
    held_count: int = 32
    vocab_first: int = 0               # the first held row (for the record)
    lora_rescale: bool = True
    norm_eps: float = 1e-5
    max_seq_len: int = 32768
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.layer_kinds) - {FULL, WINDOW}:
            raise ValueError(f"layer_kinds {self.layer_kinds} may hold only "
                             f"{FULL!r} and {WINDOW!r}")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError(
                f"held experts {self.held_first}..+{self.held_count} are not "
                f"within the router's {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    def attn(self, kind: str) -> dict:
        """One kind of layer's attention sizes under common names."""
        if kind == FULL:
            return dict(h=self.n_heads, qr=self.q_rank, kr=self.kv_rank,
                        nope=self.nope_dim, rope=self.rope_dim, v=self.v_dim,
                        theta=self.rope_theta)
        return dict(h=self.w_heads, qr=self.w_q_rank, kr=self.w_kv_rank,
                    nope=self.w_nope_dim, rope=self.w_rope_dim,
                    v=self.w_v_dim, theta=self.w_rope_theta)

    def n_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_kinds if k == kind)


def latent_moe_tiny(**overrides) -> LatentMoEConfig:
    """The CPU tests' preset: all three kinds of layer, 16 experts of which 8
    are held, a top-k and a window smaller than the test lengths."""
    base = dict(
        vocab_size=64, dim=32, layer_kinds=(FULL, FULL, WINDOW, WINDOW,
                                            WINDOW),
        first_dense=1, ffn_dim=64, n_heads=4, q_rank=16, kv_rank=8,
        nope_dim=8, rope_dim=4, v_dim=8, rope_theta=1e4, index_heads=2,
        index_dim=8, index_topk=6, w_heads=2, w_q_rank=16, w_kv_rank=16,
        w_nope_dim=12, w_rope_dim=4, w_v_dim=8, w_rope_theta=1e3, window=5,
        n_experts=16, expert_dim=16, top_k=4, n_shared=1, held_first=0,
        held_count=8, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    base.update(overrides)
    return LatentMoEConfig(**base)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(cfg: LatentMoEConfig, key: jax.Array) -> dict:
    """Random parameters: matrices ``[in, out]`` normal at ``1/sqrt(in)``,
    norm weights 1, a small router bias that is not zero."""
    dt = cfg.param_dtype

    def mat(k, n_in, *out):
        return (jax.random.normal(k, (n_in, *out), jnp.float32)
                * n_in ** -0.5).astype(dt)

    d = cfg.dim
    layers = []
    for i, kind in enumerate(cfg.layer_kinds):
        a = cfg.attn(kind)
        ks = iter(jax.random.split(jax.random.fold_in(key, i), 24))
        lp = {"attn_norm": jnp.ones((d,), dt),
              "w_qa": mat(next(ks), d, a["qr"]),
              "q_norm": jnp.ones((a["qr"],), dt),
              "w_qb": mat(next(ks), a["qr"],
                          a["h"] * (a["nope"] + a["rope"])),
              "w_kva": mat(next(ks), d, a["kr"] + a["rope"]),
              "kv_norm": jnp.ones((a["kr"],), dt),
              "w_kvb": mat(next(ks), a["kr"], a["h"] * (a["nope"] + a["v"])),
              "w_o": mat(next(ks), a["h"] * a["v"], d),
              "w_g": mat(next(ks), d, a["h"]),
              "mlp_norm": jnp.ones((d,), dt)}
        if kind == FULL:
            lp.update(
                w_iq=mat(next(ks), a["qr"], cfg.index_heads * cfg.index_dim),
                w_ik=mat(next(ks), d, cfg.index_dim),
                ik_norm_w=jnp.ones((cfg.index_dim,), dt),
                ik_norm_b=jnp.zeros((cfg.index_dim,), dt),
                w_iw=mat(next(ks), d, cfg.index_heads))
        if i < cfg.first_dense:
            lp.update(w_gate=mat(next(ks), d, cfg.ffn_dim),
                      w_up=mat(next(ks), d, cfg.ffn_dim),
                      w_down=mat(next(ks), cfg.ffn_dim, d))
        else:
            e, f = cfg.held_count, cfg.expert_dim
            sf = cfg.n_shared * f
            lp.update(
                w_router=mat(next(ks), d, cfg.n_experts),
                router_bias=jax.random.uniform(
                    next(ks), (cfg.n_experts,), jnp.float32, -0.05, 0.05),
                e_gate=mat(next(ks), d, e, f).transpose(1, 0, 2),
                e_up=mat(next(ks), d, e, f).transpose(1, 0, 2),
                e_down=mat(next(ks), f, e, d).transpose(1, 0, 2),
                s_gate=mat(next(ks), d, sf), s_up=mat(next(ks), d, sf),
                s_down=mat(next(ks), sf, d))
        layers.append(lp)
    k_top = jax.random.split(jax.random.fold_in(key, 10_000), 2)
    return {"embed": jax.random.normal(k_top[0], (cfg.vocab_size, d),
                                       jnp.float32).astype(dt),
            "layers": tuple(layers),
            "final_norm": jnp.ones((d,), dt),
            "lm_head": mat(k_top[1], d, cfg.vocab_size)}


def param_partition_specs(cfg: LatentMoEConfig, *, tp_axis: str = "tp"):
    raise NotImplementedError(
        "tensor-parallel serving of a LatentMoEConfig is not written: its "
        "cache is one latent per token, not per head, so the head split of "
        "llama.paged_cache_partition_specs does not apply; serve it at "
        "tp_size=1 (its deployment splits experts and vocabulary instead)")


def paged_cache_partition_specs(*, tp_axis: str = "tp"):
    return param_partition_specs(None, tp_axis=tp_axis)


def tp_split_dims(cfg: LatentMoEConfig) -> tuple:
    """Asked only at ``tp_size > 1``, which this model does not serve."""
    return param_partition_specs(cfg)


# ---------------------------------------------------------------------------
# the paged state
# ---------------------------------------------------------------------------

class LatentPagedCache(NamedTuple):
    """Three pools behind one block table (block 0 is trash in each):
    ``latent`` / ``index`` ``[n_full, n_blocks, bs, width]``, ``window``
    ``[n_window, n_blocks, bs, width]``; ``block_table`` [B, blocks_per_slot]
    int32, ``length`` [B] int32, and ``stats`` [2, 5 + held_count + 1] int32,
    the device-side counters (row 0 high words, row 1 low words)."""

    latent: jax.Array
    index: jax.Array
    window: jax.Array
    block_table: jax.Array
    length: jax.Array
    stats: jax.Array

    @property
    def block_size(self) -> int:
        return self.latent.shape[2]

    @property
    def logical_len(self) -> int:
        return self.block_table.shape[1] * self.latent.shape[2]


def init_paged_cache(
    cfg: LatentMoEConfig, n_slots: int, max_len: int, *,
    block_size: int, n_blocks: int | None = None,
) -> LatentPagedCache:
    """The three pools for ``n_slots`` rows of logical depth ``max_len``;
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

    def pool(n, width):
        return jnp.zeros((n, n_blocks, block_size, width), cfg.dtype)

    return LatentPagedCache(
        latent=pool(cfg.n_of(FULL), _lanes(cfg.kv_rank + cfg.rope_dim)),
        index=pool(cfg.n_of(FULL), _lanes(cfg.index_dim)),
        window=pool(cfg.n_of(WINDOW),
                    _lanes(cfg.w_kv_rank + cfg.w_rope_dim)),
        block_table=jnp.zeros((n_slots, per), jnp.int32),
        length=jnp.zeros((n_slots,), jnp.int32),
        stats=jnp.zeros((2, LOAD0 + cfg.held_count + 1), jnp.int32))


def paged_pool_bytes(pcache: LatentPagedCache) -> dict:
    """Device bytes one block holds in each pool."""
    return {name: int(np.prod(a.shape) // a.shape[1]) * a.dtype.itemsize
            for name, a in (("latent", pcache.latent),
                            ("index", pcache.index),
                            ("window", pcache.window))}


def paged_counters(pcache: LatentPagedCache) -> jax.Array:
    """The device array the engine reads back beside the tick's tokens."""
    return pcache.stats


def read_counters(stats_host: np.ndarray) -> dict:
    """The counters as Python ints (sums exact past 2**31)."""
    s = np.asarray(stats_host).astype(np.int64)
    total = (s[0] << _LO_BITS) + s[1]
    return {"choices_total": int(total[CHOICES_TOTAL]),
            "choices_held": int(total[CHOICES_HELD]),
            "keys_visible": int(total[KEYS_VISIBLE]),
            "keys_selected": int(total[KEYS_SELECTED]),
            "experts_touched": int(s[1, TOUCHED]),
            "held_load": [int(x) for x in total[LOAD0:-1]],
            "layers_batched": int(total[-1])}


def publish_paged_metrics(metrics, cfg: LatentMoEConfig,
                          pcache: LatentPagedCache,
                          stats_host: np.ndarray | None = None,
                          row_blocks: tuple = (),
                          programs: tuple = ()) -> None:
    """The model's own gauges and counters in the engine's registry.  Without
    ``stats_host`` and ``programs`` (at construction) the per-pool sizes; with
    ``stats_host`` (after a tick's readback) the counters, and from
    ``row_blocks`` (blocks mapped by each live row) what a window-sized pool
    would free.  ``programs`` holds each program a step dispatched
    (:class:`paged.Dispatched`): ``dsa.queries`` counts their
    queries times the full layers and ``dsa.mask_queries`` those of the
    programs that kept the selection as a mask, by :func:`mask_reach`, the
    function the programs' own branch comes from; nothing is read back.
    ``moe.choices_in_place`` and ``moe.choices_grouped`` are reckoned the
    same way (:func:`choices_in_place`, :func:`choices_grouped`)."""
    if stats_host is None and not programs:     # once, at construction
        per_block = paged_pool_bytes(pcache)
        metrics.gauge("kv.latent_block_bytes").set(per_block["latent"])
        metrics.gauge("kv.index_block_bytes").set(per_block["index"])
        metrics.gauge("kv.window_block_bytes").set(per_block["window"])
        metrics.gauge("kv.window_bytes_beyond_window").set(0)
        for name in ("moe.choices_total", "moe.choices_held",
                     "moe.choices_in_place", "moe.choices_grouped",
                     "moe.layers_batched",
                     "dsa.keys_visible", "dsa.keys_selected",
                     "dsa.queries", "dsa.mask_queries"):
            metrics.counter(name)
    m = pcache.logical_len
    k = min(cfg.index_topk, m)
    metrics.counter("dsa.queries").inc(cfg.n_of(FULL) * sum(
        p.rows * p.t for p in programs))
    metrics.counter("dsa.mask_queries").inc(cfg.n_of(FULL) * sum(
        p.rows * p.t for p in programs
        if p.longest + p.t <= mask_reach(p.t, m, k)))
    count_choices_by_form(metrics, cfg, programs)
    if stats_host is None:          # nothing was read back: no tick ran
        return
    keep = -(-(cfg.window - 1) // pcache.block_size) + 1
    metrics.gauge("kv.window_bytes_beyond_window").set(
        metrics.gauge("kv.window_block_bytes").value
        * sum(max(n - keep, 0) for n in row_blocks))
    c = read_counters(stats_host)
    _set_counter(metrics.counter("moe.choices_total"), c["choices_total"])
    _set_counter(metrics.counter("moe.choices_held"), c["choices_held"])
    _set_counter(metrics.counter("moe.layers_batched"), c["layers_batched"])
    _set_counter(metrics.counter("dsa.keys_visible"), c["keys_visible"])
    _set_counter(metrics.counter("dsa.keys_selected"), c["keys_selected"])
    metrics.gauge("moe.experts_touched").set(c["experts_touched"])
    for e, n in enumerate(c["held_load"]):
        metrics.gauge(f"moe.held_load.{cfg.held_first + e}").set(n)


def _set_counter(counter, total: int) -> None:
    counter.inc(total - counter.value)


# ---------------------------------------------------------------------------
# layer mathematics
# ---------------------------------------------------------------------------

def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Half-split rotary on ``x`` [B, T, heads, n] at positions [B, T]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    return llama.apply_rope(x, jnp.cos(ang), jnp.sin(ang))


def _dot(x, w, dt):
    return x @ w.astype(dt)


def _latents(cfg: LatentMoEConfig, a: dict, lp: dict, h, qpos):
    """``c_q`` [B, T, qr], the absorbed query [B, T, H, kr + rope] and the new
    latent ``c_kv | k_r`` [B, T, kr + rope], both padded to the pool's row
    (zeros against zeros add nothing to a score), and ``W_kvb``'s value half."""
    dt = cfg.dtype
    b, t, _ = h.shape
    sq = (cfg.dim / a["qr"]) ** 0.5 if cfg.lora_rescale else 1.0
    skv = (cfg.dim / a["kr"]) ** 0.5 if cfg.lora_rescale else 1.0
    c_q = rmsnorm(_dot(h, lp["w_qa"], dt), lp["q_norm"], cfg.norm_eps) \
        * jnp.asarray(sq, dt)
    q = _dot(c_q, lp["w_qb"], dt).reshape(b, t, a["h"],
                                          a["nope"] + a["rope"])
    q_rope = _rope(q[..., a["nope"]:], qpos, a["theta"])
    w_kvb = lp["w_kvb"].astype(dt).reshape(a["kr"], a["h"],
                                           a["nope"] + a["v"])
    q_abs = jnp.einsum("bthn,rhn->bthr", q[..., :a["nope"]],
                       w_kvb[..., :a["nope"]])
    kv = _dot(h, lp["w_kva"], dt)
    c_kv = rmsnorm(kv[..., :a["kr"]], lp["kv_norm"], cfg.norm_eps) \
        * jnp.asarray(skv, dt)
    k_r = _rope(kv[..., None, a["kr"]:], qpos, a["theta"])[:, :, 0]
    pad = _lanes(a["kr"] + a["rope"]) - a["kr"] - a["rope"]
    return (c_q, _pad_last(jnp.concatenate([q_abs, q_rope], -1), pad),
            _pad_last(jnp.concatenate([c_kv, k_r], -1), pad),
            w_kvb[..., a["nope"]:])


def _pad_last(x: jax.Array, pad: int) -> jax.Array:
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _finish_attention(cfg, a, lp, h, o_lat, w_v):
    """Latent-space outputs [B, T, H, kr] -> the layer's residual update."""
    dt = cfg.dtype
    b, t = h.shape[:2]
    o = jnp.einsum("bthr,rhv->bthv", o_lat.astype(dt), w_v)
    gate = jax.nn.sigmoid(_dot(h, lp["w_g"], dt).astype(jnp.float32))
    o = o * gate[..., None].astype(dt)
    return _dot(o.reshape(b, t, a["h"] * a["v"]), lp["w_o"], dt)


def _phys(table: jax.Array, pos: jax.Array, bs: int) -> jax.Array:
    """Flat in-pool positions of logical positions ``pos`` [B, N] under the
    rows' block tables [B, per]."""
    per = table.shape[1]
    blk = jnp.take_along_axis(table, jnp.clip(pos // bs, 0, per - 1), axis=1)
    return blk * bs + pos % bs


def _blocks_a_step(keys: int, bs: int, per: int) -> int:
    """Blocks of a row's table that one loop step takes: about ``keys``
    positions, at least one block, and a divisor of the table."""
    group = max(min(keys // bs, per), 1)
    while per % group:
        group -= 1
    return group


def _index_scores(cfg: LatentMoEConfig, lp: dict, h, c_q, qpos, index_flat,
                  off, table, bs: int):
    """The indexer's scores [B, T, m] (float32) over every cached key, a
    group of blocks at a time and no further than the longest row reaches;
    a key the query does not see, or that no step reached, reads ``-inf``."""
    dt = cfg.dtype
    b, t, _ = h.shape
    per = table.shape[1]
    m = per * bs
    ih, idim, rd = cfg.index_heads, cfg.index_dim, cfg.rope_dim
    with jax.named_scope("dsa.index"):
        q_i = _dot(c_q, lp["w_iq"], dt).reshape(b, t, ih, idim)
        q_i = jnp.concatenate(
            [_rope(q_i[..., :rd], qpos, cfg.rope_theta), q_i[..., rd:]], -1)
        q_i_pad = _pad_last(q_i, index_flat.shape[-1] - idim)
        wgt = (_dot(h, lp["w_iw"], dt).astype(jnp.float32)
               * (ih ** -0.5 * idim ** -0.5))
        group = _blocks_a_step(INDEX_STEP_KEYS, bs, per)
        kb = group * bs
        n_groups = jnp.minimum((jnp.max(qpos) + kb) // kb, per // group)

        def scores_of(j, acc):
            blocks = lax.dynamic_slice_in_dim(table, j * group, group, axis=1)
            phys = (blocks[:, :, None] * bs
                    + jnp.arange(bs)[None, None, :]).reshape(b, kb)
            k_i = index_flat[phys + off]                    # [B, kb, idim]
            s = jnp.einsum("bthd,bkd->bthk", q_i_pad, k_i,
                           preferred_element_type=jnp.float32)
            s = jnp.sum(jnp.maximum(s, 0.0) * wgt[..., None], axis=2)
            kpos = j * kb + jnp.arange(kb)
            s = jnp.where(kpos[None, None, :] <= qpos[:, :, None], s,
                          -jnp.inf)
            return lax.dynamic_update_slice_in_dim(acc, s, j * kb, axis=2)

        return lax.fori_loop(
            0, n_groups, scores_of,
            jnp.full((b, t, m), -jnp.inf, jnp.float32))


def _index_select(cfg: LatentMoEConfig, lp: dict, h, c_q, qpos, index_flat,
                  off, table, bs: int):
    """The selection as a list: the indexer's scores, then the exact top-k.
    Returns the selected logical positions [B, T, k] and which of them are
    real (fewer than k keys are visible early in a sequence)."""
    scores = _index_scores(cfg, lp, h, c_q, qpos, index_flat, off, table, bs)
    m = scores.shape[-1]
    kb = _blocks_a_step(INDEX_STEP_KEYS, bs, m // bs) * bs
    with jax.named_scope("dsa.select"):
        k = min(cfg.index_topk, m)
        # the exact top-k is a sort, whose cost grows faster than its width:
        # it runs over the shortest of the widths m, m/2, m/4, ... that
        # holds every visible key (the rest are -inf and cannot be chosen)
        widths = [m]
        while widths[-1] % 2 == 0 and widths[-1] // 2 >= max(k, 4 * kb):
            widths.append(widths[-1] // 2)
        need = jnp.max(qpos) + 1
        branch = sum((need <= w).astype(jnp.int32) for w in widths[1:])
        vals, idx = lax.switch(
            branch, [partial(lambda w, s: lax.top_k(s[..., :w], k), w)
                     for w in widths], scores)
    return idx, vals > -jnp.inf


def mask_reach(t: int, m: int, k: int) -> int:
    """How many keys the last query of a program may see for the program to
    keep the selection as a mask over key tiles; 0 where it never does.  A
    program of ``t`` tokens a row over a table of ``m`` positions gathers
    ``t * k`` selected rows as a list and reads at most ``m`` as a mask, so
    only a program whose list is the longer (a chunk of prefill, not a tick)
    has the mask path at all, and takes it up to ``MASK_REACH_TOPKS`` times
    ``k`` visible keys, where scoring every one of them passes what the
    sort and the gather cost.  The device's branch and the host's counters
    (:func:`publish_paged_metrics`) both come from here."""
    return min(MASK_REACH_TOPKS * k, m) if t * k > m else 0


def _ordered_bits(scores: jax.Array) -> jax.Array:
    """float32 -> uint32 in the floats' total order: ``-0.0`` below ``+0.0``,
    as ``lax.top_k`` orders them on the CPU and on the TPU alike (the
    indexer's ``relu(...) * w`` makes zeros of both signs)."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(u: jax.Array, k: int, n_steps, step: int):
    """Per query of ``u`` [B, T, m] (uint32) the k-th largest value among
    the first ``n_steps * step`` keys, by counting: the largest ``v`` that
    at least ``k`` keys reach, built a bit at a time from the top (32 passes
    of compare-and-count, no sort); and how many of the keys equal to it the
    top-k takes, ``k`` less the keys above it.  With fewer than ``k`` keys
    above a masked one (``-inf``) the value is at or below the masked keys'."""
    def count(reached, cand):
        def one_step(j, n):
            part = lax.dynamic_slice_in_dim(u, j * step, step, axis=2)
            return n + jnp.sum(reached(part, cand[..., None]), axis=-1,
                               dtype=jnp.int32)
        return lax.fori_loop(0, n_steps, one_step,
                             jnp.zeros(u.shape[:2], jnp.int32))

    def one_bit(i, thr):
        cand = thr | (jnp.uint32(1 << 31) >> jnp.asarray(i, jnp.uint32))
        return jnp.where(count(jnp.greater_equal, cand) >= k, cand, thr)

    thr = lax.fori_loop(0, 32, one_bit, jnp.zeros(u.shape[:2], jnp.uint32))
    return thr, k - count(jnp.greater, thr)


def _take(u, seen, thr, quota, taken):
    """Which keys of one tile are in the top-k: ``u`` [Q, W] and ``seen``
    [Q, W] (the key is visible to the query), ``thr`` and ``quota`` [Q] from
    :func:`_kth_largest`, ``taken`` [Q] the keys equal to ``thr`` that
    earlier tiles took.  Every visible key above the threshold, and of the
    visible keys equal to it the lowest positions until the quota is used:
    what ``lax.top_k`` returns, as a set.  Returns the mask and ``taken``
    after this tile."""
    tie = seen & (u == thr[:, None])
    rank = taken[:, None] + jnp.cumsum(tie, axis=-1, dtype=jnp.int32)
    sel = (seen & (u > thr[:, None])) | (tie & (rank <= quota[:, None]))
    return sel, rank[:, -1]


def _index_keys(cfg: LatentMoEConfig, lp: dict, h, qpos):
    """``k_I`` [B, T, index_dim] of the chunk's own positions."""
    k_i = _dot(h, lp["w_ik"], cfg.dtype).astype(jnp.float32)
    mu = jnp.mean(k_i, axis=-1, keepdims=True)
    var = jnp.mean((k_i - mu) ** 2, axis=-1, keepdims=True)
    k_i = ((k_i - mu) * lax.rsqrt(var + LN_EPS)
           * lp["ik_norm_w"].astype(jnp.float32)
           + lp["ik_norm_b"].astype(jnp.float32)).astype(cfg.dtype)
    rd = cfg.rope_dim
    return jnp.concatenate(
        [_rope(k_i[..., None, :rd], qpos, cfg.rope_theta)[:, :, 0],
         k_i[..., rd:]], -1)


def _query_block(n_queries: int) -> int:
    qb = min(n_queries, QUERY_BLOCK)
    while n_queries % qb:
        qb -= 1
    return qb


def _full_attention(cfg, lp, h, qpos, table, bs, latent_flat, index_flat,
                    off, wflat):
    """One full layer's attention: write the chunk's latent and index key,
    then attend over the indexer's exact top-k, kept as a mask over key
    tiles or made into a list by what :func:`mask_reach` says of the
    program's shape and of how far its last query sees.  Returns the
    residual update and the two pools."""
    a = cfg.attn(FULL)
    t, m = h.shape[1], table.shape[1] * bs
    c_q, q_abs, latent, w_v = _latents(cfg, a, lp, h, qpos)
    latent_flat = latent_flat.at[wflat + off].set(latent)
    index_flat = index_flat.at[wflat + off].set(_pad_last(
        _index_keys(cfg, lp, h, qpos), index_flat.shape[-1] - cfg.index_dim))
    args = (cfg, lp, h, c_q, q_abs, qpos, table, bs, latent_flat, index_flat,
            off)
    reach = mask_reach(t, m, min(cfg.index_topk, m))
    if reach:
        o_lat = lax.cond(jnp.max(qpos) < reach, lambda: _attend_mask(*args),
                         lambda: _attend_list(*args))
    else:
        o_lat = _attend_list(*args)
    return (_finish_attention(cfg, a, lp, h, o_lat, w_v), latent_flat,
            index_flat)


def _attend_list(cfg, lp, h, c_q, q_abs, qpos, table, bs, latent_flat,
                 index_flat, off):
    """Attention over the selection as a list: the sort, then the selected
    latents gathered a block of queries at a time.  [B, T, H, kr]."""
    a = cfg.attn(FULL)
    b, t, _ = h.shape
    idx, real = _index_select(cfg, lp, h, c_q, qpos, index_flat, off, table,
                              bs)
    k = idx.shape[-1]
    phys = _phys(table, idx.reshape(b, t * k), bs).reshape(b * t, k) + off
    scale = (a["nope"] + a["rope"]) ** -0.5
    qb = _query_block(b * t)

    def attend(args):
        q, ph, ok = args                # [qb, H, R], [qb, k], [qb, k]
        lat = latent_flat[ph]           # [qb, k, R]: the selected latents
        s = jnp.einsum("qhr,qkr->qhk", q, lat,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(ok[:, None, :], s, NEG), axis=-1)
        return jnp.einsum("qhk,qkr->qhr", p.astype(cfg.dtype),
                          lat[..., :a["kr"]],
                          preferred_element_type=jnp.float32)

    def blocks(x):
        return x.reshape(b * t // qb, qb, *x.shape[1:])

    o_lat = lax.map(attend, (blocks(q_abs.reshape(b * t, *q_abs.shape[2:])),
                             blocks(phys), blocks(real.reshape(b * t, k))))
    return o_lat.reshape(b, t, a["h"], a["kr"])


def _attend_mask(cfg, lp, h, c_q, q_abs, qpos, table, bs, latent_flat,
                 index_flat, off):
    """Attention over the selection as a mask: the k-th largest score of
    each query by counting, then every visible key scored a tile at a time
    along the row's block table with the mask broadcast over the heads and
    a running softmax (maximum, sum and accumulator float32, as
    :func:`llama._paged_attend` walks its tiles).  The same set as the
    list's, key for key; each latent is read once per block of queries and
    nothing is sorted or gathered by key.  [B, T, H, kr]."""
    a = cfg.attn(FULL)
    b, t, _ = h.shape
    per = table.shape[1]
    m = per * bs
    scores = _index_scores(cfg, lp, h, c_q, qpos, index_flat, off, table, bs)
    with jax.named_scope("dsa.select"):
        step = _blocks_a_step(INDEX_STEP_KEYS, bs, per) * bs
        u = _ordered_bits(scores)
        thr, quota = _kth_largest(
            u, min(cfg.index_topk, m),
            jnp.minimum(jnp.max(qpos) // step + 1, m // step), step)
    g = _blocks_a_step(MASK_KEY_TILE, bs, per)
    w = g * bs
    qb = _query_block(t)
    scale = (a["nope"] + a["rope"]) ** -0.5
    blocks_of = latent_flat.reshape(-1, bs, latent_flat.shape[-1])
    first = off // bs                   # the layer's stripe, in blocks
    stat = (qb, a["h"])

    def attend(args):
        q, pos, u_q, thr_q, quota_q, tab = args     # one row's qb queries

        def tile(j, acc):
            mx, den, o, taken = acc
            lat = blocks_of[lax.dynamic_slice_in_dim(tab, j * g, g) + first]
            lat = lat.reshape(w, lat.shape[-1])
            kpos = j * w + jnp.arange(w)
            sel, taken = _take(
                lax.dynamic_slice_in_dim(u_q, j * w, w, axis=1),
                kpos[None, :] <= pos[:, None], thr_q, quota_q, taken)
            s = jnp.einsum("qhr,kr->qhk", q, lat,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(sel[:, None, :], s, NEG)
            mx_new = jnp.maximum(mx, jnp.max(s, axis=-1))
            p = jnp.exp(s - mx_new[..., None])
            fade = jnp.exp(mx - mx_new)
            den = fade * den + jnp.sum(p, axis=-1)
            o = fade[..., None] * o + jnp.einsum(
                "qhk,kr->qhr", p.astype(cfg.dtype), lat[:, :a["kr"]],
                preferred_element_type=jnp.float32)
            return mx_new, den, o, taken

        # a query whose first tiles hold none of its keys carries a maximum
        # of NEG and weights of exp(0) until its first selected key (it has
        # one: its own position is visible) fades them to exact zeros
        _, den, o, _ = lax.fori_loop(
            0, jnp.minimum(jnp.max(pos) // w + 1, per // g), tile,
            (jnp.full(stat, NEG, jnp.float32), jnp.zeros(stat, jnp.float32),
             jnp.zeros(stat + (a["kr"],), jnp.float32),
             jnp.zeros((qb,), jnp.int32)))
        return o / den[..., None]

    def blocks(x):                      # [B, T, ...] -> [B * T/qb, qb, ...]
        return x.reshape(b * t // qb, qb, *x.shape[2:])

    with jax.named_scope("dsa.attend_mask"):
        o_lat = lax.map(attend, (
            blocks(q_abs), blocks(qpos), blocks(u), blocks(thr),
            blocks(quota), jnp.repeat(table, t // qb, axis=0)))
    return o_lat.reshape(b, t, a["h"], a["kr"])


def _window_attention(cfg, lp, h, qpos, table, bs, window_flat, off, wflat):
    """One window layer's attention over the ``T + window - 1`` positions
    that end at the chunk's last one."""
    a = cfg.attn(WINDOW)
    t = h.shape[1]
    _, q_abs, latent, w_v = _latents(cfg, a, lp, h, qpos)
    window_flat = window_flat.at[wflat + off].set(latent)
    kpos = (qpos[:, :1] - (cfg.window - 1)
            + jnp.arange(t + cfg.window - 1)[None, :])           # [B, K]
    lat = window_flat[_phys(table, jnp.maximum(kpos, 0), bs) + off]
    ok = ((kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[:, :, None])
          & (kpos[:, None, :] > qpos[:, :, None] - cfg.window))  # [B, T, K]
    s = jnp.einsum("bthr,bkr->bhtk", q_abs, lat,
                   preferred_element_type=jnp.float32) \
        * (a["nope"] + a["rope"]) ** -0.5
    p = jax.nn.softmax(jnp.where(ok[:, None], s, NEG), axis=-1)
    o_lat = jnp.einsum("bhtk,bkr->bhtr", p.astype(cfg.dtype),
                       lat[..., :a["kr"]],
                       preferred_element_type=jnp.float32).swapaxes(1, 2)
    return _finish_attention(cfg, a, lp, h, o_lat, w_v), window_flat


def _swiglu(x, w_gate, w_up, w_down, dt):
    return _dot(jax.nn.silu(_dot(x, w_gate, dt)) * _dot(x, w_up, dt),
                w_down, dt)


def route(cfg: LatentMoEConfig, lp: dict, h2):
    """Sigmoid scores over the whole router, the ``top_k`` largest with the
    bias chosen, weights normalised over the chosen (their sum plus
    ``cfg.route_norm_eps``): ``[N, k]`` both.  ``cfg`` is any config with
    ``top_k``, ``routed_scale`` and ``route_norm_eps``.  A config that says
    ``route_softmax_top_k`` has the second rule: the ``top_k`` largest
    logits chosen, weights a softmax over the chosen (no bias, no scale)."""
    logits = jnp.dot(h2.astype(jnp.float32),
                     lp["w_router"].astype(jnp.float32))
    if getattr(cfg, "route_softmax_top_k", False):
        top, experts = lax.top_k(logits, cfg.top_k)
        return experts, jax.nn.softmax(top, axis=-1)
    s = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(s + lp["router_bias"], cfg.top_k)
    picked = jnp.take_along_axis(s, experts, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    if cfg.route_norm_eps:
        total = total + cfg.route_norm_eps
    return experts, picked / total * cfg.routed_scale


def held_experts(cfg: LatentMoEConfig, lp: dict, h2, valid):
    """The held experts' part of the layer for tokens ``h2`` [N, d]: every
    choice that fell on a held expert is computed and none is dropped.
    ``cfg`` is any config with the fields of :func:`route` and ``dtype``,
    ``held_first`` and ``held_count``
    (:mod:`horovod_tpu.models.shortconv_moe` shares this layer).
    ``valid`` [N] marks real tokens (pads and idle rows choose nothing).
    Returns the weighted sum per token and the per-held-expert load.  A
    program of few rows computes them in place (:func:`rows_in_place`), one
    of many sorts its choices into tiles and multiplies the tiles in one
    grouped product (:func:`rows_grouped`)."""
    n = h2.shape[0]
    held, group, weights, load = held_choices(cfg, lp, h2, valid)
    if rows_in_place(n):
        return _experts_in_place(cfg, lp, h2, group, weights, load), load
    tiles = _tiles_grouped if rows_grouped(
        n, h2.shape[1], lp["e_gate"].shape[-1]) else _tiles_looped
    return _experts_in_tiles(cfg, lp, h2, held, group, weights, load,
                             tiles), load


def held_choices(cfg, lp: dict, h2, valid):
    """What :func:`held_experts` computes from: ``held`` [N, k] marks the
    choices of real tokens that fell on a held expert, ``group`` [N * k] is
    each choice's held expert (``held_count`` where it is not held),
    ``weights`` [N, k] the router's, ``load`` [E] the choices an expert."""
    n = h2.shape[0]
    e = cfg.held_count
    with jax.named_scope("moe.route"):
        experts, weights = route(cfg, lp, h2)
        local = experts - cfg.held_first
        held = (local >= 0) & (local < e) & valid[:, None]          # [N, k]
        group = jnp.where(held, local, e).reshape(n * cfg.top_k)
        load = jnp.sum(jax.nn.one_hot(group, e + 1, dtype=jnp.int32),
                       axis=0)[:e]                                   # [E]
    return held, group, weights, load


def rows_in_place(n_rows: int) -> bool:
    """Whether a program of ``n_rows`` tokens computes its experts over the
    rows where they stand."""
    return n_rows <= IN_PLACE_ROWS


def tile_rows(n_rows: int, cfg) -> int:
    """The rows of one expert's tile in a program of ``n_rows`` tokens that
    sorts its choices into tiles: twice the mean load an expert (``n_rows *
    top_k / n_experts``) rounded up to a power of two, so that few experts
    need a second tile, from 32 rows to :data:`TILE_ROWS`.  A tile costs the
    MXU all its rows, padding too, and at 128 rows that is no longer hidden
    behind the read of a small expert's weights.  Measured on one v5e
    (PERF.md, PR 46; the expert layer alone, ``tools/expert_layer_sweep.py``):
    at sdar's widths and 32 choices an expert, tiles of 128 / 64 / 32 rows
    2.42 / 2.08 / 2.14 ms a layer; at dots3's and 16 an expert, 128 / 32 rows
    3.01 / 2.59; at K-EXAONE's and 64 an expert (75.5 MB an expert in four
    blocks: a second tile reads it again) 128 / 64 rows 3.02 / 3.53; at
    granite's and 71 an expert 128 / 64 rows 1.77 / 1.60, where the rule's
    128 is not the best (its expert is one block, a second tile reads
    nothing again: ROADMAP S19)."""
    twice = max(-(-2 * n_rows * cfg.top_k // cfg.n_experts), 1)
    return min(TILE_ROWS, max(32, 1 << (twice - 1).bit_length()))


def rows_grouped(n_rows: int, d: int, f: int) -> bool:
    """Whether a program of ``n_rows`` tokens puts its sorted tiles through
    the grouped product: one that does not compute in place, over experts
    ``[d, f]`` wide in whole lanes (every published width; the toy widths of
    the CPU tests keep the loop over the tiles, the plain form)."""
    return not rows_in_place(n_rows) and grouped_experts.lane_aligned(d, f)


def _most_experts_touched(n_touched, e: int):
    """Whether computing all ``e`` held experts at once is cheaper than one
    touched expert a step.  Measured on one v5e at lfm2's widths (PERF.md,
    PR 32): an expert costs 30 us among all 32 at once (its 22 MB at 90 % of
    the memory's peak) and 41 us as a step of the loop, so the two cross at
    23 of 32 touched."""
    return n_touched * 4 >= e * 3


def layers_batched(n_rows: int, load) -> jax.Array:
    """1 where an expert layer of ``n_rows`` tokens with this per-expert
    ``load`` computed all its held experts at once, else 0: the predicates
    :func:`held_experts` itself goes by."""
    if not rows_in_place(n_rows):
        return jnp.int32(0)
    return _most_experts_touched(
        jnp.sum(load > 0, dtype=jnp.int32), load.shape[0]).astype(jnp.int32)


def _choices_where(cfg, programs: tuple, takes) -> int:
    """``rows x tokens a row x top_k x expert layers`` of each dispatched
    program (:class:`paged.Dispatched`) whose count of tokens ``takes``."""
    return cfg.top_k * (cfg.n_layers - cfg.first_dense) * sum(
        p.rows * p.t for p in programs if takes(p.rows * p.t))


def choices_in_place(cfg, programs: tuple) -> int:
    """The choices of the dispatched programs whose expert layers computed in
    place: those within :func:`rows_in_place`, the function the program's
    own form comes from.  Every row counts, idle and padded ones too (the
    layer computes over them), where ``moe.choices_total`` counts the real
    tokens' on the device: over programs whose rows are all live the two are
    alike."""
    return _choices_where(cfg, programs, rows_in_place)


def count_choices_by_form(metrics, cfg, programs: tuple) -> None:
    """``moe.choices_in_place`` and ``moe.choices_grouped`` of a step's
    dispatched programs, added to the engine's registry."""
    metrics.counter("moe.choices_in_place").inc(
        choices_in_place(cfg, programs))
    metrics.counter("moe.choices_grouped").inc(
        choices_grouped(cfg, programs))


def choices_grouped(cfg, programs: tuple) -> int:
    """The choices of the dispatched programs whose expert layers ran the
    grouped product, counted as :func:`choices_in_place` counts its own and
    by :func:`rows_grouped`, the predicate :func:`held_experts` goes by
    (``cfg.dim`` and ``cfg.expert_dim`` are the widths its experts have)."""
    return _choices_where(cfg, programs, lambda n: rows_grouped(
        n, cfg.dim, cfg.expert_dim))


def _experts_in_place(cfg, lp, h2, group, weights, load):
    """Every row through every expert that is computed, the row's weight on
    the expert applied to the outcome (zero where it did not choose it): no
    choice is sorted, gathered or scattered.  An expert's outcome is rounded
    to ``cfg.dtype`` as :func:`_swiglu` rounds it and the sum over a row's
    experts is float32, by expert index.  Rows that chose nothing come out
    exactly zero whatever they hold (a select, not a product with zero)."""
    dt = cfg.dtype
    n, d = h2.shape
    e = cfg.held_count
    with jax.named_scope("moe.route"):
        # [E, N]: a token chooses an expert at most once
        on = group.reshape(n, -1).T[:, None, :] == jnp.arange(e)[None, :, None]
        chosen = jnp.any(on, axis=0)
        w = jnp.sum(jnp.where(on, weights.T[:, None, :], 0.0), axis=0)
        touched = load > 0
        n_touched = jnp.sum(touched, dtype=jnp.int32)

    with jax.named_scope("moe.experts"):
        y = _in_place((jnp.dtype(dt), e), h2, lp["e_gate"], lp["e_up"],
                      lp["e_down"], chosen, w, touched, n_touched)
    return y.astype(dt)


def _weighed(out, c, wt):
    return jnp.where(c[..., None], wt[..., None] * out.astype(jnp.float32),
                     0.0)


def _in_place_batched(dt, h2, e_gate, e_up, e_down, chosen, w):
    """Every held expert at once over the rows in place, ``[N, d]`` float32."""
    gate = jnp.einsum("nd,edf->enf", h2, e_gate.astype(dt))
    up = jnp.einsum("nd,edf->enf", h2, e_up.astype(dt))
    out = jnp.einsum("enf,efd->end", jax.nn.silu(gate) * up,
                     e_down.astype(dt))
    return jnp.sum(_weighed(out, chosen, w), axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _in_place(form, h2, e_gate, e_up, e_down, chosen, w, touched, n_touched):
    """All held experts at once where most are touched, one touched expert a
    step otherwise.  The loop's bound is a device value, which jax cannot
    differentiate through; both forms compute the same sum, so the rule's
    backward is the first form's (:func:`_in_place_bwd`)."""
    dt, e = form
    n, d = h2.shape

    def batched():
        return _in_place_batched(dt, h2, e_gate, e_up, e_down, chosen, w)

    def looped():
        # the touched experts' ids, compacted once: nothing is searched for
        # inside the loop
        ids = jnp.zeros((e,), jnp.int32).at[
            jnp.where(touched, jnp.cumsum(touched) - 1, e)].set(
                jnp.arange(e, dtype=jnp.int32), mode="drop")

        def one_expert(i, y):
            j = ids[i]
            out = _swiglu(h2, e_gate[j], e_up[j], e_down[j], dt)
            return y + _weighed(out, chosen[j], w[j])

        return lax.fori_loop(0, n_touched, one_expert,
                             jnp.zeros((n, d), jnp.float32))

    return lax.cond(_most_experts_touched(n_touched, e), batched, looped)


def _in_place_fwd(form, h2, e_gate, e_up, e_down, chosen, w, touched,
                  n_touched):
    y = _in_place(form, h2, e_gate, e_up, e_down, chosen, w, touched,
                  n_touched)
    return y, (h2, e_gate, e_up, e_down, chosen, w)


def _in_place_bwd(form, res, dy):
    h2, e_gate, e_up, e_down, chosen, w = res
    _, back = jax.vjp(
        lambda h2, a, b, c, w: _in_place_batched(form[0], h2, a, b, c,
                                                 chosen, w),
        h2, e_gate, e_up, e_down, w)
    d_h2, d_gate, d_up, d_down, d_w = back(dy)
    return d_h2, d_gate, d_up, d_down, None, d_w, None, None


_in_place.defvjp(_in_place_fwd, _in_place_bwd)


def _experts_in_tiles(cfg, lp, h2, held, group, weights, load, tiles):
    """The choices sorted by expert into tile-aligned segments, every tile in
    use through its expert (``tiles``: :func:`_tiles_grouped` or
    :func:`_tiles_looped`), and each token's outcomes gathered back and
    summed by rank (:func:`_tiles_layer`, which has a derivative)."""
    n, d = h2.shape
    e, k = cfg.held_count, cfg.top_k
    tile = tile_rows(n, cfg)
    with jax.named_scope("moe.route"):
        padded = -(-load // tile) * tile
        seg_end = jnp.cumsum(padded)
        seg_start = seg_end - padded
        # the rank of a choice among its expert's: its place in a stable sort
        # by expert, less the choices of the experts before
        order = jnp.argsort(group, stable=True)
        place = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
        before = jnp.cumsum(load) - load
        g = jnp.minimum(group, e - 1)
        rows = n * k + e * tile                 # every choice held, at worst
        dest = jnp.where(group < e, seg_start[g] + place - before[g], rows)
        token = jnp.arange(n * k, dtype=jnp.int32) // k
        src = jnp.full((rows,), n, jnp.int32).at[dest].set(token, mode="drop")
    with jax.named_scope("moe.experts"):
        return _tiles_layer((tiles, jnp.dtype(cfg.dtype), tile), h2, weights,
                            lp["e_gate"], lp["e_up"], lp["e_down"], held,
                            dest, src, seg_end)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tiles_layer(form, h2, weights, e_gate, e_up, e_down, held, dest, src,
                 seg_end):
    """``[N, d]``: the rows gathered into their sorted places, the tiles in
    use through their experts, and each token's outcomes gathered back and
    summed by rank under the router's ``weights`` [N, k].  ``form`` is
    ``(tiles, dtype, tile)``; ``dest`` [N * k] is each choice's row (past
    the rows where it is not held), ``src`` [R] each row's token (``N`` for
    padding).  The derivative (:func:`_tiles_layer_bwd`) walks the same
    tiles and sorts nothing again; the indices carry none."""
    tiles, dt, tile = form
    n, d = h2.shape
    rows = src.shape[0]
    x_rows = jnp.concatenate([h2, jnp.zeros((1, d), dt)])[src]      # [R, d]
    y_rows = tiles(dt, e_gate, e_up, e_down, x_rows, seg_end, tile)
    # a choice that is not held points past the rows: it reads the last
    # one and is selected away (a zero row appended to gather instead
    # was a copy of all of y_rows, 84 MB a layer at sdar's widths)
    picked = jnp.where(
        held[..., None],
        y_rows[jnp.minimum(dest, rows - 1).reshape(held.shape)], 0)  # [N,k,d]
    y = jnp.sum(picked.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(dt)


def _tiles_layer_fwd(form, h2, weights, e_gate, e_up, e_down, held, dest, src,
                     seg_end):
    y = _tiles_layer(form, h2, weights, e_gate, e_up, e_down, held, dest, src,
                     seg_end)
    return y, (h2, weights, e_gate, e_up, e_down, held, dest, src, seg_end)


def _tiles_layer_bwd(form, res, dy):
    """Back over the sorted tiles.  A row's outcome is ``w * swiglu(x)``:
    ``dy`` is gathered to the rows as ``x`` was (``src``), the tiles give the
    rows' gradient, each row's ``<swiglu(x), dy>`` and every held expert's
    weight gradient over its own tiles, and the tokens' gradient is gathered
    from the rows as the outcome was (``dest``): no scatter of rows."""
    tiles, dt, tile = form
    h2, weights, e_gate, e_up, e_down, held, dest, src, seg_end = res
    n, d = h2.shape
    k = weights.shape[1]
    rows = src.shape[0]
    at = jnp.minimum(dest, rows - 1).reshape(n, k)
    with jax.named_scope("moe.experts"):
        pad = jnp.zeros((1, d), dt)
        x_rows = jnp.concatenate([h2, pad])[src]
        dy_rows = jnp.concatenate([dy.astype(dt), pad])[src]
        w_rows = jnp.zeros((rows,), jnp.float32).at[dest].set(
            weights.reshape(n * k).astype(jnp.float32), mode="drop")
        dx_rows, s_rows, d_gate, d_up, d_down = _TILES_GRAD[tiles](
            dt, e_gate, e_up, e_down, x_rows, dy_rows, w_rows[:, None],
            seg_end, tile)
        dh2 = jnp.sum(jnp.where(held[..., None], dx_rows[at], 0).astype(
            jnp.float32), axis=1).astype(h2.dtype)
        dw = jnp.where(held, s_rows[:, 0][at], 0.0).astype(weights.dtype)
    return (dh2, dw, d_gate.astype(e_gate.dtype), d_up.astype(e_up.dtype),
            d_down.astype(e_down.dtype), None, None, None, None)


_tiles_layer.defvjp(_tiles_layer_fwd, _tiles_layer_bwd)


def _tile_experts(seg_end, n_tiles: int, tile: int, e: int):
    """The expert of every tile, searched once for all tiles: every tile
    against every segment's end at once (a search by halving is a loop of
    its own on the device)."""
    first_row = jnp.arange(n_tiles, dtype=jnp.int32) * tile
    return jnp.minimum(jnp.searchsorted(
        seg_end, first_row, side="right", method="compare_all"),
        e - 1).astype(jnp.int32)


def _tiles_grouped(dt, e_gate, e_up, e_down, x_rows, seg_end, tile: int):
    """Every tile in use through its expert in one kernel call
    (:func:`grouped_experts.grouped_swiglu`): the tile -> expert map is
    searched once, here, for all tiles; the rows of the tiles past the last
    in use are not written, and no choice points at them."""
    tile_expert = _tile_experts(seg_end, x_rows.shape[0] // tile, tile,
                                e_gate.shape[0])
    return grouped_experts.grouped_swiglu(
        x_rows, tile_expert, seg_end[-1] // tile, e_gate, e_up, e_down,
        tile=tile, dtype=dt)


def _tiles_grouped_grad(dt, e_gate, e_up, e_down, x_rows, dy_rows, w_rows,
                        seg_end, tile: int):
    """:func:`_tiles_grouped`'s derivative, two kernel calls over the same
    tiles (:func:`grouped_experts.grouped_swiglu_grad`)."""
    tile_expert = _tile_experts(seg_end, x_rows.shape[0] // tile, tile,
                                e_gate.shape[0])
    tiles_of = jnp.diff(seg_end, prepend=0) // tile
    return grouped_experts.grouped_swiglu_grad(
        x_rows, dy_rows, w_rows, tile_expert, seg_end[-1] // tile,
        tiles_of.astype(jnp.int32), e_gate, e_up, e_down, tile=tile, dtype=dt)


def _tiles_looped(dt, e_gate, e_up, e_down, x_rows, seg_end, tile: int):
    """The plain form, for widths that are not whole lanes (the toy
    configurations of the CPU tests): a loop over the tiles in use, one
    expert's matrices sliced out a trip."""

    def one_tile(i, y):
        j = jnp.searchsorted(seg_end, i * tile, side="right")
        x = lax.dynamic_slice_in_dim(x_rows, i * tile, tile)
        out = _swiglu(x, e_gate[j], e_up[j], e_down[j], dt)
        return lax.dynamic_update_slice_in_dim(y, out, i * tile, axis=0)

    return lax.fori_loop(0, seg_end[-1] // tile, one_tile,
                         jnp.zeros(x_rows.shape, dt))


def _tiles_looped_grad(dt, e_gate, e_up, e_down, x_rows, dy_rows, w_rows,
                       seg_end, tile: int):
    """:func:`_tiles_looped`'s derivative in the same plain form: a trip a
    tile in use, ``_swiglu``'s own transpose, the expert's gradients added in
    float32 where they lie."""
    f32 = jnp.float32

    def one_tile(i, carry):
        dx, s, dg, du, dd = carry
        j = jnp.searchsorted(seg_end, i * tile, side="right")
        x = lax.dynamic_slice_in_dim(x_rows, i * tile, tile)
        dy = lax.dynamic_slice_in_dim(dy_rows, i * tile, tile)
        w = lax.dynamic_slice_in_dim(w_rows, i * tile, tile)
        out, back = jax.vjp(lambda x, a, b, c: _swiglu(x, a, b, c, dt),
                            x, e_gate[j], e_up[j], e_down[j])
        gx, ga, gb, gc = back((dy.astype(f32) * w).astype(out.dtype))
        put = functools.partial(lax.dynamic_update_slice_in_dim, axis=0)
        return (put(dx, gx.astype(dt), i * tile),
                put(s, jnp.sum(out.astype(f32) * dy.astype(f32), axis=1,
                               keepdims=True), i * tile),
                dg.at[j].add(ga.astype(f32)), du.at[j].add(gb.astype(f32)),
                dd.at[j].add(gc.astype(f32)))

    return lax.fori_loop(
        0, seg_end[-1] // tile, one_tile,
        (jnp.zeros(x_rows.shape, dt), jnp.zeros(w_rows.shape, f32),
         jnp.zeros(e_gate.shape, f32), jnp.zeros(e_up.shape, f32),
         jnp.zeros(e_down.shape, f32)))


#: each form of the tiles' product with its derivative
_TILES_GRAD = {_tiles_grouped: _tiles_grouped_grad,
               _tiles_looped: _tiles_looped_grad}


def _expert_layer(cfg, lp, h, valid):
    b, t, d = h.shape
    h2 = h.reshape(b * t, d)
    y, load = held_experts(cfg, lp, h2, valid.reshape(b * t))
    if cfg.n_shared:
        with jax.named_scope("moe.shared"):
            y = y + _swiglu(h2, lp["s_gate"], lp["s_up"], lp["s_down"],
                            cfg.dtype)
    return y.reshape(b, t, d), load


def _add_stats(stats: jax.Array, add: jax.Array, touched) -> jax.Array:
    """``stats + add`` with the carry from the low words to the high, and the
    touched gauge set (not summed) where a program gives one."""
    lo = stats[1] + add
    hi = stats[0] + (lo >> _LO_BITS)
    lo = lo & ((1 << _LO_BITS) - 1)
    if touched is not None:
        lo = lo.at[TOUCHED].set(touched)
    return jnp.stack([hi, lo])


def _forward_paged(params, tokens, cfg: LatentMoEConfig,
                   pcache: LatentPagedCache, qpos, table, valid,
                   set_touched: bool):
    """The shared body of the paged programs: ``tokens`` [B, T] at positions
    ``qpos`` under block tables ``table`` [B, per]; ``valid`` [B, T] marks the
    tokens that count (for the counters and the routing)."""
    dt = cfg.dtype
    bs = pcache.block_size
    n_blocks = pcache.latent.shape[1]
    stripe = n_blocks * bs
    wflat = _phys(table, qpos, bs)                               # [B, T]
    flat = lambda a: a.reshape(a.shape[0] * stripe, a.shape[-1])  # noqa: E731
    latent_f, index_f, window_f = (flat(pcache.latent), flat(pcache.index),
                                   flat(pcache.window))
    x = params["embed"][tokens].astype(dt)
    n_full = n_window = 0
    load = jnp.zeros((cfg.held_count,), jnp.int32)
    touched = batched = jnp.int32(0)
    for i, (kind, lp) in enumerate(zip(cfg.layer_kinds, params["layers"])):
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        if kind == FULL:
            with jax.named_scope("mla.full"):
                o, latent_f, index_f = _full_attention(
                    cfg, lp, h, qpos, table, bs, latent_f, index_f,
                    n_full * stripe, wflat)
            n_full += 1
        else:
            with jax.named_scope("mla.window"):
                o, window_f = _window_attention(
                    cfg, lp, h, qpos, table, bs, window_f,
                    n_window * stripe, wflat)
            n_window += 1
        x = x + o
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        if i < cfg.first_dense:
            x = x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], dt)
        else:
            y, layer_load = _expert_layer(cfg, lp, h, valid)
            x = x + y
            load = load + layer_load
            touched = touched + jnp.sum(layer_load > 0, dtype=jnp.int32)
            batched = batched + layers_batched(tokens.size, layer_load)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _dot(x, params["lm_head"], dt).astype(jnp.float32)
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    seen = jnp.sum(jnp.where(valid, qpos + 1, 0), dtype=jnp.int32)
    chosen = jnp.sum(jnp.where(valid, jnp.minimum(qpos + 1, cfg.index_topk),
                               0), dtype=jnp.int32)
    n_moe = cfg.n_layers - cfg.first_dense
    add = jnp.concatenate([
        jnp.stack([n_valid * (cfg.top_k * n_moe), jnp.sum(load),
                   seen * n_full, chosen * n_full, jnp.int32(0)]), load,
        batched[None]])
    stats = _add_stats(pcache.stats, add, touched if set_touched else None)
    return logits, pcache._replace(
        latent=latent_f.reshape(pcache.latent.shape),
        index=index_f.reshape(pcache.index.shape),
        window=window_f.reshape(pcache.window.shape), stats=stats)


# ---------------------------------------------------------------------------
# the engine's interface (the signatures of models/llama.py)
# ---------------------------------------------------------------------------

def decode_chunk_paged(
    params: dict, tokens: jax.Array, cfg: LatentMoEConfig,
    pcache: LatentPagedCache, *, advance: jax.Array | None = None,
    counted: jax.Array | None = None,
) -> tuple[jax.Array, LatentPagedCache]:
    """T tokens per row against the pools (the tick, and the verify round's
    wide tick).  ``advance`` [B] gates the length advance as in
    :func:`llama.decode_chunk_paged`; ``counted`` [B] says which rows' tokens
    are real (default: the rows that advance)."""
    b, t = tokens.shape
    pos = pcache.length
    qpos = pos[:, None] + jnp.arange(t)[None, :]
    adv = (jnp.full((b,), t, jnp.int32) if advance is None
           else jnp.asarray(advance, jnp.int32))
    live = adv > 0 if counted is None else jnp.asarray(counted) > 0
    valid = jnp.broadcast_to(live[:, None], (b, t))
    logits, pcache = _forward_paged(params, tokens, cfg, pcache, qpos,
                                    pcache.block_table, valid, True)
    return logits, pcache._replace(length=pos + adv)


def decode_chunk_paged_row(
    params: dict, tokens: jax.Array, cfg: LatentMoEConfig,
    pcache: LatentPagedCache, slot: jax.Array, *, new_length: jax.Array,
) -> tuple[jax.Array, LatentPagedCache]:
    """One row's T-token chunk (chunked prefill): ``tokens`` [1, T] continue
    slot ``slot`` from its length, which becomes ``new_length``; positions
    past it are padding and count for nothing."""
    b, t = tokens.shape
    if b != 1:
        raise ValueError(f"decode_chunk_paged_row is a B=1 program, "
                         f"got batch {b}")
    slot = jnp.asarray(slot, jnp.int32)
    new_length = jnp.asarray(new_length, jnp.int32)
    pos = pcache.length[slot]
    qpos = (pos + jnp.arange(t))[None, :]
    logits, pcache = _forward_paged(
        params, tokens, cfg, pcache, qpos, pcache.block_table[slot][None],
        qpos < new_length, False)
    return logits, pcache._replace(
        length=pcache.length.at[slot].set(new_length))


def spec_verify_paged(params, cfg, pcache, last_logits, drafts, active):
    """:func:`llama.spec_verify_paged` over this model's tick: the round is
    generic, and lengths alone roll back here too (what a rejected position
    wrote to the three pools lies past the row's length)."""
    return llama.spec_verify_paged(
        params, cfg, pcache, last_logits, drafts, active,
        decode=partial(decode_chunk_paged, counted=active))


def forward(params: dict, tokens: jax.Array,
            cfg: LatentMoEConfig) -> jax.Array:
    """Logits [B, L, V] of whole sequences with no cache kept: every row
    through one chunk of a cache made for the call and thrown away."""
    b, l = tokens.shape
    pcache = init_paged_cache(cfg, b, l, block_size=l)
    pcache = pcache._replace(
        block_table=1 + jnp.arange(b, dtype=jnp.int32)[:, None])
    return decode_chunk_paged(params, tokens, cfg, pcache)[0]


def generate(params: dict, cfg: LatentMoEConfig, prompt: list,
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
