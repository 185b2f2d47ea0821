"""The paged model interface: what :class:`~horovod_tpu.serving_scheduler.
ServeEngine` asks of a model, and which module answers for a config.

The engine names no model.  A model is a module with these functions, which
:mod:`horovod_tpu.models.llama` has as it stands and
:mod:`horovod_tpu.models.latent_moe`, :mod:`horovod_tpu.models.shortconv_moe`,
:mod:`horovod_tpu.models.window_moe`, :mod:`horovod_tpu.models.state_space_moe`
and :mod:`horovod_tpu.models.block_diffusion_moe` implement:

* ``init_paged_cache(cfg, n_slots, max_len, *, block_size, n_blocks)`` — the
  paged state: a NamedTuple of device arrays with ``block_table``
  ``[n_slots, blocks_per_slot]`` and ``length`` ``[n_slots]`` among them, in
  which a block id means the same block of every pool (the first array of
  more than two dimensions is a pool, ``[layers, n_blocks, ...]``);
* ``decode_chunk_paged(params, tokens, cfg, pcache, *, advance)`` — the tick
  (``tokens`` [B, T], lengths advance by ``advance`` [B]);
* ``decode_chunk_paged_row(params, tokens, cfg, pcache, slot, *,
  new_length)`` — one row's chunk of prefill, ``tokens`` [1, T], with the
  logits of every position;
* optionally ``decode_chunk_paged_rows(params, tokens, cfg, pcache, slots,
  *, new_length, sel)`` — a chunk of prefill for R rows in one program, one
  read of the weights for all of them: ``tokens`` [R, T] continue the slots
  ``slots`` [R] to ``new_length`` [R]; returns the cache and the logits of
  each row's position ``sel`` [R] alone, [R, V], picked before the final
  norm and the head (of every position, [R, T, V], with ``sel`` ``None``).
  A row whose slot is ``n_slots`` is not there and writes nothing
  (:func:`chunk_rows`).  Where a model has it the engine dispatches the rows that
  prefill in a step in whole groups of one wide width and the rest a row a
  program (``ServeEngine.chunk_widths``), and one row a program where not;
* ``spec_verify_paged(params, cfg, pcache, last_logits, drafts, active)`` —
  the speculative verify round, which leaves the cache as after each row's
  ``1 + accepted`` tokens: for state that is per position the lengths alone
  roll back (what a rejected position wrote lies past the length), a
  recurrent state is the model's to pick;
* ``paged_pool_bytes(pcache)`` — device bytes of one block, per pool;
* ``param_partition_specs(cfg, tp_axis=)``,
  ``paged_cache_partition_specs(tp_axis=)``, ``tp_split_dims(cfg)`` — the
  tensor-parallel layout (a model may raise ``NotImplementedError``);
* optionally ``serving_params(params, cfg, *, tp_size)`` — the **serving
  tree**: the tree as the model's paged programs read it, made once from the
  tree its ``init_params`` describes, where the layout that trains and loads
  checkpoints is not the one the device reads fastest (``llama``: ``wq`` /
  ``wk`` / ``wv`` side by side in one ``wqkv``, a shard's columns together,
  so that a layer reads them inside one product).  The engine calls it at
  construction, before the pool is allocated, keeps only what it returns
  and hands only that to the functions above; ``serving_partition_specs(cfg,
  tp_axis=)`` is then ``param_partition_specs`` of that tree.  It leaves the
  caller's tree as it is, shares every leaf it does not lay out anew, and
  returns a tree that is already a serving tree as it is (an engine's tree
  handed to its clone).  While the caller still holds the public tree, what
  was laid out anew is held twice (gauge ``serve.params_relaid_bytes``).
  Without the function a model is served from the tree it was given.
  :func:`serving_tree` is either, with the bytes written anew;
* ``paged_counters(pcache)`` — a small device array of counters the engine
  reads back beside the tick's tokens, or ``None``;
* ``publish_paged_metrics(metrics, cfg, pcache, stats_host, row_blocks,
  programs)`` — the model's own gauges and counters into the engine's
  registry: at construction, and after every step that dispatched a program
  (``programs``: a :class:`Dispatched` each).

**A step that yields a block a row.**  A model that generates by diffusion
over blocks (``block_diffusion_moe``) decodes, in place of one token a row a
tick, a *block* of ``B`` positions a row, some of which are still the mask id:
a tick denoises the block, a small program unmasks the most confident
positions, and only a block with no mask left is committed.  Such a model
answers three more entries, and the engine then runs its block loop
(``docs/inference.md``, "Serving a model that generates by diffusion over
blocks") in place of ``decode_chunk_paged`` / ``spec_verify_paged``, which it
need not have:

* ``block_length(cfg)`` — ``B``, and the engine's sign that a row decodes a
  block.  A row's length is then a whole number of blocks: a prompt's
  trailing ``L mod B`` tokens are not prefilled but given as the first
  positions of the first generated block;
* ``decode_block_paged(params, block_tokens, cfg, pcache, *, active,
  commit)`` — the block tick: ``block_tokens`` [n_slots, B] stand at
  ``[length, length + B)`` of every row under the block-causal mask
  (:func:`llama.tile_walk`'s ``span``), their keys are written *past the
  length* and rewritten every tick, ``length += B`` where ``commit``
  [n_slots]; returns the block's logits ``[n_slots, B, V]``;
* ``unmask(cfg, logits, block_tokens, step)`` — the sampler's rule on the
  device, the program in ``_sample``'s place: ``(block_tokens, left,
  by_threshold)``, the new ids, how many are still masked and how many the
  confidence threshold (and not the schedule) unmasked, so that the host reads
  ``[n_slots, B]`` ids and two small vectors a step and never logits.

Its config holds ``mask_token_id``, which the engine fills a fresh block
with and tells a masked position by.  A prefill chunk takes the same mask, so
the engine refuses a chunk or a page that is not a whole number of blocks.

**State of a second kind.**  The pools hold state that is per position and
immutable once written, which is why a block can be shared, cached and
replayed into by table writes alone.  A model may keep, beside them, state
that is *per sequence*: a fixed-size recurrent state a slot, carried by the
tick and by a row's prefill chunks (``shortconv_moe``'s convolution inputs;
``window_moe``'s ring of the last ``window`` keys and values of its sliding
layers, which need nothing older; ``state_space_moe``'s recurrent state of its
state-space layers).  Such a model gives the interface one more, optional
function:

* ``set_row(pcache, slot, row, length)`` — the whole of the engine's table
  write (``block_table[slot] = row``, ``length[slot] = length``) and what the
  slot's own state is at ``length``, which is 0 or a whole number of blocks
  (a prefix hit's frontier).  ``ServeEngine._set_row`` calls it inside its
  one program where the model has it: no signature changes.

The rule that keeps the prefix cache, release to cache at retirement,
preemption with replay and a cloned engine ignorant of that state is the
**snapshot rule**: the model keeps, per physical block and under the block's
id, the state at the block's last position; whichever program's counted
tokens reach a block's last position writes it; ``set_row`` at a length past
0 restores the slot's state from the block that ends there.  What the rule's
users share is here: :func:`block_before` (whose snapshot a row mapped at a
length takes), :func:`block_ends` (the blocks a program's counted tokens
fill), and of their counters :func:`read_stats` (the device's ``stats`` as
Python ints), :func:`count_from_device` (device-side sums under counters of a
registry that may outlive the engine) and :func:`publish_state_metrics` (what
they publish alike).

**The snapshot budget.**  A snapshot a block is affordable while a state is
smaller than a block (``shortconv_moe``: 90 KB beside 1.5 MB;  ``window_moe``:
3.15 MB beside 8.39 MB).  A model whose state is larger than a block
(``state_space_moe``: 38 MB beside 4 MB) keeps the rule under a **budget**
instead, and says so by a second optional function:

* ``snapshot_budget(cfg, pcache, metrics)`` — a :class:`SnapshotBudget` over
  the ``n`` entries the model's cache holds.  The engine then owns which
  block holds which entry, and ``set_row`` gains one argument: ``set_row(
  pcache, slot, row, length, snaps)``, ``snaps`` [blocks_per_slot] the entry
  of each block of ``row`` (``n`` for none).  The model restores the slot's
  state from the entry of the block that ends at ``length`` and writes a
  snapshot at a block's end only into the entry that block was given.

The budget's rule: entries are **granted where a snapshot is known to be
wanted** — at the deepest block the radix index matched for an admitted
prompt and at the prompt's last full block — and nowhere else; a prefix hit
is **rounded down** to the deepest matched block that holds an entry (the
blocks matched beyond it are released and recomputed); entries and blocks are
**evicted apart** (the least recently restored entry goes when none is free,
those never restored first, and its block stays indexed; a block that is
freed gives its entry up); and at
``insert`` an entry **follows the block that stays**.
"""

from __future__ import annotations

from types import ModuleType
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def paged_model(cfg: Any) -> ModuleType:
    """The model module for a config object, by the config's type."""
    from horovod_tpu.models import (block_diffusion_moe, latent_moe, llama,
                                    shortconv_moe, state_space_moe,
                                    window_moe)

    if isinstance(cfg, llama.LlamaConfig):
        return llama
    if isinstance(cfg, latent_moe.LatentMoEConfig):
        return latent_moe
    if isinstance(cfg, shortconv_moe.ShortConvMoEConfig):
        return shortconv_moe
    if isinstance(cfg, window_moe.WindowMoEConfig):
        return window_moe
    if isinstance(cfg, state_space_moe.StateSpaceMoEConfig):
        return state_space_moe
    if isinstance(cfg, block_diffusion_moe.BlockDiffusionMoEConfig):
        return block_diffusion_moe
    raise TypeError(
        f"ServeEngine serves a LlamaConfig, a LatentMoEConfig, a "
        f"ShortConvMoEConfig, a WindowMoEConfig, a StateSpaceMoEConfig or a "
        f"BlockDiffusionMoEConfig, not a {type(cfg).__name__}")


def serving_tree(model: ModuleType, params: Any, cfg: Any, *,
                 tp_size: int) -> tuple:
    """``(tree, relaid_bytes)``: ``params`` as ``model``'s paged programs
    read it (its ``serving_params``' tree; ``params`` itself where the model
    has no such function) and the bytes of the leaves written anew for it,
    which are held twice while the caller still holds ``params``."""
    relay = getattr(model, "serving_params", None)
    if relay is None:
        return params, 0
    given = {id(x) for x in jax.tree.leaves(params)}
    tree = relay(params, cfg, tp_size=tp_size)
    return tree, sum(x.nbytes for x in jax.tree.leaves(tree)
                     if id(x) not in given)


class Dispatched(NamedTuple):
    """One program of a step as the host knows it from its slots, for the
    counters a model reckons without a read-back: ``t`` tokens a row, the
    ``lengths`` [B] its rows held before it, and ``active`` [B], 1 where the
    row's output is read (a chunk's one row; a tick's decoding rows)."""

    t: int
    lengths: Any                # a sequence or an array of ints
    active: Any

    @property
    def rows(self) -> int:
        return len(self.lengths)

    @property
    def longest(self) -> int:
        return int(max(self.lengths))


class SnapshotBudget:
    """Host-side owner of a model's ``n`` snapshot entries and of the map
    block -> entry (module docstring, *The snapshot budget*).  Policy-free
    about *where* an entry is wanted (the engine asks); it tracks states:

    * **free** — on the free list;
    * **pending** — granted to a block whose end a live row's prefill has
      yet to reach: the device will write it, so it is neither restorable
      nor evictable until :meth:`commit` (the write is dispatched) or
      :meth:`cancel` (the row left first).  If the block is freed meanwhile
      the entry stays pending with no block and goes free at its commit;
    * **held** — a block's snapshot, restorable.  :meth:`grant` evicts, when
      none is free, the least recently restored: first, oldest first, the
      entries no row was ever restored from (a prompt's last block is asked
      for by every request and restored from by few: these must not push
      out the ones that are), then the others by their last
      :meth:`touch`.

    ``evicted`` (a counter) and ``live`` (a gauge: entries held) are the
    model's own metrics, or ``None``."""

    def __init__(self, n: int, evicted=None, live=None):
        if n < 1:
            raise ValueError(f"a snapshot budget of {n} entries")
        self.n = n
        self._free = list(range(n - 1, -1, -1))     # pop() takes low ids first
        self._held: dict[int, int] = {}             # block -> entry
        self._cold: dict[int, None] = {}            # never restored, by commit
        self._hot: dict[int, None] = {}             # by last restore
        self._pending: dict[int, int | None] = {}   # entry -> block (or None)
        self._evicted, self._live = evicted, live

    @property
    def none(self) -> int:
        """What a row carries for a block without an entry: past the pool."""
        return self.n

    def entry(self, block: int) -> int | None:
        """The entry that holds ``block``'s snapshot, if one does."""
        return self._held.get(block)

    def wanted(self, block: int) -> bool:
        """Whether ``block`` holds an entry or is about to."""
        return block in self._held or self.pending(block)

    def pending(self, block: int) -> bool:
        """Whether an entry is granted to ``block`` and not yet committed."""
        return block in self._pending.values()

    def pending_block(self, entry: int) -> int | None:
        """The block pending ``entry`` is to be held by (``None``: it was
        freed meanwhile, or the entry is not pending)."""
        return self._pending.get(entry)

    def held_count(self) -> int:
        return len(self._held)

    def pending_count(self) -> int:
        return len(self._pending)

    def grant(self, block: int, *, on_evidence: bool = True) -> int | None:
        """An entry for ``block``, pending: a free one, else the least
        recently restored held one (its block loses it); ``None`` where
        every entry is pending.  Asked for without evidence that the
        snapshot will be restored from (``on_evidence`` false: a prompt's
        own end, not a prefix another request was seen to share), it takes
        no entry that has been."""
        if self._free:
            e = self._free.pop()
        elif self._cold or (self._hot and on_evidence):
            e = self._unhold(next(iter(self._cold or self._hot)))
            if self._evicted is not None:
                self._evicted.inc()
        else:
            return None
        self._pending[e] = block
        self._gauge()
        return e

    def commit(self, entry: int) -> None:
        """The write of pending ``entry`` is dispatched: its block holds it
        from here on (the device runs programs in order), or it goes free
        where the block was freed meanwhile."""
        block = self._pending.pop(entry)
        if block is None:
            self._free.append(entry)
        else:
            if block in self._held:             # the same tokens' state:
                self._free.append(self._unhold(block))      # alike; keep one
            self._held[block] = entry
            self._cold[block] = None
        self._gauge()

    def cancel(self, entry: int) -> None:
        """The row that would have written pending ``entry`` left first."""
        if self._pending.pop(entry, -1) != -1:
            self._free.append(entry)

    def touch(self, block: int) -> None:
        """``block``'s entry was restored: most recently used."""
        self._cold.pop(block, None)
        self._hot.pop(block, None)
        self._hot[block] = None

    def drop(self, block: int) -> None:
        """``block`` was freed (it left the index, or its row did): its
        entry is free; one pending for it goes free at its commit."""
        if block in self._held:
            self._free.append(self._unhold(block))
            self._gauge()
        for p, b in self._pending.items():
            if b == block:
                self._pending[p] = None

    def move(self, src: int, dst: int) -> None:
        """The entry of ``src`` follows ``dst``, the block that stays for
        the same tokens, unless ``dst`` holds one already."""
        if src in self._held and dst not in self._held:
            order = self._hot if src in self._hot else self._cold
            self._held[dst] = self._unhold(src)
            order[dst] = None

    def _unhold(self, block: int) -> int:
        self._cold.pop(block, None)
        self._hot.pop(block, None)
        return self._held.pop(block)

    def _gauge(self) -> None:
        if self._live is not None:
            self._live.set(len(self._held))

    def check_consistency(self) -> None:
        """Every entry is in exactly one state, every held block in exactly
        one order."""
        seen = sorted([*self._free, *self._held.values(), *self._pending])
        if seen != list(range(self.n)) or sorted(self._held) != sorted(
                [*self._cold, *self._hot]):
            raise AssertionError(
                f"snapshot entries out of sync: free={self._free} "
                f"held={self._held} cold={list(self._cold)} "
                f"hot={list(self._hot)} pending={self._pending}")

    def state_lines(self) -> list[str]:
        return [f"snapshot budget: free={len(self._free)} "
                f"held={len(self._held)} pending={len(self._pending)} "
                f"of {self.n}; never restored (old->new)={list(self._cold)} "
                f"restored (old->new)={list(self._hot)}"]


def chunk_rows(pcache: Any, slots, t: int) -> tuple:
    """What a chunk program of ``t`` tokens a row reads of its rows ``slots``
    [R]: ``(there, at, pos, qpos, table)``.  ``there`` [R] is false for a row
    whose slot is past the slots (``n_slots``): such a row is not there, reads
    the last slot's row at ``at`` and must write nothing.  ``pos`` [R] the
    rows' lengths, ``qpos`` [R, t] their tokens' positions, ``table`` [R,
    per] their block tables."""
    n_slots = pcache.length.shape[0]
    at = jnp.minimum(slots, n_slots - 1)
    pos = pcache.length[at]
    return (slots < n_slots, at, pos, pos[:, None] + jnp.arange(t)[None, :],
            pcache.block_table[at])


def block_before(row, length, bs: int):
    """The block of table ``row`` that ends at ``length`` (a whole number of
    blocks): the one whose snapshot a row mapped there takes.  At 0 the
    table's first, which the caller does not use."""
    return row[jnp.maximum(length // bs - 1, 0)]


def block_ends(pos, n, t: int, table, bs: int, n_blocks: int) -> tuple:
    """The block ends a program of ``t`` tokens a row can reach, at most
    ``ceil(t / bs)`` a row: ``(j, reached, dest)``, each [B, ends].  ``j`` is
    the end's place among the row's tokens (its rows started at ``pos`` [B]
    under ``table``), ``reached`` whether it is among the ``n`` [B] that
    count, ``dest`` the physical block it fills, ``n_blocks`` (past the pool:
    a scatter drops it) where it is not reached."""
    per = table.shape[1]
    first_end = (pos // bs + 1) * bs - 1                         # [B]
    end_pos = first_end[:, None] + bs * jnp.arange(-(-t // bs))[None, :]
    j = end_pos - pos[:, None]
    reached = j < n[:, None]
    blk = jnp.take_along_axis(table, jnp.clip(end_pos // bs, 0, per - 1),
                              axis=1)
    return j, reached, jnp.where(reached, blk, n_blocks)


def count_from_device(counted: tuple, totals: dict) -> None:
    """Move each registry counter by what the device's running sum gained
    since it was last read.  ``counted`` holds ``(counter, gauge, key)``: the
    gauge (``<name>.device``) keeps this engine's device total as last read,
    so a registry that outlives an engine (``supervisor.clone_engine``, whose
    clone counts from zero) keeps counting; ``totals[key]`` is the total
    now."""
    for counter, read, key in counted:
        counter.inc(totals[key] - int(read.value))
        read.set(totals[key])


def read_stats(stats_host, head: tuple, tail: tuple) -> dict:
    """A ``stats`` array of :mod:`latent_moe`'s layout as Python ints (sums
    exact past 2**31): the running sums that ``head`` names from column 0 on
    and ``tail`` from the end back, the touched gauge (``experts_touched``)
    and, between the two, the held experts' load (``held_load``)."""
    from horovod_tpu.models import latent_moe

    s = np.asarray(stats_host).astype(np.int64)
    total = (s[0] << latent_moe._LO_BITS) + s[1]
    cut = len(total) - len(tail)
    out = {name: int(x) for name, x in zip(head, total)}
    out["experts_touched"] = int(s[1, latent_moe.TOUCHED])
    out["held_load"] = [int(x) for x in total[latent_moe.LOAD0:cut]]
    out.update((name, int(x)) for name, x in zip(tail, total[cut:]))
    return out


def publish_state_metrics(metrics, cfg: Any, pcache: Any, stats_host,
                          programs: tuple, *, per_block: dict,
                          slot_bytes: int, counted: tuple,
                          read, walk_split: int = 1) -> dict | None:
    """What the snapshot rule's users publish alike, for their
    ``publish_paged_metrics``.  At construction (no ``stats_host``, no
    ``programs``) what a cached token, a block's snapshot and a slot's state
    hold, and the ``.device`` gauges of ``counted`` at zero (a registry may
    outlive an engine, ``supervisor.clone_engine``: this engine's device
    counts from zero); after a step the share of the tables attention walked
    (``attn.blocks_*``, as :mod:`llama` counts them from ``programs`` and
    ``walk_split``) and
    ``moe.choices_in_place`` and ``moe.choices_grouped``
    (:func:`latent_moe.count_choices_by_form` of the step's programs, not
    read back); where a tick's readback brought
    ``stats_host``, the device's counters (``read(stats_host)``, the
    model's ``read_counters``) under ``counted``, the experts touched and
    each held expert's load.  Returns the counters read, ``None`` where no
    tick ran."""
    from horovod_tpu.models import latent_moe, llama

    if stats_host is None and not programs:     # once, at construction
        metrics.gauge("kv.bytes_per_token").set(
            (per_block["k"] + per_block["v"]) // pcache.block_size)
        metrics.gauge("kv.snapshot_block_bytes").set(per_block["snap"])
        metrics.gauge("state.bytes_per_slot").set(slot_bytes)
        for _, device_total, _ in counted:
            device_total.set(0)
    llama.publish_paged_metrics(metrics, cfg, pcache, programs=programs,
                                walk_split=walk_split)
    latent_moe.count_choices_by_form(metrics, cfg, programs)
    if stats_host is None:          # nothing was read back: no tick ran
        return None
    c = read(stats_host)
    count_from_device(counted, c)
    metrics.gauge("moe.experts_touched").set(c["experts_touched"])
    for e, n in enumerate(c["held_load"]):
        metrics.gauge(f"moe.held_load.{cfg.held_first + e}").set(n)
    return c
