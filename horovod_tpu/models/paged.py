"""The paged model interface: what :class:`~horovod_tpu.serving_scheduler.
ServeEngine` asks of a model, and which module answers for a config.

The engine names no model.  A model is a module with these functions, which
:mod:`horovod_tpu.models.llama` has as it stands and
:mod:`horovod_tpu.models.latent_moe` and
:mod:`horovod_tpu.models.shortconv_moe` implement:

* ``init_paged_cache(cfg, n_slots, max_len, *, block_size, n_blocks)`` — the
  paged state: a NamedTuple of device arrays with ``block_table``
  ``[n_slots, blocks_per_slot]`` and ``length`` ``[n_slots]`` among them, in
  which a block id means the same block of every pool (the first array of
  more than two dimensions is a pool, ``[layers, n_blocks, ...]``);
* ``decode_chunk_paged(params, tokens, cfg, pcache, *, advance)`` — the tick
  (``tokens`` [B, T], lengths advance by ``advance`` [B]);
* ``decode_chunk_paged_row(params, tokens, cfg, pcache, slot, *,
  new_length)`` — one row's chunk of prefill;
* ``spec_verify_paged(params, cfg, pcache, last_logits, drafts, active)`` —
  the speculative verify round, which leaves the cache as after each row's
  ``1 + accepted`` tokens: for state that is per position the lengths alone
  roll back (what a rejected position wrote lies past the length), a
  recurrent state is the model's to pick;
* ``paged_pool_bytes(pcache)`` — device bytes of one block, per pool;
* ``param_partition_specs(cfg, tp_axis=)``,
  ``paged_cache_partition_specs(tp_axis=)``, ``tp_split_dims(cfg)`` — the
  tensor-parallel layout (a model may raise ``NotImplementedError``);
* ``paged_counters(pcache)`` — a small device array of counters the engine
  reads back beside the tick's tokens, or ``None``;
* ``publish_paged_metrics(metrics, cfg, pcache, stats_host, row_blocks,
  programs)`` — the model's own gauges and counters into the engine's
  registry: at construction, and after every step that dispatched a program
  (``programs``: rows, tokens a row and the longest row's length of each).

**State of a second kind.**  The pools hold state that is per position and
immutable once written, which is why a block can be shared, cached and
replayed into by table writes alone.  A model may keep, beside them, state
that is *per sequence*: a fixed-size recurrent state a slot, carried by the
tick and by a row's prefill chunks (``shortconv_moe``'s convolution inputs).
Such a model gives the interface one more, optional function:

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
0 restores the slot's state from the block that ends there.
"""

from __future__ import annotations

from types import ModuleType
from typing import Any


def paged_model(cfg: Any) -> ModuleType:
    """The model module for a config object, by the config's type."""
    from horovod_tpu.models import latent_moe, llama, shortconv_moe

    if isinstance(cfg, llama.LlamaConfig):
        return llama
    if isinstance(cfg, latent_moe.LatentMoEConfig):
        return latent_moe
    if isinstance(cfg, shortconv_moe.ShortConvMoEConfig):
        return shortconv_moe
    raise TypeError(
        f"ServeEngine serves a LlamaConfig, a LatentMoEConfig or a "
        f"ShortConvMoEConfig, not a {type(cfg).__name__}")
