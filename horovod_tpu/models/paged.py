"""The paged model interface: what :class:`~horovod_tpu.serving_scheduler.
ServeEngine` asks of a model, and which module answers for a config.

The engine names no model.  A model is a module with these functions, which
:mod:`horovod_tpu.models.llama` has as it stands and
:mod:`horovod_tpu.models.latent_moe` implements:

* ``init_paged_cache(cfg, n_slots, max_len, *, block_size, n_blocks)`` — the
  paged state: a NamedTuple of device arrays with ``block_table``
  ``[n_slots, blocks_per_slot]`` and ``length`` ``[n_slots]`` among them, in
  which a block id means the same block of every pool;
* ``decode_chunk_paged(params, tokens, cfg, pcache, *, advance)`` — the tick
  (``tokens`` [B, T], lengths advance by ``advance`` [B]);
* ``decode_chunk_paged_row(params, tokens, cfg, pcache, slot, *,
  new_length)`` — one row's chunk of prefill;
* ``spec_verify_paged(params, cfg, pcache, last_logits, drafts, active)`` —
  the speculative verify round (lengths alone roll back);
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
"""

from __future__ import annotations

from types import ModuleType
from typing import Any


def paged_model(cfg: Any) -> ModuleType:
    """The model module for a config object, by the config's type."""
    from horovod_tpu.models import latent_moe, llama

    if isinstance(cfg, llama.LlamaConfig):
        return llama
    if isinstance(cfg, latent_moe.LatentMoEConfig):
        return latent_moe
    raise TypeError(
        f"ServeEngine serves a LlamaConfig or a LatentMoEConfig, not a "
        f"{type(cfg).__name__}")
