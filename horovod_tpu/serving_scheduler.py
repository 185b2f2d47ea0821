"""Continuous-batching decode engine: slot recycling over a paged KV pool.

The engine names no model: it serves any config whose module implements
the paged model interface (:mod:`horovod_tpu.models.paged`) — a
``LlamaConfig`` (described below), a ``LatentMoEConfig`` (three latent
pools behind the same block table) or a ``ShortConvMoEConfig`` (attention
pools, and beside them a recurrent state per slot with a snapshot per
block: the interface's second kind of state, ``docs/inference.md``); and a
model that generates by diffusion over blocks (a ``BlockDiffusionMoEConfig``)
through the interface's block entries: a step then denoises a block of
positions a row and commits it when it is clean (``ServeEngine.block``,
``docs/inference.md``, "Serving a model that generates by diffusion over
blocks").

:class:`~horovod_tpu.serving.ContinuousBatcher` admits into a fixed slot
pool but each admission runs its whole prefill at once and the pool's
dense cache reserves max_len per slot.  :class:`ServeEngine` is the next
step toward a production scheduler (Orca OSDI '22 / vLLM SOSP '23):

* a **request queue** feeding a slot table — a finished row's slot (and
  its cache blocks) are recycled for the next queued request on the very
  next step;
* **chunked prefill interleaved with decode**: admission runs one
  fixed-width prompt window per step, between decode ticks, so a long
  prompt never stalls in-flight rows for more than one window;
* a **paged KV cache** (:class:`~horovod_tpu.models.llama.PagedKVCache`):
  admission allocates only the blocks a request needs (host free-list),
  retirement returns them — recycling reuses memory without
  re-allocating device buffers or re-compiling anything;
* a **fixed-shape compiled tick**: every device program (`sample`,
  `tick`, `prefill chunk`, `table write`) has one jit signature for the
  life of the server — admission/retirement changes table *data*, never
  shapes, so XLA never re-traces (pinned by ``compile_cache_sizes`` in
  tests);
* **a tick left in flight**: the token a step hands out is the argmax of
  the logits the step *before* left on the device, so it comes from a
  small sampling program dispatched in front of the tick, and the step
  returns without waiting for the tick.  Postprocess, bookkeeping, the
  caller's turn and the next step's admissions and dispatches run beside
  the tick; the next step's read holds the host to one tick ahead
  (``docs/inference.md``, "The anatomy of a step").

Request lifecycle & fault tolerance (the production layer the above
schedulers treat as first-class scheduler transitions, not crashes):

* every request terminates with a typed
  :class:`~horovod_tpu.serving.RequestResult` — status ``OK / TIMEOUT /
  CANCELLED / FAILED / REJECTED`` plus tokens-so-far;
* ``cancel(rid)`` works in any state (queued, prefilling, decoding);
  per-request ``deadline_s`` (wall clock) and ``max_queue_steps``
  (step-counted admission budget → ``REJECTED``) bound waiting;
* **KV-pressure preemption with replay**: when the queue head has
  starved ``preempt_after`` consecutive steps on an overcommitted block
  pool, a decoding row is preempted — blocks freed, request re-queued
  with ``prompt + out`` as the replay prompt.  Which row is the victim
  (and in what order the queue admits) is a pluggable
  :class:`~horovod_tpu.scheduling.SchedulerPolicy` — FIFO (default,
  bit-compatible: evicts the youngest), priority, or EDF (evicts the
  slack-richest).  Greedy determinism makes the resumed output
  bit-identical to the uninterrupted run whoever is chosen, and
  everything rides the existing ``_set_row`` program so no new jit
  signatures appear;
* **poison-request quarantine**: a raising prefill window or decode-tick
  readback fails only the implicated request — transient faults get
  bounded step-counted retries with exponential backoff (decode retries
  reuse the replay path), then a ``FAILED`` result carrying the
  exception.  All other rows keep serving;
* deterministic fault injection via :mod:`horovod_tpu.faults` sites
  ``serve.admit`` / ``serve.prefill`` / ``serve.tick`` /
  ``serve.cache`` / ``serve.draft``, and a no-progress watchdog that
  raises with a full scheduler-state dump instead of spinning
  ``run()`` forever.

Shared-prefix KV reuse (``prefix_cache=True``; PagedAttention block
sharing + RadixAttention-style automatic indexing — see
:mod:`horovod_tpu.prefix_cache`):

* physical blocks become **reference-counted**
  (:class:`~horovod_tpu.models.llama.BlockPool`) and are registered in
  a radix tree keyed by their token-chunk path: a prompt's full block
  when the prefill chunk that fills it has been dispatched (its row
  still runs; what is dispatched later reads it whole), an answer's at
  a clean retirement, which **releases to cache** instead of freeing
  and parks zero-ref blocks in LRU order;
* admission does a **longest-prefix match** and maps the hit blocks
  straight into the new slot's block-table row — chunked prefill
  starts at the first uncached token (a full hit recomputes only the
  final chunk: the copy-on-write rule keeping the write-frontier block
  private, and the source of the logits that seed decoding).  A
  candidate whose prompt goes on into blocks a live row is admitted to
  write and has not is **held** for those few steps and admitted on the
  hit, so a batch handed over at once prefills a shared prefix once;
* under KV pressure, **cache evicts before rows preempt**: admission
  reclaims zero-ref LRU leaves first, and only a starved head that
  outlasts eviction triggers row preemption.  A preempted row's blocks
  release-to-cache too, so its replay re-admits through the cache and
  is nearly free;
* none of it adds device programs: cache hits change block-table
  *data*, never shapes — the same jit signatures serve, pinned by
  ``compile_cache_sizes()``, and every output stays bit-identical to
  the cache-off solo greedy run.

Self-drafting speculative decode (``spec=True`` / ``HVD_TPU_SPEC=1``;
prompt-lookup decoding in the continuous batch — see
:mod:`horovod_tpu.drafting` and
:func:`~horovod_tpu.models.llama.spec_verify_paged`):

* each decoding slot drafts up to ``draft_k`` tokens per tick from an
  incremental n-gram index over its own prompt + output — no draft
  model, no extra forward pass, pure host work (the ``draft``
  profiler phase);
* ONE wide verify program replaces the 1-wide tick: every row decodes
  a fixed ``(draft_k + 1)``-window per dispatch, greedy
  longest-matching-prefix acceptance runs on device, and the per-row
  cache length advances by ``1 + accepted`` — rejected positions roll
  back by the length alone where the state is per position
  (write-before-read: the frontier rewrites them before they can be
  read); a model's recurrent state is left by its own round as after
  the accepted tokens;
* acceptance only ever keeps the model's own argmax, so spec on/off
  is bit-identical to the solo greedy run for any draft quality, and
  ``compile_cache_sizes()`` stays frozen at one signature per program
  (``spec_tick`` replacing ``sample`` and ``tick``: the tokens and the
  accepted counts are the verify program's own result, so a spec engine
  reads them in lock-step).

Scheduler invariants:

1. *Write-before-read*: a row's blocks hold garbage beyond its length;
   every reader masks past the length and every writer writes a position
   before anything attends to it.  Free rows tick along with the batch
   (one program) and scatter into the trash block (block 0).
2. *Row independence*: attention never crosses rows, so each request's
   greedy output is bit-identical to its solo ``llama.generate`` run —
   including requests admitted mid-flight and requests resumed after a
   preemption (pinned by ``tests/test_serving_scheduler.py`` and
   ``tests/test_serving_faults.py``).
3. *Fixed signature*: host state (queue, slot states, free blocks) makes
   every decision; device programs only ever see [n_slots]-shaped data.
   Preempt/requeue/cancel/timeout paths reuse the same programs (a
   model with state per sequence restores it inside ``_set_row`` from
   the snapshot of the block the row is mapped up to), and
   scheduler policies (:mod:`horovod_tpu.scheduling`) only reorder
   host decisions — invariant 2 makes any admission order or victim
   choice output-preserving.

The engine is greedy-only; sampling pools stay on
:class:`~horovod_tpu.serving.ContinuousBatcher`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from horovod_tpu import alerts as alerts_mod
from horovod_tpu import device_telemetry as device_telemetry_mod
from horovod_tpu import drafting as drafting_mod
from horovod_tpu import faults as faults_mod
from horovod_tpu import metrics as metrics_mod
from horovod_tpu import monitor as monitor_mod
from horovod_tpu import profiler as profiler_mod
from horovod_tpu import scheduling as scheduling_mod
from horovod_tpu import timeseries as timeseries_mod
from horovod_tpu import tracing as tracing_mod
from horovod_tpu.metrics import Trace
from horovod_tpu.models.llama import BlockPool
from horovod_tpu.models.paged import Dispatched, paged_model, serving_tree
from horovod_tpu.parallel.mesh import tensor_parallel_mesh
from horovod_tpu.prefix_cache import RadixNode, RadixPrefixCache
from horovod_tpu.serving import (
    CANCELLED, FAILED, OK, REJECTED, TIMEOUT, Request, RequestResult,
)

FREE, PREFILL, DECODE = "free", "prefill", "decode"

# Tokens a prefill program carries at most, rows x chunk: the rows that
# prefill in a step share one read of the weights, `chunk_widths[0]` of them a
# program.  A read of the weights is paid back at the chip's ridge, 197
# TFLOP/s over 819 GB/s = 240 FLOP a byte on a v5e: 240 tokens over dense
# bfloat16 weights, 8 times that where a token reads 4 of 32 experts.
# Measured on one v5e (PERF.md, PR 39): lfm2's chunk of 256 tokens takes
# 15.96 ms as a program of one row (9.3 GB read for 0.5 TFLOP: 71 % of its
# byte roofline, 7.5 % of the FLOP peak) and about 50 ms as one of eight
# (6.6 ms a row, 40 % of the peak: past the ridge, so a wider program would
# gain nothing); Mistral's dense chunk of 256 is at the ridge as one row
# (22.7 ms) and four rows a program gained 1 %.
_CHUNK_TOKENS = 2048


@dataclasses.dataclass
class SchedulerEvent:
    """One scheduler decision, for tests/telemetry: ``kind`` is
    ``"admit"``, ``"hit"`` (admission with a prefix-cache match),
    ``"recycle"`` (OK retirement), ``"preempt"``, ``"retry"``,
    ``"cancel"``, ``"timeout"``, ``"reject"`` or ``"fail"``; ``step``
    the engine step index; ``slot`` is -1 for queue-side events
    (reject, queued cancel/timeout, admit retry)."""

    kind: str
    step: int
    slot: int
    request_id: int


@dataclasses.dataclass
class _QueueEntry:
    """A queued request plus its lifecycle state.  ``prior`` holds
    tokens already emitted before a preemption/replay re-queue (the
    replay prompt is ``req.prompt + prior``); ``wait_steps`` is the
    step-counted retry backoff; ``deadline`` is absolute monotonic;
    ``held_on`` is the radix node admission last passed the entry over
    for (a deeper prefix hit is on its way there), ``held_steps`` how
    often it has."""

    rid: int
    req: Request
    prior: list[int] = dataclasses.field(default_factory=list)
    retries: int = 0
    wait_steps: int = 0
    queued_steps: int = 0
    deadline: float | None = None
    slo_deadline: float | None = None    # enqueue + slo_s (EDF policy)
    held_on: "RadixNode | None" = None
    held_steps: int = 0
    # of a model that decodes a block a row: the blocks committed before a
    # preemption/replay re-queue, whole, and the step that unmasked each
    # of their positions (``RequestResult.blocks`` / ``.unmask_steps``)
    blocks: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Slot:
    state: str = FREE
    request_id: int = -1
    padded: np.ndarray | None = None     # [1, n_win * chunk] prompt
    n_win: int = 0
    w_done: int = 0
    true_len: int = 0
    budget: int = 0
    eos: int | None = None
    out: list[int] = dataclasses.field(default_factory=list)
    n_blocks: int = 0                    # blocks mapped by this slot
    blocks: list[int] = dataclasses.field(default_factory=list)
    base: int = 0                        # cached-prefix positions skipped
    n_hit: int = 0                       # leading shared (hit) blocks
    req: Request | None = None           # original request (for replay)
    prior: list[int] = dataclasses.field(default_factory=list)
    retries: int = 0
    wait_steps: int = 0                  # prefill-retry backoff
    deadline: float | None = None
    slo_deadline: float | None = None    # enqueue + slo_s (EDF policy)
    admit_seq: int = -1                  # monotonic; max = youngest row
    draft: "drafting_mod.NgramDraftState | None" = None
    # (block index, entry) of the snapshot entries this row's prefill has
    # yet to write (models/paged.py, the snapshot budget)
    snap_pending: list[tuple] = dataclasses.field(default_factory=list)
    # per full block of the prompt, the radix node this row reserved at its
    # admission and flips when the chunk that fills it is dispatched (None:
    # the path had one); the first n_indexed are done
    nodes: "list[RadixNode | None]" = dataclasses.field(
        default_factory=list)
    n_indexed: int = 0
    # A row of a model that decodes a block a row (``ServeEngine.block``):
    # the ids of its block in flight as the host last read them (mask ids
    # where a position is still masked; the first ``given`` are the
    # prompt's tail), the step that unmasked each position (-1: given),
    # how many denoise steps the block has had, whether it has yet to go
    # into its first tick, the blocks this stint committed, and every
    # committed block whole with its steps (replays included).
    block: list[int] = dataclasses.field(default_factory=list)
    when: list[int] = dataclasses.field(default_factory=list)
    given: int = 0
    block_step: int = 0
    fresh: bool = True
    n_committed: int = 0
    done_blocks: list = dataclasses.field(default_factory=list)
    done_steps: list = dataclasses.field(default_factory=list)


class ServeEngine:
    """Serve a queue of greedy requests through a recycled slot pool.

    ``n_slots``: compiled batch width.  ``max_len``: per-request logical
    depth bound (prompt + generation).  ``chunk``: the chunked-prefill
    window — one [1, chunk] prompt window runs per step per admitting
    slot, which is the knob trading admission latency against how much a
    long prompt delays the next decode tick.  ``block_size`` (default:
    ``chunk``) and ``n_blocks`` size the paged pool; the default pool
    fully backs every slot, smaller pools overcommit and admission waits
    for free blocks.  ``timeline``: an optional
    :class:`horovod_tpu.timeline.Timeline` receiving admit/recycle
    instants plus per-step queue/occupancy (``SCHED``) and lifecycle
    (``LIFECYCLE``: preemptions/timeouts/retries/…) counters.

    Fault-tolerance knobs:

    ``preempt_after``: consecutive steps the queue head may starve on an
    overcommitted block pool before the youngest decoding row is
    preempted and re-queued for replay (``None`` disables preemption).
    ``max_retries``: bounded retries for transient per-request faults
    (prefill windows retry in place after a ``2**retries``-step backoff;
    decode readback retries re-queue through the replay path); once
    exhausted — or immediately on a
    :class:`~horovod_tpu.faults.PermanentFault` — the request terminates
    ``FAILED`` with the exception attached, and every other row keeps
    serving.  ``watchdog_steps``: consecutive no-progress steps (no
    admission, prefill window, decode tick, retirement, preemption, or
    backoff countdown while work is pending) before ``step()`` raises
    ``RuntimeError`` with a scheduler-state dump instead of letting
    ``run()`` spin forever.  ``faults``: a
    :class:`~horovod_tpu.faults.FaultRegistry` consulted at the
    ``serve.admit`` / ``serve.prefill`` / ``serve.tick`` /
    ``serve.cache`` sites (defaults to the shared registry, which is a
    no-op unless armed).

    ``metrics``: a :class:`horovod_tpu.metrics.MetricsRegistry` fed on
    every step — TTFT / TPOT / queue-wait / e2e latency histograms
    (``serve.*_s``), lifecycle counters mirroring ``self.counters``,
    and KV-pool + prefix-cache gauges — plus one structured event per
    request state transition when the registry has an event log
    (``HVD_TPU_EVENT_LOG``).  Defaults to the process-shared
    :data:`horovod_tpu.metrics.DEFAULT` registry (one scrape sees
    training and serving together); pass
    :data:`horovod_tpu.metrics.NULL` to opt out.  Every request also
    carries a :class:`~horovod_tpu.metrics.Trace` (surfaced on
    ``RequestResult.trace`` and mirrored into the timeline as a
    per-rid ``REQ`` async span) regardless of the registry.
    ``metrics_snapshot()`` returns the registry's plain-dict snapshot.

    ``prefix_cache``: enable transparent shared-prefix KV reuse
    (:mod:`horovod_tpu.prefix_cache`) — admission longest-prefix-matches
    each prompt against the radix index of previously served requests
    and maps the hit blocks straight into the new row, so chunked
    prefill starts at the first uncached token; retirement releases
    blocks *to the cache* (zero-ref blocks park in LRU order) instead
    of freeing, and admission under KV pressure evicts cached blocks
    before any decoding row is preempted.  Off by default: block
    accounting is then exactly the classic free list and every code
    path is unchanged.  Set ``HVD_TPU_VERIFY_BLOCKS=1`` to walk the
    block tables after every step asserting refcount consistency (debug
    aid; O(slots * blocks) host work per step).

    ``spec`` / ``draft_k``: self-drafting speculative decode — each
    decoding row's prompt-lookup drafter
    (:class:`~horovod_tpu.drafting.NgramDraftState`) proposes up to
    ``draft_k`` tokens per tick from the request's own history and ONE
    always-``(draft_k + 1)``-wide batched verify program
    (:func:`~horovod_tpu.models.llama.spec_verify_paged`) decodes every
    row's chunk with per-row greedy longest-prefix acceptance; rejected
    positions roll back by the row's length alone (write-before-read).
    One extra jit signature for the life of the server (``spec_tick``
    replaces ``sample`` and ``tick`` in ``compile_cache_sizes()``),
    every output stays bit-identical to solo greedy generate, and a
    round can emit up to
    ``1 + draft_k`` tokens per row.  ``None`` reads ``HVD_TPU_SPEC`` /
    ``HVD_TPU_DRAFT_K`` (off / 4).

    ``policy``: admission-order + preemption-victim policy — a
    :class:`~horovod_tpu.scheduling.SchedulerPolicy` instance, a name
    (``fifo`` / ``priority`` / ``edf``), or ``None`` to read
    ``HVD_TPU_SCHED_POLICY``.  FIFO is bit-compatible with the
    pre-policy engine; policies reorder who waits and who is evicted,
    never any request's tokens (scheduler invariant 2).

    ``tp_size``: tensor-parallel serving — the decode path runs on a
    1-axis ``('tp',)`` device mesh
    (:func:`~horovod_tpu.parallel.mesh.tensor_parallel_mesh`) with
    params Megatron-split and the paged KV pool head-split, so KV HBM
    and the matmul work divide across ``tp_size`` chips while the
    block pool / prefix cache / block tables stay host-side and
    shard-agnostic.  Greedy outputs are token-identical to the
    unsharded engine and ``compile_cache_sizes()`` stays at one
    signature per program.  ``None`` reads ``HVD_TPU_TP`` (default
    1); at 1 there is no mesh and every code path is the
    single-device one.
    """

    def __init__(self, params: dict, cfg: Any, *,
                 n_slots: int, max_len: int, chunk: int,
                 block_size: int | None = None,
                 n_blocks: int | None = None,
                 tp_size: int | None = None,
                 timeline: Any = None,
                 preempt_after: int | None = None,
                 max_retries: int = 2,
                 watchdog_steps: int = 256,
                 faults: "faults_mod.FaultRegistry | None" = None,
                 metrics: "metrics_mod.MetricsRegistry | None" = None,
                 prefix_cache: bool = False,
                 monitor: "monitor_mod.MonitorServer | int | bool | None"
                     = None,
                 slo_window: int = 256,
                 slo_e2e_s: float | None = None,
                 profile: bool | None = None,
                 profile_window: int | None = None,
                 spec: bool | None = None,
                 draft_k: int | None = None,
                 policy: "scheduling_mod.SchedulerPolicy | str | None"
                     = None,
                 sampler: "timeseries_mod.MetricsSampler | bool | None"
                     = None,
                 alerts: "alerts_mod.AlertManager | bool | None"
                     = None,
                 device_telemetry:
                     "device_telemetry_mod.DeviceTelemetry | bool | None"
                     = None):
        if chunk < 1 or chunk > max_len:
            raise ValueError(f"chunk {chunk} must be in [1, max_len "
                             f"{max_len}]")
        if preempt_after is not None and preempt_after < 1:
            raise ValueError("preempt_after must be >= 1 (or None)")
        if watchdog_steps < 1:
            raise ValueError("watchdog_steps must be >= 1")
        block_size = chunk if block_size is None else block_size
        # Tensor-parallel serving: tp_size > 1 puts the decode path on a
        # 1-axis ('tp',) mesh — params Megatron-split per
        # llama.param_partition_specs, the paged KV pool head-split per
        # llama.paged_cache_partition_specs — while the block pool /
        # prefix cache / block tables stay host-side and shard-agnostic
        # (one logical block id addresses the same slot of every chip's
        # head slice).  None reads HVD_TPU_TP (default 1); at tp_size=1
        # no mesh exists and every code path is the single-device one.
        if tp_size is None:
            raw = os.environ.get("HVD_TPU_TP", "")
            tp_size = int(raw) if raw else 1
        tp_size = int(tp_size)
        if tp_size < 1:
            raise ValueError(f"tp_size must be >= 1, got {tp_size}")
        # The model behind the engine: a module of the paged model
        # interface (horovod_tpu.models.paged), chosen by the config's
        # type.  Everything below reaches the model through it.
        self.model = model = paged_model(cfg)
        # A model that generates by diffusion over blocks decodes a block
        # of this many positions a row a tick (models/paged.py); 0: a
        # token a row a tick.
        self.block = (int(model.block_length(cfg))
                      if hasattr(model, "block_length") else 0)
        if self.block and (chunk % self.block or block_size % self.block):
            raise ValueError(
                f"a model that generates by diffusion over blocks of "
                f"{self.block} positions needs chunk ({chunk}) and "
                f"block_size ({block_size}) to be multiples of its "
                f"block_length: a prefill chunk takes the block-causal "
                f"mask and a prefix hit's frontier is a whole number of "
                f"pages")
        if tp_size > 1:
            for dim_name, dim in model.tp_split_dims(cfg):
                if dim % tp_size:
                    raise ValueError(
                        f"tp_size={tp_size} does not divide "
                        f"cfg.{dim_name}={dim}: every tp-sharded axis "
                        f"must split evenly across the mesh")
        self.tp_size = tp_size
        # The tree as the model's paged programs read it, where the model
        # lays one out (models/paged.py, `serving_params`): made once, here,
        # and the only tree the engine keeps.  What it wrote anew is
        # `serve.params_relaid_bytes`; the caller's tree is the caller's.
        params, relaid_bytes = serving_tree(model, params, cfg,
                                            tp_size=tp_size)
        if tp_size > 1:
            self.mesh = tensor_parallel_mesh(tp_size)
            pspecs = getattr(model, "serving_partition_specs",
                             model.param_partition_specs)(cfg, tp_axis="tp")
            cspecs = model.paged_cache_partition_specs(tp_axis="tp")
            self._param_sh = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            self._cache_sh = type(cspecs)(
                *(NamedSharding(self.mesh, s) for s in cspecs))
            self._repl_sh = NamedSharding(self.mesh, PartitionSpec())
            # Pre-commit the persistent state to its exact target
            # sharding: jit cache keys distinguish committed from
            # uncommitted inputs, so an uncommitted first call would
            # mint a second signature and trip the retrace sentry.
            params = jax.tree.map(jax.device_put, params, self._param_sh)
        else:
            self.mesh = None
            self._param_sh = self._cache_sh = self._repl_sh = None
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.chunk = chunk
        self.block_size = block_size
        self.timeline = timeline
        self.preempt_after = preempt_after
        self.max_retries = max_retries
        self.watchdog_steps = watchdog_steps
        self.faults = faults if faults is not None else faults_mod.DEFAULT
        self.metrics = metrics if metrics is not None else metrics_mod.DEFAULT
        # the last construction on this registry that laid a tree out: an
        # engine handed a serving tree (a clone, over its original's
        # registry) wrote nothing and leaves the gauge as it stands
        relaid = self.metrics.gauge("serve.params_relaid_bytes")
        if relaid_bytes:
            relaid.set(relaid_bytes)
        # Scheduler policy (admission order + preemption victim): FIFO
        # default is bit-compatible with the pre-policy engine.
        self.policy = scheduling_mod.resolve_policy(policy)
        # Self-drafting speculation: env-driven when unset.
        if spec is None:
            spec = os.environ.get("HVD_TPU_SPEC", "") == "1"
        if draft_k is None:
            raw = os.environ.get("HVD_TPU_DRAFT_K", "")
            draft_k = int(raw) if raw else drafting_mod.DEFAULT_DRAFT_K
        if spec and draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {draft_k}")
        if spec and self.block:
            raise ValueError(
                "spec=True does not apply to a model that generates by "
                "diffusion over blocks: a step denoises a block of "
                f"{self.block} positions a row and commits it when it is "
                "clean, there is no next-token draft to verify")
        self.spec = bool(spec)
        self.draft_k = int(draft_k)
        self.spec_counters = {"rounds": 0, "row_rounds": 0,
                              "proposed": 0, "accepted": 0}
        if self.spec:
            # Registered up front (literal names — the HVD005 contract)
            # so spec snapshots are schema-stable from step 0.
            self.metrics.counter("serve.spec.rounds")
            self.metrics.counter("serve.spec.row_rounds")
            self.metrics.counter("serve.spec.proposed")
            self.metrics.counter("serve.spec.accepted")
            self.metrics.counter("serve.spec.draft_faults")
            self.metrics.histogram("serve.spec.accepted_per_round")
        # Register the latency histograms up front so metrics_snapshot()
        # is schema-stable from step 0 (empty histograms report zeros).
        for h in ("serve.ttft_s", "serve.tpot_s", "serve.queue_wait_s",
                  "serve.e2e_s"):
            self.metrics.histogram(h)
        if self.block:
            # Row-forwards of either kind (a commit forward is a block
            # committed), positions unmasked and by which rule, and blocks
            # a preemption dropped in flight (registered up front, by
            # literal name: HVD005).
            self._c_block = {
                "denoise": self.metrics.counter("diffusion.denoise_forwards"),
                "commit": self.metrics.counter("diffusion.commit_forwards"),
                "unmasked": self.metrics.counter("diffusion.tokens_unmasked"),
                "by_threshold": self.metrics.counter(
                    "diffusion.unmasked_by_threshold"),
                "by_schedule": self.metrics.counter(
                    "diffusion.unmasked_by_schedule"),
                "redone": self.metrics.counter("diffusion.blocks_redone")}
        # Causal tracing plane (horovod_tpu.tracing): spans are emitted
        # post-hoc from Trace stamps at terminal time, so with sampling
        # off the hot path pays one None-check per request.
        self.tracer = tracing_mod.Tracer(self.metrics)
        self._trace_fraction = tracing_mod.env_sample_fraction()
        self._trace_seed = tracing_mod.env_trace_seed()
        # The phases of step(): None = env-driven (HVD_TPU_PROFILE=1).
        # Either way they are spans on jax's profiler trace and one row
        # a step in self.prof.log (PhaseSpans); on, TickProfiler feeds
        # the rows to the serve.phase.*_s histograms and the event log.
        if profile is None:
            profile = os.environ.get("HVD_TPU_PROFILE", "") == "1"
        self.prof = (profiler_mod.TickProfiler if profile
                     else profiler_mod.PhaseSpans)(
            self.metrics, window=profile_window)
        # Device telemetry plane (horovod_tpu.device_telemetry): XLA
        # cost model + compile ledger + HBM polling + the device_sync
        # compute/stall split.  None = env-driven
        # (HVD_TPU_DEVICE_TELEMETRY=1), False = off, True = on, an
        # instance is used as-is.  Off means device is None and every
        # hot-path call site is one `is not None` test.
        if device_telemetry is False:
            self.device = None
        elif device_telemetry is None:
            self.device = device_telemetry_mod.maybe_telemetry(
                self.metrics, n_devices=tp_size)
        elif device_telemetry is True:
            self.device = device_telemetry_mod.DeviceTelemetry(
                self.metrics, n_devices=tp_size)
        else:
            self.device = device_telemetry
        # Retrace sentry: the dynamic complement to hvdlint HVD001 —
        # compile_cache_sizes() is diffed every step and any mid-serve
        # growth bumps serve.retrace (fatal under HVD_TPU_RETRACE_FATAL=1).
        self._retrace_fatal = os.environ.get(
            "HVD_TPU_RETRACE_FATAL", "") == "1"
        self.metrics.counter("serve.retrace")
        # Steps whose tokens were ready before the host asked for them:
        # over serve.steps, how often the host and not the device set
        # the pace (registered up front: schema-stable from step 0).
        self.metrics.counter("serve.step.host_bound")
        self._t0 = time.monotonic()
        self._last_step_ts: float | None = None
        # SLO goodput window: every terminal trace lands here; the
        # serve.goodput gauge tracks the windowed good fraction.
        self.slo = monitor_mod.SLOWindow(window=slo_window,
                                         slo_e2e_s=slo_e2e_s)
        self._slo_targets: dict[int, float | None] = {}
        # Health plane: time-series sampler + alert rules, ticked from
        # step() bookkeeping (no threads).  None = env-driven
        # (HVD_TPU_SAMPLE_S / HVD_TPU_ALERTS), False = off, an instance
        # is used as-is; the capacity advisor rides along whenever a
        # sampler is live.
        if sampler is False:
            self.sampler = None
        elif sampler is None:
            self.sampler = timeseries_mod.maybe_sampler(self.metrics)
        else:
            self.sampler = sampler
        if alerts is False or self.sampler is None:
            self.alerts = None
        elif alerts is None:
            self.alerts = alerts_mod.maybe_alerts(
                self.sampler, self.metrics)
        else:
            self.alerts = alerts
        self.advisor = (alerts_mod.CapacityAdvisor(
            self.sampler, alerts=self.alerts, registry=self.metrics)
            if self.sampler is not None else None)
        # Live exporter: False = off; None = env-driven
        # (HVD_TPU_MONITOR_PORT); int = bind that port; an existing
        # MonitorServer re-attaches to this engine.
        if monitor is False:
            self.monitor = None
        elif monitor is None:
            self.monitor = monitor_mod.maybe_start_monitor(
                self.metrics, self)
        elif isinstance(monitor, monitor_mod.MonitorServer):
            monitor.attach_engine(self)
            self.monitor = monitor
        elif isinstance(monitor, int) and monitor is not True:
            self.monitor = monitor_mod.MonitorServer(
                self.metrics, self, port=monitor).start()
        else:
            raise ValueError(
                f"monitor must be None / False / port int / "
                f"MonitorServer, got {monitor!r}")
        self.pcache = model.init_paged_cache(
            cfg, n_slots, max_len, block_size=block_size,
            n_blocks=n_blocks)
        if self.tp_size > 1:
            self.pcache = type(self.pcache)(*(
                jax.device_put(x, s)
                for x, s in zip(self.pcache, self._cache_sh)))
        self.blocks_per_slot = self.pcache.block_table.shape[1]
        # per pool (k and v; latent, index and window; ...) the device
        # bytes one block holds over all its layers: a block id means the
        # same block in every pool, so the pool's extent is any one's
        self._pool_block_bytes = model.paged_pool_bytes(self.pcache)
        total = next(a.shape[1] for a in self.pcache if a.ndim > 2)
        # block 0 is trash — never allocated; the pool's free list pops
        # low ids first, matching the classic free-list order
        self.pool = BlockPool(total)
        # a model whose per-sequence state is too large for a snapshot a
        # block keeps a budget of them (models/paged.py): the engine owns
        # which block holds which entry and tells `set_row`
        make_budget = getattr(model, "snapshot_budget", None)
        self.snaps = (make_budget(cfg, self.pcache, self.metrics)
                      if make_budget is not None else None)
        if self.snaps is not None:
            self.pool.on_free = self.snaps.drop
            self._no_snaps = np.full((self.blocks_per_slot,),
                                     self.snaps.none, np.int32)
        # legacy alias: the SAME list object the pool allocates from
        # (white-box tests drain it to force block starvation)
        self._free_blocks = self.pool._free
        # KV memory accounting: one physical block holds block_size
        # positions of every pool across every layer; the model says
        # what that is in bytes, pool by pool, and kv.block_bytes is
        # their sum.
        self._block_bytes = sum(self._pool_block_bytes.values())
        model.publish_paged_metrics(self.metrics, cfg, self.pcache)
        self.metrics.gauge("kv.block_bytes").set(self._block_bytes)
        self.metrics.gauge("kv.total_bytes").set(
            self._block_bytes * total)
        # Per-shard KV accounting: each chip holds n_kv_heads / tp of
        # every block (head-split pool), so shard bytes are the logical
        # bytes over tp — exact, the head axis divides evenly (checked
        # above).  Uniform schema: at tp_size=1 shard gauges equal the
        # logical ones, and the tp gauges always exist so scrapes and
        # router capacity probes never branch on engine flavor.
        self._shard_block_bytes = self._block_bytes // self.tp_size
        self.metrics.gauge("tp.size").set(self.tp_size)
        self.metrics.gauge("kv.shard_block_bytes").set(
            self._shard_block_bytes)
        self.metrics.gauge("kv.shard_total_bytes").set(
            self._shard_block_bytes * total)
        self.prefix = (RadixPrefixCache(self.pool, block_size,
                                        metrics=self.metrics,
                                        snaps=self.snaps)
                       if prefix_cache else None)
        self.prefix_counters = {"hits": 0, "blocks_reused": 0,
                                "tokens_skipped": 0, "evictions": 0}
        self._verify_blocks = os.environ.get(
            "HVD_TPU_VERIFY_BLOCKS", "") == "1"
        self._trash_row = np.zeros((self.blocks_per_slot,), np.int32)
        self.last_logits = jnp.zeros((n_slots, cfg.vocab_size),
                                     jnp.float32)
        if self.tp_size > 1:
            self.last_logits = jax.device_put(self.last_logits,
                                              self._repl_sh)
        # what a block tick leaves for the next step's unmask: the logits
        # of every position of every row's block
        self.block_logits = (jnp.zeros(
            (n_slots, self.block, cfg.vocab_size), jnp.float32)
            if self.block else None)
        self._slots = [_Slot() for _ in range(n_slots)]
        self._queue: list[_QueueEntry] = []
        self._next_id = 0
        self._admit_seq = 0
        self._starve_steps = 0
        self._idle_steps = 0
        self._finished: dict[int, RequestResult] = {}
        self.results: dict[int, RequestResult] = {}
        self.traces: dict[int, Trace] = {}
        self.events: list[SchedulerEvent] = []
        self.counters = {"preemptions": 0, "timeouts": 0,
                         "cancellations": 0, "rejections": 0,
                         "retries": 0, "failures": 0}
        self.step_index = 0
        # The rows (slot -> request id) of the tick in flight: a step
        # returns without waiting for its tick, so a fault of tick N
        # surfaces at step N+1's read and is theirs.  Emptied when the
        # engine goes idle and the tick has been waited for.
        self._tick_rows: dict[int, int] = {}

        # Sharded program signatures: explicit in/out shardings pin the
        # GSPMD layout at every jit boundary (params Megatron-split, KV
        # pool head-split, everything the host reads replicated) — XLA
        # then keeps Q·Kᵀ and the MLP matmuls chip-local with one psum
        # per attention/MLP block (the row-parallel wo/w_down reduction)
        # and tp>1 stays at one signature per program.  At tp_size=1 the
        # kwargs are empty and the decorators are byte-identical to the
        # single-device engine.
        if self.tp_size > 1:
            _p, _c, _r = self._param_sh, self._cache_sh, self._repl_sh
            _sample_sh = dict(in_shardings=(_r, _r),
                              out_shardings=(_r, _r))
            _tick_sh = dict(in_shardings=(_p, _c, _r, _r),
                            out_shardings=(_r, _c))
            _chunk_sh = dict(in_shardings=(_p, _c, _r, _r, _r, _r, _r),
                             out_shardings=(_c, _r))
            _row_sh = dict(in_shardings=(_c, _r, _r, _r),
                           out_shardings=_c)
            _spec_sh = dict(in_shardings=(_p, _c, _r, _r, _r),
                            out_shardings=(_r, _r, _r, _c))
        else:
            _sample_sh = _tick_sh = _chunk_sh = _row_sh = _spec_sh = {}

        @partial(jax.jit, **_sample_sh)
        def _sample(last_logits, counters):
            # what a step hands out, in a program of its own in front of
            # the tick: every row's token is the argmax of the logits the
            # step before left, known before the tick's layers run, so
            # the host reads it from here and leaves the tick in flight.
            # `counters` is the model's cumulative device counters (or
            # None) as they stand at this point, copied because the tick
            # behind donates the cache they live in.  Donates nothing.
            tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            return tok, jax.tree.map(jnp.copy, counters)

        @partial(jax.jit, donate_argnums=(1, 2), **_tick_sh)
        def _tick(params, pcache, last_logits, active):
            # the fixed-signature decode tick: every row argmaxes its
            # last logits (the token `_sample` hands the host, computed
            # again here so that the tick takes nothing from it) and
            # decodes one position; `active` [B] gates
            # the length advance so idle/prefilling rows hold position
            # (their garbage write lands in their own blocks or trash —
            # invariant 1).  Donation matters: decode cost IS cache
            # traffic.  The donated pool aliases the carry of
            # _paged_attend's layer scan, so the tick scatters one
            # position per row in place; undonated (or scanned in and
            # stacked out) every tick would copy every block.
            tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            logits, pcache = model.decode_chunk_paged(
                params, tok[:, None], cfg, pcache, advance=active)
            return logits[:, 0], pcache

        rows_entry = getattr(model, "decode_chunk_paged_rows", None)

        @partial(jax.jit, donate_argnums=(1, 2), **_chunk_sh)
        def _chunk(params, pcache, last_logits, toks, slots, new_len, sel):
            # one chunked-prefill window for each of the program's rows:
            # [R, chunk] tokens continue the rows `slots` [R] from their
            # current lengths to `new_len` [R]; `sel` [R] picks the window
            # position whose logits seed decoding (only the final window's
            # pick survives: later windows overwrite).  One signature a
            # width R (`chunk_widths`: the wide program and the one-row
            # one); a model without the rows entry keeps one row a
            # program.  A row whose slot is `n_slots` is not there and
            # writes nothing, its logits included.
            if rows_entry is not None:
                logits, pcache = rows_entry(
                    params, toks, cfg, pcache, slots, new_length=new_len,
                    sel=sel)
            else:
                logits, pcache = model.decode_chunk_paged_row(
                    params, toks, cfg, pcache, slots[0],
                    new_length=new_len[0])
                logits = logits[:, sel[0]]
            return pcache, last_logits.at[slots].set(logits, mode="drop")

        @partial(jax.jit, donate_argnums=(0,), **_row_sh)
        def _set_row(pcache, slot, row, length):
            # admission/retirement table write: swaps which physical
            # blocks a slot row maps to and sets its length — data
            # only, so slot recycling (and every lifecycle transition:
            # preempt, cancel, timeout, fail) reuses the same compiled
            # programs.  `length` is 0 except on a prefix-cache hit,
            # where it is the cached frontier so the first prefill
            # window continues from the first uncached token.  A model
            # with state that is not per position says in its own
            # `set_row` what a slot holds when its row is (re)mapped
            # (models/paged.py): same program, same signature.
            if hasattr(model, "set_row"):
                return model.set_row(pcache, slot, row, length)
            return pcache._replace(
                block_table=pcache.block_table.at[slot].set(row),
                length=pcache.length.at[slot].set(length))

        if self.snaps is not None:
            @partial(jax.jit, donate_argnums=(0,), **_row_sh)
            def _set_row(pcache, slot, row, length, snaps):  # noqa: F811
                # under a snapshot budget the model's `set_row` is also
                # told the entry of each block of the row (`_map_row`)
                return model.set_row(pcache, slot, row, length, snaps)

        if self.block:
            @jax.jit
            def _unmask(block_logits, tokens, step, go, counters):
                # the program in `_sample`'s place for a model that decodes
                # a block a row: the sampler's rule over the logits the
                # last tick left, for the rows `go` [B] whose block that
                # tick ran over (a block that has been in no tick yet
                # stands as the host gave it).  `tokens` [B, block] and
                # `step` [B] are the host's mirror, whole since it read
                # the step before; what it reads of this one is the new
                # ids and two small vectors, never logits, and `commit`
                # (the rows whose block came clean) goes to the tick
                # behind without the host.  Donates nothing.
                new, left, by_threshold = model.unmask(
                    cfg, block_logits, tokens, step)
                go = go > 0
                tokens = jnp.where(go[:, None], new, tokens)
                left = jnp.where(go, left, self.block)
                commit = (go & (left == 0)).astype(jnp.int32)
                return (tokens, left, jnp.where(go, by_threshold, 0), commit,
                        jax.tree.map(jnp.copy, counters))

            @partial(jax.jit, donate_argnums=(1, 2))
            def _tick(params, pcache, block_logits, tokens,  # noqa: F811
                      active, commit):
                # the block tick: every decoding row's block under the
                # block-causal mask against its pages, the block's keys
                # written past the row's length; the length advances only
                # where `commit`.  Left in flight as the one-token tick is.
                return model.decode_block_paged(
                    params, tokens, cfg, pcache, active=active,
                    commit=commit)

            self._unmask = _unmask
            _sample = None
        else:
            self._unmask = None

        if self.spec:
            @partial(jax.jit, donate_argnums=(1, 2), **_spec_sh)
            def _spec_tick(params, pcache, last_logits, drafts, active):
                # the always-wide speculative tick: one (draft_k+1)-wide
                # verify for the whole pool, acceptance and the gated
                # length advance computed in-program so the host reads
                # back tokens AND accepted counts in one sync.  Replaces
                # _tick entirely on a spec engine — still one signature
                # per program for the life of the server.
                return model.spec_verify_paged(
                    params, cfg, pcache, last_logits, drafts, active)

            self._spec_tick = _spec_tick
        else:
            self._spec_tick = None
        self._sample = _sample
        self._tick = _tick
        self._chunk = _chunk
        self._set_row = _set_row
        # Rows and programs of prefill dispatched (serve.chunk.rows over
        # serve.chunk.programs: how many rows shared a read of the weights)
        self._c_chunk_rows = self.metrics.counter("serve.chunk.rows")
        self._c_chunk_programs = self.metrics.counter("serve.chunk.programs")
        #: The rows a chunk program carries, widest first: every one is
        #: compiled here, before the constructor returns.
        self.chunk_widths = self._compile_chunk_widths(
            rows_entry is not None)
        # Device cost-model capture happens BEFORE the sentry baseline
        # on purpose: AOT lowering never mints jit call-cache entries,
        # and taking the baseline after it proves that property every
        # construction (the sentry would flag any drift immediately).
        if self.device is not None:
            self._device_capture_programs(self.device)
        # Sentry baseline: all zeros pre-warmup.  The first compile of
        # each program (0 -> 1) is legitimate; the sentry only counts
        # growth BEYOND one signature per program.
        self._jit_cache_seen = self.compile_cache_sizes()

    # -- introspection -----------------------------------------------------

    def compile_cache_sizes(self) -> dict[str, int]:
        """Per-program jit cache entry counts — the no-retrace pin:
        admission/recycling/preemption must keep every count constant.
        ``chunk`` is 1 for "one signature a width": the chunk program has
        as many as ``chunk_widths`` (compiled by the constructor where the
        model offers the rows entry, else by the first request), and what
        it holds beyond one a width is a retrace of some width.
        A spec engine adds the ``spec_tick`` key (its always-wide verify
        program, which replaces ``sample`` and ``tick`` so that those
        counts stay 0).  An engine whose model decodes a block a row has
        ``unmask`` in ``sample``'s place."""
        if self.block:
            front = {"unmask": self._unmask._cache_size()}
        else:
            front = {"sample": self._sample._cache_size()}
        sizes = {
            **front,
            "tick": self._tick._cache_size(),
            "chunk": max(self._chunk._cache_size()
                         - (len(self.chunk_widths) - 1), 0),
            "set_row": self._set_row._cache_size(),
        }
        if self._spec_tick is not None:
            sizes["spec_tick"] = self._spec_tick._cache_size()
        return sizes

    def pinned_programs(self) -> dict[str, tuple]:
        """Every pinned program as ``name -> (jitted fn, *avals)``, the
        ``ShapeDtypeStruct`` avals built from the live arrays — so
        ``fn.lower(*avals)`` lowers the very signature serving calls, and
        never touches the jit call cache (``compile_cache_sizes()`` is the
        same before and after).  ``.compile().memory_analysis()`` of a
        lowered ``tick`` / ``chunk`` is where the in-place pool shows: their
        scratch holds no second pool (tests/test_paged_inplace.py,
        chip_smoke.py).  ``chunk`` is the one-row program; a wider width's
        avals are ``_chunk_arg_avals(rows)`` in place of its last four."""
        aval = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
        p_av = jax.tree.map(aval, self.params)
        c_av = jax.tree.map(aval, self.pcache)
        ll_av = aval(self.last_logits)
        counters_av = jax.tree.map(
            aval, self.model.paged_counters(self.pcache))
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        active_av = jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)
        row_av = jax.ShapeDtypeStruct((self.blocks_per_slot,), jnp.int32)
        if self.block:
            bl_av = aval(self.block_logits)
            ids_av = jax.ShapeDtypeStruct((self.n_slots, self.block),
                                          jnp.int32)
            front = {
                "unmask": (self._unmask, bl_av, ids_av, active_av, active_av,
                           counters_av),
                "tick": (self._tick, p_av, c_av, bl_av, ids_av, active_av,
                         active_av)}
        else:
            front = {"sample": (self._sample, ll_av, counters_av),
                     "tick": (self._tick, p_av, c_av, ll_av, active_av)}
        progs = {
            **front,
            "chunk": (self._chunk, p_av, c_av, ll_av,
                      *self._chunk_arg_avals(1)),
            "set_row": ((self._set_row, c_av, i32, row_av, i32)
                        + ((row_av,) if self.snaps is not None else ())),
        }
        if self._spec_tick is not None:
            drafts_av = jax.ShapeDtypeStruct(
                (self.n_slots, self.draft_k), jnp.int32)
            progs["spec_tick"] = (self._spec_tick, p_av, c_av, ll_av,
                                  drafts_av, active_av)
        return progs

    def _chunk_arg_avals(self, rows: int) -> tuple:
        """The avals of what a chunk program of ``rows`` rows is called
        with: tokens, slots, new lengths, picked positions."""
        row = jax.ShapeDtypeStruct((rows,), jnp.int32)
        return (jax.ShapeDtypeStruct((rows, self.chunk), jnp.int32),
                row, row, row)

    def _device_room(self) -> int | None:
        """Bytes the fullest of the engine's devices has left beside what
        is allocated on it now, ``None`` where the device reports no limit
        (a CPU)."""
        devices = (self.mesh.devices.flat if self.mesh is not None
                   else jax.devices()[:1])
        room = None
        for d in devices:
            stats = d.memory_stats() or {}
            if "bytes_limit" in stats:
                left = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
                room = left if room is None else min(room, left)
        return room

    def _scratch_bytes(self, program: str, *chunk_rows: int) -> int:
        """What one run of a pinned program (the chunk program at
        ``chunk_rows`` rows) allocates beside its donated arguments: its
        compiled scratch and the results that alias none of them.  The
        compile is the jit call's own (jax keeps the lowering), so nothing
        is traced or compiled twice."""
        fn, *avals = self.pinned_programs()[program]
        if chunk_rows:
            avals[-4:] = self._chunk_arg_avals(*chunk_rows)
        m = fn.lower(*avals).compile().memory_analysis()
        return (m.temp_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes)

    def _compile_chunk_widths(self, rows_entry: bool) -> tuple:
        """The widths of the chunk program this engine dispatches, widest
        first, derived from what it can see.  A model without the rows
        entry keeps one row a program, compiled by its first use as every
        program of one signature is.  Where the model offers the entry
        there are two: the one-row program, and a wide one of as many rows
        as the slots and ``_CHUNK_TOKENS`` a program allow (a width costs
        seconds of set-up to trace and load, every engine, so there is one
        wide width and not a ladder of them: PERF.md, PR 39).  On a device
        that reports its memory the wide one is halved until its compiled
        scratch fits beside what the engine holds and a tick in flight,
        and not tried at all where not even the one-row program's scratch
        fits there twice.  Each width kept is compiled here, by a run over
        rows that are not there (it writes nothing), so that none compiles
        on first use mid-serve; the gauge ``serve.chunk.max_rows`` says
        how wide the wide one is."""
        self.metrics.gauge("serve.chunk.max_rows").set(1)
        if not rows_entry:
            return (1,)
        wide = min(self.n_slots, _CHUNK_TOKENS // self.chunk)
        room = self._device_room() if wide > 1 else None
        if room is not None:
            room -= self._scratch_bytes("spec_tick" if self.spec else "tick")
            if 2 * self._scratch_bytes("chunk", 1) > room:
                wide = 1
            while wide > 1 and self._scratch_bytes("chunk", wide) > room:
                wide //= 2
        widths = (wide, 1) if wide > 1 else (1,)
        for rows in widths:
            absent = jnp.full((rows,), self.n_slots, jnp.int32)
            zeros = jnp.zeros((rows,), jnp.int32)
            self.pcache, self.last_logits = self._chunk(
                self.params, self.pcache, self.last_logits,
                jnp.zeros((rows, self.chunk), jnp.int32), absent, zeros,
                zeros)
        self.metrics.gauge("serve.chunk.max_rows").set(wide)
        return widths

    def _device_capture_programs(
            self, dev: "device_telemetry_mod.DeviceTelemetry") -> None:
        """AOT-capture the XLA cost model of every pinned program into
        ``dev`` (FLOPs / bytes-accessed / compile wall time per
        dispatch) and hand it the exact model-side device bytes for HBM
        reconciliation.  ``jitfn.lower()`` never touches the jit call
        cache, so ``compile_cache_sizes()`` is identical telemetry-on
        vs off (pinned by tests/test_device_telemetry.py)."""
        for name, (jitfn, *avals) in self.pinned_programs().items():
            dev.capture(name, jitfn, *avals)
        param_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(self.params))
        dev.set_model_bytes(
            param_bytes=param_bytes,
            kv_total_bytes=self._block_bytes * self.pool.n_blocks)

    def free_block_count(self) -> int:
        return len(self._free_blocks)

    def cached_block_count(self) -> int:
        """Zero-ref blocks parked in the prefix cache (0 without it)."""
        return self.pool.cached_count()

    def pending(self) -> bool:
        return bool(self._queue) or any(
            s.state != FREE for s in self._slots)

    def metrics_snapshot(self) -> dict:
        """Plain-dict snapshot of the engine's registry: counters,
        gauges, and the TTFT / TPOT / queue-wait / e2e histograms with
        p50/p90/p99 — plus the windowed ``slo`` report, the ``memory``
        accounting report, and (with profiling on) the rolling
        ``profile`` phase breakdown — queryable with no timeline
        attached."""
        mem = self.memory_report()    # refreshes kv.*/mem.* gauges
        snap = self.metrics.snapshot()
        snap["slo"] = self.slo_report()
        snap["memory"] = mem
        if self.prefix is not None:
            # Bounded radix-path digest summary: what a prefix-affinity
            # router needs to know about THIS replica's cached prefixes
            # (rides /snapshot via the monitor for free).
            snap["prefix"] = self.prefix.key_digest()
        snap["profile"] = self.prof.report()
        if self.device is not None:
            snap["device"] = self.device.report()
        if self.sampler is not None:
            # Trailing points only: the full rings stay behind the
            # /timeseries endpoint; snapshots ride merge_snapshots and
            # state dumps, where bounded beats complete.
            snap["timeseries"] = self.sampler.report(points=16)
        if self.alerts is not None:
            snap["alerts"] = self.alerts.report()
        if self.advisor is not None:
            snap["advice"] = self.advisor.recommend()
        return snap

    def memory_report(self) -> dict:
        """Where the memory is: the paged KV pool by state (free /
        referenced / cached, in blocks AND device bytes derived from the
        cache dtype/shape) and the host-side observability footprint
        (registry instruments, trace ring + SLO window, event-log file,
        prefix radix index).  Also refreshes the ``kv.*`` / ``mem.*``
        gauges so a scrape sees the same numbers."""
        free = self.pool.free_count()
        referenced = self.pool.ref_count()
        cached = self.pool.cached_count()
        bb = self._block_bytes
        sbb = self._shard_block_bytes
        kv = {
            "block_bytes": bb,
            "total_bytes": bb * self.pool.n_blocks,
            # pool by pool (k and v, or latent / index / window): what
            # one block holds and what the whole pool does
            "pools": {name: {"block_bytes": b,
                             "total_bytes": b * self.pool.n_blocks}
                      for name, b in self._pool_block_bytes.items()},
            "free_blocks": free, "free_bytes": free * bb,
            "referenced_blocks": referenced,
            "referenced_bytes": referenced * bb,
            "cached_blocks": cached, "cached_bytes": cached * bb,
            # per-chip view of the same pool (logical / tp_size; block
            # *counts* are per-chip already — every chip maps every
            # block, each holding its own head slice)
            "tp_size": self.tp_size,
            "shard_block_bytes": sbb,
            "shard_total_bytes": sbb * self.pool.n_blocks,
            "shard_free_bytes": free * sbb,
            "shard_referenced_bytes": referenced * sbb,
            "shard_cached_bytes": cached * sbb,
        }
        # host side: getsizeof-level approximations — trend lines for
        # leak spotting, not byte-exact accounting
        trace_ring = sum(sys.getsizeof(t) for t in
                         list(self.traces.values()))
        trace_ring += len(self.slo) * 128    # SLO ring holds Trace refs
        log = self.metrics.active_event_log()
        try:
            log_bytes = (os.path.getsize(log.path)
                         if log is not None else 0)
        except OSError:
            log_bytes = 0
        host = {
            "registry_bytes": self.metrics.approx_footprint_bytes(),
            "trace_ring_bytes": trace_ring,
            "event_log_bytes": log_bytes,
            "prefix_index_bytes": (self.prefix.approx_footprint_bytes()
                                   if self.prefix is not None else 0),
        }
        self.metrics.gauge("kv.free_blocks").set(free)
        self.metrics.gauge("kv.free_bytes").set(free * bb)
        self.metrics.gauge("kv.referenced_blocks").set(referenced)
        self.metrics.gauge("kv.referenced_bytes").set(referenced * bb)
        self.metrics.gauge("kv.cached_blocks").set(cached)
        self.metrics.gauge("kv.cached_bytes").set(cached * bb)
        self.metrics.gauge("kv.shard_free_bytes").set(free * sbb)
        self.metrics.gauge("kv.shard_referenced_bytes").set(
            referenced * sbb)
        self.metrics.gauge("kv.shard_cached_bytes").set(cached * sbb)
        self.metrics.gauge("mem.registry_bytes").set(
            host["registry_bytes"])
        self.metrics.gauge("mem.trace_ring_bytes").set(trace_ring)
        self.metrics.gauge("mem.event_log_bytes").set(log_bytes)
        self.metrics.gauge("mem.prefix_index_bytes").set(
            host["prefix_index_bytes"])
        return {"kv": kv, "host": host}

    def slo_report(self) -> dict:
        """The SLO window's answer to "are we meeting SLOs *now*":
        goodput, status mix, and windowed TTFT/TPOT/E2E percentiles over
        the last ``slo_window`` terminal requests."""
        return self.slo.report()

    def state_dump(self) -> str:
        """Human-readable scheduler state (the watchdog's evidence):
        uptime / step totals, per-state slot and terminal-status
        counts, pool and prefix-cache pictures, every queued and live
        request, and the metrics snapshot — a full postmortem."""
        states = {FREE: 0, PREFILL: 0, DECODE: 0}
        for s in self._slots:
            states[s.state] += 1
        by_status: dict[str, int] = {}
        for r in self.results.values():
            by_status[r.status] = by_status.get(r.status, 0) + 1
        lines = [
            f"rank={metrics_mod.current_rank()} pid={os.getpid()} "
            f"step={self.step_index} uptime_s="
            f"{time.monotonic() - self._t0:.3f} "
            f"queue_depth={len(self._queue)} "
            f"free_blocks={len(self._free_blocks)}/"
            f"{self.pool.n_blocks - 1} starve_steps="
            f"{self._starve_steps} counters={self.counters}",
            f"  slots: free={states[FREE]} prefill={states[PREFILL]} "
            f"decode={states[DECODE]}; submitted={self._next_id} "
            f"finished={dict(sorted(by_status.items()))}",
            "  metrics=" + json.dumps(self.metrics_snapshot(),
                                      sort_keys=True),
        ]
        if self.alerts is not None:
            arep = self.alerts.report()
            lines.append(
                f"  alerts: firing={arep['firing']} "
                f"pending={arep['pending']} "
                f"transitions={len(arep['history'])}")
        if self.advisor is not None:
            rec = self.advisor.recommend()
            lines.append(f"  advice: {rec['action']} n={rec['n']} "
                         f"({rec['reason']})")
        bb = self._block_bytes
        lines.append(
            f"  kv bytes: block={bb} free={self.pool.free_count() * bb}"
            f" referenced={self.pool.ref_count() * bb}"
            f" cached={self.pool.cached_count() * bb}"
            f" total={bb * self.pool.n_blocks}"
            f" pools={self._pool_block_bytes}"
            f" tp_size={self.tp_size}"
            f" shard_total="
            f"{self._shard_block_bytes * self.pool.n_blocks}")
        rep = self.prof.report()
        lines.append(
            "  profile (mean ms over last "
            f"{rep['n']} ticks): " + " ".join(
                f"{p}={rep['phases'][p]['mean_s'] * 1e3:.3f}"
                for p in rep["phases"] if "." not in p)
            + f" tick={rep['tick']['mean_s'] * 1e3:.3f}")
        if self.device is not None:
            drep = self.device.report()
            mfu = drep["win"]["mfu"]
            lines.append(
                f"  device: {drep['platform']}/{drep['device_kind']}"
                f" x{drep['n_devices']}"
                f" peak_known={drep['peak_flops_known']}"
                f" mfu={'n/a' if mfu is None else f'{mfu:.4f}'}"
                f" flops/s={drep['win']['flops_per_s']:.3e}"
                f" headroom={drep['win']['overlap_headroom_pct']:.1f}%"
                f" compiles={drep['compiles']}"
                f" retrace_est_s={drep['retrace_compile_est_s']:.3f}")
        lines += ["  " + ln for ln in self.pool.state_lines()]
        if self.snaps is not None:
            lines += ["  " + ln for ln in self.snaps.state_lines()]
        if self.prefix is not None:
            lines.append(
                f"  prefix cache: indexed="
                f"{self.prefix.indexed_blocks()} "
                f"counters={self.prefix_counters} "
                f"stats={self.prefix.stats}")
        for e in self._queue:
            lines.append(
                f"  queued rid={e.rid} prompt={len(e.req.prompt)} "
                f"prior={len(e.prior)} need={self._need_blocks(e.req)} "
                f"retries={e.retries} wait={e.wait_steps} "
                f"queued_steps={e.queued_steps} held_steps={e.held_steps}"
                + ("" if e.held_on is None
                   else f" held_on=block {e.held_on.block}"))
        for i, s in enumerate(self._slots):
            lines.append(
                f"  slot {i}: {s.state}" + (
                    "" if s.state == FREE else
                    f" rid={s.request_id} w={s.w_done}/{s.n_win} "
                    f"out={len(s.out)} budget={s.budget} "
                    f"blocks={s.n_blocks} shared={s.n_hit} "
                    f"retries={s.retries} wait={s.wait_steps}"
                    + (f" block={s.block} denoise_steps={s.block_step} "
                       f"committed={s.n_committed}" if self.block else "")))
        return "\n".join(lines)

    # -- queue -------------------------------------------------------------

    def _positions(self, n: int) -> int:
        """The positions a row of ``n`` tokens writes: ``n``, in whole
        blocks where the model decodes a block a row (its last block is
        written whole though ``max_new_tokens`` cuts it)."""
        return -(-n // self.block) * self.block if self.block else n

    def _need_blocks(self, req: Request) -> int:
        # constant across replays: replay prompt grows by exactly the
        # tokens the remaining budget shrinks by
        return -(-self._positions(len(req.prompt) + req.max_new_tokens)
                 // self.block_size)

    def _row_length(self, s: _Slot) -> int:
        """The host's mirror of a row's device ``length``: what the slot's
        own bookkeeping says its row holds, with no read-back."""
        if s.state == PREFILL:
            return s.base + s.w_done * self.chunk
        if s.state == DECODE:
            if self.block:      # what was prefilled and the blocks committed
                return s.true_len + self.block * s.n_committed
            return s.true_len + len(s.out)
        return 0

    def submit(self, req: Request) -> int:
        """Queue a request; returns its id (key into ``results``).
        Validation happens here so a rejected request never holds a
        queue position."""
        L = len(req.prompt)
        if L < 1 or req.max_new_tokens < 1:
            # Malformed client data (as opposed to caller programming
            # errors below, which still raise): reject with the same
            # terminal-status contract the queue-overflow shed and the
            # router's admission-control shed use, so one status check
            # covers every "the fleet would not serve this" path.
            return self._reject_submit(req, L)
        if req.temperature not in (None, 0.0) or req.sample_key is not None:
            if self.block:
                raise ValueError(
                    "a model that generates by diffusion over blocks is "
                    "served greedily: the unmask rule keeps the argmax of "
                    "the most confident positions, and a sampled request "
                    "has no place in it")
            raise ValueError(
                "ServeEngine is greedy-only; serve sampled requests "
                "through ContinuousBatcher")
        if req.prefix is not None:
            raise ValueError(
                "ServeEngine does not splice prefix caches yet; use "
                "ContinuousBatcher for prefix requests")
        if req.slo_s is not None and req.slo_s <= 0:
            raise ValueError(f"slo_s must be positive, got {req.slo_s}")
        if self._positions(L + req.max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt {L} + max_new_tokens {req.max_new_tokens} "
                f"exceeds max_len {self.max_len}")
        n_win = -(-L // self.chunk)
        if n_win * self.chunk > self.max_len:
            raise ValueError(
                f"prompt {L} padded to {n_win * self.chunk} prefill "
                f"windows exceeds max_len {self.max_len}")
        need = self._need_blocks(req)
        if need > self.pool.n_blocks - 1:
            raise ValueError(
                f"request needs {need} cache blocks but the pool only "
                f"has {self.pool.n_blocks - 1} allocatable")
        rid = self._next_id
        self._next_id += 1
        now = time.monotonic()
        deadline = None if req.deadline_s is None else now + req.deadline_s
        slo_deadline = None if req.slo_s is None else now + req.slo_s
        self._queue.append(_QueueEntry(rid=rid, req=req,
                                       deadline=deadline,
                                       slo_deadline=slo_deadline))
        self.traces[rid] = Trace(rid=rid, enqueue_ts=now,
                                 enqueue_step=self.step_index)
        self._maybe_open_trace(req, rid, self.traces[rid], now)
        self._slo_targets[rid] = req.slo_s
        self.metrics.counter("serve.requests_submitted").inc()
        self.metrics.event("serve.submit", rid=rid, step=self.step_index,
                           prompt_len=L,
                           max_new_tokens=req.max_new_tokens)
        if self.timeline is not None:
            self.timeline.async_start("serving.requests", "REQ", rid)
        return rid

    def _maybe_open_trace(self, req: Request, rid: int, tr: Trace,
                          now: float) -> None:
        """Join the causal tracing plane at submit: adopt a propagated
        context (the router's ``replica.attempt`` span) as parent, or
        head-sample an engine-origin root keyed on ``serve:<rid>`` —
        a pure function of (seed, rid), so sampling decisions replay
        bit-identically (HVD010)."""
        ctx = getattr(req, "trace_ctx", None)
        if ctx is not None:
            sctx = ctx.child("serve.request")
            tr.parent_span_id = ctx.span_id
        elif self._trace_fraction > 0.0:
            sctx = tracing_mod.TraceContext.root(
                f"serve:{rid}", "serve.request",
                self._trace_fraction, self._trace_seed)
            if sctx is None:
                return
            tracing_mod.count_sampled(self.metrics)
        else:
            return
        tr.trace_id = sctx.trace_id
        tr.span_id = sctx.span_id
        self.tracer.span_open(sctx, "serve.request", now,
                              parent_id=tr.parent_span_id, rid=rid)

    def _reject_submit(self, req: Request, L: int) -> int:
        """Terminal ``REJECTED`` for a request invalid on its face
        (empty prompt, non-positive budget).  It gets a real rid, a
        trace, and the full submit/reject event pair — never a queue
        position — so callers poll ``results`` exactly as they would
        for a load-shed request."""
        rid = self._next_id
        self._next_id += 1
        now = time.monotonic()
        self.traces[rid] = Trace(rid=rid, enqueue_ts=now,
                                 enqueue_step=self.step_index)
        self._maybe_open_trace(req, rid, self.traces[rid], now)
        self._slo_targets[rid] = req.slo_s
        self.metrics.counter("serve.requests_submitted").inc()
        self.metrics.event("serve.submit", rid=rid, step=self.step_index,
                           prompt_len=L,
                           max_new_tokens=req.max_new_tokens)
        if self.timeline is not None:
            self.timeline.async_start("serving.requests", "REQ", rid)
        self._finish_queued(_QueueEntry(rid=rid, req=req), REJECTED)
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a request in ANY live state — queued, prefilling, or
        decoding.  Its result becomes ``CANCELLED`` with tokens-so-far;
        blocks return to the pool on the same ``_set_row`` program
        retirement uses.  Returns False when ``rid`` is unknown or
        already terminal (cancel-after-finish is not an error)."""
        for i, e in enumerate(self._queue):
            if e.rid == rid:
                self._queue.pop(i)
                self._finish_queued(e, CANCELLED)
                return True
        for slot, s in enumerate(self._slots):
            if s.state != FREE and s.request_id == rid:
                self._terminate(slot, CANCELLED)
                return True
        return False

    # -- scheduling --------------------------------------------------------

    def _map_row(self, slot: int, row: np.ndarray, length: int,
                 snaps: np.ndarray | None = None) -> None:
        """The table write (``_set_row``): slot ``slot`` maps ``row`` at
        ``length``; under a snapshot budget also the entry of each of the
        row's blocks (none, by default)."""
        budget = ()
        if self.snaps is not None:
            budget = (jnp.asarray(self._no_snaps if snaps is None
                                  else snaps),)
        if self.device is not None:
            self.device.dispatch("set_row",
                                 h2d_bytes=row.nbytes * (1 + len(budget)) + 8)
        self.pcache = self._set_row(
            self.pcache, jnp.asarray(slot, jnp.int32), jnp.asarray(row),
            jnp.asarray(length, jnp.int32), *budget)

    def _grant_snapshots(self, s: _Slot, blocks: list[int], n_hit: int,
                         matched: list[int], L: int) -> np.ndarray:
        """The snapshot entries of an admitted row's blocks: the one its
        state is restored from (the hit's last block) and the ones its
        prefill is to write, asked of the budget where a snapshot is known
        to be wanted — at the deepest block the index matched for this
        prompt and still holds, where none is held or on its way (for the
        block that is indexed, not the row's recomputed copy: the next
        bearer of this prefix hits as soon as the write is dispatched), and
        at the prompt's last full block (a replay, a next turn: without
        evidence yet, so it takes no entry that was restored from).  A
        request the budget refuses is served all the same."""
        snaps = self._no_snaps.copy()
        # what the budget cost this admission: matched, and kept
        self.metrics.counter("prefix.blocks_matched").inc(len(matched))
        self.metrics.counter("prefix.blocks_restored").inc(n_hit)
        if n_hit:
            snaps[n_hit - 1] = self.snaps.entry(blocks[n_hit - 1])
        wants = {}
        # the blocks matched beyond the hit carry no reference of this
        # row's: the eviction that made room for this very admission may
        # have freed them (and handed them back to this row at another
        # index), so the entry is asked for the deepest that is still
        # indexed, and for none where none is
        deep = len(matched)
        while deep > n_hit and matched[deep - 1] not in self.prefix:
            deep -= 1
        if deep > n_hit and not self.snaps.wanted(matched[deep - 1]):
            wants[deep - 1] = (matched[deep - 1], True)
        last = L // self.block_size - 1
        if last >= n_hit:
            wants.setdefault(last, (blocks[last], False))
        for i, (block, shared) in wants.items():
            entry = self.snaps.grant(block, on_evidence=shared)
            if entry is not None:
                snaps[i] = entry
                s.snap_pending.append((i, entry))
        return snaps

    def _commit_snapshots(self, s: _Slot, length: int) -> None:
        """The row's prefill is dispatched up to ``length``: the entries
        whose block ends lie within it are their blocks' from here on."""
        still = []
        for i, entry in s.snap_pending:
            if (i + 1) * self.block_size <= length:
                self.snaps.commit(entry)
            else:
                still.append((i, entry))
        s.snap_pending = still

    def _index_written(self, s: _Slot, length: int) -> None:
        """The row's prefill is dispatched up to ``length``: the blocks it
        fills join the radix index, hits from here on for whatever is
        dispatched later (after ``_commit_snapshots``: a block's entry is
        its own before the block can be matched)."""
        upto = length // self.block_size
        for i in range(s.n_indexed, upto):
            if s.nodes[i] is not None:
                self.prefix.written(s.nodes[i], s.blocks[i])
        s.n_indexed = upto

    def _hold(self, e: _QueueEntry) -> None:
        """Pass a candidate over for this step: a live row is writing what
        it would otherwise prefill again."""
        if e.held_steps == 0:
            self.metrics.counter("prefix.admissions_held").inc()
        e.held_steps += 1
        self.metrics.counter("prefix.held_steps").inc()

    def _admit_entry(self, e: _QueueEntry, slot: int,
                     hit: list[int] | None = None,
                     matched: list[int] | None = None) -> None:
        """Map a queue entry into a free slot.  ``hit`` is the
        prefix-cache match (already referenced by ``acquire``): its
        blocks lead the row's block table and prefill starts at the
        first position past them — the match is capped so the write
        frontier always lands in a freshly allocated private block
        (the COW rule; see :mod:`horovod_tpu.prefix_cache`).  ``matched``
        is every block the index matched, which under a snapshot budget
        may be more than the hit (it was rounded down)."""
        hit = hit or []
        prompt = list(e.req.prompt) + list(e.prior)
        L = len(prompt)
        need = self._need_blocks(e.req)
        base = len(hit) * self.block_size
        s = self._slots[slot]
        blocks = list(hit)
        for _ in range(need - len(hit)):
            b = self.pool.alloc()
            self.pool.incref(b)
            blocks.append(b)
        row = self._trash_row.copy()
        row[:need] = blocks
        snaps = None
        if self.snaps is not None and self.prefix is not None:
            snaps = self._grant_snapshots(s, blocks, len(hit),
                                          matched or hit, L)
        self._map_row(slot, row, base, snaps)
        if self.prefix is not None:
            s.nodes = self.prefix.reserve(prompt, blocks)
            s.n_indexed = len(hit)
        # A model that decodes a block a row prefills the prompt's whole
        # blocks; the trailing `L mod block` tokens are the given positions
        # of the first generated block, whose keys depend on what is
        # generated (and no logits of the prefill seed anything).
        tail = L % self.block if self.block else 0
        rem = L - tail - base             # tokens still to prefill
        n_win = -(-rem // self.chunk)     # >= 1 but for a row of blocks
        padded = np.zeros((1, n_win * self.chunk), np.int32)
        padded[0, :rem] = prompt[base:L - tail]
        s.state = PREFILL if n_win else DECODE
        s.request_id = e.rid
        s.padded = padded
        s.n_win = n_win
        s.w_done = 0
        s.true_len = L - tail
        if self.block:
            s.done_blocks, s.done_steps = list(e.blocks), list(e.steps)
            self._next_block(s, prompt[L - tail:])
        s.base = base
        s.n_hit = len(hit)
        s.budget = e.req.max_new_tokens - len(e.prior)
        s.eos = e.req.eos_id
        s.out = []
        s.n_blocks = need
        s.blocks = blocks
        s.req = e.req
        s.prior = list(e.prior)
        s.retries = e.retries
        s.wait_steps = 0
        s.deadline = e.deadline
        s.slo_deadline = e.slo_deadline
        s.admit_seq = self._admit_seq
        # drafting state seeds from the full replay context (prompt +
        # prior); emitted tokens extend it as they land
        s.draft = (drafting_mod.NgramDraftState(prompt)
                   if self.spec else None)
        self._admit_seq += 1
        tr = self.traces.get(e.rid)
        if tr is not None:
            if tr.admit_ts is None:       # first admission only: replay
                tr.admit_ts = time.monotonic()   # re-admits don't re-queue
                tr.admit_step = self.step_index
                self.metrics.histogram("serve.queue_wait_s").observe(
                    tr.admit_ts - tr.enqueue_ts)
            tr.prefix_tokens_skipped += base
        self._event("admit", slot, e.rid)
        if hit:
            self.prefix_counters["hits"] += 1
            self.prefix_counters["blocks_reused"] += len(hit)
            self.prefix_counters["tokens_skipped"] += base
            self._event("hit", slot, e.rid)

    def _admit_ready(self) -> tuple[int, int | None]:
        """Policy-ordered admission: move queued requests into free
        slots while both a slot and enough cache blocks are available.
        ``self.policy.admission_order`` decides the order candidates
        are considered (FIFO by default), and head-of-line blocking on
        BLOCK pressure applies to the first block-starved candidate in
        that order — which is what feeds the preemption trigger, so the
        policy decides who waits under pressure.  Note the order is a
        liveness/fairness lever ONLY: per-request output determinism is
        pinned by the policy-interface contract — row independence plus
        greedy determinism (scheduler invariant 2) make every request's
        tokens bit-identical to its solo run under ANY admission order
        or victim choice, so a policy can never change what anyone's
        output is, only when it arrives.  Entries serving a retry
        backoff are skipped past.  With the prefix cache on, each
        candidate first longest-prefix-matches (``serve.cache`` faults
        quarantine to that request alone — shared blocks are untouched)
        and zero-ref cached blocks are evicted LRU-leaf-first to cover
        any shortfall before the head counts as starved.  A candidate
        whose prompt goes on into blocks that a live row is admitted to
        write and has not (or, under a snapshot budget, whose matched
        block has an entry granted and not committed) is **held**: passed
        over for this step as one in backoff is, with no slot, no block
        and no part in the starvation count, and looked at again the next
        — the hit is a few chunks away, or its writer is freed and the
        node it waits on goes.  Returns
        ``(admitted, starved_need)`` — the NEW block count the stalled
        head needs (its cache hit already discounted), or None when
        nothing block-starved."""
        admitted = 0
        for e in self.policy.admission_order(self._queue):
            free = [j for j, s in enumerate(self._slots)
                    if s.state == FREE]
            if not free:
                return admitted, None
            if e.wait_steps > 0:          # admit-retry backoff
                continue
            if e.held_on is not None:
                if self.prefix.on_its_way(e.held_on):
                    self._hold(e)
                    continue
                e.held_on = None
            need = self._need_blocks(e.req)
            hit: list[int] | None = []
            if self.prefix is not None:
                try:
                    self.faults.check("serve.cache", key=e.rid)
                    with self.prof.sub("admit.cache_acquire"):
                        hit = self.prefix.acquire(
                            list(e.req.prompt) + list(e.prior))
                except Exception as exc:
                    # quarantine: nothing was referenced, the index and
                    # every shared block are intact — only this request
                    # retries or fails
                    if (isinstance(exc, faults_mod.PermanentFault)
                            or e.retries >= self.max_retries):
                        self._queue.remove(e)
                        self._finish_queued(e, FAILED, exc)
                    else:
                        e.retries += 1
                        e.wait_steps = 2 ** e.retries
                        self._bump_counter("retries")
                        self._event("retry", -1, e.rid)
                    continue
                if hit is None:           # a deeper hit is on its way
                    e.held_on = self.prefix.awaited
                    self._hold(e)
                    continue
                short = (need - len(hit)) - self.pool.free_count()
                if short > 0:             # cache evicts before rows do
                    self.prefix_counters["evictions"] += \
                        self.prefix.evict(short)
            if need - len(hit) > len(self._free_blocks):
                if hit:                   # hit blocks re-park in LRU
                    self.prefix.release(reversed(hit))
                return admitted, need - len(hit)
            try:
                self.faults.check("serve.admit", key=e.rid)
            except Exception as exc:
                if hit:
                    self.prefix.release(reversed(hit))
                if (isinstance(exc, faults_mod.PermanentFault)
                        or e.retries >= self.max_retries):
                    self._queue.remove(e)
                    self._finish_queued(e, FAILED, exc)
                else:
                    e.retries += 1
                    e.wait_steps = 2 ** e.retries
                    self._bump_counter("retries")
                    self._event("retry", -1, e.rid)
                continue
            self._queue.remove(e)
            self._admit_entry(e, free[0], hit,
                              self.prefix.last_match
                              if self.prefix is not None else None)
            admitted += 1
        return admitted, None

    def _replay_len(self, s: _Slot) -> int:
        return len(s.req.prompt) + len(s.prior) + len(s.out)

    def _replayable(self, s: _Slot) -> bool:
        # the replay prompt must still fit the chunked-prefill padding
        n_win = -(-self._replay_len(s) // self.chunk)
        return n_win * self.chunk <= self.max_len

    def _release_row_blocks(self, s: _Slot, *, register: bool) -> None:
        """Drop a retiring row's block references.  With the prefix
        cache on the blocks its dispatched chunks filled are indexed
        already (``_index_written``); the nodes of those it has not
        written leave the tree, and with ``register`` set (OK retirement
        or a requeue whose KV is known-good) the rest of its fully written
        blocks, the answer's, join the index first — release-to-cache — so
        zero-ref blocks park in LRU order instead of freeing.  Without
        ``register`` (a FAILED / expired row whose frontier is not
        trusted) nothing more is indexed; what is not indexed (all of it
        with the cache off) drops straight back toward the free list, in
        the classic order."""
        for _, entry in s.snap_pending:   # writes that will not come
            self.snaps.cancel(entry)
        if self.prefix is not None:
            self.prefix.forget(s.nodes[s.n_indexed:])
            if register and s.req is not None:
                toks = (list(s.req.prompt) + list(s.prior) + list(s.out))
                self.prefix.insert(toks, s.blocks, self._row_length(s))
        for b in reversed(s.blocks):
            self.pool.decref(b)

    def _requeue(self, slot: int, *, retried: bool) -> None:
        """Free a row and put its request back in the queue with
        ``prompt + out`` as the replay prompt (preemption, or a decode
        retry — which replays rather than re-ticking because the faulted
        tick already advanced the row's cache position).  With the
        prefix cache on the row's KV releases to cache, so the replay
        re-admits through a longest-prefix hit and is nearly free."""
        s = self._slots[slot]
        entry = _QueueEntry(
            rid=s.request_id, req=s.req,
            prior=list(s.prior) + list(s.out),
            retries=s.retries + (1 if retried else 0),
            wait_steps=2 ** (s.retries + 1) if retried else 0,
            deadline=s.deadline,
            slo_deadline=s.slo_deadline,
            blocks=s.done_blocks, steps=s.done_steps)
        if self.block and s.state == DECODE and not s.fresh:
            # the block in flight is dropped and denoised again
            self._c_block["redone"].inc()
        self._release_row_blocks(s, register=True)
        self._map_row(slot, self._trash_row, 0)
        self._slots[slot] = _Slot()
        self._queue.append(entry)

    def _preempt(self, need: int) -> int:
        """Free blocks for a starved head: evict zero-ref cached blocks
        first (they hold no live work), then preempt the policy's
        victims — FIFO evicts youngest, EDF the slack-richest (largest
        time-to-SLO-deadline, i.e. least-regretted), priority the
        lowest-priority — until ``need`` blocks are free (or no
        candidate remains).  Preempted requests re-queue for replay;
        greedy determinism makes their resumed output bit-identical
        whoever is chosen.  A preempted row's blocks release-to-cache,
        so the loop re-evicts them on the next pass — preemption still
        converges on a cache-on engine."""
        preempted = 0
        while len(self._free_blocks) < need:
            if self.prefix is not None:
                evicted = self.prefix.evict(
                    need - len(self._free_blocks))
                if evicted:
                    self.prefix_counters["evictions"] += evicted
                    continue
            cands = [(i, s) for i, s in enumerate(self._slots)
                     if s.state == DECODE and self._replayable(s)]
            if not cands:
                break
            self._preempt_row(self.policy.victim(cands))
            preempted += 1
        return preempted

    def _preempt_row(self, slot: int) -> None:
        """Take a decoding row off its slot and re-queue it for replay,
        with nothing charged to it."""
        self._event("preempt", slot, self._slots[slot].request_id)
        self._bump_counter("preemptions")
        self._requeue(slot, retried=False)

    def _terminate(self, slot: int, status: str,
                   error: BaseException | None = None) -> RequestResult:
        """Retire a row with a terminal status: blocks back to the pool
        (release-to-cache on a clean OK finish when the prefix cache is
        on), row to the trash block (the same fixed-signature table
        write for every status — OK, TIMEOUT, CANCELLED, FAILED)."""
        s = self._slots[slot]
        res = RequestResult(list(s.prior) + list(s.out), status, error)
        if self.block:
            res.blocks, res.unmask_steps = s.done_blocks, s.done_steps
        self.results[s.request_id] = res
        self._finished[s.request_id] = res
        self._finalize_trace(s.request_id, res)
        self._release_row_blocks(s, register=status == OK)
        self._map_row(slot, self._trash_row, 0)
        kind = {OK: "recycle", TIMEOUT: "timeout",
                CANCELLED: "cancel", FAILED: "fail"}[status]
        self._event(kind, slot, s.request_id)
        self._bump_status(status)
        self._slots[slot] = _Slot()
        return res

    def _finish_queued(self, e: _QueueEntry, status: str,
                       error: BaseException | None = None) -> None:
        """Terminal result for a request that never (re)entered a slot:
        tokens-so-far is whatever a previous stint emitted."""
        res = RequestResult(list(e.prior), status, error)
        if self.block:
            res.blocks, res.unmask_steps = e.blocks, e.steps
        self.results[e.rid] = res
        self._finished[e.rid] = res
        self._finalize_trace(e.rid, res)
        kind = {TIMEOUT: "timeout", CANCELLED: "cancel",
                REJECTED: "reject", FAILED: "fail"}[status]
        self._event(kind, -1, e.rid)
        self._bump_status(status)

    def _bump_status(self, status: str) -> None:
        key = {TIMEOUT: "timeouts", CANCELLED: "cancellations",
               REJECTED: "rejections", FAILED: "failures"}.get(status)
        if key is not None:
            self._bump_counter(key)

    def _bump_counter(self, key: str) -> None:
        """Advance a lifecycle counter in ``self.counters`` AND its
        mirror in the metrics registry, so both always agree (the event
        log's replay invariant is pinned against ``self.counters``)."""
        self.counters[key] += 1
        self.metrics.counter("serve." + key).inc()

    def _bump_spec(self, key: str, n: int = 1) -> None:
        """Advance a speculation counter in ``self.spec_counters`` AND
        its registry mirror (the ``SPEC`` timeline series keys)."""
        self.spec_counters[key] += n
        self.metrics.counter("serve.spec." + key).inc(n)

    def _finalize_trace(self, rid: int, res: RequestResult) -> None:
        """Terminal bookkeeping for a request's :class:`Trace`: stamp the
        end, attach it to the result (every terminal status — OK, TIMEOUT,
        CANCELLED, REJECTED, FAILED — flows through here), and feed the
        end-to-end latency histograms."""
        tr = self.traces.pop(rid, None)
        if tr is None:
            return
        tr.terminal_ts = time.monotonic()
        tr.terminal_step = self.step_index
        tr.status = res.status
        tr.n_tokens = len(res.tokens)
        res.trace = tr
        self.slo.add(tr, self._slo_targets.pop(rid, None))
        self.metrics.gauge("serve.goodput").set(self.slo.goodput())
        self.metrics.histogram("serve.e2e_s").observe(
            tr.e2e_s, exemplar=tr.trace_id)
        if tr.trace_id is not None:
            self._emit_request_spans(tr)
        tpot = tr.tpot_s
        if tpot is not None:
            self.metrics.histogram("serve.tpot_s").observe(tpot)
        self.metrics.counter("serve.requests_completed").inc()
        if self.timeline is not None:
            self.timeline.async_end("serving.requests", "REQ", rid)

    def _emit_request_spans(self, tr: Trace) -> None:
        """Post-hoc span emission for a sampled request at terminal
        time: ``serve.queue`` / ``serve.prefill`` / ``serve.decode``
        children tiled from the Trace stamps, then the
        ``serve.request`` close.  Phases a request never reached
        (queue-side REJECTED/TIMEOUT) are simply absent."""
        sctx = tracing_mod.TraceContext(tr.trace_id, tr.span_id)
        if tr.admit_ts is not None:
            self.tracer.span(sctx.child("serve.queue"), "serve.queue",
                             tr.enqueue_ts, tr.admit_ts,
                             parent_id=tr.span_id, rid=tr.rid,
                             steps=tr.queue_steps)
            if tr.first_token_ts is not None:
                self.tracer.span(
                    sctx.child("serve.prefill"), "serve.prefill",
                    tr.admit_ts, tr.first_token_ts,
                    parent_id=tr.span_id, rid=tr.rid,
                    chunks=tr.prefill_chunks)
                self.tracer.span(
                    sctx.child("serve.decode"), "serve.decode",
                    tr.first_token_ts, tr.terminal_ts,
                    parent_id=tr.span_id, rid=tr.rid,
                    n_tokens=tr.n_tokens, admit_step=tr.admit_step,
                    terminal_step=tr.terminal_step)
        self.tracer.span(sctx, "serve.request", tr.enqueue_ts,
                         tr.terminal_ts, parent_id=tr.parent_span_id,
                         rid=tr.rid, status=tr.status)

    def _emit_chunk_span(self, tr: Trace, t0: float, t1: float) -> None:
        """One ``serve.prefill_chunk`` span per dispatched prefill
        window of a sampled request, parented under the request's
        ``serve.prefill`` span.  The parent id is *derived* (same
        ``child_span_id`` the close in :meth:`_emit_request_spans`
        uses), so chunks emit before their parent exists and still
        join the tree at reconstruction."""
        prefill_id = tracing_mod.child_span_id(
            tr.trace_id, tr.span_id, "serve.prefill")
        ctx = tracing_mod.TraceContext(
            tr.trace_id,
            tracing_mod.child_span_id(tr.trace_id, prefill_id,
                                      "serve.prefill_chunk",
                                      seq=tr.prefill_chunks))
        self.tracer.span(ctx, "serve.prefill_chunk", t0, t1,
                         parent_id=prefill_id, rid=tr.rid,
                         seq=tr.prefill_chunks)

    def _slot_fault(self, slot: int, exc: BaseException) -> None:
        """Quarantine a prefill-window fault to its own request:
        transient → bounded in-place retry after a ``2**retries``-step
        backoff (the window never ran, so state is intact); permanent or
        retries exhausted → ``FAILED``, everything else keeps serving."""
        s = self._slots[slot]
        if (isinstance(exc, faults_mod.PermanentFault)
                or s.retries >= self.max_retries):
            self._terminate(slot, FAILED, exc)
            return
        s.retries += 1
        s.wait_steps = 2 ** s.retries
        self._bump_counter("retries")
        self._event("retry", slot, s.request_id)

    def _row_fault(self, slot: int, exc: BaseException) -> None:
        """Quarantine a decode-tick readback fault: the faulted tick
        already advanced the row's cache, so a transient retry goes
        through the replay path (free blocks, re-queue with prompt+out —
        greedy determinism reproduces the discarded token exactly);
        permanent or exhausted → ``FAILED``."""
        s = self._slots[slot]
        if (isinstance(exc, faults_mod.PermanentFault)
                or s.retries >= self.max_retries
                or not self._replayable(s)):
            self._terminate(slot, FAILED, exc)
            return
        self._bump_counter("retries")
        self._event("retry", slot, s.request_id)
        self._requeue(slot, retried=True)

    def _expire(self, now: float | None) -> int:
        """Deadline (wall-clock) and queue-budget (step-counted)
        enforcement; returns how many requests terminated."""
        done = 0
        if now is not None:
            i = 0
            while i < len(self._queue):
                e = self._queue[i]
                if e.deadline is not None and now >= e.deadline:
                    self._queue.pop(i)
                    self._finish_queued(e, TIMEOUT)
                    done += 1
                    continue
                i += 1
            for slot, s in enumerate(self._slots):
                if (s.state != FREE and s.deadline is not None
                        and now >= s.deadline):
                    self._terminate(slot, TIMEOUT)
                    done += 1
        return done

    def _event(self, kind: str, slot: int, rid: int) -> None:
        self.events.append(
            SchedulerEvent(kind, self.step_index, slot, rid))
        tr = self.traces.get(rid)
        if tr is not None:
            if kind == "retry":
                tr.retries += 1
            elif kind == "preempt":
                tr.preemptions += 1
        # One structured-log line per scheduler event: counter bumps are
        # 1:1 with _event() calls, so replaying the JSONL reproduces
        # ``self.counters`` exactly (tested in test_metrics.py).
        self.metrics.event("serve." + kind, rid=rid, slot=slot,
                           step=self.step_index)
        if self.timeline is not None:
            self.timeline.instant("serving.scheduler", kind.upper())

    def _check_block_invariants(self) -> None:
        """The ``HVD_TPU_VERIFY_BLOCKS=1`` debug walk: block tables,
        slot bookkeeping and the pool must agree after every step —
        each live row's table row is exactly its block list (trash
        elsewhere), no live row references a freed block or trash,
        every block's pool refcount equals the number of rows mapping
        it, every pool reference belongs to some live row, the radix
        index is structurally sound, and free + cached + referenced
        blocks account for the whole pool."""
        table = np.asarray(self.pcache.block_table)
        free = set(self._free_blocks)
        usage: dict[int, int] = {}
        for slot, s in enumerate(self._slots):
            row = table[slot]
            if s.state == FREE:
                if row.any():
                    raise AssertionError(
                        f"free slot {slot} maps blocks "
                        f"{[int(b) for b in row if b]}")
                continue
            if [int(b) for b in row[:s.n_blocks]] != s.blocks:
                raise AssertionError(
                    f"slot {slot} table row {row[:s.n_blocks]} != "
                    f"bookkeeping {s.blocks}")
            if row[s.n_blocks:].any():
                raise AssertionError(
                    f"slot {slot} maps blocks beyond its "
                    f"{s.n_blocks} allocated")
            for b in s.blocks:
                if b == 0:
                    raise AssertionError(
                        f"slot {slot} maps the trash block")
                if b in free:
                    raise AssertionError(
                        f"live slot {slot} references freed block {b}")
                usage[b] = usage.get(b, 0) + 1
        for b, n in usage.items():
            if self.pool.refcount(b) != n:
                raise AssertionError(
                    f"block {b}: {n} rows map it but pool refcount is "
                    f"{self.pool.refcount(b)}")
        for b in self.pool._ref:
            if b not in usage:
                raise AssertionError(
                    f"block {b} holds {self.pool.refcount(b)} pool "
                    f"references but no live row maps it")
        if self.prefix is not None:
            self.prefix.check_consistency()
        if self.snaps is not None:
            self.snaps.check_consistency()
            for slot, s in enumerate(self._slots):
                # an entry a row is to write at its block `i`'s end is
                # that block's, or the indexed block's of the same tokens
                path = (self.prefix.path_blocks(
                    list(s.req.prompt) + list(s.prior))
                    if s.snap_pending and self.prefix is not None else [])
                for i, entry in s.snap_pending:
                    b = self.snaps.pending_block(entry)
                    if b is not None and b != s.blocks[i] \
                            and b != (path[i:i + 1] or [None])[0]:
                        raise AssertionError(
                            f"slot {slot} writes entry {entry} at its block "
                            f"{i}'s end for block {b}, which holds other "
                            f"tokens")
        total = self.pool.n_blocks - 1
        accounted = (len(free) + self.pool.cached_count()
                     + len(self.pool._ref))
        if accounted != total:
            raise AssertionError(
                f"pool accounting leak: free={len(free)} "
                f"cached={self.pool.cached_count()} "
                f"referenced={len(self.pool._ref)} != {total}")

    def _next_block(self, s: _Slot, given: list[int] = ()) -> None:
        """Give a row its next block: the ``given`` tokens (a prompt's
        tail, in a first block) and the mask id elsewhere, in no tick
        yet."""
        n = len(given)
        s.block = list(given) + [self.cfg.mask_token_id] * (self.block - n)
        s.when = [-1] * n + [None] * (self.block - n)
        s.given, s.block_step, s.fresh = n, 0, True

    def _block_front(self, decoding: list[int]) -> tuple:
        """What the unmask program takes of the host: every decoding
        row's block as the host last read it, the denoise steps it has
        had, and ``go``, the rows whose block the tick in flight ran over
        (the others' goes into its first tick as it stands)."""
        tokens = np.zeros((self.n_slots, self.block), np.int32)
        step = np.zeros((self.n_slots,), np.int32)
        go = np.zeros((self.n_slots,), np.int32)
        for slot in decoding:
            s = self._slots[slot]
            tokens[slot] = s.block
            step[slot] = s.block_step
            go[slot] = not s.fresh
        return tokens, step, go

    def _block_read(self, s: _Slot, ids: list[int], left: int,
                    by_threshold: int) -> list[int]:
        """A decoding row's part of a step's read-back: its block's ids
        after the unmask program, how many are still masked and how many
        the confidence threshold unmasked.  Books the step that unmasked
        each position and the ``diffusion.*`` counters; a block that came
        clean is committed by the tick this step dispatched, and its
        generated tokens (the prompt's tail apart) are what the row
        emits, in order; the row's next block goes in with the next
        tick.  Any other step emits nothing."""
        count = self._c_block
        if s.fresh:         # in its first tick now: nothing was unmasked
            s.fresh = False
            count["denoise"].inc()
            return []
        mask = self.cfg.mask_token_id
        took = [i for i, (was, now) in enumerate(zip(s.block, ids))
                if was == mask and now != mask]
        for i in took:
            s.when[i] = s.block_step
        s.block = ids
        s.block_step += 1
        count["unmasked"].inc(len(took))
        count["by_threshold"].inc(by_threshold)
        count["by_schedule"].inc(len(took) - by_threshold)
        if left:
            count["denoise"].inc()
            return []
        count["commit"].inc()
        s.n_committed += 1
        s.done_blocks.append(s.block)
        s.done_steps.append(s.when)
        emit = s.block[s.given:]
        self._next_block(s)
        return emit

    def _dispatch_chunk(self, group: list[int]) -> Dispatched | None:
        """One chunk program over the next prefill window of each slot of
        ``group`` (as many as one of ``chunk_widths``), and the rows'
        bookkeeping behind it: a row whose last window this is joins the
        step's tick.  An exception out of the program is charged to every
        row of it, as the tick charges its decoding rows; returns the
        program as dispatched, ``None`` where it was not."""
        rows = [self._slots[j] for j in group]
        n = len(rows)
        program = Dispatched(
            self.chunk, tuple(self._row_length(s) for s in rows), (1,) * n)
        toks = np.empty((n, self.chunk), np.int32)
        new_len = np.empty((n,), np.int32)
        sel = np.zeros((n,), np.int32)
        for i, s in enumerate(rows):
            w = s.w_done
            toks[i] = s.padded[0, w * self.chunk:(w + 1) * self.chunk]
            # windows cover prompt[base:] — a prefix-cache hit rewound
            # nothing: the row's length started at base, so positions
            # [0, base) are the shared blocks' KV, never rewritten
            if w == s.n_win - 1:            # the final window
                new_len[i] = s.true_len
                sel[i] = s.true_len - 1 - s.base - w * self.chunk
            else:
                new_len[i] = s.base + (w + 1) * self.chunk
        traces = [self.traces.get(s.request_id) for s in rows]
        traced = any(tr is not None and tr.trace_id is not None
                     for tr in traces)
        t_chunk = time.monotonic() if traced else 0.0
        try:
            self.pcache, self.last_logits = self._chunk(
                self.params, self.pcache, self.last_logits,
                jnp.asarray(toks), jnp.asarray(np.asarray(group, np.int32)),
                jnp.asarray(new_len), jnp.asarray(sel))
        except Exception as exc:
            for j in group:
                self._slot_fault(j, exc)
            return None
        self._c_chunk_programs.inc()
        self._c_chunk_rows.inc(n)
        t_done = time.monotonic() if traced else 0.0
        for i, (s, tr) in enumerate(zip(rows, traces)):
            if self.device is not None:
                # the cost model's chunk is the one-row program: a program
                # of n rows counts as n of it; per row the token window
                # plus three int32 (slot / new_len / sel)
                self.device.dispatch("chunk",
                                     h2d_bytes=toks[i].nbytes + 12)
            s.w_done += 1
            self._commit_snapshots(s, int(new_len[i]))
            if self.prefix is not None:
                self._index_written(s, int(new_len[i]))
            if tr is not None:
                if tr.trace_id is not None:
                    self._emit_chunk_span(tr, t_chunk, t_done)
                tr.prefill_chunks += 1
            if s.w_done == s.n_win:
                s.state = DECODE          # joins this step's tick
        return program

    def step(self) -> dict[int, RequestResult]:
        """One engine step: expire deadlines, admit (preempting for a
        starved head if enabled), run one prefill window per admitting
        slot, then sample every decoding row's token and dispatch one
        decode tick over the pool, which is still running when the step
        returns.  Returns ``{request_id: RequestResult}`` for every
        request that reached a terminal state during the step."""
        # The phases are mark-based: begin() opens the tick in `expire`
        # and each mark() is the boundary at which the named phase
        # starts, so they tile the tick — as spans on the profiler
        # trace and as the durations of the step's row.
        prof = self.prof
        prof.begin(self.step_index)
        try:
            return self._step(prof)
        finally:
            prof.end()      # also closes what an exception left open

    def _step(self, prof: profiler_mod.PhaseSpans
              ) -> dict[int, RequestResult]:
        self._finished = {}
        progress = 0
        # deadlines first: an expired request must not admit or tick
        now = None
        if (any(e.deadline is not None for e in self._queue)
                or any(s.deadline is not None for s in self._slots
                       if s.state != FREE)):
            now = time.monotonic()
        progress += self._expire(now)
        # queue bookkeeping: backoff countdown + admission budgets
        i = 0
        while i < len(self._queue):
            e = self._queue[i]
            if (e.req.max_queue_steps is not None
                    and e.queued_steps >= e.req.max_queue_steps):
                self._queue.pop(i)
                self._finish_queued(e, REJECTED)
                progress += 1
                continue
            e.queued_steps += 1
            tr = self.traces.get(e.rid)
            if tr is not None:
                tr.queue_steps += 1
            if e.wait_steps > 0:
                e.wait_steps -= 1
                progress += 1
            i += 1
        # admit covers _admit_ready + preemption + the prefill windows;
        # the cache lookups and the window dispatch are nested spans
        prof.mark("admit")
        admitted, starved_need = self._admit_ready()
        progress += admitted
        if starved_need is None:
            self._starve_steps = 0
        else:
            self._starve_steps += 1
            if (self.preempt_after is not None
                    and self._starve_steps >= self.preempt_after):
                freed = self._preempt(starved_need)
                if freed:
                    progress += freed
                    self._starve_steps = 0
                    more, _ = self._admit_ready()  # head admits this step
                    progress += more
        # every program this step dispatches, as the slots know it: what
        # the model's own counters are reckoned from
        programs: list[Dispatched] = []
        chunk_rows = 0
        with prof.sub("admit.prefill_dispatch"):
            # the rows that prefill this step; back-off and the fault site
            # are a request's own, and leave it out before any dispatch
            ready = []
            for slot, s in enumerate(self._slots):
                if s.state != PREFILL:
                    continue
                if s.wait_steps > 0:          # prefill-retry backoff
                    s.wait_steps -= 1
                    progress += 1
                    continue
                try:
                    self.faults.check("serve.prefill", key=s.request_id)
                except Exception as exc:
                    self._slot_fault(slot, exc)
                    progress += 1
                    continue
                ready.append(slot)
            # one window each: whole groups of the wide width, the rest
            # a row a program; no served program carries a row that is
            # not there
            at = 0
            for width in self.chunk_widths:
                while len(ready) - at >= width:
                    program = self._dispatch_chunk(ready[at:at + width])
                    if program is not None:
                        programs.append(program)
                        chunk_rows += width
                    at += width
                    progress += width
        n_chunks = len(programs)
        tick_rows = n_tokens = n_first = 0
        decoding = [i for i, s in enumerate(self._slots)
                    if s.state == DECODE]
        spec = self.spec and bool(decoding)
        drafts_host: np.ndarray | None = None
        stats_host: np.ndarray | None = None
        if spec:
            prof.mark("draft")
            # draft phase: each decoding row proposes up to draft_k
            # continuation tokens from its own history; -1 pads can
            # never be accepted (argmax preds are >= 0).  Drafting is
            # an optimization, so a faulting drafter (serve.draft)
            # degrades its row to plain decode for the round — the
            # request never fails or retries over a draft.
            drafts_host = np.full((self.n_slots, self.draft_k), -1,
                                  np.int32)
            for slot in decoding:
                s = self._slots[slot]
                try:
                    self.faults.check("serve.draft", key=s.request_id)
                    prop = (s.draft.propose(self.draft_k)
                            if s.draft is not None else [])
                except Exception:
                    self.metrics.counter("serve.spec.draft_faults").inc()
                    prop = []
                if prop:
                    drafts_host[slot, :len(prop)] = prop
                    self._bump_spec("proposed", len(prop))
        if decoding:
            prof.mark("decode_dispatch")
            # what the read below waits for besides this step's chunks:
            # the tick the last ticking step left in flight, and its rows
            in_flight = self._tick_rows
            blamed = decoding
            tick_exc: Exception | None = None
            try:
                active = np.zeros((self.n_slots,), np.int32)
                active[decoding] = 1
                accept = accept_host = None
                programs.append(Dispatched(
                    self.draft_k + 1 if spec else self.block or 1,
                    np.array([self._row_length(s) for s in self._slots]),
                    active))
                if spec:
                    # lock-step: the tokens and the accepted counts are
                    # the verify program's own result, and so are the
                    # counters behind them
                    tok, accept, self.last_logits, self.pcache = \
                        self._spec_tick(
                            self.params, self.pcache, self.last_logits,
                            jnp.asarray(drafts_host),
                            jnp.asarray(active))
                    stats = self.model.paged_counters(self.pcache)
                elif self.block:
                    # a block a row: the unmask program in front reads the
                    # logits the last tick left and hands the host the
                    # blocks' new ids; the tick behind it takes them, and
                    # which rows commit, straight from the device, and is
                    # left in flight as below
                    prof.mark("unmask")
                    tok, left, by_threshold, commit, stats = self._unmask(
                        self.block_logits,
                        *(jnp.asarray(a) for a in
                          self._block_front(decoding)),
                        self.model.paged_counters(self.pcache))
                    prof.mark("decode_dispatch")
                    self.block_logits, self.pcache = self._tick(
                        self.params, self.pcache, self.block_logits, tok,
                        jnp.asarray(active), commit)
                    self._tick_rows = {
                        slot: self._slots[slot].request_id
                        for slot in decoding}
                    left.copy_to_host_async()
                    by_threshold.copy_to_host_async()
                else:
                    # the step's tokens (and the model's device-side
                    # counters, None for a model that keeps none) come
                    # from the sampling program in front of the tick;
                    # nothing the tick returns is read in this step, so
                    # it runs while the host goes on: postprocess,
                    # bookkeeping, the caller's turn and the next step's
                    # admissions and dispatches, up to that step's read
                    tok, stats = self._sample(
                        self.last_logits,
                        self.model.paged_counters(self.pcache))
                    self.last_logits, self.pcache = self._tick(
                        self.params, self.pcache, self.last_logits,
                        jnp.asarray(active))
                    self._tick_rows = {
                        slot: self._slots[slot].request_id
                        for slot in decoding}
                # on their way to the host as soon as they are computed
                tok.copy_to_host_async()
                if stats is not None:
                    stats.copy_to_host_async()
                if self.device is not None:
                    if not spec:
                        self.device.dispatch(
                            "unmask" if self.block else "sample")
                    self.device.dispatch(
                        "spec_tick" if spec else "tick",
                        h2d_bytes=active.nbytes + (
                            drafts_host.nbytes if spec else 0))
            except Exception as exc:
                tick_exc = exc
            if tick_exc is None:
                # what the model reckons from the dispatched programs
                # alone is reckoned here, while the device runs them (at
                # the step's end the device would wait for it), and
                # outside the tick's fault handling: host code of the
                # metrics is no fault of a row's
                self.model.publish_paged_metrics(
                    self.metrics, self.cfg, self.pcache, None, (),
                    tuple(programs))
                programs.clear()
                try:
                    # np.asarray on the device token array is the
                    # readback boundary: everything dispatched in front
                    # of the tokens' program must complete first (the
                    # tick in flight and this step's chunks; on a spec
                    # engine this step's verify too), so this wait is
                    # the device-time share.  Tokens that are ready
                    # before the host asks mean the device ran out of
                    # work first: the host set this step's pace.
                    prof.mark("device_sync")
                    if tok.is_ready():
                        self.metrics.counter("serve.step.host_bound").inc()
                    t_sync0 = time.perf_counter()
                    tok_host = np.asarray(tok)
                    if spec:
                        accept_host = np.asarray(accept)
                    if self.block:
                        left_host = np.asarray(left)
                        threshold_host = np.asarray(by_threshold)
                    stats_host = (None if stats is None
                                  else np.asarray(stats))
                    if self.device is not None:
                        # split the measured readback wait into the cost
                        # model's predicted device time of the programs
                        # it waited for (whole, though the tick in
                        # flight began a step ago: an upper bound) vs
                        # host stall; the profiler gets the same split
                        # as nested device_sync.* intervals so phase
                        # tables can show where the wait went.
                        t_sync1 = time.perf_counter()
                        d2h = tok_host.nbytes + (
                            accept_host.nbytes
                            if accept_host is not None else 0)
                        awaited = ["chunk"] * chunk_rows
                        if spec:
                            awaited.append("spec_tick")
                        elif in_flight:
                            awaited.append("tick")
                        est, stall = self.device.on_sync(
                            awaited, t_sync0, t_sync1, d2h_bytes=d2h)
                        prof.add("device_sync.compute_est",
                                 t_sync0, t_sync0 + est)
                        prof.add("device_sync.host_stall",
                                 t_sync0 + est, t_sync1)
                    # spec engines account their acceptance/emission
                    # loop as `verify`; plain engines keep the classic
                    # name
                    prof.mark("verify" if spec else "sample_postprocess")
                except Exception as exc:
                    tick_exc = exc
                    if not spec:
                        # the read waited for nothing of this step's
                        # tick: the fault is of the tick in flight, and
                        # of the rows that decoded in it
                        blamed = [
                            slot for slot in decoding
                            if in_flight.get(slot)
                            == self._slots[slot].request_id] or decoding
            if tick_exc is not None:
                # a whole-tick failure cannot be attributed to one row;
                # quarantine every row of the tick at fault (transients
                # replay).  A row that joined with this step has lost
                # its token with the read, and its tick has advanced it:
                # it replays like a preempted row, with nothing charged
                for slot in decoding:
                    if slot in blamed:
                        self._row_fault(slot, tick_exc)
                    else:
                        self._preempt_row(slot)
                progress += len(decoding)
            else:
                progress += len(decoding)
                tick_rows = len(decoding)
                if spec:
                    self._bump_spec("rounds")
                for slot in decoding:
                    s = self._slots[slot]
                    # what the step read of the row: its token, or the ids
                    # of its block
                    emit = ([int(t) for t in tok_host[slot]] if self.block
                            else [int(tok_host[slot])])
                    if accept_host is not None:
                        acc = int(accept_host[slot])
                        emit += [int(x) for x in
                                 drafts_host[slot, :acc]]
                        self._bump_spec("row_rounds")
                        self._bump_spec("accepted", acc)
                        self.metrics.histogram(
                            "serve.spec.accepted_per_round").observe(acc)
                    try:
                        self.faults.check("serve.tick", key=s.request_id)
                        for t in emit:
                            if not 0 <= t < self.cfg.vocab_size:
                                raise faults_mod.PermanentFault(
                                    "serve.tick", s.request_id, -1)
                    except Exception as exc:
                        self._row_fault(slot, exc)
                        continue
                    if self.block:
                        # a row of blocks emits nothing but a block that
                        # came clean: its generated tokens, in order
                        emit = self._block_read(
                            s, emit, int(left_host[slot]),
                            int(threshold_host[slot]))
                    if emit and not s.prior and not s.out:
                        n_first += 1
                        tr = self.traces.get(s.request_id)
                        if tr is not None and tr.first_token_ts is None:
                            tr.first_token_ts = time.monotonic()
                            self.metrics.histogram(
                                "serve.ttft_s").observe(tr.ttft_s)
                    # accepted drafts emit in order behind the
                    # unconditional token; a terminal token (budget or
                    # eos) discards the rest of the round — the row's
                    # over-advanced device length dies with the slot
                    for t in emit:
                        s.out.append(t)
                        n_tokens += 1
                        s.budget -= 1
                        if s.draft is not None:
                            s.draft.extend((t,))
                        if s.budget <= 0 or t == s.eos:
                            self._terminate(slot, OK)
                            break
        prof.mark("bookkeeping")
        if self._tick_rows and not self.pending():
            # going idle with a tick in flight: its counters are read
            # here, behind it (the one wait for a tick's own result), so
            # that the registry's totals are whole at the end of a drain.
            # A fault of that tick raises from here: every row of it has
            # been answered, so there is none to charge it to.
            self._tick_rows = {}
            final = self.model.paged_counters(self.pcache)
            if final is not None:
                stats_host = np.asarray(final)
        if programs or stats_host is not None:
            self.model.publish_paged_metrics(
                self.metrics, self.cfg, self.pcache, stats_host,
                tuple(s.n_blocks for s in self._slots if s.state != FREE),
                programs)
        if self.timeline is not None:
            self.timeline.counter(
                "serving.scheduler", "SCHED",
                {"queued": len(self._queue),
                 "decoding": len(decoding),
                 "prefilling": sum(1 for s in self._slots
                                   if s.state == PREFILL),
                 "free_blocks": len(self._free_blocks)})
            self.timeline.counter(
                "serving.scheduler", "LIFECYCLE", dict(self.counters))
            if self.spec:
                self.timeline.counter(
                    "serving.scheduler", "SPEC",
                    dict(self.spec_counters))
            if self.prefix is not None:
                self.timeline.counter(
                    "serving.scheduler", "PREFIX",
                    dict(self.prefix_counters))
        # Registry mirror of the SCHED track: occupancy gauges sampled
        # once per step, plus the step odometer — available with no
        # timeline attached (the scrape path).
        self.metrics.counter("serve.steps").inc()
        if n_tokens:
            self.metrics.counter("serve.tokens_emitted").inc(n_tokens)
        self.metrics.gauge("serve.queue_depth").set(len(self._queue))
        self.metrics.gauge("serve.decoding").set(len(decoding))
        self.metrics.gauge("serve.prefilling").set(
            sum(1 for s in self._slots if s.state == PREFILL))
        self.metrics.gauge("serve.free_blocks").set(len(self._free_blocks))
        prof.counts(chunks=n_chunks, chunk_rows=chunk_rows,
                    tick_rows=tick_rows, tokens=n_tokens,
                    first_tokens=n_first)
        self.metrics.gauge("serve.cached_blocks").set(
            self.pool.cached_count())
        if self.prefix is not None:
            self.metrics.gauge("serve.prefix_indexed_blocks").set(
                self.prefix.indexed_blocks())
        # KV pool accounting in blocks and bytes, refreshed per step so
        # a scrape between snapshots still sees live occupancy.
        bb = self._block_bytes
        free_b = self.pool.free_count()
        ref_b = self.pool.ref_count()
        cached_b = self.pool.cached_count()
        self.metrics.gauge("kv.free_blocks").set(free_b)
        self.metrics.gauge("kv.free_bytes").set(free_b * bb)
        self.metrics.gauge("kv.referenced_blocks").set(ref_b)
        self.metrics.gauge("kv.referenced_bytes").set(ref_b * bb)
        self.metrics.gauge("kv.cached_blocks").set(cached_b)
        self.metrics.gauge("kv.cached_bytes").set(cached_b * bb)
        sbb = self._shard_block_bytes
        self.metrics.gauge("kv.shard_free_bytes").set(free_b * sbb)
        self.metrics.gauge("kv.shard_referenced_bytes").set(
            ref_b * sbb)
        self.metrics.gauge("kv.shard_cached_bytes").set(cached_b * sbb)
        # Retrace sentry: a jit cache that grows past one signature per
        # program mid-serve means some host value leaked into a traced
        # shape/dtype — the exact regression HVD001 lints for statically.
        sizes = self.compile_cache_sizes()
        grew = {k: (self._jit_cache_seen[k], v)
                for k, v in sizes.items()
                if v > self._jit_cache_seen[k] and v > 1}
        self._jit_cache_seen = sizes
        if grew:
            n = sum(v - max(prev, 1) for prev, v in grew.values())
            self.metrics.counter("serve.retrace").inc(n)
            if self.device is not None:
                # compile ledger: charge the growth with the captured
                # per-program compile cost — retraces become seconds.
                self.device.on_retrace(grew)
            self.metrics.event(
                "serve.retrace", step=self.step_index,
                programs={k: {"before": prev, "after": v}
                          for k, (prev, v) in grew.items()})
            if self._retrace_fatal:
                raise RuntimeError(
                    f"retrace sentry: jit cache grew mid-serve "
                    f"(HVD_TPU_RETRACE_FATAL=1) — "
                    + ", ".join(f"{k}: {prev} -> {v}"
                                for k, (prev, v) in sorted(grew.items()))
                    + f"; a device program saw a new signature at step "
                    f"{self.step_index}.  State:\n{self.state_dump()}")
        if self._verify_blocks:
            self._check_block_invariants()
        if self.pending() and progress == 0:
            self._idle_steps += 1
            if self._idle_steps >= self.watchdog_steps:
                raise RuntimeError(
                    f"ServeEngine made no scheduling progress for "
                    f"{self._idle_steps} consecutive steps (no admit / "
                    f"prefill window / decode tick / retirement / "
                    f"preemption while work is pending) — the scheduler "
                    f"is stuck.  State:\n{self.state_dump()}")
        else:
            self._idle_steps = 0
        # Health plane: sample the registry, then judge the series —
        # both are cheap no-ops until their cadence elapses.
        if self.sampler is not None:
            self.sampler.tick()
            if self.alerts is not None:
                self.alerts.tick()
        if self.device is not None:
            self.device.on_step(self.step_index)
        self._last_step_ts = time.monotonic()
        self.step_index += 1
        return self._finished

    def run(self, requests: list[Request]) -> list[RequestResult]:
        """Serve ``requests`` to completion; returns each request's
        :class:`~horovod_tpu.serving.RequestResult` in submission order
        (each is a list of the emitted tokens, carrying ``.status``)."""
        ids = [self.submit(r) for r in requests]
        while self.pending():
            self.step()
        return [self.results[i] for i in ids]
