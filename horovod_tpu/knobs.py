"""Canonical registry of every environment knob the package reads.

``ENV_KNOBS`` is the single source of truth for the ``HOROVOD_*`` /
``HVD_TPU_*`` configuration surface: one ``(name, default, help)`` row
per knob.  The hvdlint HVD003 checker enforces membership both ways —
every getenv site in the package must have a row here, every row must
have a live read site, and the docs table in ``docs/observability.md``
must match this table exactly (regenerate it with
``python -m horovod_tpu.knobs``).

The table MUST stay a pure literal: hvdlint extracts it by AST
``literal_eval`` without importing this module (so the linter never
pulls in jax).  Keep rows sorted by name; an empty default means
"unset" (the reader treats absence and empty string the same).
"""

from __future__ import annotations

import collections

# name, default (as the env string; "" = unset), one-line help.
ENV_KNOBS = (
    ("HOROVOD_AUTOTUNE", "0",
     "Enable online (fusion-threshold, cycle-time) autotuning."),
    ("HOROVOD_AUTOTUNE_LOG", "",
     "CSV file receiving one row per autotune sample."),
    ("HOROVOD_AUTOTUNE_STEADY_STATE_SAMPLES", "10",
     "Samples per tuning point after warmup before scoring it."),
    ("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", "3",
     "Samples discarded after each knob change before measuring."),
    ("HOROVOD_CYCLE_TIME", "5.0",
     "Background dispatch-loop cycle time in milliseconds."),
    ("HOROVOD_FUSION_THRESHOLD", "67108864",
     "Tensor-fusion bucket size in bytes (64 MiB default)."),
    ("HOROVOD_HIERARCHICAL_ALLREDUCE", "0",
     "Two-level (intra-host reduce, inter-host allreduce) dispatch."),
    ("HOROVOD_SPARSE_ALLREDUCE", "0",
     "Gradient-sparsity-aware allreduce for IndexedSlices-style updates."),
    ("HOROVOD_STALL_CHECK_DISABLE", "0",
     "Disable the stalled-negotiation warning thread."),
    ("HOROVOD_STALL_CHECK_TIME", "60.0",
     "Seconds a rank may lag negotiation before a stall warning."),
    ("HOROVOD_TIMELINE", "",
     "Chrome-trace timeline output path (enables timeline recording)."),
    ("HOROVOD_TPU_CONTROLLER_TRANSPORT", "",
     "Native control-plane transport: tcp:<host>:<port> or local:<world>."),
    ("HOROVOD_TPU_COORDINATOR", "",
     "host:port of the rank-0 coordinator for multi-process init."),
    ("HOROVOD_TPU_ELASTIC_RETRIES", "3",
     "Elastic-training restarts allowed before giving up."),
    ("HOROVOD_TPU_HIERARCHY_LOCAL_SIZE", "0",
     "Inner mesh extent for hierarchical dispatch (0 = local devices)."),
    ("HOROVOD_TPU_LOCAL_RANK", "",
     "This process's rank within its host (launcher-provided)."),
    ("HOROVOD_TPU_LOCAL_SIZE", "",
     "Number of processes on this host (launcher-provided)."),
    ("HOROVOD_TPU_NATIVE_CONTROLLER", "auto",
     "Native coordination engine: auto, on, or off."),
    ("HOROVOD_TPU_NUM_PROCESSES", "",
     "World size for multi-process init (unset = single process)."),
    ("HOROVOD_TPU_PROCESS_ID", "",
     "This process's global rank (launcher-provided)."),
    ("HOROVOD_TPU_SERIALIZE_DISPATCH", "auto",
     "Depth-1 dispatch serialization: auto (CPU only), on, or off."),
    ("HOROVOD_TPU_X64", "0",
     "Enable 64-bit jax types for the torch-compat surface."),
    ("HVD_TPU_ALERTS", "1",
     "Evaluate ALERT_RULES over the sampled series (0 = off)."),
    ("HVD_TPU_AUTOSCALE", "0",
     "Actuate CapacityAdvisor recommendations from the router poller."),
    ("HVD_TPU_AUTOSCALE_COOLDOWN_S", "30",
     "Seconds the autoscaler rests between actuations."),
    ("HVD_TPU_AUTOSCALE_MAX_REPLICAS", "8",
     "Fleet size ceiling the autoscaler will not grow past."),
    ("HVD_TPU_AUTOSCALE_MIN_REPLICAS", "1",
     "Fleet size floor the autoscaler will not shrink below."),
    ("HVD_TPU_AUTOSCALE_STABLE_S", "60",
     "Seconds of sustained shrink advice before a scale-down starts."),
    ("HVD_TPU_AUTOSCALE_STEP", "1",
     "Replicas added or retired per autoscaler action at most."),
    ("HVD_TPU_DEVICE_POLL_S", "1.0",
     "Seconds between device memory_stats() polls (HBM gauges)."),
    ("HVD_TPU_DEVICE_TELEMETRY", "0",
     "Device telemetry plane in ServeEngine (cost model, MFU, HBM)."),
    ("HVD_TPU_DRAFT_K", "4",
     "Draft tokens proposed per slot per tick when speculation is on."),
    ("HVD_TPU_EVENT_LOG", "",
     "JSONL request-lifecycle event-log output path."),
    ("HVD_TPU_EVENT_LOG_MAX_MB", "",
     "Rotate the event log past this many MB, keeping one .1 "
     "generation (unset = unbounded)."),
    ("HVD_TPU_FLASH_BWD", "pallas",
     "Flash-attention backward implementation: pallas or blockwise."),
    ("HVD_TPU_LOAD_DURATION_S", "1.0",
     "Seconds of offered arrivals per saturation-sweep rung."),
    ("HVD_TPU_LOAD_LADDER", "",
     "Comma-separated offered-RPS rungs for the saturation sweep."),
    ("HVD_TPU_LOAD_PROCESS", "poisson",
     "Load-harness arrival process: poisson, bursty, or fixed."),
    ("HVD_TPU_LOAD_SEED", "0",
     "Seed for load-harness arrival schedules and request mixes."),
    ("HVD_TPU_LOAD_TIMEOUT_S", "60",
     "Seconds the load harness waits for late replies per rung."),
    ("HVD_TPU_MONITOR_PORT", "",
     "Port for the per-rank /metrics + /healthz HTTP exporter."),
    ("HVD_TPU_NEGOTIATE_TIMEOUT_S", "60",
     "Host-card negotiation deadline in seconds during init()."),
    ("HVD_TPU_PEAK_FLOPS", "",
     "Per-chip peak FLOP/s override for the serving-MFU denominator."),
    ("HVD_TPU_PROFILE", "0",
     "Feed the step rows' phases to the serve.phase.* histograms and the "
     "event log."),
    ("HVD_TPU_PROFILE_WINDOW", "256",
     "Step rows in the rolling per-phase report (/profile)."),
    ("HVD_TPU_RETRACE_FATAL", "0",
     "Raise when the retrace sentry sees a jit cache grow mid-serve."),
    ("HVD_TPU_ROUTER_DRAIN_S", "5.0",
     "Seconds stop() waits for in-flight requests before shutting down."),
    ("HVD_TPU_ROUTER_IMBALANCE", "4",
     "Inflight gap above which prefix_affinity falls back to least_loaded."),
    ("HVD_TPU_ROUTER_JOURNAL", "",
     "Path of the crash-durable request-journal JSONL WAL (unset = off)."),
    ("HVD_TPU_ROUTER_JOURNAL_KEYS", "4096",
     "Idempotency-key results kept for dedup (LRU) and after compaction."),
    ("HVD_TPU_ROUTER_MAX_FAILOVERS", "3",
     "Failover replays allowed per request before it fails terminally."),
    ("HVD_TPU_ROUTER_MIN_FREE_KV", "0",
     "Fleet free-KV fraction floor below which the router sheds (0 = off)."),
    ("HVD_TPU_ROUTER_MIN_GOODPUT", "0",
     "Fleet goodput floor below which the router sheds load (0 = off)."),
    ("HVD_TPU_ROUTER_POLICY", "prefix_affinity",
     "RouterServer policy: round_robin, least_loaded, or prefix_affinity."),
    ("HVD_TPU_ROUTER_POLL_S", "0.05",
     "Seconds between router polls of replica health and snapshots."),
    ("HVD_TPU_ROUTER_PORT", "",
     "Port for the RouterServer HTTP front door (maybe_start_router)."),
    ("HVD_TPU_ROUTER_PROBE_FAILS", "3",
     "Consecutive failed probes before an HTTP replica is marked dead."),
    ("HVD_TPU_ROUTER_SHADOW_MAX_MB", "64",
     "Fleet-wide shadow prefix index byte ceiling in MB (<= 0 = unbounded)."),
    ("HVD_TPU_ROUTER_TICKET_TTL_S", "600",
     "Seconds a finished router ticket stays readable before reaping."),
    ("HVD_TPU_SAMPLE_S", "1.0",
     "Seconds between time-series samples of the registry (<= 0 = off)."),
    ("HVD_TPU_SCHED_POLICY", "fifo",
     "ServeEngine scheduler policy: fifo, priority, or edf."),
    ("HVD_TPU_SIM_REPLICAS", "200",
     "Simulated replica count for the default simfleet campaign."),
    ("HVD_TPU_SIM_REQUESTS", "100000",
     "Offered virtual request count for the default simfleet campaign."),
    ("HVD_TPU_SIM_SEED", "0",
     "Seed for the simfleet campaign (schedule, chaos, per-replica jitter)."),
    ("HVD_TPU_SLO_E2E_S", "0",
     "End-to-end latency SLO in seconds for goodput (0 = no SLO)."),
    ("HVD_TPU_SPEC", "0",
     "Self-drafting (prompt-lookup) speculative decode in ServeEngine."),
    ("HVD_TPU_STRAGGLER_WARN_S", "1.0",
     "Step-lag threshold in seconds before a straggler warning."),
    ("HVD_TPU_SUPERVISE_BACKOFF_S", "0.5",
     "Base respawn delay for a dead replica (doubles per restart)."),
    ("HVD_TPU_SUPERVISE_MAX_RESTARTS", "3",
     "Respawns per replica before the supervisor circuit-breaks it."),
    ("HVD_TPU_TP", "1",
     "Tensor-parallel degree of ServeEngine (chips per serving replica)."),
    ("HVD_TPU_TRACE_SAMPLE", "0",
     "Fraction of requests head-sampled into the causal tracing plane."),
    ("HVD_TPU_TRACE_SEED", "0",
     "Seed for the deterministic trace sampler and span-id derivation."),
    ("HVD_TPU_VERIFY_BLOCKS", "0",
     "Walk paged-KV block tables every serve tick (debug, slow)."),
)

Knob = collections.namedtuple("Knob", ("name", "default", "help"))


def knobs() -> tuple[Knob, ...]:
    """The registry as named tuples, sorted by name."""
    return tuple(Knob(*row) for row in ENV_KNOBS)


def render_markdown_table() -> str:
    """The docs/observability.md knob table (HVD003 lints the docs copy
    against ``ENV_KNOBS``; paste this output verbatim on drift)."""
    lines = ["| Knob | Default | Meaning |", "| --- | --- | --- |"]
    for k in knobs():
        default = f"`{k.default}`" if k.default else "*(unset)*"
        lines.append(f"| `{k.name}` | {default} | {k.help} |")
    return "\n".join(lines)


if __name__ == "__main__":
    print(render_markdown_table())
