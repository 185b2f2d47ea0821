"""Self-healing for the router fleet: respawn dead replicas under a
restart budget.

PR 9's :class:`~horovod_tpu.router.RouterServer` already *survives*
replica death — in-flight requests replay onto survivors, and an HTTP
replica rejoins when its probes turn healthy — but it cannot *heal*:
a dead :class:`~horovod_tpu.router.LocalReplica` (pump thread gone,
``can_revive=False``) is permanently lost, so every local death
shrinks the fleet forever.  The :class:`ReplicaSupervisor` closes that
asymmetry.  It rides the router's existing poll pass
(:meth:`~horovod_tpu.router.RouterServer.poll_now` ticks it), and for
each dead replica:

1. **Backoff** — a respawn is attempted only after an exponential
   delay (``HVD_TPU_SUPERVISE_BACKOFF_S`` base, doubling per restart),
   so a replica that dies instantly on arrival doesn't hot-loop the
   supervisor.
2. **Budget / circuit-breaker** — after
   ``HVD_TPU_SUPERVISE_MAX_RESTARTS`` respawns the replica is
   circuit-broken to **permanent-dead** (``supervisor.permanent_deaths``)
   and never retried: a replica that keeps dying is a bug, not a blip,
   and respawning it forever would mask the bug while burning compute.
3. **Respawn** — a factory builds a replacement handle.  The default
   factory for a local replica is :func:`clone_engine`: a fresh
   :class:`~horovod_tpu.serving_scheduler.ServeEngine` with the dead
   engine's exact configuration (same params/geometry/policy — greedy
   determinism then guarantees the replacement produces bit-identical
   tokens for any replayed request).  A factory may return ``None`` to
   signal *out-of-band* respawn (e.g. relaunching a remote process
   behind an :class:`~horovod_tpu.router.HttpReplica` — the handle
   itself is still valid and revives through probes); the attempt
   still consumes budget.
4. **Warm-up** — before the replacement joins routing, the supervisor
   optionally replays the hottest recently-routed prompts (the ones
   the router's own :class:`~horovod_tpu.router.ShadowPrefixIndex`
   says were cached) through the fresh engine, so the respawned
   replica re-enters prefix-affinity routing warm instead of serving
   its first real requests from a cold radix.
5. **Commit** — :meth:`~horovod_tpu.router.RouterServer.replace_replica`
   swaps the handle in under the router lock and returns the name to
   the candidate set.

Every respawn attempt checks the ``serve.supervisor`` fault site
(key = replica name) first: a firing rule fails the attempt, burning
one unit of budget and advancing the backoff — which is exactly how
the chaos campaign proves the circuit-breaker works.

The supervisor holds no thread of its own and takes no router lock
itself; it is called from the poller (or directly from tests via
:meth:`tick`), and all its state lives behind its own small lock.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Sequence

from horovod_tpu import faults as faults_mod
from horovod_tpu import profiler as profiler_mod
from horovod_tpu.monitor import env_float
from horovod_tpu.router import LocalReplica, ReplicaHandle, RouterServer
from horovod_tpu.serving import Request


def clone_engine(eng: Any) -> Any:
    """A fresh :class:`~horovod_tpu.serving_scheduler.ServeEngine`
    with ``eng``'s exact configuration: same params/config/geometry/
    policy/faults/metrics, empty state.  Greedy determinism makes the
    clone token-identical to the original for any request, which is
    what lets a respawned replica transparently serve replays."""
    from horovod_tpu.serving_scheduler import ServeEngine

    return ServeEngine(
        eng.params, eng.cfg,
        n_slots=eng.n_slots, max_len=eng.max_len, chunk=eng.chunk,
        block_size=eng.block_size,
        # The paged cache's axis-1 extent IS n_blocks (trash block
        # included), so the clone's KV geometry matches bit-for-bit.
        n_blocks=eng.pool.n_blocks,
        tp_size=eng.tp_size,
        timeline=eng.timeline,
        preempt_after=eng.preempt_after,
        max_retries=eng.max_retries,
        watchdog_steps=eng.watchdog_steps,
        faults=eng.faults,
        metrics=eng.metrics,
        prefix_cache=eng.prefix is not None,
        monitor=False,
        slo_window=eng.slo._traces.maxlen,
        slo_e2e_s=eng.slo.slo_e2e_s,
        profile=isinstance(eng.prof, profiler_mod.TickProfiler),
        spec=eng.spec,
        draft_k=eng.draft_k,
        policy=eng.policy,
    )


class _ReplicaRecord:
    """Per-replica supervision state (guarded by the supervisor lock)."""

    __slots__ = ("restarts", "next_ts", "permanent_dead", "history")

    def __init__(self) -> None:
        self.restarts = 0               # respawn attempts consumed
        self.next_ts = 0.0              # monotonic: earliest next try
        self.permanent_dead = False     # circuit-broken
        self.history: list[dict] = []   # [{"ok": bool, "error": ...}]


class ReplicaSupervisor:
    """Respawns dead replicas for one router; see the module docstring.

    ``factories`` maps replica name → zero-arg callable returning a
    replacement :class:`~horovod_tpu.router.ReplicaHandle` (or ``None``
    for out-of-band respawn).  Replicas without a factory get the
    default: local replicas are cloned via :func:`clone_engine`;
    anything else (HTTP replicas already revive through probes) is left
    alone entirely — no budget, no backoff.

    ``warm_prefixes`` bounds how many recently-routed prompts are
    replayed into a fresh local engine before it rejoins (0 = cold
    respawn).  The candidate prompts come from the supervisor's own
    bounded ring, fed by the router's ``on_route`` hook; only prompts
    the dead replica's shadow index recognises are replayed
    (``supervisor.warm_prefixes`` counts them).
    """

    _GUARDED_BY_LOCK = ("_records", "_recent")

    def __init__(self, router: RouterServer, *,
                 max_restarts: int | None = None,
                 backoff_s: float | None = None,
                 factories: "dict[str, Callable[[], ReplicaHandle | None]] | None" = None,  # noqa: E501
                 warm_prefixes: int = 8,
                 recent_prompts: int = 64,
                 faults: "faults_mod.FaultRegistry | None" = None,
                 clock: Callable[[], float] = time.monotonic):
        self.router = router
        self.max_restarts = int(
            max_restarts if max_restarts is not None else
            env_float("HVD_TPU_SUPERVISE_MAX_RESTARTS", 3))
        self.backoff_s = (
            backoff_s if backoff_s is not None else
            env_float("HVD_TPU_SUPERVISE_BACKOFF_S", 0.5))
        self.factories = dict(factories or {})
        self.warm_prefixes = warm_prefixes
        self.faults = faults if faults is not None else router.faults
        self.metrics = router.metrics
        self.clock = clock
        self._lock = threading.Lock()
        self._records: dict[str, _ReplicaRecord] = {}
        # Recently routed prompts, newest last — the warm-up feed.
        self._recent: collections.deque = collections.deque(
            maxlen=max(recent_prompts, 1))
        # Registered up front (literal names — the HVD005 contract).
        self.metrics.counter("supervisor.respawns")
        self.metrics.counter("supervisor.respawn_failures")
        self.metrics.counter("supervisor.permanent_deaths")
        self.metrics.counter("supervisor.warm_prefixes")
        router.supervisor = self
        if router.on_route is None:
            router.on_route = self._observe_route

    # -- feeds -------------------------------------------------------------

    def _observe_route(self, name: str, req: Request) -> None:
        with self._lock:
            self._recent.append(tuple(req.prompt))

    # -- state for health()/state_dump() -----------------------------------

    def _record_locked(self, name: str) -> _ReplicaRecord:
        rec = self._records.get(name)
        if rec is None:
            rec = self._records[name] = _ReplicaRecord()
        return rec

    def state(self) -> dict[str, dict]:
        """Per-replica restart state: ``restarts`` consumed,
        ``max_restarts``, ``permanent_dead``, next-attempt delay, and
        the attempt ``history`` (newest last)."""
        with self._lock:
            now = self.clock()
            return {name: {
                "restarts": rec.restarts,
                "max_restarts": self.max_restarts,
                "permanent_dead": rec.permanent_dead,
                "next_attempt_in_s": max(rec.next_ts - now, 0.0),
                "history": list(rec.history),
            } for name, rec in self._records.items()}

    def degraded(self) -> bool:
        """True while any replica is running on its restart budget —
        the fleet serves, but not at full redundancy headroom."""
        with self._lock:
            return any(rec.restarts > 0 or rec.permanent_dead
                       for rec in self._records.values())

    # -- the respawn loop --------------------------------------------------

    def tick(self) -> int:
        """One supervision pass (the router's poller calls this every
        poll): attempt a respawn for every dead, budgeted, backed-off
        replica.  Returns how many replicas rejoined."""
        with self.router._lock:
            # A cordoned replica is being drained out of the fleet by
            # the autoscaler: if it dies mid-drain its in-flight work
            # fails over, but respawning it would fight the retire.
            dead = [r for r in self.router.replicas
                    if r.name in self.router._dead
                    and r.name not in self.router._cordoned]
        rejoined = 0
        for handle in dead:
            if self._respawn(handle):
                rejoined += 1
        return rejoined

    def _factory_for(self, handle: ReplicaHandle) -> \
            "Callable[[], ReplicaHandle | None] | None":
        fac = self.factories.get(handle.name)
        if fac is not None:
            return fac
        if isinstance(handle, LocalReplica):
            return lambda: self._default_local_factory(handle)
        return None     # HTTP replicas heal through probes

    def _respawn(self, handle: ReplicaHandle) -> bool:
        name = handle.name
        factory = self._factory_for(handle)
        if factory is None:
            return False
        now = self.clock()
        with self._lock:
            rec = self._record_locked(name)
            if rec.permanent_dead or now < rec.next_ts:
                return False
            if rec.restarts >= self.max_restarts:
                rec.permanent_dead = True
                self.metrics.counter(
                    "supervisor.permanent_deaths").inc()
                self.metrics.event("supervisor.permanent_death",
                                   replica=name,
                                   restarts=rec.restarts)
                return False
            # Burn the budget up front: a factory that crashes (or a
            # firing serve.supervisor fault) must still advance the
            # backoff, or a broken factory hot-loops every tick.
            rec.restarts += 1
            rec.next_ts = now + self.backoff_s * (2 ** (rec.restarts - 1))
        try:
            self.faults.check("serve.supervisor", key=name)
            replacement = factory()
        except Exception as e:
            self.metrics.counter("supervisor.respawn_failures").inc()
            self.metrics.event("supervisor.respawn_failure",
                               replica=name, error=str(e))
            with self._lock:
                self._records[name].history.append(
                    {"ok": False, "error": str(e)})
            return False
        with self._lock:
            rec = self._records[name]
            rec.history.append({"ok": True, "error": None})
            restarts = rec.restarts
        self.metrics.counter("supervisor.respawns").inc()
        self.metrics.event("supervisor.respawn", replica=name,
                           restarts=restarts,
                           out_of_band=replacement is None)
        if replacement is None:
            return False    # out-of-band: probes will revive the handle
        self.router.replace_replica(name, replacement)
        return True

    # -- elastic membership (the autoscaler's factory seam) ----------------

    def spawn_replica(self, name: str,
                      template: "ReplicaHandle | None" = None,
                      ) -> "ReplicaHandle | None":
        """Build a brand-new replica handle for the autoscaler's grow
        path, through the same pluggable factory seam respawn uses: an
        explicit ``factories[name]`` entry wins; otherwise a live
        local replica (``template``, or the first healthy
        :class:`~horovod_tpu.router.LocalReplica`) is cloned via
        :func:`clone_engine` and pre-warmed with its hot prompts.
        Returns ``None`` when no factory applies (an all-HTTP fleet
        grows out-of-band)."""
        fac = self.factories.get(name)
        if fac is not None:
            return fac()
        if template is None:
            with self.router._lock:
                live = [r for r in self.router.replicas
                        if r.name not in self.router._dead
                        and isinstance(r, LocalReplica)]
            template = live[0] if live else None
        if not isinstance(template, LocalReplica):
            return None
        eng = clone_engine(template.engine)
        # Warm from the template's shadow: the newcomer inherits the
        # fleet's hot prefixes instead of joining with a cold radix.
        self._warm(eng, template.name)
        return LocalReplica(eng, name=name, faults=template.faults)

    def forget(self, name: str) -> None:
        """Drop a retired replica's restart record so a future replica
        reusing the name starts with a full budget (the autoscaler
        calls this after :meth:`~horovod_tpu.router.RouterServer.retire_replica`)."""  # noqa: E501
        with self._lock:
            self._records.pop(name, None)

    # -- warm respawn ------------------------------------------------------

    def _default_local_factory(self,
                               dead: LocalReplica) -> ReplicaHandle:
        eng = clone_engine(dead.engine)
        self._warm(eng, dead.name)
        return LocalReplica(eng, name=dead.name, faults=dead.faults)

    def _warm_candidates(self, name: str) -> "list[tuple[int, ...]]":
        """Recently routed prompts the dead replica's shadow index
        recognises, newest first, deduped, bounded by
        ``warm_prefixes``."""
        if self.warm_prefixes <= 0:
            return []
        with self.router._lock:
            shadow = self.router._shadows.get(name)
        with self._lock:
            recent = list(self._recent)
        out: list[tuple[int, ...]] = []
        seen: set[tuple[int, ...]] = set()
        for prompt in reversed(recent):
            if prompt in seen:
                continue
            seen.add(prompt)
            if shadow is not None and shadow.match_tokens(prompt) > 0:
                out.append(prompt)
                if len(out) >= self.warm_prefixes:
                    break
        return out

    def _warm(self, eng: Any, name: str) -> None:
        """Best-effort prefix-cache rewarm: run each hot prompt for one
        token so its chunks land in the fresh radix.  Failures are
        swallowed — warm-up is an optimization, never a respawn
        blocker."""
        if getattr(eng, "prefix", None) is None:
            return
        for prompt in self._warm_candidates(name):
            try:
                eng.run([Request(prompt=list(prompt), max_new_tokens=1)])
                self.metrics.counter("supervisor.warm_prefixes").inc()
            except Exception:
                continue    # one bad prompt must not cold-start the rest


def supervise(router: RouterServer,
              **kwargs: Any) -> ReplicaSupervisor:
    """Attach a :class:`ReplicaSupervisor` to ``router`` (convenience
    constructor mirroring ``maybe_start_router``'s shape)."""
    return ReplicaSupervisor(router, **kwargs)
