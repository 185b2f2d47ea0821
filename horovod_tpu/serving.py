"""Continuous-batching serving loop for the llama inference stack.

The reference has no serving story (its zoo is ResNet/MNIST-era,
SURVEY.md §2.3); this is capability extension on the TPU-first side,
built from the ragged KV-cache primitives in :mod:`horovod_tpu.models.llama`:

* a fixed pool of **slots** (the compiled batch dimension — shapes never
  change, so the decode step is one cached XLA program for the life of
  the server);
* **admission** of a new request into a free slot mid-stream: a B=1
  ragged ``prefill`` (padded to one static width so every admission hits
  the same compiled program) whose K/V window is spliced into the pool
  cache at the slot row;
* a **decode tick** advancing every slot one token (per-row cache
  positions and masks do the isolation — a freshly admitted short prompt
  and a slot 900 tokens into its answer share the same batched matvecs);
* host-side orchestration only at the boundaries (which slot is free,
  which request is done) — the standard serving-engine split: control
  flow on the host, one compiled program per phase on the device.

Isolation is exact: rows are independent in attention, so each request's
greedy continuation is bit-identical to running it alone (pinned by
``tests/test_serving.py``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.models import llama
from horovod_tpu.models.llama import KVCache


@dataclasses.dataclass
class Request:
    """One generation request: prompt token ids + a new-token budget.

    ``sample_key``: PRNG key for sampled decoding (required when the
    batcher's ``temperature > 0``).  The slot replays exactly the key
    schedule solo ``generate(key=sample_key)`` uses — ``split(key,
    max_new_tokens)[i]`` for the i-th new token — so a sampled request's
    tokens equal its solo run draw for draw.

    ``prefix``: a :class:`PrefixCache` (shared system prompt) this
    request continues from; ``prompt`` is then just the suffix (the user
    turn) and the prefix's K/V are spliced instead of recomputed.  This
    explicit-handle splice is :class:`ContinuousBatcher`-only;
    :class:`~horovod_tpu.serving_scheduler.ServeEngine` instead reuses
    prefixes transparently (``prefix_cache=True``: radix-indexed,
    ref-counted paged blocks — see :mod:`horovod_tpu.prefix_cache`), so
    engine requests always carry the full prompt.

    ``temperature``: per-request override of the pool temperature.  A
    sampling pool serves greedy requests via 0.0; the reverse is not
    possible — a greedy pool compiles no sampling tick, so overrides > 0
    require a sampling pool.  ``None`` inherits the pool setting.

    Lifecycle fields (honored by
    :class:`~horovod_tpu.serving_scheduler.ServeEngine`; the simpler
    :class:`ContinuousBatcher` ignores them):

    ``deadline_s``: wall-clock budget from ``submit()`` — a request
    still queued or in flight when it expires terminates with a
    ``TIMEOUT`` result carrying its tokens-so-far.

    ``max_queue_steps``: admission budget in ENGINE STEPS — a request
    still queued after this many steps (per queue stint; a preempted
    request's replay restarts the count) is load-shed with a
    ``REJECTED`` result.  Step-counted so tests never sleep.

    ``slo_s``: SOFT end-to-end latency target for SLO accounting — a
    request finishing OK but slower than this counts against the
    engine's windowed ``serve.goodput``
    (:class:`~horovod_tpu.monitor.SLOWindow`).  Under the engine's
    ``edf`` scheduler policy (:mod:`horovod_tpu.scheduling`) the
    derived absolute deadline ALSO orders admission and picks
    preemption victims; with the default ``fifo`` policy it never
    changes scheduling or the result: the request still completes and
    returns its tokens.

    ``priority``: scheduling weight for the engine's ``priority``
    policy (higher admits first, lower is preempted first; 0 default).
    Like ``slo_s`` it never affects any request's *output* — scheduler
    policies reorder waiting, not tokens."""

    prompt: list[int]
    max_new_tokens: int
    eos_id: int | None = None
    sample_key: Any = None
    prefix: "PrefixCache | None" = None
    temperature: float | None = None
    deadline_s: float | None = None
    max_queue_steps: int | None = None
    slo_s: float | None = None
    priority: int = 0
    # Causal-trace context (horovod_tpu.tracing.TraceContext) stamped by
    # whoever minted or propagated the trace — the router sets it per
    # delivery attempt so engine spans parent under the right hop; None
    # (the default) means unsampled and costs one attribute test.
    # Excluded from the JSON wire schema's REQUIRED fields: it rides
    # request_to_json/request_from_json as an optional "trace" dict.
    trace_ctx: Any = None


# Terminal request statuses (ServeEngine request lifecycle).
OK = "OK"
TIMEOUT = "TIMEOUT"
CANCELLED = "CANCELLED"
FAILED = "FAILED"
REJECTED = "REJECTED"


class RequestResult(list):
    """Terminal result of one engine request: the emitted tokens plus a
    lifecycle status.

    Subclasses ``list`` so every pre-lifecycle consumer — parity
    asserts, ``len()``, ``np.asarray`` — keeps working on the tokens
    unchanged; the lifecycle layer reads ``status`` (one of ``OK /
    TIMEOUT / CANCELLED / FAILED / REJECTED``) and, for ``FAILED``,
    ``error`` (the exception that condemned the request).  Non-``OK``
    results carry tokens-so-far: everything emitted before the request
    terminated (greedy determinism makes that a prefix of the solo run).

    ``trace`` is the request's :class:`horovod_tpu.metrics.Trace` —
    enqueue/admit/first-token/terminal timestamps plus prefill-chunk /
    preemption / retry / prefix-reuse odometers.  The ServeEngine
    populates it for EVERY terminal state (a rejected request still has
    its enqueue and terminal stamps); simpler producers leave it None.

    ``blocks`` and ``unmask_steps`` are ``None`` but for a model that
    generates by diffusion over blocks: every block the request committed,
    whole (``[n_blocks, B]`` ids: a prompt's trailing ``L mod B`` tokens lead
    the first, and the last holds the positions ``max_new_tokens`` or an eos
    cut), and the denoise step that unmasked each of their positions (-1
    for a given one) — what a reference needs to rebuild every noisy block
    the program saw.
    """

    def __init__(self, tokens=(), status: str = OK,
                 error: BaseException | None = None,
                 trace: Any = None):
        super().__init__(tokens)
        self.status = status
        self.error = error
        self.trace = trace
        self.blocks = self.unmask_steps = None

    @property
    def tokens(self) -> list[int]:
        return list(self)

    @property
    def ok(self) -> bool:
        return self.status == OK

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        err = f", error={self.error!r}" if self.error is not None else ""
        return (f"RequestResult(status={self.status}, "
                f"tokens={list(self)}{err})")


class PrefixCache:
    """Precomputed K/V of a shared prompt prefix (the system-prompt
    pattern): prefill once, splice into every admission that carries it —
    the prefix's FLOPs are paid once per server, not once per request.

    Storage: [n_layers, 1, P, KVH, Dh] K/V plus the prefix token count.
    """

    def __init__(self, k: jax.Array, v: jax.Array, length: int):
        self.k, self.v, self.length = k, v, int(length)


def precompute_prefix(params: dict, cfg: llama.LlamaConfig,
                      tokens: list[int], *,
                      window: int | None = None) -> PrefixCache:
    """Prefill a shared prefix once → a splice-ready :class:`PrefixCache`.

    ``window``: chunk the prefill (``llama.prefill_chunked``) so a
    multi-thousand-token system prompt doesn't spike O(P²) activation
    memory at server setup — the same bound the batcher's admissions
    use.  The K/V buffer pads to a window multiple; ``length`` stays the
    true token count (the pad tail is masked/overwritten downstream).
    """
    if not tokens:
        raise ValueError("empty prefix")
    p = len(tokens)
    if window is None:
        t = jnp.asarray([tokens], jnp.int32)
        cache = llama.init_cache(cfg, 1, p)
        _, cache = llama.prefill(params, t, cfg, cache)
        return PrefixCache(cache.k, cache.v, p)
    pad = -(-p // window) * window
    t = np.zeros((1, pad), np.int32)
    t[0, :p] = tokens
    cache = llama.init_cache(cfg, 1, pad)
    cache = cache._replace(length=jnp.zeros((1,), jnp.int32))
    _, cache = llama.prefill_chunked(
        params, jnp.asarray(t), cfg, cache, window=window,
        lengths=jnp.asarray([p], jnp.int32))
    return PrefixCache(cache.k, cache.v, p)


# hvdlint: disable=HVD001 -- module-level splice shared by every ContinuousBatcher; one program per padded prompt width by construction, counted indirectly by the batcher's prefill cache sizes
@partial(jax.jit, donate_argnums=(0,))
def _splice(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
            slot: jax.Array, length: jax.Array) -> KVCache:
    """Write a B=1 prefill's K/V window into slot ``slot`` of the pool.

    k_new/v_new: [n_layers, 1, W, KVH, Dh] where W is the padded prompt
    width (a multiple of the admission window; one compiled program per
    distinct W).  Only the first W positions of the slot row are
    written; ``length`` is the row's true prompt length, and positions
    beyond it are unreadable until rewritten (write-before-read).
    """
    k = lax.dynamic_update_slice(cache.k, k_new, (0, slot, 0, 0, 0))
    v = lax.dynamic_update_slice(cache.v, v_new, (0, slot, 0, 0, 0))
    return KVCache(k=k, v=v, length=cache.length.at[slot].set(length))


class ContinuousBatcher:
    """Serve mixed-length requests through a fixed slot pool.

    ``n_slots`` is the compiled batch size; ``max_len`` bounds prompt +
    generation per request; ``admit_width`` is the admission window —
    prompts chunk in at this width (up to the pool depth), so it sets
    the admission activation-memory bound and the compiled-program
    granularity, not a prompt-length limit.

    ``temperature``/``top_k``/``top_p`` are pool-level sampling knobs
    (one compiled tick for every slot).  With ``temperature > 0`` each
    request carries its own ``sample_key`` and every slot draws from its
    own PRNG stream on solo ``generate``'s exact key schedule — sampled
    results stay draw-for-draw equal to running each request alone.
    """

    def __init__(self, params: dict, cfg: llama.LlamaConfig, *,
                 n_slots: int, max_len: int, admit_width: int,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None):
        if admit_width > max_len:
            raise ValueError(
                f"admit_width {admit_width} > max_len {max_len}: the "
                f"admission window must fit inside the pool cache")
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.admit_width = admit_width
        self.temperature = float(temperature)
        self.cache = llama.init_cache(cfg, n_slots, max_len)
        # ragged from birth: every row owns its position
        self.cache = self.cache._replace(
            length=jnp.zeros((n_slots,), jnp.int32))
        self.last_logits = jnp.zeros((n_slots, cfg.vocab_size), jnp.float32)
        # host-side slot state
        self._busy = [False] * n_slots
        self._budget = [0] * n_slots
        self._eos = [None] * n_slots
        self._out: list[list[int]] = [[] for _ in range(n_slots)]
        # per-slot key schedules (sampling): slot s's next draw uses
        # _keys[s][len(_out[s])] — exactly solo generate's split schedule.
        # All schedules are canonicalized to typed keys at admit, so the
        # free-slot dummy always stacks with them.
        self._keys: list[Any] = [None] * n_slots
        self._temps = [0.0] * n_slots
        self._dummy_key = jax.random.key(0)
        self._greedy_keys = jnp.stack([self._dummy_key] * n_slots)
        self._zero_temps = jnp.zeros((n_slots,), jnp.float32)

        @jax.jit
        def _prefill_one(params, tokens, length):
            # Chunked at the admission width: prompts up to the pool
            # depth admit through fixed admit_width windows, so
            # activation memory never spikes past O(admit_width·depth)
            # and there are at most max_len/admit_width admission
            # programs (one per window count).  The B=1 cache is sized
            # to the padded prompt (tokens.shape[1]), so the splice
            # moves only the K/V the prefill produced — the slot row's
            # tail keeps the previous occupant's bytes, which the
            # write-before-read invariant makes unreadable.
            cache = llama.init_cache(cfg, 1, tokens.shape[1])
            cache = cache._replace(length=jnp.zeros((1,), jnp.int32))
            logits, cache = llama.prefill_chunked(
                params, tokens, cfg, cache, window=admit_width,
                lengths=length)
            return logits[0], cache.k, cache.v

        @jax.jit
        def _prefill_suffix(params, pk, pv, plen, tokens, length):
            # continue from a spliced prefix: the B=1 cache starts with
            # the prefix K/V at [0, P) and the suffix chunk-prefills
            # from base position P (prefill_chunked's nonzero-base path).
            # The prefix rides along in the admission window — one extra
            # copy of its K/V per admission (suffix attention NEEDS the
            # prefix keys in context, so a prefix-free B=1 cache can't
            # work), still orders of magnitude below recomputing the
            # prefill.  One compiled program per distinct (prefix width,
            # window count) pair — servers hold few distinct prefixes.
            w_total = pk.shape[2] + tokens.shape[1]
            cache = llama.init_cache(cfg, 1, w_total)
            cache = KVCache(
                k=lax.dynamic_update_slice(cache.k, pk, (0, 0, 0, 0, 0)),
                v=lax.dynamic_update_slice(cache.v, pv, (0, 0, 0, 0, 0)),
                length=plen,
            )
            logits, cache = llama.prefill_chunked(
                params, tokens, cfg, cache, window=admit_width,
                lengths=length)
            return logits[0], cache.k, cache.v

        @partial(jax.jit, donate_argnums=(1, 2))
        def _tick(params, cache, last_logits, keys, temps):
            # donation matters here: without it every tick copies the
            # whole pool K/V (decode's cost IS cache traffic)
            if temperature > 0.0:
                # per-row [1, V] sampling with that row's own key and
                # (possibly overridden) temperature — the same math
                # solo generate's sample_logits computes, via the shared
                # filtered_logits, so draws are bit-identical per row;
                # temp <= 0 rows take the greedy branch
                def row(l, k, t):
                    # safe divisor ONLY on the greedy branch (t <= 0);
                    # every positive t divides exactly as solo generate
                    # does, keeping bit-parity at any magnitude
                    sampled = jax.random.categorical(
                        k, llama.filtered_logits(
                            l[None], jnp.where(t > 0.0, t, 1.0),
                            top_k=top_k, top_p=top_p), axis=-1)[0]
                    return jnp.where(t > 0.0, sampled,
                                     jnp.argmax(l, axis=-1))

                tok = jax.vmap(row)(last_logits, keys,
                                    temps).astype(jnp.int32)
            else:
                tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            logits, cache = llama.decode_step(params, tok, cfg, cache)
            return tok, logits, cache

        self._prefill_one = _prefill_one
        self._prefill_suffix = _prefill_suffix
        self._tick = _tick

    def compile_cache_sizes(self) -> dict[str, int]:
        """Compile counts of the batcher's device programs.  The decode
        tick must hold ONE signature for the pool's life; prefill
        programs are one per distinct padded prompt width (a multiple of
        ``admit_width``).  Tests snapshot this dict and assert it stays
        flat across steady-state serving."""
        return {
            "prefill_one": self._prefill_one._cache_size(),
            "prefill_suffix": self._prefill_suffix._cache_size(),
            "tick": self._tick._cache_size(),
        }

    # -- admission ---------------------------------------------------------

    def free_slots(self) -> list[int]:
        return [i for i, b in enumerate(self._busy) if not b]

    def admit(self, req: Request) -> int:
        """Prefill ``req`` into a free slot (chunked at ``admit_width``
        for prompts longer than one window); returns the slot index."""
        L = len(req.prompt)
        if L < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        eff_temp = (self.temperature if req.temperature is None
                    else float(req.temperature))
        # validated BEFORE any state changes: a rejected admission must
        # not leave the slot busy or spliced
        if eff_temp > 0.0 and self.temperature <= 0.0:
            # the unfixable problem first: no sample_key can make a
            # greedy pool serve a sampled request
            raise ValueError(
                "a greedy pool compiles no sampling tick; construct the "
                "ContinuousBatcher with temperature > 0 to serve sampled "
                "requests (per-request temperature can still be 0)")
        if eff_temp > 0.0 and req.sample_key is None:
            raise ValueError(
                "sampled request (temperature > 0) needs a sample_key")
        P = req.prefix.length if req.prefix is not None else 0
        p_pad = int(req.prefix.k.shape[2]) if req.prefix is not None else 0
        if P + L + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prefix {P} + prompt {L} + max_new_tokens "
                f"{req.max_new_tokens} exceeds max_len {self.max_len}")
        w = self.admit_width
        n_win = -(-L // w)
        if p_pad + n_win * w > self.max_len:
            raise ValueError(
                f"prefix buffer {p_pad} + prompt {L} padded to "
                f"{n_win * w} admission windows exceeds max_len "
                f"{self.max_len}")
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot; call step() until one opens")
        slot = free[0]
        padded = np.zeros((1, n_win * w), np.int32)
        padded[0, :L] = req.prompt
        if req.prefix is not None:
            logits, k_new, v_new = self._prefill_suffix(
                self.params, req.prefix.k, req.prefix.v,
                jnp.asarray([P], jnp.int32), jnp.asarray(padded),
                jnp.asarray([L], jnp.int32))
        else:
            logits, k_new, v_new = self._prefill_one(
                self.params, jnp.asarray(padded),
                jnp.asarray([L], jnp.int32))
        self.cache = _splice(self.cache, k_new, v_new,
                             jnp.asarray(slot, jnp.int32),
                             jnp.asarray(P + L, jnp.int32))
        self.last_logits = self.last_logits.at[slot].set(logits)
        self._busy[slot] = True
        self._budget[slot] = req.max_new_tokens
        self._eos[slot] = req.eos_id
        self._out[slot] = []
        self._temps[slot] = eff_temp
        if eff_temp > 0.0:
            # canonicalize legacy uint32 [2] keys to typed (same key
            # data → same split children → same draws), so per-slot
            # schedules and the free-slot dummy always stack together
            key = req.sample_key
            if not jax.dtypes.issubdtype(
                    getattr(key, "dtype", None), jax.dtypes.prng_key):
                key = jax.random.wrap_key_data(
                    jnp.asarray(key, jnp.uint32))
            # solo generate's schedule: one split per prospective token
            self._keys[slot] = jax.random.split(key, req.max_new_tokens)
        else:
            self._keys[slot] = None
        return slot

    # -- decode ------------------------------------------------------------

    def step(self) -> dict[int, list[int]]:
        """Advance every slot one token; returns {slot: tokens} for
        requests that finished on this tick."""
        if self.temperature > 0.0:
            keys = jnp.stack([
                self._keys[s][len(self._out[s])]
                if (self._busy[s] and self._keys[s] is not None
                    and len(self._out[s]) < len(self._keys[s]))
                else self._dummy_key
                for s in range(self.n_slots)
            ])
            temps = jnp.asarray([
                self._temps[s] if self._busy[s] else 0.0
                for s in range(self.n_slots)
            ], jnp.float32)
        else:
            keys = self._greedy_keys      # constants; _tick ignores
            temps = self._zero_temps      # them on the greedy path
        tok, self.last_logits, self.cache = self._tick(
            self.params, self.cache, self.last_logits, keys, temps)
        done: dict[int, list[int]] = {}
        tok_host = np.asarray(tok)
        for slot in range(self.n_slots):
            if not self._busy[slot]:
                continue
            t = int(tok_host[slot])
            self._out[slot].append(t)
            self._budget[slot] -= 1
            if self._budget[slot] <= 0 or t == self._eos[slot]:
                done[slot] = self._out[slot]
                self._busy[slot] = False
                # Rewind the row to 0.  Free rows still tick with the
                # batch (one compiled program for all slots), so the
                # position resumes advancing and scatters garbage K/V
                # from 0 upward — which is safe because every occupant
                # WRITES positions before attending to them: admission
                # splices [0, L) and each decode step writes pos before
                # reading [0, pos].  The rewind's only job is keeping
                # the write position in bounds on long-idle slots.
                # (Anything that reads cache rows it didn't write —
                # e.g. a future speculative-decode path — must re-splice
                # or re-validate the row first.)
                self.cache = self.cache._replace(
                    length=self.cache.length.at[slot].set(0))
        return done

    # -- convenience -------------------------------------------------------

    def run(self, requests: list[Request]) -> list[list[int]]:
        """Serve ``requests`` to completion (admission order, slots
        recycled as they free up); returns each request's tokens."""
        results: list[list[int] | None] = [None] * len(requests)
        slot_owner: dict[int, int] = {}
        pending = list(enumerate(requests))
        while pending or slot_owner:
            while pending and self.free_slots():
                idx, req = pending.pop(0)
                slot_owner[self.admit(req)] = idx
            for slot, toks in self.step().items():
                results[slot_owner.pop(slot)] = toks
        assert all(r is not None for r in results)
        return results  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Speculative decoding (draft-and-verify), greedy acceptance.
# ---------------------------------------------------------------------------


import functools


@functools.lru_cache(maxsize=32)
def _spec_programs(cfg: llama.LlamaConfig, draft_cfg: llama.LlamaConfig,
                   draft_k: int):
    """Compiled draft/verify programs, cached per (configs, draft_k) so
    repeated speculative_generate calls reuse one XLA compile (the same
    lifetime pattern as ContinuousBatcher's held closures)."""

    # hvdlint: disable=HVD001 -- held by the lru_cache: one program per config triple
    @jax.jit
    def draft_round(dparams, dcache, first_tok):
        """draft_k proposals from first_tok, in draft_k + 1 decode steps:
        the extra step consumes the LAST proposal so its K/V is in the
        draft cache — when a round accepts all draft_k proposals the
        frontier advances past that position, and a hole there would
        poison every later draft.  The extra step's own token is
        discarded (it was never verified)."""
        def step(carry, _):
            tok, cache = carry
            logits, cache = llama.decode_step(dparams, tok, draft_cfg,
                                              cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, cache), nxt

        (_, dcache), drafts = lax.scan(
            step, (first_tok, dcache), None, length=draft_k + 1)
        return jnp.moveaxis(drafts, 0, 1)[:, :draft_k], dcache

    # hvdlint: disable=HVD001 -- held by the lru_cache: one program per config triple
    @jax.jit
    def verify_round(params_, tcache, chunk):
        logits, tcache = llama.decode_chunk(params_, chunk, cfg, tcache)
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
        return logits, preds, tcache

    return draft_round, verify_round


def speculative_generate(
    params: dict,
    cfg: llama.LlamaConfig,
    draft_params: dict,
    draft_cfg: llama.LlamaConfig,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    draft_k: int = 4,
    max_len: int | None = None,
    prompt_lengths: jax.Array | None = None,
    stats: dict | None = None,
    timeline: Any = None,
) -> jax.Array:
    """Greedy speculative decoding: a small draft model proposes
    ``draft_k`` tokens per round, the target verifies the full
    ``(draft_k + 1)``-wide chunk ``[cur, d_1..d_k]`` in ONE
    :func:`~horovod_tpu.models.llama.decode_chunk` pass, and the longest
    matching prefix is accepted — so a round can accept all ``draft_k``
    proposals, with position ``draft_k`` of the verify logits supplying
    the target's own follow-on token (emitted as the next round's
    ``cur``).  No draft decode is ever wasted.

    With greedy acceptance the output is **bit-identical to the target's
    own greedy** ``generate`` — the draft only changes how many target
    passes it takes (1 + accepted per round instead of 1 per token), so
    any draft, however bad, is safe (pinned by ``tests/test_serving.py``).

    Batched with PER-ROW acceptance: rows accept different prefix lengths
    each round, which makes every cache ragged — the [B] ``length``
    vector IS the rewind (stale K/V beyond it is masked and rewritten
    before any read, the same write-before-read invariant the slot pool
    relies on).  Rows that hit their token budget freeze their length
    (clamped to prompt + max_new_tokens - 1) while slower rows continue,
    keeping every cache write in bounds by construction rather than by
    scatter-drop semantics.  Returns [B, max_new_tokens].

    ``stats``: optional dict filled with observability counters —
    ``rounds``, ``accepted_per_round`` (list of [B] int arrays) and
    ``max_length_seen`` (max cache length across rounds).  ``timeline``:
    optional :class:`horovod_tpu.timeline.Timeline` receiving a
    per-round acceptance counter event.
    """
    b, l = prompt.shape
    max_len = max_len or (l + max_new_tokens + draft_k + 1)
    if max_len < l + max_new_tokens + draft_k + 1:
        raise ValueError(
            f"max_len={max_len} < prompt {l} + max_new_tokens "
            f"{max_new_tokens} + draft_k {draft_k} + 1 (verification "
            f"overshoot needs the slack)")

    tcache = llama.init_cache(cfg, b, max_len)
    dcache = llama.init_cache(draft_cfg, b, max_len)
    lengths = (jnp.full((b,), l, jnp.int32) if prompt_lengths is None
               else jnp.asarray(prompt_lengths, jnp.int32))
    tlog, tcache = llama.prefill(params, prompt, cfg, tcache,
                                 lengths=lengths)
    _, dcache = llama.prefill(draft_params, prompt, draft_cfg, dcache,
                              lengths=lengths)

    draft_round, verify_round = _spec_programs(cfg, draft_cfg, draft_k)

    out = np.zeros((b, max_new_tokens), np.int32)
    emitted = np.zeros(b, np.int32)
    rows = np.arange(b)
    # finished rows freeze here: the largest length any row ever needs
    # is its last emitted token's position (prompt + max_new - 1), and
    # clamping to it bounds every later garbage write of the frozen row
    # to <= len_cap + draft_k < max_len — in bounds by arithmetic, not
    # by the scatter dropping out-of-range indices
    len_cap = np.asarray(lengths) + max_new_tokens - 1
    if stats is not None:
        stats["rounds"] = 0
        stats["accepted_per_round"] = []
        stats["max_length_seen"] = int(np.asarray(lengths).max())

    def emit(row, tok):
        if emitted[row] < max_new_tokens:
            out[row, emitted[row]] = tok
            emitted[row] += 1

    while (emitted < max_new_tokens).any():
        cur = jnp.argmax(tlog, axis=-1).astype(jnp.int32)     # [B]
        cur_host = np.asarray(cur)
        for r in rows:
            emit(r, int(cur_host[r]))
        # draft proposes cur's continuations: d_1..d_k
        drafts, dcache = draft_round(draft_params, dcache, cur)
        # target consumes the FULL [cur, d_1..d_k] chunk; preds[:, i] is
        # the target's greedy token after chunk[:, :i+1], so preds[:, k]
        # (the +1 width) is the follow-on token when everything accepts
        chunk = jnp.concatenate([cur[:, None], drafts], axis=1)
        logits, preds, tcache = verify_round(params, tcache, chunk)
        # per-row longest accepted prefix: d_i accepted while == preds_i-1
        d_host = np.asarray(drafts)
        p_host = np.asarray(preds)
        accept = np.zeros(b, np.int32)
        for r in rows:
            a = 0
            while a < draft_k and d_host[r, a] == p_host[r, a]:
                emit(r, int(d_host[r, a]))
                a += 1
            accept[r] = a
        # rewind both caches to the true accepted frontier (clamped for
        # rows that just finished) and pick the logits that follow each
        # row's last accepted token
        new_len = np.minimum(np.asarray(lengths) + 1 + accept, len_cap)
        lengths = jnp.asarray(new_len, jnp.int32)
        tcache = tcache._replace(length=lengths)
        dcache = dcache._replace(length=lengths)
        tlog = logits[jnp.arange(b), jnp.asarray(accept)]      # [B, V]
        if stats is not None:
            stats["rounds"] += 1
            stats["accepted_per_round"].append(accept.copy())
            stats["max_length_seen"] = max(stats["max_length_seen"],
                                           int(new_len.max()))
        if timeline is not None:
            timeline.counter(
                "serving.speculative", "ACCEPT",
                {"accepted": int(accept.sum()), "rows": b})

    return jnp.asarray(out)
