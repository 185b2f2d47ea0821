"""Self-drafting speculation and pluggable scheduler policies (PR 8).

Two seams, one contract.  The drafter/verify pair must never change
what a request's tokens ARE — greedy longest-prefix acceptance makes
every accepted token the model's own argmax, so spec on/off is
bit-identical to the solo ``llama.generate`` run (scheduler invariant
2 extended through the ``(draft_k + 1)``-wide verify tick).  Policies
must never change outputs either — they reorder *waiting* (admission
order, preemption victim), not tokens.  The directed tests here pin
both sides: drafter unit behavior, policy unit orderings, EDF evicting
the slack-richest (not the youngest) row, the priority starvation
guard, the ``serve.draft`` fault site degrading one row for one round,
and parity sweeps under preempt-replay with the prefix cache on/off —
plus the one-signature-per-program pin (``compile_cache_sizes()``
frozen mid-serve, ``spec_tick`` replacing ``tick``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import scheduling
from horovod_tpu.drafting import NgramDraftState
from horovod_tpu.faults import FaultRegistry
from horovod_tpu.metrics import MetricsRegistry
from horovod_tpu.models import llama
from horovod_tpu.serving import OK, Request
from horovod_tpu.serving_scheduler import ServeEngine, _QueueEntry

pytestmark = pytest.mark.spec


def _tiny():
    cfg = llama.llama_tiny(attn_impl="dense", dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _solo(params, cfg, req, max_len):
    out = llama.generate(
        params, jnp.asarray([req.prompt], jnp.int32), cfg,
        max_new_tokens=req.max_new_tokens, max_len=max_len)
    return [int(t) for t in np.asarray(out)[0]]


# ---------------------------------------------------------------------------
# drafter unit behavior


def test_drafter_proposes_from_repeated_suffix():
    # history ... a b c X a b c — suffix (a,b,c) matched at the earlier
    # occurrence; its continuation[0] (X) is the guess for the in-flight
    # token and is SKIPPED, so drafts start one past it.
    d = NgramDraftState([1, 2, 3, 9, 7, 5, 1, 2, 3])
    assert d.propose(3) == [7, 5, 1]


def test_drafter_no_match_returns_empty():
    d = NgramDraftState([1, 2, 3, 4, 5])
    assert d.propose(4) == []
    assert d.propose(0) == []


def test_drafter_extend_is_incremental():
    d = NgramDraftState([4, 4, 7])
    assert d.propose(2) == []          # suffix (4,4,7) / (4,7) / (7) unseen twice
    d.extend([4, 4, 7])                # now every suffix n-gram repeats
    assert d.propose(2) == [4, 7]      # match at first (4,4,7); skip the 4


def test_drafter_short_period_first_occurrence_fallback():
    # A constant stream: every recent occurrence of the suffix gram butts
    # against the end of the history (empty continuation) — the first
    # occurrence is the only usable source.  This is the lookup-friendly
    # regime of the bench arm, so it must actually draft.
    d = NgramDraftState([5, 9, 0, 0, 0])
    d.extend([0, 0, 0])
    got = d.propose(4)
    assert got == [0] * len(got) and got, got


def test_drafter_validates_ngram_bounds():
    with pytest.raises(ValueError):
        NgramDraftState([1], min_ngram=0)
    with pytest.raises(ValueError):
        NgramDraftState([1], min_ngram=3, max_ngram=2)


# ---------------------------------------------------------------------------
# policy unit orderings (duck-typed on _QueueEntry / slot records)


def _entry(rid, *, priority=0, queued_steps=0, slo_deadline=None):
    return _QueueEntry(
        rid=rid, req=Request(prompt=[1], max_new_tokens=1,
                             priority=priority),
        queued_steps=queued_steps, slo_deadline=slo_deadline)


class _Row:
    def __init__(self, admit_seq, *, priority=0, slo_deadline=None):
        self.admit_seq = admit_seq
        self.slo_deadline = slo_deadline
        self.req = Request(prompt=[1], max_new_tokens=1,
                           priority=priority)


def test_fifo_policy_is_bit_compatible_with_hardcoded():
    p = scheduling.FifoPolicy()
    q = [_entry(0), _entry(1), _entry(2)]
    assert p.admission_order(q) == q                  # identity order
    rows = [(0, _Row(5)), (1, _Row(9)), (2, _Row(7))]
    assert p.victim(rows) == 1                        # youngest row


def test_priority_policy_orders_and_guards_starvation():
    p = scheduling.PriorityPolicy(starvation_steps=10)
    lo, hi, starved = (_entry(0, priority=0),
                       _entry(1, priority=5),
                       _entry(2, priority=0, queued_steps=10))
    # starved low-priority entry jumps the high-priority one
    assert p.admission_order([lo, hi, starved]) == [starved, hi, lo]
    # victim: lowest priority first, youngest on ties
    rows = [(0, _Row(1, priority=5)), (1, _Row(2, priority=0)),
            (2, _Row(3, priority=0))]
    assert p.victim(rows) == 2
    with pytest.raises(ValueError):
        scheduling.PriorityPolicy(starvation_steps=0)


def test_edf_policy_orders_by_deadline_no_slo_last():
    p = scheduling.EdfPolicy()
    a, b, c = (_entry(0, slo_deadline=9.0), _entry(1),
               _entry(2, slo_deadline=3.0))
    assert p.admission_order([a, b, c]) == [c, a, b]
    # victim: slack-richest (latest deadline; None = infinitely slack)
    rows = [(0, _Row(1, slo_deadline=3.0)), (1, _Row(2, slo_deadline=9.0))]
    assert p.victim(rows) == 1
    rows.append((2, _Row(3, slo_deadline=None)))
    assert p.victim(rows) == 2


def test_resolve_policy_names_env_and_instances(monkeypatch):
    assert isinstance(scheduling.resolve_policy("edf"),
                      scheduling.EdfPolicy)
    inst = scheduling.PriorityPolicy(starvation_steps=7)
    assert scheduling.resolve_policy(inst) is inst
    monkeypatch.setenv("HVD_TPU_SCHED_POLICY", "priority")
    assert isinstance(scheduling.resolve_policy(None),
                      scheduling.PriorityPolicy)
    monkeypatch.setenv("HVD_TPU_SCHED_POLICY", "")
    assert isinstance(scheduling.resolve_policy(None),
                      scheduling.FifoPolicy)
    with pytest.raises(ValueError):
        scheduling.resolve_policy("sjf")


# ---------------------------------------------------------------------------
# engine-level policy behavior


class _RecordingEdf(scheduling.EdfPolicy):
    """EDF that logs each chosen victim's request id (test probe)."""

    def __init__(self):
        self.victims = []

    def victim(self, candidates):
        slot = super().victim(candidates)
        self.victims.append(dict(candidates)[slot].request_id)
        return slot


def test_edf_preempts_slack_richest_not_youngest():
    """Two decoding rows on a full pool: the FIFO rule would evict the
    YOUNGEST (second-admitted) row; EDF must instead evict the row with
    the most time left to its SLO deadline — here the first-admitted
    one — proving the victim seam is live.  The evicted request replays
    and still finishes bit-identical to its solo run."""
    cfg, params = _tiny()
    max_len = 24
    policy = _RecordingEdf()
    slack = Request(prompt=[3, 1, 4, 1, 5, 9], max_new_tokens=8,
                    slo_s=1e6)                     # slack-rich
    tight = Request(prompt=[2, 7, 1, 8, 2, 8], max_new_tokens=8,
                    slo_s=1e-6)                    # urgent
    filler = Request(prompt=[6, 6, 6, 6, 6, 6], max_new_tokens=8)
    # 3 slots over 8 usable blocks: the two 4-block rows fill the pool,
    # so the filler starves on BLOCKS with a slot free — the (only)
    # preemption trigger.
    eng = ServeEngine(params, cfg, n_slots=3, max_len=max_len, chunk=4,
                      block_size=4, n_blocks=9, preempt_after=2,
                      policy=policy)
    rid_slack = eng.submit(slack)
    rid_tight = eng.submit(tight)
    while eng._queue:                              # both rows admitted
        eng.step()
    rid_fill = eng.submit(filler)                  # starves on blocks
    steps = 0
    while eng.pending():
        eng.step()
        steps += 1
        assert steps < 400, "EDF churn did not drain"
    assert eng.counters["preemptions"] >= 1
    # FIFO would have evicted rid_tight (youngest); EDF's first victim
    # is the slack-rich first-admitted row
    assert policy.victims[0] == rid_slack
    assert policy.victims, policy.victims
    for req, rid in ((slack, rid_slack), (tight, rid_tight),
                     (filler, rid_fill)):
        res = eng.results[rid]
        assert res.status == OK
        assert list(res) == _solo(params, cfg, req, max_len)


@pytest.mark.parametrize("starvation_steps,first_done",
                         [(64, "high"), (2, "low")])
def test_priority_admission_and_starvation_guard(starvation_steps,
                                                 first_done):
    """One slot, one running filler, a low- and a high-priority waiter.
    With the default (large) starvation budget the high-priority request
    admits first; with a tiny budget the low-priority one has already
    starved past it by the time the slot frees and jumps ahead — low
    priority means later, never never."""
    cfg, params = _tiny()
    eng = ServeEngine(
        params, cfg, n_slots=1, max_len=24, chunk=4,
        policy=scheduling.PriorityPolicy(
            starvation_steps=starvation_steps))
    rid_fill = eng.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=6))
    eng.step()                                     # filler admits alone
    assert not eng._queue and eng._slots[0].request_id == rid_fill
    rid_low = eng.submit(Request(prompt=[5, 6], max_new_tokens=2,
                                 priority=0))
    rid_high = eng.submit(Request(prompt=[7, 8], max_new_tokens=2,
                                  priority=5))
    first = {"high": rid_high, "low": rid_low}[first_done]
    second = rid_low if first == rid_high else rid_high
    while first not in eng.results:
        eng.step()
    assert second not in eng.results               # admitted strictly later
    while eng.pending():
        eng.step()
    assert all(eng.results[r].status == OK
               for r in (rid_fill, rid_low, rid_high))


# ---------------------------------------------------------------------------
# speculation: parity, program pins, counters, fault degradation


def _run_all(eng, reqs):
    rids = [eng.submit(r) for r in reqs]
    while eng.pending():
        eng.step()
    return rids


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_spec_preempt_replay_parity(prefix_cache):
    """Speculation under preempt-replay churn on an overcommitted pool,
    prefix cache on and off: every request must land OK and
    bit-identical to its solo greedy run, with preemptions actually
    exercised and the program set frozen mid-serve (``spec_tick``
    replacing ``tick``, nothing retracing)."""
    cfg, params = _tiny()
    max_len = 24
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(5):
        pl = int(rng.integers(3, 8))
        reqs.append(Request(
            prompt=[int(t) for t in rng.integers(1, cfg.vocab_size, pl)],
            max_new_tokens=int(rng.integers(3, 9))))
    eng = ServeEngine(params, cfg, n_slots=3, max_len=max_len, chunk=4,
                      block_size=4, n_blocks=9, preempt_after=2,
                      prefix_cache=prefix_cache, spec=True, draft_k=3)
    rids = [eng.submit(r) for r in reqs]
    sizes = None
    while eng.pending():
        eng.step()
        if sizes is None and eng.spec_counters["rounds"] >= 1:
            sizes = eng.compile_cache_sizes()      # post-warmup snapshot
    assert eng.counters["preemptions"] >= 1, "pool not overcommitted"
    assert sizes == \
        {"sample": 0, "tick": 0, "chunk": 1, "set_row": 1, "spec_tick": 1}
    assert eng.compile_cache_sizes() == sizes      # frozen mid-serve
    for req, rid in zip(reqs, rids):
        res = eng.results[rid]
        assert res.status == OK
        assert list(res) == _solo(params, cfg, req, max_len), rid


def test_spec_accepts_on_repetitive_stream_and_mirrors_counters():
    """A doctored model (zeroed lm_head → constant greedy stream) is the
    drafter's best case: acceptance must be well above zero, emission
    must stay bit-identical to solo decode, and the host-side
    ``spec_counters`` dict must mirror the registry's ``serve.spec.*``
    counters exactly."""
    cfg, params = _tiny()
    flat = dict(params)
    flat["lm_head"] = jnp.zeros_like(flat["lm_head"])
    max_len = 32
    mreg = MetricsRegistry()
    eng = ServeEngine(flat, cfg, n_slots=2, max_len=max_len, chunk=4,
                      spec=True, draft_k=4, metrics=mreg)
    reqs = [Request(prompt=[5, 9, 2, 0, 0, 0], max_new_tokens=16)
            for _ in range(3)]
    rids = _run_all(eng, reqs)
    c = eng.spec_counters
    assert c["accepted"] > c["row_rounds"], c      # > 1 accepted/round
    assert c["proposed"] >= c["accepted"]
    for k, v in c.items():
        assert mreg.counter("serve.spec." + k).value == v
    assert (mreg.histogram("serve.spec.accepted_per_round").count
            == c["row_rounds"])
    for req, rid in zip(reqs, rids):
        assert list(eng.results[rid]) == _solo(flat, cfg, req, max_len)


def test_spec_off_engine_is_untouched():
    """A spec-off engine must be byte-for-byte the pre-PR engine: no
    ``spec_tick`` key in the program pin, no drafter on any slot, no
    ``serve.spec.*`` counters registered."""
    cfg, params = _tiny()
    mreg = MetricsRegistry()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=24, chunk=4,
                      metrics=mreg)
    _run_all(eng, [Request(prompt=[1, 2, 3], max_new_tokens=4)])
    assert eng.compile_cache_sizes() == {"sample": 1, "tick": 1, "chunk": 1,
                                         "set_row": 1}
    assert not eng.spec and eng._spec_tick is None
    assert all(s.draft is None for s in eng._slots)
    assert not any(n.startswith("serve.spec.")
                   for n in mreg.snapshot()["counters"])


def test_spec_env_knobs(monkeypatch):
    cfg, params = _tiny()
    monkeypatch.setenv("HVD_TPU_SPEC", "1")
    monkeypatch.setenv("HVD_TPU_DRAFT_K", "2")
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4)
    assert eng.spec and eng.draft_k == 2
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4,
                    spec=True, draft_k=0)


@pytest.mark.faults
def test_serve_draft_fault_degrades_row_not_request():
    """A fault injected at the ``serve.draft`` site must cost only that
    row's proposals for that round — the request never fails, never
    retries, and its output stays bit-identical to solo; the degradation
    is visible as ``serve.spec.draft_faults``."""
    cfg, params = _tiny()
    flat = dict(params)
    flat["lm_head"] = jnp.zeros_like(flat["lm_head"])
    max_len = 32
    reg = FaultRegistry()
    mreg = MetricsRegistry()
    eng = ServeEngine(flat, cfg, n_slots=1, max_len=max_len, chunk=4,
                      spec=True, draft_k=4, faults=reg, metrics=mreg)
    req = Request(prompt=[5, 9, 2, 0, 0, 0], max_new_tokens=12)
    rid = eng.submit(req)
    rule = reg.inject("serve.draft", on_hit=2, count=3, key=rid)
    while eng.pending():
        eng.step()
    assert rule.fired == 3
    assert mreg.counter("serve.spec.draft_faults").value == 3
    res = eng.results[rid]
    assert res.status == OK and eng.counters["retries"] == 0
    assert list(res) == _solo(flat, cfg, req, max_len)
    # rounds 2-4 proposed nothing, the rest drafted — acceptance survives
    assert eng.spec_counters["accepted"] > 0
