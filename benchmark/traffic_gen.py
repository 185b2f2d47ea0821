"""The one generator of serving traffic.  A mix is a data file of parameters
(``traffic/<mix>.json``); this code reads it and makes the requests.

The rule that keeps runs comparable: **the seed never changes the work.**
For ``n`` requests the multiset of shapes (system prompt, own prompt
length, output length) and the multiset of inter-arrival gaps are functions
of the file and of ``n`` alone: the shapes are the file's quantile tables
read at the ``n`` mid-quantiles and paired by a permutation fixed in the
file, the gaps are the ``n`` mid-quantiles of an exponential at the file's
rate.  The seed permutes the order of both and draws the token ids.  So
every seed offers the same tokens and the same load, in another order.

The order is **stratified** (``"stratify": k`` in the file): every run of
``k`` consecutive requests holds one gap from each ``k``-th of the sorted
gaps and one prompt from each ``k``-th of the sorted prompts.  A free
permutation of 88 exponential gaps now and then puts the short ones
together, and the burst it makes moves a mean time to first token by a
tenth from seed to seed (PERF.md, PR 24); with the strata every seed's
traffic is as bursty as every other's.

The arrival arithmetic (gap of a Poisson process = exponential at the rate;
a fixed rate = equal gaps) follows ``horovod_tpu.loadgen``'s ``Poisson`` and
``FixedRate``; it is copied here, stratified, so that a later PR cannot
change the yardstick (PERF.md, Open questions, lists the original).
"""

from __future__ import annotations

import dataclasses
import hashlib
import math

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as planned: when it is due (seconds after the phase
    starts), its prompt and how many tokens it asks for."""

    due: float
    prompt: list
    n_out: int
    system: int          # index of its system prompt, -1 for none
    own_len: int         # prompt tokens that are its own


def _interp(table: list, u: float) -> float:
    """Inverse CDF given as ``[[q, value], ...]``, piecewise linear."""
    for (q0, v0), (q1, v1) in zip(table, table[1:]):
        if u <= q1:
            return v0 + (v1 - v0) * (u - q0) / (q1 - q0)
    return float(table[-1][1])


def shapes(mix: dict, n: int) -> list:
    """The multiset of ``n`` request shapes ``(system, own_len, n_out)``, in
    the file's fixed order: a function of the file and ``n`` only."""
    sh = mix["shapes"]
    fixed = np.random.default_rng([int(sh["pairing_seed"]), n])
    mid = [(i + 0.5) / n for i in range(n)]
    own = [int(round(_interp(sh["own_prompt_quantiles"], u))) for u in mid]
    out = [int(round(_interp(sh["output_quantiles"], u))) for u in mid]
    out = [out[i] for i in fixed.permutation(n)]
    n_sys = int(sh["system_prompts"]["count"])
    system = ([int(i) % n_sys for i in fixed.permutation(n)] if n_sys
              else [-1] * n)
    return list(zip(system, own, out))


def gaps(mix: dict, n: int) -> list:
    """The multiset of ``n`` inter-arrival gaps, ascending: the mid-quantiles
    of the arrival process at the file's rate, scaled so that they sum to
    ``n / rate`` exactly (every seed offers the same load)."""
    arr = mix["arrivals"]
    rate = float(arr["rate_rps"])
    if arr["process"] == "exponential_quantiles":
        g = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    elif arr["process"] == "fixed_rate":
        g = [1.0] * n
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    scale = (n / rate) / sum(g)
    return [x * scale for x in g]


def stratified_order(n: int, k: int, rng: np.random.Generator) -> list:
    """A permutation of ``range(n)`` (of items sorted by size) in which every
    run of ``k`` consecutive places holds one item from each of the ``k``
    equal slices of the sorted list; ``k`` of 1 is a free permutation."""
    k = max(min(int(k), n), 1)
    bounds = [round(j * n / k) for j in range(k + 1)]
    slices = [list(rng.permutation(np.arange(bounds[j], bounds[j + 1])))
              for j in range(k)]
    order = []
    while any(slices):
        members = [int(s.pop()) for s in slices if s]
        order += [members[i] for i in rng.permutation(len(members))]
    return order


def plan(mix: dict, n: int, vocab: int, rng: np.random.Generator,
         system_prompts: list | None = None, timed: bool = True) -> list:
    """``n`` requests in seed order.  ``system_prompts`` are the run's shared
    prefixes (drawn once per run, by :func:`draw_system_prompts`)."""
    sh = mix["shapes"]
    tail = int(sh.get("tail_tokens", 0))
    k = int(mix.get("stratify", 1))
    order = stratified_order(n, k, rng)
    shaped = shapes(mix, n)
    if timed:
        g = gaps(mix, n)
        g = [g[i] for i in stratified_order(n, k, rng)]
        due, t = [], 0.0
        for x in g:                 # each arrival sits mid-gap: all inside
            due.append(t + 0.5 * x)
            t += x
    else:
        due = [0.0] * n
    reqs = []
    for pos, i in enumerate(order):
        system, own, n_out = shaped[int(i)]
        body = rng.integers(1, vocab, size=own + tail).tolist()
        head = list(system_prompts[system]) if system >= 0 else []
        reqs.append(Planned(due=due[pos], prompt=head + body, n_out=n_out,
                            system=system, own_len=own + tail))
    return reqs


def draw_system_prompts(mix: dict, vocab: int,
                        rng: np.random.Generator) -> list:
    sp = mix["shapes"]["system_prompts"]
    return [rng.integers(1, vocab, size=int(sp["tokens"])).tolist()
            for _ in range(int(sp["count"]))]


def digest(reqs: list) -> str:
    """A digest of a schedule: the same seed gives the same one."""
    h = hashlib.sha256()
    for r in reqs:
        h.update(repr((round(r.due, 9), r.n_out, r.system)).encode())
        h.update(np.asarray(r.prompt, np.int64).tobytes())
    return h.hexdigest()


def token_count(reqs: list) -> int:
    return sum(len(r.prompt) + r.n_out for r in reqs)
