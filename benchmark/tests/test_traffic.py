"""The traffic generator: every seed offers the same work and the same load,
in another order; latencies are taken from due times."""

import collections
import json
import os

import numpy as np

from benchmark import lib, serve_stats, traffic_gen


def _mix(name):
    return lib.load_json("traffic", name + ".json")


def _plan(mix, n, seed, timed=True):
    rng = np.random.default_rng([seed, 1])
    systems = traffic_gen.draw_system_prompts(mix, 32768, rng)
    return traffic_gen.plan(mix, n, 32768, rng, systems, timed=timed)


def _shape_multiset(reqs):
    return collections.Counter((r.system, r.own_len, r.n_out) for r in reqs)


def _gap_multiset(reqs):
    # arrivals sit mid-gap: due[k] = sum(g[:k]) + g[k] / 2
    gaps, t = [], 0.0
    for r in reqs:
        g = 2.0 * (r.due - t)
        gaps.append(round(g, 9))
        t += g
    return collections.Counter(gaps)


def test_chat_every_seed_same_shapes_and_gaps_other_order():
    mix = _mix("chat")
    a, b = _plan(mix, 99, 1), _plan(mix, 99, 2**31 + 12345)
    assert _shape_multiset(a) == _shape_multiset(b)
    assert _gap_multiset(a) == _gap_multiset(b)
    assert [r.own_len for r in a] != [r.own_len for r in b]
    assert [r.due for r in a] != [r.due for r in b]
    assert traffic_gen.token_count(a) == traffic_gen.token_count(b)
    # the load is the file's rate: n gaps sum to n / rate, all due inside
    rate = mix["arrivals"]["rate_rps"]
    assert max(r.due for r in a) < 99 / rate
    assert abs(sum(_gap_multiset(a).elements()) - 99 / rate) < 1e-6


def test_chat_order_is_stratified():
    """Every run of 4 consecutive requests holds one gap and one prompt from
    each quarter of the sorted gaps and prompts: no seed is burstier."""
    mix = _mix("chat")
    assert mix["stratify"] == 4
    n = 88
    sorted_gaps = traffic_gen.gaps(mix, n)
    cuts = [sorted_gaps[round(j * n / 4) - 1] for j in (1, 2, 3)]
    lens = sorted(s[1] for s in traffic_gen.shapes(mix, n))
    lcuts = [lens[round(j * n / 4) - 1] for j in (1, 2, 3)]
    for seed in (1, 2, 3):
        reqs = _plan(mix, n, seed)
        gaps, t = [], 0.0
        for r in reqs:
            g = 2.0 * (r.due - t)
            gaps.append(g)
            t += g
        for b in range(0, n, 4):
            q = sorted(sum(g > c + 1e-9 for c in cuts) for g in gaps[b:b + 4])
            assert q == [0, 1, 2, 3], (seed, b, q)
            ql = sorted(sum(r.own_len > c for c in lcuts)
                        for r in reqs[b:b + 4])
            assert ql == [0, 1, 2, 3], (seed, b, ql)


def test_chat_shapes_are_the_files():
    mix = _mix("chat")
    reqs = _plan(mix, 99, 7)
    lens = sorted(len(r.prompt) for r in reqs)
    outs = sorted(r.n_out for r in reqs)
    assert 288 <= lens[0] and lens[-1] <= 1024
    assert 16 <= outs[0] and outs[-1] <= 256
    assert 400 <= lens[len(lens) // 2] <= 500
    assert 85 <= outs[len(outs) // 2] <= 110
    e = mix["engine"]
    assert max(len(r.prompt) + r.n_out for r in reqs) <= e["max_len"]
    # the system prompt is a whole cache block and is shared
    assert mix["shapes"]["system_prompts"]["tokens"] % e["chunk"] == 0
    heads = collections.Counter(tuple(r.prompt[:256]) for r in reqs)
    assert len(heads) == 4 and min(heads.values()) >= 20


def test_digest_repeats_for_one_seed_and_differs_between_seeds():
    mix = _mix("chat")
    assert traffic_gen.digest(_plan(mix, 50, 5)) == \
        traffic_gen.digest(_plan(mix, 50, 5))
    assert traffic_gen.digest(_plan(mix, 50, 5)) != \
        traffic_gen.digest(_plan(mix, 50, 6))


def test_longdoc_batch_follows_seconds_and_fits_the_engine():
    mix = _mix("longdoc")
    per_s = mix["requests_per_window_second"]
    for seconds in (10, 45):
        n = max(round(seconds * per_s), 1)
        reqs = _plan(mix, n, 3, timed=False)
        assert len(reqs) == n and all(r.due == 0.0 for r in reqs)
        assert _shape_multiset(reqs) == _shape_multiset(
            _plan(mix, n, 4, timed=False))
    assert round(45 * per_s) > round(10 * per_s)
    e = mix["engine"]
    big = _plan(mix, 40, 3, timed=False)
    assert max(len(r.prompt) + r.n_out for r in big) <= e["max_len"]
    assert min(len(r.prompt) for r in big) >= 2048 + 64
    # each document is asked once: no two prompts share a first block
    assert len({tuple(r.prompt[:256]) for r in big}) == len(big)


def test_ttft_is_taken_from_due_time_so_a_late_generator_raises_it():
    def rec(sent_late):
        return {"window": (10.0, 20.0), "requests": [{
            "due": 12.0, "sent": 12.0 + sent_late, "in_window": True,
            "ok": True, "first_token": 12.2 + sent_late, "terminal": 13.0,
            "prompt_len": 300, "n_out": 20, "own_len": 44, "enqueue": 12.01,
            "recv": 12.0, "admit": 12.02, "prefill_chunks": 1,
            "prefix_skipped": 256}]}
    on_time = lib.load_module("end_to_end", "ttft_mean_ms").read(rec(0.0))
    late = lib.load_module("end_to_end", "ttft_mean_ms").read(rec(0.5))
    assert abs(on_time - 200.0) < 1e-6
    assert abs(late - 700.0) < 1e-6
    assert abs(serve_stats.gen_late_ms(rec(0.5))[0] - 500.0) < 1e-6


def test_token_gaps_count_only_steps_that_start_with_a_row_decoding():
    # steps: (began, returned, rows decoded, rows prefilling, finished)
    steps = [(0.0, 1.00, 2, 0, 0), (1.0, 1.05, 2, 1, 0), (1.05, 1.15, 2, 0, 2),
             (3.0, 3.10, 0, 1, 0), (3.1, 3.20, 1, 0, 0), (3.2, 3.25, 1, 0, 0)]
    gaps = serve_stats.token_gaps_ms({"window": (1.01, 10.0), "steps": steps})
    # 1.05: counted (2 rows were decoding); 1.15: counted; 3.10: not (both
    # rows finished in the step before); 3.20: not (that step decoded none);
    # 3.25: counted
    assert [round(g, 6) for g in gaps] == [50.0, 100.0, 50.0]


def test_benchmark_json_names_only_files_that_exist():
    spec = lib.benchmark_spec()
    for c in spec["configs"]:
        with open(os.path.join(lib.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert lib.has_module("families", cfg["family"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in spec["workloads"]:
        assert lib.has_module("drivers", _mix(w["traffic"])["driver"])
    for m in spec["end_to_end"]:
        assert lib.has_module("end_to_end", m["name"]), m["name"]
    for m in spec["per_layer"]:
        assert lib.has_module("layer_metrics", m["name"]), m["name"]
        assert set(lib.metric_cells(m, spec, m["moves"])) <= set(
            lib.metric_cells(lib.find(spec["end_to_end"], m["moves"], "m"),
                             spec))
