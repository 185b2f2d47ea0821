"""Collects ``benchmark/tests/test_step_log.py`` under tier-1: the same test
functions, parametrisations and module fixtures; the one test that pins the
length of ``BENCHMARK.json``'s ``per_layer`` at PR 35's 81 entries is taken on
those 81 (held, by their digest, to be the entries PR 35 left: the cut sees
the accepted spec and nothing edited), and what later PRs appended (a cell's
own metrics: files and entries only) is checked beside it.  Run directly
(``python -m pytest benchmark/tests``) the accepted case fails on
``len(...) == 81`` until a ``benchmark`` issue edits that line (PERF.md
section 7)."""

import hashlib
import json
import os
import sys

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))    # `import rehearse`

from benchmark import lib  # noqa: E402
from benchmark.tests import test_step_log as accepted  # noqa: E402
from benchmark.tests.test_step_log import *  # noqa: E402,F401,F403

ACCEPTED_ENTRIES = 81
#: sha-256 of ``json.dumps(per_layer[:81], sort_keys=True)`` at PR 37's commit
ACCEPTED_DIGEST = \
    "93369594d82581a81e0de054f52bc44d62b7765c7c3e83b810910a1f2e9c3481"


def test_every_new_entry_has_its_file_and_accepted_cells(monkeypatch):  # noqa: F811,E501
    spec = lib.benchmark_spec()
    assert hashlib.sha256(json.dumps(
        spec["per_layer"][:ACCEPTED_ENTRIES], sort_keys=True).encode()
    ).hexdigest() == ACCEPTED_DIGEST
    monkeypatch.setattr(accepted.lib, "benchmark_spec", lambda: dict(
        spec, per_layer=spec["per_layer"][:ACCEPTED_ENTRIES]))
    accepted.test_every_new_entry_has_its_file_and_accepted_cells()
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"][ACCEPTED_ENTRIES:]:
        assert lib.has_module("layer_metrics", m["name"]), m["name"]
        assert set(m["workloads"]) <= cells and len(m["workloads"]) == 1
        assert set(m["workloads"]) <= set(lib.metric_cells(
            lib.find(spec["end_to_end"], m["moves"], "metric"), spec))
