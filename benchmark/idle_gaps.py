"""The device's idle time by what the host was doing: shares of the traced
window, for the ``idle_*_pct`` readers in ``layer_metrics/``.

``trace_reduce.reduce`` names every idle gap of the device by the shortest
host span open at its middle and keeps the whole list in
``rec["trace"]["idle_gaps"]``.  Each reader claims the spans of one layer (the
names as the trace holds them, constants of the reader's own file);
``idle_unattributed_pct`` takes what none of them claims, so the shares add up
to the device's idle share less the pauses of under a microsecond between two
operations of one program (``between_device_ops``), which are the device's own.
"""

from __future__ import annotations

from benchmark import trace_reduce


def pct(rec: dict, claims) -> float | None:
    """Idle seconds under the span names that ``claims(name)`` accepts, over
    the window, in percent; ``None`` in a run without a trace."""
    tr = rec.get("trace")
    if not tr:
        return None
    idle = sum(s for name, s in tr["idle_gaps"]
               if name != trace_reduce.BETWEEN_OPS and claims(name))
    return 100.0 * idle / tr["window_s"]
