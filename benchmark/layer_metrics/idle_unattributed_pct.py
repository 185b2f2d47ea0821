"""``idle_unattributed_pct``: share of the traced window in which the device
idled under a name none of the three layers' readers claims: ``no_host_span``
first (no span of the program's threads was open), then whatever else the
profiler put on those threads.  With the other three it adds up to
``device_idle_pct`` less ``between_device_ops``."""

from benchmark import idle_gaps, lib

LAYERS = tuple(lib.load_module("layer_metrics", name) for name in (
    "idle_front_door_pct", "idle_sched_pct", "idle_readback_pct"))


def claims(name: str) -> bool:
    return not any(layer.claims(name) for layer in LAYERS)


def read(rec: dict):
    return idle_gaps.pct(rec, claims)
