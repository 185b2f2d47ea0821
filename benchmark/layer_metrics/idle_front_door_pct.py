"""``idle_front_door_pct``: share of the traced window in which the device
idled while the host was in the front door: the router's ``route()``
(``router.*``, and the benchmark's own ``route`` around it) or the replica's
pump between two engine steps (``replica.pump.*``: taking submissions, firing
callbacks, refreshing the router's view with its prefix digest, waiting)."""

from benchmark import idle_gaps

NAMES = ("route", "router.route", "router.admission", "router.place",
         "router.submit", "replica.pump.submit", "replica.pump.callbacks",
         "replica.pump.view", "replica.pump.wait")


def claims(name: str) -> bool:
    return name in NAMES


def read(rec: dict):
    return idle_gaps.pct(rec, claims)
