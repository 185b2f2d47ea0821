"""``idle_readback_pct``: share of the traced window in which the device idled
while the host was in the token readback (``serve.step.device_sync``, and
jax's ``np.asarray(jax.Array)`` inside it): the device has finished the tick
and the host has not yet come back with the next one."""

from benchmark import idle_gaps

NAMES = ("serve.step.device_sync", "np.asarray(jax.Array)")


def claims(name: str) -> bool:
    return name in NAMES


def read(rec: dict):
    return idle_gaps.pct(rec, claims)
