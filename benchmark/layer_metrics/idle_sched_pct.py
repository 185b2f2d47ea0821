"""``idle_sched_pct``: share of the traced window in which the device idled
while the host was in the scheduler's python: a phase of ``ServeEngine.step``
other than the readback (``serve.step[.<phase>]``, and the benchmark's own
``engine.step`` around it), or one of jax's dispatch spans, which on the
threads the reduction looks at occur only inside those phases."""

from benchmark import idle_gaps

NAMES = ("engine.step", "serve.step", "serve.step.expire", "serve.step.admit",
         "serve.step.admit.cache_acquire", "serve.step.admit.prefill_dispatch",
         "serve.step.draft", "serve.step.decode_dispatch", "serve.step.verify",
         "serve.step.sample_postprocess", "serve.step.bookkeeping",
         "shard_args", "ParseArguments",
         "PJRT_LoadedExecutable_Execute linkage",
         "PythonRefManager::CollectGarbage")
PREFIXES = ("PjitFunction(", "DevicePut")


def claims(name: str) -> bool:
    return name in NAMES or name.startswith(PREFIXES)


def read(rec: dict):
    return idle_gaps.pct(rec, claims)
