"""Worker for the tensor-parallel serving test (tests/test_serving_tp.py).

Launched as ONE fresh OS process so it controls jax backend init from
scratch: it forces ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
and ``JAX_PLATFORMS=cpu`` BEFORE the first jax import — the re-exec
fixture the `tp` marker promises — then serves the same deterministic
request stream through a ``tp_size=2`` engine (built via the
``HVD_TPU_TP`` env knob, exercising the env path the in-process tests
don't) and an unsharded engine, asserting token parity and the frozen
one-signature-per-program invariant.

Prints one final line ``WORKER_OK {json}`` on success, or
``WORKER_SKIP {reason}`` (exit 0) when the host cannot fake a
multi-device CPU mesh — the launcher skips instead of failing.
"""

import faulthandler
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:        # launched by script path, not -m
    sys.path.insert(0, REPO)

faulthandler.enable()
faulthandler.dump_traceback_later(
    float(os.environ.get("HVD_TPU_WORKER_DUMP_AFTER_S", "300")),
    exit=False)

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["HVD_TPU_TP"] = "2"          # the env knob under test


def main() -> None:
    import jax
    import jax.numpy as jnp

    if jax.device_count() < 2:
        print("WORKER_SKIP could not fake a multi-device CPU host: "
              f"device_count={jax.device_count()}")
        return

    from horovod_tpu import metrics as metrics_mod
    from horovod_tpu.models import llama
    from horovod_tpu.serving import Request
    from horovod_tpu.serving_scheduler import ServeEngine

    cfg = llama.llama_tiny(dtype=jnp.float32, n_kv_heads=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    stem = list(range(2, 11))
    reqs = [Request(prompt=stem + [40 + i], max_new_tokens=5)
            for i in range(3)]

    # tp_size unset -> HVD_TPU_TP=2 from the env above.
    sharded = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=4,
                          prefix_cache=True, spec=True, draft_k=3,
                          metrics=metrics_mod.NULL)
    assert sharded.tp_size == 2, sharded.tp_size
    plain = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=4,
                        tp_size=1, prefix_cache=True, spec=True,
                        draft_k=3, metrics=metrics_mod.NULL)
    out_s = sharded.run(reqs)
    out_p = plain.run(reqs)
    assert all(r.ok for r in out_s), [r.status for r in out_s]
    assert all(r.ok for r in out_p), [r.status for r in out_p]
    toks_s = [list(r) for r in out_s]
    toks_p = [list(r) for r in out_p]
    assert toks_s == toks_p, (toks_s, toks_p)
    sizes = sharded.compile_cache_sizes()
    assert sizes == {"sample": 0, "tick": 0, "chunk": 1, "set_row": 1,
                     "spec_tick": 1}, sizes

    print("WORKER_OK " + json.dumps(
        {"devices": jax.device_count(), "tp_size": sharded.tp_size,
         "tokens": toks_s, "compile_cache_sizes": sizes},
        sort_keys=True))


if __name__ == "__main__":
    main()
