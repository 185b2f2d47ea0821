"""Sustained-run stability harness.

Trains a llama config continuously for a wall-clock budget with a
readback fence every GROUP steps, then reports step-time drift (the
leak/fragmentation detector a single throughput number cannot give),
loss sanity, and the min/max trail.  The first group is excluded from
steady-state stats, and a transiently stalled group is reported rather
than treated as a failure.

Usage:
    python tools/tpu_sustained_run.py --model 189m --minutes 14
    python tools/tpu_sustained_run.py --model 1b   --minutes 12
    JAX_PLATFORMS=cpu python tools/tpu_sustained_run.py --smoke

Prints one ``SUMMARY {json}`` line plus the full per-group ``GROUPS``
trail.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

MODELS = {
    "189m": dict(shape=dict(vocab_size=32768, dim=1024, n_layers=8,
                            n_heads=16, n_kv_heads=4, ffn_dim=4096),
                 remat=False, fused_loss=None, opt="adamw", lbs=4),
    "570m": dict(shape=dict(vocab_size=32768, dim=1536, n_layers=14,
                            n_heads=16, n_kv_heads=4, ffn_dim=6144),
                 remat=True, fused_loss=None, opt="adamw", lbs=4),
    # The capacity ceiling: fits only with the whole memory ladder.
    "1b": dict(shape=dict(vocab_size=32768, dim=2048, n_layers=16,
                          n_heads=16, n_kv_heads=4, ffn_dim=8192),
               remat=True, fused_loss=2048, opt="sgd", lbs=2),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=sorted(MODELS), default="189m")
    ap.add_argument("--minutes", type=float, default=14.0)
    ap.add_argument("--group", type=int, default=50)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config + seconds-long run (CPU CI shape)")
    args = ap.parse_args()

    import faulthandler

    budget_s = 30.0 if args.smoke else args.minutes * 60
    faulthandler.dump_traceback_later(int(budget_s + 600), exit=True)

    if args.smoke:
        # --smoke is CPU-shaped by definition; pin it before jax starts,
        # even when the caller forgot the env var.
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import llama

    hvd.init()
    print("backend:", jax.devices(), flush=True)

    spec = MODELS[args.model]
    if args.smoke:
        cfg = llama.llama_tiny(max_seq_len=128, attn_impl="dense")
        lbs, seq, group = 8, 128, 3
    else:
        cfg = llama.llama_tiny(
            max_seq_len=args.seq, attn_impl="flash", remat=spec["remat"],
            **({"fused_loss_chunk": spec["fused_loss"]}
               if spec["fused_loss"] else {}),
            **spec["shape"])
        lbs, seq, group = spec["lbs"], args.seq, args.group
    print(f"params: {llama.num_params(cfg)/1e9:.3f}B", flush=True)

    loss = llama.make_loss_fn(cfg)
    opt = (optax.sgd(1e-3, momentum=0.9) if spec["opt"] == "sgd"
           else optax.adamw(3e-4))
    tx = hvd.DistributedOptimizer(opt)
    params = hvd.broadcast_parameters(
        llama.init_params(cfg, jax.random.key(0)), root_rank=0)
    # Broadcast, or the step compiles twice (troubleshooting.md, "First
    # step is very slow").
    opt_state = hvd.broadcast_optimizer_state(jax.jit(tx.init)(params),
                                              root_rank=0)
    step = hvd.make_train_step(loss, tx, donate=True)

    key = jax.random.key(123)

    def batch_for(i: int):
        t = jax.random.randint(jax.random.fold_in(key, i),
                               (lbs, seq + 1), 0, cfg.vocab_size, jnp.int32)
        return (t[:, :-1], t[:, 1:])

    out = step(params, opt_state, batch_for(0))
    jax.device_get(out.loss)
    state = (out.params, out.opt_state)
    print("compiled; sustained loop starting", flush=True)

    groups: list[dict] = []
    t_start = time.time()
    i = 1
    while time.time() - t_start < budget_s:
        t0 = time.perf_counter()
        for _ in range(group):
            r = step(state[0], state[1], batch_for(i))
            state = (r.params, r.opt_state)
            i += 1
        lo = float(jax.device_get(r.loss))
        dt = (time.perf_counter() - t0) / group * 1e3
        groups.append({"step": i - 1, "ms": round(dt, 2),
                       "loss": round(lo, 4)})
        if len(groups) % 4 == 0:
            g = groups[-1]
            print(f"step {g['step']}: {g['ms']} ms/step, loss {g['loss']}",
                  flush=True)

    # First group excluded: whatever warm-up is left lands in it.
    steady = [g["ms"] for g in groups[1:]] or [g["ms"] for g in groups]
    med = statistics.median(steady)
    stalled = [g for g in groups[1:] if g["ms"] > 3 * med]
    summary = {
        "model": "tiny-smoke" if args.smoke else args.model,
        "smoke": args.smoke,
        "total_steps": i - 1,
        "wall_s": round(time.time() - t_start, 1),
        "steady_ms_median": round(med, 2),
        "steady_ms_min": min(steady),
        "steady_ms_max": max(steady),
        # drift vs early steady-state: the leak/fragmentation meter.
        "drift_pct": round(
            (statistics.mean(steady[-4:]) / statistics.mean(steady[:4]) - 1)
            * 100, 2) if len(steady) >= 8 else None,
        "stalled_groups": len(stalled),
        "loss_first": groups[0]["loss"], "loss_last": groups[-1]["loss"],
        "tok_per_sec_median": round(lbs * seq * 1e3 / med, 1),
    }
    print("SUMMARY " + json.dumps(summary), flush=True)
    print("GROUPS " + json.dumps(groups), flush=True)


if __name__ == "__main__":
    main()
