"""Family ``llama_serve``: a decoder of ``horovod_tpu.models.llama``'s
architecture served by ``ServeEngine`` behind ``RouterServer([LocalReplica])``
in this process, as the program's users run it: paged KV pool, chunked
prefill, prefix cache on, greedy decoding.

The weights are the benchmark's own, made on the device from the seed by the
configuration's reference (``reference/<reference>.py``, stacked layer by
layer into the tree the program takes); from the program come the model
code, the engine, the replica's pump and the router.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import capture, lib

#: Limits of the comparison, from chip readings at the cells' own sizes
#: (PERF.md, section 2): ``gap_max`` read at most 0.065 (chat, 12 seeds) and
#: 0.076 (longdoc, 9 seeds) in sound runs and at least 6.7 under the control;
#: ``gap_mean`` at most 8.7e-4 / 1.6e-3 and at least 4.1.  ``gap_max`` is also
#: what a token altered where it is produced fails (a random token lies about
#: 4 below the best).
LIMITS = {"gap_max": 0.5, "gap_mean": 0.02}

SPANS = ("engine.step", "route")


def _reference(cfg: dict):
    return lib.load_module("reference", cfg["reference"])


def make_params(cfg: dict, seed: int) -> dict:
    """The program's parameter tree, every layer the reference's own."""
    ref = _reference(cfg)

    def build(seed):
        top = ref.top_weights(cfg, seed)
        layers = jax.lax.map(lambda i: ref.layer_weights(cfg, seed, i),
                             jnp.arange(int(cfg["num_hidden_layers"])))
        return {"embed": top["embed"], "layers": layers,
                "final_norm": top["final_norm"], "lm_head": top["lm_head"]}

    return jax.jit(build)(ref.seed_arg(seed))


def model_config(cfg: dict, max_len: int):
    from horovod_tpu.models import llama

    dt = jnp.dtype(cfg["torch_dtype"])
    if int(cfg["head_dim"]) * int(cfg["num_attention_heads"]) \
            != int(cfg["hidden_size"]):
        raise SystemExit("benchmark: LlamaConfig derives head_dim from "
                         "hidden_size / heads; the file disagrees")
    return llama.LlamaConfig(
        vocab_size=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        ffn_dim=int(cfg["intermediate_size"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=max_len,
        dtype=dt, param_dtype=dt, attn_impl="dense", remat=False)


class Served:
    """The engine behind its router, with the benchmark's stamps on
    ``ServeEngine.step`` (set on the instance before the replica's pump
    starts): when each step began and returned, and what the engine's own
    gauges read after it."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from horovod_tpu import metrics as metrics_mod
        from horovod_tpu.router import LocalReplica, RouterServer
        from horovod_tpu.serving import Request
        from horovod_tpu.serving_scheduler import ServeEngine

        e = mix["engine"]
        self.request_cls = Request
        self.vocab = int(cfg["vocab_size"])
        self.chunk = int(e["chunk"])
        params = make_params(cfg, seed)
        self.engine = eng = ServeEngine(
            params, model_config(cfg, int(e["max_len"])),
            n_slots=int(e["n_slots"]), max_len=int(e["max_len"]),
            chunk=self.chunk, prefix_cache=bool(e["prefix_cache"]),
            n_blocks=e.get("n_blocks"),
            metrics=metrics_mod.MetricsRegistry(event_log=None))
        del params
        # Every program the traffic uses: a prompt longer than one chunk
        # (two prefill windows), a table write, a few decode ticks.
        rng = np.random.default_rng([seed, 7])
        warm = eng.run([Request(
            prompt=rng.integers(1, self.vocab, self.chunk + 3).tolist(),
            max_new_tokens=3)])
        if warm[0].status != "OK" or len(warm[0]) != 3:
            raise SystemExit(f"benchmark: warm-up request failed: {warm[0]}")
        self.steps: list = []
        self._wrap_step()
        self.replica = LocalReplica(eng, "r0")
        self.router = RouterServer([self.replica])

    def _wrap_step(self) -> None:
        eng, steps = self.engine, self.steps
        inner = eng.step
        decoding = eng.metrics.gauge("serve.decoding")
        prefilling = eng.metrics.gauge("serve.prefilling")

        def step():
            t0 = time.monotonic()
            with capture.span("engine.step"):
                out = inner()
            steps.append((t0, time.monotonic(), decoding.value,
                          prefilling.value, len(out)))
            return out

        eng.step = step

    def route(self, prompt: list, n_out: int) -> int:
        with capture.span("route"):
            return self.router.route(self.request_cls(
                prompt=prompt, max_new_tokens=n_out))

    def collect(self, rid: int, timeout: float):
        """The terminal result and the merged trace, or ``(None, None)``."""
        res = self.router.result(rid, timeout=timeout)
        if res is None:
            return None, None
        return res, self.router.request_trace(rid)

    def compile_counts(self) -> dict:
        return self.engine.compile_cache_sizes()

    def close(self) -> None:
        """Stop the router and the pump, and free the engine's arrays."""
        self.router.stop(drain_s=0.0)
        eng = self.engine
        eng.params = eng.pcache = eng.last_logits = None
        self.engine = self.router = self.replica = None
        for t in threading.enumerate():
            if t.name.startswith("hvd-replica-") and t.is_alive():
                raise SystemExit(f"benchmark: {t.name} did not stop")


def build(ctx) -> Served:
    return Served(ctx.config, ctx.mix, ctx.seed)


def pick_sample(finished: list, k: int, seed: int) -> list:
    """``k`` of the finished requests ``(prompt, tokens)``, drawn from the
    seed, the longest always among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rest = order[1:]
    rng = np.random.default_rng([seed, 11])
    pick = [order[0]] + [rest[i] for i in rng.permutation(len(rest))[:k - 1]]
    return [finished[i] for i in pick]


def gaps(cfg: dict, mix: dict, seed: int, sample: list,
         precision: str = "float32") -> dict:
    """The reference over each sampled prompt with its served tokens:
    ``served`` is, per served token, how far its logit lies below the
    reference's best; with a lower ``precision`` (the control), ``control``
    is the same for the token that precision puts first at each position."""
    ref = _reference(cfg)
    seqs = [list(p) + list(t) for p, t in sample]
    pos = [list(range(len(p) - 1, len(p) + len(t) - 1)) for p, t in sample]
    pad = int(mix["check"]["pad_to"])
    rows = ref.logits_at(cfg, seed, seqs, pos, "float32", pad)
    out = {"served": np.concatenate([
        np.asarray(ref.served_gaps(r, t)) for r, (_, t) in zip(rows, sample)])}
    if precision != "float32":
        low = ref.logits_at(cfg, seed, seqs, pos, precision, pad)
        out["control"] = np.concatenate([
            np.asarray(ref.served_gaps(r, jnp.argmax(lo, axis=-1)))
            for r, lo in zip(rows, low)])
    return out


def compare(g: np.ndarray) -> list:
    values = {"gap_max": float(np.max(g)), "gap_mean": float(np.mean(g))}
    return [{"name": k, "value": v, "limit": LIMITS[k],
             "ok": bool(np.isfinite(v) and v <= LIMITS[k])}
            for k, v in values.items()]


def check(ctx, finished: list) -> list:
    """After the window, with the engine freed: a sample of the requests it
    finished, the longest among them, against the reference."""
    sample = pick_sample(finished, int(ctx.mix["check"]["sample"]), ctx.seed)
    if not sample:
        return [{"name": "served_tokens", "value": 0, "limit": 1,
                 "ok": False}]
    ctx.sample = sample
    g = gaps(ctx.config, ctx.mix, ctx.seed, sample)["served"]
    ctx.say(f"check: {len(sample)} requests, {g.size} served tokens, "
            f"{int((g > 0).sum())} not the reference's first choice")
    return compare(g)


def weight_bytes(cfg: dict) -> int:
    """Bytes of weights one decode tick has to read: every layer's matrices
    and norms, the final norm and the head (the embedding is looked up row
    by row and left out)."""
    d, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    kd = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
    per_layer = 2 * d * d + 2 * d * kd + 3 * d * f + 2 * d
    n = (int(cfg["num_hidden_layers"]) * per_layer + d
         + d * int(cfg["vocab_size"]))
    return n * jnp.dtype(cfg["torch_dtype"]).itemsize


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of keys and values one cached position holds, all layers."""
    return (2 * int(cfg["num_hidden_layers"])
            * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
            * jnp.dtype(cfg["torch_dtype"]).itemsize)


def tick_bytes(cfg: dict, live_tokens: float) -> float:
    """The least one decode tick has to move: the weights once and the keys
    and values of every position the decoding rows attend to."""
    return weight_bytes(cfg) + live_tokens * kv_bytes_per_token(cfg)


CONTROL = "fp8"         # the nearest precision below bfloat16


def probe(ctx, control: bool) -> dict:
    """For ``limits_probe.py``: a short window of the cell's own traffic, the
    served tokens against the reference and, if asked, the control's."""
    rec = lib.load_module("drivers", ctx.mix["driver"]).run(ctx)
    out = {"sound": {c["name"]: c["value"] for c in rec["checks"]
                     if c["name"] in LIMITS},
           "attempted": rec["attempted"], "failed": rec["failed"]}
    if control:
        g = gaps(ctx.config, ctx.mix, ctx.seed, ctx.sample, CONTROL)
        out["control"] = {c["name"]: c["value"]
                          for c in compare(g["control"])}
        out["tokens"] = int(g["control"].size)
    return out
