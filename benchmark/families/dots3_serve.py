"""Family ``dots3_serve``: a decoder of ``horovod_tpu.models.latent_moe``'s
architecture (latent attention with a sparse indexer, windowed latent layers,
a dropless expert layer that holds a share of the experts) served by
``ServeEngine`` behind ``RouterServer([LocalReplica])`` in this process, as
``llama_serve`` serves Mistral: the three-pool paged cache, chunked prefill,
prefix cache on, greedy decoding.

The weights are the benchmark's own, made on the device from the seed by the
configuration's reference (``reference/<reference>.py``), layer by layer in
the tree the program takes; from the program come the model code, the engine,
the replica's pump and the router.  Each engine step is stamped as in
``llama_serve`` and carries the model's counters after it.

The byte and operation counts of the tick and the chunk programs are here
(``weight_bytes``, ``kv_bytes_per_token``, ``tick_bytes``, ``chunk_flops``):
what the algorithm needs, from the configuration's shapes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import capture, lib

_llama = lib.load_module("families", "llama_serve")
pick_sample, gaps, CONTROL = _llama.pick_sample, _llama.gaps, _llama.CONTROL

#: Limits of the comparison, from chip readings at the cell's own size
#: (PERF.md, section 2): ``gap_max`` read at most 0.295 in 11 sound runs and at
#: least 7.36 under the control (fp8 in the program's place, 3 seeds);
#: ``gap_mean`` at most 1.4e-3 and at least 4.1.  ``gap_max`` is also what one
#: served token altered where it is produced fails (a random token lies about 4
#: below the best).
LIMITS = {"gap_max": 0.5, "gap_mean": 0.02}

SPANS = ("engine.step", "route")

#: a step's stamp is ``llama_serve``'s five fields, the experts its tick
#: touched, these counters, and the load of each held expert
STAMPED = ("moe.choices_total", "moe.choices_held", "dsa.keys_visible",
           "dsa.keys_selected")


def _reference(cfg: dict):
    return lib.load_module("reference", cfg["reference"])


def model_config(cfg: dict, max_len: int):
    try:
        from horovod_tpu.models import latent_moe
    except ImportError as e:
        raise SystemExit(f"benchmark: this program cannot run the "
                         f"configuration {cfg['name']!r}: {e}")
    n = int(cfg["num_hidden_layers"])
    kinds = tuple("full" if k == "full_attention" else "window"
                  for k in cfg["layer_types"][:n])
    dt = jnp.dtype(cfg["torch_dtype"])
    return latent_moe.LatentMoEConfig(
        vocab_size=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        layer_kinds=kinds, first_dense=int(cfg["first_k_dense_replace"]),
        ffn_dim=int(cfg["intermediate_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope_dim=int(cfg["qk_nope_head_dim"]),
        rope_dim=int(cfg["qk_rope_head_dim"]), v_dim=int(cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        index_heads=int(cfg["index_n_heads"]),
        index_dim=int(cfg["index_head_dim"]),
        index_topk=int(cfg["index_topk"]),
        w_heads=int(cfg["swa_num_attention_heads"]),
        w_q_rank=int(cfg["swa_q_lora_rank"]),
        w_kv_rank=int(cfg["swa_kv_lora_rank"]),
        w_nope_dim=int(cfg["swa_qk_nope_head_dim"]),
        w_rope_dim=int(cfg["swa_qk_rope_head_dim"]),
        w_v_dim=int(cfg["swa_v_head_dim"]),
        w_rope_theta=float(cfg["swa_rope_theta"]),
        window=int(cfg["sliding_window_size"]),
        n_experts=int(cfg.get("n_routed_experts_published",
                              cfg["n_routed_experts"])),
        expert_dim=int(cfg["moe_intermediate_size"]),
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared=int(cfg["n_shared_experts"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        held_first=int(cfg.get("held_experts_first", 0)),
        held_count=int(cfg["n_routed_experts"]),
        vocab_first=int(cfg.get("vocab_first_row", 0)),
        lora_rescale=bool(cfg["apply_mla_qkv_lora_rescale"]),
        norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=max_len,
        dtype=dt, param_dtype=dt)


def make_params(cfg: dict, seed: int) -> dict:
    """The program's parameter tree, every layer the reference's own."""
    ref = _reference(cfg)
    top = jax.jit(lambda s: ref.top_weights(cfg, s))(ref.seed_arg(seed))
    layers = tuple(ref.layer_weights(cfg, ref.seed_arg(seed), i)
                   for i in range(int(cfg["num_hidden_layers"])))
    return {"embed": top["embed"], "layers": layers,
            "final_norm": top["final_norm"], "lm_head": top["lm_head"]}


class Served(_llama.Served):
    """``llama_serve.Served`` over this family's model: the same router,
    replica, warm-up and stamps, the stamps with the model's counters."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from horovod_tpu import metrics as metrics_mod
        from horovod_tpu.router import LocalReplica, RouterServer
        from horovod_tpu.serving import Request
        from horovod_tpu.serving_scheduler import ServeEngine

        e = mix["engine"]
        self.request_cls = Request
        self.vocab = int(cfg["vocab_size"])
        self.chunk = int(e["chunk"])
        model_cfg = model_config(cfg, int(e["max_len"]))
        params = make_params(cfg, seed)
        self.engine = eng = ServeEngine(
            params, model_cfg, n_slots=int(e["n_slots"]),
            max_len=int(e["max_len"]), chunk=self.chunk,
            prefix_cache=bool(e["prefix_cache"]), n_blocks=e.get("n_blocks"),
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
        touched = eng.metrics.gauge("moe.experts_touched")
        counters = [eng.metrics.counter(name) for name in STAMPED]
        first, count = eng.cfg.held_first, eng.cfg.held_count
        load = [eng.metrics.gauge(f"moe.held_load.{first + e}")
                for e in range(count)]

        def step():
            t0 = time.monotonic()
            with capture.span("engine.step"):
                out = inner()
            steps.append((t0, time.monotonic(), decoding.value,
                          prefilling.value, len(out), touched.value,
                          *(c.value for c in counters),
                          *(g.value for g in load)))
            return out

        eng.step = step

    def close(self) -> None:
        ticking = [s for s in self.steps if s[2] > 0]
        if ticking:
            print(f"[bench] engine steps: {len(self.steps)}, {len(ticking)} "
                  f"with a tick, the first "
                  f"{ticking[0][1] - self.steps[0][0]:.2f} s after the first "
                  f"step began", flush=True)
        super().close()


def build(ctx) -> Served:
    return Served(ctx.config, ctx.mix, ctx.seed)


def compare(g: np.ndarray) -> list:
    values = {"gap_max": float(np.max(g)), "gap_mean": float(np.mean(g))}
    return [{"name": k, "value": v, "limit": LIMITS[k],
             "ok": bool(np.isfinite(v) and v <= LIMITS[k])}
            for k, v in values.items()]


def check(ctx, finished: list) -> list:
    """After the window, with the engine freed: a sample of the requests it
    finished, the longest among them, through the reference once over prompt
    plus served tokens."""
    sample = pick_sample(finished, int(ctx.mix["check"]["sample"]), ctx.seed)
    if not sample:
        return [{"name": "served_tokens", "value": 0, "limit": 1,
                 "ok": False}]
    ctx.sample = sample
    g = gaps(ctx.config, ctx.mix, ctx.seed, sample)["served"]
    ctx.say(f"check: {len(sample)} requests, {g.size} served tokens, "
            f"{int((g > 0).sum())} not the reference's first choice, "
            f"gap quantiles 0.5/0.9/0.99 "
            f"{[round(float(np.quantile(g, q)), 4) for q in (.5, .9, .99)]}")
    return compare(g)


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


# ---------------------------------------------------------------------------
# bytes and operations, from the configuration's shapes
# ---------------------------------------------------------------------------

def _sizes(cfg: dict) -> dict:
    n = int(cfg["num_hidden_layers"])
    kinds = cfg["layer_types"][:n]
    full = sum(1 for k in kinds if k == "full_attention")
    d = int(cfg["hidden_size"])

    def attn(prefix, heads):
        h = int(cfg[heads])
        qr = int(cfg[prefix + "q_lora_rank"])
        kr = int(cfg[prefix + "kv_lora_rank"])
        nope = int(cfg[prefix + "qk_nope_head_dim"])
        rope = int(cfg[prefix + "qk_rope_head_dim"])
        v = int(cfg[prefix + "v_head_dim"])
        return {"h": h, "kr": kr, "rope": rope, "lat": kr + rope,
                # q_a, q_b, kv_a, kv_b, o, gate (norms left out)
                # (the absorbed form's q_nope W_kvb_k and o_lat W_kvb_v
                # are kv_b's products, counted once here)
                "params": (d * qr + qr * h * (nope + rope) + d * (kr + rope)
                           + kr * h * (nope + v) + h * v * d + d * h)}

    ih, idim = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    ef = int(cfg["moe_intermediate_size"])
    return {
        "d": d, "n": n, "full": full, "window_layers": n - full,
        "dense": int(cfg["first_k_dense_replace"]),
        "a_full": attn("", "num_attention_heads"),
        "a_swa": attn("swa_", "swa_num_attention_heads"),
        "indexer_params": int(cfg["q_lora_rank"]) * ih * idim + d * idim
        + d * ih,
        "ih": ih, "idim": idim, "topk": int(cfg["index_topk"]),
        "window": int(cfg["sliding_window_size"]),
        "ffn_params": 3 * d * int(cfg["intermediate_size"]),
        "expert_params": 3 * d * ef,
        "shared_params": 3 * d * ef * int(cfg["n_shared_experts"]),
        "router_params": d * int(cfg.get("n_routed_experts_published",
                                         cfg["n_routed_experts"])),
        "k": int(cfg["num_experts_per_tok"]),
        "v": int(cfg["vocab_size"]),
        "item": jnp.dtype(cfg["torch_dtype"]).itemsize}


def dense_params(cfg: dict) -> int:
    """Parameters every token multiplies: attention and indexer projections,
    the dense SwiGLU, routers and shared experts, the head (the embedding is
    looked up row by row, the routed experts are counted by the choices)."""
    s = _sizes(cfg)
    n_moe = s["n"] - s["dense"]
    return (s["full"] * (s["a_full"]["params"] + s["indexer_params"])
            + s["window_layers"] * s["a_swa"]["params"]
            + s["dense"] * s["ffn_params"]
            + n_moe * (s["router_params"] + s["shared_params"])
            + s["d"] * s["v"])


def weight_bytes(cfg: dict) -> int:
    """Bytes of weights a decode tick has to read whatever its rows chose."""
    return dense_params(cfg) * _sizes(cfg)["item"]


def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    s = _sizes(cfg)
    return s["expert_params"] * s["item"]


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes one cached position holds in the three pools, all layers."""
    s = _sizes(cfg)
    return s["item"] * (s["full"] * (s["a_full"]["lat"] + s["idim"])
                        + s["window_layers"] * s["a_swa"]["lat"])


def tick_bytes(cfg: dict, rows: float, live_tokens: float,
               experts_touched: float) -> float:
    """The least one decode tick has to move: the weights outside the routed
    experts once, the experts its rows touched, and per decoding row and full
    layer the index keys of its whole context (the indexer scores every
    cached key) plus the latents it selected, per window layer the latents of
    the window.  ``live_tokens`` is the sum of the rows' contexts."""
    s = _sizes(cfg)
    mean_ctx = live_tokens / max(rows, 1e-9)
    selected = min(mean_ctx, s["topk"])
    windowed = min(mean_ctx, s["window"])
    per_row = s["item"] * (
        s["full"] * (mean_ctx * s["idim"] + selected * s["a_full"]["lat"])
        + s["window_layers"] * windowed * s["a_swa"]["lat"])
    return (weight_bytes(cfg) + experts_touched * expert_bytes(cfg)
            + rows * per_row)


def chunk_flops(cfg: dict, tokens: float, keys_visible: float,
                keys_selected: float, choices_held: float) -> float:
    """Operations of prefill over ``tokens`` positions: the products with the
    weights every token sees, the routed experts for the choices that were
    held, the indexer over the keys it scored and attention over the latents
    it selected (both summed over queries and full layers, as the program
    counts them), and window attention over at most ``window`` latents."""
    s = _sizes(cfg)
    af, aw = s["a_full"], s["a_swa"]
    products = 2.0 * tokens * dense_params(cfg)
    routed = 2.0 * choices_held * s["expert_params"]
    indexer = 2.0 * keys_visible * s["ih"] * s["idim"]
    attend = 2.0 * keys_selected * af["h"] * (af["lat"] + af["kr"])
    window = (2.0 * tokens * s["window_layers"] * s["window"] * aw["h"]
              * (aw["lat"] + aw["kr"]))
    return products + routed + indexer + attend + window
