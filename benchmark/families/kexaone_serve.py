"""Family ``kexaone_serve``: a decoder of ``horovod_tpu.models.window_moe``'s
architecture (window-128 grouped-query layers beside full ones, sigmoid-routed
experts and a shared one, an untied head) served by ``ServeEngine`` behind
``RouterServer([LocalReplica])`` in this process, as ``llama_serve`` serves
Mistral: the full layers' paged pools, the sliding layers' ring a slot and its
snapshot a block, chunked prefill, prefix cache on, greedy decoding.  The
configuration is one chip's share (``dots3_serve``'s cut): the experts from
``held_experts_first`` on and the vocabulary's rows from ``vocab_first_row``.

The weights are the benchmark's own, made on the device from the seed by the
configuration's reference (``reference/<reference>.py``), layer by layer in
the tree the program takes; from the program come the model code, the engine,
the replica's pump and the router.  Each engine step is stamped as in
``dots3_serve``: ``llama_serve``'s five fields, the experts its tick touched,
four of the model's counters, the load of each held expert, and last what the
live rows hold (``kv.tokens_live``, ``kv.full_bytes_live``,
``kv.window_bytes_live``).  The family also keeps, per request, the prompt
tokens the prefix cache spared it, so that the check can take a request whose
ring came from a snapshot, and after the window sends one **restore probe** a
system prompt (below).

**Restore probes.**  A ring restored wrongly is read by the sliding layers of
the ``sliding_window - 1`` positions past the hit's frontier, and after that
only through what the full layers read of those positions; the traffic's own
parts are 256 tokens and more, so a served token of the batch sees it faintly.
When the batch has drained and before the engine is freed, each system prompt
the batch used is therefore asked once more, through the same router, with an
own part of 2 tokens and 8 tokens to serve (``lfm2_serve``'s probes): admitted
on a hit, its ring restored from the prompt's last block, its served tokens
within the window of that ring.  Their mean gap against the reference is the
third number of the comparison, ``restore_gap_mean``.

The byte and operation counts of the tick and the chunk programs are here
(``weight_bytes``, ``dense_bytes``, ``expert_bytes``, ``kv_bytes_per_token``,
``ring_bytes_per_position``, ``tick_bytes``, ``chunk_flops``): what the
algorithm needs, from the configuration's shapes.

A program without ``horovod_tpu.models.window_moe`` cannot run this family and
ends at once, before anything is built.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import capture, lib

try:
    from horovod_tpu.models import window_moe
except ImportError as e:
    raise SystemExit(f"benchmark: this program cannot run the family "
                     f"'kexaone_serve': {e}")

_llama = lib.load_module("families", "llama_serve")
_lfm2 = lib.load_module("families", "lfm2_serve")
gaps, CONTROL = _llama.gaps, _llama.CONTROL
#: as ``lfm2_serve`` takes it: the longest always, and one that the prefix
#: cache spared a part of its prompt (its ring came from a snapshot) where
#: any did
pick_sample = _lfm2.pick_sample

#: Limits of the comparison, from chip readings at the cell's own size (my
#: chip runs, PR 33; PERF.md, section 2).  The readings are the dense
#: families' and not lfm2's: only an eighth of a token's 56 expert choices
#: falls on an expert held here, so a near-tie that bfloat16 flips seldom
#: changes what this chip computes (3-5 % of the served tokens are not the
#: reference's first choice, none further than 0.60 below it).
#: ``gap_max``: at most 0.599 in 15 sound runs, at least 7.26 under the
#: control (fp8 in the program's place, 2 seeds); 1.5 is over twice the one
#: and a fifth of the other, and under the 4 or so that a token altered
#: where it is produced lies below the best.  ``gap_mean``: at most 0.0034
#: sound, at least 4.19 under the control; 0.03 is nine times the one and a
#: hundredth of the other.  ``restore_gap_mean`` (the probes' 32 tokens): at
#: most 0.0111 sound, 2.80 with every restored ring zeroed (which ``gap_max``
#: 0.41 and ``gap_mean`` 0.0020 of the same run do not see); 0.1 is nine
#: times the one and under a twentieth of the other, and more than one
#: probe token in 32 a whole 1.5 off.
LIMITS = {"gap_max": 1.5, "gap_mean": 0.03, "restore_gap_mean": 0.1}
PROBE_PAD = 2048           # the reference's padding for a probe

SPANS = ("engine.step", "route")

#: a step's stamp is ``llama_serve``'s five fields, the experts its tick
#: touched, these counters, the load of each held expert (``dots3_serve``'s
#: layout so far, so ``dots3_stats``'s readers of a stamp by position read
#: this family's too), and last these gauges
STAMPED = ("moe.choices_total", "moe.choices_held", "window.state_restores",
           "attn.keys_visible")
LIVE = ("kv.tokens_live", "kv.full_bytes_live", "kv.window_bytes_live")


def _reference(cfg: dict):
    return lib.load_module("reference", cfg["reference"])


def model_config(cfg: dict, max_len: int):
    if cfg["tie_word_embeddings"]:
        raise SystemExit("benchmark: window_moe's head is its own matrix; "
                         "the file says it is tied to the embedding")
    n = int(cfg["num_hidden_layers"])
    kinds = tuple(window_moe.FULL if k == "full_attention"
                  else window_moe.SLIDING for k in cfg["layer_types"][:n])
    dt = jnp.dtype(cfg["torch_dtype"])
    held = int(cfg["num_experts"])
    return window_moe.WindowMoEConfig(
        vocab_size=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        layer_kinds=kinds, first_dense=int(cfg["first_k_dense_replace"]),
        ffn_dim=int(cfg["intermediate_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        window=int(cfg["sliding_window"]),
        n_experts=int(cfg.get("num_experts_published", held)),
        expert_dim=int(cfg["moe_intermediate_size"]),
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared=int(cfg["num_shared_experts"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        held_first=int(cfg.get("held_experts_first", 0)), held_count=held,
        norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=max_len,
        dtype=dt, param_dtype=dt)


def make_params(cfg: dict, seed: int) -> dict:
    """The program's parameter tree, every layer the reference's own."""
    ref = _reference(cfg)
    top = jax.jit(lambda s: ref.top_weights(cfg, s))(ref.seed_arg(seed))
    makers = {}
    layers = []
    for i in range(int(cfg["num_hidden_layers"])):
        kind = ref.layer_kind(cfg, i)[1]    # the shapes go by the FFN's kind
        if kind not in makers:
            makers[kind] = jax.jit(lambda s, i, k=ref.layer_kind(cfg, i):
                                   ref._layer_weights(cfg, k, s, i))
        layers.append(makers[kind](ref.seed_arg(seed), jnp.int32(i)))
    return {"embed": top["embed"], "layers": tuple(layers),
            "final_norm": top["final_norm"], "lm_head": top["lm_head"]}


class Served(_lfm2.Served):
    """``lfm2_serve.Served`` over this family's model: the same router,
    replica, warm-up, per-prompt count of the tokens the prefix cache spared
    and restore probes (one a system prompt, ``lfm2_serve``'s ``PROBE_OWN``
    own tokens and ``PROBE_OUT`` to serve); the engine and the stamps are
    this family's."""

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
        self._prompt_of: dict = {}
        self.skipped: dict = {}         # prompt -> tokens the cache spared
        self.probes: list = []          # (prompt, tokens) of the restore probes
        self._template = int(mix["shapes"]["system_prompts"]["tokens"])
        self._rng = np.random.default_rng([seed, 13])
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
        first = eng.cfg.held_first
        load = [eng.metrics.gauge(f"moe.held_load.{first + e}")
                for e in range(eng.cfg.held_count)]
        live = [eng.metrics.gauge(name) for name in LIVE]

        def step():
            t0 = time.monotonic()
            with capture.span("engine.step"):
                out = inner()
            steps.append((t0, time.monotonic(), decoding.value,
                          prefilling.value, len(out), touched.value,
                          *(c.value for c in counters),
                          *(g.value for g in load),
                          *(g.value for g in live)))
            return out

        eng.step = step


def build(ctx) -> Served:
    served = Served(ctx.config, ctx.mix, ctx.seed)
    ctx.prefix_skipped = served.skipped     # filled as results are collected
    ctx.restore_probes = served.probes      # filled when the run closes
    return served


def _line(name: str, value: float) -> dict:
    return {"name": name, "value": value, "limit": LIMITS[name],
            "ok": bool(np.isfinite(value) and value <= LIMITS[name])}


def compare(g: np.ndarray) -> list:
    return [_line("gap_max", float(np.max(g))),
            _line("gap_mean", float(np.mean(g)))]


def check(ctx, finished: list) -> list:
    """After the window, with the engine freed: a sample of the requests it
    finished, the longest and one admitted on a prefix hit among them,
    through the reference once over prompt plus served tokens; and the
    restore probes the same way (no probe that hit reads as not correct)."""
    skipped = getattr(ctx, "prefix_skipped", {})
    sample = pick_sample(finished, int(ctx.mix["check"]["sample"]), ctx.seed,
                         skipped)
    if not sample:
        return [{"name": "served_tokens", "value": 0, "limit": 1,
                 "ok": False}]
    ctx.sample = sample
    n_hit = sum(1 for p, _ in sample if skipped.get(tuple(p), 0) > 0)
    g = gaps(ctx.config, ctx.mix, ctx.seed, sample)["served"]
    ctx.say(f"check: {len(sample)} requests ({n_hit} admitted on a prefix "
            f"hit), {g.size} served tokens, {int((g > 0).sum())} not the "
            f"reference's first choice, gap quantiles 0.5/0.9/0.99 "
            f"{[round(float(np.quantile(g, q)), 4) for q in (.5, .9, .99)]}")
    probes = getattr(ctx, "restore_probes", [])
    restore = float("inf")
    if probes:
        short = dict(ctx.mix, check=dict(ctx.mix["check"], pad_to=min(
            PROBE_PAD, int(ctx.mix["check"]["pad_to"]))))
        g_r = gaps(ctx.config, short, ctx.seed, probes)["served"]
        restore = float(np.mean(g_r))
        ctx.say(f"check: {len(probes)} restore probes, {g_r.size} served "
                f"tokens, largest gap {float(np.max(g_r)):.4f}")
    return compare(g) + [_line("restore_gap_mean", restore)]


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
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd, kvh = int(cfg["head_dim"]), int(cfg["num_key_value_heads"])
    held = int(cfg["num_experts"])
    return {
        "d": d, "n": n, "full": full, "sliding": n - full,
        "dense": int(cfg["first_k_dense_replace"]),
        "h": h, "hd": hd, "kvh": kvh, "window": int(cfg["sliding_window"]),
        # q, k, v, o (norms apart)
        "attn_params": 2 * d * h * hd + 2 * d * kvh * hd,
        "attn_norms": 2 * hd,
        "ffn_params": 3 * d * int(cfg["intermediate_size"]),
        "expert_params": 3 * d * int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["num_shared_experts"]),
        "e": held, "e_all": int(cfg.get("num_experts_published", held)),
        "k": int(cfg["num_experts_per_tok"]), "v": int(cfg["vocab_size"]),
        "item": jnp.dtype(cfg["torch_dtype"]).itemsize}


def dense_params(cfg: dict) -> int:
    """Parameters a tick reads whatever its rows chose, the router's float32
    bias apart: attention, norms, the dense SwiGLU, the shared experts, the
    routers, the final norm and the head (the embedding is looked up row by
    row and left out)."""
    s = _sizes(cfg)
    n_moe = s["n"] - s["dense"]
    return (s["n"] * (s["attn_params"] + s["attn_norms"] + 2 * s["d"])
            + s["dense"] * s["ffn_params"]
            + n_moe * (s["shared"] * s["expert_params"] + s["d"] * s["e_all"])
            + s["d"] + s["d"] * s["v"])


def dense_bytes(cfg: dict) -> int:
    """Bytes of weights a decode tick has to read whatever its rows chose."""
    s = _sizes(cfg)
    return (dense_params(cfg) * s["item"]
            + (s["n"] - s["dense"]) * s["e_all"] * 4)   # the biases: float32


def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    s = _sizes(cfg)
    return s["expert_params"] * s["item"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the whole parameter tree: what a tick reads when its rows
    touch every held expert of every layer, and the embedding."""
    s = _sizes(cfg)
    return (dense_bytes(cfg) + s["d"] * s["v"] * s["item"]
            + (s["n"] - s["dense"]) * s["e"] * expert_bytes(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of keys and values one cached position holds in the pools: the
    full layers only."""
    s = _sizes(cfg)
    return 2 * s["full"] * s["kvh"] * s["hd"] * s["item"]


def ring_bytes_per_position(cfg: dict) -> int:
    """Bytes of keys and values one position of a ring holds, all sliding
    layers."""
    s = _sizes(cfg)
    return 2 * s["sliding"] * s["kvh"] * s["hd"] * s["item"]


def tick_bytes(cfg: dict, rows: float, live_tokens: float,
               experts_touched: float) -> float:
    """The least one decode tick has to move: the weights outside the experts
    once, the experts its rows touched (counted per layer), the full layers'
    keys and values of every position the decoding rows attend to, and of
    each row's ring the positions within the window (all of them once the
    row is ``sliding_window`` long)."""
    s = _sizes(cfg)
    in_window = min(live_tokens, rows * s["window"])
    return (dense_bytes(cfg) + experts_touched * expert_bytes(cfg)
            + live_tokens * kv_bytes_per_token(cfg)
            + in_window * ring_bytes_per_position(cfg))


def chunk_flops(cfg: dict, tokens: float, keys_visible: float,
                choices_held: float) -> float:
    """Operations of prefill over ``tokens`` positions: the products with the
    attention's, the dense SwiGLU's, the shared experts' and the routers'
    weights, the routed experts for the choices that fell on a held one, and
    attention over the keys each query sees (``keys_visible``: summed over
    queries and layers as the program counts them, ``sliding_window`` at most
    on a sliding layer).  The head is left out: a request needs it at one
    position."""
    s = _sizes(cfg)
    n_moe = s["n"] - s["dense"]
    per_token = (s["n"] * s["attn_params"] + s["dense"] * s["ffn_params"]
                 + n_moe * (s["shared"] * s["expert_params"]
                            + s["d"] * s["e_all"]))
    return (2.0 * tokens * per_token + 2.0 * choices_held * s["expert_params"]
            + 4.0 * keys_visible * s["h"] * s["hd"])
