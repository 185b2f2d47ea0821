"""Family ``lfm2_serve``: a decoder of ``horovod_tpu.models.shortconv_moe``'s
architecture (gated short-convolution layers beside grouped-query attention,
sigmoid-routed experts, a head tied to the embedding) served by ``ServeEngine``
behind ``RouterServer([LocalReplica])`` in this process, as ``llama_serve``
serves Mistral: the paged cache with its per-slot convolution state and
per-block snapshots, chunked prefill, prefix cache on, greedy decoding.

The weights are the benchmark's own, made on the device from the seed by the
configuration's reference (``reference/<reference>.py``), layer by layer in
the tree the program takes; from the program come the model code, the engine,
the replica's pump and the router.  Each engine step is stamped as in
``dots3_serve``: ``llama_serve``'s five fields, the experts its tick touched,
four of the model's counters, and the load of each expert.  The family also
keeps, per request, the prompt tokens the prefix cache spared it, so that the
check can take a request whose convolution state came from a snapshot, and
after the window sends one **restore probe** a template (below).

**Restore probes.**  A convolution state restored wrongly reaches no further
than ``conv_L_cache - 1`` positions a convolution layer past the hit's
frontier (22 positions for 11 layers of 3 taps) and after that only through
what the attention layers read of those positions; the traffic's own parts
are 32 tokens and more, so no served token of the batch can show it.  When
the batch has drained and before the engine is freed, each template the batch
used is therefore asked once more, through the same router, with an own part
of ``PROBE_OWN`` tokens and ``PROBE_OUT`` tokens to serve: admitted on a hit,
its state restored from the template's last block, its served tokens within
the reach of that state.  Their mean gap against the reference is the third
number of the comparison, ``restore_gap_mean``.

The byte and operation counts of the tick and the chunk programs are here
(``weight_bytes``, ``dense_bytes``, ``expert_bytes``, ``kv_bytes_per_token``,
``state_bytes_per_row``, ``tick_bytes``, ``chunk_flops``): what the algorithm
needs, from the configuration's shapes.

A program without ``horovod_tpu.models.shortconv_moe`` cannot run this family
and ends at once, before anything is built.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import capture, lib

try:
    from horovod_tpu.models import shortconv_moe
except ImportError as e:
    raise SystemExit(f"benchmark: this program cannot run the family "
                     f"'lfm2_serve': {e}")

_llama = lib.load_module("families", "llama_serve")
gaps, CONTROL = _llama.gaps, _llama.CONTROL

#: Limits of the comparison, from chip readings at the cell's own size (my
#: chip runs, PR 31; PERF.md, section 2).  The readings are wider than the
#: dense families' because a token's 48 expert choices are discrete: bfloat16
#: flips a near-tie in about one layer in eight, and an expert exchanged for
#: another moves the logits by a tenth or two (a third of the served tokens
#: are not the reference's first choice, none further than 1.8 below it).
#: ``gap_max``: at most 1.79 in 15 sound runs, at least 7.78 under the control
#: (fp8 in the program's place, 2 seeds); 3.5 is twice the one and under half
#: the other, and under the 4 or so that a token altered where it is produced
#: lies below the best.  ``gap_mean``: at most 0.088 sound, at least 4.41
#: under the control; 0.4 is over four times the one and a tenth of the other.
#: ``restore_gap_mean`` (the probes' 64 tokens): at most 0.122 sound, 2.23
#: with every snapshot zeroed (which ``gap_max`` 1.23 and ``gap_mean`` 0.089
#: of the same run do not see); 0.4 is over three times the one and under a
#: fifth of the other.
LIMITS = {"gap_max": 3.5, "gap_mean": 0.4, "restore_gap_mean": 0.4}
PROBE_OWN, PROBE_OUT, PROBE_PAD = 2, 8, 1024

SPANS = ("engine.step", "route")

#: a step's stamp is ``llama_serve``'s five fields, the experts its tick
#: touched, these counters, and the load of each expert (``dots3_serve``'s
#: layout, so ``dots3_stats``'s readers of a stamp read this family's too)
STAMPED = ("moe.choices_total", "conv.state_restores",
           "conv.snapshots_written", "attn.keys_visible")


def _reference(cfg: dict):
    return lib.load_module("reference", cfg["reference"])


def model_config(cfg: dict, max_len: int):
    if not cfg["tie_word_embeddings"]:
        raise SystemExit("benchmark: shortconv_moe's head is the embedding; "
                         "the file says it is not tied")
    n = int(cfg["num_hidden_layers"])
    kinds = tuple(shortconv_moe.CONV if k == "conv" else shortconv_moe.ATTN
                  for k in cfg["layer_types"][:n])
    dt = jnp.dtype(cfg["torch_dtype"])
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return shortconv_moe.ShortConvMoEConfig(
        vocab_size=int(cfg["vocab_size"]), dim=d, layer_kinds=kinds,
        first_dense=int(cfg["num_dense_layers"]),
        ffn_dim=int(cfg["intermediate_size"]),
        conv_kernel=int(cfg["conv_L_cache"]), n_heads=h,
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=d // h,
        rope_theta=float(cfg["rope_theta"]),
        n_experts=int(cfg["num_experts"]),
        expert_dim=int(cfg["moe_intermediate_size"]),
        top_k=int(cfg["num_experts_per_tok"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        route_norm_eps=float(cfg["route_norm_eps"]), held_first=0,
        held_count=int(cfg["num_experts"]), norm_eps=float(cfg["norm_eps"]),
        max_seq_len=max_len, dtype=dt, param_dtype=dt)


def make_params(cfg: dict, seed: int) -> dict:
    """The program's parameter tree, every layer the reference's own."""
    ref = _reference(cfg)
    top = jax.jit(lambda s: ref.top_weights(cfg, s))(ref.seed_arg(seed))
    layers = tuple(ref.layer_weights(cfg, ref.seed_arg(seed), i)
                   for i in range(int(cfg["num_hidden_layers"])))
    return {"embed": top["embed"], "layers": layers,
            "final_norm": top["final_norm"]}


class Served(_llama.Served):
    """``llama_serve.Served`` over this family's model: the same router,
    replica, warm-up and stamps, the stamps with the model's counters, and
    per prompt the tokens the prefix cache spared it."""

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
        load = [eng.metrics.gauge(f"moe.held_load.{e}")
                for e in range(eng.cfg.held_count)]

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

    def route(self, prompt: list, n_out: int) -> int:
        rid = super().route(prompt, n_out)
        self._prompt_of[rid] = tuple(prompt)
        return rid

    def collect(self, rid: int, timeout: float):
        res, tr = super().collect(rid, timeout)
        if tr is not None:
            self.skipped[self._prompt_of[rid]] = int(
                tr.get("prefix_tokens_skipped", 0))
        return res, tr

    def _probe_restores(self) -> None:
        """One request a template the batch used: the template, ``PROBE_OWN``
        own tokens, ``PROBE_OUT`` tokens to serve.  Kept where the prefix
        cache spared it the template (its state came from a snapshot)."""
        n = self._template
        templates = sorted({p[:n] for p in self._prompt_of.values()}) if n \
            else []
        sent = []
        for head in templates:
            prompt = list(head) + self._rng.integers(
                1, self.vocab, PROBE_OWN).tolist()
            sent.append((prompt, self.route(prompt, PROBE_OUT)))
        for prompt, rid in sent:
            res, _ = self.collect(rid, 120.0)
            if (res is not None and res.status == "OK"
                    and len(res) == PROBE_OUT
                    and self.skipped.get(tuple(prompt), 0) == n):
                self.probes.append((prompt, list(res)))

    def close(self) -> None:
        self._probe_restores()
        ticking = [s for s in self.steps if s[2] > 0]
        if ticking:
            print(f"[bench] engine steps: {len(self.steps)}, {len(ticking)} "
                  f"with a tick, the first "
                  f"{ticking[0][1] - self.steps[0][0]:.2f} s after the first "
                  f"step began", flush=True)
        super().close()


def build(ctx) -> Served:
    served = Served(ctx.config, ctx.mix, ctx.seed)
    ctx.prefix_skipped = served.skipped     # filled as results are collected
    ctx.restore_probes = served.probes      # filled when the run closes
    return served


def pick_sample(finished: list, k: int, seed: int, skipped: dict) -> list:
    """``k`` of the finished requests ``(prompt, tokens)``, drawn from the
    seed: the longest always among them, and one that the prefix cache spared
    a part of its prompt (its convolution state came from a snapshot) where
    any did."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 11])
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    pick = [order[0]]
    hits = [i for i in order[1:]
            if skipped.get(tuple(finished[i][0]), 0) > 0]
    if hits and k > 1:
        pick.append(hits[int(rng.integers(len(hits)))])
    rest = [i for i in order if i not in pick]
    pick += [rest[i] for i in rng.permutation(len(rest))[:k - len(pick)]]
    return [finished[i] for i in pick]


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
    conv = sum(1 for k in kinds if k == "conv")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd, kvh = d // h, int(cfg["num_key_value_heads"])
    taps = int(cfg["conv_L_cache"])
    return {
        "d": d, "n": n, "conv": conv, "attn": n - conv,
        "dense": int(cfg["num_dense_layers"]), "taps": taps,
        "h": h, "hd": hd, "kvh": kvh,
        # in, out and the taps; q, k, v, o (norms apart)
        "conv_params": d * 3 * d + d * d + taps * d,
        "attn_params": 2 * d * h * hd + 2 * d * kvh * hd,
        "attn_norms": 2 * hd,
        "ffn_params": 3 * d * int(cfg["intermediate_size"]),
        "expert_params": 3 * d * int(cfg["moe_intermediate_size"]),
        "e": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "v": int(cfg["vocab_size"]),
        "item": jnp.dtype(cfg["torch_dtype"]).itemsize}


def dense_params(cfg: dict) -> int:
    """Parameters a tick reads whatever its rows chose, the router's float32
    bias apart: operators, norms, the dense SwiGLUs, the routers, the final
    norm and the embedding, which is the head."""
    s = _sizes(cfg)
    n_moe = s["n"] - s["dense"]
    return (s["conv"] * s["conv_params"]
            + s["attn"] * (s["attn_params"] + s["attn_norms"])
            + s["n"] * 2 * s["d"] + s["dense"] * s["ffn_params"]
            + n_moe * s["d"] * s["e"] + s["d"] + s["d"] * s["v"])


def dense_bytes(cfg: dict) -> int:
    """Bytes of weights a decode tick has to read whatever its rows chose."""
    s = _sizes(cfg)
    return (dense_params(cfg) * s["item"]
            + (s["n"] - s["dense"]) * s["e"] * 4)       # the biases: float32


def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    s = _sizes(cfg)
    return s["expert_params"] * s["item"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the whole parameter tree: what a tick reads when its rows
    touch every expert of every layer."""
    s = _sizes(cfg)
    return dense_bytes(cfg) + (s["n"] - s["dense"]) * s["e"] * expert_bytes(
        cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of keys and values one cached position holds: the attention
    layers only."""
    s = _sizes(cfg)
    return 2 * s["attn"] * s["kvh"] * s["hd"] * s["item"]


def state_bytes_per_row(cfg: dict) -> int:
    """Bytes of convolution state one sequence carries, all conv layers."""
    s = _sizes(cfg)
    return s["conv"] * (s["taps"] - 1) * s["d"] * s["item"]


def tick_bytes(cfg: dict, rows: float, live_tokens: float,
               experts_touched: float) -> float:
    """The least one decode tick has to move: the weights outside the experts
    once, the experts its rows touched (counted per layer), the keys and
    values of every position the decoding rows attend to, and each row's
    convolution state read and written."""
    return (dense_bytes(cfg) + experts_touched * expert_bytes(cfg)
            + live_tokens * kv_bytes_per_token(cfg)
            + 2 * rows * state_bytes_per_row(cfg))


def chunk_flops(cfg: dict, tokens: float, keys_visible: float,
                choices: float) -> float:
    """Operations of prefill over ``tokens`` positions: the products with the
    operators', the dense SwiGLUs' and the routers' weights, the convolution's
    taps, the routed experts for the choices made, and attention over the
    keys each query sees (``keys_visible``: summed over queries and attention
    layers, as the program counts them).  The head is left out: a request
    needs it at one position."""
    s = _sizes(cfg)
    n_moe = s["n"] - s["dense"]
    per_token = (s["conv"] * s["conv_params"] + s["attn"] * s["attn_params"]
                 + s["dense"] * s["ffn_params"] + n_moe * s["d"] * s["e"])
    return (2.0 * tokens * per_token + 2.0 * choices * s["expert_params"]
            + 4.0 * keys_visible * s["h"] * s["hd"])
