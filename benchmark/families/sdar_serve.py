"""Family ``sdar_serve``: a decoder of ``horovod_tpu.models.
block_diffusion_moe``'s architecture (grouped-query attention under the
block-causal mask, 128 softmax-routed experts all held, an untied head) that
generates by **diffusion over blocks**, served by ``ServeEngine`` behind
``RouterServer([LocalReplica])`` in this process, as ``llama_serve`` serves
Mistral: paged keys and values, chunked prefill, prefix cache on, and in place
of a token a row a tick a block of ``block_length`` positions a row, denoised
until no mask is left and then committed.

The weights are the benchmark's own, made on the device from the seed by the
configuration's reference (``reference/<reference>.py``), layer by layer in
the tree the program takes; from the program come the model code, the engine,
the replica's pump and the router.  The sampler's settings
(``denoising_steps``, ``remasking``, ``confidence_threshold``: what a user of
the model sets a run) are the traffic mix's ``engine`` keys and reach the
model's config object here.  Each engine step is stamped as in
``dots3_serve``: ``llama_serve``'s five fields, the experts its tick touched,
four of the engine's counters (``STAMPED``), and the load of each expert.

**What decides ``correct``.**  The engine keeps, per finished request, every
block it committed and the denoise step that unmasked each position
(``RequestResult.blocks`` / ``.unmask_steps``).  From them the reference
rebuilds every noisy block the program saw (``reference/sdar.py``,
``step_rows``: one pass over ``[clean sequence ; noisy blocks of step s]`` a
step) and reads ``gap_max`` / ``gap_mean`` — how far each served token's
reference logit lies under the reference's best at that position in the step
that unmasked it — and ``order_off_share`` — the share of the served tokens
whose position was unmasked while the reference's log-confidence of it lay
under that of a position the same step left masked in the same block (0 where
the program took the reference's own order).
The sample holds the longest request, one the prefix cache spared a part of
its prompt, one whose prompt leaves a tail in its first block and one whose
budget cuts its last.

The byte and operation counts of the block tick and the chunk programs are
here (``weight_bytes``, ``dense_bytes``, ``expert_bytes``,
``kv_bytes_per_token``, ``tick_bytes``, ``chunk_flops``): what the algorithm
needs, from the configuration's shapes, whatever implements it.

A program without ``horovod_tpu.models.block_diffusion_moe`` cannot run this
family and ends at once, before anything is built.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import capture, lib

try:
    from horovod_tpu.models import block_diffusion_moe
except ImportError as e:
    raise SystemExit(f"benchmark: this program cannot run the family "
                     f"'sdar_serve': {e}")

_llama = lib.load_module("families", "llama_serve")
CONTROL = _llama.CONTROL

#: Limits of the comparison, from chip readings at the cell's own size (my
#: chip runs, PR 45; PERF.md, section 2), each between the largest sound
#: reading and the control's or a fault's, with room on both sides.  Sound
#: runs: 31 with the embedding at 0.02 (the configuration's; 16 of them at
#: the committed rate), and 24 before with it at 1, which read alike.  The
#: control and the faults were read twice: in a window of 10 s (110
#: requests, the slots never full) and in the cell's own of 45 s (405;
#: the skipped commit on two seeds there).
#: ``gap_max``: sound 0.35-0.61 (0.29-0.80); the control (fp8 in the
#: program's place, three seeds) 5.13-6.77; 2.0 is 3.3 times the one and
#: under two fifths of the control, and under the 4 or so that a token
#: altered where it is produced lies below the best.  The skipped commit
#: read 2.33 in the short window, 1.54 and 2.30 in the cell's: this number
#: does not hold it on every seed, the next does.  ``gap_mean``: 0.0042-0.0151 sound
#: (0.0013-0.0111); 3.34-5.11 under the control; with the commit skipped
#: (the last denoise step's keys left standing: every later block reads keys
#: of a block that still held masks) 0.44 in the short window, 0.207 and
#: 0.300 in the cell's (the seed moves it); 0.06 is four times the one and
#: under a third of the smallest other (0.1 until the readings at the
#: cell's own window).  ``order_off_share``: the
#: log-confidences of random weights lie within a few tenths of each other
#: (a softmax over 152 k near-uniform logits), so bfloat16 swaps near-ties:
#: sound runs read 0.082-0.129 (0.075-0.123); the unmask order turned round
#: (the least confident first) 0.474 and 0.473, the control 0.308-0.320, the
#: skipped commit 0.307-0.329; 0.25 is twice the one, under the others,
#: about half of the turned order.  How *far* a taken position lay under the
#: best one left (the issue's ``order_gap_max``) is in the log line and
#: decides nothing: sound runs read 0.21-0.50 and the turned order 0.65-0.68
#: (0.45 against 0.17-0.49 with the embedding at 1), because no two
#: confidences of a block lie further apart.
LIMITS = {"gap_max": 2.0, "gap_mean": 0.06, "order_off_share": 0.25}

SPANS = ("engine.step", "route")

#: a step's stamp is ``llama_serve``'s five fields, the experts its tick
#: touched, these counters, and the load of each expert (``dots3_serve``'s
#: layout, so ``dots3_stats``'s readers of a stamp by position read this
#: family's too)
STAMPED = ("moe.choices_total", "attn.keys_visible",
           "diffusion.denoise_forwards", "diffusion.commit_forwards")


def _reference(cfg: dict):
    return lib.load_module("reference", cfg["reference"])


def model_config(cfg: dict, engine: dict, max_len: int):
    if cfg["tie_word_embeddings"]:
        raise SystemExit("benchmark: block_diffusion_moe's head is its own "
                         "matrix; the file says it is tied to the embedding")
    if cfg["mlp_only_layers"] or int(cfg["decoder_sparse_step"]) != 1:
        raise SystemExit("benchmark: block_diffusion_moe has experts in "
                         "every layer; the file names dense layers")
    dt = jnp.dtype(cfg["torch_dtype"])
    return block_diffusion_moe.BlockDiffusionMoEConfig(
        vocab_size=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), rope_theta=float(cfg["rope_theta"]),
        n_experts=int(cfg["num_experts"]),
        expert_dim=int(cfg["moe_intermediate_size"]),
        top_k=int(cfg["num_experts_per_tok"]),
        norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=max_len,
        block_length=int(cfg["block_length"]),
        denoising_steps=int(engine["denoising_steps"]),
        remasking=str(engine["remasking"]),
        confidence_threshold=float(engine["confidence_threshold"]),
        mask_token_id=int(cfg["mask_token_id"]), dtype=dt, param_dtype=dt)


def make_params(cfg: dict, seed: int) -> dict:
    """The program's parameter tree, every layer the reference's own."""
    ref = _reference(cfg)
    top = jax.jit(lambda s: ref.top_weights(cfg, s))(ref.seed_arg(seed))
    make = jax.jit(lambda s, i: ref.layer_weights(cfg, s, i))
    layers = tuple(make(ref.seed_arg(seed), jnp.int32(i))
                   for i in range(int(cfg["num_hidden_layers"])))
    return {"embed": top["embed"], "layers": layers,
            "final_norm": top["final_norm"], "lm_head": top["lm_head"]}


class Served(_llama.Served):
    """``llama_serve.Served`` over this family's model: the same router,
    replica and warm-up; the stamps with the engine's counters; per prompt
    the tokens the prefix cache spared it, the blocks it committed and the
    step that unmasked each of their positions."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from horovod_tpu import metrics as metrics_mod
        from horovod_tpu.router import LocalReplica, RouterServer
        from horovod_tpu.serving import Request
        from horovod_tpu.serving_scheduler import ServeEngine

        e = mix["engine"]
        self.request_cls = Request
        # the traffic draws its ids below this: no prompt holds the mask id
        self.vocab = int(cfg["prompt_ids_below"])
        self.chunk = int(e["chunk"])
        model_cfg = model_config(cfg, e, int(e["max_len"]))
        params = make_params(cfg, seed)
        self.engine = eng = ServeEngine(
            params, model_cfg, n_slots=int(e["n_slots"]),
            max_len=int(e["max_len"]), chunk=self.chunk,
            prefix_cache=bool(e["prefix_cache"]), n_blocks=e.get("n_blocks"),
            metrics=metrics_mod.MetricsRegistry(event_log=None))
        del params
        # Every program the traffic uses: a prompt longer than one chunk
        # that leaves a tail (two prefill windows, a first block with given
        # positions), a table write, the unmask program and block ticks of
        # both kinds, a budget that cuts the last block.
        rng = np.random.default_rng([seed, 7])
        n_warm = 2 * model_cfg.block_length + 1
        warm = eng.run([Request(
            prompt=rng.integers(1, self.vocab, self.chunk + 3).tolist(),
            max_new_tokens=n_warm)])
        if warm[0].status != "OK" or len(warm[0]) != n_warm:
            raise SystemExit(f"benchmark: warm-up request failed: {warm[0]}")
        self.steps: list = []
        self._prompt_of: dict = {}
        self.skipped: dict = {}         # prompt -> tokens the cache spared
        self.blocks: dict = {}          # prompt -> (blocks, unmask steps)
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
                for e in range(eng.cfg.n_experts)]

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
        if res is not None and tr is not None:
            key = self._prompt_of[rid]
            self.skipped[key] = int(tr.get("prefix_tokens_skipped", 0))
            if getattr(res, "blocks", None):
                self.blocks[key] = (res.blocks, res.unmask_steps)
        return res, tr

    def close(self) -> None:
        self.engine.block_logits = None
        super().close()


def build(ctx) -> Served:
    served = Served(ctx.config, ctx.mix, ctx.seed)
    ctx.prefix_skipped = served.skipped     # filled as results are collected
    ctx.served_blocks = served.blocks
    return served


def pick_sample(finished: list, k: int, seed: int, skipped: dict,
                block: int) -> list:
    """``k`` of the finished requests ``(prompt, tokens)``, drawn from the
    seed: the longest always among them, then, where any is, one that the
    prefix cache spared a part of its prompt, one whose prompt leaves a tail
    in its first block and one whose budget cuts its last block."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 11])
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    pick = [order[0]]
    tail = lambda i: len(finished[i][0]) % block                # noqa: E731
    wants = (lambda i: skipped.get(tuple(finished[i][0]), 0) > 0,
             lambda i: tail(i) != 0,
             lambda i: (tail(i) + len(finished[i][1])) % block != 0)
    for want in wants:
        if len(pick) >= k or any(want(i) for i in pick[1:]):
            continue
        have = [i for i in order if i not in pick and want(i)]
        if have:
            pick.append(have[int(rng.integers(len(have)))])
    rest = [i for i in order if i not in pick]
    pick += [rest[i] for i in rng.permutation(len(rest))[:k - len(pick)]]
    return [finished[i] for i in pick]


def rows_of(cfg: dict, mix: dict, seed: int, sample: list,
            precision: str = "float32", also: list | None = None) -> list:
    """The reference's readings (``reference/sdar.py``, ``step_rows``) for a
    sample of ``(prompt, blocks, steps)``."""
    return _reference(cfg).step_rows(
        cfg, seed, sample, int(mix["engine"]["denoising_steps"]), precision,
        int(mix["check"]["pad_to"]), also)


def _line(name: str, value: float) -> dict:
    return {"name": name, "value": value, "limit": LIMITS[name],
            "ok": bool(np.isfinite(value) and value <= LIMITS[name])}


def compare(gaps: np.ndarray, order: np.ndarray) -> list:
    return [_line("gap_max", float(np.max(gaps))),
            _line("gap_mean", float(np.mean(gaps))),
            _line("order_off_share", float(np.mean(order > 0)))]


def check(ctx, finished: list) -> list:
    """After the window, with the engine freed: a sample of the requests it
    finished through the reference, one pass a denoise step over each."""
    cfg = ctx.config
    ref = _reference(cfg)
    skipped = getattr(ctx, "prefix_skipped", {})
    served = getattr(ctx, "served_blocks", {})
    block = int(cfg["block_length"])
    sample = pick_sample(finished, int(ctx.mix["check"]["sample"]), ctx.seed,
                         skipped, block)
    sample = [(p, *served[tuple(p)]) for p, t in sample
              if tuple(p) in served and _tokens_of(p, served[tuple(p)][0],
                                                   block, len(t)) == list(t)]
    if not sample:
        return [{"name": "served_blocks", "value": 0, "limit": 1,
                 "ok": False}]
    ctx.sample = sample
    rows = rows_of(cfg, ctx.mix, ctx.seed, sample)
    g = np.concatenate([ref.gaps(r) for r in rows])
    o = np.concatenate([ref.order_gaps(r) for r in rows])
    n_hit = sum(1 for p, _, _ in sample if skipped.get(tuple(p), 0) > 0)
    n_tail = sum(1 for p, _, _ in sample if len(p) % block)
    ctx.say(f"check: {len(sample)} requests ({n_hit} admitted on a prefix "
            f"hit, {n_tail} with a tail in the first block), {g.size} served "
            f"tokens, {int((g > 0).sum())} not the reference's first choice, "
            f"{int((o > 0).sum())} unmasked out of the reference's order (by "
            f"{float(np.max(o)):.4f} at the most), gap quantiles 0.5/0.9/0.99 "
            f"{[round(float(np.quantile(g, q)), 4) for q in (.5, .9, .99)]}")
    return compare(g, o)


def _tokens_of(prompt: list, blocks: list, block: int, n_out: int) -> list:
    """What a request's committed blocks say it was answered."""
    tail = len(prompt) % block
    flat = [t for b in blocks for t in b]
    return flat[tail:tail + n_out]


def probe(ctx, control: bool) -> dict:
    """For ``limits_probe.py``: a short window of the cell's own traffic, the
    served tokens against the reference and, if asked, the control's: what a
    lower precision in the program's place would have unmasked, position by
    position and in its own order."""
    rec = lib.load_module("drivers", ctx.mix["driver"]).run(ctx)
    out = {"sound": {c["name"]: c["value"] for c in rec["checks"]
                     if c["name"] in LIMITS},
           "attempted": rec["attempted"], "failed": rec["failed"]}
    if control:
        cfg, ref = ctx.config, _reference(ctx.config)
        low = rows_of(cfg, ctx.mix, ctx.seed, ctx.sample, CONTROL)
        rows = rows_of(cfg, ctx.mix, ctx.seed, ctx.sample,
                       also=[r["argmax"].tolist() for r in low])
        g = np.concatenate([ref.gaps(r, "picked_also") for r in rows])
        # the order the lower precision would have taken, by the reference's
        # confidences: its `n` most confident in place of the program's
        o = np.concatenate([
            ref.order_gaps(dict(r, taken=_retaken(ref, r, lo)))
            for r, lo in zip(rows, low)])
        out["control"] = {c["name"]: c["value"] for c in compare(g, o)}
        out["tokens"] = int(g.size)
    return out


def _retaken(ref, rows: dict, low: dict) -> np.ndarray:
    """Per step and block, as many positions taken as the program took, but
    the ones the lower precision's confidences put first."""
    taken = np.zeros(len(rows["taken"]), bool)
    for of in ref.groups(rows):
        n = int(rows["taken"][of].sum())
        taken[of[np.argsort(-low["logc"][of], kind="stable")[:n]]] = True
    return taken


# ---------------------------------------------------------------------------
# bytes and operations, from the configuration's shapes
# ---------------------------------------------------------------------------

def _sizes(cfg: dict) -> dict:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd, kvh = int(cfg["head_dim"]), int(cfg["num_key_value_heads"])
    return {
        "d": d, "n": int(cfg["num_hidden_layers"]),
        "h": h, "hd": hd, "kvh": kvh,
        # q, k, v, o (norms apart)
        "attn_params": 2 * d * h * hd + 2 * d * kvh * hd,
        "attn_norms": 2 * hd,
        "expert_params": 3 * d * int(cfg["moe_intermediate_size"]),
        "e": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "v": int(cfg["vocab_size"]), "block": int(cfg["block_length"]),
        "item": jnp.dtype(cfg["torch_dtype"]).itemsize}


def dense_params(cfg: dict) -> int:
    """Parameters a tick reads whatever its rows chose: attention, norms,
    the routers, the final norm and the head (the embedding is looked up row
    by row and left out)."""
    s = _sizes(cfg)
    return (s["n"] * (s["attn_params"] + s["attn_norms"] + 2 * s["d"]
                      + s["d"] * s["e"])
            + s["d"] + s["d"] * s["v"])


def dense_bytes(cfg: dict) -> int:
    return dense_params(cfg) * _sizes(cfg)["item"]


def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    s = _sizes(cfg)
    return s["expert_params"] * s["item"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the whole parameter tree: what a tick reads when its rows
    touch every expert of every layer, and the embedding."""
    s = _sizes(cfg)
    return (dense_bytes(cfg) + s["d"] * s["v"] * s["item"]
            + s["n"] * s["e"] * expert_bytes(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of keys and values one cached position holds, all layers."""
    s = _sizes(cfg)
    return 2 * s["n"] * s["kvh"] * s["hd"] * s["item"]


def tick_bytes(cfg: dict, rows: float, live_tokens: float,
               experts_touched: float) -> float:
    """The least one block tick has to move: the weights outside the experts
    once, the experts its rows touched (counted per layer), the keys and
    values of every committed position the decoding rows attend to, the
    block's own keys and values written and read once, and the block's
    logits written (float32, every position: the unmask program's input)."""
    s = _sizes(cfg)
    block = rows * s["block"]
    return (dense_bytes(cfg) + experts_touched * expert_bytes(cfg)
            + live_tokens * kv_bytes_per_token(cfg)
            + 2 * block * kv_bytes_per_token(cfg)
            + block * s["v"] * 4)


def chunk_flops(cfg: dict, tokens: float, keys_visible: float,
                choices: float) -> float:
    """Operations of prefill over ``tokens`` positions: the products with the
    attention's and the routers' weights, the routed experts for the choices
    made, and attention over the keys each query sees (``keys_visible``:
    summed over queries and layers as the program counts them, a query's
    whole block among them).  The head is left out: nothing of a prefill's
    logits is used."""
    s = _sizes(cfg)
    per_token = s["n"] * (s["attn_params"] + s["d"] * s["e"])
    return (2.0 * tokens * per_token + 2.0 * choices * s["expert_params"]
            + 4.0 * keys_visible * s["h"] * s["hd"])
