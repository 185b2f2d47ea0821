"""Family ``granite_serve``: a decoder of the architecture of
``horovod_tpu.models.state_space_moe`` (state-space mixers with one grouped-query attention layer
without rotary among them, softmax-routed experts beside a shared one, a tied
head) served by ``ServeEngine`` behind ``RouterServer([LocalReplica])`` in this
process, as ``llama_serve`` serves Mistral: the attention layer's paged pools,
the state-space layers' recurrent state a slot and its snapshots under a
budget (far fewer than blocks), chunked prefill, prefix cache on, greedy
decoding.  The configuration is one chip's share (``dots3_serve``'s cut): the
experts from ``held_experts_first`` on and the vocabulary's rows from
``vocab_first_row``.

The weights are the benchmark's own, made on the device from the seed by the
configuration's reference (``reference/<reference>.py``), layer by layer in
the tree the program takes; from the program come the model code, the engine,
the replica's pump and the router.  Each engine step is stamped as in
``dots3_serve``: ``llama_serve``'s five fields, the experts its tick touched,
four of the model's counters, the load of each held expert, and last the
snapshot budget's counters (``TAIL``).  The family keeps ``lfm2_serve``'s
count, per request, of the prompt tokens the prefix cache spared it and its
**restore probes**: when the batch has drained each system prompt is asked
once more with an own part of 2 tokens and 8 tokens to serve, admitted on a
hit, its state restored from the snapshot at the prompt's last block, its
served tokens right behind that state.  Their mean gap against the reference
is the third number of the comparison, ``restore_gap_mean``.

**State probes.**  The logits weigh the recurrent state lightly (with the
published initialisation its part of a mixer's output is a few hundredths
beside ``D x``), so three numbers are taken on the state itself.  When the
restore probes are done the pump is stopped and the engine is stepped from
here: each system prompt is asked once more with 2 own tokens (admitted on a
hit, its state restored) and, beside them, one prompt of ``state_fresh`` random
tokens (admitted at length 0 into a slot a finished request used); when each
has been served ``state_served`` tokens, a tick at a time, its slot's state
``pcache.ssm[:, slot]`` is fetched with the length the cache holds for it
(how many tokens the state has taken in), and the probes run to their end.
The reference's recurrence over the same tokens gives the state each slot
should hold: ``state_gap_first`` and ``state_gap`` are a head's error over
its norm, the mean over the first state-space layer and the largest anywhere, and
``state_bf16_share`` is the share of the fetched elements that a bfloat16
holds exactly (:func:`state_numbers`).

The byte and operation counts of the tick and the chunk programs are here
(``weight_bytes``, ``dense_bytes``, ``expert_bytes``, ``kv_bytes_per_token``,
``state_bytes_per_slot``, ``tick_bytes``, ``chunk_flops``): what the
algorithm needs, from the configuration's shapes.

A program without ``horovod_tpu.models.state_space_moe`` cannot run this
family and ends at once, before anything is built.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import capture, lib

try:
    from horovod_tpu.models import state_space_moe
except ImportError as e:
    raise SystemExit(f"benchmark: this program cannot run the family "
                     f"'granite_serve': {e}")

_llama = lib.load_module("families", "llama_serve")
_lfm2 = lib.load_module("families", "lfm2_serve")
gaps, CONTROL = _llama.gaps, _llama.CONTROL
#: the second control: the recurrent state rounded to bfloat16 at every token
CONTROL_STATE = "bf16_state"
pick_sample = _lfm2.pick_sample

#: Limits of the comparison, from chip readings with the cell's own engine
#: (my chip runs, PR 38, second session; PERF.md, section 2): five sound runs
#: of the program as it is now, two of them at the cell's 360 requests.  The
#: logits spread by 0.005 (the reference says why), so the first three
#: numbers are on that scale; some 9 % of the served tokens are not the
#: reference's first choice (a token's 100 expert choices are discrete and
#: bfloat16 flips a near-tie now and then), none further than a third of a
#: spread below it.  ``gap_max``: 0.0007-0.0016 sound, at least 0.0391 under
#: the control (fp8 in the program's place, 3 seeds); 0.008 is five times
#: the one and a fifth of the other, and of the 0.04 a token altered where
#: it is produced lies below the best.  ``gap_mean``: 1.7e-5 to 2.1e-5
#: sound (4.3e-5 with the state in bfloat16), at least 0.0218 under the
#: control; 2e-4 is ten times the one and a hundredth of the other.
#: ``restore_gap_mean`` (the probes' 32 tokens): 4e-6 to 3.5e-5 sound,
#: 0.0123-0.0133 with every restored state zeroed (3 runs); 4e-4 is eleven
#: times the one and a thirtieth of the other.  (The first session's limits, 0.024
#: / 0.004 / 0.002, were set from runs whose first layer's state advanced
#: twice a tick: its sound readings were thirty times these.)  On the state
#: itself (:func:`state_numbers`): ``state_gap_first`` 0.00475-0.00495 sound
#: (0.0064 with the state in bfloat16, 0.32 with the first layer's state
#: advanced twice a tick); 0.01 is twice the one and a thirtieth of the
#: fault.  ``state_gap`` 0.13-0.21 sound (0.33 with the state in bfloat16),
#: 1.03 with the twice-advanced state, 1.05 with the restored state zeroed,
#: 1.11 with a stale state left in a slot admitted at 0; 0.5 is over twice
#: the one and under half the others.
#: ``state_bf16_share`` 3.6e-5 to 3.8e-5 sound, 1.0 with the state rounded to
#: bfloat16 after every tick and chunk (which none of the other five sees)
#: and for the reference's own control ``bf16_state``; 0.01.
LIMITS = {"gap_max": 0.008, "gap_mean": 2e-4, "restore_gap_mean": 4e-4,
          "state_gap_first": 0.01, "state_gap": 0.5, "state_bf16_share": 0.01}
STATE_NUMBERS = ("state_gap_first", "state_gap", "state_bf16_share")
PROBE_PAD = 3072           # the reference's padding for a probe
STATE_SLACK = 8     # tokens asked of a state probe beyond where it is read

SPANS = ("engine.step", "route")

#: a step's stamp is ``llama_serve``'s five fields, the experts its tick
#: touched, these counters, the load of each held expert (``dots3_serve``'s
#: layout so far, so ``dots3_stats``'s readers of a stamp by position read
#: this family's too), and last ``TAIL``: five counters and a gauge
STAMPED = ("moe.choices_total", "moe.choices_held", "ssm.state_restores",
           "attn.keys_visible")
TAIL = ("ssm.snapshots_written", "ssm.snapshots_evicted",
        "ssm.state_bytes_moved", "prefix.blocks_matched",
        "prefix.blocks_restored")
TAIL_GAUGE = "ssm.snapshots_live"


def _reference(cfg: dict):
    return lib.load_module("reference", cfg["reference"])


def model_config(cfg: dict, max_len: int, snapshots: int = 24):
    if not cfg["tie_word_embeddings"]:
        raise SystemExit("benchmark: state_space_moe's head is the embedding; "
                         "the file says it is not tied")
    if int(cfg["mamba_n_groups"]) != 1 or cfg["mamba_proj_bias"]:
        raise SystemExit("benchmark: state_space_moe has one group of B and "
                         "C and no projection bias; the file says otherwise")
    n = int(cfg["num_hidden_layers"])
    kinds = tuple(state_space_moe.SSM if k == "mamba"
                  else state_space_moe.ATTN for k in cfg["layer_types"][:n])
    dt = jnp.dtype(cfg["torch_dtype"])
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    held = int(cfg["num_local_experts"])
    return state_space_moe.StateSpaceMoEConfig(
        vocab_size=int(cfg["vocab_size"]), dim=d, layer_kinds=kinds,
        ssm_heads=int(cfg["mamba_n_heads"]),
        ssm_head_dim=int(cfg["mamba_d_head"]),
        ssm_state=int(cfg["mamba_d_state"]),
        conv_kernel=int(cfg["mamba_d_conv"]),
        ssm_chunk=int(cfg["mamba_chunk_size"]), n_heads=h,
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or d // h),
        attn_scale=float(cfg["attention_multiplier"]),
        n_experts=int(cfg.get("num_local_experts_published", held)),
        expert_dim=int(cfg["intermediate_size"]),
        top_k=int(cfg["num_experts_per_tok"]),
        shared_dim=int(cfg["shared_intermediate_size"]),
        held_first=int(cfg.get("held_experts_first", 0)), held_count=held,
        embed_scale=float(cfg["embedding_multiplier"]),
        residual_scale=float(cfg["residual_multiplier"]),
        logits_scale=float(cfg["logits_scaling"]),
        norm_eps=float(cfg["rms_norm_eps"]), snapshots=int(snapshots),
        max_seq_len=max_len, dtype=dt, param_dtype=dt)


def make_params(cfg: dict, seed: int) -> dict:
    """The program's parameter tree, every layer the reference's own."""
    ref = _reference(cfg)
    top = jax.jit(lambda s: ref.top_weights(cfg, s))(ref.seed_arg(seed))
    makers = {}
    layers = []
    for i in range(int(cfg["num_hidden_layers"])):
        kind = ref.layer_kind(cfg, i)
        if kind not in makers:
            makers[kind] = jax.jit(lambda s, i, k=kind:
                                   ref._layer_weights(cfg, k, s, i))
        layers.append(makers[kind](ref.seed_arg(seed), jnp.int32(i)))
    return {"embed": top["embed"], "layers": tuple(layers),
            "final_norm": top["final_norm"]}


class Served(_lfm2.Served):
    """``lfm2_serve.Served`` over this family's model: the same router,
    replica, warm-up, per-prompt count of the tokens the prefix cache spared
    and restore probes; the engine (with its snapshot budget, the traffic
    file's ``snapshots``) and the stamps are this family's."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from horovod_tpu import metrics as metrics_mod
        from horovod_tpu.router import LocalReplica, RouterServer
        from horovod_tpu.serving import Request
        from horovod_tpu.serving_scheduler import ServeEngine

        e = mix["engine"]
        self.request_cls = Request
        self.vocab = int(cfg["vocab_size"])
        self.chunk = int(e["chunk"])
        model_cfg = model_config(cfg, int(e["max_len"]),
                                 int(e.get("snapshots", 24)))
        params = make_params(cfg, seed)
        self.engine = eng = ServeEngine(
            params, model_cfg, n_slots=int(e["n_slots"]),
            max_len=int(e["max_len"]), chunk=self.chunk,
            block_size=e.get("block_size"),
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
        self.states: list = []          # what the state probes read
        self._template = int(mix["shapes"]["system_prompts"]["tokens"])
        self._state_out = int(mix["check"]["state_served"])
        self._state_fresh = int(mix["check"]["state_fresh"])
        self._rng = np.random.default_rng([seed, 13])
        self._wrap_step()
        self.replica = LocalReplica(eng, "r0")
        self.router = RouterServer([self.replica])

    def _probe_states(self) -> None:
        """The state probes (the module's docstring), with the pump stopped
        and the engine stepped from here, as many at a time as there are
        slots.  Kept: ``(prompt, served tokens, tokens the state has taken
        in, the slot's recurrent state [layers, H, P, N] on the host)`` of
        each probe whose state stood where it was meant to (a system
        prompt's: admitted on a hit)."""
        n, width = self._template, self.engine.n_slots
        heads = sorted({p[:n] for p in self._prompt_of.values()}) if n else []
        prompts = [list(h) + self._rng.integers(
            1, self.vocab, _lfm2.PROBE_OWN).tolist() for h in heads]
        prompts.append(self._rng.integers(
            1, self.vocab, self._state_fresh).tolist())
        for i in range(0, len(prompts), width):
            self._read_states(prompts[i:i + width],
                              [i + k < len(heads) for k in range(width)])

    def _read_states(self, prompts: list, hits: list) -> None:
        eng, out = self.engine, self._state_out
        seen = len(eng.events)
        rids = [eng.submit(self.request_cls(
            prompt=p, max_new_tokens=out + STATE_SLACK)) for p in prompts]
        want = {r: len(p) + out for r, p in zip(rids, prompts)}
        slot, hit, length = {}, set(), None
        for _ in range(8 * (out + len(prompts))):
            eng.step()
            for ev in eng.events[seen:]:
                if ev.request_id in want and ev.kind == "admit":
                    slot[ev.request_id] = ev.slot
                elif ev.request_id in want and ev.kind == "hit":
                    hit.add(ev.request_id)
                elif ev.request_id in want:         # preempted, retried, ...
                    return
            seen = len(eng.events)
            if len(slot) == len(rids):
                length = np.asarray(eng.pcache.length)
                if all(length[slot[r]] >= want[r] for r in rids):
                    break
        else:
            return
        taken = {r: (int(length[slot[r]]), np.asarray(
            eng.pcache.ssm[:, slot[r]])) for r in rids}
        while eng.pending():
            eng.step()
        for prompt, rid, was_hit in zip(prompts, rids, hits):
            res = eng.results.get(rid)
            if (res is not None and res.status == "OK"
                    and (rid in hit) == was_hit):
                self.states.append((prompt, list(res)) + taken[rid])

    def close(self) -> None:
        self._probe_restores()
        ticking = [s for s in self.steps if s[2] > 0]
        if ticking:
            print(f"[bench] engine steps: {len(self.steps)}, {len(ticking)} "
                  f"with a tick, the first "
                  f"{ticking[0][1] - self.steps[0][0]:.2f} s after the first "
                  f"step began", flush=True)
        self.replica.stop()             # the engine is this thread's now
        self._probe_states()
        _llama.Served.close(self)

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
        tail = [eng.metrics.counter(name) for name in TAIL] \
            + [eng.metrics.gauge(TAIL_GAUGE)]

        def step():
            t0 = time.monotonic()
            with capture.span("engine.step"):
                out = inner()
            steps.append((t0, time.monotonic(), decoding.value,
                          prefilling.value, len(out), touched.value,
                          *(c.value for c in counters),
                          *(g.value for g in load),
                          *(c.value for c in tail)))
            return out

        eng.step = step


def build(ctx) -> Served:
    served = Served(ctx.config, ctx.mix, ctx.seed)
    ctx.prefix_skipped = served.skipped     # filled as results are collected
    ctx.restore_probes = served.probes      # filled when the run closes
    ctx.state_probes = served.states        # filled when the run closes
    return served


def _line(name: str, value: float) -> dict:
    return {"name": name, "value": value, "limit": LIMITS[name],
            "ok": bool(np.isfinite(value) and value <= LIMITS[name])}


def compare(g: np.ndarray) -> list:
    return [_line("gap_max", float(np.max(g))),
            _line("gap_mean", float(np.mean(g)))]


def state_numbers(cfg: dict, mix: dict, seed: int, states: list,
                  control: str | None = None) -> tuple:
    """The state probes against the reference's recurrence over the same
    tokens.  ``r`` [probes, layers, heads]: how far each head's recurrent
    state, as the program left it in the probe's slot, lies from the
    reference's, over the norm of the reference's.  The numbers:
    ``state_gap_first``, the mean of ``r`` in the first state-space layer
    (its inputs are the tokens' embeddings, the same in program and
    reference but for one rounding, so what differs is the state's own
    arithmetic); ``state_gap``, the largest ``r`` of any head (further layers
    inherit what bfloat16 and a flipped expert choice did to their inputs, a
    few hundredths a layer; a head that kept a last occupant's state, or one
    of a layer advanced wrongly, stands out of that);
    ``state_bf16_share``, the share of the states' elements other than 0
    that a bfloat16 holds exactly (a float32 state: 2**-16 of them).  With
    ``control`` (a lower precision) the reference's own states at that
    precision stand in the program's place.  Returns the numbers and ``r``."""
    ref = _reference(cfg)
    seqs = [list(p) + list(t) for p, t, _, _ in states]
    counts = [n for _, _, n, _ in states]
    pad = _probe_mix(mix)["check"]["pad_to"]
    want = ref.states_at(cfg, seed, seqs, counts, "float32", pad)
    got = ([s for _, _, _, s in states] if control is None
           else ref.states_at(cfg, seed, seqs, counts, control, pad))

    def heads(a):
        return np.linalg.norm(a.reshape(a.shape[:2] + (-1,)), axis=-1)

    r = np.stack([heads(g - w) / np.maximum(heads(w), 1e-30)
                  for g, w in zip(got, want)])
    bits = np.concatenate([np.ascontiguousarray(g, np.float32).reshape(-1)
                           .view(np.uint32) for g in got])
    held = bits[(bits & 0x7FFFFFFF) != 0]
    return {"state_gap_first": float(r[:, 0].mean()),
            "state_gap": float(r.max()),
            "state_bf16_share": float(np.mean((held & 0xFFFF) == 0))
            if held.size else 1.0}, r


def _probe_mix(mix: dict) -> dict:
    return dict(mix, check=dict(mix["check"], pad_to=min(
        PROBE_PAD, int(mix["check"]["pad_to"]))))


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
        g_r = gaps(ctx.config, _probe_mix(ctx.mix), ctx.seed,
                   probes)["served"]
        restore = float(np.mean(g_r))
        ctx.say(f"check: {len(probes)} restore probes, {g_r.size} served "
                f"tokens, largest gap {float(np.max(g_r)):.4f}")
    states = getattr(ctx, "state_probes", [])
    numbers = dict.fromkeys(STATE_NUMBERS, float("inf"))
    if states:
        numbers, r = state_numbers(ctx.config, ctx.mix, ctx.seed, states)
        ctx.say(f"check: {len(states)} state probes, the state read after "
                f"{[n for _, _, n, _ in states]} tokens; a head's error over "
                f"its norm, mean a layer "
                f"{[round(float(v), 4) for v in r.mean(axis=(0, 2))]}, "
                f"largest {float(np.max(r)):.4f}")
    return compare(g) + [_line("restore_gap_mean", restore)] \
        + [_line(name, numbers[name]) for name in STATE_NUMBERS]


def probe(ctx, control: bool) -> dict:
    """For ``limits_probe.py``: a short window of the cell's own traffic, the
    served tokens against the reference and, if asked, the two controls':
    fp8 in the program's place (``control``) and the recurrent state rounded
    to bfloat16 at every token (``control_state``: the sample's numbers and,
    the probes being short, their mean gap too)."""
    rec = lib.load_module("drivers", ctx.mix["driver"]).run(ctx)
    out = {"sound": {c["name"]: c["value"] for c in rec["checks"]
                     if c["name"] in LIMITS},
           "attempted": rec["attempted"], "failed": rec["failed"]}
    if control:
        for key, precision in (("control", CONTROL),
                               ("control_state", CONTROL_STATE)):
            g = gaps(ctx.config, ctx.mix, ctx.seed, ctx.sample, precision)
            out[key] = {c["name"]: c["value"] for c in compare(g["control"])}
        probes = getattr(ctx, "restore_probes", [])
        if probes:
            g = gaps(ctx.config, _probe_mix(ctx.mix), ctx.seed, probes,
                     CONTROL_STATE)
            out["control_state"]["restore_gap_mean"] = float(
                np.mean(g["control"]))
        out["tokens"] = int(g["control"].size)
        states = getattr(ctx, "state_probes", [])
        if states:
            out["control_state"].update(state_numbers(
                ctx.config, ctx.mix, ctx.seed, states, CONTROL_STATE)[0])
    return out


# ---------------------------------------------------------------------------
# bytes and operations, from the configuration's shapes
# ---------------------------------------------------------------------------

def _sizes(cfg: dict) -> dict:
    n = int(cfg["num_hidden_layers"])
    kinds = cfg["layer_types"][:n]
    ssm = sum(1 for k in kinds if k == "mamba")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd, kvh = int(cfg.get("head_dim") or d // h), \
        int(cfg["num_key_value_heads"])
    sh, sp = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    sn, taps = int(cfg["mamba_d_state"]), int(cfg["mamba_d_conv"])
    inner = sh * sp
    conv = inner + 2 * sn
    held = int(cfg["num_local_experts"])
    return {
        "d": d, "n": n, "ssm": ssm, "attn": n - ssm, "dense": 0,
        "h": h, "hd": hd, "kvh": kvh, "sh": sh, "sp": sp, "sn": sn,
        "taps": taps, "inner": inner, "conv": conv,
        # in and out (what a matrix product reads), and the rest of a mixer:
        # taps, bias and gate norm in the weights' type; dt_bias, A_log and
        # D in float32
        "mixer_mats": d * (inner + conv + sh) + inner * d,
        "mixer_rest": conv * taps + conv + inner,
        "mixer_f32": 3 * sh,
        "attn_params": 2 * d * h * hd + 2 * d * kvh * hd,
        "expert_params": 3 * d * int(cfg["intermediate_size"]),
        "shared_params": 3 * d * int(cfg["shared_intermediate_size"]),
        "e": held,
        "e_all": int(cfg.get("num_local_experts_published", held)),
        "k": int(cfg["num_experts_per_tok"]), "v": int(cfg["vocab_size"]),
        "item": jnp.dtype(cfg["torch_dtype"]).itemsize}


def dense_bytes(cfg: dict) -> int:
    """Bytes of weights a decode tick has to read whatever its rows chose:
    mixers, attention, norms, routers, shared experts, the final norm and
    the embedding, which is the head."""
    s = _sizes(cfg)
    params = (s["ssm"] * (s["mixer_mats"] + s["mixer_rest"])
              + s["attn"] * s["attn_params"]
              + s["n"] * (2 * s["d"] + s["d"] * s["e_all"]
                          + s["shared_params"])
              + s["d"] + s["d"] * s["v"])
    return params * s["item"] + s["ssm"] * s["mixer_f32"] * 4


def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's matrices."""
    s = _sizes(cfg)
    return s["expert_params"] * s["item"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the whole parameter tree: what a tick reads when its rows
    touch every held expert of every layer."""
    s = _sizes(cfg)
    return dense_bytes(cfg) + s["n"] * s["e"] * expert_bytes(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of keys and values one cached position holds: the attention
    layers only."""
    s = _sizes(cfg)
    return 2 * s["attn"] * s["kvh"] * s["hd"] * s["item"]


def state_bytes_per_slot(cfg: dict) -> int:
    """Bytes of state one sequence carries, all state-space layers: the
    recurrent state in float32 and the convolution's last inputs."""
    s = _sizes(cfg)
    return s["ssm"] * (s["sh"] * s["sp"] * s["sn"] * 4
                       + (s["taps"] - 1) * s["conv"] * s["item"])


def tick_bytes(cfg: dict, rows: float, live_tokens: float,
               experts_touched: float) -> float:
    """The least one decode tick has to move: the weights outside the experts
    once (the head among them), the experts its rows touched (counted per
    layer), the attention layer's keys and values of every position the
    decoding rows attend to, and each decoding row's state read and
    written."""
    return (dense_bytes(cfg) + experts_touched * expert_bytes(cfg)
            + live_tokens * kv_bytes_per_token(cfg)
            + 2 * rows * state_bytes_per_slot(cfg))


def chunk_flops(cfg: dict, tokens: float, keys_visible: float,
                choices_held: float) -> float:
    """Operations of prefill over ``tokens`` positions: the products with the
    mixers', the attention's, the shared experts' and the routers' weights,
    the convolution's taps, the recurrence (a multiply-add a token into each
    element of the state and one out of it: the chunked form does more, which
    is its own business), the routed experts for the choices that fell on a
    held one, and attention over the keys each query sees
    (``keys_visible``, as the program counts them).  The head is left out: a
    request needs it at one position."""
    s = _sizes(cfg)
    per_token = (s["ssm"] * (s["mixer_mats"] + s["taps"] * s["conv"]
                             + 2 * s["sh"] * s["sp"] * s["sn"])
                 + s["attn"] * s["attn_params"]
                 + s["n"] * (s["shared_params"] + s["d"] * s["e_all"]))
    return (2.0 * tokens * per_token + 2.0 * choices_held * s["expert_params"]
            + 4.0 * keys_visible * s["h"] * s["hd"])
