"""Time the expert layer alone, at a configuration's own widths.

    python tools/expert_layer_sweep.py \\
        --config benchmark/configs/sdar-30b-a3b-chat.json --rows 512 \\
        [--touched 0.79] [--forms loop,grouped] [--seed 0] [--reps 10]

builds the layer ``latent_moe.held_experts`` serves for that configuration
(its family's ``model_config``: the widths, the router's rule, the experts
held) with random weights, draws ``rows`` tokens, routes them through the
configuration's own router and times each form of the layer over that one
load on the default device.  One JSON line a (rows, form) on stdout: the
milliseconds a call, the experts touched, the tiles in use, the bytes of the
touched experts over the time, and the largest difference from the first
form's outcome.  ``--touched`` leaves that share of the held experts
reachable (the others' router columns are zero, so no token's largest
logits are theirs): the cells' ticks touch 75-80 % of theirs.

The forms.  ``tree`` is ``held_experts`` as the tree decides it; ``in_place``,
``loop`` and ``grouped`` are its three forms called directly whatever
``IN_PLACE_ROWS`` says (``no_experts`` is the sorted forms with nothing
between the gather of rows and the gather back: what they cost beside the
experts), so the thresholds in ``latent_moe`` can be read
again (``--tile-rows`` puts a height in ``latent_moe.tile_rows``' place, 0
leaves it; ``--vmem-block-mib`` sets the kernel's block budget; each is a
comma-separated list, and the forms that read it are timed over it).
``ragged_dot`` and ``gmm`` are the two alternatives PR 46 measured the
grouped kernel against: the choices sorted by expert with no padding, three
``jax.lax.ragged_dot`` or three calls of
``jax.experimental.pallas.ops.tpu.megablox.gmm``.  No cell runs this.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import itertools
import json
import os
import statistics
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from horovod_tpu.models import grouped_experts  # noqa: E402
from horovod_tpu.models import latent_moe as lm  # noqa: E402


def model_config(conf: dict):
    """The served model's config for a configuration's file, by its family's
    own ``model_config`` (the engine's part, where it asks for one, from the
    first cell of ``BENCHMARK.json`` that runs the configuration)."""
    family = importlib.import_module(f"benchmark.families.{conf['family']}")
    kw = {}
    if "engine" in inspect.signature(family.model_config).parameters:
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            cell = next(w for w in json.load(f)["workloads"]
                        if w["config"] == conf["name"])
        with open(os.path.join(REPO, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            kw["engine"] = json.load(f)["engine"]
    return family.model_config(conf, max_len=2048, **kw)


def layer_params(cfg, key, touched: float) -> dict:
    """One expert layer's weights, random: the router ``[d, n_experts]`` at
    four times ``1/sqrt(d)`` (so the chosen logits stand clear of zero) with
    the columns of all but ``touched`` of the held experts zero, no bias, and
    the held experts' three matrices at ``1/sqrt(fan_in)``."""
    d, f, e = cfg.dim, cfg.expert_dim, cfg.held_count
    ks = jax.random.split(key, 5)
    dt = cfg.param_dtype

    def mat(k, fan_in, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    live = jnp.ones((cfg.n_experts,), bool).at[
        cfg.held_first + jax.random.permutation(ks[4], e)[
            :e - round(touched * e)]].set(False)
    router = 4.0 * d ** -0.5 * jax.random.normal(
        ks[0], (d, cfg.n_experts), jnp.float32)
    return {"w_router": jnp.where(live[None, :], router, 0.0).astype(dt),
            "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
            "e_gate": mat(ks[1], d, e, d, f), "e_up": mat(ks[2], d, e, d, f),
            "e_down": mat(ks[3], f, e, f, d)}


def _tiles(tiles, cfg, lp, h2, valid):
    return lm._experts_in_tiles(
        cfg, lp, h2, *lm.held_choices(cfg, lp, h2, valid), tiles)


def _in_place(cfg, lp, h2, valid):
    _, group, weights, load = lm.held_choices(cfg, lp, h2, valid)
    return lm._experts_in_place(cfg, lp, h2, group, weights, load)


def _sorted(product, cfg, lp, h2, valid):
    """The choices sorted by expert with no padding between the experts'
    segments, ``product(rows, weights [E, a, b], sizes [E])`` three times, and
    each token's outcomes gathered back and summed by rank."""
    dt = cfg.dtype
    n, k = h2.shape[0], cfg.top_k
    held, group, weights, load = lm.held_choices(cfg, lp, h2, valid)
    order = jnp.argsort(group, stable=True)     # the choices not held last
    place = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    x = h2[order // k]
    gate = product(x, lp["e_gate"].astype(dt), load)
    up = product(x, lp["e_up"].astype(dt), load)
    out = product(jax.nn.silu(gate) * up, lp["e_down"].astype(dt), load)
    picked = jnp.where(held[..., None], out[place.reshape(n, k)], 0)
    return jnp.sum(picked.astype(jnp.float32) * weights[..., None],
                   axis=1).astype(dt)


def _lanes_within(n: int, most: int) -> int:
    """The largest divisor of ``n`` in whole lanes that is at most ``most``."""
    return max(b for b in range(128, most + 1, 128) if n % b == 0)


def _gmm(x, w, sizes):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(x, w, sizes, preferred_element_type=x.dtype, tiling=(
        128, _lanes_within(w.shape[1], 2048), _lanes_within(w.shape[2], 1024)))


FORMS = {
    "tree": lambda cfg, lp, h2, valid: lm.held_experts(cfg, lp, h2, valid)[0],
    # the route, the sort, the two gathers and the sum with no expert between
    "no_experts": partial(_tiles, lambda cfg, lp, x_rows, seg_end, tile:
                          x_rows),
    "in_place": _in_place,
    "loop": partial(_tiles, lm._tiles_looped),
    "grouped": partial(_tiles, lm._tiles_grouped),
    "ragged_dot": partial(_sorted, lax.ragged_dot),
    "gmm": partial(_sorted, _gmm),
}


def time_ms(fn, *args, reps: int) -> float:
    """The median over five batches of ``reps`` calls handed to the device
    back to back, in milliseconds a call (the first, untimed, compiles)."""
    jax.block_until_ready(fn(*args))
    batches = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        batches.append((time.perf_counter() - t0) / reps * 1e3)
    return statistics.median(batches)


#: the forms that read the tile's height, and those that read the kernel's
#: budget
READS_TILE = ("tree", "no_experts", "loop", "grouped")
READS_VMEM = ("tree", "grouped")


def sweep(conf_path: str, rows: list, forms: list, touched: float, seed: int,
          reps: int, tile_rows: list, vmem_mib: list):
    """One line (a dict) for every form over every height of tile and budget
    it reads, for each count of rows."""
    with open(conf_path) as f:
        conf = json.load(f)
    cfg = model_config(conf)
    key = jax.random.key(seed)
    lp = layer_params(cfg, jax.random.fold_in(key, 0), touched)
    expert_bytes = 3 * cfg.dim * cfg.expert_dim * jnp.dtype(
        cfg.param_dtype).itemsize
    tile_rule, budget = lm.tile_rows, grouped_experts.VMEM_BLOCK_BYTES
    try:
        for n in rows:
            h2 = jax.random.normal(jax.random.fold_in(key, n), (n, cfg.dim),
                                   jnp.float32).astype(cfg.dtype)
            valid = jnp.ones((n,), bool)
            load = jax.jit(partial(lm.held_choices, cfg))(lp, h2, valid)[3]
            first, timed = None, set()
            for tile, mib, form in itertools.product(tile_rows, vmem_mib,
                                                     forms):
                reads = (form, tile if form in READS_TILE else None,
                         mib if form in READS_VMEM else None)
                if reads in timed:
                    continue
                timed.add(reads)
                lm.tile_rows = (lambda n, cfg, _t=tile: _t) if tile \
                    else tile_rule
                grouped_experts.VMEM_BLOCK_BYTES = mib * 2**20
                tile = lm.tile_rows(n, cfg)
                fn = jax.jit(partial(FORMS[form], cfg))
                case = {"config": conf["name"], "rows": n, "form": form,
                        "tile_rows": tile, "vmem_block_mib": mib}
                try:
                    ms = time_ms(fn, lp, h2, valid, reps=reps)
                except Exception as e:  # a form the compiler refuses here
                    yield {**case, "error": str(e)[:400]}
                    continue
                y = fn(lp, h2, valid).astype(jnp.float32)
                first = y if first is None else first
                n_touched = int(jnp.sum(load > 0))
                yield {
                    **case, "ms": round(ms, 4), "held": cfg.held_count,
                    "top_k": cfg.top_k, "d": cfg.dim, "f": cfg.expert_dim,
                    "choices_held": int(jnp.sum(load)), "touched": n_touched,
                    "tiles": int(jnp.sum(-(-load // tile))),
                    "f_block": grouped_experts.f_block(
                        cfg.dim, cfg.expert_dim, tile,
                        jnp.dtype(cfg.dtype).itemsize),
                    "expert_mb": round(expert_bytes / 1e6, 2),
                    "touched_gb_s": round(
                        n_touched * expert_bytes / ms / 1e6, 1),
                    f"max_abs_vs_{forms[0]}": float(
                        jnp.max(jnp.abs(y - first))),
                    "out_rms": float(jnp.sqrt(jnp.mean(y * y))),
                    "device": jax.devices()[0].device_kind}
    finally:
        lm.tile_rows = tile_rule
        grouped_experts.VMEM_BLOCK_BYTES = budget


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a configuration's file under benchmark/configs/")
    ap.add_argument("--rows", default="512",
                    help="tokens in the program, comma-separated")
    ap.add_argument("--forms", default="loop,grouped",
                    help=f"comma-separated, of {', '.join(FORMS)}")
    ap.add_argument("--touched", type=float, default=1.0,
                    help="share of the held experts the router can reach")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tile-rows", default="0")
    ap.add_argument("--vmem-block-mib",
                    default=str(grouped_experts.VMEM_BLOCK_BYTES // 2**20))

    def ints(text):
        return [int(x) for x in text.split(",")]

    args = ap.parse_args(argv)
    for line in sweep(args.config, ints(args.rows), args.forms.split(","),
                      args.touched, args.seed, args.reps,
                      ints(args.tile_rows), ints(args.vmem_block_mib)):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
