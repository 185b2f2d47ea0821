"""Programs of the serving path, and the data-parallel train step, compiled
at real widths for a described TPU v5e, with no chip: what interpret-mode and
CPU tests cannot see.  The topology is described inside a fixture (never
while a module is imported), in this one file, and the tests skip where it
cannot be described."""

import math
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import SingleDeviceSharding

from horovod_tpu.models import latent_moe as lm
from horovod_tpu.models import shortconv_moe as sm

#: scratch of the third model's tick while its experts ran in sorted tiles
#: (PR 31's program, compiled the same way)
PARENT_TICK_TEMP_BYTES = 161_400_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _mosaic_not_the_interpreter():
    """The suite's session traces Pallas kernels for the interpreter
    (``tests/conftest.py``); what is compiled here for the described chip
    holds the Mosaic kernels a chip would run."""
    from horovod_tpu.parallel.flash_attention import interpret_mode

    with interpret_mode(False):
        yield


@pytest.fixture(scope="module")
def no_compile_cache():
    """Such a compile is written to the persistent cache and cannot be read
    back without a chip: keep it off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _benchmark_json(*path) -> dict:
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", *path)) as f:
        return json.load(f)


def _avals(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


@pytest.fixture(scope="module")
def shortconv_compiled(one_chip, no_compile_cache):
    """``compiled(program)`` of the third model's tick and chunk at the
    cell's own size (128 slots of 2048 over 1,041 blocks of 256), each
    compiled once, and the cache's shapes."""
    cfg = sm.ShortConvMoEConfig()
    n_slots = 128
    assert cfg.kv_pack == 2
    params = _avals(jax.eval_shape(
        lambda: sm.init_params(cfg, jax.random.key(0))), one_chip)
    cache = _avals(jax.eval_shape(lambda: sm.init_paged_cache(
        cfg, n_slots, 2048, block_size=256, n_blocks=1041)), one_chip)
    logits = jax.ShapeDtypeStruct((n_slots, cfg.vocab_size), jnp.float32,
                                  sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    @partial(jax.jit, donate_argnums=(1, 2))
    def tick(params, pcache, last_logits, active):
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        out, pcache = sm.decode_chunk_paged(params, tok[:, None], cfg, pcache,
                                            advance=active)
        return tok, out[:, 0], pcache

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk(params, pcache, last_logits, toks, slot, new_len, sel):
        out, pcache = sm.decode_chunk_paged_row(params, toks, cfg, pcache,
                                                slot, new_length=new_len)
        return pcache, last_logits.at[slot].set(out[0, sel])

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk_rows(params, pcache, last_logits, toks, slots, new_len, sel):
        out, pcache = sm.decode_chunk_paged_rows(
            params, toks, cfg, pcache, slots, new_length=new_len, sel=sel)
        return pcache, last_logits.at[slots].set(out, mode="drop")

    rows = jax.ShapeDtypeStruct((WIDEST,), jnp.int32, sharding=one_chip)
    lowered = {
        "tick": lambda: tick.lower(params, cache, logits, jax.ShapeDtypeStruct(
            (n_slots,), jnp.int32, sharding=one_chip)),
        "chunk": lambda: chunk.lower(
            params, cache, logits, jax.ShapeDtypeStruct(
                (1, 256), jnp.int32, sharding=one_chip), i32, i32, i32),
        "chunk_rows": lambda: chunk_rows.lower(
            params, cache, logits, jax.ShapeDtypeStruct(
                (WIDEST, 256), jnp.int32, sharding=one_chip), rows, rows,
            rows)}
    done = {}

    def compiled(program):
        if program not in done:
            done[program] = lowered[program]().compile()
        return done[program]

    return compiled, cache


#: the widest chunk program ``lfm2_batchgen``'s engine holds: 2,048 tokens a
#: program (``serving_scheduler._CHUNK_TOKENS``) over its chunk of 256
WIDEST = 8


def test_the_widest_chunk_holds_no_second_pool_and_one_position_s_logits(
        shortconv_compiled):
    """The chunk program of eight rows at the cell's size compiles for the
    chip; its scratch is under half a pool, as the one-row program's, and
    under the 537 MB that the logits of every position would be: the head is
    asked one position a row, and nothing ``[R, T, V]`` stands in the
    program."""
    from horovod_tpu import serving_scheduler

    assert WIDEST == serving_scheduler._CHUNK_TOKENS // 256
    compiled, cache = shortconv_compiled
    program = compiled("chunk_rows")
    mem = program.memory_analysis()
    pool = cache.k.size * cache.k.dtype.itemsize
    assert mem.temp_size_in_bytes < pool // 2, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 2 * pool
    assert mem.temp_size_in_bytes < WIDEST * 256 * 65536 * 4
    text = program.as_text()
    for every_position in (f"[{WIDEST},256,65536]", f"[{WIDEST * 256},65536]"):
        assert every_position not in text


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_shortconv_programs_hold_no_second_pool(shortconv_compiled, program):
    """With key rows of 64 lanes every attention layer's scatter copied the
    pool twice (5.8 GB of scratch); packed to 128 (``kv_pack``) a program's
    scratch is a fraction of one pool."""
    compiled, cache = shortconv_compiled
    mem = compiled(program).memory_analysis()
    pool = cache.k.size * cache.k.dtype.itemsize
    assert pool == 818_675_712
    assert mem.temp_size_in_bytes < pool // 2, mem.temp_size_in_bytes
    # weights and cache are arguments held once, the cache aliased in place
    assert mem.alias_size_in_bytes >= 2 * pool
    assert mem.argument_size_in_bytes < 11.2e9


def _computations(hlo: str) -> dict:
    """Each computation of an optimised HLO module's text by name."""
    out, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return {k: "\n".join(v) for k, v in out.items()}


def _reaches(comps: dict, name: str, wanted, seen: set) -> bool:
    """Whether computation ``name``, or one it calls, holds a line that
    ``wanted`` matches."""
    if name in seen:
        return False
    seen.add(name)
    body = comps[name]
    return bool(wanted.search(body)) or any(
        _reaches(comps, ref, wanted, seen)
        for ref in re.findall(r"%([\w.\-]+)", body) if ref in comps)


_A_WHILE = re.compile(r" while\(")


def test_the_shortconv_tick_computes_its_experts_in_place(shortconv_compiled):
    """A tick of 128 rows is within :data:`latent_moe.IN_PLACE_ROWS`: its
    twelve expert layers sort, gather and scatter no choice, so no loop of
    theirs holds a second loop (the tiles' loop searched its expert with
    one): the only loops that do are the attention layers' walks, one each,
    over the groups of rows (128 rows over a table of four tiles: four groups
    of 32).  The only sorts left are the router's ``top_k`` and the walk's
    order of the rows, once a program, and the scratch is no larger than the
    sorted tiles' was."""
    cfg = sm.ShortConvMoEConfig()
    assert lm.rows_in_place(128)
    compiled, _ = shortconv_compiled
    hlo = compiled("tick").as_text()
    comps = _computations(hlo)
    loops = re.findall(r"^.* while\(.*?body=%([\w.\-]+).*$", hlo, re.M)
    assert loops and all(b in comps for b in loops)
    nested = [line for line in hlo.splitlines() if " while(" in line
              and _reaches(comps, re.search(
                  r"body=%([\w.\-]+)", line).group(1), _A_WHILE, set())]
    assert len(nested) == sum(k == sm.ATTN for k in cfg.layer_kinds)
    assert all("attn.gqa" in line for line in nested), nested
    sorts = [line for line in hlo.splitlines() if " sort(" in line]
    assert len(sorts) == cfg.n_layers - cfg.first_dense + 1
    assert sum("moe.route/top_k" in line for line in sorts) == len(sorts) - 1
    assert compiled("tick").memory_analysis().temp_size_in_bytes \
        <= PARENT_TICK_TEMP_BYTES


def _grouped_products(hlo: str) -> list:
    """The Mosaic calls of the expert layers' grouped product in an optimised
    module's text."""
    return [line for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and "moe.experts/grouped_swiglu" in line]


def _assert_the_experts_are_one_grouped_product(hlo, cfg, e_gate_slice):
    """One kernel call an expert layer, compiled by Mosaic (the interpreter's
    expansion is a loop of its own and no custom call), and no loop left that
    looks an expert up or slices one out of ``e_gate``: what every trip of
    the loop over the tiles did."""
    assert len(_grouped_products(hlo)) == cfg.n_layers - cfg.first_dense
    comps = _computations(hlo)
    per_trip = re.compile(
        r"searchsorted| dynamic-slice\(.*dynamic_slice_sizes="
        + re.escape(e_gate_slice))
    for line in hlo.splitlines():
        if " while(" in line:
            assert "moe.experts" not in line, line
            body = re.search(r"body=%([\w.\-]+)", line).group(1)
            assert not _reaches(comps, body, per_trip, set()), line


#: scratch of dots3's chunk of 512 tokens and of sdar's block tick at their
#: cells' sizes on the parent of PR 46 (the loop over the tiles), compiled
#: the same way
PARENT_CHUNK_TEMP_BYTES = {"dots3": 979_840_512, "sdar": 326_099_456}


@pytest.fixture(scope="module")
def latent_chunk_compiled(one_chip, no_compile_cache):
    """The second model's chunk of 512 tokens at the published widths and
    ``dots3_longdoc32k``'s size (8 slots of 32,768 over blocks of 512),
    compiled once."""
    cfg = lm.LatentMoEConfig()
    e = _benchmark_json("traffic", "longdoc32k.json")["engine"]
    n_slots, chunk_len = e["n_slots"], e["chunk"]
    params = _avals(jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.key(0))), one_chip)
    cache = _avals(jax.eval_shape(lambda: lm.init_paged_cache(
        cfg, n_slots, e["max_len"], block_size=chunk_len)), one_chip)
    logits = jax.ShapeDtypeStruct((n_slots, cfg.vocab_size), jnp.float32,
                                  sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk(params, pcache, last_logits, toks, slot, new_len, sel):
        out, pcache = lm.decode_chunk_paged_row(
            params, toks, cfg, pcache, slot, new_length=new_len)
        return pcache, last_logits.at[slot].set(out[0, sel])

    return chunk.lower(params, cache, logits, jax.ShapeDtypeStruct(
        (1, chunk_len), jnp.int32, sharding=one_chip), i32, i32,
        i32).compile(), cfg


def test_a_chunk_of_many_rows_holds_one_grouped_product_an_expert_layer(
        latent_chunk_compiled):
    """The second model's chunk of 512 tokens is over
    :data:`latent_moe.IN_PLACE_ROWS`: compiled for the chip, each of its four
    expert layers sorts its choices into tiles and hands them to one Mosaic
    call; the loop over the tiles, with its search for the tile's expert and
    its slices of one expert's matrices a trip, is gone, and the scratch is
    no larger than the loop's was."""
    program, cfg = latent_chunk_compiled
    assert lm.rows_grouped(512, cfg.dim, cfg.expert_dim)
    _assert_the_experts_are_one_grouped_product(
        program.as_text(), cfg, "{1,%d,%d}" % (cfg.dim, cfg.expert_dim))
    assert program.memory_analysis().temp_size_in_bytes \
        <= PARENT_CHUNK_TEMP_BYTES["dots3"]


#: what one v5e chip leaves a program: 15.75 GiB less the runtime's 258 MiB
V5E_USABLE_BYTES = (15.75 * 1024 - 258) * 2**20


def _mixedq_engine() -> dict:
    """The engine of the one cell that serves the fourth model, from its
    traffic file: the size is written down there alone."""
    return _benchmark_json("traffic", "mixedq.json")["engine"]


@pytest.fixture(scope="module")
def window_compiled(one_chip, no_compile_cache):
    """``compiled(program)`` of the fourth model's tick, chunk and table write
    at the cell's own size (``kexaone_mixedq``: 64 slots of 32,768 over
    blocks of 1,024, the chunk's size), each compiled once, and the cache's
    shapes."""
    from horovod_tpu.models import window_moe as wm

    cfg = wm.WindowMoEConfig()
    e = _mixedq_engine()
    n_slots, chunk_len = e["n_slots"], e["chunk"]
    params = _avals(jax.eval_shape(
        lambda: wm.init_params(cfg, jax.random.key(0))), one_chip)
    cache = _avals(jax.eval_shape(lambda: wm.init_paged_cache(
        cfg, n_slots, e["max_len"], block_size=chunk_len,
        n_blocks=e["n_blocks"])), one_chip)
    logits = jax.ShapeDtypeStruct((n_slots, cfg.vocab_size), jnp.float32,
                                  sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    @partial(jax.jit, donate_argnums=(1, 2))
    def tick(params, pcache, last_logits, active):
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        out, pcache = wm.decode_chunk_paged(params, tok[:, None], cfg, pcache,
                                            advance=active)
        return tok, out[:, 0], pcache

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk(params, pcache, last_logits, toks, slot, new_len, sel):
        out, pcache = wm.decode_chunk_paged_row(params, toks, cfg, pcache,
                                                slot, new_length=new_len)
        return pcache, last_logits.at[slot].set(out[0, sel])

    set_row = jax.jit(wm.set_row, donate_argnums=(0,))
    lowered = {
        "tick": lambda: tick.lower(params, cache, logits, jax.ShapeDtypeStruct(
            (n_slots,), jnp.int32, sharding=one_chip)),
        "chunk": lambda: chunk.lower(
            params, cache, logits, jax.ShapeDtypeStruct(
                (1, chunk_len), jnp.int32, sharding=one_chip), i32, i32, i32),
        "set_row": lambda: set_row.lower(cache, i32, jax.ShapeDtypeStruct(
            (e["max_len"] // chunk_len,), jnp.int32, sharding=one_chip),
            i32)}
    done = {}

    def compiled(program):
        if program not in done:
            done[program] = lowered[program]().compile()
        return done[program]

    return compiled, cache


@pytest.mark.parametrize("program", ["tick", "chunk", "set_row"])
def test_window_programs_fit_the_chip_and_hold_no_second_pool(
        window_compiled, program):
    """11.96 GB of weights, the pools with their snapshots and the rings are
    held once and written in place, and the programs' scratch beside them
    leaves 0.3 GB of the chip: the full layers walk blocks of 1,024 in pieces
    of 256 (``window_moe.GATHER_ROWS``; gathered whole, each step of the walk
    copied the keys' pool), and a block's snapshot is written in a loop over
    the ends reached, not gathered a row."""
    compiled, cache = window_compiled
    mem = compiled(program).memory_analysis()
    state = sum(a.size * a.dtype.itemsize for a in cache)
    pool = cache.k.size * cache.k.dtype.itemsize
    n_blocks = cache.k.shape[1]
    assert cache.k.shape == (2, n_blocks, 1024, 8, 128)
    assert cache.ring.shape == (2, 6, 64, 128, 8, 128)
    assert cache.snap.shape == (2, 6, n_blocks, 128, 8, 128)
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < pool // 4, mem.temp_size_in_bytes
    spare = V5E_USABLE_BYTES - mem.argument_size_in_bytes \
        - mem.temp_size_in_bytes
    assert spare > 0.3e9, spare


def test_the_cells_pool_is_what_the_chip_leaves(window_compiled):
    """The cell's rule for its pool: what is left beside the weights, the
    rings and the largest program's scratch with 0.3 GB to spare, and no
    less: eight more blocks (of keys, values and snapshot) would not fit
    under it."""
    compiled, cache = window_compiled
    block = sum(a.size // a.shape[a.shape.index(cache.k.shape[1])]
                * a.dtype.itemsize for a in (cache.k, cache.v, cache.snap))
    assert block == 2 * 2 * 1024 * 8 * 128 * 2 + 2 * 6 * 128 * 8 * 128 * 2
    spare = min(V5E_USABLE_BYTES - mem.argument_size_in_bytes
                - mem.temp_size_in_bytes
                for mem in (compiled(p).memory_analysis()
                            for p in ("tick", "chunk")))
    assert 0.3e9 < spare < 0.3e9 + 8 * block, spare


def _toolcalls_engine() -> dict:
    """The engine of the one cell that serves the fifth model, from its
    traffic file: the size is written down there alone."""
    return _benchmark_json("traffic", "toolcalls.json")["engine"]


@pytest.fixture(scope="module")
def state_space_compiled(one_chip, no_compile_cache):
    """``compiled(program)`` of the fifth model's tick, chunk and table write
    at the cell's own size (``granite_toolcalls``: 64 slots of 9,216 over
    blocks of 1,024 and a budget of 24 snapshots), each compiled once, and
    the cache's shapes."""
    from horovod_tpu.models import state_space_moe as ssm

    e = _toolcalls_engine()
    cfg = ssm.StateSpaceMoEConfig(snapshots=e["snapshots"],
                                  max_seq_len=e["max_len"])
    n_slots, chunk_len, bs = e["n_slots"], e["chunk"], e["block_size"]
    params = _avals(jax.eval_shape(
        lambda: ssm.init_params(cfg, jax.random.key(0))), one_chip)
    cache = _avals(jax.eval_shape(lambda: ssm.init_paged_cache(
        cfg, n_slots, e["max_len"], block_size=bs,
        n_blocks=e["n_blocks"])), one_chip)
    logits = jax.ShapeDtypeStruct((n_slots, cfg.vocab_size), jnp.float32,
                                  sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((e["max_len"] // bs,), jnp.int32,
                               sharding=one_chip)

    @partial(jax.jit, donate_argnums=(1, 2))
    def tick(params, pcache, last_logits, active):
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        out, pcache = ssm.decode_chunk_paged(params, tok[:, None], cfg,
                                             pcache, advance=active)
        return tok, out[:, 0], pcache

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk(params, pcache, last_logits, toks, slot, new_len, sel):
        out, pcache = ssm.decode_chunk_paged_row(params, toks, cfg, pcache,
                                                 slot, new_length=new_len)
        return pcache, last_logits.at[slot].set(out[0, sel])

    set_row = jax.jit(ssm.set_row, donate_argnums=(0,))
    lowered = {
        "tick": lambda: tick.lower(params, cache, logits, jax.ShapeDtypeStruct(
            (n_slots,), jnp.int32, sharding=one_chip)),
        "chunk": lambda: chunk.lower(
            params, cache, logits, jax.ShapeDtypeStruct(
                (1, chunk_len), jnp.int32, sharding=one_chip), i32, i32, i32),
        "set_row": lambda: set_row.lower(cache, i32, row, i32, row)}
    done = {}

    def compiled(program):
        if program not in done:
            done[program] = lowered[program]().compile()
        return done[program]

    return compiled, cache


@pytest.mark.parametrize("program", ["tick", "chunk", "set_row"])
def test_state_space_programs_fit_the_chip_and_hold_no_second_state(
        state_space_compiled, program):
    """9.51 GB of weights, 2.44 GB of slots' states, the pools and the
    snapshot entries are held once and written in place, and the programs'
    scratch beside them leaves 0.3 GB of the chip: the tick updates every
    slot's state where it stands, layer by layer; a chunk reads and writes
    its one row's as a slice (scattered by an index array, the chunk copied
    all 64 slots' states first: 2.25 GB of scratch)."""
    compiled, cache = state_space_compiled
    mem = compiled(program).memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in cache)
    states = cache.ssm.size * cache.ssm.dtype.itemsize
    assert cache.ssm.shape == (9, 64, 128, 64, 128)
    assert cache.snap_ssm.shape[:2] == (9, _toolcalls_engine()["snapshots"])
    assert cache.snap_ssm.shape[1] * 20 < cache.k.shape[1]  # no snapshot a block
    assert mem.alias_size_in_bytes >= held
    # under half of the slots' states, and under the snapshot entries'
    assert mem.temp_size_in_bytes < states // 2, mem.temp_size_in_bytes
    assert mem.temp_size_in_bytes < cache.snap_ssm.size * 4
    spare = V5E_USABLE_BYTES - mem.argument_size_in_bytes \
        - mem.temp_size_in_bytes
    assert spare > 0.3e9, spare


def _run_twice(text: str, shape: tuple) -> list:
    """The instructions of a compiled program's entry computation whose
    result has ``shape`` and which the compiler rematerialised beside the
    original (``<name>.remat`` with ``<name>`` still there): run twice."""
    entry = text[text.index("ENTRY"):]
    names = set(re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = ", entry, re.M))
    dims = ",".join(str(d) for d in shape)
    clones = re.findall(
        r"^\s+%([\w.\-]+?)\.remat\d* = \w+\[" + re.escape(dims) + r"\]",
        entry, re.M)
    return [c for c in clones if c in names]


@pytest.mark.parametrize("program", ["tick", "chunk", "set_row"])
def test_no_state_space_program_updates_the_state_in_place_twice(
        state_space_compiled, program):
    """An update of the state where it stands is no value to compute again:
    XLA:TPU's rematerialisation cloned the first layer's read-modify-write of
    the slots' states in the tick at this size (the clone read what the
    original had already written: every tick advanced that layer's state
    twice; found on the chip by the benchmark's state probes, PERF.md, PR
    38).  Since then a tick advances all the layers' states in one pass at
    its end, and no compiled program may hold such a pair."""
    compiled, cache = state_space_compiled
    text = compiled(program).as_text()
    assert "ENTRY" in text and re.search(r"%pcache_ssm[\w.]* = f32\[", text)
    for a in (cache.ssm, cache.snap_ssm):
        assert _run_twice(text, a.shape) == []
    if program == "tick":
        # one reader a layer (``S C``) and one read-modify-write of it all
        entry = text[text.index("ENTRY"):]
        writes = re.findall(
            r"^\s+%[\w.\-]+ = f32\[9,64,128,64,128\]\S* fusion\(", entry,
            re.M)
        assert len(writes) == 1, writes


def _route_as_it_was(cfg, lp, h2):
    """``latent_moe.route`` before it had a second rule (PR 37), kept to
    compare lowerings with."""
    s = jax.nn.sigmoid(jnp.dot(h2.astype(jnp.float32),
                               lp["w_router"].astype(jnp.float32)))
    _, experts = lax.top_k(s + lp["router_bias"], cfg.top_k)
    picked = jnp.take_along_axis(s, experts, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    if cfg.route_norm_eps:
        total = total + cfg.route_norm_eps
    return experts, picked / total * cfg.routed_scale


def _lowered(mod, cfg, n_slots, max_len, chunk_len, n_blocks) -> dict:
    """The text of a served model's tick, one-row chunk and table write as
    they are traced now, lowered for the TPU platform (a chunk over
    :data:`latent_moe.IN_PLACE_ROWS` holds a Mosaic kernel, which has no
    lowering for the host); nothing is compiled."""
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: mod.init_paged_cache(
        cfg, n_slots, max_len, block_size=chunk_len, n_blocks=n_blocks))
    logits = jax.ShapeDtypeStruct((n_slots, cfg.vocab_size), jnp.float32)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    row = jax.ShapeDtypeStruct((max_len // chunk_len,), jnp.int32)

    @partial(jax.jit, donate_argnums=(1, 2))
    def tick(params, pcache, last_logits, active):
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        out, pcache = mod.decode_chunk_paged(
            params, tok[:, None], cfg, pcache, advance=active)
        return out[:, 0], pcache

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk(params, pcache, last_logits, toks, slot, new_len, sel):
        out, pcache = mod.decode_chunk_paged_row(
            params, toks, cfg, pcache, slot, new_length=new_len)
        return pcache, last_logits.at[slot].set(out[0, sel])

    set_row = jax.jit(mod.set_row, donate_argnums=(0,))

    def text(fn, *args):
        return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()

    return {
        "tick": text(tick, params, cache, logits, jax.ShapeDtypeStruct(
            (n_slots,), jnp.int32)),
        "chunk": text(chunk, params, cache, logits, jax.ShapeDtypeStruct(
            (1, chunk_len), jnp.int32), i32, i32, i32),
        "set_row": text(set_row, cache, i32, row, i32)}


@pytest.mark.parametrize("model", ["shortconv", "window"])
def test_the_snapshot_rules_two_users_lower_to_the_programs_they_were(
        model, monkeypatch):
    """lfm2's and K-EXAONE's tick, chunk and table write at their cells'
    sizes are, letter for letter, the programs they were before the router
    had a second rule; their ``set_row`` keeps its four arguments (the
    engine jits the five-argument form only for a model that has a
    ``snapshot_budget``).  (Lowered, not compiled.)"""
    from horovod_tpu.models import window_moe as wm

    if model == "shortconv":
        mod, cfg = sm, sm.ShortConvMoEConfig()
        size = (128, 2048, 256, 1041)
    else:
        mod, cfg = wm, wm.WindowMoEConfig()
        e = _mixedq_engine()
        size = (e["n_slots"], e["max_len"], e["chunk"], e["n_blocks"])
    assert not hasattr(mod, "snapshot_budget")
    now = _lowered(mod, cfg, *size)
    monkeypatch.setattr(lm, "route", _route_as_it_was)
    assert now == _lowered(mod, cfg, *size)


def _held_experts_as_it_was(cfg, lp, h2, valid):
    """``latent_moe.held_experts`` for a program within
    :data:`latent_moe.IN_PLACE_ROWS` as it was before a longer program's
    tiles went through one grouped product (PR 45), kept to compare
    lowerings with."""
    n = h2.shape[0]
    e = cfg.held_count
    assert n <= lm.IN_PLACE_ROWS
    with jax.named_scope("moe.route"):
        experts, weights = lm.route(cfg, lp, h2)
        local = experts - cfg.held_first
        held = (local >= 0) & (local < e) & valid[:, None]
        group = jnp.where(held, local, e).reshape(n * cfg.top_k)
        load = jnp.sum(jax.nn.one_hot(group, e + 1, dtype=jnp.int32),
                       axis=0)[:e]
    return lm._experts_in_place(cfg, lp, h2, group, weights, load), load


@pytest.mark.parametrize("model", ["shortconv", "window"])
def test_programs_within_in_place_rows_lower_to_the_programs_they_were(
        model, monkeypatch):
    """A tick of 128 rows and a one-row chunk of 256 tokens compute their
    experts in place and hold no grouped product: letter for letter the
    programs they were before the sorted tiles had one, in the third model
    and in the fourth.  (Lowered, not compiled.)"""
    from horovod_tpu.models import window_moe as wm

    mod, cfg = ((sm, sm.ShortConvMoEConfig()) if model == "shortconv"
                else (wm, wm.WindowMoEConfig()))
    size = (128, 2048, 256, 1041)
    assert lm.rows_in_place(128) and lm.rows_in_place(256)
    now = _lowered(mod, cfg, *size)
    assert not any("grouped_swiglu" in text for text in now.values())
    monkeypatch.setattr(lm, "held_experts", _held_experts_as_it_was)
    assert now == _lowered(mod, cfg, *size)


# -- the Mistral programs read wq / wk / wv inside one product (PR 42) -------

#: scratch of ``mistral7b_chat``'s programs on the public tree (three
#: products a layer, each from a staged and transposed slice), compiled the
#: same way from the parent of PR 42
PARENT_LLAMA_TEMP_BYTES = {"tick": 1_248_256, "chunk": 1_032_704,
                           "chunk_rows": 60_564_992}

#: a top-level op that hands on one layer's slice of a stacked q / k / v
#: weight: the staged ``dynamic-slice`` fusion and the transposing ``copy``
#: (``bf16[1,4096,n]``, or ``[1,4096,tp,n]`` of the fused tree)
_WEIGHT_SLICE_OP = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = bf16\[1,4096(?:,\d+){1,2}\]\S* "
    r"(?:copy|fusion)\(", re.M)


@pytest.fixture(scope="module")
def llama_compiled(one_chip, no_compile_cache):
    """``compiled(program, tree)`` of ``mistral7b_chat``'s tick, one-row chunk
    and wide chunk at the cell's own size (24 layers, 20 slots of 1280 over
    blocks of 256, bf16), as ``ServeEngine`` jits them, over the ``"serving"``
    tree (``llama.serving_params``) or the ``"public"`` one (for a caller
    that has put a projection that reads it in ``llama._qkv_heads``' place);
    each compiled once.  And the cache's shapes."""
    from horovod_tpu import serving_scheduler
    from horovod_tpu.models import llama

    c = _benchmark_json("configs", "mistral-7b-v0.3.json")
    e = _benchmark_json("traffic", "chat.json")["engine"]
    cfg = llama.LlamaConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], ffn_dim=c["intermediate_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        max_seq_len=e["max_len"], dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, attn_impl="dense", remat=False)
    n_slots, chunk_len = e["n_slots"], e["chunk"]
    wide = min(n_slots, serving_scheduler._CHUNK_TOKENS // chunk_len)
    public = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    trees = {"public": _avals(public, one_chip),
             "serving": _avals(jax.eval_shape(
                 lambda p: llama.serving_params(p, cfg, tp_size=1), public),
                 one_chip)}
    cache = _avals(jax.eval_shape(lambda: llama.init_paged_cache(
        cfg, n_slots, e["max_len"], block_size=chunk_len)), one_chip)
    logits = jax.ShapeDtypeStruct((n_slots, cfg.vocab_size), jnp.float32,
                                  sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    @partial(jax.jit, donate_argnums=(1, 2))
    def tick(params, pcache, last_logits, active):
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        out, pcache = llama.decode_chunk_paged(params, tok[:, None], cfg,
                                               pcache, advance=active)
        return out[:, 0], pcache

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk(params, pcache, last_logits, toks, slots, new_len, sel):
        out, pcache = llama.decode_chunk_paged_rows(
            params, toks, cfg, pcache, slots, new_length=new_len, sel=sel)
        return pcache, last_logits.at[slots].set(out, mode="drop")

    rows = {"chunk": 1, "chunk_rows": wide}
    done = {}

    def compiled(program, tree="serving"):
        if (program, tree) not in done:
            params = trees[tree]
            if program == "tick":
                low = tick.lower(params, cache, logits, i32(n_slots))
            else:
                r = rows[program]
                low = chunk.lower(params, cache, logits, i32(r, chunk_len),
                                  i32(r), i32(r), i32(r))
            done[program, tree] = low.compile()
        return done[program, tree]

    return compiled, cache


def _top_level(hlo: str) -> str:
    """The text of an optimised module outside its fused computations: the
    ops the device runs one after another."""
    return "\n".join(body for name, body in _computations(hlo).items()
                     if not name.startswith("fused_computation"))


@pytest.mark.parametrize("program", ["tick", "chunk", "chunk_rows"])
def test_the_mistral_layer_loop_stages_and_transposes_no_qkv_weight(
        llama_compiled, program):
    """On the serving tree the layer loop hands no slice of a q / k / v
    weight from op to op: the ``dynamic-slice`` sits inside the one product's
    fusion, which streams ``wqkv`` from HBM as ``wo`` and the MLP's products
    stream theirs.  (A product whose output is reshaped into heads directly
    brings the staged slice and the transposing ``copy`` back:
    ``llama._qkv_heads``.)"""
    compiled, _ = llama_compiled
    hlo = compiled(program).as_text()
    assert not _WEIGHT_SLICE_OP.findall(_top_level(hlo))
    # one product reads the stacked wqkv, with the slice inside its fusion
    reads = [line for line in hlo.splitlines()
             if re.search(r"dynamic-slice\(.*\bdynamic_slice_sizes="
                          r"\{1,4096,1,6144\}", line)]
    assert len(reads) == 1
    products = [name for name, body in _computations(hlo).items()
                if name.startswith("fused_computation")
                and "btd,dsn->btsn/dot_general" in body
                and " convolution(" in body]
    assert len(products) == 1, products
    assert len(re.findall(rf"kind=kOutput, calls=%{re.escape(products[0])}\b",
                          hlo)) == 1


def test_the_detector_finds_the_public_tree_s_three_slices_and_copies(
        llama_compiled, monkeypatch):
    """What the test above rules out is what the same tick compiles to with
    the projection as it was, the public tree's three products each reshaped
    into heads: a staged slice and a transposing copy each for ``wq``,
    ``wk`` and ``wv``, a layer."""
    from horovod_tpu.models import llama

    def three_products(h, lp, cfg):
        b, t, _ = h.shape
        return tuple(
            (h @ lp[name].astype(cfg.dtype)).reshape(b, t, n, cfg.head_dim)
            for name, n in (("wq", cfg.n_heads), ("wk", cfg.n_kv_heads),
                            ("wv", cfg.n_kv_heads)))

    monkeypatch.setattr(llama, "_qkv_heads", three_products)
    compiled, _ = llama_compiled
    found = _WEIGHT_SLICE_OP.findall(_top_level(
        compiled("tick", "public").as_text()))
    assert len(found) == 6, found


@pytest.mark.parametrize("program", ["tick", "chunk", "chunk_rows"])
def test_the_mistral_programs_hold_one_pool_and_no_more_scratch(
        llama_compiled, program):
    """The pool is an argument aliased in place and the scratch is the
    parent's or less; the tick may hold the fused product's output (20 rows
    of 6,144) beside its three slices, which the three products had not."""
    compiled, cache = llama_compiled
    mem = compiled(program).memory_analysis()
    pool = cache.k.size * cache.k.dtype.itemsize
    assert pool == 1_270_874_112
    assert mem.alias_size_in_bytes >= 2 * pool
    room = 20 * 6144 * 2 if program == "tick" else 0
    assert mem.temp_size_in_bytes <= PARENT_LLAMA_TEMP_BYTES[program] + room
    # the weights once: 11.0 GB with the pool's 2.54, not 12.2
    assert mem.argument_size_in_bytes < 13.7e9


def _blockgen_engine() -> dict:
    """The engine of the one cell that serves the sixth model, from its
    traffic file: the size is written down there alone."""
    return _benchmark_json("traffic", "blockgen.json")["engine"]


@pytest.fixture(scope="module")
def block_diffusion_compiled(one_chip, no_compile_cache):
    """``compiled(program)`` of the sixth model's three kinds of program at
    the cell's own size (``sdar_blockgen``: 128 slots of 2,048 over 1,029
    pages of 256): the unmask program in front, the block tick, and the chunk
    at its wide width of 8 rows and at one, each compiled once."""
    from horovod_tpu.models import block_diffusion_moe as bd

    e = _blockgen_engine()
    cfg = bd.BlockDiffusionMoEConfig(
        denoising_steps=e["denoising_steps"], remasking=e["remasking"],
        confidence_threshold=e["confidence_threshold"])
    n_slots, chunk_len, b = e["n_slots"], e["chunk"], cfg.block_length
    params = _avals(jax.eval_shape(
        lambda: bd.init_params(cfg, jax.random.key(0))), one_chip)
    cache = _avals(jax.eval_shape(lambda: bd.init_paged_cache(
        cfg, n_slots, e["max_len"], block_size=chunk_len,
        n_blocks=e["n_blocks"])), one_chip)
    sds = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(   # noqa: E731
        shape, dt, sharding=one_chip)
    block_logits = sds((n_slots, b, cfg.vocab_size), jnp.float32)
    last_logits = sds((n_slots, cfg.vocab_size), jnp.float32)

    @jax.jit
    def unmask(block_logits, tokens, step):
        return bd.unmask(cfg, block_logits, tokens, step)

    @partial(jax.jit, donate_argnums=(1, 2))
    def tick(params, pcache, block_logits, tokens, active, commit):
        return bd.decode_block_paged(params, tokens, cfg, pcache,
                                     active=active, commit=commit)

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk(params, pcache, last_logits, toks, slots, new_len, sel):
        out, pcache = bd.decode_chunk_paged_rows(
            params, toks, cfg, pcache, slots, new_length=new_len, sel=sel)
        return pcache, last_logits.at[slots].set(out, mode="drop")

    def chunk_of(rows):
        return lambda: chunk.lower(
            params, cache, last_logits, sds((rows, chunk_len)), sds((rows,)),
            sds((rows,)), sds((rows,)))

    lowered = {
        "unmask": lambda: unmask.lower(block_logits, sds((n_slots, b)),
                                       sds((n_slots,))),
        "tick": lambda: tick.lower(params, cache, block_logits,
                                   sds((n_slots, b)), sds((n_slots,)),
                                   sds((n_slots,))),
        "chunk": chunk_of(1), "chunk_rows": chunk_of(8)}
    done = {}

    def compiled(program):
        if program not in done:
            done[program] = lowered[program]().compile()
        return done[program]

    return compiled, cache, cfg


@pytest.mark.parametrize("program", ["tick", "chunk", "chunk_rows"])
def test_block_diffusion_programs_fit_the_chip_and_hold_one_pool(
        block_diffusion_compiled, program):
    """8.72 GB of weights and the 3.24 GB pool are held once and written in
    place (the block's keys land past the rows' lengths in the donated pool);
    what a program allocates beside its arguments (its scratch, and the block
    tick's logits of every position, 0.31 GB in float32) is within the room
    the mix's ``engine_is`` states, and 1 GB of the chip is to spare beside
    the two copies of the block's logits a step holds at once."""
    compiled, cache, cfg = block_diffusion_compiled
    mem = compiled(program).memory_analysis()
    pool = cache.k.size * cache.k.dtype.itemsize
    assert cache.k.shape == (6, 1029, 256, 4, 128) and 2 * pool == \
        3_236_954_112
    assert mem.alias_size_in_bytes >= 2 * pool
    fresh = mem.temp_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes
    block_logits = 128 * cfg.block_length * cfg.vocab_size * 4
    assert fresh < {"tick": 0.4e9 + block_logits, "chunk": 0.1e9,
                    "chunk_rows": 0.4e9}[program], fresh
    assert mem.temp_size_in_bytes < pool // 4, mem.temp_size_in_bytes
    spare = V5E_USABLE_BYTES - mem.argument_size_in_bytes - fresh \
        - 2 * block_logits
    assert spare > 1.0e9, spare
    m = re.search(r"(\d\.\d+) GB to spare", _benchmark_json(
        "traffic", "blockgen.json")["engine_is"])
    assert m and spare > float(m.group(1)) * 1e9


def test_the_unmask_program_reads_logits_and_allocates_next_to_nothing(
        block_diffusion_compiled):
    """The program in front of the block tick takes the 0.31 GB of logits the
    last tick left and hands back ids and two small vectors: no copy of the
    logits with the mask id's column set, no sorted copy."""
    compiled, _, cfg = block_diffusion_compiled
    mem = compiled("unmask").memory_analysis()
    assert mem.argument_size_in_bytes < 128 * cfg.block_length \
        * cfg.vocab_size * 4 + 1e5
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 1e6


def test_the_block_tick_holds_one_grouped_product_an_expert_layer(
        block_diffusion_compiled):
    """``sdar_blockgen``'s block tick is 128 rows of 4 positions, 512 tokens:
    over :data:`latent_moe.IN_PLACE_ROWS`, so each of its six expert layers
    is one Mosaic call over the sorted tiles and no loop of the program
    looks an expert up or slices one out; the scratch is no larger than the
    loop's was."""
    compiled, _, cfg = block_diffusion_compiled
    assert lm.rows_grouped(128 * cfg.block_length, cfg.dim, cfg.expert_dim)
    program = compiled("tick")
    _assert_the_experts_are_one_grouped_product(
        program.as_text(), cfg, "{1,%d,%d}" % (cfg.dim, cfg.expert_dim))
    assert program.memory_analysis().temp_size_in_bytes \
        <= PARENT_CHUNK_TEMP_BYTES["sdar"]


# --- the data-parallel train step: where the gradient exchange is placed ----


def _resnet_step(devices):
    """``resnet50_dp4``'s step (``benchmark/configs/resnet50.json`` under
    ``traffic/synthetic_b128_dp.json``) over ``devices``, as the family
    builds it, and its arguments as shapes on the mesh."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.resnet import ResNet

    cfg = _benchmark_json("configs", "resnet50.json")
    per_chip = _benchmark_json("traffic",
                               "synthetic_b128_dp.json")["per_chip_batch"]
    mesh = Mesh(np.array(devices), ("hvd",))
    model = ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                   num_classes=cfg["num_classes"], width=cfg["width"],
                   dtype=jnp.dtype(cfg["compute_dtype"]))
    size = cfg["image_size"]
    shapes = jax.eval_shape(partial(model.init, train=False),
                            jax.random.key(0), jnp.zeros((1, size, size, 3)))
    batch_stats = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                               shapes["batch_stats"])

    def loss_fn(p, batch):
        x, y = batch
        logits, _ = model.apply({"params": p, "batch_stats": batch_stats}, x,
                                train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    opt = cfg["optimizer"]
    tx = hvd.DistributedOptimizer(optax.sgd(
        opt["lr_per_chip"] * len(devices), momentum=opt["momentum"]))
    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("hvd"))
    n = per_chip * len(devices)
    args = (_avals(shapes["params"], replicated),
            _avals(jax.eval_shape(tx.init, shapes["params"]), replicated),
            (jax.ShapeDtypeStruct((n, size, size, 3), jnp.float32,
                                  sharding=rows),
             jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows)))
    return hvd.make_train_step(loss_fn, tx, mesh=mesh), args


def _entry_ops(hlo: str) -> list:
    """The entry computation of a scheduled module, an op a line, in the
    order the device runs them."""
    assert "is_scheduled=true" in hlo[:200]
    return re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", hlo,
                     re.S | re.M).group(1).splitlines()


def _result_shapes(line: str, op: str) -> list:
    """The shapes, as tuples, of what ``op`` on ``line`` yields."""
    return [tuple(int(d) for d in filter(None, dims.split(",")))
            for dims in re.findall(r"\b[a-z]+\d+\[([\d,]*)\]",
                                   line.split(f" {op}(")[0])]


@pytest.fixture(scope="module")
def dp4_step(topo, no_compile_cache):
    """The cell's step compiled once for the four described chips: the
    entry computation's ops, the indices of its collectives and of its
    convolution fusions, and the gradient leaves' shapes."""
    step, args = _resnet_step(topo.devices)
    ops = _entry_ops(step.lower(*args).compile().as_text())
    return {"ops": ops,
            "collectives": [i for i, op in enumerate(ops)
                            if " all-reduce(" in op],
            "convolutions": [i for i, op in enumerate(ops)
                             if "kind=kOutput" in op],
            "leaves": jax.tree.leaves(args[0])}


def test_the_dp4_step_packs_no_bucket_of_gradients(dp4_step):
    """102 MB of gradients, and no ``concatenate`` of the program yields as
    much as a megabyte (the parent packed two buffers of 60 and 42 MB and
    cut them up again)."""
    packed = [math.prod(shape) for op in dp4_step["ops"]
              if " concatenate(" in op
              for shape in _result_shapes(op, "concatenate")]
    assert sum(x.size for x in dp4_step["leaves"]) * 4 > 100e6
    assert all(4 * n < 1 << 20 for n in packed), packed


def test_the_dp4_step_keeps_its_buckets_apart(dp4_step):
    """The chained buckets stay several collectives (independent ones the
    compiler merges into one), none of them asynchronous (the chip's verdict
    on those is in ``PERF.md``, PR 47)."""
    from horovod_tpu.ops import fusion

    buckets = fusion.plan_buckets(dp4_step["leaves"],
                                  fusion.IN_PLACE_THRESHOLD_BYTES)
    assert len(dp4_step["collectives"]) >= len(buckets) > 2
    assert not [op for op in dp4_step["ops"]
                if "all-reduce-start" in op or "async-collective" in op]


def test_the_dp4_step_exchanges_after_its_last_convolution(dp4_step):
    """Every collective is scheduled behind the last convolution fusion and
    none between two of them: where the chain alone put them (the first
    after 71 of the 162 convolution fusions) each cost the fusions around
    it more than its bucket saved (four chips, ``PERF.md`` section 6,
    PR 47: 48.87 ms a step inside the backward pass, 48.33 behind it)."""
    assert len(dp4_step["convolutions"]) > 150
    assert min(dp4_step["collectives"]) > max(dp4_step["convolutions"])


def test_every_gradient_leaf_of_the_dp4_step_reaches_one_collective(dp4_step):
    """What the collectives reduce is the gradient tree once over and the
    loss: each leaf is an operand of its own, shape for shape."""
    reduced = [shape for i in dp4_step["collectives"] for shape in
               _result_shapes(dp4_step["ops"][i], "all-reduce")]
    assert sorted(math.prod(s) for s in reduced if s) == \
        sorted(x.size for x in dp4_step["leaves"])
    assert [s for s in reduced if not s] == [()]             # the loss


def test_one_chip_s_step_lowers_without_barrier_or_packed_bucket(topo):
    """``resnet50_train``'s step, on its lowered text (no second compile):
    an axis of one chains nothing, so no ``optimization_barrier`` stands
    between what yields a gradient and the update that takes it, and no flat
    buffer of gradients is concatenated (XLA drops the one-member
    collectives; the compiled program is the parent's op for op, ``PERF.md``
    PR 47)."""
    step, args = _resnet_step(topo.devices[:1])
    text = step.lower(*args).as_text()
    assert "optimization_barrier" not in text
    assert "stablehlo.all_reduce" in text
    assert not re.findall(r"stablehlo\.concatenate.*-> tensor<(\d+)xf32>",
                          text)


def _decoder_step(devices):
    """``mellum2_pretrain8k``'s step (``benchmark/configs/mellum2-12b-a2.5b
    .json`` under ``traffic/packed8k_b4.json``) over ``devices``, as the
    family builds it, and its arguments as shapes on the mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark import lib
    from horovod_tpu.models import moe_decoder as md

    family = lib.load_module("families", "mellum_train")
    cfg = _benchmark_json("configs", "mellum2-12b-a2.5b.json")
    mix = _benchmark_json("traffic", "packed8k_b4.json")
    mc = family.model_config(cfg)
    mesh = Mesh(np.array(devices), ("hvd",))
    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("hvd"))
    params = jax.eval_shape(lambda: md.init_params(mc, jax.random.key(0)))
    tx = hvd.DistributedOptimizer(family.optimizer(cfg))
    ids = jax.ShapeDtypeStruct(
        (mix["per_chip_batch"] * len(devices), cfg["training"]["seq_len"]),
        jnp.int32, sharding=rows)
    args = (_avals(params, replicated),
            _avals(jax.eval_shape(tx.init, params), replicated), (ids, ids))
    return hvd.make_train_step(partial(md.loss_and_counters, cfg=mc), tx,
                               mesh=mesh, has_aux=True), args, mc


@pytest.fixture(scope="module")
def decoder_step(topo, no_compile_cache):
    """The cell's train step compiled once for one described chip at the
    cell's own size: its text and the compiler's account of its memory."""
    step, args, mc = _decoder_step(topo.devices[:1])
    compiled = step.lower(*args).compile()
    return {"text": compiled.as_text(), "mem": compiled.memory_analysis(),
            "mc": mc}


def test_the_decoder_step_fits_the_chip_beside_its_state(decoder_step):
    """Weights and AdamW's two moments are the step's arguments (updated in
    place), the gradients and what the backward keeps are its scratch: all
    of it under the chip's 15.75 GiB with half a GB to spare."""
    from horovod_tpu.models import moe_decoder as md

    mem = decoder_step["mem"]
    n = md.param_count(decoder_step["mc"])
    assert n == 595_153_152
    assert mem.argument_size_in_bytes == pytest.approx(12 * n, rel=1e-3)
    assert mem.alias_size_in_bytes >= 12 * n         # updated where it lies
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < 15.75 * 2**30 - 0.5e9, (held, mem)


@pytest.mark.parametrize("kernel,calls", [
    # 3 sliding layers and 1 full one; a layer's flash forward runs once
    # more in the backward's recomputation (its outcome and row sums are
    # what dQ and dK/dV read), the grouped forward does not (the expert
    # layer's backward reads the layer's inputs alone)
    ("flash_band_fwd", 6), ("flash_band_dq", 3), ("flash_band_dkv", 3),
    ("flash_fwd", 2), ("flash_dq", 1), ("flash_dkv", 1),
    ("grouped_swiglu", 4), ("grouped_swiglu_dx", 4),
    ("grouped_swiglu_dw", 4)])
def test_the_decoder_step_holds_its_kernels_lowered_by_mosaic(decoder_step,
                                                              kernel, calls):
    found = [line for line in decoder_step["text"].splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(rf"/{kernel}/pallas_call", line)]
    assert len(found) == calls, (kernel, len(found))
