"""The grouped product of the expert layer (TPU, Pallas): every tile of the
rows that :func:`horovod_tpu.models.latent_moe.held_experts` has sorted by
expert goes through its own expert's SwiGLU in one kernel call, and the
weights of the tile after stream in while the present tile is multiplied.

Written for the MXU/VMEM model of /opt/skills/guides/pallas_guide.md
(``PrefetchScalarGridSpec``).  The grid is ``(tiles, blocks of the inner
width f)``.  Which expert a tile belongs to, and how many tiles are in use,
are device values computed once outside and prefetched as scalars; the index
maps of ``e_gate``, ``e_up`` and ``e_down`` read them, so the pipeline's
double buffering fetches step ``s + 1``'s blocks under step ``s``'s products,
and a step whose blocks are the step before's (a second tile of one expert
where ``f`` is one block) fetches nothing.  The grid is static at the worst
case; a step past the last tile in use maps to the blocks already resident
and is skipped under ``pl.when``: it costs neither bytes nor products.

The body rounds where ``latent_moe._swiglu`` rounds: gate and up to the
activations' dtype, their product in it, the down product accumulated in
float32 over the blocks of ``f`` and rounded once.

The layer's derivative is two more kernels over the same sorted tiles
(:func:`grouped_swiglu_grad`): one gives the rows' gradient as a grouped
product against the transposed weights and keeps what the weights' gradient
needs, the other gives each expert's weight gradient over its own tiles,
resident in VMEM from its first tile to its last.

Mosaic compiles the kernels; the CPU test suite opts into the Pallas
interpreter through :func:`horovod_tpu.parallel.flash_attention.
interpret_mode`, the one switch, and nothing in the package turns it on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from horovod_tpu.parallel.flash_attention import interpreted, pl, pltpu

# every served model that imports this module compiles programs that hold the
# kernel: Pallas is asked for before the first of them is traced (importing
# it there put a second into the engine's construction: PERF.md, PR 46)
pltpu.preload()

LANES = 128
#: what the kernel's blocks may take of a core's VMEM (128 MiB on a v5e), and
#: the room beside them it is compiled with for the float32 intermediates of
#: one step (gate, up and the down product of a tile)
VMEM_BLOCK_BYTES = 48 * 2**20
VMEM_STEP_BYTES = 16 * 2**20
#: what the float32 blocks of one expert's three weight gradients, two
#: buffers each, may take (:func:`_weight_grads`)
VMEM_GRAD_BYTES = 64 * 2**20


def lane_aligned(d: int, f: int) -> bool:
    """Whether experts ``[d, f]`` wide can be blocked in whole lanes: every
    published width is; the toy widths of the CPU tests are not."""
    return d % LANES == 0 and f % LANES == 0


def f_block(d: int, f: int, tile: int, itemsize: int) -> int:
    """The widest block of ``f``, in whole lanes and dividing it, at which
    two buffers of the three weight blocks fit :data:`VMEM_BLOCK_BYTES`
    beside the rows' and the outcome's two buffers and the float32
    accumulator; one lane row where none does."""
    fixed = tile * d * (4 * itemsize + 4)
    for n in range(1, f // LANES + 1):
        bf = f // n
        if f % n == 0 and bf % LANES == 0 and \
                fixed + 2 * 3 * d * bf * itemsize <= VMEM_BLOCK_BYTES:
            return bf
    return LANES


def _vmem_limit(d: int, bf: int, tile: int, itemsize: int,
                w_itemsize: int) -> int:
    """The limit a kernel over two buffers of three ``[d, bf]`` weight blocks
    is compiled with: :data:`VMEM_BLOCK_BYTES` and the step's room, which
    :func:`f_block` keeps the blocks within when the weights are stored in
    the activations' type; what float32 weights under bfloat16 products
    (training) take beyond that, added."""
    blocks = tile * d * (4 * itemsize + 4) + 2 * 3 * d * bf * w_itemsize
    return max(VMEM_BLOCK_BYTES, blocks) + VMEM_STEP_BYTES


def _index_maps(nf: int) -> tuple:
    """The index maps of a grid ``(tiles, nf blocks of f)`` whose scalars are
    ``(tile_expert, n_tiles)``: a tile's rows ``[tile, d]``, its rows'
    block of ``f`` ``[tile, bf]``, and its expert's ``[d, bf]`` and ``[bf,
    d]`` weight blocks."""

    def resident(i, j, n):
        """The (tile, block of f) whose blocks step ``(i, j)`` holds: its own
        within the tiles in use, the last one's after them."""
        last = jnp.maximum(n[0] - 1, 0)
        return jnp.minimum(i, last), jnp.where(i < n[0], j, nf - 1)

    def rows_of(i, j, te, n):
        return resident(i, j, n)[0], 0

    def rows_f(i, j, te, n):
        return resident(i, j, n)

    def up_block(i, j, te, n):
        i, j = resident(i, j, n)
        return te[i], 0, j

    def down_block(i, j, te, n):
        i, j = resident(i, j, n)
        return te[i], j, 0

    return rows_of, rows_f, up_block, down_block


def grouped_swiglu(x_rows, tile_expert, n_tiles, e_gate, e_up, e_down, *,
                   tile: int, dtype) -> jax.Array:
    """``[R, d]``: tile ``i`` of ``x_rows`` (``tile`` rows) through expert
    ``tile_expert[i]``'s SwiGLU for the first ``n_tiles`` tiles; the rows of
    the tiles after are not written and hold nothing to read.  ``e_gate`` and
    ``e_up`` are ``[E, d, f]``, ``e_down`` ``[E, f, d]``; ``tile_expert``
    ``[R / tile]`` int32 within ``E`` and ``n_tiles`` a scalar, both on the
    device."""
    rows, d = x_rows.shape
    f = e_gate.shape[-1]
    bf = f_block(d, f, tile, jnp.dtype(dtype).itemsize)
    nf = f // bf

    rows_of, _, up_block, down_block = _index_maps(nf)

    def body(te, n, x_ref, gate_ref, up_ref, down_ref, out_ref, acc_ref):
        j = pl.program_id(1)

        @pl.when(pl.program_id(0) < n[0])
        def _():
            x = x_ref[...]
            gate = jnp.dot(x, gate_ref[...].astype(dtype),
                           preferred_element_type=jnp.float32).astype(dtype)
            up = jnp.dot(x, up_ref[...].astype(dtype),
                         preferred_element_type=jnp.float32).astype(dtype)
            # silu and the product in float32, rounded once (Mosaic has no
            # bfloat16 logistic, and XLA:TPU keeps the precision inside its
            # fusion alike)
            gate, up = gate.astype(jnp.float32), up.astype(jnp.float32)
            part = jnp.dot((jax.nn.silu(gate) * up).astype(dtype),
                           down_ref[...].astype(dtype),
                           preferred_element_type=jnp.float32)
            if nf == 1:
                out_ref[...] = part.astype(out_ref.dtype)
                return

            @pl.when(j == 0)
            def _():
                acc_ref[...] = part

            @pl.when(j > 0)
            def _():
                acc_ref[...] += part

            @pl.when(j == nf - 1)
            def _():
                out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile, nf),
            in_specs=[pl.BlockSpec((tile, d), rows_of),
                      pl.BlockSpec((None, d, bf), up_block),
                      pl.BlockSpec((None, d, bf), up_block),
                      pl.BlockSpec((None, bf, d), down_block)],
            out_specs=pl.BlockSpec((tile, d), rows_of),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                d, bf, tile, jnp.dtype(dtype).itemsize,
                e_gate.dtype.itemsize)),
        interpret=interpreted(),
        name="grouped_swiglu",
    )(tile_expert, jnp.reshape(n_tiles, (1,)).astype(jnp.int32), x_rows,
      e_gate, e_up, e_down)


def grouped_swiglu_grad(x_rows, dy_rows, w_rows, tile_expert, n_tiles,
                        tiles_of, e_gate, e_up, e_down, *, tile: int,
                        dtype) -> tuple:
    """The derivative of :func:`grouped_swiglu` over the same sorted tiles.
    ``dy_rows`` [R, d] is the outcome's cotangent row by row before the
    router's weight, ``w_rows`` [R, 1] float32 that weight (the layer's
    outcome is ``w * swiglu(x)`` a row); ``tiles_of`` [E] counts each
    expert's tiles.  Returns ``(dx_rows [R, d], s_rows [R, 1], d_gate, d_up
    [E, d, f], d_down [E, f, d])``: the rows' gradient, each row's
    ``<swiglu(x), dy>`` (the gradient of its weight), and the weights'
    gradients in float32, zero for an expert with no tile.  Rows of tiles
    past the last in use are not written."""
    dx_rows, s_rows, dh_gate, dh_up, act = _row_grads(
        x_rows, dy_rows, w_rows, tile_expert, n_tiles, e_gate, e_up, e_down,
        tile=tile, dtype=dtype)
    return (dx_rows, s_rows) + _weight_grads(
        x_rows, dy_rows, dh_gate, dh_up, act, tiles_of, tile=tile)


def _row_grads(x_rows, dy_rows, w_rows, tile_expert, n_tiles, e_gate, e_up,
               e_down, *, tile: int, dtype):
    """The first kernel of the derivative, on :func:`grouped_swiglu`'s grid:
    a tile's gate and up products are made again (rounded where the forward
    rounds), ``dy`` goes back through the transposed down block, and the
    rows' gradient is accumulated in float32 over the blocks of ``f``.  Kept
    for the weights' kernel, ``[R, f]`` each in ``dtype``: the cotangents of
    the gate and up products, and the activation times the row's weight."""
    rows, d = x_rows.shape
    f = e_gate.shape[-1]
    bf = f_block(d, f, tile, jnp.dtype(dtype).itemsize)
    nf = f // bf
    f32 = jnp.float32

    rows_of, rows_f, up_block, down_block = _index_maps(nf)

    def t_dot(a, b):        # a @ b.T on the MXU, no transpose materialised
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    def body(te, n, x_ref, dy_ref, w_ref, gate_ref, up_ref, down_ref,
             dx_ref, s_ref, dhg_ref, dhu_ref, act_ref, acc_ref, s_acc):
        j = pl.program_id(1)

        @pl.when(pl.program_id(0) < n[0])
        def _():
            x, dy, w = x_ref[...], dy_ref[...], w_ref[...]
            w_gate = gate_ref[...].astype(dtype)
            w_up = up_ref[...].astype(dtype)
            gate = jnp.dot(x, w_gate, preferred_element_type=f32
                           ).astype(dtype).astype(f32)
            up = jnp.dot(x, w_up, preferred_element_type=f32
                         ).astype(dtype).astype(f32)
            sig = jax.nn.sigmoid(gate)
            silu = gate * sig
            act = (silu * up).astype(dtype).astype(f32)
            d_act = t_dot(dy, down_ref[...].astype(dtype))       # [tile, bf]
            s_part = jnp.sum(act * d_act, axis=1, keepdims=True)
            d_act = d_act * w
            d_up = (d_act * silu).astype(dtype)
            d_gate = (d_act * up * sig * (1.0 + gate * (1.0 - sig))
                      ).astype(dtype)
            dhg_ref[...] = d_gate
            dhu_ref[...] = d_up
            act_ref[...] = (act * w).astype(dtype)
            part = t_dot(d_gate, w_gate) + t_dot(d_up, w_up)      # [tile, d]
            if nf == 1:
                dx_ref[...] = part.astype(dx_ref.dtype)
                s_ref[...] = s_part
                return

            @pl.when(j == 0)
            def _():
                acc_ref[...] = part
                s_acc[...] = s_part

            @pl.when(j > 0)
            def _():
                acc_ref[...] += part
                s_acc[...] += s_part

            @pl.when(j == nf - 1)
            def _():
                dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)
                s_ref[...] = s_acc[...]

    kept = jax.ShapeDtypeStruct((rows, f), dtype)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile, nf),
            in_specs=[pl.BlockSpec((tile, d), rows_of),
                      pl.BlockSpec((tile, d), rows_of),
                      pl.BlockSpec((tile, 1), rows_of),
                      pl.BlockSpec((None, d, bf), up_block),
                      pl.BlockSpec((None, d, bf), up_block),
                      pl.BlockSpec((None, bf, d), down_block)],
            out_specs=[pl.BlockSpec((tile, d), rows_of),
                       pl.BlockSpec((tile, 1), rows_of),
                       pl.BlockSpec((tile, bf), rows_f),
                       pl.BlockSpec((tile, bf), rows_f),
                       pl.BlockSpec((tile, bf), rows_f)],
            scratch_shapes=[pltpu.VMEM((tile, d), f32),
                            pltpu.VMEM((tile, 1), f32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, d), dtype),
                   jax.ShapeDtypeStruct((rows, 1), f32), kept, kept, kept],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                d, bf, 2 * tile, jnp.dtype(dtype).itemsize,
                e_gate.dtype.itemsize)),
        interpret=interpreted(),
        name="grouped_swiglu_dx",
    )(tile_expert, jnp.reshape(n_tiles, (1,)).astype(jnp.int32), x_rows,
      dy_rows, w_rows, e_gate, e_up, e_down)


def grad_f_block(d: int, f: int) -> int:
    """The widest block of ``f``, in whole lanes and dividing it, at which
    two buffers of an expert's three float32 gradient blocks fit
    :data:`VMEM_GRAD_BYTES`; one lane row where none does."""
    for n in range(1, f // LANES + 1):
        bf = f // n
        if f % n == 0 and bf % LANES == 0 and \
                2 * 3 * d * bf * 4 <= VMEM_GRAD_BYTES:
            return bf
    return LANES


def _weight_grads(x_rows, dy_rows, dh_gate, dh_up, act, tiles_of, *,
                  tile: int) -> tuple:
    """The second kernel: each expert's ``x^T d_gate``, ``x^T d_up`` and
    ``act^T dy`` summed over its own tiles in float32.  The grid is ``(blocks
    of f, steps)``; a step is one tile of one expert, the experts in order,
    and an expert with no tile has one step that only writes its zeros, so
    the outcome's block changes when the expert does and is written back
    once an expert.  Which expert and tile a step has, and whether it opens
    an expert or is past the last, are computed outside and prefetched."""
    rows, d = x_rows.shape
    f = dh_gate.shape[-1]
    e = tiles_of.shape[0]
    bf = grad_f_block(d, f)
    n_steps = rows // tile + e          # every tile, and an empty expert one
    f32 = jnp.float32

    steps_of = jnp.maximum(tiles_of, 1)
    step_end = jnp.cumsum(steps_of)
    tile_end = jnp.cumsum(tiles_of)
    step = jnp.arange(n_steps, dtype=jnp.int32)
    expert = jnp.minimum(jnp.searchsorted(
        step_end, step, side="right", method="compare_all"), e - 1)
    within = step - (step_end - steps_of)[expert]
    in_use = step < step_end[-1]
    live = in_use & (within < tiles_of[expert])
    opens = in_use & (within == 0)
    which = jnp.clip((tile_end - tiles_of)[expert] + within, 0,
                     rows // tile - 1)
    flags = live.astype(jnp.int32) + 2 * opens.astype(jnp.int32)

    def rows_of(j, s, ex, tl, fl):
        return tl[s], 0

    def rows_f(j, s, ex, tl, fl):
        return tl[s], j

    def wide(j, s, ex, tl, fl):
        return ex[s], 0, j

    def tall(j, s, ex, tl, fl):
        return ex[s], j, 0

    def tt_dot(a, b):       # a.T @ b: the rows (sublanes) of both contracted
        return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                   preferred_element_type=f32)

    def body(ex, tl, fl, x_ref, dy_ref, dhg_ref, dhu_ref, act_ref,
             dg_ref, du_ref, dd_ref):
        flag = fl[pl.program_id(1)]

        @pl.when(flag >= 2)
        def _():
            dg_ref[...] = jnp.zeros_like(dg_ref)
            du_ref[...] = jnp.zeros_like(du_ref)
            dd_ref[...] = jnp.zeros_like(dd_ref)

        @pl.when(flag % 2 == 1)
        def _():
            x = x_ref[...]
            dg_ref[...] += tt_dot(x, dhg_ref[...])
            du_ref[...] += tt_dot(x, dhu_ref[...])
            dd_ref[...] += tt_dot(act_ref[...], dy_ref[...])

    return tuple(pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(f // bf, n_steps),
            in_specs=[pl.BlockSpec((tile, d), rows_of),
                      pl.BlockSpec((tile, d), rows_of),
                      pl.BlockSpec((tile, bf), rows_f),
                      pl.BlockSpec((tile, bf), rows_f),
                      pl.BlockSpec((tile, bf), rows_f)],
            out_specs=[pl.BlockSpec((None, d, bf), wide),
                       pl.BlockSpec((None, d, bf), wide),
                       pl.BlockSpec((None, bf, d), tall)]),
        out_shape=[jax.ShapeDtypeStruct((e, d, f), f32),
                   jax.ShapeDtypeStruct((e, d, f), f32),
                   jax.ShapeDtypeStruct((e, f, d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_GRAD_BYTES + VMEM_STEP_BYTES),
        interpret=interpreted(),
        name="grouped_swiglu_dw",
    )(expert.astype(jnp.int32), which.astype(jnp.int32), flags, x_rows,
      dy_rows, dh_gate, dh_up, act))
