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

Mosaic compiles the kernel; the CPU test suite opts into the Pallas
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

    def resident(i, j, n):
        """The (tile, block of f) whose blocks step ``(i, j)`` holds: its own
        within the tiles in use, the last one's after them."""
        last = jnp.maximum(n[0] - 1, 0)
        return jnp.minimum(i, last), jnp.where(i < n[0], j, nf - 1)

    def rows_of(i, j, te, n):
        return resident(i, j, n)[0], 0

    def up_block(i, j, te, n):
        i, j = resident(i, j, n)
        return te[i], 0, j

    def down_block(i, j, te, n):
        i, j = resident(i, j, n)
        return te[i], j, 0

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
            vmem_limit_bytes=VMEM_BLOCK_BYTES + VMEM_STEP_BYTES),
        interpret=interpreted(),
        name="grouped_swiglu",
    )(tile_expert, jnp.reshape(n_tiles, (1,)).astype(jnp.int32), x_rows,
      e_gate, e_up, e_down)
