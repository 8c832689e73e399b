"""Pallas TPU kernel for the Mamba-2 chunked rule's inter-chunk scan, in
place in the slot pool.

The in-chunk half (ops/mamba2._chunk_local) is parallel math that XLA
batches well. The sequential half is this scan over the PACKED layout (the
chunks of a step's prefilling sequences laid end to end), fused as
ops/pallas/gdn_scan.py fuses the GDN rule's: grid = (groups, chunks), the
chunk axis innermost and sequential; the running state of one group's
heads lives in VMEM scratch across a sequence's chunks; where a sequence
begins (``first[n]``) it is read from the pool at the sequence's slot, and
after every chunk it is left in the pool's block of that slot, which the
Pallas pipeline writes back when the slot changes. The pool is aliased to
the output: slots no chunk names are not touched, no state is gathered or
scattered.

Per chunk and head, operands precomputed by XLA (``l`` the in-chunk
cumulative log decay, ``l_C`` its last entry):

    y     = y_intra + (C e^l) S^T
    S    <- e^{l_C} S + ((dt x) e^{l_C - l})^T B

A group's heads share ``B``, so a grid step takes the whole group: one
block of B, ``heads / groups`` states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slot_ref, first_ref, yin_ref, cexp_ref, xdt_ref, b_ref, dl_ref,
            pool_ref, out_ref, new_ref, state, *, per: int):
    del slot_ref                        # used by the index maps alone
    n = pl.program_id(1)

    # a group's first chunk without a sequence of its own (nothing
    # prefills) starts from its slot's state too: whatever the scratch
    # held would otherwise end in that slot
    @pl.when((first_ref[n] != 0) | (n == 0))
    def _():
        state[...] = pool_ref[0]

    b = b_ref[0, 0]                                     # [C, N]
    for j in range(per):
        st = state[j]                                   # [P, N] f32
        out_ref[0, j] = yin_ref[0, j] + jax.lax.dot_general(
            cexp_ref[0, j], st, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [C, P]
        st = st * dl_ref[0, j] + jax.lax.dot(
            xdt_ref[0, j], b, preferred_element_type=jnp.float32)
        state[j] = st
        new_ref[0, j] = st


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(5,))
def mamba2_chunk_scan(
    y_intra: jnp.ndarray,  # [Nc, H, C, P] f32: the in-chunk half's output
    cexp: jnp.ndarray,     # [Nc, H, C, N] f32: C e^l
    xdT: jnp.ndarray,      # [Nc, H, P, C] f32: ((dt x) e^{l_C - l})^T
    B: jnp.ndarray,        # [Nc, G, C, N] f32
    dl: jnp.ndarray,       # [Nc, H, 1, N] f32: e^{l_C} over the lanes
    pool: jnp.ndarray,     # [slots, H, P, N] f32: every slot's state
    slot: jnp.ndarray,     # [Nc] int32: the slot of each chunk's sequence
    first: jnp.ndarray,    # [Nc] bool / int: the chunk is its seq's first
    *,
    interpret: bool = False,
):
    """Returns (y [Nc, H, C, P] f32, pool with the final state of every
    sequence in its slot). The chunks of one sequence are consecutive;
    chunks past the last sequence name a dummy slot and carry operands
    that are the identity on the state."""
    Nc, H, C, P = y_intra.shape
    G, N = B.shape[1], B.shape[-1]
    per = H // G

    def blk(heads, *tail):
        return pl.BlockSpec((1, heads) + tail,
                            lambda g, n, slot, first: (n, g, 0, 0),
                            memory_space=pltpu.VMEM)

    state = pl.BlockSpec((1, per, P, N),
                         lambda g, n, slot, first: (slot[n], g, 0, 0),
                         memory_space=pltpu.VMEM)
    out, pool = pl.pallas_call(
        functools.partial(_kernel, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(G, Nc),
            in_specs=[blk(per, C, P), blk(per, C, N), blk(per, P, C),
                      blk(1, C, N), blk(per, 1, N), state],
            out_specs=[blk(per, C, P), state],
            scratch_shapes=[pltpu.VMEM((per, P, N), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((Nc, H, C, P), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 7 (after the two prefetched arrays) is the pool: output 1
        input_output_aliases={7: 1},
        # the chunk axis is a scan over the VMEM-resident state; the group
        # axis stays sequential too, so that a slot's block is written
        # back before another group's grid steps could be reordered
        # around it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="mamba2_chunk_scan",
        interpret=interpret,
    )(slot.astype(jnp.int32), first.astype(jnp.int32), y_intra, cexp, xdT,
      B, dl, pool)
    return out, pool
