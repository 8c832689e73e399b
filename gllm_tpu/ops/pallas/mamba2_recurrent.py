"""Pallas TPU kernel for the Mamba-2 decode step, in place in the slot
pool.

One new token per row: the state of the row's slot ([H, P, N] float32,
2.1 MB at 64 heads of 64 x 128) is read once, decayed, written to and read
out, and written back once,

    S_h <- a_h S_h + (dt_h x_h) B_g^T ;   y_h = S_h C_g

As in ops/pallas/gdn_recurrent.py, grid = (rows,): a row's whole state is
one block that the Pallas pipeline brings into VMEM and takes back,
addressed by the row's slot through scalar prefetch; the pool is aliased
to the output, so slots no row names are not touched and no state is
gathered out of the pool or scattered back.

N lies along the lanes, P along the sublanes: ``B_g`` and ``C_g`` are rows
as they arrive, ``dt x`` has to become a column (to scale the rows of the
outer product) and ``y`` comes out of the lane reduction as a column and
leaves as a row. Both turns are masked reductions against the identity,
plain VPU work on [P, P] (the GDN kernel's ``column``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slots_ref, xdt_ref, b_ref, c_ref, decay_ref, pool_ref,
            out_ref, new_ref, *, heads: int, groups: int):
    del slots_ref                       # used by the index maps alone
    p = xdt_ref.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1))
    per = heads // groups
    for h in range(heads):
        g = h // per
        # [1, P] -> [P, 1]
        x_col = jnp.sum(jnp.where(eye, xdt_ref[0, h:h + 1, :], 0.0),
                        axis=1, keepdims=True)
        st = (pool_ref[0, h] * decay_ref[0, h:h + 1, :]
              + x_col * b_ref[0, g:g + 1, :])                 # [P, N]
        new_ref[0, h] = st
        y_col = jnp.sum(st * c_ref[0, g:g + 1, :], axis=1, keepdims=True)
        # [P, 1] -> [1, P]
        out_ref[0, h:h + 1, :] = jnp.sum(jnp.where(eye, y_col, 0.0),
                                         axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(4,))
def mamba2_recurrent_step(
    xdt: jnp.ndarray,        # [S, H, P] f32: dt_h x_h
    decay: jnp.ndarray,      # [S, H] f32: a_h
    B: jnp.ndarray,          # [S, G, N] f32
    C: jnp.ndarray,          # [S, G, N] f32
    pool: jnp.ndarray,       # [slots, H, P, N] f32: every slot's state
    slots: jnp.ndarray,      # [S] int32: each row's slot in the pool
    *,
    interpret: bool = False,
):
    """Returns (y [S, H, P] f32 without the skip, pool with the rows' slots
    advanced). Rows that share a slot (padding rows on the dummy slot)
    leave one of their states there."""
    S, H, P = xdt.shape
    G, N = B.shape[1:]

    def row(heads, width):
        return pl.BlockSpec((1, heads, width), lambda s, slots: (s, 0, 0),
                            memory_space=pltpu.VMEM)

    state = pl.BlockSpec((1, H, P, N), lambda s, slots: (slots[s], 0, 0, 0),
                         memory_space=pltpu.VMEM)
    # a head's decay spread over the N lanes ([S, H, N]): a [1, N] row
    # scales the rows of a [P, N] state, a [1, 1] value would have to
    # spread over sublanes and lanes at once, which Mosaic does not do
    lanes = jnp.broadcast_to(decay.astype(jnp.float32)[..., None], (S, H, N))
    out, pool = pl.pallas_call(
        functools.partial(_kernel, heads=H, groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[row(H, P), row(G, N), row(G, N), row(H, N), state],
            out_specs=[row(H, P), state]),
        out_shape=[jax.ShapeDtypeStruct((S, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 (after the prefetched slots) is the pool: output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mamba2_recurrent_step",
        interpret=interpret,
    )(slots.astype(jnp.int32), xdt.astype(jnp.float32),
      B.astype(jnp.float32), C.astype(jnp.float32), lanes, pool)
    return out, pool
