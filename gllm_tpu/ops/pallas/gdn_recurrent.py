"""Pallas TPU kernel for the gated-delta-rule decode step, in place in the
slot pool.

One new token per row: for each row the state of its slot is read once,
decayed, corrected by the delta rule, read out and written back once,

    S <- e^g S ;  S <- S + k (beta (v - S^T k))^T ;  o = S^T q

What XLA makes of ``ops/gdn.recurrent_gated_delta_step`` between a gather
of the rows' states out of the pool and a scatter back moves each state
through HBM seven times (gather out and in, one pass for ``S^T k``, one
for the update and the read-out, scatter in and out: 1.4 ms a layer for 32
rows of 30 x 96 x 192, v5e trace, PERF.md). Here grid = (rows,): a row's
whole state (2.2 MB) is one block that the Pallas pipeline brings into
VMEM and takes back, addressed by the row's slot through scalar prefetch;
the pool is aliased to the output, so slots no row names are not touched.
The kernel waits for those two transfers and for nothing else (stubbed to
a copy of the block it takes as long, v5e, PERF.md), so what the pool
stores is what it costs: the pool holds a state as ``ops/gdn.pack_state``
lays it, g heads abreast, [H / g, Dk, g Dv] with g Dv a whole number of
128-lane tiles (two heads of 192 lanes in 384: alone each would lie in
256, a third of the transfer padding).

The rule runs on a group's [Dk, g Dv] at once. The token's operands arrive
as the model has them, a row a head ([H, Dk], [H, Dv]), and are laid
beside the state here: a group's rows of v, decay and beta go side by side
through a scratch row (a store at a head's lane offset); q and k arrive
with Dk on lanes and the rule wants them as columns (Dk on sublanes, to
scale the rows of S): a row becomes a column by a masked lane reduction
against the identity, which is plain VPU work at any Dk (a transpose of
[H, 96] is not something Mosaic takes), and a group's columns are spread
each over its own head's lanes by a lane mask. The head dims need no
alignment: a block's last two dims are the array's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slots_ref, q_ref, k_ref, v_ref, decay_ref, beta_ref, pool_ref,
            out_ref, new_ref, row_ref, *, g: int):
    del slots_ref                       # used by the index maps alone
    groups, dk, lanes = pool_ref.shape[1:]
    dv = lanes // g
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, lanes), 1)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

    def column(row):                    # [1, Dk] -> [Dk, 1]
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def spread(ref, j):
        """The columns of group j's heads, each over its own head's Dv
        lanes: [Dk, g Dv] ([Dk, 1] where g = 1: the products below spread
        it themselves)."""
        out = column(ref[0, (j + 1) * g - 1:(j + 1) * g, :])
        for i in range(g - 2, -1, -1):
            out = jnp.where(lane < (i + 1) * dv,
                            column(ref[0, j * g + i:j * g + i + 1, :]), out)
        return out

    def abreast(ref, j, r):
        """Group j's g rows of Dv lanes side by side, [1, g Dv], through
        row r of the scratch."""
        for i in range(g):
            row_ref[r:r + 1, i * dv:(i + 1) * dv] = \
                ref[0, j * g + i:j * g + i + 1, :]
        return row_ref[r:r + 1, :]

    for j in range(groups):
        st = pool_ref[0, j] * abreast(decay_ref, j, 0)        # [Dk, g Dv]
        k_sp = spread(k_ref, j)
        seen = jnp.sum(st * k_sp, axis=0, keepdims=True)      # [1, g Dv]
        delta = (abreast(v_ref, j, 1) - seen) * abreast(beta_ref, j, 2)
        st = st + k_sp * delta
        new_ref[0, j] = st
        row_ref[3:4, :] = jnp.sum(st * spread(q_ref, j), axis=0,
                                  keepdims=True)
        for i in range(g):
            out_ref[0, j * g + i:j * g + i + 1, :] = \
                row_ref[3:4, i * dv:(i + 1) * dv]


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(5,))
def gdn_recurrent_step(
    q: jnp.ndarray,          # [S, H, Dk] f32 (l2normed, scaled)
    k: jnp.ndarray,          # [S, H, Dk] f32 (l2normed)
    v: jnp.ndarray,          # [S, H, Dv] f32
    g: jnp.ndarray,          # [S, H] log decay (<= 0)
    beta: jnp.ndarray,       # [S, H]
    pool: jnp.ndarray,       # [P, H / n, Dk, n Dv] f32: every slot's state
    slots: jnp.ndarray,      # [S] int32: each row's slot in the pool
    *,
    interpret: bool = False,
):
    """Returns (out [S, H, Dv] f32, pool with the rows' slots advanced).
    The pool holds a state as ``ops/gdn.pack_state`` lays it, ``n`` heads
    abreast (read from its shape). Rows that share a slot (padding rows on
    the dummy slot) leave one of their states there."""
    S, H, Dk = q.shape
    Dv = v.shape[-1]
    P, G, _, lanes = pool.shape
    n = lanes // Dv
    if (G * n, pool.shape[2], n * Dv) != (H, Dk, lanes):
        raise ValueError(f"a pool of {pool.shape} does not hold states "
                         f"of {(H, Dk, Dv)} as pack_state lays them")

    def row(width):
        return pl.BlockSpec((1, H, width), lambda s, slots: (s, 0, 0),
                            memory_space=pltpu.VMEM)

    state = pl.BlockSpec((1, G, Dk, lanes),
                         lambda s, slots: (slots[s], 0, 0, 0),
                         memory_space=pltpu.VMEM)
    # a head's two scalars spread over Dv lanes here ([S, H, Dv], 0.7 MB):
    # a [1, Dv] row scales the rows of a [Dk, Dv] state, a [1, 1] value
    # would have to spread over sublanes and lanes at once, which Mosaic
    # does not do
    def lanes_of(x):
        return jnp.broadcast_to(x.astype(jnp.float32)[..., None], v.shape)
    decay = lanes_of(jnp.exp(g.astype(jnp.float32)))
    out, pool = pl.pallas_call(
        functools.partial(_kernel, g=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[row(Dk), row(Dk), row(Dv), row(Dv), row(Dv), state],
            out_specs=[row(Dv), state],
            # rows 0-2: a group's decay, v, beta abreast; row 3: its output
            scratch_shapes=[pltpu.VMEM((8, lanes), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((S, H, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (after the prefetched slots) is the pool: output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="gdn_recurrent_step",
        interpret=interpret,
    )(slots.astype(jnp.int32), q, k, v, decay, lanes_of(beta), pool)
    return out, pool
