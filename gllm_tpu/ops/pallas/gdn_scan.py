"""Pallas TPU kernel for the gated-delta-rule chunk scan, in place in the
slot pool.

The chunked rule's in-chunk half (decay matrices, (I + A)^-1, v2,
k_cumdecay) is parallel math that XLA batches well, so it stays in
ops/gdn.py. What XLA does poorly is the *sequential* inter-chunk
recurrence: a ``lax.scan`` whose [Dk, Dv] carry goes through HBM every
chunk, between a gather of the rows' states out of the pool and a scatter
back (for one joining prompt among 31 decoding rows: ~1.3 ms a layer of
gather, pad, slice and scatter around 0.4-0.8 ms of scan, v5e trace,
PERF.md).

This kernel fuses that scan over the PACKED layout of
``ops/gdn.chunk_gated_delta_rule_packed``: the chunks of a step's
prefilling sequences laid end to end. grid = (heads, chunks), the chunk
axis innermost and sequential; the running state of one head lives in VMEM
scratch across a sequence's chunks; where a sequence begins
(``first[n]``) it is read from the pool at the sequence's slot, and after
every chunk it is left in the pool's block of that slot, which the Pallas
pipeline writes back when the slot changes. The pool is aliased to the
output, so slots no chunk names (every decoding row's) are not touched and
no state is gathered or scattered.

Recurrence per chunk, operands precomputed by XLA with the in-chunk half
(``g`` the in-chunk cumulative log decay, ``g_C`` its last entry):

    vnew  = v2 - k_cumdecay @ state
    out   = (q * e^g) @ state + attn_local @ vnew
    state = e^{g_C} state + (k * e^{g_C - g})^T @ vnew

The head dims need no alignment (a block's last two dims are the
array's). A scalar of a chunk cannot spread over sublanes and lanes at
once in Mosaic, so e^{g_C} arrives spread over Dv lanes and the
transposed operand arrives transposed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slot_ref, first_ref, qg_ref, kdt_ref, v2_ref, kcd_ref, attn_ref,
            dl_ref, pool_ref, out_ref, new_ref, state):
    del slot_ref                        # used by the index maps alone
    n = pl.program_id(1)
    g = qg_ref.shape[0]                 # heads abreast in the state
    dv = state.shape[1] // g

    # a group's first chunk without a sequence of its own (nothing
    # prefills) starts from its slot's state too: whatever the scratch
    # held would otherwise end in that slot
    @pl.when((first_ref[n] != 0) | (n == 0))
    def _():
        state[:] = pool_ref[0, 0]

    def dot(a, b):
        return jax.lax.dot(a, b, preferred_element_type=jnp.float32)

    for i in range(g):
        own = slice(i * dv, (i + 1) * dv)               # head i's lanes
        st = state[:, own]                              # [Dk, Dv] f32
        v_new = v2_ref[i, 0] - dot(kcd_ref[i, 0], st)   # [C, Dv]
        out_ref[i, 0] = dot(qg_ref[i, 0], st) + dot(attn_ref[i, 0], v_new)
        state[:, own] = st * dl_ref[i, 0] + dot(kdt_ref[i, 0], v_new)
    new_ref[0, 0] = state[:]


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(6,))
def gdn_chunk_scan(
    qg: jnp.ndarray,      # [H, N, C, Dk] f32: q (l2normed, scaled) * e^g
    kdt: jnp.ndarray,     # [H, N, Dk, C] f32: (k * e^{g_C - g})^T
    v2: jnp.ndarray,      # [H, N, C, Dv] f32: Tmat @ v_beta
    kcd: jnp.ndarray,     # [H, N, C, Dk] f32: Tmat @ (k_beta * e^g)
    attn: jnp.ndarray,    # [H, N, C, C]  f32: masked local scores
    dl: jnp.ndarray,      # [H, N, 1, Dv] f32: e^{g_C} over the lanes
    pool: jnp.ndarray,    # [P, H / g, Dk, g Dv] f32: every slot's state
    slot: jnp.ndarray,    # [N] int32: the slot of each chunk's sequence
    first: jnp.ndarray,   # [N] bool / int: the chunk is its sequence's first
    *,
    interpret: bool = False,
):
    """Returns (out [H, N, C, Dv] f32, pool with the final state of every
    sequence in its slot). The pool holds a state as
    ``ops/gdn.pack_state`` lays it, ``g`` heads abreast (read from its
    shape). The chunks of one sequence are consecutive; chunks past the
    last sequence name a dummy slot and carry operands that are the
    identity on the state (g = beta = 0)."""
    H, N, C, Dk = qg.shape
    Dv = v2.shape[-1]
    P, G, _, lanes = pool.shape
    g = lanes // Dv
    if (G * g, pool.shape[2], g * Dv) != (H, Dk, lanes):
        raise ValueError(f"a pool of {pool.shape} does not hold states "
                         f"of {(H, Dk, Dv)} as pack_state lays them")

    def blk(*tail):     # the g heads of a group, one chunk
        return pl.BlockSpec((g, 1) + tail,
                            lambda h, n, slot, first: (h, n, 0, 0),
                            memory_space=pltpu.VMEM)

    state = pl.BlockSpec((1, 1, Dk, lanes),
                         lambda h, n, slot, first: (slot[n], h, 0, 0),
                         memory_space=pltpu.VMEM)
    out, pool = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(G, N),
            in_specs=[blk(C, Dk), blk(Dk, C), blk(C, Dv), blk(C, Dk),
                      blk(C, C), blk(1, Dv), state],
            out_specs=[blk(C, Dv), state],
            scratch_shapes=[pltpu.VMEM((Dk, lanes), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((H, N, C, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 8 (after the two prefetched arrays) is the pool: output 1
        input_output_aliases={8: 1},
        # the chunk axis is a scan over the VMEM-resident state; a group's
        # blocks of the pool are its own, but the axis stays sequential so
        # that a slot's block is written back before another group's grid
        # steps could be reordered around it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="gdn_chunk_scan",
        interpret=interpret,
    )(slot.astype(jnp.int32), first.astype(jnp.int32), qg, kdt, v2, kcd,
      attn, dl, pool)
    return out, pool
