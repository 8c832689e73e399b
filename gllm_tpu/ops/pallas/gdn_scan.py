"""Pallas TPU kernel for the gated-delta-rule chunk scan.

TPU-native replacement for the sequential half of the reference's fla
Triton suite (/root/reference/gllm/layers/ops/fla/ — chunk.py's
fwd_recompute/fwd_o pipeline): the in-chunk triangular work (decay
matrices, (I+A)^-1, v', k_cumdecay) is MXU-friendly *parallel* math that
XLA already batches well (native TriangularSolve), so it stays in
ops/gdn.py; what XLA cannot do well is the *sequential* inter-chunk state
recurrence — a lax.scan whose [Dk, Dv] carry round-trips HBM every chunk.

This kernel fuses that scan: grid = (S·H, N) with the chunk axis innermost
("arbitrary" semantics), the running state lives in VMEM scratch across
chunk steps, and per-chunk operand blocks stream through the Pallas
pipeline (double-buffered DMA). HBM traffic for the state drops from
2·N·Dk·Dv·4 bytes per (seq, head) to one final write.

Recurrence per chunk (HF torch_chunk_gated_delta_rule semantics,
precomputed operands):
    v'   = k_cumdecay @ state
    vnew = v2 - v'
    out  = (q ⊙ e^g) @ state + attn_local @ vnew
    state = e^{g_C} · state + (k ⊙ e^{g_C - g})ᵀ @ vnew
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v2_ref, kcd_ref, attn_ref, g_ref, init_ref,
            out_ref, final_ref, state, *, chunk: int):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state[:] = init_ref[0]

    st = state[:]                                       # [Dk, Dv] f32
    g = g_ref[0, 0]                                     # [C, 1]
    eg = jnp.exp(g)
    v_new = v2_ref[0, 0] - jax.lax.dot(                 # [C, Dv]
        kcd_ref[0, 0], st, preferred_element_type=jnp.float32)
    out = jax.lax.dot(q_ref[0, 0] * eg, st,
                      preferred_element_type=jnp.float32) \
        + jax.lax.dot(attn_ref[0, 0], v_new,
                      preferred_element_type=jnp.float32)
    g_last = g[chunk - 1, 0]
    k_dec = k_ref[0, 0] * jnp.exp(g_last - g)           # [C, Dk]
    st = st * jnp.exp(g_last) + jax.lax.dot(
        k_dec.T, v_new, preferred_element_type=jnp.float32)
    state[:] = st
    out_ref[0, 0] = out
    final_ref[0] = st


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_chunk_scan(
    qc: jnp.ndarray,      # [B, N, C, Dk] f32 (l2normed, scaled)
    kc: jnp.ndarray,      # [B, N, C, Dk] f32
    v2: jnp.ndarray,      # [B, N, C, Dv] f32 (Tmat @ v_beta)
    kcd: jnp.ndarray,     # [B, N, C, Dk] f32 (Tmat @ (k_beta · e^gcum))
    attn: jnp.ndarray,    # [B, N, C, C]  f32 (masked local scores)
    gcum: jnp.ndarray,    # [B, N, C, 1]  f32 (in-chunk cumulative decay)
    init_state: jnp.ndarray,   # [B, Dk, Dv] f32
    *,
    interpret: bool = False,
):
    """Returns (out [B, N, C, Dv] f32, final_state [B, Dk, Dv] f32)."""
    B, N, C, Dk = qc.shape
    Dv = v2.shape[-1]

    def blk(shape_tail):
        return pl.BlockSpec((1, 1) + shape_tail,
                            lambda b, n: (b, n) + (0,) * len(shape_tail),
                            memory_space=pltpu.VMEM)

    state_spec = pl.BlockSpec((1, Dk, Dv), lambda b, n: (b, 0, 0),
                              memory_space=pltpu.VMEM)
    out, final = pl.pallas_call(
        functools.partial(_kernel, chunk=C),
        grid=(B, N),
        in_specs=[blk((C, Dk)), blk((C, Dk)), blk((C, Dv)), blk((C, Dk)),
                  blk((C, C)), blk((C, 1)), state_spec],
        out_specs=[blk((C, Dv)), state_spec],
        out_shape=[jax.ShapeDtypeStruct((B, N, C, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, Dk, Dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        # chunk axis is a sequential scan over the VMEM-resident state;
        # the batch axis is embarrassingly parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qc, kc, v2, kcd, attn, gcum, init_state)
    return out, final
