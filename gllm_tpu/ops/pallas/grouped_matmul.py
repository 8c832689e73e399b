"""Grouped matrix product over the rows of the experts that have any, as a
Pallas kernel: jax's megablox ``gmm`` with this repo's choice of blocks.

``lhs`` [m, k] holds the rows sorted by group, ``group_sizes`` [G] how many
each group has, ``rhs`` [G, k, n] the groups' matrices; row r of group g
gives ``lhs[r] @ rhs[g]``. The kernel's grid runs over the (group, row
tile) pairs that have rows, so a group without rows costs nothing and its
matrix is not read: ``rhs`` may be a whole stack of layers of which one
layer's groups have rows (models/deepseek._held_experts), read in place.
Rows past the last group's are left as they are found (the caller masks
them).

Why not XLA's ``ragged_dot``: at Nemotron 3 Nano's expert widths (2688 x
1856, stored 1920) it moves 0.1-0.2 of the v5e's bandwidth (a decode step's
61 touched experts of 10.3 MB a matrix in 3.6-7.3 ms where 0.77 ms is the
least: PERF.md section 6, PR 41), where it reaches 0.56-0.69 at the latent
cells' 5120 x 1536 and 7168 x 2048.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_BYTES = 4 << 20       # one [k, tn] block of a group's matrix at most


def blocks(m: int, k: int, n: int, itemsize: int = 2):
    """(tm, tk, tn): a row tile of 128 (or all the rows, in eights, where
    there are fewer), the whole contraction, and the widest multiple of
    128 lanes that divides ``n`` and keeps a [k, tn] block under
    BLOCK_BYTES (double-buffered beside the row tile in 16 MB of VMEM)."""
    tm = min(128, -(-m // 8) * 8)
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and k * t * itemsize <= BLOCK_BYTES]
    return tm, k, (max(fits) if fits else min(n, 128))


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, *,
                   interpret: bool = False) -> jnp.ndarray:
    """[m, n] in lhs's dtype. k and n are whole lanes (multiples of 128)
    or the kernel's own remainder handling applies."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    m, k = lhs.shape
    n = rhs.shape[-1]
    tm, tk, tn = blocks(m, k, n, rhs.dtype.itemsize)
    pad = (-m) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=lhs.dtype, tiling=(tm, tk, tn),
              interpret=interpret)
    return out[:m] if pad else out
