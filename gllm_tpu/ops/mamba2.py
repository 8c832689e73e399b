"""Mamba-2 (SSD) ops: a recurrence over a state with one scalar decay a
head (NemotronH's ``M`` blocks).

Per head h of ``P`` channels, state ``S_h`` in R^{P x N}, group g = h //
(H / G) sharing ``B_g``, ``C_g`` in R^N:

    S_h <- a_h S_h + (dt_h x_h) B_g^T ;   y_h = S_h C_g          (+ D_h x_h,
                                                     added by the model)

with ``a_h = exp(-dt_h exp(A_log_h))`` in (0, 1). The state is stored
[P, N]: N = 128 lies along the lanes.

- one new token a row: ``mamba2_recurrent_step`` (plain XLA, between a
  gather of the rows' states and a scatter back), or in place in the slot
  pool by the Pallas kernel (ops/pallas/mamba2_recurrent.py);
- a prompt's chunk: the chunked rule (SSD) over the PACKED layout the GDN
  rule uses (ops/gdn.gdn_chunk_slots at ``ModelConfig.ssm_chunk`` tokens,
  the published ``chunk_size`` 128). A
  scalar decay means no triangular solve: with ``l`` the in-chunk
  cumulative log decay, the in-chunk half is

      y_intra = ((C B^T) * exp(l_t - l_s) [s <= t]) (dt x)

  and the inter-chunk scan, per chunk,

      y     = y_intra + (C e^l) S^T
      S    <- e^{l_C} S + ((dt x) e^{l_C - l})^T B

  three products a chunk. The scan runs as a ``lax.scan``
  (``mamba2_chunk_packed``) or as the Pallas kernel in place in the pool
  (``mamba2_chunk_pool``, ops/pallas/mamba2_scan.py).

Everything computes in float32. A padded token carries dt = 0 and log
decay 0: the identity on the state.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def mamba2_recurrent_step(
    xdt: jnp.ndarray,        # [S, H, P] dt_h x_h
    decay: jnp.ndarray,      # [S, H] a_h in (0, 1]
    B: jnp.ndarray,          # [S, G, N]
    C: jnp.ndarray,          # [S, G, N]
    state: jnp.ndarray,      # [S, H, P, N] f32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row. Returns (y [S, H, P] f32 without the skip, state)."""
    H, G = xdt.shape[1], B.shape[1]
    Bh = jnp.repeat(B.astype(jnp.float32), H // G, axis=1)
    Ch = jnp.repeat(C.astype(jnp.float32), H // G, axis=1)
    state = (state * decay.astype(jnp.float32)[..., None, None]
             + xdt.astype(jnp.float32)[..., None] * Bh[:, :, None, :])
    return jnp.einsum("shpn,shn->shp", state, Ch, precision=_HI), state


def _chunk_local(xdt, la, B, C):
    """The in-chunk half: what a chunk computes without the state that
    enters it. xdt [Nc, C, H, P], la [Nc, C, H] log decay, B, C [Nc, C, G,
    N]. Returns, float32: y_intra [Nc, H, C, P], the scan's operands
    ``Cexp`` = C e^l [Nc, H, C, N], ``xd`` = (dt x) e^{l_C - l} [Nc, H, C,
    P], B over the heads' groups [Nc, G, C, N] and the chunk's decay e^{l_C}
    [Nc, H]."""
    with jax.named_scope("mamba_chunk_local"):
        Nc, Cn, H, P = xdt.shape
        G = B.shape[2]
        xdt = xdt.astype(jnp.float32).transpose(0, 2, 1, 3)   # [Nc,H,C,P]
        l = jnp.cumsum(la.astype(jnp.float32), axis=1)        # [Nc,C,H]
        l = l.transpose(0, 2, 1)                              # [Nc,H,C]
        Bg = B.astype(jnp.float32).transpose(0, 2, 1, 3)      # [Nc,G,C,N]
        Cg = C.astype(jnp.float32).transpose(0, 2, 1, 3)
        scores = jnp.einsum("ngtk,ngsk->ngts", Cg, Bg, precision=_HI)
        tril = jnp.tril(jnp.ones((Cn, Cn), bool))
        # the exponent is masked, not the result: above the diagonal
        # l_t - l_s is positive and its exponential overflows
        mask = jnp.exp(jnp.where(tril, l[..., :, None] - l[..., None, :],
                                 -jnp.inf))                   # [Nc,H,C,C]
        m = jnp.repeat(scores, H // G, axis=1) * mask
        y_intra = jnp.einsum("nhts,nhsp->nhtp", m, xdt, precision=_HI)
        l_last = l[..., -1:]                                  # [Nc,H,1]
        cexp = jnp.repeat(Cg, H // G, axis=1) * jnp.exp(l)[..., None]
        xd = xdt * jnp.exp(l_last - l)[..., None]
        return y_intra, cexp, xd, Bg, jnp.exp(l_last[..., 0])


def mamba2_chunk_packed(
    xdt: jnp.ndarray,        # [Nc, C, H, P]
    la: jnp.ndarray,         # [Nc, C, H] log decay (0 on padded tokens)
    B: jnp.ndarray,          # [Nc, C, G, N]
    C: jnp.ndarray,          # [Nc, C, G, N]
    row: jnp.ndarray,        # [Nc] int32: the sequence each chunk is of
    first: jnp.ndarray,      # [Nc] bool: the chunk is its sequence's first
    states: jnp.ndarray,     # [R, H, P, N] f32: state entering each seq
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The chunked rule over chunks packed one sequence after another (the
    layout of ``ops/gdn.chunk_gated_delta_rule_packed``): the scan runs
    down the packed axis, takes ``states[row[n]]`` where a sequence begins
    and leaves the state after each chunk in ``states[row[n]]``. Chunks
    past the last sequence carry xdt = la = 0 and a ``row`` no sequence
    uses. Returns (y [Nc, C, H, P] f32 without the skip, states)."""
    H, G = xdt.shape[2], B.shape[2]
    y_intra, cexp, xd, Bg, dl = _chunk_local(xdt, la, B, C)

    def step(carry, xs):
        st, states = carry
        r, is_first, y_i, c_i, x_i, b_i, d_i = xs
        st = jnp.where(is_first, states[r], st)
        y = y_i + jnp.einsum("hcn,hpn->hcp", c_i, st, precision=_HI)
        st = st * d_i[:, None, None] + jnp.einsum(
            "hcp,hcn->hpn", x_i, jnp.repeat(b_i, H // G, axis=0),
            precision=_HI)
        states = jax.lax.dynamic_update_index_in_dim(states, st, r, 0)
        return (st, states), y

    with jax.named_scope("mamba_chunk_scan"):
        (_, states), ys = jax.lax.scan(
            step, (jnp.zeros(states.shape[1:], jnp.float32),
                   states.astype(jnp.float32)),
            (row, first, y_intra, cexp, xd, Bg, dl))
    return ys.transpose(0, 2, 1, 3), states         # [Nc, C, H, P]


def mamba2_chunk_pool(
    xdt: jnp.ndarray,        # [Nc, C, H, P]
    la: jnp.ndarray,         # [Nc, C, H]
    B: jnp.ndarray,          # [Nc, C, G, N]
    C: jnp.ndarray,          # [Nc, C, G, N]
    slot: jnp.ndarray,       # [Nc] int32: the pool slot of each chunk's seq
    first: jnp.ndarray,      # [Nc] bool
    pool: jnp.ndarray,       # [slots, H, P, N] f32: every slot's state
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``mamba2_chunk_packed`` with the inter-chunk scan in the Pallas
    kernel, in place in the slot pool: a sequence's state is read from
    ``pool[slot[n]]`` where it begins and left there after its last chunk.
    Chunks past the last sequence name a dummy slot and carry xdt = la =
    0. Returns (y [Nc, C, H, P] f32, pool)."""
    from gllm_tpu.ops.pallas.mamba2_scan import mamba2_chunk_scan
    y_intra, cexp, xd, Bg, dl = _chunk_local(xdt, la, B, C)
    with jax.named_scope("mamba_chunk_local"):
        # what the kernel cannot spread itself: a chunk's decay over the
        # lanes of a state row, and the transposed operand transposed
        dl = jnp.broadcast_to(dl[..., None, None],
                              dl.shape + (1, pool.shape[-1]))
        xdT = xd.swapaxes(-1, -2)                         # [Nc, H, P, C]
    y, pool = mamba2_chunk_scan(y_intra, cexp, xdT, Bg, dl, pool, slot,
                                first, interpret=interpret)
    return y.transpose(0, 2, 1, 3), pool


def rms_norm_gated_grouped(y: jnp.ndarray, z: jnp.ndarray,
                           weight: jnp.ndarray, eps: float,
                           groups: int) -> jnp.ndarray:
    """Gate-then-norm over groups (Mamba-2's ``MambaRMSNormGated`` with
    ``norm_before_gate`` false): RMSNorm over each of ``groups`` equal
    parts of ``y * silu(z)``. y, z [T, D]; returns [T, D] in z's dtype."""
    T, D = y.shape
    g = (y.astype(jnp.float32)
         * jax.nn.silu(z.astype(jnp.float32))).reshape(T, groups, D // groups)
    var = jnp.mean(g * g, axis=-1, keepdims=True)
    g = (g * jax.lax.rsqrt(var + eps)).reshape(T, D)
    return (g * weight.astype(jnp.float32)).astype(z.dtype)
