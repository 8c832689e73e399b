"""Gated DeltaNet ops (Qwen3-Next / Qwen3.5 hybrid linear attention).

TPU-native equivalents of the reference's fla Triton suite
(/root/reference/gllm/layers/ops/fla/, 7210 LoC — chunked prefill
``chunk_gated_delta_rule``, recurrent decode, causal conv1d with state,
gated RMSNorm). Semantics follow the HF Qwen3Next reference math
(transformers qwen3_next torch_chunk_gated_delta_rule et al.), which those
kernels implement.

Design notes:
- everything computes in float32 (the recurrence is numerically touchy; the
  reference kernels do the same);
- the in-chunk triangular inverse (I - A)^-1 is a `solve_triangular`, not
  the reference's sequential row loop — one XLA op that maps onto the MXU;
- batched over sequences with per-token validity folded into (g, beta):
  a padded token with g = 0, beta = 0 is the identity on the state, so
  ragged batches ride in fixed [S, T] shapes with no extra machinery;
- decode (T = 1) uses the closed-form single-step update, no scan.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    inv = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
    return x * inv


def causal_conv1d(x: jnp.ndarray, state: jnp.ndarray, weight: jnp.ndarray,
                  q_lens: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv with carried state (reference
    mamba/causal_conv1d_triton.py semantics, varlen + state slots).

    x: [S, T, C] (per-seq rows, padded past q_lens[s])
    state: [S, C, K-1] last K-1 REAL inputs from previous chunks
    weight: [C, K]
    Returns (silu(conv(x)) [S, T, C], new_state [S, C, K-1]) where the new
    state holds the last K-1 valid inputs (padding excluded).
    """
    S, T, C = x.shape
    K = weight.shape[-1]
    xf = x.astype(jnp.float32)
    buf = jnp.concatenate([state.transpose(0, 2, 1).astype(jnp.float32),
                           xf], axis=1)               # [S, K-1+T, C]
    out = sum(buf[:, j:j + T, :] * weight[:, j].astype(jnp.float32)
              for j in range(K))
    out = jax.nn.silu(out)
    # new state = inputs at positions q_len-1 ... q_len-(K-1) of the valid
    # region, i.e. buf rows [q_len, q_len+K-2] (buf row i holds input i-K+1)
    idx = q_lens[:, None] + jnp.arange(K - 1)[None, :]       # [S, K-1]
    new_state = jnp.take_along_axis(
        buf, idx[:, :, None].astype(jnp.int32), axis=1)      # [S, K-1, C]
    return out, new_state.transpose(0, 2, 1)


def recurrent_gated_delta_step(
    q: jnp.ndarray,          # [S, H, Dk]
    k: jnp.ndarray,          # [S, H, Dk]
    v: jnp.ndarray,          # [S, H, Dv]
    g: jnp.ndarray,          # [S, H] log decay (<= 0)
    beta: jnp.ndarray,       # [S, H] write strength in (0, 1)
    state: jnp.ndarray,      # [S, H, Dk, Dv] f32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step of the gated delta rule (HF
    torch_recurrent_gated_delta_rule with T = 1)."""
    q = l2norm(q.astype(jnp.float32))
    k = l2norm(k.astype(jnp.float32))
    v = v.astype(jnp.float32)
    scale = q.shape[-1] ** -0.5
    q = q * scale
    state = state * jnp.exp(g)[..., None, None]
    kv_mem = jnp.einsum("shkv,shk->shv", state, k)
    delta = (v - kv_mem) * beta[..., None]
    state = state + jnp.einsum("shk,shv->shkv", k, delta)
    out = jnp.einsum("shkv,shk->shv", state, q)
    return out, state


def gdn_pallas_ok(dk: int, dv: int) -> bool:
    """Can Mosaic compile the chunk-scan kernel for these head dims?"""
    return dk % 128 == 0 and dv % 128 == 0


@functools.partial(jax.jit, static_argnames=("chunk_size", "impl"))
def chunk_gated_delta_rule(
    q: jnp.ndarray,          # [S, T, H, Dk]
    k: jnp.ndarray,          # [S, T, H, Dk]
    v: jnp.ndarray,          # [S, T, H, Dv]
    g: jnp.ndarray,          # [S, T, H] log decay (0 on padded tokens)
    beta: jnp.ndarray,       # [S, T, H] (0 on padded tokens)
    initial_state: Optional[jnp.ndarray] = None,   # [S, H, Dk, Dv]
    chunk_size: int = 64,
    impl: str = "xla",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked gated delta rule (HF torch_chunk_gated_delta_rule, batched).

    Returns (out [S, T, H, Dv] f32, final_state [S, H, Dk, Dv] f32).
    Padded tokens must carry g = 0 and beta = 0 (identity on the state).

    ``impl="pallas"`` runs the sequential inter-chunk scan in the fused
    VMEM-resident kernel (ops/pallas/gdn_scan.py); the in-chunk triangular
    math stays on XLA's native batched TriangularSolve either way.
    """
    S, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = min(chunk_size, max(16, 1 << (T - 1).bit_length()))
    pad = (-T) % C

    q = l2norm(q.astype(jnp.float32)) * Dk ** -0.5
    k = l2norm(k.astype(jnp.float32))
    v = v.astype(jnp.float32)
    g = g.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                   for a in (g, beta))
    N = (T + pad) // C

    # [S, H, N, C, D] chunked layout
    def chunked(a):
        return a.reshape(S, N, C, H, -1).transpose(0, 3, 1, 2, 4)

    qc, kc, vc = chunked(q), chunked(k), chunked(v)
    gc = g.reshape(S, N, C, H).transpose(0, 3, 1, 2)         # [S, H, N, C]
    bc = beta.reshape(S, N, C, H).transpose(0, 3, 1, 2)
    v_beta = vc * bc[..., None]
    k_beta = kc * bc[..., None]

    gcum = jnp.cumsum(gc, axis=-1)                           # [S, H, N, C]
    tril = jnp.tril(jnp.ones((C, C), bool))
    tril_strict = jnp.tril(jnp.ones((C, C), bool), -1)
    decay = jnp.where(tril,
                      jnp.exp(gcum[..., :, None] - gcum[..., None, :]), 0.0)

    # A = strictly-lower in-chunk interaction; the reference's sequential
    # row recurrence computes (I + A)^-1 — one triangular solve here.
    A = jnp.where(tril_strict, (k_beta @ kc.swapaxes(-1, -2)) * decay, 0.0)
    eye = jnp.eye(C, dtype=jnp.float32)
    Tmat = jax.scipy.linalg.solve_triangular(
        eye + A, jnp.broadcast_to(eye, A.shape), lower=True)

    v2 = Tmat @ v_beta                                       # [S,H,N,C,Dv]
    k_cumdecay = Tmat @ (k_beta * jnp.exp(gcum)[..., None])

    attn_local = jnp.where(tril, (qc @ kc.swapaxes(-1, -2)) * decay, 0.0)

    state0 = (jnp.zeros((S, H, Dk, Dv), jnp.float32)
              if initial_state is None
              else initial_state.astype(jnp.float32))

    if impl == "pallas":
        interpret = jax.default_backend() == "cpu"
        if not (interpret or gdn_pallas_ok(Dk, Dv)):
            # the runners resolve `auto` to XLA (with a warning) for such
            # a model; reaching here means pallas was asked for by name
            raise NotImplementedError(
                f"GDN Pallas scan needs 128-lane-aligned head dims, got "
                f"Dk={Dk} Dv={Dv}; use impl='xla'")
        from gllm_tpu.ops.pallas.gdn_scan import gdn_chunk_scan
        B = S * H
        out_p, final_p = gdn_chunk_scan(
            qc.reshape(B, N, C, Dk), kc.reshape(B, N, C, Dk),
            v2.reshape(B, N, C, Dv), k_cumdecay.reshape(B, N, C, Dk),
            attn_local.reshape(B, N, C, C),
            gcum.reshape(B, N, C, 1),
            state0.reshape(B, Dk, Dv), interpret=interpret)
        out = out_p.reshape(S, H, N, C, Dv)
        out = out.transpose(0, 2, 3, 1, 4).reshape(
            S, T + pad, H, Dv)[:, :T]
        return out, final_p.reshape(S, H, Dk, Dv)

    def chunk_step(state, inputs):
        q_i, k_i, v_i, kcd_i, attn_i, g_i = inputs
        # [S, H, C, Dv]
        v_prime = kcd_i @ state
        v_new = v_i - v_prime
        attn_inter = (q_i * jnp.exp(g_i)[..., None]) @ state
        out_i = attn_inter + attn_i @ v_new
        g_last = g_i[..., -1]
        state = state * jnp.exp(g_last)[..., None, None] \
            + (k_i * jnp.exp(g_last[..., None] - g_i)[..., None]) \
            .swapaxes(-1, -2) @ v_new
        return state, out_i

    # scan over chunks (axis 2 of the [S, H, N, ...] tensors)
    def mv(a):
        return jnp.moveaxis(a, 2, 0)

    final_state, outs = jax.lax.scan(
        chunk_step, state0,
        (mv(qc), mv(kc), mv(v2), mv(k_cumdecay), mv(attn_local), mv(gcum)))
    out = jnp.moveaxis(outs, 0, 2)                           # [S,H,N,C,Dv]
    out = out.transpose(0, 2, 3, 1, 4).reshape(S, T + pad, H, Dv)[:, :T]
    return out, final_state


def rms_norm_gated(x: jnp.ndarray, gate: jnp.ndarray, weight: jnp.ndarray,
                   eps: float) -> jnp.ndarray:
    """Norm-then-gate (HF Qwen3NextRMSNormGated): rmsnorm(x) * silu(gate)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight
    return (normed * jax.nn.silu(gate.astype(jnp.float32))).astype(x.dtype)
