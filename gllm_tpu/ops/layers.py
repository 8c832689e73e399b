"""Elementwise / normalization ops.

The reference calls prebuilt CUDA kernels for these
(sgl_kernel rmsnorm / fused_add_rmsnorm / silu_and_mul — SURVEY.md §2.6). On
TPU they are plain jnp: XLA fuses them into the surrounding matmuls, which is
exactly what the hand-written CUDA fusions buy on GPU.

All norms accumulate in float32 and cast back to the input dtype, matching
HF/reference numerics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    """Mean-subtracting LayerNorm without a bias (Cohere's):
    (x - mean(x)) / sqrt(var(x) + eps) * weight."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    normed = centred * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def fused_add_rms_norm(x: jnp.ndarray, residual: jnp.ndarray,
                       weight: jnp.ndarray, eps: float = 1e-6):
    """residual' = x + residual; y = rms_norm(residual').

    Mirrors the reference's fused_add_rmsnorm contract
    (/root/reference/gllm/layers/layernorm.py): returns (normed, new_residual).
    """
    new_residual = x + residual
    return rms_norm(new_residual, weight, eps), new_residual


def silu_and_mul(x: jnp.ndarray) -> jnp.ndarray:
    """x = [gate, up] concatenated on last dim → silu(gate) * up
    (reference layers/activation.py → sgl_kernel silu_and_mul)."""
    gate, up = jnp.split(x, 2, axis=-1)
    gf = gate.astype(jnp.float32)
    return ((gf * jax.nn.sigmoid(gf)).astype(x.dtype)) * up


def gelu_and_mul(x: jnp.ndarray) -> jnp.ndarray:
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.gelu(gate.astype(jnp.float32),
                       approximate=True).astype(x.dtype) * up
