"""Weight quantization: int8 / fp8 / int4 weight-only, and W8A8.

TPU-native counterpart of the reference's quantization stack
(/root/reference/gllm/layers/quantization/fp8.py W8A8 block GEMM + int4
Marlin MoE, layers/moe/fused_moe_triton/layer.py:229-552): the reference
consumes prebuilt CUDA GEMMs; on TPU the idiomatic forms are

- **weight-only** (int8 / fp8 / packed int4): narrow storage + XLA-fused
  ``cast × scale`` in the matmul epilogue — halves/quarters HBM footprint
  and weight bandwidth (the decode bottleneck);
- **W8A8**: per-token activation quantization + an int8×int8 MXU matmul
  with f32 accumulation (TPU int8 matmul runs at double MACs/cycle),
  rescaled by the outer product of the activation and weight scales.

``Quantized``/``Quantized4``/``QuantizedW8A8`` are pytree nodes, so
quantized params flow through jit, donation, and NamedSharding exactly like
plain arrays; ``qmm`` dispatches on leaf type so model code is written once
(`qmm(x, lp["q_proj"])`). Routed-expert stacks ([L, E, in, out]) quantize
with the same per-output-channel machinery and are dequantized via ``deq``
in front of the ragged grouped GEMM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Union

import jax
import jax.numpy as jnp


class Quantized(NamedTuple):
    """Per-output-channel symmetric quantization: w ≈ q * scale."""
    q: jnp.ndarray        # [..., in, out] int8 (or float8)
    scale: jnp.ndarray    # [..., 1, out] f32


class Quantized4(NamedTuple):
    """Packed int4 (two nibbles per byte along the input axis)."""
    q: jnp.ndarray        # [..., in/2, out] int8, hi/lo nibbles
    scale: jnp.ndarray    # [..., 1, out] f32


class QuantizedW8A8(NamedTuple):
    """int8 weights whose matmul also quantizes activations per token."""
    q: jnp.ndarray        # [..., in, out] int8
    scale: jnp.ndarray    # [..., 1, out] f32


BLOCK = 128   # block-scale tile edge (reference fp8.py weight_block_size)


class QuantizedBlock(NamedTuple):
    """Block-wise fp8: one f32 scale per 128×128 weight tile (the
    reference's W8A8 block-fp8 checkpoint layout, fp8.py:370-453 — DeepSeek
    V3-class fp8 checkpoints ship exactly these scales)."""
    q: jnp.ndarray        # [..., in, out] float8
    scale: jnp.ndarray    # [..., ceil(in/128), ceil(out/128)] f32


@functools.partial(jax.jit, static_argnames=("dtype",))
def quantize_weight(w: jnp.ndarray, dtype=jnp.int8) -> Quantized:
    """Quantize a [..., in, out] matmul weight per output channel. Jitted:
    the float32 copy, the quotient and its rounding fuse into two passes
    over ``w`` and never exist whole. (Op by op, a stacked matrix of 0.8 G
    weights held three float32 temporaries of 3 GB beside 8 GB of bf16
    weights, and the int8 control of a 4 B-parameter model ran out of a
    16 GB chip's memory before its KV pool was built.)"""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    if dtype == jnp.int8:
        scale = absmax / 127.0
        q = jnp.clip(jnp.round(wf / jnp.maximum(scale, 1e-9)),
                     -127, 127).astype(jnp.int8)
    else:  # float8 family
        fmax = float(jnp.finfo(dtype).max)
        scale = absmax / fmax
        q = (wf / jnp.maximum(scale, 1e-9)).astype(dtype)
    return Quantized(q, scale)


def quantize_weight_int4(w: jnp.ndarray) -> Quantized4:
    """Per-output-channel int4, packed two-per-byte on the input axis
    (the role of the reference's Marlin int4 path)."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = absmax / 7.0
    q = jnp.clip(jnp.round(wf / jnp.maximum(scale, 1e-9)),
                 -8, 7).astype(jnp.int8)
    *lead, K, N = q.shape
    if K % 2:
        raise ValueError(f"int4 packing needs an even input dim, got {K}")
    q = q.reshape(*lead, K // 2, 2, N)
    packed = ((q[..., 0, :] & 0x0F)
              | ((q[..., 1, :] & 0x0F) << 4)).astype(jnp.int8)
    return Quantized4(packed, scale)


def quantize_weight_block(w: jnp.ndarray,
                          dtype=jnp.float8_e4m3fn) -> QuantizedBlock:
    """Quantize a [..., in, out] weight with per-128×128-tile scales.
    Ragged tails pad with zeros for the absmax; the stored payload keeps
    the original shape."""
    wf = w.astype(jnp.float32)
    *lead, K, N = wf.shape
    kb, nb = -(-K // BLOCK), -(-N // BLOCK)
    wp = jnp.pad(wf, [(0, 0)] * len(lead)
                 + [(0, kb * BLOCK - K), (0, nb * BLOCK - N)])
    tiles = wp.reshape(*lead, kb, BLOCK, nb, BLOCK)
    absmax = jnp.max(jnp.abs(tiles), axis=(-3, -1))          # [..., kb, nb]
    fmax = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(absmax / fmax, 1e-9)
    q = (tiles / scale[..., :, None, :, None]).reshape(
        *lead, kb * BLOCK, nb * BLOCK)[..., :K, :N].astype(dtype)
    return QuantizedBlock(q, scale)


def _unpack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """[..., in/2, out] packed → [..., in, out] int8 in [-8, 7]."""
    lo = (q << 4).astype(jnp.int8) >> 4          # sign-extend low nibble
    hi = q >> 4                                  # arithmetic shift: high
    *lead, K2, N = q.shape
    return jnp.stack([lo, hi], axis=-2).reshape(*lead, K2 * 2, N)


def deq(w, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Dequantize any weight leaf to a dense array (expert stacks feed
    this into lax.ragged_dot)."""
    if isinstance(w, Quantized4):
        return (_unpack_int4(w.q).astype(dtype)
                * w.scale.astype(dtype))
    if isinstance(w, QuantizedBlock):
        K, N = w.q.shape[-2:]
        s = jnp.repeat(jnp.repeat(w.scale, BLOCK, axis=-2), BLOCK,
                       axis=-1)[..., :K, :N]
        return w.q.astype(dtype) * s.astype(dtype)
    if isinstance(w, (Quantized, QuantizedW8A8)):
        return w.q.astype(dtype) * w.scale.astype(dtype)
    return w


def qragged_dot(xs: jnp.ndarray, w, group_sizes: jnp.ndarray,
                expert_ids: jnp.ndarray = None) -> jnp.ndarray:
    """Grouped (ragged) GEMM against a plain or quantized expert stack
    ([E, in, out]); rows of ``xs`` are expert-sorted.

    ``QuantizedW8A8`` stacks run the int8×int8 MXU grouped GEMM with int32
    accumulation and rescale in the epilogue — per-token activation scale
    × per-(expert, output-channel) weight scale gathered by
    ``expert_ids`` ([R] i32, the row's expert). This is the compute-win
    analogue of the reference's fused quantized MoE GEMMs
    (layers/moe/fused_moe_triton/layer.py:229-552, quantization/fp8.py) —
    no dense dequantized copy of the expert stack exists anywhere.

    Weight-only stacks (int8/fp8/int4/fp8_block) dequantize into the GEMM
    transient by design: their contract is bf16 activations × narrow
    storage (the reference W4A16 Marlin semantics); TPU has no mixed
    int×bf16 MXU mode, so the cast rides the GEMM epilogue fusion."""
    if isinstance(w, QuantizedW8A8):
        assert expert_ids is not None, "W8A8 ragged GEMM needs expert ids"
        xf = xs.astype(jnp.float32)
        x_absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        x_scale = jnp.maximum(x_absmax / 127.0, 1e-9)
        xq = jnp.clip(jnp.round(xf / x_scale), -127, 127).astype(jnp.int8)
        acc = jax.lax.ragged_dot(
            xq, w.q, group_sizes,
            preferred_element_type=jnp.int32).astype(jnp.float32)
        w_scale = jnp.squeeze(w.scale.astype(jnp.float32),
                              axis=-2)[expert_ids]       # [R, out]
        return (acc * x_scale * w_scale).astype(xs.dtype)
    return jax.lax.ragged_dot(xs, deq(w, xs.dtype), group_sizes)


def qmm(x: jnp.ndarray, w) -> jnp.ndarray:
    """Matmul against a plain or quantized weight."""
    if isinstance(w, QuantizedW8A8):
        # per-token activation quantization → int8×int8 MXU matmul with
        # f32 accumulation (reference fp8.py W8A8 block GEMM analogue)
        xf = x.astype(jnp.float32)
        x_absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        x_scale = jnp.maximum(x_absmax / 127.0, 1e-9)
        xq = jnp.clip(jnp.round(xf / x_scale), -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(
            xq, w.q, (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        return (acc * x_scale * w.scale.astype(jnp.float32)
                ).astype(x.dtype)
    if isinstance(w, (Quantized, Quantized4, QuantizedBlock)):
        return x @ deq(w, x.dtype)
    return x @ w


# Matmul leaves of the model layer groups that get quantized (norms,
# biases, rope tables, routers, and embeddings stay high-precision — same
# policy as the reference's ignored-layers audit, model_loader.py:122-174).
# Routed-expert stacks are included (the reference's weight-only path
# skipped them; its int4 Marlin path is the expert-quantizing one).
QUANT_LEAVES = frozenset({
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
    "q_b_proj", "shared_gate_proj", "shared_up_proj", "shared_down_proj",
    "w_gate", "w_up", "w_down",
    "in_qkvz", "out_proj",                       # hybrid GDN projections
    "in_proj",                                   # Mamba-2's (out_proj too)
})

_MODES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


def quantize_params(params: dict, dtype=jnp.int8, mode: str = None) -> dict:
    """Quantize the eligible matmul leaves of a model param tree.

    ``mode``: int8 | fp8 | int4 | w8a8 (overrides ``dtype`` when given).
    """
    def make(v):
        if mode == "int4":
            return quantize_weight_int4(v)
        if mode == "fp8_block":
            return quantize_weight_block(v)
        if mode == "w8a8":
            qz = quantize_weight(v, jnp.int8)
            return QuantizedW8A8(qz.q, qz.scale)
        if mode is not None and mode not in _MODES:
            raise ValueError(f"unknown quantization mode {mode!r}")
        return quantize_weight(v, _MODES[mode] if mode else dtype)

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            elif k in QUANT_LEAVES:
                out[k] = make(v)
            else:
                out[k] = v
        return out

    return walk(params)


def param_bytes(params) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(params))
