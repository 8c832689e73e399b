"""Pallas TPU paged decode attention.

NOTE (unified step, docs/overlap_scheduling.md#unified-step): under
``--unified-step`` every paged step — pure decode included — routes
through the unified ragged kernel (ops/pallas/ragged_attention.py,
``unified=True``), whose decode-class blocks reproduce this kernel's
grouped round-robin fetch discipline inside the one program. This module
is kept as the legacy dispatch path (flag off) and as the PARITY ORACLE
the unified kernel's decode-class path is tested against
(tests/test_unified_step.py).

The decode half of the reference's core attention kernel
(sgl_kernel ``flash_attn_with_kvcache`` — /root/reference/gllm/layers/
attention.py:92-140; Triton split-K analogue in layers/ops/
triton_decode_attention.py). One query row per sequence attends over that
sequence's paged KV context.

Design (TPU-first, not a Triton translation):
- grid = (S,): one program per sequence; each program streams its own page
  list — HBM traffic is the sequence's *actual* context, independent of the
  padded page-table bucket (the XLA gather fallback pays the padded extent).
- KV pages stay in HBM (`pl.ANY`); the kernel double-buffers page blocks
  into VMEM with async DMA, overlapping fetch with the flash-attention
  accumulation (online softmax in f32 carried through the kv-block loop).
- GQA is computed as a kv-head-batched dot: q reshaped to [Hkv, G, D] so
  every kv head's group hits the MXU together.
- The kv-block loop bound is dynamic (ceil(kv_len / block)): padded
  sequences (kv_len 0) skip the loop entirely.
- MLA absorbed mode: ``v_cache=None`` + ``v_dim`` reads values as the
  leading ``v_dim`` lanes of each key block (the latent prefix) — one DMA
  stream instead of two (reference MLA shares the latent cache the same
  way, layers/attention.py:272-293).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gllm_tpu.ops.pallas.paged_kv import (attend_block, kv_stream_specs,
                                          make_fetch_fns, unpack_refs)

DEFAULT_KV_BLOCK = 256


def _kernel_grouped(kv_lens_ref, pt_ref,    # scalar prefetch
                    *refs,
                    page_size: int, pages_per_block: int, scale: float,
                    num_kv_heads: int, group: int, head_dim: int,
                    v_dim: int, shared_kv: bool, mqa: bool, gsz: int,
                    quant: bool):
    """``gsz`` sequences per grid program, ONE buffer slot each, fetched
    round-robin so up to ``gsz`` page DMAs are in flight at once.

    Rationale (r5 on-chip): decode compute per kv block is ~0 — the MXU
    dots are microscopic — so the per-seq double buffer of ``_kernel``
    degenerates into a chain of bare DMA *latencies* (~44 µs/seq
    measured; × S/2 programs per core × num_layers ≈ the whole decode
    step). Interleaving ``gsz`` sequences divides that latency chain by
    ``gsz`` without paying any padded-extent HBM traffic."""
    (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
     vs_buf, sems) = unpack_refs(refs, shared_kv, quant)
    gi = pl.program_id(0)
    bk = pages_per_block * page_size
    start_fetch, wait_fetch = make_fetch_fns(
        pt_ref, k_hbm, v_hbm, k_buf, v_buf, sems, pages_per_block,
        shared_kv, ks_hbm=ks_hbm, vs_hbm=vs_hbm, ks_buf=ks_buf,
        vs_buf=vs_buf)

    seq_ids = [gi * gsz + g for g in range(gsz)]
    kv_lens = [kv_lens_ref[s] for s in seq_ids]
    n_blocks = [pl.cdiv(kv_len, bk) for kv_len in kv_lens]
    for g in range(gsz):
        @pl.when(n_blocks[g] > 0)
        def _(g=g):
            start_fetch(g, seq_ids[g], 0)

    lead = (num_kv_heads * group,) if mqa else (num_kv_heads, group)
    qs = []
    for g in range(gsz):
        q = q_ref[g].astype(jnp.float32) * scale          # [Hq, D]
        qs.append(q if mqa else q.reshape(num_kv_heads, group, head_dim))

    max_nb = n_blocks[0]
    for g in range(1, gsz):
        max_nb = jnp.maximum(max_nb, n_blocks[g])

    def body(r, carry):
        out = list(carry)
        for g in range(gsz):
            m, l, acc = out[3 * g], out[3 * g + 1], out[3 * g + 2]
            live = r < n_blocks[g]

            @pl.when(live)
            def _(g=g):
                wait_fetch(g, seq_ids[g], r)

            # NOTE: the next-block re-issue for this slot happens inside
            # pl.when below, between the (buffered) loads attend_block
            # performs and the rest of the round-robin — program order
            # keeps the loads ahead of the re-issued DMA.
            m_new, l_new, acc_new = attend_block(
                qs[g], k_buf, v_buf, g, bk, num_kv_heads, head_dim,
                v_dim, shared_kv, mqa, kv_lens[g], r, m, l, acc,
                ks_buf=ks_buf, vs_buf=vs_buf)

            @pl.when(live & (r + 1 < n_blocks[g]))
            def _(g=g):
                start_fetch(g, seq_ids[g], r + 1)

            out[3 * g] = jnp.where(live, m_new, m)
            out[3 * g + 1] = jnp.where(live, l_new, l)
            out[3 * g + 2] = jnp.where(live, acc_new, acc)
        return tuple(out)

    init = []
    for _ in range(gsz):
        init += [jnp.full((*lead, 1), -jnp.inf, jnp.float32),
                 jnp.zeros((*lead, 1), jnp.float32),
                 jnp.zeros((*lead, v_dim), jnp.float32)]
    final = jax.lax.fori_loop(0, max_nb, body, tuple(init))
    for g in range(gsz):
        l, acc = final[3 * g + 1], final[3 * g + 2]
        out = acc / jnp.maximum(l, 1e-30)                # padded seqs → 0
        o_ref[g] = out.reshape(num_kv_heads * group,
                               v_dim).astype(o_ref.dtype)


def _kernel(kv_lens_ref, pt_ref,            # scalar prefetch
            *refs,
            page_size: int, pages_per_block: int, scale: float,
            num_kv_heads: int, group: int, head_dim: int, v_dim: int,
            shared_kv: bool, mqa: bool, quant: bool):
    (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
     vs_buf, sems) = unpack_refs(refs, shared_kv, quant)
    s = pl.program_id(0)
    kv_len = kv_lens_ref[s]
    bk = pages_per_block * page_size
    n_blocks = pl.cdiv(kv_len, bk)

    start_fetch, wait_fetch = make_fetch_fns(
        pt_ref, k_hbm, v_hbm, k_buf, v_buf, sems, pages_per_block,
        shared_kv, ks_hbm=ks_hbm, vs_hbm=vs_hbm, ks_buf=ks_buf,
        vs_buf=vs_buf)

    @pl.when(n_blocks > 0)
    def _():
        start_fetch(0, s, 0)

    q = q_ref[0].astype(jnp.float32) * scale          # [Hq, D]
    # MQA (Hkv == 1): keep everything 2-D — scores [Hq, BK] from one
    # q @ kᵀ MXU dot; the caches arrive 3-D with the head axis squeezed.
    qh = q if mqa else q.reshape(num_kv_heads, group, head_dim)

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _():
            start_fetch(1 - slot, s, i + 1)

        wait_fetch(slot, s, i)
        return attend_block(qh, k_buf, v_buf, slot, bk, num_kv_heads,
                            head_dim, v_dim, shared_kv, mqa, kv_len, i,
                            m, l, acc, ks_buf=ks_buf, vs_buf=vs_buf)

    lead = (num_kv_heads * group,) if mqa else (num_kv_heads, group)
    m0 = jnp.full((*lead, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((*lead, 1), jnp.float32)
    acc0 = jnp.zeros((*lead, v_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)                   # padded seqs → 0
    o_ref[0] = out.reshape(num_kv_heads * group,
                           v_dim).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "kv_block", "interpret",
                                    "v_dim", "group_size"))
def paged_decode_attention(
    q: jnp.ndarray,            # [S, Hq, D]
    k_cache: jnp.ndarray,      # [num_pages, page_size, Hkv, D]
    v_cache: Optional[jnp.ndarray],  # None → v = k[..., :v_dim] (MLA)
    kv_lens: jnp.ndarray,      # [S] int32 (0 for padded rows)
    page_table: jnp.ndarray,   # [S, max_pages] int32 (padding → dummy page 0)
    *,
    scale: float,
    kv_block: int = DEFAULT_KV_BLOCK,
    interpret: bool = False,
    v_dim: Optional[int] = None,
    group_size: int = 1,       # seqs per grid program (see _kernel_grouped)
    k_scale: Optional[jnp.ndarray] = None,   # [num_pages, Hkv] f32 (int8)
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    S, num_q_heads, head_dim = q.shape
    num_pages, page_size, num_kv_heads, _ = k_cache.shape
    max_pages = page_table.shape[1]
    group = num_q_heads // num_kv_heads
    shared_kv = v_cache is None
    quant = k_scale is not None
    if shared_kv:
        if v_dim is None:
            raise ValueError("v_dim required when v_cache is None")
    else:
        v_dim = v_cache.shape[-1]

    # MQA (MLA's latent cache): squeeze the singleton head axis — Mosaic's
    # sublane tiling rejects slicing a size-1 second-minor dim — and run
    # the kernel's 2-D path.
    mqa = num_kv_heads == 1
    if quant and (mqa or shared_kv):
        raise NotImplementedError(
            "int8 KV cache unsupported for MQA/MLA decode kernels")
    if mqa:
        k_cache = k_cache.reshape(num_pages, page_size, head_dim)
        if v_cache is not None:
            v_cache = v_cache.reshape(num_pages, page_size, v_dim)

    pages_per_block = max(1, min(kv_block // page_size, max_pages))
    # page_table must cover whole blocks; pad with dummy page 0.
    rem = max_pages % pages_per_block
    if rem:
        page_table = jnp.pad(page_table,
                             ((0, 0), (0, pages_per_block - rem)))
        max_pages += pages_per_block - rem

    gsz = max(1, group_size)
    if gsz > 1:
        # pad the seq axis to a whole number of groups; padded rows have
        # kv_len 0 (skip every round) and dummy page-table rows
        s_pad = -(-S // gsz) * gsz
        if s_pad != S:
            q = jnp.pad(q, ((0, s_pad - S), (0, 0), (0, 0)))
            kv_lens = jnp.pad(kv_lens, (0, s_pad - S))
            page_table = jnp.pad(page_table, ((0, s_pad - S), (0, 0)))
        kernel = functools.partial(
            _kernel_grouped, page_size=page_size,
            pages_per_block=pages_per_block, scale=scale,
            num_kv_heads=num_kv_heads, group=group, head_dim=head_dim,
            v_dim=v_dim, shared_kv=shared_kv, mqa=mqa, gsz=gsz,
            quant=quant)
        slots, n_prog, blk = gsz, s_pad // gsz, gsz
    else:
        kernel = functools.partial(
            _kernel, page_size=page_size, pages_per_block=pages_per_block,
            scale=scale, num_kv_heads=num_kv_heads, group=group,
            head_dim=head_dim, v_dim=v_dim, shared_kv=shared_kv, mqa=mqa,
            quant=quant)
        slots, n_prog, blk = 2, S, 1
        s_pad = S

    kv_specs, scratch_shapes, kv_inputs = kv_stream_specs(
        k_cache, v_cache, pages_per_block, page_size, num_kv_heads,
        head_dim, v_dim, mqa=mqa, slots=slots, k_scale=k_scale,
        v_scale=v_scale)
    in_specs = [
        pl.BlockSpec((blk, num_q_heads, head_dim), lambda s, *_: (s, 0, 0),
                     memory_space=pltpu.VMEM),
    ] + kv_specs
    inputs = [kv_lens, page_table, q] + kv_inputs

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_prog,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk, num_q_heads, v_dim),
                               lambda s, *_: (s, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, num_q_heads, v_dim),
                                       q.dtype),
        # Sequences/groups are independent → let Mosaic split the grid
        # across Megacore TensorCores.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)) if interpret else
        pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*inputs)
    return out[:S] if s_pad != S else out
