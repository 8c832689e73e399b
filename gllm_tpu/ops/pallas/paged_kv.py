"""Shared paged-KV streaming machinery for the Pallas attention kernels.

Both the decode kernel (grid over sequences) and the ragged prefill kernel
(grid over q blocks) stream KV pages HBM→VMEM with double-buffered async
DMA, optionally with values read as the leading ``v_dim`` lanes of each key
block (MLA absorbed layout — one DMA stream). This module is the single
copy of that discipline.

int8 quantized caches (kv_cache_dtype=int8) add a third/fourth stream: the
per-page per-head f32 scale rows (``[num_pages, Hkv]``) ride the same page
DMAs into a tiny VMEM scratch, and ``block_kv`` dequantizes each block in
VMEM right before the MXU dots — the bf16 cache never exists in HBM, so
the decode read path moves half the bytes.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def unpack_refs(refs, shared_kv: bool, quant: bool):
    """Split a kernel's ``*refs`` into its named parts.

    Layout (absent streams collapse away):
    q, k_hbm[, v_hbm][, ks_hbm, vs_hbm], o, k_buf[, v_buf][, ks_buf,
    vs_buf], sems — matching the input/scratch order built by
    ``kv_stream_specs``. Returns an 11-tuple with None for absent refs.
    """
    n_hbm = 1 + (0 if shared_kv else 1) + (2 if quant else 0)
    q_ref = refs[0]
    hbm = list(refs[1:1 + n_hbm])
    o_ref = refs[1 + n_hbm]
    bufs = list(refs[2 + n_hbm:-1])
    sems = refs[-1]
    k_hbm = hbm.pop(0)
    v_hbm = None if shared_kv else hbm.pop(0)
    ks_hbm = hbm.pop(0) if quant else None
    vs_hbm = hbm.pop(0) if quant else None
    k_buf = bufs.pop(0)
    v_buf = None if shared_kv else bufs.pop(0)
    ks_buf = bufs.pop(0) if quant else None
    vs_buf = bufs.pop(0) if quant else None
    return (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
            k_buf, v_buf, ks_buf, vs_buf, sems)


def make_fetch_fns(pt_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
                   pages_per_block: int, shared_kv: bool,
                   ks_hbm=None, vs_hbm=None, ks_buf=None, vs_buf=None):
    """(start_fetch, wait_fetch), each taking (slot, seq, kv_block_idx).

    Copies ``pages_per_block`` whole pages per block. Semaphore layout is
    [slot, k_or_v]: ONE DMA semaphore per slot per stream — every page
    copy of a block signals it and wait_fetch consumes the same count
    (a per-page sem array blew the sflag scratch budget at
    group_size ≥ 8: slots × pages × 2 × 4 B > 2 KiB). Start/wait pairs
    must match 1:1 — the callers' buffer loops guarantee it. Quantized
    caches ride each page's scale row on the same per-stream semaphore
    (one extra tiny copy per page, same 1:1 accounting).
    """
    quant = ks_hbm is not None

    def start_fetch(slot, s, blk):
        for j in range(pages_per_block):
            page_idx = pt_ref[s, blk * pages_per_block + j]
            pltpu.make_async_copy(k_hbm.at[page_idx], k_buf.at[slot, j],
                                  sems.at[slot, 0]).start()
            if quant:
                pltpu.make_async_copy(ks_hbm.at[page_idx],
                                      ks_buf.at[slot, j],
                                      sems.at[slot, 0]).start()
            if not shared_kv:
                pltpu.make_async_copy(v_hbm.at[page_idx], v_buf.at[slot, j],
                                      sems.at[slot, 1]).start()
                if quant:
                    pltpu.make_async_copy(vs_hbm.at[page_idx],
                                          vs_buf.at[slot, j],
                                          sems.at[slot, 1]).start()

    def wait_fetch(slot, s, blk):
        for j in range(pages_per_block):
            page_idx = pt_ref[s, blk * pages_per_block + j]
            pltpu.make_async_copy(k_hbm.at[page_idx], k_buf.at[slot, j],
                                  sems.at[slot, 0]).wait()
            if quant:
                pltpu.make_async_copy(ks_hbm.at[page_idx],
                                      ks_buf.at[slot, j],
                                      sems.at[slot, 0]).wait()
            if not shared_kv:
                pltpu.make_async_copy(v_hbm.at[page_idx], v_buf.at[slot, j],
                                      sems.at[slot, 1]).wait()
                if quant:
                    pltpu.make_async_copy(vs_hbm.at[page_idx],
                                          vs_buf.at[slot, j],
                                          sems.at[slot, 1]).wait()

    return start_fetch, wait_fetch


def block_kv(k_buf, v_buf, slot, bk: int, num_kv_heads: int,
             head_dim: int, v_dim: int, shared_kv: bool,
             mqa: bool = False, ks_buf=None, vs_buf=None):
    """The current VMEM block as ([BK, Hkv, D] keys, [BK, Hkv, Dv] values);
    shared-kv mode slices values from the key block (latent prefix).
    ``mqa`` mode (Hkv == 1, 3-D cache without the singleton head axis —
    Mosaic's sublane tiling rejects slicing a size-1 second-minor dim)
    returns 2-D [BK, D] / [BK, Dv]. int8 blocks (ks_buf/vs_buf present)
    come back dequantized to f32: each page's [ppb, Hkv] scale row
    broadcasts over its page_size × head_dim slab — a VPU multiply on
    data already resident in VMEM, in the shadow of the block's MXU dots.
    """
    quant = ks_buf is not None
    if mqa:
        assert not quant, "int8 KV cache unsupported in MQA kernel mode"
        k = k_buf[slot].reshape(bk, head_dim)
        v = k[:, :v_dim] if shared_kv else v_buf[slot].reshape(bk, v_dim)
        return k, v
    kb = k_buf[slot]                           # [ppb, page, Hkv, D]
    if quant:
        kb = kb.astype(jnp.float32) * ks_buf[slot][:, None, :, None]
    k = kb.reshape(bk, num_kv_heads, head_dim)
    if shared_kv:
        v = k[..., :v_dim]
    else:
        vb = v_buf[slot]
        if quant:
            vb = vb.astype(jnp.float32) * vs_buf[slot][:, None, :, None]
        v = vb.reshape(bk, num_kv_heads, v_dim)
    return k, v


def attend_block(qh, k_buf, v_buf, slot, bk: int, num_kv_heads: int,
                 head_dim: int, v_dim: int, shared_kv: bool, mqa: bool,
                 kv_len, blk_idx, m, l, acc, ks_buf=None, vs_buf=None):
    """One kv-block online-softmax update, shared by the decode kernels.

    ``qh`` is the pre-scaled query ([Hq, D] in mqa mode, else
    [Hkv, G, D]); (m, l, acc) is the running flash-attention state.
    Returns the updated (m, l, acc). Keys past ``kv_len`` are masked."""
    import jax
    import jax.numpy as jnp
    kv_axis = 1 if mqa else 2
    k, v = block_kv(k_buf, v_buf, slot, bk, num_kv_heads, head_dim,
                    v_dim, shared_kv, mqa=mqa, ks_buf=ks_buf,
                    vs_buf=vs_buf)
    if mqa:
        kt = k.astype(jnp.float32)                      # [BK, D]
        vt = v.astype(jnp.float32)                      # [BK, Dv]
        scores = jax.lax.dot_general(                   # [Hq, BK]
            qh, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        kt = k.astype(jnp.float32).transpose(1, 0, 2)   # [Hkv, BK, D]
        vt = v.astype(jnp.float32).transpose(1, 0, 2)   # [Hkv, BK, Dv]
        scores = jax.lax.dot_general(                   # [Hkv, G, BK]
            qh, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    kv_pos = blk_idx * bk + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, kv_axis)
    scores = jnp.where(kv_pos < kv_len, scores, -jnp.inf)

    m_blk = jnp.max(scores, axis=kv_axis, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new)
    l_new = l * alpha + jnp.sum(p, axis=kv_axis, keepdims=True)
    if mqa:
        pv = jax.lax.dot_general(                       # [Hq, Dv]
            p, vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        pv = jax.lax.dot_general(                       # [Hkv, G, Dv]
            p, vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    return m_new, l_new, acc * alpha + pv


def kv_stream_specs(k_cache, v_cache, pages_per_block: int, page_size: int,
                    num_kv_heads: int, head_dim: int, v_dim: int,
                    mqa: bool = False, slots: int = 2,
                    k_scale=None, v_scale=None):
    """(in_specs_tail, scratch_shapes, inputs_tail) for the KV streams.

    Appends the v stream only when a distinct v cache exists; the DMA
    semaphore array always comes last in scratch. ``mqa`` expects 3-D
    caches [P, page, D] (head axis squeezed by the caller). ``slots`` is
    the buffer-slot count: 2 for the double-buffer kernels, the seq
    group size for the grouped decode kernel (one slot per sequence).
    int8 caches (k_scale/v_scale [num_pages, Hkv] f32) append one
    scale stream per cache stream, in (k, v, k_scale, v_scale) order —
    ``unpack_refs`` mirrors this layout.
    """
    shared_kv = v_cache is None
    head_shape = () if mqa else (num_kv_heads,)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    scratch = [pltpu.VMEM((slots, pages_per_block, page_size, *head_shape,
                           head_dim), k_cache.dtype)]
    inputs = [k_cache]
    if not shared_kv:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch.append(pltpu.VMEM((slots, pages_per_block, page_size,
                                   *head_shape, v_dim), v_cache.dtype))
        inputs.append(v_cache)
    if k_scale is not None:
        assert not mqa and not shared_kv, \
            "int8 KV cache unsupported for MQA/shared-KV kernels"
        for s in (k_scale, v_scale):
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            scratch.append(pltpu.VMEM((slots, pages_per_block,
                                       num_kv_heads), jnp.float32))
            inputs.append(s)
    scratch.append(pltpu.SemaphoreType.DMA((slots, 2)))
    return in_specs, scratch, inputs
