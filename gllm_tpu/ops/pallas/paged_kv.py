"""Shared paged-KV streaming machinery for the Pallas attention kernels.

Both the decode kernel (grid over sequences) and the ragged prefill kernel
(grid over q blocks) stream KV pages HBM→VMEM with double-buffered async
DMA, optionally with values read as the leading ``v_dim`` lanes of each key
block (MLA absorbed layout — one DMA stream). This module is the single
copy of that discipline.

int8 quantized caches (kv_cache_dtype=int8) add a third/fourth stream: the
per-page per-head f32 scale rows (``[num_pages, Hkv]``) ride the same page
DMAs into a tiny VMEM scratch, and ``attend_block`` (the decode kernel)
and ``head_rows`` (the ragged body) dequantize each block in VMEM right
before the MXU dots — the bf16 cache never exists in HBM, so
the decode read path moves half the bytes.
"""

from __future__ import annotations

import jax
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
    """(start_fetch, wait_fetch), each taking (slot, seq, kv_block_idx)
    and, optionally, ``pages``.

    Copies ``pages_per_block`` whole pages per block. Semaphore layout is
    [slot, k_or_v]: ONE DMA semaphore per slot per stream — every page
    copy of a block signals it and wait_fetch consumes the same count
    (a per-page sem array blew the sflag scratch budget at
    group_size ≥ 8: slots × pages × 2 × 4 B > 2 KiB). Start/wait pairs
    must match 1:1 — the callers' buffer loops guarantee it. Quantized
    caches ride each page's scale row on the same per-stream semaphore
    (one extra tiny copy per page, same 1:1 accounting).

    ``pages`` (a traced count; the same in the start and in its wait)
    fetches only the block's first ``pages`` pages, in a loop of that
    many rounds — a context's last block, whose other pages hold nothing
    of it — and clears the value rows of the rest: their keys are masked
    by position, but a probability of exactly 0 times whatever VMEM held
    is not 0 if that is a NaN.
    """
    quant = ks_hbm is not None
    val_buf, val_scale = (k_buf, ks_buf) if shared_kv else (v_buf, vs_buf)

    def page_copies(slot, s, blk, j):
        page_idx = pt_ref[s, blk * pages_per_block + j]
        copies = [pltpu.make_async_copy(k_hbm.at[page_idx],
                                        k_buf.at[slot, j], sems.at[slot, 0])]
        if quant:
            copies.append(pltpu.make_async_copy(
                ks_hbm.at[page_idx], ks_buf.at[slot, j], sems.at[slot, 0]))
        if not shared_kv:
            copies.append(pltpu.make_async_copy(
                v_hbm.at[page_idx], v_buf.at[slot, j], sems.at[slot, 1]))
            if quant:
                copies.append(pltpu.make_async_copy(
                    vs_hbm.at[page_idx], vs_buf.at[slot, j],
                    sems.at[slot, 1]))
        return copies

    def over_pages(lo, hi, fn):
        """``fn(j)`` for the block's pages lo <= j < hi: unrolled where
        both ends are static, a loop where one is traced."""
        if isinstance(lo, int) and isinstance(hi, int):
            for j in range(lo, hi):
                fn(j)
        else:
            jax.lax.fori_loop(lo, hi, lambda j, _: fn(j), None)

    def start_fetch(slot, s, blk, pages=pages_per_block):
        def start(j):
            for c in page_copies(slot, s, blk, j):
                c.start()

        def clear(j):
            val_buf[slot, j] = jnp.zeros(val_buf.shape[2:], val_buf.dtype)
            if quant:
                val_scale[slot, j] = jnp.zeros(val_scale.shape[2:],
                                               val_scale.dtype)

        over_pages(0, pages, start)
        over_pages(pages, pages_per_block, clear)

    def wait_fetch(slot, s, blk, pages=pages_per_block):
        def wait(j):
            for c in page_copies(slot, s, blk, j):
                c.wait()

        over_pages(0, pages, wait)

    return start_fetch, wait_fetch


def heads_a_load(num_kv_heads: int, kv_dtype) -> int:
    """KV heads one load of ``head_rows`` brings: a 32-bit word of a page
    as the pool stores it ([page * Hkv, D], row = token * Hkv + head)
    holds the same lane of ``4 // itemsize`` consecutive rows, so two
    heads of a token where the cache is 16-bit and its heads pair up;
    else one."""
    return 2 if (jnp.dtype(kv_dtype).itemsize == 2
                 and num_kv_heads % 2 == 0) else 1


def head_rows(buf, s_buf, slot, g, bk: int, num_kv_heads: int, dim: int):
    """The rows of KV heads ``n * g .. n * g + n - 1`` (``g`` traced or
    static, ``n`` = ``heads_a_load``) out of the current VMEM block, each
    [BK, dim] in the cache's dtype as stored: never widened, never
    transposed. The block lies as the DMA left it, [ppb, page * Hkv, dim]
    with the heads folded into the rows, so a head is every Hkv-th row: a
    sublane-strided load. Mosaic strides 32-bit words only, and a word of
    a 16-bit block packs rows 2i (low half) and 2i + 1, which are two
    heads of one token: the words of a head PAIR are loaded at a stride of
    Hkv / 2 and each half is cut out by an integer truncation (a pack, no
    float32 on the way). A float32 block strides its own rows. int8 blocks
    (``s_buf``: the pages' [ppb, Hkv] scale rows; dequantized here, float32
    out) and 16-bit blocks under an odd head count stride their narrow rows,
    which only the interpreter does (the chip path refuses int8 caches
    before it gets here)."""
    dtype = buf.dtype
    n = heads_a_load(num_kv_heads, dtype)
    page = buf.shape[2] // num_kv_heads
    if n == 2:
        words = buf.bitcast(jnp.uint32)[
            slot, :, pl.ds(g, page, stride=num_kv_heads // 2), :]
        words = words.reshape(bk, dim)
        return [jax.lax.bitcast_convert_type(
            (words >> (16 * b)).astype(jnp.uint16), dtype) for b in (0, 1)]
    x = buf[slot, :, pl.ds(g, page, stride=num_kv_heads), :]
    if s_buf is not None:
        x = x.astype(jnp.float32) * s_buf[slot, :, pl.ds(g, 1)][:, :, None]
    return [x.reshape(bk, dim)]


def own_head_tokens(num_q_heads: int, num_kv_heads: int, bk: int):
    """[Hq, BK * Hkv] int32 for a block of folded rows (row = token *
    Hkv + kv head): the token a row holds where the row's kv head is the
    query head's own, and a token no context reaches where it is another
    head's. ``attend_block`` compares it with the tokens left."""
    shape = (num_q_heads, bk * num_kv_heads)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if num_kv_heads == 1:
        return row
    own = row % num_kv_heads == jax.lax.broadcasted_iota(
        jnp.int32, shape, 0) // (num_q_heads // num_kv_heads)
    return jnp.where(own, row // num_kv_heads, jnp.iinfo(jnp.int32).max)


def mxu_operand(q_dtype, kv_dtype, quant: bool):
    """The dtype K, V and q enter the MXU in. A 16-bit cache is used as
    stored where q has its dtype: the products are exact in the float32
    accumulator, and p, the one float32 operand left, then goes in as a
    rounded part plus the rounding's remainder, so nothing is lost
    against float32 operands. Anything else (float32 caches, int8 blocks
    dequantized in VMEM, a q of another dtype) keeps float32 operands."""
    if (not quant and q_dtype == kv_dtype
            and jnp.dtype(kv_dtype).itemsize == 2):
        return jnp.dtype(kv_dtype)
    return jnp.dtype(jnp.float32)


def attend_block(q, k_buf, v_buf, slot, own_tokens, tokens_left, scale,
                 v_dim: int, shared_kv: bool, m, l, acc, ks_buf=None,
                 vs_buf=None, chosen=None, tokens_from=None):
    """One kv-block online-softmax update of the decode kernel.

    The block is consumed as the DMA left it: ``k_buf[slot]`` is
    [ppb, page * Hkv, D], every page's tokens with their kv heads folded
    into the rows, and is never widened or re-laid-out. One MXU product
    scores all query heads against all rows ([Hq, R], R = BK * Hkv);
    ``own_tokens`` (``own_head_tokens``) keeps, per query head, the rows
    of its own kv head that hold one of the ``tokens_left`` tokens of
    the context, and the rest leave the softmax as exact zeros, so
    ``p @ V`` over the same folded rows is the per-head sum. ``q`` is
    [Hq, D] as it arrived; ``scale`` multiplies the float32 scores.
    (m, l, acc) is the running flash-attention state ([Hq, 1], [Hq, 1],
    [Hq, Dv] float32); returns it updated.

    ``chosen`` ([1, R] int32, or None: no such operand in the program) is
    a selection over the block's rows: a row counts where it is nonzero
    AND holds a token of the context. Only under a selection can a block
    hold nothing that counts; ``m`` then stays at -inf, and the exponents
    are taken against 0 in its place (``exp(-inf - -inf)`` is a NaN).

    ``tokens_from`` (a traced scalar, or None: no such condition in the
    program) is a window's lower edge within the block: a row counts only
    where its token's index in the block is at least that (a windowed
    layer's first fetched block holds up to a block less one token from
    behind the window)."""
    quant = ks_buf is not None
    k = k_buf[slot]                                  # [ppb, page*Hkv, D]
    ppb, page_rows, head_dim = k.shape
    rows = ppb * page_rows

    def dequant(x, s_buf):
        s = s_buf[slot]                              # [ppb, Hkv]
        per_row = jnp.tile(s, (1, page_rows // s.shape[1]))
        return x.astype(jnp.float32) * per_row[..., None]

    if quant:
        k = dequant(k, ks_buf)
    k = k.reshape(rows, head_dim)
    if shared_kv:
        v = k[:, :v_dim]
    else:
        v = v_buf[slot]
        if quant:
            v = dequant(v, vs_buf)
        v = v.reshape(rows, v_dim)
    operand = mxu_operand(q.dtype, k.dtype, quant)
    scores = jax.lax.dot_general(                    # [Hq, R]
        q.astype(operand), k.astype(operand), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    counts = own_tokens < tokens_left
    if chosen is not None:
        counts &= chosen != 0
    if tokens_from is not None:
        counts &= own_tokens >= tokens_from
    scores = jnp.where(counts, scores, -jnp.inf)

    m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
    m_exp = m_new if chosen is None and tokens_from is None else jnp.where(
        m_new == -jnp.inf, 0.0, m_new)
    alpha = jnp.exp(m - m_exp)
    p = jnp.exp(scores - m_exp)
    l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    if operand.itemsize == 2:                        # p in two parts
        hi = p.astype(operand)
        lo = (p - hi.astype(jnp.float32)).astype(operand)
        pv = jax.lax.dot_general(                    # [2 Hq, Dv]
            jnp.concatenate([hi, lo], axis=0), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        pv = pv[:p.shape[0]] + pv[p.shape[0]:]
    else:
        pv = jax.lax.dot_general(                    # [Hq, Dv]
            p, v.astype(operand), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return m_new, l_new, acc * alpha + pv


def kv_stream_specs(k_cache, v_cache, pages_per_block: int, slots: int = 2,
                    k_scale=None, v_scale=None):
    """(in_specs_tail, scratch_shapes, inputs_tail) for the KV streams.

    The caches arrive as their kernel reads a page — [P, page * Hkv, D]
    with the heads folded into the rows, or [P, page, D] with the
    singleton head axis squeezed (MQA) — and a block's scratch is
    ``pages_per_block`` such pages per slot. Appends the v stream only
    when a distinct v cache exists;
    the DMA semaphore array always comes last in scratch. ``slots`` is
    the buffer-slot count: 2 for the double-buffer kernels, two per
    sequence of a group for the decode kernel.
    int8 caches (k_scale/v_scale [num_pages, Hkv] f32) append one
    scale stream per cache stream, in (k, v, k_scale, v_scale) order —
    ``unpack_refs`` mirrors this layout.
    """
    streams = [k_cache] + ([] if v_cache is None else [v_cache])
    if k_scale is not None:
        assert v_cache is not None, \
            "int8 KV cache unsupported for shared-KV kernels"
        streams += [k_scale, v_scale]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY) for _ in streams]
    scratch = [pltpu.VMEM((slots, pages_per_block, *s.shape[1:]), s.dtype)
               for s in streams]
    scratch.append(pltpu.SemaphoreType.DMA((slots, 2)))
    return in_specs, scratch, streams
