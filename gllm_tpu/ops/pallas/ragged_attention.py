"""Pallas TPU ragged paged attention (prefill + mixed + decode batches).

One varlen call serving a mixed batch of prefill chunks and decode rows
against the paged KV cache (sgl_kernel ``flash_attn_varlen_func``
semantics, /root/reference/gllm/layers/attention.py:92-140). Replaces the
dense-gather XLA fallback whose HBM traffic scaled with the *padded*
page-table extent (round-1 verdict: gigabytes per layer at 4K context).

Design (TPU-first):
- grid = (num_q_blocks,) over the FLAT packed token axis. Because blocks are
  aligned with the ragged layout, q and the output use plain VMEM BlockSpecs
  — no gather/scatter at either end. A q block may span several sequences
  (decode rows are 1 token each); each program loops over exactly the
  sequences overlapping its block (host-precomputed [first,last] range via
  searchsorted, passed as scalar prefetch).
- per sequence, KV pages stream HBM→VMEM with double-buffered async DMA
  (same discipline as decode_attention.py); the kv-block loop bound is the
  causal limit of this q block within that sequence, so HBM traffic is the
  actual context, not the padded page-table width.
- GQA layout: the pool reaches the kernel as [P, page * Hkv, D], the heads
  folded into the rows (the pool's own order: no data moves; the decode
  kernel's view), so a fetched block is [BK * Hkv, D] and KV head h's keys
  every Hkv-th row of it: a sublane-strided load, of a head PAIR's 32-bit
  words where the cache is 16-bit (``paged_kv.head_rows``: each half cut
  out by an integer truncation). A block is never widened to float32 and
  never transposed. The q block is re-laid once to [Hkv, BQ*G, D] in VMEM
  scratch, and a ROLLED loop over the loads makes two plain 2-D products a
  head and kv block ([BQ*G, D] x [BK, D]^T, then p x [BK, Dv]) against
  per-head flash state in VMEM scratch (m/l/acc, carried across the
  sequence loop), so the live score tile is one KV head's ([BQ*G, BK]).
  q, K and V enter the MXU as ``paged_kv.mxu_operand`` says: a 16-bit
  cache under a q of its dtype as stored, the float32 scores taking the
  scale and p going into the value product as its rounding plus the
  rounding's remainder (nothing lost against float32 operands); a float32
  cache, int8 blocks dequantized in VMEM or a q of another dtype in
  float32. Rows outside the current sequence are masked with -inf (the
  mask is one [BQ*G, BK] a kv block, shared by the heads) and contribute
  nothing to their state. Under ONE KV head (the latent cache) a block is
  one product of every query head's rows and the state is the loops'
  carry (PR 37).
- Values may have a different head dim than keys (Dv != D) to serve the MLA
  absorbed path, where v is the latent prefix of k.
- A window (``window``, static): a query at
  position t attends positions t - window < j <= t. Of a sequence, a q
  block streams the kv blocks from the one that holds the window's start
  of its FIRST overlapping row to the causal limit of its last; blocks
  behind that are neither fetched nor scored, and rows of a fetched block
  that lie behind a query's own window are masked. Without the argument
  the program is the kernel it always was.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gllm_tpu.ops.pallas.paged_kv import (head_rows, heads_a_load,
                                          kv_stream_specs, make_fetch_fns,
                                          mxu_operand, unpack_refs)

logger = logging.getLogger(__name__)

DEFAULT_KV_BLOCK = 256
DEFAULT_Q_BLOCK = 128
NEG_INF = float("-inf")


@functools.lru_cache(maxsize=None)
def _announce_mqa(operand: str, heads: int, lanes: int, v_dim: int,
                  bq: int, bk: int) -> None:
    """Once per process and geometry, as the first program that holds the
    kernel under one KV head is traced (the blocks follow the geometry:
    ``tuning.ragged_blocks``)."""
    logger.info(
        "[startup] ragged_paged_attention under one KV head: %d query "
        "heads x %d lanes (values: the first %d), q, K and p enter the MXU "
        "as %s; q blocks of %d tokens = %d rows, kv blocks of %d tokens",
        heads, lanes, v_dim, operand, bq, bq * heads, bk)


@functools.lru_cache(maxsize=None)
def _announce_heads(operand: str, p_parts: int, heads: int, kv_heads: int,
                    bq: int, bk: int) -> None:
    """Once per process and form, as the first program that holds the
    kernel under several KV heads is traced."""
    logger.info(
        "[startup] ragged_paged_attention under %d KV heads: %d query "
        "heads each, a KV head at a time; q and a head's K and V enter the "
        "MXU as %s, %s; q blocks of %d tokens = %d rows a head, kv blocks "
        "of %d tokens", kv_heads, heads // kv_heads, operand,
        "p in two parts" if p_parts == 2 else "float32 operands", bq,
        bq * (heads // kv_heads), bk)


def block_form(q_dtype, kv_dtype, quant: bool = False):
    """(operand dtype's name, parts p enters the value product in): the
    form the ragged body under several KV heads takes, chosen from what
    it sees of a call (``mxu_operand``) and from nothing else."""
    operand = mxu_operand(jnp.dtype(q_dtype), jnp.dtype(kv_dtype), quant)
    return operand.name, _p_parts(operand)


def _p_parts(operand) -> int:
    """p goes into a 16-bit value product as its rounding plus the
    rounding's remainder; into a float32 one as it is."""
    return 2 if operand.itemsize == 2 else 1


def _online_update(scores, vt, m, l, acc, p_parts: int = 1):
    """One kv-block online-softmax update over pre-masked ``scores``:
    [R, BK] against one KV head's [BK, Dv] values.

    Exp-domain max, VPU multiply rescale. Rows with nothing visible
    yet keep m == -inf; the 0.0 stand-in keeps their p/alpha at exactly
    0 (no nan from -inf - -inf).

    ``p_parts`` (16-bit values): 2 sends p into the value
    product as its rounding to the values' dtype plus the rounding's
    remainder, stacked along the rows of ONE product
    (``paged_kv.attend_block``'s form: nothing is lost against float32
    operands); 1 takes p rounded once (the latent cache, PR 37) or, with
    float32 values, as it is."""
    m_blk = jnp.max(scores, axis=1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    alpha = jnp.exp(m - safe_m)
    p = jnp.exp(scores - safe_m)
    l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    if p_parts == 2:
        hi = p.astype(vt.dtype)
        lo = (p - hi.astype(jnp.float32)).astype(vt.dtype)
        pv = jax.lax.dot_general(                   # [2 R, Dv]
            jnp.concatenate([hi, lo], axis=0), vt,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        pv = pv[:p.shape[0]] + pv[p.shape[0]:]
    else:
        pv = jax.lax.dot_general(                   # [R, Dv]
            p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return m_new, l_new, acc * alpha + pv


def vmem_tile_limit_b() -> float:
    """VMEM budget (bytes) for the f32 score tile, resolution order:
    ``GLLM_TPU_VMEM_TILE_LIMIT_MB`` env (benchmarks/kernel_tune.py
    --vmem-probe uses it to present oversized tiles to Mosaic and observe
    the REAL ceiling) > a hand-maintained per-device ``vmem.tile_limit_mb``
    tuning-table entry (nothing auto-writes it: the score tile is a poor
    proxy for whole-kernel VMEM — a 12 MB limit derived from the r5 probe
    let a serving program through that Mosaic's 64 MB scoped cap rejected
    at 74 MB total) > the conservative 6 MB every chip tested so far
    accepts."""
    import os
    raw = os.environ.get("GLLM_TPU_VMEM_TILE_LIMIT_MB")
    if raw is not None:
        try:
            return float(raw) * 1024 * 1024
        except ValueError:
            import warnings
            warnings.warn("malformed GLLM_TPU_VMEM_TILE_LIMIT_MB; "
                          "falling back to the tuned/default limit",
                          stacklevel=2)
    from gllm_tpu.ops.pallas.tuning import get as tuned
    return float(tuned("vmem").get("tile_limit_mb", 6.0)) * 1024 * 1024


def effective_q_block(q_block: int, kv_block: int, num_q_heads: int,
                      T: int, num_kv_heads: int = 1, v_dim: int = 0) -> int:
    """The q block actually compiled: the requested block (tests use small
    ones to force blocks that span sequences), halved while the float32
    tiles that live through a kv block would crowd VMEM next to the
    double-buffered KV blocks. Under several KV heads the body attends a
    KV head at a time, so what is live is ONE head's score tile
    ([BQ * G, BK]) beside the accumulators of all of them
    ([Hkv, BQ * G, Dv]); under one KV head the tile is every query head's
    and the table's ``q_rows`` already bounds the rows. Exposed so the
    block-size sweep can tell when two requested configs alias the same
    program."""
    limit_b = vmem_tile_limit_b()
    group = num_q_heads // num_kv_heads
    acc = num_q_heads * v_dim if num_kv_heads > 1 else 0
    bq = min(q_block, T)
    while (group * kv_block + acc) * bq * 4 > limit_b and bq > 16:
        bq //= 2
    return bq


def _kernel(cu_ref, kv_lens_ref, pt_ref, first_ref, last_ref,  # prefetch
            *refs,
            page_size: int, pages_per_block: int, scale: float,
            num_kv_heads: int, group: int, head_dim: int, v_dim: int,
            q_blk: int, shared_kv: bool, mqa: bool, quant: bool,
            window=None):
    heads_scr = None
    if not mqa:
        # the per-head q rows and flash state of the ragged body (below)
        *refs, q_scr, m_scr, l_scr, acc_scr = refs
        heads_scr = (q_scr, m_scr, l_scr, acc_scr)
    (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
     vs_buf, sems) = unpack_refs(refs, shared_kv, quant)
    b = pl.program_id(0)
    t_start = b * q_blk
    s0 = first_ref[b]
    s1 = last_ref[b]
    bk = pages_per_block * page_size
    rows = q_blk * group

    start_fetch, wait_fetch = make_fetch_fns(
        pt_ref, k_hbm, v_hbm, k_buf, v_buf, sems, pages_per_block,
        shared_kv, ks_hbm=ks_hbm, vs_hbm=vs_hbm, ks_buf=ks_buf,
        vs_buf=vs_buf)

    operand = mxu_operand(q_ref.dtype, k_buf.dtype, quant)
    if mqa or operand.itemsize == 2:
        # q and the cache enter the MXU as stored where they are 16-bit
        # (``mxu_operand``; under one KV head, the latent cache, whatever
        # they are) and the float32 scores take the scale
        q_raw, score_scale = q_ref[...], scale
    else:
        # float32 operands (a float32 cache, int8 blocks dequantized in
        # VMEM, a q of another dtype): q takes the scale
        q_raw = q_ref[...].astype(jnp.float32) * scale      # [BQ, Hq, D]
        score_scale = None
    _ragged_block(q_raw, cu_ref, kv_lens_ref, o_ref, start_fetch,
                  wait_fetch, k_buf, v_buf, ks_buf, vs_buf,
                  t_start=t_start, s0=s0, s1=s1, bk=bk, rows=rows,
                  num_kv_heads=num_kv_heads, group=group,
                  head_dim=head_dim, v_dim=v_dim, q_blk=q_blk,
                  shared_kv=shared_kv, mqa=mqa, operand=operand,
                  score_scale=score_scale, window=window,
                  heads_scr=heads_scr)


def _ragged_block(q, cu_ref, kv_lens_ref, o_ref, start_fetch, wait_fetch,
                  k_buf, v_buf, ks_buf, vs_buf, *, t_start, s0, s1,
                  bk: int, rows: int, num_kv_heads: int, group: int,
                  head_dim: int, v_dim: int, q_blk: int, shared_kv: bool,
                  mqa: bool, operand, score_scale=None,
                  window=None, heads_scr=None):
    """The ragged (prefill/mixed) block body: loop the sequences
    overlapping this q block, stream each one's causal KV range with
    double-buffered DMA, and attend each fetched block under a mask of
    [rows, BK] (``rows`` = BQ * G score rows of one KV head, row r the
    token r // G). ``score_scale``: q arrives unscaled in its own dtype
    and the float32 scores take the scale (None: q is scaled already).

    Under ONE KV head (``mqa``) the block is one product of every query
    head's rows and the flash state is the loops' carry. Under several,
    a ROLLED loop takes a KV head (of a 16-bit cache: a pair) at a time:
    its keys and values are strided rows of the block as the DMA left it
    (``paged_kv.head_rows``), its q rows and flash state lie in VMEM
    scratch (``heads_scr``: q [Hkv, rows, D] in ``operand``, re-laid once
    a q block; m, l, acc float32), and the live score tile is that one
    head's. Operands as ``mxu_operand`` says: 16-bit as stored with p in
    two parts, else float32."""
    if mqa:
        # Hkv == 1 (MLA latent): flat 2-D rows [BQ*Hq, D]; the caches
        # arrive 3-D with the singleton head axis squeezed (Mosaic's
        # sublane tiling rejects slicing a size-1 second-minor dim).
        qh = q.reshape(rows, head_dim).astype(operand)
    else:
        q_scr, m_scr, l_scr, acc_scr = heads_scr
        # [BQ, Hkv, G, D] -> [Hkv, BQ, G, D] -> [Hkv, BQ*G, D]: in float32
        # (exact for a 16-bit q), whose rows Mosaic re-lays
        q_scr[...] = q.astype(jnp.float32) \
            .reshape(q_blk, num_kv_heads, group, head_dim) \
            .transpose(1, 0, 2, 3) \
            .reshape(num_kv_heads, rows, head_dim).astype(operand)
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    # p rounded once under one KV head (PR 37), in two parts under several
    p_parts = 1 if mqa else _p_parts(operand)
    # token index of each score row: row r -> t_start + r // G
    row_tok = t_start + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // group
    # the flash state: under one KV head the loops' carry
    state = (jnp.full((rows, 1), NEG_INF, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, v_dim), jnp.float32)) if mqa else None

    def seq_body(s, state):
        q_start = cu_ref[s]
        q_end = cu_ref[s + 1]                 # exclusive
        q_len = q_end - q_start
        kv_len = kv_lens_ref[s]
        # overlap of [q_start, q_end) with this q block's token range
        lo = jnp.maximum(q_start, t_start)
        hi = jnp.minimum(q_end, t_start + q_blk)   # exclusive
        # causal kv limit for the LAST overlapping row of this block:
        # absolute position of token t is kv_len - q_len + (t - q_start).
        kv_limit = kv_len - q_len + (hi - 1 - q_start) + 1
        kv_limit = jnp.where(hi > lo, jnp.minimum(kv_limit, kv_len), 0)
        n_blocks = pl.cdiv(kv_limit, bk)
        if window:
            # the first overlapping row's window starts the stream: the
            # loop counts the blocks from ``b0`` on
            p_lo = kv_len - q_len + (lo - q_start)
            b0 = jnp.where(hi > lo,
                           jnp.maximum(p_lo - window + 1, 0) // bk, 0)
            n_blocks = jnp.maximum(n_blocks - b0, 0)

        @pl.when(n_blocks > 0)
        def _():
            start_fetch(0, s, b0 if window else 0)

        def blk_body(i, state):
            slot = jax.lax.rem(i, 2)
            more = i + 1 < n_blocks
            if window:
                i = b0 + i

            @pl.when(more)
            def _():
                start_fetch(1 - slot, s, i + 1)

            wait_fetch(slot, s, i)

            def mask():
                kv_pos = i * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, bk), 1)
                in_seq = (row_tok >= q_start) & (row_tok < q_end)
                q_pos = kv_len - q_len + (row_tok - q_start)
                visible = in_seq & (kv_pos <= q_pos) & (kv_pos < kv_len)
                if window:
                    visible &= kv_pos > q_pos - window
                return visible

            def attend(qh, k, v, m, l, acc, visible=None):
                scores = jax.lax.dot_general(           # [R, BK]
                    qh, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if score_scale is not None:
                    scores = scores * score_scale
                if visible is None:
                    visible = mask()
                scores = jnp.where(visible, scores, NEG_INF)
                return _online_update(scores, v, m, l, acc, p_parts)

            if mqa:
                # the pages arrive without the singleton head axis
                # (Mosaic's sublane tiling rejects slicing a size-1
                # second-minor dim); the latent cache's values are the
                # leading lanes of its keys
                k = k_buf[slot].reshape(bk, head_dim)
                v = (k[:, :v_dim] if shared_kv
                     else v_buf[slot].reshape(bk, v_dim))
                return attend(qh, k.astype(operand), v.astype(operand),
                              *state)

            visible = mask()        # one a kv block, shared by the heads

            def heads_body(g, _):
                ks = head_rows(k_buf, ks_buf, slot, g, bk, num_kv_heads,
                               head_dim)
                vs = ([k[:, :v_dim] for k in ks] if shared_kv else
                      head_rows(v_buf, vs_buf, slot, g, bk, num_kv_heads,
                                v_dim))
                for b, (k, v) in enumerate(zip(ks, vs)):
                    h = len(ks) * g + b
                    m_scr[h], l_scr[h], acc_scr[h] = attend(
                        q_scr[h], k.astype(operand), v.astype(operand),
                        m_scr[h], l_scr[h], acc_scr[h], visible)

            return jax.lax.fori_loop(
                0, num_kv_heads // heads_a_load(num_kv_heads, k_buf.dtype),
                heads_body, None)

        return jax.lax.fori_loop(0, n_blocks, blk_body, state)

    state = jax.lax.fori_loop(s0, s1 + 1, seq_body, state)

    if mqa:
        _, l, acc = state
        out = acc / jnp.maximum(l, 1e-30)               # empty rows -> 0
        out = out.reshape(q_blk, group, v_dim)          # group == Hq
    else:
        # [Hkv, BQ*G, Dv] -> [BQ, Hkv, G, Dv] -> [BQ, Hq, Dv]
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        out = out.reshape(num_kv_heads, q_blk, group, v_dim) \
                 .transpose(1, 0, 2, 3) \
                 .reshape(q_blk, num_kv_heads * group, v_dim)
    o_ref[...] = out.astype(o_ref.dtype)


def _decode_prefix_len(cu_q_lens, S: int):
    """Length of the batch's decode prefix — the longest prefix of
    sequences with exactly one token each, which is also the token
    index where prefill rows begin (``cu[s] == s`` for every s inside
    it). Derived from ``cu_q_lens`` alone, traced (no new compile
    axis); the engine packs decode rows first, so this is the whole
    decode population for scheduler-built batches."""
    one_tok = cu_q_lens[1:S + 1] == jnp.arange(1, S + 1,
                                               dtype=cu_q_lens.dtype)
    # first False index == prefix length (argmin over {False < True});
    # the appended False covers the all-decode batch
    return jnp.argmin(jnp.concatenate(
        [one_tok, jnp.zeros((1,), bool)])).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "q_block", "kv_block", "interpret", "v_dim",
                     "window", "name"))
def ragged_paged_attention(
    q: jnp.ndarray,            # [T, Hq, D] packed ragged tokens
    k_cache: jnp.ndarray,      # [num_pages, page_size, Hkv, D]
    v_cache,                   # [P, page, Hkv, Dv] or None → v = k[:, :Dv]
    cu_q_lens: jnp.ndarray,    # [S+1] int32 (padded seqs repeat last value)
    kv_lens: jnp.ndarray,      # [S] int32 (0 for padded rows)
    page_table: jnp.ndarray,   # [S, max_pages] int32 (padding → dummy page)
    *,
    scale: float,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
    interpret: bool = False,
    v_dim=None,
    k_scale=None,              # [num_pages, Hkv] f32 (int8 cache)
    v_scale=None,
    window=None,               # a query attends its last ``window``
                               # positions only (None: all before it)
    name=None,                 # the call's name in the HLO and the trace
                               # (None: this function's)
) -> jnp.ndarray:
    T, num_q_heads, head_dim = q.shape
    _, page_size, num_kv_heads, _ = k_cache.shape
    shared_kv = v_cache is None
    quant = k_scale is not None
    if shared_kv:
        if v_dim is None:
            raise ValueError("v_dim required when v_cache is None")
    else:
        v_dim = v_cache.shape[-1]
    S, max_pages = page_table.shape
    group = num_q_heads // num_kv_heads

    # The kernel reads a page as [page * Hkv, D]: the token-major pool
    # with the kv heads folded into the rows, which is the pool's own
    # order (no data moves; the decode kernel's view), and for MQA (the
    # MLA latent cache) the squeeze of the singleton head axis that
    # Mosaic's sublane tiling asks for anyway.
    num_pages = k_cache.shape[0]
    mqa = num_kv_heads == 1
    if quant and (mqa or shared_kv):
        raise NotImplementedError(
            "int8 KV cache unsupported for MQA/MLA ragged kernels")
    k_cache = k_cache.reshape(num_pages, page_size * num_kv_heads, head_dim)
    if v_cache is not None:
        v_cache = v_cache.reshape(num_pages, page_size * num_kv_heads,
                                  v_dim)

    bq = effective_q_block(q_block, kv_block, num_q_heads, T, num_kv_heads,
                           v_dim)
    t_pad = -(-T // bq) * bq
    if t_pad != T:
        q = jnp.pad(q, ((0, t_pad - T), (0, 0), (0, 0)))
    nb = t_pad // bq

    pages_per_block = max(1, min(kv_block // page_size, max_pages))
    rem = max_pages % pages_per_block
    if rem:
        page_table = jnp.pad(page_table,
                             ((0, 0), (0, pages_per_block - rem)))
    operand = mxu_operand(q.dtype, k_cache.dtype, quant)
    if not interpret:
        if mqa:
            _announce_mqa(operand.name, num_q_heads, head_dim, v_dim, bq,
                          pages_per_block * page_size)
        else:
            _announce_heads(*block_form(q.dtype, k_cache.dtype, quant),
                            num_q_heads, num_kv_heads, bq,
                            pages_per_block * page_size)

    # Per-block overlapping sequence range: seq s covers tokens
    # [cu[s], cu[s+1]); searchsorted over the upper bounds finds the first
    # seq whose range extends past a given token.
    t_starts = jnp.arange(nb, dtype=jnp.int32) * bq
    upper = cu_q_lens[1:]
    first = jnp.clip(jnp.searchsorted(upper, t_starts, side="right"),
                     0, S - 1).astype(jnp.int32)
    last = jnp.clip(jnp.searchsorted(upper, t_starts + bq - 1,
                                     side="right"),
                    0, S - 1).astype(jnp.int32)

    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_block=pages_per_block,
        scale=scale, num_kv_heads=num_kv_heads, group=group,
        head_dim=head_dim, v_dim=v_dim, q_blk=bq, shared_kv=shared_kv,
        mqa=mqa, quant=quant, window=window)

    kv_specs, scratch_shapes, kv_inputs = kv_stream_specs(
        k_cache, v_cache, pages_per_block, k_scale=k_scale, v_scale=v_scale)
    if not mqa:
        rows = bq * group
        scratch_shapes += [
            pltpu.VMEM((num_kv_heads, rows, head_dim), operand),
            pltpu.VMEM((num_kv_heads, rows, 1), jnp.float32),
            pltpu.VMEM((num_kv_heads, rows, 1), jnp.float32),
            pltpu.VMEM((num_kv_heads, rows, v_dim), jnp.float32)]
    in_specs = [
        pl.BlockSpec((bq, num_q_heads, head_dim),
                     lambda b, *_: (b, 0, 0),
                     memory_space=pltpu.VMEM),
    ] + kv_specs
    inputs = [cu_q_lens, kv_lens, page_table, first, last, q] + kv_inputs

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bq, num_q_heads, v_dim),
                               lambda b, *_: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, num_q_heads, v_dim),
                                       q.dtype),
        # q blocks are independent → Megacore may split the grid.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)) if interpret else
        pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(*inputs)
    return out[:T]
