"""Pallas TPU ragged paged attention (prefill + mixed + decode batches).

One varlen call serving a mixed batch of prefill chunks and decode rows
against the paged KV cache (sgl_kernel ``flash_attn_varlen_func``
semantics, /root/reference/gllm/layers/attention.py:92-140). Replaces the
dense-gather XLA fallback whose HBM traffic scaled with the *padded*
page-table extent (round-1 verdict: gigabytes per layer at 4K context).

Unified mode (``unified=True`` — the ``--unified-step`` kernel, adopting
the ragged-paged-attention formulation of "Ragged Paged Attention: A
High-Performance and Flexible LLM Inference Kernel for TPU", PAPERS.md):
this is the SINGLE attention kernel for every non-MLA paged step — decode
rows are q_len=1 rows of the same ragged batch. Block geometry is
specialized per ROW CLASS inside the one kernel: a q block lying entirely
inside the batch's decode prefix (the engine packs decode rows first, one
token per sequence) runs the grouped round-robin fetch discipline of the
legacy decode kernel — ``group_size`` sequences in flight per round, one
buffer slot each, dividing the bare-DMA-latency chain that dominates
decode — while blocks carrying prefill rows keep the double-buffered
ragged stream with masked-row MXU dots. The per-block class rides scalar
prefetch, derived from ``cu_q_lens`` alone (no layout change, no extra
compile axis), so pure-decode batches do not regress against the
per-sequence decode kernel (kept in decode_attention.py as the parity
oracle). Unified mode also applies AMLA-style mul-by-add softmax
rescaling ("AMLA: MUL by ADD in FlashAttention Rescaling", PAPERS.md) in
the inner loop: the running max is quantized to integers (log2 domain),
so the accumulator rescale by 2^dm becomes an integer ADD on the f32
exponent field instead of a VPU multiply.

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
- GQA layout: the q block is reshaped to [Hkv, BQ*G, D] so scores are one
  kv-head-batched MXU dot per kv block; rows outside the current sequence
  are masked with -inf and contribute nothing to their online softmax state
  (m/l/acc carried across the sequence loop).
- Values may have a different head dim than keys (Dv != D) to serve the MLA
  absorbed path, where v is the latent prefix of k.
- A window (``window``, static; the legacy path only): a query at
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

from gllm_tpu.ops.pallas.paged_kv import (block_kv, kv_stream_specs,
                                          make_fetch_fns, mxu_operand,
                                          unpack_refs)

logger = logging.getLogger(__name__)

DEFAULT_KV_BLOCK = 256
DEFAULT_Q_BLOCK = 128
DEFAULT_GROUP = 4
NEG_INF = float("-inf")
LOG2E = 1.4426950408889634


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


def _rescale_add(x, dm_i):
    """``x * 2^dm_i`` (``dm_i`` <= 0, int32, shape broadcastable to x)
    via an integer ADD on the f32 exponent field — AMLA's mul-by-add.

    Guards: dm_i == 0 returns x untouched (incl. denormals); a result
    whose biased exponent would leave the normal range (ex + dm_i <= 0)
    flushes to 0 — by then ``x * 2^dm_i`` is below ~1e-38 and the
    flash-attention accumulator cannot distinguish it from 0. The
    integer add only ever runs inside the exponent field when the guard
    passes, so the sign bit is never touched."""
    xb = jax.lax.bitcast_convert_type(x, jnp.int32)
    ex = jnp.bitwise_and(xb, jnp.int32(0x7F800000)) >> 23
    y = jax.lax.bitcast_convert_type(xb + (dm_i << 23), jnp.float32)
    return jnp.where(dm_i >= 0, x,
                     jnp.where(ex + dm_i > 0, y, 0.0))


def _online_update(scores, vt, m, l, acc, kv_axis: int, mqa: bool,
                   amla: bool):
    """One kv-block online-softmax update over pre-masked ``scores``.

    Classic mode is the exact math both legacy kernels use (exp-domain
    max, VPU multiply rescale). AMLA mode expects ``scores`` in the
    LOG2 domain (q pre-scaled by ``scale * LOG2E``): the running max is
    quantized with ``ceil`` so every rescale factor is an exact power
    of two, applied to l/acc by ``_rescale_add`` — the block's only
    rescale multiplies become integer adds. Rows with nothing visible
    yet keep m == -inf; the 0.0 stand-in keeps their p/alpha at exactly
    0 (no nan from -inf - -inf)."""
    m_blk = jnp.max(scores, axis=kv_axis, keepdims=True)
    if amla:
        m_blk = jnp.ceil(m_blk)
    m_new = jnp.maximum(m, m_blk)
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    if amla:
        p = jnp.exp2(scores - safe_m)
        # integer-valued by construction (ceil'd maxes); clamp -inf
        # (first block) below the flush threshold before the int cast
        dm_i = jnp.maximum(m - safe_m, -160.0).astype(jnp.int32)
        l_new = (_rescale_add(l, dm_i)
                 + jnp.sum(p, axis=kv_axis, keepdims=True))
    else:
        alpha = jnp.exp(m - safe_m)
        p = jnp.exp(scores - safe_m)
        l_new = l * alpha + jnp.sum(p, axis=kv_axis, keepdims=True)
    if mqa:
        # a 16-bit value block (the latent cache as stored) takes p in
        # its own dtype: one MXU pass, float32 accumulation
        pv = jax.lax.dot_general(                   # [R, Dv]
            p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        pv = jax.lax.dot_general(                   # [H?, R, Dv]
            p, vt, (((kv_axis,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    if amla:
        acc_new = _rescale_add(acc, dm_i) + pv
    else:
        acc_new = acc * alpha + pv
    return m_new, l_new, acc_new


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
                      T: int) -> int:
    """The q block actually compiled: the requested block (tests use small
    ones to force blocks that span sequences), scaled down while the f32
    score tile would crowd VMEM next to the double-buffered KV blocks.
    Exposed so the block-size sweep can tell when two requested configs
    alias the same program."""
    limit_b = vmem_tile_limit_b()
    bq = min(q_block, T)
    while num_q_heads * bq * kv_block * 4 > limit_b and bq > 16:
        bq //= 2
    return bq


def _kernel(cu_ref, kv_lens_ref, pt_ref, first_ref, last_ref,
            cls_ref,                                      # prefetch
            *refs,
            page_size: int, pages_per_block: int, scale: float,
            num_kv_heads: int, group: int, head_dim: int, v_dim: int,
            q_blk: int, shared_kv: bool, mqa: bool, quant: bool,
            unified: bool, gsz: int, amla: bool, window=None):
    (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
     vs_buf, sems) = unpack_refs(refs, shared_kv, quant)
    b = pl.program_id(0)
    t_start = b * q_blk
    s0 = first_ref[b]
    s1 = last_ref[b]
    bk = pages_per_block * page_size
    rows = q_blk * group
    kv_axis = 1 if mqa else 2
    eff_scale = scale * (LOG2E if amla else 1.0)

    start_fetch, wait_fetch = make_fetch_fns(
        pt_ref, k_hbm, v_hbm, k_buf, v_buf, sems, pages_per_block,
        shared_kv, ks_hbm=ks_hbm, vs_hbm=vs_hbm, ks_buf=ks_buf,
        vs_buf=vs_buf)

    def _ragged_body():
        if mqa:
            # one KV head under every query head (the latent cache): a
            # block is rows x lanes of MXU work, so q and the cache enter
            # as stored where they are 16-bit (``mxu_operand``) and the
            # float32 scores take the scale
            q_raw, score_scale = q_ref[...], eff_scale
        else:
            q_raw = q_ref[...].astype(jnp.float32) * eff_scale  # [BQ, Hq, D]
            score_scale = None
        _ragged_block(q_raw, cu_ref, kv_lens_ref, o_ref, start_fetch,
                      wait_fetch, k_buf, v_buf, ks_buf, vs_buf,
                      t_start=t_start, s0=s0, s1=s1, bk=bk, rows=rows,
                      kv_axis=kv_axis, num_kv_heads=num_kv_heads,
                      group=group, head_dim=head_dim, v_dim=v_dim,
                      q_blk=q_blk, shared_kv=shared_kv, mqa=mqa,
                      amla=amla, score_scale=score_scale, window=window)

    if not unified:
        _ragged_body()
        return

    # Per-block ROW-CLASS specialization: class 1 = every token in this
    # block is its own single-token sequence (the batch's decode
    # prefix), so the block runs the grouped round-robin fetch
    # discipline; class 0 keeps the ragged masked-dot path (prefill
    # chunks, the straddling boundary block, tail padding).
    @pl.when(cls_ref[b] == 1)
    def _():
        _decode_block(q_ref, kv_lens_ref, o_ref, start_fetch, wait_fetch,
                      k_buf, v_buf, ks_buf, vs_buf, t_start=t_start,
                      bk=bk, num_kv_heads=num_kv_heads, group=group,
                      head_dim=head_dim, v_dim=v_dim, q_blk=q_blk,
                      gsz=gsz, shared_kv=shared_kv, mqa=mqa, amla=amla,
                      eff_scale=eff_scale)

    @pl.when(cls_ref[b] == 0)
    def _():
        _ragged_body()


def _ragged_block(q, cu_ref, kv_lens_ref, o_ref, start_fetch, wait_fetch,
                  k_buf, v_buf, ks_buf, vs_buf, *, t_start, s0, s1,
                  bk: int, rows: int, kv_axis: int, num_kv_heads: int,
                  group: int, head_dim: int, v_dim: int, q_blk: int,
                  shared_kv: bool, mqa: bool, amla: bool,
                  score_scale=None, window=None):
    """The ragged (prefill/mixed) block body: loop the sequences
    overlapping this q block, stream each one's causal KV range with
    double-buffered DMA, masked kv-head-batched dots. ``score_scale``
    (MQA only): q arrives unscaled in its own dtype and the scores take
    the scale."""
    if mqa:
        # Hkv == 1 (MLA latent): flat 2-D rows [BQ*Hq, D]; the caches
        # arrive 3-D with the singleton head axis squeezed (Mosaic's
        # sublane tiling rejects slicing a size-1 second-minor dim).
        operand = mxu_operand(q.dtype, k_buf.dtype, False)
        qh = q.reshape(rows, head_dim).astype(operand)
        row_tok = t_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // group
    else:
        # [BQ, Hkv, G, D] → [Hkv, BQ, G, D] → [Hkv, BQ*G, D]
        qh = q.reshape(q_blk, num_kv_heads, group, head_dim) \
              .transpose(1, 0, 2, 3).reshape(num_kv_heads, rows, head_dim)
        # token index of each score row: row r → t_start + r // G
        row_tok = t_start + jax.lax.broadcasted_iota(
            jnp.int32, (num_kv_heads, rows, 1), 1) // group

    def seq_body(s, carry):
        m, l, acc = carry
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

        def blk_body(i, carry2):
            m, l, acc = carry2
            slot = jax.lax.rem(i, 2)
            more = i + 1 < n_blocks
            if window:
                i = b0 + i

            @pl.when(more)
            def _():
                start_fetch(1 - slot, s, i + 1)

            wait_fetch(slot, s, i)
            k, v = block_kv(k_buf, v_buf, slot, bk, num_kv_heads,
                            head_dim, v_dim, shared_kv, mqa=mqa,
                            ks_buf=ks_buf, vs_buf=vs_buf)
            if mqa:
                kt = k.astype(operand)                  # [BK, D]
                vt = v.astype(operand)                  # [BK, Dv]
                scores = jax.lax.dot_general(           # [R, BK]
                    qh, kt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * score_scale
            else:
                kt = k.astype(jnp.float32).transpose(1, 0, 2)
                vt = v.astype(jnp.float32).transpose(1, 0, 2)
                scores = jax.lax.dot_general(           # [Hkv, R, BK]
                    qh, kt, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
            kv_pos = i * bk + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, kv_axis)
            in_seq = (row_tok >= q_start) & (row_tok < q_end)
            q_pos = kv_len - q_len + (row_tok - q_start)
            visible = in_seq & (kv_pos <= q_pos) & (kv_pos < kv_len)
            if window:
                visible &= kv_pos > q_pos - window
            scores = jnp.where(visible, scores, NEG_INF)
            return _online_update(scores, vt, m, l, acc, kv_axis, mqa,
                                  amla)

        return jax.lax.fori_loop(0, n_blocks, blk_body, (m, l, acc))

    lead = (rows,) if mqa else (num_kv_heads, rows)
    m0 = jnp.full((*lead, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((*lead, 1), jnp.float32)
    acc0 = jnp.zeros((*lead, v_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(s0, s1 + 1, seq_body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)                   # empty rows → 0
    if mqa:
        out = out.reshape(q_blk, group, v_dim)          # group == Hq
    else:
        # [Hkv, BQ*G, Dv] → [BQ, Hkv, G, Dv] → [BQ, Hq, Dv]
        out = out.reshape(num_kv_heads, q_blk, group, v_dim) \
                 .transpose(1, 0, 2, 3) \
                 .reshape(q_blk, num_kv_heads * group, v_dim)
    o_ref[...] = out.astype(o_ref.dtype)


def _decode_block(q_ref, kv_lens_ref, o_ref, start_fetch, wait_fetch, k_buf,
                  v_buf, ks_buf, vs_buf, *, t_start, bk: int,
                  num_kv_heads: int, group: int, head_dim: int,
                  v_dim: int, q_blk: int, gsz: int, shared_kv: bool,
                  mqa: bool, amla: bool, eff_scale: float):
    """Decode-class block body: every row r of this q block is its own
    single-token sequence ``t_start + r`` (the guarantee the per-block
    class flag encodes), so the masked ragged dots would waste a BQ×
    factor of MXU rows and — worse — serialize one double-buffered DMA
    chain per sequence. Instead, process rows in groups of ``gsz`` with
    the grouped decode kernel's round-robin discipline: one buffer slot
    per in-group sequence, up to ``gsz`` page DMAs in flight, each
    sequence's online-softmax state carried across kv rounds.

    The groups run as a ROLLED loop (rows are read from ``q_ref`` and
    written to ``o_ref`` at a traced index): unrolled in Python, the body
    was emitted ``q_blk`` times and Mosaic took a minute and a half per
    shape bucket at the default 128-row block."""
    lead = (num_kv_heads * group,) if mqa else (num_kv_heads, group)
    kv_axis = 1 if mqa else 2

    def run_group(g0, gn: int):
        """Rows [g0, g0 + gn) of the block; ``g0`` static or traced."""
        seq_ids = [t_start + g0 + g for g in range(gn)]
        kv_lens = [kv_lens_ref[sid] for sid in seq_ids]
        n_blocks = [pl.cdiv(kv_len, bk) for kv_len in kv_lens]
        for g in range(gn):
            @pl.when(n_blocks[g] > 0)
            def _(g=g):
                start_fetch(g, seq_ids[g], 0)

        qs = []
        for g in range(gn):
            qg = (q_ref[pl.ds(g0 + g, 1)].astype(jnp.float32)
                  * eff_scale).reshape(num_kv_heads * group, head_dim)
            qs.append(qg if mqa
                      else qg.reshape(num_kv_heads, group, head_dim))

        max_nb = n_blocks[0]
        for g in range(1, gn):
            max_nb = jnp.maximum(max_nb, n_blocks[g])

        def body(r, carry):
            out = list(carry)
            for g in range(gn):
                m, l, acc = out[3 * g], out[3 * g + 1], out[3 * g + 2]
                live = r < n_blocks[g]

                @pl.when(live)
                def _(g=g):
                    wait_fetch(g, seq_ids[g], r)

                k, v = block_kv(k_buf, v_buf, g, bk, num_kv_heads,
                                head_dim, v_dim, shared_kv, mqa=mqa,
                                ks_buf=ks_buf, vs_buf=vs_buf)
                if mqa:
                    kt = k.astype(jnp.float32)             # [BK, D]
                    vt = v.astype(jnp.float32)             # [BK, Dv]
                    scores = jax.lax.dot_general(          # [Hq, BK]
                        qs[g], kt, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                else:
                    kt = k.astype(jnp.float32).transpose(1, 0, 2)
                    vt = v.astype(jnp.float32).transpose(1, 0, 2)
                    scores = jax.lax.dot_general(          # [Hkv, G, BK]
                        qs[g], kt, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)
                kv_pos = r * bk + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, kv_axis)
                scores = jnp.where(kv_pos < kv_lens[g], scores, NEG_INF)
                m2, l2, acc2 = _online_update(scores, vt, m, l, acc,
                                              kv_axis, mqa, amla)

                # re-issue this slot's next block AFTER the buffered
                # loads above — program order keeps the loads ahead of
                # the DMA
                @pl.when(live & (r + 1 < n_blocks[g]))
                def _(g=g):
                    start_fetch(g, seq_ids[g], r + 1)

                out[3 * g] = jnp.where(live, m2, m)
                out[3 * g + 1] = jnp.where(live, l2, l)
                out[3 * g + 2] = jnp.where(live, acc2, acc)
            return tuple(out)

        init = []
        for _ in range(gn):
            init += [jnp.full((*lead, 1), NEG_INF, jnp.float32),
                     jnp.zeros((*lead, 1), jnp.float32),
                     jnp.zeros((*lead, v_dim), jnp.float32)]
        final = jax.lax.fori_loop(0, max_nb, body, tuple(init))
        for g in range(gn):
            l, acc = final[3 * g + 1], final[3 * g + 2]
            out = acc / jnp.maximum(l, 1e-30)
            o_ref[pl.ds(g0 + g, 1)] = out.reshape(
                1, num_kv_heads * group, v_dim).astype(o_ref.dtype)

    n_full, tail = divmod(q_blk, gsz)

    def full_group(gi, carry):
        run_group(gi * gsz, gsz)
        return carry

    jax.lax.fori_loop(0, n_full, full_group, 0)
    if tail:
        run_group(n_full * gsz, tail)


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
                     "unified", "group_size", "amla", "window", "name"))
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
    unified: bool = False,     # per-row-class block geometry + AMLA
    group_size: int = DEFAULT_GROUP,   # decode-class DMA interleave depth
    amla=None,                 # None → ride with ``unified``
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
    if window and unified:
        raise NotImplementedError("a window under the unified kernel")

    # MQA (MLA latent cache): squeeze the singleton head axis — Mosaic's
    # sublane tiling rejects slicing a size-1 second-minor dim.
    num_pages = k_cache.shape[0]
    mqa = num_kv_heads == 1
    if quant and (mqa or shared_kv):
        raise NotImplementedError(
            "int8 KV cache unsupported for MQA/MLA ragged kernels")
    if mqa:
        k_cache = k_cache.reshape(num_pages, page_size, head_dim)
        if v_cache is not None:
            v_cache = v_cache.reshape(num_pages, page_size, v_dim)

    bq = effective_q_block(q_block, kv_block, num_q_heads, T)
    t_pad = -(-T // bq) * bq
    if t_pad != T:
        q = jnp.pad(q, ((0, t_pad - T), (0, 0), (0, 0)))
    nb = t_pad // bq

    pages_per_block = max(1, min(kv_block // page_size, max_pages))
    rem = max_pages % pages_per_block
    if rem:
        page_table = jnp.pad(page_table,
                             ((0, 0), (0, pages_per_block - rem)))
    if mqa and not interpret:
        _announce_mqa(mxu_operand(q.dtype, k_cache.dtype, False).name,
                      num_q_heads, head_dim, v_dim, bq,
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

    if amla is None:
        amla = unified
    gsz = max(1, min(group_size, bq)) if unified else 1
    if unified:
        # Per-block row class (scalar prefetch, traced — not a compile
        # axis): class 1 iff the whole block lies inside the decode
        # prefix, where token t IS sequence t. The straddling boundary
        # block and everything after it run the ragged path.
        nd = _decode_prefix_len(cu_q_lens, S)
        cls = (t_starts + bq <= nd).astype(jnp.int32)
    else:
        cls = jnp.zeros((nb,), jnp.int32)

    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_block=pages_per_block,
        scale=scale, num_kv_heads=num_kv_heads, group=group,
        head_dim=head_dim, v_dim=v_dim, q_blk=bq, shared_kv=shared_kv,
        mqa=mqa, quant=quant, unified=unified, gsz=gsz, amla=amla,
        window=window)

    # decode-class blocks hold one buffer slot per in-group sequence;
    # the ragged path keeps using slots 0/1 of the same scratch
    kv_specs, scratch_shapes, kv_inputs = kv_stream_specs(
        k_cache, v_cache, pages_per_block, slots=max(2, gsz),
        k_scale=k_scale, v_scale=v_scale)
    in_specs = [
        pl.BlockSpec((bq, num_q_heads, head_dim),
                     lambda b, *_: (b, 0, 0),
                     memory_space=pltpu.VMEM),
    ] + kv_specs
    inputs = [cu_q_lens, kv_lens, page_table, first, last, cls,
              q] + kv_inputs

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
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
