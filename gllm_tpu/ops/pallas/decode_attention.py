"""Pallas TPU paged decode attention.

The decode half of the reference's core attention kernel
(sgl_kernel ``flash_attn_with_kvcache`` — /root/reference/gllm/layers/
attention.py:92-140; Triton split-K analogue in layers/ops/
triton_decode_attention.py). One query row per sequence attends over that
sequence's paged KV context.

Design (TPU-first, not a Triton translation):
- grid = (S / gsz,): ``gsz`` sequences per program, each streaming its
  own page list through two buffer slots — HBM traffic is the
  sequence's *actual* context to the page, independent of the padded
  page-table bucket (the XLA gather fallback pays the padded extent)
  and of the block's edge (a context's last block stops at its last
  page).
- KV pages stay in HBM (`pl.ANY`) and are read as the pool stores them,
  token-major with the kv heads folded into the rows of a page
  ([page * Hkv, D]: a view, no data moves). A block is consumed in that
  layout and in the cache's dtype: ONE MXU product scores every query
  head against every row ([Hq, BK * Hkv], float32 accumulation), a mask
  keeps each query head the rows of its own kv head, and ``p @ V`` over
  the same rows is the per-head sum (``paged_kv.attend_block``). The
  MXU does Hkv times the products GQA needs and has them to spare; what
  it saves is every per-element pass over K and V.
- The kv-block loop bound is dynamic (ceil(kv_len / block)): padded
  sequences (kv_len 0) skip the loop entirely.
- The grid runs in order: a sequence's slots go to the next program's
  sequence as soon as its last block is read, so no program opens on a
  cold DMA.
- MLA absorbed mode: ``v_cache=None`` + ``v_dim`` reads values as the
  leading ``v_dim`` lanes of each key block (the latent prefix) — one DMA
  stream instead of two (reference MLA shares the latent cache the same
  way, layers/attention.py:272-293).
- A selection over positions: ``chosen`` ([S, positions] bool) is one
  more condition on a block's rows beside "holds a token of the context"
  (a selected-attention layer's decoding rows: the indexer's top-k,
  models/deepseek.py ``_dsa_attention``). The kernel still streams every
  page of a context and attends under the mask, which is the right trade
  while the chosen rows touch nearly every page; a group's mask rides
  into VMEM as int32 [gsz, blocks, block rows] and a round takes its
  block's row by index. Without the argument the program has no such
  operand: the call traces to the kernel it always was. At 128 heads over
  rows of 640 lanes the mask costs nothing that can be measured (1.765
  against 1.780 ms a call of 64 rows at 6.7 k of context, PR 43:
  docs/onchip_pr43/dsa_rows_forms.json), and the products, not the
  bytes, bind (36 % of the peak FLOP/s, 38 % of the rows' HBM time).
- A window: ``window`` (static) makes a row attend the last ``window``
  positions of its context only, the current one counted. Its first
  block is the one that holds position ``kv_len - window``; the blocks
  behind it are neither fetched nor scored, and that first block's rows
  from behind the window are masked (``attend_block(tokens_from=...)``).
  Without the argument the program is the kernel it always was.

What binds (PERF.md section 6, PR 28; the kernel alone on a v5e at 32
rows of 320-1909 tokens, bf16, 8 and 32 kv heads of 128): the update
this kernel had until then upcast every block to float32, transposed it
[BK, Hkv, D] -> [Hkv, BK, D] and fed float32 operands to the MXU with
the K or V tile stationary, and that, not the DMAs, was 90 % of its
time: computing on resident blocks alone took 270 us of the 301 us a
call, fetching alone 233, against 170 us of HBM time for the bytes.
``benchmarks/decode_attn_ablation.py`` takes the three readings again.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gllm_tpu.ops.pallas.paged_kv import (attend_block, kv_stream_specs,
                                          make_fetch_fns, mxu_operand,
                                          own_head_tokens, unpack_refs)

logger = logging.getLogger(__name__)

DEFAULT_KV_BLOCK = 256


class BlockUpdate(NamedTuple):
    """The form ``paged_decode_attention`` gives its per-block update,
    chosen from what it sees of a call and from nothing else."""
    operand: str        # dtype K, V and q enter the MXU in
    p_parts: int        # 2: p as a rounded part plus the remainder
    folded_heads: int   # kv heads in a block's rows (1: no own-head mask)
    group: int          # query heads a kv head


def block_update(q_dtype, kv_dtype, num_q_heads: int, num_kv_heads: int,
                 quant: bool = False) -> BlockUpdate:
    operand = mxu_operand(jnp.dtype(q_dtype), jnp.dtype(kv_dtype), quant)
    return BlockUpdate(operand.name, 2 if operand.itemsize == 2 else 1,
                       num_kv_heads, num_q_heads // num_kv_heads)


@functools.lru_cache(maxsize=None)
def _announce(form: BlockUpdate, kv_block: int, gsz: int) -> None:
    """Once per process and form, as the first program that holds the
    kernel is traced (the start-up's warm-up, on a server)."""
    logger.info(
        "[startup] paged_decode_attention: K, V and q enter the MXU as %s, "
        "p in %d part(s); %d kv head(s) folded into a block's rows, %d "
        "query head(s) each; blocks of %d tokens, %d sequence(s) a program",
        *form, kv_block, gsz)


def _kernel(kv_lens_ref, pt_ref,            # scalar prefetch
            *refs,
            page_size: int, pages_per_block: int, scale: float,
            num_kv_heads: int, v_dim: int, shared_kv: bool, gsz: int,
            quant: bool, masked: bool, window: Optional[int]):
    """``gsz`` sequences per grid program, TWO buffer slots each: in
    round ``r`` every sequence that still has a block ``r`` starts the
    fetch of its block ``r + 1``, waits for block ``r`` and attends it,
    so ``gsz`` to ``2 gsz`` blocks are in flight while one is attended,
    and a sequence that outlives its group keeps its own double buffer.
    The flash state lives in VMEM scratch and only live sequences touch
    it (see the module docstring for what binds). ``masked``: the first
    ref is the group's selection [gsz, blocks, BK * Hkv] (int32, nonzero
    where a row counts), of which a round takes its block's [1, BK * Hkv]
    by the block's index on the sublane axis. ``window``: a sequence's
    rounds begin at the block that holds position ``kv_len - window``."""
    *refs, m_ref, l_ref, acc_ref = refs
    chosen_ref = None
    if masked:
        chosen_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
     vs_buf, sems) = unpack_refs(refs, shared_kv, quant)
    gi = pl.program_id(0)
    bk = pages_per_block * page_size
    start_fetch, wait_fetch = make_fetch_fns(
        pt_ref, k_hbm, v_hbm, k_buf, v_buf, sems, pages_per_block,
        shared_kv, ks_hbm=ks_hbm, vs_hbm=vs_hbm, ks_buf=ks_buf,
        vs_buf=vs_buf)

    def fetch(fn, s, slot, blk):
        """Start or wait for block ``blk`` of sequence ``s``, if it has
        one: a whole block unrolled, a context's last block in a loop to
        its last page and not to the block's edge (~12 % of the bytes
        at 256-token blocks)."""
        left = kv_lens_ref[s] - blk * bk

        @pl.when(left >= bk)
        def _():
            fn(slot, s, blk)

        @pl.when((left > 0) & (left < bk))
        def _():
            fn(slot, s, blk, pl.cdiv(left, page_size))

    # Every program but the first finds its first blocks on their way
    # (the grid runs in order): a sequence's pair of slots goes to the
    # next program's sequence in the round after its last block, so the
    # HBM stays busy while a group's longest context runs out alone, and
    # no program opens with a DMA's latency.
    def first_block(s):
        return jnp.maximum(kv_lens_ref[s] - window, 0) // bk

    def start_first(program, g):
        s = program * gsz + g
        fetch(start_fetch, s, 2 * g, first_block(s) if window else 0)

    for g in range(gsz):
        pl.when(gi == 0)(functools.partial(start_first, 0, g))

    def hand_over(g):
        pl.when(gi + 1 < pl.num_programs(0))(
            functools.partial(start_first, gi + 1, g))

    seq_ids = [gi * gsz + g for g in range(gsz)]
    kv_lens = [kv_lens_ref[s] for s in seq_ids]
    n_blocks = [pl.cdiv(kv_len, bk) for kv_len in kv_lens]
    if window:
        first = [first_block(s) for s in seq_ids]
        n_blocks = [n - f for n, f in zip(n_blocks, first)]
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    own_tokens = own_head_tokens(q_ref.shape[1], num_kv_heads, bk)
    max_nb = n_blocks[0]
    for g in range(1, gsz):
        max_nb = jnp.maximum(max_nb, n_blocks[g])

    def body(r, _):
        parity = jax.lax.rem(r, 2)
        for g in range(gsz):
            @pl.when(r < n_blocks[g])
            def _(g=g):
                blk = first[g] + r if window else r
                fetch(start_fetch, seq_ids[g], 2 * g + 1 - parity, blk + 1)
                fetch(wait_fetch, seq_ids[g], 2 * g + parity, blk)
                m_ref[g], l_ref[g], acc_ref[g] = attend_block(
                    q_ref[g], k_buf, v_buf, 2 * g + parity, own_tokens,
                    kv_lens[g] - blk * bk, scale, v_dim, shared_kv,
                    m_ref[g], l_ref[g], acc_ref[g], ks_buf=ks_buf,
                    vs_buf=vs_buf, chosen=(
                        chosen_ref[g, pl.ds(r, 1), :] if masked else None),
                    tokens_from=(kv_lens[g] - window - blk * bk
                                 if window else None))

            pl.when(r == n_blocks[g])(functools.partial(hand_over, g))

    jax.lax.fori_loop(0, max_nb, body, None)
    for g in range(gsz):
        pl.when(n_blocks[g] == max_nb)(functools.partial(hand_over, g))
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)                 # padded seqs → 0


@functools.partial(jax.jit,
                   static_argnames=("scale", "kv_block", "interpret",
                                    "v_dim", "group_size", "name",
                                    "window"))
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
    group_size: int = 1,       # seqs per grid program (see _kernel)
    k_scale: Optional[jnp.ndarray] = None,   # [num_pages, Hkv] f32 (int8)
    v_scale: Optional[jnp.ndarray] = None,
    name: Optional[str] = None,   # the call's name in the HLO and the trace
                                  # (None: this function's)
    chosen: Optional[jnp.ndarray] = None,    # [S, max_pages * page_size]
                                  # bool: a row attends a position of its
                                  # context only where this is true (None:
                                  # the program has no such operand)
    window: Optional[int] = None,  # a row attends its last ``window``
                                  # positions only (None: all of them)
) -> jnp.ndarray:
    S, num_q_heads, head_dim = q.shape
    num_pages, page_size, num_kv_heads, _ = k_cache.shape
    max_pages = page_table.shape[1]
    shared_kv = v_cache is None
    quant = k_scale is not None
    if shared_kv:
        if v_dim is None:
            raise ValueError("v_dim required when v_cache is None")
    else:
        v_dim = v_cache.shape[-1]

    if quant and (num_kv_heads == 1 or shared_kv):
        raise NotImplementedError(
            "int8 KV cache unsupported for MQA/MLA decode kernels")
    # The kernels read a page as [page * Hkv, D]: the token-major pool
    # with the kv heads folded into the rows, which is the pool's own
    # order (no data moves), and for MQA the squeeze of the singleton
    # head axis that Mosaic's sublane tiling asks for anyway.
    k_cache = k_cache.reshape(num_pages, page_size * num_kv_heads, head_dim)
    if v_cache is not None:
        v_cache = v_cache.reshape(num_pages, page_size * num_kv_heads,
                                  v_dim)

    pages_per_block = max(1, min(kv_block // page_size, max_pages))
    _announce(block_update(q.dtype, k_cache.dtype, num_q_heads,
                           num_kv_heads, quant),
              pages_per_block * page_size, max(1, group_size))
    # page_table must cover whole blocks; pad with dummy page 0.
    rem = max_pages % pages_per_block
    if rem:
        page_table = jnp.pad(page_table,
                             ((0, 0), (0, pages_per_block - rem)))
        max_pages += pages_per_block - rem
    masked = chosen is not None
    if masked and window:
        raise NotImplementedError("a selection's mask under a window")
    if masked:
        # as the kernel slices it: int32 (a block's [1, rows] leaves VMEM
        # by a dynamic index on the sublane axis, which Mosaic takes for
        # 32-bit words), a row a (token, kv head) as the pool folds them,
        # whole blocks
        if chosen.ndim != 2 or chosen.shape[0] != S or (
                chosen.shape[1] > max_pages * page_size):
            raise ValueError(
                f"chosen {chosen.shape} is not [{S} rows, at most "
                f"{max_pages * page_size} positions]")
        chosen = jnp.pad(chosen.astype(jnp.int32), (
            (0, 0), (0, max_pages * page_size - chosen.shape[1])))
        if num_kv_heads > 1:
            chosen = jnp.repeat(chosen, num_kv_heads, axis=1)
        chosen = chosen.reshape(S, max_pages // pages_per_block, -1)

    # pad the seq axis to a whole number of groups; padded rows have
    # kv_len 0 (skip every round) and dummy page-table rows
    gsz = max(1, group_size)
    s_pad = -(-S // gsz) * gsz
    if s_pad != S:
        q = jnp.pad(q, ((0, s_pad - S), (0, 0), (0, 0)))
        kv_lens = jnp.pad(kv_lens, (0, s_pad - S))
        page_table = jnp.pad(page_table, ((0, s_pad - S), (0, 0)))
        if masked:
            chosen = jnp.pad(chosen, ((0, s_pad - S), (0, 0), (0, 0)))
    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_block=pages_per_block,
        scale=scale, num_kv_heads=num_kv_heads, v_dim=v_dim,
        shared_kv=shared_kv, gsz=gsz, quant=quant, masked=masked,
        window=window)

    kv_specs, scratch_shapes, kv_inputs = kv_stream_specs(
        k_cache, v_cache, pages_per_block, slots=2 * gsz, k_scale=k_scale,
        v_scale=v_scale)
    scratch_shapes += [pltpu.VMEM((gsz, num_q_heads, 1), jnp.float32),
                       pltpu.VMEM((gsz, num_q_heads, 1), jnp.float32),
                       pltpu.VMEM((gsz, num_q_heads, v_dim), jnp.float32)]
    in_specs = [
        pl.BlockSpec((gsz, num_q_heads, head_dim), lambda s, *_: (s, 0, 0),
                     memory_space=pltpu.VMEM),
    ] + kv_specs
    inputs = [kv_lens, page_table, q] + kv_inputs
    if masked:
        in_specs.insert(0, pl.BlockSpec(
            (gsz,) + chosen.shape[1:], lambda s, *_: (s, 0, 0),
            memory_space=pltpu.VMEM))
        inputs.insert(2, chosen)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_pad // gsz,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((gsz, num_q_heads, v_dim),
                               lambda s, *_: (s, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, num_q_heads, v_dim),
                                       q.dtype),
        # in order: a program starts its successor's first fetches
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*inputs)
    return out[:S] if s_pad != S else out
