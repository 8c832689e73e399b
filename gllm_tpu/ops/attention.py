"""Ragged paged attention — dispatch + XLA reference implementation.

This is the core attention path, covering what the reference gets from
sgl_kernel's ``flash_attn_with_kvcache`` / ``flash_attn_varlen_func``
(/root/reference/gllm/layers/attention.py:92-140): one varlen call serving a
mixed batch of prefill chunks and decode rows against the paged KV cache, with
causal masking relative to each sequence's already-computed context (chunked
prefill attends to all cached tokens plus the causal part of its own chunk).

Two implementations:
- ``xla``: gather-based reference. Runs on any backend (CPU tests, fallback),
  numerically the oracle for the Pallas kernels.
- ``pallas``: pure-decode batches (max_q_len == 1) run the per-sequence
  decode kernel (gllm_tpu/ops/pallas/decode_attention.py); mixed/prefill
  batches run the ragged varlen kernel
  (gllm_tpu/ops/pallas/ragged_attention.py) for their chunks and the
  decode kernel for the one-token rows that ride ahead of them
  (``_mixed_step_attention``). Both stream KV pages through
  VMEM with double-buffered DMA; MLA passes ``v_cache=None`` so values are
  read as the latent prefix of each key block (one DMA stream).

Metadata layout (built by the runner, all padded to static bucket shapes):
- cu_q_lens: [S+1] int32 — cumulative query lengths (padded seqs repeat the
  last value → q_len 0)
- kv_lens:   [S] int32 — per-seq total context AFTER this step's tokens
- page_table:[S, max_pages] int32 — padded entries point at the dummy page
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class AttentionMetadata(NamedTuple):
    cu_q_lens: jnp.ndarray    # [S+1] int32
    kv_lens: jnp.ndarray      # [S] int32
    page_table: jnp.ndarray   # [S, max_pages] int32
    num_seqs: jnp.ndarray     # [] int32 (informational; padding is masked
                              # out via q_len == 0 rows)


NEG_INF = float("-inf")

# TP shard context: (mesh, axis_name), set by the runner when the Pallas
# path must run per-TP-shard under shard_map (q and KV are head-sharded, so
# the kernels partition cleanly — each shard streams only its own heads'
# pages). Read at trace time of the runner's step fn; one active
# pallas+tp runner per process (every ModelRunner.__init__ resets it).
_SHARD_CTX = None


def tp_sharded() -> bool:
    """Is the Pallas path running per TP shard (a shard context is set)?"""
    return _SHARD_CTX is not None


def set_shard_context(mesh, axis_name: str = "tp") -> None:
    global _SHARD_CTX
    _SHARD_CTX = None if mesh is None else (mesh, axis_name)


def pallas_tp_compatible(num_q_heads: int, num_kv_heads: int,
                         tp: int) -> bool:
    """Can the Pallas kernels run per-TP-shard?

    Heads-sharded case (Hkv % tp == 0): per-shard GQA group is unchanged.
    KV-replicated case (small Hkv / MLA MQA — matches kv_cache_specs /
    latent_kv_specs): tp % Hkv == 0 means each shard's contiguous q-head
    slice belongs to exactly ONE kv head (heads are grouped kv-head-major),
    which the shard slices out and runs in MQA mode."""
    if num_q_heads % tp:
        return False
    return num_kv_heads % tp == 0 or tp % num_kv_heads == 0


def paged_attention(q, k_cache, v_cache, metadata, *, scale, max_q_len,
                    impl="xla", v_dim=None, k_scale=None, v_scale=None,
                    window=None):
    """Public entry: dispatch to the (jitted) single-shard implementation,
    wrapping the Pallas path in shard_map when a TP shard context is set.
    ``k_scale``/``v_scale`` ([num_pages, Hkv] f32) mark an int8 quantized
    cache — both implementations dequantize on the read path (in-kernel
    for Pallas, on the gathered pages for XLA). ``window`` (static): a
    query at position t attends positions t - window < j <= t of its
    sequence's pages and nothing older (a windowed GQA layer whose rows
    stay in the paged pool); the Pallas calls then carry the names
    ``WINDOW_NAMES`` and fetch only the pages the window overlaps."""
    if window and impl == "pallas" and _SHARD_CTX is not None:
        raise NotImplementedError(
            "windowed paged attention under a tp shard context")
    if impl == "pallas" and _SHARD_CTX is not None:
        mesh, axis = _SHARD_CTX
        tp = mesh.shape[axis]
        if tp > 1:
            return _pallas_sharded(q, k_cache, v_cache, metadata,
                                   scale=scale, max_q_len=max_q_len,
                                   v_dim=v_dim, mesh=mesh, axis=axis,
                                   k_scale=k_scale, v_scale=v_scale)
    return _paged_attention(q, k_cache, v_cache, metadata, k_scale,
                            v_scale, scale=scale, max_q_len=max_q_len,
                            impl=impl, v_dim=v_dim, window=window)


def _pallas_sharded(q, k_cache, v_cache, metadata, *, scale, max_q_len,
                    v_dim, mesh, axis, k_scale=None, v_scale=None):
    """Run the Pallas kernels per TP shard: q sharded on its head axis, KV
    sharded on the kv-head axis when divisible (else replicated — small-Hkv
    and MLA-MQA caches are replicated by kv_cache_specs), metadata
    replicated. The per-shard call sees plain smaller arrays, so the
    kernels run untouched; GSPMD moves nothing (shardings already match
    the layer's activation/cache placement)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[axis]
    num_q_heads = q.shape[1]
    num_kv_heads = k_cache.shape[2]
    if not pallas_tp_compatible(num_q_heads, num_kv_heads, tp):
        raise ValueError(
            f"pallas tp={tp} incompatible with Hq={num_q_heads} "
            f"Hkv={num_kv_heads}")
    kv_sharded = num_kv_heads % tp == 0
    if k_scale is not None and not kv_sharded:
        # the replicated-KV MQA-slice path below is gated off for int8
        # (runner._check_kv_quant rejects the topology up front)
        raise NotImplementedError(
            "int8 KV cache needs num_kv_heads % tp == 0 on the pallas "
            "path")
    qs = P(None, axis, None)
    ks = P(None, None, axis, None) if kv_sharded else P(None, None, None,
                                                        None)
    ss = P(None, axis)          # scales shard with the kv-head axis
    md_specs = AttentionMetadata(P(None), P(None), P(None, None), P())

    def inner(q, k, v, md, ksc=None, vsc=None):
        if not kv_sharded and num_kv_heads > 1:
            # KV replicated with tp % Hkv == 0: this shard's contiguous
            # q-head slice belongs to exactly one kv head (kv-head-major
            # grouping) — slice it out and run the kernels in MQA mode.
            head = jax.lax.axis_index(axis) // (tp // num_kv_heads)
            k = jax.lax.dynamic_slice_in_dim(k, head, 1, axis=2)
            if v is not None:
                v = jax.lax.dynamic_slice_in_dim(v, head, 1, axis=2)
        return _paged_attention(q, k, v, md, ksc, vsc, scale=scale,
                                max_q_len=max_q_len, impl="pallas",
                                v_dim=v_dim)

    # Inside an already-set mesh context (the runner's step trace, or the
    # dp-manual shard_map region where the dp axis is Manual) the inner
    # shard_map binds the CONTEXT abstract mesh (mesh=None infers it) and
    # takes EVERY axis that is not manual yet, not only tp: Mosaic refuses
    # a kernel that any auto axis — even one of size 1, as dp and sp are
    # under plain tp — could still partition ("Mosaic kernels cannot be
    # automatically partitioned"; interpret mode never sees this).
    # Standalone (unit tests, no context) the concrete mesh is bound
    # fully-manual.
    from gllm_tpu.parallel.mesh import active_mesh
    am = active_mesh()
    if am.shape_tuple:
        kw = dict(mesh=None,
                  axis_names=set(am.axis_names) - set(am.manual_axes))
    else:
        kw = dict(mesh=mesh)
    if v_cache is None:
        fn = shard_map(lambda q, k, md: inner(q, k, None, md),
                       in_specs=(qs, ks, md_specs), out_specs=qs,
                       check_vma=False, **kw)
        return fn(q, k_cache, metadata)
    if k_scale is not None:
        fn = shard_map(inner, in_specs=(qs, ks, ks, md_specs, ss, ss),
                       out_specs=qs, check_vma=False, **kw)
        return fn(q, k_cache, v_cache, metadata, k_scale, v_scale)
    fn = shard_map(inner, in_specs=(qs, ks, ks, md_specs),
                   out_specs=qs, check_vma=False, **kw)
    return fn(q, k_cache, v_cache, metadata)


@functools.partial(jax.jit, static_argnames=("max_q_len", "scale", "impl",
                                             "v_dim", "window"))
def _paged_attention(
    q: jnp.ndarray,            # [T, Hq, D]
    k_cache: jnp.ndarray,      # [num_pages, page_size, Hkv, D]
    v_cache,                   # [P, page, Hkv, Dv] or None → v = k[:, :Dv]
                               # (MLA absorbed: values are the latent
                               # prefix of the keys — one cache, one DMA
                               # stream)
    metadata: AttentionMetadata,
    k_scale=None,              # [num_pages, Hkv] f32: int8 cache scales
    v_scale=None,              # (per page per kv head; None = fp cache)
    *,
    scale: float,
    max_q_len: int,
    impl: str = "xla",
    v_dim: Optional[int] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    if v_cache is None and v_dim is None:
        raise ValueError("v_dim required when v_cache is None")
    # Packed lane layout (kv_pack > 1): the cache stores ``pack`` adjacent
    # kv heads per row — [P, ps, Hkv/pack, D*pack] — so head_dim < 128
    # models still meet Mosaic's 128-lane tiling. Detected structurally:
    # non-MLA caches otherwise always have last dim == head_dim.
    pack = (k_cache.shape[-1] // q.shape[-1]
            if v_cache is not None and k_cache.shape[-1] != q.shape[-1]
            else 1)
    if impl == "xla":
        if v_cache is None:
            v_cache = k_cache[..., :v_dim]
        elif pack > 1:
            P_, ps = k_cache.shape[:2]
            hkv = k_cache.shape[2] * pack
            k_cache = k_cache.reshape(P_, ps, hkv, q.shape[-1])
            v_cache = v_cache.reshape(P_, ps, hkv, q.shape[-1])
            if k_scale is not None:
                # packed row [h_p, D*pack] unpacks to heads h_p*pack+j —
                # repeat each packed-group scale over its pack members
                k_scale = jnp.repeat(k_scale, pack, axis=1)
                v_scale = jnp.repeat(v_scale, pack, axis=1)
        return _xla_paged_attention(q, k_cache, v_cache, metadata,
                                    scale=scale, max_q_len=max_q_len,
                                    k_scale=k_scale, v_scale=v_scale,
                                    window=window)
    if impl == "pallas":
        backend = jax.default_backend()
        if backend == "cpu":
            interpret = True
        elif backend == "tpu":
            interpret = False
        else:
            raise NotImplementedError(
                f"pallas attention unsupported on backend {backend!r}; "
                "use impl='xla'")
        slot = None
        if pack > 1:
            # Expand q into block-diagonal 128-lane rows: head h's values
            # occupy the lane block its kv head holds inside the packed
            # row; the other pack-1 blocks are zero, so the kernel's
            # q·k_packed dot contracts to exactly the head's own scores
            # (2× MAC waste — irrelevant in the bandwidth-bound regime).
            T, num_q_heads, D = q.shape
            group = num_q_heads // (k_cache.shape[2] * pack)
            slot = (jnp.arange(num_q_heads, dtype=jnp.int32)
                    // group) % pack
            onehot = jax.nn.one_hot(slot, pack, dtype=q.dtype)
            q = (q[:, :, None, :] * onehot[None, :, :, None]
                 ).reshape(T, num_q_heads, pack * D)

        if max_q_len == 1:
            # Pure-decode batch: T == S, one query row per sequence (the
            # layout prepare.py emits for max_q_len == 1). The per-seq
            # decode kernel wins here: its [Hkv, G, BK] dot shape avoids
            # the ragged kernel's masked-row waste for 1-token rows.
            if q.shape[0] != metadata.kv_lens.shape[0]:
                raise ValueError(
                    f"pallas decode path requires T == S, got T={q.shape[0]} "
                    f"S={metadata.kv_lens.shape[0]}")
            out = _decode_kernel(
                q, k_cache, v_cache, metadata.kv_lens, metadata.page_table,
                k_scale, v_scale, scale=scale, interpret=interpret,
                v_dim=v_dim, window=window,
                name=WINDOW_NAMES["decode"] if window else None)
        else:
            out = _mixed_step_attention(
                q, k_cache, v_cache, metadata, k_scale, v_scale,
                scale=scale, interpret=interpret, v_dim=v_dim,
                window=window)
        if pack > 1:
            # The packed p·v_packed dot produced every lane block; keep
            # each head's own block (the rest mixed other heads' values).
            T, num_q_heads = out.shape[:2]
            D = out.shape[-1] // pack
            out = out.reshape(T, num_q_heads, pack, D)
            out = jnp.take_along_axis(
                out, slot[None, :, None, None], axis=2)[:, :, 0]
        return out
    raise ValueError(f"unknown attention impl {impl!r}")


def _decode_kernel(q, k_cache, v_cache, kv_lens, page_table, k_scale,
                   v_scale, *, scale: float, interpret: bool, v_dim,
                   name=None, chosen=None, window=None):
    """The decode kernel over one query row a sequence, at the table's
    blocks for the cache's KV heads (and for a selection's mask,
    ``chosen``, or a ``window``, where the call takes one)."""
    from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
    from gllm_tpu.ops.pallas.tuning import decode_blocks
    cfg = decode_blocks(k_cache.shape[2], chosen=chosen is not None,
                        num_q_heads=q.shape[1])
    return paged_decode_attention(
        q, k_cache, v_cache, kv_lens, page_table, scale=scale,
        interpret=interpret, v_dim=v_dim, kv_block=cfg["kv_block"],
        group_size=int(cfg.get("group", 1)), k_scale=k_scale,
        v_scale=v_scale, name=name, chosen=chosen, window=window)


# What the riding rows' call is named in the HLO and on the trace's
# ``XLA Ops`` line. It starts with the ragged kernel's name because a mixed
# step's attention is read as ONE piece of work there (perfbench's
# ``^%ragged_paged_attention``: the chunk and the rows that ride, over all
# the time spent on them, whatever implements them), and a step program is
# classed as decode-only by holding ``paged_decode_attention``.
DECODE_ROWS_NAME = "ragged_paged_attention_decode_rows"

# The windowed calls' names (``paged_attention(window=...)``), their own so
# that a reader tells a windowed layer's time from a full layer's in one
# step program: the decode-only step's call, the mixed step's chunk call,
# and its riding rows' (which, as above, begins with the chunk call's).
WINDOW_NAMES = {"decode": "swa_paged_decode_attention",
                "ragged": "swa_ragged_paged_attention",
                "rows": "swa_ragged_paged_attention_decode_rows"}


def _mixed_step_attention(q, k_cache, v_cache, md: AttentionMetadata,
                          k_scale, v_scale, *, scale: float,
                          interpret: bool, v_dim, window=None):
    """A batch with ``max_q_len > 1``, split by what ``cu_q_lens`` shows:
    the leading one-token sequences (the decode prefix: the scheduler packs
    decoding rows first, and token ``s`` IS sequence ``s`` there) go to the
    decode kernel, everything else (prefill chunks, a one-token sequence
    behind a chunk, spec-decode rows of ``1 + k`` tokens) to the ragged
    kernel, whose q block computes its every row against each sequence it
    overlaps: 256 rows for one live one in the dense cell (PERF.md
    section 6, PR 38). Both calls take the same page table; each has the
    other's sequences at ``kv_len`` 0, which both kernels skip without a
    fetch or a dot. The prefix length is traced: no compile axis."""
    from gllm_tpu.ops.pallas.ragged_attention import (
        _decode_prefix_len, ragged_paged_attention)
    from gllm_tpu.ops.pallas.tuning import ragged_blocks
    T, S = q.shape[0], md.kv_lens.shape[0]
    riding = jnp.arange(S, dtype=jnp.int32) < _decode_prefix_len(
        md.cu_q_lens, S)
    rows = _decode_kernel(
        q[:S] if T >= S else jnp.pad(q, ((0, S - T), (0, 0), (0, 0))),
        k_cache, v_cache, jnp.where(riding, md.kv_lens, 0), md.page_table,
        k_scale, v_scale, scale=scale, interpret=interpret, v_dim=v_dim,
        name=WINDOW_NAMES["rows"] if window else DECODE_ROWS_NAME,
        window=window)
    blocks = ragged_blocks(q.shape[1], k_cache.shape[2])
    out = ragged_paged_attention(
        q, k_cache, v_cache, md.cu_q_lens,
        jnp.where(riding, 0, md.kv_lens), md.page_table, scale=scale,
        interpret=interpret, v_dim=v_dim, q_block=blocks["q_block"],
        kv_block=blocks["kv_block"], k_scale=k_scale, v_scale=v_scale,
        window=window, name=WINDOW_NAMES["ragged"] if window else None)
    # the riding rows are the first min(S, T) tokens at most: write those
    # rows, not a select over all T (at 512 x 64 x 512 that is 100 MB of
    # traffic a layer)
    n = min(S, T)
    head = jnp.where(riding[:n, None, None], rows[:n], out[:n])
    return jax.lax.dynamic_update_slice(out, head, (0, 0, 0))


def _xla_paged_attention(q, k_cache, v_cache, md: AttentionMetadata, *,
                         scale: float, max_q_len: int,
                         k_scale=None, v_scale=None, window=None):
    T, num_q_heads, head_dim = q.shape
    num_pages, page_size, num_kv_heads, _ = k_cache.shape
    v_dim = v_cache.shape[-1]     # may differ from head_dim (MLA: values
                                  # are the latent prefix of the keys)
    S, max_pages = md.page_table.shape
    group = num_q_heads // num_kv_heads
    max_kv = max_pages * page_size

    q_lens = md.cu_q_lens[1:] - md.cu_q_lens[:-1]                    # [S]
    # Gather per-seq query rows → [S, Qmax, Hq, D]
    local_q = jnp.arange(max_q_len, dtype=jnp.int32)                 # [Qmax]
    q_idx = jnp.clip(md.cu_q_lens[:-1, None] + local_q[None, :], 0, T - 1)
    q_valid = local_q[None, :] < q_lens[:, None]                     # [S, Qmax]
    qg = q[q_idx]                                                    # [S,Qmax,Hq,D]

    # Gather per-seq KV pages → [S, max_kv, Hkv, D]. int8 caches
    # dequantize on the GATHERED pages (page-granular scales gathered by
    # the same table) — the full-precision cache never materializes.
    kg = k_cache[md.page_table]         # [S, MP, ps, Hkv, D]
    vg = v_cache[md.page_table]
    if k_scale is not None:
        kg = kg.astype(jnp.float32) * \
            k_scale[md.page_table][:, :, None, :, None]
        vg = vg.astype(jnp.float32) * \
            v_scale[md.page_table][:, :, None, :, None]
    kg = kg.reshape(S, max_kv, num_kv_heads, head_dim)
    vg = vg.reshape(S, max_kv, num_kv_heads, v_dim)

    # Causal+context mask: query at local index t has absolute position
    # kv_len - q_len + t; key j is visible iff j <= that position.
    kv_pos = jnp.arange(max_kv, dtype=jnp.int32)                     # [K]
    q_pos = (md.kv_lens[:, None] - q_lens[:, None] + local_q[None, :])
    visible = (kv_pos[None, None, :] <= q_pos[:, :, None])           # [S,Q,K]
    visible &= (kv_pos[None, None, :] < md.kv_lens[:, None, None])
    visible &= q_valid[:, :, None]
    if window:
        visible &= kv_pos[None, None, :] > q_pos[:, :, None] - window

    qg = qg.reshape(S, max_q_len, num_kv_heads, group, head_dim)
    scores = jnp.einsum("sqhgd,skhd->shgqk", qg.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    scores = jnp.where(visible[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # Rows with no visible keys (padding) produce NaN-free zeros:
    probs = jnp.where(visible[:, None, None, :, :], probs, 0.0)
    out = jnp.einsum("shgqk,skhd->sqhgd", probs, vg.astype(jnp.float32))
    out = out.reshape(S, max_q_len, num_q_heads, v_dim).astype(q.dtype)

    # Scatter back to the ragged token layout. Padded/invalid rows carry
    # zeros and clipped duplicate indices — scatter-add keeps it exact.
    out = jnp.where(q_valid[:, :, None, None], out, 0)
    flat = jnp.zeros((T, num_q_heads, v_dim), q.dtype)
    return flat.at[q_idx.reshape(-1)].add(
        out.reshape(S * max_q_len, num_q_heads, v_dim))
