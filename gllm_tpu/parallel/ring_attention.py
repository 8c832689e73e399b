"""Ring attention: sequence/context parallelism over an ``sp`` mesh axis.

The reference has NO sequence parallelism (SURVEY.md §2.2 row SP/CP —
long context is handled by chunked prefill + paged KV + MLA chunked-context).
This module goes beyond parity: causal ring attention for long-context
prefill, the TPU-native CP design — the sequence axis is sharded over the
``sp`` mesh axis, K/V shards rotate around the ring with
``jax.lax.ppermute`` (ICI neighbor exchanges), and each hop's partial
attention is merged with the running flash-attention state (LSE merge — the
same math as the reference's chunked-context merge_attn_states,
/root/reference/gllm/layers/ops/merge_attn_states.py).

Causality across shards: query shard q holds global positions
``[q*C, (q+1)*C)``; the K/V shard visiting from source ``s`` is
- fully visible when s < q (all its keys precede all queries),
- causally masked when s == q,
- fully masked (skipped) when s > q.

Usage: ``ring_attention(q, k, v, axis_name="sp")`` inside
``shard_map``/``pjit`` with q/k/v sharded on their sequence axis.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = float("-inf")


def _block_attention(q, k, v, scale, mask):
    """Plain f32 attention for one (q-shard, kv-shard) pair.

    Returns (out [T, Hq, D] unnormalized, m [T, Hq] rowmax,
    l [T, Hq] rowsum) for LSE merging.
    """
    Hq = q.shape[1]
    Hkv = k.shape[1]
    group = Hq // Hkv
    T, Ck = q.shape[0], k.shape[0]
    qh = q.reshape(T, Hkv, group, -1).astype(jnp.float32)
    scores = jnp.einsum("thgd,shd->thgs", qh, k.astype(jnp.float32)) * scale
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                         # [T, Hkv, g]
    # all-masked rows: keep m finite so exp() is well-defined
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                              # [T, Hkv, g]
    out = jnp.einsum("thgs,shd->thgd", p, v.astype(jnp.float32))
    return (out.reshape(T, Hq, -1), m_safe.reshape(T, Hq),
            l.reshape(T, Hq))


def _merge(acc, m, l, out_b, m_b, l_b):
    """Merge a new partial-attention block into the running flash state."""
    m_new = jnp.maximum(m, m_b)
    a1 = jnp.exp(m - m_new)
    a2 = jnp.exp(m_b - m_new)
    acc = acc * a1[..., None] + out_b * a2[..., None]
    l_new = l * a1 + l_b * a2
    return acc, m_new, l_new


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   kv_valid=None, *, axis_name: str,
                   scale: Optional[float] = None,
                   axis_size: Optional[int] = None):
    """Causal ring attention inside shard_map.

    q: [C, Hq, D] local query shard (global seq sharded over axis_name)
    k/v: [C, Hkv, D] local key/value shards.
    kv_valid: optional replicated scalar — global token count actually
    valid; keys at positions >= kv_valid are masked everywhere (the
    engine's bucketed prefill pads the token axis, and a padded KEY at a
    fake position must not leak into real queries' softmax).
    Returns the local output shard [C, Hq, D].
    """
    C, Hq, D = q.shape
    if scale is None:
        scale = D ** -0.5
    n = axis_size if axis_size is not None \
        else jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)

    pos_q = my * C + jnp.arange(C)

    acc = jnp.zeros((C, Hq, v.shape[-1]), jnp.float32)
    # finite -inf sentinel: keeps exp(m - m_new) well-defined before the
    # first contributing block
    m = jnp.full((C, Hq), -1e30, jnp.float32)
    l = jnp.zeros((C, Hq), jnp.float32)
    # mark the device-constant init values as varying over the ring axis so
    # the fori_loop carry type matches the per-shard results
    acc, m, l = (jax.lax.pcast(x, (axis_name,), to="varying")
                 for x in (acc, m, l))

    def hop(i, carry):
        acc, m, l, k_cur, v_cur = carry
        src = jax.lax.rem(my - i + n, n)     # whose shard we hold this hop
        pos_k = src * C + jnp.arange(C)
        mask = pos_k[None, :] <= pos_q[:, None]
        if kv_valid is not None:
            mask = mask & (pos_k[None, :] < kv_valid)
        out_b, m_b, l_b = _block_attention(q, k_cur, v_cur, scale, mask)
        # skip fully-masked hops (src > my): l_b is all zero there and the
        # merge is a no-op because m_b is 0-masked rows with l_b=0.
        acc, m, l = _merge(acc, m, l, out_b, m_b, l_b)
        # rotate kv to the next device on the ring
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m, l, k_nxt, v_nxt

    acc, m, l, _, _ = jax.lax.fori_loop(0, n, hop, (acc, m, l, k, v))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Optional[Mesh] = None,
                           axis_name: str = "sp",
                           scale: Optional[float] = None, kv_valid=None):
    """Shard q/k/v over ``axis_name`` on their sequence axis and run ring
    attention via shard_map.

    mesh=None binds the CONTEXT abstract mesh with only ``axis_name``
    manual — the form the serving step uses inside its jit trace (the
    other mesh axes stay GSPMD-auto); a concrete mesh is bound fully
    (standalone / unit-test use). ``kv_valid``: optional replicated scalar
    masking padded keys (see ring_attention)."""
    from jax import shard_map

    from gllm_tpu.parallel.mesh import active_mesh

    spec = P(axis_name, None, None)
    kw = (dict(mesh=None, axis_names={axis_name}) if mesh is None
          else dict(mesh=mesh))
    m = mesh if mesh is not None else active_mesh()
    sizes = dict(m.shape_tuple)
    part = functools.partial(ring_attention, axis_name=axis_name,
                             scale=scale, axis_size=sizes[axis_name])
    if kv_valid is None:
        fn = shard_map(part, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False, **kw)
        return fn(q, k, v)
    fn = shard_map(part, in_specs=(spec, spec, spec, P()),
                   out_specs=spec, check_vma=False, **kw)
    return fn(q, k, v, kv_valid)
