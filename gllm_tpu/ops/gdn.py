"""Gated DeltaNet ops (Qwen3-Next / Qwen3.5 hybrid linear attention).

TPU-native equivalents of the reference's fla Triton suite
(/root/reference/gllm/layers/ops/fla/, 7210 LoC — chunked prefill
``chunk_gated_delta_rule``, recurrent decode, causal conv1d with state,
gated RMSNorm). Semantics follow the HF Qwen3Next reference math
(transformers qwen3_next torch_chunk_gated_delta_rule et al.), which those
kernels implement.

Design notes:
- everything computes in float32 (the recurrence is numerically touchy; the
  reference kernels do the same);
- the in-chunk triangular inverse (I - A)^-1 is a `solve_triangular`, not
  the reference's sequential row loop — one XLA op that maps onto the MXU;
- batched over sequences with per-token validity folded into (g, beta):
  a padded token with g = 0, beta = 0 is the identity on the state, so
  ragged batches ride in fixed [S, T] shapes with no extra machinery;
- decode (T = 1) uses the closed-form single-step update, no scan.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


GDN_CHUNK = 64      # tokens in a chunk of the chunked rule


def gdn_heads_abreast(heads: int, dv: int) -> int:
    """How many heads' states lie side by side along the lanes of a slot
    of the pool (``pack_state``): the fewest whose ``g x Dv`` lanes are a
    whole number of the TPU's 128-lane tiles, so that the pool stores its
    elements and no padding (2 at Olmo-Hybrid's 192, 1 at Qwen3-Next's
    128); 1, the heads each alone, where the heads do not divide into
    such groups."""
    g = 128 // math.gcd(dv, 128)
    return g if heads % g == 0 else 1


def pack_state(state: jnp.ndarray, g: int) -> jnp.ndarray:
    """[.., H, Dk, Dv] -> [.., H / g, Dk, g x Dv], as the slot pool
    stores a state: head ``j g + i`` in lanes ``[i Dv, (i + 1) Dv)`` of
    group ``j``. The one definition the XLA rule, the kernels
    (ops/pallas/gdn_recurrent.py, gdn_scan.py) and the tests share."""
    *lead, H, Dk, Dv = state.shape
    n = len(lead)
    return state.reshape(*lead, H // g, g, Dk, Dv).swapaxes(
        n + 1, n + 2).reshape(*lead, H // g, Dk, g * Dv)


def unpack_state(packed: jnp.ndarray, g: int) -> jnp.ndarray:
    """``pack_state``'s inverse: [.., H / g, Dk, g x Dv] -> [.., H, Dk,
    Dv]."""
    *lead, G, Dk, lanes = packed.shape
    n = len(lead)
    return packed.reshape(*lead, G, Dk, g, lanes // g).swapaxes(
        n + 1, n + 2).reshape(*lead, G * g, Dk, lanes // g)


def gdn_chunk_slots(tokens_pad: int, seqs_pad: int,
                    chunk: int = GDN_CHUNK) -> Tuple[int, int]:
    """(chunks, tokens per chunk) of the packed layout a mixed step of
    ``tokens_pad`` token slots and ``seqs_pad`` rows runs the chunked rule
    over. A prefilling row of n tokens takes ceil(n / C) chunks, so the
    rows of a step need at most tokens / C + (rows that prefill) of them;
    the layout holds twice tokens / C where the row bucket allows that
    many rows, and the batch builder picks a token bucket whose layout
    holds the step (``BatchBuilder.shape_signature``). ``chunk``: the
    model's chunk (``ModelConfig.ssm_chunk``: 64 for the GDN rule, 128 for
    Mamba-2's)."""
    c = min(chunk, tokens_pad)
    n = tokens_pad // c
    return n + min(seqs_pad, n), c


def gdn_chunk_rows_cap(max_tokens: int, chunk: int = GDN_CHUNK) -> int:
    """Rows with more than one new token that a step of a hybrid model may
    hold (the scheduler's cap): what the layout of the LARGEST token
    bucket takes beside the step's tokens. Rows of n_i tokens need
    sum(ceil(n_i / C)) <= tokens // C + rows chunks, and that layout has
    max_tokens // C + min(row bucket, max_tokens // C) of them, so a step
    under the cap always finds a built-in bucket that holds it."""
    return max_tokens // min(chunk, max_tokens)


def gdn_chunks_needed(q_lens, c: int) -> int:
    """Chunks of ``c`` tokens the rows that prefill (more than one new
    token) take in the packed layout."""
    return sum(-(-n // c) for n in q_lens if n > 1)


class PackedChunks(NamedTuple):
    """Where the rows of a mixed step that prefill sit in the packed
    layout of ``gdn_chunk_slots`` (N chunks of C tokens): per row [S] and
    per chunk [N] index arithmetic shared by the GDN and Mamba-2 layers."""
    is_pre: jnp.ndarray     # [S] the row has more than one new token
    ch_start: jnp.ndarray   # [S] the row's first chunk
    ch_end: jnp.ndarray     # [S] one past its last chunk
    live: jnp.ndarray       # [N] the chunk belongs to a row
    row: jnp.ndarray        # [N] which row
    first: jnp.ndarray      # [N] it is its row's first chunk
    tok0: jnp.ndarray       # [N] flat index of its first token
    n_valid: jnp.ndarray    # [N] real tokens in it
    valid: jnp.ndarray      # [N, C]
    tok: jnp.ndarray        # [N, C] flat token index (clipped)


def packed_chunks(cu: jnp.ndarray, T: int, S: int, N: int,
                  C: int) -> PackedChunks:
    q_lens = cu[1:] - cu[:-1]
    is_pre = q_lens > 1
    n_ch = jnp.where(is_pre, (q_lens + C - 1) // C, 0)       # [S]
    ch_end = jnp.cumsum(n_ch)
    ch_start = ch_end - n_ch
    c_idx = jnp.arange(N, dtype=jnp.int32)
    live = c_idx < ch_end[-1]
    row = jnp.minimum(jnp.searchsorted(ch_end, c_idx, side="right"),
                      S - 1).astype(jnp.int32)
    j = c_idx - ch_start[row]                # chunk number inside its row
    first = live & (j == 0)
    tok0 = cu[row] + j * C
    local = jnp.arange(C, dtype=jnp.int32)
    n_valid = jnp.where(live, jnp.clip(q_lens[row] - j * C, 0, C), 0)
    valid = local[None, :] < n_valid[:, None]                # [N, C]
    tok = jnp.clip(tok0[:, None] + local[None, :], 0, T - 1)
    return PackedChunks(is_pre, ch_start, ch_end, live, row, first, tok0,
                        n_valid, valid, tok)


def packed_slot_of_token(cu: jnp.ndarray, ch_start: jnp.ndarray, T: int,
                         S: int, C: int):
    """(for each flat token its slot in the packed layout [T], its row
    [T]); valid for the tokens of rows that prefill."""
    t_idx = jnp.arange(T, dtype=jnp.int32)
    t_row = jnp.minimum(jnp.searchsorted(cu[1:], t_idx, side="right"),
                        S - 1).astype(jnp.int32)
    t_local = t_idx - cu[t_row]
    return (ch_start[t_row] + t_local // C) * C + t_local % C, t_row


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    inv = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
    return x * inv


def causal_conv1d(x: jnp.ndarray, state: jnp.ndarray, weight: jnp.ndarray,
                  q_lens: jnp.ndarray, bias: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv with carried state (reference
    mamba/causal_conv1d_triton.py semantics, varlen + state slots).

    x: [S, T, C] (per-seq rows, padded past q_lens[s])
    state: [S, K-1, C] last K-1 REAL inputs from previous chunks, oldest
        first (channels last, as the slot pool keeps them: on the TPU the
        channel axis lies along the lanes and a row of the pool moves
        without a change of layout)
    weight: [C, K]; bias: [C] or None (Mamba-2's convolution has one)
    Returns (silu(conv(x)) [S, T, C], new_state [S, K-1, C]) where the new
    state holds the last K-1 valid inputs (padding excluded).
    """
    S, T, C = x.shape
    K = weight.shape[-1]
    xf = x.astype(jnp.float32)
    buf = jnp.concatenate([state.astype(jnp.float32), xf],
                          axis=1)                     # [S, K-1+T, C]
    out = sum(buf[:, j:j + T, :] * weight[:, j].astype(jnp.float32)
              for j in range(K))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    out = jax.nn.silu(out)
    # new state = inputs at positions q_len-1 ... q_len-(K-1) of the valid
    # region, i.e. buf rows [q_len, q_len+K-2] (buf row i holds input i-K+1)
    idx = q_lens[:, None] + jnp.arange(K - 1)[None, :]       # [S, K-1]
    new_state = jnp.take_along_axis(
        buf, idx[:, :, None].astype(jnp.int32), axis=1)      # [S, K-1, C]
    return out, new_state


def recurrent_gated_delta_step(
    q: jnp.ndarray,          # [S, H, Dk]
    k: jnp.ndarray,          # [S, H, Dk]
    v: jnp.ndarray,          # [S, H, Dv]
    g: jnp.ndarray,          # [S, H] log decay (<= 0)
    beta: jnp.ndarray,       # [S, H] write strength in (0, 2)
    state: jnp.ndarray,      # [S, H, Dk, Dv] f32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step of the gated delta rule (HF
    torch_recurrent_gated_delta_rule with T = 1). ``beta`` is sigmoid(.)
    in (0, 1), or twice that where the model allows the transition
    ``I - beta k k^T`` a negative eigenvalue: nothing here assumes
    beta < 1, nor equal or 128-aligned head dims."""
    q = l2norm(q.astype(jnp.float32))
    k = l2norm(k.astype(jnp.float32))
    v = v.astype(jnp.float32)
    scale = q.shape[-1] ** -0.5
    q = q * scale
    state = state * jnp.exp(g)[..., None, None]
    kv_mem = jnp.einsum("shkv,shk->shv", state, k)
    delta = (v - kv_mem) * beta[..., None]
    state = state + jnp.einsum("shk,shv->shkv", k, delta)
    out = jnp.einsum("shkv,shk->shv", state, q)
    return out, state


def gdn_impl_for(attn_impl: str, tp_sharded: bool) -> str:
    """How the GDN layers' recurrent step runs beside an attention that
    runs as ``attn_impl``: the Pallas kernel (ops/pallas/gdn_recurrent.py,
    any head dims) wherever attention runs Pallas kernels, except under
    tensor parallelism, where the slot pool is sharded over value heads
    and the kernels are not partitioned; XLA otherwise. The same choice
    holds for the chunked rule's inter-chunk scan (ops/pallas/gdn_scan.py
    over the packed layout); its in-chunk half is XLA's either way."""
    pallas = attn_impl == "pallas" and not tp_sharded
    return "pallas" if pallas else "xla"


def _chunk_local(q, k, v, g, beta, C: int):
    """The in-chunk half of the chunked rule: everything a chunk of ``C``
    tokens can compute without the state that enters it. Inputs as
    ``chunk_gated_delta_rule`` takes them ([S, T, H, D], any T: padded to
    whole chunks here). Returns, each [S, H, N, C, .] float32: q and k
    (l2-normed, q scaled), ``v2 = T v_beta``, ``k_cumdecay``, the masked
    local scores and the in-chunk cumulative log decay [S, H, N, C]."""
    with jax.named_scope("gdn_chunk_local"):
        S, T, H, Dk = q.shape
        pad = (-T) % C
        q = l2norm(q.astype(jnp.float32)) * Dk ** -0.5
        k = l2norm(k.astype(jnp.float32))
        v = v.astype(jnp.float32)
        g = g.astype(jnp.float32)
        beta = beta.astype(jnp.float32)
        if pad:
            q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for a in (q, k, v))
            g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                       for a in (g, beta))
        N = (T + pad) // C

        # [S, H, N, C, D] chunked layout
        def chunked(a):
            return a.reshape(S, N, C, H, -1).transpose(0, 3, 1, 2, 4)

        qc, kc, vc = chunked(q), chunked(k), chunked(v)
        gc = g.reshape(S, N, C, H).transpose(0, 3, 1, 2)     # [S, H, N, C]
        bc = beta.reshape(S, N, C, H).transpose(0, 3, 1, 2)
        v_beta = vc * bc[..., None]
        k_beta = kc * bc[..., None]

        gcum = jnp.cumsum(gc, axis=-1)                       # [S, H, N, C]
        tril = jnp.tril(jnp.ones((C, C), bool))
        tril_strict = jnp.tril(jnp.ones((C, C), bool), -1)
        decay = jnp.where(
            tril, jnp.exp(gcum[..., :, None] - gcum[..., None, :]), 0.0)

        # A = strictly-lower in-chunk interaction; the reference's
        # sequential row recurrence computes (I + A)^-1 — one triangular
        # solve here.
        A = jnp.where(tril_strict,
                      (k_beta @ kc.swapaxes(-1, -2)) * decay, 0.0)
        eye = jnp.eye(C, dtype=jnp.float32)
        Tmat = jax.scipy.linalg.solve_triangular(
            eye + A, jnp.broadcast_to(eye, A.shape), lower=True)

        v2 = Tmat @ v_beta                                   # [S,H,N,C,Dv]
        k_cumdecay = Tmat @ (k_beta * jnp.exp(gcum)[..., None])
        attn_local = jnp.where(
            tril, (qc @ kc.swapaxes(-1, -2)) * decay, 0.0)
        return qc, kc, v2, k_cumdecay, attn_local, gcum


def _chunk_step(state, inputs):
    """One chunk of the inter-chunk recurrence on ``state`` [.., Dk, Dv]
    (the leading axes of the inputs are the state's)."""
    q_i, k_i, v_i, kcd_i, attn_i, g_i = inputs
    v_new = v_i - kcd_i @ state
    out_i = (q_i * jnp.exp(g_i)[..., None]) @ state + attn_i @ v_new
    g_last = g_i[..., -1]
    state = state * jnp.exp(g_last)[..., None, None] \
        + (k_i * jnp.exp(g_last[..., None] - g_i)[..., None]) \
        .swapaxes(-1, -2) @ v_new
    return state, out_i


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def chunk_gated_delta_rule(
    q: jnp.ndarray,          # [S, T, H, Dk]
    k: jnp.ndarray,          # [S, T, H, Dk]
    v: jnp.ndarray,          # [S, T, H, Dv]
    g: jnp.ndarray,          # [S, T, H] log decay (0 on padded tokens)
    beta: jnp.ndarray,       # [S, T, H] in (0, 2) (0 on padded tokens)
    initial_state: Optional[jnp.ndarray] = None,   # [S, H, Dk, Dv]
    chunk_size: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked gated delta rule (HF torch_chunk_gated_delta_rule, batched),
    one row of the batch per sequence: each row cut into whole chunks and
    handed to the packed rule below, which is the one implementation (the
    model calls that one; this is the form the HF rule is compared in).

    Returns (out [S, T, H, Dv] f32, final_state [S, H, Dk, Dv] f32).
    Padded tokens must carry g = 0 and beta = 0 (identity on the state).
    """
    S, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = min(chunk_size, max(16, 1 << (T - 1).bit_length()))
    pad = (-T) % C
    N = (T + pad) // C

    def chunks(a):      # [S, T, ...] -> [S * N, C, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((S * N, C) + a.shape[2:])

    state0 = (jnp.zeros((S, H, Dk, Dv), jnp.float32)
              if initial_state is None else initial_state)
    n = jnp.arange(S * N, dtype=jnp.int32)
    out, states = chunk_gated_delta_rule_packed(
        chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta),
        n // N, n % N == 0, state0)
    return out.reshape(S, T + pad, H, Dv)[:, :T], states


def chunk_gated_delta_rule_packed(
    q: jnp.ndarray,          # [N, C, H, Dk]
    k: jnp.ndarray,          # [N, C, H, Dk]
    v: jnp.ndarray,          # [N, C, H, Dv]
    g: jnp.ndarray,          # [N, C, H] log decay (0 on padded tokens)
    beta: jnp.ndarray,       # [N, C, H] (0 on padded tokens)
    row: jnp.ndarray,        # [N] int32: the sequence each chunk is of
    first: jnp.ndarray,      # [N] bool: the chunk is its sequence's first
    states: jnp.ndarray,     # [R, H, Dk, Dv] f32: state entering each seq
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The chunked rule over chunks PACKED one sequence after another: a
    mixed step's prefilling sequences, each cut into whole chunks of ``C``
    tokens (its last one padded), laid end to end, so that the layout is
    sized by the tokens that prefill and not by rows x longest row.

    The in-chunk half is per chunk and does not care whose chunk it is;
    the inter-chunk scan runs down the packed axis, takes
    ``states[row[n]]`` where a sequence begins and leaves the state after
    each chunk in ``states[row[n]]``. Chunks past the last sequence must
    carry g = beta = 0 and a ``row`` that no sequence uses (a scratch row
    of ``states``).

    Returns (out [N, C, H, Dv] f32, states with every sequence's final
    state in its row).
    """
    N, C, H, Dk = q.shape
    Dv = v.shape[-1]

    def flat(a):        # one "sequence" of N whole chunks
        return a.reshape((1, N * C) + a.shape[2:])

    qc, kc, v2, kcd, attn_local, gcum = _chunk_local(
        flat(q), flat(k), flat(v), flat(g), flat(beta), C)

    def step(carry, xs):
        st, states = carry
        r, is_first, inputs = xs
        st = jnp.where(is_first, states[r], st)
        st, out_i = _chunk_step(st, inputs)
        states = jax.lax.dynamic_update_index_in_dim(states, st, r, 0)
        return (st, states), out_i

    def mv(a):          # [1, H, N, ...] -> [N, H, ...]
        return jnp.moveaxis(a[0], 1, 0)

    with jax.named_scope("gdn_chunk_scan"):
        (_, states), outs = jax.lax.scan(
            step, (jnp.zeros((H, Dk, Dv), jnp.float32),
                   states.astype(jnp.float32)),
            (row, first, (mv(qc), mv(kc), mv(v2), mv(kcd), mv(attn_local),
                          mv(gcum))))
    return outs.transpose(0, 2, 1, 3), states       # [N, C, H, Dv]


def chunk_gated_delta_rule_pool(
    q: jnp.ndarray,          # [N, C, H, Dk]
    k: jnp.ndarray,          # [N, C, H, Dk]
    v: jnp.ndarray,          # [N, C, H, Dv]
    g: jnp.ndarray,          # [N, C, H] log decay (0 on padded tokens)
    beta: jnp.ndarray,       # [N, C, H] (0 on padded tokens)
    slot: jnp.ndarray,       # [N] int32: the pool slot of each chunk's seq
    first: jnp.ndarray,      # [N] bool: the chunk is its sequence's first
    pool: jnp.ndarray,       # [P, H / n, Dk, n Dv] f32: every slot's state
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``chunk_gated_delta_rule_packed`` with the inter-chunk scan in the
    Pallas kernel (ops/pallas/gdn_scan.py), in place in the slot pool
    (states as ``pack_state`` lays them): a sequence's state is read from
    ``pool[slot[n]]`` where it begins and left there after its last
    chunk; no state is gathered out of the pool or scattered back, and
    the slots no chunk names are not touched. Chunks past the last
    sequence name a dummy slot and carry g = beta = 0.

    Returns (out [N, C, H, Dv] f32, pool).
    """
    from gllm_tpu.ops.pallas.gdn_scan import gdn_chunk_scan
    N, C, H, Dk = q.shape
    Dv = v.shape[-1]

    def flat(a):
        return a.reshape((1, N * C) + a.shape[2:])

    qc, kc, v2, kcd, attn_local, gcum = _chunk_local(
        flat(q), flat(k), flat(v), flat(g), flat(beta), C)
    with jax.named_scope("gdn_chunk_local"):
        # what the kernel cannot spread itself: see its docstring
        g_last = gcum[..., -1:]                              # [1, H, N, 1]
        qg = qc * jnp.exp(gcum)[..., None]
        kdt = (kc * jnp.exp(g_last - gcum)[..., None]).swapaxes(-1, -2)
        dl = jnp.broadcast_to(jnp.exp(g_last)[..., None], (1, H, N, 1, Dv))
    out, pool = gdn_chunk_scan(qg[0], kdt[0], v2[0], kcd[0], attn_local[0],
                               dl[0], pool, slot, first,
                               interpret=interpret)
    return out.transpose(1, 2, 0, 3), pool          # [N, C, H, Dv]


def rms_norm_gated(x: jnp.ndarray, gate: jnp.ndarray, weight: jnp.ndarray,
                   eps: float) -> jnp.ndarray:
    """Norm-then-gate (HF Qwen3NextRMSNormGated): rmsnorm(x) * silu(gate)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight
    return (normed * jax.nn.silu(gate.astype(jnp.float32))).astype(x.dtype)
