"""Hybrid linear-attention decoder (Qwen3-Next / Qwen3.5, Olmo-Hybrid).

Reference: /root/reference/gllm/models/qwen3_5.py (1153 LoC) — a 3:1
interleave of Gated-DeltaNet linear-attention layers and gated
full-attention layers, MoE or dense MLP, partial rotary, per-head q/k norm.

TPU-first structure:
- layer_types must tile periodically (Qwen3-Next: [lin, lin, lin, full]);
  the decoder runs as ONE ``lax.scan`` over periods with the period's
  static pattern unrolled inside — compile time is O(period), not O(depth).
- The GDN state (conv + recurrent) lives in slot pools beside the paged KV
  (HybridKV), indexed per sequence via ``batch.ssm_slots`` — the TPU
  analogue of the reference's SSMSegment working pool
  (memory_manager.py:87-255). Chunked prefill carries the state between
  chunks; decode takes the closed-form recurrent step (ops/gdn.py).
- A mixed step splits its rows: a row with one new token takes the
  recurrent step, exactly as in a decode-only step; a row with more is cut
  into whole chunks that are packed one row after another
  (``gdn_chunk_slots``: sized by the tokens that prefill, not by rows x
  longest row) and takes the chunked rule; padded positions fold to the
  identity via g = 0, beta = 0.
- One decoder serves both block shapes; which one is read from the config
  (``norm_after``, ``qk_norm_full``, ``attn_output_gate``, ``use_rope``,
  ``linear_allow_neg_eigval``, ``gdn_grouped_proj``): Qwen3-Next norms
  each sublayer's input, gates attention and rotates part of each head;
  Olmo-Hybrid norms each sublayer's output, norms q and k over their
  whole width, has no gate and no rotary embedding, and lets beta reach 2.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from gllm_tpu.batching import StepBatch
from gllm_tpu.models import dense, moe
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.ops import (compute_rope_cos_sin, fused_add_rms_norm,
                          paged_attention, rms_norm, silu_and_mul, write_kv)
from gllm_tpu.ops.attention import tp_sharded
from gllm_tpu.ops.gdn import (causal_conv1d, chunk_gated_delta_rule_packed,
                              chunk_gated_delta_rule_pool,
                              gdn_chunk_slots, gdn_impl_for, l2norm,
                              pack_state, packed_chunks,
                              packed_slot_of_token,
                              recurrent_gated_delta_step, rms_norm_gated,
                              unpack_state)
from gllm_tpu.ops.rope import apply_rope
from gllm_tpu.ops.quant import qmm

Params = Dict[str, Any]


class HybridKV(NamedTuple):
    """Paged KV for the full-attention layers + GDN slot pools."""
    k: jnp.ndarray      # [La, num_pages, page_size, Hkv, D]
    v: jnp.ndarray
    conv: jnp.ndarray   # [Lg, num_slots, K-1, conv_dim] f32
    # the states as ops/gdn.pack_state lays them: g heads abreast, so that
    # a slot's lanes are whole 128-lane tiles (``cfg.ssm_slot_shapes``)
    rec: jnp.ndarray    # [Lg, num_slots, Nv / g, Dk, g Dv] f32


def period_pattern(cfg: ModelConfig) -> Tuple[str, ...]:
    """Smallest repeating layer-type pattern of THIS STAGE's layers;
    raises if non-periodic (PP stage bounds must align to the period —
    pp_runner.split_layers rounds hybrid stages to period multiples)."""
    lt = cfg.stage_layer_types
    assert lt, "hybrid model needs layer_types"
    L = len(lt)
    for p in range(1, L + 1):
        if L % p == 0 and lt == lt[:p] * (L // p):
            return lt[:p]
    raise AssertionError("unreachable")


def init_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 2) -> HybridKV:
    La, Lg = cfg.num_attn_layers, cfg.num_linear_layers
    kv_shape = (La, num_pages, page_size, cfg.kv_cache_heads, cfg.head_dim)
    conv, rec = cfg.ssm_slot_shapes
    return HybridKV(
        k=jnp.zeros(kv_shape, dtype),
        v=jnp.zeros(kv_shape, dtype),
        conv=jnp.zeros((Lg, num_slots) + conv, jnp.float32),
        rec=jnp.zeros((Lg, num_slots) + rec, jnp.float32),
    )


def make_rope_table(cfg: ModelConfig) -> jnp.ndarray:
    if not cfg.use_rope:
        return jnp.zeros((1, 1), jnp.float32)    # never read
    rot_dim = int(cfg.head_dim * cfg.partial_rotary_factor)
    return compute_rope_cos_sin(rot_dim, cfg.max_position, cfg.rope_theta,
                                cfg.rope_scaling)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Params:
    H, D = cfg.hidden_size, cfg.head_dim
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads
    La, Lg = cfg.num_attn_layers, cfg.num_linear_layers
    L = cfg.num_stage_layers
    Nk, Nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    Dk, Dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    K = cfg.linear_conv_kernel_dim
    key_dim, value_dim = Nk * Dk, Nv * Dv
    key = jax.random.key(seed)
    ks = iter(jax.random.split(key, 48))

    def w(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32)
                * scale).astype(dtype)

    s = H ** -0.5
    params: Params = {
        "attn_layers": {
            # gated: q_proj emits query+gate interleaved per head (2x)
            "q_proj": w(next(ks), (La, H, Hq * D
                                   * (2 if cfg.attn_output_gate else 1)),
                        s),
            "k_proj": w(next(ks), (La, H, Hkv * D), s),
            "v_proj": w(next(ks), (La, H, Hkv * D), s),
            "o_proj": w(next(ks), (La, Hq * D, H), (Hq * D) ** -0.5),
            "q_norm": jnp.ones(
                (La, Hq * D if cfg.qk_norm_full else D), dtype),
            "k_norm": jnp.ones(
                (La, Hkv * D if cfg.qk_norm_full else D), dtype),
        },
        "gdn_layers": {
            "in_qkvz": w(next(ks), (Lg, H, 2 * key_dim + 2 * value_dim), s),
            "in_ba": w(next(ks), (Lg, H, 2 * Nv), s),
            "conv_w": w(next(ks), (Lg, cfg.gdn_conv_dim, K),
                        K ** -0.5),
            "dt_bias": jnp.ones((Lg, Nv), jnp.float32),
            "a_log": jnp.zeros((Lg, Nv), jnp.float32),
            "gdn_norm": jnp.ones((Lg, Dv), dtype),
            "out_proj": w(next(ks), (Lg, value_dim, H),
                          value_dim ** -0.5),
        },
    }
    # the two norms of a layer: on the sublayers' inputs, or (norm_after)
    # on the mixer's and the MLP's outputs
    mlp: Params = {
        ("post_mlp_norm" if cfg.norm_after
         else "input_norm"): jnp.ones((L, H), dtype),
        "post_attn_norm": jnp.ones((L, H), dtype),
    }
    if cfg.num_experts:
        E, I = cfg.num_experts, cfg.moe_intermediate_size
        mlp["router"] = w(next(ks), (L, H, E), s)
        mlp["w_gate"] = w(next(ks), (L, E, H, I), s)
        mlp["w_up"] = w(next(ks), (L, E, H, I), s)
        mlp["w_down"] = w(next(ks), (L, E, I, H), I ** -0.5)
        SI = cfg.shared_expert_intermediate_size
        if SI:
            mlp["shared_gate_proj"] = w(next(ks), (L, H, SI), s)
            mlp["shared_up_proj"] = w(next(ks), (L, H, SI), s)
            mlp["shared_down_proj"] = w(next(ks), (L, SI, H), SI ** -0.5)
            mlp["shared_expert_gate"] = w(next(ks), (L, H, 1), s)
    else:
        I = cfg.intermediate_size
        mlp["gate_proj"] = w(next(ks), (L, H, I), s)
        mlp["up_proj"] = w(next(ks), (L, H, I), s)
        mlp["down_proj"] = w(next(ks), (L, I, H), I ** -0.5)
    params["mlp_layers"] = mlp
    if cfg.is_first_stage:
        params["embed"] = w(next(ks), (cfg.vocab_size, H), 1.0)
    if cfg.is_last_stage:
        params["final_norm"] = jnp.ones((H,), dtype)
        if not cfg.tie_word_embeddings:
            params["lm_head"] = w(next(ks), (H, cfg.vocab_size), s)
    return params


# ---------------------------------------------------------------------------
# Attention half (gated full attention)
# ---------------------------------------------------------------------------

def _attention(lp, x, batch: StepBatch, k_cache, v_cache,
               cfg: ModelConfig, cos_sin, *, attn_impl, max_q_len):
    """Full attention: gated, per-head q/k norm and partial rotary
    (Qwen3-Next), or plain with one norm over the whole q / k projection
    and no rotary (Olmo-Hybrid)."""
    T = x.shape[0]
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = qmm(x, lp["q_proj"])
    k = qmm(x, lp["k_proj"])
    gate = None
    if cfg.attn_output_gate:
        qg = q.reshape(T, Hq, 2 * D)
        q, gate = qg[..., :D], qg[..., D:]
    if cfg.qk_norm_full:
        q = rms_norm(q.reshape(T, Hq * D), lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    q, k = q.reshape(T, Hq, D), k.reshape(T, Hkv, D)
    v = qmm(x, lp["v_proj"]).reshape(T, Hkv, D)
    if not cfg.qk_norm_full:
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if cfg.use_rope:
        q, k = apply_rope(q, k, batch.positions, cos_sin)
    extra = cfg.kv_cache_heads - Hkv
    if extra:
        # zero heads up to the cache's head count (ModelConfig
        # .kv_cache_heads), each with its group of zero query heads
        k, v = (jnp.pad(a, ((0, 0), (0, extra), (0, 0))) for a in (k, v))
        q = jnp.pad(q, ((0, 0), (0, extra * (Hq // Hkv)), (0, 0)))
    k_cache, v_cache = write_kv(k_cache, v_cache, k, v, batch.slot_mapping)
    attn = paged_attention(q, k_cache, v_cache, batch.attn,
                           scale=D ** -0.5, max_q_len=max_q_len,
                           impl=attn_impl)
    attn = attn[:, :Hq].reshape(T, Hq * D)
    if gate is not None:
        attn = attn * jax.nn.sigmoid(
            gate.astype(jnp.float32).reshape(T, Hq * D)).astype(x.dtype)
    return qmm(attn, lp["o_proj"]), k_cache, v_cache


# ---------------------------------------------------------------------------
# GDN half
# ---------------------------------------------------------------------------

def _gdn_project(lp, x, cfg: ModelConfig):
    """x [T, H] -> (mixed [T, conv_dim] = [q | k | v] before the
    convolution, z [T, Nv, Dv], g [T, Nv] log decay, beta [T, Nv])."""
    T = x.shape[0]
    Nk, Nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    Dk, Dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    r = Nv // Nk
    key_dim, value_dim = Nk * Dk, Nv * Dv
    qkvz = qmm(x, lp["in_qkvz"])
    ba = qmm(x, lp["in_ba"])
    if cfg.gdn_grouped_proj:
        # Qwen3-Next: both projections interleaved per key-head group
        qkvz = qkvz.reshape(T, Nk, 2 * Dk + 2 * r * Dv)
        ba = ba.reshape(T, Nk, 2 * r)
        mixed = jnp.concatenate(
            [qkvz[..., :Dk].reshape(T, key_dim),
             qkvz[..., Dk:2 * Dk].reshape(T, key_dim),
             qkvz[..., 2 * Dk:2 * Dk + r * Dv].reshape(T, value_dim)],
            axis=-1)
        z = qkvz[..., 2 * Dk + r * Dv:].reshape(T, Nv, Dv)
        b = ba[..., :r].reshape(T, Nv)
        a = ba[..., r:].reshape(T, Nv)
    else:
        # separate q, k, v, z and b, a projections, side by side
        mixed = qkvz[:, :2 * key_dim + value_dim]
        z = qkvz[:, 2 * key_dim + value_dim:].reshape(T, Nv, Dv)
        b, a = ba[:, :Nv], ba[:, Nv:]
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = (-jnp.exp(lp["a_log"].astype(jnp.float32))
         * jax.nn.softplus(a.astype(jnp.float32)
                           + lp["dt_bias"].astype(jnp.float32)))
    return mixed, z, g, beta


def _gdn_heads(mx, cfg: ModelConfig):
    """Convolution output [.., conv_dim] -> q, k [.., Nv, Dk], v [.., Nv,
    Dv] (key heads repeated to the value heads)."""
    Nk, Nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    Dk, Dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim = Nk * Dk
    qh = mx[..., :key_dim].reshape(*mx.shape[:-1], Nk, Dk)
    kh = mx[..., key_dim:2 * key_dim].reshape(*mx.shape[:-1], Nk, Dk)
    vh = mx[..., 2 * key_dim:].reshape(*mx.shape[:-1], Nv, Dv)
    if Nv != Nk:
        qh = jnp.repeat(qh, Nv // Nk, axis=-2)
        kh = jnp.repeat(kh, Nv // Nk, axis=-2)
    return qh, kh, vh


def _gdn_recurrent_rows(mixed, g, beta, slots, conv_state, rec_state,
                        conv_w, cfg: ModelConfig, impl: str):
    """One new token per row: the convolution's last window and the
    closed-form recurrent step, state read from and written to ``slots``
    (rows that are not to be written carry the dummy slot 0).
    mixed [S, conv_dim], g / beta [S, Nv]. Returns (core [S, Nv, Dv],
    conv_state, rec_state)."""
    with jax.named_scope("gdn_conv"):
        buf = jnp.concatenate(
            [conv_state[slots], mixed.astype(jnp.float32)[:, None, :]],
            axis=1)                                      # [S, K, C]
        out_c = jax.nn.silu(
            jnp.einsum("skc,ck->sc", buf, conv_w.astype(jnp.float32)))
        conv_state = conv_state.at[slots].set(buf[:, 1:])
    qh, kh, vh = _gdn_heads(out_c, cfg)
    with jax.named_scope("gdn_recurrent"):
        if impl == "pallas":
            # in place in the pool: each row's state once in, once out
            from gllm_tpu.ops.pallas.gdn_recurrent import gdn_recurrent_step
            core, rec_state = gdn_recurrent_step(
                l2norm(qh) * qh.shape[-1] ** -0.5, l2norm(kh), vh, g, beta,
                rec_state, slots,
                interpret=jax.default_backend() == "cpu")
        elif impl == "xla":
            n = rec_state.shape[-1] // vh.shape[-1]     # heads abreast
            core, new_r = recurrent_gated_delta_step(
                qh, kh, vh, g, beta, unpack_state(rec_state[slots], n))
            rec_state = rec_state.at[slots].set(pack_state(new_r, n))
        else:
            raise ValueError(f"GDN impl {impl!r}: 'pallas' or 'xla'")
    return core, conv_state, rec_state


def _gdn_chunk_rows(mixed, g, beta, cu, slots, dummy, conv_state, rec_state,
                    conv_w, cfg: ModelConfig, impl: str):
    """The rows of a mixed step with more than one new token, through the
    chunked rule in the packed layout of ``gdn_chunk_slots``. Returns
    (core of every packed slot [N, C, Nv, Dv], for each flat token its
    packed slot [T] (valid for tokens of prefilling rows), conv_state,
    rec_state)."""
    T = mixed.shape[0]
    S = slots.shape[0]
    K = conv_w.shape[-1]
    N, C = gdn_chunk_slots(T, S)
    (is_pre, ch_start, ch_end, live, row, first, tok0, n_valid, valid,
     tok) = packed_chunks(cu, T, S, N, C)

    with jax.named_scope("gdn_conv"):
        # the K-1 inputs before each chunk: the row's carried state for
        # its first chunk, the row's own tokens for a later one
        before = jnp.clip(tok0[:, None] - (K - 1)
                          + jnp.arange(K - 1)[None, :], 0, T - 1)
        prev = jnp.where(first[:, None, None], conv_state[slots[row]],
                         mixed[before].astype(jnp.float32))
        out_c, new_c = causal_conv1d(mixed[tok], prev, conv_w, n_valid)
        last = jnp.clip(ch_end - 1, 0, N - 1)    # a row's last chunk
        w_slots = jnp.where(is_pre, slots, dummy)
        conv_state = conv_state.at[w_slots].set(new_c[last])
    qh, kh, vh = _gdn_heads(out_c, cfg)
    g_s = jnp.where(valid[..., None], g[tok], 0.0)
    beta_s = jnp.where(valid[..., None], beta[tok], 0.0)
    if impl == "pallas":
        # in place in the pool; chunks past the last row name the dummy slot
        core, rec_state = chunk_gated_delta_rule_pool(
            qh, kh, vh, g_s, beta_s, jnp.where(live, slots[row], dummy),
            first, rec_state, interpret=jax.default_backend() == "cpu")
    elif impl == "xla":
        # chunks past the last row scan into a scratch row of the states
        n = rec_state.shape[-1] // vh.shape[-1]         # heads abreast
        states = unpack_state(rec_state[slots], n)
        states = jnp.concatenate(
            [states, jnp.zeros_like(states[:1])], axis=0)
        core, states = chunk_gated_delta_rule_packed(
            qh, kh, vh, g_s, beta_s, jnp.where(live, row, S), first, states)
        with jax.named_scope("gdn_chunk_scan"):
            rec_state = rec_state.at[w_slots].set(
                pack_state(states[:S], n))
    else:
        raise ValueError(f"GDN impl {impl!r}: 'pallas' or 'xla'")
    slot_of_token, t_row = packed_slot_of_token(cu, ch_start, T, S, C)
    return core, slot_of_token, t_row, conv_state, rec_state


def _gdn_layer(lp, x, batch: StepBatch, conv_state, rec_state,
               cfg: ModelConfig, *, max_q_len: int, slot_base, impl: str):
    """One Gated-DeltaNet layer over the flat ragged batch.

    conv_state/rec_state: the slot pools of ALL this stage's GDN layers,
    layers and slots on one axis ([Lg * num_slots, K-1, conv_dim] /
    [Lg * num_slots, Nv / g, Dk, g Dv]: views of the stacked pools, as the
    paged KV is addressed, so that no layer's pool is cut out of the scan's
    carry and put back); this layer's slots begin at ``slot_base``, its dummy
    slot first. Reads/writes go through batch.ssm_slots (HF
    Qwen3NextGatedDeltaNet / fla GatedDeltaNet math).
    """
    T = x.shape[0]
    Nv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    slots = batch.ssm_slots + slot_base
    mixed, z, g, beta = _gdn_project(lp, x, cfg)
    conv_w = lp["conv_w"]

    if max_q_len == 1:
        # pure decode: flat rows are already one-per-seq ([T == S])
        core_flat, conv_state, rec_state = _gdn_recurrent_rows(
            mixed, g, beta, slots, conv_state, rec_state, conv_w, cfg, impl)
    else:
        # mixed: rows with one new token take the recurrent step (what
        # they take in a decode-only step), rows with more the chunked
        # rule; every other row writes the dummy slot
        cu = batch.attn.cu_q_lens
        q_lens = cu[1:] - cu[:-1]
        is_dec = q_lens == 1
        head = jnp.clip(cu[:-1], 0, T - 1)       # a row's first token
        core_dec, conv_state, rec_state = _gdn_recurrent_rows(
            mixed[head], g[head], beta[head],
            jnp.where(is_dec, slots, slot_base), conv_state, rec_state,
            conv_w, cfg, impl)
        core_pre, slot_of_token, t_row, conv_state, rec_state = \
            _gdn_chunk_rows(mixed, g, beta, cu, slots, slot_base,
                            conv_state, rec_state, conv_w, cfg, impl)
        core_pre = core_pre.reshape(-1, Nv, Dv)
        core_flat = jnp.where(
            is_dec[t_row][:, None, None], core_dec[t_row],
            core_pre[jnp.clip(slot_of_token, 0, core_pre.shape[0] - 1)])

    out = rms_norm_gated(core_flat.astype(x.dtype), z, lp["gdn_norm"],
                         cfg.rms_norm_eps)
    return (qmm(out.reshape(T, Nv * Dv), lp["out_proj"]),
            conv_state, rec_state)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _mlp(lp, x, cfg: ModelConfig):
    if cfg.num_experts:
        # moe_mlp covers the shared expert + sigmoid gate too (Qwen3Next's
        # sparse block is qwen2-moe-shaped).
        return moe.moe_mlp(lp, x, cfg)
    gate = qmm(x, lp["gate_proj"])
    up = qmm(x, lp["up_proj"])
    return qmm(silu_and_mul(jnp.concatenate([gate, up], axis=-1)),
               lp["down_proj"])


def forward(params: Params, kv: HybridKV, batch: StepBatch,
            cfg: ModelConfig, *, cos_sin, attn_impl: str = "xla",
            max_q_len: int, hidden_in=None, residual_in=None):
    pattern = period_pattern(cfg)
    gdn_impl = gdn_impl_for(attn_impl, tp_sharded())
    p = len(pattern)
    n_lin = sum(1 for t in pattern if t == "linear_attention")
    n_att = p - n_lin
    n_periods = cfg.num_stage_layers // p

    if cfg.is_first_stage:
        hidden = params["embed"][batch.token_ids]
        residual = jnp.zeros_like(hidden)
    else:
        hidden, residual = hidden_in, residual_in

    def layer_of(tree, idx):
        # one layer's leaves, cut from the stacked arrays where they are
        # used: a slice with one consumer fuses into the matmul that reads
        # it. (A period's leaves handed to the scan as ``xs`` and indexed
        # [j] inside were copied out whole each period, then once more
        # per layer: the compiled TPU program read every weight three
        # times a step.)
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0,
                                                   keepdims=False), tree)

    def one_layer(carry, ltype, li, a_i, g_i):
        """Layer ``li`` of this stage, the ``a_i``-th full-attention or
        ``g_i``-th GDN layer (indices may be traced)."""
        h, res, k_all, v_all, conv_all, rec_all = carry
        lp_mlp = layer_of(params["mlp_layers"], li)
        if cfg.norm_after:
            normed = h          # the mixer reads the raw stream
        else:
            normed, res = fused_add_rms_norm(
                h, res, lp_mlp["input_norm"], cfg.rms_norm_eps)
        if ltype == "full_attention":
            lp = layer_of(params["attn_layers"], a_i)
            # flat-view stacked-cache addressing (see dense._attention):
            # layer offset in the slot mapping / page table against
            # [La*P, ...] reshape views — no full layer-slice copies
            # through the scan carry
            La, P, page = k_all.shape[0], k_all.shape[1], k_all.shape[2]
            batch_l = batch._replace(
                slot_mapping=batch.slot_mapping + a_i * (P * page),
                attn=batch.attn._replace(
                    page_table=batch.attn.page_table + a_i * P))
            kc = k_all.reshape((La * P,) + k_all.shape[2:])
            vc = v_all.reshape((La * P,) + v_all.shape[2:])
            mix_out, kc, vc = _attention(
                lp, normed, batch_l, kc, vc, cfg, cos_sin,
                attn_impl=attn_impl, max_q_len=max_q_len)
            k_all = kc.reshape(k_all.shape)
            v_all = vc.reshape(v_all.shape)
        else:
            lp = layer_of(params["gdn_layers"], g_i)
            Lg, n_slots = conv_all.shape[:2]
            mix_out, conv_f, rec_f = _gdn_layer(
                lp, normed, batch,
                conv_all.reshape((Lg * n_slots,) + conv_all.shape[2:]),
                rec_all.reshape((Lg * n_slots,) + rec_all.shape[2:]),
                cfg, max_q_len=max_q_len, slot_base=g_i * n_slots,
                impl=gdn_impl)
            conv_all = conv_f.reshape(conv_all.shape)
            rec_all = rec_f.reshape(rec_all.shape)
        if cfg.norm_after:
            # x = x + norm(mixer(x)); x = x + norm(mlp(x))
            h = h + rms_norm(mix_out, lp_mlp["post_attn_norm"],
                             cfg.rms_norm_eps)
            h = h + rms_norm(_mlp(lp_mlp, h, cfg),
                             lp_mlp["post_mlp_norm"], cfg.rms_norm_eps)
        else:
            normed2, res = fused_add_rms_norm(
                mix_out, res, lp_mlp["post_attn_norm"], cfg.rms_norm_eps)
            h = _mlp(lp_mlp, normed2, cfg)
        return h, res, k_all, v_all, conv_all, rec_all

    # the period as runs of one kind of layer: [(kind, first, count)]. A
    # run of several (Qwen3-Next, Olmo-Hybrid: three GDN layers) is a scan
    # of its own, so its layer is traced and compiled once, not per copy
    runs = []
    for j, ltype in enumerate(pattern):
        if runs and runs[-1][0] == ltype:
            runs[-1][2] += 1
        else:
            runs.append([ltype, j, 1])

    def period_step(carry, pi):
        a_i, g_i = pi * n_att, pi * n_lin
        for ltype, j0, count in runs:
            full = ltype == "full_attention"
            if count == 1:
                carry = one_layer(carry, ltype, pi * p + j0, a_i, g_i)
            else:
                carry, _ = jax.lax.scan(      # traced here, in this pass
                    lambda c, t: (one_layer(c, ltype, pi * p + j0 + t,
                                            a_i + t, g_i + t), None),
                    carry, jnp.arange(count, dtype=jnp.int32))
            a_i, g_i = a_i + count * full, g_i + count * (not full)
        return carry, None

    init = (hidden, residual, kv.k, kv.v, kv.conv, kv.rec)
    (hidden, residual, k_all, v_all, conv_all, rec_all), _ = jax.lax.scan(
        period_step, init, jnp.arange(n_periods, dtype=jnp.int32))
    return hidden, residual, HybridKV(k_all, v_all, conv_all, rec_all)


compute_logits = dense.compute_logits


# ---------------------------------------------------------------------------
# Checkpoint loading
# ---------------------------------------------------------------------------

def hybrid_rules(cfg: ModelConfig):
    """Qwen3-Next or Olmo-Hybrid checkpoint → our stacked layout. Layer
    index i maps to a per-kind index (i-th attention layer / i-th linear
    layer of THIS STAGE); out-of-stage layers are skipped (PP-pruned
    loading). Olmo-Hybrid names are OLMo 3's for the block and fla's
    ``GatedDeltaNet`` for the linear layers, under ``linear_attn.`` (an
    assumption: no checkpoint was at hand): separate q/k/v/g/a/b
    projections and q/k/v convolutions, which land side by side in
    ``in_qkvz`` ([q | k | v | z]), ``in_ba`` ([b | a]) and ``conv_w``."""
    first, last = cfg.stage_layers
    attn_index = {}
    lin_index = {}
    for i, t in enumerate(cfg.layer_types):
        if not (first <= i < last):
            continue
        if t == "full_attention":
            attn_index[i] = len(attn_index)
        else:
            lin_index[i] = len(lin_index)

    def plus1(leaf_name):
        # Qwen3Next RMSNorm is zero-centered: forward scales by
        # (1 + weight); fold the offset into the stored weight so our
        # standard rms_norm applies unchanged. OLMo's is plain.
        off = 1.0 if cfg.norm_zero_centered else 0.0
        return lambda t: {leaf_name: t + off}

    attn_leaves = {
        "self_attn.q_proj.weight": ("q_proj", "t"),
        "self_attn.k_proj.weight": ("k_proj", "t"),
        "self_attn.v_proj.weight": ("v_proj", "t"),
        "self_attn.o_proj.weight": ("o_proj", "t"),
        "self_attn.q_norm.weight": ("__multi__", plus1("q_norm")),
        "self_attn.k_norm.weight": ("__multi__", plus1("k_norm")),
    }
    gdn_leaves = {
        "linear_attn.in_proj_qkvz.weight": ("in_qkvz", "t"),
        "linear_attn.in_proj_ba.weight": ("in_ba", "t"),
        "linear_attn.dt_bias": ("dt_bias", None),
        "linear_attn.A_log": ("a_log", None),
        "linear_attn.norm.weight": ("gdn_norm", None),
        "linear_attn.out_proj.weight": ("out_proj", "t"),
        "linear_attn.o_norm.weight": ("gdn_norm", None),
        "linear_attn.o_proj.weight": ("out_proj", "t"),
    }
    # fla's separate projections / convolutions: (leaf, columns)
    kd = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    vd = cfg.linear_num_value_heads * cfg.linear_value_head_dim
    nv = cfg.linear_num_value_heads
    gdn_parts = {
        "linear_attn.q_proj.weight": ("in_qkvz", slice(0, kd)),
        "linear_attn.k_proj.weight": ("in_qkvz", slice(kd, 2 * kd)),
        "linear_attn.v_proj.weight": ("in_qkvz",
                                      slice(2 * kd, 2 * kd + vd)),
        "linear_attn.g_proj.weight": ("in_qkvz",
                                      slice(2 * kd + vd, 2 * kd + 2 * vd)),
        "linear_attn.b_proj.weight": ("in_ba", slice(0, nv)),
        "linear_attn.a_proj.weight": ("in_ba", slice(nv, 2 * nv)),
    }
    conv_parts = {
        "linear_attn.q_conv1d.weight": slice(0, kd),
        "linear_attn.k_conv1d.weight": slice(kd, 2 * kd),
        "linear_attn.v_conv1d.weight": slice(2 * kd, 2 * kd + vd),
    }
    mlp_leaves = {
        "input_layernorm.weight": ("__multi__", plus1("input_norm")),
        "post_attention_layernorm.weight": ("__multi__",
                                            plus1("post_attn_norm")),
        "post_feedforward_layernorm.weight": ("__multi__",
                                              plus1("post_mlp_norm")),
        "mlp.gate_proj.weight": ("gate_proj", "t"),
        "mlp.up_proj.weight": ("up_proj", "t"),
        "mlp.down_proj.weight": ("down_proj", "t"),
        "mlp.gate.weight": ("router", "t"),
        "mlp.shared_expert.gate_proj.weight": ("shared_gate_proj", "t"),
        "mlp.shared_expert.up_proj.weight": ("shared_up_proj", "t"),
        "mlp.shared_expert.down_proj.weight": ("shared_down_proj", "t"),
        "mlp.shared_expert_gate.weight": ("shared_expert_gate", "t"),
    }
    expert_leaves = {
        "gate_proj.weight": ("w_gate", "t"),
        "up_proj.weight": ("w_up", "t"),
        "down_proj.weight": ("w_down", "t"),
    }

    def conv_tf(t):
        # HF Conv1d weight [C, 1, K] → [C, K]
        return {"conv_w": t.reshape(t.shape[0], t.shape[-1])}

    def rule(name: str):
        if name == "model.embed_tokens.weight":
            return (("embed",), None, None) if cfg.is_first_stage else None
        if name == "model.norm.weight":
            return ((("__multi__",), None, plus1("final_norm"))
                    if cfg.is_last_stage else None)
        if name == "lm_head.weight":
            if cfg.is_last_stage and not cfg.tie_word_embeddings:
                return (("lm_head",), None, "t")
            return None
        if not name.startswith("model.layers."):
            return None
        rest = name[len("model.layers."):]
        idx_s, _, leaf = rest.partition(".")
        i = int(idx_s)
        if not (first <= i < last):
            return None   # other PP stage's layer
        if leaf == "linear_attn.conv1d.weight":
            return (("gdn_layers", "__multi__"), lin_index[i], conv_tf)
        if not cfg.gdn_grouped_proj and leaf in gdn_parts:
            target, cols = gdn_parts[leaf]
            return (("gdn_layers", "__multi__"),
                    (lin_index[i], slice(None), cols),
                    lambda t: {target: t.T})
        if not cfg.gdn_grouped_proj and leaf in conv_parts:
            return (("gdn_layers", "__multi__"),
                    (lin_index[i], conv_parts[leaf]), conv_tf)
        if leaf in attn_leaves:
            target, tf = attn_leaves[leaf]
            return (("attn_layers", target), attn_index[i], tf)
        if leaf in gdn_leaves:
            target, tf = gdn_leaves[leaf]
            return (("gdn_layers", target), lin_index[i], tf)
        if leaf in mlp_leaves:
            target, tf = mlp_leaves[leaf]
            return (("mlp_layers", target), i - first, tf)
        if leaf.startswith("mlp.experts."):
            rest2 = leaf[len("mlp.experts."):]
            e_s, _, el = rest2.partition(".")
            if el in expert_leaves:
                target, tf = expert_leaves[el]
                return (("mlp_layers", target), (i - first, int(e_s)), tf)
        return None

    return rule


def load_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16,
                progress_cb=None) -> Params:
    from gllm_tpu.models.loader import _load_params
    template = jax.eval_shape(lambda: init_params(cfg, dtype=dtype))
    return _load_params(model_dir, template, hybrid_rules(cfg), progress_cb)
