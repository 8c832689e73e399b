"""Generic dense GQA decoder (Llama / Qwen2 / Qwen3 / ChatGLM-class).

The TPU-native re-design of the reference's canonical model shape
(/root/reference/gllm/models/qwen2.py:186-270, from which llama.py and
qwen3.py derive). Differences by design:

- **Functional**: params are a pytree; `forward` is a pure function traced
  once per shape bucket. No modules, no mutable state.
- **Stacked layers + lax.scan**: per-layer weights are stacked on a leading
  [L, ...] axis and the decoder runs as one `lax.scan`, so compile time and
  HLO size are O(1) in depth (a 32- vs 80-layer model compiles equally fast).
  The KV caches ride in the scan carry and are updated in place per layer —
  XLA aliases carry buffers, so there is no cache copy.
- **Rank-aware**: `first_layer:last_layer` selects this PP stage's slice;
  embeddings exist only on the first stage, final norm + head only on the
  last (mirrors the reference's per-stage builds).

Weight layout is [in, out] (x @ W), transposed from HF's [out, in] at load
time (gllm_tpu/models/loader.py).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from gllm_tpu.batching import StepBatch
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.ops import (apply_rope, compute_rope_cos_sin,
                          fused_add_rms_norm, layer_norm, paged_attention,
                          rms_norm, silu_and_mul, write_kv, write_kv_quant)
from gllm_tpu.ops.rope import apply_mrope, apply_rope_interleaved
from gllm_tpu.ops.quant import qmm
from gllm_tpu.parallel.mesh import shard_hint

Params = Dict[str, Any]


class KVCache(NamedTuple):
    """Stacked per-stage KV cache: [L, num_pages, page_size, Hkv, D].

    ``kv_cache_dtype=int8`` stores k/v as int8 and adds the running
    per-page per-kv-head f32 scales ([L, num_pages, Hkv]; dequant is
    q * scale — ops/kv_cache.write_kv_quant owns the write-side
    contract). The scale leaves keep the page axis at position 1 like
    every other leaf, so the kvswap host tier and the DP stacking treat
    them as ordinary cache payload. None = full-precision legacy cache.
    """
    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None


def init_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                  dtype=jnp.bfloat16, kv_pack: int = 1) -> KVCache:
    """kv_pack > 1 packs that many adjacent kv heads into the lane dim
    ([.., Hkv/pack, D*pack]) so head_dim < 128 models meet Mosaic's
    128-lane tiling on the Pallas path (ops/attention.py pack handling).
    An int8 ``dtype`` builds the quantized cache (scales ride along; a
    zero scale marks a never-written page)."""
    assert cfg.num_kv_heads % kv_pack == 0
    shape = (cfg.num_stage_layers, num_pages, page_size,
             cfg.num_kv_heads // kv_pack, cfg.head_dim * kv_pack)
    if jnp.dtype(dtype) == jnp.int8:
        sshape = shape[:2] + (shape[3],)     # [L, P, Hkv/pack]
        return KVCache(jnp.zeros(shape, jnp.int8),
                       jnp.zeros(shape, jnp.int8),
                       jnp.zeros(sshape, jnp.float32),
                       jnp.zeros(sshape, jnp.float32))
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# Parameter initialization (dummy-load path, reference --load-format dummy)
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Params:
    """Random params with sane scales (for weight-less bring-up and tests)."""
    L = cfg.num_stage_layers
    H, D = cfg.hidden_size, cfg.head_dim
    Hq, Hkv, I = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
    key = jax.random.key(seed)
    ks = iter(jax.random.split(key, 16))

    def w(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    params: Params = {}
    scale = H ** -0.5
    layers = {
        "input_norm": jnp.ones((L, H), dtype),
        "q_proj": w(next(ks), (L, H, Hq * D), scale),
        "k_proj": w(next(ks), (L, H, Hkv * D), scale),
        "v_proj": w(next(ks), (L, H, Hkv * D), scale),
        "o_proj": w(next(ks), (L, Hq * D, H), (Hq * D) ** -0.5),
        "post_attn_norm": jnp.ones((L, H), dtype),
        "gate_proj": w(next(ks), (L, H, I), scale),
        "up_proj": w(next(ks), (L, H, I), scale),
        "down_proj": w(next(ks), (L, I, H), I ** -0.5),
    }
    if cfg.attention_bias:
        layers["q_bias"] = jnp.zeros((L, Hq * D), dtype)
        layers["k_bias"] = jnp.zeros((L, Hkv * D), dtype)
        layers["v_bias"] = jnp.zeros((L, Hkv * D), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, D), dtype)
        layers["k_norm"] = jnp.ones((L, D), dtype)
    if cfg.sandwich_norms:
        # GLM4 normalizes each sublayer OUTPUT before the residual add
        layers["post_self_attn_norm"] = jnp.ones((L, H), dtype)
        layers["post_mlp_norm"] = jnp.ones((L, H), dtype)
    params["layers"] = layers
    if cfg.is_first_stage:
        params["embed"] = w(next(ks), (cfg.vocab_size, H), 1.0)
    if cfg.is_last_stage:
        params["final_norm"] = jnp.ones((H,), dtype)
        if not cfg.tie_word_embeddings:
            params["lm_head"] = w(next(ks), (H, cfg.vocab_size), scale)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _attention(lp, x, batch: StepBatch, k_all, v_all, cfg: ModelConfig,
               cos_sin, *, attn_impl: str, max_q_len: int, li,
               ks_all=None, vs_all=None, use_rope: bool = True,
               window: Optional[int] = None, k_mult: float = 1.0):
    """One layer's attention against the STACKED [L, P, ...] cache.

    The cache is addressed through a flat [L*P, ...] view with the layer
    offset folded into the page table (+ li*P) and slot mapping
    (+ li*P*page): the scan carry is only ever touched by a sparse
    scatter (in-place under donation) and the kernels' page DMAs — the
    earlier per-layer dynamic_index/dynamic_update_index round-trip
    materialized TWO full layer-slice copies per layer per step (~26 ms
    of a ~38 ms decode step on the r5 chip). Page 0 of every layer is
    that layer's dummy page, so offset padding entries stay harmless.

    ``ks_all``/``vs_all`` present marks the int8 quantized cache
    (kv_cache_dtype=int8): new rows quantize at write time against the
    running per-page absmax scale and the kernels dequantize in VMEM —
    the flat [L*P, Hkv] scale view is indexed by the same offset page
    ids as the cache itself.

    ``use_rope`` False leaves q and k without a positional term and
    ``window`` bounds what a query attends to its last ``window``
    positions (a model whose layers differ in kind says both per layer:
    models/cohere2_moe.py); the rows stay in the pages either way.
    ``k_mult`` other than 1 multiplies k as it leaves its projection
    (Falcon-H1's ``key_multiplier``: models/falcon_h1.py)."""
    T = x.shape[0]
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L, P, page_size = k_all.shape[0], k_all.shape[1], k_all.shape[2]
    quant = ks_all is not None
    k_cache = k_all.reshape((L * P,) + k_all.shape[2:])
    v_cache = v_all.reshape((L * P,) + v_all.shape[2:])
    k_scale = ks_all.reshape((L * P,) + ks_all.shape[2:]) if quant else None
    v_scale = vs_all.reshape((L * P,) + vs_all.shape[2:]) if quant else None

    q = qmm(x, lp["q_proj"])
    k = qmm(x, lp["k_proj"])
    v = qmm(x, lp["v_proj"])
    if "q_bias" in lp:
        q = q + lp["q_bias"]
        k = k + lp["k_bias"]
        v = v + lp["v_bias"]
    # The reshapes below must not be folded into the three dots: a folded
    # dot wants its weight as [heads, D, hidden], which in the TPU's tiled
    # layout is no view of the stored [hidden, heads*D], so the compiler
    # cuts the layer's slice out of the [L, ...] stack and transposes it,
    # every layer of every step (31.5 MB a layer at Qwen3-4B's widths).
    # Behind the barrier the dots stay 2-D and read the stack in place, as
    # the MLP's and o_proj's do. Held by tests/test_tpu_compile.py
    # (test_dense_cell_projections_read_the_stack_in_place).
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    if k_mult != 1.0:
        k = (k.astype(jnp.float32) * k_mult).astype(k.dtype)
    q = shard_hint(q.reshape(T, Hq, D), None, "tp", None)
    k = shard_hint(k.reshape(T, Hkv, D), None, "tp", None)
    v = shard_hint(v.reshape(T, Hkv, D), None, "tp", None)
    if cfg.qk_norm:
        # per-head RMSNorm over D (reference qwen3.py adds q/k norms)
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if not use_rope:
        pass                                  # no positional term at all
    elif cfg.mrope_section and batch.mrope_positions is not None:
        q, k = apply_mrope(q, k, batch.mrope_positions, cos_sin,
                           cfg.mrope_section,
                           interleaved=cfg.mrope_interleaved)
    else:
        rope_fn = (apply_rope_interleaved if cfg.rope_interleaved
                   else apply_rope)
        q, k = rope_fn(q, k, batch.positions, cos_sin)
    if quant:
        k_cache, v_cache, k_scale, v_scale = write_kv_quant(
            k_cache, v_cache, k_scale, v_scale, k, v,
            batch.slot_mapping + li * (P * page_size), page_size)
    else:
        k_cache, v_cache = write_kv(
            k_cache, v_cache, k, v,
            batch.slot_mapping + li * (P * page_size))
    if attn_impl == "ring":
        # Sequence-parallel prefill (sp mesh axis): the runner routes a
        # single-seq from-position-0 chunk here — self-attention over the
        # fresh k/v runs as causal ring attention (ICI neighbor
        # exchanges), no paged gather at all. KV was still written above
        # for the decode steps that follow. Bucketed padding rows are
        # masked via kv_valid (padded KEYS must not leak into real rows).
        from gllm_tpu.parallel.mesh import AXIS_SP
        from gllm_tpu.parallel.ring_attention import ring_attention_sharded
        attn = ring_attention_sharded(q, k, v, axis_name=AXIS_SP,
                                      scale=D ** -0.5,
                                      kv_valid=batch.attn.kv_lens[0])
    else:
        md = batch.attn._replace(
            page_table=batch.attn.page_table + li * P)
        attn = paged_attention(q, k_cache, v_cache, md,
                               scale=D ** -0.5, max_q_len=max_q_len,
                               impl=attn_impl,
                               k_scale=k_scale, v_scale=v_scale,
                               window=window)
    out = qmm(attn.reshape(T, Hq * D), lp["o_proj"])
    return (out, k_cache.reshape(k_all.shape),
            v_cache.reshape(v_all.shape),
            k_scale.reshape(ks_all.shape) if quant else None,
            v_scale.reshape(vs_all.shape) if quant else None)


def _mlp(lp, x):
    gate = shard_hint(qmm(x, lp["gate_proj"]), None, "tp")
    up = shard_hint(qmm(x, lp["up_proj"]), None, "tp")
    fused = silu_and_mul(jnp.concatenate([gate, up], axis=-1))
    return qmm(fused, lp["down_proj"])


def forward(
    params: Params,
    kv: KVCache,
    batch: StepBatch,
    cfg: ModelConfig,
    *,
    cos_sin: jnp.ndarray,
    attn_impl: str = "xla",
    max_q_len: int,
    hidden_in: Optional[jnp.ndarray] = None,
    residual_in: Optional[jnp.ndarray] = None,
    mlp_fn=None,
    deepstack: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, KVCache]:
    """Run this stage's layers. Returns (hidden, residual, new_kv).

    First stage embeds `batch.token_ids`; later PP stages take
    (hidden_in, residual_in) received from the previous stage. ``mlp_fn``
    swaps the MLP half of each block (MoE models pass their routed-expert
    MLP); the attention half and scan plumbing are shared. ``deepstack``
    is [n_levels, T, H] visual residuals: level i is added to the hidden
    stream after global layer i (Qwen3-VL; reference qwen3_vl.py:436-469).
    """
    if mlp_fn is None:
        mlp_fn = _mlp
    if cfg.is_first_stage:
        hidden = params["embed"][batch.token_ids]
        if batch.mm_embeds is not None:
            # Visual rows come pre-embedded by the vision tower; splice
            # them over the placeholder-token embeddings (reference
            # embed_input_ids merge, qwen2_5_vl.py:972-996).
            mm_main = batch.mm_embeds[:, :cfg.hidden_size]
            hidden = jnp.where(batch.mm_mask[:, None],
                               mm_main.astype(hidden.dtype), hidden)
        residual = jnp.zeros_like(hidden)
    else:
        hidden, residual = hidden_in, residual_in

    def layer_step(carry, lp):
        h, res, k_all, v_all, ks_all, vs_all, li = carry
        normed, res = fused_add_rms_norm(h, res, lp["input_norm"],
                                         cfg.rms_norm_eps)
        attn_out, k_all, v_all, ks_all, vs_all = _attention(
            lp, normed, batch, k_all, v_all, cfg, cos_sin,
            attn_impl=attn_impl, max_q_len=max_q_len, li=li,
            ks_all=ks_all, vs_all=vs_all)
        if cfg.sandwich_norms:
            attn_out = rms_norm(attn_out, lp["post_self_attn_norm"],
                                cfg.rms_norm_eps)
        normed2, res = fused_add_rms_norm(attn_out, res,
                                         lp["post_attn_norm"],
                                         cfg.rms_norm_eps)
        mlp_out = mlp_fn(lp, normed2)
        if cfg.sandwich_norms:
            mlp_out = rms_norm(mlp_out, lp["post_mlp_norm"],
                               cfg.rms_norm_eps)
        if deepstack is not None:
            # residual stream after this layer = mlp_out + res; adding the
            # level-indexed visual delta to mlp_out is equivalent to HF's
            # hidden_states += deepstack_input_embeds[layer_idx].
            nds = deepstack.shape[0]
            gl = li + cfg.first_layer
            ds = jax.lax.dynamic_index_in_dim(
                deepstack, jnp.minimum(gl, nds - 1), 0, keepdims=False)
            mlp_out = mlp_out + jnp.where(gl < nds, ds,
                                          jnp.zeros_like(ds))
        return (mlp_out, res, k_all, v_all, ks_all, vs_all, li + 1), None

    init = (hidden, residual, kv.k, kv.v, kv.k_scale, kv.v_scale,
            jnp.int32(0))
    (hidden, residual, k_all, v_all, ks_all, vs_all, _), _ = jax.lax.scan(
        layer_step, init, params["layers"])
    return hidden, residual, KVCache(k_all, v_all, ks_all, vs_all)


def _head(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Final norm and head of the rows ``x``: float32 logits. The norm is
    the model's kind (``cfg.norm_kind``); ``cfg.logit_scale`` other than 1
    multiplies the logits (Cohere)."""
    norm = layer_norm if cfg.norm_kind == "layer" else rms_norm
    normed = norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = (params["embed"].T if cfg.tie_word_embeddings
            else params["lm_head"])
    logits = (normed @ head).astype(jnp.float32)
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


def compute_full_logits(params: Params, hidden: jnp.ndarray,
                        residual: jnp.ndarray,
                        cfg: ModelConfig) -> jnp.ndarray:
    """Logits for EVERY token row [T, V] (prompt-logprob path). Single
    source of truth for the final-norm + head projection; compute_logits
    is the [S]-row gather specialization of the same math."""
    return shard_hint(_head(params, hidden + residual, cfg), None, None)


def compute_logits(params: Params, hidden: jnp.ndarray,
                   residual: jnp.ndarray, batch: StepBatch,
                   cfg: ModelConfig) -> jnp.ndarray:
    """Gather last-token hidden per sequence, final-norm, project to vocab.

    Mirrors the reference compute_logits (gather at query_start_loc-1 then
    head, qwen2.py): gathering [S, H] *before* the vocab matmul keeps the
    head GEMM at S rows instead of T.
    """
    final = hidden + residual
    sel = final[batch.logits_indices]                       # [S, H]
    # All-gather the vocab-sharded logits before sampling (the reference's
    # logits all-gather, vocab_parallel_embedding.py): the sampler sorts over
    # the full vocab per row.
    return shard_hint(_head(params, sel, cfg), None, None)


def make_rope_table(cfg: ModelConfig) -> jnp.ndarray:
    rot_dim = int(cfg.head_dim * cfg.partial_rotary_factor)
    return compute_rope_cos_sin(rot_dim, cfg.max_position,
                                cfg.rope_theta, cfg.rope_scaling)
