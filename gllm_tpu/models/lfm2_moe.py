"""LFM2-MoE decoder (``Lfm2MoeForCausalLM``, model_type lfm2_moe:
LiquidAI/LFM2-24B-A2B): an operator and a feed-forward a layer, each
behind its RMSNorm,

    x <- x + Op_i(RMSNorm(x))      Op_i by ``layer_types[i]``:
    "conv"            the gated short convolution: [B | C | z] = u W_in,
                      y = (C * conv(B * z)) W_out, ``conv_L_cache`` taps,
                      no bias, no activation (ops/short_conv.py); its
                      whole state is the convolution's window, two rows of
                      the hidden size a sequence, in the slot pool
    "full_attention"  GQA, per-head q / k RMSNorm, rotary embedding over
                      the whole head, over the paged KV pool
    x <- x + FFN_i(RMSNorm(x))     a SwiGLU in the first
                      ``first_k_dense_replace`` layers; behind them
                      sigmoid-routed experts, the choice corrected by a
                      bias, no shared expert: models/deepseek.py's router
                      and held share (``ep_share``)

and logits = RMSNorm(x) E^T over the tied embedding.

TPU-first structure, as models/nemotron_h.py:
- layers of one kind are stacked ([L_kind, ...] leaves: the two operators
  and the two feed-forwards each a group) and a layer's leaves are cut
  from its stacks where they are used; the pattern is folded into nested
  ``lax.scan``s (``nemotron_h.layer_program`` over the layers' (operator,
  feed-forward) pairs: ``cc a (ccca)x9 c`` traces five blocks for 40
  layers);
- attention is ``dense._attention`` over a cache built with ``kv_pack``
  (heads of 64 lie in pairs along the 128 lanes: both Pallas kernels
  serve it; ``runner.pick_kv_pack``);
- the windows live in ``NemotronKV.conv``, all conv layers and slots on
  one axis, addressed through ``batch.ssm_slots``; there is no recurrent
  stack (``NemotronKV.rec`` is None) and no chunked rule
  (``ModelConfig.ssm_chunked_rule``): a row of any length takes the one
  gather over the flat token axis.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from gllm_tpu.batching import StepBatch
from gllm_tpu.models import dense
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.models.deepseek import (STATS, _held_experts, deepseek_route,
                                      expert_stacks)
from gllm_tpu.models.nemotron_h import NemotronKV, layer_program
from gllm_tpu.ops import rms_norm
from gllm_tpu.ops.quant import qmm
from gllm_tpu.ops.short_conv import (short_conv_decode, short_conv_rows,
                                     token_rows)

Params = Dict[str, Any]

CONV, ATTN = "conv", "full_attention"
DENSE, MOE = "dense", "moe"
_OP_GROUP = {CONV: "conv_layers", ATTN: "attn_layers"}
_FFN_GROUP = {DENSE: "dense_layers", MOE: "moe_layers"}


def layer_kinds(cfg: ModelConfig) -> Tuple[Tuple[str, str], ...]:
    """(operator, feed-forward) of each layer of this stage."""
    first, _ = cfg.stage_layers
    return tuple(
        (op, DENSE if first + i < cfg.first_k_dense_replace else MOE)
        for i, op in enumerate(cfg.stage_layer_types))


def init_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 2,
                  kv_pack: int = 1) -> NemotronKV:
    """Pages of the attention layers (``kv_pack`` adjacent KV heads along
    the lanes of a cache row: models/dense.init_kv_cache) and the window
    of the conv layers, float32; no recurrent stack."""
    if jnp.dtype(dtype) == jnp.int8:
        raise NotImplementedError("lfm2_moe: an int8 KV cache")
    assert cfg.num_kv_heads % kv_pack == 0
    kv_shape = (cfg.num_attn_layers, num_pages, page_size,
                cfg.num_kv_heads // kv_pack, cfg.head_dim * kv_pack)
    window, none = cfg.ssm_slot_shapes
    assert none == ()
    return NemotronKV(
        k=jnp.zeros(kv_shape, dtype), v=jnp.zeros(kv_shape, dtype),
        conv=jnp.zeros((cfg.num_linear_layers, num_slots) + window,
                       jnp.float32),
        rec=None,
        stats=(jnp.zeros((len(STATS),), jnp.int32)
               if cfg.experts_held else None))


def no_mesh_specs(cfg: ModelConfig, tp: int):
    raise NotImplementedError(
        "LFM2-MoE under a mesh (tp / dp / sp > 1): the window pool is not "
        "partitioned, the packed KV layout is one replica's, and the "
        "expert layer has no exchange; one chip serves its share of a "
        "deployment (ep_share)")


make_rope_table = dense.make_rope_table


def startup_line(cfg: ModelConfig, *, weight_bytes: int, num_pages: int,
                 page_bytes: int, page_size: int, prefix_cache: bool,
                 attn_impl: str, quantized: bool) -> str:
    """What the chip holds beside the window pool's own line
    (``ModelRunner``), and which form multiplies the experts
    (``ModelDef.startup_line``)."""
    del prefix_cache                     # refused for slot state
    La = cfg.num_attn_layers
    if attn_impl != "pallas":
        experts = "xla ragged_dot (attention runs no Pallas kernel here)"
    elif quantized:
        experts = "xla ragged_dot (the Pallas kernel reads plain stacks)"
    else:
        experts = "pallas gmm (ops/pallas/grouped_matmul.py)"
    return (
        "short-convolution model: weights %d bytes (%d of %d routed "
        "experts a layer held here); KV pool %d pages x %d tokens x %d "
        "attention layers x %d B a token and layer = %d bytes; held "
        "experts: grouped products -> %s" % (
            weight_bytes, cfg.num_local_experts, cfg.num_experts,
            num_pages, page_size, La, page_bytes // (page_size * La),
            num_pages * page_bytes, experts))


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Params:
    """Seeded random weights (``--load-format dummy``): matrices normal,
    1/sqrt(fan-in), the n-th draw from ``fold_in(key(seed), n)``, one
    program a leaf; the taps 1/sqrt(taps), so that ``c`` has unit variance
    for unit ``g``; ``expert_bias`` zeros, as models/deepseek.py draws it
    (a drawn bias decides the choice and piles the rows onto few experts:
    tests/test_lfm2_moe.py puts a drawn one on both sides); the routed
    experts a layer at a time into their stacks (a whole stack's float32
    draw is 3.8 GB at the published widths and 8 held experts). The TIED
    embedding at 1/sqrt(hidden), its fan-in as the head it also is
    (models/cohere2_moe.py). perfbench/reference/lfm2_moe.py draws the
    same."""
    H, D, K = cfg.hidden_size, cfg.head_dim, cfg.linear_conv_kernel_dim
    Hq, Hkv, I = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
    E, Eh, Im = cfg.num_experts, cfg.num_local_experts, \
        cfg.moe_intermediate_size
    kinds = layer_kinds(cfg)
    Lc = sum(op == CONV for op, _ in kinds)
    La = len(kinds) - Lc
    Ld = sum(ffn == DENSE for _, ffn in kinds)
    Le = len(kinds) - Ld
    key = jax.random.key(seed)
    ks = (jax.random.fold_in(key, i) for i in itertools.count())

    def draw(k, shape, scale):
        # the three steps kept apart: what the reference's draw rounds to
        return (jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32)) * scale).astype(dtype)

    def w(shape, scale):
        return jax.jit(draw, static_argnums=(1, 2))(next(ks), shape, scale)

    s = H ** -0.5
    ones = lambda n: jnp.ones((n, H), dtype)            # noqa: E731
    params: Params = {
        "conv_layers": {
            "norm": ones(Lc),
            "in_proj": w((Lc, H, 3 * H), s),
            # [taps, channels]: a tap's channels along the lanes
            "conv_w": w((Lc, K, H), K ** -0.5),
            "out_proj": w((Lc, H, H), s),
        },
        "attn_layers": {
            "norm": ones(La),
            "q_proj": w((La, H, Hq * D), s),
            "k_proj": w((La, H, Hkv * D), s),
            "v_proj": w((La, H, Hkv * D), s),
            "o_proj": w((La, Hq * D, H), (Hq * D) ** -0.5),
            "q_norm": jnp.ones((La, D), dtype),
            "k_norm": jnp.ones((La, D), dtype),
        },
        "dense_layers": {
            "norm": ones(Ld),
            "gate_proj": w((Ld, H, I), s),
            "up_proj": w((Ld, H, I), s),
            "down_proj": w((Ld, I, H), I ** -0.5),
        },
    }
    # the router as published, [experts, hidden]: 64 experts are half a
    # 128-lane tile, and a stack whose last dimension is no whole tile is
    # laid out transposed and copied back whole in every step program
    # (models/nemotron_h.lanes)
    moe: Params = {"norm": ones(Le), "router": w((Le, E, H), s),
                   "e_bias": jnp.zeros((Le, E), jnp.float32)}
    for name, shape, scale in (("w_gate", (Eh, H, Im), s),
                               ("w_up", (Eh, H, Im), s),
                               ("w_down", (Eh, Im, H), Im ** -0.5)):
        set_layer = jax.jit(
            lambda stack, k, i, shape=shape, scale=scale:
            stack.at[i].set(draw(k, shape, scale)), donate_argnums=0)
        stack = jnp.zeros((Le,) + shape, dtype)
        for i in range(Le):
            stack = set_layer(stack, next(ks), i)
        moe[name] = stack
    params["moe_layers"] = moe
    # one stage (any mesh is refused): the embedding and the final norm
    # are both here
    tied = cfg.tie_word_embeddings
    params["embed"] = w((cfg.vocab_size, H), s if tied else 1.0)
    params["final_norm"] = jnp.ones((H,), dtype)
    if not tied:
        params["lm_head"] = w((H, cfg.vocab_size), s)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _short_conv(lp, u, batch: StepBatch, window, *, rows, slot_base):
    """The gated short convolution over the flat ragged batch. ``window``:
    the pool of ALL this stage's conv layers, layers and slots on one axis
    (a view of the stacked pool); this layer's slots begin at
    ``slot_base``, its dummy slot first. ``rows``: None in a decode-only
    step, else ``token_rows`` of the step."""
    H = u.shape[-1]
    slots = batch.ssm_slots + slot_base
    with jax.named_scope("sconv_in"):
        # one 2-D dot reads the stack in place; the thirds are read where
        # they lie, behind a barrier (models/dense._attention)
        bcz = jax.lax.optimization_barrier(qmm(u, lp["in_proj"]))
        g = (bcz[:, :H].astype(jnp.float32)
             * bcz[:, 2 * H:].astype(jnp.float32))
    with jax.named_scope("sconv_window"):
        if rows is None:
            # pure decode: flat rows are one a sequence ([T == S])
            c, window = short_conv_decode(g, lp["conv_w"], window, slots)
        else:
            c, window = short_conv_rows(g, lp["conv_w"], window, slots,
                                        batch.attn.cu_q_lens, rows,
                                        slot_base)
    with jax.named_scope("sconv_out"):
        y = (bcz[:, H:2 * H].astype(jnp.float32) * c).astype(u.dtype)
        return qmm(y, lp["out_proj"]), window


def _layer_of(stack, i):
    """Layer ``i`` of a stacked leaf, cut where it is used. The counter is
    handed over unsigned: a signed index is wrapped first (``i < 0 ? i + n
    : i``), three scalar operations a cut and layer that the device runs
    and a trace records, for a counter that is never negative."""
    return jax.lax.dynamic_index_in_dim(stack, i.astype(jnp.uint32), 0,
                                        keepdims=False)


# Rows up to which a step multiplies EVERY held expert by EVERY row
# (``_every_held_expert``). An expert's three matrices are read once a step
# whichever way, and under ~240 rows (a v5e's FLOPs over its bytes a
# second) their read outlasts the products, so the rows an expert was not
# chosen for cost nothing that is waited for; what they save is the sort
# by expert, the gathers and the scatter back of the grouped form, ~170
# small operations an expert layer where this form has ~20. At 128 rows x
# 4 / 64 = 8 tokens an expert a decode step touches nearly every held
# expert anyway.
DENSE_ROWS = 256


def _every_held_expert(r, scores, ids, valid, cfg: ModelConfig, stacks,
                       layer):
    """The held experts' part of the routed result with every held expert
    multiplied by every row and the rows it was not chosen for weighted
    zero: the same sum as ``deepseek._held_experts``, term for term.
    ``scores`` [T, E] float32 and ``ids`` [T, K]: the router's scores and
    its choice; an expert's weight a row is its score over the chosen
    scores' sum, as ``deepseek_route`` has it, read off the score matrix
    under the choice's mask (no gather by id). ``stacks``: the three
    expert matrices of all the expert layers, [Le, held, ., .]. Returns
    (combined [T, H] float32, stats [4])."""
    K, held, first = (cfg.num_experts_per_tok, cfg.experts_held,
                      cfg.expert_first)
    chosen = jnp.any(ids[:, :, None] == jnp.arange(
        cfg.num_experts, dtype=ids.dtype), axis=1) & valid[:, None]  # [T, E]
    weight = jnp.where(chosen, scores, 0.0)
    if cfg.norm_topk_prob:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                           + cfg.route_norm_eps)
    per_expert = (weight * cfg.routed_scaling_factor)[:, first:first + held]
    mine = chosen[:, first:first + held]                      # [T, held]
    w_gate, w_up, w_down = (_layer_of(w, layer) for w in stacks)
    # true batched products, the rows repeated an expert (4 MB): with the
    # expert a free dimension of the weights alone, the TPU compiler lays
    # BOTH stacks out transposed and copies them whole in every step (3.6
    # GB of temporaries: tests/test_tpu_compile.py)
    rows = jnp.broadcast_to(r, (held,) + r.shape)
    gate = jnp.einsum("eth,ehi->eti", rows, w_gate)
    up = jnp.einsum("eth,ehi->eti", rows, w_up)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(r.dtype)
    out = jnp.einsum("eti,eih->eth", act, w_down)
    combined = jnp.einsum("eth,te->th", out.astype(jnp.float32), per_expert)
    n_mine = jnp.sum(mine, dtype=jnp.int32)
    stats = jnp.stack([
        n_mine, jnp.sum(valid, dtype=jnp.int32) * K - n_mine,
        jnp.sum(jnp.any(mine, axis=0), dtype=jnp.int32), jnp.int32(1)])
    return combined, stats


def _moe(lp, r, cfg: ModelConfig, valid, stacks, layer, grouped: str):
    """(the routed experts' part held here [T, H], stats [4]). A step of
    few rows over plain stacks multiplies every held expert by every row
    (``DENSE_ROWS``); any other takes the grouped products of
    models/deepseek.py."""
    logits = jnp.einsum("th,eh->te", r.astype(jnp.float32),
                        lp["router"].astype(jnp.float32))
    weights, ids = deepseek_route(logits, lp["e_bias"], cfg)
    if not cfg.experts_held:
        # the layer whole: every expert is held
        cfg = dataclasses.replace(cfg, experts_held=cfg.num_experts)
    if stacks is not None and r.shape[0] <= DENSE_ROWS:
        # the router's own weights per chosen id go unused (and uncomputed)
        assert cfg.scoring_func == "sigmoid"
        out, stats = _every_held_expert(r, jax.nn.sigmoid(logits), ids,
                                        valid, cfg, stacks, layer)
    else:
        out, stats = _held_experts(lp, r, weights, ids, valid, cfg, stacks,
                                   layer, grouped)
    return out.astype(r.dtype), stats


def forward(params: Params, kv: NemotronKV, batch: StepBatch,
            cfg: ModelConfig, *, cos_sin, attn_impl: str = "xla",
            max_q_len: int):
    hidden = params["embed"][batch.token_ids]
    valid = jnp.arange(hidden.shape[0]) < batch.attn.cu_q_lens[-1]
    # a token's row and its place in it, for the operator of every layer
    rows = None if max_q_len == 1 else token_rows(batch.attn.cu_q_lens,
                                                  hidden.shape[0])
    with_stats = kv.stats is not None
    # the grouped products run as the Pallas kernel wherever attention
    # runs Pallas kernels (models/deepseek._grouped_dot falls back to
    # XLA's for quantized stacks)
    grouped = "pallas" if attn_impl == "pallas" else "xla"

    # the held experts' stacks stay whole (models/deepseek._held_experts)
    names = expert_stacks(cfg)
    moe_rest = params["moe_layers"]
    stacks = None
    if all(isinstance(moe_rest[k], jax.Array) for k in names):
        stacks = tuple(moe_rest[k] for k in names)
        moe_rest = {k: v for k, v in moe_rest.items() if k not in names}
    groups = {CONV: params["conv_layers"], ATTN: params["attn_layers"],
              DENSE: params["dense_layers"], MOE: moe_rest}

    def layer_of(kind, idx):
        # one layer's leaves, cut from the stack where they are used
        return jax.tree.map(lambda a: _layer_of(a, idx[kind]), groups[kind])

    def block(carry, kind):
        x, k_all, v_all, win_all, stats, idx = carry
        op, ffn = kind
        lp = layer_of(op, idx)
        u = rms_norm(x, lp["norm"], cfg.rms_norm_eps)
        if op == ATTN:
            with jax.named_scope("lfm2_attn"):
                out, k_all, v_all, _, _ = dense._attention(
                    lp, u, batch, k_all, v_all, cfg, cos_sin,
                    attn_impl=attn_impl, max_q_len=max_q_len, li=idx[ATTN])
        else:
            with jax.named_scope("sconv"):
                Lc, n_slots = win_all.shape[:2]
                out, win_f = _short_conv(
                    lp, u, batch,
                    win_all.reshape((Lc * n_slots,) + win_all.shape[2:]),
                    rows=rows, slot_base=idx[CONV] * n_slots)
                win_all = win_f.reshape(win_all.shape)
        x = x + out
        fp = layer_of(ffn, idx)
        r = rms_norm(x, fp["norm"], cfg.rms_norm_eps)
        if ffn == DENSE:
            with jax.named_scope("lfm2_ffn"):
                x = x + dense._mlp(fp, r)
        else:
            with jax.named_scope("lfm2_moe"):
                out, moe_stats = _moe(fp, r, cfg, valid, stacks, idx[MOE],
                                      grouped)
                x = x + out
                if with_stats:
                    stats = stats.at[2:6].add(moe_stats)
        return (x, k_all, v_all, win_all, stats,
                dict(idx, **{op: idx[op] + 1, ffn: idx[ffn] + 1}))

    def run(program, carry):
        for item in program:
            if isinstance(item[1], str):
                carry = block(carry, item)
            else:
                sub, count = item
                carry, _ = jax.lax.scan(
                    lambda c, _, sub=sub: (run(sub, c), None), carry, None,
                    length=count)
        return carry

    zero = jnp.int32(0)
    carry = (hidden, kv.k, kv.v, kv.conv,
             jnp.zeros((len(STATS),), jnp.int32),
             {CONV: zero, ATTN: zero, DENSE: zero, MOE: zero})
    hidden, k_all, v_all, win_all, stats, _ = run(
        layer_program(layer_kinds(cfg)), carry)
    return hidden, jnp.zeros_like(hidden), NemotronKV(
        k_all, v_all, win_all, None, stats if with_stats else None)


compute_logits = dense.compute_logits


# ---------------------------------------------------------------------------
# Checkpoint loading
# ---------------------------------------------------------------------------

def lfm2_moe_rules(cfg: ModelConfig):
    """An ``lfm2_moe`` checkpoint (transformers' Lfm2MoeForCausalLM names:
    ``model.layers.N.conv.*`` | ``.self_attn.*``, ``.operator_norm``,
    ``.ffn_norm``, ``.feed_forward.*``) -> the stacked layout. Layer N
    maps to its index among the layers of its operator and of its
    feed-forward; the routed experts this process does not hold are
    skipped."""
    first, _ = cfg.stage_layers
    index, seen = {}, {CONV: 0, ATTN: 0, DENSE: 0, MOE: 0}
    for i, (op, ffn) in enumerate(layer_kinds(cfg)):
        index[first + i] = (op, seen[op], ffn, seen[ffn])
        seen[op] += 1
        seen[ffn] += 1
    lo, held = cfg.expert_first, cfg.num_local_experts

    def conv_tf(t):         # Conv1d weight [C, 1, K] -> [K, C]
        return {"conv_w": t.reshape(t.shape[0], t.shape[-1]).T}

    op_leaves = {
        CONV: {"conv.in_proj.weight": ("in_proj", "t"),
               "conv.conv.weight": ("__multi__", conv_tf),
               "conv.out_proj.weight": ("out_proj", "t")},
        ATTN: {"self_attn.q_proj.weight": ("q_proj", "t"),
               "self_attn.k_proj.weight": ("k_proj", "t"),
               "self_attn.v_proj.weight": ("v_proj", "t"),
               "self_attn.out_proj.weight": ("o_proj", "t"),
               "self_attn.q_layernorm.weight": ("q_norm", None),
               "self_attn.k_layernorm.weight": ("k_norm", None)},
    }
    ffn_leaves = {
        DENSE: {"feed_forward.w1.weight": ("gate_proj", "t"),
                "feed_forward.w3.weight": ("up_proj", "t"),
                "feed_forward.w2.weight": ("down_proj", "t")},
        MOE: {"feed_forward.gate.weight": ("router", None),
              "feed_forward.expert_bias": ("e_bias", None)},
    }
    expert_leaves = {"w1.weight": "w_gate", "w3.weight": "w_up",
                     "w2.weight": "w_down"}

    def rule(name: str):
        if name == "model.embed_tokens.weight":
            return (("embed",), None, None)
        if name == "model.embedding_norm.weight":
            return (("final_norm",), None, None)
        if name == "lm_head.weight":
            return None if cfg.tie_word_embeddings \
                else (("lm_head",), None, "t")
        if not name.startswith("model.layers."):
            return None
        idx_s, _, leaf = name[len("model.layers."):].partition(".")
        if int(idx_s) not in index:
            return None
        op, oi, ffn, fi = index[int(idx_s)]
        if leaf == "operator_norm.weight":
            return ((_OP_GROUP[op], "norm"), oi, None)
        if leaf == "ffn_norm.weight":
            return ((_FFN_GROUP[ffn], "norm"), fi, None)
        if leaf in op_leaves[op]:
            target, tf = op_leaves[op][leaf]
            return ((_OP_GROUP[op], target), oi, tf)
        if leaf in ffn_leaves[ffn]:
            target, tf = ffn_leaves[ffn][leaf]
            return ((_FFN_GROUP[ffn], target), fi, tf)
        if ffn == MOE and leaf.startswith("feed_forward.experts."):
            e_s, _, el = leaf[len("feed_forward.experts."):].partition(".")
            e = int(e_s) - lo
            if el in expert_leaves and 0 <= e < held:
                return ((_FFN_GROUP[ffn], expert_leaves[el]), (fi, e), "t")
        return None

    return rule


def load_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16,
                progress_cb=None) -> Params:
    from gllm_tpu.models.loader import _load_params
    template = jax.eval_shape(lambda: init_params(cfg, dtype=dtype))
    return _load_params(model_dir, template, lfm2_moe_rules(cfg),
                        progress_cb)
