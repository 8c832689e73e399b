"""DeepSeek V2/V3/R1 family: MLA attention + DeepSeekMoE.

TPU-native re-design of the reference deepseek_v2.py (730 LoC,
/root/reference/gllm/models/deepseek_v2.py):

- **MLA with a latent KV cache**: each token caches one
  ``kv_lora_rank + qk_rope_head_dim`` latent row (the V2 paper's compressed
  KV). Attention runs in the *absorbed* form (reference uses absorbed
  decode :272-293 and decompressed chunked prefill; we use absorbed for
  both — one code path, MQA-shaped, and the paged-attention machinery is
  reused with Hkv=1): q_nope is folded through W_UK into latent space,
  scores = q_lat·c_kv + q_pe·k_pe, and the output latent is expanded through
  W_UV. One exception: a prompt chunk in a WINDOWED layer attends
  decompressed keys ("Two ways through a step's tokens").
- **DeepSeekMoE**: first_k_dense_replace dense layers then MoE layers (two
  homogeneous lax.scans — keeps O(1) compile depth per block type);
  grouped top-k routing: softmax (V2 greedy/group_limited_greedy) or
  sigmoid + e_score_correction_bias (V3 noaux_tc), topk_group group
  pruning, routed_scaling_factor; n_shared_experts always-on shared expert.
- YaRN rope with mscale folded into the cos/sin table and the extra
  mscale**2 factor folded into the softmax scale
  (gllm_tpu/ops/rope.py:yarn_softmax_scale_mult).
- **The same family with ``layer_types``** (dots3_note): full layers that
  choose ``index_topk`` positions with DeepSeek-V3.2's indexer beside
  windowed layers with a latent geometry of their own (``geom``), runs of
  same-kind layers as scans (``layer_runs``), the windowed layers' rows in
  a ring a sequence (``LatentKVCache.swa``), headwise gates, and an
  expert layer that holds a share of its experts (``_held_experts``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from gllm_tpu.batching import StepBatch
from gllm_tpu.models import dense
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.obs import metrics as obs
from gllm_tpu.ops import (fused_add_rms_norm, paged_attention, rms_norm,
                          silu_and_mul)
from gllm_tpu.ops.attention import AttentionMetadata
from gllm_tpu.ops.quant import deq, qmm, qragged_dot
from gllm_tpu.ops.rope import (apply_rope_interleaved, compute_rope_cos_sin,
                               yarn_softmax_scale_mult)

Params = dict


class LatentKVCache(NamedTuple):
    """latent: [L, num_pages, page_size, kv_lora_rank + qk_rope_head_dim]
    of the layers that attend their whole context (all of them, unless
    ``layer_types`` names windowed ones);
    index_k: parallel DSA indexer-key cache [L, num_pages, page_size,
    index_head_dim] in the cache's dtype; under an fp8 cache
    (``--kv-cache-dtype fp8``) fp8-e4m3 payloads with per-token scales in
    ``index_scale`` [L, num_pages, page_size] (the reference's packed
    132-byte store_index_k_fp8 layout, layers/ops/cache_kernels.py — here
    two parallel paged arrays instead of byte-packing, which XLA can't
    slice);
    swa: the windowed layers' latent rows, a ring per sequence slot
    [L_swa, slots, ring, swa_cache_width]: position p of a sequence lies
    in row ``p % ring`` of its slot, so a layer never holds more of a
    sequence than its window (slot 0 is the padding rows' dummy);
    stats: [N_STATS] int32, what THIS step's indexer and expert layers
    counted (``STATS``); the runner hands it to the host beside the
    step's tokens."""
    latent: jnp.ndarray
    index_k: Optional[jnp.ndarray] = None
    index_scale: Optional[jnp.ndarray] = None
    swa: Optional[jnp.ndarray] = None
    stats: Optional[jnp.ndarray] = None


# what ``LatentKVCache.stats`` holds, in order: positions the indexer
# scored and positions it chose (summed over the step's tokens and the
# full layers), routed assignments to experts held here and to absent
# ones, held experts with a token, and expert layers run (the last four
# summed over the expert layers)
STATS = ("dsa_seen", "dsa_chosen", "moe_held", "moe_absent", "moe_touched",
         "moe_layers")

# docs/observability.md: the counters a per-layer metric of the benchmark
# reads (perfbench/layer_metrics/dsa.*, moe.*)
_M_DSA = obs.counter(
    "gllm_dsa_positions_total",
    "Positions the DSA indexer scored (seen) and chose (chosen), summed "
    "over tokens and full-attention layers", ("what",))
_M_MOE_ASSIGN = obs.counter(
    "gllm_moe_assignments_total",
    "Routed (token, expert) assignments by where the expert lives: held "
    "by this process or absent (another chip of the expert-parallel "
    "deployment), summed over expert layers", ("where",))
_M_MOE_TOUCHED = obs.counter(
    "gllm_moe_experts_touched_total",
    "Held experts that had at least one token, summed over expert layers "
    "and steps, by the kind of step (decode: one token a row; mixed: a "
    "prefill chunk rides)", ("step",))
_M_MOE_STEPS = obs.counter(
    "gllm_moe_layer_steps_total",
    "Expert layers run, summed over steps (the denominator of experts "
    "touched a layer and step), by the kind of step", ("step",))


_M_MLA_ROWS = obs.counter(
    "gllm_mla_rows_read_total",
    "Latent rows a step's dense latent attention has to read: every "
    "sequence's context after the step, summed over the step's sequences "
    "and the layers (a prefill chunk's queries share their sequence's "
    "rows), by the kind of step (decode: one token a row; mixed: a "
    "prefill chunk rides)", ("step",))


def count_rows_read(cfg: ModelConfig, kv_lens, decode_only: bool) -> None:
    """One step's latent rows into the counter, from the batch's
    ``kv_lens`` as the host built them (no device value is read). For
    dense latent attention (``cfg.dense_mla``): a selection or a window
    bounds the other kinds' reads, and their own counters say by how
    much."""
    _M_MLA_ROWS.inc(int(kv_lens.sum()) * cfg.num_stage_layers,
                    step="decode" if decode_only else "mixed")


_M_DSA_ROWS = obs.counter(
    "gllm_dsa_rows_attended_total",
    "One-token rows (decoding rows, alone or beside a chunk) a step's "
    "selected-attention layers attended, summed over the full layers, by "
    "what computed them: the paged decode kernel under the selection's "
    "mask (kernel) or the gather of whole pages and XLA's products (xla)",
    ("path",))

# The masked decode call's name in the HLO and on the trace's ``XLA Ops``
# line: its own, so that a reader tells it from the other families'
# ``paged_decode_attention`` / ``ragged_paged_attention_decode_rows``.
DSA_ROWS_NAME = "dsa_rows_decode_attention"


def dsa_rows_path(attn_impl: str, meshed: Optional[bool] = None) -> str:
    """What computes a selected-attention layer's one-token rows:
    ``kernel`` where the runner runs attention on Pallas and no mesh is
    bound (the other families' Pallas calls go through a ``shard_map``
    under tp, which this call does not have), else ``xla``. ``meshed``:
    whether the runner has a mesh, asked outside a trace; inside one the
    bound mesh is read."""
    if meshed is None:
        from gllm_tpu.parallel.mesh import active_mesh
        meshed = bool(active_mesh().shape_tuple)
    return "kernel" if attn_impl == "pallas" and not meshed else "xla"


def count_rows_attended(cfg: ModelConfig, cu_q_lens, path: str) -> None:
    """One step's one-token rows x full layers into the counter, from the
    batch's ``cu_q_lens`` as the host built them (no device value is
    read)."""
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    layers = cfg.num_attn_layers if cfg.use_swa else cfg.num_stage_layers
    _M_DSA_ROWS.inc(int((q_lens == 1).sum()) * layers, path=path)


_M_SWA_TOKENS = obs.counter(
    "gllm_swa_tokens_attended_total",
    "Tokens a step's windowed latent layers attended, summed over the "
    "windowed layers, by the form that computed them: a chunk's tokens "
    "over keys and values decompressed once (decompressed), a one-token "
    "row with w_uk folded into its queries over the latent rows as stored "
    "(absorbed)", ("form",))


def count_swa_tokens(cfg: ModelConfig, cu_q_lens) -> None:
    """One step's tokens x windowed layers into the counter by the form
    ``_swa_attention`` gives them, from the batch's ``cu_q_lens`` as the
    host built them (no device value is read)."""
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    chunked = int(q_lens[q_lens > 1].sum())
    _M_SWA_TOKENS.inc(chunked * cfg.num_swa_layers, form="decompressed")
    _M_SWA_TOKENS.inc((int(q_lens.sum()) - chunked) * cfg.num_swa_layers,
                      form="absorbed")


def count_stats(stats, decode_only: bool) -> None:
    """One step's ``LatentKVCache.stats`` (host array) into the counters."""
    v = [int(x) for x in stats]
    kind = "decode" if decode_only else "mixed"
    _M_DSA.inc(v[0], what="seen")
    _M_DSA.inc(v[1], what="chosen")
    _M_MOE_ASSIGN.inc(v[2], where="held")
    _M_MOE_ASSIGN.inc(v[3], where="absent")
    _M_MOE_TOUCHED.inc(v[4], step=kind)
    _M_MOE_STEPS.inc(v[5], step=kind)


_FP8_MAX = 448.0     # float8_e4m3fn finite max
FULL, SWA = "full_attention", "sliding_attention"
BQ = 128             # queries of one work item of the chunk loop


def has_stats(cfg: ModelConfig) -> bool:
    return bool(cfg.use_swa or cfg.experts_held)


def init_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 0) -> LatentKVCache:
    n_full = cfg.num_attn_layers if cfg.use_swa else cfg.num_stage_layers
    latent = jnp.zeros((n_full, num_pages, page_size, cfg.mla_cache_width),
                       dtype)
    index_k = index_scale = swa = None
    if cfg.use_dsa:
        shape = (n_full, num_pages, page_size, cfg.index_head_dim)
        if jnp.dtype(dtype) == jnp.float8_e4m3fn:
            index_k = jnp.zeros(shape, jnp.float8_e4m3fn)
            index_scale = jnp.ones(shape[:-1], jnp.float32)
        else:
            index_k = jnp.zeros(shape, dtype)
    if cfg.use_swa:
        swa = jnp.zeros((cfg.num_swa_layers, max(num_slots, 1),
                         cfg.swa_ring_len(page_size), cfg.swa_cache_width),
                        dtype)
    stats = jnp.zeros((len(STATS),), jnp.int32) if has_stats(cfg) else None
    return LatentKVCache(latent, index_k, index_scale, swa, stats)


def make_rope_table(cfg: ModelConfig) -> jnp.ndarray:
    full = compute_rope_cos_sin(cfg.qk_rope_head_dim, cfg.max_position,
                                cfg.rope_theta, cfg.rope_scaling)
    if not cfg.use_swa:
        return full
    # [2, max_position, rope]: the full layers' table, then the windowed
    # layers' (a rotary base of their own)
    assert cfg.swa_qk_rope_head_dim == cfg.qk_rope_head_dim
    return jnp.stack([full, compute_rope_cos_sin(
        cfg.swa_qk_rope_head_dim, cfg.max_position, cfg.swa_rope_theta,
        None)])


class Geom(NamedTuple):
    """One attention kind's sizes (the full layers' from the DeepSeek
    keys, the windowed layers' from the ``swa_*`` keys)."""
    heads: int
    q_lora: int
    lora: int
    nope: int
    rope: int
    v: int
    width: int          # the cache row as stored
    gate: str
    window: int         # 0 = the whole context
    scale: float


def geom(cfg: ModelConfig, kind: str = FULL) -> Geom:
    if kind == SWA:
        return Geom(cfg.swa_num_heads, cfg.swa_q_lora_rank,
                    cfg.swa_kv_lora_rank, cfg.swa_qk_nope_head_dim,
                    cfg.swa_qk_rope_head_dim, cfg.swa_v_head_dim,
                    cfg.swa_cache_width, cfg.swa_attn_gate,
                    cfg.sliding_window,
                    (cfg.swa_qk_nope_head_dim
                     + cfg.swa_qk_rope_head_dim) ** -0.5)
    return Geom(cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                cfg.mla_cache_width, cfg.attn_gate, 0,
                (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
                * yarn_softmax_scale_mult(cfg.rope_scaling))


def layer_runs(cfg: ModelConfig) -> Tuple[Tuple[str, str, int], ...]:
    """This stage's layers as runs of one kind: ((attention kind, "dense"
    | "moe", count), ...). Each run is one ``lax.scan`` over its stacked
    parameters. Without ``layer_types`` that is DeepSeek's two runs: the
    leading dense layers, then the expert layers."""
    first, last = cfg.stage_layers
    runs = []
    for i in range(first, last):
        kind = (cfg.layer_types[i] if cfg.use_swa else FULL,
                "dense" if i < cfg.first_k_dense_replace else "moe")
        if runs and runs[-1][:2] == list(kind):
            runs[-1][2] += 1
        else:
            runs.append(list(kind) + [1])
    return tuple(tuple(r) for r in runs)


def run_params(params: "Params", cfg: ModelConfig):
    """The stacked parameters of each run of ``layer_runs(cfg)``."""
    if cfg.use_swa:
        return [params["runs"][f"run{i}"]
                for i in range(len(layer_runs(cfg)))]
    return [params["dense_layers" if mlp == "dense" else "moe_layers"]
            for _, mlp, _ in layer_runs(cfg)]


# ---------------------------------------------------------------------------
# Routing (reference grouped-topk / noaux_tc paths, layers/moe/topk.py +
# deepseek_v2.py DeepseekV2MOE)
# ---------------------------------------------------------------------------

def deepseek_route(router_logits: jnp.ndarray, e_bias: Optional[jnp.ndarray],
                   cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (weights [T,K] f32, ids [T,K] i32)."""
    T = router_logits.shape[0]
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = router_logits.astype(jnp.float32)
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    # the method decides, not the keys a config happens to carry: only
    # noaux_tc corrects by the bias, only it and group_limited_greedy
    # limit the choice to ``topk_group`` groups (``cfg.route_groups``)
    choice = scores
    if e_bias is not None and cfg.topk_method == "noaux_tc":
        choice = scores + e_bias

    g = cfg.route_groups
    if g:
        grouped = choice.reshape(T, g, E // g)
        if cfg.topk_method == "noaux_tc":
            # group score = sum of top-2 member scores (V3)
            top2 = jax.lax.top_k(grouped, 2)[0]
            group_scores = top2.sum(-1)
        else:
            group_scores = grouped.max(-1)
        _, top_groups = jax.lax.top_k(group_scores, cfg.topk_group)
        group_mask = jnp.zeros((T, g), bool).at[
            jnp.arange(T)[:, None], top_groups].set(True)
        choice = jnp.where(
            jnp.repeat(group_mask, E // g, axis=1), choice, -jnp.inf)

    _, ids = jax.lax.top_k(choice, K)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + cfg.route_norm_eps)
    weights = weights * cfg.routed_scaling_factor
    return weights, ids.astype(jnp.int32)


def relu2(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.square(jax.nn.relu(x))


def _shared_expert(lp: Params, x: jnp.ndarray,
                   act: str = "swiglu") -> jnp.ndarray:
    """The shared expert in the form ``ModelConfig.expert_act`` names."""
    if act == "relu2":
        return qmm(relu2(qmm(x, lp["shared_up_proj"])),
                   lp["shared_down_proj"])
    sg = qmm(x, lp["shared_gate_proj"])
    su = qmm(x, lp["shared_up_proj"])
    return qmm(silu_and_mul(jnp.concatenate([sg, su], axis=-1)),
               lp["shared_down_proj"])


_EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def expert_stacks(cfg: ModelConfig) -> Tuple[str, ...]:
    """The stacked matrices of a routed expert, by its form
    (``cfg.expert_act``): gated SiLU of three, or relu^2 of two."""
    return _EXPERT_STACKS[1:] if cfg.expert_act == "relu2" else _EXPERT_STACKS


def _counting_order(bins, n_bins: int):
    """A stable sort of ``bins`` [A] (integers in [0, n_bins)) by
    counting: (order [A] with bins[order] ascending, sizes [n_bins]). The
    rank of an entry within its bin is a running count down the A axis,
    taken 128 entries at a time as a product with a triangular matrix and
    carried over the blocks; XLA's sort of 17 k keys with their payload
    took 14 s to compile in every expert layer of every mixed step
    program, and a running sum over a long axis (a window reduction) as
    long."""
    A, B = bins.shape[0], 128
    nb = -(-A // B)
    onehot = (jnp.pad(bins, (0, nb * B - A), constant_values=n_bins)[:, None]
              == jnp.arange(n_bins, dtype=bins.dtype)[None, :])
    x = onehot.astype(jnp.float32).reshape(nb, B, n_bins)
    within = jnp.einsum("ij,bjk->bik", jnp.tril(jnp.ones((B, B), x.dtype)),
                        x, precision=jax.lax.Precision.HIGHEST)
    totals = within[:, -1, :]                              # [nb, n_bins]
    before = jnp.cumsum(totals, axis=0) - totals           # blocks before
    rank = (within - x + before[:, None, :]).reshape(nb * B, n_bins)[:A]
    sizes = jnp.sum(totals, axis=0).astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    dest = (starts[bins] + jnp.sum(
        jnp.where(onehot[:A], rank, 0.0), axis=-1).astype(jnp.int32))
    order = jnp.zeros((A,), jnp.int32).at[dest].set(
        jnp.arange(A, dtype=jnp.int32), unique_indices=True)
    return order, sizes


def _grouped_dot(xs, w, sizes, eids, impl: str):
    """The grouped product over the rows' experts: XLA's ``ragged_dot``
    (through ``qragged_dot``: plain or quantized stacks), or the Pallas
    kernel (ops/pallas/grouped_matmul.py) over a plain stack."""
    if impl == "pallas" and isinstance(w, jax.Array):
        from gllm_tpu.ops.pallas.grouped_matmul import grouped_matmul
        return grouped_matmul(xs, w, sizes,
                              interpret=jax.default_backend() == "cpu")
    return qragged_dot(xs, w, sizes, eids)


def _held_experts(lp: Params, x, weights, ids, valid, cfg: ModelConfig,
                  stacks=None, layer=None, grouped: str = "xla"):
    """The part of the routed result that the experts HELD here give
    (``cfg.experts_held`` of them from ``cfg.expert_first`` on; the router
    chose among all ``cfg.num_experts`` and normalised over all it chose).
    An assignment to an absent expert, or of a padding row, costs nothing:
    the assignments are sorted with those last, and a loop takes the held
    ones ``cap`` at a time: gathered, multiplied, scattered back. ``cap``
    is a quarter of all assignments where the held experts are at most an
    eighth of all (twice what even routing gives them), so the loop runs
    once; a step that routes more here runs it again, and the result is
    exact whatever the router does. Nothing stands in for the absent
    experts: their part is left out, as on a chip that waits for no
    exchange.

    ``stacks``: the expert matrices (``expert_stacks(cfg)``: three for
    the gated SiLU form, two for relu^2) of ALL the layers of a run, [n,
    held, ., .] each, with ``layer`` this layer's index among them. Inside a
    scan over layers XLA copies a layer's slice of a stacked operand out
    before a grouped product can read it (1.5 GB a layer and step at
    dots3_note's widths: 4.6 ms of a v5e's bandwidth, measured); so the
    product takes the whole stack as n x held groups, of which only this
    layer's have rows, and reads in place what its groups touch.
    ``grouped``: how the grouped products run ("xla" | "pallas":
    ``_grouped_dot``).
    Returns (combined [T, H] float32, stats [4])."""
    T, H = x.shape
    K, held = cfg.num_experts_per_tok, cfg.experts_held
    local = ids - cfg.expert_first
    mine = (local >= 0) & (local < held) & valid[:, None]
    flat = jnp.where(mine, local, held).reshape(-1)       # absent -> last
    n_mine = jnp.sum(mine, dtype=jnp.int32)
    order, sizes = _counting_order(flat, held + 1)
    sizes = sizes[:held]
    ends = jnp.cumsum(sizes)
    cap = -(-T * K // 4) if held * 8 <= cfg.num_experts else T * K
    order = jnp.pad(order, (0, cap))
    flat_w = weights.reshape(-1)

    def part(i, combined):
        lo = i * cap
        idx = jax.lax.dynamic_slice_in_dim(order, lo, cap)
        token_of = idx // K
        live = lo + jnp.arange(cap) < n_mine
        # this pass's rows of each expert: its sorted range cut to the pass
        cut = jnp.clip(ends, lo, lo + cap)
        sizes_i = cut - jnp.concatenate([jnp.full((1,), lo, cut.dtype),
                                         cut[:-1]])
        eids = jnp.minimum(flat[idx], held - 1)
        xs = x[token_of]
        if stacks is None:
            ws = [lp[k] for k in expert_stacks(cfg)]
        else:
            n = stacks[0].shape[0]
            ws = [w.reshape((n * held,) + w.shape[2:]) for w in stacks]
            sizes_i = jax.lax.dynamic_update_slice(
                jnp.zeros((n * held,), sizes_i.dtype), sizes_i,
                (layer * held,))
        if cfg.expert_act == "relu2":
            act = relu2(_grouped_dot(xs, ws[0], sizes_i, eids, grouped))
            out = _grouped_dot(act, ws[-1], sizes_i, eids, grouped)
        else:
            gate = _grouped_dot(xs, ws[0], sizes_i, eids, grouped)
            up = _grouped_dot(xs, ws[1], sizes_i, eids, grouped)
            act = silu_and_mul(jnp.concatenate([gate, up], axis=-1))
            out = _grouped_dot(act, ws[-1], sizes_i, eids, grouped)
        out = jnp.where(live[:, None], out.astype(jnp.float32)
                        * flat_w[idx][:, None], 0.0)
        return combined.at[token_of].add(out)

    combined = jax.lax.fori_loop(0, -(-n_mine // cap), part,
                                 jnp.zeros((T, H), jnp.float32))
    n_valid = jnp.sum(valid, dtype=jnp.int32) * K
    stats = jnp.stack([n_mine, n_valid - n_mine,
                       jnp.sum(sizes > 0, dtype=jnp.int32), jnp.int32(1)])
    return combined, stats


def _moe_block(lp: Params, x: jnp.ndarray, cfg: ModelConfig, valid=None,
               stacks=None, layer=None):
    """Returns (the expert layer's output [T, H], stats [4] or None).
    ``valid`` [T] marks the rows that are tokens (the rest is padding);
    ``stacks``, ``layer``: see ``_held_experts``."""
    T, H = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    weights, ids = deepseek_route(logits, lp.get("e_bias"), cfg)
    stats = None

    if cfg.experts_held:
        if cfg.moe_force_dense:
            raise NotImplementedError(
                "a share of the experts (experts_held) under dp > 1")
        if valid is None:
            valid = jnp.ones((T,), bool)
        combined, stats = _held_experts(lp, x, weights, ids, valid, cfg,
                                        stacks, layer)
    elif cfg.moe_force_dense:
        # DP vmap path — ragged grouped GEMM has no usable batch rule
        # (see gllm_tpu/models/moe.py dense fallback).
        w_gate = deq(lp["w_gate"], x.dtype)
        w_up = deq(lp["w_up"], x.dtype)
        w_down = deq(lp["w_down"], x.dtype)
        combined = jnp.zeros((T, H), jnp.float32)
        wf = weights.astype(jnp.float32)
        for e in range(E):
            ye = qmm(silu_and_mul(jnp.concatenate(
                [qmm(x, w_gate[e]), qmm(x, w_up[e])],
                axis=-1)), w_down[e]).astype(jnp.float32)
            w_e = jnp.sum(jnp.where(ids == e, wf, 0.0), axis=-1)
            combined = combined + ye * w_e[:, None]
        combined = combined.astype(x.dtype)
    else:
        flat_ids = ids.reshape(-1)
        sort_idx = jnp.argsort(flat_ids)
        token_of = sort_idx // K
        xs = x[token_of]
        sorted_eids = flat_ids[sort_idx]
        group_sizes = jnp.bincount(flat_ids, length=E).astype(jnp.int32)
        gate = qragged_dot(xs, lp["w_gate"], group_sizes, sorted_eids)
        up = qragged_dot(xs, lp["w_up"], group_sizes, sorted_eids)
        act = silu_and_mul(jnp.concatenate([gate, up], axis=-1))
        out = qragged_dot(act, lp["w_down"], group_sizes, sorted_eids)
        w_sorted = weights.reshape(-1)[sort_idx][:, None].astype(out.dtype)
        combined = jnp.zeros((T, H), out.dtype).at[token_of].add(
            out * w_sorted)

    if cfg.n_shared_experts:
        combined = combined + _shared_expert(lp, x, cfg.expert_act)
    return combined.astype(x.dtype), stats


# ---------------------------------------------------------------------------
# MLA attention (absorbed form; a windowed layer's chunks decompressed)
# ---------------------------------------------------------------------------
#
# Two ways through a step's tokens, for the layers that cannot hand their
# attention to the paged kernels (the indexer's selection, the window's
# ring). Both work on the ragged batch as it is: S sequences, sequence s
# owning the tokens cu[s] .. cu[s + 1].
#
# - ROWS: one query a sequence, its first token of the step. That is all of
#   a decode-only program (``max_q_len == 1``), and the decoding rows of a
#   mixed one. Every temporary is [S, ...]. Always the ABSORBED form: w_uk
#   folded into the row's queries, attention over latent rows as they are
#   stored, w_uv applied to the result. A row reads its keys once, and
#   decompressing them would cost heads x (nope + v) products a key where
#   folding costs them once a query.
# - CHUNKS: the sequences with more than one token, cut into work items of
#   ``BQ`` queries of ONE sequence, run one after the other by a loop whose
#   trip count is the step's own (``lax.fori_loop`` over a traced count):
#   a 2048-token chunk is 16 items, whatever else is in the step. Every
#   temporary is [BQ, ...], so no tensor grows with tokens x context. A
#   full layer's items stay absorbed (128 queries read thousands of rows of
#   their own choosing). A windowed layer's items attend in the
#   DECOMPRESSED form: a chunk's queries share their keys, each key is
#   decompressed once (the step's own rows before the loop, a sequence's
#   ring when the loop reaches it) and a pair then costs nope + rope + v
#   lanes a head (256 + 128 at dots3_note's widths) where the absorbed
#   form multiplies width + lora (1152 + 1024); and no [T, heads, lora]
#   tensor stands between the projections and the loop.

class Ragged(NamedTuple):
    """What both ways read of the batch's layout."""
    cu: jnp.ndarray          # [S + 1]
    q_lens: jnp.ndarray      # [S]
    kv_lens: jnp.ndarray     # [S] context after this step
    first: jnp.ndarray       # [S] flat index of each sequence's first token
    items: jnp.ndarray       # [S] work items of the chunk loop
    item_cum: jnp.ndarray    # [S] ... summed up to and with s
    seq_of: jnp.ndarray      # [T] the sequence of each token
    valid: jnp.ndarray       # [T] is a token (the rest is padding)
    chunked: jnp.ndarray     # [T] ... of a sequence with more than one


def _ragged(md: AttentionMetadata, T: int, max_q_len: int) -> Ragged:
    cu = md.cu_q_lens
    q_lens = cu[1:] - cu[:-1]
    items = jnp.where(q_lens > 1, -(-q_lens // BQ), 0)
    if max_q_len == 1:
        items = jnp.zeros_like(items)
    tok = jnp.arange(T, dtype=jnp.int32)
    seq_of = jnp.clip(jnp.searchsorted(cu[1:], tok, side="right"),
                      0, q_lens.shape[0] - 1).astype(jnp.int32)
    valid = tok < cu[-1]
    return Ragged(cu, q_lens, md.kv_lens, jnp.clip(cu[:-1], 0, T - 1),
                  items, jnp.cumsum(items), seq_of, valid,
                  valid & (q_lens[seq_of] > 1))


def _softmax(sc, mask):
    """Masked softmax over the last axis of the scores sc [N, H, K] (mask
    [N, K]) in two parts: (weights whose largest is 1, their sum). A query
    with no key gets zeros over a sum held away from 0."""
    sc = jnp.where(mask[:, None, :], sc, -jnp.inf)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - jnp.where(jnp.isfinite(m), m, 0.0))
    return p, jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


def _attend(q, keys, mask, *, scale, lora):
    """The absorbed form: softmax(q . k * scale) over the masked keys,
    values the keys' first ``lora`` lanes. q [N, H, W]; keys [N, K, W]
    (each query its own) or [K, W] (shared); mask [N, K]. Returns [N, H,
    lora] float32; a query with no key gives zeros."""
    eq = "nhw,nkw->nhk" if keys.ndim == 3 else "nhw,kw->nhk"
    sc = jnp.einsum(eq, q, keys.astype(q.dtype),
                    preferred_element_type=jnp.float32) * scale
    p, denom = _softmax(sc, mask)
    ev = "nhk,nkl->nhl" if keys.ndim == 3 else "nhk,kl->nhl"
    out = jnp.einsum(ev, p.astype(q.dtype),
                     keys[..., :lora].astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out / denom


def _attend_heads(q, parts, *, scale):
    """The decompressed form: every head its own keys and values, which
    come in parts (a ring, a stretch of the step) that one softmax spans.
    q [N, H, D]; parts ((k [K_i, H, D], v [K_i, H, V], mask [N, K_i]),
    ...). Returns [N, H, V] float32; a query with no key gives zeros.
    The scores are [N, H, K] as ``_attend``'s are: the shape the
    benchmark's ``swa_mla`` trace pattern finds a work item by. With the
    heads first the TPU compiler takes half the time over a mixed step
    program and the chip the same (PERF.md section 7)."""
    ks, vs, masks = zip(*parts)
    k, v = jnp.concatenate(ks), jnp.concatenate(vs)
    sc = jnp.einsum("nhd,khd->nhk", q, k,
                    preferred_element_type=jnp.float32) * scale
    p, denom = _softmax(sc, jnp.concatenate(masks, axis=1))
    out = jnp.einsum("nhk,khv->nhv", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out / denom


def _chunk_loop(rg: Ragged, T: int, out_shape, item_fn, seq_fn=None):
    """Run ``item_fn(s, q_start, q_pos0, n_valid) -> [BQ, ...]`` over the
    work items and lay the valid rows into a [T, ...] float32 buffer. s:
    the item's sequence; q_start: flat index of its first query; q_pos0:
    that query's position; n_valid: queries it really has.
    ``seq_fn(s)``: what the items of one sequence share. It runs once when
    the loop reaches the sequence (items come in sequence order: a loop
    over the sequences that have items, around the loop over one's
    items), and ``item_fn`` takes its result as a fifth argument."""
    def place(buf, s, j, *held):
        q_start = rg.cu[s] + j * BQ
        n_valid = jnp.minimum(BQ, rg.q_lens[s] - j * BQ)
        q_pos0 = rg.kv_lens[s] - rg.q_lens[s] + j * BQ
        res = item_fn(s, q_start, q_pos0, n_valid, *held).astype(jnp.float32)
        start = (q_start,) + (0,) * len(out_shape)
        old = jax.lax.dynamic_slice(buf, start, (BQ,) + out_shape)
        keep = (jnp.arange(BQ) < n_valid).reshape(
            (BQ,) + (1,) * len(out_shape))
        return jax.lax.dynamic_update_slice(
            buf, jnp.where(keep, res, old), start)

    def body(w, buf):
        s = jnp.searchsorted(rg.item_cum, w, side="right").astype(jnp.int32)
        return place(buf, s, w - (rg.item_cum[s] - rg.items[s]))

    def seq_body(c, buf):
        s = jnp.searchsorted(with_items, c, side="right").astype(jnp.int32)
        held = seq_fn(s)
        return jax.lax.fori_loop(
            0, rg.items[s], lambda j, b: place(b, s, j, held), buf)

    buf = jnp.zeros((T + BQ,) + out_shape, jnp.float32)
    if seq_fn is None:
        return jax.lax.fori_loop(0, rg.item_cum[-1], body, buf)[:T]
    with_items = jnp.cumsum(rg.items > 0)
    return jax.lax.fori_loop(0, with_items[-1], seq_body, buf)[:T]


def _pad_rows(a, before=0, after=BQ):
    return jnp.pad(a, ((before, after),) + ((0, 0),) * (a.ndim - 1))


KV_WIDTHS = 4           # page-table widths a work item chooses among


def _largest(x, vis, k: int):
    """Mask [N, K] of the ``k`` largest entries of x [N, K] among the
    visible ones (all of them where there are no more; among equal
    entries the earlier position, as ``lax.top_k`` orders them). No sort:
    the k-th largest value is found bit by bit on the order-preserving
    integer image of the floats, 32 counting passes over x, and XLA's
    sort of [128, 9472] with its payload took 14 s to compile in every
    full layer of every step program."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(vis, x, -jnp.inf).astype(jnp.float32), jnp.int32)
    # floats order as their bits do, negatives reversed; as unsigned
    u = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31)), jnp.uint32)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(u >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.zeros(x.shape[:1], jnp.uint32))
    above = u > t[:, None]
    ties = (u == t[:, None]) & vis
    need = k - jnp.sum(above, axis=-1)
    # of the ties the first ``need`` by position: the position of the
    # last one taken, found the same way (a cumulative sum over the row
    # is a window reduction that XLA compiles for as long as the sort)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    nbits = max(1, (x.shape[1] - 1).bit_length())

    def pbit(i, p):
        cand = p | (jnp.int32(1) << (nbits - 1 - i))
        before = jnp.sum(ties & (pos[None, :] < cand[:, None]), axis=-1)
        return jnp.where(before < need, cand, p)

    last = jax.lax.fori_loop(0, nbits, pbit,
                             jnp.zeros(x.shape[:1], jnp.int32))
    ties &= (pos[None, :] <= last[:, None]) & (need > 0)[:, None]
    return (above | ties) & vis


def _index_logits(qi, wi, kg, kscl, cfg: ModelConfig):
    """I(t, s) = sum_j w_tj ReLU(qI_tj . kI_s) * head_dim^-0.5 for queries
    qi [N, nh, hd], their head weights wi [N, nh] and keys kg [N, K, hd]
    (each query its own sequence's) or [K, hd]; ``kscl`` the keys' fp8
    scales or None. Returns [N, K] float32."""
    hd = cfg.index_head_dim
    own = kg.ndim == 3
    eq = "nhd,nkd->nhk" if own else "nhd,kd->nhk"
    if kscl is not None and cfg.index_fp8_score:
        # fp8 x fp8 scoring (reference GLLM_DSA_FP8_SCORE): quantize q per
        # score row too; the dot accumulates in f32 and the two scales
        # rescale the raw scores (both positive: they commute with ReLU)
        qf = qi.astype(jnp.float32)
        qscl = jnp.maximum(jnp.max(jnp.abs(qf), axis=-1), 1e-6) / _FP8_MAX
        raw = jnp.einsum(eq, (qf / qscl[..., None]).astype(kg.dtype), kg,
                         preferred_element_type=jnp.float32)
        ks = kscl[:, None, :] if own else kscl[None, None, :]
        sc = raw * qscl[..., None] * ks * hd ** -0.5
    elif kscl is not None:
        kf = kg.astype(jnp.float32) * kscl[..., None]
        sc = jnp.einsum(eq, qi.astype(jnp.float32), kf) * hd ** -0.5
    else:
        sc = jnp.einsum(eq, qi, kg.astype(qi.dtype),
                        preferred_element_type=jnp.float32) * hd ** -0.5
    return jnp.einsum("nhk,nh->nk", jax.nn.relu(sc), wi)


def _index_qkw(lp, x, q_resid, batch: StepBatch, cfg: ModelConfig, cos_sin):
    """The indexer's queries [T, nh, hd], this step's keys [T, hd] and the
    head weights [T, nh] (reference deepseek_v32.py:86-338). Indexer rope
    is NON-interleaved (neox half-split), unlike the main MLA rope; same
    table."""
    from gllm_tpu.ops.rope import apply_rope
    T = x.shape[0]
    nh, hd, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = qmm(q_resid, lp["idx_wq_b"]).reshape(T, nh, hd)
    # k_norm is a LayerNorm (weight + bias), unlike the RMSNorms elsewhere
    kf = (x @ lp["idx_wk"]).astype(jnp.float32)
    mu = jnp.mean(kf, axis=-1, keepdims=True)
    var = jnp.mean((kf - mu) ** 2, axis=-1, keepdims=True)
    k = ((kf - mu) * jax.lax.rsqrt(var + 1e-6)
         * lp["idx_k_norm_w"].astype(jnp.float32)
         + lp["idx_k_norm_b"].astype(jnp.float32)).astype(x.dtype)
    q_rot, k_rot = apply_rope(q[..., :rope], k[:, None, :rope],
                              batch.positions, cos_sin)
    q = jnp.concatenate([q_rot, q[..., rope:]], axis=-1)
    k = jnp.concatenate([k_rot[:, 0], k[:, rope:]], axis=-1)
    # fp32 head weighting with n_heads**-0.5 folded in (reference
    # head_weights)
    w = (x.astype(jnp.float32)
         @ lp["idx_weights"].astype(jnp.float32)) * nh ** -0.5
    return q, k, w


def _dsa_attention(lp, x, q_resid, q_full, batch: StepBatch, latent_cache,
                   index_cache, index_scale, cfg: ModelConfig, cos_sin, *,
                   max_q_len: int, g: Geom, attn_impl: str = "xla"):
    """DSA: the indexer scores every visible position of a token's
    sequence, the ``index_topk`` largest are chosen (all of them while
    there are no more), and the token attends the chosen latent rows only.
    The step's own rows and index keys are in the pages already.

    How the chosen rows are read follows from what a v5e does well. A
    work item's 128 queries choose 2048 rows each, together nearly every
    row of a context of a few times that: the item reads its sequence's
    rows once, whole pages in position order, and attends all of them
    under the choice's mask, a plain matrix product. XLA's gather of 128
    x 2048 single rows into a temporary ran at a tenth of the memory
    bandwidth and took, with the page lookup of every chosen position,
    three quarters of a mixed step (PERF.md, PR 34). A decoding row does
    the same (2048 rows of 16 spread over a context of a few times that
    touch nearly every page of it), and where attention runs on Pallas
    (``dsa_rows_path``) it does so on the paged decode kernel: the
    kernel streams the row's own pages, its context and no more, and
    takes the choice as a mask over positions, so no [rows, padded
    context, width] copy of the pages and no float32 scores over them
    exist (5.5 ms a full layer of the cell's decode step when they did;
    PERF.md, PR 43). Elsewhere (CPU, ``attention_impl=xla``, a mesh) the
    rows are gathered as whole pages and attended in XLA, which is also
    the oracle the kernel is tested against. Both read every page of a
    context: reading only the chosen rows, which contexts of many times
    the top-k need, is not here (ROADMAP B7).
    Returns (out_lat [T, H, lora] float32, index_cache, index_scale,
    stats [2])."""
    T = x.shape[0]
    md = batch.attn
    hd = cfg.index_head_dim
    qi, ki, wi = _index_qkw(lp, x, q_resid, batch, cfg, cos_sin)

    # store this step's keys into the parallel paged index cache
    P, page, _ = index_cache.shape
    flat_k = index_cache.reshape(P * page, hd)
    if index_scale is not None:
        # fp8 store (reference store_index_k_fp8): per-token amax scale,
        # quantized payload + f32 scale land in parallel paged arrays
        kf = ki.astype(jnp.float32)
        scl = jnp.maximum(jnp.max(jnp.abs(kf), axis=-1), 1e-6) / _FP8_MAX
        index_cache = flat_k.at[batch.slot_mapping].set(
            (kf / scl[:, None]).astype(flat_k.dtype)
        ).reshape(index_cache.shape)
        index_scale = index_scale.reshape(P * page).at[
            batch.slot_mapping].set(scl).reshape(P, page)
    else:
        index_cache = flat_k.at[batch.slot_mapping].set(
            ki.astype(flat_k.dtype)).reshape(index_cache.shape)

    S, max_pages = md.page_table.shape
    max_kv = max_pages * page
    kk = min(cfg.index_topk, max_kv)
    kv_pos = jnp.arange(max_kv, dtype=jnp.int32)
    rg = _ragged(md, T, max_q_len)

    def of_seq(cache, pt):
        """A sequence's rows in position order: [.., pages x page, width]
        (whole pages: the fast kind of gather)."""
        return cache[pt].reshape(pt.shape[:-1] + (pt.shape[-1] * page,)
                                 + cache.shape[2:])

    def index_logits(q, w, pt):
        ks = of_seq(index_scale, pt) if index_scale is not None else None
        return _index_logits(q, w, of_seq(index_cache, pt), ks, cfg)

    # ROWS: the first token of every sequence
    r_pos = rg.kv_lens - rg.q_lens
    logits = index_logits(qi[rg.first], wi[rg.first], md.page_table)
    vis = (kv_pos[None, :] <= r_pos[:, None]) & (rg.q_lens > 0)[:, None]
    chosen = _largest(logits, vis, kk)
    if dsa_rows_path(attn_impl) == "kernel":
        # a sequence that brings a chunk has its context at 0 here: the
        # kernel skips it without a fetch or a dot and gives zeros
        from gllm_tpu.ops.attention import _decode_kernel
        rows = _decode_kernel(
            q_full[rg.first], latent_cache[:, :, None, :], None,
            jnp.where(rg.q_lens == 1, rg.kv_lens, 0), md.page_table,
            None, None, scale=g.scale, v_dim=g.lora, chosen=chosen,
            interpret=jax.default_backend() != "tpu", name=DSA_ROWS_NAME
        ).astype(jnp.float32)
    else:
        rows = jnp.where(
            (rg.q_lens == 1)[:, None, None],
            _attend(q_full[rg.first], of_seq(latent_cache, md.page_table),
                    chosen, scale=g.scale, lora=g.lora), 0.0)
    out = jnp.zeros((T, g.heads, g.lora), jnp.float32).at[rg.first].set(
        rows)

    if max_q_len > 1:
        qi_p, wi_p, qf_p = _pad_rows(qi), _pad_rows(wi), _pad_rows(q_full)
        # The page table is as wide as the step's LONGEST context needs
        # (a decoding row's, as a rule), and a chunk early in its prompt
        # sees a fraction of that. So an item takes the narrowest of a few
        # widths that holds its last query's context: the variants differ
        # in nothing but how many pages they read (``lax.switch``; a
        # prompt of 6 k tokens under a table of 9.4 k does half the work).
        widths = sorted({min(max_pages, -(-max_pages * i // KV_WIDTHS))
                         for i in range(1, KV_WIDTHS + 1)})

        def item_of(n_pages):
            def run(s, q_start, q_pos0):
                cut = lambda a: jax.lax.dynamic_slice_in_dim(a, q_start, BQ)
                pt = md.page_table[s, :n_pages]
                q_pos = q_pos0 + jnp.arange(BQ, dtype=jnp.int32)
                chosen = _largest(
                    index_logits(cut(qi_p), cut(wi_p), pt),
                    kv_pos[None, :n_pages * page] <= q_pos[:, None],
                    min(cfg.index_topk, n_pages * page))
                return _attend(cut(qf_p), of_seq(latent_cache, pt), chosen,
                               scale=g.scale, lora=g.lora)
            return run

        variants = [item_of(n) for n in widths]
        limits = jnp.asarray([n * page for n in widths], jnp.int32)

        def item(s, q_start, q_pos0, n_valid):
            which = jnp.sum(limits < q_pos0 + BQ)     # first that holds it
            return jax.lax.switch(
                jnp.minimum(which, len(widths) - 1), variants, s, q_start,
                q_pos0)

        chunks = _chunk_loop(rg, T, (g.heads, g.lora), item)
        out = jnp.where(rg.chunked[:, None, None], chunks, out)

    seen = jnp.where(rg.valid, batch.positions + 1, 0)
    stats = jnp.stack([jnp.sum(seen, dtype=jnp.int32),
                       jnp.sum(jnp.minimum(seen, cfg.index_topk),
                               dtype=jnp.int32)])
    return out, index_cache, index_scale, stats


def _absorb(lp, q_nope, q_pe, g: Geom, dtype):
    """w_uk folded into the queries: [N, H, width] over latent rows as
    they are stored (zero over the row's pad lanes: the scores are as
    without them)."""
    q_lat = jnp.einsum("thn,hnl->thl", q_nope.astype(jnp.float32),
                       lp["w_uk"].astype(jnp.float32)).astype(dtype)
    q_full = jnp.concatenate([q_lat, q_pe], axis=-1)  # [N, Hq, lora+rope]
    pad = g.width - q_full.shape[-1]
    if pad:
        q_full = jnp.pad(q_full, ((0, 0), (0, 0), (0, pad)))
    return q_full


def _expand(lp, out_lat):
    """The latent result [N, H, lora] through w_uv: [N, H, v] float32."""
    return jnp.einsum("thl,hlv->thv", out_lat.astype(jnp.float32),
                      lp["w_uv"].astype(jnp.float32))


def _swa_attention(lp, q_nope, q_pe, entry, batch: StepBatch, ring,
                   slot_base, *, max_q_len: int, g: Geom):
    """A windowed layer: token t attends the positions (t - window, t] of
    its sequence. What lies before this step is in the sequence's ring
    (position p in row p % R of its slot), what this step brings is in
    ``entry`` [T, W]; the step's last R rows a sequence go into the ring
    at the end. The one-token rows attend absorbed, a chunk's tokens
    decompressed ("Two ways through a step's tokens"); a decode-only step
    folds and expands all its T rows, a step with a chunk its S first
    tokens. Returns (out [T, H, v] float32, ring)."""
    T, dtype = entry.shape[0], q_nope.dtype
    md = batch.attn
    n_slots, R, W = ring.shape
    win = g.window
    chunks = max_q_len > 1
    # a decode-only step is left as it was: folded first, all T rows
    q_full = None if chunks else _absorb(lp, q_nope, q_pe, g, dtype)
    rg = _ragged(md, T, max_q_len)
    slots = batch.ssm_slots + slot_base
    cs = rg.kv_lens - rg.q_lens              # positions before this step
    ridx = jnp.arange(R, dtype=jnp.int32)

    def ring_pos(cs_):
        """The position each ring row holds, for a sequence with ``cs_``
        positions written (-1: none)."""
        last = cs_[..., None] - 1
        return jnp.where(last >= 0, last - (last - ridx) % R, -1)

    # ROWS: the first token of every sequence over [ring | itself]
    own = entry[rg.first]
    rp = ring_pos(cs)                                        # [S, R]
    keys = jnp.concatenate([ring[slots], own[:, None, :]], axis=1)
    mask = jnp.concatenate(
        [(rp >= 0) & (rp > cs[:, None] - win),
         jnp.ones((rp.shape[0], 1), bool)], axis=1)
    mask &= (rg.q_lens > 0)[:, None]
    q_rows = (_absorb(lp, q_nope[rg.first], q_pe[rg.first], g, dtype)
              if chunks else q_full[rg.first])
    rows = _attend(q_rows, keys, mask, scale=g.scale, lora=g.lora)
    if chunks:
        rows = _expand(lp, rows)
    out = jnp.zeros((T,) + rows.shape[1:], jnp.float32).at[rg.first].set(
        jnp.where((rg.q_lens == 1)[:, None, None], rows, 0.0))

    if chunks:
        def decompress(rows):
            """Latent rows as stored [N, W] -> a head's keys [N, H, nope +
            rope] (the rotary lanes are the heads' in common) and values
            [N, H, v]."""
            c = rows[:, :g.lora].astype(dtype)
            k_nope = jnp.einsum("tl,hnl->thn", c, lp["w_uk"],
                                preferred_element_type=jnp.float32)
            k_pe = jnp.broadcast_to(
                rows[:, None, g.lora:g.lora + g.rope],
                k_nope.shape[:2] + (g.rope,))
            v = jnp.einsum("tl,hlv->thv", c, lp["w_uv"],
                           preferred_element_type=jnp.float32)
            return (jnp.concatenate([k_nope.astype(dtype),
                                     k_pe.astype(dtype)], axis=-1),
                    v.astype(dtype))

        # an item's keys of the step: the ``back`` rows before its first
        # query and its own, fewer where the step begins sooner; rows
        # behind the step so that a slice of that many always lies inside
        back = -(-(win - 1) // BQ) * BQ
        q_p = _pad_rows(jnp.concatenate([q_nope, q_pe], axis=-1))
        k_p, v_p = decompress(_pad_rows(entry,
                                        after=max(BQ, back + BQ - T)))
        local = jnp.arange(BQ, dtype=jnp.int32)
        span = jnp.arange(back + BQ, dtype=jnp.int32)

        def item(s, q_start, q_pos0, n_valid, ring_kv):
            cut = lambda a, at, n: jax.lax.dynamic_slice_in_dim(a, at, n)
            k0 = jnp.maximum(q_start - back, 0)
            # in-step key at flat k0 + span: same sequence iff not before
            # the sequence's first token; ``dist`` positions before the
            # query
            same = k0 + span >= rg.cu[s]
            dist = (q_start + local)[:, None] - (k0 + span)[None, :]
            m_step = same[None, :] & (dist >= 0) & (dist < win)
            rp_s = ring_pos(cs[s])                           # [R]
            q_pos = q_pos0 + local
            m_ring = (rp_s >= 0)[None, :] & (
                rp_s[None, :] > q_pos[:, None] - win)
            return _attend_heads(
                cut(q_p, q_start, BQ),
                (ring_kv + (m_ring,),
                 (cut(k_p, k0, back + BQ), cut(v_p, k0, back + BQ), m_step)),
                scale=g.scale)

        chunked = _chunk_loop(rg, T, (g.heads, g.v), item,
                              lambda s: decompress(ring[slots[s]]))
        out = jnp.where(rg.chunked[:, None, None], chunked, out)

    # the step's rows into the ring: of each sequence the last R (an
    # earlier one would land on a later one's row), padding rows into the
    # dummy slot
    pos = batch.positions
    live = rg.valid & (pos >= rg.kv_lens[rg.seq_of] - R)
    dst = jnp.where(live, slots[rg.seq_of] * R + pos % R,
                    slot_base * R + jnp.arange(T, dtype=jnp.int32) % R)
    ring = ring.reshape(n_slots * R, W).at[dst].set(
        entry.astype(ring.dtype)).reshape(ring.shape)
    return (out if chunks else _expand(lp, out)), ring


def _mla_attention(lp, x, batch: StepBatch, latent_cache, cfg: ModelConfig,
                   cos_sin, *, max_q_len: int, attn_impl: str = "xla",
                   index_cache=None, index_scale=None, kind: str = FULL,
                   ring=None, slot_base=0):
    """One latent-attention layer of ``kind``. Returns (output [T, hidden],
    latent_cache, index_cache, index_scale, ring, stats [2] or None)."""
    T = x.shape[0]
    g = geom(cfg, kind)
    Hq, nope, rope, lora = g.heads, g.nope, g.rope, g.lora
    eps, H = cfg.rms_norm_eps, cfg.hidden_size
    s_q = (H / g.q_lora) ** 0.5 if cfg.mla_lora_rescale else 1.0
    s_kv = (H / lora) ** 0.5 if cfg.mla_lora_rescale else 1.0

    if g.q_lora:
        qa = rms_norm(x @ lp["q_a_proj"], lp["q_a_norm"], eps)
        if cfg.mla_lora_rescale:
            qa = (qa * s_q).astype(x.dtype)
        q = qmm(qa, lp["q_b_proj"])
    else:
        qa = x
        q = qmm(x, lp["q_proj"])
    q = q.reshape(T, Hq, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]

    kv_a = x @ lp["kv_a_proj"]                        # [T, lora + rope]
    c_kv = rms_norm(kv_a[:, :lora], lp["kv_a_norm"], eps)
    if cfg.mla_lora_rescale:
        c_kv = (c_kv * s_kv).astype(x.dtype)
    k_pe = kv_a[:, lora:][:, None, :]                 # [T, 1, rope]
    q_pe, k_pe = apply_rope_interleaved(q_pe, k_pe, batch.positions, cos_sin)

    # Latent cache row = [c_kv | k_pe | 0-pad] — the row is padded to the
    # 128-lane tile (cfg.mla_cache_width) so Pallas can DMA pages
    entry = jnp.concatenate([c_kv, k_pe[:, 0, :]], axis=-1)
    pad = g.width - entry.shape[-1]
    if pad:
        entry = jnp.pad(entry, ((0, 0), (0, pad)))
    if kind == FULL:
        # write via flat slot scatter
        L_pages, page, width = latent_cache.shape
        flat = latent_cache.reshape(L_pages * page, width)
        latent_cache = flat.at[batch.slot_mapping].set(
            entry.astype(flat.dtype)).reshape(latent_cache.shape)

    stats = None
    if kind == SWA:
        out, ring = _swa_attention(lp, q_nope, q_pe, entry, batch, ring,
                                   slot_base, max_q_len=max_q_len, g=g)
    else:
        # Absorb q_nope through W_UK → latent space; MQA over the latent
        # cache.
        q_full = _absorb(lp, q_nope, q_pe, g, x.dtype)
        if cfg.use_dsa:
            # DSA: the indexer's choice of positions, then attention over
            # the chosen latent rows only (reference deepseek_v32.py).
            out_lat, index_cache, index_scale, stats = _dsa_attention(
                lp, x, qa, q_full, batch, latent_cache, index_cache,
                index_scale, cfg, cos_sin, max_q_len=max_q_len, g=g,
                attn_impl=attn_impl)
        else:
            # MQA over the latent cache; values are the latent prefix of
            # the keys (v_cache=None → the Pallas kernels read v from the
            # k block in VMEM, one DMA stream; the xla path slices lazily
            # inside its gather).
            kc = latent_cache[:, :, None, :]          # [P, page, 1, width]
            out_lat = paged_attention(q_full, kc, None, batch.attn,
                                      scale=g.scale, max_q_len=max_q_len,
                                      impl=attn_impl,
                                      v_dim=lora)     # [T, Hq, lora]
        out = _expand(lp, out_lat)
    if g.gate == "headwise":
        gate = jax.nn.sigmoid((x @ lp["attn_gate"]).astype(jnp.float32))
        out = out * gate[:, :, None]
    elif g.gate:
        raise NotImplementedError(f"attention gate {g.gate!r}")
    out = out.astype(x.dtype)
    return (qmm(out.reshape(T, Hq * g.v), lp["o_proj"]),
            latent_cache, index_cache, index_scale, ring, stats)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _mla_layer_init(cfg, L, dtype, w, ks, kind: str = FULL):
    H = cfg.hidden_size
    g = geom(cfg, kind)
    Hq, nope, rope, lora, v = g.heads, g.nope, g.rope, g.lora, g.v
    scale = H ** -0.5
    # where the latents are rescaled after their norms (mla_lora_rescale),
    # the matrices that read them are drawn that much smaller: queries,
    # keys and values then have unit variance, as in a trained model, and
    # the softmax is not a hard maximum that one rounding flips
    s_q = (H / g.q_lora) ** 0.5 if cfg.mla_lora_rescale and g.q_lora else 1.0
    s_kv = (H / lora) ** 0.5 if cfg.mla_lora_rescale else 1.0
    lp = {
        "input_norm": jnp.ones((L, H), dtype),
        "post_attn_norm": jnp.ones((L, H), dtype),
        "kv_a_proj": w(next(ks), (L, H, lora + rope), scale),
        "kv_a_norm": jnp.ones((L, lora), dtype),
        "w_uk": w(next(ks), (L, Hq, nope, lora), lora ** -0.5 / s_kv),
        "w_uv": w(next(ks), (L, Hq, lora, v), lora ** -0.5 / s_kv),
        "o_proj": w(next(ks), (L, Hq * v, H), (Hq * v) ** -0.5),
    }
    if g.q_lora:
        lp["q_a_proj"] = w(next(ks), (L, H, g.q_lora), scale)
        lp["q_a_norm"] = jnp.ones((L, g.q_lora), dtype)
        lp["q_b_proj"] = w(next(ks), (L, g.q_lora, Hq * (nope + rope)),
                           g.q_lora ** -0.5 / s_q)
    else:
        lp["q_proj"] = w(next(ks), (L, H, Hq * (nope + rope)), scale)
    if g.gate:
        lp["attn_gate"] = w(next(ks), (L, H, Hq), scale)
    if cfg.use_dsa and kind == FULL:
        nh, hd = cfg.index_n_heads, cfg.index_head_dim
        q_in = cfg.q_lora_rank or H
        lp["idx_wq_b"] = w(next(ks), (L, q_in, nh * hd), q_in ** -0.5 / s_q)
        lp["idx_wk"] = w(next(ks), (L, H, hd), scale)
        lp["idx_k_norm_w"] = jnp.ones((L, hd), dtype)
        lp["idx_k_norm_b"] = jnp.zeros((L, hd), dtype)
        lp["idx_weights"] = w(next(ks), (L, H, nh), scale)
    return lp


def _mlp_init(cfg, lp, L, mlp: str, dtype, w, ks):
    H = cfg.hidden_size
    scale = H ** -0.5
    if mlp == "dense":
        I = cfg.intermediate_size
        lp["gate_proj"] = w(next(ks), (L, H, I), scale)
        lp["up_proj"] = w(next(ks), (L, H, I), scale)
        lp["down_proj"] = w(next(ks), (L, I, H), I ** -0.5)
        return lp
    E, Eh = cfg.num_experts, cfg.num_local_experts
    I = cfg.moe_intermediate_size
    lp["router"] = w(next(ks), (L, H, E), scale)
    if cfg.topk_method == "noaux_tc":
        lp["e_bias"] = jnp.zeros((L, E), jnp.float32)
    lp["w_gate"] = w(next(ks), (L, Eh, H, I), scale)
    lp["w_up"] = w(next(ks), (L, Eh, H, I), scale)
    lp["w_down"] = w(next(ks), (L, Eh, I, H), I ** -0.5)
    SI = cfg.n_shared_experts * I
    lp["shared_gate_proj"] = w(next(ks), (L, H, SI), scale)
    lp["shared_up_proj"] = w(next(ks), (L, H, SI), scale)
    lp["shared_down_proj"] = w(next(ks), (L, SI, H), SI ** -0.5)
    return lp


def init_params(cfg: ModelConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Params:
    """Seeded random weights (``--load-format dummy``). DeepSeek's two
    runs draw from ``split(key, 64)`` in order, as they always have; a
    model with ``layer_types`` has as many runs as its pattern has
    changes, so its n-th draw takes ``fold_in(key, n)``, whatever the
    number of draws (perfbench/reference/dots3_note.py draws the same)."""
    H = cfg.hidden_size
    key = jax.random.key(seed)
    if cfg.use_swa:
        import itertools
        ks = (jax.random.fold_in(key, i) for i in itertools.count())
    else:
        ks = iter(jax.random.split(key, 64))

    def w(k, shape, scale):
        a = (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
        if a.size >= 1 << 27:
            # eager draws run ahead of the device: each holds its float32
            # form until its cast has run, and a run's expert stack is 3 GB
            # of float32. Where the step programs came from the compile
            # cache the host ran three stacks ahead and the process peaked
            # at 16.7 of a v5e's 16.9 GB (15.2 on a cold start; PERF.md,
            # PR 34). One large leaf at a time (a no-op while traced).
            jax.block_until_ready(a)
        return a

    params: Params = {}
    scale = H ** -0.5
    stacks = [_mlp_init(cfg, _mla_layer_init(cfg, n, dtype, w, ks, kind),
                        n, mlp, dtype, w, ks)
              for kind, mlp, n in layer_runs(cfg)]
    if cfg.use_swa:
        params["runs"] = {f"run{i}": lp for i, lp in enumerate(stacks)}
    else:
        for (_, mlp, _), lp in zip(layer_runs(cfg), stacks):
            params["dense_layers" if mlp == "dense" else "moe_layers"] = lp
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

def forward(params, kv: LatentKVCache, batch: StepBatch, cfg: ModelConfig,
            *, cos_sin, attn_impl: str = "xla", max_q_len: int,
            hidden_in=None, residual_in=None):
    if cfg.is_first_stage:
        # Out-of-vocab placeholder ids (Kimi's media pad sits past the LM
        # vocab) clamp in the gather; those rows are fully replaced by the
        # visual splice below (reference kimi_k25.py embed_input_ids).
        hidden = params["embed"][batch.token_ids]
        if batch.mm_embeds is not None:
            mm_main = batch.mm_embeds[:, :cfg.hidden_size]
            hidden = jnp.where(batch.mm_mask[:, None],
                               mm_main.astype(hidden.dtype), hidden)
        residual = jnp.zeros_like(hidden)
    else:
        hidden, residual = hidden_in, residual_in

    none = jnp.zeros((), jnp.float32)       # a carry that is not there
    cache = kv.latent
    icache = kv.index_k if cfg.use_dsa else none
    has_iscale = cfg.use_dsa and kv.index_scale is not None
    iscale = kv.index_scale if has_iscale else none
    ring = kv.swa if cfg.use_swa else none
    with_stats = kv.stats is not None
    stats = jnp.zeros((len(STATS),), jnp.int32)
    valid = jnp.arange(hidden.shape[0]) < batch.attn.cu_q_lens[-1]
    tables = {FULL: cos_sin[0], SWA: cos_sin[1]} if cfg.use_swa else {
        FULL: cos_sin}

    def make_step(kind, mlp, stacks):
        def layer_step(carry, xs):
            lp, ri = xs
            h, res, cache, icache, iscale, ring, stats, li, wi = carry
            normed, res = fused_add_rms_norm(h, res, lp["input_norm"],
                                             cfg.rms_norm_eps)
            # Flat-view stacked-cache addressing (same re-design as
            # dense._attention): the layer offset rides the slot mapping
            # (+li·P·page) and page table (+li·P) against [L·P, ...]
            # reshape VIEWS of the scan carries, so no full layer slice
            # is ever materialized — the earlier dynamic_index/update
            # round-trip copied the whole layer cache twice per layer per
            # step. All MLA helpers (latent scatter, paged MQA, DSA
            # indexer/sparse gather) are shape-generic over the flat
            # leading axis; every layer's page 0 is its own dummy page.
            # ``li`` counts the layers that hold pages, ``wi`` the
            # windowed ones, whose rings are stacked the same way (slot 0
            # of each layer is its dummy).
            L, P, page = cache.shape[0], cache.shape[1], cache.shape[2]
            batch_l = batch._replace(
                slot_mapping=batch.slot_mapping + li * (P * page),
                attn=batch.attn._replace(
                    page_table=batch.attn.page_table + li * P))
            lc = cache.reshape((L * P,) + cache.shape[2:])
            ic = (icache.reshape((L * P,) + icache.shape[2:])
                  if cfg.use_dsa else None)
            isc = (iscale.reshape((L * P,) + iscale.shape[2:])
                   if has_iscale else None)
            rc = (ring.reshape((-1,) + ring.shape[2:])
                  if cfg.use_swa else None)
            attn_out, lc, ic, isc, rc, dsa = _mla_attention(
                lp, normed, batch_l, lc, cfg, tables[kind],
                max_q_len=max_q_len, attn_impl=attn_impl,
                index_cache=ic, index_scale=isc, kind=kind, ring=rc,
                slot_base=wi * ring.shape[1] if cfg.use_swa else 0)
            if kind == SWA:
                ring = rc.reshape(ring.shape)
                wi = wi + 1
            else:
                cache = lc.reshape(cache.shape)
                if cfg.use_dsa:
                    icache = ic.reshape(icache.shape)
                if has_iscale:
                    iscale = isc.reshape(iscale.shape)
                li = li + 1
            normed2, res = fused_add_rms_norm(attn_out, res,
                                              lp["post_attn_norm"],
                                              cfg.rms_norm_eps)
            if mlp == "dense":
                out, moe = dense._mlp(lp, normed2), None
            else:
                out, moe = _moe_block(lp, normed2, cfg, valid, stacks, ri)
            if with_stats:
                if dsa is not None:
                    stats = stats.at[0:2].add(dsa)
                if moe is not None:
                    stats = stats.at[2:6].add(moe)
            return (out, res, cache, icache, iscale, ring, stats, li,
                    wi), None
        return layer_step

    carry = (hidden, residual, cache, icache, iscale, ring, stats,
             jnp.int32(0), jnp.int32(0))
    for (kind, mlp, n), lp in zip(layer_runs(cfg), run_params(params, cfg)):
        stacks = None
        if (mlp == "moe" and cfg.experts_held and n > 1 and all(
                isinstance(lp[k], jax.Array) for k in _EXPERT_STACKS)):
            # the held experts' stacks stay whole (``_held_experts``)
            stacks = tuple(lp[k] for k in _EXPERT_STACKS)
            lp = {k: v for k, v in lp.items() if k not in _EXPERT_STACKS}
        carry, _ = jax.lax.scan(make_step(kind, mlp, stacks), carry,
                                (lp, jnp.arange(n, dtype=jnp.int32)))
    hidden, residual, cache, icache, iscale, ring, stats = carry[:7]
    return hidden, residual, LatentKVCache(
        cache, icache if cfg.use_dsa else kv.index_k,
        iscale if has_iscale else kv.index_scale,
        ring if cfg.use_swa else kv.swa,
        stats if with_stats else kv.stats)


compute_logits = dense.compute_logits
