"""Cohere2-MoE decoder (``Cohere2MoeForCausalLM``, model_type cohere2_moe:
CohereLabs/command-a-plus-05-2026): a PARALLEL block over one LayerNorm,

    h = LN(x);   x <- x + Attn_l(h) + Experts(h)        layer l by
    "sliding_attention"  GQA with rotary embedding (pairs (2i, 2i+1)) that
                         attends the last ``sliding_window`` positions
    "full_attention"     GQA over the whole context, no position at all
    Experts(h) = sum over the 8 chosen of sigmoid-routed gated SiLU
                 experts + the mean of the shared experts

and logits = LN(x) E^T x logit_scale over the tied embedding.

TPU-first structure:
- every layer's rows, of either kind, live in the ONE paged pool of
  models/dense.py under the one page table ([L, pages, page, Hkv, D]): a
  windowed layer's pages behind its window are kept for the sequence's
  life and never read (``paged_attention(window=...)`` fetches the pages
  the window overlaps), so the prefix cache, the scheduler and the
  memory manager see a dense GQA model;
- attention is ``dense._attention`` (its projections, its barrier, its
  flat-view addressing of the stacked cache), told per layer kind whether
  to rotate and whether to window;
- all layers' leaves are stacked [L, ...] (both kinds have the same
  parameters) and the pattern is folded into nested ``lax.scan``s
  (``nemotron_h.layer_program``: ``sss f`` x 8 traces two blocks), a
  layer's leaves cut from the stack where they are used;
- the routed experts are models/deepseek.py's: the sigmoid route, the held
  share (``ep_share``: the router stays ``num_experts`` wide, this process
  computes its own experts' part), the shared expert. The
  ``num_shared_experts`` shared experts are stored as one of their widths
  on end and their mean is that one's output times 1 / n.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from gllm_tpu.batching import StepBatch
from gllm_tpu.models import dense
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.models.deepseek import (STATS, _held_experts, _shared_expert,
                                      deepseek_route, expert_stacks)
from gllm_tpu.models.nemotron_h import layer_program
from gllm_tpu.obs import metrics as obs
from gllm_tpu.ops import layer_norm

Params = Dict[str, Any]

SLIDING, FULL = "sliding_attention", "full_attention"

class Cohere2KV(NamedTuple):
    """The paged KV of ALL layers, windowed and full ([L, num_pages,
    page_size, Hkv, D], dense.KVCache's layout), and what the step's
    expert layers counted (models/deepseek.py STATS)."""
    k: jnp.ndarray
    v: jnp.ndarray
    stats: Optional[jnp.ndarray] = None


_M_ROWS = obs.counter(
    "gllm_attn_rows_read_total",
    "KV rows a step's attention has to read in a model whose layers "
    "differ in kind: min(context, window) of every sequence in a windowed "
    "layer, the whole context in a full one, summed over the step's "
    "sequences and the layers of the kind (a prefill chunk's queries "
    "share their sequence's rows), by the kind of layer and of step "
    "(decode: one token a row; mixed: a prefill chunk rides)",
    ("kind", "step"))


def count_rows_read(cfg: ModelConfig, kv_lens, decode_only: bool) -> None:
    """One step's rows into the counter, from the batch's ``kv_lens`` as
    the host built them and the window (no device value is read)."""
    step = "decode" if decode_only else "mixed"
    kinds = cfg.stage_layer_types
    _M_ROWS.inc(int(kv_lens.clip(max=cfg.sliding_window).sum())
                * kinds.count(SLIDING), kind="sliding", step=step)
    _M_ROWS.inc(int(kv_lens.sum()) * kinds.count(FULL), kind="full",
                step=step)


def grouped_impl(attn_impl: str, quantized: bool = False) -> str:
    """``_held_experts``'s ``grouped`` for a runner whose attention runs
    as ``attn_impl``: ops/pallas/grouped_matmul.py where that is Pallas,
    which read more of the v5e's bandwidth than XLA's ``ragged_dot`` at 16
    groups of 4096 x 4096 (a 16-row step's three products 1.49 against
    1.54 ms a layer, 83 against 80 % of the touched experts' bytes; a
    512-token step's 2.87 against 5.62 ms: benchmarks/grouped_forms.py,
    PERF.md section 6, PR 44); elsewhere, and for quantized stacks,
    XLA's."""
    return "pallas" if attn_impl == "pallas" and not quantized else "xla"


def startup_line(cfg: ModelConfig, *, weight_bytes: int, num_pages: int,
                 page_bytes: int, page_size: int, prefix_cache: bool,
                 attn_impl: str, quantized: bool) -> str:
    """The three numbers of what the chip holds (tests/test_tpu_compile.py
    holds them to the TPU compiler's count), and which form multiplies
    the experts (``ModelDef.startup_line``)."""
    kinds = cfg.stage_layer_types
    return (
        "windowed GQA model: weights %d bytes (%d of %d routed experts a "
        "layer held here); KV pool %d pages x %d layers x %d B a token = "
        "%d bytes; window %d in %d of %d layers; prefix cache %s; held "
        "experts: grouped products -> %s" % (
            weight_bytes, cfg.num_local_experts, cfg.num_experts,
            num_pages, len(kinds), page_bytes // (page_size * len(kinds)),
            num_pages * page_bytes, cfg.sliding_window,
            kinds.count(SLIDING), len(kinds),
            "on" if prefix_cache else "off",
            "pallas gmm (ops/pallas/grouped_matmul.py)"
            if grouped_impl(attn_impl, quantized) == "pallas"
            else "xla ragged_dot"))


def init_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                  dtype=jnp.bfloat16, kv_pack: int = 1) -> Cohere2KV:
    if kv_pack != 1 or jnp.dtype(dtype) == jnp.int8:
        raise NotImplementedError(
            "cohere2_moe: a lane-packed or int8 KV cache")
    kv = dense.init_kv_cache(cfg, num_pages, page_size, dtype)
    return Cohere2KV(kv.k, kv.v, jnp.zeros((len(STATS),), jnp.int32))


def no_mesh_specs(cfg: ModelConfig, tp: int):
    raise NotImplementedError(
        "cohere2_moe under a mesh (tp / dp / sp > 1): the windowed "
        "attention calls have no shard_map and the expert layer has no "
        "exchange; one chip serves its share of a deployment (ep_share)")


# the windowed layers' table (the full layers read none)
make_rope_table = dense.make_rope_table


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Params:
    """Seeded random weights (``--load-format dummy``): matrices normal,
    1/sqrt(fan-in), the n-th draw from ``fold_in(key(seed), n)``, one
    program a leaf; the routed experts a layer at a time into their stack
    (a whole stack's float32 draw is 12.9 GB at the published widths). A
    shared expert's fan-in is its own width, not the four on end, and the
    TIED embedding's is the hidden size, as the head it also is: drawn at
    unit variance, as an embedding of its own is, a row's logit for its
    own token is hidden / sigma(x) ~ 3700 where every other is ~ N(0,
    64^2), and the bf16 logits' rounding at that magnitude (steps of 16)
    is all a comparison of logprobs reads (PERF.md section 6, PR 44).
    perfbench/reference/cohere2_moe.py draws the same."""
    L, H, D = cfg.num_stage_layers, cfg.hidden_size, cfg.head_dim
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads
    E, Eh = cfg.num_experts, cfg.num_local_experts
    I, SI = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    key = jax.random.key(seed)
    ks = (jax.random.fold_in(key, i) for i in itertools.count())

    def draw(k, shape, scale):
        # the three steps kept apart: what the reference's draw rounds to
        return (jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32)) * scale).astype(dtype)

    def w(shape, scale):
        return jax.jit(draw, static_argnums=(1, 2))(next(ks), shape, scale)

    s = H ** -0.5
    layers: Params = {
        "norm": jnp.ones((L, H), dtype),
        "q_proj": w((L, H, Hq * D), s),
        "k_proj": w((L, H, Hkv * D), s),
        "v_proj": w((L, H, Hkv * D), s),
        "o_proj": w((L, Hq * D, H), (Hq * D) ** -0.5),
        "router": w((L, H, E), s),
        "shared_gate_proj": w((L, H, SI), s),
        "shared_up_proj": w((L, H, SI), s),
        "shared_down_proj": w((L, SI, H), I ** -0.5),
    }
    for name, shape, scale in (("w_gate", (Eh, H, I), s),
                               ("w_up", (Eh, H, I), s),
                               ("w_down", (Eh, I, H), I ** -0.5)):
        set_layer = jax.jit(
            lambda stack, k, i, shape=shape, scale=scale:
            stack.at[i].set(draw(k, shape, scale)), donate_argnums=0)
        stack = jnp.zeros((L,) + shape, dtype)
        for i in range(L):
            stack = set_layer(stack, next(ks), i)
        layers[name] = stack
    # one stage (any mesh is refused): the embedding and the final norm
    # are both here
    tied = cfg.tie_word_embeddings
    params: Params = {"layers": layers,
                      "embed": w((cfg.vocab_size, H), s if tied else 1.0),
                      "final_norm": jnp.ones((H,), dtype)}
    if not tied:
        params["lm_head"] = w((H, cfg.vocab_size), s)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _experts(lp, h, cfg: ModelConfig, valid, stacks, layer, grouped: str):
    """(the expert half of the block [T, H], stats [4])."""
    logits = h.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    weights, ids = deepseek_route(logits, None, cfg)
    if not cfg.experts_held:
        # the layer whole: every expert is held
        cfg = dataclasses.replace(cfg, experts_held=cfg.num_experts)
    routed, stats = _held_experts(lp, h, weights, ids, valid, cfg, stacks,
                                  layer, grouped)
    shared = _shared_expert(lp, h).astype(jnp.float32)
    return (routed + shared * (1.0 / cfg.n_shared_experts)).astype(
        h.dtype), stats


def forward(params: Params, kv: Cohere2KV, batch: StepBatch,
            cfg: ModelConfig, *, cos_sin, attn_impl: str = "xla",
            max_q_len: int):
    hidden = params["embed"][batch.token_ids]
    valid = jnp.arange(hidden.shape[0]) < batch.attn.cu_q_lens[-1]
    names = expert_stacks(cfg)
    rest = params["layers"]
    stacks = None
    if all(isinstance(rest[k], jax.Array) for k in names):
        # the held experts' stacks stay whole (deepseek._held_experts)
        stacks = tuple(rest[k] for k in names)
        rest = {k: v for k, v in rest.items() if k not in names}
    grouped = grouped_impl(attn_impl, stacks is None)

    def block(carry, kind):
        x, k_all, v_all, stats, li = carry
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
            rest)
        h = layer_norm(x, lp["norm"], cfg.rms_norm_eps)
        windowed = kind == SLIDING
        attn, k_all, v_all, _, _ = dense._attention(
            lp, h, batch, k_all, v_all, cfg, cos_sin, attn_impl=attn_impl,
            max_q_len=max_q_len, li=li, use_rope=windowed,
            window=cfg.sliding_window if windowed else None)
        moe, moe_stats = _experts(lp, h, cfg, valid, stacks, li, grouped)
        return (x + attn + moe, k_all, v_all,
                stats.at[2:6].add(moe_stats), li + 1)

    def run(program, carry):
        for item in program:
            if isinstance(item, str):
                carry = block(carry, item)
            else:
                sub, count = item
                carry, _ = jax.lax.scan(
                    lambda c, _, sub=sub: (run(sub, c), None), carry, None,
                    length=count)
        return carry

    carry = (hidden, kv.k, kv.v, jnp.zeros((len(STATS),), jnp.int32),
             jnp.int32(0))
    hidden, k_all, v_all, stats, _ = run(
        layer_program(cfg.stage_layer_types), carry)
    return hidden, jnp.zeros_like(hidden), Cohere2KV(k_all, v_all, stats)


compute_logits = dense.compute_logits


def load_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16,
                progress_cb=None) -> Params:
    raise NotImplementedError(
        "cohere2_moe: no rules for a checkpoint's tensors yet (the "
        "published checkpoint's index is not in the repository, so its "
        "tensor names are not known here); serve it with --load-format "
        "dummy")
