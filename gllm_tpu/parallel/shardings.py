"""Parameter / cache sharding specs for the dense family.

The Megatron TP recipe, expressed as mesh-axis shardings instead of the
reference's ColumnParallelLinear/RowParallelLinear module wrappers
(/root/reference/gllm/layers/linear.py, vocab_parallel_embedding.py):

- q/k/v projections: output (head) dim sharded over ``tp`` → column parallel
- o_proj / down_proj: input dim sharded over ``tp`` → row parallel; XLA
  inserts the psum the reference issues manually per layer
  (dist_utils.py:572-602)
- gate/up: column parallel
- embedding + lm_head: vocab-sharded (vocab-parallel embedding with padded
  shards + all-gathered logits → here GSPMD's gather/psum handles the
  masked lookup, and the runner constrains logits to replicated)
- KV cache: sharded over the kv-head axis when divisible, else replicated
  (small-Hkv models replicate KV like the reference's TP head-division
  bookkeeping, layers/modules/attention.py:32)

DP shards nothing here: attention-DP replicas hold full weights (reference
DP design) and split the *token/sequence* axes of each batch.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gllm_tpu.models.config import ModelConfig
from gllm_tpu.parallel.mesh import AXIS_TP


def _tp_if(divisible: bool):
    return AXIS_TP if divisible else None


def dense_param_specs(cfg: ModelConfig, tp: int) -> dict:
    """PartitionSpec pytree matching gllm_tpu.models.dense param layout."""
    qkv_ok = (cfg.num_heads * cfg.head_dim) % tp == 0
    kv_ok = (cfg.num_kv_heads * cfg.head_dim) % tp == 0
    inter_ok = cfg.intermediate_size % tp == 0
    vocab_ok = cfg.vocab_size % tp == 0

    layers = {
        "input_norm": P(None, None),
        "q_proj": P(None, None, _tp_if(qkv_ok)),
        "k_proj": P(None, None, _tp_if(kv_ok)),
        "v_proj": P(None, None, _tp_if(kv_ok)),
        "o_proj": P(None, _tp_if(qkv_ok), None),
        "post_attn_norm": P(None, None),
        "gate_proj": P(None, None, _tp_if(inter_ok)),
        "up_proj": P(None, None, _tp_if(inter_ok)),
        "down_proj": P(None, _tp_if(inter_ok), None),
    }
    if cfg.attention_bias:
        layers["q_bias"] = P(None, _tp_if(qkv_ok))
        layers["k_bias"] = P(None, _tp_if(kv_ok))
        layers["v_bias"] = P(None, _tp_if(kv_ok))
    if cfg.qk_norm:
        layers["q_norm"] = P(None, None)
        layers["k_norm"] = P(None, None)
    if cfg.sandwich_norms:
        layers["post_self_attn_norm"] = P(None, None)
        layers["post_mlp_norm"] = P(None, None)
    specs = {"layers": layers}
    if cfg.is_first_stage:
        specs["embed"] = P(_tp_if(vocab_ok), None)
    if cfg.is_last_stage:
        specs["final_norm"] = P(None)
        if not cfg.tie_word_embeddings:
            specs["lm_head"] = P(None, _tp_if(vocab_ok))
    return specs


def moe_param_specs(cfg: ModelConfig, tp: int) -> dict:
    """Dense specs + expert-parallel sharding: the expert axis shards over
    ``tp`` (the reference's EP group spans the whole stage,
    dist_utils.py:81-86,209-210). GSPMD inserts the token gathers/psums the
    reference's dp_gather_hidden/ep_all_reduce perform by hand."""
    specs = dense_param_specs(cfg, tp)
    layers = specs["layers"]
    from gllm_tpu.models.moe import moe_layer_mask
    if all(moe_layer_mask(cfg)):
        for name in ("gate_proj", "up_proj", "down_proj"):
            layers.pop(name, None)
    else:
        # mixed dense/sparse stack keeps the dense MLP leaves (their
        # dense_param_specs tp shardings apply) plus the per-layer flag
        layers["moe_mask"] = P(None)
    ep_ok = cfg.num_experts % tp == 0
    ep = _tp_if(ep_ok)
    layers["router"] = P(None, None, None)
    layers["w_gate"] = P(None, ep, None, None)
    layers["w_up"] = P(None, ep, None, None)
    layers["w_down"] = P(None, ep, None, None)
    if cfg.shared_expert_intermediate_size:
        si_ok = cfg.shared_expert_intermediate_size % tp == 0
        layers["shared_gate_proj"] = P(None, None, _tp_if(si_ok))
        layers["shared_up_proj"] = P(None, None, _tp_if(si_ok))
        layers["shared_down_proj"] = P(None, _tp_if(si_ok), None)
        layers["shared_expert_gate"] = P(None, None, None)
    return specs


def kv_cache_specs(cfg: ModelConfig, tp: int):
    from gllm_tpu.models.dense import KVCache
    kv_heads_ok = cfg.num_kv_heads % tp == 0
    spec = P(None, None, None, _tp_if(kv_heads_ok), None)
    if cfg.kv_cache_quant:
        # int8 cache: [L, P, Hkv] scales shard with the kv-head axis
        sspec = P(None, None, _tp_if(kv_heads_ok))
        return KVCache(spec, spec, sspec, sspec)
    return KVCache(spec, spec)


def latent_kv_specs(cfg: ModelConfig, tp: int):
    """MLA latent cache is MQA-shaped (no head axis) → replicated over tp."""
    from gllm_tpu.models.deepseek import LatentKVCache
    return LatentKVCache(
        P(None, None, None, None),
        P(None, None, None, None) if cfg.use_dsa else None,
        P(None, None, None) if (cfg.use_dsa and cfg.kv_cache_fp8)
        else None)


def shard_params(params, specs, mesh: Optional[Mesh]):
    """Place a param pytree onto the mesh with the given specs.

    Quantized leaves (ops/quant.py) place their int8 payload with the
    weight's spec and their [.., 1, out] scale with the same spec minus any
    axis on size-1 dims (a sharded singleton is impossible)."""
    if mesh is None:
        return params
    from gllm_tpu.ops.quant import (Quantized, Quantized4, QuantizedBlock,
                                    QuantizedW8A8)
    qtypes = (Quantized, Quantized4, QuantizedW8A8, QuantizedBlock)

    def place(x, s):
        if isinstance(x, qtypes):
            dims = list(s) + [None] * (x.q.ndim - len(s))
            if isinstance(x, QuantizedBlock):
                # tiny per-tile scale grids replicate (a 128-tile grid
                # rarely divides over tp; deq broadcasts them fine)
                scale_spec = P(*[None] * x.scale.ndim)
            else:
                scale_spec = P(*[None if x.scale.shape[i] == 1 else dims[i]
                                 for i in range(x.scale.ndim)])
            return type(x)(
                jax.device_put(x.q, NamedSharding(mesh, s)),
                jax.device_put(x.scale, NamedSharding(mesh, scale_spec)))
        return jax.device_put(x, NamedSharding(mesh, s))

    return jax.tree.map(place, params, specs,
                        is_leaf=lambda n: isinstance(n, qtypes))


def deepseek_param_specs(cfg: ModelConfig, tp: int) -> dict:
    """DeepSeek MLA + MoE shardings: query heads / absorbed W_UK/W_UV /
    o_proj shard over heads; latent projections replicate (rank dims are
    small); experts shard over tp (EP)."""
    heads_ok = cfg.num_heads % tp == 0
    h = _tp_if(heads_ok)
    ep = _tp_if(cfg.num_experts % tp == 0 if cfg.num_experts else False)
    inter_ok = cfg.intermediate_size % tp == 0
    vocab_ok = cfg.vocab_size % tp == 0

    def mla_block(has_mlp_dense: bool, L_key: str) -> dict:
        d = {
            "input_norm": P(None, None),
            "post_attn_norm": P(None, None),
            "kv_a_proj": P(None, None, None),
            "kv_a_norm": P(None, None),
            "w_uk": P(None, h, None, None),
            "w_uv": P(None, h, None, None),
            "o_proj": P(None, h, None),
        }
        if cfg.q_lora_rank:
            d["q_a_proj"] = P(None, None, None)
            d["q_a_norm"] = P(None, None)
            d["q_b_proj"] = P(None, None, h)
        else:
            d["q_proj"] = P(None, None, h)
        if cfg.use_dsa:
            # indexer replicates (cheap, per-head scores are summed —
            # reference keeps it unsharded, deepseek_v32.py:127-131)
            d["idx_wq_b"] = P(None, None, None)
            d["idx_wk"] = P(None, None, None)
            d["idx_k_norm_w"] = P(None, None)
            d["idx_k_norm_b"] = P(None, None)
            d["idx_weights"] = P(None, None, None)
        return d

    specs: dict = {}
    first, last = cfg.stage_layers
    n_dense = max(0, min(cfg.first_k_dense_replace, last) - first)
    n_moe = (last - first) - n_dense
    if n_dense:
        d = mla_block(True, "dense_layers")
        d["gate_proj"] = P(None, None, _tp_if(inter_ok))
        d["up_proj"] = P(None, None, _tp_if(inter_ok))
        d["down_proj"] = P(None, _tp_if(inter_ok), None)
        specs["dense_layers"] = d
    if n_moe:
        m = mla_block(False, "moe_layers")
        m["router"] = P(None, None, None)
        if cfg.topk_method == "noaux_tc":
            m["e_bias"] = P(None, None)
        m["w_gate"] = P(None, ep, None, None)
        m["w_up"] = P(None, ep, None, None)
        m["w_down"] = P(None, ep, None, None)
        si_ok = (cfg.n_shared_experts
                 * cfg.moe_intermediate_size) % tp == 0
        m["shared_gate_proj"] = P(None, None, _tp_if(si_ok))
        m["shared_up_proj"] = P(None, None, _tp_if(si_ok))
        m["shared_down_proj"] = P(None, _tp_if(si_ok), None)
        specs["moe_layers"] = m
    if cfg.is_first_stage:
        specs["embed"] = P(_tp_if(vocab_ok), None)
    if cfg.is_last_stage:
        specs["final_norm"] = P(None)
        if not cfg.tie_word_embeddings:
            specs["lm_head"] = P(None, _tp_if(vocab_ok))
    return specs


def vl_param_specs(cfg: ModelConfig, tp: int) -> dict:
    """VL = dense text specs + replicated vision tower (the ViT is small
    relative to the LM; per-item batches don't shard usefully over tp)."""
    import jax

    from gllm_tpu.models import qwen2_5_vl, vision
    specs = dense_param_specs(cfg, tp)
    vtemplate = jax.eval_shape(
        lambda: vision.init_vision_params(qwen2_5_vl.vision_cfg(cfg)))
    specs["visual"] = jax.tree.map(lambda s: P(*([None] * len(s.shape))),
                                   vtemplate)
    return specs


def vl3_param_specs(cfg: ModelConfig, tp: int) -> dict:
    """Qwen3-VL: dense/MoE text specs + replicated vision tower."""
    import jax

    from gllm_tpu.models import qwen3_vl, vision_qwen3
    specs = (moe_param_specs(cfg, tp) if cfg.num_experts
             else dense_param_specs(cfg, tp))
    vtemplate = jax.eval_shape(
        lambda: vision_qwen3.init_vision_params(qwen3_vl.vision_cfg(cfg)))
    specs["visual"] = jax.tree.map(lambda s: P(*([None] * len(s.shape))),
                                   vtemplate)
    return specs


def kimi_param_specs(cfg: ModelConfig, tp: int) -> dict:
    """Kimi K2.5: DeepSeek text specs + replicated MoonViT tower."""
    import jax

    from gllm_tpu.models import kimi, kimi_vision
    specs = deepseek_param_specs(cfg, tp)
    vtemplate = jax.eval_shape(
        lambda: kimi_vision.init_vision_params(kimi.vision_cfg(cfg)))
    specs["visual"] = jax.tree.map(lambda s: P(*([None] * len(s.shape))),
                                   vtemplate)
    return specs


def hybrid_param_specs(cfg: ModelConfig, tp: int) -> dict:
    """Qwen3-Next hybrid shardings: attention halves shard like dense
    (head axis), GDN projections shard on their output/head axes, MoE
    experts on the expert axis; small per-head vectors replicate."""
    import jax

    from gllm_tpu.models import hybrid
    template = jax.eval_shape(lambda: hybrid.init_params(cfg))

    def spec_for(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(leaf.shape)
        tp_ok = lambda dim: dim % tp == 0  # noqa: E731
        if name in ("q_proj", "k_proj", "v_proj", "in_qkvz", "in_ba",
                    "gate_proj", "up_proj", "shared_gate_proj",
                    "shared_up_proj"):
            return P(*([None] * (nd - 1)),
                     _tp_if(tp_ok(leaf.shape[-1])))
        if name in ("o_proj", "down_proj", "out_proj",
                    "shared_down_proj"):
            return P(None, _tp_if(tp_ok(leaf.shape[1])), None)
        if name in ("w_gate", "w_up", "w_down"):
            return P(None, _tp_if(tp_ok(leaf.shape[1])), None, None)
        if name == "embed":
            return P(_tp_if(tp_ok(leaf.shape[0])), None)
        if name == "lm_head":
            return P(None, _tp_if(tp_ok(leaf.shape[-1])))
        return P(*([None] * nd))

    import jax.tree_util as jtu
    return jtu.tree_map_with_path(spec_for, template)


def hybrid_kv_specs(cfg: ModelConfig, tp: int):
    from gllm_tpu.models.hybrid import HybridKV
    kv_heads_ok = cfg.num_kv_heads % tp == 0
    kv_spec = P(None, None, None, _tp_if(kv_heads_ok), None)
    # GDN states shard over the value-head axis when divisible: the
    # axis of the groups of heads that lie abreast in a slot
    vh_ok = cfg.ssm_slot_shapes[1][0] % tp == 0
    return HybridKV(
        k=kv_spec, v=kv_spec,
        conv=P(None, None, None, None),
        rec=P(None, None, _tp_if(vh_ok), None, None),
    )
