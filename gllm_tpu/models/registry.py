"""Architecture registry.

Maps HF ``architectures[0]`` strings to model definitions, like the
reference's architecture→class table (/root/reference/gllm/model_loader.py:
499-536). A ModelDef bundles the functional pieces the runner needs.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

from gllm_tpu.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ModelDef:
    family: str
    init_params: Callable
    forward: Callable
    compute_logits: Callable
    make_rope_table: Callable
    load_params: Callable          # (model_dir, cfg, dtype) -> params
    init_kv_cache: Callable
    param_specs: Callable          # (cfg, tp) -> PartitionSpec pytree
    kv_specs: Callable             # (cfg, tp) -> cache PartitionSpec pytree
    # VL models: (params, cfg, pixels, grid_thw) -> [n_rows, mm_embed_dim]
    embed_mm: Optional[Callable] = None
    # (cfg, kv_lens, decode_only): a step's rows into the family's counter,
    # from the host's batch at dispatch
    count_rows_read: Optional[Callable] = None
    # (cfg, *, weight_bytes, num_pages, page_bytes, page_size, prefix_cache,
    # attn_impl, quantized) -> what the chip holds, one start-up line
    startup_line: Optional[Callable] = None


def _dense_def() -> ModelDef:
    from gllm_tpu.models import dense, loader
    from gllm_tpu.parallel.shardings import (dense_param_specs,
                                             kv_cache_specs)
    return ModelDef(
        family="dense",
        init_params=dense.init_params,
        forward=dense.forward,
        compute_logits=dense.compute_logits,
        make_rope_table=dense.make_rope_table,
        load_params=loader.load_dense_params,
        init_kv_cache=dense.init_kv_cache,
        param_specs=dense_param_specs,
        kv_specs=kv_cache_specs,
    )


_DENSE_ARCHS = (
    "ChatGLMForConditionalGeneration",
    "ChatGLMModel",
    "Glm4ForCausalLM",
    "GlmForCausalLM",
    "LlamaForCausalLM",
    "MistralForCausalLM",
    "Qwen2ForCausalLM",
    "Qwen3ForCausalLM",
)


def _vl_def() -> ModelDef:
    from gllm_tpu.models import qwen2_5_vl
    from gllm_tpu.parallel.shardings import kv_cache_specs, vl_param_specs
    return ModelDef(
        family="vl",
        init_params=qwen2_5_vl.init_params,
        forward=qwen2_5_vl.forward,
        compute_logits=qwen2_5_vl.compute_logits,
        make_rope_table=qwen2_5_vl.make_rope_table,
        load_params=qwen2_5_vl.load_params,
        init_kv_cache=qwen2_5_vl.init_kv_cache,
        param_specs=vl_param_specs,
        kv_specs=kv_cache_specs,
        embed_mm=qwen2_5_vl.embed_mm,
    )


def _vl3_def() -> ModelDef:
    from gllm_tpu.models import qwen3_vl
    from gllm_tpu.parallel.shardings import kv_cache_specs, vl3_param_specs
    return ModelDef(
        family="vl3",
        init_params=qwen3_vl.init_params,
        forward=qwen3_vl.forward,
        compute_logits=qwen3_vl.compute_logits,
        make_rope_table=qwen3_vl.make_rope_table,
        load_params=qwen3_vl.load_params,
        init_kv_cache=qwen3_vl.init_kv_cache,
        param_specs=vl3_param_specs,
        kv_specs=kv_cache_specs,
        embed_mm=qwen3_vl.embed_mm,
    )


def get_model_def(cfg: ModelConfig) -> ModelDef:
    if cfg.architecture in _DENSE_ARCHS:
        return _dense_def()
    if cfg.architecture in _MOE_ARCHS:
        from gllm_tpu.models.registry_moe import moe_def
        return moe_def()
    if cfg.architecture in _MLA_ARCHS:
        from gllm_tpu.models.registry_moe import deepseek_def
        return deepseek_def()
    if cfg.architecture in _VL_ARCHS:
        return _vl_def()
    if cfg.architecture in _VL3_ARCHS:
        return _vl3_def()
    if cfg.architecture == "KimiK25ForConditionalGeneration":
        from gllm_tpu.models import kimi
        from gllm_tpu.parallel.shardings import (kimi_param_specs,
                                                 latent_kv_specs)
        return ModelDef(
            family="kimi",
            init_params=kimi.init_params,
            forward=kimi.forward,
            compute_logits=kimi.compute_logits,
            make_rope_table=kimi.make_rope_table,
            load_params=kimi.load_params,
            init_kv_cache=kimi.init_kv_cache,
            param_specs=kimi_param_specs,
            kv_specs=latent_kv_specs,
            embed_mm=kimi.embed_mm,
        )
    if cfg.architecture in _HYBRID_ARCHS:
        from gllm_tpu.models import hybrid
        from gllm_tpu.parallel.shardings import (hybrid_kv_specs,
                                                 hybrid_param_specs)
        return ModelDef(
            family="hybrid",
            init_params=hybrid.init_params,
            forward=hybrid.forward,
            compute_logits=hybrid.compute_logits,
            make_rope_table=hybrid.make_rope_table,
            load_params=hybrid.load_params,
            init_kv_cache=hybrid.init_kv_cache,
            param_specs=hybrid_param_specs,
            kv_specs=hybrid_kv_specs,
        )
    if cfg.architecture in _NEMOTRON_H_ARCHS:
        from gllm_tpu.models import nemotron_h
        return ModelDef(
            family="nemotron_h",
            init_params=nemotron_h.init_params,
            forward=nemotron_h.forward,
            compute_logits=nemotron_h.compute_logits,
            make_rope_table=nemotron_h.make_rope_table,
            load_params=nemotron_h.load_params,
            init_kv_cache=nemotron_h.init_kv_cache,
            param_specs=nemotron_h.no_mesh_specs,
            kv_specs=nemotron_h.no_mesh_specs,
        )
    if cfg.architecture in _COHERE2_MOE_ARCHS:
        from gllm_tpu.models import cohere2_moe
        return ModelDef(
            family="cohere2_moe",
            init_params=cohere2_moe.init_params,
            forward=cohere2_moe.forward,
            compute_logits=cohere2_moe.compute_logits,
            make_rope_table=cohere2_moe.make_rope_table,
            load_params=cohere2_moe.load_params,
            init_kv_cache=cohere2_moe.init_kv_cache,
            param_specs=cohere2_moe.no_mesh_specs,
            kv_specs=cohere2_moe.no_mesh_specs,
            count_rows_read=cohere2_moe.count_rows_read,
            startup_line=cohere2_moe.startup_line,
        )
    if cfg.architecture in _FALCON_H1_ARCHS:
        from gllm_tpu.models import falcon_h1
        return ModelDef(
            family="falcon_h1",
            init_params=falcon_h1.init_params,
            forward=falcon_h1.forward,
            compute_logits=falcon_h1.compute_logits,
            make_rope_table=falcon_h1.make_rope_table,
            load_params=falcon_h1.load_params,
            init_kv_cache=falcon_h1.init_kv_cache,
            param_specs=falcon_h1.no_mesh_specs,
            kv_specs=falcon_h1.no_mesh_specs,
        )
    if cfg.architecture in _LFM2_MOE_ARCHS:
        from gllm_tpu.models import lfm2_moe
        return ModelDef(
            family="lfm2_moe",
            init_params=lfm2_moe.init_params,
            forward=lfm2_moe.forward,
            compute_logits=lfm2_moe.compute_logits,
            make_rope_table=lfm2_moe.make_rope_table,
            load_params=lfm2_moe.load_params,
            init_kv_cache=lfm2_moe.init_kv_cache,
            param_specs=lfm2_moe.no_mesh_specs,
            kv_specs=lfm2_moe.no_mesh_specs,
            startup_line=lfm2_moe.startup_line,
        )
    raise NotImplementedError(
        f"architecture {cfg.architecture!r} not supported yet; "
        f"dense: {_DENSE_ARCHS}, moe: {_MOE_ARCHS}, mla: {_MLA_ARCHS}, "
        f"vl: {_VL_ARCHS}")


_MOE_ARCHS = (
    "MixtralForCausalLM",
    "Qwen2MoeForCausalLM",
    "Qwen3MoeForCausalLM",
)

_MLA_ARCHS = (
    "DeepseekV2ForCausalLM",
    "DeepseekV3ForCausalLM",
    "DeepseekV32ForCausalLM",
    # dots-studio/dots3-note-prev (model_type dots3_note): DSA full layers
    # beside windowed latent layers with a geometry of their own, headwise
    # gates; models/deepseek.py reads both geometries from the config
    "Dots3NoteForCausalLM",
    # skt/A.X-K1 (model_type axk1): DeepSeek-V3's block with dense latent
    # attention in every layer (q-LoRA, YaRN), a sigmoid router whose
    # topk_method "none" is the plain top-8 of all 192 experts
    "AXK1ForCausalLM",
)

_VL_ARCHS = (
    "Qwen2_5_VLForConditionalGeneration",
)

_VL3_ARCHS = (
    "Qwen3VLForConditionalGeneration",
    "Qwen3VLMoeForConditionalGeneration",
)

_HYBRID_ARCHS = (
    "Qwen3NextForCausalLM",
    "Qwen3_5ForCausalLM",
    "Qwen3_5MoeForCausalLM",
    # Real Qwen3.5 checkpoints ship the ConditionalGeneration arch string
    # (reference model_loader.py:527-531); same hybrid GDN stack.
    "Qwen3_5ForConditionalGeneration",
    "Qwen3_5MoeForConditionalGeneration",
    # allenai/Olmo-Hybrid-7B: the same 3:1 GDN / full-attention stack in
    # the OLMo 3 block (models/hybrid.py reads the block's shape from the
    # config)
    "OlmoHybridForCausalLM",
)


_NEMOTRON_H_ARCHS = (
    # nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B (model_type nemotron_h): blocks
    # of one mixer each (Mamba-2 | relu^2 experts | GQA without rotary) in
    # the pattern ``hybrid_override_pattern`` spells (models/nemotron_h.py)
    "NemotronHForCausalLM",
)


_COHERE2_MOE_ARCHS = (
    # CohereLabs/command-a-plus-05-2026 (model_type cohere2_moe): a parallel
    # block over one LayerNorm, GQA layers with a window and rotary
    # embedding beside full layers without positions, all in the one paged
    # pool; sigmoid-routed experts beside averaged shared ones
    # (models/cohere2_moe.py)
    "Cohere2MoeForCausalLM",
)


_FALCON_H1_ARCHS = (
    # tiiuae/Falcon-H1-34B-Instruct (model_type falcon_h1): attention heads
    # and Mamba-2 heads side by side on one norm in EVERY layer (pages and
    # a slot a layer), muP multipliers, a SwiGLU MLP (models/falcon_h1.py)
    "FalconH1ForCausalLM",
)


_LFM2_MOE_ARCHS = (
    # LiquidAI/LFM2-24B-A2B (model_type lfm2_moe): a gated short
    # convolution (its window the only state, in the slot pool) or GQA with
    # heads of 64 in lane-packed pairs, then a SwiGLU or sigmoid-routed
    # experts, in every layer (models/lfm2_moe.py)
    "Lfm2MoeForCausalLM",
)


def supported_architectures() -> Dict[str, str]:
    out = {a: "dense" for a in _DENSE_ARCHS}
    out.update({a: "moe" for a in _MOE_ARCHS})
    out.update({a: "mla-moe" for a in _MLA_ARCHS})
    out.update({a: "vl" for a in _VL_ARCHS})
    out.update({a: "vl3" for a in _VL3_ARCHS})
    out["KimiK25ForConditionalGeneration"] = "kimi"
    out.update({a: "hybrid" for a in _HYBRID_ARCHS})
    out.update({a: "nemotron_h" for a in _NEMOTRON_H_ARCHS})
    out.update({a: "cohere2_moe" for a in _COHERE2_MOE_ARCHS})
    out.update({a: "falcon_h1" for a in _FALCON_H1_ARCHS})
    out.update({a: "lfm2_moe" for a in _LFM2_MOE_ARCHS})
    return out
