"""Model configuration, parsed from HF config.json dicts.

The reference threads serving decisions through the HF config object
(/root/reference/gllm/model_loader.py:188-334 propagate_*). We instead parse
into one frozen dataclass that the functional model code closes over — every
field is static at trace time, which is what jit wants.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple


class Mup(NamedTuple):
    """Falcon-H1's muP multipliers, by where the forward pass applies them
    (models/falcon_h1.py; ``lm_head_multiplier`` is ``logit_scale``):
    the embedding's rows; k after its projection; the normed stream into
    the attention and the state-space branch and their outputs; one
    factor a channel of the in-projection's z | x | B | C | dt; the MLP's
    gate before its activation and its output."""
    embedding: float = 1.0
    key: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: Tuple[float, float, float, float, float] = (1.0,) * 5
    mlp: Tuple[float, float] = (1.0, 1.0)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    architecture: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    max_position: int = 8192
    tie_word_embeddings: bool = False
    attention_bias: bool = False      # qwen2-style qkv bias
    qk_norm: bool = False             # qwen3-style per-head q/k RMSNorm
    partial_rotary_factor: float = 1.0  # GLM: rotate only this prefix of D
    rope_interleaved: bool = False    # GLM/DeepSeek pair-interleaved layout
    sandwich_norms: bool = False      # GLM4 post_self_attn/post_mlp norms
    # int, tuple of ints, or None. Checkpoints like GLM4 / Llama-3 declare
    # several terminators (reference llm_engine.py finish_tokens treats
    # eos_token_id as a list); use ``eos_token_ids`` for finish checks.
    eos_token_id: Any = None
    bos_token_id: Optional[int] = None

    @property
    def eos_token_ids(self) -> Tuple[int, ...]:
        v = self.eos_token_id
        if v is None:
            return ()
        if isinstance(v, (list, tuple)):
            return tuple(v)
        return (v,)
    hidden_act: str = "silu"
    # MoE fields (0 experts → dense). See gllm_tpu/models/moe.py.
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    norm_topk_prob: bool = True
    # Set by the DP runner: route MoE through the dense masked path (the
    # ragged grouped GEMM doesn't batch under vmap).
    moe_force_dense: bool = False
    # Set by the runner when cache.kv_cache_dtype == "int8": the paged
    # KV cache stores int8 payload + per-page per-head f32 scales
    # (dense.init_kv_cache / ops/kv_cache.write_kv_quant). Spec builders
    # (parallel/shardings.kv_cache_specs) read it so the spec pytree
    # mirrors the cache's scale leaves.
    kv_cache_quant: bool = False
    # Set by the runner when cache.kv_cache_dtype == "fp8": a DSA model's
    # index keys then ride per-token f32 scales, a leaf of the cache that
    # the spec builders have to mirror (parallel/shardings.latent_kv_specs)
    kv_cache_fp8: bool = False
    decoder_sparse_step: int = 1      # every Nth layer is MoE (qwen2-moe)
    mlp_only_layers: Tuple[int, ...] = ()
    shared_expert_intermediate_size: int = 0

    # MLA (DeepSeek V2/V3 — reference models/deepseek_v2.py)
    q_lora_rank: int = 0              # 0 → direct q projection (V2-Lite)
    kv_lora_rank: int = 0             # > 0 enables MLA latent cache
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # DeepSeek MoE routing
    first_k_dense_replace: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    n_group: int = 0
    topk_group: int = 0
    scoring_func: str = "softmax"     # softmax (V2) | sigmoid (V3)
    # greedy | none (the plain top-k of all experts) |
    # group_limited_greedy | noaux_tc (V3: group limit on bias-corrected
    # scores). Only the last two have a group limit, whatever ``n_group``
    # says (skt/A.X-K1 states n_group 8 beside topk_method "none")
    topk_method: str = "greedy"
    # what ``norm_topk_prob`` adds to the chosen scores' sum before it
    # divides by it: a constant of the published model (DeepSeek's 1e-20;
    # lfm2_moe's 1e-6)
    route_norm_eps: float = 1e-20

    @property
    def route_groups(self) -> int:
        """Groups the router limits its choice to ``topk_group`` of
        (0 = no limit: the top-k is over all experts)."""
        if (self.topk_method in ("group_limited_greedy", "noaux_tc")
                and self.n_group and self.topk_group
                and self.topk_group < self.n_group):
            return self.n_group
        return 0

    @property
    def use_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def mla_cache_width(self) -> int:
        """Latent-row width PADDED to the 128-lane Mosaic tile so the
        Pallas kernels can DMA pages (576 → 640 for DeepSeek V3; the pad
        lanes stay zero and q is zero-padded to match, so scores are
        unchanged on every path)."""
        width = self.kv_lora_rank + self.qk_rope_head_dim
        return width + (-width) % 128

    # DeepSeek V3.2 sparse attention (DSA — reference deepseek_v32.py):
    # lightning indexer scoring + top-k physical-slot selection.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # score the indexer with fp8 operands where its keys are cached in fp8
    # (the reference's GLLM_DSA_FP8_SCORE); a key of config.json, never an
    # environment read: it decides numerics
    index_fp8_score: bool = False

    @property
    def use_dsa(self) -> bool:
        return self.index_topk > 0 and self.index_n_heads > 0

    @property
    def dense_mla(self) -> bool:
        """Latent attention over the WHOLE context in every layer (no
        indexer's selection, no windowed layers): the one kind that hands
        its attention to ``paged_attention``, one KV head under all query
        heads."""
        return self.use_mla and not (self.use_dsa or self.use_swa)

    # Windowed layers beside the full ones (``layer_types`` marks a layer
    # "sliding_attention"): they attend the last ``sliding_window``
    # tokens, the current one counted. Two families have them, and they
    # keep those layers' rows differently:
    # - windowed LATENT layers (dots3_note, models/deepseek.py) have a
    #   latent geometry of their own (the ``swa_*`` keys of the config), a
    #   rotary base of their own and no indexer, and keep of a sequence a
    #   RING of the window's last rows, never pages (``use_swa``);
    # - windowed GQA layers (cohere2_moe, models/cohere2_moe.py) share the
    #   full layers' geometry and keep their rows in the one paged pool
    #   under the one page table, behind the window too
    #   (``has_windowed_layers`` without ``use_swa``): the kernels skip
    #   the pages behind the window, the prefix cache stays.
    sliding_window: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    # "headwise": out = concat_h(sigmoid(u W_g)_h o_h) W_o, one scalar a
    # head ("" = no gate)
    attn_gate: str = ""
    swa_attn_gate: str = ""
    # c_q, c_kv scaled by sqrt(hidden / rank) after their norms
    mla_lora_rescale: bool = False
    # Expert parallelism as one chip's share: the router is
    # ``num_experts`` wide and chooses among all of them; this process
    # holds ``experts_held`` of them from ``expert_first`` on and computes
    # their part of the layer (0 = all of them: the layer is whole).
    experts_held: int = 0
    expert_first: int = 0

    @property
    def has_windowed_layers(self) -> bool:
        """``layer_types`` names windowed layers, however their rows are
        kept."""
        return "sliding_attention" in self.layer_types

    @property
    def use_swa(self) -> bool:
        """The windowed layers keep RINGS: a sequence holds, in each of
        them, a slot of the window's last rows and nothing older (the
        windowed latent layers of models/deepseek.py). What needs a
        sequence's rows again after the fact is refused for these
        (engine/llm.refuse_for_rings). A windowed GQA model keeps pages
        (models/cohere2_moe.py) and is no ``use_swa`` model."""
        return self.has_windowed_layers and self.use_mla

    @property
    def paged_windows(self) -> bool:
        """The windowed layers keep their rows in the paged pool beside
        the full layers', under the one page table."""
        return self.has_windowed_layers and not self.use_swa

    @property
    def num_local_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def swa_cache_width(self) -> int:
        """A windowed layer's latent row as stored: whole lanes, as
        ``mla_cache_width`` (1088 -> 1152 for dots3_note)."""
        width = self.swa_kv_lora_rank + self.swa_qk_rope_head_dim
        return width + (-width) % 128

    def swa_ring_len(self, page_size: int) -> int:
        """Rows of one sequence's ring in a windowed layer:
        ``ceil(window / page) + 1`` pages, so a whole window is always
        there beside the row being written."""
        return (-(-self.sliding_window // page_size) + 1) * page_size

    @property
    def num_swa_layers(self) -> int:
        return sum(1 for t in self.stage_layer_types
                   if t == "sliding_attention")

    # Multimodal (Qwen-VL family — reference models/qwen2_5_vl.py,
    # rotary_embedding.py:607-706). mrope_section sums to rot_dim/2;
    # vision_config is the raw HF vision sub-config dict, parsed by
    # gllm_tpu/models/vision.py.
    mrope_section: Tuple[int, ...] = ()
    # Qwen3-VL: frequency-interleaved [THTHW...] mrope layout instead of
    # chunked [T|H|W] sections (HF apply_interleaved_mrope).
    mrope_interleaved: bool = False
    image_token_id: int = -1
    video_token_id: int = -1
    vision_config: Optional[Dict[str, Any]] = None
    # Qwen3-VL deepstack: the ViT emits (1 + n) stacked features per visual
    # token; level i is added to the LM hidden stream after layer i
    # (reference qwen3_vl.py:436-469 Qwen3LLMModel deepstack injection).
    deepstack_num_levels: int = 0
    # Qwen3-VL videos: each temporal frame is its own vision span with a
    # timestamp text run between frames; grids are normalized to t=1
    # per-frame items (HF get_rope_index splits video_grid_thw the same way).
    mm_per_frame_video: bool = False

    @property
    def use_mm(self) -> bool:
        return self.vision_config is not None

    @property
    def mm_embed_dim(self) -> int:
        """Width of one spliced visual row ([main ‖ deepstack levels])."""
        return self.hidden_size * (1 + self.deepstack_num_levels)

    # Hybrid linear-attention (Qwen3-Next / Qwen3.5 — reference
    # models/qwen3_5.py). layer_types marks each layer "linear_attention"
    # or "full_attention".
    layer_types: Tuple[str, ...] = ()
    linear_num_value_heads: int = 0
    linear_num_key_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # What the hybrid decoder reads its block shape from (models/hybrid.py).
    # The defaults are the Qwen3-Next block; ``olmo_hybrid`` sets the other
    # value of each (from_hf_config):
    # beta = 2 sigmoid(.) in (0, 2): the transition I - beta k k^T may have
    # a negative eigenvalue (fla's allow_neg_eigval)
    linear_allow_neg_eigval: bool = False
    # x = x + norm(mixer(x)): the norm is on each sublayer's OUTPUT (OLMo
    # 2/3 ordering), not on its input
    norm_after: bool = False
    # one RMSNorm over the whole q / k projection before the split into
    # heads (OLMo), not one per head over head_dim
    qk_norm_full: bool = False
    # sigmoid output gate on full attention (q_proj twice as wide)
    attn_output_gate: bool = True
    # False where the config gives no rope_theta: the full-attention layers
    # carry no rotary embedding
    use_rope: bool = True
    # in_proj_qkvz / in_proj_ba interleaved per key-head group (Qwen3-Next)
    # or plain [q | k | v | z], [b | a] blocks (separate fla projections)
    gdn_grouped_proj: bool = True
    # RMSNorm weights stored zero-centred: forward scales by (1 + weight)
    norm_zero_centered: bool = True

    # Mamba-2 state-space layers (NemotronH's ``M`` blocks; layer_types
    # marks them "mamba", its expert blocks "moe"; Falcon-H1's layers,
    # "parallel_hybrid": the Mamba-2 heads and the attention heads read one
    # normed stream side by side, so a layer owns pages AND a slot and
    # counts as both kinds): ``mamba_num_heads``
    # heads of ``mamba_head_dim`` channels over a state of
    # ``ssm_state_size``, B and C shared by the heads of each of
    # ``mamba_n_groups`` groups, the chunked rule in chunks of
    # ``mamba_chunk_size`` tokens. ``time_step_*`` are the published
    # initialiser's (dummy weights draw ``dt_bias`` from them).
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the form of an expert: "swiglu" (silu(x W_g) * x W_u) W_d, three
    # matrices, or "relu2" relu(x W_u)^2 W_d, two
    expert_act: str = "swiglu"

    # Cohere's block (cohere2_moe, models/cohere2_moe.py): every norm is a
    # mean-subtracting LayerNorm without bias ("layer"; "rms" elsewhere),
    # the logits take ``logit_scale``, and the ``num_shared_experts``
    # shared experts' outputs are averaged
    norm_kind: str = "rms"
    logit_scale: float = 1.0
    # Falcon-H1 (falcon_h1, models/falcon_h1.py): the published muP
    # multipliers of the forward pass (None elsewhere)
    mup: Optional[Mup] = None

    @property
    def use_mamba(self) -> bool:
        return ("mamba" in self.layer_types
                or "parallel_hybrid" in self.layer_types)

    @property
    def use_short_conv(self) -> bool:
        """Gated short-convolution layers (lfm2_moe's "conv" layers,
        ops/short_conv.py): a layer's whole state is its convolution's
        window, ``linear_conv_kernel_dim - 1`` rows of the hidden size."""
        return "conv" in self.layer_types

    @property
    def use_hybrid(self) -> bool:
        """A layer of this model holds per-sequence state in the slot
        pool (``ssm_slot_shapes`` says what a slot of it stores):
        Gated-DeltaNet or Mamba-2 layers' RECURRENT state, or a gated
        short convolution's window alone. The one test every fence of the
        runner, the pp runner and the engine reads."""
        return ("linear_attention" in self.layer_types or self.use_mamba
                or self.use_short_conv)

    @property
    def ssm_chunked_rule(self) -> bool:
        """Do this model's slot-pool layers run rows of more than one
        token through a chunked rule in the packed layout of whole chunks
        (ops/gdn.gdn_chunk_slots)? Gated-DeltaNet and Mamba-2 do; a short
        convolution reads a token's predecessors over the flat token axis
        and has no such layout. The scheduler's cap on multi-token rows,
        ``BatchBuilder.shape_signature``'s choice of bucket and the
        runner's allowance for the rule's temporaries read this."""
        return self.use_hybrid and not self.use_short_conv

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def ssm_slot_shapes(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """What one slot of one recurrent layer stores, float32: (the
        convolution's window [taps - 1, channels], the recurrent state
        [heads or groups of heads, rows, lanes], or () where a layer keeps
        none: a gated short convolution). A Mamba-2 state is a
        head's own [head_dim, state]; the Gated-DeltaNet states lie g
        heads abreast, [Nv / g, Dk, g x Dv] (``ops/gdn.pack_state``: g =
        2 and 384 lanes at Olmo-Hybrid's 192, g = 1 at Qwen3-Next's
        128), so that the lanes are whole 128-lane tiles and the TPU
        stores the elements alone."""
        taps = self.linear_conv_kernel_dim - 1
        if self.use_short_conv:
            # the window is the whole state: no second shape
            return ((taps, self.hidden_size), ())
        if self.use_mamba:
            return ((taps, self.gdn_conv_dim),
                    (self.mamba_num_heads, self.mamba_head_dim,
                     self.ssm_state_size))
        from gllm_tpu.ops.gdn import gdn_heads_abreast
        Nv, Dv = self.linear_num_value_heads, self.linear_value_head_dim
        g = gdn_heads_abreast(Nv, Dv)
        return ((taps, self.gdn_conv_dim),
                (Nv // g, self.linear_key_head_dim, g * Dv))

    @property
    def ssm_chunk(self) -> int:
        """Tokens in a chunk of the recurrent layers' chunked rule (the
        packed layout of a mixed step: ops/gdn.gdn_chunk_slots); 0 where
        they have no chunked rule (``ssm_chunked_rule``)."""
        from gllm_tpu.ops.gdn import GDN_CHUNK
        if self.use_short_conv:
            return 0
        return self.mamba_chunk_size if self.use_mamba else GDN_CHUNK

    @property
    def use_seq_slots(self) -> bool:
        """Does a sequence hold a slot of per-sequence state beside its
        pages (recurrent state, or a windowed layer's ring)?"""
        return self.use_hybrid or self.use_swa

    @property
    def stage_layer_types(self) -> Tuple[str, ...]:
        """layer_types restricted to this PP stage's layer range."""
        a, b = self.stage_layers
        return self.layer_types[a:b]

    @property
    def num_attn_layers(self) -> int:
        """Layers OWNED BY THIS STAGE (= the whole model when un-staged)
        that keep rows in the paged KV pool: the full-attention layers,
        and a "parallel_hybrid" layer, which counts here AND among
        ``num_linear_layers`` (it owns pages and a slot). Sizes the
        stage's paged-KV stack."""
        if not self.layer_types:
            return self.num_stage_layers
        return sum(1 for t in self.stage_layer_types
                   if t in ("full_attention", "parallel_hybrid"))

    @property
    def kv_cache_heads(self) -> int:
        """KV heads the hybrid decoder's paged cache holds per token: a
        head count above 8 that is no multiple of 8 (Olmo-Hybrid: 30) is
        rounded up to one. The TPU stores the cache's [heads, head_dim]
        face in tiles of 8 (16 in bf16) rows anyway, so the zero heads
        take no memory that was free, and the Pallas kernels' page DMA
        refuses a slice that cuts a tile (tests/test_tpu_compile.py)."""
        h = self.num_kv_heads
        return h if h <= 8 or h % 8 == 0 else h + (-h) % 8

    @property
    def num_linear_layers(self) -> int:
        """Layers of this stage that hold a slot of the pool."""
        return sum(1 for t in self.stage_layer_types
                   if t in ("linear_attention", "mamba", "parallel_hybrid",
                            "conv"))

    @property
    def num_moe_layers(self) -> int:
        return sum(1 for t in self.stage_layer_types if t == "moe")

    @property
    def gdn_conv_dim(self) -> int:
        """Channels of a slot-pool layer's short convolution."""
        if self.use_short_conv:
            return self.hidden_size
        if self.use_mamba:
            return (self.mamba_d_inner
                    + 2 * self.mamba_n_groups * self.ssm_state_size)
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    # Pipeline-parallel stage slice (rank-aware model construction like the
    # reference's per-stage layer builds, qwen2.py:186-270). Full model by
    # default.
    first_layer: int = 0
    last_layer: int = -1              # exclusive; -1 → num_layers

    @property
    def stage_layers(self) -> Tuple[int, int]:
        last = self.num_layers if self.last_layer < 0 else self.last_layer
        return (self.first_layer, last)

    @property
    def num_stage_layers(self) -> int:
        a, b = self.stage_layers
        return b - a

    @property
    def is_first_stage(self) -> bool:
        return self.first_layer == 0

    @property
    def is_last_stage(self) -> bool:
        return self.stage_layers[1] == self.num_layers


def _first_eos(v) -> Optional[int]:
    if isinstance(v, list):
        return v[0] if v else None
    return v


def _eos_tuple(v) -> Optional[Tuple[int, ...]]:
    if v is None:
        return None
    if isinstance(v, (list, tuple)):
        return tuple(v) or None
    return (v,)


# a config.json that names its model_type and no architecture
_ARCH_OF_MODEL_TYPE = {"olmo_hybrid": "OlmoHybridForCausalLM",
                       "dots3_note": "Dots3NoteForCausalLM",
                       "axk1": "AXK1ForCausalLM",
                       "nemotron_h": "NemotronHForCausalLM",
                       "cohere2_moe": "Cohere2MoeForCausalLM",
                       "falcon_h1": "FalconH1ForCausalLM",
                       "lfm2_moe": "Lfm2MoeForCausalLM"}

# hybrid_override_pattern's letters (NemotronH)
_NEMOTRON_KINDS = {"M": "mamba", "E": "moe", "*": "full_attention"}


def _cohere2_moe(hf: Dict[str, Any]):
    """config.json of CohereLabs/command-a-plus-05-2026 (model_type
    cohere2_moe) -> (the keys ``from_hf_config`` reads, the extra fields).
    A parallel block over ONE LayerNorm; GQA whose "sliding_attention"
    layers carry rotary embedding (pairs (2i, 2i+1): ``rope_gptj``) and a
    window and whose "full_attention" layers carry no position at all; a
    sigmoid router's plain top-k over gated SiLU experts of
    ``intermediate_size`` and ``num_shared_experts`` shared ones, averaged
    (served as one shared expert of their widths on end, times 1 / n)."""
    unserved = [
        ("use_parallel_block", True), ("use_gated_activation", True),
        ("use_qk_norm", False), ("first_k_dense_replace", 0),
        ("attention_bias", False), ("rotary_pct", 1),
        ("position_embedding_type", "rope_gptj"),
        ("expert_selection_fn", "sigmoid"), ("hidden_act", "silu"),
        ("shared_expert_combination_strategy", "average")]
    bad = [f"{k}={hf[k]!r}" for k, want in unserved
           if k in hf and hf[k] != want]
    types = tuple(hf.get("layer_types") or ())
    if len(types) != hf["num_hidden_layers"] or set(types) - {
            "sliding_attention", "full_attention"}:
        bad.append(f"layer_types={types!r} for "
                   f"{hf['num_hidden_layers']} layers")
    if bad:
        raise ValueError(
            "cohere2_moe: models/cohere2_moe.py serves the published "
            "block (" + ", ".join(f"{k}={w!r}" for k, w in unserved)
            + "), not " + ", ".join(bad))
    rope = hf.get("rope_parameters") or {}
    inter, shared = hf["intermediate_size"], hf.get("num_shared_experts", 0)
    extra = dict(layer_types=types,
                 sliding_window=hf.get("sliding_window", 0) or 0,
                 norm_kind="layer", logit_scale=hf.get("logit_scale", 1.0))
    hf = {**hf,
          "rms_norm_eps": hf.get("layer_norm_eps", 1e-5),
          "rope_theta": rope.get("rope_theta", hf.get("rope_theta", 1e4)),
          "rope_scaling": None,
          "moe_intermediate_size": inter,
          "n_shared_experts": shared,
          "shared_expert_intermediate_size": shared * inter,
          "scoring_func": "sigmoid", "topk_method": "none",
          "routed_scaling_factor": 1.0}
    return hf, extra


def _falcon_h1(hf: Dict[str, Any]):
    """config.json of tiiuae/Falcon-H1-34B-Instruct (model_type falcon_h1)
    -> (the keys ``from_hf_config`` reads, the extra fields). Every layer
    is the same block: attention heads (GQA, rotary over the whole head,
    halves rotated) and Mamba-2 heads read ONE RMSNorm of the stream side
    by side, a SwiGLU MLP behind a second norm; muP multipliers at
    fourteen places. ``mamba_d_ssm`` = heads x head size is d_inner
    (``mamba_expand`` and ``mlp_expansion_factor`` are inert keys)."""
    unserved = [
        ("attn_layer_indices", None), ("mamba_use_mlp", True),
        ("mamba_rms_norm", True), ("mamba_norm_before_gate", False),
        ("mamba_conv_bias", True), ("mamba_proj_bias", False),
        ("attention_bias", False), ("mlp_bias", False),
        ("projectors_bias", False), ("hidden_act", "silu"),
        ("rope_scaling", None), ("tie_word_embeddings", False)]
    bad = [f"{k}={hf[k]!r}" for k, want in unserved
           if k in hf and hf[k] != want]
    heads, size = hf["mamba_n_heads"], hf["mamba_d_head"]
    if hf.get("mamba_d_ssm", heads * size) != heads * size:
        bad.append(f"mamba_d_ssm={hf['mamba_d_ssm']} for {heads} heads "
                   f"of {size}")
    if bad:
        raise ValueError(
            "falcon_h1: models/falcon_h1.py serves the published block ("
            + ", ".join(f"{k}={w!r}" for k, w in unserved)
            + ", mamba_d_ssm = mamba_n_heads x mamba_d_head), not "
            + ", ".join(bad))
    extra = dict(
        layer_types=("parallel_hybrid",) * hf["num_hidden_layers"],
        mamba_num_heads=heads, mamba_head_dim=size,
        ssm_state_size=hf["mamba_d_state"],
        mamba_n_groups=hf.get("mamba_n_groups", 1),
        mamba_chunk_size=hf.get("mamba_chunk_size", 128),
        linear_conv_kernel_dim=hf.get("mamba_d_conv", 4),
        logit_scale=hf.get("lm_head_multiplier", 1.0),
        mup=Mup(
            embedding=hf.get("embedding_multiplier", 1.0),
            key=hf.get("key_multiplier", 1.0),
            attention_in=hf.get("attention_in_multiplier", 1.0),
            attention_out=hf.get("attention_out_multiplier", 1.0),
            ssm_in=hf.get("ssm_in_multiplier", 1.0),
            ssm_out=hf.get("ssm_out_multiplier", 1.0),
            ssm=tuple(hf.get("ssm_multipliers") or (1.0,) * 5),
            mlp=tuple(hf.get("mlp_multipliers") or (1.0, 1.0))),
        attn_output_gate=False, norm_zero_centered=False)
    # the published base is the integer 100000000000, more than 32 bits
    return {**hf, "rope_theta": float(hf.get("rope_theta", 1e4))}, extra


def _lfm2_moe(hf: Dict[str, Any]):
    """config.json of LiquidAI/LFM2-24B-A2B (model_type lfm2_moe) -> (the
    keys ``from_hf_config`` reads, the extra fields). A layer is an
    operator and a feed-forward, each behind its RMSNorm: the operator a
    gated short convolution ("conv": ``conv_L_cache`` taps, no bias, no
    activation) or GQA with a per-head q / k norm and rotary embedding
    over the whole head; the feed-forward a SwiGLU of ``intermediate_size``
    in the first ``num_dense_layers`` layers and, behind them,
    ``num_experts`` routed experts of ``moe_intermediate_size`` under a
    sigmoid router whose choice is corrected by ``expert_bias``
    (``use_expert_bias``), no shared expert. The row gives no ``head_dim``
    (hidden / heads) and no key for the tied head (the family's
    default)."""
    unserved = [("conv_bias", False), ("norm_topk_prob", True),
                ("hidden_act", "silu"), ("attention_bias", False)]
    bad = [f"{k}={hf[k]!r}" for k, want in unserved
           if k in hf and hf[k] != want]
    types = tuple(hf.get("layer_types") or ())
    if len(types) != hf["num_hidden_layers"] or set(types) - {
            "conv", "full_attention"}:
        bad.append(f"layer_types={types!r} for "
                   f"{hf['num_hidden_layers']} layers")
    if bad:
        raise ValueError(
            "lfm2_moe: models/lfm2_moe.py serves the published block ("
            + ", ".join(f"{k}={w!r}" for k, w in unserved)
            + ', layers "conv" | "full_attention"), not ' + ", ".join(bad))
    rope = hf.get("rope_parameters") or {}
    extra = dict(layer_types=types,
                 linear_conv_kernel_dim=hf.get("conv_L_cache", 3),
                 route_norm_eps=1e-6, attn_output_gate=False,
                 norm_zero_centered=False)
    hf = {**hf,
          "rms_norm_eps": hf.get("norm_eps", 1e-5),
          "rope_theta": float(rope.get("rope_theta",
                                       hf.get("rope_theta", 1e6))),
          "rope_scaling": None,
          "first_k_dense_replace": hf.get("num_dense_layers", 0),
          "tie_word_embeddings": hf.get(
              "tie_word_embeddings", hf.get("tie_embedding", True)),
          "scoring_func": "sigmoid",
          "topk_method": ("noaux_tc" if hf.get("use_expert_bias", True)
                          else "none")}
    return hf, extra


def from_hf_config(hf: Dict[str, Any]) -> ModelConfig:
    """Parse an HF config.json dict into a ModelConfig."""
    arch = (hf.get("architectures")
            or (hf.get("text_config") or {}).get("architectures")
            or [_ARCH_OF_MODEL_TYPE.get(hf.get("model_type"),
                                        "LlamaForCausalLM")])[0]
    extra: Dict[str, Any] = {}
    if arch in ("Qwen3VLForConditionalGeneration",
                "Qwen3VLMoeForConditionalGeneration"):
        vision = hf.get("vision_config") or {}
        text = dict(hf.get("text_config") or hf)
        rope_scaling = text.get("rope_scaling") or {}
        extra = dict(
            mrope_section=tuple(rope_scaling.get("mrope_section", ())),
            mrope_interleaved=True,
            image_token_id=hf.get("image_token_id",
                                  text.get("image_token_id", -1)),
            video_token_id=hf.get("video_token_id",
                                  text.get("video_token_id", -1)),
            vision_config=vision,
            deepstack_num_levels=len(
                vision.get("deepstack_visual_indexes", ())),
            mm_per_frame_video=True,
        )
        if rope_scaling.get("type") == "mrope" \
                or rope_scaling.get("rope_type") == "mrope":
            text["rope_scaling"] = None
        hf = {**text, "architectures": [arch],
              "eos_token_id": hf.get("eos_token_id",
                                     text.get("eos_token_id"))}
    if arch in ("ChatGLMModel", "ChatGLMForConditionalGeneration"):
        # ChatGLM3 legacy config layout (reference models/chatglm.py):
        # kv_channels=head_dim, rotary over head_dim/2 interleaved
        # (RotaryEmbedding(..., is_neox_style=False)), fused
        # query_key_value / dense_h_to_4h handled by chatglm_rules.
        n_heads = hf["num_attention_heads"]
        hf = {
            "architectures": [arch],
            "vocab_size": hf["padded_vocab_size"],
            "hidden_size": hf["hidden_size"],
            "num_hidden_layers": hf["num_layers"],
            "num_attention_heads": n_heads,
            "num_key_value_heads": (hf.get("multi_query_group_num", n_heads)
                                    if hf.get("multi_query_attention", False)
                                    else n_heads),
            "head_dim": hf.get("kv_channels",
                               hf["hidden_size"] // n_heads),
            "intermediate_size": hf["ffn_hidden_size"],
            "rms_norm_eps": hf.get("layernorm_epsilon", 1e-5),
            "rope_theta": 10000.0 * hf.get("rope_ratio", 1.0),
            "max_position_embeddings": hf.get("seq_length", 8192),
            "attention_bias": bool(hf.get("add_qkv_bias", False)
                                   or hf.get("add_bias_linear", False)),
            "partial_rotary_factor": 0.5,
            "tie_word_embeddings": False,
            "eos_token_id": hf.get("eos_token_id"),
        }
    if arch == "KimiK25ForConditionalGeneration":
        # DeepSeek-V3 backbone under text_config; vision dict + the media
        # placeholder (often OUTSIDE the LM vocab) at top level. Positions
        # are plain 1-D — no mrope (reference kimi_k25.py).
        vision = dict(hf.get("vision_config") or {})
        text = dict(hf.get("text_config") or hf)
        extra = dict(
            image_token_id=hf.get("media_placeholder_token_id", -1),
            vision_config=vision,
        )
        hf = {**text, "architectures": [arch],
              "eos_token_id": hf.get("eos_token_id",
                                     text.get("eos_token_id"))}
    if arch in ("Qwen2_5_VLForConditionalGeneration",
                "Qwen2VLForConditionalGeneration"):
        # VL configs nest the LM under text_config (newer transformers) or
        # keep it flat (older checkpoints); vision is always a sub-dict.
        vision = hf.get("vision_config") or {}
        text = dict(hf.get("text_config") or hf)
        rope_scaling = text.get("rope_scaling") or {}
        extra = dict(
            mrope_section=tuple(rope_scaling.get("mrope_section", ())),
            image_token_id=hf.get("image_token_id",
                                  text.get("image_token_id", -1)),
            video_token_id=hf.get("video_token_id",
                                  text.get("video_token_id", -1)),
            vision_config=vision,
        )
        # mrope tables are plain rope tables; drop the marker type so the
        # table builder doesn't choke, keep the section split in extra.
        if rope_scaling.get("type") == "mrope" \
                or rope_scaling.get("rope_type") == "mrope":
            text["rope_scaling"] = None
        hf = {**text, "architectures": [arch],
              "eos_token_id": hf.get("eos_token_id",
                                     text.get("eos_token_id"))}
    if arch in ("Qwen3NextForCausalLM", "Qwen3_5ForCausalLM",
                "Qwen3_5MoeForCausalLM", "Qwen3_5ForConditionalGeneration",
                "Qwen3_5MoeForConditionalGeneration"):
        # Real Qwen3.5 checkpoints use the *ForConditionalGeneration arch
        # string and may nest the LM under text_config (reference reads
        # attrs with a text_config fallback, model_loader.py:180-201).
        text = dict(hf.get("text_config") or hf)
        extra = dict(
            layer_types=tuple(text.get("layer_types", ())),
            linear_num_value_heads=text.get("linear_num_value_heads", 0),
            linear_num_key_heads=text.get("linear_num_key_heads", 0),
            linear_key_head_dim=text.get("linear_key_head_dim", 0),
            linear_value_head_dim=text.get("linear_value_head_dim", 0),
            linear_conv_kernel_dim=text.get("linear_conv_kernel_dim", 4),
        )
        hf = {**text, "architectures": [arch],
              "eos_token_id": hf.get("eos_token_id",
                                     text.get("eos_token_id"))}
    if arch == "OlmoHybridForCausalLM":
        # config.json of allenai/Olmo-Hybrid-7B (model_type olmo_hybrid):
        # the linear_* keys are Qwen3-Next's; the block is OLMo 3's
        # (output norms, full-width q/k norm, no gate) and
        # ``rope_parameters.rope_theta: null`` means no rotary embedding
        rope = hf.get("rope_parameters") or {}
        theta = rope.get("rope_theta", hf.get("rope_theta"))
        extra = dict(
            layer_types=tuple(hf.get("layer_types", ())),
            linear_num_value_heads=hf.get("linear_num_value_heads", 0),
            linear_num_key_heads=hf.get("linear_num_key_heads", 0),
            linear_key_head_dim=hf.get("linear_key_head_dim", 0),
            linear_value_head_dim=hf.get("linear_value_head_dim", 0),
            linear_conv_kernel_dim=hf.get("linear_conv_kernel_dim", 4),
            linear_allow_neg_eigval=bool(
                hf.get("linear_allow_neg_eigval", False)),
            norm_after=True, qk_norm_full=True, attn_output_gate=False,
            use_rope=theta is not None, gdn_grouped_proj=False,
            norm_zero_centered=False,
        )
        hf = {**hf, "rope_theta": 10000.0 if theta is None else theta}
    if arch == "Dots3NoteForCausalLM":
        # config.json of dots-studio/dots3-note-prev (model_type
        # dots3_note): DeepSeek-V3.2's keys for the full layers, the
        # ``swa_*`` keys for the windowed ones
        extra = dict(
            layer_types=tuple(hf.get("layer_types", ())),
            sliding_window=hf.get("sliding_window_size", 0) or 0,
            swa_num_heads=hf.get("swa_num_attention_heads", 0) or 0,
            swa_q_lora_rank=hf.get("swa_q_lora_rank", 0) or 0,
            swa_kv_lora_rank=hf.get("swa_kv_lora_rank", 0) or 0,
            swa_qk_nope_head_dim=hf.get("swa_qk_nope_head_dim", 0) or 0,
            swa_qk_rope_head_dim=hf.get("swa_qk_rope_head_dim", 0) or 0,
            swa_v_head_dim=hf.get("swa_v_head_dim", 0) or 0,
            swa_rope_theta=hf.get("swa_rope_theta", 10000.0),
            attn_gate=hf.get("attention_gate_type") or "",
            swa_attn_gate=hf.get("swa_attention_gate_type") or "",
            mla_lora_rescale=bool(hf.get("apply_mla_qkv_lora_rescale")),
        )
    if arch == "NemotronHForCausalLM":
        # config.json of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B (model_type
        # nemotron_h): a block is ONE mixer, by the letters of
        # ``hybrid_override_pattern``; attention carries no rotary
        # embedding (``rope_theta`` is an inert key); the expert layer is
        # DeepSeek-V3's router (the row has no ``topk_method``: noaux_tc)
        # over experts of two matrices and relu^2
        pattern = hf["hybrid_override_pattern"]
        bad = sorted(set(pattern) - set(_NEMOTRON_KINDS))
        if bad or len(pattern) != hf["num_hidden_layers"]:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: "
                + (f"no block for {bad} (M, E and * are served; '-' is a "
                   "dense MLP block, which this model does not have)"
                   if bad else f"{len(pattern)} letters for "
                   f"{hf['num_hidden_layers']} layers"))
        if hf.get("mlp_hidden_act", "relu2") != "relu2":
            raise ValueError("NemotronH experts are relu2, not "
                             f"{hf['mlp_hidden_act']!r}")
        extra = dict(
            layer_types=tuple(_NEMOTRON_KINDS[c] for c in pattern),
            mamba_num_heads=hf["mamba_num_heads"],
            mamba_head_dim=hf["mamba_head_dim"],
            ssm_state_size=hf["ssm_state_size"],
            mamba_n_groups=hf.get("n_groups", 1),
            mamba_chunk_size=hf.get("chunk_size", 128),
            linear_conv_kernel_dim=hf.get("conv_kernel", 4),
            time_step_min=hf.get("time_step_min", 0.001),
            time_step_max=hf.get("time_step_max", 0.1),
            time_step_floor=hf.get("time_step_floor", 1e-4),
            expert_act="relu2", use_rope=False, attn_output_gate=False,
            norm_zero_centered=False,
        )
        hf = {**hf, "scoring_func": "sigmoid",
              "topk_method": hf.get("topk_method") or "noaux_tc",
              "rms_norm_eps": hf.get("layer_norm_epsilon",
                                     hf.get("norm_eps", 1e-5)),
              "shared_expert_intermediate_size":
                  hf.get("moe_shared_expert_intermediate_size", 0)}
    if arch == "Cohere2MoeForCausalLM":
        hf, extra = _cohere2_moe(hf)
    if arch == "FalconH1ForCausalLM":
        hf, extra = _falcon_h1(hf)
    if arch == "Lfm2MoeForCausalLM":
        hf, extra = _lfm2_moe(hf)
    share = hf.get("ep_share")
    if share:
        # this repo's own key, read for every family that holds a share of
        # its experts: {"chips", "rank", <count>} where <count> is the
        # config's own key for the number of routed experts
        # (``n_routed_experts``; ``num_experts`` for cohere2_moe and
        # lfm2_moe) says
        # that this key of the config counts the experts HELD here, one
        # of ``chips`` equal shares of the published count
        from gllm_tpu.models.registry import (_COHERE2_MOE_ARCHS,
                                              _LFM2_MOE_ARCHS, _MLA_ARCHS,
                                              _NEMOTRON_H_ARCHS)
        own_count = _COHERE2_MOE_ARCHS + _LFM2_MOE_ARCHS
        if arch not in _MLA_ARCHS + _NEMOTRON_H_ARCHS + own_count:
            raise ValueError(f"ep_share: {arch} holds no share of its "
                             "experts (the families of models/deepseek.py,"
                             " nemotron_h.py, cohere2_moe.py and "
                             "lfm2_moe.py do)")
        count = "num_experts" if arch in own_count else "n_routed_experts"
        held = hf[count]
        if share[count] != held * share["chips"]:
            raise ValueError(
                f"ep_share: {share['chips']} chips x {held} experts "
                f"held are not the {share[count]} published")
        extra.update(experts_held=held,
                     expert_first=held * share.get("rank", 0))
        hf = {**hf, count: share[count]}
    num_heads = hf["num_attention_heads"]
    hidden = hf["hidden_size"]
    head_dim = hf.get("head_dim") or hidden // num_heads
    qk_norm = arch in ("Qwen3ForCausalLM", "Qwen3MoeForCausalLM",
                       "Qwen3NextForCausalLM", "Qwen3_5ForCausalLM",
                       "Qwen3_5MoeForCausalLM", "OlmoHybridForCausalLM",
                       "Lfm2MoeForCausalLM",
                       "Qwen3VLForConditionalGeneration",
                       "Qwen3VLMoeForConditionalGeneration")
    is_glm4 = arch in ("Glm4ForCausalLM",)
    # GLM-4 base / ChatGLM3: interleaved partial rotary like GLM4 but
    # WITHOUT the sandwich norms
    is_glm = arch in ("GlmForCausalLM", "ChatGLMModel",
                      "ChatGLMForConditionalGeneration")
    # HF's Qwen2-family attention is bias=True UNCONDITIONALLY
    # (modeling_qwen2.py nn.Linear(..., bias=True)): the checkpoint
    # always carries q/k/v biases even when config.json says
    # attention_bias=false, so the config key must not be trusted for
    # these archs (a false value would shrink our param template and the
    # loader would reject the checkpoint's bias tensors). The reverse
    # direction is safe: if a nonstandard bias-free export ever omits the
    # tensors, the loader leaves the template's zero biases in place —
    # mathematically identical to no bias.
    if arch in ("Qwen2ForCausalLM", "Qwen2MoeForCausalLM",
                "Qwen2_5_VLForConditionalGeneration",
                "Qwen2VLForConditionalGeneration"):
        attention_bias = True
    else:
        attention_bias = hf.get("attention_bias", False)
    return ModelConfig(
        architecture=arch,
        vocab_size=hf["vocab_size"],
        hidden_size=hidden,
        num_layers=hf["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=head_dim,
        intermediate_size=hf["intermediate_size"],
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        rope_theta=hf.get("rope_theta", 10000.0),
        rope_scaling=hf.get("rope_scaling"),
        max_position=hf.get("max_position_embeddings", 8192),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attention_bias=attention_bias,
        qk_norm=qk_norm,
        partial_rotary_factor=hf.get("partial_rotary_factor", 1.0) or 1.0,
        rope_interleaved=(is_glm4 or is_glm
                          or arch == "Cohere2MoeForCausalLM"),
        sandwich_norms=is_glm4,
        eos_token_id=_eos_tuple(hf.get("eos_token_id")),
        bos_token_id=_first_eos(hf.get("bos_token_id")),
        hidden_act=hf.get("hidden_act", "silu"),
        num_experts=hf.get("num_experts",
                           hf.get("num_local_experts",
                                  hf.get("n_routed_experts", 0)) or 0),
        num_experts_per_tok=hf.get("num_experts_per_tok", 0) or 0,
        moe_intermediate_size=hf.get("moe_intermediate_size", 0) or 0,
        norm_topk_prob=hf.get("norm_topk_prob", True),
        decoder_sparse_step=hf.get("decoder_sparse_step", 1),
        mlp_only_layers=tuple(hf.get("mlp_only_layers", []) or []),
        shared_expert_intermediate_size=hf.get(
            "shared_expert_intermediate_size", 0) or 0,
        q_lora_rank=hf.get("q_lora_rank", 0) or 0,
        kv_lora_rank=hf.get("kv_lora_rank", 0) or 0,
        qk_nope_head_dim=hf.get("qk_nope_head_dim", 0) or 0,
        qk_rope_head_dim=hf.get("qk_rope_head_dim", 0) or 0,
        v_head_dim=hf.get("v_head_dim", 0) or 0,
        first_k_dense_replace=hf.get("first_k_dense_replace", 0) or 0,
        n_shared_experts=hf.get("n_shared_experts", 0) or 0,
        routed_scaling_factor=hf.get("routed_scaling_factor", 1.0) or 1.0,
        n_group=hf.get("n_group", 0) or 0,
        topk_group=hf.get("topk_group", 0) or 0,
        index_n_heads=hf.get("index_n_heads", 0) or 0,
        index_head_dim=hf.get("index_head_dim", 0) or 0,
        index_topk=hf.get("index_topk", 0) or 0,
        index_fp8_score=bool(hf.get("index_fp8_score", False)),
        scoring_func=hf.get("scoring_func", "softmax") or "softmax",
        topk_method=hf.get("topk_method", "greedy") or "greedy",
        **extra,
    )
