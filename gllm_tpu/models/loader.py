"""Checkpoint loading: HF safetensors → stacked jax param pytrees.

TPU-native counterpart of the reference ModelLoader + weight rule tables
(/root/reference/gllm/model_loader.py:337-652,
/root/reference/gllm/models/weight_loader.py): lazy shard-indexed safetensors
reading (no full-checkpoint RAM), first-match-wins name rules per
architecture, PP-stage pruning (only this stage's layers are read), and a
``dummy`` format for weight-less bring-up.

Re-design for the stacked-scan layout: instead of loading into per-module
tensors, each layer's weight lands in row ``i - first_layer`` of a stacked
[L, ...] buffer; HF's [out, in] matmul weights are transposed to [in, out]
once at load time.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gllm_tpu.models.config import ModelConfig, from_hf_config


def resolve_model_path(model: str, allow_download: bool = False,
                       cache_dir: str = None) -> str:
    """Local dir → as-is; HF-hub id → snapshot download behind a flag.

    The reference resolves hub ids with snapshot_download under a file
    lock so concurrent workers don't race the same download
    (model_loader.py hub path). Same here: an fcntl lock per model id in
    the cache dir serializes the fetch; loads stay local-path-only unless
    ``allow_download`` (CLI --allow-hub-download) — this image is
    zero-egress, so downloads must be an explicit opt-in."""
    if os.path.isdir(model):
        return model
    if not allow_download:
        raise ValueError(
            f"model path {model!r} is not a local directory; pass "
            "--allow-hub-download to fetch it from the HF hub")
    import fcntl
    import hashlib
    cache_dir = cache_dir or os.path.join(
        os.path.expanduser("~"), ".cache", "gllm_tpu")
    lock_dir = os.path.join(cache_dir, "locks")
    os.makedirs(lock_dir, exist_ok=True)
    lock_path = os.path.join(
        lock_dir, hashlib.sha256(model.encode()).hexdigest()[:24] + ".lock")
    with open(lock_path, "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        try:
            from huggingface_hub import snapshot_download
            return snapshot_download(model, cache_dir=cache_dir)
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)


def load_hf_config(model_dir: str) -> dict:
    with open(os.path.join(model_dir, "config.json")) as f:
        hf = json.load(f)
    # Checkpoints often declare extra terminators only in
    # generation_config.json (the reference reads it the same way; GLM4 /
    # Llama-3 list several eos ids there). Merge them into the config dict.
    gen_path = os.path.join(model_dir, "generation_config.json")
    if os.path.exists(gen_path):
        try:
            with open(gen_path) as f:
                gen = json.load(f)
        except (OSError, json.JSONDecodeError):
            gen = {}
        ids = []
        for v in (hf.get("eos_token_id"), gen.get("eos_token_id")):
            if v is None:
                continue
            ids.extend(v if isinstance(v, list) else [v])
        if ids:
            hf["eos_token_id"] = list(dict.fromkeys(ids))
    return hf


class LazySafetensors:
    """Shard-indexed lazy tensor access (reference model_loader.py:60-108).

    Opens each shard at most once; tensors are produced on demand so peak
    host memory is one tensor, not one checkpoint.
    """

    def __init__(self, model_dir: str):
        self.model_dir = model_dir
        self._bin = False
        index_path = os.path.join(model_dir, "model.safetensors.index.json")
        single_path = os.path.join(model_dir, "model.safetensors")
        bin_index = os.path.join(model_dir, "pytorch_model.bin.index.json")
        bin_single = os.path.join(model_dir, "pytorch_model.bin")
        if os.path.exists(index_path):
            with open(index_path) as f:
                self.weight_map: Dict[str, str] = json.load(f)["weight_map"]
        elif os.path.exists(single_path):
            from safetensors import safe_open
            with safe_open(single_path, framework="np") as f:
                names = list(f.keys())
            self.weight_map = {n: "model.safetensors" for n in names}
        elif os.path.exists(bin_index) or os.path.exists(bin_single):
            # torch .bin fallback (reference model_loader load_bin path):
            # shards are torch.load-ed lazily (mmap) one at a time.
            self._bin = True
            if os.path.exists(bin_index):
                with open(bin_index) as f:
                    self.weight_map = json.load(f)["weight_map"]
            else:
                import torch
                sd = torch.load(bin_single, map_location="cpu",
                                weights_only=True, mmap=True)
                self.weight_map = {n: "pytorch_model.bin" for n in sd}
                self._open_files = {"pytorch_model.bin": sd}
                return
        else:
            raise FileNotFoundError(
                f"no safetensors or .bin checkpoint in {model_dir}")
        self._open_files: Dict[str, object] = {}

    def names(self) -> Iterator[str]:
        return iter(self.weight_map)

    def _file(self, fname: str):
        if fname not in self._open_files:
            if self._bin:
                import torch
                self._open_files[fname] = torch.load(
                    os.path.join(self.model_dir, fname),
                    map_location="cpu", weights_only=True, mmap=True)
            else:
                from safetensors import safe_open
                self._open_files[fname] = safe_open(
                    os.path.join(self.model_dir, fname), framework="flax")
        return self._open_files[fname]

    def get(self, name: str) -> jnp.ndarray:
        f = self._file(self.weight_map[name])
        if self._bin:
            import torch
            t = f[name]
            if t.dtype == torch.bfloat16:
                return jnp.asarray(t.float().numpy()).astype(jnp.bfloat16)
            return jnp.asarray(t.numpy())
        return f.get_tensor(name)

    def __contains__(self, name: str) -> bool:
        return name in self.weight_map


# A rule maps an HF tensor to (param path, layer index or None, transform).
# transform: "t" = transpose last two dims, None = as-is. MoE expert rules
# extend the index to (layer, expert).
Rule = Tuple[Tuple[str, ...], Optional[object], Optional[str]]


def dense_rules(cfg: ModelConfig) -> Callable[[str], Optional[Rule]]:
    """Name-mapping rules for the dense GQA family (llama/qwen2/qwen3)."""
    first, last = cfg.stage_layers

    proj_map = {
        "self_attn.q_proj.weight": ("q_proj", "t"),
        "self_attn.k_proj.weight": ("k_proj", "t"),
        "self_attn.v_proj.weight": ("v_proj", "t"),
        "self_attn.o_proj.weight": ("o_proj", "t"),
        "self_attn.q_proj.bias": ("q_bias", None),
        "self_attn.k_proj.bias": ("k_bias", None),
        "self_attn.v_proj.bias": ("v_bias", None),
        "self_attn.q_norm.weight": ("q_norm", None),
        "self_attn.k_norm.weight": ("k_norm", None),
        "mlp.gate_proj.weight": ("gate_proj", "t"),
        "mlp.up_proj.weight": ("up_proj", "t"),
        "mlp.down_proj.weight": ("down_proj", "t"),
        "input_layernorm.weight": ("input_norm", None),
        "post_attention_layernorm.weight": ("post_attn_norm", None),
        "post_self_attn_layernorm.weight": ("post_self_attn_norm", None),
        "post_mlp_layernorm.weight": ("post_mlp_norm", None),
    }

    def split_gate_up(t: np.ndarray) -> dict:
        # GLM4 fused [2I, H] gate_up → our separate [H, I] gate/up
        gate, up = np.split(t, 2, axis=0)
        return {"gate_proj": gate.T, "up_proj": up.T}

    def rule(name: str) -> Optional[Rule]:
        if name == "model.embed_tokens.weight":
            return (("embed",), None, None) if cfg.is_first_stage else None
        if name == "model.norm.weight":
            return (("final_norm",), None, None) if cfg.is_last_stage else None
        if name == "lm_head.weight":
            if cfg.is_last_stage and not cfg.tie_word_embeddings:
                return (("lm_head",), None, "t")
            return None
        if name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_s, _, leaf = rest.partition(".")
            i = int(idx_s)
            if not (first <= i < last):
                return None  # other PP stage's layer — skip (EP/PP pruning)
            if leaf == "mlp.gate_up_proj.weight":
                return (("layers", "__multi__"), i - first, split_gate_up)
            if leaf in proj_map:
                target, tf = proj_map[leaf]
                return (("layers", target), i - first, tf)
        return None

    return rule


def skip_visual_rules(rules):
    """Drop every rule targeting the vision tower (disagg LM nodes never
    read visual.* shards — the inverse of the encoder's filter)."""
    def filtered(name):
        r = rules(name)
        return None if (r is not None and r[0][0] == "visual") else r
    return filtered


def _load_params(model_dir: str, template, rules,
                 progress_cb: Optional[Callable[[int, int], None]] = None,
                 skip_visual: bool = False) -> dict:
    """Shared load loop: stream tensors, apply first-match rules, fill the
    stacked host buffers, ship to device once. ``skip_visual`` drops the
    vision-tower subtree entirely (disagg LM nodes: never read or
    allocate visual.* shards)."""
    if skip_visual and "visual" in template:
        template = {k: v for k, v in template.items() if k != "visual"}
        rules = skip_visual_rules(rules)
    host: dict = jax.tree.map(
        lambda s: np.zeros(s.shape, jnp.dtype(s.dtype)), template)
    lazy = LazySafetensors(model_dir)
    names = list(lazy.names())
    total = len(names)
    for n_done, name in enumerate(names):
        r = rules(name)
        if r is None:
            continue
        path, idx, tf = r
        t = np.asarray(lazy.get(name))
        dst = host
        for kpath in path[:-1]:
            dst = dst[kpath]
        if callable(tf):
            # transform expands one HF tensor into several leaves (e.g.
            # DeepSeek kv_b_proj → absorbed w_uk + w_uv)
            for leaf_name, arr in tf(t).items():
                leaf = dst[leaf_name]
                if idx is None:
                    leaf[...] = arr.astype(leaf.dtype)
                else:
                    leaf[idx] = arr.astype(leaf.dtype)
            continue
        if tf == "t":
            t = t.T
        leaf = dst[path[-1]]
        if idx is None:
            leaf[...] = t.astype(leaf.dtype)
        else:  # int (layer) or tuple (layer, expert) index
            leaf[idx] = t.astype(leaf.dtype)
        if progress_cb:
            progress_cb(n_done + 1, total)
    return jax.tree.map(jnp.asarray, host)


def chatglm_rules(cfg: ModelConfig) -> Callable[[str], Optional[Rule]]:
    """ChatGLM3 legacy layout (reference models/chatglm.py): fused
    ``query_key_value`` split by head geometry, fused ``dense_h_to_4h``
    split into gate/up, ``transformer.*`` namespacing."""
    first, last = cfg.stage_layers
    q_rows = cfg.num_heads * cfg.head_dim
    kv_rows = cfg.num_kv_heads * cfg.head_dim

    def split_qkv_w(t: np.ndarray) -> dict:
        q, k, v = np.split(t, [q_rows, q_rows + kv_rows], axis=0)
        return {"q_proj": q.T, "k_proj": k.T, "v_proj": v.T}

    def split_qkv_b(t: np.ndarray) -> dict:
        q, k, v = np.split(t, [q_rows, q_rows + kv_rows], axis=0)
        return {"q_bias": q, "k_bias": k, "v_bias": v}

    def split_gate_up(t: np.ndarray) -> dict:
        gate, up = np.split(t, 2, axis=0)
        return {"gate_proj": gate.T, "up_proj": up.T}

    leaves = {
        "input_layernorm.weight": ("input_norm", None),
        "post_attention_layernorm.weight": ("post_attn_norm", None),
        "self_attention.dense.weight": ("o_proj", "t"),
        "mlp.dense_4h_to_h.weight": ("down_proj", "t"),
    }

    def rule(name: str) -> Optional[Rule]:
        if name == "transformer.embedding.word_embeddings.weight":
            return (("embed",), None, None) if cfg.is_first_stage else None
        if name == "transformer.encoder.final_layernorm.weight":
            return (("final_norm",), None, None) if cfg.is_last_stage \
                else None
        if name == "transformer.output_layer.weight":
            return (("lm_head",), None, "t") if cfg.is_last_stage else None
        if name.startswith("transformer.encoder.layers."):
            rest = name[len("transformer.encoder.layers."):]
            idx_s, _, leaf = rest.partition(".")
            i = int(idx_s)
            if not (first <= i < last):
                return None
            li = i - first
            if leaf == "self_attention.query_key_value.weight":
                return (("layers", "__multi__"), li, split_qkv_w)
            if leaf == "self_attention.query_key_value.bias":
                return (("layers", "__multi__"), li, split_qkv_b)
            if leaf == "mlp.dense_h_to_4h.weight":
                return (("layers", "__multi__"), li, split_gate_up)
            if leaf in leaves:
                target, tf = leaves[leaf]
                return (("layers", target), li, tf)
        return None

    return rule


_CHATGLM_ARCHS = ("ChatGLMModel", "ChatGLMForConditionalGeneration")


def load_dense_params(model_dir: str, cfg: ModelConfig,
                      dtype=jnp.bfloat16,
                      progress_cb: Optional[Callable[[int, int], None]] = None,
                      ) -> dict:
    """Load a dense-family checkpoint into the stacked param layout."""
    from gllm_tpu.models import dense
    template = jax.eval_shape(lambda: dense.init_params(cfg, dtype=dtype))
    rules = (chatglm_rules(cfg) if cfg.architecture in _CHATGLM_ARCHS
             else dense_rules(cfg))
    return _load_params(model_dir, template, rules, progress_cb)


def moe_rules(cfg: ModelConfig) -> Callable[[str], Optional[Rule]]:
    """Rules for Mixtral / Qwen2-MoE / Qwen3-MoE expert layouts
    (reference weight_loader.py MoE w13/w2 pull-based loaders)."""
    base = dense_rules(cfg)
    first, last = cfg.stage_layers
    # leaf name inside one expert → (our leaf, transform)
    expert_leaves = {
        "w1.weight": ("w_gate", "t"), "w3.weight": ("w_up", "t"),
        "w2.weight": ("w_down", "t"),
        "gate_proj.weight": ("w_gate", "t"),
        "up_proj.weight": ("w_up", "t"),
        "down_proj.weight": ("w_down", "t"),
    }
    shared_leaves = {
        "shared_expert.gate_proj.weight": ("shared_gate_proj", "t"),
        "shared_expert.up_proj.weight": ("shared_up_proj", "t"),
        "shared_expert.down_proj.weight": ("shared_down_proj", "t"),
        "shared_expert_gate.weight": ("shared_expert_gate", "t"),
    }

    def rule(name: str) -> Optional[Rule]:
        if name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_s, _, leaf = rest.partition(".")
            i = int(idx_s)
            if not (first <= i < last):
                return None
            li = i - first
            # router: qwen "mlp.gate.weight", mixtral
            # "block_sparse_moe.gate.weight"
            if leaf in ("mlp.gate.weight", "block_sparse_moe.gate.weight"):
                return (("layers", "router"), li, "t")
            for prefix in ("mlp.experts.", "block_sparse_moe.experts."):
                if leaf.startswith(prefix):
                    rest2 = leaf[len(prefix):]
                    e_s, _, el = rest2.partition(".")
                    if el in expert_leaves:
                        target, tf = expert_leaves[el]
                        return (("layers", target), (li, int(e_s)), tf)
            if leaf.startswith("mlp.shared_expert"):
                key = leaf[len("mlp."):]
                if key in shared_leaves:
                    target, tf = shared_leaves[key]
                    return (("layers", target), li, tf)
            return base(name)
        return base(name)

    return rule


def load_moe_params(model_dir: str, cfg: ModelConfig,
                    dtype=jnp.bfloat16,
                    progress_cb: Optional[Callable[[int, int], None]] = None,
                    ) -> dict:
    from gllm_tpu.models import moe
    template = jax.eval_shape(lambda: moe.init_params(cfg, dtype=dtype))
    params = _load_params(model_dir, template, moe_rules(cfg), progress_cb)
    if "moe_mask" in params.get("layers", {}):
        # derived, not a checkpoint tensor — _load_params zero-fills
        # template leaves, which would make every layer dense
        params["layers"]["moe_mask"] = np.asarray(
            moe.moe_layer_mask(cfg), bool)
    return params


def deepseek_rules(cfg: ModelConfig) -> Callable[[str], Optional[Rule]]:
    """DeepSeek V2/V3: MLA projections (kv_b_proj split into absorbed
    W_UK/W_UV at load — reference does this at runtime,
    layers/attention.py:272-293), dense-then-MoE layer groups."""
    first, last = cfg.stage_layers
    k_dense = cfg.first_k_dense_replace
    nope, v, lora = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    Hq = cfg.num_heads

    def split_kv_b(t: np.ndarray) -> dict:
        # t: [Hq*(nope+v), lora] → w_uk [Hq, nope, lora], w_uv [Hq, lora, v]
        m = t.reshape(Hq, nope + v, lora)
        return {"w_uk": m[:, :nope, :],
                "w_uv": m[:, nope:, :].transpose(0, 2, 1)}

    attn_map = {
        "self_attn.q_proj.weight": ("q_proj", "t"),
        "self_attn.q_a_proj.weight": ("q_a_proj", "t"),
        "self_attn.q_a_layernorm.weight": ("q_a_norm", None),
        "self_attn.q_b_proj.weight": ("q_b_proj", "t"),
        "self_attn.kv_a_proj_with_mqa.weight": ("kv_a_proj", "t"),
        "self_attn.kv_a_layernorm.weight": ("kv_a_norm", None),
        "self_attn.o_proj.weight": ("o_proj", "t"),
        "input_layernorm.weight": ("input_norm", None),
        "post_attention_layernorm.weight": ("post_attn_norm", None),
        "mlp.gate_proj.weight": ("gate_proj", "t"),
        "mlp.up_proj.weight": ("up_proj", "t"),
        "mlp.down_proj.weight": ("down_proj", "t"),
        "mlp.shared_experts.gate_proj.weight": ("shared_gate_proj", "t"),
        "mlp.shared_experts.up_proj.weight": ("shared_up_proj", "t"),
        "mlp.shared_experts.down_proj.weight": ("shared_down_proj", "t"),
        # DSA lightning indexer (V3.2, reference deepseek_v32.py:86-233)
        "self_attn.indexer.wq_b.weight": ("idx_wq_b", "t"),
        "self_attn.indexer.wk.weight": ("idx_wk", "t"),
        "self_attn.indexer.k_norm.weight": ("idx_k_norm_w", None),
        "self_attn.indexer.k_norm.bias": ("idx_k_norm_b", None),
        "self_attn.indexer.weights_proj.weight": ("idx_weights", "t"),
    }
    expert_leaves = {
        "gate_proj.weight": ("w_gate", "t"),
        "up_proj.weight": ("w_up", "t"),
        "down_proj.weight": ("w_down", "t"),
    }

    def rule(name: str) -> Optional[Rule]:
        if name == "model.embed_tokens.weight":
            return (("embed",), None, None) if cfg.is_first_stage else None
        if name == "model.norm.weight":
            return (("final_norm",), None, None) if cfg.is_last_stage else None
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
            return None
        group = "dense_layers" if i < k_dense else "moe_layers"
        li = (i - first) if i < k_dense else (i - max(first, k_dense))
        if leaf == "self_attn.kv_b_proj.weight":
            return ((group, "__multi__"), li, split_kv_b)
        if leaf in attn_map:
            target, tf = attn_map[leaf]
            return ((group, target), li, tf)
        if leaf == "mlp.gate.weight":
            return ((group, "router"), li, "t")
        if leaf == "mlp.gate.e_score_correction_bias":
            return ((group, "e_bias"), li, None)
        if leaf.startswith("mlp.experts."):
            rest2 = leaf[len("mlp.experts."):]
            e_s, _, el = rest2.partition(".")
            if el in expert_leaves:
                target, tf = expert_leaves[el]
                return ((group, target), (li, int(e_s)), tf)
        return None

    return rule


def load_deepseek_params(model_dir: str, cfg: ModelConfig,
                         dtype=jnp.bfloat16,
                         progress_cb=None) -> dict:
    from gllm_tpu.models import deepseek
    if cfg.use_swa:
        raise NotImplementedError(
            "no checkpoint rules for a model with windowed latent layers "
            f"({cfg.architecture}): it is served with --load-format dummy")
    template = jax.eval_shape(lambda: deepseek.init_params(cfg, dtype=dtype))
    return _load_params(model_dir, template, deepseek_rules(cfg),
                        progress_cb)


# ---------------------------------------------------------------------------
# EP-pruned / sharding-aware expert loading (reference model_loader.py:363-369
# skips non-local experts per EP rank; here the same property falls out of
# building each device's expert shard directly from the checkpoint)
# ---------------------------------------------------------------------------

# Instrumentation: largest host buffer the EP loader materialized (tests
# bound peak host RSS with it).
ep_load_stats = {"max_chunk_bytes": 0}

# (group, leaf) → HF tensor name format, per family. {i}=global layer,
# {e}=expert id. All expert projections are stored [out, in] → transposed.
_MOE_EXPERT_FMTS = {
    ("layers", "w_gate"): ("model.layers.{i}.mlp.experts.{e}."
                           "gate_proj.weight",
                           "model.layers.{i}.block_sparse_moe.experts."
                           "{e}.w1.weight"),
    ("layers", "w_up"): ("model.layers.{i}.mlp.experts.{e}."
                         "up_proj.weight",
                         "model.layers.{i}.block_sparse_moe.experts."
                         "{e}.w3.weight"),
    ("layers", "w_down"): ("model.layers.{i}.mlp.experts.{e}."
                           "down_proj.weight",
                           "model.layers.{i}.block_sparse_moe.experts."
                           "{e}.w2.weight"),
}
_DEEPSEEK_EXPERT_FMTS = {
    ("moe_layers", "w_gate"): ("model.layers.{i}.mlp.experts.{e}."
                               "gate_proj.weight",),
    ("moe_layers", "w_up"): ("model.layers.{i}.mlp.experts.{e}."
                             "up_proj.weight",),
    ("moe_layers", "w_down"): ("model.layers.{i}.mlp.experts.{e}."
                               "down_proj.weight",),
}

_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def load_params_ep(model_dir: str, cfg: ModelConfig, dtype, mesh, specs,
                   family: str,
                   progress_cb: Optional[Callable[[int, int], None]] = None,
                   ) -> dict:
    """Load an MoE checkpoint with expert stacks built shard-by-shard.

    Non-expert weights stream through the normal rule loop. Expert stacks
    ([L, E, in, out], sharded on the expert axis) are assembled via
    ``jax.make_array_from_callback``: jax asks for each device's shard and
    the callback reads ONLY those experts from the safetensors index — the
    peak host buffer is one shard, not the full expert stack, and on a
    multi-host EP mesh each process never touches non-local experts
    (the reference's EP-pruned loading, model_loader.py:363-369).
    """
    from jax.sharding import NamedSharding

    sparse_mask = None
    if family == "deepseek":
        from gllm_tpu.models import deepseek as model_mod
        rules = deepseek_rules(cfg)
        fmts = _DEEPSEEK_EXPERT_FMTS
        first, _ = cfg.stage_layers
        layer_of = lambda li: li + max(first, cfg.first_k_dense_replace)  # noqa: E731
    else:
        from gllm_tpu.models import moe as model_mod
        rules = moe_rules(cfg)
        fmts = _MOE_EXPERT_FMTS
        first, _ = cfg.stage_layers
        layer_of = lambda li: li + first                  # noqa: E731
        mask = model_mod.moe_layer_mask(cfg)
        if not all(mask):
            # mixed dense/sparse stack: dense layers have no expert
            # tensors in the checkpoint; their stack rows stay zero
            # (the per-layer flag routes around them at run time)
            sparse_mask = mask

    template = jax.eval_shape(
        lambda: model_mod.init_params(cfg, dtype=dtype))

    def rules_no_experts(name: str):
        r = rules(name)
        if r is not None and isinstance(r[0][-1], str) \
                and r[0][-1] in _EXPERT_LEAVES:
            return None
        return r

    host = _load_params(model_dir, template, rules_no_experts, progress_cb)
    if sparse_mask is not None and "moe_mask" in host.get("layers", {}):
        # derived flag, zero-filled by the template loader — rebuild it
        host["layers"]["moe_mask"] = np.asarray(sparse_mask, bool)
    lazy = LazySafetensors(model_dir)

    def place(path_keys, leaf, spec):
        arr = host
        for k in path_keys:
            arr = arr[k]
        return jax.device_put(arr, NamedSharding(mesh, spec))

    out: dict = {}
    for group, group_tree in template.items():
        if not isinstance(group_tree, dict):
            out[group] = place((group,), None, specs[group])
            continue
        out[group] = {}
        for leaf_name, leaf in group_tree.items():
            spec = specs[group][leaf_name]
            if leaf_name not in _EXPERT_LEAVES:
                out[group][leaf_name] = place((group, leaf_name), leaf,
                                              spec)
                continue
            name_fmts = (fmts.get((group, leaf_name))
                         or fmts.get(("layers", leaf_name)))
            shape, ldtype = leaf.shape, leaf.dtype

            def cb(index, _fmts=name_fmts, _shape=shape, _dtype=ldtype,
                   _layer_of=layer_of, _sparse=sparse_mask):
                # index: per-dim slices of the requested shard
                li_sl, e_sl = index[0], index[1]
                li_range = range(*li_sl.indices(_shape[0]))
                e_range = range(*e_sl.indices(_shape[1]))
                buf = np.zeros((len(li_range), len(e_range))
                               + tuple(_shape[2:]), _dtype)
                ep_load_stats["max_chunk_bytes"] = max(
                    ep_load_stats["max_chunk_bytes"], buf.nbytes)
                for a, li in enumerate(li_range):
                    if _sparse is not None and not _sparse[li]:
                        continue        # dense layer: no experts to read
                    for b, e in enumerate(e_range):
                        t = None
                        for fmt in _fmts:
                            nm = fmt.format(i=_layer_of(li), e=e)
                            if nm in lazy:
                                t = np.asarray(lazy.get(nm)).T
                                break
                        assert t is not None, (li, e, _fmts)
                        buf[a, b] = t.astype(_dtype)
                return buf

            out[group][leaf_name] = jax.make_array_from_callback(
                shape, NamedSharding(mesh, spec), cb)
    return out
