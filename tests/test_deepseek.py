"""DeepSeek V2/V3 (MLA + DeepSeekMoE): HF greedy-equivalence oracles."""

import pytest
import torch

from gllm_tpu.config import CacheConfig, EngineConfig
from gllm_tpu.engine.llm import LLM
from gllm_tpu.sampling_params import SamplingParams

BASE = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
    max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
    tie_word_embeddings=False, eos_token_id=0,
    # MLA geometry
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    # MoE: 1 dense layer then MoE layers
    n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    first_k_dense_replace=1, n_shared_experts=1, moe_layer_freq=1,
    routed_scaling_factor=1.5,
)


def make_ckpt(arch, tmpdir, **over):
    torch.manual_seed(31)
    cfg_kw = {**BASE, **over}
    if arch == "DeepseekV2ForCausalLM":
        from transformers import DeepseekV2Config, DeepseekV2ForCausalLM
        cfg = DeepseekV2Config(**cfg_kw)
        model = DeepseekV2ForCausalLM(cfg)
    else:
        from transformers import DeepseekV3Config, DeepseekV3ForCausalLM
        cfg = DeepseekV3Config(**cfg_kw)
        model = DeepseekV3ForCausalLM(cfg)
    model.eval()
    model.save_pretrained(tmpdir, safe_serialization=True)
    return model


def hf_greedy(model, prompt_ids, n):
    ids = list(prompt_ids)
    with torch.no_grad():
        for _ in range(n):
            logits = model(torch.tensor([ids])).logits[0, -1]
            ids.append(int(logits.argmax()))
    return ids[len(prompt_ids):]


def ours(model_dir, prompts, n):
    cfg = EngineConfig(model=model_dir, dtype="float32", max_model_len=128,
                       cache=CacheConfig(page_size=4, num_pages=128))
    llm = LLM(config=cfg)
    return [o.output_token_ids for o in llm.generate(
        prompt_token_ids=prompts,
        sampling_params=SamplingParams(temperature=0.0, max_tokens=n,
                                       ignore_eos=True))]


@pytest.mark.parametrize("q_lora", [None, 48])
def test_deepseek_v2_greedy_equivalence(tmp_path, q_lora):
    hf = make_ckpt("DeepseekV2ForCausalLM", tmp_path, q_lora_rank=q_lora,
                   topk_method="greedy", n_group=None, topk_group=None,
                   scoring_func="softmax", norm_topk_prob=False)
    prompts = [[7, 3, 56, 21], [99, 14, 2]]
    got = ours(str(tmp_path), prompts, 8)
    for p, g in zip(prompts, got):
        assert g == hf_greedy(hf, p, 8), (p, g)


def test_deepseek_v3_greedy_equivalence(tmp_path):
    hf = make_ckpt("DeepseekV3ForCausalLM", tmp_path, q_lora_rank=48,
                   n_group=4, topk_group=2, topk_method="noaux_tc",
                   scoring_func="sigmoid", norm_topk_prob=True)
    # give the correction bias real values so the noaux_tc path is exercised
    with torch.no_grad():
        for layer in hf.model.layers[1:]:
            layer.mlp.gate.e_score_correction_bias.add_(
                torch.randn_like(layer.mlp.gate.e_score_correction_bias)
                * 0.1)
    hf.save_pretrained(tmp_path, safe_serialization=True)
    prompts = [[5, 9, 23, 41, 77], [100, 90]]
    got = ours(str(tmp_path), prompts, 8)
    for p, g in zip(prompts, got):
        assert g == hf_greedy(hf, p, 8), (p, g)


def test_deepseek_v2_yarn_rope(tmp_path):
    scaling = {"rope_type": "yarn", "factor": 2.0, "beta_fast": 32,
               "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
               "original_max_position_embeddings": 64}
    hf = make_ckpt("DeepseekV2ForCausalLM", tmp_path, q_lora_rank=None,
                   topk_method="greedy", n_group=None, topk_group=None,
                   scoring_func="softmax", norm_topk_prob=False,
                   rope_scaling=scaling)
    prompts = [[9, 8, 7, 6, 5, 4, 3, 2]]
    got = ours(str(tmp_path), prompts, 6)
    assert got[0] == hf_greedy(hf, prompts[0], 6)


def test_mla_pallas_matches_xla(tmp_path):
    """MLA routed through the Pallas kernels (shared latent KV, v_dim <
    head_dim) must reproduce the xla-impl greedy output end-to-end."""
    make_ckpt("DeepseekV2ForCausalLM", tmp_path, q_lora_rank=None,
              topk_method="greedy", n_group=None, topk_group=None,
              scoring_func="softmax", norm_topk_prob=False)
    prompts = [[7, 3, 56, 21, 8, 4, 90], [99, 14, 2]]

    def run(impl):
        cfg = EngineConfig(model=str(tmp_path), dtype="float32",
                           max_model_len=128, attention_impl=impl,
                           cache=CacheConfig(page_size=4, num_pages=128))
        return [o.output_token_ids for o in LLM(config=cfg).generate(
            prompt_token_ids=prompts,
            sampling_params=SamplingParams(temperature=0.0, max_tokens=8,
                                           ignore_eos=True))]

    assert run("pallas") == run("xla")


# ---- the router follows ``topk_method`` ------------------------------------

def _route_by_hand(scores, bias, method, n_group, topk_group, k):
    """The four methods as their papers have them, a token at a time."""
    import numpy as np
    ids = []
    for s in scores:
        choice = s + bias if method == "noaux_tc" else s.copy()
        if method in ("group_limited_greedy", "noaux_tc"):
            groups = choice.reshape(n_group, -1)
            rank = (np.sort(groups, axis=1)[:, -2:].sum(1)
                    if method == "noaux_tc" else groups.max(1))
            keep = np.argsort(-rank, kind="stable")[:topk_group]
            mask = np.full(n_group, -np.inf)
            mask[keep] = 0.0
            choice = (groups + mask[:, None]).reshape(-1)
        ids.append(np.argsort(-choice, kind="stable")[:k])
    return np.stack(ids)


@pytest.mark.parametrize("method", ["greedy", "group_limited_greedy",
                                    "noaux_tc", "none"])
def test_router_follows_topk_method_not_the_presence_of_n_group(method):
    """A config that carries ``n_group`` / ``topk_group`` (skt/A.X-K1 does,
    beside ``topk_method`` "none") is limited to groups only by the two
    methods that have a group limit, and corrected by the bias only by
    noaux_tc; "none" and "greedy" are the plain top-k of all experts. The
    input puts the 8 largest scores into 3 groups of which the limit keeps
    2, so the limited and the plain choice differ on every token."""
    import jax.numpy as jnp
    import numpy as np
    from gllm_tpu.models.config import from_hf_config
    from gllm_tpu.models.deepseek import deepseek_route
    E, K, G, TG = 32, 4, 8, 2
    cfg = from_hf_config(dict(
        BASE, architectures=["DeepseekV3ForCausalLM"], n_routed_experts=E,
        num_experts_per_tok=K, n_group=G, topk_group=TG,
        topk_method=method, scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5))
    assert cfg.route_groups == (G if method in ("group_limited_greedy",
                                                "noaux_tc") else 0)
    rng = np.random.default_rng(7)
    logits = rng.standard_normal((16, E)).astype(np.float32)
    # the four largest logits of every token in four different groups
    for t in range(16):
        logits[t, [0, 5, 10, 15]] = [4.0, 3.5, 3.0, 2.5]
    bias = (rng.standard_normal(E) * 0.05).astype(np.float32)
    scores = 1 / (1 + np.exp(-logits.astype(np.float64)))
    w, ids = deepseek_route(jnp.asarray(logits), jnp.asarray(bias), cfg)
    want = _route_by_hand(scores, bias, method, G, TG, K)
    assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(want, 1))
    plain = _route_by_hand(scores, bias, "none", G, TG, K)
    limited = method in ("group_limited_greedy", "noaux_tc")
    assert np.array_equal(np.sort(want, 1), np.sort(plain, 1)) != limited
    if not limited:
        assert np.array_equal(np.sort(want, 1)[0], [0, 5, 10, 15])
    # weights: the chosen scores (never the biased ones), normalised, x 2.5
    picked = np.take_along_axis(scores, np.asarray(ids), 1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / picked.sum(1, keepdims=True),
        rtol=1e-5)
