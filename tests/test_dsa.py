"""DeepSeek V3.2 sparse attention (DSA) — VERDICT r1 item 8.

The correctness oracle is the reference's own
(docs/deepseek_sparse_attention_design.md:36-40): for prompts no longer
than index_topk the top-k selects every key, so sparse output must equal
dense output byte-for-byte. Both engines share ONE param pytree (the dense
path simply never reads the indexer leaves).
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.engine.llm import LLM
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.sampling_params import SamplingParams

V32 = dict(
    architecture="DeepseekV32ForCausalLM", vocab_size=256, hidden_size=64,
    num_layers=3, num_heads=4, num_kv_heads=1, head_dim=24,
    intermediate_size=96, max_position=512,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    first_k_dense_replace=1, num_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=32, n_shared_experts=1,
    routed_scaling_factor=1.0, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=2, topk_group=1, norm_topk_prob=True,
    index_n_heads=2, index_head_dim=16, index_topk=64,
)


def build_llm(mcfg, params=None, **cache_kw):
    cfg = EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=128,
        scheduler=SchedulerConfig(max_prefill_tokens=64),
        cache=CacheConfig(page_size=4, num_pages=128, **cache_kw))
    return LLM(config=cfg, model_cfg=mcfg, params=params)


def test_dsa_sparse_equals_dense_when_topk_covers():
    from gllm_tpu.models import deepseek
    mcfg_sparse = ModelConfig(**V32)
    params = deepseek.init_params(mcfg_sparse, seed=3, dtype=jnp.float32)
    # dense twin: same weights, DSA off (indexer leaves simply unread)
    mcfg_dense = dataclasses.replace(mcfg_sparse, index_topk=0,
                                     index_n_heads=0)

    rng = np.random.default_rng(0)
    prompts = [[int(x) for x in rng.integers(2, 250, size=int(n))]
               for n in rng.integers(3, 40, size=4)]
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)

    sparse = [o.output_token_ids
              for o in build_llm(mcfg_sparse, params).generate(
                  prompt_token_ids=prompts, sampling_params=sp)]
    dense = [o.output_token_ids
             for o in build_llm(mcfg_dense, params).generate(
                 prompt_token_ids=prompts, sampling_params=sp)]
    assert sparse == dense


def test_dsa_chunked_prefill_matches_unchunked():
    """Index-K cache carries across prefill chunks."""
    from gllm_tpu.models import deepseek
    mcfg = ModelConfig(**V32)
    params = deepseek.init_params(mcfg, seed=5, dtype=jnp.float32)
    rng = np.random.default_rng(2)
    prompt = [int(x) for x in rng.integers(2, 250, size=40)]
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)

    big = build_llm(mcfg, params).generate(
        prompt_token_ids=[prompt], sampling_params=sp)[0]

    cfg = EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=128,
        scheduler=SchedulerConfig(max_prefill_tokens=8,
                                  min_prefill_tokens=4),
        cache=CacheConfig(page_size=4, num_pages=128))
    chunked = LLM(config=cfg, model_cfg=mcfg, params=params).generate(
        prompt_token_ids=[prompt], sampling_params=sp)[0]
    assert big.output_token_ids == chunked.output_token_ids


def test_dsa_truncated_topk_still_serves():
    """topk smaller than the context: the sparse path must run and finish
    (output differs from dense by design — only liveness + shape here)."""
    mcfg = dataclasses.replace(ModelConfig(**V32), index_topk=8)
    llm = build_llm(mcfg)
    rng = np.random.default_rng(1)
    prompt = [int(x) for x in rng.integers(2, 250, size=30)]
    out = llm.generate(
        prompt_token_ids=[prompt],
        sampling_params=SamplingParams(temperature=0.0, max_tokens=6,
                                       ignore_eos=True))[0]
    assert len(out.output_token_ids) == 6
    mm = llm.memory_manager
    assert mm.num_free_pages == mm.allocator.num_total


# ---- fp8 index-K cache (VERDICT r03 missing #3) ----------------------------

def _greedy(llm, prompts, n=8):
    return [o.output_token_ids for o in llm.generate(
        prompt_token_ids=prompts,
        sampling_params=SamplingParams(temperature=0.0, max_tokens=n,
                                       ignore_eos=True))]


@pytest.mark.parametrize("kv_dtype", ["auto", "fp8"])
def test_index_cache_follows_the_cache_dtype_and_is_sized(kv_dtype):
    """The index-K cache is stored in the served cache's dtype; under an
    fp8 cache (the server's own --kv-cache-dtype fp8, no environment
    variable) as fp8 payloads + f32 per-token scales (reference
    store_index_k_fp8 132-byte layout). The page-budget accounting
    reflects either."""
    mcfg = ModelConfig(**V32)
    llm = build_llm(mcfg, kv_cache_dtype=kv_dtype)
    kv = llm.runner.kv
    if kv_dtype == "fp8":
        assert kv.index_k.dtype == jnp.float8_e4m3fn
        assert kv.index_scale is not None
        assert kv.index_scale.shape == kv.index_k.shape[:-1]
        # bytes/page: (latent + index_head_dim) * 1 + 4 (scale)
        per_tok = mcfg.mla_cache_width + mcfg.index_head_dim + 4
    else:
        assert kv.index_k.dtype == kv.latent.dtype == jnp.float32
        assert kv.index_scale is None
        per_tok = (mcfg.mla_cache_width + mcfg.index_head_dim) * 4
    assert llm.runner._kv_bytes_per_page() == \
        mcfg.num_layers * 4 * per_tok


def test_no_environment_variable_decides_the_index_cache(monkeypatch):
    """The two environment reads are gone: neither variable changes the
    cache or the scores."""
    from gllm_tpu.models import deepseek
    mcfg = ModelConfig(**V32)
    params = deepseek.init_params(mcfg, seed=3, dtype=jnp.float32)
    prompts = [[7, 3, 11, 23, 9, 2], [5, 5, 19]]
    base = _greedy(build_llm(mcfg, params=params), prompts)
    monkeypatch.setenv("GLLM_TPU_DSA_INDEX_DTYPE", "fp8")
    monkeypatch.setenv("GLLM_DSA_FP8_SCORE", "1")
    llm = build_llm(mcfg, params=params)
    assert llm.runner.kv.index_k.dtype == jnp.float32
    assert _greedy(llm, prompts) == base
    assert not hasattr(deepseek, "index_cache_fp8")
    assert not hasattr(deepseek, "fp8_score")


@pytest.mark.parametrize("what", ["index_keys", "whole_cache"])
def test_fp8_index_cache_matches_native(what):
    """Greedy outputs with the index keys cached in fp8 equal the
    native-dtype cache's, all eight tokens: on these float32 tiny models
    the keys' quantization error is far below the selection's margins
    (``index_keys``: fp8 payloads + scales beside NATIVE latent rows, so
    the selection alone is held). Under ``--kv-cache-dtype fp8`` the
    latent rows are rounded too (``whole_cache``) and their own error may
    move a late argmax: there the first tokens are what is held equal."""
    from gllm_tpu.models import deepseek
    mcfg = ModelConfig(**V32)
    params = deepseek.init_params(mcfg, seed=3, dtype=jnp.float32)
    prompts = [[7, 3, 11, 23, 9, 2], [5, 5, 19]]
    native = _greedy(build_llm(mcfg, params=params), prompts)
    if what == "index_keys":
        llm = build_llm(mcfg, params=params)
        kv = llm.runner.kv
        llm.runner.kv = kv._replace(
            index_k=jnp.zeros(kv.index_k.shape, jnp.float8_e4m3fn),
            index_scale=jnp.ones(kv.index_k.shape[:-1], jnp.float32))
        assert llm.runner.kv.latent.dtype == jnp.float32
        assert _greedy(llm, prompts) == native
    else:
        fp8 = _greedy(build_llm(mcfg, params=params, kv_cache_dtype="fp8"),
                      prompts)
        assert [o[:2] for o in fp8] == [o[:2] for o in native]


def test_fp8_scoring_flag():
    """``index_fp8_score`` (a key of the model's config.json; the
    reference's GLLM_DSA_FP8_SCORE) scores the indexer with fp8 operands
    where the keys are cached in fp8; the tiny-model greedy outputs still
    match the f32 scoring of the same cache (selection indices survive
    the quantization)."""
    from gllm_tpu.models import deepseek
    mcfg = ModelConfig(**V32)
    params = deepseek.init_params(mcfg, seed=3, dtype=jnp.float32)
    prompts = [[7, 3, 11, 23, 9, 2, 31, 8]]
    base = _greedy(build_llm(mcfg, params=params, kv_cache_dtype="fp8"),
                   prompts)
    fp8s = _greedy(build_llm(
        dataclasses.replace(mcfg, index_fp8_score=True), params=params,
        kv_cache_dtype="fp8"), prompts)
    assert base == fp8s
