"""Qwen3-Next hybrid (GDN + gated attention) end-to-end oracles.

HF-greedy equivalence through the full engine (chunked prefill + recurrent
decode + slot pools), chunked==unchunked, continuous-batching invariance,
and SSM prefix caching (cold == warm with state restore; rollback when no
snapshot exists).
"""

import numpy as np
import pytest
import torch

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.engine.llm import LLM
from gllm_tpu.sampling_params import SamplingParams

BASE = dict(
    vocab_size=160, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, max_position_embeddings=512,
    rms_norm_eps=1e-6, rope_theta=10000.0, partial_rotary_factor=0.25,
    tie_word_embeddings=False, eos_token_id=0, bos_token_id=1,
    layer_types=["linear_attention", "linear_attention",
                 "linear_attention", "full_attention"],
    linear_num_value_heads=4, linear_num_key_heads=2,
    linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4,
    num_experts=0, attention_bias=False,
)


def make_ckpt(tmp_path, **overrides):
    from transformers import Qwen3NextConfig, Qwen3NextForCausalLM
    torch.manual_seed(13)
    cfg = Qwen3NextConfig(**{**BASE, **overrides})
    model = Qwen3NextForCausalLM(cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    return model


def hf_greedy(model, prompt_ids, n):
    ids = list(prompt_ids)
    with torch.no_grad():
        for _ in range(n):
            logits = model(torch.tensor([ids])).logits[0, -1]
            ids.append(int(logits.argmax()))
    return ids[len(prompt_ids):]


def make_llm(model_dir, prefix=False, **sched):
    cfg = EngineConfig(
        model=model_dir, dtype="float32", max_model_len=256,
        scheduler=SchedulerConfig(**sched) if sched else SchedulerConfig(),
        cache=CacheConfig(page_size=4, num_pages=128,
                          enable_prefix_caching=prefix,
                          ssm_snapshot_slots=16))
    return LLM(config=cfg)


def test_hybrid_greedy_equivalence(tmp_path):
    hf = make_ckpt(tmp_path)
    prompts = [[7, 3, 56, 21], [99, 14, 2], [5, 6, 7, 8, 9, 10, 11]]
    llm = make_llm(str(tmp_path))
    outs = llm.generate(
        prompt_token_ids=prompts,
        sampling_params=SamplingParams(temperature=0.0, max_tokens=8,
                                       ignore_eos=True))
    for p, o in zip(prompts, outs):
        assert o.output_token_ids == hf_greedy(hf, p, 8), \
            (p, o.output_token_ids)


def test_hybrid_moe_greedy_equivalence(tmp_path):
    hf = make_ckpt(tmp_path, num_experts=8, num_experts_per_tok=2,
                   moe_intermediate_size=32,
                   shared_expert_intermediate_size=48, norm_topk_prob=True,
                   decoder_sparse_step=1, mlp_only_layers=[])
    prompts = [[7, 3, 56, 21], [99, 14, 2]]
    llm = make_llm(str(tmp_path))
    outs = llm.generate(
        prompt_token_ids=prompts,
        sampling_params=SamplingParams(temperature=0.0, max_tokens=6,
                                       ignore_eos=True))
    for p, o in zip(prompts, outs):
        assert o.output_token_ids == hf_greedy(hf, p, 6), \
            (p, o.output_token_ids)


def test_hybrid_chunked_prefill_matches(tmp_path):
    hf = make_ckpt(tmp_path)
    rng = np.random.default_rng(0)
    long_prompt = [int(x) for x in rng.integers(2, 150, size=40)]
    want = hf_greedy(hf, long_prompt, 6)
    llm = make_llm(str(tmp_path), max_prefill_tokens=8,
                   min_prefill_tokens=4)
    got = llm.generate(
        prompt_token_ids=[long_prompt],
        sampling_params=SamplingParams(temperature=0.0, max_tokens=6,
                                       ignore_eos=True))[0]
    assert got.output_token_ids == want


def test_hybrid_batch_composition_invariance(tmp_path):
    make_ckpt(tmp_path)
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1], [8, 2, 8, 1, 8, 2, 8]]
    llm = make_llm(str(tmp_path))
    together = [o.output_token_ids
                for o in llm.generate(prompt_token_ids=prompts,
                                      sampling_params=sp)]
    llm2 = make_llm(str(tmp_path))
    alone = [llm2.generate(prompt_token_ids=[p], sampling_params=sp)[0]
             .output_token_ids for p in prompts]
    assert together == alone


def test_hybrid_prefix_cache_cold_warm_with_ssm_restore(tmp_path):
    """SSM state snapshot + restore: warm run must be byte-identical to
    cold AND actually hit the cache (the reference's cold==warm oracle for
    hybrid models)."""
    make_ckpt(tmp_path)
    # page_size 4; prompt of 13 shared + 3 distinct tokens; prefill chunks
    # default (big) → whole prompt in one chunk, ends mid-page → the last
    # FULL page boundary snapshot comes from decode crossings; use aligned
    # shared prefix to give clean page-boundary snapshots
    shared = [11, 22, 33, 44, 55, 66, 77, 88, 91, 92, 93, 94]   # 12 = 3 pages
    prompts = [shared + [5, 7], shared + [9, 2, 4]]
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)

    llm_off = make_llm(str(tmp_path), prefix=False)
    off = [o.output_token_ids
           for o in llm_off.generate(prompt_token_ids=prompts,
                                     sampling_params=sp)]
    llm_on = make_llm(str(tmp_path), prefix=True)
    cold = [o.output_token_ids
            for o in llm_on.generate(prompt_token_ids=prompts,
                                     sampling_params=sp)]
    warm = [o.output_token_ids
            for o in llm_on.generate(prompt_token_ids=prompts,
                                     sampling_params=sp)]
    assert off == cold == warm
    assert llm_on.memory_manager.hit_tokens > 0
    # slot accounting: all working slots released
    assert llm_on.memory_manager.ssm_alloc.num_free == \
        llm_on.memory_manager.ssm_alloc.num_total


def test_hybrid_no_snapshot_means_no_partial_hit(tmp_path):
    """With the snapshot pool disabled, KV prefix hits must be fully
    rolled back (stateless replay would corrupt the recurrence)."""
    make_ckpt(tmp_path)
    cfg = EngineConfig(
        model=str(tmp_path), dtype="float32", max_model_len=256,
        cache=CacheConfig(page_size=4, num_pages=128,
                          enable_prefix_caching=True,
                          ssm_snapshot_slots=0))
    llm = LLM(config=cfg)
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    prompt = [11, 22, 33, 44, 55, 66, 77, 88, 5]
    a = llm.generate(prompt_token_ids=[prompt],
                     sampling_params=sp)[0].output_token_ids
    b = llm.generate(prompt_token_ids=[prompt],
                     sampling_params=sp)[0].output_token_ids
    assert a == b
    assert llm.memory_manager.hit_tokens == 0   # hits fully rolled back


def test_hybrid_overlap_scheduling_matches(tmp_path):
    make_ckpt(tmp_path)
    prompts = [[5, 9, 23], [7, 7, 2, 1]]

    def run(overlap):
        cfg = EngineConfig(
            model=str(tmp_path), dtype="float32", max_model_len=128,
            overlap_scheduling=overlap,
            cache=CacheConfig(page_size=4, num_pages=128))
        return [o.output_token_ids for o in LLM(config=cfg).generate(
            prompt_token_ids=prompts,
            sampling_params=SamplingParams(temperature=0.0, max_tokens=10,
                                           ignore_eos=True))]

    assert run(True) == run(False)


def test_hybrid_dp2_matches_dp1(tmp_path):
    """Hybrid GDN under dp: per-replica SSM pools (stacked leading axis,
    per-replica intent application) — greedy byte-identity vs dp=1."""
    from gllm_tpu.config import ParallelConfig
    make_ckpt(tmp_path)
    prompts = [[7, 3, 56, 21], [99, 14, 2], [5, 6, 7, 8, 9, 10, 11],
               [42, 13]]
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)

    def run(dp):
        cfg = EngineConfig(
            model=str(tmp_path), dtype="float32", max_model_len=256,
            cache=CacheConfig(page_size=4, num_pages=128),
            parallel=ParallelConfig(dp=dp))
        llm = LLM(config=cfg)
        return [o.output_token_ids
                for o in llm.generate(prompt_token_ids=prompts,
                                      sampling_params=sp)]

    assert run(2) == run(1)


@pytest.mark.parametrize("value_head_dim,rec_slot,sharded", [
    (8, (4, 8, 8), True),       # each head alone in a slot
    (64, (2, 8, 128), True),    # two abreast: a group a shard
    (32, (1, 8, 128), False),   # all four abreast: nothing to shard
], ids=["alone", "two_abreast", "four_abreast"])
def test_hybrid_tp2_matches_tp1(tmp_path, value_head_dim, rec_slot,
                                sharded):
    """GDN stack under tensor parallelism (GSPMD hybrid_param_specs /
    hybrid_kv_specs shard the attention axes and the slot pool's axis of
    heads, or of groups of heads abreast, where tp divides it) —
    byte-identical to tp=1."""
    from gllm_tpu.config import ParallelConfig
    from gllm_tpu.models.config import from_hf_config
    from gllm_tpu.parallel.shardings import hybrid_kv_specs
    make_ckpt(tmp_path, linear_value_head_dim=value_head_dim)
    mcfg = from_hf_config(dict(BASE, linear_value_head_dim=value_head_dim,
                               architectures=["Qwen3NextForCausalLM"]))
    assert mcfg.ssm_slot_shapes[1] == rec_slot
    assert (hybrid_kv_specs(mcfg, 2).rec[2] == "tp") == sharded
    want = [o.output_token_ids for o in make_llm(str(tmp_path)).generate(
        prompt_token_ids=[[5, 9, 23], [7, 12, 2, 44]],
        sampling_params=SamplingParams(temperature=0.0, max_tokens=8,
                                       ignore_eos=True))]
    cfg = EngineConfig(
        model=str(tmp_path), dtype="float32", max_model_len=256,
        cache=CacheConfig(page_size=4, num_pages=128,
                          ssm_snapshot_slots=16),
        parallel=ParallelConfig(tp=2))
    got = [o.output_token_ids for o in LLM(config=cfg).generate(
        prompt_token_ids=[[5, 9, 23], [7, 12, 2, 44]],
        sampling_params=SamplingParams(temperature=0.0, max_tokens=8,
                                       ignore_eos=True))]
    assert got == want, (got, want)


def test_hybrid_pp2_tp2_matches_single(tmp_path):
    """GDN stack through a pp=2 × tp=2 grid: period-aligned stages +
    GSPMD-sharded SSM pools per stage — byte-identical to the plain
    engine."""
    from gllm_tpu.config import ParallelConfig
    # two layer-type periods so pp=2 has a period-aligned split
    make_ckpt(tmp_path, num_hidden_layers=8,
              layer_types=["linear_attention", "linear_attention",
                           "linear_attention", "full_attention"] * 2)
    prompts = [[5, 9, 23], [7, 12, 2, 44]]
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    want = [o.output_token_ids for o in make_llm(str(tmp_path)).generate(
        prompt_token_ids=[list(p) for p in prompts], sampling_params=sp)]
    cfg = EngineConfig(
        model=str(tmp_path), dtype="float32", max_model_len=256,
        cache=CacheConfig(page_size=4, num_pages=128,
                          ssm_snapshot_slots=16),
        parallel=ParallelConfig(pp=2, tp=2))
    got = [o.output_token_ids for o in LLM(config=cfg).generate(
        prompt_token_ids=[list(p) for p in prompts], sampling_params=sp)]
    assert got == want, (got, want)


# ---- speculative decoding on hybrid (SSM snapshot rollback) ---------------

def make_llm_spec(model_dir, prefix=False):
    cfg = EngineConfig(
        model=model_dir, dtype="float32", max_model_len=256,
        spec_decode="ngram", spec_k=4, spec_ngram=2,
        cache=CacheConfig(page_size=4, num_pages=128,
                          enable_prefix_caching=prefix,
                          ssm_snapshot_slots=16))
    return LLM(config=cfg)


def test_hybrid_spec_byte_identity_with_rollback(tmp_path):
    """Speculative decoding on the GDN hybrid: pre-draft SSM state is
    snapshotted; a partial acceptance restores it and re-feeds the
    committed run — greedy outputs stay byte-identical to the plain
    engine, through both full-sweep and rollback paths."""
    make_ckpt(tmp_path)
    prompts = [[7, 3, 56, 21, 7, 3, 56, 21],     # draft-friendly
               [5, 9, 23, 5, 9, 23, 5, 9],
               [99, 14, 2],                      # cold
               list(range(1, 24))]
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    base = make_llm(str(tmp_path))
    want = [o.output_token_ids for o in base.generate(
        prompt_token_ids=[list(p) for p in prompts], sampling_params=sp)]
    llm = make_llm_spec(str(tmp_path))
    got = [o.output_token_ids for o in llm.generate(
        prompt_token_ids=[list(p) for p in prompts], sampling_params=sp)]
    assert got == want, (got, want)
    st = llm.scheduler.spec_stats
    assert st["proposed"] > 0 and st["accepted"] > 0
    # the rollback path must actually have been exercised
    assert st["accepted"] < st["proposed"]
    # every spec snapshot slot returned (pending frees count as returned:
    # they release at the next intent drain)
    mm = llm.scheduler.mm
    assert mm.ssm_snap_alloc.num_free + len(mm._snap_free_pending) == 16


def test_hybrid_spec_with_prefix_cache_cold_warm(tmp_path):
    """Spec + SSM prefix caching share the snapshot pool; cold and warm
    runs both match the plain engine byte-for-byte."""
    make_ckpt(tmp_path)
    prompt = [7, 3, 56, 21, 7, 3, 56, 21, 7, 3, 56, 21]
    sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)
    base = make_llm(str(tmp_path))
    want = base.generate(prompt_token_ids=[list(prompt)],
                         sampling_params=sp)[0].output_token_ids
    llm = make_llm_spec(str(tmp_path), prefix=True)
    cold = llm.generate(prompt_token_ids=[list(prompt)],
                        sampling_params=sp)[0].output_token_ids
    warm = llm.generate(prompt_token_ids=[list(prompt)],
                        sampling_params=sp)[0].output_token_ids
    assert cold == want and warm == want, (cold, warm, want)
    assert llm.scheduler.spec_stats["accepted"] > 0
