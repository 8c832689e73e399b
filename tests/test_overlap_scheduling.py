"""Overlap (chained on-device decode) must be byte-identical to sync."""

import pytest
import torch

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.engine.llm import LLM
from gllm_tpu.sampling_params import SamplingParams


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(41)
    d = tmp_path_factory.mktemp("ov_llama")
    LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        max_position_embeddings=256, eos_token_id=0,
        attention_bias=False)).save_pretrained(d, safe_serialization=True)
    return str(d)


def run(model_dir, overlap, prompts, sp):
    cfg = EngineConfig(
        model=model_dir, dtype="float32", max_model_len=128,
        overlap_scheduling=overlap,
        scheduler=SchedulerConfig(max_prefill_tokens=64, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=128))
    llm = LLM(config=cfg)
    outs = llm.generate(prompt_token_ids=prompts, sampling_params=sp)
    assert llm.memory_manager.num_free_pages == \
        llm.memory_manager.allocator.num_total  # no page leaks
    return [(o.output_token_ids, o.finish_reason) for o in outs]


def test_overlap_matches_sync_long_decode(ckpt):
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    prompts = [[3, 14, 15], [9, 2, 6, 5, 3], [58, 9]]
    assert run(ckpt, True, prompts, sp) == run(ckpt, False, prompts, sp)


def test_overlap_matches_sync_with_eos(ckpt):
    # natural EOS can land mid-chain → the chained step's work is discarded
    # and pages are released late but exactly once
    sp = SamplingParams(temperature=0.0, max_tokens=30)
    prompts = [[i, i + 1, i + 2] for i in range(1, 12, 2)]
    assert run(ckpt, True, prompts, sp) == run(ckpt, False, prompts, sp)


def test_overlap_matches_sync_max_tokens_boundary(ckpt):
    sp = SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True)
    prompts = [[5, 6], [7, 8, 9]]
    assert run(ckpt, True, prompts, sp) == run(ckpt, False, prompts, sp)


def test_overlap_page_boundary_growth(ckpt):
    # page_size 4: decode repeatedly crosses page boundaries inside chains
    sp = SamplingParams(temperature=0.0, max_tokens=13, ignore_eos=True)
    prompts = [[3] * 7]
    assert run(ckpt, True, prompts, sp) == run(ckpt, False, prompts, sp)


def test_overlap_sampled_reproducible(ckpt):
    sp = SamplingParams(temperature=0.8, top_k=30, max_tokens=12,
                        ignore_eos=True)
    a = run(ckpt, True, [[4, 8], [15, 16]], sp)
    b = run(ckpt, True, [[4, 8], [15, 16]], sp)
    assert a == b


def test_overlap_single_seq_eos_midchain_no_leak(ckpt):
    # single seq finishing by EOS while its chained step is in flight: the
    # engine must drain the chain and release every page (review repro)
    cfg = EngineConfig(
        model=ckpt, dtype="float32", max_model_len=128,
        overlap_scheduling=True,
        cache=CacheConfig(page_size=4, num_pages=128))
    llm = LLM(config=cfg)
    # find the eos organically: run greedy, use the 3rd generated token as eos
    probe = llm.generate(prompt_token_ids=[[5, 6, 7]],
                         sampling_params=SamplingParams(
                             temperature=0.0, max_tokens=8, ignore_eos=True))
    eos = probe[0].output_token_ids[2]
    llm2 = LLM(config=cfg)
    llm2.eos_token_ids = frozenset([eos])
    out = llm2.generate(prompt_token_ids=[[5, 6, 7]],
                        sampling_params=SamplingParams(temperature=0.0,
                                                       max_tokens=30))[0]
    assert out.finish_reason == "stop"
    assert not llm2._in_flight
    assert llm2.memory_manager.num_free_pages == \
        llm2.memory_manager.allocator.num_total


def run_multi(model_dir, multi, prompts, sp, depth=2):
    cfg = EngineConfig(
        model=model_dir, dtype="float32", max_model_len=128,
        overlap_scheduling=True, overlap_depth=depth,
        multi_step_decode=multi,
        scheduler=SchedulerConfig(max_prefill_tokens=64, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=128))
    llm = LLM(config=cfg)
    outs = llm.generate(prompt_token_ids=prompts, sampling_params=sp)
    assert llm.memory_manager.num_free_pages == \
        llm.memory_manager.allocator.num_total
    return [(o.output_token_ids, o.finish_reason) for o in outs]


def test_multi_step_matches_sync_greedy(ckpt):
    """K fused decode steps per dispatch == plain sync, byte for byte
    (incl. page-boundary crossings inside the fused block)."""
    sp = SamplingParams(temperature=0.0, max_tokens=23, ignore_eos=True)
    prompts = [[3, 14, 15], [9, 2, 6, 5, 3], [58, 9]]
    want = run(ckpt, False, prompts, sp)
    assert run_multi(ckpt, 4, prompts, sp) == want
    assert run_multi(ckpt, 8, prompts, sp, depth=3) == want


def test_multi_step_matches_sync_with_eos(ckpt):
    """EOS lands mid-block → the rest of the fused block's tokens for that
    seq are discarded; frees happen exactly once."""
    sp = SamplingParams(temperature=0.0, max_tokens=30)
    prompts = [[i, i + 1, i + 2] for i in range(1, 12, 2)]
    assert run_multi(ckpt, 6, prompts, sp) == run(ckpt, False, prompts, sp)


def test_multi_step_sampling_key_schedule_identical(ckpt):
    """Unseeded temp>0 sampling: the fused block folds the SAME per-step
    keys as single-step chaining, so outputs stay byte-identical."""
    sp = SamplingParams(temperature=0.8, top_p=0.9, max_tokens=12,
                        ignore_eos=True)
    prompts = [[3, 14, 15], [9, 2, 6]]
    assert run_multi(ckpt, 4, prompts, sp) == run(ckpt, True, prompts, sp)


def test_seeded_sampling_fused_multi_step(ckpt):
    """Seeded requests ride the fused multi-step block since r4: their
    draws are a pure function of (seed, out_step), which the fused scan
    advances on device — outputs byte-identical to the plain engine."""
    prompts = [[5, 17, 93, 41], [9, 9, 3, 77, 21, 60]]
    sps = [SamplingParams(temperature=0.9, seed=7, max_tokens=24,
                          ignore_eos=True),
           SamplingParams(temperature=0.7, seed=11, max_tokens=24,
                          ignore_eos=True)]
    base = run(ckpt, False, [list(p) for p in prompts], sps)
    fused = run_multi(ckpt, 4, [list(p) for p in prompts], sps)
    assert base == fused


def test_inflight_depth_knob(ckpt):
    """--inflight-depth is a real knob: at depth 3 the pipelined loop
    sustains a strictly deeper run-ahead than at the default 2 on a
    decode-saturated workload."""
    import numpy as np
    from gllm_tpu.obs.steptrace import TRACE, summarize

    def mean_depth(depth):
        llm = LLM(config=EngineConfig(
            model=ckpt, dtype="float32", max_model_len=64, max_num_seqs=8,
            pipelined_loop=True, overlap_depth=depth,
            scheduler=SchedulerConfig(max_prefill_tokens=32,
                                      max_decode_seqs=8),
            cache=CacheConfig(page_size=4, num_pages=256)))
        rng = np.random.default_rng(5)
        prompts = [[int(x) for x in rng.integers(2, 120, size=6)]
                   for _ in range(6)]
        sps = [SamplingParams(temperature=0.0, max_tokens=40,
                              ignore_eos=True) for _ in range(6)]
        llm.generate(prompt_token_ids=prompts, sampling_params=sps)
        mark = TRACE.mark()
        llm.generate(prompt_token_ids=prompts, sampling_params=sps)
        return summarize(TRACE.events(since=mark))["mean_inflight_depth"]

    d2, d3 = mean_depth(2), mean_depth(3)
    assert d3 > d2, (d2, d3)
    assert d3 > 1.0, d3


def test_config_rejects_bad_inflight_depth():
    cfg = EngineConfig(overlap_depth=0)
    with pytest.raises(ValueError, match="inflight-depth"):
        cfg.validate()
