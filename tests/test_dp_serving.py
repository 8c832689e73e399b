"""DP-attention serving: multi-replica engines in one jit program.

dp=2 greedy output must be byte-identical to dp=1 (the reference's DP
validation discipline, docs/dp_attention_design.md), with idle replicas
riding as in-program dummy batches instead of lockstep barriers.
"""

import numpy as np
import pytest
import torch

from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.engine.llm import LLM
from gllm_tpu.sampling_params import SamplingParams


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(6)
    d = tmp_path_factory.mktemp("dp_model")
    LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        max_position_embeddings=256, eos_token_id=0,
        attention_bias=False)).save_pretrained(d, safe_serialization=True)
    return str(d)


def make_llm(ckpt, dp=1, tp=1, attention_impl="auto", **sched):
    cfg = EngineConfig(
        model=ckpt, dtype="float32", max_model_len=128,
        attention_impl=attention_impl,
        scheduler=SchedulerConfig(**sched) if sched else SchedulerConfig(),
        cache=CacheConfig(page_size=4, num_pages=64),
        parallel=ParallelConfig(dp=dp, tp=tp))
    return LLM(config=cfg)


def test_dp2_greedy_byte_identity(ckpt):
    rng = np.random.default_rng(0)
    prompts = [[int(x) for x in rng.integers(2, 120, size=int(n))]
               for n in rng.integers(2, 30, size=5)]
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)

    base = [o.output_token_ids
            for o in make_llm(ckpt).generate(prompt_token_ids=prompts,
                                             sampling_params=sp)]
    dp2 = [o.output_token_ids
           for o in make_llm(ckpt, dp=2).generate(prompt_token_ids=prompts,
                                                  sampling_params=sp)]
    assert base == dp2


def test_dp2_uneven_load_and_idle_replica(ckpt):
    """One request → replica 0 busy, replica 1 idle (dummy batches); and a
    second wave lands on replica 1 (round robin)."""
    llm = make_llm(ckpt, dp=2)
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    out1 = llm.generate(prompt_token_ids=[[5, 9, 23]],
                        sampling_params=sp)[0]
    out2 = llm.generate(prompt_token_ids=[[5, 9, 23]],
                        sampling_params=sp)[0]
    # same prompt, different replicas → identical greedy output
    assert out1.output_token_ids == out2.output_token_ids
    assert llm._rr == 2                      # round-robined over replicas
    assert not llm._seq_replica              # routing entries cleaned up
    # all pages released on both replicas
    for mm in llm.memory_managers:
        assert mm.num_free_pages == mm.allocator.num_total


def test_dp2_chunked_prefill_matches_dp1(ckpt):
    rng = np.random.default_rng(3)
    long_prompt = [int(x) for x in rng.integers(2, 120, size=40)]
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    a = make_llm(ckpt, max_prefill_tokens=8, min_prefill_tokens=4).generate(
        prompt_token_ids=[long_prompt], sampling_params=sp)[0]
    b = make_llm(ckpt, dp=2, max_prefill_tokens=8,
                 min_prefill_tokens=4).generate(
        prompt_token_ids=[long_prompt, long_prompt],
        sampling_params=sp)
    assert b[0].output_token_ids == a.output_token_ids
    assert b[1].output_token_ids == a.output_token_ids


def test_dp2_moe_ep(ckpt, tmp_path):
    """MoE under DP: experts shard over tp within each replica; outputs
    must match dp=1."""
    from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM
    torch.manual_seed(8)
    Qwen2MoeForCausalLM(Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        moe_intermediate_size=32, shared_expert_intermediate_size=48,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=False,
        decoder_sparse_step=1, mlp_only_layers=[],
        max_position_embeddings=256, eos_token_id=0)).save_pretrained(
        tmp_path, safe_serialization=True)
    prompts = [[7, 3, 56], [99, 14, 2, 8]]
    sp = SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True)

    def run(dp):
        cfg = EngineConfig(
            model=str(tmp_path), dtype="float32", max_model_len=128,
            cache=CacheConfig(page_size=4, num_pages=64),
            parallel=ParallelConfig(dp=dp, tp=2, enable_ep=True))
        return [o.output_token_ids for o in LLM(config=cfg).generate(
            prompt_token_ids=prompts, sampling_params=sp)]

    assert run(2) == run(1)


def test_dp2_pallas_matches_dp1_xla(ckpt):
    """dp=2 with attention_impl='pallas' (shard_map manual over the dp
    axis, kernels in interpret mode on CPU) is byte-identical to dp=1
    XLA — the reference runs FA3 in every DP replica
    (worker.py:750-829)."""
    rng = np.random.default_rng(7)
    prompts = [[int(x) for x in rng.integers(2, 120, size=int(n))]
               for n in rng.integers(2, 30, size=5)]
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)

    base = [o.output_token_ids
            for o in make_llm(ckpt, attention_impl="xla").generate(
                prompt_token_ids=prompts, sampling_params=sp)]
    dp2 = [o.output_token_ids
           for o in make_llm(ckpt, dp=2, attention_impl="pallas").generate(
               prompt_token_ids=prompts, sampling_params=sp)]
    assert base == dp2


def test_dp2_tp2_pallas_matches_dp1_xla(ckpt):
    """dp=2 × tp=2 with Pallas attention: the dp axis is manual
    (shard_map), tp stays auto inside and the attention dispatch nests
    its tp shard_map over the context mesh."""
    rng = np.random.default_rng(9)
    prompts = [[int(x) for x in rng.integers(2, 120, size=int(n))]
               for n in rng.integers(2, 30, size=4)]
    sp = SamplingParams(temperature=0.0, max_tokens=7, ignore_eos=True)

    base = [o.output_token_ids
            for o in make_llm(ckpt, attention_impl="xla").generate(
                prompt_token_ids=prompts, sampling_params=sp)]
    dp2 = [o.output_token_ids
           for o in make_llm(ckpt, dp=2, tp=2,
                             attention_impl="pallas").generate(
               prompt_token_ids=prompts, sampling_params=sp)]
    assert base == dp2


def test_dp2_logprobs_match_dp1(ckpt):
    """Output + prompt logprobs under dp=2 (reference computes logprobs
    from every worker, sampler.py:71-91) match dp=1 numerically."""
    rng = np.random.default_rng(5)
    prompts = [[int(x) for x in rng.integers(2, 120, size=int(n))]
               for n in rng.integers(4, 24, size=4)]
    sps = [SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True,
                          logprobs=3, prompt_logprobs=2),
           SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True,
                          logprobs=2),
           SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True),
           SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True,
                          prompt_logprobs=1)]

    def run(dp):
        return make_llm(ckpt, dp=dp).generate(prompt_token_ids=prompts,
                                              sampling_params=sps)

    base, dp2 = run(1), run(2)
    for a, b in zip(base, dp2):
        assert a.output_token_ids == b.output_token_ids
        assert (a.logprobs is None) == (b.logprobs is None)
        if a.logprobs is not None:
            for (ca, ia, la), (cb, ib, lb) in zip(a.logprobs, b.logprobs):
                assert ia == ib
                np.testing.assert_allclose([ca] + la, [cb] + lb,
                                           rtol=1e-5, atol=1e-6)
        assert (a.prompt_logprobs is None) == (b.prompt_logprobs is None)
        if a.prompt_logprobs is not None:
            for pa, pb in zip(a.prompt_logprobs, b.prompt_logprobs):
                assert (pa is None) == (pb is None)
                if pa is not None:
                    assert pa[1] == pb[1]
                    np.testing.assert_allclose(
                        [pa[0]] + pa[2], [pb[0]] + pb[2],
                        rtol=1e-5, atol=1e-6)


def test_dp2_penalties_match_dp1(ckpt):
    """Penalty requests under dp (stacked PenaltyTokens with a shared
    length bucket, one replica penalized + one idle/plain)."""
    rng = np.random.default_rng(1)
    prompts = [[int(x) for x in rng.integers(2, 120, size=int(n))]
               for n in rng.integers(4, 40, size=4)]
    sps = [SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                          repetition_penalty=1.5, presence_penalty=0.4,
                          frequency_penalty=0.2),
           SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True),
           SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                          repetition_penalty=2.0),
           SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)]

    base = [o.output_token_ids
            for o in make_llm(ckpt).generate(prompt_token_ids=prompts,
                                             sampling_params=sps)]
    dp2 = [o.output_token_ids
           for o in make_llm(ckpt, dp=2).generate(prompt_token_ids=prompts,
                                                  sampling_params=sps)]
    assert base == dp2


# ---- per-DP-replica endpoints / request pinning ---------------------------

def _prefix_llm(ckpt, dp):
    cfg = EngineConfig(
        model=ckpt, dtype="float32", max_model_len=128,
        cache=CacheConfig(page_size=4, num_pages=64,
                          enable_prefix_caching=True),
        parallel=ParallelConfig(dp=dp))
    return LLM(config=cfg)


def test_dp_pinning_keeps_prefix_cache_warm(ckpt):
    """target_dp pins a seq to one replica; a multi-turn conversation's
    second turn warm-hits that replica's prefix cache. Round-robin sends
    turn 2 to the OTHER replica: no hit (reference --endpoint-per-dp
    rationale, llm_engine.py:121-133)."""
    from gllm_tpu.sampling_params import SamplingParams
    prompt = list(range(1, 25))             # 6 full pages of prefix
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)

    llm = _prefix_llm(ckpt, dp=2)
    for _ in range(2):                      # two turns, pinned to dp0
        seq = llm._allocate_seq(list(prompt), sp)
        seq.target_dp = 0
        llm.add_seq(seq)
        while llm.schedulers[0].has_unfinished:
            llm.step()
    pinned_hits = llm.schedulers[0].mm.hit_tokens

    # control: force turn 2 onto the OTHER replica → its cache is cold.
    # (Without any pin, cache-aware routing would follow the cache — see
    # test_dp_cache_aware_routing.)
    rr = _prefix_llm(ckpt, dp=2)
    for pin in (0, 1):
        seq = rr._allocate_seq(list(prompt), sp)
        seq.target_dp = pin
        rr.add_seq(seq)
        while any(s.has_unfinished for s in rr.schedulers):
            rr.step()
    assert rr.schedulers[0].mm.hit_tokens == 0
    assert rr.schedulers[1].mm.hit_tokens == 0
    assert pinned_hits > 0


def test_endpoint_per_dp_http_pins_requests(ckpt):
    """serve_per_dp: one listener per replica over ONE shared engine;
    requests to listener d land on scheduler d."""
    import http.client
    import json as _json
    import threading

    from gllm_tpu.entrypoints.api_server import serve_per_dp
    llm = _prefix_llm(ckpt, dp=2)
    servers = serve_per_dp(llm, "127.0.0.1", [0, 0])
    ports = [s.server_address[1] for s in servers]
    threads = [threading.Thread(target=s.serve_forever, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    try:
        for d, port in enumerate(ports):
            for _ in range(2):
                c = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=60)
                c.request("POST", "/v1/completions", body=_json.dumps({
                    "prompt": [5, 6, 7, 8] * 5, "max_tokens": 3,
                    "temperature": 0, "ignore_eos": True}),
                    headers={"Content-Type": "application/json"})
                r = c.getresponse()
                assert r.status == 200, r.read()
                r.read()
                c.close()
        # each endpoint pinned its two requests to its own replica:
        # turn 2 warm-hits the same replica's prefix cache on BOTH
        assert llm.schedulers[0].mm.hit_tokens > 0
        assert llm.schedulers[1].mm.hit_tokens > 0
    finally:
        for s in servers:
            s.shutdown()
        servers[0].state.engine.shutdown()


def test_dp_cache_aware_routing(ckpt):
    """Without endpoint pinning, an UNPINNED second turn routes to the
    replica holding its prefix (cache-aware routing, beyond the
    reference's round-robin) — but a request with no substantial match
    still round-robins."""
    from gllm_tpu.sampling_params import SamplingParams
    llm = _prefix_llm(ckpt, dp=2)
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    convo = list(range(1, 25))              # 6 full pages

    def run(prompt):
        seq = llm._allocate_seq(list(prompt), sp)
        llm.add_seq(seq)
        replica = llm._seq_replica[seq.seq_id]
        while any(s.has_unfinished for s in llm.schedulers):
            llm.step()
        return replica

    r1 = run(convo)                         # lands by round-robin
    # turn 2 shares the whole turn-1 prompt → must follow the cache
    r2 = run(convo + [90, 91, 92, 93])
    assert r2 == r1, (r1, r2)
    assert llm.schedulers[r1].mm.hit_tokens > 0
    # unrelated prompt: no match → round-robin continues across replicas
    seen = {run([100 + i for i in range(20)]),
            run([60 + i for i in range(20)])}
    assert len(seen) == 2, seen


def test_dp_cache_routing_short_shared_prefix_balances(ckpt):
    """A SHORT shared prefix (under half the prompt) must not funnel all
    traffic to one replica."""
    from gllm_tpu.sampling_params import SamplingParams
    llm = _prefix_llm(ckpt, dp=2)
    sp = SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True)
    sys_prompt = [7, 8, 9, 10]              # one page of shared prefix
    replicas = []
    for i in range(4):
        body = [20 + 5 * i + j for j in range(20)]  # 5 distinct pages
        seq = llm._allocate_seq(sys_prompt + body, sp)
        llm.add_seq(seq)
        replicas.append(llm._seq_replica[seq.seq_id])
        while any(s.has_unfinished for s in llm.schedulers):
            llm.step()
    assert len(set(replicas)) == 2, replicas
