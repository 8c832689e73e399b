"""Ragged paged attention kernel vs the XLA oracle (interpret mode on CPU).

Covers mixed prefill+decode batches — the layout the engine emits for
chunked prefill (reference flash_attn_varlen_func semantics): each seq
attends to its cached context plus the causal part of its own new chunk.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from gllm_tpu.ops.attention import (AttentionMetadata, _paged_attention,
                                    _xla_paged_attention)
from gllm_tpu.ops.pallas.ragged_attention import ragged_paged_attention


def build_case(rng, seqs, Hq, Hkv, D, page, num_pages, pad_seqs=0):
    """seqs: list of (q_len, kv_len) with kv_len >= q_len (context includes
    the new tokens, matching the engine's post-step kv_lens)."""
    S = len(seqs) + pad_seqs
    T = sum(q for q, _ in seqs)
    k_cache = rng.standard_normal((num_pages, page, Hkv, D)).astype(
        np.float32)
    v_cache = rng.standard_normal((num_pages, page, Hkv, D)).astype(
        np.float32)
    max_pages = max(-(-kv // page) for _, kv in seqs)
    pt = np.zeros((S, max_pages), np.int32)
    cu = np.zeros(S + 1, np.int32)
    kv_lens = np.zeros(S, np.int32)
    next_page = 1
    off = 0
    for i, (q_len, kv_len) in enumerate(seqs):
        n = -(-kv_len // page)
        pt[i, :n] = np.arange(next_page, next_page + n)
        next_page += n
        kv_lens[i] = kv_len
        off += q_len
        cu[i + 1] = off
    cu[len(seqs) + 1:] = off
    assert next_page <= num_pages
    q = rng.standard_normal((T, Hq, D)).astype(np.float32)
    md = AttentionMetadata(
        cu_q_lens=jnp.asarray(cu), kv_lens=jnp.asarray(kv_lens),
        page_table=jnp.asarray(pt),
        num_seqs=jnp.asarray(len(seqs), jnp.int32))
    return q, k_cache, v_cache, md


CASES = [
    # single prefill
    dict(seqs=[(12, 12)], Hq=4, Hkv=2, D=64, page=4, pages=8),
    # chunked prefill: new chunk attends to prior cached context
    dict(seqs=[(8, 29)], Hq=4, Hkv=2, D=64, page=4, pages=12),
    # mixed: decode rows + prefill chunks, unsorted sizes
    dict(seqs=[(1, 17), (9, 9), (1, 5), (13, 20)], Hq=8, Hkv=2, D=64,
         page=8, pages=16),
    # many decode rows spanning a q block + one prefill
    dict(seqs=[(1, 3)] * 7 + [(21, 21)], Hq=4, Hkv=4, D=32, page=4,
         pages=24),
    # padded seq rows at the tail (cu repeats, kv_len 0)
    dict(seqs=[(6, 6), (1, 9)], pad_seqs=3, Hq=4, Hkv=1, D=64, page=4,
         pages=8),
    # MQA with distinct v_dim exercised separately below
]

# The same oracle through the dispatch (``_paged_attention``, pallas against
# xla): a batch with ``max_q_len > 1`` sends its leading one-token
# sequences to the decode kernel and the rest to the ragged kernel.
SPLIT_CASES = [
    # decode rows first + one chunk with cached context (GQA)
    dict(seqs=[(1, 17), (1, 5), (1, 30), (9, 21)], Hq=8, Hkv=2, D=64,
         page=8, pages=16),
    # the same in the MLA layout: one KV head, values the keys' prefix
    dict(seqs=[(1, 9), (1, 14), (1, 3), (6, 19)], Hq=4, Hkv=1, D=64,
         page=4, pages=16, v_dim=32),
    # no decode rows: the decode call's rows all skip
    dict(seqs=[(7, 7), (5, 18)], Hq=4, Hkv=2, D=64, page=4, pages=12),
    # every row decodes, but the step was built for longer rows
    dict(seqs=[(1, 6), (1, 11), (1, 1)], max_q=4, Hq=4, Hkv=2, D=64,
         page=4, pages=8),
    # a one-token sequence BEHIND a chunk stays with the ragged kernel
    dict(seqs=[(1, 8), (5, 12), (1, 10)], Hq=4, Hkv=2, D=64, page=4,
         pages=12),
    # padded sequences (kv_len 0) at the tail: more sequence rows than
    # tokens, so q is padded for the decode call
    dict(seqs=[(1, 9), (1, 4), (3, 7)], pad_seqs=5, Hq=4, Hkv=2, D=64,
         page=4, pages=8),
    # the int8 cache with its scales
    dict(seqs=[(1, 13), (1, 6), (8, 15)], Hq=4, Hkv=2, D=64, page=4,
         pages=12, int8=True),
]
for _case in SPLIT_CASES:
    _case["dispatch"] = True


def _through_the_dispatch(rng, case, q, kc, vc, md, scale, max_q):
    """(got, want): ``_paged_attention`` with the Pallas kernels (interpret
    mode here) and with the XLA oracle, on the same arrays."""
    kw = {}
    if case.get("v_dim"):
        vc, kw["v_dim"] = None, case["v_dim"]
    if case.get("int8"):
        kc = rng.integers(-127, 128, kc.shape).astype(np.int8)
        vc = rng.integers(-127, 128, vc.shape).astype(np.int8)
        scales = [jnp.asarray(rng.uniform(0.005, 0.02, kc.shape[::2])
                              .astype(np.float32)) for _ in range(2)]
    else:
        scales = [None, None]
    args = (jnp.asarray(q), jnp.asarray(kc),
            None if vc is None else jnp.asarray(vc), md, *scales)
    return [_paged_attention(*args, scale=scale, max_q_len=max_q, impl=impl,
                             **kw) for impl in ("pallas", "xla")]


@pytest.mark.parametrize("case", CASES + SPLIT_CASES)
def test_matches_xla_oracle(case):
    rng = np.random.default_rng(7)
    case = dict(case)
    pad_seqs = case.pop("pad_seqs", 0)
    q, kc, vc, md = build_case(rng, case["seqs"], case["Hq"], case["Hkv"],
                               case["D"], case["page"], case["pages"],
                               pad_seqs)
    scale = case["D"] ** -0.5
    max_q = case.get("max_q", max(ql for ql, _ in case["seqs"]))
    if case.get("dispatch"):
        got, want = _through_the_dispatch(rng, case, q, kc, vc, md, scale,
                                          max_q)
    else:
        want = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kc),
                                    jnp.asarray(vc), md, scale=scale,
                                    max_q_len=max_q)
        got = ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
            md.kv_lens, md.page_table, scale=scale, q_block=8, kv_block=16,
            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert not np.isnan(np.asarray(got)).any()


def test_a_mixed_batch_holds_both_kernels_under_the_ragged_name():
    """What the trace's readers rely on (perfbench ``trace_patterns``): the
    riding rows' call is the decode kernel under a name that starts with
    the ragged kernel's, and a decode-only batch keeps the decode kernel's
    own."""
    import jax
    rng = np.random.default_rng(1)
    q, kc, vc, md = build_case(rng, [(1, 9), (1, 4), (3, 7)], 4, 2, 64, 4, 8)

    def kernel_names(jaxpr):
        """Each pallas_call's name, and the jitted function it sits in:
        the name the TPU compiler gives a call that has none."""
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                inner = list(kernel_names(sub))
                yield from ([eqn.params["name"]] if inner == [None]
                            else inner)

    def names(max_q_len, q):
        return sorted(kernel_names(jax.make_jaxpr(
            lambda *a: _paged_attention(*a, scale=0.125,
                                        max_q_len=max_q_len, impl="pallas"))(
                jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md).jaxpr))

    assert names(3, q) == ["ragged_paged_attention",
                           "ragged_paged_attention_decode_rows"]
    assert names(1, q[:3]) == ["paged_decode_attention"]


def test_q_block_spanning_many_seqs():
    """One q block covering several sequences (the decode-heavy mixed case):
    per-row online-softmax state must not leak across seq boundaries."""
    rng = np.random.default_rng(3)
    seqs = [(1, k) for k in [3, 9, 1, 14, 6, 2, 30, 8]] + [(5, 5)]
    q, kc, vc, md = build_case(rng, seqs, 4, 2, 32, 4, 32)
    scale = 0.2
    want = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kc),
                                jnp.asarray(vc), md, scale=scale,
                                max_q_len=5)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=scale, q_block=16, kv_block=8,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_long_context_online_softmax():
    rng = np.random.default_rng(11)
    q, kc, vc, md = build_case(rng, [(4, 260)], 4, 2, 64, 8, 40)
    scale = 0.125
    want = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kc),
                                jnp.asarray(vc), md, scale=scale,
                                max_q_len=4)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=scale, q_block=4, kv_block=16,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_distinct_v_dim_mla_layout():
    """Values as the latent prefix of keys (MLA absorbed layout: Dv < D)."""
    rng = np.random.default_rng(5)
    Hq, D, Dv, page, num_pages = 4, 64, 32, 4, 16
    seqs = [(6, 13), (1, 8)]
    S = len(seqs)
    T = sum(q for q, _ in seqs)
    k_cache = rng.standard_normal((num_pages, page, 1, D)).astype(np.float32)
    v_cache = k_cache[..., :Dv].copy()
    max_pages = 4
    pt = np.zeros((S, max_pages), np.int32)
    cu = np.zeros(S + 1, np.int32)
    kv_lens = np.zeros(S, np.int32)
    next_page, off = 1, 0
    for i, (ql, kv) in enumerate(seqs):
        n = -(-kv // page)
        pt[i, :n] = np.arange(next_page, next_page + n)
        next_page += n
        kv_lens[i] = kv
        off += ql
        cu[i + 1] = off
    q = rng.standard_normal((T, Hq, D)).astype(np.float32)
    md = AttentionMetadata(cu_q_lens=jnp.asarray(cu),
                           kv_lens=jnp.asarray(kv_lens),
                           page_table=jnp.asarray(pt),
                           num_seqs=jnp.asarray(S, jnp.int32))
    scale = D ** -0.5
    want = _xla_paged_attention(jnp.asarray(q), jnp.asarray(k_cache),
                                jnp.asarray(v_cache), md, scale=scale,
                                max_q_len=6)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        md.cu_q_lens, md.kv_lens, md.page_table, scale=scale, q_block=8,
        kv_block=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_engine_e2e_with_pallas_mixed(tmp_path):
    """Full engine with attention_impl='pallas': prefill now runs the
    ragged kernel (interpret on CPU); output must match the xla impl."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.sampling_params import SamplingParams

    torch.manual_seed(9)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        max_position_embeddings=128, eos_token_id=0, attention_bias=False))
    model.save_pretrained(tmp_path, safe_serialization=True)

    prompts = [[5, 9, 23, 40, 2, 71, 33], [8, 1], [99, 98, 97, 96, 95, 94,
                                                   93, 92, 91, 90, 89, 88]]

    def run(impl):
        cfg = EngineConfig(
            model=str(tmp_path), dtype="float32", max_model_len=64,
            attention_impl=impl,
            scheduler=SchedulerConfig(max_prefill_tokens=8,
                                      min_prefill_tokens=4),
            cache=CacheConfig(page_size=4, num_pages=64))
        return [o.output_token_ids for o in LLM(config=cfg).generate(
            prompt_token_ids=prompts,
            sampling_params=SamplingParams(temperature=0.0, max_tokens=6,
                                           ignore_eos=True))]

    assert run("pallas") == run("xla")
