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


# ---- a decoding row on the decode kernel under the selection's mask --------

def _dsa_layer_call(kind):
    """One full layer's ``_dsa_attention`` at toy sizes in bf16, a context
    several times the top-k: (call(attn_impl) -> (out, index_cache, stats),
    the flat indices of the one-token rows, of the chunk's rows and of
    the padding). ``decode``: four rows, one of them padding; ``mixed``:
    two decoding rows, a 37-token chunk and a one-token row behind it."""
    import jax
    from gllm_tpu.batching import StepBatch
    from gllm_tpu.models import deepseek as ds
    from gllm_tpu.ops.attention import AttentionMetadata
    cfg = dataclasses.replace(
        ModelConfig(**V32), index_topk=16, kv_lora_rank=128,
        qk_rope_head_dim=8)
    g = ds.geom(cfg)
    page, P, max_pages = 4, 64, 24
    if kind == "decode":
        q_lens, ctx, T, max_q = [1, 1, 0, 1], [50, 90, 0, 7], 4, 1
    else:
        q_lens, ctx, T, max_q = [1, 1, 37, 1], [60, 23, 49, 31], 48, 37
    S = len(q_lens)
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    kv_lens = np.asarray(ctx, np.int32)
    pt = np.zeros((S, max_pages), np.int32)
    nxt = 1
    for s in range(S):
        n = -(-ctx[s] // page)
        pt[s, :n] = np.arange(nxt, nxt + n)
        nxt += n
    pos = np.zeros(T, np.int32)
    slots = np.zeros(T, np.int32)
    for s in range(S):
        p = ctx[s] - q_lens[s] + np.arange(q_lens[s])
        pos[cu[s]:cu[s + 1]] = p
        slots[cu[s]:cu[s + 1]] = pt[s, p // page] * page + p % page
    ks = iter(jax.random.split(jax.random.key(4), 16))
    bf = lambda shape, scale=1.0: (jax.random.normal(
        next(ks), shape, jnp.float32) * scale).astype(jnp.bfloat16)
    H, nh, hd = cfg.hidden_size, cfg.index_n_heads, cfg.index_head_dim
    lp = {"idx_wq_b": bf((cfg.q_lora_rank, nh * hd), 0.2),
          "idx_wk": bf((H, hd), 0.2), "idx_weights": bf((H, nh), 0.2),
          "idx_k_norm_w": jnp.ones((hd,), jnp.bfloat16),
          "idx_k_norm_b": jnp.zeros((hd,), jnp.bfloat16)}
    x, q_resid = bf((T, H)), bf((T, cfg.q_lora_rank))
    q_full = bf((T, g.heads, g.width))
    latent, index = bf((P, page, g.width)), bf((P, page, hd))
    batch = StepBatch(
        token_ids=None, positions=jnp.asarray(pos),
        slot_mapping=jnp.asarray(slots), logits_indices=None, sampling=None,
        attn=AttentionMetadata(jnp.asarray(cu), jnp.asarray(kv_lens),
                               jnp.asarray(pt), jnp.int32(S)))

    def call(attn_impl):
        fn = jax.jit(lambda *a: ds._dsa_attention(
            lp, *a, None, cfg, ds.make_rope_table(cfg), max_q_len=max_q,
            g=g, attn_impl=attn_impl))
        out, icache, _, stats = fn(x, q_resid, q_full, batch, latent, index)
        text = str(jax.make_jaxpr(fn)(x, q_resid, q_full, batch, latent,
                                      index))
        return np.asarray(out), np.asarray(icache, np.float32), \
            np.asarray(stats), ds.DSA_ROWS_NAME in text
    rows = [int(cu[s]) for s in range(S) if q_lens[s] == 1]
    chunk = [t for s in range(S) if q_lens[s] > 1
             for t in range(cu[s], cu[s + 1])]
    return call, rows, chunk, list(range(int(cu[-1]), T))


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_decoding_rows_on_the_masked_kernel_equal_the_xla_rows(kind):
    """``attn_impl="pallas"`` (interpret mode here) sends the one-token
    rows to ``paged_decode_attention`` under ``_largest``'s mask, contexts
    of 2-6 times the top-k; the result is the XLA rows' within the one
    rounding of the kernel's output to bf16. A sequence that brings a
    chunk gets its rows from the chunk loop on both paths (its first row,
    which the XLA form computes and discards, never reaches the kernel:
    context 0); the index cache and the counts do not depend on the path;
    padding reads zeros."""
    call, rows, chunk, padding = _dsa_layer_call(kind)
    want, icache_x, stats_x, named_x = call("xla")
    got, icache_k, stats_k, named_k = call("pallas")
    assert named_k and not named_x
    assert np.abs(want[rows]).max() > 0.1
    np.testing.assert_allclose(got[rows], want[rows], rtol=2 ** -7,
                               atol=2e-3)
    np.testing.assert_array_equal(got[chunk], want[chunk])
    assert bool(chunk) == (kind == "mixed")
    assert not got[padding].any() and not want[padding].any()
    np.testing.assert_array_equal(icache_k, icache_x)
    np.testing.assert_array_equal(stats_k, stats_x)


def test_rows_path_follows_the_resolved_impl_and_the_mesh():
    from gllm_tpu.models.deepseek import dsa_rows_path
    assert dsa_rows_path("pallas") == "kernel"
    assert dsa_rows_path("xla") == "xla"
    assert dsa_rows_path("pallas", meshed=True) == "xla"
    assert dsa_rows_path("pallas", meshed=False) == "kernel"
    import jax
    from jax.sharding import Mesh
    from gllm_tpu.parallel.mesh import mesh_context
    with mesh_context(Mesh(np.array(jax.devices()[:2]), ("tp",))):
        assert dsa_rows_path("pallas") == "xla"


def test_truncated_topk_serves_the_same_tokens_on_the_kernel_path():
    """The engine end to end in float32, contexts past the top-k: greedy
    tokens of ``attention_impl="pallas"`` (the masked decode call in
    interpret mode) equal the XLA path's, and the counter says which
    path attended the one-token rows: decoding rows x 3 layers, every
    layer of DeepSeek-V3.2 being a full one."""
    from gllm_tpu.models import deepseek
    mcfg = dataclasses.replace(ModelConfig(**V32), index_topk=8)
    params = deepseek.init_params(mcfg, seed=3, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    prompts = [[int(x) for x in rng.integers(2, 250, size=n)]
               for n in (30, 11)]

    def run(impl):
        cfg = EngineConfig(
            load_format="dummy", dtype="float32", max_model_len=128,
            attention_impl=impl,
            scheduler=SchedulerConfig(max_prefill_tokens=64),
            cache=CacheConfig(page_size=4, num_pages=128))
        llm = LLM(config=cfg, model_cfg=mcfg, params=params)
        before = {p: deepseek._M_DSA_ROWS.get(path=p)
                  for p in ("kernel", "xla")}
        toks = _greedy(llm, prompts, n=6)
        return toks, llm.runner.dsa_rows_path, {
            p: deepseek._M_DSA_ROWS.get(path=p) - before[p] for p in before}

    toks_x, path_x, grew_x = run("xla")
    toks_k, path_k, grew_k = run("pallas")
    assert toks_k == toks_x
    assert (path_x, path_k) == ("xla", "kernel")
    # the prompts' prefill emits token 1; five decode steps of two rows
    assert grew_x == {"kernel": 0, "xla": 2 * 5 * 3}
    assert grew_k == {"kernel": 2 * 5 * 3, "xla": 0}
