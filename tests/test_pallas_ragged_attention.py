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
from gllm_tpu.ops.pallas.paged_kv import heads_a_load
from gllm_tpu.ops.pallas.ragged_attention import (_decode_prefix_len,
                                                  block_form,
                                                  ragged_paged_attention)


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
    # ... under rows enough to span decode groups, two chunks and a
    # padded tail
    dict(seqs=[(1, k) for k in (3, 9, 14, 6, 30, 8)] + [(5, 9), (7, 7)],
         pad_seqs=3, Hq=8, Hkv=2, D=32, page=4, pages=40, int8=True),
    # one KV head with a cache of values of its own: decode rows, a chunk
    # and a padded tail
    dict(seqs=[(1, 5), (1, 9), (1, 13), (6, 6)], pad_seqs=3, Hq=4, Hkv=1,
         D=64, page=4, pages=16),
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


# The ragged body under several KV heads (PR 46). One batch for every
# case, at q blocks of 8 tokens and kv blocks of 16: one-token rows whose
# q block spans sequences, with kv lengths 1 and one short of, on and one
# past a kv block; a chunk behind a cached prefix (the ``docqa`` shape); a
# fresh chunk that spans q blocks; padded sequences of kv length 0.
FORM_SEQS = [(1, 1), (1, 15), (1, 16), (1, 17), (9, 60), (21, 21), (3, 40)]
FORMS = {
    # a 16-bit cache under a q of its dtype: as stored, p in two parts
    "bf16": dict(q="bfloat16", kv="bfloat16", form=("bfloat16", 2)),
    # a float32 cache keeps float32 operands
    "f32": dict(q="float32", kv="float32", form=("float32", 1)),
    # int8 blocks are dequantized in VMEM: float32 operands
    "int8": dict(q="bfloat16", kv="int8", form=("float32", 1)),
    # a q of another dtype than the cache's: float32 operands
    "q_f32": dict(q="float32", kv="bfloat16", form=("float32", 1)),
}
# The cells' geometries cut down (query heads x KV heads, the heads a KV
# head kept): qwen3-4b's 32 x 8, olmo-hybrid's 32 x 32, nemotron's 32 x 2,
# command-a-plus's 128 x 8 in a full layer and under a window whose edge
# falls inside a fetched block.
FORM_GEOMETRIES = {
    "32x8": dict(Hq=8, Hkv=2),
    "32x32": dict(Hq=4, Hkv=4),
    "32x2": dict(Hq=32, Hkv=2),
    "128x8": dict(Hq=64, Hkv=4),
    "128x8_window": dict(Hq=64, Hkv=4, window=20),
    # an odd head count: a 16-bit cache's heads do not pair up in a word
    "odd_heads": dict(Hq=6, Hkv=3),
}


def _as_f64(x):
    return np.asarray(jnp.asarray(x, jnp.float32), np.float64)


def _kernel_ops(fn, *args):
    """(primitive, operands' (shape, dtype)) of every operation in the
    Pallas kernel ``fn`` traces to, loops and branches walked."""
    import jax
    ops = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            ops.append((eqn.primitive.name,
                        [(tuple(v.aval.shape), str(v.aval.dtype))
                         for v in eqn.invars if hasattr(v, "aval")
                         and hasattr(v.aval, "shape")]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                walk(eqn.params["jaxpr"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                find(sub)

    find(jax.make_jaxpr(fn)(*args).jaxpr)
    return ops


@pytest.mark.parametrize("geometry", list(FORM_GEOMETRIES))
@pytest.mark.parametrize("form", list(FORMS))
def test_block_form_is_chosen_from_the_call(form, geometry):
    """Under several KV heads the ragged body takes its form from the
    dtypes and the quantization it sees (``block_form``): what the lowered
    kernel's products take is that form, no KV block is widened or
    transposed where the operands are 16-bit, and every form agrees with
    float64 arithmetic on the same inputs (the result is rounded once to
    q's dtype: half a bf16 ulp = 2**-9, so 2**-8 leaves a factor of two)."""
    rng = np.random.default_rng(46)
    want_form = FORMS[form]["form"]
    geo = dict(FORM_GEOMETRIES[geometry])
    window = geo.pop("window", None)
    Hq, Hkv, D, page, pages = geo["Hq"], geo["Hkv"], 64, 8, 40
    q, kc, vc, md = build_case(rng, FORM_SEQS, Hq, Hkv, D, page, pages,
                               pad_seqs=2)
    q = jnp.asarray(q, FORMS[form]["q"])
    kw = {}
    if form == "int8":
        ks, vs = (rng.uniform(0.01, 0.02, (pages, Hkv)).astype(np.float32)
                  for _ in range(2))
        kc = rng.integers(-127, 128, kc.shape).astype(np.int8)
        vc = rng.integers(-127, 128, vc.shape).astype(np.int8)
        kw.update(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
        kc_j, vc_j = jnp.asarray(kc), jnp.asarray(vc)
        k_ref = kc.astype(np.float64) * ks[:, None, :, None]
        v_ref = vc.astype(np.float64) * vs[:, None, :, None]
    else:
        kc_j, vc_j = (jnp.asarray(x, FORMS[form]["kv"]) for x in (kc, vc))
        k_ref, v_ref = _as_f64(kc_j), _as_f64(vc_j)
    assert block_form(q.dtype, kc_j.dtype, form == "int8") == want_form

    bq, bk, G = 8, 16, Hq // Hkv
    call = lambda q, kc, vc: ragged_paged_attention(
        q, kc, vc, md.cu_q_lens, md.kv_lens, md.page_table, scale=D ** -0.5,
        q_block=bq, kv_block=bk, interpret=True, window=window, **kw)
    ops = _kernel_ops(call, q, kc_j, vc_j)
    dots = [ins for prim, ins in ops if prim == "dot_general"]
    rows = bq * G
    # a head at a time (a rolled loop over the loads; a load of a 16-bit
    # cache brings a pair): [rows, D] x [BK, D], then p x [BK, D], p's
    # two parts stacked along the rows of ONE product
    assert dots == heads_a_load(Hkv, kc_j.dtype) * [
        [((rows, D), want_form[0]), ((bk, D), want_form[0])],
        [((want_form[1] * rows, bk), want_form[0]),
         ((bk, D), want_form[0])]], dots
    block = {(bk // page, page, D), (bk, D)}
    if want_form[1] == 2:
        widened = [ins for prim, ins in ops if prim == "convert_element_type"
                   and ins[0][0] in block and ins[0][1] != "uint32"]
        assert not widened, widened
    relaid = [ins for prim, ins in ops if prim == "transpose"
              and ins[0][0][-1] == D and len(ins[0][0]) != 4]
    assert not relaid, relaid        # q and the result alone, both 4-D

    got = call(q, kc_j, vc_j)
    assert got.dtype == q.dtype
    q64, cu = _as_f64(q), np.asarray(md.cu_q_lens)
    want = np.zeros(got.shape)
    for s, (q_len, kv_len) in enumerate(FORM_SEQS):
        pt = np.asarray(md.page_table[s])
        k = np.concatenate([k_ref[p] for p in pt])[:kv_len]
        v = np.concatenate([v_ref[p] for p in pt])[:kv_len]
        for t in range(q_len):
            pos = kv_len - q_len + t
            lo = max(0, pos - window + 1) if window else 0
            for h in range(Hq):
                sc = (q64[cu[s] + t, h] @ k[lo:pos + 1, h // G].T) * D ** -0.5
                p_ = np.exp(sc - sc.max())
                want[cu[s] + t, h] = (p_ / p_.sum()) @ v[lo:pos + 1, h // G]
    rtol = 2 ** -8 if FORMS[form]["q"] == "bfloat16" else 2e-4
    np.testing.assert_allclose(_as_f64(got), want, rtol=rtol, atol=1e-5)


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


def test_decode_prefix_len_derivation():
    """What the dispatch splits a mixed step by derives from cu_q_lens
    alone: the decode prefix is the longest run of one-token sequences."""
    cu = jnp.asarray([0, 1, 2, 3, 8, 9, 9, 9], jnp.int32)  # 3 decode,
    assert int(_decode_prefix_len(cu, 7)) == 3              # then a chunk
    cu = jnp.asarray([0, 1, 2, 3, 4, 4, 4], jnp.int32)     # pure decode
    assert int(_decode_prefix_len(cu, 6)) == 4              # (+ padding)
    cu = jnp.asarray([0, 5, 6, 7], jnp.int32)               # prefill first
    assert int(_decode_prefix_len(cu, 3)) == 0


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
