"""command-a-plus-05-2026 (model_type cohere2_moe) on the normal path, at a
toy size on the CPU: the configuration file against the catalog's row, the
served model against the plain reference (perfbench/reference/
cohere2_moe.py) through chunked prefill and decode and a prefix hit, the
window's edges, both kernels with a window against the masked XLA form in
interpret mode, the eight shares that add up to the uncut layer, the
counters, the start-up lines and the fences."""

import importlib.util
import json
import logging
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.models import cohere2_moe
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.sampling_params import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT,
                                                                     path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("perfbench/reference/cohere2_moe.py", "t_ref_cohere2_moe")

# the catalog's row (model-configs guide, architectures.jsonl,
# command-a-plus-05-2026), key by key
_PERIOD = 3 * ["sliding_attention"] + ["full_attention"]
CATALOG = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": 8 * _PERIOD, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}

# a toy of the same family: a window of 24 in 3 of 4 layers, 2 KV heads
# under 4 query heads each, 4 of 32 experts held (an eighth), top 8, two
# shared experts
TOY = {"model_type": "cohere2_moe", "vocab_size": 512, "hidden_size": 64,
       "intermediate_size": 32, "num_hidden_layers": 4,
       "layer_types": list(_PERIOD), "num_attention_heads": 8,
       "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 24,
       "num_experts": 4,
       "ep_share": {"chips": 8, "rank": 0, "num_experts": 32},
       "num_experts_per_tok": 8, "num_shared_experts": 2,
       "layer_norm_eps": 1e-5, "rope_theta": 50000,
       "tie_word_embeddings": True, "logit_scale": 1,
       "max_position_embeddings": 512, "use_parallel_block": True,
       "expert_selection_fn": "sigmoid", "norm_topk_prob": True}
SEED = 2 ** 31 + 44
PAGE, CHUNK, WINDOW = 4, 32, 24


def _config_file():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "command-a-plus-05-2026.json")) as f:
        return json.load(f)


# ---- the configuration ------------------------------------------------------

def test_configuration_file_holds_the_catalogs_row_key_by_key():
    hf = _config_file()
    differs = sorted(k for k, v in CATALOG.items() if hf.get(k, "-") != v)
    assert differs == sorted(hf["reduced"]) == [
        "layer_types", "max_position_embeddings", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert set(hf["reduced_why"]) == set(hf["reduced"])
    assert hf["layer_types"] == CATALOG["layer_types"][:4] == _PERIOD
    assert hf["ep_share"] == {"chips": 8, "rank": 0, "num_experts": 128}
    assert hf["vocab_size"] * 8 == CATALOG["vocab_size"]
    # both readings of "average" are named, the one taken and the other
    said = hf["assumed"]["shared_expert_combination_strategy"]
    assert "MEAN OF THE FOUR SHARED EXPERTS' OUTPUTS" in said
    assert "(routed + shared) / 2" in said and "NOT taken" in said
    for key in ("intermediate_size", "inert_keys", "left_out",
                "architectures", "positions", "router", "block"):
        assert key in hf["assumed"]
    assert "8 chips" in hf["deployment"] and "stage 0, rank 0" in \
        hf["deployment"]
    cfg = from_hf_config(hf)
    assert cfg.architecture == "Cohere2MoeForCausalLM"
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.rms_norm_eps, cfg.rope_theta) == (4096, 128, 8, 128, 1e-5,
                                                   50000)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.n_shared_experts, cfg.shared_expert_intermediate_size,
            cfg.expert_act) == (128, 16, 0, 8, 4096, 4, 16384, "swiglu")
    assert (cfg.scoring_func, cfg.topk_method, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.route_groups) == (
                "sigmoid", "none", True, 1.0, 0)
    assert (cfg.sliding_window, cfg.norm_kind, cfg.logit_scale,
            cfg.rope_interleaved, cfg.tie_word_embeddings) == (
                4096, "layer", 1, True, True)
    # windowed layers that keep PAGES: no rings, no per-sequence slots
    assert cfg.has_windowed_layers and cfg.paged_windows
    assert not (cfg.use_swa or cfg.use_seq_slots or cfg.use_mla
                or cfg.use_hybrid)
    from gllm_tpu.models import get_model_def
    assert get_model_def(cfg).family == "cohere2_moe"
    # the windowed latent family keeps rings and reads the other property
    dots = from_hf_config(json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "dots3-note-prev.json"))))
    assert dots.has_windowed_layers and dots.use_swa
    assert not dots.paged_windows


def test_derived_sizes_are_the_arithmetic_of_the_widths():
    d = _config_file()["derived"]
    attn = 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert attn == d["attention_params_per_layer"] == 142606336
    shared = 4 * 3 * 4096 * 4096
    layer = attn + 4096 * 128 + shared + 16 * 3 * 4096 * 4096 + 4096
    assert layer == d["layer_params_held"] == 1149767680
    params = 4 * layer + 32768 * 4096 + 4096
    assert params == d["params"] and d["weight_bytes"] == 2 * params \
        == 9466585088
    assert d["kv_bytes_per_token"] == 4 * 2 * 8 * 128 * 2 == 16384
    assert d["kv_pool_bytes"] == 17280 * 16 * 16384 == 4529848320
    assert d["tokens_per_expert_per_decode_step"] == 1.0
    shapes = jax.eval_shape(lambda: cohere2_moe.init_params(
        from_hf_config(_config_file()), dtype=jnp.bfloat16))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == params


def test_the_published_block_is_what_is_served_and_nothing_else():
    for key, other in (("use_parallel_block", False), ("use_qk_norm", True),
                       ("first_k_dense_replace", 1),
                       ("shared_expert_combination_strategy", "sum"),
                       ("position_embedding_type", "rope_neox"),
                       ("expert_selection_fn", "softmax")):
        with pytest.raises(ValueError, match=key):
            from_hf_config(dict(TOY, **{key: other}))
    with pytest.raises(ValueError, match="layer_types"):
        from_hf_config(dict(TOY, layer_types=_PERIOD[:3]))
    with pytest.raises(ValueError, match="ep_share"):
        from_hf_config(dict(TOY, ep_share={"chips": 3, "num_experts": 32}))


# ---- served against the reference -------------------------------------------

def _llm(dtype="float32", quantization=None, impl="xla", prefix=True):
    from gllm_tpu.engine.llm import LLM
    return LLM(config=EngineConfig(
        load_format="dummy", dtype=dtype, seed=SEED, max_model_len=256,
        max_num_seqs=8, quantization=quantization, attention_impl=impl,
        scheduler=SchedulerConfig(max_prefill_tokens=CHUNK,
                                  max_decode_seqs=8),
        cache=CacheConfig(page_size=PAGE, num_pages=256,
                          enable_prefix_caching=prefix)),
        model_cfg=from_hf_config(TOY))


def _served(llm, prompt, n_out, prompt_logprobs=1):
    out = llm.generate(
        prompt_token_ids=[prompt],
        sampling_params=[SamplingParams(
            temperature=0.0, max_tokens=n_out, ignore_eos=True,
            prompt_logprobs=prompt_logprobs, logprobs=5)])[0]
    prefill = ([float(t[0]) for t in out.prompt_logprobs[1:]]
               if prompt_logprobs else None)
    tops = [{int(i): float(v) for i, v in zip(ids, lps)}
            for _, ids, lps in out.logprobs]
    return prefill, tops, list(out.output_token_ids)


_WEIGHTS = {}


def _errors(dtype, prefill, tops, tokens, prompt):
    """(prefill error or None, decode error, the reference's logprob
    spread) against the reference's full forward pass over prompt +
    output, float32, on its own weights drawn in ``dtype``; an error is
    the root mean square difference of the logprobs."""
    if dtype not in _WEIGHTS:
        _WEIGHTS[dtype] = REF.make_weights(TOY, SEED, jnp.dtype(dtype))
    full = prompt + tokens
    want = ([[t] for t in prompt[1:]] + [[]] * len(tokens) + [[]])[:len(full)]
    for j, top in enumerate(tops):
        want[len(prompt) - 1 + j] = sorted(top)
    ref = REF.logprobs(TOY, _WEIGHTS[dtype], full, want)
    ref_prefill = [v[0] for v in ref[:len(prompt) - 1]]

    def rms(pairs):
        return float(np.sqrt(np.mean([(a - b) ** 2 for a, b in pairs])))
    dec = rms((top[t], r) for top, row in zip(tops, ref[len(prompt) - 1:])
              for t, r in zip(sorted(top), row))
    pre = rms(zip(prefill, ref_prefill)) if prefill else None
    return pre, dec, float(np.std(ref_prefill))


PROMPT = random.Random(5).choices(range(2, 512), k=70)

# float32 on both sides: what is left is the order of the sums (pages and
# kv blocks against one dense product; 1/sqrt(fan-in) weights, the tied
# embedding's fan-in the hidden size: a logprob spread of ~1): 1e-6
# measured, limit 1e-4. bf16 weights and stream against float32 arithmetic
# on the same bf16 weights, over 4 layers: ~0.006 (prefill) and ~0.004
# (decode) measured, limit 0.03. The served model with its layer matrices
# in int8 (float32 stream) reads ~0.02, three times bf16's, and is held
# apart from float32's 1e-6 here
# (tests/perfbench/test_reference_cohere2_moe.py: the reference with int8
# matrices is not ``correct``).
F32_TOL, BF16_TOL = 1e-4, 0.03


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_in_chunks_then_decode_agrees_with_the_reference(impl):
    """70 tokens in chunks of 32, 32 and 6 under a window of 24: the
    second chunk's queries attend cached pages and lose rows to the
    window, the decoded tokens see 24 of 70-78 positions in three layers
    and all of them in the fourth. Then the same document with another
    tail: a prefix hit of 60 tokens, longer than the window."""
    llm = _llm(impl=impl)
    prefill, tops, tokens = _served(llm, PROMPT, 8)
    pre, dec, spread = _errors("float32", prefill, tops, tokens, PROMPT)
    assert 0.5 < spread < 2.0
    assert pre < F32_TOL and dec < F32_TOL, (pre, dec)
    mm = llm.memory_manager
    asked, hit = mm.query_tokens, mm.hit_tokens
    again = PROMPT[:61] + [7, 8, 9, 10, 11]
    _, tops, tokens = _served(llm, again, 8, prompt_logprobs=None)
    assert mm.hit_tokens - hit == 60 > WINDOW          # 15 whole pages
    assert mm.query_tokens - asked == len(again)
    _, dec, _ = _errors("float32", None, tops, tokens, again)
    assert dec < F32_TOL, dec
    # the counters: rows read by kind of layer, and the experts'
    from gllm_tpu.models.deepseek import _M_MOE_ASSIGN, _M_MOE_STEPS
    rows = cohere2_moe._M_ROWS
    assert rows.get(kind="sliding", step="decode") % (3 * WINDOW) == 0
    assert rows.get(kind="full", step="decode") > rows.get(
        kind="sliding", step="decode") / 3 * 2
    assert rows.get(kind="sliding", step="mixed") > 0
    assert _M_MOE_STEPS.get(step="decode") >= 4 * 14
    assert _M_MOE_ASSIGN.get(where="held") > 0
    assert _M_MOE_ASSIGN.get(where="absent") > _M_MOE_ASSIGN.get(where="held")


def test_bf16_stays_within_its_bound_and_int8_does_not_pass_for_float32():
    prefill, tops, tokens = _served(_llm("bfloat16"), PROMPT, 8)
    pre, dec, spread = _errors("bfloat16", prefill, tops, tokens, PROMPT)
    assert 1e-4 < pre < BF16_TOL and 1e-4 < dec < BF16_TOL, (pre, dec)
    prefill, tops, tokens = _served(_llm(quantization="int8"), PROMPT, 8)
    pre, dec, _ = _errors("float32", prefill, tops, tokens, PROMPT)
    assert pre > 100 * F32_TOL and dec > 100 * F32_TOL, (pre, dec)


@pytest.mark.parametrize("length, why", [
    (WINDOW - 2, "the first decoded token at context window - 1"),
    (WINDOW - 1, "... at context = window: the oldest row still seen"),
    (WINDOW, "... at window + 1: the first row falls out"),
    (WINDOW + PAGE - 1, "the window's start crosses a page boundary"),
    (CHUNK + 8, "a second chunk whose queries straddle the window's start"),
    (2 * CHUNK + 3, "a third chunk wholly past the window"),
])
def test_the_windows_edges(length, why):
    """Decode from a prompt of ``length`` tokens on the Pallas path
    (interpret mode), 6 tokens: the window's edge passes through the
    decoded positions, a page boundary and a chunk's queries."""
    llm = _llm(impl="pallas", prefix=False)
    prompt = PROMPT[:length]
    prefill, tops, tokens = _served(llm, prompt, 6)
    pre, dec, _ = _errors("float32", prefill, tops, tokens, prompt)
    assert pre < F32_TOL and dec < F32_TOL, (why, pre, dec)


def test_without_the_window_the_answers_differ():
    """The control of the comparison itself: the same weights served with
    a window that never binds are NOT the reference's model."""
    from gllm_tpu.engine.llm import LLM
    wide = from_hf_config(dict(TOY, sliding_window=4096))
    llm = LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        max_num_seqs=8, scheduler=SchedulerConfig(max_prefill_tokens=CHUNK),
        cache=CacheConfig(page_size=PAGE, num_pages=256)), model_cfg=wide)
    prefill, tops, tokens = _served(llm, PROMPT, 4)
    pre, dec, _ = _errors("float32", prefill, tops, tokens, PROMPT)
    assert pre > 1000 * F32_TOL and dec > 1000 * F32_TOL, (pre, dec)


# ---- the kernels with a window, interpret mode against masked XLA -----------

def _paged_case(rng, q_lens, kv_lens, hq=8, hkv=2, d=32, page=8):
    from gllm_tpu.ops.attention import AttentionMetadata
    S, T = len(q_lens), sum(q_lens)
    pages = -(-max(kv_lens) // page) + 1
    pool = S * pages + 1
    kc, vc = (jnp.asarray(rng.normal(size=(pool, page, hkv, d)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(T, hq, d)), jnp.float32)
    table = np.zeros((S, pages), np.int32)
    perm, at = rng.permutation(np.arange(1, pool)), 0
    for s, kv in enumerate(kv_lens):
        n = -(-kv // page)
        table[s, :n] = perm[at:at + n]
        at += n
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    return q, kc, vc, AttentionMetadata(
        jnp.asarray(cu), jnp.asarray(kv_lens, jnp.int32), jnp.asarray(table),
        jnp.asarray(S, jnp.int32))


@pytest.mark.parametrize("q_lens, kv_lens, window", [
    ([1, 1, 1, 1], [5, 40, 300, 600], 64),          # decode rows
    ([1, 1, 1, 1, 1], [63, 64, 65, 320, 1], 64),    # the window's edges
    ([1, 1, 40, 0], [100, 300, 500, 0], 64),        # riding rows + a chunk
    ([1, 1, 300], [100, 700, 900], 128),            # a chunk over q blocks
    ([1, 600], [70, 600], 100),                     # a prompt from scratch
], ids=["decode", "edges", "riding", "chunk", "fresh"])
def test_kernels_with_a_window_agree_with_the_masked_xla_form(q_lens, kv_lens,
                                                              window):
    from gllm_tpu.ops.attention import paged_attention
    q, kc, vc, md = _paged_case(np.random.default_rng(0), q_lens, kv_lens)
    kw = dict(scale=32 ** -0.5, max_q_len=max(q_lens))
    want = paged_attention(q, kc, vc, md, impl="xla", window=window, **kw)
    got = paged_attention(q, kc, vc, md, impl="pallas", window=window, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # ... and the window binds in the case at all
    full = paged_attention(q, kc, vc, md, impl="xla", **kw)
    assert float(jnp.max(jnp.abs(full - want))) > 0.1


def test_the_masked_xla_form_is_the_mask_written_out():
    """One sequence, queries at 90 .. 99 of a context of 100 under a
    window of 16: each query's softmax over its own 16 keys by hand."""
    from gllm_tpu.ops.attention import paged_attention
    rng = np.random.default_rng(1)
    q, kc, vc, md = _paged_case(rng, [10], [100], hq=2, hkv=1, d=8, page=4)
    got = np.asarray(paged_attention(q, kc, vc, md, scale=0.5, max_q_len=10,
                                     impl="xla", window=16))
    pages = np.asarray(md.page_table)[0]
    keys = np.asarray(kc)[pages].reshape(-1, 8)[:100]
    vals = np.asarray(vc)[pages].reshape(-1, 8)[:100]
    for i in range(10):
        t = 90 + i
        lo = t - 16 + 1
        s = (np.asarray(q)[i] @ keys[lo:t + 1].T) * 0.5       # [2, 16]
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[i], p @ vals[lo:t + 1], atol=1e-5)


def test_a_call_without_a_window_traces_as_it_did():
    """``window=None`` adds no operand and no operation to either kernel:
    the jaxpr of a call that names the argument is the jaxpr of one that
    does not."""
    from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
    from gllm_tpu.ops.pallas.ragged_attention import ragged_paged_attention
    q, kc, vc, md = _paged_case(np.random.default_rng(2), [1, 1], [30, 70])
    args = (q, kc, vc, md.kv_lens, md.page_table)
    kw = dict(scale=0.2, interpret=True)
    assert str(jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, **kw))(*args)) == str(jax.make_jaxpr(
            lambda *a: paged_decode_attention(*a, window=None, **kw))(*args))
    args = (q, kc, vc, md.cu_q_lens, md.kv_lens, md.page_table)
    assert str(jax.make_jaxpr(lambda *a: ragged_paged_attention(
        *a, **kw))(*args)) == str(jax.make_jaxpr(
            lambda *a: ragged_paged_attention(*a, window=None, name=None,
                                              **kw))(*args))


def test_blocks_follow_the_geometry_where_the_table_has_an_entry(monkeypatch):
    from gllm_tpu.ops.pallas import tuning
    monkeypatch.setattr(tuning, "device_tag", lambda: "tpu_v5_lite")
    # a geometry without an entry keeps the table's pair
    assert tuning.decode_blocks(8) == tuning.get("decode")
    assert tuning.decode_blocks(2, num_q_heads=32) == tuning.get("decode")
    assert tuning.ragged_blocks(32, 8) == tuning.get("ragged")
    # the cell's geometry has entries of its own, from its sweep; the
    # windowed calls take the same pair (no entry of their own)
    assert tuning.decode_blocks(8, num_q_heads=128) == tuning.get(
        "decode@128x8") == {"kv_block": 512, "group": 8}
    assert tuning.ragged_blocks(128, 8) == tuning.get("ragged@128x8") == {
        "q_block": 64, "kv_block": 512}
    assert not [k for k in tuning._table()["tpu_v5_lite"] if "_window" in k]


# ---- the share ---------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The parts of a layer's result that the eight shares' held experts
    give, with attention and the shared experts counted once, add up to
    what the uncut reference gives for the whole layer; and the served
    expert half of share r is the reference's for share r."""
    chips, held = 8, 4
    whole = {k: v for k, v in TOY.items() if k != "ep_share"}
    whole["num_experts"] = chips * held
    w_all = REF.make_weights(whole, SEED, jnp.float32)["layers"][1]
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(19, 64)), jnp.float32)
    attn, routed_all, shared = REF.block_parts(whole, h, w_all, REF._mm)
    parts = jnp.zeros_like(routed_all)
    for rank in range(chips):
        model = dict(TOY, ep_share={"chips": chips, "rank": rank,
                                    "num_experts": chips * held})
        layer = dict(w_all)
        for k in ("w_gate", "w_up", "w_down"):
            layer[k] = w_all[k][rank * held:(rank + 1) * held]
        _, routed, _ = REF.block_parts(model, h, layer, REF._mm)
        parts = parts + routed
        # the served expert layer of this share: routed part + shared mean
        cfg = from_hf_config(model)
        lp = {"router": layer["router"], "w_gate": layer["w_gate"],
              "w_up": layer["w_up"], "w_down": layer["w_down"],
              "shared_gate_proj": layer["shared_gate"],
              "shared_up_proj": layer["shared_up"],
              "shared_down_proj": layer["shared_down"]}
        with jax.default_matmul_precision("highest"):
            got, stats = cohere2_moe._experts(
                lp, h, cfg, jnp.ones((19,), bool), None, None, "xla")
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(routed + shared), atol=2e-5)
        assert int(stats[0]) + int(stats[1]) == 19 * 8
    np.testing.assert_allclose(np.asarray(parts), np.asarray(routed_all),
                               atol=2e-5)
    assert float(jnp.abs(routed_all).mean()) > 0.01
    # the layer: x + attention + all routed parts + the shared mean, once
    layer_out = h + attn + parts + shared
    want = h + sum(REF.block_parts(whole, h, w_all, REF._mm))
    np.testing.assert_allclose(np.asarray(layer_out), np.asarray(want),
                               atol=2e-5)
    assert float(jnp.abs(shared).mean()) > 1e-3


# ---- start-up lines and fences ----------------------------------------------

def test_startup_lines_say_what_is_held_and_which_kernel_serves_what(caplog):
    with caplog.at_level(logging.INFO):
        llm = _llm(impl="pallas")
    said = [r.getMessage() for r in caplog.records]
    held = [m for m in said if "[startup] windowed GQA model:" in m]
    assert len(held) == 1, said
    assert "4 of 32 routed experts a layer held here" in held[0]
    assert "KV pool 256 pages x 4 layers x 256 B a token = " in held[0]
    assert "window 24 in 3 of 4 layers; prefix cache on" in held[0]
    assert "grouped products -> " in held[0]
    # ... and the numbers are the arrays'
    kv = llm.runner.kv
    assert f"= {kv.k.nbytes + kv.v.nbytes} bytes" in held[0]
    assert f"weights {llm.runner.weight_bytes()} bytes" in held[0]
    which = [m for m in said if "[startup] windowed GQA (" in m]
    assert len(which) == 1, said
    for name in ("swa_paged_decode_attention", "swa_ragged_paged_attention",
                 "swa_ragged_paged_attention_decode_rows",
                 "paged_decode_attention", "ragged_paged_attention_decode_"
                 "rows", "window 24 in 3 of 4 layers"):
        assert name in which[0], name


def test_what_it_cannot_have_yet_is_refused_by_name():
    from gllm_tpu.engine.llm import LLM, refuse_for_rings
    cfg = from_hf_config(TOY)
    base = dict(load_format="dummy", dtype="float32")
    for kw, named in (
            (dict(parallel=ParallelConfig(tp=2)), "tp / pp / dp / sp"),
            (dict(cache=CacheConfig(kv_cache_dtype="int8")),
             "--kv-cache-dtype int8"),
            (dict(spec_decode="ngram"), "--spec-decode"),
            (dict(multi_step_decode=4), "fused multi-step decoding")):
        with pytest.raises(ValueError, match="paged pool.*" + named):
            LLM(config=EngineConfig(**base, **kw), model_cfg=cfg)
    with pytest.raises(NotImplementedError, match="under a mesh"):
        cohere2_moe.no_mesh_specs(cfg, 2)
    with pytest.raises(NotImplementedError, match="--load-format dummy"):
        cohere2_moe.load_params("/nowhere", cfg)
    # the prefix cache is NOT refused (the rings' fence would refuse it)
    with pytest.raises(ValueError, match="keep rings.*prefix-caching"):
        refuse_for_rings(EngineConfig(**base, cache=CacheConfig(
            enable_prefix_caching=True)))
    # under a tp shard context the window is refused, not dropped
    from gllm_tpu.ops import attention
    q, kc, vc, md = _paged_case(np.random.default_rng(3), [1], [30])
    attention.set_shard_context(object())
    try:
        with pytest.raises(NotImplementedError, match="window"):
            attention.paged_attention(q, kc, vc, md, scale=1.0,
                                      max_q_len=1, impl="pallas", window=8)
    finally:
        attention.set_shard_context(None)
