"""LiquidAI/LFM2-24B-A2B (``model_type: lfm2_moe``) on the normal path at a
small size that keeps the published ratios (CPU, seeded random weights,
float32): prompts through the engine at once, prefilled in two chunks
whose second has one token, two tokens or many (the window's edge cases:
it enters through the carried window AND the cached pages), decoding
beside rows that prefill (mixed steps), every sequence's logits at every
position against the plain reference's one full pass, on the XLA form and
on the Pallas kernels in interpret mode over the lane-packed cache; the
router under a drawn bias; the eight shares that add up; a freed slot that
is zeroed; the rows cap that is gone for this family and stands for the
others; the configuration file against the catalog's row; ``load_params``
from a tiny checkpoint the test writes; the fences."""

import json
import logging
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gllm_tpu.batching import StepBatch
from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.models import dense, lfm2_moe
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.models.deepseek import deepseek_route
from gllm_tpu.ops.attention import AttentionMetadata
from gllm_tpu.sampling_params import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
from lib.refchild import load_family  # noqa: E402

REF = load_family("lfm2_moe")

# the catalog's row (model-configs guide, architectures.jsonl,
# "LFM2-24B-A2B"; source
# https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json)
PATTERN = ["conv", "conv", "full_attention"] + [
    "conv", "conv", "conv", "full_attention"] * 9 + ["conv"]
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PATTERN,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}

# a toy in the published ratios: two dense layers, conv and attention 5 : 2,
# four query heads a KV head, top 4 of 16 experts. The heads keep the
# published 64 lanes (``head_dim`` stated, since 64 / 8 is not 64) so that
# the runner packs them in pairs by its own rule, as on the chip
TOY = dict(CATALOG, num_hidden_layers=7, hidden_size=64, head_dim=64,
           layer_types=["conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention"],
           num_attention_heads=8, num_key_value_heads=2, vocab_size=512,
           intermediate_size=160, moe_intermediate_size=48, num_experts=16,
           max_position_embeddings=512)
SEED = 2 ** 31 + 51

# float32 on both sides: what is left is the order of the sums (blocks of
# keys against one softmax, a grouped product against an expert at a
# time): 1.5e-6 measured, limit 1e-4
F32_TOL = 1e-4


def _config_file():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "lfm2-24b-a2b.json")) as f:
        return json.load(f)


# ---- the configuration ------------------------------------------------------

def test_configuration_file_holds_the_catalogs_row_key_by_key():
    hf = _config_file()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "LFM2-24B-A2B"][0]
    assert row["config"] == CATALOG and hf["source"] == row["source_url"]
    differs = sorted(k for k, v in CATALOG.items() if hf.get(k, "-") != v)
    assert differs == sorted(hf["reduced"]) == [
        "max_position_embeddings", "num_experts", "vocab_size"]
    assert set(hf["reduced_why"]) == set(hf["reduced"])
    assert set(hf["assumed"]) >= {
        "tie_word_embeddings", "head_dim", "route_norm_eps", "rotary",
        "conv_state", "weights_recipe", "intermediate_size"}
    assert hf["ep_share"] == {"chips": 8, "rank": 0, "num_experts": 64}
    cfg = from_hf_config(hf)
    assert cfg.architecture == "Lfm2MoeForCausalLM"
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size, cfg.vocab_size,
            cfg.rms_norm_eps, cfg.rope_theta) == (
                2048, 32, 8, 64, 11776, 1536, 8192, 1e-5, 1e6)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok, cfg.first_k_dense_replace) == (
                64, 8, 0, 4, 2)
    assert (cfg.scoring_func, cfg.topk_method, cfg.route_groups,
            cfg.route_norm_eps, cfg.routed_scaling_factor) == (
                "sigmoid", "noaux_tc", 0, 1e-6, 1)
    assert cfg.tie_word_embeddings and cfg.qk_norm and cfg.use_rope
    # 30 conv + 10 attention layers, 2 dense + 38 expert feed-forwards
    assert (cfg.num_linear_layers, cfg.num_attn_layers) == (30, 10)
    kinds = lfm2_moe.layer_kinds(cfg)
    assert [kinds.count(k) for k in (
        ("conv", "dense"), ("conv", "moe"), ("full_attention", "moe"),
        ("full_attention", "dense"))] == [2, 28, 10, 0]
    # a slot is the window and nothing else; no chunked rule
    assert cfg.use_hybrid and cfg.use_short_conv and cfg.use_seq_slots
    assert not (cfg.use_mamba or cfg.ssm_chunked_rule or cfg.use_mla)
    assert cfg.ssm_slot_shapes == ((2, 2048), ()) and cfg.ssm_chunk == 0
    from gllm_tpu.models import get_model_def
    assert get_model_def(cfg).family == "lfm2_moe"
    # the families with a chunked rule read the properties as they did,
    # and the router's epsilon is theirs
    for name, chunk in (("olmo-hybrid-7b", 64), ("falcon-h1-34b-instruct",
                                                  128),
                        ("nemotron-3-nano-30b-a3b", 128)):
        with open(os.path.join(ROOT, "perfbench", "configs",
                               name + ".json")) as f:
            other = from_hf_config(json.load(f))
        assert other.use_hybrid and other.ssm_chunked_rule
        assert other.ssm_chunk == chunk and not other.use_short_conv
        assert other.route_norm_eps == 1e-20 and len(
            other.ssm_slot_shapes[1]) == 3


def test_derived_sizes_are_the_arithmetic_of_the_widths():
    hf = _config_file()
    d = hf["derived"]
    h = 2048
    conv = h * 3 * h + h * h + 3 * h + h
    attn = 2 * h * h + 2 * h * 512 + 2 * 64 + h
    moe = 8 * 3 * h * 1536 + h * 64 + 64
    ffn = 3 * h * 11776
    assert (conv, attn, moe, ffn) == (
        d["conv_layer_params"], d["attention_layer_params"],
        d["expert_layer_params"], d["dense_ffn_params"]) == (
            16785408, 10487936, 75628608, 72351744)
    assert d["params"] == (30 * conv + 10 * attn + 38 * moe + 2 * ffn
                           + 40 * h + 8192 * h + h) == 3643893376
    # the published model whole, tied, by the same count: the card's 24B
    whole = (30 * conv + 10 * attn + 38 * (64 * 3 * h * 1536 + h * 64 + 64)
             + 2 * ffn + 40 * h + 65536 * h + h)
    assert round(whole / 1e9, 2) == 23.84
    cfg = from_hf_config(hf)
    params = jax.eval_shape(lambda: lfm2_moe.init_params(cfg))
    stored = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in jax.tree.leaves(params))
    assert stored == d["weight_bytes"] == 2 * d["params"] + 2 * 38 * 64
    assert params["conv_layers"]["conv_w"].shape == (30, 3, 2048)
    assert params["moe_layers"]["router"].shape == (38, 64, 2048)
    assert params["moe_layers"]["w_gate"].shape == (38, 8, 2048, 1536)
    assert "lm_head" not in params
    kv = jax.eval_shape(lambda: lfm2_moe.init_kv_cache(
        cfg, 16640, 16, jnp.bfloat16, num_slots=129, kv_pack=2))
    assert kv.k.shape == (10, 16640, 16, 4, 128) and kv.rec is None
    assert kv.conv.shape == (30, 129, 2, 2048)
    assert 2 * kv.k.size * 2 == d["kv_pool_bytes"] == 16640 * 16 * d[
        "kv_bytes_per_token"]
    assert 4 * kv.conv.size == d["window_pool_bytes"] == 129 * 30 * d[
        "window_bytes_per_sequence_layer"]
    assert d["expert_bytes"] == 2 * 3 * h * 1536
    assert d["fixed_weight_bytes_per_decode_step"] == 2 * (
        30 * (conv - h) + 10 * (attn - h - 128) + 38 * h * 64 + 2 * ffn
        + 8192 * h)


def test_a_block_that_is_not_the_published_one_is_refused():
    with pytest.raises(ValueError, match="conv_bias=True"):
        from_hf_config(dict(TOY, conv_bias=True))
    with pytest.raises(ValueError, match="layer_types"):
        from_hf_config(dict(TOY, layer_types=TOY["layer_types"][:-1]
                            + ["mamba"]))
    # the pattern folds into nested repeats over (operator, feed-forward)
    from gllm_tpu.models.nemotron_h import layer_program
    cd, cm, am = (("conv", "dense"), ("conv", "moe"),
                  ("full_attention", "moe"))
    kinds = lfm2_moe.layer_kinds(from_hf_config(_config_file()))
    assert layer_program(kinds) == (
        ((cd,), 2), ((am, ((cm,), 3)), 9), am, cm)


# ---- the engine against the reference ---------------------------------------

def _llm(impl="xla", model=TOY, **kw):
    from gllm_tpu.engine.llm import LLM
    kw.setdefault("max_num_seqs", 8)
    return LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        attention_impl=impl,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=256), **kw),
        model_cfg=from_hf_config(model))


def _errors(weights, out, prompt, model=TOY):
    """(prefill error, decode error, the reference's logprob spread) of
    one served sequence against the reference's one full pass over prompt +
    output: root mean square differences of the logprobs."""
    prefill = [float(t[0]) for t in out.prompt_logprobs[1:]]
    tops = [{int(i): float(v) for i, v in zip(ids, lps)}
            for _, ids, lps in out.logprobs]
    full = prompt + list(out.output_token_ids)
    want = ([[t] for t in prompt[1:]] + [[]] * (len(tops) + 1))[:len(full)]
    for j, top in enumerate(tops):
        want[len(prompt) - 1 + j] = sorted(top)
    ref = REF.logprobs(model, weights, full, want)
    ref_prefill = [v[0] for v in ref[:len(prompt) - 1]]

    def rms(pairs):
        return float(np.sqrt(np.mean([(a - b) ** 2 for a, b in pairs])))
    return (rms(zip(prefill, ref_prefill)),
            rms((top[t], r)
                for top, row in zip(tops, ref[len(prompt) - 1:])
                for t, r in zip(sorted(top), row)),
            float(np.std(ref_prefill)))


def _generate(llm, prompts, outs):
    return llm.generate(
        prompt_token_ids=prompts,
        sampling_params=[SamplingParams(
            temperature=0.0, max_tokens=n, ignore_eos=True,
            prompt_logprobs=1, logprobs=5) for n in outs])


RNG = random.Random(5)
# under --maxp 32: 50 tokens are two chunks (32 + 18), 33 a second chunk of
# ONE token, 34 of two (the carried window's edge cases: the chunk's first
# token reads both carried rows, its second one of them); the short ones
# prefill and decode beside them
PROMPTS = [RNG.choices(range(2, 512), k=n) for n in (50, 9, 21, 33, 34)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_decode_and_mixed_steps_agree_with_the_reference(
        impl, monkeypatch):
    """Five sequences at once. Three are prefilled in two chunks (the
    second enters through the carried window and the cached pages) and
    decoded through both caches; the others prefill while they decode and
    decode while they prefill. Every sequence against its own reference,
    at every position. On Pallas the runner packs the KV heads of 64 in
    pairs by its own rule. The decode-only steps (8 rows) multiply every
    held expert by every row, the mixed steps take the grouped products
    (``DENSE_ROWS`` set between them, as 128 rows and 512 tokens lie
    about the published 256)."""
    from gllm_tpu.runner.prepare import (_M_MAMBA_ROWS,
                                         _M_SCONV_CHUNK_TOKENS,
                                         _M_SCONV_ROWS)
    before = (_M_SCONV_ROWS.get(kind="chunk"), _M_SCONV_ROWS.get(
        kind="decode"), _M_SCONV_CHUNK_TOKENS.get(),
        _M_MAMBA_ROWS.get(path="chunk"))
    monkeypatch.setattr(lfm2_moe, "DENSE_ROWS", 8)
    llm = _llm(impl)
    assert llm.runner.kv_pack == (2 if impl == "pallas" else 1)
    assert llm.runner.kv.k.shape[-2:] == ((1, 128) if impl == "pallas"
                                          else (2, 64))
    assert llm.runner.kv.conv.shape == (5, 9, 2, 64)
    assert llm.runner.kv.rec is None
    steps = []
    sig = llm.runner.builder.shape_signature

    def spy(batch):
        steps.append(sorted(it.num_new_tokens for it in batch.items))
        return sig(batch)
    llm.runner.builder.shape_signature = spy
    outs = _generate(llm, PROMPTS, (4, 8, 6, 3, 3))
    # rows decoding beside rows prefilling, decode-only steps, second
    # chunks of one and of two tokens beside other rows
    assert any(rows[0] == 1 and rows[-1] > 1 for rows in steps), steps
    assert any(rows[-1] == 1 for rows in steps)
    weights = REF.make_weights(TOY, SEED, jnp.float32)
    for out, prompt in zip(outs, PROMPTS):
        pre, dec, spread = _errors(weights, out, prompt)
        assert 0.3 < spread < 2.0
        assert pre < F32_TOL and dec < F32_TOL, (len(prompt), pre, dec)
    # the operator's counters count for this family, the others' do not
    assert _M_SCONV_ROWS.get(kind="chunk") - before[0] >= 8
    assert _M_SCONV_ROWS.get(kind="decode") - before[1] >= 15
    assert _M_SCONV_CHUNK_TOKENS.get() - before[2] == sum(map(len, PROMPTS))
    assert _M_MAMBA_ROWS.get(path="chunk") == before[3]
    mm = llm.memory_manager
    assert mm.use_ssm and mm.ssm_working_slots == 8 and mm.ssm_chunk == 0
    assert llm.scheduler._chunk_rows_cap is None


def test_a_freed_slot_is_zeroed_before_its_next_tenant(monkeypatch):
    """One working slot: a second sequence takes the slot the first left.
    Its answers are the reference's (it read no row of the first: the
    ``zero`` intent went through ``_ssm_apply`` with no recurrent stack);
    with the maintenance program switched off they are far off."""
    from gllm_tpu.runner.runner import _M_SSM_APPLY, ModelRunner
    weights = REF.make_weights(TOY, SEED, jnp.float32)
    first, second = PROMPTS[1], PROMPTS[2]

    def second_tenant(llm):
        _generate(llm, [first], (4,))
        slot = np.asarray(llm.runner.kv.conv[:, 1])
        assert np.abs(slot).max() > 0.01          # the first one's rows
        return _errors(weights, _generate(llm, [second], (4,))[0],
                       second)[:2]
    before = _M_SSM_APPLY.get()
    pre, dec = second_tenant(_llm(max_num_seqs=1))
    assert pre < F32_TOL and dec < F32_TOL
    assert _M_SSM_APPLY.get() > before
    monkeypatch.setattr(ModelRunner, "_apply_ssm_intents",
                        lambda self: None)
    pre, _ = second_tenant(_llm(max_num_seqs=1))
    assert pre > 100 * F32_TOL


def test_slot_maintenance_takes_a_pool_without_a_recurrent_stack():
    """``_ssm_apply`` handed None for the recurrent stack: the window pool
    alone goes through the three classes as numpy has them."""
    from gllm_tpu.runner.runner import _ssm_apply
    rng = np.random.default_rng(3)
    pool = rng.standard_normal((3, 8, 2, 16)).astype(np.float32)
    idx = lambda *v: np.asarray(v + (0,) * (4 - len(v)), np.int32)
    conv, rec = _ssm_apply(jnp.asarray(pool), None, idx(1), idx(6), idx(2, 3),
                           idx(6), idx(4))
    want = pool.copy()
    want[:, 6] = want[:, 1]
    want[:, 2] = want[:, 3] = 0.0
    want[:, 4] = want[:, 6]
    assert rec is None
    np.testing.assert_array_equal(np.asarray(conv), want)


# ---- the rows cap -----------------------------------------------------------

@pytest.mark.parametrize("name,kind,chunk", [
    ("olmo-hybrid-7b", "gdn", 64), ("nemotron-3-nano-30b-a3b", "mamba", 128),
    ("falcon-h1-34b-instruct", "mamba", 128), ("lfm2-24b-a2b", "sconv", 0)])
def test_rows_cap_and_chunk_bucket_follow_the_chunked_rule(name, kind, chunk):
    """A hundred waiting prompts of 20 tokens at the default --maxd 256 /
    --maxp 2048. A model whose slot state has a chunked rule holds the
    rows with more than one new token under what the largest layout takes
    (36 at chunks of 64, 18 at 128) and sizes the step's bucket by the
    layout; a model without one (the short convolution) holds all hundred
    in one step, in the bucket its tokens alone name."""
    from gllm_tpu.memory_manager import make_memory_manager
    from gllm_tpu.ops import gdn
    from gllm_tpu.runner.prepare import BatchBuilder
    from gllm_tpu.scheduler import Scheduler
    from gllm_tpu.sequence import Sequence
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        cfg = from_hf_config(json.load(f))
    assert cfg.ssm_chunk == chunk and cfg.ssm_chunked_rule == bool(chunk)
    config = EngineConfig(load_format="dummy", max_model_len=4096,
                          cache=CacheConfig(page_size=16, num_pages=4096))
    builder = BatchBuilder(config, 16, use_ssm=cfg.use_hybrid,
                           ssm_chunk=cfg.ssm_chunk, ssm_kind=kind)
    mm = make_memory_manager(4096, 16, False, ssm_working_slots=257,
                             ssm_chunk=cfg.ssm_chunk)
    sched = Scheduler(config, mm)
    cap = gdn.gdn_chunk_rows_cap(builder.max_tokens, chunk) if chunk else None
    assert sched._chunk_rows_cap == cap == {64: 36, 128: 18, 0: None}[chunk]
    for i in range(100):
        sched.add_seq(Sequence(i, [3] * 20, SamplingParams(max_tokens=4)))
    seen = 0
    while sched.has_unfinished:
        batch = sched.schedule_once()
        rows = [it.num_new_tokens for it in batch.items]
        t, s, _, _ = builder.shape_signature(batch)
        if chunk:
            n, c = gdn.gdn_chunk_slots(t, s, chunk)
            assert gdn.gdn_chunks_needed(rows, c) <= n
        elif max(rows) > 1:
            # the tokens' own bucket: no layout to hold
            assert t == max(512, 1 << (sum(rows) - 1).bit_length()) or (
                t == builder.max_tokens)
        seen = max(seen, sum(r > 1 for r in rows))
        sched.process_output(batch, [7] * batch.num_seqs, 2)
    assert seen == (cap or 100)


# ---- the router -------------------------------------------------------------

def test_the_choice_follows_the_corrected_scores_and_the_weights_the_scores():
    """Under a drawn ``expert_bias`` the chosen four are the largest of
    s + b while their weights are s / (sum s + 1e-6): the program's route
    and the reference's agree, and at least one row chooses another set
    than the uncorrected scores would."""
    cfg = from_hf_config(TOY)
    rng = np.random.default_rng(11)
    r = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 64)) / 8, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.1, jnp.float32)
    layer = {"router": router, "expert_bias": bias}
    ids_ref, w_ref = REF.route(TOY, r, layer)
    logits = jnp.einsum("th,eh->te", r, router)
    w, ids = deepseek_route(logits, bias, cfg)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-6)
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.asarray(ids)
    by_bias = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :4]
    by_score = np.argsort(-s, axis=-1)[:, :4]
    assert (np.sort(chosen) == np.sort(by_bias)).all()
    differ = [t for t in range(40)
              if set(by_bias[t]) != set(by_score[t])]
    assert differ, "the drawn bias decided no row's choice"
    t = differ[0]
    np.testing.assert_allclose(
        np.asarray(w)[t], s[t, chosen[t]] / (s[t, chosen[t]].sum() + 1e-6),
        rtol=1e-6)
    # and the whole model under a drawn bias, both sides the same
    weights = REF.make_weights(TOY, SEED, jnp.float32)
    params = lfm2_moe.init_params(from_hf_config(TOY), SEED, jnp.float32)
    drawn = rng.normal(size=(5, 16)).astype(np.float32) * 0.1
    params["moe_layers"]["e_bias"] = jnp.asarray(drawn)
    for layer, b in zip(weights["layers"][2:], drawn):
        layer["expert_bias"] = jnp.asarray(b)
    want = np.asarray(REF.logits(TOY, weights, TOKENS))
    np.testing.assert_allclose(_program_logits(TOY, params), want, rtol=0,
                               atol=F32_TOL)
    zeros = np.asarray(REF.logits(
        TOY, REF.make_weights(TOY, SEED, jnp.float32), TOKENS))
    assert np.abs(zeros - want).max() > 100 * F32_TOL


# ---- the share --------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The parts of an expert layer's result that the eight shares' held
    experts give add up to what the uncut reference gives for the whole
    layer; and the served expert layer of share r is the reference's for
    share r."""
    chips, held = 8, 2
    whole = dict(TOY, num_experts=chips * held)
    w_all = REF.make_weights(whole, SEED, jnp.float32)["layers"][3]
    assert w_all["ffn"] == "moe"
    rng = np.random.default_rng(7)
    r = jnp.asarray(rng.normal(size=(19, 64)), jnp.float32)
    stored = REF._stored(None)
    with jax.default_matmul_precision("highest"):
        routed_all = REF.routed_part(whole, r, w_all, stored)
    parts = jnp.zeros_like(routed_all)
    for rank in range(chips):
        model = dict(TOY, num_experts=held,
                     ep_share={"chips": chips, "rank": rank,
                               "num_experts": chips * held})
        layer = dict(w_all)
        for k in ("w1", "w3", "w2"):
            layer[k] = w_all[k][rank * held:(rank + 1) * held]
        with jax.default_matmul_precision("highest"):
            routed = REF.routed_part(model, r, layer, stored)
        parts = parts + routed
        cfg = from_hf_config(model)
        assert (cfg.experts_held, cfg.expert_first) == (held, rank * held)
        lp = {"router": layer["router"], "e_bias": layer["expert_bias"],
              "w_gate": layer["w1"], "w_up": layer["w3"],
              "w_down": layer["w2"]}
        with jax.default_matmul_precision("highest"):
            got, stats = lfm2_moe._moe(lp, r, cfg, jnp.ones((19,), bool),
                                       None, None, "xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(routed),
                                   atol=2e-5)
        assert int(stats[0]) + int(stats[1]) == 19 * 4
        # the same share with every held expert times every row (the
        # decode-only steps' form): the same part, the same counts
        stacks = tuple(lp[k][None] for k in ("w_gate", "w_up", "w_down"))
        with jax.default_matmul_precision("highest"):
            dense_got, dense_stats = lfm2_moe._moe(
                lp, r, cfg, jnp.ones((19,), bool), stacks, jnp.int32(0),
                "xla")
        np.testing.assert_allclose(np.asarray(dense_got), np.asarray(routed),
                                   atol=2e-5)
        np.testing.assert_array_equal(np.asarray(dense_stats),
                                      np.asarray(stats))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(routed_all),
                               atol=2e-5)
    assert float(jnp.abs(routed_all).mean()) > 0.01


# ---- the program alone ------------------------------------------------------

T = 24
TOKENS = random.Random(7).choices(range(2, 512), k=T)


def _program_logits(model, params, tokens=TOKENS):
    """Logits [T, vocab] of one prefill through ``lfm2_moe.forward``."""
    cfg = from_hf_config(model)
    page, n = 4, len(tokens)
    kv = lfm2_moe.init_kv_cache(cfg, 8, page, jnp.float32, num_slots=2)
    batch = StepBatch(
        token_ids=jnp.asarray(tokens, jnp.int32),
        positions=jnp.arange(n, dtype=jnp.int32),
        slot_mapping=jnp.arange(n, dtype=jnp.int32) + page,    # from page 1
        logits_indices=jnp.asarray([n - 1], jnp.int32),
        attn=AttentionMetadata(
            cu_q_lens=jnp.asarray([0, n], jnp.int32),
            kv_lens=jnp.asarray([n], jnp.int32),
            page_table=jnp.arange(1, 8, dtype=jnp.int32)[None, :],
            num_seqs=jnp.int32(1)),
        sampling=None, ssm_slots=jnp.asarray([1], jnp.int32))
    cos_sin = lfm2_moe.make_rope_table(cfg)

    @jax.jit
    def run(params, kv, batch):
        hidden, residual, _ = lfm2_moe.forward(
            params, kv, batch, cfg, cos_sin=cos_sin, attn_impl="xla",
            max_q_len=n)
        return dense.compute_full_logits(params, hidden, residual, cfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(run(params, kv, batch))


def test_a_silenced_operator_or_a_lost_window_fails_the_comparison():
    """The loudness of the seeded weights and the probe's sight of the
    window: the output gate ``C`` replaced by ones, or the oldest tap
    zeroed (the input only the carried window supplies at a chunk's
    edge), in the program alone, reads far off the sound logits."""
    cfg = from_hf_config(TOY)
    params = lfm2_moe.init_params(cfg, SEED, jnp.float32)
    sound = _program_logits(TOY, params)
    spread = sound.std()
    tapless = dict(params, conv_layers=dict(
        params["conv_layers"],
        conv_w=params["conv_layers"]["conv_w"].at[:, 0].set(0.0)))
    off = _program_logits(TOY, tapless)
    assert np.sqrt(np.mean((off - sound) ** 2)) > 0.1 * spread
    silent = dict(params, conv_layers=dict(
        params["conv_layers"],
        out_proj=jnp.zeros_like(params["conv_layers"]["out_proj"])))
    off = _program_logits(TOY, silent)
    assert np.sqrt(np.mean((off - sound) ** 2)) > 0.2 * spread


def test_scopes_name_the_operator_and_both_feed_forwards_in_the_step():
    """The scopes the device trace is read by are in the metadata of the
    step's HLO."""
    cfg = from_hf_config(TOY)
    params = jax.eval_shape(lambda: lfm2_moe.init_params(
        cfg, dtype=jnp.float32))
    kv = jax.eval_shape(lambda: lfm2_moe.init_kv_cache(
        cfg, 16, 4, jnp.float32, num_slots=5))
    S, n = 4, 40
    batch = StepBatch(
        token_ids=jnp.zeros(n, jnp.int32), positions=jnp.zeros(n, jnp.int32),
        slot_mapping=jnp.zeros(n, jnp.int32),
        logits_indices=jnp.zeros(S, jnp.int32),
        attn=AttentionMetadata(
            cu_q_lens=jnp.zeros(S + 1, jnp.int32),
            kv_lens=jnp.zeros(S, jnp.int32),
            page_table=jnp.zeros((S, 4), jnp.int32), num_seqs=jnp.int32(S)),
        sampling=None, ssm_slots=jnp.zeros(S, jnp.int32))
    cos_sin = jax.eval_shape(lambda: lfm2_moe.make_rope_table(cfg))
    text = jax.jit(lambda p, kv, b, cs: lfm2_moe.forward(
        p, kv, b, cfg, cos_sin=cs, attn_impl="xla", max_q_len=n)).lower(
            params, kv, batch, cos_sin).compile().as_text()
    for scope in ("sconv/sconv_in", "sconv/sconv_window", "sconv/sconv_out",
                  "lfm2_attn/", "lfm2_ffn/", "lfm2_moe/"):
        assert scope in text, scope


# ---- load_params ------------------------------------------------------------

def test_load_params_reads_an_lfm2_moe_checkpoint(tmp_path):
    """A tiny checkpoint under transformers' Lfm2Moe names ([out, in]
    matrices, the convolution [C, 1, K], every published expert): the
    second share's ``load_params`` gives the stacked layout with its own
    experts, and the program's logits on it are the reference's on the
    same tensors."""
    from safetensors.numpy import save_file
    model = dict(TOY, num_experts=4,
                 ep_share={"chips": 4, "rank": 1, "num_experts": 16})
    cfg = from_hf_config(model)
    rng = np.random.default_rng(4)
    h, inter, e_inter = 64, 160, 48
    tensors = {}

    def put(name, *shape):
        tensors[name] = rng.normal(size=shape).astype(np.float32) * 0.1
        return tensors[name]
    put("model.embed_tokens.weight", 512, h)
    put("model.embedding_norm.weight", h)
    for i, kind in enumerate(TOY["layer_types"]):
        at = f"model.layers.{i}."
        put(at + "operator_norm.weight", h)
        put(at + "ffn_norm.weight", h)
        if kind == "conv":
            put(at + "conv.in_proj.weight", 3 * h, h)
            put(at + "conv.conv.weight", h, 1, 3)
            put(at + "conv.out_proj.weight", h, h)
        else:
            put(at + "self_attn.q_proj.weight", 8 * 64, h)
            put(at + "self_attn.k_proj.weight", 2 * 64, h)
            put(at + "self_attn.v_proj.weight", 2 * 64, h)
            put(at + "self_attn.out_proj.weight", h, 8 * 64)
            put(at + "self_attn.q_layernorm.weight", 64)
            put(at + "self_attn.k_layernorm.weight", 64)
        if i < 2:
            put(at + "feed_forward.w1.weight", inter, h)
            put(at + "feed_forward.w3.weight", inter, h)
            put(at + "feed_forward.w2.weight", h, inter)
        else:
            put(at + "feed_forward.gate.weight", 16, h)
            put(at + "feed_forward.expert_bias", 16)
            for e in range(16):
                ex = at + f"feed_forward.experts.{e}."
                put(ex + "w1.weight", e_inter, h)
                put(ex + "w3.weight", e_inter, h)
                put(ex + "w2.weight", h, e_inter)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    params = lfm2_moe.load_params(str(tmp_path), cfg, dtype=jnp.float32)
    template = jax.eval_shape(lambda: lfm2_moe.init_params(
        cfg, dtype=jnp.float32))
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, template)
    eq = np.testing.assert_array_equal
    # layer 4 is the fourth conv layer and the third expert layer; this
    # share holds experts 4-7
    at = "model.layers.4."
    eq(params["conv_layers"]["in_proj"][3],
       tensors[at + "conv.in_proj.weight"].T)
    eq(params["conv_layers"]["conv_w"][3],
       tensors[at + "conv.conv.weight"][:, 0].T)
    eq(params["conv_layers"]["norm"][3], tensors[at + "operator_norm.weight"])
    eq(params["moe_layers"]["norm"][2], tensors[at + "ffn_norm.weight"])
    eq(params["moe_layers"]["router"][2],
       tensors[at + "feed_forward.gate.weight"])
    eq(params["moe_layers"]["e_bias"][2],
       tensors[at + "feed_forward.expert_bias"])
    eq(params["moe_layers"]["w_down"][2][1],
       tensors[at + "feed_forward.experts.5.w2.weight"].T)
    eq(params["attn_layers"]["o_proj"][1],
       tensors["model.layers.6.self_attn.out_proj.weight"].T)
    eq(params["dense_layers"]["up_proj"][1],
       tensors["model.layers.1.feed_forward.w3.weight"].T)
    eq(params["final_norm"], tensors["model.embedding_norm.weight"])
    # the reference on the same tensors, in its own layout
    layers, seen = [], {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
    for i, kind in enumerate(TOY["layer_types"]):
        op = "conv" if kind == "conv" else "attn"
        ffn = "dense" if i < 2 else "moe"
        o, f = seen[op], seen[ffn]
        c, a = params["conv_layers"], params["attn_layers"]
        layer = {"op": op, "ffn": ffn}
        if op == "conv":
            layer.update(op_norm=c["norm"][o], in_proj=c["in_proj"][o],
                         taps=c["conv_w"][o], out_proj=c["out_proj"][o])
        else:
            layer.update(op_norm=a["norm"][o], **{
                k: a[k][o] for k in ("q_proj", "k_proj", "v_proj", "o_proj",
                                     "q_norm", "k_norm")})
        if ffn == "dense":
            d = params["dense_layers"]
            layer.update(ffn_norm=d["norm"][f], w1=d["gate_proj"][f],
                         w3=d["up_proj"][f], w2=d["down_proj"][f])
        else:
            m = params["moe_layers"]
            layer.update(ffn_norm=m["norm"][f], router=m["router"][f],
                         expert_bias=m["e_bias"][f], w1=m["w_gate"][f],
                         w3=m["w_up"][f], w2=m["w_down"][f])
        seen[op] += 1
        seen[ffn] += 1
        layers.append(layer)
    weights = {"embed": params["embed"], "final_norm": params["final_norm"],
               "layers": layers}
    np.testing.assert_allclose(
        _program_logits(model, params),
        np.asarray(REF.logits(model, weights, TOKENS)), rtol=0, atol=F32_TOL)


# ---- start-up lines and fences ----------------------------------------------

def test_startup_lines_say_what_is_held_and_which_kernel_serves_what(caplog):
    with caplog.at_level(logging.INFO):
        llm = _llm("pallas", model=dict(
            TOY, num_experts=2,
            ep_share={"chips": 8, "rank": 0, "num_experts": 16}))
    said = [r.getMessage() for r in caplog.records]
    held = [m for m in said if "[startup] short-convolution model:" in m]
    assert len(held) == 1, said
    kv = llm.runner.kv
    assert "2 of 16 routed experts a layer held here" in held[0]
    assert f"weights {llm.runner.weight_bytes()} bytes" in held[0]
    assert ("KV pool 256 pages x 4 tokens x 2 attention layers x 1024 B a "
            f"token and layer = {kv.k.nbytes + kv.v.nbytes} bytes") in held[0]
    assert "grouped products -> pallas gmm" in held[0]
    pool = [m for m in said if "[startup] window pool:" in m]
    assert len(pool) == 1, said
    assert (f"9 slots x 5 conv layers x {2 * 64 * 4} bytes as the TPU "
            f"stores them = {kv.conv.nbytes} bytes") in pool[0]
    assert "(kv_pack 2)" in pool[0] and "no recurrent stack" in pool[0]
    which = [m for m in said if "[startup] short-convolution model (" in m]
    assert len(which) == 1, said
    for part in ("5 conv + 2 attention layers", "pallas over kv_pack 2",
                 "paged_decode_attention", "ragged_paged_attention",
                 "ragged_paged_attention_decode_rows", "xla (ops/"
                 "short_conv.py", "no recurrent kernel"):
        assert part in which[0], part


def test_a_mesh_is_refused_and_the_slot_state_fences_hold():
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.runner.runner import pick_kv_pack
    cfg = from_hf_config(TOY)
    with pytest.raises(ValueError, match="short-convolution layers"):
        LLM(config=EngineConfig(load_format="dummy", dtype="float32",
                                parallel=ParallelConfig(tp=2)),
            model_cfg=cfg)
    with pytest.raises(NotImplementedError, match="under a mesh"):
        lfm2_moe.no_mesh_specs(cfg, 2)
    with pytest.raises(NotImplementedError, match="int8"):
        LLM(config=EngineConfig(load_format="dummy", dtype="float32",
                                cache=CacheConfig(kv_cache_dtype="int8")),
            model_cfg=cfg)
    with pytest.raises(ValueError, match="spec-fused"):
        LLM(config=EngineConfig(
            load_format="dummy", dtype="float32", spec_decode="ngram",
            spec_fused=True, overlap_scheduling=True, decode_chain_len=4,
            ondevice_finish=True, decode_slot_batching=True),
            model_cfg=cfg)
    # heads of 64 pack in pairs for this decoder off a mesh; the decoders
    # that build their own paged cache keep the fence
    published = from_hf_config(_config_file())
    assert pick_kv_pack(published, False) == 2
    assert pick_kv_pack(published, True) in (0, 1)     # 1: the CPU's escape
    import dataclasses
    hybrid = dataclasses.replace(
        published, layer_types=("linear_attention", "full_attention"))
    assert hybrid.use_hybrid and not hybrid.use_short_conv
    assert pick_kv_pack(hybrid, False) in (0, 1)
