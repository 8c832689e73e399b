"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``) on the
normal path at small sizes (CPU, seeded random weights): the engine's
prefill in two chunks and then decoding through the pools against the
plain reference's full forward pass, logits compared, in float32 and in
bf16 (tolerances below, each with its reason; an int8 layer fails them);
the same with the carried state zeroed at the chunk boundary, which must
fail; the recurrent step against the chunked rule token for token, in XLA
and in the Pallas kernels (interpret mode); the two shares of the experts
that add up to the uncut layer; ``load_params`` from a tiny checkpoint the
test writes; the configuration file against the catalog's row, its derived
arithmetic, the pattern's folding and the fences."""

import dataclasses
import json
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.models import nemotron_h
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.ops import mamba2
from gllm_tpu.sampling_params import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
from lib.refchild import load_family  # noqa: E402

REF = load_family("nemotron_h")

# the catalog's row (model-configs guide, architectures.jsonl,
# "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"; source
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json)
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}

# a toy of the same family: every kind of block, 2 KV heads under 2 query
# heads each, 2 groups of 4 Mamba-2 heads, half of 8 experts held
TOY = {"model_type": "nemotron_h", "hybrid_override_pattern": "MEMEM*EME",
       "num_hidden_layers": 9, "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
       "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
       "n_groups": 2, "chunk_size": 16, "conv_kernel": 4,
       "intermediate_size": 32, "moe_intermediate_size": 24,
       "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4,
       "ep_share": {"chips": 2, "rank": 0, "n_routed_experts": 8},
       "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 1,
       "topk_group": 1, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
       "max_position_embeddings": 512, "rope_theta": 10000,
       "time_step_min": 0.001, "time_step_max": 0.1,
       "time_step_floor": 1e-4, "mlp_hidden_act": "relu2",
       "tie_word_embeddings": False}
SEED = 2 ** 31 + 41


def _config_file():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


# ---- the configuration ------------------------------------------------------

def test_configuration_file_holds_the_catalogs_row_key_by_key():
    hf = _config_file()
    differs = sorted(k for k, v in CATALOG.items() if hf.get(k, "-") != v)
    assert differs == sorted(hf["reduced"]) == [
        "hybrid_override_pattern", "max_position_embeddings",
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert set(hf["reduced_why"]) == set(hf["reduced"])
    assert hf["hybrid_override_pattern"] == \
        CATALOG["hybrid_override_pattern"][:16] == "MEMEM*EMEMEM*EME"
    assert hf["ep_share"] == {"chips": 2, "rank": 0, "n_routed_experts": 128}
    assert hf["vocab_size"] * 2 == CATALOG["vocab_size"]
    cfg = from_hf_config(hf)
    assert cfg.architecture == "NemotronHForCausalLM"
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.rms_norm_eps) == (2688, 32, 2, 128, 1e-5)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
            cfg.mamba_n_groups, cfg.linear_conv_kernel_dim,
            cfg.mamba_chunk_size, cfg.mamba_d_inner, cfg.gdn_conv_dim) == (
                64, 64, 128, 8, 4, 128, 4096, 6144)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size, cfg.expert_act) == (
                128, 64, 0, 6, 1856, 3712, "relu2")
    assert (cfg.routed_scaling_factor, cfg.scoring_func, cfg.topk_method,
            cfg.norm_topk_prob, cfg.route_groups) == (
                2.5, "sigmoid", "noaux_tc", True, 0)
    assert (cfg.num_linear_layers, cfg.num_moe_layers,
            cfg.num_attn_layers) == (7, 7, 2)
    assert cfg.use_hybrid and cfg.use_mamba and cfg.use_seq_slots
    assert not (cfg.use_rope or cfg.use_mla or cfg.use_swa)
    assert cfg.ssm_chunk == 128
    assert cfg.ssm_slot_shapes == ((3, 6144), (64, 64, 128))
    from gllm_tpu.models import get_model_def
    assert get_model_def(cfg).family == "nemotron_h"
    # the GDN hybrids read the same two properties
    olmo = from_hf_config(json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "olmo-hybrid-7b.json"))))
    assert olmo.use_hybrid and not olmo.use_mamba and olmo.ssm_chunk == 64
    # two heads of 96 x 192 abreast: 384 lanes, three whole tiles
    assert olmo.ssm_slot_shapes == ((3, 11520), (15, 96, 384))


def test_derived_sizes_are_the_arithmetic_of_the_widths():
    hf = _config_file()
    d = hf["derived"]
    h = 2688
    mamba = (h * (2 * 4096 + 2 * 8 * 128 + 64) + 6144 * 4 + 6144 + 3 * 64
             + 4096 + 4096 * h + h)
    assert mamba == d["mamba_layer_params"] == 38744896
    attn = h * 4096 + 2 * h * 256 + 4096 * h + h
    assert attn == d["attention_layer_params"] == 23399040
    expert = 2 * h * 1856
    moe = h * 128 + 128 + 2 * h * 3712 + 64 * expert + h
    assert moe == d["expert_layer_params"] == 658885376
    assert 2 * expert == d["expert_bytes"] == 19955712
    emb = 2 * 65536 * h
    assert d["params"] == 7 * mamba + 2 * attn + 7 * moe + emb + h \
        == 5282534208
    # the published model whole, by the same count: the card's 31.6 B
    whole = (23 * mamba + 6 * attn
             + 23 * (moe + 64 * expert) + 2 * 131072 * h + h)
    assert round(whole / 1e9, 1) == 31.6
    # what the program holds: the same parameters, the float32 leaves at
    # 4 B, and the two widths that are no multiple of 128 stored padded
    # (1856 -> 1920, 10304 -> 10368: models/nemotron_h.lanes)
    params = jax.eval_shape(lambda: nemotron_h.init_params(
        from_hf_config(hf)))
    stored = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in jax.tree.leaves(params))
    pad = 2 * (7 * 64 * 2 * h * 64 + 7 * h * 64)
    assert stored == d["weight_bytes"] == d["weight_bytes_unpadded"] + pad
    assert d["weight_bytes_unpadded"] == 2 * d["params"] + 2 * (
        7 * (6144 + 3 * 64) + 7 * 128)
    assert d["state_bytes_per_sequence_layer"] == 4 * (
        64 * 64 * 128 + 3 * 6144) == 2170880
    assert d["state_pool_bytes"] == 65 * 7 * 2170880
    assert d["kv_bytes_per_token"] == 2048
    assert d["tokens_per_expert_per_decode_step"] == 3.0


def test_the_pattern_folds_into_nested_repeats():
    fold = nemotron_h.layer_program
    kinds = from_hf_config(_config_file()).layer_types
    m, e, a = "mamba", "moe", "full_attention"
    assert fold(kinds) == ((((((m, e), 2), m, a, e), 2)), m, e) or \
        fold(kinds) == (((((m, e), 2), m, a, e), 2), m, e)

    def unfold(program):
        out = []
        for item in program:
            if isinstance(item, str):
                out.append(item)
            else:
                out += unfold(item[0]) * item[1]
        return out

    def blocks(program):
        return sum(1 if isinstance(i, str) else blocks(i[0])
                   for i in program)
    assert unfold(fold(kinds)) == list(kinds)
    assert blocks(fold(kinds)) == 7             # of 16 layers
    whole = from_hf_config(dict(
        CATALOG, architectures=["NemotronHForCausalLM"])).layer_types
    assert len(whole) == 52 and unfold(fold(whole)) == list(whole)
    assert blocks(fold(whole)) <= 14
    assert fold((m, m, m, m)) == (((m,), 4),)
    assert fold((a,)) == (a,)


def test_a_pattern_with_a_block_that_is_not_served_is_refused():
    with pytest.raises(ValueError, match="dense MLP block"):
        from_hf_config(dict(TOY, hybrid_override_pattern="MEMEM*EM-"))
    with pytest.raises(ValueError, match="letters"):
        from_hf_config(dict(TOY, hybrid_override_pattern="MEM"))
    with pytest.raises(ValueError, match="relu2"):
        from_hf_config(dict(TOY, mlp_hidden_act="silu"))


# ---- the rule ---------------------------------------------------------------

def _rule_inputs(t=70, h=8, p=16, n=32, g=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(t, h, p)).astype(np.float32)
    dt = (np.log1p(np.exp(rng.normal(size=(t, h)))) * 0.3).astype(np.float32)
    a = np.exp(-dt * np.exp(rng.normal(size=(h,)))).astype(np.float32)
    B, C = (rng.normal(size=(t, g, n)).astype(np.float32) for _ in range(2))
    return x * dt[..., None], a, B, C


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_recurrent_step_equals_the_chunked_rule_token_for_token(impl):
    """70 tokens one at a time against three chunks of 32 (the last one
    padded) and a dead chunk behind them, the state carried by the scan:
    the outputs token for token, the final state, and (the Pallas kernels,
    interpret mode) a slot no chunk names left as it was."""
    from gllm_tpu.ops.pallas.mamba2_recurrent import \
        mamba2_recurrent_step as step_in_pool
    xdt, a, B, C = _rule_inputs()
    t, h, p = xdt.shape
    n = B.shape[-1]
    pool = jnp.asarray(np.random.default_rng(1).normal(
        size=(4, h, p, n)).astype(np.float32))
    state, ys = pool[2:3], []
    for i in range(t):
        if impl == "pallas":
            y, out = step_in_pool(xdt[i:i + 1], a[i:i + 1], B[i:i + 1],
                                  C[i:i + 1], pool + 0 if i == 0 else out,
                                  np.array([2], np.int32), interpret=True)
            state = out[2:3]
        else:
            y, state = mamba2.mamba2_recurrent_step(
                xdt[i:i + 1], a[i:i + 1], B[i:i + 1], C[i:i + 1], state)
        ys.append(np.asarray(y[0]))
    ys = np.stack(ys)
    cn, pad = 32, (-t) % 32

    def chunks(v):
        v = np.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        v = v.reshape((-1, cn) + v.shape[1:])
        return np.concatenate([v, np.zeros_like(v[:1])])     # + a dead one
    nc = (t + pad) // cn
    first = np.array([1] + [0] * nc, bool)
    args = (chunks(xdt), chunks(np.log(a)), chunks(B), chunks(C))
    if impl == "pallas":
        y, out = mamba2.mamba2_chunk_pool(
            *args, np.array([2] * nc + [0], np.int32), first, pool + 0,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(out[1]),
                                      np.asarray(pool[1]))
        final = out[2]
    else:
        y, states = mamba2.mamba2_chunk_packed(
            *args, np.array([0] * nc + [1], np.int32), first,
            jnp.concatenate([pool[2:3], jnp.zeros_like(pool[:1])]))
        final = states[0]
    y = np.asarray(y)[:nc].reshape(-1, h, p)[:t]
    np.testing.assert_allclose(y, ys, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(final), np.asarray(state[0]),
                               rtol=2e-5, atol=2e-5)


def test_the_gated_norm_gates_inside_the_norm_over_each_group():
    rng = np.random.default_rng(2)
    y, z = (rng.normal(size=(5, 32)).astype(np.float32) for _ in range(2))
    w = rng.normal(size=(32,)).astype(np.float32)
    got = mamba2.rms_norm_gated_grouped(jnp.asarray(y), jnp.asarray(z),
                                        jnp.asarray(w), 1e-5, 4)
    g = (y * z / (1 + np.exp(-z))).reshape(5, 4, 8)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
            ).reshape(5, 32) * w
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # not what the GDN layers' norm computes (norm, then gate, per head)
    from gllm_tpu.ops.gdn import rms_norm_gated
    other = rms_norm_gated(jnp.asarray(y), jnp.asarray(z), jnp.asarray(w),
                           1e-5)
    assert np.abs(np.asarray(other) - want).max() > 0.1


# ---- the engine against the reference ---------------------------------------

# The dummy recipe draws the router's bias as zeros (a balanced router, as
# the accepted expert cells'); these tests put a drawn one on both sides,
# of the size that decides the choice, so that the bias is exercised.
# Draw 3 of the generator: under draws 7 and 1 bf16 flips one expert of one
# of the 49 positions and the prefill error reads 0.08 where it reads 0.010
# here (and under draw 2 and under zeros): a flip is the router's near-tie,
# not the arithmetic under test, and BF16_TOL is written for the latter.
BIAS = np.random.default_rng(3).normal(size=(4, 8)).astype(np.float32) * 0.1


def _llm(dtype="float32", quantization=None, impl="xla"):
    from gllm_tpu.engine.llm import LLM
    llm = LLM(config=EngineConfig(
        load_format="dummy", dtype=dtype, seed=SEED, max_model_len=256,
        max_num_seqs=8, quantization=quantization, attention_impl=impl,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=256)),
        model_cfg=from_hf_config(TOY))
    moe = llm.runner.params["moe_layers"]
    assert moe["e_bias"].shape == BIAS.shape and not moe["e_bias"].any()
    moe["e_bias"] = jnp.asarray(BIAS)
    return llm


def _ref_weights(model, dtype):
    weights = REF.make_weights(model, SEED, jnp.dtype(dtype))
    moe = [la for la in weights["layers"] if la["kind"] == "moe"]
    for layer, bias in zip(moe, BIAS, strict=True):
        assert not np.asarray(layer["e_bias"]).any()
        layer["e_bias"] = jnp.asarray(bias)
    return weights


def _served_logits(llm, prompt, n_out, after_chunk=None):
    """The top logprobs the engine gives while it prefills ``prompt`` in
    two chunks (32 + the rest) and decodes ``n_out`` tokens: per decoded
    position {token: logprob} (top 5), and the prompt's own logprobs."""
    if after_chunk is not None:
        step, n = llm.step, [0]

        def hooked(*args, **kw):
            out = step(*args, **kw)
            n[0] += 1
            if n[0] == 1:
                after_chunk(llm)
            return out
        llm.step = hooked
    out = llm.generate(
        prompt_token_ids=[prompt],
        sampling_params=[SamplingParams(
            temperature=0.0, max_tokens=n_out, ignore_eos=True,
            prompt_logprobs=1, logprobs=5)])[0]
    prefill = [float(t[0]) for t in out.prompt_logprobs[1:]]
    tops = [{int(i): float(v) for i, v in zip(ids, lps)}
            for _, ids, lps in out.logprobs]
    return prefill, tops, list(out.output_token_ids)


def _errors(dtype, prefill, tops, tokens, prompt):
    """(prefill error, decode error, the reference's logprob spread)
    against the reference's full forward pass over prompt + output,
    float32, on its own weights drawn in ``dtype``; an error is the root
    mean square difference of the logprobs (the cells' comparison: a
    maximum rides on single positions where a rounding flips an expert)."""
    weights = _ref_weights(TOY, dtype)
    full = prompt + tokens
    want = [[t] for t in prompt[1:]] + [[]] * len(tokens) + [[]]
    want = want[:len(full)]
    for j, top in enumerate(tops):
        want[len(prompt) - 1 + j] = sorted(top)
    ref = REF.logprobs(TOY, weights, full, want)
    ref_prefill = [v[0] for v in ref[:len(prompt) - 1]]
    def rms(pairs):
        return float(np.sqrt(np.mean([(a - b) ** 2 for a, b in pairs])))
    pre = rms(zip(prefill, ref_prefill))
    dec = rms((top[t], r) for top, row in zip(tops, ref[len(prompt) - 1:])
              for t, r in zip(sorted(top), row))
    return pre, dec, float(np.std(ref_prefill))


PROMPT = random.Random(5).choices(range(2, 512), k=50)

# float32 on both sides: what is left is the order of the sums (two chunks
# of the chunked rule and a recurrent step a token, against a scan token by
# token; 1/sqrt(fan-in) weights, a logprob spread of 0.89): 1e-6 measured,
# limit 1e-4. bf16 weights and stream against float32 arithmetic on the
# same bf16 weights, over 9 blocks: 0.010 (prefill) and 0.010 (decode)
# measured, limit 0.05; the served model with its layer matrices in int8
# reads 0.22 on the prefill and fails it (the reference with int8
# matrices: tests/perfbench/test_reference_nemotron_h.py).
F32_TOL, BF16_TOL = 1e-4, 0.05


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_in_two_chunks_then_decode_agrees_with_the_reference(impl):
    llm = _llm(impl=impl)
    prefill, tops, tokens = _served_logits(llm, PROMPT, 6)
    pre, dec, spread = _errors("float32", prefill, tops, tokens, PROMPT)
    assert 0.5 < spread < 2.0
    assert pre < F32_TOL and dec < F32_TOL, (pre, dec)
    # the experts' counters were counted for this family too
    from gllm_tpu.models.deepseek import _M_MOE_STEPS, _M_MOE_ASSIGN
    from gllm_tpu.runner.prepare import (_M_MAMBA_CHUNK_SLOTS,
                                         _M_MAMBA_CHUNK_TOKENS,
                                         _M_MAMBA_ROWS)
    assert _M_MOE_STEPS.get(step="decode") >= 4 * 5
    assert _M_MOE_ASSIGN.get(where="held") > 0
    assert _M_MOE_ASSIGN.get(where="absent") > 0
    assert _M_MAMBA_ROWS.get(path="chunk") >= 2
    assert _M_MAMBA_ROWS.get(path="recurrent") >= 5
    assert 0 < _M_MAMBA_CHUNK_TOKENS.get() <= _M_MAMBA_CHUNK_SLOTS.get()


def test_a_zeroed_carry_at_the_chunk_boundary_fails_the_comparison():
    """The control of the comparison itself: with the dummy weights'
    decays (e^-1.6 .. e^-0.001 a token) the state carries over the
    boundary, so a second chunk that starts from nothing is far off."""
    def zero(llm):
        kv = llm.runner.kv
        llm.runner.kv = kv._replace(conv=jnp.zeros_like(kv.conv),
                                    rec=jnp.zeros_like(kv.rec))
    prefill, tops, tokens = _served_logits(_llm(), PROMPT, 6,
                                           after_chunk=zero)
    pre, dec, _ = _errors("float32", prefill, tops, tokens, PROMPT)
    assert pre > 100 * F32_TOL and dec > 100 * F32_TOL, (pre, dec)


def test_bf16_agrees_within_its_tolerance_and_an_int8_layer_does_not():
    prefill, tops, tokens = _served_logits(_llm("bfloat16"), PROMPT, 6)
    pre, dec, _ = _errors("bfloat16", prefill, tops, tokens, PROMPT)
    assert F32_TOL < pre < BF16_TOL and dec < BF16_TOL, (pre, dec)
    prefill, tops, tokens = _served_logits(
        _llm("bfloat16", quantization="int8"), PROMPT, 6)
    pre8, dec8, _ = _errors("bfloat16", prefill, tops, tokens, PROMPT)
    assert pre8 > BF16_TOL or dec8 > BF16_TOL, (pre8, dec8)


# ---- the share tied to the model --------------------------------------------

def test_two_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """At 8 experts and 2 shares: what share 0 and share 1 give, with the
    shared expert counted once, adds up to the uncut reference layer."""
    whole = dict(TOY, n_routed_experts=8)
    del whole["ep_share"]
    weights = _ref_weights(whole, "float32")
    layer = dict(next(la for la in weights["layers"]
                      if la["kind"] == "moe"))
    layer.pop("kind"), layer.pop("norm")
    u = jnp.asarray(np.random.default_rng(3).normal(
        size=(24, 64)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        uncut = REF.expert_layer(whole, u, layer, REF._mm)
    # the program's expert layer, told which half it holds
    lp = {"router": layer["router"], "e_bias": layer["e_bias"],
          "shared_up_proj": layer["shared_up"],
          "shared_down_proj": layer["shared_down"]}
    parts = []
    for rank in (0, 1):
        cfg = from_hf_config(dict(TOY, ep_share={
            "chips": 2, "rank": rank, "n_routed_experts": 8}))
        assert (cfg.experts_held, cfg.expert_first) == (4, 4 * rank)
        mine = dict(
            lp, w_up=nemotron_h._pad_to(
                layer["w_up"][4 * rank:4 * rank + 4], 2, 128),
            w_down=nemotron_h._pad_to(
                layer["w_down"][4 * rank:4 * rank + 4], 1, 128))
        with jax.default_matmul_precision("highest"):
            out, stats = nemotron_h._moe(mine, u, cfg,
                                         jnp.ones((24,), bool), None, None)
            shared = nemotron_h._shared_expert(mine, u, "relu2")
        parts.append(np.asarray(out - shared, np.float32))
        assert int(stats[0]) + int(stats[1]) == 24 * 3
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(nemotron_h._shared_expert(
            dict(lp), u, "relu2"), np.float32)
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01
    np.testing.assert_allclose(parts[0] + parts[1] + shared,
                               np.asarray(uncut), rtol=1e-4, atol=1e-5)


# ---- load_params ------------------------------------------------------------

def test_load_params_reads_a_nemotron_h_checkpoint(tmp_path):
    """A tiny checkpoint under transformers' NemotronH names ([out, in]
    matrices, the convolution [C, 1, K], every expert of the layer):
    ``load_params`` gives the stacked layout with this share's experts,
    the widths stored in whole lanes, and the engine serves it."""
    from safetensors.numpy import save_file
    cfg = from_hf_config(dict(TOY, ep_share={
        "chips": 2, "rank": 1, "n_routed_experts": 8}))
    rng = np.random.default_rng(4)
    h, din, conv = 64, 64, 64 + 2 * 2 * 16
    tensors, at = {}, "backbone.layers."

    def put(name, *shape):
        tensors[name] = rng.normal(size=shape).astype(np.float32) * 0.1
        return tensors[name]
    put("backbone.embeddings.weight", 512, h)
    put("backbone.norm_f.weight", h)
    put("lm_head.weight", 512, h)
    for i, c in enumerate(TOY["hybrid_override_pattern"]):
        put(f"{at}{i}.norm.weight", h)
        mix = f"{at}{i}.mixer."
        if c == "M":
            put(mix + "in_proj.weight", din + conv + 8, h)
            put(mix + "conv1d.weight", conv, 1, 4)
            put(mix + "conv1d.bias", conv)
            for leaf in ("dt_bias", "A_log", "D"):
                put(mix + leaf, 8)
            put(mix + "norm.weight", din)
            put(mix + "out_proj.weight", h, din)
        elif c == "*":
            put(mix + "q_proj.weight", 64, h)
            put(mix + "k_proj.weight", 32, h)
            put(mix + "v_proj.weight", 32, h)
            put(mix + "o_proj.weight", h, 64)
        else:
            put(mix + "gate.weight", 8, h)
            put(mix + "gate.e_score_correction_bias", 8)
            put(mix + "shared_experts.up_proj.weight", 48, h)
            put(mix + "shared_experts.down_proj.weight", h, 48)
            for e in range(8):
                put(f"{mix}experts.{e}.up_proj.weight", 24, h)
                put(f"{mix}experts.{e}.down_proj.weight", h, 24)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    params = nemotron_h.load_params(str(tmp_path), cfg, dtype=jnp.float32)
    template = jax.eval_shape(lambda: nemotron_h.init_params(
        cfg, dtype=jnp.float32))
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, template)
    eq = np.testing.assert_array_equal
    # layer 4 is the third Mamba-2 block, layer 6 the third expert block
    m = params["mamba_layers"]
    eq(m["in_proj"][2][:, :din + conv + 8],
       tensors[f"{at}4.mixer.in_proj.weight"].T)
    assert m["in_proj"].shape[-1] == 256 and not np.asarray(
        m["in_proj"][2][:, din + conv + 8:]).any()
    eq(m["conv_w"][2], tensors[f"{at}4.mixer.conv1d.weight"][:, 0])
    eq(m["d"][2], tensors[f"{at}4.mixer.D"])
    eq(m["norm"][2], tensors[f"{at}4.norm.weight"])
    eq(params["attn_layers"]["k_proj"][0],
       tensors[f"{at}5.mixer.k_proj.weight"].T)
    e = params["moe_layers"]
    eq(e["router"][2], tensors[f"{at}6.mixer.gate.weight"].T)
    eq(e["w_up"][2][1][:, :24],                  # expert 5 of share 1
       tensors[f"{at}6.mixer.experts.5.up_proj.weight"].T)
    eq(e["w_down"][2][3][:24], tensors[f"{at}6.mixer.experts.7."
                                        "down_proj.weight"].T)
    assert e["w_up"].shape == (4, 4, 64, 128) and not np.asarray(
        e["w_up"][..., 24:]).any()
    eq(params["lm_head"], tensors["lm_head.weight"].T)
    # and it serves: the reference on the same tensors agrees
    from gllm_tpu.engine.llm import LLM
    llm = LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=128,
        max_num_seqs=4, scheduler=SchedulerConfig(max_prefill_tokens=32),
        cache=CacheConfig(page_size=4, num_pages=64)),
        model_cfg=cfg, params=params)
    out = llm.generate(prompt_token_ids=[PROMPT[:20]], sampling_params=[
        SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True)])[0]
    assert len(out.output_token_ids) == 3


# ---- fences -----------------------------------------------------------------

def test_a_mesh_is_refused_and_the_recurrent_fences_hold():
    from gllm_tpu.engine.llm import LLM
    cfg = from_hf_config(TOY)
    with pytest.raises(ValueError, match="Mamba-2 layers"):
        LLM(config=EngineConfig(load_format="dummy", dtype="float32",
                                parallel=ParallelConfig(tp=2)),
            model_cfg=cfg)
    with pytest.raises(NotImplementedError, match="under a mesh"):
        nemotron_h.no_mesh_specs(cfg, 2)
    with pytest.raises(ValueError, match="ep_share"):
        from_hf_config({"architectures": ["LlamaForCausalLM"],
                        "vocab_size": 8, "hidden_size": 8,
                        "num_hidden_layers": 1, "num_attention_heads": 1,
                        "intermediate_size": 8, "n_routed_experts": 4,
                        "ep_share": {"chips": 2, "n_routed_experts": 8}})
    # the fences of recurrent state read one property, which is true here
    llm = _llm()
    assert llm.runner.builder.use_ssm
    assert not llm.runner.spec_fused
    assert llm.scheduler._chunk_rows_cap == (32 + 8) // 16
    assert llm.memory_manager.ssm_chunk == 16
    with pytest.raises(NotImplementedError, match="int8"):
        from gllm_tpu.engine.llm import LLM as L
        L(config=EngineConfig(load_format="dummy", dtype="float32",
                              cache=CacheConfig(kv_cache_dtype="int8")),
          model_cfg=cfg)
    assert dataclasses.replace(cfg, layer_types=()).use_hybrid is False
