"""tiiuae/Falcon-H1-34B-Instruct (``model_type: falcon_h1``) on the normal
path at a small size that keeps the published ratios (CPU, seeded random
weights, float32): three prompts through the engine at once (the longest
prefilled in two chunks, so that its second chunk enters through the
carried state, the carried window AND the cached pages; the others
decoding beside it while it prefills: mixed steps), every sequence's
logits at every position against the plain reference's one full pass, on
the XLA forms and on the Pallas kernels in interpret mode; each of the
fourteen muP multipliers applied exactly once; the configuration file
against the catalog's row, its derived arithmetic; ``load_params`` from a
tiny checkpoint the test writes; the fences."""

import dataclasses
import json
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
from gllm_tpu.models import dense, falcon_h1
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.ops.attention import AttentionMetadata
from gllm_tpu.sampling_params import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
from lib.refchild import load_family  # noqa: E402

REF = load_family("falcon_h1")

# the catalog's row (model-configs guide, architectures.jsonl,
# "Falcon-H1-34B-Instruct"; source
# https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json)
CATALOG = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}

# a toy in the published ratios: five query heads a KV head, 2 groups of 2
# Mamba-2 heads, an in-projection (32 + 96 + 4 = 132 columns) that is no
# whole multiple of 128 wide, the published multipliers
TOY = dict(CATALOG, num_hidden_layers=3, hidden_size=64,
           num_attention_heads=10, num_key_value_heads=2, head_dim=16,
           vocab_size=512, intermediate_size=96, mamba_n_heads=4,
           mamba_d_head=8, mamba_d_ssm=32, mamba_d_state=16,
           mamba_chunk_size=16, max_position_embeddings=512)
SEED = 2 ** 31 + 48

# float32 on both sides: what is left is the order of the sums (chunks of
# the chunked rule and a recurrent step a token against a scan token by
# token; blocks of keys against one softmax): 7e-7 measured, limit 1e-4
F32_TOL = 1e-4


def _config_file():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "falcon-h1-34b-instruct.json")) as f:
        return json.load(f)


# ---- the configuration ------------------------------------------------------

def test_configuration_file_holds_the_catalogs_row_key_by_key():
    hf = _config_file()
    differs = sorted(k for k, v in CATALOG.items() if hf.get(k, "-") != v)
    assert differs == sorted(hf["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers"]
    assert set(hf["reduced_why"]) == set(hf["reduced"])
    assert set(hf["assumed"]) >= {"multiplier_placements", "gated_norm",
                                  "d_inner", "dt", "weights_recipe"}
    cfg = from_hf_config(hf)
    assert cfg.architecture == "FalconH1ForCausalLM"
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.rms_norm_eps,
            cfg.rope_theta) == (5120, 20, 4, 128, 21504, 261120, 1e-5, 1e11)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
            cfg.mamba_n_groups, cfg.linear_conv_kernel_dim,
            cfg.mamba_chunk_size, cfg.mamba_d_inner, cfg.gdn_conv_dim) == (
                32, 128, 256, 2, 4, 128, 4096, 5120)
    # every layer counts as an attention layer AND as a recurrent layer
    assert cfg.layer_types == ("parallel_hybrid",) * 6
    assert (cfg.num_attn_layers, cfg.num_linear_layers,
            cfg.num_moe_layers) == (6, 6, 0)
    assert cfg.use_hybrid and cfg.use_mamba and cfg.use_seq_slots
    assert cfg.use_rope and not (cfg.use_mla or cfg.use_swa or cfg.qk_norm)
    assert cfg.ssm_chunk == 128 and cfg.kv_cache_heads == 4
    assert cfg.ssm_slot_shapes == ((3, 5120), (32, 128, 256))
    # fourteen multipliers, one of them on the head's ``logit_scale``
    m = cfg.mup
    assert cfg.logit_scale == 0.0078125
    assert (m.embedding, m.key, m.attention_in, m.attention_out, m.ssm_in,
            m.ssm_out) == (5.656854249492381, 0.011048543456039804, 1,
                           0.0375, 0.25, 0.08838834764831845)
    assert m.ssm == tuple(CATALOG["ssm_multipliers"])
    assert m.mlp == tuple(CATALOG["mlp_multipliers"])
    assert 6 + len(m.ssm) + len(m.mlp) + 1 == 14
    from gllm_tpu.models import get_model_def
    assert get_model_def(cfg).family == "falcon_h1"
    # the other state-space family reads the same properties as it did
    nemo = from_hf_config(json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "nemotron-3-nano-30b-a3b.json"))))
    assert (nemo.num_attn_layers, nemo.num_linear_layers) == (2, 7)
    assert nemo.mup is None


def test_derived_sizes_are_the_arithmetic_of_the_widths():
    hf = _config_file()
    d = hf["derived"]
    h = 5120
    attn = h * (2560 + 512 + 512) + 2560 * h
    mamba = (h * (4096 + 5120 + 32) + 5120 * 4 + 5120 + 3 * 32 + 4096
             + 4096 * h)
    mlp = 3 * h * 21504
    assert (attn, mamba, mlp) == (
        d["attention_params_per_layer"], d["mamba_params_per_layer"],
        d["mlp_params_per_layer"]) == (31457280, 68351072, 330301440)
    assert d["layer_params"] == attn + mamba + mlp + 2 * h == 430120032
    assert d["params"] == 6 * d["layer_params"] + 2 * 261120 * h + h
    # the published model whole, by the same count: the card's 34B class
    assert round((72 * d["layer_params"] + 2 * 261120 * h + h) / 1e9,
                 1) == 33.6
    # what the program holds: the same parameters, the float32 leaves at
    # 4 B, and the in-projection stored in whole lanes (9248 -> 9344)
    params = jax.eval_shape(lambda: falcon_h1.init_params(
        from_hf_config(hf)))
    stored = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in jax.tree.leaves(params))
    assert params["layers"]["in_proj"].shape == (6, 5120, 9344)
    assert stored == d["weight_bytes"] \
        == d["weight_bytes_unpadded"] + 2 * 6 * h * 96
    assert d["weight_bytes_unpadded"] == 2 * d["params"] + 2 * 6 * (
        5120 + 3 * 32)
    assert d["state_bytes_per_sequence_layer"] == 4 * (
        32 * 128 * 256 + 3 * 5120) == 4255744
    assert d["state_pool_bytes"] == 65 * 6 * 4255744
    assert d["kv_bytes_per_token"] == 6 * 2048
    assert d["kv_pool_bytes"] == 8320 * 16 * 6 * 2048
    # the pools as the runner sizes them (the start-up lines' numbers):
    # one counter of layers for the pages and for the slots
    kv = jax.eval_shape(lambda: falcon_h1.init_kv_cache(
        from_hf_config(hf), 8320, 16, jnp.bfloat16, num_slots=65))
    assert kv.k.shape == (6, 8320, 16, 4, 128)
    assert kv.rec.shape == (6, 65, 32, 128, 256)
    assert kv.conv.shape == (6, 65, 3, 5120)
    assert 2 * kv.k.size * 2 == d["kv_pool_bytes"]
    assert 4 * (kv.rec.size + kv.conv.size) == d["state_pool_bytes"]


def test_a_block_that_is_not_the_published_one_is_refused():
    with pytest.raises(ValueError, match="mamba_norm_before_gate=True"):
        from_hf_config(dict(TOY, mamba_norm_before_gate=True))
    with pytest.raises(ValueError, match="attn_layer_indices"):
        from_hf_config(dict(TOY, attn_layer_indices=[0, 2]))
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        from_hf_config(dict(TOY, mamba_d_ssm=64))


# ---- the engine against the reference ---------------------------------------

def _llm(impl="xla"):
    from gllm_tpu.engine.llm import LLM
    return LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        max_num_seqs=8, attention_impl=impl,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=256)),
        model_cfg=from_hf_config(TOY))


def _errors(weights, out, prompt):
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
    ref = REF.logprobs(TOY, weights, full, want)
    ref_prefill = [v[0] for v in ref[:len(prompt) - 1]]

    def rms(pairs):
        return float(np.sqrt(np.mean([(a - b) ** 2 for a, b in pairs])))
    return (rms(zip(prefill, ref_prefill)),
            rms((top[t], r)
                for top, row in zip(tops, ref[len(prompt) - 1:])
                for t, r in zip(sorted(top), row)),
            float(np.std(ref_prefill)))


RNG = random.Random(5)
# 50 tokens: two chunks under --maxp 32; the others join beside it
PROMPTS = [RNG.choices(range(2, 512), k=n) for n in (50, 9, 21)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_decode_and_mixed_steps_agree_with_the_reference(
        impl, monkeypatch):
    """Three sequences at once. The first is prefilled in two chunks (its
    second enters through the carried state, the carried window and the
    cached pages) and decoded through both caches; the others prefill
    while it decodes and decode while it prefills. Every sequence against
    its own reference, at every position."""
    if impl == "xla":
        # the prompt logprobs a quarter of a step's rows at a time, as a
        # 2048-token chunk's are computed over the published 261120-row
        # head (the Pallas case computes them whole)
        from gllm_tpu.runner import runner as runner_mod
        assert runner_mod.plp_block_rows(2048, 261120) == 512
        assert runner_mod.plp_block_rows(2112, 261120) == 528
        assert runner_mod.plp_block_rows(2048, 151936) == 2048
        monkeypatch.setattr(runner_mod, "plp_block_rows",
                            lambda tokens, vocab: tokens // 4)
    llm = _llm(impl)
    steps = []
    sig = llm.runner.builder.shape_signature

    def spy(batch):
        rows = [it.num_new_tokens for it in batch.items]
        steps.append((min(rows), max(rows)))
        return sig(batch)
    llm.runner.builder.shape_signature = spy
    outs = llm.generate(
        prompt_token_ids=PROMPTS,
        sampling_params=[SamplingParams(
            temperature=0.0, max_tokens=n, ignore_eos=True,
            prompt_logprobs=1, logprobs=5) for n in (6, 12, 8)])
    # a row decoding beside a row prefilling, and decode-only steps
    assert any(lo == 1 and hi > 1 for lo, hi in steps), steps
    assert any(hi == 1 for _, hi in steps)
    weights = REF.make_weights(TOY, SEED, jnp.float32)
    for out, prompt in zip(outs, PROMPTS):
        pre, dec, spread = _errors(weights, out, prompt)
        assert 0.5 < spread < 2.0
        assert pre < F32_TOL and dec < F32_TOL, (len(prompt), pre, dec)
    # the state-space counters count for this family as for NemotronH
    from gllm_tpu.runner.prepare import (_M_MAMBA_CHUNK_SLOTS,
                                         _M_MAMBA_CHUNK_TOKENS,
                                         _M_MAMBA_ROWS)
    assert _M_MAMBA_ROWS.get(path="chunk") >= 4
    assert _M_MAMBA_ROWS.get(path="recurrent") >= 20
    assert 0 < _M_MAMBA_CHUNK_TOKENS.get() <= _M_MAMBA_CHUNK_SLOTS.get()
    info = llm.memory_manager
    assert info.use_ssm and info.ssm_working_slots == 8


# ---- the fourteen multipliers -----------------------------------------------

MULTIPLIERS = (
    [(k, None) for k in (
        "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "ssm_in_multiplier", "ssm_out_multiplier")]
    + [("ssm_multipliers", i) for i in range(5)]
    + [("mlp_multipliers", i) for i in range(2)])
TOY2 = dict(TOY, num_hidden_layers=2)
T = 24          # a prefill of two chunks of the chunked rule (16 + 8)


def _program_logits(model, params):
    """Logits [T, vocab] of one prefill of T tokens through
    ``falcon_h1.forward`` under ``model``'s multipliers."""
    cfg = from_hf_config(model)
    page = 4
    kv = falcon_h1.init_kv_cache(cfg, 8, page, jnp.float32, num_slots=2)
    batch = StepBatch(
        token_ids=jnp.asarray(TOKENS, jnp.int32),
        positions=jnp.arange(T, dtype=jnp.int32),
        slot_mapping=jnp.arange(T, dtype=jnp.int32) + page,    # from page 1
        logits_indices=jnp.asarray([T - 1], jnp.int32),
        attn=AttentionMetadata(
            cu_q_lens=jnp.asarray([0, T], jnp.int32),
            kv_lens=jnp.asarray([T], jnp.int32),
            page_table=jnp.arange(1, 8, dtype=jnp.int32)[None, :],
            num_seqs=jnp.int32(1)),
        sampling=None, ssm_slots=jnp.asarray([1], jnp.int32))
    cos_sin = falcon_h1.make_rope_table(cfg)

    @jax.jit
    def run(params, kv, batch):
        hidden, residual, _ = falcon_h1.forward(
            params, kv, batch, cfg, cos_sin=cos_sin, attn_impl="xla",
            max_q_len=T)
        return dense.compute_full_logits(params, hidden, residual, cfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(run(params, kv, batch))


TOKENS = random.Random(7).choices(range(2, 512), k=T)


@pytest.fixture(scope="module")
def base():
    """The weights both sides keep while a multiplier moves (drawn under
    the published multipliers), and the reference's logits under them."""
    params = falcon_h1.init_params(from_hf_config(TOY2), SEED, jnp.float32)
    weights = REF.make_weights(TOY2, SEED, jnp.float32)
    ref = np.asarray(REF.logits(TOY2, weights, TOKENS))
    np.testing.assert_allclose(_program_logits(TOY2, params), ref,
                               rtol=0, atol=F32_TOL)
    return params, weights, ref


@pytest.mark.parametrize("key,index", MULTIPLIERS,
                         ids=[k if i is None else f"{k}-{i}"
                              for k, i in MULTIPLIERS])
def test_each_multiplier_is_applied_exactly_once(base, key, index):
    """The multiplier set to another value in the program alone moves the
    logits by far more than the tolerance (it is not inert); set to that
    value on both sides they agree again (it is not applied twice, nor at
    another place than the reference's)."""
    params, weights, ref = base
    moved = dict(TOY2)
    if index is None:
        moved[key] = TOY2[key] * 1.5
    else:
        moved[key] = list(TOY2[key])
        moved[key][index] *= 1.5
    got = _program_logits(moved, params)
    assert np.abs(got - ref).max() > 100 * F32_TOL, key
    both = np.asarray(REF.logits(moved, weights, TOKENS))
    np.testing.assert_allclose(got, both, rtol=0, atol=F32_TOL)


def test_a_silenced_branch_fails_the_comparison():
    """The loudness of the seeded weights: with either branch's output
    multiplier at zero in the program alone, the logits are far off (under
    a plain 1/sqrt(fan-in) draw the published multipliers would leave them
    within a few per cent)."""
    params = falcon_h1.init_params(from_hf_config(TOY2), SEED, jnp.float32)
    sound = _program_logits(TOY2, params)
    spread = sound.std()
    for key in ("ssm_out_multiplier", "attention_out_multiplier"):
        off = _program_logits(dict(TOY2, **{key: 0.0}), params)
        assert np.sqrt(np.mean((off - sound) ** 2)) > 0.2 * spread, key
    off = _program_logits(dict(TOY2, mlp_multipliers=[
        TOY2["mlp_multipliers"][0], 0.0]), params)
    assert np.sqrt(np.mean((off - sound) ** 2)) > 0.2 * spread


def test_scopes_name_both_halves_and_the_mlp_in_the_compiled_step():
    """The scopes the device trace is read by are in the metadata of the
    step's HLO: the three of this block, and NemotronH's inside the
    state-space half."""
    cfg = from_hf_config(TOY2)
    params = jax.eval_shape(lambda: falcon_h1.init_params(
        cfg, dtype=jnp.float32))
    kv = jax.eval_shape(lambda: falcon_h1.init_kv_cache(
        cfg, 16, 4, jnp.float32, num_slots=5))
    S, T = 4, 40
    batch = StepBatch(
        token_ids=jnp.zeros(T, jnp.int32), positions=jnp.zeros(T, jnp.int32),
        slot_mapping=jnp.zeros(T, jnp.int32),
        logits_indices=jnp.zeros(S, jnp.int32),
        attn=AttentionMetadata(
            cu_q_lens=jnp.zeros(S + 1, jnp.int32),
            kv_lens=jnp.zeros(S, jnp.int32),
            page_table=jnp.zeros((S, 4), jnp.int32), num_seqs=jnp.int32(S)),
        sampling=None, ssm_slots=jnp.zeros(S, jnp.int32))
    cos_sin = jax.eval_shape(lambda: falcon_h1.make_rope_table(cfg))
    text = jax.jit(lambda p, kv, b, cs: falcon_h1.forward(
        p, kv, b, cfg, cos_sin=cs, attn_impl="xla", max_q_len=T)).lower(
            params, kv, batch, cos_sin).compile().as_text()
    for scope in ("par_ssm/mamba_conv", "par_ssm/mamba_recurrent",
                  "par_ssm/mamba_chunk_local", "par_ssm/mamba_chunk_scan",
                  "par_ssm/mamba_gated_norm", "par_attn/", "par_mlp/"):
        assert scope in text, scope


# ---- load_params ------------------------------------------------------------

def test_load_params_reads_a_falcon_h1_checkpoint(tmp_path):
    """A tiny checkpoint under transformers' FalconH1 names ([out, in]
    matrices, the convolution [C, 1, K]): ``load_params`` gives the stacked
    layout, the in-projection stored in whole lanes, and the program's
    logits on it are the reference's on the same tensors."""
    from safetensors.numpy import save_file
    cfg = from_hf_config(TOY2)
    rng = np.random.default_rng(4)
    h, d_ssm, conv, inter = 64, 32, 32 + 2 * 2 * 16, 96
    tensors = {}

    def put(name, *shape):
        tensors[name] = rng.normal(size=shape).astype(np.float32) * 0.1
        return tensors[name]
    put("model.embed_tokens.weight", 512, h)
    put("model.final_layernorm.weight", h)
    put("lm_head.weight", 512, h)
    for i in range(2):
        at = f"model.layers.{i}."
        put(at + "input_layernorm.weight", h)
        put(at + "pre_ff_layernorm.weight", h)
        put(at + "self_attn.q_proj.weight", 160, h)
        put(at + "self_attn.k_proj.weight", 32, h)
        put(at + "self_attn.v_proj.weight", 32, h)
        put(at + "self_attn.o_proj.weight", h, 160)
        put(at + "mamba.in_proj.weight", d_ssm + conv + 4, h)
        put(at + "mamba.conv1d.weight", conv, 1, 4)
        put(at + "mamba.conv1d.bias", conv)
        for leaf in ("dt_bias", "A_log", "D"):
            put(at + "mamba." + leaf, 4)
        put(at + "mamba.norm.weight", d_ssm)
        put(at + "mamba.out_proj.weight", h, d_ssm)
        put(at + "feed_forward.gate_proj.weight", inter, h)
        put(at + "feed_forward.up_proj.weight", inter, h)
        put(at + "feed_forward.down_proj.weight", h, inter)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    params = falcon_h1.load_params(str(tmp_path), cfg, dtype=jnp.float32)
    template = jax.eval_shape(lambda: falcon_h1.init_params(
        cfg, dtype=jnp.float32))
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, template)
    eq = np.testing.assert_array_equal
    la, at = params["layers"], "model.layers.1."
    eq(la["in_proj"][1][:, :132], tensors[at + "mamba.in_proj.weight"].T)
    assert la["in_proj"].shape[-1] == 256 and not np.asarray(
        la["in_proj"][..., 132:]).any()
    eq(la["conv_w"][1], tensors[at + "mamba.conv1d.weight"][:, 0])
    eq(la["d"][1], tensors[at + "mamba.D"])
    eq(la["k_proj"][1], tensors[at + "self_attn.k_proj.weight"].T)
    eq(la["down_proj"][1], tensors[at + "feed_forward.down_proj.weight"].T)
    eq(la["pre_ff_norm"][1], tensors[at + "pre_ff_layernorm.weight"])
    eq(params["lm_head"], tensors["lm_head.weight"].T)
    # the reference on the same tensors, in its own layout
    weights = {"embed": params["embed"], "lm_head": params["lm_head"],
               "final_norm": params["final_norm"], "layers": [
        dict({k: v[i] for k, v in la.items()
              if k not in ("in_proj", "a_log", "d")},
             in_proj=la["in_proj"][i][:, :132], A_log=la["a_log"][i],
             D=la["d"][i]) for i in range(2)]}
    np.testing.assert_allclose(
        _program_logits(TOY2, params),
        np.asarray(REF.logits(TOY2, weights, TOKENS)), rtol=0, atol=F32_TOL)


# ---- fences -----------------------------------------------------------------

def test_a_mesh_is_refused_and_the_recurrent_fences_hold():
    from gllm_tpu.engine.llm import LLM
    cfg = from_hf_config(TOY)
    with pytest.raises(ValueError, match="Mamba-2 layers"):
        LLM(config=EngineConfig(load_format="dummy", dtype="float32",
                                parallel=ParallelConfig(tp=2)),
            model_cfg=cfg)
    with pytest.raises(NotImplementedError, match="under a mesh"):
        falcon_h1.no_mesh_specs(cfg, 2)
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
    assert dataclasses.replace(cfg, first_layer=2).num_attn_layers == 1
    assert dataclasses.replace(cfg, first_layer=2).num_linear_layers == 1
