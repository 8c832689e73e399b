"""perfbench/reference/lfm2_moe.py against the program at the
configuration's rehearsal widths on the CPU: the seeded weights bit for
bit, the forward through chunked prefill (window and pages cross chunk
borders) and then decode through both pools as run.py compares them, the
convolution and the rotary embedding against loops written out by hand,
and the lower-precision controls, which must fail."""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
from lib import compare
from lib.refchild import load_family

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.models import lfm2_moe
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.sampling_params import SamplingParams


REF = load_family("lfm2_moe")
CONFIG = _paths.bench_json("configs", "lfm2-24b-a2b.json")
SKIP = ("name", "source", "reduced", "reduced_why", "assumed", "chips",
        "deployment", "reference", "stage_layers", "server_flags",
        "control_flags", "probe", "derived", "rehearsal", "correct",
        "trace_patterns")
MODEL = dict({k: v for k, v in CONFIG.items() if k not in SKIP},
             **CONFIG["rehearsal"]["model"])
# float32 on both sides: what is left is the order of the sums, 1e-6 of
# the spread; the limits the rehearsal holds itself to are a thousand
# times that
LIMITS = CONFIG["rehearsal"]["correct"]
SEED = 2 ** 31 + 151


def test_seeded_weights_are_the_programs_bit_for_bit():
    mine = REF.make_weights(MODEL, SEED, jnp.bfloat16)
    cfg = from_hf_config(MODEL)
    theirs = lfm2_moe.init_params(cfg, seed=SEED, dtype=jnp.bfloat16)
    groups = {"conv": ("conv_layers", {"op_norm": "norm",
                                       "in_proj": "in_proj",
                                       "taps": "conv_w",
                                       "out_proj": "out_proj"}),
              "attn": ("attn_layers", {"op_norm": "norm",
                                       "q_proj": "q_proj",
                                       "k_proj": "k_proj",
                                       "v_proj": "v_proj",
                                       "o_proj": "o_proj",
                                       "q_norm": "q_norm",
                                       "k_norm": "k_norm"}),
              "dense": ("dense_layers", {"ffn_norm": "norm",
                                         "w1": "gate_proj", "w3": "up_proj",
                                         "w2": "down_proj"}),
              "moe": ("moe_layers", {"ffn_norm": "norm", "router": "router",
                                     "expert_bias": "e_bias", "w1": "w_gate",
                                     "w3": "w_up", "w2": "w_down"})}

    def eq(a, b, what):
        assert a.dtype == b.dtype, what
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=what)
    assert len(mine["layers"]) == 7
    seen = {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
    for li, layer in enumerate(mine["layers"]):
        for kind in (layer["op"], layer["ffn"]):
            group, leaves = groups[kind]
            for name, leaf in leaves.items():
                eq(theirs[group][leaf][seen[kind]], layer[name],
                   f"layer {li} {name}")
            seen[kind] += 1
    assert seen == {"conv": 5, "attn": 2, "dense": 2, "moe": 5}
    for name in ("embed", "final_norm"):
        eq(theirs[name], mine[name], name)
    assert "lm_head" not in theirs and "lm_head" not in mine
    # two of sixteen experts held, the router sixteen wide; the bias zeros
    assert mine["layers"][2]["w1"].shape == (2, 64, 48)
    assert mine["layers"][2]["router"].shape == (16, 64)
    assert not np.asarray(mine["layers"][2]["expert_bias"]).any()
    # the loudness: the taps give c unit variance for unit g, and the tied
    # embedding is drawn at its fan-in as a head
    taps = np.asarray(mine["layers"][0]["taps"], np.float32)
    assert taps.shape == (3, 64) and abs(taps.std() * 3 ** 0.5 - 1) < 0.15
    assert abs(np.asarray(mine["embed"], np.float32).std() * 8 - 1) < 0.05


def serve_and_compare(quantization=None):
    """What run.py does, in one process: the served logprobs of a prompt
    longer than the prefill chunk (three chunks: window and pages cross
    two chunk borders) and of a decode through both pools, against the
    reference on its own weights."""
    from gllm_tpu.engine.llm import LLM
    llm = LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        max_num_seqs=8, quantization=quantization,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=256)),
        model_cfg=from_hf_config(MODEL))
    rng = random.Random(5)
    long_probe = rng.choices(range(2, 512), k=90)      # three chunks
    dec_prompt = rng.choices(range(2, 512), k=40)
    out = llm.generate(
        prompt_token_ids=[long_probe, dec_prompt],
        sampling_params=[
            SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True,
                           prompt_logprobs=1),
            SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                           logprobs=3)])
    served_prefill = [float(t[0]) for t in out[0].prompt_logprobs[1:]]
    tops = [{int(i): float(v) for i, v in zip(ids, lps)}
            for _, ids, lps in out[1].logprobs]
    weights = REF.make_weights(MODEL, SEED, jnp.float32)
    ref_prefill = REF.logprobs(MODEL, weights, long_probe,
                               [[t] for t in long_probe[1:]] + [[]])
    full = dec_prompt + list(out[1].output_token_ids)
    want = [[] for _ in full]
    for j, top in enumerate(tops):
        want[len(dec_prompt) - 1 + j] = sorted(top)
    ref_decode = REF.logprobs(MODEL, weights, full, want)
    return compare.verdict(served_prefill,
                           [v[0] for v in ref_prefill[:-1]], tops,
                           ref_decode[len(dec_prompt) - 1:], LIMITS)


def test_reference_agrees_with_prefill_then_decode_through_the_pools():
    v = serve_and_compare()
    assert v["correct"], v["lines"]
    assert 0.3 < v["numbers"]["spread"] < 2.0
    assert v["numbers"]["prefill_rel_rms"] < 1e-4
    assert v["numbers"]["decode_rel_rms"] < 1e-4


def test_the_comparison_fails_a_served_side_in_lower_precision():
    v = serve_and_compare(quantization="int8")
    assert not v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] > 3 * LIMITS["prefill_rel_rms_max"]
    assert v["numbers"]["decode_rel_rms"] > 3 * LIMITS["decode_rel_rms_max"]


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_the_reference_in_lower_precision_is_not_correct(mode):
    weights = REF.make_weights(MODEL, SEED, jnp.float32)
    rng = random.Random(6)
    probe = rng.choices(range(2, 512), k=90)
    want = [[t] for t in probe[1:]] + [[]]
    ref = [v[0] for v in REF.logprobs(MODEL, weights, probe, want)[:-1]]
    low = [v[0] for v in REF.logprobs(MODEL, weights, probe, want,
                                      control=mode)[:-1]]
    dec_want = [[] for _ in probe]
    dec_want[-1] = [3, 4, 5]
    ref_d = REF.logprobs(MODEL, weights, probe, dec_want)[-1:]
    low_d = REF.logprobs(MODEL, weights, probe, dec_want, control=mode)[-1:]
    v = compare.verdict(low, ref, [dict(zip([3, 4, 5], low_d[0]))], ref_d,
                        LIMITS)
    assert not v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] > 3 * LIMITS["prefill_rel_rms_max"]


def test_the_convolution_is_the_equations_written_out_by_hand():
    """``short_conv`` against a numpy loop over tokens and taps that
    follows the equations letter by letter: no activation, zeros before
    the sequence, the thirds in the order B | C | z."""
    rng = np.random.default_rng(3)
    t, d, taps = 11, 6, 3
    model = {"conv_L_cache": taps}
    u = rng.standard_normal((t, d)).astype(np.float32)
    layer = {"in_proj": rng.standard_normal((d, 3 * d)).astype(np.float32),
             "taps": rng.standard_normal((taps, d)).astype(np.float32),
             "out_proj": rng.standard_normal((d, d)).astype(np.float32)}
    bcz = u @ layer["in_proj"]
    b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    g = b * z
    conv = np.zeros((t, d), np.float32)
    for i in range(t):
        for j in range(taps):
            src = i - (taps - 1) + j
            if src >= 0:
                conv[i] += layer["taps"][j] * g[src]
    want = (c * conv) @ layer["out_proj"]
    with jax.default_matmul_precision("highest"):
        got = REF.short_conv(model, jnp.asarray(u),
                             {k: jnp.asarray(v) for k, v in layer.items()},
                             REF._stored(None))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # the newest tap multiplies the token's own input
    alone = np.zeros((t, d), np.float32)
    alone[4] = 1.0
    padded = np.pad(alone, ((taps - 1, 0), (0, 0)))
    assert [float(padded[j + 4, 0]) for j in range(taps)] == [0.0, 0.0, 1.0]


def test_rotary_embedding_rotates_the_halves_by_the_published_base():
    """Pair (j, j + D / 2) of a head turns by position x base^(-2 j / D):
    written out in float64, at the published base 1e6."""
    rng = np.random.default_rng(4)
    h, t, d, base = 2, 7, 8, 1e6
    x = rng.standard_normal((t, h, d))
    want = np.zeros_like(x)
    for pos in range(t):
        for j in range(d // 2):
            ang = pos * base ** (-2.0 * j / d)
            a, b = x[pos, :, j], x[pos, :, j + d // 2]
            want[pos, :, j] = a * np.cos(ang) - b * np.sin(ang)
            want[pos, :, j + d // 2] = b * np.cos(ang) + a * np.sin(ang)
    got = REF.rope_halves(jnp.asarray(x, jnp.float32), jnp.arange(t), base)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # four query heads read one KV head: head j reads j // 4
    assert MODEL["num_attention_heads"] // MODEL["num_key_value_heads"] == 4


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(_paths.BENCH, "reference",
                            "lfm2_moe.py")).read()
    assert "gllm_tpu" not in src.split('"""', 2)[2]
