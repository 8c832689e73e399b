"""perfbench/reference/falcon_h1.py against the program at the
configuration's rehearsal widths on the CPU: the seeded weights bit for bit
(the embedding and the head in several blocks), the forward through
chunked prefill (state, window and pages cross chunk borders) and then
decode through both pools as run.py compares them, the recurrence and the
rotary embedding against loops written out by hand, and the
lower-precision controls, which must fail."""

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
from gllm_tpu.models import falcon_h1
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.sampling_params import SamplingParams


REF = load_family("falcon_h1")
CONFIG = _paths.bench_json("configs", "falcon-h1-34b-instruct.json")
SKIP = ("name", "source", "reduced", "reduced_why", "assumed", "chips",
        "deployment", "reference", "stage_layers", "server_flags",
        "control_flags", "probe", "derived", "rehearsal", "correct",
        "trace_patterns")
MODEL = dict({k: v for k, v in CONFIG.items() if k not in SKIP},
             **CONFIG["rehearsal"]["model"])
# float32 on both sides: what is left is the order of the sums (chunked
# against token by token), 1e-6 of the spread; the limits the rehearsal
# holds itself to are a thousand times that
LIMITS = CONFIG["rehearsal"]["correct"]
SEED = 2 ** 31 + 148


def test_seeded_weights_are_the_programs_bit_for_bit(monkeypatch):
    # three blocks of the vocabulary's 512 rows (171, 171, 170) on both
    # sides: the published 261120 rows are drawn in eight of 32640
    monkeypatch.setattr(REF, "VOCAB_BLOCK", 200)
    monkeypatch.setattr(falcon_h1, "VOCAB_BLOCK", 200)
    mine = REF.make_weights(MODEL, SEED, jnp.bfloat16)
    cfg = from_hf_config(MODEL)
    theirs = falcon_h1.init_params(cfg, seed=SEED, dtype=jnp.bfloat16)
    width = cfg.mamba_d_inner + cfg.gdn_conv_dim + cfg.mamba_num_heads
    same = {k: k for k in (
        "q_proj", "k_proj", "v_proj", "o_proj", "conv_w", "conv_b",
        "dt_bias", "gate_norm", "out_proj", "gate_proj", "up_proj",
        "down_proj", "input_norm", "pre_ff_norm")}
    same.update(A_log="a_log", D="d")

    def eq(a, b, what):
        assert a.dtype == b.dtype, what
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=what)
    assert len(mine["layers"]) == 3
    for li, layer in enumerate(mine["layers"]):
        for name, leaf in same.items():
            eq(theirs["layers"][leaf][li], layer[name], f"layer {li} {name}")
        stored = theirs["layers"]["in_proj"][li]
        eq(stored[:, :width], layer["in_proj"], f"layer {li} in_proj")
        assert width == 132 and stored.shape[-1] == 256
        assert not np.asarray(stored[:, width:]).any()
    for name in ("embed", "lm_head", "final_norm"):
        eq(theirs[name], mine[name], name)
    # the loudness: what a multiplier follows is drawn that much larger
    emb = np.asarray(mine["embed"], np.float32).std()
    assert abs(emb * MODEL["embedding_multiplier"] - 1) < 0.05
    k = np.asarray(mine["layers"][0]["k_proj"], np.float32).std()
    assert abs(k * MODEL["key_multiplier"] * 8 - 1) < 0.1      # 8 = sqrt(64)
    # the Mamba-2 initialiser: the state matters under these weights
    a = np.exp(-np.logaddexp(0, np.asarray(mine["layers"][0]["dt_bias"]))
               * np.exp(np.asarray(mine["layers"][0]["A_log"])))
    assert 0.19 < a.min() and a.max() < 1.0


def serve_and_compare(quantization=None):
    """What run.py does, in one process: the served logprobs of a prompt
    longer than the prefill chunk (three chunks: state, window and pages
    cross two chunk borders) and of a decode through both pools, against
    the reference on its own weights."""
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
    assert 0.5 < v["numbers"]["spread"] < 2.0
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


def test_recurrence_is_the_equations_written_out_by_hand():
    """``ssm_scan`` (a ``lax.scan`` over tokens) against a numpy loop over
    tokens and heads that follows the equations letter by letter."""
    rng = np.random.default_rng(3)
    t, h, p, n = 19, 3, 4, 5
    x = rng.standard_normal((t, h, p)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (t, h)).astype(np.float32)
    a = rng.uniform(0.2, 0.99, (t, h)).astype(np.float32)
    B, C = (rng.standard_normal((t, h, n)).astype(np.float32)
            for _ in range(2))
    want = np.zeros((t, h, p), np.float32)
    for head in range(h):
        S = np.zeros((p, n), np.float32)
        for i in range(t):
            S = a[i, head] * S + dt[i, head] * np.outer(x[i, head],
                                                        B[i, head])
            want[i, head] = S @ C[i, head]
    with jax.default_matmul_precision("highest"):
        got = REF.ssm_scan(*(jnp.asarray(v) for v in (x, dt, a, B, C)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_rotary_embedding_rotates_the_halves_by_the_published_base():
    """Pair (j, j + D / 2) of a head turns by position x base^(-2 j / D):
    written out in float64, at the published base 1e11."""
    rng = np.random.default_rng(4)
    h, t, d, base = 2, 7, 8, 1e11
    x = rng.standard_normal((h, t, d))
    want = np.zeros_like(x)
    for pos in range(t):
        for j in range(d // 2):
            ang = pos * base ** (-2.0 * j / d)
            a, b = x[:, pos, j], x[:, pos, j + d // 2]
            want[:, pos, j] = a * np.cos(ang) - b * np.sin(ang)
            want[:, pos, j + d // 2] = b * np.cos(ang) + a * np.sin(ang)
    got = REF.rotary(jnp.asarray(x, jnp.float32), base)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # five query heads read one KV head: head j reads j // 5
    assert MODEL["num_attention_heads"] // MODEL["num_key_value_heads"] == 5


def test_the_multipliers_are_spread_over_the_in_projections_columns():
    mu = REF.mu_vector(MODEL)
    assert mu.shape == (32 + 32 + 2 * 2 * 16 + 4,)
    got = [mu[0], mu[32], mu[64], mu[96], mu[128]]
    assert got == MODEL["ssm_multipliers"]
    assert mu[31] == mu[0] and mu[63] == mu[32] and mu[95] == mu[64]
    assert mu[127] == mu[96] and mu[131] == mu[128]


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(_paths.BENCH, "reference",
                            "falcon_h1.py")).read()
    assert "gllm_tpu" not in src.split('"""', 2)[2]
