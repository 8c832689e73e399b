"""perfbench/reference/nemotron_h.py against the program at the
configuration's rehearsal widths on the CPU: the seeded weights bit for
bit, the forward through chunked prefill (state and window cross chunk
borders) and then decode through the slot pool as run.py compares them,
the recurrence against a loop written out by hand, and the
lower-precision controls, which must fail."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
from lib import compare
from lib.refchild import load_family

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.models import nemotron_h
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.sampling_params import SamplingParams


REF = load_family("nemotron_h")
CONFIG = _paths.bench_json("configs", "nemotron-3-nano-30b-a3b.json")
SKIP = ("name", "source", "reduced", "reduced_why", "assumed", "chips",
        "deployment", "reference", "stage_layers", "server_flags",
        "control_flags", "probe", "derived", "rehearsal", "correct",
        "trace_patterns")
MODEL = dict({k: v for k, v in CONFIG.items() if k not in SKIP},
             **CONFIG["rehearsal"]["model"])
# float32 on both sides: what is left is the order of the sums (chunked
# against token by token), 1e-6 of the spread; the limits the rehearsal
# holds itself to are a thousand times that and a hundredth of what int8
# weights give
LIMITS = CONFIG["rehearsal"]["correct"]
SEED = 2 ** 31 + 77


def test_seeded_weights_are_the_programs_bit_for_bit():
    mine = REF.make_weights(MODEL, SEED, jnp.bfloat16)
    cfg = from_hf_config(MODEL)
    theirs = nemotron_h.init_params(cfg, seed=SEED, dtype=jnp.bfloat16)
    din, conv = cfg.mamba_d_inner, cfg.gdn_conv_dim
    inter = cfg.moe_intermediate_size
    names = {
        "mamba": ("mamba_layers", {
            "conv_w": "conv_w", "conv_b": "conv_b", "dt_bias": "dt_bias",
            "A_log": "a_log", "D": "d", "gate_norm": "gate_norm",
            "out_proj": "out_proj", "norm": "norm"}),
        "attention": ("attn_layers", {k: k for k in (
            "q_proj", "k_proj", "v_proj", "o_proj", "norm")}),
        "moe": ("moe_layers", {
            "router": "router", "e_bias": "e_bias",
            "shared_up": "shared_up_proj", "shared_down": "shared_down_proj",
            "norm": "norm"}),
    }

    def eq(a, b, what):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=what)
    at = {"mamba": 0, "attention": 0, "moe": 0}
    for li, layer in enumerate(mine["layers"]):
        kind = layer["kind"]
        group, same = names[kind]
        i = at[kind]
        at[kind] += 1
        for name, leaf in same.items():
            eq(theirs[group][leaf][i], layer[name], f"layer {li} {name}")
        if kind == "mamba":
            stored = theirs[group]["in_proj"][i]
            eq(stored[:, :din + conv + cfg.mamba_num_heads],
               layer["in_proj"], f"layer {li} in_proj")
            assert stored.shape[-1] % 128 == 0
            assert not np.asarray(
                stored[:, din + conv + cfg.mamba_num_heads:]).any()
        if kind == "moe":
            up, down = theirs[group]["w_up"][i], theirs[group]["w_down"][i]
            eq(up[:, :, :inter], layer["w_up"], f"layer {li} w_up")
            eq(down[:, :inter], layer["w_down"], f"layer {li} w_down")
            assert up.shape[-1] % 128 == 0 == down.shape[1] % 128
            assert not np.asarray(up[:, :, inter:]).any()
            assert not np.asarray(down[:, inter:]).any()
    assert at == {"mamba": 4, "attention": 1, "moe": 4}
    for name in ("embed", "lm_head", "final_norm"):
        eq(theirs[name], mine[name], name)
    # the published initialiser: the state matters under these weights
    a = np.exp(-np.logaddexp(0, np.asarray(mine["layers"][0]["dt_bias"]))
               * np.exp(np.asarray(mine["layers"][0]["A_log"])))
    assert 0.19 < a.min() and a.max() < 1.0 and (a > 0.9).mean() > 0.3


def serve_and_compare(quantization=None):
    """What run.py does, in one process: the served logprobs of a prompt
    longer than the prefill chunk (three chunks: the state and the window
    cross two chunk borders) and of a decode through the slot pool,
    against the reference on its own weights."""
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
        got, last = REF.ssm_scan(*(jnp.asarray(v) for v in (x, dt, a, B, C)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(last[-1]), S, rtol=2e-5, atol=2e-5)


def test_the_share_is_the_configurations():
    assert REF.experts_of(MODEL) == (8, 4, 0)
    assert REF.experts_of(dict(MODEL, ep_share={
        "chips": 2, "rank": 1, "n_routed_experts": 8})) == (8, 4, 4)
    whole = {k: v for k, v in MODEL.items() if k != "ep_share"}
    assert REF.experts_of(whole) == (4, 4, 0)


def test_reference_imports_nothing_of_the_program():
    import os
    src = open(os.path.join(_paths.BENCH, "reference",
                            "nemotron_h.py")).read()
    assert "gllm_tpu" not in src.split('"""', 2)[2]
