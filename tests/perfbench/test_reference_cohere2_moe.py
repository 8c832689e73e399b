"""perfbench/reference/cohere2_moe.py against the program at the
configuration's rehearsal widths on the CPU: the seeded weights bit for
bit, the forward through chunked prefill (a chunk's queries lose rows to
the window and attend cached pages) and then decode through the pages as
run.py compares them, the mask, the rotary turn and the norm against
their equations written out by hand, and the lower-precision controls,
which must fail."""

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
from gllm_tpu.models import cohere2_moe
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.sampling_params import SamplingParams


REF = load_family("cohere2_moe")
CONFIG = _paths.bench_json("configs", "command-a-plus-05-2026.json")
SKIP = ("name", "source", "reduced", "reduced_why", "assumed", "chips",
        "deployment", "reference", "stage_layers", "server_flags",
        "control_flags", "probe", "derived", "rehearsal", "correct",
        "trace_patterns")
MODEL = dict({k: v for k, v in CONFIG.items() if k not in SKIP},
             **CONFIG["rehearsal"]["model"])
# float32 on both sides: what is left is the order of the sums (pages and
# blocks against one dense product), 1e-6 of the spread; the limits the
# rehearsal holds itself to are a thousand times that and a tenth of what
# int8 weights give
LIMITS = CONFIG["rehearsal"]["correct"]
SEED = 2 ** 31 + 44


def test_seeded_weights_are_the_programs_bit_for_bit():
    mine = REF.make_weights(MODEL, SEED, jnp.bfloat16)
    theirs = cohere2_moe.init_params(from_hf_config(MODEL), seed=SEED,
                                     dtype=jnp.bfloat16)
    same = {"norm": "norm", "q_proj": "q_proj", "k_proj": "k_proj",
            "v_proj": "v_proj", "o_proj": "o_proj", "router": "router",
            "shared_gate": "shared_gate_proj", "shared_up": "shared_up_proj",
            "shared_down": "shared_down_proj", "w_gate": "w_gate",
            "w_up": "w_up", "w_down": "w_down"}

    def eq(a, b, what):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=what)
    assert [la["kind"] for la in mine["layers"]] == 3 * [
        "sliding_attention"] + ["full_attention"]
    for li, layer in enumerate(mine["layers"]):
        for name, leaf in same.items():
            eq(theirs["layers"][leaf][li], layer[name], f"layer {li} {name}")
    assert set(theirs["layers"]) == set(same.values())
    for name in ("embed", "final_norm"):
        eq(theirs[name], mine[name], name)
    assert "lm_head" not in theirs and "lm_head" not in mine       # tied
    # the router is as wide as published, 2 of 16 experts are held
    assert mine["layers"][0]["router"].shape == (64, 16)
    assert mine["layers"][0]["w_gate"].shape == (2, 64, 32)
    assert mine["layers"][0]["shared_down"].shape == (4 * 32, 64)


def serve_and_compare(quantization=None):
    """What run.py does, in one process: the served logprobs of a prompt
    longer than the prefill chunk and than the window (three chunks: the
    later ones' queries see cached pages and lose rows to the window) and
    of a decode past the window, against the reference on its own
    weights."""
    from gllm_tpu.engine.llm import LLM
    llm = LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        max_num_seqs=8, quantization=quantization,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=8, num_pages=256,
                          enable_prefix_caching=True)),
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


def test_reference_agrees_with_prefill_then_decode_through_the_pages():
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


def test_mask_rotary_and_norm_are_the_equations_written_out_by_hand():
    # the mask, position by position: t - window < j <= t
    pos = jnp.arange(9)
    seen = np.asarray(REF.visible("sliding_attention", pos, pos, 4))
    for t in range(9):
        for j in range(9):
            assert seen[t, j] == (t - 4 < j <= t), (t, j)
    full = np.asarray(REF.visible("full_attention", pos, pos, 4))
    assert (full == np.tril(np.ones((9, 9), bool))).all()
    assert seen.sum(1).tolist() == [1, 2, 3, 4, 4, 4, 4, 4, 4]
    # the rotary turn, pair by pair
    rng = np.random.default_rng(3)
    x = rng.standard_normal((5, 2, 8)).astype(np.float32)
    got = np.asarray(REF.rope_gptj(jnp.asarray(x), jnp.arange(5) + 3, 50000))
    for t in range(5):
        for i in range(4):
            ang = (t + 3) * 50000 ** (-2 * i / 8)
            a, b = x[t, :, 2 * i], x[t, :, 2 * i + 1]
            np.testing.assert_allclose(
                got[t, :, 2 * i], a * np.cos(ang) - b * np.sin(ang),
                rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(
                got[t, :, 2 * i + 1], a * np.sin(ang) + b * np.cos(ang),
                rtol=1e-5, atol=1e-5)
    # the norm subtracts the mean (an RMSNorm would not)
    row = rng.standard_normal((3, 16)).astype(np.float32) + 5.0
    w = rng.standard_normal(16).astype(np.float32)
    want = (row - row.mean(-1, keepdims=True)) / np.sqrt(
        row.var(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(
        np.asarray(REF.layer_norm(jnp.asarray(row), jnp.asarray(w), 1e-5)),
        want, rtol=1e-5, atol=1e-5)


def test_attention_in_query_blocks_is_attention_whole():
    weights = REF.make_weights(MODEL, SEED, jnp.float32)
    h = jax.random.normal(jax.random.key(1), (70, 64), jnp.float32)
    for layer in (weights["layers"][0], weights["layers"][3]):
        layer = dict(layer)
        kind = layer.pop("kind")
        with jax.default_matmul_precision("highest"):
            whole = REF.attention(MODEL, h, layer, kind, REF._mm, q_block=128)
            blocks = REF.attention(MODEL, h, layer, kind, REF._mm, q_block=16)
        np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                                   rtol=1e-5, atol=1e-5)


def test_the_rows_asked_alone_are_the_rows_of_the_whole():
    """A decode probe asks for its last few positions: the last layer's
    block is computed for those rows alone, and reads what the whole
    pass reads there."""
    weights = REF.make_weights(MODEL, SEED, jnp.float32)
    tokens = random.Random(8).choices(range(2, 512), k=77)
    whole = REF.hidden_states(MODEL, weights, tokens)
    rows = [0, 23, 24, 70, 76]
    some = REF.hidden_states(MODEL, weights, tokens, rows=rows)
    np.testing.assert_allclose(np.asarray(some),
                               np.asarray(whole)[np.asarray(rows)],
                               rtol=1e-5, atol=1e-5)


def test_the_share_is_the_configurations():
    assert REF.held_experts(MODEL) == (16, [0, 1])
    assert REF.held_experts(dict(MODEL, ep_share={
        "chips": 8, "rank": 3, "num_experts": 16})) == (16, [6, 7])
    whole = {k: v for k, v in MODEL.items() if k != "ep_share"}
    assert REF.held_experts(whole) == (2, [0, 1])
    assert REF.held_experts(
        {k: v for k, v in CONFIG.items() if k not in SKIP}) == (
            128, list(range(16)))


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(_paths.BENCH, "reference",
                            "cohere2_moe.py")).read()
    assert "gllm_tpu" not in src.split('"""', 2)[2]
    assert "paged" not in src and "pallas" not in src.lower()
