"""perfbench/reference/qwen3.py against the program at tiny widths on the
CPU: the seeded weights bit for bit, and the forward through chunked
prefill and then decode with the cache, for the tied case and for the
untied case stitched from pipeline stages. The same comparison must fail
when the served side computes in the precision below (int8 weights)."""

import dataclasses
import random

import jax.numpy as jnp
import numpy as np
import pytest

import _paths
from lib import compare
from lib.refchild import load_family

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.models import dense
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.sampling_params import SamplingParams

REF = load_family("qwen3")
TINY = _paths.bench_json("configs", "tiny-qwen3.json")
LIMITS = TINY["correct"]
SEED = 2 ** 31 + 77


def model(tied):
    keys = ("architectures", "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "max_position_embeddings", "rms_norm_eps",
            "rope_theta", "eos_token_id")
    return dict({k: TINY[k] for k in keys}, tie_word_embeddings=tied)


@pytest.mark.parametrize("tied,stages", [
    (True, None), (False, None), (False, [[0, 1], [1, 4]]),
    (False, [[0, 1], [1, 2], [2, 3], [3, 4]])],
    ids=["tied", "untied", "untied-2-stages", "untied-4-stages"])
def test_seeded_weights_are_the_programs_bit_for_bit(tied, stages):
    m = model(tied)
    mine = REF.make_weights(m, SEED, jnp.bfloat16, stages)
    cfg = from_hf_config(m)
    layer = 0
    for first, last in stages or [[0, 4]]:
        scfg = dataclasses.replace(cfg, first_layer=first, last_layer=last)
        theirs = dense.init_params(scfg, seed=SEED, dtype=jnp.bfloat16)
        for i in range(last - first):
            for name, stacked in theirs["layers"].items():
                np.testing.assert_array_equal(
                    np.asarray(stacked[i], np.float32),
                    np.asarray(mine["layers"][layer][name], np.float32),
                    err_msg=f"layer {layer} {name}")
            layer += 1
        for name in ("embed", "lm_head", "final_norm"):
            if name in theirs:
                np.testing.assert_array_equal(
                    np.asarray(theirs[name], np.float32),
                    np.asarray(mine[name], np.float32), err_msg=name)
    assert layer == 4
    assert (mine["lm_head"] is None) == tied


def serve_and_compare(tied, quantization=None):
    """What run.py does, in one process: the served logprobs of a prompt
    longer than the prefill chunk and of a decode through the cache,
    against the reference on its own seeded weights."""
    from gllm_tpu.engine.llm import LLM
    m = model(tied)
    llm = LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        max_num_seqs=8, quantization=quantization,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=256)),
        model_cfg=from_hf_config(m))
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
    weights = REF.make_weights(m, SEED, jnp.float32)
    ref_prefill = REF.logprobs(m, weights, long_probe,
                               [[t] for t in long_probe[1:]] + [[]])
    full = dec_prompt + list(out[1].output_token_ids)
    want = [[] for _ in full]
    for j, top in enumerate(tops):
        want[len(dec_prompt) - 1 + j] = sorted(top)
    ref_decode = REF.logprobs(m, weights, full, want)
    return compare.verdict(served_prefill,
                           [v[0] for v in ref_prefill[:-1]], tops,
                           ref_decode[len(dec_prompt) - 1:], LIMITS)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_reference_agrees_with_prefill_then_decode_through_the_cache(tied):
    v = serve_and_compare(tied)
    assert v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] < 1e-4
    assert v["numbers"]["decode_rel_rms"] < 1e-4


def test_the_comparison_fails_a_served_side_in_lower_precision():
    v = serve_and_compare(True, quantization="int8")
    assert not v["correct"], v["lines"]
    # three times the limit or more: the control does not sit on the line
    assert v["numbers"]["prefill_rel_rms"] > 3 * LIMITS["prefill_rel_rms_max"]
    assert v["numbers"]["decode_rel_rms"] > 3 * LIMITS["decode_rel_rms_max"]


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_the_reference_in_lower_precision_is_not_correct(mode):
    """The control of the chip runs, at a size a test can hold: the
    reference itself with its layer matrices stored in the precision below,
    put in the program's place."""
    m = model(True)
    weights = REF.make_weights(m, SEED, jnp.float32)
    rng = random.Random(6)
    probe = rng.choices(range(2, 512), k=90)
    want = [[t] for t in probe[1:]] + [[]]
    ref = [v[0] for v in REF.logprobs(m, weights, probe, want)[:-1]]
    low = [v[0] for v in REF.logprobs(m, weights, probe, want,
                                      control=mode)[:-1]]
    dec_want = [[] for _ in probe]
    dec_want[-1] = [3, 4, 5]
    ref_d = REF.logprobs(m, weights, probe, dec_want)[-1:]
    low_d = REF.logprobs(m, weights, probe, dec_want, control=mode)[-1:]
    v = compare.verdict(low, ref, [dict(zip([3, 4, 5], low_d[0]))], ref_d,
                        LIMITS)
    assert not v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] > 3 * LIMITS["prefill_rel_rms_max"]


def test_a_wrong_token_order_fails_the_comparison():
    served = [-1.0, -2.0, -3.0, -4.0]
    v = compare.verdict(served, served[::-1], [{3: -0.5}], [[-0.5]],
                        {"prefill_rel_rms_max": 0.05,
                         "decode_rel_rms_max": 0.05})
    assert not v["correct"]
    v = compare.verdict(served, served, [{3: -0.5}], [[-0.5]],
                        {"prefill_rel_rms_max": 0.05,
                         "decode_rel_rms_max": 0.05})
    assert v["correct"]


def test_a_non_finite_served_logprob_is_not_correct():
    v = compare.verdict([-1.0, float("nan")], [-1.0, -2.0], [{3: -0.5}],
                        [[-0.5]], {"prefill_rel_rms_max": 1.0,
                                   "decode_rel_rms_max": 1.0})
    assert not v["correct"]
