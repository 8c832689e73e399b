"""perfbench/reference/axk1.py against the program at the configuration's
rehearsal widths on the CPU: the seeded weights bit for bit, the forward
through chunked prefill (three chunks) and then decode through the paged
latent cache, the same with a cached prefix of whole pages (a second
request that hits), the sensitivity of the comparison to YaRN's scale and
frequencies, and the lower-precision control, which must fail.

Tolerances. Both sides compute in float32 here; the served path attends in
the ABSORBED form (queries folded through W_uk, outputs through W_uv, over
latent rows) and the reference in the DECOMPRESSED form (keys and values
per head): the same sums in another order, 1e-6 of the logprobs' spread.
The rehearsal's own limits (0.001) are a thousand times that, and what a
missing piece does (a plain 1/sqrt(d) softmax scale, plain rotary
frequencies, int8 matrices) is ten to a thousand times the limits."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
from lib import compare
from lib.refchild import load_family

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.models import deepseek
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.sampling_params import SamplingParams

REF = load_family("axk1")
CONFIG = _paths.bench_json("configs", "a.x-k1.json")
SKIP = ("name", "source", "reduced", "reduced_why", "assumed", "chips",
        "deployment", "reference", "stage_layers", "server_flags",
        "control_flags", "probe", "derived", "rehearsal", "correct",
        "trace_patterns")
MODEL = dict({k: v for k, v in CONFIG.items() if k not in SKIP},
             **CONFIG["rehearsal"]["model"])
LIMITS = CONFIG["rehearsal"]["correct"]
SEED = 2 ** 31 + 37
PAGE = 8


def test_rehearsal_widths_keep_the_share_the_router_and_yarn():
    assert REF.experts_of(MODEL) == (32, 2, 0)
    assert MODEL["topk_method"] == "none" and MODEL["n_group"] == 8
    inv, cs, scale = REF.yarn(MODEL)
    assert cs == 1.0
    # both ends of YaRN's blend are inside the rehearsal's rope dims
    plain = REF.yarn(dict(MODEL, rope_scaling=None))[0]
    assert inv[0] == plain[0] and inv[-1] == pytest.approx(plain[-1] / 32)
    assert scale == pytest.approx(24 ** -0.5 * 1.81326, rel=1e-4)


def test_yarn_scale_at_the_published_widths_is_1_8133():
    published = {k: v for k, v in CONFIG.items() if k not in SKIP}
    inv, cs, scale = REF.yarn(published)
    assert cs == 1.0
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)
    assert scale / 192 ** -0.5 == pytest.approx(1.81326, abs=5e-5)
    # the served path computes the same from the same keys
    g = deepseek.geom(from_hf_config(CONFIG))
    assert g.scale == pytest.approx(scale, rel=1e-6)
    assert (g.heads, g.q_lora, g.lora, g.nope, g.rope, g.v, g.width) == (
        64, 1536, 512, 128, 64, 128, 640)
    # pairs 0..9 turn at the plain frequency, 23.. at a 32nd of it
    plain = REF.yarn(dict(published, rope_scaling=None))[0]
    np.testing.assert_allclose(inv[:11], plain[:11])
    np.testing.assert_allclose(inv[23:], plain[23:] / 32)
    assert all(plain[i] / 32 < inv[i] < plain[i] for i in range(11, 23))


def serve(quantization=None, prefix=False):
    """What run.py does, in one process: the served logprobs of a prompt
    longer than the prefill chunk (three chunks of 32 tokens) and of a
    decode through the pages. With ``prefix`` the cache already holds the
    whole pages of both prompts' first 64 and 32 tokens (an earlier
    request left them), so both requests are prefix hits and compute only
    the rest."""
    from gllm_tpu.engine.llm import LLM
    llm = LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        max_num_seqs=8, quantization=quantization,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=PAGE, num_pages=256,
                          enable_prefix_caching=prefix)),
        model_cfg=from_hf_config(MODEL))
    rng = random.Random(5)
    long_probe = rng.choices(range(2, 512), k=90)      # three chunks
    dec_prompt = rng.choices(range(2, 512), k=40)
    hit = None
    if prefix:
        mm = llm.memory_managers[0]
        llm.generate(
            prompt_token_ids=[long_probe[:64] + [7, 7, 7],
                              dec_prompt[:32] + [9, 9]],
            sampling_params=SamplingParams(temperature=0.0, max_tokens=2,
                                           ignore_eos=True))
        hit = (mm.hit_tokens, mm.query_tokens)
    out = llm.generate(
        prompt_token_ids=[long_probe, dec_prompt],
        sampling_params=[
            SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True,
                           prompt_logprobs=1),
            SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                           logprobs=3)])
    if prefix:
        mm = llm.memory_managers[0]
        hit = (mm.hit_tokens - hit[0], mm.query_tokens - hit[1])
    served_prefill = [None if t is None else float(t[0])
                      for t in out[0].prompt_logprobs[1:]]
    tops = [{int(i): float(v) for i, v in zip(ids, lps)}
            for _, ids, lps in out[1].logprobs]
    return (MODEL, long_probe, dec_prompt, list(out[1].output_token_ids),
            served_prefill, tops, llm.runner.params, {"llm": llm, "hit": hit})


_WEIGHTS = {}


def reference_weights(dtype=jnp.float32):
    if dtype not in _WEIGHTS:
        _WEIGHTS[dtype] = REF.make_weights(MODEL, SEED, dtype)
    return _WEIGHTS[dtype]


def against_reference(served, knobs=(), control=None, skip=0):
    """``skip``: leading positions of the prefill probe the served side
    gave no logprob for (a cached prefix is not computed again)."""
    model, long_probe, dec_prompt, decoded, served_prefill, tops = served[:6]
    weights = reference_weights()

    def logprobs(tokens, want):
        hid = REF.hidden_states(model, weights, tokens, control, knobs)
        with jax.default_matmul_precision("highest"):
            lp = np.asarray(jax.nn.log_softmax(
                REF._mm(hid, weights["lm_head"]), axis=-1))
        return [[float(lp[i, t]) for t in ids] for i, ids in enumerate(want)]
    ref_prefill = logprobs(long_probe, [[t] for t in long_probe[1:]] + [[]])
    full = dec_prompt + decoded
    want = [[] for _ in full]
    for j, top in enumerate(tops):
        want[len(dec_prompt) - 1 + j] = sorted(top)
    ref_decode = logprobs(full, want)
    return compare.verdict(served_prefill[skip:],
                           [v[0] for v in ref_prefill[:-1]][skip:], tops,
                           ref_decode[len(dec_prompt) - 1:], LIMITS)


@pytest.fixture(scope="module")
def served():
    return serve()


def test_seeded_weights_are_the_programs_bit_for_bit(served):
    """The served engine's own parameters (float32 here; both sides draw in
    float32 and cast afterwards) against the reference's draws."""
    mine = reference_weights()
    cfg = from_hf_config(MODEL)
    theirs = served[6]
    layer = 0
    for (_, mlp, n), lp in zip(deepseek.layer_runs(cfg),
                               deepseek.run_params(theirs, cfg)):
        for i in range(n):
            ml = mine["layers"][layer]
            assert ml["mlp"] == mlp
            names = [k for k in ml if k != "mlp"]
            assert set(names) == set(lp), set(names) ^ set(lp)
            for name in names:
                np.testing.assert_array_equal(
                    np.asarray(lp[name][i], np.float32),
                    np.asarray(ml[name], np.float32),
                    err_msg=f"layer {layer} {name}")
            layer += 1
    assert layer == 5
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(theirs[name], np.float32),
                                      np.asarray(mine[name], np.float32))


def test_reference_agrees_with_prefill_then_decode_through_the_cache(served):
    v = against_reference(served)
    assert v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] < 1e-4
    assert v["numbers"]["decode_rel_rms"] < 1e-4
    # no expert layer ran with a bias or a group limit, and the held
    # experts' counters are on for this family
    cfg = served[7]["llm"].model_cfg
    assert cfg.route_groups == 0 and deepseek.has_stats(cfg)
    assert "e_bias" not in served[6]["moe_layers"]


def test_a_request_that_hits_a_cached_prefix_gives_the_same_logits(served):
    """The second request of a caller: its first 64 (32) tokens are whole
    cached pages, so only the rest is computed, in chunks that attend the
    cached latent rows, and the logprobs are those of the run without a
    cache, and the reference's."""
    hit = serve(prefix=True)
    assert hit[7]["hit"] == (64 + 32, 90 + 40)
    assert hit[3] == served[3]                      # the same greedy tokens
    # prompt logprobs exist from the first computed position on
    assert all(v is None for v in hit[4][:63])
    np.testing.assert_allclose(hit[4][64:], served[4][64:], rtol=0,
                               atol=2e-5)
    for a, b in zip(hit[5], served[5]):
        assert sorted(a) == sorted(b)
        np.testing.assert_allclose([a[k] for k in sorted(a)],
                                   [b[k] for k in sorted(b)], atol=2e-5)
    v = against_reference(hit, skip=64)
    assert v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] < 1e-4
    assert v["numbers"]["decode_rel_rms"] < 1e-4


@pytest.mark.parametrize("knob", ["yarn_scale", "yarn_freq"])
def test_yarn_cannot_be_left_out_unnoticed(knob, served):
    """The reference with the softmax scale's 1.8134 dropped, or with plain
    rotary frequencies, differs from the served logprobs by far more than
    the limits."""
    v = against_reference(served, knobs=(knob,))
    assert not v["correct"], (knob, v["lines"])
    assert v["numbers"]["prefill_rel_rms"] > 10 * LIMITS[
        "prefill_rel_rms_max"], (knob, v["numbers"])


def test_the_reference_in_lower_precision_is_not_correct(served):
    v = against_reference(served, control="int8")
    assert not v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] > 3 * LIMITS["prefill_rel_rms_max"]


@pytest.mark.slow
def test_the_comparison_fails_a_served_side_in_lower_precision():
    v = against_reference(serve(quantization="int8"))
    assert not v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] > 3 * LIMITS["prefill_rel_rms_max"]


def test_logprobs_in_blocks_are_the_whole_logits(served):
    model, long_probe = served[0], served[1]
    weights = reference_weights()
    want = [[t] for t in long_probe[1:]] + [[]]
    blocked = REF.logprobs(model, weights, long_probe, want, block=32)
    whole = np.asarray(jax.nn.log_softmax(
        REF.logits(model, weights, long_probe), axis=-1))
    np.testing.assert_allclose(
        [v[0] for v in blocked[:-1]],
        [whole[i, t] for i, t in enumerate(long_probe[1:])], atol=1e-5)


def test_parts_of_a_draw_are_jax_random_normals_numbers():
    key = jax.random.key(11)
    whole = np.asarray(jax.random.normal(key, (3, 50, 7), jnp.float32))
    part = np.asarray(REF.normal_part(jax.random.key_data(key),
                                      np.uint32(350 + 13), 200))
    np.testing.assert_array_equal(part, whole.reshape(-1)[363:563])


def test_reference_imports_nothing_of_the_program():
    import os
    src = open(os.path.join(_paths.BENCH, "reference", "axk1.py")).read()
    assert "gllm_tpu" not in src.split('"""', 2)[2]
    assert "dots3" not in src
