"""perfbench/reference/dots3_note.py against the program at the
configuration's rehearsal widths on the CPU: the seeded weights bit for
bit, the forward through chunked prefill and then decode through the three
caches (contexts past the rehearsal's top-k and window, so that the
selection is active and ring rows are recycled), the sensitivity of the
comparison to each part of the layer, and the lower-precision control,
which must fail."""

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

REF = load_family("dots3_note")
CONFIG = _paths.bench_json("configs", "dots3-note-prev.json")
SKIP = ("name", "source", "reduced", "reduced_why", "assumed", "chips",
        "deployment", "reference", "stage_layers", "server_flags",
        "control_flags", "probe", "derived", "rehearsal", "correct",
        "trace_patterns")
MODEL = dict({k: v for k, v in CONFIG.items() if k not in SKIP},
             **CONFIG["rehearsal"]["model"])
# float32 on both sides: what is left is the order of the sums (absorbed
# and blocked against per head and whole), 1e-6 of the spread; the limits
# the rehearsal holds itself to are 1000 times that
LIMITS = CONFIG["rehearsal"]["correct"]
SEED = 2 ** 31 + 77


def test_rehearsal_widths_keep_both_kinds_a_window_a_topk_and_a_share():
    assert MODEL["layer_types"].count("full_attention") == 2
    assert MODEL["layer_types"].count("sliding_attention") == 3
    probe = CONFIG["rehearsal"]["probe"]
    assert MODEL["index_topk"] < probe["decode_prompt_tokens"]
    assert MODEL["sliding_window_size"] < probe["decode_prompt_tokens"]
    assert REF.experts_of(MODEL) == (32, 4, 0)


def test_seeded_weights_are_the_programs_bit_for_bit(served):
    """The served engine's own parameters (float32 here; both sides draw in
    float32 and cast afterwards) against the reference's draws."""
    mine = reference_weights()
    cfg = from_hf_config(MODEL)
    theirs = served[6]
    layer = 0
    for (kind, mlp, n), lp in zip(deepseek.layer_runs(cfg),
                                  deepseek.run_params(theirs, cfg)):
        for i in range(n):
            ml = mine["layers"][layer]
            assert (ml["kind"], ml["mlp"]) == (kind, mlp)
            names = [k for k in ml if k not in ("kind", "mlp")]
            assert set(names) == set(lp)
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


def _counters():
    from gllm_tpu.memory_manager import _M_SSM_SLOTS, _M_SWA_SLOTS
    return {"seen": deepseek._M_DSA.get(what="seen"),
            "chosen": deepseek._M_DSA.get(what="chosen"),
            "held": deepseek._M_MOE_ASSIGN.get(where="held"),
            "absent": deepseek._M_MOE_ASSIGN.get(where="absent"),
            "layers": deepseek._M_MOE_STEPS.get(step="mixed"),
            "rings": _M_SWA_SLOTS.get(), "ssm": _M_SSM_SLOTS.get()}


def serve(quantization=None):
    """What run.py does, in one process: the served logprobs of a prompt
    longer than the prefill chunk (three chunks; 90 tokens against a top-k
    of 24, a window of 13 and rings of 20 rows) and of a decode through
    the pages and the rings."""
    from gllm_tpu.engine.llm import LLM
    model = MODEL
    before = _counters()
    llm = LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        max_num_seqs=8, quantization=quantization,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=256)),
        model_cfg=from_hf_config(model))
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
    after = _counters()
    return (model, long_probe, dec_prompt, list(out[1].output_token_ids),
            served_prefill, tops, llm.runner.params,
            {"llm": llm, "counted": {k: after[k] - before[k] for k in after}})


_WEIGHTS = {}


def reference_weights(dtype=jnp.float32):
    """The seeded weights, drawn once (every draw is a program of its
    own to compile)."""
    if dtype not in _WEIGHTS:
        _WEIGHTS[dtype] = REF.make_weights(MODEL, SEED, dtype)
    return _WEIGHTS[dtype]


def against_reference(served, knobs=(), control=None):
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
    return compare.verdict(served_prefill,
                           [v[0] for v in ref_prefill[:-1]], tops,
                           ref_decode[len(dec_prompt) - 1:], LIMITS)


@pytest.fixture(scope="module")
def served():
    return serve()


def test_reference_agrees_with_prefill_then_decode_through_the_caches(
        served):
    v = against_reference(served)
    assert v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] < 1e-4
    assert v["numbers"]["decode_rel_rms"] < 1e-4


def test_windowed_layers_hold_a_ring_and_the_counters_count(served):
    """Contexts of 90 and 48 tokens through rings of 20 rows: a windowed
    layer never holds more of a sequence than its ring (the pool's shape is
    the bound: window + one page, far under window + one 32-token chunk),
    the gauge follows the callers, and the step counters add up."""
    llm, counted = served[7]["llm"], served[7]["counted"]
    cfg, kv = llm.model_cfg, llm.runner.kv
    assert kv.swa.shape == (3, 1 + 8, 20, 128)
    assert kv.swa.shape[2] < cfg.sliding_window + 32
    assert kv.latent.shape[0] == kv.index_k.shape[0] == 2
    assert kv.index_k.dtype == jnp.float32 and kv.index_scale is None
    assert llm.runner.latent_pool_bytes() == (
        kv.latent.nbytes, kv.index_k.nbytes, kv.swa.nbytes)
    # both callers are gone: no ring held, and the GDN gauge never moved
    assert counted["rings"] == 0 and counted["ssm"] == 0
    assert llm.memory_manager.ssm_alloc.num_free == 8
    # every token fed was scored once a full layer: a token at position p
    # sees p + 1 positions and chooses min(p + 1, 24); the last sampled
    # token of a sequence is never fed
    lens = [90, 40 + 8 - 1]
    assert counted["seen"] == 2 * sum(n * (n + 1) // 2 for n in lens)
    assert counted["chosen"] == 2 * sum(
        sum(min(p + 1, 24) for p in range(n)) for n in lens)
    # 4 expert layers, top 2 of 32 with 4 held
    assert counted["held"] + counted["absent"] == 4 * 2 * sum(lens)
    assert 0 < counted["held"] < counted["absent"]
    assert counted["layers"] % 4 == 0


@pytest.mark.parametrize("knob", ["indexer", "window", "gate", "rescale"])
def test_no_part_of_the_layer_can_be_left_out_unnoticed(knob, served):
    """The reference with the indexer off (every visible position
    attended), the window ignored, the gates dropped or the rescale left
    out differs from the served logprobs by far more than the limits."""
    v = against_reference(served, knobs=(knob,))
    assert not v["correct"], (knob, v["lines"])
    assert v["numbers"]["prefill_rel_rms"] > 10 * LIMITS[
        "prefill_rel_rms_max"], (knob, v["numbers"])
    assert v["numbers"]["decode_rel_rms"] > 10 * LIMITS[
        "decode_rel_rms_max"], (knob, v["numbers"])


@pytest.mark.slow
def test_the_comparison_fails_a_served_side_in_lower_precision():
    v = against_reference(serve(quantization="int8"))
    assert not v["correct"], v["lines"]
    assert v["numbers"]["prefill_rel_rms"] > 3 * LIMITS["prefill_rel_rms_max"]
    assert v["numbers"]["decode_rel_rms"] > 3 * LIMITS["decode_rel_rms_max"]


def test_the_reference_in_lower_precision_is_not_correct(served):
    v = against_reference(served, control="int8")
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


def test_reference_imports_nothing_of_the_program():
    import os
    src = open(os.path.join(_paths.BENCH, "reference",
                            "dots3_note.py")).read()
    assert "gllm_tpu" not in src.split('"""', 2)[2]
