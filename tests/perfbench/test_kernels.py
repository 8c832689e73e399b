"""Each kernel's FLOP and byte function against a hand count at one shape,
and the table of peaks."""

import pytest

import _paths
from lib.serving import BenchFailure
from run import load_module, load_peaks, model_of

MODEL = model_of(_paths.bench_json("configs", "qwen3-4b.json"))
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_kv_bytes_per_token_by_hand():
    k = load_module("kernels", "attn_decode")
    # 2 (k, v) x 36 layers x 8 kv heads x 128 x 2 bytes
    assert k.kv_bytes_per_token(MODEL) == 2 * 36 * 8 * 128 * 2 == 147456


def test_decode_attention_bytes_and_flops_by_hand():
    k = load_module("kernels", "attn_decode")
    ctx = [1000, 24]
    assert k.bytes_needed(MODEL, ctx) == 147456 * 1024
    # qk^T and pv: 2 x 2 x 32 heads x 128 per context token per layer
    assert k.flops_needed(MODEL, ctx) == 4 * 32 * 128 * 36 * 1024
    seconds, bound = k.least_seconds(MODEL, ctx, PEAKS)
    assert bound == "bytes"
    assert seconds == pytest.approx(147456 * 1024 / 819e9)


def test_prefill_attention_flops_by_hand():
    k = load_module("kernels", "attn_prefill")
    assert k.causal_pairs([4]) == 10            # 1 + 2 + 3 + 4
    assert k.causal_pairs([3, 2]) == 6 + 3
    # 4 x Hq x D per causal pair per layer
    assert k.flops_needed(MODEL, [2048]) == (
        4 * 32 * 128 * 36 * (2048 * 2049 // 2))
    # q and out (32 heads) and k, v (8 heads) of 128 x 2 bytes, per layer
    assert k.bytes_needed(MODEL, [100]) == (
        (2 * 32 + 2 * 8) * 128 * 2 * 36 * 100)
    _, bound = k.least_seconds(MODEL, [2048], PEAKS)
    assert bound == "flops"
    _, bound = k.least_seconds(MODEL, [8], PEAKS)
    assert bound == "bytes"


def test_decode_step_weight_bytes_by_hand():
    k = load_module("kernels", "decode_step")
    per_layer = (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560
                 + 3 * 2560 * 9728)
    params = 36 * per_layer + 151936 * 2560
    assert k.weight_params(MODEL) == params == 4022272000
    assert k.weight_bytes_per_step(MODEL) == 2 * params
    assert k.weight_bytes_per_step(MODEL, chips=4) == params / 2
    derived = _paths.bench_json("configs", "qwen3-4b.json")["derived"]
    assert derived["weight_bytes_read_per_decode_step"] == 2 * params
    assert derived["kv_bytes_per_token"] == 147456


def test_peaks_of_the_v5e_are_the_published_ones():
    p = load_peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_an_unlisted_device_is_an_error(kind):
    with pytest.raises(BenchFailure):
        load_peaks(kind)
