"""The short-convolution cell's roofline arithmetic, each count by hand at
the published widths of LFM2-24B-A2B (its whole depth, 8 of 64 experts a
layer, an eighth of the vocabulary), and the readers of its per-layer
metrics on sources made by hand: what they read, that a pattern gone blind
raises, and that a missing source or another family's model reads nothing
(never 0)."""

import importlib.util
import os
import re

import pytest

import _paths

CONFIG = _paths.bench_json("configs", "lfm2-24b-a2b.json")
MODEL = {k: v for k, v in CONFIG.items()
         if k not in ("reduced", "reduced_why", "assumed", "derived",
                      "rehearsal", "correct", "trace_patterns")}
PEAKS = _paths.bench_json("peaks.json")["devices"]["TPU v5 lite"]
CELL = "lfm2-24b-a2b.reason"
NEW = ["kernel.packed_attn_decode_roofline_pct",
       "kernel.packed_attn_prefill_roofline_pct",
       "kernel.sconv_moe_expert_roofline_pct",
       "kernel.sconv_roofline_pct",
       "moe.sconv_moe_experts_touched_per_step",
       "runner.sconv_moe_decode_roofline_pct",
       "runner.sconv_moe_share_of_decode_pct",
       "runner.sconv_share_of_decode_pct"]


def load(folder, name):
    path = os.path.join(_paths.BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SCONV = load("kernels", "sconv")
STEP = load("kernels", "sconv_moe_decode_step")
EXPERT = load("kernels", "moe_expert")
ATTN = load("kernels", "attn_decode")
PRE = load("kernels", "attn_prefill")
H = 2048
LAYER = H * 3 * H + H * H + 3 * H           # a conv layer's weights
WINDOW = 2 * H * 4                           # a row's window, float32
FIXED = 774819840                            # parameters every step reads


def test_the_operator_by_hand():
    assert SCONV.conv_layers(MODEL) == 30
    assert SCONV.weight_params_per_layer(MODEL) == LAYER == 16783360
    assert SCONV.window_bytes_per_row_layer(MODEL) == WINDOW == 16384 \
        == CONFIG["derived"]["window_bytes_per_sequence_layer"]
    # a row and layer: the window in and out, the input and output rows
    assert SCONV.row_bytes_per_layer(MODEL) == 2 * WINDOW + 2 * H * 2
    # one decode step of 128 rows: 1.007 GB of weights, 0.157 GB of rows
    got = SCONV.bytes_needed(MODEL, 1, 128)
    assert got == 30 * (2 * LAYER + 128 * (2 * WINDOW + 4 * H))
    assert 30 * 2 * LAYER == pytest.approx(1.007e9, rel=1e-3)
    assert SCONV.flops_needed(MODEL, 128) == 30 * 128 * (
        2 * H * 3 * H + 2 * H * H + 8 * H)
    seconds, binds = SCONV.least_seconds(MODEL, 1, 128, PEAKS)
    assert binds == "bytes"
    assert seconds == pytest.approx(got / 819e9)            # 1.42 ms


def test_decode_step_by_hand():
    attn = 2 * H * 2048 + 2 * H * 512
    dense = 3 * H * 11776
    fixed = 30 * LAYER + 10 * attn + 38 * H * 64 + 2 * dense + 8192 * H
    assert STEP.fixed_weight_params(MODEL, SCONV) == fixed == FIXED
    assert 2 * fixed == CONFIG["derived"][
        "fixed_weight_bytes_per_decode_step"]
    assert (STEP.attn_layers(MODEL), STEP.expert_layers(MODEL),
            STEP.head_dim(MODEL)) == (10, 38, 64)
    # the accepted attention counts read num_hidden_layers x head_dim: the
    # model they are handed says 10 layers of heads of 64
    seen = STEP.attn_model(MODEL)
    assert ATTN.kv_bytes_per_token(seen) == 20480 \
        == CONFIG["derived"]["kv_bytes_per_token"]
    assert ATTN.kv_bytes_per_token(MODEL) == 4 * 20480     # all 40: wrong
    assert STEP.kv_bytes(MODEL, [900, 1300]) == 20480 * 2200
    assert STEP.window_bytes(MODEL, 128, SCONV) == 2 * WINDOW * 30 * 128
    assert EXPERT.expert_bytes(MODEL) == CONFIG["derived"]["expert_bytes"] \
        == 18874368
    # 10 steps of 128 rows at 1100, every held expert touched
    got = STEP.bytes_needed(MODEL, 10, 8, [1100] * 1280, EXPERT, SCONV)
    assert got == (10 * (2 * fixed + 38 * 8 * 18874368)
                   + 1280 * 30 * 2 * WINDOW + 1280 * 1100 * 20480)
    # ISSUE 51's reckoning: 9.7-10.7 GB a step, 11.8-13.1 ms at 819 GB/s
    assert 9.7e9 < got / 10 < 10.7e9
    assert 11.8e-3 < got / 10 / 819e9 < 13.1e-3
    # the weights a step reads are 7.29 GB (all of them but the embedding
    # it gathers and the norms); the operator's 30 calls 1.0 GB of them
    assert 2 * fixed + 38 * 8 * 18874368 == pytest.approx(7.29e9, rel=3e-3)


# ---- the readers, on sources made by hand ---------------------------------

class Rec:
    def __init__(self, prompt_len, times):
        self.prompt, self.times = [0] * prompt_len, times


def prom(touched_dec=0, touched_mixed=0, held=0, layers_dec=0,
         layers_mixed=0):
    return "\n".join([
        'gllm_moe_experts_touched_total{step="decode"} %d' % touched_dec,
        'gllm_moe_experts_touched_total{step="mixed"} %d' % touched_mixed,
        'gllm_moe_assignments_total{where="held"} %d' % held,
        'gllm_moe_assignments_total{where="absent"} %d' % (7 * held),
        'gllm_moe_layer_steps_total{step="decode"} %d' % layers_dec,
        'gllm_moe_layer_steps_total{step="mixed"} %d' % layers_mixed])


TIMES = {"attn_decode": 0.060, "attn_prefill": 0.040, "moe_expert": 0.400,
         "moe_expert_decode": 0.300, "sconv_in_decode": 0.050,
         "sconv_decode": 0.120}


def a_run(kernels=None, patterns=True, in_calls=40 * 30):
    """A traced slice of 40 decode-only steps (20 ms each) and 10 mixed
    steps (50 ms each) in which 127 callers decoded 50 tokens each behind
    300 tokens and two prompts (320 and 500 tokens) got their first token;
    the counters grew over 400 + 100 steps of 38 expert layers."""
    times = dict(TIMES, **(kernels or {}))
    decoded = [Rec(300, [0.0] + [1.0 + 0.01 * j for j in range(50)])
               for _ in range(127)]
    calls = {"sconv_in_decode": in_calls}
    return {
        "peaks": PEAKS, "slice": (0.5, 2.0), "model": MODEL,
        "config": {"trace_patterns": {"kernels": dict.fromkeys(times, ".")
                                      if patterns else {}}},
        "load_module": load, "info": {"page_size": 16},
        "records": decoded + [Rec(320, [1.5]), Rec(500, [1.6])],
        "trace": {"devices": {"0": {
            "step_ms": {"decode": [20.0] * 40, "prefill": [50.0] * 10},
            "kernels": {k: {"seconds": v,
                            "calls": calls.get(k, 300) if v else 0}
                        for k, v in times.items()}}}},
        "prom0": prom(),
        "prom1": prom(touched_dec=400 * 38 * 7.5, touched_mixed=100 * 38 * 8,
                      held=500 * 38 * 70, layers_dec=400 * 38,
                      layers_mixed=100 * 38),
    }


def reader(name):
    return load("layer_metrics", name).read


def test_roofline_and_share_readers_by_hand():
    run = a_run()
    rows = 127 * 50
    ctx = [300 + j for j in range(1, 51)] * 127
    assert reader("moe.sconv_moe_experts_touched_per_step")(run) == 7.5
    # the operator's operations over the decode-only steps' time
    assert reader("runner.sconv_share_of_decode_pct")(run) == \
        pytest.approx(100 * 0.120 / 0.800)
    assert reader("runner.sconv_moe_share_of_decode_pct")(run) == \
        pytest.approx(100 * 0.300 / 0.800)
    least = SCONV.least_seconds(MODEL, 40, rows * 0.8, PEAKS)[0]
    assert reader("kernel.sconv_roofline_pct")(run) == pytest.approx(
        100 * least / 0.120)
    weights = 40 * (2 * FIXED + 38 * 7.5 * 18874368)
    moving = (2 * WINDOW * 30 * rows + 20480 * sum(ctx)) * 0.8
    assert reader("runner.sconv_moe_decode_roofline_pct")(run) == \
        pytest.approx(100 * (weights + moving) / 819e9 / 0.800)
    assert reader("kernel.packed_attn_decode_roofline_pct")(run) == \
        pytest.approx(100 * 20480 * sum(ctx) / 819e9 * 0.8 / 0.060)
    seen = STEP.attn_model(MODEL)
    flops = (PRE.flops_needed(seen, [320, 500])
             + 0.2 * ATTN.flops_needed(seen, ctx))
    nbytes = (PRE.bytes_needed(seen, [320, 500]) + 0.2 * 20480 * sum(ctx))
    assert reader("kernel.packed_attn_prefill_roofline_pct")(run) == \
        pytest.approx(100 * max(flops / PEAKS["flops_per_s"],
                                nbytes / 819e9) / 0.040)
    touched = 38 * (40 * 7.5 + 10 * 8)
    least, binds = EXPERT.least_seconds(MODEL, touched, 38 * 50 * 70, PEAKS)
    assert binds == "bytes"
    assert reader("kernel.sconv_moe_expert_roofline_pct")(run) == \
        pytest.approx(100 * least / 0.400)
    for name in NEW:
        if name.endswith("_pct"):
            assert 0 < reader(name)(run) <= 100, name


@pytest.mark.parametrize("name, kernel", [
    ("kernel.sconv_roofline_pct", "sconv_decode"),
    ("runner.sconv_share_of_decode_pct", "sconv_decode"),
    ("runner.sconv_moe_share_of_decode_pct", "moe_expert_decode"),
    ("kernel.sconv_moe_expert_roofline_pct", "moe_expert"),
    ("kernel.packed_attn_decode_roofline_pct", "attn_decode"),
    ("kernel.packed_attn_prefill_roofline_pct", "attn_prefill")])
def test_a_reader_without_its_source_reads_nothing(name, kernel):
    """None, never 0 and never an exception: where the configuration has
    no pattern for the operation, where there is no trace (a parent
    without the program's part), and where no decode-only step ran."""
    assert reader(name)(a_run(patterns=False)) is None
    assert reader(name)(dict(a_run(), trace=None)) is None
    assert reader(name)(dict(a_run(), peaks=None)) is None
    assert reader(name)(dict(a_run(), slice=None)) is None
    if kernel in ("attn_decode", "attn_prefill"):
        assert reader(name)(a_run(kernels={kernel: 0.0})) is None
    elif kernel.startswith("moe_expert"):
        # the grouped product ran in every step: a pattern that matches
        # nothing beside step programs has gone blind (lib/latent_trace.py)
        from lib import latent_trace
        with pytest.raises(latent_trace.PatternBlind):
            reader(name)(a_run(kernels={kernel: 0.0}))
    none = a_run()
    none["trace"]["devices"]["0"]["step_ms"] = {"prefill": [50.0] * 10}
    if "prefill" not in name and "expert_roofline" not in name:
        assert reader(name)(none) is None


@pytest.mark.parametrize("name", NEW)
def test_another_familys_model_reads_nothing(name):
    """The readers are this family's: on the state-space cell, whose
    server counts the same expert counters and whose configuration names
    the same attention patterns, they leave the metric out."""
    nemotron = _paths.bench_json("configs", "nemotron-3-nano-30b-a3b.json")
    assert reader(name)(dict(a_run(), model=nemotron)) is None
    assert reader(name)(dict(a_run(), model={"hidden_size": 8})) is None


def test_fewer_operator_calls_a_step_than_layers_raises():
    from lib import sconv_trace
    for name in ("kernel.sconv_roofline_pct",
                 "runner.sconv_share_of_decode_pct"):
        with pytest.raises(sconv_trace.PatternBlind,
                           match="fewer operator calls a step than layers"):
            reader(name)(a_run(in_calls=40 * 10))
        # a slice's edges cut a step or two: a few calls short is sound
        assert reader(name)(a_run(in_calls=38 * 30)) > 0


def test_every_new_metric_is_this_cells_alone_and_has_its_reader():
    """By the entries' NAMES and ``workloads``, never by their place."""
    manifest = _paths.manifest()
    per_layer = manifest["per_layer"]
    mine = [m for m in per_layer if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == NEW
    for m in mine:
        assert os.path.isfile(os.path.join(
            _paths.BENCH, "layer_metrics", m["name"] + ".py"))
    rooflines = [m for m in mine if m["name"].endswith("_roofline_pct")]
    assert len(rooflines) == 5
    assert all(m["unit"] == "%" and m["source"] == "device_trace"
               and m["better"] == "higher" for m in rooflines)
    moves = {m["name"]: m["moves"] for m in mine}
    assert moves["runner.sconv_moe_decode_roofline_pct"] == "output_tok_s"
    assert moves["kernel.packed_attn_prefill_roofline_pct"] == "ttft_p50_ms"
    assert moves["runner.sconv_share_of_decode_pct"] == "itl_p95_ms"
    assert moves["moe.sconv_moe_experts_touched_per_step"] == "itl_p95_ms"
    layers = {m["name"]: m["layer"] for m in mine}
    assert set(layers.values()) == {"runner", "kernels"}
    # the other slot-pool cells' metrics stay their own (what the accepted
    # tests of PR 44 and PR 48 held before the slot gauge's list grew to
    # four cells)
    alone = lambda cell: len([m for m in per_layer
                              if m.get("workloads") == [cell]])
    assert alone("falcon-h1-34b-instruct.reason") == 8
    assert alone("command-a-plus-05-2026.docqa") == 10
    assert alone("nemotron-3-nano-30b-a3b.reason") == 10
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == 7 and len(cells) == 8
    # every list that named all seven cells names this one behind them
    everywhere = [m for m in per_layer if len(m.get("workloads", [])) >= 5]
    assert len(everywhere) == 18
    assert all(m["workloads"] == cells for m in everywhere)
    gauge = next(m for m in per_layer
                 if m["name"] == "kv.ssm_slots_peak_pct")
    assert gauge["workloads"] == [
        "olmo-hybrid-7b.reason", "nemotron-3-nano-30b-a3b.reason",
        "falcon-h1-34b-instruct.reason", CELL]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_experts", "vocab_size", "max_position_embeddings"]
    assert entry["source"] == CONFIG["source"]
    assert len(entry["why"]) <= 200
    assert not [w for w in manifest["workloads"] if w["chips"] != 1]


def test_the_configuration_names_every_pattern_the_readers_ask_for():
    kernels = CONFIG["trace_patterns"]["kernels"]
    assert set(TIMES) <= set(kernels)
    assert kernels["attn_decode"] == "^%paged_decode_attention"
    assert kernels["moe_expert"].startswith("^%gmm|")
    classes = CONFIG["trace_patterns"]["step_classes"]
    assert classes["decode"] == {"has": ["attn_decode"],
                                 "lacks": ["attn_prefill"]}
    layout = "{1,0:T(8,128)(2,1)S(1)}"
    lines = {
        # the witness: the in-projection's product, by its result
        "sconv_in_decode": [
            f"%fusion.887 = bf16[128,6144]{layout} fusion(bf16[30,2048,6144]"
            " %get-tuple-element.4091, s32[] %select_n.1515, bf16[128,2048]"],
        # everything that produces or reads it, or a window
        "sconv_decode": [
            f"%fusion.887 = bf16[128,6144]{layout} fusion(bf16[30,2048,6144]",
            "%fusion.884 = f32[128,2,2048]{2,1,0:T(2,128)S(1)} fusion("
            "f32[3870,2,2048]{2,1,0:T(2,128)} %bitcast.849, s32[1024]",
            "%fusion.889 = (f32[128]{0}, bf16[128,2048]{1,0}) fusion(bf16["
            "128,2048] %gte.1, bf16[30,2048,2048] %gte.2, s32[] %s, "
            f"bf16[128,6144]{layout} %fusion.887, f32[128,1,2048] %x)",
            "%fusion.902 = f32[3870,2,2048]{2,1,0:T(2,128)} fusion(f32[3870,"
            "2,2048]{2,1,0:T(2,128)} %bitcast.850, s32[128]{0} %copy-done.41,"
            " f32[128,2,2048]{2,1,0:T(2,128)S(1)} %copy.260)",
            f"%copy-start.8 = (bf16[128,6144]{layout}, bf16[128,6144], u32[])"
            " copy-start(bf16[128,6144] %fusion.887)",
            "%fusion.901 = f32[128,1,2048]{2,0,1:T(8,128)S(1)} fusion("
            "bf16[128,6144] %copy-done.9)"],
        # a decode-only step: every held expert times every row, the
        # array between the products as a result and as an operand
        "moe_expert_decode": [
            "%fusion.616 = bf16[8,128,1536]{2,1,0:T(8,128)(2,1)} fusion("
            "bf16[38,8,2048,1536] %gte.2614, s32[] %select_n.508",
            "%fusion.617 = bf16[128,2048]{1,0} fusion(bf16[38,8,1536,2048] "
            "%gte.2615, s32[] %s, f32[128,8] %w, bf16[8,128,1536]{2,1,0} "
            "%fusion.615, bf16[8,128,1536]{2,1,0} %fusion.616)"],
        "moe_expert": ["%gmm.9 = bf16[512,2048]{1,0} custom-call(",
                       "%fusion.616 = bf16[8,128,1536]{2,1,0} fusion("],
    }
    for kernel, some in lines.items():
        for line in some:
            assert re.search(kernels[kernel], line), (kernel, line)
    # a mixed step's rows are not 128; a copy of the product is not a
    # call of the operator; the stream's bf16 [128, 2048] is not the
    # operator's; a while is never taken
    for kernel, line in (
            ("sconv_in_decode", "%fusion.5 = bf16[512,6144]{1,0} fusion("),
            ("sconv_in_decode", f"%copy-done.8 = bf16[128,6144]{layout} "
                                "copy-done("),
            ("sconv_decode", "%fusion.891 = bf16[128,2048]{1,0} fusion("
                             "bf16[128,2048] %gte.3844, f32[1,2048] %b)"),
            ("sconv_decode", "%fusion.7 = f32[2176,2,2048]{2,1,0} fusion("),
            ("sconv_decode", "%while.170 = (s32[], bf16[128,6144]) while("),
            ("moe_expert_decode", "%gmm.9 = bf16[512,2048]{1,0} "
                                  "custom-call("),
            ("moe_expert", "%while.170 = (s32[], bf16[8,128,1536]) while(")):
        assert not re.search(kernels[kernel], line), (kernel, line)
