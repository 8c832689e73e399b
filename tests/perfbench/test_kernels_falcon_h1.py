"""The parallel-hybrid cell's roofline arithmetic, each count by hand at
the published widths of Falcon-H1-34B (6 of its 72 layers, the whole
261120-row head), and the readers of its per-layer metrics on sources made
by hand: what they read, that a pattern gone blind raises, and that a
missing source or another family's model reads nothing (never 0)."""

import importlib.util
import os
import re

import pytest

import _paths

CONFIG = _paths.bench_json("configs", "falcon-h1-34b-instruct.json")
MODEL = {k: v for k, v in CONFIG.items()
         if k not in ("reduced", "reduced_why", "assumed", "derived",
                      "rehearsal", "correct", "trace_patterns")}
PEAKS = _paths.bench_json("peaks.json")["devices"]["TPU v5 lite"]
CELL = "falcon-h1-34b-instruct.reason"
NEW = ["kernel.par_attn_decode_roofline_pct",
       "kernel.par_attn_prefill_roofline_pct",
       "kernel.par_mamba_chunk_roofline_pct",
       "kernel.par_mamba_decode_roofline_pct",
       "runner.par_head_share_of_decode_pct",
       "runner.par_hybrid_decode_roofline_pct",
       "runner.par_mamba_chunk_fill_pct",
       "runner.par_mamba_share_of_decode_pct"]


def load(folder, name):
    path = os.path.join(_paths.BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DEC = load("kernels", "par_mamba_decode")
CHUNK = load("kernels", "par_mamba_chunk")
STEP = load("kernels", "par_hybrid_decode_step")
ATTN = load("kernels", "attn_decode")
PRE = load("kernels", "attn_prefill")
STATE = 4255744         # a row and layer: 32 x 128 x 256 + 3 x 5120, float32


def test_mamba_decode_by_hand():
    assert DEC.layers(MODEL) == 6 and DEC.conv_dim(MODEL) == 5120
    assert DEC.state_bytes_per_row_layer(MODEL) == 4 * (1048576 + 15360) \
        == STATE == CONFIG["derived"]["state_bytes_per_sequence_layer"]
    # twice Nemotron 3 Nano's 2170880: the state is 128 x 256 a head
    assert DEC.bytes_needed(MODEL, 64) == 2 * STATE * 6 * 64     # 3.27 GB
    assert DEC.flops_needed(MODEL, 64) == 5 * 128 * 256 * 32 * 6 * 64
    seconds, binds = DEC.least_seconds(MODEL, 64, PEAKS)
    assert binds == "bytes"
    assert seconds == pytest.approx(3268411392 / 819e9)           # 4.0 ms


def test_mamba_chunk_by_hand():
    per = 2 * 128 * 256 + 32 * (128 * 128 + 4 * 128 * 256)
    assert CHUNK.flops_per_token_layer(MODEL) == per == 4784128
    assert CHUNK.flops_needed(MODEL, [320, 500], DEC) == per * 6 * 820
    # conv input and branch output of 2 B a token, state and window once
    # in and once out a prompt
    assert CHUNK.bytes_needed(MODEL, [320], DEC) == 6 * (
        320 * (5120 + 4096) * 2 + 2 * STATE)
    seconds, binds = CHUNK.least_seconds(MODEL, [320], PEAKS, DEC)
    assert binds == "bytes"
    assert seconds == pytest.approx(86458368 / 819e9)


def test_decode_step_by_hand():
    h = 5120
    layer = (2 * h * 2560 + 2 * h * 512          # q, o; k, v
             + h * (4096 + 5120 + 32) + 5120 * 5 + 4096 * h
             + 3 * h * 21504)
    assert STEP.layer_weight_params(MODEL, DEC) == layer == 430105600
    assert STEP.head_params(MODEL) == 261120 * h == 1336934400
    fixed = 6 * layer + 261120 * h
    assert STEP.fixed_weight_params(MODEL, DEC) == fixed == 3917568000
    assert 2 * fixed == CONFIG["derived"]["fixed_weight_bytes_per_decode_step"]
    # the head is a third of the weights a step reads here, a 25th whole
    assert 261120 * h / fixed == pytest.approx(0.341, abs=0.001)
    assert 261120 * h / (72 * layer + 261120 * h) == pytest.approx(
        0.041, abs=0.001)
    # the accepted attention counts read this family's own keys: 6 layers
    # x 4 KV heads x 128 x 2 (k, v) x 2 B = 12288 B a token
    assert ATTN.kv_bytes_per_token(MODEL) == 12288 \
        == CONFIG["derived"]["kv_bytes_per_token"]
    # 10 steps of 64 rows at 900
    got = STEP.bytes_needed(MODEL, 10, [900] * 640, DEC, ATTN)
    assert got == 10 * 2 * fixed + 640 * 6 * 2 * STATE + 640 * 900 * 12288
    # ISSUE 48's reckoning: 11.8 GB a step, 14.4 ms at 819 GB/s, the head
    # 22 % of it
    assert got / 10 == pytest.approx(11.8e9, rel=0.01)
    assert got / 10 / 819e9 == pytest.approx(14.4e-3, rel=0.01)
    assert 2 * 261120 * h / (got / 10) == pytest.approx(0.226, abs=0.005)


# ---- the readers, on sources made by hand ---------------------------------

class Rec:
    def __init__(self, prompt_len, times):
        self.prompt, self.times = [0] * prompt_len, times


def prom(tokens=0, slots=0):
    return (f"gllm_mamba_chunk_tokens_total {tokens}\n"
            f"gllm_mamba_chunk_slots_total {slots}")


TIMES = {"mamba_recurrent": 0.200, "mamba_conv": 0.030, "mamba_norm": 0.020,
         "mamba_chunk_scan": 0.010, "mamba_chunk": 0.060,
         "attn_decode": 0.020, "attn_prefill": 0.030, "head": 0.160}


def a_run(kernels=None, patterns=True):
    """A traced slice of 40 decode-only steps (20 ms each) and 10 mixed
    steps (50 ms each) in which 63 callers decoded 50 tokens each behind
    300 tokens and two prompts (320 and 500 tokens) got their first
    token."""
    times = dict(TIMES, **(kernels or {}))
    decoded = [Rec(300, [0.0] + [1.0 + 0.01 * j for j in range(50)])
               for _ in range(63)]
    calls = {"mamba_chunk": 60 * 12, "mamba_chunk_scan": 60}
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
        "prom0": prom(), "prom1": prom(tokens=8200, slots=20480),
    }


def reader(name):
    return load("layer_metrics", name).read


def test_roofline_and_share_readers_by_hand():
    run = a_run()
    rows = 63 * 50
    ctx = [300 + j for j in range(1, 51)] * 63
    assert reader("kernel.par_mamba_decode_roofline_pct")(run) == \
        pytest.approx(100 * DEC.least_seconds(MODEL, rows, PEAKS)[0] / 0.230)
    assert reader("kernel.par_mamba_chunk_roofline_pct")(run) == \
        pytest.approx(100 * CHUNK.least_seconds(
            MODEL, [320, 500], PEAKS, DEC)[0] / 0.060)
    # the Mamba-2 operations over all steps, scaled to the decode-only ones
    assert reader("runner.par_mamba_share_of_decode_pct")(run) == \
        pytest.approx(100 * 0.250 * 0.8 / 0.800)
    assert reader("runner.par_head_share_of_decode_pct")(run) == \
        pytest.approx(100 * 0.160 * 0.8 / 0.800)
    assert reader("runner.par_mamba_chunk_fill_pct")(run) == pytest.approx(
        100 * 8200 / 20480)
    weights = 40 * 2 * 3917568000
    moving = (2 * STATE * 6 * rows + 12288 * sum(ctx)) * 0.8
    assert reader("runner.par_hybrid_decode_roofline_pct")(run) == \
        pytest.approx(100 * (weights + moving) / 819e9 / 0.800)
    assert reader("kernel.par_attn_decode_roofline_pct")(run) == \
        pytest.approx(100 * 12288 * sum(ctx) / 819e9 * 0.8 / 0.020)
    flops = (PRE.flops_needed(MODEL, [320, 500])
             + 0.2 * ATTN.flops_needed(MODEL, ctx))
    nbytes = (PRE.bytes_needed(MODEL, [320, 500]) + 0.2 * 12288 * sum(ctx))
    assert reader("kernel.par_attn_prefill_roofline_pct")(run) == \
        pytest.approx(100 * max(flops / PEAKS["flops_per_s"],
                                nbytes / 819e9) / 0.030)
    for name in NEW:
        assert 0 < reader(name)(run) <= 100, name


@pytest.mark.parametrize("name, kernel", [
    ("kernel.par_mamba_decode_roofline_pct", "mamba_recurrent"),
    ("kernel.par_mamba_chunk_roofline_pct", "mamba_chunk_scan"),
    ("runner.par_mamba_share_of_decode_pct", "mamba_recurrent"),
    ("runner.par_head_share_of_decode_pct", "head"),
    ("kernel.par_attn_decode_roofline_pct", "attn_decode"),
    ("kernel.par_attn_prefill_roofline_pct", "attn_prefill")])
def test_a_reader_without_its_kernel_reads_nothing(name, kernel):
    """None, never 0 and never an exception: where the named kernel is off
    the path, where the configuration has no pattern for it, and where
    there is no trace (a parent without the program's part)."""
    assert reader(name)(a_run(kernels={kernel: 0.0})) is None
    assert reader(name)(a_run(patterns=False)) is None
    assert reader(name)(dict(a_run(), trace=None)) is None
    assert reader(name)(dict(a_run(), peaks=None)) is None
    assert reader(name)(dict(a_run(), slice=None)) is None


@pytest.mark.parametrize("name", NEW)
def test_another_familys_model_reads_nothing(name):
    """The readers are this family's: on the state-space cell of blocks of
    one mixer, whose configuration names the same patterns and whose
    server counts the same counters, they leave the metric out."""
    nemotron = _paths.bench_json("configs", "nemotron-3-nano-30b-a3b.json")
    assert reader(name)(dict(a_run(), model=nemotron)) is None
    assert reader(name)(dict(a_run(), model={"hidden_size": 8})) is None


def test_a_pattern_gone_blind_raises():
    from lib import mamba_trace
    for name, kernel in (
            ("kernel.par_mamba_decode_roofline_pct", "mamba_conv"),
            ("runner.par_mamba_share_of_decode_pct", "mamba_norm")):
        run = a_run()
        run["trace"]["devices"]["0"]["kernels"][kernel]["calls"] = 10
        with pytest.raises(mamba_trace.PatternBlind, match="no longer"):
            reader(name)(run)


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
    assert moves["runner.par_hybrid_decode_roofline_pct"] == "output_tok_s"
    assert moves["kernel.par_mamba_chunk_roofline_pct"] == "ttft_p50_ms"
    assert moves["runner.par_head_share_of_decode_pct"] == "itl_p95_ms"
    # the other state-space cell's ten stay its own
    assert len([m for m in per_layer if m.get("workloads")
                == ["nemotron-3-nano-30b-a3b.reason"]]) == 10
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == 6
    # every list that named all six cells names this one behind them, and
    # the slot gauge's list too
    everywhere = [m for m in per_layer if len(m.get("workloads", [])) >= 4]
    assert len(everywhere) >= 18
    assert all(m["workloads"][:7] == cells[:7] for m in everywhere)
    gauge = next(m for m in per_layer
                 if m["name"] == "kv.ssm_slots_peak_pct")
    assert gauge["workloads"] == ["olmo-hybrid-7b.reason",
                                  "nemotron-3-nano-30b-a3b.reason", CELL]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "falcon-h1-34b-instruct")
    assert entry["reduced"] == ["num_hidden_layers",
                                "max_position_embeddings"]
    assert len(entry["why"]) <= 200


def test_the_configuration_names_every_pattern_the_readers_ask_for():
    kernels = CONFIG["trace_patterns"]["kernels"]
    assert set(TIMES) <= set(kernels)
    assert kernels["mamba_recurrent"] == "^%mamba2_recurrent_step"
    assert kernels["mamba_chunk_scan"] == "^%mamba2_chunk_scan"
    classes = CONFIG["trace_patterns"]["step_classes"]
    assert classes["decode"] == {"has": ["attn_decode"],
                                 "lacks": ["attn_prefill"]}
    lines = {
        "mamba_conv": ["%fusion.12 = f32[64,3,5120]{2,1,0} fusion(",
                       "%fusion.7 = (f32[64,5120]{1,0}, f32[64,32]) fusion("],
        "mamba_norm": ["%fusion.3 = f32[64,4096]{1,0} fusion(",
                       "%reduce.1 = f32[2112,2]{1,0} fusion("],
        "mamba_chunk": ["%mamba2_chunk_scan.1 = (f32[32,32,128,128]",
                        "%fusion.9 = f32[32,32,128,256]{3,2,1,0} fusion("],
        # under greedy sampling the product is fused with the argmax:
        # the vocabulary is an operand's width, the result a value a row
        "head": ["%iota_reduce_fusion = (bf16[64], s32[64]) fusion("
                 "bf16[5120,261120] %params__lm_head__.1, bf16[64,5120] %f",
                 "%fusion.44 = bf16[64,261120]{1,0} fusion(",
                 "%sort.2 = (f32[64,261120]{1,0}, s32[64,261120]) sort("],
    }
    for kernel, some in lines.items():
        for line in some:
            assert re.search(kernels[kernel], line), (kernel, line)
    # the stream is 5120 wide too, in bf16: not the convolution's; the
    # step kernel's own [64, 32, 128, 256] is not the chunked rule's; a
    # while is never taken
    for kernel, line in (
            ("mamba_conv", "%fusion.5 = bf16[64,5120]{1,0} fusion("),
            ("mamba_chunk", "%mamba2_recurrent_step.1 = (f32[64,32,128]"),
            ("head", "%while.3 = (bf16[64,261120]) while("),
            ("head", "%fusion.8 = bf16[64,5120]{1,0} fusion(bf16[261120,"
                     "5120] %params__embed__.1, s32[64] %copy.3)")):
        assert not re.search(kernels[kernel], line), (kernel, line)
