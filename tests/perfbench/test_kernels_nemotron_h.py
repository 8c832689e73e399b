"""The state-space cell's roofline arithmetic, each count by hand at the
published widths of Nemotron 3 Nano (16 of its 52 blocks, 64 of its 128
routed experts), and the readers of its per-layer metrics on sources made
by hand: what they read, that a pattern gone blind raises, and that a
missing source reads nothing (never 0)."""

import importlib.util
import os

import pytest

import _paths

CONFIG = _paths.bench_json("configs", "nemotron-3-nano-30b-a3b.json")
MODEL = {k: v for k, v in CONFIG.items()
         if k not in ("reduced", "reduced_why", "assumed", "derived",
                      "rehearsal", "correct", "trace_patterns")}
PEAKS = _paths.bench_json("peaks.json")["devices"]["TPU v5 lite"]
CELL = "nemotron-3-nano-30b-a3b.reason"
NEW = ["kernel.mamba_chunk_roofline_pct", "kernel.mamba_decode_roofline_pct",
       "kernel.relu2_expert_roofline_pct",
       "kernel.ssm_attn_decode_roofline_pct",
       "kernel.ssm_attn_prefill_roofline_pct",
       "moe.relu2_experts_touched_per_step", "runner.mamba_chunk_fill_pct",
       "runner.mamba_share_of_decode_pct",
       "runner.relu2_moe_share_of_decode_pct",
       "runner.ssm_moe_decode_roofline_pct"]


def load(folder, name):
    path = os.path.join(_paths.BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DEC = load("kernels", "mamba_decode")
CHUNK = load("kernels", "mamba_chunk")
EXPERT = load("kernels", "relu2_expert")
STEP = load("kernels", "ssm_moe_decode_step")


def test_mamba_decode_by_hand():
    assert DEC.mamba_layers(MODEL) == 7 and DEC.conv_dim(MODEL) == 6144
    # state 64 x 64 x 128 and window 6144 x 3, float32
    assert DEC.state_bytes_per_row_layer(MODEL) == 4 * (524288 + 18432) \
        == 2170880 == CONFIG["derived"]["state_bytes_per_sequence_layer"]
    assert DEC.bytes_needed(MODEL, 64) == 2 * 2170880 * 7 * 64  # 1.945 GB
    assert DEC.flops_needed(MODEL, 64) == 5 * 64 * 128 * 64 * 7 * 64
    seconds, binds = DEC.least_seconds(MODEL, 64, PEAKS)
    assert binds == "bytes"
    assert seconds == pytest.approx(1945108480 / 819e9)           # 2.4 ms


def test_mamba_chunk_by_hand():
    per = 8 * 128 * 128 + 64 * (128 * 64 + 4 * 64 * 128)
    assert CHUNK.flops_per_token_layer(MODEL) == per == 2752512
    assert CHUNK.flops_needed(MODEL, [320, 500], DEC) == per * 7 * 820
    # conv input and mixer output of 2 B a token, state and window once in
    # and once out a prompt
    assert CHUNK.bytes_needed(MODEL, [320], DEC) == 7 * (
        320 * (6144 + 4096) * 2 + 2 * 2170880)
    seconds, binds = CHUNK.least_seconds(MODEL, [320], PEAKS, DEC)
    assert binds == "bytes"
    assert seconds == pytest.approx(76267520 / 819e9)


def test_relu2_expert_by_hand():
    assert EXPERT.expert_bytes(MODEL) == 2 * 2688 * 1856 * 2 == 19955712 \
        == CONFIG["derived"]["expert_bytes"]
    assert EXPERT.flops_needed(MODEL, 384) == 4 * 2688 * 1856 * 384
    seconds, binds = EXPERT.least_seconds(MODEL, 7 * 60.9, 7 * 384, PEAKS)
    assert binds == "bytes"
    assert seconds == pytest.approx(7 * 60.9 * 19955712 / 819e9)  # 10.4 ms
    # two matrices, not the gated form's three
    assert EXPERT.expert_bytes(MODEL) * 3 == load(
        "kernels", "moe_expert").expert_bytes(MODEL) * 2


def test_decode_step_by_hand():
    h = 2688
    mamba = h * (4096 + 6144 + 64) + 6144 * 5 + 4096 * h
    attn = 2 * h * 4096 + 2 * h * 256
    moe = h * 128 + 2 * h * 3712
    fixed = 7 * mamba + 2 * attn + 7 * moe + 65536 * h
    assert STEP.fixed_weight_params(MODEL, DEC) == fixed == 636217344
    # within 0.02 % of the configuration's count, which has the norm
    # vectors, the scalars and the router's bias as well
    assert 2 * fixed == pytest.approx(
        CONFIG["derived"]["fixed_weight_bytes_per_decode_step"], rel=2e-4)
    assert STEP.kv_bytes(MODEL, [1000, 24]) == 2048 * 1024 \
        == CONFIG["derived"]["kv_bytes_per_token"] * 1024
    # 10 steps of 64 rows at 1200 that touched 60.9 of 64 experts a layer
    got = STEP.bytes_needed(MODEL, 10, 60.9, [1200] * 640, EXPERT, DEC)
    assert got == pytest.approx(
        10 * (2 * fixed + 7 * 60.9 * 19955712) + 640 * 7 * 2 * 2170880
        + 640 * 1200 * 2048)
    # ISSUE 41's reckoning: 11.7 GB a step, 14.3 ms at 819 GB/s
    assert got / 10 == pytest.approx(11.88e9, rel=0.01)


# ---- the readers, on sources made by hand ---------------------------------

class Rec:
    def __init__(self, prompt_len, times):
        self.prompt, self.times = [0] * prompt_len, times


def prom(touched_dec=0, steps_dec=0, touched_mix=0, steps_mix=0, held=0,
         tokens=0, slots=0):
    return "\n".join([
        f'gllm_moe_assignments_total{{where="held"}} {held}',
        f'gllm_moe_experts_touched_total{{step="decode"}} {touched_dec}',
        f'gllm_moe_experts_touched_total{{step="mixed"}} {touched_mix}',
        f'gllm_moe_layer_steps_total{{step="decode"}} {steps_dec}',
        f'gllm_moe_layer_steps_total{{step="mixed"}} {steps_mix}',
        f"gllm_mamba_chunk_tokens_total {tokens}",
        f"gllm_mamba_chunk_slots_total {slots}"])


TIMES = {"mamba_recurrent": 0.150, "mamba_conv": 0.030, "mamba_norm": 0.020,
         "mamba_chunk_scan": 0.010, "mamba_chunk": 0.060,
         "moe_expert": 0.700, "moe_expert_decode": 0.560,
         "attn_decode": 0.020, "attn_prefill": 0.030}


def a_run(kernels=None, patterns=True):
    """A traced slice of 40 decode-only steps (20 ms each) and 10 mixed
    steps (50 ms each) in which 63 callers decoded 50 tokens each behind
    300 tokens and two prompts (320 and 500 tokens) got their first
    token."""
    times = dict(TIMES, **(kernels or {}))
    decoded = [Rec(300, [0.0] + [1.0 + 0.01 * j for j in range(50)])
               for _ in range(63)]
    calls = {"mamba_chunk": 70 * 12, "mamba_chunk_scan": 70}
    return {
        "peaks": PEAKS, "slice": (0.5, 2.0), "model": MODEL,
        "config": {"trace_patterns": {"kernels": dict.fromkeys(times, ".")
                                      if patterns else {}}},
        "load_module": load, "info": {"page_size": 16},
        "records": decoded + [Rec(320, [1.5]), Rec(500, [1.6])],
        "trace": {"devices": {"0": {
            "step_ms": {"decode": [20.0] * 40, "prefill": [50.0] * 10},
            "kernels": {k: {"seconds": v,
                            "calls": calls.get(k, 350) if v else 0}
                        for k, v in times.items()}}}},
        "prom0": prom(),
        "prom1": prom(touched_dec=7 * 40 * 61, steps_dec=280,
                      touched_mix=7 * 10 * 64, steps_mix=70,
                      held=7 * (40 * 190 + 10 * 1200),
                      tokens=8200, slots=20480),
    }


def reader(name):
    return load("layer_metrics", name).read


def test_counter_readers():
    run = a_run()
    assert reader("moe.relu2_experts_touched_per_step")(run) == \
        pytest.approx(61.0)
    assert reader("runner.mamba_chunk_fill_pct")(run) == pytest.approx(
        100 * 8200 / 20480)
    for bare in (dict(run, prom0="", prom1=""),
                 dict(run, prom0=None, prom1=None)):
        assert reader("moe.relu2_experts_touched_per_step")(bare) is None
        assert reader("runner.mamba_chunk_fill_pct")(bare) is None


def test_roofline_and_share_readers_by_hand():
    run = a_run()
    rows = 63 * 50
    assert reader("kernel.mamba_decode_roofline_pct")(run) == pytest.approx(
        100 * DEC.least_seconds(MODEL, rows, PEAKS)[0] / 0.180)
    assert reader("kernel.mamba_chunk_roofline_pct")(run) == pytest.approx(
        100 * CHUNK.least_seconds(MODEL, [320, 500], PEAKS, DEC)[0] / 0.060)
    touched = 7 * (40 * 61 + 10 * 64)
    held = 7 * 50 * (7 * (40 * 190 + 10 * 1200) / 350)
    assert reader("kernel.relu2_expert_roofline_pct")(run) == pytest.approx(
        100 * EXPERT.least_seconds(MODEL, touched, held, PEAKS)[0] / 0.700)
    # the Mamba-2 operations over all steps, scaled to the decode-only ones
    assert reader("runner.mamba_share_of_decode_pct")(run) == pytest.approx(
        100 * 0.200 * 0.8 / 0.800)
    assert reader("runner.relu2_moe_share_of_decode_pct")(run) == \
        pytest.approx(70.0)
    ctx = [300 + j for j in range(1, 51)] * 63
    weights = 40 * (2 * 636217344 + 7 * 61 * 19955712)
    moving = (DEC.bytes_needed(MODEL, rows) + 2048 * sum(ctx)) * 0.8
    assert reader("runner.ssm_moe_decode_roofline_pct")(run) == \
        pytest.approx(100 * (weights + moving) / 819e9 / 0.800)
    # the attention kernels over the 2 attention blocks: 2 KV heads of 128
    # in bf16 = 2048 B a token of context, 4 x 32 x 128 x 2 FLOPs
    attn, pre = load("kernels", "attn_decode"), load("kernels",
                                                     "attn_prefill")
    two = STEP.attn_model(MODEL)
    assert two["num_hidden_layers"] == 2
    assert attn.bytes_needed(two, ctx) == 2048 * sum(ctx)
    assert reader("kernel.ssm_attn_decode_roofline_pct")(run) == \
        pytest.approx(100 * 2048 * sum(ctx) / 819e9 * 0.8 / 0.020)
    flops = (pre.flops_needed(two, [320, 500])
             + 0.2 * attn.flops_needed(two, ctx))
    nbytes = (pre.bytes_needed(two, [320, 500]) + 0.2 * 2048 * sum(ctx))
    assert reader("kernel.ssm_attn_prefill_roofline_pct")(run) == \
        pytest.approx(100 * max(flops / PEAKS["flops_per_s"],
                                nbytes / 819e9) / 0.030)
    for name in NEW:
        if name.endswith("_roofline_pct"):
            assert 0 < reader(name)(run) <= 100, name


@pytest.mark.parametrize("name, kernel", [
    ("kernel.mamba_decode_roofline_pct", "mamba_recurrent"),
    ("kernel.mamba_chunk_roofline_pct", "mamba_chunk_scan"),
    ("runner.mamba_share_of_decode_pct", "mamba_recurrent")])
def test_a_reader_without_its_kernel_reads_nothing(name, kernel):
    """None, never 0 and never an exception: where the named kernel is off
    the path (a parent without the program's part), where the
    configuration has no pattern for it, and where there is no trace."""
    assert reader(name)(a_run(kernels={kernel: 0.0})) is None
    assert reader(name)(a_run(patterns=False)) is None
    assert reader(name)(dict(a_run(), trace=None)) is None
    assert reader(name)(dict(a_run(), peaks=None)) is None
    assert reader(name)(dict(a_run(), slice=None)) is None


@pytest.mark.parametrize("name, kernel", [
    ("kernel.ssm_attn_decode_roofline_pct", "attn_decode"),
    ("kernel.ssm_attn_prefill_roofline_pct", "attn_prefill")])
def test_an_attention_reader_without_its_kernel_reads_nothing(name, kernel):
    """As the hybrid cell's readers, whose counts these are over this
    pattern's attention blocks: None where the kernel did not run."""
    assert reader(name)(a_run(kernels={kernel: 0.0})) is None
    assert reader(name)(dict(a_run(), records=[])) is None


@pytest.mark.parametrize("name", [
    "runner.ssm_moe_decode_roofline_pct",
    "runner.relu2_moe_share_of_decode_pct",
    "kernel.relu2_expert_roofline_pct",
    "kernel.ssm_attn_decode_roofline_pct",
    "kernel.ssm_attn_prefill_roofline_pct"])
def test_the_expert_readers_read_nothing_without_their_sources(name):
    assert reader(name)(dict(a_run(), trace=None)) is None
    assert reader(name)(dict(a_run(), peaks=None)) is None
    assert reader(name)(dict(a_run(), slice=None)) is None
    other = dict(a_run(), model={"hidden_size": 8})       # another family
    assert reader(name)(other) is None


@pytest.mark.parametrize("name, blind", [
    ("kernel.mamba_decode_roofline_pct", "mamba_conv"),
    ("runner.mamba_share_of_decode_pct", "mamba_conv"),
    ("runner.mamba_share_of_decode_pct", "mamba_norm"),
    ("kernel.mamba_chunk_roofline_pct", "mamba_chunk")])
def test_a_shape_pattern_gone_blind_raises(name, blind):
    """The named kernels are the witnesses: where they ran and a shape
    pattern matched nothing beside them, a refusion or a change of widths
    has moved operations out of its sight: no number."""
    from lib import mamba_trace
    with pytest.raises(mamba_trace.PatternBlind):
        reader(name)(a_run(kernels={blind: 0.0}))


def test_the_grouped_product_gone_blind_raises():
    from lib import latent_trace
    for name, kernel in (
            ("kernel.relu2_expert_roofline_pct", "moe_expert"),
            ("runner.relu2_moe_share_of_decode_pct", "moe_expert_decode")):
        with pytest.raises(latent_trace.PatternBlind):
            reader(name)(a_run(kernels={kernel: 0.0}))


def test_every_new_metric_is_this_cells_alone_and_has_its_reader():
    manifest = _paths.manifest()
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == NEW
    assert [m["name"] for m in manifest["per_layer"][-10:]] == [
        m["name"] for m in mine]                 # appended at the end
    for m in mine:
        assert os.path.isfile(os.path.join(
            _paths.BENCH, "layer_metrics", m["name"] + ".py"))
    rooflines = [m for m in mine if m["name"].endswith("_roofline_pct")]
    assert len(rooflines) == 6
    assert all(m["unit"] == "%" and m["source"] == "device_trace"
               and m["better"] == "higher" for m in rooflines)
    # the cell is on the list of every metric that lists all the cells,
    # and on the slot gauge's
    cells = [w["name"] for w in manifest["workloads"]]
    everywhere = [m for m in manifest["per_layer"]
                  if len(m.get("workloads", [])) >= 4]
    assert len(everywhere) == 18
    assert all(m["workloads"] == cells for m in everywhere)
    gauge = [m for m in manifest["per_layer"]
             if m["name"] == "kv.ssm_slots_peak_pct"][0]
    assert gauge["workloads"] == ["olmo-hybrid-7b.reason", CELL]


def test_the_configuration_names_every_pattern_the_readers_ask_for():
    kernels = CONFIG["trace_patterns"]["kernels"]
    assert set(TIMES) <= set(kernels)
    assert kernels["mamba_recurrent"] == "^%mamba2_recurrent_step"
    assert kernels["mamba_chunk_scan"] == "^%mamba2_chunk_scan"
    classes = CONFIG["trace_patterns"]["step_classes"]
    assert classes["decode"]["lacks"] == ["attn_prefill"]
