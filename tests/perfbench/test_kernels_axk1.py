"""The dense-latent-attention cell's roofline arithmetic, each count by
hand at the published widths of A.X-K1 (5 of its 61 layers, 12 of its 192
routed experts), and the readers of its per-layer metrics on sources made
by hand and on the fixture trace."""

import importlib.util
import os

import pytest

import _paths

CONFIG = _paths.bench_json("configs", "a.x-k1.json")
MODEL = {k: v for k, v in CONFIG.items()
         if k not in ("reduced", "reduced_why", "assumed", "derived",
                      "rehearsal", "correct", "trace_patterns")}
PEAKS = _paths.bench_json("peaks.json")["devices"]["TPU v5 lite"]
CELL = "a.x-k1.docqa"


def load(folder, name):
    path = os.path.join(_paths.BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DEC = load("kernels", "mla_decode")
PRE = load("kernels", "mla_prefill")
STEP = load("kernels", "mla_moe_decode_step")
MOE = load("kernels", "moe_expert")
DOCQA = load("generators", "docqa")


def test_decode_attention_by_hand():
    # a decoded token at context 12600: 576 values of 2 B a row and layer
    assert DEC.row_values(MODEL) == 576
    assert DEC.bytes_needed(MODEL, [12600]) == 12600 * 576 * 2 * 5
    # absorbed: 64 heads x (2 x 576 + 2 x 512) a row and layer
    assert DEC.pair_flops(MODEL) == 64 * 2176 == 139264
    assert DEC.flops_needed(MODEL, [12600]) == 139264 * 5 * 12600
    seconds, binds = DEC.least_seconds(MODEL, [12600] * 32, PEAKS)
    assert binds == "bytes"
    assert seconds == pytest.approx(32 * 12600 * 5760 / 819e9)    # 2.8 ms
    # bytes bind by 2 x: 1152 B against 139 kFLOP a row and layer
    assert (1152 / 819e9) / (139264 / 197e12) == pytest.approx(1.99, abs=.01)


def test_prefill_attention_takes_the_cheaper_form_by_hand():
    assert PRE.chunks(5000, 2048) == [(0, 2048), (2048, 2048), (4096, 904)]
    # a 2048-token chunk behind 16384 rows: 35.7 M pairs
    p = 2048 * 16384 + 2048 * 2049 // 2
    assert PRE.pairs(16384, 2048) == p == 35652608
    absorbed = p * 64 * 2176                       # 4.97 TFLOP
    decompressed = p * 64 * 640 + (16384 + 2048) * 2 * 512 * 64 * 256
    assert absorbed == pytest.approx(4.965e12, rel=1e-3)
    assert decompressed == pytest.approx(1.770e12, rel=1e-3)
    assert PRE.chunk_flops(MODEL, 16384, 2048) == decompressed
    # a question of 320 behind 12288 cached rows: decompressed too
    q = 320 * 12288 + 320 * 321 // 2
    assert PRE.chunk_flops(MODEL, 12288, 320) == \
        q * 64 * 640 + 12608 * 16777216
    assert q * 64 * 2176 == pytest.approx(0.5547e12, rel=1e-3)
    # a few tokens behind a long context: absorbed (nothing to expand)
    assert PRE.chunk_flops(MODEL, 12288, 4) == \
        PRE.pairs(12288, 4) * 64 * 2176
    # a request: its new tokens in chunks, each behind what came before
    assert PRE.flops_needed(MODEL, [(12288, 320)]) == 5 * PRE.chunk_flops(
        MODEL, 12288, 320)
    assert PRE.flops_needed(MODEL, [(0, 5000)]) == 5 * (
        PRE.chunk_flops(MODEL, 0, 2048) + PRE.chunk_flops(MODEL, 2048, 2048)
        + PRE.chunk_flops(MODEL, 4096, 904))
    assert PRE.bytes_needed(MODEL, [(12288, 320)]) == 5 * 2 * (
        12608 * 576 + 320 * 64 * 320)
    assert PRE.least_seconds(MODEL, [(12288, 320)], PEAKS)[1] == "flops"


def test_decode_step_weights_by_hand():
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 64 * 512 * 256
            + 64 * 128 * 7168)
    assert STEP.attn_params(MODEL) == attn == 101122048
    fixed = (5 * attn + 3 * 7168 * 18432 + 4 * (7168 * 192 + 3 * 7168 * 2048)
             + 20480 * 7168)
    assert STEP.fixed_weight_params(MODEL) == fixed == 1230438400
    assert 2 * fixed == CONFIG["derived"]["fixed_weight_bytes_per_decode_step"]
    assert MOE.expert_bytes(MODEL) == CONFIG["derived"]["expert_bytes"] == \
        88080384
    # 10 steps of 32 rows at 12600 that touched 8.9 of 12 experts a layer
    got = STEP.bytes_needed(MODEL, 10, 8.9, [12600] * 320, MOE, DEC)
    assert got == pytest.approx(10 * (2 * fixed + 4 * 8.9 * 88080384)
                                + 320 * 12600 * 5760)
    # 5.6 GB of weights and experts and 2.3 GB of latent rows a step
    assert got / 10 == pytest.approx(7.92e9, rel=0.01)


# ---- the readers, on sources made by hand ---------------------------------

class Rec:
    def __init__(self, idx, client, doc, question, times):
        self.idx, self.client, self.times = idx, client, times
        self.prompt = DOCQA.Unsent(doc + question, doc)


def prom(asked=0, hit=0, touched_dec=0, steps_dec=0, touched_mix=0,
         steps_mix=0, held=0):
    return "\n".join([
        f"gllm_prefix_cache_query_tokens_total {asked}",
        f"gllm_prefix_cache_hit_tokens_total {hit}",
        f'gllm_moe_assignments_total{{where="held"}} {held}',
        f'gllm_moe_experts_touched_total{{step="decode"}} {touched_dec}',
        f'gllm_moe_experts_touched_total{{step="mixed"}} {touched_mix}',
        f'gllm_moe_layer_steps_total{{step="decode"}} {steps_dec}',
        f'gllm_moe_layer_steps_total{{step="mixed"}} {steps_mix}'])


def a_run(kernels=None, patterns=True):
    """A traced slice of 40 decode-only steps (15 ms each) and 10 mixed
    steps (50 ms each) in which 31 callers decoded 50 tokens each behind
    12000 + 300 tokens and two requests got their first token: caller 40's
    second (a hit: document 12005, question 300) and caller 41's first
    (document 9000 and question 200 computed whole)."""
    times = {"mla_decode": 0.240, "mla_prefill": 0.300, "moe_expert": 0.400}
    times.update(kernels or {})
    decoded = [Rec(100 + c, c, 12000, 300,
                   [0.0] + [1.0 + 0.01 * j for j in range(50)])
               for c in range(31)]
    return {
        "peaks": PEAKS, "slice": (0.5, 2.0), "model": MODEL,
        "config": {"trace_patterns": {"kernels": dict.fromkeys(times, ".")
                                      if patterns else {}}},
        "load_module": load, "info": {"page_size": 16},
        "records": decoded + [Rec(7, 40, 12005, 310, [0.1]),
                              Rec(9, 40, 12005, 300, [1.5]),
                              Rec(8, 41, 9000, 200, [1.6])],
        "trace": {"devices": {"0": {
            "step_ms": {"decode": [15.0] * 40, "prefill": [50.0] * 10},
            "kernels": {k: {"seconds": v, "calls": 50 if v else 0}
                        for k, v in times.items()}}}},
        "prom0": prom(),
        "prom1": prom(asked=2 * 12305 + 9200, hit=2 * 12000,
                      touched_dec=4 * 40 * 9, steps_dec=160,
                      touched_mix=4 * 10 * 12, steps_mix=40,
                      held=4 * (40 * 16 + 10 * 150)),
    }


def reader(name):
    return load("layer_metrics", name).read


def test_counter_readers():
    run = a_run()
    assert reader("kv.prefix_hit_tokens_pct")(run) == pytest.approx(
        100 * 24000 / 33810)
    assert reader("moe.held_experts_touched_per_step")(run) == \
        pytest.approx(9.0)
    bare = dict(run, prom0="", prom1="")
    assert reader("kv.prefix_hit_tokens_pct")(bare) is None
    assert reader("moe.held_experts_touched_per_step")(bare) is None
    assert reader("kv.prefix_hit_tokens_pct")(
        dict(run, prom0=None, prom1=None)) is None


def test_requests_prefilled_count_the_hit_and_the_first_request():
    from lib import mla_trace
    run = a_run()
    # caller 40's second request hit 750 whole pages of its document;
    # caller 41's only request is its first: computed whole
    assert sorted(mla_trace.requests_prefilled(run)) == [
        (0, 9200), (12000, 305)]
    assert mla_trace.mixed_share(run) == pytest.approx(0.2)


def test_roofline_and_share_readers_by_hand():
    run = a_run()
    ctx = [12300 + j for j in range(1, 51)] * 31
    dec = DEC.least_seconds(MODEL, ctx, PEAKS)[0]
    assert reader("kernel.mla_decode_roofline_pct")(run) == pytest.approx(
        100 * dec * 0.8 / 0.240)
    requests = [(12000, 305), (0, 9200)]
    flops = PRE.flops_needed(MODEL, requests) + 0.2 * DEC.flops_needed(
        MODEL, ctx)
    nbytes = PRE.bytes_needed(MODEL, requests) + 0.2 * DEC.bytes_needed(
        MODEL, ctx)
    assert reader("kernel.mla_prefill_roofline_pct")(run) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 0.300)
    weights = 40 * (2 * 1230438400 + 4 * 9 * 88080384)
    rows = DEC.bytes_needed(MODEL, ctx) * 0.8
    assert reader("runner.mla_moe_decode_roofline_pct")(run) == \
        pytest.approx(100 * (weights + rows) / 819e9 / 0.600)
    assert reader("runner.mla_share_of_decode_pct")(run) == pytest.approx(40)
    assert reader("runner.mla_share_of_prefill_pct")(run) == pytest.approx(60)
    # experts: 4 layers x (40 steps x 9 + 10 steps x 12) touched
    moe = MOE.least_seconds(MODEL, 4 * (40 * 9 + 10 * 12),
                            4 * (40 * 16 + 10 * 150), PEAKS)[0]
    assert reader("kernel.held_expert_roofline_pct")(run) == pytest.approx(
        100 * moe / 0.400)
    for name in ("kernel.mla_decode_roofline_pct",
                 "kernel.mla_prefill_roofline_pct",
                 "runner.mla_moe_decode_roofline_pct",
                 "kernel.held_expert_roofline_pct"):
        assert 0 < reader(name)(run) <= 100, name


@pytest.mark.parametrize("name, kernel", [
    ("kernel.mla_decode_roofline_pct", "mla_decode"),
    ("kernel.mla_prefill_roofline_pct", "mla_prefill"),
    ("runner.mla_share_of_decode_pct", "mla_decode"),
    ("runner.mla_share_of_prefill_pct", "mla_prefill")])
def test_a_reader_without_its_operation_reads_nothing(name, kernel):
    """None, never 0 and never an exception: where the kernel is off the
    path (a decompressed form under another name, a parent without the
    program's part), where the configuration has no pattern for it, and
    where there is no trace."""
    assert reader(name)(a_run(kernels={kernel: 0.0})) is None
    assert reader(name)(a_run(patterns=False)) is None
    assert reader(name)(dict(a_run(), trace=None)) is None
    assert reader(name)(dict(a_run(), peaks=None)) is None
    assert reader(name)(dict(a_run(), slice=None)) is None


def test_readers_on_the_fixture_trace_find_the_two_kernels():
    """The v5e fixture trace (a dense GQA model's: the same two Pallas
    kernels under the same names) reduced with this configuration's
    patterns: both kernels are found, steps are classed by them, and the
    share readers read a share between 0 and 100."""
    import json
    import subprocess
    import sys
    import tempfile
    fixture = os.path.join(_paths.BENCH, "fixtures")
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "reduced.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        work = os.path.join(tmp, "trace")
        os.makedirs(work)
        os.symlink(os.path.join(fixture, "v5e_1chip.xplane.pb"),
                   os.path.join(work, "v5e_1chip.xplane.pb"))
        r = subprocess.run(
            [sys.executable, os.path.join(_paths.BENCH, "trace_reduce.py"),
             "--trace-dir", work, "--patterns",
             json.dumps(CONFIG["trace_patterns"]), "--out", out],
            env=env, text=True, capture_output=True, timeout=300)
        assert r.returncode == 0, r.stderr[-800:]
        with open(out) as f:
            reduced = json.load(f)
    dev = next(iter(reduced["devices"].values()))
    assert dev["kernels"]["mla_decode"]["calls"] > 0
    assert set(dev["step_ms"]) >= {"decode"}
    run = dict(a_run(), trace=reduced, config=CONFIG)
    share = reader("runner.mla_share_of_decode_pct")(run)
    assert share is not None and 0 < share < 100
    if dev["kernels"]["mla_prefill"]["calls"]:
        assert 0 < reader("runner.mla_share_of_prefill_pct")(run) < 100
    else:
        assert reader("runner.mla_share_of_prefill_pct")(run) is None
    # a dense model's trace has no grouped product while this
    # configuration says its steps run one: the accepted reader raises
    # rather than read a pattern gone blind as a number
    from lib import latent_trace
    with pytest.raises(latent_trace.PatternBlind):
        reader("kernel.held_expert_roofline_pct")(run)


def test_every_new_metric_is_this_cells_alone_and_has_its_reader():
    mine = [m for m in _paths.manifest()["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == [
        "kernel.held_expert_roofline_pct", "kernel.mla_decode_roofline_pct",
        "kernel.mla_prefill_roofline_pct", "kv.prefix_hit_tokens_pct",
        "moe.held_experts_touched_per_step",
        "runner.mla_moe_decode_roofline_pct",
        "runner.mla_share_of_decode_pct", "runner.mla_share_of_prefill_pct"]
    for m in mine:
        assert os.path.isfile(os.path.join(
            _paths.BENCH, "layer_metrics", m["name"] + ".py"))
    rooflines = [m for m in mine if m["name"].endswith("_roofline_pct")]
    assert len(rooflines) == 4
    assert all(m["unit"] == "%" and m["source"] == "device_trace"
               and m["better"] == "higher" for m in rooflines)
