"""The latent-attention cell's roofline arithmetic, each count by hand at
the published widths of dots3-note-prev (5 of its 46 layers, 32 of its 256
routed experts), and the readers of its per-layer metrics on sources made
by hand."""

import importlib.util
import os

import pytest

import _paths
from lib import latent_trace

CONFIG = _paths.bench_json("configs", "dots3-note-prev.json")
MODEL = {k: v for k, v in CONFIG.items()
         if k not in ("reduced", "reduced_why", "assumed", "derived",
                      "rehearsal", "correct", "trace_patterns")}
PEAKS = _paths.bench_json("peaks.json")["devices"]["TPU v5 lite"]
CELL = "dots3-note-prev.longdoc"


def load(folder, name):
    path = os.path.join(_paths.BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


C = load("kernels", "latent_common")
INDEX = load("kernels", "dsa_index")
SPARSE = load("kernels", "sparse_mla")
SWA = load("kernels", "swa_mla")
MOE = load("kernels", "moe_expert")
STEP = load("kernels", "latent_moe_decode_step")


def test_layer_counts_and_rows():
    assert (C.full_layers(MODEL), C.swa_layers(MODEL),
            C.moe_layers(MODEL)) == (2, 3, 4)
    assert (C.latent_row(MODEL), C.swa_row(MODEL)) == (576, 1088)
    assert C.chunks(5000, 2048) == [(0, 2048), (2048, 2048), (4096, 904)]


def test_indexer_by_hand():
    # a decoded token at context 7000: 7000 pairs, 2 x 64 x 128 FLOPs each,
    # 2 full layers; its keys: 7000 x 128 x 2 B a layer
    assert INDEX.flops_needed(MODEL, [7000], [], C) == 2 * 64 * 128 * 2 * 7000
    assert INDEX.bytes_needed(MODEL, [7000], [], C) == 7000 * 128 * 2 * 2
    assert INDEX.least_seconds(MODEL, [7000] * 64, [], PEAKS, C)[1] == "bytes"
    # a prompt of 5000: 5000 x 5001 / 2 pairs; keys written once (5000)
    # and read by each chunk up to its end (2048 + 4096 + 5000)
    assert INDEX.pairs([], [5000]) == 12502500
    assert INDEX.bytes_needed(MODEL, [], [5000], C) == \
        (5000 + 2048 + 4096 + 5000) * 128 * 2 * 2
    assert INDEX.least_seconds(MODEL, [], [5000], PEAKS, C)[1] == "flops"


def test_sparse_attention_by_hand():
    assert SPARSE.chosen(MODEL, [100, 2048, 9000]) == 100 + 2048 + 2048
    assert SPARSE.prompt_contexts([3]) == [1, 2, 3]
    rows = 2048
    assert SPARSE.bytes_needed(MODEL, [9000], C) == 576 * 2 * 2 * rows
    assert SPARSE.flops_needed(MODEL, [9000], C) == \
        2 * 128 * (576 + 512) * 2 * rows
    # at the ridge: 278528 FLOPs for 1152 B against 197e12 / 819e9
    ratio = (SPARSE.flops_needed(MODEL, [9000], C) / PEAKS["flops_per_s"]) / (
        SPARSE.bytes_needed(MODEL, [9000], C) / PEAKS["bytes_per_s"])
    assert ratio == pytest.approx(1.005, abs=0.01)


def test_windowed_attention_by_hand():
    assert SWA.window_rows(MODEL, [100, 513, 9000]) == 100 + 513 + 513
    # a decoded token: 513 rows of 1088 values, 3 layers
    assert SWA.bytes_needed(MODEL, [9000], [], C) == 513 * 1088 * 2 * 3
    assert SWA.flops_needed(MODEL, [9000], [], C) == \
        2 * 64 * (1088 + 1024) * 3 * 513
    assert SWA.least_seconds(MODEL, [9000], [], PEAKS, C)[1] == "bytes"
    # a prompt of 1000: 487 tokens with a whole window, 513 with i rows
    pairs = 487 * 513 + 513 * 514 // 2
    assert SWA.flops_needed(MODEL, [], [1000], C) == \
        2 * 64 * (1088 + 1024) * 3 * pairs
    assert SWA.bytes_needed(MODEL, [], [1000], C) == 2 * 1000 * 1088 * 2 * 3
    assert SWA.least_seconds(MODEL, [], [1000], PEAKS, C)[1] == "flops"


def test_experts_by_hand():
    assert MOE.expert_bytes(MODEL) == 3 * 5120 * 1536 * 2 == 47185920
    # 27.7 experts touched in each of 4 layers of one decode step, 64
    # assignments a layer
    seconds, binds = MOE.least_seconds(MODEL, 4 * 27.7, 4 * 64, PEAKS)
    assert binds == "bytes"
    assert seconds == pytest.approx(4 * 27.7 * 47185920 / 819e9)   # 6.4 ms
    assert MOE.flops_needed(MODEL, 1) == 6 * 5120 * 1536


def test_decode_step_weights_by_hand():
    # ISSUE 34's arithmetic, in parameters
    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 128 * 512 * 256
            + 128 * 128 * 5120 + 5120 * 128
            + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    swa = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 64 * 1024 * 320
           + 64 * 128 * 5120 + 5120 * 64)
    assert STEP.attn_params(MODEL, "full_attention") == full == 144048128
    assert STEP.attn_params(MODEL, "sliding_attention") == swa == 90832896
    fixed = (2 * full + 3 * swa + 3 * 5120 * 13824
             + 4 * (5120 * 256 + 3 * 5120 * 1536) + 19008 * 5120)
    assert STEP.fixed_weight_params(MODEL, C) == fixed == 969867264
    ctx = [7000] * 64
    caches = STEP.cache_bytes(MODEL, ctx, INDEX, SPARSE, SWA, C)
    assert caches == 64 * (7000 * 128 * 2 * 2 + 2048 * 576 * 2 * 2
                           + 513 * 1088 * 2 * 3)


# ---- the readers, on sources made by hand ---------------------------------

class Rec:
    def __init__(self, prompt, times):
        self.prompt, self.times = [0] * prompt, times


def prom(held, absent, touched_dec, steps_dec, touched_mix, steps_mix,
         seen=0, chosen=0, rings=0):
    return "\n".join([
        f'gllm_moe_assignments_total{{where="held"}} {held}',
        f'gllm_moe_assignments_total{{where="absent"}} {absent}',
        f'gllm_moe_experts_touched_total{{step="decode"}} {touched_dec}',
        f'gllm_moe_experts_touched_total{{step="mixed"}} {touched_mix}',
        f'gllm_moe_layer_steps_total{{step="decode"}} {steps_dec}',
        f'gllm_moe_layer_steps_total{{step="mixed"}} {steps_mix}',
        f'gllm_dsa_positions_total{{what="seen"}} {seen}',
        f'gllm_dsa_positions_total{{what="chosen"}} {chosen}',
        f'gllm_swa_ring_slots_in_use {rings}'])


def a_run(kernels=None, patterns=True):
    """A traced slice of 10 decode-only steps of 64 rows at context 7000
    (12 ms each) and 2 mixed steps (150 ms each) that prefilled one prompt
    of 4096 tokens."""
    names = ("dsa_index", "sparse_mla", "swa_mla", "moe_expert",
             "moe_expert_decode", "dsa_chunk")
    times = {"dsa_index": 0.040, "sparse_mla": 0.120, "swa_mla": 0.030,
             "moe_expert": 0.100, "moe_expert_decode": 0.060,
             "dsa_chunk": 0.150}
    times.update(kernels or {})
    decoded = [Rec(6999, [0.0] + [1.0 + 0.01 * j for j in range(10)])
               for _ in range(64)]
    return {
        "peaks": PEAKS, "slice": (0.5, 2.0), "model": MODEL,
        "config": {"trace_patterns": {"kernels": dict.fromkeys(names, ".")
                                      if patterns else {}}},
        "load_module": load, "info": {"swa_rings": {"slots": 64}},
        "records": decoded + [Rec(4096, [1.5])],
        "trace": {"devices": {"0": {
            "step_ms": {"decode": [12.0] * 10, "prefill": [150.0] * 2},
            "kernels": {k: {"seconds": v, "calls": 10 if v else 0}
                        for k, v in times.items()}}}},
        "prom0": prom(0, 0, 0, 0, 0, 0),
        "prom1": prom(held=4 * (10 * 64 + 2 * 2112),
                      absent=4 * 7 * (10 * 64 + 2 * 2112),
                      touched_dec=4 * 10 * 27, steps_dec=40,
                      touched_mix=4 * 2 * 32, steps_mix=8,
                      seen=1000, chosen=400, rings=64),
    }


def reader(name):
    return load("layer_metrics", name).read


def test_counter_readers():
    run = a_run()
    assert reader("moe.held_assignments_pct")(run) == pytest.approx(12.5)
    assert reader("moe.experts_touched_per_step")(run) == pytest.approx(27.0)
    assert reader("dsa.chosen_of_visible_pct")(run) == pytest.approx(40.0)
    assert reader("kv.window_store_peak_pct")(run) == pytest.approx(100.0)
    bare = dict(run, prom0="", prom1="", info={})
    for name in ("moe.held_assignments_pct", "moe.experts_touched_per_step",
                 "dsa.chosen_of_visible_pct", "kv.window_store_peak_pct"):
        assert reader(name)(bare) is None


def test_roofline_readers_by_hand():
    run = a_run()
    ctx = [6999 + j for j in range(1, 11)] * 64
    idx = INDEX.least_seconds(MODEL, ctx, [4096], PEAKS, C)[0]
    assert reader("kernel.dsa_index_roofline_pct")(run) == pytest.approx(
        100 * idx / 0.040)
    sp = SPARSE.least_seconds(
        MODEL, ctx + list(range(1, 4097)), PEAKS, C)[0]
    assert reader("kernel.sparse_mla_roofline_pct")(run) == pytest.approx(
        100 * sp / 0.120)
    sw = SWA.least_seconds(MODEL, ctx, [4096], PEAKS, C)[0]
    assert reader("kernel.swa_mla_roofline_pct")(run) == pytest.approx(
        100 * sw / 0.030)
    # experts: 4 layers x (10 steps x 27 + 2 steps x 32) touched
    touched = 4 * (10 * 27 + 2 * 32)
    held = 4 * (10 * 64 + 2 * 2112) / 48 * 4 * 12
    moe = MOE.least_seconds(MODEL, touched, held, PEAKS)[0]
    assert reader("kernel.moe_expert_roofline_pct")(run) == pytest.approx(
        100 * moe / 0.100)
    weights = 10 * (969867264 * 2 + 4 * 27 * 47185920)
    caches = STEP.cache_bytes(MODEL, ctx, INDEX, SPARSE, SWA, C) * 10 / 12
    assert reader("runner.latent_moe_decode_roofline_pct")(run) == \
        pytest.approx(100 * (weights + caches) / 819e9 / 0.120)
    assert reader("runner.moe_share_of_decode_pct")(run) == pytest.approx(50)
    assert reader("runner.dsa_share_of_prefill_pct")(run) == pytest.approx(50)
    for name in ("kernel.dsa_index_roofline_pct",
                 "kernel.sparse_mla_roofline_pct",
                 "kernel.swa_mla_roofline_pct",
                 "kernel.moe_expert_roofline_pct",
                 "runner.latent_moe_decode_roofline_pct"):
        assert 0 < reader(name)(run) <= 100, name


@pytest.mark.parametrize("name, kernel", [
    ("kernel.dsa_index_roofline_pct", "dsa_index"),
    ("kernel.sparse_mla_roofline_pct", "sparse_mla"),
    ("kernel.swa_mla_roofline_pct", "swa_mla"),
    ("kernel.moe_expert_roofline_pct", "moe_expert"),
    ("runner.moe_share_of_decode_pct", "moe_expert_decode"),
    ("runner.dsa_share_of_prefill_pct", "dsa_chunk")])
def test_a_blind_pattern_raises_and_a_missing_one_reads_nothing(name, kernel):
    with pytest.raises(latent_trace.PatternBlind, match=kernel):
        reader(name)(a_run(kernels={kernel: 0.0}))
    assert reader(name)(a_run(patterns=False)) is None
    assert reader(name)(dict(a_run(), trace=None)) is None
    assert reader(name)(dict(a_run(), peaks=None)) is None


def test_every_new_metric_is_this_cells_alone_and_has_its_reader():
    mine = [m for m in _paths.manifest()["per_layer"]
            if m.get("workloads") == [CELL]]
    assert len(mine) == 11
    for m in mine:
        assert os.path.isfile(os.path.join(
            _paths.BENCH, "layer_metrics", m["name"] + ".py"))
    rooflines = [m for m in mine if m["name"].endswith("_roofline_pct")]
    assert len(rooflines) == 5
    assert all(m["unit"] == "%" and m["source"] == "device_trace"
               for m in rooflines)
