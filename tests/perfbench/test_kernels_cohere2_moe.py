"""The windowed-GQA cell's roofline arithmetic, each count by hand at the
published widths of command-a-plus-05-2026 (one period of its 32 layers,
16 of its 128 routed experts), and the readers of its per-layer metrics on
sources made by hand: what they read, and that a missing source or a
program without the part reads nothing (never 0)."""

import importlib.util
import os

import pytest

import _paths

CONFIG = _paths.bench_json("configs", "command-a-plus-05-2026.json")
MODEL = {k: v for k, v in CONFIG.items()
         if k not in ("reduced", "reduced_why", "assumed", "derived",
                      "rehearsal", "correct", "trace_patterns")}
PEAKS = _paths.bench_json("peaks.json")["devices"]["TPU v5 lite"]
CELL = "command-a-plus-05-2026.docqa"
NEW = ["kernel.nope_attn_decode_roofline_pct",
       "kernel.nope_attn_prefill_roofline_pct",
       "kernel.swa_attn_decode_roofline_pct",
       "kernel.swa_attn_prefill_roofline_pct",
       "kernel.swa_moe_expert_roofline_pct",
       "kv.window_rows_read_of_context_pct",
       "moe.swa_moe_experts_touched_per_step",
       "moe.swa_moe_held_assignments_pct",
       "runner.swa_attn_share_of_decode_pct",
       "runner.swa_moe_decode_roofline_pct"]
BW, FL = PEAKS["bytes_per_s"], PEAKS["flops_per_s"]


def load(folder, name):
    path = os.path.join(_paths.BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


STEP = load("kernels", "swa_moe_decode_step")
SWA = load("kernels", "swa_attn")
EXPERT = load("kernels", "moe_expert")
DEC = load("kernels", "attn_decode")
PRE = load("kernels", "attn_prefill")


def test_decode_step_by_hand():
    attn = 2 * 4096 * 16384 + 2 * 4096 * 1024
    moe = 4096 * 128 + 4 * 3 * 4096 * 4096
    fixed = 4 * (attn + moe) + 32768 * 4096
    assert STEP.fixed_weight_params(MODEL) == fixed == 1512046592
    assert 2 * fixed == CONFIG["derived"][
        "fixed_weight_bytes_per_decode_step"] == 3024093184
    assert STEP.layers(MODEL, "sliding_attention") == 3
    assert STEP.layers(MODEL, "full_attention") == 1
    # a row at 12 k reads 4096 rows in each windowed layer and 12 k in the
    # full one; a row at 1000 reads its whole context in all four
    assert STEP.rows_read(MODEL, [12000, 1000]) == (
        3 * (4096 + 1000), 12000 + 1000)
    assert DEC.kv_bytes_per_token(STEP.one_layer(MODEL)) == 4096
    assert STEP.kv_bytes(MODEL, [12000], DEC) == 4096 * (3 * 4096 + 12000)
    assert EXPERT.expert_bytes(STEP.expert_model(MODEL)) == 100663296 \
        == CONFIG["derived"]["expert_bytes"]
    # ISSUE 44's reckoning: 16 rows at a mean 12.7 k that touch 10.1 of 16
    # experts a layer: 8.7 GB a step, 10.7 ms at 819 GB/s; 10.4 GB without
    # the window
    got = STEP.bytes_needed(MODEL, 1, 10.1, [12700] * 16, EXPERT, DEC)
    assert got == pytest.approx(
        2 * fixed + 4 * 10.1 * 100663296 + 16 * 4096 * (3 * 4096 + 12700))
    assert got == pytest.approx(8.73e9, rel=0.01)
    assert got / BW == pytest.approx(10.7e-3, rel=0.01)
    unwindowed = got + 16 * 4096 * 3 * (12700 - 4096)
    assert unwindowed == pytest.approx(10.4e9, rel=0.01)


def test_windowed_attention_by_hand():
    assert SWA.decode_rows(MODEL, [100, 4096, 9000]) == 100 + 4096 + 4096
    # a question of 3 tokens behind 5000 cached rows: each query sees 4096
    assert SWA.chunk_pairs(MODEL, 5000, 3) == 3 * 4096
    # ... behind 10: the queries at 10, 11, 12 see 11, 12, 13 keys
    assert SWA.chunk_pairs(MODEL, 10, 3) == 11 + 12 + 13
    assert SWA.chunk_rows(MODEL, 5000, 3) == 4095 + 3
    assert SWA.chunk_rows(MODEL, 10, 3) == 13
    # 3 layers decoding 16 rows past the window: bytes bind
    seconds, binds = SWA.least_seconds(MODEL, 3, [12000] * 16, [], PEAKS)
    assert binds == "bytes"
    assert seconds == pytest.approx(3 * 16 * 4096 * 4096 / BW)   # 0.98 ms
    # a 320-token question behind 12 k of cached document: 2 x 128 heads
    # x 128 x 2 B of q and output a token beside 4415 rows of K and V
    seconds, binds = SWA.least_seconds(MODEL, 3, [], [(12288, 320)], PEAKS)
    nbytes = 3 * (4096 * (4095 + 320) + 2 * 128 * 128 * 2 * 320)
    flops = 3 * 4 * 128 * 128 * 320 * 4096
    assert seconds == pytest.approx(max(nbytes / BW, flops / FL))
    assert binds == "flops"


# ---- the readers, on sources made by hand ---------------------------------

class Prompt(list):
    shared = 0


class Rec:
    def __init__(self, idx, client, shared, question, times):
        self.idx, self.client, self.times = idx, client, times
        self.prompt = Prompt([0] * (shared + question))
        self.prompt.shared = shared


def prom(sliding=(0, 0), full=(0, 0), touched=0, steps=0, t_mix=0, s_mix=0,
         held=0, absent=0):
    return "\n".join([
        f'gllm_attn_rows_read_total{{kind="sliding",step="decode"}} '
        f'{sliding[0]}',
        f'gllm_attn_rows_read_total{{kind="sliding",step="mixed"}} '
        f'{sliding[1]}',
        f'gllm_attn_rows_read_total{{kind="full",step="decode"}} {full[0]}',
        f'gllm_attn_rows_read_total{{kind="full",step="mixed"}} {full[1]}',
        f'gllm_moe_assignments_total{{where="held"}} {held}',
        f'gllm_moe_assignments_total{{where="absent"}} {absent}',
        f'gllm_moe_experts_touched_total{{step="decode"}} {touched}',
        f'gllm_moe_experts_touched_total{{step="mixed"}} {t_mix}',
        f'gllm_moe_layer_steps_total{{step="decode"}} {steps}',
        f'gllm_moe_layer_steps_total{{step="mixed"}} {s_mix}'])


TIMES = {"attn_decode": 0.060, "attn_prefill": 0.012, "swa_decode": 0.080,
         "swa_prefill": 0.020, "moe_expert": 0.400}


def a_run(kernels=None, patterns=True):
    """A traced slice of 90 decode-only steps (15 ms each) and 10 mixed
    steps (60 ms each) in which 15 callers decoded 100 tokens each behind
    a document of 12000 and a question of 300, and one request (its
    caller's second: document cached in whole pages) got its first
    token."""
    times = dict(TIMES, **(kernels or {}))
    decoded = [Rec(2 * c + 1, c, 12000, 300,
                   [0.0] + [1.0 + 0.01 * j for j in range(100)])
               for c in range(15)]
    firsts = [Rec(2 * c, c, 12000, 200, [0.1]) for c in range(16)]
    hit = Rec(31, 15, 12000, 320, [1.5])
    return {
        "peaks": PEAKS, "slice": (0.5, 2.5), "model": MODEL,
        "config": {"trace_patterns": {"kernels": dict.fromkeys(times, ".")
                                      if patterns else {}}},
        "load_module": load, "info": {"page_size": 16},
        "records": decoded + firsts + [hit],
        "trace": {"devices": {"0": {
            "step_ms": {"decode": [15.0] * 90, "prefill": [60.0] * 10},
            "kernels": {k: {"seconds": v, "calls": 100 if v else 0}
                        for k, v in times.items()}}}},
        "prom0": prom(),
        "prom1": prom(sliding=(3 * 4096 * 1440, 3 * 4096 * 160),
                      full=(12500 * 1440, 12500 * 160),
                      touched=4 * 90 * 10, steps=360, t_mix=4 * 10 * 16,
                      s_mix=40, held=4 * (90 * 16 + 10 * 336),
                      absent=7 * 4 * (90 * 16 + 10 * 336)),
    }


def reader(name):
    return load("layer_metrics", name).read


CTX = [12300 + j for j in range(1, 101)] * 15


def test_counter_readers():
    run = a_run()
    assert reader("moe.swa_moe_experts_touched_per_step")(run) == \
        pytest.approx(10.0)
    # 3 windowed layers read 4096 rows a sequence where the full layer
    # reads 12500: 32.8 %
    assert reader("kv.window_rows_read_of_context_pct")(run) == \
        pytest.approx(100 * 4096 / 12500)
    # one of a token's eight assignments falls on the 16 held of 128
    assert reader("moe.swa_moe_held_assignments_pct")(run) == \
        pytest.approx(12.5)
    for bare in (dict(run, prom0="", prom1=""),
                 dict(run, prom0=None, prom1=None)):
        assert reader("moe.swa_moe_experts_touched_per_step")(bare) is None
        assert reader("kv.window_rows_read_of_context_pct")(bare) is None
        assert reader("moe.swa_moe_held_assignments_pct")(bare) is None


def test_roofline_and_share_readers_by_hand():
    run = a_run()
    share = 0.9                      # decode-only steps among all steps
    assert reader("runner.swa_attn_share_of_decode_pct")(run) == \
        pytest.approx(100 * (0.060 + 0.080) / 1.350)
    assert reader("kernel.swa_attn_decode_roofline_pct")(run) == \
        pytest.approx(100 * share * 3 * 4096 * 4096 * 1500 / BW / 0.080)
    assert reader("kernel.nope_attn_decode_roofline_pct")(run) == \
        pytest.approx(100 * share * 4096 * sum(CTX) / BW / 0.060)
    weights = 90 * (3024093184 + 4 * 10 * 100663296)
    rows = 4096 * (3 * 4096 * 1500 + sum(CTX)) * share
    assert reader("runner.swa_moe_decode_roofline_pct")(run) == \
        pytest.approx(100 * (weights + rows) / BW / 1.350)
    # the one request prefilled in the slice: 320 new tokens behind 12000
    # cached (750 whole pages); the riding rows at the mixed steps' share
    chunk, _ = SWA.least_seconds(MODEL, 3, [], [(12000, 320)], PEAKS)
    riding, _ = SWA.least_seconds(MODEL, 3, CTX, [], PEAKS)
    assert reader("kernel.swa_attn_prefill_roofline_pct")(run) == \
        pytest.approx(100 * (chunk + 0.1 * riding) / 0.020)
    pairs = 12320 * 12321 // 2 - 12000 * 12001 // 2
    flops = 4 * 128 * 128 * (pairs + 0.1 * sum(CTX))
    nbytes = ((2 * 128 + 2 * 8) * 128 * 2 * 320 + 4096 * 12000
              + 0.1 * 4096 * sum(CTX))
    assert reader("kernel.nope_attn_prefill_roofline_pct")(run) == \
        pytest.approx(100 * max(flops / FL, nbytes / BW) / 0.012)
    touched = 4 * (90 * 10 + 10 * 16)
    held = 4 * 100 * (4 * (90 * 16 + 10 * 336) / 400)
    least, _ = EXPERT.least_seconds(STEP.expert_model(MODEL), touched, held,
                                    PEAKS)
    assert reader("kernel.swa_moe_expert_roofline_pct")(run) == \
        pytest.approx(100 * least / 0.400)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_without_its_source(name):
    """No trace, no pattern (another family's cell, or this PR's files
    over a parent without the program's part), a kernel that did not run,
    no counter: None, never 0 and never an exception."""
    run = a_run()
    counters = name.startswith(("moe.", "kv."))
    bare = [dict(run, prom0=None, prom1=None)] if counters else [
        dict(run, trace=None), dict(run, peaks=None, slice=None)]
    if not name.startswith("moe."):      # counters ask for no pattern
        bare.append(a_run(patterns=False))
    idle = a_run(kernels=dict.fromkeys(TIMES, 0.0))
    if name == "kernel.swa_moe_expert_roofline_pct":
        # the accepted reader's own guard: step programs ran and the
        # pattern matched nothing is a pattern gone blind, not a zero
        from lib import latent_trace
        with pytest.raises(latent_trace.PatternBlind):
            reader(name)(idle)
    elif name.startswith("kernel.") or "share" in name:
        bare.append(idle)
    for r in bare:
        assert reader(name)(r) is None


def test_every_new_metric_is_this_cells_alone_and_has_its_reader():
    """By the entries' ``workloads``, not by their place from the end: the
    next cell's entries are appended behind these and its name to the
    lists that name all cells."""
    manifest = _paths.manifest()
    per_layer = manifest["per_layer"]
    mine = [i for i, m in enumerate(per_layer)
            if m.get("workloads") == [CELL]]
    assert sorted(per_layer[i]["name"] for i in mine) == NEW
    for i in mine:
        assert os.path.isfile(os.path.join(
            _paths.BENCH, "layer_metrics", per_layer[i]["name"] + ".py"))
    rooflines = [per_layer[i] for i in mine
                 if per_layer[i]["name"].endswith("_roofline_pct")]
    assert len(rooflines) == 6
    assert all(m["unit"] == "%" and m["source"] == "device_trace"
               and m["better"] == "higher" for m in rooflines)
    # appended: one run of entries behind everything the accepted
    # benchmark had, PR 41's ten (their cell's alone, what
    # tests/perfbench/test_kernels_nemotron_h.py held before this cell
    # came) just before them
    theirs = [i for i, m in enumerate(per_layer)
              if m.get("workloads") == ["nemotron-3-nano-30b-a3b.reason"]]
    assert len(theirs) == 10
    assert theirs + mine == list(range(theirs[0], mine[-1] + 1))
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index(CELL) == 5
    # every list that named all five cells names this one behind them
    everywhere = [m for m in per_layer if len(m.get("workloads", [])) >= 4]
    assert len(everywhere) >= 18
    assert all(m["workloads"][:6] == cells[:6] for m in everywhere)
    # the accepted metrics of the two layers this cell shares with
    # a.x-k1.docqa's prefix cache read it too
    for name in ("kv.prefix_hit_tokens_pct", "kv.prefix_match_p50_ms"):
        m = next(m for m in per_layer if m["name"] == name)
        assert m["workloads"][:2] == ["a.x-k1.docqa", CELL]


def test_the_configuration_names_every_pattern_the_readers_ask_for():
    kernels = CONFIG["trace_patterns"]["kernels"]
    assert set(TIMES) <= set(kernels)
    # the windowed calls' patterns do not match the full layer's names,
    # nor the other way round
    import re
    names = {"attn_decode": "%paged_decode_attention.3 = bf16[16,128,128]",
             "attn_prefill": "%ragged_paged_attention_decode_rows.1 = ",
             "swa_decode": "%swa_paged_decode_attention.2 = bf16[",
             "swa_prefill": "%swa_ragged_paged_attention.7 = bf16["}
    for kernel, line in names.items():
        hits = [k for k in names if re.search(kernels[k], line)]
        assert hits == [kernel], (line, hits)
    classes = CONFIG["trace_patterns"]["step_classes"]
    assert classes["decode"] == {"has": ["attn_decode"],
                                 "lacks": ["attn_prefill"]}
