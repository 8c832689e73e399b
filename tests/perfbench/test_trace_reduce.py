"""The reduction from a profiler trace to numbers: its interval arithmetic
by hand, and the whole of it on a small trace recorded on the chip
(perfbench/fixtures/, cut from a run of qwen3-4b on one v5e)."""

import json
import os

import pytest

import _paths
import trace_reduce as tr

FIXTURE = os.path.join(_paths.BENCH, "fixtures", "v5e_1chip.xplane.pb")


@pytest.mark.parametrize("spans,want", [
    ([], 0.0),
    ([(0, 10)], 10.0),
    ([(0, 10), (5, 12)], 12.0),             # overlap counts once
    ([(0, 10), (2, 3)], 10.0),              # nested
    ([(5, 6), (0, 1)], 2.0),                # unsorted, disjoint
    ([(0, 1), (1, 2)], 2.0)])               # touching
def test_busy_is_the_union_of_intervals(spans, want):
    assert tr.union_length(spans) == want


def test_gaps_are_what_the_union_leaves_of_the_window():
    spans = [(2, 4), (3, 6), (9, 10)]
    assert tr.gaps_of(spans, 0, 12) == [(0, 2), (6, 9), (10, 12)]
    assert tr.gaps_of(spans, 2, 10) == [(6, 9)]
    total = sum(e - s for s, e in tr.gaps_of(spans, 0, 12))
    assert total + tr.union_length(spans) == 12


def test_self_time_leaves_out_what_nested_operations_cover():
    # a while of 100 holds two operations of 30 and 20; one stands alone
    events = [("while", 0, 100), ("a", 10, 40), ("b", 50, 70),
              ("c", 120, 130)]
    assert sorted(tr.self_times(events)) == [("a", 30), ("b", 20),
                                             ("c", 10), ("while", 50)]
    assert sum(t for _, t in tr.self_times(events)) == tr.union_length(
        [(s, e) for _, s, e in events])


def test_labels_drop_the_layout_annotations():
    name = "%fusion.1 = bf16[32,2560]{1,0:T(8,128)(2,1)S(1)} fusion(x)"
    assert tr.label(name) == "%fusion.1 = bf16[32,2560] fusion(x)"


def test_steps_are_classed_by_the_kernels_inside_them():
    classes = {"prefill": {"has": ["attn_prefill"]},
               "decode": {"has": ["attn_decode"],
                          "lacks": ["attn_prefill"]}}
    assert tr.classify({"attn_decode"}, classes) == "decode"
    assert tr.classify({"attn_prefill"}, classes) == "prefill"
    assert tr.classify({"attn_decode", "attn_prefill"}, classes) == "prefill"
    assert tr.classify(set(), classes) is None


@pytest.fixture(scope="module")
def reduced():
    patterns = _paths.bench_json("configs", "qwen3-4b.json")[
        "trace_patterns"]
    pd = tr.load(FIXTURE)
    return tr.reduce(pd, dict(tr.DEFAULT_PATTERNS, **patterns))


def test_fixture_has_one_device_plane_that_was_busy(reduced):
    assert list(reduced["devices"]) == ["/device:TPU:0"]
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    dev = reduced["devices"]["/device:TPU:0"]
    assert dev["idle_pct"] == pytest.approx(
        100 * (1 - reduced["busy_s"] / reduced["window_s"]))
    assert 0 <= dev["idle_pct"] < 100


def test_fixture_step_programs_and_kernels_are_found_by_pattern(reduced):
    dev = reduced["devices"]["/device:TPU:0"]
    assert dev["step_ms"].get("decode"), dev["step_ms"].keys()
    assert all(0 < ms < 1000 for ms in dev["step_ms"]["decode"])
    k = dev["kernels"]["attn_decode"]
    assert k["calls"] > 0 and 0 < k["seconds"] < reduced["busy_s"]


def test_fixture_breakdown_has_the_contracts_shape(reduced):
    b = reduced["breakdown"]
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    for name, seconds in b["device_ops"] + b["idle_gaps"]:
        assert isinstance(name, str) and seconds >= 0
    ops = [s for _, s in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    assert sum(ops) <= reduced["busy_s"] * 1.0001
    json.dumps(reduced)                     # the result line can carry it


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    with pytest.raises(SystemExit):
        tr.newest_xplane(str(tmp_path))
