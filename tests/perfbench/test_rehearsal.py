"""run.py end to end on the CPU: the whole control flow at tiny widths.

The tiny configuration, its mix and its cell were added by files alone
(perfbench/configs/tiny-qwen3.json, traffic/tiny-steady.json,
cells/tiny-qwen3.steady.json): no line of the harness names them.
"""

import json
import os
import subprocess
import sys

import pytest

import _paths

RUN = os.path.join(_paths.BENCH, "run.py")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
M = _paths.manifest()


def bench(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, RUN, *args], cwd=_paths.ROOT, env=env,
                       text=True, capture_output=True, timeout=timeout)
    lines = r.stdout.strip().splitlines()
    return r.returncode, lines, r.stderr


def result(lines):
    out = json.loads(lines[-1])
    assert set(out) - {"breakdown"} == KEYS
    assert out["device"]["platform"] == "cpu"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    return out


def test_tiny_cell_added_by_files_alone_prints_the_contracts_last_line():
    rc, lines, err = bench("--workload", "tiny-qwen3.steady", "--seed",
                           str(2 ** 31 + 5), "--seconds", "3", "--trace", "0",
                           "--cpu-rehearsal")
    assert rc == 0, (lines[-15:], err[-2000:])
    out = result(lines)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 10
    e2e = {m["name"] for m in M["end_to_end"] if "workloads" not in m}
    assert set(out["metrics"]) == e2e
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["metrics"]["output_tok_s"]["value"] > 0
    # each number compared is printed beside its limit
    assert any("prefill_rel_rms" in ln and "limit" in ln for ln in lines)
    assert any("decode_rel_rms" in ln and "limit" in ln for ln in lines)


def test_traced_rehearsal_of_a_real_cell_reports_layer_metrics_only():
    rc, lines, err = bench("--workload", "qwen3-4b.chat", "--seed", "11",
                           "--seconds", "4", "--trace", "1",
                           "--cpu-rehearsal")
    assert rc == 0, (lines[-15:], err[-2000:])
    out = result(lines)
    assert out["correct"] is True          # the tied head
    layer = {m["name"] for m in M["per_layer"]}
    assert set(out["metrics"]) <= layer
    # counters and the generator's clock are readable on the CPU; nothing
    # that comes from a device trace may be
    # (a 4 s chat window may hold no step with decode rows alone, and a
    # reader with nothing to read leaves its metric out)
    assert {"sched.queue_wait_p50_ms",
            "runner.compiles_in_window"} <= set(out["metrics"])
    from_trace = {m["name"] for m in M["per_layer"]
                  if m["source"] == "device_trace"}
    assert not from_trace & set(out["metrics"])
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_closed_loop_rehearsal_meets_no_step_shape_after_its_warm_up():
    """The reason cell at tiny widths with the same geometry (two row
    buckets in the fill, two pages buckets): what the fill and the window
    use, the warm-up script has built."""
    rc, lines, err = bench("--workload", "qwen3-4b.reason", "--seed",
                           str(2 ** 31 + 129), "--seconds", "4", "--trace",
                           "0", "--cpu-rehearsal")
    assert rc == 0, (lines[-15:], err[-2000:])
    out = result(lines)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 10
    fill = [ln for ln in lines if ln.startswith("[fill] over")]
    assert len(fill) == 1 and fill[0].endswith("(should be none): []")
    assert any(ln.startswith("[window] step shapes") and ln.endswith(": []")
               for ln in lines)


def test_pipelined_rehearsal_agrees_with_the_stitched_reference():
    rc, lines, err = bench("--workload", "qwen3-8b-pp4.chat", "--seed", "12",
                           "--seconds", "3", "--trace", "0",
                           "--cpu-rehearsal")
    assert rc == 0, (lines[-15:], err[-2000:])
    out = result(lines)
    assert out["correct"] is True and out["device"]["count"] == 4


def test_the_lower_precision_control_comes_out_not_correct():
    rc, lines, err = bench("--workload", "tiny-qwen3.steady", "--seed", "13",
                           "--seconds", "3", "--trace", "0",
                           "--cpu-rehearsal", "--control")
    assert rc == 0, (lines[-15:], err[-2000:])
    assert result(lines)["correct"] is False
    assert any("NOT CORRECT" in ln for ln in lines)


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_without_a_tpu_it_fails_and_prints_no_metric(cell):
    rc, lines, err = bench("--workload", cell, "--seed", "1", "--seconds",
                           "3", "--trace", "0", timeout=300)
    assert rc != 0
    assert not any(ln.startswith("{") for ln in lines)
    assert not any("[metric]" in ln for ln in lines)


def test_an_unknown_cell_fails():
    rc, lines, err = bench("--workload", "no-such.cell", "--seed", "1",
                           "--seconds", "3", "--trace", "0")
    assert rc != 0 and not any(ln.startswith("{") for ln in lines)
