"""run.py --trace 2: one process that measures first and traces afterwards.

On the CPU, at tiny widths: the measured window of a --trace 2 run is a
--trace 0 run's (the same plan request for request, the same control
requests to the server before the window ends), and its last line holds
the end-to-end metrics and the layer metrics side by side.
"""

import array
import json
import os
import re
import threading

import pytest

import _paths
import run as bench_run
from lib.loadgen import Load, Req
from test_rehearsal import KEYS, bench

M = _paths.manifest()
E2E = {m["name"] for m in M["end_to_end"] if "workloads" not in m}
SEED = str(2 ** 31 + 4321)


def rehearse(cell, trace, seconds="4"):
    rc, lines, err = bench("--workload", cell, "--seed", SEED, "--seconds",
                           seconds, "--trace", str(trace),
                           "--cpu-rehearsal")
    assert rc == 0, (lines[-15:], err[-2000:])
    out = json.loads(lines[-1])
    assert set(out) - {"breakdown"} == KEYS
    with open(os.path.join(_paths.ROOT, "chiprun_out", "perfbench", cell,
                           f"records.seed{SEED}.trace{trace}.json")) as f:
        saved = json.load(f)
    return out, lines, saved["requests"]


def control_requests(lines):
    (line,) = [ln for ln in lines if ln.startswith("[requests]")]
    return [re.sub(r"since=\d+", "since=N", r)
            for r in json.loads(line.split(": ", 1)[1])]


@pytest.fixture(scope="module")
def reason():
    return {t: rehearse("qwen3-4b.reason", t) for t in (0, 2)}


def test_last_line_of_a_trace_2_run_holds_both_kinds_of_metric(reason):
    out, lines, _ = reason[2]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 10
    names = set(out["metrics"])
    assert E2E <= names
    by_source = {m["name"]: m["source"] for m in M["per_layer"]}
    # what counters and the steptrace give is readable on the CPU ...
    assert {"sched.queue_wait_p50_ms", "engine.decode_batch_mean",
            "kv.util_peak_pct", "engine.host_ms_per_step",
            "front.emit_lag_p95_ms", "front.admit_lag_p50_ms",
            "runner.first_use_s"} <= names
    # ... and nothing that needs a device plane is
    assert not {n for n in names - E2E
                if by_source[n] == "device_trace"
                or n.endswith("_idle_ms_per_step")
                or n == "device.idle_unattributed_pct"}
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert any(ln.startswith("[trace] host phases per decode step")
               for ln in lines)
    # --trace 0 prints what it printed: the end-to-end metrics alone
    assert set(reason[0][0]["metrics"]) == E2E


def test_the_measured_window_is_a_trace_0_runs_request_for_request(reason):
    """The frozen records are the same plan: per caller the same requests
    in the same order (how many of them a caller got through by the
    window's end is the host's speed, not the plan), none of them due
    after the window shut, whatever the tail went on to send."""
    def by_client(requests):
        out = {}
        for r in requests:
            assert r["due"] < 4.0 + 0.5     # drain_s 0: nothing later
            out.setdefault(r["client"], []).append(
                (r["idx"], r["prompt_len"], r["max_tokens"]))
        return out
    a, b = by_client(reason[0][2]), by_client(reason[2][2])
    assert set(a) == set(b) and len(a) == 12        # the cell's callers
    for client in a:
        n = min(len(a[client]), len(b[client]))
        assert n >= 1 and a[client][:n] == b[client][:n]
        assert abs(len(a[client]) - len(b[client])) <= 1
    # the tail did go on sending: the frozen copy is not the whole log
    (window,) = [ln for ln in reason[2][1]
                 if ln.startswith("[window] {")]
    assert json.loads(window[len("[window] "):])["requests_sent"] \
        == len(reason[2][2])


def test_no_control_request_before_the_window_ends_that_trace_0_lacks(
        reason):
    """Between the load's start and the window's end the server is asked
    exactly what the accepted harness asks it: the fill's step shapes,
    once, and nothing else (no /metrics, /server_info or profiler call)."""
    want = ["GET /steptrace?since=N&kind=compile"]
    assert control_requests(reason[0][1]) == want
    assert control_requests(reason[2][1]) == want


def test_an_open_loops_window_plan_does_not_change_with_its_tail():
    out, lines, requests = rehearse("tiny-qwen3.steady", 2, seconds="3")
    assert out["correct"] is True and out["failed"] == 0
    assert E2E <= set(out["metrics"])
    cell = _paths.bench_json("cells", "tiny-qwen3.steady.json")
    traffic = _paths.bench_json("traffic", cell["traffic"] + ".json")
    traffic = dict(traffic, **traffic.get("rehearsal", {}))
    config = _paths.bench_json("configs", cell["config"] + ".json")
    vocab = dict(bench_run.model_of(config), **config.get(
        "rehearsal", {}).get("model", {}))["vocab_size"]
    plan = bench_run.load_module("generators", traffic["generator"]).plan(
        traffic, dict(cell, **cell.get("rehearsal", {})), int(SEED), 3.0,
        vocab)
    got = [(r["idx"], r["prompt_len"], r["max_tokens"], r["due"])
           for r in requests if r["idx"] < len(plan)]
    assert got == [(r.idx, len(r.prompt), r.max_tokens,
                    pytest.approx(r.due)) for r in plan[:len(got)]]
    assert len(got) >= len(plan) - 1
    # the tail's own requests were sent after the window and are not in
    # the frozen copy's window: none is due before its end
    assert all(r["due"] >= 3.0 for r in requests if r["idx"] >= len(plan))


def test_freeze_copies_the_records_as_they_stand():
    load = Load(0)
    load.clock.zero = 0.0
    done, live, unsent = Req(0, [1], 4, due=0.5), Req(1, [2], 4, due=1.0), \
        Req(2, [3], 4)
    done.times, done.status, done.ended = array.array("d", [1, 2]), "ok", 2.5
    live.times = array.array("d", [3.0])
    load.records += [done, live, unsent]
    load.clock.now = lambda: 7.0
    frozen = bench_run.freeze(load)
    assert [r.idx for r in frozen] == [0, 1]        # never due: not sent
    assert (frozen[0].status, frozen[0].ended) == ("ok", 2.5)
    assert (frozen[1].status, frozen[1].ended) == ("cut", 7.0)
    live.times.append(8.0)                          # the tail goes on
    live.status = "ok"
    assert list(frozen[1].times) == [3.0] and frozen[1].status == "cut"
    assert not load.lock.locked() and isinstance(load.lock,
                                                 type(threading.Lock()))


def test_host_time_per_decode_step_reads_ph_without_collect():
    events = [
        {"kind": "decode", "ph": {"schedule": 1.0, "build": 2.0,
                                  "collect": 20.0, "output": 0.5}},
        {"kind": "decode", "ph": {"schedule": 3.0, "collect": 22.0}},
        {"kind": "prefill", "ph": {"schedule": 9.0, "collect": 100.0}},
        {"kind": "compile", "first_use_ms": 3.0}]
    assert bench_run.host_ms_per_decode_step(events) == (3.25, 2)
    assert bench_run.host_ms_per_decode_step([]) == (None, 0)
