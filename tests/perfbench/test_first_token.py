"""The readers of a request's way to its first token (perfbench/lib/
first_token.py and the eight layer metrics on it), on synthetic ``run``
dicts: each is the exact median of one field over the events of the
MEASURED window, nothing outside it is read, and a run or a program with
nothing to read gives None and raises nothing.
"""

import array
import json
import types

import pytest

import _paths
from lib import first_token
from run import load_module

M = _paths.manifest()
BY_NAME = {m["name"]: m for m in M["per_layer"]}
CELLS = [w["name"] for w in M["workloads"]]

STAGE_OF = {
    "front.parse_p50_ms": "parse_ms",
    "engine.intake_wait_p50_ms": "intake_ms",
    "sched.first_schedule_wait_p50_ms": "queue_ms",
    "runner.first_token_compute_p50_ms": "compute_ms",
    "engine.first_token_handover_p50_ms": "handover_ms",
    "front.first_token_emit_p50_ms": "emit_ms",
    "front.server_ttft_p50_ms": "total_ms",
}
PREFIX = "kv.prefix_match_p50_ms"


def reader(name):
    return load_module("layer_metrics", name).read


def first(i, parse, intake, queue, compute, handover, emit):
    stages = dict(zip(first_token.STAGES,
                      (parse, intake, queue, compute, handover, emit)))
    return dict(kind="first_token", seq=100 + i, t=1.0 + i, seq_id=i,
                prompt_tokens=300, cached_tokens=0, chunks=1,
                passes_waited=0, total_ms=round(sum(stages.values()), 3),
                **stages)


def rec(due, first_at):
    return types.SimpleNamespace(
        due=due, times=array.array("d", [] if first_at is None
                                   else [first_at, first_at + 0.02]))


def a_run():
    """Four requests and three probes in the window; the tail of the
    --trace 2 run (``steps``) holds others, which nobody reads."""
    window = [
        dict(kind="decode", seq=1, t=0.5, tokens=32, ph={"schedule": 0.2}),
        first(0, 2.0, 11.0, 0.3, 38.0, 2.0, 0.6),
        dict(kind="prefix", seq=2, t=0.7, query_tokens=9000,
             hit_tokens=8704, pages={"hbm": 544}, ms=4.0),
        first(1, 3.0, 9.0, 0.1, 25.0, 3.0, 0.4),
        dict(kind="prefix", seq=3, t=0.9, query_tokens=12000,
             hit_tokens=11776, pages={"hbm": 736}, ms=6.0),
        first(2, 2.5, 21.0, 0.2, 39.0, 1.0, 0.5),
        dict(kind="prefix", seq=4, t=1.1, query_tokens=16000,
             hit_tokens=15872, pages={"hbm": 992}, ms=9.5),
        first(3, 40.0, 1.0, 26.0, 64.0, 2.5, 9.0),
    ]
    tail = [first(9, 500.0, 500.0, 500.0, 500.0, 500.0, 500.0),
            dict(kind="prefix", seq=9, t=60.0, query_tokens=1,
                 hit_tokens=0, pages={}, ms=900.0)]
    return dict(window_steps=window, steps=tail, seconds=45.0,
                records=[rec(0.5, 0.56), rec(10.0, 10.07),
                         rec(44.0, 44.09), rec(44.9, None),
                         rec(-3.0, -2.9), rec(46.0, 46.05),
                         rec(None, 1.0)])


@pytest.mark.parametrize("name", sorted(STAGE_OF))
def test_a_stage_reads_the_exact_median_of_its_field_in_the_window(name,
                                                                   capsys):
    run = a_run()
    vals = sorted(e[STAGE_OF[name]] for e in run["window_steps"]
                  if e["kind"] == "first_token")
    # four requests: the mean of the two in the middle, no bucket's edge
    assert reader(name)(run) == pytest.approx((vals[1] + vals[2]) / 2)
    # one more request: its own value
    run["window_steps"].append(first(4, 2.2, 10.0, 0.25, 30.0, 2.2, 0.45))
    vals = sorted(e[STAGE_OF[name]] for e in run["window_steps"]
                  if e["kind"] == "first_token")
    assert reader(name)(run) == vals[2]
    capsys.readouterr()


def test_the_probe_reads_the_median_ms_of_the_windows_prefix_events():
    assert reader(PREFIX)(a_run()) == 6.0


@pytest.mark.parametrize("name", sorted(STAGE_OF) + [PREFIX])
def test_nothing_to_read_is_none_and_does_not_raise(name, capsys):
    run = a_run()
    # a --trace 1 run keeps no window_steps; a --trace 2 run that kept
    # none; a program that writes no such event (the parent), or a
    # prefix event without ``ms``
    without = dict(run)
    del without["window_steps"]
    parent = dict(run, window_steps=[
        {k: v for k, v in e.items() if k != "ms"}
        for e in run["window_steps"] if e["kind"] != "first_token"])
    for r in (without, dict(run, window_steps=[]),
              dict(run, window_steps=None), parent):
        assert reader(name)(r) is None
    assert "[first_token]" not in capsys.readouterr().out


def test_a_stage_some_requests_did_not_pass_is_read_over_those_that_did():
    run = a_run()
    for e in run["window_steps"]:
        if e.get("seq_id") in (0, 1, 2):
            del e["emit_ms"]            # unstreamed replies: no flush
    assert reader("front.first_token_emit_p50_ms")(run) == 9.0
    assert first_token.mean_over_events(run, "first_token", "emit_ms") \
        == pytest.approx(9.0 / 4)


def test_the_first_token_line_holds_means_that_add_up_and_the_clients(
        capsys):
    run = a_run()
    reader("front.server_ttft_p50_ms")(run)
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("[first_token] ")]
    got = json.loads(line.split(": ", 1)[1])
    assert got["requests"] == 4
    assert sum(got[f] for f in first_token.STAGES) \
        == pytest.approx(got["total_ms"], abs=0.01)
    assert got["parse_ms"] == pytest.approx((2.0 + 3.0 + 2.5 + 40.0) / 4)
    # the client's side: the requests DUE in the window that got a token
    assert got["client_requests"] == 3
    assert got["client_ttft_mean_ms"] == pytest.approx(
        1e3 * (0.06 + 0.07 + 0.09) / 3, abs=1e-3)


def test_the_two_workload_lists():
    for name in STAGE_OF:
        m = BY_NAME[name]
        assert m["workloads"] == CELLS
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_span", "ttft_p50_ms")
    assert BY_NAME[PREFIX]["workloads"] == ["a.x-k1.docqa"]
    assert BY_NAME[PREFIX]["layer"] == "KV manager"
    # appended to the end of the list, in the issue's order
    assert [m["name"] for m in M["per_layer"][-8:]] == [
        "front.parse_p50_ms", "engine.intake_wait_p50_ms",
        "sched.first_schedule_wait_p50_ms",
        "runner.first_token_compute_p50_ms",
        "engine.first_token_handover_p50_ms",
        "front.first_token_emit_p50_ms", "front.server_ttft_p50_ms", PREFIX]
