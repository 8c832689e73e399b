"""perfbench/host_gaps.py: the device's idle intervals named by the engine
thread's spans. Its interval arithmetic by hand, and the whole of it on a
small trace recorded on the chip with the spans in it
(perfbench/fixtures/v5e_1chip_spans.xplane.pb, cut from a --trace 2 run of
qwen3-4b.reason on one v5e, Python tracer off)."""

import json
import os
import subprocess
import sys
import types

import pytest

import _paths
import host_gaps as hg
import trace_reduce as tr

FIXTURE = os.path.join(_paths.BENCH, "fixtures",
                       "v5e_1chip_spans.xplane.pb")

# one decode-to-decode gap of a synchronous loop, in ns: the device is
# idle from 100 to 1100; the host notices at 130 (wait), reads back, puts
# the tokens out, delivers, drains the intake, schedules, builds,
# dispatches until 1000, and waits again; the program begins at 1100
SPANS = [("wait", 0, 130), ("readback", 130, 150), ("output", 150, 350),
         ("deliver", 350, 450), ("intake", 460, 480),
         ("schedule", 480, 700), ("build", 700, 900),
         ("dispatch", 900, 1000), ("wait", 1010, 2100)]
STARTS = [s[1] for s in SPANS]


def test_an_idle_interval_is_cut_along_the_spans_exactly():
    pieces = hg.cut((100, 1100), SPANS, STARTS)
    assert pieces[0] == ("wait", 100, 130)
    assert (None, 450, 460) in pieces and (None, 1000, 1010) in pieces
    assert pieces[-1] == ("wait", 1010, 1100)
    assert sum(e - s for _, s, e in pieces) == 1000
    assert all(a[2] == b[1] for a, b in zip(pieces, pieces[1:]))
    # an interval inside one span, and one that no span touches
    assert hg.cut((500, 600), SPANS, STARTS) == [("schedule", 500, 600)]
    assert hg.cut((3000, 3100), SPANS, STARTS) == [(None, 3000, 3100)]


def test_every_piece_goes_to_one_bucket_and_they_add_up():
    got = hg.attribute((100, 1100), SPANS, STARTS)
    assert got == {"schedule": 220, "build": 200,
                   # the span, and the launch latency after it ended
                   "dispatch": 100 + 90,
                   "output": 20 + 200 + 100,
                   # intake, and the two seams no span covers
                   "loop": 20 + 10 + 10,
                   # the device had finished, the host still waited
                   "unattributed": 30}
    assert sum(got.values()) == 1000
    # idle under ``wait`` with no dispatch before it in the interval is
    # nobody's launch latency
    assert hg.attribute((1500, 1600), SPANS, STARTS)["unattributed"] == 100
    assert hg.attribute((3000, 3100), SPANS, STARTS)["loop"] == 100


def test_clock_check_pairs_a_dispatch_with_the_program_it_launched():
    modules = [(1100, 2050), (3150, 4000)]
    spans = SPANS + [("dispatch", 2900, 3000), ("wait", 3010, 4080)]
    c = hg.clock_check(spans, modules)
    assert c["pairs"] == 2
    d = c["dispatch_end_to_program_start_us"]
    assert (d["median"], d["min"], d["negative"]) == (0.125, 0.1, 0)
    w = c["program_end_to_wait_end_us"]
    assert (w["min"], w["negative"]) == (0.05, 0)
    # a device clock 200 ns behind the host's: programs seem to begin
    # before their dispatch has returned
    behind = hg.clock_check(spans, [(s - 200, e - 200) for s, e in modules])
    assert behind["dispatch_end_to_program_start_us"]["negative"] == 2
    # one 200 ns ahead: they seem to end after the host saw them end
    ahead = hg.clock_check(spans, [(s + 200, e + 200) for s, e in modules])
    assert ahead["program_end_to_wait_end_us"]["negative"] == 2
    assert hg.clock_check(SPANS, []) is None


def _trace(lines):
    ev = lambda n, s, e: types.SimpleNamespace(         # noqa: E731
        name=n, start_ns=s, duration_ns=e - s)
    return types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(
            name=name, events=[ev(*e) for e in events])
            for name, events in lines])])


def test_the_engine_thread_is_the_line_with_the_spans_and_nests_are_dropped():
    pd = _trace([
        ("handler", [("SomeTraceMe", 0, 5), ("gllm:stray", 1, 2)]),
        ("python", [("gllm:dispatch", 10, 50), ("gllm:first_use", 20, 40),
                    ("PjitFunction(step)", 12, 30), ("gllm:wait", 60, 90),
                    ("gllm:build", 2, 9)])])
    assert hg.engine_spans(pd, r"^/host:CPU$") == [
        ("build", 2.0, 9.0), ("dispatch", 10.0, 50.0), ("wait", 60.0, 90.0)]
    assert hg.engine_spans(_trace([("t", [("x", 0, 1)])]), "CPU") == []


# ---- the whole of it, on the chip's trace ----------------------------------

@pytest.fixture(scope="module")
def patterns():
    return dict(tr.DEFAULT_PATTERNS, **_paths.bench_json(
        "configs", "qwen3-4b.json")["trace_patterns"])


@pytest.fixture(scope="module")
def gaps(patterns):
    return hg.reduce(tr.load(FIXTURE), patterns)


def test_fixture_is_small_and_has_the_spans_and_no_python_tracer():
    assert os.path.getsize(FIXTURE) < 1024 * 1024
    names = {e.name for p in tr.load(FIXTURE).planes
             for line in p.lines for e in line.events}
    assert {"gllm:" + n for n in ("schedule", "build", "dispatch", "wait",
                                  "readback", "output", "deliver",
                                  "intake")} <= names
    assert not any(n.startswith("$") for n in names)


def test_fixture_buckets_add_up_to_the_idle_share_within_two_percent(
        gaps, patterns):
    """The identity of ISSUE 24: the five idle metrics and the
    unattributed share are device.idle_pct of the same slice."""
    reduced = tr.reduce(tr.load(FIXTURE), patterns)
    idle_pct = reduced["devices"]["/device:TPU:0"]["idle_pct"]
    assert gaps["idle_pct"] == pytest.approx(idle_pct, rel=1e-6)
    assert gaps["window_s"] == pytest.approx(reduced["window_s"])
    assert sum(gaps["idle_pct_by_phase"].values()) \
        == pytest.approx(idle_pct, rel=0.02)
    assert gaps["identity_error_pct"] < 2.0
    # ... and in the units the metrics have: ms a step program
    per_step = gaps["idle_ms_per_step"]
    five = sum(per_step[b] for b in hg.BUCKETS if b != "unattributed")
    whole = gaps["idle_s"] * 1e3 / gaps["steps"]
    assert five + whole * gaps["unattributed_pct"] / 100.0 \
        == pytest.approx(whole, rel=0.02)
    assert gaps["steps"] >= 4 and all(v >= 0 for v in per_step.values())


def test_fixture_names_its_idle_time_and_the_clocks_agree(gaps):
    assert gaps["unattributed_pct"] < 10.0
    per_step = gaps["idle_ms_per_step"]
    assert per_step["schedule"] > 0 and per_step["build"] > 0 \
        and per_step["dispatch"] > 0 and per_step["output"] > 0
    c = gaps["clock"]
    assert c["pairs"] >= 4
    d = c["dispatch_end_to_program_start_us"]
    assert 0 <= d["median"] < 1000 and d["negative"] == 0
    assert c["program_end_to_wait_end_us"]["negative"] == 0
    for name, seconds in gaps["idle_gaps"]:
        assert name.split(": ")[1] in hg.BUCKETS and seconds >= 0
    json.dumps(gaps)


def test_the_layer_metric_readers_read_it_and_read_nothing_without_it(gaps):
    from run import load_module
    run_with = {"host_gaps": gaps}
    values = {}
    for name, bucket in [("sched.idle_ms_per_step", "schedule"),
                         ("runner.build_idle_ms_per_step", "build"),
                         ("runner.dispatch_idle_ms_per_step", "dispatch"),
                         ("engine.output_idle_ms_per_step", "output"),
                         ("engine.loop_idle_ms_per_step", "loop")]:
        read = load_module("layer_metrics", name).read
        values[name] = read(run_with)
        assert values[name] == gaps["idle_ms_per_step"][bucket]
        assert read({}) is None and read({"host_gaps": None}) is None
    read = load_module("layer_metrics", "device.idle_unattributed_pct").read
    assert read(run_with) == gaps["unattributed_pct"]
    assert read({}) is None
    # the counter readers leave their metric out of a run without the tail
    none = {"prom0": None, "prom1": None, "steps": []}
    for name in ("front.emit_lag_p95_ms", "front.admit_lag_p50_ms",
                 "runner.first_use_s"):
        assert load_module("layer_metrics", name).read(none) is None


def test_first_use_seconds_over_the_window_and_the_tail():
    from run import load_module
    read = load_module("layer_metrics", "runner.first_use_s").read
    shared = {"seq": 7, "kind": "compile", "first_use_ms": 1500.0}
    run = {"window_steps": [shared, {"seq": 8, "kind": "decode"}],
           "steps": [shared,
                     {"seq": 9, "kind": "compile", "first_use_ms": 500.0}]}
    assert read(run) == 2.0             # an event in both counts once
    assert read({"window_steps": [], "steps": []}) == 0.0


def test_as_a_child_it_writes_the_table_and_refuses_a_trace_without_spans(
        tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = os.path.join(_paths.BENCH, "host_gaps.py")
    out = tmp_path / "gaps.json"
    old = tmp_path / "old"
    old.mkdir()
    os.symlink(os.path.join(_paths.BENCH, "fixtures",
                            "v5e_1chip.xplane.pb"),
               old / "v5e_1chip.xplane.pb")
    r = subprocess.run([sys.executable, script, "--trace-dir", str(old),
                        "--out", str(tmp_path / "none.json")],
                       env=env, text=True, capture_output=True, timeout=300)
    assert r.returncode == hg.NO_SPANS and "no gllm:* span" in r.stderr
    assert not (tmp_path / "none.json").exists()
    new = tmp_path / "new"
    new.mkdir()
    os.symlink(FIXTURE, new / "spans.xplane.pb")
    r = subprocess.run([sys.executable, script, "--trace-dir", str(new),
                        "--out", str(out), "--table"],
                       env=env, text=True, capture_output=True, timeout=300)
    assert r.returncode == 0, r.stderr[-800:]
    assert "clock check over" in r.stdout and "unattributed" in r.stdout
    assert json.loads(out.read_text())["steps"] >= 4
