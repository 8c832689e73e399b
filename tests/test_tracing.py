"""Performance-attribution layer (ISSUE 10, docs/observability.md#tracing):

- SpanTrace lifecycle units: begin/event/finish, ring bound/eviction,
  open-bound untracking, phase-cap rollup, idempotent close;
- a request's way to its first token (ISSUE 39): the stages are the
  consecutive differences of one list of stamps, exactly one
  ``first_token`` event a request on every path, the tree built from
  the same stamps, and no call into SpanTrace from a decode-only step
  (pure host: a scripted engine over the real scheduler, no jit);
- summarize() attribution math (host_ms_by_phase, blocked_ms_by_phase,
  first-use wall) on synthetic events;
- the phase clock (obs/spans.phase): the closed vocabulary in ``ph`` on
  every step path, no annotation object and no jax import while no
  capture runs, the capture flag over two captures, first_use_ms against
  its counter, the two front-end histograms;
- chrome_trace JSON schema (engine-phase tracks + request tracks,
  phase slices reconstruct the step wall);
- engine e2e on a dummy-weight CPU model: step events carry the phase
  breakdown, the phase-sum ≈ step-wall invariant holds on the
  synchronous engine, span trees complete for every request
  (queued → prefill chunks → one rolled-up decode → finish), fused
  chains included;
- terminal paths (abort / deadline / quarantine) close spans;
- tracing=False: zero spans recorded, token streams byte-identical;
- /trace + /steptrace?kind= + POST /profile HTTP surface;
- obs.dump --format chrome / --kind / --since.
"""

import http.client
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.obs import spans as obs_spans
from gllm_tpu.obs.spans import (ENGINE_PHASES, HOST_PHASES, SPANS,
                                SpanTrace, chrome_trace, phase,
                                step_phases, take_phases)
from gllm_tpu.obs.steptrace import StepTrace, summarize
from gllm_tpu.sampling_params import SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_spans():
    SPANS.clear()
    yield
    SPANS.clear()


# ---- SpanTrace units -------------------------------------------------------

def _stamps(arrival_t, first_sched_t):
    """What first_token_stamps gives at the first schedule of a request
    that no serving engine submitted."""
    return [("arrival", arrival_t), ("queue", first_sched_t)]


def test_span_lifecycle_and_ring_bound():
    tr = SpanTrace(capacity=4, max_open=8, max_phases=64)
    tr.begin(1, _stamps(10.0, 10.5), prompt_tokens=7)
    assert tr.open_count == 1
    tr.begin(1, _stamps(99.0, 99.5))                 # idempotent
    assert tr.open_count == 1
    tr.event(1, "prefill_chunk", 10.6, 3.0, tokens=7)
    tr.event(999, "prefill_chunk", 0.0, 1.0)         # untracked: no-op
    rec = tr.finish(1, "stop", 11.0, output_tokens=3)
    assert rec["reason"] == "stop" and rec["output_tokens"] == 3
    assert rec["phases"][0]["ph"] == "queued"
    assert rec["phases"][0]["dur_ms"] == pytest.approx(500.0)
    assert [p["ph"] for p in rec["phases"]] == ["queued", "prefill_chunk"]
    assert tr.open_count == 0
    assert tr.finish(1, "stop", 12.0) is None        # second close: no-op
    # ring eviction: capacity 4 keeps the newest 4 completed trees
    for sid in range(2, 9):
        tr.begin(sid, _stamps(sid * 1.0, sid * 1.0 + 0.1))
        tr.finish(sid, "length", sid * 1.0 + 1)
    assert [r["seq_id"] for r in tr.spans()] == [5, 6, 7, 8]
    assert tr.dropped == 4


def test_span_open_bound_and_phase_cap():
    tr = SpanTrace(capacity=8, max_open=2, max_phases=3)
    tr.begin(1, _stamps(1.0, 1.1))
    tr.begin(2, _stamps(1.0, 1.1))
    tr.begin(3, _stamps(1.0, 1.1))                   # over the bound
    assert tr.open_count == 2 and tr.untracked == 1
    # phase cap (a long prompt in small chunks can reach it): later
    # events roll up into per-phase aggregates
    for i in range(6):
        tr.event(1, "prefill_chunk", float(i), 2.0)
    rec = tr.finish(1, "length", 10.0)
    assert len(rec["phases"]) == 3                   # queued + 2 chunks
    agg = rec["agg"]["prefill_chunk"]
    assert agg["n"] == 4 and agg["ms"] == pytest.approx(8.0)


# ---- summarize() attribution math ------------------------------------------

def _step_event(tr, kind, t, sched, build, disp, coll, wall,
                more=None, **extra):
    """``more``: seconds by phase beyond the four named ones (intake,
    output, deliver, idle, and the wait / readback split of collect)."""
    fields = step_phases(dict(
        {"schedule": sched / 1e3, "build": build / 1e3,
         "dispatch": disp / 1e3, "wait": coll / 1e3}, **(more or {})))
    tr.record(kind, num_seqs=2, tokens=2, wall_ms=coll,
              step_wall_ms=wall, **fields, **extra)
    # pin the event's t for deterministic window math
    tr._buf[(tr._next_seq - 1) % tr.capacity]["t"] = t


def test_summarize_attribution_window():
    tr = StepTrace(capacity=64)
    # two decode steps: 10ms wall each, collect 2ms
    # ... of which 1.5 waiting for the device and 0.5 reading back; the
    # second step also carries the first one's output and deliver and
    # its own pass's intake, and the loop slept 3 ms before it
    _step_event(tr, "decode", 0.010, 1.0, 2.0, 1.0, 1.5,
                wall=10.0, more={"readback": 0.0005})
    _step_event(tr, "decode", 0.020, 1.0, 2.0, 1.0, 1.5, wall=10.0,
                more={"readback": 0.0005, "output": 0.0007,
                      "deliver": 0.0003, "intake": 0.0001,
                      "idle": 0.003})
    tr.record("compile", dispatch="step", source="cache",
              first_use_ms=1500.0)
    s = summarize(tr.events())
    assert s["host_ms_by_phase"] == {"schedule": 2.0, "build": 4.0,
                                     "dispatch": 2.0, "collect": 4.0,
                                     "output": 0.7, "deliver": 0.3,
                                     "intake": 0.1}
    # what the engine thread did NOT spend working rides beside it
    assert s["blocked_ms_by_phase"] == {"wait": 3.0, "readback": 1.0,
                                        "idle": 3.0}
    assert s["compiles"] == 1 and s["first_use_ms"] == 1500.0
    # the per-step estimates are gone from the loop and from the summary
    assert not {"mfu", "device_mfu", "hbm_gbps"} & set(s)


def test_summarize_without_attribution_fields_is_none():
    tr = StepTrace(capacity=8)
    tr.record("decode", tokens=4, wall_ms=2.0, num_seqs=1)
    s = summarize(tr.events())
    assert s["host_ms_by_phase"] is None
    assert s["blocked_ms_by_phase"] is None and s["first_use_ms"] == 0.0


# ---- a request's way to its first token (ISSUE 39) -------------------------
#
# Pure host: ``HostEngine`` is LLM's own record keeping (_record_step,
# _emit_step, _record_spans, _observe_outputs, borrowed as they are) over
# the real Scheduler and memory manager, with a scripted 'device' that
# samples token 7; ServingEngine, deliver_output and the api_server's
# handler methods are the real ones. No jit, no socket.

def _spy_on(tr):
    """Log every call into a SpanTrace instance (its public methods)."""
    calls = []
    for name in ("begin", "stages", "event", "close", "finish"):
        def wrapped(*a, _real=getattr(tr, name), _name=name, **kw):
            calls.append((_name, a, kw))
            return _real(*a, **kw)
        setattr(tr, name, wrapped)
    return calls


class HostEngine:
    tokenizer = None
    model_cfg = None

    def __init__(self, maxp=6, tracing=True, prefix=False):
        from gllm_tpu.engine.llm import LLM
        from gllm_tpu.memory_manager import make_memory_manager
        from gllm_tpu.scheduler import Scheduler
        cls = type(self)
        for name in ("_record_step", "_emit_step", "_record_spans",
                     "_observe_outputs", "_allocate_seq"):
            if not hasattr(cls, name):
                setattr(cls, name, getattr(LLM, name))
        self.config = EngineConfig(
            max_model_len=256, max_num_seqs=8, tracing=tracing,
            scheduler=SchedulerConfig(max_prefill_tokens=maxp,
                                      min_prefill_tokens=2,
                                      max_decode_seqs=8),
            cache=CacheConfig(page_size=4, num_pages=64,
                              enable_prefix_caching=prefix))
        self.scheduler = Scheduler(
            self.config, make_memory_manager(64, 4, prefix))
        self.tracing = tracing
        self.spans = self.scheduler.spans = SpanTrace()
        self.runner = type("R", (), {"attn_impl": "xla"})()
        self._in_flight = []
        self._next_seq_id = 0
        self.steps = []                 # kind of every step, in order
        self.on_step = None             # hook(n) after step n's collect

    def add_seq(self, seq):
        self.scheduler.add_seq(seq)

    def abort(self, seq_id):
        self.scheduler.abort_seq(seq_id)

    @property
    def has_unfinished(self):
        return self.scheduler.has_unfinished

    def close(self):
        pass

    def step(self, after_dispatch=None, hold_launch=None):
        sched_ph = phase("schedule").start()
        batch = self.scheduler.schedule_once()
        sched_ph.stop()
        if batch is None:
            return []
        phases = take_phases()
        phases["t_enter"] = sched_ph.t0
        t_dispatch = time.monotonic()
        if after_dispatch is not None:
            after_dispatch()
        self.steps.append("decode" if batch.num_decode == batch.num_seqs
                          else "prefill")
        self._record_step(batch, time.monotonic(), t_dispatch, None, phases)
        outs = self.scheduler.process_output(batch, [7] * batch.num_seqs,
                                             ())
        self._observe_outputs(outs)
        if self.on_step is not None:
            self.on_step(len(self.steps))
        return outs

    def generate(self, prompts, max_tokens=4):
        seqs = [self._allocate_seq(p, SamplingParams(
            max_tokens=max_tokens, **GREEDY)) for p in prompts]
        for s in seqs:
            self.add_seq(s)
        while self.has_unfinished:
            self.step()
        return seqs


GREEDY = dict(temperature=0.0, ignore_eos=True)
STAGES = ("parse_ms", "intake_ms", "queue_ms", "compute_ms", "handover_ms",
          "emit_ms")


def _first_tokens(mark):
    from gllm_tpu.obs.steptrace import TRACE
    return TRACE.events(since=mark, kinds=["first_token"])


def _handler(engine):
    """An api_server Handler with no socket behind it: what it writes
    goes into a buffer."""
    import io
    import types
    from gllm_tpu.entrypoints.api_server import Handler
    h = Handler.__new__(Handler)
    h.wfile = io.BytesIO()
    h.state = types.SimpleNamespace(engine=engine)
    return h


def test_stages_are_consecutive_differences_of_one_list_of_stamps():
    from gllm_tpu.obs.steptrace import TRACE
    from gllm_tpu.sequence import Sequence
    seq = Sequence(5, list(range(9)), SamplingParams(max_tokens=3))
    seq.arrival_time = 100.0011         # inside parse: not a stamp
    (seq.received_t, seq.submitted_t, seq.admitted_t, seq.first_sched_time,
     seq.first_token_time) = 100.0, 100.0023, 100.0131, 100.0134, 100.0519
    seq.num_cached_tokens, seq.prefill_chunks, seq.passes_waited = 4, 1, 2
    stamps = obs_spans.first_token_stamps(seq, 100.0541)
    assert [n for n, _ in stamps] == ["received", "parse", "intake",
                                      "queue", "compute", "handover"]
    assert [t for _, t in stamps] == sorted(t for _, t in stamps)
    mark = TRACE.mark()
    first = obs_spans.FirstToken(seq, 100.0541)
    ev = first.record(100.0549)
    assert first.record(100.06) is None             # once
    (on_ring,) = _first_tokens(mark)
    assert {k: on_ring[k] for k in ev} == ev
    want = dict(parse_ms=2.3, intake_ms=10.8, queue_ms=0.3,
                compute_ms=38.5, handover_ms=2.2, emit_ms=0.8)
    assert {k: ev[k] for k in STAGES} == pytest.approx(want, abs=1e-6)
    assert ev["total_ms"] == pytest.approx(54.9, abs=1e-6)
    assert sum(ev[k] for k in STAGES) == pytest.approx(ev["total_ms"],
                                                       abs=1e-6)
    assert (ev["seq_id"], ev["prompt_tokens"], ev["cached_tokens"],
            ev["chunks"], ev["passes_waited"]) == (5, 9, 4, 2, 2)
    # the three instants lie on the ring's clock
    assert ev["t_first_sched"] - ev["t_received"] \
        == pytest.approx(0.0134, abs=2e-6)
    assert ev["t_token"] == pytest.approx(100.0519 - TRACE.t0, abs=1e-6)
    # a stamp not taken: its stage is absent (never 0), the next one runs
    # from the last stamp there is, and the whole still adds up
    seq.received_t = None
    seq.admitted_t = 0.0
    ev = obs_spans.FirstToken(seq, 100.0541).record()
    assert {k for k in ev if k.endswith("_ms")} == {
        "queue_ms", "compute_ms", "handover_ms", "total_ms"}
    assert "t_received" not in ev
    assert ev["queue_ms"] == pytest.approx(11.1, abs=1e-6)
    assert ev["total_ms"] == pytest.approx(51.8, abs=1e-6)


def test_offline_requests_record_at_the_collect_without_a_front():
    from gllm_tpu.obs.steptrace import TRACE
    eng = HostEngine(maxp=6)
    mark = TRACE.mark()
    seqs = eng.generate([list(range(10)), [3, 4]], max_tokens=4)
    evs = {e["seq_id"]: e for e in _first_tokens(mark)}
    assert sorted(evs) == [s.seq_id for s in seqs]      # one a request
    long, short = (evs[s.seq_id] for s in seqs)
    for e in (long, short):
        assert {k for k in e if k.endswith("_ms")} == {
            "queue_ms", "compute_ms", "total_ms"}
        assert e["total_ms"] == pytest.approx(
            e["queue_ms"] + e["compute_ms"], abs=2e-3)
        assert "t_received" not in e and e["t_token"] > e["t_first_sched"]
    # ten tokens through a budget of six: two steps carried its prompt;
    # the short one waited out the pass that had no budget left for it
    assert (long["chunks"], long["prompt_tokens"]) == (2, 10)
    assert (short["chunks"], short["passes_waited"]) == (1, 1)
    assert long["passes_waited"] == 0
    # the steps that carried a request lie between its two instants
    steps = [e for e in TRACE.events(since=mark)
             if e["kind"] in ("prefill", "decode")]
    carried = [e for e in steps
               if long["t_first_sched"] <= e["t"] <= long["t_token"]]
    assert [e["kind"] for e in carried] == ["prefill", "prefill"]


def test_a_decode_only_step_makes_no_call_into_spantrace():
    eng = HostEngine(maxp=16)
    calls = _spy_on(eng.spans)
    seqs = [eng._allocate_seq(p, SamplingParams(max_tokens=6, **GREEDY))
            for p in ([1, 2, 3, 4, 5], [6, 7, 8])]
    for s in seqs:
        eng.add_seq(s)
    eng.step()
    assert eng.steps == ["prefill"]
    assert [c[0] for c in calls] == ["begin", "begin", "event", "event"]
    del calls[:]
    for _ in range(4):
        eng.step()
    assert eng.steps[1:] == ["decode"] * 4 and calls == []
    eng.step()                                  # the finishing step
    assert [c[0] for c in calls] == ["close", "finish"] * 2
    for rec in eng.spans.spans():
        assert [p["ph"] for p in rec["phases"]] == [
            "queued", "prefill_chunk", "decode"]
        assert rec["phases"][-1]["tokens"] == 5


def test_aborted_before_its_first_token_records_nothing():
    from gllm_tpu.obs.steptrace import TRACE
    eng = HostEngine(maxp=6)
    mark = TRACE.mark()
    seq = eng._allocate_seq(list(range(10)),
                            SamplingParams(max_tokens=4, **GREEDY))
    eng.add_seq(seq)
    eng.step()                                  # the first chunk of two
    assert seq.prefill_chunks == 1 and not seq.first_token_time
    eng.abort(seq.seq_id)
    while eng.has_unfinished:
        eng.step()
    assert seq.finish_reason == "abort"
    assert _first_tokens(mark) == []
    (rec,) = eng.spans.spans()
    assert rec["reason"] == "abort"
    assert [p["ph"] for p in rec["phases"]] == ["queued", "prefill_chunk"]


def test_preempted_before_its_first_token_records_one_event():
    from gllm_tpu.obs.steptrace import TRACE
    eng = HostEngine(maxp=6)
    mark = TRACE.mark()
    seq = eng._allocate_seq(list(range(10)),
                            SamplingParams(max_tokens=3, **GREEDY))
    eng.add_seq(seq)
    eng.step()
    t_first_sched = seq.first_sched_time
    assert eng.scheduler._preempt_one(set())    # its chunk is thrown away
    assert seq.num_computed_tokens == 0
    while eng.has_unfinished:
        eng.step()
    (ev,) = _first_tokens(mark)
    # the clock of its first schedule stands; the prompt went through the
    # device in three steps, one of them twice over
    assert seq.first_sched_time == t_first_sched
    assert ev["chunks"] == 3 and ev["seq_id"] == seq.seq_id
    assert ev["t_first_sched"] == pytest.approx(
        t_first_sched - TRACE.t0, abs=2e-6)
    (rec,) = eng.spans.spans()
    assert [p["ph"] for p in rec["phases"]].count("prefill_chunk") == 3


@pytest.fixture
def served():
    from gllm_tpu.engine.serving_engine import ServingEngine
    eng = HostEngine(maxp=6)
    serving = ServingEngine(eng)
    yield eng, serving
    serving.shutdown()


def test_streamed_request_records_once_at_the_flush_with_all_six_stages(
        served):
    from gllm_tpu.obs.steptrace import TRACE
    eng, serving = served
    mark = TRACE.mark()
    h = _handler(serving)
    t_body = time.monotonic()
    handle = serving.submit(list(range(10)),
                            SamplingParams(max_tokens=5, **GREEDY),
                            received_t=t_body)
    h._stream(handle, lambda text, fin: {"choices": [{"text": text,
                                                      "finish_reason": fin}]})
    assert h.wfile.getvalue().count(b'"choices"') == 5
    (ev,) = _first_tokens(mark)
    assert {k for k in ev if k.endswith("_ms")} == set(STAGES) | {
        "total_ms"}
    assert all(ev[k] >= 0 for k in STAGES)
    assert sum(ev[k] for k in STAGES) == pytest.approx(ev["total_ms"],
                                                       abs=4e-3)
    assert ev["t_received"] == pytest.approx(t_body - TRACE.t0, abs=2e-6)
    assert (ev["chunks"], ev["prompt_tokens"]) == (2, 10)
    # the event's own instant is the flush: the end of its last stage
    assert ev["t"] >= ev["t_token"]
    assert ev["t"] - ev["t_received"] == pytest.approx(
        ev["total_ms"] / 1e3, abs=2e-3)
    # the tree of the finished request, from the same stamps
    deadline = time.monotonic() + 10
    while not eng.spans.spans():
        assert time.monotonic() < deadline
        time.sleep(0.005)
    (rec,) = eng.spans.spans()
    phs = [p["ph"] for p in rec["phases"]]
    assert sorted(phs) == sorted(["parse", "intake", "queued",
                                  "prefill_chunk", "prefill_chunk",
                                  "handover", "emit", "decode"])
    assert phs[:3] == ["parse", "intake", "queued"]
    assert rec["t0"] == t_body
    by = {p["ph"]: p for p in rec["phases"]}
    for name, field in (("parse", "parse_ms"), ("intake", "intake_ms"),
                        ("queued", "queue_ms"),
                        ("handover", "handover_ms"), ("emit", "emit_ms")):
        assert by[name]["dur_ms"] == pytest.approx(ev[field], abs=2e-3)
    assert by["decode"]["tokens"] == 4


def test_unstreamed_request_records_once_where_the_chunk_is_taken(served):
    from gllm_tpu.obs.steptrace import TRACE
    eng, serving = served
    mark = TRACE.mark()
    h = _handler(serving)
    handle = serving.submit([1, 2, 3], SamplingParams(max_tokens=4,
                                                      **GREEDY),
                            received_t=time.monotonic())
    got = h._collect(handle)
    assert got["finish"] == "length"
    assert got["usage"]["completion_tokens"] == 4
    (ev,) = _first_tokens(mark)
    # nothing is flushed for the token: no ``emit``, never a 0
    assert {k for k in ev if k.endswith("_ms")} == (
        set(STAGES) - {"emit_ms"}) | {"total_ms"}
    assert sum(ev[k] for k in STAGES if k in ev) == pytest.approx(
        ev["total_ms"], abs=4e-3)
    # a second request, submitted with no front end's stamp: no ``parse``
    handle = serving.submit([4, 5, 6], SamplingParams(max_tokens=1,
                                                      **GREEDY))
    h._collect(handle)
    evs = _first_tokens(mark)
    assert len(evs) == 2
    assert "parse_ms" not in evs[1] and "t_received" not in evs[1]
    assert "intake_ms" in evs[1]
    # one token: its tree had closed before the first token left; the
    # hand-over is on it all the same, and there is no decode to roll up
    rec = [r for r in eng.spans.spans() if r["seq_id"] == handle.seq_id][-1]
    assert [p["ph"] for p in rec["phases"]] == [
        "intake", "queued", "prefill_chunk", "handover"]


def test_prefix_probe_event_carries_its_wall_time():
    from gllm_tpu.obs.steptrace import TRACE
    eng = HostEngine(maxp=16, prefix=True)
    mark = TRACE.mark()
    eng.generate([list(range(12))], max_tokens=2)
    eng.generate([list(range(12)) + [40, 41]], max_tokens=2)
    probes = TRACE.events(since=mark, kinds=["prefix"])
    assert [p["hit_tokens"] for p in probes] == [0, 12]
    assert all(isinstance(p["ms"], float) and 0 <= p["ms"] < 1e3
               for p in probes)
    firsts = _first_tokens(mark)
    assert [e["cached_tokens"] for e in firsts] == [0, 12]
    s = summarize(TRACE.events(since=mark))
    assert s["prefix"]["match_ms"] == pytest.approx(
        sum(p["ms"] for p in probes), abs=2e-3)
    assert s["first_token"]["requests"] == 2
    assert set(s["first_token"]["mean_ms"]) == {"queue_ms", "compute_ms",
                                                "total_ms"}


# ---- chrome_trace schema ---------------------------------------------------

def test_chrome_trace_schema_and_phase_reconstruction():
    tr = StepTrace(capacity=16)
    _step_event(tr, "prefill", 0.050, 2.0, 3.0, 1.0, 4.0, wall=12.0,
                more={"output": 0.0015, "deliver": 0.0005,
                      "intake": 0.0002})
    spans = [{"seq_id": 7, "t0": 100.0, "t1": 100.2, "reason": "stop",
              "prompt_tokens": 5, "output_tokens": 3,
              "phases": [{"ph": "parse", "t": 100.0, "dur_ms": 2.0},
                         {"ph": "intake", "t": 100.002, "dur_ms": 6.0},
                         {"ph": "queued", "t": 100.008, "dur_ms": 2.0},
                         {"ph": "prefill_chunk", "t": 100.011,
                          "dur_ms": 30.0, "tokens": 5},
                         {"ph": "handover", "t": 100.042, "dur_ms": 2.0},
                         {"ph": "emit", "t": 100.044, "dur_ms": 0.5},
                         {"ph": "decode", "t": 100.042,
                          "dur_ms": 150.0, "tokens": 2,
                          "tpot_ms": 75.0}]}]
    doc = chrome_trace(tr.events(), spans, span_t0=100.0)
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert e["ph"] in ("X", "M")
        assert "name" in e and "pid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
    xs = [e for e in evs if e["ph"] == "X"]
    eng = [e for e in xs if e["pid"] == 1]
    req = [e for e in xs if e["pid"] == 2]
    # engine phase slices: schedule..collect are contiguous and span
    # exactly step_wall, ending at the event's t
    by_name = {e["name"]: e for e in eng}
    order = ["prefill:schedule", "prefill:build", "prefill:dispatch",
             "prefill:deliver", "prefill:wait", "prefill:collect"]
    present = [n for n in order if n in by_name]
    assert present[0] == "prefill:schedule"
    first = by_name[present[0]]
    last = by_name[present[-1]]
    span_us = (last["ts"] + last["dur"]) - first["ts"]
    assert span_us == pytest.approx(12.0 * 1e3, rel=0.10)
    assert last["ts"] + last["dur"] == pytest.approx(0.050 * 1e6, abs=2)
    # the host's tracks only: what the device did is the profiler's to say
    assert not any(n.endswith(":device") for n in by_name)
    # what the event carries from before its schedule began lies before
    # it, in the order the loop ran it: output, intake; the previous
    # step's deliver runs at the seam, between dispatch and wait
    before = [by_name[f"prefill:{n}"] for n in ("output", "intake")]
    assert before[-1]["ts"] + before[-1]["dur"] \
        == pytest.approx(first["ts"], abs=2)
    assert [b["ts"] for b in before] == sorted(b["ts"] for b in before)
    slices = [by_name[n] for n in order]
    for a, b in zip(slices, slices[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=2)
    # request track: root slice + children on tid 7
    assert all(e["tid"] == 7 for e in req)
    names = {e["name"] for e in req}
    # the request track draws every child the tree has: the stages up
    # to the first token and the one rolled-up decode, with its meta
    assert {"parse", "intake", "queued", "prefill_chunk", "handover",
            "emit", "decode"} <= names
    (dec,) = [e for e in req if e["name"] == "decode"]
    assert dec["args"] == {"tokens": 2, "tpot_ms": 75.0}
    assert dec["ts"] == pytest.approx(0.042 * 1e6, abs=2)
    assert any(n.startswith("request 7") for n in names)
    json.dumps(doc)                                   # serializable


# ---- the phase clock -------------------------------------------------------

class _CountingAnnotation:
    made = []

    def __init__(self, name, **args):
        _CountingAnnotation.made.append((name, args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_phase_adds_to_the_open_dict_and_makes_no_annotation(monkeypatch):
    """No capture running: a phase is two clock reads and a dict add. It
    constructs no annotation object; take_phases hands the dict over and
    opens an empty one; add=False times a nested span without counting
    it twice; stop() is idempotent."""
    monkeypatch.setattr(obs_spans, "_annotation", _CountingAnnotation)
    _CountingAnnotation.made.clear()
    assert not obs_spans.capturing()
    take_phases()
    with phase("build", step=3):
        time.sleep(0.002)
    with phase("build"):
        pass
    with phase("dispatch") as outer:
        with phase("first_use", add=False) as inner:
            time.sleep(0.001)
    sched = phase("schedule").start()
    sched.stop()
    first = sched.seconds
    sched.stop()
    assert sched.seconds == first
    ph = take_phases()
    assert set(ph) == {"build", "dispatch", "schedule"}
    assert ph["build"] >= 0.002 and ph["dispatch"] >= inner.seconds > 0
    assert outer.seconds == ph["dispatch"]
    assert take_phases() == {}
    assert _CountingAnnotation.made == []
    # ... and with one running, every phase is also an annotation
    monkeypatch.setattr(obs_spans, "_capturing", True)
    with phase("wait"):
        pass
    with phase("dispatch", step=7, rows=2, tokens=2):
        pass
    assert _CountingAnnotation.made == [
        ("gllm:wait", {}),
        ("gllm:dispatch", {"step": 7, "rows": 2, "tokens": 2})]


@pytest.mark.parametrize("how", ["sleeps", "spins"])
def test_phase_reads_the_threads_cpu_time_beside_the_wall(how):
    """A phase that blocks reads wall >> CPU, one that computes reads
    them equal; both land in the two per-phase counters, whose
    difference is what ``engine.interp_wait_ms_per_step`` reads."""
    wall, cpu = obs_spans._phase_counters("output")
    w0, c0 = wall.get(), cpu.get()
    with phase("output") as ph:
        if how == "sleeps":
            time.sleep(0.05)
        else:
            t_end = time.thread_time() + 0.05
            while time.thread_time() < t_end:
                pass
    take_phases()
    assert wall.get() - w0 == pytest.approx(ph.seconds)
    assert cpu.get() - c0 == pytest.approx(ph.cpu_seconds)
    assert ph.seconds >= 0.05
    if how == "sleeps":
        assert ph.cpu_seconds < 0.01
    else:
        # alone on its core the two agree; a loaded test host may take
        # the core away, which only ever makes the wall longer
        assert 0.05 <= ph.cpu_seconds <= ph.seconds + 1e-4
    # a nested span (add=False) is in its parent's time: not counted
    wall_fu, _ = obs_spans._phase_counters("first_use")
    f0 = wall_fu.get()
    with phase("first_use", add=False):
        pass
    assert wall_fu.get() == f0


def _prom(rows):
    return "".join(f"{name}{labels} {value}\n"
                   for name, labels, value in rows)


def _interp_wait_run(wall, cpu, steps):
    """A run's two /metrics texts: zero at the first end, the given
    seconds by phase and dispatches at the second."""
    def text(scale):
        rows = [("gllm_sampler_program_total", '{kind="greedy"}',
                 steps * scale)]
        for clock, by_phase in (("wall", wall), ("cpu", cpu)):
            rows += [(f"gllm_engine_phase_{clock}_seconds_total",
                      f'{{phase="{p}"}}', v * scale)
                     for p, v in by_phase.items()]
        return _prom(rows)
    return {"prom0": text(0), "prom1": text(1)}


def test_interp_wait_metric_reads_wall_minus_cpu_over_the_host_phases():
    sys.path.insert(0, os.path.join(REPO, "perfbench"))
    try:
        from run import load_module
        read = load_module("layer_metrics",
                           "engine.interp_wait_ms_per_step").read
    finally:
        sys.path.remove(os.path.join(REPO, "perfbench"))
    host = {"intake": 0.02, "schedule": 0.2, "build": 0.9,
            "dispatch": 0.5, "output": 0.6, "deliver": 0.4}
    blocked = {"wait": 18.0, "readback": 0.1, "idle": 3.0}
    # 1000 dispatches; the thread ran for all of its host time but 3 s
    # of ``build`` and 0.2 s of ``schedule``: 3.2 ms a step. The time
    # blocked in wait / readback / idle is not the interpreter's.
    cpu = dict(host, build=host["build"], **{k: 0.001 for k in blocked})
    wall = dict(host, build=host["build"] + 3.0,
                schedule=host["schedule"] + 0.2, **blocked)
    assert read(_interp_wait_run(wall, cpu, 1000)) \
        == pytest.approx(3.2)
    assert read(_interp_wait_run(cpu, cpu, 1000)) == pytest.approx(0.0)
    # a program without the counters (the parent), or no /metrics read
    parent = {"prom0": _prom([("gllm_sampler_program_total", "", 0)]),
              "prom1": _prom([("gllm_sampler_program_total", "", 500)])}
    assert read(parent) is None
    assert read({"prom0": None, "prom1": None}) is None


def test_obs_imports_no_jax_until_the_first_capture():
    """``gllm_tpu/obs`` stays importable without jax: the annotation
    class is imported by set_capture(True), nowhere else."""
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('spans', "
        f"{os.path.join(REPO, 'gllm_tpu', 'obs', 'spans.py')!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "sys.modules['spans'] = m\n"
        "spec.loader.exec_module(m)\n"
        "with m.phase('schedule'):\n"
        "    pass\n"
        "assert 'schedule' in m.take_phases()\n"
        "assert 'jax' not in sys.modules, 'phase() imported jax'\n"
        "m.set_capture(True)\n"
        "assert 'jax' in sys.modules and m.capturing()\n"
        "m.set_capture(False)\n"
        "assert not m.capturing()\n")
    proc = subprocess.run([sys.executable, "-c", code], text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_step_phases_fields():
    f = step_phases({"t_enter": 12.5, "intake": 0.0001, "schedule": 0.001,
                     "build": 0.002, "dispatch": 0.003, "wait": 0.020,
                     "readback": 0.0005, "output": 0.0007,
                     "deliver": 0.0003, "idle": 0.05})
    assert f["ph"] == {"intake": 0.1, "schedule": 1.0, "build": 2.0,
                       "dispatch": 3.0, "output": 0.7, "deliver": 0.3,
                       "collect": 20.5}
    assert (f["wait_ms"], f["readback_ms"], f["idle_ms"]) \
        == (20.0, 0.5, 50.0)
    # a reader that sums ``ph`` without ``collect`` reads host work only
    assert set(f["ph"]) - {"collect"} == set(HOST_PHASES)
    assert set(HOST_PHASES) | {"wait", "readback", "idle"} \
        == set(ENGINE_PHASES)


# ---- engine e2e (dummy weights, CPU) ---------------------------------------

TINY_MODEL = dict(architecture="LlamaForCausalLM", vocab_size=256,
                  hidden_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, head_dim=16, intermediate_size=128,
                  max_position=256)


def make_llm(**over):
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.models.config import ModelConfig
    cfg = EngineConfig(load_format="dummy", dtype="float32",
                       max_model_len=128, max_num_seqs=8,
                       scheduler=SchedulerConfig(max_prefill_tokens=64,
                                                 max_decode_seqs=8),
                       cache=CacheConfig(page_size=4, num_pages=128))
    mutate = over.pop("_mutate", None)
    for k, v in over.items():
        setattr(cfg, k, v)
    if mutate is not None:
        mutate(cfg)
    cfg.validate()
    return LLM(config=cfg, model_cfg=ModelConfig(**TINY_MODEL))


GREEDY = dict(temperature=0.0, ignore_eos=True)


def test_sync_engine_phase_breakdown_and_spans():
    llm = make_llm()
    from gllm_tpu.obs.steptrace import TRACE
    mark = TRACE.mark()
    outs = llm.generate(prompt_token_ids=[[3, 5, 7, 9], [11, 13]],
                        sampling_params=[
                            SamplingParams(max_tokens=6, **GREEDY),
                            SamplingParams(max_tokens=4, **GREEDY)])
    assert all(o.finish_reason == "length" for o in outs)
    steps = [e for e in TRACE.events(since=mark)
             if e["kind"] in ("prefill", "decode", "fused_block")]
    assert steps, "no step events recorded"
    tot_ph = tot_wall = tot_all = 0.0
    for e in steps:
        assert set(e["ph"]) <= set(HOST_PHASES) | {"collect"}
        assert {"schedule", "build", "dispatch", "collect"} <= set(e["ph"])
        assert e["ph"]["collect"] == pytest.approx(
            e["wait_ms"] + e["readback_ms"], abs=2e-3)
        assert e["step_wall_ms"] > 0
        # what engine.host_ms_per_step sums (perfbench/run.py): the
        # phases other than collect, every one of the closed vocabulary;
        # those of the step itself lie inside its wall
        host = {k: ms for k, ms in e["ph"].items() if k != "collect"}
        assert set(host) <= set(ENGINE_PHASES)
        assert sum(host[k] for k in ("schedule", "build", "dispatch")) \
            <= e["step_wall_ms"] + 0.005
        # the phases of the step itself (schedule-start → collect-end);
        # a step launched prepared (from the collect of the step before
        # it) has that step's output inside its wall as well
        ph_sum = sum(e["ph"][k] for k in ("schedule", "build",
                                          "dispatch", "collect"))
        if e.get("prepared"):
            ph_sum += e["ph"].get("output", 0.0)
        # never exceed the step wall (small scheduling jitter allowed);
        # the aggregate invariant below is the 10% criterion
        assert ph_sum <= e["step_wall_ms"] * 1.10 + 0.5
        tot_ph += ph_sum
        tot_wall += e["step_wall_ms"]
        tot_all += sum(e["ph"].values())
    # synchronous engine (no overlap): phase sums reconstruct the
    # measured step wall within 10%
    assert tot_ph == pytest.approx(tot_wall, rel=0.10)
    # every millisecond has a name: all phases of all events add up to
    # the loop's wall from the first step's start to the last one's end
    # (the last step's own output rides with no later event)
    loop_ms = (steps[-1]["t"] - steps[0]["t"]) * 1e3 \
        + steps[0]["step_wall_ms"]
    assert tot_all == pytest.approx(loop_ms, rel=0.10)
    assert any("output" in e["ph"] for e in steps[1:])
    s = summarize(steps)
    assert s["host_ms_by_phase"] is not None
    # span trees: one completed tree per request, none left open
    # (per-ENGINE ring: seq_ids restart per LLM, so each engine owns one)
    assert llm.spans.open_count == 0
    recs = {r["seq_id"]: r for r in llm.spans.spans()}
    assert len(recs) == 2
    for r in recs.values():
        assert r["reason"] == "length"
        phs = [p["ph"] for p in r["phases"]]
        # no front end: the tree opens with the wait for the schedule;
        # then a child a prompt chunk and ONE decode for all the rest
        assert phs[0] == "queued"
        assert phs.count("prefill_chunk") == 1
        assert phs.count("decode") == 1
        (dec,) = [p for p in r["phases"] if p["ph"] == "decode"]
        assert dec["tokens"] == r["output_tokens"] - 1
        assert dec["t"] + dec["dur_ms"] / 1e3 == pytest.approx(r["t1"])
        assert r["t1"] > r["t0"]
    # ... and one first_token event a request, written at the collect
    firsts = TRACE.events(since=mark, kinds=["first_token"])
    assert sorted(e["seq_id"] for e in firsts) == sorted(recs)
    for e in firsts:
        assert {"queue_ms", "compute_ms"} == {
            k for k in e if k.endswith("_ms")} - {"total_ms"}
        assert e["total_ms"] == pytest.approx(
            e["queue_ms"] + e["compute_ms"], abs=2e-3)
        assert e["chunks"] == 1 and "t_received" not in e


def test_fused_engine_rolls_its_blocks_up_into_one_decode_span():
    """A fused block's k / k_exec / dead_substeps are on its
    ``fused_block`` step event; the request's tree gets one ``decode``
    child for all of them, and the blocks make no call into
    SpanTrace."""
    from gllm_tpu.obs.steptrace import TRACE
    llm = make_llm(overlap_scheduling=True, multi_step_decode=4)
    calls = _spy_on(llm.spans)
    mark = TRACE.mark()
    outs = llm.generate(prompt_token_ids=[[2, 4, 6, 8]],
                        sampling_params=SamplingParams(max_tokens=12,
                                                       **GREEDY))
    assert outs[0].num_output_tokens == 12
    (rec,) = llm.spans.spans()
    assert [p["ph"] for p in rec["phases"]] == ["queued",
                                                "prefill_chunk", "decode"]
    assert rec["phases"][-1]["tokens"] == 11
    assert llm.spans.open_count == 0
    blocks = TRACE.events(since=mark, kinds=["fused_block"])
    assert blocks and all(b["k"] >= 1 for b in blocks)
    # the prefill step's chunk, then the finish with its roll-up: one
    # call each, whatever the number of blocks in between
    assert [c[0] for c in calls] == ["begin", "event", "close", "finish"]


def _dp2(cfg):
    from gllm_tpu.config import ParallelConfig
    cfg.parallel = ParallelConfig(dp=2)


def _dp2_pipelined(cfg):
    _dp2(cfg)
    cfg.overlap_scheduling = cfg.pipelined_loop = True


STEP_PATHS = {
    "sync": {},
    "fused": dict(overlap_scheduling=True, multi_step_decode=4),
    "pipelined": dict(overlap_scheduling=True, pipelined_loop=True),
    "dp": dict(_mutate=_dp2),
    "dp_pipelined": dict(_mutate=_dp2_pipelined),   # dp super-steps
}


@pytest.mark.parametrize("path", sorted(STEP_PATHS))
def test_every_phase_of_the_vocabulary_on_every_step_path(path):
    """Served through the engine loop, every step path (sync, fused
    blocks, the pipelined loop, the stacked dp program) names all of its
    time: each step event has the split of its collect, and over a
    request the events carry every phase of the closed vocabulary."""
    from gllm_tpu.engine.serving_engine import ServingEngine
    from gllm_tpu.obs.steptrace import TRACE
    over = dict(STEP_PATHS[path])
    mutate = over.pop("_mutate", None)
    llm = make_llm(**over) if mutate is None else make_llm(_mutate=mutate)
    eng = ServingEngine(llm)
    try:
        time.sleep(0.12)                # the loop idles: nothing to do
        mark = TRACE.mark()
        handles = [eng.submit([3, 5, 7, 9 + i],
                              SamplingParams(max_tokens=10, **GREEDY))
                   for i in range(3)]
        for h in handles:
            assert [c for c in h][-1].finish_reason == "length"
    finally:
        eng.shutdown()
    steps = [e for e in TRACE.events(since=mark)
             if isinstance(e.get("ph"), dict)]
    assert steps
    if path == "fused":
        assert any(e["kind"] == "fused_block" for e in steps)
    if path.startswith("dp"):
        assert all(e.get("dp") for e in steps)
    seen = set()
    for e in steps:
        assert set(e["ph"]) <= set(HOST_PHASES) | {"collect"}
        assert e["ph"]["collect"] == pytest.approx(
            e["wait_ms"] + e.get("readback_ms", 0.0), abs=2e-3)
        seen |= set(e["ph"]) - {"collect"}
        seen |= {k[:-3] for k in ("wait_ms", "readback_ms", "idle_ms")
                 if k in e}
    assert seen == set(ENGINE_PHASES), sorted(set(ENGINE_PHASES) - seen)
    assert "idle_ms" in steps[0] and steps[0]["idle_ms"] >= 50


def test_first_use_ms_on_the_compile_event_agrees_with_the_counter():
    from gllm_tpu.obs import metrics as obs_metrics
    from gllm_tpu.obs.steptrace import TRACE
    llm = make_llm()
    ctr = obs_metrics.REGISTRY.get("gllm_step_first_use_seconds_total")
    before = sum(v for _, _, v in ctr.samples())
    mark = TRACE.mark()
    llm.generate(prompt_token_ids=[[3, 5, 7, 9]],
                 sampling_params=SamplingParams(max_tokens=4, **GREEDY))
    compiles = TRACE.events(since=mark, kinds=["compile"])
    assert len(compiles) >= 2           # a prefill and a decode signature
    for e in compiles:
        assert e["first_use_ms"] > 0
        assert e["source"] in ("compiled", "cache")
        assert {"dispatch", "tokens_pad", "seqs_pad", "pages_pad"} <= set(e)
    grown = sum(v for _, _, v in ctr.samples()) - before
    assert grown == pytest.approx(
        sum(e["first_use_ms"] for e in compiles) / 1e3, abs=1e-3)
    # the same signatures again: no first use, no event, no growth
    mark = TRACE.mark()
    llm.generate(prompt_token_ids=[[3, 5, 7, 11]],
                 sampling_params=SamplingParams(max_tokens=4, **GREEDY))
    assert TRACE.events(since=mark, kinds=["compile"]) == []
    assert sum(v for _, _, v in ctr.samples()) - before \
        == pytest.approx(grown, abs=1e-9)


def test_tracing_off_is_byte_identical_and_records_nothing():
    prompts = [[3, 5, 7, 9], [2, 4, 6]]
    sps = [SamplingParams(max_tokens=8, **GREEDY) for _ in prompts]
    import dataclasses as dc
    want = [o.output_token_ids for o in make_llm().generate(
        prompt_token_ids=prompts,
        sampling_params=[dc.replace(s) for s in sps])]
    llm_off = make_llm(tracing=False)
    assert llm_off.tracing is False
    got = [o.output_token_ids for o in llm_off.generate(
        prompt_token_ids=prompts,
        sampling_params=[dc.replace(s) for s in sps])]
    assert got == want
    assert llm_off.spans.spans() == []
    assert llm_off.spans.open_count == 0


def test_terminal_paths_close_spans():
    """abort / deadline / quarantine all close the request's span tree
    with the terminal reason (no tree may leak open)."""
    from gllm_tpu.engine.serving_engine import ServingEngine
    from gllm_tpu.faults import FAULTS
    FAULTS.reset()
    llm = make_llm()
    eng = ServingEngine(llm)
    try:
        # abort mid-stream (the model may hit the length cap first on a
        # fast box — either way the span closes with the chunk's reason)
        ha = eng.submit([5, 6, 7], SamplingParams(max_tokens=5000,
                                                  **GREEDY))
        last = ha.chunks.get(timeout=60)      # at least one token flowed
        eng.abort(ha.seq_id)
        while last.finish_reason is None:
            last = ha.chunks.get(timeout=60)
        assert last.finish_reason in ("abort", "length")
        spans = llm.spans
        deadline = time.monotonic() + 10
        while not any(r["seq_id"] == ha.seq_id for r in spans.spans()):
            assert time.monotonic() < deadline, "span never closed"
            time.sleep(0.01)
        rec = [r for r in spans.spans() if r["seq_id"] == ha.seq_id][-1]
        assert rec["reason"] == last.finish_reason
        # deadline mid-generation
        hb = eng.submit([9, 9, 9], SamplingParams(max_tokens=10000,
                                                  **GREEDY),
                        deadline_s=0.25)
        for c in hb:
            last = c
        assert last.finish_reason == "deadline"
        rec = [r for r in spans.spans() if r["seq_id"] == hb.seq_id][-1]
        assert rec["reason"] == "deadline"
        # quarantine (injected step exception)
        FAULTS.arm("step_exception:0:1")
        hc = eng.submit([1, 2, 3], SamplingParams(max_tokens=8,
                                                  **GREEDY))
        for c in hc:
            last = c
        assert last.finish_reason == "error"
        recs = [r for r in spans.spans() if r["seq_id"] == hc.seq_id]
        if recs:                        # quarantined after admission
            assert recs[-1]["reason"] == "error"
        assert spans.open_count == 0
    finally:
        FAULTS.reset()
        eng.shutdown()


# ---- HTTP surface ----------------------------------------------------------

def _drive_completion(port):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions", body=json.dumps({
        "prompt": [5, 6, 7, 8], "max_tokens": 6, "temperature": 0,
        "ignore_eos": True}),
        headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 200, r.read()
    r.read()
    conn.close()


@pytest.fixture(scope="module")
def trace_server():
    from gllm_tpu.entrypoints.api_server import serve
    llm = make_llm()
    httpd = serve(llm, "127.0.0.1", 0, served_model="trace-smoke")
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    # drive one request so the steptrace ring has content (the span
    # ring is cleared per test — span-needing tests drive their own)
    _drive_completion(port)
    yield port
    httpd.shutdown()
    httpd.state.engine.shutdown()


def _req(port, method, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request(method, path)
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, body


def test_trace_endpoint_serves_chrome_json(trace_server):
    _drive_completion(trace_server)     # fresh spans (ring cleared per test)
    status, body = _req(trace_server, "GET", "/trace")
    assert status == 200
    doc = json.loads(body)
    evs = doc["traceEvents"]
    pids = {e["pid"] for e in evs if e["ph"] == "X"}
    assert 1 in pids and 2 in pids      # engine + request tracks
    assert any(e["name"].endswith(":collect") for e in evs
               if e["ph"] == "X")


def test_steptrace_kind_filter(trace_server):
    status, body = _req(trace_server, "GET", "/steptrace?kind=prefill")
    assert status == 200
    d = json.loads(body)
    assert d["events"] and all(e["kind"] == "prefill"
                               for e in d["events"])
    status, body = _req(trace_server, "GET",
                        "/steptrace?kind=prefill,decode")
    kinds = {e["kind"] for e in json.loads(body)["events"]}
    assert kinds <= {"prefill", "decode"}


def test_phase_and_deliver_counters_in_metrics(trace_server):
    """/metrics carries the engine thread's seconds by phase on both
    clocks (one label: ``phase``) and how the steps' outputs left."""
    def sample(text, name, label):
        for line in text.splitlines():
            if line.startswith(name + label + " "):
                return float(line.rsplit(" ", 1)[1])
        return None

    before = _req(trace_server, "GET", "/metrics")[1].decode()
    _drive_completion(trace_server)
    status, body = _req(trace_server, "GET", "/metrics")
    assert status == 200
    text = body.decode()
    for name in ENGINE_PHASES:
        label = f'{{phase="{name}"}}'
        wall = sample(text, "gllm_engine_phase_wall_seconds_total", label)
        cpu = sample(text, "gllm_engine_phase_cpu_seconds_total", label)
        assert wall is not None and cpu is not None, name
        assert 0 <= cpu <= wall + 1e-3, (name, cpu, wall)
    # idling is all wall and next to no CPU
    assert sample(text, "gllm_engine_phase_cpu_seconds_total",
                  '{phase="idle"}') \
        < 0.5 * sample(text, "gllm_engine_phase_wall_seconds_total",
                       '{phase="idle"}')
    grew = {when: sample(text, "gllm_deliver_total", f'{{when="{when}"}}')
            - (sample(before, "gllm_deliver_total",
                      f'{{when="{when}"}}') or 0.0)
            for when in ("after_dispatch", "flush")}
    # one request alone: every step but its last is handed over behind
    # the next one's dispatch, the last is flushed
    assert grew["flush"] == 1 and grew["after_dispatch"] >= 2


def _hist_count(name):
    from gllm_tpu.obs import metrics as obs_metrics
    return obs_metrics.REGISTRY.get(name).snapshot()[2]


def test_front_end_histograms_observe_per_request_and_per_chunk(
        trace_server):
    """gllm_http_admit_lag_seconds: once per request, whatever its form;
    gllm_http_emit_lag_seconds: once per stamped SSE chunk — a stream's
    first token and every eighth after it (a non-streamed reply writes
    no chunk)."""
    admit0 = _hist_count("gllm_http_admit_lag_seconds")
    emit0 = _hist_count("gllm_http_emit_lag_seconds")
    _drive_completion(trace_server)             # not streamed
    assert _hist_count("gllm_http_admit_lag_seconds") == admit0 + 1
    assert _hist_count("gllm_http_emit_lag_seconds") == emit0
    conn = http.client.HTTPConnection("127.0.0.1", trace_server,
                                      timeout=120)
    conn.request("POST", "/v1/completions", body=json.dumps({
        "prompt": [5, 6, 7, 8], "max_tokens": 18, "temperature": 0,
        "ignore_eos": True, "stream": True}),
        headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 200
    chunks = r.read().count(b'"choices"')
    conn.close()
    assert chunks == 18
    assert _hist_count("gllm_http_admit_lag_seconds") == admit0 + 2
    # tokens 1, 9 and 17 of the 18 carry the stamp
    assert _hist_count("gllm_http_emit_lag_seconds") == emit0 + 3
    status, body = _req(trace_server, "GET", "/metrics")
    assert b'gllm_http_emit_lag_seconds_bucket{le="5e-05"}' in body
    assert b'gllm_http_emit_lag_seconds_bucket{le="0.1"}' in body


def test_two_captures_leave_the_flag_clear_and_spans_in_the_trace(
        trace_server, tmp_path, monkeypatch):
    """/start_profile ... /stop_profile twice in one process: each
    answers with the server's time.monotonic(), the capture flag is set
    only in between, the phases are TraceAnnotations in the profiler's
    trace (Python tracer off), and /steptrace gives the ring's t0 on the
    same clock."""
    import glob
    import jax
    monkeypatch.setenv("GLLM_PROFILE_DIR", str(tmp_path))
    for _ in range(2):
        assert not obs_spans.capturing()
        t_before = time.monotonic()
        status, body = _req(trace_server, "POST", "/start_profile")
        assert status == 200, body
        started = json.loads(body)
        assert started["trace_dir"] == str(tmp_path)
        assert obs_spans.capturing()
        _drive_completion(trace_server)
        status, body = _req(trace_server, "POST", "/stop_profile")
        assert status == 200, body
        stopped = json.loads(body)
        assert not obs_spans.capturing()
        assert t_before <= started["t_monotonic"] \
            <= stopped["t_monotonic"] <= time.monotonic()
    status, body = _req(trace_server, "POST", "/stop_profile")
    assert json.loads(body)["status"] == "noop"
    newest = sorted(glob.glob(os.path.join(str(tmp_path), "**",
                                           "*.xplane.pb"),
                              recursive=True), key=os.path.getmtime)[-1]
    names = set()
    for plane in jax.profiler.ProfileData.from_file(newest).planes:
        for line in plane.lines:
            names |= {e.name for e in line.events}
    assert {"gllm:schedule", "gllm:build", "gllm:dispatch", "gllm:wait",
            "gllm:readback", "gllm:output", "gllm:deliver",
            "gllm:intake"} <= names, sorted(n for n in names
                                            if n.startswith("gllm"))
    # python_tracer_level 0: no Python call of any thread is traced
    assert not any(n.startswith("$") for n in names)
    d = json.loads(_req(trace_server, "GET", "/steptrace?kind=none")[1])
    assert 0 < d["t0"] <= time.monotonic()


def test_profile_oneshot_endpoint(trace_server, tmp_path, monkeypatch):
    monkeypatch.setenv("GLLM_PROFILE_DIR", str(tmp_path))
    status, body = _req(trace_server, "POST", "/profile?seconds=0.1")
    assert status == 200, body
    d = json.loads(body)
    assert d["status"] == "ok" and d["trace_dir"] == str(tmp_path)
    t_start, t_stop = d["t_monotonic"]
    assert 0.1 <= t_stop - t_start < 5 and not obs_spans.capturing()
    assert os.path.isdir(str(tmp_path))
    # artifact landed (jax profiler writes plugins/profile/<run>/)
    assert any(os.scandir(str(tmp_path)))
    status, body = _req(trace_server, "POST", "/profile?seconds=0")
    assert status == 400
    status, body = _req(trace_server, "POST", "/profile?seconds=bogus")
    assert status == 400
    # a legacy /stop_profile must NOT truncate an in-flight one-shot
    box = {}

    def oneshot():
        box["r"] = _req(trace_server, "POST", "/profile?seconds=4")

    th = threading.Thread(target=oneshot)
    th.start()
    # poll: before the capture starts /stop_profile is a harmless noop
    # (200); once the one-shot owns the profiler it must refuse (409)
    deadline = time.monotonic() + 3.0
    saw_409 = False
    while time.monotonic() < deadline:
        status, _ = _req(trace_server, "POST", "/stop_profile")
        if status == 409:
            saw_409 = True
            break
        time.sleep(0.1)
    th.join()
    assert saw_409, "stop_profile never refused during the one-shot"
    assert box["r"][0] == 200, box["r"][1]


# ---- dump CLI --------------------------------------------------------------

def test_dump_chrome_format_and_filters(tmp_path, capsys):
    from gllm_tpu.obs import dump
    tr = StepTrace(capacity=16)
    _step_event(tr, "decode", 0.010, 1.0, 1.0, 1.0, 1.0, wall=5.0)
    tr.record("compile", dispatch="step")
    p = tmp_path / "t.jsonl"
    tr.to_jsonl(str(p))
    assert dump.main([str(p), "--format", "chrome"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert any(e.get("name") == "decode:collect"
               for e in doc["traceEvents"])
    # kind/since filters drop events before formatting
    assert dump.main([str(p), "--kind", "compile", "--summary"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out[out.index("{"):])["compiles"] == 1
    assert dump.main([str(p), "--since", "2", "--summary"]) == 0
