"""Performance-attribution layer: request-scoped spans, the engine-loop
phase clock, and the Chrome-trace converter (stdlib only).

The steptrace ring alone says how long each engine iteration's *collect*
took, not where the host's wall clock went (schedule vs batch-build vs
dispatch vs collect). This module holds the pure-host pieces of the
attribution stack; what the DEVICE did in the same milliseconds is read
from a profiler capture, on whose clock the phases below also sit
(perfbench/host_gaps.py):

- :func:`first_token_stamps` / :class:`FirstToken` — a request's way
  to its first token as ONE list of ``time.monotonic()`` stamps, each
  taken where a stage ends (body read, intake put, add_seq, first
  schedule, first token, hand-over, flush); the stages are the
  differences of consecutive stamps, so they add up to the whole. One
  ``first_token`` event a request on the steptrace ring carries them
  (always on: per request, not per token or step);
- :class:`SpanTrace` — one span tree per request, built from the same
  stamps (parse → intake → queued → one child a prefill chunk →
  handover → emit → one rolled-up decode → detokenize → finish),
  completed trees held in a bounded ring like the steptrace. A step
  that carries no prompt chunk makes no call into it;
- :class:`phase` — the one timing primitive of the engine loop: a
  context manager that adds its wall time to the open step's phase dict
  (the steptrace ``ph`` field) and, only while a profiler capture runs,
  is also a ``jax.profiler.TraceAnnotation("gllm:<name>")`` so the same
  span sits on the device trace's clock;
- :func:`chrome_trace` — steptrace step events + request spans →
  Chrome trace-event JSON (Perfetto/chrome://tracing loadable): one
  track per engine phase, one per request. Shared by ``GET /trace``
  and ``python -m gllm_tpu.obs.dump --format chrome``.

Same design constraints as the rest of ``gllm_tpu/obs``: no jax import
(the annotation class is imported at the first capture, never before),
no device work, no new jit static arguments; every recorded number is
host arithmetic the engine already had. Span recording is gated by
``EngineConfig.tracing`` (default ON; token streams are byte-identical
either way, tests/test_tracing.py).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional

from gllm_tpu.obs import metrics as _metrics
from gllm_tpu.obs.steptrace import TRACE

__all__ = ["SpanTrace", "SPANS", "chrome_trace", "SPAN_PHASES",
           "ENGINE_PHASES", "HOST_PHASES", "phase", "take_phases",
           "step_phases", "set_capture", "capturing",
           "FIRST_TOKEN_STAGES", "first_token_stamps", "FirstToken"]

# The stages of a request's way to its first token, each named for the
# stamp that ends it (docs/observability.md stage table): the
# ``first_token`` event carries ``<stage>_ms``.
FIRST_TOKEN_STAGES = ("parse", "intake", "queue", "compute", "handover",
                      "emit")
# Span phase taxonomy (docs/observability.md span-phase catalog): the
# child spans a request tree may carry, in the order a request passes
# them. ``parse`` / ``intake`` / ``queued`` / ``handover`` / ``emit`` are
# the stages above, from the same stamps (``compute`` is drawn as its
# parts: one ``prefill_chunk`` = one step that carried a chunk of the
# prompt, dispatch → collect); ``decode`` = first token → finish, ONE
# rolled-up span at finish (``tokens``, ``tpot_ms``; what each decoding
# step did is on its ``decode`` / ``fused_block`` step event);
# ``detokenize`` = accumulated host detokenization/stream time (one
# rolled-up span at finish).
SPAN_PHASES = ("parse", "intake", "queued", "prefill_chunk", "handover",
               "emit", "decode", "detokenize")
_TREE_CHILD = {"parse": "parse", "intake": "intake", "queue": "queued",
               "handover": "handover", "emit": "emit"}

# Engine-loop phases (docs/observability.md phase catalog): the closed
# vocabulary of what the engine thread does with its time, each opened
# where the work is done. One pass of the serving loop, in order:
#   intake    ServingEngine._run_loop: intake drain (llm.add_seq),
#             _drain_push_work, _expire_deadlines
#   schedule  LLM.step fill pass: scheduler passes forming the batch/chain
#   build     runner: host work up to the jit call (drains, batch build)
#   dispatch  runner: the jit call and _start_host_copy; the FIRST use of
#             a step signature nests a ``first_use`` span inside it
#             (trace + lower + compile or cache read)
#   deliver   ServingEngine._run_loop at LLM.step's after_dispatch seam:
#             the PREVIOUS step's outputs — deliver_output (detokenise,
#             the handles' queues), the journal, _reap_aborted — so the
#             handler threads send them under this step's ``wait``; at
#             once (a flush) wherever the pass launches nothing
#   wait      runner.collect: blocked until the step's tokens are on the
#             host (the program, then the copy started at dispatch) — the
#             only phase in which an idle device is not the host's doing
#   readback  runner.collect: the step's other outputs (logprobs, finish
#             steps, speculation counts) to numpy, ready with the tokens
#   output    LLM.step after the collect: process_output, logprobs, stop
#             strings, _observe_outputs (the next schedule needs it)
#   idle      ServingEngine._run_loop: _wake.wait, nothing to do
# (the tuple keeps the order its readers have always had)
ENGINE_PHASES = ("intake", "schedule", "build", "dispatch", "wait",
                 "readback", "output", "deliver", "idle")
# What a step event's ``ph`` holds: the phases in which the HOST works,
# plus ``collect`` = wait + readback (the name the field has always had
# for the time blocked in runner.collect). ``wait`` / ``readback`` /
# ``idle`` ride beside it as ``wait_ms`` / ``readback_ms`` / ``idle_ms``:
# a reader that sums ``ph`` without ``collect`` keeps reading host time.
HOST_PHASES = ("intake", "schedule", "build", "dispatch", "output",
               "deliver")

# ---- the phase clock -------------------------------------------------------

# Where the engine thread's time went, by phase, on two clocks. The wall
# counter minus the CPU counter over the HOST_PHASES is time the thread
# spent in a phase without running: queued for the interpreter behind the
# handler threads, or descheduled (docs/observability.md#tracing). Over
# wait / readback / idle the difference is the blocking those phases are
# for.
_M_PHASE_WALL = _metrics.counter(
    "gllm_engine_phase_wall_seconds_total",
    "wall seconds the engine thread spent in each engine-loop phase",
    ("phase",))
_M_PHASE_CPU = _metrics.counter(
    "gllm_engine_phase_cpu_seconds_total",
    "CPU seconds of the engine thread itself (time.thread_time) inside "
    "each engine-loop phase", ("phase",))
_phase_children: Dict[str, tuple] = {}


def _phase_counters(name: str) -> tuple:
    """The (wall, cpu) counter children of one phase, bound once."""
    pair = _phase_children.get(name)
    if pair is None:
        pair = _phase_children[name] = (_M_PHASE_WALL.labels(phase=name),
                                        _M_PHASE_CPU.labels(phase=name))
    return pair


_capturing = False      # a profiler capture is running in this process
_annotation = None      # jax.profiler.TraceAnnotation, from the first capture
_tls = threading.local()


def set_capture(on: bool) -> None:
    """Process-wide switch, set by whoever starts and stops the profiler
    (api_server ``_profile`` / ``_profile_oneshot``): while on, every
    :class:`phase` is also a TraceAnnotation. jax is imported here, at
    the first capture, and nowhere else in ``gllm_tpu/obs``."""
    global _capturing, _annotation
    if on and _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    _capturing = bool(on)


def capturing() -> bool:
    return _capturing


def _open_phases() -> dict:
    try:
        return _tls.ph
    except AttributeError:
        _tls.ph = {}
        return _tls.ph


def take_phases() -> dict:
    """The calling thread's open phase dict ({name: seconds}), replaced
    by an empty one. The engine takes it when a step is dispatched and
    again when it is collected, so every second a phase measured lands in
    exactly one step event: what ran since the previous take — the
    previous step's ``output``, this pass's ``intake`` — rides with the
    step dispatched next, and the previous step's ``deliver`` (run at
    the seam, after the dispatch's take) with the same step's collect."""
    ph = _open_phases()
    _tls.ph = {}
    return ph


class phase:
    """``with phase("build", step=n):`` — time one engine-loop phase.

    Always: wall seconds added to the thread's open phase dict under
    ``name`` (``add=False`` for a span nested in another phase, which
    would count twice) and kept on ``.seconds``; the same wall seconds,
    and the thread's own CPU seconds beside them (``.cpu_seconds``,
    ``time.thread_time()``), added to the two phase counters. While a
    capture runs: also a ``TraceAnnotation("gllm:<name>", **args)``.
    With none running a phase is four clock reads, one dict add and two
    counter adds, and constructs nothing of jax. ``start()`` / ``stop()``
    open and close it by hand where the phase ends in several places of
    a loop body; ``stop`` is idempotent.
    """

    __slots__ = ("name", "args", "add", "t0", "c0", "seconds",
                 "cpu_seconds", "_ann", "_open")

    def __init__(self, name: str, add: bool = True, **args):
        self.name = name
        self.args = args
        self.add = add
        self.t0 = None
        self.c0 = 0.0
        self.seconds = 0.0
        self.cpu_seconds = 0.0
        self._ann = None
        self._open = False

    def __enter__(self):
        if _capturing:
            self._ann = _annotation("gllm:" + self.name, **self.args)
            self._ann.__enter__()
        self._open = True
        # the wall reading encloses the CPU reading, so wall - CPU >= 0
        self.t0 = time.monotonic()
        self.c0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    start = __enter__

    def stop(self) -> None:
        if not self._open:
            return
        self._open = False
        self.cpu_seconds = time.thread_time() - self.c0
        self.seconds = time.monotonic() - self.t0
        if self.add:
            ph = _open_phases()
            ph[self.name] = ph.get(self.name, 0.0) + self.seconds
            wall, cpu = _phase_counters(self.name)
            wall.inc(self.seconds)
            cpu.inc(self.cpu_seconds)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


def step_phases(phases: dict) -> dict:
    """Step-event fields from a step's phase dict (seconds): ``ph`` (ms,
    :data:`HOST_PHASES` that occurred plus ``collect`` = wait + readback)
    and ``wait_ms`` / ``readback_ms`` / ``idle_ms`` beside it."""
    ms = {k: round(v * 1e3, 3) for k, v in phases.items()
          if k in ENGINE_PHASES}
    out = {"ph": {k: ms[k] for k in HOST_PHASES if k in ms}}
    out["ph"]["collect"] = round(ms.get("wait", 0.0)
                                 + ms.get("readback", 0.0), 3)
    for k in ("wait", "readback", "idle"):
        if k in ms:
            out[k + "_ms"] = ms[k]
    return out


# ---- a request's way to its first token ------------------------------------

def first_token_stamps(seq, t_deliver: float = 0.0) -> list:
    """The ONE list of stamps a request has on its way to its first
    token, as ``[(name, time.monotonic())]`` in the order they were
    taken: the first entry is where the account starts (``received``:
    the front end's body read; ``arrival``: the allocation, for a
    request no serving engine submitted), every later one is named for
    the stage of :data:`FIRST_TOKEN_STAGES` that ends at it (the last,
    ``emit``, is the recording thread's to add: :class:`FirstToken`). A stamp
    not taken is left out, so a stage not passed is absent and the next
    one runs from the last stamp there is: the differences of
    consecutive entries always add up to last minus first."""
    if seq.submitted_t:
        marks = (("received", seq.received_t),
                 ("parse", seq.submitted_t), ("intake", seq.admitted_t))
    else:
        marks = (("arrival", seq.arrival_time),)
    marks += (("queue", seq.first_sched_time),
              ("compute", seq.first_token_time),
              ("handover", t_deliver))
    return [(name, t) for name, t in marks if t]


def _stage_spans(stamps: list) -> list:
    """``(stage, start, seconds)`` of every consecutive pair."""
    return [(name, t0, t1 - t0)
            for (_, t0), (name, t1) in zip(stamps, stamps[1:])]


class FirstToken:
    """A request's stamps up to its first token and the counts beside
    them, frozen where they are taken (``deliver_output`` on the engine
    thread for a served request, whose first chunk carries this to the
    thread that takes it; ``_observe_outputs`` for one no serving engine
    submitted). ``record`` adds the flush (``t_emit``; none where nothing
    is flushed for the token: an unstreamed reply) and writes the
    request's ONE ``first_token`` event onto the steptrace ring; with
    ``spans``, the ``handover`` and ``emit`` children onto its tree as
    well. ``chunks`` = steps that carried a chunk of the prompt (the one
    that sampled the token included), ``cached_tokens`` = the prefix hit
    of its last admission."""

    __slots__ = ("stamps", "fields", "spans")

    def __init__(self, seq, t_deliver: float = 0.0,
                 spans: Optional["SpanTrace"] = None):
        self.stamps = first_token_stamps(seq, t_deliver)
        self.fields = {"seq_id": seq.seq_id,
                       "prompt_tokens": seq.prompt_len,
                       "cached_tokens": seq.num_cached_tokens,
                       "chunks": seq.prefill_chunks + 1,
                       "passes_waited": seq.passes_waited}
        self.spans = spans

    def record(self, t_emit: float = 0.0) -> Optional[dict]:
        """Write the event, once; returns its fields."""
        stamps, self.stamps = self.stamps, None
        if stamps is None:
            return None
        if t_emit:
            stamps.append(("emit", t_emit))
        ev = self.fields
        for name, _, sec in _stage_spans(stamps):
            ev[name + "_ms"] = round(sec * 1e3, 3)
        ev["total_ms"] = round((stamps[-1][1] - stamps[0][1]) * 1e3, 3)
        t0, at = TRACE.t0, dict(stamps)
        for key, name in (("t_received", "received"),
                          ("t_first_sched", "queue"),
                          ("t_token", "compute")):
            if name in at:
                ev[key] = round(at[name] - t0, 6)
        TRACE.record("first_token", **ev)
        if self.spans is not None:
            self.spans.stages(ev["seq_id"], stamps, ("handover", "emit"))
        return ev


class SpanTrace:
    """Bounded per-request span trees.

    Open trees live in a dict keyed by seq_id (bounded by ``max_open``
    — beyond it new requests go untracked, counted in ``untracked``);
    ``finish`` moves a tree into a fixed-capacity completed ring.
    A tree caps its child-phase list at ``max_phases``; later events
    roll up into per-phase ``{n, ms}`` aggregates instead of growing
    without bound (a long prompt in small chunks can reach it; the
    decoding steps were never the tree's to hold one by one).
    """

    def __init__(self, capacity: Optional[int] = None,
                 max_open: Optional[int] = None,
                 max_phases: Optional[int] = None):
        if capacity is None:
            capacity = int(os.environ.get("GLLM_OBS_SPAN_CAP", "1024"))
        if max_open is None:
            max_open = int(os.environ.get("GLLM_OBS_SPAN_OPEN", "4096"))
        if max_phases is None:
            max_phases = int(os.environ.get("GLLM_OBS_SPAN_PHASES",
                                            "512"))
        if capacity <= 0 or max_open <= 0 or max_phases <= 0:
            raise ValueError("span bounds must be positive")
        self.capacity = capacity
        self.max_open = max_open
        self.max_phases = max_phases
        self._lock = threading.Lock()
        self._open: Dict[int, dict] = {}
        self._done: deque = deque(maxlen=capacity)
        self._finished = 0          # lifetime completed-span count
        self.untracked = 0          # begins refused by the open bound

    # ---- lifecycle ---------------------------------------------------------

    def begin(self, seq_id: int, stamps: list,
              prompt_tokens: int = 0) -> None:
        """Open a request tree at its first schedule, from the stamps
        it carries by then (:func:`first_token_stamps`): the tree starts
        at the first one and gets a child a stage passed (``parse``,
        ``intake``, ``queued``). Idempotent per seq_id."""
        with self._lock:
            if seq_id in self._open:
                return
            if len(self._open) >= self.max_open:
                self.untracked += 1
                return
            rec = {"seq_id": seq_id, "t0": stamps[0][1], "t1": None,
                   "reason": None, "prompt_tokens": prompt_tokens,
                   "output_tokens": 0, "phases": [], "agg": {}}
            self._open[seq_id] = rec
            self._stages_locked(rec, stamps, _TREE_CHILD)

    def stages(self, seq_id: int, stamps: list, only) -> None:
        """The stages ``only`` names, of a request's stamps, as child
        spans: onto its open tree, or onto its finished one (the first
        token of a short request leaves after its tree has closed)."""
        with self._lock:
            rec = self._open.get(seq_id)
            if rec is None:
                rec = next((r for r in reversed(self._done)
                            if r["seq_id"] == seq_id), None)
            if rec is not None:
                self._stages_locked(rec, stamps, only)

    def _stages_locked(self, rec, stamps, only) -> None:
        for name, t, sec in _stage_spans(stamps):
            if name in only and sec > 0:
                self._append_locked(rec, _TREE_CHILD[name], t, sec * 1e3,
                                    None)

    def event(self, seq_id: int, ph: str, t: float, dur_ms: float,
              **meta) -> None:
        """Append one child span (monotonic start ``t``, ``dur_ms``)
        to an open tree; silently dropped when the request is
        untracked (holes, bounded-out requests, tracing off)."""
        with self._lock:
            rec = self._open.get(seq_id)
            if rec is not None:
                self._append_locked(rec, ph, t, dur_ms, meta)

    def _append_locked(self, rec, ph, t, dur_ms, meta) -> None:
        if len(rec["phases"]) >= self.max_phases:
            agg = rec.setdefault("agg", {}).setdefault(
                ph, {"n": 0, "ms": 0.0})
            agg["n"] += 1
            agg["ms"] += dur_ms
            return
        ev = {"ph": ph, "t": t, "dur_ms": round(dur_ms, 3)}
        if meta:
            ev.update(meta)
        rec["phases"].append(ev)

    def close(self, seq, reason: str, t: float) -> Optional[dict]:
        """Close ``seq``'s tree with the roll-ups computed here and now:
        ONE ``decode`` child from its first token to ``t`` (``tokens``
        after the first, ``tpot_ms``), the accumulated ``detokenize``
        wall, then :meth:`finish`."""
        n = seq.num_output_tokens
        with self._lock:
            rec = self._open.get(seq.seq_id)
            if rec is not None:
                t_first = seq.first_token_time
                if n > 1 and t_first:
                    ms = (t - t_first) * 1e3
                    self._append_locked(
                        rec, "decode", t_first, ms,
                        {"tokens": n - 1,
                         "tpot_ms": round(ms / (n - 1), 3)})
                detok = getattr(seq, "_detok_s", 0.0)
                if detok:
                    self._append_locked(rec, "detokenize", t - detok,
                                        detok * 1e3,
                                        {"accumulated": True})
        return self.finish(seq.seq_id, reason, t, output_tokens=n)

    def finish(self, seq_id: int, reason: str, t: float,
               output_tokens: int = 0, **meta) -> Optional[dict]:
        """Close a request tree (first close wins — abort/deadline/
        quarantine and the normal output path may race) and push it
        into the completed ring."""
        with self._lock:
            rec = self._open.pop(seq_id, None)
            if rec is None:
                return None
            rec["t1"] = t
            rec["reason"] = reason
            if output_tokens:
                rec["output_tokens"] = output_tokens
            rec.update(meta)
            for ph, agg in rec["agg"].items():
                agg["ms"] = round(agg["ms"], 3)
            if not rec["agg"]:
                del rec["agg"]
            self._done.append(rec)
            self._finished += 1
            return rec

    # ---- reads -------------------------------------------------------------

    @property
    def open_count(self) -> int:
        with self._lock:
            return len(self._open)

    @property
    def dropped(self) -> int:
        """Completed spans lost to ring rollover."""
        with self._lock:
            return max(0, self._finished - len(self._done))

    def spans(self) -> List[dict]:
        """Completed request trees, oldest first."""
        with self._lock:
            return list(self._done)

    def open_spans(self) -> List[dict]:
        """Still-open trees (shallow copies; phases shared)."""
        with self._lock:
            return [dict(r) for r in self._open.values()]

    def clear(self) -> None:
        with self._lock:
            self._open.clear()
            self._done.clear()
            self._finished = 0
            self.untracked = 0


# Default/standalone instance. Engine code uses a PER-LLM ``SpanTrace``
# (``LLM.spans``) — seq_ids are a per-engine counter starting at 0, so
# two co-resident engines sharing one ring would silently merge each
# other's trees (begin() idempotence absorbs the second engine's open,
# its events land in the first engine's tree). This global remains the
# fallback for components constructed without an engine.
SPANS = SpanTrace()


# ---- Chrome trace-event export ---------------------------------------------

# Track (tid) layout of the engine process row in the exported trace;
# ``wait`` is a derived track (see chrome_trace).
_ENGINE_TIDS = {"schedule": 1, "build": 2, "dispatch": 3, "wait": 4,
                "collect": 5, "output": 7, "deliver": 8, "intake": 9}
_PID_ENGINE = 1
_PID_REQUESTS = 2


def _meta(pid: int, name: str, tid: Optional[int] = None,
          thread: Optional[str] = None) -> dict:
    if tid is None:
        return {"ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": name}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": thread or name}}


def _x(name: str, ts_s: float, dur_s: float, pid: int, tid: int,
       args: Optional[dict] = None) -> dict:
    ev = {"name": name, "ph": "X", "ts": round(ts_s * 1e6, 1),
          "dur": round(max(0.0, dur_s) * 1e6, 1), "pid": pid, "tid": tid}
    if args:
        ev["args"] = args
    return ev


def chrome_trace(step_events: Iterable[dict], spans: Iterable[dict] = (),
                 span_t0: float = 0.0) -> dict:
    """steptrace step events + request span trees → Chrome trace-event
    JSON (the ``{"traceEvents": [...]}`` object format; load in
    Perfetto or chrome://tracing).

    Engine phases are reconstructed backwards from each step event's
    collect-end timestamp ``t`` using the recorded phase walls:
    ``[t - step_wall, t]`` holds schedule → build → dispatch → deliver →
    wait → collect in order (deliver = the previous step's outputs
    handed over at the seam, where a serving loop recorded one; a
    flushed hand-over, which ran before the schedule, is drawn there
    too; wait = the pipelined slack between dispatch end and collect
    start); the previous step's output and this pass's intake lie
    before it. A step launched PREPARED (``prepared`` on its event,
    docs/overlap_scheduling.md#prepared-launch) was dispatched first:
    its ``[t - step_wall, t]`` holds dispatch → output (of the step
    before it) → intake → schedule → build (of the step after it) →
    deliver → wait → collect.
    Request spans use absolute monotonic times; ``span_t0`` (the
    steptrace ring's epoch) rebases them onto the same axis.
    """
    events: List[dict] = [
        _meta(_PID_ENGINE, "engine loop"),
        _meta(_PID_REQUESTS, "requests"),
    ]
    for name, tid in _ENGINE_TIDS.items():
        events.append(_meta(_PID_ENGINE, name, tid=tid))

    for e in step_events:
        ph = e.get("ph")
        if not isinstance(ph, dict):
            continue                   # compile/chain_break/... events
        end = float(e.get("t", 0.0))
        sched = float(ph.get("schedule", 0.0)) / 1e3
        build = float(ph.get("build", 0.0)) / 1e3
        disp = float(ph.get("dispatch", 0.0)) / 1e3
        coll = float(ph.get("collect", e.get("wall_ms", 0.0))) / 1e3
        deliver = float(ph.get("deliver", 0.0)) / 1e3
        wall = float(e.get("step_wall_ms",
                           (sched + build + disp + deliver + coll)
                           * 1e3)) / 1e3
        wait = max(0.0, wall - (sched + build + disp + deliver + coll))
        args = {"kind": e.get("kind"), "seq": e.get("seq"),
                "num_seqs": e.get("num_seqs"),
                "tokens": e.get("tokens")}
        if "k" in e:
            args["k"] = e["k"]
        before = [(name, float(ph.get(name, 0.0)) / 1e3)
                  for name in ("intake", "output")]
        order = (("schedule", sched), ("build", build),
                 ("dispatch", disp), ("deliver", deliver),
                 ("wait", wait), ("collect", coll))
        if e.get("prepared"):
            # launched from the collect of the step before it: that
            # step's output and this pass's intake lie INSIDE the wall
            wait = max(0.0, wait - sum(dur for _, dur in before))
            order = (("dispatch", disp), *reversed(before),
                     ("schedule", sched), ("build", build),
                     ("deliver", deliver), ("wait", wait),
                     ("collect", coll))
            before = []
        t = end - wall
        for name, dur in before:
            if dur > 0:
                t -= dur
                events.append(_x(f"{e.get('kind', 'step')}:{name}", t,
                                 dur, _PID_ENGINE, _ENGINE_TIDS[name]))
        t = end - wall
        for name, dur in order:
            if dur > 0:
                events.append(_x(f"{e.get('kind', 'step')}:{name}", t,
                                 dur, _PID_ENGINE, _ENGINE_TIDS[name],
                                 args if name == "collect" else None))
            t += dur

    for rec in spans:
        sid = int(rec.get("seq_id", 0))
        t0 = float(rec.get("t0", 0.0)) - span_t0
        t1 = rec.get("t1")
        t1 = (float(t1) - span_t0) if t1 is not None else None
        events.append(_meta(_PID_REQUESTS, f"req {sid}", tid=sid))
        if t1 is not None and t1 > t0:
            events.append(_x(
                f"request {sid} ({rec.get('reason') or 'open'})", t0,
                t1 - t0, _PID_REQUESTS, sid,
                {"prompt_tokens": rec.get("prompt_tokens"),
                 "output_tokens": rec.get("output_tokens"),
                 "reason": rec.get("reason")}))
        for c in rec.get("phases", ()):
            args = {k: v for k, v in c.items()
                    if k not in ("ph", "t", "dur_ms")}
            events.append(_x(c["ph"], float(c["t"]) - span_t0,
                             float(c["dur_ms"]) / 1e3, _PID_REQUESTS,
                             sid, args or None))
    return {"traceEvents": events, "displayTimeUnit": "ms"}
