"""Threaded serving core: continuous-batching loop + per-request streams.

The reference splits this across PipeAsyncLLM (asyncio streams,
/root/reference/gllm/async_llm_engine.py:11-139) and the worker processes it
talks to over zmq. Our single-controller design needs neither asyncio nor
IPC: one engine thread owns the scheduler + runner and runs the continuous
batching loop; HTTP handler threads submit requests through a thread-safe
queue and block on per-sequence output queues (SSE streams one queue item
per token). Client disconnects abort the sequence mid-flight, matching the
reference's disconnect→abort propagation.

Request-lifecycle robustness (docs/robustness.md): the reference survives
faults by process supervision — a crashed worker is restarted from
outside. A single-controller engine must survive them in-process instead:

- **admission control**: bounded intake queue + max-resident-requests;
  over-limit submits raise :class:`RequestRejected` (HTTP 429/503 with
  Retry-After in api_server) instead of growing an unbounded queue.
- **deadlines**: per-request wall-clock budgets (``SamplingParams.
  deadline_s`` / submit kwarg / ``config.request_deadline_s`` TTL) abort
  requests stuck in the waiting queue or overrunning, with a terminal
  ``deadline`` chunk.
- **fault isolation**: a step exception quarantines only the scheduled
  batch (``LLM.quarantine_step_failure``) — those requests get terminal
  error chunks, everything else reschedules, and the engine returns to
  idle instead of hot-retrying the failed step forever. N consecutive
  failures escalate to a latched unhealthy state (readiness 503,
  admission closed, liveness still up).
- **watchdog**: the engine thread updates a heartbeat every loop pass; a
  watchdog thread flips readiness while the heartbeat is stale (a hung
  device dispatch blocks the loop inside collect) and restores it on
  recovery.
- **graceful drain**: ``shutdown(drain=True)`` stops admitting, lets
  in-flight requests finish (bounded), then closes every open handle
  with a terminal chunk before joining — no client blocks forever.
- **self-healing recovery** (``config.engine_recovery``,
  docs/robustness.md#recovery-lifecycle): the unhealthy latch (or a
  watchdog HARD stall, or an engine-loop death) hands the lifecycle to
  an in-process :class:`~gllm_tpu.engine.recovery.EngineSupervisor`
  instead of bricking the replica — the engine is torn down and rebuilt
  in-process with bounded exponential backoff (K failed rebuilds within
  a window latch the crash-loop state, today's permanent unhealthy),
  ``/readyz`` reports ``recovering`` with Retry-After, and journaled
  retry-safe requests (seeded or greedy) replay onto the rebuilt engine
  from their committed prefix — no stream hangs, no stream silently
  drops or repeats a token.
"""

from __future__ import annotations

import copy
import dataclasses
import logging
import queue
import threading
import time
from typing import List, Optional

from gllm_tpu import faults
from gllm_tpu.engine.llm import LLM
from gllm_tpu.obs import metrics as obs
from gllm_tpu.obs.spans import FirstToken, phase
from gllm_tpu.obs.steptrace import TRACE
from gllm_tpu.sampling_params import SamplingParams

logger = logging.getLogger(__name__)

_M_SUBMITTED = obs.counter("gllm_requests_submitted_total",
                           "requests submitted to the serving engine")
_M_ACTIVE = obs.gauge("gllm_requests_active",
                      "requests with an open output stream")
_M_ABORTED = obs.counter("gllm_requests_aborted_total",
                         "requests aborted (client disconnect or error)")
_M_REJECTED = obs.counter(
    "gllm_requests_rejected_total",
    "submits rejected by admission control, by reason "
    "(queue_full/resident_limit/unhealthy/recovering/draining)",
    ("reason",))
_M_DEADLINE = obs.counter(
    "gllm_request_deadline_exceeded_total",
    "requests aborted because their wall-clock deadline/TTL expired")
# The HTTP front's own time (docs/observability.md): what a request
# waits for OUTSIDE the engine's steps, on the interpreter the handler
# threads share with the engine thread. Observed where it happens, in
# the intake drain (its twin, the emit lag of a token, is observed by
# the handler thread: api_server._sse).
_M_ADMIT_LAG = obs.histogram(
    "gllm_http_admit_lag_seconds",
    "request body read to llm.add_seq returning in the engine loop: "
    "parse, validation, tokenisation, the intake queue",
    buckets=obs.FAST_LATENCY_BUCKETS)
_M_DELIVER = obs.counter(
    "gllm_deliver_total",
    "collected steps whose outputs were handed to the handler threads, by "
    "when: after_dispatch (the next step was already on the device) or "
    "flush (nothing to launch: idle, a step that dispatched nothing, a "
    "failed step, a deadline, shutdown)", ("when",))
_M_STEP_FAIL = obs.counter(
    "gllm_engine_step_failures_total",
    "engine iterations that raised (each quarantines its batch)")
_M_HEALTHY = obs.gauge(
    "gllm_engine_healthy",
    "1 while the engine accepts work; 0 after the unhealthy latch")
_M_HB_AGE = obs.gauge(
    "gllm_engine_heartbeat_age_seconds",
    "age of the engine thread's last loop-iteration heartbeat")
# Info-style reason metric (value 1 on the current class, 0 on stale
# ones) so a fleet supervisor / router can tell a step-failure latch
# from a watchdog stall from a crash loop without scraping logs.
_M_UNHEALTHY_REASON = obs.gauge(
    "gllm_engine_unhealthy_reason",
    "why this engine is not ready: 1 on the active reason class "
    "(step_failures|stall|loop_death|crash_loop), 0 otherwise; all 0 "
    "while healthy", ("reason",))
_UNHEALTHY_REASON_CLASSES = ("step_failures", "stall", "loop_death",
                             "crash_loop")


class _HandOverFailed(Exception):
    """Carries an exception of the hand-over out through ``LLM.step``,
    inside which it runs, so that the loop does not take it for a failed
    step and quarantine the batch in flight (``__cause__`` is the
    exception)."""


class RequestRejected(Exception):
    """Admission control refused a submit. ``status`` is the HTTP code
    the api_server maps it to (429 over-capacity, 503 unavailable) and
    ``retry_after`` the Retry-After hint in seconds."""

    def __init__(self, reason: str, message: str, status: int = 429,
                 retry_after: float = 1.0):
        super().__init__(message)
        self.reason = reason
        self.status = status
        self.retry_after = retry_after


@dataclasses.dataclass
class StreamChunk:
    token_id: Optional[int]
    text: str
    finish_reason: Optional[str]
    # cumulative counts for usage reporting
    num_prompt_tokens: int = 0
    num_output_tokens: int = 0
    # (chosen_logprob, top_ids, top_logprobs) for this token, when the
    # request asked for logprobs
    logprob: Optional[tuple] = None
    # full per-position prompt logprobs, attached on the finishing chunk
    prompt_logprobs: Optional[list] = None
    # authoritative full output text on the finishing chunk (stop-string
    # truncation may shorten it relative to the streamed deltas)
    final_text: Optional[str] = None
    # terminal failure detail (quarantine / shutdown / engine death) —
    # the finish_reason says what class of end this is, error says why
    error: Optional[str] = None
    # retry hint in seconds on terminal error chunks whose failure is
    # transient (a request dropped as not-replay-safe during a
    # supervised recovery): the client may resubmit after this long
    retry_after: Optional[float] = None
    # time.monotonic() of the engine step that queued this chunk, on one
    # token in EMIT_LAG_EVERY (0.0 on the others): the handler thread
    # observes gllm_http_emit_lag_seconds against it
    t_deliver: float = 0.0
    # on the chunk of a request's FIRST token: its stamps so far
    # (obs/spans.FirstToken). The thread that takes the chunk ends the
    # request's last stage and records its ``first_token`` event
    first_token: Optional[FirstToken] = None


class _Emitter:
    """The one thread that writes the middle tokens of every attached
    stream (``RequestHandle.attach``). A step's chunks reach it as ONE
    list, so a step wakes one thread where it woke a handler thread a
    stream: at 128 streams the handlers' turns at the interpreter
    (two a chunk, and a woken thread's way to them) took longer than
    the step (my chip runs, PR 51: ``front.emit_lag_p95_ms`` 45 beside
    a decode step of 18). Started with the first list posted."""

    def __init__(self):
        self._q: "queue.SimpleQueue[list]" = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    def post(self, batch: list) -> None:
        if self._thread is None:
            with self._lock:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._run, name="gllm-emitter", daemon=True)
                    self._thread.start()
        self._q.put(batch)

    def _run(self) -> None:
        while True:
            for handle, chunk in self._q.get():
                try:
                    handle.emit(chunk)
                except Exception:   # noqa: BLE001 — the others' streams go on
                    logger.exception("emitter: stream %s", handle.seq_id)


EMITTER = _Emitter()


class RequestHandle:
    # liveness poll interval for the bounded get below
    POLL_S = 0.5

    def __init__(self, seq_id: int, prompt_len: int, engine=None):
        self.seq_id = seq_id
        self.prompt_len = prompt_len
        self.chunks: "queue.SimpleQueue[StreamChunk]" = queue.SimpleQueue()
        # ``attach``: once a sink is attached every chunk goes by the
        # emitter thread, in the order it was put. ``_sink`` writes a
        # middle token onto the stream's socket and is dropped for good
        # the first time it hands a chunk back; whatever it does not
        # write goes on to ``chunks``, where the handler thread waits.
        # ``unsent``: the tail of an event the socket did not take
        # whole, for the handler thread to send before anything else.
        self._lock = threading.Lock()
        self._routed = False
        self._sink = None
        self.unsent = b""
        # when set, __iter__ polls engine liveness instead of blocking
        # forever on a queue a dead engine thread will never feed
        self._engine = engine
        # replay veto (docs/robustness.md#recovery-lifecycle): the
        # api_server clears this once a partial tool-call delta has
        # been streamed — a replayed continuation could then re-emit or
        # contradict already-delivered structured output
        self.replay_safe = True

    def attach(self, sink) -> bool:
        """The handler thread offers ``sink(chunk) -> bool`` (True: the
        chunk's event is on the socket) and goes to wait on ``chunks``.
        Refused where a chunk is already waiting there: that stream
        stays the handler thread's, as every stream was."""
        with self._lock:
            if not self.chunks.empty():
                return False
            self._sink, self._routed = sink, True
            return True

    def put(self, chunk: "StreamChunk", batch: Optional[list] = None
            ) -> None:
        """Every chunk of the stream comes through here. ``batch``: the
        list the caller will post to the emitter itself (one list a
        step)."""
        if not self._routed:
            with self._lock:
                if not self._routed:
                    self.chunks.put(chunk)
                    return
        if batch is not None:
            batch.append((self, chunk))
        else:
            EMITTER.post([(self, chunk)])

    def emit(self, chunk: "StreamChunk") -> None:
        """On the emitter thread: a middle token to the sink, anything
        else (the last chunk, an error, whatever the sink hands back or
        fails on) to the handler thread, which deals with it as it
        always has."""
        sink = self._sink
        if (sink is not None and chunk.finish_reason is None
                and chunk.token_id is not None):
            try:
                if sink(chunk):
                    return
            except Exception:       # noqa: BLE001 — the handler's to see
                pass
            self._sink = None
        self.chunks.put(chunk)

    def __iter__(self):
        while True:
            if self._engine is None:
                chunk = self.chunks.get()
            else:
                try:
                    chunk = self.chunks.get(timeout=self.POLL_S)
                except queue.Empty:
                    if not self._engine.is_alive:
                        # drain anything that raced in before declaring
                        # the stream dead
                        try:
                            chunk = self.chunks.get_nowait()
                        except queue.Empty:
                            yield StreamChunk(None, "", "error",
                                              error="engine thread died")
                            return
                    else:
                        continue
            yield chunk
            if chunk.finish_reason is not None:
                return


# A stream's first token and every EMIT_LAG_EVERY-th after it carry the
# stamp: a sample is all a percentile needs, and 32 handler threads each
# observing every token is Python work on the interpreter the engine
# thread shares with them.
EMIT_LAG_EVERY = 8


def deliver_output(llm: LLM, out, handle: RequestHandle,
                   emitted: dict, now: float = 0.0,
                   batch: Optional[list] = None) -> None:
    """Turn one SeqOutput into a StreamChunk on the request's queue
    (shared by the single-host and multi-host serving engines). ``now``:
    the step's time.monotonic(), for the emit-lag stamp. ``batch``: see
    ``RequestHandle.put``."""
    text = ""
    final_text = None
    if llm.tokenizer is not None:
        # the engine step may already have detokenized (stop strings) —
        # emit the delta of seq.output_text beyond what this handle
        # already streamed
        if out.new_token_id is not None:
            llm._stream_detokenize(out.seq)
        if out.finish_reason is not None:
            final_text = llm._finalize(out.seq).text
        full = out.seq.output_text
        text = full[emitted.get(out.seq.seq_id, 0):]
        emitted[out.seq.seq_id] = len(full)
    if out.new_token_id is not None or out.finish_reason:
        lp = first = None
        if out.new_token_id is not None and out.seq.output_logprobs:
            lp = out.seq.output_logprobs[-1]
        if out.new_token_id is not None and not out.seq.first_token_out:
            out.seq.first_token_out = True
            first = FirstToken(
                out.seq, now or time.monotonic(),
                llm.spans if getattr(llm, "tracing", False) else None)
        handle.put(StreamChunk(
            token_id=out.new_token_id,
            text=text,
            finish_reason=out.finish_reason,
            num_prompt_tokens=out.seq.prompt_len,
            num_output_tokens=out.seq.num_output_tokens,
            logprob=lp,
            prompt_logprobs=(out.seq.prompt_logprobs
                             if out.finish_reason else None),
            final_text=final_text,
            t_deliver=(now if out.seq.num_output_tokens
                       % EMIT_LAG_EVERY == 1 else 0.0),
            first_token=first), batch)
    if out.finish_reason is not None:
        emitted.pop(out.seq.seq_id, None)


class ServingEngine:
    """Owns the LLM on a dedicated thread; thread-safe submit/abort."""

    def __init__(self, llm: LLM, *,
                 max_queued_requests: Optional[int] = None,
                 max_resident_requests: Optional[int] = None,
                 request_deadline_s: Optional[float] = None,
                 max_step_failures: Optional[int] = None,
                 watchdog_stall_s: Optional[float] = None,
                 drain_timeout_s: Optional[float] = None,
                 engine_recovery: Optional[bool] = None,
                 llm_factory=None):
        self.llm = llm
        cfg = getattr(llm, "config", None)

        def knob(override, name, default):
            if override is not None:
                return override
            return getattr(cfg, name, default) if cfg is not None \
                else default

        # 0 = unbounded/disabled (byte-identical legacy behavior)
        self.max_queued_requests = knob(max_queued_requests,
                                        "max_queued_requests", 0)
        self.max_resident_requests = knob(max_resident_requests,
                                          "max_resident_requests", 0)
        self.request_deadline_s = knob(request_deadline_s,
                                       "request_deadline_s", 0.0)
        self.max_step_failures = max(1, knob(max_step_failures,
                                             "max_step_failures", 3))
        self.watchdog_stall_s = knob(watchdog_stall_s,
                                     "watchdog_stall_s", 0.0)
        self.drain_timeout_s = knob(drain_timeout_s, "drain_timeout_s",
                                    5.0)
        self.engine_recovery = bool(knob(engine_recovery,
                                         "engine_recovery", False))
        self.watchdog_hard_stall_s = knob(None, "watchdog_hard_stall_s",
                                          0.0)
        if cfg is not None and getattr(cfg, "fault_inject", ""):
            faults.FAULTS.arm(cfg.fault_inject)

        self._intake: "queue.Queue" = queue.Queue()
        # pd-pool push tickets (docs/pd_pools.md): handler threads
        # enqueue (prompt_ids, target_addr, ticket) via push_prefix();
        # the engine loop drains them — the KV spill/export must run on
        # the engine thread — and hands the socket send to a daemon
        # thread that resolves the ticket.
        self._push_work: "queue.Queue" = queue.Queue()
        self._handles: dict[int, RequestHandle] = {}
        self._seqs: dict[int, object] = {}
        self._emitted: dict[int, int] = {}   # seq_id → chars streamed
        self._deadlines: dict[int, float] = {}  # seq_id → abs monotonic
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._draining = False
        self._healthy = True
        self._stalled = False
        self._failed_steps = 0          # consecutive; reset on success
        self._heartbeat = time.monotonic()
        # ---- self-healing recovery (docs/robustness.md) ----
        # _gen supersedes engine threads: every loop pass checks its own
        # generation and a stale (abandoned or exiting) thread can never
        # touch shared state again — the mechanism that makes abandoning
        # a WEDGED thread safe. _recovering gates readiness ("recovering"
        # + Retry-After) and admission; the journal + supervisor exist
        # only under the flag (off = byte-identical legacy lifecycle).
        self._gen = 0
        self._recovering = False
        self._recover_mu = threading.Lock()
        self._unhealthy_reason = ""          # human detail for /readyz
        self._unhealthy_class = ""           # metric reason class
        self._pending_replay: dict = {}      # old seq_id → JournalEntry
        self._journal = None
        self.supervisor = None
        if self.engine_recovery:
            from gllm_tpu.engine.recovery import (EngineSupervisor,
                                                  RequestJournal)
            self._journal = RequestJournal()
            self.supervisor = EngineSupervisor(
                self, llm_factory or self._default_factory(),
                max_rebuilds=knob(None, "max_rebuilds", 3),
                rebuild_window_s=knob(None, "rebuild_window_s", 300.0),
                backoff_s=knob(None, "rebuild_backoff_s", 0.25),
                backoff_max_s=knob(None, "rebuild_backoff_max_s", 30.0))
        _M_HEALTHY.set(1)
        for c in _UNHEALTHY_REASON_CLASSES:
            _M_UNHEALTHY_REASON.set(0, reason=c)
        self._thread = self._spawn_engine_thread()
        self._watchdog: Optional[threading.Thread] = None
        if self.watchdog_stall_s > 0:
            self._watchdog = threading.Thread(target=self._watch,
                                              daemon=True,
                                              name="gllm-watchdog")
            self._watchdog.start()

    def _default_factory(self):
        """Rebuild recipe for the supervisor: a fresh LLM from the same
        (already-validated) config. model_cfg and tokenizer are pure
        host objects and carry over; weights reload from the checkpoint
        — after a hard fault the old device state is suspect by
        definition. The persistent XLA compile cache and the disk
        prefix tier make the rebuild warm (docs/robustness.md)."""
        cfg, model_cfg = self.llm.config, self.llm.model_cfg
        tokenizer = self.llm.tokenizer

        def build():
            return LLM(config=cfg, model_cfg=model_cfg,
                       tokenizer=tokenizer)

        return build

    def _spawn_engine_thread(self) -> threading.Thread:
        t = threading.Thread(target=self._run, args=(self._gen,),
                             daemon=True, name="gllm-engine")
        t.start()
        return t

    # ---- health / readiness (any thread) -----------------------------------

    @property
    def is_alive(self) -> bool:
        """Liveness: the engine thread is running (/healthz). A
        supervised rebuild counts as alive — the whole point of
        in-process recovery is that the external supervisor must NOT
        restart the process while the internal one is mid-rebuild."""
        if self._stop:
            return False
        return self._thread.is_alive() or self._recovering

    @property
    def heartbeat_age(self) -> float:
        return time.monotonic() - self._heartbeat

    def readiness(self) -> tuple:
        """(ready, reason) — admission-facing readiness (/readyz). An
        unready engine still serves liveness: a load balancer drains it,
        the supervisor does not kill it unless /healthz also fails."""
        if not self.is_alive:
            return False, "dead"
        if self._recovering:
            return False, "recovering"
        if not self._healthy:
            return False, "unhealthy"
        if self._draining:
            return False, "draining"
        if self._stalled:
            return False, "stalled"
        return True, "ok"

    def retry_after_s(self) -> float:
        """Retry-After hint matching the current readiness state: the
        supervisor's next-attempt ETA while recovering, a long backoff
        for the (permanent) unhealthy latch, short otherwise."""
        if self._recovering and self.supervisor is not None:
            return max(1.0, self.supervisor.eta_s())
        if not self._healthy:
            return 30.0
        return 5.0

    def health(self) -> dict:
        age = self.heartbeat_age
        _M_HB_AGE.set(age)
        ready, why = self.readiness()
        with self._lock:
            resident = len(self._handles)
        out = {"alive": self.is_alive, "ready": ready, "reason": why,
               "healthy": self._healthy, "draining": self._draining,
               "stalled": self._stalled,
               "recovering": self._recovering,
               "unhealthy_reason": self._unhealthy_class or None,
               "unhealthy_detail": self._unhealthy_reason or None,
               "retry_after_s": round(self.retry_after_s(), 2),
               "heartbeat_age_s": round(age, 3),
               "consecutive_step_failures": self._failed_steps,
               "resident_requests": resident,
               "queued_requests": self._intake.qsize()}
        if self.supervisor is not None:
            out["recoveries"] = self.supervisor.recoveries
            out["rebuilds_failed"] = self.supervisor.rebuilds_failed
        return out

    # ---- client-facing (any thread) ---------------------------------------

    def _admit(self) -> None:
        """Admission control; raises RequestRejected instead of letting
        the intake queue grow without bound. Limits of 0 = legacy
        unbounded behavior."""
        if faults.FAULTS.fire("intake_burst"):
            _M_REJECTED.inc(reason="queue_full")
            raise RequestRejected(
                "queue_full", "intake queue full (injected burst)",
                status=429, retry_after=1.0)
        if self._recovering:
            _M_REJECTED.inc(reason="recovering")
            raise RequestRejected(
                "recovering", "engine is rebuilding after a fault; "
                "retry shortly", status=503,
                retry_after=self.retry_after_s())
        if not self._healthy:
            _M_REJECTED.inc(reason="unhealthy")
            raise RequestRejected(
                "unhealthy", "engine is unhealthy (latched after "
                "repeated step failures)", status=503, retry_after=30.0)
        if self._draining or self._stop:
            _M_REJECTED.inc(reason="draining")
            raise RequestRejected("draining", "engine is shutting down",
                                  status=503, retry_after=5.0)
        if self.max_resident_requests:
            with self._lock:
                resident = len(self._handles)
            if resident >= self.max_resident_requests:
                _M_REJECTED.inc(reason="resident_limit")
                raise RequestRejected(
                    "resident_limit",
                    f"{resident} requests resident (limit "
                    f"{self.max_resident_requests})",
                    status=429, retry_after=1.0)
        if self.max_queued_requests \
                and self._intake.qsize() >= self.max_queued_requests:
            _M_REJECTED.inc(reason="queue_full")
            raise RequestRejected(
                "queue_full",
                f"intake queue full (limit {self.max_queued_requests})",
                status=429, retry_after=1.0)

    def submit(self, token_ids: List[int],
               sampling_params: SamplingParams,
               mm_input: Optional[dict] = None,
               disagg_items: Optional[list] = None,
               target_dp: Optional[int] = None,
               deadline_s: Optional[float] = None,
               received_t: Optional[float] = None) -> RequestHandle:
        """``received_t``: time.monotonic() at which the front end had
        read the request's body (gllm_http_admit_lag_seconds)."""
        sampling_params.validate()
        self._admit()
        mm_state = None
        if mm_input:
            # Hashing + position building over full pixel arrays is
            # hundreds of ms for big images — do it before taking the
            # engine-wide lock.
            from gllm_tpu.engine.mm import build_mm_state
            mm_state = build_mm_state(token_ids, self.llm.model_cfg,
                                      **mm_input)
        ttl = (deadline_s if deadline_s is not None
               else sampling_params.deadline_s
               if sampling_params.deadline_s is not None
               else self.request_deadline_s)
        with self._lock:
            seq = self.llm._allocate_seq(token_ids, sampling_params)
            seq.mm = mm_state
            seq.received_t = received_t
            if target_dp is not None:
                # per-DP-endpoint pinning (reference --endpoint-per-dp,
                # llm_engine.py:121-133 + sequence.py:79-83): the endpoint
                # that received the request pins its KV/prefix-cache to
                # that replica
                seq.target_dp = target_dp
            if disagg_items is not None:
                # skeleton request → coordinator (gate A admits it later)
                seq._disagg_items = disagg_items
            handle = RequestHandle(seq.seq_id, len(token_ids),
                                   engine=self)
            self._handles[seq.seq_id] = handle
            self._seqs[seq.seq_id] = seq
            if ttl and ttl > 0:
                self._deadlines[seq.seq_id] = time.monotonic() + ttl
            if self._journal is not None:
                # immutable submission for crash replay — committed
                # token ids append as chunks are delivered
                self._journal.record(
                    seq.seq_id, token_ids, sampling_params,
                    mm=mm_state is not None,
                    disagg=disagg_items is not None,
                    target_dp=target_dp)
            _M_SUBMITTED.inc()
            _M_ACTIVE.set(len(self._handles))
        self._enqueue(seq)
        return handle

    def _enqueue(self, seq) -> None:
        """Onto the intake queue, stamped: where the handler's stage
        (``parse``) ends and the wait for the engine loop's pass
        (``intake``) begins. The stamp precedes the put, so the engine
        thread never reads a sequence without it."""
        seq.submitted_t = time.monotonic()
        self._intake.put(seq)
        self._wake.set()

    def _alloc_committed(self, llm, prompt_ids, committed_ids,
                         sampling_params):
        """Allocate a sequence that CONTINUES from a committed prefix:
        prompt + committed resubmitted with the ORIGINAL prompt_len
        (num_output_tokens counts the committed tokens, so max_tokens /
        min_tokens / penalties and the seeded sampling out_step all
        continue exactly), committed output text re-detokenized so the
        handle's char cursor lines up and only NEW deltas stream. The
        ONE definition of replay adoption — the in-process recovery
        path (_adopt_llm) and the cross-replica continuation path
        (submit_continuation) must never drift apart. Caller holds
        self._lock."""
        seq = llm._allocate_seq(list(prompt_ids) + list(committed_ids),
                                sampling_params)
        seq.prompt_len = len(prompt_ids)
        if llm.tokenizer is not None and committed_ids:
            seq.detok_prefix_offset = max(0, len(prompt_ids) - 6)
            seq.detok_read_offset = len(prompt_ids)
            llm._stream_detokenize(seq)
            self._emitted[seq.seq_id] = len(seq.output_text)
        return seq

    def submit_continuation(self, prompt_ids: List[int],
                            committed_ids: List[int],
                            sampling_params: SamplingParams,
                            deadline_s: Optional[float] = None,
                            target_dp: Optional[int] = None,
                            received_t: Optional[float] = None
                            ) -> RequestHandle:
        """Cross-replica failover continuation (docs/robustness.md#fleet
        -topology--failover): resume a retry-safe stream another replica
        started, from its committed prefix. Rides EXACTLY the replay
        semantics ``_adopt_llm`` proved in-process — ``prompt +
        committed`` resubmitted with the ORIGINAL prompt_len, so
        num_output_tokens counts the committed tokens and max_tokens /
        min_tokens / penalties / the seeded sampling out_step all
        continue where the dead replica's stream stopped. The committed
        output text is re-detokenized so the handle's char cursor lines
        up and only NEW deltas stream. The front router is the caller
        (via the api_server ``gllm_continuation`` path); the safety
        predicate (greedy or seeded, no mm/disagg/stop-strings/
        prompt_logprobs) is enforced router-side before resubmission."""
        sampling_params.validate()
        self._admit()
        prompt_ids = [int(t) for t in prompt_ids]
        committed_ids = [int(t) for t in committed_ids]
        ttl = (deadline_s if deadline_s is not None
               else sampling_params.deadline_s
               if sampling_params.deadline_s is not None
               else self.request_deadline_s)
        with self._lock:
            seq = self._alloc_committed(self.llm, prompt_ids,
                                        committed_ids, sampling_params)
            if target_dp is not None:
                seq.target_dp = target_dp
            seq.received_t = received_t
            handle = RequestHandle(seq.seq_id, len(prompt_ids),
                                   engine=self)
            self._handles[seq.seq_id] = handle
            self._seqs[seq.seq_id] = seq
            if ttl and ttl > 0:
                self._deadlines[seq.seq_id] = time.monotonic() + ttl
            if self._journal is not None:
                # journal as prompt + already-committed so a LOCAL crash
                # after adoption replays the same request again
                self._journal.record(seq.seq_id, prompt_ids,
                                     sampling_params,
                                     target_dp=target_dp)
                for t in committed_ids:
                    self._journal.commit(seq.seq_id, t)
            _M_SUBMITTED.inc()
            _M_ACTIVE.set(len(self._handles))
        self._enqueue(seq)
        return handle

    def push_prefix(self, prompt_ids: List[int], target_addr: str,
                    wait_s: float = 5.0) -> int:
        """pd-pool KV handoff (docs/pd_pools.md): ship ``prompt_ids``'s
        finished prefix KV chain to ``target_addr`` (a decode replica's
        prefix serve port). Any thread may call this; the KV export runs
        on the engine thread (queued here, drained each loop pass) and
        the socket send on a daemon thread, so neither the caller nor
        the step loop can stall on the other. Returns the number of
        pages the target ACCEPTED — 0 on any failure or timeout (a
        failed push costs the decode side a re-prefill, never more)."""
        ticket = {"done": threading.Event(), "pages": 0}
        self._push_work.put(([int(t) for t in prompt_ids],
                             str(target_addr), ticket))
        self._wake.set()
        ticket["done"].wait(timeout=wait_s)
        return int(ticket["pages"])

    def _drain_push_work(self, llm) -> None:
        """Engine-thread half of :meth:`push_prefix`: spill + pack the
        chain (device-ordering-safe only here), then hand the payloads
        to a shipper thread."""
        while True:
            try:
                ids, addr, ticket = self._push_work.get_nowait()
            except queue.Empty:
                return
            try:
                pages = llm.export_prefix_chain(ids)
            except Exception:
                logger.exception("prefix export for pd push failed")
                pages = []
            if not pages:
                ticket["done"].set()
                continue
            geometry = llm.prefix_tiers.geometry

            def _ship(pages=pages, addr=addr, ticket=ticket,
                      geometry=geometry):
                from gllm_tpu.kvstore.peer import PrefixPusher
                try:
                    ticket["pages"] = PrefixPusher(geometry).push(
                        addr, pages)
                except Exception:   # pragma: no cover - push never raises
                    logger.exception("pd prefix push failed")
                finally:
                    ticket["done"].set()

            threading.Thread(target=_ship, daemon=True,
                             name="gllm-kv-push").start()

    def abort(self, seq_id: int) -> None:
        entry = self._pending_replay.get(seq_id)
        if entry is not None:
            # client went away while its request waited for the rebuild:
            # mark the journal entry so _adopt_llm skips the replay
            entry.aborted = True
            return
        self.llm.abort(seq_id)
        self._wake.set()

    def shutdown(self, drain: bool = False,
                 timeout: Optional[float] = None) -> None:
        """Stop the engine. ``drain=True`` first stops admitting and
        waits (bounded by ``timeout``/``drain_timeout_s``) for in-flight
        requests to finish; either way every still-open handle gets a
        terminal chunk so no HTTP thread blocks forever on a stream the
        engine will never feed."""
        self._draining = True
        if drain:
            limit = time.monotonic() + (timeout if timeout is not None
                                        else self.drain_timeout_s)
            while time.monotonic() < limit:
                with self._lock:
                    if not self._handles and self._intake.empty():
                        break
                time.sleep(0.01)
        self._stop = True
        self._wake.set()
        if self.supervisor is not None:
            self.supervisor.close()
        self._thread.join(timeout=5)
        # the loop's finally already closed the handles if the thread
        # exited; this is the backstop for a hung/killed thread
        self._close_open_handles("abort", "engine shutdown")
        # requests still parked for replay (shutdown raced a recovery)
        for entry in self._take_pending():
            h = entry.handle
            if h is not None:
                _M_ABORTED.inc()
                h.put(StreamChunk(None, "", "abort",
                                         error="engine shutdown"))
        # stop serving peers, drain pending disk writes; host-tier
        # pages are NOT force-demoted here (an operator who wants the
        # warm cache persisted calls flush_host_to_disk first)
        close = getattr(self.llm, "close", None)
        if callable(close):
            close()

    # ---- engine thread ----------------------------------------------------

    def _run(self, gen: int) -> None:
        try:
            self._run_loop(gen)
        except Exception as e:  # pragma: no cover on the latch branch
            logger.exception("engine loop died")
            detail = f"engine loop died: {type(e).__name__}: {e}"
            if not self._maybe_recover("loop_death", detail):
                if self._healthy:
                    # keep an earlier latch's reason class (e.g. the
                    # crash-loop idle thread dying must not relabel it)
                    self._set_unhealthy_reason("loop_death", detail)
                self._healthy = False
                _M_HEALTHY.set(0)
        finally:
            # a SUPERSEDED loop (recovery bumped the generation) must
            # not close the handles — the supervisor owns them now and
            # retry-safe streams will continue on the rebuilt engine
            if self._gen == gen:
                self._close_open_handles("abort", "engine stopped")

    def _run_loop(self, gen: int) -> None:
        """The continuous-batching loop. One pass: ``intake``, then
        ``llm.step`` — the fill pass (``schedule``, ``build``,
        ``dispatch``), the hand-over of the PREVIOUS step's outputs
        (``deliver``), the collect (``wait``, ``readback``, ``output``).

        A collected step's outputs are held as ``pending`` and handed
        to the handler threads only once the next step is on the device
        (``LLM.step``'s ``after_dispatch`` seam): the emitter thread
        (and a handler thread for each stream that is not attached to
        it) then sends their chunks while this thread is blocked in
        ``wait`` with the interpreter released, and not while it builds
        the next step with the device idle. Wherever the loop will not launch — nothing
        left to run, a step that dispatched nothing, a failed step, a
        deadline about to close a stream, the loop's exit — the pending
        outputs are flushed at once; a superseded generation drops them
        (they were never committed to the journal, so replay recomputes
        them).

        A decode step that ``LLM.step`` prepared under the running one
        is launched from the collect, before ``output``
        (docs/overlap_scheduling.md#prepared-launch): the next pass then
        finds it in flight, forms nothing, and reaches the seam at once.
        ``hold_launch`` is this loop's say in that launch."""
        llm = self.llm
        pending: list = []      # collected, committed in the scheduler,
                                # not yet delivered
        handed = False          # this pass has reached the hand-over

        def hand_over(when: str = "after_dispatch") -> None:
            nonlocal pending, handed
            handed = True
            if self._gen != gen:
                # a hard-stall recovery abandoned this thread while it
                # was blocked in the fill pass — the rebuilt engine owns
                # the handles; delivering now would corrupt their streams
                pending = []
                return
            outputs, pending = pending, []
            try:
                with phase("deliver"):
                    if outputs:
                        _M_DELIVER.inc(when=when)
                        self._deliver(llm, outputs)
                    # aborted sequences never produce a SeqOutput →
                    # close their streams here; only ever right after
                    # the pending outputs have gone out, so a sequence
                    # that FINISHED in them has left _seqs and is not
                    # taken for an abort
                    self._reap_aborted()
            except Exception as e:
                if when != "after_dispatch":
                    raise
                # inside llm.step: not a failure of the step
                raise _HandOverFailed() from e

        def hold_launch() -> Optional[str]:
            """Asked by ``LLM.step`` at the collect, before it launches
            the decode step it prepared under the running one: what only
            this loop can see. A request on the intake queue has to ride
            the very next program, so that step is formed as always;
            push work, a deadline about to close a stream and the loop's
            end are the next pass's to handle before anything launches."""
            if not self._intake.empty():
                return "arrival"
            if (self._stop or self._gen != gen
                    or not self._push_work.empty()
                    or self._expired_deadlines()):
                return "other"
            return None

        while not self._stop and self._gen == gen:
            self._heartbeat = time.monotonic()
            # chaos point (docs/robustness.md#recovery-lifecycle): dies
            # OUTSIDE the per-step quarantine try, the way an unhandled
            # runner/driver fault would — exercises the supervised
            # rebuild, not the batch quarantine
            faults.FAULTS.maybe_raise("engine_hard_crash")
            with phase("intake"):
                drained = self._drain_intake(llm)
                self._drain_push_work(llm)
                expired = self._expired_deadlines()
            if expired:
                # the budget ran out between a step's collect and the
                # hand-over of its tokens: those go first (a final chunk
                # closes the stream with its own finish_reason, a middle
                # token precedes the ``deadline`` chunk)
                hand_over("flush")
                with phase("intake"):
                    self._expire_deadlines(expired)
            if not llm.has_unfinished:
                if pending:
                    hand_over("flush")
                if not drained:
                    with phase("idle"):
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
                continue
            handed = False
            try:
                outputs = llm.step(after_dispatch=hand_over,
                                   hold_launch=hold_launch)
            except _HandOverFailed as e:
                raise e.__cause__     # the loop dies of it, as it always has
            except Exception as e:
                if self._gen != gen:
                    return        # superseded while blocked in step
                if not handed:
                    # step N's tokens are real (committed in the
                    # scheduler) whatever became of step N+1: out before
                    # the quarantine
                    hand_over("flush")
                logger.exception("engine step failed")
                self._on_step_failure(e)
                continue
            if self._gen != gen:
                # a hard-stall recovery abandoned this thread while it
                # was blocked in step — the rebuilt engine owns the
                # handles; delivering now would corrupt their streams
                return
            self._failed_steps = 0
            if not handed:
                # the pass never reached the seam: it launched nothing
                # (``[]`` paths), so there is nothing to hide the
                # hand-over behind
                hand_over("flush")
            pending = outputs
        if self._gen == gen:
            hand_over("flush")      # drain / shutdown: the loop's exit

    def _drain_intake(self, llm) -> bool:
        """Admit everything the front end has queued (the ``intake``
        phase). True if anything was taken off the queue."""
        drained = False
        while True:
            try:
                seq = self._intake.get_nowait()
            except queue.Empty:
                return drained
            if self._seqs.get(seq.seq_id) is not seq:
                # a recovery partition cleared/re-keyed this request
                # while its submit raced the trigger (the put landed
                # after the partition's intake drain): the journal
                # replay owns it now — admitting the stale
                # old-engine Sequence would compute it twice, and
                # its old seq id can collide with a rebuilt-engine
                # id (identity check, not membership: a replayed
                # request may hold the same id on a NEW Sequence)
                continue
            try:
                items = getattr(seq, "_disagg_items", None)
                if items is not None:
                    llm.submit_disagg(seq, items)
                else:
                    llm.add_seq(seq)
            except ValueError as e:
                self._deliver_error(seq.seq_id, "error", str(e))
            # where ``intake`` ends (obs/spans.first_token_stamps); the
            # admit lag is read off the same instant
            seq.admitted_t = time.monotonic()
            if seq.received_t is not None:
                _M_ADMIT_LAG.observe(seq.admitted_t - seq.received_t)
            drained = True

    def _deliver(self, llm, outputs) -> None:
        """One step's outputs to their handles and the journal (the
        ``deliver`` phase)."""
        now = time.monotonic()
        batch: list = []
        for out in outputs:
            handle = self._handles.get(out.seq.seq_id)
            if handle is None:
                continue
            deliver_output(llm, out, handle, self._emitted, now, batch)
            if self._journal is not None:
                if out.new_token_id is not None:
                    # DELIVERED = committed: replay continues from
                    # exactly what the client's stream already holds
                    self._journal.commit(out.seq.seq_id,
                                         out.new_token_id)
                if out.finish_reason is not None:
                    self._journal.pop(out.seq.seq_id)
            if out.finish_reason is not None:
                with self._lock:
                    self._handles.pop(out.seq.seq_id, None)
                    self._seqs.pop(out.seq.seq_id, None)
                    self._deadlines.pop(out.seq.seq_id, None)
                    _M_ACTIVE.set(len(self._handles))
                self._emitted.pop(out.seq.seq_id, None)
        if batch:
            EMITTER.post(batch)

    # ---- fault isolation ---------------------------------------------------

    def _on_step_failure(self, exc: BaseException) -> None:
        """Quarantine the failed step's batch; escalate to the latched
        unhealthy state after max_step_failures consecutive failures
        (the old behavior failed EVERY request and then hot-retried the
        broken step forever because the failing sequences stayed
        scheduler-resident)."""
        _M_STEP_FAIL.inc()
        self._failed_steps += 1
        detail = f"{type(exc).__name__}: {exc}"
        try:
            failed = self.llm.quarantine_step_failure()
        except Exception:
            logger.exception("quarantine after step failure failed")
            self._latch_unhealthy(f"unrecoverable step failure: {detail}")
            return
        # Latch BEFORE delivering the terminal chunks: a client whose
        # failed request just returned may immediately probe /readyz,
        # and readiness must already reflect the escalation by the time
        # any client can observe the failure (the pre-fix order lost
        # that race — the order-dependent healthz-vs-readyz flake).
        if self._failed_steps >= self.max_step_failures:
            self._latch_unhealthy(
                f"{self._failed_steps} consecutive step failures "
                f"(last: {detail})")
            if self._recovering:
                # the latch became a supervised rebuild: the failed
                # batch's streams stay OPEN — the supervisor partitions
                # them, and the retry-safe ones replay from their
                # committed prefix instead of dying here
                return
        for sid in failed:
            self._deliver_error(sid, "error", detail)

    def _latch_unhealthy(self, why: str, cls: str = "step_failures",
                         quarantine: bool = True) -> None:
        """quarantine=False when another thread still owns the LLM (a
        WEDGED engine thread mid-dispatch): only host-side state is
        touched — handles close, and a later wake finds nothing to
        feed."""
        if self._maybe_recover(cls, why):
            return           # the supervisor owns the lifecycle now
        if not self._healthy:
            return
        logger.error("engine latched unhealthy: %s", why)
        self._set_unhealthy_reason(cls, why)
        self._healthy = False
        _M_HEALTHY.set(0)
        TRACE.record("fault", point="engine_unhealthy", error=why[:200])
        if quarantine:
            try:
                self.llm.quarantine_step_failure(everything=True)
            except Exception:  # pragma: no cover
                logger.exception("full quarantine failed")
        self._close_open_handles("error", why)

    # ---- self-healing recovery (docs/robustness.md#recovery-lifecycle) ----

    def _set_unhealthy_reason(self, cls: str, detail: str) -> None:
        self._unhealthy_class = cls
        self._unhealthy_reason = detail
        for c in _UNHEALTHY_REASON_CLASSES:
            _M_UNHEALTHY_REASON.set(1 if c == cls else 0, reason=c)

    def _clear_unhealthy_reason(self) -> None:
        self._unhealthy_class = self._unhealthy_reason = ""
        for c in _UNHEALTHY_REASON_CLASSES:
            _M_UNHEALTHY_REASON.set(0, reason=c)

    def _maybe_recover(self, cls: str, why: str) -> bool:
        """Route a would-be unhealthy latch into a supervised rebuild.
        True = recovery owns the lifecycle (begun now, or already in
        progress); False = fall through to the permanent latch (no
        supervisor, stopping, or the crash-loop budget is spent)."""
        sup = self.supervisor
        if sup is None or self._stop or not self._healthy:
            return False
        with self._recover_mu:
            if self._recovering:
                return True
            if not sup.may_recover():
                return False
            self._recovering = True
            self._set_unhealthy_reason(cls, why)
            from gllm_tpu.engine import recovery as _rec
            _rec._M_RECOVERING.set(1)
            TRACE.record("recovery", phase="begin", reason=cls)
            # supersede the current engine thread BEFORE the supervisor
            # joins it: a cooperative loop exits next pass, a wedged one
            # is abandoned behind the bump either way
            self._gen += 1
        self._wake.set()
        sup.trigger(cls, why)
        return True

    def _crash_loop_latch(self, why: str) -> None:
        """Terminal state of the rebuild ladder: K failed rebuilds
        within the window — permanent unhealthy (exactly the
        pre-recovery latch), pending-replay streams get terminal error
        chunks, the external supervisor takes over via /healthz."""
        logger.error("engine crash-loop latched: %s", why)
        with self._recover_mu:
            self._recovering = False
            self._set_unhealthy_reason("crash_loop", why)
            self._healthy = False
            self._gen += 1
        _M_HEALTHY.set(0)
        # Liveness stays up exactly like the legacy latch — /healthz
        # 200 so the balancer drains while the EXTERNAL supervisor
        # decides, /readyz 503 with reason class crash_loop. The thread
        # is a pure heartbeat idler, NOT a _run loop: self.llm is still
        # the torn-down engine (the rebuild failed), possibly with a
        # wedged thread inside step() — a second stepper on the same
        # object would race it.
        self._heartbeat = time.monotonic()
        self._thread = threading.Thread(target=self._idle_loop,
                                        args=(self._gen,), daemon=True,
                                        name="gllm-engine")
        self._thread.start()
        from gllm_tpu.engine import recovery as _rec
        _rec._M_RECOVERING.set(0)
        TRACE.record("fault", point="engine_unhealthy", error=why[:200])
        for entry in self._take_pending():
            h = entry.handle
            if h is None:
                continue
            _M_ABORTED.inc()
            h.put(StreamChunk(
                None, "", "error",
                error=f"engine crash-looped during recovery: {why}"))
        self._close_open_handles("error", why)

    def _idle_loop(self, gen: int) -> None:
        """Crash-loop liveness thread: keeps /healthz 200 (and the
        heartbeat fresh) without ever touching the torn-down LLM.
        Admission is closed and nothing is resident, so there is no
        work it could miss."""
        while not self._stop and self._gen == gen:
            self._heartbeat = time.monotonic()
            self._wake.wait(timeout=0.2)
            self._wake.clear()

    def _take_pending(self) -> list:
        with self._lock:
            pending = list(self._pending_replay.values())
            self._pending_replay.clear()
        return pending

    def _partition_for_replay(self) -> list:
        """Called by the supervisor once the old engine is down: snap
        every open stream against the journal. Retry-safe entries are
        parked in _pending_replay (their handles stay open — the client
        keeps polling liveness, which recovery keeps True); everything
        else ends now with a terminal error chunk carrying Retry-After.
        Returns the parked entries."""
        from gllm_tpu.engine.recovery import _M_REPLAYED
        with self._lock:
            handles = dict(self._handles)
            self._handles.clear()
            self._seqs.clear()
            deadlines = dict(self._deadlines)
            self._deadlines.clear()
            _M_ACTIVE.set(0)
        self._emitted.clear()
        # stale intake: never-admitted seqs are journaled too — replay
        # reconstructs them, the old Sequence objects are discarded
        while True:
            try:
                self._intake.get_nowait()
            except queue.Empty:
                break
        retry = self.retry_after_s()
        entries = []
        for sid, handle in handles.items():
            entry = self._journal.pop(sid) if self._journal is not None \
                else None
            if entry is not None:
                entry.handle = handle
                entry.deadline = deadlines.get(sid)
            why = entry.unsafe_reason() if entry is not None \
                else "request predates the journal"
            if why is None:
                with self._lock:
                    self._pending_replay[sid] = entry
                entries.append(entry)
                continue
            _M_REPLAYED.inc(outcome="unsafe")
            _M_ABORTED.inc()
            handle.put(StreamChunk(
                None, "", "error",
                error=("engine is rebuilding after a fault and this "
                       f"request is not replay-safe ({why}); retry "
                       f"after ~{retry:.0f}s"),
                retry_after=retry))
        TRACE.record("recovery", phase="partition",
                     replayable=len(entries),
                     dropped=len(handles) - len(entries))
        return entries

    def _adopt_llm(self, llm, entries: list) -> tuple:
        """Swap in the rebuilt engine, replay the parked entries, and
        restart the loop. Returns (replayed, dropped). Runs on the
        supervisor thread — no engine thread is alive for this
        generation, so the scheduler is single-owner here."""
        from gllm_tpu.engine.recovery import _M_REPLAYED
        from gllm_tpu.engine import recovery as _rec
        with self._lock:
            # a submit that slipped past _admit in the instant before
            # the recovering flag set may have allocated an old-engine
            # seq: seed the rebuilt engine's id counter past EVERY id
            # the old engine ever handed out (submit allocates under
            # this same lock, so inside it the swap is atomic — any
            # later submit allocates from the new llm) so a replayed
            # or new seq can never collide with a stale one
            llm._next_seq_id = max(llm._next_seq_id,
                                   self.llm._next_seq_id,
                                   max(self._handles.keys(),
                                       default=-1) + 1)
            self.llm = llm
        now = time.monotonic()
        replayed = dropped = 0
        for entry in entries:
            with self._lock:
                parked = self._pending_replay.pop(entry.seq_id, None)
            if parked is None:
                # a concurrent shutdown already closed this stream —
                # replaying would deliver past its terminal chunk
                dropped += 1
                continue
            h = entry.handle
            if entry.aborted:
                dropped += 1
                _M_REPLAYED.inc(outcome="aborted")
                _M_ABORTED.inc()
                h.put(StreamChunk(None, "", "abort"))
                continue
            if entry.deadline is not None and now >= entry.deadline:
                dropped += 1
                _M_REPLAYED.inc(outcome="expired")
                _M_DEADLINE.inc()
                _M_ABORTED.inc()
                h.put(StreamChunk(None, "", "deadline"))
                continue
            sp = copy.deepcopy(entry.sampling)
            with self._lock:
                # prompt + committed resubmits with the ORIGINAL
                # prompt_len — byte-identical continuation for greedy
                # and seeded requests (_alloc_committed is the shared
                # adoption recipe; the router's cross-replica
                # continuation path rides the same one)
                seq = self._alloc_committed(llm, entry.prompt,
                                            entry.committed, sp)
                if entry.target_dp is not None:
                    seq.target_dp = entry.target_dp
                h.seq_id = seq.seq_id
                self._handles[seq.seq_id] = h
                self._seqs[seq.seq_id] = seq
                if entry.deadline is not None:
                    self._deadlines[seq.seq_id] = entry.deadline
                if self._journal is not None:
                    self._journal.adopt(seq.seq_id, entry)
                _M_ACTIVE.set(len(self._handles))
            self._enqueue(seq)
            _M_REPLAYED.inc(outcome="replayed")
            replayed += 1
        # fresh loop under the bumped generation
        self._failed_steps = 0
        self._heartbeat = time.monotonic()
        self._stalled = False
        self._thread = self._spawn_engine_thread()
        with self._recover_mu:
            self._recovering = False
            self._clear_unhealthy_reason()
        _rec._M_RECOVERING.set(0)
        self._wake.set()
        return replayed, dropped

    def _expired_deadlines(self) -> list:
        """Requests past their wall-clock budget — including ones still
        sitting unscheduled in the waiting queue, which the per-step
        output path would never touch."""
        if not self._deadlines:
            return []
        now = time.monotonic()
        with self._lock:
            return [sid for sid, t in self._deadlines.items() if now >= t]

    def _expire_deadlines(self, expired: list) -> None:
        """Abort them. A stream that closed since the list was made (its
        final chunk was pending and has gone out) is left alone."""
        for sid in expired:
            if sid not in self._deadlines:
                continue
            self.llm.abort(sid)
            _M_DEADLINE.inc()
            self._deliver_error(sid, "deadline")

    def _reap_aborted(self):
        with self._lock:
            dead = [sid for sid, seq in self._seqs.items()
                    if seq.is_finished]
            for sid in dead:
                self._seqs.pop(sid, None)
        for sid in dead:
            self._deliver_error(sid, "abort")

    def _deliver_error(self, seq_id: int, reason: str,
                       detail: Optional[str] = None) -> None:
        if getattr(self.llm.config, "tracing", True):
            # abort/deadline/shutdown requests never reach the engine's
            # normal finish path — close their span tree with the same
            # reason the terminal chunk carries (first close wins)
            self.llm.spans.finish(seq_id, reason or "error",
                                  time.monotonic())
        with self._lock:
            handle = self._handles.pop(seq_id, None)
            self._seqs.pop(seq_id, None)
            self._deadlines.pop(seq_id, None)
            _M_ACTIVE.set(len(self._handles))
        self._emitted.pop(seq_id, None)
        if self._journal is not None:
            self._journal.pop(seq_id)
        if handle is not None:
            _M_ABORTED.inc()
            handle.put(StreamChunk(None, "", reason or "error",
                                          error=detail))

    def _close_open_handles(self, reason: str,
                            detail: Optional[str] = None) -> None:
        """Terminal chunk for every open stream (engine-wide failure or
        shutdown) — replaces the old _fail_all, which leaked the
        scheduler state that caused the hot-retry loop."""
        with self._lock:
            handles = list(self._handles.values())
            self._handles.clear()
            self._seqs.clear()
            self._emitted.clear()
            self._deadlines.clear()
            _M_ACTIVE.set(0)
        if self._journal is not None:
            self._journal.clear()
        if handles:
            _M_ABORTED.inc(len(handles))
        if getattr(self.llm.config, "tracing", True):
            now = time.monotonic()
            for h in handles:
                self.llm.spans.finish(h.seq_id, reason or "error",
                                      now)
        for h in handles:
            h.put(StreamChunk(None, "", reason, error=detail))

    # ---- watchdog ----------------------------------------------------------

    def _watch(self) -> None:
        """Detect a wedged engine thread (hung device dispatch blocks the
        loop inside collect, so the heartbeat goes stale) and flip
        readiness while it lasts. Liveness is untouched: the supervisor
        restarts on /healthz, the balancer routes on /readyz.

        With ``watchdog_hard_stall_s`` > 0 (requires engine_recovery),
        a heartbeat past the HARD threshold escalates to the supervised
        rebuild: the wedged thread is abandoned behind a generation
        bump and a fresh engine takes over — a hung device call no
        longer bricks the replica until a human restarts it."""
        stall = self.watchdog_stall_s
        hard = self.watchdog_hard_stall_s
        interval = max(0.02, min(stall / 4.0, 1.0))
        while not self._stop:
            time.sleep(interval)
            if self._recovering:
                continue      # heartbeat is expectedly stale mid-rebuild
            if not self._thread.is_alive():
                if self.supervisor is None:
                    return    # loop died permanently; nothing to watch
                continue      # between generations
            age = time.monotonic() - self._heartbeat
            _M_HB_AGE.set(age)
            if age > stall:
                if not self._stalled:
                    self._stalled = True
                    TRACE.record("fault", point="dispatch_stall_detected",
                                 age_s=round(age, 3))
                    logger.error(
                        "engine heartbeat stale %.2fs (> %.2fs) — "
                        "readiness off", age, stall)
                if hard > 0 and age > hard:
                    why = (f"engine heartbeat stale {age:.2f}s (hard "
                           f"threshold {hard:.2f}s) — abandoning the "
                           "wedged engine thread")
                    # _latch_unhealthy tries _maybe_recover first;
                    # budget spent → permanent latch WITHOUT
                    # quarantining (the wedged thread still owns the
                    # LLM)
                    self._latch_unhealthy(why, cls="stall",
                                          quarantine=False)
            elif self._stalled:
                self._stalled = False
                logger.info("engine heartbeat recovered — readiness on")
