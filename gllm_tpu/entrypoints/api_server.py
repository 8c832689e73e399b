"""OpenAI-compatible HTTP server (stdlib, dependency-free).

Serves the same route surface as the reference FastAPI server
(/root/reference/gllm/entrypoints/api_server.py:41-207):
``/v1/chat/completions``, ``/v1/completions``, ``/v1/models``, ``/health``,
``/version``, ``/server_info``, ``/start_profile``, ``/stop_profile`` —
with SSE streaming, client-disconnect abort, and the reference's CLI flag
surface (:267-508) mapped onto EngineConfig.

Implementation note: this image ships neither fastapi nor uvicorn, so the
server is a ThreadingHTTPServer — one OS thread per in-flight request,
blocking on the ServingEngine's per-sequence queues. The engine itself is
single-threaded continuous batching; HTTP concurrency is intake concurrency.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import socket
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import gllm_tpu
from gllm_tpu import faults
from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.engine.llm import LLM
from gllm_tpu.engine.serving_engine import RequestRejected, ServingEngine
from gllm_tpu.entrypoints import protocol as proto
from gllm_tpu.obs import metrics as obs_metrics

logger = logging.getLogger(__name__)

# How long a committed token waits for the socket: deliver_output's stamp
# on the chunk (engine thread; a stream's first token and every eighth
# after it) to the flush of the SSE event that carries it (``_sse`` on the
# stream's handler thread, ``_sink`` on the emitter thread). Those threads
# share one interpreter with the engine thread; this is their share of
# the gap between tokens.
_M_EMIT_LAG = obs_metrics.histogram(
    "gllm_http_emit_lag_seconds",
    "deliver_output's stamp of a token to the flush of "
    "the SSE chunk that carries it (a stream's first token and every "
    "eighth after it)",
    buckets=(5e-5, 1e-4, 2e-4, 5e-4, 1e-3, 2e-3, 5e-3, 1e-2, 2e-2, 5e-2,
             0.1))


class ServerState:
    def __init__(self, llm: LLM, served_model: str,
                 tool_parser: Optional[str] = None, engine=None,
                 pin_dp: Optional[int] = None,
                 replica_id: Optional[str] = None):
        from gllm_tpu.entrypoints.tool_parsers import get_tool_parser
        self._llm = llm
        self.engine = engine if engine is not None else ServingEngine(llm)
        self.served_model = served_model
        # per-DP-replica endpoint: every request this state admits is
        # pinned to replica ``pin_dp`` (reference --endpoint-per-dp)
        self.pin_dp = pin_dp
        self.start_time = time.time()
        # fleet identity (docs/robustness.md#fleet-topology--failover):
        # replica_id is stable for the life of THIS process; together
        # with start_time + the supervised-recovery engine generation it
        # lets a front router detect a silent restart (same address, new
        # process) explicitly instead of inferring it from lost streams
        self.replica_id = (replica_id
                           or os.environ.get("GLLM_REPLICA_ID")
                           or uuid.uuid4().hex[:12])
        # jax.profiler state: _profile_mu makes every check+transition
        # atomic across the legacy /start_profile//stop_profile pair
        # and the POST /profile one-shot; _profiling_oneshot marks a
        # capture /stop_profile must not truncate; _profile_lock
        # serializes whole one-shot captures.
        self._profiling = False
        self._profiling_oneshot = False
        self._profile_mu = threading.Lock()
        self._profile_lock = threading.Lock()   # POST /profile one-shot
        self.tool_parser = get_tool_parser(
            tool_parser, llm.config.model or served_model,
            architecture=getattr(llm.model_cfg, "architecture", "") or "")

    @property
    def llm(self):
        """The engine's CURRENT LLM: a supervised in-process rebuild
        (docs/robustness.md#recovery-lifecycle) swaps ServingEngine.llm,
        and every HTTP route must follow the swap instead of serving a
        torn-down engine's state."""
        return getattr(self.engine, "llm", self._llm)

    # ---- request handling -------------------------------------------------

    def encode_chat(self, req: proto.ChatCompletionRequest):
        """Returns (token_ids, mm_input_or_None)."""
        kwargs = dict(req.chat_template_kwargs)
        if req.tools:
            kwargs["tools"] = req.tools
        if self.llm.model_cfg.use_mm:
            messages = _normalize_mm_messages(req.messages)
            try:
                if self.llm.disagg_coordinator is not None:
                    # disagg LM node: text-only skeleton; pixels never
                    # opened here — items ship raw to the encoder fleet
                    ids, items = self.llm.encode_skeleton(messages,
                                                          **kwargs)
                    return ids, ({"disagg_items": items} if items
                                 else None)
                return self.llm.process_mm_messages(messages, **kwargs)
            except proto.ProtocolError:
                raise
            except Exception as e:
                raise proto.ProtocolError(f"multimodal encode failed: {e}")
        # Text-only model: media parts must be rejected, not silently
        # dropped — the caller would believe the model saw the image.
        for m in req.messages:
            c = m.get("content")
            if isinstance(c, list) and any(
                    isinstance(p, dict)
                    and p.get("type") in ("image_url", "image", "video",
                                          "video_url")
                    for p in c):
                raise proto.ProtocolError(
                    "this model is not multimodal; image/video content "
                    "parts are not supported")
        tok = self.llm.tokenizer
        if tok is None:
            raise proto.ProtocolError("server has no tokenizer loaded")
        # render_chat_ids prefers the checkpoint's bundled DSv3.2 message
        # encoder (model-native DSML markup) over the generic template
        return self.llm.render_chat_ids(req.messages, **kwargs), None

    def encode_completion(self, req: proto.CompletionRequest):
        if isinstance(req.prompt, list):
            return list(req.prompt)
        if self.llm.tokenizer is None:
            raise proto.ProtocolError(
                "server has no tokenizer; send token-array prompts")
        return self.llm.tokenizer.encode(req.prompt)


def _split_disagg(mm_input):
    """(mm_input, disagg_items): disagg skeleton requests carry raw items
    under "disagg_items" instead of processor outputs."""
    if mm_input and "disagg_items" in mm_input:
        return None, mm_input["disagg_items"]
    return mm_input, None


def _normalize_mm_messages(messages):
    """OpenAI image content → HF-processor image entries.

    ``image_url`` parts (data: URLs decoded to PIL — the serving host is
    zero-egress, remote URLs are left for the processor to resolve) become
    ``{"type": "image", "image": ...}`` like the reference's
    extract_modify_mm (model_runner.py:663-690)."""
    import base64
    import copy
    import io

    out = copy.deepcopy(messages)
    for message in out:
        contents = message.get("content")
        if not isinstance(contents, list):
            continue
        for content in contents:
            if content.get("type") not in ("image_url", "video_url"):
                continue
            kind = content["type"][:-4]                  # image | video
            data = content.pop(content["type"])
            if isinstance(data, dict):
                data = data.get("url")
            content["type"] = kind
            if isinstance(data, str) and data.startswith("data:"):
                header, _, b64 = data.partition(",")
                raw = base64.b64decode(b64)
                if kind == "image":
                    from PIL import Image
                    data = Image.open(io.BytesIO(raw))
                    data.load()
                else:
                    data = raw
            content[kind] = data
    return out


class Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    state: ServerState = None  # injected

    # quiet default logging; route through logging module
    def log_message(self, fmt, *args):
        logger.debug("%s " + fmt, self.address_string(), *args)

    # ---- helpers ----------------------------------------------------------

    def _json(self, obj, code=200, headers=None):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _text(self, body: str, content_type: str, code=200):
        raw = body.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)

    def _read_json(self) -> dict:
        length = int(self.headers.get("Content-Length", 0))
        raw = self.rfile.read(length) if length else b"{}"
        # the instant the request's body was in hand: admit lag is
        # measured from here (gllm_http_admit_lag_seconds)
        self._t_body = time.monotonic()
        try:
            d = json.loads(raw)
        except json.JSONDecodeError as e:
            raise proto.ProtocolError(f"invalid JSON body: {e}") from e
        if not isinstance(d, dict):
            raise proto.ProtocolError("request body must be a JSON object")
        return d

    def _sse_start(self):
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.end_headers()

    def _sse(self, obj, chunk=None) -> None:
        """One SSE event, flushed. ``chunk``: the StreamChunk it carries;
        its ``t_deliver`` stamp (set by deliver_output on the engine
        thread) to this flush is the emit lag of the token. The flush of
        a request's first token ends its last stage (``emit``): this
        thread writes its ``first_token`` event."""
        self.wfile.write(b"data: " + json.dumps(obj).encode() + b"\n\n")
        self.wfile.flush()
        if chunk is not None:
            self._sent(chunk)

    @staticmethod
    def _sent(chunk) -> None:
        """``chunk``'s event is on the socket: its stamps, where it
        carries any."""
        if chunk.t_deliver or chunk.first_token is not None:
            now = time.monotonic()
            if chunk.t_deliver:
                _M_EMIT_LAG.observe(now - chunk.t_deliver)
            if chunk.first_token is not None:
                chunk.first_token.record(now)

    def _sink(self, handle, make_chunk):
        """What ``RequestHandle.attach`` is offered for a plain stream:
        a middle token's event written from the emitter thread, so that
        a step's tokens cost one thread's turns at the interpreter and
        not a turn of every handler thread. The socket is never waited
        for there: what it does not take at once is this thread's to
        send (``handle.unsent``), and so is everything after it. With a
        fault point armed the chunk goes the handler thread's way, past
        the points in ``_stream``."""
        conn = self.connection

        def sink(chunk) -> bool:
            if faults.FAULTS.active:
                return False
            data = (b"data: " + json.dumps(make_chunk(
                chunk.text or "", None)).encode() + b"\n\n")
            try:
                n = conn.send(data, socket.MSG_DONTWAIT)
            except BlockingIOError:
                n = 0
            if n < len(data):
                handle.unsent = data[n:]
                raise BlockingIOError
            self._sent(chunk)
            return True
        return sink

    # ---- routes -----------------------------------------------------------

    def do_GET(self):
        st = self.state
        if self.path in ("/health", "/healthz"):
            # LIVENESS (docs/robustness.md): 200 while the engine thread
            # runs — even when unhealthy/draining (the supervisor
            # restarts on liveness, the balancer routes on readiness).
            # Replaces the static always-ok /health stub.
            eng = st.engine
            alive = bool(getattr(eng, "is_alive", True))
            body = {"status": "ok" if alive else "dead"}
            health = getattr(eng, "health", None)
            if callable(health):
                body.update(health())
            self._json(body, code=200 if alive else 503)
        elif self.path == "/readyz":
            # READINESS: may this instance be sent new requests? The
            # body carries the latch reason CLASS (step_failures /
            # stall / loop_death / crash_loop — also the
            # gllm_engine_unhealthy_reason info metric) + human detail,
            # so a router can tell a recovering replica (come back
            # after Retry-After) from a crash-looped one (reschedule).
            eng = st.engine
            readiness = getattr(eng, "readiness", None)
            ready, why = readiness() if callable(readiness) \
                else (True, "ok")
            if ready:
                self._json({"status": "ok"})
            else:
                body = {"status": "unavailable", "reason": why}
                cls = getattr(eng, "_unhealthy_class", "")
                if cls:
                    body["unhealthy_reason"] = cls
                    body["detail"] = getattr(eng, "_unhealthy_reason",
                                             "")
                retry_fn = getattr(eng, "retry_after_s", None)
                retry = retry_fn() if callable(retry_fn) else 5.0
                self._json(body, code=503, headers={
                    "Retry-After": str(max(1, int(round(retry))))})
        elif self.path == "/metrics":
            # Prometheus text exposition (gllm_tpu/obs/metrics.py):
            # request-latency histograms (TTFT/TPOT/ITL/e2e/queue),
            # per-step-kind counters, scheduler/KV gauges. Pure host
            # state — scraping never touches the device.
            self._text(obs_metrics.render(),
                       "text/plain; version=0.0.4; charset=utf-8")
        elif self.path.split("?", 1)[0] == "/steptrace":
            # JSON dump of the step-trace ring (pipe into
            # ``python -m gllm_tpu.obs.dump -`` for a readable table);
            # ?since=N resumes from a previous dump's last seq and
            # ?kind=a,b filters by event kind.
            from urllib.parse import parse_qs, urlparse
            from gllm_tpu.obs.steptrace import TRACE, summarize
            q = parse_qs(urlparse(self.path).query)
            try:
                since = int(q.get("since", ["0"])[0])
            except ValueError:
                self._json(proto.error_response(
                    "since must be an integer"), code=400)
                return
            kinds = [k for part in q.get("kind", [])
                     for k in part.split(",") if k]
            events = TRACE.events(since=since, kinds=kinds or None)
            # ``t0``: the ring's epoch on time.monotonic() — an event's
            # ``t`` plus it is an instant on the clock /start_profile and
            # /stop_profile answer with (one machine, one clock)
            self._json({"events": events,
                        "dropped": TRACE.dropped,
                        "next_since": TRACE.mark(),
                        "t0": TRACE.t0,
                        "summary": summarize(events)})
        elif self.path.split("?", 1)[0] == "/trace":
            # Chrome trace-event JSON (Perfetto / chrome://tracing
            # loadable): one track per engine phase, one track per
            # request (this engine's span ring — spans are per-LLM;
            # seq_ids restart per engine). ?since=N limits the step
            # events like /steptrace.
            from urllib.parse import parse_qs, urlparse
            from gllm_tpu.obs.spans import SPANS, chrome_trace
            from gllm_tpu.obs.steptrace import TRACE
            q = parse_qs(urlparse(self.path).query)
            try:
                since = int(q.get("since", ["0"])[0])
            except ValueError:
                self._json(proto.error_response(
                    "since must be an integer"), code=400)
                return
            spans = getattr(st.llm, "spans", SPANS)
            self._json(chrome_trace(
                TRACE.events(since=since),
                spans.spans() + spans.open_spans(),
                span_t0=TRACE.t0))
        elif self.path == "/version":
            self._json({"version": gllm_tpu.__version__})
        elif self.path == "/v1/models":
            self._json({"object": "list", "data": [{
                "id": st.served_model, "object": "model",
                "created": int(st.start_time), "owned_by": "gllm-tpu"}]})
        elif self.path == "/server_info":
            cfg = st.llm.config
            eng = st.engine
            sup = getattr(eng, "supervisor", None)
            self._json({
                "model": cfg.model,
                "uptime_s": round(time.time() - st.start_time, 1),
                # explicit restart detection for the front router
                # (docs/robustness.md#fleet-topology--failover): a new
                # replica_id or start_time at the same address is a
                # process restart (journaled streams are gone); a bumped
                # engine_generation is a SUPERVISED in-process recovery
                # (streams replay locally, the router need not act)
                "replica": {
                    "replica_id": st.replica_id,
                    "start_time": round(st.start_time, 3),
                    "engine_generation": getattr(eng, "_gen", 0),
                    "recoveries": (sup.recoveries
                                   if sup is not None else 0),
                },
                "max_model_len": cfg.max_model_len,
                "schedule_method": cfg.scheduler.schedule_method,
                # pd-pool topology (docs/pd_pools.md): the router's
                # placement layer keys on this role
                "pool_role": cfg.scheduler.pool_role,
                "page_size": cfg.cache.page_size,
                "num_pages": st.llm.runner.num_pages,
                "prefix_caching": cfg.cache.enable_prefix_caching,
                # tiered prefix store (docs/kv_offload.md): which lower
                # tiers are live, and the peer-server address peers
                # should put in their --prefix-peers
                "prefix_store": {
                    "host_pool": cfg.cache.host_pool_configured,
                    "disk_path": cfg.cache.kv_disk_path,
                    "peers": cfg.cache.prefix_peers,
                    "serve_port": (
                        st.llm.prefix_tiers.server.port
                        if getattr(st.llm, "prefix_tiers", None)
                        is not None
                        and st.llm.prefix_tiers.server is not None
                        else None),
                    # per-peer circuit-breaker health (state / trips /
                    # failure counters, docs/robustness.md)
                    "peer_health": (
                        st.llm.prefix_tiers.client.peer_health()
                        if getattr(st.llm, "prefix_tiers", None)
                        is not None
                        and st.llm.prefix_tiers.client is not None
                        else None),
                },
                "parallel": {
                    "tp": cfg.parallel.tp, "dp": cfg.parallel.dp,
                    "pp": cfg.parallel.pp,
                    # per-stage [first, last) layer assignment — None on
                    # the single-runner (pp == 1)
                    "stage_layers": ([list(b) for b in getattr(
                        st.llm.runner, "stage_bounds", [])] or None),
                    # which fast-path flags this topology actually runs
                    # (docs/overlap_scheduling.md#topology-matrix) — the
                    # router/operator sees the lifted combinations, not
                    # just the raw grid
                    "fast_path": {
                        "overlap_scheduling": cfg.overlap_scheduling,
                        "pipelined_loop": cfg.pipelined_loop,
                        "spec_fused": cfg.spec_fused,
                    },
                },
                "attention_impl": st.llm.runner.attn_impl,
                # hybrid models: the recurrent-state slots beside the
                # pages (None for a model without them)
                "ssm_slots": ({"working": st.llm.runner.ssm_working_slots,
                               "snapshot":
                               st.llm.runner.ssm_snapshot_slots}
                              if getattr(st.llm.runner,
                                         "ssm_working_slots", 0) else None),
                # windowed latent layers: a ring of the window's rows a
                # sequence and layer (None for a model without them)
                "swa_rings": ({
                    "slots": st.llm.runner.ssm_working_slots,
                    "rows": st.llm.model_cfg.swa_ring_len(
                        cfg.cache.page_size),
                    "layers": st.llm.model_cfg.num_swa_layers,
                    "window": st.llm.model_cfg.sliding_window}
                    if st.llm.model_cfg.use_swa else None),
                # the device as jax reports it, so a jax-free parent
                # (chip_smoke.py) can say what the server ran on
                "device": _device_info(),
                "waiting": len(st.llm.scheduler.waiting),
                "running": len(st.llm.scheduler.running),
            })
        else:
            self._json(proto.error_response("not found", 404), code=404)

    def do_POST(self):
        try:
            if self.path == "/v1/chat/completions":
                self._chat()
            elif self.path == "/v1/completions":
                self._completion()
            elif self.path == "/start_profile":
                self._profile(True)
            elif self.path == "/stop_profile":
                self._profile(False)
            elif self.path.split("?", 1)[0] == "/profile":
                self._profile_oneshot()
            elif self.path == "/fault_inject":
                self._fault_inject()
            else:
                self._json(proto.error_response("not found", 404), code=404)
        except proto.ProtocolError as e:
            self._json(proto.error_response(str(e)), code=400)
        except RequestRejected as e:
            # admission control (docs/robustness.md): 429 over-capacity /
            # 503 unavailable, always with a Retry-After hint
            self._json(
                proto.error_response(str(e), e.status), code=e.status,
                headers={"Retry-After":
                         str(max(1, int(round(e.retry_after))))})
        except BrokenPipeError:
            pass  # client went away mid-write; abort handled in stream loop
        except Exception as e:  # pragma: no cover
            logger.exception("request failed")
            try:
                self._json(proto.error_response(f"internal error: {e}", 500),
                           code=500)
            except Exception:
                pass

    # ---- chat / completions ----------------------------------------------

    def _submit_choices(self, req, ids, mm_input, disagg_items,
                        count=None, rank_logprobs=False):
        """Submit ``count`` (default ``n``) independent sequences for one
        request (explicit seeds step per choice so seeded requests still
        differ); ``rank_logprobs`` forces chosen-logprob collection for
        best_of ranking."""
        import dataclasses as dc
        st = self.state
        handles = []
        try:
            for i in range(count if count is not None else req.n):
                sp = dc.replace(req.sampling)
                if sp.seed is not None:
                    sp.seed = sp.seed + i
                if rank_logprobs and sp.logprobs is None:
                    sp.logprobs = 0      # chosen-logprob only, for ranking
                handles.append(st.engine.submit(list(ids), sp,
                                                mm_input=mm_input,
                                                disagg_items=disagg_items,
                                                target_dp=st.pin_dp,
                                                received_t=self._t_body))
        except Exception:
            # partial submit must not leak running sequences: abort the
            # choices already admitted before re-raising
            for h in handles:
                st.engine.abort(h.seq_id)
            raise
        return handles

    def _sse_open(self, handles, *chunks) -> bool:
        """Send the SSE preamble (headers + any role chunks). A client
        that disconnected in the submit→stream window otherwise escapes
        every downstream abort handler and leaves the admitted sequences
        generating with no consumer — abort them here instead."""
        try:
            self._sse_start()
            for c in chunks:
                self._sse(c)
            return True
        except (BrokenPipeError, ConnectionResetError):
            for h in handles:
                self.state.engine.abort(h.seq_id)
            return False

    def _stream_many(self, handles, make_chunk):
        """Interleave n request streams into one SSE stream with
        per-choice indices (OpenAI ``stream`` + ``n > 1`` semantics —
        VERDICT r2 parity closure; each handle drains on its own thread
        into a merged queue, so a slow choice never stalls the others)."""
        import queue as _q
        import threading
        merged: "_q.Queue" = _q.Queue()

        def pump(i, h):
            # the sentinel MUST go up even if the handle iterator raises,
            # or the merge loop below waits forever on a dead choice; the
            # error rides along so the consumer can abort the siblings
            err = None
            try:
                for c in h:
                    merged.put((i, c))
            except Exception as e:       # noqa: BLE001 — surfaced below
                err = e
            merged.put((i, (None, err)))

        for i, h in enumerate(handles):
            threading.Thread(target=pump, args=(i, h),
                             daemon=True).start()
        done, first_err = 0, None
        try:
            while done < len(handles):
                i, c = merged.get()
                if isinstance(c, tuple):
                    done += 1
                    first_err = first_err or c[1]
                    continue
                self._sse(make_chunk(c.text or "", c.finish_reason, i), c)
                if c.finish_reason in ("error", "abort", "deadline") \
                        and (c.error or c.retry_after is not None):
                    self._sse(proto.stream_error_event(
                        c.error, c.finish_reason, c.retry_after))
            if first_err is not None:
                # a choice died mid-stream: abort the rest and close the
                # connection without [DONE] so the client sees a broken
                # stream, matching the single-choice path's behavior
                raise first_err
            self.wfile.write(b"data: [DONE]\n\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            for h in handles:
                self.state.engine.abort(h.seq_id)
        except Exception:
            for h in handles:
                self.state.engine.abort(h.seq_id)
            raise

    def _run_choices(self, req, ids, mm_input=None):
        """Submit best_of sequences, collect all, rank by mean logprob when
        best_of > n, return the top n collected dicts (reference n/best_of
        semantics, protocol.py:170-203). Logprobs now flow under dp/pp
        too, so ranking works under every parallel mode."""
        rank = req.best_of > req.n
        mm_input, disagg_items = _split_disagg(mm_input)
        handles = self._submit_choices(req, ids, mm_input, disagg_items,
                                       count=req.best_of,
                                       rank_logprobs=rank)
        results = [self._collect(h) for h in handles]
        if rank:
            def score(r):
                lps = [e[1][0] for e in r["lp"] or [] if e[1] is not None]
                return sum(lps) / len(lps) if lps else float("-inf")
            results.sort(key=score, reverse=True)
        results = results[:req.n]
        prompt_tokens = results[0]["usage"]["prompt_tokens"] if results \
            else 0
        completion = sum(r["usage"]["completion_tokens"] for r in results)
        return results, proto.usage_dict(prompt_tokens, completion)

    def _decode_one(self, token_id: int) -> str:
        tok = self.state.llm.tokenizer
        return tok.decode([token_id]) if tok is not None else str(token_id)

    def _fault_inject(self):
        """Admin fault arming over the wire (chaos harnesses / soak
        rigs only): POST {"spec": "point[:after_n[:count]]"} arms
        gllm_tpu.faults points on this live server, {"reset": true}
        disarms everything. 404 unless GLLM_FAULT_INJECT_HTTP=1 — a
        production server must not expose a self-sabotage endpoint."""
        if os.environ.get("GLLM_FAULT_INJECT_HTTP", "0") in ("", "0"):
            self._json(proto.error_response("not found", 404), code=404)
            return
        body = self._read_json()
        if body.get("reset"):
            faults.FAULTS.reset()
        spec = body.get("spec", "")
        if spec:
            try:
                faults.FAULTS.arm(spec)
            except ValueError as e:
                raise proto.ProtocolError(str(e))
        self._json({"status": "ok",
                    "armed": {p: list(v) for p, v in
                              faults.FAULTS.armed_state().items()},
                    "hits": dict(faults.FAULTS.hits)})

    def _router_preamble(self, rid, ids, sp, mm, disagg):
        """First SSE event of a router-proxied stream
        (docs/robustness.md#fleet-topology--failover): the prompt token
        ids the router needs to journal the stream for cross-replica
        continuation, this replica's identity, and the PR 14 replay-
        safety verdict (None = the stream may fail over mid-flight)."""
        from gllm_tpu.engine.recovery import JournalEntry
        entry = JournalEntry(seq_id=0, prompt=tuple(ids), sampling=sp,
                             mm=mm, disagg=disagg)
        return {"gllm": {
            "prompt_token_ids": [int(t) for t in ids],
            "request_id": rid,
            "replica_id": self.state.replica_id,
            "unsafe_reason": entry.unsafe_reason(),
        }}

    def _chat(self):
        st = self.state
        body = self._read_json()
        # internal front-router extension (gllm_tpu/router/): never set
        # by OpenAI clients; asks for the journaling preamble +
        # per-token ids, and carries the committed prefix when this
        # request CONTINUES a stream a dead replica started
        router = body.pop("gllm_router", None)
        cont = (router or {}).get("continuation")
        req = proto.ChatCompletionRequest.from_dict(
            body, default_max_tokens=256)
        if cont is not None:
            # continuation prompts arrive as the original token ids —
            # re-encoding (and multimodal processing) is skipped; the
            # safety predicate already vetoed mm/disagg router-side
            ids, mm_input = [int(t) for t in
                             cont.get("prompt_token_ids", [])], None
            if not ids:
                raise proto.ProtocolError(
                    "gllm_router.continuation needs prompt_token_ids")
        else:
            ids, mm_input = st.encode_chat(req)
        if cont is not None and (not req.stream or req.n != 1):
            # the n>1 path would silently drop committed_token_ids and
            # stream fresh generations off the bare continuation prompt
            raise proto.ProtocolError(
                "gllm_router.continuation requires stream=true, n=1")
        if not req.stream:
            results, usage = self._run_choices(req, ids, mm_input)
            choices = []
            for r in results:
                text, tool_calls = r["text"], None
                if req.tools and req.tool_choice != "none":
                    from gllm_tpu.entrypoints.tool_parsers import (
                        schemas_from_tools)
                    text, calls = st.tool_parser.parse(
                        text, schemas_from_tools(req.tools))
                    tool_calls = [c.to_openai() for c in calls] or None
                lp = None
                if req.sampling.logprobs is not None:
                    lp = proto.chat_logprobs_content(r["lp"],
                                                     self._decode_one)
                choices.append({"text": text,
                                "finish_reason": r["finish"],
                                "tool_calls": tool_calls, "logprobs": lp})
            self._json(proto.chat_completion_response(req.model, choices,
                                                      usage))
            return
        mm_input, disagg_items = _split_disagg(mm_input)
        parse_tools = bool(req.tools) and req.tool_choice != "none"
        if req.n > 1:
            if parse_tools:
                raise proto.ProtocolError(
                    "stream with n > 1 and tool parsing is not supported")
            rid = proto.new_request_id(chat=True)
            # submit BEFORE the SSE headers go out: a submit-time
            # validation error (e.g. prompt > max_model_len) must still
            # surface as a clean JSON error, not a dead 200 stream
            handles = self._submit_choices(req, ids, mm_input,
                                           disagg_items)
            if not self._sse_open(handles, *[
                    proto.chat_completion_chunk(rid, req.model, None, None,
                                                role=True, index=i)
                    for i in range(req.n)]):
                return
            self._stream_many(handles, lambda text, fin, i: proto.
                              chat_completion_chunk(rid, req.model, text,
                                                    fin, index=i))
            return
        if cont is not None:
            handle = st.engine.submit_continuation(
                ids, cont.get("committed_token_ids", []), req.sampling,
                target_dp=st.pin_dp, received_t=self._t_body)
        else:
            handle = st.engine.submit(list(ids), req.sampling,
                                      mm_input=mm_input,
                                      disagg_items=disagg_items,
                                      target_dp=st.pin_dp,
                                      received_t=self._t_body)
        if req.stream and parse_tools:
            # Incremental tool streaming (reference streams tool deltas):
            # text deltas flow through live; only potential-markup suffixes
            # are held back; completed calls emit OpenAI tool_call deltas.
            from gllm_tpu.entrypoints.tool_parsers import (
                StreamingToolCalls, schemas_from_tools)
            stream = StreamingToolCalls(st.tool_parser,
                                        schemas_from_tools(req.tools))
            rid = proto.new_request_id(chat=True)
            if not self._sse_open(
                    [handle], proto.chat_completion_chunk(
                        rid, req.model, None, None, role=True)):
                return

            def emit(text, deltas):
                if text:
                    self._sse(proto.chat_completion_chunk(rid, req.model,
                                                          text, None))
                for d in deltas:
                    # a structured tool-call delta is on the wire: this
                    # stream can no longer replay across a supervised
                    # engine rebuild (docs/robustness.md#replay-safety)
                    handle.replay_safe = False
                    chunk = proto.chat_completion_chunk(rid, req.model,
                                                        None, None)
                    chunk["choices"][0]["delta"]["tool_calls"] = [d]
                    self._sse(chunk)

            fin = None
            err_ev = None
            try:
                for chunk_out in handle:
                    if chunk_out.first_token is not None:
                        # its text may be held back as potential markup:
                        # the stages end where the chunk is taken
                        chunk_out.first_token.record()
                    emit(*stream.feed(chunk_out.text or ""))
                    fin = chunk_out.finish_reason or fin
                    if fin in ("error", "abort", "deadline") and (
                            chunk_out.error
                            or chunk_out.retry_after is not None):
                        err_ev = proto.stream_error_event(
                            chunk_out.error, fin, chunk_out.retry_after)
                emit(*stream.finish())
                if stream.saw_tool_calls:
                    fin = "tool_calls"
                self._sse(proto.chat_completion_chunk(rid, req.model, None,
                                                      fin))
                if err_ev is not None:
                    self._sse(err_ev)
                self.wfile.write(b"data: [DONE]\n\n")
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):
                st.engine.abort(handle.seq_id)
        elif req.stream:
            rid = ((router or {}).get("request_id")
                   or proto.new_request_id(chat=True))
            preamble = []
            if router is not None:
                preamble.append(self._router_preamble(
                    rid, ids, req.sampling, mm_input is not None,
                    disagg_items is not None))
            if cont is None:
                # a continuation's client already holds the role chunk
                # from the replica that started the stream
                preamble.append(proto.chat_completion_chunk(
                    rid, req.model, None, None, role=True))
            if not self._sse_open([handle], *preamble):
                return
            self._stream(handle, lambda text, fin: proto.
                         chat_completion_chunk(rid, req.model, text, fin),
                         router=router is not None,
                         push_to=(None if cont is not None else
                                  (router or {}).get("push_to")),
                         prompt_ids=ids)

    def _completion(self):
        st = self.state
        body = self._read_json()
        router = body.pop("gllm_router", None)
        cont = (router or {}).get("continuation")
        req = proto.CompletionRequest.from_dict(
            body, default_max_tokens=256)
        if cont is not None:
            if not req.stream or req.n != 1:
                raise proto.ProtocolError(
                    "gllm_router.continuation requires stream=true, n=1")
            ids = [int(t) for t in cont.get("prompt_token_ids", [])]
            if not ids:
                raise proto.ProtocolError(
                    "gllm_router.continuation needs prompt_token_ids")
        else:
            ids = st.encode_completion(req)
        if req.stream:
            rid = ((router or {}).get("request_id")
                   or proto.new_request_id(chat=False))
            # submit before the SSE headers (see _chat): submit errors
            # still get a JSON error response
            if req.n > 1:
                handles = self._submit_choices(req, ids, None, None)
                if not self._sse_open(handles):
                    return
                self._stream_many(handles, lambda text, fin, i: proto.
                                  completion_chunk(rid, req.model,
                                                   text or "", fin,
                                                   index=i))
                return
            if cont is not None:
                handle = st.engine.submit_continuation(
                    ids, cont.get("committed_token_ids", []),
                    req.sampling, target_dp=st.pin_dp,
                    received_t=self._t_body)
            else:
                handle = st.engine.submit(ids, req.sampling,
                                          target_dp=st.pin_dp,
                                          received_t=self._t_body)
            preamble = []
            if router is not None:
                preamble.append(self._router_preamble(
                    rid, ids, req.sampling, False, False))
            if not self._sse_open([handle], *preamble):
                return
            self._stream(handle, lambda text, fin: proto.completion_chunk(
                rid, req.model, text or "", fin),
                router=router is not None,
                push_to=(None if cont is not None else
                         (router or {}).get("push_to")),
                prompt_ids=ids)
            return
        results, usage = self._run_choices(req, ids)
        choices = []
        for r in results:
            text = r["text"]
            lp = None
            if req.sampling.logprobs is not None \
                    or req.sampling.prompt_logprobs is not None:
                entries = []
                offset0 = 0
                if req.echo and r["plp"] is not None:
                    entries.extend(
                        (tid, e) for tid, e in zip(ids, r["plp"]))
                lp_list = r["lp"] or []
                entries.extend(lp_list)
                lp = proto.completion_logprobs(entries, self._decode_one,
                                               offset0)
            if req.echo and isinstance(req.prompt, str):
                text = req.prompt + text
            choices.append({"text": text, "finish_reason": r["finish"],
                            "logprobs": lp})
        self._json(proto.completion_response(req.model, choices, usage))

    def _collect(self, handle):
        """Drain one request's stream → {"text", "finish", "usage", "lp"
        [(token_id, entry)], "plp"}."""
        text_parts, finish = [], "stop"
        usage = proto.usage_dict(0, 0)
        lp, plp, final_text = [], None, None
        for chunk in handle:
            if chunk.first_token is not None:
                # nothing is flushed for the token of an unstreamed
                # reply: its stages end here, with no ``emit``
                chunk.first_token.record()
            if chunk.text:
                text_parts.append(chunk.text)
            if chunk.token_id is not None and chunk.logprob is not None:
                lp.append((chunk.token_id, chunk.logprob))
            if chunk.finish_reason is not None:
                finish = chunk.finish_reason
                usage = proto.usage_dict(chunk.num_prompt_tokens,
                                         chunk.num_output_tokens)
                plp = chunk.prompt_logprobs
                final_text = chunk.final_text
        text = final_text if final_text is not None \
            else "".join(text_parts)
        return {"text": text, "finish": finish,
                "usage": usage, "lp": lp or None, "plp": plp}

    def _stream(self, handle, make_chunk, router: bool = False,
                push_to=None, prompt_ids=None):
        pushed_pages = None
        try:
            if not router and getattr(self, "connection", None) is not None:
                handle.attach(self._sink(handle, make_chunk))
            for chunk in handle:
                if handle.unsent:
                    self.wfile.write(handle.unsent)
                    handle.unsent = b""
                    # this chunk's own event, which the sink had begun
                    self._sent(chunk)
                    continue
                # chaos points (docs/robustness.md#fleet): replica_kill
                # hard-closes the connection mid-stream — from a front
                # router's side this is the serving process dying;
                # replica_hang stalls before the next chunk (the wedged
                # replica the router's idle timeout must catch)
                if faults.FAULTS.fire("replica_kill"):
                    self.state.engine.abort(handle.seq_id)
                    try:
                        self.connection.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                    self.close_connection = True
                    return
                faults.FAULTS.maybe_stall("replica_hang")
                # one SSE event per generated token (even when incremental
                # detokenization held text back) — clients measure ITL from
                # event arrivals
                ev = make_chunk(chunk.text or "", chunk.finish_reason)
                if router and chunk.token_id is not None:
                    # per-token ids for the front router's stream
                    # journal (stripped before the client sees them)
                    ev["gllm"] = {"token_id": int(chunk.token_id)}
                    if push_to and pushed_pages is None:
                        # pd-pool handoff (docs/pd_pools.md): the first
                        # sampled token means prefill is done — ship the
                        # prompt's prefix KV chain to the router-picked
                        # decode replica and report the accepted count
                        # so the router can migrate with zero re-prefill
                        pushed_pages = self.state.engine.push_prefix(
                            prompt_ids or [], push_to)
                        ev["gllm"]["pushed_pages"] = int(pushed_pages)
                self._sse(ev, chunk)
                if chunk.finish_reason in ("error", "abort", "deadline") \
                        and (chunk.error
                             or chunk.retry_after is not None):
                    self._sse(proto.stream_error_event(
                        chunk.error, chunk.finish_reason,
                        chunk.retry_after))
            self.wfile.write(b"data: [DONE]\n\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            # client disconnect → abort the sequence
            # (reference async_llm_engine.py:113-126)
            self.state.engine.abort(handle.seq_id)

    # ---- profiler (reference profiler_mixin.py:12-117) --------------------

    def _start_capture(self):
        """Start the profiler the way this server always does: into
        ``GLLM_PROFILE_DIR``, with the Python tracer OFF (jax's default
        traces every Python call of the engine thread and of every
        handler thread: it inflates the host time the capture is taken
        to measure, and the read-back at the stop) and the host tracer
        at level 1, the lowest that records TraceAnnotations; then turn
        the engine-loop phases into ``gllm:*`` spans (obs/spans.py).
        Returns (trace_dir, time.monotonic() at the start). Caller holds
        ``_profile_mu``."""
        import jax
        from gllm_tpu.obs import spans
        trace_dir = os.environ.get("GLLM_PROFILE_DIR",
                                   "/tmp/gllm_tpu_profile")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        spans.set_capture(True)
        return trace_dir, time.monotonic()

    @staticmethod
    def _stop_capture() -> float:
        """Spans off first, so that no phase opens an annotation the
        stopped profiler would never close. Returns time.monotonic() at
        the stop (before the trace is written). Caller holds
        ``_profile_mu``."""
        import jax
        from gllm_tpu.obs import spans
        spans.set_capture(False)
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        return t_stop

    def _profile(self, start: bool):
        st = self.state
        with st._profile_mu:
            if start and not st._profiling:
                trace_dir, t_start = self._start_capture()
                st._profiling = True
                # ``t_monotonic``: the server's time.monotonic() at the
                # start (at the stop below) — a caller on the same
                # machine places the slice on its own clock by it, not
                # by the request's round trip
                self._json({"status": "profiling started",
                            "trace_dir": trace_dir,
                            "t_monotonic": t_start})
            elif not start and st._profiling_oneshot:
                # a POST /profile capture owns the profiler right now —
                # stopping it here would truncate that capture and make
                # its own stop_trace raise
                self._json(proto.error_response(
                    "a one-shot /profile capture is in progress", 409),
                    code=409)
            elif not start and st._profiling:
                try:
                    t_stop = self._stop_capture()
                finally:
                    st._profiling = False
                self._json({"status": "profiling stopped",
                            "t_monotonic": t_stop})
            else:
                self._json({"status": "noop"})

    def _profile_oneshot(self):
        """POST /profile?seconds=N — one-shot jax.profiler capture:
        start, sleep N seconds (serving continues; the engine thread is
        untouched), stop, return the artifact directory. The
        start/stop pair above remains for manual bracketing; this is
        the capture-and-return call a bench/ops script wants."""
        from urllib.parse import parse_qs, urlparse
        st = self.state
        q = parse_qs(urlparse(self.path).query)
        try:
            seconds = float(q.get("seconds", ["3"])[0])
        except ValueError:
            self._json(proto.error_response("seconds must be a number"),
                       code=400)
            return
        if not 0 < seconds <= 120:
            self._json(proto.error_response(
                "seconds must be in (0, 120]"), code=400)
            return
        if not st._profile_lock.acquire(blocking=False):
            self._json(proto.error_response(
                "a profile capture is already running", 409), code=409)
            return
        try:
            # check + start atomically vs /start_profile (_profile_mu):
            # a racing manual start must not double-start the profiler
            with st._profile_mu:
                if st._profiling:
                    self._json(proto.error_response(
                        "profiler already started via /start_profile",
                        409), code=409)
                    return
                st._profiling = True
                st._profiling_oneshot = True
                trace_dir, t_start = self._start_capture()
            try:
                time.sleep(seconds)
            finally:
                with st._profile_mu:
                    try:
                        t_stop = self._stop_capture()
                    finally:
                        st._profiling = False
                        st._profiling_oneshot = False
            self._json({"status": "ok", "seconds": seconds,
                        "trace_dir": trace_dir,
                        "t_monotonic": [t_start, t_stop]})
        finally:
            st._profile_lock.release()


def build_engine_config(args) -> EngineConfig:
    return EngineConfig(
        model=args.model,
        tokenizer=args.tokenizer,
        dtype=args.dtype,
        seed=args.seed,
        max_model_len=args.max_model_len,
        max_num_seqs=args.max_num_seqs,
        load_format=args.load_format,
        allow_hub_download=args.allow_hub_download,
        attention_impl=args.attention_impl,
        overlap_scheduling=args.overlap_scheduling,
        pipelined_loop=args.pipelined_loop,
        overlap_depth=args.inflight_depth,
        decode_slot_batching=args.decode_slot_batching,
        chain_under_prefill=args.chain_under_prefill,
        decode_chain_len=args.decode_chain_len,
        ondevice_finish=args.ondevice_finish,
        spec_decode=args.spec_decode,
        spec_k=args.spec_k,
        spec_ngram=args.spec_ngram,
        spec_fused=args.spec_fused,
        quantization=args.quantization,
        sp_ring_threshold=args.sp_ring_threshold,
        mm_processor_min_pixels=args.mm_processor_min_pixels,
        mm_processor_max_pixels=args.mm_processor_max_pixels,
        tracing=not args.no_tracing,
        max_queued_requests=args.max_queued_requests,
        max_resident_requests=args.max_resident_requests,
        request_deadline_s=args.request_deadline_s,
        max_step_failures=args.max_step_failures,
        watchdog_stall_s=args.watchdog_stall_s,
        drain_timeout_s=args.drain_timeout_s,
        engine_recovery=args.engine_recovery,
        max_rebuilds=args.max_rebuilds,
        rebuild_window_s=args.rebuild_window_s,
        rebuild_backoff_s=args.rebuild_backoff_s,
        rebuild_backoff_max_s=args.rebuild_backoff_max_s,
        watchdog_hard_stall_s=args.watchdog_hard_stall_s,
        fault_inject=args.fault_inject,
        scheduler=SchedulerConfig(
            schedule_method=args.schedule_method,
            max_decode_seqs=args.maxd,
            max_prefill_tokens=args.maxp,
            min_prefill_tokens=args.minp,
            min_token_bucket=args.min_token_bucket,
            min_row_bucket=args.min_row_bucket,
            min_page_bucket=args.min_page_bucket,
            iter_smooth=args.iterp,
            init_new_token_ratio=args.init_new_token_ratio,
            min_new_token_ratio=args.min_new_token_ratio,
            pool_role=args.pool_role,
        ),
        enforce_eager=args.enforce_eager,
        cache=CacheConfig(
            page_size=args.page_size,
            memory_util=args.memory_util,
            num_pages=args.num_pages,
            kv_cache_dtype=args.kv_cache_dtype,
            enable_prefix_caching=args.enable_prefix_caching,
            kv_host_pool_gb=args.kv_host_pool_gb,
            swap_policy=args.swap_policy,
            kv_disk_path=args.kv_disk_path,
            kv_disk_gb=args.kv_disk_gb,
            prefix_peers=args.prefix_peers,
            prefix_serve_port=args.prefix_serve_port,
        ),
        parallel=ParallelConfig(
            pp=args.pp, tp=args.tp, dp=args.dp,
            sp=args.sp, enable_ep=args.enable_ep,
            assigned_layers=([int(x) for x in
                              args.assigned_layers.split(",") if x]
                             if args.assigned_layers else None)),
    )


def _sigterm_as_interrupt() -> None:
    """SIGTERM (what a supervisor sends) takes the same graceful-drain
    exit as Ctrl-C, and the process then exits 0."""
    import signal
    import threading
    if threading.current_thread() is not threading.main_thread():
        return      # embedded in a thread: the host application owns signals

    def _raise(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _raise)


def _device_info() -> dict:
    """The devices as jax reports them, with each local device's memory
    (limit / in use / peak; None where the backend reports none)."""
    import jax
    devices = jax.devices()
    keys = ("bytes_limit", "bytes_in_use", "peak_bytes_in_use")
    memory = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        memory.append({k: stats.get(k) for k in keys} if stats else None)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory": memory}


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="gllm-tpu OpenAI-compatible API server")
    p.add_argument("--model", required=True)
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-model-len", type=int, default=4096)
    p.add_argument("--max-num-seqs", type=int, default=256)
    p.add_argument("--load-format", default="auto",
                   choices=["auto", "dummy"])
    p.add_argument("--attention-impl", default="auto",
                   choices=["auto", "pallas", "xla"])
    # scheduler (reference --schedule-method/--maxd/--maxp/--minp/--iterp)
    p.add_argument("--schedule-method", default="chunked_prefill",
                   choices=["chunked_prefill", "token_throttling",
                            "split_pd"])
    p.add_argument("--maxd", type=int, default=256)
    p.add_argument("--maxp", type=int, default=2048)
    p.add_argument("--minp", type=int, default=128)
    p.add_argument("--iterp", type=int, default=16)
    p.add_argument("--min-token-bucket", type=int, default=16,
                   help="smallest token bucket of a mixed step (a power "
                        "of two): fewer step programs to compile, "
                        "shorter chunks padded to it")
    p.add_argument("--min-row-bucket", type=int, default=8,
                   help="smallest row bucket of a step (a power of two, "
                        "capped at the largest step's rows)")
    p.add_argument("--min-page-bucket", type=int, default=4,
                   help="smallest page-table width of a step (a power of "
                        "two, capped at --max-model-len in pages)")
    p.add_argument("--pool-role", default="mixed",
                   choices=["prefill", "decode", "mixed"],
                   help="pd-pool role advertised on /server_info "
                        "(docs/pd_pools.md): the front router places "
                        "new prompts on prefill replicas and migrates "
                        "each stream to a decode replica at first "
                        "token, pushing the prefix KV chain ahead of "
                        "it; mixed (default) serves both phases")
    p.add_argument("--init-new-token-ratio", type=float, default=0.7,
                   help="adaptive KV admission ramp start (reference "
                        "--init-new-token-ratio)")
    p.add_argument("--min-new-token-ratio", type=float, default=0.1,
                   help="admission ramp floor")
    p.add_argument("--enforce-eager", action="store_true",
                   help="disable donation/async dispatch tricks (debug; "
                        "the reference's --disable-cuda-graph analogue)")
    # cache
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--memory-util", type=float, default=0.9,
                   help="fraction of device memory for the KV cache")
    p.add_argument("--num-pages", type=int, default=None)
    p.add_argument("--kv-cache-dtype", default="auto",
                   choices=("auto", "bfloat16", "float16", "float32",
                            "fp8", "int8"),
                   help="paged-KV storage dtype; int8 stores quantized "
                        "K/V with per-page per-head scales dequantized "
                        "in-kernel (halves KV reads, ~2x page capacity; "
                        "docs/kv_quantization.md). auto = model dtype")
    p.add_argument("--quantization", default=None,
                   choices=["int8", "fp8", "int4", "w8a8", "fp8_block"],
                   help="weight-only quantization")
    p.add_argument("--enable-prefix-caching", action="store_true")
    p.add_argument("--kv-host-pool-gb", type=float, default=0.0,
                   help="host-RAM KV tier size in GiB (gllm_tpu/kvswap):"
                        " preemption victims swap out instead of "
                        "recomputing, evicted prefix pages spill here; "
                        "0 disables the tier (docs/kv_offload.md)")
    p.add_argument("--swap-policy", default="auto",
                   choices=["auto", "swap", "recompute"],
                   help="auto: swap iff a host pool is configured; "
                        "swap: require the pool; recompute: legacy "
                        "free-and-recompute preemption")
    p.add_argument("--kv-disk-path", default=None,
                   help="disk prefix tier behind the host pool: "
                        "content-addressed page files under this "
                        "directory, written on host-tier eviction, "
                        "probed on host miss (needs "
                        "--enable-prefix-caching and --kv-host-pool-gb; "
                        "docs/kv_offload.md)")
    p.add_argument("--kv-disk-gb", type=float, default=4.0,
                   help="byte budget of the disk prefix tier "
                        "(LRU-evicted above it)")
    p.add_argument("--prefix-peers", default=None,
                   help="comma-separated host:port of peer replicas' "
                        "prefix servers — match_prefix restores "
                        "digest-addressed pages another replica "
                        "computed (docs/kv_offload.md)")
    p.add_argument("--prefix-serve-port", type=int, default=None,
                   help="serve this replica's prefix pages to peers on "
                        "this port (0 = ephemeral; omit to not serve)")
    p.add_argument("--allow-hub-download", action="store_true",
                   help="resolve a non-local model id via HF-hub snapshot "
                        "download (file-lock serialized); default is "
                        "local-path-only")
    p.add_argument("--overlap-scheduling", action="store_true",
                   help="chain decode steps on-device (no host round trip "
                        "between decode iterations)")
    p.add_argument("--pipelined-loop", action="store_true",
                   help="bubble-zero engine loop: speculatively re-form "
                        "the next decode batch off promised token counts "
                        "when a chain breaks (finish, compaction, "
                        "membership growth) instead of draining the "
                        "pipeline; divergence is reconciled at collect "
                        "time (implies --overlap-scheduling; "
                        "docs/overlap_scheduling.md#pipelined-loop)")
    p.add_argument("--inflight-depth", type=int, default=2,
                   help="max dispatched-but-uncollected engine entries "
                        "under --overlap-scheduling (the pipelined "
                        "loop's run-ahead bound; depth 2 hides host "
                        "batch building, deeper also hides the "
                        "remote-dispatch round trip)")
    p.add_argument("--decode-slot-batching", action="store_true",
                   help="persistent-slot decode chains (needs "
                        "--overlap-scheduling): finished rows become "
                        "masked holes instead of breaking the fused "
                        "chain, decode-ready seqs join vacant slots at "
                        "chain boundaries (docs/overlap_scheduling.md)")
    p.add_argument("--chain-under-prefill", type=int, default=0,
                   help="with prefill work waiting, chain up to K decode "
                        "steps before yielding one sync pass to prefill; "
                        "0 = legacy, any waiting arrival unfuses every "
                        "step until the queue drains")
    p.add_argument("--decode-chain-len", type=int, default=None,
                   help="fused decode chain length: K decode steps per "
                        "device dispatch (needs --overlap-scheduling); "
                        "default 1, or 16 with --ondevice-finish")
    p.add_argument("--ondevice-finish", action="store_true",
                   help="detect EOS/stop-token finishes INSIDE fused "
                        "decode blocks (carried alive mask + early block "
                        "exit) instead of burning dead sub-steps until "
                        "the host notices; token streams are identical "
                        "(docs/overlap_scheduling.md)")
    p.add_argument("--spec-decode", default=None, choices=["ngram"],
                   help="prompt-lookup speculative decoding: verify up to "
                        "--spec-k n-gram drafts per decode step (greedy "
                        "requests only; byte-identical outputs)")
    p.add_argument("--spec-k", type=int, default=4)
    p.add_argument("--spec-ngram", type=int, default=2)
    p.add_argument("--spec-fused", action="store_true",
                   help="fuse draft+verify into the chained multi-step "
                        "dispatch (requires --spec-decode ngram): the "
                        "device drafts from a carried recent-token ring "
                        "and one dispatch emits up to K*(spec_k+1) "
                        "tokens; greedy streams byte-identical, chains "
                        "and speculation compose "
                        "(docs/speculative_decoding.md)")
    p.add_argument("--mm-processor-min-pixels", type=int, default=None,
                   help="lower bound on image/video resolution fed to the "
                        "multimodal processor (reference "
                        "api_server.py:488-494)")
    p.add_argument("--mm-processor-max-pixels", type=int, default=None,
                   help="upper bound on image/video resolution — the "
                        "lever that keeps large-image workloads inside "
                        "HBM")
    p.add_argument("--endpoint-per-dp", action="store_true",
                   help="one HTTP listener per DP replica, each pinning "
                        "its requests to that replica (session affinity "
                        "keeps a conversation's prefix cache on one "
                        "replica; reference --endpoint-per-dp)")
    p.add_argument("--endpoint-per-dp-ports", default=None,
                   help="comma-separated ports, one per replica in "
                        "DP-rank order (default: port, port+1, ...)")
    p.add_argument("--tool-call-parser", default=None,
                   choices=["qwen", "hermes", "deepseek", "none"],
                   help="tool-call markup parser (default: auto-detect "
                        "from model name)")
    # request-lifecycle robustness (docs/robustness.md)
    p.add_argument("--max-queued-requests", type=int, default=0,
                   help="admission bound on the intake queue; over-limit "
                        "submits get HTTP 429 + Retry-After instead of "
                        "queueing unboundedly (0 = unbounded)")
    p.add_argument("--max-resident-requests", type=int, default=0,
                   help="cap on concurrently open request streams; "
                        "beyond it submits get HTTP 429 (0 = unbounded)")
    p.add_argument("--request-deadline-s", type=float, default=0.0,
                   help="default wall-clock TTL per request: waiting or "
                        "overrunning requests are aborted with finish "
                        "reason 'deadline' (0 = none; per-request "
                        "deadline_s overrides)")
    p.add_argument("--max-step-failures", type=int, default=3,
                   help="consecutive failed engine steps before the "
                        "engine latches unhealthy (readiness 503); "
                        "individual failures only abort their own batch")
    p.add_argument("--watchdog-stall-s", type=float, default=0.0,
                   help="flip /readyz to 503 while the engine heartbeat "
                        "is staler than this (hung device dispatch); "
                        "must exceed the longest legitimate compile "
                        "(0 = watchdog off)")
    p.add_argument("--drain-timeout-s", type=float, default=5.0,
                   help="graceful-shutdown budget for in-flight requests "
                        "before they are aborted with terminal chunks")
    p.add_argument("--engine-recovery", action="store_true",
                   help="supervised in-process recovery "
                        "(docs/robustness.md): an unhealthy latch / "
                        "engine-loop death / watchdog hard stall tears "
                        "the engine down and rebuilds it in-process — "
                        "/readyz reports 'recovering' with Retry-After "
                        "and retry-safe (seeded or greedy) requests "
                        "replay from their committed prefix")
    p.add_argument("--max-rebuilds", type=int, default=3,
                   help="crash-loop latch: this many FAILED rebuilds "
                        "within --rebuild-window-s latch the permanent "
                        "unhealthy state (never an infinite rebuild "
                        "loop)")
    p.add_argument("--rebuild-window-s", type=float, default=300.0)
    p.add_argument("--rebuild-backoff-s", type=float, default=0.25,
                   help="first-retry rebuild backoff; doubles per "
                        "failure up to --rebuild-backoff-max-s")
    p.add_argument("--rebuild-backoff-max-s", type=float, default=30.0)
    p.add_argument("--watchdog-hard-stall-s", type=float, default=0.0,
                   help="heartbeat age that ESCALATES a watchdog stall "
                        "to a supervised rebuild (abandons the wedged "
                        "engine thread; needs --engine-recovery and "
                        "--watchdog-stall-s; 0 = soft readiness flips "
                        "only)")
    p.add_argument("--replica-id", default=None,
                   help="stable fleet identity advertised on "
                        "/server_info (with start_time + engine "
                        "generation) so a front router detects silent "
                        "process restarts; default: random per process "
                        "(env GLLM_REPLICA_ID)")
    p.add_argument("--fault-inject", default="",
                   help="deterministic fault injection spec "
                        "'point[:after_n[:count]][,...]' "
                        "(gllm_tpu/faults.py; chaos testing only)")
    p.add_argument("--no-tracing", action="store_true",
                   help="disable the request-span tracing layer "
                        "(GET /trace request tracks; the engine-phase "
                        "attribution and the first_token events on "
                        "/steptrace stay on). Token "
                        "streams are byte-identical either way "
                        "(docs/observability.md#tracing)")
    p.add_argument("--skip-warmup", action="store_true",
                   help="don't pre-compile decode buckets before serving "
                        "(first requests pay compile latency instead)")
    # parallelism / multi-host (reference --launch-mode master|slave →
    # jax.distributed coordinator/worker)
    p.add_argument("--coordinator-address", default=None,
                   help="host:port of host 0 for multi-host serving")
    p.add_argument("--blob-advertise-host", default=None,
                   help="address followers use to reach host 0's bulk-"
                        "payload (MM pixel) server; default resolves "
                        "gethostname(), which is wrong on hosts whose "
                        "/etc/hosts maps the hostname to loopback")
    p.add_argument("--num-hosts", type=int, default=1)
    p.add_argument("--host-id", type=int, default=None)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--assigned-layers", default=None,
                   help="comma-separated per-stage layer counts for pp "
                        "(reference --assigned-layers)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1,
                   help="sequence parallelism: long prefill chunks run "
                        "ring attention over an sp mesh axis (beyond the "
                        "reference); requires pp=dp=1")
    p.add_argument("--sp-ring-threshold", type=int, default=1024)
    p.add_argument("--enable-ep", action="store_true")
    return p


def serve(llm: LLM, host: str, port: int,
          served_model: Optional[str] = None,
          tool_parser: Optional[str] = None,
          pin_dp: Optional[int] = None,
          engine=None,
          replica_id: Optional[str] = None) -> ThreadingHTTPServer:
    """Build the HTTP server (caller decides foreground vs thread)."""
    state = ServerState(llm, served_model or llm.config.model, tool_parser,
                        engine=engine, pin_dp=pin_dp,
                        replica_id=replica_id)
    handler = type("BoundHandler", (Handler,), {"state": state})
    httpd = ThreadingHTTPServer((host, port), handler)
    httpd.state = state
    return httpd


def serve_per_dp(llm: LLM, host: str, ports: List[int],
                 served_model: Optional[str] = None,
                 tool_parser: Optional[str] = None
                 ) -> List[ThreadingHTTPServer]:
    """One HTTP listener per DP replica, all sharing ONE engine: listener
    d pins its requests to replica d, so a client holding a conversation
    on one endpoint keeps its prefix cache (and KV) on one replica
    (reference --endpoint-per-dp, api_server.py run_server +
    llm_engine.py:121-133 pinning)."""
    assert len(ports) == llm.dp, (len(ports), llm.dp)
    engine = ServingEngine(llm)
    return [serve(llm, host, p, served_model, tool_parser,
                  pin_dp=d, engine=engine)
            for d, p in enumerate(ports)]


def main(argv=None):
    logging.basicConfig(level=logging.INFO)
    args = make_parser().parse_args(argv)
    multihost = False
    if args.num_hosts > 1 or args.coordinator_address:
        from gllm_tpu.parallel.multihost import init_multihost
        init_multihost(args.coordinator_address, args.num_hosts,
                       args.host_id)
        import jax
        multihost = jax.process_count() > 1
    t_start = time.monotonic()
    llm = LLM(config=build_engine_config(args))
    if not args.skip_warmup:
        llm.runner.warmup()
        if not multihost:
            # Serving-readiness yardstick (reference: CUDA-graph capture
            # logs): one real token through the full engine path.
            from gllm_tpu.sampling_params import SamplingParams
            t0 = time.monotonic()
            llm.generate(prompt_token_ids=[[1, 2, 3]],
                         sampling_params=SamplingParams(
                             temperature=0.0, max_tokens=1,
                             ignore_eos=True))
            logger.info("[startup] phase=first_token seconds=%.2f "
                        "total_startup_seconds=%.2f",
                        time.monotonic() - t0,
                        time.monotonic() - t_start)
    if multihost:
        # Host 0 runs the HTTP frontend + broadcasts every tick's intake;
        # followers mirror the deterministic engine loop so all processes
        # issue identical jit programs (the role of the reference's zmq
        # master/slave plane, comm.py:191-319).
        import jax

        from gllm_tpu.parallel.multihost_engine import (
            MultihostEngine, MultihostServingEngine)
        if jax.process_index() != 0:
            logger.info("follower %d joined; mirroring engine loop",
                        jax.process_index())
            MultihostEngine(llm).run_follower()
            return
        state = ServerState(llm, args.served_model_name or args.model,
                            tool_parser=args.tool_call_parser,
                            engine=MultihostServingEngine(
                                llm,
                                advertise_host=args.blob_advertise_host))
        handler = type("BoundHandler", (Handler,), {"state": state})
        httpd = ThreadingHTTPServer((args.host, args.port), handler)
        httpd.state = state
    elif args.endpoint_per_dp and args.dp > 1:
        if args.endpoint_per_dp_ports:
            ports = [int(p) for p in
                     args.endpoint_per_dp_ports.split(",") if p]
            if len(ports) != args.dp:
                raise SystemExit(
                    f"--endpoint-per-dp-ports has {len(ports)} ports "
                    f"but dp={args.dp}")
        else:
            ports = [args.port + d for d in range(args.dp)]
        servers = serve_per_dp(llm, args.host, ports,
                               args.served_model_name or args.model,
                               tool_parser=args.tool_call_parser)
        logger.info("DP per-replica endpoints: %s",
                    ", ".join(f"dp{d}->:{p}"
                              for d, p in enumerate(ports)))
        import threading
        threads = [threading.Thread(target=s.serve_forever, daemon=True)
                   for s in servers[1:]]
        for t in threads:
            t.start()
        try:
            servers[0].serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            for s in servers[1:]:
                s.shutdown()
            servers[0].state.engine.shutdown(drain=True)
        return
    else:
        httpd = serve(llm, args.host, args.port,
                      args.served_model_name or args.model,
                      tool_parser=args.tool_call_parser,
                      replica_id=args.replica_id)
    logger.info("serving %s on %s:%d", args.model, args.host, args.port)
    _sigterm_as_interrupt()
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        # graceful drain: stop admitting, let in-flight requests finish
        # (bounded), close every open stream with a terminal chunk, join
        eng = httpd.state.engine
        try:
            eng.shutdown(drain=True)
        except TypeError:   # MultihostServingEngine: no drain support
            eng.shutdown()


if __name__ == "__main__":
    main()
