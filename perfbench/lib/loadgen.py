"""Load generation from the client's side of the HTTP stream.

A copy of the idea in ``benchmarks/backend_request_func.py`` with its flaw
repaired: a request is timed from the instant it was DUE, not from the
instant this generator got round to sending it, and the generator's own
lateness is kept beside it. One thread per open stream; every token event
is stamped on arrival. All times are seconds on ``time.monotonic()``
relative to ``zero``, the first instant of the measured window, so the
warm-up and ramp are negative. Where the window opens on an event and not
at a planned instant (a closed loop's fill), ``zero`` is provisional until
``Load.open`` moves it and every time stamped so far with it: stamps and
the move share one lock.
"""

import array
import http.client
import json
import socket
import threading
import time


class Req:
    """One request and what happened to it."""

    __slots__ = ("idx", "client", "due", "prompt", "max_tokens", "sent",
                 "times", "status", "ended", "sock", "first")

    def __init__(self, idx, prompt, max_tokens, due=None, client=None):
        self.idx, self.client, self.due = idx, client, due
        self.prompt, self.max_tokens = prompt, max_tokens
        self.sent = None            # when the request left this process
        self.times = array.array("d")   # arrival of each output token
        self.status = "planned"     # ok | cut | failed: <why>
        self.ended = None
        self.sock = None
        self.first = None           # an Event, set at the first token


class Clock:
    """Seconds relative to ``zero`` (set once the load starts)."""

    def __init__(self):
        self.zero = None

    def now(self):
        return time.monotonic() - self.zero

    def sleep_until(self, t):
        wait = t - self.now()
        if wait > 0:
            time.sleep(wait)


class Load:
    """Shared state of one load: the clock, the stop flag, open streams."""

    def __init__(self, port, model="bench"):
        self.port, self.model = port, model
        self.clock = Clock()
        self.stopping = threading.Event()
        self.opened = threading.Event()     # ``zero`` is final
        self.lock = threading.Lock()
        self.open = set()
        self.threads = []
        self.records = []

    def stream(self, req):
        """Send ``req`` now and stamp its tokens until the stream ends."""
        clock = self.clock
        body = json.dumps({
            "model": self.model, "prompt": req.prompt,
            "max_tokens": req.max_tokens, "ignore_eos": True,
            "temperature": 0.0, "stream": True})
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=600)
        try:
            with self.lock:
                req.sent = clock.now()
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            # the response takes the socket over from the connection
            # (conn.sock becomes None), so keep it to be able to cut it
            req.sock = conn.sock
            with self.lock:
                self.open.add(req)
            if self.stopping.is_set():
                req.status = "cut"
                return
            resp = conn.getresponse()
            if resp.status != 200:
                req.status = (f"failed: HTTP {resp.status} "
                              f"{resp.read()[:120]!r}")
                return
            buf, finished = b"", False
            while True:
                # read1 returns what has arrived; read(n) would wait for n
                # bytes and batch several tokens into one arrival
                chunk = resp.read1(65536)
                if not chunk:
                    break
                buf += chunk
                events = []
                while True:
                    event, sep, rest = buf.partition(b"\n\n")
                    if not sep:
                        break
                    buf = rest
                    events.append(event)
                n = sum(b'"choices"' in e for e in events)
                with self.lock:     # one event, one token
                    req.times.extend([clock.now()] * n)
                if n and req.first is not None:
                    req.first.set()
                for event in events:
                    if b'"choices"' in event:
                        if (b'"finish_reason": null' not in event
                                and b'"finish_reason":null' not in event):
                            finished = True
                    elif b'"error"' in event:
                        req.status = f"failed: {event[:160]!r}"
            if req.status == "planned":
                if len(req.times) == req.max_tokens and finished:
                    req.status = "ok"
                elif self.stopping.is_set():
                    req.status = "cut"
                else:
                    req.status = (f"failed: stream ended after "
                                  f"{len(req.times)} of {req.max_tokens}")
        except (OSError, http.client.HTTPException) as e:
            if req.status == "planned":
                req.status = ("cut" if self.stopping.is_set()
                              else f"failed: {type(e).__name__}: {e}")
        finally:
            with self.lock:
                req.ended = clock.now()
                self.open.discard(req)
            if req.first is not None:
                req.first.set()     # whoever waits for it must not hang
            conn.close()

    def launch(self, req):
        """Stream ``req`` on a thread of its own (open loop)."""
        self.records.append(req)
        t = threading.Thread(target=self.stream, args=(req,), daemon=True)
        self.threads.append(t)
        t.start()

    def open_window(self, zero):
        """Make ``zero`` (on time.monotonic()) the window's first instant:
        every time stamped against the provisional zero moves with it."""
        with self.lock:
            shift = zero - self.clock.zero
            for r in self.records:
                for name in ("due", "sent", "ended"):
                    if getattr(r, name) is not None:
                        setattr(r, name, getattr(r, name) - shift)
                for i in range(len(r.times)):
                    r.times[i] -= shift
            self.clock.zero = zero
        self.opened.set()

    def cut(self, reqs):
        """Shut the sockets of ``reqs`` that are still open: their threads
        end, and the server aborts the sequences."""
        with self.lock:
            live = [r for r in reqs if r in self.open]
        for r in live:
            if r.status == "planned":
                r.status = "cut"
        for sock in [r.sock for r in live]:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def stop(self):
        """Cut every open stream and wait for the threads."""
        self.stopping.set()
        deadline = time.monotonic() + 30
        while True:
            self.cut(list(self.open))
            for t in self.threads:
                t.join(timeout=0.2)
            alive = sum(t.is_alive() for t in self.threads)
            if not alive:
                return
            if time.monotonic() > deadline:
                raise RuntimeError(f"{alive} load threads did not end")


def completion(port, prompt, max_tokens, timeout=900, **extra):
    """One non-streamed /v1/completions call. Returns the parsed reply."""
    body = dict(model="bench", prompt=prompt, max_tokens=max_tokens,
                ignore_eos=True, temperature=0.0, **extra)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"completion -> {resp.status}: {raw[:300]!r}")
        return json.loads(raw)
    finally:
        conn.close()
