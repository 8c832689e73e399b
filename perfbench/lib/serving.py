"""The serving child and the HTTP calls on it (standard library only).

Copied in shape from ``chip_smoke.py`` (PR 21), which stays the bring-up
smoke: one ``python -m gllm_tpu.entrypoints.api_server`` child owns the
chip, the parent never imports jax, SIGTERM is a clean exit.
"""

import http.client
import json
import signal
import socket
import subprocess
import sys
import time


class BenchFailure(Exception):
    """The run cannot give a result (no chip, dead server, bad reply)."""


def check(cond, what):
    if not cond:
        raise BenchFailure(what)


def call(port, method, path, body=None, timeout=600):
    """One HTTP exchange. Returns (status, bytes)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = None if body is None else json.dumps(body)
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(port, path, timeout=120):
    status, body = call(port, "GET", path, timeout=timeout)
    check(status == 200, f"GET {path} -> {status}: {body[:300]!r}")
    return json.loads(body)


def get_text(port, path, timeout=120):
    status, body = call(port, "GET", path, timeout=timeout)
    check(status == 200, f"GET {path} -> {status}: {body[:300]!r}")
    return body.decode()


def post_json(port, path, body=None, timeout=600):
    status, raw = call(port, "POST", path, body, timeout=timeout)
    check(status == 200, f"POST {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def prom_samples(text, name):
    """{label-string: value} of one Prometheus metric's samples; the label
    string is ``{a="b"}`` as printed, or ``""``."""
    out = {}
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            head, value = line.rsplit(" ", 1)
            out[head[len(name):]] = float(value)
    return out


def prom_histogram(text, name):
    """Cumulative buckets of a Prometheus histogram (labels other than
    ``le`` summed): sorted [(upper bound, cumulative count)]."""
    acc = {}
    for labels, v in prom_samples(text, name + "_bucket").items():
        le = labels.split('le="', 1)[1].split('"', 1)[0]
        bound = float("inf") if le == "+Inf" else float(le)
        acc[bound] = acc.get(bound, 0.0) + v
    return sorted(acc.items())


class Server:
    """One api_server child: start, wait for /readyz, SIGTERM, exit 0."""

    def __init__(self, checkout, model_dir, flags, env, log_path,
                 ready_timeout, seed):
        self.checkout, self.model_dir, self.flags = checkout, model_dir, flags
        self.env, self.log_path = env, log_path
        self.ready_timeout, self.seed = ready_timeout, seed
        self.proc = None
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]

    def start(self):
        cmd = [sys.executable, "-m", "gllm_tpu.entrypoints.api_server",
               "--model", self.model_dir, "--tokenizer", "",
               "--load-format", "dummy", "--seed", str(self.seed),
               "--host", "127.0.0.1", "--port", str(self.port)] + self.flags
        print("[server] $ " + " ".join(cmd[1:]), flush=True)
        self.log_file = open(self.log_path, "w")
        self.proc = subprocess.Popen(cmd, cwd=self.checkout, env=self.env,
                                     stdout=self.log_file,
                                     stderr=subprocess.STDOUT)

    def wait_ready(self):
        t0 = time.monotonic()
        while True:
            if self.proc.poll() is not None:
                raise BenchFailure("server exited with code "
                                   f"{self.proc.returncode} before it was "
                                   "ready")
            if time.monotonic() - t0 > self.ready_timeout:
                raise BenchFailure(
                    f"server not ready after {self.ready_timeout:.0f}s")
            try:
                status, _ = call(self.port, "GET", "/readyz", timeout=5)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.5)

    def stop(self):
        """SIGTERM, wait, kill if it does not go. Returns the exit code."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log_file.close()
        return self.proc.returncode

    def tail(self, n=40):
        with open(self.log_path, errors="replace") as f:
            lines = f.readlines()[-n:]
        print(f"[server] --- last {len(lines)} log lines ({self.log_path})")
        for line in lines:
            print("  | " + line.rstrip())
        sys.stdout.flush()
