"""Per-request serving measurements: TTFT / ITL / E2E latency.

stdlib re-design of the reference's vLLM-style async request functions
(/root/reference/benchmarks/backend_request_func.py:38-46): each request
streams from the OpenAI endpoint and records time-to-first-token,
inter-token latencies, and end-to-end latency. Thread-per-request instead of
aiohttp (this image has no aiohttp).
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import time
from typing import List, Optional


@dataclasses.dataclass
class RequestResult:
    success: bool = False
    ttft_s: float = 0.0
    itl_s: List[float] = dataclasses.field(default_factory=list)
    e2e_s: float = 0.0
    output_tokens: int = 0
    error: str = ""

    @property
    def tpot_s(self) -> float:
        return (sum(self.itl_s) / len(self.itl_s)) if self.itl_s else 0.0


def stream_completion(host: str, port: int, payload: dict,
                      path: str = "/v1/completions",
                      timeout: float = 600.0) -> RequestResult:
    """Fire one streaming request; measure token arrival times."""
    res = RequestResult()
    payload = dict(payload, stream=True)
    t0 = time.perf_counter()
    last = t0
    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        conn.request("POST", path, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            res.error = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return res
        buf = b""
        while True:
            # read1 returns as soon as ANY bytes are available; plain
            # read(4096) would block until 4 KiB accumulate across SSE
            # events, batching arrivals and faking TTFT/ITL
            chunk = resp.read1(4096)
            if not chunk:
                break
            buf += chunk
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data: "):
                    continue
                payload_b = event[6:]
                if payload_b == b"[DONE]":
                    continue
                d = json.loads(payload_b)
                choice = d["choices"][0]
                delta = choice.get("delta")
                # one event == one token: completion chunks carry "text",
                # chat chunks a delta with "content" (possibly empty when
                # detokenization held bytes back); skip the role preamble
                is_token = ("text" in choice if delta is None
                            else "content" in (delta or {}))
                if delta is not None and "role" in delta and "content" \
                        not in delta:
                    is_token = False
                now = time.perf_counter()
                if is_token:
                    if res.output_tokens == 0:
                        res.ttft_s = now - t0
                    else:
                        res.itl_s.append(now - last)
                    res.output_tokens += 1
                    last = now
        res.e2e_s = time.perf_counter() - t0
        res.success = res.output_tokens > 0
        conn.close()
    except Exception as e:  # noqa: BLE001
        res.error = str(e)
    return res


def run_requests(host: str, port: int, payloads: List[dict],
                 concurrency: int, request_rate: float = float("inf"),
                 seed: int = 0, path: str = "/v1/completions"):
    """Drive pre-built payloads with bounded concurrency and (optionally)
    Poisson arrivals; returns (results, wall_s). Payloads and the arrival
    schedule are fully materialized BEFORE any thread starts, so seeded
    runs reproduce exactly (a shared RNG touched from worker threads
    would not be thread-safe)."""
    import random
    import threading

    results: List[RequestResult] = [None] * len(payloads)
    sem = threading.Semaphore(concurrency)

    def worker(i):
        with sem:
            results[i] = stream_completion(host, port, payloads[i],
                                           path=path)

    arrivals = [0.0] * len(payloads)
    if request_rate > 0 and request_rate != float("inf"):
        r, t = random.Random(seed), 0.0
        for i in range(len(payloads)):
            t += r.expovariate(request_rate)
            arrivals[i] = t

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(payloads))]
    for i, t in enumerate(threads):
        wait = arrivals[i] - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        t.start()
    for t in threads:
        t.join()
    return results, time.perf_counter() - t0


def percentile(vals: List[float], p: float) -> float:
    if not vals:
        return 0.0
    vals = sorted(vals)
    i = min(len(vals) - 1, int(p / 100.0 * len(vals)))
    return vals[i]


def _dist(vals: List[float]) -> dict:
    """mean/p50/p90/p99 in ms (the reference's serving-benchmark shape)."""
    if not vals:
        return {"mean": 0, "p50": 0, "p90": 0, "p99": 0}
    return {"mean": round(1e3 * sum(vals) / len(vals), 1),
            "p50": round(1e3 * percentile(vals, 50), 1),
            "p90": round(1e3 * percentile(vals, 90), 1),
            "p99": round(1e3 * percentile(vals, 99), 1)}


def summarize(results: List[RequestResult], wall_s: float) -> dict:
    ok = [r for r in results if r.success]
    out_toks = sum(r.output_tokens for r in ok)
    itls = [t for r in ok for t in r.itl_s]
    return {
        "completed": len(ok),
        "failed": len(results) - len(ok),
        "wall_s": round(wall_s, 2),
        "request_throughput_rps": round(len(ok) / wall_s, 3),
        "output_tok_s": round(out_toks / wall_s, 1),
        "output_tokens": out_toks,
        "ttft_ms": _dist([r.ttft_s for r in ok]),
        "tpot_ms": _dist([r.tpot_s for r in ok if r.itl_s]),
        # per-token inter-arrival across ALL requests: the tail here is
        # what streaming users feel as a stall
        "itl_ms": _dist(itls),
        "e2e_ms": _dist([r.e2e_s for r in ok]),
    }
