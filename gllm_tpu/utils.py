"""Small shared helpers (shape bucketing, math, circuit breaking).

The bucketing helpers implement the static-shape discipline XLA wants: every
jit-compiled step function sees only a small set of padded shapes, mirroring the
reference engine's power-of-two CUDA-graph buckets
(/root/reference/gllm/model_runner.py:471-489).

:class:`CircuitBreaker` is the shared per-remote failure ladder: the
prefix-peer client (kvstore/peer.py) and the fleet front router
(gllm_tpu/router/) both talk to remotes that can die, flap, or
crash-loop, and both need the same guarantee — a broken remote costs at
most one probe per backoff window, never a per-request stall.
"""

from __future__ import annotations

import os
import time
from typing import Optional


class CircuitBreaker:
    """Per-remote circuit breaker (docs/robustness.md#peer-breakers).

    closed → (``threshold`` consecutive failures) → open for
    ``base_s · 2^(trips-1)`` seconds ±``jitter`` (capped at ``max_s``)
    → half-open: exactly ONE probe is admitted — success closes and
    resets the backoff ladder, failure re-opens with the next-longer
    window. The jitter de-synchronizes a fleet of replicas hammering
    the same recovering remote.

    Single-threaded by contract (one prober owns each instance —
    the engine thread for prefix peers, the router's health poller for
    serving replicas); ``now`` injection keeps the chaos tests
    clock-free.
    """

    def __init__(self, base_s: float = 30.0, max_s: float = 300.0,
                 threshold: int = 1, jitter: float = 0.1):
        self.base_s = max(0.001, float(base_s))
        self.max_s = max(self.base_s, float(max_s))
        self.threshold = max(1, int(threshold))
        self.jitter = max(0.0, min(1.0, float(jitter)))
        self.state = "closed"            # closed | open | half_open
        self.trips = 0                   # consecutive opens (backoff rung)
        self._fails = 0                  # consecutive failures while closed
        self._until = 0.0                # open-state expiry (monotonic)
        # lifetime health counters (surfaced on /server_info and
        # /router_info)
        self.failures = 0
        self.successes = 0
        self.opens = 0
        self.probes = 0                  # half-open recovery probes

    def allow(self, now: Optional[float] = None) -> bool:
        """May the caller probe this remote now? The True returned after
        an open window expires IS the single half-open probe — further
        calls return False until success()/failure() resolves it."""
        if self.state == "closed":
            return True
        if self.state == "half_open":
            return False
        now = time.monotonic() if now is None else now
        if now >= self._until:
            self.state = "half_open"
            self.probes += 1
            return True
        return False

    def success(self) -> None:
        self.successes += 1
        self.state = "closed"
        self._fails = 0
        self.trips = 0

    def failure(self, now: Optional[float] = None) -> None:
        self.failures += 1
        if self.state == "half_open":
            self._open(now)              # the recovery probe failed
            return
        if self.state == "open":
            return                       # already backing off
        self._fails += 1
        if self._fails >= self.threshold:
            self._open(now)

    def _open(self, now: Optional[float]) -> None:
        now = time.monotonic() if now is None else now
        self.trips += 1
        self._fails = 0
        self.opens += 1
        self.state = "open"
        back = min(self.max_s, self.base_s * (2 ** (self.trips - 1)))
        if self.jitter:
            import random
            back *= 1.0 + self.jitter * (2.0 * random.random() - 1.0)
        self._until = now + back

    def down_for(self, now: Optional[float] = None) -> float:
        if self.state != "open":
            return 0.0
        now = time.monotonic() if now is None else now
        return max(0.0, self._until - now)

    def health(self) -> dict:
        return {"state": self.state, "trips": self.trips,
                "failures": self.failures, "successes": self.successes,
                "opens": self.opens, "probes": self.probes,
                "down_for_s": round(self.down_for(), 2)}


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, multiple: int) -> int:
    return cdiv(x, multiple) * multiple


def next_pow2(x: int, minimum: int = 1) -> int:
    """Smallest power of two >= max(x, minimum)."""
    v = max(x, minimum, 1)
    return 1 << (v - 1).bit_length()


def bucket_size(x: int, minimum: int, maximum: int) -> int:
    """Pad ``x`` to a power-of-two bucket, clamped to [minimum, maximum].

    Keeps the number of distinct compiled shapes logarithmic in the range —
    the XLA-compilation-cache analogue of the reference's CUDA-graph bucket
    table (/root/reference/gllm/model_runner.py:1525-1615).
    """
    if x > maximum:
        raise ValueError(f"size {x} exceeds maximum bucket {maximum}")
    return min(next_pow2(x, minimum), maximum)


class LRUBytesCache:
    """Byte-budgeted LRU (reference MultiModalEmbeddingCache,
    model_runner.py:161-221): caps both entry count and total bytes so one
    huge entry can't squat on the pool. Thread-safe: the multihost blob
    chain serves this cache from a peer-server handler thread while the
    engine thread writes it."""

    def __init__(self, max_entries: int = 64, max_mb: float = 256.0):
        import threading
        from collections import OrderedDict
        self._cache = OrderedDict()
        self._lock = threading.Lock()
        self.max_entries = max_entries
        self.max_bytes = int(max_mb * 1024 * 1024)
        self._cur_bytes = 0
        self.hits = 0
        self.misses = 0
        # Keys whose values exceeded max_bytes and were rejected by put():
        # a peer serving this cache can answer "will never have" instead
        # of letting downstream fetchers poll out their full deadline.
        self.oversize = set()
        self._oversize_capped = False

    @staticmethod
    def _size_of(value) -> int:
        nbytes = getattr(value, "nbytes", None)
        if nbytes is not None:
            return int(nbytes)
        if isinstance(value, (bytes, bytearray, memoryview)):
            return len(value)
        return 0

    def get(self, key):
        with self._lock:
            v = self._cache.get(key)
            if v is None:
                self.misses += 1
                return None
            self.hits += 1
            self._cache.move_to_end(key)
            return v

    def pop(self, key) -> None:
        """Invalidate one entry (a caller replaced or poisoned the
        underlying data; the cached copy must not be served again)."""
        with self._lock:
            v = self._cache.pop(key, None)
            if v is not None:
                self._cur_bytes -= self._size_of(v)

    def put(self, key, value) -> None:
        sz = self._size_of(value)
        if sz > self.max_bytes:
            with self._lock:
                if key not in self.oversize:
                    import logging
                    log = logging.getLogger("gllm_tpu")
                    if len(self.oversize) < 1024:
                        self.oversize.add(key)
                        log.warning(
                            "LRUBytesCache: value for %r (%d B) exceeds "
                            "max_bytes=%d — never cacheable", key, sz,
                            self.max_bytes)
                    elif not self._oversize_capped:
                        self._oversize_capped = True
                        log.warning(
                            "LRUBytesCache: oversize-key set capped at "
                            "1024 — further oversize keys lose the peer "
                            "'never' fast-path")
            return
        with self._lock:
            if key in self._cache:
                self._cur_bytes -= self._size_of(self._cache[key])
                self._cache.move_to_end(key)
            self._cache[key] = value
            self._cur_bytes += sz
            while (len(self._cache) > self.max_entries
                   or self._cur_bytes > self.max_bytes):
                _, evicted = self._cache.popitem(last=False)
                self._cur_bytes -= self._size_of(evicted)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent (on-disk) XLA compilation cache.

    Serving cold-start is compile-bound: the bucketed jit grid is tens of
    programs at seconds to a minute and a half each (the reference pays
    the analogous cost once per CUDA-graph capture,
    model_runner.py:1525). With the persistent cache every process that
    compiles the same (program, compile-options) pair — a restarted
    server, the next arm of one chip command — reads the serialized
    executable back instead of compiling again.

    One rule for where it lives. If ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already holds that directory and this sets no other. Otherwise it
    is ``<checkout>/.jax_cache``: the path is part of every cache key, so
    it must not move between processes (never a home, temporary, pid or
    time-derived name).

    min_entry_size/min_compile_time are zeroed because the default
    thresholds (1 s compile floor) skip exactly the small bucketed decode
    programs we most need cached. Safe to call repeatedly. Returns the
    directory in effect.
    """
    import jax
    d = jax.config.jax_compilation_cache_dir
    if not d:
        d = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    # Zero the skip thresholds, but only where they still hold jax's
    # library defaults (0 bytes / 1.0 s): a non-default value is a
    # deliberate choice by an embedding application and is respected.
    for knob, default in (("jax_persistent_cache_min_entry_size_bytes", 0),
                          ("jax_persistent_cache_min_compile_time_secs",
                           1.0)):
        if getattr(jax.config, knob) == default:
            jax.config.update(knob, 0)
    return d


def tpu_compiler_options() -> dict:
    """Per-jit XLA compile options for the TPU backend.

    Scoped-VMEM limit: XLA's default 16 MiB scope can't hold a Pallas
    attention kernel's buffers plus an operand/result XLA chooses to stage
    in VMEM (the v5e compiler asks 17-21 MiB for the ragged kernel at the
    serving buckets; tests/test_tpu_compile.py pins the
    refusal). v5e cores carry 128 MiB of VMEM; 64 MiB leaves ample
    headroom. Passed via jit(compiler_options=...) so a process whose
    XLA_FLAGS are parsed by a CPU-only XLA never sees a TPU-only flag."""
    import jax
    if jax.default_backend() == "tpu":
        return {"xla_tpu_scoped_vmem_limit_kib": 65536}
    return None
