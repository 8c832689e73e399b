"""Dependency-free observability layer (metrics, step traces, spans).

Three pillars, all pure-host bookkeeping (no jax import, no device work,
no effect on jit cache keys):

- ``gllm_tpu.obs.metrics``: a Prometheus-style registry (Counter / Gauge /
  Histogram with fixed buckets, thread-safe, text-exposition renderer)
  served by the api_server's ``GET /metrics``.
- ``gllm_tpu.obs.steptrace``: a ring buffer of per-step records (kind,
  batch size, token counts, wall ms, and the engine-loop phase
  fields) dumped by ``GET /steptrace``. ``python -m gllm_tpu.obs.dump
  trace.jsonl`` pretty-prints a saved trace.
- ``gllm_tpu.obs.spans``: the performance-attribution layer — per-request
  span trees, the engine-loop phase clock (``phase``: steptrace ``ph``
  on the host clock, ``gllm:*`` TraceAnnotations while a capture runs),
  and the Chrome trace-event converter behind ``GET /trace`` and ``obs.dump
  --format chrome`` (docs/observability.md#tracing--attribution).

Every round-5 finding (unfused decode steps at 8x the fused latency, the
sampled-path sort, the tuning-table regression) had to be excavated from
ad-hoc stderr logs; this layer makes the same questions one HTTP GET or
one JSON blob.
"""

from gllm_tpu.obs import metrics, spans, steptrace  # noqa: F401
