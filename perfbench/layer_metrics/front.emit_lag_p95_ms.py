"""How long a committed token waits for the socket (95th percentile, ms):
from deliver_output's stamp on the chunk (engine thread) to the handler
thread's flush of the SSE event that carries it. Source: /metrics
``gllm_http_emit_lag_seconds`` histogram, its growth over the tail of a
--trace 2 run. Layer: HTTP front."""

from lib import sources


def read(run):
    q = sources.histogram_quantile(run, "gllm_http_emit_lag_seconds", 0.95)
    return None if q is None else 1e3 * q
