"""Median time a request waited in the scheduler's queue before its first
step (ms). Source: /metrics ``gllm_request_queue_seconds`` histogram, its
growth over the window. Layer: scheduler."""

from lib import sources


def read(run):
    q = sources.histogram_quantile(run, "gllm_request_queue_seconds", 0.5)
    return None if q is None else 1e3 * q
