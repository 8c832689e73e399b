"""Sequences preempted in the window (count). Source: /metrics
``gllm_sched_preemptions_total``, its growth. Layer: scheduler."""

from lib import sources


def read(run):
    return sources.counter_delta(run, "gllm_sched_preemptions_total")
