"""XLA programs compiled inside the window (count); should read 0.
Source: /metrics ``gllm_xla_programs_total{source="compiled"}``, its
growth. Layer: runner."""

from lib import sources


def read(run):
    return sources.counter_delta(run, "gllm_xla_programs_total",
                                 '{source="compiled"}')
