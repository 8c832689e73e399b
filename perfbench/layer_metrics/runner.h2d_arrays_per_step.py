"""Host arrays the runner places on the device per step dispatch (count):
a transfer each, and each a crossing into jax on the engine thread while
the handler threads wait for the interpreter. 2 on the default path (the
packed batch and the tokens); a later PR that adds a leaf to the step
batch shows up here. Source: /metrics ``gllm_step_h2d_arrays_total`` over
``gllm_sampler_program_total`` (every label: one per dispatch), their
growth. Layer: runner."""

from lib import sources


def read(run):
    arrays = sources.counter_delta(run, "gllm_step_h2d_arrays_total")
    steps = sources.counter_delta(run, "gllm_sampler_program_total")
    # a program that lacks the counter (before PR 25) reads no growth
    if not arrays or not steps:
        return None
    return arrays / steps
