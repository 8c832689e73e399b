"""Mean sequences per decode step (seqs): decode tokens over decode
sub-steps in the window. Source: /metrics ``gllm_step_tokens_total
{kind="decode"}`` over ``gllm_decode_steps_total``, their growth. Layer:
engine loop."""

from lib import sources


def read(run):
    steps = sources.counter_delta(run, "gllm_decode_steps_total")
    tokens = sources.counter_delta(run, "gllm_step_tokens_total",
                                   '{kind="decode"}')
    if not steps or tokens is None:
        return None
    return tokens / steps
