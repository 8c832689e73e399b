"""How full the Mamba-2 chunked rule's packed layout was (%): the real
tokens of the rows that prefilled over the token slots the layout computed
(chunks of 128), their growth over the window. 100 % would be no padding.
Source: /metrics ``gllm_mamba_chunk_tokens_total`` over
``gllm_mamba_chunk_slots_total``. Layer: runner."""

from lib import sources


def read(run):
    tokens = sources.counter_delta(run, "gllm_mamba_chunk_tokens_total")
    slots = sources.counter_delta(run, "gllm_mamba_chunk_slots_total")
    # a program without the counters reads no growth
    if not tokens or not slots:
        return None
    return 100.0 * tokens / slots
