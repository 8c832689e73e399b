"""Share of the mixed steps' device time that the full layers' selection
and sparse attention over the prefill chunks take (%): the ``dsa_chunk``
operations of the configuration's ``trace_patterns`` (the loop over work
items of 128 queries: index scores, top-k, gather, attention), over the
mixed step programs' time. The rows that decode inside mixed steps go
another way and are not in it. Source: device trace. Layer: runner."""

from lib import latent_trace, sources


def read(run):
    mixed = sources.step_ms(run, "prefill")
    if not mixed:
        return None
    sec = latent_trace.seconds(run, "dsa_chunk", mixed_only=True)
    if not sec:
        return None
    return 100.0 * sec / (sum(mixed) / 1e3)
