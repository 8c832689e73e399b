"""Share of the mixed steps' device time that the ragged attention kernel
over the latent cache takes (%): the ``mla_prefill`` operations of the
configuration's ``trace_patterns`` (``ragged_paged_attention``, one call a
layer, serving the question or chunk and the rows that ride) over the
mixed step programs' time. Source: device trace. Layer: runner."""

from lib import mla_trace, sources


def read(run):
    mixed = sources.step_ms(run, "prefill")
    sec = mla_trace.seconds(run, "mla_prefill") if mixed else None
    if not sec:
        return None
    return 100.0 * sec / (sum(mixed) / 1e3)
