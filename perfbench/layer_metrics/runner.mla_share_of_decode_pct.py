"""Share of the decode-only steps' device time that the attention kernel
over the latent cache takes (%): the ``mla_decode`` operations of the
configuration's ``trace_patterns`` (``paged_decode_attention``, one call a
layer) over the decode-only step programs' time. The projections around it
(q, the latent row, the fold through W_uk and W_uv, W_o) are not in it.
Source: device trace. Layer: runner."""

from lib import mla_trace, sources


def read(run):
    dec = sources.step_ms(run, "decode")
    sec = mla_trace.seconds(run, "mla_decode") if dec else None
    if not sec:
        return None
    return 100.0 * sec / (sum(dec) / 1e3)
