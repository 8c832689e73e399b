"""Share of the decode-only steps' device time that the attention calls
take (%): the windowed layers' ``swa_paged_decode_attention`` and the full
layer's ``paged_decode_attention`` (the ``swa_decode`` and ``attn_decode``
operations of the configuration's ``trace_patterns``, one call a layer)
over the decode-only step programs' time. The projections around them are
not in it. Source: device trace. Layer: runner."""

from lib import sources, swa_trace


def read(run):
    dec = sources.step_ms(run, "decode")
    swa = swa_trace.seconds(run, "swa_decode") if dec else None
    full = swa_trace.seconds(run, "attn_decode") if dec else None
    if not swa or not full:
        return None
    return 100.0 * (swa + full) / (sum(dec) / 1e3)
