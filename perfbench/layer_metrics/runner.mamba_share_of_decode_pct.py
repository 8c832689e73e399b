"""Share of the decode-only steps' device time that the Mamba-2 layers'
own operations take (%): the recurrent step kernel, the convolution and
the grouped gated norm (the ``mamba_recurrent``, ``mamba_conv`` and
``mamba_norm`` operations of the configuration's ``trace_patterns``),
without the layers' two projections, which are matrix products like any
other. Their time over all steps is scaled by the decode-only steps' share
of all steps. Source: device trace. Layer: runner."""

from lib import mamba_trace, sources


def read(run):
    seconds = mamba_trace.decode_seconds(run)
    dec = sources.step_ms(run, "decode")
    if not dec or not seconds:
        return None
    mixed = sources.step_ms(run, "prefill")
    share = len(dec) / (len(dec) + len(mixed))
    return 100.0 * seconds * share / (sum(dec) / 1e3)
