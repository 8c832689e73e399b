"""The gated short convolution in decode-only steps, in-projection to
out-projection: least time over device time in the traced slice (%). Least
time from kernels/sconv.py: a conv layer's weights read once a decode step
(33.57 MB at LFM2-24B-A2B's widths, 30 layers), each decoded row's window
read and written and its input and output crossed once, over the peak
bytes/s, against the projections' and gates' FLOPs, the larger bound.
Device time: the ``sconv_decode`` operations of the configuration's
``trace_patterns`` (XLA's: the operator has no kernel of its own), held to
one call a conv layer and decode step by lib/sconv_trace.py. The rows
decoded in decode-only steps are the tokens decoded in the slice scaled by
those steps' share of all steps. Source: device trace. Layer: kernels."""

from lib import sconv_trace, sources


def read(run):
    if not sconv_trace.is_family(run):
        return None
    sconv = run["load_module"]("kernels", "sconv")
    seconds = sconv_trace.operator_seconds(run, sconv)
    found = sconv_trace.decode_share(run)
    ctx = sources.decode_contexts(run)
    if not seconds or found is None or not ctx:
        return None
    dec, share = found
    least, _ = sconv.least_seconds(run["model"], len(dec), len(ctx) * share,
                                   run["peaks"])
    return 100.0 * least / seconds
