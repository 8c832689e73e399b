"""Share of the decode-only steps' device time that the gated short
convolution takes (%): the ``sconv_decode`` operations of the
configuration's ``trace_patterns`` (in-projection to out-projection, the
window's gather and scatter between them; held to one call a conv layer
and step by lib/sconv_trace.py), over the decode-only step programs' time.
Source: device trace. Layer: runner."""

from lib import sconv_trace


def read(run):
    if not sconv_trace.is_family(run):
        return None
    seconds = sconv_trace.operator_seconds(
        run, run["load_module"]("kernels", "sconv"))
    found = sconv_trace.decode_share(run)
    if not seconds or found is None:
        return None
    return 100.0 * seconds / (sum(found[0]) / 1e3)
