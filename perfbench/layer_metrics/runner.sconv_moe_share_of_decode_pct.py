"""Share of the decode-only steps' device time that the grouped products
over the held experts take (%): the ``moe_expert_decode`` operations of the
configuration's ``trace_patterns`` (a decode-only step multiplies every
held expert by every row: XLA's three batched products, found by the
array between them), over the decode-only step programs' time. The
router and its top-k are not in it.
Source: device trace. Layer: runner."""

from lib import latent_trace, sconv_trace


def read(run):
    if not sconv_trace.is_family(run):
        return None
    found = sconv_trace.decode_share(run)
    sec = latent_trace.seconds(run, "moe_expert_decode")
    if found is None or not sec:
        return None
    return 100.0 * sec / (sum(found[0]) / 1e3)
