"""The decode attention kernel over the latent cache: least time over
device time in the traced slice (%). Least time from kernels/mla_decode.py
over the contexts of the tokens decoded in the slice by decode-only steps
(all tokens decoded in the slice times the decode-only steps' share of all
steps: the rows of a mixed step are the ragged kernel's): every context row
read once a layer, 576 values; bytes bind. Source: device trace, kernel
``mla_decode`` of the configuration's ``trace_patterns``. Layer: kernels."""

from lib import mla_trace, sources


def read(run):
    sec = mla_trace.seconds(run, "mla_decode")
    share = mla_trace.mixed_share(run) if sec else None
    ctx = sources.decode_contexts(run) if sec else None
    if not sec or share is None or not ctx:
        return None
    k = run["load_module"]("kernels", "mla_decode")
    least, _ = k.least_seconds(run["model"], ctx, run["peaks"])
    return 100.0 * least * (1.0 - share) / sec
