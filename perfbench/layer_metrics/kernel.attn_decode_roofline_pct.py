"""Decode attention kernel: least time over device time in the traced
slice (%). Least time from kernels/attn_decode.py over the contexts of the
tokens decoded in the slice by decode-only steps (all tokens decoded in the
slice times the decode-only steps' share of all steps: the rows of a mixed
step are the ragged kernel's); bytes bind. Source: device trace, kernel
``attn_decode`` of the configuration's ``trace_patterns``. Layer:
kernels."""

from lib import sources


def read(run):
    if run["peaks"] is None or run["slice"] is None:
        return None
    seconds, calls = sources.kernel_seconds(run, "attn_decode")
    ctx = sources.decode_contexts(run)
    n_mixed = len(sources.step_ms(run, "prefill"))
    n_decode = len(sources.step_ms(run, "decode"))
    if not calls or not ctx or not n_decode:
        return None
    k = run["load_module"]("kernels", "attn_decode")
    least, _ = k.least_seconds(run["model"], ctx, run["peaks"])
    return 100.0 * least * n_decode / (n_mixed + n_decode) / seconds
