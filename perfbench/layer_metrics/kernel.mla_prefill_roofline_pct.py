"""The ragged attention kernel over the latent cache: least time over
device time in the traced slice (%). The kernel serves a whole mixed step:
the question (or the prefill chunk) and the decoding rows that ride. Least
time is the larger of FLOPs over the peak FLOP/s and bytes over the peak
bytes/s, where the work is that of the requests whose first token arrived
in the slice (kernels/mla_prefill.py: the new tokens behind the cached
document, the cheaper of the absorbed and the decompressed form chunk by
chunk) plus what the rows that decoded inside mixed steps need
(kernels/mla_decode.py, the contexts of the tokens decoded in the slice
times the mixed steps' share of all steps). Source: device trace, kernel
``mla_prefill`` of the configuration's ``trace_patterns``. Layer:
kernels."""

from lib import mla_trace, sources


def read(run):
    sec = mla_trace.seconds(run, "mla_prefill")
    if not sec:
        return None
    requests = mla_trace.requests_prefilled(run)
    riding = mla_trace.mixed_share(run)
    if not requests or riding is None:
        return None
    pre = run["load_module"]("kernels", "mla_prefill")
    dec = run["load_module"]("kernels", "mla_decode")
    ctx = sources.decode_contexts(run)
    model, peaks = run["model"], run["peaks"]
    flops = (pre.flops_needed(model, requests)
             + riding * dec.flops_needed(model, ctx))
    nbytes = (pre.bytes_needed(model, requests)
              + riding * dec.bytes_needed(model, ctx))
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least / sec
