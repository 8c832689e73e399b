"""The full layer's attention calls of the mixed steps
(``ragged_paged_attention`` for the chunks and
``ragged_paged_attention_decode_rows`` for the rows that decode beside
them): least time over device time in the traced slice (%). Work from
kernels/attn_prefill.py and kernels/attn_decode.py over one layer: a
request prefilled as (cached, new) does the causal pairs of its whole
prompt less those of its cached part, reads the cached rows' K and V once
and moves q, K, V and the output of its new tokens once; the riding rows
read their whole contexts at the mixed steps' share of all steps. The
larger of the FLOPs' and the bytes' time. Source: device trace, kernel
``attn_prefill`` of the configuration's ``trace_patterns``. Layer:
kernels."""

from lib import swa_trace


def read(run):
    sec = swa_trace.seconds(run, "attn_prefill")
    share = swa_trace.decode_share(run) if sec else None
    if not sec or share is None:
        return None
    ctx, chunks = swa_trace.work(run)
    if not chunks:
        return None
    load = run["load_module"]
    pre, dec = load("kernels", "attn_prefill"), load("kernels", "attn_decode")
    step = swa_trace.step_module(run)
    model = step.one_layer(run["model"])
    riding = 1.0 - share
    whole = [c + n for c, n in chunks]
    cached = [c for c, _ in chunks]
    flops = (pre.flops_needed(model, whole) - pre.flops_needed(model, cached)
             + riding * dec.flops_needed(model, ctx))
    nbytes = (pre.bytes_needed(model, [n for _, n in chunks])
              + dec.bytes_needed(model, cached)
              + riding * dec.bytes_needed(model, ctx))
    peaks = run["peaks"]
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least * step.layers(run["model"], swa_trace.FULL) / sec
