"""Ragged prefill attention of a parallel-hybrid decoder, 20 query heads
over 4 KV heads in every layer: least time over device time in the traced
slice (%). As kernel.ssm_attn_prefill_roofline_pct (the kernels of a whole
mixed step: the prompt's chunk through the ragged kernel and the decoding
rows that ride with it on the decode kernel), with FLOPs and KV counted
over all the layers (kernels/attn_prefill.py and kernels/attn_decode.py
read this configuration's own keys). Source: device trace, kernel
``attn_prefill``. Layer: kernels."""

from lib import mla_trace, par_trace, sources


def read(run):
    if not par_trace.is_family(run):
        return None
    seconds = mla_trace.seconds(run, "attn_prefill")
    prompts = sources.prefills_in_slice(run)
    if not seconds or not prompts:
        return None
    load = run["load_module"]
    pre, dec = load("kernels", "attn_prefill"), load("kernels", "attn_decode")
    model, peaks = run["model"], run["peaks"]
    riding = mla_trace.mixed_share(run)
    ctx = sources.decode_contexts(run)
    flops = (pre.flops_needed(model, prompts)
             + riding * dec.flops_needed(model, ctx))
    nbytes = (pre.bytes_needed(model, prompts)
              + riding * dec.bytes_needed(model, ctx))
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least / seconds
