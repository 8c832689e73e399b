"""Ragged prefill attention kernel over KV heads of 64 packed in pairs, in
the 10 attention layers of 40: least time over device time in the traced
slice (%). As kernel.ssm_attn_prefill_roofline_pct (the kernels of a whole
mixed step: the prompt's chunk through the ragged kernel and the decode
rows that ride with it), with FLOPs and KV counted over the attention
layers only and the head size derived (kernels/attn_prefill.py and
kernels/attn_decode.py called with
kernels/sconv_moe_decode_step.attn_model). Source: device trace, kernel
``attn_prefill``. Layer: kernels."""

from lib import mla_trace, sconv_trace, sources


def read(run):
    if not sconv_trace.is_family(run):
        return None
    seconds = mla_trace.seconds(run, "attn_prefill")
    prompts = sources.prefills_in_slice(run)
    riding = mla_trace.mixed_share(run)
    if not seconds or not prompts or riding is None:
        return None
    load = run["load_module"]
    pre, dec = load("kernels", "attn_prefill"), load("kernels", "attn_decode")
    model = load("kernels", "sconv_moe_decode_step").attn_model(run["model"])
    ctx = sources.decode_contexts(run)
    peaks = run["peaks"]
    flops = (pre.flops_needed(model, prompts)
             + riding * dec.flops_needed(model, ctx))
    nbytes = (pre.bytes_needed(model, prompts)
              + riding * dec.bytes_needed(model, ctx))
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least / seconds
