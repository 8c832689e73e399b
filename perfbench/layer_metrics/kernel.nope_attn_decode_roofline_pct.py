"""The full layer's decode attention call (``paged_decode_attention``) at
8 KV heads under 16 query heads each, no positional term: least time over
device time in the traced slice (%). As
kernel.attn_decode_roofline_pct, with the KV counted over the
full-attention layers only (kernels/attn_decode.py over one layer,
kernels/swa_moe_decode_step.py) and the contexts of the tokens decoded by
decode-only steps. Source: device trace, kernel ``attn_decode`` of the
configuration's ``trace_patterns``. Layer: kernels."""

from lib import sources, swa_trace


def read(run):
    sec = swa_trace.seconds(run, "attn_decode")
    share = swa_trace.decode_share(run) if sec else None
    ctx = sources.decode_contexts(run) if sec else None
    if not sec or share is None or not ctx:
        return None
    step = swa_trace.step_module(run)
    model = run["model"]
    least, _ = run["load_module"]("kernels", "attn_decode").least_seconds(
        step.one_layer(model), ctx, run["peaks"])
    return 100.0 * least * step.layers(model, swa_trace.FULL) * share / sec
