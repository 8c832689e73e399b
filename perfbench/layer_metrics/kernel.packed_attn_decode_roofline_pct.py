"""Decode attention kernel over KV heads of 64 packed in pairs (8 KV heads
under 4 query heads each, per-head q / k norm, rotary embedding), in the
10 attention layers of 40: least time over device time in the traced slice
(%). As kernel.attn_decode_roofline_pct, with the KV counted over the
attention layers only and the head size derived (kernels/attn_decode.py
called with kernels/sconv_moe_decode_step.attn_model): the KV bytes as
stored; the block-diagonal q's doubled products are the kernel's cost, not
the floor's. The contexts of the tokens decoded in the slice are scaled by
the decode-only steps' share of all steps (a mixed step's decoding rows
run on ``ragged_paged_attention_decode_rows``, which the ``attn_prefill``
pattern takes). Source: device trace, kernel ``attn_decode``. Layer:
kernels."""

from lib import mla_trace, sconv_trace, sources


def read(run):
    if not sconv_trace.is_family(run):
        return None
    seconds = mla_trace.seconds(run, "attn_decode")
    ctx = sources.decode_contexts(run)
    found = sconv_trace.decode_share(run)
    if not seconds or not ctx or found is None:
        return None
    load = run["load_module"]
    model = load("kernels", "sconv_moe_decode_step").attn_model(run["model"])
    least, _ = load("kernels", "attn_decode").least_seconds(
        model, ctx, run["peaks"])
    return 100.0 * least * found[1] / seconds
