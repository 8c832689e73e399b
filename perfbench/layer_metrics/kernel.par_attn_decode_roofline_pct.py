"""Decode attention kernel of a parallel-hybrid decoder, 4 KV heads under 5
query heads each with rotary embedding, in every layer: least time over
device time in the traced slice (%). As kernel.attn_decode_roofline_pct
(kernels/attn_decode.py counts ``num_hidden_layers`` x
``num_key_value_heads`` x ``head_dim``, this configuration's own keys, and
all its layers attend), with the KV of the contexts of the tokens decoded
in the slice scaled by the decode-only steps' share of all steps (a mixed
step's decoding rows run on ``ragged_paged_attention_decode_rows``, which
the ``attn_prefill`` pattern takes). Source: device trace, kernel
``attn_decode``. Layer: kernels."""

from lib import mla_trace, par_trace, sources


def read(run):
    if not par_trace.is_family(run):
        return None
    seconds = mla_trace.seconds(run, "attn_decode")
    ctx = sources.decode_contexts(run)
    share = par_trace.decode_share(run)
    if not seconds or not ctx or share is None:
        return None
    least, _ = run["load_module"]("kernels", "attn_decode").least_seconds(
        run["model"], ctx, run["peaks"])
    return 100.0 * least * share[1] / seconds
