"""Decode attention kernel of a state-space hybrid: least time over device
time in the traced slice (%). As kernel.hybrid_attn_decode_roofline_pct,
with the KV counted over the attention blocks of ``hybrid_override_pattern``
only (kernels/attn_decode.py called with that layer count,
kernels/ssm_moe_decode_step.py): 2 KV heads under 16 query heads each, no
rotary embedding. Source: device trace, kernel ``attn_decode``. Layer:
kernels."""

from lib import sources


def read(run):
    if run["peaks"] is None or run["slice"] is None:
        return None
    if "hybrid_override_pattern" not in run["model"]:
        return None
    seconds, calls = sources.kernel_seconds(run, "attn_decode")
    ctx = sources.decode_contexts(run)
    n_mixed = len(sources.step_ms(run, "prefill"))
    n_decode = len(sources.step_ms(run, "decode"))
    if not calls or not ctx or not n_decode:
        return None
    load = run["load_module"]
    model = load("kernels", "ssm_moe_decode_step").attn_model(run["model"])
    least, _ = load("kernels", "attn_decode").least_seconds(
        model, ctx, run["peaks"])
    return 100.0 * least * n_decode / (n_mixed + n_decode) / seconds
