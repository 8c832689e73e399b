"""Ragged prefill attention kernel of a state-space hybrid: least time over
device time in the traced slice (%). As
kernel.hybrid_attn_prefill_roofline_pct (the kernels of a whole mixed step:
the prompt's chunk through the ragged kernel and the decode rows that ride
with it), with FLOPs and KV counted over the attention blocks of
``hybrid_override_pattern`` only (kernels/attn_prefill.py and
kernels/attn_decode.py called with that layer count,
kernels/ssm_moe_decode_step.py). Source: device trace, kernel
``attn_prefill``. Layer: kernels."""

from lib import sources


def read(run):
    if run["peaks"] is None or run["slice"] is None:
        return None
    if "hybrid_override_pattern" not in run["model"]:
        return None
    seconds, calls = sources.kernel_seconds(run, "attn_prefill")
    prompts = sources.prefills_in_slice(run)
    if not calls or not prompts:
        return None
    load = run["load_module"]
    pre, dec = load("kernels", "attn_prefill"), load("kernels", "attn_decode")
    model = load("kernels", "ssm_moe_decode_step").attn_model(run["model"])
    n_mixed = len(sources.step_ms(run, "prefill"))
    n_decode = len(sources.step_ms(run, "decode"))
    riding = n_mixed / (n_mixed + n_decode)
    ctx = sources.decode_contexts(run)
    peaks = run["peaks"]
    flops = (pre.flops_needed(model, prompts)
             + riding * dec.flops_needed(model, ctx))
    nbytes = (pre.bytes_needed(model, prompts)
              + riding * dec.bytes_needed(model, ctx))
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least / seconds
