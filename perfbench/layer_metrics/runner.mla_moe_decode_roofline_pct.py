"""Least time over device time of the decode-only step programs of a
dense-latent-attention decoder that holds a share of its experts, in the
traced slice (%): the cell's share of the whole step. Least time = (the
weights every step reads x decode steps + the held experts touched in
decode steps + the latent rows of the rows decoded) / peak bytes/s
(kernels/mla_moe_decode_step.py). Tokens decoded inside mixed steps are
left out of both sides as far as the trace can tell: the latent rows'
bytes are scaled by the share of decode-only steps among all steps. The
experts touched are the program's count
(``gllm_moe_experts_touched_total{step="decode"}`` per
``gllm_moe_layer_steps_total{step="decode"}``, growth over the tail).
Source: device trace. Layer: runner."""

from lib import latent_trace, mla_trace, sources


def read(run):
    if run["peaks"] is None or run["slice"] is None:
        return None
    dec = sources.step_ms(run, "decode")
    share = mla_trace.mixed_share(run)
    touched = latent_trace.per_layer_step(run, "decode")
    ctx = sources.decode_contexts(run)
    if not dec or share is None or touched is None or not ctx:
        return None
    load = run["load_module"]
    step = load("kernels", "mla_moe_decode_step")
    mla, moe = load("kernels", "mla_decode"), load("kernels", "moe_expert")
    model = run["model"]
    weights = step.bytes_needed(model, len(dec), touched, [], moe, mla)
    rows = mla.bytes_needed(model, ctx) * (1.0 - share)
    least = (weights + rows) / run["peaks"]["bytes_per_s"]
    return 100.0 * least / (sum(dec) / 1e3)
