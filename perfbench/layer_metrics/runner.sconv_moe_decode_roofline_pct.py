"""Least time over device time of the decode-only step programs of a
short-convolution decoder that holds a share of its experts, in the traced
slice (%): the cell's share of the whole step. Least time = (the weights
every step reads x decode steps + the held experts touched in decode steps
x 18.87 MB + the windows of the rows decoded, read and written + the KV
of the attention layers) / peak bytes/s
(kernels/sconv_moe_decode_step.py). Tokens decoded inside mixed steps are
left out of both sides as far as the trace can tell: window and KV bytes
are scaled by the share of decode-only steps among all steps. The experts
touched are the program's count
(``gllm_moe_experts_touched_total{step="decode"}`` per
``gllm_moe_layer_steps_total{step="decode"}``, growth over the tail).
Source: device trace. Layer: runner."""

from lib import latent_trace, sconv_trace, sources


def read(run):
    if not sconv_trace.is_family(run):
        return None
    found = sconv_trace.decode_share(run)
    touched = latent_trace.per_layer_step(run, "decode")
    ctx = sources.decode_contexts(run)
    if found is None or touched is None or not ctx:
        return None
    dec, share = found
    load = run["load_module"]
    step = load("kernels", "sconv_moe_decode_step")
    expert, sconv = load("kernels", "moe_expert"), load("kernels", "sconv")
    model = run["model"]
    weights = step.bytes_needed(model, len(dec), touched, [], expert, sconv)
    moving = (step.window_bytes(model, len(ctx), sconv)
              + step.kv_bytes(model, ctx)) * share
    least = (weights + moving) / run["peaks"]["bytes_per_s"]
    return 100.0 * least / (sum(dec) / 1e3)
