"""Least time over device time of the decode-only step programs of a
windowed-GQA decoder that holds a share of its experts, in the traced
slice (%): the cell's share of the whole step. Least time = (the weights
every step reads x decode steps + the held experts touched in decode steps
x 100.66 MB + the KV rows read, by kind of layer: min(context, 4096) in a
windowed layer, the context in the full one, 4096 B a row) / peak bytes/s
(kernels/swa_moe_decode_step.py). Tokens decoded inside mixed steps are
left out of both sides as far as the trace can tell: the rows' bytes are
scaled by the share of decode-only steps among all steps. The experts
touched are the program's count
(``gllm_moe_experts_touched_total{step="decode"}`` per
``gllm_moe_layer_steps_total{step="decode"}``, growth over the tail).
Source: device trace. Layer: runner."""

from lib import latent_trace, sources, swa_trace


def read(run):
    if not swa_trace.is_family(run) or run["peaks"] is None or (
            run["slice"] is None):
        return None
    dec = sources.step_ms(run, "decode")
    share = swa_trace.decode_share(run)
    touched = latent_trace.per_layer_step(run, "decode")
    ctx = sources.decode_contexts(run)
    if not dec or share is None or touched is None or not ctx:
        return None
    load = run["load_module"]
    step = swa_trace.step_module(run)
    expert, decode = load("kernels", "moe_expert"), load("kernels",
                                                         "attn_decode")
    model = run["model"]
    weights = step.bytes_needed(model, len(dec), touched, [], expert, decode)
    rows = step.kv_bytes(model, ctx, decode) * share
    least = (weights + rows) / run["peaks"]["bytes_per_s"]
    return 100.0 * least / (sum(dec) / 1e3)
