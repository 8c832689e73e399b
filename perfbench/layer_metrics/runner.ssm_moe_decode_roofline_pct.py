"""Least time over device time of the decode-only step programs of a
state-space hybrid that holds a share of its experts, in the traced slice
(%): the cell's share of the whole step. Least time = (the weights every
step reads x decode steps + the held experts touched in decode steps x
19.96 MB + the Mamba-2 state of the rows decoded, read and written + the
KV of the attention layers) / peak bytes/s
(kernels/ssm_moe_decode_step.py). Tokens decoded inside mixed steps are
left out of both sides as far as the trace can tell: state and KV bytes
are scaled by the share of decode-only steps among all steps. The experts
touched are the program's count
(``gllm_moe_experts_touched_total{step="decode"}`` per
``gllm_moe_layer_steps_total{step="decode"}``, growth over the tail).
Source: device trace. Layer: runner."""

from lib import latent_trace, mamba_trace, sources


def read(run):
    if not mamba_trace.traced(run):
        return None
    if "hybrid_override_pattern" not in run["model"]:
        return None
    dec = sources.step_ms(run, "decode")
    mixed = sources.step_ms(run, "prefill")
    touched = latent_trace.per_layer_step(run, "decode")
    ctx = sources.decode_contexts(run)
    if not dec or touched is None or not ctx:
        return None
    load = run["load_module"]
    step = load("kernels", "ssm_moe_decode_step")
    expert = load("kernels", "relu2_expert")
    decode = load("kernels", "mamba_decode")
    model = run["model"]
    weights = step.bytes_needed(model, len(dec), touched, [], expert, decode)
    moving = (decode.bytes_needed(model, len(ctx))
              + step.kv_bytes(model, ctx))
    moving *= len(dec) / (len(dec) + len(mixed))
    least = (weights + moving) / run["peaks"]["bytes_per_s"]
    return 100.0 * least / (sum(dec) / 1e3)
