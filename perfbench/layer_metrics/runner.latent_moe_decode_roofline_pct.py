"""Least time over device time of the decode-only step programs of a
latent-attention decoder with windowed layers and a share of its experts,
in the traced slice (%). Least time = (the weights every step reads x
decode steps + the experts touched in decode steps + index keys, chosen
rows and window rows of the rows decoded) / peak bytes/s
(kernels/latent_moe_decode_step.py). Tokens decoded inside mixed steps are
left out of both sides as far as the trace can tell: the caches' bytes are
scaled by the share of decode-only steps among all steps. Source: device
trace. Layer: runner."""

from lib import latent_trace, sources


def read(run):
    if run["peaks"] is None or run["slice"] is None:
        return None
    if "sliding_window_size" not in run["model"]:
        return None
    dec = sources.step_ms(run, "decode")
    touched = latent_trace.per_layer_step(run, "decode")
    if not dec or touched is None:
        return None
    mixed = sources.step_ms(run, "prefill")
    k = latent_trace.modules(run)
    model, common = run["model"], k["latent_common"]
    step = k["latent_moe_decode_step"]
    ctx = sources.decode_contexts(run)
    caches = step.cache_bytes(model, ctx, k["dsa_index"], k["sparse_mla"],
                              k["swa_mla"], common)
    caches *= len(dec) / (len(dec) + len(mixed))
    weights = len(dec) * (
        step.fixed_weight_params(model, common) * 2
        + k["moe_expert"].bytes_needed(
            model, touched * common.moe_layers(model)))
    least = (weights + caches) / run["peaks"]["bytes_per_s"]
    return 100.0 * least / (sum(dec) / 1e3)
