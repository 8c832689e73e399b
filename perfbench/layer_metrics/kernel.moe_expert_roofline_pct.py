"""The grouped product over the held experts: least time over device time
in the traced slice (%). Least time from kernels/moe_expert.py: the three
matrices of every (expert, layer, step) that had a token, read once, over
the peak bytes/s, against 6 x hidden x width FLOPs an assignment to a held
expert, the larger bound. The counts are the program's
(``gllm_moe_experts_touched_total``, ``gllm_moe_assignments_total
{where="held"}``, per ``gllm_moe_layer_steps_total``), their growth over
the tail scaled to the step programs of the traced slice. Device time:
kernel ``moe_expert`` of the configuration's ``trace_patterns`` (XLA's
ragged-dot). Source: device trace. Layer: kernels."""

from lib import latent_trace


def read(run):
    sec = latent_trace.seconds(run, "moe_expert")
    if not sec:
        return None
    k = latent_trace.modules(run)
    n_dec, n_mixed = latent_trace.steps(run)
    layers = k["latent_common"].moe_layers(run["model"])
    t_dec = latent_trace.per_layer_step(run, "decode")
    t_mixed = latent_trace.per_layer_step(run, "mixed")
    held = latent_trace.held_per_layer_step(run)
    if held is None or (n_dec and t_dec is None) or (
            n_mixed and t_mixed is None):
        return None
    touched = layers * (n_dec * (t_dec or 0) + n_mixed * (t_mixed or 0))
    least, _ = k["moe_expert"].least_seconds(
        run["model"], touched, layers * (n_dec + n_mixed) * held,
        run["peaks"])
    return 100.0 * least / sec
