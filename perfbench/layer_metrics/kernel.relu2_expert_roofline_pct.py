"""The grouped product over the held relu^2 experts: least time over
device time in the traced slice (%). The quantity
``kernel.moe_expert_roofline_pct`` reads, through this configuration's
``trace_patterns`` (``moe_expert``: XLA's ragged-dot) and widths and the
two-matrix count of kernels/relu2_expert.py, under a name of this cell's
own (the accepted metric's list of cells is pinned by the accepted
benchmark's tests): the two matrices of every (expert, layer, step) that
had a token read once (19.96 MB an expert at 2688 x 1856) against 4 x
hidden x width FLOPs an assignment, the larger bound. The counts are the
program's (``gllm_moe_experts_touched_total``,
``gllm_moe_assignments_total{where="held"}``, per
``gllm_moe_layer_steps_total``), their growth over the tail scaled to the
step programs of the traced slice. Source: device trace. Layer: kernels."""

from lib import latent_trace, mamba_trace


def read(run):
    if "hybrid_override_pattern" not in run["model"]:
        return None
    sec = latent_trace.seconds(run, "moe_expert")
    if not sec:
        return None
    n_dec, n_mixed = latent_trace.steps(run)
    layers = mamba_trace.expert_layers(run["model"])
    t_dec = latent_trace.per_layer_step(run, "decode")
    t_mixed = latent_trace.per_layer_step(run, "mixed")
    held = latent_trace.held_per_layer_step(run)
    if held is None or (n_dec and t_dec is None) or (
            n_mixed and t_mixed is None):
        return None
    touched = layers * (n_dec * (t_dec or 0) + n_mixed * (t_mixed or 0))
    least, _ = run["load_module"]("kernels", "relu2_expert").least_seconds(
        run["model"], touched, layers * (n_dec + n_mixed) * held,
        run["peaks"])
    return 100.0 * least / sec
