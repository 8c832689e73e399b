"""The grouped product over the held gated-SiLU experts at 2048 x 1536:
least time over device time in the traced slice (%). The quantity
``kernel.moe_expert_roofline_pct`` reads, through this configuration's
``trace_patterns`` (``moe_expert``: the Pallas ``gmm`` of a mixed step
and the batched products of a decode-only one, which read every held
expert whether it has a token or not: the kernel's cost, not the
floor's), its own count of
expert layers (``num_hidden_layers - num_dense_layers``) and the
three-matrix count of kernels/moe_expert.py, under a name of this cell's
own (the accepted metric's list of cells is pinned by the accepted
benchmark's tests): the three matrices of every (expert, layer, step) that
had a token read once (18.87 MB an expert) against 6 x hidden x width
FLOPs an assignment, the larger bound. The counts are the program's
(``gllm_moe_experts_touched_total``,
``gllm_moe_assignments_total{where="held"}``, per
``gllm_moe_layer_steps_total``), their growth over the tail scaled to the
step programs of the traced slice. Source: device trace. Layer: kernels."""

from lib import latent_trace, sconv_trace


def read(run):
    if not sconv_trace.is_family(run):
        return None
    sec = latent_trace.seconds(run, "moe_expert")
    if not sec:
        return None
    load = run["load_module"]
    n_dec, n_mixed = latent_trace.steps(run)
    layers = load("kernels", "sconv_moe_decode_step").expert_layers(
        run["model"])
    t_dec = latent_trace.per_layer_step(run, "decode")
    t_mixed = latent_trace.per_layer_step(run, "mixed")
    held = latent_trace.held_per_layer_step(run)
    if held is None or (n_dec and t_dec is None) or (
            n_mixed and t_mixed is None):
        return None
    touched = layers * (n_dec * (t_dec or 0) + n_mixed * (t_mixed or 0))
    least, _ = load("kernels", "moe_expert").least_seconds(
        run["model"], touched, layers * (n_dec + n_mixed) * held,
        run["peaks"])
    return 100.0 * least / sec
