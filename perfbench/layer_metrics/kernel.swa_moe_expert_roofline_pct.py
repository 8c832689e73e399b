"""The grouped product over the held experts: least time over device time
in the traced slice (%). The quantity ``kernel.moe_expert_roofline_pct``
reads, read by its reader through this configuration's ``trace_patterns``
(``moe_expert``) and its one expert width (``intermediate_size``, put
under the reader's key: kernels/swa_moe_decode_step.expert_model), under a
name of this cell's own (the accepted metric's list of cells is pinned by
the accepted benchmark's tests): the three matrices of every (expert,
layer, step) that had a token read once (100.66 MB an expert at 4096 x
4096) against 6 x hidden x width FLOPs an assignment, the larger bound
(kernels/moe_expert.py). Source: device trace. Layer: kernels."""

from lib import swa_trace


def read(run):
    if not swa_trace.is_family(run):
        return None
    model = swa_trace.step_module(run).expert_model(run["model"])
    return run["load_module"](
        "layer_metrics", "kernel.moe_expert_roofline_pct").read(
            dict(run, model=model))
