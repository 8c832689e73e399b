"""The grouped product over the held experts: least time over device time
in the traced slice (%). The quantity ``kernel.moe_expert_roofline_pct``
reads, read by its reader through this configuration's ``trace_patterns``
(``moe_expert``: XLA's ragged-dot) and widths, under a name of this cell's
own (the accepted metric's list of cells is pinned by the accepted
benchmark's tests): the three matrices of every (expert, layer, step) that
had a token read once (88.1 MB an expert at 7168 x 2048) against 6 x hidden
x width FLOPs an assignment, the larger bound (kernels/moe_expert.py).
Source: device trace. Layer: kernels."""


def read(run):
    return run["load_module"](
        "layer_metrics", "kernel.moe_expert_roofline_pct").read(run)
