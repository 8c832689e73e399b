"""Share of the decode-only steps' device time that the state-space half of
a parallel-hybrid layer takes with its own operations (%): the recurrent
step kernel, the convolution and the grouped gated norm (the
``mamba_recurrent``, ``mamba_conv`` and ``mamba_norm`` operations of the
configuration's ``trace_patterns``), without the branch's two projections,
which are matrix products like any other. The quantity
``runner.mamba_share_of_decode_pct`` reads, read by its reader, under a
name of this cell's own (the accepted metric's list of cells is pinned by
the accepted benchmark's tests). Source: device trace. Layer: runner."""

from lib import par_trace


def read(run):
    if not par_trace.is_family(run):
        return None
    return run["load_module"](
        "layer_metrics", "runner.mamba_share_of_decode_pct").read(run)
