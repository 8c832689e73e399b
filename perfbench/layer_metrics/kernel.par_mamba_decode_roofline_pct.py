"""The Mamba-2 recurrent step of a parallel-hybrid layer at 32 heads of
128 x 256 in 2 groups: least time over device time in the traced slice (%).
Least time from kernels/par_mamba_decode.py: the recurrent state and the
convolution window of every row decoded in the slice, read once and
written once in each of the layers, over the peak bytes/s. Device time: the
step kernel (``mamba2_recurrent_step``, in place in the slot pool) and the
convolution's operations, which move the window (the configuration's
``trace_patterns`` ``mamba_recurrent`` and ``mamba_conv``; mixed steps run
their decoding rows through the same operations). None where the trace
shows no such kernel. Source: device trace. Layer: kernels."""

from lib import mamba_trace, par_trace, sources


def read(run):
    found = par_trace.is_family(run) and mamba_trace.recurrent_seconds(run)
    if not found:
        return None
    rows = len(sources.decode_contexts(run))
    if not rows:
        return None
    k = run["load_module"]("kernels", "par_mamba_decode")
    least, _ = k.least_seconds(run["model"], rows, run["peaks"])
    return 100.0 * least / found[0]
