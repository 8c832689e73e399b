"""The Mamba-2 chunked rule (SSD) of a parallel-hybrid layer at 32 heads of
128 x 256 in 2 groups, chunks of 128: least time over device time in the
traced slice (%). Least time from kernels/par_mamba_chunk.py for the
prompts whose first token arrived in the slice (the larger of bytes over
peak bytes/s and FLOPs over the chip's bf16 peak; the rule runs in float32,
so the share also says what the float32 costs). Device time: the operations
the configuration's ``trace_patterns`` name ``mamba_chunk`` (the in-chunk
half and the inter-chunk scan kernel), checked against the named kernel
among them (lib/mamba_trace.py raises where the shape patterns went blind).
None where the trace shows none. Source: device trace. Layer: kernels."""

from lib import mamba_trace, par_trace, sources


def read(run):
    seconds = par_trace.is_family(run) and mamba_trace.chunk_seconds(run)
    if not seconds:
        return None
    prompts = sources.prefills_in_slice(run)
    if not prompts:
        return None
    load = run["load_module"]
    least, _ = load("kernels", "par_mamba_chunk").least_seconds(
        run["model"], prompts, run["peaks"],
        load("kernels", "par_mamba_decode"))
    return 100.0 * least / seconds
