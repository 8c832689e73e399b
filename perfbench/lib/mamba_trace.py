"""Device time of the Mamba-2 layers' own operations in a traced slice,
for the state-space cell's readers, with the checks that keep a pattern
from going blind in silence (lib/gdn_trace.py's, for this family).

Two of those operations are Pallas kernels whose names survive into the
trace (``mamba2_recurrent_step``, ``mamba2_chunk_scan``). The rest are XLA
fusions, which the v5e trace shows as bare HLO lines: the configuration's
``trace_patterns`` find them by the shapes in those lines (``mamba_conv``:
the convolution's channel count; ``mamba_norm``: the gated norm's groups;
``mamba_chunk``: the chunked layout [chunks, heads, 128, .]). The named
kernels are the witnesses: wherever they ran, the shape patterns must have
matched beside them, or the reader raises. A reader reads nothing (None)
where the trace, the pattern or the kernel is not there: a parent without
the program's part.
"""

from lib import sources

SLACK = 0.75    # a slice's edges cut a step: counts may differ a little


class PatternBlind(RuntimeError):
    pass


def configured(run, kernel):
    return kernel in run["config"].get("trace_patterns", {}).get("kernels",
                                                                 {})


def traced(run):
    return (run["peaks"] is not None and run["slice"] is not None
            and bool(run["trace"]))


def recurrent_seconds(run):
    """(seconds of the step kernel with the convolution's operations:
    what moves the state and the window; the kernel's calls), or None
    where the trace shows no recurrent step."""
    if not traced(run) or not configured(run, "mamba_recurrent"):
        return None
    rec_s, rec_n = sources.kernel_seconds(run, "mamba_recurrent")
    if not rec_n:
        return None
    return rec_s + _beside(run, "mamba_conv", rec_n), rec_n


def decode_seconds(run):
    """Seconds of the recurrent step kernel, the convolution around it and
    the gated norm behind it, or None where the trace shows no recurrent
    step."""
    found = recurrent_seconds(run)
    if found is None:
        return None
    return found[0] + _beside(run, "mamba_norm", found[1])


def _beside(run, name, rec_n):
    sec, n = sources.kernel_seconds(run, name)
    if n < SLACK * rec_n:
        raise PatternBlind(
            f"mamba2_recurrent_step ran {rec_n} times in the slice and the "
            f"{name!r} pattern matched {n} operations: they are no longer "
            "found by their shapes (configs/<name>.json trace_patterns)")
    return sec


def chunk_seconds(run):
    """Seconds of the chunked rule (in-chunk half and inter-chunk scan),
    or None where the trace shows none of it."""
    if not traced(run) or not configured(run, "mamba_chunk_scan"):
        return None
    scan_s, scan_n = sources.kernel_seconds(run, "mamba_chunk_scan")
    all_s, all_n = sources.kernel_seconds(run, "mamba_chunk")
    if not scan_n:
        return None
    if all_n < 2 * scan_n or all_s <= scan_s:
        raise PatternBlind(
            f"mamba2_chunk_scan ran {scan_n} times and the 'mamba_chunk' "
            f"pattern matched {all_n} operations ({all_s:.4f} s against "
            f"{scan_s:.4f} s of the scan): the in-chunk half's operations "
            "are no longer found by their shapes (configs/<name>.json "
            "trace_patterns)")
    return all_s


def expert_layers(model):
    return model["hybrid_override_pattern"].count("E")
