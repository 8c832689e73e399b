"""What the readers of the parallel-hybrid cell (Falcon-H1: attention heads
and Mamba-2 heads side by side in every layer) share: the family's test and
the share of decode-only steps among the traced steps. The Mamba-2
operations' device time comes from lib/mamba_trace.py, which reads the
configuration's ``trace_patterns`` and nothing of a family's keys. A reader
reads nothing (None) where the model is another family's or the trace is
not there: a parent without the program's part."""

from lib import mamba_trace, mla_trace, sources


def is_family(run):
    return (run["model"].get("model_type") == "falcon_h1"
            and mamba_trace.traced(run))


def decode_share(run):
    """(device ms of the decode-only steps, their share of all traced
    steps), or None where the slice holds no decode-only step."""
    dec = sources.step_ms(run, "decode")
    return (dec, 1.0 - mla_trace.mixed_share(run)) if dec else None
