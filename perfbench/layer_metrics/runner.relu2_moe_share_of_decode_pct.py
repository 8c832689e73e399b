"""Share of the decode-only steps' device time that the grouped products
over the held relu^2 experts take (%): the ``moe_expert_decode`` operations
of the configuration's ``trace_patterns`` (XLA's ragged-dot at the row
count only a decode step has), over the decode-only step programs' time.
The router, the shared expert and the sort and scatter around the products
are not in it. Source: device trace. Layer: runner."""

from lib import latent_trace, mamba_trace, sources


def read(run):
    if not mamba_trace.traced(run):
        return None
    if "hybrid_override_pattern" not in run["model"]:
        return None
    dec = sources.step_ms(run, "decode")
    if not dec:
        return None
    sec = latent_trace.seconds(run, "moe_expert_decode")
    if not sec:
        return None
    return 100.0 * sec / (sum(dec) / 1e3)
