"""Least time over device time of the decode-only step programs of a
parallel-hybrid decoder, in the traced slice (%): the cell's share of the
whole step. Least time = (the layers' weights and the head, every decode
step + the Mamba-2 state and window of the rows decoded, read and written
in every layer + the KV of their contexts in every layer) / peak bytes/s
(kernels/par_hybrid_decode_step.py). Tokens decoded inside mixed steps are
left out of both sides as far as the trace can tell: state and KV bytes
are scaled by the share of decode-only steps among all steps. Source:
device trace. Layer: runner."""

from lib import par_trace, sources


def read(run):
    share = par_trace.is_family(run) and par_trace.decode_share(run)
    ctx = sources.decode_contexts(run) if share else None
    if not share or not ctx:
        return None
    dec, of_all = share
    load = run["load_module"]
    step = load("kernels", "par_hybrid_decode_step")
    decode, attn = (load("kernels", "par_mamba_decode"),
                    load("kernels", "attn_decode"))
    model = run["model"]
    weights = step.bytes_needed(model, len(dec), [], decode, attn)
    moving = step.bytes_needed(model, 0, ctx, decode, attn) * of_all
    least = (weights + moving) / run["peaks"]["bytes_per_s"]
    return 100.0 * least / (sum(dec) / 1e3)
