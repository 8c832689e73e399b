"""Least time over device time of the decode-only step programs in the
traced slice (%). Least time = (weight bytes x decode steps + KV bytes) /
peak bytes/s: weight bytes from the configuration's published sizes
(kernels/decode_step.py), steps counted in the trace itself, KV bytes from
the generator's record of every decoded token's context
(kernels/attn_decode.py). Tokens decoded inside mixed steps are left out of
both sides as far as the trace can tell: the KV bytes are scaled by the
share of decode-only steps among all steps that carried decode rows.
Source: device trace. Layer: runner."""

from lib import sources


def read(run):
    if run["peaks"] is None or run["slice"] is None:
        return None
    dec = sources.step_ms(run, "decode")
    if not dec:
        return None
    mixed = sources.step_ms(run, "prefill")
    k = run["load_module"]("kernels", "attn_decode")
    s = run["load_module"]("kernels", "decode_step")
    chips = run["cell"]["chips"]
    kv = k.bytes_needed(run["model"], sources.decode_contexts(run))
    kv *= len(dec) / (len(dec) + len(mixed))
    weights = s.weight_bytes_per_step(run["model"], chips=chips) * len(dec)
    least = (weights + kv / chips) / run["peaks"]["bytes_per_s"]
    return 100.0 * least / (sum(dec) / 1e3)
