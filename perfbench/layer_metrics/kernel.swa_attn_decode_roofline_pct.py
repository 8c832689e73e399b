"""The windowed layers' decode attention call
(``swa_paged_decode_attention``): least time over device time in the
traced slice (%). Least time from kernels/swa_attn.py over the contexts of
the tokens decoded in the slice by decode-only steps (all tokens decoded
in the slice times the decode-only steps' share of all steps: the rows of
a mixed step are the ``swa_prefill`` calls'): K and V of min(context,
4096) rows read once a windowed layer, whatever the context holds behind
the window; bytes bind. Source: device trace, kernel ``swa_decode`` of the
configuration's ``trace_patterns``. Layer: kernels."""

from lib import sources, swa_trace


def read(run):
    sec = swa_trace.seconds(run, "swa_decode")
    share = swa_trace.decode_share(run) if sec else None
    ctx = sources.decode_contexts(run) if sec else None
    if not sec or share is None or not ctx:
        return None
    step = swa_trace.step_module(run)
    least, _ = run["load_module"]("kernels", "swa_attn").least_seconds(
        run["model"], step.layers(run["model"], swa_trace.SLIDING), ctx, [],
        run["peaks"])
    return 100.0 * least * share / sec
