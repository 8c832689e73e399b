"""The windowed layers' attention calls of the mixed steps
(``swa_ragged_paged_attention`` for the chunks and
``swa_ragged_paged_attention_decode_rows`` for the rows that decode beside
them): least time over device time in the traced slice (%). Least time
from kernels/swa_attn.py: every request prefilled in the slice as (cached,
new) (a caller's document is cached after its first request: its whole
pages are hits and only the question is computed, of whose windows the
last 4095 cached rows are read), and the riding rows' min(context, 4096)
rows at the mixed steps' share of all steps; the larger of the FLOPs' and
the bytes' time. Source: device trace, kernel ``swa_prefill`` of the
configuration's ``trace_patterns``. Layer: kernels."""

from lib import swa_trace


def read(run):
    sec = swa_trace.seconds(run, "swa_prefill")
    share = swa_trace.decode_share(run) if sec else None
    if not sec or share is None:
        return None
    ctx, chunks = swa_trace.work(run)
    if not chunks:
        return None
    step = swa_trace.step_module(run)
    model = run["model"]
    swa = run["load_module"]("kernels", "swa_attn")
    n = step.layers(model, swa_trace.SLIDING)
    chunk_s, _ = swa.least_seconds(model, n, [], chunks, run["peaks"])
    riding_s, _ = swa.least_seconds(model, n, ctx, [], run["peaks"])
    return 100.0 * (chunk_s + riding_s * (1.0 - share)) / sec
