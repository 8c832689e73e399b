"""What the readers of a windowed-GQA cell (command-a-plus-05-2026) share.

The four attention calls keep their names in the trace: the full layer's
``paged_decode_attention`` (a decode-only step) and
``ragged_paged_attention`` with its ``..._decode_rows`` (a mixed one), and
the windowed layers' ``swa_paged_decode_attention`` and
``swa_ragged_paged_attention`` with its ``..._decode_rows``
(``ops/attention.WINDOW_NAMES``), found through the configuration's
``trace_patterns`` (``attn_decode``, ``attn_prefill``, ``swa_decode``,
``swa_prefill``). A reader reads nothing (None) where the trace, the
pattern or the operation is not there: a parent without the program's
part, or another family's cell.
"""

from lib import mla_trace, sources

SLIDING, FULL = "sliding_attention", "full_attention"


def is_family(run):
    return "swa_decode" in run["config"].get("trace_patterns", {}).get(
        "kernels", {})


def seconds(run, kernel):
    """Device seconds of ``kernel`` in the slice, or None."""
    if not is_family(run):
        return None
    return mla_trace.seconds(run, kernel)


def step_module(run):
    return run["load_module"]("kernels", "swa_moe_decode_step")


def decode_share(run):
    """Share of the slice's step programs that were decode-only, or None
    where none ran."""
    mixed = mla_trace.mixed_share(run)
    return None if mixed is None else 1.0 - mixed


def work(run):
    """(contexts of the tokens decoded in the slice, (cached, new) of the
    requests prefilled in it)."""
    return sources.decode_contexts(run), mla_trace.requests_prefilled(run)
