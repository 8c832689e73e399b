"""What the readers of a dense-latent-attention cell (A.X-K1) share: the
kernels' device time from the trace, and the work of the traced slice as
the traffic defines it.

The two attention kernels keep their function names in the trace
(``paged_decode_attention`` in a decode-only step, ``ragged_paged_attention``
in a mixed one), found through the configuration's ``trace_patterns``
(``mla_decode``, ``mla_prefill``). A reader returns None where the trace,
the pattern or the operation is not there.
"""

from lib import sources


def seconds(run, kernel):
    """Device seconds of ``kernel`` in the slice, or None."""
    if run["peaks"] is None or run["slice"] is None or not run["trace"]:
        return None
    if kernel not in run["config"].get("trace_patterns", {}).get("kernels",
                                                                 {}):
        return None
    sec, calls = sources.kernel_seconds(run, kernel)
    return sec if calls else None


def requests_prefilled(run):
    """(cached, new) of every request whose first token arrived inside the
    traced slice. A prompt that says how much of it is its caller's
    document (``generators/docqa.py``) hit the prefix cache for the
    document's whole pages, unless it was its caller's first request (the
    one that prefilled the document); any other prompt was computed
    whole."""
    lo, hi = run["slice"]
    page = run["info"]["page_size"]
    first = {}
    for r in run["records"]:
        first[r.client] = min(first.get(r.client, r.idx), r.idx)
    out = []
    for r in run["records"]:
        if not (len(r.times) and lo <= r.times[0] < hi):
            continue
        shared = getattr(r.prompt, "shared", 0)
        cached = 0 if r.idx == first[r.client] else shared // page * page
        out.append((cached, len(r.prompt) - cached))
    return out


def mixed_share(run):
    """Share of the slice's step programs that were mixed steps (the rows
    that decode inside them are the ragged kernel's)."""
    n_mixed = len(sources.step_ms(run, "prefill"))
    n_decode = len(sources.step_ms(run, "decode"))
    if not n_mixed + n_decode:
        return None
    return n_mixed / (n_mixed + n_decode)
