"""Share of the routed (token, expert) assignments that fell on experts
held here (%): 12.5 where the router spreads evenly over 256 experts of
which 32 are held. Source: /metrics ``gllm_moe_assignments_total``, the
growth of ``where="held"`` over all. Layer: runner."""

from lib import sources


def read(run):
    held = sources.counter_delta(run, "gllm_moe_assignments_total",
                                 '{where="held"}')
    total = sources.counter_delta(run, "gllm_moe_assignments_total")
    if not total or held is None:
        return None
    return 100.0 * held / total
