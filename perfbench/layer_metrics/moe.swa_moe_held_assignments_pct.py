"""Share of the routed (token, expert) assignments that fell on experts
held here (%): 12.5 where the sigmoid router spreads evenly over 128
experts of which 16 are held, 1 held assignment a token of the top 8. The
quantity ``moe.held_assignments_pct`` reads, read by its reader, under a
name of this cell's own (the accepted metric's list of cells is pinned by
the accepted benchmark's tests). Source: /metrics
``gllm_moe_assignments_total``, the growth of ``where="held"`` over all.
Layer: runner."""


def read(run):
    return run["load_module"](
        "layer_metrics", "moe.held_assignments_pct").read(run)
