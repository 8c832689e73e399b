"""Held experts with at least one token, per expert layer and decode-only
step (experts): what a decode step has to read of each layer's 16 held
experts. The quantity ``moe.experts_touched_per_step`` reads, read by its
reader, under a name of this cell's own (the accepted metric's list of
cells is pinned by the accepted benchmark's tests). 16 rows x 8 / 128 = 1
token an expert give 16 (1 - (127/128)^128) = 10.1 of 16 where the router
spreads evenly. Source: /metrics
``gllm_moe_experts_touched_total{step="decode"}`` over
``gllm_moe_layer_steps_total{step="decode"}``, their growth. Layer:
runner."""


def read(run):
    return run["load_module"](
        "layer_metrics", "moe.experts_touched_per_step").read(run)
