"""Held experts with at least one token, per expert layer and decode-only
step (experts): what a decode step has to read of each layer's 8 held
experts. The quantity ``moe.experts_touched_per_step`` reads, read by its
reader, under a name of this cell's own (the accepted metric's list of
cells is pinned by the accepted benchmark's tests). 128 rows x 4 / 64 = 8
tokens an expert give 8 (1 - (63/64)^512) = 7.997 of 8 where the router
spreads evenly. Source: /metrics
``gllm_moe_experts_touched_total{step="decode"}`` over
``gllm_moe_layer_steps_total{step="decode"}``, their growth. Layer:
runner."""


def read(run):
    if run["model"].get("model_type") != "lfm2_moe":
        return None
    return run["load_module"](
        "layer_metrics", "moe.experts_touched_per_step").read(run)
