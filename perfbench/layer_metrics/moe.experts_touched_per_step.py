"""Held experts with at least one token, per expert layer and decode-only
step (experts): what a decode step has to read of each layer's 32 held
experts. 64 rows x 8 / 256 = 2 tokens an expert give 32 (1 - e^-2) = 27.7
where the router spreads evenly. Source: /metrics
``gllm_moe_experts_touched_total{step="decode"}`` over
``gllm_moe_layer_steps_total{step="decode"}``, their growth. Layer:
runner."""

from lib import latent_trace


def read(run):
    if run["prom0"] is None or run["prom1"] is None:
        return None
    return latent_trace.per_layer_step(run, "decode")
