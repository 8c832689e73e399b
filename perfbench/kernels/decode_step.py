"""A whole decode step: every weight matrix is read once per step, and the
KV of every context once (kernels/attn_decode.py). Weight bytes from the
published sizes: per layer q, k, v, o and the three MLP matrices; the head
once (the tied embedding serves as the head; the token gather reads a few
rows and is left out). Norm vectors are left out (0.005 %).
"""


def weight_params(model):
    h, i = model["hidden_size"], model["intermediate_size"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or h // hq
    per_layer = h * hq * d + 2 * h * hkv * d + hq * d * h + 3 * h * i
    return model["num_hidden_layers"] * per_layer + model["vocab_size"] * h


def weight_bytes_per_step(model, weight_bytes=2, chips=1):
    """Bytes of weights each chip reads per decode step (a pipelined or
    tensor-parallel deployment reads a chip's share on each chip)."""
    return weight_params(model) * weight_bytes / chips
