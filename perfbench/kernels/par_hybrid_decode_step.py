"""A whole decode step of a decoder whose every layer runs attention heads
and Mamba-2 heads side by side, then a dense SwiGLU MLP (Falcon-H1): every
weight matrix is read once, every row's Mamba-2 state and window are read
and written in every layer (kernels/par_mamba_decode.py), and each row
reads the KV of its whole context in every layer (kernels/attn_decode.py,
whose ``num_hidden_layers`` x ``num_key_value_heads`` x ``head_dim`` are
this family's own keys: all layers attend).

Weight parameters that every step reads, from the published sizes. A layer:
q and o (2 h heads d), k and v (2 h kv_heads d), the in-projection (h x
(2 d_ssm + 2 G N + H)), the convolution (conv_dim x taps + conv_dim), the
out-projection (d_ssm x h), the MLP (3 h i). The head once (vocab x h; the
embedding is a matrix of its own of which a step gathers a few rows). Norm
vectors, A_log, D, dt_bias left out. Falcon-H1-34B at 6 layers: 6 x
430.11 M + 1336.9 M = 3917.6 M parameters, 7.84 GB in bf16, of which the
head is 2.67 GB: 34 % of the weights a step reads here, where the whole
model's 72 layers make it 4 %.
"""


def layer_weight_params(model, decode):
    """``decode``: the module kernels/par_mamba_decode.py."""
    h = model["hidden_size"]
    d_ssm, conv = model["mamba_d_ssm"], decode.conv_dim(model)
    attn = (2 * h * model["num_attention_heads"] * model["head_dim"]
            + 2 * h * model["num_key_value_heads"] * model["head_dim"])
    mamba = (h * (d_ssm + conv + model["mamba_n_heads"])
             + conv * (model["mamba_d_conv"] + 1) + d_ssm * h)
    return attn + mamba + 3 * h * model["intermediate_size"]


def head_params(model):
    return model["vocab_size"] * model["hidden_size"]


def fixed_weight_params(model, decode):
    """Parameters every decode step reads."""
    return (model["num_hidden_layers"] * layer_weight_params(model, decode)
            + head_params(model))


def bytes_needed(model, steps, contexts, decode, attn, weight_bytes=2):
    """``steps`` decode-only steps that decoded rows at ``contexts`` (one
    entry a row and step). ``decode``, ``attn``: the modules
    kernels/par_mamba_decode.py and kernels/attn_decode.py."""
    return (steps * fixed_weight_params(model, decode) * weight_bytes
            + decode.bytes_needed(model, len(contexts))
            + attn.bytes_needed(model, contexts))
