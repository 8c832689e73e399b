"""A whole decode step of a state-space hybrid that holds a share of its
experts (Nemotron 3 Nano: blocks of one mixer, Mamba-2 | relu^2 experts |
GQA): every weight matrix that every step uses is read once, the held
experts that have a token are read once (kernels/relu2_expert.py), every
row's Mamba-2 state is read and written in every Mamba-2 layer
(kernels/mamba_decode.py), and each row reads the KV of its whole context
in the attention layers.

Weight parameters that every step reads, from the published sizes. A
Mamba-2 block: ``in_proj`` (h x (2 H P + 2 G N + H)), the convolution
(conv_dim x taps + conv_dim), ``out_proj`` (H P x h). An attention block:
q and o (2 h heads d) and k, v (2 h kv_heads d). An expert block: the
router (h x published experts) and the shared expert (2 h shared). The
head once (vocab x h; the embedding is a matrix of its own of which a step
gathers a few rows). Norm vectors, A_log, D, dt_bias, the router's bias
left out. Nemotron 3 Nano at 16 blocks: 7 x 38.73 M + 2 x 23.40 M + 7 x
20.30 M + 176.2 M = 636.2 M parameters, 1.27 GB in bf16, beside 7 x 64 x
9.98 M = 4.47 G parameters of held experts, of which a step reads the
touched ones: 60.9 of 64 a layer at 64 rows under a uniform choice (8.5
GB); the seeded router chooses less evenly in the deeper layers and the
cell's window reads 55.0 (PERF.md section 5). The count is the
program's counter, never this reckoning.
"""


def count(model, letter):
    return model["hybrid_override_pattern"].count(letter)


def attn_model(model):
    """``model`` as kernels/attn_decode.py and attn_prefill.py have to see
    it: its layer count is the number of blocks that hold KV."""
    return dict(model, num_hidden_layers=count(model, "*"))


def fixed_weight_params(model, decode):
    """Parameters every decode step reads, whatever the router does.
    ``decode``: the module kernels/mamba_decode.py."""
    h = model["hidden_size"]
    d_inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    conv = decode.conv_dim(model)
    mamba = (h * (d_inner + conv + model["mamba_num_heads"])
             + conv * (model["conv_kernel"] + 1) + d_inner * h)
    attn = (2 * h * model["num_attention_heads"] * model["head_dim"]
            + 2 * h * model["num_key_value_heads"] * model["head_dim"])
    published = (model.get("ep_share") or {}).get(
        "n_routed_experts", model["n_routed_experts"])
    moe = h * published + 2 * h * model["moe_shared_expert_intermediate_size"]
    return (count(model, "M") * mamba + count(model, "*") * attn
            + count(model, "E") * moe + model["vocab_size"] * h)


def kv_bytes(model, contexts, kv_bytes_per_value=2):
    """K and V of every context, read once in each attention layer."""
    per_token = (2 * count(model, "*") * model["num_key_value_heads"]
                 * model["head_dim"] * kv_bytes_per_value)
    return per_token * sum(contexts)


def bytes_needed(model, steps, touched_per_layer_step, contexts, expert,
                 decode, weight_bytes=2):
    """``steps`` decode-only steps that touched ``touched_per_layer_step``
    held experts a layer each and decoded rows at ``contexts`` (one entry
    a row and step). ``expert``, ``decode``: the modules
    kernels/relu2_expert.py and kernels/mamba_decode.py."""
    return (steps * (fixed_weight_params(model, decode) * weight_bytes
                     + expert.bytes_needed(
                         model, touched_per_layer_step * count(model, "E")))
            + decode.bytes_needed(model, len(contexts))
            + kv_bytes(model, contexts))
