"""A whole decode step of an lfm2_moe decoder that holds a share of its
experts (LFM2-24B-A2B: a gated short convolution or GQA, then a SwiGLU or
routed experts, in every layer): every weight matrix that every step uses
is read once, the held experts that have a token are read once
(kernels/moe_expert.py: three matrices of ``hidden x
moe_intermediate_size``), every row's window is read and written in every
conv layer (kernels/sconv.py), and each row reads the KV of its whole
context in the attention layers.

Weight parameters that every step reads, from the published sizes. A conv
layer: the in-projection, the out-projection and the taps (kernels/
sconv.py). An attention layer: q and o (2 h heads d) and k, v (2 h
kv_heads d), d = hidden / heads where the configuration gives no
``head_dim``. An expert layer: the router (h x published experts). A dense
layer (the first ``num_dense_layers``): 3 h ``intermediate_size``. The tied
head once (vocab x h; of the embedding it also is a step gathers a few
rows). Norm vectors and the router's bias left out. LFM2-24B-A2B at its
whole depth and an eighth of the vocabulary: 30 x 16.78 M + 10 x 10.49 M +
38 x 0.13 M + 2 x 72.35 M + 16.78 M = 774.8 M parameters, 1.55 GB in bf16,
beside 38 x 8 x 9.44 M = 2.87 G parameters of held experts, of which a
step reads the touched ones: at 128 rows x 4 / 64 = 8 tokens an expert,
all but 0.03 % of them under a uniform choice (5.74 GB). The count is the
program's counter, never this reckoning.
"""


def head_dim(model):
    return model.get("head_dim") or (model["hidden_size"]
                                     // model["num_attention_heads"])


def attn_layers(model):
    return list(model["layer_types"]).count("full_attention")


def expert_layers(model):
    return model["num_hidden_layers"] - model.get("num_dense_layers", 0)


def attn_model(model):
    """``model`` as kernels/attn_decode.py and attn_prefill.py have to see
    it: its layer count is the number of layers that hold KV, and the head
    size is stated."""
    return dict(model, num_hidden_layers=attn_layers(model),
                head_dim=head_dim(model))


def fixed_weight_params(model, sconv):
    """Parameters every decode step reads, whatever the router does.
    ``sconv``: the module kernels/sconv.py."""
    h, d = model["hidden_size"], head_dim(model)
    attn = (2 * h * model["num_attention_heads"] * d
            + 2 * h * model["num_key_value_heads"] * d)
    published = (model.get("ep_share") or {}).get("num_experts",
                                                  model["num_experts"])
    dense = 3 * h * model["intermediate_size"]
    return (sconv.conv_layers(model) * sconv.weight_params_per_layer(model)
            + attn_layers(model) * attn + expert_layers(model) * h * published
            + model.get("num_dense_layers", 0) * dense
            + model["vocab_size"] * h)


def kv_bytes(model, contexts, kv_bytes_per_value=2):
    """K and V of every context, read once in each attention layer, as
    stored (two heads of 64 abreast in a cache row: the same bytes)."""
    per_token = (2 * attn_layers(model) * model["num_key_value_heads"]
                 * head_dim(model) * kv_bytes_per_value)
    return per_token * sum(contexts)


def window_bytes(model, rows, sconv):
    """The windows of ``rows`` decoded rows, read and written in every
    conv layer."""
    return (2 * sconv.window_bytes_per_row_layer(model)
            * sconv.conv_layers(model) * rows)


def bytes_needed(model, steps, touched_per_layer_step, contexts, expert,
                 sconv, weight_bytes=2):
    """``steps`` decode-only steps that touched ``touched_per_layer_step``
    held experts a layer each and decoded rows at ``contexts`` (one entry
    a row and step). ``expert``, ``sconv``: the modules
    kernels/moe_expert.py and kernels/sconv.py."""
    return (steps * (fixed_weight_params(model, sconv) * weight_bytes
                     + expert.bytes_needed(
                         model, touched_per_layer_step
                         * expert_layers(model)))
            + window_bytes(model, len(contexts), sconv)
            + kv_bytes(model, contexts))
