"""A whole decode step of a dense-latent-attention decoder that holds a
share of its experts (A.X-K1): every weight matrix that every step uses is
read once, the held experts that have a token are read once
(kernels/moe_expert.py), and each row reads the latent rows of its whole
context in every layer (kernels/mla_decode.py).

Weight parameters that every step reads, from the published sizes. A
layer's attention: W_qa (h q_lora), W_qb (q_lora heads (nope + rope)),
W_kva (h (lora + rope)), W_uk and W_uv (heads lora (nope + v)), W_o (heads
v h). A dense layer's MLP: 3 h i. An expert layer: the router (h x
published experts) and the shared expert (3 h moe_i n_shared). The head
once (vocab x h; the embedding is a matrix of its own of which a step
gathers a few rows). A.X-K1 at 5 layers: 5 x 101.1 M + 396.4 M + 4 x 45.4 M
+ 146.8 M = 1230.4 M parameters, 2.46 GB in bf16, beside 4 x 12 x 44.0 M =
2.11 G parameters of held experts, of which a step reads the touched ones
(8.9 of 12 a layer at 32 rows: 3.1 GB).
"""


def attn_params(model):
    h, hq = model["hidden_size"], model["num_attention_heads"]
    ql, lora = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    q = h * ql + ql * hq * (nope + rope) if ql else h * hq * (nope + rope)
    return (q + h * (lora + rope) + hq * lora * (nope + v) + hq * v * h)


def moe_layers(model):
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def fixed_weight_params(model):
    """Parameters every decode step reads, whatever the router does."""
    h = model["hidden_size"]
    published = (model.get("ep_share") or {}).get(
        "n_routed_experts", model["n_routed_experts"])
    return (model["num_hidden_layers"] * attn_params(model)
            + model["first_k_dense_replace"] * 3 * h
            * model["intermediate_size"]
            + moe_layers(model) * (
                h * published + 3 * h * model["moe_intermediate_size"]
                * model["n_shared_experts"])
            + model["vocab_size"] * h)


def bytes_needed(model, steps, touched_per_layer_step, contexts, moe, mla,
                 weight_bytes=2):
    """``steps`` decode-only steps that touched ``touched_per_layer_step``
    held experts a layer each and decoded rows at ``contexts``."""
    return (steps * (fixed_weight_params(model) * weight_bytes
                     + moe.bytes_needed(
                         model, touched_per_layer_step * moe_layers(model)))
            + mla.bytes_needed(model, contexts))
