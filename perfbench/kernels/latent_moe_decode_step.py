"""A whole decode step of a latent-attention decoder with windowed layers
and a share of its experts: every weight matrix that every step uses is
read once, the experts that have a token are read once
(kernels/moe_expert.py), and each row reads, of its sequence, the index
keys of the whole context (kernels/dsa_index.py), the chosen latent rows
(kernels/sparse_mla.py) and the window's rows (kernels/swa_mla.py).

Weight parameters that every step reads, from the published sizes. A full
layer: W_qa (h q_lora), W_qb (q_lora heads (nope + rope)), W_kva (h (lora
+ rope)), W_uk and W_uv (heads lora (nope + v)), W_o (heads v h), the gate
(h heads), the indexer's W_Iq (q_lora nh hd), W_Ik (h hd), W_Iw (h nh). A
windowed layer: the same without the indexer, at the swa sizes. A dense
layer's MLP: 3 h i. An expert layer: the router (h x published experts)
and the shared expert (3 h moe_i n_shared). The head once (vocab x h; the
embedding is a matrix of its own of which a step gathers a few rows).
dots3_note at 5 layers: 2 x 144.1 M + 3 x 90.8 M + 212.3 M + 4 x 24.9 M +
97.3 M = 969.8 M parameters, 1.94 GB in bf16, beside 4 x 32 x 23.6 M = 3.02
G parameters of held experts, of which a step reads the touched ones.
"""


def attn_params(model, kind):
    h = model["hidden_size"]
    if kind == "sliding_attention":
        hq, ql, lora = (model["swa_num_attention_heads"],
                        model["swa_q_lora_rank"], model["swa_kv_lora_rank"])
        nope, rope, v = (model["swa_qk_nope_head_dim"],
                         model["swa_qk_rope_head_dim"],
                         model["swa_v_head_dim"])
        gate, index = model.get("swa_attention_gate_type"), 0
    else:
        hq, ql, lora = (model["num_attention_heads"], model["q_lora_rank"],
                        model["kv_lora_rank"])
        nope, rope, v = (model["qk_nope_head_dim"],
                         model["qk_rope_head_dim"], model["v_head_dim"])
        gate = model.get("attention_gate_type")
        nh, hd = model["index_n_heads"], model["index_head_dim"]
        index = ql * nh * hd + h * hd + h * nh
    return (h * ql + ql * hq * (nope + rope) + h * (lora + rope)
            + hq * lora * (nope + v) + hq * v * h
            + (h * hq if gate else 0) + index)


def fixed_weight_params(model, common):
    """Parameters every decode step reads, whatever the router does."""
    h = model["hidden_size"]
    total = sum(attn_params(model, t) for t in model["layer_types"])
    total += model["first_k_dense_replace"] * 3 * h * model[
        "intermediate_size"]
    published = (model.get("ep_share") or {}).get(
        "n_routed_experts", model["n_routed_experts"])
    total += common.moe_layers(model) * (
        h * published + 3 * h * model["moe_intermediate_size"]
        * model["n_shared_experts"])
    return total + model["vocab_size"] * h


def cache_bytes(model, contexts, index, sparse, swa, common):
    """What the rows of decode steps read of their sequences' caches."""
    return (index.bytes_needed(model, contexts, [], common)
            + sparse.bytes_needed(model, contexts, common)
            + swa.bytes_needed(model, contexts, [], common))
