"""Sizes shared by the roofline functions of a latent-attention decoder
with windowed layers and a share of its experts (dots3_note): layer counts
by kind and the widths of what each kind caches. ``model`` is the served
``config.json`` (the configuration file's keys)."""


def full_layers(model):
    return sum(1 for t in model["layer_types"] if t == "full_attention")


def swa_layers(model):
    return sum(1 for t in model["layer_types"] if t == "sliding_attention")


def moe_layers(model):
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def latent_row(model):
    """Values of a full layer's cached row: the latent and the shared
    rotary key (576 for dots3_note; stored in 640 lanes, which the count
    of the least leaves out)."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def swa_row(model):
    return model["swa_kv_lora_rank"] + model["swa_qk_rope_head_dim"]


def chunks(prompt_len, chunk):
    """(first position, tokens) of each prefill chunk of a prompt."""
    return [(lo, min(chunk, prompt_len - lo))
            for lo in range(0, prompt_len, chunk)]
