"""Dense latent attention, one query token a sequence: the least a chip
must do to attend a paged latent cache (MLA with no indexer and no window:
every layer reads the whole context).

Bytes: every cached row of the context is read once a layer, ``kv_lora_rank
+ qk_rope_head_dim`` values (576 for A.X-K1; stored in 640 lanes, which the
count of the least leaves out); the queries, the output and the page table
are thousands of times smaller and are left out. FLOPs: in the absorbed
form (the query folded through W_uk, the output through W_uv) a head and
context row cost 2 x (lora + rope) for the score and 2 x lora for the
value. That is the cheaper form for one query: expanding a context row's
keys and values for all heads costs 2 x lora x heads x (nope + v) = 16.8
MFLOP, a hundred times the 0.14 MFLOP the row costs absorbed.

At 64 heads a context row and layer is 1152 B against 139 kFLOP: 1.41 ns of
a v5e's bandwidth against 0.71 ns of its bf16 peak, so bytes bind, by 2 x.
"""


def layers(model):
    return model["num_hidden_layers"]


def row_values(model):
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def bytes_per_context_token(model, kv_bytes=2):
    return layers(model) * row_values(model) * kv_bytes


def bytes_needed(model, contexts, kv_bytes=2):
    return bytes_per_context_token(model, kv_bytes) * sum(contexts)


def pair_flops(model):
    """FLOPs of one (query, context row) pair in the absorbed form, all
    heads, one layer."""
    return model["num_attention_heads"] * (
        2 * row_values(model) + 2 * model["kv_lora_rank"])


def flops_needed(model, contexts):
    return pair_flops(model) * layers(model) * sum(contexts)


def least_seconds(model, contexts, peaks):
    """(seconds, which bound binds)."""
    by_bytes = bytes_needed(model, contexts) / peaks["bytes_per_s"]
    by_flops = flops_needed(model, contexts) / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
