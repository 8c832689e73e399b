"""Decode attention: the least a chip must do for one query token per
sequence against a paged KV cache.

Bytes: every cached key and value of the context is read once,
``2 x layers x kv_heads x head_dim x kv_bytes`` per token of context (the
query, the output and the page table are thousands of times smaller and are
left out: the count is the least the algorithm needs). FLOPs: ``q k^T`` and
``p v`` are 2 x 2 x q_heads x head_dim per token of context per layer.
"""


def kv_bytes_per_token(model, kv_bytes=2):
    d = model.get("head_dim") or (model["hidden_size"]
                                  // model["num_attention_heads"])
    return (2 * model["num_hidden_layers"] * model["num_key_value_heads"]
            * d * kv_bytes)


def bytes_needed(model, contexts, kv_bytes=2):
    return kv_bytes_per_token(model, kv_bytes) * sum(contexts)


def flops_needed(model, contexts):
    d = model.get("head_dim") or (model["hidden_size"]
                                  // model["num_attention_heads"])
    return (4 * model["num_attention_heads"] * d
            * model["num_hidden_layers"] * sum(contexts))


def least_seconds(model, contexts, peaks):
    """(seconds, which bound binds)."""
    by_bytes = bytes_needed(model, contexts) / peaks["bytes_per_s"]
    by_flops = flops_needed(model, contexts) / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
