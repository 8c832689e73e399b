"""Prefill (ragged, causal) attention: the least a chip must do to prefill
whole prompts.

FLOPs: ``4 x q_heads x head_dim`` per causal query-key pair per layer; a
prompt of n tokens has n (n + 1) / 2 pairs. Bytes: the prompt's keys and
values written and read once, and q and the output once.
"""


def causal_pairs(prompt_lens):
    return sum(n * (n + 1) // 2 for n in prompt_lens)


def flops_needed(model, prompt_lens):
    d = model.get("head_dim") or (model["hidden_size"]
                                  // model["num_attention_heads"])
    return (4 * model["num_attention_heads"] * d
            * model["num_hidden_layers"] * causal_pairs(prompt_lens))


def bytes_needed(model, prompt_lens, act_bytes=2):
    d = model.get("head_dim") or (model["hidden_size"]
                                  // model["num_attention_heads"])
    per_token = (2 * model["num_attention_heads"]
                 + 2 * model["num_key_value_heads"]) * d * act_bytes
    return per_token * model["num_hidden_layers"] * sum(prompt_lens)


def least_seconds(model, prompt_lens, peaks):
    by_flops = flops_needed(model, prompt_lens) / peaks["flops_per_s"]
    by_bytes = bytes_needed(model, prompt_lens) / peaks["bytes_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
