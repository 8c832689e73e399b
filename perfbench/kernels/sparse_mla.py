"""Latent attention over the rows the indexer chose (absorbed form): the
least a chip must do per token in the full-attention layers.

A token at context c attends k = min(c, index_topk) rows. Bytes: each
chosen row is read once for that token (``latent_row`` values; every token
has a choice of its own, so nothing is counted as shared). FLOPs, per token
and layer: every head's score against k rows (2 x row FLOPs each) and the
weighted sum of their latents (2 x kv_lora_rank): ``2 k heads (row +
kv_lora_rank)``; the absorption of q through W_uk and of the output through
W_uv are matrix products of the layer, not of the kernel.

At 128 heads: 2 x 128 x 1088 = 278528 FLOPs for 1152 B of row, 242 FLOP/B:
at the v5e's ridge (197e12 / 819e9 = 240). The larger bound is taken.
"""


def chosen(model, contexts):
    k = model["index_topk"]
    return sum(min(c, k) for c in contexts)


def prompt_contexts(prompt_lens):
    """The context (positions visible) of every token of whole prompts."""
    return [c for n in prompt_lens for c in range(1, n + 1)]


def flops_needed(model, contexts, common):
    per_row = 2 * model["num_attention_heads"] * (
        common.latent_row(model) + model["kv_lora_rank"])
    return per_row * common.full_layers(model) * chosen(model, contexts)


def bytes_needed(model, contexts, common, row_bytes=2):
    return (common.latent_row(model) * row_bytes
            * common.full_layers(model) * chosen(model, contexts))


def least_seconds(model, contexts, peaks, common):
    by_flops = flops_needed(model, contexts, common) / peaks["flops_per_s"]
    by_bytes = bytes_needed(model, contexts, common) / peaks["bytes_per_s"]
    return max(by_flops, by_bytes), ("bytes" if by_bytes >= by_flops
                                     else "flops")
