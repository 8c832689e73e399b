"""Windowed latent attention (absorbed form): the least a chip must do per
token in the windowed layers.

A token at context c attends w = min(c, window) rows. FLOPs per token and
layer: ``2 w heads (row + swa_kv_lora_rank)`` as in kernels/sparse_mla.py
with the windowed geometry. Bytes: a decoded token reads its window's rows
once (w x row values); the tokens of one prompt share their windows, so a
prompt of n tokens reads each of its rows once and writes it once (2 n
rows), whatever the window.

At 64 heads of 1088 + 1024: 270336 FLOPs for 2176 B, 124 FLOP/B: under
the v5e's ridge, so a decoded token is bound by bytes and a prompt (one
read a row for up to 513 queries) by FLOPs.
"""


def window_rows(model, contexts):
    w = model["sliding_window_size"]
    return sum(min(c, w) for c in contexts)


def flops_needed(model, decode_contexts, prompt_lens, common):
    per_row = 2 * model["swa_num_attention_heads"] * (
        common.swa_row(model) + model["swa_kv_lora_rank"])
    w = model["sliding_window_size"]
    rows = window_rows(model, decode_contexts)
    for n in prompt_lens:
        full = max(0, n - w)
        rows += full * w + (n - full) * (n - full + 1) // 2
    return per_row * common.swa_layers(model) * rows


def bytes_needed(model, decode_contexts, prompt_lens, common, row_bytes=2):
    rows = window_rows(model, decode_contexts) + 2 * sum(prompt_lens)
    return common.swa_row(model) * row_bytes * common.swa_layers(model) * rows


def least_seconds(model, decode_contexts, prompt_lens, peaks, common):
    by_flops = flops_needed(model, decode_contexts, prompt_lens,
                            common) / peaks["flops_per_s"]
    by_bytes = bytes_needed(model, decode_contexts, prompt_lens,
                            common) / peaks["bytes_per_s"]
    return max(by_flops, by_bytes), ("bytes" if by_bytes >= by_flops
                                     else "flops")
