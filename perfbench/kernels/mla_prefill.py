"""Dense latent attention for many query tokens of one sequence (a prefill
chunk, or the question behind a cached document): the least a chip must do,
whichever of the two forms of the algebra a kernel takes.

A request computes ``new`` tokens behind ``cached`` ones (the prefix cache's
hit; 0 for a prompt prefilled whole), in chunks of at most ``chunk`` tokens.
A chunk of n tokens behind c rows attends n c + n (n + 1) / 2 causal pairs.

- ABSORBED (queries folded through W_uk into latent space, values read as
  the latent rows): 2 x (lora + rope) + 2 x lora FLOPs a pair and head
  (2176 for A.X-K1), nothing to expand.
- DECOMPRESSED (keys and values expanded per head): 2 x (nope + rope) + 2 x
  v FLOPs a pair and head (640), and 2 x lora x heads x (nope + v) FLOPs a
  context row and chunk to expand it (16.8 M).

The least is the cheaper form, chunk by chunk: a kernel of either form is
held to the same work, and a share of the peak read against it cannot pass
100 % because the other form would have needed less. A 2048-token chunk
behind 16384 rows: 35.7 M pairs, 4.96 TFLOP absorbed, 1.46 + 0.31 = 1.77
decompressed. A 320-token question behind 12288 rows: 3.98 M pairs, 0.554
TFLOP absorbed, 0.163 + 0.212 = 0.375 decompressed.

Bytes: the chunk's context rows read once a layer (lora + rope values; the
stored row's pad lanes are left out), its queries read and its outputs
written once (heads x (nope + rope + v)): a long way under the FLOPs for
any chunk of more than a few tokens (at the ridge near 9 query tokens).
"""


def chunks(new, chunk):
    """(tokens before it within the request's new ones, tokens) of each
    chunk."""
    return [(lo, min(chunk, new - lo)) for lo in range(0, new, chunk)]


def pairs(cached, n):
    return n * cached + n * (n + 1) // 2


def chunk_flops(model, cached, n):
    """The cheaper form's FLOPs for one chunk and layer."""
    heads, lora = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    p = pairs(cached, n)
    absorbed = p * heads * (2 * (lora + rope) + 2 * lora)
    decompressed = (p * heads * (2 * (nope + rope) + 2 * v)
                    + (cached + n) * 2 * lora * heads * (nope + v))
    return min(absorbed, decompressed)


def flops_needed(model, requests, chunk=2048):
    """``requests``: [(cached, new)]."""
    return model["num_hidden_layers"] * sum(
        chunk_flops(model, cached + lo, n)
        for cached, new in requests for lo, n in chunks(new, chunk))


def bytes_needed(model, requests, chunk=2048, act_bytes=2):
    row = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    qo = model["num_attention_heads"] * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])
    return model["num_hidden_layers"] * act_bytes * sum(
        (cached + lo + n) * row + n * qo
        for cached, new in requests for lo, n in chunks(new, chunk))


def least_seconds(model, requests, peaks, chunk=2048):
    by_flops = flops_needed(model, requests, chunk) / peaks["flops_per_s"]
    by_bytes = bytes_needed(model, requests, chunk) / peaks["bytes_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
