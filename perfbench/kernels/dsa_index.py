"""The DSA indexer: the least a chip must do to score, for every token, the
positions of its sequence that it may see, in the full-attention layers.

Per (query, visible position) and layer: ``index_n_heads`` dot products of
``index_head_dim`` (2 nh hd FLOPs; the ReLU and the weighted sum over heads
are 3 nh more and left out). A decoded token at context c sees c positions;
a prompt of n tokens has n (n + 1) / 2 pairs.

Bytes, per layer: a decoded token reads the index keys of its context once
(c x hd x key bytes: nothing is shared between sequences); a prompt's keys
are written once and read once per chunk that follows (the queries of one
chunk share them): for chunks ending at e_1 < e_2 < ... the reads are
sum(e_i) keys. The queries (nh x hd a token) are left out.

At dots3_note's sizes (64 heads of 128) a decoded token is bound by bytes
(16384 FLOPs a byte-pair of 256 B: 64 FLOP/B against the v5e's ridge of
240), a 2048-token chunk by FLOPs.
"""


def pairs(decode_contexts, prompt_lens):
    return (sum(decode_contexts)
            + sum(n * (n + 1) // 2 for n in prompt_lens))


def flops_needed(model, decode_contexts, prompt_lens, common):
    return (2 * model["index_n_heads"] * model["index_head_dim"]
            * common.full_layers(model)
            * pairs(decode_contexts, prompt_lens))


def bytes_needed(model, decode_contexts, prompt_lens, common, chunk=2048,
                 key_bytes=2):
    keys = sum(decode_contexts)
    for n in prompt_lens:
        keys += n + sum(lo + m for lo, m in common.chunks(n, chunk))
    return (keys * model["index_head_dim"] * key_bytes
            * common.full_layers(model))


def least_seconds(model, decode_contexts, prompt_lens, peaks, common):
    by_flops = flops_needed(model, decode_contexts, prompt_lens,
                            common) / peaks["flops_per_s"]
    by_bytes = bytes_needed(model, decode_contexts, prompt_lens,
                            common) / peaks["bytes_per_s"]
    return max(by_flops, by_bytes), ("bytes" if by_bytes >= by_flops
                                     else "flops")
