"""Windowed GQA attention over the paged pool: the least a chip must do
for the layers that attend the last ``sliding_window`` positions only
(the current one counted), whose older rows stay in their pages and are
never read.

Decode (one query token a sequence): K and V of ``min(context, window)``
rows read once a windowed layer (kernels/attn_decode.py's bytes a row);
FLOPs 4 x q_heads x head_dim a row.

A chunk of ``new`` query tokens behind ``cached`` rows (a prefill chunk,
or a request that hit the prefix cache for its document): the query at
position p sees ``min(p + 1, window)`` keys, so the pairs are the sum of
that over p = cached .. cached + new - 1; FLOPs 4 x q_heads x head_dim a
pair. Bytes: the rows any of the chunk's windows reaches, ``min(cached,
window - 1) + new``, K and V once, and q and the output of the new tokens
once (kernels/attn_prefill.py's bytes a token without its K and V, which
are in the rows already).
"""


def decode_rows(model, contexts):
    w = model["sliding_window"]
    return sum(min(c, w) for c in contexts)


def chunk_pairs(model, cached, new):
    w = model["sliding_window"]
    return sum(min(p + 1, w) for p in range(cached, cached + new))


def chunk_rows(model, cached, new):
    return min(cached, model["sliding_window"] - 1) + new


def _dims(model):
    return (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"])


def least_seconds(model, layers, contexts, chunks, peaks, act_bytes=2):
    """(seconds, which bound binds) for ``layers`` windowed layers that
    decode a token at each of ``contexts`` and prefill ``chunks`` ((cached,
    new) pairs)."""
    hq, hkv, d = _dims(model)
    rows = decode_rows(model, contexts) + sum(
        chunk_rows(model, c, n) for c, n in chunks)
    pairs = decode_rows(model, contexts) + sum(
        chunk_pairs(model, c, n) for c, n in chunks)
    new = sum(n for _, n in chunks)
    nbytes = layers * act_bytes * (2 * hkv * d * rows + 2 * hq * d * new)
    flops = layers * 4 * hq * d * pairs
    by_bytes = nbytes / peaks["bytes_per_s"]
    by_flops = flops / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
