"""Mamba-2 prefill (the chunked rule, SSD) under Falcon-H1's key names: the
least a chip must do to take whole prompts through the state-space half of
every layer, in chunks of C = ``mamba_chunk_size`` tokens. A scalar decay a
head means no triangular solve.

FLOPs per token and layer (a multiply-add is 2; a product with a triangular
factor counts the half that is not zero):

- in the chunk: ``C B^T`` once a group (G C N) and the masked scores times
  ``dt x`` once a head (H C P);
- against the state, a head: ``(C e^l) S^T`` and ``(dt x e^{l_C - l})^T
  B``, 2 P N each: 4 H P N.

Falcon-H1-34B: 2 x 128 x 256 + 32 x (128 x 128 + 4 x 128 x 256) = 4784128 a
token and layer; x 6 layers = 28.7 MFLOP a token. The rule runs in float32.

Bytes: per token and layer the convolution's input and the branch's output
before the gate (``conv_dim + d_ssm`` values of 2 B) and per prompt and
layer the state and window once in and once out
(kernels/par_mamba_decode.py). A prompt of 320 tokens: 6 x (320 x 18432 +
8511488) = 86 MB, 0.106 ms at 819 GB/s, against 9.2 GFLOP, 0.047 ms at the
bf16 peak: bytes bind; the rule's float32 products run several times under
that peak, so on the chip the FLOPs are what takes the time.
"""


def flops_per_token_layer(model):
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    g, n = model["mamba_n_groups"], model["mamba_d_state"]
    c = model["mamba_chunk_size"]
    return g * c * n + h * (c * p + 4 * p * n)


def flops_needed(model, prompt_lens, decode):
    return (flops_per_token_layer(model) * decode.layers(model)
            * sum(prompt_lens))


def bytes_needed(model, prompt_lens, decode, act_bytes=2):
    per_token = (decode.conv_dim(model) + model["mamba_d_ssm"]) * act_bytes
    per_prompt = 2 * decode.state_bytes_per_row_layer(model)
    return decode.layers(model) * (per_token * sum(prompt_lens)
                                   + per_prompt * len(prompt_lens))


def least_seconds(model, prompt_lens, peaks, decode):
    """``decode``: the module kernels/par_mamba_decode.py (layer count,
    state bytes). (seconds, which bound binds)."""
    by_flops = flops_needed(model, prompt_lens, decode) / peaks["flops_per_s"]
    by_bytes = bytes_needed(model, prompt_lens, decode) / peaks["bytes_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
