"""Mamba-2 prefill (the chunked rule, SSD): the least a chip must do to
take whole prompts through the state-space layers, in chunks of C =
``chunk_size`` tokens. A scalar decay a head means no triangular solve.

FLOPs per token and Mamba-2 layer (a multiply-add is 2; a product with a
triangular factor counts the half that is not zero):

- in the chunk: ``C B^T`` once a group (G C N) and the masked scores times
  ``dt x`` once a head (H C P);
- against the state, a head: ``(C e^l) S^T`` and ``(dt x e^{l_C - l})^T
  B``, 2 P N each: 4 H P N.

Nemotron 3 Nano: 8 x 128 x 128 + 64 x (128 x 64 + 4 x 64 x 128) = 2752512 a
token and layer; x 7 layers = 19.3 MFLOP a token. The rule runs in float32.

Bytes: per token and layer the convolution's input and the mixer's output
before the gate (``conv_dim + H P`` values of 2 B) and per prompt and layer
the state and window once in and once out (kernels/mamba_decode.py). A
prompt of 320 tokens: 7 x (320 x 20480 + 4341760) = 76 MB, 0.093 ms at 819
GB/s, against 6.2 GFLOP, 0.031 ms at the bf16 peak: bytes bind; the rule's
float32 products run several times under that peak, so on the chip the
FLOPs are what takes the time.
"""


def flops_per_token_layer(model):
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n, c = model["n_groups"], model["ssm_state_size"], model["chunk_size"]
    return g * c * n + h * (c * p + 4 * p * n)


def flops_needed(model, prompt_lens, decode):
    return (flops_per_token_layer(model) * decode.mamba_layers(model)
            * sum(prompt_lens))


def bytes_needed(model, prompt_lens, decode, act_bytes=2):
    per_token = (decode.conv_dim(model) + model["mamba_num_heads"]
                 * model["mamba_head_dim"]) * act_bytes
    per_prompt = 2 * decode.state_bytes_per_row_layer(model)
    return decode.mamba_layers(model) * (per_token * sum(prompt_lens)
                                         + per_prompt * len(prompt_lens))


def least_seconds(model, prompt_lens, peaks, decode):
    """``decode``: the module kernels/mamba_decode.py (layer count, state
    bytes). (seconds, which bound binds)."""
    by_flops = flops_needed(model, prompt_lens, decode) / peaks["flops_per_s"]
    by_bytes = bytes_needed(model, prompt_lens, decode) / peaks["bytes_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
