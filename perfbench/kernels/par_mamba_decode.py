"""Mamba-2 decode (the recurrent step) under Falcon-H1's key names: the
least a chip must do to advance one token of one sequence through the
state-space half of every layer (every layer has one: ``attn_layer_indices``
null says nothing of the Mamba-2 heads, which no layer lacks).

Bytes, per decoded row and layer: the recurrent state is read once and
written once, ``2 x H x P x N x 4`` (float32), and so is the convolution's
window of the last taps - 1 inputs, ``2 x conv_dim x (taps - 1) x 4`` with
``conv_dim = d_ssm + 2 G N``. Falcon-H1-34B: 2 x 32 x 128 x 256 x 4 =
8388608 B of state and 2 x 5120 x 3 x 4 = 122880 B of window, 8511488 B a
row and layer (twice Nemotron 3 Nano's: the state is 128 x 256 a head).
The token's own x, B, C, z (tens of kB) are left out: the count is the
least the algorithm needs.

FLOPs, per row, layer and head: decay the state (P N), the rank-one update
(2 P N), ``S C`` (2 P N): 5 P N. Far under the bytes' time on any chip:
bytes bind.
"""


def layers(model):
    return model["num_hidden_layers"]


def conv_dim(model):
    return (model["mamba_d_ssm"]
            + 2 * model["mamba_n_groups"] * model["mamba_d_state"])


def state_bytes_per_row_layer(model):
    """Recurrent state plus convolution window of one sequence in one
    layer (float32), read or written once."""
    state = (model["mamba_n_heads"] * model["mamba_d_head"]
             * model["mamba_d_state"])
    window = conv_dim(model) * (model["mamba_d_conv"] - 1)
    return 4 * (state + window)


def bytes_needed(model, rows):
    """``rows``: sequence-steps decoded (one per token that a decode step
    gave out)."""
    return 2 * state_bytes_per_row_layer(model) * layers(model) * rows


def flops_needed(model, rows):
    per_head = 5 * model["mamba_d_head"] * model["mamba_d_state"]
    return per_head * model["mamba_n_heads"] * layers(model) * rows


def least_seconds(model, rows, peaks):
    """(seconds, which bound binds)."""
    by_bytes = bytes_needed(model, rows) / peaks["bytes_per_s"]
    by_flops = flops_needed(model, rows) / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
