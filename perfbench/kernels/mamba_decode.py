"""Mamba-2 decode (the recurrent step): the least a chip must do to advance
one token of one sequence through the state-space layers.

Bytes, per decoded row and Mamba-2 layer: the recurrent state is read once
and written once, ``2 x H x P x N x 4`` (float32), and so is the
convolution's window of the last taps - 1 inputs, ``2 x conv_dim x (taps -
1) x 4`` with ``conv_dim = H P + 2 G N``. Nemotron 3 Nano: 2 x 64 x 64 x
128 x 4 = 4194304 B of state and 2 x 6144 x 3 x 4 = 147456 B of window,
4341760 B a row and layer. The token's own x, B, C, z (tens of kB) are left
out: the count is the least the algorithm needs.

FLOPs, per row, layer and head: decay the state (P N), the rank-one update
(2 P N), ``S C`` (2 P N): 5 P N. Far under the bytes' time on any chip:
bytes bind.
"""


def mamba_layers(model):
    return model["hybrid_override_pattern"].count("M")


def conv_dim(model):
    return (model["mamba_num_heads"] * model["mamba_head_dim"]
            + 2 * model["n_groups"] * model["ssm_state_size"])


def state_bytes_per_row_layer(model):
    """Recurrent state plus convolution window of one sequence in one
    Mamba-2 layer (float32), read or written once."""
    state = (model["mamba_num_heads"] * model["mamba_head_dim"]
             * model["ssm_state_size"])
    window = conv_dim(model) * (model["conv_kernel"] - 1)
    return 4 * (state + window)


def bytes_needed(model, rows):
    """``rows``: sequence-steps decoded (one per token that a decode step
    gave out)."""
    return 2 * state_bytes_per_row_layer(model) * mamba_layers(model) * rows


def flops_needed(model, rows):
    per_head = 5 * model["mamba_head_dim"] * model["ssm_state_size"]
    return per_head * model["mamba_num_heads"] * mamba_layers(model) * rows


def least_seconds(model, rows, peaks):
    """(seconds, which bound binds)."""
    by_bytes = bytes_needed(model, rows) / peaks["bytes_per_s"]
    by_flops = flops_needed(model, rows) / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
