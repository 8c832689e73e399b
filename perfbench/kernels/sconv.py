"""The gated short convolution of an lfm2_moe decoder in a decode step:
the least a chip must do to advance the decoding rows by one token through
the operator of every "conv" layer, in-projection to out-projection.

Bytes. A layer's weights are read once a step whatever the rows: the
in-projection ``h x 3 h``, the out-projection ``h x h`` and the taps
``conv_L_cache x h`` (33.57 MB in bf16 at LFM2-24B-A2B's h = 2048; 1.007
GB over its 30 conv layers). Per decoded row and layer the window of the
last ``conv_L_cache - 1`` inputs is read once and written once, float32
(2 x 2 x 2048 x 4 = 32768 B), and the operator's input and output rows
cross once each (2 x h x 2 B). The in-projection's [rows, 3 h] result and
the two products need not leave the chip's fast memory and are left out:
the count is the least the algorithm needs.

FLOPs, per row and layer: the two projections ``2 h (3 h) + 2 h h``, the
two gates and the taps ``(2 + 2 conv_L_cache) h``. At 128 rows: 4.3 GFLOP
against 33.6 MB a layer, 128 FLOP/B where the chip's ridge is 240: bytes
bind.
"""


def conv_layers(model):
    return list(model["layer_types"]).count("conv")


def weight_params_per_layer(model):
    h = model["hidden_size"]
    return h * 3 * h + h * h + model["conv_L_cache"] * h


def window_bytes_per_row_layer(model):
    """One sequence's window in one conv layer (float32), read or written
    once."""
    return 4 * (model["conv_L_cache"] - 1) * model["hidden_size"]


def row_bytes_per_layer(model, act_bytes=2):
    """What one decoded row moves in one layer: its window in and out,
    its input and its output."""
    return (2 * window_bytes_per_row_layer(model)
            + 2 * model["hidden_size"] * act_bytes)


def bytes_needed(model, steps, rows, weight_bytes=2):
    """``steps`` decode steps that decoded ``rows`` rows in all (one a
    token that a decode step gave out)."""
    layers = conv_layers(model)
    return layers * (steps * weight_params_per_layer(model) * weight_bytes
                     + rows * row_bytes_per_layer(model))


def flops_needed(model, rows):
    h = model["hidden_size"]
    per_row = 2 * h * 3 * h + 2 * h * h + (2 + 2 * model["conv_L_cache"]) * h
    return conv_layers(model) * rows * per_row


def least_seconds(model, steps, rows, peaks):
    """(seconds, which bound binds)."""
    by_bytes = bytes_needed(model, steps, rows) / peaks["bytes_per_s"]
    by_flops = flops_needed(model, rows) / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
