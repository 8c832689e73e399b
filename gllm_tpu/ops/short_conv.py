"""The gated short convolution's causal part (lfm2_moe's "conv" layers):

    c_t = sum_j w[j] * g_{t-(K-1)+j}           depthwise, K taps, no bias,
                                               no activation, g_{<0} = 0

over a step's flat ragged batch, with the ``K - 1`` inputs before a row's
first new token carried per sequence in the slot pool (the layer's whole
state: ``ModelConfig.ssm_slot_shapes``).

The operator has no chunked rule. Over the flat token axis the inputs
before a token are its row's own earlier tokens, or the row's carried
window where the row begins: one gather by ``cu``. So a row of any length
takes the same path (a decoding row is a row of one token), nothing is
packed into chunks, and a step holds as many multi-token rows as it has
rows. Plain ``jax.numpy``: XLA fuses the products into the gathers.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def short_conv_decode(g: jnp.ndarray, weight: jnp.ndarray,
                      window: jnp.ndarray, slots: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One new token a row. g [S, C] float32; weight [K, C] (a tap's
    channels along the lanes: [C, K] is laid out transposed by the TPU
    compiler and copied back in every step); window
    [slots, K-1, C] float32, oldest first (in place under donation); slots
    [S] (a row that is not to be written names the dummy slot). Returns
    (c [S, C] float32, window)."""
    K = weight.shape[0]
    w = weight.astype(jnp.float32)
    old = window[slots]                                   # [S, K-1, C]
    c = g * w[K - 1] + sum(old[:, j] * w[j] for j in range(K - 1))
    new = jnp.concatenate([old[:, 1:], g[:, None, :]], axis=1)
    return c, window.at[slots].set(new)


def token_rows(cu: jnp.ndarray, T: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(for each of a step's T flat tokens its row [T], its place in that
    row [T]) from the rows' starts cu [S + 1]; tokens past cu[-1] fall to
    the last row. The same for every layer of a step: computed once, ahead
    of the layers (one comparison of tokens against starts, no search)."""
    t_idx = jnp.arange(T, dtype=jnp.int32)
    t_row = jnp.minimum(
        jnp.sum(t_idx[:, None] >= cu[None, 1:], axis=1, dtype=jnp.int32),
        cu.shape[0] - 2)
    return t_row, t_idx - cu[t_row]


def short_conv_rows(g: jnp.ndarray, weight: jnp.ndarray, window: jnp.ndarray,
                    slots: jnp.ndarray, cu: jnp.ndarray, rows, dummy
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rows of any number of new tokens over the flat token axis. g [T, C]
    float32; cu [S + 1] the rows' starts (rows past the last real one are
    empty); ``rows`` = ``token_rows(cu, T)``; the others as
    ``short_conv_decode``. The window is written from each row's last
    ``K - 1`` real inputs (its own tokens, and the carried window's newest
    rows where it has fewer); an empty row writes ``dummy``. Returns (c
    [T, C] float32, window); c past cu[-1] is meaningless."""
    T, S, K = g.shape[0], slots.shape[0], weight.shape[0]
    w = weight.astype(jnp.float32)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    t_row, local = rows
    old = window[slots]                                   # [S, K-1, C]

    def before(at, row, place, back):
        # the input ``back`` places before flat index ``at``, which is
        # place ``place`` of row ``row``: the row's own token, or row
        # K-1 - (back - place) of its carried window
        own = g[jnp.clip(at - back, 0, T - 1)]
        carried = old[row, jnp.clip(K - 1 - back + place, 0, K - 2)]
        return jnp.where((place >= back)[:, None], own, carried)

    c = g * w[K - 1] + sum(before(t_idx, t_row, local, K - 1 - j) * w[j]
                           for j in range(K - 1))
    # the new window: the K-1 inputs up to and with each row's last token
    n = cu[1:] - cu[:-1]
    new = jnp.stack([before(cu[1:], jnp.arange(S, dtype=jnp.int32), n,
                            K - 1 - j) for j in range(K - 1)], axis=1)
    return c, window.at[jnp.where(n > 0, slots, dummy)].set(new)
