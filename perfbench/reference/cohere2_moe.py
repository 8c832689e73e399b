"""Plain reference for CohereLabs/command-a-plus-05-2026 (``model_type:
cohere2_moe``): a decoder of PARALLEL blocks over one LayerNorm, whose
attention is windowed with rotary embedding or full without any position,
by ``layer_types``.

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, one sequence at a time; attention over a dense [queries, keys]
mask built from the positions, a block of queries at a time so that the
scores fit. For layer l of kind ``layer_types[l]`` and stream x [T, hidden]:

    h = LN(x) = (x - mean x) / sqrt(var x + layer_norm_eps) * w_l  (no bias)
    q = h Wq (num_attention_heads x head_dim), k = h Wk, v = h Wv
                                        (num_key_value_heads x head_dim)
    sliding_attention: q, k <- rotary over all head_dim (rotary_pct 1),
        pairs (2i, 2i+1) turned by t * theta^(-2i / head_dim)
        (position_embedding_type rope_gptj, rope_theta); the query at
        position t sees j with t - sliding_window < j <= t
    full_attention: no positional term; the query at t sees j <= t
    a = concat_heads(softmax(q k^T / sqrt(head_dim)) v) Wo
    s = sigmoid(h Wr) over all num_experts (expert_selection_fn); ids = the
        num_experts_per_tok largest; w_i = s_i / sum of the chosen
        (norm_topk_prob); no bias, no scaling factor
    E(h) = (silu(h Wgate) * (h Wup)) Wdown at width intermediate_size
    m = sum_{i chosen and held} w_i E_i(h) + (1 / n) sum_{j < n} S_j(h)
        (n = num_shared_experts of E's form and width;
        shared_expert_combination_strategy "average")
    x <- x + a + m                                  (use_parallel_block)

    logits = LN(x; w_f) Embed^T * logit_scale        (tie_word_embeddings)

Departures from the published model, each because the benchmark's
configuration says so: ``num_experts`` in the model dict counts the experts
HELD (``ep_share`` gives the published count, the chips that share a layer
and this chip's rank): the router is as wide as published and normalises
over all the experts it chose, the absent experts' part is left out, and
that partial result goes on to the next block, as on a chip that runs
without its exchange. The vocabulary is the slice the configuration gives.
The vision tower is not part of the language model and is left out.
Weights are random, rounded to the served dtype (bf16); arithmetic on them
is float32.

Nothing here is taken from the program under test. ``make_weights`` draws
with ``jax.random`` in the order, shapes and scales of the served
``--load-format dummy`` recipe (the n-th draw from ``fold_in(key(seed),
n)``: q, k, v, o, the router, the shared experts' gate, up and down
matrices as one of the n widths on end, each stacked over the layers; then
the routed experts' gate matrices a layer at a time, their up matrices,
their down matrices; then the embedding; matrices normal, 1/sqrt(fan-in),
a shared expert's fan-in its own width, the tied embedding's the hidden
size, as the head it also is), so that the same seed names the
same model on both sides. That the two recipes agree bit for bit is a test
(``tests/perfbench/test_reference_cohere2_moe.py``), not an import.
"""

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 128          # an expert's tokens are padded to a multiple of this
Q_BLOCK = 256       # queries of one block of the attention


def held_experts(model):
    """(router width, the ids of the experts held here)."""
    held = model["num_experts"]
    share = model.get("ep_share")
    if not share:
        return held, list(range(held))
    assert share["num_experts"] == held * share["chips"]
    first = held * share.get("rank", 0)
    return share["num_experts"], list(range(first, first + held))


def make_weights(model, seed, dtype=jnp.bfloat16, stage_layers=None):
    """Seeded weights for ``model`` (the published ``config.json`` keys).
    Returns {"layers": [per-layer dict with "kind", "norm", ...], "embed",
    "final_norm"}; matrices are [in, out]."""
    assert not stage_layers, "one stage"
    kinds = list(model["layer_types"])
    n_layers = model["num_hidden_layers"]
    assert len(kinds) == n_layers, kinds
    hidden, vocab = model["hidden_size"], model["vocab_size"]
    d = model["head_dim"]
    qd = model["num_attention_heads"] * d
    kd = model["num_key_value_heads"] * d
    inter, n_shared = model["intermediate_size"], model["num_shared_experts"]
    wide, held = held_experts(model)
    key = jax.random.key(seed)
    keys = (jax.random.fold_in(key, i) for i in itertools.count())

    def normal(shape, scale):
        # the served recipe draws, scales and rounds in three steps; the
        # barrier keeps them apart (perfbench/reference/olmo_hybrid.py)
        return jax.jit(lambda k: (jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32))
            * scale).astype(dtype))(next(keys))

    s_in = hidden ** -0.5
    stacked = {
        "q_proj": normal((n_layers, hidden, qd), s_in),
        "k_proj": normal((n_layers, hidden, kd), s_in),
        "v_proj": normal((n_layers, hidden, kd), s_in),
        "o_proj": normal((n_layers, qd, hidden), qd ** -0.5),
        "router": normal((n_layers, hidden, wide), s_in),
        # the n shared experts on end: columns j * inter .. (j + 1) * inter
        # of gate and up, and those rows of down, are shared expert j
        "shared_gate": normal((n_layers, hidden, n_shared * inter), s_in),
        "shared_up": normal((n_layers, hidden, n_shared * inter), s_in),
        "shared_down": normal((n_layers, n_shared * inter, hidden),
                              inter ** -0.5),
    }
    routed = {
        name: [normal(shape, scale) for _ in range(n_layers)]
        for name, shape, scale in (
            ("w_gate", (len(held), hidden, inter), s_in),
            ("w_up", (len(held), hidden, inter), s_in),
            ("w_down", (len(held), inter, hidden), inter ** -0.5))}
    # the tied embedding is also the head: fan-in hidden (at unit variance
    # a token's logit for itself would be hidden / sigma(x), thousands)
    assert model.get("tie_word_embeddings", True), "the tied head only"
    out = {"layers": [], "final_norm": jnp.ones((hidden,), dtype),
           "embed": normal((vocab, hidden), s_in)}
    for i, kind in enumerate(kinds):
        layer = {"kind": kind, "norm": jnp.ones((hidden,), dtype)}
        layer.update({k: v[i] for k, v in stacked.items()})
        layer.update({k: v[i] for k, v in routed.items()})
        out["layers"].append(layer)
    return out


def layer_norm(x, w, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _mm(a, w):
    return a @ w.astype(jnp.float32)


def lower_precision(w, mode):
    """``w`` [in, out] as it reads after a round trip through the precision
    below bf16: symmetric, one scale per output channel (its largest
    magnitude), ``int8`` rounded to 255 levels or ``fp8`` (e4m3) cast. Only
    the control uses it: the reference with its layer matrices so stored
    has to come out NOT correct."""
    wf = w.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(wf), axis=0, keepdims=True), 1e-9)
    if mode == "int8":
        scale = absmax / 127.0
        return jnp.clip(jnp.round(wf / scale), -127, 127) * scale
    if mode == "fp8":
        scale = absmax / float(jnp.finfo(jnp.float8_e4m3fn).max)
        return (wf / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown control precision {mode!r}")


@functools.partial(jax.jit, static_argnames=("theta",))
def rope_gptj(x, positions, theta):
    """x [T, heads, D]: the pair (2i, 2i + 1) of every head turned by the
    angle position * theta^(-2i / D)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def visible(kind, q_pos, k_pos, window):
    """The dense mask [queries, keys] from the positions alone."""
    seen = k_pos[None, :] <= q_pos[:, None]
    if kind == "sliding_attention":
        # the current token counted: window positions in all
        seen &= k_pos[None, :] > q_pos[:, None] - window
    return seen


@functools.partial(jax.jit, static_argnames=("kind", "window"))
def _attend(q_b, q_pos, k, v, kind, window):
    """One block of queries q_b [n, hq, d] at positions q_pos against the
    keys and values k, v [hkv, S, d] of positions 0 .. S - 1. The query
    heads of a kv head share its keys and values: one product a kv head
    over (query, head of the group) rows. Compiled once a shape, whatever
    the layer."""
    n_q, hq, d = q_b.shape
    hkv = k.shape[0]
    q_g = q_b.reshape(n_q, hkv, hq // hkv, d).transpose(1, 0, 2, 3)
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("kqgd,ksd->kqgs", q_g / math.sqrt(d), k)
        seen = visible(kind, q_pos, jnp.arange(k.shape[1]), window)
        scores = jnp.where(seen[None, :, None, :], scores, -jnp.inf)
        # the softmax, with its division done on the [query, d] result
        # instead of on every score (every query sees itself: no row is
        # empty)
        e = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        out = (jnp.einsum("kqgs,ksd->kqgd", e, v)
               / jnp.sum(e, axis=-1, keepdims=True))
    return out.transpose(1, 0, 2, 3).reshape(n_q, hq, d)


def attention(model, h, layer, kind, mm, q_block=Q_BLOCK, rows=None):
    """h [T, hidden] -> the attention half of the block [T, hidden], or,
    with ``rows`` (ascending positions), of those queries only [len(rows),
    hidden] against the keys and values of all T positions. A block of
    queries is scored against the keys up to its own last position (what
    lies behind is masked anyway: the dense mask is built over that
    slice, from the positions)."""
    t = h.shape[0]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    pos = jnp.arange(t)
    at = pos if rows is None else jnp.asarray(rows, jnp.int32)
    q = mm(h[at], layer["q_proj"]).reshape(len(at), hq, d)
    k = mm(h, layer["k_proj"]).reshape(t, hkv, d)
    v = mm(h, layer["v_proj"]).reshape(t, hkv, d)
    if kind == "sliding_attention":
        theta = (model.get("rope_parameters") or {}).get(
            "rope_theta", model.get("rope_theta"))
        q, k = rope_gptj(q, at, theta), rope_gptj(k, pos, theta)
    k, v = (a.transpose(1, 0, 2) for a in (k, v))            # [hkv, T, d]
    n = len(at)
    pad = (-n) % q_block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    q_pos = jnp.pad(at, (0, pad), constant_values=int(at[-1]))
    out = []
    for lo in range(0, n + pad, q_block):
        # keys past the block's last query are masked for every query of
        # it: left out, in whole blocks so that few shapes are compiled
        keys = min(t, -(-(int(q_pos[lo + q_block - 1]) + 1) // q_block)
                   * q_block)
        out.append(_attend(q[lo:lo + q_block], q_pos[lo:lo + q_block],
                           k[:, :keys], v[:, :keys], kind=kind,
                           window=model["sliding_window"]))
    return mm(jnp.concatenate(out)[:n].reshape(n, hq * d), layer["o_proj"])


def route(model, h, layer):
    """(ids [T, k] over all the published experts, weights [T, k])."""
    s = jax.nn.sigmoid(h @ layer["router"].astype(jnp.float32))
    _, ids = jax.lax.top_k(s, model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if model.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w


def gated(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


@jax.jit
def _add_expert(out, h, tok, weight, w_gate, w_up, w_down):
    """out + weight * expert(h[tok]) scattered back to the rows ``tok``."""
    with jax.default_matmul_precision("highest"):
        y = gated(h[tok], w_gate, w_up, w_down)
    return out.at[tok].add(y * weight[:, None])


def routed_part(model, h, layer, mm, experts=None):
    """What the experts ``experts`` (ids among all the published ones;
    this chip's share by default) give: sum_i w_i E_i(h) over the
    assignments to them. An expert takes its own tokens, padded to a
    multiple of ROWS with rows of weight 0 (one compiled shape a
    multiple). ``layer`` holds the matrices of the experts
    ``held_experts(model)`` names, in that order."""
    _, held = held_experts(model)
    experts = held if experts is None else experts
    ids, w = (np.asarray(a) for a in route(model, h, layer))
    out = jnp.zeros_like(h)
    for e in experts:
        tok, slot = np.nonzero(ids == e)
        if not len(tok):
            continue
        at = held.index(e)
        pad = (-len(tok)) % ROWS
        out = _add_expert(
            out, h, np.pad(tok, (0, pad)).astype(np.int32),
            np.pad(w[tok, slot], (0, pad)).astype(np.float32),
            *(_stored(layer[k][at], mm)
              for k in ("w_gate", "w_up", "w_down")))
    return out


def _stored(w, mm):
    """``w`` as ``mm`` reads it (float32, or through the control's
    lower precision)."""
    return getattr(mm, "stored", lambda x: x.astype(jnp.float32))(w)


@functools.partial(jax.jit, static_argnames=("n", "control"))
def _shared_mean(h, gate, up, down, n, control):
    mm = _matmul(control)
    inter = gate.shape[1] // n
    total = jnp.zeros_like(h)
    with jax.default_matmul_precision("highest"):
        for j in range(n):
            cols = slice(j * inter, (j + 1) * inter)
            total = total + gated(h, _stored(gate[:, cols], mm),
                                  _stored(up[:, cols], mm),
                                  _stored(down[cols, :], mm))
    return total / n


def shared_part(model, h, layer, control=None):
    """The mean of the shared experts' outputs: expert j is the columns
    j * intermediate_size .. of gate and up and those rows of down. (The
    other reading of ``shared_expert_combination_strategy: average``, the
    mean of the routed and the shared part, is not taken: the
    configuration's ``assumed``.)"""
    return _shared_mean(h, layer["shared_gate"], layer["shared_up"],
                        layer["shared_down"], n=model["num_shared_experts"],
                        control=control)


def _matmul(control):
    """``a @ w`` with w in float32, or (the control) as it reads after a
    round trip through the lower precision: every matrix the served
    ``--quantization`` stores so (the router and the embedding stay as
    they are)."""
    if control is None:
        return _mm

    def mm(a, w):
        return a @ lower_precision(w, control)
    mm.stored = lambda w: lower_precision(w, control)
    mm.control = control
    return mm


def block_parts(model, h, layer, mm, experts=None, rows=None):
    """(attention, routed experts' part, shared experts' part) of one
    layer for the normed stream ``h``; with ``rows``, of those positions
    only (attention still sees every position's keys and values)."""
    layer = dict(layer)
    kind = layer.pop("kind")
    mine = h if rows is None else h[jnp.asarray(rows, jnp.int32)]
    with jax.default_matmul_precision("highest"):
        return (attention(model, h, layer, kind, mm, rows=rows),
                routed_part(model, mine, layer, mm, experts),
                shared_part(model, mine, layer,
                            getattr(mm, "control", None)))


def hidden_states(model, weights, tokens, control=None, rows=None):
    """Final-norm hidden states [T, hidden] of one token sequence; with
    ``rows`` (ascending positions), of those positions only [len(rows),
    hidden]: every layer but the last is computed whole, since the last
    layer's keys and values need every position, and the last layer's
    block for the rows asked alone (a decode probe asks for its last few
    positions: a quarter less of the float32 work, nothing else differs)."""
    eps = model.get("layer_norm_eps", 1e-5)
    tokens = jnp.asarray(tokens, jnp.int32)
    mm = _matmul(control)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for layer in weights["layers"][:-1]:
            h = layer_norm(x, layer["norm"], eps)
            x = x + sum(block_parts(model, h, layer, mm))
        last = weights["layers"][-1]
        h = layer_norm(x, last["norm"], eps)
        if rows is not None:
            x = x[jnp.asarray(rows, jnp.int32)]
        x = x + sum(block_parts(model, h, last, mm, rows=rows))
        return layer_norm(x, weights["final_norm"], eps)


def logits(model, weights, tokens, control=None):
    with jax.default_matmul_precision("highest"):
        return _mm(hidden_states(model, weights, tokens, control),
                   weights["embed"].T) * model.get("logit_scale", 1)


def logprobs(model, weights, tokens, want, control=None, block=256):
    """Log-probabilities the model gives, after reading ``tokens[:i+1]``, to
    each token id in ``want[i]`` (a list, possibly empty), for every i.
    Returns a list of lists shaped like ``want``. The vocabulary is
    normalised in blocks of positions so the logits never exist whole."""
    rows = [i for i, ids in enumerate(want) if ids]
    hid = hidden_states(model, weights, tokens, control, rows=rows)
    scale = model.get("logit_scale", 1)

    @jax.jit
    def block_lp(h, embed):     # the head is an argument, not a constant
        with jax.default_matmul_precision("highest"):
            return jax.nn.log_softmax(_mm(h, embed.T) * scale, axis=-1)

    out = [[] for _ in want]
    for lo in range(0, len(rows), block):
        idx = list(range(lo, min(lo + block, len(rows))))
        pad = idx + [idx[-1]] * (block - len(idx))     # one compiled shape
        take = jax.device_get(block_lp(hid[jnp.asarray(pad)],
                                       weights["embed"]))
        for r, i in enumerate(idx):
            out[rows[i]] = [float(take[r, tok]) for tok in want[rows[i]]]
    return out
