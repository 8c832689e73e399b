"""Plain reference for LiquidAI/LFM2-24B-A2B (``model_type: lfm2_moe``): a
decoder whose layers are an operator and a feed-forward, each behind its
RMSNorm; the operator a gated short convolution or grouped-query
attention, by ``layer_types``.

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, one sequence at a time; the convolution as ``conv_L_cache``
shifted products over the whole sequence, attention over a dense [queries,
keys] mask, a block of queries at a time so that the scores fit. With
d = hidden_size, eps = norm_eps, RMSNorm(x; w) = x / sqrt(mean x^2 + eps)
* w, no bias anywhere (conv_bias false), for the stream x [T, d]:

    x_0 = Embed[token]
    layer i:
      x <- x + Op_i(RMSNorm(x; w_op_i))     ShortConv where layer_types[i]
                                            == "conv", else Attn
      x <- x + FFN_i(RMSNorm(x; w_ffn_i))   SwiGLU(intermediate_size) for
                                            i < num_dense_layers, else MoE
    logits = RMSNorm(x; w_emb_norm) Embed^T

    ShortConv(u):  [B | C | z] = u W_in      d -> 3 d, thirds in this order
                   g_t = B_t * z_t
                   c_t = sum_{j < K} w[:, j] * g_{t-(K-1)+j}
                                             depthwise, causal, K =
                                             conv_L_cache taps, g_{<0} = 0,
                                             no bias, NO activation
                   y_t = (C_t * c_t) W_out
    Attn(u):  q = u W_q [heads x D], k = u W_k, v = u W_v [kv heads x D],
              D = d / heads; q, k <- RMSNorm over each head's D (w_qn,
              w_kn) BEFORE the rotary embedding; rotary over all D, halves
              rotated, theta = rope_parameters.rope_theta; causal
              softmax(q k^T / sqrt(D)) v, query head j reads KV head
              j // (heads / kv heads); then W_o
    MoE(r):   s = sigmoid(r W_r) over all num_experts, float32
              chosen = top num_experts_per_tok of (s + b)   b = expert_bias
              w = s[chosen] / (sum s[chosen] + 1e-6)        norm_topk_prob
              sum_k w_k (silu(r W1_e) * (r W3_e)) W2_e x routed_scaling_factor
              no shared expert

Departures from the published model, each because the benchmark's
configuration says so or the catalog's row is silent:
- ``num_experts`` in the model dict counts the experts HELD (``ep_share``
  gives the published count, the chips that share a layer and this chip's
  rank): the router is as wide as published and normalises over all the
  experts it chose, the absent experts' terms are left out, and that
  partial result goes on to the next layer, as on a chip that runs without
  its exchange. The vocabulary is the slice the configuration gives.
- the head is tied to the embedding (the family's ``tie_embedding``
  default; the row gives no key).
- the published cache keeps ``conv_L_cache`` rows of g a sequence; the
  oldest is never read again (c_t reads g_{t-2} .. g_t), so the served
  state keeps K - 1 and nothing here depends on the difference.
- the rotary convention (halves rotated, the whole head), the 1e-6 of the
  normalisation and ``intermediate_size`` used as it stands are the
  family's modelling code as remembered: the configuration's ``assumed``.
Weights are random, rounded to the served dtype (bf16); arithmetic on them
is float32.

Nothing here is taken from the program under test. ``make_weights`` draws
with ``jax.random`` in the order, shapes and scales of the served
``--load-format dummy`` recipe (the n-th draw from ``fold_in(key(seed),
n)``: the conv layers' W_in, taps, W_out; the attention layers' W_q, W_k,
W_v, W_o; the dense layers' W1, W3, W2; the routers ([experts, hidden], the
taps [taps, hidden]), each stacked over
the layers of its kind; then the held experts' W1 a layer at a time, their
W3, their W2; then the embedding; matrices normal, 1/sqrt(fan-in), the
taps 1/sqrt(K), the tied embedding's fan-in the hidden size, as the head
it also is; ``expert_bias`` zeros, norms ones), so that the same seed
names the same model on both sides. That the two recipes agree bit for bit
is a test (``tests/perfbench/test_reference_lfm2_moe.py``), not an import.
"""

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 128          # an expert's tokens are padded to a multiple of this
Q_BLOCK = 256       # queries of one block of the attention
ROUTE_EPS = 1e-6    # added to the chosen scores' sum (assumed)


def held_experts(model):
    """(router width, the ids of the experts held here)."""
    held = model["num_experts"]
    share = model.get("ep_share")
    if not share:
        return held, list(range(held))
    assert share["num_experts"] == held * share["chips"]
    first = held * share.get("rank", 0)
    return share["num_experts"], list(range(first, first + held))


def head_dim(model):
    return model.get("head_dim") or (model["hidden_size"]
                                     // model["num_attention_heads"])


def make_weights(model, seed, dtype=jnp.bfloat16, stage_layers=None):
    """Seeded weights for ``model`` (the published ``config.json`` keys).
    Returns {"layers": [per-layer dict with "op", "ffn", "op_norm",
    "ffn_norm", ...], "embed", "final_norm"}; matrices are [in, out]."""
    assert not stage_layers, "one stage"
    assert not model.get("conv_bias", False), "no bias in the convolution"
    kinds = list(model["layer_types"])
    assert len(kinds) == model["num_hidden_layers"], kinds
    n_dense = model.get("num_dense_layers", 0)
    hidden, vocab, taps = (model["hidden_size"], model["vocab_size"],
                           model["conv_L_cache"])
    d = head_dim(model)
    qd = model["num_attention_heads"] * d
    kd = model["num_key_value_heads"] * d
    inter, e_inter = model["intermediate_size"], \
        model["moe_intermediate_size"]
    wide, held = held_experts(model)
    n_conv = kinds.count("conv")
    n_attn = len(kinds) - n_conv
    n_moe = len(kinds) - n_dense
    key = jax.random.key(seed)
    keys = (jax.random.fold_in(key, i) for i in itertools.count())

    def normal(shape, scale):
        # the served recipe draws, scales and rounds in three steps; the
        # barrier keeps them apart (perfbench/reference/olmo_hybrid.py)
        return jax.jit(lambda k: (jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32))
            * scale).astype(dtype))(next(keys))

    s_in = hidden ** -0.5
    assert model.get("tie_word_embeddings",
                     model.get("tie_embedding", True)), "the tied head only"
    drawn = {
        "conv": {"in_proj": normal((n_conv, hidden, 3 * hidden), s_in),
                 "taps": normal((n_conv, taps, hidden), taps ** -0.5),
                 "out_proj": normal((n_conv, hidden, hidden), s_in)},
        "attn": {"q_proj": normal((n_attn, hidden, qd), s_in),
                 "k_proj": normal((n_attn, hidden, kd), s_in),
                 "v_proj": normal((n_attn, hidden, kd), s_in),
                 "o_proj": normal((n_attn, qd, hidden), qd ** -0.5)},
        "dense": {"w1": normal((n_dense, hidden, inter), s_in),
                  "w3": normal((n_dense, hidden, inter), s_in),
                  "w2": normal((n_dense, inter, hidden), inter ** -0.5)},
        "router": normal((n_moe, wide, hidden), s_in),   # [experts, hidden]
        "routed": {
            name: [normal(shape, scale) for _ in range(n_moe)]
            for name, shape, scale in (
                ("w1", (len(held), hidden, e_inter), s_in),
                ("w3", (len(held), hidden, e_inter), s_in),
                ("w2", (len(held), e_inter, hidden), e_inter ** -0.5))},
        "embed": normal((vocab, hidden), s_in)}
    conv, attn, dense, router, routed = (
        drawn[k] for k in ("conv", "attn", "dense", "router", "routed"))
    out = {"layers": [], "final_norm": jnp.ones((hidden,), dtype),
           "embed": drawn["embed"]}
    seen = {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
    for i, kind in enumerate(kinds):
        op = "conv" if kind == "conv" else "attn"
        ffn = "dense" if i < n_dense else "moe"
        layer = {"op": op, "ffn": ffn,
                 "op_norm": jnp.ones((hidden,), dtype),
                 "ffn_norm": jnp.ones((hidden,), dtype)}
        at = seen[op]
        layer.update({k: v[at] for k, v in
                      (conv if op == "conv" else attn).items()})
        if op == "attn":
            layer["q_norm"] = jnp.ones((d,), dtype)
            layer["k_norm"] = jnp.ones((d,), dtype)
        at = seen[ffn]
        if ffn == "dense":
            layer.update({k: v[at] for k, v in dense.items()})
        else:
            layer["router"] = router[at]
            layer["expert_bias"] = jnp.zeros((wide,), jnp.float32)
            layer.update({k: v[at] for k, v in routed.items()})
        seen[op] += 1
        seen[ffn] += 1
        out["layers"].append(layer)
    return out


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


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


def _stored(control):
    """A layer matrix as the forward pass reads it: float32, or (the
    control) after a round trip through the lower precision, for every
    matrix the served ``--quantization`` stores so (the router, the taps,
    the norms and the embedding stay as they are)."""
    if control is None:
        return lambda w: w.astype(jnp.float32)
    return lambda w: lower_precision(w, control)


def short_conv(model, u, layer, stored):
    """u [T, d] -> the gated short convolution's output [T, d]."""
    d, taps = u.shape[-1], model["conv_L_cache"]
    bcz = u @ stored(layer["in_proj"])
    b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    g = b * z
    w = layer["taps"].astype(jnp.float32)                     # [K, d]
    # tap j multiplies the input K-1-j places back; zeros before the first
    padded = jnp.pad(g, ((taps - 1, 0), (0, 0)))
    conv = sum(padded[j:j + g.shape[0]] * w[j] for j in range(taps))
    return (c * conv) @ stored(layer["out_proj"])


@functools.partial(jax.jit, static_argnames=("theta",))
def rope_halves(x, positions, theta):
    """x [T, heads, D]: (x_i, x_{i + D/2}) turned by the angle
    position * theta^(-2i / D): the halves rotated."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


@jax.jit
def _attend(q_b, q_pos, k, v):
    """One block of queries q_b [n, hq, d] at positions q_pos against the
    keys and values k, v [hkv, S, d] of positions 0 .. S - 1, under the
    causal mask built from the positions."""
    n_q, hq, d = q_b.shape
    hkv = k.shape[0]
    q_g = q_b.reshape(n_q, hkv, hq // hkv, d).transpose(1, 0, 2, 3)
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("kqgd,ksd->kqgs", q_g / math.sqrt(d), k)
        seen = jnp.arange(k.shape[1])[None, :] <= q_pos[:, None]
        scores = jnp.where(seen[None, :, None, :], scores, -jnp.inf)
        e = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        out = (jnp.einsum("kqgs,ksd->kqgd", e, v)
               / jnp.sum(e, axis=-1, keepdims=True))
    return out.transpose(1, 0, 2, 3).reshape(n_q, hq, d)


def attention(model, u, layer, stored, q_block=Q_BLOCK):
    """u [T, d] -> the attention operator's output [T, d]; a block of
    queries is scored against the keys up to its own last position."""
    t = u.shape[0]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = head_dim(model), model.get("norm_eps", 1e-5)
    theta = float((model.get("rope_parameters") or {}).get(
        "rope_theta", model.get("rope_theta", 1e6)))
    pos = jnp.arange(t)
    q = (u @ stored(layer["q_proj"])).reshape(t, hq, d)
    k = (u @ stored(layer["k_proj"])).reshape(t, hkv, d)
    v = (u @ stored(layer["v_proj"])).reshape(t, hkv, d)
    q = rope_halves(rms_norm(q, layer["q_norm"], eps), pos, theta)
    k = rope_halves(rms_norm(k, layer["k_norm"], eps), pos, theta)
    k, v = (a.transpose(1, 0, 2) for a in (k, v))            # [hkv, T, d]
    pad = (-t) % q_block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    q_pos = jnp.pad(pos, (0, pad), constant_values=t - 1)
    out = []
    for lo in range(0, t + pad, q_block):
        keys = min(t, lo + q_block)
        out.append(_attend(q[lo:lo + q_block], q_pos[lo:lo + q_block],
                           k[:, :keys], v[:, :keys]))
    return jnp.concatenate(out)[:t].reshape(t, hq * d) @ stored(
        layer["o_proj"])


def route(model, r, layer):
    """(ids [T, k] over all the published experts, weights [T, k]): the
    choice follows the bias-corrected scores, the weights the scores."""
    s = jax.nn.sigmoid(r @ layer["router"].astype(jnp.float32).T)
    choice = s + layer["expert_bias"] if model.get("use_expert_bias",
                                                   True) else s
    _, ids = jax.lax.top_k(choice, model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if model.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return ids, w * model.get("routed_scaling_factor", 1)


def swiglu(r, w1, w3, w2):
    return (jax.nn.silu(r @ w1) * (r @ w3)) @ w2


@jax.jit
def _add_expert(out, r, tok, weight, w1, w3, w2):
    """out + weight * expert(r[tok]) scattered back to the rows ``tok``."""
    with jax.default_matmul_precision("highest"):
        y = swiglu(r[tok], w1, w3, w2)
    return out.at[tok].add(y * weight[:, None])


def routed_part(model, r, layer, stored, experts=None):
    """What the experts ``experts`` (ids among all the published ones;
    this chip's share by default) give: sum_k w_k E_k(r) over the
    assignments to them. An expert takes its own tokens, padded to a
    multiple of ROWS with rows of weight 0. ``layer`` holds the matrices
    of the experts ``held_experts(model)`` names, in that order."""
    _, held = held_experts(model)
    experts = held if experts is None else experts
    ids, w = (np.asarray(a) for a in route(model, r, layer))
    out = jnp.zeros_like(r)
    for e in experts:
        tok, slot = np.nonzero(ids == e)
        if not len(tok):
            continue
        at = held.index(e)
        pad = (-len(tok)) % ROWS
        out = _add_expert(
            out, r, np.pad(tok, (0, pad)).astype(np.int32),
            np.pad(w[tok, slot], (0, pad)).astype(np.float32),
            *(stored(layer[k][at]) for k in ("w1", "w3", "w2")))
    return out


def layer_forward(model, x, layer, stored, rows=None):
    """One layer over the stream x [T, d]; with ``rows`` (ascending
    positions) the operator is computed whole and the feed-forward, and
    the result, for those positions only."""
    eps = model.get("norm_eps", 1e-5)
    u = rms_norm(x, layer["op_norm"], eps)
    op = short_conv if layer["op"] == "conv" else attention
    x = x + op(model, u, layer, stored)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    r = rms_norm(x, layer["ffn_norm"], eps)
    if layer["ffn"] == "dense":
        return x + swiglu(r, *(stored(layer[k]) for k in ("w1", "w3",
                                                          "w2")))
    return x + routed_part(model, r, layer, stored)


def hidden_states(model, weights, tokens, control=None, rows=None):
    """Final-norm hidden states [T, d] of one token sequence; with
    ``rows``, of those positions only (the last layer's feed-forward is
    computed for them alone; nothing else differs)."""
    stored = _stored(control)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for layer in weights["layers"][:-1]:
            x = layer_forward(model, x, layer, stored)
        x = layer_forward(model, x, weights["layers"][-1], stored, rows)
        return rms_norm(x, weights["final_norm"],
                        model.get("norm_eps", 1e-5))


def logits(model, weights, tokens, control=None):
    with jax.default_matmul_precision("highest"):
        return hidden_states(model, weights, tokens, control) @ weights[
            "embed"].T.astype(jnp.float32)


def logprobs(model, weights, tokens, want, control=None, block=256):
    """Log-probabilities the model gives, after reading ``tokens[:i+1]``, to
    each token id in ``want[i]`` (a list, possibly empty), for every i.
    Returns a list of lists shaped like ``want``. The vocabulary is
    normalised in blocks of positions so the logits never exist whole."""
    rows = [i for i, ids in enumerate(want) if ids]
    hid = hidden_states(model, weights, tokens, control, rows=rows)

    @jax.jit
    def block_lp(h, embed):     # the head is an argument, not a constant
        with jax.default_matmul_precision("highest"):
            return jax.nn.log_softmax(h @ embed.T.astype(jnp.float32),
                                      axis=-1)

    out = [[] for _ in want]
    for lo in range(0, len(rows), block):
        idx = list(range(lo, min(lo + block, len(rows))))
        pad = idx + [idx[-1]] * (block - len(idx))     # one compiled shape
        take = jax.device_get(block_lp(hid[jnp.asarray(pad)],
                                       weights["embed"]))
        for r, i in enumerate(idx):
            out[rows[i]] = [float(take[r, tok]) for tok in want[rows[i]]]
    return out
