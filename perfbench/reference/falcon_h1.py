"""Plain reference for tiiuae/Falcon-H1-34B-Instruct (``model_type:
falcon_h1``): a decoder whose every layer runs attention heads and Mamba-2
heads SIDE BY SIDE on one normed stream, with muP multipliers at fourteen
places.

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, no chunking, one sequence at a time; the recurrence is a
``lax.scan`` over tokens, attention a masked softmax over the whole
sequence (computed a block of queries at a time so that it fits). d =
``hidden_size``, eps = ``rms_norm_eps``, RMSNorm with a weight, no bias
anywhere but the convolution's. The catalog's row gives every multiplier's
VALUE; WHERE each applies is the published modelling code's (transformers
``FalconH1``):

    x_0 = m_e Embed[token]                        m_e embedding_multiplier
    layer i:
      u  = RMSNorm(x; w_in_i)
      x <- x + m_so SSM(m_si u) + m_ao Attn(m_ai u)
                    m_si, m_so ssm_in / ssm_out_multiplier,
                    m_ai, m_ao attention_in / attention_out_multiplier
      x <- x + MLP(RMSNorm(x; w_ff_i))
    logits = m_lm RMSNorm(x; w_f) W_head          m_lm lm_head_multiplier

    Attn(a): q = a W_q [Hq x D];  k = m_k (a W_k) [Hkv x D]  (m_k
             key_multiplier);  v = a W_v;  rotary embedding over all D dims
             of q and k, halves rotated (x1, x2 = the first, second D / 2),
             base ``rope_theta``;  causal softmax(q k^T / sqrt(D)) v, query
             head j reads KV head j // (Hq / Hkv);  then W_o
    SSM(s):  [z | xBC | dt] = (s W_in) * mu   widths d_ssm | d_ssm + 2 G N | H
             mu: one factor a channel, ``ssm_multipliers`` over z (d_ssm) |
             x (d_ssm) | B (G N) | C (G N) | dt (H)
             xBC <- silu(conv_causal_depthwise(xBC; ``mamba_d_conv`` taps,
             bias));  [x | B | C] widths d_ssm | G N | G N
             H = ``mamba_n_heads`` heads of P = ``mamba_d_head``, state N =
             ``mamba_d_state``, G = ``mamba_n_groups``, head h reads group
             h // (H / G);  d_ssm = ``mamba_d_ssm`` = H P
             dt_h = softplus(dt_h + dt_bias_h)  (not clamped)
             a_h = exp(-dt_h exp(A_log_h))
             S_h in R^{P x N} from zero, per token:
                 S_h <- a_h S_h + dt_h x_h B_g^T ;  y_h = S_h C_g + D_h x_h
             SSM = RMSNorm_over_each_of_G_groups_of_(d_ssm / G)(y * silu(z);
                   w) W_out        (``mamba_rms_norm`` true,
                   ``mamba_norm_before_gate`` false: the gate INSIDE the
                   norm; the grouping is Mamba-2's convention)
    MLP(r):  (silu(m_g (r W_gate)) * (r W_up)) W_down m_d
             [m_g, m_d] = ``mlp_multipliers``

``attn_layer_indices`` null: every layer has attention. ``mamba_use_mlp``
true: every layer has the MLP. ``mamba_expand``, ``mlp_expansion_factor``
and ``num_logits_to_keep`` are read by nothing.

Departures from the published model, each because the benchmark's
configuration says so: the depth (``num_hidden_layers`` in the model dict)
and the weights, which are random, rounded to the served dtype (bf16), with
arithmetic on them in float32.

Nothing here is taken from the program under test. ``make_weights`` draws
with ``jax.random`` in the order, shapes and scales of the served
``--load-format dummy`` recipe (the n-th draw from ``fold_in(key(seed),
n)``): W_q, W_k, W_v, W_o, W_in, the convolution's weight and bias, the
step's draw, ``A_log``, W_out, each stacked over the layers; then W_gate a
layer at a time, W_up, W_down; then the embedding in blocks of at most
``VOCAB_BLOCK`` rows, then the head in as many blocks of columns. A matrix
that a multiplier follows is drawn at 1/sqrt(fan-in) DIVIDED by that
multiplier (the embedding at 1 / m_e, W_in a column at a time by m_si mu),
so that with the published multipliers in the forward pass every sublayer
is as loud as without them: against a plain 1/sqrt(fan-in) draw the
multipliers (0.0375, 0.088, 0.011 on the sublayers' outputs, 0.011 on k)
would leave ``Head(Embed)`` and nothing of the layers to compare. ``A_log``
= log U[1, 16], ``dt_bias`` the inverse softplus of a log-uniform step in
[0.001, 0.1] floored at 1e-4, ``D`` = 1: the Mamba-2 initialiser, under
which the state carries over hundreds of tokens. That the two recipes agree
bit for bit is a test (``tests/perfbench/test_reference_falcon_h1.py``),
not an import.
"""

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 32768     # rows of the vocabulary drawn at a time
Q_BLOCK = 512           # queries attended at a time
STEP_MIN, STEP_MAX, STEP_FLOOR = 0.001, 0.1, 1e-4

# the served --quantization's leaves: what the control stores lower
QUANTIZED = ("q_proj", "k_proj", "v_proj", "o_proj", "in_proj", "out_proj",
             "gate_proj", "up_proj", "down_proj")


def _dims(model):
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    g, n = model["mamba_n_groups"], model["mamba_d_state"]
    assert model.get("mamba_d_ssm", h * p) == h * p
    return dict(hidden=model["hidden_size"], h=h, p=p, g=g, n=n,
                d_ssm=h * p, conv_dim=h * p + 2 * g * n,
                taps=model["mamba_d_conv"], hq=model["num_attention_heads"],
                hkv=model["num_key_value_heads"], d=model["head_dim"],
                inter=model["intermediate_size"], eps=model["rms_norm_eps"])


def mu_vector(model):
    """``ssm_multipliers`` spread over the in-projection's columns z | x |
    B | C | dt (float64)."""
    m = _dims(model)
    gn = m["g"] * m["n"]
    return np.repeat(np.asarray(model["ssm_multipliers"], np.float64),
                     (m["d_ssm"], m["d_ssm"], gn, gn, m["h"]))


def make_weights(model, seed, dtype=jnp.bfloat16, stage_layers=None):
    """Seeded weights for ``model`` (the published ``config.json`` keys).
    Returns {"layers": [a dict a layer], "embed", "final_norm",
    "lm_head"}; matrices are [in, out]."""
    assert not stage_layers, "one stage: any mesh is refused"
    m = _dims(model)
    layers, hidden, vocab = model["num_hidden_layers"], m["hidden"], \
        model["vocab_size"]
    key = jax.random.key(seed)
    keys = (jax.random.fold_in(key, i) for i in itertools.count())

    def normal(shape, scale):
        # the served recipe draws, scales and rounds in three steps; the
        # barrier keeps them apart (perfbench/reference/olmo_hybrid.py)
        return jax.jit(lambda k: (jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32))
            * scale).astype(dtype))(next(keys))

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def pieces(shape, axis, piece, scale):
        parts = []
        for lo in range(0, shape[axis], piece):
            part = list(shape)
            part[axis] = min(piece, shape[axis] - lo)
            parts.append(normal(tuple(part), scale))
        return parts

    m_ai, m_ao = (model["attention_in_multiplier"],
                  model["attention_out_multiplier"])
    m_g, m_d = model["mlp_multipliers"]
    s, taps = hidden ** -0.5, m["taps"]
    qd, kd = m["hq"] * m["d"], m["hkv"] * m["d"]
    width = m["d_ssm"] + m["conv_dim"] + m["h"]
    stacked = {
        "q_proj": normal((layers, hidden, qd), s / m_ai),
        "k_proj": normal((layers, hidden, kd),
                         s / (m_ai * model["key_multiplier"])),
        "v_proj": normal((layers, hidden, kd), s / m_ai),
        "o_proj": normal((layers, qd, hidden), qd ** -0.5 / m_ao),
        "in_proj": normal(
            (layers, hidden, width),
            (s / (mu_vector(model) * model["ssm_in_multiplier"])
             ).astype(np.float32)),
        "conv_w": normal((layers, m["conv_dim"], taps), taps ** -0.5),
        "conv_b": uniform((layers, m["conv_dim"]), -taps ** -0.5,
                          taps ** -0.5),
    }
    dt = jnp.maximum(jnp.exp(uniform(
        (layers, m["h"]), jnp.log(STEP_MIN), jnp.log(STEP_MAX))), STEP_FLOOR)
    stacked["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    stacked["A_log"] = jnp.log(uniform((layers, m["h"]), 1.0, 16.0))
    stacked["out_proj"] = normal(
        (layers, m["d_ssm"], hidden),
        m["d_ssm"] ** -0.5 / model["ssm_out_multiplier"])
    mlp = {name: pieces(shape, 0, 1, scale) for name, shape, scale in (
        ("gate_proj", (layers, hidden, m["inter"]), s / m_g),
        ("up_proj", (layers, hidden, m["inter"]), s),
        ("down_proj", (layers, m["inter"], hidden),
         m["inter"] ** -0.5 / m_d))}
    rows = -(-vocab // -(-vocab // VOCAB_BLOCK))
    out = {"final_norm": jnp.ones((hidden,), dtype)}
    out["embed"] = jnp.concatenate(pieces(
        (vocab, hidden), 0, rows, 1.0 / model["embedding_multiplier"]))
    out["lm_head"] = jnp.concatenate(pieces(
        (hidden, vocab), 1, rows, s / model["lm_head_multiplier"]), axis=1)
    out["layers"] = []
    for i in range(layers):
        layer = {k: v[i] for k, v in stacked.items()}
        layer.update({k: v[i][0] for k, v in mlp.items()})
        layer.update(input_norm=jnp.ones((hidden,), dtype),
                     pre_ff_norm=jnp.ones((hidden,), dtype),
                     gate_norm=jnp.ones((m["d_ssm"],), dtype),
                     D=jnp.ones((m["h"],), jnp.float32))
        out["layers"].append(layer)
    return out


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


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


def causal_conv(x, w, b):
    """Causal depthwise convolution over time. x [T, C], w [C, taps], b
    [C]: out[t] = b + sum_j w[:, j] x[t - (taps - 1) + j], zeros before
    the start."""
    t, taps = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return b + sum(padded[j:j + t] * w[:, j].astype(jnp.float32)
                   for j in range(taps))


def ssm_scan(x, dt, a, B, C):
    """The recurrence, token by token, from a zero state. x [T, H, P], dt,
    a [T, H], B, C [T, H, N] (a group's row repeated over its heads).
    Returns y [T, H, P] without the skip."""
    def step(state, xs):
        x_t, dt_t, a_t, b_t, c_t = xs
        state = (state * a_t[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    state0 = jnp.zeros(x.shape[1:] + B.shape[-1:], jnp.float32)
    return jax.lax.scan(step, state0, (x, dt, a, B, C))[1]


def ssm(model, s, layer, mm):
    """s [T, hidden] -> the state-space branch's output [T, hidden]."""
    m = _dims(model)
    t, h, p, g, n = s.shape[0], m["h"], m["p"], m["g"], m["n"]
    d_ssm = m["d_ssm"]
    zxbcdt = mm(s, layer["in_proj"]) * jnp.asarray(mu_vector(model),
                                                   jnp.float32)
    z = zxbcdt[:, :d_ssm]
    xbc = jax.nn.silu(causal_conv(
        zxbcdt[:, d_ssm:d_ssm + m["conv_dim"]], layer["conv_w"],
        layer["conv_b"]))
    dt = jax.nn.softplus(zxbcdt[:, -h:] + layer["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(layer["A_log"]))
    x = xbc[:, :d_ssm].reshape(t, h, p)
    B = jnp.repeat(xbc[:, d_ssm:d_ssm + g * n].reshape(t, g, n), h // g,
                   axis=1)
    C = jnp.repeat(xbc[:, d_ssm + g * n:].reshape(t, g, n), h // g, axis=1)
    y = ssm_scan(x, dt, a, B, C)
    y = (y + layer["D"][None, :, None] * x).reshape(t, d_ssm)
    gated = (y * jax.nn.silu(z)).reshape(t, g, d_ssm // g)
    var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    normed = (gated * jax.lax.rsqrt(var + m["eps"])).reshape(t, d_ssm)
    return mm(normed * layer["gate_norm"].astype(jnp.float32),
              layer["out_proj"])


def rotary(x, theta):
    """x [H, T, D] at positions 0 .. T-1: halves rotated, all D dims."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(model, a, layer, mm):
    """a [T, hidden] -> the attention branch's output [T, hidden]."""
    m = _dims(model)
    t, hq, hkv, d = a.shape[0], m["hq"], m["hkv"], m["d"]
    theta = float(model["rope_theta"])
    q = mm(a, layer["q_proj"]).reshape(t, hq, d).transpose(1, 0, 2)
    k = (mm(a, layer["k_proj"]) * model["key_multiplier"]
         ).reshape(t, hkv, d).transpose(1, 0, 2)
    v = mm(a, layer["v_proj"]).reshape(t, hkv, d).transpose(1, 0, 2)
    q, k = rotary(q, theta), rotary(k, theta)
    k, v = (jnp.repeat(x, hq // hkv, axis=0) for x in (k, v))
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)       # keys past the block's last query
        scores = jnp.einsum("hqd,hsd->hqs", q[:, lo:hi], k[:, :hi]) \
            / math.sqrt(d)
        causal = (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :])
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqs,hsd->hqd", probs, v[:, :hi]))
    out = jnp.concatenate(outs, axis=1)
    return mm(out.transpose(1, 0, 2).reshape(t, hq * d), layer["o_proj"])


def mlp(model, r, layer, mm):
    m_g, m_d = model["mlp_multipliers"]
    return mm(jax.nn.silu(mm(r, layer["gate_proj"]) * m_g)
              * mm(r, layer["up_proj"]), layer["down_proj"]) * m_d


def block(model, x, layer, mm):
    """One layer: x [T, hidden] -> x."""
    eps = _dims(model)["eps"]
    u = rms_norm(x, layer["input_norm"], eps)
    x = (x + model["ssm_out_multiplier"]
         * ssm(model, u * model["ssm_in_multiplier"], layer, mm)
         + model["attention_out_multiplier"]
         * attention(model, u * model["attention_in_multiplier"], layer, mm))
    return x + mlp(model, rms_norm(x, layer["pre_ff_norm"], eps), layer, mm)


def _matmul(control):
    """``a @ w`` with w in float32, or (the control) as it reads after a
    round trip through the lower precision: every matrix the served
    ``--quantization`` stores so (the convolution, the embedding and the
    head stay as they are)."""
    if control is None:
        return _mm
    return lambda a, w: a @ lower_precision(w, control)


def hidden_states(model, weights, tokens, control=None):
    """Final-norm hidden states [T, hidden] of one token sequence."""
    tokens = jnp.asarray(tokens, jnp.int32)
    mm = _matmul(control)
    step = jax.jit(lambda x, layer: block(model, x, layer, mm))
    with jax.default_matmul_precision("highest"):
        x = (weights["embed"][tokens].astype(jnp.float32)
             * model["embedding_multiplier"])
        for layer in weights["layers"]:
            x = step(x, layer)
        return rms_norm(x, weights["final_norm"], _dims(model)["eps"])


def logits(model, weights, tokens, control=None):
    with jax.default_matmul_precision("highest"):
        return _mm(hidden_states(model, weights, tokens, control),
                   weights["lm_head"]) * model["lm_head_multiplier"]


def logprobs(model, weights, tokens, want, control=None, block=256):
    """Log-probabilities the model gives, after reading ``tokens[:i+1]``, to
    each token id in ``want[i]`` (a list, possibly empty), for every i.
    Returns a list of lists shaped like ``want``. The vocabulary is
    normalised in blocks of positions so the logits never exist whole."""
    hid = hidden_states(model, weights, tokens, control)
    head, m_lm = weights["lm_head"], model["lm_head_multiplier"]

    @jax.jit
    def block_lp(h, head):      # the head is an argument, not a constant
        with jax.default_matmul_precision("highest"):
            return jax.nn.log_softmax(_mm(h, head) * m_lm, axis=-1)

    out = [[] for _ in want]
    rows = [i for i, ids in enumerate(want) if ids]
    for lo in range(0, len(rows), block):
        idx = rows[lo: lo + block]
        pad = idx + [idx[-1]] * (block - len(idx))     # one compiled shape
        take = jax.device_get(block_lp(hid[jnp.asarray(pad)], head))
        for r, i in enumerate(idx):
            out[i] = [float(take[r, tok]) for tok in want[i]]
    return out
