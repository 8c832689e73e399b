"""Plain reference for NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type:
nemotron_h``): a decoder whose block is ONE mixer, by the letters of
``hybrid_override_pattern`` (M Mamba-2, E expert layer, * attention).

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, no chunking, one sequence at a time; the recurrence is a
``lax.scan`` over tokens. u = RMSNorm(x; w_i, eps); no bias anywhere but
the convolution's.

    x_0 = Embed[token];  for block i:  x <- x + Mixer_i(RMSNorm(x))
    logits = RMSNorm(x; w_f) W_head                         (untied head)

M  Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``, state
   N = ``ssm_state_size``, G = ``n_groups`` groups; head h reads group
   h // (H / G); d_inner = H P, whatever ``expand`` says):
    [z | xBC | dt] = u W_in            widths d_inner | d_inner + 2 G N | H
    xBC <- silu(conv_causal_depthwise(xBC; ``conv_kernel`` taps, bias))
    [x | B | C] = xBC                  widths d_inner | G N | G N
    dt_h <- softplus(dt_h + dt_bias_h)        (not clamped: time_step_min /
                                     max / floor are the initialiser's)
    a_h = exp(-dt_h exp(A_log_h))
    S_h in R^{P x N} from zero, per token:
        S_h <- a_h S_h + dt_h x_h B_g^T ;   y_h = S_h C_g + D_h x_h
    Mixer(u) = RMSNorm_over_each_group_of_(d_inner / G)(y * silu(z); w) W_out

*  attention (``num_attention_heads`` query / ``num_key_value_heads`` KV
   heads of ``head_dim``; no positional embedding: ``rope_theta`` and
   ``partial_rotary_factor`` are inert; no q/k norm, no gate, no bias):
    Mixer(u) = causal softmax(q k^T / sqrt(head_dim)) v W_o

E  expert layer (DeepSeek-V3's noaux_tc; ``n_group`` 1 / ``topk_group`` 1
   inert):
    s = sigmoid(float32(u) W_r);  ids = top-k(s + e_score_correction_bias)
    w = ``routed_scaling_factor`` * s[ids] / (sum s[ids] + 1e-20)
    expert_e(u) = relu(u W_up,e)^2 W_down,e      (two matrices, no gate)
    Mixer(u) = sum_{k: ids_k held here} w_k expert_{ids_k}(u)
               + relu(u W_up,s)^2 W_down,s

Departures from the published model, each because the benchmark's
configuration says so: ``n_routed_experts`` in the model dict counts the
experts HELD (``ep_share`` gives the published count, the chips that share
a layer and this chip's rank): the router is as wide as published and
normalises over all the experts it chose, the absent experts' part is
left out, and that partial result goes on to the next block, as on a chip
that runs without its exchange. The vocabulary is the slice the
configuration gives. Weights are random, rounded to the served dtype
(bf16); arithmetic on them is float32.

Nothing here is taken from the program under test. ``make_weights`` draws
with ``jax.random`` in the order, shapes and scales of the served
``--load-format dummy`` recipe (the n-th draw from ``fold_in(key(seed),
n)``: the Mamba-2 leaves stacked over the M blocks, the attention leaves
over the * blocks, the router, its bias and the shared expert over the E
blocks, then the routed experts' up matrices a block at a time, then their
down matrices, the embedding, the head; matrices normal, 1/sqrt(fan-in);
``A_log`` = log U[1, 16], ``dt_bias`` the inverse softplus of a log-uniform
draw in [time_step_min, time_step_max] floored at time_step_floor, ``D``
= 1: the published initialiser, under which the state carries over
hundreds of tokens), so that the same seed names the same model on both
sides. That the two recipes agree bit for bit is a test
(``tests/perfbench/test_reference_nemotron_h.py``), not an import.
"""

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
ROWS = 128          # an expert's tokens are padded to a multiple of this


def experts_of(model):
    """(router width, experts held here, first held expert)."""
    held = model["n_routed_experts"]
    share = model.get("ep_share")
    if not share:
        return held, held, 0
    assert share["n_routed_experts"] == held * share["chips"]
    return share["n_routed_experts"], held, held * share.get("rank", 0)


def _dims(model):
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    return dict(hidden=model["hidden_size"], h=h, p=p, g=g, n=n,
                d_inner=h * p, conv_dim=h * p + 2 * g * n,
                taps=model["conv_kernel"], hq=model["num_attention_heads"],
                hkv=model["num_key_value_heads"], d=model["head_dim"],
                eps=model.get("layer_norm_epsilon",
                              model.get("norm_eps", 1e-5)))


def make_weights(model, seed, dtype=jnp.bfloat16, stage_layers=None):
    """Seeded weights for ``model`` (the published ``config.json`` keys).
    Returns {"layers": [per-block dict with "kind", "norm", ...], "embed",
    "final_norm", "lm_head"}; matrices are [in, out]."""
    assert not stage_layers, "one stage: the pattern has no period to cut"
    m = _dims(model)
    pattern = model["hybrid_override_pattern"]
    assert len(pattern) == model["num_hidden_layers"], pattern
    kinds = [KINDS[c] for c in pattern]
    lm, la, le = (kinds.count(k) for k in ("mamba", "attention", "moe"))
    hidden, vocab = m["hidden"], model["vocab_size"]
    wide, held, _ = experts_of(model)
    inter = model["moe_intermediate_size"]
    shared = model["moe_shared_expert_intermediate_size"]
    key = jax.random.key(seed)
    keys = (jax.random.fold_in(key, i) for i in itertools.count())

    def normal(shape, scale, dt=dtype):
        # the served recipe draws, scales and rounds in three steps; the
        # barrier keeps them apart (perfbench/reference/olmo_hybrid.py)
        return jax.jit(lambda k: (jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32))
            * scale).astype(dt))(next(keys))

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    s_in, taps = hidden ** -0.5, m["taps"]
    mamba = {
        "in_proj": normal((lm, hidden, m["d_inner"] + m["conv_dim"]
                           + m["h"]), s_in),
        "conv_w": normal((lm, m["conv_dim"], taps), taps ** -0.5),
        "conv_b": uniform((lm, m["conv_dim"]), -taps ** -0.5, taps ** -0.5),
    }
    dt = jnp.maximum(jnp.exp(uniform(
        (lm, m["h"]), jnp.log(model["time_step_min"]),
        jnp.log(model["time_step_max"]))), model["time_step_floor"])
    mamba["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    mamba["A_log"] = jnp.log(uniform((lm, m["h"]), 1.0, 16.0))
    mamba["out_proj"] = normal((lm, m["d_inner"], hidden),
                               m["d_inner"] ** -0.5)
    qd, kd = m["hq"] * m["d"], m["hkv"] * m["d"]
    attn = {
        "q_proj": normal((la, hidden, qd), s_in),
        "k_proj": normal((la, hidden, kd), s_in),
        "v_proj": normal((la, hidden, kd), s_in),
        "o_proj": normal((la, qd, hidden), qd ** -0.5),
    }
    moe = {
        "router": normal((le, hidden, wide), s_in),
        # zeros, as the served recipe: a balanced router (the published
        # bias exists to balance the load; a drawn one skews it)
        "e_bias": jnp.zeros((le, wide), jnp.float32),
        "shared_up": normal((le, hidden, shared), s_in),
        "shared_down": normal((le, shared, hidden), shared ** -0.5),
    }
    ups = [normal((held, hidden, inter), s_in) for _ in range(le)]
    downs = [normal((held, inter, hidden), inter ** -0.5)
             for _ in range(le)]
    out = {"layers": [], "final_norm": jnp.ones((hidden,), dtype)}
    out["embed"] = normal((vocab, hidden), 1.0)
    out["lm_head"] = normal((hidden, vocab), s_in)
    at = {"mamba": 0, "attention": 0, "moe": 0}
    for kind in kinds:
        i = at[kind]
        at[kind] += 1
        layer = {"kind": kind, "norm": jnp.ones((hidden,), dtype)}
        if kind == "mamba":
            layer.update({k: v[i] for k, v in mamba.items()})
            layer["D"] = jnp.ones((m["h"],), jnp.float32)
            layer["gate_norm"] = jnp.ones((m["d_inner"],), dtype)
        elif kind == "attention":
            layer.update({k: v[i] for k, v in attn.items()})
        else:
            layer.update({k: v[i] for k, v in moe.items()})
            layer["w_up"], layer["w_down"] = ups[i], downs[i]
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


def ssm_scan(x, dt, a, B, C, state0=None):
    """The recurrence, token by token. x [T, H, P], dt, a [T, H], B, C [T,
    H, N] (a group's row repeated over its heads). Returns (y [T, H, P]
    without the skip, the last state [H, P, N])."""
    def step(state, xs):
        x_t, dt_t, a_t, b_t, c_t = xs
        state = (state * a_t[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    if state0 is None:
        state0 = jnp.zeros(x.shape[1:] + B.shape[-1:], jnp.float32)
    state, y = jax.lax.scan(step, state0, (x, dt, a, B, C))
    return y, state


def mamba2(model, u, layer, mm):
    """u [T, hidden] -> the Mamba-2 mixer's output [T, hidden]."""
    m = _dims(model)
    t, h, p, g, n = u.shape[0], m["h"], m["p"], m["g"], m["n"]
    d_inner = m["d_inner"]
    zxbcdt = mm(u, layer["in_proj"])
    z = zxbcdt[:, :d_inner]
    xbc = jax.nn.silu(causal_conv(
        zxbcdt[:, d_inner:d_inner + m["conv_dim"]], layer["conv_w"],
        layer["conv_b"]))
    dt = jax.nn.softplus(zxbcdt[:, -h:] + layer["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(layer["A_log"]))
    x = xbc[:, :d_inner].reshape(t, h, p)
    B = jnp.repeat(xbc[:, d_inner:d_inner + g * n].reshape(t, g, n),
                   h // g, axis=1)
    C = jnp.repeat(xbc[:, d_inner + g * n:].reshape(t, g, n), h // g, axis=1)
    y, _ = ssm_scan(x, dt, a, B, C)
    y = (y + layer["D"][None, :, None] * x).reshape(t, d_inner)
    gated = (y * jax.nn.silu(z)).reshape(t, g, d_inner // g)
    var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    normed = (gated * jax.lax.rsqrt(var + m["eps"])).reshape(t, d_inner)
    return mm(normed * layer["gate_norm"].astype(jnp.float32),
              layer["out_proj"])


def attention(model, u, layer, mm):
    """u [T, hidden] -> the attention mixer's output [T, hidden]."""
    m = _dims(model)
    t, hq, hkv, d = u.shape[0], m["hq"], m["hkv"], m["d"]
    q = mm(u, layer["q_proj"]).reshape(t, hq, d).transpose(1, 0, 2)
    k = mm(u, layer["k_proj"]).reshape(t, hkv, d).transpose(1, 0, 2)
    v = mm(u, layer["v_proj"]).reshape(t, hkv, d).transpose(1, 0, 2)
    k, v = (jnp.repeat(a, hq // hkv, axis=0) for a in (k, v))
    scores = jnp.einsum("hqd,hsd->hqs", q, k) / math.sqrt(d)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hqs,hsd->hqd", probs, v)
    return mm(out.transpose(1, 0, 2).reshape(t, hq * d), layer["o_proj"])


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(model, u, layer):
    """(ids [T, k] over all the published experts, weights [T, k])."""
    s = jax.nn.sigmoid(u @ layer["router"].astype(jnp.float32))
    _, ids = jax.lax.top_k(s + layer["e_bias"], model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if model.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * model["routed_scaling_factor"]


@jax.jit
def _add_expert(out, u, tok, weight, w_up, w_down):
    """out + weight * expert(u[tok]) scattered back to the rows ``tok``."""
    with jax.default_matmul_precision("highest"):
        y = relu2(u[tok] @ w_up) @ w_down
    return out.at[tok].add(y * weight[:, None])


def routed_part(model, u, layer, mm, first=None, held=None):
    """What the experts ``first .. first + held`` (this chip's share by
    default) give: sum_k w_k expert_{ids_k}(u) over the assignments to
    them. An expert takes its own tokens, padded to a multiple of ROWS
    with rows of weight 0 (one compiled shape a multiple)."""
    _, h0, f0 = experts_of(model)
    first = f0 if first is None else first
    held = h0 if held is None else held
    ids, w = (np.asarray(a) for a in route(model, u, layer))
    out = jnp.zeros_like(u)
    for e in range(first, first + held):
        tok, slot = np.nonzero(ids == e)
        if not len(tok):
            continue
        pad = (-len(tok)) % ROWS
        out = _add_expert(
            out, u, np.pad(tok, (0, pad)).astype(np.int32),
            np.pad(w[tok, slot], (0, pad)).astype(np.float32),
            _stored(layer["w_up"][e - f0], mm),
            _stored(layer["w_down"][e - f0], mm))
    return out


def _stored(w, mm):
    """``w`` as ``mm`` reads it (float32, or through the control's
    lower precision)."""
    return getattr(mm, "stored", lambda x: x.astype(jnp.float32))(w)


def shared_part(u, layer, mm):
    return mm(relu2(mm(u, layer["shared_up"])), layer["shared_down"])


def expert_layer(model, u, layer, mm):
    return routed_part(model, u, layer, mm) + shared_part(u, layer, mm)


MIXERS = {"mamba": mamba2, "attention": attention}


def _matmul(control):
    """``a @ w`` with w in float32, or (the control) as it reads after a
    round trip through the lower precision: every matrix the served
    ``--quantization`` stores so (the router, the convolution, the
    embedding and the head stay as they are)."""
    if control is None:
        return _mm

    def mm(a, w):
        return a @ lower_precision(w, control)
    mm.stored = lambda w: lower_precision(w, control)
    return mm


def hidden_states(model, weights, tokens, control=None):
    """Final-norm hidden states [T, hidden] of one token sequence."""
    eps = _dims(model)["eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    mm = _matmul(control)
    fns = {kind: jax.jit(lambda u, layer, fn=fn: fn(model, u, layer, mm))
           for kind, fn in MIXERS.items()}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for layer in weights["layers"]:
            layer = dict(layer)
            kind = layer.pop("kind")
            u = rms_norm(x, layer.pop("norm"), eps)
            x = x + (expert_layer(model, u, layer, mm) if kind == "moe"
                     else fns[kind](u, layer))
        return rms_norm(x, weights["final_norm"], eps)


def logits(model, weights, tokens, control=None):
    with jax.default_matmul_precision("highest"):
        return _mm(hidden_states(model, weights, tokens, control),
                   weights["lm_head"])


def logprobs(model, weights, tokens, want, control=None, block=256):
    """Log-probabilities the model gives, after reading ``tokens[:i+1]``, to
    each token id in ``want[i]`` (a list, possibly empty), for every i.
    Returns a list of lists shaped like ``want``. The vocabulary is
    normalised in blocks of positions so the logits never exist whole."""
    hid = hidden_states(model, weights, tokens, control)
    head = weights["lm_head"]

    @jax.jit
    def block_lp(h, head):      # the head is an argument, not a constant
        with jax.default_matmul_precision("highest"):
            return jax.nn.log_softmax(_mm(h, head), axis=-1)

    out = [[] for _ in want]
    rows = [i for i, ids in enumerate(want) if ids]
    for lo in range(0, len(rows), block):
        idx = rows[lo: lo + block]
        pad = idx + [idx[-1]] * (block - len(idx))     # one compiled shape
        take = jax.device_get(block_lp(hid[jnp.asarray(pad)], head))
        for r, i in enumerate(idx):
            out[i] = [float(take[r, tok]) for tok in want[i]]
    return out
