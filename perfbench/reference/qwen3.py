"""Plain reference for the dense Qwen3 family (``Qwen3ForCausalLM``).

The forward pass as the model card and ``config.json`` describe it, in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, one sequence at a time. Per layer:

    h  = RMSNorm(x) ; q, k, v = h Wq, h Wk, h Wv        (no biases)
    q, k = RMSNorm over each head's ``head_dim`` (the per-head q/k norm)
    q, k = RoPE(q, k)      rotate-half ("NeoX") form, theta from the config
    a  = causal softmax(q k^T / sqrt(head_dim)) v       grouped-query: each
         of the ``num_key_value_heads`` serves Hq / Hkv query heads
    x  = x + a Wo
    x  = x + (silu(g Wg) * (g Wu)) Wd   with g = RMSNorm(x)     (SwiGLU)

then a final RMSNorm and the head: the embedding transposed where
``tie_word_embeddings`` is set, a matrix of its own otherwise.

Nothing here is taken from the program under test. The weights are made
here, from the seed: ``make_weights`` draws them with ``jax.random`` in the
order and scales of the served ``--load-format dummy`` recipe (normal,
1/sqrt(fan-in); unit-variance embedding; unit norm weights), stage by stage
where the deployment is pipelined, so that the same seed names the same
model on both sides. That the two recipes agree bit for bit is a test
(``tests/perfbench/test_reference.py``), not an import. Departure from the
published model: weights are random, rounded to the served dtype (bf16);
arithmetic on them is float32.
"""

import math

import jax
import jax.numpy as jnp


def make_weights(model, seed, dtype=jnp.bfloat16, stage_layers=None):
    """Seeded weights for ``model`` (the published ``config.json`` keys).

    ``stage_layers`` is the pipelined deployment's [[first, last), ...]; each
    stage draws from the same seed on its own (its layers, the embedding on
    the first, the head on the last), as independent stage processes would.
    Returns {"layers": [per-layer dict, ...], "embed", "final_norm",
    "lm_head" or None}; matrices are [in, out].
    """
    n_layers = model["num_hidden_layers"]
    hidden, inter = model["hidden_size"], model["intermediate_size"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or hidden // hq
    tied = bool(model.get("tie_word_embeddings", False))
    vocab = model["vocab_size"]
    stage_layers = stage_layers or [[0, n_layers]]

    def normal(key, shape, scale):
        # jitted, so that the float32 draw is fused into the rounding and
        # never exists whole (a stacked MLP matrix is 3.6 GB of float32)
        return jax.jit(lambda k: (jax.random.normal(k, shape, jnp.float32)
                                  * scale).astype(dtype))(key)

    def stack(spec):
        # one leaf at a time: each draw holds several times its size in
        # temporaries, and the host has 40 GiB
        return {name: normal(*args) for name, args in spec.items()}

    out = {"layers": [], "embed": None, "lm_head": None,
           "final_norm": jnp.ones((hidden,), dtype)}
    for first, last in stage_layers:
        n = last - first
        keys = iter(jax.random.split(jax.random.key(seed), 16))
        s_in = hidden ** -0.5
        stacked = stack({
            "q_proj": (next(keys), (n, hidden, hq * d), s_in),
            "k_proj": (next(keys), (n, hidden, hkv * d), s_in),
            "v_proj": (next(keys), (n, hidden, hkv * d), s_in),
            "o_proj": (next(keys), (n, hq * d, hidden), (hq * d) ** -0.5),
            "gate_proj": (next(keys), (n, hidden, inter), s_in),
            "up_proj": (next(keys), (n, hidden, inter), s_in),
            "down_proj": (next(keys), (n, inter, hidden), inter ** -0.5),
        })
        for i in range(n):
            layer = {k: v[i] for k, v in stacked.items()}
            layer["input_norm"] = jnp.ones((hidden,), dtype)
            layer["post_attn_norm"] = jnp.ones((hidden,), dtype)
            layer["q_norm"] = jnp.ones((d,), dtype)
            layer["k_norm"] = jnp.ones((d,), dtype)
            out["layers"].append(layer)
        if first == 0:
            out["embed"] = normal(next(keys), (vocab, hidden), 1.0)
        if last == n_layers and not tied:
            out["lm_head"] = normal(next(keys), (hidden, vocab), s_in)
    assert len(out["layers"]) == n_layers, "stage_layers do not tile the model"
    return out


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, positions, theta):
    """x [T, heads, D]; rotate-half form over the whole head."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def _layer(model, x, layer, positions, control=None):
    """One decoder layer on x [T, H] (float32). ``control`` names the lower
    precision the seven layer matrices are stored in (the control only)."""
    mm = _mm if control is None else (
        lambda a, w: a @ lower_precision(w, control))
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // hq
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    t = x.shape[0]
    h = rms_norm(x, layer["input_norm"], eps)
    q = mm(h, layer["q_proj"]).reshape(t, hq, d)
    k = mm(h, layer["k_proj"]).reshape(t, hkv, d)
    v = mm(h, layer["v_proj"]).reshape(t, hkv, d)
    q = rope(rms_norm(q, layer["q_norm"], eps), positions, theta)
    k = rope(rms_norm(k, layer["k_norm"], eps), positions, theta)
    # heads first, the queries of a key-value group side by side: the
    # scores are then one batched matrix product per key-value head
    group = hq // hkv
    qh = jnp.transpose(q.reshape(t, hkv, group, d), (1, 2, 0, 3))
    kh, vh = jnp.transpose(k, (1, 0, 2)), jnp.transpose(v, (1, 0, 2))
    scores = jnp.einsum("hqd,hsd->hqs", qh.reshape(hkv, group * t, d), kh)
    scores = scores.reshape(hkv, group, t, t) / math.sqrt(d)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).reshape(hkv, group * t, t)
    attn = jnp.einsum("hqs,hsd->hqd", probs, vh).reshape(hkv, group, t, d)
    attn = jnp.transpose(attn, (2, 0, 1, 3)).reshape(t, hq * d)
    x = x + mm(attn, layer["o_proj"])
    g = rms_norm(x, layer["post_attn_norm"], eps)
    mlp = jax.nn.silu(mm(g, layer["gate_proj"])) * mm(g, layer["up_proj"])
    return x + mm(mlp, layer["down_proj"])


def hidden_states(model, weights, tokens, control=None):
    """Final-norm hidden states [T, H] of one token sequence."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    layer_fn = jax.jit(lambda x, layer: _layer(model, x, layer, positions,
                                               control))
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for layer in weights["layers"]:
            x = layer_fn(x, layer)
        return rms_norm(x, weights["final_norm"], model["rms_norm_eps"])


def logprobs(model, weights, tokens, want, control=None, block=256):
    """Log-probabilities the model gives, after reading ``tokens[:i+1]``, to
    each token id in ``want[i]`` (a list, possibly empty), for every i.
    Returns a list of lists shaped like ``want``. The vocabulary is
    normalised in blocks of positions so the logits never exist whole."""
    hid = hidden_states(model, weights, tokens, control)
    head = (weights["embed"].T if weights["lm_head"] is None
            else weights["lm_head"])

    @jax.jit
    def block_lp(h, head):      # the head is an argument, not a constant
        with jax.default_matmul_precision("highest"):
            return jax.nn.log_softmax(_mm(h, head), axis=-1)

    out = [[] for _ in want]
    rows = [i for i, ids in enumerate(want) if ids]
    for lo in range(0, len(rows), block):
        idx = rows[lo: lo + block]
        pad = idx + [idx[-1]] * (block - len(idx))     # one compiled shape
        lp = block_lp(hid[jnp.asarray(pad)], head)
        take = jax.device_get(lp)
        for r, i in enumerate(idx):
            out[i] = [float(take[r, tok]) for tok in want[i]]
    return out
