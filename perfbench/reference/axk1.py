"""Plain reference for skt/A.X-K1 (``model_type: axk1``): a decoder whose
every layer is dense latent attention (MLA with a low-rank query, YaRN
rotary), with a leading dense layer and expert layers after it.

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, one sequence at a time, in the DECOMPRESSED form: keys and
values are expanded per head as the equations have them, and nothing is
absorbed into the query or the output (the served path folds W_uk into
the query and W_uv into the output and attends the latent rows: this
file shares no attention code and no algebra with it). Queries are taken
in blocks of ``BLOCK`` positions, ``HEADS`` heads at a time, so that a
probe of some thousand tokens fits the host; a block sees all the keys it
may attend. Blocks, experts and the parts of a weight draw run on a pool
of threads; that changes no number. u = RMSNorm(x); no projection has a
bias.

Block, every layer (DeepSeek-V3's pre-norm ordering, eps ``rms_norm_eps``):

    h = x + Attn(RMSNorm(x)) ;  y = h + FFN(RMSNorm(h))

then a final RMSNorm and the head (a matrix of its own).

Attention, every layer, token t at position t:

    c_q = RMSNorm(u W_qa)                           q_lora_rank wide
    q_h = c_q W_qb -> heads of [nope | rope]; rope part rotated
    [c_kv | k_r] = u W_kva ; c_kv = RMSNorm(c_kv) ; k_r rotated, one for
          all heads
    k_h = [c_kv W_uk,h | k_r] ; v_h = c_kv W_uv,h
    o_h = sum_{s <= t} softmax_s(q_h . k_h,s * scale) v_h,s
    Attn = concat_h(o_h) W_o

Rotary: interleaved pairs (2i, 2i + 1) of the ``qk_rope_head_dim`` dims,
frequencies by YaRN's NTK-by-parts rule (``rope_scaling``: pair i turns
with theta^(-2i/d) where it makes more than ``beta_fast`` turns over
``original_max_position_embeddings``, with that over ``factor`` where it
makes fewer than ``beta_slow``, a linear blend between). cos and sin are
scaled by m(mscale) / m(mscale_all_dim) (1 here: both are 1), and the
softmax scale is (nope + rope)^-0.5 * m(mscale_all_dim)^2, with
m(a) = 0.1 a ln(factor) + 1: 192^-0.5 * 1.8134 for factor 32.

Expert layer (layers ``first_k_dense_replace`` ..): s = sigmoid(u' W_r)
over ALL the published experts; ids = the ``num_experts_per_tok`` largest
of s (``topk_method`` "none": no group limit, no correction bias; the
keys ``n_group`` / ``topk_group`` are inert); w = s[ids] / sum s[ids]
(``norm_topk_prob``) times ``routed_scaling_factor``;
FFN = sum_{k: ids_k held here} w_k E_ids_k(u') + E_shared(u'),
E(z) = (silu(z W_g) * z W_u) W_d. Layers before: SwiGLU of
``intermediate_size``.

Departures from the published model, each because the benchmark's
configuration says so: ``n_routed_experts`` in the model dict counts the
experts HELD (``ep_share`` gives the published count, the chips that share
a layer and this chip's rank): the router is as wide as published, the
weights are normalised over all the chosen experts, the absent experts'
part of the result is left out, and that partial result goes on to the
next layer, as on a chip that runs without its exchange. The vocabulary is
the slice the configuration gives. Weights are random, rounded to the
served dtype (bf16); arithmetic on them is float32. Training-only keys
(``seq_aux``, ``ep_size``) are not read.

Nothing here is taken from the program under test. ``make_weights`` draws
the numbers ``jax.random.normal`` gives in the order, shapes and scales of
the served ``--load-format dummy`` recipe (normal, 1/sqrt(fan-in); the
n-th draw from ``split(key(seed), 64)[n]``; the layers of a run, the
leading dense ones and then the expert ones, drawn as one stacked array),
so that the same seed names the same model on both sides. A stacked draw
of 705 M numbers would take 11 GB while ``jax.random.normal`` makes it, so
``normal_part`` makes a draw in parts of at most ``PART`` numbers: jax's
counter-based generator gives the i-th number of a draw from the key and i
alone. That the parts are ``jax.random.normal``'s numbers, and that the two
recipes agree bit for bit, are tests
(``tests/perfbench/test_reference_axk1.py``), not imports.
"""

import concurrent.futures
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

BLOCK = 128         # queries taken at a time
HEADS = 16          # heads of a block taken at a time
KEYS = 1024         # a block sees keys up to a multiple of this
ROWS = 128          # an expert's tokens are padded to a multiple of this
PART = 1 << 23      # numbers of a weight draw made at a time

CORES = len(os.sched_getaffinity(0))
_WORK = concurrent.futures.ThreadPoolExecutor(max(2, min(CORES, 16)))
_DRAWS = concurrent.futures.ThreadPoolExecutor(max(1, min(CORES, 16) // 2))


def experts_of(model):
    """(router width, experts held here, first held expert)."""
    held = model["n_routed_experts"]
    share = model.get("ep_share")
    if not share:
        return held, held, 0
    assert share["n_routed_experts"] == held * share["chips"]
    return share["n_routed_experts"], held, held * share.get("rank", 0)


def layer_kinds(model):
    """"dense" | "moe" per layer."""
    return ["dense" if i < model["first_k_dense_replace"] else "moe"
            for i in range(model["num_hidden_layers"])]


def yarn(model):
    """(inverse frequencies [rope / 2] as float64, cos/sin factor, softmax
    scale) from ``rope_theta`` and ``rope_scaling``."""
    d, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    base = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    scale = (model["qk_nope_head_dim"] + d) ** -0.5
    s = model.get("rope_scaling")
    if not s:
        return base, 1.0, scale
    assert s.get("rope_type", s.get("type")) == "yarn", s
    factor, orig = s["factor"], s["original_max_position_embeddings"]

    def pair_with_turns(turns):
        # the pair index whose wavelength makes ``turns`` turns over the
        # original context
        return d * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(pair_with_turns(s.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_with_turns(s.get("beta_slow", 1))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0, 1)
    inv = base * (1 - ramp) + base / factor * ramp

    def m(a):
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0
    all_dim = m(s.get("mscale_all_dim", 0.0))
    return inv, m(s.get("mscale", 1.0)) / all_dim, scale * all_dim ** 2


@functools.partial(jax.jit, static_argnums=(2,))
def normal_part(key_data, start, count):
    """Numbers ``start .. start + count`` of ``jax.random.normal(key,
    shape, float32)`` in row-major order, whatever ``shape`` (under 2**32
    numbers): jax's partitionable threefry hashes the key with each
    number's own index, and ``uniform`` and ``normal`` are elementwise on
    the bits (jax/_src/random.py ``_uniform``, ``_normal_real``, step for
    step)."""
    index = start + jax.lax.iota(jnp.uint32, count)
    bits1, bits2 = threefry2x32_p.bind(key_data[0], key_data[1],
                                       jnp.zeros_like(index), index)
    one = np.array(1.0, np.float32)
    mantissa = jax.lax.shift_right_logical(bits1 ^ bits2, jnp.uint32(32 - 23))
    floats = jax.lax.bitcast_convert_type(
        mantissa | jnp.uint32(one.view(np.uint32)), jnp.float32) - one
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jnp.maximum(lo, floats * (one - lo) + lo)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


@functools.partial(jax.jit, static_argnums=(2, 3, 5))
def _scaled_draw(key_data, start, parts, part, scale, dtype):
    """Numbers ``start .. start + parts * part`` of a draw, scaled and
    rounded, a part at a time."""
    def one(i):
        # the served recipe draws, scales and rounds in three steps; the
        # barrier keeps XLA from folding the scale into the draw
        drawn = jax.lax.optimization_barrier(
            normal_part(key_data, start + i * jnp.uint32(part), part))
        return (drawn * scale).astype(dtype)
    return jax.lax.map(one, jnp.arange(parts, dtype=jnp.uint32)).reshape(-1)


def _now(fn):
    """``fn`` with its result computed when it returns (a pool's thread
    that went on to queue its next task would hold no bound on what is in
    flight)."""
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return jax.block_until_ready(fn(*args))
    return run


class _Draw:
    """Slice ``lead`` (along the first axis) of the draw ``(normal(key,
    shape) * scale).astype(dtype)``, queued at once and waited for when the
    slice is first read."""

    def __init__(self, key, shape, scale, dtype, lead):
        assert jax.config.jax_threefry_partitionable, (
            "normal_part follows jax's partitionable threefry")
        assert math.prod(shape) < 1 << 32
        self.shape = shape[1:]
        size = math.prod(self.shape)
        parts = -(-size // PART)
        while size % parts:
            parts += 1
        self.drawn = _DRAWS.submit(
            _now(_scaled_draw), jax.random.key_data(key),
            np.uint32(lead * size), parts, size // parts, np.float32(scale),
            jnp.dtype(dtype))

    def __call__(self):
        return self.drawn.result().reshape(self.shape)


class _Drawn(dict):
    """A mapping whose values may be draws, put together at first use and
    kept."""

    def __getitem__(self, name):
        value = dict.__getitem__(self, name)
        if isinstance(value, _Draw):
            value = value()
            dict.__setitem__(self, name, value)
        return value


def make_weights(model, seed, dtype=jnp.bfloat16, stage_layers=None):
    """Seeded weights for ``model`` (the served ``config.json`` keys).
    Returns {"layers": [per-layer mapping with "mlp", ...], "embed",
    "final_norm", "lm_head"}; matrices are [in, out]. Which draw a matrix
    is (``split(key, 64)[n]``, and which layer of a run's stacked draw) is
    settled here, in the served recipe's order; the draws are queued in
    the layers' order and this returns at once."""
    assert not stage_layers, "the reference has no pipeline stages"
    hidden, vocab = model["hidden_size"], model["vocab_size"]
    hq, q_lora, lora = (model["num_attention_heads"], model["q_lora_rank"],
                        model["kv_lora_rank"])
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    n_router, held, _ = experts_of(model)
    keys = iter(jax.random.split(jax.random.key(seed), 64))

    def normal(shape, scale):
        """The recipe's next draw: (key, shape, scale)."""
        return next(keys), shape, scale

    kinds = layer_kinds(model)
    runs = [(mlp, kinds.count(mlp)) for mlp in ("dense", "moe")
            if mlp in kinds]
    assert kinds == [mlp for mlp, n in runs for _ in range(n)]
    s_in = hidden ** -0.5
    stacks = []
    for mlp, n in runs:
        st = {
            "kv_a_proj": normal((n, hidden, lora + rope), s_in),
            "w_uk": normal((n, hq, nope, lora), lora ** -0.5),
            "w_uv": normal((n, hq, lora, v), lora ** -0.5),
            "o_proj": normal((n, hq * v, hidden), (hq * v) ** -0.5),
            "q_a_proj": normal((n, hidden, q_lora), s_in),
            "q_b_proj": normal((n, q_lora, hq * (nope + rope)),
                               q_lora ** -0.5),
        }
        if mlp == "dense":
            inter = model["intermediate_size"]
            st["gate_proj"] = normal((n, hidden, inter), s_in)
            st["up_proj"] = normal((n, hidden, inter), s_in)
            st["down_proj"] = normal((n, inter, hidden), inter ** -0.5)
        else:
            inter = model["moe_intermediate_size"]
            st["router"] = normal((n, hidden, n_router), s_in)
            st["w_gate"] = normal((n, held, hidden, inter), s_in)
            st["w_up"] = normal((n, held, hidden, inter), s_in)
            st["w_down"] = normal((n, held, inter, hidden), inter ** -0.5)
            si = model["n_shared_experts"] * inter
            st["shared_gate_proj"] = normal((n, hidden, si), s_in)
            st["shared_up_proj"] = normal((n, hidden, si), s_in)
            st["shared_down_proj"] = normal((n, si, hidden), si ** -0.5)
        stacks.append((mlp, n, st))
    embed = normal((1, vocab, hidden), 1.0)
    lm_head = normal((1, hidden, vocab), s_in)

    weights = _Drawn(embed=_Draw(*embed, dtype, 0), layers=[],
                     final_norm=jnp.ones((hidden,), dtype))
    for mlp, n, st in stacks:
        for i in range(n):
            layer = _Drawn({k: _Draw(*draw, dtype, i)
                            for k, draw in st.items()})
            layer.update(mlp=mlp,
                         input_norm=jnp.ones((hidden,), dtype),
                         post_attn_norm=jnp.ones((hidden,), dtype),
                         q_a_norm=jnp.ones((q_lora,), dtype),
                         kv_a_norm=jnp.ones((lora,), dtype))
            weights["layers"].append(layer)
    weights["lm_head"] = _Draw(*lm_head, dtype, 0)
    return weights


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


@jax.jit
def _mm(a, w):
    with jax.default_matmul_precision("highest"):
        return a @ w.astype(jnp.float32)


def lower_precision(w, mode):
    """``w`` [in, out] as it reads after a round trip through the precision
    below bf16: symmetric, one scale per output channel. Only the control
    uses it."""
    wf = w.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2, keepdims=True), 1e-9)
    if mode == "int8":
        scale = absmax / 127.0
        return jnp.clip(jnp.round(wf / scale), -127, 127) * scale
    if mode == "fp8":
        scale = absmax / float(jnp.finfo(jnp.float8_e4m3fn).max)
        return (wf / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown control precision {mode!r}")


@functools.partial(jax.jit, static_argnums=(2,))
def _mm_lower(a, w, mode):
    with jax.default_matmul_precision("highest"):
        return a @ lower_precision(w, mode)


def rotate(x, pos, inv, factor):
    """Rotate channel pairs (2i, 2i + 1) of x [..., T, d] by pos * inv[i];
    cos and sin times ``factor``. x's second-to-last axis is time."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("span", "scale"))
def _attend_block(q, k, val, lo, *, span, scale):
    """Queries ``lo .. lo + BLOCK`` against the keys ``0 .. span``:
    q [hq, T, d], k [hq, T, d], val [hq, T, v] -> [BLOCK, hq, v]. The span
    only leaves out keys that no query of the block may attend."""
    with jax.default_matmul_precision("highest"):
        hq, block = q.shape[0], min(BLOCK, q.shape[1])
        allowed = (jnp.arange(span)[None, :]
                   <= (lo + jnp.arange(block))[:, None])    # [B, keys]

        def heads(h0):
            n = min(HEADS, hq)
            qh = jax.lax.dynamic_slice(q, (h0, lo, 0),
                                       (n, block, q.shape[2]))
            kh = jax.lax.dynamic_slice(k, (h0, 0, 0), (n, span, k.shape[2]))
            vh = jax.lax.dynamic_slice(val, (h0, 0, 0),
                                       (n, span, val.shape[2]))
            s = jnp.einsum("hqd,hsd->hqs", qh, kh) * scale
            p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("hqs,hsv->hqv", p, vh)
        out = jax.lax.map(heads, jnp.arange(0, hq, min(HEADS, hq)))
        return jnp.transpose(out.reshape(hq, block, -1), (1, 0, 2))


def latent_attention(model, u, layer, mm, knobs=()):
    """u [T, H] (normed; T a multiple of BLOCK or under it) -> the
    attention's output [T, H]. ``knobs``: what a sensitivity test leaves
    out ("yarn_scale": the softmax scale without m^2; "yarn_freq": plain
    rotary frequencies)."""
    t, eps = u.shape[0], model["rms_norm_eps"]
    hq, nope, rope, lora, v = (
        model["num_attention_heads"], model["qk_nope_head_dim"],
        model["qk_rope_head_dim"], model["kv_lora_rank"],
        model["v_head_dim"])
    assert hq % min(HEADS, hq) == 0 and (t < BLOCK or t % BLOCK == 0)
    inv, cs, scale = yarn(model)
    if "yarn_scale" in knobs:
        scale = (nope + rope) ** -0.5
    if "yarn_freq" in knobs:
        inv = yarn(dict(model, rope_scaling=None))[0]
    pos = jnp.arange(t)
    c_q = rms_norm(_mm(u, layer["q_a_proj"]), layer["q_a_norm"], eps)
    q = mm(c_q, layer["q_b_proj"]).reshape(t, hq, nope + rope)
    q = jnp.transpose(q, (1, 0, 2))                         # [hq, T, .]
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], pos, inv, cs)], axis=-1)
    kv_a = _mm(u, layer["kv_a_proj"])
    c_kv = rms_norm(kv_a[:, :lora], layer["kv_a_norm"], eps)
    k_r = rotate(kv_a[:, lora:], pos, inv, cs)              # [T, rope]
    # decompressed: every head's own keys and values
    k = jnp.concatenate(
        [jnp.einsum("tl,hnl->htn", c_kv, layer["w_uk"].astype(jnp.float32)),
         jnp.broadcast_to(k_r[None], (hq, t, rope))], axis=-1)
    val = jnp.einsum("tl,hlv->htv", c_kv, layer["w_uv"].astype(jnp.float32))

    def block(lo):
        # the keys a block can see: up to its last query, cut in steps of
        # KEYS positions so that few shapes occur
        span = min(-(-(lo + BLOCK) // KEYS) * KEYS, t)
        return _attend_block(q, k, val, lo, span=span, scale=scale)
    # the last blocks see the most keys: they start first, so that the
    # pool's threads end together
    starts = range(0, t, BLOCK)
    blocks = list(_WORK.map(_now(block), reversed(starts)))
    o = jnp.concatenate(blocks[::-1], axis=0)
    return mm(o.reshape(t, hq * v), layer["o_proj"])


def swiglu(z, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(z, wg)) * mm(z, wu), wd)


def route(model, u, layer):
    """(weights [T, k], ids [T, k]) over all the published experts: the
    plain top-k of the sigmoid scores."""
    assert model.get("scoring_func") == "sigmoid"
    assert model.get("topk_method") == "none", (
        "the reference has no group limit and no correction bias")
    s = jax.nn.sigmoid(_mm(u, layer["router"]))
    _, ids = jax.lax.top_k(s, model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if model.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * model.get("routed_scaling_factor", 1.0), ids


def routed_part(model, u, layer, mm, first=None, held=None):
    """What the experts ``first .. first + held`` give (``layer``'s
    ``w_gate`` etc. are theirs, in order): each expert on the tokens routed
    to it, an expert at a time."""
    _, h, f = experts_of(model)
    first = f if first is None else first
    held = h if held is None else held
    w, ids = route(model, u, layer)
    w, ids = np.asarray(w), np.asarray(ids)
    w_gate, w_up, w_down = layer["w_gate"], layer["w_up"], layer["w_down"]

    def expert(e):
        rows, slot = np.nonzero(ids == first + e)
        if not len(rows):
            return None
        # padded with weight-0 copies of the first row, so that the
        # products of all experts have few shapes (each shape is compiled)
        pad = -len(rows) % ROWS
        idx = np.concatenate([rows, np.full(pad, rows[0])])
        we = np.concatenate([w[rows, slot], np.zeros(pad, w.dtype)])
        y = swiglu(u[idx], w_gate[e], w_up[e], w_down[e], mm)
        return idx, y * jnp.asarray(we)[:, None]
    out = jnp.zeros_like(u)
    for part in _WORK.map(_now(expert), range(held)):
        if part is not None:
            out = out.at[part[0]].add(part[1])
    return out


def shared_part(u, layer, mm):
    return swiglu(u, layer["shared_gate_proj"], layer["shared_up_proj"],
                  layer["shared_down_proj"], mm)


def _layer(model, x, layer, control=None, knobs=()):
    """One decoder layer on x [T, H] (float32). ``control`` names the lower
    precision the large matrices are stored in (the control only: every
    matrix the served ``--quantization`` stores so)."""
    mm = _mm if control is None else (
        lambda a, w: _mm_lower(a, w, control))
    eps = model["rms_norm_eps"]
    u = rms_norm(x, layer["input_norm"], eps)
    h = x + latent_attention(model, u, layer, mm, knobs)
    u2 = rms_norm(h, layer["post_attn_norm"], eps)
    if layer["mlp"] == "dense":
        return h + swiglu(u2, layer["gate_proj"], layer["up_proj"],
                          layer["down_proj"], mm)
    return h + routed_part(model, u2, layer, mm) + shared_part(u2, layer, mm)


def hidden_states(model, weights, tokens, control=None, knobs=()):
    """Final-norm hidden states [T, H] of one token sequence. A sequence
    longer than a block is padded at its end to whole blocks: no position
    sees a later one."""
    t = len(tokens)
    pad = -t % BLOCK if t > BLOCK else 0
    tokens = jnp.asarray(list(tokens) + [0] * pad, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for layer in weights["layers"]:
            x = _layer(model, x, layer, control, knobs)
        return rms_norm(x, weights["final_norm"], model["rms_norm_eps"])[:t]


def logits(model, weights, tokens, control=None, knobs=()):
    """[T, vocab] (small sizes only: the tests)."""
    hid = hidden_states(model, weights, tokens, control, knobs)
    return _mm(hid, weights["lm_head"])


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
            return jax.nn.log_softmax(h @ head.astype(jnp.float32), axis=-1)

    out = [[] for _ in want]
    rows = [i for i, ids in enumerate(want) if ids]
    for lo in range(0, len(rows), block):
        idx = rows[lo: lo + block]
        pad = idx + [idx[-1]] * (block - len(idx))     # one compiled shape
        take = jax.device_get(block_lp(hid[jnp.asarray(pad)], head))
        for r, i in enumerate(idx):
            out[i] = [float(take[r, tok]) for tok in want[i]]
    return out
