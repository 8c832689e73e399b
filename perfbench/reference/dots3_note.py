"""Plain reference for dots3-note-prev (``model_type: dots3_note``): a
decoder whose layers are latent attention (MLA) in two geometries, chosen
per layer by ``layer_types``, with a leading dense layer and expert layers
after it.

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, one sequence at a time, keys and values per head as the
equations have them (nothing absorbed). Queries are taken in blocks of
``BLOCK`` positions, ``HEADS`` heads at a time, so that a 6144-token probe
fits the host; every block sees all the keys it may attend. The blocks of
a layer, the experts of a layer and the parts of a weight draw are
independent of each other and run on a pool of threads (the host's cores
are what a run's set-up waits for); that changes no number.
u = RMSNorm(x); every projection is bias-free.

Block, every layer (DeepSeek-V3's ordering, eps ``rms_norm_eps``):

    h = x + Attn(RMSNorm(x)) ;  y = h + FFN(RMSNorm(h))

then a final RMSNorm and the head (a matrix of its own).

Full-attention layer (``layer_types[i] == "full_attention"``), token t:

    c_q = s_q RMSNorm(u W_qa)                      q_lora_rank wide
    q_h = c_q W_qb -> heads of [nope | rope]; rope part rotated
          (interleaved pairs, theta ``rope_theta``)
    [c_kv | k_r] = u W_kva ; c_kv = s_kv RMSNorm(c_kv) ; k_r = rope(k_r),
          one for all heads
    k_h = [c_kv W_uk,h | k_r] ; v_h = c_kv W_uv,h
    indexer (DeepSeek-V3.2's): qI = c_q W_Iq -> index_n_heads heads of
          index_head_dim; kI = LayerNorm(u W_Ik) (eps 1e-6); rotary
          (half-split) on the first rope dims of each;
          w = u W_Iw * index_n_heads^-0.5 * index_head_dim^-0.5;
          I(t, s) = sum_j w_j ReLU(qI_tj . kI_s);
          S_t = the index_topk positions s <= t with the largest I(t, s)
          (all of them while t < index_topk)
    o_h = sum_{s in S_t} softmax_s(q_h . k_h,s (nope + rope)^-0.5) v_h,s
    g = sigmoid(u W_g), one scalar a head (``attention_gate_type``
          headwise) ; Attn = concat_h(g_h o_h) W_o

Windowed layer (``"sliding_attention"``): the same latent form at the
``swa_*`` sizes and ``swa_rope_theta``, attending s in (t - window, t]
(the window counts the current token), no indexer.

``apply_mla_qkv_lora_rescale``: s_q = sqrt(hidden / q_lora_rank), s_kv =
sqrt(hidden / kv_lora_rank), after the latents' norms; else 1.

Expert layer (layers ``first_k_dense_replace`` ..): s = sigmoid(u' W_r)
over ALL the published experts; ids = top-k(s + b); w = s[ids] / sum
s[ids] (``norm_topk_prob``) times ``routed_scaling_factor``;
FFN = sum_{k: ids_k held here} w_k E_ids_k(u') + E_shared(u'),
E(z) = (silu(z W_g) * z W_u) W_d. The weights are normalised over all the
chosen experts, held or not. Layers before: SwiGLU of ``intermediate_size``.

Departures from the published model, each because the benchmark's
configuration says so: ``n_routed_experts`` in the model dict counts the
experts HELD (``ep_share`` gives the published count, the chips that share
a layer and this chip's rank): the router is as wide as published, the
absent experts' part of the result is left out, and that partial result
goes on to the next layer, as on a chip that runs without its exchange.
The vocabulary is the slice the configuration gives. Weights are random,
rounded to the served dtype (bf16); arithmetic on them is float32. The
vision and audio towers and the MTP head are left out.

Nothing here is taken from the program under test. ``make_weights`` draws
the numbers ``jax.random.normal`` gives in the order, shapes and scales of
the served ``--load-format dummy`` recipe (normal, 1/sqrt(fan-in), and the
matrices that read a rescaled latent smaller by its rescale; the n-th draw
from ``fold_in(key(seed), n)``; runs of same-kind layers drawn as one
stacked array), so that the same seed names the same model on both sides.
A stacked draw of 755 M numbers takes 14 GB while ``jax.random.normal``
makes it, so ``normal_part`` makes a draw in parts of at most ``PART``
numbers:
jax's counter-based generator gives the i-th number of a draw from the key
and i alone. That the parts are ``jax.random.normal``'s numbers, and that
the two recipes agree bit for bit, are tests
(``tests/perfbench/test_reference_dots3.py``), not imports.
"""

import concurrent.futures
import functools
import itertools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

FULL, SWA = "full_attention", "sliding_attention"
BLOCK = 128         # queries taken at a time
HEADS = 16          # heads of a block taken at a time
KEYS = 1024         # a full layer's block sees keys up to a multiple of this
ROWS = 128          # an expert's tokens are padded to a multiple of this
PART = 1 << 23      # numbers of a weight draw made at a time

# blocks, experts and parts of draws, one thread a core; draws have a
# narrower pool of their own so that the layers' work never queues behind
# every later layer's weights
CORES = len(os.sched_getaffinity(0))
_WORK = concurrent.futures.ThreadPoolExecutor(max(2, min(CORES, 16)))
_DRAWS = concurrent.futures.ThreadPoolExecutor(max(1, min(CORES, 16) // 2))


def _geom(model, kind):
    if kind == SWA:
        return dict(heads=model["swa_num_attention_heads"],
                    q_lora=model["swa_q_lora_rank"],
                    lora=model["swa_kv_lora_rank"],
                    nope=model["swa_qk_nope_head_dim"],
                    rope=model["swa_qk_rope_head_dim"],
                    v=model["swa_v_head_dim"],
                    theta=model["swa_rope_theta"],
                    gate=model.get("swa_attention_gate_type"),
                    window=model["sliding_window_size"])
    return dict(heads=model["num_attention_heads"],
                q_lora=model["q_lora_rank"], lora=model["kv_lora_rank"],
                nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
                v=model["v_head_dim"], theta=model["rope_theta"],
                gate=model.get("attention_gate_type"), window=0)


def experts_of(model):
    """(router width, experts held here, first held expert)."""
    held = model["n_routed_experts"]
    share = model.get("ep_share")
    if not share:
        return held, held, 0
    assert share["n_routed_experts"] == held * share["chips"]
    return share["n_routed_experts"], held, held * share.get("rank", 0)


def layer_kinds(model):
    """[(attention kind, "dense" | "moe")] per layer."""
    types = model["layer_types"]
    assert len(types) == model["num_hidden_layers"]
    return [(t, "dense" if i < model["first_k_dense_replace"] else "moe")
            for i, t in enumerate(types)]


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
    rounded, a part at a time: only a part's float32 temporaries exist at
    once, and the parts land in one array (putting 30 parts together
    afterwards took longer than drawing them)."""
    def one(i):
        # the served recipe draws, scales and rounds in three steps; the
        # barrier keeps XLA from folding the scale into the draw
        # (reference/olmo_hybrid.py)
        drawn = jax.lax.optimization_barrier(
            normal_part(key_data, start + i * jnp.uint32(part), part))
        return (drawn * scale).astype(dtype)
    return jax.lax.map(one, jnp.arange(parts, dtype=jnp.uint32)).reshape(-1)


def _now(fn):
    """``fn`` with its result computed when it returns: jax hands back
    arrays whose work is still queued, and a pool's thread that went on to
    queue its next task would hold no bound on what is in flight."""
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
        # equal parts of at most PART numbers
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
    Returns {"layers": [per-layer mapping with "kind", "mlp", ...],
    "embed", "final_norm", "lm_head"}; matrices are [in, out]. Which draw
    a matrix is (``fold_in(key, n)``, and which layer of a run's stacked
    draw) is settled here, in the served recipe's order, and the draws are
    queued in the layers' order; this returns at once and the child goes on
    to read its questions (4 G parameters take a minute of every core to
    draw, and the parent's question of 6144 tokens does not fit a pipe's
    buffer: it would wait that long to learn that its server had
    failed)."""
    assert not stage_layers, "the reference has no pipeline stages"
    hidden, vocab = model["hidden_size"], model["vocab_size"]
    n_router, held, _ = experts_of(model)
    key = jax.random.key(seed)
    count = itertools.count()

    def normal(shape, scale):
        """The recipe's next draw: (key, shape, scale)."""
        return jax.random.fold_in(key, next(count)), shape, scale

    runs = []
    for kind in layer_kinds(model):
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    s_in = hidden ** -0.5
    stacks = []
    for (kind, mlp), n in runs:
        g = _geom(model, kind)
        hq, nope, rope, lora, v = (g["heads"], g["nope"], g["rope"],
                                   g["lora"], g["v"])
        # where the latents are rescaled, the matrices that read them are
        # drawn that much smaller (the served recipe: unit-variance
        # queries, keys and values)
        s_q, s_kv = _rescale(model, g["q_lora"]), _rescale(model, lora)
        st = {
            "kv_a_proj": normal((n, hidden, lora + rope), s_in),
            "w_uk": normal((n, hq, nope, lora), lora ** -0.5 / s_kv),
            "w_uv": normal((n, hq, lora, v), lora ** -0.5 / s_kv),
            "o_proj": normal((n, hq * v, hidden), (hq * v) ** -0.5),
            "q_a_proj": normal((n, hidden, g["q_lora"]), s_in),
            "q_b_proj": normal((n, g["q_lora"], hq * (nope + rope)),
                               g["q_lora"] ** -0.5 / s_q),
        }
        if g["gate"]:
            st["attn_gate"] = normal((n, hidden, hq), s_in)
        if kind == FULL:
            nh, hd = model["index_n_heads"], model["index_head_dim"]
            st["idx_wq_b"] = normal((n, g["q_lora"], nh * hd),
                                    g["q_lora"] ** -0.5 / s_q)
            st["idx_wk"] = normal((n, hidden, hd), s_in)
            st["idx_weights"] = normal((n, hidden, nh), s_in)
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
        stacks.append((kind, mlp, n, g, st))
    embed = normal((1, vocab, hidden), 1.0)
    lm_head = normal((1, hidden, vocab), s_in)

    # the parts are queued in the order the forward pass reads them
    weights = _Drawn(embed=_Draw(*embed, dtype, 0), layers=[],
                     final_norm=jnp.ones((hidden,), dtype))
    for kind, mlp, n, g, st in stacks:
        for i in range(n):
            layer = _Drawn({k: _Draw(*draw, dtype, i)
                            for k, draw in st.items()})
            layer.update(
                kind=kind, mlp=mlp,
                input_norm=jnp.ones((hidden,), dtype),
                post_attn_norm=jnp.ones((hidden,), dtype),
                q_a_norm=jnp.ones((g["q_lora"],), dtype),
                kv_a_norm=jnp.ones((g["lora"],), dtype))
            if kind == FULL:
                hd = model["index_head_dim"]
                layer["idx_k_norm_w"] = jnp.ones((hd,), dtype)
                layer["idx_k_norm_b"] = jnp.zeros((hd,), dtype)
            if mlp == "moe":
                layer["e_bias"] = jnp.zeros((n_router,), jnp.float32)
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
    below bf16 (reference/olmo_hybrid.py): symmetric, one scale per output
    channel. Only the control uses it."""
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


def rope_interleaved(x, pos, theta):
    """Rotate channel pairs (2i, 2i + 1) of x [..., T, d] by pos * theta^
    (-2i / d). x's second-to-last axis is time."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rope_half(x, pos, theta):
    """Half-split rotary: channel i pairs with i + d / 2."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _rescale(model, rank):
    if model.get("apply_mla_qkv_lora_rescale"):
        return math.sqrt(model["hidden_size"] / rank)
    return 1.0


@functools.partial(jax.jit, static_argnames=("span", "scale", "window",
                                             "topk"))
def _attend_block(q, k, val, index, lo, k_lo, *, span, scale, window, topk):
    """Queries ``lo .. lo + BLOCK`` against the keys ``k_lo .. k_lo +
    span``: q [hq, T, d], k [hq, T, d], val [hq, T, v] -> [BLOCK, hq, v].
    What a query may attend is masked (the causal order, the window, the
    indexer's choice); the span only leaves out keys that no query of the
    block may attend."""
    with jax.default_matmul_precision("highest"):
        hq, block = q.shape[0], min(BLOCK, q.shape[1])
        qp = lo + jnp.arange(block)
        kp = k_lo + jnp.arange(span)
        allowed = kp[None, :] <= qp[:, None]                # [B, keys]
        if window:
            allowed &= kp[None, :] > qp[:, None] - window
        if index is not None:
            qi, ki, wi = index          # [nh, T, hd], [T, hd], [T, nh]
            qi = jax.lax.dynamic_slice_in_dim(qi, lo, block, 1)
            ki = jax.lax.dynamic_slice_in_dim(ki, k_lo, span, 0)
            wi = jax.lax.dynamic_slice_in_dim(wi, lo, block, 0)
            sc = jax.nn.relu(jnp.einsum("hqd,sd->hqs", qi, ki))
            score = jnp.einsum("hqs,qh->qs", sc, wi)
            score = jnp.where(allowed, score, -jnp.inf)
            # exactly topk of them: among equal scores (a sum of ReLUs
            # is 0 where every head's product is negative) the earlier
            # position, as lax.top_k orders them
            chosen = jax.lax.top_k(score, min(topk, span))[1]
            allowed &= jnp.zeros_like(allowed).at[
                jnp.arange(block)[:, None], chosen].set(True)

        def heads(h0):
            n = min(HEADS, hq)
            qh = jax.lax.dynamic_slice(q, (h0, lo, 0),
                                       (n, block, q.shape[2]))
            kh = jax.lax.dynamic_slice(k, (h0, k_lo, 0),
                                       (n, span, k.shape[2]))
            vh = jax.lax.dynamic_slice(val, (h0, k_lo, 0),
                                       (n, span, val.shape[2]))
            s = jnp.einsum("hqd,hsd->hqs", qh, kh) * scale
            p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("hqs,hsv->hqv", p, vh)
        out = jax.lax.map(heads, jnp.arange(0, hq, min(HEADS, hq)))
        return jnp.transpose(out.reshape(hq, block, -1), (1, 0, 2))


def latent_attention(model, u, layer, kind, mm, knobs=()):
    """u [T, H] (normed; T a multiple of BLOCK or under it) -> the
    attention's output [T, H]. ``knobs``: the parts a sensitivity test
    leaves out ("indexer", "window", "gate", "rescale")."""
    g = _geom(model, kind)
    t, eps = u.shape[0], model["rms_norm_eps"]
    hq, nope, rope, lora, v = (g["heads"], g["nope"], g["rope"], g["lora"],
                               g["v"])
    assert hq % min(HEADS, hq) == 0 and (t < BLOCK or t % BLOCK == 0)
    pos = jnp.arange(t)
    s_q = 1.0 if "rescale" in knobs else _rescale(model, g["q_lora"])
    s_kv = 1.0 if "rescale" in knobs else _rescale(model, lora)
    c_q = s_q * rms_norm(_mm(u, layer["q_a_proj"]), layer["q_a_norm"], eps)
    q = mm(c_q, layer["q_b_proj"]).reshape(t, hq, nope + rope)
    q = jnp.transpose(q, (1, 0, 2))                         # [hq, T, .]
    q = jnp.concatenate(
        [q[..., :nope], rope_interleaved(q[..., nope:], pos, g["theta"])],
        axis=-1)
    kv_a = _mm(u, layer["kv_a_proj"])
    c_kv = s_kv * rms_norm(kv_a[:, :lora], layer["kv_a_norm"], eps)
    k_r = rope_interleaved(kv_a[:, lora:], pos, g["theta"])  # [T, rope]
    k = jnp.concatenate(
        [jnp.einsum("tl,hnl->htn", c_kv, layer["w_uk"].astype(jnp.float32)),
         jnp.broadcast_to(k_r[None], (hq, t, rope))], axis=-1)
    val = jnp.einsum("tl,hlv->htv", c_kv, layer["w_uv"].astype(jnp.float32))

    topk = model.get("index_topk", 0)
    index = None
    if kind == FULL and "indexer" not in knobs and t > topk:
        nh, hd = model["index_n_heads"], model["index_head_dim"]
        qi = _mm(c_q, layer["idx_wq_b"]).reshape(t, nh, hd)
        ki = _mm(u, layer["idx_wk"])
        mu = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean((ki - mu) ** 2, axis=-1, keepdims=True)
        ki = ((ki - mu) * jax.lax.rsqrt(var + 1e-6)
              * layer["idx_k_norm_w"].astype(jnp.float32)
              + layer["idx_k_norm_b"].astype(jnp.float32))
        qi = jnp.transpose(qi, (1, 0, 2))
        qi = jnp.concatenate([rope_half(qi[..., :rope], pos, g["theta"]),
                              qi[..., rope:]], axis=-1)     # [nh, T, hd]
        ki = jnp.concatenate([rope_half(ki[:, :rope], pos, g["theta"]),
                              ki[:, rope:]], axis=-1)       # [T, hd]
        wi = _mm(u, layer["idx_weights"]) * nh ** -0.5 * hd ** -0.5
        index = (qi, ki, wi)
    window = 0 if "window" in knobs else g["window"]

    def block(lo):
        # the keys a block can see: up to its last query, cut in steps of
        # KEYS positions so that few shapes occur; in a windowed layer the
        # window before its first query and the block itself
        if window:
            span = min(t, BLOCK + -(-(window - 1) // BLOCK) * BLOCK)
            k_lo = min(max(0, lo + BLOCK - span), t - span)
        else:
            span, k_lo = min(-(-(lo + BLOCK) // KEYS) * KEYS, t), 0
        return _attend_block(q, k, val, index, lo, k_lo, span=span,
                             scale=(nope + rope) ** -0.5, window=window,
                             topk=topk)
    # the last blocks of a full layer see the most keys: they start first,
    # so that the pool's threads end together
    starts = range(0, t, BLOCK)
    blocks = list(_WORK.map(_now(block), reversed(starts)))
    o = jnp.concatenate(blocks[::-1], axis=0)
    if g["gate"] == "headwise" and "gate" not in knobs:
        o = o * jax.nn.sigmoid(_mm(u, layer["attn_gate"]))[:, :, None]
    elif g["gate"] not in (None, "", "headwise"):
        raise ValueError(f"unknown gate {g['gate']!r}")
    return mm(o.reshape(t, hq * v), layer["o_proj"])


def swiglu(z, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(z, wg)) * mm(z, wu), wd)


def route(model, u, layer):
    """(weights [T, k], ids [T, k]) over all the published experts."""
    s = jax.nn.sigmoid(_mm(u, layer["router"]))
    assert model.get("scoring_func", "sigmoid") == "sigmoid"
    assert not model.get("n_group"), "the reference has no group limit"
    _, ids = jax.lax.top_k(s + layer["e_bias"], model["num_experts_per_tok"])
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
    h = x + latent_attention(model, u, layer, layer["kind"], mm, knobs)
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
