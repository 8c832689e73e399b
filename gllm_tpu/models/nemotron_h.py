"""NemotronH decoder (``NemotronHForCausalLM``, model_type nemotron_h):
blocks of ONE mixer each, in an arbitrary pattern.

    x <- x + Mixer_i(RMSNorm(x))       Mixer_i by ``layer_types[i]``:
    "mamba"           Mamba-2 (ops/mamba2.py): state in the slot pool
    "moe"             DeepSeek-V3's router over experts of two matrices and
                      relu^2, a share of them held here (models/deepseek.py)
    "full_attention"  GQA over the paged KV pool, no rotary, no norms

TPU-first structure, as models/hybrid.py:
- layers of one kind are stacked ([L_kind, ...] leaves) and a layer's
  leaves are cut from the stack where they are used; the pattern need not
  be periodic: it is folded into nested repeats (``layer_program``:
  ``MEMEM*EMEMEM*EME`` = 2 x (2 x ME, M, *, E), M, E) and every repeat is
  a ``lax.scan``, so a step program traces 7 blocks for these 16 layers;
- the Mamba-2 state (convolution window + recurrent state) lives in slot
  pools beside the paged KV of the attention layers (``NemotronKV``; the
  fields the runner's slot maintenance reads are HybridKV's), all layers
  and slots on one axis, addressed through ``batch.ssm_slots``;
- a mixed step splits its rows as the GDN layers do: a row with one new
  token takes the recurrent step, a row with more is cut into whole
  chunks packed one row after another (``gdn_chunk_slots`` at
  ``cfg.ssm_chunk`` tokens) and takes the chunked rule.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gllm_tpu.batching import StepBatch
from gllm_tpu.models import dense
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.models.deepseek import (STATS, _held_experts, _shared_expert,
                                      deepseek_route, expert_stacks)
from gllm_tpu.ops import paged_attention, rms_norm, write_kv
from gllm_tpu.ops.attention import tp_sharded
from gllm_tpu.ops.gdn import (causal_conv1d, gdn_chunk_slots, gdn_impl_for,
                              packed_chunks, packed_slot_of_token)
from gllm_tpu.ops.mamba2 import (mamba2_chunk_packed, mamba2_chunk_pool,
                                 mamba2_recurrent_step,
                                 rms_norm_gated_grouped)
from gllm_tpu.ops.quant import qmm

Params = Dict[str, Any]

MAMBA, MOE, ATTN = "mamba", "moe", "full_attention"
_GROUP = {MAMBA: "mamba_layers", MOE: "moe_layers", ATTN: "attn_layers"}


class NemotronKV(NamedTuple):
    """Paged KV for the attention layers + the Mamba-2 slot pools."""
    k: jnp.ndarray      # [La, num_pages, page_size, Hkv, D]
    v: jnp.ndarray
    conv: jnp.ndarray   # [Lm, num_slots, K-1, conv_dim] f32
    rec: jnp.ndarray    # [Lm, num_slots, H, P, N] f32
    # what the step's expert layers counted (models/deepseek.py STATS), or
    # None where the layer is whole
    stats: Optional[jnp.ndarray] = None


def layer_program(kinds: Tuple[str, ...]):
    """``kinds`` folded into nested repeats: a tuple whose items are a
    kind, or (sub-program, count) for ``count`` >= 2 copies on end. At
    each place the repeat that covers most layers is taken (the shortest
    unit among equals)."""
    out, i, n = [], 0, len(kinds)
    while i < n:
        best = None
        for p in range(1, (n - i) // 2 + 1):
            r = 1
            while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                r += 1
            if r >= 2 and (best is None or p * r > best[0] * best[1]):
                best = (p, r)
        if best:
            p, r = best
            out.append((layer_program(kinds[i:i + p]), r))
            i += p * r
        else:
            out.append(kinds[i])
            i += 1
    return tuple(out)


def init_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                  dtype=jnp.bfloat16, num_slots: int = 2) -> NemotronKV:
    La, Lm = cfg.num_attn_layers, cfg.num_linear_layers
    kv_shape = (La, num_pages, page_size, cfg.kv_cache_heads, cfg.head_dim)
    conv, rec = cfg.ssm_slot_shapes
    return NemotronKV(
        k=jnp.zeros(kv_shape, dtype), v=jnp.zeros(kv_shape, dtype),
        conv=jnp.zeros((Lm, num_slots) + conv, jnp.float32),
        rec=jnp.zeros((Lm, num_slots) + rec, jnp.float32),
        stats=(jnp.zeros((len(STATS),), jnp.int32)
               if cfg.experts_held else None))


def no_mesh_specs(cfg: ModelConfig, tp: int):
    raise NotImplementedError(
        "NemotronH under a mesh (tp / dp / sp > 1): the Mamba-2 slot pool "
        "and its kernels are not partitioned, and the expert layer has no "
        "exchange; one chip serves its share of a deployment (ep_share)")


def make_rope_table(cfg: ModelConfig) -> jnp.ndarray:
    return jnp.zeros((1, 1), jnp.float32)    # no rotary embedding: never read


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def lanes(n: int) -> int:
    """``n`` rounded up to whole lanes of 128. The two widths of this
    family that are no multiple of 128 (the routed experts' 1856, the
    Mamba-2 input projection's 10304) are STORED padded with zeros to the
    next one (1920, 10368): a bf16 array lies in tiles of 128 lanes
    anyway, and where a stack's last dimension is not whole tiles the TPU
    compiler lays the parameter out transposed and copies the whole stack
    back (4.3 GB of expert matrices) in every step program that reads it
    inside a scan. A zero column of ``W_up`` gives relu(0)^2 = 0 against a
    zero row of ``W_down``; the projection's pad columns are never read."""
    return -(-n // 128) * 128


def _pad_to(a, axis: int, width: int):
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, width - a.shape[axis])
    return jnp.pad(a, pad)


@jax.jit
def _inverse_softplus(dt):
    return dt + jnp.log(-jnp.expm1(-dt))


def init_params(cfg: ModelConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Params:
    """Seeded random weights (``--load-format dummy``): matrices normal,
    1/sqrt(fan-in); the n-th draw takes ``fold_in(key(seed), n)``. The
    Mamba-2 scalars follow the published initialiser, so that the state
    matters: ``A_log`` = log U[1, 16], ``dt_bias`` the inverse softplus of
    a log-uniform draw in [time_step_min, time_step_max] floored at
    time_step_floor, ``D`` = 1 (a token's decay lies between e^-1.6 and
    e^-0.001: the state carries over hundreds of tokens). The routed
    experts are drawn a layer at a time into their stack (a whole stack's
    float32 draw is 8.9 GB at the published widths).
    perfbench/reference/nemotron_h.py draws the same."""
    H, D = cfg.hidden_size, cfg.head_dim
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads
    La, Lm, Le = cfg.num_attn_layers, cfg.num_linear_layers, cfg.num_moe_layers
    Nh, Din, K = cfg.mamba_num_heads, cfg.mamba_d_inner, \
        cfg.linear_conv_kernel_dim
    conv_dim = cfg.gdn_conv_dim
    key = jax.random.key(seed)
    ks = (jax.random.fold_in(key, i) for i in itertools.count())

    def draw(k, shape, scale):
        # the three steps kept apart (no scale folded into the draw's own
        # constants): what the reference's draw rounds to
        return (jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32)) * scale).astype(dtype)

    def w(shape, scale):
        # one program a leaf: eager, the draw, its scaling and its cast
        # were three dispatches with a float32 copy between them, and ~90 s
        # of a cold start-up at the published widths
        return jax.jit(draw, static_argnums=(1, 2))(next(ks), shape, scale)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    s = H ** -0.5
    ones = lambda n: jnp.ones((n, H), dtype)            # noqa: E731
    params: Params = {}
    params["mamba_layers"] = {
        "norm": ones(Lm),
        "in_proj": _pad_to(w((Lm, H, Din + conv_dim + Nh), s), -1,
                           lanes(Din + conv_dim + Nh)),
        "conv_w": w((Lm, conv_dim, K), K ** -0.5),
        "conv_b": uniform((Lm, conv_dim), -K ** -0.5, K ** -0.5),
        "dt_bias": _inverse_softplus(jnp.maximum(
            jnp.exp(uniform((Lm, Nh), jnp.log(cfg.time_step_min),
                            jnp.log(cfg.time_step_max))),
            cfg.time_step_floor)),
        "a_log": jnp.log(uniform((Lm, Nh), 1.0, 16.0)),
        "d": jnp.ones((Lm, Nh), jnp.float32),
        "gate_norm": jnp.ones((Lm, Din), dtype),
        "out_proj": w((Lm, Din, H), Din ** -0.5),
    }
    params["attn_layers"] = {
        "norm": ones(La),
        "q_proj": w((La, H, Hq * D), s),
        "k_proj": w((La, H, Hkv * D), s),
        "v_proj": w((La, H, Hkv * D), s),
        "o_proj": w((La, Hq * D, H), (Hq * D) ** -0.5),
    }
    E, Eh = cfg.num_experts, cfg.num_local_experts
    I, SI = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    moe: Params = {
        "norm": ones(Le),
        "router": w((Le, H, E), s),
        # zeros, as models/deepseek.py draws it: a drawn bias of the
        # scores' own size (sigmoid of unit logits spreads ~0.2) decides
        # the choice and piles the rows on a few experts, the opposite
        # of what the published bias is there for
        "e_bias": jnp.zeros((Le, E), jnp.float32),
        "shared_up_proj": w((Le, H, SI), s),
        "shared_down_proj": w((Le, SI, H), SI ** -0.5),
    }

    for name, shape, scale, axis in (("w_up", (Eh, H, I), s, 2),
                                     ("w_down", (Eh, I, H), I ** -0.5, 1)):
        stored = list(shape)
        stored[axis] = lanes(I)             # see ``lanes``
        # a layer's experts drawn, padded and put into the stack in place
        set_layer = jax.jit(
            lambda stack, k, i, shape=shape, scale=scale, axis=axis:
            stack.at[i].set(_pad_to(draw(k, shape, scale), axis, lanes(I))),
            donate_argnums=0)
        stack = jnp.zeros([Le] + stored, dtype)
        for i in range(Le):
            stack = set_layer(stack, next(ks), i)
        moe[name] = stack
    params["moe_layers"] = moe
    if cfg.is_first_stage:
        params["embed"] = w((cfg.vocab_size, H), 1.0)
    if cfg.is_last_stage:
        params["final_norm"] = jnp.ones((H,), dtype)
        params["lm_head"] = w((H, cfg.vocab_size), s)
    return params


# ---------------------------------------------------------------------------
# Mixers
# ---------------------------------------------------------------------------

def _attention(lp, x, batch: StepBatch, k_cache, v_cache, cfg: ModelConfig,
               *, attn_impl, max_q_len):
    """GQA without rotary embedding, norms, gate or bias."""
    T = x.shape[0]
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # the reshapes must not be folded into the dots (models/dense.py
    # ``_attention``, docs/stacked_layers.md): a folded dot wants its
    # layer's weight cut out of the stack and transposed
    q, k, v = jax.lax.optimization_barrier(
        (qmm(x, lp["q_proj"]), qmm(x, lp["k_proj"]), qmm(x, lp["v_proj"])))
    q = q.reshape(T, Hq, D)
    k = k.reshape(T, Hkv, D)
    v = v.reshape(T, Hkv, D)
    k_cache, v_cache = write_kv(k_cache, v_cache, k, v, batch.slot_mapping)
    attn = paged_attention(q, k_cache, v_cache, batch.attn, scale=D ** -0.5,
                           max_q_len=max_q_len, impl=attn_impl)
    return qmm(attn.reshape(T, Hq * D), lp["o_proj"]), k_cache, v_cache


def _moe(lp, x, cfg: ModelConfig, valid, stacks, layer, grouped="xla"):
    """The expert layer: (output [T, H], stats [4] or None). ``grouped``:
    the grouped products as XLA's ragged_dot or as the Pallas kernel
    (models/deepseek._grouped_dot), chosen as the Mamba-2 kernels are."""
    logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    weights, ids = deepseek_route(logits, lp["e_bias"], cfg)
    if not cfg.experts_held:
        # the layer whole: every expert is held
        cfg = dataclasses.replace(cfg, experts_held=cfg.num_experts)
    combined, stats = _held_experts(lp, x, weights, ids, valid, cfg, stacks,
                                    layer, grouped)
    combined = combined + _shared_expert(lp, x, cfg.expert_act)
    return combined.astype(x.dtype), stats


def _mamba_split(mx, cfg: ModelConfig):
    """Convolution output [.., conv_dim] -> x [.., H, P], B, C [.., G, N]."""
    Nh, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N = cfg.mamba_n_groups, cfg.ssm_state_size
    lead = mx.shape[:-1]
    d = Nh * P
    return (mx[..., :d].reshape(*lead, Nh, P),
            mx[..., d:d + G * N].reshape(*lead, G, N),
            mx[..., d + G * N:].reshape(*lead, G, N))


def _mamba_recurrent_rows(xbc, dt, la, slots, conv_state, rec_state, lp,
                          cfg: ModelConfig, impl: str):
    """One new token per row: the convolution's last window and the
    recurrent step, state read from and written to ``slots`` (rows that
    are not to be written carry the dummy slot). xbc [S, conv_dim], dt /
    la [S, H]. Returns (y [S, H, P] with the skip, conv_state, rec_state)."""
    with jax.named_scope("mamba_conv"):
        buf = jnp.concatenate(
            [conv_state[slots], xbc.astype(jnp.float32)[:, None, :]], axis=1)
        out_c = jax.nn.silu(
            jnp.einsum("skc,ck->sc", buf, lp["conv_w"].astype(jnp.float32))
            + lp["conv_b"])
        conv_state = conv_state.at[slots].set(buf[:, 1:])
    x, B, C = _mamba_split(out_c, cfg)
    xdt = x * dt[..., None]
    with jax.named_scope("mamba_recurrent"):
        if impl == "pallas":
            # in place in the pool: each row's state once in, once out
            from gllm_tpu.ops.pallas.mamba2_recurrent import \
                mamba2_recurrent_step as step_in_pool
            y, rec_state = step_in_pool(
                xdt, jnp.exp(la), B, C, rec_state, slots,
                interpret=jax.default_backend() == "cpu")
        elif impl == "xla":
            y, new_r = mamba2_recurrent_step(xdt, jnp.exp(la), B, C,
                                             rec_state[slots])
            rec_state = rec_state.at[slots].set(new_r)
        else:
            raise ValueError(f"Mamba-2 impl {impl!r}: 'pallas' or 'xla'")
    return y + lp["d"][None, :, None] * x, conv_state, rec_state


def _mamba_chunk_rows(xbc, dt, la, cu, slots, dummy, conv_state, rec_state,
                      lp, cfg: ModelConfig, impl: str):
    """The rows of a mixed step with more than one new token, through the
    chunked rule in the packed layout of ``gdn_chunk_slots`` (the index
    arithmetic is ops/gdn.packed_chunks). Returns (y of every
    packed slot [N * C, H, P] with the skip, for each flat token its
    packed slot and its row [T], conv_state, rec_state)."""
    T = xbc.shape[0]
    S = slots.shape[0]
    K = lp["conv_w"].shape[-1]
    N, C = gdn_chunk_slots(T, S, cfg.ssm_chunk)
    (is_pre, ch_start, ch_end, live, row, first, tok0, n_valid, valid,
     tok) = packed_chunks(cu, T, S, N, C)

    with jax.named_scope("mamba_conv"):
        # the K-1 inputs before each chunk: the row's carried window for
        # its first chunk, the row's own tokens for a later one
        before = jnp.clip(tok0[:, None] - (K - 1)
                          + jnp.arange(K - 1)[None, :], 0, T - 1)
        prev = jnp.where(first[:, None, None], conv_state[slots[row]],
                         xbc[before].astype(jnp.float32))
        out_c, new_c = causal_conv1d(xbc[tok], prev, lp["conv_w"], n_valid,
                                     bias=lp["conv_b"])
        last = jnp.clip(ch_end - 1, 0, N - 1)    # a row's last chunk
        w_slots = jnp.where(is_pre, slots, dummy)
        conv_state = conv_state.at[w_slots].set(new_c[last])
    x, B, C_ = _mamba_split(out_c, cfg)
    xdt = jnp.where(valid[..., None, None], x * dt[tok][..., None], 0.0)
    la_s = jnp.where(valid[..., None], la[tok], 0.0)
    if impl == "pallas":
        # in place in the pool; chunks past the last row name the dummy slot
        y, rec_state = mamba2_chunk_pool(
            xdt, la_s, B, C_, jnp.where(live, slots[row], dummy), first,
            rec_state, interpret=jax.default_backend() == "cpu")
    elif impl == "xla":
        # chunks past the last row scan into a scratch row of the states
        states = jnp.concatenate(
            [rec_state[slots], jnp.zeros((1,) + rec_state.shape[1:],
                                         rec_state.dtype)], axis=0)
        y, states = mamba2_chunk_packed(
            xdt, la_s, B, C_, jnp.where(live, row, S), first, states)
        with jax.named_scope("mamba_chunk_scan"):
            rec_state = rec_state.at[w_slots].set(states[:S])
    else:
        raise ValueError(f"Mamba-2 impl {impl!r}: 'pallas' or 'xla'")
    y = y + lp["d"][None, None, :, None] * x
    slot_of_token, t_row = packed_slot_of_token(cu, ch_start, T, S, C)
    return (y.reshape((N * C,) + y.shape[2:]), slot_of_token, t_row,
            conv_state, rec_state)


def _mamba_layer(lp, u, batch: StepBatch, conv_state, rec_state,
                 cfg: ModelConfig, *, max_q_len: int, slot_base, impl: str,
                 in_scale=None):
    """One Mamba-2 mixer over the flat ragged batch. conv_state /
    rec_state: the slot pools of ALL this stage's Mamba-2 layers, layers
    and slots on one axis (views of the stacked pools); this layer's slots
    begin at ``slot_base``, its dummy slot first. ``in_scale`` (float32,
    as wide as the stored in-projection): one factor a channel of z | xBC
    | dt, applied to the projection's output (models/falcon_h1.py)."""
    T = u.shape[0]
    Nh, Din = cfg.mamba_num_heads, cfg.mamba_d_inner
    slots = batch.ssm_slots + slot_base
    # one 2-D dot reads the stack in place; the cuts stay behind a barrier
    # (see ``_attention``). Columns past z | xBC | dt are padding
    zxbcdt = jax.lax.optimization_barrier(qmm(u, lp["in_proj"]))
    conv_dim = cfg.gdn_conv_dim
    z = zxbcdt[:, :Din]
    if in_scale is not None:
        # in float32 (xBC and dt go on in float32 anyway); the gate back
        # in the stream's dtype, which the gated norm's output takes
        zxbcdt = zxbcdt.astype(jnp.float32) * in_scale
        z = zxbcdt[:, :Din].astype(u.dtype)
    xbc = zxbcdt[:, Din:Din + conv_dim]
    dt = jax.nn.softplus(
        zxbcdt[:, Din + conv_dim:Din + conv_dim + Nh].astype(jnp.float32)
        + lp["dt_bias"])
    la = -dt * jnp.exp(lp["a_log"])                      # log decay <= 0

    if max_q_len == 1:
        # pure decode: flat rows are already one-per-seq ([T == S])
        y, conv_state, rec_state = _mamba_recurrent_rows(
            xbc, dt, la, slots, conv_state, rec_state, lp, cfg, impl)
    else:
        # mixed: rows with one new token take the recurrent step, rows
        # with more the chunked rule; every other row writes the dummy slot
        cu = batch.attn.cu_q_lens
        is_dec = (cu[1:] - cu[:-1]) == 1
        head = jnp.clip(cu[:-1], 0, T - 1)       # a row's first token
        y_dec, conv_state, rec_state = _mamba_recurrent_rows(
            xbc[head], dt[head], la[head],
            jnp.where(is_dec, slots, slot_base), conv_state, rec_state, lp,
            cfg, impl)
        y_pre, slot_of_token, t_row, conv_state, rec_state = \
            _mamba_chunk_rows(xbc, dt, la, cu, slots, slot_base, conv_state,
                              rec_state, lp, cfg, impl)
        y = jnp.where(
            is_dec[t_row][:, None, None], y_dec[t_row],
            y_pre[jnp.clip(slot_of_token, 0, y_pre.shape[0] - 1)])

    with jax.named_scope("mamba_gated_norm"):
        out = rms_norm_gated_grouped(y.reshape(T, Din), z, lp["gate_norm"],
                                     cfg.rms_norm_eps, cfg.mamba_n_groups)
    return qmm(out, lp["out_proj"]), conv_state, rec_state


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params: Params, kv: NemotronKV, batch: StepBatch,
            cfg: ModelConfig, *, cos_sin, attn_impl: str = "xla",
            max_q_len: int, hidden_in=None, residual_in=None):
    del cos_sin                                  # no rotary embedding
    # the Mamba-2 kernels and the grouped product run as Pallas kernels
    # wherever the GDN layers' would (ops/gdn.gdn_impl_for)
    mamba_impl = gdn_impl_for(attn_impl, tp_sharded())
    hidden = (params["embed"][batch.token_ids] if cfg.is_first_stage
              else hidden_in + residual_in)
    valid = jnp.arange(hidden.shape[0]) < batch.attn.cu_q_lens[-1]
    with_stats = kv.stats is not None

    # the held experts' stacks stay whole (models/deepseek._held_experts)
    names = expert_stacks(cfg)
    moe_rest = params["moe_layers"]
    stacks = None
    if all(isinstance(moe_rest[k], jax.Array) for k in names):
        stacks = tuple(moe_rest[k] for k in names)
        moe_rest = {k: v for k, v in moe_rest.items() if k not in names}
    groups = {MAMBA: params["mamba_layers"], ATTN: params["attn_layers"],
              MOE: moe_rest}

    def layer_of(tree, idx):
        # one layer's leaves, cut from the stack where they are used
        # (models/hybrid.forward)
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0,
                                                   keepdims=False), tree)

    def block(carry, kind):
        h, k_all, v_all, conv_all, rec_all, stats, idx = carry
        i = idx[kind]
        lp = layer_of(groups[kind], i)
        u = rms_norm(h, lp["norm"], cfg.rms_norm_eps)
        if kind == ATTN:
            # flat-view stacked-cache addressing (models/dense._attention)
            La, P, page = k_all.shape[:3]
            batch_l = batch._replace(
                slot_mapping=batch.slot_mapping + i * (P * page),
                attn=batch.attn._replace(
                    page_table=batch.attn.page_table + i * P))
            out, kc, vc = _attention(
                lp, u, batch_l, k_all.reshape((La * P,) + k_all.shape[2:]),
                v_all.reshape((La * P,) + v_all.shape[2:]), cfg,
                attn_impl=attn_impl, max_q_len=max_q_len)
            k_all, v_all = kc.reshape(k_all.shape), vc.reshape(v_all.shape)
        elif kind == MAMBA:
            Lm, n_slots = conv_all.shape[:2]
            out, conv_f, rec_f = _mamba_layer(
                lp, u, batch,
                conv_all.reshape((Lm * n_slots,) + conv_all.shape[2:]),
                rec_all.reshape((Lm * n_slots,) + rec_all.shape[2:]),
                cfg, max_q_len=max_q_len, slot_base=i * n_slots,
                impl=mamba_impl)
            conv_all = conv_f.reshape(conv_all.shape)
            rec_all = rec_f.reshape(rec_all.shape)
        else:
            out, moe_stats = _moe(lp, u, cfg, valid, stacks, i, mamba_impl)
            if with_stats:
                stats = stats.at[2:6].add(moe_stats)
        return (h + out, k_all, v_all, conv_all, rec_all, stats,
                dict(idx, **{kind: i + 1}))

    def run(program, carry):
        for item in program:
            if isinstance(item, str):
                carry = block(carry, item)
            else:
                sub, count = item
                carry, _ = jax.lax.scan(
                    lambda c, _, sub=sub: (run(sub, c), None), carry, None,
                    length=count)
        return carry

    zero = jnp.int32(0)
    carry = (hidden, kv.k, kv.v, kv.conv, kv.rec,
             jnp.zeros((len(STATS),), jnp.int32),
             {MAMBA: zero, MOE: zero, ATTN: zero})
    hidden, k_all, v_all, conv_all, rec_all, stats, _ = run(
        layer_program(cfg.stage_layer_types), carry)
    return hidden, jnp.zeros_like(hidden), NemotronKV(
        k_all, v_all, conv_all, rec_all, stats if with_stats else None)


compute_logits = dense.compute_logits


# ---------------------------------------------------------------------------
# Checkpoint loading
# ---------------------------------------------------------------------------

def nemotron_rules(cfg: ModelConfig):
    """A ``nemotron_h`` checkpoint (transformers' NemotronHForCausalLM
    names: ``backbone.layers.N.norm`` / ``.mixer.*``) -> the stacked
    layout. Layer N maps to its index among the layers of its kind in
    THIS STAGE; other stages' layers and the routed experts this process
    does not hold are skipped."""
    first, last = cfg.stage_layers
    index, seen = {}, {MAMBA: 0, MOE: 0, ATTN: 0}
    for i in range(first, last):
        kind = cfg.layer_types[i]
        index[i] = (kind, seen[kind])
        seen[kind] += 1
    lo, held = cfg.expert_first, cfg.num_local_experts

    def conv_tf(t):         # Conv1d weight [C, 1, K] -> [C, K]
        return {"conv_w": t.reshape(t.shape[0], t.shape[-1])}

    def padded(leaf, axis):
        # [out, in] -> [in, out], zeros up to the stored width (``lanes``)
        def tf(t):
            t = t.T
            pad = [(0, 0), (0, 0)]
            pad[axis] = (0, lanes(t.shape[axis]) - t.shape[axis])
            return {leaf: np.pad(t, pad)}
        return tf

    leaves = {
        MAMBA: {"mixer.in_proj.weight": ("__multi__", padded("in_proj", 1)),
                "mixer.conv1d.bias": ("conv_b", None),
                "mixer.dt_bias": ("dt_bias", None),
                "mixer.A_log": ("a_log", None),
                "mixer.D": ("d", None),
                "mixer.norm.weight": ("gate_norm", None),
                "mixer.out_proj.weight": ("out_proj", "t")},
        ATTN: {"mixer.q_proj.weight": ("q_proj", "t"),
               "mixer.k_proj.weight": ("k_proj", "t"),
               "mixer.v_proj.weight": ("v_proj", "t"),
               "mixer.o_proj.weight": ("o_proj", "t")},
        MOE: {"mixer.gate.weight": ("router", "t"),
              "mixer.gate.e_score_correction_bias": ("e_bias", None),
              "mixer.shared_experts.up_proj.weight": ("shared_up_proj", "t"),
              "mixer.shared_experts.down_proj.weight": ("shared_down_proj",
                                                        "t")},
    }
    expert_leaves = {"up_proj.weight": padded("w_up", 1),
                     "down_proj.weight": padded("w_down", 0)}

    def rule(name: str):
        if name == "backbone.embeddings.weight":
            return (("embed",), None, None) if cfg.is_first_stage else None
        if name == "backbone.norm_f.weight":
            return (("final_norm",), None, None) if cfg.is_last_stage \
                else None
        if name == "lm_head.weight":
            return (("lm_head",), None, "t") if cfg.is_last_stage else None
        if not name.startswith("backbone.layers."):
            return None
        idx_s, _, leaf = name[len("backbone.layers."):].partition(".")
        if int(idx_s) not in index:
            return None                     # another stage's layer
        kind, li = index[int(idx_s)]
        group = _GROUP[kind]
        if leaf == "norm.weight":
            return ((group, "norm"), li, None)
        if kind == MAMBA and leaf == "mixer.conv1d.weight":
            return ((group, "__multi__"), li, conv_tf)
        if leaf in leaves[kind]:
            target, tf = leaves[kind][leaf]
            return ((group, target), li, tf)
        if kind == MOE and leaf.startswith("mixer.experts."):
            e_s, _, el = leaf[len("mixer.experts."):].partition(".")
            e = int(e_s) - lo
            if el in expert_leaves and 0 <= e < held:
                return ((group, "__multi__"), (li, e), expert_leaves[el])
        return None

    return rule


def load_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16,
                progress_cb=None) -> Params:
    from gllm_tpu.models.loader import _load_params
    template = jax.eval_shape(lambda: init_params(cfg, dtype=dtype))
    return _load_params(model_dir, template, nemotron_rules(cfg), progress_cb)
