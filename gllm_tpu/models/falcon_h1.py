"""Falcon-H1 decoder (``FalconH1ForCausalLM``, model_type falcon_h1:
tiiuae/Falcon-H1-34B-Instruct): every layer is the same block, whose
attention heads and Mamba-2 heads read ONE normed stream side by side,

    x_0 = m_e Embed[token]
    u = RMSNorm(x);  x <- x + m_so SSM(m_si u) + m_ao Attn(m_ai u)
    x <- x + MLP(RMSNorm(x));      logits = m_lm RMSNorm(x) W_head

    Attn  GQA with rotary embedding over the whole head (halves rotated),
          k = m_k (a W_k)
    SSM   Mamba-2 (ops/mamba2.py), its in-projection's output times one
          factor a channel of z | x | B | C | dt (``ssm_multipliers``)
    MLP   (silu(m_g r W_gate) * r W_up) W_down m_d

with the published muP multipliers m_* (``ModelConfig.mup``; m_lm is
``logit_scale``, applied by ``dense._head``). No multiplier is folded into
a stored weight: each is applied to an activation, where the plain
reference applies it (m_si rides the in-projection's factors: the
projection is linear).

TPU-first structure:
- a layer owns pages AND a slot: its attention half is ``dense._attention``
  over the paged pool ([L, pages, page, Hkv, D]), its state-space half
  ``nemotron_h._mamba_layer`` over the slot pools ([L, slots, ...]), both
  addressed by the one layer counter (``NemotronKV`` with La = Lm = L);
- all leaves are stacked [L, ...] and the layers run as ONE ``lax.scan``;
  every projection is a 2-D dot that reads its stack in place
  (docs/stacked_layers.md);
- a mixed step splits its rows as NemotronH's does (one new token: the
  recurrent step; more: the chunked rule in the packed layout), and the
  attention half takes the same ``batch.attn``.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from gllm_tpu.batching import StepBatch
from gllm_tpu.models import dense, nemotron_h
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.models.nemotron_h import (NemotronKV, _inverse_softplus,
                                        _mamba_layer, _pad_to, lanes)
from gllm_tpu.ops import rms_norm
from gllm_tpu.ops.attention import tp_sharded
from gllm_tpu.ops.gdn import gdn_impl_for
from gllm_tpu.ops.quant import qmm

Params = Dict[str, Any]

# rows of the embedding (columns of the head) drawn at a time by
# ``init_params``: a float32 draw of 32640 x 5120 is 0.67 GB
VOCAB_BLOCK = 32768


# pages and slots for EVERY layer: NemotronH's pools, sized by the counting
# properties (``num_attn_layers`` = ``num_linear_layers`` = the layers)
init_kv_cache = nemotron_h.init_kv_cache


def no_mesh_specs(cfg: ModelConfig, tp: int):
    raise NotImplementedError(
        "Falcon-H1 under a mesh (tp / dp / sp > 1): the Mamba-2 slot pool "
        "and its kernels are not partitioned; one chip serves its layers "
        "of a deployment")


make_rope_table = dense.make_rope_table


def _in_proj_mu(cfg: ModelConfig) -> np.ndarray:
    """``ssm_in_multiplier`` x ``ssm_multipliers``: one factor (float64) a
    column of the in-projection's z | x | B | C | dt."""
    Din, GN = cfg.mamba_d_inner, cfg.mamba_n_groups * cfg.ssm_state_size
    widths = (Din, Din, GN, GN, cfg.mamba_num_heads)
    return np.repeat(np.asarray(cfg.mup.ssm, np.float64),
                     widths) * cfg.mup.ssm_in


def in_proj_factors(cfg: ModelConfig) -> np.ndarray:
    """``_in_proj_mu`` in float32 over the in-projection as STORED: the
    columns that pad it to whole lanes take 1."""
    mu = _in_proj_mu(cfg)
    out = np.ones((lanes(mu.size),), np.float32)
    out[:mu.size] = mu
    return out


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, seed: int = 0,
                dtype=jnp.bfloat16) -> Params:
    """Seeded random weights (``--load-format dummy``), the n-th draw from
    ``fold_in(key(seed), n)``.

    Loudness. The published multipliers were trained against; against a
    1/sqrt(fan-in) draw they silence the layers (m_ao 0.0375, m_so 0.088
    and m_d 0.011 on unit-size sublayer outputs add under 2 % to a stream
    of size m_e 5.66; m_k 0.011 flattens every softmax), and a comparison
    of logits would read ``Head(Embed)`` against itself. So each matrix
    that a multiplier follows is drawn at the other families' scale
    DIVIDED by that multiplier (embedding 1 / m_e; W_q, W_v s / m_ai; W_k
    s / (m_ai m_k); W_o / m_ao; W_in's columns s / (m_si mu); W_out /
    m_so; W_gate s / m_g; W_down / m_d; head s / m_lm, s = 1 /
    sqrt(hidden)), the multipliers stay in the forward pass at their
    published values, and the stream, the scores and the logits come out
    as loud as NemotronH's. The Mamba-2 scalars are NemotronH's (``A_log``
    = log U[1, 16], ``dt_bias`` the inverse softplus of a log-uniform step
    in [0.001, 0.1], ``D`` = 1).

    Memory. No float32 draw over ~1.5 GB is in flight: the MLP stacks are
    drawn a layer at a time into their arrays, the embedding and the head
    in blocks of ``VOCAB_BLOCK`` rows of the vocabulary at most (a whole
    head's float32 draw is 5.35 GB at the published widths, beside 7.8 GB
    of leaves). perfbench/reference/falcon_h1.py draws the same."""
    L, H, D = cfg.num_stage_layers, cfg.hidden_size, cfg.head_dim
    Hq, Hkv, I = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
    Nh, Din, K = cfg.mamba_num_heads, cfg.mamba_d_inner, \
        cfg.linear_conv_kernel_dim
    conv_dim, V, m = cfg.gdn_conv_dim, cfg.vocab_size, cfg.mup
    key = jax.random.key(seed)
    ks = (jax.random.fold_in(key, i) for i in itertools.count())

    def draw(k, shape, scale):
        # the three steps kept apart: what the reference's draw rounds to
        return (jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32)) * scale).astype(dtype)

    def w(shape, scale):
        # one program a leaf; a scale a column is an operand of it
        static = (1, 2) if isinstance(scale, float) else (1,)
        return jax.jit(draw, static_argnums=static)(next(ks), shape, scale)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    def in_pieces(shape, axis, piece, scale):
        """An array of ``shape`` drawn ``piece`` indices of ``axis`` at a
        time (a key a piece), each piece put into the array in place."""
        def put(a, k, lo, n):
            part = shape[:axis] + (n,) + shape[axis + 1:]
            at = (0,) * axis + (lo,) + (0,) * (len(shape) - axis - 1)
            return jax.lax.dynamic_update_slice(a, draw(k, part, scale), at)
        put = jax.jit(put, static_argnums=3, donate_argnums=0)
        out = jnp.zeros(shape, dtype)
        for lo in range(0, shape[axis], piece):
            out = put(out, next(ks), lo, min(piece, shape[axis] - lo))
        return out

    s = H ** -0.5
    width = Din + conv_dim + Nh
    layers: Params = {
        "input_norm": jnp.ones((L, H), dtype),
        "q_proj": w((L, H, Hq * D), s / m.attention_in),
        "k_proj": w((L, H, Hkv * D), s / (m.attention_in * m.key)),
        "v_proj": w((L, H, Hkv * D), s / m.attention_in),
        "o_proj": w((L, Hq * D, H), (Hq * D) ** -0.5 / m.attention_out),
        "in_proj": _pad_to(
            w((L, H, width), (s / _in_proj_mu(cfg)).astype(np.float32)),
            -1, lanes(width)),
        "conv_w": w((L, conv_dim, K), K ** -0.5),
        "conv_b": uniform((L, conv_dim), -K ** -0.5, K ** -0.5),
        "dt_bias": _inverse_softplus(jnp.maximum(
            jnp.exp(uniform((L, Nh), jnp.log(cfg.time_step_min),
                            jnp.log(cfg.time_step_max))),
            cfg.time_step_floor)),
        "a_log": jnp.log(uniform((L, Nh), 1.0, 16.0)),
        "d": jnp.ones((L, Nh), jnp.float32),
        "gate_norm": jnp.ones((L, Din), dtype),
        "out_proj": w((L, Din, H), Din ** -0.5 / m.ssm_out),
        "pre_ff_norm": jnp.ones((L, H), dtype),
    }
    for name, shape, scale in (
            ("gate_proj", (L, H, I), s / m.mlp[0]),
            ("up_proj", (L, H, I), s),
            ("down_proj", (L, I, H), I ** -0.5 / m.mlp[1])):
        layers[name] = in_pieces(shape, 0, 1, scale)
    # one stage (any mesh is refused): both ends are here
    rows = -(-V // -(-V // VOCAB_BLOCK))        # equal blocks
    return {"layers": layers,
            "embed": in_pieces((V, H), 0, rows, 1.0 / m.embedding),
            "final_norm": jnp.ones((H,), dtype),
            "lm_head": in_pieces((H, V), 1, rows, s / cfg.logit_scale)}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _times(x, m: float):
    """``x * m`` in float32, in x's dtype; nothing where m is 1."""
    return x if m == 1.0 else (x.astype(jnp.float32) * m).astype(x.dtype)


def _mlp(lp, r, cfg: ModelConfig):
    m_g, m_d = cfg.mup.mlp
    gate = qmm(r, lp["gate_proj"]).astype(jnp.float32) * m_g
    act = (jax.nn.silu(gate)
           * qmm(r, lp["up_proj"]).astype(jnp.float32)).astype(r.dtype)
    return _times(qmm(act, lp["down_proj"]), m_d)


def forward(params: Params, kv: NemotronKV, batch: StepBatch,
            cfg: ModelConfig, *, cos_sin, attn_impl: str = "xla",
            max_q_len: int):
    # the Mamba-2 kernels run as Pallas kernels wherever the GDN layers'
    # would (ops/gdn.gdn_impl_for)
    mamba_impl = gdn_impl_for(attn_impl, tp_sharded())
    m = cfg.mup
    in_scale = jnp.asarray(in_proj_factors(cfg))
    hidden = _times(params["embed"][batch.token_ids], m.embedding)

    def layer_step(carry, lp):
        x, k_all, v_all, conv_all, rec_all, li = carry
        u = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        with jax.named_scope("par_ssm"):
            L, n_slots = conv_all.shape[:2]
            ssm, conv_f, rec_f = _mamba_layer(
                lp, u, batch,
                conv_all.reshape((L * n_slots,) + conv_all.shape[2:]),
                rec_all.reshape((L * n_slots,) + rec_all.shape[2:]),
                cfg, max_q_len=max_q_len, slot_base=li * n_slots,
                impl=mamba_impl, in_scale=in_scale)
            conv_all = conv_f.reshape(conv_all.shape)
            rec_all = rec_f.reshape(rec_all.shape)
        with jax.named_scope("par_attn"):
            attn, k_all, v_all, _, _ = dense._attention(
                lp, _times(u, m.attention_in), batch, k_all, v_all, cfg,
                cos_sin, attn_impl=attn_impl, max_q_len=max_q_len, li=li,
                k_mult=m.key)
        x = x + (ssm.astype(jnp.float32) * m.ssm_out
                 + attn.astype(jnp.float32) * m.attention_out
                 ).astype(x.dtype)
        with jax.named_scope("par_mlp"):
            x = x + _mlp(lp, rms_norm(x, lp["pre_ff_norm"],
                                      cfg.rms_norm_eps), cfg)
        return (x, k_all, v_all, conv_all, rec_all, li + 1), None

    init = (hidden, kv.k, kv.v, kv.conv, kv.rec, jnp.int32(0))
    (hidden, k_all, v_all, conv_all, rec_all, _), _ = jax.lax.scan(
        layer_step, init, params["layers"])
    return hidden, jnp.zeros_like(hidden), NemotronKV(
        k_all, v_all, conv_all, rec_all)


compute_logits = dense.compute_logits


# ---------------------------------------------------------------------------
# Checkpoint loading
# ---------------------------------------------------------------------------

def falcon_h1_rules(cfg: ModelConfig):
    """A ``falcon_h1`` checkpoint (transformers' FalconH1ForCausalLM names:
    ``model.layers.N.mamba.*`` / ``.self_attn.*`` / ``.feed_forward.*`` /
    ``.input_layernorm`` / ``.pre_ff_layernorm``) -> the stacked layout;
    other stages' layers are skipped."""
    first, last = cfg.stage_layers

    def conv_tf(t):         # Conv1d weight [C, 1, K] -> [C, K]
        return {"conv_w": t.reshape(t.shape[0], t.shape[-1])}

    def in_proj_tf(t):      # [out, in] -> [in, out], whole lanes
        t = t.T
        return {"in_proj": np.pad(
            t, [(0, 0), (0, lanes(t.shape[1]) - t.shape[1])])}

    leaves = {
        "input_layernorm.weight": ("input_norm", None),
        "pre_ff_layernorm.weight": ("pre_ff_norm", None),
        "self_attn.q_proj.weight": ("q_proj", "t"),
        "self_attn.k_proj.weight": ("k_proj", "t"),
        "self_attn.v_proj.weight": ("v_proj", "t"),
        "self_attn.o_proj.weight": ("o_proj", "t"),
        "mamba.in_proj.weight": ("__multi__", in_proj_tf),
        "mamba.conv1d.weight": ("__multi__", conv_tf),
        "mamba.conv1d.bias": ("conv_b", None),
        "mamba.dt_bias": ("dt_bias", None),
        "mamba.A_log": ("a_log", None),
        "mamba.D": ("d", None),
        "mamba.norm.weight": ("gate_norm", None),
        "mamba.out_proj.weight": ("out_proj", "t"),
        "feed_forward.gate_proj.weight": ("gate_proj", "t"),
        "feed_forward.up_proj.weight": ("up_proj", "t"),
        "feed_forward.down_proj.weight": ("down_proj", "t"),
    }

    def rule(name: str):
        if name == "model.embed_tokens.weight":
            return (("embed",), None, None)
        if name == "model.final_layernorm.weight":
            return (("final_norm",), None, None)
        if name == "lm_head.weight":
            return (("lm_head",), None, "t")
        if not name.startswith("model.layers."):
            return None
        idx_s, _, leaf = name[len("model.layers."):].partition(".")
        if not first <= int(idx_s) < last or leaf not in leaves:
            return None
        target, tf = leaves[leaf]
        return (("layers", target), int(idx_s) - first, tf)

    return rule


def load_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16,
                progress_cb=None) -> Params:
    from gllm_tpu.models.loader import _load_params
    template = jax.eval_shape(lambda: init_params(cfg, dtype=dtype))
    return _load_params(model_dir, template, falcon_h1_rules(cfg),
                        progress_cb)
