"""GDN ops vs the HF Qwen3Next torch reference math."""

import numpy as np
import pytest
import torch

import jax.numpy as jnp

from gllm_tpu.ops import gdn

hf = pytest.importorskip(
    "transformers.models.qwen3_next.modeling_qwen3_next")


def rand(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


@pytest.mark.parametrize("T,chunk", [(1, 16), (7, 4), (64, 16), (100, 32)])
def test_chunk_rule_matches_hf(T, chunk):
    rng = np.random.default_rng(0)
    S, H, Dk, Dv = 2, 3, 8, 16
    q, k = rand(rng, S, T, H, Dk), rand(rng, S, T, H, Dk)
    v = rand(rng, S, T, H, Dv)
    g = -np.abs(rand(rng, S, T, H))
    beta = 1 / (1 + np.exp(-rand(rng, S, T, H)))
    init = rand(rng, S, H, Dk, Dv)

    want, want_state = hf.torch_chunk_gated_delta_rule(
        torch.tensor(q), torch.tensor(k), torch.tensor(v),
        torch.tensor(g), torch.tensor(beta), chunk_size=chunk,
        initial_state=torch.tensor(init), output_final_state=True,
        use_qk_l2norm_in_kernel=True)

    got, got_state = gdn.chunk_gated_delta_rule(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(g),
        jnp.asarray(beta), initial_state=jnp.asarray(init),
        chunk_size=chunk)
    np.testing.assert_allclose(np.asarray(got), want.numpy(),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_state), want_state.numpy(),
                               rtol=2e-4, atol=2e-4)


def test_recurrent_step_matches_hf():
    rng = np.random.default_rng(1)
    S, H, Dk, Dv = 3, 2, 8, 16
    q, k = rand(rng, S, 1, H, Dk), rand(rng, S, 1, H, Dk)
    v = rand(rng, S, 1, H, Dv)
    g = -np.abs(rand(rng, S, 1, H))
    beta = 1 / (1 + np.exp(-rand(rng, S, 1, H)))
    init = rand(rng, S, H, Dk, Dv)

    want, want_state = hf.torch_recurrent_gated_delta_rule(
        torch.tensor(q), torch.tensor(k), torch.tensor(v),
        torch.tensor(g), torch.tensor(beta),
        initial_state=torch.tensor(init), output_final_state=True,
        use_qk_l2norm_in_kernel=True)

    got, got_state = gdn.recurrent_gated_delta_step(
        jnp.asarray(q[:, 0]), jnp.asarray(k[:, 0]), jnp.asarray(v[:, 0]),
        jnp.asarray(g[:, 0]), jnp.asarray(beta[:, 0]), jnp.asarray(init))
    np.testing.assert_allclose(np.asarray(got), want.numpy()[:, 0],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_state), want_state.numpy(),
                               rtol=2e-4, atol=2e-4)


def test_chunk_then_recurrent_continuation():
    """State handoff: chunked prefill followed by recurrent decode steps
    equals one chunked pass over the whole sequence."""
    rng = np.random.default_rng(2)
    S, T, H, Dk, Dv = 2, 20, 2, 8, 8
    q, k = rand(rng, S, T, H, Dk), rand(rng, S, T, H, Dk)
    v = rand(rng, S, T, H, Dv)
    g = -np.abs(rand(rng, S, T, H))
    beta = 1 / (1 + np.exp(-rand(rng, S, T, H)))

    full, full_state = gdn.chunk_gated_delta_rule(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(g),
        jnp.asarray(beta), chunk_size=8)

    split = 15
    part, state = gdn.chunk_gated_delta_rule(
        jnp.asarray(q[:, :split]), jnp.asarray(k[:, :split]),
        jnp.asarray(v[:, :split]), jnp.asarray(g[:, :split]),
        jnp.asarray(beta[:, :split]), chunk_size=8)
    outs = [np.asarray(part)]
    for t in range(split, T):
        o, state = gdn.recurrent_gated_delta_step(
            jnp.asarray(q[:, t]), jnp.asarray(k[:, t]),
            jnp.asarray(v[:, t]), jnp.asarray(g[:, t]),
            jnp.asarray(beta[:, t]), state)
        outs.append(np.asarray(o)[:, None])
    got = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(got, np.asarray(full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(full_state),
                               rtol=2e-4, atol=2e-4)


def test_padded_tokens_are_identity():
    """g = 0, beta = 0 rows leave the state unchanged (ragged batching)."""
    rng = np.random.default_rng(3)
    S, T, H, Dk, Dv = 1, 12, 2, 8, 8
    q, k = rand(rng, S, T, H, Dk), rand(rng, S, T, H, Dk)
    v = rand(rng, S, T, H, Dv)
    g = -np.abs(rand(rng, S, T, H))
    beta = 1 / (1 + np.exp(-rand(rng, S, T, H)))
    valid = 7
    g2 = g.copy()
    beta2 = beta.copy()
    g2[:, valid:] = 0.0
    beta2[:, valid:] = 0.0

    _, state_padded = gdn.chunk_gated_delta_rule(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(g2),
        jnp.asarray(beta2), chunk_size=4)
    _, state_exact = gdn.chunk_gated_delta_rule(
        jnp.asarray(q[:, :valid]), jnp.asarray(k[:, :valid]),
        jnp.asarray(v[:, :valid]), jnp.asarray(g[:, :valid]),
        jnp.asarray(beta[:, :valid]), chunk_size=4)
    np.testing.assert_allclose(np.asarray(state_padded),
                               np.asarray(state_exact),
                               rtol=2e-4, atol=2e-4)


def test_causal_conv1d_state_handoff():
    rng = np.random.default_rng(4)
    S, T, C, K = 2, 10, 6, 4
    x = rand(rng, S, T, C)
    w = rand(rng, C, K)
    state0 = np.zeros((S, K - 1, C), np.float32)
    q_lens = np.asarray([T, 7], np.int32)

    out, new_state = gdn.causal_conv1d(jnp.asarray(x), jnp.asarray(state0),
                                       jnp.asarray(w),
                                       jnp.asarray(q_lens))
    # torch oracle per seq (full conv over valid prefix)
    import torch.nn.functional as F
    for s, L in enumerate(q_lens):
        xs = torch.tensor(x[s, :L].T[None])           # [1, C, L]
        ref = F.conv1d(F.pad(xs, (K - 1, 0)), torch.tensor(w)[:, None, :],
                       groups=C)
        ref = F.silu(ref)[0].T.numpy()
        np.testing.assert_allclose(np.asarray(out)[s, :L], ref,
                                   rtol=1e-5, atol=1e-5)
        # state = last K-1 valid inputs
        want_state = x[s, L - (K - 1):L]
        np.testing.assert_allclose(np.asarray(new_state)[s], want_state,
                                   rtol=1e-6, atol=1e-6)

    # continuation: feed next chunk with carried state == full-seq conv
    x2 = rand(rng, S, 5, C)
    out2, _ = gdn.causal_conv1d(jnp.asarray(x2), new_state, jnp.asarray(w),
                                jnp.asarray([5, 5], np.int32))
    full = np.concatenate([x[1:2, :7], x2[1:2]], axis=1)
    ref_full = F.silu(F.conv1d(
        F.pad(torch.tensor(full.transpose(0, 2, 1)), (K - 1, 0)),
        torch.tensor(w)[:, None, :], groups=C))[0].T.numpy()
    np.testing.assert_allclose(np.asarray(out2)[1], ref_full[7:],
                               rtol=1e-5, atol=1e-5)


# (S, H, Dk, Dv): heads each alone in a slot (24 and 16 lanes, too few
# heads to fill a tile; 128 lanes, whole as they are; three heads of 192,
# which do not pair up) and heads abreast (``gdn.gdn_heads_abreast``: two
# of 192 at Olmo-Hybrid's widths, four of 32, sixteen of 24)
STATE_SHAPES = [(5, 3, 12, 24), (8, 2, 8, 16), (3, 4, 128, 128),
                (3, 4, 96, 192), (2, 3, 96, 192), (4, 8, 8, 32),
                (3, 16, 8, 24)]
ABREAST = {(3, 24): 1, (2, 16): 1, (4, 128): 1, (4, 192): 2, (3, 192): 1,
           (8, 32): 4, (16, 24): 16}


@pytest.mark.parametrize("S,H,Dk,Dv", STATE_SHAPES)
def test_state_pack_round_trip(S, H, Dk, Dv):
    """``pack_state`` lays g heads abreast along the lanes, head j g + i
    in lanes [i Dv, (i + 1) Dv) of group j, g the fewest heads whose lanes
    are whole 128-lane tiles (1 where the heads do not divide into such
    groups); ``unpack_state`` is its inverse, under any leading axes."""
    g = gdn.gdn_heads_abreast(H, Dv)
    assert g == ABREAST[H, Dv]
    assert g == 1 or (g * Dv) % 128 == 0 and ((g - 1) * Dv) % 128
    state = rand(np.random.default_rng(8), S, 2, H, Dk, Dv)
    packed = np.asarray(gdn.pack_state(jnp.asarray(state), g))
    assert packed.shape == (S, 2, H // g, Dk, g * Dv)
    for h in range(H):
        np.testing.assert_array_equal(
            packed[:, :, h // g, :, (h % g) * Dv:(h % g + 1) * Dv],
            state[:, :, h])
    np.testing.assert_array_equal(
        np.asarray(gdn.unpack_state(jnp.asarray(packed), g)), state)
    np.testing.assert_array_equal(
        np.asarray(gdn.unpack_state(jnp.asarray(packed[0, 0]), g)),
        state[0, 0])


@pytest.mark.parametrize("S,H,Dk,Dv", STATE_SHAPES)
def test_pallas_recurrent_step_matches_xla(S, H, Dk, Dv):
    """The in-place decode kernel (ops/pallas/gdn_recurrent.py, interpret
    mode on CPU) is numerically the XLA recurrent step between a gather
    and a scatter, at head dims that need not be equal or 128-aligned and
    beta up to 2, over a pool that holds the states as ``pack_state`` lays
    them; slots no row names are left as they were."""
    from gllm_tpu.ops.pallas.gdn_recurrent import gdn_recurrent_step
    rng = np.random.default_rng(5)
    P = 2 * S + 1
    n = gdn.gdn_heads_abreast(H, Dv)
    q, k = rand(rng, S, H, Dk), rand(rng, S, H, Dk)
    v = rand(rng, S, H, Dv)
    g = -np.abs(rand(rng, S, H))
    beta = rng.uniform(0.0, 2.0, (S, H)).astype(np.float32)
    pool = rand(rng, P, H, Dk, Dv)
    slots = rng.permutation(P)[:S].astype(np.int32)
    ref, ref_state = gdn.recurrent_gated_delta_step(
        *(jnp.asarray(a) for a in (q, k, v, g, beta, pool[slots])))
    got, new_pool = gdn_recurrent_step(
        gdn.l2norm(jnp.asarray(q)) * Dk ** -0.5, gdn.l2norm(jnp.asarray(k)),
        jnp.asarray(v), jnp.asarray(g), jnp.asarray(beta),
        gdn.pack_state(jnp.asarray(pool), n), jnp.asarray(slots),
        interpret=True)
    assert new_pool.shape == (P, H // n, Dk, n * Dv)
    new_pool = np.asarray(gdn.unpack_state(new_pool, n))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new_pool[slots], np.asarray(ref_state),
                               rtol=1e-5, atol=1e-5)
    rest = np.setdiff1d(np.arange(P), slots)
    np.testing.assert_array_equal(new_pool[rest], pool[rest])


def test_pallas_kernels_refuse_a_pool_that_cannot_hold_the_heads():
    """The kernels read how many heads lie abreast from the pool's shape:
    a pool whose lanes are no whole number of heads' Dv, or whose groups
    do not add up to the heads, is refused, not read askew."""
    from gllm_tpu.ops.pallas.gdn_recurrent import gdn_recurrent_step
    from gllm_tpu.ops.pallas.gdn_scan import gdn_chunk_scan
    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    S, H, Dk, Dv, P, N, C = 2, 4, 8, 32, 3, 2, 4
    for bad in ((P, 2, Dk, 80), (P, 1, Dk, 2 * Dv), (P, 2, 2 * Dk, 2 * Dv)):
        with pytest.raises(ValueError, match="pack_state"):
            gdn_recurrent_step(z(S, H, Dk), z(S, H, Dk), z(S, H, Dv),
                               z(S, H), z(S, H), z(*bad),
                               jnp.zeros(S, jnp.int32), interpret=True)
        with pytest.raises(ValueError, match="pack_state"):
            gdn_chunk_scan(z(H, N, C, Dk), z(H, N, Dk, C), z(H, N, C, Dv),
                           z(H, N, C, Dk), z(H, N, C, C), z(H, N, 1, Dv),
                           z(*bad), jnp.zeros(N, jnp.int32),
                           jnp.zeros(N, jnp.int32), interpret=True)


def test_pallas_recurrent_step_rows_on_the_dummy_slot_are_harmless():
    """Padding rows all name slot 0: whatever they leave there, the rows
    with slots of their own are what they are without them."""
    from gllm_tpu.ops.pallas.gdn_recurrent import gdn_recurrent_step
    rng = np.random.default_rng(6)
    S, H, Dk, Dv, P = 6, 2, 8, 16, 5
    q, k, v = rand(rng, S, H, Dk), rand(rng, S, H, Dk), rand(rng, S, H, Dv)
    g = -np.abs(rand(rng, S, H))
    beta = rng.uniform(0.0, 2.0, (S, H)).astype(np.float32)
    pool = rand(rng, P, H, Dk, Dv)      # two heads of 16: each alone
    slots = np.array([0, 3, 0, 1, 0, 4], np.int32)
    args = [jnp.asarray(a) for a in (q, k, v, g, beta)]
    got, new_pool = gdn_recurrent_step(*args, jnp.asarray(pool),
                                       jnp.asarray(slots), interpret=True)
    real = np.array([1, 3, 5])
    want, want_pool = gdn_recurrent_step(
        *(a[real] for a in args), jnp.asarray(pool),
        jnp.asarray(slots[real]), interpret=True)
    np.testing.assert_array_equal(np.asarray(got)[real], np.asarray(want))
    np.testing.assert_array_equal(np.asarray(new_pool)[[1, 3, 4]],
                                  np.asarray(want_pool)[[1, 3, 4]])
    assert np.isfinite(np.asarray(new_pool)).all()


def _packed_case(rng, lens, H, Dk, Dv, C, spare=2):
    """Sequences of ``lens`` tokens cut into chunks of ``C`` and laid end
    to end (+ ``spare`` dead chunks), as a mixed step lays its prefilling
    rows: (q, k, v, g, beta, row, first)."""
    n_ch = [-(-n // C) for n in lens]
    N = sum(n_ch) + spare
    q, k = rand(rng, N, C, H, Dk), rand(rng, N, C, H, Dk)
    v = rand(rng, N, C, H, Dv)
    g = -np.abs(rand(rng, N, C, H))
    beta = rng.uniform(0.0, 2.0, (N, C, H)).astype(np.float32)
    row = np.full(N, len(lens), np.int32)
    first = np.zeros(N, bool)
    c0 = 0
    for r, n in enumerate(lens):
        pad = np.arange(n_ch[r] * C).reshape(n_ch[r], C) >= n
        g[c0:c0 + n_ch[r]][pad] = 0.0
        beta[c0:c0 + n_ch[r]][pad] = 0.0
        row[c0:c0 + n_ch[r]] = r
        first[c0] = True
        c0 += n_ch[r]
    g[c0:], beta[c0:] = 0.0, 0.0
    return q, k, v, g, beta, row, first


@pytest.mark.parametrize("lens,chunk,H,Dk,Dv", [
    ([7], 4, 3, 12, 24), ([64, 20, 33], 16, 3, 12, 24),
    ([100, 1, 31], 32, 3, 12, 24), ([64, 20, 33], 16, 4, 96, 192),
    ([7], 4, 2, 128, 128), ([100, 1, 31], 32, 3, 96, 192),
    ([64, 20, 33], 16, 8, 8, 32)])
def test_pallas_scan_matches_xla(lens, chunk, H, Dk, Dv):
    """The fused VMEM-scan kernel (ops/pallas/gdn_scan.py, interpret mode
    on CPU), in place in the slot pool over the packed layout, is
    numerically the XLA chunk scan between a gather and a scatter, at head
    dims that are neither equal nor 128-aligned and beta up to 2, with the
    heads each alone in a slot or abreast (``STATE_SHAPES``); slots no
    chunk names are left as they were."""
    rng = np.random.default_rng(3)
    P = 9
    n = gdn.gdn_heads_abreast(H, Dv)
    q, k, v, g, beta, row, first = _packed_case(rng, lens, H, Dk, Dv, chunk)
    R = len(lens)
    pool = rand(rng, P, H, Dk, Dv)
    slots = rng.permutation(np.arange(1, P))[:R].astype(np.int32)
    states = np.concatenate([pool[slots], np.zeros((1, H, Dk, Dv),
                                                   np.float32)])
    args = [jnp.asarray(a) for a in (q, k, v, g, beta)]
    ref, ref_states = gdn.chunk_gated_delta_rule_packed(
        *args, jnp.asarray(row), jnp.asarray(first), jnp.asarray(states))
    slot = np.where(row < R, slots[np.minimum(row, R - 1)], 0)
    got, new_pool = gdn.chunk_gated_delta_rule_pool(
        *args, jnp.asarray(slot, jnp.int32), jnp.asarray(first),
        gdn.pack_state(jnp.asarray(pool), n), interpret=True)
    assert new_pool.shape == (P, H // n, Dk, n * Dv)
    new_pool = np.asarray(gdn.unpack_state(new_pool, n))
    live = row < R
    assert np.abs(np.asarray(ref)[live]).max() > 0.1
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new_pool[slots], np.asarray(ref_states)[:R],
                               rtol=1e-5, atol=1e-5)
    rest = np.setdiff1d(np.arange(1, P), slots)
    np.testing.assert_array_equal(new_pool[rest], pool[rest])
    assert np.isfinite(new_pool).all()


@pytest.mark.parametrize("H,Dk,Dv", [(2, 8, 8), (4, 96, 192), (2, 128, 128),
                                     (3, 96, 192)])
def test_pallas_chunks_then_recurrent_steps_through_one_pool(H, Dk, Dv):
    """A prompt's chunks leave in its slot the state that its decode steps
    read: the scan kernel and then the recurrent kernel, both in place in
    ONE pool (heads alone or abreast), give what the float32 recurrent
    rule gives token by token; the sequence beside it, which only
    decodes, and the slots neither names are what they would be alone."""
    from gllm_tpu.ops.pallas.gdn_recurrent import gdn_recurrent_step
    rng = np.random.default_rng(9)
    C, P, n_pre, n_dec = 8, 5, 13, 3
    n = gdn.gdn_heads_abreast(H, Dv)
    q, k, v, g, beta, row, first = _packed_case(rng, [n_pre], H, Dk, Dv, C,
                                                spare=1)
    pool = rand(rng, P, H, Dk, Dv)
    own, other = 3, 1                   # the prompt's slot, a decoder's
    dq, dk_ = rand(rng, n_dec, 2, H, Dk), rand(rng, n_dec, 2, H, Dk)
    dv_ = rand(rng, n_dec, 2, H, Dv)
    dg = -np.abs(rand(rng, n_dec, 2, H))
    dbeta = rng.uniform(0.0, 2.0, (n_dec, 2, H)).astype(np.float32)

    # the reference: one token at a time, states as [H, Dk, Dv]
    flat = lambda a: a[:2].reshape((2 * C,) + a.shape[2:])[:n_pre]
    st = {own: jnp.asarray(pool[own])[None],
          other: jnp.asarray(pool[other])[None]}
    want_pre = []
    for t in range(n_pre):
        o, st[own] = gdn.recurrent_gated_delta_step(
            *(jnp.asarray(flat(a)[t][None]) for a in (q, k, v, g, beta)),
            st[own])
        want_pre.append(np.asarray(o[0]))
    want_dec = np.zeros((n_dec, 2, H, Dv), np.float32)
    for t in range(n_dec):
        for r, s in enumerate((own, other)):
            o, st[s] = gdn.recurrent_gated_delta_step(
                *(jnp.asarray(a[t, r][None])
                  for a in (dq, dk_, dv_, dg, dbeta)), st[s])
            want_dec[t, r] = np.asarray(o[0])

    packed = gdn.pack_state(jnp.asarray(pool), n)
    slot = np.where(row < 1, own, 0).astype(np.int32)
    got_pre, packed = gdn.chunk_gated_delta_rule_pool(
        *(jnp.asarray(a) for a in (q, k, v, g, beta)), jnp.asarray(slot),
        jnp.asarray(first), packed, interpret=True)
    tol = dict(rtol=2e-4, atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(got_pre[:2]).reshape(-1, H, Dv)[:n_pre],
        np.stack(want_pre), **tol)
    for t in range(n_dec):
        got, packed = gdn_recurrent_step(
            gdn.l2norm(jnp.asarray(dq[t])) * Dk ** -0.5,
            gdn.l2norm(jnp.asarray(dk_[t])), jnp.asarray(dv_[t]),
            jnp.asarray(dg[t]), jnp.asarray(dbeta[t]), packed,
            jnp.asarray([own, other], jnp.int32), interpret=True)
        np.testing.assert_allclose(np.asarray(got), want_dec[t], **tol)
    after = np.asarray(gdn.unpack_state(packed, n))
    for s in (own, other):
        np.testing.assert_allclose(after[s], np.asarray(st[s][0]), **tol)
    np.testing.assert_array_equal(after[[2, 4]], pool[[2, 4]])


def test_pallas_scan_ragged_padding():
    """Padded tokens (g=0, beta=0) are the identity on the state through
    the kernel: a sequence's state is what the recurrent step leaves after
    its real tokens, whatever follows them in its last chunk and in the
    dead chunks behind it (which only touch the dummy slot 0)."""
    rng = np.random.default_rng(4)
    H, Dk, Dv, C, P = 2, 8, 8, 8, 4
    lens = [20, 13]
    q, k, v, g, beta, row, first = _packed_case(rng, lens, H, Dk, Dv, C,
                                                spare=3)
    pool = rand(rng, P, H, Dk, Dv)
    slots = np.array([2, 1], np.int32)
    slot = np.where(row < 2, slots[np.minimum(row, 1)], 0)
    got, new_pool = gdn.chunk_gated_delta_rule_pool(
        *(jnp.asarray(a) for a in (q, k, v, g, beta)),
        jnp.asarray(slot, jnp.int32), jnp.asarray(first),
        jnp.asarray(pool), interpret=True)
    c0 = 0
    for r, n in enumerate(lens):
        n_ch = -(-n // C)
        tok = lambda a: jnp.asarray(
            a[c0:c0 + n_ch].reshape((n_ch * C,) + a.shape[2:])[:n])
        state = jnp.asarray(pool[slots[r]])[None]
        outs = []
        for t in range(n):
            o, state = gdn.recurrent_gated_delta_step(
                tok(q)[t][None], tok(k)[t][None], tok(v)[t][None],
                tok(g)[t][None], tok(beta)[t][None], state)
            outs.append(np.asarray(o[0]))
        np.testing.assert_allclose(
            np.asarray(got[c0:c0 + n_ch]).reshape(-1, H, Dv)[:n],
            np.stack(outs), rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(new_pool)[slots[r]],
                                   np.asarray(state[0]),
                                   rtol=2e-4, atol=2e-5)
        c0 += n_ch
    np.testing.assert_array_equal(np.asarray(new_pool)[3], pool[3])
