"""``ops/gated_delta_rule.py`` (interpreted): the chunked kernels against the
token-by-token recurrence, output and all five gradients (the hand-written
backward against autodiff of the scan); the state's running maximum; strong
decay; float32 state under bfloat16 inputs; which key head a value head reads;
the triangular solve; what the schedule says a call holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops import gated_delta_rule as gdr
from ps_pytorch_tpu.ops.gated_delta_rule import (
    gated_delta_rule, gated_delta_rule_reference, gdr_schedule,
)


def _rule_inputs(seed=0, b=2, s=100, hk=2, hv=4, dk=16, dv=16, gate=0.1):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, s, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, hk, dk)))
    v = jax.random.normal(ks[2], (b, s, hv, dv))
    g = -gate * jax.nn.softplus(jax.random.normal(ks[3], (b, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    return q, k, v, g, beta


def _agrees(args, tol=2e-5):
    """The chunked kernels (interpreted) against the recurrence: output and
    all five gradients, the hand-written backward against autodiff of the
    token-by-token scan."""
    o, state_max = gated_delta_rule(*args)
    want, last = gated_delta_rule_reference(*args)
    assert o.shape == want.shape and bool(jnp.isfinite(o).all())
    assert float(jnp.abs(o - want).max()) < tol
    assert float(state_max) >= float(jnp.abs(last).max()) * (1 - 1e-5)
    w = jax.random.normal(jax.random.key(9), want.shape)
    got = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a)[0] * w),
                   argnums=tuple(range(5)))(*args)
    ref = jax.grad(lambda *a: jnp.sum(gated_delta_rule_reference(*a)[0] * w),
                   argnums=tuple(range(5)))(*args)
    for name, a, r in zip(("q", "k", "v", "g", "beta"), got, ref):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - r).max()) \
            < tol * max(float(jnp.abs(r).max()), 1.0), name


@pytest.mark.parametrize("s", [37, 64, 100, 256, 576, 704],
                         ids=lambda s: f"S_{s}")
def test_chunked_rule_agrees_with_the_recurrence(s):
    """S under one chunk, one chunk, S that is no whole number of chunks (the
    tail is padded with tokens the state passes unchanged), four chunks; then
    the two where the walk crosses grid steps, so that the states and ``dS``
    pass through the kernels' scratch: nine chunks (``group`` 3, three grid
    steps) and eleven (``group`` 1, eleven)."""
    groups = {576: (3, 3), 704: (1, 11)}
    if s in groups:
        assert gdr_schedule(1, s, 4, 16, 16, k_heads=2)[2:4] \
            == (groups[s][0], (2, groups[s][1]))
    _agrees(_rule_inputs(s=s, b=1 if s >= 256 else 2))


@pytest.mark.parametrize("s", [128, 160, 200], ids=lambda s: f"S_{s}")
def test_state_maximum_is_the_largest_state_at_a_chunk_boundary(s):
    """The counter is the kernel's running maximum beside the state: the
    largest |S| over the states the recurrence holds after every 64 tokens
    and after the last one (two whole chunks; a third that is half padding;
    at S = 200 values are written in the second chunk alone, so the largest
    state stands at a middle boundary and the last is the smallest)."""
    args = _rule_inputs(s=s, b=1)
    if s == 200:
        written = (jnp.arange(s) >= 64) & (jnp.arange(s) < 128)
        args = args[:4] + (args[4] * written[None, :, None],)
    _, state_max = gated_delta_rule(*args)
    ends = sorted(set(range(gdr.CHUNK, s, gdr.CHUNK)) | {s})
    tops = [float(jnp.abs(gated_delta_rule_reference(
        *(a[:, :n] for a in args))[1]).max()) for n in ends]
    assert float(state_max) == pytest.approx(max(tops), rel=1e-5)
    if s == 200:
        assert tops.index(max(tops)) == 1 and tops[-1] < 0.5 * max(tops)


def test_strong_decay_is_finite_and_equal():
    """A = 16 and a softplus of 1.31 (dt_bias = 1): g = -21 a token, a chunk's
    total decay exp(-1344), whose inverse float32 does not hold: every
    exponent the chunked form takes is <= 0."""
    q, k, v, g, beta = _rule_inputs(s=160)
    g = jnp.full_like(g, -16.0 * float(jax.nn.softplus(1.0)))
    assert float(jnp.sum(g[0, :64, 0])) < -1300
    _agrees((q, k, v, g, beta))


def test_beta_zero_only_decays_and_no_gate_is_the_plain_delta_rule():
    q, k, v, g, beta = _rule_inputs(s=100)
    o, state_max = gated_delta_rule(q, k, v, g, jnp.zeros_like(beta))
    assert float(jnp.abs(o).max()) == 0.0 == float(state_max)
    # g = 0, beta = 1: S <- S + k (v - S^T k)^T, after which S^T k_t = v_t
    args = (k, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    o, _ = gated_delta_rule(*args)
    np.testing.assert_allclose(o, v, atol=2e-5)     # reads back what it wrote
    _agrees((q, k, v, jnp.zeros_like(g), jnp.ones_like(beta)))


def test_state_is_float32_under_bfloat16_inputs():
    q, k, v, g, beta = _rule_inputs(b=1, s=128)
    bf = lambda t: t.astype(jnp.bfloat16)
    o, _ = gated_delta_rule(bf(q), bf(k), bf(v), g, beta)
    assert o.dtype == jnp.bfloat16
    want, _ = gated_delta_rule_reference(bf(q), bf(k), bf(v), g, beta)
    # the operands' and the output's rounding, chunk by chunk; a state carried
    # in bfloat16 would lose 2^-8 of itself at each of the boundaries as well
    err = float(jnp.abs(o.astype(jnp.float32) - want).max())
    assert err < 2 ** -6 * float(jnp.abs(want).max())
    hs = _kept_states(bf(q), bf(k), bf(v), g, beta)
    assert hs.dtype == jnp.float32
    assert float(jnp.abs(hs - hs.astype(jnp.bfloat16).astype(jnp.float32))
                 .max()) > 0        # it holds more bits than bfloat16 has


def test_bfloat16_gradients_stay_near_the_float32_recurrence():
    """bfloat16 q, k, v under float32 g and beta, two chunks: all five
    gradients of the kernels against autodiff of the float32 recurrence on
    the same (rounded) inputs. What is rounded on the way: the matmuls'
    operands (``T``, ``beta v``, ``beta exp(gamma) k``, the state's copy,
    ``V'``, the gradients that feed the MXU) and the gradients handed out in
    bfloat16, each 2^-9 of its size, through two chunks of 64: 2^-6 of a
    gradient's largest entry, the limit the output has in the test above,
    holds all five with room (read 0.2-0.7% over three seeds; the gates'
    gradients are float32 and are held to the same)."""
    q, k, v, g, beta = _rule_inputs(b=1, s=128)
    bf = lambda t: t.astype(jnp.bfloat16)
    args = (bf(q), bf(k), bf(v), g, beta)
    w = jax.random.normal(jax.random.key(9), v.shape)
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0].astype(jnp.float32) * w)
    got = jax.grad(loss(gated_delta_rule), argnums=tuple(range(5)))(*args)
    ref = jax.grad(loss(gated_delta_rule_reference),
                   argnums=tuple(range(5)))(*args)
    for name, a, r in zip(("q", "k", "v", "g", "beta"), got, ref):
        assert a.dtype == (jnp.float32 if name in ("g", "beta")
                           else jnp.bfloat16), name
        a, r = a.astype(jnp.float32), r.astype(jnp.float32)
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - r).max()) \
            < 2 ** -6 * float(jnp.abs(r).max()), name


def _kept_states(q, k, v, g, beta):
    """The entering states the forward keeps for the backward."""
    _, vjp = jax.vjp(lambda *a: gated_delta_rule(*a)[0], q, k, v, g, beta)
    kept = [a for a in jax.tree.leaves(vjp) if getattr(a, "ndim", 0) == 4
            and a.shape[-2:] == (k.shape[-1], v.shape[-1])]
    assert len(kept) == 1
    return kept[0]


def test_value_heads_2j_and_2j_plus_1_read_key_head_j():
    """``repeat_interleave``, not ``tile``: with four value heads on two key
    heads, value head 1 reads key head 0 (a tile would hand it key head 1)."""
    q, k, v, g, beta = _rule_inputs(s=64)
    o, _ = gated_delta_rule(q, k, v, g, beta)
    want, _ = gated_delta_rule_reference(q, k, v, g, beta)
    tiled, _ = gated_delta_rule_reference(
        jnp.tile(q, (1, 1, 2, 1)), jnp.tile(k, (1, 1, 2, 1)), v, g, beta)
    assert float(jnp.abs(o - want).max()) < 2e-5
    assert float(jnp.abs(o - tiled)[:, :, 1:3].max()) > 0.05
    np.testing.assert_allclose(o[:, :, (0, 3)], tiled[:, :, (0, 3)],
                               atol=2e-5)


def test_the_triangular_inverse_is_forward_substitution():
    """What solves a chunk's system (``gdr_solve``: ``X`` formed from a
    chunk's k rows, gamma and beta, the chunks along the lanes, forward
    substitution, and back) against a dense inverse of ``I + X`` built here
    from the same rows, on random systems (three chunks of two key heads,
    two value heads each: twelve systems beside the padded ones, which must
    come back as the identity) and where every key is the same (X all ones
    under the diagonal: its inverse is bidiagonal, and a product of powers
    of X would lose every digit)."""
    c, dk, n, hk, r = 64, 16, 3, 2, 2
    strict = jnp.tril(jnp.ones((c, c)), -1)
    ks = jax.random.split(jax.random.key(0), 3)
    k = jax.random.normal(ks[0], (hk, n * c, dk)) * 0.5
    g = -0.05 * jax.nn.softplus(jax.random.normal(ks[1], (hk * r, n, c)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (hk * r, n, c)))
    gam = jnp.cumsum(g, axis=-1)

    def systems(t):         # [Hk, n' C, r C] -> [Hk, r, n', C, C]
        return t.reshape(hk, -1, c, r, c).transpose(0, 3, 1, 2, 4)

    t = systems(gdr._solved(k, gam, beta, True))
    kc = k.reshape(hk, 1, n, c, dk)
    kk = jnp.einsum("hrncd,hrnkd->hrnck", kc, kc, precision="highest")
    gm, bt = gam.reshape(hk, r, n, c), beta.reshape(hk, r, n, c)
    x = strict * bt[..., None] * kk \
        * jnp.exp(strict * (gm[..., :, None] - gm[..., None, :]))
    assert float(jnp.abs(x).max()) > 1.0        # no easy systems
    np.testing.assert_allclose(t[:, :, :n], jnp.linalg.inv(jnp.eye(c) + x),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(
        t[:, :, n:], jnp.broadcast_to(jnp.eye(c), t[:, :, n:].shape))
    same = jnp.zeros((hk, n * c, dk)).at[..., 0].set(1.0)
    ones = systems(gdr._solved(same, jnp.zeros_like(gam),
                               jnp.ones_like(beta), True))[:, :, :n]
    np.testing.assert_array_equal(
        ones, jnp.broadcast_to(jnp.eye(c) - jnp.eye(c, k=-1), ones.shape))
    # and its derivative is -T^T dT T^T under the mask
    x, t = x.reshape(-1, c, c), t[:, :, :n].reshape(-1, c, c)
    w = jax.random.normal(jax.random.key(1), x.shape)
    got = jax.vmap(gdr._solve_pullback)(t, w)
    ref = jax.grad(lambda a: jnp.sum(
        jnp.linalg.inv(jnp.eye(c) + a * strict) * w))(x)
    np.testing.assert_allclose(got, ref * strict, atol=2e-4, rtol=1e-3)


def test_schedule_says_what_a_call_holds():
    sc = gdr_schedule(1, 16384, 32, 128, 128)
    assert (sc.chunk, sc.chunks, sc.group, sc.grid) == (64, 256, 8, (32, 32))
    assert sc.kept_bytes == 32 * 256 * 128 * 128 * 4      # 512 MiB a layer
    assert "chunk=64 chunks=256 group=8 grid=32x32" in sc.describe()
    # the cell's calls: 16 key heads, two value heads a grid step, bfloat16
    # rows. gdr_solve reads k (64 MiB), gamma and beta (2 each) and writes T
    # (128, float32); a forward call reads q, k a key head (64 MiB each), v
    # (128), gamma, beta, T and a decay a chunk (32 KiB), and writes o (128)
    # and the entering states; a backward call reads those, the states and
    # dO and writes the five gradients. Nothing but the states is kept
    # beside the inputs.
    sc = gdr_schedule(1, 16384, 32, 128, 128, k_heads=16, itemsize=2)
    mib, lam = 2 ** 20, 32 * 2 ** 10
    assert (sc.grid, sc.heads_a_step, sc.solve_grid) == ((16, 32), 2, (16, 2))
    assert (sc.kept_bytes, sc.kept_other_bytes) == (512 * mib, 0)
    assert sc.solve_bytes == (64 + 4 + 128) * mib
    assert sc.fwd_bytes == (2 * 64 + 2 * 128 + 4 + 128 + 512) * mib + lam
    assert sc.bwd_bytes == (4 * 64 + 3 * 128 + 8 + 128 + 512) * mib + lam
    assert sc.describe().endswith(
        f"heads=2 solve_grid=16x2 kept={512 * mib}+0 solve_bytes={196 * mib} "
        f"fwd_bytes={1028 * mib + lam} bwd_bytes={1288 * mib + lam}")
    # a short sequence is solved as one whole block of 128 chunks
    assert gdr_schedule(2, 100, 4, 16, 16, k_heads=2).solve_grid == (4, 1)
    assert gdr_schedule(2, 100, 4, 16, 16).chunks == 2
    with pytest.raises(ValueError, match="multiple of Hk"):
        gated_delta_rule(*_rule_inputs(hk=3, hv=4, s=8))
