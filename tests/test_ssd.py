"""``ops/ssd.py`` (interpreted): the chunked state-space-dual kernels against
Mamba-2's token-by-token recurrence, output and all six gradients (the
hand-written backward against autodiff of the scan); lengths that are no whole
number of chunks; heads that share a slab's lanes and heads that fill one;
which group a head reads; the state's running maximum; strong decay; float32
state under bfloat16 inputs; what the schedule says a call holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops.ssd import CHUNK, ssd, ssd_reference, ssd_schedule

NAMES = ("x", "dt", "A", "B", "C", "D")


def _inputs(seed=0, b=2, s=100, heads=4, p=8, groups=2, n=16, a_max=2.7,
            dtype=jnp.float32):
    """As the layer hands them over: dt a softplus, A = -exp(A_log) with A_log
    uniform (to ``exp(2.7)`` = 15, about the initialiser's 16)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, s, heads, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=a_max))
    bb = (0.5 * jax.random.normal(ks[3], (b, s, groups, n))).astype(dtype)
    cc = (0.5 * jax.random.normal(ks[4], (b, s, groups, n))).astype(dtype)
    d = jax.random.normal(ks[5], (heads,))
    return x, dt, a, bb, cc, d


def _agrees(args, chunk, tol=3e-5):
    """The chunked kernels (interpreted) against the recurrence: output and
    all six gradients."""
    y, state_max = ssd(*args, chunk=chunk)
    want, last = ssd_reference(*args)
    assert y.shape == want.shape and bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y - want).max()) \
        < tol * max(float(jnp.abs(want).max()), 1.0)
    assert float(state_max) >= float(jnp.abs(last).max()) * (1 - 1e-5)
    w = jax.random.normal(jax.random.key(9), want.shape)
    got = jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=chunk)[0] * w),
                   argnums=tuple(range(6)))(*args)
    ref = jax.grad(lambda *a: jnp.sum(ssd_reference(*a)[0] * w),
                   argnums=tuple(range(6)))(*args)
    for name, a, r in zip(NAMES, got, ref):
        assert a.shape == r.shape and bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - r).max()) \
            < tol * max(float(jnp.abs(r).max()), 1.0), name


@pytest.mark.parametrize("s,chunk", [(11, 16), (40, 16), (144, 16),
                                     (176, 16), (200, CHUNK)],
                         ids=lambda v: str(v))
def test_chunked_form_agrees_with_the_recurrence(s, chunk):
    """S under one chunk, S that is no whole number of chunks (the tail is
    padded with tokens the state passes unchanged); then the two where the
    walk crosses grid steps, so that the states and ``dh`` pass through the
    kernels' scratch: nine chunks (``group`` 3, three grid
    steps) and eleven (``group`` 1, eleven); and the published chunk of 128,
    one and a half of them."""
    steps = {144: (3, 3), 176: (1, 11)}
    if s in steps:
        sched = ssd_schedule(1, s, 4, 8, 16, 2, chunk=chunk)
        assert (sched.group, sched.grid) == (steps[s][0], (2, steps[s][1]))
    _agrees(_inputs(s=s, b=1 if s >= 144 else 2), chunk)


@pytest.mark.parametrize("heads,p,groups,slab", [(8, 64, 2, 2), (2, 128, 1, 1),
                                                 (6, 32, 2, 3), (4, 8, 4, 1)])
def test_heads_share_a_slab_of_lanes_or_fill_one(heads, p, groups, slab):
    """The cell's 64-wide heads go two a slab (four heads a group here: two
    slabs a grid step); 128-wide heads one; 32-wide heads three where a group
    has three (four would fit and do not divide it); one head a group."""
    assert ssd_schedule(1, 48, heads, p, 16, groups,
                        chunk=16).heads_a_slab == slab
    _agrees(_inputs(b=1, s=48, heads=heads, p=p, groups=groups), 16)


def test_head_i_reads_group_i_over_r():
    """``repeat``, not ``tile``: with four heads on two groups, head 1 reads
    group 0 (a tile would hand it group 1)."""
    x, dt, a, bb, cc, d = _inputs(s=32, b=1)
    y, _ = ssd(x, dt, a, bb, cc, d, chunk=16)
    per_head = lambda t: jnp.repeat(t, 2, axis=2)
    alone, _ = ssd(x, dt, a, per_head(bb), per_head(cc), d, chunk=16)
    np.testing.assert_allclose(y, alone, atol=2e-6)
    tiled = lambda t: jnp.tile(t, (1, 1, 2, 1))
    wrong, _ = ssd(x, dt, a, tiled(bb), tiled(cc), d, chunk=16)
    assert float(jnp.abs(y - wrong)[:, :, 1].max()) > 0.1


@pytest.mark.parametrize("s", [32, 40, 50], ids=lambda s: f"S_{s}")
def test_state_maximum_is_the_largest_state_at_a_chunk_boundary(s):
    """The counter is the kernel's running maximum beside the state: the
    largest |h| over the states the recurrence holds after every chunk of 16
    and after the last token (at S = 50 x is written in the second chunk
    alone, so the largest state stands at a middle boundary)."""
    args = _inputs(s=s, b=1)
    if s == 50:
        written = (jnp.arange(s) >= 16) & (jnp.arange(s) < 32)
        args = (args[0] * written[None, :, None, None],) + args[1:]
    _, state_max = ssd(*args, chunk=16)
    ends = sorted(set(range(16, s, 16)) | {s})
    cut = lambda n: tuple(t[:, :n] if t.ndim > 1 else t for t in args)
    tops = [float(jnp.abs(ssd_reference(*cut(n))[1]).max()) for n in ends]
    assert float(state_max) == pytest.approx(max(tops), rel=1e-5)
    if s == 50:
        assert tops.index(max(tops)) == 1


def test_strong_decay_is_finite_and_equal():
    """A = -16 under a step of 1.31 (a softplus of 1): -21 a token, a chunk's
    total decay exp(-336) at 16 tokens, whose inverse float32 does not hold:
    every exponent the chunked form takes is <= 0."""
    x, dt, a, bb, cc, d = _inputs(s=40)
    dt = jnp.full_like(dt, float(jax.nn.softplus(1.0)))
    a = jnp.full_like(a, -16.0)
    assert float(jnp.sum(dt[0, :16, 0]) * a[0]) < -300
    _agrees((x, dt, a, bb, cc, d), 16)


def test_no_step_passes_the_state_on_and_no_decay_is_a_running_sum():
    x, dt, a, bb, cc, d = _inputs(s=40)
    y, state_max = ssd(x, jnp.zeros_like(dt), a, bb, cc, d, chunk=16)
    np.testing.assert_allclose(y, d[:, None] * x, atol=1e-6)   # D x alone
    assert float(state_max) == 0.0
    # A -> 0: h_t = sum_{j <= t} dt_j x_j B_j^T, no decay
    y, _ = ssd(x, dt, 0.0 * a, bb, cc, 0.0 * d, chunk=16)
    r = x.shape[2] // bb.shape[2]
    scores = jnp.einsum("bign,bjgn->bgij", cc, bb)
    mask = jnp.tril(jnp.ones((x.shape[1],) * 2))
    want = jnp.einsum("bhij,bjhp->bihp",
                      jnp.repeat(scores, r, axis=1) * mask,
                      dt[..., None] * x)
    np.testing.assert_allclose(y, want, atol=3e-5)


def _kept_states(args, chunk):
    """The entering states the forward keeps for the backward."""
    _, vjp = jax.vjp(lambda *a: ssd(*a, chunk=chunk)[0], *args)
    kept = [a for a in jax.tree.leaves(vjp) if getattr(a, "ndim", 0) == 5]
    assert len(kept) == 1
    return kept[0]


def test_state_is_float32_under_bfloat16_inputs():
    x, dt, a, bb, cc, d = _inputs(b=1, s=64, dtype=jnp.bfloat16)
    args = (x, dt, a, bb, cc, d)
    y, _ = ssd(*args, chunk=16)
    assert y.dtype == jnp.bfloat16
    want, _ = ssd_reference(*args)
    # the operands' and the output's rounding, chunk by chunk; a state carried
    # in bfloat16 would lose 2^-8 of itself at each of the boundaries as well
    err = float(jnp.abs(y.astype(jnp.float32) - want).max())
    assert err < 2 ** -6 * float(jnp.abs(want).max())
    hs = _kept_states(args, 16)
    assert hs.dtype == jnp.float32 and hs.shape == (1, 2, 4, 16, 16)
    assert float(jnp.abs(hs - hs.astype(jnp.bfloat16).astype(jnp.float32))
                 .max()) > 0        # it holds more bits than bfloat16 has


def test_bfloat16_gradients_stay_near_the_float32_recurrence():
    """bfloat16 x, B, C under float32 dt, A, D, four chunks: all six gradients
    of the kernels against autodiff of the float32 recurrence on the same
    (rounded) inputs; what is rounded on the way is the matmuls' operands and
    the gradients handed out in bfloat16."""
    args = _inputs(b=1, s=64, dtype=jnp.bfloat16)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0].astype(jnp.float32) * w)
    got = jax.grad(loss(lambda *a: ssd(*a, chunk=16)),
                   argnums=tuple(range(6)))(*args)
    ref = jax.grad(loss(ssd_reference), argnums=tuple(range(6)))(*args)
    for name, g, r in zip(NAMES, got, ref):
        assert g.dtype == (jnp.bfloat16 if name in ("x", "B", "C")
                           else jnp.float32), name
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.abs(g - r).max()) \
            < 2 ** -5 * float(jnp.abs(r).max()), name


def test_schedule_says_what_a_call_holds():
    """The cell's calls: 64 heads of 64 with 128 states in 8 groups, S=16384,
    bfloat16 rows. A forward call reads x (128 MiB), B and C a group (32
    each), dt and gamma (4 each), a decay a chunk and D, and writes y (128)
    and the entering states (256); a backward call reads those, the states
    and dY and writes the gradients. Nothing but the states is kept beside
    the inputs."""
    sc = ssd_schedule(1, 16384, 64, 64, 128, 8, itemsize=2)
    mib = 2 ** 20
    small = 64 * 128 * 4 + 64 * 64 * 4      # exp(gamma_C) a chunk; D a feature
    assert (sc.chunk, sc.chunks, sc.group, sc.grid) == (128, 128, 8, (8, 16))
    assert (sc.heads_a_step, sc.heads_a_slab) == (8, 2)
    assert sc.kept_bytes == 256 * mib
    assert sc.fwd_bytes == (2 * 128 + 64 + 8 + 256) * mib + small
    assert sc.bwd_bytes == (3 * 128 + 2 * 64 + 16 + 256) * mib \
        + small + 64 * 64 * 4
    assert sc.describe() == (
        f"chunk=128 chunks=128 group=8 grid=8x16 heads=8 slab=2 "
        f"kept={256 * mib} fwd_bytes={sc.fwd_bytes} bwd_bytes={sc.bwd_bytes}")
    assert ssd_schedule(2, 100, 4, 8, 16, 2).chunks == 1
    assert ssd_schedule(2, 100, 4, 8, 16, 2, chunk=16)[1:4] == (7, 7, (4, 1))
    with pytest.raises(ValueError, match="multiple of G"):
        ssd(*_inputs(heads=3, groups=2, s=8))
