"""``ops/eva_attention.py``: the four kernels, interpreted, against the plain
form (``eva_reference``: the ``[S, S + S / c]`` scores under the mask, one
softmax, float32): the output, every gradient (phi's and mu's among them) and
the largest pooling weight, at two window sizes, a length that is no whole
number of windows and one no longer than a window; the pooling alone; the
float32 statistics under bfloat16 inputs; the schedule's live tiles and pairs;
the refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops.eva_attention import (
    SAVED_NAMES, _pool_fwd_call, eva_attention, eva_pool_reference,
    eva_reference, eva_schedule, live_pairs,
)


def _inputs(b, h, s, d, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 6)
    q, k, v = (jax.random.normal(key, (b, h, s, d)).astype(dtype)
               for key in keys[:3])
    phi, mu = (jax.random.normal(key, (h, d)) for key in keys[3:5])
    return (q, k, v, phi, mu), jax.random.normal(keys[5], (b, h, s, d))


# (batch, heads, S, head dim, window, chunk): four windows of one tile; three
# windows of 64 in tiles of 64 with 8 summaries each, two sequences; a last
# window that is not whole (padded at the end); a sequence inside one window
SHAPES = {"four_windows": (1, 2, 128, 32, 32, 4),
          "wider_window": (2, 2, 192, 16, 64, 8),
          "a_partial_last_window": (1, 2, 80, 16, 32, 4),
          "inside_one_window": (1, 2, 24, 16, 32, 4)}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_output_and_every_gradient_agree_with_the_plain_form(shape):
    """float32 both sides: 5e-6 absolute on outputs of order 1 and gradients
    up to about 4 is the order of the online softmax's reductions."""
    b, h, s, d, window, chunk = SHAPES[shape]
    args, w = _inputs(b, h, s, d)
    sizes = dict(window=window, chunk=chunk)

    def both(fn):
        def loss(*a):
            o, top = fn(*a, **sizes)
            return jnp.sum(o.astype(jnp.float32) * w), (o, top)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))(*args)

    (_, (o, top)), got = both(eva_attention)
    (_, (o_ref, top_ref)), want = both(eva_reference)
    np.testing.assert_allclose(o, o_ref, atol=5e-6)
    np.testing.assert_allclose(float(top), float(top_ref), rtol=1e-6)
    assert 1 / chunk < float(top) <= 1.0
    reads_summaries = s > window
    for name, g, g_ref in zip(("q", "k", "v", "phi", "mu"), got, want):
        assert g.shape == g_ref.shape and g.dtype == g_ref.dtype, name
        np.testing.assert_allclose(g, g_ref, atol=5e-6, err_msg=name)
        # phi and mu move nothing where no query reads a summary
        assert (float(jnp.abs(g_ref).max()) > 0.1) \
            == (reads_summaries or name in "qkv"), name


def test_the_pooling_alone():
    """The summaries and the largest weight of ``eva_pool_fwd`` against the
    plain pooling; a chunk whose first key lies along phi is read through it."""
    (_, k, v, phi, mu), _ = _inputs(2, 2, 64, 16, seed=3)
    k = k.at[0, 0, 8].set(60.0 * phi[0] / jnp.linalg.norm(phi[0]))
    sched = eva_schedule(4, 64, 16, 4, 32, 4)
    ks, vs, top = _pool_fwd_call(k.reshape(4, 64, 16), v.reshape(4, 64, 16),
                                 phi, mu, 0.25, sched, True)
    ks_ref, vs_ref, alpha = eva_pool_reference(k, v, phi, mu, chunk=4)
    np.testing.assert_allclose(ks.reshape(ks_ref.shape), ks_ref, atol=2e-5)
    np.testing.assert_allclose(vs.reshape(vs_ref.shape), vs_ref, atol=2e-6)
    np.testing.assert_allclose(alpha.sum(-1), 1.0, atol=1e-6)
    assert float(top) == pytest.approx(float(alpha.max())) and top > 0.999
    np.testing.assert_allclose(ks_ref[0, 0, 2], k[0, 0, 8] + mu[0], rtol=1e-3)


def test_statistics_are_float32_under_bfloat16_inputs():
    """bfloat16 q, k, v: the outputs leave in bfloat16, and what lies between
    (scores, running maxima and sums, the accumulators, the pooling's softmax,
    the gradients' sums) is float32: against the plain form on the SAME
    rounded inputs the output is within a bfloat16 rounding of an output of
    order 1 (2^-8), and phi's and mu's gradients, float32 sums over 256
    tokens of products of rounded factors, within 2%."""
    args, w = _inputs(1, 2, 128, 32, jnp.bfloat16, seed=1)
    sizes = dict(window=32, chunk=4)
    o, _ = eva_attention(*args, **sizes)
    o_ref, _ = eva_reference(*args, **sizes)
    assert o.dtype == jnp.bfloat16 and o_ref.dtype == jnp.float32
    assert float(jnp.abs(o.astype(jnp.float32) - o_ref).max()) < 2 ** -6
    loss = lambda fn: lambda *a: jnp.sum(fn(*a, **sizes)[0].astype(
        jnp.float32) * w)
    got = jax.grad(loss(eva_attention), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(eva_reference), argnums=(0, 1, 2, 3, 4))(*args)
    assert [g.dtype for g in got] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    for g, g_ref in zip(got, want):
        scale = float(jnp.abs(g_ref).max())
        assert float(jnp.abs(g.astype(jnp.float32) - g_ref).max()) \
            < 0.02 * scale


def test_the_schedule_counts_the_live_tiles_of_the_cells_shape():
    """S=16384 in 8 windows of 2048, 512-row tiles: 8 x (4 x 5 / 2) token
    tiles and sum_w 4 w summary-tile rows a head; 24,125,440 live pairs."""
    sc = eva_schedule(32, 16384, 128, 2, 2048, 16)
    assert (sc.block_q, sc.block_s, sc.windows, sc.pool_rows) \
        == (512, 128, 8, 512)
    assert sc.grid == (32, 32) and sc.bwd_grid == (32, 8)
    assert sc.token_tiles == 8 * 10 == 80
    assert sc.summary_tiles == sum(4 * w for w in range(8)) == 112
    assert sc.pairs == live_pairs(16384, 2048, 16) == 24_125_440 \
        == 8 * (2048 * 2049 // 2) + 2048 * 128 * sum(range(8))
    rows = 32 * 16384 * 128 * 2
    assert sc.fwd_bytes == 4 * rows + 2 * rows // 16 + 32 * 16384 * 4
    assert sc.pool_fwd_bytes == 2 * rows + 2 * rows // 16
    assert "token_tiles=80 summary_tiles=112 pairs=24125440" in sc.describe()
    # no longer than a window: one window of the sequence's own length
    one = eva_schedule(4, 24, 16, 4, 32, 4)
    assert (one.s, one.window, one.windows, one.summary_tiles) == (24, 24, 1, 0)
    assert live_pairs(24, 32, 4) == 24 * 25 // 2
    assert SAVED_NAMES == ("eva_o", "eva_lse", "eva_ks", "eva_vs")


@pytest.mark.parametrize("s,window,chunk", [(126, 32, 4), (128, 30, 4),
                                            (128, 32, 0)])
def test_a_length_that_is_no_whole_number_of_chunks_is_refused(s, window,
                                                                chunk):
    args, _ = _inputs(1, 2, s, 16)
    for fn in (eva_attention, eva_reference):
        with pytest.raises((ValueError, ZeroDivisionError),
                           match="refused, not padded|division|modulo"):
            fn(*args, window=window, chunk=chunk)


def test_shapes_that_do_not_agree_are_refused():
    (q, k, v, phi, mu), _ = _inputs(1, 2, 32, 16)
    with pytest.raises(ValueError, match=r"phi and mu .* \[H, d\]"):
        eva_attention(q, k, v, phi[:1], mu, window=32, chunk=4)
    with pytest.raises(ValueError, match="alike"):
        eva_attention(q, k[:, :1], v, phi, mu, window=32, chunk=4)
