"""``ops/grouped_matmul.py:gmm`` (interpreted): forward and both gradients
against a loop over the groups, ragged and empty groups and rows past the last
one; bfloat16 rows on float32 weights; ``gmm_t`` against weights stored
transposed; a width off the 128 lanes is taken whole."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops.grouped_matmul import _tiles, gmm, gmm_t

GROUPS = {
    "ragged": [5, 0, 19, 3, 0, 13],
    "empty_first_and_last": [0, 17, 23, 0],
    "one_group_takes_every_row": [0, 0, 40, 0],
    "rows_past_the_groups": [7, 9, 0, 8],          # 24 of 40 rows covered
}


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_gmm_and_both_gradients_against_a_loop(name):
    sizes = np.asarray(GROUPS[name], np.int32)
    m, k, n, g = 40, 16, 24, len(sizes)
    ks = jax.random.split(jax.random.key(3), 3)
    lhs = jax.random.normal(ks[0], (m, k))
    rhs = jax.random.normal(ks[1], (g, k, n))
    cot = jax.random.normal(ks[2], (m, n))
    off = np.concatenate([[0], np.cumsum(sizes)])

    def loop(lhs, rhs):
        out = jnp.zeros((m, n))
        for e in range(g):
            out = out.at[off[e]:off[e + 1]].set(
                lhs[off[e]:off[e + 1]] @ rhs[e])
        return out

    got, vjp = jax.vjp(lambda a, b: gmm(a, b, jnp.asarray(sizes)), lhs, rhs)
    want, vjp_want = jax.vjp(loop, lhs, rhs)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.asarray(got[off[-1]:]).any()
    for a, b in zip(vjp(cot), vjp_want(cot)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("rows", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(GROUPS))
def test_gmm_t_is_gmm_on_the_transposed_weights(name, rows):
    """Weights stored [E, N, K], as a checkpoint stores a linear layer: the
    forward and both gradients are ``gmm``'s on ``swapaxes(rhs, 1, 2)``, the
    weights' gradient in the stored layout; float32 to the last bits,
    bfloat16 rows within their own rounding."""
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    m, k, n, g = 40, 16, 24, len(GROUPS[name])
    ks = jax.random.split(jax.random.key(7), 3)
    lhs = jax.random.normal(ks[0], (m, k)).astype(rows)
    rhs_t = jax.random.normal(ks[1], (g, n, k))
    cot = jax.random.normal(ks[2], (m, n)).astype(rows)
    got, vjp = jax.vjp(lambda a, b: gmm_t(a, b, sizes), lhs, rhs_t)
    want, vjp_want = jax.vjp(
        lambda a, b: gmm(a, jnp.swapaxes(b, 1, 2), sizes), lhs, rhs_t)
    tol = 1e-5 if rows == "float32" else 0.1
    f32 = lambda a: np.asarray(a, np.float32)
    assert got.dtype == lhs.dtype and got.shape == (m, n)
    np.testing.assert_allclose(f32(got), f32(want), atol=tol)
    for a, b in zip(vjp(cot), vjp_want(cot)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(f32(a), f32(b), atol=tol)
    with pytest.raises(ValueError, match="rhs \\[E, N, K\\]"):
        gmm_t(lhs, jnp.swapaxes(rhs_t, 1, 2), sizes)


def test_a_width_off_the_128_lanes_is_taken_whole():
    """1856 = 14.5 x 128 has no tile but itself, as N or as K, whatever the
    table's target and the weights' budget say; widths on the lanes tile as
    they did."""
    assert _tiles(18432, 2688, 1856, jnp.bfloat16, jnp.float32) \
        == (256, 896, 1856)
    assert _tiles(18432, 1856, 2688, jnp.bfloat16, jnp.float32) \
        == (256, 1856, 896)
    assert _tiles(18432, 2688, 1856, jnp.float32, jnp.float32)[2] == 1856
    assert _tiles(18432, 1856, 2688, jnp.bfloat16) == (256, 1856, 896)
    assert _tiles(32768, 2048, 1024, jnp.bfloat16, jnp.float32) \
        == (256, 2048, 1024)


@pytest.mark.parametrize("k_tiles", [1, 2])
@pytest.mark.parametrize("name", sorted(GROUPS))
def test_gmm_bfloat16_rows_on_float32_weights(name, k_tiles, monkeypatch):
    """What a bfloat16 model hands the kernels: bfloat16 rows, the float32
    expert weights as they are (a tile is cast in VMEM). Forward and the
    gradient to the rows come back bfloat16, within its rounding of a float32
    ``einsum`` over the rounded operands; the gradient to the weights comes
    back FLOAT32 from the float32 accumulator, unrounded."""
    from ps_pytorch_tpu.ops import grouped_matmul
    sizes = np.asarray(GROUPS[name], np.int32)
    m, k, n, g = 40, 16, 24, len(sizes)
    if k_tiles > 1:     # several row, K and N tiles: the cast tiles by K tile
        monkeypatch.setitem(grouped_matmul._TILES, 2, (8, 128, 128))
        k, n = 128 * k_tiles, 256
    ks = jax.random.split(jax.random.key(5), 3)
    lhs = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (g, k, n))
    cot = jax.random.normal(ks[2], (m, n)).astype(jnp.bfloat16)
    group = np.repeat(np.arange(g + 1), np.append(sizes, m - sizes.sum()))
    onehot = jnp.asarray(group[:, None] == np.arange(g)[None], jnp.float32)
    rounded = rhs.astype(jnp.bfloat16).astype(jnp.float32)

    def reference(lhs, rhs):    # rows past the groups match no group: zeros
        return jnp.einsum("mk,mg,gkn->mn", lhs.astype(jnp.float32), onehot,
                          rhs, precision=jax.lax.Precision.HIGHEST)

    got, vjp = jax.vjp(lambda a, b: gmm(a, b, jnp.asarray(sizes)), lhs, rhs)
    want, vjp_want = jax.vjp(reference, lhs, rounded)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=0.06 * k_tiles, rtol=2 ** -7)
    dlhs, drhs = vjp(cot)
    dlhs_want, drhs_want = vjp_want(cot.astype(jnp.float32))
    assert dlhs.dtype == jnp.bfloat16 and drhs.dtype == jnp.float32
    np.testing.assert_allclose(dlhs.astype(jnp.float32),
                               dlhs_want.astype(jnp.float32),
                               atol=0.06 * k_tiles, rtol=2 ** -7)
    # float32 out of the accumulator: products of bfloat16 values are exact
    # in float32, so only the order of the sum differs from the reference
    np.testing.assert_allclose(drhs, drhs_want, atol=1e-5, rtol=1e-6)
    assert np.abs(np.asarray(drhs) - np.asarray(
        drhs.astype(jnp.bfloat16).astype(jnp.float32))).max() > 0
