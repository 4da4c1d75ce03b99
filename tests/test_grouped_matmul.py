"""``ops/grouped_matmul.py:gmm`` (interpreted): forward and both gradients
against a loop over the groups, ragged and empty groups and rows past the last
one, which come back as exact zeros whatever they hold and are visited without
being multiplied (PR 48); bfloat16 rows on float32 weights; ``gmm_t`` against
weights stored transposed; a width off the 128 lanes is taken whole."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops import grouped_matmul
from ps_pytorch_tpu.ops.grouped_matmul import (
    _lhs_block, _tiles, _visits, gmm, gmm_t,
)

M = 40
GROUPS = {
    "ragged": [5, 0, 19, 3, 0, 13],
    "empty_first_and_last": [0, 17, 23, 0],
    "one_group_takes_every_row": [0, 0, 40, 0],
    "rows_past_the_groups": [7, 9, 0, 8],          # 24 of 40 rows covered
    # 21 of 40: in row tiles of 8 the last group ends inside the third tile,
    # which it shares with the tail, and two whole tiles of tail follow
    "whole_tiles_of_tail_behind_a_shared_one": [7, 9, 0, 5],
    "no_group_has_a_row": [0, 0, 0, 0],
}
ROW_TILES = [M, 8]     # the 40 rows whole (the table's targets), or five tiles


def _cut_the_rows(monkeypatch, tm):
    for itemsize, (_, tk, tn) in list(grouped_matmul._TILES.items()):
        monkeypatch.setitem(grouped_matmul._TILES, itemsize, (tm, tk, tn))


def _nan_past(rows, covered):
    """``rows`` with NaN in every row from ``covered`` on: what the rows past
    the groups hold must reach no output."""
    return rows.at[covered:].set(jnp.nan)


@pytest.mark.parametrize("tm", ROW_TILES)
@pytest.mark.parametrize("name", sorted(GROUPS))
def test_gmm_and_both_gradients_against_a_loop(name, tm, monkeypatch):
    """... with NaN in the rows past the groups: the output there and the
    gradient to those rows are exactly 0, and no other number moves (a NaN
    in the cotangent's rows past the groups reaches no row's gradient)."""
    _cut_the_rows(monkeypatch, tm)
    sizes = np.asarray(GROUPS[name], np.int32)
    m, k, n, g = M, 16, 24, len(sizes)
    ks = jax.random.split(jax.random.key(3), 3)
    off = np.concatenate([[0], np.cumsum(sizes)])
    lhs = _nan_past(jax.random.normal(ks[0], (m, k)), off[-1])
    rhs = jax.random.normal(ks[1], (g, k, n))
    cot = jax.random.normal(ks[2], (m, n))

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
    dlhs = vjp(cot)[0]
    assert not np.asarray(dlhs[off[-1]:]).any()
    np.testing.assert_array_equal(vjp(_nan_past(cot, off[-1]))[0], dlhs)


@pytest.mark.parametrize("tm", ROW_TILES)
@pytest.mark.parametrize("rows", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(GROUPS))
def test_gmm_t_is_gmm_on_the_transposed_weights(name, rows, tm, monkeypatch):
    """Weights stored [E, N, K], as a checkpoint stores a linear layer: the
    forward and both gradients are ``gmm``'s on ``swapaxes(rhs, 1, 2)``, the
    weights' gradient in the stored layout; float32 to the last bits,
    bfloat16 rows within their own rounding; exact zeros past the groups,
    forward and to the rows' gradient, whatever the rows there hold."""
    _cut_the_rows(monkeypatch, tm)
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    m, k, n, g = M, 16, 24, len(GROUPS[name])
    covered = sum(GROUPS[name])
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
    # (the weights' gradient masks the cotangent's rows, not ``lhs``'s: a
    # NaN there would reach it, so the rows above are clean)
    dlhs = vjp(cot)[0]
    assert not f32(got)[covered:].any() and not f32(dlhs)[covered:].any()
    np.testing.assert_array_equal(
        f32(gmm_t(_nan_past(lhs, covered), rhs_t, sizes)), f32(got))
    np.testing.assert_array_equal(
        f32(vjp(_nan_past(cot, covered))[0]), f32(dlhs))
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
    sizes = np.asarray(GROUPS[name], np.int32)
    m, k, n, g = M, 16, 24, len(sizes)
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

    # the kernels' rows hold NaN past the groups (the reference's would
    # multiply it by the one-hot's zeros)
    got, vjp = jax.vjp(lambda a, b: gmm(a, b, jnp.asarray(sizes)),
                       _nan_past(lhs, sizes.sum()), rhs)
    want, vjp_want = jax.vjp(reference, lhs, rounded)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=0.06 * k_tiles, rtol=2 ** -7)
    dlhs, drhs = vjp(cot)
    dlhs_want, drhs_want = vjp_want(cot.astype(jnp.float32))
    assert dlhs.dtype == jnp.bfloat16 and drhs.dtype == jnp.float32
    for rows in (got, dlhs, vjp(_nan_past(cot, sizes.sum()))[0]):
        assert not np.asarray(rows[sizes.sum():], np.float32).any()
    np.testing.assert_allclose(dlhs.astype(jnp.float32),
                               dlhs_want.astype(jnp.float32),
                               atol=0.06 * k_tiles, rtol=2 ** -7)
    # float32 out of the accumulator: products of bfloat16 values are exact
    # in float32, so only the order of the sum differs from the reference
    np.testing.assert_allclose(drhs, drhs_want, atol=1e-5, rtol=1e-6)
    assert np.abs(np.asarray(drhs) - np.asarray(
        drhs.astype(jnp.bfloat16).astype(jnp.float32))).max() > 0 \
        or not sizes.any()


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_the_rows_past_the_groups_are_visited_and_not_multiplied(name):
    """The schedule of ``moe_gmm_fwd|dlhs`` over row tiles of 8: the visits
    that multiply are the groups' own, a tile a group touches each
    (``sum(ceil-tiles of the groups)``, not the grid's ``M/tm + groups``);
    the tiles of the rows past them follow, as the pseudo-group's visits,
    and each of those, like every grid step past the schedule's end, reads
    the block the last multiplying visit read last: the pipeline fetches no
    row for it."""
    sizes, tm, k_steps = np.asarray(GROUPS[name]), 8, 3
    ends = np.cumsum(sizes)
    own = sum(-(-e // tm) - (e - s) // tm for s, e in zip(sizes, ends) if s)
    tail = -(-M // tm) - ends[-1] // tm if ends[-1] < M else 0
    offs, gid, tid, nv = map(np.asarray, _visits(
        jnp.asarray(sizes), M, tm, remainder=True, visit_empty=False))
    assert list(nv) == [own + tail] and len(gid) == M // tm + len(sizes)
    assert list(offs) == [0, *ends, M]
    assert (gid[:own] < len(sizes)).all() and (np.diff(gid[:own]) >= 0).all()
    assert (gid[own:own + tail] == len(sizes)).all()
    assert list(tid[own:own + tail]) == list(range(M // tm - tail, M // tm))
    for ki in range(k_steps):
        blocks = [tuple(int(b) for b in _lhs_block(
            i, ki, offs, tid, nv, n_groups=len(sizes), row_tiles=M // tm,
            tm=tm, last_k=k_steps - 1)) for i in range(len(gid))]
        assert blocks[:own] == [(t, ki) for t in tid[:own]]
        rest = (int(tid[max(own - 1, 0)]), k_steps - 1)
        assert blocks[own:] == [rest] * (len(gid) - own)
