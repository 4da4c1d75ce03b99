"""``ops/moe_rows.py``, interpreted: a held share's take and combine against
the plain ``tokens[idx]`` / ``.at[idx].add`` and ``jax.vjp`` of them, forward
and both gradients, over the routings a layer can draw (no row owned, every
row owned, empty groups, a token with every choice held and one with none,
k = 1 and 6, a width that is no multiple of 256, fewer tokens than a tile),
with NaN planted in every row past the count."""

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.ops import moe_rows


class Routing(NamedTuple):
    tokens: int
    k: int
    experts: int
    held: int
    d: int
    rows: int               # the main part's; the overflow part has the rest
    empty: tuple = ()       # held experts no token may choose
    all_held: int = -1      # a token whose every choice is held
    none_held: int = -1     # ... and one with none


# name: (routing, which part). "main" covers the first ``rows`` sorted places,
# "over" the rest.
CASES = {
    "count_0": (Routing(64, 3, 8, 4, 128, 128, empty=(0, 1, 2, 3)), "main"),
    "count_is_rows": (Routing(64, 3, 8, 4, 128, 64), "main"),
    "overflow_part": (Routing(64, 3, 8, 4, 128, 64), "over"),
    "a_tail": (Routing(64, 3, 8, 4, 128, 128), "main"),
    "an_empty_first_group": (Routing(64, 3, 8, 4, 128, 128, empty=(0,)),
                             "main"),
    "an_empty_middle_group": (Routing(64, 3, 8, 4, 128, 128, empty=(2,)),
                              "main"),
    "all_and_none_of_a_tokens_choices": (
        Routing(64, 3, 8, 4, 128, 128, all_held=5, none_held=9), "main"),
    "k_1": (Routing(64, 1, 8, 4, 128, 64), "main"),
    "k_6": (Routing(64, 6, 16, 4, 128, 128), "main"),
    "d_21_lane_tiles_scaled": (Routing(64, 3, 8, 4, 384, 128), "main"),
    "fewer_tokens_than_a_tile": (Routing(24, 3, 8, 4, 128, 48), "main"),
    "tokens_in_two_tiles": (Routing(512, 3, 8, 4, 128, 512), "main"),
    "tiles_of_128_tokens": (Routing(384, 3, 8, 4, 128, 512), "main"),
}


@lru_cache(maxsize=None)      # a case's three tests share one routing
def _route(r: Routing, part: str, dtype):
    """One part of a drawn routing: what ``DroplessMoE`` hands the ops, and
    the plain index form of the same."""
    rng = np.random.default_rng(52)
    scores = rng.normal(size=(r.tokens, r.experts))
    scores[:, list(r.empty)] = -50.0
    if r.all_held >= 0:
        scores[r.all_held, :r.held] += 50.0
    if r.none_held >= 0:
        scores[r.none_held, :r.held] -= 50.0
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :r.k].astype(np.int32)
    gates = rng.uniform(0.1, 1.0, size=(r.tokens, r.k)).astype(np.float32)
    flat = idx.reshape(-1)
    key = np.where(flat < r.held, flat, r.held)
    order = np.argsort(key, kind="stable").astype(np.int32)
    sizes = np.bincount(key, minlength=r.held + 1)[:r.held].astype(np.int32)
    start, size = (0, r.rows) if part == "main" \
        else (r.rows, r.tokens * r.k - r.rows)
    count = int(np.clip(sizes.sum() - start, 0, size))
    sched = moe_rows.rows_schedule(size, count, r.tokens, r.k, r.d, dtype,
                                   r.held)
    local = jnp.asarray(idx)
    has, _ = moe_rows.held_tables(local, jnp.asarray(gates), r.held)
    place, lo = moe_rows.held_places(has, jnp.asarray(sizes),
                                     sched.tokens_tile)
    plan = moe_rows.rows_plan(has, place, lo, start=start, count=count)
    return (jnp.asarray(order[start:start + size]), jnp.asarray(gates), local,
            plan, sched, count)


def _planted(x, count):
    """``x`` with NaN in every row at and past ``count``."""
    return jnp.where((jnp.arange(x.shape[0]) < count)[:, None], x, jnp.nan)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


# every case in float32 and, where the shapes are the ones most cases share
# (a compile of the interpreted kernel a shape and dtype: tier-1's time),
# in bfloat16, whose segments are 16 rows for float32's 8
BOTH_DTYPES = [(name, jnp.float32) for name in sorted(CASES)] + [
    (name, jnp.bfloat16) for name in (
        "a_tail", "count_0", "an_empty_first_group", "an_empty_middle_group",
        "all_and_none_of_a_tokens_choices", "k_6")]
DTYPE_IDS = [f"{name}-{jnp.dtype(dtype).name}" for name, dtype in BOTH_DTYPES]


@pytest.mark.parametrize("name,dtype", BOTH_DTYPES, ids=DTYPE_IDS)
def test_the_take_is_the_plain_gather_and_its_transpose(name, dtype):
    """Forward the tokens' rows of the sorted assignments before the count;
    backward the cotangent's rows summed into their tokens in float32, and
    nothing of the rows past the count, which hold NaN."""
    r, part = CASES[name]
    idx, _, _, plan, sched, count = _route(r, part, dtype)
    tokens = jax.random.normal(jax.random.key(1), (r.tokens, r.d), dtype)
    ct = _planted(jax.random.normal(jax.random.key(2), (idx.shape[0], r.d),
                                    dtype), count)
    owned = (jnp.arange(idx.shape[0]) < count)[:, None]
    got, vjp = jax.vjp(lambda x: moe_rows.take(x, idx, plan, r.k, sched),
                       tokens)
    np.testing.assert_array_equal(
        np.asarray(jnp.where(owned, got, 0), np.float32),
        np.asarray(jnp.where(owned, tokens[idx // r.k], 0), np.float32))
    want = jnp.zeros((r.tokens, r.d), jnp.float32).at[idx // r.k].add(
        jnp.where(owned, ct, 0).astype(jnp.float32))
    (d_tokens,) = vjp(ct)
    assert d_tokens.dtype == tokens.dtype
    np.testing.assert_allclose(np.asarray(d_tokens, np.float32), want,
                               **_tol(dtype))


@pytest.mark.parametrize("name,dtype", BOTH_DTYPES, ids=DTYPE_IDS)
def test_the_combine_is_the_plain_scatter_add_and_its_transpose(name, dtype):
    """Forward each token's gated rows summed in float32 (to float32's last
    bits whatever the rows' dtype: no gate and no row is rounded on the way);
    backward the cotangent's rows times their gates, and each gate's gradient;
    the rows past the count hold NaN and reach none of the three."""
    r, part = CASES[name]
    idx, gates, local, plan, sched, count = _route(r, part, dtype)
    out = _planted(jax.random.normal(jax.random.key(3), (idx.shape[0], r.d),
                                     dtype), count)
    ct = jax.random.normal(jax.random.key(4), (r.tokens, r.d), jnp.float32)

    owned = (jnp.arange(idx.shape[0]) < count)[:, None]

    def plain(out, gates):
        rows = jnp.where(owned, out, 0).astype(jnp.float32) \
            * gates.reshape(-1)[idx][:, None]
        return jnp.zeros((r.tokens, r.d), jnp.float32).at[idx // r.k].add(
            jnp.where(owned, rows, 0))

    got, vjp = jax.vjp(
        lambda o, g: moe_rows.combine(o, g, None, local, idx, plan, sched,
                                      jnp.float32), out, gates)
    want, plain_vjp = jax.vjp(plain, out, gates)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    (d_out, d_gates), (want_out, want_gates) = vjp(ct), plain_vjp(ct)
    assert d_out.dtype == out.dtype and d_gates.dtype == gates.dtype
    np.testing.assert_allclose(
        np.asarray(jnp.where(owned, d_out, 0), np.float32),
        np.asarray(jnp.where(owned, want_out, 0), np.float32), **_tol(dtype))
    np.testing.assert_allclose(d_gates, want_gates, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_what_is_added_is_added_in_float32_and_the_sum_rounded_once(dtype):
    """With another part's float32 sum handed in, the result is ``dtype`` of
    (that + this part's float32 sum), not the sum of two rounded parts, and
    the cotangent reaches it as it is."""
    r, part = CASES["a_tail"]
    idx, gates, local, plan, sched, count = _route(r, part, dtype)
    out = _planted(jax.random.normal(jax.random.key(3), (idx.shape[0], r.d),
                                     dtype), count)
    add = 100 * jax.random.normal(jax.random.key(5), (r.tokens, r.d))
    alone = moe_rows.combine(out, gates, None, local, idx, plan, sched,
                             jnp.float32)
    got, vjp = jax.vjp(
        lambda o, g, a: moe_rows.combine(o, g, a, local, idx, plan, sched,
                                         dtype), out, gates, add)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray((alone + add).astype(dtype),
                                             np.float32))
    ct = jax.random.normal(jax.random.key(4), (r.tokens, r.d), dtype)
    d_out, d_gates, d_add = vjp(ct)
    want_out, want_gates = jax.vjp(
        lambda o, g: moe_rows.combine(o, g, None, local, idx, plan, sched,
                                      jnp.float32), out, gates)[1](
        ct.astype(jnp.float32))
    assert d_add.dtype == jnp.float32
    np.testing.assert_array_equal(d_add, ct.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(d_out, np.float32),
                                  np.asarray(want_out, np.float32))
    np.testing.assert_array_equal(d_gates, want_gates)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_places_are_the_stable_sorts(name):
    """``held_places`` counts where a stable sort by held expert puts each
    (token, held expert); the plan's table keeps the part's own, before its
    count, and every one of them once."""
    r, part = CASES[name]
    idx, _, local, plan, sched, count = _route(r, part, jnp.float32)
    place = np.asarray(plan.place).T
    want = np.full_like(place, -1)
    for row, a in enumerate(np.asarray(idx[:count])):
        want[a // r.k, np.asarray(local).reshape(-1)[a]] = row
    np.testing.assert_array_equal(place, want)
    assert int(plan.count[0]) == count
    # a tile's runs: where an expert's rows for it begin, and no further
    # than the count
    lo = np.asarray(plan.lo).reshape(sched.tiles + 1, r.held)
    assert (np.diff(lo, axis=0) >= 0).all() and lo.max() <= count
    assert int(np.diff(lo, axis=0).sum()) == count


def test_a_schedule_says_what_a_call_moves():
    """The record of the ``KERNELS`` line at a small shape: the bytes are the
    shapes', and a width off the 128 lanes is taken whole."""
    s = moe_rows.rows_schedule(1024, 512, 256, 4, 384, jnp.bfloat16, 4)
    assert (s.tokens_tile, s.seg, s.segs, s.cols, s.tiles) == (256, 16, 16,
                                                               384, 1)
    assert s.bwd_bytes == 512 * 384 * 2 + 256 * 384 * 2
    assert s.fwd_bytes == s.bwd_bytes + 256 * 384 * 4
    assert "tokens_tile=256 seg=16 segs=16 cols=384" in s.describe()
    assert moe_rows.rows_schedule(1024, 512, 384, 4, 384, jnp.bfloat16,
                                  4).tokens_tile == 128
    assert moe_rows.rows_schedule(64, 32, 24, 3, 200, jnp.float32,
                                  2)[:4] == (24, 8, 32, 200)
