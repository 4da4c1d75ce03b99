"""The Gated DeltaNet mixer's two elementwise ops (``ops/gdn_mix.py``:
convolution -> SiLU -> l2 norm, and the gated output norm), interpreted,
against the plain chain ``models/gdn.py`` had until PR 41: ``jax.numpy`` ops on
``models/ssm.causal_conv1d`` and ``nn.RMSNorm``, differentiated by JAX."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from ps_pytorch_tpu.models.ssm import causal_conv1d
from ps_pytorch_tpu.ops import gdn_mix
from ps_pytorch_tpu.ops.gdn_mix import (
    conv_silu_l2norm, gated_rms_norm, mix_schedule,
)

TAPS, EPS = 4, 1e-6


def plain_conv_chain(qkv, weight, key_heads, value_heads, d):
    """``gdn_sublayer``'s lines between the projection and the delta rule as
    they were: the convolution in the rows' dtype, the norms in float32."""
    b, s, _ = qkv.shape
    dt = qkv.dtype
    y = nn.silu(causal_conv1d(qkv, weight, jnp.zeros((), dt)))
    q, k, v = jnp.split(y, [key_heads * d, 2 * key_heads * d], axis=-1)

    def unit(t, scale=1.0):
        t = t.reshape(b, s, key_heads, d).astype(jnp.float32)
        return (t * (jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                   + 1e-6) * scale)).astype(dt)
    return unit(q, d ** -0.5), unit(k), v.reshape(b, s, value_heads, d)


def plain_gated_norm(o, z, scale):
    y = nn.RMSNorm(epsilon=EPS, dtype=o.dtype).apply(
        {"params": {"scale": scale}}, o)
    return (y * nn.silu(z.reshape(o.shape))).reshape(z.shape)


def _inputs(b, s, key_heads, value_heads, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 9)
    c = (2 * key_heads + value_heads) * d
    rows = lambda k, shape: jax.random.normal(k, shape).astype(dtype)
    return dict(
        qkv=rows(ks[0], (b, s, c)),
        weight=0.5 * jax.random.normal(ks[1], (TAPS, c)),
        o=rows(ks[2], (b, s, value_heads, d)),
        z=rows(ks[3], (b, s, value_heads * d)),
        scale=1.0 + 0.3 * jax.random.normal(ks[4], (d,)),
        cts=(rows(ks[5], (b, s, key_heads, d)),
             rows(ks[6], (b, s, key_heads, d)),
             rows(ks[7], (b, s, value_heads, d))),
        g=rows(ks[8], (b, s, value_heads * d)))


def _both_ways(conv, norm, x):
    """(names, every output and every gradient of both chains)."""
    out, pull = jax.vjp(conv, x["qkv"], x["weight"])
    gated, pull_norm = jax.vjp(norm, x["o"], x["z"], x["scale"])
    return (("q", "k", "v", "d_qkv", "d_conv_weight", "gated", "d_o", "d_z",
             "d_scale"),
            tuple(out) + pull(x["cts"]) + (gated,) + pull_norm(x["g"]))


def _ops(key_heads, value_heads, d):
    return (lambda qkv, w: conv_silu_l2norm(
                qkv, w, key_heads=key_heads, value_heads=value_heads,
                key_dim=d, value_dim=d),
            lambda o, z, scale: gated_rms_norm(o, z, scale, eps=EPS))


def _plain(key_heads, value_heads, d):
    return (lambda qkv, w: plain_conv_chain(qkv, w, key_heads, value_heads,
                                            d),
            plain_gated_norm)


# (batch, S, key heads, value heads, head width, tokens a grid step, heads a
# step in lanes): three tiles, the last ragged, two heads a step and two
# sequences; whole tiles, one head of 128 lanes a step; a sequence shorter
# than the rows a block brings
SHAPES = {"three_tiles_last_ragged": (2, 72, 2, 4, 16, 32, 32),
          "whole_tiles_a_head_of_128": (1, 48, 1, 1, 128, 16, 128),
          "shorter_than_a_block": (2, 5, 1, 2, 8, 512, 512)}


@pytest.fixture
def tiles(monkeypatch):
    def use(rows, lanes):
        monkeypatch.setattr(gdn_mix, "ROWS", rows)
        monkeypatch.setattr(gdn_mix, "LANES", lanes)
    return use


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_float32_outputs_and_every_gradient_agree_with_the_plain_chain(
        tiles, shape):
    b, s, hk, hv, d, rows, lanes = SHAPES[shape]
    tiles(rows, lanes)
    x = _inputs(b, s, hk, hv, d)
    names, got = _both_ways(*_ops(hk, hv, d), x)
    _, want = _both_ways(*_plain(hk, hv, d), x)
    for name, a, r in zip(names, got, want):
        assert a.shape == r.shape and a.dtype == r.dtype == jnp.float32, name
        assert float(jnp.abs(a - r).max()) \
            <= 1e-5 * max(float(jnp.abs(r).max()), 1.0), name


def test_a_sequence_starts_from_zeros_whatever_precedes_it_in_the_batch(
        tiles):
    """Rows before token 0 are zeros for EVERY batch row: the second sequence
    of a batch reads nothing of the first one's tail, forward or backward."""
    tiles(32, 512)
    b, s, hk, hv, d = 2, 64, 1, 2, 8
    x = _inputs(b, s, hk, hv, d)
    conv, _ = _ops(hk, hv, d)
    out, pull = jax.vjp(conv, x["qkv"], x["weight"])
    second = slice(1, 2)
    alone, pull_alone = jax.vjp(conv, x["qkv"][second], x["weight"])
    for a, a1 in zip(out + pull(x["cts"])[:1],
                     alone + pull_alone(tuple(c[second]
                                              for c in x["cts"]))[:1]):
        assert float(jnp.abs(a[second] - a1).max()) < 1e-6
    # and a tail that did leak would show
    loud = x["qkv"].at[0, -3:].set(1e3)
    assert bool(jnp.array_equal(conv(loud, x["weight"])[0][1], out[0][1]))


def test_bfloat16_rows_stay_near_the_float32_chain(tiles):
    """bfloat16 rows, float32 inside a tile: every output and gradient
    against the plain chain in float32 on the same (rounded) inputs, by the
    limit ``test_bfloat16_gradients_stay_near_the_float32_recurrence`` has
    (2^-6 of the largest entry; read under 2^-8: one rounding of an output).
    What leaves a tile is the rows' dtype; the parameters' gradients are
    float32."""
    tiles(32, 32)
    b, s, hk, hv, d = 2, 72, 2, 4, 16
    x = _inputs(b, s, hk, hv, d, jnp.bfloat16)
    names, got = _both_ways(*_ops(hk, hv, d), x)
    up = jax.tree.map(lambda a: a.astype(jnp.float32), x)
    _, want = _both_ways(*_plain(hk, hv, d), up)
    for name, a, r in zip(names, got, want):
        wide = name in ("d_conv_weight", "d_scale")
        assert a.dtype == (jnp.float32 if wide else jnp.bfloat16), name
        a = a.astype(jnp.float32)
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(a - r).max()) \
            < 2 ** -6 * float(jnp.abs(r).max()), name


def test_schedule_says_what_the_calls_hold_and_move():
    """At the cell's shape: 2048 tokens of one head a step, 512 rows a trip
    of the loop inside, and within 1% of the floor the issue counts
    (bfloat16 in HBM: 512 + 384 MiB forward, 768 + 640 backward a layer) for
    the rows a tile reads of its neighbours."""
    sc = mix_schedule(1, 16384, 16, 32, 128, TAPS, itemsize=2)
    assert (sc.lanes, sc.rows, sc.chunk, sc.halo) == (128, 2048, 512, 16)
    assert sc.conv_grid == (1, 64, 8) and sc.norm_grid == (1, 32, 8)
    mib = 2 ** 20
    for moved, floor in ((sc.conv_fwd_bytes, 512), (sc.conv_bwd_bytes, 768),
                         (sc.norm_fwd_bytes, 384), (sc.norm_bwd_bytes, 640)):
        assert floor * mib <= moved < 1.01 * floor * mib
    assert "conv_grid=1x64x8" in sc.describe()
    # a tiny shape: one tile of the rows a block brings, both heads a step
    tiny = mix_schedule(2, 40, 2, 4, 16, TAPS, itemsize=4)
    assert (tiny.lanes, tiny.rows, tiny.conv_grid) == (32, 48, (2, 4, 1))


@pytest.mark.parametrize("wrong", ["two_widths", "qkv_width", "weight_width",
                                   "z_width", "taps"])
def test_the_ops_refuse_shapes_they_were_not_built_for(wrong):
    x = _inputs(1, 16, 1, 2, 8)
    conv = dict(key_heads=1, value_heads=2, key_dim=8, value_dim=8)
    with pytest.raises(ValueError, match="conv_silu_l2norm|gated_rms_norm"):
        if wrong == "two_widths":
            conv_silu_l2norm(x["qkv"], x["weight"], **dict(conv, value_dim=4))
        elif wrong == "qkv_width":
            conv_silu_l2norm(x["qkv"][..., :-8], x["weight"][:, :-8], **conv)
        elif wrong == "weight_width":
            conv_silu_l2norm(x["qkv"], x["weight"][:, :-8], **conv)
        elif wrong == "z_width":
            gated_rms_norm(x["o"], x["z"][..., :-8], x["scale"], eps=EPS)
        else:
            conv_silu_l2norm(x["qkv"], jnp.ones((9, x["qkv"].shape[2])),
                             **conv)
