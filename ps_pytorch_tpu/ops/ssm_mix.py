"""The two elementwise chains of a Mamba-2 mixer (Pallas), each one pass over
HBM forward and one backward.

    conv_bias_silu:     c_t = bias + sum_k w[k] * xBC[t - (K - 1) + k]      zeros before token 0
                        [x | B | C] = silu(c)
    gated_group_norm:   g = y * silu(z)                                     the gate FIRST
                        g / sqrt(mean g^2 + eps) * scale                    a group of d_inner / G features

Written as ``jax.numpy`` ops and differentiated by JAX these were about 170
XLA ops and 46 GB a step in the Nemotron-3-Nano cell (PERF.md section 5, PR
44); neither holds a matmul, so the time is the bytes. Here each chain is one
function with a ``custom_vjp`` that keeps its inputs and nothing else, as
``ops/gdn_mix.py`` does for the Gated DeltaNet mixer's.

The convolution IS ``ops/gdn_mix.py``'s kernel pair (its ``conv_chain``), told
by a ``ConvChain`` what differs here: a bias, the row after the taps' in the
weight operand, added to the taps' float32 sum and its gradient summed beside
theirs; three segments of lane tiles, none of them normalised; and outputs
that leave TOKEN-major, ``[B, S, width]``, which is what ``ops/ssd.py`` reads
(a group's heads side by side along the lanes), so neither the split of ``x |
B | C`` nor the three cotangents' way back is a pass of its own.

The norm is a kernel pair of its own beside ``gdn_mix``'s (whose norm comes
BEFORE the gate and spans one head's 128 lanes): a block is ``rows`` tokens of
one group, its ``d_inner / G`` features along the lanes (512 in the published
model: four lane tiles, so a quarter of the rows ``gdn_mix`` takes a step),
``y`` as ``ssd`` wrote it and ``z`` as the projection did. Float32 inside a
step only. Backward, with ``n = rsqrt(mean g^2 + eps)`` and ``gh = g n``:
``d gh = ct scale``, ``dg = n (d gh - gh mean(d gh gh))``, ``dy = dg silu(z)``,
``dz = dg y (sig + silu(z) (1 - sig))``, and the scale's gradient ``sum_t ct
gh`` summed in float32 in its output block across the batch rows and sequence
tiles (eight partial rows, which XLA adds).

``ssm_mix_schedule`` says what the calls hold and move; the trainer prints it
on its ``KERNELS`` line. On one v5e at 16384 tokens, 6144 channels and 8
groups of 512, in the cell's step (PERF.md section 5, PR 45): the
convolution's three forward calls 0.89 ms, the three backward 2.24 (the
vector unit's), the norm 0.60 and 1.00 (the memory's: 670 GB/s).
"""

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ps_pytorch_tpu.ops import gdn_mix as _mix
from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default
from ps_pytorch_tpu.ops.gdn_mix import (
    ConvChain, _compiler_params, _each_chunk, _fold, _rows_from, _sigmoid,
    conv_chain, refuse_taps,
)

_F32 = jnp.float32
SUB = _mix.SUB


class SsmMixSchedule(NamedTuple):
    """What the mixer's calls hold and move, from the shapes alone."""
    lanes: int          # channels a grid step of the convolution
    rows: int           # ... and its tokens
    chunk: int          # rows a kernel works on at a time
    halo: int           # rows of the tile before (and, backward, after) a step also reads
    conv_grid: tuple    # (batch, channel tiles of x, B and C together, sequence tiles)
    norm_lanes: int     # channels a grid step of the norm: whole groups
    norm_rows: int
    norm_chunk: int
    norm_grid: tuple    # (batch, steps over the groups, sequence tiles)
    conv_fwd_bytes: int     # what the three forward calls of the convolution move through HBM
    conv_bwd_bytes: int     # ... the three backward calls
    norm_fwd_bytes: int     # ... the gated norm's forward call
    norm_bwd_bytes: int     # ... its backward call

    def describe(self) -> str:
        grid = lambda g: "x".join(map(str, g))
        return (f"lanes={self.lanes} rows={self.rows} chunk={self.chunk} "
                f"halo={self.halo} conv_grid={grid(self.conv_grid)} "
                f"norm_lanes={self.norm_lanes} norm_rows={self.norm_rows} "
                f"norm_chunk={self.norm_chunk} "
                f"norm_grid={grid(self.norm_grid)} "
                f"conv_fwd_bytes={self.conv_fwd_bytes} "
                f"conv_bwd_bytes={self.conv_bwd_bytes} "
                f"norm_fwd_bytes={self.norm_fwd_bytes} "
                f"norm_bwd_bytes={self.norm_bwd_bytes}")


def _conv_lanes(widths) -> int:
    """Channels a step of the convolution: the widest tile up to LANES that
    every segment is whole tiles of."""
    return math.gcd(*widths, _mix.LANES)


def _norm_tiles(s: int, groups: int, d: int):
    """(rows a step, rows worked on at a time, groups a step)."""
    return _mix._tiles(s, groups, groups, d)


def ssm_mix_schedule(batch: int, s: int, d_inner: int, bc: int, groups: int,
                     taps: int, itemsize: int = 2) -> SsmMixSchedule:
    """``d_inner``: the width of x (and of y, z); ``bc``: of B and of C."""
    lanes = _conv_lanes((d_inner, bc, bc))
    rows, chunk = _mix._rows_a_step(s, lanes)
    n_rows, n_chunk, n_groups = _norm_tiles(s, groups, d_inner // groups)
    n_lanes = n_groups * (d_inner // groups)
    channels = d_inner + 2 * bc
    conv_grid = (batch, channels // lanes, -(-s // rows))
    norm_grid = (batch, groups // n_groups, -(-s // n_rows))
    block, halo = rows * lanes * itemsize, _mix.HALO * lanes * itemsize
    weight = (taps + 1) * channels * 4
    n_block, scale = n_rows * n_lanes * itemsize, d_inner * 4
    return SsmMixSchedule(
        lanes, rows, chunk, _mix.HALO, conv_grid, n_lanes, n_rows, n_chunk,
        norm_grid,
        math.prod(conv_grid) * (2 * block + halo) + weight,
        math.prod(conv_grid) * (3 * block + 3 * halo) + weight + SUB * weight,
        math.prod(norm_grid) * 3 * n_block + scale,
        math.prod(norm_grid) * 5 * n_block + scale + SUB * scale)


# --------------------------------------------------------------------------
# convolution + bias -> SiLU -> [x | B | C]
# --------------------------------------------------------------------------

def conv_bias_silu(xbc, weight, bias, *, widths,
                   interpret: Optional[bool] = None):
    """``xbc [B, S, C]``, the depthwise causal convolution's ``weight [K,
    C]`` and ``bias [C]`` -> ``silu(conv(xbc) + bias)`` cut into segments of
    ``widths`` channels (they add up to C), each ``[B, S, width]`` in xbc's
    dtype. Differentiable in all three; the weight's and the bias's gradients
    are float32."""
    if interpret is None:
        interpret = _interpret_default()
    channels = xbc.shape[2]
    if sum(widths) != channels or weight.shape[1:] != (channels,) \
            or bias.shape != (channels,):
        raise ValueError(
            f"conv_bias_silu: xbc {xbc.shape} [B, S, C] with C the sum of "
            f"{tuple(widths)}; weight {weight.shape} [K, C]; bias "
            f"{bias.shape} [C]")
    refuse_taps("conv_bias_silu", weight.shape[0])
    d = _conv_lanes(widths)
    firsts = [sum(widths[:i]) // d for i in range(len(widths))]
    chain = ConvChain(
        "ssm_conv",
        tuple((name, first, width // d, False, 1.0)
              for name, first, width in zip("xbc", firsts, widths)),
        d, heads=1, biased=True, token_major=True)
    operand = jnp.concatenate([weight.astype(_F32), bias.astype(_F32)[None]])
    out = conv_chain(xbc, operand, chain, bool(interpret))
    return tuple(a.reshape(a.shape[0], *a.shape[2:]) for a in out)


# --------------------------------------------------------------------------
# the gate, then the group norm
# --------------------------------------------------------------------------

def _gate(y_ref, z_ref, at, lanes):
    """``(y, sigmoid(z), silu(z), g = y silu(z))`` of a block's rows ``at``,
    float32."""
    y = y_ref[at, lanes].astype(_F32)
    z = z_ref[at, lanes].astype(_F32)
    sig = _sigmoid(z)
    gate = z * sig
    return y, sig, gate, y * gate


def _norm_fwd_kernel(scale_ref, y_ref, z_ref, out_ref, *, groups, d, chunk,
                     eps):
    for h in range(groups):
        lanes = pl.ds(h * d, d)
        scale = scale_ref[:, lanes]

        def step(r0, carry, lanes=lanes, scale=scale):
            at = pl.ds(r0, chunk)
            g = _gate(y_ref, z_ref, at, lanes)[3]
            norm = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                                 + eps)
            out_ref[at, lanes] = ((g * norm) * scale).astype(out_ref.dtype)
            return carry

        _each_chunk(y_ref.shape[0], chunk, step)


def _norm_bwd_kernel(scale_ref, y_ref, z_ref, ct_ref, dy_ref, dz_ref,
                     dscale_ref, *, groups, d, chunk, s, eps):
    rows = y_ref.shape[0]
    tile = pl.program_id(2)

    @pl.when((pl.program_id(1) == 0) & (tile == 0))
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    ragged = s % rows != 0
    for h in range(groups):
        lanes = pl.ds(h * d, d)
        scale = scale_ref[:, lanes]

        def step(r0, acc, lanes=lanes, scale=scale):
            at = pl.ds(r0, chunk)
            y, sig, gate, g = _gate(y_ref, z_ref, at, lanes)
            ct = ct_ref[at, lanes].astype(_F32)
            norm = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                                 + eps)
            gh = g * norm
            dgh = ct * scale
            dg = norm * (dgh - gh * jnp.mean(dgh * gh, axis=-1,
                                             keepdims=True))
            dy_ref[at, lanes] = (dg * gate).astype(dy_ref.dtype)
            dz_ref[at, lanes] = ((dg * y) * (sig + gate * (1.0 - sig))
                                 ).astype(dz_ref.dtype)
            to_scale = ct * gh
            if ragged:      # rows past the sequence's end hold anything
                to_scale = jnp.where(
                    _rows_from(tile * rows + r0, chunk) < s, to_scale, 0.0)
            return acc + _fold(to_scale)

        dscale_ref[:, lanes] += _each_chunk(rows, chunk, step,
                                            jnp.zeros((SUB, d), _F32))


def _norm_specs(rows, lanes, order):
    """A step's lanes of the scale and of ``[B, S, d_inner]``; ``order`` maps
    a grid step to (batch, step over the groups, sequence tile)."""
    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *g: index(*order(*g)))

    return (spec((1, lanes), lambda b, j, i: (0, j)),
            spec((None, rows, lanes), lambda b, j, i: (b, i, j)))


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _norm_fwd_call(y, z, scale, d, eps, tiles, interpret):
    rows, chunk, groups = tiles
    bt, s, d_inner = y.shape
    scale_spec, tile = _norm_specs(rows, groups * d, lambda b, j, i: (b, j, i))
    return pl.pallas_call(
        partial(_norm_fwd_kernel, groups=groups, d=d, chunk=chunk, eps=eps),
        grid=(bt, d_inner // (groups * d), -(-s // rows)),
        in_specs=[scale_spec, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_compiler_params("parallel"),
        interpret=interpret, name="ssm_norm_fwd")(scale, y, z)


@partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _norm_bwd_call(y, z, scale, ct, d, eps, tiles, interpret):
    rows, chunk, groups = tiles
    bt, s, d_inner = y.shape
    lanes = groups * d
    # the groups outermost: a step's block of the scale's gradient stays
    # while the batch rows and sequence tiles under it are summed
    scale_spec, tile = _norm_specs(rows, lanes, lambda j, b, i: (b, j, i))
    return pl.pallas_call(
        partial(_norm_bwd_kernel, groups=groups, d=d, chunk=chunk, s=s,
                eps=eps),
        grid=(d_inner // lanes, bt, -(-s // rows)),
        in_specs=[scale_spec, tile, tile, tile],
        out_specs=[tile, tile,
                   pl.BlockSpec((SUB, lanes), lambda j, b, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((SUB, d_inner), _F32)],
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret, name="ssm_norm_bwd")(scale, y, z, ct)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(y, z, scale, groups, eps, interpret):
    return _norm_fwd(y, z, scale, groups, eps, interpret)[0]


def _norm_plan(y, groups):
    d = y.shape[2] // groups
    return d, _norm_tiles(y.shape[1], groups, d)


def _norm_fwd(y, z, scale, groups, eps, interpret):
    d, tiles = _norm_plan(y, groups)
    return (_norm_fwd_call(y, z, scale, d, eps, tiles, interpret),
            (y, z, scale))


def _norm_bwd(groups, eps, interpret, res, ct):
    y, z, scale = res
    d, tiles = _norm_plan(y, groups)
    dy, dz, dscale = _norm_bwd_call(y, z, scale, ct.astype(y.dtype), d, eps,
                                    tiles, interpret)
    return dy, dz, dscale.sum(axis=0, keepdims=True)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_group_norm(y, z, scale, *, groups: int, eps: float,
                     interpret: Optional[bool] = None):
    """``y, z [B, S, d_inner]`` and the norm's ``scale [d_inner]`` ->
    ``GroupRMSNorm(y * silu(z)) * scale`` over each of ``groups`` groups of
    ``d_inner / groups`` features, in z's dtype. Differentiable in all three;
    the scale's gradient is float32."""
    if interpret is None:
        interpret = _interpret_default()
    d_inner = z.shape[-1]
    if y.shape != z.shape or scale.shape != (d_inner,) or d_inner % groups:
        raise ValueError(f"gated_group_norm: y {y.shape}, z {z.shape} [B, S, "
                         f"d_inner], scale {scale.shape} [d_inner], "
                         f"{groups} groups")
    d = d_inner // groups
    if d % _mix.LANES and _mix.LANES % d:
        raise ValueError(
            f"gated_group_norm: groups of {d} features: a block is whole "
            f"groups and whole tiles of {_mix.LANES} lanes, so a group is a "
            "multiple of a tile or a whole share of one")
    return _norm(y.astype(z.dtype), z, scale.astype(_F32).reshape(1, d_inner),
                 groups, float(eps), bool(interpret))
