"""The state-space mixers: a hybrid decoder's Mamba-1 layer and the gated
memory unit that reads a Mamba layer's scan output, and the Mamba-2 layer.

Both are the first half of a ``models/transformer.Block`` (``x + mixer(norm(
x))``), written as functions called from the block's ``@nn.compact`` body
like ``attention_sublayer``, so their sub-modules live in the block's scope
under the names given here. The norm is the caller's (``make_norm``): this
module knows no arch.

Mamba-1 (arXiv:2312.00752, the family's defaults; ``d_inner = expand * d``,
``dt_rank = ceil(d / 16)``):

    [u, z] = in_proj(a)                         d -> 2 d_inner, no bias
    u      = silu(conv1d(u))                    depthwise, causal, d_conv taps, bias
    [dt, B, C] = x_proj(u)                      d_inner -> dt_rank + 2 d_state, no bias
    delta  = softplus(dt_proj(dt))              dt_rank -> d_inner, bias
    A      = -exp(A_log)                        [d_inner, d_state]
    m      = selective_scan(u, delta, A, B, C, D)       ops/selective_scan.py
    out    = out_proj(m * silu(z))              d_inner -> d, no bias

``m`` (before the gate, ``D * u`` included) is what the layer hands on. The
gated memory unit (SambaY, arXiv:2507.06607) is ``out_proj(silu(in_proj(a)) *
m)`` with ``in_proj: d -> d_inner`` and ``out_proj: d_inner -> d``, no biases.

Precision under a narrower compute dtype: the projections and the
convolution run in it; ``delta`` (the bias's add and the softplus), ``A``,
``B``, ``C`` and the scan's state are float32; ``m`` leaves in the compute
dtype.

Mamba-2 (arXiv:2405.21060; ``d_inner = heads * head_dim`` whatever ``d`` is,
``G`` groups of ``N`` states):

    [z, xBC, dt] = in_proj(a)                   d -> d_inner + (d_inner + 2 G N) + heads, no bias
    xBC    = silu(conv1d(xBC))                  depthwise, causal, d_conv taps, bias                        }
    [x, B, C] = xBC                             x: heads of head_dim; B, C: G groups of N, head h reads group h // (heads / G)     } conv_bias_silu
    delta  = softplus(dt + dt_bias)             a head, float32, no clamp
    A      = -exp(A_log)                        a scalar a head
    y      = ssd(x, delta, A, B, C, D)          ops/ssd.py; D a scalar a head
    y      = GroupRMSNorm(y * silu(z))          the gate FIRST, then the norm over each of G groups of d_inner / G; one scale [d_inner]    } gated_group_norm
    out    = out_proj(y)                        d_inner -> d, no bias

**A share of the heads** (``mamba2_sublayer``'s ``heads`` is the count HELD;
tensor parallelism's layer): column-parallel in, row-parallel out. ``in_proj``
holds the held heads' columns of z, x and dt and B and C WHOLE (a group's B
and C serve heads on every chip that holds some of the group), the
convolution their channels, ``out_proj`` the held heads' rows, so its result is
this chip's PART of the sublayer's output. The gated norm's statistic spans a
group's heads wherever they lie: with a mesh axis bound (``axis_name``) the
sum of squares and the count are summed over it (a scalar a token and group)
and so is the partial output; with none both are this chip's own, the norm is
over the held channels alone, no collective runs and none is emulated, and
what the absent heads would add is left out.

The two bracketed chains are one op each, ``ops/ssm_mix.py`` (Pallas, since
PR 45; the convolution's kernels are ``ops/gdn_mix.py``'s): each makes one
pass over HBM forward and one backward and keeps its inputs alone for the
backward. The plain chain they replace (``jax.numpy`` ops on
``causal_conv1d``, differentiated by JAX) lives on in
``tests/test_ssm_mix.py`` as what they are held to; ``causal_conv1d`` itself
stays ``mamba_sublayer``'s.

Precision: the projections in the compute dtype; ``delta``, ``A`` and the
state float32; inside an op's tile everything is float32: the convolution's
products, their sum and the bias (the plain chain rounded each tap's
multiply-add to the compute dtype), the SiLU, the gate's product, the norm's
statistics and its scale, and the gradients of the convolution's weight and
bias and of the scale, summed over the tokens. ``x``, ``B``, ``C``, the normed
rows and the gradients that leave an op are rounded to the compute dtype
once; ``y`` leaves ``ssd`` in it.
"""

import math
from functools import partial
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ps_pytorch_tpu.models.remat import kept
from ps_pytorch_tpu.ops.selective_scan import selective_scan
from ps_pytorch_tpu.telemetry.trace import device_scope

# The family's initialisers (mamba_ssm Mamba.__init__): delta's bias is the
# inverse softplus of a step drawn log-uniformly from [DT_MIN, DT_MAX].
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def dt_rank(d_model: int) -> int:
    return math.ceil(d_model / 16)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _symmetric_uniform(bound: float):
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


def causal_conv1d(u, weight, bias):
    """Depthwise causal convolution over the sequence: ``out[t] = bias +
    sum_k weight[k] * u[t - (K - 1) + k]`` with zeros before the sequence
    (``torch.nn.Conv1d(groups=channels, padding=K - 1)`` cut to S). u: [B, S,
    C]; weight: [K, C]; bias: [C]. K shifted copies, multiplied and added:
    K is 4."""
    taps, s = weight.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(u.dtype)
    for k in range(taps):
        out = out + weight[k].astype(u.dtype) * padded[:, k:k + s]
    return out


def mamba_sublayer(mod: nn.Module, x, norm: nn.Module, *, dtype,
                   d_state: int, d_conv: int, expand: int):
    """``x + Mamba(norm(x))`` and ``{"memory": m, "ssm_state_abs_max": ...}``:
    the scan's output before the gate, and the largest |state| at the scan's
    chunk boundaries. ``mod``: the block, whose scope holds the parameters."""
    d = x.shape[-1]
    d_inner, rank = expand * d, dt_rank(d)
    dense = lambda n, name, **kw: nn.Dense(n, dtype=dtype, name=name, **kw)
    with device_scope("ssm_proj"):
        a = norm(x)
        u, z = jnp.split(dense(2 * d_inner, "in_proj", use_bias=False)(a), 2,
                         axis=-1)
    with device_scope("ssm_conv"):
        conv_w = mod.param("conv_weight",
                           _symmetric_uniform(d_conv ** -0.5),
                           (d_conv, d_inner))
        conv_b = mod.param("conv_bias", _symmetric_uniform(d_conv ** -0.5),
                           (d_inner,))
        u = nn.silu(causal_conv1d(u, conv_w, conv_b))
    with device_scope("ssm_proj"):
        dt, b, c = jnp.split(
            dense(rank + 2 * d_state, "x_proj", use_bias=False)(u),
            [rank, rank + d_state], axis=-1)
        # the bias is added in float32 below, under the softplus
        dt = dense(d_inner, "dt_proj", use_bias=False,
                   kernel_init=_symmetric_uniform(rank ** -0.5))(dt)
    with device_scope("ssm_conv"):
        dt_bias = mod.param("dt_bias", _dt_bias_init, (d_inner,))
        delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    a_log = mod.param(
        "A_log", lambda key, shape: jnp.log(jnp.broadcast_to(
            jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)),
        (d_inner, d_state))
    skip = mod.param("D", nn.initializers.ones, (d_inner,))
    with device_scope("ssm_scan"):
        m, state_max = selective_scan(u, delta, -jnp.exp(a_log), b, c, skip)
    with device_scope("ssm_conv"):
        gated = m * nn.silu(z)
    with device_scope("ssm_proj"):
        x = x + dense(d, "out_proj", use_bias=False)(gated)
    return x, {"memory": m, "ssm_state_abs_max": state_max}


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(1, 16)`` a head: mamba_ssm's Mamba2 ``A_init_range``."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


class _NormScale(nn.Module):
    """A gated output norm's one parameter, under the name and shape
    ``nn.RMSNorm`` would give it (``<name>/scale [features]``, ones): the
    Mamba-2 layer's ``ssm_norm`` and ``models/gdn.py``'s ``gdn_norm``."""

    @nn.compact
    def __call__(self, features):
        return self.param("scale", nn.initializers.ones, (features,))


def _gated_group_norm_over(y, z, scale, *, groups: int, eps: float,
                           axis_name: str):
    """``ops/ssm_mix.gated_group_norm`` with each group's mean square taken
    over ``axis_name`` too: the sum of squares and the count of the channels
    held here, each summed over the axis. Plain ``jax.numpy`` in float32:
    the form that runs where shares of a layer's heads meet, which one chip
    never does."""
    shape = y.shape
    g = (y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))).reshape(
        *shape[:-1], groups, -1)
    squares = jax.lax.psum(jnp.sum(g * g, axis=-1, keepdims=True), axis_name)
    count = jax.lax.psum(g.shape[-1], axis_name)
    g = g * jax.lax.rsqrt(squares / count + eps)
    return (g.reshape(shape) * scale).astype(z.dtype)


def mamba2_sublayer(mod: nn.Module, x, norm: nn.Module, *, dtype, heads: int,
                    head_dim: int, groups: int, d_state: int, d_conv: int,
                    chunk: int, norm_eps: float, out_scale: float = 1.0,
                    axis_name: Optional[str] = None):
    """``x + out_scale * Mamba2(norm(x))``, ``norm(x)`` and
    ``{"ssd_state_abs_max": ...}``: the largest |state| at the chunk
    boundaries. ``mod``: the block, whose scope holds the parameters.
    ``heads``: the heads held here, with B and C of all ``groups`` whole;
    ``axis_name``: the mesh axis the shares of a layer's heads lie along,
    where one is bound (the module's docstring)."""
    # where the arch asks for it: the other archs' start-up does not pay for it
    from ps_pytorch_tpu.ops.ssd import ssd
    from ps_pytorch_tpu.ops.ssm_mix import conv_bias_silu, gated_group_norm

    bt, s, d = x.shape
    d_inner, bc = heads * head_dim, groups * d_state
    dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dtype, name=name)
    with device_scope("ssm_proj"):
        a = norm(x)
        z, xbc, dt = jnp.split(
            dense(2 * d_inner + 2 * bc + heads, "in_proj")(a),
            [d_inner, 2 * d_inner + 2 * bc], axis=-1)
        # all the kernels' backward reads of the block's recomputed forward
        z, xbc, dt = kept(z, "ssm_z"), kept(xbc, "ssm_xbc"), kept(dt, "ssm_dt")
    with device_scope("ssm_conv"):
        init = _symmetric_uniform(d_conv ** -0.5)
        conv_w = mod.param("conv_weight", init, (d_conv, d_inner + 2 * bc))
        conv_b = mod.param("conv_bias", init, (d_inner + 2 * bc,))
        u, b, c = conv_bias_silu(xbc, conv_w, conv_b,
                                 widths=(d_inner, bc, bc))
        dt_bias = mod.param("dt_bias", _dt_bias_init, (heads,))
        delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    a_log = mod.param("A_log", _a_log_init, (heads,))
    skip = mod.param("D", nn.initializers.ones, (heads,))
    with device_scope("ssd_core"):
        y, state_max = ssd(u.reshape(bt, s, heads, head_dim), delta,
                           -jnp.exp(a_log),
                           b.reshape(bt, s, groups, d_state),
                           c.reshape(bt, s, groups, d_state), skip,
                           chunk=chunk)
    with device_scope("ssm_conv"):
        mix_norm = gated_group_norm if axis_name is None else partial(
            _gated_group_norm_over, axis_name=axis_name)
        g = mix_norm(y.reshape(bt, s, d_inner), z,
                     _NormScale(name="ssm_norm")(d_inner),
                     groups=groups, eps=norm_eps)
    with device_scope("ssm_proj"):
        # what a block's second half starts from, bar a scale and a sum
        out = kept(dense(d, "out_proj")(g), "ssm_out")
        if axis_name is not None:       # the shares' parts of the output
            out = jax.lax.psum(out, axis_name)
        if out_scale != 1.0:
            out = out * jnp.asarray(out_scale, dtype)
        x = x + out
    return x, a, {"ssd_state_abs_max": state_max}


def gmu_sublayer(x, memory, norm: nn.Module, *, dtype):
    """``x + out_proj(silu(in_proj(norm(x))) * memory)``: the gated memory
    unit, on a Mamba layer's scan output ``memory [B, S, d_inner]``."""
    dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dtype, name=name)
    with device_scope("gmu"):
        gate = nn.silu(dense(memory.shape[-1], "in_proj")(norm(x)))
        return x + dense(x.shape[-1], "out_proj")(gate * memory)
