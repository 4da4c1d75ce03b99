"""The Gated DeltaNet mixer: linear attention whose state is a matrix a head,
corrected by the delta rule under a learned decay (Qwen3-Next's linear
layers; Gated Delta Networks, arXiv:2412.06464).

The first half of a block (``x + mixer(norm(x))``), written as a function
called from the block's ``@nn.compact`` body like ``attention_sublayer`` and
``models/ssm.mamba_sublayer``, so its sub-modules live in the block's scope
under the names given here. The norm is the caller's (``make_norm``); the
sizes are the arch's. With ``a = norm(x)``, ``Hk`` key heads of ``dk`` and
``Hv`` value heads of ``dv``:

    [q, k, v, z] = in_proj_qkvz(a)              d -> 2 Hk dk + 2 Hv dv, no bias
    [b, alpha]   = in_proj_ba(a)                d -> 2 Hv, no bias
    [q, k, v]    = silu(conv1d([q, k, v]))      depthwise, causal, taps, no bias    }
    q = q / sqrt(sum q^2 + 1e-6) / sqrt(dk)     k = k / sqrt(sum k^2 + 1e-6)        } conv_silu_l2norm
    beta = sigmoid(b)     g = -exp(A_log) softplus(alpha + dt_bias)     float32, a value head
    o = gated_delta_rule(q, k, v, g, beta)      ops/gated_delta_rule.py
    o = RMSNorm_dv(o) * silu(z)                 a head; one plain scale [dv]        } gated_rms_norm
    out = out_proj(o)                           Hv dv -> d, no bias

Value heads ``r j .. r j + r - 1`` read key head ``j`` (``r = Hv / Hk``). The
projections are laid out side by side (``[q | k | v | z]``, ``[b | alpha]``);
the published checkpoint groups them a key head, which is a loader's matter.

The two bracketed chains are one op each, ``ops/gdn_mix.py`` (Pallas, since
PR 41): each makes one pass over HBM forward and one backward and keeps its
inputs alone for the backward, which forms the convolution, its SiLU and the
statistics again inside a tile. The plain chain they replace (``jax.numpy``
ops on ``models/ssm.causal_conv1d`` and ``nn.RMSNorm``, differentiated by
JAX) lives on in ``tests/test_gdn_mix.py`` as what they are held to;
``causal_conv1d`` itself stays ``mamba_sublayer``'s.

Precision under a narrower compute dtype: the projections and the kernel's
matmul operands run in it, and q, k, v, the gated output and the gradients
that leave an op are rounded to it once; inside a tile everything is
float32: the convolution's products and sums (the plain chain rounded each
tap's multiply-add to the compute dtype), the SiLU, the l2 norms, the output
norm's statistics and its product with ``silu(z)``, and the gradients of the
convolution's weight and the norm's scale, summed over the tokens.
``beta``, ``g`` and the delta rule's state are float32 as before.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from ps_pytorch_tpu.models.ssm import (
    _dt_bias_init, _NormScale, _symmetric_uniform,
)
from ps_pytorch_tpu.telemetry.trace import device_scope

A_MAX = 16.0        # A = exp(A_log) is drawn from U(0, A_MAX) a value head


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(0, 16)``: the published code's. (Its ``dt_bias = ones`` is a
    placeholder a checkpoint overwrites: with it g = -1.31 A, and all but the
    heads that draw A < 0.8 lose more than 1/e of their state a token. The
    bias is Mamba's, ``models/ssm._dt_bias_init``: the inverse softplus of a
    step drawn log-uniformly from [0.001, 0.1], the linear-attention
    library's.)"""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4, A_MAX)
                   ).astype(dtype)


def gdn_sublayer(mod: nn.Module, x, norm: nn.Module, *, dtype,
                 key_heads: int, value_heads: int, key_dim: int,
                 value_dim: int, conv: int, norm_eps: float):
    """``x + GatedDeltaNet(norm(x))``, ``norm(x)`` and ``{"gdn_state_abs_max":
    ...}``: the largest |state| at the delta rule's chunk boundaries.
    ``mod``: the block, whose scope holds the parameters."""
    # where the arch asks for it: the other archs' start-up does not pay for it
    from ps_pytorch_tpu.ops.gated_delta_rule import gated_delta_rule
    from ps_pytorch_tpu.ops.gdn_mix import conv_silu_l2norm, gated_rms_norm

    b, s, d = x.shape
    k_width, v_width = key_heads * key_dim, value_heads * value_dim
    dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dtype, name=name)
    with device_scope("gdn_proj"):
        a = norm(x)
        qkv, z = jnp.split(dense(2 * k_width + 2 * v_width,
                                 "in_proj_qkvz")(a), [2 * k_width + v_width],
                           axis=-1)
        ba = dense(2 * value_heads, "in_proj_ba")(a)
    with device_scope("gdn_mix"):
        conv_w = mod.param("conv_weight", _symmetric_uniform(conv ** -0.5),
                           (conv, 2 * k_width + v_width))
        q, k, v = conv_silu_l2norm(qkv, conv_w, key_heads=key_heads,
                                   value_heads=value_heads, key_dim=key_dim,
                                   value_dim=value_dim)
        a_log = mod.param("A_log", _a_log_init, (value_heads,))
        dt_bias = mod.param("dt_bias", _dt_bias_init, (value_heads,))
        beta, alpha = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
        beta = jax.nn.sigmoid(beta)
        g = -jnp.exp(a_log) * jax.nn.softplus(alpha + dt_bias)
    with device_scope("gdn_core"):
        o, state_max = gated_delta_rule(q, k, v, g, beta)
    with device_scope("gdn_mix"):
        o = gated_rms_norm(o, z, _NormScale(name="gdn_norm")(value_dim),
                           eps=norm_eps)
    with device_scope("gdn_proj"):
        x = x + dense(d, "out_proj")(o)
    return x, a, {"gdn_state_abs_max": state_max}
