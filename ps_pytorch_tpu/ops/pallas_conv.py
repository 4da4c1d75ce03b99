"""Pallas 3x3 convolution prototype for the HBM-bound early ResNet blocks.

Why this exists (PERF.md §7, VERDICT r4 next #4): the round-4 chip trace
shows the headline step is 92% conv time, and its early blocks (32x32 /
16x16 spatial, 64 channels — plus their ``transpose(jvp)`` backward twins,
the top-5 ops) run HBM-bound at ~486 GB/s / 65-80 bf16 TF/s while the deep
blocks hit 119-169 TF/s. At 486 GB/s the observed op time implies XLA moves
roughly 2x the minimal activation bytes for these geometries, so a kernel
that reads each input byte once has headroom ~1.4x on ~35% of the step —
IF its MXU schedule doesn't give the advantage back (Cout=64 fills only
half the 128-lane MXU tile; that waste is intrinsic to the geometry). This
module is the accept/reject experiment: correctness is pinned here and in
``tests/test_pallas_conv.py`` (interpret mode off-TPU, same semantics).
The A/B against ``lax.conv_general_dilated`` in a benchmark cell on the
chip has not been run (ROADMAP Speed 3); the decision is made on that
number, not on this docstring.

Scope (deliberately the trace's hot geometry, not a general conv):
NHWC, 3x3, stride 1, SAME padding, C_in/C_out free (lane-efficient when
multiples of 128, the headline case is 64). Decomposition: 9 shifted
matmuls — for each tap (dy, dx), ``out += x[:, dy:dy+H, dx:dx+W, :] @
w[dy, dx]`` — accumulated in an f32 VMEM scratch; one HBM read of x, one
HBM write of out per batch tile. The grad-input twin is the same kernel on
spatially-flipped, in/out-transposed weights (what ``transpose(jvp)`` of a
stride-1 SAME conv is), so an accept covers the backward hotspot too.

Reference counterpart: none (CUDA/cuDNN convs are the reference's vendor
black box; this is the TPU-native equivalent of writing a custom kernel
for one profiled hotspot).
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default


def _conv_kernel(x_ref, w_ref, o_ref, acc, *, h, w, c_out, variant):
    """One batch tile: x_ref [Bt, H+2, W+2, C], w_ref [9C, Co] (tap-major),
    o_ref [Bt, H, W, Co], acc f32 [Bt*H*W, Co].

    Two MXU schedules, chosen by the on-chip A/B:
    - ``taps9``: 9 accumulating dots, K = C each (K=64 quarter-fills the
      128x128 MXU at the hot geometry, but no patch materialization);
    - ``im2col``: one dot, K = 9C (K=576 keeps the systolic K dim ~90%
      fed; pays a [rows, 9C] lane-concat relayout in VMEM).
    """
    bt = o_ref.shape[0]
    c_in = x_ref.shape[-1]

    def tap(t):
        # NOTE: laziness here is style, not VMEM control — the traced jaxpr
        # is identical either way and Mosaic schedules by dataflow. VMEM
        # residency is governed by block_n (and the im2col halving in
        # conv3x3), not by where these slices appear in Python.
        dy, dx = divmod(t, 3)
        return x_ref[:, dy:dy + h, dx:dx + w, :].reshape(bt * h * w, c_in)

    if variant == "im2col":
        patches = jnp.concatenate([tap(t) for t in range(9)], axis=1)
        acc[:] = jax.lax.dot_general(
            patches, w_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc[:] = jnp.zeros_like(acc)
        for t in range(9):
            acc[:] += jax.lax.dot_general(
                tap(t), w_ref[t * c_in:(t + 1) * c_in, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[:] = acc[:].reshape(bt, h, w, c_out).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("block_n", "interpret", "variant"))
def _conv3x3(x, w, block_n, interpret, variant):
    n, h, wd, c = x.shape
    c_out = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    w2 = w.reshape(9 * c, c_out)
    return pl.pallas_call(
        partial(_conv_kernel, h=h, w=wd, c_out=c_out, variant=variant),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, h + 2, wd + 2, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * c, c_out), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, h, wd, c_out),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, wd, c_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n * h * wd, c_out), jnp.float32)],
        name="conv3x3",
        interpret=interpret,
    )(xp, w2)


def effective_block_n(n: int, block_n: int = 4,
                      variant: str = "taps9") -> int:
    """The batch tile ``conv3x3`` ACTUALLY runs for a requested block_n:
    im2col materializes [Bt*H*W, 9C] patches in VMEM, so its tile is halved
    to stay under the double-buffering budget — halved BEFORE the
    divisibility shrink (halving afterwards could yield a block_n that no
    longer divides N, and grid = N // block_n would then silently leave the
    tail batch rows unwritten). Exposed so an A/B can record the tile each
    variant really used, with one source of truth."""
    if variant == "im2col":
        block_n = max(block_n // 2, 1)
    while n % block_n:
        block_n //= 2
    return max(block_n, 1)


def conv3x3(x, w, *, block_n: int = 4, variant: str = "taps9",
            interpret: Optional[bool] = None) -> jax.Array:
    """NHWC 3x3 stride-1 SAME conv. x [N,H,W,C] @ w [3,3,C,Co] -> [N,H,W,Co].

    ``block_n`` is the batch tile per grid step (auto-shrunk to divide N,
    halved first for im2col — see effective_block_n); ``variant`` picks the
    MXU schedule (see _conv_kernel). f32 accumulation regardless of dtype —
    matches ``lax.conv_general_dilated(..., preferred_element_type=f32)``.
    """
    if x.ndim != 4 or w.shape[:2] != (3, 3) or w.shape[2] != x.shape[-1]:
        raise ValueError(f"need x [N,H,W,C] and w [3,3,C,Co]; got "
                         f"{x.shape} / {w.shape}")
    if variant not in ("taps9", "im2col"):
        raise ValueError(f"unknown variant {variant!r}")
    if interpret is None:
        interpret = _interpret_default()
    return _conv3x3(x, w, effective_block_n(x.shape[0], block_n, variant),
                    interpret, variant)


def conv3x3_input_grad(g, w, *, block_n: int = 4, variant: str = "taps9",
                       interpret: Optional[bool] = None) -> jax.Array:
    """Gradient w.r.t. the conv INPUT — the trace's ``transpose(jvp)``
    backward twin. For stride-1 SAME, d/dx is itself a 3x3 SAME conv of the
    cotangent with spatially-flipped, channel-transposed weights."""
    wt = jnp.flip(w, axis=(0, 1)).swapaxes(2, 3)
    return conv3x3(g, wt, block_n=block_n, variant=variant,
                   interpret=interpret)


# ---------------------------------------------------------------------------
# Differentiable op + flax module, so an accepted kernel is adoptable in the
# headline model (a kernel that wins its microbench but can't be trained
# through decides nothing).
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def conv3x3_op(x, w, variant="taps9"):
    """Differentiable 3x3 SAME conv: Pallas forward, Pallas input-grad,
    XLA weight-grad (dW was never the HBM-bound hotspot — the trace's top
    ops are the activation-sized fwd/input-grad convs, PERF.md §7)."""
    return conv3x3(x, w, variant=variant)


def _conv_op_fwd(x, w, variant):
    return conv3x3(x, w, variant=variant), (x, w)


def _conv_op_bwd(variant, res, g):
    x, w = res
    dx = conv3x3_input_grad(g, w, variant=variant)
    # dW[dy,dx,ci,co] = sum_{n,h,w} xpad[n,h+dy,w+dx,ci] g[n,h,w,co] —
    # 9 contraction einsums, left to XLA (reduction-shaped, not the
    # bandwidth-bound twin this prototype targets).
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    h, wd = x.shape[1], x.shape[2]
    # f32 ACCUMULATION via preferred_element_type, not astype: upcasting
    # the operands would let XLA materialize f32 copies of activation-sized
    # tensors — HBM traffic this prototype exists to avoid.
    taps = [jnp.einsum("nhwc,nhwd->cd",
                       xp[:, dy:dy + h, dx:dx + wd, :], g,
                       preferred_element_type=jnp.float32)
            for dy in range(3) for dx in range(3)]
    dw = jnp.stack(taps).reshape(3, 3, *taps[0].shape).astype(w.dtype)
    return dx, dw


conv3x3_op.defvjp(_conv_op_fwd, _conv_op_bwd)
