"""Fused Adam / AMSGrad parameter update (Pallas).

Companion to ``ops/fused_sgd.py``: ONE kernel invocation over a flat
concatenation of every parameter leaf performs the reference's exact Adam
update (``optim/adam.py:38-94``: weight-decay fold, biased first/second
moments, optional AMSGrad max, torch-style eps OUTSIDE the sqrt,
bias-corrected step size) in a single HBM read+write pass with params and
both moment buffers aliased in place. The bias-correction scalar is
computed host-side per step and fed through SMEM. (Flat layout for the
same reason as fused_sgd: a kernel per leaf pays per-launch overhead that
swamps the single-pass win at CNN scale.)

Off-TPU the kernel runs in Pallas interpreter mode; golden tests assert
agreement with ``optim.adam`` (itself a golden transcription of the
reference's torch fork).
"""

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.optim.adam import AdamState
from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default
from ps_pytorch_tpu.ops.fused_sgd import LANES, BLOCK_ROWS, _pad2d


def _make_kernel(b1: float, b2: float, eps: float, weight_decay: float,
                 amsgrad: bool):
    if amsgrad:
        def kernel(ss_ref, p_ref, m_ref, v_ref, vh_ref, g_ref,
                   p_out, m_out, v_out, vh_out):
            step_size = ss_ref[0, 0]
            p = p_ref[:]
            g = g_ref[:]
            if weight_decay != 0.0:
                g = g + weight_decay * p
            m = b1 * m_ref[:] + (1.0 - b1) * g
            v = b2 * v_ref[:] + (1.0 - b2) * g * g
            vh = jnp.maximum(vh_ref[:], v)
            p_out[:] = p - step_size * m / (jnp.sqrt(vh) + eps)
            m_out[:] = m
            v_out[:] = v
            vh_out[:] = vh
    else:
        def kernel(ss_ref, p_ref, m_ref, v_ref, g_ref, p_out, m_out, v_out):
            step_size = ss_ref[0, 0]
            p = p_ref[:]
            g = g_ref[:]
            if weight_decay != 0.0:
                g = g + weight_decay * p
            m = b1 * m_ref[:] + (1.0 - b1) * g
            v = b2 * v_ref[:] + (1.0 - b2) * g * g
            p_out[:] = p - step_size * m / (jnp.sqrt(v) + eps)
            m_out[:] = m
            v_out[:] = v
    return kernel


@partial(jax.jit, static_argnames=("b1", "b2", "eps", "weight_decay",
                                   "amsgrad", "interpret"))
def _fused_update_padded(bufs, step_size, *, b1, b2, eps, weight_decay,
                         amsgrad, interpret):
    # bufs: (p2d, m2d, v2d[, vh2d], g2d) all [R, 128] float32.
    nblk = bufs[0].shape[0] // BLOCK_ROWS
    vspec = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    n_out = len(bufs) - 1            # every state buffer except g is updated
    shape = jax.ShapeDtypeStruct(bufs[0].shape, jnp.float32)
    return pl.pallas_call(
        _make_kernel(b1, b2, eps, weight_decay, amsgrad),
        grid=(nblk,),
        in_specs=[sspec] + [vspec] * len(bufs),
        out_specs=[vspec] * n_out,
        out_shape=[shape] * n_out,
        # p, m, v(, vh) update in place; operand 0 is step_size, g is last.
        input_output_aliases={i + 1: i for i in range(n_out)},
        name="fused_adam",
        interpret=interpret,
    )(jnp.reshape(step_size.astype(jnp.float32), (1, 1)), *bufs)


class FusedAdam:
    """Drop-in fused optimizer (same ``init`` contract as ``optim.adam``);
    dispatched by the train steps via its ``apply`` method."""

    def __init__(self, lr, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, weight_decay: float = 0.0,
                 amsgrad: bool = False, interpret: Optional[bool] = None):
        self.lr = lr
        self.b1, self.b2, self.eps = b1, b2, eps
        self.weight_decay = weight_decay
        self.amsgrad = amsgrad
        self.interpret = interpret

    def init(self, params) -> AdamState:
        z = lambda: jax.tree.map(jnp.zeros_like, params)
        return AdamState(step=jnp.zeros((), jnp.int32), exp_avg=z(),
                         exp_avg_sq=z(),
                         max_exp_avg_sq=z() if self.amsgrad else ())

    def apply(self, params: Any, state: AdamState, grads: Any):
        import numpy as np

        interpret = self.interpret
        if interpret is None:
            interpret = _interpret_default()
        t = state.step + 1
        tf = t.astype(jnp.float32)
        lr_t = self.lr(state.step) if callable(self.lr) else self.lr
        step_size = lr_t * jnp.sqrt(1 - self.b2 ** tf) / (1 - self.b1 ** tf)

        leaves_p, treedef = jax.tree.flatten(params)
        sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in leaves_p]
        flat = lambda tree: jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32)
             for l in jax.tree.flatten(tree)[0]])
        bufs = [_pad2d(flat(params))[0], _pad2d(flat(state.exp_avg))[0],
                _pad2d(flat(state.exp_avg_sq))[0]]
        if self.amsgrad:
            bufs.append(_pad2d(flat(state.max_exp_avg_sq))[0])
        bufs.append(_pad2d(flat(grads))[0])
        outs = _fused_update_padded(
            tuple(bufs), step_size, b1=self.b1, b2=self.b2, eps=self.eps,
            weight_decay=self.weight_decay, amsgrad=self.amsgrad,
            interpret=interpret)

        def unflat(a2d):
            vec = a2d.reshape(-1)
            res, off = [], 0
            for leaf, size in zip(leaves_p, sizes):
                res.append(vec[off:off + size].reshape(leaf.shape)
                           .astype(leaf.dtype))
                off += size
            return jax.tree.unflatten(treedef, res)

        return unflat(outs[0]), AdamState(
            step=t, exp_avg=unflat(outs[1]), exp_avg_sq=unflat(outs[2]),
            max_exp_avg_sq=unflat(outs[3]) if self.amsgrad else ())
