"""Fused momentum-SGD parameter update (Pallas).

ONE kernel invocation for the whole parameter tree computes the reference's
exact SGD update (``optim/sgd.py:75-91``: weight-decay fold, first-step
momentum init, dampening, Nesterov) in a single HBM read+write pass over a
flat concatenation of all leaves, with the parameter and momentum buffers
aliased in-place (``input_output_aliases``) — where the composed optax path
emits several elementwise kernels over the same bytes. The update is
bandwidth-bound, so passes over HBM are the cost model; the flat layout
exists because a kernel-per-leaf variant paid ~60 pallas_call launches on
ResNet-18 and measured 2.4% slower than optax on v5e.

Off-TPU the kernel runs in Pallas interpreter mode; golden tests assert
bit-level agreement with ``optim.sgd`` (the optax transform) on the CPU mesh.
"""

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.optim.sgd import SGDState

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default

LANES = 128
BLOCK_ROWS = 256          # f32 tile multiple (8); 256*128*4B = 128 KiB/block


def _make_kernel(momentum: float, dampening: float, weight_decay: float,
                 nesterov: bool):
    def kernel(lr_ref, first_ref, p_ref, b_ref, g_ref, p_out, b_out):
        lr = lr_ref[0, 0]
        first = first_ref[0, 0] != 0
        p = p_ref[:]
        d_p = g_ref[:]
        if weight_decay != 0.0:
            d_p = d_p + weight_decay * p
        buf = jnp.where(first, d_p,
                        momentum * b_ref[:] + (1.0 - dampening) * d_p)
        d = d_p + momentum * buf if nesterov else buf
        p_out[:] = p - lr * d
        b_out[:] = buf
    return kernel


@partial(jax.jit,
         static_argnames=("momentum", "dampening", "weight_decay",
                          "nesterov", "interpret"))
def _fused_update_padded(p2d, b2d, g2d, lr, first, *, momentum, dampening,
                         weight_decay, nesterov, interpret):
    nblk = p2d.shape[0] // BLOCK_ROWS
    vspec = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _make_kernel(momentum, dampening, weight_decay, nesterov),
        grid=(nblk,),
        in_specs=[sspec, sspec, vspec, vspec, vspec],
        out_specs=[vspec, vspec],
        out_shape=[jax.ShapeDtypeStruct(p2d.shape, jnp.float32),
                   jax.ShapeDtypeStruct(b2d.shape, jnp.float32)],
        input_output_aliases={2: 0, 3: 1},   # p, buf update in place
        name="fused_sgd",
        interpret=interpret,
    )(jnp.reshape(lr.astype(jnp.float32), (1, 1)),
      jnp.reshape(first.astype(jnp.int32), (1, 1)),
      p2d, b2d, g2d)


def _pad2d(a: jax.Array):
    size = a.size
    rows = max(-(-size // LANES), 1)
    rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    pad = rows * LANES - size
    return jnp.pad(jnp.ravel(a).astype(jnp.float32), (0, pad)).reshape(rows, LANES), pad


class FusedSGD:
    """Drop-in optimizer for the SPMD step's fused path.

    Same ``init`` contract as the optax transform (``optim.sgd``) so
    TrainState/checkpoints are interchangeable; ``apply`` replaces
    update+apply_updates with the single-pass kernel. ``make_train_step``
    dispatches on the presence of ``apply``.
    """

    def __init__(self, lr, momentum: float = 0.0, dampening: float = 0.0,
                 weight_decay: float = 0.0, nesterov: bool = False,
                 interpret: Optional[bool] = None):
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError("Nesterov momentum requires a momentum and zero dampening")
        self.lr = lr
        self.momentum = momentum
        self.dampening = dampening
        self.weight_decay = weight_decay
        self.nesterov = nesterov
        self.interpret = interpret

    def init(self, params) -> SGDState:
        # Momentum buffers always exist on the fused path (the kernel reads
        # them); momentum==0 degrades gracefully (buf = d_p each step).
        return SGDState(step=jnp.zeros((), jnp.int32),
                        momentum=jax.tree.map(jnp.zeros_like, params))

    def apply(self, params: Any, state: SGDState, grads: Any):
        """-> (new_params, new_state).

        The whole parameter tree updates in ONE kernel invocation: leaves
        are concatenated into a single flat f32 vector (two extra
        bandwidth passes, ~0.1 ms at ResNet-18 scale), padded once, and
        the update runs as a single grid — instead of one ``pallas_call``
        per leaf (~60 launches for ResNet-18, the measured overhead that
        made the per-leaf variant 2.4% SLOWER than optax on v5e)."""
        interpret = self.interpret
        if interpret is None:
            interpret = _interpret_default()
        lr_t = self.lr(state.step) if callable(self.lr) else self.lr
        lr_t = jnp.asarray(lr_t, jnp.float32)
        first = (state.step == 0)

        leaves_p, treedef = jax.tree.flatten(params)
        leaves_b = jax.tree.flatten(state.momentum)[0]
        leaves_g = jax.tree.flatten(grads)[0]
        sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in leaves_p]
        flat = lambda ls: jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in ls])
        p2d, _ = _pad2d(flat(leaves_p))
        b2d, _ = _pad2d(flat(leaves_b))
        g2d, _ = _pad2d(flat(leaves_g))
        p_new, b_new = _fused_update_padded(
            p2d, b2d, g2d, lr_t, first,
            momentum=self.momentum, dampening=self.dampening,
            weight_decay=self.weight_decay, nesterov=self.nesterov,
            interpret=interpret)

        def unflat(a2d):
            vec = a2d.reshape(-1)
            out, off = [], 0
            for leaf, size in zip(leaves_p, sizes):
                out.append(vec[off:off + size].reshape(leaf.shape)
                           .astype(leaf.dtype))
                off += size
            return jax.tree.unflatten(treedef, out)

        return unflat(p_new), SGDState(step=state.step + 1,
                                       momentum=unflat(b_new))


def fused_sgd_step(params, state: SGDState, grads, *, lr, momentum=0.0,
                   dampening=0.0, weight_decay=0.0, nesterov=False,
                   interpret=None):
    """Functional convenience wrapper over :class:`FusedSGD`."""
    opt = FusedSGD(lr, momentum, dampening, weight_decay, nesterov, interpret)
    return opt.apply(params, state, grads)
