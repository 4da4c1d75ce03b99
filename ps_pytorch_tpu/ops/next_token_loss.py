"""The next-token cross-entropy, with its own gradient.

One function for every step builder on the cells' path (``parallel/sp.py``,
``parallel/ep.py``): weighted softmax cross-entropy of ``logits [..., V]``
against integer ``targets [...]``, summed. A caller that has no target for
a position (the last one of a sequence) gives it weight 0 and any target,
so the logits are never sliced to ``S - 1`` rows.

Why not ``optax.softmax_cross_entropy_with_integer_labels`` under autodiff
(ledger PR 31, PERF.md §6 PR 32). It picks the target's logit with
``take_along_axis``, whose gradient is a scatter: on ``[1, S-1, V]`` logits
the TPU compiler ran it as ``while`` loops (28 ms a step at S = 16384), and
autodiff keeps the float32 copy of the logits and the float32 softmax as
residuals (1.24 GB each there). Here
the target's logit is a compare against an iota and a sum, the residuals
are the logits as they came plus one float32 a row, and the gradient is one
elementwise pass.

Precision is autodiff's: upcast, max, sums, the loss and the gradient's
arithmetic in float32; ``dlogits`` leaves in the logits' dtype, which is
what the cotangent of ``.astype(float32)`` was already cast to.
"""

import jax
import jax.numpy as jnp


def _rows(logits, targets, weights):
    """[rows, V] float32 logits, the hit mask, [rows] float32 weights.
    Flattened so every batch size reduces the same 2-D shape."""
    v = logits.shape[-1]
    x = logits.reshape(-1, v).astype(jnp.float32)
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) \
        == targets.reshape(-1, 1)
    return x, hit, weights.reshape(-1).astype(jnp.float32)


@jax.custom_vjp
def next_token_loss(logits, targets, weights):
    """-> (sum of w * ce, sum of w), float32 scalars.

    logits [..., V] in the model's dtype; targets int [...]; weights float
    [...], the targets' shape. ``ce`` of a row is optax's, term for term:
    ``log(sum(exp(x - max))) - (x[target] - max)`` in float32. The weights
    are constants to the gradient: only the logits get one.

    A row of weight 0 has to be finite all the same: it enters as ``0 * ce``
    and ``0 * softmax``, where the ``[:, :-1]`` slice this replaced never
    read it. A select in place of the products keeps such a row out, and
    cost 3.8 ms of a 103 ms step in ``olmoe_s4096_1chip`` (PERF.md section 6,
    PR 32): XLA computes ``dlogits`` inside the operand of the head's
    weight-gradient matmul, and the select slowed that fusion.
    """
    return _forward(logits, targets, weights)[0]


def _forward(logits, targets, weights):
    x, hit, w = _rows(logits, targets, weights)
    top = jnp.max(x, axis=-1)
    shifted = x - top[:, None]
    log_norm = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    ce = log_norm - jnp.sum(jnp.where(hit, shifted, 0.0), axis=-1)
    return (jnp.sum(w * ce), jnp.sum(w)), \
        (logits, targets, weights, log_norm + top)


def _backward(residuals, cotangents):
    logits, targets, weights, lse = residuals
    g_sum, _ = cotangents
    x, hit, w = _rows(logits, targets, weights)
    dx = (jnp.exp(x - lse[:, None]) - hit) * (w * g_sum)[:, None]
    return dx.astype(logits.dtype).reshape(logits.shape), None, \
        jnp.zeros_like(weights)


next_token_loss.defvjp(_forward, _backward)
