"""Optimizers consuming externally aggregated gradients.

Functional (optax-compatible) re-designs of the reference's forked torch
optimizers, which take MPI-aggregated numpy gradients via ``step(grads=...)``
(``optim/sgd.py:59-91``, ``optim/adam.py:38-94``). Here the "externally
supplied gradient" is the in-graph ``psum``-averaged gradient pytree; the
update math is bit-for-bit the reference's (verified by golden tests against a
numpy transcription of the torch update rules).
"""

from ps_pytorch_tpu.optim.sgd import sgd  # noqa: F401
from ps_pytorch_tpu.optim.adam import adam  # noqa: F401
from ps_pytorch_tpu.optim.schedules import build_schedule  # noqa: F401


def build_optimizer(cfg):
    """Config -> GradientTransformation (reference: master build_model wires
    SGD at ``sync_replicas_master_nn.py:124-131``). The lr argument is a
    float or a ``step -> lr`` schedule (optim/schedules.py); both optimizer
    families accept either."""
    lr = build_schedule(cfg)
    if cfg.optimizer == "sgd":
        return sgd(lr=lr, momentum=cfg.momentum,
                   weight_decay=cfg.weight_decay, nesterov=cfg.nesterov)
    if cfg.optimizer == "adam":
        return adam(lr=lr, b1=cfg.adam_beta1, b2=cfg.adam_beta2,
                    eps=cfg.adam_eps, weight_decay=cfg.weight_decay,
                    amsgrad=cfg.amsgrad)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
