"""Learning-rate schedules (step -> lr callables).

The reference tuned a single constant lr by grid-sweeping seven values over
relaunched MPI jobs (``tune.sh:1-36``); its optimizers had no schedule
surface at all. This framework's optax transforms accept ``step -> lr``
callables, so schedules are pure functions here — traced into the jitted
step, no host-side mutation, no retrace per step (the step index is a
traced scalar).

Exposed through TrainConfig: ``lr_schedule`` (constant | step | cosine),
``lr_warmup_steps`` (linear 0 -> lr prefix), ``lr_decay_steps`` (the step
period / cosine horizon), ``lr_decay_factor`` (step gamma / cosine floor).
"""

from typing import Callable, Union

import jax.numpy as jnp

Schedule = Union[float, Callable]


def step_decay(lr: float, decay_steps: int, gamma: float = 0.1) -> Callable:
    """lr * gamma^(step // decay_steps) — the classic staircase."""
    if decay_steps <= 0:
        raise ValueError("step schedule needs lr_decay_steps > 0")

    def f(step):
        return lr * gamma ** jnp.floor_divide(step, decay_steps).astype(jnp.float32)
    return f


def cosine(lr: float, total_steps: int, floor_factor: float = 0.0) -> Callable:
    """Cosine from lr to lr*floor_factor over total_steps, flat after."""
    if total_steps <= 0:
        raise ValueError("cosine schedule needs a positive horizon")
    lo = lr * floor_factor

    def f(step):
        t = jnp.clip(step.astype(jnp.float32) if hasattr(step, "astype")
                     else jnp.float32(step), 0.0, float(total_steps))
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t / total_steps))
        return lo + (lr - lo) * cos
    return f


def with_warmup(base: Schedule, warmup_steps: int) -> Callable:
    """Linear 0 -> base over warmup_steps, then the base schedule (shifted so
    its own step 0 is the end of warmup)."""
    if warmup_steps <= 0:
        return base

    def f(step):
        step = jnp.asarray(step)
        tgt = base(jnp.maximum(step - warmup_steps, 0)) if callable(base) else base
        frac = (step.astype(jnp.float32) + 1.0) / float(warmup_steps)
        return jnp.where(step < warmup_steps, tgt * jnp.minimum(frac, 1.0), tgt)
    return f


def build_schedule(cfg) -> Schedule:
    """TrainConfig -> float (constant, the jit-cheapest form) or callable."""
    kind = getattr(cfg, "lr_schedule", "constant")
    if kind == "constant":
        base: Schedule = cfg.lr
    elif kind == "step":
        base = step_decay(cfg.lr, cfg.lr_decay_steps or cfg.max_steps,
                          cfg.lr_decay_factor)
    elif kind == "cosine":
        if not 0.0 <= cfg.lr_decay_factor <= 1.0:
            raise ValueError("cosine needs lr_decay_factor in [0, 1] "
                             f"(the floor fraction), got {cfg.lr_decay_factor}")
        base = cosine(cfg.lr, cfg.lr_decay_steps or cfg.max_steps,
                      cfg.lr_decay_factor)
    else:
        raise ValueError(f"unknown lr_schedule {kind!r} (constant|step|cosine)")
    return with_warmup(base, getattr(cfg, "lr_warmup_steps", 0))
