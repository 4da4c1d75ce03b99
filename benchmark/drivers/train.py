"""Driver for ``train.py``: ``runtime.Trainer`` (CNNs, data-parallel, K-of-N).

Builds the trainer as ``train.main`` does in its plain branch (compile cache,
``config_from_args``, ``Trainer(cfg)``); the harness then calls the trainer's
own ``train()``.
"""

THROUGHPUT = "images_per_s"
# No checkpoint inside the window and no resume from an older run; --epochs 0
# so that only --max-steps ends the loop (the default of one epoch is 48
# steps at this batch).
FIXED_ARGS = ["--eval-freq", "0", "--resume", "false", "--epochs", "0"]


def build(argv):
    from ps_pytorch_tpu.config import config_from_args
    from ps_pytorch_tpu.runtime import Trainer
    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    return Trainer(config_from_args(argv))


def drain(trainer):
    import jax
    jax.block_until_ready(trainer.state.params)


def period_steps(trainer):
    """Steps after which the loop's own work repeats: an epoch of the loader
    (its turnover costs a step 20-45 ms) and the trainer's log-and-sync."""
    import math
    return math.lcm(len(trainer.train_loader), max(trainer.cfg.log_every, 1))


def samples_per_step(trainer):
    """Images per step: the global batch, masked replicas included, as the
    trainer's own ``examples_per_sec`` counts them."""
    return trainer.cfg.batch_size


def shape(trainer):
    return {"batch": trainer.cfg.batch_size}


def variables(trainer):
    """The trainer's own variables; BatchNorm statistics of replica 0."""
    import jax
    out = {"params": trainer.state.params}
    if jax.tree.leaves(trainer.state.batch_stats):
        out["batch_stats"] = jax.tree.map(lambda a: a[0],
                                          trainer.state.batch_stats)
    return out


def sample_input(trainer, config, rng):
    import numpy as np
    n = config["reference_check"]["samples"]
    hw, c = config["image_size"], config["image_channels"]
    return rng.standard_normal((n, hw, hw, c)).astype(np.float32)


def system_forward(trainer, variables, x):
    """Inference-mode forward in the cell's compute dtype."""
    return trainer.model.apply(variables, x, train=False)


def activation_dtype(trainer):
    import harness
    import jax.numpy as jnp
    from ps_pytorch_tpu.data.datasets import sample_shape

    x = jnp.zeros((1,) + sample_shape(trainer.cfg.dataset), jnp.float32)
    return harness.activation_dtypes(
        lambda v, x: trainer.model.apply(
            v, x, train=False, capture_intermediates=True,
            mutable=["intermediates"]),
        variables(trainer), x)
