"""Driver for ``train_lm.py``: ``runtime.LMTrainer`` (transformer LM).

Builds the trainer as ``train_lm.main`` does (compile cache,
``config_from_args``, ``LMTrainer(cfg)``); the harness then calls the
trainer's own ``train()``.
"""

THROUGHPUT = "tokens_per_s"
# No checkpoint inside the window and no resume from an older run.
FIXED_ARGS = ["--eval-freq", "0", "--resume", "false"]


def build(argv):
    from ps_pytorch_tpu.config import config_from_args
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    return LMTrainer(config_from_args(argv))


def drain(trainer):
    import jax
    jax.block_until_ready(trainer.state.params)


def period_steps(trainer):
    """Steps after which the loop's own work repeats: the trainer's
    log-and-sync. An epoch of the token stream is left out: it is longer than
    a window (244 steps of 4096 tokens) and its turnover resets a cursor."""
    return max(trainer.cfg.log_every, 1)


def samples_per_step(trainer):
    """Tokens per step."""
    return trainer.cfg.batch_size * trainer.cfg.lm_seq_len


def shape(trainer):
    cfg = trainer.cfg
    return {"batch": cfg.batch_size, "seq_len": cfg.lm_seq_len,
            "heads": cfg.lm_heads, "head_dim": cfg.lm_d_model // cfg.lm_heads,
            "layers": cfg.lm_layers}


def variables(trainer):
    return {"params": trainer.state.params}


def sample_input(trainer, config, rng):
    import numpy as np
    n = config["reference_check"]["samples"]
    return rng.integers(0, trainer.cfg.lm_vocab,
                        (n, trainer.cfg.lm_seq_len)).astype(np.int32)


def system_forward(trainer, variables, tokens):
    return trainer.model.apply(variables, tokens)


def activation_dtype(trainer):
    import harness
    import jax.numpy as jnp

    model = trainer.model.clone(attention_impl="full")
    tokens = jnp.zeros((1, min(trainer.cfg.lm_seq_len, 8)), jnp.int32)
    return harness.activation_dtypes(
        lambda v, t: model.apply(v, t, capture_intermediates=True,
                                 mutable=["intermediates"]),
        variables(trainer), tokens)
