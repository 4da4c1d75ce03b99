"""Driver for ``train_lm.py`` on an MoE model: ``runtime.LMTrainer`` under
``--lm-parallelism ep`` (``models/moe.MoETransformerLM``).

Everything that is the same as for the dense LM is ``drivers/train_lm.py``'s,
taken from that file: how the trainer is built and drained, the loop's period,
tokens per step, the variables and the sampled tokens. What differs: the model
returns ``(logits, aux)``, so the forward hands on the logits of the pair, and
a kernel's cost function needs the experts' sizes beside the attention's.
"""

import os

import harness

_lm = harness.load_module(os.path.join(harness.HERE, "drivers", "train_lm.py"))

THROUGHPUT = _lm.THROUGHPUT
FIXED_ARGS = _lm.FIXED_ARGS
build = _lm.build
drain = _lm.drain
period_steps = _lm.period_steps
samples_per_step = _lm.samples_per_step
variables = _lm.variables
sample_input = _lm.sample_input


def shape(trainer):
    cfg = trainer.cfg
    return dict(_lm.shape(trainer), d_model=cfg.lm_d_model,
                experts=cfg.lm_experts, top_k=cfg.lm_moe_top_k,
                ffn_dim=cfg.lm_ffn_dim or 4 * cfg.lm_d_model)


def _unsharded(trainer, **kw):
    """The trainer's model outside its ``shard_map``: no bound mesh axis."""
    return trainer.model.clone(ep_axis=None, n_local_experts=None, **kw)


def system_forward(trainer, variables, tokens):
    return _unsharded(trainer).apply(variables, tokens)[0]


def activation_dtype(trainer):
    import jax.numpy as jnp

    model = _unsharded(trainer, attention_impl="full")
    tokens = jnp.zeros((1, min(trainer.cfg.lm_seq_len, 8)), jnp.int32)
    return harness.activation_dtypes(
        lambda v, t: model.apply(v, t, capture_intermediates=True,
                                 mutable=["intermediates"]),
        variables(trainer), tokens)
