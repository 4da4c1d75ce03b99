"""Driver for ``train_lm.py`` on a hybrid decoder: ``runtime.LMTrainer`` under
``--lm-parallelism sp`` on one chip with a stack that mixes state-space,
differential-attention, gated-memory and cross-attention layers
(``models/transformer.TransformerLM``, the ``phi4flash`` arch).

Everything that is the same as for the dense LM is ``drivers/train_lm.py``'s,
taken from that file: how the trainer is built and drained, the loop's period,
tokens per step, the variables, the sampled tokens, the forward and the
activations' dtypes. What differs is what a kernel's cost function needs to
know: the key/value heads and the head size (not ``d / heads`` here), the
window of each ATTENTION layer, how many layers scan, the scan's channels and
states, and the boundary states the program's schedule keeps for the backward.
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
system_forward = _lm.system_forward


def shape(trainer):
    """``windows``: per attention layer (window, full and cross alike), the
    keys a query sees (0: every key before it, also where the window is no
    shorter than the sequence); ``scan_layers`` state-space layers of
    ``d_inner`` channels and ``d_state`` states, ``scan_kept_bytes`` of
    chunk-boundary states a layer."""
    from ps_pytorch_tpu.models.transformer import ARCHS, ATTENTION_KINDS
    from ps_pytorch_tpu.ops.selective_scan import scan_schedule

    cfg = trainer.cfg
    arch = ARCHS[cfg.lm_arch]
    kinds = [arch.layer_kind(i, cfg.lm_layers) for i in range(cfg.lm_layers)]
    windows = [arch.layer_window(i, cfg.lm_layers) or 0
               for i, k in enumerate(kinds) if k in ATTENTION_KINDS]
    d_inner = arch.ssm_expand * cfg.lm_d_model
    sched = scan_schedule(cfg.batch_size, cfg.lm_seq_len, d_inner,
                          arch.ssm_state)
    return dict(_lm.shape(trainer), d_model=cfg.lm_d_model,
                head_dim=cfg.lm_head_dim or cfg.lm_d_model // cfg.lm_heads,
                kv_heads=cfg.lm_kv_heads or cfg.lm_heads,
                windows=[w if w < cfg.lm_seq_len else 0 for w in windows],
                scan_layers=sum(k.startswith("mamba") for k in kinds),
                d_inner=d_inner, d_state=arch.ssm_state,
                scan_kept_bytes=sched.kept_bytes)


def activation_dtype(trainer):
    """dtypes of the model's intermediate outputs (``harness.
    activation_dtypes``) with the blocks' counters left out: a hybrid block
    returns ``ssm_state_abs_max`` and ``diff_lambda_max`` beside what it
    hands on, float32 scalars that are no activation."""
    import jax
    import jax.numpy as jnp

    model = trainer.model.clone(attention_impl="full")
    tokens = jnp.zeros((1, min(trainer.cfg.lm_seq_len, 8)), jnp.int32)
    _, state = jax.eval_shape(
        lambda v, t: model.apply(v, t, capture_intermediates=True,
                                 mutable=["intermediates"]),
        variables(trainer), tokens)
    leaves = [a for a in jax.tree.leaves(state["intermediates"]) if a.ndim]
    return sorted({str(a.dtype) for a in leaves})
