"""Driver for ``train_lm.py`` on a stack of EVA layers with several prediction
heads: ``runtime.LMTrainer`` under ``--lm-parallelism sp`` on one chip
(``models/transformer.TransformerLM``, the ``evabyte`` arch).

Everything that is the same as for the dense LM is ``drivers/train_lm.py``'s,
taken from that file: how the trainer is built and drained, the loop's period,
tokens per step, the variables, the sampled tokens and the forward. What
differs is what a kernel's cost function needs to know (the head size and the
key/value heads as the flags give them, the window, the chunk, the prediction
heads) and how the activations' dtypes are read: at a whole number of chunks,
which ``train_lm.py``'s eight tokens are not, and without the float32 logits.
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
    """``eva_window`` tokens a window, ``eva_chunk`` tokens a summary,
    ``pred_heads`` prediction heads; every layer is an EVA layer."""
    from ps_pytorch_tpu.models.transformer import ARCHS

    cfg = trainer.cfg
    arch = ARCHS[cfg.lm_arch]
    return dict(_lm.shape(trainer), d_model=cfg.lm_d_model,
                head_dim=cfg.lm_head_dim or cfg.lm_d_model // cfg.lm_heads,
                kv_heads=cfg.lm_kv_heads or cfg.lm_heads,
                eva_window=arch.eva_window, eva_chunk=arch.eva_chunk,
                pred_heads=arch.pred_heads)


def activation_dtype(trainer):
    """dtypes of the blocks' and the embedding's intermediate outputs on two
    chunks of tokens: what the kernels and the matmuls are fed. Left out: the
    head's output and the model's own, which are the float32 logits by the
    arch (``fp32_logits``; ``harness.activation_dtypes`` drops the last leaf
    for that, and here they are not the last), and the blocks' counter
    ``eva_pool_weight_max``, a float32 scalar that is no activation."""
    import jax
    import jax.numpy as jnp
    from ps_pytorch_tpu.models.transformer import ARCHS

    model = trainer.model.clone(attention_impl="full")
    chunk = ARCHS[trainer.cfg.lm_arch].eva_chunk
    tokens = jnp.zeros((1, min(trainer.cfg.lm_seq_len, 2 * chunk)), jnp.int32)
    _, state = jax.eval_shape(
        lambda v, t: model.apply(v, t, capture_intermediates=True,
                                 mutable=["intermediates"]),
        variables(trainer), tokens)
    inner = {k: v for k, v in state["intermediates"].items()
             if k not in ("lm_head", "__call__")}
    return sorted({str(a.dtype) for a in jax.tree.leaves(inner) if a.ndim})
