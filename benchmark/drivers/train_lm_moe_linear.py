"""Driver for ``train_lm.py`` on an MoE model whose stack mixes linear-attention
and softmax-attention layers: ``runtime.LMTrainer`` under ``--lm-parallelism
ep`` with ``--lm-experts-held`` (``models/moe.MoETransformerLM``, the
``qwen3next`` arch: Gated DeltaNet layers by ``models/gdn.py`` and
``ops/gated_delta_rule.py``).

Everything that is the same as for the model that holds a share of its experts
under attention layers alone is ``drivers/train_lm_moe_held.py``'s, taken from
that file. What differs is what a kernel's cost function needs to know: which
layers attend (``windows`` has an entry for each of those alone, 0: every key
before the query), how many run the delta rule and at what sizes, the entering
states the program's schedule keeps for the backward, and the shared expert's
width.
"""

import os

import harness

_held = harness.load_module(
    os.path.join(harness.HERE, "drivers", "train_lm_moe_held.py"))

THROUGHPUT = _held.THROUGHPUT
FIXED_ARGS = _held.FIXED_ARGS
build = _held.build
drain = _held.drain
period_steps = _held.period_steps
samples_per_step = _held.samples_per_step
variables = _held.variables
sample_input = _held.sample_input
system_forward = _held.system_forward


def shape(trainer):
    """``gdn_layers`` linear-attention layers of ``gdn_key_heads`` key and
    ``gdn_value_heads`` value heads, ``gdn_key_dim`` x ``gdn_value_dim`` a
    state, ``gdn_kept_bytes`` of entering states a layer."""
    from ps_pytorch_tpu.models.transformer import ARCHS
    from ps_pytorch_tpu.ops.gated_delta_rule import gdr_schedule

    cfg = trainer.cfg
    arch = ARCHS[cfg.lm_arch]
    kinds = [arch.layer_kind(i, cfg.lm_layers) for i in range(cfg.lm_layers)]
    sched = gdr_schedule(cfg.batch_size, cfg.lm_seq_len, arch.gdn_value_heads,
                         arch.gdn_key_dim, arch.gdn_value_dim)
    width = cfg.lm_ffn_dim or 4 * cfg.lm_d_model
    return dict(_held.shape(trainer),
                windows=[0] * kinds.count("attention"),
                gdn_layers=kinds.count("gdn"),
                gdn_key_heads=arch.gdn_key_heads,
                gdn_value_heads=arch.gdn_value_heads,
                gdn_key_dim=arch.gdn_key_dim,
                gdn_value_dim=arch.gdn_value_dim,
                gdn_kept_bytes=sched.kept_bytes,
                shared_width=arch.shared_experts * width)


def activation_dtype(trainer):
    """dtypes of the model's intermediate outputs (``harness.
    activation_dtypes``) with what is no activation left out: a block returns
    its layer's counter (``gdn_state_abs_max``, a float32 scalar) beside the
    routing statistics."""
    import jax
    import jax.numpy as jnp

    model = trainer.model.clone(ep_axis=None, n_local_experts=None,
                                attention_impl="full")
    tokens = jnp.zeros((1, min(trainer.cfg.lm_seq_len, 8)), jnp.int32)
    _, state = jax.eval_shape(
        lambda v, t: model.apply(v, t, capture_intermediates=True,
                                 mutable=["intermediates"]),
        variables(trainer), tokens)
    leaves = [a for a in jax.tree.leaves(state["intermediates"]) if a.ndim]
    return sorted({str(a.dtype) for a in leaves})
