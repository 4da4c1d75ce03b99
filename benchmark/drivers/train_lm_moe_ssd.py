"""Driver for ``train_lm.py`` on an MoE model whose layers are ONE sublayer
each, a Mamba-2 mixer, an attention mixer or an expert layer alone, and that
keeps state no gradient moves (the router bias): ``runtime.LMTrainer`` under
``--lm-parallelism ep`` with ``--lm-experts-held`` (``models/moe.
MoETransformerLM``, the ``nemotronh`` arch: Mamba-2 layers by ``models/ssm.py``
and ``ops/ssd.py``).

Everything that is the same as for the model that holds a share of its experts
under attention layers alone is ``drivers/train_lm_moe_held.py``'s, taken from
that file. What differs: the variables carry the bias beside the parameters
(as ``drivers/train_lm_moe_mixed.py``'s do), so the harness moves it off zero
with the norm scales; and what a kernel's cost function needs to know: which
layers attend (``windows`` has an entry for each of those alone, 0: every key
before the query), how many run the recurrence and at what sizes, the entering
states the program's schedule keeps for the backward, how many layers route,
and the shared expert's width.
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
sample_input = _held.sample_input
system_forward = _held.system_forward


def variables(trainer):
    return {"params": trainer.state.params,
            "moe_state": trainer.state.batch_stats}


def shape(trainer):
    """``ssd_layers`` Mamba-2 layers of ``ssd_heads`` heads of ``ssd_head_dim``
    with ``ssd_state`` states, B and C in ``ssd_groups`` groups,
    ``ssd_kept_bytes`` of entering states a layer; ``expert_layers`` layers
    that route."""
    from ps_pytorch_tpu.models.transformer import ARCHS
    from ps_pytorch_tpu.ops.ssd import ssd_schedule

    cfg = trainer.cfg
    arch = ARCHS[cfg.lm_arch]
    kinds = [arch.layer_kind(i, cfg.lm_layers) for i in range(cfg.lm_layers)]
    sched = ssd_schedule(cfg.batch_size, cfg.lm_seq_len, arch.ssm_heads,
                         arch.ssm_head_dim, arch.ssm_state, arch.ssm_groups,
                         chunk=arch.ssm_chunk)
    width = cfg.lm_ffn_dim or 4 * cfg.lm_d_model
    return dict(_held.shape(trainer),
                windows=[0] * kinds.count("attention"),
                ssd_layers=kinds.count("mamba2"),
                ssd_heads=arch.ssm_heads, ssd_head_dim=arch.ssm_head_dim,
                ssd_state=arch.ssm_state, ssd_groups=arch.ssm_groups,
                ssd_kept_bytes=sched.kept_bytes,
                expert_layers=kinds.count("experts"),
                shared_width=arch.shared_experts * width)


def activation_dtype(trainer):
    """dtypes of the model's intermediate outputs (``harness.
    activation_dtypes``) with what is no activation left out: a block returns
    its layer's counter (``ssd_state_abs_max``, a float32 scalar) or its
    assignment counts (int32) beside the routing statistics."""
    import jax
    import jax.numpy as jnp

    model = trainer.model.clone(ep_axis=None, n_local_experts=None,
                                attention_impl="full")
    tokens = jnp.zeros((1, min(trainer.cfg.lm_seq_len, 8)), jnp.int32)
    _, state = jax.eval_shape(
        lambda v, t: model.apply(v, t, capture_intermediates=True,
                                 mutable=["intermediates"]),
        variables(trainer), tokens)
    leaves = [a for a in jax.tree.leaves(state["intermediates"])
              if a.ndim and jnp.issubdtype(a.dtype, jnp.floating)]
    return sorted({str(a.dtype) for a in leaves})
