"""Driver for ``train_lm.py`` on an MoE model whose stack mixes kinds of
feed-forward layer and that keeps state no gradient moves: leading dense
layers, then expert layers with a shared expert beside the routed ones and a
router bias in ``models/moe.MOE_STATE`` (``TrainState.batch_stats``); the
``trinity`` arch under ``--lm-parallelism ep`` with ``--lm-dense-layers``,
``--lm-dense-ffn-dim`` and ``--lm-experts-held``.

Everything that is the same as for the model that holds a share of its experts
in every layer is ``drivers/train_lm_moe_held.py``'s, taken from that file. What
differs: the variables carry the bias beside the parameters, so the harness
moves it off zero with the norm scales; and a kernel's cost function needs to
know which layers route. What the comparison then sees at the cell's size is
measured, not assumed (``controls/trinity_mini.py``, read on the chip): a
system that chose by the score alone fails the limit, one that weighed by
score + bias passes it, because the maximum over tokens sits on the tokens
whose 8th and 9th expert change places between bfloat16 and float32;
``tests/test_trinity.py`` tells both apart at a tiny float32 size.
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
    """``moe_layers`` / ``dense_layers``: how many layers route and how many are
    dense; ``shared_width``: the shared experts' width."""
    from ps_pytorch_tpu.models.transformer import ARCHS

    cfg = trainer.cfg
    arch = ARCHS[cfg.lm_arch]
    width = cfg.lm_ffn_dim or 4 * cfg.lm_d_model
    return dict(_held.shape(trainer),
                moe_layers=cfg.lm_layers - cfg.lm_dense_layers,
                dense_layers=cfg.lm_dense_layers,
                dense_ffn_dim=cfg.lm_dense_ffn_dim or 4 * cfg.lm_d_model,
                shared_width=arch.shared_experts * width)


def activation_dtype(trainer):
    import jax.numpy as jnp

    model = trainer.model.clone(ep_axis=None, n_local_experts=None,
                                attention_impl="full")
    tokens = jnp.zeros((1, min(trainer.cfg.lm_seq_len, 8)), jnp.int32)
    found = harness.activation_dtypes(
        lambda v, t: model.apply(v, t, capture_intermediates=True,
                                 mutable=["intermediates"]),
        variables(trainer), tokens)
    # the expert layers also hand out their assignment counts (int32), which
    # are no activation: the cost functions size floating-point tensors
    return [d for d in found if not d.startswith(("int", "uint"))]
