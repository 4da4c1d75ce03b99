"""Driver for ``train_lm.py`` on an MoE model that holds ONE chip's share of
every layer: of the routed experts (``--lm-experts-held``) AND of each mixer's
heads and the shared expert's channels (``--lm-mixer-shares``), every layer a
mixer (Mamba-2 or attention) with an expert half: ``runtime.LMTrainer`` under
``--lm-parallelism ep`` (``models/moe.MoETransformerLM``, the ``granite4h``
arch).

Everything that is the same as for the model of one-sublayer blocks is
``drivers/train_lm_moe_ssd.py``'s, taken from that file. What differs: there
is no state beside the parameters (a softmax router, no bias), and what a
kernel's cost function needs to know is of the share HELD: the query and
key/value heads and the Mamba-2 heads this chip holds (the program's flags and
its ``ARCHS`` row keep the model's counts), the shared expert's held width;
every layer routes.
"""

import os

import harness

_ssd = harness.load_module(
    os.path.join(harness.HERE, "drivers", "train_lm_moe_ssd.py"))
_held = harness.load_module(
    os.path.join(harness.HERE, "drivers", "train_lm_moe_held.py"))

THROUGHPUT = _ssd.THROUGHPUT
FIXED_ARGS = _ssd.FIXED_ARGS
build = _ssd.build
drain = _ssd.drain
period_steps = _ssd.period_steps
samples_per_step = _ssd.samples_per_step
sample_input = _ssd.sample_input
system_forward = _ssd.system_forward
activation_dtype = _ssd.activation_dtype
variables = _held.variables


def shape(trainer):
    """The sizes HELD: ``heads`` query heads on ``kv_heads`` key/value heads
    in the layers that attend (``windows`` has an entry for each of those, 0:
    every key before the query), ``ssd_heads`` Mamba-2 heads in ``ssd_layers``
    layers with B and C in ``ssd_groups`` groups, ``ssd_kept_bytes`` of
    entering states a layer, ``shared_width`` channels of the shared expert;
    ``expert_layers`` layers route (all of them) over ``experts_held`` of
    ``experts``."""
    from ps_pytorch_tpu.models.transformer import ARCHS
    from ps_pytorch_tpu.ops.ssd import ssd_schedule

    cfg = trainer.cfg
    arch, shares = ARCHS[cfg.lm_arch], cfg.lm_mixer_shares
    kinds = [arch.layer_kind(i, cfg.lm_layers) for i in range(cfg.lm_layers)]
    heads = arch.ssm_heads // shares
    sched = ssd_schedule(cfg.batch_size, cfg.lm_seq_len, heads,
                         arch.ssm_head_dim, arch.ssm_state, arch.ssm_groups,
                         chunk=arch.ssm_chunk)
    return dict(_held.shape(trainer),
                heads=cfg.lm_heads // shares,
                kv_heads=(cfg.lm_kv_heads or cfg.lm_heads) // shares,
                head_dim=cfg.lm_head_dim or cfg.lm_d_model // cfg.lm_heads,
                windows=[0] * kinds.count("attention"),
                ssd_layers=kinds.count("mamba2"), ssd_heads=heads,
                ssd_head_dim=arch.ssm_head_dim, ssd_state=arch.ssm_state,
                ssd_groups=arch.ssm_groups, ssd_kept_bytes=sched.kept_bytes,
                expert_layers=cfg.lm_layers,
                shared_width=arch.shared_width // shares,
                mixer_shares=shares)
