"""Driver for ``train_lm.py`` on an MoE model that holds a share of its
experts and mixes kinds of attention layer: ``runtime.LMTrainer`` under
``--lm-parallelism ep`` with ``--lm-experts-held``, ``--lm-kv-heads`` and
``--lm-head-dim`` (``models/moe.MoETransformerLM``, the ``smallthinker`` arch).

Everything that is the same as for the MoE model that holds every expert is
``drivers/train_lm_moe.py``'s, taken from that file. What differs is what a
kernel's cost function needs to know: the key/value heads, the head size (not
``d / heads`` here), each layer's window and the experts held.
"""

import os

import harness

_moe = harness.load_module(
    os.path.join(harness.HERE, "drivers", "train_lm_moe.py"))

THROUGHPUT = _moe.THROUGHPUT
FIXED_ARGS = _moe.FIXED_ARGS
build = _moe.build
drain = _moe.drain
period_steps = _moe.period_steps
samples_per_step = _moe.samples_per_step
variables = _moe.variables
sample_input = _moe.sample_input
system_forward = _moe.system_forward
activation_dtype = _moe.activation_dtype


def shape(trainer):
    """``windows``: per layer, the keys a query sees (0: every key before
    it, also where the window is no shorter than the sequence)."""
    from ps_pytorch_tpu.models.transformer import ARCHS

    cfg = trainer.cfg
    arch = ARCHS[cfg.lm_arch]
    windows = [arch.layer_window(i) or 0 for i in range(cfg.lm_layers)]
    return dict(_moe.shape(trainer),
                head_dim=cfg.lm_head_dim or cfg.lm_d_model // cfg.lm_heads,
                kv_heads=cfg.lm_kv_heads or cfg.lm_heads,
                windows=[w if w < cfg.lm_seq_len else 0 for w in windows],
                experts_held=cfg.lm_experts_held or cfg.lm_experts)
