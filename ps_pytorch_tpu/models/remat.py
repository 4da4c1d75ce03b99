"""What a rematerialised block keeps: the one tuple of names, the function
that gives a tensor its name where it is made, and the wrapper that both LM
classes put round a block under ``--remat``.

A leaf of ``models/``: ``transformer.py``, ``moe.py`` and ``ssm.py`` each name
what their own sublayer's backward reads and import this module downward; it
imports no model. The flash forward's two names are given inside the kernel's
``custom_vjp`` (``ops/flash_attention.py:SAVED_NAMES``), a layer below.
"""

import flax.linen as nn
import jax
from jax.ad_checkpoint import checkpoint_name

from ps_pytorch_tpu.ops.eva_attention import SAVED_NAMES as EVA_SAVED_NAMES
from ps_pytorch_tpu.ops.flash_attention import SAVED_NAMES


# What a rematerialised block keeps beside its input (``remat_block``): the one
# list, each name given where the tensor is made (``kept``; the flash forward's
# two in ``ops/flash_attention.py``, the EVA forward's four in
# ``ops/eva_attention.py``).
KEPT_NAMES = SAVED_NAMES + EVA_SAVED_NAMES + (
    "attn_q", "attn_k", "attn_v", "attn_q_unnormed", "attn_k_unnormed",
    "attn_gate", "attn_out", "ssm_z", "ssm_xbc", "ssm_dt", "ssm_out",
    "moe_gates",
    "moe_idx", "moe_order", "moe_inv", "moe_load", "mlp_out")


def kept(x, name: str):
    """``x`` under a name of ``KEPT_NAMES`` (any other raises): a block under
    ``remat_block`` keeps it for its backward pass; anywhere else the name
    lowers to nothing and the program is the program without it."""
    if name not in KEPT_NAMES:
        raise ValueError(f"{name!r} is not kept; models.remat.KEPT_NAMES "
                         f"has {KEPT_NAMES}")
    return checkpoint_name(x, name)


def remat_block(block_cls):
    """Per-block rematerialisation for both LM classes: the backward pass
    keeps a block's input and recomputes its interior, except what is dear to
    make again and cheap to hold, kept by name (``KEPT_NAMES``; bytes a token
    in bfloat16, H query and Hkv key/value heads of hd, E router outputs,
    top-k):

    - ``flash_o``, ``flash_lse``: the flash forward kernel's output and
      log-sum-exp, (2 hd + 4) H: spares the kernel's second run, the dearest
      part of a block at long sequences;
    - ``eva_o``, ``eva_lse``, ``eva_ks``, ``eva_vs``: an EVA layer's core
      output and log-sum-exp, as flash's, and its chunk summaries, (2 hd / c)
      2 H more: spares both forward kernels' second run;
    - ``attn_q``, ``attn_k``, ``attn_v``: the flash backward's other three
      operands as ``attend`` takes them (a differential layer's as its two
      calls do: a pair's heads apart, its value 2 hd wide; a cross layer's q
      alone), 2 hd (H + 2 Hkv): spares the q/k/v projections,
      RoPE and the transposing copies into heads;
    - ``attn_q_unnormed``, ``attn_k_unnormed``: a q/k norm's input where the
      arch has one (its backward reads it: without it the projections run
      again all the same), 2 hd (H + Hkv);
    - ``attn_gate``: the output gate's projection where the arch has one,
      2 hd H, the one matmul the sublayer's recomputed forward still held;
    - ``attn_out``: the output projection's result, 2 d, all a block's second
      half needs of the first bar a norm and a sum (the norm's backward reads
      it where the arch norms a sublayer's output, so the sum is not what is
      named). A block that is an attention mixer alone reads it nowhere in
      its backward and keeps nothing for it;
    - ``ssm_z``, ``ssm_xbc``, ``ssm_dt``: what a Mamba-2 layer's ``in_proj``
      hands the kernels, 2 (2 d_inner + 2 groups d_state + heads): that block
      is one sublayer, and its recomputed forward was this matmul for the
      kernels' backward (the three slices and not the array: the slices are
      what a Pallas consumer makes XLA write out);
    - ``ssm_out``: a Mamba-2 layer's ``out_proj`` result, 2 d, as
      ``attn_out``: what a block that is a mixer AND an expert half needs of
      the first half in its second (the one matmul the recomputed first half
      would still hold for it); a block that is the mixer alone reads it
      nowhere in its backward and keeps nothing for it;
    - ``moe_gates``, ``moe_idx`` (inside ``_top_k``'s forward rule),
      ``moe_order``, ``moe_inv``: 16 k in all, and ``moe_load``, 4 E a layer:
      spares ``top_k``, both sorts and the [T k, E] count; the router's
      matmul and its scores run again;
    - ``mlp_out``: an expert or dense layer's result where the arch norms a
      sublayer's output, 2 d: that norm's backward reads it, and without the
      name the routed rows' combine (a scatter-add, which the TPU runs row by
      row), the shared expert's and the dense layer's down projections run
      again for nothing else.

    Each sublayer names what its own backward reads; there is no budget and
    no test of an arch. **Not named**, each for the bytes of the cell that
    runs it (PERF.md, Findings PR 46): a Gated DeltaNet layer's ``in_proj``
    output (24 KiB a token, three layers of Qwen3-Next's four, whose step
    plans within 0.5 GiB of where XLA starts rematerialising by itself), a
    Mamba-1 layer's (the hybrid's step is the fullest of all), the experts'
    hidden rows and the gathered rows ``xs`` (as large as ``in_proj``'s
    output for a fifth of its time).
    The kernels' own forward passes (scan, delta rule, state-space dual,
    the mixers' chains) run again for their residuals.

    The list is one for every arch, so a cell that needs room drops a name
    for all. Which first, by what each is worth where an arch holds them all
    (the Trinity cell, ms of a 418 ms step for GiB planned; PERF.md, Findings
    PR 46): ``attn_q`` with ``attn_k`` (7.8 for 0.56: beside the norms'
    inputs they spare only the norms, RoPE and the copies into heads), then
    ``attn_gate`` (7.6 for 0.50), the norms' inputs last (20.9 for 0.42:
    without them both projections run again)."""
    return nn.remat(block_cls, policy=jax.checkpoint_policies
                    .save_only_these_names(*KEPT_NAMES))
