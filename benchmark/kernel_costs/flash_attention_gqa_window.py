"""What flash attention under a sliding window and grouped-query heads has to
do in one training step, in the WINDOW layers (``shape["windows"][l] > 0``;
the global layers are ``flash_attention_gqa_causal.py``'s, which also says how
products and bytes are counted).

Under a window of W keys a query at position i sees min(i + 1, W) keys: a
head's band is W (W + 1) / 2 + (S - W) W pairs (58.7M at S = 16384, W = 4096,
against the causal triangle's 134.2M). A kernel that masks the band and still
visits every causal tile reads at 44% of what one that skips them reads.
Bytes are the tensors', as in the global layers: every query and every key is
in some band.
"""

import os

import harness

_causal = harness.load_module(os.path.join(
    harness.HERE, "kernel_costs", "flash_attention_gqa_causal.py"))


def required_per_step(shape):
    return _causal.required(shape, window_layers=True)
