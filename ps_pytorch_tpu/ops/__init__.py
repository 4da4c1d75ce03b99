"""Pallas TPU kernels for the framework's hot ops.

Every kernel here has a jax/XLA-equivalent fallback and runs in Pallas
interpreter mode off-TPU, so the test suite exercises kernel semantics on the
CPU mesh while real runs compile to Mosaic.

- ``quantize``: on-device int8 block quantization (stochastic rounding) — the
  TPU-native leg of the reference's gradient-compression capability
  (``compression.py``): gradients are shrunk on-chip before a DCN hop instead
  of Blosc-packed on the host.
- ``flash_attention``: blockwise online-softmax causal attention (fwd +
  dq/dkv bwd) — no [S, S] materialization; the single-chip long-context
  attention path.
- ``selective_scan``: a Mamba-1 layer's recurrence, chunked over the sequence
  with the float32 state carried in VMEM, and a hand-written backward that
  keeps chunk-boundary states only.
"""

from ps_pytorch_tpu.ops.quantize import (  # noqa: F401
    dequantize_int8, quantize_int8, quantized_nbytes,
)
from ps_pytorch_tpu.ops.flash_attention import flash_attention  # noqa: F401
