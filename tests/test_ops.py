"""Pallas kernel tests (interpreter mode on the CPU mesh).

- quantize: round-trip error bound, unbiasedness of stochastic rounding,
  wire-size accounting.
"""

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------- quantize --

def test_quantize_roundtrip_error_bound(rng):
    from ps_pytorch_tpu.ops import dequantize_int8, quantize_int8

    x = jnp.asarray(rng.normal(size=(333, 17)).astype(np.float32))
    qt = quantize_int8(x, jax.random.key(0))
    out = dequantize_int8(qt)
    assert out.shape == x.shape
    # Stochastic rounding error <= 1 quantum; quantum = blockmax/127.
    max_q = float(jnp.max(jnp.abs(x))) / 127.0
    assert float(jnp.max(jnp.abs(out - x))) <= max_q + 1e-6


def test_quantize_unbiased(rng):
    from ps_pytorch_tpu.ops import dequantize_int8, quantize_int8

    x = jnp.full((2048,), 0.31416, jnp.float32)
    outs = []
    for i in range(64):
        qt = quantize_int8(x, jax.random.key(i))
        outs.append(np.asarray(dequantize_int8(qt)))
    mean = np.mean(outs)
    # E[dequant] == x for stochastic rounding; tolerance ~ quantum/sqrt(64).
    quantum = 0.31416 / 127.0
    assert abs(mean - 0.31416) < quantum / 4


def test_quantize_wire_size(rng):
    from ps_pytorch_tpu.ops import quantize_int8, quantized_nbytes

    x = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
    qt = quantize_int8(x, jax.random.key(0))
    # ~4x smaller than float32 (int8 + per-2048-elem scale overhead).
    assert quantized_nbytes(qt) < x.size * 4 / 3.5


def test_quantize_zero_block():
    from ps_pytorch_tpu.ops import dequantize_int8, quantize_int8

    x = jnp.zeros((4096,), jnp.float32)
    out = dequantize_int8(quantize_int8(x, jax.random.key(0)))
    assert float(jnp.max(jnp.abs(out))) == 0.0
