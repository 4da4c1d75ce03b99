"""On-device int8 block quantization (Pallas).

The TPU-native re-expression of the reference's gradient-compression
capability (``compression.py:18-45`` Blosc/snappy on the host): before a
gradient crosses a slow boundary (DCN hop between slices, host offload for
the async aggregator), it is shrunk 4x on-chip — one fused pass computing the
per-block absmax scale and stochastically rounding to int8 — instead of being
pulled to the host and byte-compressed there. Stochastic rounding keeps the
quantizer unbiased (E[q*scale] = x), which is what gradient averaging needs;
the reference codec was lossless but paid host round-trip + CPU time.

Kernels run compiled on TPU and in Pallas interpreter mode elsewhere, so the
CPU test mesh exercises identical semantics. The rounding noise is supplied
as an input array (generated with jax.random outside the kernel) — fully
deterministic given a key, portable across backends.

This is the ``codec="int8"`` option of the async/DCN path
(``parallel/async_dp.py``); ``codec="blosc"`` (native C++, ``compression/``)
remains the lossless alternative.
"""

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default

LANES = 128
BLOCK_ROWS = 32          # int8 min sublane tile is 32
BLOCK = BLOCK_ROWS * LANES


class QuantizedTensor(NamedTuple):
    values: jax.Array     # int8 [R, 128], R = ceil(size/BLOCK)*BLOCK_ROWS
    scales: jax.Array     # float32 [R / BLOCK_ROWS, 1]
    shape: Tuple[int, ...]  # original shape
    size: int             # original element count


def _quant_kernel(s_ref, x_ref, u_ref, v_ref):
    # s_ref: whole scales vector in SMEM (scalar reads are SMEM-only on TPU;
    # Mosaic forbids scalar VMEM stores, so the per-block absmax reduce runs
    # as an XLA fusion outside and the kernel fuses the rest of the pass:
    # divide + stochastic round + clip + int8 cast, one read+write of x).
    scale = s_ref[pl.program_id(0)]
    # Stochastic rounding: floor(x/s + u), u ~ U[0,1). Unbiased.
    q = jnp.floor(x_ref[:] / scale + u_ref[:])
    v_ref[:] = jnp.clip(q, -127, 127).astype(jnp.int8)


@partial(jax.jit, static_argnames=("interpret",))
def _quantize_full(x, key, interpret):
    """Whole quantize path (ravel+pad+noise+absmax+kernel) as ONE program.

    Keeping the prep ops inside the jit matters on real hardware: executed
    eagerly they cost ~16 ms/64 MiB in dispatch+materialisation where the
    fused program takes ~0.09 ms (measured on v5e).
    """
    size = x.size if x.shape else 1
    flat = jnp.ravel(x).astype(jnp.float32)
    rows = -(-max(size, 1) // BLOCK) * BLOCK_ROWS
    pad = rows * LANES - size
    x2d = jnp.pad(flat, (0, pad)).reshape(rows, LANES)
    noise = jax.random.uniform(key, (rows, LANES), jnp.float32)
    return _quantize_padded(x2d, noise, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def _quantize_padded(x2d, noise, interpret):
    nblk = x2d.shape[0] // BLOCK_ROWS
    amax = jnp.max(jnp.abs(x2d.reshape(nblk, BLOCK)), axis=1)
    scales = jnp.maximum(amax / 127.0, 1e-30)
    values = pl.pallas_call(
        _quant_kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.int8),
        name="quantize_int8",
        interpret=interpret,
    )(scales, x2d, noise)
    return values, scales.reshape(nblk, 1)


def quantize_int8(x: jax.Array, key: jax.Array,
                  interpret: Optional[bool] = None) -> QuantizedTensor:
    """float array (any shape) -> int8 values + per-2048-element scales."""
    if interpret is None:
        interpret = _interpret_default()
    shape = tuple(x.shape)
    size = int(np.prod(shape)) if shape else 1
    values, scales = _quantize_full(x, key, interpret)
    return QuantizedTensor(values=values, scales=scales, shape=shape, size=size)


@jax.jit
def _dequant(values, scales):
    nblk = scales.shape[0]
    v = values.reshape(nblk, BLOCK).astype(jnp.float32)
    return (v * scales).reshape(-1)


def dequantize_int8(qt: QuantizedTensor) -> jax.Array:
    """Inverse transform (a plain fused multiply — no kernel needed)."""
    flat = _dequant(qt.values, qt.scales)
    return flat[:qt.size].reshape(qt.shape)


def quantized_nbytes(qt: QuantizedTensor) -> int:
    """Wire size of the compressed representation."""
    return qt.values.size * 1 + qt.scales.size * 4
