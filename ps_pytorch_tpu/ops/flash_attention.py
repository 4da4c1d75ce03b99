"""Fused blockwise causal attention (Pallas) — flash attention for one chip.

The LM's single-device attention path (``parallel/ring.py:full_attention``)
materializes the [B, H, S, S] score matrix in HBM: at the suite geometry
(B=8, H=8, S=2048, f32) that is 1 GiB per layer of traffic the MXU never
needed. This kernel is the TPU-native fix: the classic flash-attention
blockwise online-softmax schedule (m/l running statistics, rescaled
accumulator) tiled for the MXU, so scores only ever exist as a
[block_q, block_kv] VMEM tile. Long-context on ONE chip is the capability
this buys — the multi-chip long-context path is ring attention
(``parallel/ring.py``), whose per-hop local product this kernel can also
serve as the inner block of.

Reference counterpart: the reference has no attention at all (CNN zoo,
``src/models/*.py``); this belongs to the long-context surface (SURVEY
§5.7) the TPU build treats as first-class.

Design notes
- grid (B*H, S/bq, S/bkv), kv innermost with ``arbitrary`` semantics; the
  output/accumulator block index is independent of the kv step (the
  standard revisited-output accumulation pattern).
- Causal blocks strictly above the diagonal are compute-skipped with
  ``pl.when`` (the score tile is never formed); masking uses a finite
  -1e30 so fully-masked rows stay NaN-free.
- Softmax statistics are carried as [bq, 1] f32 VMEM scratch; the saved
  residual is one LSE row-vector per query ([B*H, S, 1] f32), not the
  score matrix — backward recomputes p per tile from q, k and LSE.
- Backward = two kernels over the same tiling: dq accumulates over kv
  blocks; dk/dv accumulate over q blocks (multi-output pallas_call).
  ``delta = rowsum(dO * O)`` is a cheap XLA elementwise pass outside.
- Matmuls run with ``preferred_element_type=f32`` (bf16 inputs hit the
  MXU natively, accumulate in f32); the probability tile is cast to the
  value dtype for the PV product.
- Compiled on TPU, Pallas interpreter elsewhere — the CPU test mesh runs
  identical semantics (same pattern as ``ops/quantize.py``).
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default

NEG_INF = -1e30


def _pick_block(s: int, requested: int) -> int:
    """Largest power-of-two block <= requested that divides ``s`` (min 8,
    the f32 sublane tile); 0 = no aligned block exists (caller raises)."""
    b = 1
    while b * 2 <= min(requested, s):
        b *= 2
    while b >= 8:
        if s % b == 0:
            return b
        b //= 2
    return 0


def _score_tile(q_ref, k_ref, i, j, bq, bkv, scale, causal):
    """Masked f32 score tile for block (i, j) — shared by all three kernels
    so forward and backward can never disagree on scaling or masking.
    Returns (scaled q, scores)."""
    q = q_ref[0].astype(jnp.float32) * scale
    s = _dot(q, k_ref[0].astype(jnp.float32), trans_b=True)     # [bq, bkv]
    if causal:
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return q, s


def _dot(a, b, *, trans_a=False, trans_b=False):
    """2-D matmul with f32 accumulation, optional transposes folded into
    dimension numbers (no materialized transpose ops in the kernel)."""
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, causal, scale, bq, bkv):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # causal: the kv block is dead unless its first key is <= the last query
    needed = (j * bkv <= i * bq + bq - 1) if causal else (j <= j)

    @pl.when(needed)
    def _tile():
        _, s = _score_tile(q_ref, k_ref, i, j, bq, bkv, scale, causal)
        m_prev, l_prev = m_sc[:], l_sc[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:] = m_new
        pv = _dot(p.astype(v_ref.dtype), v_ref[0])
        acc[:] = acc[:] * alpha + pv

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_sc[:] + jnp.log(l)


def _fwd_call(q3, k3, v3, causal, scale, bq, bkv, interpret):
    bh, s, d = q3.shape
    grid = (bh, s // bq, s // bkv)
    kern = partial(_fwd_kernel, causal=causal, scale=scale, bq=bq, bkv=bkv)
    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_fwd",
        interpret=interpret,
    )(q3, k3, v3)
    return o, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, causal, scale, bq, bkv):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    needed = (j * bkv <= i * bq + bq - 1) if causal else (j <= j)

    @pl.when(needed)
    def _tile():
        _, s = _score_tile(q_ref, k_ref, i, j, bq, bkv, scale, causal)
        p = jnp.exp(s - lse_ref[0])                             # [bq, bkv]
        do = do_ref[0].astype(jnp.float32)
        dov = _dot(do, v_ref[0].astype(jnp.float32), trans_b=True)
        ds = p * (dov - delta_ref[0])
        dq_acc[:] = dq_acc[:] + _dot(ds, k_ref[0].astype(jnp.float32)) * scale

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal, scale, bq, bkv):
    j = pl.program_id(1)          # kv block (parallel)
    i = pl.program_id(2)          # q block (innermost, accumulated)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    needed = (j * bkv <= i * bq + bq - 1) if causal else (j <= j)

    @pl.when(needed)
    def _tile():
        q, s = _score_tile(q_ref, k_ref, i, j, bq, bkv, scale, causal)
        p = jnp.exp(s - lse_ref[0])
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] = dv_acc[:] + _dot(p, do, trans_a=True)
        dov = _dot(do, v_ref[0].astype(jnp.float32), trans_b=True)
        ds = p * (dov - delta_ref[0])
        dk_acc[:] = dk_acc[:] + _dot(ds, q, trans_a=True)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_call(q3, k3, v3, o3, lse, do3, causal, scale, bq, bkv, interpret):
    bh, s, d = q3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)                     # [bh, s, 1]

    dq = pl.pallas_call(
        partial(_dq_kernel, causal=causal, scale=scale, bq=bq, bkv=bkv),
        grid=(bh, s // bq, s // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dq",
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)

    dk, dv = pl.pallas_call(
        partial(_dkv_kernel, causal=causal, scale=scale, bq=bq, bkv=bkv),
        grid=(bh, s // bkv, s // bq),
        in_specs=[
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(k3, v3, q3, do3, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom-vjp wrapper
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, scale, bq, bkv, interpret):
    o, _ = _fwd_call(q3, k3, v3, causal, scale, bq, bkv, interpret)
    return o


def _flash_fwd(q3, k3, v3, causal, scale, bq, bkv, interpret):
    o, lse = _fwd_call(q3, k3, v3, causal, scale, bq, bkv, interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, scale, bq, bkv, interpret, res, do3):
    q3, k3, v3, o3, lse = res
    return _bwd_call(q3, k3, v3, o3, lse, do3, causal, scale, bq, bkv,
                     interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: int = 256, block_kv: int = 256,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused attention over [B, H, S, D] tensors; drop-in for
    ``ring.full_attention`` (same signature semantics, same output).

    Raises ValueError when S has no power-of-two block divisor >= 8: a
    caller that asked for the fused kernel is told it cannot have it.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, h, s, d = q.shape
    bq = _pick_block(s, min(block_q, s))
    bkv = _pick_block(s, min(block_kv, s))
    if not bq or not bkv:
        raise ValueError(
            f"flash_attention needs a power-of-two block >= 8 dividing the "
            f"sequence length; S={s} has none (use attention 'full')")
    if scale is None:
        scale = float(d) ** -0.5
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * h, s, d)
    v3 = v.reshape(b * h, s, d)
    o3 = _flash(q3, k3, v3, causal, float(scale), bq, bkv, bool(interpret))
    return o3.reshape(b, h, s, d)
