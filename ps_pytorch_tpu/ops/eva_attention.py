"""EVA attention (Pallas): exact causal attention inside a window, one softmax
shared with chunk summaries of every earlier window ("Efficient Attention via
Control Variates", arXiv:2302.04542, in the reduced form EvaByte's public
``eva.py`` runs: two learned vectors a head, no sampling).

For query ``n`` of head ``h``, window ``W``, chunk ``c``, ``P = W / c``, ``w =
n // W``, ``s = d ** -0.5``:

    alpha_m = softmax_{m in chunk j}(s k_m . phi_h)                 the pooling
    ks_j = sum_m alpha_m k_m + mu_h      vs_j = sum_m alpha_m v_m
    E_n = {m : m // W = w, m <= n}       a block of the diagonal, not a band
    R_n = {j : j < w P}                  every chunk of every EARLIER window
    o_n = (sum_E e^{s q_n.k_m} v_m + sum_R e^{s q_n.ks_j} vs_j)
          / (sum_E e^{s q_n.k_m} + sum_R e^{s q_n.ks_j})            the core

In window 0 ``R_n`` is empty and the layer is plain causal attention.

Four kernels, one ``custom_vjp`` (``eva_attention``):

- ``eva_pool_fwd``: grid (heads, S / R), R = ``pool_rows`` tokens (the
  compute tile's 512). A step reads R rows of k and v once, forms the chunks'
  softmax in float32 as an ``[R / c, R]`` matrix that is 0 outside a chunk's
  own tokens (``_pool_weights``) and writes the R / c summaries as two
  matmuls against it: 1/16 of what it read. It also hands out the largest ``alpha`` of its step (the
  counter ``eva_pool_weight_max``: 1/c is a mean, 1.0 a chunk read through
  one token).
- ``eva_fwd``: grid (heads, S / block_q), ``block_q`` the compute tile (512
  rows, or the window where that is shorter). A step holds a q tile, its
  window's K and V (the block's index does not change along a window's q
  tiles, so they are fetched once a window) and the head's summaries whole
  (S / c rows). ONE online softmax walks the ``w`` summary tiles of P rows
  (wholly live: no mask), the token tiles before the diagonal (no mask) and
  the diagonal tile (masked). Summary tiles of the query's own and later
  windows and token tiles outside the window are never visited. Scores exist
  only as a ``[block_q, block_q]`` or ``[block_q, P]`` tile; the residual is
  one float32 log-sum-exp a row, lane-dense as flash's.
- ``eva_bwd``: grid (heads, S / W), the window axis ``arbitrary``. A step
  holds a window of q, dO, K, V and the head's summaries. Per (key tile, q
  tile) pair the score tile, ``p = exp(s - lse)`` and ``dO V^T`` are formed
  once and feed dV, dK and dQ, held transposed as flash's backward holds them.
  dK and dV sum over the q tiles in loop carries; dQ sums in float32 scratch
  over the window's token tiles and the ``w`` summary tiles; the summaries'
  gradients sum over the windows that read them in their float32 output
  block, which stays in VMEM for the whole head.
- ``eva_pool_bwd``: grid (heads, S / R). Reads k, v, the summaries' float32
  gradients and the core's dK and dV, forms ``alpha`` again and ADDS the
  pooling's share into dK and dV in place (``input_output_aliases``); dphi
  leaves as one float32 row a step, summed outside with dmu (``sum_j dks_j``).

What is kept for the backward: q, k, v, the summaries, o and the log-sum-exp
(the last four by name, ``SAVED_NAMES``, so that a rematerialised block runs
neither forward kernel again). Softmax statistics, accumulators, the
pooling's softmax and every gradient's sum are float32 whatever the inputs
are; matmul operands are cast to float32 (one bfloat16 MXU pass under
Mosaic) and the probability tile to the values' dtype, as in
``ops/flash_attention.py``, whose helpers the kernels share.

The sequence is padded at its END to whole windows where it is longer than
one (a padded token is after every real query and its chunk is in the last
window, whose summaries nothing reads) and a sequence no longer than a window
runs as one window. A length that is no multiple of the chunk is refused.
Under Mosaic R / c has to be a multiple of 8 (the float32 sublane tile: 32
at the published sizes); the interpreter takes any.
"""

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default
from ps_pytorch_tpu.ops.flash_attention import (
    NEG_INF, VMEM_LIMIT_BYTES, _bdot, _div, _mask, _pick_block, _rows,
)
from ps_pytorch_tpu.telemetry.trace import device_scope

_TILE = 512         # the compute tile's rows: flash's, measured there (PR 26)

# What the forward kernels leave for the backward, by name (``models/remat.py:
# KEPT_NAMES`` holds them): the core's output and log-sum-exp, the summaries.
SAVED_NAMES = ("eva_o", "eva_lse", "eva_ks", "eva_vs")


class EvaSchedule(NamedTuple):
    """What the kernels hold and visit for [bh, s, d] queries; static per
    shape. ``s`` is the padded length, ``window`` the one the kernels run
    (the whole sequence where it is no longer than the arch's window)."""
    s: int
    window: int
    chunk: int
    block_q: int                # compute tile: q rows, and a token tile's keys
    block_s: int                # summaries a window, and a summary tile's rows
    grid: Tuple[int, int]       # eva_fwd: (heads, q tiles)
    bwd_grid: Tuple[int, int]   # eva_bwd: (heads, windows)
    pool_rows: int              # tokens a step of either pooling kernel
    token_tiles: int            # live (q tile, token tile) pairs a head, either pass
    summary_tiles: int          # live (q tile, summary tile) pairs a head
    pairs: int                  # (query, key or summary) pairs a head's mask admits
    fwd_bytes: int              # HBM bytes one eva_fwd call moves
    bwd_bytes: int              # ... one eva_bwd call
    pool_fwd_bytes: int         # ... one eva_pool_fwd call
    pool_bwd_bytes: int         # ... one eva_pool_bwd call

    @property
    def windows(self) -> int:
        return self.s // self.window

    def describe(self) -> str:
        grid = lambda g: "x".join(map(str, g))
        return (f"window={self.window} chunk={self.chunk} bq={self.block_q} "
                f"summaries={self.block_s}/{self.s // self.chunk} "
                f"grid={grid(self.grid)} bwd_grid={grid(self.bwd_grid)} "
                f"pool_rows={self.pool_rows} "
                f"token_tiles={self.token_tiles} "
                f"summary_tiles={self.summary_tiles} pairs={self.pairs} "
                f"fwd_bytes={self.fwd_bytes} bwd_bytes={self.bwd_bytes} "
                f"pool_fwd_bytes={self.pool_fwd_bytes} "
                f"pool_bwd_bytes={self.pool_bwd_bytes}")


def live_pairs(s: int, window: int, chunk: int) -> int:
    """(query, key) and (query, summary) pairs one head's mask admits over
    ``s`` tokens: the causal block of each window and, for a query of window
    ``w``, the ``w * window / chunk`` summaries before it."""
    pairs, start, w = 0, 0, 0
    while start < s:
        n = min(window, s - start)
        pairs += n * (n + 1) // 2 + n * w * (window // chunk)
        start, w = start + n, w + 1
    return pairs


def _whole_chunks(s: int, window: int, chunk: int) -> None:
    if chunk < 1 or window % chunk or s % chunk:
        raise ValueError(
            f"eva_attention: the length S={s} and the window {window} are "
            f"whole numbers of chunks of {chunk} tokens; a length that is "
            f"not is refused, not padded")


def eva_schedule(bh: int, s: int, d: int, itemsize: int, window: int,
                 chunk: int) -> EvaSchedule:
    """Pure, from the shape alone. Raises ValueError for a length that is no
    multiple of the chunk, or a window that is none."""
    _whole_chunks(s, window, chunk)
    if s <= window:             # one window: plain causal attention
        unit = math.lcm(8, chunk)
        window = -(-s // unit) * unit
    s_pad = -(-s // window) * window
    bq = _pick_block(window, _TILE)
    if not bq:
        raise ValueError(f"eva_attention needs a power-of-two tile >= 8 "
                         f"dividing the window; {window} has none")
    n_w, per, p = s_pad // window, window // bq, window // chunk
    token_tiles = n_w * per * (per + 1) // 2
    summary_tiles = per * n_w * (n_w - 1) // 2
    row = d * itemsize
    rows, sums = s_pad * row, (s_pad // chunk) * row
    return EvaSchedule(
        s_pad, window, chunk, bq, p, (bh, s_pad // bq), (bh, n_w),
        bq if bq % chunk == 0 else window,
        token_tiles, summary_tiles, live_pairs(s, window, chunk),
        bh * (4 * rows + 2 * sums + 4 * s_pad),
        bh * (7 * rows + 2 * sums + 8 * s_pad + 2 * (s_pad // chunk) * d * 4),
        bh * 2 * (rows + sums),
        bh * (6 * rows + 2 * (s_pad // chunk) * d * 4))


def _compiler_params(second_axis: str):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", second_axis),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


# --------------------------------------------------------------------------
# the pooling
# --------------------------------------------------------------------------

def _dot(a, b, ca, cb):
    """``a`` . ``b`` contracting ``a``'s dimension ``ca`` with ``b``'s ``cb``,
    float32 accumulation."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pool_weights(k, phi, scale, chunk):
    """k [R, d] float32, phi [1, d] -> A [R / c, R] float32: row j holds chunk
    j's softmax of ``scale k . phi`` at its own ``c`` tokens and 0 elsewhere,
    so that the pooling and its backward are matmuls against it (an
    ``[R, d] -> [R / c, c, d]`` reshape and sums over sublanes are what
    Mosaic refuses to lay out). Everything two-dimensional: the scores go
    from a column to a lane-dense row through one aligned transpose."""
    r = k.shape[0]
    z = jnp.sum(k * phi, axis=-1, keepdims=True) * scale        # [R, 1]
    z = jnp.broadcast_to(z, (r, 128)).T[:1]                     # [1, R]
    shape = (r // chunk, r)
    mine = jax.lax.broadcasted_iota(jnp.int32, shape, 0) == jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, shape, 1), jnp.int32(chunk))
    z = jnp.where(mine, z, NEG_INF)
    e = jnp.where(mine, jnp.exp(z - jnp.max(z, axis=1, keepdims=True)), 0.0)
    return e / jnp.sum(e, axis=1, keepdims=True)


def _pool_fwd_kernel(k_ref, v_ref, phi_ref, mu_ref, ks_ref, vs_ref, top_ref,
                     *, scale, chunk):
    f32 = jnp.float32
    k = k_ref[0].astype(f32)
    alpha = _pool_weights(k, phi_ref[0].astype(f32), scale, chunk)
    ks_ref[0] = (_dot(alpha, k, 1, 0)
                 + mu_ref[0].astype(f32)).astype(ks_ref.dtype)
    vs_ref[0] = _dot(alpha, v_ref[0].astype(f32), 1, 0).astype(vs_ref.dtype)
    top_ref[...] = jnp.full(top_ref.shape, jnp.max(alpha), f32)


def _pool_bwd_kernel(k_ref, v_ref, phi_ref, dks_ref, dvs_ref, dk_in, dv_in,
                     dk_ref, dv_ref, dphi_ref, *, scale, chunk):
    f32 = jnp.float32
    k, v, phi = (ref[0].astype(f32) for ref in (k_ref, v_ref, phi_ref))
    alpha = _pool_weights(k, phi, scale, chunk)                 # [P, R]
    dks, dvs = dks_ref[0].astype(f32), dvs_ref[0].astype(f32)  # [P, d]
    # alpha is 0 outside a chunk's own tokens, and so is dz
    dalpha = _dot(dks, k, 1, 1) + _dot(dvs, v, 1, 1)
    dz = alpha * (dalpha - jnp.sum(alpha * dalpha, axis=1, keepdims=True)) \
        * scale
    dk_ref[0] = (dk_in[0].astype(f32) + _dot(alpha, dks, 0, 0)
                 + _dot(dz, jnp.broadcast_to(phi, dks.shape), 0, 0)
                 ).astype(dk_ref.dtype)
    dv_ref[0] = (dv_in[0].astype(f32)
                 + _dot(alpha, dvs, 0, 0)).astype(dv_ref.dtype)
    dphi = jnp.sum(_dot(dz, k, 1, 0), axis=0, keepdims=True)   # [1, d]
    dphi_ref[...] = jnp.broadcast_to(dphi, dphi_ref.shape)


def _pool_layout(heads, d, sched):
    """What both pooling calls share: (steps a head, the BlockSpec of a
    head's vector out of ``[H, 1, d]`` for a grid over B H flat heads, of a
    step's rows and of its summaries)."""
    w = sched.pool_rows
    return (sched.s // w,
            pl.BlockSpec((1, 1, d), lambda b, i: (
                jax.lax.rem(b, jnp.int32(heads)), 0, 0)),
            pl.BlockSpec((1, w, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, w // sched.chunk, d), lambda b, i: (b, i, 0)))


def _pool_fwd_call(k3, v3, phi, mu, scale, sched, interpret):
    bh, s, d = k3.shape
    heads = phi.shape[0]
    steps, vec_spec, rows, sums = _pool_layout(heads, d, sched)
    ks, vs, top = pl.pallas_call(
        partial(_pool_fwd_kernel, scale=scale, chunk=sched.chunk),
        grid=(bh, steps),
        in_specs=[rows, rows, vec_spec, vec_spec],
        out_specs=[sums, sums,
                   pl.BlockSpec((1, 1, 8, 128), lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s // sched.chunk, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, s // sched.chunk, d), v3.dtype),
                   jax.ShapeDtypeStruct((bh, steps, 8, 128),
                                        jnp.float32)],
        compiler_params=_compiler_params("parallel"),
        name="eva_pool_fwd", interpret=interpret,
    )(k3, v3, phi.reshape(heads, 1, d), mu.reshape(heads, 1, d))
    return ks, vs, jnp.max(top)


def _pool_bwd_call(k3, v3, phi, dks, dvs, dk3, dv3, scale, sched, interpret):
    bh, s, d = k3.shape
    heads = phi.shape[0]
    steps, vec_spec, rows, sums = _pool_layout(heads, d, sched)
    dk, dv, dphi = pl.pallas_call(
        partial(_pool_bwd_kernel, scale=scale, chunk=sched.chunk),
        grid=(bh, steps),
        in_specs=[rows, rows, vec_spec, sums, sums, rows, rows],
        out_specs=[rows, rows,
                   pl.BlockSpec((1, 1, 8, d), lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(dk3.shape, dk3.dtype),
                   jax.ShapeDtypeStruct(dv3.shape, dv3.dtype),
                   jax.ShapeDtypeStruct((bh, steps, 8, d),
                                        jnp.float32)],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=_compiler_params("parallel"),
        name="eva_pool_bwd", interpret=interpret,
    )(k3, v3, phi.reshape(heads, 1, d), dks, dvs, dk3, dv3)
    dphi = jnp.sum(dphi[:, :, 0].reshape(-1, heads, steps, d),
                   axis=(0, 2))
    dmu = jnp.sum(dks.reshape(-1, heads, s // sched.chunk, d), axis=(0, 2))
    return dk, dv, dphi, dmu


# --------------------------------------------------------------------------
# the core
# --------------------------------------------------------------------------

def _diagonal(s, q_axis):
    """Mask a [1, ., .] score tile of queries and keys that start at the same
    token; queries run along ``q_axis``: flash's mask, so that the passes of
    both ops agree on it."""
    return _mask(s, 0, 0, q_axis, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref, *,
                scale, sched):
    bq, p = sched.block_q, sched.block_s
    per = sched.window // bq            # q tiles a window
    i = pl.program_id(1)
    w = _div(i, per)                    # the query tile's window
    t_q = i - w * per                   # ... and its place inside it
    d = q_ref.shape[-1]
    q = q_ref[...].astype(jnp.float32) * scale          # [1, bq, d]

    def _online(carry, s, v):
        m_prev, l_prev, acc = carry
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(pr, axis=2, keepdims=True)
        return m_new, l_new, acc * alpha + _bdot(pr.astype(v.dtype), v, 2, 1)

    def _summary(u, carry):
        rows = _rows(u * p, p)
        s = _bdot(q, ks_ref[:, rows, :].astype(jnp.float32), 2, 2)
        return _online(carry, s, vs_ref[:, rows, :])

    def _token(t, carry, *, masked):
        rows = _rows(t * bq, bq)
        s = _bdot(q, k_ref[:, rows, :].astype(jnp.float32), 2, 2)
        return _online(carry, _diagonal(s, 1) if masked else s,
                       v_ref[:, rows, :])

    carry = (jnp.full((1, bq, 1), NEG_INF, jnp.float32),
             jnp.zeros((1, bq, 1), jnp.float32),
             jnp.zeros((1, bq, d), jnp.float32))
    carry = jax.lax.fori_loop(0, w, _summary, carry)
    carry = jax.lax.fori_loop(0, t_q, partial(_token, masked=False), carry)
    m, l, acc = _token(t_q, carry, masked=True)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse = m + jnp.log(l)                                # [1, bq, 1]
    # a column -> one lane-dense row through an aligned transpose
    lse_ref[0, 0] = jnp.broadcast_to(lse[0], (bq, 128)).T[:1]


def _fwd_call(q3, k3, v3, ks, vs, scale, sched, interpret):
    bh, s, d = q3.shape
    bq, w = sched.block_q, sched.window
    per = w // bq
    tile = pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0))
    win = pl.BlockSpec((1, w, d), lambda b, i: (b, _div(i, per), 0))
    sums = pl.BlockSpec((1, s // sched.chunk, d), lambda b, i: (b, 0, 0))
    return pl.pallas_call(
        partial(_fwd_kernel, scale=scale, sched=sched),
        grid=sched.grid,
        in_specs=[tile, win, win, sums, sums],
        out_specs=[tile,
                   pl.BlockSpec((1, 1, 1, bq), lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, s // bq, 1, bq), jnp.float32)],
        compiler_params=_compiler_params("arbitrary"),
        name="eva_fwd", interpret=interpret,
    )(q3, k3, v3, ks, vs)


def _bwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, dks_ref, dvs_ref, dq_acc,
                *, scale, sched):
    bq, p = sched.block_q, sched.block_s
    n_t = sched.window // bq            # token tiles, and q tiles, a window
    w = pl.program_id(1)
    d = q_ref.shape[-1]

    @pl.when(w == 0)
    def _init():
        dks_ref[...] = jnp.zeros_like(dks_ref)
        dvs_ref[...] = jnp.zeros_like(dvs_ref)

    dq_acc[...] = jnp.zeros_like(dq_acc)

    def _pair(t, carry, *, k, v, masked):
        """Keys (or summaries) k, v against q tile ``t``: [1, keys, bq]."""
        dk, dv = carry
        rows = _rows(t * bq, bq)
        q = q_ref[:, rows, :].astype(jnp.float32) * scale
        do = do_ref[:, rows, :].astype(jnp.float32)
        s = _bdot(k, q, 2, 2)
        if masked:
            s = _diagonal(s, 2)
        pr = jnp.exp(s - lse_ref[:, t])
        dv = dv + _bdot(pr, do, 2, 1)
        ds = pr * (_bdot(v, do, 2, 2) - delta_ref[:, t])
        dk = dk + _bdot(ds, q, 2, 1)
        dq_acc[:, rows, :] += _bdot(ds, k * scale, 1, 1)
        return dk, dv

    def _token_tile(j, _):
        rows = _rows(j * bq, bq)
        kw = dict(k=k_ref[:, rows, :].astype(jnp.float32),
                  v=v_ref[:, rows, :].astype(jnp.float32))
        carry = _pair(j, (jnp.zeros((1, bq, d), jnp.float32),) * 2,
                      masked=True, **kw)
        dk, dv = jax.lax.fori_loop(j + 1, n_t,
                                   partial(_pair, masked=False, **kw), carry)
        dk_ref[:, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[:, rows, :] = dv.astype(dv_ref.dtype)

    def _summary_tile(u, _):
        rows = _rows(u * p, p)
        kw = dict(k=ks_ref[:, rows, :].astype(jnp.float32),
                  v=vs_ref[:, rows, :].astype(jnp.float32))
        dk, dv = jax.lax.fori_loop(
            0, n_t, partial(_pair, masked=False, **kw),
            (jnp.zeros((1, p, d), jnp.float32),) * 2)
        dks_ref[:, rows, :] += dk
        dvs_ref[:, rows, :] += dv

    jax.lax.fori_loop(0, n_t, _token_tile, None)
    jax.lax.fori_loop(0, w, _summary_tile, None)
    dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_call(q3, k3, v3, ks, vs, o3, lse, do3, scale, sched, interpret):
    bh, s, d = q3.shape
    bq, w = sched.block_q, sched.window
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)         # [bh, s/bq, 1, bq]
    win = pl.BlockSpec((1, w, d), lambda b, i: (b, i, 0))
    sums = pl.BlockSpec((1, s // sched.chunk, d), lambda b, i: (b, 0, 0))
    row = pl.BlockSpec((1, w // bq, 1, bq), lambda b, i: (b, i, 0, 0))
    f32_sums = jax.ShapeDtypeStruct((bh, s // sched.chunk, d), jnp.float32)
    return pl.pallas_call(
        partial(_bwd_kernel, scale=scale, sched=sched),
        grid=sched.bwd_grid,
        in_specs=[win, win, win, sums, sums, win, row, row],
        out_specs=[win, win, win, sums, sums],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype),
                   f32_sums, f32_sums],
        scratch_shapes=[pltpu.VMEM((1, w, d), jnp.float32)],
        compiler_params=_compiler_params("arbitrary"),
        name="eva_bwd", interpret=interpret,
    )(q3, k3, v3, ks, vs, do3, lse, delta)


# --------------------------------------------------------------------------
# custom-vjp wrapper
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _eva(q3, k3, v3, phi, mu, scale, sched, interpret):
    return _eva_fwd(q3, k3, v3, phi, mu, scale, sched, interpret)[0]


def _eva_fwd(q3, k3, v3, phi, mu, scale, sched, interpret):
    with device_scope("eva_pool"):
        ks, vs, top = _pool_fwd_call(k3, v3, phi, mu, scale, sched, interpret)
        ks = checkpoint_name(ks, SAVED_NAMES[2])
        vs = checkpoint_name(vs, SAVED_NAMES[3])
    with device_scope("attn_core"):
        o, lse = _fwd_call(q3, k3, v3, ks, vs, scale, sched, interpret)
        o = checkpoint_name(o, SAVED_NAMES[0])
        lse = checkpoint_name(lse, SAVED_NAMES[1])
    return (o, top), (q3, k3, v3, phi, ks, vs, o, lse)


def _eva_bwd(scale, sched, interpret, res, cts):
    q3, k3, v3, phi, ks, vs, o3, lse = res
    do3, _ = cts        # the largest weight is a counter: no gradient
    with device_scope("attn_core"):
        dq, dk, dv, dks, dvs = _bwd_call(q3, k3, v3, ks, vs, o3, lse,
                                         do3.astype(q3.dtype), scale, sched,
                                         interpret)
    with device_scope("eva_pool"):
        dk, dv, dphi, dmu = _pool_bwd_call(k3, v3, phi, dks, dvs, dk, dv,
                                           scale, sched, interpret)
    return dq, dk, dv, dphi.astype(phi.dtype), dmu.astype(phi.dtype)


_eva.defvjp(_eva_fwd, _eva_bwd)


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array,
                  mu: jax.Array, *, window: int, chunk: int,
                  scale: Optional[float] = None,
                  interpret: Optional[bool] = None):
    """-> ``(o [B, H, S, d] in q's dtype, the largest pooling weight)`` for q,
    k, v [B, H, S, d] (k as the scores take it: rotated) and phi, mu [H, d].
    Differentiable in all five; the second output carries no gradient. The
    scale is the scores' and the pooling's alike. Opens its own device
    scopes: the pooling under ``eva_pool``, the core under ``attn_core``."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, s, d = q.shape
    if k.shape != q.shape or v.shape != q.shape or phi.shape != (h, d) \
            or mu.shape != (h, d):
        raise ValueError(
            f"eva_attention: q, k, v {q.shape}, {k.shape}, {v.shape} "
            f"[B, H, S, d] alike, phi and mu {phi.shape}, {mu.shape} [H, d]")
    sched = eva_schedule(b * h, s, d, q.dtype.itemsize, window, chunk)
    if scale is None:
        scale = float(d) ** -0.5
    pad = sched.s - s
    q3, k3, v3 = (jnp.pad(t.reshape(b * h, s, d), ((0, 0), (0, pad), (0, 0)))
                  if pad else t.reshape(b * h, s, d) for t in (q, k, v))
    o3, top = _eva(q3, k3, v3, phi, mu, float(scale), sched, bool(interpret))
    return o3[:, :s].reshape(b, h, s, d), jax.lax.stop_gradient(top)


# --------------------------------------------------------------------------
# the plain form
# --------------------------------------------------------------------------

def eva_pool_reference(k, v, phi, mu, *, chunk: int,
                       scale: Optional[float] = None):
    """The summaries in plain ``jax.numpy``, float32: k, v [B, H, S, d], phi,
    mu [H, d] -> (ks, vs [B, H, S / c, d], alpha [B, H, S / c, c])."""
    f32 = jnp.float32
    b, h, s, d = k.shape
    _whole_chunks(s, chunk, chunk)
    scale = float(d) ** -0.5 if scale is None else scale
    kc, vc = (t.astype(f32).reshape(b, h, s // chunk, chunk, d)
              for t in (k, v))
    z = jnp.einsum("bhjcd,hd->bhjc", kc, phi.astype(f32),
                   precision="highest") * scale
    alpha = jax.nn.softmax(z, axis=-1)
    ks = jnp.einsum("bhjc,bhjcd->bhjd", alpha, kc, precision="highest") \
        + mu.astype(f32)[None, :, None, :]
    vs = jnp.einsum("bhjc,bhjcd->bhjd", alpha, vc, precision="highest")
    return ks, vs, alpha


def eva_core_reference(q, k, v, ks, vs, *, window: int, chunk: int,
                       scale: Optional[float] = None):
    """The core in plain ``jax.numpy``: the ``[S, S + S / c]`` scores over
    ``[tokens | summaries]`` under the mask, ONE softmax, float32. -> o [B,
    H, S, d] float32."""
    f32 = jnp.float32
    b, h, s, d = q.shape
    _whole_chunks(s, window, chunk)
    scale = float(d) ** -0.5 if scale is None else scale
    n = jnp.arange(s)
    j = jnp.arange(s // chunk)
    tokens = (n[:, None] // window == n[None, :] // window) \
        & (n[None, :] <= n[:, None])
    summaries = j[None, :] < (n[:, None] // window) * (window // chunk)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(f32) * scale,
        jnp.concatenate([k.astype(f32), ks.astype(f32)], axis=2),
        precision="highest")
    scores = jnp.where(jnp.concatenate([tokens, summaries], axis=1), scores,
                       -jnp.inf)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
        jnp.concatenate([v.astype(f32), vs.astype(f32)], axis=2),
        precision="highest")


def eva_reference(q, k, v, phi, mu, *, window: int, chunk: int,
                  scale: Optional[float] = None):
    """What the kernels are held to: the pooling and the core in their plain
    forms, float32. -> ``(o [B, H, S, d] float32, the largest pooling
    weight)``."""
    ks, vs, alpha = eva_pool_reference(k, v, phi, mu, chunk=chunk,
                                       scale=scale)
    return eva_core_reference(q, k, v, ks, vs, window=window, chunk=chunk,
                              scale=scale), jnp.max(alpha)
