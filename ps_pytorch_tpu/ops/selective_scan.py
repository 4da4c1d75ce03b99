"""The selective scan of a Mamba-1 layer (Pallas), with a hand-written backward.

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) B_t^T        h_0 = 0
    y_t = h_t C_t + D * u_t

``u, delta [B, S, Di]``, ``A [Di, N]`` (negative), ``B, C [B, S, N]``,
``D [Di]``; the state ``h [B, Di, N]`` is float32 whatever the inputs are, and
the recurrence is the exact one: ``exp(delta A)`` is computed, never
approximated, and ``N`` is not cut.

Why a kernel. Autodiff through a ``lax.scan`` over S steps keeps ``[S, Di,
N]`` float32 a sequence (2.7 GB a layer at S = 8192, Di = 5120, N = 16), and a
loop of S small XLA ops pays a dispatch a token. A decay that differs for
every (channel, state) pair has no matmul form (Mamba-2's has: its A is one
number a head), so the work is elementwise on the vector unit, and what the
kernel has to get right is the layout:

- A vector register is 8 x 128 float32. One register holds 1024 CHANNELS of
  one token for one state index ``n``; the ``N`` state indices are ``N``
  registers, unrolled. So ``delta_t`` and ``u_t`` are registers as they come
  (no broadcast), ``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM and
  splat, and the sum over ``n`` for ``y_t`` is ``N - 1`` register adds: no
  cross-lane work at all in the forward pass. ``[B, S, Di]`` row-major IS
  ``[B, S, Di/1024, 8, 128]``: the wrapper reshapes (no copy, no transpose;
  the cast to float32 fuses into whatever made the array) and a grid step's
  block is ``chunk`` tokens of one channel block, rows of 4 KiB a block
  stride apart, which the DMA gathers. Di is padded to 1024 channels and S
  to whole chunks where they are not already (a padded token has delta = 0
  and u = 0: the state passes it unchanged).
- Sequential over chunks of ``chunk`` tokens: grid (B, Di/1024, S/chunk), the
  last axis ``arbitrary`` with the state carried in VMEM scratch; inside a
  step a ``fori_loop`` walks the tokens. The forward kernel also writes the
  state at the END of every chunk, ``[B, Di/1024, S/chunk, N, 8, 128]``
  float32: the residuals of the ``custom_vjp`` are the inputs and these
  boundary states, nothing a token.
- Backward = one kernel over the chunks in REVERSE. A step recomputes its
  chunk's states from the boundary state before it into VMEM scratch
  (``chunk + 1`` states), then walks the tokens backwards with
  ``g_t = dy_t C_t + exp(delta_{t+1} A) g_{t+1}`` carried across chunks.
  dA sums over tokens in the output block (it does not move along the
  chunk axis); d delta and du are sums over ``n``, register adds again. dB
  and dC are sums over CHANNELS: the kernel reduces each register over its
  sublanes (8 -> 1) and writes rows ``[S, N, 128]`` a channel block, which
  XLA sums over lanes and blocks.

``scan_schedule`` says what a call holds; the trainer prints it on its
``KERNELS`` line. The second output, the largest ``|h|`` over the boundary
states, is the scan's numerical-health counter (``ssm_state_abs_max``).
"""

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default

SUB, LANES = 8, 128
CHANNELS = SUB * LANES      # channels a register holds: a channel block
# Tokens a grid step walks. The backward keeps chunk + 1 states of one
# channel block in VMEM (64 KiB each at N = 16): 8.1 MiB at 128.
CHUNK = 128
VMEM_LIMIT_BYTES = 48 * 2 ** 20


class ScanSchedule(NamedTuple):
    """What a selective-scan call holds, from its shape alone."""
    chunk: int          # tokens a grid step
    chunks: int         # grid steps along the sequence
    blocks: int         # channel blocks of 1024
    grid: tuple         # (batch, blocks, chunks)
    carried_bytes: int  # the state carried from chunk to chunk, all rows
    kept_bytes: int     # boundary states the backward reads (the residual beside the inputs)
    bwd_vmem_bytes: int     # states a backward step recomputes and holds

    def describe(self) -> str:
        return (f"chunk={self.chunk} chunks={self.chunks} "
                f"grid={'x'.join(map(str, self.grid))} "
                f"carried={self.carried_bytes} kept={self.kept_bytes} "
                f"bwd_vmem={self.bwd_vmem_bytes}")


def scan_schedule(batch: int, s: int, d_inner: int, n_state: int,
                  chunk: Optional[int] = None) -> ScanSchedule:
    chunk = min(chunk or CHUNK, s)
    chunks = -(-s // chunk)
    blocks = -(-d_inner // CHANNELS)
    state = blocks * CHANNELS * n_state * 4
    return ScanSchedule(chunk, chunks, blocks, (batch, blocks, chunks),
                        batch * state, batch * chunks * state,
                        (chunk + 1) * CHANNELS * n_state * 4)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _tree_sum(xs):
    """Sum of a list of registers as a tree: a chain of N adds is N
    latencies, a tree log N."""
    xs = list(xs)
    while len(xs) > 1:
        xs = [a + b for a, b in zip(xs[::2], xs[1::2])] + \
            ([xs[-1]] if len(xs) % 2 else [])
    return xs[0]


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, y_ref, hb_ref, h_ref, *,
                chunk, n):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def token(t, h):
        d = d_ref[t]
        du = d * u_ref[t]
        new, ys = [], []
        for i in range(n):
            hi = jnp.exp(d * a_ref[i]) * h[i] + du * b_ref[0, t * n + i]
            ys.append(hi * c_ref[0, t * n + i])
            new.append(hi)
        y_ref[t] = _tree_sum(ys)
        return tuple(new)

    h = jax.lax.fori_loop(0, chunk, token,
                          tuple(h_ref[i] for i in range(n)))
    for i in range(n):
        h_ref[i] = h[i]
        hb_ref[i] = h[i]


def _tokens_spec(chunk, index):
    """A chunk of one channel block of a [B, S, blocks, 8, 128] array."""
    return pl.BlockSpec((None, chunk, None, SUB, LANES),
                        lambda b, c, k: (b, index(k), c, 0, 0))


def _scalars_spec(chunk, n, chunks, index):
    """A chunk's B_t[n] or C_t[n] as scalars: [B * chunks, 1, chunk * N] in
    SMEM, one row a (batch row, chunk); the block's last two dimensions are
    the array's, which is what Mosaic asks of a block it does not tile."""
    return pl.BlockSpec((None, 1, chunk * n),
                        lambda b, c, k: (b * chunks + index(k), 0, 0),
                        memory_space=pltpu.SMEM)


def _fwd_call(u5, d5, a4, bs, cs, chunk, interpret):
    bt, sp, blocks = u5.shape[:3]
    n = a4.shape[1]
    chunks = sp // chunk
    same = lambda k: k
    return pl.pallas_call(
        partial(_fwd_kernel, chunk=chunk, n=n),
        grid=(bt, blocks, chunks),
        in_specs=[
            _tokens_spec(chunk, same), _tokens_spec(chunk, same),
            pl.BlockSpec((None, n, SUB, LANES), lambda b, c, k: (c, 0, 0, 0)),
            _scalars_spec(chunk, n, chunks, same),
            _scalars_spec(chunk, n, chunks, same),
        ],
        out_specs=[
            _tokens_spec(chunk, same),
            pl.BlockSpec((None, None, None, n, SUB, LANES),
                         lambda b, c, k: (b, c, k, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u5.shape, jnp.float32),
            jax.ShapeDtypeStruct((bt, blocks, chunks, n, SUB, LANES),
                                 jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, SUB, LANES), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(u5, d5, a4, bs, cs)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, hb_ref, dy_ref,
                du_ref, dd_ref, da_ref, db_ref, dc_ref, hs_ref, g_ref, *,
                chunk, n, chunks):
    k = pl.program_id(2)            # chunk chunks - 1 - k: the last one first

    @pl.when(k == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    # The state before the chunk: the boundary the forward kept, zero before
    # the first chunk (whose block index is clamped to a state it must not
    # read).
    first = k == chunks - 1
    for i in range(n):
        hs_ref[0, i] = jnp.where(first, 0.0, hb_ref[i])

    def recompute(t, h):
        d = d_ref[t]
        du = d * u_ref[t]
        new = []
        for i in range(n):
            hi = jnp.exp(d * a_ref[i]) * h[i] + du * b_ref[0, t * n + i]
            hs_ref[t + 1, i] = hi
            new.append(hi)
        return tuple(new)

    jax.lax.fori_loop(0, chunk, recompute,
                      tuple(hs_ref[0, i] for i in range(n)))

    def token(j, carry):
        g_next, da = carry          # a_{t+1} g_{t+1}; dA's sum over tokens
        t = chunk - 1 - j
        d, u, dy = d_ref[t], u_ref[t], dy_ref[t]
        du = d * u
        g_new, da_new, to_d, to_u = [], [], [], []
        for i in range(n):
            a = jnp.exp(d * a_ref[i])
            b = b_ref[0, t * n + i]
            g = dy * c_ref[0, t * n + i] + g_next[i]
            dc_ref[t, pl.ds(i, 1), :] = jnp.sum(dy * hs_ref[t + 1, i],
                                                axis=0, keepdims=True)
            db_ref[t, pl.ds(i, 1), :] = jnp.sum(g * du, axis=0,
                                                keepdims=True)
            gah = g * (a * hs_ref[t, i])        # g dh_t/da
            to_d.append(gah * a_ref[i])
            to_u.append(g * b)
            da_new.append(da[i] + gah * d)
            g_new.append(g * a)
        through_b = _tree_sum(to_u)             # sum_n g B_t[n]
        dd_ref[t] = _tree_sum(to_d) + u * through_b
        du_ref[t] = d * through_b
        return tuple(g_new), tuple(da_new)

    g, da = jax.lax.fori_loop(
        0, chunk, token,
        (tuple(g_ref[i] for i in range(n)),
         tuple(da_ref[i] for i in range(n))))
    for i in range(n):
        g_ref[i] = g[i]
        da_ref[i] = da[i]


def _bwd_call(u5, d5, a4, bs, cs, hb, dy5, chunk, interpret):
    bt, sp, blocks = u5.shape[:3]
    n = a4.shape[1]
    chunks = sp // chunk
    rev = lambda k: chunks - 1 - k
    before = lambda k: jnp.maximum(chunks - 2 - k, 0)
    rows = pl.BlockSpec((None, None, chunk, n, LANES),
                        lambda b, c, k: (b, c, rev(k), 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        partial(_bwd_kernel, chunk=chunk, n=n, chunks=chunks),
        grid=(bt, blocks, chunks),
        in_specs=[
            _tokens_spec(chunk, rev), _tokens_spec(chunk, rev),
            pl.BlockSpec((None, n, SUB, LANES), lambda b, c, k: (c, 0, 0, 0)),
            _scalars_spec(chunk, n, chunks, rev),
            _scalars_spec(chunk, n, chunks, rev),
            pl.BlockSpec((None, None, None, n, SUB, LANES),
                         lambda b, c, k: (b, c, before(k), 0, 0, 0)),
            _tokens_spec(chunk, rev),
        ],
        out_specs=[
            _tokens_spec(chunk, rev), _tokens_spec(chunk, rev),
            pl.BlockSpec((None, None, n, SUB, LANES),
                         lambda b, c, k: (b, c, 0, 0, 0)),
            rows, rows,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u5.shape, f32),
            jax.ShapeDtypeStruct(u5.shape, f32),
            jax.ShapeDtypeStruct((bt, blocks, n, SUB, LANES), f32),
            jax.ShapeDtypeStruct((bt, blocks, sp, n, LANES), f32),
            jax.ShapeDtypeStruct((bt, blocks, sp, n, LANES), f32),
        ],
        scratch_shapes=[pltpu.VMEM((chunk + 1, n, SUB, LANES), f32),
                        pltpu.VMEM((n, SUB, LANES), f32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(u5, d5, a4, bs, cs, hb, dy5)


# --------------------------------------------------------------------------
# custom-vjp core, on the kernel's layout
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(u5, d5, a4, bs, cs, chunk, interpret):
    return _fwd_call(u5, d5, a4, bs, cs, chunk, interpret)


def _scan_fwd(u5, d5, a4, bs, cs, chunk, interpret):
    y5, hb = _fwd_call(u5, d5, a4, bs, cs, chunk, interpret)
    return (y5, hb), (u5, d5, a4, bs, cs, hb)


def _scan_bwd(chunk, interpret, res, cts):
    u5, d5, a4, bs, cs, hb = res
    dy5, _ = cts        # the boundary states are a counter's input: no gradient
    du5, dd5, da, db, dc = _bwd_call(u5, d5, a4, bs, cs, hb, dy5, chunk,
                                     interpret)
    rows = lambda r: r.sum(axis=(1, 4)).reshape(bs.shape)
    return du5, dd5, da.sum(axis=0), rows(db), rows(dc)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, delta, A, B, C, D, *, chunk: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """-> ``(y [B, S, Di] in u's dtype, largest |h| over the chunk-boundary
    states)``. Differentiable in every array argument; the second output
    carries no gradient."""
    if interpret is None:
        interpret = _interpret_default()
    bt, s, di = u.shape
    n = A.shape[1]
    sched = scan_schedule(bt, s, di, n, chunk)
    sp, dp = sched.chunks * sched.chunk, sched.blocks * CHANNELS
    f32 = jnp.float32

    def tokens(x):      # [B, S, Di] -> [B, Sp, blocks, 8, 128] float32
        x = jnp.pad(x.astype(f32), ((0, 0), (0, sp - s), (0, dp - di)))
        return x.reshape(bt, sp, sched.blocks, SUB, LANES)

    def scalars(x):     # [B, S, N] -> [B * chunks, 1, chunk * N] float32
        x = jnp.pad(x.astype(f32), ((0, 0), (0, sp - s), (0, 0)))
        return x.reshape(bt * sched.chunks, 1, sched.chunk * n)

    a4 = jnp.pad(A.astype(f32), ((0, dp - di), (0, 0))) \
        .reshape(sched.blocks, SUB, LANES, n).transpose(0, 3, 1, 2)
    y5, hb = _scan(tokens(u), tokens(delta), a4, scalars(B), scalars(C),
                   sched.chunk, bool(interpret))
    y = y5.reshape(bt, sp, dp)[:, :s, :di]
    y = y + D.astype(f32) * u.astype(f32)
    return y.astype(u.dtype), jax.lax.stop_gradient(jnp.max(jnp.abs(hb)))


def selective_scan_reference(u, delta, A, B, C, D):
    """The recurrence token by token (``lax.scan``), float32: what the tests
    hold the kernels to. -> y [B, S, Di] float32."""
    f32 = jnp.float32
    u, delta, B, C = (x.astype(f32) for x in (u, delta, B, C))

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs                     # [B, Di], [B, Di], [B, N], [B, N]
        h = jnp.exp(d_t[..., None] * A) * h \
            + (d_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + D * u_t

    h0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), f32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.swapaxes(x, 0, 1)
                                        for x in (u, delta, B, C)))
    return jnp.swapaxes(y, 0, 1)
