"""The gated delta rule of a Gated DeltaNet layer (Pallas), chunked, with a
hand-written backward.

A head's state is a matrix ``S [dk, dv]`` (key x value), float32, ``S_0 = 0``:

    S   <- exp(g_t) S                      g_t <= 0: the gate, one number a value head
    d_t  = beta_t (v_t - S^T k_t)          the delta rule's correction
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

``q, k [B, S, Hk, dk]`` (the caller's: l2-normalised, q scaled), ``v [B, S,
Hv, dv]``, ``g, beta [B, S, Hv]``; value heads ``r j .. r j + r - 1`` read key
head ``j`` (``r = Hv / Hk``).

Why chunks. Token by token the recurrence is S rank-one updates that each
read the state; autodiff through a ``lax.scan`` over them keeps ``[dk, dv]``
float32 a token and head (34 GB a layer at S = 16384, 32 heads of 128 x
128). Over a chunk of ``C`` tokens the corrections solve a unit lower
triangular system, and everything else is a matmul. With ``gamma_i`` the
running sum of ``g`` inside the chunk and ``S`` the state that enters it:

    X   = tril_-1(beta_i (k_i . k_j) exp(gamma_i - gamma_j))      [C, C]
    T   = (I + X)^-1                     forward substitution, float32
    U   = T (beta v)      W = T (beta k exp(gamma))
    V'  = U - W S
    O   = (q exp(gamma)) S + tril(q_i . k_j exp(gamma_i - gamma_j)) V'
    S  <- exp(gamma_C) S + (k exp(gamma_C - gamma))^T V'

Every exponent taken is <= 0: ``exp(gamma_i - gamma_j)`` is one exponential
of a difference under the mask, never ``exp(gamma_i) exp(-gamma_j)`` (a
chunk's total decay can be exp(-1344), whose inverse float32 does not hold).

Which part runs where:

- XLA: the copy of the five inputs into the kernels' layout (a head's tokens
  as rows: ``[B Hk, S, dk]``, ``[B Hv, S, dv]``; g and beta as ``[B Hv, S /
  C, C]``, a chunk a row) and of ``o`` and the five gradients out of it; the
  gate's running sum inside a chunk, ``gamma``, and ``exp(gamma_C)`` (2 MB
  and 32 KB a layer at the cell's size), and in the backward the reverse
  running sum that takes ``d gamma`` to ``d g``. No decay, score or ``X`` a
  chunk wide and no float32 copy of a row passes through HBM.
- ``gdr_solve`` (Pallas, chunk-parallel): ``T`` of ``LANES`` chunks of one
  key head and its ``r`` value heads a grid step. It forms each chunk's ``k
  k^T`` and ``X`` in VMEM, turns them so that the CHUNKS lie along the lanes
  (row i of every system: a strided read and a 128 x 128 transpose), and
  solves by forward substitution over the C rows, 128 systems a register
  row: row i is ``e_i - sum_{j<i} X[i, j] T[j]``, a broadcast multiply-add
  of whole registers. XLA's ``triangular_solve`` walks the rows of every
  system through HBM and a product of ``(I - X^(2^i))`` loses its digits
  where keys repeat; substitution is backward stable. ``T`` leaves turned
  back, ``[B Hk, S, r C]`` float32: a chunk's rows, its value heads side by side (128 MB a
  layer at the cell's size, written once and read once by each walk; not
  kept: the backward solves again, or XLA shares the recomputed forward's).
- ``gdr_fwd`` (Pallas, MXU): the state's walk over the chunks of one KEY
  head, its ``r`` value heads side by side in one grid step (``r`` states in
  VMEM scratch across grid steps), so ``q k^T`` is formed once a key head
  (``k k^T`` too in the backward) and q and k are read once. A chunk's
  other tensors are formed in VMEM from its q, k, v rows, its gamma and
  beta and its ``T``: the masked decays, ``U``, ``W``, ``q exp(gamma)``, the masked scores, ``k
  exp(gamma_C - gamma)``; then the state's four matmuls. It writes ``o``,
  the state that ENTERS every chunk, ``[B Hv, S / C, dk, dv]`` float32 (512
  MB a layer at the cell's size, alive for one block's backward under
  per-block remat), and each state element's largest size over the states
  that leave a chunk (a running maximum beside the state, so the counter
  costs no pass over the kept states).
- ``gdr_bwd`` (Pallas, MXU): the same walk in reverse with ``dS`` carried. A
  chunk's tensors are formed again from the inputs, ``T`` and the kept
  state, and ``dO`` and ``dS`` are pulled back to ``dq, dk, dv, d gamma, d
  beta`` inside the kernel: through the state's matmuls, ``U = T (beta v)``,
  ``W = T (beta exp(gamma) k)``, the inverse (``dX = -T^T dT T^T`` under the
  strict mask), ``X``, the scores and the three decayed copies of q and k.
  ``dq`` and ``dk`` leave summed over a key head's value heads.

The ``custom_vjp`` keeps the five inputs and the entering states and nothing
else. The state, ``gamma``, the decays, ``X``, the solve, ``dX`` and every
accumulation are float32; matmul operands are in the inputs' dtype (``T``,
``beta v``, ``beta exp(gamma) k``, the state's copy, ``V'`` and the
gradients that feed the MXU: bfloat16 under bfloat16 inputs, as in the
published kernels; what is carried from chunk to chunk is never rounded).
Under float32 inputs every product is a true float32 product.

``gdr_schedule`` says what a call holds and moves; the trainer prints it on
its ``KERNELS`` line. The second output, the largest ``|S|`` over the chunk
boundaries, is the layer's numerical-health counter (``gdn_state_abs_max``):
with unit keys and beta <= 1 the state is bounded.
"""

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default

CHUNK = 64              # tokens a chunk: the published kernels'
GROUP = 8               # chunks a grid step of gdr_fwd / gdr_bwd walks
LANES = 128             # chunks a grid step of gdr_solve solves side by side, one a lane
VMEM_LIMIT_BYTES = 48 * 2 ** 20
_HIGHEST = jax.lax.Precision.HIGHEST


class GdrSchedule(NamedTuple):
    """What a gated-delta-rule call holds and moves, from its shape alone."""
    chunk: int          # tokens a chunk
    chunks: int         # chunks a sequence (S padded to whole chunks)
    group: int          # chunks a grid step of gdr_fwd / gdr_bwd
    grid: tuple         # of gdr_fwd / gdr_bwd: (batch x key heads, chunk groups)
    heads_a_step: int   # value heads a grid step walks side by side
    solve_grid: tuple   # of gdr_solve: (batch x key heads, blocks of LANES chunks)
    kept_bytes: int     # entering states the backward reads (the residual beside the inputs)
    kept_other_bytes: int   # what else the forward keeps for the backward
    solve_bytes: int    # what a gdr_solve call moves through HBM
    fwd_bytes: int      # what a gdr_fwd call moves
    bwd_bytes: int      # what a gdr_bwd call moves

    def describe(self) -> str:
        return (f"chunk={self.chunk} chunks={self.chunks} group={self.group} "
                f"grid={'x'.join(map(str, self.grid))} "
                f"heads={self.heads_a_step} "
                f"solve_grid={'x'.join(map(str, self.solve_grid))} "
                f"kept={self.kept_bytes}+{self.kept_other_bytes} "
                f"solve_bytes={self.solve_bytes} fwd_bytes={self.fwd_bytes} "
                f"bwd_bytes={self.bwd_bytes}")


def gdr_schedule(batch: int, s: int, v_heads: int, dk: int, dv: int, *,
                 k_heads: Optional[int] = None,
                 itemsize: int = 2) -> GdrSchedule:
    """``k_heads`` (the value heads' number where not given) and the rows'
    ``itemsize`` size the grids over key heads and the byte counts; the
    first six fields and ``kept_bytes`` do not depend on them."""
    chunks = -(-s // CHUNK)
    group = max(c for c in range(1, GROUP + 1) if chunks % c == 0)
    blocks = -(-chunks // LANES)
    k_heads = k_heads or v_heads
    tokens = batch * chunks * CHUNK
    qk = 2 * tokens * k_heads * dk * itemsize   # q and k, a KEY head
    v = tokens * v_heads * dv * itemsize        # as large: o, dO, dv
    gates = 2 * tokens * v_heads * 4            # gamma and beta, float32
    lam = batch * v_heads * chunks * 4          # exp(gamma_C), one a chunk
    solved = tokens * v_heads * CHUNK * 4       # T, float32, read by both walks
    kept = batch * v_heads * chunks * dk * dv * 4
    padded = batch * blocks * LANES * CHUNK     # gdr_solve's tokens
    return GdrSchedule(
        CHUNK, chunks, group, (batch * k_heads, chunks // group),
        v_heads // k_heads, (batch * k_heads, blocks), kept, 0,
        padded * (k_heads * dk * itemsize + v_heads * (8 + CHUNK * 4)),
        qk + 2 * v + gates + lam + solved + kept,
        2 * qk + 3 * v + 2 * gates + lam + solved + kept)


def _dot(a, b, contract):
    """``a`` and ``b`` contracted over one dimension each, float32 out; true
    float32 products where the operands are float32."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        precision=_HIGHEST if a.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# a chunk's tensors, formed where they are used (values in VMEM)
# --------------------------------------------------------------------------

def _masks(c):
    """Row and column numbers of a ``[C, C]`` tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _solve_pullback(t, ct):
    """``d (I + x)^-1 = -T dx T``: the gradient of ``x`` from ``T``'s, under
    the strict mask (float32, true float32 products)."""
    rows, cols = _masks(t.shape[0])
    return jnp.where(rows > cols, -_dot(_dot(t, ct, (0, 0)), t, (1, 1)), 0.0)


class _Chunk(NamedTuple):
    """What one value head's walk over one chunk needs (``_chunk_tensors``)."""
    t: jax.Array        # [C, C] float32
    tb: jax.Array       # the same in the inputs' dtype, as it feeds the MXU
    decay: jax.Array    # [C, C] float32: exp(gamma_i - gamma_j), 1 over the diagonal
    bcol: jax.Array     # [C, 1] beta
    eg: jax.Array       # [C, 1] exp(gamma)
    ekd: jax.Array      # [C, 1] exp(gamma_C - gamma)
    bv: jax.Array       # [C, dv] beta v, the inputs' dtype
    bk: jax.Array       # [C, dk] beta exp(gamma) k
    u: jax.Array        # [C, dv] float32
    w: jax.Array        # [C, dk] the inputs' dtype
    qg: jax.Array       # [C, dk]
    m: jax.Array        # [C, C]
    kd: jax.Array       # [C, dk]


def _column(row, at):
    """``[1, C]`` -> ``[C, 1]``: ``row`` under the mask ``at`` (one element a
    row of the mask), summed along the lanes."""
    c = row.shape[-1]
    return jnp.sum(jnp.where(at, jnp.broadcast_to(row, (c, c)), 0.0), axis=1,
                   keepdims=True)


def _decays(grow, brow):
    """-> ``(exp(gamma_i - gamma_j) under the mask i >= j, 1 over the
    diagonal; beta as a column; gamma as a column)`` of one chunk and value
    head: ``grow, brow [1, C]``."""
    rows, cols = _masks(grow.shape[-1])
    gcol = _column(grow, rows == cols)
    decay = jnp.exp(jnp.where(rows >= cols, gcol - grow, 0.0))   # exponents <= 0
    return decay, _column(brow, rows == cols), gcol


def _chunk_tensors(qf, kf, vb, qk, grow, brow, t):
    """``qf, kf``: a chunk's q, k rows ``[C, dk]`` float32; ``vb [C, dv]``
    in the inputs' dtype; ``qk [C, C]`` float32; ``grow, brow [1, C]`` gamma
    and beta of one value head; ``t [C, C]`` float32, the chunk's solved
    system (``gdr_solve``'s)."""
    dt = vb.dtype
    c = grow.shape[-1]
    rows, cols = _masks(c)
    decay, bcol, gcol = _decays(grow, brow)
    glast = _column(grow, cols == c - 1)        # gamma_C in every row
    eg, ekd = jnp.exp(gcol), jnp.exp(glast - gcol)
    tb = t.astype(dt)
    bv = (bcol * vb.astype(jnp.float32)).astype(dt)
    bk = ((bcol * eg) * kf).astype(dt)
    return _Chunk(
        t, tb, decay, bcol, eg, ekd, bv, bk,
        u=_dot(tb, bv, (1, 0)), w=_dot(tb, bk, (1, 0)).astype(dt),
        qg=(qf * eg).astype(dt),
        m=jnp.where(rows >= cols, qk * decay, 0.0).astype(dt),
        kd=(kf * ekd).astype(dt))


def _chunk_rows(i, c):
    return pl.ds(pl.multiple_of(i * c, c), c)


# --------------------------------------------------------------------------
# gdr_fwd: the states' walk over the chunks of one key head
# --------------------------------------------------------------------------

def _fwd_kernel(lam_ref, q_ref, k_ref, v_ref, gam_ref, beta_ref, t_ref,
                o_ref, hs_ref, top_ref, s_ref, *, c, group, r):
    first = pl.program_id(1) * group

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        top_ref[...] = jnp.zeros_like(top_ref)

    def chunk(i, carry):
        rows = _chunk_rows(i, c)
        qb, kb = q_ref[rows, :], k_ref[rows, :]
        dt = kb.dtype
        qk = _dot(qb, kb, (1, 1))
        qf, kf = qb.astype(jnp.float32), kb.astype(jnp.float32)
        out = []
        for h in range(r):
            s, top = carry[h]
            at = pl.ds(first + i, 1)
            ch = _chunk_tensors(qf, kf, v_ref[h, rows, :], qk,
                                gam_ref[h, at, :], beta_ref[h, at, :],
                                t_ref[rows, h * c:(h + 1) * c])
            hs_ref[h, i] = s
            sb = s.astype(dt)
            vpb = (ch.u - _dot(ch.w, sb, (1, 0))).astype(dt)
            o = _dot(ch.qg, sb, (1, 0)) + _dot(ch.m, vpb, (1, 0))
            o_ref[h, rows, :] = o.astype(o_ref.dtype)
            s = lam_ref[h, first + i] * s + _dot(ch.kd, vpb, (0, 0))
            out.append((s, jnp.maximum(top, jnp.abs(s))))
        return tuple(out)

    # top: each state element's largest size over the states that LEFT a
    # chunk so far (the first chunk's entering state is zero)
    done = jax.lax.fori_loop(
        0, group, chunk, tuple((s_ref[h], top_ref[h]) for h in range(r)))
    for h in range(r):
        s_ref[h], top_ref[h] = done[h]


def _key_rows_spec(rows, width, index):
    """``rows`` tokens of one key head of a [B Hk, S, width] array."""
    return pl.BlockSpec((None, rows, width), lambda h, j: (h, index(j), 0))


def _value_rows_spec(r, rows, width, index):
    """The same tokens of a key head's ``r`` value heads, [B Hv, S, width]."""
    return pl.BlockSpec((r, rows, width), lambda h, j: (h, index(j), 0))


def _gates_spec(r, n, c):
    """A key head's value heads' gamma or beta whole, a chunk a row: [B Hv,
    n, C] (fetched once a head: the block does not move along the walk)."""
    return pl.BlockSpec((r, n, c), lambda h, j: (h, 0, 0))


def _states_spec(r, group, dk, dv, index):
    return pl.BlockSpec((r, group, dk, dv), lambda h, j: (h, index(j), 0, 0))


def _lam_spec(r, n):
    """A key head's value heads' decays, one a chunk, as scalars: [B Hk, r,
    n] in SMEM."""
    return pl.BlockSpec((None, r, n), lambda h, j: (h, 0, 0),
                        memory_space=pltpu.SMEM)


def _compiler_params(chunks="arbitrary"):
    """Heads in parallel; the chunks in order along a walk."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", chunks),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


# --------------------------------------------------------------------------
# gdr_solve: T = (I + X)^-1 of every chunk, LANES chunks side by side
# --------------------------------------------------------------------------

def _solve_kernel(k_ref, gam_ref, beta_ref, t_ref, xs_ref, xt_ref, tt_ref, *,
                  c, r):
    """One key head's ``LANES`` chunks. ``t_ref [LANES C, r C]``: a chunk's
    ``T`` rows, its value heads side by side along the lanes; ``xs_ref`` the
    same of ``X``; ``xt_ref, tt_ref [C (row), r C (head, column), LANES
    (chunk)]``: the systems with the chunks along the lanes, where forward
    substitution is a broadcast multiply-add of whole registers."""
    first = pl.program_id(1) * LANES
    strict = jnp.greater(*_masks(c))

    def form(i, _):             # X of chunk i, from its k rows, gamma and beta
        rows = _chunk_rows(i, c)
        kb = k_ref[rows, :]
        kk = _dot(kb, kb, (1, 1))
        at = pl.ds(first + i, 1)
        for h in range(r):
            decay, bcol, _ = _decays(gam_ref[h, at, :], beta_ref[h, at, :])
            xs_ref[rows, h * c:(h + 1) * c] = jnp.where(
                strict, bcol * kk * decay, 0.0)
        return 0

    jax.lax.fori_loop(0, LANES, form, 0)

    def chunks_to_lanes(i, _):  # row i of every chunk: [chunk, (head, column)]
        xt_ref[i] = xs_ref[pl.ds(i, LANES, stride=c), :].T
        return 0

    jax.lax.fori_loop(0, c, chunks_to_lanes, 0)

    # row i of T is e_i - sum_{j<i} X[i, j] T[j]: backward stable where a
    # product of (I - X^(2^i)) loses its digits on repeated keys
    col = jax.lax.broadcasted_iota(jnp.int32, (c, LANES), 0)
    unit = lambda i: (col == i).astype(jnp.float32)
    heads = [slice(h * c, (h + 1) * c) for h in range(r)]
    for at in heads:
        tt_ref[0, at, :] = unit(0)

    def row(i, _):
        def term(j, acc):
            return tuple(a - xt_ref[i, pl.ds(at.start + j, 1), :]
                         * tt_ref[j, at, :] for a, at in zip(acc, heads))
        acc = jax.lax.fori_loop(0, i, term, (unit(i),) * r)
        for a, at in zip(acc, heads):
            tt_ref[i, at, :] = a
        return 0

    jax.lax.fori_loop(1, c, row, 0)

    def lanes_to_chunks(i, _):
        t_ref[pl.ds(i, LANES, stride=c), :] = tt_ref[i].T
        return 0

    jax.lax.fori_loop(0, c, lanes_to_chunks, 0)


def _solve_call(k, gam, beta, interpret):
    """k [B Hk, n C, dk], gam, beta [B Hv, n, C] with n a multiple of LANES
    -> T [B Hk, n C, r C] float32."""
    bhk, s, dk = k.shape
    bh, n, c = gam.shape
    r = bh // bhk
    f32 = jnp.float32
    return pl.pallas_call(
        partial(_solve_kernel, c=c, r=r),
        grid=(bhk, n // LANES),
        in_specs=[_key_rows_spec(LANES * c, dk, lambda j: j),
                  _gates_spec(r, n, c), _gates_spec(r, n, c)],
        out_specs=_key_rows_spec(LANES * c, r * c, lambda j: j),
        out_shape=jax.ShapeDtypeStruct((bhk, s, r * c), f32),
        scratch_shapes=[pltpu.VMEM((LANES * c, r * c), f32),
                        pltpu.VMEM((c, r * c, LANES), f32),
                        pltpu.VMEM((c, r * c, LANES), f32)],
        compiler_params=_compiler_params("parallel"),
        interpret=interpret, name="gdr_solve")(k, gam, beta)


def _solved(k, gam, beta, interpret):
    """``_solve_call`` on the chunks padded to whole grid steps (a padded
    chunk has k = 0: its system is the identity)."""
    n = gam.shape[1]
    pad = -n % LANES
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad * gam.shape[2]), (0, 0)))
        gam, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                     for a in (gam, beta))
    return _solve_call(k, gam, beta, interpret)


def _fwd_call(q, k, v, gam, beta, lam, t, group, interpret):
    bhk, s, dk = k.shape
    bh, n, c = gam.shape
    dv, r = v.shape[-1], bh // bhk
    rows = group * c
    same = lambda j: j
    f32 = jnp.float32
    return pl.pallas_call(
        partial(_fwd_kernel, c=c, group=group, r=r),
        grid=(bhk, n // group),
        in_specs=[_lam_spec(r, n), _key_rows_spec(rows, dk, same),
                  _key_rows_spec(rows, dk, same),
                  _value_rows_spec(r, rows, dv, same),
                  _gates_spec(r, n, c), _gates_spec(r, n, c),
                  _key_rows_spec(rows, r * c, same)],
        out_specs=[
            _value_rows_spec(r, rows, dv, same),
            _states_spec(r, group, dk, dv, same),
            pl.BlockSpec((r, dk, dv), lambda h, j: (h, 0, 0)),      # top
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), v.dtype),
                   jax.ShapeDtypeStruct((bh, n, dk, dv), f32),
                   jax.ShapeDtypeStruct((bh, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((r, dk, dv), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="gdr_fwd")(lam, q, k, v, gam, beta, t)


# --------------------------------------------------------------------------
# gdr_bwd: the walk in reverse, dS carried, a chunk's tensors formed again
# --------------------------------------------------------------------------

def _bwd_kernel(lam_ref, q_ref, k_ref, v_ref, gam_ref, beta_ref, t_ref,
                hs_ref, do_ref, dq_ref, dk_ref, dv_ref, dgam_ref, dbeta_ref, ds_ref,
                *, c, group, groups, r):
    first = (groups - 1 - pl.program_id(1)) * group     # the last group first

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def chunk(j, carry):        # carry: the gradients of the states that LEAVE
        i = group - 1 - j
        rows = _chunk_rows(i, c)
        at = pl.ds(first + i, 1)
        qb, kb = q_ref[rows, :], k_ref[rows, :]
        dt = kb.dtype
        f32 = jnp.float32
        kk, qk = _dot(kb, kb, (1, 1)), _dot(qb, kb, (1, 1))
        qf, kf = qb.astype(f32), kb.astype(f32)
        rw, cl = _masks(c)
        as_row = lambda col: jnp.sum(       # [C, 1] -> [1, C]
            jnp.where(rw == cl, jnp.broadcast_to(col, (c, c)), 0.0), axis=0,
            keepdims=True)
        dq = jnp.zeros(qf.shape, f32)       # summed over the key head's value heads
        dk = jnp.zeros(kf.shape, f32)
        dkk = jnp.zeros((c, c), f32)
        dqk = jnp.zeros((c, c), f32)
        out = []
        for h in range(r):
            ds = carry[h]
            vb, do = v_ref[h, rows, :], do_ref[h, rows, :]
            ch = _chunk_tensors(qf, kf, vb, qk, gam_ref[h, at, :],
                                beta_ref[h, at, :],
                                t_ref[rows, h * c:(h + 1) * c])
            lam = lam_ref[h, first + i]
            s = hs_ref[h, i]
            sb, dsb = s.astype(dt), ds.astype(dt)
            vpb = (ch.u - _dot(ch.w, sb, (1, 0))).astype(dt)
            # the state's four matmuls, pulled back
            du = (_dot(ch.m, do, (0, 0)) + _dot(ch.kd, dsb, (1, 0))).astype(dt)
            dw = (-_dot(du, sb, (1, 1))).astype(dt)
            dqg = _dot(do, sb, (1, 1))
            dm = jnp.where(rw >= cl, _dot(do, vpb, (1, 1)), 0.0)
            dkd = _dot(vpb, dsb, (1, 1))
            dlam = jnp.sum(jnp.sum(ds * s, axis=0, keepdims=True), axis=1,
                           keepdims=True)
            out.append(lam * ds + _dot(ch.qg, do, (0, 0))
                       - _dot(ch.w, du, (0, 0)))
            # U = T (beta v), W = T (beta exp(gamma) k), T = (I + X)^-1
            dx = _solve_pullback(
                ch.t, _dot(du, ch.bv, (1, 1)) + _dot(dw, ch.bk, (1, 1)))
            dbv, dbk = _dot(ch.tb, du, (0, 0)), _dot(ch.tb, dw, (0, 0))
            # X = beta_i (k_i . k_j) decay_ij, M = (q_i . k_j) decay_ij
            dxk = dx * (kk * ch.decay)                  # d X x X / beta
            dkk = dkk + dx * ch.bcol * ch.decay
            dqk = dqk + dm * ch.decay
            e = ch.bcol * dxk + dm * (qk * ch.decay)    # d decay x decay
            # the rows that carry a per-token factor: beta v, beta exp(gamma) k,
            # q exp(gamma), k exp(gamma_C - gamma)
            egk = ch.eg * kf
            kdf = ch.ekd * kf
            through_bk = jnp.sum(dbk * egk, axis=1, keepdims=True)
            through_kd = jnp.sum(dkd * kdf, axis=1, keepdims=True)
            dv_ref[h, rows, :] = (ch.bcol * dbv).astype(dv_ref.dtype)
            dk = dk + (ch.bcol * ch.eg) * dbk + ch.ekd * dkd
            dq = dq + ch.eg * dqg
            dbeta = jnp.sum(dxk, axis=1, keepdims=True) + through_bk \
                + jnp.sum(dbv * vb.astype(f32), axis=1, keepdims=True)
            dgam = jnp.sum(e, axis=1, keepdims=True) + ch.bcol * through_bk \
                + jnp.sum(dqg * (ch.eg * qf), axis=1, keepdims=True) \
                - through_kd
            # gamma_C: every k exp(gamma_C - gamma) and the state's decay
            last = jnp.sum(through_kd, axis=0, keepdims=True) + dlam * lam
            dgam_ref[h, at, :] = as_row(dgam) \
                - jnp.sum(e, axis=0, keepdims=True) \
                + jnp.where(cl[:1] == c - 1, last, 0.0)
            dbeta_ref[h, at, :] = as_row(dbeta)
        # k k^T and q k^T are a key head's: their gradients once, summed
        dkkb, dqkb = dkk.astype(dt), dqk.astype(dt)
        dk = dk + _dot(dkkb, kb, (1, 0)) + _dot(dkkb, kb, (0, 0)) \
            + _dot(dqkb, qb, (0, 0))
        dq = dq + _dot(dqkb, kb, (1, 0))
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        return tuple(out)

    done = jax.lax.fori_loop(0, group, chunk,
                             tuple(ds_ref[h] for h in range(r)))
    for h in range(r):
        ds_ref[h] = done[h]


def _bwd_call(q, k, v, gam, beta, lam, t, hs, do, group, interpret):
    bhk, s, dk = k.shape
    bh, n, c = gam.shape
    dv, r = v.shape[-1], bh // bhk
    rows, groups = group * c, n // group
    rev = lambda j: groups - 1 - j
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        partial(_bwd_kernel, c=c, group=group, groups=groups, r=r),
        grid=(bhk, groups),
        in_specs=[_lam_spec(r, n), _key_rows_spec(rows, dk, rev),
                  _key_rows_spec(rows, dk, rev),
                  _value_rows_spec(r, rows, dv, rev),
                  _gates_spec(r, n, c), _gates_spec(r, n, c),
                  _key_rows_spec(rows, r * c, rev),
                  _states_spec(r, group, dk, dv, rev),
                  _value_rows_spec(r, rows, dv, rev)],
        out_specs=[_key_rows_spec(rows, dk, rev),
                   _key_rows_spec(rows, dk, rev),
                   _value_rows_spec(r, rows, dv, rev),
                   _gates_spec(r, n, c), _gates_spec(r, n, c)],
        out_shape=[like(q), like(k), like(v), like(gam), like(beta)],
        scratch_shapes=[pltpu.VMEM((r, dk, dv), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="gdr_bwd")(lam, q, k, v, gam, beta, t, hs, do)


# --------------------------------------------------------------------------
# custom-vjp core, on the kernels' layout
# --------------------------------------------------------------------------

def _gates(g, key_heads):
    """g [B Hv, n, C] -> (gamma, the same shape; exp(gamma_C) [B Hk, r, n])."""
    gam = jnp.cumsum(g, axis=-1)
    bh, n, _ = g.shape
    return gam, jnp.exp(gam[..., -1]).reshape(key_heads, bh // key_heads, n)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdr(q, k, v, g, beta, group, interpret):
    return _gdr_fwd(q, k, v, g, beta, group, interpret)[0]


def _gdr_fwd(q, k, v, g, beta, group, interpret):
    gam, lam = _gates(g, k.shape[0])
    t = _solved(k, gam, beta, interpret)
    o, hs, top = _fwd_call(q, k, v, gam, beta, lam, t, group, interpret)
    return (o, jnp.max(top)), (q, k, v, g, beta, hs)


def _gdr_bwd(group, interpret, res, cts):
    q, k, v, g, beta, hs = res
    do, _ = cts         # the boundary states are a counter's input: no gradient
    gam, lam = _gates(g, k.shape[0])
    t = _solved(k, gam, beta, interpret)
    dq, dk, dv, dgam, dbeta = _bwd_call(q, k, v, gam, beta, lam, t, hs,
                                        do.astype(v.dtype), group, interpret)
    # gamma is g's running sum: g_t reaches every gamma from t on
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgam, -1), axis=-1), -1)
    return dq, dk, dv, dg, dbeta


_gdr.defvjp(_gdr_fwd, _gdr_bwd)


def gated_delta_rule(q, k, v, g, beta, *, interpret: Optional[bool] = None):
    """-> ``(o [B, S, Hv, dv] in v's dtype, largest |S| over the chunk
    boundaries)``. Differentiable in all five arguments; the second output
    carries no gradient. A sequence that is no whole number of chunks is
    padded (a padded token has g = 0, beta = 0 and k = 0: the state passes
    it unchanged)."""
    if interpret is None:
        interpret = _interpret_default()
    bt, s, hk, dk = k.shape
    hv, dv = v.shape[2:]
    if hv % hk or q.shape != k.shape or g.shape != (bt, s, hv) \
            or beta.shape != g.shape:
        raise ValueError(
            f"gated_delta_rule: q, k {q.shape}, {k.shape} [B, S, Hk, dk], v "
            f"{v.shape} [B, S, Hv, dv] with Hv a multiple of Hk, g, beta "
            f"{g.shape}, {beta.shape} [B, S, Hv]")
    dt = v.dtype
    sched = gdr_schedule(bt, s, hv, dk, dv, k_heads=hk,
                         itemsize=jnp.dtype(dt).itemsize)
    c, n = sched.chunk, sched.chunks
    pad = n * c - s
    f32 = jnp.float32

    def heads_first(a):         # [B, S, H, ...] -> [B H, n C, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])

    o, state_max = _gdr(
        heads_first(q.astype(dt)), heads_first(k.astype(dt)), heads_first(v),
        heads_first(g.astype(f32)).reshape(bt * hv, n, c),
        heads_first(beta.astype(f32)).reshape(bt * hv, n, c),
        sched.group, bool(interpret))
    o = jnp.moveaxis(o.reshape(bt, hv, n * c, dv), 1, 2)[:, :s]
    return o, jax.lax.stop_gradient(state_max)


def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence token by token (``lax.scan``), float32: what the tests
    hold the kernels to. -> ``(o [B, S, Hv, dv] float32, the last state [B,
    Hv, dk, dv])``."""
    f32 = jnp.float32
    r = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(a.astype(f32), r, axis=2) for a in (q, k))
    v, g, beta = (a.astype(f32) for a in (v, g, beta))

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs        # [B, H, d], ..., [B, H], [B, H]
        s = s * jnp.exp(g_t)[..., None, None]
        delta = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision=_HIGHEST))
        s = s + k_t[..., :, None] * delta[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HIGHEST)

    s0 = jnp.zeros((q.shape[0], v.shape[2], q.shape[3], v.shape[3]), f32)
    last, o = jax.lax.scan(step, s0, tuple(jnp.swapaxes(a, 0, 1)
                                           for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), last
