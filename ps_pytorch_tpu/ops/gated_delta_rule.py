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

- XLA, batched over every chunk and head at once (``_prepare``): the running
  sums, the masked decays, ``k k^T`` and ``q k^T`` ONCE a key head (the
  ``r`` value heads of a key head differ in gate and beta only: q and k are
  broadcast, never repeated in HBM), ``U`` and ``W``.
- ``gdr_tril`` (Pallas, vector unit): ``T``, by forward substitution over
  the C rows with the CHUNKS along the lanes, 128 systems a register row:
  row i is ``e_i - sum_{j<i} X[i, j] T[j]``, a broadcast multiply-add of
  eight registers a term. XLA's ``triangular_solve`` walks the rows of every
  system through HBM and a product of ``(I - X^(2^i))`` loses its digits
  where keys repeat; substitution is backward stable. Its derivative is
  ``-T^T dT T^T`` (two batched matmuls).
- ``gdr_fwd`` (Pallas, MXU): the state's walk over the chunks of one value
  head, the state in VMEM scratch across grid steps: four matmuls a chunk.
  It writes the state that ENTERS every chunk, ``[B Hv, S / C, dk, dv]``
  float32 (512 MB a layer at the cell's size, alive for one block's backward
  under per-block remat), and each state element's largest size over the
  states that leave a chunk (a running maximum beside the state, so the
  counter costs no pass over the kept states).
- ``gdr_bwd`` (Pallas, MXU): the same walk in reverse with ``dS`` carried:
  a chunk's ``V'`` is recomputed from the kept state, nine matmuls.

The ``custom_vjp`` keeps the five inputs and the entering states; its
backward recomputes ``_prepare`` (a chunk's ``T``, ``U``, ``W``) from the
inputs under ``jax.vjp``, runs ``gdr_bwd``, and pulls the prepared tensors'
gradients back through it. The state and every accumulation are float32;
matmul operands are in the inputs' dtype (the state's bfloat16 copy feeds
the MXU under bfloat16 inputs, as in the published kernels; what is carried
from chunk to chunk is never rounded).

``gdr_schedule`` says what a call holds; the trainer prints it on its
``KERNELS`` line. The second output, the largest ``|S|`` over the chunk
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
LANES = 128             # systems a grid step of gdr_tril solves side by side
VMEM_LIMIT_BYTES = 48 * 2 ** 20
_HIGHEST = jax.lax.Precision.HIGHEST


class GdrSchedule(NamedTuple):
    """What a gated-delta-rule call holds, from its shape alone."""
    chunk: int          # tokens a chunk
    chunks: int         # chunks a sequence (S padded to whole chunks)
    group: int          # chunks a grid step
    grid: tuple         # (batch x value heads, chunk groups)
    tril_grid: int      # grid steps of gdr_tril, LANES systems each
    kept_bytes: int     # entering states the backward reads (the residual beside the inputs)

    def describe(self) -> str:
        return (f"chunk={self.chunk} chunks={self.chunks} group={self.group} "
                f"grid={'x'.join(map(str, self.grid))} "
                f"tril_grid={self.tril_grid} kept={self.kept_bytes}")


def gdr_schedule(batch: int, s: int, v_heads: int, dk: int,
                 dv: int) -> GdrSchedule:
    chunks = -(-s // CHUNK)
    group = max(c for c in range(1, GROUP + 1) if chunks % c == 0)
    bh = batch * v_heads
    return GdrSchedule(CHUNK, chunks, group, (bh, chunks // group),
                       -(-bh * chunks // LANES),
                       bh * chunks * dk * dv * 4)


def _dot(a, b, contract):
    """``a`` and ``b`` contracted over one dimension each, float32 out; true
    float32 products where the operands are float32."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        precision=_HIGHEST if a.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# gdr_tril: T = (I + X)^-1 for strictly lower triangular X, chunks along lanes
# --------------------------------------------------------------------------

def _tril_kernel(x_ref, t_ref, *, c):
    # x_ref, t_ref: [C (row), C (column), LANES (system)] float32
    col = jax.lax.broadcasted_iota(jnp.int32, (c, LANES), 0)
    t_ref[0] = (col == 0).astype(jnp.float32)

    def row(i, _):
        def term(j, acc):
            return acc - x_ref[i, pl.ds(j, 1), :] * t_ref[j]
        t_ref[i] = jax.lax.fori_loop(0, i, term,
                                     (col == i).astype(jnp.float32))
        return 0

    jax.lax.fori_loop(1, c, row, 0)


def _tril_call(xt, interpret):
    c, _, n = xt.shape
    spec = pl.BlockSpec((c, c, LANES), lambda i: (0, 0, i))
    return pl.pallas_call(
        partial(_tril_kernel, c=c), grid=(n // LANES,), in_specs=[spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct(xt.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="gdr_tril")(xt)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tril_inverse(x, interpret):
    """``(I + x)^-1`` of every strictly lower triangular ``x [..., C, C]``
    (float32; what lies on or over the diagonal is not read)."""
    c = x.shape[-1]
    flat = x.reshape(-1, c, c)
    n = flat.shape[0]
    pad = -n % LANES
    xt = jnp.pad(flat, ((0, pad), (0, 0), (0, 0))).transpose(1, 2, 0)
    t = _tril_call(xt, interpret).transpose(2, 0, 1)[:n]
    return t.reshape(x.shape)


def _tril_inverse_fwd(x, interpret):
    t = _tril_inverse(x, interpret)
    return t, t


def _tril_inverse_bwd(interpret, t, ct):
    # d (I + x)^-1 = -T dx T
    tt = jnp.swapaxes(t, -1, -2)
    dx = -jnp.matmul(jnp.matmul(tt, ct, precision=_HIGHEST), tt,
                     precision=_HIGHEST)
    c = t.shape[-1]
    return (jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), dx, 0.0),)


_tril_inverse.defvjp(_tril_inverse_fwd, _tril_inverse_bwd)


# --------------------------------------------------------------------------
# what a chunk's walk needs, from the inputs (XLA, every chunk at once)
# --------------------------------------------------------------------------

def _prepare(q, k, v, g, beta, interpret):
    """q, k: [B, Hk, n, C, dk]; v: [B, Hk, r, n, C, dv]; g, beta: [B, Hk, r,
    n, C] float32. -> ``(w, u, qg, m, kd, lam)``: [BH, S, dk], [BH, S, dv],
    [BH, S, dk], [BH, S, C], [BH, S, dk] in v's dtype and [BH, 1, n]
    float32, BH = B Hk r."""
    dt = v.dtype
    b, hk, r, n, c, dv = v.shape
    dk = k.shape[-1]
    f32 = jnp.float32
    mm = lambda eq, x, y: jnp.einsum(
        eq, x.astype(dt), y.astype(dt), preferred_element_type=f32,
        precision=_HIGHEST if dt == f32 else None)
    gamma = jnp.cumsum(g, axis=-1)
    rows = jnp.arange(c)
    incl = rows[:, None] >= rows[None, :]
    strict = rows[:, None] > rows[None, :]
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.exp(jnp.where(incl, diff, 0.0))         # exponents <= 0
    kk = mm("bhncd,bhnkd->bhnck", k, k)[:, :, None]
    qk = mm("bhncd,bhnkd->bhnck", q, k)[:, :, None]
    x = jnp.where(strict, beta[..., None] * kk * decay, 0.0)
    t = _tril_inverse(x, interpret)
    kf, vf = k.astype(f32)[:, :, None], v.astype(f32)
    u = mm("bhrnck,bhrnkd->bhrncd", t, beta[..., None] * vf)
    w = mm("bhrnck,bhrnkd->bhrncd", t,
           (beta * jnp.exp(gamma))[..., None] * kf)
    qg = q.astype(f32)[:, :, None] * jnp.exp(gamma)[..., None]
    kd = kf * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    m = jnp.where(incl, qk * decay, 0.0)
    lam = jnp.exp(gamma[..., -1])
    bh = b * hk * r
    rows_of = lambda a: a.astype(dt).reshape(bh, n * c, a.shape[-1])
    return (rows_of(w), rows_of(u), rows_of(qg), rows_of(m), rows_of(kd),
            lam.reshape(bh, 1, n))


# --------------------------------------------------------------------------
# gdr_fwd: the state's walk over the chunks of one value head
# --------------------------------------------------------------------------

def _chunk_rows(i, c):
    return pl.ds(pl.multiple_of(i * c, c), c)


def _fwd_kernel(lam_ref, w_ref, u_ref, qg_ref, m_ref, kd_ref,
                o_ref, hs_ref, top_ref, s_ref, *, c, group):
    first = pl.program_id(1) * group

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        top_ref[...] = jnp.zeros_like(top_ref)

    def chunk(i, carry):
        s, top = carry
        rows = _chunk_rows(i, c)
        hs_ref[i] = s
        sb = s.astype(w_ref.dtype)
        vp = u_ref[rows, :].astype(jnp.float32) \
            - _dot(w_ref[rows, :], sb, (1, 0))
        vpb = vp.astype(w_ref.dtype)
        o = _dot(qg_ref[rows, :], sb, (1, 0)) \
            + _dot(m_ref[rows, :], vpb, (1, 0))
        o_ref[rows, :] = o.astype(o_ref.dtype)
        s = lam_ref[0, first + i] * s + _dot(kd_ref[rows, :], vpb, (0, 0))
        return s, jnp.maximum(top, jnp.abs(s))

    # top: each state element's largest size over the states that LEFT a
    # chunk so far (the first chunk's entering state is zero)
    s_ref[...], top_ref[...] = jax.lax.fori_loop(
        0, group, chunk, (s_ref[...], top_ref[...]))


def _rows_spec(rows, width, index):
    """``rows`` tokens of one value head of a [BH, S, width] array."""
    return pl.BlockSpec((None, rows, width), lambda h, j: (h, index(j), 0))


def _lam_spec(n):
    """A value head's decays, one a chunk, as scalars: [BH, 1, n] in SMEM."""
    return pl.BlockSpec((None, 1, n), lambda h, j: (h, 0, 0),
                        memory_space=pltpu.SMEM)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _fwd_call(w, u, qg, m, kd, lam, c, group, interpret):
    bh, s, dk = w.shape
    dv, n = u.shape[-1], lam.shape[-1]
    rows = group * c
    same = lambda j: j
    f32 = jnp.float32
    return pl.pallas_call(
        partial(_fwd_kernel, c=c, group=group),
        grid=(bh, n // group),
        in_specs=[_lam_spec(n), _rows_spec(rows, dk, same),
                  _rows_spec(rows, dv, same), _rows_spec(rows, dk, same),
                  _rows_spec(rows, c, same), _rows_spec(rows, dk, same)],
        out_specs=[
            _rows_spec(rows, dv, same),
            pl.BlockSpec((None, group, dk, dv), lambda h, j: (h, j, 0, 0)),
            pl.BlockSpec((None, dk, dv), lambda h, j: (h, 0, 0)),   # top
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), u.dtype),
                   jax.ShapeDtypeStruct((bh, n, dk, dv), f32),
                   jax.ShapeDtypeStruct((bh, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="gdr_fwd")(lam, w, u, qg, m, kd)


# --------------------------------------------------------------------------
# gdr_bwd: the walk in reverse, dS carried
# --------------------------------------------------------------------------

def _bwd_kernel(lam_ref, w_ref, u_ref, qg_ref, m_ref, kd_ref, hs_ref, do_ref,
                dw_ref, du_ref, dqg_ref, dm_ref, dkd_ref, dlam_ref, ds_ref, *,
                c, group, groups):
    first = (groups - 1 - pl.program_id(1)) * group     # the last group first

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def chunk(j, ds):           # ds: the gradient of the state that LEAVES
        i = group - 1 - j
        rows = _chunk_rows(i, c)
        dt = w_ref.dtype
        s = hs_ref[i]
        sb, dsb = s.astype(dt), ds.astype(dt)
        w, qg, m, kd, do = (ref[rows, :] for ref in
                            (w_ref, qg_ref, m_ref, kd_ref, do_ref))
        vp = u_ref[rows, :].astype(jnp.float32) - _dot(w, sb, (1, 0))
        vpb = vp.astype(dt)
        dvp = _dot(m, do, (0, 0)) + _dot(kd, dsb, (1, 0))
        dvpb = dvp.astype(dt)
        du_ref[rows, :] = dvpb
        dw_ref[rows, :] = (-_dot(dvpb, sb, (1, 1))).astype(dt)
        dqg_ref[rows, :] = _dot(do, sb, (1, 1)).astype(dt)
        dm_ref[rows, :] = _dot(do, vpb, (1, 1)).astype(dt)
        dkd_ref[rows, :] = _dot(vpb, dsb, (1, 1)).astype(dt)
        dlam_ref[i] = jnp.sum(ds * s, axis=0, keepdims=True)
        return lam_ref[0, first + i] * ds + _dot(qg, do, (0, 0)) \
            - _dot(w, dvpb, (0, 0))

    ds_ref[...] = jax.lax.fori_loop(0, group, chunk, ds_ref[...])


def _bwd_call(w, u, qg, m, kd, lam, hs, do, c, group, interpret):
    bh, s, dk = w.shape
    dv, n = u.shape[-1], lam.shape[-1]
    rows, groups = group * c, n // group
    rev = lambda j: groups - 1 - j
    f32 = jnp.float32
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        partial(_bwd_kernel, c=c, group=group, groups=groups),
        grid=(bh, groups),
        in_specs=[_lam_spec(n), _rows_spec(rows, dk, rev),
                  _rows_spec(rows, dv, rev), _rows_spec(rows, dk, rev),
                  _rows_spec(rows, c, rev), _rows_spec(rows, dk, rev),
                  pl.BlockSpec((None, group, dk, dv),
                               lambda h, j: (h, rev(j), 0, 0)),
                  _rows_spec(rows, dv, rev)],
        out_specs=[_rows_spec(rows, dk, rev), _rows_spec(rows, dv, rev),
                   _rows_spec(rows, dk, rev), _rows_spec(rows, c, rev),
                   _rows_spec(rows, dk, rev),
                   pl.BlockSpec((None, group, 1, dv),
                                lambda h, j: (h, rev(j), 0, 0))],
        out_shape=[like(w), like(u), like(qg), like(m), like(kd),
                   jax.ShapeDtypeStruct((bh, n, 1, dv), f32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="gdr_bwd")(lam, w, u, qg, m, kd, hs, do)


# --------------------------------------------------------------------------
# custom-vjp core, on the chunked layout
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdr(q, k, v, g, beta, group, interpret):
    return _gdr_fwd(q, k, v, g, beta, group, interpret)[0]


def _gdr_fwd(q, k, v, g, beta, group, interpret):
    c = v.shape[-2]
    w, u, qg, m, kd, lam = _prepare(q, k, v, g, beta, interpret)
    o, hs, top = _fwd_call(w, u, qg, m, kd, lam, c, group, interpret)
    return (o, jnp.max(top)), (q, k, v, g, beta, hs)


def _gdr_bwd(group, interpret, res, cts):
    q, k, v, g, beta, hs = res
    do, _ = cts         # the boundary states are a counter's input: no gradient
    c = v.shape[-2]
    prepared, pull = jax.vjp(
        lambda *a: _prepare(*a, interpret), q, k, v, g, beta)
    w, u, qg, m, kd, lam = prepared
    dw, du, dqg, dm, dkd, dlam = _bwd_call(w, u, qg, m, kd, lam, hs,
                                           do.astype(u.dtype), c, group,
                                           interpret)
    return pull((dw, du, dqg, dm, dkd,
                 jnp.sum(dlam, axis=-1).reshape(lam.shape)))


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
    sched = gdr_schedule(bt, s, hv, dk, dv)
    c, n, r = sched.chunk, sched.chunks, hv // hk
    pad = n * c - s
    f32 = jnp.float32

    def chunks(a, heads):       # [B, S, H, ...] -> [B, *heads, n, C, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((bt, n, c) + heads + a.shape[3:])
        order = tuple(range(3, 3 + len(heads)))
        return a.transpose((0,) + order + (1, 2)
                           + tuple(range(3 + len(heads), a.ndim)))

    dt = v.dtype
    o, state_max = _gdr(
        chunks(q.astype(dt), (hk,)), chunks(k.astype(dt), (hk,)),
        chunks(v, (hk, r)), chunks(g.astype(f32), (hk, r)),
        chunks(beta.astype(f32), (hk, r)), sched.group, bool(interpret))
    o = o.reshape(bt, hv, n * c, dv).transpose(0, 2, 1, 3)[:, :s]
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
