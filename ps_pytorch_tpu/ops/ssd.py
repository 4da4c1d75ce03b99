"""Mamba-2's recurrence in its chunked state-space-dual form (Pallas), with a
hand-written backward (state-space duality, arXiv:2405.21060).

A head's state is a matrix ``h [P, N]`` (head feature x state), float32, from
zero; its decay is ONE number a head and token:

    h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T
    y_t = h_t C_t + D x_t

``x [B, S, H, P]``, ``dt [B, S, H]`` (> 0, float32: the caller's softplus),
``A [H]`` (< 0), ``B, C [B, S, G, N]`` (head ``h`` reads group ``h // (H /
G)``), ``D [H]``.

Why chunks. Token by token the recurrence is S rank-one updates of a ``[P,
N]`` state and no matmul (``ops/selective_scan.py`` walks 16 states a channel
that way; 128 states are 6.4 times its work on the vector unit), and autodiff
through a ``lax.scan`` keeps a state a token. Over a chunk of ``C`` tokens it
is ``ops/gated_delta_rule.py``'s walk WITHOUT the delta rule's correction (no
triangular solve, no ``U`` / ``W``): with ``q = C``, ``k = B``, ``v = dt x``,
``gamma_i`` the running sum of ``dt A`` inside the chunk and ``h`` the state
that enters it,

    Y   = exp(gamma) (q h^T) + tril((q_i . k_j) exp(gamma_i - gamma_j)) v + D x
    h  <- exp(gamma_C) h + (v exp(gamma_C - gamma))^T k

Every exponent taken is <= 0 (one exponential of a difference under the mask).

Which part runs where:

- XLA: ``dt`` turned so that a chunk is a row (``[B, H, S / C, C]``, 4 MB a
  layer at the cell's size), its running sum ``gamma`` and ``exp(gamma_C)``;
  in the backward the reverse running sum that takes ``d gamma`` to ``d dt``
  and ``d A``. ``x``, ``B``, ``C`` and ``y`` stay in the layer's own layout,
  ``[B, S, H P]`` and ``[B, S, G N]``: a group's heads are side by side along
  the lanes, so a block is a group and no copy into a kernel's layout exists.
  No mask, score or decayed copy a chunk wide passes through HBM.
- ``ssd_fwd`` (Pallas, MXU): the walk over the chunks of one GROUP, its ``H /
  G`` heads' states ``[H / G P, N]`` float32 in VMEM scratch across grid
  steps, so ``C B^T`` is formed once a group and B and C are read once. Heads
  narrower than the 128 lanes are worked on a slab of ``128 / P`` at a time:
  per-token factors are laid over a slab by a select on the lane, and the
  slab's heads meet their own masked scores in ONE matmul (the scores side by
  side, the values stacked under a lane mask). It writes ``y``, the state
  that ENTERS every chunk (``[B, G, S / C, H / G P, N]`` float32: 268 MB a
  layer at the cell's size, alive for one block's backward under per-block
  remat) and each state element's largest size over the states that leave a
  chunk.
- ``ssd_bwd`` (Pallas, MXU): the same walk in reverse with ``dh`` carried; a
  chunk's tensors are formed again from the inputs and the kept state, and
  ``dY`` and ``dh`` are pulled back to ``dx, dB, dC, d dt, d gamma`` and
  ``dD``'s per-feature sums inside the kernel. ``dB`` and ``dC`` leave summed
  over the group's heads.

The ``custom_vjp`` keeps the inputs and the entering states and nothing else.
The state, ``gamma``, the decays and every accumulation are float32; matmul
operands are in ``x``'s dtype (the masked scores, ``dt x``, the state's copy:
bfloat16 under bfloat16 inputs; what is carried from chunk to chunk is never
rounded). Under float32 inputs every product is a true float32 product.

``ssd_schedule`` says what a call holds and moves; the trainer prints it on
its ``KERNELS`` line. The second output, the largest ``|h|`` over the chunk
boundaries, is the layer's numerical-health counter (``ssd_state_abs_max``).
"""

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default
from ps_pytorch_tpu.ops.gated_delta_rule import _chunk_rows, _column, _dot

CHUNK = 128             # tokens a chunk: the published chunk_size
GROUP = 8               # chunks a grid step walks
LANES = 128
VMEM_LIMIT_BYTES = 64 * 2 ** 20
_HIGHEST = jax.lax.Precision.HIGHEST    # the reference's einsum


class SsdSchedule(NamedTuple):
    """What a state-space-dual call holds and moves, from its shape alone."""
    chunk: int          # tokens a chunk
    chunks: int         # chunks a sequence (S padded to whole chunks)
    group: int          # chunks a grid step
    grid: tuple         # (batch x groups, chunk groups)
    heads_a_step: int   # heads a grid step walks: a group's
    heads_a_slab: int   # ... of which this many share a matmul's lanes
    kept_bytes: int     # entering states the backward reads (the residual beside the inputs)
    fwd_bytes: int      # what an ssd_fwd call moves through HBM
    bwd_bytes: int      # what an ssd_bwd call moves

    def describe(self) -> str:
        return (f"chunk={self.chunk} chunks={self.chunks} group={self.group} "
                f"grid={'x'.join(map(str, self.grid))} "
                f"heads={self.heads_a_step} slab={self.heads_a_slab} "
                f"kept={self.kept_bytes} fwd_bytes={self.fwd_bytes} "
                f"bwd_bytes={self.bwd_bytes}")


def _heads_a_slab(heads_a_group: int, p: int) -> int:
    """Heads that share the 128 lanes of a slab: as many as fit and divide
    the group."""
    return max(n for n in range(1, max(LANES // p, 1) + 1)
               if heads_a_group % n == 0)


def ssd_schedule(batch: int, s: int, heads: int, p: int, n: int, groups: int,
                 *, chunk: int = CHUNK, itemsize: int = 2) -> SsdSchedule:
    chunks = -(-s // chunk)
    group = max(c for c in range(1, GROUP + 1) if chunks % c == 0)
    tokens = batch * chunks * chunk
    x = tokens * heads * p * itemsize           # as large: y, dY, dx
    bc = 2 * tokens * groups * n * itemsize     # B and C, a GROUP
    rows = 2 * tokens * heads * 4               # dt and gamma, float32
    lam = batch * heads * chunks * 4
    kept = batch * heads * chunks * p * n * 4
    skip = heads * p * 4
    return SsdSchedule(
        chunk, chunks, group, (batch * groups, chunks // group),
        heads // groups, _heads_a_slab(heads // groups, p), kept,
        2 * x + bc + rows + lam + skip + kept,
        3 * x + 2 * bc + 2 * rows + lam + 2 * skip + kept)


# --------------------------------------------------------------------------
# a chunk's tensors, formed where they are used (values in VMEM)
# --------------------------------------------------------------------------

def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _as_row(col, diag):
    """``[C, 1]`` -> ``[1, C]``."""
    c = col.shape[0]
    return jnp.sum(jnp.where(diag, jnp.broadcast_to(col, (c, c)), 0.0),
                   axis=0, keepdims=True)


class _Head(NamedTuple):
    """One head's per-token numbers over one chunk."""
    decay: jax.Array    # [C, C] float32: exp(gamma_i - gamma_j) where i >= j, else 1
    dcol: jax.Array     # [C, 1] dt
    eg: jax.Array       # [C, 1] exp(gamma)
    ekd: jax.Array      # [C, 1] exp(gamma_C - gamma)


def _head(grow, drow):
    """``grow, drow [1, C]``: gamma and dt of one head over one chunk."""
    c = grow.shape[-1]
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    diag = rows == cols
    gcol = _column(grow, diag)
    glast = _column(grow, cols == c - 1)        # gamma_C in every row
    return _Head(jnp.exp(jnp.where(rows >= cols, gcol - grow, 0.0)),
                 _column(drow, diag), jnp.exp(gcol), jnp.exp(glast - gcol))


def _over_lanes(cols, p, width):
    """Per-token numbers of a slab's heads ``cols`` (each ``[C, 1]``) laid
    over the slab's lanes: ``[C, width]``, head ``i``'s over lanes ``i p ..
    (i + 1) p - 1``."""
    c = cols[0].shape[0]
    lane = _iota((c, width), 1)
    out = jnp.broadcast_to(cols[-1], (c, width))
    for i in range(len(cols) - 2, -1, -1):
        out = jnp.where(lane < (i + 1) * p, cols[i], out)
    return out


def _over_rows(scalars, p, shape):
    """The same for one number a head over the ROWS of a slab's state."""
    row = _iota(shape, 0)
    out = jnp.full(shape, scalars[-1], jnp.float32)
    for i in range(len(scalars) - 2, -1, -1):
        out = jnp.where(row < (i + 1) * p, scalars[i], out)
    return out


def _lanes_of(i, z, p):
    """``z [C, width]`` with every lane outside head ``i`` of the slab zero."""
    lane = _iota(z.shape, 1)
    return jnp.where((lane >= i * p) & (lane < (i + 1) * p), z,
                     jnp.zeros_like(z))


def _stacked(v, hp, p):
    """``[hp C, width]``: block ``i`` is ``v`` on head ``i``'s lanes alone,
    so that the heads' scores side by side, times this, are each head's own
    product."""
    if hp == 1:
        return v
    return jnp.concatenate([_lanes_of(i, v, p) for i in range(hp)], axis=0)


def _masked_scores(scores, heads, dt):
    """The slab's heads' ``tril(scores * decay)`` side by side: [C, hp C]."""
    c = scores.shape[0]
    keep = _iota((c, c), 0) >= _iota((c, c), 1)
    ms = [jnp.where(keep, scores * h.decay, 0.0).astype(dt) for h in heads]
    return ms[0] if len(ms) == 1 else jnp.concatenate(ms, axis=1)


# --------------------------------------------------------------------------
# ssd_fwd: the states' walk over the chunks of one group
# --------------------------------------------------------------------------

def _fwd_kernel(lam_ref, x_ref, b_ref, c_ref, dt_ref, gam_ref, d_ref,
                y_ref, hs_ref, top_ref, s_ref, *, c, group, r, p, hp):
    first = pl.program_id(1) * group
    w = hp * p
    slabs = r // hp

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        top_ref[...] = jnp.zeros_like(top_ref)

    def chunk(i, carry):
        rows = _chunk_rows(i, c)
        at = pl.ds(first + i, 1)
        cb, bb = c_ref[rows, :], b_ref[rows, :]
        dt = bb.dtype
        scores = _dot(cb, bb, (1, 1))
        out = []
        for sl in range(slabs):
            s, top = carry[sl]
            at_w = slice(sl * w, (sl + 1) * w)
            ids = range(sl * hp, (sl + 1) * hp)
            heads = [_head(gam_ref[h, at, :], dt_ref[h, at, :]) for h in ids]
            xf = x_ref[rows, at_w].astype(jnp.float32)
            vf = xf * _over_lanes([h.dcol for h in heads], p, w)
            hs_ref[i, at_w, :] = s
            y = _over_lanes([h.eg for h in heads], p, w) \
                * _dot(cb, s.astype(dt), (1, 1)) \
                + _dot(_masked_scores(scores, heads, dt),
                       _stacked(vf.astype(dt), hp, p), (1, 0)) \
                + d_ref[:, at_w] * xf
            y_ref[rows, at_w] = y.astype(y_ref.dtype)
            vd = (vf * _over_lanes([h.ekd for h in heads], p, w)).astype(dt)
            s = _over_rows([lam_ref[h, first + i] for h in ids], p, s.shape) \
                * s + _dot(vd, bb, (0, 0))
            out.append((s, jnp.maximum(top, jnp.abs(s))))
        return tuple(out)

    # top: each state element's largest size over the states that LEFT a
    # chunk so far (the first chunk's entering state is zero)
    at = [slice(sl * w, (sl + 1) * w) for sl in range(slabs)]
    done = jax.lax.fori_loop(
        0, group, chunk, tuple((s_ref[a, :], top_ref[a, :]) for a in at))
    for a, (s, top) in zip(at, done):
        s_ref[a, :], top_ref[a, :] = s, top


def _tokens_spec(groups, rows, width, index):
    """``rows`` tokens of one group's ``width`` features of a [B, S, groups
    x width] array; the grid's first axis is batch x group."""
    return pl.BlockSpec((None, rows, width),
                        lambda h, j: (h // groups, index(j), h % groups))


def _rows_spec(groups, r, n, c):
    """A group's heads' dt or gamma whole, a chunk a row: [B, H, n, C]
    (fetched once a group: the block does not move along the walk)."""
    return pl.BlockSpec((None, r, n, c),
                        lambda h, j: (h // groups, h % groups, 0, 0))


def _states_spec(groups, group, width, n, index):
    """[B, G, chunks, H / G P, N]."""
    return pl.BlockSpec((None, None, group, width, n),
                        lambda h, j: (h // groups, h % groups, index(j), 0, 0))


def _lam_spec(r, n):
    """A group's heads' decays, one a chunk, as scalars: [B G, r, n]."""
    return pl.BlockSpec((None, r, n), lambda h, j: (h, 0, 0),
                        memory_space=pltpu.SMEM)


def _skip_spec(groups, width):
    """D laid over a group's features: [1, H P]."""
    return pl.BlockSpec((1, width), lambda h, j: (0, h % groups))


def _compiler_params():
    """Groups in parallel; the chunks in order along a walk."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _fwd_call(x, b, c, dtr, gam, lam, skip, groups, p, group, interpret):
    bt, s, hp_all = x.shape
    _, heads, n, ck = dtr.shape
    r = heads // groups
    width, state = hp_all // groups, b.shape[-1] // groups
    rows = group * ck
    same = lambda j: j
    f32 = jnp.float32
    return pl.pallas_call(
        partial(_fwd_kernel, c=ck, group=group, r=r, p=p,
                hp=_heads_a_slab(r, p)),
        grid=(bt * groups, n // group),
        in_specs=[_lam_spec(r, n), _tokens_spec(groups, rows, width, same),
                  _tokens_spec(groups, rows, state, same),
                  _tokens_spec(groups, rows, state, same),
                  _rows_spec(groups, r, n, ck), _rows_spec(groups, r, n, ck),
                  _skip_spec(groups, width)],
        out_specs=[
            _tokens_spec(groups, rows, width, same),
            _states_spec(groups, group, width, state, same),
            pl.BlockSpec((None, None, width, state),
                         lambda h, j: (h // groups, h % groups, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bt, groups, n, width, state), f32),
                   jax.ShapeDtypeStruct((bt, groups, width, state), f32)],
        scratch_shapes=[pltpu.VMEM((width, state), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssd_fwd")(lam, x, b, c, dtr, gam, skip)


# --------------------------------------------------------------------------
# ssd_bwd: the walk in reverse, dh carried, a chunk's tensors formed again
# --------------------------------------------------------------------------

def _bwd_kernel(lam_ref, x_ref, b_ref, c_ref, dt_ref, gam_ref, d_ref, hs_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dgam_ref, dd_ref,
                ds_ref, *, c, group, groups_n, r, p, hp):
    first = (groups_n - 1 - pl.program_id(1)) * group   # the last group first
    w = hp * p
    slabs = r // hp
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    def chunk(j, carry):        # carry: the gradients of the states that LEAVE
        i = group - 1 - j
        rows = _chunk_rows(i, c)
        at = pl.ds(first + i, 1)
        cb, bb = c_ref[rows, :], b_ref[rows, :]
        dt = bb.dtype
        scores = _dot(cb, bb, (1, 1))
        rw, cl = _iota((c, c), 0), _iota((c, c), 1)
        diag, keep = rw == cl, rw >= cl
        dq = jnp.zeros(cb.shape, f32)       # summed over the group's heads
        dk = jnp.zeros(bb.shape, f32)
        dscores = jnp.zeros((c, c), f32)
        out = []
        for sl in range(slabs):
            ds = carry[sl]
            at_w = slice(sl * w, (sl + 1) * w)
            ids = range(sl * hp, (sl + 1) * hp)
            heads = [_head(gam_ref[h, at, :], dt_ref[h, at, :]) for h in ids]
            lams = [lam_ref[h, first + i] for h in ids]
            xf = x_ref[rows, at_w].astype(f32)
            dy = dy_ref[rows, at_w]
            dyf = dy.astype(f32)
            dsel = _over_lanes([h.dcol for h in heads], p, w)
            eg = _over_lanes([h.eg for h in heads], p, w)
            ekd = _over_lanes([h.ekd for h in heads], p, w)
            vf = xf * dsel
            vb = vf.astype(dt)
            vdf = vf * ekd
            s = hs_ref[i, at_w, :]
            sb, dsb = s.astype(dt), ds.astype(dt)
            dygf = eg * dyf
            dyg = dygf.astype(dt)
            # the state's matmuls, pulled back
            out.append(_over_rows(lams, p, s.shape) * ds
                       + _dot(dyg, cb, (0, 0)))
            dq = dq + _dot(dyg, sb, (1, 0))
            dk = dk + _dot(vdf.astype(dt), dsb, (1, 0))
            dvd = _dot(bb, dsb, (1, 1))                 # [C, w]
            inter = _dot(cb, sb, (1, 1))                # [C, w]
            # the masked scores' product with the values
            both = _dot(_masked_scores(scores, heads, dt), dy, (0, 0))
            dv = both[:c] if hp == 1 else sum(
                _lanes_of(n, both[n * c:(n + 1) * c], p) for n in range(hp))
            dv = dv + ekd * dvd
            dx_ref[rows, at_w] = (dsel * dv + d_ref[:, at_w] * dyf
                                  ).astype(dx_ref.dtype)
            dd_ref[:, at_w] += jnp.sum(dyf * xf, axis=0, keepdims=True)
            # per-token numbers: a head's lanes summed
            through = dygf * inter - dvd * vdf      # d gamma through exp(gamma) and exp(gamma_C - gamma)
            for n, (h, head, lam) in enumerate(zip(ids, heads, lams)):
                dm = jnp.where(keep, _dot(_lanes_of(n, dy, p), vb, (1, 1)),
                               0.0)
                dscores = dscores + dm * head.decay
                e = dm * (scores * head.decay)          # d decay x decay
                own = lambda z: jnp.sum(_lanes_of(n, z, p), axis=1,
                                        keepdims=True)
                # gamma_C: every v exp(gamma_C - gamma) and the state's decay
                rows_h = slice(n * p, (n + 1) * p)
                last = jnp.sum(own(dvd * vdf), axis=0, keepdims=True) \
                    + lam * jnp.sum(jnp.sum(ds[rows_h] * s[rows_h], axis=0,
                                            keepdims=True), axis=1,
                                    keepdims=True)
                dgam_ref[h, at, :] = _as_row(
                    jnp.sum(e, axis=1, keepdims=True) + own(through), diag) \
                    - jnp.sum(e, axis=0, keepdims=True) \
                    + jnp.where(cl[:1] == c - 1, last, 0.0)
                ddt_ref[h, at, :] = _as_row(own(dv * xf), diag)
        # C B^T is a group's: its gradient once, summed
        dsc = dscores.astype(dt)
        dc_ref[rows, :] = (dq + _dot(dsc, bb, (1, 0))).astype(dc_ref.dtype)
        db_ref[rows, :] = (dk + _dot(dsc, cb, (0, 0))).astype(db_ref.dtype)
        return tuple(out)

    at = [slice(sl * w, (sl + 1) * w) for sl in range(slabs)]
    done = jax.lax.fori_loop(0, group, chunk,
                             tuple(ds_ref[a, :] for a in at))
    for a, ds in zip(at, done):
        ds_ref[a, :] = ds


def _bwd_call(x, b, c, dtr, gam, lam, skip, hs, dy, groups, p, group,
              interpret):
    bt, s, _ = x.shape
    _, heads, n, ck = dtr.shape
    r = heads // groups
    width, state = x.shape[-1] // groups, b.shape[-1] // groups
    rows, groups_n = group * ck, n // group
    rev = lambda j: groups_n - 1 - j
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    tokens = lambda wd: _tokens_spec(groups, rows, wd, rev)
    return pl.pallas_call(
        partial(_bwd_kernel, c=ck, group=group, groups_n=groups_n, r=r, p=p,
                hp=_heads_a_slab(r, p)),
        grid=(bt * groups, groups_n),
        in_specs=[_lam_spec(r, n), tokens(width), tokens(state),
                  tokens(state), _rows_spec(groups, r, n, ck),
                  _rows_spec(groups, r, n, ck), _skip_spec(groups, width),
                  _states_spec(groups, group, width, state, rev),
                  tokens(width)],
        out_specs=[tokens(width), tokens(state), tokens(state),
                   _rows_spec(groups, r, n, ck), _rows_spec(groups, r, n, ck),
                   pl.BlockSpec((None, 1, width), lambda h, j: (h, 0, 0))],
        out_shape=[like(x), like(b), like(c), like(dtr), like(gam),
                   jax.ShapeDtypeStruct((bt * groups, 1, width),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((width, state), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="ssd_bwd")(lam, x, b, c, dtr, gam, skip, hs, dy)


# --------------------------------------------------------------------------
# custom-vjp core, on the kernels' layout
# --------------------------------------------------------------------------

def _decays(dtr, a, groups):
    """dt [B, H, n, C], A [H] -> (gamma, the same shape; exp(gamma_C) [B G, H
    / G, n])."""
    gam = jnp.cumsum(dtr * a[None, :, None, None], axis=-1)
    bt, heads, n, _ = dtr.shape
    return gam, jnp.exp(gam[..., -1]).reshape(bt * groups, heads // groups, n)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _ssd(x, b, c, dtr, a, skip, groups, p, group, interpret):
    return _ssd_fwd(x, b, c, dtr, a, skip, groups, p, group, interpret)[0]


def _ssd_fwd(x, b, c, dtr, a, skip, groups, p, group, interpret):
    gam, lam = _decays(dtr, a, groups)
    y, hs, top = _fwd_call(x, b, c, dtr, gam, lam, skip, groups, p, group,
                           interpret)
    return (y, jnp.max(top)), (x, b, c, dtr, a, skip, hs)


def _ssd_bwd(groups, p, group, interpret, res, cts):
    x, b, c, dtr, a, skip, hs = res
    dy, _ = cts         # the boundary states are a counter's input: no gradient
    gam, lam = _decays(dtr, a, groups)
    dx, db, dc, ddt, dgam, dd = _bwd_call(
        x, b, c, dtr, gam, lam, skip, hs, dy.astype(x.dtype), groups, p,
        group, interpret)
    # gamma is dt A's running sum: dt_t A reaches every gamma from t on
    dda = jnp.flip(jnp.cumsum(jnp.flip(dgam, -1), axis=-1), -1)
    return (dx, db, dc, ddt + dda * a[None, :, None, None],
            jnp.sum(dda * dtr, axis=(0, 2, 3)),
            jnp.sum(dd.reshape(x.shape[0], 1, -1), axis=0))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, a, b, c, d, *, chunk: int = CHUNK,
        interpret: Optional[bool] = None):
    """-> ``(y [B, S, H, P] in x's dtype, largest |h| over the chunk
    boundaries)``. Differentiable in all six arguments; the second output
    carries no gradient. A sequence that is no whole number of chunks is
    padded (a padded token has dt = 0 and x = 0: the state passes it
    unchanged)."""
    if interpret is None:
        interpret = _interpret_default()
    bt, s, heads, p = x.shape
    groups, n = b.shape[2:]
    if heads % groups or c.shape != b.shape or b.shape[:2] != (bt, s) \
            or dt.shape != (bt, s, heads) or a.shape != (heads,) \
            or d.shape != (heads,):
        raise ValueError(
            f"ssd: x {x.shape} [B, S, H, P], dt {dt.shape} [B, S, H], A, D "
            f"{a.shape}, {d.shape} [H], B, C {b.shape}, {c.shape} [B, S, G, "
            f"N] with H a multiple of G")
    dtype = x.dtype
    sched = ssd_schedule(bt, s, heads, p, n, groups, chunk=chunk,
                         itemsize=jnp.dtype(dtype).itemsize)
    chunks = sched.chunks
    pad = chunks * chunk - s
    f32 = jnp.float32

    def flat(t):                # [B, S, heads or groups, width] -> [B, n C, ...]
        return jnp.pad(t.astype(dtype).reshape(bt, s, -1),
                       ((0, 0), (0, pad), (0, 0)))

    dtr = jnp.moveaxis(jnp.pad(dt.astype(f32), ((0, 0), (0, pad), (0, 0))),
                       2, 1).reshape(bt, heads, chunks, chunk)
    y, state_max = _ssd(
        flat(x), flat(b), flat(c), dtr, a.astype(f32),
        jnp.repeat(d.astype(f32), p)[None], groups, p, sched.group,
        bool(interpret))
    return (y[:, :s].reshape(bt, s, heads, p),
            jax.lax.stop_gradient(state_max))


def ssd_reference(x, dt, a, b, c, d):
    """The recurrence token by token (``lax.scan``), float32: what the tests
    hold the kernels to. -> ``(y [B, S, H, P] float32, the last state [B, H,
    P, N])``."""
    f32 = jnp.float32
    r = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(t.astype(f32), r, axis=2) for t in (b, c))
    x, dt, a, d = (t.astype(f32) for t in (x, dt, a, d))

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs        # [B, H, P], [B, H], [B, H, N] x 2
        h = h * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t, precision=_HIGHEST) \
            + d[:, None] * x_t

    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], f32)
    last, y = jax.lax.scan(step, h0, tuple(jnp.swapaxes(t, 0, 1)
                                           for t in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1), last
