"""Grouped matmul over rows sorted by group (Pallas) — the routed experts' matmul.

``gmm(lhs [M, K], rhs [E, K, N], group_sizes [E]) -> [M, N]``: rows
``offset[e] .. offset[e+1]`` of ``lhs`` (``offset`` the running sum of
``group_sizes``) are multiplied by ``rhs[e]``; rows past ``sum(group_sizes)``
come back as zeros. It is what a dropless mixture-of-experts layer does
after sorting its token-to-expert assignments by expert
(``models/moe.py:DroplessMoE``): work and bytes grow with the assignments,
never with experts x capacity.

Design (the megablox one that ships with jax, written out for this repo's
three uses)
- Rows are cut into tiles of ``tm``. A tile that holds rows of g groups is
  visited g times, once per group, and each visit stores only its group's
  rows (masked read-modify-write of the output block, which stays in VMEM
  while consecutive grid steps keep its index). The list of visits
  (``group id``, ``row tile``) is built outside the kernel from
  ``group_sizes`` and handed in by scalar prefetch, so the block index maps
  read it; the grid has the static worst case ``M/tm + E`` visits and the
  ones past the real count are skipped (their block indices repeat the last
  real step's, so they move no data).
- Rows past the groups are a pseudo-group E whose visits store zeros and do
  nothing else: no matmul, and the rows' block index repeats the last
  multiplying visit's, so no row of theirs is fetched. A layer that holds a
  share of its experts sizes its rows for 1.5 times what the share draws at
  balance (``models/moe.py:HELD_ROWS_SLACK``), so a third of its row tiles
  are such (v5e, PR 48: PERF.md, Findings).
- ``moe_gmm_fwd``: grid (N tiles, visits, K tiles), f32 accumulator over K.
  ``moe_gmm_dlhs`` is the same kernel reading ``rhs`` transposed (the
  contraction runs over its last axis): no [E, N, K] copy of the weights is
  ever made, which is what ``jax.lax.ragged_dot`` pays for that gradient.
  The kernel streams ``rhs`` itself: a group's [K, tn] weights come in by
  one DMA started on the first visit of the group before it (two buffers),
  because the automatic pipeline fetches one grid step ahead and a float32
  weight tile takes longer than a visit of a few bfloat16 rows computes
  (v5e, PR 28: 16.5 -> 15.6 ms a layer's three matmuls forward and back).
  ``moe_gmm_drhs``: grid (K tiles, N tiles, visits), per group
  ``lhs^T dout`` accumulated over the group's visits with the other groups'
  rows masked to zero; a group with no rows is visited once so that its
  gradient is written as zeros.
- ``rhs`` may be wider than ``lhs``: a bfloat16 model hands its float32
  expert parameters over as they are, the kernel casts a group's weights to
  the rows' dtype in VMEM once a group, and ``moe_gmm_drhs`` writes the
  weight gradient in ``rhs.dtype`` straight from its float32 accumulator.
  Casting outside instead costs six XLA passes over 805M parameters a step
  and holds six bfloat16 copies for the backward (v5e: 18.1 against 15.6 ms).
- Matmuls run with ``preferred_element_type=f32`` (bf16 inputs hit the MXU
  natively; f32 inputs take the default single bf16 pass, as every other
  matmul of the LM step does).
- Compiled on TPU, Pallas interpreter elsewhere (``ops/_backend.py``).

On the v5e at M=32768, K/N=2048/1024, 64 groups this design was the faster
against ``jax.lax.ragged_dot`` (XLA's own Mosaic kernel): PERF.md, Findings
PR 25 has the A/B.
"""

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default

# (tm, tk, tn) targets by the rows' itemsize, from chip sweeps at the OLMoE
# cell's shape (PERF.md, Findings PR 25 for 4 bytes, PR 28 for 2). Few rows a
# tile: a tile that straddles groups is multiplied once a group. All of K and
# wide N: a group's weights are then fetched once a call.
_TILES = {4: (512, 1024, 512), 2: (256, 2048, 2048)}
# What the two weight buffers (and the cast copy) of ``moe_gmm_fwd|dlhs`` may
# take of VMEM (they hold all of K for one N tile), and the limit handed to
# Mosaic (v5e: 128 MiB physical, 16 MiB scoped by default).
WEIGHTS_VMEM_BYTES = 40 * 2 ** 20
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _fit(dim: int, target: int, align: int) -> int:
    """Largest tile <= target that divides ``dim`` and is a multiple of
    ``align``; the whole of ``dim`` when it is no larger than the target (a
    full-extent block is always legal)."""
    if dim <= target:
        return dim
    for t in range(target - target % align, 0, -align):
        if dim % t == 0:
            return t
    raise ValueError(f"no tile <= {target} that is a multiple of {align} "
                     f"divides {dim}")


def _tiles(m: int, k: int, n: int, dtype, weights_dtype=None):
    """(tm, tk, tn) for [m, k] rows of ``dtype``; with ``weights_dtype``, tn
    also keeps two [k, tn] weight buffers of that dtype, and their copy in
    ``dtype`` where the two differ, inside WEIGHTS_VMEM_BYTES. An ``n``
    or ``k`` that is no multiple of 128 lanes (an expert width of 1856) has no
    tile but itself and is taken whole, whatever the table and the budget say:
    VMEM_LIMIT_BYTES is then the only bound (1856 under K = 2688 and bfloat16
    rows: 50 MB of the 64 MiB; tests/test_mosaic_compile.py compiles it).
    Such an ``n`` can be the rows' last dimension (a block may span a whole
    dimension) but not the weights': the weights come in by a hand-written
    DMA, whose slices keep to the 128 lanes, so a caller stores them with
    that dimension second to last and multiplies by ``gmm_t``."""
    tm, tk, tn = _TILES[jnp.dtype(dtype).itemsize]
    if weights_dtype is not None:
        rows, w = jnp.dtype(dtype), jnp.dtype(weights_dtype)
        column = k * (2 * w.itemsize + (w != rows) * rows.itemsize)
        tn = min(tn, max(WEIGHTS_VMEM_BYTES // column // 128, 1) * 128)
    lanes = lambda dim, target: dim if dim % 128 else _fit(dim, target, 128)
    return _fit(m, tm, 8), lanes(k, tk), lanes(n, tn)


class GmmSchedule(NamedTuple):
    """What the three kernels of one expert matmul ``[rows, d] x [groups, d,
    f]`` do, from its shape alone (the matmul back to ``d`` runs the same
    three with ``fwd`` and ``dlhs`` exchanged and ``drhs``'s last two, as
    does one against weights stored transposed, ``gmm_t``)."""
    fwd: tuple          # (tm, tk, tn) of moe_gmm_fwd
    dlhs: tuple         # ... of moe_gmm_dlhs: [rows, f], the weights transposed
    drhs: tuple         # ... of moe_gmm_drhs
    row_tiles: int      # row tiles of tm the call is sized for
    row_tiles_at_balance: int   # ... the groups own at balance; the rest
                                # are visited and not multiplied
    visits: int         # fwd's and dlhs's grid along the visits (worst case)
    weight_bytes: int   # weights a fwd or dlhs call streams: every group's, once

    def describe(self) -> str:
        return " ".join(
            f"{name}={'/'.join(map(str, v)) if isinstance(v, tuple) else v}"
            for name, v in self._asdict().items())


def gmm_schedule(rows: int, rows_at_balance: int, d: int, f: int, groups: int,
                 itemsize: int = 2, weights_itemsize: int = 4) -> GmmSchedule:
    """The ``KERNELS`` line's record of an expert layer's grouped matmuls:
    ``rows`` the layer sizes its sorted rows for, ``rows_at_balance`` of them
    the held groups' at balance (``models/moe.py:held_rows``)."""
    dtype, weights = (
        {2: jnp.bfloat16, 4: jnp.float32}[size]
        for size in (itemsize, weights_itemsize))
    fwd = _tiles(rows, d, f, dtype, weights)
    return GmmSchedule(
        fwd, _tiles(rows, f, d, dtype, weights), _tiles(rows, d, f, dtype),
        rows // fwd[0], -(-rows_at_balance // fwd[0]),
        rows // fwd[0] + groups, groups * d * f * weights_itemsize)


def _dot(a, b, *, trans_a=False, trans_b=False):
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _visits(group_sizes, m: int, tm: int, *, remainder: bool,
            visit_empty: bool):
    """The kernels' schedule: -> (offsets [G+1], group_ids [V], tile_ids [V],
    n_visits [1]), int32, V = M/tm + G - 1 the static worst case. With
    ``remainder`` the rows past the groups are one more group (G = E + 1),
    visited last."""
    gs = group_sizes.astype(jnp.int32)
    if remainder:
        gs = jnp.concatenate([gs, (m - jnp.sum(gs))[None]])
    g = gs.shape[0]
    ends = jnp.cumsum(gs)
    starts = ends - gs
    tiles = (ends + tm - 1) // tm - starts // tm
    tiles = jnp.where(gs == 0, 1 if visit_empty else 0, tiles)
    tile_ends = jnp.cumsum(tiles)
    n_visits = tile_ends[-1]
    v = m // tm + g - 1
    i = jnp.minimum(jnp.arange(v, dtype=jnp.int32),
                    jnp.maximum(n_visits - 1, 0))
    group_ids = jnp.searchsorted(tile_ends, i, side="right").astype(jnp.int32)
    first_tile = jnp.minimum(starts // tm, m // tm - 1)
    tile_ids = first_tile[group_ids] + i - (tile_ends - tiles)[group_ids]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_ids, tile_ids.astype(jnp.int32), n_visits[None]


def _lhs_block(i, ki, offsets, tile_ids, n_visits, *, n_groups: int,
               row_tiles: int, tm: int, last_k: int):
    """The block of rows visit ``i`` of ``moe_gmm_fwd|dlhs`` reads at K step
    ``ki``. A visit that multiplies nothing (the rows past the groups, whose
    tiles are the schedule's last, and the grid's steps past its end) must
    move no data: it keeps the last multiplying visit's final block instead
    of fetching its own rows or walking K again."""
    covered = offsets[n_groups]
    own = n_visits[0] - jnp.where(covered < row_tiles * tm,
                                  row_tiles - covered // tm, 0)
    at = jnp.minimum(i, jnp.maximum(own - 1, 0))
    return tile_ids[at], jnp.where(i < own, ki, last_k)


def _rows_of_group(offs_ref, gid_ref, tid_ref, i, shape, tm):
    g = gid_ref[i]
    rows = tid_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return g, (rows >= offs_ref[g]) & (rows < offs_ref[g + 1])


def _weight_turns(group_ids, n_visits):
    """For the kernels that stream ``rhs`` themselves: per visit -> (slot [V]
    of the two weight buffers its group uses, next [V] the group visited after
    it, or a number past every group when there is none), int32."""
    v = group_ids.shape[0]
    i = jnp.arange(v, dtype=jnp.int32)
    change = (i == 0) | (group_ids != jnp.roll(group_ids, 1))
    slot = (jnp.cumsum(change) - 1) % 2
    at = jax.lax.cummin(jnp.where(change, i, v), reverse=True)
    nxt = jnp.concatenate([at[1:], jnp.full((1,), v, jnp.int32)])
    nxt_group = jnp.where(nxt < n_visits[0],
                          group_ids[jnp.minimum(nxt, v - 1)],
                          jnp.iinfo(jnp.int32).max)
    return slot.astype(jnp.int32), nxt_group.astype(jnp.int32)


def _gmm_kernel(offs_ref, gid_ref, tid_ref, nv_ref, slot_ref, next_ref,
                lhs_ref, rhs_hbm, out_ref, acc, wbuf, sem, *cast,
                tm, tk, tn, n_groups, trans_rhs):
    ni, i, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = i < nv_ref[0]
    g = gid_ref[i]
    s = slot_ref[i]
    # Rows past the groups (group n_groups) are multiplied by nothing: their
    # visits fetch no weights and no rows (``_lhs_block``), run no matmul and
    # only store zeros over whatever the accumulator still holds.
    own = live & (g < n_groups)
    first = (i == 0) | (g != gid_ref[jnp.maximum(i - 1, 0)])
    w = cast[0] if cast else None   # the group's weights in the rows' dtype

    def k_tile(ref, c):
        rows = pl.ds(pl.multiple_of(c * tk, tk), tk)
        return ref[:, rows] if trans_rhs else ref[rows, :]

    def fetch(group, to):
        cols = pl.ds(pl.multiple_of(ni * tn, tn), tn)
        src = rhs_hbm.at[group, cols, :] if trans_rhs \
            else rhs_hbm.at[group, :, cols]
        return pltpu.make_async_copy(src, wbuf.at[to], sem.at[to])

    # A group's weights (all of K, this N tile) come in by one DMA that is
    # started on the first visit of the group BEFORE it, so that it has that
    # group's every visit to arrive in; the automatic pipeline looks one
    # grid step ahead, which a float32 tile under a few rows of bfloat16
    # outlasts.
    @pl.when(own & first & (k == 0))
    def _weights():
        @pl.when(i == 0)
        def _():
            fetch(g, s).start()
        fetch(g, s).wait()

        @pl.when(next_ref[i] < n_groups)
        def _():
            fetch(next_ref[i], 1 - s).start()
        if cast:    # float32 weights under narrower rows: cast once a group
            for c in range(w.shape[0]):
                w[c] = k_tile(wbuf.at[s], c).astype(w.dtype)

    @pl.when(own & (k == 0))
    def _init():
        acc[:] = jnp.zeros_like(acc)

    @pl.when(own)
    def _tile():
        rhs = w[k] if cast else k_tile(wbuf.at[s], k)
        acc[:] += _dot(lhs_ref[...], rhs, trans_b=trans_rhs)

    @pl.when(live & (k == pl.num_programs(2) - 1))
    def _store():
        _, mine = _rows_of_group(offs_ref, gid_ref, tid_ref, i, acc.shape, tm)
        val = jnp.where(g == n_groups, 0.0, acc[:])    # rows past the groups
        out_ref[...] = jnp.where(
            mine, val, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


# The calls are jitted on their static arguments: a training run traces its
# model five times (init, the dtype probe, a forward, the FLOPs count, the
# step) and a layer calls like shapes twice, and tracing a kernel body costs
# 0.1 s on a chip's host. A trace is reused wherever the signature repeats.
@partial(jax.jit, static_argnames=("tiles", "trans_rhs", "name", "interpret"))
def _gmm_call(lhs, rhs, group_sizes, *, tiles, trans_rhs: bool, name: str,
              interpret: bool):
    """lhs [M, K] x rhs [E, K, N] (or [E, N, K] read transposed) -> [M, N]
    in ``lhs.dtype``; ``rhs`` may be wider than ``lhs`` (float32 parameters
    under bfloat16 rows) and is then cast in VMEM, once a group."""
    m, k = lhs.shape
    e = rhs.shape[0]
    n = rhs.shape[1] if trans_rhs else rhs.shape[2]
    tm, tk, tn = tiles
    offs, gid, tid, nv = _visits(group_sizes, m, tm, remainder=True,
                                 visit_empty=False)

    def lhs_map(ni, i, ki, offs, gid, tid, nv, slot, nxt):
        return _lhs_block(i, ki, offs, tid, nv, n_groups=e,
                          row_tiles=m // tm, tm=tm, last_k=k // tk - 1)

    w_tile = (tn, tk) if trans_rhs else (tk, tn)
    w_block = (tn, k) if trans_rhs else (k, tn)
    scratch = [pltpu.VMEM((tm, tn), jnp.float32),
               pltpu.VMEM((2,) + w_block, rhs.dtype),
               pltpu.SemaphoreType.DMA((2,))]
    if rhs.dtype != lhs.dtype:
        scratch.append(pltpu.VMEM((k // tk,) + w_tile, lhs.dtype))
    return pl.pallas_call(
        partial(_gmm_kernel, tm=tm, tk=tk, tn=tn, n_groups=e,
                trans_rhs=trans_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n // tn, m // tm + e, k // tk),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda ni, i, ki, offs, gid, tid, nv,
                                   slot, nxt: (tid[i], ni)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=name,
        interpret=interpret,
    )(offs, gid, tid, nv, *_weight_turns(gid, nv), lhs, rhs)


def _drhs_kernel(offs_ref, gid_ref, tid_ref, nv_ref, lhs_ref, dout_ref,
                 out_ref, acc, *, tm):
    i = pl.program_id(2)
    live = i < nv_ref[0]
    g = gid_ref[i]
    first = (i == 0) | (g != gid_ref[jnp.maximum(i - 1, 0)])
    last = (i == nv_ref[0] - 1) | \
        (g != gid_ref[jnp.minimum(i + 1, pl.num_programs(2) - 1)])

    @pl.when(live & first)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    @pl.when(live)
    def _tile():
        _, mine = _rows_of_group(offs_ref, gid_ref, tid_ref, i,
                                 lhs_ref.shape, tm)
        lhs = jnp.where(mine, lhs_ref[...], jnp.zeros_like(lhs_ref))
        acc[:] += _dot(lhs, dout_ref[...], trans_a=True)

    @pl.when(live & last)
    def _store():
        out_ref[0] = acc[:].astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("tiles", "out_dtype", "interpret"))
def _drhs_call(lhs, dout, group_sizes, *, tiles, out_dtype, interpret: bool):
    """Per group ``lhs[rows]^T dout[rows]``: [M, K], [M, N] -> [E, K, N]."""
    m, k = lhs.shape
    n = dout.shape[1]
    e = group_sizes.shape[0]
    tm, tk, tn = tiles
    sched = _visits(group_sizes, m, tm, remainder=False, visit_empty=True)
    return pl.pallas_call(
        partial(_drhs_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, m // tm + e - 1),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ki, ni, i, offs, gid, tid, nv:
                             (tid[i], ki)),
                pl.BlockSpec((tm, tn),
                             lambda ki, ni, i, offs, gid, tid, nv:
                             (tid[i], ni)),
            ],
            out_specs=pl.BlockSpec((1, tk, tn),
                                   lambda ki, ni, i, offs, gid, tid, nv:
                                   (gid[i], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="moe_gmm_drhs",
        interpret=interpret,
    )(*sched, lhs, dout)


def _check(lhs, rhs, group_sizes):
    if lhs.ndim != 2 or rhs.ndim != 3 or group_sizes.ndim != 1:
        raise ValueError(f"gmm wants lhs [M, K], rhs [E, K, N], group_sizes "
                         f"[E]; got {lhs.shape}, {rhs.shape}, "
                         f"{group_sizes.shape}")
    if lhs.shape[1] != rhs.shape[1] or rhs.shape[0] != group_sizes.shape[0]:
        raise ValueError(f"gmm shapes disagree: lhs {lhs.shape}, rhs "
                         f"{rhs.shape}, group_sizes {group_sizes.shape}")


@jax.custom_vjp
def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Rows of ``lhs`` grouped by ``group_sizes`` times their group's
    ``rhs[e]``; float32 accumulation, result and the gradient to the rows in
    ``lhs.dtype``, the gradient to ``rhs`` in ``rhs.dtype``."""
    _check(lhs, rhs, group_sizes)
    (m, k), n = lhs.shape, rhs.shape[2]
    return _gmm_call(lhs, rhs, group_sizes,
                     tiles=_tiles(m, k, n, lhs.dtype, rhs.dtype),
                     trans_rhs=False, name="moe_gmm_fwd",
                     interpret=interpret_default())


def _gmm_fwd(lhs, rhs, group_sizes):
    return gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, dout):
    lhs, rhs, group_sizes = res
    (m, k), n = lhs.shape, rhs.shape[2]
    interpret = interpret_default()
    dlhs = _gmm_call(dout, rhs, group_sizes,
                     tiles=_tiles(m, n, k, dout.dtype, rhs.dtype),
                     trans_rhs=True, name="moe_gmm_dlhs", interpret=interpret)
    drhs = _drhs_call(lhs, dout, group_sizes,
                      tiles=_tiles(m, k, n, lhs.dtype),
                      out_dtype=jnp.dtype(rhs.dtype), interpret=interpret)
    return dlhs, drhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


@jax.custom_vjp
def gmm_t(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``gmm`` against weights stored transposed, as a checkpoint stores a
    linear layer: rows of ``lhs`` [M, K] times their group's ``rhs[e]^T``,
    ``rhs`` [E, N, K] -> [M, N]. The same three kernels with their roles
    exchanged (the forward reads the weights transposed as ``gmm``'s gradient
    to the rows does, and the reverse), so every array that is sliced along
    its last dimension has K there: for an N that is no multiple of 128."""
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[2] \
            or rhs.shape[0] != group_sizes.shape[0]:
        raise ValueError(f"gmm_t wants lhs [M, K], rhs [E, N, K], group_sizes "
                         f"[E]; got {lhs.shape}, {rhs.shape}, "
                         f"{group_sizes.shape}")
    (m, k), n = lhs.shape, rhs.shape[1]
    return _gmm_call(lhs, rhs, group_sizes,
                     tiles=_tiles(m, k, n, lhs.dtype, rhs.dtype),
                     trans_rhs=True, name="moe_gmm_fwd",
                     interpret=interpret_default())


def _gmm_t_fwd(lhs, rhs, group_sizes):
    return gmm_t(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_t_bwd(res, dout):
    lhs, rhs, group_sizes = res
    (m, k), n = lhs.shape, rhs.shape[1]
    interpret = interpret_default()
    dlhs = _gmm_call(dout, rhs, group_sizes,
                     tiles=_tiles(m, n, k, dout.dtype, rhs.dtype),
                     trans_rhs=False, name="moe_gmm_dlhs",
                     interpret=interpret)
    drhs = _drhs_call(dout, lhs, group_sizes,
                      tiles=_tiles(m, n, k, dout.dtype),
                      out_dtype=jnp.dtype(rhs.dtype), interpret=interpret)
    return dlhs, drhs, None


gmm_t.defvjp(_gmm_t_fwd, _gmm_t_bwd)
