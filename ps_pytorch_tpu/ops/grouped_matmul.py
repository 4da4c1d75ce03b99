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
- Rows past the groups are a pseudo-group E whose visits store zeros.
- ``moe_gmm_fwd``: grid (N tiles, visits, K tiles), f32 accumulator over K.
  ``moe_gmm_dlhs`` is the same kernel reading ``rhs`` transposed (the
  contraction runs over its last axis): no [E, N, K] copy of the weights is
  ever made, which is what ``jax.lax.ragged_dot`` pays for that gradient.
  ``moe_gmm_drhs``: grid (K tiles, N tiles, visits), per group
  ``lhs^T dout`` accumulated over the group's visits with the other groups'
  rows masked to zero; a group with no rows is visited once so that its
  gradient is written as zeros.
- Matmuls run with ``preferred_element_type=f32`` (bf16 inputs hit the MXU
  natively; f32 inputs take the default single bf16 pass, as every other
  matmul of the LM step does).
- Compiled on TPU, Pallas interpreter elsewhere (``ops/_backend.py``).

On the v5e at M=32768, K/N=2048/1024, 64 groups this design was the faster
against ``jax.lax.ragged_dot`` (XLA's own Mosaic kernel): PERF.md, Findings
PR 25 has the A/B.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default

# (tm, tk, tn) targets by input itemsize: what fits the 16 MiB of scoped VMEM
# double-buffered, from the chip sweep (PERF.md, Findings PR 25).
_TILES = {4: (512, 1024, 512), 2: (512, 1024, 1024)}


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


def _tiles(m: int, k: int, n: int, dtype):
    tm, tk, tn = _TILES[jnp.dtype(dtype).itemsize]
    return _fit(m, tm, 8), _fit(k, tk, 128), _fit(n, tn, 128)


def _dot(a, b, *, trans_a=False, trans_b=False):
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _visits(group_sizes, m: int, tm: int, *, remainder: bool,
            visit_empty: bool):
    """The kernels' schedule: -> (offsets [G+1], group_ids [V], tile_ids [V],
    n_visits [1]), int32, V = M/tm + G - 1 the static worst case. With
    ``remainder`` the rows past the groups are one more group (G = E + 1)."""
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


def _rows_of_group(offs_ref, gid_ref, tid_ref, i, shape, tm):
    g = gid_ref[i]
    rows = tid_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return g, (rows >= offs_ref[g]) & (rows < offs_ref[g + 1])


def _gmm_kernel(offs_ref, gid_ref, tid_ref, nv_ref, lhs_ref, rhs_ref,
                out_ref, acc, *, tm, n_groups, trans_rhs):
    i, k = pl.program_id(1), pl.program_id(2)
    live = i < nv_ref[0]

    @pl.when(live & (k == 0))
    def _init():
        acc[:] = jnp.zeros_like(acc)

    @pl.when(live)
    def _tile():
        acc[:] += _dot(lhs_ref[...], rhs_ref[0], trans_b=trans_rhs)

    @pl.when(live & (k == pl.num_programs(2) - 1))
    def _store():
        g, mine = _rows_of_group(offs_ref, gid_ref, tid_ref, i, acc.shape, tm)
        val = jnp.where(g == n_groups, 0.0, acc[:])    # rows past the groups
        out_ref[...] = jnp.where(
            mine, val, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _gmm_call(lhs, rhs, group_sizes, *, trans_rhs: bool, name: str):
    """lhs [M, K] x rhs [E, K, N] (or [E, N, K] read transposed) -> [M, N]."""
    m, k = lhs.shape
    e = rhs.shape[0]
    n = rhs.shape[1] if trans_rhs else rhs.shape[2]
    tm, tk, tn = _tiles(m, k, n, lhs.dtype)
    sched = _visits(group_sizes, m, tm, remainder=True, visit_empty=False)
    last_group, last_k = e - 1, k // tk - 1

    def k_block(i, ki, nv):
        # A skipped visit must not move data: keep the last real visit's
        # final K block instead of walking K again.
        return jnp.where(i < nv[0], ki, last_k)

    def lhs_map(ni, i, ki, offs, gid, tid, nv):
        return tid[i], k_block(i, ki, nv)

    def rhs_map(ni, i, ki, offs, gid, tid, nv):
        g, kb = jnp.minimum(gid[i], last_group), k_block(i, ki, nv)
        return (g, ni, kb) if trans_rhs else (g, kb, ni)

    rhs_block = (1, tn, tk) if trans_rhs else (1, tk, tn)
    return pl.pallas_call(
        partial(_gmm_kernel, tm=tm, n_groups=e, trans_rhs=trans_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, m // tm + e, k // tk),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda ni, i, ki, offs, gid, tid, nv:
                                   (tid[i], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name=name,
        interpret=interpret_default(),
    )(*sched, lhs, rhs)


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


def _drhs_call(lhs, dout, group_sizes, out_dtype):
    """Per group ``lhs[rows]^T dout[rows]``: [M, K], [M, N] -> [E, K, N]."""
    m, k = lhs.shape
    n = dout.shape[1]
    e = group_sizes.shape[0]
    tm, tk, tn = _tiles(m, k, n, lhs.dtype)
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
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="moe_gmm_drhs",
        interpret=interpret_default(),
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
    ``rhs[e]``; float32 accumulation, result in ``lhs.dtype``."""
    _check(lhs, rhs, group_sizes)
    return _gmm_call(lhs, rhs, group_sizes, trans_rhs=False,
                     name="moe_gmm_fwd")


def _gmm_fwd(lhs, rhs, group_sizes):
    return gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, dout):
    lhs, rhs, group_sizes = res
    dlhs = _gmm_call(dout, rhs, group_sizes, trans_rhs=True,
                     name="moe_gmm_dlhs")
    drhs = _drhs_call(lhs, dout, group_sizes, rhs.dtype)
    return dlhs, drhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)
