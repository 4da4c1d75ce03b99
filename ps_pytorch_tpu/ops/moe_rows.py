"""The rows of a held share summed into their tokens (Pallas): the combine of a
dropless expert layer that holds a share of its experts, and the transpose of
its take (``models/moe.py:DroplessMoE.part``).

``rows_sum(out [R, d], plan, sched, gate) -> [T, d]``:
``y[t] = sum_e gate[e, t] * out[place[e, t]]`` over the held experts ``e``
that token ``t`` is assigned to and whose sorted place is among the part's
first ``count`` rows. In place of XLA's scatter-add, which the TPU runs row by
row (v5e, PR 52: 3.2 ms for 15,360 float32 rows of 4096 against this
kernel's 0.8).

Design
- **The sort is stable**, so inside a held group the rows stand in token order
  and the rows of expert ``e`` for a tile of ``tt`` tokens are ONE contiguous
  run. A run is fetched by DMAs of ``seg`` rows that start at a multiple of 8
  (Mosaic refuses a slice of a tiled HBM array that starts anywhere else, and
  a slice of one row: "must be aligned to tiling (8)"; so a row cannot be
  fetched by its index, and a run is fetched with up to 7 rows of its
  neighbours before it and ``seg - 1`` after). ``segs`` such segments, of any
  experts, fill one ``[segs * seg, d]`` buffer.
- A token's rows are picked out of the buffer on the MXU: a ``[segs * seg,
  tt]`` matrix (transposed: the buffer's rows down, the tile's tokens along)
  that holds a token's gate where the buffer's row is the token's row of that
  segment's expert and zero elsewhere, contracted with the buffer over the
  rows. A segment's rows are consecutive numbers and the plan's table of
  places comes transposed (``place[e, t]``, the tokens along the lanes), so a
  segment's 16 rows of the matrix are ONE comparison of its expert's row of
  the table with the rows' numbers: no search, no reduction. The matrix is
  float32 cut into three bfloat16 pieces whose sum is the float32 exactly
  (float32 rows: one product at the MXU's float32 precision), so no gate and
  no row is rounded: the sum is a float32 sum, accumulated in a ``[tt, d]``
  float32 scratch and written once a tile, with what is to be added to it
  (the other part's sum) added first, in the output's dtype. Neighbours' rows
  meet zeros.
- The grid is the token tiles. A tile's steps (its segments in whole buffers:
  none where no token of it has a held expert) are a loop inside the kernel,
  which lists the segments itself, in scalar code, from where each expert's
  run for each tile begins (``lo``, (tiles + 1) x H integers by scalar
  prefetch; the list outside the kernel was 80 small XLA ops a part: v5e,
  PR 52, 0.65 ms a call and 30 s of a cold set-up's compile). A step lists
  and starts the next step's DMAs, this tile's or a later one's, before it
  waits for its own; the list's state lives in SMEM over the grid.
- **Rows at and past ``count``** (the slack rows of a layer sized for 1.5 times
  balance, whose tiles the grouped matmuls visit and do not multiply) are in
  no run, so no DMA is started for them; where a run's last segment reaches
  past ``count`` the rows there are replaced by zeros before the product (a
  zero gate times a NaN is a NaN).

Compiled on TPU, Pallas interpreter elsewhere (``ops/_backend.py``).
"""

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default
from ps_pytorch_tpu.ops.grouped_matmul import VMEM_LIMIT_BYTES, _fit

HBM_ROWS = 8        # a DMA's rows start at a multiple of this (the HBM tiling)
LANES = 128


class RowsSchedule(NamedTuple):
    """What ``moe_rows_sum`` does for one part of a layer, from its shapes."""
    tokens_tile: int    # tt: tokens an output block holds
    seg: int            # rows a DMA fetches
    segs: int           # segments a step multiplies at once
    cols: int           # columns of d a matmul takes at a time
    tiles: int          # token tiles: the grid
    rows: int           # rows the part is sized for
    rows_at_balance: int    # ... of which the held groups own at balance
    fwd_bytes: int      # the forward's call (the combine) moves at balance
    bwd_bytes: int      # ... the backward's (the take's transpose)

    def describe(self) -> str:
        return " ".join(f"{name}={v}" for name, v in self._asdict().items())


def rows_schedule(rows: int, rows_at_balance: int, tokens: int, k: int,
                  d: int, dtype, groups: int) -> RowsSchedule:
    """Tiles of ``moe_rows_sum`` for ``rows`` sorted rows of ``d`` in
    ``dtype``, of which ``groups`` held experts own ``rows_at_balance`` at
    balance, summed into ``tokens`` tokens of ``k`` choices. From the shapes
    alone: the ``KERNELS`` line's record."""
    item = jnp.dtype(dtype).itemsize
    seg = 16 if item == 2 else HBM_ROWS     # a packed VMEM tile is 16 rows
    # The tokens lie along the lanes of the plan's tables: whole lane tiles,
    # or all. 256 tokens and 256 rows a step from the chip's sweep at the
    # five held cells' shapes (PERF.md, Findings PR 52): a tile twice as tall
    # has runs twice as long, so fewer of a segment's rows are neighbours'
    # (Qwen3-Next, 2.5 rows a run of 128 tokens: 1.86 ms a combine against
    # 2.26), and a buffer twice as long halves the steps.
    tt = next((n for n in (2 * LANES, LANES) if tokens % n == 0), tokens)
    segs = 2 * LANES // seg
    cols = d if d % LANES else _fit(d, 512, LANES)
    tiles = tokens // tt
    # a call reads the rows the held groups own (and their segments'
    # neighbours, not counted) and writes the tokens: the combine adds the
    # other part's float32 sum, the take's transpose has none
    moved = rows_at_balance * d * item + tokens * d * item
    return RowsSchedule(tt, seg, segs, cols, tiles, rows, rows_at_balance,
                        fwd_bytes=moved + tokens * d * 4, bwd_bytes=moved)


class RowsPlan(NamedTuple):
    """One part's routing as ``rows_sum`` reads it (``rows_plan``)."""
    place: jax.Array    # [H, T] int32: the part's row of (held expert, token), or -1
    lo: jax.Array       # [(tiles + 1) * H] int32: the part's row where expert e's
                        # run for tile i begins, at [i * H + e]; it ends where
                        # tile i + 1's begins
    count: jax.Array    # [1] int32: the part's rows that held groups own


def held_tables(local, gates, n_held: int):
    """Token-major tables of a layer's routing: ``local`` [T, k] the choices as
    held experts' numbers (anything outside ``0..n_held - 1``: not held),
    ``gates`` [T, k] -> (has [T, H] bool, gate [T, H] float32). By comparison:
    no gather, no scatter, and the gates' gradient is a select."""
    hit = local[..., None] == jnp.arange(n_held, dtype=local.dtype)
    gate = jnp.sum(jnp.where(hit, gates[..., None].astype(jnp.float32), 0.0),
                   axis=1)
    return jnp.any(hit, axis=1), gate


def held_places(has, group_sizes, tt: int):
    """Where a stable sort by held expert puts each (token, held expert), with
    no sort: ``has`` [T, H] (token t is assigned to held expert e),
    ``group_sizes`` [H] its sums over tokens -> (place [T, H] int32, the sorted
    place of (t, e): e's first place plus the tokens before t that have e,
    whatever ``has`` says there; lo [T / tt + 1, H]: the place of e's first row
    at or after token i * tt)."""
    t, h = has.shape
    gs = group_sizes.astype(jnp.int32)
    first = jnp.cumsum(gs) - gs
    per_tile = has.reshape(t // tt, tt, h)
    # tokens before t inside its tile: a strict lower triangle of ones times
    # ones and zeros, exact in bfloat16, summed in float32 (counts up to tt)
    below = jnp.tril(jnp.ones((tt, tt), jnp.bfloat16), -1)
    inside = jnp.einsum("ij,njh->nih", below, per_tile.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    in_tile = jnp.sum(per_tile, axis=1, dtype=jnp.int32)      # [tiles, H]
    before = jnp.cumsum(in_tile, axis=0) - in_tile
    place = first + before[:, None] + inside.astype(jnp.int32)
    lo = first + jnp.concatenate(
        [before, jnp.sum(in_tile, axis=0, keepdims=True)])
    return place.reshape(t, h), lo


def rows_plan(has, place, lo, *, start, count) -> RowsPlan:
    """The plan of the part whose rows are the sorted places from ``start``
    on, of which the first ``count`` are summed; ``place`` and ``lo`` from
    ``held_places`` at the part's schedule's ``tokens_tile``."""
    place = place - start
    place = jnp.where(has & (place >= 0) & (place < count), place, -1)
    lo = jnp.clip(lo - start, 0, count).astype(jnp.int32)
    return RowsPlan(place.T, lo.reshape(-1),
                    jnp.asarray(count, jnp.int32)[None])


def _pieces(x, n: int):
    """``x`` float32 as ``n`` bfloat16 arrays whose sum is ``x`` (three: to the
    last bit)."""
    out = []
    for _ in range(n):
        p = x.astype(jnp.bfloat16)
        out.append(p)
        x = x - p.astype(jnp.float32)
    return out


# The kernel's own state, int32 in SMEM. ``cursor``: the tile, expert and row
# its list of segments has come to, and the slot of the step to run next;
# ``steps`` [2, 2 + 2 * segs]: of the step in each buffer its tile (the number
# of tiles: none), how many segments it has, their first rows (a multiple of 8
# plus the eighths of rows a segment moved back from the array's end does not
# own, in the low bits) and their experts.
_TILE, _EXPERT, _ROW, _TURN = range(4)


def _sum_kernel(lo_ref, count_ref, place_ref, *rest, seg, segs, cols, rows,
                gated, added):
    rest = list(rest)
    gate_ref = rest.pop(0) if gated else None
    add_ref = rest.pop(0) if added else None
    rows_hbm, out_ref, acc, wt, buf, sem, cursor, steps = rest
    i, tiles = pl.program_id(0), pl.num_programs(0)
    count = count_ref[0]
    tt, d = out_ref.shape
    h = place_ref.shape[0]
    last = -(-rows // seg) * seg - seg      # where the array's last segment starts

    def fetch(slot, s, base):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(base, HBM_ROWS), seg)],
            buf.at[slot, pl.ds(pl.multiple_of(s * seg, seg), seg)],
            sem.at[slot])

    def fill(slot):
        """The next step into ``slot``: up to ``segs`` segments of the first
        tile, from the cursor on, that has any, each fetched from the multiple
        of 8 at or before where it begins. A run of expert e for tile i is
        ``lo[i, e] .. lo[i + 1, e]``; a step holds one tile's segments."""
        def more(state):
            tile, e, _, n = state
            return (n < segs) & (tile < tiles) & ((e < h) | (n == 0))

        def one(state):
            tile, e, row, n = state
            done = e == h                   # nothing found in this tile
            at = tile * h + jnp.minimum(e, h - 1)
            a, b = lo_ref[at], lo_ref[at + h]
            begin = jnp.where(row >= 0, row, a // HBM_ROWS * HBM_ROWS)
            found = jnp.logical_not(done) & (begin < b)

            @pl.when(found)
            def _():
                base = jnp.minimum(begin, last)
                steps[slot, 2 + n] = base + (begin - base) // HBM_ROWS
                steps[slot, 2 + segs + n] = e
                fetch(slot, n, base).start()
            return (jnp.where(done, tile + 1, tile),
                    jnp.where(done, 0, jnp.where(found, e, e + 1)),
                    jnp.where(found, begin + seg, -1),
                    n + found.astype(jnp.int32))

        tile, e, row, n = jax.lax.while_loop(
            more, one,
            (cursor[_TILE], cursor[_EXPERT], cursor[_ROW], jnp.int32(0)))
        cursor[_TILE], cursor[_EXPERT], cursor[_ROW] = tile, e, row
        steps[slot, 0] = jnp.where(n > 0, tile, tiles)
        steps[slot, 1] = n

    @pl.when(i == 0)
    def _first():
        # What the buffers hold before a segment's first DMA meets zeros in
        # the matrix: it must be numbers. The first step is one of no
        # segments, whose only work is to list and start the first real one
        # (so the list is made in ONE place, the loop below).
        buf[...] = jnp.zeros_like(buf)
        cursor[_TILE], cursor[_EXPERT], cursor[_ROW] = 0, 0, -1
        cursor[_TURN] = 0
        steps[0, 0], steps[0, 1] = 0, 0

    acc[...] = jnp.zeros_like(acc)
    number = jax.lax.broadcasted_iota(jnp.int32, (seg, 1), 0)

    def mine():
        """Whether the step to run next is this tile's."""
        return steps[cursor[_TURN], 0] == i

    def step(_):
        slot = cursor[_TURN]
        fill(1 - slot)      # the next step's rows, this tile's or a later one's
        n = steps[slot, 1]

        # The matrix, transposed: row c is the buffer's row c, column t the
        # tile's token t. A segment's rows are consecutive numbers and its
        # expert's places lie along the lanes (the tables come transposed,
        # [H, tt]), so its 16 rows of the matrix are ONE comparison of the
        # expert's row of places with the rows' numbers: no reduction.
        def one(s, _):
            real = s < n
            code = steps[slot, 2 + s]
            base = code // HBM_ROWS * HBM_ROWS
            owned = base + code % HBM_ROWS * HBM_ROWS
            at = pl.ds(pl.multiple_of(s * seg, seg), seg)

            @pl.when(real)
            def _wait():
                fetch(slot, s, base).wait()

            @pl.when(real & (base + seg > count))
            def _past():     # rows no group owns may hold anything
                fetched = buf[slot, at, :]
                buf[slot, at, :] = jnp.where(base + number < count, fetched,
                                             jnp.zeros_like(fetched))

            e = jnp.where(real, steps[slot, 2 + segs + s], 0)
            rows_no = base + number                           # [seg, 1]
            hit = (place_ref[pl.ds(e, 1), :] == rows_no) \
                & (rows_no >= owned) & real                   # [seg, tt]
            val = gate_ref[pl.ds(e, 1), :] if gated else 1.0
            wt[at, :] = jnp.where(hit, val, 0.0)
            return 0
        jax.lax.fori_loop(0, segs, one, 0)

        # bfloat16 rows are exact on the MXU and the float32 matrix goes in
        # three bfloat16 pieces (ones and zeros in one); float32 rows take
        # the MXU's own float32 product
        wide = buf.dtype == jnp.float32
        ws = [wt[...]] if wide else _pieces(wt[...], 3 if gated else 1)

        def block(j, _):
            at = pl.ds(pl.multiple_of(j * cols, cols), cols)
            fetched = buf[slot, :, at]
            part = acc[:, at]
            for wp in ws:
                part += jax.lax.dot_general(
                    wp, fetched, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST if wide else None)
            acc[:, at] = part
            return 0
        jax.lax.fori_loop(0, d // cols, block, 0)
        cursor[_TURN] = 1 - slot
        return mine()

    # (the condition rides in the loop's value: the interpreter's ``while``
    # reads a reference in its condition as it was before the loop)
    jax.lax.while_loop(lambda go: go, step, mine())
    # the float32 sum is rounded once, after what is added to it
    total = acc[...] + add_ref[...] if added else acc[...]
    out_ref[...] = total.astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("sched", "dtype", "interpret"))
def _sum_call(rows, plan: RowsPlan, gate, add, *, sched: RowsSchedule, dtype,
              interpret: bool):
    r, d = rows.shape
    h, t = plan.place.shape
    tt, seg, segs = sched.tokens_tile, sched.seg, sched.segs
    if r % seg:     # a part whose rows are no whole segments (small shapes)
        rows = jnp.pad(rows, ((0, seg - r % seg), (0, 0)))
    gated, added = gate is not None, add is not None
    of_experts = pl.BlockSpec((h, tt), lambda i, *_: (0, i))
    of_tokens = pl.BlockSpec((tt, d), lambda i, *_: (i, 0))
    return pl.pallas_call(
        partial(_sum_kernel, seg=seg, segs=segs, cols=sched.cols, rows=r,
                gated=gated, added=added),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(sched.tiles,),
            in_specs=[of_experts] * (1 + gated) + [of_tokens] * added
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=of_tokens,
            scratch_shapes=[pltpu.VMEM((tt, d), jnp.float32),
                            pltpu.VMEM((seg * segs, tt), jnp.float32),
                            pltpu.VMEM((2, seg * segs, d), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((4,), jnp.int32),
                            pltpu.SMEM((2, 2 + 2 * segs), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="moe_rows_sum",
        interpret=interpret,
    )(plan.lo, plan.count, plan.place,
      *((gate,) if gated else ()), *((add,) if added else ()), rows)


def rows_sum(rows: jax.Array, plan: RowsPlan, sched: RowsSchedule,
             gate: Optional[jax.Array] = None,
             add: Optional[jax.Array] = None, dtype=jnp.float32) -> jax.Array:
    """``y[t] = add[t] + sum_e gate[e, t] * rows[plan.place[e, t]]`` over the
    places that are not -1 (``gate`` float32 [H, T], None: ones; ``add``
    float32 [T, d] or None), summed in float32 and rounded once to ``dtype``,
    [T, d]. No derivative of its own: the callers' ``custom_vjp``s use it both
    ways."""
    return _sum_call(rows, plan, gate, add, sched=sched,
                     dtype=jnp.dtype(dtype), interpret=interpret_default())


# The two movements of a held share's rows, each other's transposes. ``idx``
# [R] int32 are the part's sorted assignments (an assignment being token * k +
# choice), of which the first ``plan.count[0]`` belong to held groups; the rows
# at and past that count are the layer's slack.

@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def take(tokens, idx, plan: RowsPlan, k: int, sched: RowsSchedule):
    """``xs[r] = tokens[idx[r] // k]``, [R, d] in the tokens' dtype. Forward
    XLA's gather over every sized row (Mosaic fetches no row by its index, see
    the module's text; what the rows past the count hold is their assignments'
    tokens: numbers no grouped matmul reads). Backward ``moe_rows_sum`` with
    unit gates over the rows before the count, summed in float32: what the
    cotangent holds past the count reaches nothing."""
    return tokens[idx // k]


def _take_fwd(tokens, idx, plan, k, sched):
    return take(tokens, idx, plan, k, sched), plan


def _take_bwd(k, sched, plan, ct):
    return rows_sum(ct, plan, sched, dtype=ct.dtype), None, None


take.defvjp(_take_fwd, _take_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def combine(out, gates, add, local, idx, plan: RowsPlan, sched: RowsSchedule,
            dtype):
    """``y[t] = add[t] + sum_j gates[t, j] * out[place of (t, j)]`` over the
    assignments among the part's first ``plan.count[0]`` rows, summed in float32
    by ``moe_rows_sum`` and rounded once to ``dtype``: [T, d]. ``out`` [R, d]
    the experts' output in sorted order, ``gates`` [T, k], ``add`` float32
    [T, d] (another part's sum) or None, ``local`` [T, k] the choices as held
    experts' numbers (``held_tables``). Backward the cotangent's rows taken
    (XLA's gather, as ``take``) and scaled by their gates, and each gate's
    gradient the dot of its row with the cotangent's, zero past the count:
    what ``out`` holds there (anything: a NaN) reaches neither."""
    _, gate = held_tables(local, gates, plan.place.shape[0])
    return rows_sum(out, plan, sched, gate.T, add, dtype)


def _combine_fwd(out, gates, add, local, idx, plan, sched, dtype):
    return (combine(out, gates, add, local, idx, plan, sched, dtype),
            (out, gates, idx, plan.count[0], () if add is None else (0,)))


def _combine_bwd(sched, dtype, res, ct):
    out, gates, idx, count, added = res    # added: () where add was None
    k = gates.shape[1]
    rows = ct[idx // k].astype(jnp.float32)               # [R, d]
    d_out = rows * gates.reshape(-1)[idx][:, None]
    owned = jnp.arange(idx.shape[0]) < count
    d_gate = jnp.where(
        owned, jnp.sum(rows * out.astype(jnp.float32), axis=-1), 0.0)
    d_gates = jnp.zeros((gates.size,), jnp.float32).at[idx].add(
        d_gate, unique_indices=True)
    return (d_out.astype(out.dtype),
            d_gates.reshape(gates.shape).astype(gates.dtype),
            ct.astype(jnp.float32) if added else None, None, None, None)


combine.defvjp(_combine_fwd, _combine_bwd)
