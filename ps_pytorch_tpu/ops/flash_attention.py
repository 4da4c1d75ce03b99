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
- What a grid step holds is a schedule computed from the shape alone:
  ``flash_schedule(bh, s, d, itemsize, causal, window=, bh_kv=, dv=)``. On a v5e a 256x256x64
  tile alone in a grid step costs 1.4 us however the grid is cut (the MXU's
  and the reductions' latencies with nothing to overlap them), so a step
  holds many tiles and a tile is large. The compute tile is the whole row
  of scores for S <= 1024 (a plain softmax: no running statistics at all),
  else 512x512; tile, mask, group and window are the two passes' to share,
  and what a step keeps in VMEM is each pass's own, sized by that pass's
  blocks against the stated budget (PR 34; until then the larger pass sized
  both, and at S = 16384 the forward inherited a kv axis it had no need of):
  * a forward step holds a q tile of ``g`` query heads (whole groups;
    ``block_h`` of them batched through each product so that independent
    chains interleave) and ``block_kv_major`` K/V rows of their K/V heads:
    all of S wherever that fits, which is every shape a cell runs;
  * a backward step holds ``bwd_block_kv_major`` K/V rows of ``bwd_g`` K/V
    heads, with their dK/dV outputs and f32 sums, and ``block_q_major``
    q/dO/dQ rows of as many query heads: the heads themselves, or under
    grouped-query heads ``bwd_g`` of the ``bwd_g * group`` they serve, one
    set after another along the grid. The rows are cut, q rows first, until
    one head fits.
  Heads a step are counted in K/V heads, the one count the passes share:
  the most that fit both.
- Forward: grid (bh/g, S/block_q, S/block_kv_major), kv innermost with
  ``arbitrary`` semantics. Inside a step a ``fori_loop`` walks the compute
  tiles of the K/V block over the span the q tile sees (``_kv_span``: up to
  the causal limit, from the window's edge): tiles wholly inside first,
  without a mask, then the ones the diagonal or the window's edge crosses,
  masked with a finite -1e30 (fully masked rows stay NaN-free). A dead tile
  costs nothing. With the K/V head whole there is no kv axis and m/l/acc are
  loop carries; where a head does not fit, the K/V index map clamps a dead
  block to the last live one, so it is not fetched either, and m/l/acc
  cross the axis's steps in VMEM scratch.
- The saved residual is one LSE per query, not the score matrix, stored
  lane-dense as [bh, S/block_q, 1, block_q]: a [.., S, 1] array pads every
  float to a 128-lane row in VMEM and HBM.
- Backward = ONE kernel, grid (bh_kv/bwd_g, S/bwd_block_kv_major,
  group * S/block_q_major). The innermost (``arbitrary``) axis walks the
  query heads of the step's K/V heads, ``bwd_g`` consecutive heads at a time
  (a head reads the K/V head ``index // group``, as in the forward), and for
  each set its q blocks: the K/V block's index does not change along it, so
  K and V are fetched once for the whole group
  and never repeated in HBM, and dK/dV sum over all its steps in f32
  scratch (no scratch where the axis has one step). Per (kv tile, q tile)
  pair the score tile, p = exp(s - lse) and dO.V^T are formed once and feed
  dV, dK and dQ: five products. The tile is held transposed ([block_kv,
  block_q]) so that LSE and delta broadcast along sublanes and only the dQ
  product contracts over its leading dimension. A step's kv-tile loop runs
  over the span its q rows see (``_kv_span`` again; where the rows are all
  of S that is the whole block, and a plain loop) and, for each kv tile,
  the q-tile loops over the span that sees it (``_q_span``), so every trip
  is a live pair: ``FlashSchedule.bwd_visits`` counts them by the same two
  functions. dK/dV sum over the q tiles in loop carries; dQ sums over the
  kv tiles of a step in its f32 output block (in scratch for narrower
  outputs) and leaves once per (kv block, q block): with one kv block that
  is dQ itself, otherwise ``dq_partials`` f32 partials that XLA sums.
  ``delta = rowsum(dO * O)`` is a cheap XLA elementwise pass outside.
- Grouped-query heads and a sliding window are parameters of the same two
  kernels. With a window ``W`` (query i sees keys ``i-W < j <= i``) the
  spans start at the band's first tile and the loops mask its lower edge as
  they mask the diagonal, grid steps wholly outside the band are dead as
  causally dead ones are, and the index maps clamp to the band from both
  sides; such calls are named ``flash_win_*`` so that a trace tells the two
  kinds of layer apart.
- The value has a width of its own, ``dv = v.shape[-1]``, read from the
  operand: the forward's accumulator, output block and ``P.V`` product, the
  backward's dO, ``delta``, ``dO.V^T`` (which contracts over it) and dV are
  ``dv`` wide, the scores, dQ and dK ``d`` wide, the default scale ``d ** -0.5``. One
  algorithm with one more size, no second path: where ``dv == d`` (every
  caller but a differential layer, whose one value is a pair's two value heads
  side by side, ``models/transformer.py:attention_sublayer``) the schedule
  record, the traced kernels and the Mosaic modules are what they were before
  the width was told apart (PR 50), and the record prints no ``dv=``. A
  step's VMEM is sized with both widths; at ``d = 64, dv = 128`` both pad to
  128 lanes, so a step holds what a call of 64-wide values held.
- Matmuls run with ``preferred_element_type=f32``; q, k, v, dO are cast to
  f32 before the products and the probability tile to the value dtype for
  the PV product (under Mosaic an f32 operand takes one bf16 MXU pass).
- Compiled on TPU, Pallas interpreter elsewhere — the CPU test mesh runs
  identical semantics (same pattern as ``ops/quantize.py``).
"""

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default

NEG_INF = -1e30

# What the blocks of one grid step may take of VMEM (v5e: 128 MiB physical,
# 16 MiB scoped by default), and the limit handed to Mosaic: the budget plus
# room for the compiler's own temporaries.
VMEM_BUDGET_BYTES = 40 * 2 ** 20
VMEM_LIMIT_BYTES = 64 * 2 ** 20
# Live score elements a step should hold before more heads stop paying, and
# the steps a call keeps so that the pipeline's first fetch and last
# write-back (which nothing hides) stay a small share.
_STEP_WORK = 4 * 2 ** 20
_MIN_STEPS = 8
# The compute tile: a whole row of scores up to _ROW_TILE keys, else _TILE x
# _TILE; heads are batched through a tile while their f32 scores stay under
# _TILE_BYTES (v5e A/B of PR 26, PERF.md section 6).
_ROW_TILE, _TILE = 1024, 512
_TILE_BYTES = 2 ** 19


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


def _next_smaller(s: int, rows: int, tile: int) -> int:
    """The next block under ``rows`` that divides ``s`` in whole tiles."""
    n = s // rows + 1
    while s % n or (s // n) % tile:
        n += 1
    return s // n


class FlashSchedule(NamedTuple):
    """What one grid step of each kernel holds; static per shape. The compute
    tile, the mask, the group and the window are shared; the heads and rows a
    step keeps are each pass's own."""
    block_q: int            # compute tile, q rows (forward: the q block)
    block_kv: int           # compute tile, k/v rows
    # forward: grid (bh/g, S/block_q, S/block_kv_major)
    g: int                  # query heads a step (whole groups; divides bh)
    block_h: int            # heads a compute tile, either pass (1 under a group)
    block_kv_major: int     # k/v rows a step keeps in VMEM
    grid: Tuple[int, int, int]
    steps: int                        # grid steps a call
    live: int                         # ... of which not dead under the mask
    vmem_bytes: int                   # the forward step's blocks
    # backward: grid (bh_kv/bwd_g, S/bwd_block_kv_major,
    #                 group * S/block_q_major)
    # of ``bwd_g`` K/V heads and as many of their query heads at a time
    bwd_block_kv_major: int  # k/v rows a step keeps in VMEM
    block_q_major: int      # q/dO/dQ rows a step keeps in VMEM
    bwd_grid: Tuple[int, int, int]
    bwd_steps: int
    bwd_live: int
    bwd_vmem_bytes: int               # the backward step's blocks
    group: int = 1          # query heads a K/V head
    window: int = 0         # keys a query sees, itself included; 0: all before it
    tiles: int = 0          # (q tile, kv tile) pairs a call's mask covers, either pass
    live_tiles: int = 0     # ... of which hold a pair the mask admits
    bwd_visits: int = 0     # pair slots the backward's tile loops visit
    dv: int = 0             # the value's (and the output's) width where it is not the keys'

    @property
    def dead(self) -> int:
        return self.steps - self.live

    @property
    def bwd_g(self) -> int:
        """K/V heads a step of either pass; the backward's heads a step."""
        return self.g // self.group

    @property
    def dq_partials(self) -> int:
        """dQ leaves the backward as this many arrays (float32 where > 1)."""
        return self.bwd_grid[1]

    def describe(self) -> str:
        kind = (f" kv_heads={self.bwd_g}" if self.group > 1 else "") \
            + (f" window={self.window}" if self.window else "") \
            + (f" dv={self.dv}" if self.dv else "")
        grid = lambda g: "x".join(map(str, g))
        return (f"g={self.g}/{self.block_h} bq={self.block_q} "
                f"kv={self.block_kv}/{self.block_kv_major} "
                f"grid={grid(self.grid)} live={self.live}/{self.steps} "
                f"bwd g={self.bwd_g}/{self.block_h} "
                f"q={self.block_q}/{self.block_q_major} "
                f"kv={self.block_kv}/{self.bwd_block_kv_major} "
                f"grid={grid(self.bwd_grid)} "
                f"live={self.bwd_live}/{self.bwd_steps} "
                f"dq_partials={self.dq_partials}"
                f"{kind} tiles={self.live_tiles}/{self.tiles} "
                f"bwd_visits={self.live_tiles}/{self.bwd_visits}")


def _tile_bytes(bq, bkv):
    return 4 * max(bq * bkv * 4, _TILE_BYTES)


def _lanes(d):
    """The last dimension of a block pads to 128 lanes."""
    return -(-d // 128) * 128


def _fwd_bytes(g, gk, bq, bkv, kvm, s, d, itemsize, dv=0):
    """VMEM of one forward step's blocks (``g`` query heads on ``gk`` K/V
    heads; q and k ``d`` wide, v and o ``dv``, 0 for ``d``): inputs and
    outputs double-buffered, the f32 accumulators, the score tiles. The last
    dimension of a block pads to 128 lanes, an LSE row to 8 sublanes, a
    [rows, 1] statistic to 128 lanes."""
    dp, dvp = _lanes(d), _lanes(dv or d)
    return (2 * (g * bq + gk * kvm) * (dp + dvp) * itemsize    # q o k v
            + 2 * g * 8 * bq * 4                               # lse
            + (s > kvm) * g * bq * (dvp + 2 * 128) * 4         # acc, m, l
            + _tile_bytes(bq, bkv))


def _bwd_bytes(g, bq, bkv, kvm, qm, s, d, itemsize, group=1, dv=0):
    """VMEM of one backward step's blocks: ``g`` K/V heads and one query head
    of each (q, k and their gradients ``d`` wide, v, dO and dV ``dv``);
    padded as the forward's."""
    dp, dvp = _lanes(d), _lanes(dv or d)
    dq_size = 4 if s > kvm else itemsize
    return (2 * g * (qm + kvm) * (dp + dvp) * itemsize         # q dO k v
            + 2 * g * (qm * dq_size * dp
                       + kvm * itemsize * (dp + dvp))          # dq dk dv
            + 2 * 2 * g * 8 * qm * 4                           # lse, delta
            + (dq_size < 4 and kvm > bkv) * g * qm * dp * 4    # dq's sum
            + (group * (s // qm) > 1) * g * kvm * (dp + dvp) * 4  # dk, dv sums
            + _tile_bytes(bq, bkv))


def _live_blocks(s, q_rows, kv_rows, causal, window=0):
    """Of the (q block, kv block) pairs of an S x S grid of blocks: how many
    there are, and how many are not dead: the kv block's first key <= the q
    block's last query and, under a window, its last key inside the window
    of the q block's first query."""
    n_q, n_kv = s // q_rows, s // kv_rows
    live = sum(_live_block(qi * q_rows, q_rows, kj * kv_rows, kv_rows,
                           causal, window)
               for qi in range(n_q) for kj in range(n_kv))
    return n_q * n_kv, live


def _live_block(q0, q_rows, k0, kv_rows, causal, window):
    """Does some query of q0 .. q0+q_rows-1 see some key of k0 .. k0+kv_rows-1?
    On traced or plain integers: the kernels' own test of a dead step."""
    live = (q0 + q_rows - 1 >= k0) if causal else True
    if window:
        live = live & (q0 - (k0 + kv_rows - 1) < window)
    return live


def _div(x, n: int):
    """``max(x, 0) // n`` of a traced int32. Not jnp's ``//``: its floor
    correction goes through ``sign``, whose Mosaic lowering traces a helper
    function each time (24 ms a call on the chip's host: with eight to twelve
    a kernel, 10 s of a GPT-2 step's first dispatch)."""
    return jax.lax.div(jnp.maximum(x, 0), jnp.int32(n))


# The tile loops' bounds, on traced int32 in the kernels and (``_INTS``) on
# plain integers where the schedule counts what those loops visit.
_TRACED = (_div, jnp.minimum)
_INTS = (lambda x, n: max(x, 0) // n, min)


def _kv_span(q0, rows, k0, bkv, n, causal, window, ops=_TRACED):
    """Of the ``n`` tiles of ``bkv`` keys from key ``k0`` on, the span
    [first, end) that queries q0 .. q0+rows-1 can see: a tile is live when
    its first key <= the last query and, under a window, its last key is one
    the first query still sees. Both kernels' kv-tile loops run over it."""
    div, least = ops
    end = least(div(q0 + rows - k0 + bkv - 1, bkv), n) if causal else n
    first = least(div(q0 - window + 1 - k0, bkv), end) if window else 0
    return first, end


def _q_span(ks, bkv, q0, bq, n, causal, window, ops=_TRACED):
    """Of the ``n`` tiles of ``bq`` queries from query ``q0`` on, the span
    [first, end) that can see keys ks .. ks+bkv-1: a tile is live when its
    last query >= the first key and, under a window, its first query still
    sees the last key. The backward's q-tile loops run over it."""
    div, least = ops
    first = least(div(ks - q0, bq), n) if causal else 0
    end = n
    if window:
        end = least(div(ks + bkv - 1 + window - q0 + bq - 1, bq), n)
        first = least(first, end)
    return first, end


def _bwd_visits(s, bq, bkv, kvm, qm, causal, window):
    """Pair slots the backward's loops visit for one query head: every trip
    of a q-tile loop, and every kv tile whose K and V a step loads and casts
    to find no q tile for. By the loops' own bounds (``_kv_span``,
    ``_q_span``) on the steps that are not dead."""
    visits = 0
    for k0 in range(0, s, kvm):
        for q0 in range(0, s, qm):
            if not _live_block(q0, qm, k0, kvm, causal, window):
                continue
            lo, hi = _kv_span(q0, qm, k0, bkv, kvm // bkv, causal, window,
                              _INTS)
            for j in range(lo, hi):
                t0, t1 = _q_span(k0 + j * bkv, bkv, q0, bq, qm // bq, causal,
                                 window, _INTS)
                visits += max(t1 - t0, 1)
    return visits


def flash_schedule(bh: int, s: int, d: int, itemsize: int, causal: bool,
                   block_q: Optional[int] = None,
                   block_kv: Optional[int] = None,
                   block_kv_major: Optional[int] = None, *,
                   window: Optional[int] = None,
                   bh_kv: Optional[int] = None,
                   dv: Optional[int] = None) -> FlashSchedule:
    """The schedule of both kernels for [bh, s, d] queries of ``itemsize``
    bytes over [bh_kv, s, d] keys and [bh_kv, s, dv] values (``bh_kv``
    ``None``: as many heads as the queries; ``dv`` ``None``: as wide as the
    keys), under a ``window`` of keys (``None`` or >= s: every key before
    the query): pure, from the shape alone. ``block_q`` / ``block_kv`` /
    ``block_kv_major`` are upper bounds (the tests' override). Raises
    ValueError when S has no power-of-two block divisor >= 8."""
    group, window = _group(bh, bh_kv), _window(window, s, causal)
    dv = 0 if dv in (None, d) else int(dv)
    # The compute tile: a whole row of scores where that is at most
    # _ROW_TILE wide (a plain softmax, no running statistics), else
    # _TILE x _TILE (measured on a v5e: PERF.md, PR 26).
    tile = _ROW_TILE if s <= _ROW_TILE else _TILE
    bq = _pick_block(s, block_q or tile)
    bkv = _pick_block(s, min(block_kv or tile, block_kv_major or s))
    if not bq or not bkv:
        raise ValueError(
            f"flash_attention needs a power-of-two block >= 8 dividing the "
            f"sequence length; S={s} has none (use attention 'full')")

    def fwd_fits(gk, kvm):
        return _fwd_bytes(gk * group, gk, bq, bkv, kvm, s, d, itemsize, dv) \
            <= VMEM_BUDGET_BYTES

    def bwd_fits(gk, kvm, qm):
        return _bwd_bytes(gk, bq, bkv, kvm, qm, s, d, itemsize, group, dv) \
            <= VMEM_BUDGET_BYTES

    # Rows a step keeps, each pass by its own blocks: all of S, cut down
    # until one K/V head fits the budget beside what the pass holds of its
    # query heads (the forward a q tile of the whole group, the backward the
    # q/dO/dQ rows of one of them); never under a compute tile.
    top = _pick_block(s, block_kv_major) if block_kv_major else s
    fwd_kvm = top
    while not fwd_fits(1, fwd_kvm) and fwd_kvm > bkv:
        fwd_kvm = _next_smaller(s, fwd_kvm, bkv)
    kvm, qm = top, s
    while not bwd_fits(1, kvm, qm) and (kvm > bkv or qm > bq):
        if qm >= kvm and qm > bq or kvm == bkv:
            qm = _next_smaller(s, qm, bq)
        else:
            kvm = _next_smaller(s, kvm, bkv)
    # K/V heads a step, the one count the passes share (the forward holds
    # their whole groups): the largest divisor of bh_kv that fits both,
    # leaves a call _MIN_STEPS steps where the heads allow, and is not past
    # the point where a step already holds _STEP_WORK live score elements.
    n_kv = bh // group
    per_head = (s * s // 2 if causal else s * s) // ((s // kvm) * (s // qm))
    gk = 1
    for cand in range(2, n_kv + 1):
        if n_kv % cand:
            continue
        if not (fwd_fits(cand, fwd_kvm) and bwd_fits(cand, kvm, qm)) \
                or n_kv // cand < min(n_kv, _MIN_STEPS):
            break
        gk = cand
        if cand * per_head >= _STEP_WORK:
            break
    return _schedule(bh, s, d, itemsize, causal, gk, bq, bkv, fwd_kvm, kvm,
                     qm, group=group, window=window, dv=dv)


def _group(bh, bh_kv):
    """Query heads a K/V head."""
    if bh_kv is None or bh_kv == bh:
        return 1
    if bh_kv < 1 or bh % bh_kv:
        raise ValueError(f"{bh} query heads do not divide over {bh_kv} "
                         f"key/value heads")
    return bh // bh_kv


def _window(window, s, causal):
    """0 where the window never closes (none given, or >= S)."""
    if window is None or window >= s:
        return 0
    if window < 1 or not causal:
        raise ValueError(f"window={window}: a window is >= 1 key and needs "
                         f"causal=True")
    return int(window)


def _schedule(bh, s, d, itemsize, causal, gk, bq, bkv, fwd_kvm, kvm, qm, *,
              group=1, window=0, dv=0):
    """The grids and counts that follow from ``gk`` K/V heads a step, the
    compute tile, the forward's K/V rows and the backward's K/V and q rows
    (``dv``: the value's width where it is not ``d``, else 0)."""
    g = gk * group
    # heads a compute tile: independent chains for the scheduler to
    # interleave, while the f32 score tiles stay around _TILE_BYTES; one
    # where heads share K/V (a tile then reads one K/V head)
    hb = max(c for c in range(1, g + 1)
             if g % c == 0 and (c == 1 or group == 1
                                and c * bq * bkv * 4 <= _TILE_BYTES))
    steps, live = _live_blocks(s, bq, fwd_kvm, causal, window)
    bwd_steps, bwd_live = _live_blocks(s, qm, kvm, causal, window)
    tiles, live_tiles = _live_blocks(s, bq, bkv, causal, window)
    n = bh // g
    return FlashSchedule(
        bq, bkv,
        g, hb, fwd_kvm, (n, s // bq, s // fwd_kvm), n * steps, n * live,
        _fwd_bytes(g, gk, bq, bkv, fwd_kvm, s, d, itemsize, dv),
        kvm, qm,
        (n, s // kvm, group * (s // qm)), n * group * bwd_steps,
        n * group * bwd_live,
        _bwd_bytes(gk, bq, bkv, kvm, qm, s, d, itemsize, group, dv),
        group, window, bh * tiles, bh * live_tiles,
        bh * _bwd_visits(s, bq, bkv, kvm, qm, causal, window), dv)


def _bdot(a, b, ca, cb):
    """Matmul batched over the leading (head) dimension with f32
    accumulation, contracting ``a``'s dimension ``ca`` with ``b``'s ``cb``:
    transposes are folded into the dimension numbers (no materialized
    transpose ops in the kernel)."""
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _mask(s, q0, k0, q_axis, window):
    """Mask a [heads, ., .] score tile whose queries start at ``q0`` and keys
    at ``k0``; queries run along ``q_axis`` — shared by both kernels so
    forward and backward can never disagree on masking. A query sees the keys
    at or before it, the last ``window`` of them where there is a window."""
    shape = (1,) + s.shape[1:]
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 3 - q_axis)
    ok = q_pos >= k_pos
    if window:
        ok = jnp.logical_and(ok, q_pos - k_pos < window)
    return jnp.where(ok, s, NEG_INF)


def _rows(start, size):
    return pl.ds(pl.multiple_of(start, size), size)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                causal, scale, sched):
    g, hb, bq, bkv, kvm = (sched.g, sched.block_h, sched.block_q,
                           sched.block_kv, sched.block_kv_major)
    group, window = sched.group, sched.window
    dv = v_ref.shape[-1]
    jm = pl.program_id(2)
    q0 = pl.program_id(1) * bq          # first query of this step
    k0 = jm * kvm                       # first key of this step's K/V block
    n_tiles = kvm // bkv
    single = sched.grid[2] == 1         # no kv axis: nothing carried over

    # the live tiles of this step's K/V block; of them, those whose last key
    # <= the first query need no mask at the diagonal, and those whose first
    # key the last query still sees none at the window's edge
    t_lo, n_live = _kv_span(q0, bq, k0, bkv, n_tiles, causal, window)
    n_full = jnp.minimum(_div(q0 + 1 - k0, bkv), n_live) if causal \
        else n_tiles
    if window:
        t_in = jnp.clip(_div(q0 + bq - window - k0 + bkv - 1, bkv), t_lo,
                        n_live)
        n_full = jnp.clip(n_full, t_in, n_live)

    def _kv(hs):
        """The K/V head(s) of query heads ``hs``: their own, or the one a
        group shares (a tile then holds one query head)."""
        return hs if group == 1 else pl.ds(_div(hs.start, group), 1)

    def _scores(hs, q, t, masked):
        s = _bdot(q, k_ref[_kv(hs), _rows(t * bkv, bkv), :]
                  .astype(jnp.float32), 2, 2)           # [hb, bq, bkv]
        return _mask(s, q0, k0 + t * bkv, 1, window) if masked else s

    def _pv(hs, p, t):
        v = v_ref[_kv(hs), _rows(t * bkv, bkv), :]
        return _bdot(p.astype(v.dtype), v, 2, 1)        # [hb, bq, dv]

    def _tile(t, carry, *, hs, q, masked):
        m_prev, l_prev, acc = carry
        s = _scores(hs, q, t, masked)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        return m_new, l_new, acc * alpha + _pv(hs, p, t)

    def _emit(hs, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[hs] = (acc / l).astype(o_ref.dtype)
        lse = m + jnp.log(l)                            # [hb, bq, 1]
        # columns -> lane-dense rows through one aligned transpose a head
        for h in range(hb):
            lse_ref[hs.start + h, 0] = jnp.broadcast_to(
                lse[h], (bq, 128)).T[:1]

    def _heads(hg, _):
        hs = pl.ds(hg * hb, hb)
        q = q_ref[hs].astype(jnp.float32) * scale
        if single and n_tiles == 1:
            # the whole row in one tile: a plain softmax, nothing online
            s = _scores(hs, q, 0, causal)
            m = jnp.max(s, axis=2, keepdims=True)
            p = jnp.exp(s - m)
            _emit(hs, m, jnp.sum(p, axis=2, keepdims=True), _pv(hs, p, 0))
            return
        if single:
            carry = (jnp.full((hb, bq, 1), NEG_INF, jnp.float32),
                     jnp.zeros((hb, bq, 1), jnp.float32),
                     jnp.zeros((hb, bq, dv), jnp.float32))
        else:
            carry = tuple(ref[hs] for ref in scratch)
        if window:
            carry = jax.lax.fori_loop(
                t_lo, t_in, partial(_tile, hs=hs, q=q, masked=True), carry)
        carry = jax.lax.fori_loop(
            t_in if window else 0, n_full,
            partial(_tile, hs=hs, q=q, masked=False), carry)
        if causal:
            carry = jax.lax.fori_loop(
                n_full, n_live, partial(_tile, hs=hs, q=q, masked=True),
                carry)
        if single:
            _emit(hs, *carry)
        else:
            for ref, val in zip(scratch, carry):
                ref[hs] = val

    if single:
        jax.lax.fori_loop(0, g // hb, _heads, None)
        return

    m_sc, l_sc, acc_sc = scratch

    @pl.when(jm == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(n_live > t_lo)
    def _run():
        jax.lax.fori_loop(0, g // hb, _heads, None)

    @pl.when(jm == pl.num_programs(2) - 1)
    def _finalize():
        def _fin(hg, _):
            hs = pl.ds(hg * hb, hb)
            _emit(hs, m_sc[hs], l_sc[hs], acc_sc[hs])
        jax.lax.fori_loop(0, g // hb, _fin, None)


def _fwd_call(q3, k3, v3, causal, scale, sched, interpret):
    bh, s, d = q3.shape
    dv = v3.shape[-1]
    g, bq, kvm = sched.g, sched.block_q, sched.block_kv_major
    gk, window = g // sched.group, sched.window

    def kv_map(b, i, jm):
        # a dead block is not fetched: keep the nearest live one
        if causal:
            jm = jnp.minimum(jm, _div(i * bq + bq - 1, kvm))
        if window:
            jm = jnp.maximum(jm, _div(i * bq - window + 1, kvm))
        return (b, jm, 0)

    o, lse = pl.pallas_call(
        partial(_fwd_kernel, causal=causal, scale=scale, sched=sched),
        grid=sched.grid,
        in_specs=[
            pl.BlockSpec((g, bq, d), lambda b, i, jm: (b, i, 0)),
            pl.BlockSpec((gk, kvm, d), kv_map),
            pl.BlockSpec((gk, kvm, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((g, bq, dv), lambda b, i, jm: (b, i, 0)),
            pl.BlockSpec((g, 1, 1, bq), lambda b, i, jm: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, s // bq, 1, bq), jnp.float32),
        ],
        # m, l, acc between the steps of a kv axis
        scratch_shapes=[] if sched.grid[2] == 1 else [
            pltpu.VMEM((g, bq, 1), jnp.float32),
            pltpu.VMEM((g, bq, 1), jnp.float32),
            pltpu.VMEM((g, bq, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        name="flash_win_fwd" if window else "flash_fwd",
        interpret=interpret,
    )(q3, k3, v3)
    return o, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_scratch(sched, dq_dtype):
    """Which f32 accumulators the backward step needs beyond its output
    blocks: dQ's when the output block cannot hold the running sum itself
    (not f32, and more than one kv tile adds to it), dK/dV's when they
    build up over several steps: q blocks, a group's query heads."""
    return (dq_dtype != jnp.float32
            and sched.bwd_block_kv_major > sched.block_kv,
            sched.bwd_grid[2] > 1)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *scratch, causal, scale, sched):
    g, hb, bq, bkv, kvm, qm = (sched.bwd_g, sched.block_h, sched.block_q,
                               sched.block_kv, sched.bwd_block_kv_major,
                               sched.block_q_major)
    group, window = sched.group, sched.window
    # the innermost axis walks the query heads of the step's K/V heads, g at
    # a time, and for each such set its q blocks: every step adds to the
    # same K/V block's dK and dV
    i = pl.program_id(2)
    im, first_head = _q_block(i, sched), _q_heads(i, sched) * g
    k0 = pl.program_id(1) * kvm         # first key of this step's K/V block
    q0 = im * qm                        # first query of this step's q block
    n_q, n_kv = qm // bq, kvm // bkv
    d, dv = q_ref.shape[-1], v_ref.shape[-1]
    dq_scratch, dkv_scratch = _bwd_scratch(sched, dq_ref.dtype)
    scratch = list(scratch)
    # dQ sums over the kv tiles of a step: in the output block where that is
    # f32, else in scratch; one pair a step writes its product directly
    one_pair = n_q == 1 and n_kv == 1
    dq_sum = scratch.pop(0) if dq_scratch else dq_ref
    # dK/dV sum over the q tiles of a step in loop carries, and over the
    # steps of the innermost axis, where there are several, in scratch
    dk_acc, dv_acc = scratch if dkv_scratch else (None, None)
    live = _live_block(q0, qm, k0, kvm, causal, window)
    # the kv tiles this step's q rows can see: all of them where the rows are
    # all of S (a plain loop then, and the one that writes dK and dV whole)
    j_lo, j_hi = (0, n_kv) if sched.bwd_grid[2] == group else \
        _kv_span(q0, qm, k0, bkv, n_kv, causal, window)

    if dk_acc is not None:
        @pl.when(i == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    if not one_pair:
        dq_sum[:] = jnp.zeros_like(dq_sum)

    def _pair(t, carry, *, hs, k, v, ks, masked):
        dk, dv_ = carry
        rows = _rows(t * bq, bq)
        q = q_ref[hs, rows, :].astype(jnp.float32) * scale
        do = do_ref[hs, rows, :].astype(jnp.float32)
        s = _bdot(k, q, 2, 2)                               # [hb, bkv, bq]
        if masked:
            s = _mask(s, q0 + t * bq, ks, 2, window)
        p = jnp.exp(s - lse_ref[hs, t])
        dv_ = dv_ + _bdot(p, do, 2, 1)                      # [hb, bkv, dv]
        ds = p * (_bdot(v, do, 2, 2) - delta_ref[hs, t])
        dk = dk + _bdot(ds, q, 2, 1)
        dq = _bdot(ds, k * scale, 1, 1)                     # [hb, bq, d]
        if one_pair:
            dq_ref[hs, rows, :] = dq.astype(dq_ref.dtype)
        else:
            dq_sum[hs, rows, :] += dq
        return dk, dv_

    def _kv_tile(j, _, *, hs, kv):
        """One kv tile of K/V head(s) ``kv`` against the q tiles of query
        heads ``hs`` that see it."""
        rows = _rows(j * bkv, bkv)
        ks = k0 + j * bkv               # first key of the tile
        k = k_ref[kv, rows, :].astype(jnp.float32)
        v = v_ref[kv, rows, :].astype(jnp.float32)
        # the live q tiles; of them, those whose first query >= the last key
        # need no mask at the diagonal, and those whose last query sees the
        # first key none at the window's far edge
        t_live, t_hi = _q_span(ks, bkv, q0, bq, n_q, causal, window)
        t_full = jnp.clip(_div(ks + bkv - 1 - q0 + bq - 1, bq), t_live,
                          t_hi) if causal else 0
        if window:
            t_in = jnp.clip(_div(ks + window - q0, bq), t_full, t_hi)
        kw = dict(hs=hs, k=k, v=v, ks=ks)
        # one value twice where the widths are equal: the kernel traced for
        # ``dv == d`` is, equation for equation, the one-width kernel
        zeros = jnp.zeros((hb, bkv, d), jnp.float32)
        carry = (zeros, zeros if dv == d
                 else jnp.zeros((hb, bkv, dv), jnp.float32))
        if causal:
            carry = jax.lax.fori_loop(
                t_live, t_full, partial(_pair, masked=True, **kw), carry)
        carry = jax.lax.fori_loop(
            t_full, t_in if window else n_q,
            partial(_pair, masked=False, **kw), carry)
        if window:
            carry = jax.lax.fori_loop(
                t_in, t_hi, partial(_pair, masked=True, **kw), carry)
        dk, dv_ = carry
        if dk_acc is None:
            dk_ref[kv, rows, :] = dk.astype(dk_ref.dtype)
            dv_ref[kv, rows, :] = dv_.astype(dv_ref.dtype)
        else:
            dk_acc[kv, rows, :] += dk
            dv_acc[kv, rows, :] += dv_

    def _heads(hg, _):
        # a tile's query heads with their own K/V, or one query head with
        # the K/V head its group shares
        hs = pl.ds(hg * hb, hb)
        kv = hs if group == 1 else \
            pl.ds(_div(first_head + hg, group), 1)
        jax.lax.fori_loop(j_lo, j_hi, partial(_kv_tile, hs=hs, kv=kv), None)

    @pl.when(live)
    def _run():
        jax.lax.fori_loop(0, g // hb, _heads, None)

    if one_pair and causal:
        @pl.when(jnp.logical_not(live))
        def _dead():
            dq_ref[:] = jnp.zeros_like(dq_ref)

    if dq_sum is not dq_ref:
        dq_ref[:] = dq_sum[:].astype(dq_ref.dtype)

    if dk_acc is not None:
        @pl.when(i == pl.num_programs(2) - 1)
        def _finalize():
            dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _q_block(i, sched):
    """The q block of position ``i`` on the backward's innermost axis: the
    sets of query heads follow one another there, each with all its q
    blocks (``_q_heads`` is the other half of ``i``)."""
    return i if sched.group == 1 else \
        jax.lax.rem(i, jnp.int32(sched.bwd_grid[2] // sched.group))


def _q_heads(i, sched):
    """Which set of ``bwd_g`` query heads, of the ``group`` sets that the
    step's ``bwd_g`` K/V heads serve, position ``i`` works on: query heads
    follow their K/V heads in order, so the sets are consecutive heads."""
    return 0 if sched.group == 1 else \
        jax.lax.div(i, jnp.int32(sched.bwd_grid[2] // sched.group))


def _bwd_call(q3, k3, v3, o3, lse, do3, causal, scale, sched, interpret):
    bh, s, d = q3.shape
    dv = v3.shape[-1]
    g, bq, kvm, qm = (sched.bwd_g, sched.block_q, sched.bwd_block_kv_major,
                      sched.block_q_major)
    group, window = sched.group, sched.window
    n_kvm = s // kvm
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)         # [bh, s/bq, 1, bq]

    def q_heads(b, i):
        return b * group + _q_heads(i, sched)

    def q_map(b, jm, i):
        im = _q_block(i, sched)
        # a dead block is not fetched: take the nearest live one
        if causal:
            im = jnp.maximum(im, _div(jm * kvm, qm))
        if window:
            im = jnp.minimum(im, _div(jm * kvm + kvm + window - 2, qm))
        return (q_heads(b, i), im, 0)

    kv_map = lambda b, jm, i: (b, jm, 0)
    q_spec, do_spec = (pl.BlockSpec((g, qm, w), q_map) for w in (d, dv))
    k_spec, v_spec = (pl.BlockSpec((g, kvm, w), kv_map) for w in (d, dv))
    row_spec = pl.BlockSpec((g, qm // bq, 1, bq),
                            lambda b, jm, i: q_map(b, jm, i) + (0,))
    # one kv block: the step's dQ is dQ; several: f32 partials, summed below
    dq_dtype = q3.dtype if n_kvm == 1 else jnp.float32
    dq_scratch, dkv_scratch = _bwd_scratch(sched, dq_dtype)
    dq, dk, dv = pl.pallas_call(
        partial(_bwd_kernel, causal=causal, scale=scale, sched=sched),
        grid=sched.bwd_grid,
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((None, g, qm, d),
                         lambda b, jm, i: (jm, q_heads(b, i),
                                           _q_block(i, sched), 0)),
            k_spec, v_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_kvm, bh, s, d), dq_dtype),
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in
                        dq_scratch * [(g, qm, d)]
                        + dkv_scratch * [(g, kvm, d), (g, kvm, dv)]],
        compiler_params=_compiler_params(),
        name="flash_win_bwd_dkv" if window else "flash_bwd_dkv",
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
    dq = dq[0] if n_kvm == 1 else jnp.sum(dq, axis=0).astype(q3.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom-vjp wrapper
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, causal, scale, sched, interpret):
    o, _ = _fwd_call(q3, k3, v3, causal, scale, sched, interpret)
    return o


# What the forward kernel leaves for the backward, by name: a remat policy
# that saves these (``jax.checkpoint_policies.save_only_these_names``) spares
# the recomputed block its forward kernel at one [bh, S, D] output and one
# float32 row a query. The policy's whole list, these two first, is
# ``models/remat.py:KEPT_NAMES``.
SAVED_NAMES = ("flash_o", "flash_lse")


def _flash_fwd(q3, k3, v3, causal, scale, sched, interpret):
    o, lse = _fwd_call(q3, k3, v3, causal, scale, sched, interpret)
    o = checkpoint_name(o, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, scale, sched, interpret, res, do3):
    q3, k3, v3, o3, lse = res
    return _bwd_call(q3, k3, v3, o3, lse, do3, causal, scale, sched,
                     interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    block_kv_major: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused attention over q [B, H, S, D], k [B, Hkv, S, D] and v [B, Hkv,
    S, Dv], H a multiple of Hkv (query head h reads key/value head ``h // (H
    / Hkv)``); the output is [B, H, S, Dv]; the scores are ``q . k`` times
    ``scale`` (``D ** -0.5`` where none is given: an arch with a multiplier
    of its own, ``Arch.attn_scale``, passes it). Drop-in
    for ``ring.full_attention`` (same signature semantics, same output).
    ``window``: query i sees keys ``i - window < j <= i`` (needs ``causal``).
    The schedule comes from ``flash_schedule`` at the inputs' shape; the
    block arguments bound it from above (the tests' override).

    Raises ValueError when S has no power-of-two block divisor >= 8: a
    caller that asked for the fused kernel is told it cannot have it.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, h, s, d = q.shape
    h_kv, dv = k.shape[1], v.shape[-1]
    sched = flash_schedule(b * h, s, d, q.dtype.itemsize, causal,
                           block_q, block_kv, block_kv_major,
                           window=window, bh_kv=b * h_kv, dv=dv)
    if scale is None:
        scale = float(d) ** -0.5
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * h_kv, s, d)
    v3 = v.reshape(b * h_kv, s, dv)
    o3 = _flash(q3, k3, v3, causal, float(scale), sched, bool(interpret))
    return o3.reshape(b, h, s, dv)
