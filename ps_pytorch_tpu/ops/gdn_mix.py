"""The two elementwise chains of a Gated DeltaNet mixer (Pallas), each one
pass over HBM forward and one backward.

    conv_silu_l2norm:   c_t = sum_k w[k] * qkv[t - (K - 1) + k]     zeros before token 0
                        y = silu(c)
                        q = y / sqrt(sum y^2 + 1e-6) / sqrt(d)      a head; k the same, no 1/sqrt(d)
                        v = y
    gated_rms_norm:     o / sqrt(mean o^2 + eps) * scale * silu(z)  a head

Written as ``jax.numpy`` ops and differentiated by JAX these are about sixty
passes over ``[S, 8192]`` a layer (four shifted products, float32 copies of
q, k and o, a tuple of the four products for the weight's gradient); the
convolution, the norms and the gates have no matmul in them, so the time is
the bytes. Here each chain is one function with a ``custom_vjp`` that keeps
its inputs and nothing else; the backward forms the convolution, its SiLU and
the statistics again inside a tile.

Layout. A grid step holds ``rows`` tokens of ``heads`` heads: a ``[rows,
heads * d]`` block of the projection's output as the matmul wrote it, tokens
along the sublanes and a head's ``d`` channels along the lanes, so a head's
norm is a lane reduction of one register column and needs no relayout (``d``
is 128 in the published model). Everything inside a step is float32: the
block is cast once into VMEM scratch behind ``HALO`` rows that a second
block of the same array brings (the last rows of the tile before; zeros in a
sequence's first tile, for every batch row: the grid has the batch as an axis
of its own), and a tap's shifted copy is a read of that scratch a row
further on (a sublane shift of its registers). A step works through its
block a head and ``chunk`` rows at a time. No shifted product, padded copy
or float32 row passes through HBM.

q, k, v leave HEAD-MAJOR, ``[B, H, S, d]``, which is what
``ops/gated_delta_rule.py`` reads (its ``[B H, S, d]``), and ``o`` and the
three cotangents are read so: the functions below hand out and take ``[B, S,
H, d]`` as the model writes it, through a transpose that XLA cancels against
the delta rule's own. z, the gated norm's output and its cotangent stay as
the projections read and write them, ``[B, S, H d]``.

Backward of the convolution chain, a tile: with ``n = rsqrt(sum y^2 + eps)``
and ``yh = y n``, ``dy = n s (g - yh (yh . g))`` (``dy = g`` for v), ``dc =
dy (sig + y (1 - sig))``, ``d qkv[t] = sum_k w[k] dc[t + K - 1 - k]``, which
reads ``K - 1`` rows AFTER the tile: a third block brings the next tile's
first rows of ``qkv`` and of the cotangent, and their ``dc`` is formed here
too (zeros after the sequence's end). ``dW[k] = sum_t dc[t] qkv[t - (K - 1)
+ k]`` is summed in float32 in the output block across the sequence tiles
and batch rows (eight partial rows a tap, which XLA adds). The three
segments (q, k, v) are three calls a direction, each with its own constants;
the gated norm is one.

The convolution's kernel pair serves the Mamba-2 mixer too
(``ops/ssm_mix.py:conv_bias_silu``): what differs between the two uses is a
``ConvChain``'s static fields and nothing else: a bias (the row after the
taps' in the weight operand, its gradient a partial row beside theirs), the
segments and which of them are normalised, and whether the outputs leave
head-major or token-major (a block spec). Without a bias the kernels trace to
the same bodies as before there was one.

``mix_schedule`` says what the calls hold and move; the trainer prints it on
its ``KERNELS`` line. On one v5e at 16384 tokens, 16 key and 32 value heads
of 128, in the cell's step (PERF.md section 5, PR 41): the three forward
calls 1.12 ms, the three backward 2.83 (the vector unit's: about sixty
float32 operations an element), the norm 0.64 and 1.02 (the memory's: 630
GB/s).
"""

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_pytorch_tpu.ops._backend import interpret_default as _interpret_default
from ps_pytorch_tpu.ops.selective_scan import _tree_sum

# A grid step holds ROWS tokens of LANES channels (one head of 128) and works
# through them CHUNK rows at a time in a loop. Measured on a v5e (PERF.md
# section 6, PR 41): a step wants a quarter of a million elements (2048 x 128
# and 512 x 512 read the same, 512 x 128 a third slower), and a trip of the
# loop wants hundreds of rows: every trip waits out its chain's latency (a
# lane reduction, an rsqrt, a tanh), so 32 rows a trip read 2.6 times slower
# than 128 and 128 a seventh slower than 512. One head a step and a loop that
# is not unrolled keep the kernels' traced and compiled bodies small: the
# same work written out for four heads and four chunks a step traced four
# to five times longer, which a run pays twice before its first step.
ROWS = 2048
LANES = 128
HALO = 16           # rows of a neighbouring tile a block brings: one bfloat16 register tile
CHUNK = 512
SUB = 8             # partial rows of a sum over tokens (a float32 register's sublanes)
L2_EPS = 1e-6
VMEM_LIMIT_BYTES = 48 * 2 ** 20
_F32 = jnp.float32


class MixSchedule(NamedTuple):
    """What the mixer's calls hold and move, from the shapes alone."""
    lanes: int          # channels a grid step (heads a step x head width)
    rows: int           # tokens a grid step
    chunk: int          # rows of one head a kernel works on at a time
    halo: int           # rows of the tile before (and, backward, after) a step also reads
    conv_grid: tuple    # (batch, channel tiles of q, k and v together, sequence tiles)
    norm_grid: tuple    # (batch, channel tiles of the value heads, sequence tiles)
    conv_fwd_bytes: int     # what the three forward calls of the convolution chain move through HBM
    conv_bwd_bytes: int     # ... the three backward calls
    norm_fwd_bytes: int     # ... the gated norm's forward call
    norm_bwd_bytes: int     # ... its backward call

    def describe(self) -> str:
        grid = lambda g: "x".join(map(str, g))
        return (f"lanes={self.lanes} rows={self.rows} chunk={self.chunk} "
                f"halo={self.halo} conv_grid={grid(self.conv_grid)} "
                f"norm_grid={grid(self.norm_grid)} "
                f"conv_fwd_bytes={self.conv_fwd_bytes} "
                f"conv_bwd_bytes={self.conv_bwd_bytes} "
                f"norm_fwd_bytes={self.norm_fwd_bytes} "
                f"norm_bwd_bytes={self.norm_bwd_bytes}")


def _rows_a_step(s: int, lanes: int):
    """(rows a step, rows of them worked on at a time) at ``lanes`` channels
    a step. A step wider than LANES (a Mamba-2 group: ``ops/ssm_mix.py``)
    takes as many fewer rows, so a step and a trip of its loop hold the same
    elements."""
    wide = max(lanes // LANES, 1)
    rows = min(max(ROWS // wide, HALO), -(-s // HALO) * HALO)
    # the most rows up to CHUNK that divide a step's, whole registers of them
    chunk = next(c for c in range(min(rows, max(CHUNK // wide, SUB)), 0, -SUB)
                 if rows % c == 0)
    return rows, chunk


def _tiles(s: int, key_heads: int, value_heads: int, d: int):
    """(rows a step, rows of them worked on at a time, heads a step)."""
    heads = math.gcd(key_heads, value_heads, max(LANES // d, 1))
    return (*_rows_a_step(s, heads * d), heads)


def mix_schedule(batch: int, s: int, key_heads: int, value_heads: int,
                 d: int, taps: int, itemsize: int = 2) -> MixSchedule:
    rows, chunk, heads = _tiles(s, key_heads, value_heads, d)
    lanes = heads * d
    tiles = -(-s // rows)
    total = 2 * key_heads + value_heads
    conv_steps = batch * (total // heads) * tiles
    norm_steps = batch * (value_heads // heads) * tiles
    block, halo = rows * lanes * itemsize, HALO * lanes * itemsize
    weight = taps * total * d * 4
    return MixSchedule(
        lanes, rows, chunk, HALO,
        (batch, total // heads, tiles), (batch, value_heads // heads, tiles),
        conv_steps * (2 * block + halo) + weight,
        conv_steps * (3 * block + 3 * halo) + weight + SUB * weight,
        norm_steps * 3 * block + d * 4,
        norm_steps * 5 * block + d * 4 + SUB * d * 4)


def _compiler_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=(semantics,) * 3,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _sigmoid(x):
    """The logistic through tanh: one transcendental and no division."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _fold(p):
    """[rows, d] -> [SUB, d]: the sum of its groups of eight rows (register
    adds; the eight partial rows are added outside the kernel)."""
    return _tree_sum(p[a:a + SUB] for a in range(0, p.shape[0], SUB))


def _each_chunk(rows, chunk, body, carry=0):
    """``carry = body(r0, carry)`` for ``r0 = 0, chunk, ..`` under ``rows``:
    a loop whose body is traced and compiled once."""
    if rows == chunk:
        return body(0, carry)
    return jax.lax.fori_loop(
        0, rows // chunk,
        lambda r, c: body(pl.multiple_of(r * chunk, chunk), c), carry)


def _rows_from(first, n):
    """Token numbers ``first .. first + n - 1`` as a column."""
    return first + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)


# --------------------------------------------------------------------------
# convolution -> SiLU -> l2 norm
# --------------------------------------------------------------------------

def _shifted(buf, first, n, lanes, shifts):
    """Rows ``first + j .. first + j + n - 1`` of ``buf`` for every ``j`` of
    ``shifts`` (each under SUB; ``first`` a multiple of SUB): one aligned
    read of ``n + SUB`` rows, then a slice a shift, which moves whole
    registers' sublanes."""
    x = buf[pl.ds(first, n + SUB), lanes]
    return [x[j:j + n] for j in shifts]


def _weighted(xs, w):
    """sum_k xs[k] w[k]."""
    return _tree_sum(x * wk for x, wk in zip(xs, w))


def _taps(w, bias, xbuf, r0, n, lanes):
    """The convolution's ``len(w)`` shifted copies of rows ``r0 .. r0 + n -
    1`` of the tile (tap k reads ``K - 1 - k`` rows back) and their weighted
    sum, under the ``bias`` row where there is one. ``xbuf`` holds the tile
    behind HALO rows of the one before."""
    taps = len(w)
    xs = _shifted(xbuf, r0 + HALO - SUB, n, lanes,
                  [SUB - (taps - 1) + k for k in range(taps)])
    c = _weighted(xs, w)
    return xs, c if bias is None else c + bias


def _weight_rows(w_ref, lanes, biased):
    """The taps' rows of the weight operand and, where the convolution has a
    bias, the row after them that holds it."""
    taps = w_ref.shape[0] - biased
    return ([w_ref[k:k + 1, lanes] for k in range(taps)],
            w_ref[taps:taps + 1, lanes] if biased else None)


def _conv_fwd_kernel(w_ref, u_ref, before_ref, o_ref, xbuf, *, biased, heads,
                     d, chunk, unit, scale):
    rows = u_ref.shape[0]
    xbuf[:HALO] = jnp.where(pl.program_id(2) == 0, 0.0,
                            before_ref[...].astype(_F32))
    xbuf[HALO:] = u_ref[...].astype(_F32)
    for h in range(heads):
        lanes = pl.ds(h * d, d)
        w, bias = _weight_rows(w_ref, lanes, biased)

        def step(r0, carry, h=h, lanes=lanes, w=w, bias=bias):
            _, c = _taps(w, bias, xbuf, r0, chunk, lanes)
            y = c * _sigmoid(c)
            if unit:
                y = y * (jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                       + L2_EPS) * scale)
            o_ref[h, pl.ds(r0, chunk), :] = y.astype(o_ref.dtype)
            return carry

        _each_chunk(rows, chunk, step)


def _pull_back(w, bias, xbuf, r0, n, lanes, g, unit, scale):
    """``(shifted copies, dc)`` of rows ``r0 .. r0 + n - 1``: the chain
    formed again from ``xbuf`` and the cotangent ``g`` pulled back through
    the norm and the SiLU."""
    xs, c = _taps(w, bias, xbuf, r0, n, lanes)
    sig = _sigmoid(c)
    y = c * sig
    if unit:
        norm = jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
        yh = y * norm
        g = (norm * scale) * (g - yh * jnp.sum(yh * g, axis=-1,
                                               keepdims=True))
    return xs, g * (sig + y * (1.0 - sig))


def _conv_bwd_kernel(w_ref, u_ref, before_ref, after_ref, g_ref, g_after_ref,
                     du_ref, dw_ref, xbuf, dcbuf, *, biased, heads, d, chunk,
                     s, unit, scale):
    rows = u_ref.shape[0]
    taps = w_ref.shape[0] - biased
    tile = pl.program_id(2)

    @pl.when((pl.program_id(1) == 0) & (tile == 0))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # rows past the sequence's end count as zeros: the rows after the last
    # tile always, a tile's own only where S is no whole number of tiles
    ragged = s % rows != 0
    inside = lambda first, n: _rows_from(tile * rows + first, n) < s
    own = u_ref[...].astype(_F32)
    xbuf[:HALO] = jnp.where(tile == 0, 0.0, before_ref[...].astype(_F32))
    xbuf[HALO:HALO + rows] = \
        jnp.where(inside(0, rows), own, 0.0) if ragged else own
    xbuf[HALO + rows:] = jnp.where(inside(rows, HALO),
                                   after_ref[...].astype(_F32), 0.0)
    for h in range(heads):
        lanes = pl.ds(h * d, d)
        w, bias = _weight_rows(w_ref, lanes, biased)

        def pull(r0, sums, h=h, lanes=lanes, w=w, bias=bias):
            g = g_ref[h, pl.ds(r0, chunk), :].astype(_F32)
            if ragged:
                g = jnp.where(inside(r0, chunk), g, 0.0)
            xs, dc = _pull_back(w, bias, xbuf, r0, chunk, lanes, g, unit,
                                scale)
            dcbuf[pl.ds(r0, chunk), lanes] = dc
            # a tap's gradient is dc on its shifted copy; the bias's, dc
            return tuple(acc + _fold(dc * x) for acc, x in zip(sums, xs)) \
                + ((sums[taps] + _fold(dc),) if biased else ())

        sums = _each_chunk(rows, chunk, pull, tuple(
            jnp.zeros((SUB, d), _F32) for _ in range(taps + biased)))
        for k, total in enumerate(sums):
            dw_ref[k, :, lanes] += total
        # the next tile's first rows: their dc reaches this tile's last rows
        g = jnp.where(inside(rows, HALO), g_after_ref[h].astype(_F32), 0.0)
        dcbuf[rows:, lanes] = _pull_back(w, bias, xbuf, rows, HALO, lanes, g,
                                         unit, scale)[1]

        def push(r0, carry, lanes=lanes, w=w):
            du = _weighted(_shifted(dcbuf, r0, chunk, lanes,
                                    [taps - 1 - k for k in range(taps)]), w)
            du_ref[pl.ds(r0, chunk), lanes] = du.astype(du_ref.dtype)
            return carry

        _each_chunk(rows, chunk, push)


def _segments(key_heads, value_heads, d):
    """(name, first head, heads, normalised?, the length a head is scaled to)
    of q, k and v in the projection's ``[q | k | v]``."""
    return (("q", 0, key_heads, True, 1.0 / math.sqrt(d)),
            ("k", key_heads, key_heads, True, 1.0),
            ("v", 2 * key_heads, value_heads, False, 1.0))


class ConvChain(NamedTuple):
    """What tells one use of the convolution's kernels from another, all of
    it static: the calls' names, the segments of the channels (a call each
    way a segment), a head's width and the heads a grid step, whether the
    weight operand's last row is a bias, and how the outputs leave."""
    name: str           # the calls are <name>_fwd_<segment>, <name>_bwd_<segment>
    segments: tuple     # ((name, first head, heads, normalised?, scale), ...)
    d: int
    heads: int
    biased: bool
    token_major: bool   # [B, S, heads d] (one head a step), else [B, heads, S, d]


def _halo_blocks(s, rows):
    """Index (in blocks of HALO rows) of the rows before and after sequence
    tile ``i``, clamped at the sequence's ends, where the kernels put
    zeros."""
    per, last = rows // HALO, -(-s // HALO) - 1
    return (lambda i: jnp.maximum(i * per - 1, 0),
            lambda i: jnp.minimum((i + 1) * per, last))


def _conv_specs(s, w_rows, rows, lanes, first, order):
    """Block specs of one segment's calls: the weight's, the tile's, and the
    HALO rows before and after it. ``first``: the segment's first channel
    tile; ``order`` maps a grid step to (batch, channel tile, sequence
    tile)."""
    before, after = _halo_blocks(s, rows)

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *g: index(*order(*g)))

    return (spec((w_rows, lanes), lambda b, j, i: (0, first + j)),
            spec((None, rows, lanes), lambda b, j, i: (b, i, first + j)),
            spec((None, HALO, lanes),
                 lambda b, j, i: (b, before(i), first + j)),
            spec((None, HALO, lanes),
                 lambda b, j, i: (b, after(i), first + j)))


def _heads_layout(chain, bt, n_heads, s):
    """How a segment's output (and its cotangent) lies in HBM, as the
    kernels' ``[heads, rows, d]`` blocks see it: ``(shape, spec)`` with
    ``spec(rows, at)`` the block of ``rows`` tokens that ``at(*grid step) ->
    (batch, channel tile, row block)`` names. Head-major ``[B, H, S, d]``;
    token-major the same rows under ONE head as wide as the segment, ``[B, 1,
    S, H d]``, a reshape of ``[B, S, H d]``."""
    heads, d = chain.heads, chain.d
    if chain.token_major:
        place = lambda b, j, i: (b, 0, i, j)
        shape = (bt, 1, s, n_heads * d)
    else:
        place = lambda b, j, i: (b, j, i, 0)
        shape = (bt, n_heads, s, d)
    return shape, lambda rows, at: pl.BlockSpec(
        (None, heads, rows, d), lambda *g: place(*at(*g)))


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _conv_fwd_call(qkv, w, chain, segment, tiles, interpret):
    name, first, n_heads, unit, scale = segment
    rows, chunk = tiles
    heads, d = chain.heads, chain.d
    bt, s, _ = qkv.shape
    same = lambda b, j, i: (b, j, i)
    w_spec, tile, before, _ = _conv_specs(s, w.shape[0], rows, heads * d,
                                          first // heads, same)
    shape, out = _heads_layout(chain, bt, n_heads, s)
    return pl.pallas_call(
        partial(_conv_fwd_kernel, biased=chain.biased, heads=heads, d=d,
                chunk=chunk, unit=unit, scale=scale),
        grid=(bt, n_heads // heads, -(-s // rows)),
        in_specs=[w_spec, tile, before],
        out_specs=out(rows, same),
        out_shape=jax.ShapeDtypeStruct(shape, qkv.dtype),
        scratch_shapes=[pltpu.VMEM((HALO + rows, heads * d), _F32)],
        compiler_params=_compiler_params("parallel"),
        interpret=interpret, name=f"{chain.name}_fwd_{name}")(w, qkv, qkv)


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _conv_bwd_call(qkv, w, g, chain, segment, tiles, interpret):
    """-> (d qkv of the segment's channels ``[B, S, heads d]``, the gradient
    of its rows of the weight operand as eight partial rows each ``[K, SUB,
    heads d]``)."""
    name, first, n_heads, unit, scale = segment
    rows, chunk = tiles
    heads, d = chain.heads, chain.d
    bt, s, _ = qkv.shape
    lanes = heads * d
    # the channel tile outermost: a tile's dW block stays while the batch
    # rows and sequence tiles under it are summed
    order = lambda j, b, i: (b, j, i)
    after = _halo_blocks(s, rows)[1]
    _, cotangent = _heads_layout(chain, bt, n_heads, s)
    return pl.pallas_call(
        partial(_conv_bwd_kernel, biased=chain.biased, heads=heads, d=d,
                chunk=chunk, s=s, unit=unit, scale=scale),
        grid=(n_heads // heads, bt, -(-s // rows)),
        in_specs=[
            *_conv_specs(s, w.shape[0], rows, lanes, first // heads, order),
            cotangent(rows, order),
            cotangent(HALO, lambda j, b, i: (b, j, after(i))),
        ],
        out_specs=[
            pl.BlockSpec((None, rows, lanes), lambda j, b, i: (b, i, j)),
            pl.BlockSpec((w.shape[0], SUB, lanes), lambda j, b, i: (0, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, s, n_heads * d), qkv.dtype),
            jax.ShapeDtypeStruct((w.shape[0], SUB, n_heads * d), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((HALO + rows + HALO, lanes), _F32),
                        pltpu.VMEM((rows + HALO, lanes), _F32)],
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
        name=f"{chain.name}_bwd_{name}")(w, qkv, qkv, qkv, g, g)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv_chain(qkv, w, chain, interpret):
    """The convolution chain over ``qkv [B, S, C]`` under the weight operand
    ``w`` (the taps' rows ``[K, C]`` float32, then the bias's where the chain
    has one): a tuple of the segments' outputs as ``chain`` lays them. Keeps
    its two inputs for the backward and nothing else."""
    return _conv_fwd(qkv, w, chain, interpret)[0]


def _conv_tiles(qkv, chain):
    return _rows_a_step(qkv.shape[1], chain.heads * chain.d)


def _conv_fwd(qkv, w, chain, interpret):
    tiles = _conv_tiles(qkv, chain)
    out = tuple(_conv_fwd_call(qkv, w, chain, segment, tiles, interpret)
                for segment in chain.segments)
    return out, (qkv, w)


def _conv_bwd(chain, interpret, res, cts):
    qkv, w = res
    tiles = _conv_tiles(qkv, chain)
    du, dw = zip(*(
        _conv_bwd_call(qkv, w, g.astype(qkv.dtype), chain, segment, tiles,
                       interpret)
        for g, segment in zip(cts, chain.segments)))
    return (jnp.concatenate(du, axis=-1),
            jnp.concatenate(dw, axis=-1).sum(axis=1))


conv_chain.defvjp(_conv_fwd, _conv_bwd)


def refuse_taps(op: str, taps: int):
    if taps > SUB:
        raise ValueError(f"{op}: {taps} taps: a tap reads under {SUB} rows "
                         "back")


def conv_silu_l2norm(qkv, weight, *, key_heads: int, value_heads: int,
                     key_dim: int, value_dim: int,
                     interpret: Optional[bool] = None):
    """``qkv [B, S, (2 Hk + Hv) d]`` (the projection's ``[q | k | v]``) and
    the depthwise causal convolution's ``weight [K, (2 Hk + Hv) d]`` ->
    ``q, k [B, S, Hk, d]``, each head of unit length and q scaled by ``1 /
    sqrt(d)``, and ``v [B, S, Hv, d]``, in qkv's dtype. Differentiable in
    both; the weight's gradient is float32."""
    if interpret is None:
        interpret = _interpret_default()
    if key_dim != value_dim:
        raise ValueError(
            f"conv_silu_l2norm: key heads of {key_dim} and value heads of "
            f"{value_dim}: a tile holds whole heads of one width, two widths "
            "are not built")
    if qkv.shape[2] != (2 * key_heads + value_heads) * key_dim \
            or weight.shape[1:] != qkv.shape[2:]:
        raise ValueError(
            f"conv_silu_l2norm: qkv {qkv.shape} [B, S, (2 Hk + Hv) d] with "
            f"Hk, Hv, d = {key_heads}, {value_heads}, {key_dim}; weight "
            f"{weight.shape} [K, (2 Hk + Hv) d]")
    refuse_taps("conv_silu_l2norm", weight.shape[0])
    chain = ConvChain(
        "gdn_conv", _segments(key_heads, value_heads, key_dim), key_dim,
        _tiles(qkv.shape[1], key_heads, value_heads, key_dim)[2],
        biased=False, token_major=False)
    out = conv_chain(qkv, weight.astype(_F32), chain, bool(interpret))
    return tuple(jnp.moveaxis(a, 1, 2) for a in out)


# --------------------------------------------------------------------------
# the gated output norm
# --------------------------------------------------------------------------

def _norm_fwd_kernel(scale_ref, o_ref, z_ref, out_ref, *, heads, d, chunk,
                     eps):
    scale = scale_ref[...]
    for h in range(heads):
        lanes = pl.ds(h * d, d)


        def step(r0, carry, h=h, lanes=lanes):
            o = o_ref[h, pl.ds(r0, chunk), :].astype(_F32)
            z = z_ref[pl.ds(r0, chunk), lanes].astype(_F32)
            norm = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                 + eps)
            out_ref[pl.ds(r0, chunk), lanes] = \
                ((o * norm) * scale * (z * _sigmoid(z))).astype(out_ref.dtype)
            return carry

        _each_chunk(z_ref.shape[0], chunk, step)


def _norm_bwd_kernel(scale_ref, o_ref, z_ref, g_ref, do_ref, dz_ref,
                     dscale_ref, *, heads, d, chunk, s, eps):
    rows = z_ref.shape[0]
    tile = pl.program_id(2)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0) & (tile == 0))
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    scale = scale_ref[...]
    ragged = s % rows != 0
    for h in range(heads):
        lanes = pl.ds(h * d, d)


        def step(r0, acc, h=h, lanes=lanes):
            o = o_ref[h, pl.ds(r0, chunk), :].astype(_F32)
            z = z_ref[pl.ds(r0, chunk), lanes].astype(_F32)
            g = g_ref[pl.ds(r0, chunk), lanes].astype(_F32)
            norm = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                 + eps)
            sig = _sigmoid(z)
            gate, oh = z * sig, o * norm
            through = g * gate              # the cotangent of oh * scale
            dz = (g * oh) * scale * (sig + gate * (1.0 - sig))
            doh = through * scale
            do = norm * (doh - oh * jnp.mean(doh * oh, axis=-1,
                                             keepdims=True))
            do_ref[h, pl.ds(r0, chunk), :] = do.astype(do_ref.dtype)
            dz_ref[pl.ds(r0, chunk), lanes] = dz.astype(dz_ref.dtype)
            to_scale = through * oh
            if ragged:      # rows past the sequence's end hold anything
                to_scale = jnp.where(
                    _rows_from(tile * rows + r0, chunk) < s, to_scale, 0.0)
            return acc + _fold(to_scale)

        dscale_ref[...] += _each_chunk(rows, chunk, step,
                                       jnp.zeros((SUB, d), _F32))


def _norm_specs(rows, heads, d):
    """A tile of the scale, of head-major ``o`` and of ``[B, S, H d]``."""
    return (pl.BlockSpec((1, d), lambda b, j, i: (0, 0)),
            pl.BlockSpec((None, heads, rows, d), lambda b, j, i: (b, j, i, 0)),
            pl.BlockSpec((None, rows, heads * d), lambda b, j, i: (b, i, j)))


def _norm_grid(o, tiles):
    bt, n_heads, s, _ = o.shape
    return (bt, n_heads // tiles[2], -(-s // tiles[0]))


@partial(jax.jit, static_argnums=(3, 4, 5))
def _norm_fwd_call(o, z, scale, eps, tiles, interpret):
    rows, chunk, heads = tiles
    d = o.shape[3]
    scale_spec, head_major, flat = _norm_specs(rows, heads, d)
    return pl.pallas_call(
        partial(_norm_fwd_kernel, heads=heads, d=d, chunk=chunk, eps=eps),
        grid=_norm_grid(o, tiles),
        in_specs=[scale_spec, head_major, flat],
        out_specs=flat,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        compiler_params=_compiler_params("parallel"),
        interpret=interpret, name="gdn_norm_fwd")(scale, o, z)


@partial(jax.jit, static_argnums=(4, 5, 6))
def _norm_bwd_call(o, z, scale, g, eps, tiles, interpret):
    rows, chunk, heads = tiles
    d = o.shape[3]
    scale_spec, head_major, flat = _norm_specs(rows, heads, d)
    return pl.pallas_call(
        partial(_norm_bwd_kernel, heads=heads, d=d, chunk=chunk,
                s=o.shape[2], eps=eps),
        grid=_norm_grid(o, tiles),
        in_specs=[scale_spec, head_major, flat, flat],
        out_specs=[head_major, flat,
                   pl.BlockSpec((SUB, d), lambda b, j, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((SUB, d), _F32)],
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret, name="gdn_norm_bwd")(scale, o, z, g)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm(o, z, scale, eps, interpret):
    return _norm_fwd(o, z, scale, eps, interpret)[0]


def _norm_tiles(o):
    return _tiles(o.shape[2], o.shape[1], o.shape[1], o.shape[3])


def _norm_fwd(o, z, scale, eps, interpret):
    return (_norm_fwd_call(o, z, scale, eps, _norm_tiles(o), interpret),
            (o, z, scale))


def _norm_bwd(eps, interpret, res, g):
    o, z, scale = res
    do, dz, dscale = _norm_bwd_call(o, z, scale, g.astype(z.dtype), eps,
                                    _norm_tiles(o), interpret)
    return do, dz, dscale.sum(axis=0, keepdims=True)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_rms_norm(o, z, scale, *, eps: float,
                   interpret: Optional[bool] = None):
    """``o [B, S, H, d]``, ``z [B, S, H d]`` and the norm's ``scale [d]`` ->
    ``RMSNorm_d(o) * scale * silu(z)`` as ``[B, S, H d]`` in z's dtype.
    Differentiable in all three; the scale's gradient is float32."""
    if interpret is None:
        interpret = _interpret_default()
    bt, s, n_heads, d = o.shape
    if z.shape != (bt, s, n_heads * d) or scale.shape != (d,):
        raise ValueError(f"gated_rms_norm: o {o.shape} [B, S, H, d], z "
                         f"{z.shape} [B, S, H d], scale {scale.shape} [d]")
    return _norm(jnp.moveaxis(o, 2, 1).astype(z.dtype), z,
                 scale.astype(_F32).reshape(1, d), float(eps),
                 bool(interpret))
