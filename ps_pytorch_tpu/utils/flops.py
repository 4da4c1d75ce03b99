"""Analytic per-step FLOPs model, by jaxpr traversal.

The reference never measured utilization — its notebooks report relative
speedups only (SURVEY §6) — so "is it actually fast" was unanswerable. This
module is the framework's own bar: count the matmul/conv FLOPs of any jitted
function (forward, or the full value_and_grad training step) and divide by
the chip's peak to get MFU.

Counting is exact for ``dot_general`` and the grouped matmuls (the routed rows
only), dense S x S for the flash-attention kernels, and
exact-up-to-boundary-effects for
``conv_general_dilated`` (useful MACs only — taps on lhs_dilation-inserted
zeros are excluded, which matters for the grad-input convs of strided
layers); elementwise/reduction traffic is deliberately ignored (it is
bandwidth, not FLOPs, and contributes <1% on these models). Backward-pass FLOPs are counted for real by tracing
``jax.value_and_grad`` rather than assuming the usual 3x-forward rule —
conv_transpose/rewrites make the true multiple model-dependent.
"""

import math
from typing import Any, Callable, Iterable, Optional

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp


def _prod(xs: Iterable[int]) -> int:
    return math.prod(int(x) for x in xs)


def _dot_general_flops(eqn) -> int:
    lhs = eqn.invars[0].aval
    rhs = eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = _prod(lhs.shape[i] for i in lb)
    k = _prod(lhs.shape[i] for i in lc)
    m = _prod(lhs.shape[i] for i in range(len(lhs.shape))
              if i not in set(lc) | set(lb))
    n = _prod(rhs.shape[i] for i in range(len(rhs.shape))
              if i not in set(rc) | set(rb))
    return 2 * batch * m * k * n


def _routed_matmul_flops(eqn) -> int:
    """The grouped-matmul kernels of ``ops/grouped_matmul.py``. Their grid is
    the static worst case of row-tile visits and the kernel skips the ones
    past the real count, so grid x body would charge up to twice the work;
    what they do is the routed work, 2 * rows * K * N, read from the two
    array operands: ([M, K], [E, K, N]), ([M, N], [E, K, N] read transposed)
    or ([M, K], [M, N])."""
    a, b = (v.aval.shape for v in eqn.invars[-2:])
    if len(b) == 3:
        return 2 * a[0] * b[1] * b[2]
    return 2 * a[0] * a[1] * b[1]


def _flash_flops(eqn) -> int:
    """The kernels of ``ops/flash_attention.py``. They walk the live tiles in
    ``fori_loop``s whose trip counts depend on the grid position, which grid x
    body cannot count; they are charged the dense S x S products, like
    ``full_attention``: two in ``flash_fwd`` (the scores over the keys'
    width, the weighted sum over the value's), five in ``flash_bwd_dkv``
    (the scores, dQ and dK over the keys' width, dP and dV over the value's;
    q is the first [bh, S, D] operand, v the third, [bh_kv, S, Dv]), under a
    window too (``flash_win_*``): the program's own logged ``mfu`` therefore
    overstates a window layer's work; the benchmark's charges the band
    (``benchmark/reference/smallthinker_21b_a3b.py``)."""
    bh, s, d = eqn.invars[0].aval.shape
    dv = eqn.invars[2].aval.shape[-1]
    widths = d + dv if eqn.params["name"].endswith("_fwd") else 3 * d + 2 * dv
    return widths * 2 * bh * s * s


def _conv_flops(eqn) -> int:
    # 2 * (#output elements incl. batch & Cout) * Kh*Kw*... * Cin_per_group.
    # The kernel's in-feature dim is already Cin/feature_group_count, so
    # grouped/depthwise convs are handled by construction.
    #
    # lhs_dilation inserts zeros into the INPUT (the grad-input conv of a
    # stride-s forward carries lhs_dilation=s): taps on inserted zeros do no
    # useful work, and only 1/prod(lhs_dilation) of taps hit real data —
    # without this division a stride-2 conv's backward overcounts ~3x
    # (empirically verified against the fwd==grad-input==grad-weight MAC
    # identity). rhs_dilation needs no correction: the formula reads the
    # UNdilated rhs shape, so inserted kernel zeros never enter the count.
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    kernel_in_c = rhs.shape[dn.rhs_spec[1]]
    kernel_spatial = _prod(rhs.shape[d] for d in dn.rhs_spec[2:])
    lhs_dil = _prod(eqn.params.get("lhs_dilation") or (1,))
    return 2 * _prod(out.shape) * kernel_in_c * kernel_spatial // lhs_dil


def _sub_jaxprs(eqn):
    """Yield every jaxpr nested in an eqn's params (pjit, remat, scan, cond
    branches, custom_vjp...), so counting recurses through the whole program."""
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def _trips(eqn) -> int:
    """How many times an eqn's nested jaxpr runs per call of the eqn: a
    ``scan`` body once per iteration, a ``shard_map`` body once on every
    device of its manual axes (its shapes are per-shard), a ``pallas_call``
    kernel once per grid point (its shapes are one block's)."""
    name = eqn.primitive.name
    if name == "scan":
        return int(eqn.params.get("length", 1))
    if name == "shard_map":
        mesh = eqn.params["mesh"]
        return _prod(mesh.shape[a] for a in eqn.params["manual_axes"])
    if name == "pallas_call":
        return _prod(g for g in eqn.params["grid_mapping"].grid
                     if isinstance(g, int))
    return 1


def count_jaxpr_flops(jaxpr) -> int:
    """Matmul+conv FLOPs of a jaxpr across ALL devices it runs on, recursing
    into nested call jaxprs with each body multiplied by its trip count
    (``_trips``). Both branches of a ``cond`` count, so a kernel that skips
    causally-dead tiles is charged the dense S x S, like ``full_attention``.
    """
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "pallas_call" and \
                eqn.params.get("name", "").startswith("moe_gmm_"):
            total += _routed_matmul_flops(eqn)
        elif name == "pallas_call" and \
                eqn.params.get("name", "").startswith("flash_"):
            total += _flash_flops(eqn)
        else:
            trips = _trips(eqn)
            for sub in _sub_jaxprs(eqn):
                total += trips * count_jaxpr_flops(sub)
    return total


def forward_flops(fn: Callable, *args: Any) -> int:
    """FLOPs of one call of ``fn(*args)`` (abstract trace; nothing executes)."""
    closed = jax.make_jaxpr(fn)(*args)
    return count_jaxpr_flops(closed.jaxpr)


def training_flops(model, sample_shape, num_classes: int,
                   rngs: Optional[dict] = None) -> int:
    """FLOPs of one forward+backward on a batch of ``sample_shape`` images.

    Traces the real ``jax.value_and_grad`` of the cross-entropy loss (BN
    batch_stats threaded when the model has them), so the backward multiple
    is measured, not assumed. Optimizer-update FLOPs are elementwise and
    excluded (<0.1% for these CNNs).
    """
    import optax

    x = jnp.zeros(sample_shape, jnp.float32)
    y = jnp.zeros((sample_shape[0],), jnp.int32)
    variables = model.init(jax.random.key(0), x, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", None)

    def loss_fn(params, x, y):
        v = {"params": params}
        if batch_stats is not None:
            v["batch_stats"] = batch_stats
            logits, _ = model.apply(v, x, train=True, mutable=["batch_stats"],
                                    rngs={"dropout": jax.random.key(1)})
        else:
            logits = model.apply(v, x, train=True,
                                 rngs={"dropout": jax.random.key(1)})
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    grad_fn = jax.value_and_grad(loss_fn)
    closed = jax.make_jaxpr(grad_fn)(params, x, y)
    return count_jaxpr_flops(closed.jaxpr)


# Peak dense bf16 FLOPs/sec per chip, by device_kind substring (matched
# case-insensitively, first hit wins — order matters for 'v5p' vs 'v5 lite').
# Public figures: v6e/Trillium 918 TF, v5p 459 TF, v5e 197 TF, v4 275 TF,
# v3 123 TF, v2 45 TF.
_PEAK_BF16 = (
    ("v6e", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_bf16(device_kind: str) -> Optional[float]:
    """Peak bf16 FLOPs/sec for a jax device_kind. A TPU kind missing from
    the table is an error (a silent None would read as "MFU n/a" on the
    very machine MFU is for); anything else (CPU) is None — callers then
    report MFU as null rather than a fiction."""
    kind = (device_kind or "").lower()
    for sub, peak in _PEAK_BF16:
        if sub in kind:
            return peak
    if "tpu" in kind:
        raise ValueError(f"no peak bf16 FLOP/s known for TPU device_kind "
                         f"{device_kind!r}; add it to utils/flops._PEAK_BF16")
    return None
