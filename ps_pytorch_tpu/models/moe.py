"""Mixture-of-Experts transformer LM: capacity routing (switch top-1, GShard
top-2; ``MoEMLP``) or dropless top-k routing by sort and grouped matmul
(``DroplessMoE``, the ``olmoe``, ``smallthinker`` and ``trinity`` archs; it can
hold a share of its experts, score by softmax or sigmoid, and choose under a
bias that the train step moves against the load). The arch picks one per
model; a dropless model may start with dense gated layers and pass every token
through shared experts beside the routed ones (``GatedFFN``).

Beyond-parity model family backing expert parallelism (``parallel/ep.py``;
the reference has no MoE or EP anywhere, SURVEY §2.5). Design points:

- **Routing** with a per-expert capacity: ``top_k=1`` (switch) sends each
  token to its argmax expert, gate = the raw top probability; ``top_k=2``
  (GShard) sends it to its two best experts with gates renormalized over
  the pair, and first choices claim capacity slots before second choices
  (rank-priority dispatch — overflow drops second choices first).
  Assignments beyond ``capacity = ceil(top_k * tokens/expert *
  capacity_factor)`` are dropped; a token with ALL assignments dropped
  contributes zero MLP output (the residual stream carries it unchanged).
  Gradients flow through the gate probabilities (top-k selection itself is
  non-differentiable), the standard switch/GShard estimator.
- **Per-group dispatch** (``n_groups``): capacity accounting runs
  independently per contiguous token group. Under expert parallelism each
  device is one group, so the unsharded oracle with ``n_groups = n_devices``
  is BIT-IDENTICAL to the sharded run — equivalence is testable exactly
  (tests/test_ep.py), not just statistically.
- **Stacked expert parameters** ``experts_w1/b1/w2/b2`` with a leading
  [n_experts] axis: under EP this axis shards over the mesh; the module
  works on the local slice inside shard_map (``ep_axis`` bound) and on the
  full stack outside it.
- **Load-balance auxiliary loss** (switch eq. 4: E * mean_e(frac_tokens_e *
  mean_prob_e)) returned alongside the output; the LM sums it over layers
  and the train step adds ``aux_coef`` times it to the CE loss.

The attention half of a block IS ``models/transformer.py``'s
(``attention_sublayer``: one q/k/v/o path, norm, RoPE and q/k norm for both
LM classes), so MoE slots into the same runtime contracts.

**Why two routers stay.** The capacity path's tests pin rank-priority
dispatch per group, bit-identical between the sharded step and the
unsharded oracle, through the dense ``[g, tg, e, cap]`` one-hot tensors; the
dropless path holds no tensor with an expert and a capacity axis and its work
grows with tokens x top_k only. The capacity path would fall out of the
sorted one with a keep mask ``pos < cap`` (PERF.md, Findings PR 25: not done,
with the reason).
"""

import math
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ps_pytorch_tpu.models.gdn import gdn_sublayer
from ps_pytorch_tpu.models.remat import kept, remat_block
from ps_pytorch_tpu.models.ssm import mamba2_sublayer
from ps_pytorch_tpu.models.transformer import (
    ACTS, ARCHS, COUNTER_NAMES, LM_COUNTERS, GatedFFN, attention_sublayer,
    embed_tokens, make_norm, refuse_hybrid,
)
from ps_pytorch_tpu.ops import moe_rows
from ps_pytorch_tpu.ops.grouped_matmul import gmm, gmm_t
from ps_pytorch_tpu.telemetry.trace import device_scope

# What a dropless model returns beside its logits (and the ep step passes
# on), with how each is taken over the layers.
_OVER_LAYERS = {"aux": jnp.mean, "z_loss": jnp.mean,
                "expert_load_max_over_mean": jnp.max, "moe_dropped": jnp.sum,
                "moe_held_share": jnp.mean, "moe_tail_rows_share": jnp.mean}
DROPLESS_STATS = tuple(_OVER_LAYERS)
# The collection of what no gradient moves: ``expert_bias`` [experts] in each
# expert layer whose arch chooses under a bias. It travels in
# ``TrainState.batch_stats`` ({} for every other arch) and is saved with it.
MOE_STATE = "moe_state"
# Such a model also returns, under this key, each layer's assignments to every
# router output, as a tree of the collection's shape.
EXPERT_COUNTS = "expert_counts"
# ... from which the ep step derives these two beside DROPLESS_STATS.
BIAS_STATS = ("moe_bias_abs_max", "moe_load_all_max_over_mean")
# An arch with ``load_all_stat`` and no bias has its expert layers report the
# second themselves (``DroplessMoE.load_all_stat``), the worst layer's.
LOAD_ALL_STAT = BIAS_STATS[1]


def lm_variables(params, moe_state=None):
    """What ``apply`` takes: the parameters and, where the model has one, its
    ``MOE_STATE`` collection."""
    return {"params": params, **({MOE_STATE: moe_state} if moe_state else {})}


def update_expert_bias(bias, counts, rate: float):
    """One step of the balancing that needs no auxiliary loss (torchtitan's,
    whose ``load_balance_coeff`` is ``rate``): an expert that drew fewer
    assignments than the mean is chosen a little more readily next step, one
    that drew more a little less, every expert by the same ``rate``; the
    steps are centred, so the bias keeps its mean. ``counts``: assignments to
    each of ALL the router's outputs in the step, over every device."""
    counts = counts.astype(jnp.float32)
    delta = rate * jnp.sign(jnp.mean(counts) - counts)
    return bias + (delta - jnp.mean(delta))


class MoEMLP(nn.Module):
    """MoE MLP: route each token to its top ``top_k`` of ``n_experts``
    expert FFNs — switch-style (top_k=1, gate = raw top probability) or
    GShard-style (top_k=2, gates renormalized over the selected pair,
    first choices claim capacity slots before second choices)."""
    n_experts: int
    d_model: int
    d_hidden: int
    capacity_factor: float = 1.25
    n_groups: int = 1                 # capacity accounting granularity
    ep_axis: Optional[str] = None     # set inside shard_map for EP
    # Under EP each device stores n_experts / n_devices experts; flax
    # validates stored param shapes against their declaration, so the
    # declaration must say the LOCAL count (parallel/ep.py sets this).
    n_local_experts: Optional[int] = None
    top_k: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        # x: [B, S, D] (the local shard when under shard_map)
        b, s, d = x.shape
        e = self.n_experts
        with device_scope("moe_route"):
            tokens = x.reshape(-1, d)                 # [T, D]
        t = tokens.shape[0]
        if self.ep_axis is not None and self.n_groups != 1:
            raise ValueError("under expert parallelism each device is one "
                             "dispatch group: use n_groups=1")
        if t % self.n_groups:
            raise ValueError(f"{t} tokens not divisible into "
                             f"{self.n_groups} groups")
        if d != self.d_model:
            raise ValueError(f"input feature dim {d} != d_model "
                             f"{self.d_model}")
        g = self.n_groups
        tg = t // g
        # Capacity scales with top_k: the router makes top_k*tg assignments
        # per group, so slots must too — otherwise top-2 at the default
        # factor would structurally drop ~37% of assignments even under a
        # perfectly uniform router, quietly degenerating toward an
        # attenuated top-1.
        cap = max(math.ceil(self.top_k * tg / e * self.capacity_factor), 1)

        if self.top_k not in (1, 2):
            raise ValueError(f"top_k must be 1 or 2, got {self.top_k}")
        with device_scope("moe_route"):
            router = nn.Dense(e, use_bias=False, dtype=self.dtype,
                              name="router")(tokens)      # [T, E]
            probs = jax.nn.softmax(router.astype(jnp.float32), axis=-1)
            top_gates, top_idx = jax.lax.top_k(probs, self.top_k)  # [T, k]
            if self.top_k > 1:
                # GShard: gates renormalized over the selected experts. (For
                # top_k=1 the raw probability is kept — normalizing would
                # make every gate 1.0 and change switch semantics.)
                top_gates = top_gates / jnp.sum(top_gates, axis=-1,
                                                keepdims=True)

        # Per-group dispatch with RANK PRIORITY: rank-0 (first-choice)
        # assignments claim each expert's capacity slots before rank-1, so
        # overflow drops second choices first (GShard's ordering). Each
        # rank's queue positions are offset by the counts the earlier
        # ranks already enqueued.
        with device_scope("moe_dispatch"):
            xg = tokens.reshape(g, tg, d)
            counts = jnp.zeros((g, 1, e), jnp.float32)    # slots used so far
            disp = jnp.zeros((g, tg, e, cap), jnp.float32)
            combine = jnp.zeros((g, tg, e, cap), jnp.float32)
            oh0_g = None
            for r in range(self.top_k):
                oh = jax.nn.one_hot(top_idx[:, r], e, dtype=jnp.float32)
                oh_g = oh.reshape(g, tg, e)
                if r == 0:
                    oh0_g = oh_g
                pos = jnp.cumsum(oh_g, axis=1) - oh_g + counts  # [G, TG, E]
                pos_tok = jnp.sum(pos * oh_g, axis=-1)          # [G, TG]
                keep = pos_tok < cap
                slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), cap,
                                      dtype=jnp.float32)        # [G, TG, C]
                d_r = (oh_g * keep[..., None])[..., None] \
                    * slot[:, :, None, :]
                disp = disp + d_r
                combine = combine + d_r * top_gates[:, r].reshape(g, tg, 1, 1)
                counts = counts + jnp.sum(oh_g, axis=1, keepdims=True)
            expert_in = jnp.einsum("gtec,gtd->gecd", disp, xg)  # [G,E,C,D]

        # Stacked expert FFNs. Under EP the leading axis is the LOCAL
        # expert slice; all_to_all swaps the grouping from
        # (all experts, my tokens) to (my experts, all groups' tokens).
        el = self.n_local_experts if self.n_local_experts is not None else e
        w1 = self.param("experts_w1", nn.initializers.lecun_normal(),
                        (el, d, self.d_hidden))
        b1 = self.param("experts_b1", nn.initializers.zeros,
                        (el, self.d_hidden))
        w2 = self.param("experts_w2", nn.initializers.lecun_normal(),
                        (el, self.d_hidden, d))
        b2 = self.param("experts_b2", nn.initializers.zeros, (el, d))

        def ffn(xin, w1, b1, w2, b2):
            h = jnp.einsum("ecd,edh->ech", xin.astype(self.dtype),
                           w1.astype(self.dtype)) + b1[:, None].astype(
                               self.dtype)
            return jnp.einsum("ech,ehd->ecd", nn.gelu(h),
                              w2.astype(self.dtype)) + b2[:, None].astype(
                                  self.dtype)

        if self.ep_axis is not None:
            # Inside shard_map: this device is ONE group (g == 1) and holds
            # el = e / n experts.
            n = jax.lax.axis_size(self.ep_axis)
            if el * n != e:
                raise ValueError(f"n_local_experts={el} x {n} devices != "
                                 f"{e} experts")
            with device_scope("moe_dispatch"):
                ein = expert_in[0]                    # [E, C, D]
                ein = jax.lax.all_to_all(ein, self.ep_axis, split_axis=0,
                                         concat_axis=1, tiled=True)
            with device_scope("moe_experts"):
                out = ffn(ein, w1, b1, w2, b2)        # [E/n, n*C, D]
            with device_scope("moe_dispatch"):
                out = jax.lax.all_to_all(out, self.ep_axis, split_axis=1,
                                         concat_axis=0, tiled=True)
                expert_out = out[None]                # [1, E, C, D]
        else:
            with device_scope("moe_experts"):
                expert_out = jax.vmap(
                    ffn, in_axes=(0, None, None, None, None))(
                    expert_in, w1, b1, w2, b2)        # [G, E, C, D]

        with device_scope("moe_dispatch"):
            y = jnp.einsum("gtec,gecd->gtd", combine,
                           expert_out.astype(jnp.float32))
            y = y.reshape(b, s, d).astype(x.dtype)

        # Load-balance loss over FIRST choices (switch eq. 4; GShard uses
        # the same first-choice fractions), per group then averaged.
        with device_scope("moe_route"):
            frac_tokens = jnp.mean(oh0_g, axis=1)     # [G, E]
            frac_probs = jnp.mean(probs.reshape(g, tg, e), axis=1)
            aux = e * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
        return y, aux


# Rows the held experts' part of a layer is sized for, over what they get at
# balance (T * k * held / E): a block of experts that draws more than this
# takes the overflow path below, which is slower and drops nothing.
HELD_ROWS_SLACK = 1.5
HELD_ROWS_TILE = 512      # ... in whole multiples of this many rows
_GATE_EPS = 1e-20         # under the sum of sigmoid gates (torchtitan's)


def held_rows(assignments: int, held: int, experts: int) -> int:
    """Rows the main part of a layer with ``held`` of ``experts`` experts is
    sized for, of ``assignments`` (T * k): all of them, or the held block's
    balanced share with ``HELD_ROWS_SLACK``, in whole row tiles."""
    if held == experts:
        return assignments
    return min(assignments,
               -(-int(HELD_ROWS_SLACK * assignments * held / experts)
                 // HELD_ROWS_TILE) * HELD_ROWS_TILE)


def _when(pred, fn, args, ints):
    """``fn(*args, *ints)`` where ``pred``, else zeros of its shape, with
    neither pass run nor any residual kept where it is false: the backward
    pass is a ``cond`` of its own over ``jax.vjp(fn)`` in ``args`` (float
    arrays; ``ints`` carry no gradient), from the arguments alone. (A
    ``cond`` differentiated by JAX keeps both branches' residuals, zeros for
    the one not taken: the full-size buffers this exists to avoid.) Both
    ``cond`` ops and their zeros (which XLA hoists out of the branch) stand
    for a part's combine and are under its device scope; the scopes ``fn``
    opens inside are the innermost: the one place where scopes nest."""
    @jax.custom_vjp
    def run(pred, args, ints):
        out = jax.eval_shape(fn, *args, *ints)
        with device_scope("moe_dispatch"):
            return jax.lax.cond(
                pred, lambda: fn(*args, *ints),
                lambda: jnp.zeros(out.shape, out.dtype))

    def fwd(pred, args, ints):
        return run(pred, args, ints), (pred, args, ints)

    def bwd(res, ct):
        pred, args, ints = res
        with device_scope("moe_dispatch"):
            grads = jax.lax.cond(
                pred,
                lambda: jax.vjp(lambda *a: fn(*a, *ints), *args)[1](ct),
                lambda: tuple(jnp.zeros_like(a) for a in args))
        return None, grads, None

    run.defvjp(fwd, bwd)
    return run(pred, tuple(args), tuple(ints))


# A layer that holds every expert moves its rows by gathers, forward AND
# backward. JAX transposes a gather into a scatter-add, which the TPU runs row
# by row (v5e, PR 38: 72 ns a row of 2048 against a gather's 34); the transpose
# of a PERMUTATION's gather is the gather by the inverse permutation, which
# only the caller knows. ``order`` [T*k] is the sorted assignments (an
# assignment being token * k + choice) and ``inv`` [T, k] = ``argsort(order)``
# the sorted place of each. (A held share's part covers fewer rows than there
# are assignments, and a gather of T*k rows of which most are not there lost
# to the scatter-add of the few that are (PR 38); since PR 52 that part's
# sums are ``ops/moe_rows.py``'s kernel, which follows the sort's runs, and
# its takes XLA's gathers of the part's rows: ``DroplessMoE.part``.)

@jax.custom_vjp
def _rows_in(tokens, order, inv):
    """The token row of every sorted assignment: [T*k, D]."""
    return tokens[order // inv.shape[1]]


def _rows_in_fwd(tokens, order, inv):
    return _rows_in(tokens, order, inv), inv


def _rows_in_bwd(inv, ct):
    # a token's k rows, summed in float32 (the tokens' dtype at the least)
    rows = ct[inv].astype(jnp.promote_types(ct.dtype, jnp.float32))
    return jnp.sum(rows, axis=1).astype(ct.dtype), None, None


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@jax.custom_vjp
def _rows_out(out, gates, order, inv):
    """The combine: ``y[t] = sum_j gates[t * k + j] * out[inv[t, j]]``,
    float32 [T, D], from the experts' output ``out`` [T*k, D] in sorted order
    and the assignments' ``gates`` [T*k] (flat: a float32 [T, 8] pads to 128
    lanes on the TPU)."""
    return jnp.sum(out[inv].astype(jnp.float32)
                   * gates.reshape(inv.shape)[..., None], axis=1)


def _rows_out_fwd(out, gates, order, inv):
    return _rows_out(out, gates, order, inv), (out, gates, order, inv)


def _rows_out_bwd(res, ct):
    out, gates, order, inv = res
    ct_rows = ct[order // inv.shape[1]]               # [T*k, D] float32
    d_out = ct_rows * gates[order][:, None]
    # a gate's gradient where its row sits, then taken back to its assignment
    d_gate = jnp.sum(ct_rows * out.astype(jnp.float32), axis=-1)
    return (d_out.astype(out.dtype),
            d_gate[inv.reshape(-1)].astype(gates.dtype), None, None)


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _top_k(scores, bias, k):
    """``(gates, idx)`` [T, k]: the ``k`` largest of each row of ``scores``
    [T, E], or of ``scores + bias`` under a ``bias`` [E], which chooses and
    does not weigh (``gates`` stay ``scores``'s own, and no gradient reaches
    it). Backward each gate's gradient goes to its column by a comparison
    with the columns' numbers: T * k * E compares in one reduce, where the
    transpose of ``lax.top_k``'s gather scatters."""
    if bias is None:
        gates, idx = jax.lax.top_k(scores, k)
        return gates, idx
    _, idx = jax.lax.top_k(scores + bias, k)
    return jnp.take_along_axis(scores, idx, axis=-1), idx


def _top_k_fwd(scores, bias, k):
    gates, idx = _top_k(scores, bias, k)
    # both: a rematerialised block then runs no ``top_k`` a second time
    gates, idx = kept(gates, "moe_gates"), kept(idx, "moe_idx")
    return (gates, idx), (idx, jnp.arange(scores.shape[-1], dtype=idx.dtype))


def _top_k_bwd(k, res, cts):
    idx, columns = res
    hit = idx[..., None] == columns                           # [T, k, E]
    return jnp.sum(jnp.where(hit, cts[0][..., None], 0), axis=-2), None


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


class DroplessMoE(nn.Module):
    """Dropless top-k MoE FFN with gated experts, OLMoE's by default.

        r = h Wr (float32, highest precision)    p = softmax(r)
        I = top-k of p,  g_i = p_i     (``gate_norm``: g_i = p_i / sum_I p)
        y = sum_{i in I} g_i * (act(h Wgate_i) * (h Wup_i)) Wdown_i

    with ``act`` SiLU (SwiGLU), ReLU or its square (not ``gated``: ``act(h
    Wup_i) Wdown_i``, two grouped matmuls, no ``experts_gate`` and
    ``experts_up`` stored transposed, [held, f, d]), and the
    router reading ``router_x`` where the caller hands one (an arch whose router sits before attention)
    in place of ``h``. ``score`` "sigmoid": p = sigmoid(r), each output by
    itself. ``select_bias``: I = top-k of p + b, with b the layer's
    ``expert_bias`` [E] in the ``MOE_STATE`` collection: it chooses and does
    not weigh (g stays p's), no gradient reaches it, and the train step moves
    it (``update_expert_bias``) from the counts this layer hands out under
    ``EXPERT_COUNTS``. ``route_scale`` multiplies the gates.

    The T*k assignments are sorted by expert (stable: inside an expert the
    order is the token order), the rows gathered, the three matmuls run as
    grouped matmuls over the ragged groups (``ops/grouped_matmul.gmm``), and
    each token gathers its k rows back through the sort's inverse, scaled by
    its gates and summed in float32. With every expert held each movement of
    rows is a gather, forward and backward (``_rows_in``, ``_rows_out``: the
    transpose of a permutation's gather is the gather by its inverse, where
    JAX's own derivative would scatter-add); the gates' gradient goes back to
    the scores by a comparison (``_top_k``) and the counts are comparisons
    too: that layer holds no scatter, which the TPU runs row by row. Nothing
    here is sized T x E x anything but the router's own logits and
    probabilities and those comparisons, and no assignment is ever dropped.

    **A share of the experts** (``n_held`` < ``n_experts``; expert
    parallelism's layer, here without its exchange): the module holds the
    contiguous block ``share * n_held .. (share + 1) * n_held - 1``. The
    router, its softmax, the top-k and the gates stay ``n_experts`` wide; an
    assignment to an expert not held sorts past the last held group, is not
    multiplied and adds nothing, so ``y`` is this block's part of the layer's
    result (the shares' parts add up to the whole). Take, grouped matmuls
    and combine run over the first ``HELD_ROWS_SLACK * T*k * n_held /
    n_experts`` sorted rows. The combine and the take's transpose are ONE
    Pallas kernel (``ops/moe_rows.py``, ``moe_rows_sum``: the sort is stable,
    so a held expert's rows for a tile of tokens are one run, fetched by DMA
    in segments and picked out and gated on the MXU in float32; where the
    sort put each (token, held expert) is counted from the choices, no second
    sort; the rows past the held groups' count are never fetched), with no
    scatter of rows, no float32 ``[rows, d]`` product and no zeros in HBM.
    The take and the cotangent's take are XLA's gathers over the part's rows
    (Mosaic fetches no single row of a tiled array by its index: PERF.md,
    Findings PR 52), and the gates' gradients go back to ``[T, k]`` by the
    one scatter of scalars a part has left. Where the block drew more, the
    rows past them go through the same three steps under a ``cond``
    (``_when``): nothing held is dropped, whatever the router does.

    Returns ``(y, stats)`` with ``stats`` keyed by ``DROPLESS_STATS``:
    ``aux`` = E * sum_e f_e P_e over ALL k choices and all E router outputs
    (f_e = assignments to e / T, so sum_e f_e = k; P_e the mean router
    probability — what HF's ``load_balancing_loss_func`` computes),
    ``z_loss`` = mean logsumexp(r)^2, ``expert_load_max_over_mean`` = busiest
    HELD expert's assignments / (T*k/E), ``moe_held_share`` = assignments to
    held experts / (T*k), ``moe_dropped`` = held assignments whose output was
    not added (counted from the combine's own indices; 0 by construction),
    ``moe_tail_rows_share`` = rows of the main part that no held group owns /
    rows it is sized for: the share of the grouped matmuls' row tiles that are
    visited and not multiplied (0 where every expert is held); with
    ``load_all_stat`` also ``moe_load_all_max_over_mean`` = the busiest of ALL
    E outputs' assignments / (T*k/E): it tells a router that drifts towards
    the held experts from one that is unbalanced everywhere.
    """
    n_experts: int
    d_model: int
    d_hidden: int
    top_k: int = 8
    dtype: Any = jnp.float32
    act: str = "silu"
    gate_norm: bool = False
    n_held: int = 0                   # experts held here (0 = all)
    share: int = 0                    # which block of n_held, 0-based
    down_std: float = 0.0             # experts_down init: normal(std) | lecun_normal
    score: str = "softmax"            # softmax | sigmoid
    select_bias: bool = False         # top-k of score + expert_bias (MOE_STATE)
    route_scale: float = 1.0
    gated: bool = True                # False: act(h Wup_i) Wdown_i, no gate projection
    load_all_stat: bool = False       # stats also hold LOAD_ALL_STAT: the busiest of ALL E outputs over the mean

    @nn.compact
    def __call__(self, x, router_x=None):
        b, s, d = x.shape
        e, k, f = self.n_experts, self.top_k, self.d_hidden
        held = self.n_held or e
        if not 1 <= k <= e:
            raise ValueError(f"top_k={k} must be in 1..n_experts={e}")
        if e % held or not 0 <= self.share < e // held:
            raise ValueError(f"n_held={held} must divide n_experts={e} and "
                             f"share={self.share} name one of its blocks")
        # The router is d x E: float32 under `highest` costs nothing, and a
        # bf16-grade pass flips ties between the k-th and (k+1)-th expert.
        with device_scope("moe_route"):
            tokens = x.reshape(-1, d)                 # [T, D]
            t = tokens.shape[0]
            router_in = tokens if router_x is None \
                else router_x.reshape(-1, d)
            router = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              name="router")(router_in.astype(jnp.float32))
            if self.score == "sigmoid":
                probs = jax.nn.sigmoid(router)        # [T, E] float32
            else:
                probs = jax.nn.softmax(router, axis=-1)
            bias = self.variable(
                MOE_STATE, "expert_bias", jnp.zeros, (e,), jnp.float32
            ).value if self.select_bias else None
            gates, idx = _top_k(probs, bias, k)       # [T, k]
            if self.gate_norm:
                total = jnp.sum(gates, axis=-1, keepdims=True)
                if self.score == "sigmoid":
                    total = total + _GATE_EPS   # the scores do not sum to 1
                gates = gates / total
            if self.route_scale != 1.0:
                gates = gates * self.route_scale

        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("experts_gate", init, (held, d, f)) \
            if self.gated else None
        # Without a gate the up projection is stored [held, f, d], as a
        # checkpoint stores a linear layer, and multiplied by ``gmm_t``: an
        # expert width off the 128 lanes (1856) can be the last dimension of
        # rows, not of weights that a kernel fetches by its own DMA.
        w_up = self.param("experts_up", init, (held, d, f)) if self.gated \
            else self.param("experts_up", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=-1, out_axis=-2,
                batch_axis=(0,)), (held, f, d))
        w_down = self.param(
            "experts_down", nn.initializers.normal(self.down_std)
            if self.down_std else init, (held, f, d))
        act = ACTS[self.act]

        # Assignment a = token * k + choice; sorted by expert, stable. One
        # to an expert not held takes the key past the last held group.
        with device_scope("moe_route"):
            flat_e = idx.reshape(-1)                  # [T*k]
            load = kept(jnp.sum(
                flat_e[:, None] == jnp.arange(e, dtype=flat_e.dtype),
                axis=0, dtype=jnp.int32), "moe_load")
            first = self.share * held
            group_sizes = load[first:first + held]
            key = flat_e if held == e else jnp.where(
                (flat_e >= first) & (flat_e < first + held),
                flat_e - first, held)
            order = kept(jnp.argsort(key, stable=True), "moe_order")
            n_held_rows = jnp.sum(group_sizes)

        def part(tokens, gates, *rest, inv=None, add=None):
            """The rows ``order`` (sorted assignments), the first
            ``sum(sizes)`` of which the groups cover: taken, through the
            experts, gated, and summed into their tokens. Where ``order``
            holds every assignment and ``inv`` [T, k] their sorted places both
            movements are gathers (``_rows_in``, ``_rows_out``); a held share's
            part takes by XLA's gather and sums by ``ops/moe_rows.py``'s
            kernel, forward and backward, over the rows before its count.
            ``rest``: the experts' weights (``weights`` below), then ``order``,
            ``sizes`` and, for a held share, ``local`` [T, k] (the choices as
            held experts' numbers), ``has``, ``place``, ``lo``
            (``moe_rows.held_places``) and ``span`` = (the part's first
            sorted place, its count of held rows). Float32 [T, D]; with
            ``add`` (the other part's) that added in float32 too and the sum
            rounded once, to the tokens' dtype."""
            if inv is None:
                *rest, local, has, place, lo, span = rest
                sched = rows_sched(rest[-2].shape[0])
                with device_scope("moe_route"):
                    plan = moe_rows.rows_plan(has, place, lo, start=span[0],
                                              count=span[1])
            *w_in, w_down, order, sizes = rest
            with device_scope("moe_dispatch"):
                xs = moe_rows.take(tokens, order, plan, k, sched) \
                    if inv is None else _rows_in(tokens, order, inv)
                xs = xs.astype(self.dtype)            # [rows, D]
            # The float32 expert weights go to the kernels as they are: a
            # tile is cast to the rows' dtype in VMEM, and the weight gradient
            # comes back float32 from the float32 accumulator
            # (ops/grouped_matmul.py).
            with device_scope("moe_experts"):
                if self.gated:
                    h = act(gmm(xs, w_in[0], sizes)) * gmm(xs, w_in[1], sizes)
                else:
                    h = act(gmm_t(xs, w_in[0], sizes))
                out = gmm(h, w_down, sizes)
            with device_scope("moe_dispatch"):
                if inv is not None:
                    return _rows_out(out, gates.reshape(-1), order, inv)
                return moe_rows.combine(
                    out, gates, add, local, order, plan, sched,
                    jnp.float32 if add is None else tokens.dtype)

        rows = held_rows(t * k, held, e)    # the main part's
        weights = (w_gate, w_up, w_down) if self.gated else (w_up, w_down)

        def rows_sched(n):      # of a held share's part of n sorted rows
            return moe_rows.rows_schedule(n, 0, t, k, d, self.dtype, held)

        if rows == t * k:
            with device_scope("moe_route"):       # the sort's inverse
                inv = kept(jnp.argsort(order), "moe_inv").reshape(t, k)
            y = part(tokens, gates, *weights, order, group_sizes, inv=inv)
        else:
            with device_scope("moe_route"):
                ends = jnp.minimum(jnp.cumsum(group_sizes), rows)
                sizes_main = jnp.diff(ends, prepend=0)
                main = order[:rows]
                # where the sort put each (token, held expert), by counting
                local = idx - first
                has, _ = moe_rows.held_tables(local, gates, held)
                place, lo = moe_rows.held_places(
                    has, group_sizes, rows_sched(rows).tokens_tile)
                routing = (local, has, place, lo)
                in_main = jnp.minimum(n_held_rows, rows)
                overflows = n_held_rows > rows
                over, sizes_over = order[rows:], group_sizes - sizes_main
                span_over = jnp.stack([jnp.full_like(in_main, rows),
                                       n_held_rows - in_main])
            extra = _when(overflows, part, (tokens, gates, *weights),
                          (over, sizes_over, *routing, span_over))
            y = part(tokens, gates, *weights, main, sizes_main, *routing,
                     jnp.stack([jnp.zeros_like(in_main), in_main]), add=extra)

        # Counters, off the gradient path. An assignment's output is added
        # where the combine's own index for it falls on a row the grouped
        # matmul covered, the first sum(sizes) of a part: the sorted place the
        # gather or the kernel reads (the overflow part's, run or not run as
        # a whole, by their number).
        with device_scope("moe_route"):
            if rows == t * k:
                added = jnp.sum(inv < n_held_rows, dtype=jnp.int32)
            else:
                added = jnp.sum(has & (place < in_main), dtype=jnp.int32) \
                    + n_held_rows - in_main
            stats = {
                "aux": e * jnp.sum((load.astype(jnp.float32) / t)
                                   * jnp.mean(probs, axis=0)),
                "z_loss": jnp.mean(jax.nn.logsumexp(router, axis=-1) ** 2),
                "expert_load_max_over_mean":
                    jnp.max(group_sizes).astype(jnp.float32) * e / (t * k),
                "moe_dropped": (n_held_rows - added).astype(jnp.float32),
                "moe_held_share": n_held_rows.astype(jnp.float32) / (t * k),
                "moe_tail_rows_share": 1.0 - jnp.minimum(
                    n_held_rows, rows).astype(jnp.float32) / rows,
            }
            if self.load_all_stat:
                stats[LOAD_ALL_STAT] = \
                    jnp.max(load).astype(jnp.float32) * e / (t * k)
        if self.select_bias:
            stats[EXPERT_COUNTS] = {"expert_bias": load}
        with device_scope("moe_dispatch"):
            y = y.reshape(b, s, d).astype(x.dtype)
        return y, stats


class MoEBlock(nn.Module):
    """transformer.Block with the dense MLP swapped for an expert layer
    (``MoEMLP`` or ``DroplessMoE``, by the arch; with the arch's shared experts
    beside the routed part), or, where ``dense_ffn_dim`` is set, for a
    ``GatedFFN`` of that width: one of a dropless model's leading dense layers,
    whose ``aux`` is None. The first half is the mixer of the layer's kind
    (``Arch.layer_kind``): attention, or a Gated DeltaNet layer, whose counter
    (``COUNTER_NAMES``) a dropless layer's ``aux`` carries to the model. Under
    an arch with a ``layer_pattern`` the block is ONE of the halves: a mixer
    alone (attention or Mamba-2), whose ``aux`` is what it counted ({} for
    attention), or the expert layer alone ("experts").

    **A share of the mixers** (``mixer_shares`` > 1; tensor parallelism's
    block, here without its exchange): the block holds share 0 of that many
    of its mixer's heads (a Mamba-2 layer's ``ssm_heads / mixer_shares`` with
    B and C whole, ``n_heads / mixer_shares`` query heads on ``kv_heads /
    mixer_shares`` key/value heads) and of the shared expert's channels,
    column-parallel in and row-parallel out, so each half's result is this
    chip's PART of the sublayer's output, as the held experts' is. With
    ``mixer_axis`` bound (inside a ``shard_map`` over that mesh axis, every
    device with its own share's parameters) the mixer's and the shared
    expert's parts, and a Mamba-2 layer's norm statistic, are summed over it;
    with none nothing is, no collective runs, and what the absent chips would
    add is left out. (The routed experts' part stays this block's own either
    way: their exchange is not built.) ``n_heads`` and ``kv_heads`` stay the
    model's counts."""
    n_heads: int
    d_model: int
    n_experts: int
    capacity_factor: float = 1.25
    n_groups: int = 1
    ep_axis: Optional[str] = None
    n_local_experts: Optional[int] = None
    top_k: int = 1
    attention_impl: str = "full"      # "full" | "flash" (seq is never sharded here)
    dtype: Any = jnp.float32
    # Autoregressive decode (models/generate.py): cached attention, one
    # token per call. The MoE dispatch runs with n_groups = B (each
    # decoded token its own capacity group): top_k experts per token are
    # distinct, each claims slot 0 of its expert within its own group, so
    # decode NEVER drops an assignment and batch rows decode
    # independently — with one shared group, two rows routing to the same
    # expert at cap=1 would silently zero one row's MLP output. The
    # batched training forward CAN drop (capacity overflow); decode ==
    # training forward exactly when that forward dropped nothing.
    decode: bool = False
    decode_cache_len: int = 0
    arch: str = "gpt2"                # ARCHS row: attention half AND which MoE FFN
    ffn_dim: int = 0                  # expert width (0 = 4 * d_model)
    layer: int = 0                    # index in the stack (the layer's kind)
    kv_heads: int = 0                 # key/value heads (0 = n_heads)
    head_dim: int = 0                 # 0 = d_model / n_heads
    experts_held: int = 0             # dropless: experts held here (0 = all)
    experts_share: int = 0            # ... which block of them, 0-based
    dense_ffn_dim: int = 0            # > 0: no experts here, a GatedFFN of this width
    mixer_shares: int = 1             # chips a layer's mixers and shared expert are divided over
    mixer_axis: Optional[str] = None  # the mesh axis those shares lie along, where one is bound

    def _held(self, count: int, what: str) -> int:
        """This block's share of ``count`` heads or channels."""
        if count % self.mixer_shares:
            raise ValueError(
                f"mixer_shares={self.mixer_shares} does not divide the "
                f"{count} {what} of lm_arch={self.arch}")
        return count // self.mixer_shares

    @nn.compact
    def __call__(self, x, positions=None):
        b, s, d = x.shape
        a = ARCHS[self.arch]
        counted, normed = {}, None
        kind = a.layer_kind(self.layer)
        if self.mixer_shares > 1 and (kind == "gdn" or a.ssm_groups > 1):
            raise NotImplementedError(
                f"--lm-arch {self.arch}: a share of the mixers is built for "
                f"attention and for Mamba-2 layers whose B and C are ONE "
                f"group (a share of linear-attention heads, or of the heads "
                f"of {a.ssm_groups} groups, is not)")
        if self.decode and kind in ("gdn", "mamba2"):
            refuse_hybrid(self.arch, "decode")
        if kind == "gdn":
            x, normed, counted = gdn_sublayer(
                self, x, make_norm(self.arch, self.dtype), dtype=self.dtype,
                key_heads=a.gdn_key_heads, value_heads=a.gdn_value_heads,
                key_dim=a.gdn_key_dim, value_dim=a.gdn_value_dim,
                conv=a.gdn_conv, norm_eps=a.norm_eps)
        elif kind == "mamba2":
            x, normed, counted = mamba2_sublayer(
                self, x, make_norm(self.arch, self.dtype), dtype=self.dtype,
                heads=self._held(a.ssm_heads, "Mamba-2 heads"),
                head_dim=a.ssm_head_dim,
                groups=a.ssm_groups, d_state=a.ssm_state, d_conv=a.ssm_conv,
                chunk=a.ssm_chunk, norm_eps=a.norm_eps,
                out_scale=a.residual_scale, axis_name=self.mixer_axis)
        elif kind != "experts":
            x, normed, _ = attention_sublayer(
                self, x, positions, arch=self.arch,
                n_heads=self._held(self.n_heads, "query heads"),
                dtype=self.dtype, attention_impl=self.attention_impl,
                decode=self.decode, decode_cache_len=self.decode_cache_len,
                layer=self.layer,
                kv_heads=self._held(self.kv_heads or self.n_heads,
                                    "key/value heads"),
                head_dim=self.head_dim or d // self.n_heads,
                mixer_axis=self.mixer_axis)
        if a.layer_pattern and kind != "experts":
            return x, counted           # a mixer alone
        # The block's second half is a dense layer's scope or an expert
        # layer's three: the norms and the residual sum go to the first and
        # the last of them.
        enter, leave = ("ffn", "ffn") if self.dense_ffn_dim \
            else ("moe_route", "moe_dispatch")
        with device_scope(enter):
            y = make_norm(self.arch, self.dtype)(x)
        width = self.ffn_dim or 4 * self.d_model
        if self.dense_ffn_dim:
            with device_scope("ffn"):
                m, aux = GatedFFN(self.dense_ffn_dim, self.dtype,
                                  a.expert_act, name="mlp")(y), None
        elif a.dropless:
            m, aux = DroplessMoE(self.n_experts, self.d_model, width,
                                 top_k=self.top_k, dtype=self.dtype,
                                 act=a.expert_act, gate_norm=a.gate_norm,
                                 n_held=self.experts_held,
                                 share=self.experts_share,
                                 down_std=a.expert_down_std,
                                 score=a.router_score,
                                 select_bias=a.router_bias_rate > 0,
                                 route_scale=a.route_scale,
                                 gated=a.expert_gated,
                                 load_all_stat=a.load_all_stat
                                 and not a.router_bias_rate, name="moe")(
                y, normed if a.early_router else None)
            if EXPERT_COUNTS in aux:
                aux[EXPERT_COUNTS] = {"moe": aux[EXPERT_COUNTS]}
            shared_width = a.shared_width or a.shared_experts * width
            if shared_width:
                # every token's, whatever share of the routed experts is
                # held; of its channels the block's share of the mixers
                with device_scope("moe_shared"):
                    shared = GatedFFN(
                        self._held(shared_width, "shared channels"),
                        self.dtype, a.expert_act, a.expert_gated,
                        name="shared")(y)
                    if self.mixer_axis is not None:
                        shared = jax.lax.psum(shared, self.mixer_axis)
                    if a.shared_gate:
                        shared = shared * nn.sigmoid(nn.Dense(
                            1, use_bias=False, dtype=self.dtype,
                            name="shared_gate")(y))
                    m = m + shared
        else:
            m, aux = MoEMLP(self.n_experts, self.d_model,
                            self.ffn_dim or 4 * self.d_model,
                            capacity_factor=self.capacity_factor,
                            n_groups=(b * s) if self.decode else self.n_groups,
                            ep_axis=self.ep_axis,
                            n_local_experts=self.n_local_experts,
                            top_k=self.top_k, dtype=self.dtype,
                            name="moe")(y)
        with device_scope(leave):
            if a.post_norm:     # its backward reads its input (``remat_block``)
                m = make_norm(self.arch, self.dtype, name="post_mlp_norm")(
                    kept(m, "mlp_out"))
            if a.residual_scale != 1.0:
                m = m * jnp.asarray(a.residual_scale, self.dtype)
            x = x + m
        if counted:
            # what the mixer counted rides in a dropless layer's statistics
            if not isinstance(aux, dict):
                raise NotImplementedError(
                    f"--lm-arch {self.arch}: block {self.layer}'s mixer counts "
                    f"{sorted(counted)}, and only an expert layer of a "
                    f"dropless arch hands counters on")
            aux.update(counted)
        return x, aux


class MoETransformerLM(nn.Module):
    """Decoder-only LM with an MoE MLP in every block, after ``dense_layers``
    leading blocks with a dense gated feed-forward of ``dense_ffn_dim``
    (dropless archs).

    Returns (logits [B, S, V] in ``dtype``; the loss casts them to float32,
    aux): for a capacity arch the
    scalar sum of the layers' load-balance losses; for a dropless arch a dict
    keyed by ``DROPLESS_STATS`` over the expert layers (``aux`` and ``z_loss``
    averaged over layers, the busiest layer's ``expert_load_max_over_mean``,
    ``moe_dropped`` summed, ``moe_held_share`` and ``moe_tail_rows_share``
    averaged) and, where the arch
    chooses under a bias, ``EXPERT_COUNTS``: each layer's assignments to every
    router output, a tree of the ``MOE_STATE`` collection's shape. What the
    mixers count (``COUNTER_NAMES``: a linear-attention layer's
    ``gdn_state_abs_max``) is sown in ``LM_COUNTERS``, the largest over the
    layers, as ``TransformerLM`` does. With the arch's ``tied_head`` the
    logits are ``ln_f(x) . tok_embed^T``, over its ``logits_divisor``."""
    vocab_size: int = 256
    n_layers: int = 2
    n_heads: int = 4
    d_model: int = 128
    n_experts: int = 8
    capacity_factor: float = 1.25
    n_groups: int = 1
    max_seq_len: int = 2048
    ep_axis: Optional[str] = None
    n_local_experts: Optional[int] = None
    top_k: int = 1                    # 1 = switch, 2 = GShard; dropless: 1..n_experts
    attention_impl: str = "full"      # "full" | "flash"
    arch: str = "gpt2"                # ARCHS row (models/transformer.py)
    ffn_dim: int = 0                  # expert width (0 = 4 * d_model)
    kv_heads: int = 0                 # key/value heads (0 = n_heads)
    head_dim: int = 0                 # 0 = d_model / n_heads
    experts_held: int = 0             # dropless: experts held here (0 = all)
    experts_share: int = 0            # ... which block of them, 0-based
    dense_layers: int = 0             # leading blocks with a dense gated FFN
    dense_ffn_dim: int = 0            # ... of this width (0 = 4 * d_model)
    mixer_shares: int = 1             # chips a layer's mixers and shared expert are divided over (this model holds share 0)
    mixer_axis: Optional[str] = None  # the mesh axis those shares lie along, where one is bound (MoEBlock)
    # Per-block remat (see models/transformer.py TransformerLM.remat); the
    # recompute replays the block's all_to_alls, which is SPMD-legal.
    remat: bool = False
    dtype: Any = jnp.float32
    # Autoregressive decode (see MoEBlock.decode).
    decode: bool = False
    decode_cache_len: int = 0

    @nn.compact
    def __call__(self, tokens, positions: Optional[jax.Array] = None):
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        x = embed_tokens(tokens, positions, arch=self.arch,
                         vocab_size=self.vocab_size, d_model=self.d_model,
                         max_seq_len=self.max_seq_len, dtype=self.dtype)
        Blk = remat_block(MoEBlock) if (self.remat and not self.decode) \
            else MoEBlock
        per_layer = []
        for i in range(self.n_layers):
            x, aux = Blk(self.n_heads, self.d_model, self.n_experts,
                         capacity_factor=self.capacity_factor,
                         n_groups=self.n_groups, ep_axis=self.ep_axis,
                         n_local_experts=self.n_local_experts,
                         top_k=self.top_k,
                         attention_impl=self.attention_impl,
                         dtype=self.dtype, decode=self.decode,
                         decode_cache_len=self.decode_cache_len,
                         arch=self.arch, ffn_dim=self.ffn_dim, layer=i,
                         kv_heads=self.kv_heads, head_dim=self.head_dim,
                         experts_held=self.experts_held,
                         experts_share=self.experts_share,
                         dense_ffn_dim=(self.dense_ffn_dim or 4 * self.d_model)
                         if i < self.dense_layers else 0,
                         mixer_shares=self.mixer_shares,
                         mixer_axis=self.mixer_axis,
                         name=f"block_{i}")(x, positions)
            if aux is not None:
                per_layer.append((f"block_{i}", aux))
        with device_scope("moe_route"):     # the layers' statistics as one
            if ARCHS[self.arch].dropless:
                # (a block that is a mixer alone has its counters only)
                routed = [(n, a) for n, a in per_layer if "aux" in a]
                if not routed:
                    raise ValueError(
                        f"--lm-arch {self.arch} at depth {self.n_layers} "
                        f"holds no expert layer, and the ep step trains an "
                        f"expert stack")
                aux_total = {k: over(jnp.stack([a[k] for _, a in routed]))
                             for k, over in _OVER_LAYERS.items()}
                if ARCHS[self.arch].router_bias_rate:
                    aux_total[EXPERT_COUNTS] = {name: a[EXPERT_COUNTS]
                                                for name, a in routed}
                elif ARCHS[self.arch].load_all_stat:
                    aux_total[LOAD_ALL_STAT] = jnp.max(jnp.stack(
                        [a[LOAD_ALL_STAT] for _, a in routed]))
                # what the mixers counted rode in their layers' statistics
                for k in COUNTER_NAMES:
                    vs = [a[k] for _, a in per_layer if k in a]
                    if vs:      # a no-op unless the caller asks
                        self.sow(LM_COUNTERS, k, jnp.max(jnp.stack(vs)))
            else:
                aux_total = jnp.float32(0.0)
                for _, aux in per_layer:
                    aux_total = aux_total + aux
        a = ARCHS[self.arch]
        with device_scope("head"):
            x = make_norm(self.arch, self.dtype, name="ln_f")(x)
            if a.tied_head:
                # the embedding's rows are the head's columns: one parameter,
                # which receives both gradients (as ``TransformerLM``'s)
                table = self.variables["params"]["tok_embed"]["embedding"]
                logits = jax.lax.dot_general(
                    x, table.astype(self.dtype), (((2,), (1,)), ((), ())))
            else:
                logits = nn.Dense(self.vocab_size, use_bias=False,
                                  dtype=self.dtype, name="lm_head")(x)
            if a.logits_divisor != 1.0:
                logits = logits / jnp.asarray(a.logits_divisor, self.dtype)
        return logits, aux_total
