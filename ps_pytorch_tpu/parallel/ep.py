"""Expert parallelism (EP) — MoE experts sharded over the mesh.

Beyond-parity (the reference has no MoE/EP, SURVEY §2.5; with dp/tp/pp/sp/
zero this closes the full DP/TP/PP/SP/EP/ZeRO inventory): the stacked
expert FFNs of ``models/moe.MoETransformerLM`` shard their leading
[n_experts] axis over the mesh's 'data' axis — the DeepSpeed-MoE layout
where the EP group IS the DP group: every device holds its batch shard AND
n_experts/n experts. Token routing crosses devices with one pair of
``all_to_all`` collectives per MoE layer (dispatch slots out, expert
outputs back), executed INSIDE the layer when ``ep_axis`` is bound — the
same inside-the-module collective pattern as ring attention.

Gradient structure mirrors ``parallel/pp.py``: the loss is a LOCAL sum
(never psum inside the differentiated function — the double-count pitfall),
expert-parameter grads are complete per-device via the all_to_all
transpose (every token that visited the expert contributes, wherever it
came from), and replicated params (router, attention, embeddings) need one
psum over 'data'.

Exactness: dispatch capacity is accounted per device; the unsharded oracle
with ``n_groups = n_devices`` computes the identical math, so
sharded-vs-unsharded equivalence is exact (tests/test_ep.py), not
statistical.
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import keystr, tree_flatten_with_path

from ps_pytorch_tpu.models.moe import (
    EXPERT_COUNTS, MOE_STATE, lm_variables, update_expert_bias,
)
from ps_pytorch_tpu.models.transformer import (
    ARCHS, LM_COUNTERS, lm_counters,
)
from ps_pytorch_tpu.ops.next_token_loss import next_token_loss
from ps_pytorch_tpu.parallel.dp import TrainState
from ps_pytorch_tpu.parallel.tp import _opt_state_specs
from ps_pytorch_tpu.telemetry.trace import device_scope

_EXPERT_KEY = "experts_"   # models/moe.py stacked expert param names
# How each of the model's routing statistics crosses the data axis.
_STAT_REDUCE = {"aux": jax.lax.pmean, "z_loss": jax.lax.pmean,
                "expert_load_max_over_mean": jax.lax.pmax,
                "moe_dropped": jax.lax.psum, "moe_held_share": jax.lax.pmean,
                "moe_tail_rows_share": jax.lax.pmean,
                "moe_load_all_max_over_mean": jax.lax.pmax}


def ep_param_specs(params, axis: str = "data"):
    """Stacked expert leaves shard over ``axis``; everything else
    replicates."""
    paths, treedef = tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [P(axis) if _EXPERT_KEY in keystr(p) else P()
                  for p, _ in paths])


def ep_state_specs(state_shapes: TrainState, axis: str = "data") -> TrainState:
    pspecs = ep_param_specs(state_shapes.params, axis)
    return TrainState(
        step=P(),
        params=pspecs,
        opt_state=_opt_state_specs(state_shapes.opt_state,
                                   state_shapes.params, pspecs),
        # the model's MOE_STATE collection ({} for most archs): replicated
        batch_stats=jax.tree.map(lambda _: P(), state_shapes.batch_stats),
    )


def create_ep_train_state(model, tx: optax.GradientTransformation,
                          mesh: Mesh, sample_tokens,
                          rng: Optional[jax.Array] = None,
                          axis: str = "data") -> TrainState:
    """Init the MoE LM with expert-sharded placement. ``model`` should be
    the ORACLE form (ep_axis=None) — the parameter tree is identical."""
    if rng is None:
        rng = jax.random.key(0)
    init_model = model.clone(ep_axis=None, n_groups=1,
                             n_local_experts=None)
    init_len = min(sample_tokens[1], 128)

    def init_fn(rng):
        variables = init_model.init(
            rng, jnp.zeros((sample_tokens[0], init_len), jnp.int32),
            positions=jnp.arange(init_len))
        params = variables["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params),
                          batch_stats=variables.get(MOE_STATE, {}))

    shapes = jax.eval_shape(init_fn, rng)
    specs = ep_state_specs(shapes, axis)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    with mesh:
        return jax.jit(init_fn, out_shardings=shardings)(rng)


def make_ep_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                       state: TrainState, *, axis: str = "data",
                       remat: bool = False, donate: bool = True) -> Callable:
    """-> step_fn(state, tokens) -> (state, {'loss', 'aux'}); a dropless
    arch adds the rest of ``DROPLESS_STATS``: 'z_loss',
    'expert_load_max_over_mean', 'moe_dropped', 'moe_held_share',
    'moe_tail_rows_share', and its loss the arch's z-loss term, scaled by the
    token count as ``aux`` is.
    The load-balance term's coefficient is the arch's too
    (``Arch.aux_coef``). A model that holds a share of its
    experts (``experts_held``) trains that share's part of each layer, on one
    device: there is no exchange.

    Where the arch chooses its experts under a bias (``router_bias_rate``),
    the bias is ``state.batch_stats`` (the model's ``MOE_STATE`` collection):
    no gradient, no momentum and no weight decay reach it. After the
    optimizer's update the step moves it by ``update_expert_bias`` from the
    step's assignments to every router output, summed over ``axis``, and adds
    ``BIAS_STATS`` to the metrics: 'moe_bias_abs_max' (the largest |bias|
    after the move) and 'moe_load_all_max_over_mean' (the busiest of ALL the
    router's outputs over the mean, worst layer). What the model counted on
    the way (``lm_counters``: an arch with linear-attention layers'
    'gdn_state_abs_max') comes with the loss too, as under ``parallel/sp.py``.
    An arch with ``load_all_stat`` and no bias reports
    'moe_load_all_max_over_mean' from its layers' own counts.

    tokens [B, S] int32, batch sharded over ``axis``. ``model`` must be
    built with ``ep_axis=axis`` and ``n_groups=1`` (each device dispatches
    its own tokens); n_experts must divide by the axis size.
    """
    if getattr(model, "ep_axis", None) != axis:
        raise ValueError(f"model.ep_axis={model.ep_axis!r} != step axis "
                         f"{axis!r} — build the model with ep_axis={axis!r}")
    n = mesh.shape[axis]
    arch = ARCHS[model.arch]
    if arch.dropless and n > 1:
        # The capacity path's all_to_all moves fixed [E, C, D] slots; ragged
        # groups need a ragged all-to-all, which is the four-chip cell's PR.
        raise NotImplementedError(
            f"dropless routing across chips: not built (lm_arch="
            f"{model.arch!r} over {n} devices); see PERF.md §7")
    if model.n_experts % n:
        raise ValueError(f"{model.n_experts} experts not divisible over "
                         f"{n} devices")
    # flax validates stored param shapes against their declaration; inside
    # shard_map each device holds the local expert slice, so the module
    # must declare the local count. remat is per-block (MoETransformerLM
    # docstring) — the recompute replays the block's all_to_alls,
    # SPMD-legal since every shard recomputes the same program.
    model = model.clone(n_local_experts=model.n_experts // n, n_groups=1,
                        remat=remat)
    biased = arch.router_bias_rate > 0

    def local_step(state, tokens):
        def loss_fn(params):
            (logits, aux), sown = model.apply(
                lm_variables(params, state.batch_stats), tokens,
                mutable=[LM_COUNTERS])
            stats = dict(aux) if arch.dropless else {"aux": aux}
            # The last position has no target: weight 0 under a filler (the
            # sequence's first token), so the logits keep all S rows.
            seq = tokens.shape[1]
            with device_scope("loss"):
                weights = jnp.broadcast_to(jnp.arange(seq) < seq - 1,
                                           tokens.shape).astype(jnp.float32)
                ce_sum, count = next_token_loss(
                    logits, jnp.roll(tokens, -1, axis=1), weights)
                reg = arch.aux_coef * stats["aux"] \
                    + arch.z_loss_coef * stats.get("z_loss", 0.0)
                # LOCAL sums; collectives on the grads, not in the loss.
                return ce_sum + reg * count, (count, ce_sum, stats,
                                              lm_counters(sown))

        (_, (count, ce_sum, stats, counters)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        counts = stats.pop(EXPERT_COUNTS, None)

        def reduce_grad(path, g):
            # Expert leaves are device-owned: the all_to_all transpose
            # already delivered every visiting token's contribution.
            if _EXPERT_KEY in keystr(path):
                return g / total
            return jax.lax.psum(g, axis) / total

        with device_scope("grad_reduce"):
            total = jax.lax.psum(count, axis)
            paths, treedef = tree_flatten_with_path(grads)
            grads = jax.tree_util.tree_unflatten(
                treedef, [reduce_grad(p, g) for p, g in paths])
            loss = jax.lax.psum(ce_sum, axis) / total
            metrics = {"loss": loss,
                       **{k: _STAT_REDUCE[k](v, axis)
                          for k, v in stats.items()},
                       **{k: jax.lax.pmax(v, axis)
                          for k, v in counters.items()}}
        with device_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt)
        if biased:
            with device_scope("router_bias"):
                counts = jax.tree.map(lambda c: jax.lax.psum(c, axis), counts)
                bias = jax.tree.map(
                    lambda b, c: update_expert_bias(
                        b, c, arch.router_bias_rate),
                    state.batch_stats, counts)
                state = state.replace(batch_stats=bias)
                # every layer's leaf is [experts]: one [layers, experts] each
                stacked = lambda tree: jnp.stack(jax.tree.leaves(tree))
                metrics["moe_bias_abs_max"] = jnp.max(jnp.abs(stacked(bias)))
                load = stacked(counts).astype(jnp.float32)
                metrics["moe_load_all_max_over_mean"] = jnp.max(
                    jnp.max(load, axis=-1) / jnp.mean(load, axis=-1))
        return state, metrics

    specs = ep_state_specs(jax.eval_shape(lambda s: s, state), axis)
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P(axis, None)),
        out_specs=(specs, P()),     # every metric a replicated scalar
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())
