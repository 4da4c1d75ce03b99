"""The SPMD data-parallel step — the heart of the framework.

Replaces the reference's entire per-step wire protocol (SURVEY §2.3): weight
broadcast (``sync_replicas_master_nn.py:218-225``), per-layer gradient upload
(``distributed_worker.py:254-272``), master-side Waitany aggregation with
backup-worker cutoff (``sync_replicas_master_nn.py:156-186``) and the
master-side optimizer step (``:204-208``) — with ONE jitted ``shard_map`` over
the ('data','model') mesh:

- parameters + optimizer state are mesh-replicated; "weight broadcast"
  ceases to exist as communication;
- gradients are averaged in-graph with a masked ``psum`` riding ICI;
- the K-of-N backup-worker capability (`--num-aggregate`,
  ``sync_replicas_master_nn.py:116,179``) becomes a per-replica participation
  mask: contributions are weighted, summed with ``psum``, and divided by the
  participating count — replicas excluded by the coordinator's deadline policy
  (runtime/coordinator.py) contribute nothing, yet every replica still ends
  the step with identical parameters;
- BatchNorm running statistics stay replica-local, exactly like the reference
  (workers exclude BN running stats from weight sync,
  ``distributed_worker.py:245-252``): ``batch_stats`` leaves carry a leading
  [n_data] axis sharded over the data axis. ``sync_batchnorm=True`` opts into
  cross-replica stat averaging instead.
"""

from functools import partial
from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ps_pytorch_tpu.telemetry.trace import device_scope


class TrainState(flax.struct.PyTreeNode):
    step: jnp.ndarray              # int32 scalar, replicated
    params: Any                    # replicated
    opt_state: Any                 # replicated
    batch_stats: Any               # leading [n_data] axis, sharded over 'data'; {} if no BN


def _model_collections(model, sample_shape, rng):
    variables = model.init(rng, jnp.zeros(sample_shape, jnp.float32), train=False)
    return variables["params"], variables.get("batch_stats", {})


def create_train_state(model, tx: optax.GradientTransformation, mesh: Mesh,
                       sample_shape, rng) -> TrainState:
    """Initialize replicated params/opt_state and per-replica batch_stats,
    placed with the shardings make_train_step expects.

    The init runs *inside* jit with explicit out_shardings, so it produces
    correctly placed global arrays in both single- and multi-process worlds
    (a host-side init + device_put would not be legal across processes)."""
    n_data = mesh.shape["data"]

    def init_fn(rng):
        params, batch_stats = _model_collections(model, sample_shape, rng)
        opt_state = tx.init(params)
        batch_stats = jax.tree.map(
            lambda a: jnp.tile(a[None], (n_data,) + (1,) * a.ndim), batch_stats)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt_state, batch_stats=batch_stats)

    shapes = jax.eval_shape(init_fn, rng)
    shardings = state_shardings(mesh, shapes)
    return jax.jit(init_fn, out_shardings=shardings)(rng)


def state_specs(state: TrainState) -> TrainState:
    """PartitionSpec pytree (prefix form) matching TrainState placement."""
    return TrainState(
        step=P(),
        params=jax.tree.map(lambda _: P(), state.params),
        opt_state=jax.tree.map(lambda _: P(), state.opt_state),
        batch_stats=jax.tree.map(lambda _: P("data"), state.batch_stats),
    )


def state_shardings(mesh: Mesh, state: TrainState, specs=None) -> TrainState:
    """PartitionSpec pytree -> NamedSharding pytree; ``specs`` overrides the
    default data-parallel placement (e.g. zero_state_specs)."""
    specs = state_specs(state) if specs is None else specs
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def make_loss_fn(model, has_bn: bool, input_norm=None):
    """The per-replica supervised loss shared by the DP and ZeRO steps:
    cross-entropy + accuracy, BN batch_stats threaded when present.

    ``input_norm``: optional (scale[C], shift[C]) applied in-graph
    (``x * scale - shift``) so the host can ship raw uint8 batches
    (augment.device_norm_constants) — XLA fuses it into the first conv's
    input pipeline for free."""
    if input_norm is not None:
        scale = jnp.asarray(input_norm[0], jnp.float32)
        shift = jnp.asarray(input_norm[1], jnp.float32)

    def loss_fn(params, bs_local, x, y, rng):
        if input_norm is not None:
            with device_scope("conv"):   # XLA fuses it into the first conv
                x = x * scale - shift
        variables = {"params": params}
        if has_bn:
            variables["batch_stats"] = bs_local
        # Unused rngs are ignored by flax, so pass dropout unconditionally.
        kw = dict(train=True, rngs={"dropout": rng})
        if has_bn:
            logits, mut = model.apply(variables, x, mutable=["batch_stats"], **kw)
            new_bs = mut["batch_stats"]
        else:
            logits = model.apply(variables, x, **kw)
            new_bs = bs_local
        with device_scope("loss"):
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            acc = jnp.mean(jnp.argmax(logits, -1) == y)
        return loss, (new_bs, acc)

    return loss_fn


def apply_optimizer(tx, params, opt_state, grads):
    """update+apply for an optax transform, under the ``optimizer`` scope."""
    with device_scope("optimizer"):
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt


def masked_metrics(loss, acc, m, denom, msum):
    return {
        "loss": jax.lax.psum(loss * m, "data") / denom,
        "accuracy": jax.lax.psum(acc * m, "data") / denom,
        "participating": msum,
    }


def health_metrics(metrics, gnorm):
    """Fold the watchdog signals into the step metrics: the global grad
    norm and a nonfinite flag over norm+loss. Computed from values the
    step already materializes — no extra collectives, no extra sync
    (telemetry/health.py reads them at the trainer's existing 1-deep
    pipeline sync point)."""
    metrics["grad_norm"] = gnorm
    metrics["nonfinite"] = 1.0 - jnp.isfinite(
        gnorm + metrics["loss"]).astype(jnp.float32)
    return metrics


def place_state(mesh: Mesh, state: TrainState, specs=None) -> TrainState:
    """Host-local (numpy) TrainState -> correctly placed global arrays.

    jit with out_shardings is the multi-process-legal way to do this (a bare
    ``jax.device_put`` cannot target non-addressable devices); every process
    must pass the same host-local values (true after load_checkpoint).
    ``specs`` overrides the placement (e.g. zero_state_specs for the
    sharded-weight-update layout)."""
    return jax.jit(lambda s: s,
                   out_shardings=state_shardings(mesh, state, specs))(state)


def fetch_replicated(mesh: Mesh, state: TrainState) -> TrainState:
    """Global TrainState -> host-local numpy on EVERY process (batch_stats'
    'data'-sharded leaves are gathered). The multi-process-safe inverse of
    place_state, used for checkpointing and host-side eval."""
    specs = jax.tree.map(lambda _: P(), state)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    replicated = jax.jit(lambda s: s, out_shardings=shardings)(state)
    return jax.device_get(replicated)


def make_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                    state: TrainState, *, sync_batchnorm: bool = False,
                    remat: bool = False, donate: bool = True,
                    input_norm=None, skip_nonfinite: bool = False) -> Callable:
    """Build the jitted SPMD train step.

    Returns ``step_fn(state, x, y, mask, rng) -> (state, metrics)`` where
      x: [B, H, W, C] global batch (sharded over 'data'),
      y: [B] int labels,
      mask: [n_data] float participation vector (K-of-N; all-ones = sync mode),
      rng: scalar PRNG key (per-replica dropout keys are folded in-graph).
    metrics: dict of replicated scalars (loss, accuracy, participating,
    grad_norm, nonfinite).

    ``skip_nonfinite`` (the health plane's skip-step action) additionally
    gates the update on ``isfinite(grad_norm)``: a NaN/Inf step leaves
    params and optimizer state untouched — in-graph, so the poison never
    reaches the weights even before the host notices.
    """
    has_bn = bool(jax.tree.leaves(state.batch_stats))
    loss_fn = make_loss_fn(model, has_bn, input_norm)
    vg = jax.value_and_grad(
        jax.checkpoint(loss_fn) if remat else loss_fn, has_aux=True)

    def local_step(state, x, y, mask, rng):
        # Runs per-replica inside shard_map; x/y/mask are the local shards.
        bs_local = jax.tree.map(lambda a: a[0], state.batch_stats)
        rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
        (loss, (new_bs, acc)), grads = vg(state.params, bs_local, x, y, rng)
        m = mask[0]
        # Masked mean over participating replicas == "aggregate the first K
        # arrivals then divide by K" (sync_replicas_master_nn.py:179,204-208).
        with device_scope("grad_reduce"):
            msum = jax.lax.psum(m, "data")
            denom = jnp.maximum(msum, 1.0)
            gavg = jax.tree.map(
                lambda g: jax.lax.psum(g * m, "data") / denom, grads)
            # Global gradient norm over the averaged (post-psum) tree: every
            # replica computes the identical scalar, so it doubles as the
            # health plane's NaN/Inf sentinel at zero extra collectives.
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                 for g in jax.tree.leaves(gavg)))
        new_params, new_opt = apply_optimizer(
            tx, state.params, state.opt_state, gavg)
        # An all-zero mask must be a true no-op: the reference master never
        # steps without K gradients (sync_replicas_master_nn.py:179,204-208);
        # without this guard momentum decay/step counters would still move.
        with device_scope("optimizer"):
            stepped = msum > 0
            if skip_nonfinite:
                stepped = jnp.logical_and(stepped, jnp.isfinite(gnorm))
            new_params = jax.tree.map(
                lambda new, old: jnp.where(stepped, new, old),
                new_params, state.params)
            new_opt = jax.tree.map(
                lambda new, old: jnp.where(stepped, new, old),
                new_opt, state.opt_state)
        with device_scope("grad_reduce"):
            if has_bn and sync_batchnorm:
                # Masked mean: replicas excluded by K-of-N must not
                # contaminate the synced stats (same discipline as the
                # gradient path).
                new_bs = jax.tree.map(
                    lambda a: jax.lax.psum(a * m, "data") / denom, new_bs)
            metrics = health_metrics(
                masked_metrics(loss, acc, m, denom, msum), gnorm)
        with device_scope("optimizer"):
            new_state = state.replace(
                step=state.step + 1, params=new_params, opt_state=new_opt,
                batch_stats=jax.tree.map(lambda a: a[None], new_bs))
        return new_state, metrics

    specs = state_specs(state)
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P("data"), P("data"), P("data"), P()),
        out_specs=(specs, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_eval_step(model, input_norm=None) -> Callable:
    """Jitted single-shard eval: (params, batch_stats_local, x, y) ->
    dict(sum_loss, top1, top5, count). The evaluator feeds replica-0 batch
    stats, mirroring the reference evaluator consuming a single worker's
    checkpoint (``distributed_evaluator.py:90-106``). ``input_norm`` as in
    make_loss_fn (raw uint8 batches, in-graph normalize)."""
    if input_norm is not None:
        scale = jnp.asarray(input_norm[0], jnp.float32)
        shift = jnp.asarray(input_norm[1], jnp.float32)

    @jax.jit
    def eval_step(params, batch_stats, x, y):
        if input_norm is not None:
            x = x * scale - shift
        variables = {"params": params}
        if jax.tree.leaves(batch_stats):
            variables["batch_stats"] = batch_stats
        logits = model.apply(variables, x, train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        top1 = (jnp.argmax(logits, -1) == y).sum()
        top5 = (jax.lax.top_k(logits, 5)[1] == y[:, None]).any(-1).sum()
        return {"sum_loss": loss.sum(), "top1": top1, "top5": top5,
                "count": jnp.asarray(y.shape[0], jnp.int32)}

    return eval_step


def replica0_batch_stats(state: TrainState):
    """Pull one replica's BN stats to the host (for eval/checkpoint), matching
    the reference's 'a worker checkpoints its local BN stats' behavior
    (``distributed_worker.py:175-177``)."""
    return jax.tree.map(lambda a: a[0], state.batch_stats)
