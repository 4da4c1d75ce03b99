"""Sequence-parallel (long-context) training step.

The long-context counterpart of ``parallel/dp.py``: instead of sharding the
batch, the SEQUENCE axis of every example is sharded over the mesh's 'data'
axis, attention runs as a ring (``parallel/ring.py``), and each shard
computes the next-token loss for its local tokens; gradients are summed with
``psum`` exactly like the data-parallel path — one jitted shard_map, params
replicated, collectives on ICI. The reference has no equivalent capability
(SURVEY §5.7); this is where the framework exceeds it.

Loss detail at the shard boundary: shard i needs token 1 of shard i+1 as the
target for its last local position, obtained with a single ppermute of the
first local token — no overlap halo, no gather.
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ps_pytorch_tpu.ops.next_token_loss import next_token_loss
from ps_pytorch_tpu.parallel.dp import TrainState
from ps_pytorch_tpu.telemetry.trace import device_scope


def create_lm_train_state(model, tx, mesh: Mesh, sample_tokens,
                          rng: Optional[jax.Array] = None) -> TrainState:
    """Replicated params/opt_state for the LM (no batch_stats)."""
    # Ring attention needs a bound mesh axis; init runs under plain jit, so
    # use a full-attention clone — the parameter tree is identical.
    init_model = model
    if getattr(model, "attention_impl", "full") == "ring":
        init_model = model.clone(attention_impl="full")
    if rng is None:
        rng = jax.random.key(0)
    # Param shapes don't depend on sequence length (pos_embed is sized by
    # max_seq_len), so init at a short dummy length: running full attention
    # at the caller's global S would materialize [S, S] — OOM in exactly the
    # long-context regime this module exists for.
    init_len = min(sample_tokens[1], 128)

    def init_fn(rng):
        variables = init_model.init(
            rng, jnp.zeros((sample_tokens[0], init_len), jnp.int32),
            positions=jnp.arange(init_len))
        params = variables["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), batch_stats={})

    shapes = jax.eval_shape(init_fn, rng)
    specs = TrainState(step=P(), params=jax.tree.map(lambda _: P(), shapes.params),
                       opt_state=jax.tree.map(lambda _: P(), shapes.opt_state),
                       batch_stats={})
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(init_fn, out_shardings=shardings)(rng)


def _targets_and_weights(tokens, first_next, positions, total: int,
                         heads: int = 1):
    """Next-token targets and their weights for ``heads`` prediction heads:
    head ``i`` at position ``t`` predicts token ``t + 1 + i``. One head:
    ``[B, S]`` arrays, the local shift with the next shard's first token
    (``first_next``) as the last target, and the global last position (of
    ``total``) weighted out. Several: ``[B, S, heads]``; head ``i``'s last
    ``i + 1`` positions have no target inside the sequence and weigh 0 (the
    caller holds the whole sequence: ``first_next`` is then a target of
    weight 0)."""
    targets, weights = [], []
    for i in range(heads):
        shifted = [tokens[:, 1 + i:], first_next]
        if i:
            shifted.append(jnp.zeros((tokens.shape[0], i), tokens.dtype))
        targets.append(jnp.concatenate(shifted, axis=1))
        no_target = positions == (total - 1) if i == 0 \
            else positions >= (total - 1 - i)
        weights.append(jnp.broadcast_to(jnp.where(no_target, 0.0, 1.0),
                                        tokens.shape))
    if heads == 1:
        return targets[0], weights[0]
    return jnp.stack(targets, axis=-1), jnp.stack(weights, axis=-1)


def _local_nexttoken_loss(model, axis_name: str, params, tokens):
    """Per-shard next-token loss (sum, (count, counters)) — shared by the
    train step and the grad-free eval so their framing can never diverge.
    ``counters``: what the model counted on the way (``lm_counters``: a
    hybrid arch's ``ssm_state_abs_max`` and ``diff_lambda_max``, an EVA
    arch's ``eva_pool_weight_max``; {} for the others) and, where the arch
    has several prediction heads, ``next_token_loss_head0``: head 0's own
    mean loss, the next-token loss that compares with other models (the
    step's loss is the mean over every head's targets, all weighted alike).

    LOCAL sums only — no collective inside (the train step differentiates
    this; differentiating through an in-loss psum double-counts cross-shard
    cotangents); normalization and the cross-shard sum happen outside.
    """
    # models/transformer.py imports parallel/ring.py, and so this package
    from ps_pytorch_tpu.models.transformer import (
        ARCHS, LM_COUNTERS, lm_counters,
    )
    heads = ARCHS[getattr(model, "arch", "gpt2")].pred_heads
    n = jax.lax.axis_size(axis_name)
    if heads > 1 and n > 1:
        raise ValueError(
            f"lm_arch={model.arch} has {heads} prediction heads: head i's "
            f"target is i + 1 tokens ahead and the boundary ppermute "
            f"carries one token; train it under sp on one device")
    idx = jax.lax.axis_index(axis_name)
    s_local = tokens.shape[1]
    positions = idx * s_local + jnp.arange(s_local)
    logits, sown = model.apply({"params": params}, tokens,
                               positions=positions, mutable=[LM_COUNTERS])
    # Next-token targets: local shift; the boundary target (first token of
    # the next shard) arrives via one ppermute hop.
    perm = [(j, (j - 1) % n) for j in range(n)]
    with device_scope("loss"):
        first_next = jax.lax.ppermute(tokens[:, :1], axis_name, perm)
        # The global last token has no target: weighted out.
        targets, w = _targets_and_weights(tokens, first_next, positions,
                                          n * s_local, heads)
        loss_sum, count = next_token_loss(logits, targets, w)
        counters = lm_counters(sown)
        if heads > 1:
            # one shard holds the sequence, so the local mean is the mean
            own, n_own = next_token_loss(
                jax.lax.stop_gradient(logits[:, :, 0]), targets[..., 0],
                w[..., 0])
            counters["next_token_loss_head0"] = own / n_own
        return loss_sum, (count, counters)


def make_sp_train_step(model, tx, mesh: Mesh, *, axis_name: str = "data",
                       remat: bool = False, donate: bool = True) -> Callable:
    """-> step_fn(state, tokens) -> (state, metrics).

    tokens: [B, S] global int32, S sharded over ``axis_name``. The model must
    be built with ``attention_impl='ring'`` and the same ``axis_name``.
    (No rng parameter: the LM has no dropout yet; add an ``rngs`` dict to the
    apply call when it does.)

    ``remat`` enables PER-BLOCK rematerialization (TransformerLM.remat —
    backward stores only block boundaries; the long-context lever when S/N
    activations still don't fit). The recomputation replays each block's
    ring ppermutes, which is SPMD-legal because every shard recomputes the
    same program.
    """
    if remat:
        model = model.clone(remat=True)

    def local_step(state, tokens):
        def loss_fn(params):
            return _local_nexttoken_loss(model, axis_name, params, tokens)

        (loss_sum, (count, counters)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        # Params are replicated, so each shard's backprop yields only the
        # contribution of computational paths through that shard (ring
        # ppermutes transpose to reverse ppermutes); the full mean-loss
        # gradient is their sum over the global token count.
        with device_scope("grad_reduce"):
            total = jax.lax.psum(count, axis_name)
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g, axis_name) / total, grads)
            loss = jax.lax.psum(loss_sum, axis_name) / total
            counters = {k: jax.lax.pmax(v, axis_name)
                        for k, v in counters.items()}
        with device_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_state = state.replace(step=state.step + 1, params=new_params,
                                      opt_state=new_opt)
        return new_state, {"loss": loss, **counters}

    specs = TrainState(step=P(), params=P(), opt_state=P(), batch_stats={})
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P(None, axis_name)),
        out_specs=(specs, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_sp_eval_fn(model, mesh: Mesh, *, axis_name: str = "data") -> Callable:
    """-> eval_fn(params, tokens) -> mean next-token loss (scalar).

    Grad-free forward through the SAME sharded ring-attention path as the
    train step (shared loss framing, `_local_nexttoken_loss`) — evaluating
    with a full-attention clone at the global sequence length would
    materialize the [S, S] score matrix on one device, the exact OOM the
    long-context design exists to avoid."""

    def local_eval(params, tokens):
        loss_sum, (count, _) = _local_nexttoken_loss(model, axis_name,
                                                     params, tokens)
        return jax.lax.psum(loss_sum, axis_name) / \
            jax.lax.psum(count, axis_name)

    sharded = jax.shard_map(
        local_eval, mesh=mesh,
        in_specs=(P(), P(None, axis_name)),
        out_specs=P(),
        check_vma=False)
    return jax.jit(sharded)
