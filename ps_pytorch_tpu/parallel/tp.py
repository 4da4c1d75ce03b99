"""Tensor parallelism (Megatron-style) for the transformer LM — GSPMD path.

Beyond-parity capability (the reference has no TP anywhere, SURVEY §2.5):
the transformer's weight matrices are sharded over the mesh's 'model' axis
and the train step is jitted with those shardings annotated — XLA/GSPMD
inserts the collectives ("pick a mesh, annotate shardings, let XLA insert
collectives" — the scaling-book recipe). This is deliberately the OTHER
idiom from ``parallel/dp.py``/``sp.py``'s explicit shard_map: weight-update
math identical on every path, communication chosen by the compiler. The two
idioms compose — the same jit shards its batch over 'data', so a 2-D
(data × model) mesh runs DP × TP in one program.

Sharding layout (standard Megatron column→row pairing: the annotations make
each block's attention and MLP shard-local up to one post-sum all-reduce
each, with collective placement GSPMD's to choose):

- q/k/v projections (``Dense_0/1/2`` kernels): column-parallel
  P(None, 'model') → a shard's output slice is HEAD-ALIGNED when
  n_heads % tp_degree == 0 (each projection is its own kernel; a packed
  qkv Dense(3d) would put shard boundaries inside q/k/v). With
  non-divisible head counts the math stays correct — GSPMD reshards inside
  attention — it just communicates more.
- attention out-proj  (``Dense_3`` kernel): row-parallel     P('model', None)
- MLP up-projection   (``Dense_4`` kernel): column-parallel, bias P('model')
- MLP down-projection (``Dense_5`` kernel): row-parallel, bias replicated
  (GSPMD adds the replicated bias once, after the partial-sum reduce —
  correctness the hand-written shard_map version would have to re-derive).
- ``lm_head`` kernel: column-parallel → vocab-sharded logits; the loss's
  reshard is GSPMD's to place.
- embeddings / LayerNorms / positional tables: replicated.

Optimizer states mirror their parameter's sharding (momentum of a sharded
kernel is sharded the same way), matched structurally by path suffix +
shape, so optimizer memory also drops by the TP degree.
"""

import re
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import keystr, tree_flatten_with_path

from ps_pytorch_tpu.models.transformer import refuse_head_kinds
from ps_pytorch_tpu.parallel.dp import TrainState

# flax auto-names the Block's Dense layers in call order
# (models/transformer.py Block.__call__): 0=q, 1=k, 2=v, 3=attn-out,
# 4=mlp-up, 5=mlp-down.
_KERNEL_RULES = [
    (re.compile(r"Dense_[012].*kernel"), ("col",)),
    (re.compile(r"Dense_3.*kernel"), ("row",)),
    (re.compile(r"Dense_4.*kernel"), ("col",)),
    (re.compile(r"Dense_5.*kernel"), ("row",)),
    (re.compile(r"lm_head.*kernel"), ("col",)),
    (re.compile(r"Dense_4.*bias"), ("bias_col",)),
]


def tp_param_specs(params, axis: str = "model"):
    """PartitionSpec pytree for the TransformerLM parameter tree."""

    def spec_for(path) -> P:
        s = keystr(path)
        for pat, (kind,) in _KERNEL_RULES:
            if pat.search(s):
                if kind == "col":
                    return P(None, axis)
                if kind == "row":
                    return P(axis, None)
                return P(axis)  # bias of a column-parallel layer
        return P()

    paths, treedef = tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p) for p, _ in paths])


def _opt_state_specs(opt_shapes, param_shapes, param_specs):
    """Mirror each parameter's spec onto the congruent optimizer-state leaf.

    optax states embed the parameter tree (momentum/trace, Adam mu/nu), so an
    opt leaf whose path ENDS WITH a parameter's path and matches its shape
    carries that parameter's sharding; anything else (step counts, empty
    states) stays replicated.
    """
    pmap = []
    for path, leaf in tree_flatten_with_path(param_shapes)[0]:
        pmap.append((keystr(path), leaf.shape))
    spec_by_key = {k: s for (k, _), s in
                   zip(pmap, jax.tree.leaves(
                       param_specs, is_leaf=lambda x: isinstance(x, P)))}

    leaves, treedef = tree_flatten_with_path(opt_shapes)
    out = []
    for path, leaf in leaves:
        s = keystr(path)
        spec = P()
        for (pkey, pshape) in pmap:
            if s.endswith(pkey) and tuple(leaf.shape) == tuple(pshape):
                spec = spec_by_key[pkey]
                break
        out.append(spec)
    return jax.tree_util.tree_unflatten(treedef, out)


def tp_state_specs(state_shapes: TrainState, axis: str = "model") -> TrainState:
    pspecs = tp_param_specs(state_shapes.params, axis)
    return TrainState(
        step=P(),
        params=pspecs,
        opt_state=_opt_state_specs(state_shapes.opt_state,
                                   state_shapes.params, pspecs),
        batch_stats=jax.tree.map(lambda _: P(), state_shapes.batch_stats),
    )


def create_tp_train_state(model, tx: optax.GradientTransformation,
                          mesh: Mesh, sample_tokens,
                          rng: Optional[jax.Array] = None,
                          axis: str = "model") -> TrainState:
    """Init the LM with TP-sharded placement (params AND optimizer state land
    sharded — no replicated staging copy)."""
    if rng is None:
        rng = jax.random.key(0)
    init_len = min(sample_tokens[1], 128)

    def init_fn(rng):
        variables = model.init(
            rng, jnp.zeros((sample_tokens[0], init_len), jnp.int32),
            positions=jnp.arange(init_len))
        params = variables["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), batch_stats={})

    shapes = jax.eval_shape(init_fn, rng)
    specs = tp_state_specs(shapes, axis)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    with mesh:
        return jax.jit(init_fn, out_shardings=shardings)(rng)


def make_tp_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                       state: TrainState, *, axis: str = "model",
                       remat: bool = False, donate: bool = True) -> Callable:
    """-> step_fn(state, tokens) -> (state, {'loss'}).

    tokens: [B, S] int32, batch sharded over 'data' (DP) while every weight
    matrix stays sharded over ``axis`` (TP). One jit; GSPMD places the
    per-block all-reduces and the gradient all-reduce over 'data'.

    The model must be ``attention_impl='full'`` — TP shards heads, not the
    sequence; compose with ``parallel/sp.py`` for sequence sharding instead.
    """
    if getattr(model, "attention_impl", "full") != "full":
        raise ValueError("TP step requires attention_impl='full' "
                         "(ring attention shards sequence, not heads)")
    refuse_head_kinds(model, "tensor parallelism")

    # Per-block remat (TransformerLM.remat): checkpointing the whole loss
    # instead would save no peak memory (the recompute holds all residuals
    # at once) while paying a full extra forward.
    if remat:
        model = model.clone(remat=True)

    def step(state, tokens):
        def loss_fn(params):
            logits = model.apply({"params": params}, tokens)
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1].astype(jnp.float32), tokens[:, 1:])
            return per.mean()

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return state.replace(step=state.step + 1, params=new_params,
                             opt_state=new_opt), {"loss": loss}

    specs = tp_state_specs(jax.eval_shape(lambda s: s, state), axis)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                      is_leaf=lambda x: isinstance(x, P))
    tok_sh = NamedSharding(mesh, P("data", None))
    loss_sh = NamedSharding(mesh, P())
    return jax.jit(step,
                   in_shardings=(sh, tok_sh),
                   out_shardings=(sh, {"loss": loss_sh}),
                   donate_argnums=(0,) if donate else ())
