"""Pipeline parallelism (GPipe-style) for the transformer LM.

Beyond-parity capability (reference has no PP, SURVEY §2.5; with
``parallel/dp.py``/``tp.py``/``sp.py``/``zero.py`` this completes the
DP/TP/PP/SP/ZeRO inventory): the transformer's blocks are split into S
equal stages laid out along the mesh's 'model' axis; a microbatched
schedule streams M microbatches through the stages, passing activations to
the next stage with a single ``ppermute`` hop per tick. The whole schedule
is one ``lax.scan`` inside one ``shard_map`` — ``jax.grad`` differentiates
straight through it (ppermute transposes to the reverse hop), so backward
pipelining needs no hand-written schedule. Composes with data parallelism:
the batch axis shards over 'data', stages over 'model', in the same jit.

Layout:
- per-block parameters are STACKED along a leading stage axis sharded
  P('model') — each stage holds only its own blocks' weights and optimizer
  state (the memory win PP exists for);
- embeddings / final LayerNorm / lm_head are replicated; only stage 0
  embeds and only the last stage computes logits+loss, so their gradients
  arrive via one psum over 'model' (zero contributions elsewhere).

Schedule: tick t has stage s processing microbatch (t - s); T = M + S - 1
ticks total, the classic GPipe bubble of (S-1)/(M+S-1) idle fraction —
documented cost, not hidden: utilization rises with M. Bubble ticks skip
their block compute via ``lax.cond`` (zeros instead of garbage), so the
bubble costs schedule latency but not FLOPs. Activations cross stages
uncompressed over ICI (the reference's PS crossed the full gradient over
TCP every step, SURVEY §2.3).

Forward semantics are bit-compatible with ``models/transformer.TransformerLM``
(same module math; `tests/test_pp.py` pins PP against the unsharded model).
"""

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ps_pytorch_tpu.models.transformer import (
    Block, EmbedRows, refuse_head_kinds,
)
from ps_pytorch_tpu.parallel.dp import TrainState


# ---------------------------------------------------------------------------
# Parameter restructuring: TransformerLM tree <-> PP (stacked-stage) tree
# ---------------------------------------------------------------------------

def stack_stage_params(params: dict, n_stages: int) -> dict:
    """TransformerLM param tree -> PP tree.

    {'blocks': stacked [n_stages, layers_per_stage, ...] leaves,
     'tok_embed'/'pos_embed'/'ln_f'/'lm_head': untouched}
    """
    n_layers = len([k for k in params if k.startswith("block_")])
    if n_layers == 0 or n_layers % n_stages:
        raise ValueError(f"{n_layers} blocks not divisible into "
                         f"{n_stages} stages")
    per = n_layers // n_stages
    blocks = [params[f"block_{i}"] for i in range(n_layers)]
    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves).reshape(
            (n_stages, per) + leaves[0].shape), *blocks)
    out = {k: v for k, v in params.items() if not k.startswith("block_")}
    out["blocks"] = stacked
    return out


def unstack_stage_params(pp_params: dict) -> dict:
    """Inverse of ``stack_stage_params`` (checkpoint interchange with the
    unsharded TransformerLM tree)."""
    stacked = pp_params["blocks"]
    any_leaf = jax.tree.leaves(stacked)[0]
    n_stages, per = any_leaf.shape[:2]
    out = {k: v for k, v in pp_params.items() if k != "blocks"}
    for s in range(n_stages):
        for l in range(per):
            out[f"block_{s * per + l}"] = jax.tree.map(
                lambda a: a[s, l], stacked)
    return out


# ---------------------------------------------------------------------------
# Pipeline edges (embed / head): the SAME flax modules TransformerLM uses,
# applied to the matching param subtrees — exact by construction, including
# compute-dtype casts and LayerNorm internals (hand-rolled math here
# silently diverged for non-f32 dtypes).
# ---------------------------------------------------------------------------

def _embed(model, params, tokens):
    tok = EmbedRows(model.vocab_size, model.d_model, dtype=model.dtype)
    pos = EmbedRows(model.max_seq_len, model.d_model, dtype=model.dtype)
    x = tok.apply({"params": params["tok_embed"]}, tokens)
    p = pos.apply({"params": params["pos_embed"]},
                  jnp.arange(tokens.shape[1]))
    return x + p[None]


def _head(model, params, x):
    ln = nn.LayerNorm(dtype=model.dtype)
    dense = nn.Dense(model.vocab_size, use_bias=False, dtype=model.dtype)
    x = ln.apply({"params": params["ln_f"]}, x)
    return dense.apply({"params": params["lm_head"]}, x).astype(jnp.float32)


def _apply_stage(block_module: Block, stage_params, x, *,
                 remat: bool = False):
    """Run this stage's ``layers_per_stage`` blocks sequentially.

    stage_params leaves: [layers_per_stage, ...] (stage axis already
    squeezed by shard_map). ``remat`` checkpoints each block, so backward
    stores only block boundaries — the classic PP+remat memory shape."""
    apply = lambda blk, x: block_module.apply({"params": blk}, x)
    if remat:
        apply = jax.checkpoint(apply)
    per = jax.tree.leaves(stage_params)[0].shape[0]
    for l in range(per):
        x = apply(jax.tree.map(lambda a: a[l], stage_params), x)
    return x


def reference_forward(model, params, tokens):
    """Unsharded forward through the SAME edge modules + Block applies the
    pipeline uses — the oracle `tests/test_pp.py` pins against
    ``model.apply`` and against the PP schedule."""
    x = _embed(model, params, tokens)
    n_layers = len([k for k in params if k.startswith("block_")])
    block = Block(model.n_heads, model.d_model, model.dtype,
                  getattr(model, "attention_impl", "full"))
    for i in range(n_layers):
        x = block.apply({"params": params[f"block_{i}"]}, x)
    return _head(model, params, x)


# ---------------------------------------------------------------------------
# The pipelined step
# ---------------------------------------------------------------------------

def pp_state_specs(state_shapes: TrainState) -> TrainState:
    """Stacked block leaves (and their optimizer mirrors) shard over
    'model'; everything else replicates. Matching is BY KEY: exactly the
    top-level ``'blocks'`` entry (what ``stack_stage_params`` produces) is
    stage-sharded — a new stacked param group under another key would need
    its own rule here."""
    def param_specs(tree):
        return {k: (jax.tree.map(lambda _: P("model"), v) if k == "blocks"
                    else jax.tree.map(lambda _: P(), v))
                for k, v in tree.items()}

    pspecs = param_specs(state_shapes.params)
    # optax states embed the param tree: mirror by path suffix.
    from ps_pytorch_tpu.parallel.tp import _opt_state_specs
    return TrainState(
        step=P(),
        params=pspecs,
        opt_state=_opt_state_specs(state_shapes.opt_state,
                                   state_shapes.params, pspecs),
        batch_stats={},
    )


def create_pp_train_state(model, tx: optax.GradientTransformation,
                          mesh: Mesh, n_stages: int, sample_tokens,
                          rng: Optional[jax.Array] = None) -> TrainState:
    if rng is None:
        rng = jax.random.key(0)
    init_len = min(sample_tokens[1], 128)

    def init_fn(rng):
        variables = model.init(
            rng, jnp.zeros((sample_tokens[0], init_len), jnp.int32),
            positions=jnp.arange(init_len))
        params = stack_stage_params(variables["params"], n_stages)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), batch_stats={})

    shapes = jax.eval_shape(init_fn, rng)
    specs = pp_state_specs(shapes)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    with mesh:
        # Init straight into the stage-sharded layout: the partitionable
        # threefry generator draws the same bits under any sharding, so the
        # weights equal the unsharded oracle init bitwise (the PP-vs-
        # unsharded parity tests pin it) and no device ever holds the whole
        # model.
        return jax.jit(init_fn, out_shardings=shardings)(rng)


def make_pp_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                       state: TrainState, *, num_microbatches: int,
                       axis_name: str = "model", data_axis: str = "data",
                       remat: bool = False, donate: bool = True) -> Callable:
    """-> step_fn(state, tokens) -> (state, {'loss'}).

    tokens [B, S]: batch sharded over ``data_axis`` (size may be 1), every
    stage sees the same local tokens (stage 0 embeds, the last stage needs
    the targets). The model must be ``attention_impl='full'``.
    """
    if getattr(model, "attention_impl", "full") not in ("full", "flash"):
        # ring needs a sequence mesh axis; full/flash are sequence-local
        # and run fine inside the per-stage shard_map.
        raise ValueError("PP step requires attention_impl='full'|'flash'")
    refuse_head_kinds(model, "pipeline parallelism")
    n_stages = mesh.shape[axis_name]
    M = num_microbatches
    stacked = jax.tree.leaves(state.params["blocks"])[0].shape[0]
    if stacked != n_stages:
        # A state stacked for S' stages silently truncates to the mesh's S
        # stages otherwise (each shard would drop all but its first slice).
        raise ValueError(
            f"state was stacked for {stacked} stages but the mesh's "
            f"'{axis_name}' axis has {n_stages} — rebuild the state with "
            f"n_stages={n_stages}")
    block = Block(model.n_heads, model.d_model, model.dtype,
                  getattr(model, "attention_impl", "full"))

    def pipeline_loss(params, tokens):
        """Runs on ONE stage (inside shard_map): the full T-tick schedule
        with this stage's slice of work per tick."""
        s_idx = jax.lax.axis_index(axis_name)
        stage_params = jax.tree.map(lambda a: a[0], params["blocks"])
        b, seq = tokens.shape
        if b % M:
            raise ValueError(f"local batch {b} not divisible into "
                             f"{M} microbatches")
        mb = b // M
        micro = tokens.reshape(M, mb, seq)
        T = M + n_stages - 1
        perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            y_prev, loss_sum, tok_count = carry
            # Activation handoff: stage s's tick-(t-1) output becomes stage
            # s+1's tick-t input. (The wrap edge S-1 -> 0 carries bubble
            # garbage; stage 0 always overwrites it with a fresh embed.)
            # The ppermute stays UNconditional — every shard must execute
            # the collective; everything else (embed, the stage's blocks,
            # the head) is collective-free and gated behind lax.cond.
            recv = jax.lax.ppermute(y_prev, axis_name, perm_fwd)
            mb_idx = t - s_idx            # microbatch this stage works on
            valid = (mb_idx >= 0) & (mb_idx < M)
            safe_idx = jnp.clip(mb_idx, 0, M - 1)
            my_tokens = micro[safe_idx]
            x_in = jax.lax.cond(
                valid & (s_idx == 0),
                lambda: _embed(model, params, my_tokens).astype(recv.dtype),
                lambda: recv)
            # Bubble ticks (the (S-1)/(M+S-1) idle fraction) skip embed and
            # block compute entirely: their output is garbage consumed only
            # by other bubble ticks, so zeros are just as good and cost
            # nothing.
            y = jax.lax.cond(
                valid,
                lambda: _apply_stage(block, stage_params, x_in,
                                     remat=remat),
                lambda: jnp.zeros_like(x_in))
            # Last stage: loss for its (valid) microbatch.
            is_last = s_idx == n_stages - 1
            take = valid & is_last

            def head_loss():
                logits = _head(model, params, y)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], my_tokens[:, 1:]).sum()

            loss_sum = loss_sum + jax.lax.cond(
                take, head_loss, lambda: jnp.float32(0.0))
            tok_count = tok_count + jnp.where(take, mb * (seq - 1), 0)
            return (y, loss_sum, tok_count), None

        y0 = jnp.zeros_like(_embed(model, params, micro[0]))
        (_, loss_sum, tok_count), _ = jax.lax.scan(
            tick, (y0, jnp.float32(0.0), jnp.int32(0)), jnp.arange(T))
        # LOCAL sums only — nonzero on the last stage alone. No collective
        # here: differentiating through an in-loss psum with replicated
        # params double-counts cross-shard cotangents (the sp.py pitfall;
        # observed here as a ~3% loss drift vs the unsharded oracle).
        # Normalization and the cross-stage sum happen on the gradients.
        return loss_sum, tok_count

    def local_step(state, tokens):
        def loss_fn(params):
            loss_sum, tok_count = pipeline_loss(params, tokens)
            return loss_sum, tok_count

        (loss_sum, tok_count), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        # grads = d(local loss SUM)/d(params): the last stage's loss seeded
        # the cotangents, which flowed back across stages via the ppermute
        # transposes — each stage's block grads land where those blocks
        # live. Global token count normalizes; contributions sum across
        # shards: block stacks over 'data' only (stage-owned along
        # 'model'), edge params (embed/head/ln_f — touched on stage 0 and
        # last only, zero grads elsewhere) over both axes.
        total = jax.lax.psum(tok_count, (axis_name, data_axis))
        denom = total.astype(jnp.float32)

        def reduce_grad(is_blocks, g):
            axes = (data_axis,) if is_blocks else (axis_name, data_axis)
            return jax.lax.psum(g, axes) / denom

        grads = {k: jax.tree.map(lambda g: reduce_grad(k == "blocks", g), v)
                 for k, v in grads.items()}
        loss = jax.lax.psum(loss_sum, (axis_name, data_axis)) / denom
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return state.replace(step=state.step + 1, params=new_params,
                             opt_state=new_opt), {"loss": loss}

    specs = pp_state_specs(jax.eval_shape(lambda s: s, state))
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P(data_axis, None)),
        out_specs=(specs, {"loss": P()}),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())
