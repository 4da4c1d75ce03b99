"""Shared LM evaluation oracle — ONE definition of the held-out next-token
loss for a checkpointed LM, used by both the in-trainer eval
(``lm_trainer.LMTrainer.evaluate``) and the standalone polling evaluator
(``evaluator.Evaluator``). Keeping the apply-dispatch (plain / pp-unstack /
MoE), the loss framing (logits[:, :-1] vs tokens[:, 1:]), and the
perplexity clamp in one place means the trainer's EVAL and the evaluator's
EVAL_LM can never silently diverge for the same checkpoint.

The config is self-describing (``network`` holds the model family,
``lm_model_axis`` the RESOLVED pp stage count — lm_trainer writes both
into the checkpoint).
"""

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import optax

_LM_NETWORKS = ("TransformerLM", "MoETransformerLM")


def perplexity(loss: float) -> float:
    return float(jnp.exp(min(loss, 30.0)))


def lm_geometry(cfg) -> dict:
    return dict(vocab_size=cfg.lm_vocab, d_model=cfg.lm_d_model,
                n_layers=cfg.lm_layers, n_heads=cfg.lm_heads,
                max_seq_len=cfg.lm_seq_len)


def build_lm_model(cfg, **kw):
    """The model a config describes — ONE construction for the trainer, the
    oracle and the checkpoint template, so a field that changes the forward
    with identical param shapes (top_k, arch) can never be left out of one of
    them. ``cfg.network`` decides the family where a checkpoint recorded it;
    a live config says it through ``lm_parallelism=ep``. ``kw`` adds what
    only the caller knows (attention_impl, ep_axis, axis_name). The compute
    dtype is ``cfg.compute_dtype`` (activations and matmul inputs; parameters
    stay float32), as for the CNNs."""
    from ps_pytorch_tpu.models import DTYPES
    geo = dict(lm_geometry(cfg), arch=cfg.lm_arch, ffn_dim=cfg.lm_ffn_dim,
               kv_heads=cfg.lm_kv_heads, head_dim=cfg.lm_head_dim,
               dtype=DTYPES[cfg.compute_dtype])
    if cfg.network == "MoETransformerLM" or cfg.lm_parallelism == "ep":
        from ps_pytorch_tpu.models.moe import MoETransformerLM
        from ps_pytorch_tpu.models.transformer import refuse_hybrid
        refuse_hybrid(cfg.lm_arch, "expert parallelism")
        return MoETransformerLM(n_experts=cfg.lm_experts,
                                top_k=cfg.lm_moe_top_k,
                                experts_held=cfg.lm_experts_held,
                                mixer_shares=cfg.lm_mixer_shares,
                                dense_layers=cfg.lm_dense_layers,
                                dense_ffn_dim=cfg.lm_dense_ffn_dim, **geo, **kw)
    from ps_pytorch_tpu.models.transformer import TransformerLM
    return TransformerLM(**geo, **kw)


def build_lm_oracle(cfg) -> Tuple[Callable, Callable]:
    """-> (loss_fn(params, tokens, moe_state=None) jitted,
    to_tree(saved_params)); ``moe_state`` is the saved state's
    ``batch_stats`` where the model keeps a ``models/moe.MOE_STATE``
    collection (an arch that chooses its experts under a bias).

    ``to_tree`` maps the checkpoint's param layout to the plain model tree
    (pp checkpoints store stage-stacked blocks). EP note: the oracle
    dispatches in ONE capacity group, while EP training grouped per device
    — only WHICH overflow tokens drop can differ (models/moe.py)."""
    to_tree = lambda p: p
    model = build_lm_model(cfg)
    if cfg.network == "MoETransformerLM":
        from ps_pytorch_tpu.models.moe import lm_variables
        apply = lambda p, t, ms: model.apply(lm_variables(p, ms), t)[0]
    else:
        apply = lambda p, t, ms: model.apply({"params": p}, t)
    if cfg.lm_parallelism == "pp":
        if cfg.lm_model_axis <= 0:
            raise ValueError(
                "pp checkpoint config has unresolved lm_model_axis=0 "
                "(written before stage counts were recorded) — evaluate "
                "in-trainer or pass the stage count explicitly")
        from ps_pytorch_tpu.parallel.pp import unstack_stage_params
        to_tree = unstack_stage_params

    @jax.jit
    def loss_fn(params, tokens, moe_state=None):
        logits = apply(params, tokens, moe_state).astype(jnp.float32)
        if logits.ndim == 4:    # several prediction heads: head 0 is the next token's
            logits = logits[:, :, 0]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()

    return loss_fn, to_tree


def build_lm_template(cfg):
    """Template TrainState for deserializing an LM checkpoint outside the
    trainer (polling evaluator, generate.py CLI): same model family and
    optimizer construction as LMTrainer, so the tree structure matches
    byte-for-byte. Layout normalization (pp stage-stacking -> plain tree)
    stays with ``build_lm_oracle``'s to_tree — one source of truth."""
    import jax.numpy as jnp

    from ps_pytorch_tpu.models.moe import MOE_STATE
    from ps_pytorch_tpu.optim import build_schedule
    from ps_pytorch_tpu.optim.sgd import sgd
    from ps_pytorch_tpu.parallel.dp import TrainState

    model = build_lm_model(cfg)
    init_len = min(cfg.lm_seq_len, 128)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, init_len), jnp.int32),
                           positions=jnp.arange(init_len))
    params = variables["params"]
    if cfg.lm_parallelism == "pp":
        from ps_pytorch_tpu.parallel.pp import stack_stage_params
        params = stack_stage_params(params, cfg.lm_model_axis)
    tx = sgd(lr=build_schedule(cfg), momentum=cfg.momentum,
             weight_decay=cfg.weight_decay, nesterov=cfg.nesterov)
    # batch_stats: an MoE model's MOE_STATE collection, {} for most archs
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=tx.init(params),
                      batch_stats=variables.get(MOE_STATE, {}))
