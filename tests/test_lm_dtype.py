"""The dtype contract of the LM step: ``--compute-dtype`` reaches the LM
classes through ``runtime/lm_eval.build_lm_model`` (bfloat16 by default, as for
the CNNs), activations and matmul inputs are that dtype, and parameters,
gradients, momentum, the router, the logits the loss sees and the loss stay
float32."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models.moe import MoETransformerLM
from ps_pytorch_tpu.models.transformer import TransformerLM
from ps_pytorch_tpu.runtime.lm_eval import (
    build_lm_model, build_lm_oracle, build_lm_template,
)

_TINY = dict(lm_vocab=97, lm_d_model=64, lm_layers=2, lm_heads=4,
             lm_seq_len=32, batch_size=4, lm_corpus_tokens=20_000)
ARCH = {
    "gpt2": dict(_TINY),
    "olmoe": dict(_TINY, lm_arch="olmoe", lm_parallelism="ep", lm_experts=8,
                  lm_moe_top_k=4, lm_ffn_dim=32),
}


def _cfg(arch, **kw):
    return TrainConfig(**{**ARCH[arch], **kw})


def _tokens():
    return jnp.asarray(np.random.default_rng(7).integers(0, 97, (2, 32)),
                       jnp.int32)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _logits(model, params, tokens):
    out = model.apply({"params": params}, tokens)
    return out[0] if isinstance(out, tuple) else out


@pytest.mark.parametrize("arch", sorted(ARCH))
def test_default_config_computes_in_bfloat16(arch):
    """Every intermediate of the model a default config builds is bfloat16,
    the logits it returns too (the loss casts them), but the router's
    outputs and the routing statistics (scalars); the parameters it
    initialises are float32."""
    cfg = _cfg(arch)
    assert cfg.compute_dtype == "bfloat16"
    model = build_lm_model(cfg)
    assert model.dtype == jnp.bfloat16
    tokens = _tokens()
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]
    assert {a.dtype for a in jax.tree.leaves(params)} == {jnp.dtype("float32")}
    _, state = jax.eval_shape(
        lambda p, t: model.apply({"params": p}, t, capture_intermediates=True,
                                 mutable=["intermediates"]), params, tokens)
    seen = set()
    for path, a in jax.tree_util.tree_flatten_with_path(
            state["intermediates"])[0]:
        name = jax.tree_util.keystr(path)
        if a.ndim == 0:                       # aux, z_loss and the counters
            assert a.dtype == jnp.float32, name
        elif "router" in name:
            assert a.dtype == jnp.float32, name
        else:
            assert a.dtype == jnp.bfloat16, name
            seen.add(name.split("'")[1])
    assert {"__call__", "block_0", "block_1", "ln_f", "lm_head",
            "tok_embed"} <= seen


@pytest.mark.parametrize("arch", sorted(ARCH))
def test_float32_config_is_the_model_as_it_was(arch):
    """``compute_dtype="float32"`` builds what the classes build with no dtype
    given (the parent's model: ``tests/test_arch_gpt2.py`` holds its golden
    hashes): same parameter tree and values, logits bit for bit. The
    bfloat16 build initialises the very same float32 parameters."""
    cfg = _cfg(arch, compute_dtype="float32")
    built = build_lm_model(cfg)
    assert built.dtype == jnp.float32
    geo = dict(vocab_size=97, d_model=64, n_layers=2, n_heads=4, max_seq_len=32)
    plain = TransformerLM(**geo) if arch == "gpt2" else MoETransformerLM(
        n_experts=8, top_k=4, arch="olmoe", ffn_dim=32, **geo)
    tokens = _tokens()
    p_built = built.init(jax.random.key(0), tokens)["params"]
    p_plain = plain.init(jax.random.key(0), tokens)["params"]
    p_bf16 = build_lm_model(_cfg(arch)).init(jax.random.key(0),
                                             tokens)["params"]
    for other in (p_plain, p_bf16):
        assert jax.tree.structure(other) == jax.tree.structure(p_built)
        for a, b in zip(jax.tree.leaves(other), jax.tree.leaves(p_built)):
            assert a.dtype == b.dtype == jnp.float32
            np.testing.assert_array_equal(a, b)
    a = np.asarray(_logits(built, p_built, tokens))
    b = np.asarray(_logits(plain, p_plain, tokens))
    assert a.dtype == np.float32 and a.tobytes() == b.tobytes()
    # and bfloat16 is a different computation of the same function
    c = _logits(build_lm_model(_cfg(arch)), p_built, tokens)
    assert c.dtype == jnp.bfloat16
    assert 0 < np.abs(np.asarray(c, np.float32) - a).max() < 0.3


@pytest.mark.parametrize("arch", sorted(ARCH))
def test_trainer_oracle_and_template_build_the_same_dtype(arch, tmp_path,
                                                          monkeypatch):
    """One construction: what the flag says is what the trainer, the oracle
    and the checkpoint template compute in, and the template's state is
    float32 either way (a checkpoint from before the flag was wired resumes)."""
    from ps_pytorch_tpu.runtime import lm_eval
    built = []
    real = lm_eval.build_lm_model
    monkeypatch.setattr(lm_eval, "build_lm_model",
                        lambda cfg, **kw: built.append(real(cfg, **kw))
                        or built[-1])
    for name in ("bfloat16", "float32"):
        cfg = _cfg(arch, compute_dtype=name, train_dir=str(tmp_path),
                   network="MoETransformerLM" if arch == "olmoe"
                   else "TransformerLM")
        del built[:]
        build_lm_oracle(cfg)
        template = build_lm_template(cfg)
        assert [m.dtype for m in built] == [jnp.dtype(name)] * 2
        assert {a.dtype for a in jax.tree.leaves(template)
                if jnp.issubdtype(a.dtype, jnp.floating)} == \
            {jnp.dtype("float32")}


@pytest.mark.parametrize("arch", sorted(ARCH))
def test_one_step_keeps_state_gradients_and_loss_float32(
        arch, tmp_path, monkeypatch, capsys):
    """One ``LMTrainer`` step in bfloat16: every leaf of ``params`` and
    ``opt_state`` and every gradient leaf handed to ``optim/sgd.py`` is
    float32, the logged loss a float32 scalar; the ``KERNELS`` line, the first
    JSONL record and the gauge say what the built model computes in."""
    from ps_pytorch_tpu.runtime import lm_trainer
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    one = jax.devices()[:1]     # flash and dropless routing are one-chip paths
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    handed, real_sgd = [], lm_trainer.sgd

    def spy_sgd(**kw):
        tx = real_sgd(**kw)

        def update(grads, state, params=None):
            handed.append(jax.tree.map(lambda g: g.dtype, grads))
            return tx.update(grads, state, params)
        return optax.GradientTransformation(tx.init, update)

    monkeypatch.setattr(lm_trainer, "sgd", spy_sgd)
    metrics = tmp_path / "m.jsonl"
    t = LMTrainer(_cfg(arch, lm_attention="flash", lr=0.05, momentum=0.9,
                       max_steps=2, eval_freq=0, log_every=1,
                       train_dir=str(tmp_path), metrics_file=str(metrics)))
    step_fn, step_metrics = t.step_fn, []

    def spy_step(state, tokens):
        state, m = step_fn(state, tokens)
        step_metrics.append(m)
        return state, m

    t.step_fn = spy_step
    t.train()

    f32 = {jnp.dtype("float32")}
    assert handed and {d for tree in handed
                       for d in jax.tree.leaves(tree)} == f32
    assert jax.tree.structure(handed[0]) == jax.tree.structure(t.state.params)
    for tree in (t.state.params, t.state.opt_state):
        assert {a.dtype for a in jax.tree.leaves(tree)
                if jnp.issubdtype(a.dtype, jnp.floating)} == f32
    for m in step_metrics:
        assert m["loss"].dtype == jnp.float32 and m["loss"].shape == ()
    # the loss casts the model's bfloat16 logits: every exp, log and reduction
    # over the vocabulary in the step runs on float32
    over_vocab = [(e.primitive.name, v.aval.dtype)
                  for e in _eqns(jax.make_jaxpr(step_fn)(
                      t.state, jnp.zeros((4, 32), jnp.int32)).jaxpr)
                  if e.primitive.name in ("exp", "log", "reduce_max",
                                          "reduce_sum")
                  for v in e.invars
                  if getattr(v.aval, "shape", ())[-1:] == (97,)]
    assert over_vocab and {d for _, d in over_vocab} == f32

    assert t.model.dtype == jnp.bfloat16
    line = next(l for l in capsys.readouterr().out.splitlines()
                if l.startswith("KERNELS "))
    assert line.endswith(" dtype=bfloat16") and "flash_attention[" in line
    # the schedule's record: both passes, and what the backward's loops visit
    assert " grid=" in line and " bwd g=" in line and " dq_partials=" in line
    assert " bwd_visits=" in line and " tiles=" in line
    records = [json.loads(l) for l in metrics.read_text().splitlines()]
    assert [r.get("compute_dtype") for r in records] == ["bfloat16", None]
    assert t.registry.get("compute_dtype") == 2


def test_a_float32_run_says_float32(tmp_path, monkeypatch, capsys):
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    metrics = tmp_path / "m.jsonl"
    t = LMTrainer(_cfg("gpt2", compute_dtype="float32", lm_attention="flash",
                       max_steps=1, eval_freq=0, log_every=1,
                       train_dir=str(tmp_path), metrics_file=str(metrics)))
    t.train()
    assert " dtype=float32" in capsys.readouterr().out
    assert json.loads(metrics.read_text().splitlines()[0])[
        "compute_dtype"] == "float32"
    assert t.registry.get("compute_dtype") == 4


def test_an_unknown_compute_dtype_is_refused():
    with pytest.raises(KeyError, match="float8"):
        build_lm_model(_cfg("gpt2", compute_dtype="float8"))
