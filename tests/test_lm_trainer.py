"""LM entry point (runtime/lm_trainer.py, train_lm.py): long-context
training through the standard config/checkpoint/metrics contract, on the
8-device CPU mesh (ring attention, sequence sharded)."""

import pathlib

import numpy as np
import pytest

from conftest import free_port
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.data.text import TokenLoader, synthetic_tokens

REPO = pathlib.Path(__file__).resolve().parent.parent


def _cfg(tmp_path, **kw):
    base = dict(batch_size=8, lr=0.3, momentum=0.9, max_steps=40,
                eval_freq=0, log_every=100, lm_seq_len=128,
                lm_d_model=64, lm_layers=2, lm_heads=4,
                lm_corpus_tokens=120_000, train_dir=str(tmp_path))
    base.update(kw)
    return TrainConfig(**base)


def test_token_loader_shards_disjoint_and_shapes():
    toks = synthetic_tokens(50_000, vocab=64, seed=3)
    l0 = TokenLoader(toks, 8, 128, seed=1, host_id=0, num_hosts=2)
    l1 = TokenLoader(toks, 8, 128, seed=1, host_id=1, num_hosts=2)
    assert set(l0._order(0)).isdisjoint(l1._order(0))
    b = l0.next_batch()
    assert b.shape == (4, 128) and b.dtype == np.int32


def test_token_loader_rejects_bad_geometry():
    toks = synthetic_tokens(1_000, vocab=16)
    with pytest.raises(ValueError):
        TokenLoader(toks, 7, 128, num_hosts=2)      # divisibility
    with pytest.raises(ValueError):
        TokenLoader(toks, 512, 128)                 # too few windows


def test_lm_trains_below_uniform_floor_and_evaluates(tmp_path):
    """Next-token loss on the Markov stream must fall far below the
    uniform floor log(vocab) and generalize to the held-out tail."""
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    t = LMTrainer(_cfg(tmp_path))
    t.train()
    r = t.evaluate(max_batches=4)
    assert r["loss"] < 0.4 * np.log(256), r
    assert r["perplexity"] < 256 ** 0.4


def test_lm_checkpoint_resume(tmp_path):
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    cfg = _cfg(tmp_path, max_steps=10, eval_freq=5)
    LMTrainer(cfg).train()
    t2 = LMTrainer(cfg.replace(max_steps=12))
    t2.train()
    assert t2.start_step == 10          # resumed, not retrained
    assert int(t2.state.step) == 12


@pytest.mark.parametrize("mode,extra", [
    ("tp", dict(lm_model_axis=4)),
    ("pp", dict(lm_model_axis=4, lm_layers=4, lm_microbatches=2)),
    ("ep", dict(lm_experts=8)),
])
def test_lm_parallelism_modes_train_and_evaluate(tmp_path, mode, extra):
    """tp/pp/ep through the SAME entry-point contract as sp: loss falls
    well below the uniform floor and the oracle eval generalizes."""
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    t = LMTrainer(_cfg(tmp_path, lm_parallelism=mode, max_steps=30, **extra))
    t.train()
    r = t.evaluate(max_batches=2)
    assert r["loss"] < 0.5 * np.log(256), (mode, r)


def test_tokens_from_file_bytes_and_validation(tmp_path):
    from ps_pytorch_tpu.data.text import tokens_from_file

    p = tmp_path / "corpus.bin"
    p.write_bytes(bytes(range(256)) * 4)
    toks = tokens_from_file(str(p))
    assert toks.dtype == np.int32 and len(toks) == 1024
    assert toks[:256].tolist() == list(range(256))
    assert len(tokens_from_file(str(p), max_tokens=100)) == 100
    with pytest.raises(ValueError, match="vocab"):
        tokens_from_file(str(p), vocab=64)
    empty = tmp_path / "empty.bin"
    empty.write_bytes(b"")
    with pytest.raises(ValueError, match="empty"):
        tokens_from_file(str(empty))


def test_lm_trains_on_real_byte_corpus(tmp_path):
    """The real-data LM path: a byte-level corpus from an actual file must
    train below the uniform floor (repetitive text, so it is learnable in
    few steps)."""
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    corpus = tmp_path / "corpus.txt"
    corpus.write_bytes(("".join(f"line {i % 7} of the corpus\n"
                                for i in range(8000))).encode())
    t = LMTrainer(_cfg(tmp_path, lm_corpus_file=str(corpus), max_steps=30))
    t.train()
    r = t.evaluate(max_batches=2)
    assert r["loss"] < 0.4 * np.log(256), r


@pytest.mark.parametrize("mode,extra", [
    ("sp", {}),
    ("pp", dict(lm_model_axis=4, lm_layers=4, lm_microbatches=2)),
])
def test_standalone_evaluator_scores_lm_checkpoints(tmp_path, mode, extra):
    """The polling-evaluator contract (reference distributed_evaluator.py)
    extends to LM checkpoints: self-describing config -> EVAL_LM line with
    held-out loss below the uniform floor."""
    from ps_pytorch_tpu.runtime.evaluator import Evaluator
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
    from ps_pytorch_tpu.runtime import checkpoint as ckpt

    cfg = _cfg(tmp_path, lm_parallelism=mode, max_steps=30, eval_freq=30,
               **extra)
    LMTrainer(cfg).train()
    step = ckpt.latest_step(str(tmp_path))
    assert step == 30
    lines = []
    r = Evaluator(str(tmp_path), printer=lines.append).evaluate_step(step)
    assert lines and lines[0].startswith(f"EVAL_LM step {step} loss ")
    assert r["loss"] < 0.6 * np.log(256), (mode, r)


def _assert_final_agrees(leader: str, follower: str, dump: str) -> None:
    """Both processes printed a FINAL line and they are identical (the
    state is replicated/consistently sharded at the end)."""
    assert "FINAL" in leader and "FINAL" in follower, dump
    fin_l = [l for l in leader.splitlines() if l.startswith("FINAL")][-1]
    fin_f = [l for l in follower.splitlines() if l.startswith("FINAL")][-1]
    assert fin_l == fin_f, dump


def _launch_lm_2proc(tmp_path, extra_flags, max_steps=10):
    from ps_pytorch_tpu.tools import launch

    ckpt = tmp_path / "ckpt"
    run_dir = tmp_path / "run"
    rc = launch.main([
        "launch", "--run-dir", str(run_dir), "--simulate", "2",
        "--devices-per-host", "4", "--port", str(free_port()),
        "--entry", str(REPO / "train_lm.py"), "--cwd", str(REPO),
        "--wait", "--timeout", "600",
        "--",
        "--batch-size", "8", "--lr", "0.3", "--momentum", "0.9",
        "--max-steps", str(max_steps), "--eval-freq", str(max_steps),
        "--lm-seq-len", "128", "--lm-d-model", "64",
        "--lm-corpus-tokens", "120000",
        "--train-dir", str(ckpt), "--log-every", "5", *extra_flags,
    ])
    logs = [run_dir / f"proc_{i}.log" for i in range(2)]
    dump = "\n\n".join(f"== {l} ==\n{l.read_text()[-3000:]}"
                       for l in logs if l.exists())
    return rc, ckpt, logs, dump


@pytest.mark.slow
def test_lm_two_process_sequence_parallel(tmp_path):
    """Launch-driven multi-host LM (sp): 2 OS processes x 4 fake devices,
    the sequence sharded over all 8 — cross-process token globalization +
    ring attention collectives over a real jax.distributed bootstrap.
    (sp state is fully replicated, so the checkpoint gather takes
    all_replicated's local-read path; the pp test below covers the
    process_allgather branch.)"""
    rc, ckpt, logs, dump = _launch_lm_2proc(tmp_path, [])
    assert rc == 0, dump
    leader, follower = logs[0].read_text(), logs[1].read_text()
    assert "attention=ring" in leader, dump
    # Replicated state at both ends: the held-out eval agrees exactly.
    _assert_final_agrees(leader, follower, dump)
    # Leader-only write, collective gather: exactly one committed step.
    assert (ckpt / "model_step_10").is_dir(), dump


@pytest.mark.slow
def test_lm_two_process_pipeline_sharded_gather(tmp_path):
    """pp over 2 OS processes: the stage-stacked block params shard over a
    'model' axis whose columns span BOTH processes, so the checkpoint
    gather and the oracle eval MUST take all_replicated's
    process_allgather(tiled=True) branch (non-fully-addressable leaves) —
    the exact path the old tiled=False gather crashed on."""
    rc, ckpt, logs, dump = _launch_lm_2proc(
        tmp_path, ["--lm-parallelism", "pp", "--lm-model-axis", "4",
                   "--lm-layers", "4", "--lm-microbatches", "2"],
        max_steps=6)
    assert rc == 0, dump
    leader, follower = logs[0].read_text(), logs[1].read_text()
    assert "parallelism=pp" in leader, dump
    _assert_final_agrees(leader, follower, dump)
    assert (ckpt / "model_step_6").is_dir(), dump


@pytest.mark.slow
@pytest.mark.parametrize("mode,flags", [
    ("tp", ["--lm-parallelism", "tp", "--lm-model-axis", "4"]),
    ("ep", ["--lm-parallelism", "ep", "--lm-experts", "8"]),
])
def test_lm_two_process_tp_ep(tmp_path, mode, flags):
    """tp over 2 OS processes proves GSPMD collectives across a real
    process boundary; ep proves the MoE dispatch all_to_all crossing
    processes (the DeepSpeed-MoE wire pattern). Both end with identical
    FINAL lines on each process and a committed checkpoint."""
    rc, ckpt, logs, dump = _launch_lm_2proc(tmp_path, flags, max_steps=6)
    assert rc == 0, dump
    leader, follower = logs[0].read_text(), logs[1].read_text()
    assert f"parallelism={mode}" in leader, dump
    _assert_final_agrees(leader, follower, dump)
    assert (ckpt / "model_step_6").is_dir(), dump


@pytest.mark.parametrize("mode,extra", [
    ("sp", {}),
    ("tp", dict(lm_model_axis=4)),
    ("pp", dict(lm_model_axis=4, lm_layers=4, lm_microbatches=2)),
    ("ep", dict(lm_experts=8)),
])
def test_lm_remat_is_numerically_identical(tmp_path, mode, extra):
    """--remat trades FLOPs for activation memory; it must not change the
    math (same seed + batches -> same held-out loss)."""
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    losses = {}
    for remat in (False, True):
        # float32: in bfloat16 the recomputed block rounds where XLA fuses
        # differently, and the two runs part by 1e-4
        t = LMTrainer(_cfg(tmp_path / f"r{remat}", lm_parallelism=mode,
                           max_steps=4, remat=remat, compute_dtype="float32",
                           **extra))
        t.train()
        losses[remat] = t.evaluate(max_batches=1)["loss"]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)


def test_lm_parallelism_resume_same_mode(tmp_path):
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    cfg = _cfg(tmp_path, lm_parallelism="pp", lm_model_axis=4, lm_layers=4,
               lm_microbatches=2, max_steps=6, eval_freq=3)
    LMTrainer(cfg).train()
    t2 = LMTrainer(cfg.replace(max_steps=8))
    t2.train()
    assert t2.start_step == 6
    assert int(t2.state.step) == 8


# ---- the olmoe arch through the same contract (dropless MoE: one device) ----

_OLMOE = dict(lm_arch="olmoe", lm_parallelism="ep", lm_experts=8,
              lm_moe_top_k=4, lm_ffn_dim=32, lm_vocab=97, lm_seq_len=32,
              batch_size=4, lr=0.05, log_every=1, lm_corpus_tokens=20_000)


def _one_device(monkeypatch):
    """Dropless routing across chips is not built: give the trainer one of
    the test mesh's eight devices, as a one-chip machine would."""
    import jax
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)


def test_olmoe_trains_saves_resumes_and_evaluates(tmp_path, monkeypatch):
    """--lm-arch olmoe through LMTrainer: 3 steps, a checkpoint that
    describes itself, a resume, the in-trainer eval and the standalone
    lm_eval oracle agreeing on the restored weights, and the routing counters
    in the JSONL record and the registry."""
    import json

    import jax.numpy as jnp

    import generate
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.lm_eval import (
        build_lm_oracle, build_lm_template,
    )
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    _one_device(monkeypatch)
    metrics = tmp_path / "metrics.jsonl"
    cfg = _cfg(tmp_path, max_steps=3, eval_freq=3, metrics_file=str(metrics),
               **_OLMOE)
    LMTrainer(cfg).train()
    t2 = LMTrainer(cfg.replace(max_steps=4))
    t2.train()
    assert t2.start_step == 3 and int(t2.state.step) == 4
    r = t2.evaluate(max_batches=2)
    assert np.isfinite(r["loss"]) and r["batches"] == 2

    records = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert [rec["step"] for rec in records] == [1, 2, 3, 4]
    for rec in records:
        assert rec["moe_dropped"] == 0.0
        assert 1.0 <= rec["expert_load_max_over_mean"] <= 2.0   # 8 experts, top-4
        assert np.isfinite(rec["z_loss"]) and np.isfinite(rec["aux"])
    assert t2.registry.get("moe_dropped") == 0.0
    assert t2.registry.get("expert_load_max_over_mean") == \
        records[-1]["expert_load_max_over_mean"]

    with open(f"{ckpt.checkpoint_path(str(tmp_path), 4)}/config.json") as f:
        saved = TrainConfig.from_json(f.read())
    assert (saved.lm_arch, saved.lm_ffn_dim, saved.network) == \
        ("olmoe", 32, "MoETransformerLM")
    state, _, _ = ckpt.load_checkpoint(str(tmp_path), 4,
                                       build_lm_template(saved))
    loss_fn, to_tree = build_lm_oracle(saved)
    tokens = TokenLoader(t2.val_tokens, 4, 32, seed=0,
                         shuffle=False).next_batch()
    np.testing.assert_allclose(
        float(loss_fn(to_tree(state.params), jnp.asarray(tokens))),
        t2._oracle_eval_fn()(jnp.asarray(tokens)), rtol=1e-6)

    with pytest.raises(SystemExit):     # decoding this arch: one clear refusal
        generate.main(["--train-dir", str(tmp_path), "--prompt", "a"])


def test_olmoe_checkpoint_refuses_to_resume_under_gpt2(tmp_path, monkeypatch):
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    _one_device(monkeypatch)
    cfg = _cfg(tmp_path, max_steps=1, eval_freq=1, **_OLMOE)
    LMTrainer(cfg).train()
    other = cfg.replace(lm_arch="gpt2", lm_moe_top_k=2, max_steps=2)
    with pytest.raises(ValueError, match="lm_arch=olmoe"):
        LMTrainer(other).train()


# ---- PR 32: the sp step with ops/next_token_loss.py against the loss it replaced ----

@pytest.mark.parametrize("n_dev,dtype", [(1, "float32"), (1, "bfloat16"),
                                         (8, "float32"), (8, "bfloat16")])
def test_sp_step_is_the_one_with_the_loss_spelled_the_parents_way(
        monkeypatch, n_dev, dtype):
    """One momentum-SGD step of ``make_sp_train_step`` (one device; the ring
    over eight, where a shard's last target comes from the next shard)
    against the same step with ``parallel/sp.py``'s loss as it was spelled
    before PR 32: the whole logits cast to float32, optax, the weights."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.optim.sgd import sgd
    from ps_pytorch_tpu.parallel import sp

    def parent_loss(logits, targets, weights):
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), targets)
        return jnp.sum(per_tok * weights), jnp.sum(weights)

    seq, vocab = 64, 97
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    model = TransformerLM(
        vocab_size=vocab, n_layers=2, n_heads=4, d_model=32, max_seq_len=seq,
        dtype=jnp.dtype(dtype), axis_name="data",
        attention_impl="ring" if n_dev > 1 else "full")
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, vocab, (2, seq)), jnp.int32)
    tx = sgd(lr=0.1, momentum=0.9, weight_decay=1e-4)
    state = sp.create_lm_train_state(model, tx, mesh, (2, seq),
                                     jax.random.key(3))
    got_state, got = sp.make_sp_train_step(model, tx, mesh, donate=False)(
        state, tokens)
    monkeypatch.setattr(sp, "next_token_loss", parent_loss)
    want_state, want = sp.make_sp_train_step(model, tx, mesh, donate=False)(
        state, tokens)

    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]),
                               rtol=1e-6)
    # float32: the two softmaxes differ in the last bit; bfloat16: dlogits is
    # the same rounding (test_next_token_loss.py), the sums' order is XLA's,
    # and over the ring XLA also fuses the bfloat16 hops another way round
    # the other loss (1.7e-5 on parameters that move by up to 1e-2)
    atol = {"float32": 5e-7, "bfloat16": 2e-6 if n_dev == 1 else 5e-5}[dtype]
    moved = 0.0
    for a, b, c in zip(*(jax.tree.leaves(jax.device_get(t.params))
                         for t in (got_state, want_state, state))):
        np.testing.assert_allclose(a, b, atol=atol, rtol=0)
        moved = max(moved, float(np.abs(a - c).max()))
    assert moved > 1e-3
