"""The ``phi4flash`` arch on ``train_lm.py``'s path: ``LMTrainer`` under
``--lm-parallelism sp`` on one device (a file of its own, so that the test
runner can give it a worker of its own: it builds three trainers)."""

import json

import jax
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.transformer import ARCHS, COUNTER_NAMES

S, WINDOW, VOCAB = 24, 5, 53        # tests/test_phi4flash.py's tiny size
# what this arch's layers count, of COUNTER_NAMES
HYBRID_COUNTERS = ("ssm_state_abs_max", "diff_lambda_max")
assert set(HYBRID_COUNTERS) < set(COUNTER_NAMES)


@pytest.fixture(autouse=True)
def tiny_window(monkeypatch):
    """The window is the arch row's, not a flag: a row whose window closes at
    S=24."""
    monkeypatch.setitem(tr_mod.ARCHS, "phi4flash",
                        ARCHS["phi4flash"]._replace(window=WINDOW))


def _trainer_cfg(tmp_path, **kw):
    base = dict(network="TransformerLM", lm_arch="phi4flash", batch_size=2,
                lr=0.05, momentum=0.9, eval_freq=0, log_every=1, lm_seq_len=S,
                lm_vocab=VOCAB, lm_d_model=32, lm_layers=8, lm_heads=4,
                lm_kv_heads=2, lm_head_dim=8, lm_ffn_dim=48,
                lm_attention="flash", remat=True, compute_dtype="float32",
                lm_corpus_tokens=20_000, donate=False,
                train_dir=str(tmp_path))
    base.update(kw)
    return TrainConfig(**base)


def test_lm_trainer_trains_logs_the_counters_and_resumes(
        tmp_path, monkeypatch, capsys):
    """``train_lm.py``'s path: ``LMTrainer`` under sp on one device. Three
    steps and a checkpoint, a second trainer that resumes from it bit for bit
    and goes on; every record and the registry carry both counters; the
    ``KERNELS`` line prints a flash record a kind of attention layer and the
    scan's schedule; a checkpoint of another depth is refused."""
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    metrics = tmp_path / "metrics.jsonl"
    cfg = _trainer_cfg(tmp_path, max_steps=3, eval_freq=3,
                       metrics_file=str(metrics))
    first = LMTrainer(cfg)
    kernels = next(line for line in capsys.readouterr().out.splitlines()
                   if line.startswith("KERNELS"))
    # S = 24 is over the tiny window of 5: a record for the window layers and
    # one for the full and cross layers, each a call of half the heads
    assert kernels.count("flash_attention[") == 2 and "window=5" in kernels
    assert "selective_scan[chunk=24 chunks=1 grid=2x1x1" in kernels
    first.train()
    resumed = LMTrainer(cfg.replace(max_steps=6, eval_freq=0))
    assert resumed.maybe_resume() and resumed.start_step == 3
    a, b = jax.device_get((first.state, resumed.state))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    resumed.train()
    assert int(resumed.state.step) == 6

    records = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert [r["step"] for r in records] == [1, 2, 3, 4, 5, 6]
    assert records[-1]["loss"] < records[0]["loss"]
    for r in records:
        assert 0 < r["ssm_state_abs_max"] < 100
        assert 0.3 < r["diff_lambda_max"] < 1.5
    for name in HYBRID_COUNTERS:
        assert resumed.registry.get(name) == records[-1][name]

    other = LMTrainer(cfg.replace(lm_layers=4))
    with pytest.raises(ValueError, match="lm_layers=8"):
        other.maybe_resume()
