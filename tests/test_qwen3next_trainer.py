"""The ``qwen3next`` arch on ``train_lm.py``'s path: ``LMTrainer`` under
``--lm-parallelism ep`` on one device (a file of its own, so that the test
runner can give it a worker of its own: it builds four trainers)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.transformer import ARCHS

S, VOCAB = 96, 97       # tests/test_qwen3next.py's tiny size: a chunk and a half


@pytest.fixture(autouse=True)
def tiny_linear_layers(monkeypatch):
    """The linear layers' sizes are the arch row's, not flags."""
    monkeypatch.setitem(tr_mod.ARCHS, "qwen3next", ARCHS["qwen3next"]._replace(
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16))


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)


def _trainer_cfg(tmp_path, **kw):
    base = dict(batch_size=2, lr=0.05, momentum=0.9, eval_freq=0, log_every=1,
                lm_seq_len=S, lm_vocab=VOCAB, lm_d_model=32, lm_layers=4,
                lm_heads=4, lm_kv_heads=2, lm_head_dim=16, lm_ffn_dim=16,
                lm_experts=16, lm_experts_held=4, lm_moe_top_k=3,
                lm_arch="qwen3next", lm_parallelism="ep",
                lm_attention="flash", remat=True, compute_dtype="float32",
                lm_corpus_tokens=20_000, donate=False,
                train_dir=str(tmp_path))
    base.update(kw)
    return TrainConfig(**base)


def test_lm_trainer_trains_logs_the_counter_and_resumes(tmp_path, capsys):
    """Three steps and a checkpoint, a second trainer that resumes from it bit
    for bit and goes on; the loss falls; every record and the registry carry
    ``gdn_state_abs_max`` beside the routing statistics; the ``KERNELS`` line
    prints the delta rule's schedule and the mixer ops' beside the flash
    record and the grouped matmul."""
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    metrics = tmp_path / "metrics.jsonl"
    cfg = _trainer_cfg(tmp_path, max_steps=3, eval_freq=3,
                       metrics_file=str(metrics))
    first = LMTrainer(cfg)
    kernels = next(line for line in capsys.readouterr().out.splitlines()
                   if line.startswith("KERNELS"))
    assert kernels.count("flash_attention[") == 1     # one kind of attention layer
    assert "gated_delta_rule[chunk=64 chunks=2 group=2 grid=4x1 heads=2 solve_grid=4x1 " in kernels
    assert " gdn_mix[lanes=32 rows=96 chunk=96 halo=16 conv_grid=2x4x1 norm_grid=2x2x1 conv_fwd_bytes=" in kernels
    assert "grouped_matmul mode=interpret dtype=float32" in kernels
    first.train()
    resumed = LMTrainer(cfg.replace(max_steps=8, eval_freq=0))
    assert resumed.maybe_resume() and resumed.start_step == 3
    a, b = jax.device_get((first.state, resumed.state))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    resumed.train()
    assert int(resumed.state.step) == 8

    records = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert [r["step"] for r in records] == list(range(1, 9))
    assert records[0]["compute_dtype"] == "float32"
    assert records[-1]["loss"] < records[0]["loss"]
    for r in records:
        assert 0 < r["gdn_state_abs_max"] < 50
        assert r["moe_dropped"] == 0.0 and r["aux"] > 0
        assert 0.1 < r["moe_held_share"] < 0.5
        assert "ssm_state_abs_max" not in r
    assert resumed.registry.get("gdn_state_abs_max") == \
        records[-1]["gdn_state_abs_max"]

    other = LMTrainer(cfg.replace(lm_layers=8))
    with pytest.raises(ValueError, match="lm_layers=4"):
        other.maybe_resume()


def test_remat_changes_no_step_and_bfloat16_reaches_the_layers(tmp_path):
    """``--remat`` is the same step (the delta rule's forward run again gives
    the same states to its backward), and ``--compute-dtype bfloat16`` reaches
    the linear layers: their output leaves in it while the gate and the
    kernel's kept states stay float32."""
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    states = []
    for remat in (True, False):
        t = LMTrainer(_trainer_cfg(tmp_path / str(remat), remat=remat,
                                   max_steps=2))
        t.train()
        states.append(jax.device_get(t.state.params))
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(states[0])[0],
                            jax.tree.leaves(states[1])):
        np.testing.assert_allclose(x, y, atol=1e-6, rtol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))

    narrow = LMTrainer(_trainer_cfg(tmp_path / "bf16", max_steps=1,
                                    compute_dtype="bfloat16"))
    assert narrow.model.dtype == jnp.bfloat16
    model = narrow.model.clone(ep_axis=None, n_local_experts=None)
    tokens = jnp.zeros((1, S), jnp.int32)
    _, state = jax.eval_shape(
        lambda v: model.apply(v, tokens, capture_intermediates=True,
                              mutable=["intermediates"]),
        {"params": narrow.state.params})
    block = state["intermediates"]["block_0"]
    assert block["out_proj"]["__call__"][0].dtype == jnp.bfloat16
    # the output norm is ``ops/gdn_mix.gated_rms_norm`` since PR 41; the
    # module under its name only hands that op the float32 scale
    assert block["gdn_norm"]["__call__"][0].dtype == jnp.float32
    assert all(a.dtype == jnp.float32
               for a in jax.tree.leaves(narrow.state.params))
    narrow.train()
    assert int(narrow.state.step) == 1
