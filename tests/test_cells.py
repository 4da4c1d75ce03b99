"""The benchmark's cells against the program, without the chip.

Reads BENCHMARK.json, benchmark/configs/*.json, benchmark/traffic/*.json and
the drivers' fixed arguments; edits none of them. A cell whose argv the entry
point refuses costs a chip run to find out (PR 25's parent exited 2 on its
cell's argv); here it costs a parse. Nothing is allocated: parameter counts
come from ``jax.eval_shape``.
"""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from ps_pytorch_tpu.config import config_from_args

REPO = Path(__file__).resolve().parents[1]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = {c["name"]: c for c in BENCH["workloads"]}
# What each configuration file states, under the key it states it by.
PARAMETERS = {"resnet18_cifar10": ("parameters", 11_173_962),
              "gpt2_medium": ("parameters_as_run_s1024", 406_188_032),
              "olmoe_1b_7b": ("parameters_as_run", 1_045_186_560)}


def _json(kind, name):
    return json.loads((REPO / "benchmark" / kind / f"{name}.json").read_text())


@pytest.fixture
def harness(monkeypatch):
    """benchmark/harness.py, found as benchmark/run.py finds it."""
    monkeypatch.syspath_prepend(str(REPO / "benchmark"))
    import harness
    return harness


def _cell_config(harness, cell_name, tmp_path):
    """The TrainConfig of a cell: harness.run_cell's argv through the entry
    point's own parser and TrainConfig's validation."""
    cell = CELLS[cell_name]
    config = _json("configs", cell["config"])
    traffic = _json("traffic", cell["traffic"])
    driver = harness.Files().module("drivers", config["driver"] + ".py")
    argv = (list(config["program_args"]) + list(traffic["args"])
            + list(driver.FIXED_ARGS)
            + ["--seed", "27", "--max-steps", str(10 ** 9),
               "--train-dir", str(tmp_path / "train_dir"),
               "--metrics-file", str(tmp_path / "metrics.jsonl")])
    return config_from_args(argv)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_argv_is_accepted_by_its_entry_point(cell, harness, tmp_path):
    cfg = _cell_config(harness, cell, tmp_path)
    assert cfg.eval_freq == 0 and cfg.resume is False
    assert cfg.max_steps == 10 ** 9 and cfg.log_every == 1


def _n_params(tree):
    return sum(math.prod(l.shape) for l in jax.tree.leaves(tree))


@pytest.mark.parametrize("config", sorted(PARAMETERS))
def test_config_has_its_stated_parameter_count(config, harness, tmp_path):
    key, stated = PARAMETERS[config]
    assert _json("configs", config)[key] == stated
    # The configuration's first cell gives the sequence length (GPT-2's
    # position table has one row a position).
    cell = next(c["name"] for c in BENCH["workloads"] if c["config"] == config)
    cfg = _cell_config(harness, cell, tmp_path)
    if _json("configs", config)["driver"] == "train":
        from ps_pytorch_tpu.data.datasets import sample_shape
        from ps_pytorch_tpu.models import build_model
        model = build_model(cfg.network, cfg.num_classes, cfg.compute_dtype)
        x = jnp.zeros((1, *sample_shape(cfg.dataset)), jnp.float32)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), x, train=False))
    else:
        from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
        model = build_lm_model(cfg, attention_impl="full")
        tokens = jnp.zeros((1, cfg.lm_seq_len), jnp.int32)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), tokens))
    assert _n_params(shapes["params"]) == stated
