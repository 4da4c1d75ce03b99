"""Test harness: fake 8-device CPU mesh (SURVEY §4 'implication for the new
build') — the standard JAX mechanism for exercising multi-device collective
code without TPUs. Must run before jax initializes its backends."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Env, not only config: the child processes tests spawn inherit it.
os.environ["JAX_NUM_CPU_DEVICES"] = "8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# Also through config, in case a plugin imported jax before this file ran.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: a multi-process launch, or a whole train step compiled "
        "at a cell's full size (the chip run measures that it fits)")


@pytest.fixture(scope="session")
def mesh8():
    from ps_pytorch_tpu.parallel import make_mesh
    return make_mesh(data=8)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def committed_record():
    """-> load(name): a committed root-level JSON record, e.g. a drill's
    RESILIENCE_r*.json, parsed afresh on every call."""
    import json
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    return lambda name: json.loads((root / name).read_text())


# An OS-assigned free TCP port for launch-driven multi-process tests: the
# launcher's own, imported by the test files from here.
from ps_pytorch_tpu.tools.launch import free_port  # noqa: E402,F401
