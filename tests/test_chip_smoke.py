"""chip_smoke.py off the chip.

The script itself has no CPU mode (it must fail in a sandbox without an
accelerator). Its legs take sizes, so they are rehearsed here tiny, on the
8-device CPU mesh with the Pallas kernels under the interpreter — the only
way the file runs off the chip. Also pins where the compile cache lands.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke
from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache


def test_train_leg_tiny(tmp_path):
    info = chip_smoke.train_leg(
        str(tmp_path), network="LeNet", dataset="synthetic_mnist",
        per_device_batch=8, steps=4, dtype="float32", device_metrics=False)
    # 8 devices -> the leg runs K-of-N with K=7, so the mask is exercised.
    assert info["devices"] == 8 and info["participating"] == 7
    assert info["global_batch"] == 64 and info["set_up_s"] > 0


def test_kernel_leg_tiny():
    info = chip_smoke.kernel_leg(
        interpret=True, attn_shape=(1, 2, 128, 64), conv_shape=(4, 8, 8, 16),
        quant_n=5000)
    assert {"flash_f32", "flash_bf16", "conv_taps9", "conv_im2col",
            "quantize_int8"} <= set(info)


def test_lm_leg_tiny(tmp_path):
    info = chip_smoke.lm_leg(
        str(tmp_path), d_model=32, layers=2, heads=2, vocab=61, seq_len=128,
        batch=2, steps=4, dtype="float32", device_metrics=False)
    # Several devices shard the sequence, which only ring attention serves.
    assert info["devices"] == 8 and info["attention"] == "ring"


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=str(REPO))
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    for line in proc.stdout.splitlines():   # no result line of any kind
        assert not line.startswith("{"), line


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/placed/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_compile_cache_defaults_to_the_checkout_from_any_cwd(
        monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
