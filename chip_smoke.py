#!/usr/bin/env python
"""Chip smoke test: the quickest proof that the system still starts on a TPU.

ONE process (it holds the chip and starts no child that needs it) drives the
main path once on every device ``jax.devices()`` returns, through the entry
points a user calls, and checks what comes out by the repo's own means:

- train leg:  ``train.main`` — ResNet-18 at full width on CIFAR-10 geometry,
  synthetic data from a seed, global batch 1024 per chip, bf16; K-of-N with
  K = n-1 when there are several chips. A checkpoint must commit, the FINAL
  line must print, and the metrics JSONL must carry a finite loss,
  ``participating`` equal to the mask sum, a numeric MFU and device memory.
- kernel leg: each Pallas kernel compiled by Mosaic (``interpret=False``) at
  the shape the code calls it with, compared with its ``jax.numpy``
  reference at the tolerance its own test file uses.
- LM leg:     ``train_lm.main`` at the suite geometry (d_model 512, 8
  layers, 8 heads, vocab 32,000, S=2048, batch 8) — the flash kernel on one
  chip, ring attention over several.

There is no CPU mode: any platform but ``tpu`` exits non-zero before a leg
runs. Outputs (train dirs, metrics) go under ``chiprun_out/chip_smoke/``,
wiped at start so no run resumes a stale checkpoint. The last stdout line of
a passing run is ``{"ok": true, "device": {...}}``; a failing run prints no
such line and exits non-zero. The legs take their sizes as arguments so
``tests/test_chip_smoke.py`` can rehearse them tiny under the interpreter.
"""

import contextlib
import io
import json
import math
import os
import shutil
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")


class _Tee(io.TextIOBase):
    def __init__(self, *sinks):
        self.sinks = sinks

    def write(self, s):
        for sink in self.sinks:
            sink.write(s)
        return len(s)

    def flush(self):
        for sink in self.sinks:
            sink.flush()


def _run_entry(main, argv):
    """Run an entry point's ``main(argv)``; -> its stdout (also echoed)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        rc = main(argv)
    assert rc == 0, f"entry point returned {rc}"
    out = buf.getvalue()
    assert "\nFINAL " in out, "no FINAL line"
    return out


def _read_metrics(path, steps, t_start, device_metrics):
    """Checks common to both trainers' JSONL; -> (records, timing dict).
    set_up_s runs from the leg's start to the first logged step (imports,
    build, compile, step 1); steady_step_s is the median wall time between
    later records, so it counts the host as well as the device."""
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["step"] for r in recs] == list(range(1, steps + 1)), \
        f"expected steps 1..{steps}, logged {[r['step'] for r in recs]}"
    for r in recs:
        assert math.isfinite(r["loss"]), f"step {r['step']}: loss {r['loss']}"
        if device_metrics:
            assert isinstance(r["mfu"], float) and r["mfu"] > 0, \
                f"step {r['step']}: mfu {r['mfu']!r} (FLOPs trace or peak " \
                f"lookup failed)"
            assert r.get("device_mem_peak_bytes", 0) > 0, \
                f"step {r['step']}: no device_mem_peak_bytes"
    if device_metrics:
        assert all(r["mfu"] < 1 for r in recs[2:]), \
            f"steady MFU not below 1: {[r['mfu'] for r in recs[2:]]}"
    gaps = [b["ts"] - a["ts"] for a, b in zip(recs[2:], recs[3:])]
    info = {"set_up_s": round(recs[0]["ts"] - t_start, 2),
            "steady_step_s": round(statistics.median(gaps), 5)}
    if device_metrics:
        info["steady_mfu"] = statistics.median(r["mfu"] for r in recs[2:])
    return recs, info


def train_leg(out_dir, *, network="ResNet18", dataset="synthetic_cifar10",
              per_device_batch=1024, steps=12, dtype="bfloat16",
              device_metrics=True):
    import jax

    import train
    from ps_pytorch_tpu.data import augment
    from ps_pytorch_tpu.runtime import checkpoint as ckpt

    t_start = time.time()
    devices = jax.devices()
    n_dev = len(devices)
    k = n_dev - 1 if n_dev > 1 else 1
    train_dir = os.path.join(out_dir, "train_dir")
    metrics = os.path.join(out_dir, "train_metrics.jsonl")
    argv = ["--network", network, "--dataset", dataset,
            "--batch-size", str(per_device_batch * n_dev),
            "--compute-dtype", dtype, "--max-steps", str(steps),
            "--eval-freq", str(max(steps * 2 // 3, 1)),
            "--train-dir", train_dir, "--resume", "false",
            "--metrics-file", metrics]
    if n_dev > 1:   # exercise the mask of the masked psum
        argv += ["--mode", "kofn", "--num-aggregate", str(k)]
    out = _run_entry(train.main, argv)

    assert f"MESH data={n_dev} " in out, "mesh does not span every device"
    masks = [json.loads(line.split(" ", 3)[3])
             for line in out.splitlines() if line.startswith("MASK step ")]
    assert masks and all(sum(m) == k for m in masks), f"masks {masks}"
    recs, info = _read_metrics(metrics, steps, t_start, device_metrics)
    assert all(r["participating"] == k for r in recs), \
        f"participating {[r['participating'] for r in recs]} != mask sum {k}"
    assert ckpt.latest_valid_step(train_dir) == steps, "no valid checkpoint"
    shutil.rmtree(train_dir)   # checked; too big to bring back from the chip
    assert augment._load_native_loader() is not None, \
        "the data loader ran on its numpy fallback (native build failed)"
    if device_metrics:
        peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
        assert min(peaks) > 0 and max(peaks) < 10 * min(peaks), \
            f"per-device peak memory is lopsided: {peaks}"
        info["peak_bytes_per_device"] = peaks
    info.update(devices=n_dev, global_batch=per_device_batch * n_dev,
                participating=k, final_loss=recs[-1]["loss"],
                wall_s=round(time.time() - t_start, 1))
    return info


def lm_leg(out_dir, *, d_model=512, layers=8, heads=8, vocab=32000,
           seq_len=2048, batch=8, steps=6, dtype="bfloat16",
           device_metrics=True):
    import jax

    import train_lm
    from ps_pytorch_tpu.runtime import checkpoint as ckpt

    t_start = time.time()
    n_dev = len(jax.devices())
    # LMTrainer rejects a sequence-local kernel under a sharded sequence by
    # design: flash on one device, ring (what "auto" resolves to) over several.
    attention, impl = ("flash", "flash") if n_dev == 1 else ("auto", "ring")
    train_dir = os.path.join(out_dir, "lm_train_dir")
    metrics = os.path.join(out_dir, "lm_metrics.jsonl")
    out = _run_entry(train_lm.main, [
        "--lm-d-model", str(d_model), "--lm-layers", str(layers),
        "--lm-heads", str(heads), "--lm-vocab", str(vocab),
        "--lm-seq-len", str(seq_len), "--batch-size", str(batch),
        "--lm-attention", attention, "--compute-dtype", dtype,
        "--max-steps", str(steps), "--eval-freq", str(max(steps - 1, 1)),
        "--train-dir", train_dir, "--resume", "false",
        "--metrics-file", metrics])
    assert f"LM mesh devices={n_dev} " in out and f"attention={impl} " in out
    recs, info = _read_metrics(metrics, steps, t_start, device_metrics)
    assert ckpt.latest_valid_step(train_dir) == steps, "no valid checkpoint"
    shutil.rmtree(train_dir)
    info.update(devices=n_dev, attention=impl, final_loss=recs[-1]["loss"],
                wall_s=round(time.time() - t_start, 1))
    return info


# ---------------------------------------------------------------- kernels --

def _close(got, want, rtol, atol, what):
    """assert_allclose that also refuses non-finite output; -> max abs err."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all(), f"{what}: non-finite output"
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)
    return float(np.abs(got - want).max())


def _check_flash(dtype, shape, interpret):
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.ops.flash_attention import flash_attention
    from ps_pytorch_tpu.parallel.ring import full_attention

    q, k, v, w = (jax.random.normal(key, shape, dtype)
                  for key in jax.random.split(jax.random.key(0), 4))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    def oracle(q, k, v):
        with jax.default_matmul_precision("highest"):
            return full_attention(*(t.astype(jnp.float32) for t in (q, k, v)),
                                  causal=True)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                    * w.astype(jnp.float32)),
            argnums=(0, 1, 2)))

    # test_flash_attention.py: f32 2e-5 fwd / 5e-4 grads, bf16 2e-2 fwd (it
    # has no bf16 grad pin; those are held to 2e-2 of the oracle's scale).
    # The f32 pins hold under the interpreter only: compiled, the kernel's
    # default-precision f32 dots are single bf16 MXU passes, as XLA's own
    # are, so on the chip f32 inputs get the bf16 tolerance (1.0e-2 measured
    # against this highest-precision oracle).
    exact = dtype == jnp.float32 and interpret
    tol = 2e-5 if exact else 2e-2
    name = f"flash[{jnp.dtype(dtype).name}]"
    got = jax.jit(flash)(q, k, v)
    assert got.dtype == dtype
    errs = {"fwd": _close(got, jax.jit(oracle)(q, k, v), tol, tol,
                          f"{name} fwd")}
    for g, r, leaf in zip(grads(flash)(q, k, v), grads(oracle)(q, k, v),
                          "qkv"):
        gtol = 5e-4 if exact else 2e-2 * float(jnp.abs(r).max())
        errs[f"d{leaf}"] = _close(g, r, 5e-4 if exact else 2e-2, gtol,
                                  f"{name} d{leaf}")
    return errs


def _check_conv(variant, shape, interpret):
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.ops.pallas_conv import conv3x3, conv3x3_input_grad

    kx, kw = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kx, shape, jnp.bfloat16)
    w = jax.random.normal(kw, (3, 3, shape[-1], shape[-1]), jnp.bfloat16) * 0.1

    def xla(x, w):
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32).astype(x.dtype)

    # test_pallas_conv.py::test_matches_xla_bf16: bf16 resolution, 2e-2.
    errs = {"fwd": _close(
        conv3x3(x, w, variant=variant, interpret=interpret),
        jax.jit(xla)(x, w), 2e-2, 2e-2, f"conv3x3[{variant}] fwd")}
    # The backward twin is the same kernel on flipped, transposed weights
    # (that identity itself is pinned against autodiff in f32 by the tests).
    errs["dx"] = _close(
        conv3x3_input_grad(x, w, variant=variant, interpret=interpret),
        jax.jit(xla)(x, jnp.flip(w, axis=(0, 1)).swapaxes(2, 3)),
        2e-2, 2e-2, f"conv3x3[{variant}] input grad")
    return errs


def _check_quantize(n, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ps_pytorch_tpu.ops.quantize import (
        BLOCK, dequantize_int8, quantize_int8,
    )

    x = jax.random.normal(jax.random.key(2), (n,), jnp.float32)
    qt = quantize_int8(x, jax.random.key(3), interpret=interpret)
    assert qt.values.dtype == jnp.int8
    # test_ops.py::test_quantize_roundtrip_error_bound, held per block:
    # stochastic rounding errs by at most one quantum = blockmax / 127.
    err = np.abs(np.asarray(dequantize_int8(qt)) - np.asarray(x))
    err = np.pad(err, (0, -n % BLOCK)).reshape(-1, BLOCK)
    quantum = np.asarray(qt.scales).reshape(-1, 1)
    assert (err <= quantum * (1 + 1e-5) + 1e-6).all(), \
        f"quantize_int8: error {float((err / quantum).max()):.4f} quanta"
    return {"max_quanta": float((err / quantum).max())}


def kernel_leg(*, interpret=False, attn_shape=(8, 8, 2048, 64),
               conv_shape=(1024, 32, 32, 64), quant_n=9_231_114):
    """Every check runs; the leg fails afterwards with each failed kernel's
    own message (for a kernel Mosaic refuses, the compiler's)."""
    import jax.numpy as jnp

    checks = [
        ("flash_f32", lambda: _check_flash(jnp.float32, attn_shape, interpret)),
        ("flash_bf16", lambda: _check_flash(jnp.bfloat16, attn_shape,
                                            interpret)),
        ("conv_taps9", lambda: _check_conv("taps9", conv_shape, interpret)),
        ("conv_im2col", lambda: _check_conv("im2col", conv_shape, interpret)),
        ("quantize_int8", lambda: _check_quantize(quant_n, interpret)),
    ]
    t_start = time.time()
    info, failed = {"interpret": interpret}, []
    for name, check in checks:
        t0 = time.time()
        try:
            info[name] = dict(check(), seconds=round(time.time() - t0, 2))
        except Exception as e:
            traceback.print_exc()
            failed.append(f"{name}: {type(e).__name__}: {str(e)[:300]}")
        print(f"KERNEL {name} {'FAILED' if name not in info else info[name]}",
              flush=True)
    if failed:
        raise AssertionError("kernels failed:\n  " + "\n  ".join(failed))
    info["wall_s"] = round(time.time() - t_start, 1)
    return info


def main() -> int:
    import jax

    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"platform: {device['platform']}\ndevice_kind: {device['kind']}\n"
          f"devices: {device['count']}\njax: {jax.__version__}\n"
          f"compile_cache: {cache_dir} "
          f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
          f" entries at start)", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke.py needs a TPU and has no CPU mode; jax found "
              f"platform {device['platform']!r}", file=sys.stderr)
        return 1

    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    failed = []
    for name, leg in (("train", lambda: train_leg(OUT_DIR)),
                      ("kernels", lambda: kernel_leg(interpret=False)),
                      ("lm", lambda: lm_leg(OUT_DIR))):
        try:
            print(f"LEG {name} ok {json.dumps(leg())}", flush=True)
        except Exception:
            traceback.print_exc()
            print(f"LEG {name} FAILED", flush=True)
            failed.append(name)
    if failed:
        print(f"chip_smoke.py: failed legs: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
