#!/usr/bin/env python
"""Headline benchmark: ResNet-18 / CIFAR-10 training throughput + MFU.

One process, one measurement, one JSON line:
{"metric", "value", "unit", "vs_baseline", "platform", "device_kind", ...}.
The number is a device metric, so the run needs a TPU: with any other
platform, or on any error, it prints one JSON line carrying "error" and no
"value", and exits non-zero. It never measures on the CPU.

Baseline derivation (vs_baseline): the reference publishes no absolute
throughput (BASELINE.md); its headline distributed config is ResNet-18 /
CIFAR-10 on 8 MPI workers (m4.2xlarge CPUs) at a 5.19x speedup over 1 worker
(BASELINE.md, b=1024 "normal" speedup row). A single m4.2xlarge (8-vCPU
Broadwell Xeon) sustains ~80 images/sec on ResNet-18/CIFAR-10 training in
that era's PyTorch — an ESTIMATE, since the reference measured none — so the
8-worker MPI cluster's effective rate is ~80 * 5.19 ~= 415 images/sec.
vs_baseline = measured / 415.

MFU: per-image fwd+bwd FLOPs counted from the traced value_and_grad jaxpr
(ps_pytorch_tpu/utils/flops.py — measured backward multiple, not the 3x
rule), divided by the chip's peak bf16 FLOPs (v5e = 197 TF/s/chip).

Synthetic CIFAR-shaped data: this measures the training step
(forward+backward+psum+update), not host input I/O (bench_suite.py measures
the loader separately).
"""

import argparse
import json
import sys
import time
import traceback

BASELINE_IMGS_PER_SEC = 415.0  # estimate-derived; see module docstring
METRIC = "resnet18_cifar10_train_images_per_sec"


def require_tpu(devices) -> None:
    d = devices[0]
    if d.platform != "tpu":
        raise RuntimeError(f"{METRIC} is a device metric and needs a TPU; "
                           f"jax resolved platform={d.platform} "
                           f"({d.device_kind})")


def measure(args) -> dict:
    """Model/state construction and the timing loop are bench_suite.py's
    (_build/time_steps) so the two benchmarks cannot silently diverge."""
    import jax

    from bench_suite import _build, time_steps
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.utils.flops import peak_flops_bf16, training_flops

    if args.steps < 1 or args.warmup < 1:
        raise ValueError("--steps and --warmup must be >= 1")

    t_init = time.perf_counter()
    devices = jax.devices()
    init_s = time.perf_counter() - t_init
    require_tpu(devices)
    kind = devices[0].device_kind
    n_dev = len(devices)
    batch = args.per_device_batch * n_dev
    state, step_fn, x, y, mask = _build("ResNet18", "Cifar10", batch)

    t_c = time.perf_counter()
    sec_per_step = time_steps(state, step_fn, x, y, mask,
                              steps=args.steps, warmup=args.warmup)
    compile_s = time.perf_counter() - t_c - sec_per_step * args.steps
    imgs_per_sec = batch / sec_per_step

    # FLOPs model: per-image fwd+bwd from the traced grad jaxpr (batch=8 to
    # keep the trace fast; per-image cost is batch-invariant for these CNNs).
    model = build_model("ResNet18", 10, "bfloat16")
    flops_per_image = training_flops(model, (8, 32, 32, 3), 10) / 8
    mfu = flops_per_image * imgs_per_sec / (peak_flops_bf16(kind) * n_dev)

    return {
        "metric": METRIC,
        "value": round(imgs_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMGS_PER_SEC, 2),
        "sec_per_step": round(sec_per_step, 5),
        "mfu": round(mfu, 4),
        "flops_per_image_gf": round(flops_per_image / 1e9, 3),
        "global_batch": batch,
        "devices": n_dev,
        "platform": devices[0].platform,
        "device_kind": kind,
        "init_s": round(init_s, 1),
        "compile_s": round(compile_s, 1),
        "baseline_note": "415 img/s = estimate-derived 8-worker MPI rate",
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--per-device-batch", type=int, default=1024)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    args = p.parse_args(argv)

    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        out = measure(args)
    except Exception as e:  # the one boundary: report, then fail the run
        traceback.print_exc()
        print(json.dumps({"metric": METRIC,
                          "error": f"{type(e).__name__}: {e}"[:400]}))
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
