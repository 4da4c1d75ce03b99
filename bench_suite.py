#!/usr/bin/env python
"""Benchmark suite — the five BASELINE.json configs (SURVEY §7 item 8).

Each config runs the real jitted SPMD train step on synthetic data shaped
like its dataset and reports images/sec (and for LeNet, a time-to-loss
convergence probe). One JSON line per config; ``--markdown`` additionally
emits a BASELINE.md-compatible table.

Configs (BASELINE.json "configs"):
  1. lenet_mnist_single   — single_machine.py parity (1 device, b=128)
  2. lenet_mnist_dp       — distributed LeNet/MNIST sync SGD (all devices)
  3. resnet18_cifar10_dp  — the headline 8-worker ResNet-18/CIFAR-10 config
  4. vgg11_cifar100_kofn  — VGG-11/CIFAR-100 with K-of-N (async) aggregation
  5. resnet50_imagenet    — ResNet-50 @ 224px (new, stresses the allreduce)

Usage: python bench_suite.py [--configs lenet_mnist_dp,...] [--steps 20]
"""

import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

# Effective reference rates (images/sec) derived in BASELINE.md/bench.py:
# a single m4.2xlarge sustains ~80 img/s on ResNet-18; LeNet ~1,245 img/s
# (526.16 s for 8 epochs x 8192... see BASELINE.md); scaled by the published
# "normal" speedups at the matching worker counts. None published for
# VGG/CIFAR-100 or ResNet-50/ImageNet -> vs_baseline null there.
BASELINES = {
    "lenet_mnist_single": 1245.0,        # 60000*8192-step epochs / 526.16 s ~ single node
    "lenet_mnist_dp": 1245.0 * 5.59,     # 8-worker LeNet speedup (SURVEY §6)
    "resnet18_cifar10_dp": 80.0 * 5.19,  # 8-worker ResNet-18 b=1024 row
    "vgg11_cifar100_kofn": None,
    "resnet50_imagenet": None,
}


def _build(network, dataset, batch, *, mode="sync", num_aggregate=0,
           n_devices=None, dtype="bfloat16", fused=False, remat=False,
           shard_update=False, lr=0.1, conv_impl="xla"):
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.data.datasets import DATASET_SHAPES
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel import (
        create_train_state, make_mesh, make_train_step,
    )

    devices = jax.devices()
    if n_devices:
        devices = devices[:n_devices]
    cfg = TrainConfig(dataset=dataset, network=network, batch_size=batch,
                      lr=lr, momentum=0.9, weight_decay=1e-4,
                      compute_dtype=dtype, mode=mode,
                      num_aggregate=num_aggregate, fused_optimizer=fused,
                      remat=remat, shard_update=shard_update,
                      conv_impl=conv_impl)
    mesh = make_mesh(data=len(devices), devices=devices)
    model = build_model(cfg.network, cfg.num_classes, cfg.compute_dtype,
                        conv_impl=cfg.conv_impl)
    tx = build_optimizer(cfg)
    h, w, c, ncls, _ = DATASET_SHAPES[dataset]
    if shard_update:
        from ps_pytorch_tpu.parallel.zero import (
            create_zero_train_state, make_zero_train_step,
        )
        state = create_zero_train_state(model, tx, mesh, (1, h, w, c),
                                        jax.random.key(0))
        step_fn = make_zero_train_step(model, tx, mesh, state, remat=remat,
                                       donate=True)
    else:
        state = create_train_state(model, tx, mesh, (1, h, w, c),
                                   jax.random.key(0))
        step_fn = make_train_step(model, tx, mesh, state, remat=remat,
                                  donate=True)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, h, w, c)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, ncls, batch).astype(np.int32))
    n_data = mesh.shape["data"]
    mask = np.ones(n_data, np.float32)
    if mode == "kofn" and 0 < num_aggregate < n_data:
        mask[num_aggregate:] = 0.0
    return state, step_fn, x, y, jnp.asarray(mask)


def time_steps(state, step_fn, x, y, mask, steps=20, warmup=3, tracer=None):
    """Mean seconds/step (float — bench.py depends on this return type).
    ``tracer``: optional telemetry Tracer; when given, the timed loop's
    dispatch and final sync are recorded as spans so suite rows can carry
    a per-phase breakdown."""
    from contextlib import nullcontext

    def span(name, i):
        return (tracer.span(name, step=i) if tracer is not None
                else nullcontext())

    for i in range(warmup):
        state, metrics = step_fn(state, x, y, mask, jax.random.key(i))
    _ = float(metrics["loss"])
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for i in range(steps):
        with span("host_dispatch", i + 1):
            state, metrics = step_fn(state, x, y, mask, jax.random.key(100 + i))
    with span("device_sync", steps):
        jax.block_until_ready(state.params)
        _ = float(metrics["loss"])
    return (time.perf_counter() - t0) / steps


def bench_throughput(name, network, dataset, per_device_batch, steps, **kw):
    from ps_pytorch_tpu.telemetry import Tracer

    n_dev = kw.pop("n_devices", None) or len(jax.devices())
    batch = per_device_batch * n_dev
    state, step_fn, x, y, mask = _build(network, dataset, batch,
                                        n_devices=n_dev, **kw)
    tracer = Tracer()
    sec_per_step = time_steps(state, step_fn, x, y, mask, steps=steps,
                              tracer=tracer)
    ips = batch / sec_per_step
    base = BASELINES.get(name)
    return {"config": name, "network": network, "dataset": dataset,
            "platform": jax.devices()[0].platform,
            "devices": n_dev, "global_batch": batch,
            "sec_per_step": round(sec_per_step, 5),
            "images_per_sec": round(ips, 1),
            # Host-side phase accounting for the timed window (telemetry
            # tracer): dispatch vs trailing-sync seconds, with counts.
            "phases": tracer.totals(),
            "vs_baseline": round(ips / base, 2) if base else None,
            # The reference published only relative speedups; the absolute
            # per-node rates under BASELINES are estimates (see comment
            # there), so vs_baseline is estimate-derived, not measured.
            "vs_baseline_basis": "estimate" if base else None}


def bench_input_pipeline(name, dataset, per_device_batch, steps, workers=1):
    """Loader-only throughput at the headline config's batch size: full
    augmentation stack (pad/crop/flip or RRC, normalize) + prefetch, no
    device in the loop. Compared against the training step's demand in
    main() (the loader must outrun the chip or it IS the bottleneck —
    VERDICT r1 item 4; reference capability: multiprocess loader,
    my_data_loader.py:37-75). ``workers`` drives the loader's assembly
    pool (0 = one per CPU) — the augmented ImageNet row runs it the way a
    real host would."""
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.data.augment import (
        CROP_STACKS, RRC_STACKS, input_norm_for, norm_constants_for,
    )
    from ps_pytorch_tpu.data.datasets import DataLoader, load_arrays

    n_dev = len(jax.devices())
    batch = per_device_batch * n_dev
    cfg = TrainConfig(dataset=dataset, network="ResNet18", batch_size=batch)
    dev_norm = input_norm_for(cfg) is not None
    x, y = load_arrays(cfg.dataset, cfg.data_dir, train=True, seed=0)
    loader = DataLoader(x, y, batch, cfg.dataset, train=True, seed=0,
                        device_normalize=dev_norm, workers=workers)
    xb, _ = loader.next_batch()  # warm the prefetch thread (and bind xb
    #                              for the bytes row even at --steps 0)
    t0 = time.perf_counter()
    n_img = 0
    for _ in range(steps):
        xb, _ = loader.next_batch()
        n_img += len(xb)
    dt = time.perf_counter() - t0
    ips = n_img / dt
    if dataset in RRC_STACKS:
        h, w = xb.shape[1], xb.shape[2]
        stack = f"rrc{h}x{w}+flip"
    elif dataset in CROP_STACKS:
        stack = "pad4+crop+flip"
    else:
        stack = "shuffle+batch"
    if not dev_norm and norm_constants_for(dataset) is not None:
        stack += "+normalize"
    return {"config": name, "dataset": dataset, "global_batch": batch,
            # The loader is HOST-side by design: its throughput is valid
            # whatever backend jax resolved to; the ratio row pairs it with
            # the chip row's platform.
            "platform": "host",
            "loader_images_per_sec": round(ips, 1),
            # Bandwidth of the SHIPPED batches (xb), not the storage array:
            # uint8-stored data host-normalized to float32 ships 4x the
            # storage bytes.
            "bytes_per_sec_mb": round(ips * xb.nbytes / len(xb) / 1e6, 1),
            "augment": stack,
            "loader_workers": loader.workers,
            "device_normalize": dev_norm}


def bench_quantizer(name, steps):
    """On-device int8 quantizer throughput (ops/quantize.py) on a VGG-11-
    sized gradient vector — the codec="int8" wire-path cost (VERDICT r2
    item 1: quantizer throughput measured on the chip, not asserted)."""
    from ps_pytorch_tpu.ops.quantize import (
        dequantize_int8, quantize_int8, quantized_nbytes,
    )

    n = 9_231_114          # VGG-11 (CIFAR head) parameter count
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    keys = jax.random.split(jax.random.key(0), 32)
    q = quantize_int8(x, keys[0])
    y = dequantize_int8(q)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for i in range(steps):
        q = quantize_int8(x, keys[i % 32])
    jax.block_until_ready(q.values)
    dt_q = (time.perf_counter() - t0) / steps
    # Per-call BLOCKING latency alongside pipelined throughput: the two
    # diverge by the per-dispatch cost, so the artifact itself shows
    # whether a low GB/s figure is kernel time or dispatch latency.
    t0 = time.perf_counter()
    for i in range(min(steps, 5)):
        q = quantize_int8(x, keys[i % 32])
        jax.block_until_ready(q.values)
    dt_block = (time.perf_counter() - t0) / min(steps, 5)
    t0 = time.perf_counter()
    for _ in range(steps):
        y = dequantize_int8(q)
    jax.block_until_ready(y)
    dt_d = (time.perf_counter() - t0) / steps
    nbytes = n * 4
    err = float(jnp.max(jnp.abs(y - x)))
    return {"config": name, "tensor_bytes": nbytes,
            "wire_bytes": quantized_nbytes(q),
            "shrink": round(nbytes / quantized_nbytes(q), 2),
            "quantize_ms": round(dt_q * 1e3, 3),
            "quantize_blocking_ms": round(dt_block * 1e3, 3),
            "dequantize_ms": round(dt_d * 1e3, 3),
            "quantize_gbps": round(nbytes / dt_q / 1e9, 1),
            "max_abs_err": round(err, 5),
            "platform": jax.devices()[0].platform}


def bench_async_multislice(name, steps, *, network="ResNet18",
                           dataset="synthetic", per_slice_batch=512,
                           n_slices=2):
    """Async (stale-gradient) mode throughput next to the sync rows: the
    in-process MultiSliceTrainer with device-resident canonical state
    (VERDICT r2 item 5 — async benched on hardware, not asserted). Each
    tick: every slice computes its psum-averaged gradient, the PS-role
    update applies the pooled average. images/sec counts all slice work."""
    import jax

    devices = jax.devices()
    if len(devices) % n_slices:
        n_slices = 1
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    cfg = TrainConfig(dataset=dataset, network=network,
                      batch_size=per_slice_batch, lr=0.1, momentum=0.9,
                      weight_decay=1e-4, mode="async", max_steps=10 ** 9,
                      eval_freq=0, log_every=10 ** 9)
    t = MultiSliceTrainer(cfg, n_slices=n_slices)
    for _ in range(3):          # compile + warm
        t.tick()
    jax.block_until_ready(t.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        t.tick()
    jax.block_until_ready(t.params)
    dt = (time.perf_counter() - t0) / steps
    imgs = per_slice_batch * n_slices
    return {"config": name, "network": network,
            "platform": jax.devices()[0].platform, "n_slices": n_slices,
            "per_slice_batch": per_slice_batch,
            "sec_per_tick": round(dt, 5),
            "images_per_sec": round(imgs / dt, 1),
            "applied": t.applied, "dropped_stale": t.dropped_stale,
            "pool_wire_bytes": t.aggregator.wire_bytes()}


def bench_transformer_lm(name, steps, *, batch=8, seq_len=2048, d_model=512,
                         n_layers=8, n_heads=8, vocab=32000, remat=False,
                         attention=None):
    """Transformer-LM training throughput (tokens/sec) — the long-context
    surface (SURVEY: SP/ring attention first-class) benched next to the CNN
    rows. Single-axis mesh over all devices; ring attention shards the
    sequence when >1 device is present, full attention on one device (ring
    degenerates to a pointless self-permute there)."""
    import jax
    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.parallel.mesh import make_mesh
    from ps_pytorch_tpu.parallel.sp import (
        create_lm_train_state, make_sp_train_step,
    )

    devices = jax.devices()
    # An explicit attention override is sequence-LOCAL (flash/full), and
    # make_sp_train_step shards the sequence over the mesh — so those rows
    # pin to ONE device: the row measures the single-chip kernel, on any
    # topology, instead of silently computing block-diagonal attention.
    if attention is not None:
        devices = devices[:1]
    n = len(devices)
    mesh = make_mesh(data=n, devices=devices)
    impl = attention or ("ring" if n > 1 else "full")
    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_layers=n_layers, n_heads=n_heads,
                          max_seq_len=seq_len, attention_impl=impl,
                          axis_name="data")
    cfg = TrainConfig(dataset="synthetic", network="LeNet", batch_size=batch,
                      lr=0.01, momentum=0.9)
    tx = build_optimizer(cfg)
    state = create_lm_train_state(model, tx, mesh, (batch, seq_len))
    step_fn = make_sp_train_step(model, tx, mesh, remat=remat)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, vocab, size=(batch, seq_len)),
                         jnp.int32)
    for _ in range(3):
        state, m = step_fn(state, tokens)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step_fn(state, tokens)
    jax.block_until_ready(state.params)
    dt = (time.perf_counter() - t0) / steps
    toks = batch * seq_len
    return {"config": name, "attention": impl,
            "platform": jax.devices()[0].platform, "devices": n,
            "batch": batch, "seq_len": seq_len, "d_model": d_model,
            "n_layers": n_layers, "remat": remat,
            "sec_per_step": round(dt, 5),
            "tokens_per_sec": round(toks / dt, 1),
            "loss": round(float(m["loss"]), 4)}


def bench_moe_lm(name, steps, *, batch=8, seq_len=2048, d_model=512,
                 n_layers=8, n_heads=8, vocab=32000, n_experts=8):
    """MoE (switch top-1) LM throughput next to the dense transformer row:
    same geometry with every block's MLP replaced by n_experts experts —
    ~n_experts x the MLP parameters at (ideally) dense-like step time. The
    gap between this row's tokens/sec and transformer_lm_2k's is the
    routing overhead (dispatch/combine einsums + capacity accounting;
    all_to_all only materializes with >1 device). Experts shard over
    'data' (parallel/ep.py)."""
    import jax
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.models.moe import MoETransformerLM
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel.ep import (
        create_ep_train_state, make_ep_train_step,
    )
    from ps_pytorch_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    n = len(devices)
    mesh = make_mesh(data=n, model=1, devices=devices)
    # Round UP to a multiple of the device count: ep requires
    # n_experts % n_devices == 0 (max(n_experts, n) breaks on e.g. 6
    # devices).
    e = -(-n_experts // n) * n
    model = MoETransformerLM(vocab_size=vocab, d_model=d_model,
                             n_layers=n_layers, n_heads=n_heads,
                             n_experts=e, max_seq_len=seq_len,
                             ep_axis="data")
    cfg = TrainConfig(dataset="synthetic", network="LeNet", batch_size=batch,
                      lr=0.01, momentum=0.9)
    tx = build_optimizer(cfg)
    state = create_ep_train_state(model, tx, mesh, (batch, seq_len))
    step_fn = make_ep_train_step(model, tx, mesh, state)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, vocab, size=(batch, seq_len)),
                         jnp.int32)
    for _ in range(3):
        state, m = step_fn(state, tokens)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step_fn(state, tokens)
    jax.block_until_ready(state.params)
    dt = (time.perf_counter() - t0) / steps
    return {"config": name,
            "platform": jax.devices()[0].platform, "devices": n,
            "batch": batch, "seq_len": seq_len, "d_model": d_model,
            "n_layers": n_layers, "n_experts": e,
            "sec_per_step": round(dt, 5),
            "tokens_per_sec": round(batch * seq_len / dt, 1),
            "loss": round(float(m["loss"]), 4),
            "aux": round(float(m["aux"]), 4)}


def bench_lm_decode(name, steps, *, batch=1, prompt_len=128, n_new=128,
                    d_model=512, n_layers=8, n_heads=8, vocab=32000,
                    max_seq_len=2048):
    """Decode throughput for the k/v-cache generation path (VERDICT r4
    weak #7: ``models/generate.py`` had zero perf evidence).

    The whole prefill+sample loop is ONE jitted program (two ``lax.scan``s),
    so prefill and per-token costs cannot be timed separately inside a run.
    Instead two program variants are timed — ``n_new=1`` (prefill + one
    sample) and ``n_new=1+N`` — and the difference isolates the per-token
    decode cost; the n_new=1 run bounds prefill. ``steps`` is the number of
    timed repetitions of each variant (compile excluded)."""
    from ps_pytorch_tpu.models.generate import generate
    from ps_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_layers=n_layers, n_heads=n_heads,
                          max_seq_len=max_seq_len, attention_impl="full")
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, (batch, prompt_len)),
        jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    kw = dict(vocab=vocab, d_model=d_model, n_layers=n_layers,
              n_heads=n_heads, max_seq_len=max_seq_len,
              temperature=1.0, top_k=40, seed=0)

    def timed(n):
        out = generate(params, prompt, n_new=n, **kw)   # compile
        out.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(steps):
            generate(params, prompt, n_new=n, **kw).block_until_ready()
        return (time.perf_counter() - t0) / steps

    t_prefill = timed(1)            # prefill scan + 1 sampled token
    t_full = timed(1 + n_new)
    per_tok = (t_full - t_prefill) / n_new
    return {"config": name, "platform": jax.devices()[0].platform,
            "batch": batch, "prompt_len": prompt_len, "n_new": n_new,
            "d_model": d_model, "n_layers": n_layers, "vocab": vocab,
            "prefill_plus1_s": round(t_prefill, 5),
            "sec_per_token": round(per_tok, 6),
            "decode_tokens_per_sec": round(batch / per_tok, 1)
            if per_tok > 0 else None,
            "end_to_end_tokens_per_sec": round(
                batch * (1 + n_new) / t_full, 1)}


def bench_serving(name, steps, *, slots, n_req=8, prompt_len=32, n_new=64,
                  d_model=128, n_layers=2, n_heads=4, vocab=256,
                  seq_len=256):
    """Continuous-batching serving throughput (ps_pytorch_tpu/serving/):
    ``n_req`` identical-seeded requests drained closed-loop through a
    ``slots``-wide engine. slots=1 IS the sequential baseline (one request
    decodes at a time through the same engine mechanics), so the
    batched/sequential pair isolates what slot-batching buys at the same
    model, prompts, and sampling seeds. ``tokens_sha256`` hashes every
    request's sampled tokens in request order — main() asserts the batched
    and sequential hashes MATCH, which is the slot-count-invariance (and
    hence generate()-parity) contract inside the artifact itself."""
    import hashlib

    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.serving.engine import ServingEngine
    from ps_pytorch_tpu.serving.loadgen import make_requests, run_closed_loop

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_layers=n_layers, n_heads=n_heads,
                          max_seq_len=seq_len)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, prompt_len), jnp.int32),
                        positions=jnp.arange(prompt_len))["params"]
    engine = ServingEngine(params, slots=slots, vocab=vocab, d_model=d_model,
                           n_layers=n_layers, n_heads=n_heads,
                           max_seq_len=seq_len)
    # Warm-up drains the jit cache (prefill at this prompt length, the
    # vmapped step, the sampler) so the timed loop measures decode, not
    # compiles. Different seed base -> does not perturb the timed tokens.
    warm = make_requests(min(slots, 2), prompt_len=prompt_len, n_new=4,
                         vocab=vocab, seed=9999)
    run_closed_loop(engine, warm)
    reqs = make_requests(n_req, prompt_len=prompt_len, n_new=n_new,
                         vocab=vocab, seed=123)
    stats = run_closed_loop(engine, reqs)
    sha = hashlib.sha256(json.dumps(
        [r.tokens for r in reqs]).encode()).hexdigest()
    return {"config": name, "platform": jax.devices()[0].platform,
            "slots": slots, "n_req": n_req, "prompt_len": prompt_len,
            "n_new": n_new, "d_model": d_model, "n_layers": n_layers,
            "vocab": vocab,
            "completed": stats["completed"], "tokens": stats["tokens"],
            "wall_s": round(stats["wall_s"], 4),
            "tokens_per_sec": round(stats["tokens_per_sec"], 1),
            "ttft_p50_ms": round(stats["ttft_p50_ms"], 2),
            "ttft_p99_ms": round(stats["ttft_p99_ms"], 2),
            "latency_p50_ms": round(stats["latency_p50_ms"], 2),
            "latency_p99_ms": round(stats["latency_p99_ms"], 2),
            "tokens_sha256": sha}


def bench_slo_sweep(name, steps, *, slots=4, n_req=10, prompt_len=16,
                    n_new=24, d_model=64, n_layers=2, n_heads=2, vocab=128,
                    seq_len=64,
                    slo_spec="ttft_p99<30s;latency_p99<60s;"
                             "availability>=99",
                    rates=(1.0, 2.0, 4.0, 8.0)):
    """Goodput-under-SLO harness row (ISSUE 8): a rising-offered-load
    Poisson ladder through the open-loop path (AdmissionQueue +
    serve_loop), each rung judged against ``slo_spec`` offline; the KNEE
    is the highest compliant arrival rate and goodput-under-SLO is the
    knee rung's tokens/sec — the row's headline. ``knee_bar`` is the
    lowest offered rate: the engine failing its (deliberately loose) SLO
    even there is a regression, and tools/regress.py's slo family gates
    ``knee_rps >= knee_bar``. ``steps`` is unused (each rung is one
    open-loop run; its length is n_req/rate)."""
    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.serving.engine import ServingEngine
    from ps_pytorch_tpu.serving.loadgen import (
        make_requests, run_closed_loop, run_slo_sweep,
    )

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_layers=n_layers, n_heads=n_heads,
                          max_seq_len=seq_len)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, prompt_len), jnp.int32),
                        positions=jnp.arange(prompt_len))["params"]
    engine = ServingEngine(params, slots=slots, vocab=vocab,
                           d_model=d_model, n_layers=n_layers,
                           n_heads=n_heads, max_seq_len=seq_len)
    # Warm the jit cache (prefill/step/sampler) so rung 0 doesn't pay
    # compile time inside its TTFT percentiles.
    run_closed_loop(engine, make_requests(
        min(slots, 2), prompt_len=prompt_len, n_new=4, vocab=vocab,
        seed=9999))
    sweep = run_slo_sweep(engine, slo_spec, rates=rates, n_req=n_req,
                          prompt_len=prompt_len, n_new=n_new, seed=321)
    knee_bar = min(rates)
    ladder = [{k: r.get(k) for k in
               ("rate_rps", "completed", "shed", "rejected", "failed",
                "tokens_per_sec", "ttft_p99_ms", "latency_p99_ms",
                "availability")} | {"compliant": r["slo"]["compliant"]}
              for r in sweep["ladder"]]
    return {"config": name, "platform": jax.devices()[0].platform,
            "slots": slots, "n_req_per_rung": n_req, "n_new": n_new,
            "slo_spec": slo_spec, "ladder": ladder,
            "knee_rps": sweep["knee_rps"],
            "goodput_under_slo_tps": sweep["goodput_under_slo_tps"],
            "knee_bar": knee_bar,
            "ok": bool(sweep["ok"] and sweep["knee_rps"] is not None
                       and sweep["knee_rps"] >= knee_bar)}


def bench_reqtrace_overhead(name, steps, *, reps=3, slots=8, n_req=8,
                            prompt_len=32, n_new=64, d_model=128,
                            n_layers=2, n_heads=4, vocab=256, seq_len=256):
    """Request-observability cost row: the serve_batched_8 workload drained
    closed-loop through a bare engine vs one carrying the FULL request
    plane — declared serving registry, RequestTraceLog ring, and an
    SLOTracker fed by every terminal request. min-of-reps both sides;
    ``ok`` needs the <2% budget AND bitwise-identical sampled tokens (the
    plane is host-side by contract — a tracer that perturbs sampling is
    broken, not slow)."""
    import hashlib

    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.serving.engine import ServingEngine
    from ps_pytorch_tpu.serving.loadgen import make_requests, run_closed_loop
    from ps_pytorch_tpu.serving.reqtrace import RequestTraceLog
    from ps_pytorch_tpu.telemetry import Registry, declare_serving_metrics
    from ps_pytorch_tpu.telemetry.slo import SLOTracker

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_layers=n_layers, n_heads=n_heads,
                          max_seq_len=seq_len)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, prompt_len), jnp.int32),
                        positions=jnp.arange(prompt_len))["params"]

    def run(traced):
        kw = {}
        if traced:
            registry = declare_serving_metrics(Registry())
            kw = dict(registry=registry,
                      reqtrace=RequestTraceLog(256, sample=0.05),
                      slo=SLOTracker("ttft_p99<30s;latency_p99<60s;"
                                     "availability>=99", registry=registry))
        engine = ServingEngine(params, slots=slots, vocab=vocab,
                               d_model=d_model, n_layers=n_layers,
                               n_heads=n_heads, max_seq_len=seq_len, **kw)
        run_closed_loop(engine, make_requests(
            min(slots, 2), prompt_len=prompt_len, n_new=4, vocab=vocab,
            seed=9999))
        best, sha = None, None
        for _ in range(reps):
            reqs = make_requests(n_req, prompt_len=prompt_len, n_new=n_new,
                                 vocab=vocab, seed=123)
            stats = run_closed_loop(engine, reqs)
            if best is None or stats["wall_s"] < best:
                best = stats["wall_s"]
            if sha is None:
                sha = hashlib.sha256(json.dumps(
                    [r.tokens for r in reqs]).encode()).hexdigest()
        return best, sha

    baseline_s, sha_bare = run(False)
    traced_s, sha_traced = run(True)
    frac = (traced_s - baseline_s) / baseline_s
    bitwise = sha_bare == sha_traced
    return {"config": name, "platform": jax.devices()[0].platform,
            "slots": slots, "n_req": n_req, "n_new": n_new, "reps": reps,
            "baseline_s": round(baseline_s, 5),
            "traced_s": round(traced_s, 5),
            "overhead_frac": round(frac, 5),
            "bitwise_identical": bitwise,
            "ok": bool(bitwise and frac < 0.02)}


def bench_pallas_conv_ab(name, steps, *, batch=1024, hw=32, c=64):
    """A/B: Pallas 3x3 conv prototype vs lax.conv on the trace's hot
    geometry (PERF.md §7: 32x32/64-ch blocks HBM-bound at ~486 GB/s, the
    step's one remaining lever, bounded ≈ +17%). Times the fwd kernel and
    the grad-input twin; ``accepted`` is decided HERE, by ratio, not in
    prose (VERDICT r4 next #4: 'a number either way')."""
    from ps_pytorch_tpu.ops.pallas_conv import conv3x3, conv3x3_input_grad

    platform = jax.devices()[0].platform
    if platform != "tpu":
        batch, steps = 64, min(steps, 3)    # interpret-mode smoke only
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, hw, hw, c)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 3, c, c)) * 0.1, jnp.bfloat16)

    def xla_conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32).astype(x.dtype)

    xla_conv = jax.jit(xla_conv)

    def timed(fn, *args):
        fn(*args).block_until_ready()       # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            r = fn(*args)
        r.block_until_ready()
        return (time.perf_counter() - t0) / steps

    # XLA's grad-input baseline is its OWN transpose(jvp) program (the
    # trace's actual backward hotspot), not the forward conv re-timed.
    # vjp through the bf16 conv exactly as the models build it (flax leaves
    # preferred_element_type unset; an explicit f32 accumulate makes the
    # transpose rule feed an f32 cotangent to a bf16-weight conv, which
    # lax rejects).
    def bf16_conv(xx):
        return jax.lax.conv_general_dilated(
            xx, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    _, xla_vjp = jax.vjp(bf16_conv, x)
    xla_bwd = jax.jit(lambda gg: xla_vjp(gg)[0])

    t_xla = timed(xla_conv, x, w)
    t_xla_bwd = timed(xla_bwd, x)       # x reused as the cotangent
    from ps_pytorch_tpu.ops.pallas_conv import effective_block_n

    # Both MXU schedules (9 accumulating K=C dots vs one K=9C im2col dot);
    # the better one per direction is the prototype's number.
    block_n = 4   # pinned + recorded: a tile-size change must never read
    raw = {}      # as a kernel change in cross-round ratio comparisons
    for v in ("taps9", "im2col"):
        # One jitted program per direction, symmetric with the XLA
        # baselines: conv3x3_input_grad's weight flip/transpose would
        # otherwise run as separate eager dispatches every iteration —
        # dispatch cost charged only to the Pallas side of the
        # accept/reject ratio.
        pl_fwd = jax.jit(
            lambda xx, _v=v: conv3x3(xx, w, variant=_v, block_n=block_n))
        pl_bwd = jax.jit(
            lambda gg, _v=v: conv3x3_input_grad(gg, w, variant=_v,
                                                block_n=block_n))
        raw[v] = (timed(pl_fwd, x), timed(pl_bwd, x))
    # Ratios/verdicts from RAW seconds; rounding is display-only.
    t_pl = min(f for f, _ in raw.values())
    t_pl_bwd = min(b for _, b in raw.values())
    # Per-variant EFFECTIVE tile (conv3x3 halves it for im2col before the
    # divisibility shrink) — the tile each schedule really ran, so a
    # cross-round ratio change can be told apart from a tile change
    # (ADVICE r5 #3).
    variants = {v: {"fwd_ms": round(f * 1e3, 3),
                    "grad_input_ms": round(b * 1e3, 3),
                    "block_n": effective_block_n(batch, block_n, v)}
                for v, (f, b) in raw.items()}
    flops = 2 * batch * hw * hw * c * c * 9
    ratio = t_xla / t_pl
    ratio_bwd = t_xla_bwd / t_pl_bwd
    on_tpu = platform == "tpu"
    return {"config": name, "platform": platform, "batch": batch,
            "hw": hw, "channels": c, "block_n": block_n,
            "xla_ms": round(t_xla * 1e3, 3),
            "pallas_ms": round(t_pl * 1e3, 3),
            "xla_grad_input_ms": round(t_xla_bwd * 1e3, 3),
            "pallas_grad_input_ms": round(t_pl_bwd * 1e3, 3),
            "variants": variants,
            "xla_tflops": round(flops / t_xla / 1e12, 1),
            "pallas_tflops": round(flops / t_pl / 1e12, 1),
            "speedup_vs_xla": round(ratio, 3),
            "speedup_vs_xla_bwd": round(ratio_bwd, 3),
            "accepted_fwd": bool(on_tpu and ratio > 1.05),
            "accepted_bwd": bool(on_tpu and ratio_bwd > 1.05),
            "accepted": bool(on_tpu and (ratio > 1.05 or ratio_bwd > 1.05))}


def bench_time_to_loss(name, network, dataset, batch, target_loss,
                       max_steps=400):
    """Convergence probe: wall-clock to reach target training loss on a
    learnable synthetic task (the evaluator-accuracy contract's fast proxy)."""
    # lr=0.02: random-label memorization diverges at the throughput rows'
    # lr=0.1 (loss spikes to ~60 then plateaus at chance — observed on v5e).
    # Matmul precision is pinned to f32: on TPU the default (bf16 passes
    # even for f32 inputs) left the same probe stuck at chance loss (2.32
    # after 200 steps, first r3 suite run) while CPU converged by step 120 —
    # random-label memorization has no margin for matmul noise in its
    # unstable early phase. Throughput rows keep the hardware default; this
    # row measures convergence, so exactness wins over speed.
    with jax.default_matmul_precision("highest"):
        state, step_fn, x, y, mask = _build(network, dataset, batch,
                                            dtype="float32", lr=0.02)
        # Warmup/compile outside the clock. The step donates its input
        # state, so continue from the warmed-up state rather than reusing
        # donated buffers.
        state, m = step_fn(state, x, y, mask, jax.random.key(0))
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        # Loss is checked EVERY step so converged-at-step-N is exact (a
        # 10-step check stride reported up to 9 steps late — VERDICT r2
        # weak #8). The per-step device sync this forces is acceptable:
        # this row measures convergence, not pipelined throughput (the
        # *_dp rows measure that).
        for i in range(max_steps):
            state, m = step_fn(state, x, y, mask, jax.random.key(1 + i))
            if float(m["loss"]) <= target_loss:
                break
    loss = float(m["loss"])
    dt = time.perf_counter() - t0
    return {"config": name, "network": network, "dataset": dataset,
            "platform": jax.devices()[0].platform,
            "target_loss": target_loss, "reached_loss": round(loss, 4),
            "steps": i + 1, "seconds": round(dt, 3),
            "converged": loss <= target_loss}


class LatencyKV:
    """In-process KV with a deterministic per-op service time — the DCN
    model for the wire microbench. A real coordination-service op crosses
    the data-center network (gRPC, ~ms RTT); the plain dict KV costs ~0,
    which would hide exactly the put/get legs the overlapped wire
    pipelines. ``time.sleep`` releases the GIL, so overlapping these waits
    with encode/decode on worker threads is the same concurrency a real
    in-flight RPC provides. ``rtt_s`` is recorded in the bench row.

    ``classes`` upgrades the flat RTT to PER-LINK latency: a list of
    ``(key_prefix, rtt_s)`` pairs, first match wins, flat ``rtt_s`` as the
    fallback. That is the 2-tier DCN model the hierarchy bench needs —
    intra-group keys ride a fast link, inter-region up-links a slow one —
    and it mirrors how the fault plane scopes ``link_jitter:prefix=``."""

    def __init__(self, inner, rtt_s: float, classes=None):
        self.inner = inner
        self.rtt_s = rtt_s
        self.classes = list(classes or [])
        self.ops = 0

    def _wait(self, key=""):
        self.ops += 1
        rtt = self.rtt_s
        for prefix, class_rtt in self.classes:
            if key.startswith(prefix):
                rtt = class_rtt
                break
        if rtt > 0:
            time.sleep(rtt)

    def set(self, key, value):
        self._wait(key)
        self.inner.set(key, value)

    def get(self, key, default=None):
        self._wait(key)
        return self.inner.get(key, default)

    def delete(self, key):
        self._wait(key)
        self.inner.delete(key)

    def keys(self, prefix=""):
        self._wait(prefix)
        return self.inner.keys(prefix)


def bench_wire(name, steps, *, payload_mb=64, leaf_kb=1024, codec="blosc",
               bucket_mb=4.0, workers=4, rtt_ms=2.0, trace_out=""):
    """Wire microbench: one writer channel publishes a payload_mb pytree,
    one reader channel reads it back, over a LatencyKV. bucket_mb=0 +
    workers=0 is the blocking wire; the overlapped/blocking row pair at the
    same geometry is the tentpole's publish+read win. Rows record
    payload_sha256 over the ordered chunk values so bitwise identity
    between the pair is an assertion, not a hope."""
    import hashlib

    from ps_pytorch_tpu.parallel.transport import KVPytreeChannel
    from ps_pytorch_tpu.runtime.coordinator import KVStore

    n_leaves = max(int(payload_mb * 1024 // leaf_kb), 1)
    per_leaf = int(leaf_kb * 1024 // 4)
    rng = np.random.default_rng(0)
    # Mildly compressible floats (values in [-1, 1]): blosc gets a real
    # ratio without the payload degenerating to a constant.
    tree = {f"l{i:04d}": rng.normal(size=(per_leaf,))
            .astype(np.float32) / 4.0 for i in range(n_leaves)}
    bucket_bytes = int(bucket_mb * (1 << 20))
    publish_s = read_s = 0.0
    sha = payload_bytes = buckets = None
    reps = max(steps, 1)
    for rep in range(reps):
        kv = LatencyKV(KVStore(), rtt_ms / 1e3)
        writer = KVPytreeChannel(kv, "bench/wire", tree, codec=codec,
                                 bucket_bytes=bucket_bytes, workers=workers)
        reader = KVPytreeChannel(kv, "bench/wire", tree, codec=codec,
                                 bucket_bytes=bucket_bytes, workers=workers)
        t0 = time.perf_counter()
        writer.publish(1, tree)
        t1 = time.perf_counter()
        got = reader.read()
        t2 = time.perf_counter()
        assert got is not None and got[0] == 1
        publish_s += t1 - t0
        read_s += t2 - t1
        if rep == 0:
            for k in tree:
                np.testing.assert_array_equal(got[1][k], tree[k])
            # Hash the armoured payload in key order, straight off the
            # backing dict (no RTT model on the audit path).
            h = hashlib.sha256()
            meta = json.loads(kv.inner.get("bench/wire/1/meta"))
            for l_idx, n in enumerate(meta["chunks"]):
                for c_idx in range(n):
                    h.update(kv.inner.get(f"bench/wire/1/{l_idx}/{c_idx}")
                             .encode("ascii"))
            sha = h.hexdigest()
            payload_bytes = writer.last_publish_bytes
            buckets = len(writer.last_publish_bucket_bytes)
    row = {"config": name, "platform": "host", "payload_mb": payload_mb,
           "leaves": n_leaves, "codec": codec, "bucket_mb": bucket_mb,
           "workers": workers, "rtt_ms": rtt_ms, "buckets": buckets,
           "wire_mb": round(payload_bytes / 1e6, 2),
           "publish_s": round(publish_s / reps, 3),
           "read_s": round(read_s / reps, 3),
           "total_s": round((publish_s + read_s) / reps, 3),
           "steps": reps, "payload_sha256": sha}
    if trace_out:
        from ps_pytorch_tpu.telemetry import Tracer, set_default_tracer
        tracer = Tracer(pid=0)
        prev = set_default_tracer(tracer)
        try:
            kv = LatencyKV(KVStore(), rtt_ms / 1e3)
            writer = KVPytreeChannel(kv, "bench/wire", tree, codec=codec,
                                     bucket_bytes=bucket_bytes,
                                     workers=workers)
            reader = KVPytreeChannel(kv, "bench/wire", tree, codec=codec,
                                     bucket_bytes=bucket_bytes,
                                     workers=workers)
            writer.publish(1, tree)
            reader.read()
        finally:
            set_default_tracer(prev)
        with open(trace_out, "w") as f:
            for s in tracer.spans():
                f.write(json.dumps(s) + "\n")
    return row


def bench_codec_agg(name, steps, *, codec="int8lat", payload_mb=24,
                    leaf_kb=1024, contributors=4, frac=0.01, rtt_ms=2.0,
                    bucket_mb=4.0, workers=4, trace_out=""):
    """Gradient-wire + leader-aggregation bench for one grad codec:
    ``contributors`` senders each encode a payload_mb float32 gradient
    tree, publish it through a KVPytreeChannel over the LatencyKV, and the
    leader reads all of them back and aggregates. codec="blosc" is the
    decode-then-average baseline (today's leader: per-contributor float32
    trees, averaged in float). The homomorphic family (int8lat/topk/randk)
    ships codec payloads instead and the leader sums them in the
    compressed domain — submit_encoded + collect, ONE decode after the
    cutoff. wire_mb is armoured bytes on the KV for all contributors;
    bitwise_identical pins the homomorphic average against the
    decode_then_average oracle over the exact same payloads."""
    from ps_pytorch_tpu.compression.codecs import (
        HOMOMORPHIC_GRAD_CODECS, decode_then_average, encode_leaves,
        is_payload)
    from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator
    from ps_pytorch_tpu.parallel.transport import KVPytreeChannel
    from ps_pytorch_tpu.runtime.coordinator import KVStore

    homomorphic = codec in HOMOMORPHIC_GRAD_CODECS
    n_leaves = max(int(payload_mb * 1024 // leaf_kb), 1)
    per_leaf = int(leaf_kb * 1024 // 4)
    rng = np.random.default_rng(7)
    trees = [{f"l{i:04d}": rng.normal(size=(per_leaf,))
              .astype(np.float32) / 4.0 for i in range(n_leaves)}
             for _ in range(contributors)]
    leaves0, treedef = jax.tree.flatten(trees[0])
    raw_bytes = contributors * sum(l.nbytes for l in leaves0)
    bucket_bytes = int(bucket_mb * (1 << 20))
    if homomorphic:
        template = jax.tree.unflatten(treedef, encode_leaves(
            codec, [np.zeros_like(l) for l in leaves0],
            slice_id=0, step=0, frac=frac))
    else:
        template = trees[0]

    encode_s = publish_s = read_s = agg_s = 0.0
    wire_bytes = bitwise = rel_err = None
    reps = max(min(steps, 3), 1)
    for rep in range(reps):
        kv = LatencyKV(KVStore(), rtt_ms / 1e3)
        writers = [KVPytreeChannel(kv, f"bench/agg/{w}", template,
                                   codec="blosc", bucket_bytes=bucket_bytes,
                                   workers=workers)
                   for w in range(contributors)]
        readers = [KVPytreeChannel(kv, f"bench/agg/{w}", template,
                                   codec="blosc", bucket_bytes=bucket_bytes,
                                   workers=workers)
                   for w in range(contributors)]
        # Sender side: homomorphic codecs pay an explicit encode before
        # the wire; the blosc baseline compresses inside publish().
        t0 = time.perf_counter()
        if homomorphic:
            payloads = [encode_leaves(codec, jax.tree.leaves(t),
                                      slice_id=w, step=rep, frac=frac)
                        for w, t in enumerate(trees)]
            wire_trees = [jax.tree.unflatten(treedef, p) for p in payloads]
        else:
            wire_trees = trees
        t1 = time.perf_counter()
        for w, tree in enumerate(wire_trees):
            writers[w].publish(rep + 1, tree)
        t2 = time.perf_counter()
        got = [r.read() for r in readers]
        t3 = time.perf_counter()
        assert all(g is not None and g[0] == rep + 1 for g in got)
        # Leader side: the real collect() path for this codec.
        agg = StaleGradientAggregator(
            contributors, staleness_limit=4, num_aggregate=0,
            compress=homomorphic, codec=codec if homomorphic else "blosc",
            topk_frac=frac)
        t4 = time.perf_counter()
        for w, (_, tree, _meta) in enumerate(got):
            if homomorphic:
                agg.submit_encoded(w, rep + 1, tree)
            else:
                agg.submit(w, rep + 1, tree)
        avg, _info = agg.collect(rep + 1)
        t5 = time.perf_counter()
        encode_s += t1 - t0
        publish_s += t2 - t1
        read_s += t3 - t2
        agg_s += t5 - t4
        if rep == 0:
            wire_bytes = sum(w.last_publish_bytes for w in writers)
            avg_leaves = [np.asarray(l) for l in jax.tree.leaves(avg)]
            true_mean = [np.mean([t[k] for t in trees], axis=0)
                         for k in sorted(trees[0])]
            num = sum(float(np.sum((a - m) ** 2))
                      for a, m in zip(avg_leaves, true_mean))
            den = sum(float(np.sum(m ** 2)) for m in true_mean)
            rel_err = round((num / max(den, 1e-30)) ** 0.5, 6)
            if homomorphic:
                # Oracle: decode every contribution, average in float — the
                # compressed-domain sum must match it bitwise (int8lat) /
                # exactly per-position (sparse adds in the same order).
                oracle = decode_then_average(
                    codec, [(1.0, [l for l in jax.tree.leaves(
                        got[w][1], is_leaf=is_payload)])
                        for w in range(contributors)])
                oracle = [o.reshape(a.shape)
                          for o, a in zip(oracle, avg_leaves)]
                bitwise = all(np.array_equal(a, o)
                              for a, o in zip(avg_leaves, oracle))
    row = {"config": name, "platform": "host", "grad_codec": codec,
           "contributors": contributors, "payload_mb": payload_mb,
           "leaves": n_leaves, "frac": frac if homomorphic else None,
           "rtt_ms": rtt_ms, "bucket_mb": bucket_mb, "workers": workers,
           "raw_mb": round(raw_bytes / 1e6, 2),
           "wire_mb": round(wire_bytes / 1e6, 2),
           "wire_ratio": round(raw_bytes / max(wire_bytes, 1), 2),
           "encode_s": round(encode_s / reps, 3),
           "publish_s": round(publish_s / reps, 3),
           "read_s": round(read_s / reps, 3),
           "agg_s": round(agg_s / reps, 4),
           "total_s": round((encode_s + publish_s + read_s + agg_s)
                            / reps, 3),
           "agg_rel_err": rel_err, "bitwise_identical": bitwise,
           "steps": reps}
    if trace_out:
        from ps_pytorch_tpu.telemetry import Tracer, set_default_tracer
        tracer = Tracer(pid=0)
        prev = set_default_tracer(tracer)
        try:
            kv = LatencyKV(KVStore(), rtt_ms / 1e3)
            ch = KVPytreeChannel(kv, "bench/agg/0", template, codec="blosc",
                                 bucket_bytes=bucket_bytes, workers=workers)
            ch.publish(1, wire_trees[0])
        finally:
            set_default_tracer(prev)
        with open(trace_out, "w") as f:
            for s in tracer.spans():
                f.write(json.dumps(s) + "\n")
    return row


def bench_hier_agg(name, steps, *, codec="int8lat", payload_mb=8,
                   leaf_kb=512, n_slices=4, group_size=2, frac=0.01,
                   intra_rtt_ms=1.0, inter_rtt_ms=30.0):
    """Flat star vs 2-tier hierarchy over a per-link-latency DCN model
    (parallel/hierarchy.py). The LatencyKV classes give intra-group keys a
    fast link and everything crossing regions a slow one — the geometry
    where a tree pays off: flat ships ``n_slices`` payloads over the slow
    link, the hierarchy ships ``n_groups`` re-encoded group aggregates
    (members ride the fast link). ``rel_err`` pins the hier average
    against the flat compressed-domain average — the re-encode hop may
    round to the codec lattice, so this is a tolerance, not bitwise."""
    from ps_pytorch_tpu.compression.codecs import encode_leaves, is_payload
    from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator
    from ps_pytorch_tpu.parallel.hierarchy import (
        GroupAggregator, HierarchyPlan, RootAggregator,
    )
    from ps_pytorch_tpu.parallel.transport import KVPytreeChannel
    from ps_pytorch_tpu.runtime.coordinator import KVStore

    plan = HierarchyPlan(n_slices, group_size)
    n_leaves = max(int(payload_mb * 1024 // leaf_kb), 1)
    per_leaf = int(leaf_kb * 1024 // 4)
    rng = np.random.default_rng(11)
    trees = [{f"l{i:04d}": rng.normal(size=(per_leaf,))
              .astype(np.float32) / 4.0 for i in range(n_leaves)}
             for _ in range(n_slices)]
    leaves0, treedef = jax.tree.flatten(trees[0])
    template = jax.tree.unflatten(treedef, encode_leaves(
        codec, [np.zeros_like(l) for l in leaves0],
        slice_id=0, step=0, frac=frac))
    payloads = [encode_leaves(codec, jax.tree.leaves(t), slice_id=w,
                              step=1, frac=frac)
                for w, t in enumerate(trees)]
    wire_trees = [jax.tree.unflatten(treedef, p) for p in payloads]
    classes = [("bench/hgrad/", intra_rtt_ms / 1e3)]

    def clock_kv():
        # Everything not intra-group (flat star legs AND hier up-links)
        # crosses regions at the slow RTT.
        return LatencyKV(KVStore(), inter_rtt_ms / 1e3, classes=classes)

    flat_s = hier_s = 0.0
    flat_avg = hier_avg = None
    flat_slow = hier_slow = None
    reps = max(min(steps, 3), 1)
    for rep in range(reps):
        # -- flat star: n_slices payloads over the slow link ------------
        kv = clock_kv()
        t0 = time.perf_counter()
        for w, tree in enumerate(wire_trees):
            KVPytreeChannel(kv, f"bench/flat/{w}", template,
                            codec="blosc").publish(1, tree)
        agg = StaleGradientAggregator(n_slices, staleness_limit=4,
                                      num_aggregate=0, compress=True,
                                      codec=codec, topk_frac=frac)
        for w in range(n_slices):
            got = KVPytreeChannel(kv, f"bench/flat/{w}", template,
                                  codec="blosc").read()
            agg.submit_encoded(w, 1, got[1])
        avg, _ = agg.collect(1)
        flat_s += time.perf_counter() - t0
        if rep == 0:
            flat_avg = [np.asarray(l) for l in jax.tree.leaves(avg)]
            flat_slow = kv.ops

        # -- 2-tier: members ride the fast link, one re-encoded payload
        #    per group crosses regions --------------------------------
        kv = clock_kv()
        t0 = time.perf_counter()
        for w, tree in enumerate(wire_trees):
            gid = plan.group_of(w)
            KVPytreeChannel(kv, f"bench/hgrad/{gid}/{w}", template,
                            codec="blosc").publish(1, tree)
        root = RootAggregator(plan.n_groups, codec, staleness_limit=4)
        for gid in range(plan.n_groups):
            ga = GroupAggregator(plan, gid, codec, staleness_limit=4,
                                 topk_frac=frac)
            for sid in plan.members(gid):
                got = KVPytreeChannel(kv, f"bench/hgrad/{gid}/{sid}",
                                      template, codec="blosc").read()
                ga.submit_encoded(sid, 1, got[1])
            step, wsum, up = ga.collect_and_reencode(1)
            KVPytreeChannel(kv, f"bench/hagg/{gid}", template,
                            codec="blosc").publish(
                                1, up, meta={"wsum": wsum})
        for gid in range(plan.n_groups):
            got = KVPytreeChannel(kv, f"bench/hagg/{gid}", template,
                                  codec="blosc").read()
            root.submit_group(gid, 1, float(got[2]["wsum"]), got[1])
        avg, _ = root.collect(1)
        hier_s += time.perf_counter() - t0
        if rep == 0:
            hier_avg = [np.asarray(l) for l in
                        jax.tree.leaves(avg, is_leaf=is_payload)]
            hier_slow = kv.ops
    num = sum(float(np.sum((h.reshape(f.shape) - f) ** 2))
              for h, f in zip(hier_avg, flat_avg))
    den = sum(float(np.sum(f ** 2)) for f in flat_avg)
    rel_err = round((num / max(den, 1e-30)) ** 0.5, 6)
    return {"config": name, "platform": "host", "grad_codec": codec,
            "n_slices": n_slices, "group_size": plan.group_size,
            "n_groups": plan.n_groups, "payload_mb": payload_mb,
            "intra_rtt_ms": intra_rtt_ms, "inter_rtt_ms": inter_rtt_ms,
            "flat_s": round(flat_s / reps, 3),
            "hier_s": round(hier_s / reps, 3),
            "speedup": round(flat_s / max(hier_s, 1e-9), 3),
            "flat_kv_ops": flat_slow, "hier_kv_ops": hier_slow,
            "rel_err": rel_err, "steps": reps}


def bench_ops_overhead(name, steps, *, batch=256, reps=3):
    """Ops-plane cost row: the SAME jitted LeNet step loop timed bare and
    with the full live-ops work per step — running /metrics exporter,
    registry gauge/counter/histogram updates, health-watchdog observation,
    and a flight-recorder step record. Both loops materialize the loss
    (the sync the real trainers pay anyway), so overhead_frac isolates
    exactly what the ops plane adds. min-of-reps on both sides trims
    scheduler noise; the budget asserted in the row (and enforced by
    tools/regress.py) is <2%."""
    import tempfile

    from ps_pytorch_tpu.telemetry import (
        FlightRecorder, HealthMonitor, MetricsExporter, Registry,
        declare_training_metrics, host_rss_bytes,
    )

    state0, step_fn, x, y, mask = _build("LeNet", "synthetic_mnist", batch,
                                         n_devices=1)

    def run(ops) -> float:
        # The jitted step donates its input buffers; each rep needs a
        # fresh copy of the initial state or the second rep reads
        # deleted buffers.
        state = jax.tree.map(jnp.copy, state0)
        registry = declare_training_metrics(Registry())
        health = HealthMonitor("nonfinite:warn;spike:warn;divergence:warn",
                               registry=registry)
        tmp = tempfile.mkdtemp(prefix="bench_ops_")
        flightrec = FlightRecorder(os.path.join(tmp, "flightrec.json"),
                                   registry=registry)
        exporter = MetricsExporter(registry).start() if ops else None
        try:
            for i in range(3):
                state, metrics = step_fn(state, x, y, mask,
                                         jax.random.key(i))
            jax.block_until_ready(state.params)
            t0 = time.perf_counter()
            prev = None
            for i in range(steps):
                state, metrics = step_fn(state, x, y, mask,
                                         jax.random.key(100 + i))
                loss = float(metrics["loss"])
                if ops:
                    registry.inc("train_steps")
                    registry.set("train_step", float(i + 1))
                    registry.set("train_loss", loss)
                    t_step = time.perf_counter() - (prev or t0)
                    registry.set("train_step_time_s", t_step)
                    registry.observe("train_step_latency_s", t_step)
                    registry.set("host_rss_bytes", float(host_rss_bytes()))
                    flightrec.record_step(i + 1, loss=loss,
                                          step_time=t_step)
                    health.observe_step(i + 1, loss=loss, nonfinite=False,
                                        step_time=t_step)
                prev = time.perf_counter()
            jax.block_until_ready(state.params)
            return time.perf_counter() - t0
        finally:
            if exporter is not None:
                exporter.stop()

    baseline_s = min(run(False) for _ in range(reps))
    ops_s = min(run(True) for _ in range(reps))
    frac = (ops_s - baseline_s) / baseline_s
    return {"config": name, "platform": jax.devices()[0].platform,
            "steps": steps, "reps": reps, "global_batch": batch,
            "baseline_s": round(baseline_s, 5), "ops_s": round(ops_s, 5),
            "overhead_frac": round(frac, 5), "ok": frac < 0.02}


def bench_integrity_overhead(name, steps, *, batch=256, reps=3):
    """Gradient-integrity cost row: the SAME jitted LeNet step loop timed
    bare and with the full per-step integrity work the async PS leader
    adds — wire digests over every armoured chunk on BOTH sides (the
    writer's stamp and the reader's verify, for all 4 contributors) plus
    the compressed-domain screen (validators + norms + MAD gate +
    quarantine bookkeeping) over one 4-contributor round. Payload encode
    and armouring are NOT in the delta — the homomorphic wire pays those
    with or without integrity. One process does all 4 contributors' digest
    work here, so the row is an upper bound on any single process's share;
    the budget asserted (and enforced by tools/regress.py) is <2%."""
    from ps_pytorch_tpu.compression.codecs import encode_leaves
    from ps_pytorch_tpu.parallel.transport import _encode_leaf
    from ps_pytorch_tpu.resilience.integrity import (
        GradIntegrity, verify_digest, wire_digest,
    )

    state0, step_fn, x, y, mask = _build("LeNet", "synthetic_mnist", batch,
                                         n_devices=1)
    # One round of LeNet-gradient-shaped int8lat contributions, encoded
    # and armoured once up front (that cost exists regardless).
    rng = np.random.default_rng(0)
    grad_leaves = [rng.standard_normal(l.shape).astype(np.float32) * 0.01
                   for l in jax.tree.leaves(state0.params)]
    contribs, chunks = [], []
    for sid in range(4):
        payloads = encode_leaves("int8lat", grad_leaves, slice_id=sid,
                                 step=0)
        contribs.append((sid, payloads))
        chunks.append([c for p in payloads
                       for c in _encode_leaf(p, 3, "blosc")])
    wire_chunks = sum(len(c) for c in chunks)

    def run(integrity) -> float:
        state = jax.tree.map(jnp.copy, state0)
        gi = GradIntegrity() if integrity else None
        for i in range(3):
            state, metrics = step_fn(state, x, y, mask, jax.random.key(i))
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        for i in range(steps):
            state, metrics = step_fn(state, x, y, mask,
                                     jax.random.key(100 + i))
            float(metrics["loss"])
            if integrity:
                for sid_chunks in chunks:
                    toks = [wire_digest(c) for c in sid_chunks]
                    assert all(verify_digest(c, t)
                               for c, t in zip(sid_chunks, toks))
                admitted, _ = gi.screen(contribs, step=i)
                assert len(admitted) == 4
        jax.block_until_ready(state.params)
        return time.perf_counter() - t0

    baseline_s = min(run(False) for _ in range(reps))
    integrity_s = min(run(True) for _ in range(reps))
    frac = (integrity_s - baseline_s) / baseline_s
    return {"config": name, "platform": jax.devices()[0].platform,
            "steps": steps, "reps": reps, "global_batch": batch,
            "contributors": 4, "wire_chunks": wire_chunks,
            "baseline_s": round(baseline_s, 5),
            "integrity_s": round(integrity_s, 5),
            "overhead_frac": round(frac, 5), "ok": frac < 0.02}


def bench_elastic_overhead(name, steps, *, batch=256, reps=3):
    """Elastic control-plane cost row: the SAME jitted LeNet step loop
    timed bare and with the full per-step elastic work the trainers add
    when --elastic is on and no faults fire — heartbeat, lease refresh
    (throttled to one write per interval), membership recompute over the
    announcement keys, and the leader_epoch/world_size gauge updates.
    In-process KVStore, so the row measures the control-plane arithmetic
    itself; in a real run the throttles bound the KV traffic to a few
    RPCs per lease interval regardless of step rate. min-of-reps on both
    sides; the budget asserted in the row is <2%."""
    from ps_pytorch_tpu import elastic as elx
    from ps_pytorch_tpu.runtime.coordinator import KVStore
    from ps_pytorch_tpu.telemetry import (
        Registry, declare_elastic_metrics, declare_training_metrics,
    )

    state0, step_fn, x, y, mask = _build("LeNet", "synthetic_mnist", batch,
                                         n_devices=1)

    def run(elastic) -> float:
        state = jax.tree.map(jnp.copy, state0)
        registry = declare_training_metrics(Registry())
        election = announcer = membership = None
        if elastic:
            declare_elastic_metrics(registry)
            kv = KVStore()
            election = elx.LeaderElection(kv, "bench", 0, 1, interval_s=1.0)
            announcer = elx.MemberAnnouncer(kv, "bench", 0, [0],
                                            interval_s=1.0)
            membership = elx.MembershipRegistry(kv, "bench", 1, 1)
            election.claim_initial()
            announcer.join()
        for i in range(3):
            state, metrics = step_fn(state, x, y, mask, jax.random.key(i))
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        for i in range(steps):
            state, metrics = step_fn(state, x, y, mask,
                                     jax.random.key(100 + i))
            float(metrics["loss"])
            if elastic:
                announcer.beat(i + 1)
                election.refresh(i + 1)
                membership.update(i + 1)
                registry.set("leader_epoch", float(election.epoch))
                registry.set("world_size",
                             float(len(membership.members) or 1))
        jax.block_until_ready(state.params)
        return time.perf_counter() - t0

    baseline_s = min(run(False) for _ in range(reps))
    elastic_s = min(run(True) for _ in range(reps))
    frac = (elastic_s - baseline_s) / baseline_s
    return {"config": name, "platform": jax.devices()[0].platform,
            "steps": steps, "reps": reps, "global_batch": batch,
            "baseline_s": round(baseline_s, 5),
            "elastic_s": round(elastic_s, 5),
            "overhead_frac": round(frac, 5), "ok": frac < 0.02}


def bench_kvrep_overhead(name, steps, *, payload_mb=24, leaf_kb=1024,
                         codec="blosc", bucket_mb=4.0, workers=4,
                         rtt_ms=2.0, n_backends=3, reps=5):
    """Quorum-replication cost row (ISSUE 14, runtime/kvrep.py): the wire
    bench's publish+read — the SAME payload through the SAME overlapped
    KVPytreeChannel at the same RTT — over one LatencyKV (the single
    store every consumer ran on before --kv-replicas) and over a
    ReplicatedKV spanning n_backends LatencyKVs. Writes fan out in
    parallel (wall cost = slowest responder, not the sum) and reads tag-
    compare headers without copying each replica's payload, so the
    replicated wall time is one RTT plus a fixed ~0.1 ms dispatch tax per
    op — amortized over wire-sized values that is the <5% overhead_frac
    this row asserts and the kvrep regress family gates. min-of-reps on
    both legs; payload equality is asserted on the replicated leg (the
    quorum plane may not perturb the wire)."""
    from ps_pytorch_tpu.parallel.transport import KVPytreeChannel
    from ps_pytorch_tpu.runtime.coordinator import KVStore
    from ps_pytorch_tpu.runtime.kvrep import ReplicatedKV

    rtt_s = rtt_ms / 1e3
    n_leaves = max(int(payload_mb * 1024 // leaf_kb), 1)
    per_leaf = int(leaf_kb * 1024 // 4)
    rng = np.random.default_rng(0)
    tree = {f"l{i:04d}": rng.normal(size=(per_leaf,))
            .astype(np.float32) / 4.0 for i in range(n_leaves)}
    bucket_bytes = int(bucket_mb * (1 << 20))

    def run(kv) -> float:
        writer = KVPytreeChannel(kv, "bench/kvrep", tree, codec=codec,
                                 bucket_bytes=bucket_bytes, workers=workers)
        reader = KVPytreeChannel(kv, "bench/kvrep", tree, codec=codec,
                                 bucket_bytes=bucket_bytes, workers=workers)
        t0 = time.perf_counter()
        writer.publish(1, tree)
        got = reader.read()
        dt = time.perf_counter() - t0
        assert got is not None and got[0] == 1
        for k in tree:
            np.testing.assert_array_equal(got[1][k], tree[k])
        return dt

    single_s = min(run(LatencyKV(KVStore(), rtt_s)) for _ in range(reps))
    replicated_s = min(
        run(ReplicatedKV([LatencyKV(KVStore(), rtt_s)
                          for _ in range(n_backends)], writer="bench"))
        for _ in range(reps))
    frac = (replicated_s - single_s) / single_s
    return {"config": name, "platform": "host", "payload_mb": payload_mb,
            "leaves": n_leaves, "codec": codec, "bucket_mb": bucket_mb,
            "workers": workers, "rtt_ms": rtt_ms, "n_backends": n_backends,
            "reps": reps, "single_s": round(single_s, 5),
            "replicated_s": round(replicated_s, 5),
            "overhead_frac": round(frac, 5), "ok": frac < 0.05}


def bench_zero(name, steps, *, n_shards=2, payload_mb=24, leaf_kb=1024,
               optimizer="sgd", workers=4, rtt_ms=2.0):
    """ZeRO-over-the-wire row (ISSUE 15, parallel/zero_wire.py): N single-
    shard-owner ZeroWireUpdater instances drive the SAME deterministic
    gradient stream over one LatencyKV. n_shards=1 IS the replicated
    baseline — the one owner applies the full update and publishes the
    full param pytree, exactly what the monolithic canonical publish
    shipped. Each row records the per-replica wire bytes (max over
    members: the sharded owner publishes 1/N of the tree), the
    publish/assemble walls, the per-replica optimizer-state footprint
    (~1/N — the memory claim), and a sha256 of the final assembled
    params; main() derives zero_wire_win_* rows asserting the sharded
    run is BITWISE identical to the replicated one while cutting both
    per-replica publish bytes and optimizer memory."""
    import hashlib

    from ps_pytorch_tpu.parallel.zero_wire import ZeroWireUpdater
    from ps_pytorch_tpu.runtime.coordinator import KVStore

    n_leaves = max(int(payload_mb * 1024 // leaf_kb), 1)
    per_leaf = int(leaf_kb * 1024 // 4)
    rng = np.random.default_rng(0)
    tree = {f"l{i:04d}": rng.normal(size=(per_leaf,))
            .astype(np.float32) / 4.0 for i in range(n_leaves)}
    opt_kw = dict(lr=0.05, momentum=0.9) if optimizer == "sgd" \
        else dict(lr=1e-3)
    kv = LatencyKV(KVStore(), rtt_ms / 1e3)
    members = list(range(n_shards))
    ups = [ZeroWireUpdater(inner=None, kv=kv, run_id="bench/zw", params=tree,
                           optimizer=optimizer, members=members, me=m,
                           n_shards=n_shards, workers=workers, **opt_kw)
           for m in members]
    rounds = max(min(steps, 5), 2)
    publish_s = assemble_s = 0.0
    grng = np.random.default_rng(1)
    full = None
    for rnd in range(rounds):
        g = {k: grng.normal(size=v.shape).astype(np.float32) / 8.0
             for k, v in tree.items()}
        t0 = time.perf_counter()
        for u in ups:                   # each member: update + publish 1/N
            u.apply_and_publish(g, version=rnd + 1)
        t1 = time.perf_counter()
        trees = [u.assemble_round() for u in ups]
        assemble_s += time.perf_counter() - t1
        publish_s += t1 - t0
        full = trees[0]
    h = hashlib.sha256()
    for k in sorted(full):
        h.update(np.ascontiguousarray(full[k], np.float32).tobytes())
    out_mb = [u.wire_stats()["zw_bytes_out"] / 1e6 for u in ups]
    in_mb = [u.wire_stats()["zw_bytes_in"] / 1e6 for u in ups]
    opt_mb = [u.opt_state_nbytes() / 1e6 for u in ups]
    return {"config": name, "platform": "host", "payload_mb": payload_mb,
            "leaves": n_leaves, "optimizer": optimizer, "shards": n_shards,
            "workers": workers, "rtt_ms": rtt_ms, "rounds": rounds,
            "wire_out_mb_max": round(max(out_mb), 3),
            "wire_out_mb_mean": round(sum(out_mb) / len(out_mb), 3),
            "wire_in_mb_max": round(max(in_mb), 3),
            "publish_s": round(publish_s / rounds, 4),
            "assemble_s": round(assemble_s / rounds, 4),
            "total_s": round((publish_s + assemble_s) / rounds, 4),
            "opt_state_mb_max": round(max(opt_mb), 3),
            "params_sha256": h.hexdigest()}


CONFIGS = {
    "lenet_mnist_single": lambda steps: bench_throughput(
        "lenet_mnist_single", "LeNet", "synthetic_mnist", 128, steps,
        n_devices=1),
    "lenet_mnist_dp": lambda steps: bench_throughput(
        "lenet_mnist_dp", "LeNet", "synthetic_mnist", 1024, steps),
    "resnet18_cifar10_dp": lambda steps: bench_throughput(
        "resnet18_cifar10_dp", "ResNet18", "synthetic", 1024, steps),
    "vgg11_cifar100_kofn": lambda steps: bench_throughput(
        "vgg11_cifar100_kofn", "VGG11", "synthetic_cifar100", 256, steps,
        mode="kofn",
        num_aggregate=max(len(jax.devices()) - 1, 1)),
    "resnet50_imagenet": lambda steps: bench_throughput(
        "resnet50_imagenet", "ResNet50_ImageNet", "synthetic_imagenet", 32,
        steps),
    # -- capability rows (VERDICT r2 items 1, 6, 8): same headline task, one
    # feature toggled, so each row isolates that feature's cost/win. --
    "resnet18_fused_sgd": lambda steps: bench_throughput(
        "resnet18_fused_sgd", "ResNet18", "synthetic", 1024, steps,
        fused=True),
    "resnet18_zero1": lambda steps: bench_throughput(
        "resnet18_zero1", "ResNet18", "synthetic", 1024, steps,
        shard_update=True),
    "resnet18_remat": lambda steps: bench_throughput(
        "resnet18_remat", "ResNet18", "synthetic", 1024, steps, remat=True),
    "resnet18_b2048": lambda steps: bench_throughput(
        "resnet18_b2048", "ResNet18", "synthetic", 2048, steps),
    "resnet18_b4096": lambda steps: bench_throughput(
        "resnet18_b4096", "ResNet18", "synthetic", 4096, steps),
    "int8_quantizer": lambda steps: bench_quantizer("int8_quantizer", steps),
    "resnet18_async_2slice": lambda steps: bench_async_multislice(
        "resnet18_async_2slice", steps),
    "transformer_lm_2k": lambda steps: bench_transformer_lm(
        "transformer_lm_2k", steps),
    # remat cost on the LM (the CNN ladder has resnet18_remat): per-block
    # recompute tax in tokens/sec at the same geometry.
    "transformer_lm_2k_remat": lambda steps: bench_transformer_lm(
        "transformer_lm_2k_remat", steps, remat=True),
    # fused blockwise attention (ops/flash_attention.py) at the same
    # geometry: the tokens/sec delta vs transformer_lm_2k is the cost of
    # materializing [S, S] scores, paid by the "full" path.
    "transformer_lm_2k_flash": lambda steps: bench_transformer_lm(
        "transformer_lm_2k_flash", steps, attention="flash"),
    # single-chip long context: S=8192 — the materializing path's backward
    # residuals alone ([B,H,S,S] per block) exceed HBM here; flash makes
    # the geometry trainable on one chip at all.
    "transformer_lm_8k_flash": lambda steps: bench_transformer_lm(
        "transformer_lm_8k_flash", steps, batch=1, seq_len=8192,
        attention="flash"),
    "moe_lm_2k": lambda steps: bench_moe_lm("moe_lm_2k", steps),
    # decode economics of the one-jit k/v-cache generator: b=1 (latency)
    # and b=32 (batched sampling
    # throughput — same per-step work modulo the [B,V] sample).
    "lm_decode_b1": lambda steps: bench_lm_decode(
        "lm_decode_b1", min(steps, 5)),
    "lm_decode_b32": lambda steps: bench_lm_decode(
        "lm_decode_b32", min(steps, 5), batch=32),
    "pallas_conv_ab": lambda steps: bench_pallas_conv_ab(
        "pallas_conv_ab", steps),
    # Full-step A/B of the same experiment: the headline config with every
    # stride-1 3x3 on the Pallas path (custom VJP — Pallas fwd+input-grad,
    # XLA dW). images_per_sec vs resnet18_cifar10_dp is the adoption
    # decision at step granularity.
    "resnet18_pallas_conv": lambda steps: bench_throughput(
        "resnet18_pallas_conv", "ResNet18", "synthetic", 1024, steps,
        conv_impl="pallas"),
    # VGG-11 on the Pallas path at the committed vgg11_cifar100_kofn
    # geometry (all 3x3 s1 convs past the stem, biased): the delta vs that
    # row isolates the conv impl across VGG's channel ladder (64..512).
    "vgg11_pallas_conv": lambda steps: bench_throughput(
        "vgg11_pallas_conv", "VGG11", "synthetic_cifar100", 256, steps,
        mode="kofn", num_aggregate=max(len(jax.devices()) - 1, 1),
        conv_impl="pallas"),
    "lenet_convergence": lambda steps: bench_time_to_loss(
        "lenet_convergence", "LeNet", "synthetic_mnist", 512,
        target_loss=0.8),
    "input_pipeline": lambda steps: bench_input_pipeline(
        "input_pipeline", "synthetic_cifar10", 1024, steps),
    # ImageNet geometry (224 px, 602 KB/image): no augment stack (the
    # reference had none for ImageNet), so this measures the
    # shuffle+batch+ship path against resnet50_imagenet's chip demand —
    # 1,666 img/s in BENCH_SUITE_r03.json, ~1.0 GB/s from this loader.
    "input_pipeline_imagenet": lambda steps: bench_input_pipeline(
        "input_pipeline_imagenet", "synthetic_imagenet", 32, steps),
    # The REAL ImageNet train path: 256px uint8 store -> random-resized-
    # crop -> bilinear 224 -> hflip (native kernel when built, counter-rng)
    # through the multi-worker pool (workers=0: one per CPU). This row —
    # not the augment-free one above — is what loader_vs_chip_demand_
    # imagenet prefers: the 2.9x margin measured without augmentation was
    # the optimistic bound (VERDICT r5 weak #4).
    "input_pipeline_imagenet_augmented": lambda steps: bench_input_pipeline(
        "input_pipeline_imagenet_augmented", "synthetic_imagenet_rrc", 32,
        steps, workers=0),
    # -- overlapped gradient wire (parallel/buckets.py + transport.py):
    # blocking vs overlapped at the same payload/codec/RTT. The 64 MB pair
    # is the acceptance row (>= 25% publish+read win at --wire-workers 4);
    # main() derives wire_overlap_win_* from each pair and checks the
    # payload sha256s match (bitwise-identical wire). --
    "wire_blocking_8mb": lambda steps: bench_wire(
        "wire_blocking_8mb", min(steps, 5), payload_mb=8,
        bucket_mb=0, workers=0),
    "wire_overlapped_8mb": lambda steps: bench_wire(
        "wire_overlapped_8mb", min(steps, 5), payload_mb=8,
        bucket_mb=2, workers=4),
    "wire_blocking_24mb": lambda steps: bench_wire(
        "wire_blocking_24mb", min(steps, 4), payload_mb=24,
        bucket_mb=0, workers=0),
    "wire_overlapped_24mb": lambda steps: bench_wire(
        "wire_overlapped_24mb", min(steps, 4), payload_mb=24,
        bucket_mb=4, workers=4),
    "wire_blocking_64mb": lambda steps: bench_wire(
        "wire_blocking_64mb", min(steps, 3), payload_mb=64,
        bucket_mb=0, workers=0),
    "wire_overlapped_64mb": lambda steps: bench_wire(
        "wire_overlapped_64mb", min(steps, 3), payload_mb=64,
        bucket_mb=4, workers=4),
    # -- homomorphic gradient codecs (compression/codecs.py + async_dp
    # submit_encoded/collect): 4 contributors x 24 MB through the same
    # LatencyKV wire, leader aggregating in the compressed domain. The
    # blosc row is the decode-then-average baseline; main() derives
    # wire_codec_win_* from each pair (ISSUE 9 acceptance: topk@0.01
    # >= 2x wire-bytes cut, int8lat end-to-end win + bitwise-identical
    # to the decode-then-average oracle). --
    "wire_codec_blosc_24mb": lambda steps: bench_codec_agg(
        "wire_codec_blosc_24mb", min(steps, 3), codec="blosc"),
    "wire_codec_int8lat_24mb": lambda steps: bench_codec_agg(
        "wire_codec_int8lat_24mb", min(steps, 3), codec="int8lat"),
    "wire_codec_topk_24mb": lambda steps: bench_codec_agg(
        "wire_codec_topk_24mb", min(steps, 3), codec="topk", frac=0.01),
    "wire_codec_randk_24mb": lambda steps: bench_codec_agg(
        "wire_codec_randk_24mb", min(steps, 3), codec="randk", frac=0.01),
    # -- serving (ps_pytorch_tpu/serving/): 8 concurrent requests, batched
    # (8 slots) vs sequential (1 slot) through the same engine. main()
    # derives serve_batch_win_8 (ISSUE 5 acceptance: >= 1.5x tokens/sec AND
    # bitwise-identical tokens). --
    "serve_sequential_8": lambda steps: bench_serving(
        "serve_sequential_8", steps, slots=1),
    "serve_batched_8": lambda steps: bench_serving(
        "serve_batched_8", steps, slots=8),
    # -- request-scoped observability (ISSUE 8): the SLO ladder (knee +
    # goodput-under-SLO headline) and the reqtrace+SLO plane's cost on the
    # serve_batched_8 workload; both feed SLO_r*.json, gated by regress.py's
    # slo family. --
    "slo_sweep": lambda steps: bench_slo_sweep("slo_sweep", steps),
    "serve_reqtrace_overhead": lambda steps: bench_reqtrace_overhead(
        "serve_reqtrace_overhead", steps),
    # -- live ops plane (ISSUE 6): exporter + watchdogs + flight recorder
    # cost on the bare step loop; the row asserts the <2% budget that
    # tools/regress.py's ops family gates. --
    "ops_overhead": lambda steps: bench_ops_overhead(
        "ops_overhead", max(steps, 30)),
    # -- elastic control plane (ISSUE 7): heartbeat + lease + membership
    # cost per step when no faults fire; same <2% posture as ops_overhead.
    "elastic_overhead": lambda steps: bench_elastic_overhead(
        "elastic_overhead", max(steps, 30)),
    # gradient-integrity plane (resilience/integrity.py): per-step digest +
    # screen cost for a 4-contributor round; same <2% posture.
    "integrity_overhead": lambda steps: bench_integrity_overhead(
        "integrity_overhead", max(steps, 30)),
    # -- quorum-replicated coordination plane (ISSUE 14, runtime/kvrep.py):
    # the wire bench's 24 MB publish+read, 1 store vs majority-write/
    # newest-read over 3 at the same 2 ms RTT; parallel fan-out + header-
    # only tag peeks keep the per-op wall cost at one RTT, so the row
    # asserts the <5% budget the kvrep regress family gates.
    "kvrep_overhead": lambda steps: bench_kvrep_overhead(
        "kvrep_overhead", steps),
    # -- hierarchical multi-hop sync (ISSUE 11, parallel/hierarchy.py):
    # flat star vs 2-tier tree over the per-link LatencyKV (fast
    # intra-group, 20-50 ms inter-region). Each row carries BOTH legs;
    # main() derives hierarchy_win_* (acceptance: hier beats flat at
    # >= 3 slices). --
    "hier_sync_4slice": lambda steps: bench_hier_agg(
        "hier_sync_4slice", min(steps, 3), n_slices=4, group_size=2),
    "hier_sync_9slice": lambda steps: bench_hier_agg(
        "hier_sync_9slice", min(steps, 2), n_slices=9, group_size=3,
        payload_mb=4),
    # -- ZeRO-over-the-wire (ISSUE 15, parallel/zero_wire.py): sharded
    # weight update on the KV plane. The 1shard row IS the replicated
    # baseline (one owner, full-pytree publish); main() derives
    # zero_wire_win_* from each N-shard row vs it — acceptance: bitwise-
    # identical final params, per-replica publish bytes <= 0.75x the
    # full-pytree publish, optimizer state ~1/N per replica. --
    "zero_wire_1shard": lambda steps: bench_zero(
        "zero_wire_1shard", steps, n_shards=1),
    "zero_wire_2shard": lambda steps: bench_zero(
        "zero_wire_2shard", steps, n_shards=2),
    "zero_wire_4shard": lambda steps: bench_zero(
        "zero_wire_4shard", steps, n_shards=4),
}


def _run_isolated(name: str, steps: int, timeout_s: float) -> dict:
    """One config in a CHILD process with a hard wall-clock bound.

    A blocked device call cannot be interrupted in-process; a killed child
    frees the chip for the next row. The chip belongs to one process at a
    time, so the --isolate parent only imports jax and never opens a
    backend — keep it that way, or no child can take the chip. The compile
    cache keeps the per-child restart cost to seconds."""
    import subprocess
    import sys as _sys
    cmd = [_sys.executable, os.path.abspath(__file__), "--configs", name,
           "--steps", str(steps)]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout_s, cwd=os.path.dirname(
                                 os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"config": name, "error": f"timeout after {timeout_s:.0f}s "
                                         "(killed; device freed)"}
    for line in reversed(res.stdout.splitlines()):
        try:
            r = json.loads(line)
        except ValueError:
            continue
        if isinstance(r, dict) and r.get("config") == name:
            return r
    return {"config": name,
            "error": f"child rc={res.returncode}: "
                     f"{(res.stderr or res.stdout)[-200:]}"}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--configs", default=",".join(CONFIGS))
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--markdown", default="", help="also write a table here")
    p.add_argument("--isolate", action="store_true",
                   help="run each config in its own process with "
                        "--row-timeout; a hung row is killed and recorded "
                        "instead of hanging the suite")
    p.add_argument("--row-timeout", type=float, default=600.0)
    args = p.parse_args(argv)

    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()   # config only; opens no backend (see --isolate)
    rows = []
    for name in args.configs.split(","):
        name = name.strip()
        if name not in CONFIGS:
            raise SystemExit(f"unknown config {name!r}; have {sorted(CONFIGS)}")
        if args.isolate:
            r = _run_isolated(name, args.steps, args.row_timeout)
        else:
            try:
                r = CONFIGS[name](args.steps)
            except Exception as e:  # later rows still run; the exit code
                traceback.print_exc()     # below says one failed
                r = {"config": name, "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps(r), flush=True)
        rows.append(r)

    # Loader-vs-chip: when both the headline training config and the loader
    # bench ran, print their ratio — >= 2.0 means the input pipeline can
    # feed the chip with headroom (VERDICT r1 item 4's done-bar). The
    # ImageNet pairing PREFERS the augmented row (the real train path) and
    # falls back to the augment-free one; loader_config records which fed
    # the ratio so cross-round comparisons can't silently mix them.
    for chip_cfg, loader_cfgs, label in (
            ("resnet18_cifar10_dp", ("input_pipeline",),
             "loader_vs_chip_demand"),
            ("resnet50_imagenet", ("input_pipeline_imagenet_augmented",
                                   "input_pipeline_imagenet"),
             "loader_vs_chip_demand_imagenet")):
        chip = next((r for r in rows if r.get("config") == chip_cfg
                     and "images_per_sec" in r), None)
        loader = next((r for c in loader_cfgs for r in rows
                       if r.get("config") == c
                       and "loader_images_per_sec" in r), None)
        if chip and loader:
            ratio = loader["loader_images_per_sec"] / chip["images_per_sec"]
            print(json.dumps({"config": label,
                              "loader_config": loader["config"],
                              "ratio": round(ratio, 2),
                              "ok": ratio >= 2.0}), flush=True)

    # Wire overlap: for each blocking/overlapped pair that ran, derive the
    # end-to-end publish+read win and assert the two payloads were bitwise
    # identical (same sha256 over the ordered chunk values). ok needs BOTH:
    # a fast-but-different wire is a broken wire. 1.25x is the ISSUE 4
    # acceptance bar at the 64 MB row.
    for row in list(rows):
        cfg_name = row.get("config", "")
        if not cfg_name.startswith("wire_blocking_") or "error" in row:
            continue
        size = cfg_name[len("wire_blocking_"):]
        over = next((r for r in rows
                     if r.get("config") == f"wire_overlapped_{size}"
                     and "error" not in r), None)
        if over is None:
            continue
        ratio = row["total_s"] / max(over["total_s"], 1e-9)
        bitwise = (row["payload_sha256"] == over["payload_sha256"])
        out = {"config": f"wire_overlap_win_{size}",
               "blocking_s": row["total_s"], "overlapped_s": over["total_s"],
               "ratio": round(ratio, 3), "bitwise_identical": bitwise,
               "ok": bool(bitwise and ratio >= 1.25)}
        print(json.dumps(out), flush=True)
        rows.append(out)

    # Homomorphic grad codecs: each codec row vs the blosc decode-then-
    # average baseline at the same geometry. wire_ratio is bytes-on-wire
    # cut, total_ratio the end-to-end (encode+publish+read+aggregate) win.
    # ISSUE 9 bars: topk@0.01 needs >= 2x wire cut; int8lat needs an
    # end-to-end win AND bitwise identity to the oracle (a fast lossy
    # "lossless" path is a broken path).
    base = next((r for r in rows if r.get("config") == "wire_codec_blosc_24mb"
                 and "error" not in r), None)
    if base:
        for cname in ("int8lat", "topk", "randk"):
            row = next((r for r in rows
                        if r.get("config") == f"wire_codec_{cname}_24mb"
                        and "error" not in r), None)
            if row is None:
                continue
            wire_ratio = base["wire_mb"] / max(row["wire_mb"], 1e-9)
            total_ratio = base["total_s"] / max(row["total_s"], 1e-9)
            out = {"config": f"wire_codec_win_{cname}_24mb",
                   "baseline_wire_mb": base["wire_mb"],
                   "wire_mb": row["wire_mb"],
                   "wire_ratio": round(wire_ratio, 3),
                   "baseline_total_s": base["total_s"],
                   "total_s": row["total_s"],
                   "total_ratio": round(total_ratio, 3),
                   "bitwise_identical": row.get("bitwise_identical"),
                   "agg_rel_err": row.get("agg_rel_err")}
            if cname == "int8lat":
                out["ok"] = bool(out["bitwise_identical"]
                                 and total_ratio > 1.0 and wire_ratio >= 2.0)
            else:
                out["ok"] = bool(out["bitwise_identical"]
                                 and wire_ratio >= 2.0)
            print(json.dumps(out), flush=True)
            rows.append(out)

    # Hierarchical sync: each hier_sync_* row already carries both legs at
    # the same geometry/link model; the derived row states the acceptance
    # bar (ISSUE 11): the tree must beat the flat star once >= 3 slices
    # share the slow link, with the hier average inside codec tolerance.
    for row in list(rows):
        cfg_name = row.get("config", "")
        if not cfg_name.startswith("hier_sync_") or "error" in row:
            continue
        out = {"config": f"hierarchy_win_{cfg_name[len('hier_sync_'):]}",
               "n_slices": row["n_slices"], "n_groups": row["n_groups"],
               "flat_s": row["flat_s"], "hier_s": row["hier_s"],
               "speedup": row["speedup"], "rel_err": row["rel_err"],
               "ok": bool(row["n_slices"] >= 3 and row["speedup"] > 1.0
                          and row["rel_err"] < 0.05)}
        print(json.dumps(out), flush=True)
        rows.append(out)

    # ZeRO-over-the-wire: each N-shard row vs the 1shard replicated
    # baseline at the same geometry/RTT/grad stream. The three claims the
    # derived row certifies: (1) the sharded update is BITWISE identical
    # to the replicated one (same final-params sha256 — disjoint-slice
    # float32 ops are IEEE-identical to the full-vector ops), (2) the
    # per-replica publish bytes drop to ~1/N of the full-pytree publish,
    # (3) the per-replica optimizer state drops to ~1/N.
    zbase = next((r for r in rows if r.get("config") == "zero_wire_1shard"
                  and "error" not in r), None)
    if zbase:
        for row in list(rows):
            cfg_name = row.get("config", "")
            if not cfg_name.startswith("zero_wire_") or "error" in row \
                    or row is zbase or cfg_name.startswith("zero_wire_win"):
                continue
            n = row["shards"]
            wire_ratio = row["wire_out_mb_max"] / \
                max(zbase["wire_out_mb_max"], 1e-9)
            opt_ratio = row["opt_state_mb_max"] / \
                max(zbase["opt_state_mb_max"], 1e-9)
            bitwise = (row["params_sha256"] == zbase["params_sha256"])
            out = {"config": f"zero_wire_win_{n}shard",
                   "shards": n,
                   "baseline_wire_out_mb": zbase["wire_out_mb_max"],
                   "wire_out_mb_max": row["wire_out_mb_max"],
                   "wire_out_ratio": round(wire_ratio, 3),
                   "baseline_opt_state_mb": zbase["opt_state_mb_max"],
                   "opt_state_mb_max": row["opt_state_mb_max"],
                   "opt_state_ratio": round(opt_ratio, 3),
                   "baseline_total_s": zbase["total_s"],
                   "total_s": row["total_s"],
                   "bitwise_identical": bitwise,
                   "ok": bool(bitwise and wire_ratio <= 0.75
                              and opt_ratio <= 1.0 / n + 0.15)}
            print(json.dumps(out), flush=True)
            rows.append(out)

    # Serving: batched (8 slots) vs sequential (1 slot) aggregate
    # tokens/sec at 8 concurrent requests, AND the two runs' sampled tokens
    # must hash identically (slot-count invariance = generate() parity,
    # proven inside the artifact). ok needs BOTH — a fast engine that
    # samples different tokens is a broken engine. 1.5x is the ISSUE 5
    # acceptance bar.
    seq = next((r for r in rows if r.get("config") == "serve_sequential_8"
                and "error" not in r), None)
    bat = next((r for r in rows if r.get("config") == "serve_batched_8"
                and "error" not in r), None)
    if seq and bat:
        ratio = bat["tokens_per_sec"] / max(seq["tokens_per_sec"], 1e-9)
        bitwise = (seq["tokens_sha256"] == bat["tokens_sha256"])
        out = {"config": "serve_batch_win_8",
               "sequential_tokens_per_sec": seq["tokens_per_sec"],
               "batched_tokens_per_sec": bat["tokens_per_sec"],
               "ratio": round(ratio, 3), "bitwise_identical": bitwise,
               "ttft_p99_ms": bat["ttft_p99_ms"],
               "latency_p99_ms": bat["latency_p99_ms"],
               "ok": bool(bitwise and ratio >= 1.5)}
        print(json.dumps(out), flush=True)
        rows.append(out)

    if args.markdown:
        lines = ["| config | devices | global batch | sec/step | images/sec | vs baseline |",
                 "|---|---|---|---|---|---|"]
        for r in rows:
            if "error" in r:
                lines.append(f"| {r['config']} | — | — | — | — | ERROR: {r['error'][:60]} |")
                continue
            if "images_per_sec" not in r:
                detail = (f"{r['seconds']} s total | — | converged={r['converged']}"
                          if "seconds" in r else
                          ", ".join(f"{k}={v}" for k, v in r.items()
                                    if k != "config") + " | — | — ")
                lines.append(f"| {r['config']} | — | {r.get('steps','—')} steps "
                             f"| {detail} |")
                continue
            vs = f"{r['vs_baseline']}x" if r["vs_baseline"] else "n/a"
            lines.append(f"| {r['config']} | {r['devices']} | {r['global_batch']} "
                         f"| {r['sec_per_step']} | {r['images_per_sec']} | {vs} |")
        with open(args.markdown, "w") as f:
            f.write("\n".join(lines) + "\n")
    return 1 if any("error" in r for r in rows) else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
