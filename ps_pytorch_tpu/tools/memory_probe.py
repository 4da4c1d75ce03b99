#!/usr/bin/env python
"""Measure peak HBM per feature mode — the memory story, quantified.

VERDICT r3 item 7: ZeRO-1 (``--shard-update``), remat (``--remat``) and
pipeline microbatching exist to BUY memory; their throughput costs are in
PERF.md §2 but the payoff (HBM bytes) was never measured. This probe runs
each mode in its own CHILD process (``memory_stats()['peak_bytes_in_use']``
is a process-lifetime high-water mark — in-process sequential measurement
would only ever report the max so far) and writes one JSON artifact.

Modes
  lm_base / lm_remat          TransformerLM b=8 S=2048, d_model 512, 8 layers:
                              per-block remat drops every block's
                              intermediates (incl. the [B,H,S,S] attention
                              matrix) from the backward's saved set.
  lm_pp_m1 / lm_pp_m8         GPipe schedule on a 1-stage mesh: microbatch
                              count M slices the activation working set ~M×
                              (the single-chip-measurable half of PP's
                              memory claim; the per-stage parameter split
                              needs >1 chip).
  cnn_base / cnn_remat /      ResNet-18 b=1024 (headline geometry); zero1
  cnn_zero1                   on 1 device is the documented degenerate case
                              (no cross-replica shard to exploit) — the row
                              exists so the artifact states that, with a
                              number, instead of PERF.md asserting it.

Reference counterpart: the reference never measured memory (its models fit
trivially); this is a beyond-parity artifact required by the long-context
surface (SURVEY §5.7).

    python -m ps_pytorch_tpu.tools.memory_probe --out MEMORY_r04.json
"""

import argparse
import json
import os
import subprocess
import sys
import time

MODES = ("lm_base", "lm_remat", "lm_flash", "lm_pp_m1", "lm_pp_m8",
         "cnn_base", "cnn_remat", "cnn_zero1")

def _lm_step(mode, *, batch=8, seq_len=2048, d_model=512, n_layers=8,
             n_heads=8, vocab=32000):
    import jax
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel.mesh import make_mesh

    cfg = TrainConfig(dataset="synthetic", network="LeNet",
                      batch_size=batch, lr=0.01, momentum=0.9)
    tx = build_optimizer(cfg)
    if mode.startswith("lm_pp"):
        from ps_pytorch_tpu.parallel.pp import (
            create_pp_train_state, make_pp_train_step,
        )
        mesh = make_mesh(data=1, model=len(jax.devices()))
        n_stages = mesh.shape["model"]
        model = TransformerLM(vocab_size=vocab, d_model=d_model,
                              n_layers=n_layers, n_heads=n_heads,
                              max_seq_len=seq_len, attention_impl="full")
        state = create_pp_train_state(model, tx, mesh, n_stages,
                                      (batch, seq_len))
        m = int(mode.rsplit("_m", 1)[1])
        step = make_pp_train_step(model, tx, mesh, state, num_microbatches=m)
    else:
        from ps_pytorch_tpu.parallel.sp import (
            create_lm_train_state, make_sp_train_step,
        )
        # lm_flash: fused blockwise attention (ops/flash_attention.py) — its
        # backward saves one LSE row per query instead of the [B,H,S,S]
        # probability tensor the "full" path's backward keeps per block.
        # Flash is sequence-local, so this mode pins to ONE device (on the
        # single-chip evidence host every lm_* mode is 1-device anyway).
        if mode.endswith("flash"):
            mesh = make_mesh(data=1)
            impl = "flash"
        else:
            mesh = make_mesh(data=len(jax.devices()))
            impl = "ring" if len(jax.devices()) > 1 else "full"
        model = TransformerLM(vocab_size=vocab, d_model=d_model,
                              n_layers=n_layers, n_heads=n_heads,
                              max_seq_len=seq_len, attention_impl=impl,
                              axis_name="data")
        state = create_lm_train_state(model, tx, mesh, (batch, seq_len))
        step = make_sp_train_step(model, tx, mesh,
                                  remat=mode.endswith("remat"))
    import numpy as np
    import jax.numpy as jnp
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, vocab, size=(batch, seq_len)), jnp.int32)
    return state, lambda st, i: step(st, tokens)


def _cnn_step(mode, *, network="ResNet18", dataset="Cifar10",
              per_device_batch=1024):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.data.datasets import DATASET_SHAPES
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel import (
        create_train_state, make_mesh, make_train_step,
    )
    from ps_pytorch_tpu.parallel.zero import (
        create_zero_train_state, make_zero_train_step,
    )

    remat = mode.endswith("remat")
    batch = per_device_batch * len(jax.devices())
    cfg = TrainConfig(dataset=dataset, network=network, batch_size=batch,
                      lr=0.1, momentum=0.9, weight_decay=1e-4, remat=remat,
                      shard_update=mode.endswith("zero1"))
    mesh = make_mesh(data=len(jax.devices()))
    model = build_model(cfg.network, cfg.num_classes, cfg.compute_dtype)
    tx = build_optimizer(cfg)
    h, w, c, ncls, _ = DATASET_SHAPES[dataset]
    if cfg.shard_update:
        state = create_zero_train_state(model, tx, mesh, (1, h, w, c),
                                        jax.random.key(0))
        step = make_zero_train_step(model, tx, mesh, state, remat=remat,
                                    donate=True)
    else:
        state = create_train_state(model, tx, mesh, (1, h, w, c),
                                   jax.random.key(0))
        step = make_train_step(model, tx, mesh, state, remat=remat,
                               donate=True)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, h, w, c)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, ncls, batch).astype(np.int32))
    mask = jnp.ones(mesh.shape["data"], jnp.float32)
    return state, lambda st, i: step(st, x, y, mask, jax.random.key(i))


def child_main(mode: str) -> int:
    import jax

    dev = jax.local_devices()[0]
    t0 = time.perf_counter()
    state, tick = (_lm_step if mode.startswith("lm") else _cnn_step)(mode)
    for i in range(3):
        state, metrics = tick(state, i)
    jax.block_until_ready(state.params)
    stats = dev.memory_stats() or {}
    out = {
        "mode": mode, "platform": dev.platform,
        "device_kind": dev.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use": stats.get("bytes_in_use"),
        "largest_alloc": stats.get("largest_alloc_size"),
        "loss": round(float(metrics["loss"]), 4),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    if out["peak_bytes_in_use"] is None:
        out["note"] = "backend reports no memory_stats (CPU)"
    print(json.dumps(out))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", default="", help="internal: run one mode")
    p.add_argument("--modes", default=",".join(MODES))
    p.add_argument("--out", default="MEMORY_r04.json")
    p.add_argument("--timeout", type=float, default=600.0)
    args = p.parse_args(argv)
    if args.child:
        return child_main(args.child)

    # Validate the WHOLE list before spawning anything: each child costs
    # minutes, and a typo in mode 5 must not surface only after four
    # children ran. (Empty entries would fall through --child to the
    # parent branch in the child and recursively run the whole suite; a
    # typo would dispatch on prefix/suffix and silently measure the BASE
    # config under the wrong label — r4 review.)
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    bad = [m for m in modes if m not in MODES]
    if bad:
        p.error(f"unknown mode(s) {bad}; valid: {', '.join(MODES)}")

    rows = []
    for mode in modes:
        cmd = [sys.executable, "-m", "ps_pytorch_tpu.tools.memory_probe",
               "--child", mode]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=args.timeout)
            line = (proc.stdout or "").strip().splitlines()
            row = (json.loads(line[-1]) if proc.returncode == 0 and line
                   else {"mode": mode, "error":
                         (proc.stderr or "no output").strip()[-300:]})
        except subprocess.TimeoutExpired:
            row = {"mode": mode, "error": f"timeout {args.timeout:.0f}s"}
        print(json.dumps(row), flush=True)
        rows.append(row)
        # Rewrite the artifact after EVERY row: the worst-case child budget
        # exceeds the batch scripts' outer timeout, and a SIGKILL at row 6/7
        # must still leave a quotable artifact (r4 review finding).
        _write_doc(args.out, rows)

    _write_doc(args.out, rows, final=True)
    return 0


def _write_doc(out: str, rows, final: bool = False) -> None:
    # Derived deltas the PERF table quotes directly.
    by = {r["mode"]: r for r in rows}

    def peak(m):
        v = by.get(m, {}).get("peak_bytes_in_use")
        return v if isinstance(v, int) and v > 0 else None

    deltas = {}
    for a, b, key in (("lm_base", "lm_remat", "lm_remat_saves_bytes"),
                      ("lm_pp_m1", "lm_pp_m8", "pp_m8_saves_bytes"),
                      ("cnn_base", "cnn_remat", "cnn_remat_saves_bytes"),
                      ("cnn_base", "cnn_zero1", "cnn_zero1_saves_bytes")):
        if peak(a) and peak(b):
            deltas[key] = peak(a) - peak(b)
    doc = {"rows": rows, "deltas": deltas, "complete": final}
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, out)
    if final:
        print(json.dumps({"wrote": out, "deltas": deltas}))


if __name__ == "__main__":
    sys.exit(main())
