#!/usr/bin/env python
"""Serve a ``train_lm.py`` checkpoint over HTTP with continuous batching.

The serving counterpart of ``generate.py``: instead of one ad-hoc decode,
stand up the full request lifecycle — bounded admission queue, slot-based
continuously-batched decode (``ps_pytorch_tpu/serving/``), and hot reload
of newer VALID checkpoints while requests stream (corrupt newest ones are
walked past, same contract as training resume).

    python train_lm.py --lm-corpus-file corpus.txt --train-dir ./lm ...
    python serve.py --train-dir ./lm --serve-port 8300 --serve-slots 8
    curl -s localhost:8300/v1/generate -d '{"prompt": "def train(", "n_new": 64}'

Model geometry comes from the checkpoint's own config; the ``--serve-*``
flags (config.py) size the engine. Byte-level LM: "prompt" is UTF-8 text;
send "tokens" (int list) for non-byte vocabularies.

Fleet mode: pass ``--serve-kv-dir <dir> --serve-replica-id <i>`` and the
replica registers itself in the directory-backed coordination KV
(``serve/<fleet>/replica/<i>``) and beats a liveness lease from the serve
loop; ``tools/router.py``-less fleets just point the Router's FleetView at
the same dir. SIGTERM triggers graceful drain (stop admitting, finish
in-flight, deregister, exit) — the zero-downtime half of a rolling
restart. ``--fault-spec "replica_kill:served=20,r=<i>"`` arms the
drill's SIGKILL. With ``--kv-replicas <spec,...>`` the registry rides the
quorum-replicated coordination plane (``runtime/kvrep.py``) instead of a
single directory, so losing a minority of KV backends never blinds the
router.
"""

import argparse
import dataclasses
import json
import signal
import sys
import time


def main(argv=None) -> int:
    # The --serve-* surface lives in config.py with everything else (one
    # dataclass, config-time validation); serve.py just consumes it.
    from ps_pytorch_tpu.config import TrainConfig, add_train_args

    p = add_train_args(argparse.ArgumentParser(description=__doc__))
    ns = p.parse_args(argv)
    try:
        args = TrainConfig(**{f.name: getattr(ns, f.name)
                              for f in dataclasses.fields(TrainConfig)})
    except ValueError as e:
        p.error(str(e))

    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from ps_pytorch_tpu.models.transformer import refuse_hybrid
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.lm_eval import (
        build_lm_oracle, build_lm_template, lm_geometry,
    )
    from ps_pytorch_tpu.serving.engine import ServingEngine
    from ps_pytorch_tpu.serving.reload import CheckpointWatcher
    from ps_pytorch_tpu.serving.reqtrace import RequestTraceLog
    from ps_pytorch_tpu.serving.server import ServingFrontend
    from ps_pytorch_tpu.telemetry.health import HealthMonitor
    from ps_pytorch_tpu.telemetry.registry import (
        Registry, declare_serving_metrics,
    )
    from ps_pytorch_tpu.telemetry.slo import SLOTracker

    step = ckpt.latest_valid_step(args.train_dir)
    if step is None:
        p.error(f"no valid model_step_<k> checkpoints in {args.train_dir}")
    with open(f"{ckpt.checkpoint_path(args.train_dir, step)}/config.json") as f:
        cfg = TrainConfig.from_json(f.read())
    try:
        refuse_hybrid(cfg.lm_arch, "serve.py")
    except ValueError as e:
        p.error(str(e))
    if cfg.network != "TransformerLM":
        # The engine's slot decode reuses Block.decode's fixed-length KV
        # cache, which the MoE blocks don't implement.
        p.error(f"serve.py decodes TransformerLM checkpoints; this one is "
                f"{cfg.network}, lm_arch={cfg.lm_arch} (generate.py decodes "
                f"a gpt2-arch MoE checkpoint one-shot; decoding the olmoe "
                f"and smallthinker archs is not built)")
    if cfg.lm_arch != "gpt2" or cfg.lm_kv_heads not in (0, cfg.lm_heads) \
            or cfg.lm_head_dim:
        p.error(f"serve.py decodes lm_arch=gpt2 checkpoints with equal head "
                f"counts of d / heads; this one is lm_arch={cfg.lm_arch}, "
                f"lm_kv_heads={cfg.lm_kv_heads}, lm_head_dim="
                f"{cfg.lm_head_dim} (a cache for RoPE, window layers or "
                f"grouped-query heads is not built)")
    template = build_lm_template(cfg)
    _, to_tree = build_lm_oracle(cfg)
    got = ckpt.load_latest_valid(args.train_dir, template)
    if got is None:
        p.error(f"no restorable checkpoint in {args.train_dir}")
    state, meta, _, step = got

    geo = lm_geometry(cfg)
    registry = Registry()
    declare_serving_metrics(registry)
    # Request-scoped observability plane: lifecycle trace ring
    # (/debug/requests) and SLO burn-rate tracker (/slo), both optional.
    reqtrace = (RequestTraceLog(args.reqtrace_keep,
                                sample=args.reqtrace_sample)
                if args.reqtrace_keep > 0 else None)
    slo = (SLOTracker(args.slo_spec, registry=registry)
           if args.slo_spec else None)
    engine = ServingEngine(
        to_tree(state.params), slots=args.serve_slots,
        vocab=geo["vocab_size"], d_model=geo["d_model"],
        n_layers=geo["n_layers"], n_heads=geo["n_heads"],
        max_seq_len=geo["max_seq_len"], model_step=step, registry=registry,
        reqtrace=reqtrace, slo=slo)
    # Always build the watcher: the periodic poll is gated by
    # --serve-reload-s, but POST /admin/reload (the rolling-reload driver)
    # force-polls regardless.
    watcher = CheckpointWatcher(args.train_dir, template, to_tree=to_tree,
                                start_step=step)
    # Watchdog over the serve loop: the stall detector notices a wedged
    # drive thread (health.beat() runs once per loop iteration) and the
    # state shows up under /healthz's "health" key.
    health = HealthMonitor(args.health_spec or "stall:warn",
                           registry=registry)
    # Identity fields for /healthz: elastic training runs stamp which
    # leadership epoch committed each checkpoint (extra_meta); the serving
    # process itself is a single-process "leader" of its own plane.
    identity = {"leader": True, "role": "serving"}
    for k in ("leader_epoch", "leader_pid"):
        if k in meta:
            identity[k] = meta[k]
    # Fleet plane: registrar (KV record + liveness lease, beaten by the
    # serve loop) and the replica_kill fault injector for the drill.
    registrar = None
    if args.kv_replicas:
        # Quorum-replicated fleet registry: the replica record + liveness
        # lease survive loss of a minority of KV backends, so the router
        # never loses its fleet view to a single dead store.
        from ps_pytorch_tpu.runtime.kvrep import build_replicated_kv
        from ps_pytorch_tpu.serving.router import FleetRegistrar
        fleet_kv = build_replicated_kv(
            args, process_index=args.serve_replica_id)
        registrar = FleetRegistrar(fleet_kv, args.serve_fleet,
                                   args.serve_replica_id)
        identity["replica_id"] = args.serve_replica_id
    elif args.serve_kv_dir:
        from ps_pytorch_tpu.runtime.coordinator import FileKV
        from ps_pytorch_tpu.serving.router import FleetRegistrar
        registrar = FleetRegistrar(FileKV(args.serve_kv_dir),
                                   args.serve_fleet, args.serve_replica_id)
        identity["replica_id"] = args.serve_replica_id
    injector = None
    if args.fault_spec:
        from ps_pytorch_tpu.resilience.faults import FaultInjector
        injector = FaultInjector(args.fault_spec,
                                 process_index=args.serve_replica_id)
    frontend = ServingFrontend(
        engine, watcher=watcher, host=args.serve_host, port=args.serve_port,
        max_queue=args.serve_max_queue, reload_s=args.serve_reload_s,
        default_deadline_s=args.serve_deadline_s,
        default_n_new=args.serve_max_new, health=health, identity=identity,
        max_body_bytes=args.serve_max_body_bytes, registrar=registrar,
        injector=injector, advertise=args.serve_advertise)
    frontend.start()
    print(json.dumps({"serving": f"http://{args.serve_host}:{frontend.port}",
                      "metrics": f"http://{args.serve_host}:{frontend.port}"
                                 "/metrics",
                      "model_step": step, "slots": args.serve_slots,
                      "vocab": geo["vocab_size"],
                      "seq_len": geo["max_seq_len"],
                      "replica_id": (args.serve_replica_id
                                     if registrar else None),
                      "slo_spec": args.slo_spec or None,
                      "reqtrace_keep": args.reqtrace_keep}))
    sys.stdout.flush()

    # SIGTERM = graceful drain: stop admitting, finish in-flight slots,
    # deregister from the fleet, exit 0 — a rolling restart never turns
    # into client-visible errors.
    draining = {"flag": False}

    def _drain(signum, frame):
        draining["flag"] = True

    signal.signal(signal.SIGTERM, _drain)
    try:
        while not draining["flag"]:
            time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    finally:
        frontend.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
