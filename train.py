#!/usr/bin/env python
"""Training entry point — the TPU-native replacement for the reference's
``distributed_nn.py`` + ``run_pytorch.sh`` (and, with a 1-device mesh,
``single_machine.py``: in SPMD the single-machine baseline is just the
degenerate mesh, no separate code path).

No mpirun: on a TPU pod slice, launch this same script on every host
(``python -m ps_pytorch_tpu.tools.launch`` or your pod runner); JAX's
distributed runtime wires the hosts together, the mesh spans all chips, and
each host feeds its own data shard.

Example:
    python train.py --network LeNet --dataset MNIST --batch-size 512 \
        --lr 0.01 --momentum 0.9 --max-steps 1000 --eval-freq 100
"""

import sys


def main(argv=None) -> int:
    from ps_pytorch_tpu.config import config_from_args
    from ps_pytorch_tpu.parallel import dist
    from ps_pytorch_tpu.runtime import Trainer

    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Multi-host bootstrap (no mpirun): tools/launch.py exports the env
    # contract; single-process runs skip this.
    if dist.initialize_from_env():
        import jax
        print(f"DIST process {jax.process_index()}/{jax.process_count()} "
              f"local_devices={jax.local_device_count()}")
    cfg = config_from_args(argv)
    print(f"CONFIG {cfg.to_json()}")
    if cfg.mode == "async":
        import jax
        if jax.process_count() > 1:
            # One slice per process: gradients cross the process/DCN
            # boundary codec-compressed over the coordination-service KV
            # (runtime/async_trainer.py) — the reference's cross-machine
            # async path (resnet_split.py:25-42 staleness tags).
            from ps_pytorch_tpu.runtime.async_trainer import AsyncTrainer
            trainer = AsyncTrainer(cfg)
            print(f"ASYNC process-slices {trainer.n} x "
                  f"{len(trainer.mesh.devices.flat)} devices")
        else:
            # Single process: device groups act as independent slices
            # feeding the aggregator in-process (runtime/multislice.py).
            from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer
            trainer = MultiSliceTrainer(cfg, n_slices=cfg.async_slices,
                                        fetch_every=cfg.fetch_every)
            print(f"SLICES {cfg.async_slices} x "
                  f"{len(trainer.meshes[0].devices.flat)} devices")
    elif cfg.auto_resume > 0:
        # Crash containment: a failed step loop restarts the trainer, which
        # restores from the latest VALID checkpoint (runtime/checkpoint.py
        # manifest verification) and fast-forwards the data stream. One
        # FaultInjector is threaded across restarts so injected once-only
        # faults (chaos drills) do not re-fire after resume.
        from ps_pytorch_tpu import resilience
        import jax
        injector = None
        if cfg.fault_spec:
            injector = resilience.FaultInjector(
                cfg.fault_spec, process_index=jax.process_index())
        resume_cfg = cfg if cfg.resume else cfg.replace(resume=1)
        built = []

        def make_trainer():
            # First build honours the user's --resume; rebuilds always
            # resume (that is the whole point of the restart).
            t = Trainer(resume_cfg if built else cfg, injector=injector)
            if not built:
                print(f"MESH data={t.mesh.shape['data']} "
                      f"model={t.mesh.shape['model']} "
                      f"devices={len(t.mesh.devices.flat)}")
            built.append(t)
            return t

        resilience.run_with_auto_resume(
            make_trainer, max_restarts=cfg.auto_resume,
            exceptions=(Exception,))
        trainer = built[-1]
        result = trainer.evaluate()
        print(f"FINAL loss {result['loss']:.6f} prec1 {result['prec1']:.4f} "
              f"prec5 {result['prec5']:.4f}")
        return 0
    else:
        trainer = Trainer(cfg)
        print(f"MESH data={trainer.mesh.shape['data']} model={trainer.mesh.shape['model']} "
              f"devices={len(trainer.mesh.devices.flat)}")
    trainer.train()
    result = trainer.evaluate()
    print(f"FINAL loss {result['loss']:.6f} prec1 {result['prec1']:.4f} "
          f"prec5 {result['prec5']:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
