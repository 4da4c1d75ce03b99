#!/usr/bin/env python
"""LM training entry point — long context through the standard contract.

Same config/checkpoint/metrics machinery as ``train.py``, driving the
transformer LM under the selected parallelism: sequence-parallel ring
attention (default), tensor parallelism, GPipe pipeline, or MoE expert
parallelism.

    python train_lm.py --lm-seq-len 4096 --batch-size 8 --lr 0.3 \
        --momentum 0.9 --max-steps 200 --eval-freq 100
    python train_lm.py --lm-parallelism tp --lm-model-axis 4 ...
    python train_lm.py --lm-parallelism pp --lm-layers 8 --lm-microbatches 8 ...
    python train_lm.py --lm-parallelism ep --lm-experts 16 ...
"""

import sys


def main(argv=None) -> int:
    from ps_pytorch_tpu.config import config_from_args
    from ps_pytorch_tpu.parallel import dist
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if dist.initialize_from_env():
        import jax
        print(f"DIST process {jax.process_index()}/{jax.process_count()}")
    cfg = config_from_args(argv)
    print(f"CONFIG {cfg.to_json()}")
    trainer = LMTrainer(cfg)
    print(f"LM mesh devices={len(trainer.mesh.devices.flat)} "
          f"parallelism={cfg.lm_parallelism} "
          f"attention={getattr(trainer.model, 'attention_impl', 'full')} "
          f"seq_len={cfg.lm_seq_len}")
    trainer.train()
    result = trainer.evaluate(max_batches=8)
    print(f"FINAL lm_loss {result['loss']:.6f} "
          f"perplexity {result['perplexity']:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
