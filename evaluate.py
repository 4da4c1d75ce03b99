#!/usr/bin/env python
"""Standalone polling evaluator — replacement for the reference's
``distributed_evaluator.py`` + ``evaluate_pytorch.sh``: watches a checkpoint
directory and reports loss / Prec@1 / Prec@5 for each new ``model_step_<k>``.

    python evaluate.py --train-dir ./train_dir [--poll-s 10] [--once STEP]
"""

import argparse
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--train-dir", default="./train_dir")
    p.add_argument("--poll-s", type=float, default=10.0)
    # None-defaults so step/timeout 0 stay expressible (a falsy check would
    # make `--once 0` / `--stop-after 0` silently mean "disabled").
    p.add_argument("--once", type=int, default=None,
                   help="evaluate exactly this step then exit")
    p.add_argument("--stop-after", type=int, default=None,
                   help="exit once this step has been evaluated")
    p.add_argument("--idle-timeout-s", type=float, default=None,
                   help="exit after this long with no new checkpoints")
    args = p.parse_args(argv)

    from ps_pytorch_tpu.parallel import dist
    dist.initialize_from_env()  # multi-host env contract
    from ps_pytorch_tpu.runtime import Evaluator
    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    ev = Evaluator(args.train_dir, poll_s=args.poll_s)
    if args.once is not None:
        ev.evaluate_step(args.once)
        return 0
    ev.run(stop_after=args.stop_after,
           idle_timeout_s=args.idle_timeout_s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
