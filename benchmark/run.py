#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``, on the accelerator this machine
holds:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and, when
traced, ``breakdown``. There is no CPU mode: without a TPU, with another
number of chips than the cell asks for, or without the program beside it, the
command exits non-zero and prints no result. ``harness.py`` says how a run
measures.
"""

import time

T_ORIGIN = time.monotonic()     # set-up counts from process start

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(CHECKOUT, "ps_pytorch_tpu")):
        print(f"the program (ps_pytorch_tpu/) is not in {CHECKOUT}: nothing "
              f"to measure", file=sys.stderr)
        return 3
    for p in (CHECKOUT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.chdir(CHECKOUT)      # the program's relative defaults (./data) stay inside

    import harness
    bench = harness.load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    cell = harness.find_cell(bench, args.workload)

    import jax
    platform = jax.default_backend()
    n = len(jax.devices())
    if platform != "tpu" or n != cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} TPU chip(s); jax "
              f"found platform {platform!r} with {n} device(s)",
              file=sys.stderr)
        return 3

    result = harness.run_cell(bench, args.workload, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_origin=T_ORIGIN)
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
