#!/usr/bin/env python
"""Real-dataset time-to-accuracy harness.

Drives the REAL contract end to end — ``train.py`` writes checkpoints,
``evaluate.py --once`` scores the final ``model_step_<k>`` — and records
steps, wall-clock, and Prec@1/Prec@5 into a JSON artifact. This is the
framework's analogue of the reference's accuracy oracle (the standalone
evaluator scoring worker checkpoints, ``distributed_evaluator.py:90-106``).

Default task: LeNet on ``Digits`` — scikit-learn's bundled copy of the UCI
handwritten-digit scans (real data, available with zero network egress) at
MNIST geometry. With network access, ``--dataset MNIST`` runs the classic
oracle instead (tools/data_prepare.py fetches the IDX files first).

    python -m ps_pytorch_tpu.tools.accuracy_run --out ACCURACY.json
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time


def _platform():
    """(platform, device_kind), read only AFTER the train/evaluate children
    have exited: a chip belongs to one process at a time, so the harness
    must not open a backend while a child still needs it."""
    import jax
    d = jax.devices()[0]
    return d.platform, d.device_kind


def _write_source_corpus(repo: str, path: str) -> int:
    """REAL byte corpus with zero egress: the framework's own source tree
    (human-written Python), concatenated. ~hundreds of KB — far past the
    LM's batch/seq/held-out geometry needs."""
    parts = []
    for top in ("ps_pytorch_tpu", "tests"):
        for root, _, files in sorted(os.walk(os.path.join(repo, top))):
            for f in sorted(files):
                if f.endswith(".py"):
                    with open(os.path.join(root, f), "rb") as fh:
                        parts.append(fh.read())
    data = b"\n".join(parts)
    with open(path, "wb") as f:
        f.write(data)
    return len(data)


# Matches finite AND nan/inf floats: a diverged run prints "loss nan" and
# must be reported as divergence, not as "evaluate.py failed".
_FLOAT = r"([\d.eE+-]+|nan|inf)"


def _run_child(label: str, cmd, repo: str, timeout_s: float):
    r = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=timeout_s, cwd=repo)
    if r.returncode != 0:
        raise RuntimeError(f"{label} failed rc={r.returncode}: "
                           f"{(r.stderr or r.stdout)[-400:]}")
    return r


def _emit(result: dict, args, repo: str) -> dict:
    print(json.dumps(result))
    if args.out:
        with open(os.path.join(repo, args.out) if not os.path.isabs(args.out)
                  else args.out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def run_lm(args, repo: str) -> dict:
    """LM real-data oracle: train_lm.py on a byte-level real corpus ->
    checkpoint -> evaluate.py --once scores it (EVAL_LM line)."""
    # Resolve harness-side paths against repo: the children run cwd=repo,
    # so a relative --train-dir must mean the same directory to both.
    train_dir = args.train_dir if os.path.isabs(args.train_dir) \
        else os.path.join(repo, args.train_dir)
    os.makedirs(train_dir, exist_ok=True)
    corpus = os.path.join(train_dir, "corpus.bin")
    corpus_bytes = _write_source_corpus(repo, corpus)
    train_cmd = [
        sys.executable, os.path.join(repo, "train_lm.py"),
        "--lm-corpus-file", corpus, "--lm-seq-len", "256",
        "--lm-d-model", "128", "--lm-layers", "2", "--lm-heads", "4",
        "--batch-size", "16", "--momentum", "0.9",
        # lr 0.1 + warmup + cosine: real source bytes are a harder stream
        # than the synthetic Markov corpus — the synthetic recipe's lr 0.3
        # diverged here (loss -> 1e15, observed).
        "--lr", "0.1", "--lr-schedule", "cosine", "--lr-warmup-steps", "50",
        "--max-steps", str(args.max_steps),
        "--eval-freq", str(args.max_steps),    # one final checkpoint
        "--log-every", "100", "--train-dir", train_dir,
    ]
    t0 = time.perf_counter()
    _run_child("train_lm.py", train_cmd, repo, args.timeout_s)
    train_s = time.perf_counter() - t0
    ev = _run_child(
        "evaluate.py",
        [sys.executable, os.path.join(repo, "evaluate.py"),
         "--train-dir", train_dir, "--once", str(args.max_steps)],
        repo, args.timeout_s)
    m = re.search(rf"EVAL_LM step (\d+) loss {_FLOAT} perplexity {_FLOAT}",
                  ev.stdout)
    if m is None:
        raise RuntimeError(f"no EVAL_LM line in evaluate.py output: "
                           f"{ev.stdout[-400:]}")
    ppl = float(m.group(3))
    platform, kind = _platform()
    return _emit({
        "metric": "lm_time_to_perplexity",
        "dataset": f"framework source bytes ({corpus_bytes} B)",
        "network": "TransformerLM", "data": "real",
        "steps": int(m.group(1)), "train_wall_s": round(train_s, 1),
        "eval_loss": float(m.group(2)), "perplexity": ppl,
        "target_perplexity": args.target_ppl,
        "met_target": ppl <= args.target_ppl,
        "platform": platform, "device_kind": kind,
        "contract": "train_lm.py checkpoint -> evaluate.py --once (EVAL_LM)",
    }, args, repo)


def run(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dataset", default="Digits")
    p.add_argument("--network", default="LeNet")
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--max-steps", type=int, default=1200)
    p.add_argument("--target-prec1", type=float, default=0.98)
    p.add_argument("--lm", action="store_true",
                   help="LM oracle on a real byte corpus (the source tree) "
                        "instead of the CNN/Digits oracle")
    p.add_argument("--target-ppl", type=float, default=16.0)
    p.add_argument("--train-dir", default="./train_dir_accuracy")
    p.add_argument("--out", default="")
    p.add_argument("--timeout-s", type=float, default=1200.0)
    args = p.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if args.lm:
        return run_lm(args, repo)
    train_cmd = [
        sys.executable, os.path.join(repo, "train.py"),
        "--dataset", args.dataset, "--network", args.network,
        "--batch-size", str(args.batch_size), "--lr", str(args.lr),
        "--momentum", "0.9", "--weight-decay", "1e-4",
        "--compute-dtype", "float32", "--epochs", "0",
        "--max-steps", str(args.max_steps),
        "--eval-freq", str(args.max_steps),     # one final checkpoint
        "--log-every", "200", "--train-dir", args.train_dir,
    ]
    t0 = time.perf_counter()
    _run_child("train.py", train_cmd, repo, args.timeout_s)
    train_s = time.perf_counter() - t0

    ev = _run_child(
        "evaluate.py",
        [sys.executable, os.path.join(repo, "evaluate.py"),
         "--train-dir", args.train_dir, "--once", str(args.max_steps)],
        repo, args.timeout_s)
    m = re.search(rf"EVAL step (\d+) loss {_FLOAT} prec1 {_FLOAT} "
                  rf"prec5 {_FLOAT}", ev.stdout)
    if m is None:
        raise RuntimeError(f"no EVAL line in evaluate.py output: "
                           f"{ev.stdout[-400:]}")
    prec1, prec5 = float(m.group(3)), float(m.group(4))

    platform, kind = _platform()
    return _emit({
        "metric": "time_to_accuracy",
        "dataset": args.dataset, "network": args.network,
        "data": "real",
        "steps": int(m.group(1)), "train_wall_s": round(train_s, 1),
        "eval_loss": float(m.group(2)),
        "prec1": prec1, "prec5": prec5,
        "target_prec1": args.target_prec1,
        "met_target": prec1 >= args.target_prec1,
        "platform": platform,
        "device_kind": kind,
        "contract": "train.py checkpoint -> evaluate.py --once",
    }, args, repo)


if __name__ == "__main__":
    r = run()
    sys.exit(0 if r["met_target"] else 1)
