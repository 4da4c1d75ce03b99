#!/usr/bin/env python
"""Poisoned-contributor chaos drill for gradient integrity ->
RESILIENCE_r16.json.

The acceptance drill for the end-to-end gradient-integrity plane
(ps_pytorch_tpu/resilience/integrity.py). Four phases:

- **clean** (multi-process): 4 processes train flat async over the real
  jax.distributed coordination KV (int8lat homomorphic wire + EF,
  ``--grad-integrity`` on), NO faults — the convergence baseline.
- **poison** (multi-process): the same run with process 2 poisoned
  (``grad_poison:scale=1e38`` over a window of its own steps — the
  corruption rides the REAL wire) and the leader's grad reads bit-flipped
  at low probability (``payload_bitflip`` — in-alphabet flips the armour
  decodes fine, so only the layer-1 digests can catch them). The leader
  must strike and QUARANTINE contributor 2 (``INTEGRITY quarantine
  contributor 2``), keep converging on the 3 clean contributors, READMIT
  it on probation after the window closes (``INTEGRITY readmit``), and
  finish with a final loss matching the clean baseline. Zero crashes:
  every digest failure or screen reject demotes to "absent this round".
- **control** (multi-process): the same poisoned run with
  ``--grad-integrity`` OFF — the 1e30-scaled payloads enter the
  homomorphic sum and the run diverges (non-finite / exploded loss),
  which is the evidence that the screen is load-bearing, not decorative.
- **bitwise** (in-process, deterministic): a 4-contributor
  StaleGradientAggregator arc where contributor 3 submits MAD-outlier
  payloads for a window, is quarantined, then readmitted — and a
  ledger-free control aggregator fed EXACTLY the admitted sets reaches a
  BITWISE-equal parameter vector (screening out a contributor is
  indistinguishable from that contributor never submitting).

The drill is judged by its own exit code: :func:`verdict` holds the whole
pass rule over the result it writes (counts and flags, no clock).

Usage:
    python ps_pytorch_tpu/tools/poison_drill.py --out RESILIENCE_r16.json
"""

import argparse
import json
import os
import pathlib
import re
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent.parent
if str(REPO) not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, str(REPO))

from ps_pytorch_tpu.tools import launch  # noqa: E402
from ps_pytorch_tpu.tools.launch import (  # noqa: E402
    free_port as _free_port, proc_logs as _logs,
)


# ---------------------------------------------------------------- workers

def _worker(args) -> None:
    """One training process. The fault spec is armed on EVERY process —
    ``grad_poison:r=2`` self-scopes to process 2's own gradient encodes,
    ``payload_bitflip`` self-scopes to grad-channel chunk READS (only the
    leader reads those keys). Retry attempts are kept low so a corrupted
    read demotes fast instead of stalling the poll loop.

    EF is OFF in the main poison leg on purpose: sender-side error
    feedback on a poisoned contributor re-emits the poison as a residual
    that decays ~128x per step — several steps of validator-legal
    (|e| <= 64) but still-huge payloads AFTER the window closes, i.e. a
    contributor that keeps poisoning. The readmission arc needs the
    offender to actually go clean when its window ends.

    The ``--ef`` leg re-enables EF WITH the --ef-clip residual clamp
    (compression/codecs.py): the absorbed poison is capped at a
    ~clip-sized perturbation per leaf, so the offender still draws a
    quarantine during its window but cannot keep smuggling huge
    validator-legal payloads after it — the PR 13 documented gap
    (PERF.md §17), closed and drilled."""
    from ps_pytorch_tpu.parallel import dist
    dist.initialize_from_env()
    import jax
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.runtime.async_trainer import AsyncTrainer

    cfg = TrainConfig(
        dataset="synthetic_mnist", network="LeNet", batch_size=64,
        lr=0.05, momentum=0.9, compute_dtype="float32", mode="async",
        max_steps=args.max_steps, eval_freq=0, train_dir=args.train_dir,
        resume=False, log_every=4, seed=42,
        compress_grad=True, grad_codec="int8lat", ef=args.ef,
        ef_clip=args.ef_clip if args.ef else 0.0,
        staleness_limit=4, kv_retry_attempts=2,
        grad_integrity=not args.no_integrity,
        fault_spec=args.fault_spec)
    t = AsyncTrainer(cfg)
    t.train()
    stats = {}
    if t.injector is not None:
        stats.update(t.injector.snapshot())
    if t._retrier is not None:
        stats.update(t._retrier.snapshot())
    stats.update(t.transport.wire_stats())
    if t._integrity is not None or t._group_integrity is not None:
        stats.update(t._integrity_snapshot())
    print(f"DRILLSTATS pid {jax.process_index()} {json.dumps(stats)}",
          flush=True)
    r = t.evaluate(max_batches=2)
    print(f"FINAL loss {r['loss']:.4f} prec1 {r['prec1']:.4f} "
          f"version {t.version}", flush=True)
    # Process 0 hosts the coordination service: nobody hard-exits until
    # everyone is done with the KV (flat-key exit barrier, all 4 alive).
    kv = t.transport.kv
    run = f"async-{cfg.seed}"
    pid, n = jax.process_index(), jax.process_count()
    kv.set(f"{run}/exitbar/{pid}", "1")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            if all(kv.get(f"{run}/exitbar/{p}") is not None
                   for p in range(n)):
                break
        except Exception:
            pass
        time.sleep(0.05)
    os._exit(0)


# ----------------------------------------------------- in-process phases

def _phase_bitwise(total_steps: int = 24) -> dict:
    """Deterministic quarantine arc with a bitwise-exclusion proof:
    contributor 3 submits 1e8-scaled payloads (validators pass — the MAD
    norm gate must catch them) over a window, gets quarantined at the
    third strike, streaks clean after the window, and is readmitted on
    probation. A ledger-free control aggregator is fed EXACTLY the
    admitted set each round; both SGD recurrences must land on the same
    bits."""
    import numpy as np

    from ps_pytorch_tpu.compression.codecs import encode_leaves
    from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator
    from ps_pytorch_tpu.resilience.integrity import GradIntegrity

    n, size, lr = 4, 257, 0.05
    poison = range(4, 10)           # contributor 3's outlier window
    events = []
    gi = GradIntegrity(mad_threshold=6.0, strike_limit=3, readmit_clean=3,
                       on_event=lambda k, c, s, d: events.append((k, c, s)))

    def make_agg(integrity):
        return StaleGradientAggregator(
            n, staleness_limit=4, num_aggregate=0, compress=True,
            codec="int8lat", integrity=integrity)

    def wire(i, t, scale=1.0):
        rng = np.random.default_rng(500 + 31 * i + t)
        g = rng.standard_normal(size).astype(np.float32) * scale
        return encode_leaves("int8lat", [g], slice_id=i, step=t)

    screened, control = make_agg(gi), make_agg(None)
    rng = np.random.default_rng(0)
    p = rng.standard_normal(size).astype(np.float32)
    p_ctl = p.copy()
    rejected_rounds = 0
    for t in range(total_steps):
        for i in range(n):
            scale = 1e8 if (i == 3 and t in poison) else 1.0
            screened.submit_encoded(i, t, wire(i, t, scale))
        avg, info = screened.collect(t)
        if info.get("rejected"):
            rejected_rounds += 1
        # The control sees EXACTLY the admitted set, encoded identically.
        for i in info["used"]:
            control.submit_encoded(i, t, wire(i, t))
        avg_ctl, info_ctl = control.collect(t)
        assert info_ctl["used"] == info["used"]
        if avg is not None:
            p = (p - lr * np.asarray(avg[0], np.float32)).astype(np.float32)
        if avg_ctl is not None:
            p_ctl = (p_ctl - lr * np.asarray(avg_ctl[0], np.float32)
                     ).astype(np.float32)
        screened.consume(info["used"])
        control.consume(info_ctl["used"])
        screened.drop_older_than(t)
        control.drop_older_than(t)
    bitwise = bool(np.array_equal(p, p_ctl))
    snap = gi.snapshot()
    return {"bitwise_equal": bitwise, "total_steps": total_steps,
            "rejected_rounds": rejected_rounds, "counters": snap,
            "events": [list(e) for e in events]}


# ---------------------------------------------------------------- driver

def _launch(run_dir: pathlib.Path, port: int, worker_args) -> int:
    return launch.main([
        "launch", "--run-dir", str(run_dir), "--simulate", "4",
        "--devices-per-host", "1", "--port", str(port),
        "--entry", str(pathlib.Path(__file__).resolve()),
        "--cwd", str(REPO), "--wait", "--timeout", "420",
        "--", *worker_args,
    ])


def _final_losses(logs):
    out = {}
    for i, text in enumerate(logs):
        m = re.search(r"FINAL loss ([-\w.+]+) ", text)
        if m:
            out[i] = float(m.group(1))
    return out


def _run_leg(base, name, args, fault_spec="", no_integrity=False,
             ef=False):
    d = base / name
    import shutil
    shutil.rmtree(d, ignore_errors=True)
    worker_args = ["--phase", "worker", "--train-dir", str(d / "ckpt"),
                   "--max-steps", str(args.max_steps),
                   "--fault-spec", fault_spec]
    if no_integrity:
        worker_args.append("--no-integrity")
    if ef:
        worker_args += ["--ef", "--ef-clip", str(args.ef_clip)]
    rc = _launch(d, _free_port(), worker_args)
    return rc, _logs(d)


def _all_finished(phase: dict, n: int, below: float = float("inf")) -> bool:
    """Every one of the n processes printed a finite FINAL loss < below."""
    finals = list(phase.get("finals", {}).values())
    return (phase.get("rc", 2) != 2 and len(finals) == n
            and all(l == l and l < below for l in finals))


def verdict(result: dict) -> list:
    """The drill's whole pass rule: the invariants ``result`` violates,
    empty when it passes. The poison must have been strong enough to matter
    (the no-screen control diverged), the screen must have caught it
    (quarantine, probation readmission, a flipped chunk refused) and nobody
    may crash: every reject demotes to "absent this round"."""
    integ = result.get("integrity", {})
    counters = result.get("counters", {})
    phases = result.get("phases", {})
    poison, bitw = phases.get("poison", {}), phases.get("bitwise", {})
    replay = bitw.get("counters", {})
    n = result.get("processes", 4)
    gap = integ.get("loss_gap", float("nan"))
    rules = [
        ("clean: every process finished with a finite loss < 10",
         _all_finished(phases.get("clean", {}), n, below=10)),
        ("poison: every process finished with a finite loss",
         _all_finished(poison, n)),
        ("poison: nobody crashed", integ.get("crashes", -1) == 0),
        ("poison: contributor 2 was quarantined",
         poison.get("quarantined_at_version", -1) >= 0
         and integ.get("quarantines", 0) >= 1),
        ("poison: contributor 2 was readmitted on probation",
         poison.get("readmitted_at_version", -1) >= 0
         and integ.get("readmissions", 0) >= 1),
        ("poison: the screen rejected >= 3 payloads",
         integ.get("screen_rejects", 0) >= 3),
        ("poison: the wire digests caught >= 1 flipped chunk",
         integ.get("wire_integrity_failures", 0) >= 1),
        ("poison: the fault plane poisoned >= 3 gradients and flipped "
         ">= 1 chunk",
         counters.get("grad_poisons", 0) >= 3
         and counters.get("payload_bitflips", 0) >= 1),
        ("poison: final loss within 0.75 of the clean baseline",
         gap == gap and gap < 0.75),
        ("control: without the screen the run diverged",
         phases.get("control", {}).get("rc", 2) != 2
         and integ.get("control_diverged") is True),
        ("bitwise: screening a contributor out equals its never "
         "submitting, bit for bit",
         result.get("bitwise_equal") is True
         and bitw.get("bitwise_equal") is True),
        ("bitwise: the replay quarantined, readmitted and ended with "
         "nobody quarantined",
         replay.get("integrity_quarantines", 0) >= 1
         and replay.get("integrity_readmissions", 0) >= 1
         and replay.get("integrity_outlier_rejects", 0) >= 3
         and replay.get("integrity_quarantined", -1) == 0),
    ]
    if "poison_ef" in phases:   # RESILIENCE_r16 predates this leg
        ef = phases["poison_ef"]
        rules.append(
            ("poison_ef: with error feedback on, still quarantined and "
             "every process finished finite",
             _all_finished(ef, n)
             and ef.get("quarantined_at_version", -1) >= 0))
    return [name for name, held in rules if not held]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--phase", default="",
                    help="internal: worker phase (worker)")
    ap.add_argument("--train-dir", default="")
    ap.add_argument("--fault-spec", default="")
    ap.add_argument("--no-integrity", action="store_true")
    ap.add_argument("--ef", action="store_true",
                    help="worker: sender-side error feedback ON (the EF x "
                         "integrity composition leg)")
    ap.add_argument("--ef-clip", type=float, default=1.0,
                    help="per-leaf residual L2 cap for the --ef leg")
    ap.add_argument("--max-steps", type=int, default=40)
    # Poison window in process 2's OWN step clock: opens early (step 3)
    # and stays open 16 steps — enough leader screenings for 3 strikes —
    # then the long clean tail drives the probation readmission.
    ap.add_argument("--poison-step", type=int, default=3)
    ap.add_argument("--poison-steps", type=int, default=16)
    ap.add_argument("--out", default="RESILIENCE_r16.json")
    ap.add_argument("--run-dir", default="/tmp/poison_drill")
    args = ap.parse_args(argv)

    if args.phase == "worker":
        _worker(args)
        return 0

    base = pathlib.Path(args.run_dir)
    # scale=1e38 makes every poisoned payload a DETERMINISTIC screen
    # reject: any leaf with absmax > ~1e-17 lands either past the int8lat
    # exponent bound (|e| <= 64 <=> absmax <= ~1e21) or at inf (finite
    # scan). 1e30 is NOT enough — tiny leaves (bias grads ~1e-9) scale to
    # ~1e21, a validator-LEGAL exponent, and the MAD norm gate abstains
    # below 4 simultaneous fresh contributors (exercised instead by the
    # bitwise phase, where contributor counts are controlled). The
    # bitflips are reader-side and in-alphabet: armour decodes fine, only
    # the crc tokens catch them.
    poison_spec = (
        f"grad_poison:scale=1e38,r=2,step={args.poison_step},"
        f"steps={args.poison_steps};"
        f"payload_bitflip:p=0.01,seed=11,prefix=async-42/agrad")

    # -- phase 1: clean baseline ----------------------------------------
    rc_c, logs_c = _run_leg(base, "clean", args)
    finals_c = _final_losses(logs_c)
    print(f"PHASE clean rc={rc_c} finals={finals_c}")

    # -- phase 2: poisoned contributor + bit-flipped wire, screen ON ----
    rc_p, logs_p = _run_leg(base, "poison", args, fault_spec=poison_spec)
    all_p = "\n".join(logs_p)
    finals_p = _final_losses(logs_p)
    quarantined = re.search(
        r"INTEGRITY quarantine contributor 2 at version (\d+)", logs_p[0])
    readmitted = re.search(
        r"INTEGRITY readmit contributor 2 at version (\d+)", logs_p[0])
    summary = re.search(
        r"INTEGRITY pid 0 screen_rejects (\d+) outlier_rejects (\d+) "
        r"strikes (\d+) quarantines (\d+) readmissions (\d+) "
        r"wire_failures (\d+)", logs_p[0])
    stats = {int(m.group(1)): json.loads(m.group(2)) for m in re.finditer(
        r"DRILLSTATS pid (\d+) (\{.*\})", all_p)}
    poisons = sum(s.get("grad_poisons", 0) for s in stats.values())
    bitflips = sum(s.get("payload_bitflips", 0) for s in stats.values())
    s_rejects = int(summary.group(1)) if summary else 0
    s_strikes = int(summary.group(3)) if summary else 0
    s_quar = int(summary.group(4)) if summary else 0
    s_readmit = int(summary.group(5)) if summary else 0
    s_wire = int(summary.group(6)) if summary else 0
    loss_clean = finals_c.get(0, float("nan"))
    loss_poison = finals_p.get(0, float("nan"))
    loss_gap = abs(loss_poison - loss_clean)
    print(f"PHASE poison rc={rc_p} quarantined={bool(quarantined)} "
          f"readmitted={bool(readmitted)} finals={finals_p} "
          f"screen_rejects={s_rejects} quarantines={s_quar} "
          f"readmissions={s_readmit} wire_failures={s_wire} "
          f"grad_poisons={poisons} bitflips={bitflips} "
          f"loss_gap={loss_gap:.4f}")

    # -- phase 2b: same poison with EF RE-ENABLED (+ --ef-clip) ---------
    # The PR 13 gap: unclamped EF turned one poisoned window into many
    # steps of validator-legal re-emission. With the residual clamp the
    # offender must still be quarantined during its window, and the run
    # must stay finite and complete — the composition is safe again.
    rc_e, logs_e = _run_leg(base, "poison_ef", args,
                            fault_spec=poison_spec, ef=True)
    finals_e = _final_losses(logs_e)
    quarantined_ef = re.search(
        r"INTEGRITY quarantine contributor 2 at version (\d+)", logs_e[0])
    print(f"PHASE poison_ef rc={rc_e} "
          f"quarantined={bool(quarantined_ef)} finals={finals_e} "
          f"ef_clip={args.ef_clip}")

    # -- phase 3: same poison, screen OFF — must diverge ----------------
    rc_n, logs_n = _run_leg(base, "control", args, fault_spec=poison_spec,
                            no_integrity=True)
    finals_n = _final_losses(logs_n)
    ctl_loss = finals_n.get(0, float("nan"))
    # Divergence = non-finite loss or an order of magnitude off baseline.
    control_diverged = bool(ctl_loss != ctl_loss or
                            abs(ctl_loss) > 10 * max(loss_clean, 0.1))
    print(f"PHASE control rc={rc_n} diverged={control_diverged} "
          f"finals={finals_n}")

    # -- phase 4: deterministic bitwise exclusion -----------------------
    p4 = _phase_bitwise()
    print(f"PHASE bitwise bitwise_equal="
          f"{p4['bitwise_equal']} counters={p4['counters']}")

    # -- artifact -------------------------------------------------------
    art = {
        "round": 16,
        "platform": "cpu",
        "scenario": "poisoned_contributor_quarantine_readmit + "
                    "bitflip_wire_digests + no_screen_divergence_control "
                    "+ bitwise_exclusion",
        "processes": 4,
        "bitwise_equal": p4["bitwise_equal"],
        "counters": {"grad_poisons": int(poisons),
                     "payload_bitflips": int(bitflips)},
        "integrity": {
            "quarantines": s_quar,
            "readmissions": s_readmit,
            "screen_rejects": s_rejects,
            "strikes": s_strikes,
            "wire_integrity_failures": s_wire,
            "crashes": 0 if (len(finals_p) == 4 and rc_p != 2) else 1,
            "loss_clean": loss_clean,
            "loss_poisoned": loss_poison,
            "loss_gap": round(loss_gap, 4),
            "control_diverged": control_diverged,
        },
        "phases": {
            "clean": {"rc": rc_c, "finals": finals_c},
            "poison": {"rc": rc_p, "finals": finals_p,
                       "poison_step": args.poison_step,
                       "poison_steps": args.poison_steps,
                       "max_steps": args.max_steps,
                       "quarantined_at_version":
                           int(quarantined.group(1)) if quarantined else -1,
                       "readmitted_at_version":
                           int(readmitted.group(1)) if readmitted else -1,
                       "per_process_stats": stats},
            "poison_ef": {"rc": rc_e, "finals": finals_e,
                          "ef_clip": args.ef_clip,
                          "quarantined_at_version":
                              int(quarantined_ef.group(1))
                              if quarantined_ef else -1},
            "control": {"rc": rc_n, "finals": finals_n,
                        "diverged": control_diverged},
            "bitwise": p4,
        },
    }
    violations = verdict(art)
    ok = art["ok"] = not violations
    for v in violations:
        print(f"VIOLATED {v}")
    if not ok:
        for name, leg in (("poison", logs_p), ("poison_ef", logs_e)):
            print("\n\n".join(f"== {name} proc_{i} ==\n{t[-3000:]}"
                              for i, t in enumerate(leg)))
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"WROTE {args.out} ok={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
