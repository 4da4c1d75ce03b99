#!/usr/bin/env python
"""Multi-process elastic chaos drill -> RESILIENCE_r11.json.

The acceptance drill for the elastic control plane (ps_pytorch_tpu/elastic/),
run over REAL OS processes and the REAL jax.distributed coordination-service
KV — not the in-process KVStore the unit tests use. Two phases, both driven
through tools/launch.py ``--simulate``:

- **failover**: 3 processes train async (``--elastic``, initial leader =
  process 1 — NOT process 0, which hosts the coordination service). A
  ``leader_kill`` fault SIGKILLs the leader mid-run; a follower must detect
  the stale lease, campaign, win a higher epoch, fast-forward from the
  KV-published canonical params, and finish the run. Evidence is parsed
  from the per-process logs (FAULT / ELECTED / ELASTIC / FINAL lines).
- **rebalance**: 3 control-plane processes drive the epoch'd membership
  protocol (join -> leave -> rejoin, each bumping the view epoch) and a
  :class:`~ps_pytorch_tpu.elastic.rebalance.ShardedKVUpdate` over the
  DistributedKV: rounds at n=3, member 2 hands off and goes dormant,
  rounds at n=2, member 2 readmits (adopting params + momentum through the
  KV), rounds at n=3 again. Every process asserts the final full vector is
  BITWISE equal to the replicated SGD recurrence — the exactness guard,
  over the real wire.

The drill is judged by its own exit code: :func:`verdict` holds the whole
pass rule over the result it writes (counts and flags, no clock).

Usage:
    python ps_pytorch_tpu/tools/elastic_drill.py --out RESILIENCE_r11.json
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

def _sync(kv, run: str, tag: str, pid: int, n: int,
          timeout_s: float = 120.0) -> None:
    """Flat KV barrier: everyone writes sync/{tag}/{pid}, everyone waits
    for all n. The coordination service's own barrier needs matching
    timeouts on every call site; this stays duck-typed on the KV."""
    kv.set(f"{run}/sync/{tag}/{pid}", "1")
    deadline = time.monotonic() + timeout_s
    while True:
        if all(kv.get(f"{run}/sync/{tag}/{p}") is not None
               for p in range(n)):
            return
        if time.monotonic() > deadline:
            raise TimeoutError(f"sync barrier {tag!r} incomplete")
        time.sleep(0.02)


def _worker_failover(args) -> None:
    """One training process of the leader-kill phase. Only the INITIAL
    leader (process 1) arms the fault: leader_kill is role-addressed with
    ``step >= N`` semantics, so arming it everywhere would also fire on
    whoever wins the post-kill election — a kill cascade, not a drill.
    The lease interval (1.5s -> 4.5s timeout) leaves headroom over the
    first-step JIT-compile stall (~3s) so leadership doesn't churn at
    startup."""
    from ps_pytorch_tpu.parallel import dist
    dist.initialize_from_env()
    import jax
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.runtime.async_trainer import AsyncTrainer

    armed = jax.process_index() == 1
    cfg = TrainConfig(
        dataset="synthetic_mnist", network="LeNet", batch_size=128,
        lr=0.05, momentum=0.9, compute_dtype="float32", mode="async",
        max_steps=args.max_steps, eval_freq=4, train_dir=args.train_dir,
        resume=False, log_every=2,
        compress_grad=bool(args.grad_codec), ef=args.ef,
        grad_codec=args.grad_codec or TrainConfig.grad_codec,
        elastic=True, elastic_leader=1, leader_lease_s=3.0,
        heartbeat_interval_s=3.0, kv_retry_attempts=3,
        fault_spec=f"leader_kill:step={args.kill_step}" if armed else "")
    t = AsyncTrainer(cfg)
    t.train()
    stats = t._retrier.snapshot() if t._retrier is not None else {}
    print(f"DRILLSTATS pid {jax.process_index()} {json.dumps(stats)}",
          flush=True)
    r = t.evaluate(max_batches=2)
    print(f"FINAL loss {r['loss']:.4f} prec1 {r['prec1']:.4f} "
          f"version {t.version}", flush=True)
    # The killed leader (process 1) can never reach the distributed
    # shutdown barrier, so survivors must not wait at it either — but
    # process 0 hosts the coordination service, so it must ALSO not exit
    # before the other survivor is done with the KV. Flat-key exit
    # barrier among the survivors, then a hard exit.
    kv = t.election.kv
    run = f"async-{cfg.seed}"
    kv.set(f"{run}/exitbar/{jax.process_index()}", "1")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if all(kv.get(f"{run}/exitbar/{p}") is not None for p in (0, 2)):
            break
        time.sleep(0.05)
    os._exit(0)


def _worker_rebalance(args) -> None:
    """One control-plane process of the rejoin/rebalance phase."""
    import numpy as np

    from ps_pytorch_tpu.parallel import dist
    dist.initialize_from_env()
    import jax
    from ps_pytorch_tpu.elastic import (
        MemberAnnouncer, MembershipRegistry, ShardedKVUpdate,
    )
    from ps_pytorch_tpu.runtime.coordinator import DistributedKV

    kv = DistributedKV()
    pid, n = jax.process_index(), jax.process_count()
    run = "drill-rebalance"
    lr, mu, size = 0.05, 0.9, 257
    rng = np.random.default_rng(17)
    p0 = rng.standard_normal(size).astype(np.float32)
    grads = [rng.standard_normal(size).astype(np.float32)
             for _ in range(8)]

    # -- membership: join -> leave -> rejoin, one epoch bump each --------
    ann = MemberAnnouncer(kv, run, pid, [pid], interval_s=0.2)
    reg = MembershipRegistry(kv, run, n, n, timeout_s=60.0) \
        if pid == 0 else None
    ann.join()
    _sync(kv, run, "joined", pid, n)
    if reg is not None:
        view = reg.update(step=0)
        assert view["members"] == list(range(n)), view
    _sync(kv, run, "viewed1", pid, n)
    if pid == 2:
        ann.leave()
    _sync(kv, run, "left", pid, n)
    if reg is not None:
        view = reg.update(step=1)
        assert view["members"] == [0, 1], view
    _sync(kv, run, "viewed2", pid, n)
    if pid == 2:
        ann.join()              # readmission with a bumped incarnation
    _sync(kv, run, "rejoined", pid, n)
    if reg is not None:
        view = reg.update(step=2)
        assert view["members"] == list(range(n)), view
        print(f"MEMBERSHIP {json.dumps(reg.snapshot())}", flush=True)

    # -- sharded update: exactness across two rebalances over the KV ----
    upd = ShardedKVUpdate(kv, run, size, list(range(n)), pid, lr,
                          momentum=mu, timeout_s=60.0)
    upd.init(p0)
    full = None
    for g in grads[:3]:
        full = upd.step(g)
    upd.set_members([0, 1])                 # member 2 hands off, dormant
    if pid != 2:
        for g in grads[3:5]:
            full = upd.step(g)
    upd.set_members(list(range(n)))         # member 2 readmitted
    for g in grads[5:]:
        full = upd.step(g)
    ref = ShardedKVUpdate.replicated_reference(p0, grads, lr, mu)
    equal = bool(np.array_equal(full, ref))
    print(f"REBALANCE pid {pid} bitwise_equal "
          f"{str(equal).lower()} {json.dumps(upd.snapshot())}", flush=True)
    print("FINAL rebalance ok" if equal else "REBALANCE MISMATCH",
          flush=True)
    # Process 0 hosts the coordination service: nobody hard-exits until
    # everyone is done with the KV.
    _sync(kv, run, "exit", pid, n)
    os._exit(0 if equal else 3)


# ---------------------------------------------------------------- driver

def _launch(run_dir: pathlib.Path, port: int, worker_args) -> int:
    return launch.main([
        "launch", "--run-dir", str(run_dir), "--simulate", "3",
        "--devices-per-host", "2", "--port", str(port),
        "--entry", str(pathlib.Path(__file__).resolve()),
        "--cwd", str(REPO), "--wait", "--timeout", "420",
        "--", *worker_args,
    ])


def verdict(result: dict) -> list:
    """The drill's whole pass rule: the invariants ``result`` violates,
    empty when it passes. A drill in which nobody died proved nothing, so
    the kill, the election and the membership changes are demanded, not
    only the absence of errors."""
    counters = result.get("counters", {})
    el = result.get("elastic", {})
    phases = result.get("phases", {})
    fo, rb = phases.get("failover", {}), phases.get("rebalance", {})
    member = rb.get("membership", {})
    rules = [
        ("failover: the launcher ran the processes (rc != 2)",
         fo.get("rc", 2) != 2),
        ("failover: the leader was SIGKILLed",
         counters.get("leader_kills", 0) >= 1),
        ("failover: exactly one election",
         el.get("elections", 0) == 1),
        ("failover: the new leader holds an epoch >= 2",
         el.get("final_epoch", 0) >= 2),
        # records written before this key existed do not hold it
        ("failover: both survivors finished",
         fo.get("survivors_finished", 2) == 2),
        ("failover: the new leader folded at least one membership change",
         el.get("membership_changes", 0)
         > member.get("membership_changes", 0)),
        ("the retry plane never gave up",
         counters.get("kv_giveups", -1) == 0),
        ("rebalance: every process exited 0", rb.get("rc", -1) == 0),
        ("rebalance: sharded update bitwise equal to the replicated "
         "recurrence on every process",
         result.get("bitwise_equal") is True),
        ("rebalance: join, leave and rejoin each bumped the view epoch",
         member.get("epoch", 0) >= 3
         and member.get("membership_changes", 0) >= 3),
    ]
    return [name for name, held in rules if not held]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--phase", default="",
                    help="internal: worker phase (failover|rebalance)")
    ap.add_argument("--train-dir", default="")
    # Long enough that the post-failover leader actually LEADS for a
    # stretch (folds membership, evicts the corpse, publishes versions)
    # rather than electing at the finish line: versions advance ~2/s on
    # this mesh while the kill lands ~7s in and detection adds the 9s
    # lease timeout.
    ap.add_argument("--max-steps", type=int, default=48)
    # Kill at the leader's own step 2 — one real iteration after the JIT
    # compile stall. Any later and the leader may have drained the
    # followers' banked grads into the full version stream already,
    # leaving the election nothing to lead (it would land at the finish
    # line with membership never folded).
    ap.add_argument("--kill-step", type=int, default=2)
    # Gradient-compression soak: run the failover phase with a compressed
    # wire codec so the kill/election path also exercises the error-
    # feedback residual surviving leader promotion.
    ap.add_argument("--grad-codec", default="",
                    help="wire codec for the failover phase "
                         "(e.g. int8lat); empty = uncompressed")
    ap.add_argument("--ef", action="store_true",
                    help="enable error feedback with --grad-codec")
    ap.add_argument("--out", default="RESILIENCE_r11.json")
    ap.add_argument("--run-dir", default="/tmp/elastic_drill")
    args = ap.parse_args(argv)

    if args.phase == "failover":
        _worker_failover(args)
        return 0
    if args.phase == "rebalance":
        _worker_rebalance(args)
        return 0

    base = pathlib.Path(args.run_dir)
    d1, d2 = base / "failover", base / "rebalance"
    # Fresh dirs: _promote() deliberately adopts the newest valid
    # checkpoint it finds, so a stale ckpt/ from a previous drill would
    # teleport the new leader straight to the finish line.
    import shutil
    for d in (d1, d2):
        shutil.rmtree(d, ignore_errors=True)

    # -- phase 1: leader kill mid-run -----------------------------------
    rc1 = _launch(d1, _free_port(), [
        "--phase", "failover", "--train-dir", str(d1 / "ckpt"),
        "--max-steps", str(args.max_steps),
        "--kill-step", str(args.kill_step)]
        + (["--grad-codec", args.grad_codec] if args.grad_codec else [])
        + (["--ef"] if args.ef else []))
    logs = _logs(d1)
    dump = "\n\n".join(f"== proc_{i} ==\n{t[-2500:]}"
                       for i, t in enumerate(logs))
    killed = "FAULT leader_kill: SIGKILL" in logs[1]
    elected = [(i, m) for i, t in enumerate(logs)
               for m in [re.search(
                   r"ELECTED async leader process (\d+) epoch (\d+) at "
                   r"version (\d+) \(election ([0-9.]+)s\)", t)] if m]
    survivors_final = [i for i, t in enumerate(logs)
                       if i != 1 and "FINAL" in t]
    elastic_lines = re.findall(
        r"ELASTIC pid (\d+) epoch (\d+) world (\d+) membership_changes "
        r"(\d+) wins (\d+)", "\n".join(logs))
    new_leader = elected[0] if elected else None
    final_epoch = int(new_leader[1].group(2)) if new_leader else 0
    latency = float(new_leader[1].group(4)) if new_leader else -1.0
    leader_changes = 0
    for line in elastic_lines:
        if new_leader and int(line[0]) == int(new_leader[1].group(1)):
            leader_changes = int(line[3])
    kv_giveups = sum(json.loads(m).get("kv_giveups", 0) for m in re.findall(
        r"DRILLSTATS pid \d+ (\{.*\})", "\n".join(logs)))
    print(f"PHASE failover killed={killed} "
          f"elected={[(i, m.group(2)) for i, m in elected]} "
          f"survivors_finished={survivors_final} latency={latency:.3f}s "
          f"membership_changes={leader_changes} kv_giveups={kv_giveups}")

    # -- phase 2: rejoin + sharded rebalance exactness ------------------
    rc2 = _launch(d2, _free_port(), ["--phase", "rebalance"])
    logs2 = _logs(d2)
    rebal = re.findall(r"REBALANCE pid (\d+) bitwise_equal (\w+) (\{.*\})",
                       "\n".join(logs2))
    member = re.search(r"MEMBERSHIP (\{.*\})", logs2[0])
    msnap = json.loads(member.group(1)) if member else {}
    bitwise = len(rebal) == 3 and all(r[1] == "true" for r in rebal)
    rebalances = max((json.loads(r[2]).get("rebalances", 0)
                      for r in rebal), default=0)
    print(f"PHASE rebalance rc={rc2} bitwise={bitwise} "
          f"membership={msnap} rebalances={rebalances}")

    # -- artifact -------------------------------------------------------
    art = {
        "round": 11,
        "platform": "cpu",
        "scenario": "elastic_leader_kill_failover + rejoin_readmit + "
                    "sharded_rebalance_bitwise",
        "processes": 3,
        "bitwise_equal": bitwise,
        "grad_codec": args.grad_codec or "none",
        "error_feedback": bool(args.ef),
        "counters": {"leader_kills": int(killed),
                     "kv_giveups": int(kv_giveups)},
        "elastic": {
            "elections": len(elected),
            "membership_changes": leader_changes
            + int(msnap.get("membership_changes", 0)),
            "final_epoch": final_epoch,
            "election_latency_s": round(latency, 3),
            "view_epoch_rejoin": int(msnap.get("epoch", 0)),
            "rebalances": int(rebalances),
            "world_size_after_kill": 2,
        },
        "phases": {
            "failover": {"rc": rc1, "killed_pid": 1,
                         "survivors_finished": len(survivors_final),
                         "new_leader_pid":
                             int(new_leader[1].group(1)) if new_leader
                             else -1,
                         "resumed_at_version":
                             int(new_leader[1].group(3)) if new_leader
                             else -1,
                         "max_steps": args.max_steps,
                         "kill_step": args.kill_step},
            "rebalance": {"rc": rc2, "membership": msnap},
        },
    }
    violations = verdict(art)
    ok = art["ok"] = not violations
    for v in violations:
        print(f"VIOLATED {v}")
    if not ok:
        print(dump)
        print("\n\n".join(f"== proc_{i} ==\n{t[-2500:]}"
                          for i, t in enumerate(logs2)))
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"WROTE {args.out} ok={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
