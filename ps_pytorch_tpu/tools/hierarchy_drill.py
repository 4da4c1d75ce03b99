#!/usr/bin/env python
"""Subtree-partition chaos drill for hierarchical sync -> RESILIENCE_r14.json.

The acceptance drill for the partition-tolerant multi-hop sync plane
(ps_pytorch_tpu/parallel/hierarchy.py). Two phases:

- **partition** (multi-process): 4 processes train async with
  ``--sync-topology hier`` (2 groups of 2, int8lat + EF) over the REAL
  jax.distributed coordination KV, driven through tools/launch.py
  ``--simulate``. A ``kv_partition:group=1,...`` fault window cuts group 1
  (processes 2, 3) off the KV mid-run: the root must declare the subtree
  partitioned (``HIER partition group 1``), keep applying updates from the
  surviving group (degraded-mode continuation), then re-graft the healed
  subtree (``HIER regraft group 1``) and complete the run. Evidence is
  parsed from the per-process logs (HIER / HIERARCHY / DRILLSTATS / FINAL
  lines).
- **bitwise** (in-process, deterministic): the same partition -> degrade ->
  heal -> re-graft arc through :class:`HierarchicalAggregator` driving a
  seeded SGD recurrence, checkpointed mid-run AFTER the re-graft (params +
  the member/hop error-feedback residuals — exactly what MultiSliceTrainer
  checkpoints under ``--auto-resume``). The rerun from the checkpoint must
  reach a final vector BITWISE equal to the uninterrupted run.

The drill is judged by its own exit code: :func:`verdict` holds the whole
pass rule over the result it writes (counts and flags, no clock). It
deliberately does NOT demand zero ``kv_giveups``: inside a partition window
the retry plane giving up after bounded attempts IS the contract (degraded
mode), so the rule is on the lifecycle counters instead.

Usage:
    python ps_pytorch_tpu/tools/hierarchy_drill.py --out RESILIENCE_r14.json
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

def _worker_partition(args) -> None:
    """One training process of the subtree-partition phase. The fault spec
    is armed on EVERY process — ``kv_partition:group=1`` self-scopes by
    ``process_index // gsize``, so only group 1 (pids 2, 3) actually loses
    its KV, keyed on its own step clock. Retry attempts are kept low so a
    partitioned step degrades in ~100 ms instead of stalling out the
    window; the lease interval leaves headroom over the first-step JIT
    stall so group leadership doesn't churn at startup."""
    from ps_pytorch_tpu.parallel import dist
    dist.initialize_from_env()
    import jax
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.runtime.async_trainer import AsyncTrainer

    cfg = TrainConfig(
        dataset="synthetic_mnist", network="LeNet", batch_size=64,
        lr=0.05, momentum=0.9, compute_dtype="float32", mode="async",
        max_steps=args.max_steps, eval_freq=0, train_dir=args.train_dir,
        resume=False, log_every=4,
        compress_grad=True, grad_codec="int8lat", ef=True,
        sync_topology="hier", sync_group_size=2, staleness_limit=4,
        leader_lease_s=3.0, kv_retry_attempts=2,
        fault_spec=f"kv_partition:group=1,gsize=2,"
                   f"step={args.cut_step},steps={args.cut_steps}")
    t = AsyncTrainer(cfg)
    t.train()
    stats = dict(t.transport.stats)
    if t.injector is not None:
        stats.update(t.injector.snapshot())
    if t._retrier is not None:
        stats.update(t._retrier.snapshot())
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

def _phase_bitwise(resume_step: int = 20, total_steps: int = 32) -> dict:
    """Deterministic partition arc + bit-for-bit resume through the
    in-process HierarchicalAggregator: group 1's members go silent for a
    window, the root degrades then re-grafts, and a checkpoint taken after
    the re-graft (params + EF residuals) replays to the SAME final bits as
    the uninterrupted run."""
    import numpy as np

    from ps_pytorch_tpu.parallel.hierarchy import HierarchicalAggregator

    n, size, lr = 4, 513, 0.05
    outage = range(8, 15)           # steps where group 1 is cut off
    events = []

    def grad(i, t):
        rng = np.random.default_rng(1000 + 97 * i + t)
        return {"w": rng.standard_normal(size).astype(np.float32)}

    def make_agg(on_event=None):
        return HierarchicalAggregator(
            n, group_size=2, staleness_limit=4, staleness_decay=0.5,
            codec="int8lat", error_feedback=True, hop_ef=True,
            on_event=on_event)

    def run(t0, p0, agg, ckpt_at=None):
        p, ckpt = p0.copy(), None
        for t in range(t0, total_steps):
            for i in range(n):
                if i >= 2 and t in outage:
                    continue        # group 1 cut off from the root
                agg.submit(i, t, grad(i, t))
            avg, info = agg.collect(t)
            if avg is not None:
                p = (p - lr * np.asarray(avg["w"], np.float32)
                     ).astype(np.float32)
            agg.consume(info["used"])
            agg.drop_older_than(t)
            if ckpt_at is not None and t == ckpt_at:
                assert not agg._members._pool and not agg.root._pool \
                    and all(not g.inner._pool for g in agg._groups), \
                    "checkpoint taken with in-flight contributions"
                ckpt = (p.copy(), agg.ef_state_dict())
        return p, ckpt

    agg = make_agg(lambda kind, gid, step, st:
                   events.append((kind, gid, step, st)))
    rng = np.random.default_rng(0)
    p0 = rng.standard_normal(size).astype(np.float32)
    final, ckpt = run(0, p0, agg, ckpt_at=resume_step - 1)
    counters = dict(agg.root.counters)

    p_ck, ef_state = ckpt
    agg2 = make_agg()
    agg2.load_ef_state(ef_state)
    final2, _ = run(resume_step, p_ck, agg2)
    bitwise = bool(np.array_equal(final, final2))
    return {"bitwise_equal": bitwise, "resume_step": resume_step,
            "total_steps": total_steps, "counters": counters,
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


_LIFECYCLE = ("partitions", "regrafts", "degraded_steps")


def verdict(result: dict) -> list:
    """The drill's whole pass rule: the invariants ``result`` violates,
    empty when it passes. The partition -> degrade -> heal -> re-graft arc
    must actually have happened, over the real processes and in the
    in-process replay, and the resumed continuation must be bitwise."""
    hier = result.get("hierarchy", {})
    phases = result.get("phases", {})
    part, bitw = phases.get("partition", {}), phases.get("bitwise", {})
    replay = bitw.get("counters", {})
    n = result.get("processes", 4)
    rules = [
        ("partition: the launcher ran the processes (rc != 2)",
         part.get("rc", 2) != 2),
        ("partition: the root declared group 1 partitioned",
         part.get("declared_at_version", -1) >= 0),
        ("partition: the root re-grafted group 1",
         part.get("regrafted_at_version", -1) >= 0),
        # records written before this key existed do not hold it
        ("partition: every process finished", part.get("finished", n) == n),
        ("partition: the fault plane dropped KV ops",
         result.get("counters", {}).get("kv_partition_drops", 0) > 0),
        ("partition: both groups healthy at the end",
         hier.get("groups_healthy_final", 0) == hier.get("groups", 2)),
    ] + [
        (f"partition: {key} >= 1", hier.get(key, 0) >= 1)
        for key in _LIFECYCLE
    ] + [
        (f"bitwise replay: {key} >= 1", replay.get(key, 0) >= 1)
        for key in _LIFECYCLE
    ] + [
        ("bitwise replay: resume after the re-graft reaches the same bits",
         result.get("bitwise_equal") is True
         and bitw.get("bitwise_equal") is True),
    ]
    return [name for name, held in rules if not held]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--phase", default="",
                    help="internal: worker phase (partition)")
    ap.add_argument("--train-dir", default="")
    # Long enough that the root lives through the whole arc: the cut
    # opens ~6 member steps in, stays open for 12 (so group 1 goes silent
    # past the staleness limit and the partition is DECLARED), and the
    # heal leaves ~20 more leader versions for catch-up + re-graft.
    ap.add_argument("--max-steps", type=int, default=40)
    ap.add_argument("--cut-step", type=int, default=6)
    ap.add_argument("--cut-steps", type=int, default=12)
    ap.add_argument("--out", default="RESILIENCE_r14.json")
    ap.add_argument("--run-dir", default="/tmp/hierarchy_drill")
    args = ap.parse_args(argv)

    if args.phase == "partition":
        _worker_partition(args)
        return 0

    base = pathlib.Path(args.run_dir)
    d1 = base / "partition"
    import shutil
    shutil.rmtree(d1, ignore_errors=True)

    # -- phase 1: subtree partition mid-run over real processes ---------
    rc1 = _launch(d1, _free_port(), [
        "--phase", "partition", "--train-dir", str(d1 / "ckpt"),
        "--max-steps", str(args.max_steps),
        "--cut-step", str(args.cut_step),
        "--cut-steps", str(args.cut_steps)])
    logs = _logs(d1)
    all_logs = "\n".join(logs)
    partitioned = re.search(r"HIER partition group 1 at version (\d+)",
                            logs[0])
    regrafted = re.search(r"HIER regraft group 1 at version (\d+)", logs[0])
    finals = [i for i, t in enumerate(logs) if "FINAL" in t]
    summary = re.search(
        r"HIERARCHY pid 0 .* partitions (\d+) regrafts (\d+) "
        r"degraded_steps (\d+) groups_healthy (\d+)", logs[0])
    stats = {int(m.group(1)): json.loads(m.group(2)) for m in re.finditer(
        r"DRILLSTATS pid (\d+) (\{.*\})", all_logs)}
    drops = sum(s.get("kv_partition_drops", 0) for s in stats.values())
    giveups = sum(s.get("hop_giveups", 0) for s in stats.values())
    kv_giveups = sum(s.get("kv_giveups", 0) for s in stats.values())
    failovers = sum(s.get("failovers", 0) for s in stats.values())
    p_part = int(summary.group(1)) if summary else 0
    p_regraft = int(summary.group(2)) if summary else 0
    p_degraded = int(summary.group(3)) if summary else 0
    p_healthy = int(summary.group(4)) if summary else 0
    print(f"PHASE partition declared="
          f"{bool(partitioned)} regrafted={bool(regrafted)} "
          f"finals={finals} partitions={p_part} regrafts={p_regraft} "
          f"degraded_steps={p_degraded} kv_drops={drops} "
          f"hop_giveups={giveups}")

    # -- phase 2: deterministic bitwise resume --------------------------
    p2 = _phase_bitwise()
    print(f"PHASE bitwise bitwise_equal="
          f"{p2['bitwise_equal']} counters={p2['counters']}")

    # -- artifact -------------------------------------------------------
    art = {
        "round": 14,
        "platform": "cpu",
        "scenario": "hier_subtree_partition_degrade_regraft + "
                    "bitwise_ef_resume",
        "processes": 4,
        "bitwise_equal": p2["bitwise_equal"],
        "counters": {"kv_partition_drops": int(drops)},
        "hierarchy": {
            "groups": 2,
            "group_size": 2,
            "partitions": p_part,
            "regrafts": p_regraft,
            "degraded_steps": p_degraded,
            "groups_healthy_final": p_healthy,
            "failovers": int(failovers),
            "hop_giveups": int(giveups),
            "kv_giveups": int(kv_giveups),
        },
        "phases": {
            "partition": {"rc": rc1,
                          "finished": len(finals),
                          "cut_step": args.cut_step,
                          "cut_steps": args.cut_steps,
                          "max_steps": args.max_steps,
                          "declared_at_version":
                              int(partitioned.group(1)) if partitioned
                              else -1,
                          "regrafted_at_version":
                              int(regrafted.group(1)) if regrafted
                              else -1,
                          "per_process_stats": stats},
            "bitwise": p2,
        },
    }
    violations = verdict(art)
    ok = art["ok"] = not violations
    for v in violations:
        print(f"VIOLATED {v}")
    if not ok:
        print("\n\n".join(f"== proc_{i} ==\n{t[-3000:]}"
                          for i, t in enumerate(logs)))
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"WROTE {args.out} ok={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
