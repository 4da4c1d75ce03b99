#!/usr/bin/env python
"""Quorum-replicated coordination-plane chaos drill -> RESILIENCE_r17.json.

The acceptance drill for ReplicatedKV (ps_pytorch_tpu/runtime/kvrep.py):
the KV ITSELF is the victim. Three phases:

- **train**: 3 REAL ``python -m ps_pytorch_tpu.runtime.kvrep`` backend
  server processes; 3 REAL elastic async-training processes (tools/launch
  ``--simulate``) run their whole coordination plane — election, lease,
  membership, gradient wire — over the quorum (``--kv-replicas`` with 3
  HTTP backends, quorum 2). The driver SIGKILLs backend 1 mid-run and
  restarts it EMPTY on the same port (the restart IS the wipe). The run
  must complete every version with zero retry giveups; every client must
  eject, probe, rejoin and anti-entropy-resync the reborn backend; the
  drill then verifies the wiped backend is tag-equal key-by-key.
- **serve**: 3 serve.py replicas register/beat through ``--kv-replicas``
  over 3 FileKV directory backends; the router's FleetView reads the same
  quorum. Mid-open-loop-load the driver wipes one directory clean.
  Availability must stay 1.00 with zero 5xx, the router's fleet view must
  never lose a replica, and the wiped directory must be repopulated
  (lease beats fan out to all backends; quorum reads repair the rest).
- **bitwise**: a momentum-SGD recurrence whose state lives ONLY in the
  replicated KV, with ``kv_backend_kill`` (window) and ``kv_backend_wipe``
  faults armed on one backend and a client restart mid-sequence that
  resumes from a quorum read. The final vector must be BITWISE equal to
  the pure-numpy oracle — the exactness guard for resume-through-quorum.

The drill is judged by its own exit code: :func:`verdict` holds the whole
pass rule over the result it writes (counts and flags, no clock).

Usage:
    python ps_pytorch_tpu/tools/kvrep_drill.py --out RESILIENCE_r17.json
"""

import argparse
import base64
import json
import os
import pathlib
import re
import shutil
import signal
import subprocess
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parent.parent.parent
if str(REPO) not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, str(REPO))

# The serve phase's fleet is router_drill's: same tiny LM, same checkpoint.
from ps_pytorch_tpu.tools import launch  # noqa: E402
from ps_pytorch_tpu.tools.launch import (  # noqa: E402
    free_port as _free_port, proc_logs as _logs,
)
from ps_pytorch_tpu.tools.router_drill import (  # noqa: E402
    FLEET, V, _wait_ready, _write_checkpoint,
)


# ---------------------------------------------------------------- workers

def _worker_train(args) -> None:
    """One elastic async-training process whose ENTIRE coordination plane
    rides the replicated KV: election lease, membership heartbeat, the
    gradient wire, canonical params. No process is killed in this phase —
    the KV backends are the victims — so everyone reaches the exit
    barrier (held on the replicated KV itself)."""
    from ps_pytorch_tpu.parallel import dist
    dist.initialize_from_env()
    import jax
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.runtime.async_trainer import AsyncTrainer

    cfg = TrainConfig(
        dataset="synthetic_mnist", network="LeNet", batch_size=128,
        lr=0.05, momentum=0.9, compute_dtype="float32", mode="async",
        max_steps=args.max_steps, eval_freq=4, train_dir=args.train_dir,
        resume=False, log_every=2,
        elastic=True, elastic_leader=1, leader_lease_s=3.0,
        heartbeat_interval_s=3.0, kv_retry_attempts=3,
        kv_replicas=args.kv_replicas, kv_quorum=2,
        kv_resync_s=args.resync_s)
    t = AsyncTrainer(cfg)
    t.train()
    r = t.evaluate(max_batches=2)
    stats = dict(t._kvrep.snapshot())
    stats["kvrep_backends_healthy"] = t._kvrep.healthy_count()
    if t._retrier is not None:
        stats.update(t._retrier.snapshot())
    pid = jax.process_index()
    print(f"KVREPSTATS pid {pid} {json.dumps(stats)}", flush=True)
    print(f"FINAL loss {r['loss']:.4f} prec1 {r['prec1']:.4f} "
          f"version {t.version}", flush=True)
    # Exit barrier over the replicated KV: the barrier's poll loop keeps
    # every client ticking (probation probes included) until all three
    # are done writing, so the reborn backend sees the final keys too.
    kv = t.election.kv
    run = f"async-{cfg.seed}"
    kv.set(f"{run}/exitbar/{pid}", "1")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if all(kv.get(f"{run}/exitbar/{p}") is not None for p in range(3)):
            break
        time.sleep(0.05)
    os._exit(0)


# ---------------------------------------------------------------- driver

class KVBackend:
    """One ``python -m ps_pytorch_tpu.runtime.kvrep`` server process —
    independently killable, restartable EMPTY on the same port."""

    def __init__(self, idx: int, port: int, base: pathlib.Path):
        self.idx = idx
        self.port = port
        self.log_path = base / f"kv_backend_{idx}.log"
        self.proc = None

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def start(self) -> None:
        log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "ps_pytorch_tpu.runtime.kvrep",
             "--port", str(self.port)],
            stdout=log, stderr=log, cwd=str(REPO),
            env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def wait_ready(self, timeout_s: float = 20.0) -> None:
        import urllib.request
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(self.url + "/healthz",
                                            timeout=1.0) as r:
                    if r.status == 200:
                        return
            except Exception:
                time.sleep(0.1)
        raise TimeoutError(f"kv backend {self.idx} not ready on {self.url}")

    def sigkill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGKILL)
            self.proc.wait(timeout=10)

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()


def _launch(run_dir: pathlib.Path, port: int, worker_args) -> int:
    return launch.main([
        "launch", "--run-dir", str(run_dir), "--simulate", "3",
        "--devices-per-host", "2", "--port", str(port),
        "--entry", str(pathlib.Path(__file__).resolve()),
        "--cwd", str(REPO), "--wait", "--timeout", "420",
        "--", *worker_args,
    ])


def _phase_train(args, base: pathlib.Path) -> dict:
    """Backend SIGKILL + empty-restart (the wipe) under live training."""
    from ps_pytorch_tpu.runtime.kvrep import HttpKV, ReplicatedKV

    run_dir = base / "train"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    backends = [KVBackend(i, _free_port(), run_dir) for i in range(3)]
    for b in backends:
        b.start()
    for b in backends:
        b.wait_ready()
    specs = ",".join(b.url for b in backends)
    victim = backends[1]
    evidence = {"killed": False, "wiped": False, "kill_at_s": -1.0}

    def _killer():
        # Fire once training is demonstrably under way (a step >= 2 line
        # in any proc log), with a generous fallback for slow JIT.
        t0 = time.monotonic()
        deadline = t0 + 60.0
        while time.monotonic() < deadline:
            logs = "\n".join(_logs(run_dir))
            m = re.findall(r"STEP\s+(\d+)", logs)
            if any(int(x) >= 2 for x in m):
                break
            time.sleep(0.25)
        victim.sigkill()
        evidence["killed"] = True
        evidence["kill_at_s"] = round(time.monotonic() - t0, 2)
        time.sleep(args.kill_window_s)
        victim.start()          # same port, EMPTY store: the wipe
        victim.wait_ready()
        evidence["wiped"] = True

    killer = threading.Thread(target=_killer, daemon=True)
    killer.start()
    rc = _launch(run_dir, _free_port(), [
        "--phase", "train", "--train-dir", str(run_dir / "ckpt"),
        "--max-steps", str(args.max_steps),
        "--kv-replicas", specs, "--resync-s", str(args.resync_s)])
    killer.join(timeout=90.0)

    logs = _logs(run_dir)
    finals = [i for i, t in enumerate(logs) if "FINAL" in t]
    versions = [int(m.group(1)) for t in logs
                for m in [re.search(r"FINAL .* version (\d+)", t)] if m]
    stats = {}
    for t in logs:
        for m in re.finditer(r"KVREPSTATS pid (\d+) (\{.*\})", t):
            stats[int(m.group(1))] = json.loads(m.group(2))
    giveups = sum(s.get("kv_giveups", 0) for s in stats.values())
    rejoins = sum(s.get("kvrep_rejoins", 0) for s in stats.values())
    resyncs = sum(s.get("kvrep_resyncs", 0) for s in stats.values())
    ejections = sum(s.get("kvrep_ejections", 0) for s in stats.values())
    healthy_end = [s.get("kvrep_backends_healthy", 0)
                   for s in stats.values()]

    # Key-by-key tag equality: the reborn backend vs an untouched one.
    rkv = ReplicatedKV([HttpKV(b.url) for b in backends], writer="driver")
    tag_equal, driver_resync, tags0 = False, False, {}
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        tags0 = rkv.backend_tags(0)
        if tags0 and tags0 == rkv.backend_tags(1):
            tag_equal = True
            break
        time.sleep(0.5)
    if not tag_equal:
        # Clients resynced during the run (counted above); a final driver
        # pass only mops up keys written in the exit race, and its use is
        # recorded in the artifact.
        rkv.resync_backend(1)
        driver_resync = True
        tags0 = rkv.backend_tags(0)
        tag_equal = bool(tags0) and tags0 == rkv.backend_tags(1)
    for b in backends:
        b.stop()

    out = {"rc": rc, "procs": 3, "backends": 3,
           "finals": len(finals), "max_version": max(versions, default=0),
           "max_steps": args.max_steps,
           "giveups": giveups, "ejections": ejections,
           "rejoins": rejoins, "resyncs": resyncs,
           "healthy_at_exit": healthy_end,
           "kills": int(evidence["killed"]), "wipes": int(evidence["wiped"]),
           "kill_at_s": evidence["kill_at_s"],
           "resync_tag_equal": tag_equal, "keys_compared": len(tags0),
           "driver_resync": driver_resync}
    print(f"PHASE train rc={rc} finals={len(finals)} giveups={giveups} "
          f"rejoins={rejoins} resyncs={resyncs} tag_equal={tag_equal} "
          f"keys={len(tags0)}", flush=True)
    if _train_violations(out):
        print("\n\n".join(f"== proc_{i} ==\n{t[-2500:]}"
                          for i, t in enumerate(logs)))
    return out


class Replica:
    """One serve.py subprocess registering through --kv-replicas."""

    def __init__(self, rid: int, base: pathlib.Path, train_dir: str,
                 kv_specs: str):
        self.rid = rid
        self.log_path = base / f"replica_{rid}.log"
        self.train_dir = train_dir
        self.kv_specs = kv_specs
        self.proc = None

    def start(self) -> None:
        cmd = [sys.executable, str(REPO / "serve.py"),
               "--train-dir", self.train_dir,
               "--serve-port", "0", "--serve-host", "127.0.0.1",
               "--serve-slots", "4", "--serve-max-queue", "64",
               "--serve-reload-s", "0",
               "--kv-replicas", self.kv_specs,
               "--serve-fleet", FLEET,
               "--serve-replica-id", str(self.rid),
               "--serve-deadline-s", "20"]
        log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            cmd, stdout=log, stderr=log, cwd=str(REPO),
            env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def log(self) -> str:
        return self.log_path.read_text() if self.log_path.exists() else ""

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)


def _phase_serve(args, base: pathlib.Path) -> dict:
    """Backend wipe under live fleet serving: the router's fleet view and
    client availability must not notice one KV backend losing its data."""
    from ps_pytorch_tpu.runtime.coordinator import FileKV
    from ps_pytorch_tpu.runtime.kvrep import ReplicatedKV
    from ps_pytorch_tpu.serving.loadgen import run_http_open_loop
    from ps_pytorch_tpu.serving.router import FleetView, Router
    from ps_pytorch_tpu.telemetry.registry import (
        Registry, declare_router_metrics,
    )

    run_dir = base / "serve"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    train_dir = str(run_dir / "ckpt")
    _write_checkpoint(train_dir, 1, seed=0)
    kv_dirs = [run_dir / f"kv{i}" for i in range(3)]
    specs = ",".join(f"dir:{d}" for d in kv_dirs)

    replicas = [Replica(r, run_dir, train_dir, specs) for r in range(3)]
    for rep in replicas:
        rep.start()
    rkv = ReplicatedKV([FileKV(str(d)) for d in kv_dirs], writer="driver")
    # Single-core CI box: 3 JAX replicas under load starve their lease-
    # beat threads for several seconds at a stretch, so a 3 s lease gate
    # would empty the view for reasons that have nothing to do with the
    # KV. The /readyz probe stays as the liveness gate; the lease gate is
    # kept but sized for GIL starvation, not network failure.
    view = FleetView(rkv, FLEET, lease_timeout_s=15.0, probe_timeout_s=2.0)
    router = Router(view, registry=declare_router_metrics(Registry()),
                    retries=3, backoff_s=0.05, hedge_s=0.0,
                    request_timeout_s=30.0, refresh_s=0.25)
    out = {}
    try:
        router.start()
        _wait_ready(view, 3)
        print(f"FLEET ready: 3 replicas behind {router.port} "
              f"(quorum KV over {specs})", flush=True)

        min_view = {"n": 3}
        sampling = {"on": True}

        def _sample():
            while sampling["on"]:
                min_view["n"] = min(min_view["n"], len(view.poll()))
                time.sleep(0.15)

        load_out = {}

        def _bg_load():
            load_out.update(run_http_open_loop(
                f"http://127.0.0.1:{router.port}", args.serve_requests,
                rate_rps=args.serve_rps, prompt_len=6, n_new=8, vocab=V,
                seed=500, deadline_s=15.0, timeout_s=40.0))

        sampler = threading.Thread(target=_sample, daemon=True)
        loader = threading.Thread(target=_bg_load, daemon=True)
        sampler.start()
        loader.start()
        time.sleep(1.0)          # load in flight before the wipe
        wiped_files = 0
        for f in kv_dirs[1].iterdir():
            if f.is_file():
                f.unlink()
                wiped_files += 1
        print(f"WIPE kv backend 1: {wiped_files} keys deleted mid-load",
              flush=True)
        loader.join(timeout=120.0)
        sampling["on"] = False
        sampler.join(timeout=5.0)

        # Lease beats fan out to ALL backends and quorum reads repair the
        # rest, so the wiped directory repopulates within a few beats
        # (retry loop: beat threads can be compute-starved on this box).
        repop_kv = FileKV(str(kv_dirs[1]))
        repop = 0
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            view.poll()
            repop = len(repop_kv.keys(f"serve/{FLEET}/"))
            if repop > 0:
                break
            time.sleep(0.5)
        availability = load_out.get("availability")
        out = {"availability": availability,
               "availability_floor": 1.0,
               "failed_5xx": load_out.get("failed_5xx", -1),
               "offered": args.serve_requests,
               "requests": load_out.get("requests", 0),
               "completed": load_out.get("completed", 0),
               "status_counts": load_out.get("status_counts", {}),
               "latency_p99_ms": load_out.get("latency_p99_ms"),
               "min_fleet_view": min_view["n"], "wiped_backend": 1,
               "wiped_keys": wiped_files, "repopulated_keys": repop,
               "read_repairs": rkv.counters["kvrep_read_repairs"],
               "wipes": 1}
        print(f"PHASE serve availability={availability} "
              f"5xx={load_out.get('failed_5xx')} min_view={min_view['n']} "
              f"repopulated={repop}", flush=True)
        if _serve_violations(out):
            for rep in replicas:
                print(f"== replica_{rep.rid} ==\n{rep.log()[-2000:]}")
    finally:
        try:
            router.stop()
        except Exception:
            pass
        for rep in replicas:
            rep.stop()
    return out


def _phase_bitwise() -> dict:
    """Kill-window + wipe faults on one backend, client restart mid-
    sequence, final state BITWISE equal to the numpy oracle."""
    import numpy as np

    from ps_pytorch_tpu.resilience.faults import FaultInjector, ManualClock
    from ps_pytorch_tpu.runtime.coordinator import KVStore
    from ps_pytorch_tpu.runtime.kvrep import ReplicatedKV

    spec = ("kv_backend_kill:backend=2,step=3,steps=4;"
            "kv_backend_wipe:backend=2,step=9")
    inj = FaultInjector(spec, process_index=0)
    stores = [KVStore() for _ in range(3)]
    wrapped = [inj.wrap_backend(kv, i) for i, kv in enumerate(stores)]
    clk = ManualClock()

    def client(writer: str) -> ReplicatedKV:
        return ReplicatedKV(wrapped, quorum=2, writer=writer,
                            clock=clk.time, resync_s=1.0, seed=7)

    lr, mu, size = np.float32(0.05), np.float32(0.9), 193
    rng = np.random.default_rng(23)
    p0 = rng.standard_normal(size).astype(np.float32)
    grads = [rng.standard_normal(size).astype(np.float32)
             for _ in range(12)]

    def enc(p, m, v: int) -> str:
        return f"{v}:" + base64.b64encode(
            np.concatenate([p, m]).tobytes()).decode("ascii")

    def dec(raw: str):
        v, _, b64 = raw.partition(":")
        flat = np.frombuffer(base64.b64decode(b64), dtype=np.float32)
        return flat[:size].copy(), flat[size:].copy(), int(v)

    rkv = client("c0")
    p, m = p0.copy(), np.zeros(size, np.float32)
    rkv.set("bw/state", enc(p, m, 0))
    resumed_at = -1
    for step, g in enumerate(grads):
        inj.maybe_crash(step)
        if step == 6:
            # Client restart mid-outage: a FRESH client (empty health
            # state, empty version cache) must recover the exact state
            # from a quorum read while backend 2 is still dark.
            rkv = client("c1")
            p, m, v = dec(rkv.get("bw/state"))
            assert v == step, (v, step)
            resumed_at = step
        m = mu * m + g
        p = p - lr * m
        rkv.set("bw/state", enc(p, m, step + 1))
        clk.advance(0.7)
    snap = rkv.snapshot()

    # Oracle: the same recurrence with no KV anywhere near it.
    op, om = p0.copy(), np.zeros(size, np.float32)
    for g in grads:
        om = mu * om + g
        op = op - lr * om
    reader = client("c2")
    rp, rm, rv = dec(reader.get("bw/state"))
    bitwise = (bool(np.array_equal(rp, op)) and bool(np.array_equal(rm, om))
               and rv == len(grads))

    # The wipe at step 9 is masked by quorum reads; one anti-entropy pass
    # must bring backend 2 back to key-by-key tag equality.
    reader.resync_backend(2)
    tags0 = reader.backend_tags(0)
    tag_equal = bool(tags0) and tags0 == reader.backend_tags(2)
    counters = inj.snapshot()
    out = {"bitwise_equal": bitwise, "resumed_at_step": resumed_at,
           "steps": len(grads), "resync_tag_equal": tag_equal,
           "kills": counters.get("kv_backend_kills", 0),
           "wipes": counters.get("kv_backend_wipes", 0),
           "drops": counters.get("kv_backend_drops", 0),
           "rejoins": snap.get("kvrep_rejoins", 0),
           "resyncs": snap.get("kvrep_resyncs", 0),
           "read_repairs": snap.get("kvrep_read_repairs", 0),
           "ejections": snap.get("kvrep_ejections", 0)}
    print(f"PHASE bitwise bitwise_equal={bitwise} "
          f"kills={out['kills']} wipes={out['wipes']} "
          f"rejoins={out['rejoins']} tag_equal={tag_equal}", flush=True)
    return out


def _train_violations(train: dict) -> list:
    rules = [
        ("train: every process exited 0 and finished",
         train.get("rc", -1) == 0
         and train.get("finals", 0) == train.get("procs", 3)),
        ("train: a backend was SIGKILLed and restarted empty",
         train.get("kills", 0) >= 1 and train.get("wipes", 0) >= 1),
        ("train: zero retry give-ups", train.get("giveups", -1) == 0),
        ("train: the clients rejoined and resynced the reborn backend",
         train.get("rejoins", 0) >= 1 and train.get("resyncs", 0) >= 1),
        ("train: the reborn backend is tag-equal key by key",
         train.get("resync_tag_equal") is True),
        # records written before max_steps was kept do not hold it
        ("train: every version was completed",
         train.get("max_version", 0) >= train.get("max_steps", 1)),
    ]
    return [name for name, held in rules if not held]


def _serve_violations(serve: dict) -> list:
    avail = serve.get("availability")
    rules = [
        ("serve: availability 1.00 through the wipe", avail == 1.0),
        ("serve: zero client 5xx", serve.get("failed_5xx", -1) == 0),
        # records written before the offered count was kept do not hold it
        ("serve: every offered request was sent",
         serve.get("requests", 0) >= serve.get("offered", 1)),
        ("serve: the router's fleet view never lost a replica",
         serve.get("min_fleet_view", 0) == 3),
        ("serve: a backend directory was wiped and repopulated",
         serve.get("wiped_keys", 0) > 0
         and serve.get("repopulated_keys", 0) > 0),
    ]
    return [name for name, held in rules if not held]


def verdict(result: dict) -> list:
    """The drill's whole pass rule: the invariants ``result`` violates,
    empty when it passes. A backend must really have been killed AND wiped,
    and the quorum must have masked it end to end: training with zero
    give-ups and a resynced backend, serving with zero 5xx, and a resume
    through a quorum read that is bitwise the oracle's."""
    kvrep = result.get("kvrep", {})
    bitw = kvrep.get("bitwise", {})
    rules = [
        (f"{key} >= 1", kvrep.get(key, 0) >= 1)
        for key in ("backend_kills", "backend_wipes", "rejoins", "resyncs")
    ] + [
        ("the retry plane never gave up",
         result.get("counters", {}).get("kv_giveups", -1) == 0),
        ("bitwise: the resumed recurrence equals the oracle bit for bit",
         result.get("bitwise_equal") is True
         and bitw.get("bitwise_equal") is True),
        ("bitwise: the fresh client resumed mid-outage from a quorum read",
         bitw.get("resumed_at_step", -1) == 6),
        ("bitwise: kill and wipe fired, the client rejoined and resynced "
         "to tag equality",
         bitw.get("kills", 0) >= 1 and bitw.get("wipes", 0) >= 1
         and bitw.get("rejoins", 0) >= 1 and bitw.get("resyncs", 0) >= 1
         and bitw.get("resync_tag_equal") is True),
    ]
    return ([name for name, held in rules if not held]
            + _train_violations(kvrep.get("train", {}))
            + _serve_violations(kvrep.get("serve", {})))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--phase", default="",
                    help="internal: worker phase (train)")
    ap.add_argument("--train-dir", default="")
    ap.add_argument("--kv-replicas", default="")
    ap.add_argument("--resync-s", type=float, default=2.0)
    # Long enough that the kill + probation + rejoin + resync cycle runs
    # to completion INSIDE the run (versions advance ~2/s on this mesh;
    # the kill lands once step 2 is logged and the window is ~4 s).
    ap.add_argument("--max-steps", type=int, default=24)
    ap.add_argument("--kill-window-s", type=float, default=4.0)
    # Sized for the drill box (1 CPU, 3 replica processes): the phase
    # proves wipe-masking, not throughput, so the open loop stays well
    # under fleet capacity.
    ap.add_argument("--serve-requests", type=int, default=60)
    ap.add_argument("--serve-rps", type=float, default=8.0)
    ap.add_argument("--out", default="RESILIENCE_r17.json")
    ap.add_argument("--run-dir", default="/tmp/kvrep_drill")
    args = ap.parse_args(argv)

    if args.phase == "train":
        _worker_train(args)
        return 0

    base = pathlib.Path(args.run_dir)
    base.mkdir(parents=True, exist_ok=True)

    train = _phase_train(args, base)
    serve = _phase_serve(args, base)
    bitwise = _phase_bitwise()

    art = {
        "round": 17,
        "platform": "cpu",
        "scenario": "kv_backend_kill_wipe_quorum: elastic_train + "
                    "fleet_serve + bitwise_resume",
        "processes": 3,
        "backends": 3,
        "bitwise_equal": bool(bitwise["bitwise_equal"]),
        "counters": {
            "kv_giveups": int(train["giveups"]),
            "kv_backend_kills": int(train["kills"] + bitwise["kills"]),
            "kv_backend_wipes": int(train["wipes"] + serve["wipes"]
                                    + bitwise["wipes"]),
        },
        "kvrep": {
            "backend_kills": int(train["kills"] + bitwise["kills"]),
            "backend_wipes": int(train["wipes"] + serve["wipes"]
                                 + bitwise["wipes"]),
            "rejoins": int(train["rejoins"] + bitwise["rejoins"]),
            "resyncs": int(train["resyncs"] + bitwise["resyncs"]),
            "train": train,
            "serve": serve,
            "bitwise": bitwise,
        },
    }
    violations = verdict(art)
    ok = art["ok"] = not violations
    for v in violations:
        print(f"VIOLATED {v}")
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"WROTE {args.out} ok={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
