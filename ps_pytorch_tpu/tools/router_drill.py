#!/usr/bin/env python
"""Fleet-serving chaos drill -> RESILIENCE_r15.json.

The acceptance drill for the serving router (ps_pytorch_tpu/serving/
router.py), run over REAL serve.py processes on real sockets, discovered
through a real directory-backed coordination KV (FileKV) — not the
in-process fixtures the unit tests use. Three phases, one router:

- **kill**: 3 replicas serve a tiny LM checkpoint behind the router; the
  victim arms ``replica_kill:served=N`` (``--fault-spec``) and SIGKILLs
  itself mid-Poisson-load. The router must absorb the death — stale
  lease + connection-error ejection + failover retries — with ZERO
  client-visible 5xx and availability at or above the floor.
- **reload**: the victim is restarted (same replica id, bumped
  incarnation), a step-2 checkpoint is committed, and
  ``Router.roll_reload`` drains -> reloads -> resumes each replica in
  turn while open-loop load keeps flowing: zero failed requests, and
  every replica's ``/healthz`` must show ``model_step`` advanced.
- **hedge**: one replica is pulsed with SIGSTOP/SIGCONT (a genuinely
  stalled backend, no synthetic sleeps) while the same load runs twice —
  hedging off, then hedging on. Hedged dispatch must lower routed p99
  (the serving-time ``num_aggregate`` analogue: a backup request beats a
  straggler exactly like a backup worker beats a slow gradient).

Bitwise evidence: the same seeded request routed repeatedly (landing on
different replicas) must return identical tokens — cross-replica decode
determinism, the serving twin of the trainers' bitwise-equality drills.

The drill is judged by its own exit code: :func:`verdict` holds the whole
pass rule over the result it writes. The hedge phase compares two of the
drill's own runs under a stall it injects; no rule reads a clock against a
budget from outside.

Usage:
    python ps_pytorch_tpu/tools/router_drill.py --out RESILIENCE_r15.json
"""

import argparse
import json
import os
import pathlib
import shutil
import signal
import subprocess
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parent.parent.parent
if str(REPO) not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, str(REPO))

V, D, L, H, S = 61, 32, 2, 2, 96     # tests/test_serving.py geometry
FLEET = "drill"
AVAILABILITY_FLOOR = 0.99


def _lm_cfg(train_dir: str):
    from ps_pytorch_tpu.config import TrainConfig
    return TrainConfig(network="TransformerLM", lm_vocab=V, lm_d_model=D,
                       lm_layers=L, lm_heads=H, lm_seq_len=S,
                       train_dir=train_dir)


def _write_checkpoint(train_dir: str, step: int, seed: int) -> None:
    """Commit a tiny TransformerLM checkpoint; different seeds produce
    different params so a reload is observable."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_template

    cfg = _lm_cfg(train_dir)
    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          max_seq_len=S)
    params = model.init(jax.random.key(seed),
                        jnp.zeros((1, 8), jnp.int32),
                        positions=jnp.arange(8))["params"]
    template = build_lm_template(cfg)
    ckpt.save_checkpoint(train_dir, step, template.replace(params=params),
                         config_json=cfg.to_json())


class Replica:
    """One serve.py subprocess, its log, and its KV identity."""

    def __init__(self, rid: int, base: pathlib.Path, train_dir: str,
                 kv_dir: str, fault_spec: str = ""):
        self.rid = rid
        self.train_dir = train_dir
        self.kv_dir = kv_dir
        self.fault_spec = fault_spec
        self.log_path = base / f"replica_{rid}.log"
        self.proc: subprocess.Popen = None

    def start(self) -> None:
        cmd = [sys.executable, str(REPO / "serve.py"),
               "--train-dir", self.train_dir,
               "--serve-port", "0", "--serve-host", "127.0.0.1",
               "--serve-slots", "4", "--serve-max-queue", "64",
               "--serve-reload-s", "0",
               "--serve-kv-dir", self.kv_dir,
               "--serve-fleet", FLEET,
               "--serve-replica-id", str(self.rid),
               "--serve-deadline-s", "20"]
        if self.fault_spec:
            cmd += ["--fault-spec", self.fault_spec]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(cmd, stdout=log, stderr=log,
                                     cwd=str(REPO), env=env)

    def log(self) -> str:
        return self.log_path.read_text() if self.log_path.exists() else ""

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)


def _wait_ready(view, n: int, timeout_s: float = 120.0) -> list:
    """Block until ``n`` backends are health-gated ready (startup includes
    the replicas' JIT warmup, hence the generous timeout)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        ready = view.poll()
        if len(ready) >= n:
            return ready
        time.sleep(0.25)
    raise TimeoutError(f"only {len(view.poll())} of {n} replicas ready")


def _healthz(url: str) -> dict:
    import urllib.request
    with urllib.request.urlopen(url + "/healthz", timeout=5.0) as r:
        return json.loads(r.read())


def _bitwise_probe(router_url: str, tries: int = 4) -> bool:
    """Same seeded request routed ``tries`` times (round-robin spreads it
    across replicas) must decode identical tokens."""
    from ps_pytorch_tpu.serving.loadgen import http_post_generate
    body = {"tokens": [3, 1, 4, 1, 5], "n_new": 12, "seed": 42,
            "temperature": 0.8, "top_k": 7, "deadline_s": 15}
    outs = []
    for _ in range(tries):
        code, resp = http_post_generate(router_url, body, timeout_s=30.0)
        if code != 200:
            return False
        outs.append(resp.get("tokens"))
    return all(t == outs[0] for t in outs) and outs[0] is not None


def verdict(result: dict) -> list:
    """The drill's whole pass rule: the invariants ``result`` violates,
    empty when it passes. A replica must really have died under load with
    no client the wiser, the rolling reload must fail no request and leave
    every replica on the new step, and hedging must beat no hedging under
    the injected straggler."""
    router = result.get("router", {})
    kill, reload_, hedge = (router.get(k, {})
                            for k in ("kill", "reload", "hedge"))
    n = router.get("replicas", 3)
    avail, ratio = kill.get("availability"), hedge.get("p99_ratio")
    rules = [
        ("bitwise: one seeded request returns the same tokens from any "
         "replica", result.get("bitwise_equal") is True),
        ("kill: a replica was SIGKILLed under load",
         kill.get("replica_kills", 0) >= 1),
        ("kill: zero client 5xx", kill.get("failed_5xx", -1) == 0),
        ("kill: availability at or above its floor",
         avail is not None and avail >= AVAILABILITY_FLOOR),
        ("reload: requests flowed during the roll and none failed",
         reload_.get("failed_5xx", -1) == 0
         and reload_.get("requests", 0) > 0),
        ("reload: at least 3 replicas, every one of them rolled",
         n >= 3 and reload_.get("replicas_rolled", 0) == n),
        ("reload: every replica serves the new model step",
         reload_.get("model_step_advanced") is True),
        ("hedge: hedged p99 below un-hedged p99 under the pulsed straggler",
         ratio is not None and ratio < 1.0),
        ("hedge: at least one hedge fired", hedge.get("hedges", 0) >= 1),
        # records written before this key existed do not hold it
        ("hedge: zero client 5xx with hedging on",
         hedge.get("failed_5xx", 0) == 0),
    ]
    return [name for name, held in rules if not held]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="RESILIENCE_r15.json")
    ap.add_argument("--run-dir", default="/tmp/router_drill")
    ap.add_argument("--replicas", type=int, default=3)
    # Victim dies after serving this many requests — far enough in that
    # it holds in-flight work when the SIGKILL lands.
    ap.add_argument("--kill-served", type=int, default=8)
    ap.add_argument("--kill-requests", type=int, default=90)
    ap.add_argument("--kill-rps", type=float, default=18.0)
    ap.add_argument("--reload-requests", type=int, default=90)
    ap.add_argument("--reload-rps", type=float, default=12.0)
    ap.add_argument("--hedge-requests", type=int, default=60)
    ap.add_argument("--hedge-rps", type=float, default=10.0)
    ap.add_argument("--hedge-s", type=float, default=0.15)
    args = ap.parse_args(argv)

    from ps_pytorch_tpu.runtime.coordinator import FileKV
    from ps_pytorch_tpu.serving.loadgen import run_http_open_loop
    from ps_pytorch_tpu.serving.router import FleetView, Router
    from ps_pytorch_tpu.telemetry.registry import (
        Registry, declare_router_metrics,
    )

    base = pathlib.Path(args.run_dir)
    shutil.rmtree(base, ignore_errors=True)
    base.mkdir(parents=True)
    train_dir = str(base / "ckpt")
    kv_dir = str(base / "kv")
    _write_checkpoint(train_dir, 1, seed=0)

    n = args.replicas
    victim_id = n - 1
    replicas = {}
    for rid in range(n):
        fault = (f"replica_kill:served={args.kill_served},r={victim_id}"
                 if rid == victim_id else "")
        rep = Replica(rid, base, train_dir, kv_dir, fault_spec=fault)
        rep.start()
        replicas[rid] = rep

    kv = FileKV(kv_dir)
    view = FleetView(kv, FLEET, lease_timeout_s=3.0, probe_timeout_s=0.5)
    registry = declare_router_metrics(Registry())
    router = Router(view, registry=registry, retries=3,
                    backoff_s=0.05, hedge_s=0.0, request_timeout_s=30.0,
                    refresh_s=0.25)
    art = {"round": 15, "platform": "cpu",
           "scenario": "router_replica_kill_failover + rolling_reload + "
                       "hedged_tail_latency",
           "processes": n, "bitwise_equal": False,
           "counters": {"replica_kills": 0},
           "router": {"replicas": n}}
    try:
        router.start()
        _wait_ready(view, n)
        print(f"FLEET ready: {n} replicas behind {router.port}", flush=True)

        # -- bitwise: same seed through the router, any replica ----------
        bitwise = _bitwise_probe(f"http://127.0.0.1:{router.port}")
        art["bitwise_equal"] = bitwise
        print(f"BITWISE cross-replica determinism: {bitwise}", flush=True)

        # -- phase A: SIGKILL a replica under open-loop load -------------
        stats_kill = run_http_open_loop(
            f"http://127.0.0.1:{router.port}", args.kill_requests,
            rate_rps=args.kill_rps, prompt_len=6, n_new=8, vocab=V,
            seed=100, deadline_s=15.0, timeout_s=40.0)
        time.sleep(0.5)
        victim = replicas[victim_id]
        victim_rc = victim.proc.poll()
        killed = (victim_rc == -signal.SIGKILL
                  or "FAULT replica_kill" in victim.log())
        art["counters"]["replica_kills"] = int(killed)
        art["router"]["kill"] = {
            "replica_kills": int(killed),
            "victim": victim_id, "victim_rc": victim_rc,
            "availability": stats_kill["availability"],
            "availability_floor": AVAILABILITY_FLOOR,
            "failed_5xx": stats_kill["failed_5xx"],
            "requests": stats_kill["requests"],
            "completed": stats_kill["completed"],
            "status_counts": stats_kill["status_counts"],
            "retries": router.counters["retries"],
            "latency_p99_ms": stats_kill["latency_p99_ms"],
        }
        print(f"PHASE kill killed={killed} "
              f"availability={stats_kill['availability']:.4f} "
              f"5xx={stats_kill['failed_5xx']} "
              f"retries={router.counters['retries']}", flush=True)

        # -- phase B: restart victim, commit step 2, roll the fleet ------
        restarted = Replica(victim_id, base, train_dir, kv_dir)
        restarted.start()
        replicas[victim_id] = restarted
        _wait_ready(view, n)
        _write_checkpoint(train_dir, 2, seed=1)
        load_out = {}

        def _bg_load():
            load_out.update(run_http_open_loop(
                f"http://127.0.0.1:{router.port}", args.reload_requests,
                rate_rps=args.reload_rps, prompt_len=6, n_new=8, vocab=V,
                seed=200, deadline_s=15.0, timeout_s=40.0))

        bg = threading.Thread(target=_bg_load, daemon=True)
        bg.start()
        time.sleep(0.5)          # load in flight before the roll starts
        roll = router.roll_reload(settle_timeout_s=30.0)
        bg.join(timeout=120.0)
        steps = {}
        for b in view.poll():
            steps[b.id] = _healthz(b.url).get("model_step")
        advanced = len(steps) == n and all(s == 2 for s in steps.values())
        art["router"]["reload"] = {
            "replicas_rolled": sum(r.get("ok", False) for r in roll),
            "model_step_advanced": advanced,
            "steps_after": steps, "from_step": 1, "to_step": 2,
            "requests": load_out.get("requests", 0),
            "completed": load_out.get("completed", 0),
            "failed_5xx": load_out.get("failed_5xx", -1),
            "status_counts": load_out.get("status_counts", {}),
            "results": roll,
        }
        print(f"PHASE reload rolled={roll} steps={steps} "
              f"load_5xx={load_out.get('failed_5xx')}", flush=True)

        # -- phase C: hedged vs un-hedged p99 under a pulsing straggler --
        stall = {"stop": False}
        straggler = replicas[0].proc

        def _pulse():
            while not stall["stop"]:
                if straggler.poll() is not None:
                    return
                os.kill(straggler.pid, signal.SIGSTOP)
                time.sleep(0.4)
                os.kill(straggler.pid, signal.SIGCONT)
                time.sleep(0.6)

        pulser = threading.Thread(target=_pulse, daemon=True)
        pulser.start()
        try:
            router.hedge_s = 0.0
            no_hedge = run_http_open_loop(
                f"http://127.0.0.1:{router.port}", args.hedge_requests,
                rate_rps=args.hedge_rps, prompt_len=6, n_new=8, vocab=V,
                seed=300, deadline_s=15.0, timeout_s=40.0)
            hedges_before = router.counters["hedges"]
            router.hedge_s = args.hedge_s
            hedged = run_http_open_loop(
                f"http://127.0.0.1:{router.port}", args.hedge_requests,
                rate_rps=args.hedge_rps, prompt_len=6, n_new=8, vocab=V,
                seed=300, deadline_s=15.0, timeout_s=40.0)
        finally:
            stall["stop"] = True
            pulser.join(timeout=5.0)
            if straggler.poll() is None:
                os.kill(straggler.pid, signal.SIGCONT)
        hedges = router.counters["hedges"] - hedges_before
        p99_no = no_hedge["latency_p99_ms"]
        p99_yes = hedged["latency_p99_ms"]
        ratio = (p99_yes / p99_no
                 if p99_no and p99_yes and p99_no > 0 else None)
        art["router"]["hedge"] = {
            "hedge_s": args.hedge_s,
            "p99_no_hedge_ms": p99_no, "p99_hedge_ms": p99_yes,
            "p99_ratio": None if ratio is None else round(ratio, 4),
            "hedges": hedges,
            "failed_5xx": hedged["failed_5xx"],
            "hedge_wins": router.counters["hedge_wins"],
            "hedge_cancelled": router.counters["hedge_cancelled"],
            "no_hedge_availability": no_hedge["availability"],
            "hedge_availability": hedged["availability"],
        }
        print(f"PHASE hedge p99 {p99_no}ms -> {p99_yes}ms "
              f"ratio={ratio} hedges={hedges}", flush=True)

        art["counters"].update(
            {f"router_{k}": v for k, v in router.counters.items()})
        art["counters"]["backend_ejections"] = view.ejections
    finally:
        try:
            router.stop()
        except Exception:
            pass
        for rep in replicas.values():
            rep.stop()
    violations = verdict(art)
    art["ok"] = not violations
    for v in violations:
        print(f"VIOLATED {v}")
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"WROTE {args.out} ok={art['ok']}")
    if not art["ok"]:
        for rid, rep in replicas.items():
            print(f"== replica_{rid} ==\n{rep.log()[-2000:]}")
    return 0 if art["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
