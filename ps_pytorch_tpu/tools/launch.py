#!/usr/bin/env python
"""Multi-host job launcher / fleet control.

The TPU-native replacement for the reference's launch stack:

- ``launch``  — spawn one trainer process per host, wired together through
  the ``jax.distributed`` env contract (``parallel/dist.py``). Replaces
  ``run_pytorch.sh``'s ``mpirun -n P+1 --hostfile hosts_address``
  (``run_pytorch.sh:1-16``): there is no extra master rank — every process is
  a peer driving the same SPMD step.
- ``status``  — liveness + last progress line per process (the reference
  greps ``ps aux`` over ssh, ``tools/pytorch_ec2.py:304-306``).
- ``kill``    — terminate the fleet (``tools/killall.sh``,
  ``pytorch_ec2.py:821-852`` kill_python/kill_all_python).

Host modes:
- ``--simulate N``: N local processes, each given ``--devices-per-host``
  fake CPU devices through ``JAX_PLATFORMS=cpu`` + ``JAX_NUM_CPU_DEVICES``
  in its environment — the standard JAX multi-host test rig; how CI
  exercises the full DCN bootstrap + sharded-input + KV-control path on one
  machine.
- ``--hostfile FILE``: one host per line (the reference's ``hosts_address``
  format); processes are started over ``ssh`` (TPU pod VMs, where this
  script runs on every worker VM against its local chips).

Run artifacts land in ``--run-dir``: ``proc_<i>.log``, ``procs.json``.
"""

import argparse
import json
import os
import shlex
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional

from ps_pytorch_tpu.parallel import dist

PROCS_FILE = "procs.json"


def free_port() -> int:
    """A TCP port free on this host now (for ``--simulate`` coordinators)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _read_hostfile(path: str) -> List[str]:
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.split("#")[0].strip()
            if line:
                hosts.append(line.split()[0])
    if not hosts:
        raise ValueError(f"hostfile {path} lists no hosts")
    return hosts


def _env_for(rank: int, n: int, coordinator: str, platform: str,
             devices_per_host: int) -> dict:
    env = dict(os.environ)
    env[dist.ENV_COORD] = coordinator
    env[dist.ENV_NPROC] = str(n)
    env[dist.ENV_PID] = str(rank)
    if platform:
        # Plain JAX env vars, read by the child at import.
        env["JAX_PLATFORMS"] = platform
        if platform == "cpu":
            env["JAX_NUM_CPU_DEVICES"] = str(devices_per_host)
    return env


def cmd_launch(args, train_argv: List[str]) -> int:
    os.makedirs(args.run_dir, exist_ok=True)
    if args.hostfile:
        hosts: Optional[List[str]] = _read_hostfile(args.hostfile)
        n = len(hosts)
        coordinator = f"{hosts[0]}:{args.port}"
    else:
        hosts = None
        n = args.simulate
        coordinator = f"127.0.0.1:{args.port}"
    entry = args.entry
    records = []
    for rank in range(n):
        log_path = os.path.join(args.run_dir, f"proc_{rank}.log")
        cmd = [sys.executable, entry] + train_argv
        if hosts is None:
            env = _env_for(rank, n, coordinator, args.platform or "cpu",
                           args.devices_per_host)
            with open(log_path, "w") as log:
                # The child inherits its own fd; the parent's copy is closed
                # immediately (round-1 advisor: fd leak across large fleets).
                p = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                     env=env, cwd=args.cwd or None)
            records.append({"rank": rank, "host": "local", "pid": p.pid,
                            "log": log_path})
        else:
            # ssh mode: export the env contract inline; the remote side runs
            # against its real local chips (platform override not forced).
            # `echo REMOTE_PID $$` + `exec` publishes the REMOTE python's own
            # pid into the locally captured log — `p.pid` here is only the
            # local ssh client, and signalling that number on the remote host
            # would hit an arbitrary process (round-1 advisor, medium).
            env_args = " ".join(
                f"{k}={shlex.quote(v)}" for k, v in {
                    dist.ENV_COORD: coordinator, dist.ENV_NPROC: str(n),
                    dist.ENV_PID: str(rank),
                }.items())
            remote = f"cd {shlex.quote(args.cwd or '.')} && " \
                     f"echo REMOTE_PID $$ && exec env {env_args} " \
                     f"{shlex.quote(sys.executable)} {shlex.quote(entry)} " \
                     + " ".join(shlex.quote(a) for a in train_argv)
            with open(log_path, "w") as log:
                p = subprocess.Popen(["ssh", "-o", "BatchMode=yes",
                                      hosts[rank], remote],
                                     stdout=log, stderr=subprocess.STDOUT)
            records.append({"rank": rank, "host": hosts[rank], "pid": p.pid,
                            "log": log_path, "entry": entry})
    with open(os.path.join(args.run_dir, PROCS_FILE), "w") as f:
        json.dump({"coordinator": coordinator, "n": n,
                   "hostfile": args.hostfile, "procs": records}, f, indent=1)
    print(f"LAUNCHED {n} processes (coordinator {coordinator}) -> {args.run_dir}")
    if args.wait:
        return cmd_wait(args)
    return 0


def _load_procs(run_dir: str) -> dict:
    with open(os.path.join(run_dir, PROCS_FILE)) as f:
        return json.load(f)


def proc_logs(run_dir) -> List[str]:
    """Every launched process's log so far, by rank ([] before a launch)."""
    try:
        procs = _load_procs(str(run_dir))["procs"]
    except (FileNotFoundError, ValueError):   # not launched yet, or mid-write
        return []
    out = []
    for rec in procs:
        with open(rec["log"]) as f:
            out.append(f.read())
    return out


def _alive(pid: int) -> bool:
    # Reap THIS pid if it is our exited child — otherwise it lingers as a
    # zombie and os.kill(pid, 0) keeps reporting it alive. Never waitpid(-1):
    # that steals exit statuses from unrelated children when launch is used
    # as a library (round-1 advisor).
    try:
        os.waitpid(pid, os.WNOHANG)
    except ChildProcessError:
        pass  # not our child (or already reaped) — /proc check below decides
    try:
        os.kill(pid, 0)
    except (ProcessLookupError, PermissionError):
        return False
    try:  # zombie (exited, unreaped by some other parent) counts as dead
        with open(f"/proc/{pid}/stat") as f:
            state = f.read().rsplit(") ", 1)[1].split()[0]
        return state != "Z"
    except (OSError, IndexError):
        return True


def _last_progress_line(log: str) -> str:
    try:
        with open(log, "rb") as f:
            tail = f.read()[-4096:].decode(errors="replace").splitlines()
        for line in reversed(tail):
            if line.strip():
                return line.strip()[-120:]
    except OSError:
        pass
    return "<no output>"


def cmd_status(args) -> int:
    meta = _load_procs(args.run_dir)
    n_alive = 0
    for r in meta["procs"]:
        alive = _alive(r["pid"])
        n_alive += alive
        print(f"rank {r['rank']} host {r['host']} pid {r['pid']} "
              f"{'ALIVE' if alive else 'EXITED'}  {_last_progress_line(r['log'])}")
    print(f"STATUS {n_alive}/{meta['n']} alive")
    return 0 if n_alive == meta["n"] else 1


def cmd_wait(args) -> int:
    """Block until every process exits; propagate the worst exit status by
    checking the logs' final lines for a FINAL marker."""
    meta = _load_procs(args.run_dir)
    deadline = time.monotonic() + args.timeout if args.timeout else None
    while True:
        if all(not _alive(r["pid"]) for r in meta["procs"]):
            break
        if deadline and time.monotonic() > deadline:
            print("WAIT timeout; killing fleet", file=sys.stderr)
            cmd_kill(args)
            return 2
        time.sleep(0.5)

    def _has_final(path: str) -> bool:
        with open(path) as f:
            return "FINAL" in f.read()

    ok = all(_has_final(r["log"]) for r in meta["procs"])
    print(f"DONE ok={ok}")
    return 0 if ok else 1


def _remote_pid(record: dict) -> Optional[int]:
    """The REMOTE trainer's own pid, parsed from the 'REMOTE_PID <n>' line
    its launch wrapper echoed into the locally captured log."""
    try:
        with open(record["log"]) as f:
            for line in f:
                if line.startswith("REMOTE_PID "):
                    return int(line.split()[1])
    except (OSError, ValueError, IndexError):
        pass
    return None


def cmd_kill(args) -> int:
    meta = _load_procs(args.run_dir)
    for sig in (signal.SIGTERM, signal.SIGKILL):
        any_alive = False
        for r in meta["procs"]:
            # r["pid"] is the liveness proxy either way: in ssh mode it is
            # the local ssh client, which exits when the remote command does
            # — so remote fleets get the same grace-then-SIGKILL escalation
            # as local ones instead of a single fire-and-forget SIGTERM.
            if not _alive(r["pid"]):
                continue
            any_alive = True
            if r["host"] not in ("local",):
                # Signal the REMOTE trainer's own pid (parsed from its log);
                # fall back to pkill by entry-script match — the semantic
                # equivalent of the reference fleet tool's kill-all-python,
                # scoped to this job's entry (tools/pytorch_ec2.py:821-852).
                rpid = _remote_pid(r)
                if rpid is not None:
                    cmd = f"kill -{int(sig)} {rpid}"
                else:
                    cmd = f"pkill -{int(sig)} -f {shlex.quote(r.get('entry', 'train.py'))}"
                subprocess.run(["ssh", "-o", "BatchMode=yes", r["host"], cmd],
                               capture_output=True)
            else:
                try:
                    os.kill(r["pid"], sig)
                except ProcessLookupError:
                    pass
        if not any_alive:
            break
        time.sleep(args.grace)
    print("KILLED")
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd", required=True)

    pl = sub.add_parser("launch", help="start a multi-host training job")
    pl.add_argument("--run-dir", default="./launch_run")
    pl.add_argument("--hostfile", default="",
                    help="one host per line (hosts_address format); default: simulate locally")
    pl.add_argument("--simulate", type=int, default=2,
                    help="local process count when no hostfile is given")
    pl.add_argument("--devices-per-host", type=int, default=4)
    pl.add_argument("--platform", default="",
                    help="force a JAX platform on the children (simulate => cpu)")
    pl.add_argument("--port", type=int, default=12355)
    pl.add_argument("--entry", default="train.py")
    pl.add_argument("--cwd", default="")
    pl.add_argument("--wait", action="store_true")
    pl.add_argument("--timeout", type=float, default=0.0)
    pl.add_argument("--grace", type=float, default=3.0)

    for name in ("status", "wait", "kill"):
        ps = sub.add_parser(name)
        ps.add_argument("--run-dir", default="./launch_run")
        ps.add_argument("--timeout", type=float, default=0.0)
        ps.add_argument("--grace", type=float, default=3.0)
    return p


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--" in argv:
        i = argv.index("--")
        argv, train_argv = argv[:i], argv[i + 1:]
    else:
        train_argv = []
    args = build_parser().parse_args(argv)
    if args.cmd == "launch":
        return cmd_launch(args, train_argv)
    if args.cmd == "status":
        return cmd_status(args)
    if args.cmd == "wait":
        return cmd_wait(args)
    if args.cmd == "kill":
        return cmd_kill(args)
    return 2


if __name__ == "__main__":
    sys.exit(main())
