"""Tools-layer tests: analyze speedup math, LR sweep harness, and the
multi-host launcher driving a REAL 2-process x 4-fake-device distributed run
(the CI stand-in for a TPU pod, SURVEY §4)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import free_port

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------- analyze --

def _write_jsonl(path, step_times, host=0):
    with open(path, "w") as f:
        for i, t in enumerate(step_times, start=1):
            f.write(json.dumps({"step": i, "epoch": 0, "loss": 1.0, "acc": 0.5,
                                "participating": 8, "step_time": t,
                                "data_time": 0.001}) + "\n")


def test_analyze_speedups(tmp_path):
    from ps_pytorch_tpu.tools.analyze import analyze, to_markdown

    # Baseline "1": 1.0 s/step. Run "8", two hosts: slowest 0.25, fastest 0.2.
    _write_jsonl(tmp_path / "n1.jsonl", [9.0, 1.0, 1.0, 1.0])  # first skipped
    _write_jsonl(tmp_path / "n8_h0.jsonl", [9.0, 0.25, 0.25, 0.25])
    _write_jsonl(tmp_path / "n8_h1.jsonl", [9.0, 0.20, 0.20, 0.20])
    rows = analyze({"1": [str(tmp_path / "n1.jsonl")],
                    "8": [str(tmp_path / "n8_h0.jsonl"),
                          str(tmp_path / "n8_h1.jsonl")]})
    by = {r["run"]: r for r in rows}
    assert by["1"]["speedup_normal"] == 1.0
    # normal = vs slowest host (notebook max-per-step), ideal = vs fastest.
    assert by["8"]["speedup_normal"] == pytest.approx(1.0 / 0.25)
    assert by["8"]["speedup_ideal"] == pytest.approx(1.0 / 0.20)
    md = to_markdown(rows)
    assert "| 8 |" in md and "4.00x" in md


def test_analyze_parses_human_lines(tmp_path):
    from ps_pytorch_tpu.runtime.metrics import format_line
    from ps_pytorch_tpu.tools.analyze import per_step_times

    log = tmp_path / "worker.log"
    with open(log, "w") as f:
        f.write("noise line\n")
        for i in range(1, 4):
            f.write(format_line(i, 0, 1.0, 0.5, 8, 0.5, 0.01) + "\n")
    s = per_step_times([str(log)], skip_first=1)
    assert s["steps"] == 2 and s["normal"] == pytest.approx(0.5)


def test_analyze_wire_summary_and_cli(tmp_path, capsys):
    """wire mode: per-stage totals, per-bucket breakdown, overlap fractions
    (1 - wall/serial), from both Tracer span JSONL and Chrome trace input."""
    from ps_pytorch_tpu.tools import analyze

    spans = [
        {"name": "wire_publish", "t0": 0.0, "dur": 0.5, "tid": 1},
        {"name": "wire_encode", "t0": 0.0, "dur": 0.3, "tid": 2,
         "args": {"bucket": 0, "leaves": 2}},
        {"name": "wire_put", "t0": 0.3, "dur": 0.3, "tid": 2,
         "args": {"bucket": 0, "bytes": 1000}},
        {"name": "wire_encode", "t0": 0.1, "dur": 0.2, "tid": 3,
         "args": {"bucket": 1, "leaves": 1}},
        {"name": "wire_put", "t0": 0.3, "dur": 0.2, "tid": 3,
         "args": {"bucket": 1, "bytes": 500}},
        {"name": "wire_read", "t0": 1.0, "dur": 0.4, "tid": 1},
        {"name": "wire_decode", "t0": 1.0, "dur": 0.3, "tid": 2,
         "args": {"bucket": 0, "leaves": 2}},
        {"name": "wire_decode", "t0": 1.0, "dur": 0.3, "tid": 3,
         "args": {"bucket": 1, "leaves": 1}},
        {"name": "step", "t0": 0.0, "dur": 2.0, "tid": 1},  # non-wire: ignored
    ]
    p = tmp_path / "spans.jsonl"
    p.write_text("\n".join(json.dumps(s) for s in spans))
    summary = analyze.wire_summary(analyze.read_span_events(str(p)))
    # publish wall 0.5 s vs encode+put serial 1.0 s -> half the work hidden.
    assert summary["publish_overlap_fraction"] == pytest.approx(0.5)
    # read wall 0.4 s vs decode serial 0.6 s.
    assert summary["read_overlap_fraction"] == pytest.approx(0.3333)
    assert [b["bucket"] for b in summary["buckets"]] == [0, 1]
    assert summary["buckets"][0]["bytes"] == 1000
    assert summary["stages"]["wire_put"]["bytes"] == 1500
    assert "step" not in summary["stages"]
    # Chrome-trace input (ts/dur in µs) parses to the same events.
    chrome = tmp_path / "trace.json"
    chrome.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": s["name"], "ts": s["t0"] * 1e6,
         "dur": s["dur"] * 1e6, "pid": 0, "tid": s["tid"],
         "args": s.get("args", {})} for s in spans]}))
    assert (analyze.wire_summary(analyze.read_span_events(str(chrome)))
            == summary)
    # Blocking wire (no sub-spans) -> fractions read n/a, not 0 or a crash.
    blk = tmp_path / "blocking.jsonl"
    blk.write_text(json.dumps({"name": "wire_publish", "t0": 0.0,
                               "dur": 0.5, "tid": 1}))
    assert (analyze.wire_summary(analyze.read_span_events(str(blk)))
            ["publish_overlap_fraction"] is None)

    from ps_pytorch_tpu.tools.analyze import main as analyze_main
    assert analyze_main(["wire", str(p)]) == 0
    out = capsys.readouterr().out
    assert "publish overlap fraction: 0.5000" in out
    assert "| wire_put | 2 |" in out


def test_analyze_codec_summary_and_cli(tmp_path, capsys):
    """codec mode: per-bucket raw-vs-wire byte accounting from the
    bytes/bytes_raw args transport stamps on wire_encode spans."""
    from ps_pytorch_tpu.tools import analyze

    spans = [
        {"name": "wire_publish", "t0": 0.0, "dur": 0.5,
         "args": {"bytes": 1500, "bytes_raw": 6000}},
        {"name": "wire_encode", "t0": 0.0, "dur": 0.3,
         "args": {"bucket": 0, "bytes": 1000, "bytes_raw": 4000}},
        {"name": "wire_encode", "t0": 0.1, "dur": 0.2,
         "args": {"bucket": 1, "bytes": 500, "bytes_raw": 2000}},
        {"name": "wire_put", "t0": 0.3, "dur": 0.3,
         "args": {"bucket": 0, "bytes": 1000}},   # put spans: not counted
    ]
    p = tmp_path / "spans.jsonl"
    p.write_text("\n".join(json.dumps(s) for s in spans))
    s = analyze.codec_summary(analyze.read_span_events(str(p)))
    assert [b["bucket"] for b in s["buckets"]] == [0, 1]
    assert s["buckets"][0]["ratio"] == pytest.approx(4.0)
    assert s["total_bytes"] == 1500 and s["total_bytes_raw"] == 6000
    assert s["total_ratio"] == pytest.approx(4.0)
    assert s["publish"]["count"] == 1
    # Blocking wire: no bucketed encode spans -> publish totals carry it.
    blk = tmp_path / "blocking.jsonl"
    blk.write_text(json.dumps(spans[0]))
    s2 = analyze.codec_summary(analyze.read_span_events(str(blk)))
    assert s2["buckets"] == [] and s2["total_ratio"] == pytest.approx(4.0)

    from ps_pytorch_tpu.tools.analyze import main as analyze_main
    assert analyze_main(["codec", str(p)]) == 0
    out = capsys.readouterr().out
    assert "| 0 | 0.300000 s | 4000 | 1000 | 4.000x |" in out
    assert "total: 6000 raw -> 1500 on wire (4.000x)" in out


# ------------------------------------------------------------------ sweep --

TRAIN_ARGS = ["--network", "LeNet", "--dataset", "synthetic_mnist",
              "--batch-size", "64", "--eval-freq", "0", "--resume", "false"]
CPU_ENV = {"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1"}


def test_sweep_trial_and_best(tmp_path):
    from ps_pytorch_tpu.tools.sweep import run_trial

    r = run_trial(0.05, probe_step=3, train_argv=TRAIN_ARGS,
                  entry=str(REPO / "train.py"), avg_last=2,
                  extra_env=CPU_ENV)
    assert r["steps"] == 3, r.get("error", "")
    assert r["loss"] == r["loss"]  # not NaN


# ---------------------------------------------------------------- launch --

@pytest.mark.slow
def test_launch_simulated_pod(tmp_path):
    """2 processes x 4 fake CPU devices: full jax.distributed bootstrap,
    global-mesh SPMD step with per-host input shards, leader-published K-of-N
    mask over the coordination-service KV, multi-host checkpointing."""
    from ps_pytorch_tpu.tools import launch

    run_dir = tmp_path / "run"
    ckpt_dir = tmp_path / "ckpt"
    rc = launch.main([
        "launch", "--run-dir", str(run_dir), "--simulate", "2",
        "--devices-per-host", "4", "--port", str(free_port()),
        "--entry", str(REPO / "train.py"), "--cwd", str(REPO),
        "--wait", "--timeout", "600",
        "--",
        "--network", "LeNet", "--dataset", "synthetic_mnist",
        "--batch-size", "256", "--max-steps", "6", "--eval-freq", "3",
        "--train-dir", str(ckpt_dir), "--mode", "kofn", "--num-aggregate", "7",
        "--resume", "false", "--compute-dtype", "float32",
    ])
    logs = [run_dir / f"proc_{i}.log" for i in range(2)]
    dump = "\n\n".join(f"== {l} ==\n{l.read_text()[-3000:]}" for l in logs
                       if l.exists())
    assert rc == 0, dump
    for log in logs:
        text = log.read_text()
        assert "DIST process" in text, dump
        assert "FINAL" in text, dump
    # K-of-N over the KV: every step ran with 7 of 8 replicas participating.
    assert "participating 7" in logs[0].read_text(), dump
    # Both hosts wrote / one won: committed checkpoints exist and are loadable.
    assert (ckpt_dir / "model_step_6").is_dir(), dump
    # status + kill on a finished fleet behave.
    assert launch.main(["status", "--run-dir", str(run_dir)]) == 1  # all exited
    assert launch.main(["kill", "--run-dir", str(run_dir)]) == 0


def test_launch_hostfile_parse(tmp_path):
    from ps_pytorch_tpu.tools.launch import _read_hostfile

    hf = tmp_path / "hosts_address"
    hf.write_text("# fleet\n10.0.0.1 slots=1\n10.0.0.2\n\n")
    assert _read_hostfile(str(hf)) == ["10.0.0.1", "10.0.0.2"]


def test_remote_pid_parsed_from_log(tmp_path):
    """ssh-mode kill must target the REMOTE trainer's own pid (echoed by the
    launch wrapper), not the local ssh client's (round-1 advisor, medium)."""
    from ps_pytorch_tpu.tools.launch import _remote_pid

    log = tmp_path / "proc_0.log"
    log.write_text("REMOTE_PID 4242\nDIST process 0/2\n")
    assert _remote_pid({"log": str(log)}) == 4242
    log.write_text("no pid line here\n")
    assert _remote_pid({"log": str(log)}) is None
    assert _remote_pid({"log": str(tmp_path / "missing.log")}) is None


def test_alive_does_not_reap_unrelated_children():
    """_alive must only reap the pid it was asked about — waitpid(-1) would
    steal exit statuses from other subprocess.Popen children of a library
    caller (round-1 advisor)."""
    import subprocess
    import sys
    import time as _time

    from ps_pytorch_tpu.tools.launch import _alive

    other = subprocess.Popen([sys.executable, "-c", "print('x')"])
    _time.sleep(0.5)  # let it exit so it is reapable
    gone = subprocess.Popen([sys.executable, "-c", "pass"])
    gone.wait()
    # Probing an unrelated pid must not consume `other`'s exit status.
    _alive(gone.pid)
    assert other.wait(timeout=10) == 0


@pytest.mark.slow
def test_kofn_excludes_injected_straggler(tmp_path):
    """End-to-end straggler handling: slow down HOST 0 (the leader — the
    side the zero-duration tie-break would otherwise favor) in a 2-process
    kofn run and assert the published mask flips to host 1's replicas.
    Proves per-step duration telemetry actually reaches the policy
    (VERDICT r1 item 6; reference per-worker timing,
    distributed_worker.py:169-173)."""
    from ps_pytorch_tpu.tools import launch

    run_dir = tmp_path / "run"
    rc = launch.main([
        "launch", "--run-dir", str(run_dir), "--simulate", "2",
        "--devices-per-host", "4", "--port", str(free_port()),
        "--entry", str(REPO / "train.py"), "--cwd", str(REPO),
        "--wait", "--timeout", "600",
        "--",
        "--network", "LeNet", "--dataset", "synthetic_mnist",
        "--batch-size", "256", "--max-steps", "10", "--eval-freq", "0",
        "--train-dir", str(tmp_path / "ckpt"), "--mode", "kofn",
        "--num-aggregate", "4", "--resume", "false",
        "--compute-dtype", "float32", "--log-every", "1",
        "--inject-step-delay", "0.35", "--inject-delay-process", "0",
    ])
    logs = [run_dir / f"proc_{i}.log" for i in range(2)]
    dump = "\n\n".join(f"== {l} ==\n{l.read_text()[-3000:]}" for l in logs
                       if l.exists())
    assert rc == 0, dump
    leader = logs[0].read_text()
    # First mask (zero durations everywhere) tie-breaks to replicas 0-3;
    # once real durations flow, host 0 is measurably slow and the fastest-4
    # policy must flip to host 1's replicas.
    assert "MASK step" in leader, dump
    assert "[0, 0, 0, 0, 1, 1, 1, 1]" in leader, dump


@pytest.mark.slow
def test_kill_and_resume(tmp_path):
    """Failure recovery: kill a 2-process run mid-training, relaunch with
    --resume, and verify training continues from the last committed
    checkpoint instead of step 1 (the capability the reference lacks —
    SURVEY §5.4 'there is no resume')."""
    from ps_pytorch_tpu.tools import launch

    run1 = tmp_path / "run1"
    run2 = tmp_path / "run2"
    ckpt = tmp_path / "ckpt"
    args = ["--network", "LeNet", "--dataset", "synthetic_mnist",
            "--batch-size", "256", "--eval-freq", "2", "--train-dir",
            str(ckpt), "--compute-dtype", "float32", "--resume", "true"]
    rc = launch.main([
        "launch", "--run-dir", str(run1), "--simulate", "2",
        "--devices-per-host", "4", "--port", str(free_port()),
        "--entry", str(REPO / "train.py"), "--cwd", str(REPO),
        "--", "--max-steps", "50"] + args)
    assert rc == 0
    # Wait until at least one checkpoint commits, then kill the fleet.
    import time
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if any(p.name.startswith("model_step_") for p in ckpt.glob("*")):
            break
        time.sleep(0.5)
    else:
        raise AssertionError("no checkpoint appeared before the kill")
    assert launch.main(["kill", "--run-dir", str(run1)]) == 0
    steps = [int(p.name.split("_")[-1]) for p in ckpt.glob("model_step_*")]
    resumed_from = max(steps)

    # Relaunch: must RESUME (not restart at step 1) and finish.
    rc = launch.main([
        "launch", "--run-dir", str(run2), "--simulate", "2",
        "--devices-per-host", "4", "--port", str(free_port()),
        "--entry", str(REPO / "train.py"), "--cwd", str(REPO),
        "--wait", "--timeout", "600",
        "--", "--max-steps", str(resumed_from + 4)] + args)
    logs = [run2 / f"proc_{i}.log" for i in range(2)]
    dump = "\n\n".join(f"== {l} ==\n{l.read_text()[-2500:]}" for l in logs
                       if l.exists())
    assert rc == 0, dump
    text = logs[0].read_text()
    assert f"RESUME" in text and f"at step {resumed_from}" in text, dump
    first_step = next(int(l.split()[1]) for l in text.splitlines()
                      if l.startswith("STEP "))
    assert first_step == resumed_from + 1, dump
