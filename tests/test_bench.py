"""bench.py / bench_suite.py exit-code contract (no chip needed).

bench.py's number is a device metric: without a TPU, or on any error, it
prints ONE parseable JSON line carrying "error" and no "value", and exits
non-zero — it never measures on the CPU. bench_suite.py exits non-zero when
any row errored, and its --isolate parent opens no backend (the chip belongs
to one process at a time, so a parent that touched it would starve its rows).
"""

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import bench
import bench_suite


def _json_lines(text):
    out = []
    for line in text.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def _only_error_line(capsys):
    rows = _json_lines(capsys.readouterr().out)
    assert len(rows) == 1, rows
    assert rows[0]["metric"] == bench.METRIC
    assert "value" not in rows[0] and "platform" not in rows[0]
    return rows[0]["error"]


def test_no_tpu_is_nonzero_with_one_error_line(capsys):
    assert bench.main([]) == 1
    assert "needs a TPU" in _only_error_line(capsys)


def test_bad_steps_is_nonzero_with_one_error_line(capsys):
    assert bench.main(["--steps", "0"]) == 1
    assert "--steps" in _only_error_line(capsys)


def test_require_tpu_names_the_platform_found():
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    with pytest.raises(RuntimeError, match="platform=cpu"):
        bench.require_tpu([cpu])
    bench.require_tpu([types.SimpleNamespace(platform="tpu",
                                             device_kind="TPU v5 lite")])


def test_failed_measurement_is_nonzero(monkeypatch, capsys):
    monkeypatch.setattr(bench, "require_tpu", lambda devices: None)

    def boom(*a, **k):
        raise RuntimeError("compile exploded")

    monkeypatch.setattr(bench_suite, "_build", boom)
    assert bench.main([]) == 1
    assert "compile exploded" in _only_error_line(capsys)


def test_result_line_names_the_device(monkeypatch, capsys):
    from ps_pytorch_tpu.utils import flops

    monkeypatch.setattr(bench, "require_tpu", lambda devices: None)
    monkeypatch.setattr(bench_suite, "_build", lambda *a, **k: (None,) * 5)
    monkeypatch.setattr(bench_suite, "time_steps", lambda *a, **k: 0.5)
    monkeypatch.setattr(flops, "training_flops", lambda *a, **k: 8e9)
    monkeypatch.setattr(flops, "peak_flops_bf16", lambda kind: 1e12)
    assert bench.main(["--per-device-batch", "4"]) == 0
    (row,) = _json_lines(capsys.readouterr().out)
    assert row["metric"] == bench.METRIC and "error" not in row
    assert row["global_batch"] == 4 * row["devices"] == 32
    assert row["value"] == 64.0 and row["sec_per_step"] == 0.5
    assert row["mfu"] == pytest.approx(1e9 * 64 / (1e12 * 8), rel=1e-3)
    assert {"platform", "device_kind", "compile_s"} <= set(row)


def test_cli_without_a_chip_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "bench.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=str(REPO))
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["metric"] == bench.METRIC and "value" not in last
    assert "platform=cpu" in last["error"]


def _suite(monkeypatch, configs):
    monkeypatch.setattr(bench_suite, "CONFIGS", configs)


def test_suite_row_error_makes_exit_nonzero(monkeypatch, capsys):
    _suite(monkeypatch, {"boom": lambda steps: 1 / 0,
                         "fine": lambda steps: {"config": "fine"}})
    assert bench_suite.main(["--configs", "boom,fine"]) == 1
    rows = {r["config"]: r for r in _json_lines(capsys.readouterr().out)}
    assert "ZeroDivisionError" in rows["boom"]["error"]
    assert rows["fine"] == {"config": "fine"}   # later rows still ran


def test_suite_all_rows_ok_exits_zero(monkeypatch, capsys):
    _suite(monkeypatch, {"fine": lambda steps: {"config": "fine"}})
    assert bench_suite.main(["--configs", "fine"]) == 0


def test_suite_isolate_parent_opens_no_backend():
    # Fresh interpreter: the parent drives one (faked) isolated row, then
    # must still be able to hand the chip to a child — i.e. no backend yet.
    code = (
        "import bench_suite\n"
        "bench_suite.CONFIGS = {'row': None}\n"
        "bench_suite._run_isolated = lambda n, s, t: {'config': n}\n"
        "rc = bench_suite.main(['--isolate', '--configs', 'row'])\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), 'backend opened'\n"
        "raise SystemExit(rc)\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-800:]
