"""Unit tests for the evidence harnesses' parent logic (no subprocesses):
memory_probe's artifact/delta bookkeeping and accuracy_run's contract
parsing, and memory_probe's eight builders at tiny geometry on the CPU mesh.
The numbers need the chip; these tests pin everything that can break
without one.
"""

import json
import re
import subprocess
import sys
import types
from pathlib import Path

import pytest

from ps_pytorch_tpu.tools import accuracy_run, memory_probe

REPO = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------ memory_probe --

def test_write_doc_deltas_and_atomicity(tmp_path):
    out = tmp_path / "MEM.json"
    rows = [
        {"mode": "lm_base", "peak_bytes_in_use": 1000},
        {"mode": "lm_remat", "peak_bytes_in_use": 400},
        {"mode": "cnn_base", "peak_bytes_in_use": None},  # CPU row: no stats
        {"mode": "cnn_remat", "peak_bytes_in_use": 300},
    ]
    memory_probe._write_doc(str(out), rows)
    doc = json.loads(out.read_text())
    assert doc["deltas"] == {"lm_remat_saves_bytes": 600}  # cnn pair skipped
    assert doc["complete"] is False
    memory_probe._write_doc(str(out), rows, final=True)
    assert json.loads(out.read_text())["complete"] is True
    assert not (tmp_path / "MEM.json.tmp").exists()   # os.replace committed


def test_memory_probe_unknown_mode_rejected(tmp_path, monkeypatch):
    # Whole list validated BEFORE any child spawns: a typo after a valid
    # mode must not cost the minutes the valid mode's child takes.
    def forbidden(*a, **k):
        raise AssertionError("child spawned despite invalid mode list")

    monkeypatch.setattr(memory_probe.subprocess, "run", forbidden)
    with pytest.raises(SystemExit):
        memory_probe.main(["--modes", "lm_base,lm_typo",
                           "--out", str(tmp_path / "m.json")])


def test_memory_probe_rewrites_artifact_per_row(tmp_path, monkeypatch):
    """A SIGKILL mid-suite must still leave a quotable artifact: after each
    faked child the on-disk doc already contains every finished row."""
    out = tmp_path / "MEM.json"
    seen = []

    def fake_run(cmd, capture_output, text, timeout):
        mode = cmd[cmd.index("--child") + 1]
        # The artifact written BEFORE this child ran holds the prior rows.
        seen.append(len(json.loads(out.read_text())["rows"])
                    if out.exists() else 0)
        row = {"mode": mode, "peak_bytes_in_use": 100}
        return types.SimpleNamespace(returncode=0, stdout=json.dumps(row),
                                     stderr="")

    monkeypatch.setattr(memory_probe.subprocess, "run", fake_run)
    memory_probe.main(["--modes", "lm_base,lm_remat,cnn_base",
                       "--out", str(out)])
    assert seen == [0, 1, 2]
    doc = json.loads(out.read_text())
    assert [r["mode"] for r in doc["rows"]] == ["lm_base", "lm_remat",
                                               "cnn_base"]
    assert doc["complete"] is True


def test_memory_probe_timeout_row(tmp_path, monkeypatch):
    def fake_run(cmd, capture_output, text, timeout):
        raise subprocess.TimeoutExpired(cmd, timeout)

    monkeypatch.setattr(memory_probe.subprocess, "run", fake_run)
    out = tmp_path / "MEM.json"
    memory_probe.main(["--modes", "lm_base", "--timeout", "5",
                       "--out", str(out)])
    doc = json.loads(out.read_text())
    assert doc["rows"][0]["error"] == "timeout 5s"


@pytest.mark.parametrize("mode", memory_probe.MODES)
def test_memory_probe_mode_builds_and_ticks(mode):
    """Every mode's step builds from the package's own parallel/ modules
    and runs: the child's measurement loop, minus the chip."""
    import numpy as np

    if mode.startswith("lm"):
        # The pp modes lay one stage on each of the 8 CPU devices.
        state, tick = memory_probe._lm_step(
            mode, batch=8, seq_len=128, d_model=32, n_heads=2, vocab=61,
            n_layers=8 if "_pp_" in mode else 1)
    else:
        state, tick = memory_probe._cnn_step(
            mode, network="LeNet", dataset="synthetic_mnist",
            per_device_batch=2)
    for i in range(2):
        state, metrics = tick(state, i)
    assert np.isfinite(float(metrics["loss"]))


# ------------------------------------------------------------ accuracy_run --

def test_eval_regex_accepts_nan():
    """A diverged run prints 'loss nan' — that must parse as divergence,
    not crash the harness as 'no EVAL line' (accuracy_run._FLOAT)."""
    line = "EVAL_LM step 2000 loss nan perplexity nan"
    m = re.search(rf"EVAL_LM step (\d+) loss {accuracy_run._FLOAT} "
                  rf"perplexity {accuracy_run._FLOAT}", line)
    assert m and m.group(3) == "nan"


def test_write_source_corpus(tmp_path):
    n = accuracy_run._write_source_corpus(str(REPO), str(tmp_path / "c.bin"))
    data = (tmp_path / "c.bin").read_bytes()
    assert n == len(data) and n > 100_000
    assert b"def " in data        # real source bytes, not padding


def test_accuracy_run_contract_parse(tmp_path, monkeypatch):
    """Parent logic end to end with faked train/evaluate children: the
    EVAL line becomes the artifact, met_target compares against prec1."""
    def fake_child(label, cmd, repo, timeout_s):
        out = ("EVAL step 1200 loss 0.031 prec1 0.9940 prec5 1.0000"
               if "evaluate.py" in label else "STEP done")
        return types.SimpleNamespace(stdout=out, stderr="", returncode=0)

    monkeypatch.setattr(accuracy_run, "_run_child", fake_child)
    monkeypatch.setattr(accuracy_run, "_platform",
                        lambda: ("tpu", "TPU v5 lite"))
    out = tmp_path / "ACC.json"
    r = accuracy_run.run(["--out", str(out), "--max-steps", "1200"])
    doc = json.loads(out.read_text())
    assert doc == r
    assert r["prec1"] == 0.994 and r["met_target"] is True
    assert r["platform"] == "tpu" and r["steps"] == 1200


def test_accuracy_run_missing_eval_line(monkeypatch):
    def fake_child(label, cmd, repo, timeout_s):
        return types.SimpleNamespace(stdout="garbage", stderr="",
                                     returncode=0)

    monkeypatch.setattr(accuracy_run, "_run_child", fake_child)
    with pytest.raises(RuntimeError, match="no EVAL line"):
        accuracy_run.run(["--max-steps", "10"])
