"""The harness end to end on the CPU, on fixture cells that exist only as
files under ``fixtures/`` — which is also the proof that a configuration, a
mix, a per-layer metric and a reader are added without editing the harness."""

import json
import os
import subprocess
import sys

import pytest

import harness
from conftest import HERE, ROOT

FIXTURES = harness.Files(os.path.join(HERE, "fixtures"))
REAL = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = {"tiny_lenet_cell": ("tiny_lenet", "tiny_sync", "images_per_s"),
         "tiny_gpt2_cell": ("tiny_gpt2", "tiny_s32", "tokens_per_s")}


def fixture_benchmark():
    """The real metric entries over the two fixture cells, plus a metric of
    the fixture's own."""
    n = __import__("jax").device_count()
    bench = {
        "workloads": [{"name": name, "config": c, "traffic": t, "chips": n,
                       "why": "fixture"} for name, (c, t, _) in CELLS.items()],
        "end_to_end": [dict(m) for m in REAL["end_to_end"]],
        "per_layer": [dict(m) for m in REAL["per_layer"]] + [
            {"name": "losses_logged", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "trainers", "moves": "mfu"}]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [name for name, (_, _, rate) in CELLS.items()
                              if m.get("moves", m["name"]) == rate]
    return bench


@pytest.fixture(scope="module")
def runs():
    """Each fixture cell once untraced and once traced, through the real
    Trainer / LMTrainer."""
    bench = fixture_benchmark()
    out = {}
    for name in CELLS:
        for trace in (False, True):
            lines = []
            out[name, trace] = (harness.run_cell(
                bench, name, seed=3, seconds=1.0, trace=trace, files=FIXTURES,
                say=lines.append), lines)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_line_has_the_contract_keys_and_end_to_end_metrics(runs, cell):
    result, lines = runs[cell, False]
    json.loads(json.dumps(result))
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["metrics"]) == {CELLS[cell][2], "mfu", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 10
    assert any(line.startswith("REFERENCE") and line.endswith("ok")
               for line in lines)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_has_per_layer_metrics_and_no_made_up_device_numbers(runs, cell):
    result, lines = runs[cell, True]
    got = set(result["metrics"])
    # host-side readers answer; trace readers find no TPU plane in a CPU
    # trace, return nothing, and are left out
    assert {"first_step_s", "compiles_in_window", "step_ms_p90",
            "data_wait_ms_per_step", "losses_logged"} <= got
    assert not got & {"device_idle", "conv_share", "flash_ms_per_step",
                      "flash_roofline", "host_ms_per_step",
                      "allreduce_ms_per_step", "allreduce_exposed_ms"}
    assert not got & {"mfu", "setup_s", "images_per_s", "tokens_per_s"}
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["losses_logged"]["value"] == result["attempted"]
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert result["correct"] is True


def test_wrong_participating_and_missed_tolerance_fail_the_run(tmp_path):
    bench = fixture_benchmark()
    for sub in ("configs", "traffic"):
        os.makedirs(tmp_path / sub)
    traffic = FIXTURES.json("traffic", "tiny_sync.json")
    traffic["expect_participating"] = 3
    (tmp_path / "traffic" / "tiny_sync.json").write_text(json.dumps(traffic))
    config = FIXTURES.json("configs", "tiny_gpt2.json")
    config["layer_norm_epsilon_as_run"] = 1e-2      # a reference that differs
    (tmp_path / "configs" / "tiny_gpt2.json").write_text(json.dumps(config))
    for sub in ("reference", "layer_metrics", "readers"):
        os.symlink(FIXTURES.path(sub), tmp_path / sub)
    os.symlink(FIXTURES.path("peaks.json"), tmp_path / "peaks.json")
    os.symlink(FIXTURES.path("configs", "tiny_lenet.json"),
               tmp_path / "configs" / "tiny_lenet.json")
    os.symlink(FIXTURES.path("traffic", "tiny_s32.json"),
               tmp_path / "traffic" / "tiny_s32.json")
    files = harness.Files(str(tmp_path))
    masked = harness.run_cell(bench, "tiny_lenet_cell", seed=3, seconds=0.3,
                              trace=False, files=files, say=lambda s: None)
    assert masked["correct"] is False
    assert masked["failed"] == masked["attempted"] > 0
    lines = []
    off = harness.run_cell(bench, "tiny_gpt2_cell", seed=3, seconds=0.3,
                           trace=False, files=files, say=lines.append)
    assert off["correct"] is False and off["failed"] == 0
    assert any(line.startswith("REFERENCE") and line.endswith("FAILED")
               for line in lines)


class FakeTrainer:
    def __init__(self):
        self.train_loader = self
        self.batches = 0

    def next_batch(self):
        self.batches += 1
        return self.batches


def drive(shim, clock, step_times):
    for dt in step_times:
        shim()
        clock[0] += dt


def test_shim_ends_warmup_only_on_two_steps_that_agree():
    clock = [0.0]
    t = FakeTrainer()
    shim = harness.StepShim(t, lambda tr: None, seconds=1.0,
                            clock=lambda: clock[0])
    with pytest.raises(harness.WindowDone):
        # step 1 compiles, 2-5 settle, 5 and 6 agree: the window opens at 7
        drive(shim, clock, [30.0, 2.0, 1.0, 0.5, 0.2, 0.19] + [0.2] * 10)
    assert shim.window_first == 7
    assert shim.window_last == 11 and len(shim.window_durations()) == 5
    assert shim.t_window1 - shim.t_window0 == pytest.approx(1.0)
    assert t.batches == 11      # the call that ends the window takes no batch


def test_shim_warmup_is_never_shorter_than_the_constant_and_is_capped():
    clock = [0.0]
    shim = harness.StepShim(FakeTrainer(), lambda tr: None, seconds=0.5,
                            clock=lambda: clock[0])
    with pytest.raises(harness.WindowDone):
        drive(shim, clock, [0.1] * 20)
    assert shim.window_first == harness.WARMUP_STEPS + 1
    clock = [0.0]
    shim = harness.StepShim(FakeTrainer(), lambda tr: None, seconds=5.0,
                            clock=lambda: clock[0])
    with pytest.raises(harness.WindowDone):     # steps that never agree
        drive(shim, clock, [0.1, 0.2] * 60)
    assert shim.window_first == \
        harness.WARMUP_STEPS + harness.MAX_EXTRA_WARMUP + 1


def test_window_holds_whole_periods_and_ends_with_the_drain():
    clock = [0.0]

    def drain(trainer):
        clock[0] += 0.05        # the device was 0.05 s behind the host

    # a period of 3 steps, the third slow (an epoch's end); 5.7 s are over
    # inside the 15th period, and the window runs to its end
    shim = harness.StepShim(FakeTrainer(), drain, seconds=5.7, period=3,
                            clock=lambda: clock[0])
    with pytest.raises(harness.WindowDone):
        drive(shim, clock, [0.1] * 5 + [0.1, 0.1, 0.2] * 40)
    d = shim.window_durations()
    assert shim.align == 3 and len(d) % 3 == 0 and len(d) == 45
    assert d[-1] == pytest.approx(0.25)         # the last step bears the drain
    assert sum(d) == pytest.approx(shim.t_window1 - shim.t_window0)
    # a period too long for the window to hold MIN_REPEATS of: no alignment
    clock = [0.0]
    shim = harness.StepShim(FakeTrainer(), lambda tr: None, seconds=1.0,
                            period=7, clock=lambda: clock[0])
    with pytest.raises(harness.WindowDone):
        drive(shim, clock, [0.1] * 40)
    assert shim.align == 1 and len(shim.window_durations()) == 10


def test_rate_counts_what_recurs_with_the_period_and_not_a_lone_stall():
    steps = [0.1, 0.1, 0.2] * 10
    step_s, how = harness.steady_step_s(steps, 3, sum(steps))
    assert how == "typical period" and step_s == pytest.approx(0.4 / 3)
    stalled = list(steps)
    stalled[4] += 2.0           # one step of one period stalls
    step_s, _ = harness.steady_step_s(stalled, 3, sum(stalled))
    assert step_s == pytest.approx(0.4 / 3)
    # a loop that syncs every third step: two dispatches and the wait
    step_s, _ = harness.steady_step_s([0.01, 0.01, 0.28] * 10, 3, 3.0)
    assert step_s == pytest.approx(0.1)
    # too few whole periods: plainly steps over seconds, stall and all
    step_s, how = harness.steady_step_s(stalled[:11], 3, sum(stalled[:11]))
    assert how == "steps over seconds"
    assert step_s == pytest.approx(sum(stalled[:11]) / 11)


def test_losses_are_judged_on_the_steps_the_program_logged():
    def rec(step, loss, participating=3):
        return {"step": step, "loss": loss, "participating": participating}
    every10 = [rec(s, 5.0 - 0.01 * s) for s in range(10, 200, 10)]
    out = harness._loss_checks(every10, 21, 150, 3)     # log_every=10
    assert out["enough"] and out["learned"] and out["failed"] == 0
    assert len(out["window"]) == 13
    out = harness._loss_checks(every10, 21, 60, 3)
    assert not out["enough"]                    # four records judge nothing
    bad = every10[:5] + [rec(60, float("nan")), rec(70, 4.0, 2)] + every10[7:]
    assert harness._loss_checks(bad, 21, 150, 3)["failed"] == 2
    assert harness._loss_checks(bad, 21, 150, None)["failed"] == 1


def test_reference_check_moves_every_vector_leaf_off_its_initial_value():
    import numpy as np
    v = {"params": {"bn": {"scale": np.ones(8, np.float32),
                           "bias": np.zeros(8, np.float32)},
                    "conv": {"kernel": np.ones((3, 3, 2, 8), np.float32)}},
         "batch_stats": {"bn": {"mean": np.zeros(8, np.float32),
                                "var": np.ones(8, np.float32)}}}
    out = harness._unsettle(v, np.random.default_rng(0))
    again = harness._unsettle(v, np.random.default_rng(0))
    for path in (("params", "bn", "scale"), ("params", "bn", "bias"),
                 ("batch_stats", "bn", "mean"), ("batch_stats", "bn", "var")):
        a, b, c = v, out, again
        for k in path:
            a, b, c = a[k], b[k], c[k]
        assert np.all(a != b) and np.all(np.abs(a - b) <= 0.2)
        assert np.array_equal(b, c) and b.dtype == np.float32
    assert np.all(out["batch_stats"]["bn"]["var"] > 0.5)
    assert out["params"]["conv"]["kernel"] is v["params"]["conv"]["kernel"]


def test_command_line_refuses_the_cpu_and_a_bare_directory(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = ["--workload", "resnet18_1chip", "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    r = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py")]
                       + cmd, env=env, capture_output=True, text=True)
    assert r.returncode != 0 and "TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    # a directory that holds only BENCHMARK.json and the benchmark's files
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, str(tmp_path / "benchmark" / "run.py")]
                       + cmd, env=env, capture_output=True, text=True)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_importing_the_harness_touches_no_device():
    code = ("import sys; sys.path[:0] = [%r, %r]; import harness, trace_reduce;"
            "assert 'jax' not in sys.modules" % (ROOT, os.path.join(ROOT, "benchmark")))
    subprocess.run([sys.executable, "-c", code], check=True)
