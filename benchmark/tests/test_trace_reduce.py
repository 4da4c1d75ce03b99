"""The reduction from an xplane to busy/idle, per-op, collective and gap
numbers: on the one recorded device trace the repo has, and on planes built by
hand where the arithmetic can be checked by eye."""

import os
from types import SimpleNamespace as NS

import pytest

import harness
import trace_reduce as tr
from conftest import BENCH, ROOT

XPLANE = os.path.join(ROOT, "profile_r04", "plugins", "profile",
                      "2026_07_30_19_43_35", "vm.xplane.pb")


def plane(name, **lines):
    return NS(name=name, lines=[
        NS(name=ln.replace("_", " "), events=[
            NS(name=n, start_ns=s, duration_ns=d) for n, s, d in evs])
        for ln, evs in lines.items()])


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.total(tr.clip([(0, 10)], 2, 5)) == 3


def test_three_event_plane_overlap():
    """A fusion 0-10 us, an all-reduce 8-14 us that overlaps its last 2 us,
    a fusion 20-30 us: busy 24 of 30, collective 6 of which 4 exposed."""
    data = NS(planes=[
        plane("/device:TPU:0", XLA_Ops=[
            ("%fusion.1 = f32[8] fusion(...), kind=kOutput", 0, 10_000),
            ("%all-reduce.3 = f32[8] all-reduce(f32[8] %fusion.1)", 8_000, 6_000),
            ("%fusion.2 = f32[8] fusion(...), kind=kLoop", 20_000, 10_000)],
            XLA_Modules=[("jit_step(1)", 0, 30_000)]),
        plane("/host:CPU", main=[("dispatch", 13_000, 8_000),
                                 ("tiny", 15_000, 1_000)])])
    t = tr.from_profile_data(data)
    chip = t.chip(0)
    assert chip.busy_s() == pytest.approx(24e-6)
    assert chip.idle_share() == pytest.approx(6 / 30)
    coll, exposed = chip.collectives()
    assert coll == pytest.approx(6e-6) and exposed == pytest.approx(4e-6)
    assert chip.matching_seconds("kind=kOutput") == pytest.approx(10e-6)
    assert chip.op_seconds()["all-reduce.3 all-reduce f32[8]"] == \
        pytest.approx(6e-6)
    assert t.attribute_gaps(chip) == [("dispatch", pytest.approx(6e-6))]
    assert t.attribute_gaps(chip, (0, 40e-6))[0] == \
        ("unattributed", pytest.approx(10e-6))


def test_async_collective_hidden_behind_compute():
    data = NS(planes=[plane("/device:TPU:0", XLA_Ops=[
        ("%fusion.1 = f32[8] fusion(...)", 0, 10_000),
        ("%all-reduce-done.1 = f32[8] all-reduce-done(...)", 10_000, 1_000)],
        Async_XLA_Ops=[("%all-reduce-start.1 = f32[8] all-reduce-start(...)",
                        2_000, 8_000)])])
    coll, exposed = tr.from_profile_data(data).chip(0).collectives()
    assert coll == pytest.approx(9e-6) and exposed == pytest.approx(1e-6)


def test_recorded_trace_conv_share_and_idle():
    """profile_r04: ResNet-18 b=1024, three steps, old stack. PERF.md quoted
    92% of device time in convolutions and under 1% idle from it."""
    t = tr.load(XPLANE)
    chip = t.chip(0)
    spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                          "conv_share.json"))
    conv = chip.matching_seconds(spec["params"]["pattern"])
    assert conv / chip.busy_s() == pytest.approx(0.92, abs=0.01)
    assert 0 <= chip.idle_share() < 0.01
    lo, hi, periods = chip.steady_window()
    assert periods == 2 and (hi - lo) / periods == pytest.approx(0.0292, abs=5e-4)
    assert chip.busy_s((lo, hi)) / periods == pytest.approx(0.0292, abs=5e-4)
    top = max(chip.op_seconds().items(), key=lambda kv: kv[1])
    assert top[0] == "convert_reduce_fusion.7 kOutput bf16[1024,32,32,64]"
    assert chip.collectives() == (0.0, 0.0)


def test_readers_on_a_hand_built_trace():
    """Four 10 ms step periods on two chips: each step a 6 ms conv fusion, a
    2 ms Pallas kernel and a 1 ms all-reduce half hidden behind the kernel;
    chip 1 idles 1 ms more. The CPU cannot produce such a plane, so the
    readers' device paths are exercised here."""
    ms = 1_000_000

    def chip(n, extra_idle):
        ops, mods = [], []
        for k in range(5):
            t0 = k * 10 * ms
            mods.append(("jit_local_step(1)", t0, 9 * ms))
            ops += [
                ("%fusion.1 = bf16[8,8] fusion(bf16[8,8] %p), kind=kOutput",
                 t0, 6 * ms - extra_idle),
                ('%block_0.3 = f32[8,8] custom-call(f32[8,8] %q), '
                 'custom_call_target="tpu_custom_call"', t0 + 6 * ms, 2 * ms),
                ("%all-reduce = f32[8] all-reduce(f32[8] %g)",
                 t0 + 7 * ms + ms // 2, ms)]
        return plane(f"/device:TPU:{n}", XLA_Ops=ops, XLA_Modules=mods)

    trace = tr.from_profile_data(NS(planes=[chip(0, 0), chip(1, ms)]))
    lo, hi, periods = trace.chip(0).steady_window()
    assert (lo, hi, periods) == (pytest.approx(0.01), pytest.approx(0.04), 3)
    files = harness.Files()
    run = harness.Run(
        trace=trace, durations=[0.012] * 20, step_s=0.012, files=files,
        say=lambda s: None,
        peak=files.json("peaks.json")["TPU v5 lite"],
        shape={"batch": 1, "seq_len": 1024, "heads": 16, "head_dim": 64,
               "layers": 1, "activation_dtypes": ["float32"]})

    def read(metric):
        spec = files.json("layer_metrics", metric + ".json")
        return files.module("readers", spec["reader"] + ".py").read(
            run, **spec.get("params", {}))

    assert read("conv_share") == pytest.approx(100 * 6 / 8.5)
    assert read("flash_ms_per_step") == pytest.approx(2.0)
    assert read("allreduce_ms_per_step") == pytest.approx(1.0)
    assert read("allreduce_exposed_ms") == pytest.approx(0.5)
    assert read("host_ms_per_step") == pytest.approx(12 - 8.5)
    assert read("device_idle") == pytest.approx(100 * 2.5 / 10)   # chip 1
    cost = files.module("kernel_costs", "flash_attention_causal.py")
    flops, nbytes = cost.required_per_step(run.shape)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("flash_roofline") == pytest.approx(100 * least / 2e-3)
