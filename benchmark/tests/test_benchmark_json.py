"""BENCHMARK.json against the contract's rules that a file can be held to,
and against the benchmark's own files."""

import os
import re

import pytest

import harness
from conftest import BENCH, ROOT

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _metrics():
    return BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["benchmark"]
    assert BENCHMARK["command"] == ["python3", "benchmark/run.py"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    rs = BENCHMARK["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check at the full 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", _metrics() + BENCHMARK["configs"]
                         + BENCHMARK["workloads"], ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry and not (key == "source" and "better" in entry):
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]
    if "better" in entry:       # a metric
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= {"bound"} if "bound" in entry else {"layer", "moves"}
        assert set(entry) <= allowed
    elif "file" in entry:       # a configuration
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["file"].startswith("benchmark/configs/")
        assert len(entry["reduced"]) <= 16
    else:                       # a cell
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4)


def test_end_to_end_rules():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in _metrics()]
    assert len(names) == len(set(names))


def test_cells_configs_and_files_agree():
    configs = {c["name"] for c in BENCHMARK["configs"]}
    cells = BENCHMARK["workloads"]
    assert 2 <= len(cells) <= 24
    assert {c["config"] for c in cells} == configs
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs))
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    files = harness.Files()
    for c in BENCHMARK["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert os.path.exists(files.path("reference", c["name"] + ".py"))
        assert os.path.exists(files.path("drivers", cfg["driver"] + ".py"))
        assert cfg["reference_check"]["why"]
    for cell in cells:
        traffic = files.json("traffic", cell["traffic"] + ".json")
        assert traffic["why"] and traffic["who"]
        e2e = harness.metrics_for(BENCHMARK, "end_to_end", cell["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        reported = {m["name"] for m in e2e}
        layer = [m for m in harness.metrics_for(BENCHMARK, "per_layer",
                                                cell["name"])
                 if m["moves"] in reported]
        assert layer
    for m in _metrics():
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in cells}


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_every_layer_metric_has_its_file_and_reader(metric):
    files = harness.Files()
    spec = files.json("layer_metrics", metric["name"] + ".json")
    reader = files.module("readers", spec["reader"] + ".py")
    assert callable(reader.read) and spec["what"]
    assert metric["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}
    for v in spec.get("params", {}).values():
        assert "TO BE SET" not in str(v)
    if "pattern" in spec.get("params", {}):
        re.compile(spec["params"]["pattern"])


def test_files_under_paths_are_named_within_the_rules():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, names in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            assert ok.match(os.path.relpath(os.path.join(d, n), ROOT))


def test_program_args_say_what_the_configuration_says():
    gpt2 = harness.Files().json("configs", "gpt2_medium.json")
    args = dict(zip(gpt2["program_args"][::2], gpt2["program_args"][1::2]))
    assert int(args["--lm-d-model"]) == gpt2["n_embd"]
    assert int(args["--lm-layers"]) == gpt2["n_layer"]
    assert int(args["--lm-heads"]) == gpt2["n_head"]
    assert int(args["--lm-vocab"]) == gpt2["vocab_size"]
    s1024 = harness.Files().json("traffic", "s1024_1chip.json")["args"]
    s128 = harness.Files().json("traffic", "s128_1chip.json")["args"]
    tokens = lambda a: int(a[a.index("--lm-seq-len") + 1]) * \
        int(a[a.index("--batch-size") + 1])                     # noqa: E731
    assert tokens(s1024) == tokens(s128)
    assert int(s1024[s1024.index("--lm-seq-len") + 1]) == gpt2["n_positions"]
    one = harness.Files().json("traffic", "sync_1chip.json")["args"]
    four = harness.Files().json("traffic", "kofn3of4_4chip.json")["args"]
    assert 4 * int(one[one.index("--batch-size") + 1]) == \
        int(four[four.index("--batch-size") + 1])


def test_peaks_have_sources_and_unknown_kind_is_an_error():
    peaks = harness.Files().json("peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all(p["source"] for p in peaks.values())
    with pytest.raises(KeyError, match="peaks.json"):
        harness.peak_for(harness.Files(), "TPU v9")
