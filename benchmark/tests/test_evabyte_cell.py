"""The EvaByte configuration, its mix, driver, reference counts, cost functions
and readers, held to each other and to the catalog the configuration was
copied from (where this machine has it); and the new scope as a case of
``readers/device_scopes.py``'s rule."""

import json
import os
import types

import pytest

import harness
from conftest import BENCH as BENCH_DIR

FILES = harness.Files()
CONFIG = FILES.json("configs", "evabyte_6_5b.json")
TRAFFIC = FILES.json("traffic", "s16384_bytes_1chip.json")
BENCH = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "evabyte_s16384_1chip"
REF = FILES.module("reference", "evabyte_6_5b.py")
CORE = FILES.module("kernel_costs", "eva_attention.py")
POOL = FILES.module("kernel_costs", "eva_pool.py")
ds = harness.load_module(os.path.join(BENCH_DIR, "readers",
                                      "device_scopes.py"))
NEW_METRICS = ("dev_eva_pool_ms_per_step", "eva_core_roofline",
               "eva_pool_roofline", "eva_pool_weight_max")
JOINED = ("dev_attn_core_ms_per_step", "dev_attn_proj_ms_per_step",
          "dev_attn_pos_ms_per_step", "dev_ffn_ms_per_step",
          "dev_embed_ms_per_step", "dev_head_ms_per_step",
          "dev_loss_ms_per_step", "dev_recompute_ms_per_step")
# no flash kernel runs (the patterns match flash_fwd / flash_bwd_dkv by name
# and their costs count a causal triangle), no expert, no scan; and the
# patterns keyed by a shape
KEPT_OUT = ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
            "flash_gqa_roofline", "flash_win_ms_per_step",
            "flash_win_roofline", "flash_ms_per_step", "flash_roofline",
            "flash_diff_roofline", "head_loss_ms_per_step",
            "moe_gmm_ms_per_step", "dev_moe_experts_ms_per_step",
            "dev_ssm_scan_ms_per_step", "dev_ssd_core_ms_per_step",
            "dev_gdn_core_ms_per_step", "images_per_s")
SHAPE = {"batch": 1, "seq_len": 16384, "heads": 32, "kv_heads": 32,
         "head_dim": 128, "layers": 4, "d_model": 4096, "eva_window": 2048,
         "eva_chunk": 16, "pred_heads": 8}


def _args(argv):
    return dict(zip(argv[::2], argv[1::2]))


def test_the_argv_is_what_the_cell_says():
    a = _args(CONFIG["program_args"])
    assert a["--lm-arch"] == "evabyte" and a["--lm-parallelism"] == "sp"
    assert int(a["--lm-d-model"]) == CONFIG["hidden_size"] == 4096
    assert int(a["--lm-layers"]) == CONFIG["num_hidden_layers"] == 4
    assert int(a["--lm-heads"]) == CONFIG["num_attention_heads"] \
        == CONFIG["num_key_value_heads"] == 32
    assert int(a["--lm-ffn-dim"]) == CONFIG["intermediate_size"] == 11008
    assert int(a["--lm-vocab"]) == CONFIG["vocab_size"] == 320
    assert "--lm-head-dim" not in a and "--lm-kv-heads" not in a
    assert a["--lm-attention"] == "flash" and a["--remat"] == "true"
    assert a["--compute-dtype"] == "bfloat16" and a["--momentum"] == "0.9"
    assert float(a["--lr"]) in (0.01, 0.03, 0.1)
    t = _args(TRAFFIC["args"])
    assert int(t["--lm-seq-len"]) == 16384 \
        == CONFIG["max_position_embeddings"] // 2
    assert int(t["--lm-seq-len"]) % CONFIG["chunk_size"] == 0
    assert int(t["--batch-size"]) == 1 and TRAFFIC["trace_steps"] == 6
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("evabyte_6_5b", "s16384_bytes_1chip", 1)
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    assert driver.FIXED_ARGS == ["--eval-freq", "0", "--resume", "false"]
    assert driver.THROUGHPUT == "tokens_per_s"


def test_every_published_key_is_carried_unchanged_but_the_depth():
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/evabyte_6_5b.json"
    assert CONFIG["published"] == {"num_hidden_layers": 32}
    assert "num_hidden_layers" in CONFIG["cut"]
    assert "stages of a pipeline" in CONFIG["deployment"]
    for key in ("deployment", "cut", "assumed", "departures",
                "parameters_by_kind"):
        assert CONFIG[key], key
    for key in ("rule", "found", "remat", "fewer_layers_means"):
        assert CONFIG["cut"][key], key
    assert 0 < CONFIG["reference_check"]["max_abs_logit_err"] < 1
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert key in CONFIG and CONFIG[key] == value, key


def test_the_arch_row_says_what_the_published_keys_say():
    from ps_pytorch_tpu.models.transformer import ARCHS
    row = ARCHS["evabyte"]
    assert (row.eva_window, row.eva_chunk, row.pred_heads, row.norm_eps,
            row.rope_theta) == tuple(CONFIG[k] for k in (
                "window_size", "chunk_size", "num_pred_heads", "rms_norm_eps",
                "rope_theta"))
    assert row.embed_std == row.eva_std == CONFIG["init_std"]
    assert row.zero_centred_norm == CONFIG["norm_add_unit_offset"]
    assert row.f32_logits == CONFIG["fp32_logits"]
    assert row.tied_head == CONFIG["tie_word_embeddings"]
    assert row.mixer_layers == ("eva",) \
        and CONFIG["attention_class"] == "eva"


def test_the_cells_name_is_in_the_lists_that_read_it():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", [])}
    assert listed == {"tokens_per_s", *JOINED, *NEW_METRICS}
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in harness.metrics_for(BENCH, g, CELL)}
    assert {"tokens_per_s", "mfu", "setup_s", "dev_optimizer_ms_per_step",
            "peak_hbm", "dev_unscoped_share"} <= reports
    assert not set(KEPT_OUT) & reports
    new = [m for m in BENCH["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    at = BENCH["per_layer"].index(new[0])
    assert BENCH["per_layer"][at:at + len(new)] == new  # appended, in one piece
    assert CELL in [c["name"] for c in BENCH["workloads"]]
    for m in new:
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("mfu" if m["name"] == "eva_pool_weight_max"
                              else "tokens_per_s")
        spec = FILES.json("layer_metrics", m["name"] + ".json")
        assert callable(FILES.module("readers", spec["reader"] + ".py").read)
    want = {"dev_eva_pool_ms_per_step": (
                "device_scopes", {"scope": "eva_pool", "per": "step_ms"}),
            "eva_core_roofline": (
                "scope_roofline", {"scope": "attn_core",
                                   "cost": "eva_attention"}),
            "eva_pool_roofline": (
                "scope_roofline", {"scope": "eva_pool", "cost": "eva_pool"}),
            "eva_pool_weight_max": (
                "jsonl_field", {"field": "eva_pool_weight_max"})}
    for name, (reader, params) in want.items():
        spec = FILES.json("layer_metrics", name + ".json")
        assert (spec["reader"], spec["params"]) == (reader, params)
    # eleven cells at least, one of them on four chips: a second slot is free
    assert len(BENCH["workloads"]) >= 11
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_parameter_counts_by_hand():
    d, f, v, h, hd = 4096, 11008, 320, 32, 128
    layer = 4 * d * d + 3 * d * f + 2 * d + 2 * h * hd
    by = CONFIG["parameters_by_kind"]
    assert layer == 202_391_552 == by["layer"]
    assert (v * d, d * 8 * v, d) == (by["embedding"], by["head"],
                                     by["final_norm"])
    assert REF.param_count(CONFIG) == 4 * layer + v * d + 8 * v * d + d \
        == CONFIG["parameters_as_run"] == 821_366_784
    assert REF.param_count(dict(CONFIG, **CONFIG["published"])) \
        == 32 * layer + 9 * v * d + d == CONFIG["parameters_published"] \
        == 6_488_330_240


def test_train_flops_closed_form_against_a_count_by_hand():
    """A small size, every term spelled out: d=8 in 2 heads of 4, width 6, 3
    prediction heads on 11 ids, 2 layers; windows of 4 tokens in chunks of 2;
    S=10: windows of 4, 4 and 2 tokens."""
    small = dict(CONFIG, hidden_size=8, num_attention_heads=2,
                 intermediate_size=6, vocab_size=11, num_pred_heads=3,
                 num_hidden_layers=2, window_size=4, chunk_size=2)
    s = 10
    pairs = (4 * 5 // 2) + (4 * 5 // 2 + 4 * 2) + (2 * 3 // 2 + 2 * 4)
    assert REF.live_pairs(s, small) == pairs == 39 \
        == CORE.pairs(s, 4, 2)
    macs = {"matrices": 2 * (4 * 8 * 8 + 3 * 8 * 6),
            "head": 8 * 3 * 11,
            "attention": 2 * 2 * 2 * 4 * pairs / s,     # layers, heads, 2 hd a pair
            "pooling": 2 * 2 * 3 * 4}
    assert REF.macs_per_token(small, s) == pytest.approx(macs)
    assert REF.train_flops_per_sample(small, s) \
        == pytest.approx(6 * sum(macs.values()))
    # at the cell's size: 85.4 TFLOP a step, the matmuls 80.6, the live pairs
    # 4.74 (5.6%); 7.3% at the declared 32,768
    per_token = REF.macs_per_token(CONFIG, 16384)
    step = lambda k, n=16384: 6 * REF.macs_per_token(CONFIG, n)[k] * n / 1e12
    assert per_token["matrices"] + per_token["head"] == 819_986_432
    assert REF.live_pairs(16384, CONFIG) == 24_125_440
    assert step("matrices") + step("head") == pytest.approx(80.61, rel=1e-3)
    assert step("attention") == pytest.approx(4.743, rel=1e-3)
    assert REF.train_flops_per_sample(CONFIG, 16384) * 16384 \
        == pytest.approx(85.36e12, rel=1e-3)
    share = lambda n: step("attention", n) / (
        REF.train_flops_per_sample(CONFIG, n) * n / 1e12)
    assert share(16384) == pytest.approx(0.0556, abs=1e-3)
    assert share(32768) == pytest.approx(0.0735, abs=1e-3)


def test_the_cost_functions_against_counts_by_hand():
    shape = dict(SHAPE, activation_dtypes=["bfloat16", "float32"])
    flops, nbytes = CORE.required_per_step(shape)
    # 12 hd a live pair, heads, layers: what the reference counts
    assert flops == 12 * 128 * 32 * 24_125_440 * 4 \
        == pytest.approx(4.743e12, rel=1e-3)
    assert flops == pytest.approx(
        6 * REF.macs_per_token(CONFIG, 16384)["attention"] * 16384)
    rows = 32 * 16384 * 128 * 4     # the widest dtype the run found
    sums = rows // 16
    assert nbytes == 4 * ((4 * rows + 2 * sums) + (10 * rows + 4 * sums)
                          + 2 * 32 * 16384 * 4)
    peak = FILES.json("peaks.json")["TPU v5 lite"]
    assert flops / peak["bf16_flops_per_s"] > nbytes / peak["hbm_bytes_per_s"]
    flops, nbytes = POOL.required_per_step(shape)
    assert flops == 0
    assert nbytes == 4 * (8 * rows + 4 * sums)
    # bfloat16 alone: half of it; 1.1 GB a layer, 5 ms of a step at the peak
    flops, nbytes = POOL.required_per_step(
        dict(SHAPE, activation_dtypes=["bfloat16"]))
    assert nbytes == pytest.approx(4 * 1.107e9, rel=1e-3)
    assert nbytes / peak["hbm_bytes_per_s"] == pytest.approx(5.4e-3, rel=2e-2)


def test_the_driver_says_what_the_cost_functions_need():
    from ps_pytorch_tpu.config import config_from_args
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    cfg = config_from_args(CONFIG["program_args"] + TRAFFIC["args"])
    shape = driver.shape(types.SimpleNamespace(cfg=cfg))
    assert shape == SHAPE
    assert driver.samples_per_step(types.SimpleNamespace(cfg=cfg)) == 16384


def test_the_controls_are_the_references_own_attributes():
    controls = harness.load_module(os.path.join(
        BENCH_DIR, "controls", "evabyte_6_5b.py"))
    assert controls.CELL == CELL
    for name, control in controls.CONTROLS.items():
        assert set(control) <= {"ref", "ref_variables"}, name
        for attr in control.get("ref", {}):
            assert hasattr(REF, attr), (name, attr)
    for name, over in controls.LOSS_CONTROLS.items():
        for attr in over:
            assert hasattr(REF, attr), (name, attr)
    check = CONFIG["reference_check"]
    assert set(check["controls"]) == set(controls.CONTROLS)
    assert set(controls.LOSS_CONTROLS) <= set(check["unseen_on_the_chip"]) \
        <= set(controls.CONTROLS) | set(controls.LOSS_CONTROLS)


# ---- the new scope, by the reader's rule -----------------------------------

def test_the_new_scopes_ops_are_given_to_it():
    from ps_pytorch_tpu.telemetry.trace import DEVICE_SCOPES
    scope = "eva_pool"
    assert scope in DEVICE_SCOPES
    stack = "jit(local_step)/{}/block_2/" + scope + "/pallas_call"
    cases = {
        stack.format("jvp(TransformerLM)"): "forward",
        stack.format("transpose(jvp(TransformerLM))/jvp(TransformerLM)/"
                     "checkpoint"): "backward",
        stack.format("transpose(jvp(TransformerLM))/jvp(TransformerLM)/"
                     "checkpoint/rematted_computation"): "recompute",
    }
    for name, part in cases.items():
        assert ds.scope_of(name, DEVICE_SCOPES) == (scope, part)
    # a parameter that merely carries the letters is not the scope
    assert ds.scope_of(f"jit(s)/jvp(LM)/block_0/{scope}_norm/mul",
                       DEVICE_SCOPES)[0] == ds.UNSCOPED


def test_the_rooflines_read_nothing_where_the_program_has_no_such_scope(
        monkeypatch):
    """The parent's program has no ``eva_pool``: the reader returns None and
    the result line leaves the metric out."""
    reader = FILES.module("readers", "scope_roofline.py")
    said = []
    shape = dict(SHAPE, activation_dtypes=["bfloat16", "float32"])
    run = harness.Run(files=FILES, shape=shape, say=said.append,
                      peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    monkeypatch.setattr(ds, "read", lambda r, scope, per: {
        "attn_core": 50.0}.get(scope))
    flops, nbytes = CORE.required_per_step(shape)
    want = 100.0 * max(flops / 1e12, nbytes / 1e9) / 50.0e-3
    assert reader.read(run, "attn_core", "eva_attention") \
        == pytest.approx(want)
    assert "eva_attention" in said[0] and "attn_core" in said[0]
    assert reader.read(run, "eva_pool", "eva_pool") is None
