"""The Phi-4-mini-flash configuration, its mix, driver, reference counts, cost
functions and readers, held to each other and to the catalog the
configuration was copied from (where this machine has it); and the four
state-space scopes as cases of ``readers/device_scopes.py``'s rule."""

import json
import os
import types

import pytest

import harness
from conftest import BENCH as BENCH_DIR

FILES = harness.Files()
CONFIG = FILES.json("configs", "phi4_mini_flash.json")
TRAFFIC = FILES.json("traffic", "s8192_reasoning_1chip.json")
BENCH = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "phi4flash_s8192_1chip"
REF = FILES.module("reference", "phi4_mini_flash.py")
SCAN_COST = FILES.module("kernel_costs", "selective_scan.py")
DIFF_COST = FILES.module("kernel_costs", "flash_attention_diff.py")
ds = harness.load_module(os.path.join(BENCH_DIR, "readers",
                                      "device_scopes.py"))
NEW_SCOPES = ("ssm_scan", "ssm_proj", "ssm_conv", "gmu")
NEW_METRICS = ("dev_ssm_scan_ms_per_step", "dev_ssm_proj_ms_per_step",
               "dev_ssm_conv_ms_per_step", "dev_gmu_ms_per_step",
               "ssm_scan_roofline", "flash_diff_roofline",
               "ssm_state_abs_max")
JOINED = ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
          "flash_win_ms_per_step", "dev_attn_core_ms_per_step",
          "dev_attn_proj_ms_per_step", "dev_attn_pos_ms_per_step",
          "dev_embed_ms_per_step", "dev_head_ms_per_step",
          "dev_loss_ms_per_step", "dev_ffn_ms_per_step",
          "dev_recompute_ms_per_step")
# the three flash rooflines count one head width; head_loss_ms_per_step's
# pattern lists the other cells' vocabulary widths, not 25008
KEPT_OUT = ("flash_roofline", "flash_gqa_roofline", "flash_win_roofline",
            "flash_ms_per_step", "head_loss_ms_per_step", "images_per_s")


def _args(argv):
    return dict(zip(argv[::2], argv[1::2]))


def test_the_argv_is_what_the_cell_says():
    a = _args(CONFIG["program_args"])
    assert a["--lm-arch"] == "phi4flash" and a["--lm-parallelism"] == "sp"
    assert int(a["--lm-d-model"]) == CONFIG["hidden_size"] == 2560
    assert int(a["--lm-layers"]) == CONFIG["num_hidden_layers"] == 8
    assert int(a["--lm-heads"]) == CONFIG["num_attention_heads"] == 40
    assert int(a["--lm-kv-heads"]) == CONFIG["num_key_value_heads"] == 20
    assert int(a["--lm-head-dim"]) == 2560 // 40 == 64
    assert int(a["--lm-ffn-dim"]) == CONFIG["intermediate_size"] == 10240
    assert int(a["--lm-vocab"]) == CONFIG["vocab_size"] == 25008
    assert a["--lm-attention"] == "flash" and a["--remat"] == "true"
    assert a["--compute-dtype"] == "bfloat16" and a["--momentum"] == "0.9"
    t = _args(TRAFFIC["args"])
    assert int(t["--lm-seq-len"]) == 8192 <= CONFIG["max_position_embeddings"]
    assert int(t["--batch-size"]) in (1, 2) and TRAFFIC["trace_steps"] == 6
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("phi4_mini_flash", "s8192_reasoning_1chip", 1)
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    assert driver.FIXED_ARGS == ["--eval-freq", "0", "--resume", "false"]
    assert driver.THROUGHPUT == "tokens_per_s"


def test_every_published_key_is_carried_unchanged_but_the_reduced_ones():
    assert CONFIG["reduced"] == ["num_hidden_layers", "vocab_size"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 200064}
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    for key in CONFIG["reduced"]:
        assert CONFIG[key] < CONFIG["published"][key] and key in CONFIG["cut"]
    assert CONFIG["mamba"] == {"d_state": 16, "d_conv": 4, "expand": 2,
                               "dt_rank": 160}
    for key in ("deployment", "cut", "assumed", "departures"):
        assert CONFIG[key], key
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_arch_row_says_what_the_published_keys_say():
    from ps_pytorch_tpu.models.ssm import dt_rank
    from ps_pytorch_tpu.models.transformer import ARCHS
    row = ARCHS["phi4flash"]
    assert row.norm_eps == CONFIG["layer_norm_eps"]
    assert row.window == CONFIG["sliding_window"]
    assert row.tied_head == CONFIG["tie_word_embeddings"]
    assert (row.ssm_state, row.ssm_conv, row.ssm_expand,
            dt_rank(CONFIG["hidden_size"])) == tuple(
        CONFIG["mamba"][k] for k in ("d_state", "d_conv", "expand", "dt_rank"))
    n = CONFIG["num_hidden_layers"]
    assert [row.layer_kind(i, n) for i in range(n)] \
        == [REF.layer_kind(CONFIG, i) for i in range(n)]


def test_the_cells_name_is_in_the_lists_that_read_it():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", [])}
    assert listed == {"tokens_per_s", *JOINED, *NEW_METRICS}
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in harness.metrics_for(BENCH, g, CELL)}
    assert {"tokens_per_s", "mfu", "setup_s"} <= reports
    assert not set(KEPT_OUT) & reports
    new = [m for m in BENCH["per_layer"] if m["name"] in NEW_METRICS]
    assert BENCH["per_layer"][-len(new):] == new    # appended, in one piece
    for m in new:
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("mfu" if m["name"] == "ssm_state_abs_max"
                              else "tokens_per_s")
        spec = FILES.json("layer_metrics", m["name"] + ".json")
        assert callable(FILES.module("readers", spec["reader"] + ".py").read)
    for name in NEW_METRICS[:4]:
        spec = FILES.json("layer_metrics", name + ".json")
        assert spec["reader"] == "device_scopes"
        assert spec["params"] == {"scope": name[4:-12], "per": "step_ms"}


def test_parameter_counts_by_hand():
    d, f, v = 2560, 10240, 25008
    ffn, norms = 3 * d * f, 4 * d
    mamba = d * 10240 + 5120 * 192 + 160 * 5120 + 5120 * d \
        + 4 * 5120 + 3 * 5120 + 5120 * 16 + ffn + norms
    attention = 2 * d * 2560 + 2 * d * 1280 + 4 * 64 + 128 + ffn + norms
    cross = 2 * d * 2560 + 4 * 64 + 128 + ffn + norms
    gmu = 2 * d * 5120 + ffn + norms
    assert round(mamba / 1e6, 2) == 119.9 and round(attention / 1e6, 2) == 98.31
    assert round(gmu / 1e6, 2) == 104.87 and round(cross / 1e6, 2) == 91.76
    as_run = 3 * mamba + 3 * attention + gmu + cross + v * d + 2 * d
    assert REF.param_count(CONFIG) == as_run == CONFIG["parameters_as_run"]
    published = 9 * mamba + 9 * attention + 7 * gmu + 7 * cross \
        + 200064 * d + 2 * d
    assert REF.param_count(dict(CONFIG, **CONFIG["published"])) == published \
        == CONFIG["parameters_published"]
    assert 3.8e9 < published < 3.9e9


def test_train_flops_closed_form_against_a_count_by_hand():
    """A small size, every term spelled out: d=8 in 2 heads of 4 on 2 K/V
    heads, width 12, vocabulary 11, depth 4 (Mamba, window of 3, Mamba that
    hands on, full that hands on), S=5, d_inner 16 of 2 states, 4 taps,
    dt_rank 1."""
    small = dict(CONFIG, hidden_size=8, num_attention_heads=2,
                 num_key_value_heads=2, intermediate_size=12, vocab_size=11,
                 num_hidden_layers=4, sliding_window=3,
                 mamba=dict(d_state=2, d_conv=4, expand=2, dt_rank=1))
    s = 5
    ffn = 3 * 8 * 12
    mamba = 8 * 32 + 16 * (1 + 4) + 1 * 16 + 16 * 8 + ffn
    attention = 2 * 8 * 8 + 2 * 8 * 8 + ffn
    # keys a query sees: window 3 at S=5: 1+2+3+3+3 = 12 of 5; causal 15 of 5
    keys = 12 / 5 + 15 / 5
    macs = {"matrices": 2 * mamba + 2 * attention, "head": 8 * 11,
            "attention": 3 * 8 * keys, "conv": 2 * 4 * 16,
            "scan": 2 * 16 * (3.5 * 2 + 2)}
    assert REF.macs_per_token(small, s) == pytest.approx(macs)
    assert REF.train_flops_per_sample(small, s) == \
        pytest.approx(6 * sum(macs.values()))
    # at the cell's size: the issue's 5.9 GFLOP a token, 0.42 of it attention
    per_token = REF.macs_per_token(CONFIG, 8192)
    assert 6 * sum(per_token.values()) == pytest.approx(5.918e9, rel=1e-3)
    assert 6 * per_token["attention"] == pytest.approx(0.4232e9, rel=1e-3)
    # every matrix once and the head: all but the vectors of the 915M
    assert 0 < CONFIG["parameters_as_run"] - per_token["matrices"] \
        - per_token["head"] < 1e6


SHAPE = {"batch": 2, "seq_len": 16, "heads": 4, "kv_heads": 2, "head_dim": 8,
         "windows": [4, 0], "scan_layers": 3, "d_inner": 32, "d_state": 4,
         "scan_kept_bytes": 1000, "activation_dtypes": ["bfloat16"]}


def test_scan_cost_against_a_count_by_hand():
    flops, nbytes = SCAN_COST.required_per_step(SHAPE)
    tokens = 32
    assert flops == 3 * (3 * tokens * 32 * (7 * 4 + 3))
    a_layer = tokens * 32 * (2 + 2 + 2 + 2 + 2) \
        + tokens * 32 * (4 + 4 + 4) \
        + 6 * tokens * 4 * 4 + 3 * (32 * 4 + 32) * 4 + 2 * 1000
    assert nbytes == 3 * a_layer
    wide = dict(SHAPE, activation_dtypes=["float32"])
    assert SCAN_COST.required_per_step(wide)[1] \
        == nbytes + 3 * tokens * 32 * 5 * 2


def test_differential_attention_cost_against_a_count_by_hand():
    flops, nbytes = DIFF_COST.required_per_step(SHAPE)
    # pairs a softmax: window 4 at S=16: 1+2+3+4*13 = 58; causal 136
    per_pair = 9 * 8 * 2        # 3 hd forward, 6 hd backward, 2 FLOPs
    assert flops == 2 * 4 * (58 + 136) * per_pair
    a_layer = 6 * 2 * (4 + 2) * 16 * 8 * 2 + 3 * 2 * 4 * 16 * 4
    assert nbytes == 2 * a_layer
    # half as much again as attention whose values are as wide as its keys
    gqa = FILES.module("kernel_costs", "flash_attention_gqa_causal.py")
    assert flops == 1.5 * (gqa.required(SHAPE, True)[0]
                           + gqa.required(SHAPE, False)[0])


def test_the_driver_says_what_the_cost_functions_need():
    from ps_pytorch_tpu.config import config_from_args
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    cfg = config_from_args(CONFIG["program_args"] + TRAFFIC["args"])
    shape = driver.shape(types.SimpleNamespace(cfg=cfg))
    batch = int(_args(TRAFFIC["args"])["--batch-size"])
    assert shape == {
        "batch": batch, "seq_len": 8192, "heads": 40, "head_dim": 64,
        "layers": 8, "d_model": 2560, "kv_heads": 20,
        "windows": [512, 512, 0, 0], "scan_layers": 3, "d_inner": 5120,
        "d_state": 16, "scan_kept_bytes": batch * 64 * 5120 * 16 * 4}
    assert driver.samples_per_step(types.SimpleNamespace(cfg=cfg)) \
        == batch * 8192


# ---- the new scopes, by the reader's rule ----------------------------------

@pytest.mark.parametrize("scope", NEW_SCOPES)
def test_a_new_scopes_ops_are_given_to_it(scope):
    from ps_pytorch_tpu.telemetry.trace import DEVICE_SCOPES
    assert scope in DEVICE_SCOPES
    stack = "jit(local_step)/{}/block_4/" + scope + "/pallas_call"
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
    assert ds.scope_of(f"jit(s)/jvp(LM)/block_0/{scope}_weight/mul",
                       DEVICE_SCOPES)[0] == ds.UNSCOPED


def test_scope_roofline_divides_a_cost_by_a_scopes_time(monkeypatch):
    reader = FILES.module("readers", "scope_roofline.py")
    said = []
    run = harness.Run(files=FILES, shape=SHAPE, say=said.append,
                      peak={"bf16_flops_per_s": 1e12,
                            "hbm_bytes_per_s": 1e9})
    monkeypatch.setattr(ds, "read", lambda r, scope, per: {
        "ssm_scan": 0.05}.get(scope))
    flops, nbytes = SCAN_COST.required_per_step(SHAPE)
    want = 100.0 * max(flops / 1e12, nbytes / 1e9) / 0.05e-3
    assert reader.read(run, "ssm_scan", "selective_scan") == \
        pytest.approx(want)
    assert "bound by memory" in said[0] and "ssm_scan" in said[0]
    # a program without the scope (the parent): nothing to read, no error
    assert reader.read(run, "gmu", "selective_scan") is None
