"""The Nemotron-3-Nano configuration, its mix, driver, reference counts, cost
functions and readers, held to each other and to the catalog the configuration
was copied from (where this machine has it); and the new scope as a case of
``readers/device_scopes.py``'s rule."""

import json
import os
import types

import pytest

import harness
from conftest import BENCH as BENCH_DIR

FILES = harness.Files()
CONFIG = FILES.json("configs", "nemotron3_nano_30b_a3b.json")
TRAFFIC = FILES.json("traffic", "s16384_ssd_1chip.json")
BENCH = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron3nano_s16384_1chip"
REF = FILES.module("reference", "nemotron3_nano_30b_a3b.py")
COST = FILES.module("kernel_costs", "ssd.py")
GMM = FILES.module("kernel_costs", "moe_grouped_matmul_ungated.py")
ds = harness.load_module(os.path.join(BENCH_DIR, "readers",
                                      "device_scopes.py"))
NEW_METRICS = ("dev_ssd_core_ms_per_step", "ssd_core_roofline",
               "ssd_state_abs_max", "moe_gmm_ungated_roofline")
JOINED = ("dev_ssm_proj_ms_per_step", "dev_ssm_conv_ms_per_step",
          "dev_attn_core_ms_per_step", "dev_attn_proj_ms_per_step",
          "dev_attn_pos_ms_per_step", "dev_embed_ms_per_step",
          "dev_head_ms_per_step", "dev_loss_ms_per_step",
          "dev_moe_route_ms_per_step", "dev_moe_dispatch_ms_per_step",
          "dev_moe_experts_ms_per_step", "dev_moe_shared_ms_per_step",
          "dev_recompute_ms_per_step", "flash_fwd_ms_per_step",
          "flash_bwd_ms_per_step", "flash_gqa_roofline",
          "moe_gmm_ms_per_step", "expert_load_max_over_mean", "moe_dropped",
          "moe_held_share", "moe_bias_abs_max", "moe_load_all_max_over_mean")
# no window layer, no dense layer, no Mamba-1 scan; the held roofline counts
# nine calls a layer in every layer; and the patterns keyed by a shape: a
# vocabulary of 16,384 is also the cell's row count
KEPT_OUT = ("flash_win_ms_per_step", "flash_win_roofline",
            "dev_ffn_ms_per_step", "dev_ssm_scan_ms_per_step",
            "ssm_scan_roofline", "moe_gmm_held_roofline", "moe_gmm_roofline",
            "head_loss_ms_per_step", "flash_ms_per_step", "flash_roofline",
            "moe_dispatch_ms_per_step", "moe_dispatch_held_ms_per_step",
            "moe_dispatch_s8192_ms_per_step", "moe_shared_ms_per_step",
            "images_per_s")


def _args(argv):
    return dict(zip(argv[::2], argv[1::2]))


def test_the_argv_is_what_the_cell_says():
    a = _args(CONFIG["program_args"])
    assert a["--lm-arch"] == "nemotronh" and a["--lm-parallelism"] == "ep"
    assert int(a["--lm-d-model"]) == CONFIG["hidden_size"] == 2688
    assert int(a["--lm-layers"]) == CONFIG["num_hidden_layers"] == 9
    assert int(a["--lm-heads"]) == CONFIG["num_attention_heads"] == 32
    assert int(a["--lm-kv-heads"]) == CONFIG["num_key_value_heads"] == 2
    assert int(a["--lm-head-dim"]) == CONFIG["head_dim"] == 128
    assert int(a["--lm-ffn-dim"]) == CONFIG["moe_intermediate_size"] == 1856
    assert int(a["--lm-experts"]) == CONFIG["n_routed_experts_published"] \
        == 128
    assert int(a["--lm-experts-held"]) == CONFIG["experts_held"] \
        == CONFIG["n_routed_experts"] == 16
    assert int(a["--lm-moe-top-k"]) == CONFIG["num_experts_per_tok"] == 6
    assert int(a["--lm-vocab"]) == CONFIG["vocab_size"] == 16384
    assert a["--lm-attention"] == "flash" and a["--remat"] == "true"
    assert a["--compute-dtype"] == "bfloat16" and a["--momentum"] == "0.9"
    assert float(a["--lr"]) in (0.01, 0.03, 0.1)
    t = _args(TRAFFIC["args"])
    assert int(t["--lm-seq-len"]) == 16384 <= CONFIG["max_position_embeddings"]
    assert int(t["--batch-size"]) == 1 and TRAFFIC["trace_steps"] == 6
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("nemotron3_nano_30b_a3b", "s16384_ssd_1chip", 1)
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    assert driver.FIXED_ARGS == ["--eval-freq", "0", "--resume", "false"]
    assert driver.THROUGHPUT == "tokens_per_s"


def test_every_published_key_is_carried_unchanged_but_the_reduced_ones():
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/nemotron3_nano_30b_a3b.json"
    assert CONFIG["published"] == {"num_hidden_layers": 52,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert CONFIG["n_routed_experts"] * 8 \
        == CONFIG["published"]["n_routed_experts"] \
        == CONFIG["n_routed_experts_published"]
    assert CONFIG["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert len(CONFIG["hybrid_override_pattern"]) == 52
    for key in CONFIG["reduced"]:
        assert CONFIG[key] < CONFIG["published"][key] and key in CONFIG["cut"]
    assert "8 chips share each layer" in CONFIG["deployment"]
    for key in ("deployment", "cut", "assumed", "departures",
                "parameters_by_kind"):
        assert CONFIG[key], key
    for key in ("rule", "found", "remat", "fewer_layers_means"):
        assert CONFIG["cut"][key], key
    assert 0 < CONFIG["reference_check"]["max_abs_logit_err"] < 1
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_arch_row_says_what_the_published_keys_say():
    from ps_pytorch_tpu.models.transformer import ARCHS
    row = ARCHS["nemotronh"]
    assert row.norm_eps == CONFIG["layer_norm_epsilon"] == CONFIG["norm_eps"]
    assert row.layer_pattern == CONFIG["hybrid_override_pattern"]
    assert row.gate_norm == CONFIG["norm_topk_prob"]
    assert row.route_scale == CONFIG["routed_scaling_factor"]
    assert row.router_bias_rate == CONFIG["router_bias_rate"]
    assert row.expert_act == CONFIG["mlp_hidden_act"] and not row.expert_gated
    assert (row.ssm_heads, row.ssm_head_dim, row.ssm_groups, row.ssm_state,
            row.ssm_conv, row.ssm_chunk) == tuple(CONFIG[k] for k in (
                "mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "conv_kernel", "chunk_size"))
    assert row.shared_experts * CONFIG["moe_intermediate_size"] \
        == CONFIG["moe_shared_expert_intermediate_size"]
    assert [row.layer_kind(i) for i in range(52)] \
        == [REF.layer_kind(CONFIG, i) for i in range(52)]


def test_the_cells_name_is_in_the_lists_that_read_it():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", [])}
    assert listed == {"tokens_per_s", *JOINED, *NEW_METRICS}
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in harness.metrics_for(BENCH, g, CELL)}
    assert {"tokens_per_s", "mfu", "setup_s"} <= reports
    assert not set(KEPT_OUT) & reports
    new = [m for m in BENCH["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    at = BENCH["per_layer"].index(new[0])
    assert BENCH["per_layer"][at:at + len(new)] == new  # appended, in one piece
    assert CELL in [c["name"] for c in BENCH["workloads"]]
    for m in new:
        assert m["workloads"][0] == CELL
        assert m["moves"] == ("mfu" if m["name"] == "ssd_state_abs_max"
                              else "tokens_per_s")
        spec = FILES.json("layer_metrics", m["name"] + ".json")
        assert callable(FILES.module("readers", spec["reader"] + ".py").read)
    spec = FILES.json("layer_metrics", "dev_ssd_core_ms_per_step.json")
    assert (spec["reader"], spec["params"]) == (
        "device_scopes", {"scope": "ssd_core", "per": "step_ms"})
    spec = FILES.json("layer_metrics", "ssd_core_roofline.json")
    assert (spec["reader"], spec["params"]) == (
        "scope_roofline", {"scope": "ssd_core", "cost": "ssd"})
    spec = FILES.json("layer_metrics", "ssd_state_abs_max.json")
    assert (spec["reader"], spec["params"]) == (
        "jsonl_field", {"field": "ssd_state_abs_max"})
    spec = FILES.json("layer_metrics", "moe_gmm_ungated_roofline.json")
    same = FILES.json("layer_metrics", "moe_gmm_ms_per_step.json")
    assert spec["reader"] == "roofline" \
        and spec["params"]["cost"] == "moe_grouped_matmul_ungated" \
        and spec["params"]["pattern"] == same["params"]["pattern"]
    # ten cells at least, one of them on four chips
    assert len(BENCH["workloads"]) >= 10
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_parameter_counts_by_hand():
    d, v, f = 2688, 16384, 1856
    mamba = d * (4096 + 6144 + 64) + 4096 * d + 5 * 6144 + 3 * 64 + 4096 + d
    attention = 2 * d * 4096 + 2 * d * 256 + d
    experts = lambda held: held * 2 * d * f + d * 128 + 2 * d * 3712 + d
    by_kind = CONFIG["parameters_by_kind"]
    assert mamba == 38_744_896 == by_kind["mamba2_layer"]
    assert attention == 23_399_040 == by_kind["attention_layer"]
    assert experts(16) == 179_948_160 == by_kind["expert_layer_as_run"]
    assert experts(128) == 1_297_468_032 == by_kind["expert_layer_published"]
    assert 2 * v * d == by_kind["embedding_and_head_as_run"]
    as_run = 4 * mamba + attention + 4 * experts(16) + 2 * v * d + d
    assert REF.param_count(CONFIG) == as_run == CONFIG["parameters_as_run"] \
        == 986_254_336
    published = 23 * mamba + 6 * attention + 23 * experts(128) \
        + 2 * 131072 * d + d
    whole = dict(CONFIG, **CONFIG["published"], experts_held=128)
    assert REF.param_count(whole) == published \
        == CONFIG["parameters_published"] == 31_577_937_344
    # what a token passes: six experts and the shared one in 23 layers, 29
    # mixers and the routers: the "A3B"; and the head
    active = 23 * mamba + 6 * attention \
        + 23 * (6 * 2 * d * f + d * 128 + 2 * d * 3712 + d)
    assert 2.8e9 < active < 3.3e9


def test_train_flops_closed_form_against_a_count_by_hand():
    """A small size, every term spelled out: d=8; Mamba-2 layers of 2 heads of
    4 with 3 states in 1 group, 4 taps; attention of 2 heads of 4 on 1 K/V
    head; 4 router outputs of which 2 are held, top-2, width 6, the shared
    expert 12; vocabulary 11; depth 6, MEMEM*; S=5."""
    small = dict(CONFIG, hidden_size=8, num_attention_heads=2,
                 num_key_value_heads=1, head_dim=4, mamba_num_heads=2,
                 mamba_head_dim=4, n_groups=1, ssm_state_size=3,
                 moe_intermediate_size=6,
                 moe_shared_expert_intermediate_size=12, n_routed_experts=2,
                 n_routed_experts_published=4, experts_held=2,
                 num_experts_per_tok=2, vocab_size=11, num_hidden_layers=6)
    s = 5
    mamba = 8 * (8 + (8 + 6) + 2) + 8 * 8 + 5 * 14    # in, out, conv with its bias
    macs = {"mamba2_projections": 3 * mamba,
            "mamba2_recurrence": 3 * (2.5 * 8 * 3 + 1.5 * 8),
            "projections": 2 * 8 * 8 + 2 * 8 * 4,     # q, o; k, v
            "attention": 2 * 8 * (s + 1) / 2,     # two products a causal pair
            "shared": 2 * (2 * 8 * 12),
            "router": 2 * 8 * 4,
            "experts": 2 * (2 / 4) * (2 * 2 * 8 * 6),   # k x held / E experts a token, two matmuls
            "head": 8 * 11}
    assert REF.macs_per_token(small, s) == pytest.approx(macs)
    assert REF.train_flops_per_sample(small, s) == \
        pytest.approx(6 * sum(macs.values()))
    # at the cell's size: 2.44 GFLOP a token, 39.9 TFLOP a step; the Mamba-2
    # layers' projections 15.2, the one attention layer's core 6.6, the
    # recurrence 0.52
    per_token = REF.macs_per_token(CONFIG, 16384)
    step = lambda k: 6 * per_token[k] * 16384 / 1e12
    assert 6 * sum(per_token.values()) * 16384 == pytest.approx(39.90e12,
                                                                rel=1e-3)
    assert step("mamba2_projections") == pytest.approx(15.23, rel=1e-3)
    assert step("attention") == pytest.approx(6.597, rel=1e-3)
    assert step("mamba2_recurrence") == pytest.approx(0.516, rel=1e-2)
    assert step("experts") == pytest.approx(2.94, rel=1e-2)
    # the cost function counts the same recurrence
    assert COST.FLOPS_PER_STATE == 2 * 2.5
    assert REF.recurrence_macs_per_token(CONFIG) \
        == 2.5 * 64 * 64 * 128 + 1.5 * 64 * 64


SHAPE = {"batch": 2, "seq_len": 16, "ssd_layers": 3, "ssd_heads": 4,
         "ssd_head_dim": 8, "ssd_state": 16, "ssd_groups": 2,
         "ssd_kept_bytes": 1000, "activation_dtypes": ["bfloat16", "float32"]}


def test_ssd_cost_against_a_count_by_hand():
    flops, nbytes = COST.required_per_step(SHAPE)
    tokens = 32
    # five operations a state element and token forward, twice that backward
    assert flops == 3 * (3 * tokens * 4 * 8 * 16 * 5)
    xbc = (4 * 8 + 2 * 2 * 16) * 2      # x a head; B, C a group; bfloat16
    dt, out = 4 * 4, 4 * 8 * 2          # dt float32; y bfloat16
    a_layer = tokens * (xbc + dt + out) \
        + tokens * (xbc + dt + out + xbc + dt) + 2 * 1000
    assert nbytes == 3 * a_layer
    # at the cell's size: 0.52 TFLOP and 4.3 GB a step: bound by memory
    cell = dict(SHAPE, batch=1, seq_len=16384, ssd_layers=4, ssd_heads=64,
                ssd_head_dim=64, ssd_state=128, ssd_groups=8,
                ssd_kept_bytes=256 * 2 ** 20)
    flops, nbytes = COST.required_per_step(cell)
    assert flops == pytest.approx(5.154e11, rel=1e-3)
    assert nbytes == 4 * (16384 * (3 * (4096 + 2048) * 2 + 3 * 256
                                   + 2 * 4096 * 2) + 2 * 256 * 2 ** 20)
    peak = FILES.json("peaks.json")["TPU v5 lite"]
    assert nbytes / peak["hbm_bytes_per_s"] > flops / peak["bf16_flops_per_s"]


def test_ungated_grouped_matmul_cost_against_a_count_by_hand():
    shape = {"batch": 1, "seq_len": 16384, "top_k": 6, "experts": 128,
             "experts_held": 16, "d_model": 2688, "ffn_dim": 1856,
             "layers": 9, "expert_layers": 4,
             "activation_dtypes": ["bfloat16", "float32"]}
    flops, nbytes = GMM.required_per_step(shape)
    rows = 16384 * 6 // 8
    assert rows == 12288
    # two matmuls in three passes in each of the FOUR layers that route
    assert flops == 4 * 6 * 2 * rows * 2688 * 1856 == pytest.approx(2.94e12,
                                                                    rel=1e-2)
    assert nbytes == 4 * 6 * (16 * 2688 * 1856 * 4
                              + rows * (2688 + 1856) * 4)
    held = FILES.module("kernel_costs", "moe_grouped_matmul_held.py")
    assert held.required_per_step(shape)[0] == flops * 9 / 4 * 3 / 2


def test_the_driver_says_what_the_cost_functions_need():
    from ps_pytorch_tpu.config import config_from_args
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    cfg = config_from_args(CONFIG["program_args"] + TRAFFIC["args"])
    shape = driver.shape(types.SimpleNamespace(cfg=cfg))
    held = FILES.module("drivers", "train_lm_moe_held.py")
    assert shape == dict(
        held.shape(types.SimpleNamespace(cfg=cfg)), windows=[0],
        ssd_layers=4, ssd_heads=64, ssd_head_dim=64, ssd_state=128,
        ssd_groups=8, ssd_kept_bytes=128 * 64 * 64 * 128 * 4,
        expert_layers=4, shared_width=3712)
    assert (shape["batch"], shape["seq_len"], shape["heads"],
            shape["kv_heads"], shape["head_dim"], shape["layers"]) \
        == (1, 16384, 32, 2, 128, 9)
    assert driver.samples_per_step(types.SimpleNamespace(cfg=cfg)) == 16384
    # the roofline the cell joins takes its widths from this shape: one layer
    # of 32 query heads of 128
    gqa = FILES.module("kernel_costs", "flash_attention_gqa_causal.py")
    flops, _ = gqa.required_per_step(dict(shape, activation_dtypes=["bfloat16"]))
    assert flops == pytest.approx(6.597e12, rel=1e-3)
    flops, _ = GMM.required_per_step(dict(shape,
                                          activation_dtypes=["bfloat16"]))
    assert flops == pytest.approx(2.94e12, rel=1e-2)


def test_the_controls_are_the_references_own_attributes():
    controls = harness.load_module(os.path.join(
        BENCH_DIR, "controls", "nemotron3_nano_30b_a3b.py"))
    assert controls.CELL == CELL
    for name, control in controls.CONTROLS.items():
        assert set(control) <= {"ref", "ref_variables"}, name
        for attr in control.get("ref", {}):
            assert hasattr(REF, attr), (name, attr)
    assert set(CONFIG["reference_check"]["controls"]) \
        == set(controls.CONTROLS)


# ---- the new scope, by the reader's rule -----------------------------------

def test_the_new_scopes_ops_are_given_to_it():
    from ps_pytorch_tpu.telemetry.trace import DEVICE_SCOPES
    scope = "ssd_core"
    assert scope in DEVICE_SCOPES
    stack = "jit(local_step)/{}/block_2/" + scope + "/pallas_call"
    cases = {
        stack.format("jvp(MoETransformerLM)"): "forward",
        stack.format("transpose(jvp(MoETransformerLM))/jvp(MoETransformerLM)/"
                     "checkpoint"): "backward",
        stack.format("transpose(jvp(MoETransformerLM))/jvp(MoETransformerLM)/"
                     "checkpoint/rematted_computation"): "recompute",
    }
    for name, part in cases.items():
        assert ds.scope_of(name, DEVICE_SCOPES) == (scope, part)
    # a parameter that merely carries the letters is not the scope
    assert ds.scope_of(f"jit(s)/jvp(LM)/block_0/{scope}_norm/mul",
                       DEVICE_SCOPES)[0] == ds.UNSCOPED


def test_the_roofline_reads_nothing_where_the_program_has_no_such_scope(
        monkeypatch):
    """The parent's program has no ``ssd_core``: the reader returns None and
    the result line leaves the metric out."""
    reader = FILES.module("readers", "scope_roofline.py")
    said = []
    run = harness.Run(files=FILES, shape=SHAPE, say=said.append,
                      peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    monkeypatch.setattr(ds, "read", lambda r, scope, per: {
        "ssd_core": 0.05}.get(scope))
    flops, nbytes = COST.required_per_step(SHAPE)
    want = 100.0 * max(flops / 1e12, nbytes / 1e9) / 0.05e-3
    assert reader.read(run, "ssd_core", "ssd") == pytest.approx(want)
    assert "bound by memory" in said[0] and "ssd_core" in said[0]
    assert reader.read(run, "ssm_scan", "ssd") is None
