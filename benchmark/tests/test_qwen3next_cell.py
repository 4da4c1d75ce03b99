"""The Qwen3-Next configuration, its mix, driver, reference counts, cost
function and readers, held to each other and to the catalog the configuration
was copied from (where this machine has it); and the three linear-attention
scopes as cases of ``readers/device_scopes.py``'s rule."""

import json
import os
import types

import pytest

import harness
from conftest import BENCH as BENCH_DIR

FILES = harness.Files()
CONFIG = FILES.json("configs", "qwen3_next_80b_a3b.json")
TRAFFIC = FILES.json("traffic", "s16384_hybrid_1chip.json")
BENCH = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "qwen3next_s16384_1chip"
REF = FILES.module("reference", "qwen3_next_80b_a3b.py")
COST = FILES.module("kernel_costs", "gated_delta_rule.py")
ds = harness.load_module(os.path.join(BENCH_DIR, "readers",
                                      "device_scopes.py"))
NEW_SCOPES = ("gdn_proj", "gdn_mix", "gdn_core")
NEW_METRICS = ("dev_gdn_proj_ms_per_step", "dev_gdn_mix_ms_per_step",
               "dev_gdn_core_ms_per_step", "gdn_core_roofline",
               "gdn_state_abs_max")
JOINED = ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
          "moe_gmm_ms_per_step", "expert_load_max_over_mean", "moe_dropped",
          "moe_held_share", "flash_gqa_roofline", "moe_gmm_held_roofline",
          "dev_attn_core_ms_per_step", "dev_attn_proj_ms_per_step",
          "dev_attn_pos_ms_per_step", "dev_embed_ms_per_step",
          "dev_head_ms_per_step", "dev_loss_ms_per_step",
          "dev_moe_route_ms_per_step", "dev_moe_dispatch_ms_per_step",
          "dev_moe_experts_ms_per_step", "dev_moe_shared_ms_per_step",
          "dev_recompute_ms_per_step",
          # its pattern spells vocabulary widths, and 18,992 is one of them
          "head_loss_ms_per_step")
# no window layer, no dense layer, no selection bias
KEPT_OUT = ("flash_win_ms_per_step", "flash_win_roofline", "dev_ffn_ms_per_step",
            "moe_bias_abs_max", "images_per_s")


def _args(argv):
    return dict(zip(argv[::2], argv[1::2]))


def test_the_argv_is_what_the_cell_says():
    a = _args(CONFIG["program_args"])
    assert a["--lm-arch"] == "qwen3next" and a["--lm-parallelism"] == "ep"
    assert int(a["--lm-d-model"]) == CONFIG["hidden_size"] == 2048
    assert int(a["--lm-layers"]) == CONFIG["num_hidden_layers"] == 4
    assert int(a["--lm-heads"]) == CONFIG["num_attention_heads"] == 16
    assert int(a["--lm-kv-heads"]) == CONFIG["num_key_value_heads"] == 2
    assert int(a["--lm-head-dim"]) == CONFIG["head_dim"] == 256
    assert int(a["--lm-ffn-dim"]) == CONFIG["moe_intermediate_size"] == 512
    assert int(a["--lm-experts"]) == CONFIG["num_experts_published"] == 512
    assert int(a["--lm-experts-held"]) == CONFIG["experts_held"] \
        == CONFIG["num_experts"] == 64
    assert int(a["--lm-moe-top-k"]) == CONFIG["num_experts_per_tok"] == 10
    assert int(a["--lm-vocab"]) == CONFIG["vocab_size"] == 18992
    assert a["--lm-attention"] == "flash" and a["--remat"] == "true"
    assert a["--compute-dtype"] == "bfloat16" and a["--momentum"] == "0.9"
    assert float(a["--lr"]) in (0.01, 0.03, 0.1)
    t = _args(TRAFFIC["args"])
    assert int(t["--lm-seq-len"]) == 16384 <= CONFIG["max_position_embeddings"]
    assert int(t["--batch-size"]) == 1 and TRAFFIC["trace_steps"] == 6
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("qwen3_next_80b_a3b", "s16384_hybrid_1chip", 1)
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    assert driver.FIXED_ARGS == ["--eval-freq", "0", "--resume", "false"]
    assert driver.THROUGHPUT == "tokens_per_s"


def test_every_published_key_is_carried_unchanged_but_the_reduced_ones():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/qwen3_next_80b_a3b.json"
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert CONFIG["num_experts"] * 8 == CONFIG["published"]["num_experts"] \
        == CONFIG["num_experts_published"]
    assert CONFIG["num_hidden_layers"] == CONFIG["full_attention_interval"]
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
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_arch_row_says_what_the_published_keys_say():
    from ps_pytorch_tpu.models.transformer import ARCHS
    row = ARCHS["qwen3next"]
    assert row.norm_eps == CONFIG["rms_norm_eps"]
    assert row.rope_theta == CONFIG["rope_theta"]
    assert row.rope_share == CONFIG["partial_rotary_factor"]
    assert row.aux_coef == CONFIG["router_aux_loss_coef"]
    assert row.gate_norm == CONFIG["norm_topk_prob"]
    assert (row.gdn_key_heads, row.gdn_value_heads, row.gdn_key_dim,
            row.gdn_value_dim, row.gdn_conv) == tuple(CONFIG[k] for k in (
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim"))
    assert row.shared_experts * CONFIG["moe_intermediate_size"] \
        == CONFIG["shared_expert_intermediate_size"]
    assert len(row.mixer_layers) == CONFIG["full_attention_interval"]
    n = CONFIG["published"]["num_hidden_layers"]
    assert [row.layer_kind(i, n) == "gdn" for i in range(n)] \
        == [REF.is_linear(CONFIG, i) for i in range(n)]


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
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG["name"]
    for m in new:
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("mfu" if m["name"] == "gdn_state_abs_max"
                              else "tokens_per_s")
        spec = FILES.json("layer_metrics", m["name"] + ".json")
        assert callable(FILES.module("readers", spec["reader"] + ".py").read)
    for name in NEW_METRICS[:3]:
        spec = FILES.json("layer_metrics", name + ".json")
        assert spec["reader"] == "device_scopes"
        assert spec["params"] == {"scope": name[4:-12], "per": "step_ms"}
    spec = FILES.json("layer_metrics", "gdn_core_roofline.json")
    assert (spec["reader"], spec["params"]) == (
        "scope_roofline", {"scope": "gdn_core", "cost": "gated_delta_rule"})
    spec = FILES.json("layer_metrics", "gdn_state_abs_max.json")
    assert (spec["reader"], spec["params"]) == (
        "jsonl_field", {"field": "gdn_state_abs_max"})
    # nine cells, one of them on four chips
    assert len(BENCH["workloads"]) == 9
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_parameter_counts_by_hand():
    d, v, f = 2048, 18992, 512
    linear = d * (2048 + 2048 + 4096 + 4096) + d * 64 + 4 * 8192 \
        + 4096 * d + 32 + 32 + 128
    attention = 3 * d * 4096 + 2 * d * 512 + 2 * 256
    experts = lambda held: held * 3 * d * f + d * 512 + 3 * d * f + d + 2 * d
    by_kind = CONFIG["parameters_by_kind"]
    assert linear == 33_718_464 == by_kind["linear_attention_mixer"]
    assert attention == 27_263_488 == by_kind["softmax_attention_mixer"]
    assert experts(64) == 205_527_040 == by_kind["expert_half_of_a_layer_as_run"]
    assert experts(512) == by_kind["expert_half_of_a_layer_published"]
    as_run = 3 * linear + attention + 4 * experts(64) + 2 * v * d + d
    assert REF.param_count(CONFIG) == as_run == CONFIG["parameters_as_run"] \
        == 1_028_320_320
    published = 36 * linear + 12 * attention + 48 * experts(512) \
        + 2 * 151936 * d + d
    whole = dict(CONFIG, **CONFIG["published"], experts_held=512)
    assert REF.param_count(whole) == published \
        == CONFIG["parameters_published"]
    assert 79e9 < published < 80e9
    # what a token passes: ten experts and the shared one, a mixer and the
    # router a layer: the "A3B"; and the head
    active = 36 * linear + 12 * attention \
        + 48 * (11 * 3 * d * f + d * 512 + d)
    assert 3.2e9 < active < 3.3e9 and active + 151936 * d < 3.6e9


def test_train_flops_closed_form_against_a_count_by_hand():
    """A small size, every term spelled out: d=8; linear layers of 1 key and 2
    value heads of 4, 4 taps; attention of 2 heads of 4 on 1 K/V head; 4
    router outputs of which 2 are held, top-2, width 6; vocabulary 11; depth
    4 (three linear layers, one full); S=5."""
    small = dict(CONFIG, hidden_size=8, num_attention_heads=2,
                 num_key_value_heads=1, head_dim=4, linear_num_key_heads=1,
                 linear_num_value_heads=2, linear_key_head_dim=4,
                 linear_value_head_dim=4, moe_intermediate_size=6,
                 shared_expert_intermediate_size=6, num_experts=2,
                 num_experts_published=4, experts_held=2,
                 num_experts_per_tok=2, vocab_size=11, num_hidden_layers=4)
    s = 5
    linear = 8 * (4 + 4 + 8 + 8) + 8 * 4 + 8 * 8 + 4 * 16     # qkvz, ba, out, conv
    attention = 3 * 8 * 8 + 2 * 8 * 4                         # q, gate, o; k, v
    macs = {"linear_projections": 3 * linear,
            "linear_recurrence": 3 * 3.5 * 2 * 4 * 4,
            "projections": attention,
            "attention": 2 * 8 * (s + 1) / 2,     # two products a causal pair
            "shared": 4 * (3 * 8 * 6 + 8),
            "router": 4 * 8 * 4,
            "experts": 4 * (2 / 4) * (2 * 3 * 8 * 6),   # k x held / E experts a token
            "head": 8 * 11}
    assert REF.macs_per_token(small, s) == pytest.approx(macs)
    assert REF.train_flops_per_sample(small, s) == \
        pytest.approx(6 * sum(macs.values()))
    # at the cell's size: 1.63 GFLOP a token, 26.8 TFLOP a step; the linear
    # layers' projections 9.9, the one full layer's core 6.6, the recurrence 0.54
    per_token = REF.macs_per_token(CONFIG, 16384)
    step = lambda k: 6 * per_token[k] * 16384 / 1e12
    assert 6 * sum(per_token.values()) == pytest.approx(1.6347e9, rel=1e-4)
    assert step("linear_projections") == pytest.approx(9.944, rel=1e-3)
    assert step("attention") == pytest.approx(6.597, rel=1e-3)
    assert step("linear_recurrence") == pytest.approx(0.5412, rel=1e-3)
    # the cost function counts the same recurrence
    assert COST.FLOPS_PER_STATE == 2 * 3.5
    assert REF.recurrence_macs_per_token(CONFIG) == 3.5 * 32 * 128 * 128


SHAPE = {"batch": 2, "seq_len": 16, "gdn_layers": 3, "gdn_key_heads": 2,
         "gdn_value_heads": 4, "gdn_key_dim": 8, "gdn_value_dim": 8,
         "gdn_kept_bytes": 1000, "activation_dtypes": ["bfloat16", "float32"]}


def test_delta_rule_cost_against_a_count_by_hand():
    flops, nbytes = COST.required_per_step(SHAPE)
    tokens = 32
    # seven operations a state element and token forward, twice that backward
    assert flops == 3 * (3 * tokens * 4 * 8 * 8 * 7)
    qkv = (2 * 2 * 8 + 4 * 8) * 2       # q, k a key head; v a value head; bfloat16
    gates, out = 2 * 4 * 4, 4 * 8 * 2   # g, beta float32; o bfloat16
    a_layer = tokens * (qkv + gates + out) \
        + tokens * (qkv + gates + out + qkv + gates) + 2 * 1000
    assert nbytes == 3 * a_layer
    wide = dict(SHAPE, activation_dtypes=["float32"])
    assert COST.required_per_step(wide)[1] \
        == nbytes + 3 * tokens * (3 * qkv + 2 * out)    # each as wide again
    # at the cell's size: 0.54 TFLOP and 6.5 GB a step: bound by memory
    cell = dict(SHAPE, batch=1, seq_len=16384, gdn_key_heads=16,
                gdn_value_heads=32, gdn_key_dim=128, gdn_value_dim=128,
                gdn_kept_bytes=512 * 2 ** 20)
    flops, nbytes = COST.required_per_step(cell)
    assert flops == pytest.approx(5.412e11, rel=1e-3)
    assert nbytes == 6_480_199_680
    peak = FILES.json("peaks.json")["TPU v5 lite"]
    assert nbytes / peak["hbm_bytes_per_s"] > flops / peak["bf16_flops_per_s"]


def test_the_driver_says_what_the_cost_functions_need():
    from ps_pytorch_tpu.config import config_from_args
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    cfg = config_from_args(CONFIG["program_args"] + TRAFFIC["args"])
    shape = driver.shape(types.SimpleNamespace(cfg=cfg))
    held = FILES.module("drivers", "train_lm_moe_held.py")
    assert shape == dict(
        held.shape(types.SimpleNamespace(cfg=cfg)), windows=[0],
        gdn_layers=3, gdn_key_heads=16, gdn_value_heads=32, gdn_key_dim=128,
        gdn_value_dim=128, gdn_kept_bytes=32 * 256 * 128 * 128 * 4,
        shared_width=512)
    assert (shape["batch"], shape["seq_len"], shape["heads"],
            shape["kv_heads"], shape["head_dim"]) == (1, 16384, 16, 2, 256)
    assert driver.samples_per_step(types.SimpleNamespace(cfg=cfg)) == 16384
    # the two rooflines the cell joins take their widths from this shape
    gqa = FILES.module("kernel_costs", "flash_attention_gqa_causal.py")
    flops, _ = gqa.required_per_step(dict(shape, activation_dtypes=["bfloat16"]))
    assert flops == pytest.approx(6.597e12, rel=1e-3)   # one layer at head dim 256
    gmm = FILES.module("kernel_costs", "moe_grouped_matmul_held.py")
    flops, _ = gmm.required_per_step(dict(shape, activation_dtypes=["bfloat16"]))
    assert flops == pytest.approx(1.546e12, rel=1e-3)   # 4 layers x 20,480 rows x width 512


# ---- the new scopes, by the reader's rule ----------------------------------

@pytest.mark.parametrize("scope", NEW_SCOPES)
def test_a_new_scopes_ops_are_given_to_it(scope):
    from ps_pytorch_tpu.telemetry.trace import DEVICE_SCOPES
    assert scope in DEVICE_SCOPES
    stack = "jit(local_step)/{}/block_1/" + scope + "/pallas_call"
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
    """The parent's program has no ``gdn_core``: the reader returns None and
    the result line leaves the metric out."""
    reader = FILES.module("readers", "scope_roofline.py")
    said = []
    run = harness.Run(files=FILES, shape=SHAPE, say=said.append,
                      peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    monkeypatch.setattr(ds, "read", lambda r, scope, per: {
        "gdn_core": 0.05}.get(scope))
    flops, nbytes = COST.required_per_step(SHAPE)
    want = 100.0 * max(flops / 1e12, nbytes / 1e9) / 0.05e-3
    assert reader.read(run, "gdn_core", "gated_delta_rule") == \
        pytest.approx(want)
    assert "bound by memory" in said[0] and "gdn_core" in said[0]
    assert reader.read(run, "gdn_mix", "gated_delta_rule") is None
