"""The OLMoE configuration, its mix, driver, cost function, reader and trace
patterns, held to each other and to the catalog the configuration was copied
from (where this machine has it)."""

import json
import os
import re
import types

import pytest

import harness

FILES = harness.Files()
CONFIG = FILES.json("configs", "olmoe_1b_7b.json")
TRAFFIC = FILES.json("traffic", "s4096_1chip.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _args(argv):
    return dict(zip(argv[::2], argv[1::2]))


def test_program_args_say_what_the_published_keys_say():
    a = _args(CONFIG["program_args"])
    assert a["--lm-arch"] == CONFIG["model_type"] == "olmoe"
    assert a["--lm-parallelism"] == "ep"
    assert int(a["--lm-d-model"]) == CONFIG["hidden_size"]
    assert int(a["--lm-layers"]) == CONFIG["num_hidden_layers"]
    assert int(a["--lm-heads"]) == CONFIG["num_attention_heads"] \
        == CONFIG["num_key_value_heads"]
    assert int(a["--lm-vocab"]) == CONFIG["vocab_size"]
    assert int(a["--lm-experts"]) == CONFIG["num_experts"]
    assert int(a["--lm-moe-top-k"]) == CONFIG["num_experts_per_tok"]
    assert int(a["--lm-ffn-dim"]) == CONFIG["intermediate_size"]
    t = _args(TRAFFIC["args"])
    assert int(t["--lm-seq-len"]) == CONFIG["max_position_embeddings"]
    assert CONFIG["norm_topk_prob"] is False and CONFIG["hidden_act"] == "silu"
    assert CONFIG["reduced"] == ["num_hidden_layers"]


def test_every_published_key_is_carried_unchanged_but_the_depth():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "OLMoE-1B-7B-0125-Instruct")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] < value
        else:
            assert CONFIG[key] == value, key


def test_reference_counts_the_published_model():
    ref = FILES.module("reference", "olmoe_1b_7b.py")
    assert ref.param_count(dict(CONFIG, num_hidden_layers=16)) \
        == CONFIG["parameters_published"] == 6_919_161_856
    assert ref.param_count(CONFIG) == CONFIG["parameters_as_run"]
    d, f, v = 2048, 1024, 50304
    per_token = 2 * (4 * d * d + 2 * 4096 * d + 8 * 3 * d * f + 64 * d) + d * v
    assert ref.train_flops_per_sample(CONFIG, seq_len=4096, batch=1) \
        == 6 * per_token


def _trainer():
    cfg = types.SimpleNamespace(batch_size=1, lm_seq_len=4096, lm_heads=16,
                                lm_d_model=2048, lm_layers=2, lm_experts=64,
                                lm_moe_top_k=8, lm_ffn_dim=1024, log_every=1)
    return types.SimpleNamespace(cfg=cfg)


def test_driver_shape_feeds_the_cost_function():
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    assert driver.THROUGHPUT == "tokens_per_s"
    assert driver.samples_per_step(_trainer()) == 4096
    shape = dict(driver.shape(_trainer()), activation_dtypes=["float32"])
    assert shape["head_dim"] == 128 and shape["ffn_dim"] == 1024
    cost = FILES.module("kernel_costs", "moe_grouped_matmul.py")
    flops, nbytes = cost.required_per_step(shape)
    rows = 4096 * 8
    assert flops == 2 * 9 * 2 * rows * 2048 * 1024
    assert nbytes == 2 * 9 * 4 * (64 * 2048 * 1024 + rows * (2048 + 1024))
    half = cost.required_per_step(dict(shape, activation_dtypes=["bfloat16"]))
    assert half == (flops, nbytes // 2)
    flash = FILES.module("kernel_costs", "flash_attention_causal.py")
    assert flash.required_per_step(shape)[0] == 2 * 6 * 16 * 4096 * 2048 * 128 * 2


def test_jsonl_field_reads_the_median_or_nothing():
    reader = FILES.module("readers", "jsonl_field.py")
    run = types.SimpleNamespace(window_records=[
        {"step": 1, "moe_dropped": 0.0, "x": 3.0}, {"step": 2, "x": 1.0},
        {"step": 3, "moe_dropped": 0.0, "x": None}])
    assert reader.read(run, field="moe_dropped") == 0.0
    assert reader.read(run, field="x") == 2.0
    assert reader.read(run, field="z_loss") is None


# HLO texts as the v5e's trace of the cell names them (my chip run, PR 25).
TRACE_TEXTS = {
    "gmm": ['%moe_gmm_fwd.10 = f32[32768,1024]{1,0:T(8,128)} custom-call(s32[66]{0:T(128)S(1)} %copy-done.251, f32[32768,2048]{1,0:T(8,128)} %fusion.8), custom_call_target="tpu_custom_call", operand_layout_constraints={}',
            '%moe_gmm_dlhs.6 = f32[32768,1024]{1,0:T(8,128)} custom-call(s32[66]{0:T(128)S(1)} %copy-done.252), custom_call_target="tpu_custom_call"',
            '%moe_gmm_drhs = f32[64,1024,2048]{2,1,0:T(8,128)} custom-call(s32[65]{0:T(128)S(1)} %pad_add_fusion.6), custom_call_target="tpu_custom_call"'],
    "dispatch": ['%fusion.29 = f32[4096,2048]{1,0:T(8,128)S(1)} fusion(f32[4096,2048]{1,0:T(8,128)S(1)} %copy-done.29, s32[32768]{0:T(1024)} %get-tuple-element.132, f32[32768,2048]{1,0:T(8,128)} %add_any.69, s32[32768]{0:T(1024)} %copy-done.176), kind=kCustom, calls=%fused_computation.103',
                 '%fusion.15 = f32[32768,2048]{1,0:T(8,128)} fusion(f32[4096,2048]{1,0:T(8,128)S(1)} %copy.148, s32[32768]{0:T(1024)S(1)} %copy-done.71), kind=kCustom, calls=%fused_computation.15',
                 '%fusion.23 = s32[64]{0:T(128)S(1)} fusion(s32[32768]{0:T(1024)S(1)} %bitcast.361, s32[32768]{0:T(1024)} %copy-done.177, s32[]{:T(128)} %constant.176), kind=kCustom, calls=%fused_computation.585',
                 '%sort.8 = (s32[32768]{0:T(1024)}, s32[32768]{0:T(1024)S(1)}) sort(s32[32768]{0:T(1024)S(1)} %reshape.112, s32[32768]{0:T(1024)S(1)} %iota.3), dimensions={0}, is_stable=true, to_apply=%region_7.10'],
    "neither": ['%flash_fwd.2 = (f32[16,4096,128]{2,1,0:T(8,128)S(1)}, f32[16,4096,1]{2,1,0:T(8,128)}) custom-call(f32[16,4096,128]{2,1,0:T(8,128)} %maximum_bitcast_fusion), custom_call_target="tpu_custom_call"',
                '%fusion.18 = f32[4096,2048]{1,0:T(8,128)S(1)} fusion(f32[50304,2048]{1,0:T(8,128)} %state_params__tok_embed____embedding__.1, s32[4096]{0:T(1024)S(1)} %broadcast_clamp_fusion.6), kind=kCustom, calls=%fused_computation.18',
                '%sort.1 = (f32[4096,64]{0,1:T(8,128)}, s32[4096,64]{0,1:T(8,128)S(1)}) sort(f32[4096,64]{0,1:T(8,128)S(1)} %get-tuple-element.211, s32[4096,64]{0,1:T(8,128)S(1)} %iota.1), dimensions={1}, is_stable=true',
                '%add_any.49 = f32[32768,2048]{1,0:T(8,128)} add(f32[32768,2048]{1,0:T(8,128)} %moe_gmm_dlhs.7, f32[32768,2048]{1,0:T(8,128)} %moe_gmm_dlhs.8)',
                '%multiply_add_fusion = (f32[64,2048,1024]{2,1,0:T(8,128)}, f32[64,2048,1024]{2,1,0:T(8,128)}) fusion(f32[64,2048,1024]{2,1,0:T(8,128)} %moe_gmm_drhs.7), kind=kLoop, calls=%fused_computation.2'],
}


@pytest.mark.parametrize("metric,kind", [
    ("moe_gmm_ms_per_step", "gmm"), ("moe_gmm_roofline", "gmm"),
    ("moe_dispatch_ms_per_step", "dispatch"),
    ("flash_fwd_ms_per_step", None), ("flash_bwd_ms_per_step", None)])
def test_trace_patterns_find_their_ops_and_no_others(metric, kind):
    rx = re.compile(FILES.json("layer_metrics", metric + ".json")
                    ["params"]["pattern"])
    for k, texts in TRACE_TEXTS.items():
        for text in texts:
            hit = bool(rx.search(text))
            if kind is None:        # the flash patterns keep off the MoE ops
                assert hit == (k == "neither" and text.startswith("%flash_fwd")
                               and metric == "flash_fwd_ms_per_step"), text
            else:
                assert hit == (k == kind), text
