"""The SmallThinker configuration, its mix, driver, cost functions and trace
patterns, held to each other and to the catalog the configuration was copied
from (where this machine has it)."""

import json
import os
import re
import types

import pytest

import harness

FILES = harness.Files()
CONFIG = FILES.json("configs", "smallthinker_21b_a3b.json")
TRAFFIC = FILES.json("traffic", "s16384_1chip.json")
BENCH = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "smallthinker_s16384_1chip"


def _args(argv):
    return dict(zip(argv[::2], argv[1::2]))


def test_program_args_say_what_the_configurations_keys_say():
    a = _args(CONFIG["program_args"])
    assert a["--lm-arch"] == "smallthinker" and a["--lm-parallelism"] == "ep"
    assert int(a["--lm-d-model"]) == CONFIG["hidden_size"] == 2560
    assert int(a["--lm-layers"]) == CONFIG["num_hidden_layers"] == 4
    assert int(a["--lm-heads"]) == CONFIG["num_attention_heads"] == 28
    assert int(a["--lm-kv-heads"]) == CONFIG["num_key_value_heads"] == 4
    assert int(a["--lm-head-dim"]) == CONFIG["head_dim"] == 128
    assert int(a["--lm-vocab"]) == CONFIG["vocab_size"]
    assert int(a["--lm-experts"]) \
        == CONFIG["moe_num_primary_experts_published"] == 64
    assert int(a["--lm-experts-held"]) == CONFIG["experts_held"] \
        == CONFIG["moe_num_primary_experts"] == 16
    assert int(a["--lm-moe-top-k"]) \
        == CONFIG["moe_num_active_primary_experts"] == 6
    assert int(a["--lm-ffn-dim"]) == CONFIG["moe_ffn_hidden_size"] == 768
    assert a["--lm-attention"] == "flash"
    t = _args(TRAFFIC["args"])
    assert int(t["--lm-seq-len"]) == CONFIG["max_position_embeddings"] == 16384
    assert int(t["--batch-size"]) == 1


def test_reduced_and_the_held_values_agree():
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {"num_hidden_layers": 52,
                                   "moe_num_primary_experts": 64,
                                   "vocab_size": 151936}
    for key in CONFIG["reduced"]:
        assert CONFIG[key] < CONFIG["published"][key], key
    # the floors: a whole period of the layer pattern, at least 8 routed
    # experts, at least an eighth of the vocabulary
    n = CONFIG["num_hidden_layers"]
    assert CONFIG["sliding_window_layout"][:n] == [0, 1, 1, 1] \
        == CONFIG["rope_layout"][:n]
    assert CONFIG["moe_num_primary_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CONFIG["published"]["vocab_size"]
    assert CONFIG["published"]["vocab_size"] % CONFIG["vocab_size"] == 0


def test_the_arch_row_says_what_the_published_keys_say():
    from ps_pytorch_tpu.models.transformer import ARCHS
    row = ARCHS["smallthinker"]
    assert row.window == CONFIG["sliding_window_size"] == 4096
    assert list(row.window_layers) == CONFIG["sliding_window_layout"][:4]
    assert list(row.rope_layers) == CONFIG["rope_layout"][:4]
    assert row.rope_theta == CONFIG["rope_theta"] == 1500000
    assert row.norm_eps == CONFIG["rms_norm_eps"] == 1e-6
    assert row.gate_norm is CONFIG["norm_topk_prob"] is True
    assert row.z_loss_coef == CONFIG["z_loss_coef_as_run"] == 0.0
    assert row.rms_norm and row.dropless and row.early_router
    assert row.expert_act == "relu" and not row.qk_norm
    assert [CONFIG["sliding_window_layout"][i] for i in range(52)] \
        == [int(row.layer_window(i) is not None) for i in range(52)]
    assert [CONFIG["rope_layout"][i] for i in range(52)] \
        == [int(row.layer_rope(i)) for i in range(52)]


def test_every_published_key_is_carried_unchanged_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"]
    if not row:
        pytest.skip("this machine's catalog has no SmallThinker row")
    assert CONFIG["source"] == row[0]["source_url"]
    for key, value in row[0]["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] < value
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key


def test_reference_counts_the_published_model_and_the_cut():
    ref = FILES.module("reference", "smallthinker_21b_a3b.py")
    published = dict(CONFIG, **CONFIG["published"], experts_held=64)
    assert ref.param_count(published) == CONFIG["parameters_published"] \
        == 21_506_562_560
    layer = 21_140_480 + 16 * 5_898_240
    assert ref.param_count(CONFIG) == CONFIG["parameters_as_run"] \
        == 4 * layer + 2 * CONFIG["vocab_size"] * 2560 + 2560
    assert ref.param_count(dict(CONFIG, vocab_size=37984)) == 656_529_920
    d, f = 2560, 768
    macs = 4 * (2 * d * 3584 + 2 * d * 512 + d * 64 + 1.5 * 3 * d * f) \
        + 2 * 3584 * (8192.5 + 3 * 3584.125) + d * CONFIG["vocab_size"]
    assert ref.train_flops_per_sample(CONFIG, seq_len=16384, batch=1) \
        == pytest.approx(6 * macs, rel=1e-12)


def _trainer():
    cfg = types.SimpleNamespace(
        batch_size=1, lm_seq_len=16384, lm_heads=28, lm_kv_heads=4,
        lm_head_dim=128, lm_d_model=2560, lm_layers=4, lm_experts=64,
        lm_experts_held=16, lm_moe_top_k=6, lm_ffn_dim=768,
        lm_arch="smallthinker", log_every=1)
    return types.SimpleNamespace(cfg=cfg)


def _shape(**kw):
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    return dict(driver.shape(_trainer()), activation_dtypes=["bfloat16"], **kw)


def test_driver_shape_says_heads_windows_and_the_share():
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    assert driver.THROUGHPUT == "tokens_per_s"
    assert driver.samples_per_step(_trainer()) == 16384
    shape = _shape()
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"]) \
        == (28, 4, 128)
    assert shape["windows"] == [0, 4096, 4096, 4096]
    assert (shape["experts"], shape["experts_held"], shape["top_k"]) \
        == (64, 16, 6)
    short = _trainer()
    short.cfg.lm_seq_len = 4096          # the window never closes
    assert driver.shape(short)["windows"] == [0, 0, 0, 0]


def test_flash_costs_against_hand_counts_at_a_small_shape():
    causal = FILES.module("kernel_costs", "flash_attention_gqa_causal.py")
    window = FILES.module("kernel_costs", "flash_attention_gqa_window.py")
    small = dict(batch=2, seq_len=8, heads=4, kv_heads=2, head_dim=16,
                 windows=[0, 3, 3], activation_dtypes=["bfloat16", "float32"])
    # causal: 8 * 9 / 2 = 36 pairs a head; window of 3: 1 + 2 + 6 * 3 = 21
    assert causal.pairs(8, 0) == 36 and causal.pairs(8, 3) == 21
    assert causal.pairs(8, 100) == 36
    tensors = 6 * 2 * (4 + 2) * 8 * 16 * 4 + 3 * 2 * 4 * 8 * 4
    assert causal.required_per_step(small) \
        == (6 * 2 * 4 * 36 * 16 * 2, tensors)
    assert window.required_per_step(small) \
        == (2 * 6 * 2 * 4 * 21 * 16 * 2, 2 * tensors)
    # the cell: each counts its own kind of layer only, K and V once a kv head
    flops_c, bytes_c = causal.required_per_step(_shape())
    flops_w, bytes_w = window.required_per_step(_shape())
    assert flops_c == 6 * 28 * (16384 * 16385 // 2) * 128 * 2
    assert flops_w == 3 * 6 * 28 * 58_722_304 * 128 * 2
    assert bytes_w == 3 * bytes_c == 3 * (
        6 * (28 + 4) * 16384 * 128 * 2 + 3 * 28 * 16384 * 4)
    none = _shape(windows=[0, 0, 0, 0])
    assert window.required_per_step(none) == (0, 0)


def test_held_grouped_matmul_cost_against_a_hand_count():
    cost = FILES.module("kernel_costs", "moe_grouped_matmul_held.py")
    flops, nbytes = cost.required_per_step(_shape())
    rows = 16384 * 6 // 4
    assert flops == 4 * 9 * 2 * rows * 2560 * 768
    assert nbytes == 4 * 9 * (16 * 2560 * 768 * 4 + rows * (2560 + 768) * 2)
    every = FILES.module("kernel_costs", "moe_grouped_matmul.py")
    assert every.required_per_step(_shape())[0] == 4 * flops


# HLO texts as the v5e's compiler names them at the cell's shape (compiled for
# a described v5e, PR 29; the dispatch fusions as PR 25's trace named OLMoE's).
TRACE_TEXTS = {
    "window": ['%flash_win_fwd.3 = (bf16[28,16384,128]{2,1,0:T(8,128)(2,1)}, f32[28,32,1,512]{3,2,1,0:T(1,128)}) custom-call(bf16[28,16384,128]{2,1,0:T(8,128)(2,1)} %bitcast.1, bf16[4,16384,128]{2,1,0:T(8,128)(2,1)} %bitcast.2), custom_call_target="tpu_custom_call"',
               '%flash_win_bwd_dkv = (f32[4,28,16384,128]{3,2,1,0:T(8,128)}, bf16[4,16384,128]{2,1,0:T(8,128)(2,1)}, bf16[4,16384,128]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[28,16384,128]{2,1,0:T(8,128)(2,1)} %bitcast.9), custom_call_target="tpu_custom_call"'],
    "global": ['%flash_fwd = (bf16[28,16384,128]{2,1,0:T(8,128)(2,1)}, f32[28,32,1,512]{3,2,1,0:T(1,128)}) custom-call(bf16[28,16384,128]{2,1,0:T(8,128)(2,1)} %bitcast.1), custom_call_target="tpu_custom_call"',
               '%flash_bwd_dkv.1 = (f32[4,28,16384,128]{3,2,1,0:T(8,128)}, bf16[4,16384,128]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[28,16384,128]{2,1,0:T(8,128)(2,1)} %bitcast.9), custom_call_target="tpu_custom_call"'],
    "gmm": ['%moe_gmm_fwd.10 = bf16[36864,768]{1,0:T(8,128)(2,1)} custom-call(s32[18]{0:T(128)S(1)} %copy-done.251, bf16[36864,2560]{1,0:T(8,128)(2,1)} %fusion.8), custom_call_target="tpu_custom_call"',
            '%moe_gmm_dlhs.6 = bf16[36864,2560]{1,0:T(8,128)(2,1)} custom-call(s32[18]{0:T(128)S(1)} %copy-done.252), custom_call_target="tpu_custom_call"',
            '%moe_gmm_drhs = f32[16,2560,768]{2,1,0:T(8,128)} custom-call(s32[17]{0:T(128)S(1)} %pad_add_fusion.6), custom_call_target="tpu_custom_call"'],
    "dispatch": ['%fusion.29 = f32[16384,2560]{1,0:T(8,128)S(1)} fusion(f32[16384,2560]{1,0:T(8,128)S(1)} %copy-done.29, s32[36864]{0:T(1024)} %get-tuple-element.132, f32[36864,2560]{1,0:T(8,128)} %add_any.69), kind=kCustom, calls=%fused_computation.103',
                 '%fusion.15 = bf16[36864,2560]{1,0:T(8,128)(2,1)} fusion(bf16[16384,2560]{1,0:T(8,128)(2,1)S(1)} %copy.148, s32[36864]{0:T(1024)S(1)} %copy-done.71), kind=kCustom, calls=%fused_computation.15',
                 '%fusion.23 = s32[64]{0:T(128)S(1)} fusion(s32[98304]{0:T(1024)S(1)} %bitcast.361, s32[]{:T(128)} %constant.176), kind=kCustom, calls=%fused_computation.585',
                 '%sort.8 = (s32[98304]{0:T(1024)}, s32[98304]{0:T(1024)S(1)}) sort(s32[98304]{0:T(1024)S(1)} %reshape.112, s32[98304]{0:T(1024)S(1)} %iota.3), dimensions={0}, is_stable=true, to_apply=%region_7.10'],
    "neither": ['%fusion.18 = bf16[16384,2560]{1,0:T(8,128)(2,1)} fusion(f32[18992,2560]{1,0:T(8,128)} %state_params__tok_embed____embedding__.1, s32[16384]{0:T(1024)S(1)} %broadcast_clamp_fusion.6), kind=kCustom, calls=%fused_computation.18',
                '%sort.1 = (f32[16384,64]{0,1:T(8,128)}, s32[16384,64]{0,1:T(8,128)S(1)}) sort(f32[16384,64]{0,1:T(8,128)S(1)} %get-tuple-element.211, s32[16384,64]{0,1:T(8,128)S(1)} %iota.1), dimensions={1}, is_stable=true',
                '%add_any.49 = bf16[36864,2560]{1,0:T(8,128)(2,1)} add(bf16[36864,2560]{1,0:T(8,128)(2,1)} %moe_gmm_dlhs.7, bf16[36864,2560]{1,0:T(8,128)(2,1)} %moe_gmm_dlhs.8)',
                '%fusion.31 = bf16[28,16384,128]{2,1,0:T(8,128)(2,1)} fusion(f32[4,28,16384,128]{3,2,1,0:T(8,128)} %flash_win_bwd_dkv.2), kind=kLoop, calls=%fused_computation.31'],
}
PATTERNS = {
    "flash_win_ms_per_step": {"window"}, "flash_win_roofline": {"window"},
    "flash_gqa_roofline": {"global"},
    "flash_fwd_ms_per_step": {"global"}, "flash_bwd_ms_per_step": {"global"},
    "moe_gmm_held_roofline": {"gmm"}, "moe_gmm_ms_per_step": {"gmm"},
    "moe_dispatch_held_ms_per_step": {"dispatch"},
}


@pytest.mark.parametrize("metric", sorted(PATTERNS))
def test_trace_patterns_find_their_ops_and_no_others(metric):
    rx = re.compile(FILES.json("layer_metrics", metric + ".json")
                    ["params"]["pattern"])
    for kind, texts in TRACE_TEXTS.items():
        for text in texts:
            hit = bool(rx.search(text))
            if metric == "flash_fwd_ms_per_step":
                assert hit == text.startswith("%flash_fwd"), text
            elif metric == "flash_bwd_ms_per_step":
                assert hit == text.startswith("%flash_bwd_dkv"), text
            else:
                assert hit == (kind in PATTERNS[metric]), text


def test_the_cells_lists_in_the_benchmark():
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("smallthinker_21b_a3b", "s16384_1chip", 1)
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in harness.metrics_for(BENCH, g, CELL)}
    assert {"tokens_per_s", "mfu", "setup_s", "flash_fwd_ms_per_step",
            "flash_bwd_ms_per_step", "moe_gmm_ms_per_step",
            "expert_load_max_over_mean", "moe_dropped",
            "flash_win_ms_per_step", "flash_win_roofline",
            "flash_gqa_roofline", "moe_gmm_held_roofline",
            "moe_dispatch_held_ms_per_step", "moe_held_share"} <= reports
    # their patterns or costs know equal heads, one mask, every expert held
    # or another cell's row count: a `benchmark` PR's to mend
    assert not {"flash_ms_per_step", "flash_roofline", "moe_gmm_roofline",
                "moe_dispatch_ms_per_step", "images_per_s"} & reports
    for name in ("flash_win_ms_per_step", "flash_win_roofline",
                 "flash_gqa_roofline", "moe_gmm_held_roofline",
                 "moe_dispatch_held_ms_per_step", "moe_held_share"):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        spec = FILES.json("layer_metrics", name + ".json")
        FILES.module("readers", spec["reader"] + ".py")
        if "cost" in spec["params"]:
            FILES.module("kernel_costs", spec["params"]["cost"] + ".py")


def test_roofline_reader_returns_nothing_where_nothing_matches():
    """A program without the window kernels (the parent, or a mix whose
    window never closes) gives no number and raises nothing."""
    reader = FILES.module("readers", "roofline.py")
    chip = types.SimpleNamespace(matching_seconds=lambda pattern, window: 0.0)
    run = types.SimpleNamespace(steady=lambda: (chip, (0.0, 1.0), 3))
    spec = FILES.json("layer_metrics", "flash_win_roofline.json")
    assert reader.read(run, **spec["params"]) is None
