"""The Granite-4.0-H-Small configuration, its mix, driver and reference
counts, held to each other, to the catalog the configuration was copied from
(where this machine has it) and to the program's own jaxpr at a tiny size."""

import json
import os
import types

import pytest

import harness
from conftest import BENCH as BENCH_DIR

FILES = harness.Files()
CONFIG = FILES.json("configs", "granite_4_0_h_small.json")
TRAFFIC = FILES.json("traffic", "tp8_share_1chip.json")
BENCH = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "granite4h_small_tp8_1chip"
REF = FILES.module("reference", "granite_4_0_h_small.py")
NEW_METRICS = ("mixer_held_share",)
JOINED = ("dev_attn_core_ms_per_step", "dev_attn_proj_ms_per_step",
          "dev_attn_pos_ms_per_step", "dev_embed_ms_per_step",
          "dev_head_ms_per_step", "dev_loss_ms_per_step",
          "dev_moe_route_ms_per_step", "dev_moe_dispatch_ms_per_step",
          "dev_moe_experts_ms_per_step", "dev_moe_shared_ms_per_step",
          "dev_recompute_ms_per_step", "dev_ssm_proj_ms_per_step",
          "dev_ssm_conv_ms_per_step", "dev_ssd_core_ms_per_step",
          "ssd_core_roofline", "ssd_state_abs_max", "flash_fwd_ms_per_step",
          "flash_bwd_ms_per_step", "flash_gqa_roofline",
          "moe_gmm_ms_per_step", "moe_gmm_held_roofline",
          "expert_load_max_over_mean", "moe_dropped", "moe_held_share",
          "moe_load_all_max_over_mean")
HEAD_KEYS = ("mamba_n_heads", "num_attention_heads", "num_key_value_heads")


def _args(argv):
    return dict(zip(argv[::2], argv[1::2]))


def test_the_argv_is_what_the_cell_says():
    a = _args(CONFIG["program_args"])
    of = CONFIG["mixer_share"][1]
    assert a["--lm-arch"] == "granite4h" and a["--lm-parallelism"] == "ep"
    assert int(a["--lm-d-model"]) == CONFIG["hidden_size"] == 4096
    assert int(a["--lm-layers"]) == CONFIG["num_hidden_layers"] == 10
    # the flags keep the model's counts; the configuration's keys the held ones
    assert int(a["--lm-mixer-shares"]) == of == 8
    assert int(a["--lm-heads"]) == CONFIG["num_attention_heads"] * of == 32
    assert int(a["--lm-kv-heads"]) == CONFIG["num_key_value_heads"] * of == 8
    assert "--lm-head-dim" not in a         # hidden_size / heads = 128
    assert int(a["--lm-ffn-dim"]) == CONFIG["intermediate_size"] == 768
    assert int(a["--lm-experts"]) == CONFIG["num_local_experts_published"] \
        == 72
    assert int(a["--lm-experts-held"]) == CONFIG["experts_held"] \
        == CONFIG["num_local_experts"] == 9
    assert int(a["--lm-moe-top-k"]) == CONFIG["num_experts_per_tok"] == 10
    assert int(a["--lm-vocab"]) == CONFIG["vocab_size"] == 12544
    assert a["--lm-attention"] == "flash" and a["--remat"] == "true"
    assert a["--compute-dtype"] == "bfloat16" and a["--momentum"] == "0.9"
    assert float(a["--lr"]) in (0.01, 0.03, 0.1)
    t = _args(TRAFFIC["args"])
    # rule (b) of cut.rule halved the issue's 16,384
    assert int(t["--lm-seq-len"]) == 8192 <= CONFIG["max_position_embeddings"]
    assert int(t["--batch-size"]) == 1 and TRAFFIC["trace_steps"] == 6
    assert "rule (b)" in TRAFFIC["why"] and "(b)" in CONFIG["cut"]["found"]
    cell = harness.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("granite_4_0_h_small", "tp8_share_1chip", 1)
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    assert driver.FIXED_ARGS == ["--eval-freq", "0", "--resume", "false"]
    assert driver.THROUGHPUT == "tokens_per_s"


def test_every_published_key_is_carried_unchanged_but_the_reduced_ones():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_local_experts",
                                 "vocab_size", *HEAD_KEYS]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/granite_4_0_h_small.json"
    assert CONFIG["published"] == {
        "num_hidden_layers": 40, "num_local_experts": 72,
        "vocab_size": 100352, "mamba_n_heads": 128,
        "num_attention_heads": 32, "num_key_value_heads": 8}
    for key in ("vocab_size", "num_local_experts", *HEAD_KEYS):
        assert CONFIG[key] * 8 == CONFIG["published"][key], key
    assert CONFIG["num_local_experts_published"] == 72
    # no width is reduced: the shared expert's key keeps the published 1536,
    # the channels held are said beside it
    assert CONFIG["shared_intermediate_size"] == 1536 \
        == 8 * CONFIG["shared_channels_held"]
    assert not [k for k in CONFIG["reduced"]
                if "size" in k and k != "vocab_size" or k.endswith("_dim")]
    assert CONFIG["layer_types"][:10] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4 and len(CONFIG["layer_types"]) == 40
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
                   if r["name"] == "granite-4.0-h-small")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_arch_row_says_what_the_published_keys_say():
    from ps_pytorch_tpu.models.transformer import ARCHS
    row = ARCHS["granite4h"]
    assert row.norm_eps == CONFIG["rms_norm_eps"]
    assert (row.embed_multiplier, row.attn_scale, row.residual_scale,
            row.logits_divisor) == tuple(CONFIG[k] for k in (
                "embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling"))
    assert (row.ssm_heads, row.ssm_head_dim, row.ssm_groups, row.ssm_state,
            row.ssm_conv, row.ssm_chunk) == (
        CONFIG["published"]["mamba_n_heads"], *(CONFIG[k] for k in (
            "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
            "mamba_chunk_size")))
    assert row.ssm_heads * row.ssm_head_dim \
        == CONFIG["mamba_expand"] * CONFIG["hidden_size"]
    assert row.shared_width == CONFIG["shared_intermediate_size"]
    assert row.tied_head == CONFIG["tie_word_embeddings"]
    assert row.aux_coef == CONFIG["router_aux_loss_coef"]
    assert [row.layer_kind(i) == "mamba2" for i in range(40)] \
        == [t == "mamba" for t in CONFIG["layer_types"]]


def test_the_cells_name_is_in_the_lists_that_read_it():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g] if CELL in m.get("workloads", [])}
    assert listed == {"tokens_per_s", *JOINED, *NEW_METRICS}
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in harness.metrics_for(BENCH, g, CELL)}
    assert {"tokens_per_s", "mfu", "setup_s"} <= reports
    assert BENCH["per_layer"][-1]["name"] == "mixer_held_share"     # appended
    assert BENCH["per_layer"][-1]["workloads"] == [CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG["name"]
    spec = FILES.json("layer_metrics", "mixer_held_share.json")
    assert (spec["reader"], spec["params"]) == (
        "jsonl_field", {"field": "mixer_held_share"})
    # the parent's program logs no such field: the reader returns None and
    # the result line leaves the metric out
    reader = FILES.module("readers", "jsonl_field.py")
    run = harness.Run(window_records=[{"loss": 1.0}, {"loss": 0.9}])
    assert reader.read(run, "mixer_held_share") is None
    run = harness.Run(window_records=[{"mixer_held_share": 0.125}] * 3)
    assert reader.read(run, "mixer_held_share") == 0.125
    assert len(BENCH["workloads"]) == 12
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_parameter_counts_by_hand():
    d, v, f = 4096, 12544, 768
    mamba = lambda heads: d * (2 * heads * 64 + 2 * 128 + heads) \
        + heads * 64 * d + 5 * (heads * 64 + 256) + 3 * heads + heads * 64 + d
    attention = lambda q, kv: 2 * d * q * 128 + 2 * d * kv * 128 + d
    half = lambda held, shared: held * 3 * d * f + d * 72 + 3 * d * shared + d
    by_kind = CONFIG["parameters_by_kind"]
    assert mamba(16) == 13_708_592 == by_kind["mamba2_mixer_as_run"]
    assert attention(4, 1) == 5_246_976 == by_kind["attention_mixer_as_run"]
    assert half(9, 192) == 87_592_960 == by_kind["expert_half_as_run"]
    assert v * d == by_kind["embedding_as_run"]
    as_run = 9 * mamba(16) + attention(4, 1) + 10 * half(9, 192) + v * d + d
    assert REF.param_count(CONFIG) == as_run == CONFIG["parameters_as_run"] \
        == 1_055_938_224
    assert mamba(128) == 102_291_072 and attention(32, 8) == 41_947_136 \
        and half(72, 1536) == 698_650_624
    published = 36 * mamba(128) + 4 * attention(32, 8) \
        + 40 * half(72, 1536) + 100352 * d + d
    whole = dict(CONFIG, **CONFIG["published"], mixer_share=[0, 1],
                 experts_held=72)
    whole.pop("num_local_experts_published")
    assert REF.param_count(whole) == published \
        == CONFIG["parameters_published"] == 32_207_337_984
    # what a token passes: ten experts and the shared one and a mixer in 40
    # layers: the "A9B"; and the head
    active = 36 * mamba(128) + 4 * attention(32, 8) \
        + 40 * half(10, 1536) + 100352 * d
    assert 8.5e9 < active < 9.5e9


def test_train_flops_closed_form_against_a_count_by_hand():
    """A small size, every term spelled out: d=8; ONE of two chips' share: 2
    held Mamba-2 heads of 4 with 3 states, 4 taps; 1 held query head of 4 on 1
    K/V head; the shared expert's 6 held channels of 12; 4 router outputs of
    which 2 are held, top-2, width 6; vocabulary 11; depth 3 (M, M,
    attention); S=5."""
    small = dict(CONFIG, hidden_size=8, num_attention_heads=1,
                 num_key_value_heads=1, mamba_n_heads=2, mamba_d_head=4,
                 mamba_d_state=3, intermediate_size=6,
                 shared_intermediate_size=12, mixer_share=[0, 2],
                 num_local_experts=2, num_local_experts_published=4,
                 experts_held=2, num_experts_per_tok=2, vocab_size=11,
                 num_hidden_layers=3,
                 layer_types=["mamba", "mamba", "attention"])
    s = 5
    mamba = 8 * (8 + (8 + 6) + 2) + 8 * 8 + 5 * 14    # in, out, conv with its bias
    macs = {"mamba2_projections": 2 * mamba,
            "mamba2_recurrence": 2 * (2.5 * 8 * 3 + 1.5 * 8),
            "projections": 2 * 8 * 4 + 2 * 8 * 4,     # q, o; k, v: one head each
            "attention": 2 * 4 * (s + 1) / 2,     # two products a causal pair
            "shared": 3 * (3 * 8 * 6),
            "router": 3 * 8 * 4,
            "experts": 3 * (2 / 4) * (2 * 3 * 8 * 6),   # k x held / E experts a token, three matmuls
            "head": 8 * 11}
    assert REF.macs_per_token(small, s) == pytest.approx(macs)
    assert REF.train_flops_per_sample(small, s) == \
        pytest.approx(6 * sum(macs.values()))
    # at the issue's S = 16,384: 33.0 TFLOP a step, the Mamba-2 projections
    # 12.1, the routed experts 11.6, the shared expert 2.3, the head 5.05
    per_token = REF.macs_per_token(CONFIG, 16384)
    step = lambda k: 6 * per_token[k] * 16384 / 1e12
    assert 6 * sum(per_token.values()) * 16384 == pytest.approx(33.01e12,
                                                                rel=1e-3)
    assert step("mamba2_projections") == pytest.approx(12.12, rel=1e-3)
    assert step("experts") == pytest.approx(11.60, rel=1e-3)
    assert step("shared") == pytest.approx(2.319, rel=1e-3)
    assert step("head") == pytest.approx(5.051, rel=1e-3)
    # ... and at the cell's S = 8,192: 16.3 TFLOP a step, 1.99 GFLOP a token
    assert REF.train_flops_per_sample(CONFIG, 8192) * 8192 \
        == pytest.approx(16.30e12, rel=1e-3)
    cost = FILES.module("kernel_costs", "ssd.py")
    assert cost.FLOPS_PER_STATE == 2 * 2.5
    assert REF.recurrence_macs_per_token(CONFIG) \
        == 2.5 * 16 * 64 * 128 + 1.5 * 16 * 64


def test_forward_flops_closed_form_against_the_programs_jaxpr():
    """The walk of the program's jaxpr (``utils/flops.py``) at a tiny size,
    ONE of two chips' share: it finds the closed form's projections, shared
    expert, router and head; attention dense S x S (``full_attention``
    multiplies what it then masks), the routed experts on every sorted row the
    held part is sized for, and, for the recurrence, the chunked kernel's own
    matmuls (counted apart, from the kernel alone at the layer's shape). With
    those three parts exchanged the forward agrees exactly."""
    import jax
    import jax.numpy as jnp

    from ps_pytorch_tpu.models import transformer as tr_mod
    from ps_pytorch_tpu.models.moe import MoETransformerLM
    from ps_pytorch_tpu.ops.ssd import ssd
    from ps_pytorch_tpu.utils.flops import count_jaxpr_flops

    s, b = 32, 2
    tiny = dict(CONFIG, hidden_size=32, num_attention_heads=2,
                num_key_value_heads=1, mamba_n_heads=4, mamba_d_head=8,
                mamba_d_state=16, mamba_chunk_size=32, intermediate_size=16,
                shared_intermediate_size=32, mixer_share=[0, 2],
                num_local_experts=4, num_local_experts_published=8,
                experts_held=4, num_experts_per_tok=3, vocab_size=97,
                num_hidden_layers=3,
                layer_types=["mamba", "attention", "mamba"])
    row = tr_mod.ARCHS["granite4h"]
    tr_mod.ARCHS["granite4h"] = row._replace(
        ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_chunk=32,
        shared_width=32, mixer_layers=("mamba2", "attention", "mamba2"))
    try:
        model = MoETransformerLM(
            vocab_size=97, n_layers=3, n_heads=4, kv_heads=2, d_model=32,
            max_seq_len=s, arch="granite4h", ffn_dim=16, n_experts=8,
            top_k=3, experts_held=4, mixer_shares=2)
        tokens = jnp.zeros((b, s), jnp.int32)
        variables = model.init(jax.random.key(0), tokens)
        walked = count_jaxpr_flops(jax.make_jaxpr(
            lambda v: model.apply(v, tokens)[0])(variables).jaxpr)
        f32 = jnp.float32
        kernel = count_jaxpr_flops(jax.make_jaxpr(
            lambda *a: ssd(*a, chunk=32)[0])(
                jnp.zeros((b, s, 4, 8)), jnp.zeros((b, s, 4), f32),
                jnp.zeros((4,), f32), jnp.zeros((b, s, 1, 16)),
                jnp.zeros((b, s, 1, 16)), jnp.zeros((4,), f32)).jaxpr)
    finally:
        tr_mod.ARCHS["granite4h"] = row
    parts = REF.macs_per_token(tiny, s)
    assert parts["shared"] == 3 * 3 * 32 * 16
    assert parts["experts"] == 3 * 3 * (4 / 8) * 3 * 32 * 16
    assert parts["head"] == 32 * 97
    # the held part's rows: 1.5 x T*k*held/E in whole tiles of 512, capped at T*k
    rows = min(b * s * 3, 512)
    exchanged = dict(parts, attention=2 * 16 * s,
                     experts=3 * rows / (b * s) * 3 * 32 * 16,
                     mamba2_recurrence=0.0,
                     # the convolution is no matmul
                     mamba2_projections=parts["mamba2_projections"]
                     - 2 * 5 * (32 + 2 * 16))
    assert kernel > 0
    assert walked == 2 * sum(exchanged.values()) * tokens.size + 2 * kernel


def test_the_driver_says_what_the_cost_functions_need():
    from ps_pytorch_tpu.config import config_from_args
    driver = FILES.module("drivers", CONFIG["driver"] + ".py")
    cfg = config_from_args(CONFIG["program_args"] + TRAFFIC["args"])
    shape = driver.shape(types.SimpleNamespace(cfg=cfg))
    # the sizes HELD, where the program's flags say the model's
    assert (cfg.lm_heads, cfg.lm_kv_heads, cfg.lm_mixer_shares) == (32, 8, 8)
    assert (shape["batch"], shape["seq_len"], shape["heads"],
            shape["kv_heads"], shape["head_dim"], shape["layers"]) \
        == (1, 8192, 4, 1, 128, 10)
    assert (shape["windows"], shape["ssd_layers"], shape["ssd_heads"],
            shape["ssd_head_dim"], shape["ssd_state"], shape["ssd_groups"]) \
        == ([0], 9, 16, 64, 128, 1)
    assert shape["ssd_kept_bytes"] == 32 * 16 * 64 * 128 * 4
    assert (shape["experts"], shape["experts_held"], shape["top_k"],
            shape["d_model"], shape["ffn_dim"], shape["expert_layers"],
            shape["shared_width"], shape["mixer_shares"]) \
        == (72, 9, 10, 4096, 768, 10, 192, 8)
    assert driver.samples_per_step(types.SimpleNamespace(cfg=cfg)) == 8192
    assert driver.variables(types.SimpleNamespace(state=types.SimpleNamespace(
        params={"w": 1}))) == {"params": {"w": 1}}
    act = dict(shape, activation_dtypes=["bfloat16", "float32"])
    # one layer of 4 query heads of 128 on one K/V head
    gqa = FILES.module("kernel_costs", "flash_attention_gqa_causal.py")
    flops, _ = gqa.required_per_step(act)
    assert flops == pytest.approx(
        6 * REF.macs_per_token(CONFIG, 8192)["attention"] * 8192, rel=1e-3)
    # three grouped matmuls in three passes over 10,240 rows at balance, in
    # every one of the ten layers
    held = FILES.module("kernel_costs", "moe_grouped_matmul_held.py")
    flops, _ = held.required_per_step(act)
    assert flops == pytest.approx(
        6 * REF.macs_per_token(CONFIG, 8192)["experts"] * 8192, rel=1e-3)
    ssd_cost = FILES.module("kernel_costs", "ssd.py")
    flops, nbytes = ssd_cost.required_per_step(act)
    assert flops == 3 * 9 * 8192 * 16 * 64 * 128 * 5
    peak = FILES.json("peaks.json")["TPU v5 lite"]
    assert nbytes / peak["hbm_bytes_per_s"] > flops / peak["bf16_flops_per_s"]


def test_the_controls_are_the_references_own_attributes():
    controls = harness.load_module(os.path.join(
        BENCH_DIR, "controls", "granite_4_0_h_small.py"))
    assert controls.CELL == CELL
    for name, control in controls.CONTROLS.items():
        assert set(control) <= {"ref", "ref_variables"}, name
        for attr in control.get("ref", {}):
            assert hasattr(REF, attr), (name, attr)
    assert set(CONFIG["reference_check"]["controls"]) \
        == set(controls.CONTROLS)
